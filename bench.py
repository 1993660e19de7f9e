#!/usr/bin/env python
"""Benchmark: ResNet-50 fused training-step throughput (images/sec).

``python bench.py`` IS the measurement: one process that holds the chip,
takes the numbers and prints them as JSON lines (the last line is the
fullest).  It measures an accelerator or nothing: when JAX finds no TPU
it exits non-zero within seconds and prints no result, and a secondary
phase that fails makes the exit code non-zero after the line is printed.
A CPU number is never written under these metric names.
(``chip_smoke.py`` is the quick check that the system starts on the
chip at all; run it first.)

Baseline: the reference's only citable training-throughput figure —
~170 images/sec, ImageNet-22k Inception on 4×GTX-980 data parallel
(reference docs/tutorials/imagenet_full.md:45; BASELINE.md).  Here the
whole step (fwd + bwd + SGD-momentum update, buffers donated) is one XLA
computation over every visible chip, batch sharded dp.

Env knobs: BENCH_BATCH (per-device batch, default 64), BENCH_STEPS
(timed steps, default 20), BENCH_LAYERS (default 50), BENCH_DTYPE,
BENCH_REMAT, BENCH_PEAK_TFLOPS (override chip peak for the MFU figure).
"""
import json
import os
import sys
import time

BASELINE_IMAGES_PER_SEC = 170.0

# bf16 peak TFLOPs per chip, keyed on substrings of jax device_kind
# (matched case-insensitively on the raw AND space-stripped string: the
# real chip reports "TPU v5 lite", which must hit the v5e entry — the
# silent r2 MFU:null bug).  Sources: public TPU/GPU spec sheets.
_PEAK_TFLOPS = [
    ("v6e", 918.0), ("v6", 918.0),
    ("v5p", 459.0), ("v5e", 197.0), ("v5lite", 197.0),
    ("v4", 275.0), ("v3", 123.0), ("v2", 45.0),
    ("H100", 989.0), ("A100", 312.0),
]

# int8 peak TOPS per chip: generations with an int8 MXU mode double the
# bf16 rate (v5e/v6e/H100/A100 per spec sheets); earlier TPUs run int8
# operands through the bf16 pipe at the bf16 rate, so the entry equals
# the bf16 peak — pricing a quantized kernel there stays honest instead
# of silently optimistic
_PEAK_TFLOPS_INT8 = [
    ("v6e", 1836.0), ("v6", 1836.0),
    ("v5p", 918.0), ("v5e", 394.0), ("v5lite", 394.0),
    ("v4", 275.0), ("v3", 123.0), ("v2", 45.0),
    ("H100", 1979.0), ("A100", 624.0),
]

# fp8 (e4m3/e5m2) peak TFLOPs: only chips with a native fp8 MXU path
# are listed; everything else falls back to the bf16 table (fp8 storage
# still halves the weight bytes, compute runs at the wide rate)
_PEAK_TFLOPS_FP8 = [
    ("v6e", 1836.0), ("v6", 1836.0),
    ("H100", 1979.0),
]

# HBM bandwidth GB/s per chip (public spec sheets), for the achieved-
# bytes/s roofline sanity number (VERDICT r4: measure, don't estimate)
_PEAK_HBM_GBPS = [
    ("v6e", 1640.0), ("v6", 1640.0),
    ("v5p", 2765.0), ("v5e", 819.0), ("v5lite", 819.0),
    ("v4", 1228.0), ("v3", 900.0), ("v2", 700.0),
    ("H100", 3350.0), ("A100", 2039.0),
]


def _lookup_peak(table, device_kind):
    """Match device_kind against a (key, value) spec table, case- and
    separator-insensitively ("TPU v5 lite" must hit "v5lite" — the
    silent r2 MFU:null bug)."""
    kind = str(device_kind).lower()
    flat = kind.replace(" ", "").replace("-", "")
    for key, val in table:
        k = key.lower()
        if k in kind or k.replace(" ", "") in flat:
            return val
    return None


def _lookup_peak_hbm(device_kind):
    """Peak HBM GB/s for the chip, or (None, note)."""
    if os.environ.get("BENCH_PEAK_HBM_GBPS"):
        return float(os.environ["BENCH_PEAK_HBM_GBPS"]), None
    val = _lookup_peak(_PEAK_HBM_GBPS, device_kind)
    if val is not None:
        return val, None
    return None, ("unknown device_kind %r: set BENCH_PEAK_HBM_GBPS to get "
                  "an hbm_util figure" % str(device_kind))


def _lookup_peak_tflops(device_kind, dtype=None):
    """Peak TFLOPs for the chip at a compute dtype, or (None, note).

    ``dtype`` None/"bf16"/"bfloat16"/"float32" reads the bf16 table
    (the historical behavior); "int8" and "fp8" read their own tables
    (quantized kernels are priced at the rate their MXU mode actually
    sustains).  Env overrides: BENCH_PEAK_TFLOPS, and per-dtype
    BENCH_PEAK_TFLOPS_INT8 / BENCH_PEAK_TFLOPS_FP8.  An fp8-less chip
    falls back to its bf16 peak (storage-only fp8)."""
    dt = str(dtype or "").lower().replace("_e4m3", "").replace("_e5m2", "")
    if dt == "int8":
        if os.environ.get("BENCH_PEAK_TFLOPS_INT8"):
            return float(os.environ["BENCH_PEAK_TFLOPS_INT8"]), None
        val = _lookup_peak(_PEAK_TFLOPS_INT8, device_kind)
        if val is not None:
            return val, None
        return None, ("unknown device_kind %r: set BENCH_PEAK_TFLOPS_INT8 "
                      "to get an MFU figure" % str(device_kind))
    if dt == "fp8":
        if os.environ.get("BENCH_PEAK_TFLOPS_FP8"):
            return float(os.environ["BENCH_PEAK_TFLOPS_FP8"]), None
        val = _lookup_peak(_PEAK_TFLOPS_FP8, device_kind)
        if val is not None:
            return val, None
        # no native fp8 pipe: price at the wide rate
        return _lookup_peak_tflops(device_kind)
    if os.environ.get("BENCH_PEAK_TFLOPS"):
        return float(os.environ["BENCH_PEAK_TFLOPS"]), None
    val = _lookup_peak(_PEAK_TFLOPS, device_kind)
    if val is not None:
        return val, None
    return None, ("unknown device_kind %r: set BENCH_PEAK_TFLOPS to get "
                  "an MFU figure" % str(device_kind))


def _utc_ts():
    """ISO-8601 UTC second stamp of a measurement."""
    return time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())


def _emit(payload):
    _stamp_autotune(payload)
    _stamp_retrace(payload)
    sys.stdout.write(json.dumps(payload) + "\n")
    _emit_telemetry_summary(payload)


def _stamp_autotune(payload):
    """When a run is driven by ``tools/autotune.py --replay``, the
    replay loop exports the manifest config id + manifest hash; stamp
    every BENCH line with them so measured numbers join back to their
    predicted row (docs/perf.md "Autotuning & chip windows").  No-op
    outside a replay window — the keys are simply absent."""
    cfg = os.environ.get("BENCH_AUTOTUNE_CONFIG_ID")
    man = os.environ.get("BENCH_AUTOTUNE_MANIFEST_HASH")
    if cfg:
        payload.setdefault("autotune_config_id", cfg)
    if man:
        payload.setdefault("autotune_manifest_hash", man)
    return payload


def _stamp_retrace(payload):
    """When the retrace sentry is on (``MXTPU_RETRACE_SENTRY=1``),
    stamp the post-warmup retrace count and the divergent-ingredient
    names into every BENCH line so benchdiff (slo.py DIRECTIONS) flags
    any nonzero value.  No-op with the sentry off — keys are simply
    absent."""
    try:
        from mxnet_tpu.observability import retrace as _retrace
        if not _retrace.installed():
            return payload
        st = _retrace.stats()
        payload.setdefault("retraces_after_warmup",
                           st["retraces_after_warmup"])
        payload.setdefault("retrace_attributions",
                           [",".join(a["divergent"])
                            for a in st["attributions"]])
    except Exception:
        pass
    return payload


def _stamp_run_id(payload):
    """Stamp the payload with the telemetry run_id so a BENCH_*.json
    row can be joined against its event log (no-op when telemetry is
    off — the key is simply absent)."""
    try:
        from mxnet_tpu import observability as obs
        if obs.enabled():
            payload["run_id"] = obs.run_id()
    except Exception:
        pass
    return payload


def _emit_telemetry_summary(payload):
    """Mirror the bench result into the event log as a ``summary``
    record and flush, so the telemetry dir is self-contained."""
    try:
        from mxnet_tpu import observability as obs
        if obs.enabled():
            obs.emit("summary", source="bench", **payload)
            obs.flush()
    except Exception:
        pass
    sys.stdout.flush()


def _require_tpu(jax):
    """The devices to measure on, or SystemExit(2) within seconds: a
    bench that falls back to the host measures something nobody
    deploys, under a device metric's name."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.stderr.write(
            "bench.py: no TPU (jax.devices() is %s); nothing measured\n"
            % (devices,))
        raise SystemExit(2)
    return devices


def measure():
    """The measurement.  Returns the process exit code."""
    import numpy as np
    import jax
    devices = _require_tpu(jax)
    from mxnet_tpu.parallel import enable_persistent_cache
    enable_persistent_cache()
    # MXTPU_RETRACE_SENTRY=1: _stamp_retrace adds the attributed
    # post-warmup retrace count to every BENCH line
    from mxnet_tpu.observability import retrace as _retrace_sentry
    _retrace_sentry.maybe_install()
    from mxnet_tpu.models import resnet
    from mxnet_tpu import optimizer as opt_mod
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.trainer import ShardedTrainer

    n_dev = len(devices)
    platform = devices[0].platform
    device_kind = getattr(devices[0], "device_kind", platform)
    per_dev_batch = int(os.environ.get("BENCH_BATCH", "64"))
    steps = int(os.environ.get("BENCH_STEPS", "20"))
    num_layers = int(os.environ.get("BENCH_LAYERS", "50"))
    global_batch = per_dev_batch * n_dev
    # bf16 compute by default (2x MXU rate; f32 master weights) — the
    # policy knob the fp32-only reference never had (SURVEY §7)
    dtype = os.environ.get("BENCH_DTYPE", "bfloat16")
    remat = os.environ.get("BENCH_REMAT", "") not in ("", "0")

    mesh = make_mesh(devices, dp=n_dev)
    sym = resnet.get_symbol(num_classes=1000, num_layers=num_layers)
    rng = np.random.RandomState(0)

    def run_once(per_dev, n_steps):
        """Build + time the fused step at one per-device batch size.
        Returns (images_per_sec, step_time, trainer)."""
        gbatch = per_dev * n_dev
        optimizer = opt_mod.create("sgd", learning_rate=0.1, momentum=0.9,
                                   wd=1e-4, rescale_grad=1.0 / gbatch)
        trainer = ShardedTrainer(sym, optimizer, mesh,
                                 compute_dtype=dtype or None, remat=remat)
        params, opt_state, aux = trainer.init_params(
            {"data": (gbatch, 3, 224, 224)},
            label_shapes={"softmax_label": (gbatch,)})
        batch = trainer.shard_batch({
            "data": rng.rand(gbatch, 3, 224, 224).astype(np.float32),
            "softmax_label": rng.randint(
                0, 1000, size=(gbatch,)).astype(np.float32),
        })
        for _ in range(2):      # warmup (compile)
            params, opt_state, aux, outs = trainer.step(
                params, opt_state, aux, batch)
        jax.block_until_ready(outs)
        t0 = time.perf_counter()
        for _ in range(n_steps):
            params, opt_state, aux, outs = trainer.step(
                params, opt_state, aux, batch)
        jax.block_until_ready(outs)
        dt = time.perf_counter() - t0
        return gbatch * n_steps / dt, dt / n_steps, trainer

    sweep = None
    # default: find the MFU-best batch
    if os.environ.get("BENCH_AUTOTUNE", "1") != "0":
        # short sweep over per-device batch, then full run at the winner
        candidates = [int(x) for x in os.environ.get(
            "BENCH_AUTOTUNE_BATCHES", "64,128,256,512").split(",")]
        sweep = {}
        best_ips = None
        for cand in candidates:
            try:
                ips, st, _tr = run_once(cand, max(3, steps // 4))
                sweep[cand] = round(ips, 1)
            except Exception as exc:  # noqa: BLE001 (OOM at big batch)
                sweep[cand] = "failed: %r" % exc
                continue
            # emit a preliminary line after EVERY completed candidate:
            # if the call's time limit cuts the sweep, a real number is
            # already out (readers take the LAST JSON line, so the
            # final payload supersedes these).  All fields come from
            # the best candidate SO FAR, so the record is
            # self-consistent.
            if best_ips is None or ips > best_ips:
                best_ips, best_st, best_cand = ips, st, cand
            _emit({
                "metric": "resnet%d_train_images_per_sec" % num_layers,
                "value": round(best_ips, 2),
                "unit": "images/sec",
                "vs_baseline": round(best_ips / BASELINE_IMAGES_PER_SEC, 3),
                "platform": platform,
                "device_kind": str(device_kind),
                "n_devices": n_dev,
                "global_batch": best_cand * n_dev,
                "step_time_ms": round(best_st * 1e3, 2),
                "compute_dtype": dtype or "float32",
                "measured_at_utc": _utc_ts(),
                "note": "preliminary (autotune sweep in progress)",
                "batch_sweep": {str(k): v for k, v in sweep.items()},
            })
        survivors = [(v, k) for k, v in sweep.items()
                     if not isinstance(v, str)]
        if survivors:   # else: every candidate failed — keep the default
            per_dev_batch = max(survivors)[1]
            global_batch = per_dev_batch * n_dev

    # BENCH_PROFILE=<dir>: capture a jax profiler trace of the timed loop
    # (the layout/fusion audit the MFU gap analysis needs, VERDICT r3 #1)
    profile_dir = os.environ.get("BENCH_PROFILE")
    if profile_dir:
        with jax.profiler.trace(profile_dir):
            images_per_sec, step_time, trainer = run_once(per_dev_batch,
                                                          steps)
    else:
        images_per_sec, step_time, trainer = run_once(per_dev_batch, steps)

    # MFU = model FLOPs per step / step time / total peak FLOPs.
    # Model FLOPs from XLA's own cost analysis of the compiled step
    # (counts fwd+bwd+update exactly as executed).  Failures are
    # REPORTED, not swallowed — the r2 "mfu": null was two silent holes.
    notes = []
    flops_per_step = None
    bytes_per_step = None
    try:
        cost = trainer.compiled_step_cost_analysis()
        if cost and cost.get("flops"):
            flops_per_step = float(cost["flops"])
        else:
            notes.append("cost_analysis returned %r" % (
                None if not cost else sorted(cost)[:4]))
        if cost and cost.get("bytes accessed"):
            bytes_per_step = float(cost["bytes accessed"])
    except Exception as exc:  # noqa: BLE001
        notes.append("cost_analysis failed: %r" % exc)
    flops_src = "xla_cost_analysis"
    if flops_per_step is None:
        # analytic fallback: ResNet-50 fwd ≈ 4.1e9 FLOPs/img @224², bwd ≈ 2×
        flops_per_step = 3.0 * 4.1e9 * global_batch * (num_layers / 50.0)
        flops_src = "analytic"
    peak, peak_note = _lookup_peak_tflops(device_kind)
    if peak_note:
        notes.append(peak_note)
    mfu = None
    if peak:
        mfu = flops_per_step / step_time / (peak * 1e12 * n_dev)

    donated = None
    try:
        donated = trainer.donation_verified()
    except Exception:
        pass

    # chip-free MXL-R cross-check: the analyzer's static roofline for
    # the same graph, printed next to the measured MFU and mirrored to
    # the event log so the measured-vs-ceiling gap is trackable —
    # bench, mfu_audit and the autotuner all share this one summary
    # path (analysis.roofline.static_ceiling_summary)
    from mxnet_tpu.analysis import static_ceiling_summary
    srep = static_ceiling_summary(
        sym, {"data": (global_batch, 3, 224, 224)},
        device_kind=str(device_kind), compute_dtype=dtype or None,
        emit=True)
    static_ceiling = srep.get("static_mfu_ceiling")
    if srep.get("static_mfu_ceiling_error"):
        notes.append("static roofline failed: %s"
                     % srep["static_mfu_ceiling_error"])

    payload = {
        "metric": "resnet%d_train_images_per_sec" % num_layers,
        "value": round(images_per_sec, 2),
        "unit": "images/sec",
        "vs_baseline": round(images_per_sec / BASELINE_IMAGES_PER_SEC, 3),
        "platform": platform,
        "device_kind": str(device_kind),
        "n_devices": n_dev,
        "global_batch": global_batch,
        "step_time_ms": round(step_time * 1e3, 2),
        "compute_dtype": dtype or "float32",
        "measured_at_utc": _utc_ts(),
        "mfu": round(mfu, 4) if mfu is not None else None,
        "static_mfu_ceiling": (round(static_ceiling, 4)
                               if static_ceiling is not None else None),
        "model_tflops_per_step": round(flops_per_step / 1e12, 3),
        "flops_source": flops_src,
        "donation_ok": donated,
    }
    if bytes_per_step is not None:
        # achieved HBM traffic: XLA's own bytes-accessed figure for the
        # compiled step divided by measured step time — the chip-local
        # roofline sanity number (the ICI analog is unmeasurable on one
        # chip and is NOT faked here)
        hbm_gbps = bytes_per_step / step_time / 1e9
        payload["hbm_bytes_per_step"] = int(bytes_per_step)
        payload["hbm_gbps_achieved"] = round(hbm_gbps, 1)
        peak_hbm, hbm_note = _lookup_peak_hbm(device_kind)
        if peak_hbm:
            payload["hbm_util"] = round(hbm_gbps / (peak_hbm * n_dev), 4)
        elif hbm_note:
            notes.append(hbm_note)
    if notes:
        payload["mfu_notes"] = "; ".join(notes)
    if sweep:
        payload["batch_sweep"] = {str(k): v for k, v in sweep.items()}
    _stamp_run_id(payload)

    # Emit the primary metric NOW: a hang in the secondary measurements
    # below must not cost the number already in hand (readers take the
    # LAST JSON line, so the richer payload wins when the secondaries
    # do complete).
    _emit(payload)

    # secondary metrics (VERDICT r2 #8): the user-facing Module+DataIter
    # path, the allreduce bandwidth, the feed overlap and the LM step.
    # Each failure is recorded in the line AND in the exit code: a run
    # that lost a phase must not read as a pass.
    failed = []
    if os.environ.get("BENCH_SECONDARY", "1") != "0":
        # the user-facing module path runs at the autotuned batch too
        os.environ.setdefault("BENCH_MODULE_BATCH", str(per_dev_batch))
        phases = [("module_path", lambda: _measure_module_path(jax)),
                  ("overlap", lambda: _measure_overlap(jax))]
        if n_dev > 1:   # a one-device psum moves no bytes: not measured
            phases.append(("allreduce", lambda: _measure_allreduce(jax)))
        if os.environ.get("BENCH_TRANSFORMER", "1") != "0":
            phases.append(("transformer",
                           lambda: _measure_transformer(jax)))
        for name, phase in phases:
            try:
                payload.update(phase())
            except Exception as exc:  # noqa: BLE001 — reported, then rc
                import traceback
                traceback.print_exc()
                payload["%s_error" % name] = repr(exc)
                failed.append(name)
        # the number that proves the Module path gives up nothing vs
        # the direct ShardedTrainer loop (target: within 10%)
        if payload.get("module_path_images_per_sec"):
            payload["module_vs_direct"] = round(
                payload["module_path_images_per_sec"] / images_per_sec, 3)
        _emit(payload)
    return 1 if failed else 0


def _measure_module_path(jax):
    """Time the path users actually call: ImageRecordIter (raw records,
    uint8 to device) -> Module.fit fused steps.  train_imagenet-shaped,
    sized down to bound runtime."""
    import tempfile
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import recordio as rio

    # module fused step at MXU rate; f32 master weights
    os.environ.setdefault("MXNET_COMPUTE_DTYPE", "bfloat16")
    per_dev = int(os.environ.get("BENCH_MODULE_BATCH", "64"))
    n_dev = len(jax.devices())
    batch = per_dev * n_dev
    layers = int(os.environ.get("BENCH_MODULE_LAYERS", "50"))
    # >=20 timed batches: enough samples that the module-vs-direct ratio
    # is a measurement, not noise (VERDICT r4 weak #4)
    n_batches = int(os.environ.get("BENCH_MODULE_BATCHES", "20"))

    # synthetic raw .rec: enough records for the timed batches
    import shutil
    tmp = tempfile.mkdtemp()
    try:
        path = os.path.join(tmp, "bench.rec")
        w = rio.MXRecordIO(path, "w")
        rng = np.random.RandomState(0)
        img = rng.randint(0, 255, (3, 224, 224), np.uint8)
        # enough records that the timed loop never crosses an epoch
        # reset (which would measure pipeline-restart cost, not rate)
        n_rec = batch * (n_batches + 4)
        for i in range(n_rec):
            w.write(rio.pack(rio.IRHeader(0, float(i % 1000), i, 0),
                             img.tobytes()))
        w.close()

        it = mx.io.ImageRecordIter(path_imgrec=path,
                                   data_shape=(3, 224, 224),
                                   batch_size=batch, dtype="uint8",
                                   preprocess_threads=4, prefetch_buffer=3)
        from mxnet_tpu.models import resnet
        sym = resnet.get_symbol(num_classes=1000, num_layers=layers)
        ctxs = [mx.tpu(i) for i in range(n_dev)]
        mod = mx.mod.Module(sym, context=ctxs if n_dev > 1 else ctxs[0])
        mod.bind(data_shapes=it.provide_data,
                 label_shapes=it.provide_label)
        mod.init_params(mx.init.Xavier())
        mod.init_optimizer(kvstore="device" if n_dev > 1 else None,
                           optimizer="sgd",
                           optimizer_params={"learning_rate": 0.1,
                                             "momentum": 0.9})

        def batches():
            while True:
                it.reset()
                for b in it:
                    yield b

        def _sync():
            mod.get_outputs()[0].data.block_until_ready()

        gen = batches()
        for _ in range(2):      # warmup/compile
            mod.forward_backward(next(gen))
            mod.update()
        _sync()                 # drain warmup before the timer starts
        t0 = time.perf_counter()
        done = 0
        for b in gen:
            mod.forward_backward(b)
            mod.update()
            done += 1
            if done >= n_batches:
                break
        _sync()
        dt = time.perf_counter() - t0
        return {
            "module_path_images_per_sec": round(batch * done / dt, 2),
            "module_path_batches": done,
            "module_path_fused":
                mod._exec_group.execs[0]._n_fused_step > 0,
        }
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _measure_transformer(jax):
    """Transformer-LM fused-step secondary: tokens/sec + MFU of the
    long-context path (ring-attention-capable MultiHeadAttention,
    models/transformer.py) — the workload class the reference's
    bucketed RNNs never reached.  Tightly bounded: one compile + a few
    steps."""
    import numpy as np
    from mxnet_tpu.models import transformer
    from mxnet_tpu import optimizer as opt_mod
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.trainer import ShardedTrainer

    seq = int(os.environ.get("BENCH_TF_SEQ", "1024"))
    dim = int(os.environ.get("BENCH_TF_DIM", "512"))
    layers = int(os.environ.get("BENCH_TF_LAYERS", "8"))
    vocab = int(os.environ.get("BENCH_TF_VOCAB", "8192"))
    per_dev = int(os.environ.get("BENCH_TF_BATCH", "8"))
    steps = int(os.environ.get("BENCH_TF_STEPS", "6"))

    devices = jax.devices()
    n_dev = len(devices)
    batch = per_dev * n_dev
    mesh = make_mesh(devices, dp=n_dev)
    sym = transformer.get_symbol(vocab_size=vocab, num_layers=layers,
                                 num_heads=8, dim=dim, seq_len=seq)
    optimizer = opt_mod.create("sgd", learning_rate=0.1, momentum=0.9,
                               rescale_grad=1.0 / (batch * seq))
    trainer = ShardedTrainer(sym, optimizer, mesh,
                             compute_dtype="bfloat16")
    params, opt_state, aux = trainer.init_params(
        {"data": (batch, seq)},
        label_shapes={"softmax_label": (batch, seq)})
    rng = np.random.RandomState(0)
    batch_arrays = trainer.shard_batch({
        "data": rng.randint(0, vocab, (batch, seq)).astype(np.int32),
        "softmax_label": rng.randint(0, vocab,
                                     (batch, seq)).astype(np.float32),
    })
    for _ in range(2):
        params, opt_state, aux, outs = trainer.step(
            params, opt_state, aux, batch_arrays)
    jax.block_until_ready(outs)
    t0 = time.perf_counter()
    for _ in range(steps):
        params, opt_state, aux, outs = trainer.step(
            params, opt_state, aux, batch_arrays)
    jax.block_until_ready(outs)
    dt = (time.perf_counter() - t0) / steps
    out = {
        "transformer_tokens_per_sec": round(batch * seq / dt, 1),
        "transformer_step_ms": round(dt * 1e3, 2),
        "transformer_config": "L%d d%d s%d v%d b%d" % (layers, dim, seq,
                                                       vocab, batch),
    }
    # MFU holes are REPORTED, never silent (the r2 lesson, see primary)
    notes = []
    try:
        cost = trainer.compiled_step_cost_analysis()
        peak, peak_note = _lookup_peak_tflops(
            devices[0].device_kind)
        if peak_note:
            notes.append(peak_note)
        if cost and cost.get("flops") and peak:
            out["transformer_mfu"] = round(
                float(cost["flops"]) / dt / (peak * 1e12 * n_dev), 4)
        elif not (cost and cost.get("flops")):
            notes.append("cost_analysis returned %r" % (
                None if not cost else sorted(cost)[:4]))
    except Exception as exc:  # noqa: BLE001
        notes.append("cost_analysis failed: %r" % exc)
    if notes:
        out["transformer_mfu_notes"] = "; ".join(notes)
    return out


def _measure_overlap(jax):
    """Input-pipeline overlap proof (docs/perf.md "Overlap"): a slow
    synthetic feed behind DevicePrefetcher with telemetry routed to a
    scratch dir, then :func:`overlap_report` over the recorded events.
    ``overlap_ratio`` > 1 means the fetch/h2d host time ran UNDER the
    step; the ``data_wait``/``h2d`` p50s show where per-batch host time
    goes.  Wall-clock bounded: ~n_batches × (fetch + step) seconds."""
    import shutil
    import tempfile
    import numpy as np
    from mxnet_tpu import observability as obs
    from mxnet_tpu.observability import events as _ev
    from mxnet_tpu.observability.aggregate import read_events
    from mxnet_tpu.observability.spans import overlap_report
    from mxnet_tpu.parallel.overlap import DevicePrefetcher

    n_batches = int(os.environ.get("BENCH_OVERLAP_BATCHES", "10"))
    fetch_s = float(os.environ.get("BENCH_OVERLAP_FETCH_S", "0.03"))
    tmp = tempfile.mkdtemp(prefix="mxtpu_bench_overlap_")
    saved = {k: os.environ.get(k)
             for k in ("MXTPU_TELEMETRY", "MXTPU_TELEMETRY_DIR")}
    os.environ["MXTPU_TELEMETRY"] = "1"
    os.environ["MXTPU_TELEMETRY_DIR"] = tmp
    try:
        _ev.refresh()
        rng = np.random.RandomState(0)

        def slow_feed():
            while True:
                time.sleep(fetch_s)     # stands in for decode/augment
                yield rng.rand(64, 64).astype(np.float32)

        compute = jax.jit(lambda x: jax.numpy.tanh(x @ x))
        pf = DevicePrefetcher(slow_feed(), place_fn=jax.device_put,
                              name="bench-overlap")
        try:
            # +1: the first step record only bounds the steady-state
            # window (compile exclusion) — it is not counted
            for i in range(n_batches + 1):
                batch = next(pf)
                t0 = time.perf_counter()
                compute(batch).block_until_ready()
                time.sleep(fetch_s)     # stands in for device compute
                obs.record_step(i, time.perf_counter() - t0)
        finally:
            pf.close()
        obs.flush()
        rep = overlap_report(read_events(tmp))
        out = {"overlap_ratio": rep["overlap_ratio"]}
        p50 = rep.get("phase_p50_ms") or {}
        if "data_wait" in p50:
            out["data_wait_ms_p50"] = p50["data_wait"]
        if "h2d" in p50:
            out["h2d_ms_p50"] = p50["h2d"]
        return out
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        _ev.refresh()
        shutil.rmtree(tmp, ignore_errors=True)


def _measure_allreduce(jax):
    """Allreduce bandwidth over every visible device, in-process over
    ICI (the kvstore push/pull -> psum secondary metric, BASELINE.md).
    Needs more than one device: a one-device psum moves no bytes."""
    size = int(os.environ.get("BENCH_ALLREDUCE_BYTES", str(64 << 20)))
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools", "bandwidth"))
    import measure as bw
    n, results = bw.measure_psum([size], repeat=5)
    _size, dt, gbps = results[0]
    return {
        "allreduce_bytes": size,
        "allreduce_time_ms": round(dt * 1e3, 3),
        "allreduce_gbps": round(gbps, 2),
        "allreduce_devices": n,
        "allreduce_platform": jax.devices()[0].platform,
    }


if __name__ == "__main__":
    sys.exit(measure())
