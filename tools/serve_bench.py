#!/usr/bin/env python
"""serve_bench — closed/open-loop load generator for the batching server.

Drives an in-process :class:`mxnet_tpu.serving.ModelServer` over a toy
MLP (or a ``--checkpoint prefix@epoch``) with a weighted request-size
distribution, and prints exactly ONE BENCH-style JSON line:

    {"metric": "serve_throughput_rps", "value": ..., "unit": "req/s",
     "latency_ms": {"p50","p95","p99","mean"}, "occupancy": ...,
     "padding_waste": ..., "lowerings_after_warmup": 0, "buckets": [...],
     "rejected": 0, "mode": "closed", "requests": 200, ...}

Modes:
    closed  (default) ``--concurrency`` workers, each submits its next
            request the moment the previous one completes — measures
            sustainable throughput.
    open    requests arrive on a ``--rate`` schedule regardless of
            completions — measures latency under offered load (and how
            the 429 backpressure behaves past saturation).

Open-loop arrivals default to a fixed period but ``--arrival`` shapes
them like production traffic (mean offered rate stays ``--rate``):

    poisson    memoryless exponential inter-arrival gaps
    bursty     on-off square wave (period ``--arrival-param``, default
               2 s): the ON half arrives at 2x rate, the OFF half idles
    diurnal    sinusoidal rate modulation (one compressed "day" per
               ``--arrival-param`` seconds, default 10)
    heavytail  lognormal think times (sigma ``--arrival-param``,
               default 1.5) — a few huge gaps, many tiny ones

``--tenant-mix "name:frac;..."`` assigns each request a tenant drawn
from the mix; the BENCH line stamps the arrival process, the offered
vs achieved rate, and the per-tenant request counts so benchdiff and
the burn-rate drill see traffic shape, not just totals.

``--generate`` switches the bench to the generative workload: a small
decoder-only LM served through ``add_generative_model`` under a mixed
prompt-length distribution (``--prompt-sizes``), closed-loop workers
streaming tokens.  The BENCH line becomes::

    {"metric": "serve_tokens_per_sec", "value": ..., "unit": "tok/s",
     "ttft_ms": {"p50","p95"}, "itl_ms": {"p50","p95"},
     "lowerings_after_warmup": 0, "rejected_429": ..., ...}

tokens/sec counts generated tokens over the timed window; TTFT is
submit → first streamed token, ITL the gap between consecutive streamed
tokens of one sequence.  KV-cache 429s are retried after the server's
``retry_after_ms`` hint and counted in ``rejected_429`` — past
saturation the bench demonstrates (rather than dies on) backpressure.

``--fleet N`` benches the multi-replica router (docs/serving.md
"Fleet"): N replica processes behind the FleetRouter, closed-loop load
with a live weight hot-swap at the halfway mark (no drain).  The BENCH
line becomes::

    {"metric": "fleet_throughput_rps", "value": ..., "unit": "req/s",
     "replicas": N, "balance_ratio": ..., "swap_pause_ms_p95": ...,
     "swap_lowerings": 0, "version_skew": {"v2": [0, 1, ...]}, ...}

``balance_ratio`` is max/mean per-replica request count (1.0 = the
least-loaded dispatch spread perfectly); ``swap_lowerings`` must stay
0 — the swap re-binds through the program registry, never re-compiles.

``lowerings_after_warmup`` comes from the executor program-registry
counters: the AOT contract is that it stays 0 no matter how many
requests run (the CI smoke asserts exactly that).  With telemetry on
(``MXTPU_TELEMETRY_DIR``), per-batch ``serve`` events flow to the event
log for ``mxtop --serve`` / ``parse_log.py``.
"""
from __future__ import annotations

import argparse
import json
import os
import random
import sys
import threading
import time

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.abspath(__file__)), ".."))


def _stamp_retrace(out):
    """Stamp the retrace sentry's verdict into a BENCH payload: the
    post-warmup retrace count plus the divergent-ingredient names of
    each attribution.  Keys are absent when the sentry is off
    (``MXTPU_RETRACE_SENTRY=1`` enables it), so benchdiff only
    compares runs that measured them."""
    try:
        from mxnet_tpu.observability import retrace as _retrace
        if not _retrace.installed():
            return
        st = _retrace.stats()
        out.setdefault("retraces_after_warmup",
                       st["retraces_after_warmup"])
        out.setdefault("retrace_attributions",
                       [",".join(a["divergent"])
                        for a in st["attributions"]])
    except Exception:
        pass


def _ctx():
    """Where the bench serves from: the accelerator when the process has
    one, else the host (the example scripts' rule)."""
    from mxnet_tpu.context import default_device_context
    return default_device_context()


def build_model(args):
    """(symbol_json, params dict, per-sample input shapes, input name)."""
    import mxnet_tpu as mx
    if args.checkpoint:
        from mxnet_tpu.serving import checkpoint_files
        prefix, _, epoch = args.checkpoint.partition("@")
        sym_path, params_path = checkpoint_files(prefix, int(epoch or 0))
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from mxserve import parse_shapes
        shapes = parse_shapes(args.shapes)
        return sym_path, params_path, shapes
    # toy MLP: feature dim sized so the matmuls are real but CPU-fast
    net = mx.models.get_mlp(num_classes=10, hidden=(64, 32))
    mod = mx.mod.Module(net, data_names=("data",),
                        label_names=("softmax_label",))
    mod.bind(data_shapes=[("data", (2, args.features))],
             label_shapes=[("softmax_label", (2,))])
    mod.init_params()
    arg_params, aux_params = mod.get_params()
    params = {"arg:" + k: v for k, v in arg_params.items()}
    params.update({"aux:" + k: v for k, v in aux_params.items()})
    return net.tojson(), params, {"data": (args.features,)}


def sample_sizes(dist, count, seed):
    """Deterministic weighted request-size sequence from "1:100,8:20"."""
    from mxnet_tpu.serving import parse_histogram
    hist = parse_histogram(dist)
    sizes, weights = zip(*sorted(hist.items()))
    rng = random.Random(seed)
    return [rng.choices(sizes, weights=weights)[0] for _ in range(count)]


ARRIVALS = ("fixed", "poisson", "bursty", "diurnal", "heavytail")


def arrival_offsets(arrival, rate, count, seed, param=None):
    """Absolute submit offsets (seconds from t0) for ``count`` open-loop
    arrivals at mean rate ``rate``, shaped by ``arrival``.  Every
    process normalizes to the same mean offered rate, so ``--arrival``
    changes burstiness, never the offered load.  Deterministic in
    ``seed``."""
    import math
    if rate <= 0:
        return [0.0] * count
    rng = random.Random(seed)
    mean_gap = 1.0 / rate
    if arrival == "poisson":
        gaps = [rng.expovariate(rate) for _ in range(count)]
    elif arrival == "bursty":
        # on-off square wave: ON half of each period arrives at 2x
        # rate, OFF half idles — mean stays `rate`
        period = float(param or 2.0)
        offs, t = [], 0.0
        while len(offs) < count:
            phase = t % period
            if phase < period / 2.0:
                offs.append(t)
                t += rng.expovariate(2.0 * rate)
            else:
                t += (period - phase)    # skip to the next ON window
        return offs[:count]
    elif arrival == "diurnal":
        # sinusoidal modulation: one compressed "day" per `period`
        # seconds, rate swinging 0.2x..1.8x around the mean
        period = float(param or 10.0)
        offs, t = [], 0.0
        for _ in range(count):
            offs.append(t)
            inst = rate * (1.0 + 0.8 * math.sin(
                2.0 * math.pi * t / period))
            t += rng.expovariate(max(inst, 0.05 * rate))
        return offs
    elif arrival == "heavytail":
        # lognormal think times normalized to the mean gap: most gaps
        # tiny, a few huge — the tail that breaks fixed-rate tuning
        sigma = float(param or 1.5)
        mu = math.log(mean_gap) - sigma * sigma / 2.0
        gaps = [rng.lognormvariate(mu, sigma) for _ in range(count)]
    else:                                # fixed (legacy default)
        return [i * mean_gap for i in range(count)]
    offs, t = [], 0.0
    for g in gaps:
        offs.append(t)
        t += g
    return offs


def parse_tenant_mix(raw):
    """``"name:frac;..."`` -> ordered (names, weights); None when
    unset.  Fractions are weights — they need not sum to 1."""
    if not raw:
        return None
    names, weights = [], []
    for part in raw.replace(",", ";").split(";"):
        part = part.strip()
        if not part:
            continue
        name, _, frac = part.partition(":")
        names.append(name.strip())
        weights.append(float(frac or 1.0))
    return (names, weights) if names else None


def run_closed(srv, model, inputs_for, sizes, concurrency):
    """Closed loop: each worker's next request waits on its previous."""
    lock = threading.Lock()
    cursor = [0]
    errors = []

    def worker():
        while True:
            with lock:
                i = cursor[0]
                if i >= len(sizes):
                    return
                cursor[0] += 1
            try:
                srv.predict(model, inputs_for(sizes[i]), timeout=60.0)
            except Exception as exc:
                errors.append(exc)
                return
    threads = [threading.Thread(target=worker, daemon=True)
               for _ in range(concurrency)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return time.perf_counter() - t0, 0, errors


def run_open(srv, model, inputs_for, sizes, rate, arrival="fixed",
             arrival_param=None, seed=7, tenant_mix=None):
    """Open loop: arrivals on the ``--arrival``-shaped schedule; 429
    rejections are counted, not retried (the generator models clients
    that back off).  Returns (wall_s, rejected, errors, info) where
    info carries the arrival stamp + per-tenant counts for BENCH."""
    from mxnet_tpu.serving import ServerBusy
    futures, rejected, errors = [], 0, []
    offsets = arrival_offsets(arrival, rate, len(sizes), seed,
                              param=arrival_param)
    tenants = None
    tenant_counts = {}
    if tenant_mix:
        names, weights = tenant_mix
        rng = random.Random(seed + 1)
        tenants = [rng.choices(names, weights=weights)[0]
                   for _ in sizes]
    t0 = time.perf_counter()
    for i, n in enumerate(sizes):
        delay = (t0 + offsets[i]) - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        if tenants is not None:
            tenant_counts[tenants[i]] = \
                tenant_counts.get(tenants[i], 0) + 1
        try:
            futures.append(srv.submit(model, inputs_for(n)))
        except ServerBusy:
            rejected += 1
    for fut in futures:
        try:
            fut.result(timeout=60.0)
        except Exception as exc:
            errors.append(exc)
    wall_s = time.perf_counter() - t0
    span = offsets[-1] if offsets and offsets[-1] > 0 else wall_s
    info = {"arrival": arrival,
            "offered_rate": round(len(sizes) / span, 2)
            if span > 0 else None}
    if tenant_counts:
        info["tenants"] = dict(sorted(tenant_counts.items()))
    return wall_s, rejected, errors, info


def build_lm(args):
    """Small decoder-only LM + deterministic random params for the
    generative bench (token-level correctness is covered by tests;
    the bench only needs real matmul shapes)."""
    import numpy as np
    from mxnet_tpu import ndarray as nd
    from mxnet_tpu.models import transformer as tf
    full = tf.get_symbol(vocab_size=args.vocab, num_layers=args.layers,
                         num_heads=args.heads, dim=args.dim,
                         seq_len=args.max_seq_len)
    shapes = full.infer_shape(data=(1, args.max_seq_len),
                              softmax_label=(1, args.max_seq_len))[0]
    rng = np.random.RandomState(args.seed)
    params = {}
    for name, shp in zip(full.list_arguments(), shapes):
        if name in ("data", "softmax_label"):
            continue
        params[name] = nd.array(rng.randn(*shp).astype(np.float32) * 0.05)
    return params


def check_logits(args, params):
    """Equivalence gate (--check-logits): greedy-decode a small mixed
    prompt set twice — f32 reference vs the --quantize dtype — through
    standalone engines with per-step logits collection on, and return
    the minimum per-step cosine similarity.  docs/perf.md sets the bar
    at >= 0.999; the caller fails the bench below it."""
    import numpy as np
    from mxnet_tpu.serving.generate import GenerationEngine

    kw = dict(vocab_size=args.vocab, num_layers=args.layers,
              num_heads=args.heads, dim=args.dim,
              max_seq_len=args.max_seq_len, max_new_tokens=args.max_new,
              prompt_buckets=args.prompt_buckets,
              prompt_histogram=(None if args.prompt_buckets
                                else args.prompt_sizes),
              decode_buckets=args.decode_buckets,
              kv_blocks=args.kv_blocks, kv_block_size=args.kv_block_size)
    lengths = sorted(set(sample_sizes(args.prompt_sizes, 8, args.seed)))
    rng = np.random.RandomState(args.seed)
    prompts = [rng.randint(1, args.vocab, size=n).tolist()
               for n in lengths]
    per_engine = []
    for quantize in ("", args.quantize):   # "" forces f32 even with env
        eng = GenerationEngine(params=dict(params), quantize=quantize,
                               ctx=_ctx(), **kw)
        eng.collect_logits = True
        eng.generate(prompts)
        per_engine.append(eng.last_logits)
    worst = 1.0
    for ref_rows, q_rows in zip(*per_engine):
        for a, b in zip(ref_rows, q_rows):
            a = np.asarray(a, dtype=np.float64).ravel()
            b = np.asarray(b, dtype=np.float64).ravel()
            denom = float(np.linalg.norm(a) * np.linalg.norm(b))
            cos = float(np.dot(a, b)) / denom if denom else 1.0
            worst = min(worst, cos)
    return worst


def run_generate(args):
    """Closed-loop generative drill; prints the tokens/sec BENCH line."""
    import numpy as np
    from mxnet_tpu.observability.counters import percentile
    from mxnet_tpu.serving import ModelServer, ServerBusy

    params = build_lm(args)
    logits_cos = None
    if args.check_logits:
        if not args.quantize:
            print("--check-logits requires --quantize", file=sys.stderr)
            return 2
        logits_cos = check_logits(args, params)
    srv = ModelServer(max_delay_ms=args.max_delay_ms,
                      max_queue=args.max_queue)
    engine = srv.add_generative_model(
        "lm", params, vocab_size=args.vocab, num_layers=args.layers,
        num_heads=args.heads, dim=args.dim, max_seq_len=args.max_seq_len,
        max_new_tokens=args.max_new, quantize=args.quantize,
        prompt_buckets=args.prompt_buckets,
        prompt_histogram=None if args.prompt_buckets else args.prompt_sizes,
        decode_buckets=args.decode_buckets,
        kv_blocks=args.kv_blocks, kv_block_size=args.kv_block_size,
        ctx=_ctx())
    from mxnet_tpu.executor import program_registry_stats
    lowerings_at_warmup = program_registry_stats()["lowerings"]

    lengths = sample_sizes(args.prompt_sizes, args.requests, args.seed)
    rng = np.random.RandomState(args.seed)
    prompts = [rng.randint(1, args.vocab, size=n).tolist()
               for n in lengths]

    lock = threading.Lock()
    cursor = [0]
    ttft, itl, errors = [], [], []
    rejected = [0]
    tokens = [0]

    def worker():
        while True:
            with lock:
                i = cursor[0]
                if i >= len(prompts):
                    return
                cursor[0] += 1
            t_submit = time.perf_counter()
            while True:
                try:
                    _fut, stream = srv.generate(
                        "lm", prompts[i], max_new_tokens=args.max_new)
                    break
                except ServerBusy as exc:
                    with lock:
                        rejected[0] += 1
                    time.sleep((exc.retry_after_ms or 50.0) / 1e3)
            t_prev = None
            try:
                for _tok in stream:
                    t_now = time.perf_counter()
                    with lock:
                        tokens[0] += 1
                        if t_prev is None:
                            ttft.append((t_now - t_submit) * 1e3)
                        else:
                            itl.append((t_now - t_prev) * 1e3)
                    t_prev = t_now
            except Exception as exc:
                errors.append(exc)
                return

    threads = [threading.Thread(target=worker, daemon=True)
               for _ in range(args.concurrency)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall_s = time.perf_counter() - t0

    stats = srv.stats()
    lowerings_after = program_registry_stats()["lowerings"] \
        - lowerings_at_warmup
    kv = engine.cache.stats()
    srv.close()
    try:
        from mxnet_tpu.observability import events as _events
        _events.flush()
    except Exception:
        pass

    def pct(vals):
        if not vals:
            return None
        return {"p50": round(percentile(vals, 50), 3),
                "p95": round(percentile(vals, 95), 3),
                "mean": round(sum(vals) / len(vals), 3)}

    out = {
        "metric": "serve_tokens_per_sec",
        "value": round(tokens[0] / wall_s, 2) if wall_s > 0 else 0.0,
        "unit": "tok/s",
        "mode": "generate",
        "requests": args.requests,
        "tokens": tokens[0],
        "rejected_429": rejected[0],
        "errors": len(errors),
        "wall_s": round(wall_s, 3),
        "ttft_ms": pct(ttft),
        "itl_ms": pct(itl),
        "prompt_buckets": list(engine.prompt_buckets),
        "decode_buckets": list(engine.decode_buckets),
        "kv_blocks_high_water": kv["blocks_high_water"],
        "kv_block_size": kv["block_size"],
        "batches": stats.get("batches"),
        "lowerings_after_warmup": lowerings_after,
        "quantize": args.quantize or None,
        "serving_dtype": engine.serving_dtype,
        "kernel_path": engine.kernel_path(),
    }
    if logits_cos is not None:
        out["logits_cosine_min"] = round(logits_cos, 7)
    if errors:
        out["first_error"] = repr(errors[0])
    _stamp_retrace(out)
    print(json.dumps(out, default=str))
    if errors:
        return 1
    if logits_cos is not None and logits_cos < 0.999:
        print("logits equivalence gate FAILED: min cosine %.7f < 0.999"
              % logits_cos, file=sys.stderr)
        return 1
    return 0


def run_fleet(args):
    """Fleet drill (--fleet N): spawn N replica processes behind the
    FleetRouter, drive closed-loop load over the toy MLP, hot-swap to
    perturbed v2 params at the halfway mark WITHOUT drain, and print
    one BENCH line: fleet throughput, per-replica dispatch balance
    (max/mean requests; 1.0 = perfectly even), and the hot-swap
    rotation-pause tail."""
    import tempfile
    import numpy as np
    from mxnet_tpu import ndarray as nd
    from mxnet_tpu.serving.fleet import launch_fleet

    symbol, params, shapes = build_model(args)
    if not isinstance(params, dict):
        print("--fleet needs the toy MLP (no --checkpoint)",
              file=sys.stderr)
        return 2
    input_name = next(iter(shapes))
    tmp = tempfile.mkdtemp(prefix="serve_bench_fleet_")
    sym_path = os.path.join(tmp, "bench-symbol.json")
    with open(sym_path, "w") as fout:
        fout.write(symbol)
    v1_path = os.path.join(tmp, "bench-v1.params")
    nd.save(v1_path, params)
    v2_path = os.path.join(tmp, "bench-v2.params")
    nd.save(v2_path, {k: nd.array(v.asnumpy() * 1.01 + 0.001)
                      for k, v in params.items()})
    spec_path = os.path.join(tmp, "fleet.json")
    with open(spec_path, "w") as fout:
        json.dump({"models": [{
            "name": "bench", "symbol": sym_path, "params": v1_path,
            "input_shapes": {k: list(v) for k, v in shapes.items()},
            "histogram": None if args.buckets else args.sizes,
            "buckets": args.buckets}],
            "version": "v1",
            "max_delay_ms": args.max_delay_ms,
            "max_queue": args.max_queue}, fout)

    router = launch_fleet(spec_path, n_replicas=args.fleet,
                          directory=os.path.join(tmp, "fleet"),
                          base_port=args.fleet_base_port)
    try:
        rng = np.random.RandomState(args.seed)
        sizes = sample_sizes(args.sizes, args.requests, args.seed)
        pool = {n: rng.rand(n, *shapes[input_name]).astype("float32")
                for n in set(sizes)}
        # warmup through every replica (untimed)
        for _ in range(2 * args.fleet):
            router.predict("bench", {input_name: pool[sizes[0]]},
                           timeout=60.0)

        swap_result = {}
        halfway = threading.Event()

        def swapper():
            halfway.wait(timeout=300.0)
            swap_result.update(router.swap(v2_path, version="v2"))

        swap_thread = threading.Thread(target=swapper, daemon=True)
        swap_thread.start()
        lock = threading.Lock()
        cursor = [0]
        errors = []

        def worker():
            while True:
                with lock:
                    i = cursor[0]
                    if i >= len(sizes):
                        return
                    cursor[0] += 1
                if i == len(sizes) // 2:
                    halfway.set()        # swap fires mid-load
                try:
                    router.predict(
                        "bench", {input_name: pool[sizes[i]]},
                        timeout=60.0)
                except Exception as exc:
                    errors.append(exc)
                    return

        threads = [threading.Thread(target=worker, daemon=True)
                   for _ in range(args.concurrency)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        halfway.set()
        swap_thread.join(timeout=300.0)
        wall_s = time.perf_counter() - t0
        st = router.stats()
    finally:
        router.close()
    try:
        from mxnet_tpu.observability import events as _events
        _events.flush()
    except Exception:
        pass

    per_replica = {i: r.get("requests", 0)
                   for i, r in st["replicas"].items()}
    counts = [c for c in per_replica.values() if c] or [0]
    mean = sum(counts) / len(counts)
    completed = args.requests - len(errors)
    lowerings = sum(r.get("lowerings", 0)
                    for r in (swap_result.get("replicas") or {}).values()
                    if isinstance(r, dict))
    out = {
        "metric": "fleet_throughput_rps",
        "value": round(completed / wall_s, 2) if wall_s > 0 else 0.0,
        "unit": "req/s",
        "mode": "fleet",
        "replicas": args.fleet,
        "requests": args.requests,
        "completed": completed,
        "errors": len(errors),
        "rejected": st.get("rejected", 0),
        "wall_s": round(wall_s, 3),
        "balance_ratio": round(max(counts) / mean, 3) if mean else None,
        "per_replica_requests": per_replica,
        "swap_pause_ms_p95": st.get("swap_pause_ms_p95"),
        "swap_lowerings": lowerings,
        "version_skew": st.get("version_skew"),
        "generation": st.get("generation"),
    }
    if errors:
        out["first_error"] = repr(errors[0])
    _stamp_retrace(out)
    print(json.dumps(out, default=str))
    if lowerings:
        print("fleet swap performed %d new lowerings (want 0)"
              % lowerings, file=sys.stderr)
        return 1
    return 1 if errors else 0


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="serve_bench", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--requests", type=int, default=200)
    ap.add_argument("--mode", choices=("closed", "open"), default="closed")
    ap.add_argument("--concurrency", type=int, default=8,
                    help="closed-loop worker count")
    ap.add_argument("--rate", type=float, default=200.0,
                    help="open-loop mean arrival rate (req/s)")
    ap.add_argument("--arrival", choices=ARRIVALS, default="fixed",
                    help="open-loop arrival process (mean rate stays "
                         "--rate; shapes burstiness)")
    ap.add_argument("--arrival-param", type=float, default=None,
                    help="process knob: bursty/diurnal period seconds, "
                         "heavytail sigma")
    ap.add_argument("--tenant-mix", default=None,
                    help='per-tenant request mix "name:frac;..." '
                         "(stamped into BENCH)")
    ap.add_argument("--sizes", default="1:60,2:25,4:10,8:5",
                    help='request-size distribution "n:weight,..."')
    ap.add_argument("--buckets", default=None,
                    help='explicit buckets "1,8" (default: planner '
                         "output over --sizes)")
    ap.add_argument("--max-delay-ms", type=float, default=None)
    ap.add_argument("--max-queue", type=int, default=None)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--features", type=int, default=128,
                    help="toy-MLP feature dim")
    ap.add_argument("--checkpoint", help="serve prefix@epoch instead of "
                                         "the toy MLP")
    ap.add_argument("--shapes", default="data=(128,)",
                    help="per-sample shapes (with --checkpoint)")
    ap.add_argument("--json", action="store_true",
                    help="(default behavior; kept for symmetry)")
    gen = ap.add_argument_group("generative mode")
    gen.add_argument("--generate", action="store_true",
                     help="bench token generation instead of predict")
    gen.add_argument("--prompt-sizes", default="4:50,12:30,24:20",
                     help='prompt-length distribution "len:weight,..."')
    gen.add_argument("--prompt-buckets", default=None,
                     help='explicit prompt-length buckets "8,16,32"')
    gen.add_argument("--decode-buckets", default=None,
                     help='explicit decode batch buckets "1,2,4,8"')
    gen.add_argument("--max-new", type=int, default=16,
                     help="tokens generated per request")
    gen.add_argument("--quantize", default=None,
                     help='weight-only quantization dtype ("int8" or '
                          '"fp8_e4m3"; default: MXTPU_QUANTIZE env)')
    gen.add_argument("--check-logits", action="store_true",
                     help="before the timed run, greedy-decode a probe "
                          "prompt set at f32 and at --quantize and fail "
                          "unless per-step logits cosine >= 0.999")
    gen.add_argument("--kv-blocks", type=int, default=None)
    gen.add_argument("--kv-block-size", type=int, default=None)
    gen.add_argument("--vocab", type=int, default=128)
    gen.add_argument("--layers", type=int, default=2)
    gen.add_argument("--heads", type=int, default=4)
    gen.add_argument("--dim", type=int, default=64)
    gen.add_argument("--max-seq-len", type=int, default=64)
    fl = ap.add_argument_group("fleet mode (docs/serving.md \"Fleet\")")
    fl.add_argument("--fleet", type=int, default=None, metavar="N",
                    help="spawn N replica processes behind the "
                         "FleetRouter and bench through it (with a "
                         "mid-run live weight hot-swap)")
    fl.add_argument("--fleet-base-port", type=int, default=None,
                    help="replica i listens on base+i "
                         "(MXTPU_FLEET_BASE_PORT)")
    args = ap.parse_args(argv)

    # MXTPU_RETRACE_SENTRY=1: attribute any post-warmup lowering in the
    # BENCH line (the CLI equivalent of the conftest hook)
    from mxnet_tpu.observability import retrace as _retrace
    _retrace.maybe_install()

    if args.generate:
        return run_generate(args)
    if args.fleet:
        return run_fleet(args)

    import numpy as np
    from mxnet_tpu.serving import ModelServer

    symbol, params, shapes = build_model(args)
    input_name = next(iter(shapes))
    srv = ModelServer(max_delay_ms=args.max_delay_ms,
                      max_queue=args.max_queue)
    plan = srv.add_model("bench", symbol, params, shapes,
                         histogram=args.sizes, buckets=args.buckets,
                         ctx=_ctx())

    rng = np.random.RandomState(args.seed)
    # pre-generate request payloads outside the timed window
    sizes = sample_sizes(args.sizes, args.requests, args.seed)
    pool = {n: rng.rand(n, *shapes[input_name]).astype("float32")
            for n in set(sizes)}

    def inputs_for(n):
        return pool[n]

    # warmup traffic (not timed): one request per bucket through the
    # full pipeline, then snapshot the registry counters
    for b in plan.buckets:
        srv.predict("bench", pool.get(b, rng.rand(
            b, *shapes[input_name]).astype("float32")))
    from mxnet_tpu.executor import program_registry_stats
    lowerings_at_warmup = program_registry_stats()["lowerings"]

    open_info = {}
    if args.mode == "closed":
        wall_s, rejected, errors = run_closed(
            srv, "bench", inputs_for, sizes, args.concurrency)
    else:
        wall_s, rejected, errors, open_info = run_open(
            srv, "bench", inputs_for, sizes, args.rate,
            arrival=args.arrival, arrival_param=args.arrival_param,
            seed=args.seed,
            tenant_mix=parse_tenant_mix(args.tenant_mix))

    stats = srv.stats()
    lowerings_after = program_registry_stats()["lowerings"] \
        - lowerings_at_warmup
    srv.close()
    try:
        from mxnet_tpu.observability import events as _events
        _events.flush()
    except Exception:
        pass

    completed = args.requests - rejected - len(errors)
    out = {
        "metric": "serve_throughput_rps",
        "value": round(completed / wall_s, 2) if wall_s > 0 else 0.0,
        "unit": "req/s",
        "mode": args.mode,
        "requests": args.requests,
        "completed": completed,
        "rejected": rejected,
        "errors": len(errors),
        "wall_s": round(wall_s, 3),
        "latency_ms": stats.get("latency_ms"),
        "occupancy": stats.get("occupancy"),
        "padding_waste": stats.get("padding_waste"),
        "planned_waste": round(plan.waste, 4),
        "pow2_waste": round(plan.pow2_waste, 4),
        "buckets": list(plan.buckets),
        "batches": stats.get("batches"),
        "lowerings_after_warmup": lowerings_after,
    }
    if args.mode == "open":
        # traffic-shape stamp: the arrival process, the rate the
        # schedule actually offered, and the rate the server achieved
        # — the offered-vs-achieved gap IS the saturation signal
        out.update(open_info)
        out["achieved_rate"] = out["value"]
        if args.tenant_mix:
            out["tenant_mix"] = args.tenant_mix
    if errors:
        out["first_error"] = repr(errors[0])
    _stamp_retrace(out)
    # mirror the BENCH payload into the event log (when telemetry is
    # on) so parse_log/mxtop gain the arrival/traffic-shape columns
    try:
        from mxnet_tpu.observability import events as _events
        _events.emit("summary", source="serve_bench", bench=out)
        _events.flush()
    except Exception:
        pass
    print(json.dumps(out, default=str))
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
