#!/usr/bin/env python
"""AOT audit of the fused train step through the REAL TPU compiler.

No chip is needed: jax's compile-only topology path
(jax.experimental.topologies + the local libtpu PJRT plugin) runs the
actual XLA:TPU/Mosaic pipeline and returns the compiled executable's
text, cost analysis (flops, bytes accessed, optimal_seconds) and memory
analysis (argument/output/temp/alias sizes) for a v5e — the audit
docs/mfu_gap.md previously said needed a live chip.

This closes the two blind spots of tools/mfu_audit.py on a CPU-only
box (reference for the gap they cover: mfu_audit.py's own "CPU-audit
trap" note): XLA:CPU upcasts bf16 convs and packs thousands of layout
transposes, so only the StableHLO could be audited before; here the
numbers come from the TPU backend itself.

Usage:
  python tools/aot_audit.py [--topology v5e:2x2] [--batch 64,256]
                            [--layers 50] [--mirror-compare]

Prints one human line per batch + a final JSON line.  Exits 2 with a
clear message when the local PJRT plugin cannot provide the topology
(e.g. no libtpu in the image) — callers/tests treat that as SKIP.
"""
import argparse
import json
import os
import re
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

def _peaks_for(device_kind):
    """(peak_flops, peak_hbm_bytes_s) for the topology's device kind,
    through bench.py's lookup helpers (single spec table, and the
    BENCH_PEAK_TFLOPS/BENCH_PEAK_HBM_GBPS env overrides apply here the
    same as in the bench itself)."""
    import bench
    tf, _tf_note = bench._lookup_peak_tflops(device_kind)
    gb, _gb_note = bench._lookup_peak_hbm(device_kind)
    if tf is None or gb is None:
        return None, None
    return tf * 1e12, gb * 1e9


def topology_devices(name):
    """Compile-only devices from the local TPU compiler, or None if the
    plugin can't provide them (no libtpu / bad name / already in use —
    libtpu serves ONE process at a time).  Shared by this tool and
    aot_longcontext_check.py; both exit 2 on None (callers SKIP).

    MXTPU_AOT_TOPOLOGY=0 skips the probe entirely: on boxes with a
    half-installed libtpu the get_topology_desc call can HANG inside the
    plugin instead of failing, and no subprocess timeout can make that
    cheap."""
    if os.environ.get("MXTPU_AOT_TOPOLOGY", "1") in ("0", "off", "no"):
        print("topology probe disabled (MXTPU_AOT_TOPOLOGY=0)",
              file=sys.stderr)
        return None
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(name, platform="tpu")
    except Exception as exc:  # noqa: BLE001
        print("topology %r unavailable: %s" % (name, exc), file=sys.stderr)
        return None
    return list(topo.devices)


def _topology_mesh(name, n_devices=1):
    """A 1-axis Mesh of compile-only devices, or None."""
    import numpy as np
    from jax.sharding import Mesh
    devs = topology_devices(name)
    if devs is None:
        return None
    return Mesh(np.array(devs[:n_devices]), ("dp",))


def _abstract_step_args(trainer, batch, image=224, num_classes=1000,
                        data_shape=None):
    """The fused step's argument pytree as sharding-annotated
    ShapeDtypeStructs — zero allocation, so compile-only devices work."""
    import jax
    import jax.numpy as jnp

    data_shape = data_shape or (batch, 3, image, image)
    label_shape = (batch,)
    params, opt_state, aux = trainer.abstract_state(
        {"data": data_shape}, label_shapes={"softmax_label": label_shape})
    repl = trainer._replicated()

    def _abs(shape, dtype, sharding):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    batch_abs = {
        "data": _abs(data_shape, jnp.float32,
                     trainer.batch_sharding(data_shape)),
        "softmax_label": _abs(label_shape, jnp.float32,
                              trainer.batch_sharding(label_shape)),
    }
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    rng_abs = _abs(key.shape, key.dtype, repl)
    scalar = lambda dt: _abs((), dt, repl)  # noqa: E731
    return (params, opt_state, aux, batch_abs, rng_abs,
            scalar(jnp.float32), scalar(jnp.float32), scalar(jnp.int32))


def _build_trainer(mesh, layers, batch, dtype, mirror=None,
                   num_classes=1000):
    """mirror: None (off), "env" (MXNET_BACKWARD_DO_MIRROR need_mirror
    rules), or "blocks" (resnet mirror_blocks attr tagging — whole
    residual units recompute, block boundaries kept)."""
    from mxnet_tpu.models import resnet
    from mxnet_tpu import optimizer as opt_mod
    from mxnet_tpu.parallel.trainer import ShardedTrainer

    sym = resnet.get_symbol(num_classes=num_classes, num_layers=layers,
                            mirror_blocks=(mirror == "blocks"))
    optimizer = opt_mod.create("sgd", learning_rate=0.1, momentum=0.9,
                               wd=1e-4, rescale_grad=1.0 / batch)
    if mirror != "env":
        return ShardedTrainer(sym, optimizer, mesh, compute_dtype=dtype)
    # env-driven mirroring (reference static_graph.cc:404 analog): the
    # need_mirror rules pick eligible ops with no per-op attrs needed
    prev = os.environ.get("MXNET_BACKWARD_DO_MIRROR")
    os.environ["MXNET_BACKWARD_DO_MIRROR"] = "1"
    try:
        return ShardedTrainer(sym, optimizer, mesh, compute_dtype=dtype)
    finally:
        if prev is None:
            os.environ.pop("MXNET_BACKWARD_DO_MIRROR", None)
        else:
            os.environ["MXNET_BACKWARD_DO_MIRROR"] = prev


def aot_compile(trainer, batch, image=224):
    """lower + compile on the topology; returns (compiled, lowered)."""
    args = _abstract_step_args(trainer, batch, image=image)
    lowered = trainer._jit_step.lower(*args)
    return lowered.compile(), lowered


def audit(mesh, batch, layers, dtype):
    trainer = _build_trainer(mesh, layers, batch, dtype)
    compiled, lowered = aot_compile(trainer, batch)

    shlo = lowered.as_text()
    conv_dtypes = {}
    for ty in re.findall(
            r"stablehlo\.convolution.*?->\s*tensor<[^>]*x(\w+)>", shlo):
        conv_dtypes[ty] = conv_dtypes.get(ty, 0) + 1

    hlo = compiled.as_text()
    fusions = len(re.findall(r"\bfusion\(", hlo))
    transposes = len(re.findall(r"\btranspose\(", hlo))
    copies = len(re.findall(r"\bcopy\(", hlo))
    # Mosaic/XLA:TPU conv dtypes as COMPILED (the CPU-trap killer): count
    # convolution ops by result element type
    compiled_convs = {}
    for ty in re.findall(r"= (\w+)\[[^\]]*\]\S* convolution\(", hlo):
        compiled_convs[ty] = compiled_convs.get(ty, 0) + 1

    ca = compiled.cost_analysis() or {}
    if isinstance(ca, (list, tuple)):   # per-device list on some backends
        ca = ca[0] if ca else {}
    flops = float(ca.get("flops") or 0.0)
    byts = float(ca.get("bytes accessed") or 0.0)
    mem = compiled.memory_analysis()

    out = {
        "batch": batch,
        "stablehlo_conv_dtypes": conv_dtypes,
        "compiled_conv_dtypes": compiled_convs,
        "backend_fusions": fusions,
        "backend_transposes": transposes,
        "backend_copies": copies,
        "model_tflops_per_step": round(flops / 1e12, 3),
        "bytes_gb_per_step": round(byts / 1e9, 3),
        "generated_code_bytes": mem.generated_code_size_in_bytes,
        "argument_bytes": mem.argument_size_in_bytes,
        "temp_bytes": mem.temp_size_in_bytes,
        "alias_bytes": mem.alias_size_in_bytes,
    }
    kind = getattr(mesh.devices.flat[0], "device_kind", "")
    peak_tf, peak_hbm = _peaks_for(kind)
    out["device_kind"] = str(kind)

    # cross-check the static analyzer's liveness-based peak-HBM estimate
    # (analysis/memory.py) against the TPU compiler's own memory
    # analysis: the estimate must land in the same regime as
    # argument+temp bytes, and both must fit the device's HBM
    try:
        from mxnet_tpu.analysis import (AnalysisContext, peak_hbm_report,
                                        hbm_capacity_bytes)
        ctx = AnalysisContext(
            trainer.symbol,
            shapes={"data": (batch, 3, 224, 224),
                    "softmax_label": (batch,)},
            mesh=mesh, sharding_rules=trainer.rules, grad_req="write")
        rep = peak_hbm_report(ctx)
        out["analysis_peak_hbm_bytes"] = rep["peak_bytes"]
        compiled_live = mem.argument_size_in_bytes + mem.temp_size_in_bytes
        if compiled_live:
            # > 1: the analyzer over-estimates (no fusion credit, no
            # optimizer state in the static graph); the audit line shows
            # how far
            out["analysis_vs_compiled"] = round(
                float(rep["peak_bytes"]) / compiled_live, 2)
        cap = hbm_capacity_bytes(kind)
        if cap:
            out["hbm_capacity_bytes"] = cap
            out["analysis_peak_hbm_ok"] = bool(rep["peak_bytes"] <= cap)
    except Exception as exc:  # noqa: BLE001 — audit must not die on lint
        out["analysis_note"] = "static memory cross-check failed: %s" % exc
    if flops and byts and peak_tf:
        intensity = flops / byts
        ridge = peak_tf / peak_hbm
        out["arith_intensity_flops_per_byte"] = round(intensity, 1)
        out["roofline_mfu_ceiling"] = round(min(1.0, intensity / ridge), 3)
        # roofline-projected step time/MFU from the TPU backend's own
        # numbers: time = max(compute-bound, bandwidth-bound)
        t_roof = max(flops / peak_tf, byts / peak_hbm)
        out["roofline_step_ms"] = round(t_roof * 1e3, 2)
        out["roofline_mfu"] = round(flops / t_roof / peak_tf, 3)
        out["roofline_images_per_sec"] = round(batch / t_roof, 1)
    elif flops and byts:
        out["arith_intensity_flops_per_byte"] = round(flops / byts, 1)
        out["roofline_note"] = ("unknown device_kind %r: no peak specs, "
                                "roofline omitted" % str(kind))
    if os.environ.get("AOT_BREAKDOWN", "1") != "0":
        out["entry_breakdown"] = entry_breakdown(hlo)
    dump = os.environ.get("AOT_DUMP_HLO")
    if dump:
        # one file per batch — a multi-batch audit must not silently
        # overwrite earlier dumps
        root, ext = os.path.splitext(dump)
        path = "%s.b%d%s" % (root, batch, ext or ".hlo")
        with open(path, "w") as f:
            f.write(hlo)
        out["hlo_dumped_to"] = path
    return out
    # (cost_analysis "optimal_seconds" is a negative sentinel on the
    # compile-only topology client — not reported)


_DTYPE_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "f64": 8, "s32": 4,
                "u32": 4, "s8": 1, "u8": 1, "pred": 1, "s64": 8, "u64": 8}


def _shape_bytes(dt, shape):
    if dt not in _DTYPE_BYTES:
        return 0
    n = 1
    for d in shape.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dt]


def entry_breakdown(hlo, top=12):
    """Rank op kinds in the ENTRY computation by materialized output
    bytes — every ENTRY-level instruction result is an HBM buffer, so
    this ranks the traffic the fusion boundaries actually generate.
    Excluded: fusion-internal ops (free), get-tuple-element (zero-copy
    view), parameter (an input, not written traffic).  Tuple-typed
    results (multi-output fusions) are summed over their members."""
    m = re.search(r"^ENTRY [^{]*\{(.*)", hlo, re.S | re.M)
    if not m:
        return []
    body = m.group(1)
    end = body.find("\n}")
    body = body[:end] if end >= 0 else body
    stats = {}
    line_re = re.compile(
        r"=\s+(\((?:[^()]|\([^)]*\))*\)|\w+\[[0-9,]*\]\S*)\s+([\w-]+)\(")
    member_re = re.compile(r"(\w+)\[([0-9,]*)\]")
    for ty, op in line_re.findall(body):
        if op in ("get-tuple-element", "parameter"):
            continue
        size = sum(_shape_bytes(dt, shape)
                   for dt, shape in member_re.findall(ty))
        if size <= 0:
            continue
        cnt, tot = stats.get(op, (0, 0))
        stats[op] = (cnt + 1, tot + size)
    ranked = sorted(stats.items(), key=lambda kv: -kv[1][1])[:top]
    return [{"op": op, "count": cnt, "output_gb": round(tot / 1e9, 3)}
            for op, (cnt, tot) in ranked]


def mirror_compare(mesh, layers, dtype, batch, image=112):
    """Compile plain vs env-mirrored vs block-mirrored on the TPU
    backend and report real activation-memory (temp bytes) deltas — the
    hardware-level numbers behind the recompute knobs.  Smaller image
    bounds compile time."""
    out = {"mirror_image": image, "mirror_batch": batch}
    tp = None
    for mode, key in ((None, "plain"), ("env", "env"), ("blocks", "blocks")):
        tr = _build_trainer(mesh, layers, batch, dtype, mirror=mode)
        compiled, _ = aot_compile(tr, batch, image=image)
        t = compiled.memory_analysis().temp_size_in_bytes
        out["temp_bytes_%s" % key] = t
        if mode is None:
            tp = t
        elif tp:
            out["temp_saving_pct_%s" % key] = round(100.0 * (tp - t) / tp, 1)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--topology", default="v5e:2x2",
                    help="PJRT TPU topology name (compile-only)")
    ap.add_argument("--batch", default="64,256")
    ap.add_argument("--layers", type=int, default=50)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--mirror-compare", action="store_true")
    args = ap.parse_args()

    import jax
    jax.config.update("jax_platforms", "cpu")   # never touch a live chip

    mesh = _topology_mesh(args.topology)
    if mesh is None:
        print(json.dumps({"error": "topology unavailable",
                          "topology": args.topology}))
        return 2

    results = []
    for b in (int(x) for x in args.batch.split(",")):
        r = audit(mesh, b, args.layers, args.dtype)
        results.append(r)
        print("batch %d [TPU-compiled]: convs %s | fusions=%d "
              "transposes=%d copies=%d | %.2f TF %.2f GB -> roofline "
              "%.1f img/s (MFU %.2f) | temp %.0f MB"
              % (b, r["compiled_conv_dtypes"], r["backend_fusions"],
                 r["backend_transposes"], r["backend_copies"],
                 r["model_tflops_per_step"], r["bytes_gb_per_step"],
                 r.get("roofline_images_per_sec", 0.0),
                 r.get("roofline_mfu", 0.0),
                 r["temp_bytes"] / 1e6))
    payload = {"topology": args.topology, "audit": results}
    if args.mirror_compare:
        payload["mirror"] = mirror_compare(mesh, args.layers, args.dtype,
                                           batch=int(args.batch.split(",")[0]))
        print("mirror temp MB: plain=%.0f env=%.0f (%s%%) blocks=%.0f (%s%%)"
              % (payload["mirror"]["temp_bytes_plain"] / 1e6,
                 payload["mirror"]["temp_bytes_env"] / 1e6,
                 payload["mirror"].get("temp_saving_pct_env"),
                 payload["mirror"]["temp_bytes_blocks"] / 1e6,
                 payload["mirror"].get("temp_saving_pct_blocks")))
    print(json.dumps(payload))
    return 0


if __name__ == "__main__":
    sys.exit(main())
