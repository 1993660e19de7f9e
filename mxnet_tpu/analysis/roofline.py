"""Static roofline / precision lint (MXL-R): per-op FLOPs + HBM bytes,
arithmetic intensity vs the device ridge point, MXU padding waste, and
precision hazards — from the graph alone, no chip, no XLA compile.

FLOP model (calibrated against the XLA:TPU cost analysis recorded in
docs/mfu_gap.md): each op's forward FLOPs come from its
``cost_flops`` hook (conv/FC/dot: 2 FLOPs per MAC); training triples
the MXU work (forward + dgrad + wgrad are each a same-shape matmul) and
doubles everything else (forward + an elementwise-ish backward).  For
ResNet-50 b256 this lands at 6.28 TF/step vs the compiler's 6.28.

Traffic model: each op moves its inputs + outputs through HBM once
per pass, priced at the compute dtype (the trainer casts to bf16 on
TPU); MXU ops pay 3 passes in training, others 2, plus 24 bytes per
trained parameter scalar (f32 grad write + optimizer state + master
weight round-trip).  The raw per-op sum is fusion-blind, so training
traffic is **calibrated against the compiled AOT rows in
docs/mfu_gap.md's table** with two terms: a fusion factor (XLA elides ~23% of
naive per-op traffic once producers fuse into consumers) and a
batch-independent staging term per trained parameter (the
copy-start/copy-done alternate-memory traffic visible in the AOT
entry-computation breakdown scales with the weight set, not the
batch).  With the defaults (0.77 / 637 B per param) the v5e ResNet-50
ceilings land at 0.19/0.30/0.33 for b64/b256/b512 vs the compiler's
0.193/0.293/0.331.  Both knobs have env overrides
(``MXTPU_ROOFLINE_FUSION_FACTOR`` /
``MXTPU_ROOFLINE_STAGING_BYTES_PER_PARAM``); inference pricing stays
uncalibrated (the fit is a training-step fit).  The raw sum is kept in
the report as ``op_hbm_bytes_per_step``.

Peaks come from bench.py's spec-sheet table
(``_lookup_peak_tflops``/``_lookup_peak_hbm``, so lint and bench can
never disagree; ``BENCH_PEAK_TFLOPS``/``BENCH_PEAK_HBM_GBPS`` overrides
apply here too).  The ridge point peak_flops/peak_bw (v5e: 197e12/819e9
≈ 240 fl/B) classifies each op and the whole graph compute- vs
bandwidth-bound, and ``mfu_ceiling = min(1, intensity/ridge)``
reproduces the docs/mfu_gap.md MFU-ceiling table statically.

Per-op findings only fire above a significance floor
(``MXTPU_LINT_ROOFLINE_MIN_FLOPS``, default 5e10 training FLOPs) so toy
test graphs and the b2 model-zoo sweep stay clean; real batch sizes
surface the findings.
"""
from __future__ import annotations

import os as _os

import numpy as _np

from ..ops.registry import op_cost
from .core import register_rule
from .memory import _grad_req_of
from .propagation import edge_shapes, fmt_bytes
from .tiling import LANES, min_tile

__all__ = ["roofline_report", "device_peaks", "resolve_compute_dtype",
           "mxu_padding_waste", "static_mfu_ceiling",
           "static_ceiling_summary"]

# training multipliers: an MXU op's backward is two more same-shape
# matmuls (dgrad + wgrad); everything else pays one elementwise-ish
# backward pass
_TRAIN_PASSES_MXU = 3
_TRAIN_PASSES_OTHER = 2
# f32 grad write + optimizer state read/write + master weight round-trip
_PARAM_UPDATE_BYTES = 24
# training-traffic calibration vs the compiled AOT table
# (docs/mfu_gap.md): fraction of naive per-op bytes that survive XLA
# fusion, and alternate-memory staging bytes per trained parameter
# (batch-independent: the entry computation's copy-start/done pairs
# move weights, not activations)
_FUSION_FACTOR = 0.77
_STAGING_BYTES_PER_PARAM = 637


def _env_float(name, default):
    raw = _os.environ.get(name)
    if raw:
        try:
            return float(raw)
        except ValueError:
            pass
    return float(default)


def _min_flops():
    return _env_float("MXTPU_LINT_ROOFLINE_MIN_FLOPS", 5e10)


def resolve_compute_dtype(ctx):
    """The dtype matmuls run at: the explicit ``compute_dtype`` hint,
    else bfloat16 for the tpu target (the ShardedTrainer default),
    else float32."""
    cd = getattr(ctx, "compute_dtype", None)
    if cd:
        return str(_np.dtype(cd).name) if cd != "bfloat16" else "bfloat16"
    return "bfloat16" if ctx.target == "tpu" else "float32"


def _itemsize(dtype):
    if str(dtype) == "bfloat16":
        return 2
    return _np.dtype(dtype).itemsize


def resolve_device_kind(ctx):
    dk = getattr(ctx, "device_kind", None)
    return dk or _os.environ.get("MXTPU_LINT_DEVICE_KIND", "v5e")


def device_peaks(device_kind, dtype=None):
    """(peak_flops_per_s, peak_hbm_bytes_per_s) from bench.py's spec
    table (env overrides apply), or (None, None) when unknown.
    ``dtype`` ("int8"/"fp8") reads the quantized peak tables — how a
    graph with QuantizedDense nodes prices those rows."""
    try:
        import bench
        tf, _note = bench._lookup_peak_tflops(device_kind, dtype=dtype)
        gb, _note2 = bench._lookup_peak_hbm(device_kind)
    except Exception:
        return None, None
    if tf is None or gb is None:
        return None, None
    return tf * 1e12, gb * 1e9


def mxu_padding_waste(dims, compute_dtype="bfloat16"):
    """Fraction of MXU work spent on tile padding for ``(m, k, n)``
    matmul dims: k and n pad to the 128-lane granule, m to the dtype's
    sublane granule.  0.0 = perfectly tiled."""
    sub, _lanes = min_tile(compute_dtype)
    done = padded = 0
    for m, k, n in dims:
        done += m * k * n
        padded += (-(-m // sub) * sub) * (-(-k // LANES) * LANES) \
            * (-(-n // LANES) * LANES)
    if not padded:
        return 0.0
    return 1.0 - float(done) / float(padded)


def _training(ctx):
    for node in ctx.variables():
        if node.name in ctx.data_names or node.name in ctx.label_names:
            continue
        if _grad_req_of(ctx, node.name) != "null":
            return True
    return False


def _op_costs(ctx):
    """Cached per-op cost rows + graph totals."""
    if "roofline_costs" in ctx.cache:
        return ctx.cache["roofline_costs"]
    shapes = edge_shapes(ctx)
    compute_dtype = resolve_compute_dtype(ctx)
    item = _itemsize(compute_dtype)
    training = _training(ctx)
    rows = []
    complete = True
    for node in ctx.op_nodes():
        in_shapes = [shapes.get((id(c), ci)) for c, ci in node.inputs]
        out_shapes = [shapes.get((id(node), i))
                      for i in range(node.op.num_outputs)]
        if any(s is None for s in in_shapes) or \
                any(s is None for s in out_shapes):
            complete = False
            continue
        try:
            cost = op_cost(node.op, in_shapes, out_shapes)
        except Exception:
            complete = False
            continue
        passes = (_TRAIN_PASSES_MXU if cost["mxu"]
                  else _TRAIN_PASSES_OTHER) if training else 1
        flops = cost["flops"] * passes
        byts = cost["bytes_elements"] * item * passes
        reduce_len = cost["reduce_len"] or 0
        if cost["mxu_dims"]:
            reduce_len = max([reduce_len] +
                             [k for _m, k, _n in cost["mxu_dims"]])
        rows.append({
            "node": node.name,
            "op": type(node.op).op_name,
            "flops": flops,
            "bytes": byts,
            "mxu": cost["mxu"],
            "mxu_dims": cost["mxu_dims"],
            "reduce_len": int(reduce_len),
            "compute_dtype": cost.get("compute_dtype"),
        })
    param_bytes = 0
    param_count = 0
    if training:
        for node in ctx.variables():
            if node.name in ctx.data_names or node.name in ctx.label_names:
                continue
            if _grad_req_of(ctx, node.name) == "null":
                continue
            shape = shapes.get((id(node), 0))
            if shape is None:
                continue
            param_count += int(_np.prod(shape, dtype=_np.int64))
    param_bytes = param_count * _PARAM_UPDATE_BYTES
    facts = {"rows": rows, "complete": complete, "training": training,
             "compute_dtype": compute_dtype, "param_bytes": param_bytes,
             "param_count": param_count}
    ctx.cache["roofline_costs"] = facts
    return facts


def roofline_report(ctx):
    """The whole-graph static roofline (cached on the context).

    Keys: ``flops_per_step``, ``hbm_bytes_per_step``, ``intensity``,
    ``device_kind``, ``peak_tflops``, ``peak_hbm_gbps``, ``ridge``,
    ``mfu_ceiling``, ``bound``, ``compute_dtype``, ``mode``,
    ``complete``, ``per_op`` (top rows by FLOPs).  Peak-dependent keys
    are None when the device kind is unknown."""
    if "roofline_report" in ctx.cache:
        return ctx.cache["roofline_report"]
    facts = _op_costs(ctx)
    flops = sum(r["flops"] for r in facts["rows"])
    op_bytes = sum(r["bytes"] for r in facts["rows"])
    calibration = None
    if facts["training"] and ctx.target == "tpu":
        # the docs/mfu_gap.md fit (see module docstring): fused traffic +
        # param-update round-trip + batch-independent staging
        calibration = {
            "fusion_factor": _env_float(
                "MXTPU_ROOFLINE_FUSION_FACTOR", _FUSION_FACTOR),
            "staging_bytes_per_param": _env_float(
                "MXTPU_ROOFLINE_STAGING_BYTES_PER_PARAM",
                _STAGING_BYTES_PER_PARAM),
        }
        byts = calibration["fusion_factor"] * op_bytes \
            + facts["param_bytes"] \
            + calibration["staging_bytes_per_param"] \
            * facts["param_count"]
    else:
        byts = op_bytes + facts["param_bytes"]
    device_kind = resolve_device_kind(ctx)
    base_dtype = facts["compute_dtype"]
    peak_f, peak_b = device_peaks(
        device_kind,
        dtype=base_dtype if base_dtype in ("int8", "fp8") else None)
    # mixed-precision pricing: rows that declare their own compute
    # dtype (QuantizedDense -> int8/fp8) run at that dtype's peak, so
    # the graph's effective peak is flops-over-time across the mix
    # (time = Σ flops_d / peak_d) — a fully-int8 graph gets the full
    # int8 rate, a mixed graph something in between
    quant_flops = sum(r["flops"] for r in facts["rows"]
                      if r.get("compute_dtype"))
    if peak_f and quant_flops:
        t = 0.0
        for r in facts["rows"]:
            pf = peak_f
            if r.get("compute_dtype"):
                pd, _ = device_peaks(device_kind, dtype=r["compute_dtype"])
                pf = pd or peak_f
            t += r["flops"] / pf
        if t > 0:
            peak_f = flops / t
    report = {
        "flops_per_step": flops,
        "hbm_bytes_per_step": byts,
        "op_hbm_bytes_per_step": op_bytes + facts["param_bytes"],
        "calibration": calibration,
        "param_count": facts["param_count"],
        "intensity": (flops / byts) if byts else None,
        "device_kind": device_kind,
        "peak_tflops": (peak_f / 1e12) if peak_f else None,
        "peak_hbm_gbps": (peak_b / 1e9) if peak_b else None,
        "ridge": None, "mfu_ceiling": None, "bound": None,
        "compute_dtype": facts["compute_dtype"],
        "quantized_flops": quant_flops or 0,
        "mode": "training" if facts["training"] else "inference",
        "complete": facts["complete"],
        "per_op": sorted(facts["rows"], key=lambda r: -r["flops"])[:8],
    }
    if peak_f and peak_b and byts and flops:
        ridge = peak_f / peak_b
        report["ridge"] = ridge
        report["mfu_ceiling"] = min(1.0, report["intensity"] / ridge)
        report["bound"] = ("compute" if report["intensity"] >= ridge
                           else "bandwidth")
    ctx.cache["roofline_report"] = report
    return report


def static_mfu_ceiling(symbol, shapes, device_kind=None,
                       compute_dtype=None, grad_req=None, target="tpu"):
    """Convenience wrapper for bench/mfu_audit: the roofline report of
    ``symbol`` at ``shapes`` with no analysis context plumbing."""
    from .core import AnalysisContext
    ctx = AnalysisContext(symbol, shapes=shapes, grad_req=grad_req,
                          target=target)
    ctx.compute_dtype = compute_dtype
    ctx.device_kind = device_kind
    return roofline_report(ctx)


def static_ceiling_summary(symbol, shapes, device_kind=None,
                           compute_dtype=None, grad_req=None,
                           target="tpu", emit=False):
    """The ONE static-ceiling summary path shared by bench.py,
    tools/mfu_audit.py and the autotuner: flat ``static_*`` keys ready
    to merge into a BENCH payload / audit row.  Never raises — analyzer
    failures come back as ``static_mfu_ceiling_error``.  ``emit=True``
    also mirrors the roofline to the telemetry counter stream
    (``counters.emit_static_roofline``) so the measured-vs-ceiling gap
    is trackable."""
    try:
        rep = static_mfu_ceiling(symbol, shapes, device_kind=device_kind,
                                 compute_dtype=compute_dtype,
                                 grad_req=grad_req, target=target)
    except Exception as exc:  # noqa: BLE001 — callers print, not crash
        return {"static_mfu_ceiling_error":
                "%s: %s" % (type(exc).__name__, exc)}
    out = {
        "static_tflops_per_step": round(rep["flops_per_step"] / 1e12, 3),
        "static_hbm_gb_per_step": round(
            rep["hbm_bytes_per_step"] / 1e9, 3),
        "static_mfu_ceiling": (round(rep["mfu_ceiling"], 4)
                               if rep["mfu_ceiling"] is not None
                               else None),
        "static_bound": rep["bound"],
    }
    if emit:
        try:
            from ..observability import counters as _counters
            _counters.emit_static_roofline(
                symbol, shapes, device_kind=device_kind,
                compute_dtype=compute_dtype)
        except Exception:
            pass
    return out


# ----------------------------------------------------------------------
# the MXL-R rules
# ----------------------------------------------------------------------
def _active(ctx):
    return ctx.target == "tpu" and ctx.symbol is not None


@register_rule("MXL-R001", "info",
               doc="MXU op is bandwidth-bound at this batch size")
def _rule_r001(ctx):
    if not _active(ctx):
        return
    rep = roofline_report(ctx)
    if rep["ridge"] is None:
        return
    floor = _min_flops()
    for r in _op_costs(ctx)["rows"]:
        if not r["mxu"] or r["flops"] < floor or not r["bytes"]:
            continue
        intensity = r["flops"] / r["bytes"]
        if intensity < rep["ridge"]:
            ctx.report(r["node"],
                       "%s is bandwidth-bound: arithmetic intensity "
                       "%.0f fl/B < %s ridge %.0f — HBM feeds the MXU "
                       "slower than it computes at this shape (larger "
                       "batch or fused neighbors would help)"
                       % (r["op"], intensity, rep["device_kind"],
                          rep["ridge"]))


@register_rule("MXL-R002", "warning",
               doc="MXU tile padding wastes a large fraction of the op")
def _rule_r002(ctx):
    if not _active(ctx):
        return
    threshold = _env_float("MXTPU_LINT_MXU_WASTE_PCT", 25.0) / 100.0
    floor = _min_flops()
    compute_dtype = resolve_compute_dtype(ctx)
    for r in _op_costs(ctx)["rows"]:
        if not r["mxu_dims"] or r["flops"] < floor:
            continue
        waste = mxu_padding_waste(r["mxu_dims"], compute_dtype)
        if waste >= threshold:
            worst = max(r["mxu_dims"],
                        key=lambda d: -mxu_padding_waste([d],
                                                         compute_dtype))
            ctx.report(r["node"],
                       "%s pads %.0f%% of its MXU tiles away: matmul "
                       "dims %s vs the (%d, %d, %d) granule — pick "
                       "tile-aligned channel/feature sizes"
                       % (r["op"], 100.0 * waste, worst,
                          min_tile(compute_dtype)[0], LANES, LANES))


@register_rule("MXL-R003", "warning",
               doc="fp32 dot/conv on TPU: MXU peak rate needs bf16")
def _rule_r003(ctx):
    if not _active(ctx):
        return
    if _itemsize(resolve_compute_dtype(ctx)) < 4:
        return
    floor = _min_flops()
    mxu = [r for r in _op_costs(ctx)["rows"] if r["mxu"]]
    flops = sum(r["flops"] for r in mxu)
    if not mxu or flops < floor:
        return
    ctx.report(None,
               "%d dot/conv op(s) (%.2f TF/step) run at float32: the "
               "MXU's spec-sheet peak is bf16 — fp32 halves (or worse) "
               "the achievable rate; set compute_dtype=bfloat16 and "
               "keep f32 accumulation" % (len(mxu), flops / 1e12))


@register_rule("MXL-R004", "warning",
               doc="long bf16 accumulation chain (reduction hazard)")
def _rule_r004(ctx):
    if not _active(ctx):
        return
    if _itemsize(resolve_compute_dtype(ctx)) >= 4:
        return
    hazard_n = _env_float("MXTPU_LINT_BF16_REDUCE_N", 4096)
    floor = _min_flops()
    for r in _op_costs(ctx)["rows"]:
        if r["flops"] < floor or r["reduce_len"] < hazard_n:
            continue
        ctx.report(r["node"],
                   "%s accumulates over %d elements at bfloat16 (~8 "
                   "mantissa bits): force f32 accumulation "
                   "(preferred_element_type) or split the reduction"
                   % (r["op"], r["reduce_len"]))


@register_rule("MXL-R005", "info",
               doc="whole-graph static roofline / MFU-ceiling summary")
def _rule_r005(ctx):
    if not _active(ctx):
        return
    rep = roofline_report(ctx)
    if rep["flops_per_step"] < _min_flops() or rep["ridge"] is None:
        return
    ctx.report(None,
               "static roofline (%s, %s, %s): %.2f TF + %s per step -> "
               "intensity %.0f fl/B vs ridge %.0f -> %s-bound, MFU "
               "ceiling %.2f%s"
               % (rep["device_kind"], rep["compute_dtype"], rep["mode"],
                  rep["flops_per_step"] / 1e12,
                  fmt_bytes(rep["hbm_bytes_per_step"]),
                  rep["intensity"], rep["ridge"], rep["bound"],
                  rep["mfu_ceiling"],
                  "" if rep["complete"]
                  else " (partial: some shapes unknown)"))
