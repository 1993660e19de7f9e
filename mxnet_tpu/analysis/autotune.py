"""Chip-free autotuner: search-space grammar, constraint pruning,
memoized static pricing, and replay-manifest construction
(docs/perf.md "Autotuning & chip windows").

Chip windows are scarce, so config selection happens off-chip: every
model the search needs already exists in this package and prices a
graph without lowering anything — MXL-R (roofline MFU ceiling,
calibrated against the compiled AOT table in docs/mfu_gap.md), MXL-M
(peak-HBM fit), MXL-K (Mosaic tile legality), MXL-E (pipeline/MoE
schedule lint — infeasible stage splits and expert counts are pruned,
a feasible pipeline config's ceiling is scaled by its simulated 1F1B
bubble) and MXL-D (distributed lint).  The tuner enumerates a config
grammar, **prunes infeasible
candidates before pricing them** (an illegal tile or an OOM config
must not spend analysis time, and must never reach a chip), prices the
survivors through one memoized analysis context per distinct graph
(a multi-hundred-config sweep re-lowers each distinct symbol once —
``GraphMemo.stats`` counts it), and ranks by static MFU ceiling with
HBM-headroom tiebreak plus a Pareto frontier over predicted
throughput vs. predicted peak memory.

The output is a deterministic, provenance-stamped **replay manifest**
(``build_manifest``): the ordered top-K configs with predicted
MFU / peak-HBM / ICI bytes and the exact ``bench.py`` command line for
each, so a chip window runs only the top-K in order.  Identical inputs
produce byte-identical manifests — nothing time- or machine-dependent
enters the hashed body.  ``tools/autotune.py`` is the CLI; its
``--replay`` side stamps each BENCH line with the manifest config id,
gates every result through the slo.py sentry, and re-ranks the
remaining candidates with :func:`fit_correction` as measured numbers
arrive.

HBM feasibility is a *predictor*, not the MXL-M lint: the analytic
peak keeps every residual live, while the compiled step re-materializes
and dies long before that bound (docs/mfu_gap.md's table: 11.2 GB
compiled temp at b512 vs 70 GB analytic).  The predictor credits activations with
``MXTPU_AUTOTUNE_ACT_CREDIT`` (default 0.2, calibrated against the
same AOT rows) and shards state across the config's mesh; MXL-M's own
lint semantics are untouched.
"""
from __future__ import annotations

import hashlib
import itertools
import json
import re as _re

from .core import AnalysisContext, run_rules
from .memory import hbm_capacity_bytes, peak_hbm_report
from .propagation import comm_report
from .roofline import (_env_float, _op_costs, device_peaks,
                       roofline_report)
from .tiling import LANES, block_findings

__all__ = ["AXES", "default_space", "parse_space", "space_configs",
           "parse_sharding", "config_id", "canonical_json", "GraphMemo",
           "predicted_peak_hbm", "prune_config", "price_config",
           "search", "build_manifest", "bench_command",
           "fit_correction", "apply_correction", "rerank"]

# ---------------------------------------------------------------------
# search-space grammar
# ---------------------------------------------------------------------
#: axis order IS the grammar order: config dicts, manifest rows and
#: config ids all serialize axes in this order
AXES = ("batch", "remat", "sharding", "dtype", "bucket_mb", "prefetch",
        "serve_block", "serve_buckets", "stages", "microbatches",
        "experts", "capacity_factor")

#: axes whose values are ints ("none" -> None for the optional ones)
_INT_AXES = ("batch", "bucket_mb", "prefetch", "serve_block", "stages",
             "microbatches", "experts")
#: axes whose values are floats
_FLOAT_AXES = ("capacity_factor",)
_OPTIONAL_AXES = ("serve_block", "serve_buckets", "stages", "experts",
                  "capacity_factor")

#: the serve paged-KV pool the MXL-K gate checks serve_block against —
#: (pool_rows, head_dim): any realistic pool dominates the block, so
#: only the block's own granule alignment matters
_SERVE_POOL = (4096, LANES)


def default_space(model="resnet50"):
    """The stock search space: the known-good batch ladder (the
    docs/mfu_gap.md v5e table), both remat policies, single-chip dp,
    bf16 compute, and the PR-8 overlap knob defaults."""
    del model  # one stock space today; per-model spaces can fork here
    return {
        "batch": (64, 128, 256, 512),
        "remat": ("none", "blocks"),
        "sharding": ("dp1",),
        "dtype": ("bfloat16",),
        "bucket_mb": (25,),
        "prefetch": (2,),
        "serve_block": (None,),
        "serve_buckets": (None,),
        # pipeline / MoE axes (MXL-E): single-valued defaults keep the
        # stock sweep's graph count unchanged; widen them with e.g.
        # "stages=2,4;microbatches=4,8" or "experts=4,8"
        "stages": (None,),
        "microbatches": (8,),
        "experts": (None,),
        "capacity_factor": (None,),
    }


def parse_space(spec, base=None):
    """Parse the grammar string ``"batch=64,128;remat=none,blocks;
    sharding=dp1,dp2tp2;dtype=bfloat16,int8;serve_block=16,32"`` into a
    space dict.  Unknown axes are an error; unnamed axes keep their
    ``base`` (default-space) values."""
    space = dict(base or default_space())
    for part in (spec or "").split(";"):
        part = part.strip()
        if not part:
            continue
        if "=" not in part:
            raise ValueError("bad space term %r (want axis=v1,v2,...)"
                             % part)
        axis, _, raw = part.partition("=")
        axis = axis.strip()
        if axis not in AXES:
            raise ValueError("unknown axis %r (valid: %s)"
                             % (axis, ", ".join(AXES)))
        vals = []
        for tok in raw.split(","):
            tok = tok.strip()
            if not tok:
                continue
            if axis in _OPTIONAL_AXES and tok.lower() == "none":
                vals.append(None)
            elif axis in _INT_AXES:
                vals.append(int(tok))
            elif axis in _FLOAT_AXES:
                vals.append(float(tok))
            else:
                vals.append(tok)
        if not vals:
            raise ValueError("axis %r has no values" % axis)
        space[axis] = tuple(vals)
    return space


def space_configs(space):
    """Enumerate the space as config dicts, in deterministic grammar
    order (itertools.product over AXES)."""
    axes = [tuple(space.get(a) or (default_space()[a])) for a in AXES]
    return [dict(zip(AXES, combo)) for combo in itertools.product(*axes)]


# the "dp2tp2pp4ep2"-style sharding grammar lives with the sharding
# rules it configures; the tuner re-exports it (axes: dp/fsdp, tp, pp
# pipeline stages, ep expert parallelism)
from ..parallel.sharding import _SHARDING_RE, parse_sharding  # noqa: E402,F401


def canonical_json(obj):
    """The one serialization determinism hangs on: sorted keys, no
    whitespace."""
    return json.dumps(obj, sort_keys=True, separators=(",", ":"))


def config_id(config):
    """Content-hash id for a config: same config -> same id, on any
    machine, forever (the manifest/BENCH join key)."""
    ordered = {k: config.get(k) for k in AXES}
    ordered["model"] = config.get("model")
    digest = hashlib.sha256(
        canonical_json(ordered).encode()).hexdigest()
    return "at-" + digest[:10]


# ---------------------------------------------------------------------
# models the tuner knows how to build
# ---------------------------------------------------------------------
_RESNET_RE = _re.compile(r"^resnet(\d+)$")


def _model_builder(model):
    """(build_fn(remat_blocks, experts, capacity_factor) -> symbol,
    shapes_fn(batch) -> dict).  ``experts`` / ``capacity_factor`` are
    the MoE axes: on the transformer builders they swap every FFN for a
    routed expert block (ops/moe.py); the conv models reject them."""
    m = _RESNET_RE.match(model)
    if m:
        layers = int(m.group(1))

        def build(remat, experts=None, capacity_factor=None):
            if experts:
                raise ValueError("model %r has no MoE variant (axis "
                                 "experts=%s)" % (model, experts))
            from ..models import resnet
            return resnet.get_symbol(num_classes=1000, num_layers=layers,
                                     mirror_blocks=remat)

        return build, lambda b: {"data": (b, 3, 224, 224)}
    if model in ("transformer", "transformer_moe"):
        def build(remat, experts=None, capacity_factor=None):
            from ..models import transformer, transformer_moe
            if model == "transformer_moe":
                kw = {}
                if experts:
                    kw["num_experts"] = int(experts)
                if capacity_factor:
                    kw["moe_capacity_factor"] = float(capacity_factor)
                return transformer_moe.get_symbol(mirror_blocks=remat,
                                                  **kw)
            if experts:
                return transformer.get_symbol(
                    mirror_blocks=remat, num_experts=int(experts),
                    moe_capacity_factor=float(capacity_factor or 0.0))
            return transformer.get_symbol(mirror_blocks=remat)

        return build, lambda b: {"data": (b, 512)}
    raise ValueError("unknown model %r (resnetNN, transformer or "
                     "transformer_moe)" % (model,))


# ---------------------------------------------------------------------
# memoized per-graph analysis
# ---------------------------------------------------------------------
class GraphMemo(object):
    """One symbol build per distinct (model, remat) and one analysis
    context per distinct *graph* key — configs differing only in
    graph-free axes (bucket_mb, prefetch, serve_buckets, serve_block)
    share every report.  ``stats`` counts re-lowerings so sweeps can
    assert each distinct symbol was analyzed once."""

    def __init__(self, device_kind="v5e", hbm_bytes=None):
        self.device_kind = device_kind
        self.hbm_bytes = hbm_bytes
        self._symbols = {}
        self._ctxs = {}
        self.stats = {"symbols_built": 0, "analyses": 0, "memo_hits": 0}

    def symbol(self, model, remat, experts=None, capacity_factor=None):
        key = (model, remat, experts, capacity_factor)
        if key not in self._symbols:
            build, _shapes = _model_builder(model)
            self._symbols[key] = build(remat == "blocks", experts,
                                       capacity_factor)
            self.stats["symbols_built"] += 1
        return self._symbols[key]

    @staticmethod
    def graph_key(model, config):
        """The axes that change the analyzed graph or its pricing."""
        return (model, config["batch"], config["remat"],
                config["dtype"], config["sharding"],
                config.get("stages"), config.get("microbatches"),
                config.get("experts"), config.get("capacity_factor"))

    def ctx(self, model, config):
        key = self.graph_key(model, config)
        hit = key in self._ctxs
        if hit:
            self.stats["memo_hits"] += 1
            return self._ctxs[key]
        self.stats["analyses"] += 1
        sym = self.symbol(model, config["remat"],
                          config.get("experts"),
                          config.get("capacity_factor"))
        _build, shapes_fn = _model_builder(model)
        deg = parse_sharding(config["sharding"])
        # an explicit "stages" axis pipelines without a pp mesh entry
        # in the sharding rule; both spell the same pipeline degree
        pp = int(config.get("stages") or deg["pp"])
        world = deg["dp"] * deg["tp"] * pp * deg["ep"]
        mesh = None
        if world > 1:
            from ..parallel.mesh import LogicalMesh
            axes = {}
            if deg["dp"] > 1:
                axes["dp"] = deg["dp"]
            if deg["tp"] > 1:
                axes["tp"] = deg["tp"]
            if pp > 1:
                axes["pp"] = pp
            if deg["ep"] > 1:
                axes["ep"] = deg["ep"]
            mesh = LogicalMesh(**axes)
        # int8 is the quantized *serving* axis: price the graph in
        # inference mode (no grads, no param-update traffic) at the
        # int8 MXU peak
        grad_req = "null" if config["dtype"] in ("int8", "fp8") \
            else "write"
        ctx = AnalysisContext(sym, shapes=shapes_fn(config["batch"]),
                              grad_req=grad_req, target="tpu",
                              mesh=mesh, world_size=max(1, world),
                              compute_dtype=config["dtype"],
                              device_kind=self.device_kind,
                              hbm_bytes=self.hbm_bytes)
        # MXL-E reads the microbatch count off the context (overrides
        # the MXTPU_LINT_MICROBATCHES default)
        mb = config.get("microbatches")
        if mb:
            ctx.schedule_microbatches = int(mb)
        self._ctxs[key] = ctx
        return ctx


# ---------------------------------------------------------------------
# constraint pruning (before pricing)
# ---------------------------------------------------------------------
def predicted_peak_hbm(config, mem):
    """Calibrated per-device peak-HBM prediction for a config.

    The analytic ``peak_hbm_report`` keeps every residual live;
    compiled programs re-materialize and stage, so activations get an
    AOT-calibrated credit (``MXTPU_AUTOTUNE_ACT_CREDIT``, default 0.2
    — docs/mfu_gap.md b512: 11.2 GB compiled temp vs 70 GB analytic).
    dp·tp shard the batch/hidden activation axes; params/grads/opt
    state shard over tp, and over dp too when the rule is fsdp
    (ZeRO-3)."""
    deg = parse_sharding(config["sharding"])
    credit = _env_float("MXTPU_AUTOTUNE_ACT_CREDIT", 0.2)
    pp = int(config.get("stages") or deg["pp"])
    act_div = max(1, deg["dp"] * deg["tp"])
    # pp splits the layer stack (each stage holds ~1/pp of the params);
    # ep shards the expert stacks, which this model treats as the bulk
    # of an MoE config's state
    state_div = max(1, deg["tp"] * pp * deg["ep"]
                    * (deg["dp"] if deg["fsdp"] else 1))
    state = (mem["params_bytes"] + mem["grads_bytes"]
             + mem["aux_bytes"]) / float(state_div)
    act = mem["activations_bytes"] * credit / float(act_div)
    return state + act


def _serve_block_findings(config):
    """Graph-free MXL-K gate: a paged-KV serve block must sit on the
    compute dtype's Mosaic granule (int8 -> (32, 128))."""
    block = config.get("serve_block")
    if not block:
        return []
    return [f for f in block_findings(
        (int(block), LANES), _SERVE_POOL, config["dtype"],
        label="serve_block %s" % block) if f[1] == "error"]


def prune_config(model, config, memo, budget_bytes):
    """The feasibility gates, cheap-to-expensive, run BEFORE any
    pricing: returns ``None`` for a feasible config, else a
    ``"mxl-k: ..." | "mxl-m: ..." | "mxl-e: ..." | "mxl-d: ..."``
    reason string.
    """
    # 1. MXL-K tile legality — needs no graph at all
    bad = _serve_block_findings(config)
    if bad:
        return "mxl-k: %s" % bad[0][2]
    try:
        ctx = memo.ctx(model, config)
    except ValueError as exc:
        # e.g. an "experts" axis on a model with no MoE variant
        return "build: %s" % exc
    # 2. MXL-M HBM fit — memory report only, roofline never touched
    if budget_bytes:
        mem = peak_hbm_report(ctx)
        pred = predicted_peak_hbm(config, mem)
        if pred > budget_bytes:
            return ("mxl-m: predicted peak %.1f GB > %.1f GB %s HBM"
                    % (pred / 1e9, budget_bytes / 1e9,
                       memo.device_kind))
    deg = parse_sharding(config["sharding"])
    # 3. MXL-E schedule lint — pipeline/MoE configs only: an imbalanced
    # partition, a deadlocking back-edge, an over-budget 1F1B stash or
    # an indivisible expert count never reaches pricing (or a chip)
    pp = int(config.get("stages") or deg["pp"])
    if pp > 1 or deg["ep"] > 1 or config.get("experts"):
        if "autotune_mxl_e" not in ctx.cache:
            issues = run_rules(ctx, select=("MXL-E*",))
            ctx.cache["autotune_mxl_e"] = [
                i for i in issues if i.severity == "error"]
        errors = ctx.cache["autotune_mxl_e"]
        if errors:
            return "mxl-e: %s" % errors[0].message
    # 4. MXL-D distributed lint — sharded configs only
    if deg["dp"] * deg["tp"] * pp * deg["ep"] > 1:
        if "autotune_mxl_d" not in ctx.cache:
            issues = run_rules(ctx, select=("MXL-D*",))
            ctx.cache["autotune_mxl_d"] = [
                i for i in issues if i.severity == "error"]
        errors = ctx.cache["autotune_mxl_d"]
        if errors:
            return "mxl-d: %s" % errors[0].message
    return None


# ---------------------------------------------------------------------
# pricing + ranking
# ---------------------------------------------------------------------
def _recompute_flops(ctx):
    """Extra forward FLOPs a remat (mirror) policy replays in backward:
    every op inside a ``force_mirroring`` segment recomputes its
    forward once.  Approximation shared with the executor's mirror map
    (``executor._mirror_segments``)."""
    if "autotune_recompute" in ctx.cache:
        return ctx.cache["autotune_recompute"]
    from ..executor import _mirror_segments
    facts = _op_costs(ctx)
    by_name = {r["node"]: r for r in facts["rows"]}
    extra = 0.0
    try:
        segments = _mirror_segments(list(ctx.op_nodes()))
    except Exception:
        segments = []
    for is_mirror, nodes in segments:
        if not is_mirror:
            continue
        for node in nodes:
            row = by_name.get(node.name)
            if row is None:
                continue
            passes = 3 if row["mxu"] else 2
            extra += row["flops"] / float(passes)
    ctx.cache["autotune_recompute"] = extra
    return extra


def price_config(model, config, memo, budget_bytes):
    """Static price for a feasible config: MFU ceiling (remat pays its
    recompute replay in the time term but earns no useful-FLOP credit;
    a pipeline config pays its 1F1B bubble), per-device step-time
    floor, throughput ceiling, predicted peak HBM + headroom, and ICI
    bytes for sharded configs."""
    ctx = memo.ctx(model, config)
    rep = roofline_report(ctx)
    mem = peak_hbm_report(ctx)
    deg = parse_sharding(config["sharding"])
    pp = int(config.get("stages") or deg["pp"])
    world = max(1, deg["dp"] * deg["tp"] * pp * deg["ep"])
    pred_peak = predicted_peak_hbm(config, mem)
    out = {
        "mfu_ceiling": rep["mfu_ceiling"],
        "tflops_per_step": round(rep["flops_per_step"] / 1e12, 3),
        "hbm_traffic_gb_per_step": round(
            rep["hbm_bytes_per_step"] / 1e9, 3),
        "peak_hbm_gb": round(pred_peak / 1e9, 3),
        "hbm_headroom_gb": (round((budget_bytes - pred_peak) / 1e9, 3)
                            if budget_bytes else None),
        "bound": rep["bound"],
        "mode": rep["mode"],
        "ici_bytes": 0,
        "step_ms_floor": None,
        "samples_per_sec_ceiling": None,
        "bubble_fraction": None,
    }
    # a pipelined config idles (1 - bubble) of each stage away: the
    # MXL-E simulator's 1F1B bubble scales the ceiling down and the
    # step floor up (same slot-synchronous model the lint validates)
    bubble = 0.0
    if pp > 1:
        from .schedule import schedule_report
        sched = schedule_report(ctx)
        if sched and sched.get("schedules"):
            bubble = float(
                sched["schedules"]["1f1b"]["bubble_fraction"])
            out["bubble_fraction"] = round(bubble, 4)
    peak_f = (rep["peak_tflops"] or 0) * 1e12
    peak_b = (rep["peak_hbm_gbps"] or 0) * 1e9
    if peak_f and peak_b:
        flops = rep["flops_per_step"] / world
        byts = rep["hbm_bytes_per_step"] / world
        extra = _recompute_flops(ctx) / world \
            if config["remat"] == "blocks" else 0.0
        t = max((flops + extra) / peak_f, byts / peak_b)
        if 0.0 < bubble < 1.0:
            t /= (1.0 - bubble)
        out["step_ms_floor"] = round(t * 1e3, 3)
        out["samples_per_sec_ceiling"] = round(config["batch"] / t, 1)
        out["mfu_ceiling"] = round(flops / (t * peak_f), 4)
    if world > 1:
        try:
            out["ici_bytes"] = int(comm_report(ctx)["total_bytes"])
        except Exception:
            out["ici_bytes"] = None
    return out


def _mark_pareto(entries):
    """Non-dominated set over (throughput ceiling max, peak HBM min)."""
    for e in entries:
        tput = e["predicted"].get("samples_per_sec_ceiling") or 0.0
        peak = e["predicted"].get("peak_hbm_gb")
        peak = float("inf") if peak is None else peak
        dominated = False
        for o in entries:
            if o is e:
                continue
            ot = o["predicted"].get("samples_per_sec_ceiling") or 0.0
            op = o["predicted"].get("peak_hbm_gb")
            op = float("inf") if op is None else op
            if ot >= tput and op <= peak and (ot > tput or op < peak):
                dominated = True
                break
        e["pareto"] = not dominated
    return entries


def search(model="resnet50", device_kind="v5e", space=None,
           hbm_gb=None, memo=None):
    """Enumerate, prune, price, rank.  Returns the full (deterministic)
    result dict; :func:`build_manifest` turns it into the replay
    manifest."""
    space = space or default_space(model)
    if hbm_gb:
        budget = int(float(hbm_gb) * (1 << 30))
    else:
        budget = hbm_capacity_bytes(device_kind)
    memo = memo or GraphMemo(device_kind=device_kind, hbm_bytes=budget)
    entries, pruned = [], []
    for config in space_configs(space):
        cfg = dict(config)
        cfg["model"] = model
        cid = config_id(cfg)
        reason = prune_config(model, config, memo, budget)
        if reason:
            pruned.append({"config_id": cid, "config": config,
                           "reason": reason})
            continue
        entries.append({"config_id": cid, "config": config,
                        "predicted": price_config(model, config, memo,
                                                  budget)})
    entries.sort(key=lambda e: (
        -(e["predicted"]["mfu_ceiling"] or 0.0),
        -(e["predicted"]["hbm_headroom_gb"] or 0.0),
        e["config_id"]))
    _mark_pareto(entries)
    for i, e in enumerate(entries):
        e["rank"] = i + 1
    peak_f, peak_b = device_peaks(device_kind)
    return {
        "model": model,
        "device_kind": device_kind,
        "space": {a: list(space.get(a) or default_space()[a])
                  for a in AXES},
        "hbm_budget_bytes": budget,
        "peaks": {"tflops": (peak_f / 1e12) if peak_f else None,
                  "hbm_gbps": (peak_b / 1e9) if peak_b else None},
        "calibration": {
            "fusion_factor": _env_float(
                "MXTPU_ROOFLINE_FUSION_FACTOR", 0.77),
            "staging_bytes_per_param": _env_float(
                "MXTPU_ROOFLINE_STAGING_BYTES_PER_PARAM", 637),
            "act_credit": _env_float("MXTPU_AUTOTUNE_ACT_CREDIT", 0.2),
        },
        "counts": {"total": len(entries) + len(pruned),
                   "priced": len(entries), "pruned": len(pruned),
                   "symbols_built": memo.stats["symbols_built"],
                   "analyses": memo.stats["analyses"],
                   "memo_hits": memo.stats["memo_hits"]},
        "entries": entries,
        "pruned": pruned,
    }


# ---------------------------------------------------------------------
# replay manifest
# ---------------------------------------------------------------------
def bench_command(model, config, cid):
    """The exact command a chip window runs for this config.  The
    replay driver adds ``BENCH_AUTOTUNE_MANIFEST_HASH`` at run time
    (the hash covers these commands, so it cannot appear inside them).
    """
    deg = parse_sharding(config["sharding"])
    world = max(1, deg["dp"] * deg["tp"])
    env = [("BENCH_BATCH", max(1, config["batch"] // world)),
           ("BENCH_DTYPE", config["dtype"]),
           ("BENCH_REMAT", 1 if config["remat"] == "blocks" else 0)]
    m = _RESNET_RE.match(model)
    if m:
        env.append(("BENCH_LAYERS", int(m.group(1))))
    env += [("MXTPU_BUCKET_MB", config["bucket_mb"]),
            ("MXTPU_PREFETCH", 1),
            ("MXTPU_PREFETCH_DEPTH", config["prefetch"])]
    if config.get("serve_block"):
        env.append(("MXTPU_SERVE_BLOCK", config["serve_block"]))
    if config.get("serve_buckets"):
        env.append(("MXTPU_SERVE_BUCKETS", config["serve_buckets"]))
    pp = int(config.get("stages") or deg["pp"])
    if pp > 1:
        env.append(("BENCH_PP_STAGES", pp))
        env.append(("BENCH_MICROBATCHES",
                    config.get("microbatches") or 8))
    if config.get("experts"):
        env.append(("BENCH_MOE_EXPERTS", config["experts"]))
        if config.get("capacity_factor"):
            env.append(("BENCH_MOE_CAPACITY",
                        config["capacity_factor"]))
    env.append(("BENCH_AUTOTUNE_CONFIG_ID", cid))
    return " ".join("%s=%s" % (k, v) for k, v in env) + " python bench.py"


def build_manifest(result, top_k=8, provenance=None):
    """Deterministic replay manifest from a :func:`search` result:
    ordered top-K configs + predictions + exact bench commands, a
    provenance block (argv / git commit / calibration — inputs, never
    wall-clock time), and a content hash over the whole body.  Same
    inputs -> byte-identical ``canonical_json(manifest)``."""
    configs = []
    for e in result["entries"][:top_k]:
        configs.append({
            "rank": e["rank"],
            "config_id": e["config_id"],
            "config": e["config"],
            "pareto": e["pareto"],
            "predicted": e["predicted"],
            "bench_cmd": bench_command(result["model"], e["config"],
                                       e["config_id"]),
        })
    body = {
        "manifest_version": 1,
        "kind": "autotune_replay_manifest",
        "model": result["model"],
        "device_kind": result["device_kind"],
        "space": result["space"],
        "hbm_budget_bytes": result["hbm_budget_bytes"],
        "peaks": result["peaks"],
        "calibration": result["calibration"],
        "counts": result["counts"],
        "provenance": dict(provenance or {}),
        "configs": configs,
        "pruned": result["pruned"],
    }
    body["manifest_hash"] = hashlib.sha256(
        canonical_json(body).encode()).hexdigest()[:16]
    return body


# ---------------------------------------------------------------------
# measured-vs-predicted correction (mid-window re-ranking)
# ---------------------------------------------------------------------
def fit_correction(pairs):
    """Fit measured ≈ a·predicted + b over ``[(predicted, measured)]``
    pairs.  One point (or a degenerate spread) fits a pure ratio; two
    or more fit least squares.  Returns ``{"kind", "a", "b", "n"}`` or
    None with no usable pairs."""
    pts = [(float(p), float(m)) for p, m in pairs
           if p is not None and m is not None and p > 0]
    if not pts:
        return None
    n = len(pts)
    mean_p = sum(p for p, _ in pts) / n
    mean_m = sum(m for _, m in pts) / n
    var = sum((p - mean_p) ** 2 for p, _ in pts)
    if n == 1 or var <= 1e-12:
        return {"kind": "ratio", "a": mean_m / mean_p, "b": 0.0, "n": n}
    a = sum((p - mean_p) * (m - mean_m) for p, m in pts) / var
    b = mean_m - a * mean_p
    return {"kind": "linear", "a": a, "b": b, "n": n}


def apply_correction(correction, predicted):
    if correction is None or predicted is None:
        return predicted
    return correction["a"] * float(predicted) + correction["b"]


def rerank(entries, correction):
    """Re-sort manifest config entries by the corrected predicted MFU
    (stable on the original rank for ties) — the mid-window move after
    each measured result lands."""
    return sorted(entries, key=lambda e: (
        -(apply_correction(correction,
                           e["predicted"].get("mfu_ceiling")) or 0.0),
        e.get("rank", 0), e["config_id"]))
