"""Share of its roofline that Kimi delta attention's rule reaches, a pass
apart: the least time the chip could take for the rule over the window's
steps (``flops_ling.kda_call``: the recurrence's operations; q, k, v, g and
β read and o written once, and for the backward the same reads, do, and the
five gradients written; recomputation not counted) — KDA layers x steps —
over the device time of the events of that pass's kernel, those whose name
begins ``kda_forward`` or ``kda_backward``.  A forward kernel that a
mirrored block ran again would count in the time and not in the least time.
``None`` where the configuration has no KDA layer or the trace holds no such
event."""
from .. import flops, flops_ling, trace_reduce

KERNELS = {"forward": "kda_forward", "backward": "kda_backward"}


def read(ctx, which):
    cfg, c = ctx["config"], ctx["counters"]
    if ctx["peaks"] is None or not c.get("steps") \
            or "layer_mixers" not in cfg:
        return None
    layers = [mixer for mixer, _mlp in flops_ling.layers(cfg)].count("kda")
    events = [d for n, _s, d in ctx["trace"].first_chip_ops()
              if trace_reduce.op_name(n).startswith(KERNELS[which])]
    if not layers or not events:
        return None
    itemsize = 2 if cfg["training"]["compute_dtype"] == "bfloat16" else 4
    d = int(cfg["head_dim"])
    ops, nbytes = flops_ling.kda_call(
        c["batch"] // ctx["chips"], int(cfg["num_attention_heads"]),
        c["seq"], d, d, itemsize, backward=which == "backward")
    least = flops.roofline_seconds(ops, nbytes,
                                   ctx["peaks"]["bf16_flops_per_s"],
                                   ctx["peaks"]["hbm_bytes_per_s"])[0]
    return 100.0 * least * layers * c["steps"] / (sum(events) * 1e-9)
