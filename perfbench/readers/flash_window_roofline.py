"""Share of its roofline that a flash-attention kernel under a sliding
window reaches: the least time the chip could take for the windowed layers'
calls (``flops_laguna.flash_window_call``: the mathematics' products over the
band's exact pairs for every query head — the backward's four products,
twice the forward's; k and v read, dk and dv written, once a key/value head)
over the summed device time of the trace's ``flash_window_forward`` /
``flash_window_backward*`` events.

The windowed layers are those ``layer_types`` calls ``sliding_attention``,
each with its own query heads (``num_attention_heads_per_layer``) on
``num_key_value_heads`` of ``head_dim``: a step's least time is theirs
summed, and the window's steps are the events over the layers (events /
distinct kernel names for the backward, as ``flash_backward_roofline``
counts calls).  ``None`` where the trace holds no such event or the
configuration has no sliding window."""
import re

from .. import flops, flops_laguna, trace_reduce

NAMES = {"forward": "flash_window_forward",
         "backward": "flash_window_backward"}


def read(ctx, direction):
    cfg, c = ctx["config"], ctx["counters"]
    if direction not in NAMES:
        raise ValueError("flash_window_roofline reads %r not" % (direction,))
    if ctx["peaks"] is None or "seq" not in c \
            or "sliding_window" not in cfg:
        return None
    events, kernels = [], set()
    for n, s, d in ctx["trace"].first_chip_ops():
        short = trace_reduce.op_name(n)
        if short.startswith(NAMES[direction]):
            events.append((n, s, d))
            kernels.add(re.sub(r"\.\d+$", "", short))
    heads = [h for kind, h, _mlp in flops_laguna.layers(cfg)
             if kind == "sliding_attention"]
    if not events or not heads:
        return None
    d = int(cfg["head_dim"])
    itemsize = 2 if cfg["training"]["compute_dtype"] == "bfloat16" else 4
    least = 0.0
    for h in heads:
        ops, nbytes = flops_laguna.flash_window_call(
            c["batch"] // ctx["chips"], h, int(cfg["num_key_value_heads"]),
            c["seq"], int(cfg["sliding_window"]), d, d, itemsize,
            backward=direction == "backward")
        least += flops.roofline_seconds(
            ops, nbytes, ctx["peaks"]["bf16_flops_per_s"],
            ctx["peaks"]["hbm_bytes_per_s"])[0]
    steps = len(events) / float(len(kernels)) / len(heads)
    took = sum(dur for _n, _s, dur in events) * 1e-9
    return 100.0 * least * steps / took
