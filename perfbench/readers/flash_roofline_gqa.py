"""Share of its roofline that a flash-attention kernel reaches in a
configuration with grouped query heads (``num_attention_heads`` query heads
on ``num_key_value_heads`` key/value heads of ``head_dim``): the least time
the chip could take for the calls' shapes (``flops_zaya.flash_forward_call``
/ ``flash_backward_call``: the mathematics' products over the causal half
for every query head; k and v read, dk and dv written, once a key/value
head) over the summed device time of the trace's events.

``direction="forward"`` reads the ``flash_forward`` events, one a call;
``"backward"`` those whose name begins ``flash_backward``, calls = events /
distinct kernel names (``flash_backward_roofline.backward_events``).
``None`` where the trace holds no such event or the configuration has no
grouped heads to read."""
from .. import flops, flops_zaya, trace_reduce
from .flash_backward_roofline import backward_events


def read(ctx, direction):
    cfg, c = ctx["config"], ctx["counters"]
    if ctx["peaks"] is None or "seq" not in c \
            or "num_key_value_heads" not in cfg or "head_dim" not in cfg:
        return None
    ops_of = ctx["trace"].first_chip_ops()
    if direction == "forward":
        events = trace_reduce.kernel_events(ops_of, "flash_forward")
        calls, count = len(events), flops_zaya.flash_forward_call
    elif direction == "backward":
        events, kernels = backward_events(ops_of)
        calls = len(events) / float(kernels or 1)
        count = flops_zaya.flash_backward_call
    else:
        raise ValueError("flash_roofline_gqa reads %r not" % (direction,))
    if not events:
        return None
    d = int(cfg["head_dim"])
    itemsize = 2 if cfg["training"]["compute_dtype"] == "bfloat16" else 4
    ops, nbytes = count(
        c["batch"] // ctx["chips"], int(cfg["num_attention_heads"]),
        int(cfg["num_key_value_heads"]), c["seq"], c["seq"], d, d, itemsize,
        causal=True)
    least, _bound = flops.roofline_seconds(
        ops, nbytes, ctx["peaks"]["bf16_flops_per_s"],
        ctx["peaks"]["hbm_bytes_per_s"])
    took = sum(dur for _n, _s, dur in events) * 1e-9
    return 100.0 * least * calls / took
