"""Share of its roofline that the ``flash_forward`` kernel reaches: the least
time the chip could take for the calls' shapes (``flops.flash_forward_call``:
causal half of the score and value products; one pass over q, k, v, o) over
the summed device time of the trace's ``flash_forward`` events."""
from .. import flops, trace_reduce


def read(ctx, kernel="flash_forward"):
    cfg, c = ctx["config"], ctx["counters"]
    events = trace_reduce.kernel_events(ctx["trace"].first_chip_ops(), kernel)
    if not events or ctx["peaks"] is None or "seq" not in c:
        return None
    heads = int(cfg["n_head"])
    itemsize = 2 if cfg["training"]["compute_dtype"] == "bfloat16" else 4
    ops, nbytes = flops.flash_forward_call(
        c["batch"] // ctx["chips"], heads, c["seq"], c["seq"],
        int(cfg["n_embd"]) // heads, itemsize, causal=True)
    least, _bound = flops.roofline_seconds(
        ops, nbytes, ctx["peaks"]["bf16_flops_per_s"],
        ctx["peaks"]["hbm_bytes_per_s"])
    took = sum(d for _n, _s, d in events) * 1e-9
    return 100.0 * least * len(events) / took
