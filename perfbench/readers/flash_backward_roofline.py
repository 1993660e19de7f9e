"""Share of its roofline that the flash backward reaches: the least time the
chip could take for the calls' shapes (``flops_flash_backward.
flash_backward_call``: the causal half of the four products dv, dp, dq, dk;
one pass over q, k, v, o, do, dq, dk, dv and lse) over the summed device
time of the trace's ``flash_backward*`` events.

A call is counted once whether one kernel implements it or several whose
names begin ``flash_backward``: calls = events / distinct kernel names.  The
widths are the configuration's: ``n_embd / n_head`` for q, k and v alike,
or latent attention's ``qk_nope_head_dim + qk_rope_head_dim`` against
``v_head_dim``.  ``None`` where the trace holds no such event (a program
whose backward is not a kernel)."""
import re

from .. import flops, flops_flash_backward, trace_reduce

PREFIX = "flash_backward"


def backward_events(events):
    """(events whose instruction's name begins ``flash_backward``, the
    number of distinct kernels among them: ``flash_backward.3`` and
    ``flash_backward`` are one kernel, ``flash_backward_dq`` another)."""
    found, kernels = [], set()
    for n, s, d in events:
        short = trace_reduce.op_name(n)
        if short.startswith(PREFIX):
            found.append((n, s, d))
            kernels.add(re.sub(r"\.\d+$", "", short))
    return found, len(kernels)


def widths(cfg):
    """(heads, width of q and k, width of v) of the configuration."""
    if "qk_rope_head_dim" in cfg:
        return (int(cfg["num_attention_heads"]),
                int(cfg["qk_nope_head_dim"]) + int(cfg["qk_rope_head_dim"]),
                int(cfg["v_head_dim"]))
    heads = int(cfg["n_head"])
    return heads, int(cfg["n_embd"]) // heads, int(cfg["n_embd"]) // heads


def read(ctx):
    cfg, c = ctx["config"], ctx["counters"]
    events, kernels = backward_events(ctx["trace"].first_chip_ops())
    if not events or ctx["peaks"] is None or "seq" not in c:
        return None
    heads, d_qk, d_v = widths(cfg)
    itemsize = 2 if cfg["training"]["compute_dtype"] == "bfloat16" else 4
    ops, nbytes = flops_flash_backward.flash_backward_call(
        c["batch"] // ctx["chips"], heads, c["seq"], c["seq"], d_qk, d_v,
        itemsize, causal=True)
    least, _bound = flops.roofline_seconds(
        ops, nbytes, ctx["peaks"]["bf16_flops_per_s"],
        ctx["peaks"]["hbm_bytes_per_s"])
    took = sum(d for _n, _s, d in events) * 1e-9
    return 100.0 * least * (len(events) / float(kernels)) / took
