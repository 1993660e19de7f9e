"""Share of its roofline that the ``flash_forward`` kernel reaches in a
latent-attention configuration, whose q and k are wider than its v: the
least time the chip could take for the calls' shapes
(``flops_joyai.flash_forward_call``: causal half of q·kᵀ at
``qk_nope + qk_rope`` and of p·v at ``v_head_dim``; one pass over q, k, v,
o) over the summed device time of the trace's ``flash_forward`` events."""
from .. import flops, flops_joyai, trace_reduce


def read(ctx, kernel="flash_forward"):
    cfg, c = ctx["config"], ctx["counters"]
    events = trace_reduce.kernel_events(ctx["trace"].first_chip_ops(), kernel)
    if not events or ctx["peaks"] is None or "seq" not in c \
            or "qk_rope_head_dim" not in cfg:
        return None
    itemsize = 2 if cfg["training"]["compute_dtype"] == "bfloat16" else 4
    ops, nbytes = flops_joyai.flash_forward_call(
        c["batch"] // ctx["chips"], int(cfg["num_attention_heads"]),
        c["seq"], c["seq"],
        int(cfg["qk_nope_head_dim"]) + int(cfg["qk_rope_head_dim"]),
        int(cfg["v_head_dim"]), itemsize, causal=True)
    least, _bound = flops.roofline_seconds(
        ops, nbytes, ctx["peaks"]["bf16_flops_per_s"],
        ctx["peaks"]["hbm_bytes_per_s"])
    took = sum(d for _n, _s, d in events) * 1e-9
    return 100.0 * least * len(events) / took
