"""Share of its roofline that the grouped expert product reaches: the
program's ``lax.ragged_dot`` is one Mosaic call a projection on the chip,
named ``ragged-dot-none`` in the device trace (forward, the recomputed
forward and the two products of each backward alike).  Every such call
multiplies the rows routed to the experts held by one (hidden, expert
width) matrix an expert, whichever way round: ``flops_joyai.
expert_product_call`` with the rows the run's routing counters report (the
mean over the window's steps and the routed layers), over the summed device
time of those events.  ``None`` where the trace names no such call."""
from .. import flops, flops_joyai, trace_reduce


def read(ctx, kernel="ragged-dot-none"):
    cfg, c = ctx["config"], ctx["counters"]
    events = trace_reduce.kernel_events(ctx["trace"].first_chip_ops(), kernel)
    layers = c.get("routed_layers")
    if not events or ctx["peaks"] is None or not layers or not c.get("steps"):
        return None
    rows = sum(r["local_assignments"] for r in layers) \
        / float(len(layers) * c["steps"])
    itemsize = 2 if cfg["training"]["compute_dtype"] == "bfloat16" else 4
    ops, nbytes = flops_joyai.expert_product_call(
        rows, int(cfg["n_routed_experts"]), int(cfg["hidden_size"]),
        int(cfg["moe_intermediate_size"]), itemsize)
    least, _bound = flops.roofline_seconds(
        ops, nbytes, ctx["peaks"]["bf16_flops_per_s"],
        ctx["peaks"]["hbm_bytes_per_s"])
    took = sum(d for _n, _s, d in events) * 1e-9
    return 100.0 * least * len(events) / took
