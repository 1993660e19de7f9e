"""``expert_matmul_roofline`` for a configuration whose routed layer names the
experts it holds ``num_experts`` (one a token, ``moe_intermediate_size`` wide):
the same reading — the least time of a grouped product of the rows the run's
routing counters report over the summed device time of the trace's
``ragged-dot-none`` events — by the reader that has it, handed the
configuration under the key it reads.  ``None`` where that reader finds
nothing to read or the configuration has no such key."""
from . import expert_matmul_roofline


def read(ctx, **kw):
    cfg = ctx["config"]
    if "num_experts" not in cfg:
        return None
    return expert_matmul_roofline.read(
        dict(ctx, config=dict(cfg, n_routed_experts=cfg["num_experts"])),
        **kw)
