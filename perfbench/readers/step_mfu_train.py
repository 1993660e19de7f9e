"""Whole-step share of the chip's bf16 peak for a training cell: the
operations forward and backward need per step x steps over the window's
seconds over chips x peak.  The count is the function the configuration
names under ``"flops"`` (``"package.module:function"``, called with the
configuration and the run's counters; recomputation is not counted): a new
family brings its own, and a name that does not resolve is an error."""
from .. import common


def read(ctx):
    cfg, c = ctx["config"], ctx["counters"]
    if not c.get("steps") or ctx["peaks"] is None:
        return None
    per_step = common.named_function(cfg["flops"])(cfg, c)
    rate = per_step * c["steps"] / ctx["elapsed_s"]
    return 100.0 * rate / (ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"])
