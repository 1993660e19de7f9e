"""Per-step host time inside the program's own spans, from the in-memory
ring of ``mxnet_tpu.observability.spans``: the mean, over the window's
steps, of the summed self time (duration minus what its child spans cover)
of each step's spans called one of ``names``.

The window's steps are the last ``counters["steps"]`` step roots the ring
holds: nothing after the window (``release()``, the trace's reduction)
dispatches a step.  ``None`` where the program keeps no such ring (a tree
from before the spans), where the ring holds fewer step roots than the
window counted, and where no step holds a span of these names."""


def window_steps(ctx):
    """The window's step roots, oldest first, or ``None``."""
    from mxnet_tpu.observability import spans
    steps_of = getattr(spans, "steps", None)
    n = int(ctx["counters"].get("steps") or 0)
    if steps_of is None or n <= 0:
        return None
    steps = steps_of(n)
    return steps if len(steps) == n else None


def read(ctx, names):
    from mxnet_tpu.observability import spans
    steps = window_steps(ctx)
    if steps is None:
        return None
    found = [rec for root in steps for rec in root.named(*names)]
    if not found:
        return None
    return sum(spans.self_ns(rec) for rec in found) * 1e-6 / len(steps)
