"""How often ``Module.fit`` issued the next batch's copy while a step ran:
of the window's step roots but the last (whose look-ahead finds the
window's end), the share in per cent that hold an ``h2d`` span which
begins after the root's own ``step_dispatch`` has ended, by the ring's
``t0_ns`` / ``t1_ns``.  A program that copies every batch at its dispatch
reads 0; ``None`` where ``program_span`` would give ``None`` (a tree from
before the ring among them), or the window has no step but the last."""
from .program_span import window_steps


def read(ctx, copy="h2d", dispatch="step_dispatch"):
    steps = window_steps(ctx)
    if steps is None or len(steps) < 2:
        return None
    ahead = 0
    for root in steps[:-1]:
        queued = min(r.t1_ns for r in root.named(dispatch))
        ahead += any(r.t0_ns >= queued for r in root.named(copy))
    return 100.0 * ahead / (len(steps) - 1)
