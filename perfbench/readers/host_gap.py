"""Host time per step during which the program has nothing queued on the
device: the mean, over consecutive steps k and k+1 of the window, of the
end of step k+1's ``step_dispatch`` minus the end of step k's last
``metric_sync`` (the blocking read that returns once step k's output is on
the host, so the device has run dry).  One value fewer than steps; ``None``
where ``program_span`` would give ``None``, or no pair has both spans."""
from .program_span import window_steps


def read(ctx, sync="metric_sync", dispatch="step_dispatch"):
    steps = window_steps(ctx)
    if steps is None:
        return None
    gaps = []
    for before, after in zip(steps, steps[1:]):
        synced = before.named(sync)
        queued = after.named(dispatch)
        if synced and queued:
            gaps.append(max(r.t1_ns for r in queued)
                        - max(r.t1_ns for r in synced))
    if not gaps:
        return None
    return sum(gaps) * 1e-6 / len(gaps)
