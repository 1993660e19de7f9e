"""Idle share of the device: 1 - (union of device-op intervals) / window."""


def read(ctx):
    trace = ctx["trace"]
    if trace.n_chips == 0 or trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - trace.busy_s / trace.window_s)
