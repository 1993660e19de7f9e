"""Calls of the flash forward kernel for each call of the flash backward in
the traced window: the trace's ``flash_forward`` events over the backward's
calls (``flash_backward_roofline.backward_events``: events / distinct kernel
names, so a backward made of two kernels is still one call).

A layer's attention runs the backward once a step, and the forward once,
plus once more for every time the step recomputes it: 1.0 where nothing is
recomputed or where a recomputed block keeps the kernel's output and softmax
statistics, 2.0 where a block's recomputation runs the kernel again.
``None`` where the trace holds no event of either kernel."""
from .. import trace_reduce
from .flash_backward_roofline import backward_events


def read(ctx):
    ops = ctx["trace"].first_chip_ops()
    forward = trace_reduce.kernel_events(ops, "flash_forward")
    backward, kernels = backward_events(ops)
    if not forward or not backward:
        return None
    return len(forward) * float(kernels) / len(backward)
