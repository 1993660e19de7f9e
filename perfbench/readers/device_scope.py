"""Share of the first chip's busy time that the program's own scopes name:
the traced window's device events joined with the compiled step's
``{instruction: op_name}`` map, which the program keeps
(``mxnet_tpu.observability.device_scopes``: a scope a graph node, a phase a
pass — forward, recompute, backward, update, grad_sync).

Of the first chip's ``XLA Ops`` events the reader keeps those that begin
inside an ``XLA Modules`` event of the registered step's module (another
program's ``fusion.3`` is not the step's), reduces them with
``trace_reduce.self_times`` (a ``while`` is charged only what its body does
not cover) and sums what the arguments select:

- no argument: everything under a graph node, ``update`` or ``grad_sync``;
- ``phase``: that phase alone;
- ``op_types``: the nodes of those operator types, every phase.

The share is of the chip's whole busy time (``self_times`` over every event
of the window, other programs too), so shares of one total cannot pass
100 %.  ``None`` where the program has no registry (a tree from before it),
no registered step ran in the window, or no event joined the map; once
events joined, the true share, 0.0 included.  The first call also prints
the table — the largest (op type, phase) pairs and nodes, milliseconds a
step — so every traced run's log holds it."""
import sys
import time

from .. import trace_reduce

_KEPT = "_device_scope"      # the reduction, kept on the run's ctx


def reduce_window(ctx):
    """``(table, busy_ns)`` of the traced window, or ``None``."""
    try:
        from mxnet_tpu.observability import device_scopes
    except ImportError:         # a tree from before the registry
        return None
    summary = ctx["trace"]
    ops = summary.first_chip_ops()
    modules = getattr(summary, "modules", None) or {}
    if not ops or not modules:
        return None
    module_events = modules[sorted(modules)[0]]
    record = device_scopes.latest(
        {device_scopes.module_of(n) for n, _s, _d in module_events})
    if record is None:
        return None
    t0 = time.perf_counter()
    n_mapped = len(record.scopes())
    took = time.perf_counter() - t0
    own = device_scopes.inside(ops, module_events, record.module)
    table = device_scopes.table(trace_reduce.self_times(own), record)
    if not table["joined_ns"]:
        return None
    steps = int(ctx["counters"].get("steps") or 1)
    sys.stdout.write(
        "[perfbench device_scope] %s: %d instructions mapped in %.1f s; %s\n"
        % (record.module, n_mapped, took,
           " | ".join(device_scopes.lines(table, steps))))
    sys.stdout.flush()
    busy = sum(trace_reduce.self_times(ops).values())
    return table, busy


def read(ctx, phase=None, op_types=None):
    if _KEPT not in ctx:
        ctx[_KEPT] = reduce_window(ctx)
    if ctx[_KEPT] is None:
        return None
    table, busy = ctx[_KEPT]
    if phase is not None:
        ns = table["by_phase"].get(phase, 0.0)
    elif op_types is not None:
        ns = sum(v for (op_type, _phase), v in table["by_type_phase"].items()
                 if op_type in op_types)
    else:
        ns = table["scoped_ns"]
    return 100.0 * ns / busy
