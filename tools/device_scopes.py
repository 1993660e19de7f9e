#!/usr/bin/env python
"""device_scopes: which graph node, and which pass over it, took the device's
time?

Reads one profiler capture (``.xplane.pb`` or ``.xplane.pb.gz``, as
``mx.profiler.profiler_set_state("run")`` or a benchmark's traced run leaves
it) and the step's record, which the job dumps once with
``mxnet_tpu.observability.device_scopes.dump("STEP.json")`` (any time after
the first step: ``{instruction: op_name}`` of the compiled step, the graph's
``{node: op type}`` and the module's name).  Every device event that begins
inside an execution of that module is charged to its instruction's scope:
the graph node ``executor._run_node`` lowered it under, and the pass —
``forward``, ``recompute`` (a mirrored segment's second run), ``backward``,
``update``, ``grad_sync`` — or ``other`` where the instruction carries no
scope of the program's (docs/observability.md, "Device time by scope").

The trace reduction is ``perfbench.trace_reduce``'s (a ``while`` is charged
only what its body does not cover), the grammar and the table
``mxnet_tpu.observability.device_scopes``'s; the benchmark's reader
``perfbench/readers/device_scope.py`` prints the same table.

Usage::

    python tools/device_scopes.py RUN.xplane.pb --scopes STEP.json \\
        [--window pb.window] [--steps N] [--json]
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))
from mxnet_tpu.observability import device_scopes  # noqa: E402
from perfbench import trace_reduce  # noqa: E402


def report(data, record, window_span=None):
    """``{chip: (table, executions of the step's module)}`` for every
    ``/device:TPU:<n>`` plane of ``data`` that ran the step."""
    window = trace_reduce.find_window(data, window_span) \
        if window_span else None
    modules = trace_reduce.device_lines(data, trace_reduce.MODULES_LINE)
    out = {}
    for chip, ops in sorted(trace_reduce.device_lines(data).items()):
        runs = modules.get(chip, [])
        if window:
            ops = trace_reduce.clip(ops, window)
            runs = trace_reduce.clip(runs, window)
        own = device_scopes.inside(ops, runs, record.module)
        if not own:
            continue
        table = device_scopes.table(trace_reduce.self_times(own), record)
        out[chip] = (table, sum(
            1 for n, _s, _d in runs
            if device_scopes.module_of(n) == record.module))
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("xplane", help="path of an .xplane.pb(.gz) capture")
    ap.add_argument("--scopes", required=True,
                    help="the step's record, as device_scopes.dump wrote it")
    ap.add_argument("--window", default=None,
                    help="host span that bounds the window (default: the "
                         "whole capture)")
    ap.add_argument("--steps", type=int, default=None,
                    help="steps to divide by (default: the executions of "
                         "the step's module in the window)")
    ap.add_argument("--json", action="store_true",
                    help="print the tables as one JSON object, nanoseconds")
    args = ap.parse_args(argv)
    record = device_scopes.load(args.scopes)
    rep = report(trace_reduce.load(args.xplane), record, args.window)
    if not rep:
        print("no /device:TPU:<n> plane ran %s in %s"
              % (record.module, args.xplane))
        return 1
    if args.json:
        def plain(value):       # (op type, phase) keys as "op type phase"
            if not isinstance(value, dict):
                return value
            return {" ".join(k) if isinstance(k, tuple) else k: v
                    for k, v in value.items()}

        print(json.dumps({chip: {k: plain(v) for k, v in table.items()}
                          for chip, (table, _n) in rep.items()}))
        return 0
    for chip, (table, executions) in rep.items():
        print("%s  %s x %d" % (chip, record.module, executions))
        for line in device_scopes.lines(table, args.steps or executions):
            head, _, rest = line.partition(": ")
            print("  %s:" % head)
            for item in rest.split(", "):
                print("    %s" % item)
    return 0


if __name__ == "__main__":
    sys.exit(main())
