#!/usr/bin/env python
"""idle_gaps: where was the host while the chip did nothing?

Reads one profiler capture (``.xplane.pb`` or ``.xplane.pb.gz``, as
``mx.profiler.profiler_set_state("run")`` or a benchmark's traced run
leaves it) and charges every interval in which no operation ran on a chip
to the *innermost* of the program's own spans (``mx.<name>``, see
``docs/observability.md``) open on the host at that time; idle time under
no span goes to ``(no host span)``.  The program's spans are trace
annotations whether or not telemetry is on, so any capture holds them.

The window is the span ``--window`` names (a benchmark's ``pb.window``),
else each chip's first to last operation.  The trace reduction is
``perfbench.trace_reduce``'s; the host's clock and the device's differ by
about a millisecond, so gaps shorter than that can land one span off.

Usage::

    python tools/idle_gaps.py RUN.xplane.pb [--window pb.window] [--json]
"""
import argparse
import bisect
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))
from mxnet_tpu.observability.spans import TRACE_PREFIX  # noqa: E402
from perfbench import trace_reduce  # noqa: E402

OTHER = "(no host span)"


def innermost_timeline(spans):
    """Disjoint, sorted [(start, end, name)]: wherever some span is open,
    the open span that started last (of nested spans, the innermost)."""
    spans = sorted((s, s + d, n) for n, s, d in spans if d > 0)
    cuts = sorted({t for s, e, _n in spans for t in (s, e)})
    out, active, i = [], [], 0
    for a, b in zip(cuts, cuts[1:]):
        while i < len(spans) and spans[i][0] <= a:
            active.append(spans[i])
            i += 1
        active = [sp for sp in active if sp[1] >= b]
        if active:
            out.append((a, b, max(active, key=lambda sp: (sp[0], -sp[1]))[2]))
    return out


def innermost_charges(gaps, spans, other=OTHER):
    """{span name: ns}: every instant of every gap charged to the
    innermost span open then, or to ``other``."""
    timeline = innermost_timeline(spans)
    starts = [a for a, _b, _n in timeline]
    totals = {}
    for lo, hi in gaps:
        left = hi - lo
        i = max(0, bisect.bisect_right(starts, lo) - 1)
        while i < len(timeline) and timeline[i][0] < hi:
            a, b, name = timeline[i]
            part = min(b, hi) - max(a, lo)
            if part > 0:
                totals[name] = totals.get(name, 0.0) + part
                left -= part
            i += 1
        if left > 0:
            totals[other] = totals.get(other, 0.0) + left
    return totals


def report(data, window_span=None, prefix=TRACE_PREFIX):
    """{chip: {"window_s", "idle_s", "named_share", "by_span": [[name, s]]}}
    for every ``/device:TPU:<n>`` plane of ``data``."""
    window = trace_reduce.find_window(data, window_span) \
        if window_span else None
    spans = trace_reduce.host_spans(data, prefix=prefix)
    out = {}
    for chip, ops in sorted(trace_reduce.device_lines(data).items()):
        if not ops:
            continue
        win = window or (min(s for _n, s, _d in ops),
                         max(s + d for _n, s, d in ops))
        gaps = trace_reduce.idle_gaps(ops, win)
        charged = innermost_charges(gaps, trace_reduce.clip(spans, win))
        idle = sum(charged.values())
        out[chip] = {
            "window_s": (win[1] - win[0]) * 1e-9,
            "idle_s": idle * 1e-9,
            "named_share": 1.0 - charged.get(OTHER, 0.0) / idle
            if idle else None,
            "by_span": trace_reduce.top(charged, n=len(charged))}
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("xplane", help="path of an .xplane.pb(.gz) capture")
    ap.add_argument("--window", default=None,
                    help="host span that bounds the window (default: each "
                         "chip's first to last operation)")
    ap.add_argument("--json", action="store_true",
                    help="print the report as one JSON object")
    args = ap.parse_args(argv)
    rep = report(trace_reduce.load(args.xplane), args.window)
    if args.json:
        print(json.dumps(rep))
        return 0
    if not rep:
        print("no /device:TPU:<n> plane with operations in %s" % args.xplane)
        return 1
    for chip, r in rep.items():
        print("%s  window %.3f s  idle %.3f s (%.1f %%)  under mx.* spans "
              "%.1f %%" % (chip, r["window_s"], r["idle_s"],
                           100.0 * r["idle_s"] / r["window_s"],
                           100.0 * (r["named_share"] or 0.0)))
        for name, seconds in r["by_span"]:
            print("  %-24s %9.4f s  %5.1f %%" % (
                name, seconds, 100.0 * seconds / r["idle_s"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
