"""From a profiler trace (``.xplane.pb``) to the numbers the benchmark reports.

What one real trace of a v5e looks like (PERF.md, PR 24): every chip is a
plane ``/device:TPU:<n>`` with the lines ``XLA Modules`` (one event per
program execution, named ``jit_<fn>(<fingerprint>)``), ``XLA Ops`` (one event
per executed HLO instruction, named by the instruction's text, ``%name = ...``;
a ``while`` encloses the events of its body) and ``Async XLA Ops``
(copies in flight, overlapping the others).  Host threads are lines of the
plane ``/host:CPU``; ``jax.profiler.TraceAnnotation`` spans land on the line
of the thread that opened them.  Times are nanoseconds; the device's clock
and the host's differ by about a millisecond.

Reductions, all pure functions of (name, start, duration) triples so that
the test can feed them by hand:

- ``busy_union``      seconds in which some operation ran on the device;
- ``self_times``      per-name device time, an enclosing ``while`` not
                      counted twice;
- ``idle_gaps`` and ``attribute_gaps``  the idle intervals, each charged to
                      the host span that overlaps it most.
"""
import gzip
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_DEVICE = re.compile(r"^/device:TPU:\d+$")


def load(path):
    """``ProfileData`` of an ``.xplane.pb`` (or ``.xplane.pb.gz``) file."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def _events(line):
    return [(ev.name, float(ev.start_ns), float(ev.duration_ns))
            for ev in line.events]


def device_lines(data, line_name=OPS_LINE):
    """{plane name: [(name, start_ns, dur_ns)]} for every chip's line."""
    out = {}
    for plane in data.planes:
        if not _DEVICE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name == line_name:
                out[plane.name] = _events(line)
    return out


def host_spans(data, names=None, prefix=None):
    """Host events [(name, start_ns, dur_ns)] over all host threads, kept
    where the name is in ``names`` or starts with ``prefix``."""
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for ev in line.events:
                nm = ev.name
                if (names and nm in names) or (prefix and
                                               nm.startswith(prefix)):
                    out.append((nm, float(ev.start_ns),
                                float(ev.duration_ns)))
    return out


def op_name(text):
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    head = text.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def clip(events, window):
    """Events cut to ``window`` = (start_ns, end_ns); empty ones dropped."""
    lo, hi = window
    out = []
    for name, start, dur in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            out.append((name, s, e - s))
    return out


def merged_intervals(events):
    """Sorted, disjoint [start, end] pairs covering the events."""
    spans = sorted((s, s + d) for _n, s, d in events if d > 0)
    out = []
    for s, e in spans:
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def busy_union(events):
    """Nanoseconds covered by at least one event."""
    return sum(e - s for s, e in merged_intervals(events))


def self_times(events):
    """{short op name: ns} where an event that encloses others (a ``while``
    around its body) is charged only the time none of them covers."""
    evs = sorted(((s, -(d), n) for n, s, d in events if d > 0))
    totals = {}
    stack = []          # [name, end, self_ns, cursor]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, end, acc, cursor = stack.pop()
            acc += max(0.0, end - cursor)
            totals[name] = totals.get(name, 0.0) + acc
            if stack:
                stack[-1][3] = max(stack[-1][3], end)

    for s, negd, n in evs:
        close(s)
        e = s - negd
        if stack:
            top = stack[-1]
            top[2] += max(0.0, s - top[3])
            top[3] = max(top[3], s)
            e = min(e, top[1])
        stack.append([op_name(n), e, 0.0, s])
    close(float("inf"))
    return totals


def idle_gaps(events, window):
    """[(start, end)] inside ``window`` that no event covers."""
    lo, hi = window
    gaps = []
    cursor = lo
    for s, e in merged_intervals(clip(events, window)):
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    if hi > cursor:
        gaps.append((cursor, hi))
    return gaps


def attribute_gaps(gaps, spans, other="(no host span)"):
    """{span name: ns of idle time}: each gap is charged to the host span
    that overlaps it most, whole; a gap no span touches goes to ``other``."""
    totals = {}
    spans = sorted(spans, key=lambda t: t[1])
    for gs, ge in gaps:
        best, best_ov = other, 0.0
        for name, s, d in spans:
            if s >= ge:
                break
            ov = min(ge, s + d) - max(gs, s)
            if ov > best_ov:
                best, best_ov = name, ov
        totals[best] = totals.get(best, 0.0) + (ge - gs)
    return totals


def top(totals, n=10):
    """[[name, seconds], ...] the ``n`` largest, seconds from ns."""
    items = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v * 1e-9] for k, v in items]


def by_stem(totals):
    """{name with its trailing ``.<n>`` dropped: summed ns}: all the
    ``fusion.<n>`` together, all the ``flash_forward.<n>`` together."""
    out = {}
    for name, ns in totals.items():
        stem = re.sub(r"\.\d+$", "", name)
        out[stem] = out.get(stem, 0.0) + ns
    return out


def kernel_events(events, kernel):
    """Events of the op lines whose instruction is named ``kernel`` or
    ``kernel.<n>`` (Pallas kernels keep their ``name=`` as the HLO name)."""
    out = []
    for n, s, d in events:
        short = op_name(n)
        if short == kernel or short.startswith(kernel + "."):
            out.append((n, s, d))
    return out


class TraceSummary(object):
    """The reductions of one traced window, over all chips used."""

    def __init__(self, data, window_ns, span_prefix="pb."):
        self.window = window_ns
        self.window_s = (window_ns[1] - window_ns[0]) * 1e-9
        self.ops = {p: clip(ev, window_ns)
                    for p, ev in device_lines(data, OPS_LINE).items()}
        self.modules = {p: clip(ev, window_ns)
                        for p, ev in device_lines(data, MODULES_LINE).items()}
        if not any(self.ops.values()):      # no op line: fall back
            self.ops = self.modules
        self.spans = [t for t in clip(host_spans(data, prefix=span_prefix),
                                      window_ns)
                      if t[0] != span_prefix + "window"]
        n = max(1, len(self.ops))
        self.busy_s = sum(busy_union(ev) for ev in self.ops.values()) \
            * 1e-9 / n
        self.n_chips = len(self.ops)

    def first_chip_ops(self):
        return self.ops[sorted(self.ops)[0]] if self.ops else []

    def breakdown(self):
        ops = self.first_chip_ops()
        gaps = idle_gaps(ops, self.window)
        return {"device_ops": top(self_times(ops)),
                "idle_gaps": top(attribute_gaps(gaps, self.spans))}


def find_window(data, name="pb.window"):
    """(start_ns, end_ns) of the benchmark's own window span."""
    spans = host_spans(data, names={name})
    if not spans:
        raise ValueError("trace has no %r span" % name)
    _n, s, d = max(spans, key=lambda t: t[2])
    return (s, s + d)
