"""Phase spans: one name, three sinks.

``span("data_wait")`` / ``span("h2d")`` / ``span("step_dispatch")`` /
``span("allreduce")`` / ``span("ckpt_save")`` time a phase on the host.
Every span, with no switch,

(a) opens a ``jax.profiler.TraceAnnotation("mx." + name)``, so any
    profiler session (``mx.profiler.profiler_set_state("run")``, a
    benchmark's traced run) holds the program's spans in the same
    xplane as the device's ``XLA Ops``, on the trace's clock;
(b) appends a closed-span record ``(id, parent_id, name, step, t0_ns,
    t1_ns, thread)`` to a bounded in-memory ring (``perf_counter_ns``
    times; the parent is the enclosing span on the same thread; the
    oldest record is dropped first).  :func:`steps`, :func:`self_ns`
    and :func:`snapshot` read it.

With ``MXTPU_TELEMETRY=1`` it also (c) emits a ``span`` record to the
event log (plus trace/span ids under ``MXTPU_TRACE=1``).  The variable
adds the log and nothing else.
"""
from __future__ import annotations

import collections
import itertools
import threading
import time

from jax.profiler import TraceAnnotation as _Annotation

from . import events
from . import trace as _trace
from .phases import STEP_DISPATCH, TRAIN_PHASES

__all__ = ["span", "SPAN_NAMES", "timed_iter", "overlap_report",
           "SpanRecord", "steps", "self_ns", "snapshot", "reset"]

#: canonical phase names (free-form names are allowed; these are the
#: ones the built-in wiring emits and mxtop groups by).  Compat alias
#: for the shared registry — the ONE definition lives in
#: :mod:`.phases` so spans / profiler.annotate / parse_log columns
#: can't drift.
SPAN_NAMES = TRAIN_PHASES

#: prefix of the program's spans in a profiler trace (a benchmark's own
#: annotations carry another)
TRACE_PREFIX = "mx."

#: closed spans kept.  A fit step leaves about ten, so this holds some
#: 400 steps: more than any traced window, at well under a megabyte.
RING_CAPACITY = 4096

#: what a ring record holds, in order
_FIELDS = ("id", "parent_id", "name", "step", "t0_ns", "t1_ns", "thread")

_ring = collections.deque(maxlen=RING_CAPACITY)
_next_id = itertools.count(1).__next__
_local = threading.local()


class _Span(object):
    """An open span; ``t0_ns`` / ``t1_ns`` / ``dur_s`` can be read from it
    once the ``with`` block has closed."""
    __slots__ = ("name", "step", "fields", "log", "id", "parent_id",
                 "t0_ns", "t1_ns", "_stack", "_ann", "_ids")

    def __init__(self, name, step, fields):
        self.name = name
        self.step = step
        self.fields = fields
        self.log = True         # False: keep this span out of the event log

    def __enter__(self):
        try:
            stack = _local.stack
        except AttributeError:
            stack = _local.stack = []
        self._stack = stack
        self.parent_id = stack[-1] if stack else None
        self.id = _next_id()
        stack.append(self.id)
        self._ann = _Annotation(TRACE_PREFIX + self.name)
        self._ann.__enter__()
        self.t0_ns = t0 = time.perf_counter_ns()
        # the log's own ids (MXTPU_TRACE=1): push a trace frame so this
        # span's record carries trace/span/parent ids and emits inside
        # it bind to it.  The log is asked with the clock reading just
        # taken: two reads a span, however slow the host's clock is.
        self._ids = (_trace.begin_span(self.name) or None) \
            if events.get(t0 * 1e-9) is not None else None
        return self

    def __exit__(self, *exc):
        self.t1_ns = t1 = time.perf_counter_ns()
        self._ann.__exit__(*exc)
        if self._ids is not None:
            _trace.end_span()
        self._stack.pop()
        _ring.append((self.id, self.parent_id, self.name, self.step,
                      self.t0_ns, t1, threading.get_ident()))
        if self.log and events.get(t1 * 1e-9) is not None:
            events.emit("span", step=self.step, name=self.name,
                        dur_ms=round((t1 - self.t0_ns) * 1e-6, 3),
                        **(self._ids or {}), **self.fields)
        return False

    @property
    def dur_s(self):
        return (self.t1_ns - self.t0_ns) * 1e-9


def span(name, step=None, **fields):
    """Context manager timing one phase: a trace annotation and a ring
    record always, a ``span`` event when telemetry is on."""
    return _Span(name, step, fields)


def timed_iter(iterable, name="data_wait", step_from=None):
    """Pass-through generator that times each ``next()`` under ``span``
    — the input-pipeline wait the fit loops can't see otherwise.

    ``step_from``: optional zero-arg callable giving the step to tag
    each span with (called per batch, AFTER the fetch).
    """
    it = iter(iterable)
    while True:
        with span(name) as sp:
            try:
                item = next(it)
            except StopIteration:
                sp.log = False      # the fetch that found the end
                return
            if step_from is not None:
                sp.step = step_from()
        yield item


# ----------------------------------------------------------------------
# reading the ring
# ----------------------------------------------------------------------
class SpanRecord(object):
    """One closed span as the readers see it, with its ``children`` (by
    start time) where it came from :func:`steps`."""
    __slots__ = _FIELDS + ("children",)

    def __init__(self, *values):
        for field, value in zip(_FIELDS, values):
            setattr(self, field, value)
        self.children = []

    @property
    def dur_ns(self):
        return self.t1_ns - self.t0_ns

    def walk(self):
        """This span, then every descendant, parents before children and
        siblings by start time."""
        yield self
        for child in self.children:
            for rec in child.walk():
                yield rec

    def named(self, *names):
        """The spans of :meth:`walk` called one of ``names``."""
        return [rec for rec in self.walk() if rec.name in names]


def snapshot():
    """Everything in the ring, oldest first, as plain dicts (what a
    flight dump carries: the last phases before a crash)."""
    return [dict(zip(_FIELDS, rec)) for rec in list(_ring)]


def reset():
    """Empty the ring (tests)."""
    _ring.clear()


def steps(n):
    """The last ``n`` step roots, newest last, each a :class:`SpanRecord`
    with its descendants linked under ``children``.  A step root is a
    parentless span that is, or contains, a ``step_dispatch``: a
    ``fit_step`` of ``Module.fit``, or the bare ``step_dispatch`` of a
    ``ShardedTrainer.step``.  Chosen by parent links and ring order,
    never by ``step`` numbers, which start again with every ``fit``.
    Fewer than ``n`` come back where the ring holds fewer."""
    raw = list(_ring)
    if not raw or n <= 0:
        return []
    children = {}
    for rec in raw:
        children.setdefault(rec[1], []).append(rec)
    # children close before their parents, so a full ring may have
    # dropped some of an old root's: such a root is not whole
    whole_from = raw[0][5] if len(raw) == _ring.maxlen else 0

    def build(rec):
        node = SpanRecord(*rec)
        node.children = [build(c) for c in sorted(
            children.get(node.id, ()), key=lambda c: c[4])]
        return node

    out = []
    for rec in reversed(children.get(None, ())):
        if rec[4] < whole_from:
            break
        root = build(rec)
        if root.named(STEP_DISPATCH):
            out.append(root)
            if len(out) == n:
                break
    out.reverse()
    return out


def self_ns(span):
    """A span's duration minus the part its children cover (they ran on
    its thread, one after another, inside it)."""
    return span.dur_ns - sum(c.dur_ns for c in span.children)


def overlap_report(records, phases=("data_wait", "h2d")):
    """Did the async machinery actually overlap?  From merged event
    records (:func:`..aggregate.read_events` output, or any list of
    record dicts), compute per-rank and pod-wide::

        overlap_ratio = serial_ms / wall_ms

    where ``serial_ms`` sums every ``phases`` span PLUS every ``step``
    record's duration inside the steady-state window, and ``wall_ms``
    is the elapsed wall clock between the rank's first and last
    ``step`` record.  The first step record bounds the window but is
    excluded from the sums, so compile time never pollutes the ratio.

    Serial execution: phases and steps tile the wall exactly, ratio
    ≈ 1.0 (slightly below — metric/callback time belongs to no phase).
    With the async feed on, the producer thread's ``data_wait``/``h2d``
    spans run DURING device compute, the same host time is counted in
    two phases, and the ratio rises above 1 — "wall < Σ phases" is the
    proof the dead time went under the step.  ``phases`` deliberately
    excludes ``allreduce``/``kv_barrier``: those spans nest inside the
    ``step`` record's window and would double-count serially.  So do the
    loop's own ``data_wait``/``h2d`` where the step records cover whole
    iterations (``Module.fit``: ``timing="iteration"``); there only the
    producer thread's spans (tagged ``async``) are added.

    Returns ``{"overlap_ratio", "wall_ms", "serial_ms", "steps",
    "phase_ms": {phase: total}, "phase_p50_ms": {phase: p50},
    "per_rank": {rank: {...same shape...}}}``; ratios are None when a
    rank has fewer than two step records.
    """
    per_rank_events = {}
    for rec in records:
        if not isinstance(rec, dict):
            continue
        kind = rec.get("kind")
        if kind not in ("span", "step"):
            continue
        per_rank_events.setdefault(rec.get("rank") or 0, []).append(rec)

    def _p50(vals):
        vals = sorted(vals)
        n = len(vals)
        if not n:
            return None
        mid = n // 2
        return vals[mid] if n % 2 else 0.5 * (vals[mid - 1] + vals[mid])

    per_rank = {}
    tot_wall = tot_serial = tot_steps = 0.0
    pod_phase = {}
    pod_phase_durs = {}
    for rank, recs in sorted(per_rank_events.items()):
        steps = [r for r in recs if r.get("kind") == "step"
                 and r.get("wall_ms") is not None
                 and r.get("dur_ms") is not None]
        steps.sort(key=lambda r: r["wall_ms"])
        entry = {"overlap_ratio": None, "wall_ms": None, "serial_ms": None,
                 "steps": len(steps), "phase_ms": {}, "phase_p50_ms": {}}
        per_rank[rank] = entry
        if len(steps) < 2:
            continue
        t0, t1 = steps[0]["wall_ms"], steps[-1]["wall_ms"]
        wall = float(t1) - float(t0)
        if wall <= 0:
            continue
        serial = sum(float(r["dur_ms"]) for r in steps[1:])
        whole = any(r.get("timing") == "iteration" for r in steps)
        phase_durs = {}
        for r in recs:
            if r.get("kind") != "span" or r.get("name") not in phases:
                continue
            if whole and not r.get("async"):
                continue
            w = r.get("wall_ms")
            if w is None or not (t0 < w <= t1):
                continue
            d = float(r.get("dur_ms") or 0.0)
            serial += d
            phase_durs.setdefault(r["name"], []).append(d)
        entry.update(
            wall_ms=round(wall, 3), serial_ms=round(serial, 3),
            overlap_ratio=round(serial / wall, 4),
            phase_ms={k: round(sum(v), 3)
                      for k, v in sorted(phase_durs.items())},
            phase_p50_ms={k: round(_p50(v), 3)
                          for k, v in sorted(phase_durs.items())})
        tot_wall += wall
        tot_serial += serial
        tot_steps += len(steps)
        for k, v in phase_durs.items():
            pod_phase[k] = pod_phase.get(k, 0.0) + sum(v)
            pod_phase_durs.setdefault(k, []).extend(v)
    return {
        "overlap_ratio": round(tot_serial / tot_wall, 4) if tot_wall else None,
        "wall_ms": round(tot_wall, 3) if tot_wall else None,
        "serial_ms": round(tot_serial, 3) if tot_wall else None,
        "steps": int(tot_steps),
        "phase_ms": {k: round(v, 3) for k, v in sorted(pod_phase.items())},
        "phase_p50_ms": {k: round(_p50(v), 3)
                         for k, v in sorted(pod_phase_durs.items())},
        "per_rank": per_rank,
    }
