"""Pod-wide telemetry: structured event log, phase spans, counters,
cross-rank aggregation.

The event log is off by default.  Set ``MXTPU_TELEMETRY=1`` (and optionally
``MXTPU_TELEMETRY_DIR=/some/scratch``) and every rank appends typed
JSONL records — step timings, phase spans, derived counters, faults,
checkpoint lifecycle, collective traffic — to its own
``events-rank*.jsonl``.  ``tools/mxtop.py`` renders the merged pod
report; :mod:`.aggregate` publishes live per-rank summaries over the
coordination-service KV.  Schema and usage: docs/observability.md.

The fit loops / trainer / kvstore / resilience seams call
:func:`record_step` and :func:`spans.span`.  A span is always a trace
annotation (``mx.<name>``) and a record in :mod:`.spans`' in-memory ring,
about 2 us; with telemetry off neither call writes to the log, and
``record_step`` only notes the step in the flight recorder's ring.

The device's side of a step is named by :mod:`.device_scopes`: the scopes
the step is lowered under, and the map from a compiled step's
instructions to them ("Device time by scope" in docs/observability.md).
"""
from __future__ import annotations

from . import (events, spans, counters, aggregate, phases, trace,
               flight, slo, locktrace, retrace, metrics, sloengine,
               device_scopes)
from .events import (enabled, emit, flush, refresh, run_id, last_fault,
                     EventLog)
from .phases import PHASES, TRAIN_PHASES, SERVE_PHASES
from .spans import span, timed_iter, SPAN_NAMES, overlap_report
from .counters import (StepStats, percentile, global_stats,
                       emit_trainer_counters, emit_sentinel_counters)
from .aggregate import (publish_summary, collect_summaries,
                        heartbeat_ages, pod_view, read_events,
                        build_report, EventTailer)

__all__ = [
    "events", "spans", "counters", "aggregate", "phases", "trace",
    "flight", "slo", "locktrace", "retrace", "metrics", "sloengine",
    "device_scopes",
    "enabled", "emit", "flush", "refresh", "run_id", "last_fault",
    "EventLog",
    "PHASES", "TRAIN_PHASES", "SERVE_PHASES",
    "span", "timed_iter", "SPAN_NAMES", "overlap_report",
    "StepStats", "percentile", "global_stats",
    "emit_trainer_counters", "emit_sentinel_counters",
    "publish_summary", "collect_summaries", "heartbeat_ages",
    "pod_view", "read_events", "build_report", "EventTailer",
    "record_step",
]

#: publish a KV summary every N recorded steps (override via env)
_PUBLISH_EVERY = 10


def record_step(step, dur_s, batch_size=None, epoch=None, **fields):
    """The one call a training loop makes per step when telemetry is
    on: emits the ``step`` record, folds the timing into the process
    :class:`StepStats`, and every ``_PUBLISH_EVERY`` steps pushes the
    compact summary to the coordination KV for the live pod view.
    No-op when telemetry is off (the step still lands in the crash
    flight recorder's bounded ring); never raises."""
    try:
        flight.note("step", step, {"dur_ms": round(float(dur_s) * 1e3, 3)})
    except Exception:
        pass
    log = events.get()
    if log is None:
        return
    try:
        stats = counters.global_stats()
        stats.observe(dur_s, step=step, batch_size=batch_size)
        rec = {"dur_ms": round(float(dur_s) * 1e3, 3)}
        if batch_size:
            rec["batch_size"] = batch_size
            if dur_s > 0:
                rec["samples_per_sec"] = round(batch_size / dur_s, 2)
        if epoch is not None:
            rec["epoch"] = epoch
        rec.update(fields)
        log.emit("step", step=step, **rec)
        if step is not None and step % _PUBLISH_EVERY == 0:
            aggregate.publish_summary(step=step)
    except Exception:
        pass
