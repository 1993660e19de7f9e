"""One small reader per kind of per-layer metric.

``read(ctx, **args)`` gets the traced run's context — ``ctx["trace"]``
(a ``trace_reduce.TraceSummary``), ``ctx["counters"]`` (what the driver
counted), ``ctx["cell"]``, ``ctx["config"]``, ``ctx["peaks"]``,
``ctx["chips"]``, ``ctx["elapsed_s"]`` — and returns the number, or ``None``
where it finds nothing to read (the harness then leaves the metric out; a
share of a roofline or of a peak is never reported as 0).
"""
