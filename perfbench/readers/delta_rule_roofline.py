"""Share of its roofline that the gated delta rule reaches: the least time
the chip could take for the rule over the window's steps
(``flops_qwen3_next.delta_rule_call``: the recurrence's operations; q, k, v,
g and β read and o written once, twice that for a backward; recomputation
not counted) — delta-rule layers x steps x (forward + backward) — over the
device time of the events that implement it:

- the events whose name begins ``gated_delta``, where a kernel of that name
  runs;
- else the step's loop events (``while*``) that hold no ``ragged-dot``
  event.  In this family's program those are the chunk scans of the rule
  (forward, the recomputed forward, backward), and a loop that the rule's own
  triangular solve lowers to would count with them; the routed layers' chunk
  loops hold the grouped products and do not count.  A loop inside a loop
  that counts is not counted again.

So the metric reads the same work whatever implements it.  In the XLA form
what the rule prepares outside its loops (the triangular systems, the
products within a chunk) is not in the time, so the share reads high there;
a kernel that takes all of the rule inside reads true.  ``None`` where the
configuration has no delta-rule layer or the trace holds no such event."""
from .. import flops, flops_qwen3_next as count, trace_reduce

KERNEL = "gated_delta"
LOOP = "while"
INSIDE_ROUTED = "ragged-dot"


def rule_events(events):
    """The events that implement the rule (the module's text)."""
    named = [(n, s, d) for n, s, d in events
             if trace_reduce.op_name(n).startswith(KERNEL)]
    if named:
        return named
    routed = sorted(s for n, s, _d in events
                    if trace_reduce.op_name(n).startswith(INSIDE_ROUTED))
    loops = sorted(((s, d, n) for n, s, d in events
                    if trace_reduce.op_name(n).startswith(LOOP)),
                   key=lambda e: (e[0], -e[1]))
    found, outer_end = [], -1
    for s, d, n in loops:
        if s < outer_end:               # inside a loop already looked at
            continue
        outer_end = s + d
        if not any(s <= r < s + d for r in routed):
            found.append((n, s, d))
    return found


def read(ctx):
    cfg, c = ctx["config"], ctx["counters"]
    if ctx["peaks"] is None or not c.get("steps") \
            or "linear_num_value_heads" not in cfg:
        return None
    layers = count.layer_kinds(cfg).count("linear_attention")
    events = rule_events(ctx["trace"].first_chip_ops())
    if not layers or not events:
        return None
    itemsize = 2 if cfg["training"]["compute_dtype"] == "bfloat16" else 4
    least = 0.0
    for backward in (False, True):
        ops, nbytes = count.delta_rule_call(
            c["batch"] // ctx["chips"], int(cfg["linear_num_key_heads"]),
            int(cfg["linear_num_value_heads"]), c["seq"],
            int(cfg["linear_key_head_dim"]),
            int(cfg["linear_value_head_dim"]), itemsize, backward=backward)
        least += flops.roofline_seconds(
            ops, nbytes, ctx["peaks"]["bf16_flops_per_s"],
            ctx["peaks"]["hbm_bytes_per_s"])[0]
    took = sum(d for _n, _s, d in events) * 1e-9
    return 100.0 * least * layers * c["steps"] / took
