"""What the routed layers counted over the window (``counters
["routed_layers"]``: per layer, the device-side sums the ``RoutedExperts``
op keeps as auxiliary state, differenced over the window by the driver).

``local_assignment_pct``  of the ``assignments_per_step`` x steps x layers
                          (token, expert) assignments the routers made, the
                          share that landed on experts held here and was
                          computed here;
``load_max_over_mean``    per layer, the busiest held expert's tokens a step
                          (summed over the steps) over the mean expert's;
                          the mean of that over the layers.  1 is a
                          balanced router.
``None`` where the run counted no routed layer."""


def read(ctx, what):
    c = ctx["counters"]
    layers = c.get("routed_layers")
    if not layers or not c.get("steps"):
        return None
    if what == "local_assignment_pct":
        made = c["assignments_per_step"] * c["steps"] * len(layers)
        return 100.0 * sum(r["local_assignments"] for r in layers) / made
    if what == "load_max_over_mean":
        ratios = [r["peak_tokens_sum"] * len(r["expert_tokens"])
                  / float(r["local_assignments"])
                  for r in layers if r["local_assignments"]]
        return sum(ratios) / len(ratios) if ratios else None
    raise ValueError("routing_counters reads %r not" % (what,))
