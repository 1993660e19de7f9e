"""Share of the first chip's busy time under the KDA layers' mixer nodes,
every pass (forward, recompute, backward): the graph nodes ``layer<i>_kda``
of the layers ``layer_mixers`` calls ``kda`` (the node names
``models.transformer_kda_mla_moe`` gives), read from the step's own scope
map as ``device_scope`` reads it, whose one reduction of the window a run's
readers share.  ``None`` where ``device_scope`` has nothing to read or the
configuration has no such layer."""
from . import device_scope
from .. import flops_ling


def read(ctx):
    nodes = {"layer%d_kda" % i
             for i, (mixer, _mlp) in enumerate(flops_ling.layers(
                 ctx["config"]))
             if mixer == "kda"} if "layer_mixers" in ctx["config"] else set()
    if device_scope._KEPT not in ctx:
        ctx[device_scope._KEPT] = device_scope.reduce_window(ctx)
    if ctx[device_scope._KEPT] is None or not nodes:
        return None
    table, busy = ctx[device_scope._KEPT]
    return 100.0 * sum(ns for node, ns in table["by_node"].items()
                       if node in nodes) / busy
