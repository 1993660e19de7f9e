"""Small pieces every driver and the harness share."""
import contextlib
import json
import os
import statistics

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def named_file(kind, name):
    """``perfbench/<kind>/<name>.json`` — how the harness finds what
    belongs to one configuration, cell or metric."""
    return os.path.join(HERE, kind, name + ".json")


def cell_entry(bench, name):
    """The cell's entry in ``BENCHMARK.json``."""
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit("perfbench: BENCHMARK.json lists no cell %r (has %s)"
                     % (name, [w["name"] for w in bench["workloads"]]))


def merged(base, override):
    """``base`` with ``override``'s keys on top (one level of dicts)."""
    out = dict(base)
    for k, v in (override or {}).items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = merged(out[k], v)
        else:
            out[k] = v
    return out


def reference_module(config):
    """The plain reference that a configuration's file names."""
    import importlib
    return importlib.import_module("perfbench.reference."
                                   + config["reference"])


def named_function(spec):
    """``"package.module:function"`` -> the function.  How a configuration
    names code of its own (its count of operations), so that a new family
    brings a new file and edits none; a bad name is an error, not a metric
    left out."""
    import importlib
    module, _, name = str(spec).partition(":")
    if not module or not name:
        raise ValueError("want 'package.module:function', got %r" % (spec,))
    return getattr(importlib.import_module(module), name)


def enable_compile_cache():
    """Every program into the persistent cache, sub-second ones too, so that
    only a checkout's first run compiles; the directory is the program's
    choice (``JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache``).
    Returns it."""
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    from mxnet_tpu.parallel import enable_persistent_cache
    return enable_persistent_cache()


def jax_key(seed):
    """A PRNG key from any whole number up to and past 2**32."""
    import jax
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def median(values):
    return statistics.median(values)


class Compiles(object):
    """Counts XLA executables obtained (compiled, or loaded from the
    persistent cache) and the seconds that took, from jax's own monitoring
    events: the set-up cost, and the proof a window paid none.  (Copied
    from ``chip_smoke.py``; PERF.md lists the original.)"""

    def __init__(self):
        import jax.monitoring as monitoring
        self.n = 0
        self.seconds = 0.0
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += seconds

    def _event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return (self.n, self.seconds, self.cache_hits)

    def since(self, snap):
        return {"executables": self.n - snap[0],
                "compile_s": round(self.seconds - snap[1], 3),
                "cache_hits": self.cache_hits - snap[2]}


class Env(object):
    """What a driver is given: the cell, its configuration, the seed, the
    devices, and ``span(name)`` — a host span that lands in the profiler's
    trace under ``pb.<name>`` in a traced run and costs nothing otherwise."""

    def __init__(self, cell_name, cell, config, seed, devices, rehearse,
                 tracing, log):
        self.cell_name = cell_name
        self.cell = cell
        self.config = config
        self.seed = int(seed)
        self.devices = devices
        self.rehearse = rehearse
        self.tracing = tracing
        self.log = log
        self.traffic = cell["traffic"]
        self.limits = cell.get("limits", {})

    def span(self, name):
        if not self.tracing:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation("pb." + name)

    def ctx(self, i=0):
        """The program's context for device ``i`` of this run."""
        import mxnet_tpu as mx
        return mx.cpu(i) if self.rehearse else mx.tpu(i)


def leaf_gaps(prog, ref, skip=()):
    """{leaf: |prog - ref| / max(ref, median of ref)}: the gap between two
    norms of each leaf, measured against the reference's norm of that leaf
    or of the median leaf, whichever is larger."""
    names = [n for n in ref if n not in skip]
    med = statistics.median(float(ref[n]) for n in names)
    return {n: abs(float(prog[n]) - float(ref[n]))
            / max(float(ref[n]), med, 1e-30) for n in names}


def dead_leaves(ref_grad_norms, share=1e-3):
    """Leaves whose reference gradient is nought to rounding: under a
    thousandth of the median leaf's.  They move by round-off alone and are
    left out of the parameter-change comparison (a rule on the reference's
    gradient, not a list of names)."""
    med = statistics.median(float(v) for v in ref_grad_norms.values())
    return {n for n, v in ref_grad_norms.items() if float(v) < share * med}
