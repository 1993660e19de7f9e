"""Executor: bind a Symbol to devices and run it as ONE XLA computation.

This is the TPU-native replacement of the reference's GraphExecutor
(``src/symbol/graph_executor.cc``, ``Executor::Bind`` at :1151) — SURVEY §3.2:
the whole Init pipeline (backward pass construction, context assignment,
memory planning, op instantiation, bulk segments) collapses into tracing the
graph into a jax function and letting XLA compile/fuse/plan it:

- ``MakeBackwardPass`` (static_graph.cc:395)  -> jax.vjp over the traced fwd
- grad_req write/add/null (OpReqType)         -> post-vjp combine
- memory plan + GraphStoragePool              -> XLA buffer planning/donation
- bulk segments / cached engine ops           -> a single jitted computation
- per-shape rebinding (Executor.reshape)      -> jit's shape-keyed compile cache

Monitor callbacks (graph_executor.cc:937) run via an eager interpret mode.
"""
from __future__ import annotations

import numpy as _np

import jax
import jax.extend
import jax.numpy as jnp

from .base import MXNetError
from .context import Context
from .ndarray import NDArray, zeros
from . import random as _random
from .observability import device_scopes as _device_scopes
from .observability import spans as _spans
from .kernels.delta_rule import DELTA_RESIDUALS, KDA_RESIDUALS
from .ops.moe import ROUTED_RESIDUALS
from .parallel.ring_attention import FLASH_RESIDUALS
from .train_step import (apply_updates, compute_cast, loss_and_grads,
                         no_cast, preprocess_grads)

_ZERO_KEY = None


def _zero_key():
    global _ZERO_KEY
    if _ZERO_KEY is None:
        # the first caller may be inside a trace (``_saved_residuals``):
        # the key kept for the process must be an array, not its tracer
        with jax.ensure_compile_time_eval():
            _ZERO_KEY = jax.random.PRNGKey(0)
    return _ZERO_KEY


__all__ = ["Executor", "simple_bind", "trace_residual_bytes"]


def _saved_residuals(trace, arg_values, aux_values, wrt_names):
    """``[(aval, checkpoint_name or None)]`` of what jax's vjp would
    save across ``trace`` when differentiating wrt ``wrt_names``: the
    residual outputs of the linearized trace's jaxpr (how
    ``jax.ad_checkpoint.saved_residuals`` finds them), each with the
    name its producer gave it.  jax's own listing describes a residual
    by the equation that made it, so it calls a named value ``named``
    only where nothing stands between: here the name is followed back
    through the ``reduce_precision`` jax puts on a saved value that the
    forward pass also reads, through the branches of the ``cond`` that
    ``kernels.common.dispatch`` stages and through the ``shard_map``
    that ``sharded_self_attention`` wraps the kernel in."""
    wrt = {n: arg_values[n] for n in wrt_names}

    def f(wrt_values):
        merged = dict(arg_values)
        merged.update(wrt_values)
        return trace(merged, aux_values, _zero_key(), True)

    closed, (_outs, f_jvp) = jax.make_jaxpr(
        lambda w: jax.linearize(f, w), return_shape=True)(wrt)
    jaxpr = closed.jaxpr
    n_res = len(jax.tree_util.tree_leaves(f_jvp))
    residuals = jaxpr.outvars[len(jaxpr.outvars) - n_res:]
    name_of = _namer(jaxpr)
    return [(v.aval, name_of(v)) for v in residuals]


def _namer(jaxpr):
    """``var -> the checkpoint_name it carries, or None`` for the
    variables of ``jaxpr`` (see ``_saved_residuals``)."""
    made_by = {v: e for e in jaxpr.eqns for v in e.outvars}
    named_as = {e.invars[0]: e for e in jaxpr.eqns
                if e.primitive.name == "name"}

    def name_of(var):
        while isinstance(var, jax.extend.core.Var):
            eqn = named_as.get(var) or made_by.get(var)
            if eqn is None:
                return None
            if eqn.primitive.name == "name":
                return eqn.params["name"]
            if eqn.primitive.name == "reduce_precision":
                var = eqn.invars[0]
                continue
            if eqn.primitive.name not in ("cond", "shard_map"):
                return None
            # the equation's output i is its inner jaxprs' output i
            i = eqn.outvars.index(var)
            for inner in eqn.params.get("branches") or (
                    eqn.params["jaxpr"],):
                inner = getattr(inner, "jaxpr", inner)
                name = _namer(inner)(inner.outvars[i])
                if name is not None:
                    return name
            return None
    return name_of


def _nbytes(aval):
    size = getattr(aval, "size", None)
    dtype = getattr(aval, "dtype", None)
    if size is None or dtype is None:
        return 0
    return int(size) * dtype.itemsize


def trace_residual_bytes(trace, arg_values, aux_values, wrt_names):
    """Bytes of residuals jax's vjp would save across ``trace`` when
    differentiating wrt ``wrt_names`` — the backend-independent
    activation-memory number (what mirroring shrinks; the values a
    mirrored segment keeps by name, ``trace_mirror_kept``, are counted).
    Shared by Executor.backward_residual_bytes, the multichip dryrun, and
    the mirror tests."""
    return sum(_nbytes(aval) for aval, _name in _saved_residuals(
        trace, arg_values, aux_values, wrt_names))


def trace_mirror_kept(trace, arg_values, aux_values, wrt_names):
    """``[(name, bytes)]`` of the residuals of ``trace`` that are saved
    under a ``checkpoint_name``: what the mirrored segments keep by
    ``KEPT`` instead of recomputing, one entry a value (the same names
    come back once a block; outside a mirrored segment a named value is
    saved like any other and listed too).  Empty where no segment holds
    a producer that names anything."""
    return [(name, _nbytes(aval)) for aval, name in _saved_residuals(
        trace, arg_values, aux_values, wrt_names) if name is not None]


def _as_list(obj, names, what):
    """Normalize list-or-dict user input to a list aligned with ``names``."""
    if obj is None:
        return [None] * len(names)
    if isinstance(obj, dict):
        return [obj.get(n) for n in names]
    obj = list(obj)
    if len(obj) != len(names):
        raise MXNetError("%s: expected %d entries (%s), got %d"
                         % (what, len(names), names, len(obj)))
    return obj




class _Program:
    """Compiled form of a symbol graph: pure trace + jitted entries."""

    __slots__ = ("trace", "jit_forward", "jit_fwd_bwd", "needs_rng",
                 "mirrored", "_jit_forward_mon", "monitor_sink")

    def __init__(self, trace, jit_forward, jit_fwd_bwd, needs_rng,
                 mirrored=False):
        self.trace = trace
        self.jit_forward = jit_forward
        self.jit_fwd_bwd = jit_fwd_bwd
        self.needs_rng = needs_rng
        self.mirrored = mirrored        # some segment is a checkpoint
        self._jit_forward_mon = None
        self.monitor_sink = None

    def mirror_kept(self, arg_values, aux_values, wrt_names):
        """``[(name, bytes)]`` of what the mirrored segments save by name
        (``KEPT``) instead of recomputing, for these shapes: the counter
        that says a kernel's second forward call, or the routed layer's
        second sort, is gone.  Traced on
        demand, like ``trace_residual_bytes``; ``[]`` for a program with
        no mirrored segment, whose named values are saved like all its
        others."""
        if not self.mirrored:
            return []
        return trace_mirror_kept(self.trace, arg_values, aux_values,
                                 wrt_names)

    def jit_forward_monitored(self):
        """Compiled forward that streams every op output to the installed
        monitor through ``jax.debug.callback`` — per-op stats come from the
        SAME XLA computation that training runs, not an eager re-trace
        (parity: graph_executor.cc:937-951 fires inside the real executor).
        The sink is read through ``self`` at call time so one compiled
        program serves every executor bound to this symbol."""
        if self._jit_forward_mon is None:
            import functools

            def dispatch(name, value):
                sink = self.monitor_sink
                if sink is not None:
                    sink(name, value)

            def monitored(arg_values, aux_values, rng, is_train):
                def mon(name, o):
                    jax.debug.callback(functools.partial(dispatch, name), o)
                return self.trace(arg_values, aux_values, rng, is_train,
                                  monitor=mon)

            self._jit_forward_mon = jax.jit(monitored,
                                            static_argnames=("is_train",))
        return self._jit_forward_mon


def _consumable(arrays, beside=None):
    """``{name: buffer}`` of ``arrays`` for a call that donates them: each
    NDArray's own buffer where it may be given away
    (``NDArray._donatable``), and for the others one batched copy on the
    device, so that the donation takes nothing from whoever shares them.
    In a training loop that is the first step after the parameters were
    set; from then on every buffer is the step's own output.  A copy is
    placed like the array it copies, or like ``beside[name]``."""
    out = {n: nd._donatable() for n, nd in arrays.items()}
    shared = {n: arrays[n].data for n, buf in out.items() if buf is None}
    if shared:
        out.update(jax.device_put(
            shared, {n: (beside or shared)[n].sharding for n in shared},
            may_alias=False))
    return out


# The ``checkpoint_name``s a mirrored segment saves instead of
# recomputing.  The rule: a producer names what a kernel hands its own
# backward when recomputing it costs a second call of the kernel, and it
# names that set whole.  Four producers do: the flash forward (its
# operands q, k, v with its output and softmax statistics), the gated
# delta rule's forward sweep (q, k, v, the chunk scalars, the chunk states
# and the output, which the gated norm after it reads), KDA's forward
# sweep (the same, its running sums of the decay a channel and β apart)
# and the routed
# layer (the chosen experts and their weights, the sorted order and the
# counts, and the routed sum: its recomputation is the grouped products
# again).  Operands and discrete choices alone would be cheap to
# recompute, but in bfloat16 a recomputation is not the first computation
# to the bit: statistics kept from the first call no longer normalise
# scores made from recomputed operands (PERF.md section 6, PR 32: a 1 %
# error in ZAYA1's gradient norms), and a top-k taken again may order a
# near-tie otherwise than the kept sort did.  A named value is saved only
# where the segment's backward reads it: the routed sum where a learned
# scale follows the layer (ZAYA1) and not where an add does.  Held a
# block: the flash kernel's 336 MB in JoyAI (q and k 101 MB each) and
# 42 MB in ZAYA1, a delta-rule layer's 337 MB in Qwen3-Next (the states
# 134 MB), a KDA layer's 538 MB in Ling (the decays' running sums 134 MB
# and the states 134 MB), a routed layer's 34 MB where the sum is kept and
# under 2 MB where not, against a block input of 33.5 MB.  A segment that holds no
# such name saves what a policy-less checkpoint saves: its inputs.
KEPT = FLASH_RESIDUALS + DELTA_RESIDUALS + KDA_RESIDUALS + ROUTED_RESIDUALS


def mirror_checkpoint(fn):
    """``fn`` as a mirrored segment runs it: recomputed in backward from
    its inputs, but for the values named in ``KEPT``."""
    return jax.checkpoint(
        fn, policy=jax.checkpoint_policies.save_only_these_names(*KEPT))


def _mirror_segments(op_nodes):
    """Partition the op schedule into checkpoint segments — the
    jax-native MakeBackwardPass mirror map (static_graph.cc:396-440).

    A node recomputes in backward ("is mirrored") under the reference's
    need_mirror rules (static_graph.cc:409-425): its ``force_mirroring``
    attr, or MXNET_BACKWARD_DO_MIRROR=1 for every op type outside the
    reference's skip list (heavy MXU ops whose recompute costs more than
    the activation is worth), except every 100th eligible node (the
    reference's default: a periodic keep so recompute chains stay
    bounded).  Consecutive mirrored nodes form ONE
    ``jax.checkpoint`` segment — internals dropped from the residual set
    and recomputed in backward, but for the values their producer names
    in ``KEPT`` — split at differing ``mirror_stage``
    attrs so users can pin stage boundaries.  ``op_nodes`` excludes
    variables (hoisted to a prelude: a weight/bias variable must not
    break an otherwise-contiguous mirror run).  Returns
    [(is_mirror, [nodes])].
    """
    import os as _os
    do_mirror = int(_os.environ.get("MXNET_BACKWARD_DO_MIRROR", "0") or 0)
    mirror_step = 100
    counter = [0]
    env_skip = {"Convolution", "FullyConnected", "Concat", "SoftmaxOutput",
                "CuDNNBatchNorm"}

    def need(node):
        t = type(node.op).op_name or type(node.op).__name__
        if t == "Dropout":
            return False
        if str(node.attrs.get("force_mirroring", "")).lower() in ("true",
                                                                  "1"):
            return True
        if not do_mirror:
            return False
        if t in env_skip:
            return False
        counter[0] += 1
        if counter[0] % mirror_step == 0:
            return False
        return True

    segments = []
    for node in op_nodes:
        m = need(node)
        stage = node.attrs.get("mirror_stage") if m else None
        if segments and segments[-1][0] == m and segments[-1][2] == stage:
            segments[-1][1].append(node)
        else:
            segments.append([m, [node], stage])
    return [(m, nodes) for m, nodes, _stage in segments]


# Cross-symbol program registry (docs/perf.md "Overlap", compile cache):
# the per-symbol _jit_cache only helps when the SAME Symbol object is
# rebound, but common flows (module rebind after a bucketing change,
# Executor.reshape, rebuilding the net from the same script) produce a
# *fresh* Symbol with an identical graph.  Keying on the graph JSON hash
# lets those reuse the traced program instead of re-tracing + re-jitting.
_PROGRAM_REGISTRY = {}


def program_registry_stats():
    """Compile-cache counters ({"hits", "misses", "lowerings"}) plus
    this registry's entry count — the observable contract the serving
    warmup and the Predictor reuse tests assert on ("zero lowerings
    after warmup" is a delta of these numbers)."""
    from .parallel import overlap as _overlap
    stats = _overlap.compile_cache_stats()
    stats["programs"] = len(_PROGRAM_REGISTRY)
    return stats


def _bind_env_fingerprint(validate_mode):
    """Host state a program build bakes in beyond (symbol, group2ctx):
    the compute dtype, the backward-mirror env read by
    ``_mirror_segments``, and the active validation-rules fingerprint.
    Folded into both the per-symbol ``_jit_cache`` key and (via
    ``ctx_key``) the global ``_PROGRAM_REGISTRY`` key so a flag flip
    between binds lowers a fresh program instead of reusing a stale one
    (MXL-X002: every baked ingredient must be a key ingredient)."""
    import os
    if validate_mode == "off":
        rules = ("off",)
    else:
        from .analysis import RULE_REGISTRY
        rules = (validate_mode,) + tuple(sorted(RULE_REGISTRY))
    return (os.environ.get("MXNET_COMPUTE_DTYPE", ""),
            os.environ.get("MXNET_BACKWARD_DO_MIRROR", ""),
            rules)


def _lookup_program(symbol, ctx_key, group2ctx):
    import os
    from .parallel import overlap as _overlap
    try:
        gkey = (_overlap.graph_fingerprint(symbol), ctx_key,
                os.environ.get("MXNET_COMPUTE_DTYPE", ""))
    except Exception:
        _overlap.note_lowering()
        return _build_program(symbol, group2ctx)
    prog = _PROGRAM_REGISTRY.get(gkey)
    if prog is None:
        _overlap.note_lowering()
        prog = _PROGRAM_REGISTRY[gkey] = _build_program(symbol, group2ctx)
    else:
        _overlap.note_hit()
    return prog


def _build_program(symbol, group2ctx):
    """Flatten the symbol into an executable schedule and jit it.

    Parity: the GraphExecutor Init pipeline (graph_executor.h:40-72); device
    placement for ctx_group nodes is resolved here (AssignContext analog,
    graph_executor.cc:391) with XLA inserting the transfers.  Mirrored
    nodes (static_graph.cc:396 MakeBackwardPass) lower to per-segment
    ``jax.checkpoint``: their activations leave the residual set and are
    recomputed during the vjp — the TPU-native memory/FLOPs trade.  The
    checkpoint (``mirror_checkpoint``) saves the names in ``KEPT``, so a
    block that holds the flash kernel, the delta rule's or a routed layer
    recomputes everything but the kernels' calls, their operands and the
    routing's choices.
    """
    topo = symbol._topo()
    heads = list(symbol._heads)
    n_rng = sum(1 for n in topo if not n.is_variable and n.op.need_rng)
    needs_rng = n_rng > 0
    n_rng = max(n_rng, 1)

    node_device = {}
    for node in topo:
        group = node.attrs.get("ctx_group")
        if group and group in group2ctx:
            node_device[id(node)] = group2ctx[group].jax_device

    variables = [n for n in topo if n.is_variable]
    segments = _mirror_segments([n for n in topo if not n.is_variable])
    any_mirror = any(m for m, _ in segments)
    # (id(node), out_idx) values needed beyond each mirror segment: by
    # external consumers or as graph heads — everything else is internal
    # to its segment and free to drop+recompute.  Variables live in no
    # segment (prelude; seg -2) so they are always segment inputs.
    seg_of = {}
    for si, (m, nodes) in enumerate(segments):
        for n in nodes:
            seg_of[id(n)] = si
    ext_needed = {i: [] for i in range(len(segments))}
    if any_mirror:
        seen = set()

        def _mark(key, consumer_seg):
            psi = seg_of.get(key[0], -2)
            if psi >= 0 and psi != consumer_seg and key not in seen:
                seen.add(key)
                ext_needed[psi].append(key)

        for node in topo:
            if node.is_variable:
                continue
            for c, ci in node.inputs:
                _mark((id(c), ci), seg_of[id(node)])
        for n, i in heads:
            _mark((id(n), i), -1)

    def _run_node(node, values, aux_values, aux_out, key, is_train,
                  monitor):
        op = node.op
        ins = [values[(id(c), ci)] for c, ci in node.inputs]
        aux_names = ["%s_%s" % (node.name, a)
                     for a in op.list_auxiliary_states()]
        aux_in = [aux_values[a] for a in aux_names]
        # the node's name on every instruction it lowers to, in each pass
        # over it (observability/device_scopes.py); metadata, no fusion
        # moves
        with jax.named_scope(node.name):
            outs, aux_updates = op.forward(ins, aux_in, is_train, key)
        dev = node_device.get(id(node))
        if dev is not None:
            outs = [jax.device_put(o, dev) for o in outs]
        for i, o in enumerate(outs):
            values[(id(node), i)] = o
        if aux_updates is not None:
            for a, u in zip(aux_names, aux_updates):
                aux_out[a] = u
        if monitor is not None:
            for oname, o in zip(op.list_outputs(), outs):
                monitor("%s_%s" % (node.name, oname), o)

    def _seg_aux_names(nodes):
        names = []
        for node in nodes:
            names.extend("%s_%s" % (node.name, a)
                         for a in node.op.list_auxiliary_states())
        return names

    def trace(arg_values, aux_values, rng, is_train, monitor=None):
        """Evaluate the graph; pure & jax-traceable (the 'StaticGraph run')."""
        values = {}
        aux_out = dict(aux_values)
        rngs = jax.random.split(rng, n_rng) if needs_rng else None
        rng_i = 0
        # a monitor observes every op output: that pins all activations
        # live anyway AND a checkpointed callback would double-fire on
        # recompute — monitored traces run unmirrored
        mirror_active = any_mirror and monitor is None
        for node in variables:
            values[(id(node), 0)] = arg_values[node.name]
        for si, (is_mirror, nodes) in enumerate(segments):
            seg_n_rng = sum(1 for n in nodes if n.op.need_rng)
            if not (is_mirror and mirror_active):
                for node in nodes:
                    key = None
                    if node.op.need_rng:
                        key = rngs[rng_i]
                        rng_i += 1
                    _run_node(node, values, aux_values, aux_out, key,
                              is_train, monitor)
                continue

            # in the order the segment first reads them: an order by
            # id() differs from process to process, and with it the
            # lowered text and the compile cache's key
            ext_keys = list(dict.fromkeys(
                (id(c), ci) for n in nodes for c, ci in n.inputs
                if seg_of.get(id(c), -2) != si))
            out_keys = ext_needed[si]
            aux_names = _seg_aux_names(nodes)
            seg_keys = (rngs[rng_i:rng_i + seg_n_rng]
                        if needs_rng else None)
            rng_i += seg_n_rng

            def seg_fn(ext_vals, aux_in, keys, _nodes=nodes,
                       _ext_keys=ext_keys, _out_keys=out_keys):
                local = dict(zip(_ext_keys, ext_vals))
                local_aux_out = {}
                ki = 0
                for node in _nodes:
                    key = None
                    if node.op.need_rng:
                        key = keys[ki]
                        ki += 1
                    _run_node(node, local, aux_in, local_aux_out, key,
                              is_train, None)
                return [local[k] for k in _out_keys], local_aux_out

            seg_aux_in = {a: aux_values[a] for a in aux_names}
            seg_outs, seg_aux_out = mirror_checkpoint(seg_fn)(
                [values[k] for k in ext_keys], seg_aux_in, seg_keys)
            for k, v in zip(out_keys, seg_outs):
                values[k] = v
            aux_out.update(seg_aux_out)
        outputs = [values[(id(n), i)] for n, i in heads]
        return outputs, aux_out

    def fwd_bwd(arg_values, aux_values, rng, out_grads, wrt):
        """Forward + vjp in ONE XLA computation (replaces the reference's
        explicit Backward nodes, static_graph.cc:395)."""
        return loss_and_grads(trace, no_cast, wrt, arg_values, aux_values,
                              rng, out_grads)

    return _Program(trace, jax.jit(trace, static_argnames=("is_train",)),
                    jax.jit(fwd_bwd), needs_rng, mirrored=any_mirror)

class Executor:
    """Parity: include/mxnet/symbolic.h:323 + python/mxnet/executor.py."""

    def __init__(self, symbol, ctx, args, args_grad=None, grad_req="write",
                 aux_states=None, group2ctx=None, shared_exec=None,
                 validate=None):
        self._symbol = symbol
        self._ctx = ctx if isinstance(ctx, Context) else Context(ctx)
        self._group2ctx = group2ctx or {}
        self._monitor_callback = None
        from .parallel import overlap as _overlap
        _overlap.enable_persistent_cache()   # on-disk XLA cache, idempotent

        # bind-time graph validation knob: "warn" (default) surfaces lint
        # findings as GraphLintWarning, "error" refuses to bind a graph
        # with error-severity findings (the reference GraphExecutor's
        # fail-at-bind contract), "off" skips the pass entirely.
        # MXTPU_BIND_VALIDATE overrides the default for whole runs.
        import os as _os
        if validate is None:
            validate = _os.environ.get("MXTPU_BIND_VALIDATE", "warn")
        if validate not in ("warn", "error", "off"):
            raise MXNetError("validate must be 'warn', 'error' or 'off', "
                             "got %r" % (validate,))
        self._validate_mode = validate

        self._arg_names = symbol.list_arguments()
        self._out_names = symbol.list_outputs()
        self._aux_names = symbol.list_auxiliary_states()
        # graphs embedding host-callback ops (CustomOp/NativeOp, the
        # torch/plugin bridges) need a sync point after backward: the
        # callback replay runs on jax's async callback thread while the
        # caller may mutate host state (a torch optimizer stepping the
        # module's params in-place) as soon as backward() returns
        self._has_host_ops = any(
            getattr(node.op, "host_callback", False)
            for node in symbol._topo() if node.op is not None)

        arg_list = _as_list(args, self._arg_names, "args")
        if any(a is None for a in arg_list):
            missing = [n for n, a in zip(self._arg_names, arg_list) if a is None]
            raise MXNetError("bind: missing arguments %s" % missing)
        self.arg_arrays = arg_list
        self.arg_dict = dict(zip(self._arg_names, arg_list))

        self.grad_arrays = _as_list(args_grad, self._arg_names, "args_grad")
        self.grad_dict = {n: g for n, g in zip(self._arg_names, self.grad_arrays)
                          if g is not None}

        if isinstance(grad_req, str):
            self._grad_req = {n: grad_req for n in self._arg_names}
        elif isinstance(grad_req, (list, tuple)):
            self._grad_req = dict(zip(self._arg_names, grad_req))
        else:
            self._grad_req = {n: grad_req.get(n, "null") for n in self._arg_names}
        for n in self._arg_names:
            if self._grad_req.get(n, "null") not in ("null", "write", "add"):
                raise MXNetError("invalid grad_req %r" % self._grad_req[n])
            if self._grad_req[n] != "null" and self.grad_dict.get(n) is None:
                self._grad_req[n] = "null"

        aux_list = _as_list(aux_states, self._aux_names, "aux_states")
        if any(a is None for a in aux_list):
            # allocate missing aux from inferred shapes
            shapes = {n: a.shape for n, a in self.arg_dict.items()}
            _, _, aux_shapes = symbol.infer_shape(**shapes)
            if aux_shapes is None:
                raise MXNetError("bind: cannot infer aux shapes")
            aux_list = [a if a is not None
                        else zeros(s, ctx=self._ctx, dtype=t)
                        for a, s, t in zip(aux_list, aux_shapes,
                                           _aux_dtypes(symbol))]
        self.aux_arrays = aux_list
        self.aux_dict = dict(zip(self._aux_names, aux_list))
        self._check_placement()

        # static graph lint BEFORE tracing: a bad graph fails here with
        # positioned findings instead of an opaque XLA trace error
        # (GraphExecutor bind-time inference parity; analysis/).
        self.bind_issues = []
        if validate != "off":
            self._validate_bind(args, args_grad, grad_req, aux_states)

        # outputs are allocated AT BIND and updated in place by forward:
        # a handle taken once (MXExecutorOutputs, reference c_api.cc
        # MXExecutorOutputs contract) stays aliased to the executor's
        # live outputs across forwards
        out_shapes = None
        try:
            _, out_shapes, _ = symbol.infer_shape(
                **{n: a.shape for n, a in self.arg_dict.items()})
        except Exception:
            pass
        if out_shapes is not None:
            self.outputs = [NDArray(jnp.zeros(s), ctx=self._ctx)
                            for s in out_shapes]
        else:
            self.outputs = [None] * len(self._out_names)

        # The traced program is a pure function of (symbol, group2ctx,
        # baked host flags) — NOT of this executor — and is cached on the
        # symbol so every executor bound to the same graph shares one
        # compile cache (the analog of GraphStoragePool sharing; also what
        # makes repeated bind cheap).  The key folds in every env/flag the
        # build actually bakes (compute dtype, the backward-mirror envs
        # read by _mirror_segments) plus the validation-rules fingerprint,
        # so a flag flip between binds cannot reuse a stale program.
        # Caching bound methods here would pin the first executor's buffers.
        # ... and the mesh context the bind happens under (the Module
        # mesh group's): attention traces differently per mesh, so a
        # one-device program must not be reused for a mesh executor.
        from .parallel.ring_attention import current_sequence_parallel
        scope = current_sequence_parallel()
        cache_key = (tuple(sorted((k, str(v))
                                  for k, v in self._group2ctx.items())),
                     _bind_env_fingerprint(self._validate_mode),
                     scope.fingerprint() if scope is not None else None)
        cache = getattr(symbol, "_jit_cache", None)
        if cache is None:
            cache = symbol._jit_cache = {}
        if cache_key not in cache:
            cache[cache_key] = _lookup_program(symbol, cache_key,
                                               self._group2ctx)
        self._program = cache[cache_key]
        self._needs_rng = self._program.needs_rng
        self._jit_forward = self._program.jit_forward
        self._jit_fwd_bwd = self._program.jit_fwd_bwd
        # dispatch counters (one fused call per fit step is the contract
        # the tests assert — graph_executor.cc:842 bulk-segment analog)
        self._n_forward = 0
        self._n_fwd_bwd = 0
        self._n_fused_step = 0
        self._n_monitored_compiled = 0
        self._fused_cache = None  # (optimizer fingerprint, jitted step)
        # device_scopes records, made at a step's first dispatch
        self._fused_record = None
        self._fwd_bwd_record = None

    def _check_placement(self):
        """Refuse arrays that do not live on this executor's context (or,
        for ``ctx_group`` graphs, on one of the ``group2ctx`` contexts) —
        the reference's bind contract (graph_executor.cc:391
        AssignContext checks every arg against its node's device).  jit
        would otherwise run wherever the committed arrays happen to sit,
        whatever ``ctx`` says."""
        devices = {c.jax_device
                   for c in [self._ctx] + list(self._group2ctx.values())}
        for kind, arrays in (("argument", self.arg_dict),
                             ("gradient", self.grad_dict),
                             ("aux state", self.aux_dict)):
            for name, arr in arrays.items():
                if devices.isdisjoint(arr.data.devices()):
                    raise MXNetError(
                        "bind: %s %r lives on %s, not on the executor's "
                        "context %s; move it with as_in_context()"
                        % (kind, name, sorted(str(d) for d in
                                              arr.data.devices()),
                           self._ctx))

    def _validate_bind(self, args, args_grad, grad_req, aux_states):
        """Run the static analyzer with full bind context and apply the
        validate= policy: 'warn' emits one GraphLintWarning summarizing
        warning+error findings, 'error' raises MXNetError when any
        error-severity finding exists (refuse-to-bind, the reference
        GraphExecutor contract)."""
        from .analysis import analyze, format_issues, GraphLintWarning
        # no world_size= here: AnalysisContext reads
        # MXTPU_LINT_DISTRIBUTED / MXTPU_LINT_WORLD_SIZE itself, so the
        # per-rank collective-trace diff (MXL-D001..003) joins bind-time
        # validation whenever the env knob is on
        issues = analyze(
            self._symbol,
            shapes={n: tuple(a.shape) for n, a in self.arg_dict.items()},
            type_dict={n: a.dtype for n, a in self.arg_dict.items()},
            args=args, args_grad=args_grad, grad_req=grad_req,
            aux_states=aux_states, group2ctx=self._group2ctx,
            target=self._ctx.device_type)
        self.bind_issues = issues
        errors = [i for i in issues if i.severity == "error"]
        visible = [i for i in issues if i.severity != "info"]
        if errors and self._validate_mode == "error":
            raise MXNetError(
                "bind validation failed with %d error(s) (pass "
                "validate='warn'/'off' or fix the graph):\n%s"
                % (len(errors), format_issues(errors)))
        if visible:
            import warnings
            warnings.warn("graph lint found %d issue(s) at bind:\n%s"
                          % (len(visible), format_issues(visible)),
                          GraphLintWarning, stacklevel=3)

    @property
    def output_dict(self):
        """name -> output NDArray (reference executor.py output_dict);
        duplicate names raise, as the reference's _get_dict does."""
        if len(set(self._out_names)) != len(self._out_names):
            raise MXNetError("Duplicate names detected in outputs: %s"
                             % (self._out_names,))
        return dict(zip(self._out_names, self.outputs))

    def _publish_output(self, i, value):
        """Update output slot i IN PLACE: the NDArray object is stable for
        the life of the executor (MXExecutorOutputs handles stay aliased,
        reference c_api.cc MXExecutorOutputs), only its buffer moves.
        Dtype/shape may legitimately differ from the bind-time allocation
        (Cast outputs, reshape) — rebind storage directly then."""
        nd = self.outputs[i]
        if nd is None:
            self.outputs[i] = NDArray(value, ctx=self._ctx)
        elif nd.dtype == value.dtype and nd.shape == value.shape:
            nd._set_data(value)
        else:
            nd._storage = value

    @property
    def _trace(self):
        return self._program.trace

    # ------------------------------------------------------------------
    # public API (python/mxnet/executor.py parity)
    # ------------------------------------------------------------------
    def forward(self, is_train=False, **kwargs):
        for name, arr in kwargs.items():
            if name not in self.arg_dict:
                raise MXNetError("forward: unknown argument %r" % name)
            if isinstance(arr, NDArray):
                self.arg_dict[name]._set_data(arr.data)
            else:
                self.arg_dict[name]._set_data(jnp.asarray(arr))
        self._n_forward += 1
        arg_values = {n: a.data for n, a in self.arg_dict.items()}
        aux_values = {n: a.data for n, a in self.aux_dict.items()}
        rng = _random.next_key() if self._needs_rng else _zero_key()
        if self._monitor_callback is not None:
            import os as _os
            if _os.environ.get("MXTPU_MONITOR_MODE", "compiled") == "interpret":
                # eager op-by-op debugging path (NaiveEngine analog)
                outs, aux_out = self._trace(arg_values, aux_values, rng,
                                            is_train, monitor=self._run_monitor)
            else:
                prog = self._program
                prog.monitor_sink = self._run_monitor
                try:
                    outs, aux_out = prog.jit_forward_monitored()(
                        arg_values, aux_values, rng, is_train=bool(is_train))
                    # debug callbacks are asynchronous: flush them so the
                    # monitor queue is complete when toc() reads it
                    jax.effects_barrier()
                finally:
                    prog.monitor_sink = None
                self._n_monitored_compiled += 1
        else:
            with _spans.span("step_dispatch"):
                outs, aux_out = self._jit_forward(
                    arg_values, aux_values, rng, is_train=bool(is_train))
        for i, o in enumerate(outs):
            self._publish_output(i, o)
        if is_train:
            for n, a in self.aux_dict.items():
                if aux_out[n] is not aux_values[n]:
                    a._set_data(aux_out[n])
        self._last_inputs = (arg_values, aux_values, rng)
        return self.outputs

    def backward(self, out_grads=None):
        if not hasattr(self, "_last_inputs"):
            raise MXNetError("backward called before forward(is_train=True)")
        arg_values, aux_values, rng = self._last_inputs
        wrt_names = tuple(n for n in self._arg_names
                          if self._grad_req.get(n, "null") != "null")
        if not wrt_names:
            return
        if out_grads is None:
            ograds = None
        else:
            if isinstance(out_grads, NDArray):
                out_grads = [out_grads]
            ograds = [g.data if isinstance(g, NDArray) else jnp.asarray(g)
                      for g in out_grads]
        wrt = {n: arg_values[n] for n in wrt_names}
        self._n_fwd_bwd += 1
        _outs, _aux, grads = self._jit_fwd_bwd(arg_values, aux_values, rng,
                                               ograds, wrt)
        for n in wrt_names:
            g = grads[n]
            tgt = self.grad_dict[n]
            if self._grad_req[n] == "add":
                tgt._set_data(tgt.data + g)
            else:
                tgt._set_data(g)
        if self._has_host_ops:
            # order the host-side backward effects (torch .grad fills,
            # custom-op buffer writes) before the caller's next move
            for n in wrt_names:
                grads[n].block_until_ready()

    def forward_backward(self, out_grads=None, **kwargs):
        """Fused train step building block: one XLA computation for fwd+bwd."""
        for name, arr in kwargs.items():
            self.arg_dict[name]._set_data(
                arr.data if isinstance(arr, NDArray) else jnp.asarray(arr))
        arg_values = {n: a.data for n, a in self.arg_dict.items()}
        aux_values = {n: a.data for n, a in self.aux_dict.items()}
        rng = _random.next_key() if self._needs_rng else _zero_key()
        wrt_names = tuple(n for n in self._arg_names
                          if self._grad_req.get(n, "null") != "null")
        if out_grads is None:
            ograds = None
        else:
            ograds = [g.data if isinstance(g, NDArray) else jnp.asarray(g)
                      for g in out_grads]
        wrt = {n: arg_values[n] for n in wrt_names}
        self._n_fwd_bwd += 1
        step_args = (arg_values, aux_values, rng, ograds, wrt)
        if self._fwd_bwd_record is None:
            self._fwd_bwd_record = self._register_step(self._jit_fwd_bwd,
                                                       step_args)
        with _spans.span("step_dispatch"):
            outs, aux_out, grads = self._jit_fwd_bwd(*step_args)
        for i, o in enumerate(outs):
            self._publish_output(i, o)
        for n, a in self.aux_dict.items():
            a._set_data(aux_out[n])
        for n in wrt_names:
            tgt = self.grad_dict[n]
            if self._grad_req[n] == "add":
                tgt._set_data(tgt.data + grads[n])
            else:
                tgt._set_data(grads[n])
        if self._has_host_ops:
            for n in wrt_names:
                grads[n].block_until_ready()
        return self.outputs

    # -- fused train step (fwd + bwd + optimizer update, ONE dispatch) --
    @staticmethod
    def _fused_compute_dtype():
        """Optional reduced-precision compute for the fused step
        (MXNET_COMPUTE_DTYPE=bfloat16): fwd+bwd run at MXU rate while
        master weights, optimizer state, grads and aux stay f32 — the
        policy knob the fp32-only reference never had (SURVEY §7)."""
        import os
        name = os.environ.get("MXNET_COMPUTE_DTYPE", "").strip()
        if not name or name in ("float32", "f32"):
            return None
        return jnp.dtype(name)

    def _build_fused_step(self, optimizer):
        """Jit fwd+bwd+update as one XLA computation — the full analog of
        the reference's bulk segments (graph_executor.cc:842-892): the
        whole fit step is one dispatch, with the optimizer math fused in
        (≡ server-side update, kvstore_dist_server.h:164, run on-device)."""
        trace = self._program.trace
        wrt_names = tuple(n for n in self._arg_names
                          if self._grad_req.get(n, "null") != "null")
        # per-param lr/wd multipliers are static floats at trace time
        # (reference _get_lr/_get_wd, optimizer.py:122-141)
        name2idx = {n: i for i, n in optimizer.idx2name.items()}
        lrm, wdm = {}, {}
        for n in wrt_names:
            idx = name2idx.get(n, n)
            lrm[n] = optimizer.lr_mult.get(
                idx, optimizer.lr_mult.get(n, 1.0))
            wdm[n] = optimizer.wd_mult.get(
                idx, optimizer.wd_mult.get(n, 1.0))

        # an Executor knows its labels by their names
        cast = compute_cast(self._symbol, self._fused_compute_dtype(),
                            [n for n in self._arg_names
                             if n.endswith("label")])

        def step(wrt, old_grads, arg_values, aux_values, rng, states, lr,
                 wd, t):
            # ``old_grads`` is there to be donated: the new gradients take
            # its buffers, as the new weights take ``wrt``'s
            del old_grads
            outs, aux_out, grads = loss_and_grads(
                trace, cast, wrt, arg_values, aux_values, rng)
            new_w, new_s = apply_updates(
                optimizer, wrt, preprocess_grads(optimizer, grads), states,
                lr, wd, t, lr_mult=lrm, wd_mult=wdm)
            return outs, aux_out, grads, new_w, new_s

        # Everything the step replaces is donated — the weights, the old
        # gradients, the auxiliary states, the optimizer state — so every
        # output but ``outs`` reuses an input's buffer.  The runtime
        # allocates each remaining output buffer on the calling thread
        # before it launches (43 us apiece on a TPU v5e: 18 ms of
        # ResNet-50's 417, during which the chip ran nothing).
        # (``keep_unused``: the old gradients are an operand only to be
        # donated; pruned, the new ones would be allocated.)
        return wrt_names, jax.jit(step, donate_argnums=(0, 1, 3, 5),
                                  keep_unused=True)

    def _get_fused(self, optimizer):
        """(wrt_names, jitted step) for this optimizer, cached by a
        value fingerprint over exactly what _build_fused_step bakes:
        optimizer class, hyperparameter scalars (minus the per-step
        update counters, which mutate every step and would defeat the
        cache), the per-param multiplier maps, and the compute dtype.
        An id()-keyed cache would miss for a fresh-but-identical
        optimizer (needless relower of the whole fused step) and could
        falsely hit on a gc-recycled id (stale program, wrong
        hyperparameters) — MXL-X002."""
        import os
        from .parallel import overlap as _overlap
        hypers = {k: v for k, v in sorted(vars(optimizer).items())
                  if isinstance(v, (int, float, bool, str, type(None)))
                  and k not in ("num_update", "begin_num_update")}
        key = _overlap.cache_key(
            type(optimizer).__name__, hypers,
            getattr(optimizer, "lr_mult", None),
            getattr(optimizer, "wd_mult", None),
            getattr(optimizer, "idx2name", None),
            os.environ.get("MXNET_COMPUTE_DTYPE", ""))
        if self._fused_cache is None or self._fused_cache[0] != key:
            self._fused_cache = (key, self._build_fused_step(optimizer))
            self._fused_record = None
        return self._fused_cache[1]

    def fused_step(self, optimizer, states, num_update, **kwargs):
        """Run one full train step (forward + backward + optimizer update)
        as a single XLA dispatch.  Writes updated params into the bound
        arg arrays, grads into grad arrays, aux/outputs as forward does.
        ``states`` is a dict name -> optimizer-state pytree (jax arrays),
        mutated-by-replacement and returned.
        """
        wrt_names, jit_step = self._get_fused(optimizer)
        for name, arr in kwargs.items():
            self.arg_dict[name]._set_data(
                arr.data if isinstance(arr, NDArray) else jnp.asarray(arr))
        wrt, old_grads, arg_values, aux_values = self._fused_operands(
            wrt_names)
        rng = _random.next_key() if self._needs_rng else _zero_key()
        if optimizer.lr_scheduler is not None:
            lr = optimizer.lr_scheduler(num_update)
        else:
            lr = optimizer.lr
        self._n_fused_step += 1
        # host scalars ride with the call; ``jnp.float32(lr)`` would be a
        # device program and a transfer of its own, each
        step_args = (wrt, old_grads, arg_values, aux_values, rng, states,
                     _np.float32(lr), _np.float32(optimizer.wd),
                     _np.int32(num_update))
        if self._fused_record is None:
            self._fused_record = self._register_step(jit_step, step_args)
        with _spans.span("step_dispatch", step=num_update):
            outs, aux_out, grads, new_w, new_s = jit_step(*step_args)
        del wrt, old_grads, aux_values, step_args       # donated
        for i, o in enumerate(outs):
            self._publish_output(i, o)
        for n, a in self.aux_dict.items():
            a._bind_fresh(aux_out[n])
        for n in wrt_names:
            self.grad_dict[n]._bind_fresh(grads[n])
            self.arg_dict[n]._bind_fresh(new_w[n])
        return new_s

    def _register_step(self, jitted, step_args):
        """A step's first dispatch: its ``device_scopes`` record (the
        jitted function, the arguments' shapes, the graph's nodes)."""
        from .parallel.ring_attention import reopen_current_scope
        return _device_scopes.register(
            "jit_" + jitted.__name__, jitted, step_args,
            _device_scopes.graph_nodes(self._symbol),
            context=reopen_current_scope())     # a mesh group's

    def device_scopes(self):
        """The ``device_scopes.StepRecord`` of the train step this
        executor dispatched (the fused step's, else ``forward_backward``'s),
        or ``None`` before the first one: ``record.scopes()`` maps each
        instruction of the compiled step to its graph node and pass
        (docs/observability.md, "Device time by scope")."""
        return self._fused_record or self._fwd_bwd_record

    def _fused_operands(self, wrt_names, donate=True):
        """``(weights, old gradients, other arguments, auxiliary states)``
        as the fused step takes them: the first, second and last are
        buffers the call may consume (:func:`_consumable`).  Without
        ``donate`` nothing is taken or copied, and the old gradients are
        described, not held: what a lowering needs."""
        wrt = {n: self.arg_dict[n] for n in wrt_names}
        arg_values = {n: a.data for n, a in self.arg_dict.items()
                      if n not in wrt}
        if not donate:
            wrt = {n: a.data for n, a in wrt.items()}
            return (wrt, {n: jax.ShapeDtypeStruct(w.shape, w.dtype,
                                                  sharding=w.sharding)
                          for n, w in wrt.items()},
                    arg_values, {n: a.data for n, a in self.aux_dict.items()})
        wrt = _consumable(wrt)
        # a mesh group binds its gradient arrays on one device: the step
        # wants them where the weights are
        old_grads = _consumable({n: self.grad_dict[n] for n in wrt_names},
                                beside=wrt)
        return wrt, old_grads, arg_values, _consumable(self.aux_dict)

    def _lower_fused(self, optimizer, states):
        wrt_names, jit_step = self._get_fused(optimizer)
        wrt, old_grads, arg_values, aux_values = self._fused_operands(
            wrt_names, donate=False)        # lowering consumes nothing
        return jit_step.lower(wrt, old_grads, arg_values, aux_values,
                              _zero_key(), states, _np.float32(0.01),
                              _np.float32(0.0), _np.int32(1))

    def lower_fused_step(self, optimizer, states):
        """Optimized-HLO text of the fused step for the currently bound
        arrays — introspection hook (tests assert the sharded step carries
        an all-reduce; the perf story's equivalent of debug_str)."""
        return self._lower_fused(optimizer, states).compile().as_text()

    def fused_step_memory_analysis(self, optimizer, states):
        """XLA's compiled memory analysis of the fused train step
        (``temp_size_in_bytes`` is the activation/workspace peak the
        mirroring trade shrinks — the MemoryCost introspection the
        reference's example/memcost reads off the allocator logs)."""
        return self._lower_fused(optimizer, states).compile(
            ).memory_analysis()

    def _bound_for_vjp(self):
        return ({n: a.data for n, a in self.arg_dict.items()},
                {n: a.data for n, a in self.aux_dict.items()},
                tuple(n for n in self._arg_names
                      if self._grad_req.get(n, "null") != "null"))

    def backward_residual_bytes(self):
        """Bytes of residuals jax saves between forward and backward for
        the bound shapes — the activation-memory quantity mirroring
        (``force_mirroring``/MXNET_BACKWARD_DO_MIRROR ->
        ``jax.checkpoint``) exists to shrink.  It counts what a mirrored
        segment keeps by name (``mirror_kept``: what the flash kernel,
        the delta rule's forward sweep and the routed layer hand their
        backward) beside the segments' inputs.
        Backend-independent: read from the partial-eval trace, not the
        compiled executable (XLA:CPU does not attribute temp buffers)."""
        return trace_residual_bytes(self._program.trace,
                                    *self._bound_for_vjp())

    def mirror_kept(self):
        """``[(name, bytes)]`` the mirrored segments keep by name for the
        bound shapes (``_Program.mirror_kept``)."""
        return self._program.mirror_kept(*self._bound_for_vjp())

    def init_fused_states(self, optimizer):
        """Optimizer-state arrays for every learnable arg (fused path)."""
        states = {}
        for n in self._arg_names:
            if self._grad_req.get(n, "null") == "null":
                continue
            a = self.arg_dict[n]
            s = optimizer.create_state_arrays(a.shape, a.dtype)
            if s is not None:
                # beside the weight it updates (a mesh-replicated weight
                # gives a mesh-replicated state)
                states[n] = jax.device_put(s, a.data.sharding)
        return states

    # -- monitor (MXExecutorSetMonitorCallback parity) ------------------
    def set_monitor_callback(self, callback):
        self._monitor_callback = callback

    def _run_monitor(self, name, value):
        self._monitor_callback(name, NDArray(value, ctx=self._ctx))

    # -- param management ----------------------------------------------
    def copy_params_from(self, arg_params, aux_params=None,
                         allow_extra_params=False):
        for name, arr in arg_params.items():
            if name in self.arg_dict:
                arr.copyto(self.arg_dict[name])
            elif not allow_extra_params:
                raise MXNetError("unknown argument %r" % name)
        if aux_params:
            for name, arr in aux_params.items():
                if name in self.aux_dict:
                    arr.copyto(self.aux_dict[name])
                elif not allow_extra_params:
                    raise MXNetError("unknown aux state %r" % name)

    def reshape(self, partial_shaping=False, allow_up_sizing=False, **new_shapes):
        """Re-bind to new input shapes (executor.py:270). Param arrays are
        shared; data/label arrays reallocated; jit recompiles per shape."""
        arg_shapes, _, aux_shapes = self._symbol.infer_shape(**new_shapes)
        if arg_shapes is None:
            raise MXNetError("reshape: cannot infer shapes from %s" % new_shapes)
        new_args = {}
        new_grads = {}
        for name, shape in zip(self._arg_names, arg_shapes):
            cur = self.arg_dict[name]
            if tuple(cur.shape) == tuple(shape):
                new_args[name] = cur
                if name in self.grad_dict:
                    new_grads[name] = self.grad_dict[name]
            else:
                if not partial_shaping and name not in new_shapes:
                    raise MXNetError(
                        "reshape changed shape of %s; pass partial_shaping=True"
                        % name)
                new_args[name] = zeros(shape, ctx=self._ctx, dtype=cur.dtype)
                if name in self.grad_dict:
                    new_grads[name] = zeros(shape, ctx=self._ctx, dtype=cur.dtype)
        aux = {n: a for n, a in self.aux_dict.items()}
        return Executor(self._symbol, self._ctx, new_args, new_grads,
                        self._grad_req, aux, group2ctx=self._group2ctx,
                        shared_exec=self, validate=self._validate_mode)

    def debug_str(self):
        """Execution plan dump (GraphExecutor::Print parity); under jit the
        real plan is XLA's — expose both our schedule and cost analysis."""
        lines = [self._symbol.debug_str(), ""]
        total = sum(_np.prod(a.shape) * a.dtype.itemsize
                    for a in self.arg_arrays + self.aux_arrays
                    + [g for g in self.grad_arrays if g is not None])
        lines.append("Total %d MB allocated (args+grads+aux)" % (total // (1 << 20)))
        return "\n".join(lines)


def _aux_dtypes(symbol, type_dict=None, floating=_np.float32):
    """The dtype each auxiliary state is allocated in: ``floating``, but
    an op's own where it declares an integer one (counters)."""
    _, _, aux_types = symbol.infer_type(**(type_dict or {}))
    return [t if not _np.issubdtype(t, _np.floating) else _np.dtype(floating)
            for t in aux_types]


def simple_bind(symbol, ctx, grad_req="write", type_dict=None, group2ctx=None,
                shared_exec=None, validate=None, **kwargs):
    """Allocate arg/grad/aux arrays from inferred shapes and bind
    (parity: symbol.py:630-710)."""
    arg_shapes, _, aux_shapes = symbol.infer_shape(**kwargs)
    if arg_shapes is None:
        raise MXNetError("simple_bind: cannot infer shapes from %s" % kwargs)
    arg_names = symbol.list_arguments()
    type_dict = type_dict or {}
    args = {}
    grads = {}
    for name, shape in zip(arg_names, arg_shapes):
        dtype = type_dict.get(name, _np.float32)
        args[name] = zeros(shape, ctx=ctx, dtype=dtype)
        req = grad_req if isinstance(grad_req, str) else \
            (grad_req.get(name, "null") if isinstance(grad_req, dict)
             else dict(zip(arg_names, grad_req)).get(name, "null"))
        if req != "null":
            grads[name] = zeros(shape, ctx=ctx, dtype=dtype)
    aux = [zeros(s, ctx=ctx, dtype=t)
           for s, t in zip(aux_shapes, _aux_dtypes(symbol, type_dict))]
    return Executor(symbol, ctx, args, grads, grad_req, aux,
                    group2ctx=group2ctx, shared_exec=shared_exec,
                    validate=validate)
