"""ShardedTrainer: the whole training step as ONE pjit'd XLA computation.

This is the TPU-native form of the reference's data-parallel SGD loop
(`model.py:115-305 _train_multi_device` + executor_manager batch slicing +
kvstore push/pull): forward, backward, gradient all-reduce, and optimizer
update fuse into a single compiled program over a device mesh.  The
collectives are *implicit*: batch inputs are sharded over ``dp`` (and the
sequence axis over ``sp``), parameters are sharded per rule (tp) or
replicated; because the out-sharding of parameters is the same as their
in-sharding, XLA inserts the gradient psum over ICI exactly where the
reference did a kvstore push/pull — this ≡ ``update_on_kvstore`` with the
update running server-side (kvstore_dist_server.h:164), except the "server"
is the compiled step itself.

Buffer donation on (params, opt_state, aux) gives in-place parameter
updates — the analog of the reference's shared memory pool + kWriteInplace.
"""
from __future__ import annotations

import functools

import numpy as _np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..base import MXNetError
from .sharding import param_pspec, batch_pspec
from . import overlap as _overlap
from ..observability import device_scopes as _device_scopes
from ..observability.phases import GRAD_SYNC

__all__ = ["ShardedTrainer", "ShardedPredictor"]


def _place_batch(batch, sharding_fn):
    """dict of host/NDArray arrays -> placed jax arrays (the one batch
    placement rule, shared by ShardedTrainer and ShardedPredictor)."""
    from .sharding import put_local_sharded
    out = {}
    for name, arr in batch.items():
        arr = getattr(arr, "data", arr) if hasattr(arr, "asnumpy") else arr
        out[name] = put_local_sharded(arr, sharding_fn(arr.shape))
    return out


class ShardedTrainer(object):
    """Compile a Symbol's train step over a Mesh.

    Parameters
    ----------
    symbol : Symbol with loss head(s) (e.g. SoftmaxOutput).
    optimizer : mxnet_tpu.optimizer.Optimizer (its pure update_fn is traced
        into the step; its host-side schedule drives the lr scalar).
    mesh : jax.sharding.Mesh from parallel.make_mesh.
    data_names / label_names : input argument names.
    rules : optional ShardingRules for parameter placement.
    seq_axis : batch axis to shard over 'sp' for sequence parallelism.
    """

    def __init__(self, symbol, optimizer, mesh, data_names=("data",),
                 label_names=("softmax_label",), rules=None, seq_axis=None,
                 compute_dtype=None, remat=False, cast_exempt=(), zero1=False, fsdp=False, sentinel=None,
                 loss_scale_init=2.0 ** 15, loss_scale_growth=200,
                 step_timeout_s=None):
        self.symbol = symbol
        self.optimizer = optimizer
        self.mesh = mesh
        self.data_names = tuple(data_names)
        self.label_names = tuple(label_names)
        self.rules = rules
        self.seq_axis = seq_axis
        # mixed precision: master params/opt-state/aux stay f32; the
        # forward+backward trace runs in compute_dtype (bf16 feeds the MXU
        # at 2x f32 rate); grads come back f32 via the cast's transpose.
        # The reference is fp32-only (real_t = float) — this is the policy
        # decision SURVEY §7 flags for TPU ("bf16/f32 policy decisions the
        # reference never faced").
        self.compute_dtype = (jnp.dtype(compute_dtype)
                              if compute_dtype is not None else None)
        self.remat = bool(remat)
        # ZeRO-1 (beyond-reference): shard OPTIMIZER STATE over the dp
        # axis — each dp rank keeps 1/dp of momentum/adam state, the
        # update computes sharded, and XLA all-gathers the new params
        # (the scaling-book optimizer-state-sharding recipe).  Parameters
        # themselves stay replicated (unlike ZeRO-3), so fwd/bwd is
        # untouched; only the update's layout changes.
        self.zero1 = bool(zero1) and "dp" in mesh.shape \
            and mesh.shape["dp"] > 1
        # FSDP / ZeRO-3 (beyond-reference): PARAMETERS live dp-sharded
        # too; GSPMD all-gathers each weight where the forward needs it
        # and reduce-scatters its gradient — memory scales 1/dp for
        # params+grads+state at the cost of per-layer gather traffic.
        # Optimizer state follows the parameter sharding automatically.
        self.fsdp = bool(fsdp) and "dp" in mesh.shape \
            and mesh.shape["dp"] > 1
        # numeric sentinel (resilience): gate the update INSIDE the
        # compiled step on all-gradients-finite, with dynamic loss
        # scaling — a host-side check would force a device sync every
        # step, so the skip/backoff decision is traced (docs/resilience.md)
        from .. import resilience as _resilience
        self.sentinel = _resilience.sentinel_enabled() if sentinel is None \
            else bool(sentinel)
        self._loss_scale_init = float(loss_scale_init)
        self._loss_scale_growth = int(loss_scale_growth)
        self._sentinel_state = None
        # step watchdog timeout (None = env MXTPU_STEP_TIMEOUT_S at call
        # time, so a launcher can arm it without touching user code)
        self.step_timeout_s = step_timeout_s
        # allreduce-over-backward: chain per-bucket optimization
        # barriers through the traced grads (reverse-topo, ~MXTPU_
        # BUCKET_MB each) so XLA emits one collective per bucket as its
        # grads finish instead of one tail-end fused collective.
        # Identity math; pointless on a single device.
        self._bucket_grads = _overlap.bucket_bytes() > 0 \
            and self.mesh.size > 1
        # fused optimizer sweep (MXTPU_FUSED_OPT): replace the per-leaf
        # update loop with one bucketed flatten/update/unflatten —
        # bit-identical, elementwise optimizers only.  The Pallas sweep
        # ('kernel') is a single-device program; on a multi-device mesh
        # it degrades to the fused XLA sweep ('1'), which GSPMD
        # partitions like any other elementwise computation.
        from ..kernels import fused_opt as _fused
        self._fused_opt = _fused.fused_opt_mode() \
            if _fused.supports_fused(optimizer) else ""
        if self._fused_opt == "kernel" and self.mesh.size > 1:
            self._fused_opt = "1"

        self._arg_names = symbol.list_arguments()
        self._aux_names = symbol.list_auxiliary_states()
        self.param_names = [n for n in self._arg_names
                            if n not in self.data_names
                            and n not in self.label_names]
        from ..executor import _build_program
        from ..train_step import (apply_updates, cast_each, compute_cast,
                                  loss_and_grads, preprocess_grads)
        program = _build_program(symbol, {})
        self._trace = program.trace
        self._needs_rng = program.needs_rng
        self.num_update = 0

        trace = self._trace
        if self.remat:
            base_trace = trace

            def trace(args, aux, rng, is_train):
                return jax.checkpoint(
                    lambda a: base_trace(a, aux, rng, is_train))(args)
        cast = compute_cast(symbol, self.compute_dtype, self.label_names,
                            cast_exempt)
        self._cast_exempt = cast.exempt

        growth = jnp.int32(self._loss_scale_growth)
        min_scale, max_scale = jnp.float32(1.0), jnp.float32(2.0 ** 24)

        def train_step(params, opt_state, aux, batch, rng, lr, wd, t,
                       sstate=None):
            """One fused step: fwd + bwd + psum(grad) + update.

            With ``sstate`` (the sentinel's state) the step is gated on
            every gradient being finite: a step that is not keeps the old
            params/state/aux, halves the loss scale and bumps the skip
            counter, all without a host round-trip (the sentinel
            contract, docs/resilience.md)."""
            outs, aux_out, grads = loss_and_grads(trace, cast, params, batch,
                                                  aux, rng)
            if self._bucket_grads:
                with jax.named_scope(GRAD_SYNC):
                    grads = _overlap.interleave_grad_buckets(grads)
            grads = preprocess_grads(optimizer, grads)
            new_params, new_opt_state = apply_updates(
                optimizer, params, grads, opt_state, lr, wd, t,
                fused=self._fused_opt)
            if sstate is not None:
                # NOTE on the loss scale: the built-in loss heads keep the
                # reference's backward semantics (SoftmaxOutput bwd =
                # p - onehot, head gradient IGNORED unless out_grad=True),
                # so a scaled cotangent seed would not reach the gradients
                # — the gate therefore checks the TRUE grads, and the
                # dynamic scale is pure backoff state: halved on a bad
                # step, grown after good ones, exported via
                # sentinel_stats() for losses that do consume it
                # (out_grad=True heads, custom grad_scale).
                finite = jnp.bool_(True)
                for name in params:
                    finite = jnp.logical_and(
                        finite, jnp.all(jnp.isfinite(grads[name])))

                def keep_old(new, old):
                    return jax.tree_util.tree_map(
                        lambda n, o: jnp.where(finite, n, o), new, old)

                new_params = keep_old(new_params, params)
                new_opt_state = keep_old(
                    new_opt_state, {n: opt_state[n] for n in new_opt_state})
                aux_out = keep_old(aux_out, aux)
            if self.zero1:
                # pin layouts: state stays dp-sharded, weights come back
                # replicated (XLA inserts the all-gather) — the ZeRO-1
                # contract
                new_params = {
                    n: jax.lax.with_sharding_constraint(
                        w, self.param_sharding(n, w.shape))
                    for n, w in new_params.items()}
                new_opt_state = {
                    n: jax.tree_util.tree_map(
                        lambda a, _n=n: jax.lax.with_sharding_constraint(
                            a, self.opt_state_sharding(_n, a.shape)), s)
                    for n, s in new_opt_state.items()}
            if sstate is None:
                return new_params, new_opt_state, aux_out, outs

            scale = sstate["scale"]
            good = jnp.where(finite, sstate["good_steps"] + 1,
                             jnp.int32(0))
            grow = good >= growth
            new_scale = jnp.where(
                finite,
                jnp.where(grow, jnp.minimum(scale * 2.0, max_scale),
                          scale),
                jnp.maximum(scale * 0.5, min_scale))
            new_sstate = {
                "scale": new_scale,
                "good_steps": jnp.where(grow, jnp.int32(0), good),
                "skipped": sstate["skipped"]
                + jnp.where(finite, jnp.int32(0), jnp.int32(1)),
                "last_good": jnp.where(finite, t, sstate["last_good"]),
            }
            return new_params, new_opt_state, aux_out, outs, new_sstate

        # everything the step replaces is donated (the sentinel's state
        # with it): in-place parameter updates
        self._jit_step = jax.jit(
            train_step,
            donate_argnums=(0, 1, 2, 8) if self.sentinel else (0, 1, 2))
        self._abstract_args = None   # ShapeDtypeStructs of the step args
        self._step_record = None     # device_scopes record of the step
        self._lowered = None         # cached jax.stages.Lowered
        self._compiled_step = None   # cached jax.stages.Compiled of it
        self._cache_entry = None     # overlap compile-cache slot
        # on-disk XLA cache, idempotent
        _overlap.enable_persistent_cache()

        def eval_step(params, aux, batch, rng):
            args = cast_each(cast, params)
            args.update(cast_each(cast, batch))
            outs, _ = trace(args, cast_each(cast, aux), rng, False)
            return outs

        self._jit_eval = jax.jit(eval_step)

    # ------------------------------------------------------------------
    # shardings
    # ------------------------------------------------------------------
    def param_sharding(self, name, shape):
        if self.fsdp:
            spec = param_pspec(name, shape, self.mesh, self.rules)
            if all(ax is None for ax in spec) and shape and \
                    shape[0] % self.mesh.shape["dp"] == 0:
                # otherwise-replicated param: shard axis 0 over dp
                return NamedSharding(
                    self.mesh, P("dp", *([None] * (len(shape) - 1))))
            return NamedSharding(self.mesh, spec)
        return NamedSharding(self.mesh,
                             param_pspec(name, shape, self.mesh, self.rules))

    def batch_sharding(self, shape):
        return NamedSharding(self.mesh,
                             batch_pspec(shape, self.mesh, self.seq_axis))

    def opt_state_sharding(self, name, shape):
        """ZeRO-1 placement for one optimizer-state array: axis 0 sharded
        over dp when divisible, else the parameter's own sharding."""
        if self.zero1 and shape and \
                shape[0] % self.mesh.shape["dp"] == 0:
            return NamedSharding(
                self.mesh, P("dp", *([None] * (len(shape) - 1))))
        return self.param_sharding(name, shape)

    def _replicated(self):
        return NamedSharding(self.mesh, P())

    # ------------------------------------------------------------------
    # state init
    # ------------------------------------------------------------------
    def init_params(self, data_shapes, initializer=None, label_shapes=None,
                    dtype=_np.float32):
        """Infer shapes, allocate sharded params/opt_state/aux.

        Returns (params, opt_state, aux) dicts of jax.Arrays placed with
        their pjit shardings (so the first step doesn't reshard).
        """
        shape_map, aux_map = self._shape_maps(data_shapes, label_shapes)

        from ..ndarray import NDArray
        from ..initializer import Uniform
        initializer = initializer or Uniform(0.07)
        from .sharding import put_replicated_host
        params = {}
        for name in self.param_names:
            host = NDArray(jnp.zeros(shape_map[name], dtype=dtype))
            initializer(name, host)
            params[name] = put_replicated_host(
                host.data, self.param_sharding(name, host.shape))
        opt_state = {}
        for name in self.param_names:
            s = self.optimizer.create_state_arrays(shape_map[name], dtype)
            if s is not None:
                opt_state[name] = jax.tree_util.tree_map(
                    lambda a, _n=name: put_replicated_host(
                        a, self.opt_state_sharding(_n, a.shape)), s)
        return params, opt_state, self._init_aux(aux_map, dtype)

    def _aux_dtypes(self, dtype):
        """{aux name: dtype}: ``dtype`` for the floating states, an op's
        own for those it declares otherwise (integer counters)."""
        from ..executor import _aux_dtypes
        return dict(zip(self._aux_names, _aux_dtypes(
            self.symbol, {n: dtype for n in self.data_names}, dtype)))

    def _init_aux(self, aux_map, dtype):
        from .sharding import put_replicated_host
        dtypes = self._aux_dtypes(dtype)
        aux = {}
        for name in self._aux_names:
            init_val = jnp.ones(aux_map[name], dtype=dtypes[name]) \
                if name.endswith("moving_var") else \
                jnp.zeros(aux_map[name], dtype=dtypes[name])
            aux[name] = put_replicated_host(init_val, self._replicated())
        return aux

    def init_aux(self, data_shapes, label_shapes=None, dtype=_np.float32):
        """The auxiliary state alone, as :meth:`init_params` makes it
        (moving variances 1, everything else 0; integer where an op keeps
        counters): for callers that bring their own parameters."""
        _shape_map, aux_map = self._shape_maps(data_shapes, label_shapes)
        return self._init_aux(aux_map, dtype)

    def _shape_maps(self, data_shapes, label_shapes=None):
        shapes = dict(data_shapes)
        if label_shapes:
            shapes.update(label_shapes)
        arg_shapes, _, aux_shapes = self.symbol.infer_shape(**shapes)
        if arg_shapes is None:
            raise MXNetError("cannot infer shapes from %s" % (shapes,))
        return (dict(zip(self._arg_names, arg_shapes)),
                dict(zip(self._aux_names, aux_shapes)))

    def abstract_state(self, data_shapes, label_shapes=None,
                       dtype=_np.float32):
        """(params, opt_state, aux) as sharding-annotated
        ShapeDtypeStructs — the restore target for sharded checkpoints
        (and a zero-alloc way to inspect placements)."""
        shape_map, aux_map = self._shape_maps(data_shapes, label_shapes)

        def _abs(shape, sharding):
            return jax.ShapeDtypeStruct(tuple(shape), _np.dtype(dtype),
                                        sharding=sharding)

        params = {n: _abs(shape_map[n], self.param_sharding(n, shape_map[n]))
                  for n in self.param_names}
        opt_state = {}
        for n in self.param_names:
            # eval_shape: shapes only, no buffers — a full Adam state
            # materialized here would OOM exactly the huge-model case
            # this path exists for
            s = jax.eval_shape(
                lambda _n=n: self.optimizer.create_state_arrays(
                    shape_map[_n], dtype))
            if s is not None:
                opt_state[n] = jax.tree_util.tree_map(
                    lambda a, _n=n: _abs(
                        a.shape, self.opt_state_sharding(_n, a.shape)), s)
        aux_dtypes = self._aux_dtypes(dtype)
        aux = {n: jax.ShapeDtypeStruct(tuple(aux_map[n]), aux_dtypes[n],
                                       sharding=self._replicated())
               for n in self._aux_names}
        return params, opt_state, aux

    # ------------------------------------------------------------------
    # sharded checkpoints (orbax): each host writes/reads only its own
    # shards — the pod-scale story the reference's gather-to-rank-0
    # NDArray files cannot tell (models larger than one host's RAM).
    # Classic 0x112-format checkpoints remain available through
    # model.save_checkpoint for single-host/interchange use.
    # ------------------------------------------------------------------
    def save_checkpoint(self, path, params, opt_state, aux):
        """Write (params, opt_state, aux) + the update counter sharded
        to ``path`` (a directory).  Multi-host: every process must call
        this; arrays stay distributed end-to-end."""
        from .ckpt import ocp_save
        return ocp_save(path, {"params": params, "opt_state": opt_state,
                               "aux": aux}, self.num_update)

    def load_checkpoint(self, path, data_shapes, label_shapes=None,
                        dtype=_np.float32):
        """Restore (params, opt_state, aux) with this trainer's
        shardings; arrays come back placed, ready for step().  The
        trainer's update counter resumes too — Adam bias correction and
        lr schedules continue where they stopped, not from step 1."""
        from .ckpt import ocp_restore
        params_t, opt_t, aux_t = self.abstract_state(
            data_shapes, label_shapes, dtype)
        restored, step = ocp_restore(
            path, {"params": params_t, "opt_state": opt_t, "aux": aux_t})
        self.num_update = step
        return restored["params"], restored["opt_state"], restored["aux"]

    def checkpoint_manager(self, directory, keep=None):
        """A :class:`mxnet_tpu.resilience.CheckpointManager` rooted at
        ``directory`` for versioned keep-last-K checkpoints of this
        trainer's state (see save_checkpoint_versioned/auto_resume)."""
        from ..resilience import CheckpointManager
        return CheckpointManager(directory, keep=keep)

    def save_checkpoint_versioned(self, directory, params, opt_state, aux,
                                  keep=None):
        """Commit an atomic ``step_<NNNNNNNN>`` checkpoint under
        ``directory`` (pruned to keep-last-K); safe against preemption
        at any instant — see docs/resilience.md."""
        mgr = self.checkpoint_manager(directory, keep=keep)
        return mgr.save({"params": params, "opt_state": opt_state,
                         "aux": aux}, self.num_update)

    def latest_step(self, directory):
        """Newest committed step under ``directory``, or None."""
        return self.checkpoint_manager(directory).latest_step()

    def auto_resume(self, directory, data_shapes, label_shapes=None,
                    dtype=_np.float32):
        """Resume from the latest committed checkpoint under
        ``directory``: returns (params, opt_state, aux, step) with the
        trainer's update counter restored, or None when the run is
        fresh.  The one call a preemptible training script makes before
        its loop."""
        mgr = self.checkpoint_manager(directory)
        params_t, opt_t, aux_t = self.abstract_state(
            data_shapes, label_shapes, dtype)
        got = mgr.auto_resume(
            {"params": params_t, "opt_state": opt_t, "aux": aux_t})
        if got is None:
            return None
        restored, step = got
        self.num_update = step
        return (restored["params"], restored["opt_state"],
                restored["aux"], step)

    def hotstate_snapshot(self, params, opt_state, aux):
        """Host-offload this rank's shards of the training state into
        the warm-handoff area (``resilience.hotstate.snapshot``): the
        device→host half of warm elasticity.  Call at every stable
        point (right after a versioned checkpoint commits is the
        natural cadence) and again before ``exit_for_remesh``."""
        from ..resilience import hotstate as _hotstate
        return _hotstate.snapshot(
            {"params": params, "opt_state": opt_state, "aux": aux},
            step=self.num_update)

    def elastic_resume(self, directory, data_shapes, label_shapes=None,
                       dtype=_np.float32, source="auto", kv=None):
        """:meth:`auto_resume` for a re-meshed incarnation — the
        resharded-resume seam of elastic training.

        ``source`` picks the rung of the recovery ladder:

        - ``"warm"``: resume from the host-memory handoff area
          (``resilience.hotstate``) — the KV-agreed shard directory
          names which surviving payload serves each old rank, the
          assembled host tree is re-placed with THIS trainer's mesh
          shardings (``put_replicated_host``), and no checkpoint is
          read.  Any missing/corrupt shard degrades to the checkpoint
          rung — structured, never a crash.
        - ``"cold"``: the PR-3 versioned checkpoint under
          ``directory`` (``abstract_state`` supplies
          ShapeDtypeStruct+sharding targets and orbax reshards the
          saved leaves onto the new mesh).
        - ``"auto"`` (default): warm when ``MXTPU_WARM_REMESH`` is on,
          cold otherwise.

        Either way the transition leaves its ``elastic`` telemetry
        record: an ``event="resume"`` stamped with generation, world
        size, the ``path`` actually taken (``warm``/``cold``), the
        restore ``duration_ms``, and — when the warm rung gave way —
        the ``fallback_reason``, so ``mxtop`` and the ``--fault``
        timelines show the topology change AND what the recovery cost.
        """
        import time as _t
        from ..resilience import elastic as _elastic
        from ..resilience import hotstate as _hotstate
        from .sharding import put_replicated_host
        t0 = _t.monotonic()
        got, path, fallback, meta = None, "cold", None, None
        try_warm = source == "warm" or (
            source == "auto" and _hotstate.warm_enabled())
        if try_warm:
            abstract = self.abstract_state(data_shapes, label_shapes,
                                           dtype)
            target = {"params": abstract[0], "opt_state": abstract[1],
                      "aux": abstract[2]}
            try:
                host_tree, step, meta = _hotstate.warm_resume(
                    target, kv=kv)
                placed = jax.tree_util.tree_map(
                    lambda a, t: put_replicated_host(a, t.sharding),
                    host_tree, target)
                self.num_update = step
                got = (placed["params"], placed["opt_state"],
                       placed["aux"], step)
                path = "warm"
            except _hotstate.HotStateUnavailable as exc:
                fallback = exc.reason
        if got is None:
            got = self.auto_resume(directory, data_shapes, label_shapes,
                                   dtype)
        try:
            world = jax.process_count()
        except Exception:
            world = 1
        _elastic.emit_transition(
            "resume", step=None if got is None else got[3],
            world_size=world, fresh=got is None, path=path,
            fallback_reason=fallback,
            n_payloads=None if meta is None else meta.get("n_payloads"),
            duration_ms=round((_t.monotonic() - t0) * 1000.0, 3),
            mesh={a: int(s) for a, s in self.mesh.shape.items()})
        return got

    def shard_batch(self, batch):
        """Place host batch arrays onto the mesh with dp/sp sharding —
        the analog of executor_manager.load_data_batch slicing.

        Multi-process: each process passes its PROCESS-LOCAL portion
        (the reference's num_parts/part_index shard); the global batch
        is their concatenation over the dp axis."""
        from ..observability import spans as _spans
        with _spans.span("h2d", step=self.num_update):
            return _place_batch(batch, self.batch_sharding)

    # ------------------------------------------------------------------
    # steps
    # ------------------------------------------------------------------
    def _init_sentinel_state(self):
        """Replicated device scalars for the compiled sentinel gate."""
        from .sharding import put_replicated_host
        rep = self._replicated()
        return {
            "scale": put_replicated_host(
                jnp.float32(self._loss_scale_init), rep),
            "good_steps": put_replicated_host(jnp.int32(0), rep),
            "skipped": put_replicated_host(jnp.int32(0), rep),
            "last_good": put_replicated_host(jnp.int32(0), rep),
        }

    def sentinel_stats(self):
        """Host view of the sentinel counters: dict with ``scale``,
        ``good_steps``, ``skipped``, ``last_good`` — or None when the
        sentinel is off or no step has run.  Forces a device sync, so
        poll it at logging cadence, not every step."""
        if self._sentinel_state is None:
            return None
        return {k: _np.asarray(jax.device_get(v)).item()
                for k, v in self._sentinel_state.items()}

    def step(self, params, opt_state, aux, batch, rng=None):
        """Run one fused train step; returns (params, opt_state, aux, outputs)."""
        self.num_update += 1
        opt = self.optimizer
        if opt.lr_scheduler is not None:
            lr = opt.lr_scheduler(self.num_update)
        else:
            lr = opt.lr
        if rng is None:
            from .. import random as _random
            from ..executor import _zero_key
            rng = _random.next_key() if self._needs_rng else _zero_key()

        from .. import resilience as _resilience
        inj = _resilience.injector()
        if inj is not None:
            spec = inj.match("batch", step=self.num_update)
            if spec is not None and spec.kind == "nan":
                batch = dict(batch)
                for name in self.data_names:
                    if name in batch:
                        batch[name] = _resilience.poison_nan(batch[name])

        # host scalars ride with the call, as in Executor.fused_step
        step_args = (params, opt_state, aux, batch, rng,
                     _np.float32(lr), _np.float32(opt.wd),
                     _np.int32(self.num_update))
        if self.sentinel:
            if self._sentinel_state is None:
                self._sentinel_state = self._init_sentinel_state()
            step_args = step_args + (self._sentinel_state,)
        if self._abstract_args is None:
            self._abstract_args = jax.tree_util.tree_map(
                _device_scopes.abstractify, step_args)
            self._adopt_cached_step()
            self.device_scopes()    # on record from the first dispatch on

        from .. import observability as _obs
        # host dispatch wall only: XLA execution is async, so this
        # understates device time unless the caller syncs (the Module
        # path does via update(); docs/observability.md)
        dispatched = _obs.span("step_dispatch", step=self.num_update)

        def dispatch():
            # inside the guarded region so injected hangs are caught
            # exactly like a wedged collective would be
            with dispatched:
                _resilience.maybe_fault("step", step=self.num_update)
                with self._sp_scope():
                    out = self._jit_step(*step_args)
            if self.sentinel:
                self._sentinel_state = out[4]
                return out[:4]
            return out

        timeout = self.step_timeout_s
        if timeout is None:
            timeout = _resilience.step_timeout_s()

        # the fused step is a pod-wide rendezvous (the in-step psum means
        # every rank must enter for any to leave), so ledger it like a
        # collective: a step that never completes stays pending and the
        # flight dump names which update number the pod is wedged in
        _obs.flight.collective_begin(
            "train_step", self.num_update,
            participants=list(range(jax.process_count())))
        # without a timeout this is dispatch() itself
        out = _resilience.run_with_timeout(
            dispatch, timeout or None, phase="train_step",
            step=self.num_update)
        _obs.record_step(self.num_update, dispatched.dur_s,
                         batch_size=self._batch_samples(batch),
                         timing="dispatch")
        _obs.flight.collective_end("train_step", self.num_update)
        return out

    @staticmethod
    def _batch_samples(batch):
        """Leading-dim sample count of the first batch array (telemetry
        throughput only)."""
        try:
            first = next(iter(batch.values())) if isinstance(batch, dict) \
                else batch[0]
            return int(first.shape[0])
        except Exception:
            return None

    def emit_telemetry_counters(self, step_time_s=None):
        """Emit MFU / flops / HBM-bytes / sentinel counters for this
        trainer to the event log (needs one executed step for the cost
        analysis; polls sentinel_stats, which syncs the device — call
        at logging cadence).  Returns the cost fields emitted."""
        from .. import observability as _obs
        if not _obs.enabled():
            return {}
        fields = _obs.emit_trainer_counters(self, step_time_s)
        if self._sentinel_state is not None:
            _obs.emit_sentinel_counters(self.sentinel_stats(),
                                        step=self.num_update)
        return fields

    def eval(self, params, aux, batch, rng=None):
        if rng is None:
            rng = jax.random.PRNGKey(0)
        with self._sp_scope():
            return self._jit_eval(params, aux, batch, rng)

    def _step_cache_key(self):
        """Compile-cache key for this trainer's step: every input that
        shapes the traced program (docs/perf.md "Overlap").  Two
        trainers agreeing on all of it produce byte-identical traces,
        so sharing the jitted step (and its Lowered) is sound — the
        closures bake optimizer hypers and shardings as constants,
        which is exactly why those are in the key."""
        return _overlap.cache_key(
            _overlap.graph_fingerprint(self.symbol),
            _overlap.abstract_fingerprint(self._abstract_args),
            tuple(sorted((str(a), int(s))
                         for a, s in self.mesh.shape.items())),
            tuple(repr(d) for d in self.mesh.devices.flat),
            _overlap.rules_fingerprint(self.rules),
            str(self.compute_dtype), self.seq_axis, self.remat,
            self.zero1, self.fsdp, self.sentinel, self._bucket_grads, self._fused_opt,
            sorted(self._cast_exempt),
            _overlap.optimizer_fingerprint(self.optimizer),
            jax.__version__)

    def _adopt_cached_step(self):
        """First-step seam: look this trainer's key up in the process-
        global compile cache.  Hit → adopt the cached jitted step and
        Lowered (zero new tracing/lowering — a rebind, a bucketing
        module's second trainer, or an elastic re-mesh resume at a
        previously-seen world size skips straight to the compiled
        executable).  Miss → register ours for the next bind."""
        key = self._step_cache_key()
        entry = _overlap.compile_cache_get(key)
        if entry is not None:
            self._jit_step = entry["jit_step"]
            self._lowered = entry.get("lowered")
            self._cache_entry = entry
            return
        _overlap.note_lowering()
        self._cache_entry = {"jit_step": self._jit_step, "lowered": None}
        _overlap.compile_cache_put(key, self._cache_entry)

    # ------------------------------------------------------------------
    # async input feed (docs/perf.md "Overlap")
    # ------------------------------------------------------------------
    def prefetch_feed(self, batches, depth=None, prefetch=True):
        """Wrap an iterator of host batch dicts in a
        :class:`~mxnet_tpu.parallel.overlap.DevicePrefetcher` that runs
        :func:`_place_batch` (the ``shard_batch`` placement, timed as
        ``h2d``) on a background thread — batch N+1 transfers while
        step N runs.  Feed ``step()`` its output directly; do not call
        ``shard_batch`` again.  ``prefetch=None`` defers to
        ``MXTPU_PREFETCH``; returns ``batches`` unchanged when off."""
        if not _overlap.prefetch_enabled(prefetch):
            return batches
        return _overlap.DevicePrefetcher(
            batches, place_fn=lambda b: _place_batch(b, self.batch_sharding),
            depth=depth, name="trainer-feed")

    # ------------------------------------------------------------------
    # introspection (bench/MFU support)
    # ------------------------------------------------------------------
    def _lower(self):
        """Lowered form of the step at the shapes/shardings of the first
        executed step (needs one step() call first).  Stored into the
        compile-cache entry so later binds with the same key skip
        lowering entirely."""
        if self._lowered is None and self._abstract_args is not None:
            self._lowered = self.device_scopes().lower()
            if self._cache_entry is not None:
                self._cache_entry["lowered"] = self._lowered
        return self._lowered

    def _compiled(self):
        """The step as an AOT-compiled executable (for introspection;
        ``step()`` dispatches through jit's own cache), or None before
        the first step.  Compiled once."""
        if self._compiled_step is None:
            lowered = self._lower()
            if lowered is None:
                return None
            self._compiled_step = lowered.compile()
        return self._compiled_step

    def device_scopes(self):
        """The step's ``device_scopes.StepRecord``, or ``None`` before the
        first step: ``record.scopes()`` maps each instruction of the
        compiled step to its graph node and pass.  The record holds the
        jitted step, its abstract arguments and the mesh context the step
        is traced under — no device buffer — and outlives this trainer
        (docs/observability.md, "Device time by scope")."""
        if self._step_record is None and self._abstract_args is not None:
            from .ring_attention import attention_scope
            self._step_record = _device_scopes.register(
                "jit_" + self._jit_step.__name__, self._jit_step,
                self._abstract_args,
                _device_scopes.graph_nodes(self.symbol),
                context=functools.partial(attention_scope, self.mesh,
                                          self.seq_axis))
        return self._step_record

    def compiled_step_cost_analysis(self):
        """XLA cost analysis of the whole COMPILED train step (dict with
        'flops'), or None before the first step.  (The lowered-only
        analysis returns None on the TPU backend — found on the chip in
        PR 21: bench.py's MFU had been on its analytic fallback.)"""
        compiled = self._compiled()
        return None if compiled is None else compiled.cost_analysis()

    def donation_verified(self):
        """True iff XLA actually aliased donated inputs to outputs (the
        in-place-update guarantee), from the executable's memory analysis."""
        compiled = self._compiled()
        if compiled is None:
            return None
        mem = compiled.memory_analysis()
        if mem is None:
            return None
        alias = getattr(mem, "alias_size_in_bytes", None)
        if alias is None:
            return None
        return alias > 0

    def _sp_scope(self):
        """The mesh context MultiHeadAttention needs while the step is
        traced or run (ring or per-device flash attention)."""
        from .ring_attention import attention_scope
        return attention_scope(self.mesh, self.seq_axis)


class ShardedPredictor(object):
    """Mesh-sharded inference: the serving-side counterpart of
    ShardedTrainer (batch sharded over dp/sp, parameters placed by the
    same tp rules, forward jitted once per input shape).

    Beyond-reference: the reference predictor (c_predict_api) is
    single-device; this one serves models that only fit sharded, from
    either checkpoint format.

    Parameters
    ----------
    symbol : inference symbol (loss heads fine — run is_train=False).
    mesh / rules / seq_axis : as ShardedTrainer.
    arg_params / aux_params : host dicts (e.g. from
        model.load_checkpoint) — placed with the param shardings.
    """

    def __init__(self, symbol, mesh, arg_params, aux_params=None,
                 rules=None, seq_axis=None, data_names=("data",),
                 label_names=("softmax_label",), compute_dtype=None):
        from .sharding import put_replicated_host
        self.symbol = symbol
        self.mesh = mesh
        self.rules = rules
        self.seq_axis = seq_axis
        self.data_names = tuple(data_names)
        self.label_names = tuple(label_names)
        self.compute_dtype = (jnp.dtype(compute_dtype)
                              if compute_dtype is not None else None)
        from ..executor import _build_program
        program = _build_program(symbol, {})
        self._trace = program.trace

        self._arg_names = symbol.list_arguments()
        self._aux_names = symbol.list_auxiliary_states()
        missing = [n for n in self._arg_names
                   if n not in self.data_names and n not in arg_params
                   and n not in self.label_names]
        if missing:
            raise MXNetError("ShardedPredictor: missing parameters %s"
                             % missing)
        self.params = {}
        for name, value in arg_params.items():
            host = _np.asarray(getattr(value, "asnumpy", lambda: value)())
            sharding = NamedSharding(
                mesh, param_pspec(name, host.shape, mesh, rules))
            self.params[name] = put_replicated_host(host, sharding)
        self.aux = {}
        for name, value in (aux_params or {}).items():
            host = _np.asarray(getattr(value, "asnumpy", lambda: value)())
            self.aux[name] = put_replicated_host(
                host, NamedSharding(mesh, P()))

        from ..train_step import cast_each, compute_cast
        cast = compute_cast(symbol, self.compute_dtype, self.label_names)

        def forward(params, aux, batch, rng):
            args = cast_each(cast, params)
            # loss-layer label slots bind as zeros (predict contract)
            for n in self._arg_names:
                if n not in args and n not in batch:
                    shape = self._label_shape(n, batch)
                    args[n] = jnp.zeros(shape, jnp.float32)
            args.update(cast_each(cast, batch))
            outs, _ = self._trace(args, cast_each(cast, aux), rng, False)
            return [o.astype(jnp.float32)
                    if self.compute_dtype is not None
                    and jnp.issubdtype(o.dtype, jnp.floating) else o
                    for o in outs]

        self._jit_forward = jax.jit(forward)
        self._label_shapes = {}

    def _label_shape(self, name, batch):
        key = tuple(sorted((k, tuple(v.shape)) for k, v in batch.items()))
        cache = self._label_shapes.get(key)
        if cache is None:
            shapes = {k: tuple(v.shape) for k, v in batch.items()}
            arg_shapes, _, _ = self.symbol.infer_shape_partial(**shapes)
            cache = dict(zip(self._arg_names, arg_shapes or []))
            self._label_shapes[key] = cache
        shape = cache.get(name)
        if shape is None:
            raise MXNetError("cannot infer shape for %r" % name)
        return shape

    @classmethod
    def from_checkpoint(cls, prefix, epoch, mesh, **kwargs):
        """Build from a classic prefix-symbol.json + params checkpoint."""
        from ..model import load_checkpoint
        sym, arg_params, aux_params = load_checkpoint(prefix, epoch)
        return cls(sym, mesh, arg_params, aux_params, **kwargs)

    def batch_sharding(self, shape):
        return NamedSharding(self.mesh,
                             batch_pspec(shape, self.mesh, self.seq_axis))

    def predict(self, batch):
        """batch: dict name -> host/NDArray array (process-local portion
        under multi-process).  Returns list of host numpy outputs (the
        GLOBAL batch on every process)."""
        placed = _place_batch(batch, self.batch_sharding)
        rng = jax.random.PRNGKey(0)
        from .ring_attention import attention_scope
        with attention_scope(self.mesh, self.seq_axis):
            outs = self._jit_forward(self.params, self.aux, placed, rng)
        if jax.process_count() > 1:
            # outputs stay dp-sharded across hosts: gather before the
            # host copy (device_get cannot read non-addressable shards)
            from jax.experimental import multihost_utils
            return [_np.asarray(multihost_utils.process_allgather(
                o, tiled=True)) for o in outs]
        return [_np.asarray(jax.device_get(o)) for o in outs]
