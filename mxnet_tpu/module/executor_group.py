"""DataParallelExecutorGroup for the Module API.

TPU-native counterpart of ``python/mxnet/module/executor_group.py:21``.

Device placement is TPU-first: a homogeneous multi-context bind builds ONE
executor over a ``jax.sharding.Mesh`` of those devices — the batch is
sharded along the mesh's data axis and parameters are replicated, so the
backward pass carries an XLA ``all-reduce`` over the mesh *inside* the
compiled step.  That single executor is what lets the fused
fwd+bwd+optimizer step (one dispatch per fit step) apply to multi-device
and multi-host training — the TPU collapse of the reference's per-device
executors + host/PS gradient reduction (``comm.h:186-345``,
``kvstore_dist.h:181-226``).

The legacy per-context slicing group (reference semantics,
``executor_group.py:104``) remains for heterogeneous contexts and
indivisible batches.
"""
from __future__ import annotations

import logging

import numpy as _np

from ..base import MXNetError
from ..ndarray import NDArray, zeros, concatenate
from ..executor_manager import (_split_input_slice, _check_arguments,
                                _bind_exec)
from ..io import DataDesc
from ..observability import spans as _spans

__all__ = ["DataParallelExecutorGroup"]


def _as_data_desc(pairs):
    out = []
    for item in pairs or []:
        if isinstance(item, DataDesc):
            out.append(item)
        else:
            out.append(DataDesc(item[0], tuple(item[1])))
    return out


class DataParallelExecutorGroup(object):
    """Parity: module/executor_group.py:21 (richer than the legacy
    executor_manager group: label-less bind, inputs_need_grad, merged
    outputs/input-grads, shared-group rebinding for bucketing)."""

    def __init__(self, symbol, contexts, workload, data_shapes, label_shapes,
                 param_names, for_training, inputs_need_grad,
                 shared_group=None, logger=None, fixed_param_names=None,
                 grad_req="write"):
        _check_arguments(symbol)
        self.symbol = symbol
        self.contexts = contexts
        self.workload = workload or [1] * len(contexts)
        self.for_training = for_training
        self.inputs_need_grad = inputs_need_grad
        self.fixed_param_names = set(fixed_param_names or [])

        self.arg_names = symbol.list_arguments()
        self.aux_names = symbol.list_auxiliary_states()
        # fixed params stay in param_names (so they are initialized, synced
        # and checkpointed); only their grad_req becomes 'null' — matching
        # the reference (module.py fixed_param_names handling)
        self.param_names = list(param_names)

        self.data_shapes = _as_data_desc(data_shapes)
        self.label_shapes = _as_data_desc(label_shapes)
        self.data_names = [d.name for d in self.data_shapes]
        self.label_names = [l.name for l in self.label_shapes]

        self.batch_size = self.data_shapes[0].shape[0]

        # -- sharded single-executor mode ------------------------------
        self.sharded = False
        self._mesh = None
        self._data_sharding = None
        self._repl_sharding = None
        if shared_group is not None:
            self.sharded = shared_group.sharded
            self._mesh = shared_group._mesh
            self._data_sharding = shared_group._data_sharding
            self._repl_sharding = shared_group._repl_sharding
            self._n_proc = shared_group._num_proc
        elif len(contexts) > 1:
            self._try_init_mesh(contexts, logger)
        if self.sharded:
            # one executor over the mesh sees the full (global) batch
            self.slices = [slice(0, self.batch_size)]
            contexts = [contexts[0]]
        else:
            self.slices = _split_input_slice(self.batch_size, self.workload)

        if shared_group is None:
            self.shared_data_arrays = [{} for _ in contexts]
        else:
            self.shared_data_arrays = shared_group.shared_data_arrays

        input_names = set(self.data_names) | set(self.label_names)
        if isinstance(grad_req, str):
            grad_req_dict = {}
            for name in self.arg_names:
                if name in self.fixed_param_names:
                    grad_req_dict[name] = "null"
                elif name in self.param_names:
                    grad_req_dict[name] = grad_req if for_training else "null"
                elif name in input_names:
                    grad_req_dict[name] = "write" if (
                        for_training and inputs_need_grad and
                        name in self.data_names) else "null"
                else:
                    grad_req_dict[name] = "null"
        else:
            grad_req_dict = dict(grad_req)
            # fixed params stay frozen regardless of how grad_req was spelled
            for name in self.fixed_param_names:
                grad_req_dict[name] = "null"

        self.execs = []
        for i, ctx in enumerate(contexts):
            islice = self.slices[i]
            shard = islice.stop - islice.start
            if self.sharded:
                # the mesh executor sees the global batch (local x hosts)
                shard = self.batch_size * self._n_proc
            input_shapes = {}
            for d in self.data_shapes + self.label_shapes:
                input_shapes[d.name] = (shard,) + tuple(d.shape[1:])
            shared_exec = None if shared_group is None else \
                shared_group.execs[i]
            need_grad = {n for n, r in grad_req_dict.items() if r != "null"}
            with self._mesh_scope():    # the program is keyed on it
                exec_ = _bind_exec(
                    self.symbol, ctx, input_shapes, self.param_names,
                    need_grad=need_grad if for_training else False,
                    base_exec=shared_exec,
                    shared_data_arrays=self.shared_data_arrays[i],
                    grad_req=grad_req_dict)
            self.execs.append(exec_)

        self.data_arrays = [[(self.slices[i], e.arg_dict[name])
                             for i, e in enumerate(self.execs)]
                            for name in self.data_names]
        self.label_arrays = [[(self.slices[i], e.arg_dict[name])
                              for i, e in enumerate(self.execs)]
                             for name in self.label_names]
        self.param_arrays = [[e.arg_dict[name] for e in self.execs]
                             for name in self.param_names]
        if for_training:
            # aligned with param_names; [None] entries for no-grad (fixed)
            # params, skipped by _update_params (model.py:91 contract)
            self.grad_arrays = [[e.grad_dict.get(name) for e in self.execs]
                                for name in self.param_names]
        else:
            self.grad_arrays = []
        self.aux_arrays = [[e.aux_dict[name] for e in self.execs]
                           for name in self.aux_names]

        # the batch copied ahead of its step (``stage_data_batch``), and
        # how many batches were bound from it / copied at dispatch.  Inputs
        # that view a shared buffer (a smaller bucket's) cannot hold a
        # copy beside them
        self._staged = None
        self._can_stage = not any(
            nd._parent is not None
            for per_exec in self.data_arrays + self.label_arrays
            for _, nd in per_exec)
        self.n_staged = 0
        self.n_loaded = 0

    # ------------------------------------------------------------------
    # sharded-mode plumbing
    # ------------------------------------------------------------------
    def _try_init_mesh(self, contexts, logger):
        """One mesh axis 'dp' over the context devices (all processes'
        devices under jax.distributed).  Falls back to legacy slicing when
        contexts are heterogeneous/duplicated or the batch doesn't divide."""
        import jax
        log = logger or logging
        if len({c.device_type for c in contexts}) != 1:
            return
        devices = [c.jax_device for c in contexts]
        if len(set(devices)) != len(devices):
            return
        n_proc = jax.process_count()
        if n_proc > 1:
            # SPMD over the pod: every process binds the same global
            # computation over all devices (its ctx list = local devices)
            devices = list(jax.devices())
        if (self.batch_size * n_proc) % len(devices) != 0:
            log.warning(
                "batch %d not divisible by %d devices: using per-device "
                "slicing instead of the sharded executor",
                self.batch_size * n_proc, len(devices))
            return
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        self._mesh = Mesh(_np.asarray(devices), ("dp",))
        self._data_sharding = NamedSharding(self._mesh, P("dp"))
        self._repl_sharding = NamedSharding(self._mesh, P())
        self._n_proc = n_proc
        self.sharded = True

    @property
    def _num_proc(self):
        return getattr(self, "_n_proc", 1)

    def _mesh_scope(self):
        """The mesh context attention ops need while the mesh executor
        is bound, traced and run (``ring_attention.sequence_parallel``):
        per-device flash attention — GSPMD cannot partition a Mosaic
        kernel.  A no-op for per-device slicing groups (no mesh)."""
        from ..parallel.ring_attention import attention_scope
        return attention_scope(self._mesh)

    def _put_sharded(self, value, sharding):
        """numpy/NDArray -> global jax array with the given sharding; the
        value is this process's local portion (= the whole array when
        single-process)."""
        from ..parallel.sharding import put_local_sharded
        if isinstance(value, NDArray):
            value = value.asnumpy()
        return put_local_sharded(value, sharding)

    def _ensure_on_mesh(self, extra_trees=()):
        """Commit params/aux (replicated) and any extra pytrees onto the
        mesh; loads/checkpoint restores leave arrays on the default device
        otherwise.  Data/label arrays are committed by load_data_batch."""
        import jax
        if not self.sharded:
            return [t for t in extra_trees]
        exec_ = self.execs[0]
        repl = self._repl_sharding

        def _committed(arr):
            return getattr(arr, "sharding", None) == repl

        for d in (exec_.arg_dict, exec_.aux_dict):
            for name, nd in d.items():
                if name in self.data_names or name in self.label_names:
                    continue
                if not _committed(nd.data):
                    nd._set_data(self._put_sharded(nd.data, repl))
        out = []
        for tree in extra_trees:
            out.append(jax.tree_util.tree_map(
                lambda a: a if _committed(a)
                else self._put_sharded(_np.asarray(a), repl), tree))
        return out

    # ------------------------------------------------------------------
    def _batch_sources(self, data_batch):
        """``(bound array, source)`` of every input ``data_batch`` fills:
        what :meth:`load_data_batch` hands to ``_set_data``.  A mesh group
        issues its ``device_put`` here; a per-context group yields the
        host slices, which the bound arrays copy."""
        sources = [(self.data_arrays, data_batch.data)]
        if self.label_arrays and data_batch.label:
            sources.append((self.label_arrays, data_batch.label))
        for targets, srcs in sources:
            for per_exec, src in zip(targets, srcs):
                if self.sharded:
                    yield per_exec[0][1], self._put_sharded(
                        src, self._data_sharding)
                    continue
                if isinstance(src, NDArray):
                    src = src.asnumpy()
                for islice, dst in per_exec:
                    yield dst, src[islice]

    def stage_data_batch(self, data_batch):
        """Issue ``data_batch``'s copies to the device now and keep the
        device arrays beside the batch they came from, without rebinding
        the inputs: the bound data and labels stay the running step's.
        The next :meth:`load_data_batch` binds them if it is handed this
        batch, and drops them whatever it is handed.  ``None`` drops the
        stage; a group that cannot stage (its inputs are views) stages
        nothing."""
        self._staged = None
        if data_batch is None or not self._can_stage:
            return
        with _spans.span("h2d", ahead=1):
            self._staged = (data_batch, [
                (dst, dst._placed(src))
                for dst, src in self._batch_sources(data_batch)])

    def load_data_batch(self, data_batch):
        staged, self._staged = self._staged, None
        if staged is not None and staged[0] is data_batch:
            # copied while the step before ran (``stage_data_batch``)
            self.n_staged += 1
            for dst, arr in staged[1]:
                dst._set_data(arr)
            return
        del staged      # another batch's stage goes before this one's copy
        self.n_loaded += 1
        # ``h2d`` is the ISSUE of the copies: ``device_put`` may return
        # before the bytes have moved, so what is still in flight shows up
        # as device idle time after the dispatch, not in this span
        with _spans.span("h2d"):
            for dst, src in self._batch_sources(data_batch):
                dst._set_data(src)

    def forward(self, data_batch=None, is_train=None):
        if data_batch is not None:
            self.load_data_batch(data_batch)
        if is_train is None:
            is_train = self.for_training
        self._ensure_on_mesh()
        with self._mesh_scope():
            for exec_ in self.execs:
                exec_.forward(is_train=is_train)

    def forward_backward(self, data_batch):
        """Fused fwd+bwd: ONE XLA dispatch per executor instead of the
        forward-then-recompute-in-backward pair (the fit-path hot loop)."""
        if not self.for_training:
            raise MXNetError("re-bind with for_training=True to run backward")
        self.load_data_batch(data_batch)
        self._ensure_on_mesh()
        with self._mesh_scope():
            for exec_ in self.execs:
                exec_.forward_backward()

    def fused_step(self, data_batch, optimizer, states, num_update):
        """Whole train step (fwd+bwd+optimizer update) as one dispatch.
        Single-executor groups: one context, or a sharded mesh group —
        where the dispatch also carries the gradient all-reduce over the
        'dp' axis (the in-step collapse of kvstore device/dist_sync)."""
        if len(self.execs) != 1:
            raise MXNetError("fused_step requires a single-context or "
                             "sharded group")
        self.load_data_batch(data_batch)
        if self.sharded:
            states = self._ensure_on_mesh((states,))[0]
        with self._mesh_scope():
            return self.execs[0].fused_step(optimizer, states, num_update)

    def fused_step_hlo(self, optimizer):
        """Lowered HLO text of the fused step (introspection/tests: the
        sharded step must contain an all-reduce over the mesh)."""
        exec_ = self.execs[0]
        states = self._ensure_on_mesh(
            (exec_.init_fused_states(optimizer),))[0]
        if self.sharded and self._num_proc == 1:
            # lower with batch inputs committed the way load_data_batch
            # commits them, else the trace sees unsharded data
            for name in self.data_names + self.label_names:
                nd = exec_.arg_dict[name]
                nd._set_data(self._put_sharded(nd.data,
                                               self._data_sharding))
        elif self.sharded:
            # multi-process: the bind-time buffers are global-shaped, so
            # re-putting them as "local" data would square the batch —
            # require a loaded batch instead
            for name in self.data_names + self.label_names:
                if exec_.arg_dict[name].data.sharding != self._data_sharding:
                    raise MXNetError("fused_step_hlo under multi-process "
                                     "needs a batch loaded first "
                                     "(load_data_batch)")
        with self._mesh_scope():
            return exec_.lower_fused_step(optimizer, states)

    def backward(self, out_grads=None):
        if not self.for_training:
            raise MXNetError("re-bind with for_training=True to run backward")
        with self._mesh_scope():
            for i, exec_ in enumerate(self.execs):
                if out_grads is not None:
                    islice = self.slices[i]
                    sliced = [g[islice] if g.shape[0] == self.batch_size
                              else g for g in out_grads]
                    exec_.backward(sliced)
                else:
                    exec_.backward()

    # ------------------------------------------------------------------
    def get_outputs(self, merge_multi_context=True):
        outputs = [[e.outputs[i] for e in self.execs]
                   for i in range(len(self.execs[0].outputs))]
        if merge_multi_context:
            return [_merge(parts) for parts in outputs]
        return outputs

    def get_input_grads(self, merge_multi_context=True):
        if not self.inputs_need_grad:
            raise MXNetError("bind with inputs_need_grad=True first")
        grads = [[e.grad_dict[name] for e in self.execs]
                 for name in self.data_names]
        if merge_multi_context:
            return [_merge(parts) for parts in grads]
        return grads

    def get_params(self, arg_params, aux_params):
        """Average device copies out into host dicts (executor_group.py:470)."""
        for name, block in zip(self.param_names, self.param_arrays):
            full = sum(w.asnumpy() for w in block) / len(block)
            arg_params[name] = NDArray(full)
        for name, block in zip(self.aux_names, self.aux_arrays):
            full = sum(w.asnumpy() for w in block) / len(block)
            aux_params[name] = NDArray(full)

    def set_params(self, arg_params, aux_params):
        for exec_ in self.execs:
            exec_.copy_params_from(arg_params, aux_params)
        # a mesh group's parameters live on EVERY device of the mesh, not
        # on the first context until the next step happens to move them
        self._ensure_on_mesh()

    def update_metric(self, eval_metric, labels):
        # the metric's own arithmetic is this span's self time; its
        # blocking reads are the ``metric_sync`` children
        with _spans.span("metric"):
            if self.sharded and self._num_proc > 1:
                # outputs are global (batch x hosts); this process owns the
                # local batch — evaluate on our addressable output shards
                exec_ = self.execs[0]
                local_outs = []
                for out in exec_.outputs:
                    shards = sorted(out.data.addressable_shards,
                                    key=lambda s: s.index[0].start or 0)
                    local_outs.append(NDArray(_np.concatenate(
                        [_np.asarray(s.data) for s in shards])))
                eval_metric.update(list(labels), local_outs)
                return
            for texec, islice in zip(self.execs, self.slices):
                labels_slice = [label[islice] for label in labels]
                eval_metric.update(labels_slice, texec.outputs)

    def install_monitor(self, mon):
        for exec_ in self.execs:
            mon.install(exec_)


def _merge(parts):
    if len(parts) == 1:
        return parts[0]
    return concatenate(parts, axis=0)
