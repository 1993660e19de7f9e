"""Canonical phase-name registry: the ONE place a phase is named.

Before this module, the training phase names lived in
``spans.SPAN_NAMES``, the serving phase names were implicit in the
``serve`` record's ``*_ms`` field names, and ``tools/parse_log.py``
re-derived its column names from both — three copies that could (and
in review almost did) drift.  Everything now imports from here:

- :mod:`.spans` re-exports :data:`TRAIN_PHASES` as ``SPAN_NAMES``
  (compat alias) and the fit/trainer/kvstore wiring uses the named
  constants,
- :mod:`mxnet_tpu.profiler` exposes the same :data:`PHASES` so an
  xprof region name and an event-log span name can never disagree,
- :mod:`mxnet_tpu.serving.telemetry` derives its per-phase ``*_ms``
  fields from :data:`SERVE_PHASES`,
- ``tools/parse_log.py`` builds its serve phase columns from the same
  tuple.

Free-form span names remain legal everywhere (``span("my_phase")``
works); the registry fixes the *built-in* names, it does not close the
namespace.
"""
from __future__ import annotations

__all__ = ["TRAIN_PHASES", "SERVE_PHASES", "PHASES", "is_canonical",
           "DATA_WAIT", "H2D", "STEP", "ALLREDUCE", "KV_BARRIER",
           "CKPT_SAVE", "EVAL", "HOTSTATE_SNAPSHOT", "WARM_RESUME",
           "FIT_STEP", "STEP_DISPATCH", "UPDATE", "METRIC", "METRIC_SYNC",
           "BATCH_END", "EPOCH_END", "QUEUE_WAIT", "PACK", "DEVICE", "UNPACK",
           "DEVICE_PHASES", "GRAD_SYNC", "ATTENTION_SCOPES", "ROUTED_SCOPES",
           "DEVICE_SUBSCOPES", "COMPILER_NAMED"]

#: phases the training wiring emits (fit loops, ShardedTrainer, kvstore,
#: and the warm-elasticity transition: host offload + warm assembly).
#: From ``fit_step`` on: one iteration of ``Module.fit`` and what it is
#: made of — the jitted call's dispatch (also ``ShardedTrainer.step``'s),
#: a non-fused ``update``, ``metric`` with the blocking read ``metric_sync``
#: inside it, and the batch-end callbacks — then ``epoch_end``, the
#: parameters' round trip through the host and the epoch-end callbacks.
TRAIN_PHASES = ("data_wait", "h2d", "step", "allreduce", "kv_barrier",
                "ckpt_save", "eval", "hotstate_snapshot", "warm_resume",
                "fit_step", "step_dispatch", "update", "metric",
                "metric_sync", "batch_end", "epoch_end")

#: request-visible serving phases, in pipeline order (docs/serving.md)
SERVE_PHASES = ("queue_wait", "pack", "device", "unpack")

#: every built-in phase name, training first then serving
PHASES = TRAIN_PHASES + SERVE_PHASES

(DATA_WAIT, H2D, STEP, ALLREDUCE, KV_BARRIER, CKPT_SAVE, EVAL,
 HOTSTATE_SNAPSHOT, WARM_RESUME, FIT_STEP, STEP_DISPATCH, UPDATE, METRIC,
 METRIC_SYNC, BATCH_END, EPOCH_END) = TRAIN_PHASES
(QUEUE_WAIT, PACK, DEVICE, UNPACK) = SERVE_PHASES

#: what a device operation of a compiled train step is charged to
#: (:mod:`.device_scopes`): ``forward``, ``recompute`` and ``backward``
#: are read off jax's own name stack around a graph node's scope; the
#: step opens ``update`` (the host phase's word: the optimizer's update,
#: here inside the step) and ``grad_sync`` itself; ``other`` is the rest.
GRAD_SYNC = "grad_sync"
DEVICE_PHASES = ("forward", "recompute", "backward", UPDATE, GRAD_SYNC,
                 "other")

#: scopes inside one graph node, where a node is more than a tenth of a
#: step and holds unlike work: the attention ops (``GatedDeltaNet``'s
#: ``kernel`` holds the rule's own ``gated_delta_rule`` scope) and
#: ``RoutedExperts`` (``dispatch``: the sort, the gathers, the scatters)
ATTENTION_SCOPES = ("proj_in", "rotary_norm", "kernel", "proj_out")
ROUTED_SCOPES = ("route", "dispatch", "experts", "shared")
DEVICE_SUBSCOPES = ATTENTION_SCOPES + ROUTED_SCOPES

#: instructions the compiler names itself, dropping the ``op_name`` they
#: were lowered under, and the sub-scope they belong to: XLA:TPU makes
#: of ``lax.ragged_dot`` a Mosaic call ``ragged-dot-none`` (and
#: ``ragged-dot-metadata`` beside it) whose ``op_name`` is that name.
#: :mod:`.device_scopes` puts such an instruction under the scope its
#: computation's other instructions share (a routed layer's chunk loop)
COMPILER_NAMED = {"ragged-dot": "experts"}

_CANON = frozenset(PHASES)


def is_canonical(name):
    """Is ``name`` one of the built-in phase names?"""
    return name in _CANON
