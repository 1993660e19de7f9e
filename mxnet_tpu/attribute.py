"""Attribute scoping for symbols (parity: python/mxnet/attribute.py).

``with mx.AttrScope(ctx_group='stage1'):`` attaches attrs to every symbol
created inside — the mechanism behind ctx-group model parallelism
(SURVEY §2 "Parallelism strategies": example/model-parallel-lstm/lstm.py:48-99).
"""
from __future__ import annotations

import threading

__all__ = ["AttrScope"]


class AttrScope:
    _current = threading.local()

    def __init__(self, **kwargs):
        for value in kwargs.values():
            if not isinstance(value, str):
                raise ValueError("attributes must be strings")
        self._attr = kwargs
        self._old_scope = None

    def get(self, attr):
        """Merge scope attrs into user-supplied ``attr`` dict (user wins)."""
        if self._attr:
            ret = self._attr.copy()
            if attr:
                ret.update(attr)
            return ret
        return attr if attr else {}

    def __enter__(self):
        if not hasattr(AttrScope._current, "value"):
            AttrScope._current.value = AttrScope()
        self._old_scope = AttrScope._current.value
        attr = AttrScope._current.value._attr.copy()
        attr.update(self._attr)
        self._attr = attr
        AttrScope._current.value = self
        return self

    def __exit__(self, ptype, value, trace):
        AttrScope._current.value = self._old_scope

    @staticmethod
    def current():
        if not hasattr(AttrScope._current, "value"):
            AttrScope._current.value = AttrScope()
        return AttrScope._current.value


def mirror_scope(stage_name, enabled=True):
    """Attr scope tagging every op created inside it for activation
    recompute: ``force_mirroring`` (overrides the env knob's conv skip
    list) + ``mirror_stage=stage_name`` (segment boundary — ops sharing
    a stage form ONE jax.checkpoint segment in the executor's mirror
    lowering, executor.py ``_mirror_segments``).  A segment saves its
    inputs and recomputes the rest in backward, but for the values their
    producer names in ``executor.KEPT``: what the flash attention kernel
    hands its backward (q, k, v, the output, the softmax statistics),
    what the gated delta rule's forward sweep and KDA's hand on (q, k,
    v, the chunk scalars, the chunk states, the output) and what the routed
    layer hands its backward (the chosen experts and their weights, the
    sorted order, the counts; the routed sum where a backward reads it),
    because recomputing them is a second run of the kernels and a kept
    value fits only the operands and choices it was made from — so a
    block runs each forward kernel and its grouped products once, and a
    block with no such producer saves its inputs alone.  What is kept
    depends only on what the traced segment holds; nothing here or in
    the environment selects it.
    ``enabled=False`` returns a no-op context so model builders can
    expose a ``mirror_blocks`` flag without branching (models/resnet.py,
    models/transformer.py)."""
    if not enabled:
        import contextlib
        return contextlib.nullcontext()
    return AttrScope(force_mirroring="true", mirror_stage=stage_name)
