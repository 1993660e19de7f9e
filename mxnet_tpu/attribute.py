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
    because recomputing the last two is a second call of the kernel and
    they fit only the operands they were made from — so a block with
    attention in it runs the forward kernel once, and a block with none
    saves its inputs alone.  What is kept depends only on what the
    traced segment holds; nothing here or in the environment selects it.
    ``enabled=False`` returns a no-op context so model builders can
    expose a ``mirror_blocks`` flag without branching (models/resnet.py,
    models/transformer.py)."""
    if not enabled:
        import contextlib
        return contextlib.nullcontext()
    return AttrScope(force_mirroring="true", mirror_stage=stage_name)
