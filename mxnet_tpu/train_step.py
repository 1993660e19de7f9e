"""The body of a train step, once: what is cast, what is differentiated,
how an optimizer's update is applied.

``Executor`` (``Module.fit``), ``ShardedTrainer`` and ``GPipeTrainer``
each jit a step of their own shape — what they donate, what they return
and how their arguments are sharded is theirs — over these pure
functions.  A change to casting, to the vjp or to the update is made
here and reaches every caller.
"""
from __future__ import annotations

import numpy as _np

import jax
import jax.numpy as jnp

from .observability.phases import UPDATE

__all__ = ["compute_cast", "no_cast", "cast_each", "zero_cotangent",
           "loss_and_grads", "preprocess_grads", "apply_updates"]


def no_cast(name, array):
    return array


def compute_cast(symbol, compute_dtype, label_names, extra_exempt=()):
    """``cast(name, array)`` for running ``symbol`` in ``compute_dtype``
    (None: nothing is cast) while the stored arrays keep their dtype.

    Never cast: non-floating arrays, and floating arrays that hold
    integers — ``label_names``, ``extra_exempt`` and every variable that
    feeds an Embedding's id slot.  bfloat16 holds integers exactly only
    to 256: a class label or a vocabulary id above that would round to
    another one.  ``cast.exempt`` is the set of exempted names."""
    cdt = None if compute_dtype is None else jnp.dtype(compute_dtype)
    exempt = set(label_names) | set(extra_exempt)
    for node in symbol._topo():
        if node.op is not None \
                and getattr(node.op, "op_name", "") == "Embedding":
            src, _ = node.inputs[0]
            if src.is_variable:
                exempt.add(src.name)
    exempt = frozenset(exempt)

    def cast(name, array):
        if cdt is None or name in exempt \
                or not jnp.issubdtype(array.dtype, jnp.floating):
            return array
        return array.astype(cdt)

    cast.exempt = exempt
    return cast


def cast_each(cast, arrays):
    """``{name: cast(name, array)}`` over a dict of arrays."""
    return {n: cast(n, a) for n, a in arrays.items()}


def zero_cotangent(tree):
    """Zero cotangents for outputs nothing differentiates (auxiliary
    state): zeros of a floating leaf's own dtype, and for an integer leaf
    (an op's counters) the ``float0`` zeros jax asks for."""
    def zero(a):
        if jnp.issubdtype(a.dtype, jnp.inexact):
            return jnp.zeros_like(a)
        return _np.zeros(a.shape, jax.dtypes.float0)

    return jax.tree_util.tree_map(zero, tree)


def loss_and_grads(trace, cast, wrt, other_args, aux, rng, out_grads=None):
    """Forward and vjp of ``trace`` in training mode: ``(outs, aux_out,
    grads)``, ``grads`` shaped and typed like ``wrt``.

    ``trace`` is a program's ``trace(args, aux, rng, is_train)``; its
    arguments are ``other_args`` with ``wrt`` laid over them.  Without
    ``out_grads`` every output is a loss head and takes a cotangent of
    ones; the auxiliary outputs take :func:`zero_cotangent`."""
    def f(wrt_values):
        # the cast is INSIDE f: the vjp through astype hands the stored
        # dtype's cotangents to the master weights
        args = cast_each(cast, other_args)
        args.update(cast_each(cast, wrt_values))
        outs, aux_out = trace(args, cast_each(cast, aux), rng, True)
        # auxiliary states (BatchNorm's statistics) stay as they are stored
        return outs, {n: v.astype(aux[n].dtype) for n, v in aux_out.items()}

    (outs, aux_out), vjp_fn = jax.vjp(f, wrt)
    if out_grads is None:
        out_grads = [jnp.ones_like(o) for o in outs]
    grads = vjp_fn((out_grads, zero_cotangent(aux_out)))[0]
    return outs, aux_out, grads


def preprocess_grads(optimizer, grads):
    """Each gradient as the optimizer's update takes it (rescaled,
    clipped): what a finiteness check looks at, and the ``grads`` of
    :func:`apply_updates`.  Under the device scope ``update``, as
    :func:`apply_updates` is."""
    with jax.named_scope(UPDATE):
        return {n: optimizer._preprocess_grad(g) for n, g in grads.items()}


def apply_updates(optimizer, weights, grads, states, lr, wd, t, *,
                  lr_mult=None, wd_mult=None, fused=""):
    """``optimizer.update_fn`` over every leaf of ``weights``:
    ``(new_weights, new_states)``, dicts keyed like ``weights``; a leaf
    without optimizer state has no entry in ``states`` or ``new_states``.

    ``grads`` are preprocessed (:func:`preprocess_grads`).  ``lr_mult`` /
    ``wd_mult`` map a key to a static factor on ``lr`` / ``wd``; a factor
    of exactly 1.0 emits no multiply.  ``fused`` ('1' or 'kernel') runs
    ``kernels.fused_opt.fused_apply`` in place of the loop: bit-identical,
    elementwise optimizers only, and it knows no multipliers.  Either way
    under the device scope ``update`` (observability/device_scopes.py)."""
    with jax.named_scope(UPDATE):
        if fused:
            from .kernels.fused_opt import fused_apply
            new_w, new_s = fused_apply(optimizer, weights, grads, states, lr,
                                       wd, t, mode=fused)
            return new_w, {n: s for n, s in new_s.items() if s is not None}

        def scaled(x, mult, n):
            m = 1.0 if mult is None else mult.get(n, 1.0)
            return x if m == 1.0 else x * m

        new_w, new_s = {}, {}
        for n in weights:
            w, s = optimizer.update_fn(weights[n], grads[n], states.get(n),
                                       scaled(lr, lr_mult, n),
                                       scaled(wd, wd_mult, n), t)
            new_w[n] = w
            if s is not None:
                new_s[n] = s
        return new_w, new_s
