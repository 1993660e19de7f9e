"""Fused optimizer step: bucketed flatten -> update -> unflatten.

The per-leaf loop of ``train_step.apply_updates`` costs
one fusion boundary (and on real hardware, one kernel launch) per
parameter; a transformer with hundreds of small norm/bias leaves spends
more time between updates than in them.  This module replaces the loop
with one sweep per size-targeted bucket:

1. leaves are grouped by dtype and packed into buckets by
   ``parallel.overlap.partition_buckets`` (the PR-8 size-targeted
   partition) of 64 MB;
2. each bucket's weights/grads/state leaves are flattened and
   concatenated into single vectors INSIDE the traced step;
3. the optimizer's pure ``update_fn`` runs once on the concatenated
   vectors — as a plain fused XLA computation when
   ``MXTPU_FUSED_OPT=1``; on the Pallas elementwise sweep kernel below
   when ``MXTPU_FUSED_OPT=kernel`` and the step is placed on a TPU
   (the same XLA computation anywhere else, ``common.dispatch``);
4. results are sliced back to the original leaf shapes.

Bit-identity: this is only legal for optimizers whose update is purely
elementwise (``Optimizer.elementwise``) — then flatten/concat commutes
with the update exactly, so the fused step is bit-identical to the
per-leaf path (asserted on a multi-device mesh by
tests/test_kernels.py).  LAMB (per-tensor trust ratios) and SGLD
(per-leaf noise draws) refuse the fused path and fall back.

The sweep kernel views each bucket as a (rows, 128) lane-major sheet,
tail-padded with zeros to a whole number of row blocks (dropped on
unflatten), and tiles rows in granule-aligned blocks; scalars (lr, wd,
t) ride as (1, 1) blocks.
"""
from __future__ import annotations

import functools

import numpy as _np

from ..base import MXNetError
from ..analysis.tiling import register_kernel_spec
from .common import cdiv, dispatch, env_flag, pick_block

__all__ = ["fused_opt_mode", "supports_fused", "plan_buckets",
           "fused_apply", "fused_opt_kernel_spec"]

_LANES = 128
_BUCKET_NBYTES = 64 << 20     # size target of one concatenated bucket


def fused_opt_mode(explicit=None):
    """``MXTPU_FUSED_OPT``: '' (off), '1' (fused XLA sweep), 'kernel'
    (fused Pallas sweep).  ``explicit`` overrides the env."""
    mode = explicit if explicit is not None else env_flag("MXTPU_FUSED_OPT")
    if mode in (True, 1):
        mode = "1"
    if mode in ("", "0", False, None):
        return ""
    if mode not in ("1", "kernel"):
        raise MXNetError("MXTPU_FUSED_OPT must be '', '1' or 'kernel', "
                         "got %r" % (mode,))
    return mode


def supports_fused(optimizer):
    """True when the optimizer's update is elementwise (flatten-safe)."""
    return bool(getattr(optimizer, "elementwise", False))


def plan_buckets(params, names=None, nbytes=None):
    """Partition param names into fused buckets.

    Same-dtype leaves pack together (concat needs one dtype per
    vector), each group split by the PR-8 size-targeted greedy
    partition.  Returns ``[[name, ...], ...]`` covering every name."""
    from ..parallel.overlap import partition_buckets, _nbytes
    names = list(names if names is not None else params)
    by_dtype = {}
    for n in names:
        by_dtype.setdefault(str(_np.dtype(params[n].dtype)), []).append(n)
    target = _BUCKET_NBYTES if nbytes is None else int(nbytes)
    buckets = []
    for _dt, group in sorted(by_dtype.items()):
        sized = [(n, _nbytes(params[n])) for n in group]
        buckets.extend(partition_buckets(sized, target))
    return buckets


# ----------------------------------------------------------------------
# the elementwise sweep kernel
# ----------------------------------------------------------------------
def _sweep_block_layout(rows, block_rows, dtype, n_state):
    """(block, array, dtype) triples: weight, grad, state leaves, then
    the (1, 1) scalars lr/wd/t, then outputs (weight', state') — shared
    by the pallas_call and the MXL-K spec."""
    sheet = ((block_rows, _LANES), (rows, _LANES), str(dtype))
    scalar = ((1, 1), (1, 1), "float32")
    in_blocks = [sheet, sheet] + [sheet] * n_state + [scalar] * 3
    out_blocks = [sheet] + [sheet] * n_state
    return in_blocks, out_blocks


def _sweep_kernel(*refs, update, n_state):
    """Grid (row_blocks,): one elementwise update over a sheet block.
    ``update(w, g, state_leaves, lr, wd, t) -> (w', state_leaves')`` is
    the optimizer's pure formula, traced straight into the kernel."""
    w_ref, g_ref = refs[0], refs[1]
    s_refs = refs[2:2 + n_state]
    lr_ref, wd_ref, t_ref = refs[2 + n_state:5 + n_state]
    ow_ref = refs[5 + n_state]
    os_refs = refs[6 + n_state:]
    lr = lr_ref[0, 0]
    wd = wd_ref[0, 0]
    t = t_ref[0, 0]
    new_w, new_state = update(w_ref[...], g_ref[...],
                              [r[...] for r in s_refs], lr, wd, t)
    ow_ref[...] = new_w.astype(ow_ref.dtype)
    for r, v in zip(os_refs, new_state):
        r[...] = v.astype(r.dtype)


def _sweep_call(w, g, state_leaves, lr, wd, t, update, interpret,
                block_rows=512):
    """Run one bucket's update through the Pallas sweep.  ``w``/``g``/
    state leaves are flat 1-D same-dtype vectors."""
    import jax
    import jax.numpy as jnp
    import jax.experimental.pallas as pl

    n = w.shape[0]
    sub = {1: 32, 2: 16}.get(jnp.dtype(w.dtype).itemsize, 8)
    br = pick_block(cdiv(n, _LANES), sub, block_rows)
    # the flat vector is padded anyway: pad to whole row BLOCKS
    rows = cdiv(cdiv(n, _LANES), br) * br
    pad = rows * _LANES - n
    n_state = len(state_leaves)

    def sheet(v):
        return jnp.pad(v, (0, pad)).reshape(rows, _LANES)

    def scalar(v):
        return jnp.asarray(v, jnp.float32).reshape(1, 1)

    in_blocks, out_blocks = _sweep_block_layout(rows, br, w.dtype, n_state)
    grid = (rows // br,)

    def row_map(i):
        return (i, 0)

    def pin_map(i):
        return (0, 0)

    in_specs = [pl.BlockSpec(b[0], row_map) for b in in_blocks[:2 + n_state]]
    in_specs += [pl.BlockSpec(b[0], pin_map)
                 for b in in_blocks[2 + n_state:]]
    out_specs = [pl.BlockSpec(b[0], row_map) for b in out_blocks]
    kernel = functools.partial(_sweep_kernel, update=update,
                               n_state=n_state)
    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=[jax.ShapeDtypeStruct(b[1], w.dtype)
                   for b in out_blocks],
        name="fused_opt_sweep",
        interpret=interpret,
    )(sheet(w), sheet(g), *[sheet(s) for s in state_leaves],
      scalar(lr), scalar(wd), scalar(t))

    def unsheet(v):
        return v.reshape(rows * _LANES)[:n]

    return unsheet(outs[0]), [unsheet(v) for v in outs[1:]]


# ----------------------------------------------------------------------
# the fused apply
# ----------------------------------------------------------------------
def fused_apply(optimizer, params, grads, opt_state, lr, wd, t,
                names=None, nbytes=None, mode=None, interpret=None):
    """One fused optimizer step over ``names`` (default: all params).

    Pure/traceable; returns ``(new_params, new_opt_state)`` dicts for
    exactly the covered names.  ``grads`` are preprocessed, as
    ``train_step.apply_updates`` takes them.
    """
    import jax
    import jax.numpy as jnp

    if not supports_fused(optimizer):
        raise MXNetError(
            "%s is not elementwise (per-tensor norms or per-leaf rng): "
            "the fused optimizer sweep would change semantics"
            % type(optimizer).__name__)
    mode = fused_opt_mode(mode) or "1"
    names = list(names if names is not None else params)
    new_params, new_state = {}, {}

    def update(w, g, state_leaves, lr_, wd_, t_):
        if state_leaves:
            treedef = _state_treedef(optimizer, w)
            state = jax.tree_util.tree_unflatten(treedef, state_leaves)
        else:
            state = None
        nw, ns = optimizer.update_fn(w, g, state, lr_, wd_, t_)
        return nw, (jax.tree_util.tree_leaves(ns) if ns is not None else [])

    for bucket in plan_buckets(params, names=names, nbytes=nbytes):
        sizes = [int(_np.prod(params[n].shape or (1,))) for n in bucket]
        w_flat = jnp.concatenate(
            [jnp.ravel(params[n]) for n in bucket])
        g_flat = jnp.concatenate([jnp.ravel(grads[n]) for n in bucket])
        state_leaves = _concat_state(optimizer, opt_state, bucket)
        scalars = [jnp.asarray(v, jnp.float32) for v in (lr, wd, t)]

        def pallas_sweep(w, g, leaves, lr_, wd_, t_, interpret=False):
            return _sweep_call(w, g, leaves, lr_, wd_, t_, update,
                               interpret)

        if mode != "kernel":
            nw, ns = update(w_flat, g_flat, state_leaves, *scalars)
        elif interpret is not None:
            nw, ns = pallas_sweep(w_flat, g_flat, state_leaves, *scalars,
                                  interpret=bool(interpret))
        else:
            nw, ns = dispatch(pallas_sweep, update, w_flat, g_flat,
                              state_leaves, *scalars)
        offset = 0
        for n, size in zip(bucket, sizes):
            shape = tuple(params[n].shape)
            new_params[n] = jax.lax.dynamic_slice_in_dim(
                nw, offset, size).reshape(shape)
            if ns:
                leaves = [jax.lax.dynamic_slice_in_dim(s, offset, size)
                          .reshape(shape) for s in ns]
                treedef = _state_treedef(optimizer, params[n])
                new_state[n] = jax.tree_util.tree_unflatten(treedef,
                                                            leaves)
            else:
                new_state[n] = None
            offset += size
    return new_params, new_state


def _state_treedef(optimizer, like):
    import jax
    proto = optimizer.create_state_arrays((1,), _np.float32)
    return jax.tree_util.tree_structure(proto)


def _concat_state(optimizer, opt_state, bucket):
    """Per-component concatenation of the bucket's state pytrees.
    Returns a list of flat vectors, one per state leaf position
    (``[]`` for stateless optimizers)."""
    import jax
    import jax.numpy as jnp
    proto = optimizer.create_state_arrays((1,), _np.float32)
    if proto is None:
        return []
    n_leaves = len(jax.tree_util.tree_leaves(proto))
    cols = [[] for _ in range(n_leaves)]
    for n in bucket:
        leaves = jax.tree_util.tree_leaves(opt_state[n])
        if len(leaves) != n_leaves:
            raise MXNetError("fused_apply: state of %r has %d leaves, "
                             "optimizer declares %d"
                             % (n, len(leaves), n_leaves))
        for i, leaf in enumerate(leaves):
            cols[i].append(jnp.ravel(leaf))
    return [jnp.concatenate(c) for c in cols]


def fused_opt_kernel_spec(numel=1 << 20, block_rows=512, dtype="float32",
                          n_state=1):
    """MXL-K spec for the sweep at one dtype (CI sweeps f32/bf16/int8;
    row blocks are granule multiples at all three) — same layout helper
    as the call."""
    sub = {1: 32, 2: 16}.get(_np.dtype(dtype).itemsize, 8)
    br = pick_block(cdiv(numel, _LANES), sub, block_rows)
    rows = cdiv(cdiv(numel, _LANES), br) * br
    in_blocks, out_blocks = _sweep_block_layout(rows, br, dtype, n_state)
    names_in = (["weight", "grad"]
                + ["state%d" % i for i in range(n_state)]
                + ["lr", "wd", "t"])
    names_out = ["weight_out"] + ["state%d_out" % i for i in range(n_state)]
    blocks = [{"role": "in", "name": nm, "block": b[0], "array": b[1],
               "dtype": b[2]} for nm, b in zip(names_in, in_blocks)]
    blocks += [{"role": "out", "name": nm, "block": b[0], "array": b[1],
                "dtype": b[2]} for nm, b in zip(names_out, out_blocks)]
    return {"name": "fused_opt_sweep[%s]" % dtype,
            "origin": "mxnet_tpu/kernels/fused_opt.py",
            "grid": (rows // br,),
            "blocks": blocks}


register_kernel_spec(
    "kernels.fused_opt.sweep",
    lambda: [fused_opt_kernel_spec(dtype=dt)
             for dt in ("float32", "bfloat16", "int8")])
