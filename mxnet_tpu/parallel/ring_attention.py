"""Long-context attention: flash kernel + ring sequence parallelism.

This subsystem has no reference counterpart (SURVEY §5 "Long-context /
sequence parallelism": the reference only offers bucketing and pipeline
LSTM) — it is the TPU-native capability that replaces those workarounds
for long sequences:

- ``flash_attention``: fused online-softmax attention as a Pallas TPU
  kernel (MXU matmuls, no (seq, seq) materialization in HBM) where the
  computation is placed on a TPU; the jnp reference implementation
  anywhere else, so tests/CPU paths stay exact.  The kernel multiplies
  q, k, v in the dtype they arrive in (bfloat16 operands are not
  widened; float32 operands get the product they always got, at
  Mosaic's default precision), sums every product in float32, rounds p
  to v's dtype for p·v only, and keeps the softmax state and the saved
  logsumexp in float32.
  Under ``causal`` a query block reads the key blocks up to the
  diagonal and no further: masked blocks are skipped, not computed.
- ``ring_attention``: blockwise attention over a ``Mesh`` axis ("sp"):
  each device holds a sequence chunk of q/k/v; k/v chunks rotate around
  the ring via ``lax.ppermute`` while the online-softmax state (o, m, l)
  accumulates — compute and ICI transfer overlap, HBM stays O(seq/sp).
  Use inside ``shard_map`` (see tests/test_ring_attention.py) or through
  ``models/transformer.py``'s trainer integration.

Math (online softmax): for each incoming kv block,
    m' = max(m, rowmax(s));  c = exp(m - m')
    l  = l*c + rowsum(exp(s - m'));  o = o*c + exp(s - m') @ v
final output o / l — associative across blocks, so ring order is free.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["attention_reference", "flash_attention", "ring_attention",
           "blockwise_combine", "sequence_parallel",
           "current_sequence_parallel", "attention_scope"]

_NEG_INF = -1e30
# sublanes of a float32 tile: the logsumexp row is stored once per
# sublane so the pallas output block is a legal Mosaic (8,128) tile
_LSE_ROWS = 8


def attention_reference(q, k, v, causal=False, scale=None,
                        q_offset=0, kv_offset=0):
    """Plain softmax attention; q (..., Sq, D), k/v (..., Sk, D).

    ``q_offset``/``kv_offset`` are the global positions of element 0 (used
    for causal masking of sequence chunks).
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("...qd,...kd->...qk", q, k) * scale
    if causal:
        qpos = jnp.arange(q.shape[-2])[:, None] + q_offset
        kpos = jnp.arange(k.shape[-2])[None, :] + kv_offset
        s = jnp.where(qpos >= kpos, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("...qk,...kd->...qd", p, v.astype(p.dtype)) \
        .astype(q.dtype)


def _block_step(q, k, v, scale, causal, q_offset, kv_offset, m, l, o):
    """One online-softmax accumulation step (see module docstring)."""
    s = jnp.einsum("...qd,...kd->...qk", q, k).astype(jnp.float32) * scale
    if causal:
        qpos = jnp.arange(q.shape[-2])[:, None] + q_offset
        kpos = jnp.arange(k.shape[-2])[None, :] + kv_offset
        s = jnp.where(qpos >= kpos, s, _NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[..., None])
    c = jnp.exp(m - m_new)
    l_new = l * c + jnp.sum(p, axis=-1)
    o_new = o * c[..., None] + jnp.einsum(
        "...qk,...kd->...qd", p, v.astype(jnp.float32))
    return m_new, l_new, o_new


def blockwise_combine(q, kv_blocks, causal=False, scale=None, q_offset=0,
                      kv_offsets=None):
    """Attention over a list of (k, v) blocks with online-softmax combine.
    The building block ring_attention distributes over devices."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    batch_shape = q.shape[:-1]
    m = jnp.full(batch_shape, _NEG_INF, jnp.float32)
    l = jnp.zeros(batch_shape, jnp.float32)
    o = jnp.zeros(batch_shape + (kv_blocks[0][1].shape[-1],), jnp.float32)
    if kv_offsets is None:
        kv_offsets = []
        off = 0
        for k, _ in kv_blocks:
            kv_offsets.append(off)
            off += k.shape[-2]
    for (k, v), koff in zip(kv_blocks, kv_offsets):
        m, l, o = _block_step(q, k, v, scale, causal, q_offset, koff,
                              m, l, o)
    return (o / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)


# ----------------------------------------------------------------------
# Pallas flash attention (TPU)
# ----------------------------------------------------------------------
def _causal_k_blocks(q_block, block_q, block_k, n_k_blocks):
    """``(unmasked, visited)`` for query block ``q_block`` of a causal
    call with both offsets 0: key blocks ``[0, unmasked)`` lie wholly at
    or below the diagonal (every key visible to every row of the block),
    ``[unmasked, visited)`` are crossed by it and need the mask, and
    ``[visited, n_k_blocks)`` hold no visible key and are never read.
    Python ints in, ints out; the kernel hands it ``pl.program_id``."""
    first_row = q_block * block_q
    clamp = min if isinstance(q_block, int) else jnp.minimum
    unmasked = clamp((first_row + 1) // block_k, n_k_blocks)
    visited = clamp((first_row + block_q + block_k - 1) // block_k,
                    n_k_blocks)
    return unmasked, visited


def _scale_folds_into(scale, dtype):
    """Whether ``q * scale`` in ``dtype`` loses nothing the scores would
    keep: float32 rounds it where the product rounds anyway; a narrower
    dtype only when ``scale`` is a power of two (1/8 at 64-wide heads)."""
    return dtype == jnp.float32 or math.frexp(scale)[0] == 0.5


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_k, causal,
                  scale, seq_k):
    """Grid: (batch*heads, q_blocks).  One q block against the key
    blocks it can see: all of them, or under ``causal`` those up to the
    diagonal (``_causal_k_blocks``), of which only the ones the diagonal
    crosses are masked.

    Both products take q, k, v as they come (bfloat16 operands are not
    widened) and sum in float32; p is rounded to v's dtype for p·v; the
    online-softmax state and lse are float32.

    The scores are held keys-by-queries, s = k·qᵀ (block_k, block_q):
    the per-query statistics m, l are then (1, block_q) rows, a vector
    register per 128 queries where a (block_q, 1) column takes one per
    8, and o accumulates as (d_v, block_q) with no lane left empty at
    d_v = 64.  q and k share one width, v and o another (latent
    attention: 192 against 128).  Outputs the normalized o block and the logsumexp stats
    (saved for the blockwise backward)."""
    import jax.experimental.pallas as pl

    block_q = q_ref.shape[0]
    d_v = v_ref.shape[1]        # its own width: q·kᵀ contracts over q's
    n_k_blocks = seq_k // block_k
    q_offset = pl.program_id(1) * block_q
    q = q_ref[...]
    fold = _scale_folds_into(scale, q.dtype)
    if fold:
        q = q * scale

    def step(masked, i, carry):
        m, l, o = carry             # (1, block_q) twice, (d_v, block_q)
        start = pl.multiple_of(i * block_k, block_k)
        k = k_ref[pl.ds(start, block_k), :]
        v = v_ref[pl.ds(start, block_k), :]
        s = lax.dot_general(k, q, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        if not fold:
            s = s * scale
        if masked:
            kpos = lax.broadcasted_iota(jnp.int32, s.shape, 0)
            qpos = lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(qpos - kpos >= start - q_offset, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
        p = jnp.exp(s - m_new)
        c = jnp.exp(m - m_new)
        l_new = l * c + jnp.sum(p, axis=0, keepdims=True)
        o_new = o * c + lax.dot_general(
            v, p.astype(v.dtype), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, o_new

    carry = (jnp.full((1, block_q), _NEG_INF, jnp.float32),
             jnp.zeros((1, block_q), jnp.float32),
             jnp.zeros((d_v, block_q), jnp.float32))
    if causal:
        unmasked, visited = _causal_k_blocks(pl.program_id(1), block_q,
                                             block_k, n_k_blocks)
        carry = lax.fori_loop(0, unmasked, functools.partial(step, False),
                              carry)
        carry = lax.fori_loop(unmasked, visited,
                              functools.partial(step, True), carry)
    else:
        carry = lax.fori_loop(0, n_k_blocks, functools.partial(step, False),
                              carry)
    m, l, o = carry
    l_safe = jnp.maximum(l, 1e-30)
    o_ref[...] = (o / l_safe).T.astype(o_ref.dtype)
    # a 1-D (block_q,) stats row cannot be a TPU output block (Mosaic
    # tiles the last two dims): the row is written to every sublane of
    # one float32 tile, and row 0 is read back outside the kernel
    lse_ref[...] = jnp.broadcast_to(m + jnp.log(l_safe),
                                    (_LSE_ROWS, block_q))


# forward block extents, largest first; the last is also the backward's
# key block where the caller fixes none
_FLASH_BLOCKS = (512, 256, 128)


def _flash_blocks(sq, sk, block_q=None, block_k=None):
    """``(block_q, block_k)`` of the forward kernel: what the caller
    fixed, else the largest of 512/256/128 that divides the sequence.
    On a v5e small blocks pay for their bookkeeping, not their products:
    at (8·16, 1024, 64) bfloat16 causal a call takes 1.87 ms at 128/128,
    0.86 at 256/256, 0.52 at 512/512, 0.57 at 1024/1024 (PERF.md, PR
    26), though 512/512 computes 3 of 4 blocks where 128/128 computes
    36 of 64.  None where a block does not divide its sequence: the
    kernel has no partial blocks."""
    blocks = tuple(
        block or next((b for b in _FLASH_BLOCKS if seq % b == 0), None)
        for seq, block in ((sq, block_q), (sk, block_k)))
    if None in blocks or sq % blocks[0] or sk % blocks[1]:
        return None
    return blocks


def _flash_block_layout(bh, sq, sk, d, block_q, d_v=None):
    """(block, array) pairs of the forward pallas_call, in q/k/v then
    o/lse order — the ONE place the kernel's block shapes live, shared
    by the call below and the registered MXL-K kernel spec
    (``flash_kernel_spec``) so the static tile validator always checks
    what actually runs.  ``d`` is the width of q and k, ``d_v`` that of
    v and o where it differs."""
    d_v = d if d_v is None else d_v
    in_blocks = [
        ((None, block_q, d), (bh, sq, d)),              # q
        ((None, sk, d), (bh, sk, d)),                   # k
        ((None, sk, d_v), (bh, sk, d_v)),               # v
    ]
    out_blocks = [
        ((None, block_q, d_v), (bh, sq, d_v)),          # o
        ((None, _LSE_ROWS, block_q), (bh, _LSE_ROWS, sq)),  # lse
    ]
    return in_blocks, out_blocks


@functools.partial(jax.jit, static_argnames=(
    "causal", "scale", "block_q", "block_k", "interpret"))
def _flash_forward_kernel_call(q, k, v, causal, scale, block_q, block_k,
                               interpret):
    """(o, lse) of the forward kernel.  Jitted so that a model's layers,
    which call it with the same shapes, trace and lower the kernel once
    and not once a layer (24 layers of GPT-2-medium: 4–5 s of set-up)."""
    import jax.experimental.pallas as pl

    B, H, Sq, D = q.shape
    sk, d_v = v.shape[-2:]
    q3 = q.reshape(B * H, Sq, D)
    k3 = k.reshape(B * H, sk, D)
    v3 = v.reshape(B * H, sk, d_v)

    (qb, kb, vb), (ob, lseb) = _flash_block_layout(B * H, Sq, sk, D,
                                                   block_q, d_v)
    kernel = functools.partial(_flash_kernel, block_k=block_k,
                               causal=causal, scale=scale, seq_k=sk)
    out, lse = pl.pallas_call(
        kernel,
        grid=(B * H, Sq // block_q),
        in_specs=[
            pl.BlockSpec(qb[0], lambda b, i: (b, i, 0)),
            pl.BlockSpec(kb[0], lambda b, i: (b, 0, 0)),
            pl.BlockSpec(vb[0], lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec(ob[0], lambda b, i: (b, i, 0)),
            pl.BlockSpec(lseb[0], lambda b, i: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(ob[1], q.dtype),
            jax.ShapeDtypeStruct(lseb[1], jnp.float32),
        ],
        name="flash_forward",
        interpret=interpret,
    )(q3, k3, v3)
    return out.reshape(B, H, Sq, d_v), lse[:, 0].reshape(B, H, Sq)


# the backward's query chunk: sequences longer than this are walked in
# chunks of it, so that under ``causal`` a key block meets only the
# queries at or below it
_BWD_Q_CHUNK = 1024


def _flash_backward_blockwise(q, k, v, o, lse, do, causal, scale, block_k):
    """Flash-attention backward: blockwise recompute from the saved
    logsumexp stats — per-iteration footprint is O(Sq · block_k), never
    the full (Sq, Sk) score matrix (the training-path memory guarantee
    the fused forward alone does not give).  q and k share one width, v,
    o and do another.

    Standard identities (p = exp(s·scale − lse)):
        dv_j = pᵀ @ do
        ds   = p ⊙ (do @ vᵀ − rowsum(do ⊙ o)) · scale
        dq  += ds @ k_j,   dk_j = dsᵀ @ q

    A sequence of more than ``_BWD_Q_CHUNK`` queries is walked in chunks
    of that many, and under ``causal`` a key block starts at the chunk
    its first key lies in: the chunks above the diagonal hold no visible
    key and are not computed (half the work at 8,192 queries).  A
    shorter sequence is one chunk, computed whole.
    """
    qf = q.astype(jnp.float32)
    dof = do.astype(jnp.float32)
    delta = jnp.sum(dof * o.astype(jnp.float32), axis=-1)   # (B, H, Sq)
    sq = q.shape[-2]
    sk = k.shape[-2]
    n_blocks = sk // block_k
    chunk = _BWD_Q_CHUNK if sq > _BWD_Q_CHUNK and sq % _BWD_Q_CHUNK == 0 \
        else sq
    n_chunks = sq // chunk

    def chunked(x, axis):
        """``x`` with its query axis split chunk-major: (n_chunks, ...,
        chunk, ...).  A chunk is then one index of the leading axis, which
        a loop reads and updates in place."""
        if n_chunks == 1:
            return x
        axis = axis % x.ndim
        x = x.reshape(x.shape[:axis] + (n_chunks, chunk) + x.shape[axis + 1:])
        return jnp.moveaxis(x, axis, 0)

    qf, dof = chunked(qf, -2), chunked(dof, -2)
    lse, delta = chunked(lse, -1), chunked(delta, -1)

    def at(x, j):
        return x if n_chunks == 1 else x[j]

    def body(i, carry):
        dq, dk, dv = carry
        kb = lax.dynamic_slice_in_dim(k, i * block_k, block_k,
                                      axis=-2).astype(jnp.float32)
        vb = lax.dynamic_slice_in_dim(v, i * block_k, block_k,
                                      axis=-2).astype(jnp.float32)

        def against(j, inner):
            dq, dkb, dvb = inner
            qc, doc = at(qf, j), at(dof, j)
            s = jnp.einsum("...qd,...kd->...qk", qc, kb) * scale
            if causal:
                qpos = j * chunk + jnp.arange(chunk)[:, None]
                kpos = i * block_k + jnp.arange(block_k)[None, :]
                s = jnp.where(qpos >= kpos, s, _NEG_INF)
            p = jnp.exp(s - at(lse, j)[..., None])
            dvb = dvb + jnp.einsum("...qk,...qd->...kd", p, doc)
            dp = jnp.einsum("...qd,...kd->...qk", doc, vb)
            ds = p * (dp - at(delta, j)[..., None]) * scale
            dqc = jnp.einsum("...qk,...kd->...qd", ds, kb)
            dq = dq + dqc if n_chunks == 1 else dq.at[j].add(dqc)
            dkb = dkb + jnp.einsum("...qk,...qd->...kd", ds, qc)
            return dq, dkb, dvb

        inner = (dq, jnp.zeros_like(kb), jnp.zeros_like(vb))
        if n_chunks == 1:
            dq, dkb, dvb = against(0, inner)
        else:
            first = (i * block_k) // chunk if causal else 0
            dq, dkb, dvb = lax.fori_loop(first, n_chunks, against, inner)
        dk = lax.dynamic_update_slice_in_dim(dk, dkb, i * block_k, axis=-2)
        dv = lax.dynamic_update_slice_in_dim(dv, dvb, i * block_k, axis=-2)
        return dq, dk, dv

    dq0 = jnp.zeros(qf.shape, jnp.float32)
    dk0 = jnp.zeros(k.shape, jnp.float32)
    dv0 = jnp.zeros(v.shape, jnp.float32)
    dq, dk, dv = lax.fori_loop(0, n_blocks, body, (dq0, dk0, dv0))
    if n_chunks > 1:
        dq = jnp.moveaxis(dq, 0, -3).reshape(q.shape)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def flash_attention(q, k, v, causal=False, scale=None, block_q=None,
                    block_k=None, interpret=None):
    """Fused attention; q/k (B, H, S, D), v (B, H, S, D_v) with D_v = D
    or not (the output is as wide as v).  The Pallas kernel where the
    computation is placed on a TPU, the jnp reference elsewhere
    (``kernels.common.dispatch``: decided when the enclosing step is
    lowered, so a compile-only lowering against a TPU topology carries
    the Mosaic call and a cpu-placed step on a chip host does not).  An
    explicit ``interpret`` runs the kernel either way: ``True`` through
    the Pallas interpreter (tests), ``False`` through Mosaic.

    The kernel multiplies in the operands' own dtype and sums in
    float32: q·kᵀ of bfloat16 operands loses nothing (a product of two
    bfloat16 numbers is exact in float32), p is rounded to v's dtype
    before p·v, and m, l, o and the saved lse are float32.  With
    ``causal`` (both offsets 0) each query block reads key blocks
    0 .. ⌈(i + 1)·block_q / block_k⌉ − 1 and masks only those the
    diagonal crosses; the blocks above it are never read.

    Differentiable: the forward runs the fused kernel and saves the
    logsumexp stats; the backward is the blockwise flash backward
    (recompute per kv block from the stats — O(Sq·block_k) live memory,
    never the (Sq, Sk) score matrix), attached via custom_vjp.

    ``block_q``/``block_k`` default to the largest of 512/256/128 that
    divides the sequence (``_flash_blocks``; the backward's key block to
    128).  Sequence lengths must be multiples of the block sizes for the
    kernel path (pad upstream); otherwise falls back to the reference
    implementation.
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])

    def reference(q, k, v):
        return attention_reference(q, k, v, causal=causal, scale=scale)

    blocks = _flash_blocks(q.shape[-2], k.shape[-2], block_q, block_k)
    if blocks is None:                 # hard kernel constraint
        return reference(q, k, v)
    bwd_block_k = block_k or _FLASH_BLOCKS[-1]   # the backward's, as it was
    block_q, block_k = blocks

    def kernel(q, k, v, interpret=False):
        @jax.custom_vjp
        def _fa(q, k, v):
            out, _ = _flash_forward_kernel_call(
                q, k, v, causal, scale, block_q, block_k, interpret)
            return out

        def _fa_fwd(q, k, v):
            out, lse = _flash_forward_kernel_call(
                q, k, v, causal, scale, block_q, block_k, interpret)
            return out, (q, k, v, out, lse)

        def _fa_bwd(res, ct):
            q, k, v, out, lse = res
            return _flash_backward_blockwise(q, k, v, out, lse, ct, causal,
                                             scale, bwd_block_k)

        _fa.defvjp(_fa_fwd, _fa_bwd)
        return _fa(q, k, v)

    if interpret is not None:
        return kernel(q, k, v, interpret=bool(interpret))
    from ..kernels.common import dispatch
    return dispatch(kernel, reference, q, k, v)


# ----------------------------------------------------------------------
# Ring attention over a mesh axis
# ----------------------------------------------------------------------
def ring_attention(q, k, v, axis_name="sp", causal=False, scale=None):
    """Sequence-parallel attention inside shard_map.

    Every device holds the (B, H, S/n, D) chunk of q, k, v for its slice
    of the sequence (chunks in ring order = sequence order).  k/v rotate
    one hop per step via ppermute; each device accumulates online-softmax
    state for its q chunk.  After n steps every q chunk has attended to
    the full sequence.  Communication: each step moves 2·B·H·(S/n)·D
    elements over ICI, overlapped with the attention compute of the
    previous block (XLA schedules the ppermute DMA concurrently).
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    chunk = q.shape[-2]

    # derive the init state arithmetically from q so the scan carry
    # inherits q's varying-manual-axes type (dp, sp, ...) under shard_map
    zero = q[..., 0].astype(jnp.float32) * 0.0
    m0 = zero + _NEG_INF
    l0 = zero
    o0 = jnp.broadcast_to(zero[..., None], zero.shape + (v.shape[-1],))
    q_offset = my * chunk

    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(s, carry):
        k, v, m, l, o = carry
        # kv currently originates from shard (my - s) mod n
        src = (my - s) % n
        kv_offset = src * chunk
        if causal:
            m, l, o = _block_step(q, k, v, scale, True, q_offset,
                                  kv_offset, m, l, o)
        else:
            m, l, o = _block_step(q, k, v, scale, False, 0, 0, m, l, o)
        k = lax.ppermute(k, axis_name, perm)
        v = lax.ppermute(v, axis_name, perm)
        return k, v, m, l, o

    k, v, m, l, o = lax.fori_loop(0, n, step, (k, v, m0, l0, o0))
    return (o / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)


# ----------------------------------------------------------------------
# Mesh context: tells symbolic MultiHeadAttention what its step is sharded over
# ----------------------------------------------------------------------
import contextlib as _contextlib
import threading as _threading

_SP_STATE = _threading.local()


class _SPContext(object):
    __slots__ = ("mesh", "seq_axis", "batch_axis")

    def __init__(self, mesh, seq_axis, batch_axis):
        self.mesh = mesh
        self.seq_axis = seq_axis
        self.batch_axis = batch_axis

    def fingerprint(self):
        """What a program traced under this context bakes in."""
        return (tuple(self.mesh.shape.items()),
                tuple(d.id for d in self.mesh.devices.flat),
                self.seq_axis, self.batch_axis)


@_contextlib.contextmanager
def sequence_parallel(mesh, seq_axis="sp", batch_axis="dp"):
    """While active, MultiHeadAttention knows the mesh its step is
    sharded over (must be active when the step is TRACED —
    ShardedTrainer and the Module mesh group do this through
    :func:`attention_scope`).
    With ``seq_axis`` an axis of ``mesh`` it lowers to ring_attention
    over that axis; otherwise (``seq_axis=None``: data/tensor parallel
    only) each device runs the flash path on its own block of the batch
    under shard_map — GSPMD cannot partition a Mosaic kernel."""
    prev = getattr(_SP_STATE, "ctx", None)
    _SP_STATE.ctx = _SPContext(
        mesh, seq_axis,
        batch_axis if batch_axis in mesh.axis_names else None)
    try:
        yield
    finally:
        _SP_STATE.ctx = prev


def current_sequence_parallel():
    return getattr(_SP_STATE, "ctx", None)


def attention_scope(mesh, seq_axis=None):
    """The context to trace and run a step over ``mesh`` under (None or
    one device: nothing to tell): :func:`sequence_parallel` with the
    ring over 'sp' when the step shards a sequence axis and the mesh has
    one, per-device flash attention otherwise."""
    if mesh is None or mesh.size == 1:
        return _contextlib.nullcontext()
    ring = seq_axis is not None and "sp" in mesh.axis_names
    return sequence_parallel(mesh, seq_axis="sp" if ring else None)


def sharded_self_attention(q, k, v, causal=False):
    """Attention dispatch for (B, H, S, D): flash/reference on one
    device; under a :func:`sequence_parallel` mesh context, ring
    attention over the sequence axis, or per-device flash."""
    ctx = current_sequence_parallel()
    if ctx is None:
        return flash_attention(q, k, v, causal=causal)
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    mesh = ctx.mesh

    if ctx.seq_axis in mesh.axis_names:
        spec = P(ctx.batch_axis, None, ctx.seq_axis, None)
        check_vma = True

        def att(q, k, v):
            return ring_attention(q, k, v, axis_name=ctx.seq_axis,
                                  causal=causal)
    else:
        # "Mosaic kernels cannot be automatically partitioned. Please
        # wrap the call in a shard_map" (the four-chip host, PR 21): the
        # batch splits over dp, the heads over tp where those divide,
        # and every device attends over its own block
        def split(axis, dim):
            size = mesh.shape.get(axis, 1) if axis else 1
            return axis if size > 1 and dim % size == 0 else None

        spec = P(split(ctx.batch_axis, q.shape[0]),
                 split("tp", q.shape[1]), None, None)
        check_vma = False       # pallas_call outputs declare no vma

        def att(q, k, v):
            return flash_attention(q, k, v, causal=causal)

    return shard_map(att, mesh=mesh, in_specs=(spec,) * 3, out_specs=spec,
                     check_vma=check_vma)(q, k, v)


def flash_kernel_spec(batch_heads=8, seq_q=1024, seq_k=1024, head_dim=64,
                      block_q=None, dtype="bfloat16", head_dim_v=None):
    """MXL-K kernel spec for the flash forward pallas_call.

    Built from the same :func:`_flash_block_layout` the kernel itself
    uses, at a representative training shape, so the static tile
    validator (analysis/tiling.py) checks the blocks that actually run.
    The lse output deliberately carries ``_LSE_ROWS`` sublanes: a 1-D
    ``(block_q,)`` stats row is exactly the historical bug Mosaic
    rejected (no second dimension to tile).
    """
    block_q, _block_k = _flash_blocks(seq_q, seq_k, block_q)
    in_blocks, out_blocks = _flash_block_layout(batch_heads, seq_q, seq_k,
                                                head_dim, block_q,
                                                head_dim_v)
    blocks = []
    for name, (blk, arr) in zip(("q", "k", "v"), in_blocks):
        blocks.append({"role": "in", "name": name, "block": blk,
                       "array": arr, "dtype": dtype})
    for name, (blk, arr) in zip(("o", "lse"), out_blocks):
        blocks.append({"role": "out", "name": name, "block": blk,
                       "array": arr,
                       "dtype": "float32" if name == "lse" else dtype})
    return {"name": "flash_forward",
            "origin": "mxnet_tpu/parallel/ring_attention.py",
            "grid": (batch_heads, seq_q // block_q),
            "blocks": blocks}


try:
    from ..analysis.tiling import register_kernel_spec as _register_spec
    _register_spec("parallel.ring_attention.flash_forward",
                   flash_kernel_spec)
except Exception:            # analysis package optional at import time
    pass
