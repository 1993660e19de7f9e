"""Long-context attention: flash kernel + ring sequence parallelism.

This subsystem has no reference counterpart (SURVEY §5 "Long-context /
sequence parallelism": the reference only offers bucketing and pipeline
LSTM) — it is the TPU-native capability that replaces those workarounds
for long sequences:

- ``flash_attention``: fused online-softmax attention as a pair of
  Pallas TPU kernels, forward and backward (MXU matmuls, no (seq, seq)
  materialization in HBM in either) where the computation is placed on
  a TPU; the jnp reference implementation anywhere else, so tests/CPU
  paths stay exact.  Both kernels multiply q, k, v (and do) in the
  dtype they arrive in (bfloat16 operands are not widened; float32
  operands get the product they always got, at Mosaic's default
  precision), sum every product in float32, round p (and ds) to the
  operands' dtype only for the products that consume them, and keep the
  softmax state, the saved logsumexp and the gradients' accumulators in
  float32.  Under ``causal`` a query block meets the key blocks up to
  the diagonal and no further, in both directions: masked blocks are
  skipped, not computed.  Under a sliding ``window`` W as well (query i
  sees keys i − W + 1 … i) a query block meets only the key blocks of
  its band, and the calls are named ``flash_window_forward`` /
  ``flash_window_backward``.
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
from jax.ad_checkpoint import checkpoint_name

__all__ = ["attention_reference", "flash_attention", "ring_attention",
           "blockwise_combine", "sequence_parallel",
           "current_sequence_parallel", "attention_scope",
           "mesh_axis_that_splits",
           "FLASH_RESIDUALS"]

# What the flash forward hands its backward — q, k, v, the output and the
# logsumexp — each under a ``checkpoint_name``: a ``jax.checkpoint`` whose
# policy saves these names (the executor's mirrored segments) does not run
# the kernel again to recompute the last two.  The operands are named with
# them because the five belong together: q, k, v recomputed in bfloat16
# differ in the last bit from the first call's (XLA fuses a recomputation
# differently), and statistics kept from the first call then fit them no
# longer (PERF.md section 6, PR 32).
FLASH_RESIDUALS = ("flash_q", "flash_k", "flash_v", "flash_out", "flash_lse")

_NEG_INF = -1e30
# sublanes of a float32 tile: the logsumexp row is stored once per
# sublane so the pallas output block is a legal Mosaic (8,128) tile
_LSE_ROWS = 8


def _visible(qpos, kpos, window):
    """Which keys a query sees under the causal mask, and with a sliding
    ``window`` W only the W up to and including itself."""
    if window is None:
        return qpos >= kpos
    return (qpos >= kpos) & (qpos - kpos < window)


def attention_reference(q, k, v, causal=False, scale=None,
                        q_offset=0, kv_offset=0, window=None):
    """Plain softmax attention; q (..., Sq, D), k/v (..., Sk, D).  Where
    q is (B, H, Sq, D) and k/v have fewer heads (grouped queries: H a
    multiple of theirs), query head h attends key/value head
    h // (H / H_kv): k and v are repeated, here and nowhere else.

    ``q_offset``/``kv_offset`` are the global positions of element 0 (used
    for causal masking of sequence chunks); ``window`` (with ``causal``)
    lets query i see keys i − window + 1 … i only.
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    group = _head_group(q, k)
    if group > 1:
        k, v = (jnp.repeat(t, group, axis=1) for t in (k, v))
    s = jnp.einsum("...qd,...kd->...qk", q, k) * scale
    if causal:
        qpos = jnp.arange(q.shape[-2])[:, None] + q_offset
        kpos = jnp.arange(k.shape[-2])[None, :] + kv_offset
        s = jnp.where(_visible(qpos, kpos, window), s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("...qk,...kd->...qd", p, v.astype(p.dtype)) \
        .astype(q.dtype)


def _head_group(q, k):
    """Query heads a key/value head of (B, H, S, D) operands: 1, or H /
    H_kv where k has fewer heads than q."""
    if q.ndim != 4 or q.shape[1] == k.shape[1]:
        return 1
    if q.shape[1] % k.shape[1]:
        raise ValueError("%d query heads do not group over %d key/value "
                         "heads" % (q.shape[1], k.shape[1]))
    return q.shape[1] // k.shape[1]


def _block_step(q, k, v, scale, causal, q_offset, kv_offset, m, l, o,
                window=None):
    """One online-softmax accumulation step (see module docstring)."""
    s = jnp.einsum("...qd,...kd->...qk", q, k).astype(jnp.float32) * scale
    if causal:
        qpos = jnp.arange(q.shape[-2])[:, None] + q_offset
        kpos = jnp.arange(k.shape[-2])[None, :] + kv_offset
        s = jnp.where(_visible(qpos, kpos, window), s, _NEG_INF)
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


def _window_k_blocks(q_block, block_q, block_k, n_k_blocks, window):
    """:func:`_causal_k_blocks` under a sliding ``window`` W, where row r
    sees keys r − W + 1 … r: ``(first, lo, hi, visited)`` — key blocks
    ``[first, lo)`` are crossed by the band's lower edge (masked),
    ``[lo, hi)`` lie wholly inside the band of every row, ``[hi,
    visited)`` are crossed by the diagonal (masked; with a band narrower
    than a block, by both edges), and no other block holds a visible key.
    Python ints in, ints out; the kernel hands it ``pl.program_id``."""
    first_row = q_block * block_q
    last_row = first_row + block_q - 1
    traced = not isinstance(q_block, int)
    clamp_hi = jnp.minimum if traced else min
    clamp_lo = jnp.maximum if traced else max
    unmasked, visited = _causal_k_blocks(q_block, block_q, block_k,
                                         n_k_blocks)
    first = clamp_lo(first_row - window + 1, 0) // block_k
    inside = clamp_lo(last_row - window + block_k, 0) // block_k
    lo = clamp_hi(clamp_lo(inside, first), visited)
    hi = clamp_lo(clamp_hi(unmasked, visited), lo)
    return first, lo, hi, visited


def _scale_folds_into(scale, dtype):
    """Whether ``q * scale`` in ``dtype`` loses nothing the scores would
    keep: float32 rounds it where the product rounds anyway; a narrower
    dtype only when ``scale`` is a power of two (1/8 at 64-wide heads)."""
    return dtype == jnp.float32 or math.frexp(scale)[0] == 0.5


def _mask_past_diagonal(s, key_lead):
    """A keys-by-queries score tile with every key its query must not see
    set to ``_NEG_INF``; ``key_lead`` is the position of the tile's first
    key less that of its first query."""
    kpos = lax.broadcasted_iota(jnp.int32, s.shape, 0)
    qpos = lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(qpos - kpos >= key_lead, s, _NEG_INF)


def _mask(s, key_lead, window):
    """:func:`_mask_past_diagonal`, and under a sliding ``window`` also
    every key ``window`` or more positions before its query."""
    if window is None:
        return _mask_past_diagonal(s, key_lead)
    kpos = lax.broadcasted_iota(jnp.int32, s.shape, 0)
    qpos = lax.broadcasted_iota(jnp.int32, s.shape, 1)
    lead = qpos - kpos - key_lead       # query's position less the key's
    return jnp.where((lead >= 0) & (lead < window), s, _NEG_INF)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_k, causal,
                  scale, seq_k, window=None):
    """Grid: (batch*heads, q_blocks).  One q block against the key
    blocks it can see: all of them, or under ``causal`` those up to the
    diagonal (``_causal_k_blocks``), of which only the ones the diagonal
    crosses are masked; under a ``window`` those of its band
    (``_window_k_blocks``), of which only the ones either edge crosses
    are masked.  A row that sees no key of a masked block takes
    exp(0) there while its maximum is still the initial −1e30; the
    first block holding a key it sees multiplies that by
    exp(−1e30 − m) = 0, and every row sees itself.

    Both products take q, k, v as they come (bfloat16 operands are not
    widened) and sum in float32; p is rounded to v's dtype for p·v; the
    online-softmax state and lse are float32.

    The scores are held keys-by-queries, s = k·qᵀ (block_k, block_q):
    the per-query statistics m, l are then (1, block_q) rows, a vector
    register per 128 queries where a (block_q, 1) column takes one per
    8, and o accumulates as (d_v, block_q) with no lane left empty at
    d_v = 64.  q and k share one width, v and o another (latent
    attention: 192 against 128).  Outputs the normalized o block and the
    logsumexp stats (saved for the backward kernel)."""
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
            s = _mask(s, start - q_offset, window)
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
    if window is not None:
        first, lo, hi, visited = _window_k_blocks(
            pl.program_id(1), block_q, block_k, n_k_blocks, window)
        for start, stop, masked in ((first, lo, True), (lo, hi, False),
                                    (hi, visited, True)):
            carry = lax.fori_loop(start, stop,
                                  functools.partial(step, masked), carry)
    elif causal:
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


# block extents of both kernels, largest first
_FLASH_BLOCKS = (512, 256, 128)
# VMEM a Mosaic kernel gets without asking, and what the backward kernel
# may ask for of a v5e core's 128 MiB before it splits a group (below)
_SCOPED_VMEM_DEFAULT = 16 << 20
_VMEM_BUDGET = 100 << 20


def _vmem_bytes(shape, itemsize):
    """Bytes a VMEM buffer of ``shape`` takes: the last dim padded to
    128 lanes, the one before it to a 32-bit tile's 8 sublanes."""
    *lead, rows, lanes = shape
    rows = -(-rows * itemsize // 32) * 32 // itemsize
    return math.prod(lead) * rows * (-(-lanes // 128) * 128) * itemsize


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


def _flash_block_layout(bh, sq, sk, d, block_q, d_v=None, group=1):
    """(block, array) pairs of the forward pallas_call, in q/k/v then
    o/lse order — the ONE place the kernel's block shapes live, shared
    by the call below and the registered MXL-K kernel spec
    (``flash_kernel_spec``) so the static tile validator always checks
    what actually runs.  ``d`` is the width of q and k, ``d_v`` that of
    v and o where it differs; ``group`` the query heads a key/value head
    (k and v then hold ``bh / group`` heads)."""
    d_v = d if d_v is None else d_v
    in_blocks = [
        ((None, block_q, d), (bh, sq, d)),              # q
        ((None, sk, d), (bh // group, sk, d)),          # k
        ((None, sk, d_v), (bh // group, sk, d_v)),      # v
    ]
    out_blocks = [
        ((None, block_q, d_v), (bh, sq, d_v)),          # o
        ((None, _LSE_ROWS, block_q), (bh, _LSE_ROWS, sq)),  # lse
    ]
    return in_blocks, out_blocks


def _kernel_name(direction, window):
    """``flash_forward`` / ``flash_backward``, and ``flash_window_*`` for
    a call under a sliding window: the trace tells the two apart."""
    return "flash_%s%s" % ("window_" if window is not None else "",
                           direction)


@functools.partial(jax.jit, static_argnames=(
    "causal", "scale", "block_q", "block_k", "interpret", "window"))
def _flash_forward_kernel_call(q, k, v, causal, scale, block_q, block_k,
                               interpret, window=None):
    """(o, lse) of the forward kernel.  Jitted so that a model's layers,
    which call it with the same shapes, trace and lower the kernel once
    and not once a layer (24 layers of GPT-2-medium: 4–5 s of set-up).

    Grouped queries (k and v of fewer heads than q): the grid stays one
    program a query head and query block, and the index map sends query
    head b to key/value head b // group, so k and v are never repeated;
    a group's heads follow one another in the grid and find their k and
    v block already in VMEM."""
    import jax.experimental.pallas as pl

    B, H, Sq, D = q.shape
    sk, d_v = v.shape[-2:]
    group = _head_group(q, k)
    q3 = q.reshape(B * H, Sq, D)
    k3 = k.reshape(B * H // group, sk, D)
    v3 = v.reshape(B * H // group, sk, d_v)

    (qb, kb, vb), (ob, lseb) = _flash_block_layout(B * H, Sq, sk, D,
                                                   block_q, d_v, group)
    kernel = functools.partial(_flash_kernel, block_k=block_k,
                               causal=causal, scale=scale, seq_k=sk,
                               window=window)
    if group == 1:
        kv_head = lambda b, i: (b, 0, 0)                # noqa: E731
    else:
        kv_head = lambda b, i: (b // group, 0, 0)       # noqa: E731
    # k and v are whole per key/value head, twice (the pipeline's two
    # buffers): 16 MiB at 8,192 keys of 256 in bfloat16, with q, o and lse
    # beside them 17 — past what Mosaic gives a kernel unasked.  Only then
    # is a limit asked for, so every smaller shape lowers as it always did.
    blocks = sum(2 * _vmem_bytes(blk[1:], size) for (blk, _arr), size in zip(
        (qb, kb, vb, ob, lseb), (q.dtype.itemsize,) * 4 + (4,)))
    params = {}
    if blocks > _SCOPED_VMEM_DEFAULT - (1 << 20):
        from jax.experimental.pallas import tpu as pltpu
        params["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=int(blocks + 8 * block_q * block_k * 4
                                 + (4 << 20)))
    out, lse = pl.pallas_call(
        kernel,
        grid=(B * H, Sq // block_q),
        in_specs=[
            pl.BlockSpec(qb[0], lambda b, i: (b, i, 0)),
            pl.BlockSpec(kb[0], kv_head),
            pl.BlockSpec(vb[0], kv_head),
        ],
        out_specs=[
            pl.BlockSpec(ob[0], lambda b, i: (b, i, 0)),
            pl.BlockSpec(lseb[0], lambda b, i: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(ob[1], q.dtype),
            jax.ShapeDtypeStruct(lseb[1], jnp.float32),
        ],
        name=_kernel_name("forward", window),
        interpret=interpret,
        **params,
    )(q3, k3, v3)
    return out.reshape(B, H, Sq, d_v), lse[:, 0].reshape(B, H, Sq)


def _causal_q_blocks(k_block, block_q, block_k, n_q_blocks):
    """The mirror of :func:`_causal_k_blocks` for key block ``k_block``:
    ``(visited, unmasked)`` — query blocks ``[0, visited)`` end before
    its first key and are never read, ``[visited, unmasked)`` are
    crossed by the diagonal and need the mask, ``[unmasked,
    n_q_blocks)`` see every key of the block."""
    first_key = k_block * block_k
    clamp = min if isinstance(k_block, int) else jnp.minimum
    visited = clamp(first_key // block_q, n_q_blocks)
    unmasked = clamp((first_key + block_k + block_q - 2) // block_q,
                     n_q_blocks)
    return visited, unmasked


def _window_q_blocks(k_block, block_q, block_k, n_q_blocks, window):
    """The mirror of :func:`_window_k_blocks` for key block ``k_block``,
    whose key c is seen by rows c … c + W − 1: ``(visited, lo, hi,
    end)`` — query blocks ``[visited, lo)`` are crossed by the diagonal,
    ``[lo, hi)`` see every key of the block, ``[hi, end)`` are crossed
    by the band's lower edge (both masked), and the blocks after the one
    that holds the block's last key + W − 1 never see it."""
    first_key = k_block * block_k
    traced = not isinstance(k_block, int)
    clamp_hi = jnp.minimum if traced else min
    clamp_lo = jnp.maximum if traced else max
    visited, unmasked = _causal_q_blocks(k_block, block_q, block_k,
                                         n_q_blocks)
    end = clamp_hi((first_key + block_k + window - 2) // block_q + 1,
                   n_q_blocks)
    lo = clamp_hi(unmasked, end)
    hi = clamp_lo(clamp_hi((first_key + window) // block_q, end), lo)
    return visited, lo, hi, end


def _flash_backward_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                           dq_ref, dk_ref, dv_ref, dq_acc, *, block_q,
                           causal, scale, group, window=None):
    """Grid: (batch*key/value heads, k_blocks), the key blocks in order.
    One key block against the query blocks that can see it: all of them,
    or under ``causal`` those from the diagonal down
    (``_causal_q_blocks``), of which only the ones the diagonal crosses
    are masked; under a ``window`` those of its band
    (``_window_q_blocks``), masked where either edge crosses them.  With
    p = exp(s·scale − lse) recomputed from the saved
    logsumexp and delta = rowsum(do ⊙ o):

        dv_j = pᵀ·do      ds = p ⊙ (do·vᵀ − delta)
        dk_j = scale · dsᵀ·q      dq += scale · ds·k_j

    The tiles are held keys-by-queries like the forward's, (block_k,
    block_q), so lse and delta are (1, block_q) rows and dv, dk come out
    of plain products; dq is accumulated transposed, (d, block_q) a
    query block, in a float32 VMEM scratch that lives across the key
    blocks of one (batch·head) and is written out, cast once, after the
    last.  q, k, v, do are multiplied as they come; p and ds are rounded
    to their dtype for the products that consume them; every sum, the
    softmax arithmetic and the three accumulators are float32.

    Grouped queries: the ``group`` query heads of this key/value head lie
    one after another along the rows of q, do, dq (and of lse, delta and
    the scratch, by query blocks), each a sequence of its own; the key
    block meets every head's query blocks in turn and dk, dv sum over
    them in the same float32 accumulators — k and v are read once a
    group, dk and dv written once."""
    import jax.experimental.pallas as pl

    block_k = k_ref.shape[0]
    n_q_blocks = q_ref.shape[0] // (block_q * group)    # of one head
    j = pl.program_id(1)
    k_offset = j * block_k
    k = k_ref[...]
    v = v_ref[...]
    fold = _scale_folds_into(scale, k.dtype)
    k_scaled = k * scale if fold else k

    @pl.when(j == 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def step(masked, first, i, carry):
        # query block i of the head whose blocks start at row ``first``
        dk, dv = carry              # (block_k, d), (block_k, d_v)
        row = i + first if first else i
        start = pl.multiple_of(row * block_q, block_q)
        q = q_ref[pl.ds(start, block_q), :]
        do = do_ref[pl.ds(start, block_q), :]
        s = lax.dot_general(k_scaled, q, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        if not fold:
            s = s * scale
        if masked:
            s = _mask(s, k_offset - i * block_q, window)
        p = jnp.exp(s - lse_ref[row])
        dv = dv + jnp.dot(p.astype(do.dtype), do,
                          preferred_element_type=jnp.float32)
        dp = lax.dot_general(v, do, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        ds = (p * (dp - delta_ref[row])).astype(q.dtype)
        dk = dk + jnp.dot(ds, q, preferred_element_type=jnp.float32)
        dq_acc[row] += lax.dot_general(k, ds, (((0,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32)
        return dk, dv

    carry = (jnp.zeros(k_ref.shape, jnp.float32),
             jnp.zeros(v_ref.shape, jnp.float32))
    for head in range(group):
        first = head * n_q_blocks
        if window is not None:
            visited, lo, hi, end = _window_q_blocks(j, block_q, block_k,
                                                    n_q_blocks, window)
            for start, stop, masked in ((visited, lo, True),
                                        (lo, hi, False), (hi, end, True)):
                carry = lax.fori_loop(
                    start, stop, functools.partial(step, masked, first),
                    carry)
        elif causal:
            visited, unmasked = _causal_q_blocks(j, block_q, block_k,
                                                 n_q_blocks)
            carry = lax.fori_loop(
                visited, unmasked, functools.partial(step, True, first),
                carry)
            carry = lax.fori_loop(
                unmasked, n_q_blocks, functools.partial(step, False, first),
                carry)
        else:
            carry = lax.fori_loop(
                0, n_q_blocks, functools.partial(step, False, first), carry)
    dk, dv = carry
    dk_ref[...] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        def write(i, _):
            start = pl.multiple_of(i * block_q, block_q)
            dq_ref[pl.ds(start, block_q), :] = \
                (dq_acc[i] * scale).T.astype(dq_ref.dtype)
            return 0
        lax.fori_loop(0, group * n_q_blocks, write, 0)


def _flash_backward_block_layout(bh, sq, sk, d, block_q, block_k, d_v=None,
                                 group=1, split=1):
    """(block, array) pairs of the backward pallas_call, in q/k/v/do/
    lse/delta then dq/dk/dv order, and the shape of its float32 dq
    scratch: shared by the call below and
    ``flash_backward_kernel_spec``, like :func:`_flash_block_layout`.
    ``bh`` counts key/value heads; the ``group`` query heads of each lie
    along the rows, ``group / split`` of them a program (``rows`` =
    group·sq / split; :func:`_flash_backward_split`).  q, do and dq are
    whole per program, k, v, dk, dv go by key blocks (dk, dv one partial
    sum a program); lse and delta are one (1, block_q) row a query block,
    indexed by the block."""
    d_v = d if d_v is None else d_v
    rows, programs = group * sq // split, bh * split
    stats = ((None, rows // block_q, 1, block_q),
             (programs, rows // block_q, 1, block_q))
    in_blocks = [
        ((None, rows, d), (programs, rows, d)),         # q
        ((None, block_k, d), (bh, sk, d)),              # k
        ((None, block_k, d_v), (bh, sk, d_v)),          # v
        ((None, rows, d_v), (programs, rows, d_v)),     # do
        stats,                                          # lse
        stats,                                          # delta
    ]
    out_blocks = [
        ((None, rows, d), (programs, rows, d)),         # dq
        ((None, block_k, d), (programs, sk, d)),        # dk
        ((None, block_k, d_v), (programs, sk, d_v)),    # dv
    ]
    return in_blocks, out_blocks, (rows // block_q, d, block_q)


def _flash_backward_split(bh, sq, sk, d, block_q, block_k, d_v, group,
                          itemsize, budget=None):
    """``(split, vmem bytes, layout)``: into how many programs a key/value
    head's group of query heads is divided so that the kernel fits VMEM,
    what it then asks for, and :func:`_flash_backward_block_layout` at that
    split.  A program holds its query heads' q, do and dq whole
    (twice: the pipeline's two buffers) and a float32 dq scratch — 80 MiB
    at four heads of 8,192 × 128, 268 at eight of 8,192 × 256 — so past
    ``budget`` the group goes over 2, 4, … programs, each with the whole
    key/value head's blocks and a float32 partial dk, dv that the caller
    sums.  1 wherever the whole group fits: those shapes lower as they
    did before there was a split."""
    budget = _VMEM_BUDGET if budget is None else budget
    for split in range(1, group + 1):
        if group % split:
            continue
        layout = _flash_backward_block_layout(
            bh, sq, sk, d, block_q, block_k, d_v, group, split)
        ins, outs, acc = layout
        partial = 4 if split > 1 else itemsize
        sizes = (itemsize,) * 4 + (4, 4, itemsize, partial, partial)
        # every block twice, the scratch, and room for the (block_k,
        # block_q) float32 tiles between the products
        vmem = sum(2 * _vmem_bytes(blk[1:], size)
                   for (blk, _arr), size in zip(ins + outs, sizes)) \
            + _vmem_bytes(acc, 4) + 8 * block_q * block_k * 4 + (4 << 20)
        if vmem <= budget or split == group:
            return split, vmem, layout


@functools.partial(jax.jit, static_argnames=(
    "causal", "scale", "block_q", "block_k", "interpret", "window"))
def _flash_backward_kernel_call(q, k, v, o, lse, do, causal, scale,
                                block_q, block_k, interpret, window=None):
    """(dq, dk, dv) of the backward kernel from the forward's residuals.
    Jitted for the reason the forward call is: a model's layers lower it
    once."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, Sq, D = q.shape
    sk, d_v = v.shape[-2:]
    group = _head_group(q, k)
    bh = B * H // group
    split, vmem, (ins, outs, acc) = _flash_backward_split(
        bh, Sq, sk, D, block_q, block_k, d_v, group, q.dtype.itemsize)
    # a key/value head's query heads are neighbours in (B, H, ...): they
    # become ``split`` runs of group·Sq / split rows by a reshape that
    # moves nothing
    programs, rows = bh * split, group * Sq // split
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    operands = (q.reshape(programs, rows, D), k.reshape(bh, sk, D),
                v.reshape(bh, sk, d_v), do.reshape(programs, rows, d_v),
                lse.reshape(programs, rows // block_q, 1, block_q),
                delta.reshape(programs, rows // block_q, 1, block_q))
    kernel = functools.partial(_flash_backward_kernel, block_q=block_q,
                               causal=causal, scale=scale,
                               group=group // split, window=window)
    whole = lambda b, j: (b, 0, 0)          # noqa: E731
    by_key = lambda b, j: (b, j, 0)         # noqa: E731
    rows = lambda b, j: (b, 0, 0, 0)        # noqa: E731
    if split == 1:
        kv_by_key, partial = by_key, (k.dtype, v.dtype)
    else:       # programs b·split .. b·split + split − 1 share head b's k, v
        kv_by_key = lambda b, j: (b // split, j, 0)     # noqa: E731
        partial = (jnp.float32, jnp.float32)
    dq, dk, dv = pl.pallas_call(
        kernel,
        grid=(programs, sk // block_k),
        in_specs=[pl.BlockSpec(blk, index) for (blk, _arr), index in zip(
            ins, (whole, kv_by_key, kv_by_key, whole, rows, rows))],
        out_specs=[pl.BlockSpec(blk, index) for (blk, _arr), index in zip(
            outs, (whole, by_key, by_key))],
        out_shape=[jax.ShapeDtypeStruct(arr, dtype)
                   for (_blk, arr), dtype in zip(outs, (q.dtype,) + partial)],
        scratch_shapes=[pltpu.VMEM(acc, jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=int(vmem)),
        name=_kernel_name("backward", window),
        interpret=interpret,
    )(*operands)
    if split > 1:       # the programs' float32 partial sums, rounded once
        dk, dv = (t.reshape((bh, split) + t.shape[1:]).sum(axis=1)
                  .astype(x.dtype) for t, x in ((dk, k), (dv, v)))
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)


def flash_attention(q, k, v, causal=False, scale=None, block_q=None,
                    block_k=None, interpret=None, window=None):
    """Fused attention; q (B, H, S, D), k (B, H_kv, S, D), v (B, H_kv, S,
    D_v) with D_v = D or not (the output is as wide as v) and H_kv = H or
    a divisor of it (grouped queries: head h attends key/value head
    h // (H / H_kv); both kernels index k and v by that, neither repeats
    them, and dk, dv come back summed over a group).  The Pallas kernel where the
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

    Differentiable: the forward runs the fused kernel and saves its
    operands, its output and the logsumexp stats, each under a
    ``checkpoint_name`` (``FLASH_RESIDUALS``: a checkpoint that saves
    those names does not run the kernel again, and its backward reads
    what the forward read and wrote); the backward is a second Pallas
    kernel
    (``_flash_backward_kernel``, attached via custom_vjp) under the same
    rules: it recomputes p a (block_k, block_q) tile at a time from the
    stats, never the (Sq, Sk) score matrix; multiplies q, k, v, do as
    they come and rounds p and ds to their dtype for the products that
    consume them; sums, and accumulates dq, dk, dv, in float32; and
    under ``causal`` a key block meets only the query blocks from the
    diagonal down.  It takes every shape the forward kernel takes.

    ``window`` W (with ``causal``): query i sees keys i − W + 1 … i, the
    sliding-window causal mask (W keys with itself).  Each query block
    then reads only the key blocks of its band, from the first that meets
    the band to the diagonal, and each key block meets only the query
    blocks up to the one that holds its last key + W − 1; the blocks that
    either edge crosses are masked, the rest are not.  Both calls are
    named ``flash_window_forward`` / ``flash_window_backward``, and the
    forward's residuals carry the same ``FLASH_RESIDUALS`` names.

    ``block_q``/``block_k`` default to the largest of 512/256/128 that
    divides the sequence (``_flash_blocks``), for both kernels.
    Sequence lengths must be multiples of the block sizes for the kernel
    path (pad upstream); otherwise falls back to the reference
    implementation.
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if window is not None and (not causal or window < 1):
        raise ValueError("a sliding window of %r needs causal attention and "
                         "one key or more" % (window,))

    def reference(q, k, v):
        return attention_reference(q, k, v, causal=causal, scale=scale,
                                   window=window)

    blocks = _flash_blocks(q.shape[-2], k.shape[-2], block_q, block_k)
    if blocks is None:                 # hard kernel constraint
        return reference(q, k, v)
    block_q, block_k = blocks

    def kernel(q, k, v, interpret=False):
        @jax.custom_vjp
        def _fa(q, k, v):
            out, _ = _flash_forward_kernel_call(
                q, k, v, causal, scale, block_q, block_k, interpret,
                window=window)
            return out

        def _fa_fwd(q, k, v):
            out, lse = _flash_forward_kernel_call(
                q, k, v, causal, scale, block_q, block_k, interpret,
                window=window)
            # a name is the identity: outside a checkpoint it lowers to
            # nothing
            res = tuple(checkpoint_name(x, name) for x, name in zip(
                (q, k, v, out, lse), FLASH_RESIDUALS))
            return res[3], res

        def _fa_bwd(res, ct):
            q, k, v, out, lse = res
            return _flash_backward_kernel_call(
                q, k, v, out, lse, ct, causal, scale, block_q, block_k,
                interpret, window=window)

        _fa.defvjp(_fa_fwd, _fa_bwd)
        return _fa(q, k, v)

    if interpret is not None:
        return kernel(q, k, v, interpret=bool(interpret))
    from ..kernels.common import dispatch
    return dispatch(kernel, reference, q, k, v)


# ----------------------------------------------------------------------
# Ring attention over a mesh axis
# ----------------------------------------------------------------------
def ring_attention(q, k, v, axis_name="sp", causal=False, scale=None,
                   window=None):
    """Sequence-parallel attention inside shard_map (``window``: the
    sliding-window causal mask of :func:`attention_reference`).

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
                                  kv_offset, m, l, o, window)
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


def reopen_current_scope():
    """``make()`` -> the context that is active now, to be entered again
    later (a step lowered once more, after its owner left the scope), or
    None where none is active."""
    ctx = current_sequence_parallel()
    if ctx is None:
        return None
    return functools.partial(sequence_parallel, ctx.mesh,
                             seq_axis=ctx.seq_axis,
                             batch_axis=ctx.batch_axis)


def attention_scope(mesh, seq_axis=None):
    """The context to trace and run a step over ``mesh`` under (None or
    one device: nothing to tell): :func:`sequence_parallel` with the
    ring over 'sp' when the step shards a sequence axis and the mesh has
    one, per-device flash attention otherwise."""
    if mesh is None or mesh.size == 1:
        return _contextlib.nullcontext()
    ring = seq_axis is not None and "sp" in mesh.axis_names
    return sequence_parallel(mesh, seq_axis="sp" if ring else None)


def mesh_axis_that_splits(mesh, axis, dim):
    """``axis`` where ``mesh`` has more than one device along it and they
    divide ``dim``, else None (the dimension stays whole a device): the
    entry of a ``shard_map`` spec for a per-device kernel call."""
    size = mesh.shape.get(axis, 1) if axis else 1
    return axis if size > 1 and dim % size == 0 else None


def sharded_self_attention(q, k, v, causal=False, window=None):
    """Attention dispatch for (B, H, S, D): flash/reference on one
    device; under a :func:`sequence_parallel` mesh context, ring
    attention over the sequence axis, or per-device flash.  ``window``
    (with ``causal``): the sliding-window mask, on every path."""
    ctx = current_sequence_parallel()
    if ctx is None:
        return flash_attention(q, k, v, causal=causal, window=window)
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    mesh = ctx.mesh
    group = _head_group(q, k)

    if ctx.seq_axis in mesh.axis_names:
        if group > 1:       # the ring's einsums pair heads one to one
            k, v = (jnp.repeat(t, group, axis=1) for t in (k, v))
        spec = P(ctx.batch_axis, None, ctx.seq_axis, None)
        check_vma = True

        def att(q, k, v):
            return ring_attention(q, k, v, axis_name=ctx.seq_axis,
                                  causal=causal, window=window)
    else:
        # "Mosaic kernels cannot be automatically partitioned. Please
        # wrap the call in a shard_map" (the four-chip host, PR 21): the
        # batch splits over dp, the heads over tp where those divide,
        # and every device attends over its own block
        # heads split only where the key/value heads do: a device keeps
        # whole groups
        spec = P(mesh_axis_that_splits(mesh, ctx.batch_axis, q.shape[0]),
                 mesh_axis_that_splits(mesh, "tp", k.shape[1]), None, None)
        check_vma = False       # pallas_call outputs declare no vma

        def att(q, k, v):
            return flash_attention(q, k, v, causal=causal, window=window)

    return shard_map(att, mesh=mesh, in_specs=(spec,) * 3, out_specs=spec,
                     check_vma=check_vma)(q, k, v)


def _kernel_spec(name, grid, blocks):
    """A spec dict for analysis/tiling.py from ``(role, name, (block,
    array), dtype)`` rows."""
    return {"name": name, "origin": "mxnet_tpu/parallel/ring_attention.py",
            "grid": grid,
            "blocks": [{"role": role, "name": n, "block": blk, "array": arr,
                        "dtype": dt} for role, n, (blk, arr), dt in blocks]}


def flash_kernel_spec(batch_heads=8, seq_q=1024, seq_k=1024, head_dim=64,
                      block_q=None, dtype="bfloat16", head_dim_v=None,
                      group=1):
    """MXL-K kernel spec for the flash forward pallas_call.

    Built from the same :func:`_flash_block_layout` the kernel itself
    uses, at a representative training shape, so the static tile
    validator (analysis/tiling.py) checks the blocks that actually run.
    The lse output deliberately carries ``_LSE_ROWS`` sublanes: a 1-D
    ``(block_q,)`` stats row is exactly the historical bug Mosaic
    rejected (no second dimension to tile).
    """
    block_q, _block_k = _flash_blocks(seq_q, seq_k, block_q)
    ins, outs = _flash_block_layout(batch_heads, seq_q, seq_k, head_dim,
                                    block_q, head_dim_v, group)
    return _kernel_spec(
        "flash_forward", (batch_heads, seq_q // block_q),
        [("in", n, b, dtype) for n, b in zip(("q", "k", "v"), ins)]
        + [("out", "o", outs[0], dtype), ("out", "lse", outs[1], "float32")])


def flash_backward_kernel_spec(batch_heads=8, seq_q=1024, seq_k=1024,
                               head_dim=64, dtype="bfloat16",
                               head_dim_v=None, group=1):
    """MXL-K kernel spec for the flash backward pallas_call, from the
    :func:`_flash_backward_block_layout` the call itself uses and the
    blocks and the split of a group ``flash_attention`` gives it for these
    shapes.  lse and delta are float32 (1, block_q) rows, one a query
    block: each block covers its array's last two dims whole."""
    block_q, block_k = _flash_blocks(seq_q, seq_k)
    bh = batch_heads // group
    split, _vmem, (ins, outs, _acc) = _flash_backward_split(
        bh, seq_q, seq_k, head_dim, block_q, block_k,
        head_dim if head_dim_v is None else head_dim_v, group,
        jnp.dtype(dtype).itemsize)
    partial = "float32" if split > 1 else dtype
    return _kernel_spec(
        "flash_backward", (bh * split, seq_k // block_k),
        [("in", n, b, "float32" if n in ("lse", "delta") else dtype)
         for n, b in zip(("q", "k", "v", "do", "lse", "delta"), ins)]
        + [("out", n, b, dt) for n, b, dt in zip(
            ("dq", "dk", "dv"), outs, (dtype, partial, partial))])


try:
    from ..analysis.tiling import register_kernel_spec as _register_spec
    _register_spec("parallel.ring_attention.flash_forward",
                   flash_kernel_spec)
    _register_spec("parallel.ring_attention.flash_backward",
                   flash_backward_kernel_spec)
except Exception:            # analysis package optional at import time
    pass
