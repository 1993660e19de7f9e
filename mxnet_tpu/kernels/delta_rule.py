"""The gated delta rule's chunk-parallel form as two Pallas kernels.

``ops/linear_attention.py`` has the mathematics (its module text) and the
XLA form, which is the reference here; this module is the same algorithm
with a chunk's whole working set held in VMEM.  A program takes a block of
tokens of one value head (forward) or of one key head's group of value heads
(backward) straight from the (B, S, H·d) view of q, k, v — value head h reads
key head h // r by its block index, nothing is repeated or transposed in HBM
— and walks the block in an inner loop with the float32 state (d_k, d_v) in
a VMEM scratch that lives across the sequence's blocks.  Beside q, k, v a
program reads the per-token scalars prepared outside (``_chunk_scalars``: G,
the running sum of g inside a chunk, and β, a row of C numbers a chunk).

The loop's step is a *tile*: as many chunks as fill a vector register's 128
lanes (two of 64 tokens), side by side.  What does not read the state
(:func:`_tile_locals`, shared by both kernels) is computed for the tile at
once on (R, R) arrays that are 0 outside the chunks on their diagonal,
because the masked decay is (the argument masked, not the result):
A = tril(β K Kᵀ ⊙ decay, −1), T = (I + A)⁻¹ (:func:`_unit_lower_inverse`:
rows by substitution inside diagonal blocks of ``_DIAG``, then the block
merges −L₂⁻¹ X L₁⁻¹ as float32 products at ``highest``, up to the chunk),
W, U, the decayed q and k — a kernel's time is the rows it pushes through
the matrix unit, product by product, so products that share an operand are
one product and a tile's chunks share every product that does not read the
state.  Then the tile's chunks meet the state one after another.  Nothing of
it leaves VMEM.  The rounding points are the XLA form's: products take their
operands in the dtype q, k, v came in and sum in float32; decays, G, the
state, A and T's computation are float32; T, W, V′, the decayed q and k are
rounded to the compute dtype where ``linear_attention``'s XLA form rounds
them.

The backward (``jax.custom_vjp``) is two sweeps: the forward kernel as the
``fwd`` rule runs it, where it also writes each chunk's starting state in
the compute dtype (N × d_k × d_v a head) and names what it hands on
(``DELTA_RESIDUALS``: a checkpoint that saves those names, as the
executor's mirrored segments do, keeps the states from the first sweep to
the backward kernel; a bare one runs the sweep again as its recomputation),
and ``gated_delta_backward``, which walks blocks, tiles and chunks in reverse
with dS in VMEM, recomputes a tile's locals, and returns all five gradients
— dq and dk of a key head summed over its r value heads in float32 inside
the kernel.  Cotangents are rounded to the compute dtype for the products
that consume them, as the forward rounds its operands.  One rounding point
is not the XLA form's: the e^{G_C} term of dg multiplies dS′ by the chunk's
starting state as the backward reads it back, rounded to the compute dtype,
where autodiff of the scan's ``state * decay`` holds the float32 state — a
relative 2⁻⁹ on one of dg's terms in bfloat16, nothing in float32.

Blocks and the VMEM request come from the shapes (:func:`delta_blocks`,
:func:`_vmem_need`): a limit is asked for only past what Mosaic gives
unasked, and shapes whose working set no block holds go to the reference.
Mosaic kernels are not partitioned by GSPMD: on a mesh of several chips
``ops.linear_attention.gated_delta_rule`` calls this module per device
under ``shard_map``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

__all__ = ["delta_blocks", "gated_delta_rule_kernel",
           "gated_delta_forward_kernel_spec",
           "gated_delta_backward_kernel_spec", "DELTA_RESIDUALS",
           "kda_blocks", "kda_rule_kernel", "KDA_RESIDUALS"]

# What the forward sweep hands on — q, k, v, the chunk-major scalars, the
# output and the chunk states — each under a ``checkpoint_name``, as the
# flash kernel's are (``ring_attention.FLASH_RESIDUALS``, and why the
# operands belong to the set): a ``jax.checkpoint`` whose policy saves
# these names does not run the sweep again.  The backward kernel does not
# read the output; the gated norm after the rule does, in its own backward,
# and a checkpoint that lacks it runs the sweep again to have it.
DELTA_RESIDUALS = ("delta_q", "delta_k", "delta_v", "delta_scalars",
                   "delta_out", "delta_states")

# tokens a program, largest first (the flash kernels' blocks)
_BLOCKS = (512, 256, 128)
# rows of the diagonal blocks T is found in by substitution; above that
# size blocks merge by products
_DIAG = 16
_NEG_INF = -1e30
_F32 = jnp.float32
_HIGHEST = lax.Precision.HIGHEST


def delta_blocks(seq, chunk, d_k, d_v, group=1, itemsize=2):
    """``(block, C)``: tokens a program and tokens a chunk for a sequence
    of ``seq``, ``group`` value heads a key head and operands of
    ``itemsize`` bytes, or None where the kernels do not take the shapes — a
    chunk that is no power of two or under a bfloat16 tile's 16 rows, a
    sequence that is no whole number of chunks, head widths that are no
    whole vector registers' lanes, no block that the scalars' (chunks, C)
    tiles allow (a multiple of 8 chunks, or the whole sequence), or none
    whose working set VMEM holds (:func:`_vmem_need` of the backward, which
    meets a whole group a program, against what a kernel may ask for: the
    flash kernels' budget)."""
    from ..parallel import ring_attention as ra
    C = min(int(chunk), int(seq))
    if C & (C - 1) or C < 16 or seq % C or d_k % 128 or d_v % 128:
        return None
    blocks = [b for b in _BLOCKS
              if seq % b == 0 and b % C == 0 and (b // C) % 8 == 0]
    if not blocks and seq <= _BLOCKS[0]:
        blocks = [seq]
    for block in blocks:
        layout = _backward_layout(1, block, 1, group, d_k, d_v, block, C)
        if _vmem_need(layout, _BACKWARD_SIZES, itemsize, block, C, d_k, d_v,
                      group) <= ra._VMEM_BUDGET:
            return block, C
    return None


# bytes an element of each block of the two calls' layouts, the operands'
# own size where None
_FORWARD_SIZES = (None, None, None, 4, None, None)
_BACKWARD_SIZES = (None,) * 4 + (4, None) + (None,) * 3 + (4,)


def _vmem_need(layout, sizes, itemsize, block, C, d_k, d_v, group):
    """Bytes of VMEM a call with ``layout`` works in: every block twice
    (the pipeline's two buffers), the float32 state a value head, and room
    for what a tile keeps between its products — some sixteen float32
    arrays of (R, R) and of (R, d_k + d_v) and eight of the state's size —
    with the flash kernels' 4 MiB to spare."""
    from ..parallel import ring_attention as ra
    ins, outs = layout
    R = _pack(block // C, C) * C
    held = sum(2 * ra._vmem_bytes([n for n in blk if n is not None],
                                  size or itemsize)
               for (blk, _arr, _index), size in zip(ins + outs, sizes))
    return held + 4 * d_k * d_v * (group + 8) \
        + 16 * 4 * R * (R + d_k + d_v) + (4 << 20)


def _compiler_params(need):
    """Both calls': a head's blocks in order, and a VMEM limit only where
    the need is past what Mosaic gives a kernel unasked, so that every
    smaller shape lowers as it did without one (the flash forward's rule)."""
    from jax.experimental.pallas import tpu as pltpu
    from ..parallel import ring_attention as ra
    limit = {}
    if need > ra._SCOPED_VMEM_DEFAULT - (1 << 20):
        limit["vmem_limit_bytes"] = int(need)
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"), **limit)


def _chunk_scalars(g, beta, C):
    """(B, H, 2, N, C) float32: G (the running sum of g inside each chunk)
    and β, a row a chunk — the one thing prepared outside the kernels, 2 MB
    where q is 67."""
    B, S, H = g.shape

    def rows(t):
        return jnp.moveaxis(t.astype(_F32), 1, 2).reshape(B, H, S // C, C)

    return jnp.stack([jnp.cumsum(rows(g), axis=-1), rows(beta)], axis=2)


# ----------------------------------------------------------------------
# what both kernels compute for a tile
# ----------------------------------------------------------------------
def _dot(a, b, contract=(1, 0)):
    """a · b over ``contract`` = (a's dim, b's dim), summed in float32."""
    return lax.dot_general(a, b, (((contract[0],), (contract[1],)), ((), ())),
                           preferred_element_type=_F32)


def _dot32(a, b):
    return lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                           precision=_HIGHEST, preferred_element_type=_F32)


def _indices(R):
    row = lax.broadcasted_iota(jnp.int32, (R, R), 0)
    col = lax.broadcasted_iota(jnp.int32, (R, R), 1)
    return row, col


def _to_col(x_row, eye):
    """(1, R) -> (R, 1), exactly: a mask and a sum."""
    return jnp.sum(jnp.where(eye, x_row, 0.0), axis=1, keepdims=True)


def _to_row(x_col, eye):
    return jnp.sum(jnp.where(eye, x_col, 0.0), axis=0, keepdims=True)


def _substitute(a, nb):
    """The inverses of the ``nb``-row diagonal blocks of I + tril(a, −1),
    side by side along the lanes — (nb, R): row r of every block in sublane
    r — by forward substitution on all blocks at once: block row j, once
    final, leaves every later row of its block, times that row's entry in
    column j, which a lane gather spreads over its block's lanes.  A step
    is two vector registers at 16 rows of 128, not the tile's sixteen."""
    R = a.shape[0]
    # iotas of their own: Mosaic does not take a slice of the tile's
    r = lax.broadcasted_iota(jnp.int32, (nb, R), 0)
    lane = lax.broadcasted_iota(jnp.int32, (nb, R), 1)
    low = sum(jnp.where((lane // nb == b) & (r > lane % nb),
                        a[b * nb:(b + 1) * nb, :], 0.0)
              for b in range(R // nb))
    x = jnp.where(r == lane % nb, 1.0, 0.0).astype(_F32)
    first = (lane // nb) * nb
    for j in range(nb - 1):
        x = x - jnp.take_along_axis(low, first + j, axis=1) * x[j:j + 1, :]
    return x


def _merge(x, a, n):
    """From x, the inverse of the block-diagonal part of I + tril(a, −1) at
    blocks of n rows, that at 2n: the inverse of [[L₁, 0], [X, L₂]] is
    [[L₁⁻¹, 0], [−L₂⁻¹ X L₁⁻¹, L₂⁻¹]].  Only the rows of the odd blocks
    change, so both products run on those rows alone, R / 2 of them: X's
    rows times x, then the same rows of x (which hold L₂⁻¹) times that, put
    back at its rows — float32 products at ``highest``, as
    ``linear_attention.unit_lower_inverse`` makes them."""
    R = a.shape[0]
    odd = [slice(b * n, (b + 1) * n) for b in range(1, R // n, 2)]
    r = lax.broadcasted_iota(jnp.int32, (R // 2, R), 0)
    lane = lax.broadcasted_iota(jnp.int32, (R // 2, R), 1)
    zeros = jnp.zeros((n, R), _F32)

    def gather(t):          # the odd blocks' rows of an (R, R) tile
        return jnp.concatenate([t[s, :] for s in odd], axis=0)

    def spread(t):          # and back to their places, zeros between
        return jnp.concatenate(
            [piece for p in range(len(odd))
             for piece in (zeros, t[p * n:(p + 1) * n, :])], axis=0)

    # gathered row r lies in pair r // n, whose even block holds X's columns
    below = jnp.where(lane // n == 2 * (r // n), gather(a), 0.0)
    return x - spread(_dot32(gather(x), spread(_dot32(below, x))))


def _unit_lower_inverse(a, C):
    """(I + tril(a, −1))⁻¹ of a float32 (R, R) tile that holds R / C chunks
    on its diagonal (only what lies below the diagonal inside a chunk is
    read; the result is 0 outside the chunks): forward substitution inside
    the diagonal blocks of ``_DIAG`` rows (:func:`_substitute`), then the
    merges of ``linear_attention.unit_lower_inverse`` (:func:`_merge`) up
    to the chunk."""
    R = a.shape[0]
    nb = min(_DIAG, C)
    row, col = _indices(R)
    x = jnp.where((row // nb) == (col // nb),
                  jnp.concatenate([_substitute(a, nb)] * (R // nb), axis=0),
                  0.0)
    while nb < C:
        x = _merge(x, a, nb)
        nb *= 2
    return x


def _tile_scalars(gb_ref, lead, first, pack):
    """(1, R) rows of G and β for the ``pack`` chunks from ``first``: the
    chunks' rows of C, end to end along the lanes.  ``lead`` indexes what
    comes before the (2, chunks, C) axes of ``gb_ref``."""
    import jax.experimental.pallas as pl

    def row(which):
        rows = gb_ref[lead + (which, pl.ds(first, pack), slice(None))]
        return jnp.concatenate([rows[i:i + 1, :] for i in range(pack)],
                               axis=1)
    return row(0), row(1)


def _tile_locals(q, k, v, G_row, b_row, C):
    """Everything of a tile of R = pack · C tokens that does not read the
    state, as the XLA form computes and rounds it (the module's text), the
    tile's chunks side by side: every (R, R) array is 0 outside the chunks
    on its diagonal, because the decay is.  q, k (R, d_k), v (R, d_v) in
    the compute dtype; G_row, b_row (1, R) float32.  Products that share
    an operand are one product: [β K; Q] Kᵀ and T [β e^G K | β V]."""
    dt = v.dtype
    R, d_k = q.shape
    row, col = _indices(R)
    eye = row == col
    inside = (row >= col) & ((row // C) == (col // C))
    G, beta = _to_col(G_row, eye), _to_col(b_row, eye)
    decay = jnp.exp(jnp.where(inside, G - G_row, _NEG_INF))
    e_g = jnp.exp(G)
    # G at each chunk's last token: a (1, 1) a chunk, and down the rows
    lane = lax.broadcasted_iota(jnp.int32, (1, R), 1)
    lasts = [jnp.sum(jnp.where(lane == i * C + C - 1, G_row, 0.0), axis=1,
                     keepdims=True) for i in range(R // C)]
    chunk_of = lax.broadcasted_iota(jnp.int32, (R, 1), 0) // C
    last = sum(jnp.where(chunk_of == i, x, 0.0) for i, x in enumerate(lasts))
    k_decay = jnp.exp(last - G)
    k32 = k.astype(_F32)
    kb = (k32 * beta).astype(dt)
    xk = _dot(jnp.concatenate([kb, q], axis=0), k, (1, 1))      # (2R, R)
    a = xk[:R] * decay
    t = _unit_lower_inverse(a, C).astype(dt)
    kbg = (kb.astype(_F32) * e_g).astype(dt)
    vb = (v.astype(_F32) * beta).astype(dt)
    wu = _dot(t, jnp.concatenate([kbg, vb], axis=1))            # (R, d_k+d_v)
    return dict(
        inside=inside, eye=eye, beta=beta, decay=decay, e_g=e_g,
        k_decay=k_decay, e_last=[jnp.exp(x) for x in lasts], kb=kb, a=a,
        t=t, kbg=kbg, vb=vb, w=wu[:, :d_k].astype(dt), u=wu[:, d_k:],
        p32=xk[R:] * decay, qg=(q.astype(_F32) * e_g).astype(dt),
        kt=(k32 * k_decay).astype(dt))


def _pack(n_chunks, C):
    """Chunks a tile: as many as fill a vector register's 128 lanes, if
    the block's chunks divide into such tiles."""
    pack = max(1, 128 // C)
    while n_chunks % pack:
        pack //= 2
    return pack


# ----------------------------------------------------------------------
# forward
# ----------------------------------------------------------------------
def _forward_kernel(q_ref, k_ref, v_ref, gb_ref, o_ref, *rest, chunk):
    """Grid: (batch · value heads, blocks), the blocks of a head in order.
    ``rest``: the chunk states' output block where the backward asked for
    them, then the float32 state scratch.  A tile's chunks meet the state
    one after another; what does not read it is computed for the tile."""
    import jax.experimental.pallas as pl

    states_ref = rest[0] if len(rest) == 2 else None
    state = rest[-1]
    C = chunk
    n_chunks = q_ref.shape[0] // C
    pack = _pack(n_chunks, C)
    R = pack * C

    @pl.when(pl.program_id(1) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    def one_tile(tile, carry):
        rows = pl.ds(pl.multiple_of(tile * R, R), R)
        q, k, v = q_ref[rows, :], k_ref[rows, :], v_ref[rows, :]
        dt = v.dtype
        x = _tile_locals(q, k, v, *_tile_scalars(gb_ref, (), tile * pack,
                                                 pack), C)
        p = x["p32"].astype(dt)
        outs = []
        for i in range(pack):
            at = slice(i * C, (i + 1) * C)
            s = state[...].astype(dt)
            if states_ref is not None:
                states_ref[tile * pack + i] = s
            ws = _dot(jnp.concatenate([x["w"][at], x["qg"][at]], axis=0), s)
            v_new = (x["u"][at] - ws[:C]).astype(dt)
            outs.append(ws[C:] + _dot(p[at, at], v_new))
            state[...] = state[...] * x["e_last"][i] \
                + _dot(x["kt"][at], v_new, (0, 0))
        o_ref[rows, :] = jnp.concatenate(outs, axis=0).astype(o_ref.dtype)
        return carry

    lax.fori_loop(0, n_chunks // pack, one_tile, 0)


def _forward_layout(B, S, Hk, Hv, d_k, d_v, block, C, emit_states):
    """(block, array, index map) rows of the forward call, q/k/v/scalars
    then o (and the chunk states): the one place its blocks live, shared
    with the MXL-K spec."""
    r, N, nc = Hv // Hk, S // C, block // C
    by_key = ((None, block, d_k), (B, S, Hk * d_k),
              lambda p, i: (p // Hv, i, (p % Hv) // r))
    by_value = ((None, block, d_v), (B, S, Hv * d_v),
                lambda p, i: (p // Hv, i, p % Hv))
    scalars = ((None, None, 2, nc, C), (B, Hv, 2, N, C),
               lambda p, i: (p // Hv, p % Hv, 0, i, 0))
    ins, outs = [by_key, by_key, by_value, scalars], [by_value]
    if emit_states:
        outs.append(((None, None, nc, d_k, d_v), (B, Hv, N, d_k, d_v),
                     lambda p, i: (p // Hv, p % Hv, i, 0, 0)))
    return ins, outs


@functools.partial(jax.jit, static_argnames=(
    "block", "chunk", "emit_states", "interpret"))
def _forward_call(q, k, v, gb, block, chunk, emit_states, interpret):
    """o (B, S, H_v, d_v), and with ``emit_states`` each chunk's starting
    state (B, H_v, N, d_k, d_v) in v's dtype.  Jitted so that a model's
    layers lower the kernel once."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, S, Hk, d_k = q.shape
    Hv, d_v = v.shape[2:]
    ins, outs = _forward_layout(B, S, Hk, Hv, d_k, d_v, block, chunk,
                                emit_states)
    need = _vmem_need((ins, outs), _FORWARD_SIZES, v.dtype.itemsize, block,
                      chunk, d_k, d_v, 1)
    res = pl.pallas_call(
        functools.partial(_forward_kernel, chunk=chunk),
        grid=(B * Hv, S // block),
        in_specs=[pl.BlockSpec(blk, index) for blk, _arr, index in ins],
        out_specs=[pl.BlockSpec(blk, index) for blk, _arr, index in outs],
        out_shape=[jax.ShapeDtypeStruct(arr, v.dtype)
                   for _blk, arr, _index in outs],
        scratch_shapes=[pltpu.VMEM((d_k, d_v), _F32)],
        compiler_params=_compiler_params(need),
        name="gated_delta_forward",
        interpret=interpret,
    )(q.reshape(B, S, Hk * d_k), k.reshape(B, S, Hk * d_k),
      v.reshape(B, S, Hv * d_v), gb)
    o = res[0].reshape(B, S, Hv, d_v)
    return (o, res[1]) if emit_states else o


# ----------------------------------------------------------------------
# backward
# ----------------------------------------------------------------------
def _backward_kernel(q_ref, k_ref, v_ref, do_ref, gb_ref, states_ref,
                     dq_ref, dk_ref, dv_ref, dgb_ref, d_state, *, chunk,
                     group):
    """Grid: (batch · key heads, blocks), the blocks of a head in reverse
    (the index maps turn them round).  A program meets the ``group`` value
    heads of its key head in turn, chunk by chunk from the block's last:
    each head's dS lives in its own float32 scratch tile, dq and dk sum
    over the heads in float32 and are written once.

    With P = Q Kᵀ ⊙ decay, K̃ = K e^{G_C − G}, S the chunk's starting state
    and dS′ the gradient of its final one:

        dV′ = Pᵀ dO + K̃ dS′        dS = e^{G_C} dS′ + (Q e^G)ᵀ dO − Wᵀ dV′
        dU = dV′    dW = −dV′ Sᵀ    d(Q e^G) = dO Sᵀ    dK̃ = V′ dS′ᵀ
        dP = (dO V′ᵀ) below and on the diagonal
        dT = dW (β e^G K)ᵀ + dU (β V)ᵀ     dA = −Tᵀ dT Tᵀ below the diagonal

    then through the element-wise factors to q, k, v, β and to G: every
    exp(G_i − G_j) sends its term to G_i and takes it from G_j, e^{G_C}
    gathers at the chunk's last token."""
    import jax.experimental.pallas as pl

    C = chunk
    d_k, d_v = q_ref.shape[1], v_ref.shape[1] // group
    n_chunks = q_ref.shape[0] // C
    pack = _pack(n_chunks, C)
    R = pack * C
    lane = lax.broadcasted_iota(jnp.int32, (1, R), 1)

    @pl.when(pl.program_id(1) == 0)
    def _():
        d_state[...] = jnp.zeros_like(d_state)

    def one_tile(step, carry):
        tile = n_chunks // pack - 1 - step
        rows = pl.ds(pl.multiple_of(tile * R, R), R)
        q, k = q_ref[rows, :], k_ref[rows, :]
        dt = q.dtype
        q32, k32 = q.astype(_F32), k.astype(_F32)
        dq = jnp.zeros(q.shape, _F32)
        dk = jnp.zeros(k.shape, _F32)
        for h in range(group):
            lanes = slice(h * d_v, (h + 1) * d_v)
            v, do = v_ref[rows, lanes], do_ref[rows, lanes]
            x = _tile_locals(q, k, v, *_tile_scalars(gb_ref, (h,),
                                                     tile * pack, pack), C)
            p = x["p32"].astype(dt)
            # the chunks against the state, the tile's last first
            pieces, to_last = [None] * pack, jnp.zeros((1, R), _F32)
            for i in reversed(range(pack)):
                at = slice(i * C, (i + 1) * C)
                s = states_ref[h, tile * pack + i]
                ds_next = d_state[h]
                ds_dt = ds_next.astype(dt)
                v_new = (x["u"][at] - _dot(x["w"][at], s)).astype(dt)
                dv_new = (_dot(p[at, at], do[at], (0, 0))
                          + _dot(x["kt"][at], ds_dt)).astype(dt)   # = dU
                d_state[h] = ds_next * x["e_last"][i] + _dot(
                    jnp.concatenate([x["qg"][at], x["w"][at]], axis=0),
                    jnp.concatenate([do[at], -dv_new], axis=0), (0, 0))
                from_s = _dot(jnp.concatenate([do[at], dv_new], axis=0), s,
                              (1, 1))                   # d(Q e^G); −dW
                pieces[i] = (v_new, dv_new, (-from_s[C:]).astype(dt),
                             from_s[:C], _dot(v_new, ds_dt, (1, 1)))
                to_last = to_last + jnp.where(
                    lane == i * C + C - 1,
                    x["e_last"][i] * jnp.sum(jnp.sum(
                        ds_next * s.astype(_F32), axis=1, keepdims=True),
                        axis=0, keepdims=True), 0.0)
            v_new, dv_new, dw, dqg, dkt = (
                jnp.concatenate(t, axis=0) for t in zip(*pieces))

            # the tile's chunks side by side again
            dp = jnp.where(x["inside"], _dot(do, v_new, (1, 1)), 0.0)
            dwu = jnp.concatenate([dw, dv_new], axis=1)
            d_t = _dot(dwu, jnp.concatenate([x["kbg"], x["vb"]], axis=1),
                       (1, 1)).astype(dt)
            from_t = _dot(x["t"], dwu, (0, 0))          # d(β e^G K) | d(β V)
            dkbg, dvb = from_t[:, :d_k], from_t[:, d_k:]
            da = -_dot(_dot(x["t"], d_t, (0, 0)).astype(dt), x["t"], (1, 1))
            da = jnp.where(x["inside"] & ~x["eye"], da, 0.0)

            both = jnp.concatenate([(da * x["decay"]).astype(dt),
                                    (dp * x["decay"]).astype(dt)], axis=0)
            from_k = _dot(both, k)                      # (2R, d_k)
            dq1 = dqg * x["e_g"]
            dk1 = dkt * x["k_decay"]
            dkb1 = dkbg * x["e_g"]
            dkb = from_k[:R] + dkb1
            dq = dq + from_k[R:] + dq1
            dk = dk + _dot(both, jnp.concatenate([x["kb"], q], axis=0),
                           (0, 0)) + dk1 + dkb * x["beta"]
            dv_ref[rows, lanes] = (dvb * x["beta"]).astype(dv_ref.dtype)

            # to G and β, a number a token
            e = da * x["a"] + dp * x["p32"]
            from_kt = jnp.sum(dk1 * k32, axis=1, keepdims=True)
            dG = jnp.sum(dq1 * q32 + dkb1 * x["kb"].astype(_F32), axis=1,
                         keepdims=True) - from_kt \
                + jnp.sum(e, axis=1, keepdims=True)
            # e^{G_C − G} sends its term to the chunk's last token
            kt_row = _to_row(from_kt, x["eye"])
            for i in range(pack):
                mine = (lane // C) == i
                to_last = to_last + jnp.where(
                    lane == i * C + C - 1,
                    jnp.sum(jnp.where(mine, kt_row, 0.0), axis=1,
                            keepdims=True), 0.0)
            dG_row = _to_row(dG, x["eye"]) \
                - jnp.sum(e, axis=0, keepdims=True) + to_last
            d_beta = _to_row(
                jnp.sum(dkb * k32, axis=1, keepdims=True)
                + jnp.sum(dvb * v.astype(_F32), axis=1, keepdims=True),
                x["eye"])
            for i in range(pack):
                at = slice(i * C, (i + 1) * C)
                dgb_ref[h, 0, pl.ds(tile * pack + i, 1), :] = dG_row[:, at]
                dgb_ref[h, 1, pl.ds(tile * pack + i, 1), :] = d_beta[:, at]
        dq_ref[rows, :] = dq.astype(dq_ref.dtype)
        dk_ref[rows, :] = dk.astype(dk_ref.dtype)
        return carry

    lax.fori_loop(0, n_chunks // pack, one_tile, 0)


def _backward_layout(B, S, Hk, Hv, d_k, d_v, block, C):
    """(block, array, index map) rows of the backward call: q/k/v/do/
    scalars/states, then dq/dk/dv/the scalars' gradients.  The arrays are
    views that move nothing: a key head's ``r`` value heads are neighbours
    along v's columns and along the head axes of the scalars and states."""
    r, N, nc, last = Hv // Hk, S // C, block // C, S // block - 1
    by_key = ((None, block, d_k), (B, S, Hk * d_k),
              lambda p, i: (p // Hk, last - i, p % Hk))
    by_group = ((None, block, r * d_v), (B, S, Hv * d_v),
                lambda p, i: (p // Hk, last - i, p % Hk))
    scalars = ((None, None, r, 2, nc, C), (B, Hk, r, 2, N, C),
               lambda p, i: (p // Hk, p % Hk, 0, 0, last - i, 0))
    states = ((None, None, r, nc, d_k, d_v), (B, Hk, r, N, d_k, d_v),
              lambda p, i: (p // Hk, p % Hk, 0, last - i, 0, 0))
    return ([by_key, by_key, by_group, by_group, scalars, states],
            [by_key, by_key, by_group, scalars])


@functools.partial(jax.jit, static_argnames=("block", "chunk", "interpret"))
def _backward_call(q, k, v, gb, states, do, block, chunk, interpret):
    """(dq, dk, dv, dgb) from the operands, the chunk states of the
    forward sweep and the output's cotangent."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, S, Hk, d_k = q.shape
    Hv, d_v = v.shape[2:]
    r = Hv // Hk
    ins, outs = _backward_layout(B, S, Hk, Hv, d_k, d_v, block, chunk)
    need = _vmem_need((ins, outs), _BACKWARD_SIZES, v.dtype.itemsize, block,
                      chunk, d_k, d_v, r)
    operands = (q.reshape(B, S, Hk * d_k), k.reshape(B, S, Hk * d_k),
                v.reshape(B, S, Hv * d_v), do.reshape(B, S, Hv * d_v),
                gb.reshape(ins[4][1]), states.reshape(ins[5][1]))
    dq, dk, dv, dgb = pl.pallas_call(
        functools.partial(_backward_kernel, chunk=chunk, group=r),
        grid=(B * Hk, S // block),
        in_specs=[pl.BlockSpec(blk, index) for blk, _arr, index in ins],
        out_specs=[pl.BlockSpec(blk, index) for blk, _arr, index in outs],
        out_shape=[jax.ShapeDtypeStruct(arr, dtype) for (_blk, arr, _index),
                   dtype in zip(outs, (q.dtype, k.dtype, v.dtype, _F32))],
        scratch_shapes=[pltpu.VMEM((r, d_k, d_v), _F32)],
        compiler_params=_compiler_params(need),
        name="gated_delta_backward",
        interpret=interpret,
    )(*operands)
    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape),
            dgb.reshape(gb.shape))


# ----------------------------------------------------------------------
# the rule, differentiable
# ----------------------------------------------------------------------
def gated_delta_rule_kernel(q, k, v, g, beta, block, chunk, interpret=False):
    """The rule through the kernels.  q, k (B, S, H_k, d_k), v (B, S, H_v,
    d_v) in the compute dtype, H_v a multiple of H_k; g, beta (B, S, H_v);
    ``(block, chunk)`` from :func:`delta_blocks`.  -> o (B, S, H_v, d_v)."""

    @jax.custom_vjp
    def rule(q, k, v, gb):
        return _forward_call(q, k, v, gb, block, chunk, False, interpret)

    def rule_fwd(q, k, v, gb):
        o, states = _forward_call(q, k, v, gb, block, chunk, True, interpret)
        # a name is the identity: outside a checkpoint it lowers to nothing
        q, k, v, gb, o, states = (
            checkpoint_name(x, name) for x, name in zip(
                (q, k, v, gb, o, states), DELTA_RESIDUALS))
        return o, (q, k, v, gb, states)

    def rule_bwd(res, do):
        q, k, v, gb, states = res
        return _backward_call(q, k, v, gb, states, do.astype(v.dtype),
                              block, chunk, interpret)

    rule.defvjp(rule_fwd, rule_bwd)
    # the running sum and the chunk-major rows of g and β are plain jnp,
    # outside the custom_vjp: jax differentiates them.  The scope is the
    # XLA form's: what a compiled step makes for the rule carries its name
    with jax.named_scope("gated_delta_rule"):
        return rule(q, k, v, _chunk_scalars(g, beta, chunk))


# ----------------------------------------------------------------------
# Kimi delta attention: a decay a key channel
# ----------------------------------------------------------------------
# KDA (arXiv:2510.26692) decays the state by one number a key channel,
# S ← (I − β k kᵀ) Diag(α) S + β k vᵀ, α = e^g, g (d_k) a token.  Per chunk,
# with Γ the running sum of g inside it, a channel's:
#
#     A = tril(β ⊙ Σ_c k_ic k_jc e^{Γ_ic − Γ_jc}, −1),  T = (I + A)⁻¹
#     W = T (β ⊙ K ⊙ e^Γ),  U = T (β ⊙ V),  V′ = U − W S
#     O = (Q ⊙ e^Γ) S + tril(Σ_c q_ic k_jc e^{Γ_ic − Γ_jc}) V′
#     S′ = Diag(e^{Γ_C}) S + (K ⊙ e^{Γ_C − Γ})ᵀ V′
#
# The decay no longer factors out of Q Kᵀ as one (R, R) matrix: a pair
# product carries e^{Γ_i − Γ_j} a channel.  It is formed as the product of
# (Q ⊙ e^{Γ − Γ_r}) and (K ⊙ e^{Γ_r − Γ})ᵀ, with r the first row of the
# query row's sub-block of ``_DIAG`` rows, a product a sub-block: off the
# diagonal (keys before the sub-block) both factors are at most 1; on it
# the key factor reaches e^{−Σ g} over at most 15 tokens.  With the gate
# bounded below by −5 (``kda_safe_gate``: g = −5 σ(·)) that is at most
# e^{75}, inside float32's e^{88.7}, and the product stays e^{Γ_i − Γ_j}
# ≤ 1 where it is kept; keys past the sub-block have their argument
# masked (not the result), so no exponent ever passes 75.  An unbounded
# gate could overflow there: the op bounds it, the kernels assume it.  The
# reference row cancels out of every product, so its gradient, a sum of
# cancelling terms, is left out.
#
# Both kernels walk a head's blocks, tiles of R = pack · C tokens and
# chunks as the scalar ones do, with the same blocks, inverse and VMEM
# rule.  Besides q, k, v they read Γ (B, S, H·d_k) float32 — the running
# sum taken outside, which jax differentiates — and β as rows (B, H, N, C).
KDA_RESIDUALS = ("kda_q", "kda_k", "kda_v", "kda_gates", "kda_beta",
                 "kda_out", "kda_states")

# bytes an element of each block of the two calls' layouts
_KDA_FORWARD_SIZES = (None, None, None, 4, 4, None, None)
_KDA_BACKWARD_SIZES = (None,) * 4 + (4, 4, None) + (None,) * 3 + (4, 4)


def kda_blocks(seq, chunk, d_k, d_v, itemsize=2):
    """``(block, C)`` of :func:`delta_blocks` where the KDA kernels' own
    working set (:func:`_kda_vmem_need` of the backward) fits too, else
    None."""
    from ..parallel import ring_attention as ra
    found = delta_blocks(seq, chunk, d_k, d_v, 1, itemsize)
    if found is None:
        return None
    block, C = found
    layout = _kda_layout(1, block, 1, d_k, d_v, block, C, backward=True)
    if _kda_vmem_need(layout, _KDA_BACKWARD_SIZES, itemsize, block, C, d_k,
                      d_v) > ra._VMEM_BUDGET:
        return None
    return found


def _kda_vmem_need(layout, sizes, itemsize, block, C, d_k, d_v):
    """:func:`_vmem_need`, and the sub-blocks' key factors of a tile, float32
    and in the compute dtype."""
    R = _pack(block // C, C) * C
    return _vmem_need(layout, sizes, itemsize, block, C, d_k, d_v, 1) \
        + (R // min(_DIAG, C)) * R * d_k * (4 + itemsize)


def _kda_layout(B, S, H, d_k, d_v, block, C, backward, emit_states=False):
    """(block, array, index map) rows of a KDA call.  Forward: q, k, v, Γ,
    β, then o (and the chunk states).  Backward, the blocks in reverse: q,
    k, v, do, Γ, β, states, then dq, dk, dv, dΓ, dβ."""
    N, nc, last = S // C, block // C, S // block - 1

    def at(i):
        return last - i if backward else i

    by_key = ((None, block, d_k), (B, S, H * d_k),
              lambda p, i: (p // H, at(i), p % H))
    by_value = ((None, block, d_v), (B, S, H * d_v),
                lambda p, i: (p // H, at(i), p % H))
    rows = ((None, None, nc, C), (B, H, N, C),
            lambda p, i: (p // H, p % H, at(i), 0))
    states = ((None, None, nc, d_k, d_v), (B, H, N, d_k, d_v),
              lambda p, i: (p // H, p % H, at(i), 0, 0))
    if backward:
        return ([by_key, by_key, by_value, by_value, by_key, rows, states],
                [by_key, by_key, by_value, by_key, rows])
    return ([by_key, by_key, by_value, by_key, rows],
            [by_value] + ([states] if emit_states else []))


def _kda_rows(b_ref, first, pack):
    """(1, R) row of β for the ``pack`` chunks from ``first``."""
    import jax.experimental.pallas as pl
    rows = b_ref[pl.ds(first, pack), :]
    return jnp.concatenate([rows[i:i + 1, :] for i in range(pack)], axis=1)


def _kda_locals(q, k, v, G, b_row, C):
    """Everything of a tile of R tokens that does not read the state (the
    text above), the tile's chunks side by side: q, k (R, d_k), v (R,
    d_v) in the compute dtype, Γ (R, d_k) and the β row (1, R) float32.
    Each sub-block b of n rows has its reference row's factors ``fac[b]``
    (R, d_k), e^{Γ_r − Γ} on the keys of its chunk up to its own last row
    and 0 elsewhere, and its rows of [β K; Q] ⊙ e^{Γ − Γ_r} (``kd``,
    ``qd``) meet K ⊙ fac[b] (``kc[b]``) in one product."""
    dt = v.dtype
    R, d_k = q.shape
    n = min(_DIAG, C)
    row, col = _indices(R)
    eye = row == col
    same = (row // C) == (col // C)
    beta = _to_col(b_row, eye)
    q32, k32 = q.astype(_F32), k.astype(_F32)
    key_row = lax.broadcasted_iota(jnp.int32, (R, d_k), 0)
    refs = [G[b * n:b * n + 1, :] for b in range(R // n)]
    e_in = jnp.exp(G - jnp.concatenate(
        [jnp.broadcast_to(r, (n, d_k)) for r in refs], axis=0))
    qd32, kd32 = q32 * e_in, k32 * beta * e_in
    qd, kd = qd32.astype(dt), kd32.astype(dt)
    fac, kc, a_rows, p_rows = [], [], [], []
    for b, r in enumerate(refs):
        seen = ((key_row // C) == (b * n) // C) & (key_row < (b + 1) * n)
        f = jnp.exp(jnp.where(seen, r - G, _NEG_INF))
        fac.append(f)
        kc.append((k32 * f).astype(dt))
        both = _dot(jnp.concatenate([kd[b * n:(b + 1) * n],
                                     qd[b * n:(b + 1) * n]], axis=0),
                    kc[b], (1, 1))                              # (2n, R)
        a_rows.append(both[:n])
        p_rows.append(both[n:])
    strict = (row > col) & same
    lower = (row >= col) & same
    a = jnp.where(strict, jnp.concatenate(a_rows, axis=0), 0.0)
    p32 = jnp.where(lower, jnp.concatenate(p_rows, axis=0), 0.0)
    t = _unit_lower_inverse(a, C).astype(dt)
    e_g = jnp.exp(G)
    kbg32 = k32 * beta * e_g
    kbg = kbg32.astype(dt)
    vb = (v.astype(_F32) * beta).astype(dt)
    wu = _dot(t, jnp.concatenate([kbg, vb], axis=1))            # (R, d_k+d_v)
    # Γ at each chunk's last token: a row a chunk, down the chunk's rows,
    # and as a column (d_k, 1) for the state's rows
    lasts = [jnp.sum(jnp.where(key_row == i * C + C - 1, G, 0.0), axis=0,
                     keepdims=True) for i in range(R // C)]
    f_last = jnp.exp(jnp.concatenate(
        [jnp.broadcast_to(x, (C, d_k)) for x in lasts], axis=0) - G)
    eye_k = _indices(d_k)
    eye_k = eye_k[0] == eye_k[1]
    kt32 = k32 * f_last
    return dict(
        eye=eye, eye_k=eye_k, strict=strict, lower=lower, beta=beta,
        e_in=e_in, qd=qd, kd=kd, qd32=qd32, kd32=kd32, fac=fac, kc=kc,
        t=t, e_g=e_g, kbg=kbg, kbg32=kbg32, vb=vb, f_last=f_last,
        e_last=[jnp.exp(_to_col(x, eye_k)) for x in lasts],
        w=wu[:, :d_k].astype(dt), u=wu[:, d_k:], p32=p32,
        qg32=q32 * e_g, qg=(q32 * e_g).astype(dt), kt32=kt32,
        kt=kt32.astype(dt))


def _kda_forward_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, *rest,
                        chunk):
    """Grid: (batch · heads, blocks), a head's blocks in order; ``rest``:
    the chunk states' block where the backward asked for them, then the
    float32 state.  The scalar forward's walk with :func:`_kda_locals`."""
    import jax.experimental.pallas as pl

    states_ref = rest[0] if len(rest) == 2 else None
    state = rest[-1]
    C = chunk
    n_chunks = q_ref.shape[0] // C
    pack = _pack(n_chunks, C)
    R = pack * C

    @pl.when(pl.program_id(1) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    def one_tile(tile, carry):
        rows = pl.ds(pl.multiple_of(tile * R, R), R)
        v = v_ref[rows, :]
        dt = v.dtype
        x = _kda_locals(q_ref[rows, :], k_ref[rows, :], v, g_ref[rows, :],
                        _kda_rows(b_ref, tile * pack, pack), C)
        p = x["p32"].astype(dt)
        outs = []
        for i in range(pack):
            at = slice(i * C, (i + 1) * C)
            s = state[...].astype(dt)
            if states_ref is not None:
                states_ref[tile * pack + i] = s
            ws = _dot(jnp.concatenate([x["w"][at], x["qg"][at]], axis=0), s)
            v_new = (x["u"][at] - ws[:C]).astype(dt)
            outs.append(ws[C:] + _dot(p[at, at], v_new))
            state[...] = state[...] * x["e_last"][i] \
                + _dot(x["kt"][at], v_new, (0, 0))
        o_ref[rows, :] = jnp.concatenate(outs, axis=0).astype(o_ref.dtype)
        return carry

    lax.fori_loop(0, n_chunks // pack, one_tile, 0)


@functools.partial(jax.jit, static_argnames=(
    "block", "chunk", "emit_states", "interpret"))
def _kda_forward_call(q, k, v, G, b, block, chunk, emit_states, interpret):
    """o (B, S, H, d_v), and with ``emit_states`` each chunk's starting
    state (B, H, N, d_k, d_v) in v's dtype."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, S, H, d_k = q.shape
    d_v = v.shape[-1]
    ins, outs = _kda_layout(B, S, H, d_k, d_v, block, chunk, False,
                            emit_states)
    need = _kda_vmem_need((ins, outs), _KDA_FORWARD_SIZES, v.dtype.itemsize,
                          block, chunk, d_k, d_v)
    res = pl.pallas_call(
        functools.partial(_kda_forward_kernel, chunk=chunk),
        grid=(B * H, S // block),
        in_specs=[pl.BlockSpec(blk, index) for blk, _arr, index in ins],
        out_specs=[pl.BlockSpec(blk, index) for blk, _arr, index in outs],
        out_shape=[jax.ShapeDtypeStruct(arr, v.dtype)
                   for _blk, arr, _index in outs],
        scratch_shapes=[pltpu.VMEM((d_k, d_v), _F32)],
        compiler_params=_compiler_params(need),
        name="kda_forward",
        interpret=interpret,
    )(q.reshape(B, S, H * d_k), k.reshape(B, S, H * d_k),
      v.reshape(B, S, H * d_v), G, b)
    o = res[0].reshape(B, S, H, d_v)
    return (o, res[1]) if emit_states else o


def _kda_backward_kernel(q_ref, k_ref, v_ref, do_ref, g_ref, b_ref,
                         states_ref, dq_ref, dk_ref, dv_ref, dg_ref, db_ref,
                         d_state, *, chunk):
    """Grid: (batch · heads, blocks), a head's blocks in reverse.  The
    scalar backward's chunk steps (its text), then the pair products and
    the factors per channel: a product P = X Yᵀ gives dX = dP Y and
    dY = dPᵀ X, and a factor e^{±Γ} sends ± its term to Γ."""
    import jax.experimental.pallas as pl

    C = chunk
    d_k = q_ref.shape[1]
    n_chunks = q_ref.shape[0] // C
    pack = _pack(n_chunks, C)
    R = pack * C
    n = min(_DIAG, C)
    lane = lax.broadcasted_iota(jnp.int32, (1, R), 1)
    key_row = lax.broadcasted_iota(jnp.int32, (R, d_k), 0)

    @pl.when(pl.program_id(1) == 0)
    def _():
        d_state[...] = jnp.zeros_like(d_state)

    def one_tile(step, carry):
        tile = n_chunks // pack - 1 - step
        rows = pl.ds(pl.multiple_of(tile * R, R), R)
        q, k, v, do = (r[rows, :] for r in (q_ref, k_ref, v_ref, do_ref))
        dt = q.dtype
        k32 = k.astype(_F32)
        x = _kda_locals(q, k, v, g_ref[rows, :],
                        _kda_rows(b_ref, tile * pack, pack), C)
        p = x["p32"].astype(dt)
        # the chunks against the state, the tile's last first
        pieces, to_last = [None] * pack, jnp.zeros((R, d_k), _F32)
        for i in reversed(range(pack)):
            at = slice(i * C, (i + 1) * C)
            s = states_ref[tile * pack + i]
            ds_next = d_state[...]
            ds_dt = ds_next.astype(dt)
            v_new = (x["u"][at] - _dot(x["w"][at], s)).astype(dt)
            dv_new = (_dot(p[at, at], do[at], (0, 0))
                      + _dot(x["kt"][at], ds_dt)).astype(dt)     # = dU
            d_state[...] = ds_next * x["e_last"][i] + _dot(
                jnp.concatenate([x["qg"][at], x["w"][at]], axis=0),
                jnp.concatenate([do[at], -dv_new], axis=0), (0, 0))
            from_s = _dot(jnp.concatenate([do[at], dv_new], axis=0), s,
                          (1, 1))                       # d(Q e^Γ); −dW
            dkt = _dot(v_new, ds_dt, (1, 1))
            pieces[i] = (v_new, dv_new, (-from_s[C:]).astype(dt),
                         from_s[:C], dkt)
            # Γ_C's own: through Diag(e^{Γ_C}) and through K e^{Γ_C − Γ}
            d_last = _to_row(x["e_last"][i] * jnp.sum(
                ds_next * s.astype(_F32), axis=1, keepdims=True),
                x["eye_k"]) + jnp.sum(dkt * x["kt32"][at], axis=0,
                                      keepdims=True)
            to_last = to_last + jnp.where(key_row == i * C + C - 1, d_last,
                                          0.0)
        v_new, dv_new, dw, dqg, dkt = (
            jnp.concatenate(t, axis=0) for t in zip(*pieces))

        # the tile's chunks side by side again
        dp = jnp.where(x["lower"], _dot(do, v_new, (1, 1)), 0.0)
        dwu = jnp.concatenate([dw, dv_new], axis=1)
        d_t = _dot(dwu, jnp.concatenate([x["kbg"], x["vb"]], axis=1),
                   (1, 1)).astype(dt)
        from_t = _dot(x["t"], dwu, (0, 0))          # d(β K e^Γ) | d(β V)
        dkbg, dvb = from_t[:, :d_k], from_t[:, d_k:]
        da = -_dot(_dot(x["t"], d_t, (0, 0)).astype(dt), x["t"], (1, 1))
        da = jnp.where(x["strict"], da, 0.0).astype(dt)
        dp = dp.astype(dt)

        # the pair products, a sub-block at a time
        d_rows, d_cols = [], jnp.zeros((R, d_k), _F32)
        for b in range(R // n):
            mine = slice(b * n, (b + 1) * n)
            left = jnp.concatenate([da[mine], dp[mine]], axis=0)  # (2n, R)
            d_rows.append(_dot(left, x["kc"][b]))
            d_cols = d_cols + x["fac"][b] * _dot(
                left, jnp.concatenate([x["kd"][mine], x["qd"][mine]],
                                      axis=0), (0, 0))
        dkd = jnp.concatenate([r[:n] for r in d_rows], axis=0)
        dqd = jnp.concatenate([r[n:] for r in d_rows], axis=0)

        dq = dqd * x["e_in"] + dqg * x["e_g"]
        dk = x["beta"] * (dkd * x["e_in"] + dkbg * x["e_g"]) + d_cols \
            + dkt * x["f_last"]
        dg = dqd * x["qd32"] + dkd * x["kd32"] - d_cols * k32 \
            + dqg * x["qg32"] + dkbg * x["kbg32"] - dkt * x["kt32"] \
            + to_last
        d_beta = _to_row(
            jnp.sum(k32 * (dkd * x["e_in"] + dkbg * x["e_g"]), axis=1,
                    keepdims=True)
            + jnp.sum(dvb * v.astype(_F32), axis=1, keepdims=True),
            x["eye"])
        dq_ref[rows, :] = dq.astype(dq_ref.dtype)
        dk_ref[rows, :] = dk.astype(dk_ref.dtype)
        dv_ref[rows, :] = (dvb * x["beta"]).astype(dv_ref.dtype)
        dg_ref[rows, :] = dg
        for i in range(pack):
            db_ref[pl.ds(tile * pack + i, 1), :] = \
                d_beta[:, i * C:(i + 1) * C]
        return carry

    lax.fori_loop(0, n_chunks // pack, one_tile, 0)


@functools.partial(jax.jit, static_argnames=("block", "chunk", "interpret"))
def _kda_backward_call(q, k, v, G, b, states, do, block, chunk, interpret):
    """(dq, dk, dv, dΓ, dβ) from the operands, the chunk states of the
    forward sweep and the output's cotangent."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, S, H, d_k = q.shape
    d_v = v.shape[-1]
    ins, outs = _kda_layout(B, S, H, d_k, d_v, block, chunk, True)
    need = _kda_vmem_need((ins, outs), _KDA_BACKWARD_SIZES,
                          v.dtype.itemsize, block, chunk, d_k, d_v)
    dq, dk, dv, dG, db = pl.pallas_call(
        functools.partial(_kda_backward_kernel, chunk=chunk),
        grid=(B * H, S // block),
        in_specs=[pl.BlockSpec(blk, index) for blk, _arr, index in ins],
        out_specs=[pl.BlockSpec(blk, index) for blk, _arr, index in outs],
        out_shape=[jax.ShapeDtypeStruct(arr, dtype) for (_blk, arr, _index),
                   dtype in zip(outs, (q.dtype, k.dtype, v.dtype, _F32,
                                       _F32))],
        scratch_shapes=[pltpu.VMEM((d_k, d_v), _F32)],
        compiler_params=_compiler_params(need),
        name="kda_backward",
        interpret=interpret,
    )(q.reshape(B, S, H * d_k), k.reshape(B, S, H * d_k),
      v.reshape(B, S, H * d_v), do.reshape(B, S, H * d_v), G, b, states)
    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape),
            dG, db)


def kda_rule_kernel(q, k, v, g, beta, block, chunk, interpret=False):
    """The KDA rule through the kernels.  q, k (B, S, H, d_k), v (B, S,
    H, d_v) in the compute dtype; g (B, S, H, d_k) float32, each ≥ −5 (the
    text above); beta (B, S, H); ``(block, chunk)`` from
    :func:`kda_blocks`.  -> o (B, S, H, d_v)."""

    @jax.custom_vjp
    def rule(q, k, v, G, b):
        return _kda_forward_call(q, k, v, G, b, block, chunk, False,
                                 interpret)

    def rule_fwd(q, k, v, G, b):
        o, states = _kda_forward_call(q, k, v, G, b, block, chunk, True,
                                      interpret)
        q, k, v, G, b, o, states = (
            checkpoint_name(x, name) for x, name in zip(
                (q, k, v, G, b, o, states), KDA_RESIDUALS))
        return o, (q, k, v, G, b, states)

    def rule_bwd(res, do):
        q, k, v, G, b, states = res
        return _kda_backward_call(q, k, v, G, b, states, do.astype(v.dtype),
                                  block, chunk, interpret)

    rule.defvjp(rule_fwd, rule_bwd)
    # the running sum and β's chunk rows are plain jnp, outside the
    # custom_vjp: jax differentiates them
    with jax.named_scope("kda_rule"):
        B, S, H, d_k = g.shape
        G = jnp.cumsum(g.astype(_F32).reshape(B, S // chunk, chunk, H, d_k),
                       axis=2).reshape(B, S, H * d_k)
        b = jnp.moveaxis(beta.astype(_F32), 1, 2).reshape(B, H, S // chunk,
                                                          chunk)
        return rule(q, k, v, G, b)


# ----------------------------------------------------------------------
# MXL-K kernel specs (analysis/tiling.py)
# ----------------------------------------------------------------------
def _kernel_spec(name, grid, rows, names, dtypes):
    return {"name": name, "origin": "mxnet_tpu/kernels/delta_rule.py",
            "grid": grid,
            "blocks": [{"role": role, "name": n, "block": blk, "array": arr,
                        "dtype": dt}
                       for (role, (blk, arr, _index)), n, dt
                       in zip(rows, names, dtypes)]}


def gated_delta_forward_kernel_spec(batch=1, seq=8192, key_heads=16,
                                    value_heads=32, d_k=128, d_v=128,
                                    chunk=64, dtype="bfloat16"):
    """MXL-K spec of the forward call that also writes the chunk states,
    from the :func:`_forward_layout` the call uses, at the timed shape."""
    block, C = delta_blocks(seq, chunk, d_k, d_v, value_heads // key_heads)
    ins, outs = _forward_layout(batch, seq, key_heads, value_heads, d_k,
                                d_v, block, C, True)
    return _kernel_spec(
        "gated_delta_forward", (batch * value_heads, seq // block),
        [("in", b) for b in ins] + [("out", b) for b in outs],
        ("q", "k", "v", "scalars", "o", "states"),
        (dtype,) * 3 + ("float32", dtype, dtype))


def gated_delta_backward_kernel_spec(batch=1, seq=8192, key_heads=16,
                                     value_heads=32, d_k=128, d_v=128,
                                     chunk=64, dtype="bfloat16"):
    """MXL-K spec of the backward call, from :func:`_backward_layout`."""
    block, C = delta_blocks(seq, chunk, d_k, d_v, value_heads // key_heads)
    ins, outs = _backward_layout(batch, seq, key_heads, value_heads, d_k,
                                 d_v, block, C)
    return _kernel_spec(
        "gated_delta_backward", (batch * key_heads, seq // block),
        [("in", b) for b in ins] + [("out", b) for b in outs],
        ("q", "k", "v", "do", "scalars", "states", "dq", "dk", "dv",
         "dscalars"),
        (dtype,) * 4 + ("float32", dtype) + (dtype,) * 3 + ("float32",))


try:
    from ..analysis.tiling import register_kernel_spec as _register_spec
    _register_spec("kernels.delta_rule.gated_delta_forward",
                   gated_delta_forward_kernel_spec)
    _register_spec("kernels.delta_rule.gated_delta_backward",
                   gated_delta_backward_kernel_spec)
except Exception:            # analysis package optional at import time
    pass
