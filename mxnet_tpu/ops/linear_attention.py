"""Linear attention with a decayed state: the gated delta rule.

``GatedDeltaNet`` is the token mixer of hybrid decoders that keep one
softmax-attention layer in a few and mix the other layers' tokens through a
fixed-size state (Gated DeltaNet, arXiv:2412.06464; the ``qwen3_next``
layer of the ``transformers`` library).  Per value head, with a state S
(d_k × d_v) that starts at 0:

    S ← exp(g_t) · S;   δ_t = β_t · (v_t − Sᵀ k_t);   S ← S + k_t δ_tᵀ;
    o_t = Sᵀ q_t

:func:`gated_delta_rule_recurrent` is that recurrence, one token at a time
(the definition; tests and ``chip_smoke.py`` hold the fast forms to it).
:func:`gated_delta_rule` is its chunk-parallel form, the one the op runs:
within a chunk of C tokens every δ depends on the δ before it through a
unit lower-triangular system, which is solved for a chunk at once (the
WY form: with G the running sum of g inside the chunk and
A = tril(β K Kᵀ ⊙ exp(G_i − G_j), −1), T = (I + A)⁻¹, W = T (β e^G K),
U = T β V), and S is carried from chunk to chunk:

    V' = U − W S;   O = (Q e^G) S + tril(Q Kᵀ ⊙ exp(G_i − G_j)) V';
    S ← e^{G_C} S + (K e^{G_C − G})ᵀ V'

Decays (g, G, every exp) and the state are float32; the products take
their operands in the dtype q, k, v come in and sum in float32; T is
found in float32 (:func:`unit_lower_inverse`: block substitution, matrix
products only).  No exp ever takes a positive argument: the factors are
exp(G_i − G_j) for i ≥ j, e^G and e^{G_C − G}, all at most 1, whatever the
decay.

Which form runs where.  One algorithm, two implementations, chosen by
``kernels.common.dispatch`` when the enclosing step is lowered, as
``flash_attention`` is: **where the step is placed on a TPU** and the
shapes allow (``kernels.delta_rule.delta_blocks``: whole blocks of chunks,
a chunk a power of two, head widths of whole registers, a working set that
VMEM holds), two Pallas kernels
(``kernels/delta_rule.py``: ``gated_delta_forward``, and by
``jax.custom_vjp`` ``gated_delta_backward``) that keep a chunk's working
set in VMEM and read q and k of the key heads as they are; **everywhere
else** (the CPU, the tests, a shape the kernels refuse)
:func:`gated_delta_rule_xla`: plain ``jax.numpy`` for all chunks at once
and one ``lax.scan`` over the chunks inside the one XLA step,
differentiated by ``jax``'s autodiff of the scan — the kernels' reference,
with the same rounding points (but one, in dg: ``kernels/delta_rule.py``
names it).  On a mesh of several chips (the step traced under
``parallel.ring_attention.attention_scope``) that choice is made per device
under ``shard_map``, the batch split over dp and the key heads over tp,
because GSPMD does not partition a Mosaic kernel; where the mesh shards the
sequence itself the state would cross devices, and the XLA form runs,
partitioned by GSPMD.

``KimiDeltaAttention`` (Kimi delta attention, arXiv:2510.26692) decays the
state by one number a key channel, S ← Diag(e^{g_t}) S, g_t (d_k) bounded
below: :func:`kda_rule_recurrent` is its definition, :func:`kda_rule` its
chunk-parallel form, placed the same way (``kda_forward`` /
``kda_backward``, or :func:`kda_rule_xla`); ``kernels/delta_rule.py`` has
its equations and why the bound keeps every exponent finite.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..base import MXNetError
from ..dparam import Field, ParamStruct
from .attention import (KERNEL, PROJ_IN, PROJ_OUT, ROTARY_NORM, rms_norm,
                        shift_tokens)
from .registry import OperatorProperty, register_op, require_known

_HIGHEST = lax.Precision.HIGHEST


def gated_delta_rule_recurrent(q, k, v, g, beta):
    """The recurrence itself.  q, k (B, S, H, d_k), v (B, S, H, d_v), g and
    beta (B, S, H); q and k as the rule takes them (normalised and scaled
    by the caller).  -> o (B, S, H, d_v), all in float32."""
    q, k, v, g, beta = (t.astype(jnp.float32) for t in (q, k, v, g, beta))
    B, _S, H, d_k = q.shape

    def token(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = state * jnp.exp(g_t)[..., None, None]
        mem = jnp.einsum("bhkv,bhk->bhv", state, k_t, precision=_HIGHEST)
        delta = (v_t - mem) * b_t[..., None]
        state = state + k_t[..., :, None] * delta[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t,
                                 precision=_HIGHEST)

    state = jnp.zeros((B, H, d_k, v.shape[-1]), jnp.float32)
    _, o = lax.scan(token, state, tuple(
        jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def unit_lower_inverse(a):
    """(I + tril(a, −1))⁻¹ of float32 ``a`` (..., C, C), C a power of two
    (only what lies below the diagonal is read), by block substitution:
    the inverse of a unit lower-triangular [[L₁, 0], [X, L₂]] is
    [[L₁⁻¹, 0], [−L₂⁻¹ X L₁⁻¹, L₂⁻¹]], from 1 × 1 blocks up — log₂ C levels of small matrix products, as stable as
    forward substitution (a Neumann series of the nilpotent part is not:
    its powers grow by binomials where keys repeat)."""
    C = a.shape[-1]
    if C & (C - 1):
        raise ValueError("a chunk of %d tokens is no power of two" % C)
    lead = a.shape[:-2]
    inv = jnp.ones(lead + (C, 1, 1), jnp.float32)       # the 1 × 1 blocks
    n = 1
    while n < C:
        m = C // (2 * n)
        # block (i, 1; j, 0) of the 2n-blocks; the diagonal i = j picked by
        # a mask and a sum, which is exact (no product through the MXU)
        low = a.reshape(lead + (m, 2, n, m, 2, n))[..., :, 1, :, :, 0, :]
        eye = jnp.eye(m, dtype=a.dtype)[:, None, :, None]
        low = jnp.sum(low * eye, axis=-2)               # (..., m, n, n)
        first, second = inv[..., 0::2, :, :], inv[..., 1::2, :, :]
        cross = -jnp.matmul(jnp.matmul(second, low, precision=_HIGHEST),
                            first, precision=_HIGHEST)
        inv = jnp.concatenate([
            jnp.concatenate([first, jnp.zeros_like(first)], axis=-1),
            jnp.concatenate([cross, second], axis=-1)], axis=-2)
        n *= 2
    return inv[..., 0, :, :]


def gated_delta_rule(q, k, v, g, beta, chunk=64, interpret=None):
    """The chunk-parallel form of :func:`gated_delta_rule_recurrent` (the
    module's text has the equations).  q, k (B, S, H_k, d_k), v (B, S, H,
    d_v) in the compute dtype, H a multiple of H_k (value head h reads key
    head h // (H / H_k)); g and beta (B, S, H) float32; S a multiple of
    ``chunk`` or shorter than it (then one chunk).  -> o (B, S, H, d_v) in
    v's dtype.

    The Pallas kernels (``kernels/delta_rule.py``) where the computation is
    placed on a TPU and they take the shapes (``delta_blocks``), the XLA
    form below anywhere else (``kernels.common.dispatch``, as
    ``flash_attention`` decides).  An explicit ``interpret`` runs the
    kernels either way: ``True`` through the Pallas interpreter (tests),
    ``False`` through Mosaic.

    Traced for a mesh of several devices (``current_sequence_parallel``, as
    ``sharded_self_attention`` reads it) the choice is made per device under
    ``shard_map``, the batch split over dp and the key heads over tp where
    those divide — GSPMD cannot partition a Mosaic kernel.  Where the mesh
    shards the sequence the state would cross devices: the XLA form, which
    GSPMD partitions."""
    from ..kernels import delta_rule
    if v.shape[2] % q.shape[2]:
        raise ValueError("%d value heads do not group over %d key heads"
                         % (v.shape[2], q.shape[2]))

    def reference(q, k, v, g, beta):
        return gated_delta_rule_xla(q, k, v, g, beta, chunk)

    blocks = delta_rule.delta_blocks(
        q.shape[1], chunk, q.shape[-1], v.shape[-1],
        v.shape[2] // q.shape[2], v.dtype.itemsize)
    return _placed(delta_rule.gated_delta_rule_kernel, reference, blocks,
                   interpret, q, k, v, g, beta)


def _placed(kernel, reference, blocks, interpret, q, k, v, g, beta):
    """A rule's kernels (``kernel(q, k, v, g, beta, *blocks, interpret)``)
    or its XLA form (``reference``), chosen as :func:`gated_delta_rule`'s
    text says: the XLA form where ``blocks`` is None or the mesh shards
    the sequence; else per device under ``shard_map`` on a mesh, the batch
    split over dp and the key heads over tp where those divide — a device
    keeps whole groups: the value heads split as the key heads do, and g
    as they do, whether a number a head or a vector."""
    from ..kernels.common import dispatch
    from ..parallel.ring_attention import (current_sequence_parallel,
                                           mesh_axis_that_splits)
    ctx = current_sequence_parallel()
    if blocks is None or (ctx is not None
                          and ctx.seq_axis in ctx.mesh.axis_names):
        return reference(q, k, v, g, beta)

    def placed(q, k, v, g, beta, interpret=False):
        return kernel(q, k, v, g, beta, *blocks, interpret=interpret)

    def rule(q, k, v, g, beta):
        if interpret is not None:
            return placed(q, k, v, g, beta, interpret=bool(interpret))
        return dispatch(placed, reference, q, k, v, g, beta)

    if ctx is None:
        return rule(q, k, v, g, beta)
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    dp = mesh_axis_that_splits(ctx.mesh, ctx.batch_axis, q.shape[0])
    tp = mesh_axis_that_splits(ctx.mesh, "tp", q.shape[2])
    heads, scalars = P(dp, None, tp, None), P(dp, None, tp)
    return shard_map(rule, mesh=ctx.mesh,
                     in_specs=(heads,) * 3 + (heads if g.ndim == 4
                                              else scalars, scalars),
                     out_specs=heads, check_vma=False)(q, k, v, g, beta)


def gated_delta_rule_xla(q, k, v, g, beta, chunk=64):
    """:func:`gated_delta_rule` as plain ``jax.numpy`` and one ``lax.scan``,
    differentiated by ``jax``: the kernels' reference, and what runs off a
    TPU.  q and k of fewer heads than v are repeated here and nowhere
    else."""
    r = v.shape[2] // q.shape[2]
    if r > 1:
        q, k = (jnp.repeat(t, r, axis=2) for t in (q, k))
    B, S, H, d_k = q.shape
    d_v = v.shape[-1]
    C = min(int(chunk), S)
    if S % C:
        raise ValueError("%d tokens are no whole number of chunks of %d"
                         % (S, C))
    N, dt, f32 = S // C, v.dtype, jnp.float32

    def chunks(t):      # (B, S, H, ...) -> (B, H, N, C, ...)
        t = t.reshape((B, N, C, H) + t.shape[3:])
        return jnp.moveaxis(t, 3, 1)

    def dot(a, b, spec):
        return jnp.einsum(spec, a, b, preferred_element_type=f32)

    with jax.named_scope("gated_delta_rule"):
        q, k, v = chunks(q), chunks(k), chunks(v)
        beta = chunks(beta.astype(f32))
        G = jnp.cumsum(chunks(g.astype(f32)), axis=-1)  # (B, H, N, C)
        # exp(G_i − G_j) where i ≥ j, 0 above the diagonal; the argument is
        # masked, not the result: above the diagonal it is positive
        row = jnp.arange(C)
        decay = jnp.exp(jnp.where(row[:, None] >= row[None, :],
                                  G[..., :, None] - G[..., None, :], -1e30))
        e_g = jnp.exp(G)[..., None]
        k_beta = (k.astype(f32) * beta[..., None]).astype(dt)
        a = dot(k_beta, k, "bhnid,bhnjd->bhnij") * decay
        t_inv = unit_lower_inverse(a).astype(dt)
        w = dot(t_inv, (k_beta.astype(f32) * e_g).astype(dt),
                "bhnij,bhnjd->bhnid").astype(dt)
        u = dot(t_inv, (v.astype(f32) * beta[..., None]).astype(dt),
                "bhnij,bhnjd->bhnid")
        qk = (dot(q, k, "bhnid,bhnjd->bhnij") * decay).astype(dt)
        q_in = (q.astype(f32) * e_g).astype(dt)
        last = G[..., -1:]                              # (B, H, N, 1)
        k_out = (k.astype(f32) * jnp.exp(last - G)[..., None]).astype(dt)

        def one_chunk(state, x):
            w_c, u_c, qk_c, q_c, k_c, decay_c = x
            s = state.astype(dt)
            v_new = (u_c - dot(w_c, s, "bhik,bhkv->bhiv")).astype(dt)
            o = dot(q_c, s, "bhik,bhkv->bhiv") \
                + dot(qk_c, v_new, "bhij,bhjv->bhiv")
            state = state * decay_c[..., None] \
                + dot(k_c, v_new, "bhik,bhiv->bhkv")
            return state, o.astype(dt)

        state = jnp.zeros((B, H, d_k, d_v), f32)
        _, o = lax.scan(one_chunk, state, tuple(
            jnp.moveaxis(t, 2, 0)
            for t in (w, u, qk, q_in, k_out, jnp.exp(last))))
        # (N, B, H, C, d_v) -> (B, S, H, d_v)
        return jnp.moveaxis(o, (0, 2), (1, 3)).reshape(B, S, H, d_v)


def kda_rule_recurrent(q, k, v, g, beta):
    """Kimi delta attention's recurrence itself: the gated delta rule with
    a decay a key channel.  q, k (B, S, H, d_k), v (B, S, H, d_v), g (B,
    S, H, d_k), beta (B, S, H).  Per head, from S = 0:

        S ← Diag(e^{g_t}) S;  δ_t = β_t (v_t − Sᵀ k_t);  S ← S + k_t δ_tᵀ;
        o_t = Sᵀ q_t

    -> o (B, S, H, d_v), all in float32."""
    q, k, v, g, beta = (t.astype(jnp.float32) for t in (q, k, v, g, beta))
    B, _S, H, d_k = q.shape

    def token(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = state * jnp.exp(g_t)[..., :, None]
        mem = jnp.einsum("bhkv,bhk->bhv", state, k_t, precision=_HIGHEST)
        delta = (v_t - mem) * b_t[..., None]
        state = state + k_t[..., :, None] * delta[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t,
                                 precision=_HIGHEST)

    state = jnp.zeros((B, H, d_k, v.shape[-1]), jnp.float32)
    _, o = lax.scan(token, state, tuple(
        jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def kda_rule(q, k, v, g, beta, chunk=64, interpret=None):
    """The chunk-parallel form of :func:`kda_rule_recurrent`.  q, k (B, S,
    H, d_k), v (B, S, H, d_v) in the compute dtype; g (B, S, H, d_k)
    float32, bounded below (``kernels/delta_rule.py`` says by what and
    why); beta (B, S, H) float32.  -> o (B, S, H, d_v) in v's dtype.

    Placed as :func:`gated_delta_rule` places the scalar rule: the Pallas
    kernels ``kda_forward`` / ``kda_backward`` where the step is placed on
    a TPU and they take the shapes (``kernels.delta_rule.kda_blocks``),
    per device under ``shard_map`` on a mesh; :func:`kda_rule_xla`
    anywhere else and where the mesh shards the sequence."""
    from ..kernels import delta_rule

    def reference(q, k, v, g, beta):
        return kda_rule_xla(q, k, v, g, beta, chunk)

    blocks = delta_rule.kda_blocks(q.shape[1], chunk, q.shape[-1],
                                   v.shape[-1], v.dtype.itemsize)
    return _placed(delta_rule.kda_rule_kernel, reference, blocks, interpret,
                   q, k, v, g, beta)


def kda_rule_xla(q, k, v, g, beta, chunk=64):
    """:func:`kda_rule` as plain ``jax.numpy`` and one ``lax.scan`` over
    the chunks, differentiated by ``jax``: the kernels' reference, and
    what runs off a TPU.  A chunk's pair sums carry e^{Γ_i − Γ_j} a channel
    as a (C, C, d_k) array, its argument masked above the diagonal, and
    are taken in float32; the rest rounds where :func:`gated_delta_rule_xla`
    does."""
    B, S, H, d_k = q.shape
    d_v = v.shape[-1]
    C = min(int(chunk), S)
    if S % C:
        raise ValueError("%d tokens are no whole number of chunks of %d"
                         % (S, C))
    N, dt, f32 = S // C, v.dtype, jnp.float32

    def chunks(t):      # (B, S, H, ...) -> (N, B, H, C, ...)
        t = t.reshape((B, N, C, H) + t.shape[3:])
        return jnp.moveaxis(jnp.moveaxis(t, 3, 2), 1, 0)

    def dot(a, b, spec):
        return jnp.einsum(spec, a, b, preferred_element_type=f32)

    row = jnp.arange(C)
    lower = row[:, None] >= row[None, :]

    def one_chunk(state, x):
        q_c, k_c, v_c, G, b = x
        q32, k32 = q_c.astype(f32), k_c.astype(f32)
        decay = jnp.exp(jnp.where(lower[..., None],
                                  G[..., :, None, :] - G[..., None, :, :],
                                  -1e30))                # (B, H, C, C, d_k)

        def pairs(x32):
            return jnp.einsum("bhic,bhjc,bhijc->bhij", x32, k32, decay,
                              precision=_HIGHEST)

        a = jnp.where(row[:, None] > row[None, :],
                      pairs(k32 * b[..., None]), 0.0)
        t_inv = unit_lower_inverse(a).astype(dt)
        e_g = jnp.exp(G)
        w = dot(t_inv, (k32 * b[..., None] * e_g).astype(dt),
                "bhij,bhjd->bhid").astype(dt)
        u = dot(t_inv, (v_c.astype(f32) * b[..., None]).astype(dt),
                "bhij,bhjd->bhid")
        qk = jnp.where(lower, pairs(q32), 0.0).astype(dt)
        last = G[..., -1:, :]                            # (B, H, 1, d_k)
        k_out = (k32 * jnp.exp(last - G)).astype(dt)
        s = state.astype(dt)
        v_new = (u - dot(w, s, "bhik,bhkv->bhiv")).astype(dt)
        o = dot((q32 * e_g).astype(dt), s, "bhik,bhkv->bhiv") \
            + dot(qk, v_new, "bhij,bhjv->bhiv")
        state = state * jnp.exp(last)[..., 0, :, None] \
            + dot(k_out, v_new, "bhik,bhiv->bhkv")
        return state, o.astype(dt)

    with jax.named_scope("kda_rule"):
        G = jnp.cumsum(chunks(g.astype(f32)), axis=3)  # (N, B, H, C, d_k)
        state = jnp.zeros((B, H, d_k, d_v), f32)
        _, o = lax.scan(one_chunk, state, (
            chunks(q), chunks(k), chunks(v), G, chunks(beta.astype(f32))))
        # (N, B, H, C, d_v) -> (B, S, H, d_v)
        return jnp.moveaxis(o, (0, 2), (1, 3)).reshape(B, S, H, d_v)


def l2_normalise(x, eps=1e-6):
    """x · rsqrt(Σx² + eps) over the last axis, in float32."""
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                         + eps)


def causal_depthwise_conv(z, w):
    """A depthwise causal convolution over the sequence of z (B, S, D) with
    ``w`` (taps, D): the last tap reads the current token, the one before
    it the previous token; positions before the first read zeros.  No
    bias.  The sum in float32, z's dtype out."""
    taps = w.shape[0]
    w32, z32 = w.astype(jnp.float32), z.astype(jnp.float32)
    return sum(shift_tokens(z32, taps - 1 - j) * w32[j]
               for j in range(taps)).astype(z.dtype)


class _GatedDeltaNetParam(ParamStruct):
    num_key_heads = Field(int, required=True, lower=1)
    num_value_heads = Field(int, required=True, lower=1,
                            doc="a multiple of num_key_heads: each key "
                                "head serves that many value heads")
    key_head_dim = Field(int, required=True, lower=1)
    value_head_dim = Field(int, required=True, lower=1)
    conv_taps = Field(int, default=4, lower=1,
                      doc="taps of the causal depthwise convolution")
    chunk = Field(int, default=64, lower=1,
                  doc="tokens a chunk of the chunk-parallel rule (a power "
                      "of two; the program's choice, not a width)")
    eps = Field(float, default=1e-6, doc="of the gated output norm")


@register_op("GatedDeltaNet")
class GatedDeltaNet(OperatorProperty):
    """Gated delta rule token mixer, data (B, S, E) -> (B, S, E).

    H_k = ``num_key_heads`` of d_k, H_v = ``num_value_heads`` of d_v,
    r = H_v / H_k value heads a key head.

    1. [q, k, v, z] = u W_qkvzᵀ laid out a key head: q (d_k), k (d_k),
       v (r·d_v), z (r·d_v);  [b, a] = u W_baᵀ a key head: b (r), a (r).
    2. [q ‖ k ‖ v] (2·H_k·d_k + H_v·d_v channels) through a causal
       depthwise convolution of ``conv_taps`` taps, no bias, then SiLU.
    3. q and k L2-normalised a head (:func:`l2_normalise`), q times
       d_k^−½; value head j reads key head j // r.
    4. β = sigmoid(b),  g = −exp(A_log) · softplus(a + dt_bias) a value
       head, in float32; o = :func:`gated_delta_rule`.
    5. y = (RMSNorm(o) ⊙ silu(z)) W_outᵀ, the norm over a head's d_v
       channels with one gain vector (``norm_gamma``, (d_v,)) for all
       heads.

    No biases; weights are (out_features, in_features)."""
    param_cls = _GatedDeltaNetParam
    mxu = True

    def list_arguments(self):
        return ["data", "in_proj_qkvz_weight", "in_proj_ba_weight",
                "conv_weight", "A_log", "dt_bias", "norm_gamma",
                "out_weight"]

    def _dims(self):
        p = self.param
        if p.num_value_heads % p.num_key_heads:
            raise MXNetError("GatedDeltaNet: %d value heads on %d key heads"
                             % (p.num_value_heads, p.num_key_heads))
        return (p.num_key_heads, p.num_value_heads, p.key_head_dim,
                p.value_head_dim)

    def infer_shape(self, in_shapes):
        data = in_shapes[0]
        if data is None:
            require_known("GatedDeltaNet", in_shapes[:1], ["data"])
        if len(data) != 3:
            raise MXNetError("GatedDeltaNet: data must be (B, S, E)")
        E = data[2]
        hk, hv, dk, dv = self._dims()
        return ([data, (2 * hk * dk + 2 * hv * dv, E), (2 * hv, E),
                 (self.param.conv_taps, 2 * hk * dk + hv * dv), (hv,),
                 (hv,), (dv,), (E, hv * dv)], [data], [])

    def cost_mxu_dims(self, in_shapes, out_shapes):
        B, S, E = in_shapes[0]
        hk, hv, dk, dv = self._dims()
        T = B * S
        return [(T, E, 2 * hk * dk + 2 * hv * dv), (T, E, 2 * hv),
                (T, hv * dv, E), (T * hv, dk, dv), (T * hv, dk, dv),
                (T * hv, dk, dv)]

    def cost_flops(self, in_shapes, out_shapes):
        # the projections, and the recurrence's own operations a token and
        # value head: Sᵀk, the rank-one update, Sᵀq
        return float(sum(2 * m * k * n for m, k, n in
                         self.cost_mxu_dims(in_shapes, out_shapes)))

    def forward(self, inputs, aux, is_train, rng):
        x, w_qkvz, w_ba, w_conv, a_log, dt_bias, gamma, w_out = inputs
        B, S, _E = x.shape
        hk, hv, dk, dv = self._dims()
        r = hv // hk
        with jax.named_scope(PROJ_IN):
            qkvz = (x @ w_qkvz.T).reshape(B, S, hk, 2 * dk + 2 * r * dv)
            ba = (x @ w_ba.T).reshape(B, S, hk, 2 * r)
        # this mixer's counterpart of rotary and q-k norm: the convolution,
        # the L2 normalisation, the decay and β
        with jax.named_scope(ROTARY_NORM):
            q, k, v, z = jnp.split(qkvz, [dk, 2 * dk, 2 * dk + r * dv],
                                   axis=-1)
            mixed = jnp.concatenate([t.reshape(B, S, -1)
                                     for t in (q, k, v)], axis=-1)
            mixed = jax.nn.silu(causal_depthwise_conv(mixed, w_conv))
            q, k, v = jnp.split(mixed, [hk * dk, 2 * hk * dk], axis=-1)
            q = l2_normalise(q.reshape(B, S, hk, dk)) * dk ** -0.5
            k = l2_normalise(k.reshape(B, S, hk, dk))
            q, k = q.astype(x.dtype), k.astype(x.dtype)
            b = ba[..., :r].reshape(B, S, hv).astype(jnp.float32)
            a = ba[..., r:].reshape(B, S, hv).astype(jnp.float32)
            g = -jnp.exp(a_log.astype(jnp.float32)) \
                * jax.nn.softplus(a + dt_bias.astype(jnp.float32))
            v = v.reshape(B, S, hv, dv)
            beta = jax.nn.sigmoid(b)
        with jax.named_scope(KERNEL):
            o = gated_delta_rule(q, k, v, g, beta, self.param.chunk)
        with jax.named_scope(PROJ_OUT):
            o = rms_norm(o, gamma, self.param.eps)
            o = (o.astype(jnp.float32) * jax.nn.silu(
                z.reshape(B, S, hv, dv).astype(jnp.float32))).astype(x.dtype)
            return [o.reshape(B, S, hv * dv) @ w_out.T], None


class _KimiDeltaAttentionParam(ParamStruct):
    num_heads = Field(int, required=True, lower=1,
                      doc="heads of q, k and v alike")
    head_dim = Field(int, required=True, lower=1,
                     doc="width of a head of q, k and v")
    conv_taps = Field(int, default=4, lower=1,
                      doc="taps of the causal depthwise convolutions")
    gate_lower_bound = Field(
        float, default=-5.0, lower=-5.0, upper=-1e-3,
        doc="g = bound · sigmoid(·): the least log-decay a channel takes "
            "a token; at least −5, which keeps the kernels' exponents "
            "finite (kernels/delta_rule.py)")
    chunk = Field(int, default=64, lower=1,
                  doc="tokens a chunk of the chunk-parallel rule (a power "
                      "of two; the program's choice, not a width)")
    eps = Field(float, default=1e-6, doc="of the output norm")


@register_op("KimiDeltaAttention")
class KimiDeltaAttention(OperatorProperty):
    """Kimi delta attention token mixer (arXiv:2510.26692), data (B, S, E)
    -> (B, S, E): the gated delta rule with a decay a key channel.

    H = ``num_heads`` heads of d = ``head_dim`` for q, k and v alike.

    1. q, k, v = u W_qᵀ, u W_kᵀ, u W_vᵀ (E → H·d each), each through its
       own causal depthwise convolution of ``conv_taps`` taps, no bias,
       then SiLU.
    2. q and k L2-normalised a head (:func:`l2_normalise`), q times d^−½.
    3. The decay a channel, bounded (the safe gate):
       g = ``gate_lower_bound`` · sigmoid(exp(A_log) ⊙ (u W_fᵀ + dt_bias)),
       W_f full rank (E → H·d), ``A_log`` a head, ``dt_bias`` a channel,
       in float32;  β = sigmoid(u W_bᵀ) a head;  o = :func:`kda_rule`.
    4. y = (RMSNorm(o) ⊙ sigmoid(u W_gᵀ)) W_outᵀ: the norm over a head's d
       channels with one gain vector (``norm_gamma``, (d,)) for all heads,
       the gate a channel (W_g full rank, E → H·d).

    No biases; weights are (out_features, in_features)."""
    param_cls = _KimiDeltaAttentionParam
    mxu = True

    def list_arguments(self):
        return ["data", "q_weight", "k_weight", "v_weight", "q_conv_weight",
                "k_conv_weight", "v_conv_weight", "f_weight", "A_log",
                "dt_bias", "b_weight", "g_weight", "norm_gamma",
                "out_weight"]

    def infer_shape(self, in_shapes):
        data = in_shapes[0]
        if data is None:
            require_known("KimiDeltaAttention", in_shapes[:1], ["data"])
        if len(data) != 3:
            raise MXNetError("KimiDeltaAttention: data must be (B, S, E)")
        E, H, d = data[2], self.param.num_heads, self.param.head_dim
        conv = (self.param.conv_taps, H * d)
        return ([data, (H * d, E), (H * d, E), (H * d, E), conv, conv, conv,
                 (H * d, E), (H,), (H * d,), (H, E), (H * d, E), (d,),
                 (E, H * d)], [data], [])

    def cost_mxu_dims(self, in_shapes, out_shapes):
        B, S, E = in_shapes[0]
        H, d = self.param.num_heads, self.param.head_dim
        T = B * S
        return [(T, E, 5 * H * d), (T, E, H), (T, H * d, E),
                (T * H, d, d), (T * H, d, d), (T * H, d, d)]

    def cost_flops(self, in_shapes, out_shapes):
        # the projections (q, k, v, the decay, the output gate; β; out) and
        # the recurrence's own operations a token and head
        return float(sum(2 * m * k * n for m, k, n in
                         self.cost_mxu_dims(in_shapes, out_shapes)))

    def forward(self, inputs, aux, is_train, rng):
        (x, wq, wk, wv, cq, ck, cv, wf, a_log, dt_bias, wb, wg, gamma,
         w_out) = inputs
        B, S, _E = x.shape
        H, d, f32 = self.param.num_heads, self.param.head_dim, jnp.float32
        with jax.named_scope(PROJ_IN):
            q, k, v = x @ wq.T, x @ wk.T, x @ wv.T
            f = jnp.dot(x, wf.T, preferred_element_type=f32)
            b = jnp.dot(x, wb.T, preferred_element_type=f32)
            gate = x @ wg.T
        with jax.named_scope(ROTARY_NORM):
            q, k, v = (jax.nn.silu(causal_depthwise_conv(t, w))
                       for t, w in ((q, cq), (k, ck), (v, cv)))
            q = l2_normalise(q.reshape(B, S, H, d)) * d ** -0.5
            k = l2_normalise(k.reshape(B, S, H, d))
            q, k = q.astype(x.dtype), k.astype(x.dtype)
            v = v.reshape(B, S, H, d)
            g = self.param.gate_lower_bound * jax.nn.sigmoid(
                jnp.exp(a_log.astype(f32))[:, None]
                * (f.reshape(B, S, H, d) + dt_bias.astype(f32).reshape(H, d)))
            beta = jax.nn.sigmoid(b)
        with jax.named_scope(KERNEL):
            o = kda_rule(q, k, v, g, beta, self.param.chunk)
        with jax.named_scope(PROJ_OUT):
            o = rms_norm(o, gamma, self.param.eps)
            o = (o.astype(f32) * jax.nn.sigmoid(
                gate.reshape(B, S, H, d).astype(f32))).astype(x.dtype)
            return [o.reshape(B, S, H * d) @ w_out.T], None
