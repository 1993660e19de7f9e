"""Transformer operators: LayerNorm, RMSNorm, MultiHeadAttention,
MultiHeadLatentAttention (low-rank query and key/value paths, one rotary
key shared by all heads), CompressedConvAttention (attention inside a
compressed latent mixed by two causal convolutions, grouped query heads),
GatedAttention (grouped query heads, RMSNorm on q and k, a partial rotary
— YaRN-scaled or not —, a sigmoid gate on the output, a sliding window).

TPU-native extensions beyond the reference op set (the reference predates
transformers; SURVEY §5 notes its only long-sequence tools are bucketing
and pipeline LSTM).  These ops complete the symbolic surface needed by
``models/transformer.py`` and lower to the flash/ring attention kernels
in ``parallel/ring_attention.py``.
"""
from __future__ import annotations

import functools
import math

import numpy as _np

import jax
import jax.numpy as jnp
from jax import lax

from ..base import MXNetError
from ..dparam import Field, ParamStruct
from ..observability.phases import ATTENTION_SCOPES
from .registry import (OperatorProperty, register_op, require_known,
                       contract_sharding, dedup_axes)

# the device sub-scopes of an attention node (observability/device_scopes.py)
PROJ_IN, ROTARY_NORM, KERNEL, PROJ_OUT = ATTENTION_SCOPES


class _LayerNormParam(ParamStruct):
    axis = Field(int, default=-1)
    eps = Field(float, default=1e-5)


@register_op("LayerNorm")
class LayerNorm(OperatorProperty):
    """y = (x - mean) / sqrt(var + eps) * gamma + beta over ``axis``."""
    param_cls = _LayerNormParam

    def list_arguments(self):
        return ["data", "gamma", "beta"]

    def infer_shape(self, in_shapes):
        data = in_shapes[0]
        if data is None:
            require_known("LayerNorm", in_shapes[:1], ["data"])
        d = (data[self.param.axis],)
        return [data, d, d], [data], []

    def forward(self, inputs, aux, is_train, rng):
        x, gamma, beta = inputs
        ax = self.param.axis
        mu = jnp.mean(x, axis=ax, keepdims=True)
        var = jnp.var(x, axis=ax, keepdims=True)
        y = (x - mu) * jnp.reciprocal(jnp.sqrt(var + self.param.eps))
        shape = [1] * x.ndim
        shape[ax] = x.shape[ax]
        return [y * gamma.reshape(shape) + beta.reshape(shape)], None

    def infer_sharding(self, in_specs, in_shapes, out_shapes, mesh_shape):
        data = in_specs[0]
        ax = self.param.axis % len(data) if data else 0
        norm = data[ax] if data else ()
        return {"out": [tuple(data)],
                "in": [None, (norm,), (norm,)]}


def rms_norm(x, gamma, eps):
    """x / sqrt(mean(x²) + eps) · gamma over the last axis; the mean and
    the scaling in float32 whatever x is, the result in x's dtype."""
    x32 = x.astype(jnp.float32)
    inv = jnp.reciprocal(jnp.sqrt(
        jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + eps))
    return (x32 * inv * gamma.astype(jnp.float32)).astype(x.dtype)


class _RMSNormParam(ParamStruct):
    eps = Field(float, default=1e-6)


@register_op("RMSNorm")
class RMSNorm(OperatorProperty):
    """y = x / sqrt(mean(x²) + eps) * gamma over the last axis: no mean
    is subtracted and there is no shift."""
    param_cls = _RMSNormParam

    def list_arguments(self):
        return ["data", "gamma"]

    def infer_shape(self, in_shapes):
        data = in_shapes[0]
        if data is None:
            require_known("RMSNorm", in_shapes[:1], ["data"])
        return [data, (data[-1],)], [data], []

    def forward(self, inputs, aux, is_train, rng):
        return [rms_norm(inputs[0], inputs[1], self.param.eps)], None

    def cost_flops(self, in_shapes, out_shapes):
        # square, sum, scale, gain: four vector passes over the input
        return 4.0 * float(_np.prod(in_shapes[0], dtype=_np.int64))

    def cost_reduce_len(self, in_shapes, out_shapes):
        return int(in_shapes[0][-1])

    def infer_sharding(self, in_specs, in_shapes, out_shapes, mesh_shape):
        data = in_specs[0]
        return {"out": [tuple(data)],
                "in": [None, (data[-1] if data else (),)]}


def rotary_interleaved(x, theta):
    """Rotary position embedding of x (..., S, D) whose pairs lie
    interleaved, (x0, x1), (x2, x3), …: pair i of position p is turned
    by the angle p · theta^(−2i/D).  The pairs are brought to halves
    first, (x0, x2, …, x1, x3, …), then rotated as halves, so the
    result is in the half layout; a score q·k does not depend on the
    layout as long as q and k share it.  float32 inside, x's dtype out."""
    s, d = x.shape[-2], x.shape[-1]
    x32 = x.astype(jnp.float32)
    x32 = jnp.concatenate([x32[..., 0::2], x32[..., 1::2]], axis=-1)
    inv_freq = 1.0 / (float(theta) ** (
        jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1)
    half = d // 2
    turned = jnp.concatenate([-x32[..., half:], x32[..., :half]], axis=-1)
    return (x32 * cos + turned * sin).astype(x.dtype)


def yarn_inv_freq(rotary_dim, theta, factor, original_max_position,
                  beta_fast=32.0, beta_slow=1.0):
    """YaRN's inverse frequencies (arXiv:2309.00071; as the transformers
    library's ``_compute_yarn_parameters`` builds them, with its default
    truncation) for the ``rotary_dim / 2`` pairs of a rotary at ``theta``:
    pair i turns at theta^(−2i/r) where it makes more than ``beta_fast``
    turns over ``original_max_position`` positions, at that over
    ``factor`` where it makes fewer than ``beta_slow``, and between the
    two along a linear ramp over the pairs.  float64 numpy."""
    r = int(rotary_dim)

    def pair_of(turns):     # the (fractional) pair that makes ``turns``
        return r * math.log(original_max_position / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(pair_of(beta_fast)), 0)
    high = min(math.ceil(pair_of(beta_slow)), r - 1)
    if low == high:
        high += 0.001
    extrapolation = 1.0 / theta ** (_np.arange(0, r, 2) / float(r))
    ramp = _np.clip((_np.arange(r // 2) - low) / (high - low), 0.0, 1.0)
    return extrapolation / factor * ramp + extrapolation * (1.0 - ramp)


@functools.lru_cache(maxsize=None)
def _half_swap(d, r):
    """The d × d signed permutation of the half-split rotary on the first
    ``r`` channels: t @ P is (−t[r/2:r], t[:r/2], 0 …) — each channel's
    partner, the first half's negated — and zero from channel r on.  Every
    column holds one ±1 or nothing, so a product with it is exact."""
    half = r // 2
    p = _np.zeros((d, d), _np.float32)
    i = _np.arange(half)
    p[i + half, i] = -1.0
    p[i, i + half] = 1.0
    return p


def _swap(t, perm):
    """t @ ``perm`` in float32, exact where every column of ``perm`` holds
    one ±1 or nothing: a 16-bit t in one pass of the matrix unit and out
    in its own dtype, a wider one as float32 at ``HIGHEST``."""
    precision = None
    if jnp.dtype(t.dtype).itemsize > 2:
        t, precision = t.astype(jnp.float32), lax.Precision.HIGHEST
    return jnp.matmul(t, jnp.asarray(perm, t.dtype), precision=precision,
                      preferred_element_type=t.dtype).astype(jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _turn(r, x, cos, sin):
    """x · cos + (x @ P) · sin with P = ``_half_swap(d, r)``, x's dtype."""
    perm = _half_swap(x.shape[-1], r)
    return (x.astype(jnp.float32) * cos + _swap(x, perm) * sin).astype(
        x.dtype)


def _turn_fwd(r, x, cos, sin):
    return _turn(r, x, cos, sin), (cos, sin)


def _turn_bwd(r, res, g):
    """g · cos + (g @ Pᵀ) · sin: the partner is taken before the product
    with sin, which both channels of a pair share, so the products read g
    in its own dtype (autodiff would give (g · sin) @ Pᵀ, a float32
    operand rounded on the matrix unit's default pass).  g's own term is
    a product too, with the identity: the matrix unit writes both terms
    in whatever layout the gradient's consumer asks for (a headwise
    gate's 129-wide q gradient wants the sequence minor), where an
    elementwise pass would have the compiler relayout g in float32
    first."""
    cos, sin = res
    d = g.shape[-1]
    dx = _swap(g, _np.eye(d, dtype=_np.float32)) * cos \
        + _swap(g, _half_swap(d, r).T) * sin
    return dx.astype(g.dtype), None, None


_turn.defvjp(_turn_fwd, _turn_bwd)


def rotary_half(x, theta, rotary_dim=None, inv_freq=None, scale=None):
    """Rotary position embedding of x (..., S, D) in the half-split
    layout, on the first ``rotary_dim`` channels (all of them by
    default): channel i < rotary_dim/2 of position p pairs with channel
    i + rotary_dim/2 and the pair is turned by the angle
    p · theta^(−2i/rotary_dim) — or p · ``inv_freq[i]`` where the
    frequencies are given (:func:`yarn_inv_freq`) — and cos and sin are
    multiplied by ``scale`` where one is given (YaRN's attention factor:
    the rotated channels' scores grow by its square); the channels from
    ``rotary_dim`` on pass through.  float32 inside, x's dtype out.

    Computed as x · cos + (x @ P) · sin, P the signed half-swap
    (``_half_swap``), cos and sin (S, D) tables that hold a pair's value
    on both its channels and 1 and 0 on pass-through ones: no slice of a
    head, the transpose before the call and the cast after it fuse into
    the product.  Term by term these are a · cos − b · sin and
    b · cos + a · sin, so value and gradient are those of the halves'
    formula to the bit, operation by operation (but for the sign of a
    pass-through zero)."""
    s, d = x.shape[-2], x.shape[-1]
    r = d if rotary_dim is None else int(rotary_dim)
    if inv_freq is None:
        inv_freq = 1.0 / (float(theta) ** (
            jnp.arange(0, r, 2, dtype=jnp.float32) / r))
    else:
        inv_freq = jnp.asarray(inv_freq, jnp.float32)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if scale is not None:
        cos, sin = cos * scale, sin * scale

    def table(t, rest):     # (S, r/2) -> (S, D): a pair's value twice
        return jnp.pad(jnp.tile(t, (1, 2)), ((0, 0), (0, d - r)),
                       constant_values=rest)

    return _turn(r, x, table(cos, 1.0), table(sin, 0.0))


def shift_tokens(z, n, axis=1):
    """z moved ``n`` positions later along ``axis``, zeros in front: the
    value at position t is z's at t − n (what a causal convolution's tap
    n reads)."""
    if n == 0:
        return z
    pad = [(0, 0)] * z.ndim
    pad[axis] = (n, 0)
    return lax.slice_in_dim(jnp.pad(z, pad), 0, z.shape[axis], axis=axis)


def causal_conv_pair(z, w0, w1):
    """The two causal convolutions of compressed convolutional attention
    on z (B, S, H, d): a depthwise one over the sequence, ``w0`` (taps,
    H·d), then one that mixes the d channels of each head, ``w1`` (taps,
    H, d, d).  A convolution's last tap reads the current token, the one
    before it the previous token, and so on; positions before the first
    read zeros.  Sums in float32, z's dtype out."""
    B, S, H, d = z.shape
    t0, t1 = w0.shape[0], w1.shape[0]
    taps0 = w0.astype(jnp.float32).reshape(t0, H, d)
    z32 = z.astype(jnp.float32)
    z0 = sum(shift_tokens(z32, t0 - 1 - j) * taps0[j]
             for j in range(t0)).astype(z.dtype)
    stacked = jnp.stack([shift_tokens(z0, t1 - 1 - j) for j in range(t1)],
                        axis=3)                         # (B, S, H, taps, d)
    return jnp.einsum("bshtc,thcd->bshd", stacked, w1,
                      preferred_element_type=jnp.float32).astype(z.dtype)


class _CCAParam(ParamStruct):
    num_heads = Field(int, required=True, lower=1, doc="query heads")
    num_kv_heads = Field(int, required=True, lower=2,
                         doc="key/value heads: even (the value's second "
                             "half is the previous token's), a divisor of "
                             "num_heads")
    head_dim = Field(int, required=True, lower=2)
    conv_taps0 = Field(int, default=2, lower=1,
                       doc="taps of the depthwise convolution")
    conv_taps1 = Field(int, default=2, lower=1,
                       doc="taps of the convolution that mixes a head's "
                           "channels")
    rope_theta = Field(float, default=10000.0)
    partial_rotary_factor = Field(float, default=1.0,
                                  doc="share of a head that is rotated")
    eps = Field(float, default=1e-5, doc="of the q and k normalisation")


@register_op("CompressedConvAttention")
class CompressedConvAttention(OperatorProperty):
    """Compressed convolutional attention (Zyphra's CCA, arXiv:2510.04476
    section 3, grouped-query form), data (B, S, E) -> (B, S, E).

    Everything between the down-projections and the out-projection
    happens in a latent narrower than E: H = ``num_heads`` query heads
    and H_kv = ``num_kv_heads`` key/value heads of d = ``head_dim``,
    g = H / H_kv query heads a key/value head.

    1. q̃ = u W_qᵀ (E → H·d), k̃ = u W_kᵀ (E → H_kv·d), ṽ = u W_vᵀ
       (E → H_kv·d).
    2. value shift: the first half of v's channels (key/value heads
       0 .. H_kv/2 − 1) are ṽ's at the current token, the second half
       ṽ's at the previous token (zeros at token 0).
    3. q̃ and k̃ each go through two causal convolutions with weights of
       their own (:func:`causal_conv_pair`).
    4. q-k mean of the values before the convolutions: m_q[h] =
       ½(q̃[h] + k̃[h // g]), m_k[j] = ½(k̃[j] + mean of q̃ over group
       j);  q′ = conv(q̃) + m_q, k′ = conv(k̃) + m_k.
    5. q̂ = q′ / rms(q′), k̂ = τ_j · k′ / rms(k′) per head over its d
       channels (no gain; τ = ``k_temp``, a learned scalar a key/value
       head), in float32.
    6. rotary on the first ``partial_rotary_factor``·d channels of each
       head of q̂ and k̂, half-split pairing (:func:`rotary_half`).
    7. causal softmax(q̂ k̂ᵀ / √d) v, query head h on key/value head
       h // g (the flash path of ``parallel/ring_attention.py``, which
       indexes k and v by group and repeats neither); heads concatenated
       -> ``out_weight`` (H·d → E).

    No biases; weights are (out_features, in_features)."""
    param_cls = _CCAParam
    mxu = True

    def list_arguments(self):
        return ["data", "q_weight", "k_weight", "v_weight",
                "q_conv0_weight", "q_conv1_weight", "k_conv0_weight",
                "k_conv1_weight", "k_temp", "out_weight"]

    def infer_shape(self, in_shapes):
        data = in_shapes[0]
        if data is None:
            require_known("CompressedConvAttention", in_shapes[:1], ["data"])
        if len(data) != 3:
            raise MXNetError("CompressedConvAttention: data must be "
                             "(B, S, E)")
        p = self.param
        E, H, K, d = data[2], p.num_heads, p.num_kv_heads, p.head_dim
        if H % K or K % 2:
            raise MXNetError("CompressedConvAttention: %d query heads on "
                             "%d key/value heads (want an even number that "
                             "divides the query heads)" % (H, K))
        if int(round(p.partial_rotary_factor * d)) % 2:
            raise MXNetError("the rotated part of a head must be even")
        t0, t1 = p.conv_taps0, p.conv_taps1
        return ([data, (H * d, E), (K * d, E), (K * d, E),
                 (t0, H * d), (t1, H, d, d), (t0, K * d), (t1, K, d, d),
                 (K,), (E, H * d)], [data], [])

    def cost_mxu_dims(self, in_shapes, out_shapes):
        B, S, E = in_shapes[0]
        p = self.param
        H, K, d = p.num_heads, p.num_kv_heads, p.head_dim
        T = B * S
        return [(T, E, H * d), (T, E, K * d), (T, E, K * d), (T, H * d, E),
                (T * (H + K), p.conv_taps1 * d, d), (S, d, S), (S, S, d)]

    def cost_flops(self, in_shapes, out_shapes):
        B = in_shapes[0][0]
        dims = self.cost_mxu_dims(in_shapes, out_shapes)
        proj = sum(2 * m * k * n for m, k, n in dims[:5])
        attn = sum(2 * B * self.param.num_heads * m * k * n
                   for m, k, n in dims[5:])
        return float(proj + attn)

    def cost_reduce_len(self, in_shapes, out_shapes):
        return int(in_shapes[0][1])     # softmax over the key axis

    def forward(self, inputs, aux, is_train, rng):
        x, wq, wk, wv, qc0, qc1, kc0, kc1, temp, wo = inputs
        B, S, _E = x.shape
        p = self.param
        H, K, d = p.num_heads, p.num_kv_heads, p.head_dim
        g = H // K
        with jax.named_scope(PROJ_IN):
            q = (x @ wq.T).reshape(B, S, H, d)
            k = (x @ wk.T).reshape(B, S, K, d)
            v = x @ wv.T                                # (B, S, K·d)

        def unit_rms(t):
            return t * lax.rsqrt(jnp.mean(jnp.square(t), axis=-1,
                                          keepdims=True) + p.eps)

        rot = int(round(p.partial_rotary_factor * d))

        def heads(t):       # (B, S, heads, d) -> (B, heads, S, d) rotated
            return rotary_half(t.transpose(0, 2, 1, 3), p.rope_theta,
                               rot).astype(x.dtype)

        # the value shift, the convolutions with the q-k mean, the
        # normalisation and the rotary: all that lies between the
        # projections and the kernel
        with jax.named_scope(ROTARY_NORM):
            half = K * d // 2
            v = jnp.concatenate([v[..., :half],
                                 shift_tokens(v[..., half:], 1)], axis=-1)
            q32 = q.astype(jnp.float32).reshape(B, S, K, g, d)
            k32 = k.astype(jnp.float32)
            mean_q = 0.5 * (q32 + k32[:, :, :, None, :])
            mean_k = 0.5 * (k32 + jnp.mean(q32, axis=3))
            q = causal_conv_pair(q, qc0, qc1).astype(jnp.float32) \
                + mean_q.reshape(B, S, H, d)
            k = causal_conv_pair(k, kc0, kc1).astype(jnp.float32) + mean_k
            q = unit_rms(q)
            k = unit_rms(k) * temp.astype(jnp.float32)[:, None]
            q, k = heads(q), heads(k)
            v = v.reshape(B, S, K, d).transpose(0, 2, 1, 3)

        from ..parallel.ring_attention import sharded_self_attention
        with jax.named_scope(KERNEL):
            o = sharded_self_attention(q, k, v, causal=True)
        with jax.named_scope(PROJ_OUT):
            return [o.transpose(0, 2, 1, 3).reshape(B, S, H * d) @ wo.T], \
                None


class _GatedAttentionParam(ParamStruct):
    num_heads = Field(int, required=True, lower=1, doc="query heads")
    num_kv_heads = Field(int, required=True, lower=1,
                         doc="key/value heads, a divisor of num_heads")
    head_dim = Field(int, required=True, lower=2)
    rope_theta = Field(float, default=10000.0)
    partial_rotary_factor = Field(float, default=1.0,
                                  doc="share of a head that is rotated")
    eps = Field(float, default=1e-6, doc="of the q and k RMSNorms")
    qk_norm = Field(bool, default=True,
                    doc="RMSNorm on q and k, a gain vector each")
    gate = Field(str, default="elementwise",
                 enum=("elementwise", "headwise"),
                 doc="a gate a channel of a head, or one a head")
    window = Field(int, default=0, lower=0,
                   doc="sliding window: a query sees itself and the "
                       "window - 1 keys before it (0: every key before it)")
    rope_type = Field(str, default="default", enum=("default", "yarn"))
    rope_factor = Field(float, default=1.0, doc="YaRN's scaling factor")
    rope_original_max_position = Field(
        int, default=0, lower=0, doc="YaRN's original context length")
    rope_beta_fast = Field(float, default=32.0)
    rope_beta_slow = Field(float, default=1.0)
    rope_attention_factor = Field(
        float, default=1.0, doc="YaRN's factor on cos and sin")


@register_op("GatedAttention")
class GatedAttention(OperatorProperty):
    """Softmax attention with grouped query heads, per-head RMSNorm on q
    and k, a partial rotary and a sigmoid output gate (the full-attention
    layer of the ``qwen3_next`` family; with a sliding window, YaRN, a
    gate a head and no q/k norm the two layer kinds of ``laguna``), data
    (B, S, E) -> (B, S, E).

    H = ``num_heads`` query heads on H_kv = ``num_kv_heads`` key/value
    heads of d = ``head_dim``.  [q_h ‖ gate_h] = u W_qᵀ a head (E → H·2d;
    with ``gate="headwise"`` gate_h is one channel, E → H·(d + 1));
    k, v = u W_kᵀ, u W_vᵀ (E → H_kv·d);  q and k through an RMSNorm over
    d with a gain vector each (``q_norm_gamma``, ``k_norm_gamma``: one for
    all heads; none where ``qk_norm`` is off);  rotary on the first
    ``partial_rotary_factor``·d channels, half-split pairing
    (:func:`rotary_half`) — with ``rope_type="yarn"`` at YaRN's
    frequencies (:func:`yarn_inv_freq`) and cos and sin times
    ``rope_attention_factor``;  causal softmax(q kᵀ / √d) v, query head
    h on key/value head h // (H / H_kv), and with ``window`` W query i
    sees keys i − W + 1 … i only (the flash path of
    ``parallel/ring_attention.py``, k and v of H_kv heads, repeated
    nowhere);  y = (o ⊙ sigmoid(gate)) W_oᵀ (H·d → E): the gate, a
    channel's or a head's, is applied outside the kernel.  No biases;
    weights are (out_features, in_features)."""
    param_cls = _GatedAttentionParam
    mxu = True

    def list_arguments(self):
        norms = ["q_norm_gamma", "k_norm_gamma"] if self.param.qk_norm \
            else []
        return ["data", "q_weight", "k_weight", "v_weight"] + norms \
            + ["out_weight"]

    def infer_shape(self, in_shapes):
        data = in_shapes[0]
        if data is None:
            require_known("GatedAttention", in_shapes[:1], ["data"])
        if len(data) != 3:
            raise MXNetError("GatedAttention: data must be (B, S, E)")
        p = self.param
        E, H, K, d = data[2], p.num_heads, p.num_kv_heads, p.head_dim
        if H % K:
            raise MXNetError("GatedAttention: %d query heads do not group "
                             "over %d key/value heads" % (H, K))
        if int(round(p.partial_rotary_factor * d)) % 2:
            raise MXNetError("the rotated part of a head must be even")
        if p.rope_type == "yarn" and not p.rope_original_max_position:
            raise MXNetError("GatedAttention: YaRN needs "
                             "rope_original_max_position")
        norms = [(d,), (d,)] if p.qk_norm else []
        return ([data, (H * self._q_width(), E), (K * d, E), (K * d, E)]
                + norms + [(E, H * d)], [data], [])

    def cost_mxu_dims(self, in_shapes, out_shapes):
        B, S, E = in_shapes[0]
        p = self.param
        H, K, d = p.num_heads, p.num_kv_heads, p.head_dim
        T = B * S
        keys = min(S, p.window) if p.window else S
        return [(T, E, H * self._q_width()), (T, E, K * d), (T, E, K * d),
                (T, H * d, E), (S, d, keys), (S, keys, d)]

    def cost_flops(self, in_shapes, out_shapes):
        B = in_shapes[0][0]
        dims = self.cost_mxu_dims(in_shapes, out_shapes)
        proj = sum(2 * m * k * n for m, k, n in dims[:4])
        attn = sum(2 * B * self.param.num_heads * m * k * n
                   for m, k, n in dims[4:])
        return float(proj + attn)

    def cost_reduce_len(self, in_shapes, out_shapes):
        return int(in_shapes[0][1])     # softmax over the key axis

    def _q_width(self):
        """A head's columns of ``q_weight``: its query and its gate."""
        d = self.param.head_dim
        return 2 * d if self.param.gate == "elementwise" else d + 1

    def _rotary(self):
        """(inv_freq, scale) for :func:`rotary_half`: None, None but for
        YaRN."""
        p = self.param
        if p.rope_type != "yarn":
            return None, None
        rot = int(round(p.partial_rotary_factor * p.head_dim))
        return (yarn_inv_freq(rot, p.rope_theta, p.rope_factor,
                              p.rope_original_max_position,
                              p.rope_beta_fast, p.rope_beta_slow),
                p.rope_attention_factor)

    def forward(self, inputs, aux, is_train, rng):
        p = self.param
        x, wq, wk, wv = inputs[:4]
        wo = inputs[-1]
        B, S, _E = x.shape
        H, K, d = p.num_heads, p.num_kv_heads, p.head_dim
        with jax.named_scope(PROJ_IN):
            qg = (x @ wq.T).reshape(B, S, H, self._q_width())
            q, gate = qg[..., :d], qg[..., d:]
            k = (x @ wk.T).reshape(B, S, K, d)
            v = (x @ wv.T).reshape(B, S, K, d)
        rot = int(round(p.partial_rotary_factor * d))
        inv_freq, scale = self._rotary()

        def heads(t, gamma):    # normalised, (B, heads, S, d), rotated
            if p.qk_norm:
                t = rms_norm(t, gamma, p.eps)
            return rotary_half(t.transpose(0, 2, 1, 3), p.rope_theta, rot,
                               inv_freq, scale)

        gq, gk = inputs[4:6] if p.qk_norm else (None, None)
        with jax.named_scope(ROTARY_NORM):
            q, k, v = heads(q, gq), heads(k, gk), v.transpose(0, 2, 1, 3)
        from ..parallel.ring_attention import sharded_self_attention
        with jax.named_scope(KERNEL):
            o = sharded_self_attention(q, k, v, causal=True,
                                       window=p.window or None)
        with jax.named_scope(PROJ_OUT):
            o = o.transpose(0, 2, 1, 3).astype(jnp.float32) \
                * jax.nn.sigmoid(gate.astype(jnp.float32))
            return [o.astype(x.dtype).reshape(B, S, H * d) @ wo.T], None


class _MLAParam(ParamStruct):
    num_heads = Field(int, required=True, lower=1)
    q_lora_rank = Field(int, required=True, lower=0,
                        doc="0: a direct query projection, no inner norm")
    kv_lora_rank = Field(int, required=True, lower=1)
    qk_nope_head_dim = Field(int, required=True, lower=1)
    qk_rope_head_dim = Field(int, required=True, lower=2)
    v_head_dim = Field(int, required=True, lower=1)
    rope_theta = Field(float, default=10000.0)
    rope_interleave = Field(bool, default=True,
                            doc="rotary pairs interleaved (False: "
                                "half-split, rotary_half)")
    q_norm = Field(bool, default=False,
                   doc="an RMSNorm a head over q's whole width, one gain "
                       "vector for all heads")
    gate = Field(str, default="none", enum=("none", "headwise"),
                 doc="headwise: each head's output times sigmoid(u w_hᵀ), "
                     "gate_weight (heads, E)")
    eps = Field(float, default=1e-6, doc="of the inner RMSNorms")


@register_op("MultiHeadLatentAttention")
class MultiHeadLatentAttention(OperatorProperty):
    """Latent attention (DeepSeek-V2/V3's MLA), data (B, S, E) -> (B, S, E).

    The query goes through a rank-``q_lora_rank`` bottleneck with an
    RMSNorm inside it (``q_lora_rank`` 0: one direct projection,
    ``q_weight``); keys and values come from one rank-``kv_lora_rank``
    latent (RMSNorm inside) plus ONE rotary key of ``qk_rope_head_dim``
    that every head shares.  Per head, q = [q_nope ; rot(q_rope)],
    k = [k_nope ; rot(k_rope)] (``qk_nope_head_dim + qk_rope_head_dim``
    wide), v is ``v_head_dim`` wide; with ``q_norm`` q is RMS-normed over
    its whole width before the rotary; the rotary pairs interleaved
    channels, or with ``rope_interleave`` off the two halves
    (:func:`rotary_half`); causal softmax(q kᵀ / sqrt(width of q)) v;
    with ``gate="headwise"`` head h's output times sigmoid(u w_hᵀ);
    heads concatenated -> ``out_weight``.  No biases.  Weights are
    (out_features, in_features).  The attention itself is the flash
    path of ``parallel/ring_attention.py``, which takes q/k of one width
    and v of another.
    """
    param_cls = _MLAParam
    mxu = True

    def list_arguments(self):
        p = self.param
        query = ["q_a_weight", "q_a_norm_gamma", "q_b_weight"] \
            if p.q_lora_rank else ["q_weight"]
        if p.q_norm:
            query.append("q_norm_gamma")
        gate = ["gate_weight"] if p.gate == "headwise" else []
        return ["data"] + query + ["kv_a_weight", "kv_a_norm_gamma",
                                   "kv_b_weight"] + gate + ["out_weight"]

    def _dims(self):
        p = self.param
        return (p.num_heads, p.q_lora_rank, p.kv_lora_rank,
                p.qk_nope_head_dim, p.qk_rope_head_dim, p.v_head_dim)

    def infer_shape(self, in_shapes):
        data = in_shapes[0]
        if data is None:
            require_known("MultiHeadLatentAttention", in_shapes[:1],
                          ["data"])
        if len(data) != 3:
            raise MXNetError("MultiHeadLatentAttention: data must be "
                             "(B, S, E)")
        E = data[2]
        H, rq, rkv, nope, rope, dv = self._dims()
        if rope % 2:
            raise MXNetError("qk_rope_head_dim must be even (rotary pairs)")
        p = self.param
        query = [(rq, E), (rq,), (H * (nope + rope), rq)] if rq \
            else [(H * (nope + rope), E)]
        if p.q_norm:
            query.append((nope + rope,))
        gate = [(H, E)] if p.gate == "headwise" else []
        return ([data] + query + [(rkv + rope, E), (rkv,),
                                  (H * (nope + dv), rkv)]
                + gate + [(E, H * dv)], [data], [])

    def cost_mxu_dims(self, in_shapes, out_shapes):
        B, S, E = in_shapes[0]
        H, rq, rkv, nope, rope, dv = self._dims()
        T = B * S
        query = [(T, E, rq), (T, rq, H * (nope + rope))] if rq \
            else [(T, E, H * (nope + rope))]
        gate = [(T, E, H)] if self.param.gate == "headwise" else []
        return query + [(T, E, rkv + rope), (T, rkv, H * (nope + dv))] \
            + gate + [(T, H * dv, E), (S, nope + rope, S), (S, S, dv)]

    def cost_flops(self, in_shapes, out_shapes):
        B, S, _E = in_shapes[0]
        H = self.param.num_heads
        dims = self.cost_mxu_dims(in_shapes, out_shapes)
        proj = sum(2 * m * k * n for m, k, n in dims[:-2])
        attn = sum(2 * B * H * m * k * n for m, k, n in dims[-2:])
        return float(proj + attn)

    def cost_reduce_len(self, in_shapes, out_shapes):
        return int(in_shapes[0][1])     # softmax over the key axis

    def forward(self, inputs, aux, is_train, rng):
        p = self.param
        args = dict(zip(self.list_arguments(), inputs))
        x = args["data"]
        B, S, _E = x.shape
        H, rq, rkv, nope, rope, dv = self._dims()

        def heads(t, width):    # (B, S, H*width) -> (B, H, S, width)
            return t.reshape(B, S, H, width).transpose(0, 2, 1, 3)

        def rotate(t):          # (..., S, rope)
            if p.rope_interleave:
                return rotary_interleaved(t, p.rope_theta)
            return rotary_half(t, p.rope_theta)

        # a low-rank path holds its norm between two products: both
        # products and the norm are the projection.  (The scopes follow
        # the statements' order; moving a statement would renumber the
        # compiled step.)
        with jax.named_scope(PROJ_IN):
            if rq:
                q = rms_norm(x @ args["q_a_weight"].T,
                             args["q_a_norm_gamma"], p.eps) \
                    @ args["q_b_weight"].T
            else:
                q = x @ args["q_weight"].T
            q = heads(q, nope + rope)
        with jax.named_scope(ROTARY_NORM):
            if p.q_norm:
                q = rms_norm(q, args["q_norm_gamma"], p.eps)
            q = jnp.concatenate([q[..., :nope], rotate(q[..., nope:])],
                                axis=-1)
        with jax.named_scope(PROJ_IN):
            ckv = x @ args["kv_a_weight"].T                 # (B, S, rkv+rope)
        with jax.named_scope(ROTARY_NORM):
            k_rope = rotate(ckv[..., rkv:])
        with jax.named_scope(PROJ_IN):
            kv = heads(rms_norm(ckv[..., :rkv], args["kv_a_norm_gamma"],
                                p.eps) @ args["kv_b_weight"].T, nope + dv)
        with jax.named_scope(ROTARY_NORM):
            k = jnp.concatenate(
                [kv[..., :nope],
                 jnp.broadcast_to(k_rope[:, None], (B, H, S, rope))],
                axis=-1)
        from ..parallel.ring_attention import sharded_self_attention
        with jax.named_scope(KERNEL):
            o = sharded_self_attention(q, k, kv[..., nope:], causal=True)
        with jax.named_scope(PROJ_OUT):
            o = o.transpose(0, 2, 1, 3)                     # (B, S, H, dv)
            if p.gate == "headwise":
                gate = jax.nn.sigmoid(jnp.dot(
                    x, args["gate_weight"].T,
                    preferred_element_type=jnp.float32))
                o = (o.astype(jnp.float32) * gate[..., None]).astype(x.dtype)
            return [o.reshape(B, S, H * dv) @ args["out_weight"].T], None

    def infer_sharding(self, in_specs, in_shapes, out_shapes, mesh_shape):
        """Head-parallel over whatever axis shards the rows of the two
        up-projections (``q_b``, ``kv_b``: heads), closed by a
        row-parallel ``out_weight``; the two low-rank down-projections
        and their norms stay replicated (they are shared by all heads)."""
        at = {n: i for i, n in enumerate(self.list_arguments())}
        q_up = at["q_b_weight" if self.param.q_lora_rank else "q_weight"]
        data, out_spec = in_specs[0], in_specs[at["out_weight"]]
        head = tuple(in_specs[q_up][0] if in_specs[q_up] else ())
        out_c = tuple(out_spec[1] if len(out_spec) > 1 else ())
        batch = tuple(data[0] if data else ())
        seq = tuple(data[1] if len(data) > 1 else ())
        required = [None] * len(in_specs)
        required[at["kv_b_weight"]] = (head, ())    # splits as q's does
        out = {"out": [(batch, seq, ())], "in": required}
        if head and head == out_c:
            out["reduce"] = {head: "head-parallel latent attention closed "
                                   "by row-parallel out projection: partial "
                                   "sums over %s" % "+".join(head)}
        elif head or out_c:
            axes = head or out_c
            out["notes"] = [{
                "kind": "attn_unreduced",
                "arg": q_up if head else at["out_weight"],
                "axes": axes,
                "message": "latent attention is head-parallel over %s but "
                           "the out projection does not close it with a "
                           "matching row-parallel reduction: XLA "
                           "all-gathers the per-head activations instead"
                           % "+".join(axes)}]
        return out


class _MHAParam(ParamStruct):
    num_heads = Field(int, required=True, lower=1)
    causal = Field(bool, default=False)
    dropout = Field(float, default=0.0)
    use_flash = Field(bool, default=True)


@register_op("MultiHeadAttention")
class MultiHeadAttention(OperatorProperty):
    """Fused self-attention block: qkv projection + attention + out proj.

    data (B, S, E); qkv_weight (3E, E), out_weight (E, E) with reference-
    style (out_features, in_features) layout; lowers to the Pallas flash
    kernel on TPU (parallel/ring_attention.flash_attention).
    """
    param_cls = _MHAParam
    need_rng = True
    mxu = True

    def list_arguments(self):
        return ["data", "qkv_weight", "qkv_bias", "out_weight", "out_bias"]

    def infer_shape(self, in_shapes):
        data = in_shapes[0]
        if data is None:
            require_known("MultiHeadAttention", in_shapes[:1], ["data"])
        if len(data) != 3:
            raise MXNetError("MultiHeadAttention: data must be (B, S, E)")
        E = data[2]
        if E % self.param.num_heads:
            raise MXNetError("embed dim %d not divisible by num_heads %d"
                             % (E, self.param.num_heads))
        return ([data, (3 * E, E), (3 * E,), (E, E), (E,)],
                [data], [])

    def cost_mxu_dims(self, in_shapes, out_shapes):
        B, S, E = in_shapes[0]
        H = self.param.num_heads
        D = E // H
        # qkv proj, out proj, then per-(batch, head): q@k.T and p@v
        return [(B * S, E, 3 * E), (B * S, E, E),
                (S, D, S), (S, S, D)]

    def cost_flops(self, in_shapes, out_shapes):
        B, S, E = in_shapes[0]
        H = self.param.num_heads
        D = E // H
        proj = 2 * B * S * E * (3 * E + E)
        attn = 2 * B * H * (S * D * S + S * S * D)
        return float(proj + attn)

    def cost_reduce_len(self, in_shapes, out_shapes):
        return int(in_shapes[0][1])     # softmax over the key axis

    def forward(self, inputs, aux, is_train, rng):
        x, wqkv, bqkv, wo, bo = inputs
        B, S, E = x.shape
        H = self.param.num_heads
        D = E // H
        def heads(t):  # (B, S, E) -> (B, H, S, D)
            return t.reshape(B, S, H, D).transpose(0, 2, 1, 3)

        with jax.named_scope(PROJ_IN):
            qkv = x @ wqkv.T + bqkv  # (B, S, 3E)
            q, k, v = (heads(t) for t in jnp.split(qkv, 3, axis=-1))
        with jax.named_scope(KERNEL):
            if self.param.use_flash:
                from ..parallel.ring_attention import sharded_self_attention
                o = sharded_self_attention(q, k, v, causal=self.param.causal)
            else:
                from ..parallel.ring_attention import attention_reference
                o = attention_reference(q, k, v, causal=self.param.causal)
        with jax.named_scope(PROJ_OUT):
            o = o.transpose(0, 2, 1, 3).reshape(B, S, E)
            if is_train and self.param.dropout > 0.0 and rng is not None:
                keep = 1.0 - self.param.dropout
                mask = jax.random.bernoulli(rng, keep, o.shape)
                o = jnp.where(mask, o / keep, 0.0).astype(o.dtype)
            return [o @ wo.T + bo], None

    def infer_sharding(self, in_specs, in_shapes, out_shapes, mesh_shape):
        data, qkv_w = in_specs[0], in_specs[1]
        out_w = in_specs[3]
        required = [None] * len(in_specs)
        reduce = {}
        notes = []
        # input projection: data feature dim contracts against qkv_w dim 1
        d_c = data[2] if len(data) > 2 else ()
        w_c = qkv_w[1] if len(qkv_w) > 1 else ()
        r, n, conflict = contract_sharding(d_c, w_c, 0, 1,
                                           "MultiHeadAttention qkv")
        reduce.update(r)
        notes.extend(n)
        if conflict:
            required[0] = (tuple(data[0]), tuple(data[1]), tuple(w_c))
        # head-parallel attention (qkv_w dim 0 over tp = heads split) must
        # be closed by a row-parallel out projection (out_w dim 1 on the
        # same axis) whose psum merges the per-head partial outputs
        head = tuple(qkv_w[0] if qkv_w else ())
        out_c = tuple(out_w[1] if len(out_w) > 1 else ())
        if head and head == out_c:
            reduce[head] = ("head-parallel attention closed by row-parallel "
                            "out projection: partial sums over %s"
                            % "+".join(head))
        elif head or out_c:
            axes = head or out_c
            notes.append({
                "kind": "attn_unreduced", "arg": 1 if head else 3,
                "axes": axes,
                "message": "attention is head-parallel over %s but the out "
                           "projection does not close it with a matching "
                           "row-parallel reduction: XLA all-gathers the "
                           "per-head activations instead" % "+".join(axes)})
        required[2] = (head,)
        batch = tuple(data[0] if data else ())
        seq = tuple(data[1] if len(data) > 1 else ())
        feat = dedup_axes(out_w[0] if out_w else (), batch + seq)
        if head and head == out_c:
            feat = ()          # row-parallel out proj: output replicated
        required[4] = (feat,)
        out = {"out": [(batch, seq, feat)], "in": required}
        if reduce:
            out["reduce"] = reduce
        if notes:
            out["notes"] = notes
        return out


def paged_decode_attention(q, k_pool, v_pool, table, pos):
    """Single-query attention over a block-paged KV cache: gather the
    blocks the table names, mask positions past ``pos``, softmax.
    ``q (B, H, D)``, pools ``(NB, BS, H, D)``, ``table (B, MB) int32``,
    ``pos (B,) int32`` (the newest token's index).  Returns ``(B, H,
    D)`` in q's dtype."""
    import jax
    B, H, D = q.shape
    BS = k_pool.shape[1]
    MB = table.shape[1]
    scale = 1.0 / float(_np.sqrt(D))
    kk = k_pool[table].reshape(B, MB * BS, H, D).astype(q.dtype)
    vv = v_pool[table].reshape(B, MB * BS, H, D).astype(q.dtype)
    s = jnp.einsum("bhd,bthd->bht", q, kk) * scale
    t_idx = jnp.arange(MB * BS, dtype=jnp.int32)
    s = jnp.where(t_idx[None, None, :] <= pos[:, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bht,bthd->bhd", p, vv.astype(p.dtype))
    return o.astype(q.dtype)


class _CachedMHAParam(ParamStruct):
    num_heads = Field(int, required=True, lower=1)
    mode = Field(str, default="decode", doc="prefill | decode")


@register_op("CachedMultiHeadAttention")
class CachedMultiHeadAttention(OperatorProperty):
    """Decode-mode MultiHeadAttention over a block-paged KV cache.

    The generative counterpart of :class:`MultiHeadAttention`: same
    projection weights (so one checkpoint serves training, full
    forward, prefill, and decode graphs), but keys/values stream
    through the paged pools of :mod:`mxnet_tpu.serving.kvcache` and the
    cache append is a **functional update** — the op returns the new
    pools as extra outputs, so the whole step stays jit-pure and the
    compiled program is shape-stable across sequences.

    Inputs beyond the MHA five: ``k_cache``/``v_cache`` pools
    ``(num_blocks, block_size, H, D)``, ``block_table`` ``(B,
    blocks_per_seq)`` naming each row's pool blocks, and ``seq_pos``
    ``(B,)`` — the prompt length in prefill mode (positions ``0..L-1``
    are written; padded positions scatter to the trash block), the new
    token's position in decode mode (position-offset masking limits
    attention to slots ``<= seq_pos``).

    - ``mode="prefill"``: data ``(B, S, E)``; causal self-attention over
      the prompt (identical math to the full-forward reference path)
      plus a scatter of all S keys/values into the pools.
    - ``mode="decode"``: data ``(B, 1, E)``; scatter the single new
      k/v at ``(table[b, pos//bs], pos % bs)``, then single-query
      attention over every cached slot the table names, masked to
      positions ``<= seq_pos`` — padded rows route to the trash block
      and produce ignored outputs, never clobbered cache state.
    """
    param_cls = _CachedMHAParam
    mxu = True

    def list_arguments(self):
        return ["data", "qkv_weight", "qkv_bias", "out_weight", "out_bias",
                "k_cache", "v_cache", "block_table", "seq_pos"]

    def list_outputs(self):
        return ["output", "k_cache_out", "v_cache_out"]

    def infer_shape(self, in_shapes):
        data, cache = in_shapes[0], in_shapes[5]
        if data is None or cache is None:
            require_known("CachedMultiHeadAttention",
                          [in_shapes[0], in_shapes[5]],
                          ["data", "k_cache"])
        if len(data) != 3:
            raise MXNetError(
                "CachedMultiHeadAttention: data must be (B, S, E)")
        if len(cache) != 4:
            raise MXNetError(
                "CachedMultiHeadAttention: k_cache must be "
                "(num_blocks, block_size, num_heads, head_dim)")
        B, S, E = data
        H = self.param.num_heads
        if E % H:
            raise MXNetError("embed dim %d not divisible by num_heads %d"
                             % (E, H))
        if cache[2] != H or cache[3] != E // H:
            raise MXNetError(
                "cache heads/head_dim %s do not match (H=%d, D=%d)"
                % (cache[2:], H, E // H))
        if self.param.mode == "decode" and S != 1:
            raise MXNetError("decode mode takes one token per row, "
                             "got S=%d" % S)
        if self.param.mode not in ("prefill", "decode"):
            raise MXNetError("mode must be prefill|decode, got %r"
                             % self.param.mode)
        table = in_shapes[7]
        mb = table[1] if table is not None and len(table) == 2 else None
        if mb is None:
            raise MXNetError("block_table must be (B, blocks_per_seq)")
        return ([data, (3 * E, E), (3 * E,), (E, E), (E,),
                 tuple(cache), tuple(cache), (B, mb), (B,)],
                [data, tuple(cache), tuple(cache)], [])

    def _ctx_len(self, in_shapes):
        """Cached context slots the table can name (attention width)."""
        cache, table = in_shapes[5], in_shapes[7]
        return int(table[1]) * int(cache[1])

    def cost_mxu_dims(self, in_shapes, out_shapes):
        B, S, E = in_shapes[0]
        H = self.param.num_heads
        D = E // H
        T = self._ctx_len(in_shapes) if self.param.mode == "decode" else S
        # qkv proj, out proj, then per-(batch, head): q@k.T and p@v over
        # the cached context length
        return [(B * S, E, 3 * E), (B * S, E, E),
                (S, D, T), (S, T, D)]

    def cost_flops(self, in_shapes, out_shapes):
        B, S, E = in_shapes[0]
        H = self.param.num_heads
        D = E // H
        T = self._ctx_len(in_shapes) if self.param.mode == "decode" else S
        proj = 2 * B * S * E * (3 * E + E)
        attn = 2 * B * H * (S * D * T + S * T * D)
        return float(proj + attn)

    def cost_reduce_len(self, in_shapes, out_shapes):
        return int(self._ctx_len(in_shapes)
                   if self.param.mode == "decode" else in_shapes[0][1])

    def forward(self, inputs, aux, is_train, rng):
        import jax
        x, wqkv, bqkv, wo, bo, kc, vc, table, seq_pos = inputs
        B, S, E = x.shape
        H = self.param.num_heads
        D = E // H
        BS = kc.shape[1]
        table = table.astype(jnp.int32)
        pos = seq_pos.astype(jnp.int32)
        qkv = x @ wqkv.T + bqkv                       # (B, S, 3E)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        kh = k.reshape(B, S, H, D)
        vh = v.reshape(B, S, H, D)

        if self.param.mode == "prefill":
            from ..parallel.ring_attention import attention_reference

            def heads(t):
                return t.reshape(B, S, H, D).transpose(0, 2, 1, 3)
            o = attention_reference(heads(q), heads(k), heads(v),
                                    causal=True)
            o = o.transpose(0, 2, 1, 3).reshape(B, S, E)
            # scatter every prompt position; padded ones (>= seq_pos)
            # route to the trash block so the write stays static-shape
            j = jnp.arange(S, dtype=jnp.int32)
            blocks = jnp.take_along_axis(
                table, jnp.broadcast_to((j // BS)[None, :], (B, S)), axis=1)
            blocks = jnp.where(j[None, :] < pos[:, None], blocks, 0)
            idx_b = blocks.reshape(-1)
            idx_s = jnp.tile(j % BS, B)
            kc = kc.at[idx_b, idx_s].set(
                kh.reshape(B * S, H, D).astype(kc.dtype))
            vc = vc.at[idx_b, idx_s].set(
                vh.reshape(B * S, H, D).astype(vc.dtype))
        else:
            # decode: append the one new k/v, then single-query
            # attention over the cached context (scatter-then-attend:
            # the new token reads its own k/v back from the pool)
            blk = jnp.take_along_axis(table, (pos // BS)[:, None],
                                      axis=1)[:, 0]
            slot = pos % BS
            kc = kc.at[blk, slot].set(kh[:, 0].astype(kc.dtype))
            vc = vc.at[blk, slot].set(vh[:, 0].astype(vc.dtype))
            o = paged_decode_attention(q.reshape(B, H, D), kc, vc, table,
                                       pos)
            o = o.astype(q.dtype).reshape(B, 1, E)
        return [o @ wo.T + bo, kc, vc], None

    def infer_sharding(self, in_specs, in_shapes, out_shapes, mesh_shape):
        # head-parallel like MHA: cache pools shard dim 2 (heads) on the
        # same axis as qkv_weight dim 0; tables/positions replicated
        data, qkv_w = in_specs[0], in_specs[1]
        head = tuple(qkv_w[0] if qkv_w else ())
        cache = (tuple(), tuple(), head, tuple())
        batch = tuple(data[0] if data else ())
        seq = tuple(data[1] if len(data) > 1 else ())
        out_w = in_specs[3]
        out_c = tuple(out_w[1] if len(out_w) > 1 else ())
        feat = () if (head and head == out_c) \
            else dedup_axes(out_w[0] if out_w else (), batch + seq)
        out = {"out": [(batch, seq, feat), cache, cache],
               "in": [None, None, (head,), None, (feat,),
                      cache, cache, None, None]}
        if head and head == out_c:
            out["reduce"] = {head: "head-parallel cached attention closed "
                                   "by row-parallel out projection"}
        return out
