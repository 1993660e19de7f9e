"""Mixture-of-Experts FFN with expert parallelism.

Two routed layers live here.  ``MoE`` (below) is the dense-mask form for
moderate expert counts.  ``RoutedExperts`` (further down) is the sorted
form for layers of which a device holds a share of the experts: it
routes over all of them, sorts the (token, expert) assignments by expert
and runs one grouped matrix product per projection over the experts
held, for every assignment that landed on them.  It scores with a sigmoid
or a softmax over its own router weight, or takes the scores from a router
outside it: ``MLPRouter`` (after it) is one, an MLP over a stream that
runs from layer to layer beside the residual.

Beyond-reference (SURVEY's parallelism table lists expert parallelism as
absent from the reference): a Switch-style routed FFN whose expert
weights carry a leading ``num_experts`` axis — shard that axis over an
``ep`` mesh dimension (parallel.param_pspec does it by name) and GSPMD
partitions the expert einsums across ranks, inserting the combine
collective where the routed outputs merge.

The op's dispatch is the dense einsum formulation (every expert computes
every token, the routing mask selects): no dynamic shapes, no sorting —
the XLA-friendly form for moderate expert counts.  ``top_k`` experts per
token (Switch's top-1 by default; GShard-style top-2+ scales each hit by
its gate probability), and an optional ``capacity_factor``: each expert
accepts at most ``ceil(cf * T * top_k / K)`` tokens, overflow tokens are
dropped from that expert (their residual path carries them — Switch §2.2)
— the token-drop risk MXL-E007 lints.  Gate gradients flow through the
probability scaling; the op returns the load-balance auxiliary loss as a
second output (fraction·probability dot product, Switch eq. 4).

:func:`expert_parallel_moe` is the explicit shard_map form of the same
block: tokens and experts both sharded over ``ep``, dispatch and combine
each one ``lax.all_to_all`` — the collective pair MXL-E008 prices per
rank and replays through the MXL-D trace diff.
"""
from __future__ import annotations

import functools

import numpy as _np

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ..base import MXNetError
from ..dparam import Field, ParamStruct
from ..observability.phases import ROUTED_SCOPES
from .attention import rms_norm
from .registry import (OperatorProperty, register_cost_rule, register_op,
                       register_sharding_rule, require_known)


def moe_capacity(tokens, num_experts, top_k=1, capacity_factor=0.0):
    """Per-expert token capacity: ``ceil(cf * T * top_k / K)``, or 0
    meaning unbounded (``capacity_factor`` unset)."""
    if not capacity_factor or capacity_factor <= 0:
        return 0
    import math
    return int(math.ceil(int(tokens) * int(top_k) *
                         float(capacity_factor) / int(num_experts)))


def _routing(t, wg, num_experts, top_k, capacity_factor):
    """Shared gating math: returns ``(probs, mask, combine)`` where
    ``mask`` is the {0,1} token->expert assignment after any capacity
    drop and ``combine = probs * mask`` the combine weights."""
    K, topk = num_experts, min(int(top_k), num_experts)
    logits = t @ wg.T                               # (T, K)
    probs = jax.nn.softmax(logits, axis=-1)
    if topk == 1:
        sel = jnp.argmax(probs, axis=-1)            # (T,)
        mask = jax.nn.one_hot(sel, K, dtype=t.dtype)
    else:
        _, inds = lax.top_k(probs, topk)            # (T, topk)
        mask = jnp.sum(jax.nn.one_hot(inds, K, dtype=t.dtype), axis=1)
    cap = moe_capacity(t.shape[0], K, topk, capacity_factor)
    if cap:
        pos = jnp.cumsum(mask, axis=0) - mask       # queue position
        mask = mask * (pos < cap).astype(t.dtype)
    return probs, mask, probs * mask


class _MoEParam(ParamStruct):
    num_experts = Field(int, required=True, lower=2)
    hidden_size = Field(int, required=True, lower=1)
    top_k = Field(int, default=1, lower=1,
                  doc="experts per token (Switch=1, GShard-style=2+)")
    capacity_factor = Field(
        float, default=0.0, lower=0.0,
        doc="per-expert capacity = ceil(cf*T*top_k/K); 0 = unbounded "
            "(overflow tokens are dropped from the expert)")


@register_op("MoE", aliases=("SwitchFFN",))
class MoE(OperatorProperty):
    """data (..., E) -> (..., E); outputs [y, aux_loss(1,)]."""
    param_cls = _MoEParam

    def list_arguments(self):
        return ["data", "gate_weight", "expert_fc1_weight",
                "expert_fc1_bias", "expert_fc2_weight",
                "expert_fc2_bias"]

    def list_outputs(self):
        return ["output", "aux_loss"]

    def infer_shape(self, in_shapes):
        data = in_shapes[0]
        if data is None:
            require_known("MoE", in_shapes[:1], ["data"])
        if len(data) < 2:
            raise MXNetError("MoE: data must be (..., embed)")
        E = data[-1]
        K, H = self.param.num_experts, self.param.hidden_size
        if self.param.top_k > K:
            raise MXNetError("MoE: top_k (%d) > num_experts (%d)"
                             % (self.param.top_k, K))
        return ([data, (K, E), (K, H, E), (K, H), (K, E, H), (K, E)],
                [data, (1,)], [])

    def forward(self, inputs, aux, is_train, rng):
        x, wg, w1, b1, w2, b2 = inputs
        K = self.param.num_experts
        topk = min(self.param.top_k, K)
        shape = x.shape
        t = x.reshape(-1, shape[-1])                    # (T, E)
        probs, mask, combine = _routing(
            t, wg, K, topk, self.param.capacity_factor)

        h = jnp.einsum("te,khe->tkh", t, w1) + b1[None]
        h = jax.nn.relu(h)
        y = jnp.einsum("tkh,keh->tke", h, w2) + b2[None]
        out = jnp.einsum("tke,tk->te", y, combine)

        # load-balance aux (Switch eq. 4): K * <fraction, mean prob>;
        # fractions normalized by top_k so a balanced router scores 1
        frac = jnp.mean(mask, axis=0) / topk
        mean_p = jnp.mean(probs, axis=0)
        aux_loss = (K * jnp.sum(frac * mean_p)).reshape(1)
        return [out.reshape(shape), aux_loss], None


def expert_parallel_moe(x, wg, w1, b1, w2, b2, *, axis="ep", top_k=1,
                        capacity_factor=1.25):
    """Expert-parallel MoE block — CALL INSIDE shard_map over ``axis``.

    ``x`` is this member's token shard ``(..., E)``; ``w1/b1/w2/b2`` are
    the member's expert shard (leading dim ``K/ep``); ``wg`` is the full
    replicated gate ``(K, E)``.  Routing is computed locally, tokens are
    packed into per-expert capacity slots and exchanged with one
    ``lax.all_to_all`` (dispatch), the local experts run, and a second
    ``all_to_all`` returns the routed outputs (combine) — the exact
    collective pair the MXL-E008 lint prices.  Per-member capacity is
    ``ceil(cf * T_local * top_k / K)``; a ``capacity_factor`` is
    REQUIRED here (the packed exchange needs a static slot count).

    Matches the dense :class:`MoE` forward applied per member shard with
    the same capacity factor.
    """
    if not capacity_factor or capacity_factor <= 0:
        raise ValueError("expert_parallel_moe needs capacity_factor > 0")
    from ..parallel.pipeline import _axis_size
    ep = _axis_size(axis)
    K = wg.shape[0]
    k_local = w1.shape[0]
    if k_local * ep != K:
        raise ValueError("expert shard (%d) * ep (%d) != num_experts "
                         "(%d)" % (k_local, ep, K))
    shape = x.shape
    t = x.reshape(-1, shape[-1])                        # (Tl, E)
    probs, mask, combine = _routing(t, wg, K, top_k, capacity_factor)
    cap = moe_capacity(t.shape[0], K, top_k, capacity_factor)
    pos = jnp.cumsum(mask, axis=0) - mask
    # dispatch tensor (Tl, K, C): one-hot capacity slot per assignment
    dis = mask[:, :, None] * jax.nn.one_hot(pos, cap, dtype=t.dtype)
    expert_in = jnp.einsum("tkc,te->kce", dis, t)       # (K, C, E)
    # exchange: split experts across members, gather my experts' slots
    # from every member along the capacity dim -> (K/ep, ep*C, E)
    expert_in = lax.all_to_all(expert_in, axis, 0, 1, tiled=True)
    h = jax.nn.relu(
        jnp.einsum("kce,khe->kch", expert_in, w1) + b1[:, None, :])
    y = jnp.einsum("kch,keh->kce", h, w2) + b2[:, None, :]
    # return each member's slots to the token owner -> (K, C, E)
    y = lax.all_to_all(y, axis, 1, 0, tiled=True)
    out = jnp.einsum("tkc,kce->te", dis * combine[:, :, None], y)
    frac = jnp.mean(mask, axis=0) / min(int(top_k), K)
    aux_loss = K * jnp.sum(frac * jnp.mean(probs, axis=0))
    return out.reshape(shape), aux_loss


@register_sharding_rule("MoE")
def _moe_transfer(op, in_specs, in_shapes, out_shapes, mesh_shape):
    """Output follows the data spec; expert weights sharded over an
    expert-parallel axis turn the routed dispatch/combine into the
    all-to-all pair (priced per device like every reshard: each member
    keeps 1/ep of its tokens locally)."""
    data_spec = tuple(in_specs[0] or ())
    w1_spec = tuple(in_specs[2] or ())
    ep_axes = tuple(w1_spec[0]) if w1_spec else ()
    notes = []
    if ep_axes:
        for leg in ("dispatch", "combine"):
            notes.append({
                "kind": "alltoall", "arg": 0, "axes": ep_axes,
                "message": "MoE expert %s: routed tokens exchanged "
                           "with the %s expert shards over an "
                           "all-to-all" % (leg, "+".join(ep_axes))})
    aux_rank = len(out_shapes[1]) if len(out_shapes) > 1 and \
        out_shapes[1] is not None else 1
    return {"out": [data_spec, ((),) * aux_rank], "notes": notes}


@register_cost_rule("MoE")
def _moe_cost(op, in_shapes, out_shapes):
    """Price the ROUTED execution plan (each token visits ``top_k``
    experts), not the dense einsum the CPU reference computes — the
    TPU plan the analyzer validates is the expert-parallel one."""
    data = in_shapes[0]
    if data is None:
        return {}
    T = 1
    for d in data[:-1]:
        T *= int(d)
    E = int(data[-1])
    K = int(op.param.num_experts)
    H = int(op.param.hidden_size)
    topk = min(int(op.param.top_k), K)
    gate = 2.0 * T * K * E
    ffn = 2.0 * T * topk * E * H * 2
    return {"flops": gate + ffn, "mxu": True,
            "mxu_dims": [(T * topk, E, H), (T * topk, H, E)]}


# ----------------------------------------------------------------------
# RoutedExperts: top-k routing on sigmoid or softmax scores over all the
# experts, the experts held here computed by sorted, grouped matrix products
# ----------------------------------------------------------------------
# the device sub-scopes of a RoutedExperts node
# (observability/device_scopes.py); DISPATCH is the sort, the gathers and
# the scatters around the grouped products
ROUTE, DISPATCH, EXPERTS, SHARED = ROUTED_SCOPES

#: What the routed layer's backward is handed that is neither a weight nor
#: the layer's input — the chosen experts and their weights, the sorted
#: order and the counts — and the routed sum, each under a
#: ``checkpoint_name`` where it is made: a ``jax.checkpoint`` whose policy
#: saves these names (the executor's mirrored segments) makes no discrete
#: choice a second time, and where a backward reads the routed sum (a
#: learned scale after the layer) does not walk the chunks again to have
#: it.  The set is whole for the flash kernel's reason
#: (``ring_attention.FLASH_RESIDUALS``): a top-k taken again from scores
#: that differ in the last bit may order a near-tie otherwise, and an order
#: kept from the first call then sorts the wrong slots' weights.
ROUTED_RESIDUALS = ("routed_idx", "routed_w", "routed_order",
                    "routed_counts", "routed_out")
_IDX, _W, _ORDER, _COUNTS, _OUT = ROUTED_RESIDUALS


def gated_ffn(x, w_gate, w_up, w_down):
    """W_down(silu(W_gate x) ⊙ W_up x); weights (out_features, in_features)."""
    return (jax.nn.silu(x @ w_gate.T) * (x @ w_up.T)) @ w_down.T


def route_topk(scores, bias, top_k, scaling=1.0, normalize=True, n_group=1,
               topk_group=1):
    """``(idx (T, k) int32, w (T, k) float32)`` from float32 ``scores``
    (T, N): the ``top_k`` largest of scores + bias are chosen (the bias
    steers the choice only); the weights are the scores at the chosen
    experts — divided by their sum where ``normalize`` — times
    ``scaling``.  The choice carries no gradient; the weights carry the
    scores'.

    With ``n_group`` > 1 the choice is group-limited (DeepSeek-V3's):
    the N experts fall into ``n_group`` groups of N / n_group in order, a
    group scores the sum of its two largest scores + bias, and only the
    ``topk_group`` best groups' experts may be chosen — the others are
    masked to −∞ before the top-k."""
    choice = scores + bias.astype(jnp.float32)
    if n_group > 1:
        T, N = choice.shape
        grouped = choice.reshape(T, n_group, N // n_group)
        _, keep = lax.top_k(jnp.sum(lax.top_k(grouped, 2)[0], axis=-1),
                            topk_group)
        kept = jnp.any(keep[..., None] == jnp.arange(n_group), axis=1)
        choice = jnp.where(kept[:, :, None], grouped, -jnp.inf) \
            .reshape(T, N)
    _, idx = lax.top_k(choice, top_k)
    # named before anything reads it: the weights' backward reads the
    # choice too
    idx = checkpoint_name(idx.astype(jnp.int32), _IDX)
    chosen = idx[..., None] == jnp.arange(scores.shape[-1])
    w = jnp.sum(jnp.where(chosen, scores[:, None, :], 0.0), axis=-1)
    if normalize:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx, checkpoint_name(w * scaling, _W)


def sigmoid_scores(h, router_weight):
    """s = sigmoid(h W_rᵀ) (T, N), summed and kept in float32."""
    return jax.nn.sigmoid(jnp.dot(h, router_weight.T,
                                  preferred_element_type=jnp.float32))


def softmax_scores(h, router_weight):
    """p = softmax(h W_rᵀ) over all N experts (T, N), the logits summed and
    the softmax taken in float32."""
    return jax.nn.softmax(jnp.dot(h, router_weight.T,
                                  preferred_element_type=jnp.float32),
                          axis=-1)


def route_sigmoid_topk(h, router_weight, bias, top_k, scaling=1.0):
    """:func:`route_topk` over :func:`sigmoid_scores`, the chosen weights
    normalised."""
    return route_topk(sigmoid_scores(h, router_weight), bias, top_k, scaling)


def _sorted_assignments(idx, first, n_local):
    """The (token, expert) assignments sorted by expert held.

    ``idx`` (T, k): the experts each token chose.  Returns ``order`` (the
    T·k slots, those on experts ``first .. first + n_local − 1`` first
    and grouped by expert, in token order within an expert; every other
    slot after them) and ``counts`` (n_local,): the assignments on each
    expert held."""
    held = (idx >= first) & (idx < first + n_local)
    eid = jnp.where(held, idx - first, n_local).reshape(-1)
    order = jnp.argsort(eid, stable=True).astype(jnp.int32)
    counts = jnp.sum(eid[:, None] == jnp.arange(n_local)[None, :], axis=0,
                     dtype=jnp.int32)
    return checkpoint_name(order, _ORDER), checkpoint_name(counts, _COUNTS)


#: rows of the sorted assignment list that ``RoutedExperts`` computes at a
#: time: twice what a balanced router sends 16 of 256 experts at 8,192
#: tokens of 8 choices, so that the usual step is one chunk
CHUNK_ROWS = 8192


def _chunk_rows(n_slots, chunk_rows):
    """Rows of the sorted list computed at a time: ``chunk_rows`` where
    it divides the list, else the whole list."""
    return chunk_rows if n_slots % chunk_rows == 0 else n_slots


def _chunk_plan(c, rows, tok, w_sorted, counts):
    """What chunk ``c`` of the sorted list holds: its rows' tokens and
    weights, which of its rows are assignments on experts held, and how
    many rows of each expert held fall inside it."""
    lo = c * rows
    ends = jnp.cumsum(counts)
    starts = ends - counts
    sizes = jnp.clip(jnp.minimum(ends, lo + rows) - jnp.maximum(starts, lo),
                     0, None).astype(jnp.int32)
    valid = (lo + jnp.arange(rows)) < ends[-1]
    return (lax.dynamic_slice_in_dim(tok, lo, rows),
            lax.dynamic_slice_in_dim(w_sorted, lo, rows), valid[:, None],
            sizes)


def _grouped_ffn(x, w_gate, w_up, w_down, sizes):
    """The gated FFN of rows sorted by expert: row r of group e goes
    through expert e's three matrices (``lax.ragged_dot``: on a TPU one
    grouped Mosaic product a projection, which visits only the row tiles
    that groups cover).  Rows past the last group come out undefined:
    the caller masks them."""
    gate = lax.ragged_dot(x, w_gate.transpose(0, 2, 1), sizes)
    up = lax.ragged_dot(x, w_up.transpose(0, 2, 1), sizes)
    return lax.ragged_dot(jax.nn.silu(gate) * up,
                          w_down.transpose(0, 2, 1), sizes)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def routed_experts(h, w, idx, w_gate, w_up, w_down, first, chunk_rows):
    """Σ over a token's assignments on experts held of wᵢ · Eᵢ(h).

    ``h`` (T, E); ``idx``, ``w`` (T, k): every token's chosen experts and
    their weights; ``w_gate``/``w_up`` (L, H, E), ``w_down`` (L, E, H):
    the L experts held, which are experts ``first .. first + L − 1``.
    Every assignment on an expert held is computed, however many there
    are: the sorted list is walked in chunks of ``chunk_rows`` rows for
    as long as it holds such assignments (a balanced router fills the
    first chunk half; a router that sends everything here walks them
    all).  Assignments on other experts add nothing: theirs is another
    device's part.  Returns (y (T, E) in h's dtype, counts (L,) int32).
    """
    return _routed_fwd(h, w, idx, w_gate, w_up, w_down, first,
                       chunk_rows)[0]


def _routed_fwd(h, w, idx, w_gate, w_up, w_down, first, chunk_rows):
    n_tok, k = idx.shape
    rows = _chunk_rows(n_tok * k, chunk_rows)
    with jax.named_scope(DISPATCH):
        order, counts = _sorted_assignments(idx, first, w_gate.shape[0])
        tok = order // k
        w_sorted = w.reshape(-1)[order]

    def chunk(c, y):
        with jax.named_scope(DISPATCH):
            tok_c, w_c, valid, sizes = _chunk_plan(c, rows, tok, w_sorted,
                                                   counts)
            x = jnp.where(valid, h[tok_c], 0)
        with jax.named_scope(EXPERTS):
            o = _grouped_ffn(x, w_gate, w_up, w_down, sizes)
        with jax.named_scope(DISPATCH):
            o = jnp.where(valid, o, 0).astype(jnp.float32) * w_c[:, None]
            return y.at[tok_c].add(o)

    with jax.named_scope(DISPATCH):
        n_chunks = (jnp.sum(counts) + rows - 1) // rows
        y = lax.fori_loop(0, n_chunks, chunk,
                          jnp.zeros(h.shape, jnp.float32))
        # the routed sum, before a shared expert is added to it
        y = checkpoint_name(y.astype(h.dtype), _OUT)
    return ((y, counts),
            (h, w, idx, w_gate, w_up, w_down, order, counts))


def _routed_bwd(first, chunk_rows, res, cts):
    """Walks the same chunks: each recomputes its rows' FFN and takes
    its vjp, so nothing is kept from the forward's loop; the choice, its
    weights, the sorted order and the counts are the forward's own
    (``ROUTED_RESIDUALS``)."""
    h, w, idx, w_gate, w_up, w_down, order, counts = res
    dy = cts[0]
    n_tok, k = idx.shape
    rows = _chunk_rows(n_tok * k, chunk_rows)
    with jax.named_scope(DISPATCH):
        tok = order // k
        w_sorted = w.reshape(-1)[order]

    def chunk(c, carry):
        dh, dw_sorted, d_gate, d_up, d_down = carry
        with jax.named_scope(DISPATCH):
            tok_c, w_c, valid, sizes = _chunk_plan(c, rows, tok, w_sorted,
                                                   counts)
            x = jnp.where(valid, h[tok_c], 0)
            dy_c = jnp.where(valid, dy[tok_c], 0)
        with jax.named_scope(EXPERTS):
            o, vjp = jax.vjp(
                lambda x, a, b, c_: _grouped_ffn(x, a, b, c_, sizes),
                x, w_gate, w_up, w_down)
            o = jnp.where(valid, o, 0)
            dw_c = jnp.sum(o.astype(jnp.float32)
                           * dy_c.astype(jnp.float32), axis=-1)
            dx, dg, du, dd = vjp((dy_c.astype(jnp.float32)
                                  * w_c[:, None]).astype(o.dtype))
        with jax.named_scope(DISPATCH):
            dh = dh.at[tok_c].add(
                jnp.where(valid, dx, 0).astype(jnp.float32))
            dw_sorted = lax.dynamic_update_slice_in_dim(dw_sorted, dw_c,
                                                        c * rows, axis=0)
        with jax.named_scope(EXPERTS):
            return (dh, dw_sorted, d_gate + dg.astype(jnp.float32),
                    d_up + du.astype(jnp.float32),
                    d_down + dd.astype(jnp.float32))

    with jax.named_scope(DISPATCH):
        n_chunks = (jnp.sum(counts) + rows - 1) // rows
        zeros32 = functools.partial(jnp.zeros, dtype=jnp.float32)
        dh, dw_sorted, d_gate, d_up, d_down = lax.fori_loop(
            0, n_chunks, chunk,
            (zeros32(h.shape), zeros32((n_tok * k,)), zeros32(w_gate.shape),
             zeros32(w_up.shape), zeros32(w_down.shape)))
        # back from sorted rows to (token, choice) slots: the inverse of a
        # permutation is a gather too
        dw = dw_sorted[jnp.argsort(order)].reshape(n_tok, k)
    return (dh.astype(h.dtype), dw.astype(w.dtype), None,
            d_gate.astype(w_gate.dtype), d_up.astype(w_up.dtype),
            d_down.astype(w_down.dtype))


routed_experts.defvjp(_routed_fwd, _routed_bwd)


class _RoutedExpertsParam(ParamStruct):
    num_experts = Field(int, required=True, lower=2,
                        doc="experts the router scores (its width)")
    hidden_size = Field(int, required=True, lower=1,
                        doc="width of one expert's gated FFN")
    top_k = Field(int, required=True, lower=1)
    num_local_experts = Field(
        int, default=0, lower=0,
        doc="experts held here (0: all of them); the expert weights' "
            "leading axis, which an 'ep' mesh axis shards")
    first_expert = Field(int, default=0, lower=0,
                         doc="index of the first expert held")
    shared_hidden_size = Field(
        int, default=0, lower=0,
        doc="width of the shared expert every token goes through (0: none)")
    routed_scaling_factor = Field(float, default=1.0)
    score_func = Field(
        str, default="sigmoid", enum=("sigmoid", "softmax", "given"),
        doc="sigmoid / softmax: the op scores, s = sigmoid(h W_rᵀ) or "
            "softmax(h W_rᵀ) over all num_experts, in float32, with a "
            "router_weight of its own; given: the scores are the second "
            "input, (T, num_experts), made by a router outside the op "
            "(their gradient goes back to it)")
    shared_gate = Field(
        bool, default=False,
        doc="the shared expert's result is scaled by sigmoid(h w_sᵀ), "
            "w_s = shared_score_weight (1, E), a number a token")
    norm_topk_prob = Field(
        bool, default=True,
        doc="divide the chosen scores by their sum (False: a chosen "
            "score is the weight as it stands)")
    chunk_rows = Field(
        int, default=0, lower=0,
        doc="rows of the sorted assignment list computed at a time "
            "(0: CHUNK_ROWS)")
    n_group = Field(int, default=1, lower=1,
                    doc="groups the experts fall into, in order (1: no "
                        "group limit; route_topk)")
    topk_group = Field(int, default=1, lower=1,
                       doc="groups a token may choose its experts from")


#: the counters a RoutedExperts node keeps as auxiliary state, summed on
#: the device over the training steps it has run (int32; read them with
#: ``routing_counters``)
ROUTING_COUNTERS = ("local_assignments", "expert_tokens", "peak_tokens_sum",
                    "peak_tokens_max")


@register_op("RoutedExperts")
class RoutedExperts(OperatorProperty):
    """Routed gated-SiLU FFN over the experts held here, plus a shared one.

    data (..., E) -> (..., E).  s = sigmoid(h W_rᵀ) over all
    ``num_experts`` in float32 — or softmax(h W_rᵀ) with
    ``score_func="softmax"``, or, with ``score_func="given"``, the
    ``scores`` input as a router outside the op made it (a softmax over
    an MLP's logits, say); the ``top_k`` largest of s + ``router_bias``
    are chosen (the bias is auxiliary state: it steers the choice, takes
    no gradient and is left as it was), from the ``topk_group`` best of
    ``n_group`` groups where those are set (:func:`route_topk`); w = s at
    the chosen experts, normalised over them (``norm_topk_prob``) and
    scaled.  y = Σᵢ wᵢ Eᵢ(h) over the chosen
    experts *held here* (``first_expert .. first_expert +
    num_local_experts − 1``) — every such assignment, whatever the
    imbalance; what the other experts would add is another device's part
    and is left out — plus the shared expert, times sigmoid(h w_sᵀ) where
    ``shared_gate`` is set.  No biases; weights are
    (out_features, in_features), the experts' stacked on a leading axis.

    Auxiliary state besides the bias, summed over training steps:
    ``local_assignments`` (1,) assignments that landed on experts held;
    ``expert_tokens`` (L,) the same per expert; ``peak_tokens_sum`` (1,)
    the busiest expert's tokens, summed over steps; ``peak_tokens_max``
    (1,) its running maximum.
    """
    param_cls = _RoutedExpertsParam
    mxu = True

    def _held(self):
        return self.param.num_local_experts or self.param.num_experts

    def _given(self):
        return self.param.score_func == "given"

    def list_arguments(self):
        args = ["data", "scores" if self._given() else "router_weight",
                "expert_gate_weight", "expert_up_weight",
                "expert_down_weight"]
        if self.param.shared_hidden_size:
            args += ["shared_gate_weight", "shared_up_weight",
                     "shared_down_weight"]
            if self.param.shared_gate:
                args.append("shared_score_weight")
        return args

    def list_auxiliary_states(self):
        return ["router_bias"] + list(ROUTING_COUNTERS)

    def infer_shape(self, in_shapes):
        data = in_shapes[0]
        if data is None:
            require_known("RoutedExperts", in_shapes[:1], ["data"])
        p = self.param
        E, N, H, L = data[-1], p.num_experts, p.hidden_size, self._held()
        if p.top_k > N:
            raise MXNetError("RoutedExperts: top_k (%d) > num_experts (%d)"
                             % (p.top_k, N))
        if p.first_expert + L > N:
            raise MXNetError("RoutedExperts: experts %d..%d held of %d"
                             % (p.first_expert, p.first_expert + L - 1, N))
        if N % p.n_group or p.topk_group > p.n_group \
                or (p.n_group > 1 and N // p.n_group < 2) \
                or p.top_k > p.topk_group * (N // p.n_group):
            raise MXNetError("RoutedExperts: %d experts in %d groups, %d "
                             "groups kept, top %d" % (N, p.n_group,
                                                      p.topk_group, p.top_k))
        router = tuple(data[:-1]) + (N,) if self._given() else (N, E)
        shapes = [data, router, (L, H, E), (L, H, E), (L, E, H)]
        if p.shared_hidden_size:
            S = p.shared_hidden_size
            shapes += [(S, E), (S, E), (E, S)]
            if p.shared_gate:
                shapes.append((1, E))
        elif p.shared_gate:
            raise MXNetError("RoutedExperts: shared_gate without a shared "
                             "expert")
        return shapes, [data], [(N,), (1,), (L,), (1,), (1,)]

    def infer_type(self, in_types):
        known = [t for t in in_types if t is not None]
        base = known[0] if known else None
        return ([base] * len(self.list_arguments()), [base],
                [base] + [_np.dtype("int32")] * len(ROUTING_COUNTERS))

    def forward(self, inputs, aux, is_train, rng):
        p = self.param
        x, router, w_gate, w_up, w_down = inputs[:5]
        h = x.reshape(-1, x.shape[-1])
        with jax.named_scope(ROUTE):
            if self._given():
                scores = router.reshape(-1, p.num_experts) \
                    .astype(jnp.float32)
            elif p.score_func == "softmax":
                scores = softmax_scores(h, router)
            else:
                scores = sigmoid_scores(h, router)
            idx, w = route_topk(scores, aux[0], p.top_k,
                                p.routed_scaling_factor, p.norm_topk_prob,
                                p.n_group, p.topk_group)
        y, counts = routed_experts(h, w, idx, w_gate, w_up, w_down,
                                   p.first_expert,
                                   p.chunk_rows or CHUNK_ROWS)
        if p.shared_hidden_size:
            with jax.named_scope(SHARED):
                shared = gated_ffn(h, *inputs[5:8])
                if p.shared_gate:
                    gate = jax.nn.sigmoid(jnp.dot(
                        h, inputs[8].T, preferred_element_type=jnp.float32))
                    shared = (shared.astype(jnp.float32)
                              * gate).astype(y.dtype)
                y = y + shared
        if not is_train:
            return [y.reshape(x.shape)], None
        _bias, total, per_expert, peak_sum, peak_max = aux
        peak = jnp.max(counts).reshape(1)

        def add(old, new):
            return old + new.astype(old.dtype)

        return [y.reshape(x.shape)], [
            aux[0], add(total, jnp.sum(counts).reshape(1)),
            add(per_expert, counts), add(peak_sum, peak),
            jnp.maximum(peak_max, peak.astype(peak_max.dtype))]


class _MLPRouterParam(ParamStruct):
    num_experts = Field(int, required=True, lower=2,
                        doc="experts scored (the softmax's width)")
    hidden_size = Field(int, required=True, lower=1,
                        doc="width of the router's stream and of its MLP")
    has_state = Field(bool, default=True,
                      doc="takes the previous layer's router stream (False: "
                          "the first layer, whose stream starts at 0)")
    eps = Field(float, default=1e-5, doc="of the RMSNorm on the stream")


@register_op("MLPRouter")
class MLPRouter(OperatorProperty):
    """A router with a stream of its own (the ZAYA1 report,
    arXiv:2511.17127): data (T, E), state (T, R) -> scores (T, N)
    float32, state (T, R).

    r = h W_dᵀ;  s = r + γ · s_prev (γ a learned scalar; no s_prev and no
    γ where ``has_state`` is False);  z = W_3 gelu(W_2 gelu(W_1
    RMSNorm(s))) with tanh-approximated GELUs and no biases, the last
    product summed and kept in float32;  scores = softmax(z) over all
    ``num_experts`` in float32.  ``state`` out is s: the next layer's
    router mixes it in, so a layer's routing sees the layers before and
    its gradient reaches their W_d and γ.  Feed ``scores`` to
    ``RoutedExperts(score_func="given")``."""
    param_cls = _MLPRouterParam
    mxu = True

    def list_arguments(self):
        state = ["state", "state_gain"] if self.param.has_state else []
        return ["data"] + state + ["down_weight", "norm_gamma", "fc1_weight",
                                   "fc2_weight", "out_weight"]

    def list_outputs(self):
        return ["scores", "state"]

    def infer_shape(self, in_shapes):
        data = in_shapes[0]
        if data is None:
            require_known("MLPRouter", in_shapes[:1], ["data"])
        if len(data) != 2:
            raise MXNetError("MLPRouter: data must be (tokens, E)")
        T, E = data
        R, N = self.param.hidden_size, self.param.num_experts
        state = [(T, R), (1,)] if self.param.has_state else []
        return ([data] + state + [(R, E), (R,), (R, R), (R, R), (N, R)],
                [(T, N), (T, R)], [])

    def infer_type(self, in_types):
        known = [t for t in in_types if t is not None]
        base = known[0] if known else None
        return ([base] * len(self.list_arguments()),
                [_np.dtype("float32"), base], [])

    def forward(self, inputs, aux, is_train, rng):
        h, rest = inputs[0], list(inputs[1:])
        if self.param.has_state:
            prev, gain = rest[:2]
            rest = rest[2:]
        w_down, gamma, w1, w2, w_out = rest
        s = h @ w_down.T
        if self.param.has_state:
            s = s + gain * prev
        z = rms_norm(s, gamma, self.param.eps)
        z = jax.nn.gelu(z @ w1.T, approximate=True)
        z = jax.nn.gelu(z @ w2.T, approximate=True)
        logits = jnp.dot(z, w_out.T, preferred_element_type=jnp.float32)
        return [jax.nn.softmax(logits, axis=-1), s], None

    def cost_mxu_dims(self, in_shapes, out_shapes):
        T, E = in_shapes[0]
        R, N = self.param.hidden_size, self.param.num_experts
        return [(T, E, R), (T, R, R), (T, R, R), (T, R, N)]

    def cost_flops(self, in_shapes, out_shapes):
        return float(sum(2 * m * k * n for m, k, n in
                         self.cost_mxu_dims(in_shapes, out_shapes)))


def routing_counters(aux, node_name):
    """{counter: numpy array} of one RoutedExperts node out of an
    auxiliary-state dict (``<node>_<counter>`` keys)."""
    return {c: _np.asarray(aux["%s_%s" % (node_name, c)])
            for c in ROUTING_COUNTERS}


@register_sharding_rule("RoutedExperts")
def _routed_transfer(op, in_specs, in_shapes, out_shapes, mesh_shape):
    """Output follows the data spec.  Expert stacks sharded over an
    expert-parallel axis make every member hold a share of the experts:
    tokens reach the experts and the partial results come back over the
    all-to-all pair, priced as for ``MoE``; router and shared expert are
    replicated (every member computes them alike)."""
    data_spec = tuple(in_specs[0] or ())
    gate_spec = tuple(in_specs[2] or ())
    ep_axes = tuple(gate_spec[0]) if gate_spec else ()
    notes = []
    if ep_axes:
        for leg in ("dispatch", "combine"):
            notes.append({
                "kind": "alltoall", "arg": 0, "axes": ep_axes,
                "message": "RoutedExperts %s: routed tokens exchanged "
                           "with the %s expert shards over an "
                           "all-to-all" % (leg, "+".join(ep_axes))})
    required = [None] * len(in_specs)
    for i in (3, 4):                    # up, down split as gate does
        required[i] = (ep_axes,) + ((),) * 2
    return {"out": [data_spec], "in": required, "notes": notes}


@register_cost_rule("RoutedExperts")
def _routed_cost(op, in_shapes, out_shapes):
    """The routed plan: the router over every expert, ``top_k`` gated
    FFNs a token of which the share held here is computed, and the
    shared expert for every token."""
    data = in_shapes[0]
    if data is None:
        return {}
    T = 1
    for d in data[:-1]:
        T *= int(d)
    p = op.param
    E, N, H = int(data[-1]), int(p.num_experts), int(p.hidden_size)
    held = int(p.num_local_experts or N)
    rows = max(1, T * int(p.top_k) * held // N)     # expected, balanced
    S = int(p.shared_hidden_size)
    given = p.score_func == "given"     # the router is another node
    flops = (0.0 if given else 2.0 * T * N * E) + 6.0 * rows * E * H \
        + 6.0 * T * E * S + (2.0 * T * E if p.shared_gate else 0.0)
    dims = ([] if given else [(T, E, N)]) \
        + [(rows, E, H), (rows, E, H), (rows, H, E)]
    if S:
        dims += [(T, E, S), (T, E, S), (T, S, E)]
    return {"flops": flops, "mxu": True, "mxu_dims": dims}
