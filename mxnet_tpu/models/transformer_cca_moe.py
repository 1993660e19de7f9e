"""Decoder-only LM of the ZAYA1 layer family: attention inside a compressed,
convolution-mixed latent with grouped query heads, a top-1 mixture of experts
whose router is an MLP with a stream of its own, scaled residuals, and a head
tied to the embedding.

Two streams go from layer to layer: the residual x (B, S, dim) and the
router's s (B·S, router_hidden_size).  One layer, with RMSNorm of a learned
gain and scale-and-bias vectors of ``dim`` (seeded 1 and 0) on the residual
and on each sublayer's output (the ZAYA1 report, arXiv:2511.17127):

    h = (a1 ⊙ x + b1) + (c1 ⊙ CCA(RMSNorm(x)) + e1)
    y = (a2 ⊙ h + b2) + (c2 ⊙ MoE(RMSNorm(h), s) + e2)

CCA is ``CompressedConvAttention`` (arXiv:2510.04476).  MoE is ``MLPRouter``
— which takes the previous layer's s, hands on its own and scores all
``num_experts`` — followed by ``RoutedExperts`` on those scores: one expert a
token (``num_experts_per_tok``), the softmax score itself as the gate, no
shared expert; the layer computes the part that the ``n_local_experts`` it
holds give (``first_expert`` onward; all of them by default).  The first
layer's router has no stream to take and no gain for it.  A final RMSNorm
and the embedding matrix as the head (one variable read by ``Embedding`` and
by the head's ``FullyConnected``, so its gradient is the sum of both uses)
give the next-token distribution.  No biases in any product.

Inputs: ``data`` (B, S) token ids, ``softmax_label`` (B, S) the next tokens.

Expert weights are named ``*_expert_*`` so that ``parallel.param_pspec``
shards their leading axis over an ``ep`` mesh axis.
"""
from __future__ import annotations

from .. import symbol as sym


def _scale_shift(x, name, dim):
    """scale ⊙ x + bias over the last axis, both vectors of ``dim``."""
    return x * sym.Variable("%s_scale" % name, shape=(dim,)) \
        + sym.Variable("%s_bias" % name, shape=(dim,))


def decoder_block(x, state, name, seq_len, dim, attention, router, routed,
                  eps):
    """One layer on the residual x (B, S, dim) and the router's stream
    ``state`` (B·S, router width; None for the first layer): -> (x, state).
    ``attention``, ``router``, ``routed`` are the keyword arguments of
    ``CompressedConvAttention``, ``MLPRouter`` and ``RoutedExperts``."""
    h = sym.RMSNorm(data=x, eps=eps, name="%s_norm1" % name)
    att = sym.CompressedConvAttention(data=h, name="%s_att" % name,
                                      **attention)
    x = _scale_shift(x, "%s_res1" % name, dim) \
        + _scale_shift(att, "%s_att_out" % name, dim)
    h = sym.RMSNorm(data=x, eps=eps, name="%s_norm2" % name)
    h = sym.Reshape(data=h, shape=(-1, dim))
    stream = {} if state is None else {"state": state}
    scored = sym.MLPRouter(data=h, has_state=state is not None,
                           name="%s_router" % name, **dict(router, **stream))
    f = sym.RoutedExperts(data=h, scores=scored[0], name="%s_moe" % name,
                          **routed)
    f = sym.Reshape(data=f, shape=(-1, seq_len, dim))
    x = _scale_shift(x, "%s_res2" % name, dim) \
        + _scale_shift(f, "%s_moe_out" % name, dim)
    return x, scored[1]


def routed_layer_names(num_layers):
    """Names of the ``RoutedExperts`` nodes :func:`get_symbol` builds
    (their counters are ``<name>_<counter>`` auxiliary states)."""
    return ["layer%d_moe" % i for i in range(num_layers)]


def get_symbol(vocab_size=32000, num_layers=4, dim=256, seq_len=512,
               num_heads=8, num_kv_heads=2, head_dim=16, cca_time0=2,
               cca_time1=2, rope_theta=10000.0, partial_rotary_factor=0.5,
               moe_intermediate_size=128, num_experts=16, n_local_experts=0,
               first_expert=0, num_experts_per_tok=1, router_hidden_size=32,
               rms_norm_eps=1e-5, mirror_blocks=False):
    """The LM symbol (module docstring).  ``mirror_blocks=True`` makes
    the backward pass recompute each layer from its two inputs (per-layer
    recomputation, as ``models.transformer`` has it; what the attention
    kernel and the routed layer hand their backward is kept, and the
    routed sum, which the learned scale after the layer reads:
    ``attribute.mirror_scope``)."""
    from ..attribute import mirror_scope
    attention = dict(num_heads=num_heads, num_kv_heads=num_kv_heads,
                     head_dim=head_dim, conv_taps0=cca_time0,
                     conv_taps1=cca_time1, rope_theta=rope_theta,
                     partial_rotary_factor=partial_rotary_factor,
                     eps=rms_norm_eps)
    router = dict(num_experts=num_experts, hidden_size=router_hidden_size,
                  eps=rms_norm_eps)
    routed = dict(num_experts=num_experts,
                  num_local_experts=n_local_experts,
                  first_expert=first_expert,
                  hidden_size=moe_intermediate_size,
                  top_k=num_experts_per_tok, score_func="given",
                  norm_topk_prob=False)

    embed = sym.Variable("tok_embed_weight")
    data = sym.Variable("data")
    label = sym.Variable("softmax_label")
    x = sym.Embedding(data=data, weight=embed, input_dim=vocab_size,
                      output_dim=dim, name="tok_embed")
    state = None
    for i in range(num_layers):
        name = "layer%d" % i
        with mirror_scope(name, enabled=mirror_blocks):
            x, state = decoder_block(x, state, name, seq_len, dim, attention,
                                     router, routed, rms_norm_eps)
    x = sym.RMSNorm(data=x, eps=rms_norm_eps, name="final_norm")
    logits = sym.FullyConnected(
        data=sym.Reshape(data=x, shape=(-1, dim)), weight=embed,
        num_hidden=vocab_size, no_bias=True, name="softmax_logits")
    return sym.SoftmaxOutput(
        data=logits, label=sym.Reshape(data=label, shape=(-1,)),
        name="softmax")
