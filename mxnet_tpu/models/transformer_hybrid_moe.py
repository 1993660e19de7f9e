"""Decoder-only LM of the Qwen3-Next layer family: a hybrid of linear and
softmax attention over a mixture of experts.

Every block is pre-RMSNorm with two residual connections and no bias
anywhere:  x ← x + Mixer(RMSNorm(x));  x ← x + MoE(RMSNorm(x)).  Layer i
(0-based) mixes tokens with softmax attention where
(i + 1) % ``full_attention_interval`` = 0 — ``GatedAttention``: grouped
query heads, RMSNorm on q and k, a partial rotary, a sigmoid gate on the
output — and with the gated delta rule otherwise (``GatedDeltaNet``: a
fixed-size decayed state a head, a short causal convolution in front of
it).  Every layer's FFN is ``RoutedExperts`` on a softmax over all
``num_experts``: ``num_experts_per_tok`` a token, their weights normalised
over the chosen ones, plus one shared expert behind a sigmoid gate; the
layer computes the part of the result that the ``n_local_experts`` it
holds give (``first_expert`` onward; all of them by default).  A final
RMSNorm and an untied head give the next-token distribution.

The family writes its RMSNorm x / rms(x) · (1 + w) with w seeded 0; with no
weight decay that is ``RMSNorm``'s x / rms(x) · g with g seeded 1 — the same
function of the stored number and the same gradient — so the repo's op is
used as it is, gains seeded 1.

Inputs: ``data`` (B, S) token ids, ``softmax_label`` (B, S) the next tokens.

Expert weights are named ``*_expert_*`` so that ``parallel.param_pspec``
shards their leading axis over an ``ep`` mesh axis.
"""
from __future__ import annotations

from .. import symbol as sym


def layer_kinds(num_layers, full_attention_interval):
    """'full_attention' or 'linear_attention' for each of the layers."""
    return ["full_attention" if (i + 1) % full_attention_interval == 0
            else "linear_attention" for i in range(num_layers)]


def decoder_block(x, name, kind, seq_len, dim, attention, delta, routed, eps):
    """One block on x (B, S, dim); ``attention``, ``delta`` and ``routed``
    are the keyword arguments of ``GatedAttention``, ``GatedDeltaNet`` and
    ``RoutedExperts``."""
    h = sym.RMSNorm(data=x, eps=eps, name="%s_norm1" % name)
    if kind == "full_attention":
        x = x + sym.GatedAttention(data=h, eps=eps, name="%s_att" % name,
                                   **attention)
    else:
        x = x + sym.GatedDeltaNet(data=h, eps=eps, name="%s_gdn" % name,
                                  **delta)
    h = sym.RMSNorm(data=x, eps=eps, name="%s_norm2" % name)
    f = sym.RoutedExperts(data=sym.Reshape(data=h, shape=(-1, dim)),
                          name="%s_moe" % name, **routed)
    return x + sym.Reshape(data=f, shape=(-1, seq_len, dim),
                           name="%s_ffn_out" % name)


def routed_layer_names(num_layers):
    """Names of the ``RoutedExperts`` nodes :func:`get_symbol` builds
    (their counters are ``<name>_<counter>`` auxiliary states)."""
    return ["layer%d_moe" % i for i in range(num_layers)]


def get_symbol(vocab_size=32000, num_layers=4, dim=256, seq_len=512,
               full_attention_interval=4, num_heads=8, num_kv_heads=2,
               head_dim=32, rope_theta=10000.0, partial_rotary_factor=0.25,
               linear_num_key_heads=4, linear_num_value_heads=8,
               linear_key_head_dim=32, linear_value_head_dim=32,
               linear_conv_kernel_dim=4, delta_chunk=64,
               moe_intermediate_size=128, shared_expert_intermediate_size=128,
               num_experts=16, n_local_experts=0, first_expert=0,
               num_experts_per_tok=4, rms_norm_eps=1e-6, mirror_blocks=False):
    """The LM symbol (module docstring).  ``mirror_blocks=True`` makes the
    backward pass recompute each block from its input (per-layer
    recomputation; what the attention kernel, the delta rule's forward
    sweep and the routed layer hand their backward is kept:
    ``attribute.mirror_scope``)."""
    from ..attribute import mirror_scope
    attention = dict(num_heads=num_heads, num_kv_heads=num_kv_heads,
                     head_dim=head_dim, rope_theta=rope_theta,
                     partial_rotary_factor=partial_rotary_factor)
    delta = dict(num_key_heads=linear_num_key_heads,
                 num_value_heads=linear_num_value_heads,
                 key_head_dim=linear_key_head_dim,
                 value_head_dim=linear_value_head_dim,
                 conv_taps=linear_conv_kernel_dim, chunk=delta_chunk)
    routed = dict(num_experts=num_experts,
                  num_local_experts=n_local_experts,
                  first_expert=first_expert,
                  hidden_size=moe_intermediate_size,
                  top_k=num_experts_per_tok, score_func="softmax",
                  norm_topk_prob=True,
                  shared_hidden_size=shared_expert_intermediate_size,
                  shared_gate=bool(shared_expert_intermediate_size))

    data = sym.Variable("data")
    label = sym.Variable("softmax_label")
    x = sym.Embedding(data=data, input_dim=vocab_size, output_dim=dim,
                      name="tok_embed")
    kinds = layer_kinds(num_layers, full_attention_interval)
    for i, kind in enumerate(kinds):
        name = "layer%d" % i
        with mirror_scope(name, enabled=mirror_blocks):
            x = decoder_block(x, name, kind, seq_len, dim, attention, delta,
                              routed, rms_norm_eps)
    x = sym.RMSNorm(data=x, eps=rms_norm_eps, name="final_norm")
    logits = sym.FullyConnected(
        data=sym.Reshape(data=x, shape=(-1, dim)), num_hidden=vocab_size,
        no_bias=True, name="lm_head")
    return sym.SoftmaxOutput(
        data=logits, label=sym.Reshape(data=label, shape=(-1,)),
        name="softmax")
