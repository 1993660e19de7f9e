"""Decoder-only LM of the DeepSeek-V3 layer family: latent attention,
sigmoid-routed experts with a shared one, a multi-token-prediction module.

Every block is pre-RMSNorm with two residual connections and no bias
anywhere:  x ← x + Attn(RMSNorm(x));  x ← x + FFN(RMSNorm(x)).  Attention
is ``MultiHeadLatentAttention`` (low-rank query and key/value paths, a
rotary slice, one rotary key for all heads).  The first
``first_k_dense`` blocks have a dense gated-SiLU FFN, every later one the
``RoutedExperts`` layer: the router scores all ``n_routed_experts``, and
the layer computes the part of the result that the ``n_local_experts``
it holds give (``first_expert`` onward; all of them by default), plus the
shared expert.  A final RMSNorm and an untied head give the next-token
distribution.

``num_nextn_predict_layers = 1`` adds the prediction module of the
DeepSeek-V3 report (arXiv:2412.19437, section 2.2): position i's main
stream before the final norm and the embedding of token i+1 — which is
position i's label — are normed, concatenated and projected back to
``dim``, go through one routed block and a norm of their own and then
through *the main head's weights*, against token i+2.  The embedding table
is the main one.  Its loss enters the step ``mtp_loss_weight`` times.

Inputs: ``data`` (B, S) token ids, ``softmax_label`` (B, S) the next
tokens, and with the module ``mtp_label`` (B, S) the tokens after those.
Outputs: the main head's probabilities first, the module's second.

Expert weights are named ``*_expert_*`` so that ``parallel.param_pspec``
shards their leading axis over an ``ep`` mesh axis.
"""
from __future__ import annotations

from .. import symbol as sym


def _gated_ffn(h, name, width, dim):
    """W_down(silu(W_gate h) ⊙ W_up h) on (T, dim) rows."""
    gate = sym.FullyConnected(data=h, num_hidden=width, no_bias=True,
                              name="%s_gate" % name)
    up = sym.FullyConnected(data=h, num_hidden=width, no_bias=True,
                            name="%s_up" % name)
    act = sym.Activation(data=gate, act_type="silu") * up
    return sym.FullyConnected(data=act, num_hidden=dim, no_bias=True,
                              name="%s_down" % name)


def decoder_block(x, name, seq_len, dim, attention, eps, dense_width=0,
                  routed=None):
    """One block on x (B, S, dim): latent attention, then the dense gated
    FFN of ``dense_width`` or the routed layer (``routed``: the keyword
    arguments of ``RoutedExperts``)."""
    h = sym.RMSNorm(data=x, eps=eps, name="%s_norm1" % name)
    x = x + sym.MultiHeadLatentAttention(data=h, eps=eps,
                                         name="%s_att" % name, **attention)
    h = sym.RMSNorm(data=x, eps=eps, name="%s_norm2" % name)
    h = sym.Reshape(data=h, shape=(-1, dim))
    if dense_width:
        f = _gated_ffn(h, "%s_ffn" % name, dense_width, dim)
    else:
        f = sym.RoutedExperts(data=h, name="%s_moe" % name, **routed)
    return x + sym.Reshape(data=f, shape=(-1, seq_len, dim),
                           name="%s_ffn_out" % name)


def routed_layer_names(num_layers, first_k_dense=1,
                       num_nextn_predict_layers=1):
    """Names of the ``RoutedExperts`` nodes :func:`get_symbol` builds
    (their counters are ``<name>_<counter>`` auxiliary states)."""
    names = ["layer%d_moe" % i for i in range(first_k_dense, num_layers)]
    if num_nextn_predict_layers:
        names.append("mtp_moe")
    return names


def get_symbol(vocab_size=32000, num_layers=4, first_k_dense=1, dim=256,
               seq_len=512, num_heads=8, q_lora_rank=96, kv_lora_rank=64,
               qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32,
               rope_theta=10000.0, intermediate_size=1024,
               moe_intermediate_size=128, n_routed_experts=16,
               n_local_experts=0, first_expert=0, n_shared_experts=1,
               num_experts_per_tok=4, routed_scaling_factor=1.0,
               rms_norm_eps=1e-6,
               num_nextn_predict_layers=1, mtp_loss_weight=0.3,
               mirror_blocks=False):
    """The LM symbol (module docstring).  ``mirror_blocks=True`` makes
    the backward pass recompute each block from its input (per-layer
    recomputation, as ``models.transformer`` has it; what the attention
    kernel and the routed layer hand their backward is kept:
    ``attribute.mirror_scope``)."""
    from ..attribute import mirror_scope
    if num_nextn_predict_layers not in (0, 1):
        raise ValueError("one prediction module at the most (depth 1)")

    attention = dict(num_heads=num_heads, q_lora_rank=q_lora_rank,
                     kv_lora_rank=kv_lora_rank,
                     qk_nope_head_dim=qk_nope_head_dim,
                     qk_rope_head_dim=qk_rope_head_dim,
                     v_head_dim=v_head_dim, rope_theta=rope_theta)
    routed = dict(num_experts=n_routed_experts,
                  num_local_experts=n_local_experts,
                  first_expert=first_expert,
                  hidden_size=moe_intermediate_size,
                  top_k=num_experts_per_tok,
                  shared_hidden_size=n_shared_experts * moe_intermediate_size,
                  routed_scaling_factor=routed_scaling_factor)

    def scope(name):
        return mirror_scope(name, enabled=mirror_blocks)

    embed = sym.Variable("tok_embed_weight")
    head = sym.Variable("lm_head_weight")

    def predict(x, label, name, final_norm, grad_scale=1.0):
        """norm -> the head -> softmax against ``label``."""
        x = sym.RMSNorm(data=x, eps=rms_norm_eps, name=final_norm)
        logits = sym.FullyConnected(
            data=sym.Reshape(data=x, shape=(-1, dim)), weight=head,
            num_hidden=vocab_size, no_bias=True, name="%s_logits" % name)
        return sym.SoftmaxOutput(
            data=logits, label=sym.Reshape(data=label, shape=(-1,)),
            grad_scale=grad_scale, name=name)

    data = sym.Variable("data")
    label = sym.Variable("softmax_label")
    x = sym.Embedding(data=data, weight=embed, input_dim=vocab_size,
                      output_dim=dim, name="tok_embed")
    for i in range(num_layers):
        name = "layer%d" % i
        with scope(name):
            x = decoder_block(
                x, name, seq_len, dim, attention, rms_norm_eps,
                dense_width=intermediate_size if i < first_k_dense else 0,
                routed=routed)
    main = predict(x, label, "softmax", "final_norm")
    if not num_nextn_predict_layers:
        return main

    with scope("mtp"):
        nxt = sym.Embedding(data=label, weight=embed, input_dim=vocab_size,
                            output_dim=dim, name="mtp_embed")
        both = sym.Concat(
            sym.RMSNorm(data=x, eps=rms_norm_eps, name="mtp_hnorm"),
            sym.RMSNorm(data=nxt, eps=rms_norm_eps, name="mtp_enorm"),
            dim=2)
        h = sym.FullyConnected(
            data=sym.Reshape(data=both, shape=(-1, 2 * dim)),
            num_hidden=dim, no_bias=True, name="mtp_proj")
        h = decoder_block(sym.Reshape(data=h, shape=(-1, seq_len, dim)),
                          "mtp", seq_len, dim, attention, rms_norm_eps,
                          routed=routed)
    mtp = predict(h, sym.Variable("mtp_label"), "mtp_softmax",
                  "mtp_final_norm", grad_scale=mtp_loss_weight)
    return sym.Group([main, mtp])
