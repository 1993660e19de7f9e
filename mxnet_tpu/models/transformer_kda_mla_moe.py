"""Decoder-only LM of the Ling-3.0 hybrid layer family: Kimi delta attention
and latent attention by layer over a mixture of experts.

Every block is pre-RMSNorm with two residual connections and no bias
anywhere:  x ← x + Mixer(RMSNorm(x));  x ← x + FFN(RMSNorm(x)).  Each layer
reads its kind from per-layer lists:

- ``mixer_types[i]``: ``"kda"`` — ``KimiDeltaAttention``: a fixed-size state
  a head decayed by one number a key channel, short causal convolutions in
  front of it, a gated output norm — or ``"mla"`` —
  ``MultiHeadLatentAttention`` with a direct query (no low-rank path), an
  RMSNorm on q a head, half-split rotary on the shared rotary slice, and a
  sigmoid gate a head on the output;
- ``mlp_layer_types[i]``: ``"dense"`` — a gated SiLU FFN of
  ``intermediate_size`` — or ``"sparse"`` — ``RoutedExperts`` on sigmoid
  scores over all ``num_experts``, group-limited (``n_group`` groups, the
  ``topk_group`` best kept), ``num_experts_per_tok`` a token, their weights
  normalised over the chosen and times ``routed_scaling_factor``, plus an
  ungated shared expert; the layer computes the part of the result that the
  ``n_local_experts`` it holds give (``first_expert`` onward; all of them by
  default), walking the assignments on them in chunks of twice what a
  balanced router sends them from a sequence.

A final RMSNorm and an untied head give the next-token distribution.

Inputs: ``data`` (B, S) token ids, ``softmax_label`` (B, S) the next tokens.

Expert weights are named ``*_expert_*`` so that ``parallel.param_pspec``
shards their leading axis over an ``ep`` mesh axis.
"""
from __future__ import annotations

from .. import symbol as sym
from .transformer_mla_moe import _gated_ffn
from .transformer_swa_moe import chunk_rows

MIXERS = ("kda", "mla")
FFNS = ("dense", "sparse")


def routed_layer_names(mlp_layer_types):
    """Names of the ``RoutedExperts`` nodes :func:`get_symbol` builds
    (their counters are ``<name>_<counter>`` auxiliary states)."""
    return ["layer%d_moe" % i for i, kind in enumerate(mlp_layer_types)
            if kind == "sparse"]


def mixer_node(i, kind):
    """The graph node of layer ``i``'s mixer."""
    return "layer%d_%s" % (i, "kda" if kind == "kda" else "att")


def get_symbol(vocab_size=32000, seq_len=512, dim=256,
               mixer_types=("kda", "mla"), mlp_layer_types=("dense",
                                                            "sparse"),
               num_heads=4, head_dim=128, conv_taps=4, kda_lower_bound=-5.0,
               delta_chunk=64, kv_lora_rank=64, qk_nope_head_dim=32,
               qk_rope_head_dim=16, v_head_dim=32, rope_theta=10000.0,
               intermediate_size=1024, moe_intermediate_size=128,
               shared_expert_intermediate_size=128, num_experts=16,
               n_local_experts=0, first_expert=0, num_experts_per_tok=4,
               n_group=1, topk_group=1, routed_scaling_factor=1.0,
               rms_norm_eps=1e-6, mirror_blocks=False):
    """The LM symbol (module docstring).  ``mirror_blocks=True`` makes the
    backward pass recompute each block from its input (per-layer
    recomputation; what the attention kernel, the KDA rule's forward sweep
    and the routed layer hand their backward is kept:
    ``attribute.mirror_scope``)."""
    from ..attribute import mirror_scope
    if len(mixer_types) != len(mlp_layer_types):
        raise ValueError("one entry a layer in mixer_types and "
                         "mlp_layer_types")
    kda = dict(num_heads=num_heads, head_dim=head_dim, conv_taps=conv_taps,
               gate_lower_bound=kda_lower_bound, chunk=delta_chunk)
    mla = dict(num_heads=num_heads, q_lora_rank=0, kv_lora_rank=kv_lora_rank,
               qk_nope_head_dim=qk_nope_head_dim,
               qk_rope_head_dim=qk_rope_head_dim, v_head_dim=v_head_dim,
               rope_theta=rope_theta, rope_interleave=False, q_norm=True,
               gate="headwise")
    routed = dict(num_experts=num_experts, num_local_experts=n_local_experts,
                  first_expert=first_expert,
                  hidden_size=moe_intermediate_size,
                  top_k=num_experts_per_tok, score_func="sigmoid",
                  norm_topk_prob=True, n_group=n_group,
                  topk_group=topk_group,
                  shared_hidden_size=shared_expert_intermediate_size,
                  routed_scaling_factor=routed_scaling_factor,
                  chunk_rows=chunk_rows(seq_len, num_experts_per_tok,
                                        n_local_experts or num_experts,
                                        num_experts))
    eps = rms_norm_eps

    data = sym.Variable("data")
    label = sym.Variable("softmax_label")
    x = sym.Embedding(data=data, input_dim=vocab_size, output_dim=dim,
                      name="tok_embed")
    for i, (mixer, mlp) in enumerate(zip(mixer_types, mlp_layer_types)):
        if mixer not in MIXERS or mlp not in FFNS:
            raise ValueError("layer %d: %r mixer, %r FFN" % (i, mixer, mlp))
        name = "layer%d" % i
        with mirror_scope(name, enabled=mirror_blocks):
            h = sym.RMSNorm(data=x, eps=eps, name="%s_norm1" % name)
            if mixer == "kda":
                x = x + sym.KimiDeltaAttention(
                    data=h, eps=eps, name=mixer_node(i, mixer), **kda)
            else:
                x = x + sym.MultiHeadLatentAttention(
                    data=h, eps=eps, name=mixer_node(i, mixer), **mla)
            h = sym.Reshape(data=sym.RMSNorm(data=x, eps=eps,
                                             name="%s_norm2" % name),
                            shape=(-1, dim))
            if mlp == "dense":
                f = _gated_ffn(h, "%s_ffn" % name, intermediate_size, dim)
            else:
                f = sym.RoutedExperts(data=h, name="%s_moe" % name, **routed)
            x = x + sym.Reshape(data=f, shape=(-1, seq_len, dim),
                                name="%s_ffn_out" % name)
    x = sym.RMSNorm(data=x, eps=eps, name="final_norm")
    logits = sym.FullyConnected(
        data=sym.Reshape(data=x, shape=(-1, dim)), num_hidden=vocab_size,
        no_bias=True, name="lm_head")
    return sym.SoftmaxOutput(
        data=logits, label=sym.Reshape(data=label, shape=(-1,)),
        name="softmax")
