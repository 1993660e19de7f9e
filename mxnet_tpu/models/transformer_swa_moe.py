"""Decoder-only LM of the Laguna layer family: sliding-window and full
attention by layer over a mixture of experts.

Every block is pre-RMSNorm with two residual connections and no bias
anywhere:  x ← x + Attn(RMSNorm(x));  x ← x + FFN(RMSNorm(x)).  Each layer
reads its own kind from per-layer lists:

- ``layer_types[i]``: ``"full_attention"`` or ``"sliding_attention"`` —
  ``GatedAttention`` over every key before a query, or over the
  ``sliding_window`` keys up to and including it; each kind with its own
  rotary (``rope_parameters[kind]``: theta, the share of a head rotated,
  and ``rope_type`` ``"default"`` or ``"yarn"`` with YaRN's factor,
  original context, β_fast, β_slow and attention factor);
- ``num_attention_heads_per_layer[i]``: the layer's query heads, all on
  the same ``num_kv_heads`` key/value heads of ``head_dim``;
- ``mlp_layer_types[i]``: ``"dense"`` — a gated SiLU FFN of
  ``intermediate_size`` — or ``"sparse"`` — ``RoutedExperts`` on sigmoid
  scores over all ``num_experts``: ``num_experts_per_tok`` a token, their
  weights normalised over the chosen and times ``routed_scaling_factor``,
  plus an ungated shared expert; the layer computes the part of the result
  that the ``n_local_experts`` it holds give (``first_expert`` onward; all
  of them by default), walking the assignments on them in chunks of twice
  what a balanced router sends them from a sequence.

q and k are not normalised; each head's attention output passes one sigmoid
gate, a column of the query projection (``GatedAttention``'s headwise gate).  A final RMSNorm and an untied head give the
next-token distribution.

Inputs: ``data`` (B, S) token ids, ``softmax_label`` (B, S) the next tokens.

Expert weights are named ``*_expert_*`` so that ``parallel.param_pspec``
shards their leading axis over an ``ep`` mesh axis.
"""
from __future__ import annotations

from .. import symbol as sym
from .transformer_mla_moe import _gated_ffn

#: the rotary keys of a layer kind's ``rope_parameters`` entry and the
#: ``GatedAttention`` fields they set
_ROPE_FIELDS = (("rope_theta", "rope_theta"),
                ("partial_rotary_factor", "partial_rotary_factor"),
                ("rope_type", "rope_type"), ("factor", "rope_factor"),
                ("original_max_position_embeddings",
                 "rope_original_max_position"),
                ("beta_fast", "rope_beta_fast"),
                ("beta_slow", "rope_beta_slow"),
                ("attention_factor", "rope_attention_factor"))


def attention_args(kind, heads, num_kv_heads, head_dim, sliding_window,
                   rope):
    """``GatedAttention``'s keyword arguments for one layer."""
    args = dict(num_heads=heads, num_kv_heads=num_kv_heads,
                head_dim=head_dim, qk_norm=False, gate="headwise",
                window=sliding_window if kind == "sliding_attention" else 0)
    for key, field in _ROPE_FIELDS:
        if key in rope:
            args[field] = rope[key]
    return args


def decoder_block(x, name, seq_len, dim, attention, eps, dense_width=0,
                  routed=None):
    """One block on x (B, S, dim): gated attention (``attention``: the
    keyword arguments of ``GatedAttention``), then the dense gated FFN of
    ``dense_width`` or the routed layer (``routed``: those of
    ``RoutedExperts``)."""
    h = sym.RMSNorm(data=x, eps=eps, name="%s_norm1" % name)
    x = x + sym.GatedAttention(data=h, eps=eps, name="%s_att" % name,
                               **attention)
    h = sym.RMSNorm(data=x, eps=eps, name="%s_norm2" % name)
    h = sym.Reshape(data=h, shape=(-1, dim))
    if dense_width:
        f = _gated_ffn(h, "%s_ffn" % name, dense_width, dim)
    else:
        f = sym.RoutedExperts(data=h, name="%s_moe" % name, **routed)
    return x + sym.Reshape(data=f, shape=(-1, seq_len, dim),
                           name="%s_ffn_out" % name)


def chunk_rows(seq_len, top_k, held, num_experts):
    """Rows of a routed layer's sorted assignments computed at a time:
    twice what a balanced router sends the ``held`` experts from one
    sequence, so that a step walks one chunk.  At 32 of 256 experts, 8 a
    token, the shared default (``ops.moe.CHUNK_ROWS``) is what a balanced
    router sends, and a step walked one chunk or two as the tokens fell."""
    return max(1, 2 * seq_len * top_k * held // num_experts)


def routed_layer_names(mlp_layer_types):
    """Names of the ``RoutedExperts`` nodes :func:`get_symbol` builds
    (their counters are ``<name>_<counter>`` auxiliary states)."""
    return ["layer%d_moe" % i for i, kind in enumerate(mlp_layer_types)
            if kind == "sparse"]


def get_symbol(vocab_size=32000, seq_len=512, dim=256,
               layer_types=("full_attention", "sliding_attention"),
               num_attention_heads_per_layer=(4, 8),
               mlp_layer_types=("dense", "sparse"), num_kv_heads=2,
               head_dim=32, sliding_window=64, rope_parameters=None,
               intermediate_size=1024, moe_intermediate_size=128,
               shared_expert_intermediate_size=128, num_experts=16,
               n_local_experts=0, first_expert=0, num_experts_per_tok=4,
               routed_scaling_factor=1.0, rms_norm_eps=1e-6,
               mirror_blocks=False):
    """The LM symbol (module docstring).  ``rope_parameters`` maps each
    layer kind to its rotary (plain rotary at theta 10,000 on the whole
    head where a kind is missing).  ``mirror_blocks=True`` makes the
    backward pass recompute each block from its input (per-layer
    recomputation; what the attention kernel and the routed layer hand
    their backward is kept: ``attribute.mirror_scope``)."""
    from ..attribute import mirror_scope
    layers = len(layer_types)
    if not len(num_attention_heads_per_layer) == len(mlp_layer_types) \
            == layers:
        raise ValueError("one entry a layer in layer_types, "
                         "num_attention_heads_per_layer and mlp_layer_types")
    routed = dict(num_experts=num_experts, num_local_experts=n_local_experts,
                  first_expert=first_expert,
                  hidden_size=moe_intermediate_size,
                  top_k=num_experts_per_tok, score_func="sigmoid",
                  norm_topk_prob=True,
                  shared_hidden_size=shared_expert_intermediate_size,
                  routed_scaling_factor=routed_scaling_factor,
                  chunk_rows=chunk_rows(seq_len, num_experts_per_tok,
                                        n_local_experts or num_experts,
                                        num_experts))
    rope_parameters = rope_parameters or {}

    data = sym.Variable("data")
    label = sym.Variable("softmax_label")
    x = sym.Embedding(data=data, input_dim=vocab_size, output_dim=dim,
                      name="tok_embed")
    for i, (kind, heads, mlp) in enumerate(zip(
            layer_types, num_attention_heads_per_layer, mlp_layer_types)):
        if mlp not in ("dense", "sparse") or kind not in (
                "full_attention", "sliding_attention"):
            raise ValueError("layer %d: %r attention, %r FFN" % (i, kind,
                                                                mlp))
        name = "layer%d" % i
        attention = attention_args(kind, int(heads), num_kv_heads, head_dim,
                                   sliding_window,
                                   rope_parameters.get(kind, {}))
        with mirror_scope(name, enabled=mirror_blocks):
            x = decoder_block(
                x, name, seq_len, dim, attention, rms_norm_eps,
                dense_width=intermediate_size if mlp == "dense" else 0,
                routed=routed)
    x = sym.RMSNorm(data=x, eps=rms_norm_eps, name="final_norm")
    logits = sym.FullyConnected(
        data=sym.Reshape(data=x, shape=(-1, dim)), num_hidden=vocab_size,
        no_bias=True, name="lm_head")
    return sym.SoftmaxOutput(
        data=logits, label=sym.Reshape(data=label, shape=(-1,)),
        name="softmax")
