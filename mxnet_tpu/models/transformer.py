"""Decoder-only transformer language model.

TPU-native flagship for long-context training (no reference counterpart —
the reference's sequence story is unrolled LSTM + bucketing, SURVEY §5).
Attention lowers to the Pallas flash kernel on TPU; under a mesh with an
``sp`` axis the ShardedTrainer can run it sequence-parallel with
ring attention (parallel/ring_attention.py).
"""
from __future__ import annotations

from .. import symbol as sym


def transformer_block(x, name, num_heads, dim, seq_len, ffn_mult=4,
                      dropout=0.0, causal=True, num_experts=0,
                      moe_top_k=1, moe_capacity_factor=0.0):
    """One decoder layer.  ``num_experts > 0`` swaps the dense FFN for a
    routed MoE FFN (``ops/moe.py``: top-k gating, optional capacity
    factor) — the Switch-Transformer layer shape; the aux load-balance
    output is dropped at the symbol level (the trainer's loss already
    carries the head loss; wire it in explicitly when training MoE for
    real)."""
    ln1 = sym.LayerNorm(data=x, name="%s_ln1" % name)
    att = sym.MultiHeadAttention(data=ln1, num_heads=num_heads,
                                 causal=causal, dropout=dropout,
                                 name="%s_att" % name)
    x = x + att
    ln2 = sym.LayerNorm(data=x, name="%s_ln2" % name)
    if num_experts:
        moe = sym.MoE(data=sym.Reshape(data=ln2, shape=(-1, dim)),
                      num_experts=num_experts,
                      hidden_size=ffn_mult * dim, top_k=moe_top_k,
                      capacity_factor=moe_capacity_factor,
                      name="%s_moe" % name)
        h = moe[0]
    else:
        h = sym.FullyConnected(data=sym.Reshape(data=ln2, shape=(-1, dim)),
                               num_hidden=ffn_mult * dim,
                               name="%s_ffn1" % name)
        h = sym.Activation(data=h, act_type="relu")
        h = sym.FullyConnected(data=h, num_hidden=dim,
                               name="%s_ffn2" % name)
    h = sym.Reshape(data=h, shape=(-1, seq_len, dim),
                    name="%s_ffn_out" % name)
    return x + h


def get_symbol(vocab_size=32000, num_layers=4, num_heads=8, dim=256,
               seq_len=512, ffn_mult=4, dropout=0.0, mirror_blocks=False,
               num_experts=0, moe_top_k=1, moe_capacity_factor=0.0):
    """LM symbol: data (B, S) token ids, softmax_label (B, S) next tokens.

    ``num_experts > 0`` builds the MoE variant: every layer's FFN becomes
    a routed ``layer%d_moe`` expert block whose ``*_expert_*`` weights
    shard over an ``ep`` mesh axis (parallel.param_pspec matches the
    names).

    ``mirror_blocks=True`` tags every op inside each decoder layer with
    ``force_mirroring`` + a per-layer ``mirror_stage`` (same mechanism
    as models.resnet): backward recomputes whole layers and keeps only
    layer-boundary activations and what a layer's kernels hand their
    backward (``attribute.mirror_scope``) — the standard per-layer remat
    for HBM-limited long-context training, here expressed as symbol attrs
    and lowered by the executor's mirror segments (executor.py
    ``_mirror_segments``)."""
    from ..attribute import mirror_scope

    def layer_scope(name):
        return mirror_scope(name, enabled=mirror_blocks)

    data = sym.Variable("data")
    pos = sym.Variable("pos_embed_weight", shape=(seq_len, dim))
    tok = sym.Embedding(data=data, input_dim=vocab_size, output_dim=dim,
                        name="tok_embed")
    x = sym.broadcast_add(tok, sym.expand_dims(pos, axis=0))
    for i in range(num_layers):
        with layer_scope("layer%d" % i):
            x = transformer_block(x, "layer%d" % i, num_heads, dim,
                                  seq_len, ffn_mult=ffn_mult,
                                  dropout=dropout,
                                  num_experts=num_experts,
                                  moe_top_k=moe_top_k,
                                  moe_capacity_factor=moe_capacity_factor)
    x = sym.LayerNorm(data=x, name="final_ln")
    logits = sym.FullyConnected(
        data=sym.Reshape(data=x, shape=(-1, dim)),
        num_hidden=vocab_size, name="lm_head")
    label = sym.Reshape(data=sym.Variable("softmax_label"),
                        shape=(-1,), name="label_flat")
    return sym.SoftmaxOutput(data=logits, label=label, name="softmax")


# ----------------------------------------------------------------------
# generation graphs: prefill + paged-cache decode
# ----------------------------------------------------------------------
def _cached_lm(seq_len, mode, vocab_size, num_layers, num_heads, dim,
               max_seq_len, ffn_mult=4):
    """Shared builder for the prefill/decode symbols.

    Weight names match :func:`get_symbol` exactly (``tok_embed_weight``,
    ``pos_embed_weight``, ``layer%d_att_qkv_weight``, ``lm_head_*``, …)
    so one trained checkpoint binds the training graph, the full
    forward, AND both generation graphs.  Position embeddings are
    gathered by an explicit ``pos_ids`` input (an Embedding over the
    same ``pos_embed_weight`` table the full model broadcast-adds), so
    the decode graph is position-agnostic and ONE traced program serves
    every decode step and every batch bucket.

    Outputs: ``[logits] + [layer0 k_cache_out, layer0 v_cache_out, …]``
    — the cache append is a functional update the caller feeds back.
    """
    data = sym.Variable("data")                 # (B, S) token ids
    pos_ids = sym.Variable("pos_ids")           # (B, S) positions
    seq_pos = sym.Variable("seq_pos")           # (B,) len / current pos
    block_table = sym.Variable("block_table")   # (B, blocks_per_seq)
    tok = sym.Embedding(data=data, input_dim=vocab_size, output_dim=dim,
                        name="tok_embed")
    pos = sym.Embedding(data=pos_ids, input_dim=max_seq_len,
                        output_dim=dim, name="pos_embed")
    x = tok + pos
    cache_outs = []
    for i in range(num_layers):
        name = "layer%d" % i
        ln1 = sym.LayerNorm(data=x, name="%s_ln1" % name)
        att = sym.CachedMultiHeadAttention(
            data=ln1, num_heads=num_heads, mode=mode,
            block_table=block_table, seq_pos=seq_pos,
            name="%s_att" % name)
        x = x + att[0]
        cache_outs.extend([att[1], att[2]])
        ln2 = sym.LayerNorm(data=x, name="%s_ln2" % name)
        h = sym.FullyConnected(data=sym.Reshape(data=ln2, shape=(-1, dim)),
                               num_hidden=ffn_mult * dim,
                               name="%s_ffn1" % name)
        h = sym.Activation(data=h, act_type="relu")
        h = sym.FullyConnected(data=h, num_hidden=dim, name="%s_ffn2" % name)
        h = sym.Reshape(data=h, shape=(-1, seq_len, dim),
                        name="%s_ffn_out" % name)
        x = x + h
    x = sym.LayerNorm(data=x, name="final_ln")
    logits = sym.FullyConnected(
        data=sym.Reshape(data=x, shape=(-1, dim)),
        num_hidden=vocab_size, name="lm_head")
    return sym.Group([logits] + cache_outs)


def get_prefill_symbol(prompt_len, vocab_size=32000, num_layers=4,
                       num_heads=8, dim=256, max_seq_len=512, ffn_mult=4):
    """Prompt-ingestion graph for one prompt-length bucket: data
    ``(B, prompt_len)``, causal attention, and a scatter of every
    prompt position's k/v into the paged cache (padded positions route
    to the trash block, steered by ``seq_pos`` = real lengths).
    Logits cover all positions; the caller reads row ``L-1``."""
    return _cached_lm(prompt_len, "prefill", vocab_size, num_layers,
                      num_heads, dim, max_seq_len, ffn_mult)


def get_decode_symbol(vocab_size=32000, num_layers=4, num_heads=8,
                      dim=256, max_seq_len=512, ffn_mult=4):
    """Single-token decode graph: data ``(B, 1)`` (each row one active
    sequence's newest token), cache append + single-query attention
    over the block table.  Shape- and position-agnostic: every decode
    batch bucket binds this same JSON, so the program registry traces
    it once."""
    return _cached_lm(1, "decode", vocab_size, num_layers, num_heads,
                      dim, max_seq_len, ffn_mult)


def generate(params, prompts, vocab_size=32000, num_layers=4, num_heads=8,
             dim=256, max_seq_len=512, ffn_mult=4, max_new_tokens=16,
             eos_id=None, prompt_buckets=None, decode_buckets=None,
             kv_blocks=None, kv_block_size=None, ctx=None):
    """Greedy generation for a batch of prompts — the one-shot
    convenience over :class:`mxnet_tpu.serving.generate.
    GenerationEngine` (which the batching server drives incrementally).

    ``params``: the trained checkpoint (dict of NDArrays or a params
    path) of a :func:`get_symbol` model with the same dims.  Prefill
    programs are AOT-compiled per prompt-length bucket and decode per
    batch-size bucket (both through the exact-DP planner when buckets
    are not given); the loop itself performs zero lowerings.  Returns
    ``[generated token list per prompt]``.
    """
    from ..serving.generate import GenerationEngine
    engine = GenerationEngine(
        params=params, vocab_size=vocab_size, num_layers=num_layers,
        num_heads=num_heads, dim=dim, max_seq_len=max_seq_len,
        ffn_mult=ffn_mult, max_new_tokens=max_new_tokens,
        prompt_buckets=prompt_buckets, decode_buckets=decode_buckets,
        kv_blocks=kv_blocks, kv_block_size=kv_block_size, ctx=ctx)
    return engine.generate(prompts, max_new_tokens=max_new_tokens,
                           eos_id=eos_id)
