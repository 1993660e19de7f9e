"""Operations and bytes of ``configs/joyai-llm-flash.json``, from shapes.

The conventions are ``flops.py``'s: one multiply-add = 2 operations, a
training step = 3 x the forward, recomputation not counted, causal attention
counted as its lower triangle.  What is counted is what *this chip* computes
(the configuration's ``deployment``): the router over all its width, the
shared expert for every token, and of a token's ``num_experts_per_tok``
routed assignments the share that lands on the experts held here — by
expectation ``n_routed_experts / router_width`` of them, or the number the
run counted where a caller has it.
"""


def attention_projection_macs(cfg):
    """Multiply-adds a token of latent attention's five projections."""
    e, h = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    rq, rkv = int(cfg["q_lora_rank"]), int(cfg["kv_lora_rank"])
    nope, rope, dv = (int(cfg["qk_nope_head_dim"]),
                      int(cfg["qk_rope_head_dim"]), int(cfg["v_head_dim"]))
    return (e * rq + rq * h * (nope + rope) + e * (rkv + rope)
            + rkv * h * (nope + dv) + h * dv * e)


def attention_score_macs(cfg, seq, causal=True):
    """Multiply-adds a token of q·kᵀ and p·v over ``seq`` keys (half of
    them under ``causal``): heads x (width of q + width of v) a key."""
    h = int(cfg["num_attention_heads"])
    width = (int(cfg["qk_nope_head_dim"]) + int(cfg["qk_rope_head_dim"])
             + int(cfg["v_head_dim"]))
    keys = seq / 2.0 if causal else float(seq)
    return keys * h * width


def gated_ffn_macs(cfg, width):
    return 3 * int(cfg["hidden_size"]) * int(width)


def local_assignments_per_token(cfg):
    """Expected assignments a token makes on experts held here."""
    return (int(cfg["num_experts_per_tok"]) * int(cfg["n_routed_experts"])
            / float(cfg["deployment"]["router_width"]))


def routed_layer_macs(cfg, local_per_token=None):
    """Multiply-adds a token of one routed layer: the router, the shared
    expert, and ``local_per_token`` expert FFNs (expected where None)."""
    if local_per_token is None:
        local_per_token = local_assignments_per_token(cfg)
    moe = int(cfg["moe_intermediate_size"])
    return (int(cfg["hidden_size"]) * int(cfg["deployment"]["router_width"])
            + gated_ffn_macs(cfg, int(cfg["n_shared_experts"]) * moe)
            + local_per_token * gated_ffn_macs(cfg, moe))


def forward_macs_per_token(cfg, seq, causal=True):
    """Multiply-adds a token of the whole forward pass: the dense blocks,
    the routed blocks, the prediction module and one head each for the
    main stream and the module.  The embeddings are gathers."""
    e = int(cfg["hidden_size"])
    attention = (attention_projection_macs(cfg)
                 + attention_score_macs(cfg, seq, causal))
    dense = int(cfg["first_k_dense_replace"])
    routed = int(cfg["num_hidden_layers"]) - dense
    mtp = int(cfg["num_nextn_predict_layers"])
    macs = dense * (attention + gated_ffn_macs(cfg,
                                               cfg["intermediate_size"]))
    macs += (routed + mtp) * (attention + routed_layer_macs(cfg))
    macs += mtp * 2 * e * e                         # the module's projection
    macs += (1 + mtp) * e * int(cfg["vocab_size"])  # the head, once a stream
    return macs


def train_step_flops(cfg, batch, seq, causal=True):
    return 3 * 2 * forward_macs_per_token(cfg, seq, causal) \
        * int(batch) * int(seq)


def train_step(cfg, counters):
    """Operations of one training step from a run's counters: what the
    configuration names under ``"flops"``."""
    return train_step_flops(cfg, counters["batch"], counters["seq"])


def flash_forward_call(batch, heads, seq_q, seq_k, d_qk, d_v, itemsize,
                       causal=True):
    """(operations, bytes) of one flash-forward call whose q and k are
    ``d_qk`` wide and whose v and o are ``d_v`` wide: q·kᵀ and p·v, one
    read of q, k, v, one write of o and of a float32 log-sum-exp a row."""
    ops = 2 * batch * heads * seq_q * seq_k * (d_qk + d_v)
    if causal:
        ops //= 2
    nbytes = itemsize * batch * heads * (
        seq_q * d_qk + seq_k * d_qk + seq_k * d_v + seq_q * d_v) \
        + 4 * batch * heads * seq_q
    return ops, nbytes


def expert_product_call(rows, experts, d_in, d_out, itemsize):
    """(operations, bytes) of one grouped expert product: ``rows`` sorted
    rows of ``d_in`` against their expert's (d_in, d_out) matrix — one read
    of the rows and of every expert's matrix, one write of the result.  The
    two products of its backward (by the matrix transposed; rows by rows
    into the matrices' gradient) move the same operations."""
    ops = 2 * rows * d_in * d_out
    nbytes = itemsize * (rows * (d_in + d_out) + experts * d_in * d_out)
    return ops, nbytes
