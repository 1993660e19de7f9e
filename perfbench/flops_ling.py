"""Operations and bytes of ``configs/ling-3.0-flash-vl.json``, from shapes.

The conventions are ``flops.py``'s: one multiply-add = 2 operations, a
training step = 3 x the forward, recomputation not counted, causal attention
counted as its lower triangle.  What is counted is what *this chip*
computes (the configuration's ``deployment``): the token mixers, the router
and the shared expert for every token, the dense layer's FFN, and of a
token's ``num_experts_per_tok`` assignments in a routed layer those that land
on the experts held here — the number the run's routing counters report
where a caller has them, else the expectation ``num_experts /
router_width`` of them.

Kimi delta attention's rule is counted as its recurrence's own operations a
token and head, whatever implements it, as ``flops_qwen3_next`` counts the
scalar rule: the decay of the state a channel (d_k·d_v), Sᵀk (2·d_k·d_v),
the rank-one update (2·d_k·d_v) and Sᵀq (2·d_k·d_v).
"""
from . import flops_qwen3_next


def layers(cfg):
    """[(mixer kind, FFN kind)] of the layers held."""
    n = int(cfg["num_hidden_layers"])
    return list(zip(cfg["layer_mixers"][:n], cfg["mlp_layer_types"][:n]))


def kda_projection_macs(cfg):
    """Multiply-adds a token of a KDA layer's projections (q, k, v, the
    decay, the output gate; β; out) and of its three convolutions."""
    e, h, d = (int(cfg["hidden_size"]), int(cfg["num_attention_heads"]),
               int(cfg["head_dim"]))
    return e * 5 * h * d + e * h + h * d * e \
        + int(cfg["short_conv_kernel_size"]) * 3 * h * d


def kda_rule_ops_per_token(cfg):
    """Operations a token of the recurrence over all heads: 7·d_k·d_v a
    head (the module's text)."""
    d = int(cfg["head_dim"])
    return 7 * int(cfg["num_attention_heads"]) * d * d


def latent_attention_macs(cfg, seq):
    """Multiply-adds a token of the latent attention layer: the direct
    query, the latent with its rotary key, the latent's up-projection, the
    gate a head and out, and q·kᵀ (nope + rope wide) and p·v (v wide) over
    half the sequence (causal)."""
    e, h = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    nope, rope = int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"])
    dv, r = int(cfg["v_head_dim"]), int(cfg["kv_lora_rank"])
    return e * h * (nope + rope) + e * (r + rope) + r * h * (nope + dv) \
        + e * h + h * dv * e + seq / 2.0 * h * (nope + rope + dv)


def routed_macs(cfg, local_per_token):
    """Multiply-adds a token of a routed layer: the router over every
    expert, ``local_per_token`` gated experts, the ungated shared one."""
    e = int(cfg["hidden_size"])
    return e * int(cfg["deployment"]["router_width"]) \
        + local_per_token * 3 * e * int(cfg["moe_intermediate_size"]) \
        + 3 * e * int(cfg["moe_shared_expert_intermediate_size"])


def forward_ops_per_token(cfg, seq, local_per_token=None):
    """Operations a token of the whole forward pass: the layers by kind and
    the head over this chip's rows.  The embedding is a gather."""
    if local_per_token is None:
        local_per_token = flops_qwen3_next.local_assignments_per_token(cfg)
    e = int(cfg["hidden_size"])
    ops = 2 * e * int(cfg["vocab_size"])
    for mixer, mlp in layers(cfg):
        if mixer == "kda":
            ops += 2 * kda_projection_macs(cfg) + kda_rule_ops_per_token(cfg)
        else:
            ops += 2 * latent_attention_macs(cfg, seq)
        ops += 2 * (3 * e * int(cfg["intermediate_size"]) if mlp == "dense"
                    else routed_macs(cfg, local_per_token))
    return ops


def train_step_flops(cfg, batch, seq, local_per_token=None):
    return 3 * forward_ops_per_token(cfg, seq, local_per_token) \
        * int(batch) * int(seq)


def train_step(cfg, counters):
    """Operations of one training step from a run's counters: what the
    configuration names under ``"flops"``."""
    return train_step_flops(
        cfg, counters["batch"], counters["seq"],
        local_per_token=flops_qwen3_next.local_assignments_per_token(
            cfg, counters))


def kda_call(batch, heads, seq, d_k, d_v, itemsize, backward=False):
    """(operations, bytes) of the KDA rule over one layer's tokens: the
    recurrence's operations (the backward: twice them); one read of q, k,
    v (``itemsize``), of g (float32, a channel) and β (float32, a head),
    and one write of o — for a backward the same reads and do, and the
    writes of dq, dk, dv, dg and dβ."""
    ops = 7 * batch * seq * heads * d_k * d_v
    inputs = batch * seq * heads * (itemsize * (2 * d_k + d_v)
                                    + 4 * d_k + 4)
    out = batch * seq * heads * itemsize * d_v
    if backward:
        return 2 * ops, 2 * inputs + out
    return ops, inputs + out
