"""Operations and bytes of ``configs/qwen3-next-80b-a3b.json``, from shapes.

The conventions are ``flops.py``'s: one multiply-add = 2 operations, a
training step = 3 x the forward, recomputation not counted, causal attention
counted as its lower triangle.  What is counted is what *this chip* computes
(the configuration's ``deployment``): the token mixers, the router, the
shared expert and its gate for every token, and of a token's
``num_experts_per_tok`` assignments those that land on the experts held here
— the number the run's routing counters report where a caller has them, else
the expectation ``num_experts / router_width`` of them.

The gated delta rule is counted as the recurrence's own operations a token
and value head, whatever implements it: the decay of the state (d_k·d_v),
Sᵀk (2·d_k·d_v), the rank-one update (2·d_k·d_v) and Sᵀq (2·d_k·d_v).  A
chunk-parallel form spends more (its triangular system, its products within
a chunk) and a fused kernel may spend less memory traffic; neither changes
the count.
"""
from . import flops_zaya
from .reference.qwen3_next import layer_kinds   # the one rule of the pattern


def _linear(cfg):
    return (int(cfg["linear_num_key_heads"]),
            int(cfg["linear_num_value_heads"]),
            int(cfg["linear_key_head_dim"]),
            int(cfg["linear_value_head_dim"]))


def delta_projection_macs(cfg):
    """Multiply-adds a token of a delta-rule layer's projections (q, k, v,
    z; b, a; out) and of its depthwise convolution."""
    e = int(cfg["hidden_size"])
    hk, hv, dk, dv = _linear(cfg)
    keys, values = hk * dk, hv * dv
    return e * (2 * keys + 2 * values) + e * 2 * hv + values * e \
        + int(cfg["linear_conv_kernel_dim"]) * (2 * keys + values)


def delta_rule_ops_per_token(cfg):
    """Operations a token of the recurrence over all value heads (the
    module's text): 7·d_k·d_v a head."""
    _hk, hv, dk, dv = _linear(cfg)
    return 7 * hv * dk * dv


def delta_rule_call(batch, key_heads, value_heads, seq, d_k, d_v, itemsize,
                    backward=False):
    """(operations, bytes) of the rule over one layer's tokens: the
    recurrence's operations; one read of q, k (by key head), v, of g and β
    (float32, a value head) and one write of o — for a backward twice both
    (its operations are the forward's twice over, it reads what the forward
    read and the output's gradient, and writes five gradients)."""
    ops = 7 * batch * seq * value_heads * d_k * d_v
    nbytes = batch * seq * (
        itemsize * (2 * key_heads * d_k + 2 * value_heads * d_v)
        + 4 * 2 * value_heads)
    return (2 * ops, 2 * nbytes) if backward else (ops, nbytes)


def attention_projection_macs(cfg):
    """Multiply-adds a token of the gated attention's projections: q with
    its gate (twice the heads' width), k, v, and out."""
    e = int(cfg["hidden_size"])
    hq, hkv, d = (int(cfg["num_attention_heads"]),
                  int(cfg["num_key_value_heads"]), int(cfg["head_dim"]))
    return e * 2 * hq * d + 2 * e * hkv * d + hq * d * e


def local_assignments_per_token(cfg, counters=None):
    """Assignments a token makes on experts held here: counted by the run
    where ``counters`` has its routed layers, else expected."""
    layers = (counters or {}).get("routed_layers")
    if layers and counters.get("steps"):
        tokens = counters["batch"] * counters["seq"] * counters["steps"]
        return sum(r["local_assignments"] for r in layers) \
            / float(len(layers) * tokens)
    return (int(cfg["num_experts_per_tok"]) * int(cfg["num_experts"])
            / float(cfg["deployment"]["router_width"]))


def routed_layer_macs(cfg, local_per_token):
    """Multiply-adds a token of the mixture: the router over every expert,
    ``local_per_token`` gated experts, the shared one and its gate."""
    e = int(cfg["hidden_size"])
    return e * int(cfg["deployment"]["router_width"]) \
        + local_per_token * 3 * e * int(cfg["moe_intermediate_size"]) \
        + 3 * e * int(cfg["shared_expert_intermediate_size"]) + e


def forward_ops_per_token(cfg, seq, causal=True, local_per_token=None):
    """Operations a token of the whole forward pass: the layers by kind and
    the head over this chip's rows.  The embedding is a gather."""
    if local_per_token is None:
        local_per_token = local_assignments_per_token(cfg)
    ops = 2 * int(cfg["hidden_size"]) * int(cfg["vocab_size"])
    for kind in layer_kinds(cfg):
        if kind == "full_attention":
            ops += 2 * (attention_projection_macs(cfg)
                        + flops_zaya.attention_score_macs(cfg, seq, causal))
        else:
            ops += 2 * delta_projection_macs(cfg) \
                + delta_rule_ops_per_token(cfg)
        ops += 2 * routed_layer_macs(cfg, local_per_token)
    return ops


def train_step_flops(cfg, batch, seq, causal=True, local_per_token=None):
    return 3 * forward_ops_per_token(cfg, seq, causal, local_per_token) \
        * int(batch) * int(seq)


def train_step(cfg, counters):
    """Operations of one training step from a run's counters: what the
    configuration names under ``"flops"``."""
    return train_step_flops(
        cfg, counters["batch"], counters["seq"],
        local_per_token=local_assignments_per_token(cfg, counters))
