"""Operations and bytes of ``configs/laguna-xs2.json``, from shapes.

The conventions are ``flops.py``'s: one multiply-add = 2 operations, a
training step = 3 x the forward, recomputation not counted, causal attention
counted as its lower triangle — and attention under a sliding window W as
the exact pairs of its band: query i meets min(i + 1, W) keys, W·S − W(W −
1)/2 pairs over S ≥ W positions.  What is counted is what *this chip*
computes (the configuration's ``deployment``): attention, the router and the
shared expert for every token, the dense layer's FFN, and of a token's
``num_experts_per_tok`` assignments in a routed layer those that land on the
experts held here — the number the run's routing counters report where a
caller has them, else the expectation ``num_experts / router_width`` of
them.
"""
from . import flops_qwen3_next, flops_zaya


def layers(cfg):
    """[(attention kind, query heads, FFN kind)] of the layers held."""
    n = int(cfg["num_hidden_layers"])
    return list(zip(cfg["layer_types"][:n],
                    (int(h) for h in cfg["num_attention_heads_per_layer"][:n]),
                    cfg["mlp_layer_types"][:n]))


def band_pairs(seq, window=None):
    """(query, key) pairs a sequence of ``seq`` attends to: the causal
    triangle, or under a sliding ``window`` the band's exact count."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * seq - window * (window - 1) // 2


def attention_macs(cfg, heads, seq, window=None):
    """Multiply-adds a token of one layer's gated attention: q with its
    gate (a column a head), k, v and out, and q·kᵀ and p·v over the
    pairs it meets (a causal layer counted as half the square, as
    ``flops_zaya.attention_score_macs`` counts it)."""
    e, kv, d = (int(cfg["hidden_size"]), int(cfg["num_key_value_heads"]),
                int(cfg["head_dim"]))
    keys = seq / 2.0 if window is None else band_pairs(seq, window) \
        / float(seq)
    return e * heads * (d + 1) + 2 * e * kv * d + heads * d * e \
        + keys * heads * 2 * d


def routed_macs(cfg, local_per_token):
    """Multiply-adds a token of a routed layer: the router over every
    expert, ``local_per_token`` gated experts, the ungated shared one."""
    e = int(cfg["hidden_size"])
    return e * int(cfg["deployment"]["router_width"]) \
        + local_per_token * 3 * e * int(cfg["moe_intermediate_size"]) \
        + 3 * e * int(cfg["shared_expert_intermediate_size"])


def forward_ops_per_token(cfg, seq, local_per_token=None):
    """Operations a token of the whole forward pass: the layers by kind and
    the head over this chip's rows.  The embedding is a gather."""
    if local_per_token is None:
        local_per_token = flops_qwen3_next.local_assignments_per_token(cfg)
    e = int(cfg["hidden_size"])
    ops = 2 * e * int(cfg["vocab_size"])
    for kind, heads, mlp in layers(cfg):
        window = int(cfg["sliding_window"]) \
            if kind == "sliding_attention" else None
        ops += 2 * attention_macs(cfg, heads, seq, window)
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


def flash_window_call(batch, heads, kv_heads, seq, window, d_qk, d_v,
                      itemsize, backward=False):
    """(operations, bytes) of one windowed flash call with grouped query
    heads: q·kᵀ and p·v over the band's pairs for every query head (the
    backward: the mathematics' four products, twice that); one pass over
    q, o (and do, dq) by query head and k, v (and dk, dv) once a key/value
    head, and a float32 log-sum-exp a row (``flops_zaya``'s bytes)."""
    ops = 2 * batch * heads * band_pairs(seq, window) * (d_qk + d_v)
    passes = 2 if backward else 1
    nbytes = flops_zaya._grouped_bytes(batch, heads, kv_heads, seq, seq,
                                       d_qk, d_v, itemsize, passes)
    return (2 * ops if backward else ops), nbytes
