"""Operations and bytes of ``configs/zaya1-8b.json``, from shapes.

The conventions are ``flops.py``'s: one multiply-add = 2 operations, a
training step = 3 x the forward, recomputation not counted, causal attention
counted as its lower triangle.  What is counted is what *this chip* computes
(the configuration's ``deployment``): attention, its convolutions and the
router's MLP for every token, and of a token's ``num_experts_per_tok``
assignments the share that lands on the experts held here — by expectation
``num_experts / router_width`` of them, or the number the run counted where
a caller has it.
"""
from . import flops_flash_backward, flops_joyai


def _heads(cfg):
    return (int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"]),
            int(cfg["head_dim"]))


def attention_projection_macs(cfg):
    """Multiply-adds a token of the four projections: into the query
    latent, the key latent and the value latent, and out of the first."""
    e = int(cfg["hidden_size"])
    hq, hkv, d = _heads(cfg)
    return e * hq * d + 2 * e * hkv * d + hq * d * e


def convolution_macs(cfg):
    """Multiply-adds a token of the two convolutions on q and on k: a
    depthwise one (``cca_time0`` taps a channel) and one that mixes the d
    channels of a head (``cca_time1`` taps of d x d a head)."""
    hq, hkv, d = _heads(cfg)
    return (hq + hkv) * d * (int(cfg["cca_time0"])
                             + int(cfg["cca_time1"]) * d)


def attention_score_macs(cfg, seq, causal=True):
    """Multiply-adds a token of q·kᵀ and p·v over ``seq`` keys (half of
    them under ``causal``): query heads x twice the head's width a key."""
    hq, _hkv, d = _heads(cfg)
    keys = seq / 2.0 if causal else float(seq)
    return keys * hq * 2 * d


def router_macs(cfg):
    """Multiply-adds a token of the router: into its stream, two square
    layers, and out to every expert of ``deployment.router_width``."""
    e, r = int(cfg["hidden_size"]), int(cfg["router_hidden_size"])
    return e * r + 2 * r * r + r * int(cfg["deployment"]["router_width"])


def local_assignments_per_token(cfg):
    """Expected assignments a token makes on experts held here."""
    return (int(cfg["num_experts_per_tok"]) * int(cfg["num_experts"])
            / float(cfg["deployment"]["router_width"]))


def expert_macs(cfg):
    """Multiply-adds of one gated expert on one token."""
    return 3 * int(cfg["hidden_size"]) * int(cfg["moe_intermediate_size"])


def layer_macs(cfg, seq, causal=True, local_per_token=None):
    """Multiply-adds a token of one layer (``local_per_token`` expert FFNs,
    expected where None)."""
    if local_per_token is None:
        local_per_token = local_assignments_per_token(cfg)
    return (attention_projection_macs(cfg) + convolution_macs(cfg)
            + attention_score_macs(cfg, seq, causal) + router_macs(cfg)
            + local_per_token * expert_macs(cfg))


def forward_macs_per_token(cfg, seq, causal=True):
    """Multiply-adds a token of the whole forward pass: the layers and the
    tied head over this chip's rows.  The embedding is a gather."""
    return (int(cfg["num_hidden_layers"]) * layer_macs(cfg, seq, causal)
            + int(cfg["hidden_size"]) * int(cfg["vocab_size"]))


def train_step_flops(cfg, batch, seq, causal=True):
    return 3 * 2 * forward_macs_per_token(cfg, seq, causal) \
        * int(batch) * int(seq)


def train_step(cfg, counters):
    """Operations of one training step from a run's counters: what the
    configuration names under ``"flops"``."""
    return train_step_flops(cfg, counters["batch"], counters["seq"])


def _grouped_bytes(batch, heads, kv_heads, seq_q, seq_k, d_qk, d_v, itemsize,
                   passes):
    """Bytes of ``passes`` passes over q- and o-shaped arrays (by query
    head) and k- and v-shaped ones (by key/value head: read or written
    once a group), and of a float32 log-sum-exp a query row."""
    return passes * itemsize * batch * (
        heads * seq_q * (d_qk + d_v) + kv_heads * seq_k * (d_qk + d_v)) \
        + 4 * batch * heads * seq_q


def flash_forward_call(batch, heads, kv_heads, seq_q, seq_k, d_qk, d_v,
                       itemsize, causal=True):
    """(operations, bytes) of one flash-forward call with grouped query
    heads: q·kᵀ and p·v over ``heads`` query heads (the mathematics',
    whatever implements it); one read of q, of k and v once a key/value
    head, one write of o and of the log-sum-exp."""
    ops, _ = flops_joyai.flash_forward_call(batch, heads, seq_q, seq_k,
                                            d_qk, d_v, itemsize, causal)
    return ops, _grouped_bytes(batch, heads, kv_heads, seq_q, seq_k, d_qk,
                               d_v, itemsize, passes=1)


def flash_backward_call(batch, heads, kv_heads, seq_q, seq_k, d_qk, d_v,
                        itemsize, causal=True):
    """(operations, bytes) of one flash-backward call with grouped query
    heads: the four products of ``flops_flash_backward.flash_backward_call``
    over ``heads`` query heads; one read of q, o, do, k, v and the
    log-sum-exp, one write of dq, dk, dv — k, v, dk, dv once a key/value
    head."""
    ops, _ = flops_flash_backward.flash_backward_call(
        batch, heads, seq_q, seq_k, d_qk, d_v, itemsize, causal)
    return ops, _grouped_bytes(batch, heads, kv_heads, seq_q, seq_k, d_qk,
                               d_v, itemsize, passes=2)
