"""The DeepSeek-V3-family layer set against the plain reference, at small
sizes on the CPU with seeded weights: RMSNorm, the gated SiLU FFN, the
interleaved rotary, latent attention (and the flash path with q/k of one
width and v of another), the routed layer on the experts it holds — every
assignment computed, the shares adding up to the uncut layer, its counters
— and the whole model through ``ShardedTrainer.step``: both heads' row
losses and every leaf's gradient.

The reference is the benchmark's, ``perfbench/reference/joyai_llm_flash.py``
(plain ``jax.numpy``, nothing of ``mxnet_tpu``)."""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import mxnet_tpu as mx                                      # noqa: E402
from mxnet_tpu.ops import moe                                # noqa: E402
from mxnet_tpu.ops.attention import rotary_interleaved      # noqa: E402
from mxnet_tpu.ops.registry import (create_operator, op_cost,     # noqa: E402
                                    sharding_transfer)
from mxnet_tpu.parallel import ring_attention as ra          # noqa: E402
from perfbench.reference import joyai_llm_flash as ref      # noqa: E402

CFG = {
    "hidden_size": 32, "vocab_size": 64, "num_attention_heads": 2,
    "q_lora_rank": 24, "kv_lora_rank": 16, "qk_nope_head_dim": 8,
    "qk_rope_head_dim": 8, "v_head_dim": 12, "intermediate_size": 48,
    "moe_intermediate_size": 16, "n_routed_experts": 4,
    "n_shared_experts": 1, "num_experts_per_tok": 4,
    "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "num_nextn_predict_layers": 1, "rms_norm_eps": 1e-6,
    "rope_theta": 10000.0, "routed_scaling_factor": 2.5,
    "norm_topk_prob": True, "scoring_func": "sigmoid",
    "initializer_range": 0.2,
    "mtp_loss_weight": 0.3,
    "deployment": {"router_width": 16, "first_expert": 4},
    "program": {"mirror_blocks": True},
}
SEQ = 16


def _close(a, b, tol=2e-5):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    scale = max(np.abs(b).max(), 1e-30)
    assert np.abs(a - b).max() <= tol * scale, (np.abs(a - b).max(), scale)


def _op(name, **attrs):
    return create_operator(name, **attrs)


def _highest(fn):
    def run(*args, **kw):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kw)
    return run


# -- RMSNorm, SiLU, the gated FFN ---------------------------------------------
def test_rms_norm_forward_and_backward():
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (3, 5, 32), jnp.float32)
    g = 1.0 + 0.1 * jax.random.normal(jax.random.fold_in(key, 1), (32,))
    op = _op("RMSNorm", eps=1e-6)

    def prog(x, g):
        return op.forward([x, g], [], True, None)[0][0]

    _close(prog(x, g), ref._rms_norm(x, g, 1e-6))
    w = jax.random.normal(jax.random.fold_in(key, 2), x.shape)
    got = jax.grad(lambda x, g: jnp.sum(prog(x, g) * w), (0, 1))(x, g)
    want = jax.grad(lambda x, g: jnp.sum(ref._rms_norm(x, g, 1e-6) * w),
                    (0, 1))(x, g)
    for a, b in zip(got, want):
        _close(a, b)
    assert op.infer_shape([(3, 5, 32), None])[0][1] == (32,)


def test_rms_norm_in_bfloat16_keeps_float32_statistics():
    x = (100.0 + jax.random.normal(jax.random.PRNGKey(3), (4, 256))
         ).astype(jnp.bfloat16)
    op = _op("RMSNorm")
    y = op.forward([x, jnp.ones((256,), jnp.bfloat16)], [], True, None)[0][0]
    assert y.dtype == jnp.bfloat16
    _close(y.astype(jnp.float32),
           ref._rms_norm(x.astype(jnp.float32), 1.0, 1e-6), tol=1e-2)


def test_gated_ffn_symbol_matches_reference():
    key = jax.random.PRNGKey(4)
    ks = jax.random.split(key, 4)
    x = jax.random.normal(ks[0], (6, 32))
    wg, wu = (0.3 * jax.random.normal(k, (48, 32)) for k in ks[1:3])
    wd = 0.3 * jax.random.normal(ks[3], (32, 48))
    from mxnet_tpu.models.transformer_mla_moe import _gated_ffn
    net = _gated_ffn(mx.sym.Variable("data"), "ffn", 48, 32)
    exe = net.simple_bind(mx.cpu(), data=(6, 32))
    for n, v in (("data", x), ("ffn_gate_weight", wg), ("ffn_up_weight", wu),
                 ("ffn_down_weight", wd)):
        exe.arg_dict[n][:] = np.asarray(v)
    out = exe.forward(is_train=False)[0].asnumpy()
    _close(out, _highest(ref._gated_ffn)(x, wg, wu, wd, None))
    _close(moe.gated_ffn(x, wg, wu, wd), out)


# -- rotary ---------------------------------------------------------------------
@pytest.mark.parametrize("theta", [10000.0, 32000000.0])
def test_interleaved_rotary_is_a_complex_rotation(theta):
    """Pair i of position p, (x[2i], x[2i+1]) read as a complex number,
    times exp(j p theta^(-2i/D)); the result laid out as halves."""
    s, d = 12, 8
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(5), (2, 3, s, d)),
                   np.float64)
    z = x[..., 0::2] + 1j * x[..., 1::2]
    ang = np.arange(s)[:, None] * theta ** (-np.arange(0, d, 2) / d)[None, :]
    turned = z * np.exp(1j * ang)
    want = np.concatenate([turned.real, turned.imag], axis=-1)
    _close(rotary_interleaved(jnp.asarray(x, jnp.float32), theta), want,
           tol=1e-5)
    _close(ref.rotary_interleaved(jnp.asarray(x, jnp.float32), theta), want,
           tol=1e-5)


def test_rotary_score_depends_on_the_distance_only():
    key = jax.random.PRNGKey(6)
    q = jnp.tile(jax.random.normal(key, (1, 8)), (10, 1))
    k = jnp.tile(jax.random.normal(jax.random.fold_in(key, 1), (1, 8)),
                 (10, 1))
    s = rotary_interleaved(q, 100.0) @ rotary_interleaved(k, 100.0).T
    _close(s[5, 3], s[7, 5], tol=1e-5)
    _close(s[9, 0], s[9 - 0, 0], tol=1e-5)
    assert abs(float(s[5, 3] - s[5, 4])) > 1e-4


# -- flash attention, q/k of one width and v of another -----------------------
@pytest.mark.parametrize("shape", [
    # (B, H, S, d_qk, d_v, dtype)
    (1, 2, 256, 24, 16, jnp.float32),
    (2, 1, 512, 192, 128, jnp.bfloat16),
    (1, 1, 256, 64, 64, jnp.float32),
])
def test_flash_forward_and_backward_widths(shape):
    b, h, s, dqk, dv, dtype = shape
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    q = jax.random.normal(ks[0], (b, h, s, dqk), dtype)
    k = jax.random.normal(ks[1], (b, h, s, dqk), dtype)
    v = jax.random.normal(ks[2], (b, h, s, dv), dtype)
    w = jax.random.normal(ks[3], (b, h, s, dv), jnp.float32)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) * w)

    def kernel(q, k, v):
        return ra.flash_attention(q, k, v, causal=True, interpret=True)

    def plain(q, k, v):
        return ra.attention_reference(q, k, v, causal=True)

    out = kernel(q, k, v)
    assert out.shape == (b, h, s, dv) and out.dtype == dtype
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    _close(out.astype(jnp.float32), plain(q, k, v).astype(jnp.float32), tol)
    got = jax.grad(loss(kernel), (0, 1, 2))(q, k, v)
    want = jax.grad(loss(plain), (0, 1, 2))(q, k, v)
    for a, b_ in zip(got, want):
        assert a.shape == b_.shape
        _close(a.astype(jnp.float32), b_.astype(jnp.float32), tol)


@pytest.mark.parametrize("block_q,block_k", [(8, 8), (16, 8), (8, 16)])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_flash_backward_kernel_unequal_widths(dtype, causal, block_q,
                                              block_k):
    """The backward kernel with q and k 24 wide against v, o and do 16
    wide, over several query and key blocks (and, without ``causal``,
    more keys than queries): dq, dk and dv against ``jax.grad`` of the
    float32 reference."""
    sk = 32 if causal else 48
    ks = jax.random.split(jax.random.PRNGKey(8), 4)
    q = jax.random.normal(ks[0], (1, 2, 32, 24), dtype)
    k = jax.random.normal(ks[1], (1, 2, sk, 24), dtype)
    v = jax.random.normal(ks[2], (1, 2, sk, 16), dtype)
    w = jax.random.normal(ks[3], (1, 2, 32, 16), jnp.float32)

    def grads(fn, *args):
        return jax.grad(lambda q, k, v: jnp.sum(
            fn(q, k, v).astype(jnp.float32) * w), (0, 1, 2))(*args)

    got = grads(lambda q, k, v: ra.flash_attention(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k,
        interpret=True), q, k, v)
    want = grads(lambda q, k, v: ra.attention_reference(
        q, k, v, causal=causal), *(a.astype(jnp.float32) for a in (q, k, v)))
    for g, x, want_g in zip(got, (q, k, v), want):
        assert g.dtype == x.dtype and g.shape == x.shape
        _close(g.astype(jnp.float32), want_g,
               2e-5 if dtype == "float32" else 2e-2)


# -- latent attention ------------------------------------------------------------
def _mla_op():
    return _op("MultiHeadLatentAttention", num_heads=2, q_lora_rank=24,
               kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8,
               v_head_dim=12, rope_theta=10000.0, eps=1e-6)


def test_latent_attention_forward_and_backward():
    op = _mla_op()
    shapes = op.infer_shape([(2, SEQ, 32)] + [None] * 7)[0]
    names = op.list_arguments()
    ks = jax.random.split(jax.random.PRNGKey(9), len(names) + 1)
    vals = [(1.0 + 0.1 * jax.random.normal(k, s)) if n.endswith("gamma")
            else 0.3 * jax.random.normal(k, s)
            for n, s, k in zip(names, shapes, ks)]
    w = jax.random.normal(ks[-1], (2, SEQ, 32))

    def prog(*vals):
        return op.forward(list(vals), [], True, None)[0][0]

    def plain(*vals):
        p = {"att_" + n: v for n, v in zip(names[1:], vals[1:])}
        return ref.latent_attention(vals[0], p, CFG)

    _close(prog(*vals), _highest(plain)(*vals), 5e-5)
    wrt = tuple(range(len(vals)))
    got = jax.grad(lambda *v: jnp.sum(prog(*v) * w), wrt)(*vals)
    want = jax.grad(lambda *v: jnp.sum(_highest(plain)(*v) * w), wrt)(*vals)
    for n, a, b_ in zip(names, got, want):
        _close(a, b_, 2e-4)


def test_latent_attention_shares_one_rotary_key_between_heads():
    """The key's rotary slice comes from ``kv_a_weight``'s last rows and is
    the same for every head: zeroing the per-head (nope) part of q and k
    makes every head's scores equal, so with equal values every head
    gives the same output."""
    op = _mla_op()
    shapes = op.infer_shape([(1, SEQ, 32)] + [None] * 7)[0]
    ks = jax.random.split(jax.random.PRNGKey(10), 8)
    x, wqa, gq, wqb, wkva, gkv, wkvb, wo = (
        0.3 * jax.random.normal(k, s) for k, s in zip(ks, shapes))
    gq, gkv = jnp.ones_like(gq), jnp.ones_like(gkv)
    wqb = wqb.reshape(2, 16, 24).at[:, :8].set(0.0)       # no q_nope
    wqb = wqb.at[1].set(wqb[0]).reshape(32, 24)           # same q_rope
    wkvb = wkvb.reshape(2, 20, 16).at[:, :8].set(0.0)     # no k_nope
    wkvb = wkvb.at[1].set(wkvb[0]).reshape(40, 16)        # same v
    wo = jnp.eye(32, 24)
    out = op.forward([x, wqa, gq, wqb, wkva, gkv, wkvb, wo], [], True,
                     None)[0][0]
    _close(out[..., :12], out[..., 12:24], 1e-5)


# -- the routed layer ------------------------------------------------------------
def _routed_weights(key, tokens=40, e=32, width=16, held=4, h=16, std=0.3):
    ks = jax.random.split(key, 8)
    p = {"moe_router_weight": std * jax.random.normal(ks[1], (width, e)),
         "moe_expert_gate_weight": std * jax.random.normal(ks[2],
                                                           (held, h, e)),
         "moe_expert_up_weight": std * jax.random.normal(ks[3], (held, h, e)),
         "moe_expert_down_weight": std * jax.random.normal(ks[4],
                                                           (held, e, h)),
         "moe_shared_gate_weight": std * jax.random.normal(ks[5], (h, e)),
         "moe_shared_up_weight": std * jax.random.normal(ks[6], (h, e)),
         "moe_shared_down_weight": std * jax.random.normal(ks[7], (e, h))}
    return jax.random.normal(ks[0], (tokens, e)), p


ROUTED_LEAVES = ("moe_router_weight", "moe_expert_gate_weight",
                 "moe_expert_up_weight", "moe_expert_down_weight",
                 "moe_shared_gate_weight", "moe_shared_up_weight",
                 "moe_shared_down_weight")


def _routed_op(first=4, held=4, width=16, top_k=4, shared=16):
    return _op("RoutedExperts", num_experts=width, num_local_experts=held,
               first_expert=first, hidden_size=16, top_k=top_k,
               shared_hidden_size=shared, routed_scaling_factor=2.5)


def _zero_aux(op, e=32):
    shapes = op.infer_shape([(1, e)] + [None] * (len(op.list_arguments())
                                                 - 1))[2]
    types = op.infer_type([np.dtype("float32")])[2]
    return [jnp.zeros(s, t) for s, t in zip(shapes, types)]


@pytest.mark.parametrize("chunk_rows", [8192, 16, 8])
def test_routed_layer_forward_backward_and_counters(chunk_rows, monkeypatch):
    monkeypatch.setattr(moe, "CHUNK_ROWS", chunk_rows)
    h, p = _routed_weights(jax.random.PRNGKey(11))
    op = _routed_op()
    w = jax.random.normal(jax.random.PRNGKey(12), h.shape)

    def prog(h, *leaves):
        outs, aux = op.forward([h] + list(leaves), _zero_aux(op), True, None)
        return outs[0], aux

    def plain(h, *leaves):
        return ref.routed_layer(h, dict(zip(ROUTED_LEAVES, leaves)), CFG)[0]

    leaves = [p[n] for n in ROUTED_LEAVES]
    out, aux = prog(h, *leaves)
    _close(out, _highest(plain)(h, *leaves), 5e-5)
    wrt = tuple(range(1 + len(leaves)))
    got = jax.grad(lambda *a: jnp.sum(prog(*a)[0] * w), wrt)(h, *leaves)
    want = jax.grad(lambda *a: jnp.sum(_highest(plain)(*a) * w), wrt)(
        h, *leaves)
    for a, b_ in zip(got, want):
        _close(a, b_, 2e-4)
    # the counters: what the reference's weights say landed here
    weights, _ = ref.route(h, p["moe_router_weight"], jnp.zeros((16,)), 4,
                           2.5)
    per_expert = np.asarray(jnp.sum(weights[:, 4:8] > 0, axis=0))
    _bias, total, tokens, peak_sum, peak_max = (np.asarray(a) for a in aux)
    assert tokens.dtype == np.int32
    assert tokens.tolist() == per_expert.tolist()
    assert total[0] == per_expert.sum()
    assert peak_sum[0] == peak_max[0] == per_expert.max()


def test_routed_layer_counters_accumulate_and_keep_their_peak():
    h, p = _routed_weights(jax.random.PRNGKey(13))
    op = _routed_op()
    leaves = [p[n] for n in ROUTED_LEAVES]
    _o, aux1 = op.forward([h] + leaves, _zero_aux(op), True, None)
    _o, aux2 = op.forward([h[::-1] * 0.5] + leaves, aux1, True, None)
    _o, aux3 = op.forward([h] + leaves, aux2, False, None)
    assert aux3 is None                 # evaluation counts nothing
    assert int(aux2[3][0]) >= int(aux1[3][0]) + 1
    assert int(aux2[4][0]) >= int(aux1[4][0])
    assert int(aux2[1][0]) == int(np.asarray(aux2[2]).sum())


def test_shares_add_up_to_the_uncut_layer():
    """Four shares of eight experts, the shared expert and what every
    chip computes alike counted once, give the uncut reference's layer."""
    width, per_share, k = 32, 8, 6
    cfg = dict(CFG, num_experts_per_tok=k, n_routed_experts=width,
               deployment={"router_width": width, "first_expert": 0})
    h, p = _routed_weights(jax.random.PRNGKey(14), tokens=48, width=width,
                           held=width)
    whole, _ = _highest(ref.routed_layer)(h, p, cfg)
    shared = _highest(ref.shared_expert)(h, p)
    total = jnp.zeros_like(h)
    counted = 0
    for share in range(width // per_share):
        first = share * per_share
        op = _routed_op(first=first, held=per_share, width=width, top_k=k)
        cut = dict(p, **{n: p[n][first:first + per_share] for n in (
            "moe_expert_gate_weight", "moe_expert_up_weight",
            "moe_expert_down_weight")})
        outs, aux = op.forward([h] + [cut[n] for n in ROUTED_LEAVES],
                               _zero_aux(op), True, None)
        total = total + (outs[0] - shared)      # the routed part alone
        counted += int(aux[1][0])
        # and the reference, given the same share, gives the same part
        part, _ = _highest(ref.routed_layer)(h, cut, cfg, first=first,
                                             held=per_share)
        _close(outs[0], part, 5e-5)
    assert counted == 48 * k            # every assignment, exactly once
    _close(total + shared, whole, 5e-5)


@pytest.mark.parametrize("chunk_rows", [8192, 32])
def test_no_token_dropped_when_every_token_chooses_the_same_experts(
        chunk_rows, monkeypatch):
    """A router that sends every token to experts 4–7, all held here:
    every one of the T·k assignments is computed, through as many chunks
    as that takes."""
    h, p = _routed_weights(jax.random.PRNGKey(15), tokens=64)
    h = jnp.abs(h)
    router = jnp.full((16, 32), -1.0).at[4:8].set(1.0)
    p = dict(p, moe_router_weight=router)
    monkeypatch.setattr(moe, "CHUNK_ROWS", chunk_rows)
    op = _routed_op()
    leaves = [p[n] for n in ROUTED_LEAVES]
    outs, aux = op.forward([h] + leaves, _zero_aux(op), True, None)
    assert int(aux[1][0]) == 64 * 4
    assert np.asarray(aux[2]).tolist() == [64] * 4
    _close(outs[0], _highest(ref.routed_layer)(h, p, CFG)[0], 5e-5)
    got = jax.grad(lambda h: jnp.sum(op.forward(
        [h] + leaves, _zero_aux(op), True, None)[0][0] ** 2))(h)
    want = jax.grad(lambda h: jnp.sum(_highest(ref.routed_layer)(
        h, p, CFG)[0] ** 2))(h)
    _close(got, want, 2e-4)


def test_experts_held_elsewhere_add_nothing():
    h, p = _routed_weights(jax.random.PRNGKey(16))
    router = jnp.full((16, 32), -1.0).at[8:12].set(1.0)
    p = dict(p, moe_router_weight=router)
    op = _routed_op(shared=0)
    outs, aux = op.forward([jnp.abs(h)] + [p[n] for n in ROUTED_LEAVES[:4]],
                           _zero_aux(op), True, None)
    assert float(jnp.abs(outs[0]).max()) == 0.0
    assert int(aux[1][0]) == 0


def test_router_bias_steers_the_choice_and_not_the_weights():
    h, p = _routed_weights(jax.random.PRNGKey(17))
    bias = jnp.zeros((16,)).at[5].set(10.0)
    idx, w = moe.route_sigmoid_topk(h, p["moe_router_weight"], bias, 4, 2.5)
    assert bool(jnp.all(jnp.any(idx == 5, axis=1)))
    _close(jnp.sum(w, axis=1), jnp.full((40,), 2.5), 1e-5)
    scores = jax.nn.sigmoid(h @ p["moe_router_weight"].T)
    chosen = jnp.take_along_axis(scores, idx, axis=1)
    _close(w, chosen / jnp.sum(chosen, axis=1, keepdims=True) * 2.5, 1e-5)


def test_new_ops_have_cost_and_sharding_rules():
    op = _routed_op()
    shapes, outs, _aux = op.infer_shape(
        [(40, 32)] + [None] * (len(op.list_arguments()) - 1))
    cost = op_cost(op, shapes, outs)
    # router + shared expert for all 40 tokens, 40·4·4/16 expected rows
    assert cost["flops"] == 2.0 * 40 * 16 * 32 + 6.0 * 40 * 32 * 16 \
        + 6.0 * 40 * 32 * 16
    assert cost["mxu"] and (40, 32, 16) in cost["mxu_dims"]
    specs = [((), ())] + [((), ())] + [(("ep",), (), ())] * 3 \
        + [((), ())] * 3
    xfer = sharding_transfer(op, specs, shapes, outs, {"ep": 4})
    assert [n["kind"] for n in xfer["notes"]] == ["alltoall", "alltoall"]
    mla = _mla_op()
    shapes, outs, _ = mla.infer_shape([(2, SEQ, 32)] + [None] * 7)
    cost = op_cost(mla, shapes, outs)
    assert cost["mxu"] and cost["flops"] > 0 and cost["reduce_len"] == SEQ
    specs = [tuple(() for _ in s) for s in shapes]
    specs[3] = (("tp",), ())
    specs[7] = ((), ("tp",))
    assert ("tp",) in sharding_transfer(mla, specs, shapes, outs,
                                        {"tp": 2})["reduce"]
    norm = _op("RMSNorm")
    assert op_cost(norm, [(4, 32), (32,)], [(4, 32)])["reduce_len"] == 32


def test_schedule_report_prices_the_routed_layer():
    from mxnet_tpu.models import transformer_mla_moe
    net = transformer_mla_moe.get_symbol(
        vocab_size=64, num_layers=2, dim=32, seq_len=16, num_heads=2,
        q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8,
        qk_rope_head_dim=8, v_head_dim=12, intermediate_size=48,
        moe_intermediate_size=16, n_routed_experts=16, n_local_experts=6,
        num_experts_per_tok=4, num_nextn_predict_layers=0)
    from mxnet_tpu.analysis import analyze
    from mxnet_tpu.analysis.schedule import schedule_report  # noqa: F401
    from mxnet_tpu.parallel import LogicalMesh
    issues = [i for i in analyze(
        net, shapes={"data": (2, 16), "softmax_label": (2, 16)},
        mesh=LogicalMesh(ep=4)) if i.rule_id == "MXL-E006"]
    assert issues and "6 experts do not divide" in issues[0].message
    assert {i.node for i in issues} == {"layer1_moe"}


# -- the whole small model through ShardedTrainer.step ------------------------
def _model(compute_dtype=None):
    from mxnet_tpu import optimizer as opt_mod
    from mxnet_tpu.models import transformer_mla_moe
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.trainer import ShardedTrainer
    from perfbench.drivers.train_step_blocks import symbol_args
    net = transformer_mla_moe.get_symbol(**symbol_args(CFG, SEQ))
    batch = 2
    opt = opt_mod.create("sgd", learning_rate=0.5, momentum=0.9, wd=0.0,
                         rescale_grad=1.0 / (batch * SEQ))
    trainer = ShardedTrainer(net, opt, make_mesh(jax.devices()[:1], dp=1),
                             label_names=("softmax_label", "mtp_label"),
                             compute_dtype=compute_dtype)
    return net, trainer, batch


def _batch(batch, seed=0):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, CFG["vocab_size"], (batch, SEQ))
    lab = np.roll(ids, -1, axis=1)
    return (ids.astype(np.int32), lab.astype(np.float32),
            np.roll(lab, -1, axis=1).astype(np.float32))


def _one_step(seed=0):
    net, trainer, batch = _model()
    ids, lab, lab2 = _batch(batch, seed)
    shapes = {"data": (batch, SEQ)}
    labels = {"softmax_label": (batch, SEQ), "mtp_label": (batch, SEQ)}
    params = ref.init_params(CFG, jax.random.PRNGKey(20 + seed))
    assert {n: tuple(s) for n, s in ref.param_shapes(CFG).items()} == {
        n: tuple(trainer._shape_maps(shapes, labels)[0][n])
        for n in trainer.param_names}
    mom = {n: jnp.zeros_like(a) for n, a in params.items()}
    aux = trainer.init_aux(shapes, labels)
    feed = trainer.shard_batch({"data": ids, "softmax_label": lab,
                                "mtp_label": lab2})
    w0 = {n: np.asarray(a) for n, a in params.items()}
    _p, new_mom, new_aux, outs = trainer.step(params, mom, aux, feed)
    return w0, new_mom, new_aux, outs, (ids, lab, lab2)


def test_whole_model_losses_and_per_leaf_gradients():
    w0, mom, aux, outs, (ids, lab, lab2) = _one_step()
    w = {n: jnp.asarray(a) for n, a in w0.items()}
    (_loss, rows), grads = _highest(jax.value_and_grad(
        ref.loss_fn, has_aux=True))(w, jnp.asarray(ids), jnp.asarray(lab),
                                    jnp.asarray(lab2), CFG)
    for out, labels, name in ((outs[0], lab, "main"), (outs[1], lab2, "mtp")):
        p = np.take_along_axis(np.asarray(out), labels.reshape(-1, 1)
                               .astype(np.int64), axis=1)[:, 0]
        _close(-np.log(p), rows[name], 1e-4)
    assert set(grads) == set(mom) == set(ref.param_shapes(CFG))
    for n, g in grads.items():
        got = -np.asarray(mom[n]) / 0.5      # m1 = -lr * g
        assert np.abs(np.asarray(g)).max() > 0, n
        _close(got, g, 5e-4)
    # the counters of the three routed layers moved, the bias did not
    for layer in ("layer1_moe", "layer2_moe", "mtp_moe"):
        c = moe.routing_counters(aux, layer)
        assert c["local_assignments"][0] == c["expert_tokens"].sum() > 0
        assert not np.asarray(aux[layer + "_router_bias"]).any()


def test_shared_head_gets_both_losses_gradients():
    """``lm_head_weight`` and ``tok_embed_weight`` serve the main stream
    and the prediction module: their gradient is the main loss's plus
    0.3 times the module's."""
    w0, mom, _aux, _outs, (ids, lab, lab2) = _one_step(seed=1)
    w = {n: jnp.asarray(a) for n, a in w0.items()}

    def part(which):
        def loss(w):
            _l, rows = ref.loss_fn(w, jnp.asarray(ids), jnp.asarray(lab),
                                   jnp.asarray(lab2), CFG)
            return jnp.mean(rows[which])
        return _highest(jax.grad(loss))(w)

    main, mtp = part("main"), part("mtp")
    for leaf in ("lm_head_weight", "tok_embed_weight"):
        assert float(jnp.abs(mtp[leaf]).max()) > 0
        _close(-np.asarray(mom[leaf]) / 0.5, main[leaf] + 0.3 * mtp[leaf],
               5e-4)
    assert float(jnp.abs(main["mtp_proj_weight"]).max()) == 0.0


def test_prediction_module_reads_the_next_token_and_predicts_the_one_after():
    """The module's input is the label (token i+1) and its target
    ``mtp_label`` (token i+2): changing ``mtp_label`` moves nothing going
    forward; changing one label moves the module's rows from that
    position on (attention is causal) and none of the main head's."""
    _net, trainer, batch = _model()
    ids, lab, lab2 = _batch(batch, seed=2)
    params = ref.init_params(CFG, jax.random.PRNGKey(30))
    shapes = {"data": (batch, SEQ)}
    labels = {"softmax_label": (batch, SEQ), "mtp_label": (batch, SEQ)}
    aux = trainer.init_aux(shapes, labels)

    def probs(lab, lab2):
        feed = trainer.shard_batch({"data": ids, "softmax_label": lab,
                                    "mtp_label": lab2})
        return [np.asarray(o) for o in trainer.eval(params, aux, feed)]

    base = probs(lab, lab2)
    moved = probs(lab, (lab2 + 1) % CFG["vocab_size"])
    assert all(np.array_equal(a, b) for a, b in zip(base, moved))
    lab_b = lab.copy()
    lab_b[0, 9] = (lab_b[0, 9] + 1) % CFG["vocab_size"]
    other = probs(lab_b, lab2)
    assert np.array_equal(base[0], other[0])
    rows = np.abs(base[1] - other[1]).max(axis=1).reshape(batch, SEQ)
    assert not rows[0, :9].any() and not rows[1].any()
    assert rows[0, 9] > 0 and rows[0, 10:].all()


def test_module_path_keeps_integer_counters():
    """Through ``simple_bind`` the counters are int32 auxiliary states
    and a fused step in bfloat16 still counts exactly."""
    from mxnet_tpu.models import transformer_mla_moe
    from perfbench.drivers.train_step_blocks import symbol_args
    net = transformer_mla_moe.get_symbol(**symbol_args(CFG, SEQ))
    exe = net.simple_bind(mx.cpu(), data=(2, SEQ), softmax_label=(2, SEQ),
                          mtp_label=(2, SEQ))
    assert exe.aux_dict["layer1_moe_expert_tokens"].dtype == np.int32
    assert exe.aux_dict["layer1_moe_router_bias"].dtype == np.float32
    _net, trainer, batch = _model(compute_dtype="bfloat16")
    ids, lab, lab2 = _batch(batch, seed=3)
    shapes = {"data": (batch, SEQ)}
    labels = {"softmax_label": (batch, SEQ), "mtp_label": (batch, SEQ)}
    params, mom, aux = trainer.init_params(shapes, label_shapes=labels)
    feed = trainer.shard_batch({"data": ids, "softmax_label": lab,
                                "mtp_label": lab2})
    state = (params, mom, aux)
    for _ in range(3):
        *state, _outs = trainer.step(*state, feed)
    c = moe.routing_counters(state[2], "layer2_moe")
    assert c["expert_tokens"].dtype == np.int32
    assert c["local_assignments"][0] == c["expert_tokens"].sum()
    assert 0 < c["local_assignments"][0] <= 3 * batch * SEQ * 4
    assert c["peak_tokens_max"][0] * 3 >= c["peak_tokens_sum"][0]
