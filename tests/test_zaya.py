"""The ZAYA1-family layer set against the plain reference, at small sizes on
the CPU with seeded weights: tanh GELU, the half-split partial rotary, the
flash kernels with grouped query heads, compressed convolutional attention
(each of its steps shown to matter), the MLP router and its stream, the
top-1 routed layer on scores given from outside — the two shares of a layer
adding up to the uncut one, JoyAI's settings unchanged to the bit — and the
whole model through ``ShardedTrainer.step``: row losses, every leaf's
gradient, the tied matrix's two uses, and ``mirror_blocks`` carrying both
streams.

The reference is the benchmark's, ``perfbench/reference/zaya1.py`` (plain
``jax.numpy``, nothing of ``mxnet_tpu``)."""
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
from mxnet_tpu.ops.attention import rotary_half             # noqa: E402
from mxnet_tpu.ops.registry import create_operator, op_cost  # noqa: E402
from mxnet_tpu.parallel import ring_attention as ra          # noqa: E402
from perfbench.reference import zaya1 as ref                # noqa: E402

CFG = {
    "hidden_size": 32, "vocab_size": 64, "num_attention_heads": 8,
    "num_key_value_heads": 2, "head_dim": 8, "cca_time0": 2, "cca_time1": 2,
    "partial_rotary_factor": 0.5,
    "rope_parameters": {"hybrid": {"rope_theta": 10000.0,
                                   "partial_rotary_factor": 0.5}},
    "layer_types": ["hybrid"] * 3, "hidden_act": "silu",
    "tie_word_embeddings": True,
    "moe_intermediate_size": 16, "num_experts": 4, "num_experts_per_tok": 1,
    "router_hidden_size": 12, "num_hidden_layers": 3, "rms_norm_eps": 1e-5,
    # wide enough that a router's logits are told apart in float32
    "initializer_range": 0.3,
    "deployment": {"router_width": 8, "first_expert": 4},
    "program": {"mirror_blocks": True},
}
SEQ = 16
ATT_LEAVES = ("att_q_weight", "att_k_weight", "att_v_weight",
              "att_q_conv0_weight", "att_q_conv1_weight",
              "att_k_conv0_weight", "att_k_conv1_weight", "att_k_temp",
              "att_out_weight")
ROUTER_LEAVES = ("router_state_gain", "router_down_weight",
                 "router_norm_gamma", "router_fc1_weight",
                 "router_fc2_weight", "router_out_weight")
EXPERT_LEAVES = ("moe_expert_gate_weight", "moe_expert_up_weight",
                 "moe_expert_down_weight")


def _close(a, b, tol=2e-5):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    scale = max(np.abs(b).max(), 1e-30)
    assert np.abs(a - b).max() <= tol * scale, (np.abs(a - b).max(), scale)


def _highest(fn):
    def run(*args, **kw):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kw)
    return run


def _layer(key, layer=1):
    """Seeded leaves of one layer, by the reference's names, every scalar
    and vector moved off its seeded 1 / 0 / 0.5 so that it is exercised."""
    params = ref.init_params(CFG, key)
    n = len("layer%d_" % layer)
    p = {k[n:]: v for k, v in params.items()
         if k.startswith("layer%d_" % layer)}
    for i, (name, v) in enumerate(sorted(p.items())):
        if v.ndim == 1:
            p[name] = v + 0.2 * jax.random.normal(
                jax.random.fold_in(key, 100 + i), v.shape)
    return p


# -- GELU, rotary -------------------------------------------------------------
def test_gelu_is_the_tanh_approximation():
    x = jnp.linspace(-4.0, 4.0, 41)
    op = create_operator("Activation", act_type="gelu")
    got = op.forward([x], [], True, None)[0][0]
    want = 0.5 * x * (1 + np.tanh(np.sqrt(2 / np.pi)
                                  * (x + 0.044715 * x ** 3)))
    _close(got, want, 1e-6)
    _close(got, ref._gelu(x), 1e-6)
    exact = np.asarray(jax.nn.gelu(x, approximate=False))
    assert np.abs(np.asarray(got) - exact).max() > 1e-5     # not erf's


@pytest.mark.parametrize("rotary_dim", [8, 4])
def test_half_split_rotary_turns_the_first_channels_only(rotary_dim):
    """Channel i < r/2 of position p with channel i + r/2, read as a
    complex number, times exp(j p theta^(-2i/r)); channels from r on pass."""
    s, d, theta = 12, 8, 5e6
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(5), (2, 3, s, d)),
                   np.float64)
    half = rotary_dim // 2
    z = x[..., :half] + 1j * x[..., half:rotary_dim]
    ang = np.arange(s)[:, None] * theta ** (
        -np.arange(0, rotary_dim, 2) / rotary_dim)[None, :]
    turned = z * np.exp(1j * ang)
    want = np.concatenate([turned.real, turned.imag, x[..., rotary_dim:]],
                          axis=-1)
    x32 = jnp.asarray(x, jnp.float32)
    _close(rotary_half(x32, theta, rotary_dim), want, 1e-5)
    _close(ref.rotary_half(x32, theta, rotary_dim), want, 1e-5)
    q = jnp.tile(x32[:1, :1, :1], (1, 1, s, 1))
    scores = jnp.einsum("bhqd,bhkd->bhqk", rotary_half(q, 100.0, rotary_dim),
                        rotary_half(q, 100.0, rotary_dim))[0, 0]
    _close(scores[5, 3], scores[7, 5], 1e-5)    # the distance alone


# -- the flash kernels with grouped heads ------------------------------------
@pytest.mark.parametrize("heads,kv_heads,d_qk,d_v", [
    (8, 2, 128, 128),       # this family: 4 query heads a key/value head
    (4, 2, 64, 64),         # the dense LM's width
    (4, 1, 192, 128),       # latent attention's widths, one key/value head
])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_flash_kernels_with_grouped_heads(heads, kv_heads, d_qk, d_v, dtype):
    """Both Pallas kernels, interpreted, against the plain attention on k
    and v repeated over each group: out, dq, and dk, dv summed over it."""
    b, s = 2, 256
    dt = jnp.dtype(dtype)
    ks = jax.random.split(jax.random.PRNGKey(40), 4)
    q = jax.random.normal(ks[0], (b, heads, s, d_qk), dt)
    k = jax.random.normal(ks[1], (b, kv_heads, s, d_qk), dt)
    v = jax.random.normal(ks[2], (b, kv_heads, s, d_v), dt)
    ct = jax.random.normal(ks[3], (b, heads, s, d_v), jnp.float32)
    group = heads // kv_heads

    def kernel(q, k, v):
        o = ra.flash_attention(q, k, v, causal=True, block_q=128,
                               block_k=64, interpret=True)
        return jnp.sum(o.astype(jnp.float32) * ct), o

    def plain(q, k, v):
        q, k, v = (t.astype(jnp.float32) for t in (q, k, v))
        o = ra.attention_reference(q, jnp.repeat(k, group, axis=1),
                                   jnp.repeat(v, group, axis=1), causal=True)
        return jnp.sum(o * ct), o

    (_, got_o), got = jax.value_and_grad(kernel, (0, 1, 2), has_aux=True)(
        q, k, v)
    (_, want_o), want = _highest(jax.value_and_grad(
        plain, (0, 1, 2), has_aux=True))(q, k, v)
    # float32 operands: the interpreter multiplies at full precision;
    # bfloat16: p and ds are rounded to 8 bits for the products
    tol = 2e-5 if dtype == "float32" else 3e-2
    assert got_o.shape == (b, heads, s, d_v) and got_o.dtype == dt
    assert got[1].shape == k.shape and got[2].shape == v.shape
    _close(got_o.astype(jnp.float32), want_o, tol)
    for g, w in zip(got, want):
        _close(g.astype(jnp.float32), w, tol)


def test_grouped_heads_must_divide():
    q = jnp.zeros((1, 6, 128, 8))
    kv = jnp.zeros((1, 4, 128, 8))
    with pytest.raises(ValueError, match="do not group"):
        ra.flash_attention(q, kv, kv, causal=True, interpret=True)


def test_kernel_specs_describe_grouped_blocks():
    """The tile validator's specs come from the layouts the calls use: k
    and v by key/value head forward; a group's q rows in one block
    backward."""
    fwd = ra.flash_kernel_spec(8, 1024, 1024, 128, group=4)
    by_name = {blk["name"]: blk for blk in fwd["blocks"]}
    assert by_name["k"]["array"] == (2, 1024, 128)
    assert by_name["q"]["array"] == (8, 1024, 128)
    bwd = ra.flash_backward_kernel_spec(8, 1024, 1024, 128, group=4)
    by_name = {blk["name"]: blk for blk in bwd["blocks"]}
    assert bwd["grid"] == (2, 2)
    assert by_name["q"]["array"] == by_name["dq"]["array"] == (2, 4096, 128)
    assert by_name["dk"]["array"] == (2, 1024, 128)
    from mxnet_tpu.analysis.tiling import spec_findings
    assert not spec_findings(fwd) and not spec_findings(bwd)


# -- compressed convolutional attention ------------------------------------
def _cca_op():
    return create_operator(
        "CompressedConvAttention", num_heads=8, num_kv_heads=2, head_dim=8,
        conv_taps0=2, conv_taps1=2, rope_theta=10000.0,
        partial_rotary_factor=0.5, eps=1e-5)


def _cca_inputs(seed=50):
    key = jax.random.PRNGKey(seed)
    p = _layer(key)
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, SEQ, 32))
    return x, p, [p[n] for n in ATT_LEAVES]


def test_cca_forward_and_backward():
    x, _p, leaves = _cca_inputs()
    op = _cca_op()
    w = jax.random.normal(jax.random.PRNGKey(51), x.shape)

    def prog(x, *leaves):
        return op.forward([x] + list(leaves), [], True, None)[0][0]

    def plain(x, *leaves):
        return ref.cca(x, dict(zip(ATT_LEAVES, leaves)), CFG)

    _close(prog(x, *leaves), _highest(plain)(x, *leaves), 2e-5)
    wrt = tuple(range(1 + len(leaves)))
    got = jax.grad(lambda *a: jnp.sum(prog(*a) * w), wrt)(x, *leaves)
    want = jax.grad(lambda *a: jnp.sum(_highest(plain)(*a) * w), wrt)(
        x, *leaves)
    for name, a, b in zip(("data",) + ATT_LEAVES, got, want):
        assert float(jnp.abs(b).max()) > 0, name
        _close(a, b, 1e-4)
    shapes = op.infer_shape([(2, SEQ, 32)] + [None] * len(ATT_LEAVES))[0]
    assert [tuple(s) for s in shapes[1:]] == [tuple(a.shape) for a in leaves]
    cost = op_cost(op, shapes, [(2, SEQ, 32)])
    assert cost["mxu"] and cost["flops"] > 0 and cost["reduce_len"] == SEQ


@pytest.mark.parametrize("step", ref.CCA_STEPS)
def test_cca_every_step_matters(step):
    """Steps 2–6 of the op's docstring (value shift, the convolutions, the
    q-k mean, the normalisation with its temperature, rotary): the op
    differs from a reference that leaves any one of them out, by far more
    than it differs from the whole reference."""
    x, p, leaves = _cca_inputs(seed=52)
    got = _cca_op().forward([x] + leaves, [], True, None)[0][0]
    whole = _highest(ref.cca)(x, p, CFG)
    less = _highest(ref.cca)(x, p, CFG, without=(step,))
    scale = float(jnp.abs(whole).max())
    assert float(jnp.abs(got - whole).max()) < 2e-5 * scale
    assert float(jnp.abs(got - less).max()) > 1e-2 * scale, step


def test_cca_value_shift_reads_the_previous_token():
    """Key/value head 1's value at position t is made from token t − 1:
    moving the last token's input moves no output through head 1's value,
    and position 0 reads zeros there."""
    x, p, _ = _cca_inputs(seed=53)
    v = x @ p["att_v_weight"].T
    from mxnet_tpu.ops.attention import shift_tokens
    shifted = shift_tokens(v[..., 8:], 1)
    assert not np.asarray(shifted[:, 0]).any()
    _close(shifted[:, 1:], v[:, :-1, 8:], 1e-7)


def test_cca_rejects_heads_that_do_not_group():
    op = create_operator("CompressedConvAttention", num_heads=6,
                         num_kv_heads=4, head_dim=8)
    with pytest.raises(mx.base.MXNetError, match="key/value heads"):
        op.infer_shape([(2, SEQ, 32)] + [None] * len(ATT_LEAVES))


# -- the router and the routed layer ----------------------------------------
def _router_op(has_state=True):
    return create_operator("MLPRouter", num_experts=8, hidden_size=12,
                           has_state=has_state, eps=1e-5)


def _zero_aux(op, e=32):
    n_in = len(op.list_arguments())
    shapes = op.infer_shape([(1, e)] + [None] * (n_in - 1))[2]
    types = op.infer_type([np.dtype("float32")])[2]
    return [jnp.zeros(s, t) for s, t in zip(shapes, types)]


@pytest.mark.parametrize("has_state", [True, False])
def test_router_scores_and_stream(has_state):
    key = jax.random.PRNGKey(60)
    p = _layer(key)
    u = jax.random.normal(jax.random.fold_in(key, 1), (40, 32))
    state = jax.random.normal(jax.random.fold_in(key, 2), (40, 12)) \
        if has_state else None
    names = ROUTER_LEAVES if has_state else ROUTER_LEAVES[1:]
    op = _router_op(has_state)
    lead = [u, state] if has_state else [u]

    def prog(*args):
        return op.forward(list(args), [], True, None)[0]

    def plain(*args):
        u, rest = args[0], args[1:]
        if has_state:
            st, rest = rest[0], rest[1:]
        else:
            st = None
        return ref.router(u, st, dict(zip(names, rest)), CFG)

    args = lead + [p[n] for n in names]
    (scores, stream), (want_scores, want_stream) = prog(*args), \
        _highest(plain)(*args)
    assert scores.dtype == jnp.float32 and scores.shape == (40, 8)
    _close(jnp.sum(scores, axis=1), jnp.ones((40,)), 1e-6)
    _close(scores, want_scores, 2e-5)
    _close(stream, want_stream, 2e-5)
    w = jax.random.normal(jax.random.fold_in(key, 3), (40, 8))
    w2 = jax.random.normal(jax.random.fold_in(key, 4), (40, 12))

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a)[0] * w) + jnp.sum(fn(*a)[1] * w2)

    wrt = tuple(range(len(args)))
    got = jax.grad(loss(prog), wrt)(*args)
    want = jax.grad(loss(_highest(plain)), wrt)(*args)
    for a, b in zip(got, want):
        _close(a, b, 1e-4)
    shapes = op.infer_shape([(40, 32)] + [None] * (len(args) - 1))
    assert [tuple(s) for s in shapes[0]] == [tuple(a.shape) for a in args]
    assert shapes[1] == [(40, 8), (40, 12)]
    assert op.infer_type([np.dtype("float32")])[1][0] == np.dtype("float32")


def _routed_op(first=4, held=4, width=8):
    return create_operator(
        "RoutedExperts", num_experts=width, num_local_experts=held,
        first_expert=first, hidden_size=16, top_k=1, score_func="given",
        norm_topk_prob=False)


def _routed_inputs(seed=61, tokens=48):
    key = jax.random.PRNGKey(seed)
    p = _layer(key)
    u = jax.random.normal(jax.random.fold_in(key, 1), (tokens, 32))
    state = jax.random.normal(jax.random.fold_in(key, 2), (tokens, 12))
    return u, state, p


def test_top1_routed_layer_forward_backward_and_counters():
    """Router and routed layer together against the reference's layer: the
    gate is the softmax score itself, its gradient reaches the router's
    every leaf and the stream it took."""
    u, state, p = _routed_inputs()
    router, routed = _router_op(), _routed_op()
    names = ROUTER_LEAVES + EXPERT_LEAVES
    w = jax.random.normal(jax.random.PRNGKey(62), u.shape)

    def prog(u, state, *leaves):
        scores, stream = router.forward(
            [u, state] + list(leaves[:len(ROUTER_LEAVES)]), [], True,
            None)[0]
        outs, aux = routed.forward(
            [u, scores] + list(leaves[len(ROUTER_LEAVES):]),
            _zero_aux(routed), True, None)
        return outs[0], stream, aux

    def plain(u, state, *leaves):
        return ref.routed_layer(u, state, dict(zip(names, leaves)), CFG)

    leaves = [p[n] for n in names]
    out, stream, aux = prog(u, state, *leaves)
    want_out, want_stream, margin = _highest(plain)(u, state, *leaves)
    assert float(jnp.min(margin)) > 1e-4        # no near-tie in this draw
    _close(out, want_out, 5e-5)
    _close(stream, want_stream, 2e-5)
    wrt = tuple(range(2 + len(leaves)))
    got = jax.grad(lambda *a: jnp.sum(prog(*a)[0] * w), wrt)(
        u, state, *leaves)
    want = jax.grad(lambda *a: jnp.sum(_highest(plain)(*a)[0] * w), wrt)(
        u, state, *leaves)
    for name, a, b in zip(("data", "state") + names, got, want):
        assert float(jnp.abs(b).max()) > 0, name
        _close(a, b, 2e-4)
    # the counters: what the reference's argmax says landed on experts 4-7
    probs, _ = _highest(ref.router)(u, state, p, CFG)
    chosen = np.asarray(jnp.argmax(probs, axis=1))
    per_expert = [int(np.sum(chosen == e)) for e in range(4, 8)]
    _bias, total, tokens, peak_sum, peak_max = (np.asarray(a) for a in aux)
    assert tokens.tolist() == per_expert and total[0] == sum(per_expert)
    assert peak_sum[0] == peak_max[0] == max(per_expert)
    assert 0 < total[0] < 48                    # some land elsewhere


def test_gate_is_the_score_itself_not_renormalised():
    scores = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(63),
                                              (20, 8)), axis=-1)
    idx, w = moe.route_topk(scores, jnp.zeros((8,)), 1, normalize=False)
    _close(w[:, 0], jnp.max(scores, axis=1), 1e-7)
    assert np.array_equal(np.asarray(idx[:, 0]),
                          np.asarray(jnp.argmax(scores, axis=1)))
    bias = jnp.zeros((8,)).at[5].set(10.0)      # steers, weighs nothing
    idx, w = moe.route_topk(scores, bias, 1, normalize=False)
    assert np.asarray(idx == 5).all()
    _close(w[:, 0], scores[:, 5], 1e-7)


def test_two_shares_add_up_to_the_uncut_layer():
    """Experts 0–3 on one chip, 4–7 on the other, each routing over all 8:
    the two partial results add up to the reference's uncut layer, every
    token computed exactly once, and the reference given a share gives
    that share's part."""
    u, state, p = _routed_inputs(seed=64)
    full = jax.random.split(jax.random.PRNGKey(65), 3)
    p = dict(p,
             moe_expert_gate_weight=0.3 * jax.random.normal(full[0],
                                                            (8, 16, 32)),
             moe_expert_up_weight=0.3 * jax.random.normal(full[1],
                                                          (8, 16, 32)),
             moe_expert_down_weight=0.3 * jax.random.normal(full[2],
                                                            (8, 32, 16)))
    whole, _, _ = _highest(ref.routed_layer)(u, state, p, CFG, first=0,
                                             held=8)
    scores, _ = _router_op().forward(
        [u, state] + [p[n] for n in ROUTER_LEAVES], [], True, None)[0]
    total, counted = jnp.zeros_like(u), 0
    for first in (0, 4):
        op = _routed_op(first=first)
        cut = {n: p[n][first:first + 4] for n in EXPERT_LEAVES}
        outs, aux = op.forward([u, scores] + [cut[n] for n in EXPERT_LEAVES],
                               _zero_aux(op), True, None)
        part, _, _ = _highest(ref.routed_layer)(u, state, dict(p, **cut),
                                                CFG, first=first, held=4)
        _close(outs[0], part, 5e-5)
        total, counted = total + outs[0], counted + int(aux[1][0])
    assert counted == 48                # one expert a token, once
    _close(total, whole, 5e-5)


def test_joyai_settings_are_unchanged_to_the_bit():
    """``RoutedExperts`` as JoyAI-LLM-Flash sets it (sigmoid scores of its
    own router weight, 4 of 16, normalised, scaled) against the routing
    arithmetic written out as it stood before scores could be given."""
    key = jax.random.PRNGKey(66)
    ks = jax.random.split(key, 5)
    h = jax.random.normal(ks[0], (40, 32))
    w_router = 0.3 * jax.random.normal(ks[1], (16, 32))
    gate, up = (0.3 * jax.random.normal(k, (4, 16, 32)) for k in ks[2:4])
    down = 0.3 * jax.random.normal(ks[4], (4, 32, 16))
    op = create_operator(
        "RoutedExperts", num_experts=16, num_local_experts=4, first_expert=4,
        hidden_size=16, top_k=4, routed_scaling_factor=2.5)
    assert op.list_arguments()[:2] == ["data", "router_weight"]
    got = op.forward([h, w_router, gate, up, down], _zero_aux(op), True,
                     None)[0][0]
    scores = jax.nn.sigmoid(jnp.dot(h, w_router.T,
                                    preferred_element_type=jnp.float32))
    _, idx = jax.lax.top_k(scores + jnp.zeros((16,), jnp.float32), 4)
    chosen = idx[..., None] == jnp.arange(16)
    w = jnp.sum(jnp.where(chosen, scores[:, None, :], 0.0), axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    want = moe.routed_experts(h, w * 2.5, idx.astype(jnp.int32), gate, up,
                              down, 4, moe.CHUNK_ROWS)[0]
    assert np.array_equal(np.asarray(got), np.asarray(want))
    idx2, w2 = moe.route_sigmoid_topk(h, w_router, jnp.zeros((16,)), 4, 2.5)
    assert np.array_equal(np.asarray(idx2), np.asarray(idx))
    assert np.array_equal(np.asarray(w2), np.asarray(w * 2.5))


def test_given_scores_cost_no_router_product():
    op = _routed_op()
    shapes, outs, _aux = op.infer_shape([(40, 32)] + [None] * 4)
    assert shapes[1] == (40, 8)                 # scores: tokens x experts
    cost = op_cost(op, shapes, outs)
    assert cost["flops"] == 6.0 * 20 * 32 * 16  # 40·1·4/8 expected rows
    router = _router_op()
    shapes, outs, _ = router.infer_shape([(40, 32)] + [None] * 7)
    assert op_cost(router, shapes, outs)["flops"] == 2.0 * 40 * (
        32 * 12 + 2 * 12 * 12 + 12 * 8)


# -- the whole small model through ShardedTrainer.step ------------------------
def _model(mirror=True, compute_dtype=None):
    from mxnet_tpu import optimizer as opt_mod
    from mxnet_tpu.models import transformer_cca_moe
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.trainer import ShardedTrainer
    from perfbench.drivers.train_step_zaya import symbol_args
    cfg = dict(CFG, program={"mirror_blocks": mirror})
    net = transformer_cca_moe.get_symbol(**symbol_args(cfg, SEQ))
    batch = 2
    opt = opt_mod.create("sgd", learning_rate=0.5, momentum=0.9, wd=0.0,
                         rescale_grad=1.0 / (batch * SEQ))
    trainer = ShardedTrainer(net, opt, make_mesh(jax.devices()[:1], dp=1),
                             label_names=("softmax_label",),
                             compute_dtype=compute_dtype)
    return net, trainer, batch


def _batch(batch, seed=0):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, CFG["vocab_size"], (batch, SEQ))
    return ids.astype(np.int32), np.roll(ids, -1, axis=1).astype(np.float32)


def _seeded(seed):
    """The reference's seeded weights with every scale, bias, gain and
    temperature moved off its seed, so that each takes a gradient that
    tells it from its neighbours."""
    key = jax.random.PRNGKey(70 + seed)
    params = ref.init_params(CFG, key)
    for i, (name, v) in enumerate(sorted(params.items())):
        if v.ndim == 1:
            params[name] = v + 0.1 * jax.random.normal(
                jax.random.fold_in(key, 100 + i), v.shape)
    return params


def _one_step(seed=0, mirror=True):
    _net, trainer, batch = _model(mirror)
    ids, lab = _batch(batch, seed)
    shapes, labels = {"data": (batch, SEQ)}, {"softmax_label": (batch, SEQ)}
    params = _seeded(seed)
    assert {n: tuple(s) for n, s in ref.param_shapes(CFG).items()} == {
        n: tuple(trainer._shape_maps(shapes, labels)[0][n])
        for n in trainer.param_names}
    mom = {n: jnp.zeros_like(a) for n, a in params.items()}
    aux = trainer.init_aux(shapes, labels)
    feed = trainer.shard_batch({"data": ids, "softmax_label": lab})
    w0 = {n: np.asarray(a) for n, a in params.items()}
    _p, new_mom, new_aux, outs = trainer.step(params, mom, aux, feed)
    return w0, new_mom, new_aux, outs, (ids, lab)


def test_whole_model_losses_and_per_leaf_gradients():
    w0, mom, aux, outs, (ids, lab) = _one_step()
    w = {n: jnp.asarray(a) for n, a in w0.items()}
    (_loss, rows), grads = _highest(jax.value_and_grad(
        ref.loss_fn, has_aux=True))(w, jnp.asarray(ids), jnp.asarray(lab),
                                    CFG)
    assert float(jnp.min(rows["margin"])) > 1e-5    # no near-tie drawn
    p = np.take_along_axis(np.asarray(outs[0]), lab.reshape(-1, 1)
                           .astype(np.int64), axis=1)[:, 0]
    _close(-np.log(p), rows["main"], 1e-4)
    assert set(grads) == set(mom) == set(ref.param_shapes(CFG))
    assert "lm_head_weight" not in grads        # the head is the embedding
    for n, g in grads.items():
        assert np.abs(np.asarray(g)).max() > 0, n
        _close(-np.asarray(mom[n]) / 0.5, g, 5e-4)      # m1 = -lr * g
    for i in range(3):
        c = moe.routing_counters(aux, "layer%d_moe" % i)
        assert c["local_assignments"][0] == c["expert_tokens"].sum()
        assert not np.asarray(aux["layer%d_moe_router_bias" % i]).any()
    assert sum(int(moe.routing_counters(aux, "layer%d_moe" % i)
                   ["local_assignments"][0]) for i in range(3)) > 0


def test_tied_matrix_gets_the_embeddings_and_the_heads_gradient():
    """One variable is read by ``Embedding`` and by the head's product:
    its gradient is the sum of the two uses', each taken alone in the
    reference (the other use's copy held constant)."""
    w0, mom, _aux, _outs, (ids, lab) = _one_step(seed=1)
    w = {n: jnp.asarray(a) for n, a in w0.items()}

    def loss(embed_use, head_use):
        p = dict(w, tok_embed_weight=embed_use)
        x = p["tok_embed_weight"][jnp.asarray(ids)]
        state = None
        for i in range(CFG["num_hidden_layers"]):
            x, state, _ = ref.layer(x, state, ref._leaves(p, "layer%d" % i),
                                    CFG)
        rows = ref._head_rows(x, p["final_norm_gamma"], head_use,
                              jnp.asarray(lab), CFG, None)
        return jnp.mean(rows)

    table = w["tok_embed_weight"]
    g_embed, g_head = _highest(jax.grad(loss, (0, 1)))(table, table)
    assert float(jnp.abs(g_embed).max()) > 0 < float(jnp.abs(g_head).max())
    got = -np.asarray(mom["tok_embed_weight"]) / 0.5
    _close(got, g_embed + g_head, 5e-4)
    assert np.abs(got - np.asarray(g_head)).max() > 1e-3 * np.abs(got).max()


def test_mirror_blocks_carries_both_streams():
    """Per-layer recomputation with two streams crossing every segment's
    edge gives the gradients of the unmirrored step — those of an earlier
    layer's router among them, which the later layers' routing reaches
    through the stream alone."""
    _w0, mirrored, aux_m, outs_m, _ = _one_step(seed=2, mirror=True)
    _w0, plain, aux_p, outs_p, _ = _one_step(seed=2, mirror=False)
    _close(outs_m[0], outs_p[0], 1e-6)
    for n in plain:
        _close(mirrored[n], plain[n], 1e-5)
    for leaf in ("layer0_router_down_weight", "layer1_router_down_weight",
                 "layer1_router_state_gain", "layer2_router_state_gain"):
        assert float(jnp.abs(plain[leaf]).max()) > 0, leaf
    for n in aux_p:
        assert np.array_equal(np.asarray(aux_m[n]), np.asarray(aux_p[n])), n


def test_earlier_routers_learn_through_the_stream_alone():
    """Cut the stream between layers 0 and 1 (gain 0) and layer 0's W_d
    loses the part of its gradient that came through later layers'
    routing: the stream carries gradient, not only values."""
    w0, mom, _aux, _outs, (ids, lab) = _one_step(seed=3)
    w = {n: jnp.asarray(a) for n, a in w0.items()}

    def grad_down(gain):
        p = dict(w, layer1_router_state_gain=jnp.full((1,), gain))
        return _highest(jax.grad(lambda p: ref.loss_fn(
            p, jnp.asarray(ids), jnp.asarray(lab), CFG)[0]))(p)[
                "layer0_router_down_weight"]

    with_stream = grad_down(float(w0["layer1_router_state_gain"][0]))
    without = grad_down(0.0)
    _close(-np.asarray(mom["layer0_router_down_weight"]) / 0.5, with_stream,
           5e-4)
    assert float(jnp.abs(with_stream - without).max()) \
        > 1e-2 * float(jnp.abs(with_stream).max())


def test_balance_sends_every_expert_its_share():
    """Scores whose shared part outweighs what tells tokens apart: without
    β three experts take most tokens, with :func:`balance`'s β each of the
    16 takes its 64 of 1,024 — and β weighs nothing."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(90))
    logits = 0.008 * jax.random.normal(k1, (1024, 16)) \
        + 0.03 * jax.random.normal(k2, (16,))
    probs = jax.nn.softmax(logits, axis=-1)
    plain = np.bincount(np.asarray(jnp.argmax(probs, axis=1)), minlength=16)
    assert plain.max() > 400
    beta = ref.balance(probs)
    steered = np.bincount(np.asarray(jnp.argmax(probs + beta, axis=1)),
                          minlength=16)
    assert steered.min() >= 62 and steered.max() <= 66, steered
    idx, w = moe.route_topk(probs, beta, 1, normalize=False)
    _close(w[:, 0], jnp.take_along_axis(probs, idx, axis=1)[:, 0], 1e-7)


def test_seeded_balancing_bias_reaches_program_and_reference_alike():
    """The benchmark's β, made from the seeded weights and a batch, set as
    the program's auxiliary state and handed to the reference: the same
    rows' losses, every expert held gets its share, and the step leaves β
    as it was."""
    _net, trainer, batch = _model()
    ids, lab = _batch(batch, seed=5)
    shapes, labels = {"data": (batch, SEQ)}, {"softmax_label": (batch, SEQ)}
    params = _seeded(5)
    bias = _highest(ref.balancing_bias)(CFG, params, jnp.asarray(ids))
    assert bias.shape == (3, 8) and float(jnp.abs(bias).max()) > 0
    aux = trainer.init_aux(shapes, labels)
    for i in range(3):
        aux["layer%d_moe_router_bias" % i] = bias[i]
    mom = {n: jnp.zeros_like(a) for n, a in params.items()}
    feed = trainer.shard_batch({"data": ids, "softmax_label": lab})
    w = {n: jnp.asarray(np.asarray(a)) for n, a in params.items()}
    _p, _m, new_aux, outs = trainer.step(params, mom, aux, feed)
    _loss, rows = _highest(ref.loss_fn)(w, jnp.asarray(ids),
                                        jnp.asarray(lab), CFG, bias=bias)
    p = np.take_along_axis(np.asarray(outs[0]), lab.reshape(-1, 1)
                           .astype(np.int64), axis=1)[:, 0]
    _close(-np.log(p), rows["main"], 1e-4)
    for i in range(3):
        c = moe.routing_counters(new_aux, "layer%d_moe" % i)
        # 32 tokens over 8 experts, 4 of them here: 4 each, 16 in all
        assert c["expert_tokens"].tolist() == [4, 4, 4, 4]
        assert np.array_equal(np.asarray(new_aux["layer%d_moe_router_bias"
                                                 % i]), np.asarray(bias[i]))


def test_bfloat16_step_counts_exactly_and_keeps_float32_masters():
    _net, trainer, batch = _model(compute_dtype="bfloat16")
    ids, lab = _batch(batch, seed=4)
    shapes, labels = {"data": (batch, SEQ)}, {"softmax_label": (batch, SEQ)}
    params = ref.init_params(CFG, jax.random.PRNGKey(80))
    mom = {n: jnp.zeros_like(a) for n, a in params.items()}
    aux = trainer.init_aux(shapes, labels)
    feed = trainer.shard_batch({"data": ids, "softmax_label": lab})
    state = (params, mom, aux)
    for _ in range(3):
        *state, _outs = trainer.step(*state, feed)
    assert all(a.dtype == jnp.float32 for a in state[0].values())
    total = 0
    for i in range(3):
        c = moe.routing_counters(state[2], "layer%d_moe" % i)
        assert c["expert_tokens"].dtype == np.int32
        assert c["local_assignments"][0] == c["expert_tokens"].sum()
        assert c["local_assignments"][0] <= 3 * batch * SEQ
        total += int(c["local_assignments"][0])
    assert total > 0
