"""The Qwen3-Next-family layer set against the plain reference, at small sizes
on the CPU with seeded weights: the chunk-parallel gated delta rule against
the per-token recurrence (outputs and every gradient), the triangular
inverse it rests on, the ``GatedDeltaNet`` and ``GatedAttention`` ops (each
step of the delta-rule layer shown to matter), softmax top-k routing with a
gated shared expert — the sixteen shares of a layer adding up to the uncut
one — both flash kernels at a group of eight query heads of 256 on one
key/value head (the backward's group split over programs), a mirrored
attention block that runs the forward kernel once, and the whole model
through ``ShardedTrainer.step``: row losses, every leaf's gradient,
``mirror_blocks``.

The reference is the benchmark's, ``perfbench/reference/qwen3_next.py``
(plain ``jax.numpy``, nothing of ``mxnet_tpu``, the recurrence a token at a
time)."""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import mxnet_tpu as mx                                      # noqa: E402,F401
from mxnet_tpu.ops import linear_attention as la            # noqa: E402
from mxnet_tpu.ops import moe                                # noqa: E402
from mxnet_tpu.ops.registry import create_operator, op_cost  # noqa: E402
from mxnet_tpu.parallel import ring_attention as ra          # noqa: E402
from perfbench.reference import qwen3_next as ref           # noqa: E402

CFG = {
    "hidden_size": 32, "vocab_size": 64, "full_attention_interval": 4,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
    "partial_rotary_factor": 0.25, "rope_theta": 10000.0,
    "linear_num_key_heads": 2, "linear_num_value_heads": 4,
    "linear_key_head_dim": 8, "linear_value_head_dim": 4,
    "linear_conv_kernel_dim": 4, "hidden_act": "silu",
    "norm_topk_prob": True, "tie_word_embeddings": False,
    "mlp_only_layers": [], "decoder_sparse_step": 1,
    "moe_intermediate_size": 16, "shared_expert_intermediate_size": 12,
    "num_experts": 2, "num_experts_per_tok": 10, "num_hidden_layers": 4,
    "rms_norm_eps": 1e-6,
    # wide enough that a router's scores are told apart in float32
    "initializer_range": 0.3,
    "dt_bias_init": {"dt_min": 0.01, "dt_max": 0.5},
    "deployment": {"router_width": 32, "first_expert": 6},
    "program": {"mirror_blocks": True, "delta_chunk": 16},
}
SEQ = 64
F32 = jnp.float32     # conftest turns x64 on: every draw says its dtype
GDN_LEAVES = ("gdn_in_proj_qkvz_weight", "gdn_in_proj_ba_weight",
              "gdn_conv_weight", "gdn_A_log", "gdn_dt_bias",
              "gdn_norm_gamma", "gdn_out_weight")
ATT_LEAVES = ("att_q_weight", "att_k_weight", "att_v_weight",
              "att_q_norm_gamma", "att_k_norm_gamma", "att_out_weight")
MOE_LEAVES = ("moe_router_weight", "moe_expert_gate_weight",
              "moe_expert_up_weight", "moe_expert_down_weight",
              "moe_shared_gate_weight", "moe_shared_up_weight",
              "moe_shared_down_weight", "moe_shared_score_weight")


def _close(a, b, tol=2e-5):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    scale = max(np.abs(b).max(), 1e-30)
    assert np.abs(a - b).max() <= tol * scale, (np.abs(a - b).max(), scale)


def _highest(fn):
    def run(*args, **kw):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kw)
    return run


def _layer(key, layer):
    """Seeded leaves of one layer, by the reference's names, every vector
    moved off its seeded 1 so that it is exercised."""
    params = ref.init_params(CFG, key)
    n = len("layer%d_" % layer)
    p = {k[n:]: v for k, v in params.items()
         if k.startswith("layer%d_" % layer)}
    for i, (name, v) in enumerate(sorted(p.items())):
        if name.endswith("_gamma"):
            p[name] = v + 0.2 * jax.random.normal(
                jax.random.fold_in(key, 100 + i), v.shape, dtype=F32)
    return p


# -- the rule: chunk-parallel against the recurrence --------------------------
def _rule_inputs(seed=0, B=2, S=128, H=3, dk=16, dv=8):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = la.l2_normalise(jax.random.normal(ks[0], (B, S, H, dk), dtype=F32)) \
        * dk ** -0.5
    # keys that lean one way: neighbours' products are far from 0
    k = la.l2_normalise(jax.random.normal(ks[1], (B, S, H, dk), dtype=F32)
                        + 1.0)
    v = jax.random.normal(ks[2], (B, S, H, dv), dtype=F32)
    g = -jnp.exp(jax.random.uniform(ks[3], (B, S, H), F32, -4, 3))
    beta = jax.nn.sigmoid(2.0 * jax.random.normal(ks[4], (B, S, H),
                                                  dtype=F32))
    return (q, k, v, g, beta), jax.random.normal(ks[5], (B, S, H, dv),
                                                 dtype=F32)


@pytest.mark.parametrize("chunk", [16, 64])
def test_chunked_rule_is_the_recurrence(chunk):
    """Outputs and the gradient of every input, over a sequence of several
    chunks, decays from a token's memory to hundreds."""
    args, w = _rule_inputs()

    def value_and_grads(fn):
        return jax.value_and_grad(
            lambda *a: jnp.sum(fn(*a) * w), argnums=(0, 1, 2, 3, 4))(*args)

    want_o = la.gated_delta_rule_recurrent(*args)
    got_o = la.gated_delta_rule(*args, chunk=chunk)
    assert got_o.shape == want_o.shape and got_o.dtype == jnp.float32
    _close(got_o, want_o, 2e-5)
    _, want = value_and_grads(la.gated_delta_rule_recurrent)
    _, got = value_and_grads(lambda *a: la.gated_delta_rule(*a, chunk=chunk))
    for name, a, b in zip(("q", "k", "v", "g", "beta"), got, want):
        assert float(jnp.abs(b).max()) > 0, name
        _close(a, b, 5e-5)


def test_chunked_rule_equals_the_references_recurrence():
    """The benchmark's reference writes the recurrence out on its own (no
    matrix unit, blocks rematerialised): the two recurrences agree, and
    the planted fault (no delta correction) is another function."""
    args, _w = _rule_inputs(seed=1)
    want = ref.delta_rule(*args)
    _close(la.gated_delta_rule_recurrent(*args), want, 1e-5)
    _close(la.gated_delta_rule(*args, chunk=32), want, 2e-5)
    faulty = ref.delta_rule(*args, without=("delta",))
    assert float(jnp.abs(faulty - want).max()) \
        > 0.05 * float(jnp.abs(want).max())


def test_rule_survives_any_decay():
    """Whatever g is — a state that forgets within a token, one that never
    forgets — nothing overflows: no exp takes a positive argument."""
    (q, k, v, _g, beta), w = _rule_inputs(seed=2, S=64)
    for g in (jnp.full(beta.shape, -80.0), jnp.zeros(beta.shape)):
        out, grads = jax.value_and_grad(
            lambda q, k, v, g, beta: jnp.sum(
                la.gated_delta_rule(q, k, v, g, beta, chunk=16) * w),
            argnums=(0, 1, 2, 3, 4))(q, k, v, g, beta)
        assert np.isfinite(float(out))
        assert all(bool(jnp.isfinite(t).all()) for t in grads)
        _close(la.gated_delta_rule(q, k, v, g, beta, chunk=16),
               la.gated_delta_rule_recurrent(q, k, v, g, beta), 2e-5)


def test_rule_wants_whole_chunks():
    (q, k, v, g, beta), _w = _rule_inputs(S=48)
    with pytest.raises(ValueError):
        la.gated_delta_rule(q, k, v, g, beta, chunk=32)
    with pytest.raises(ValueError):
        la.unit_lower_inverse(jnp.zeros((48, 48)))
    # shorter than a chunk: one chunk of its own length
    (q, k, v, g, beta), _w = _rule_inputs(S=32)
    _close(la.gated_delta_rule(q, k, v, g, beta, chunk=64),
           la.gated_delta_rule_recurrent(q, k, v, g, beta), 2e-5)


@pytest.mark.parametrize("size", [1, 2, 16, 64])
def test_unit_lower_inverse(size):
    """Block substitution inverts I + tril(a, −1), reads nothing on or above
    the diagonal, and stays exact where a Neumann series would not: every
    entry below the diagonal 0.9."""
    key = jax.random.PRNGKey(size)
    a = 0.3 * jax.random.normal(key, (3, size, size), dtype=F32)
    low = jnp.tril(a, -1)
    inv = la.unit_lower_inverse(a)
    eye = jnp.eye(size)
    _close(_highest(jnp.matmul)(inv, eye + low), jnp.broadcast_to(
        eye, a.shape), 1e-5)
    assert not np.asarray(jnp.triu(inv, 1)).any()
    same = la.unit_lower_inverse(jnp.full((size, size), 0.9))
    _close(_highest(jnp.matmul)(same, eye + jnp.tril(
        jnp.full((size, size), 0.9), -1)), eye, 1e-4)


# -- the delta-rule layer -----------------------------------------------------
def _gdn_op(chunk=16):
    return create_operator(
        "GatedDeltaNet", num_key_heads=2, num_value_heads=4, key_head_dim=8,
        value_head_dim=4, conv_taps=4, chunk=chunk, eps=1e-6)


def _gdn_inputs(seed=10):
    key = jax.random.PRNGKey(seed)
    p = _layer(key, 0)
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, SEQ, 32), dtype=F32)
    return x, p, [p[n] for n in GDN_LEAVES]


def test_gated_delta_net_forward_and_backward():
    x, _p, leaves = _gdn_inputs()
    op = _gdn_op()
    w = jax.random.normal(jax.random.PRNGKey(11), x.shape, dtype=F32)

    def prog(x, *leaves):
        return op.forward([x] + list(leaves), [], True, None)[0][0]

    def plain(x, *leaves):
        return ref.gated_delta_net(x, dict(zip(GDN_LEAVES, leaves)), CFG)

    _close(prog(x, *leaves), _highest(plain)(x, *leaves), 2e-5)
    wrt = tuple(range(1 + len(leaves)))
    got = jax.grad(lambda *a: jnp.sum(prog(*a) * w), wrt)(x, *leaves)
    want = jax.grad(lambda *a: jnp.sum(_highest(plain)(*a) * w), wrt)(
        x, *leaves)
    for name, a, b in zip(("data",) + GDN_LEAVES, got, want):
        assert float(jnp.abs(b).max()) > 0, name
        _close(a, b, 2e-4)
    shapes = op.infer_shape([(2, SEQ, 32)] + [None] * len(GDN_LEAVES))[0]
    assert [tuple(s) for s in shapes[1:]] == [tuple(a.shape) for a in leaves]
    cost = op_cost(op, shapes, [(2, SEQ, 32)])
    assert cost["mxu"] and cost["flops"] > 0


@pytest.mark.parametrize("step", ref.RULE_STEPS)
def test_gated_delta_net_every_step_matters(step):
    """The delta correction, the decay, the convolution, the normalisation
    of q and k, the output gate: the op differs from a reference that
    leaves any one of them out by far more than from the whole one."""
    x, p, leaves = _gdn_inputs(seed=12)
    got = _gdn_op().forward([x] + leaves, [], True, None)[0][0]
    whole = _highest(ref.gated_delta_net)(x, p, CFG)
    less = _highest(ref.gated_delta_net)(x, p, CFG, without=(step,))
    scale = float(jnp.abs(whole).max())
    assert float(jnp.abs(got - whole).max()) < 2e-5 * scale
    assert float(jnp.abs(got - less).max()) > 1e-2 * scale, step


def test_gated_delta_net_is_causal_and_the_convolution_reads_four_tokens():
    x, _p, leaves = _gdn_inputs(seed=13)
    op = _gdn_op()
    base = op.forward([x] + leaves, [], True, None)[0][0]
    moved = op.forward([x.at[:, 40].add(1.0)] + leaves, [], True, None)[0][0]
    assert not np.asarray(jnp.abs(moved - base)[:, :40]).any()
    assert float(jnp.abs(moved - base)[:, 40:].max()) > 1e-3
    z = jax.random.normal(jax.random.PRNGKey(14), (1, 8, 3), dtype=F32)
    taps = jnp.asarray([[8.0] * 3, [4.0] * 3, [2.0] * 3, [1.0] * 3])
    out = la.causal_depthwise_conv(z, taps)
    _close(out[0, 5], z[0, 5] + 2 * z[0, 4] + 4 * z[0, 3] + 8 * z[0, 2], 1e-6)
    _close(out[0, 0], z[0, 0], 1e-6)            # zeros before the first


def test_gated_delta_net_rejects_heads_that_do_not_group():
    op = create_operator("GatedDeltaNet", num_key_heads=3, num_value_heads=4,
                         key_head_dim=8, value_head_dim=4)
    with pytest.raises(mx.base.MXNetError):
        op.infer_shape([(1, 16, 32)] + [None] * 7)


# -- gated attention ----------------------------------------------------------
def _att_op():
    return create_operator(
        "GatedAttention", num_heads=4, num_kv_heads=2, head_dim=8,
        rope_theta=10000.0, partial_rotary_factor=0.25, eps=1e-6)


def test_gated_attention_forward_and_backward():
    key = jax.random.PRNGKey(20)
    p = _layer(key, 3)
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, SEQ, 32), dtype=F32)
    leaves = [p[n] for n in ATT_LEAVES]
    op = _att_op()
    w = jax.random.normal(jax.random.PRNGKey(21), x.shape, dtype=F32)

    def prog(x, *leaves):
        return op.forward([x] + list(leaves), [], True, None)[0][0]

    def plain(x, *leaves):
        return ref.gated_attention(x, dict(zip(ATT_LEAVES, leaves)), CFG)

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
    # the gate is a head's second half of q_weight's rows: with it shut
    # (a large negative gate) nothing comes out
    shut = p["att_q_weight"].reshape(4, 16, 32).at[:, 8:].set(0.0)
    x1 = jnp.concatenate([x[..., :31], jnp.ones_like(x[..., :1])], axis=-1)
    shut = shut.at[:, 8:, 31].set(-1e4).reshape(64, 32)
    out = prog(x1 * 1.0, shut, *leaves[1:])
    assert float(jnp.abs(out).max()) < 1e-6


def test_gated_attention_rejects_heads_that_do_not_group():
    op = create_operator("GatedAttention", num_heads=6, num_kv_heads=4,
                         head_dim=8)
    with pytest.raises(mx.base.MXNetError):
        op.infer_shape([(1, 16, 32)] + [None] * 6)


# -- both flash kernels at a group of eight heads of 256 ----------------------
def _qkv256(dtype, seq=256):
    ks = jax.random.split(jax.random.PRNGKey(30), 4)
    q = jax.random.normal(ks[0], (1, 8, seq, 256), dtype)
    k, v = (jax.random.normal(kk, (1, 1, seq, 256), dtype) for kk in ks[1:3])
    return q, k, v, jax.random.normal(ks[3], (1, 8, seq, 256), dtype)


def _attention_grads(fn, q, k, v, w):
    return jax.value_and_grad(
        lambda q, k, v: jnp.sum((fn(q, k, v) * w).astype(jnp.float32)),
        argnums=(0, 1, 2))(q, k, v)


@pytest.mark.parametrize("share,programs", [(None, 1), (0.7, 0), (0, 8)])
@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 3e-2)])
def test_flash_kernels_at_eight_heads_of_256_on_one(dtype, tol, share,
                                                    programs, monkeypatch):
    """Forward and backward in interpret mode against ``attention_reference``
    — the whole group in one program, and (a smaller VMEM budget: a share of
    what the whole group asks for) the group split over 2 and over 8
    programs whose partial dk, dv are summed: the same numbers."""
    q, k, v, w = _qkv256(jnp.dtype(dtype))
    shape = (1, 256, 256, 256, 256, 256, 256, 8, jnp.dtype(dtype).itemsize)
    if share is not None:
        whole = ra._flash_backward_split(*shape, budget=1 << 40)[1]
        monkeypatch.setattr(ra, "_VMEM_BUDGET", int(share * whole))
    jax.clear_caches()
    split, vmem, _layout = ra._flash_backward_split(*shape)
    if programs:
        assert split == programs
    else:       # split as far as it must, and then it fits
        assert split in (2, 4) and vmem <= ra._VMEM_BUDGET

    def kernel(q, k, v):
        return ra.flash_attention(q, k, v, causal=True, interpret=True)

    def plain(q, k, v):
        return ra.attention_reference(*(t.astype(jnp.float32)
                                        for t in (q, k, v)), causal=True)

    got_o, got = _attention_grads(kernel, q, k, v, w)
    want_o, want = _attention_grads(plain, q, k, v, w.astype(jnp.float32))
    _close(got_o, want_o, tol)
    for a, b in zip(got, want):
        assert a.dtype == jnp.dtype(dtype)
        _close(a.astype(jnp.float32), b, tol)
    jax.clear_caches()


def test_backward_splits_a_group_only_where_it_must():
    """The shapes the accepted cells run keep the whole group in a program
    (they lower as before); 16 heads on 2 of 256 at 8,192 keys go over four
    programs a key/value head and then fit the budget.  The forward asks
    for VMEM only where k and v outgrow what Mosaic gives unasked."""
    accepted = [((8 * 16, 1024, 1024, 64, 512, 512, 64, 1), 2),
                ((32, 8192, 8192, 192, 512, 512, 128, 1), 2),
                ((2, 8192, 8192, 128, 512, 512, 128, 4), 2)]
    for args, item in accepted:
        split, vmem, _layout = ra._flash_backward_split(*args, item)
        assert split == 1 and vmem <= ra._VMEM_BUDGET, args
    split, vmem, _layout = ra._flash_backward_split(2, 8192, 8192, 256, 512,
                                                    512, 256, 8, 2)
    assert split == 4 and vmem <= ra._VMEM_BUDGET
    whole = ra._flash_backward_split(2, 8192, 8192, 256, 512, 512, 256, 8, 2,
                                     budget=1 << 40)
    assert whole[0] == 1 and whole[1] > 256 << 20    # 268 MB unsplit
    spec = ra.flash_backward_kernel_spec(16, 8192, 8192, 256, group=8)
    assert spec["grid"] == (8, 16)
    by_name = {b["name"]: b for b in spec["blocks"]}
    assert by_name["q"]["array"] == (8, 2 * 8192, 256)
    assert by_name["k"]["array"] == (2, 8192, 256)
    assert by_name["dk"]["array"] == (8, 8192, 256)
    assert by_name["dk"]["dtype"] == "float32"
    assert by_name["dq"]["dtype"] == "bfloat16"
    zaya = ra.flash_backward_kernel_spec(8, 8192, 8192, 128, group=4)
    assert zaya["grid"] == (2, 16)
    assert {b["name"]: b for b in zaya["blocks"]}["dk"]["dtype"] == "bfloat16"


def test_forward_asks_for_vmem_only_past_the_default():
    def limit(q_shape, kv_shape, d_v=None):
        q = jax.ShapeDtypeStruct(q_shape, jnp.bfloat16)
        k = jax.ShapeDtypeStruct(kv_shape, jnp.bfloat16)
        v = jax.ShapeDtypeStruct(kv_shape[:-1] + (d_v or kv_shape[-1],),
                                 jnp.bfloat16)
        jaxpr = jax.make_jaxpr(lambda q, k, v: ra._flash_forward_kernel_call(
            q, k, v, True, 0.1, 512, 512, True))(q, k, v)
        calls = []

        def walk(j):
            for eqn in j.eqns:
                if eqn.primitive.name == "pallas_call":
                    calls.append(eqn.params["compiler_params"])
                for value in eqn.params.values():
                    sub = getattr(value, "jaxpr", value)
                    if hasattr(sub, "eqns"):
                        walk(sub)
        walk(jaxpr.jaxpr)
        assert len(calls) == 1
        params = dict(calls[0] or {}).get("mosaic_tpu")
        return getattr(params, "vmem_limit_bytes", None)

    assert limit((1, 8, 8192, 128), (1, 2, 8192, 128)) is None
    assert limit((1, 32, 8192, 192), (1, 32, 8192, 192), 128) is None
    big = limit((1, 16, 8192, 256), (1, 2, 8192, 256))
    assert big is not None and 17 << 20 < big < 64 << 20


def test_mirrored_attention_block_runs_the_forward_kernel_once(monkeypatch):
    """A ``GatedAttention`` block under the executor's mirrored checkpoint:
    the gradient's program holds one ``flash_forward`` and one
    ``flash_backward`` — the kernel's output and statistics are kept, the
    norms, the rotary and the gate around it are recomputed."""
    from mxnet_tpu.executor import mirror_checkpoint
    from mxnet_tpu.kernels import common
    from test_mirror import _kernel_calls
    monkeypatch.setattr(
        common, "dispatch",
        lambda kernel, _reference, *args: kernel(*args, interpret=True))
    key = jax.random.PRNGKey(40)
    p = _layer(key, 3)
    x = jax.random.normal(jax.random.fold_in(key, 1), (1, 128, 32), dtype=F32)
    leaves = [p[n] for n in ATT_LEAVES]
    op = _att_op()

    def block(x, *leaves):
        return jnp.sum(jnp.sin(op.forward([x] + list(leaves), [], True,
                                          None)[0][0]))

    wrt = tuple(range(1 + len(leaves)))
    for fn, calls in ((block, 1), (jax.checkpoint(block), 2),
                      (mirror_checkpoint(block), 1)):
        found = _kernel_calls(jax.make_jaxpr(jax.grad(fn, wrt))(
            x, *leaves).jaxpr)
        assert found == {"flash_forward": calls, "flash_backward": 1}, found
    got = jax.grad(mirror_checkpoint(block), wrt)(x, *leaves)
    want = jax.grad(block, wrt)(x, *leaves)
    for a, b in zip(got, want):
        _close(a, b, 1e-5)


# -- routing: softmax over all the experts, ten a token, a gated shared one ---
def _routed_op(first=6, held=2, width=32, shared=12, top_k=10):
    return create_operator(
        "RoutedExperts", num_experts=width, num_local_experts=held,
        first_expert=first, hidden_size=16, top_k=top_k,
        score_func="softmax", shared_hidden_size=shared,
        shared_gate=bool(shared))


def _zero_aux(op, tokens=96):
    shapes = op.infer_shape([(tokens, 32)] + [None] * (
        len(op.list_arguments()) - 1))[2]
    types = op.infer_type([np.dtype("float32")] * len(op.list_arguments()))[2]
    return [jnp.zeros(s, t) for s, t in zip(shapes, types)]


def _routed_inputs(seed=50, tokens=96):
    key = jax.random.PRNGKey(seed)
    p = _layer(key, 1)
    return jax.random.normal(jax.random.fold_in(key, 1), (tokens, 32),
                             dtype=F32), p


def test_softmax_top10_weights_sum_to_one():
    h, p = _routed_inputs()
    scores = moe.softmax_scores(h, p["moe_router_weight"])
    assert scores.dtype == jnp.float32 and scores.shape == (96, 32)
    _close(jnp.sum(scores, axis=-1), jnp.ones((96,)), 1e-6)
    idx, w = moe.route_topk(scores, jnp.zeros((32,)), 10)
    _close(jnp.sum(w, axis=-1), jnp.ones((96,)), 1e-6)
    assert all(len(set(row)) == 10 for row in np.asarray(idx).tolist())
    want, _margin = _highest(ref.routing)(h, p, CFG)
    got = jnp.zeros((96, 32)).at[jnp.arange(96)[:, None], idx].set(w)
    _close(got, want, 1e-5)


def test_routed_layer_forward_backward_and_counters():
    h, p = _routed_inputs(seed=51)
    op = _routed_op()
    assert op.list_arguments() == [
        "data", "router_weight", "expert_gate_weight", "expert_up_weight",
        "expert_down_weight", "shared_gate_weight", "shared_up_weight",
        "shared_down_weight", "shared_score_weight"]
    w = jax.random.normal(jax.random.PRNGKey(52), h.shape, dtype=F32)
    leaves = [p[n] for n in MOE_LEAVES]

    def prog(h, *leaves):
        outs, aux = op.forward([h] + list(leaves), _zero_aux(op), True, None)
        return outs[0], aux

    def plain(h, *leaves):
        return ref.routed_layer(h, dict(zip(MOE_LEAVES, leaves)), CFG)

    out, aux = prog(h, *leaves)
    want_out, margin = _highest(plain)(h, *leaves)
    assert float(jnp.min(margin)) > 1e-6        # no near-tie in this draw
    _close(out, want_out, 5e-5)
    wrt = tuple(range(1 + len(leaves)))
    got = jax.grad(lambda *a: jnp.sum(prog(*a)[0] * w), wrt)(h, *leaves)
    want = jax.grad(lambda *a: jnp.sum(_highest(plain)(*a)[0] * w), wrt)(
        h, *leaves)
    for name, a, b in zip(("data",) + MOE_LEAVES, got, want):
        assert float(jnp.abs(b).max()) > 0, name
        _close(a, b, 2e-4)
    weights, _ = _highest(ref.routing)(h, p, CFG)
    per_expert = [int(np.sum(np.asarray(weights[:, e]) > 0)) for e in (6, 7)]
    _bias, total, tokens, peak_sum, peak_max = (np.asarray(a) for a in aux)
    assert tokens.tolist() == per_expert and total[0] == sum(per_expert)
    assert peak_sum[0] == peak_max[0] == max(per_expert)
    assert 0 < total[0] < 96 * 2


def test_shared_gate_scales_the_shared_expert_a_token():
    h, p = _routed_inputs(seed=53)
    leaves = [p[n] for n in MOE_LEAVES]
    gated = _routed_op().forward([h] + leaves, _zero_aux(_routed_op()), True,
                                 None)[0][0]
    plain_op = create_operator(
        "RoutedExperts", num_experts=32, num_local_experts=2, first_expert=6,
        hidden_size=16, top_k=10, score_func="softmax",
        shared_hidden_size=12)
    assert "shared_score_weight" not in plain_op.list_arguments()
    ungated = plain_op.forward([h] + leaves[:-1], _zero_aux(plain_op), True,
                               None)[0][0]
    routed_only = _routed_op(shared=0)
    routed = routed_only.forward([h] + leaves[:4], _zero_aux(routed_only),
                                 True, None)[0][0]
    gate = jax.nn.sigmoid(h @ p["moe_shared_score_weight"].T)
    _close(gated - routed, gate * (ungated - routed), 1e-5)
    with pytest.raises(mx.base.MXNetError):
        create_operator("RoutedExperts", num_experts=8, hidden_size=4,
                        top_k=2, shared_gate=True).infer_shape(
                            [(4, 8)] + [None] * 4)


def test_sixteen_shares_add_up_to_the_uncut_layer():
    """Experts 2i, 2i + 1 on chip i of 16, each routing over all 32: the
    sixteen partial results — the shared expert, which every chip computes
    alike, counted once — add up to the reference's uncut layer, every
    assignment computed exactly once, and the reference given a share
    gives that share's part."""
    h, p = _routed_inputs(seed=54)
    full = jax.random.split(jax.random.PRNGKey(55), 3)
    p = dict(p, **{
        name: 0.3 * jax.random.normal(k, shape, dtype=F32)
        for name, k, shape in zip(MOE_LEAVES[1:4], full, (
            (32, 16, 32), (32, 16, 32), (32, 32, 16)))})
    stacks = MOE_LEAVES[1:4]
    whole, _ = _highest(ref.routed_layer)(h, p, CFG, first=0, held=32)
    total, counted = jnp.zeros_like(h), 0
    for chip in range(16):
        first = 2 * chip
        cut = {n: p[n][first:first + 2] for n in stacks}
        with_shared = chip == 0
        op = _routed_op(first=first, shared=12 if with_shared else 0)
        leaves = [p["moe_router_weight"]] + [cut[n] for n in stacks]
        if with_shared:
            leaves += [p[n] for n in MOE_LEAVES[4:]]
        outs, aux = op.forward([h] + leaves, _zero_aux(op), True, None)
        part, _ = _highest(ref.routed_layer)(h, dict(p, **cut), CFG,
                                             first=first, held=2,
                                             shared=with_shared)
        _close(outs[0], part, 5e-5)
        total, counted = total + outs[0], counted + int(aux[1][0])
    assert counted == 96 * 10           # ten experts a token, each once
    _close(total, whole, 5e-5)


def test_sigmoid_settings_are_unchanged_to_the_bit():
    """``RoutedExperts`` with its defaults (JoyAI-LLM-Flash's sigmoid
    scores) gives what it gave before it could score with a softmax."""
    key = jax.random.PRNGKey(56)
    ks = jax.random.split(key, 5)
    h = jax.random.normal(ks[0], (40, 32), dtype=F32)
    w_router = 0.3 * jax.random.normal(ks[1], (16, 32), dtype=F32)
    gate, up = (0.3 * jax.random.normal(k, (4, 16, 32), dtype=F32)
                for k in ks[2:4])
    down = 0.3 * jax.random.normal(ks[4], (4, 32, 16), dtype=F32)
    op = create_operator(
        "RoutedExperts", num_experts=16, num_local_experts=4, first_expert=4,
        hidden_size=16, top_k=4, routed_scaling_factor=2.5)
    got = op.forward([h, w_router, gate, up, down], _zero_aux(op, 40), True,
                     None)[0][0]
    idx, w = moe.route_sigmoid_topk(h, w_router, jnp.zeros((16,)), 4, 2.5)
    want = moe.routed_experts(h, w, idx, gate, up, down, 4,
                              moe.CHUNK_ROWS)[0]
    assert np.array_equal(np.asarray(got), np.asarray(want))


# -- the layer pattern --------------------------------------------------------
@pytest.mark.parametrize("layers,interval,full", [
    (4, 4, [3]), (8, 4, [3, 7]), (6, 3, [2, 5]), (3, 4, []), (2, 1, [0, 1])])
def test_layer_pattern_follows_full_attention_interval(layers, interval, full):
    from mxnet_tpu.models import transformer_hybrid_moe as model
    kinds = model.layer_kinds(layers, interval)
    assert [i for i, k in enumerate(kinds) if k == "full_attention"] == full
    assert kinds == ref.layer_kinds(dict(CFG, num_hidden_layers=layers,
                                         full_attention_interval=interval))
    net = model.get_symbol(
        vocab_size=64, num_layers=layers, dim=32, seq_len=16,
        full_attention_interval=interval, num_heads=4, num_kv_heads=2,
        head_dim=8, linear_num_key_heads=2, linear_num_value_heads=4,
        linear_key_head_dim=8, linear_value_head_dim=4,
        moe_intermediate_size=16, shared_expert_intermediate_size=12,
        num_experts=8, num_experts_per_tok=2, delta_chunk=16)
    args = net.list_arguments()
    for i in range(layers):
        assert ("layer%d_att_q_weight" % i in args) == (i in full)
        assert ("layer%d_gdn_A_log" % i in args) == (i not in full)
        assert "layer%d_moe_shared_score_weight" % i in args
    assert model.routed_layer_names(layers) == [
        "layer%d_moe" % i for i in range(layers)]


# -- the whole small model through ShardedTrainer.step ------------------------
def _model(mirror=True, compute_dtype=None):
    from mxnet_tpu import optimizer as opt_mod
    from mxnet_tpu.models import transformer_hybrid_moe
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.trainer import ShardedTrainer
    from perfbench.drivers.train_step_qwen3_next import symbol_args
    cfg = dict(CFG, program=dict(CFG["program"], mirror_blocks=mirror))
    net = transformer_hybrid_moe.get_symbol(**symbol_args(cfg, SEQ))
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
    key = jax.random.PRNGKey(70 + seed)
    params = ref.init_params(CFG, key)
    for i, (name, v) in enumerate(sorted(params.items())):
        if name.endswith("_gamma"):
            params[name] = v + 0.1 * jax.random.normal(
                jax.random.fold_in(key, 100 + i), v.shape, dtype=F32)
    return params


def _one_step(seed=0, mirror=True, compute_dtype=None):
    _net, trainer, batch = _model(mirror, compute_dtype)
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
    new_p, new_mom, new_aux, outs = trainer.step(params, mom, aux, feed)
    return w0, new_p, new_mom, new_aux, outs, (ids, lab)


def test_whole_model_losses_and_per_leaf_gradients():
    w0, _p, mom, aux, outs, (ids, lab) = _one_step()
    w = {n: jnp.asarray(a) for n, a in w0.items()}
    (_loss, rows), grads = _highest(jax.value_and_grad(
        ref.loss_fn, has_aux=True))(w, jnp.asarray(ids), jnp.asarray(lab),
                                    CFG)
    assert float(jnp.min(rows["margin"])) > 1e-6    # no near-tie drawn
    p = np.take_along_axis(np.asarray(outs[0]), lab.reshape(-1, 1)
                           .astype(np.int64), axis=1)[:, 0]
    _close(-np.log(p), rows["main"], 1e-4)
    assert set(grads) == set(mom) == set(ref.param_shapes(CFG))
    for n, g in grads.items():
        assert np.abs(np.asarray(g)).max() > 0, n
        _close(-np.asarray(mom[n]) / 0.5, g, 5e-4)      # m1 = -lr * g
    for i in range(4):
        c = moe.routing_counters(aux, "layer%d_moe" % i)
        assert c["local_assignments"][0] == c["expert_tokens"].sum() > 0
        assert not np.asarray(aux["layer%d_moe_router_bias" % i]).any()


def test_mirror_blocks_gives_the_unmirrored_gradients():
    _w0, _p, mirrored, aux_m, outs_m, _ = _one_step(seed=4, mirror=True)
    _w0, _p, plain, aux_p, outs_p, _ = _one_step(seed=4, mirror=False)
    # float32 and a long chain (the triangular inverse, the scan, decays
    # over many orders of magnitude at this file's wide seeds): the two
    # programs fuse differently and part at 1e-5, the delta-rule layers'
    # gradients at 1e-4 (2e-3 at another seed, with no token routed
    # otherwise)
    _close(outs_m[0], outs_p[0], 5e-5)
    for n in plain:
        _close(mirrored[n], plain[n], 1e-3)
    for n in aux_p:
        assert np.array_equal(np.asarray(aux_m[n]), np.asarray(aux_p[n])), n


def test_bfloat16_step_keeps_float32_masters_and_follows_the_reference():
    w0, new_p, mom, _aux, outs, (ids, lab) = _one_step(
        seed=3, compute_dtype="bfloat16")
    assert all(a.dtype == jnp.float32 for a in new_p.values())
    assert all(a.dtype == jnp.float32 for a in mom.values())
    w = {n: jnp.asarray(a) for n, a in w0.items()}
    _loss, rows = _highest(ref.loss_fn)(w, jnp.asarray(ids),
                                        jnp.asarray(lab), CFG)
    # at this file's wide seeds (0.3) rounding sends tokens to other experts
    # and single rows and leaves far; the mean loss follows (the cell's
    # limits are read at the published widths and seeds)
    p = np.take_along_axis(np.asarray(outs[0], np.float32),
                           lab.reshape(-1, 1).astype(np.int64), axis=1)[:, 0]
    want = float(np.mean(np.asarray(rows["main"])))
    assert abs(float(np.mean(-np.log(p))) - want) < 0.05 * want
    assert all(bool(jnp.isfinite(g).all()) for g in mom.values())


def test_seeds_of_the_delta_rule_leaves():
    """``A_log`` is the log of a uniform draw under 16; ``dt_bias`` the
    library's 1 unless the configuration asks for Mamba-2's seed, the
    inverse softplus of a step log-uniform between its bounds."""
    key = jax.random.PRNGKey(5)
    big = dict(CFG, linear_num_value_heads=64, linear_num_key_heads=32)
    p = ref.init_params(big, key)
    a = np.exp(np.asarray(p["layer0_gdn_A_log"]))
    assert 0 < a.min() and a.max() < 16 and a.mean() > 4
    step = np.log1p(np.exp(np.asarray(p["layer0_gdn_dt_bias"])))
    assert 0.01 * 0.999 <= step.min() and step.max() <= 0.5 * 1.001
    assert np.std(np.log(step)) > 0.5
    ones = ref.init_params(dict(big, dt_bias_init=1.0), key)
    assert np.array_equal(np.asarray(ones["layer1_gdn_dt_bias"]),
                          np.ones(64, np.float32))
    assert not np.asarray(p["layer3_att_q_norm_gamma"] - 1).any()
