"""Ring attention / flash kernel / transformer ops.

Ring vs full-attention equality runs on the 8-device CPU mesh from
conftest (the multi-chip stand-in, SURVEY §4)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

import mxnet_tpu as mx
from mxnet_tpu import symbol as sym
from mxnet_tpu.parallel.ring_attention import (
    _causal_k_blocks, _causal_q_blocks, _flash_blocks,
    _flash_forward_kernel_call, attention_reference, blockwise_combine, flash_attention, ring_attention)
from mxnet_tpu.test_utils import (assert_almost_equal,
                                  check_numeric_gradient, tpu_lowering_text)

rng = np.random.RandomState(11)


def _qkv(B=2, H=2, S=32, D=8):
    q = rng.randn(B, H, S, D).astype(np.float32)
    k = rng.randn(B, H, S, D).astype(np.float32)
    v = rng.randn(B, H, S, D).astype(np.float32)
    return q, k, v


def test_blockwise_combine_matches_full():
    q, k, v = _qkv()
    full = attention_reference(q, k, v)
    blocks = [(k[..., i:i + 8, :], v[..., i:i + 8, :])
              for i in range(0, 32, 8)]
    blk = blockwise_combine(q, blocks)
    assert_almost_equal(np.asarray(blk), np.asarray(full),
                        rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_kernel_interpret_matches_reference(causal):
    q, k, v = _qkv(B=1, H=2, S=16, D=8)
    want = attention_reference(q, k, v, causal=causal)
    got = flash_attention(q, k, v, causal=causal, block_q=8, block_k=8,
                          interpret=True)
    assert_almost_equal(np.asarray(got), np.asarray(want),
                        rtol=1e-4, atol=1e-5)


def test_flash_dispatch_follows_placement():
    """Kernel or reference is decided by the platform the step is
    lowered FOR, not by what the process can see: the same jitted
    function carries the Mosaic custom call in its TPU lowering and
    none in its cpu lowering, which runs and matches the reference."""
    q, k, v = _qkv(B=1, H=2, S=256, D=8)   # multiple of the 128 blocks
    step = jax.jit(lambda a, b, c: flash_attention(a, b, c, causal=True))
    assert "tpu_custom_call" in tpu_lowering_text(step, q, k, v)
    assert "tpu_custom_call" not in step.lower(q, k, v).as_text()
    want = attention_reference(q, k, v, causal=True)
    for got in (step(q, k, v), flash_attention(q, k, v, causal=True)):
        assert_almost_equal(np.asarray(got), np.asarray(want),
                            rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_full(causal):
    n_sp = 4
    B, H, S, D = 2, 2, 32, 8
    q, k, v = _qkv(B, H, S, D)
    want = attention_reference(q, k, v, causal=causal)

    devs = np.array(jax.devices()[:n_sp])
    mesh = Mesh(devs, ("sp",))

    def f(q, k, v):
        return ring_attention(q, k, v, axis_name="sp", causal=causal)

    sharded = shard_map(f, mesh=mesh,
                        in_specs=(P(None, None, "sp", None),) * 3,
                        out_specs=P(None, None, "sp", None))
    got = jax.jit(sharded)(q, k, v)
    assert_almost_equal(np.asarray(got), np.asarray(want),
                        rtol=1e-4, atol=1e-5)


def test_ring_attention_grad_flows():
    n_sp = 2
    B, H, S, D = 1, 1, 16, 4
    q, k, v = _qkv(B, H, S, D)
    devs = np.array(jax.devices()[:n_sp])
    mesh = Mesh(devs, ("sp",))

    def loss_ring(q, k, v):
        f = shard_map(
            lambda a, b, c: ring_attention(a, b, c, axis_name="sp"),
            mesh=mesh, in_specs=(P(None, None, "sp", None),) * 3,
            out_specs=P(None, None, "sp", None))
        return jnp.sum(f(q, k, v) ** 2)

    def loss_full(q, k, v):
        return jnp.sum(attention_reference(q, k, v) ** 2)

    g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g_full = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
    for gr, gf in zip(g_ring, g_full):
        assert_almost_equal(np.asarray(gr), np.asarray(gf),
                            rtol=1e-3, atol=1e-4)


# ------------------------------------------------- symbolic ops
def test_layernorm_forward_backward():
    x = rng.randn(4, 6).astype(np.float64)
    d = sym.Variable("x")
    s = sym.LayerNorm(data=d, name="ln")
    ex = s.simple_bind(mx.cpu(), x=x.shape)
    ex.arg_dict["x"][:] = x.astype(np.float32)
    ex.arg_dict["ln_gamma"][:] = np.ones(6, np.float32)
    ex.arg_dict["ln_beta"][:] = np.zeros(6, np.float32)
    out = ex.forward()[0].asnumpy()
    mu = x.mean(-1, keepdims=True)
    want = (x - mu) / np.sqrt(x.var(-1, keepdims=True) + 1e-5)
    assert_almost_equal(out, want, rtol=1e-4, atol=1e-5)

    check_numeric_gradient(sym.sum(s * s), {
        "x": x, "ln_gamma": rng.rand(6) + 0.5, "ln_beta": rng.randn(6)},
        rtol=2e-2, atol=2e-3)


def test_mha_matches_manual():
    B, S, E, H = 2, 8, 16, 2
    x = rng.randn(B, S, E).astype(np.float32)
    wqkv = rng.randn(3 * E, E).astype(np.float32) * 0.2
    bqkv = rng.randn(3 * E).astype(np.float32) * 0.1
    wo = rng.randn(E, E).astype(np.float32) * 0.2
    bo = rng.randn(E).astype(np.float32) * 0.1

    d = sym.Variable("x")
    s = sym.MultiHeadAttention(data=d, num_heads=H, causal=True, name="att")
    ex = s.simple_bind(mx.cpu(), x=x.shape)
    ex.arg_dict["x"][:] = x
    ex.arg_dict["att_qkv_weight"][:] = wqkv
    ex.arg_dict["att_qkv_bias"][:] = bqkv
    ex.arg_dict["att_out_weight"][:] = wo
    ex.arg_dict["att_out_bias"][:] = bo
    out = ex.forward()[0].asnumpy()

    qkv = x @ wqkv.T + bqkv
    q, k, v = np.split(qkv, 3, axis=-1)
    to_heads = lambda t: t.reshape(B, S, H, E // H).transpose(0, 2, 1, 3)
    o = attention_reference(to_heads(q), to_heads(k), to_heads(v),
                            causal=True)
    o = np.asarray(o).transpose(0, 2, 1, 3).reshape(B, S, E)
    want = o @ wo.T + bo
    assert_almost_equal(out, want, rtol=1e-4, atol=1e-5)


def test_transformer_trains():
    np.random.seed(0)
    V, S = 30, 12
    net = mx.models.transformer.get_symbol(vocab_size=V, num_layers=1,
                                           num_heads=2, dim=16, seq_len=S)
    # learn to predict the next token of a fixed cyclic sequence
    seq = (np.arange(64 * S) * 7 % V).reshape(64, S).astype(np.float32)
    lbl = np.roll(seq.reshape(-1), -1).reshape(64, S)
    it = mx.io.NDArrayIter(seq, lbl, batch_size=16, shuffle=True,
                           label_name="softmax_label")
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.fit(it, num_epoch=15, optimizer="adam",
            optimizer_params={"learning_rate": 0.01},
            initializer=mx.init.Xavier())
    score = dict(mod.score(mx.io.NDArrayIter(
        seq, lbl, batch_size=16, label_name="softmax_label"), "acc"))
    assert score["accuracy"] > 0.8, score


def test_transformer_sharded_trainer_sp():
    """Full fused train step over a dp×sp mesh: MHA lowers to ring
    attention; outputs match the single-device step bit-for-bit-ish."""
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.trainer import ShardedTrainer
    from mxnet_tpu import optimizer as opt_mod

    V, S, B = 20, 16, 4
    net = mx.models.transformer.get_symbol(vocab_size=V, num_layers=1,
                                           num_heads=2, dim=8, seq_len=S)
    r = np.random.RandomState(0)
    data = r.randint(0, V, (B, S)).astype(np.float32)
    label = r.randint(0, V, (B, S)).astype(np.float32)

    outs = {}
    for tag, kwargs in [("single", dict(dp=1)),
                        ("sp", dict(dp=2, sp=2))]:
        mesh = make_mesh(jax.devices()[:np.prod(
            [v for v in kwargs.values()])], **kwargs)
        mx.random.seed(42)  # identical param init across both runs
        opt = opt_mod.create("sgd", learning_rate=0.1)
        tr = ShardedTrainer(net, opt, mesh,
                            seq_axis=1 if "sp" in kwargs else None)
        params, opt_state, aux = tr.init_params(
            {"data": (B, S)}, label_shapes={"softmax_label": (B, S)},
            initializer=mx.init.Xavier(rnd_type="gaussian"))
        batch = tr.shard_batch({"data": data, "softmax_label": label})
        params, opt_state, aux, out = tr.step(params, opt_state, aux,
                                              batch)
        outs[tag] = np.asarray(out[0])
    assert_almost_equal(outs["single"], outs["sp"], rtol=1e-3, atol=1e-4)


def _flash_calls(text):
    """(Mosaic kernels lowered, calls of the jitted forward kernel, calls
    of the jitted backward kernel)."""
    return (text.count("tpu_custom_call"),
            text.count("call @_flash_forward_kernel_call"),
            text.count("call @_flash_backward_kernel_call"))


def test_mesh_steps_carry_the_flash_kernel_per_device():
    """GSPMD cannot partition a Mosaic kernel ("wrap the call in a
    shard_map" — what the four-chip host said in PR 21), so a step
    sharded over a mesh WITHOUT a sequence axis must run the flash path
    per device: the dp=4 ShardedTrainer step and the Module mesh
    group's fused step both lower for a TPU with one call of the forward
    kernel and one of the backward kernel per layer (each kernel lowered
    once: its call is jitted), and on the cpu mesh they compute what one
    device computes."""
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.trainer import ShardedTrainer
    from mxnet_tpu import optimizer as opt_mod

    V, S, B, L = 32, 128, 8, 2          # S a multiple of the 128 blocks
    net = mx.models.transformer.get_symbol(vocab_size=V, num_layers=L,
                                           num_heads=2, dim=16, seq_len=S)
    r = np.random.RandomState(0)
    data = r.randint(0, V, (B, S)).astype(np.float32)
    label = r.randint(0, V, (B, S)).astype(np.float32)

    outs = {}
    for dp in (1, 4):
        mx.random.seed(42)
        tr = ShardedTrainer(net, opt_mod.create("sgd", learning_rate=0.1),
                            make_mesh(jax.devices()[:dp], dp=dp))
        params, opt_state, aux = tr.init_params(
            {"data": (B, S)}, label_shapes={"softmax_label": (B, S)},
            initializer=mx.init.Xavier(rnd_type="gaussian"))
        batch = tr.shard_batch({"data": data, "softmax_label": label})
        _p, _o, _a, out = tr.step(params, opt_state, aux, batch)
        outs[dp] = np.asarray(out[0])
        with tr._sp_scope():
            text = tpu_lowering_text(tr._jit_step, *tr._abstract_args)
        assert _flash_calls(text) == (2, L, L), dp
    assert_almost_equal(outs[1], outs[4], rtol=1e-3, atol=1e-4)

    mod = mx.mod.Module(net, context=[mx.cpu(i) for i in range(4)])
    mod.fit(mx.io.NDArrayIter(data, label, batch_size=B), num_epoch=1,
            kvstore="device", optimizer="sgd", eval_metric="ce",
            optimizer_params={"learning_rate": 0.1},
            initializer=mx.init.Xavier())
    group = mod._exec_group
    exe = group.execs[0]
    assert group.sharded and exe._n_fused_step == 1
    with group._mesh_scope():
        wrt, step = exe._get_fused(mod._optimizer)
        text = tpu_lowering_text(
            step, *exe._fused_operands(wrt, donate=False),
            jax.random.PRNGKey(0), mod._fused_holder["states"],
            jnp.float32(0.1), jnp.float32(0.0), jnp.int32(1))
    assert _flash_calls(text) == (2, L, L)
    # the same graph bound on one device afterwards gets its own program
    one = mx.mod.Module(net, context=mx.cpu(5))
    one.bind(data_shapes=[("data", (B, S))],
             label_shapes=[("softmax_label", (B, S))])
    assert one._exec_group.execs[0]._program is not exe._program


@pytest.mark.parametrize("causal", [False, True])
def test_flash_kernel_differentiable(causal):
    """The pallas forward carries the flash backward kernel (recompute
    from saved logsumexp, a (block_k, block_q) tile at a time) — must
    match reference grads exactly."""
    q, k, v = _qkv(B=1, H=1, S=16, D=8)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal, block_q=8,
                                       block_k=8, interpret=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=causal) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        assert_almost_equal(np.asarray(a), np.asarray(b),
                            rtol=1e-3, atol=1e-4)


# ----------------------------------------------------------------------
# the forward kernel's inner loop: which key blocks a causal query block
# visits, and what dtype it multiplies in
# ----------------------------------------------------------------------
@pytest.mark.parametrize("args,want", [
    ((1024, 1024), (512, 512)),             # gpt2m_train_s1024: 3 of 4
    ((768, 384), (256, 128)),
    ((256, 4096), (256, 512)),
    ((1024, 1024, 128), (128, 512)),        # a fixed block is kept
    ((16, 16, 8, 8), (8, 8)),
    ((100, 128), None),                     # no block tiles 100:
    ((128, 128, None, 48), None),           # the reference runs instead
])
def test_flash_blocks_from_shapes(args, want):
    assert _flash_blocks(*args) == want


def test_flash_default_blocks_match_reference():
    """No block given: the kernel runs (not the fallback) on blocks
    chosen from the shapes, here 256 by 128, and matches."""
    q = rng.randn(1, 1, 256, 8).astype(np.float32)
    k, v = (rng.randn(1, 1, 384, 8).astype(np.float32) for _ in range(2))
    assert _flash_blocks(256, 384) == (256, 128)
    got = flash_attention(q, k, v, causal=True, interpret=True)
    assert_almost_equal(np.asarray(got),
                        np.asarray(attention_reference(q, k, v, causal=True)),
                        rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("sq,sk,block_q,block_k,want", [
    (1024, 1024, 128, 128, (36, 64)),       # gpt2m_train_s1024's shape
    (1024, 1024, 256, 128, (20, 32)),
    (1024, 1024, 128, 256, (20, 32)),
    (1024, 1024, 512, 512, (3, 4)),
    (512, 1024, 128, 128, (10, 32)),        # sq < sk
    (1024, 512, 128, 128, (26, 32)),        # sq > sk: the bound clamps
    (96, 48, 16, 8, (30, 36)),
    (64, 64, 8, 16, (20, 32)),
    (16, 16, 8, 1, (24, 32)),
])
def test_causal_visit_count(sq, sk, block_q, block_k, want):
    """Query block i reads key blocks [0, visited): exactly those that
    hold a key some row of it may see (none skipped, none wholly masked
    read), and masks only [unmasked, visited), the ones not wholly
    visible to its first row."""
    n_q, n_k = sq // block_q, sk // block_k
    total = 0
    for i in range(n_q):
        first, last = i * block_q, (i + 1) * block_q - 1
        unmasked, visited = _causal_k_blocks(i, block_q, block_k, n_k)
        assert isinstance(visited, int) and isinstance(unmasked, int)
        assert visited == sum(j * block_k <= last for j in range(n_k))
        assert unmasked == sum((j + 1) * block_k - 1 <= first
                               for j in range(n_k))
        assert 0 <= unmasked <= visited <= n_k and visited >= 1
        total += visited
    assert (total, n_q * n_k) == want


@pytest.mark.parametrize("block_q,block_k", [(8, 8), (16, 8), (8, 16)])
def test_flash_causal_skips_blocks_above_the_diagonal(block_q, block_k):
    """Blocks past the diagonal are not read, not read-and-masked: with
    the last key block of v all NaN, a kernel that visits it gives every
    row NaN (0 × NaN), one that stops at the diagonal only the query
    blocks that reach it."""
    q, k, v = _qkv(B=1, H=2, S=32, D=8)
    v_nan = v.copy()
    v_nan[..., 32 - block_k:, :] = np.nan
    clean = (32 - block_k) // block_q * block_q     # whole q blocks before
    got = np.asarray(flash_attention(q, k, v_nan, causal=True,
                                     block_q=block_q, block_k=block_k,
                                     interpret=True))
    want = np.asarray(attention_reference(q, k, v, causal=True))
    assert np.isfinite(got[..., :clean, :]).all()
    assert_almost_equal(got[..., :clean, :], want[..., :clean, :],
                        rtol=1e-4, atol=1e-5)
    # the same call without causal reads every block
    assert np.isnan(np.asarray(flash_attention(
        q, k, v_nan, block_q=block_q, block_k=block_k,
        interpret=True))).all()


def _bf16_qkv(S, D):
    q, k, v = (jnp.asarray(a, jnp.bfloat16) for a in _qkv(1, 2, S, D))
    return (q, k, v), tuple(a.astype(jnp.float32) for a in (q, k, v))


def _max_rel(got, want):
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(np.asarray(got, np.float32) - want))
                 / np.max(np.abs(want)))


# D=16: scale 1/4 folds into bfloat16 q exactly; D=8: it cannot, and is
# applied to the float32 scores
@pytest.mark.parametrize("D", [16, 8])
@pytest.mark.parametrize("causal", [False, True])
def test_flash_bfloat16_operands_keep_the_scores(causal, D):
    """bfloat16 q, k, v are multiplied as they are and summed in
    float32: the scores lose nothing (lse to 1e-5 of the float32
    log-sum-exp of the same values), and the output differs from the
    float32 reference by p's and its own rounding to bfloat16."""
    (q, k, v), (qf, kf, vf) = _bf16_qkv(32, D)
    scale = float(D) ** -0.5
    got, lse = _flash_forward_kernel_call(q, k, v, causal, scale, 8, 8,
                                          True)
    assert got.dtype == jnp.bfloat16 and lse.dtype == jnp.float32
    s = jnp.einsum("...qd,...kd->...qk", qf, kf) * scale
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((32, 32), bool)), s, -jnp.inf)
    assert_almost_equal(np.asarray(lse),
                        np.asarray(jax.nn.logsumexp(s, axis=-1)),
                        rtol=1e-5, atol=1e-5)
    want = attention_reference(qf, kf, vf, causal=causal, scale=scale)
    assert _max_rel(got, want) <= 1e-2


# ----------------------------------------------------------------------
# the backward kernel: same dtype rule, same diagonal, by key blocks
# ----------------------------------------------------------------------
def _grads(fn, q, k, v, w):
    return jax.grad(lambda q, k, v: jnp.sum(
        fn(q, k, v).astype(jnp.float32) * w), argnums=(0, 1, 2))(q, k, v)


@pytest.mark.parametrize("block_q,block_k", [(8, 8), (16, 8), (8, 16)])
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_flash_backward_kernel_matches_reference(dtype, causal, block_q,
                                                 block_k):
    """dq, dk, dv of the Pallas backward against ``jax.grad`` of the
    float32 reference, over several query and key blocks: float32
    operands to float32's rounding, bfloat16 operands (multiplied as
    they are, p and ds rounded for their products) to bfloat16's."""
    r = np.random.RandomState(30)
    q, k, v, w = (jnp.asarray(r.randn(1, 2, 32, 16), dt)
                  for dt in (dtype, dtype, dtype, jnp.float32))
    got = _grads(lambda q, k, v: flash_attention(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k,
        interpret=True), q, k, v, w)
    want = _grads(lambda q, k, v: attention_reference(
        q, k, v, causal=causal),
        *(a.astype(jnp.float32) for a in (q, k, v)), w)
    tol = 1e-5 if dtype == "float32" else 2e-2
    for g, x, want_g in zip(got, (q, k, v), want):
        assert g.dtype == x.dtype and g.shape == x.shape
        assert _max_rel(g, want_g) <= tol


@pytest.mark.parametrize("sq,sk,block_q,block_k,want", [
    (1024, 1024, 128, 128, (36, 64)),       # the rows of
    (1024, 1024, 256, 128, (20, 32)),       # test_causal_visit_count:
    (1024, 1024, 128, 256, (20, 32)),       # the backward visits the
    (1024, 1024, 512, 512, (3, 4)),         # block pairs the forward
    (8192, 8192, 512, 512, (136, 256)),     # does, and no other
    (512, 1024, 128, 128, (10, 32)),
    (1024, 512, 128, 128, (26, 32)),
    (96, 48, 16, 8, (30, 36)),
    (64, 64, 8, 16, (20, 32)),
])
def test_causal_backward_visit_count(sq, sk, block_q, block_k, want):
    """Key block j meets query blocks [visited, n_q): exactly those with
    a row that may see one of its keys, and masks only [visited,
    unmasked), the ones whose first row does not see its last key — the
    pairs, and the masked pairs, that ``_causal_k_blocks`` gives the
    forward."""
    n_q, n_k = sq // block_q, sk // block_k
    pairs, masked = set(), set()
    for j in range(n_k):
        visited, unmasked = _causal_q_blocks(j, block_q, block_k, n_q)
        assert isinstance(visited, int) and isinstance(unmasked, int)
        assert 0 <= visited <= unmasked <= n_q
        pairs.update((i, j) for i in range(visited, n_q))
        masked.update((i, j) for i in range(visited, unmasked))
    forward, forward_masked = set(), set()
    for i in range(n_q):
        unmasked, visited = _causal_k_blocks(i, block_q, block_k, n_k)
        forward.update((i, j) for j in range(visited))
        forward_masked.update((i, j) for j in range(unmasked, visited))
    assert pairs == forward and masked == forward_masked
    assert (len(pairs), n_q * n_k) == want


@pytest.mark.parametrize("block_q,block_k", [(8, 8), (16, 8), (8, 16)])
def test_flash_backward_causal_skips_blocks_above_the_diagonal(block_q,
                                                               block_k):
    """Pairs past the diagonal are not read, not read-and-masked: with
    the first query block of ``do`` all NaN, a key block that lies
    wholly past that query block never meets it and keeps finite dk and
    dv (one that meets it under the mask gets 0 × NaN), and the other
    query blocks keep their dq."""
    q, k, v = _qkv(B=1, H=2, S=32, D=8)
    do = rng.randn(1, 2, 32, 8).astype(np.float32)
    do_nan = do.copy()
    do_nan[..., :block_q, :] = np.nan
    do_zero = do.copy()
    do_zero[..., :block_q, :] = 0.0
    clean = -(-block_q // block_k) * block_k    # first key past the block

    def vjp(fn, do):
        return [np.asarray(g) for g in jax.vjp(fn, q, k, v)[1](do)]

    dq, dk, dv = vjp(lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=block_q, block_k=block_k,
        interpret=True), do_nan)
    want = vjp(lambda q, k, v: attention_reference(q, k, v, causal=True),
               do_zero)
    for got, ref, first in ((dq, want[0], block_q), (dk, want[1], clean),
                            (dv, want[2], clean)):
        assert np.isfinite(got[..., first:, :]).all()
        assert_almost_equal(got[..., first:, :], ref[..., first:, :],
                            rtol=1e-4, atol=1e-5)
    assert np.isnan(dq[..., :block_q, :]).all()
    # the same call without causal: every key block meets the NaN block
    _dq, dk, dv = vjp(lambda q, k, v: flash_attention(
        q, k, v, block_q=block_q, block_k=block_k, interpret=True), do_nan)
    assert np.isnan(dk).all() and np.isnan(dv).all()


@pytest.mark.parametrize("causal", [False, True])
def test_flash_bfloat16_gradient_matches_reference(causal):
    """The backward kernel reads the forward's lse: with bfloat16
    operands it must still give the float32 reference's gradient, to
    bfloat16's rounding."""
    (q, k, v), wide = _bf16_qkv(32, 16)

    def loss(fn, q, k, v):
        return jnp.sum(jnp.sin(fn(q, k, v).astype(jnp.float32)))

    got = jax.grad(lambda *a: loss(lambda q, k, v: flash_attention(
        q, k, v, causal=causal, block_q=8, block_k=8, interpret=True), *a),
        argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: loss(lambda q, k, v: attention_reference(
        q, k, v, causal=causal), *a), argnums=(0, 1, 2))(*wide)
    for g, w in zip(got, want):
        assert g.dtype == jnp.bfloat16
        assert _max_rel(g, w) <= 1e-2
