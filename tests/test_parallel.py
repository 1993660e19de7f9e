"""Mesh / sharded-trainer tests on the 8-device CPU mesh.

The reference fakes multi-device with multiple cpu(i) contexts
(tests/python/unittest/test_multi_device_exec.py); conftest.py's
xla_force_host_platform_device_count=8 is our analog (SURVEY §4).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import mxnet_tpu as mx
from mxnet_tpu import parallel


def _mlp():
    data = mx.sym.Variable("data")
    fc1 = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    act = mx.sym.Activation(fc1, act_type="relu")
    fc2 = mx.sym.FullyConnected(act, num_hidden=4, name="fc2")
    return mx.sym.SoftmaxOutput(fc2, name="softmax")


def test_make_mesh_shapes():
    assert len(jax.devices()) == 8, "conftest must force 8 cpu devices"
    mesh = parallel.make_mesh(dp=4, tp=2)
    assert mesh.shape == {"dp": 4, "tp": 2}
    mesh = parallel.make_mesh(dp=-1, tp=2)
    assert mesh.shape["dp"] == 4
    with pytest.raises(ValueError):
        parallel.make_mesh(dp=3, tp=2)
    mesh = parallel.auto_mesh()
    assert mesh.shape == {"dp": 8}


def test_param_pspec_rules():
    mesh = parallel.make_mesh(dp=4, tp=2)
    assert parallel.param_pspec("fc1_weight", (16, 8), mesh) == P("tp", None)
    assert parallel.param_pspec("fc1_bias", (16,), mesh) == P("tp")
    # non-divisible: replicate
    assert parallel.param_pspec("w", (5, 3), mesh) == P(None, None)
    assert parallel.batch_pspec((32, 8), mesh) == P("dp", None)


def test_dp_trainer_step_runs_and_learns():
    mesh = parallel.auto_mesh()  # dp=8
    net = _mlp()
    opt = mx.optimizer.create("sgd", learning_rate=0.5,
                              rescale_grad=1.0 / 64)
    tr = parallel.ShardedTrainer(net, opt, mesh)
    assert set(tr.param_names) == {"fc1_weight", "fc1_bias",
                                   "fc2_weight", "fc2_bias"}
    mx.random.seed(0)
    params, opt_state, aux, = tr.init_params({"data": (64, 8)},
                                             label_shapes={"softmax_label": (64,)})
    rng = np.random.RandomState(0)
    x = rng.randn(64, 8).astype(np.float32)
    y = (x.sum(axis=1) > 0).astype(np.float32) * 3  # labels in {0,3}
    batch = tr.shard_batch({"data": x, "softmax_label": y})

    first_acc = None
    for i in range(30):
        params, opt_state, aux, outs = tr.step(params, opt_state, aux, batch)
        pred = np.asarray(outs[0]).argmax(axis=1)
        acc = (pred == y).mean()
        if first_acc is None:
            first_acc = acc
    assert acc > 0.9, "did not learn: acc=%s (first=%s)" % (acc, first_acc)


def test_dp_matches_single_device():
    """DP-sharded step == unsharded step (the reference's
    test_model_parallel.py equivalence pattern)."""
    net = _mlp()

    def run(mesh):
        opt = mx.optimizer.create("sgd", learning_rate=0.1)
        tr = parallel.ShardedTrainer(net, opt, mesh)
        mx.random.seed(7)
        params, opt_state, aux = tr.init_params(
            {"data": (16, 8)}, label_shapes={"softmax_label": (16,)})
        rng = np.random.RandomState(1)
        x = rng.randn(16, 8).astype(np.float32)
        y = (rng.rand(16) * 4).astype(np.float32)
        batch = tr.shard_batch({"data": x, "softmax_label": y})
        for _ in range(3):
            params, opt_state, aux, outs = tr.step(params, opt_state, aux, batch)
        return {k: np.asarray(v) for k, v in params.items()}

    p_multi = run(parallel.auto_mesh())          # dp=8
    p_single = run(parallel.make_mesh(jax.devices()[:1], dp=1))
    for k in p_multi:
        np.testing.assert_allclose(p_multi[k], p_single[k], rtol=2e-4,
                                   atol=2e-5)


def test_tp_trainer_matches_replicated():
    """Tensor-parallel sharded params produce the same math."""
    net = _mlp()

    def run(mesh):
        opt = mx.optimizer.create("sgd", learning_rate=0.1)
        tr = parallel.ShardedTrainer(net, opt, mesh)
        mx.random.seed(3)
        params, opt_state, aux = tr.init_params(
            {"data": (8, 8)}, label_shapes={"softmax_label": (8,)})
        rng = np.random.RandomState(2)
        x = rng.randn(8, 8).astype(np.float32)
        y = (rng.rand(8) * 4).astype(np.float32)
        batch = tr.shard_batch({"data": x, "softmax_label": y})
        for _ in range(2):
            params, opt_state, aux, outs = tr.step(params, opt_state, aux, batch)
        return {k: np.asarray(v) for k, v in params.items()}, np.asarray(outs[0])

    p_tp, out_tp = run(parallel.make_mesh(dp=2, tp=4))
    p_rep, out_rep = run(parallel.make_mesh(jax.devices()[:1], dp=1))
    np.testing.assert_allclose(out_tp, out_rep, rtol=2e-4, atol=2e-5)
    for k in p_tp:
        np.testing.assert_allclose(p_tp[k], p_rep[k], rtol=2e-4, atol=2e-5)


def test_batchnorm_aux_updates_in_sharded_step():
    data = mx.sym.Variable("data")
    bn = mx.sym.BatchNorm(data, name="bn")
    net = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(bn, num_hidden=2),
                               name="softmax")
    mesh = parallel.auto_mesh()
    opt = mx.optimizer.create("sgd", learning_rate=0.01)
    tr = parallel.ShardedTrainer(net, opt, mesh)
    params, opt_state, aux = tr.init_params(
        {"data": (16, 4)}, label_shapes={"softmax_label": (16,)})
    assert "bn_moving_mean" in aux and "bn_moving_var" in aux
    x = np.random.RandomState(0).randn(16, 4).astype(np.float32) * 3 + 1
    batch = tr.shard_batch({"data": x,
                            "softmax_label": np.zeros(16, np.float32)})
    before = np.asarray(aux["bn_moving_mean"]).copy()
    params, opt_state, aux, _ = tr.step(params, opt_state, aux, batch)
    after = np.asarray(aux["bn_moving_mean"])
    assert not np.allclose(before, after)


def test_sharded_trainer_bf16_compute():
    """bf16 compute / f32 master params: step runs, params & aux stay f32,
    outputs track the f32 run loosely."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.trainer import ShardedTrainer
    from mxnet_tpu import optimizer as opt_mod

    net = mx.models.get_mlp(num_classes=4, hidden=(16,))
    r = np.random.RandomState(0)
    X = r.rand(8, 10).astype(np.float32)
    y = r.randint(0, 4, (8,)).astype(np.float32)

    outs = {}
    for tag, cdt in [("f32", None), ("bf16", "bfloat16")]:
        mesh = make_mesh(jax.devices()[:2], dp=2)
        mx.random.seed(7)
        opt = opt_mod.create("sgd", learning_rate=0.1)
        tr = ShardedTrainer(net, opt, mesh, compute_dtype=cdt)
        params, opt_state, aux = tr.init_params(
            {"data": (8, 10)}, label_shapes={"softmax_label": (8,)})
        batch = tr.shard_batch({"data": X, "softmax_label": y})
        params, opt_state, aux, out = tr.step(params, opt_state, aux, batch)
        assert all(v.dtype == jnp.float32 for v in params.values())
        outs[tag] = np.asarray(out[0], np.float32)
    # bf16 mantissa is 8 bits: outputs agree to ~1e-2
    np.testing.assert_allclose(outs["f32"], outs["bf16"],
                               rtol=5e-2, atol=5e-2)


def test_sharded_trainer_remat():
    import jax
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.trainer import ShardedTrainer
    from mxnet_tpu import optimizer as opt_mod

    net = mx.models.get_mlp(num_classes=4, hidden=(16,))
    mesh = make_mesh(jax.devices()[:2], dp=2)
    opt = opt_mod.create("sgd", learning_rate=0.1)
    tr = ShardedTrainer(net, opt, mesh, remat=True)
    params, opt_state, aux = tr.init_params(
        {"data": (8, 10)}, label_shapes={"softmax_label": (8,)})
    r = np.random.RandomState(0)
    batch = tr.shard_batch({
        "data": r.rand(8, 10).astype(np.float32),
        "softmax_label": r.randint(0, 4, (8,)).astype(np.float32)})
    params, opt_state, aux, out = tr.step(params, opt_state, aux, batch)
    assert np.isfinite(np.asarray(out[0])).all()


def test_bf16_labels_stay_exact():
    """review finding: class ids > 256 must not round through bf16."""
    import jax
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.trainer import ShardedTrainer
    from mxnet_tpu import optimizer as opt_mod

    n_cls = 1000
    net = mx.models.get_mlp(num_classes=n_cls, hidden=(8,))
    mesh = make_mesh(jax.devices()[:1], dp=1)
    opt = opt_mod.create("sgd", learning_rate=1.0)
    tr = ShardedTrainer(net, opt, mesh, compute_dtype="bfloat16")
    params, opt_state, aux = tr.init_params(
        {"data": (2, 10)}, label_shapes={"softmax_label": (2,)})
    X = np.zeros((2, 10), np.float32)
    y = np.array([999.0, 257.0], np.float32)  # not bf16-representable
    batch = tr.shard_batch({"data": X, "softmax_label": y})
    p2, _, _, _ = tr.step(params, opt_state, aux, batch)
    # the SoftmaxOutput gradient is p - onehot(label): after one big step
    # from zero-init, the bias column of the TRUE class must move up
    bias = np.asarray(p2["fc2_bias"], np.float32)
    assert bias[999] > bias[998] and bias[257] > bias[256], (
        bias[[256, 257, 998, 999]])


def test_bf16_embedding_ids_stay_exact():
    """advisor finding: vocab ids > 256 are not bf16-representable; inputs
    feeding an Embedding's id slot must be exempt from the compute cast."""
    import jax
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.trainer import ShardedTrainer
    from mxnet_tpu import optimizer as opt_mod
    from mxnet_tpu.models import transformer

    V, S = 1000, 4
    net = transformer.get_symbol(vocab_size=V, num_layers=1, num_heads=2,
                                 dim=16, seq_len=S)
    mesh = make_mesh(jax.devices()[:1], dp=1)
    tr = ShardedTrainer(net, opt_mod.create("sgd", learning_rate=1.0),
                        mesh, compute_dtype="bfloat16")
    assert "data" in tr._cast_exempt  # detected from the Embedding node
    params, opt_state, aux = tr.init_params(
        {"data": (2, S)}, label_shapes={"softmax_label": (2, S)})
    ids = np.full((2, S), 999.0, np.float32)  # 999 rounds to 1000 in bf16
    batch = tr.shard_batch({"data": ids, "softmax_label": ids})
    p0 = {k: np.asarray(v) for k, v in params.items()}
    p2, _, _, _ = tr.step(params, opt_state, aux, batch)
    # only embedding row 999 (not 1000's neighborhood via rounding) moves
    emb_delta = np.abs(np.asarray(p2["tok_embed_weight"], np.float32)
                       - p0["tok_embed_weight"]).sum(axis=1)
    assert emb_delta[999] > 0
    assert emb_delta[996] == 0 and emb_delta[992] == 0


# ----------------------------------------------------------------------
# one cast rule (train_step.compute_cast) behind every front end
# ----------------------------------------------------------------------
_CAST_V, _CAST_D = 1200, 8


def _embedding_net():
    """Ids and labels of 999 are 1000 in bfloat16: a front end that casts
    either looks up, or credits, row 1000."""
    data = mx.sym.Variable("data")
    e = mx.sym.Embedding(data, input_dim=_CAST_V, output_dim=_CAST_D,
                         name="embed")
    out = mx.sym.FullyConnected(e, num_hidden=_CAST_V, name="head")
    return mx.sym.SoftmaxOutput(out, name="softmax")


def _cast_batch():
    ids = np.full((4,), 999.0, np.float32)
    return {"data": ids, "softmax_label": ids.copy()}


def _one_step_module(monkeypatch):
    monkeypatch.setenv("MXNET_COMPUTE_DTYPE", "bfloat16")
    batch = _cast_batch()
    it = mx.io.NDArrayIter(batch["data"], batch["softmax_label"],
                           batch_size=4, label_name="softmax_label")
    mod = mx.mod.Module(_embedding_net(), context=mx.cpu())
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params(mx.init.Uniform(0.1))
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 1.0})
    before = {k: v.asnumpy() for k, v in mod.get_params()[0].items()}
    mod.forward_backward(next(iter(it)))
    mod.update()
    assert mod._exec_group.execs[0]._n_fused_step == 1
    after = {k: v.asnumpy() for k, v in mod.get_params()[0].items()}
    return (after["embed_weight"] - before["embed_weight"],
            after["head_bias"] - before["head_bias"])


def _one_step_trainer(monkeypatch):
    import jax
    tr = parallel.ShardedTrainer(
        _embedding_net(), mx.optimizer.create("sgd", learning_rate=1.0),
        parallel.make_mesh(jax.devices()[:1], dp=1),
        compute_dtype="bfloat16")
    assert tr._cast_exempt == {"data", "softmax_label"}
    params, opt_state, aux = tr.init_params(
        {"data": (4,)}, label_shapes={"softmax_label": (4,)})
    before = {k: np.asarray(v) for k, v in params.items()}
    params, _, _, _ = tr.step(params, opt_state, aux,
                              tr.shard_batch(_cast_batch()))
    return (np.asarray(params["embed_weight"]) - before["embed_weight"],
            np.asarray(params["head_bias"]) - before["head_bias"])


def _one_step_gpipe(monkeypatch):
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.parallel.pipeline import GPipeTrainer

    def embed(ep, batch):
        return jnp.take(ep["table"], batch["data"].astype(jnp.int32), axis=0)

    def block(lp, h):
        return h + jnp.tanh(h @ lp["w"])

    def head_loss(hp, h, batch):
        logp = jax.nn.log_softmax(h @ hp["w"] + hp["b"])
        labels = batch["softmax_label"].astype(jnp.int32)
        return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))

    rs = np.random.RandomState(0)
    params = {
        "embed": {"table": rs.randn(_CAST_V, _CAST_D).astype(np.float32)},
        "layers": {"w": rs.randn(2, _CAST_D, _CAST_D).astype(np.float32)
                   * 0.1},
        "head": {"w": rs.randn(_CAST_D, _CAST_V).astype(np.float32) * 0.1,
                 "b": np.zeros((_CAST_V,), np.float32)}}
    tr = GPipeTrainer(embed, block, head_loss, params,
                      parallel.make_mesh(jax.devices()[:2], pp=2),
                      mx.optimizer.create("sgd", learning_rate=1.0),
                      num_microbatches=2)
    tr.step(_cast_batch())
    return (np.asarray(tr.params["embed"]["table"])
            - params["embed"]["table"],
            np.asarray(tr.params["head"]["b"]) - params["head"]["b"])


@pytest.mark.parametrize("one_step", [_one_step_module, _one_step_trainer,
                                      _one_step_gpipe],
                         ids=["module", "trainer", "gpipe"])
def test_front_ends_exempt_ids_and_labels_from_the_cast(one_step,
                                                        monkeypatch):
    """``Module``'s fused step, ``ShardedTrainer.step`` and
    ``GPipeTrainer.step`` leave the same inputs in their stored dtype
    under bfloat16 compute: what feeds an Embedding's id slot, and the
    label."""
    embed_delta, bias_delta = one_step(monkeypatch)
    rows = np.abs(embed_delta).sum(axis=1)
    assert rows[999] > 0 and rows[1000] == 0        # looked up row 999
    assert np.count_nonzero(rows) == 1
    assert bias_delta[999] > 0 > bias_delta[1000]   # credited class 999


def test_sharded_predictor_bf16_keeps_embedding_ids_exact():
    """``ShardedPredictor(compute_dtype="bfloat16")`` looks up the rows
    the float32 predictor does: ids above 256 are not cast with the
    data (they were: 999 read row 1000)."""
    import jax
    rs = np.random.RandomState(1)
    arg_params = {
        "embed_weight": rs.randn(_CAST_V, _CAST_D).astype(np.float32),
        "head_weight": rs.randn(_CAST_V, _CAST_D).astype(np.float32) * 0.5,
        "head_bias": np.zeros((_CAST_V,), np.float32)}
    ids = np.array([999.0, 257.0, 1001.0, 3.0], np.float32)
    mesh = parallel.make_mesh(jax.devices()[:2], dp=2)
    f32 = parallel.ShardedPredictor(_embedding_net(), mesh, arg_params)
    bf16 = parallel.ShardedPredictor(_embedding_net(), mesh, arg_params,
                                     compute_dtype="bfloat16")
    want = np.log(f32.predict({"data": ids})[0])
    got = np.log(bf16.predict({"data": ids})[0])
    # bfloat16 products move a log-probability by hundredths; another
    # row's (ids as bfloat16 would read: 1000, 256, 1000, 3) by ones
    rounded = np.log(f32.predict(
        {"data": np.array([1000.0, 256.0, 1000.0, 3.0], np.float32)})[0])
    assert np.abs(rounded - want)[:3].max() > 1.0
    np.testing.assert_allclose(got, want, atol=0.1)


def test_zero1_optimizer_state_sharding():
    """ZeRO-1 (beyond-reference): momentum state lives dp-sharded (1/dp
    per rank), parameters stay replicated, and training matches the
    replicated-state baseline exactly."""
    net = _mlp()

    def run(zero1):
        mesh = parallel.make_mesh(dp=8)
        opt = mx.optimizer.create("sgd", learning_rate=0.1, momentum=0.9)
        tr = parallel.ShardedTrainer(net, opt, mesh, zero1=zero1)
        mx.random.seed(11)
        params, opt_state, aux = tr.init_params(
            {"data": (16, 8)}, label_shapes={"softmax_label": (16,)})
        rng = np.random.RandomState(3)
        x = rng.randn(16, 8).astype(np.float32)
        y = (rng.rand(16) * 4).astype(np.float32)
        batch = tr.shard_batch({"data": x, "softmax_label": y})
        for _ in range(4):
            params, opt_state, aux, _outs = tr.step(params, opt_state,
                                                    aux, batch)
        return tr, params, opt_state

    tr, params, opt_state = run(zero1=True)
    # state for (16, 8) fc1_weight is dp-sharded: each device holds 1/8
    mom = jax.tree_util.tree_leaves(opt_state["fc1_weight"])[0]
    assert mom.sharding.spec[0] == "dp", mom.sharding
    assert mom.addressable_shards[0].data.shape[0] == mom.shape[0] // 8
    # params stayed replicated
    assert params["fc1_weight"].sharding.is_fully_replicated

    _, params_base, _ = run(zero1=False)
    for k in params:
        np.testing.assert_allclose(np.asarray(params[k]),
                                   np.asarray(params_base[k]),
                                   rtol=2e-5, atol=2e-6)

    # the compiled step really does gather: collective ops in the HLO
    lowered = tr._lower()
    hlo = lowered.compile().as_text()
    assert "all-gather" in hlo or "all-reduce" in hlo


def test_fsdp_param_sharding():
    """FSDP/ZeRO-3 (beyond-reference): params live dp-sharded (1/dp per
    rank), GSPMD gathers/scatters around compute, and training matches
    the replicated baseline."""
    net = _mlp()

    def run(fsdp):
        mesh = parallel.make_mesh(dp=8)
        opt = mx.optimizer.create("sgd", learning_rate=0.1, momentum=0.9)
        tr = parallel.ShardedTrainer(net, opt, mesh, fsdp=fsdp)
        mx.random.seed(13)
        params, opt_state, aux = tr.init_params(
            {"data": (16, 8)}, label_shapes={"softmax_label": (16,)})
        rng = np.random.RandomState(5)
        x = rng.randn(16, 8).astype(np.float32)
        y = (rng.rand(16) * 4).astype(np.float32)
        batch = tr.shard_batch({"data": x, "softmax_label": y})
        for _ in range(4):
            params, opt_state, aux, _ = tr.step(params, opt_state, aux,
                                                batch)
        return params, opt_state

    params, opt_state = run(fsdp=True)
    # fc1_weight (16, 8): axis 0 dp-sharded, 2 rows per device
    w = params["fc1_weight"]
    assert w.sharding.spec[0] == "dp", w.sharding
    assert w.addressable_shards[0].data.shape == (2, 8)
    # its momentum follows the same partition
    mom = jax.tree_util.tree_leaves(opt_state["fc1_weight"])[0]
    assert mom.sharding.spec[0] == "dp"

    params_base, _ = run(fsdp=False)
    for k in params:
        np.testing.assert_allclose(np.asarray(params[k]),
                                   np.asarray(params_base[k]),
                                   rtol=2e-5, atol=2e-6)


def _np_moe(x, wg, w1, b1, w2, b2):
    t = x.reshape(-1, x.shape[-1])
    logits = t @ wg.T
    e = np.exp(logits - logits.max(-1, keepdims=True))
    probs = e / e.sum(-1, keepdims=True)
    top1 = probs.argmax(-1)
    out = np.zeros_like(t)
    for i, k in enumerate(top1):
        h = np.maximum(t[i] @ w1[k].T + b1[k], 0)
        out[i] = (h @ w2[k].T + b2[k]) * probs[i, k]
    return out.reshape(x.shape)


def test_moe_forward_matches_numpy():
    from mxnet_tpu.test_utils import check_symbolic_forward
    rng = np.random.RandomState(0)
    T, E, K, H = 12, 8, 4, 16
    x = rng.randn(T, E).astype(np.float32)
    wg = rng.randn(K, E).astype(np.float32)
    w1 = (rng.randn(K, H, E) * 0.3).astype(np.float32)
    b1 = (rng.randn(K, H) * 0.1).astype(np.float32)
    w2 = (rng.randn(K, E, H) * 0.3).astype(np.float32)
    b2 = (rng.randn(K, E) * 0.1).astype(np.float32)
    s = mx.sym.MoE(mx.sym.Variable("x"), num_experts=K, hidden_size=H,
                   name="moe")
    want = _np_moe(x, wg, w1, b1, w2, b2)
    check_symbolic_forward(s, [x, wg, w1, b1, w2, b2], [want], rtol=1e-4,
                           atol=1e-5)


def test_moe_ep_sharded_matches_replicated():
    """Expert parallelism: expert stacks sharded over 'ep', training step
    equals the replicated run; the combine collective is in the HLO."""
    E, K, H = 8, 4, 16

    def net():
        data = mx.sym.Variable("data")
        y, aux_l = mx.sym.MoE(data, num_experts=K, hidden_size=H,
                              name="moe")
        out = mx.sym.FullyConnected(y, num_hidden=4, name="cls")
        return mx.sym.SoftmaxOutput(out, name="softmax")

    def run(mesh):
        opt = mx.optimizer.create("sgd", learning_rate=0.1)
        tr = parallel.ShardedTrainer(net(), opt, mesh)
        mx.random.seed(17)
        params, opt_state, aux = tr.init_params(
            {"data": (16, E)}, label_shapes={"softmax_label": (16,)})
        rng = np.random.RandomState(7)
        batch = tr.shard_batch({
            "data": rng.randn(16, E).astype(np.float32),
            "softmax_label": (rng.rand(16) * 4).astype(np.float32)})
        for _ in range(3):
            params, opt_state, aux, _ = tr.step(params, opt_state, aux,
                                                batch)
        return tr, params

    mesh_ep = parallel.make_mesh(dp=2, ep=4)
    tr, p_ep = run(mesh_ep)
    w1 = p_ep["moe_expert_fc1_weight"]
    assert w1.sharding.spec[0] == "ep", w1.sharding
    assert w1.addressable_shards[0].data.shape[0] == 1  # 4 experts / 4

    _, p_rep = run(parallel.make_mesh(dp=8))
    for k in p_ep:
        np.testing.assert_allclose(np.asarray(p_ep[k]),
                                   np.asarray(p_rep[k]),
                                   rtol=2e-4, atol=2e-5)


def test_sharded_checkpoint_roundtrip(tmp_path):
    """Orbax sharded save/restore: every array comes back equal AND
    placed with the trainer's shardings (params zero1-sharded state,
    aux replicated) — the pod-scale checkpoint path where no host ever
    gathers the full model."""
    import jax

    def net():
        d = mx.sym.Variable("data")
        h = mx.sym.FullyConnected(d, num_hidden=8, name="fc1")
        h = mx.sym.BatchNorm(h, name="bn")
        h = mx.sym.Activation(h, act_type="relu")
        out = mx.sym.FullyConnected(h, num_hidden=4, name="fc2")
        return mx.sym.SoftmaxOutput(out, name="softmax")

    mesh = parallel.make_mesh(dp=8)
    opt = mx.optimizer.create("sgd", learning_rate=0.1, momentum=0.9)
    tr = parallel.ShardedTrainer(net(), opt, mesh, zero1=True)
    shapes = {"data": (16, 6)}
    lshapes = {"softmax_label": (16,)}
    params, opt_state, aux = tr.init_params(shapes, label_shapes=lshapes)
    rng = np.random.RandomState(0)
    batch = tr.shard_batch({
        "data": rng.rand(16, 6).astype(np.float32),
        "softmax_label": (rng.rand(16) * 4).astype(np.float32)})
    for _ in range(2):   # momentum state becomes nontrivial
        params, opt_state, aux, _ = tr.step(params, opt_state, aux, batch)

    ckpt = tmp_path / "ckpt"
    tr.save_checkpoint(ckpt, params, opt_state, aux)

    # a FRESH trainer restores placed states and continues stepping
    tr2 = parallel.ShardedTrainer(net(), opt, mesh, zero1=True)
    p2, s2, a2 = tr2.load_checkpoint(ckpt, shapes, label_shapes=lshapes)
    for name in params:
        assert np.allclose(np.asarray(params[name]), np.asarray(p2[name]))
        assert p2[name].sharding == tr2.param_sharding(name,
                                                       p2[name].shape)
    for name in opt_state:
        got = jax.tree_util.tree_leaves(s2[name])
        want = jax.tree_util.tree_leaves(opt_state[name])
        for g, w in zip(got, want):
            assert np.allclose(np.asarray(g), np.asarray(w))
    for name in aux:
        assert np.allclose(np.asarray(aux[name]), np.asarray(a2[name]))

    # the restored state steps identically to the original
    pa, sa, aa, outs_a = tr.step(params, opt_state, aux, batch)
    pb, sb, ab, outs_b = tr2.step(p2, s2, a2, batch)
    for name in pa:
        assert np.allclose(np.asarray(pa[name]), np.asarray(pb[name]),
                           atol=1e-6)


def test_sharded_checkpoint_resumes_update_counter():
    """Resume restores num_update: Adam's bias correction continues at
    the saved step (a fresh trainer would otherwise re-apply the step-1
    correction to mature state)."""
    import tempfile

    def net():
        d = mx.sym.Variable("data")
        out = mx.sym.FullyConnected(d, num_hidden=4, name="fc")
        return mx.sym.SoftmaxOutput(out, name="softmax")

    import jax
    mesh = parallel.make_mesh(jax.devices()[:2], dp=2)
    shapes = {"data": (8, 6)}
    lshapes = {"softmax_label": (8,)}
    rng = np.random.RandomState(0)
    batch_host = {"data": rng.rand(8, 6).astype(np.float32),
                  "softmax_label": (rng.rand(8) * 4).astype(np.float32)}

    def make():
        opt = mx.optimizer.create("adam", learning_rate=0.05)
        tr = parallel.ShardedTrainer(net(), opt, mesh)
        return tr

    tr = make()
    mx.random.seed(3)
    params, state, aux = tr.init_params(shapes, label_shapes=lshapes)
    batch = tr.shard_batch(batch_host)
    for _ in range(5):
        params, state, aux, _ = tr.step(params, state, aux, batch)
    with tempfile.TemporaryDirectory() as d:
        tr.save_checkpoint(d + "/ck", params, state, aux)

        tr2 = make()
        p2, s2, a2 = tr2.load_checkpoint(d + "/ck", shapes,
                                         label_shapes=lshapes)
        assert tr2.num_update == tr.num_update == 5

        # step 6 from the restored trainer == step 6 from the original
        pa, _, _, _ = tr.step(params, state, aux, batch)
        pb, _, _, _ = tr2.step(p2, s2, a2, batch)
        for name in pa:
            assert np.allclose(np.asarray(pa[name]), np.asarray(pb[name]),
                               atol=1e-6), name


def test_sharded_predictor_matches_single_device(tmp_path):
    """ShardedPredictor (serving side): tp-sharded inference from a
    classic checkpoint matches the single-device Predictor bitwise-close,
    loss-head label slot bound as zeros."""
    import jax
    from mxnet_tpu.predictor import Predictor

    def net():
        d = mx.sym.Variable("data")
        h = mx.sym.FullyConnected(d, num_hidden=16, name="fc1")
        h = mx.sym.Activation(h, act_type="relu")
        out = mx.sym.FullyConnected(h, num_hidden=4, name="fc2")
        return mx.sym.SoftmaxOutput(out, name="softmax")

    sym = net()
    mod = mx.mod.Module(sym, context=mx.context.cpu())
    mod.bind(data_shapes=[("data", (8, 6))],
             label_shapes=[("softmax_label", (8,))])
    mod.init_params(mx.init.Xavier())
    prefix = str(tmp_path / "m")
    mod.save_checkpoint(prefix, 0)

    rng = np.random.RandomState(2)
    x = rng.rand(8, 6).astype(np.float32)

    ref = Predictor(prefix + "-symbol.json", prefix + "-0000.params",
                    {"data": (8, 6)})
    want = ref.forward(data=x)[0]

    mesh = parallel.make_mesh(dp=4, tp=2)
    sp = parallel.ShardedPredictor.from_checkpoint(prefix, 0, mesh)
    got = sp.predict({"data": x})[0]
    assert got.shape == want.shape
    assert np.allclose(got, want, atol=1e-5)

    # params actually landed tp-sharded where the rules say so
    spec = sp.params["fc1_weight"].sharding.spec
    assert any(ax == "tp" for ax in spec if ax is not None)
