"""Module API + FeedForward + model zoo tests.

Mirrors the reference's tests/python/unittest/test_module.py and
tests/python/train/test_mlp.py (small end-to-end runs asserting an accuracy
threshold, SURVEY §4).
"""
import jax
import numpy as np
import pytest

import mxnet_tpu as mx


def _toy_problem(n=200, d=10, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, d).astype("float32")
    w = rng.randn(d)
    y = (X @ w > 0).astype("float32")
    return X, y


def test_module_bind_forward():
    net = mx.models.get_mlp(num_classes=2, hidden=(8,))
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(data_shapes=[("data", (4, 10))],
             label_shapes=[("softmax_label", (4,))])
    mod.init_params(initializer=mx.init.Uniform(0.1))
    batch = mx.io.DataBatch(data=[mx.nd.ones((4, 10))],
                            label=[mx.nd.zeros((4,))])
    mod.forward(batch, is_train=False)
    out = mod.get_outputs()[0]
    assert out.shape == (4, 2)
    np.testing.assert_allclose(out.asnumpy().sum(axis=1),
                               np.ones(4), rtol=1e-5)


def test_module_fit_accuracy():
    X, y = _toy_problem()
    train = mx.io.NDArrayIter(X, y, batch_size=20, shuffle=True)
    val = mx.io.NDArrayIter(X, y, batch_size=20)
    net = mx.models.get_mlp(num_classes=2, hidden=(16,))
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.fit(train, eval_data=val, optimizer="sgd",
            optimizer_params={"learning_rate": 0.5}, num_epoch=5)
    score = dict(mod.score(val, "acc"))
    assert score["accuracy"] > 0.9, score


def test_module_get_set_params_roundtrip():
    net = mx.models.get_mlp(num_classes=2, hidden=(8,))
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(data_shapes=[("data", (4, 10))],
             label_shapes=[("softmax_label", (4,))])
    mod.init_params(initializer=mx.init.Uniform(0.1))
    arg_params, aux_params = mod.get_params()
    assert "fc1_weight" in arg_params

    mod2 = mx.mod.Module(net, context=mx.cpu())
    mod2.bind(data_shapes=[("data", (4, 10))],
              label_shapes=[("softmax_label", (4,))])
    mod2.set_params(arg_params, aux_params)
    a1, _ = mod2.get_params()
    np.testing.assert_allclose(a1["fc1_weight"].asnumpy(),
                               arg_params["fc1_weight"].asnumpy())


def test_module_save_load_checkpoint(tmp_path):
    net = mx.models.get_mlp(num_classes=2, hidden=(8,))
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(data_shapes=[("data", (4, 10))],
             label_shapes=[("softmax_label", (4,))])
    mod.init_params(initializer=mx.init.Uniform(0.1))
    prefix = str(tmp_path / "mod_test")
    mod.save_checkpoint(prefix, 3)

    mod2 = mx.mod.Module.load(prefix, 3, context=mx.cpu())
    mod2.bind(data_shapes=[("data", (4, 10))],
              label_shapes=[("softmax_label", (4,))])
    a0, _ = mod.get_params()
    a1, _ = mod2.get_params()
    for k in a0:
        np.testing.assert_allclose(a0[k].asnumpy(), a1[k].asnumpy())


def test_module_input_grads():
    # Pin the global init stream: with only 8 ReLU units, an unlucky
    # ambient RNG state (depends on how much stream earlier tests
    # consumed) can leave every hidden pre-activation negative for the
    # all-ones input, making the input gradient exactly zero (~0.4%).
    mx.random.seed(42)
    net = mx.models.get_mlp(num_classes=2, hidden=(8,))
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(data_shapes=[("data", (4, 10))],
             label_shapes=[("softmax_label", (4,))],
             for_training=True, inputs_need_grad=True)
    mod.init_params(initializer=mx.init.Uniform(0.1))
    batch = mx.io.DataBatch(data=[mx.nd.ones((4, 10))],
                            label=[mx.nd.zeros((4,))])
    mod.forward(batch, is_train=True)
    mod.backward()
    igrads = mod.get_input_grads()
    assert igrads[0].shape == (4, 10)
    assert np.abs(igrads[0].asnumpy()).sum() > 0


def test_module_multi_context_slicing():
    """Batch slicing across two CPU contexts (reference fakes multi-device
    with cpu dev_ids, test_multi_device_exec.py)."""
    np.random.seed(0)  # NDArrayIter shuffles via the global numpy RNG
    X, y = _toy_problem()
    train = mx.io.NDArrayIter(X, y, batch_size=20, shuffle=True)
    net = mx.models.get_mlp(num_classes=2, hidden=(16,))
    mod = mx.mod.Module(net, context=[mx.cpu(0), mx.cpu(1)])
    mod.fit(train, optimizer="sgd",
            optimizer_params={"learning_rate": 0.5}, num_epoch=3)
    val = mx.io.NDArrayIter(X, y, batch_size=20)
    score = dict(mod.score(val, "acc"))
    assert score["accuracy"] > 0.85, score


def test_feedforward_fit_score_predict(tmp_path):
    X, y = _toy_problem()
    train = mx.io.NDArrayIter(X, y, batch_size=20, shuffle=True)
    val = mx.io.NDArrayIter(X, y, batch_size=20)
    model = mx.FeedForward(mx.models.get_mlp(2, (16,)), ctx=mx.cpu(),
                           num_epoch=5, optimizer="sgd", learning_rate=0.5)
    model.fit(train, eval_data=val)
    assert model.score(val) > 0.9
    pred = model.predict(val)
    assert pred.shape == (200, 2)

    prefix = str(tmp_path / "ff_test")
    model.save(prefix)
    m2 = mx.FeedForward.load(prefix, 5, ctx=mx.cpu())
    assert m2.score(val) > 0.9


def test_feedforward_epoch_size_exact_multiple():
    """epoch_size == batches-per-pass: each epoch drains the iterator
    exactly, so epoch 2+ begins with it exhausted and the driver must
    reset-and-retry instead of raising (reference do_reset semantics)."""
    X, y = _toy_problem()
    train = mx.io.NDArrayIter(X, y, batch_size=20)  # 10 batches/pass
    model = mx.FeedForward(mx.models.get_mlp(2, (16,)), ctx=mx.cpu(),
                           num_epoch=3, epoch_size=10, optimizer="sgd",
                           learning_rate=0.5)
    model.fit(train)
    assert model.score(mx.io.NDArrayIter(X, y, batch_size=20)) > 0.7


def test_feedforward_numpy_input():
    X, y = _toy_problem()
    model = mx.FeedForward(mx.models.get_mlp(2, (16,)), ctx=mx.cpu(),
                           num_epoch=4, optimizer="sgd", learning_rate=0.5,
                           numpy_batch_size=20)
    model.fit(X, y)
    pred = model.predict(X)
    acc = ((pred.argmax(axis=1) == y).mean())
    assert acc > 0.85


def test_bucketing_module():
    """Per-bucket executors sharing params (bucketing_module.py:189)."""
    batch_size = 8

    def sym_gen(seq_len):
        # embedding + pooled sum keeps param shapes independent of seq_len
        data = mx.sym.Variable("data")
        label = mx.sym.Variable("softmax_label")
        embed = mx.sym.Embedding(data, name="embed", input_dim=20,
                                 output_dim=6)
        pooled = mx.sym.sum_axis(embed, axis=1)
        fc = mx.sym.FullyConnected(pooled, name="fc", num_hidden=4)
        net = mx.sym.SoftmaxOutput(fc, label=label, name="softmax")
        return net, ("data",), ("softmax_label",)

    mod = mx.mod.BucketingModule(sym_gen, default_bucket_key=12,
                                 context=mx.cpu())
    mod.bind(data_shapes=[("data", (batch_size, 12))],
             label_shapes=[("softmax_label", (batch_size,))])
    mod.init_params(initializer=mx.init.Uniform(0.1))
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1})

    # feed genuinely different buckets: the 8-bucket binds a new executor
    # sharing params with the default 12-bucket (switch_bucket shared path)
    for seq_len in (12, 8, 12, 8):
        data = mx.nd.ones((batch_size, seq_len))
        label = mx.nd.zeros((batch_size,))
        batch = mx.io.DataBatch(data=[data], label=[label],
                                provide_data=[("data", (batch_size, seq_len))],
                                provide_label=[("softmax_label", (batch_size,))],
                                bucket_key=seq_len)
        mod.forward(batch, is_train=True)
        mod.backward()
        mod.update()
    out = mod.get_outputs()[0]
    assert out.shape == (batch_size, 4)
    # updates through bucket 8 must be visible in shared params
    arg_params, _ = mod.get_params()
    assert "embed_weight" in arg_params and "fc_weight" in arg_params


def test_sequential_module():
    net1 = mx.sym.FullyConnected(mx.sym.Variable("data"), name="fc1",
                                 num_hidden=8)
    net1 = mx.sym.Activation(net1, act_type="relu")
    net2 = mx.sym.FullyConnected(mx.sym.Variable("data"), name="fc2",
                                 num_hidden=2)
    net2 = mx.sym.SoftmaxOutput(net2, name="softmax")

    mod1 = mx.mod.Module(net1, label_names=None, context=mx.cpu())
    mod2 = mx.mod.Module(net2, context=mx.cpu())
    seq = mx.mod.SequentialModule()
    seq.add(mod1).add(mod2, take_labels=True, auto_wiring=True)

    X, y = _toy_problem()
    train = mx.io.NDArrayIter(X, y, batch_size=20, shuffle=True)
    seq.bind(data_shapes=train.provide_data,
             label_shapes=train.provide_label)
    seq.init_params(initializer=mx.init.Uniform(0.1))
    seq.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.5})
    metric = mx.metric.create("acc")
    for epoch in range(3):
        train.reset()
        metric.reset()
        for batch in train:
            seq.forward_backward(batch)
            seq.update()
            seq.update_metric(metric, batch.label)
    assert metric.get()[1] > 0.8


@pytest.mark.parametrize("name,builder,shape", [
    ("lenet", lambda: mx.models.get_lenet(10), (2, 1, 28, 28)),
    ("resnet18", lambda: mx.models.get_resnet(10, 18, (3, 32, 32)),
     (2, 3, 32, 32)),
])
def test_model_zoo_forward(name, builder, shape):
    net = builder()
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(data_shapes=[("data", shape)],
             label_shapes=[("softmax_label", (shape[0],))])
    mod.init_params(initializer=mx.init.Xavier())
    batch = mx.io.DataBatch(data=[mx.nd.ones(shape)],
                            label=[mx.nd.zeros((shape[0],))])
    mod.forward(batch, is_train=False)
    out = mod.get_outputs()[0]
    assert out.shape == (shape[0], 10)
    assert np.all(np.isfinite(out.asnumpy()))


def test_model_zoo_shapes():
    """All zoo symbols infer shapes (parity: test_symbol/infer_shape)."""
    cases = [
        (mx.models.get_alexnet(100), (2, 3, 224, 224), 100),
        (mx.models.get_vgg(10, 11), (2, 3, 224, 224), 10),
        (mx.models.get_googlenet(10), (2, 3, 224, 224), 10),
        (mx.models.get_inception_bn(10), (2, 3, 224, 224), 10),
        (mx.models.get_inception_v3(10), (2, 3, 299, 299), 10),
        (mx.models.get_resnet(10, 50), (2, 3, 224, 224), 10),
    ]
    for net, dshape, ncls in cases:
        _, out_shapes, _ = net.infer_shape(data=dshape)
        assert out_shapes[0] == (dshape[0], ncls)


def test_module_fixed_params_initialized_and_frozen():
    """fixed_param_names: initialized + checkpointed, but not updated."""
    net = mx.models.get_mlp(num_classes=2, hidden=(8,))
    mod = mx.mod.Module(net, context=mx.cpu(),
                        fixed_param_names=["fc1_weight"])
    mod.bind(data_shapes=[("data", (4, 10))],
             label_shapes=[("softmax_label", (4,))])
    mod.init_params(initializer=mx.init.Uniform(0.1))
    arg_params, _ = mod.get_params()
    w0 = arg_params["fc1_weight"].asnumpy()
    fc2_0 = arg_params["fc2_weight"].asnumpy()
    assert np.abs(w0).sum() > 0, "fixed param was not initialized"

    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.5})
    batch = mx.io.DataBatch(data=[mx.nd.ones((4, 10))],
                            label=[mx.nd.zeros((4,))])
    for _ in range(3):
        mod.forward_backward(batch)
        mod.update()
    arg_params, _ = mod.get_params()
    np.testing.assert_allclose(arg_params["fc1_weight"].asnumpy(), w0,
                               err_msg="fixed param was updated")
    # non-fixed params must have moved
    assert not np.allclose(arg_params["fc2_weight"].asnumpy(), fc2_0)
    assert not np.allclose(arg_params["fc2_bias"].asnumpy(), 0)


def test_module_reshape_keeps_grad_req():
    net = mx.models.get_mlp(num_classes=2, hidden=(8,))
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(data_shapes=[("data", (4, 10))],
             label_shapes=[("softmax_label", (4,))], grad_req="add")
    mod.init_params(initializer=mx.init.Uniform(0.1))
    mod.reshape(data_shapes=[("data", (8, 10))],
                label_shapes=[("softmax_label", (8,))])
    batch = mx.io.DataBatch(data=[mx.nd.ones((8, 10))],
                            label=[mx.nd.zeros((8,))])
    # with grad_req='add', two backward passes double the gradient
    mod.forward(batch, is_train=True)
    mod.backward()
    g1 = mod._exec_group.execs[0].grad_dict["fc1_weight"].asnumpy().copy()
    mod.forward(batch, is_train=True)
    mod.backward()
    g2 = mod._exec_group.execs[0].grad_dict["fc1_weight"].asnumpy()
    np.testing.assert_allclose(g2, 2 * g1, rtol=1e-4)


def test_print_summary_param_count(capsys):
    """Labels don't count as params; shared weights count once."""
    net = mx.models.get_mlp(num_classes=2, hidden=(8,))
    mx.viz.print_summary(net, shape={"data": (4, 10)})
    out = capsys.readouterr().out
    # mlp 10->8->2: fc1 10*8+8, fc2 8*2+2 = 88 + 18 = 106
    assert "Total params: 106" in out


def test_monitor():
    net = mx.models.get_mlp(num_classes=2, hidden=(8,))
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(data_shapes=[("data", (4, 10))],
             label_shapes=[("softmax_label", (4,))])
    mon = mx.Monitor(interval=1, pattern=".*weight")
    mod.install_monitor(mon)
    mod.init_params(initializer=mx.init.Uniform(0.1))
    batch = mx.io.DataBatch(data=[mx.nd.ones((4, 10))],
                            label=[mx.nd.zeros((4,))])
    mon.tic()
    mod.forward(batch, is_train=False)
    res = mon.toc()
    assert len(res) > 0
    names = [k for _, k, _ in res]
    assert any("weight" in n for n in names)


def test_fit_step_is_one_fused_dispatch():
    """VERDICT r1: the fit hot loop must be ONE trace execution per step —
    fwd+bwd+update fused (no forward-then-recompute-in-backward pair)."""
    X, y = _toy_problem()
    n_batches = len(X) // 20
    train = mx.io.NDArrayIter(X, y, batch_size=20)
    net = mx.models.get_mlp(num_classes=2, hidden=(16,))
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.fit(train, optimizer="sgd",
            optimizer_params={"learning_rate": 0.5},
            initializer=mx.init.Uniform(0.1), num_epoch=2)
    exec_ = mod._exec_group.execs[0]
    assert exec_._n_fused_step == 2 * n_batches, (
        exec_._n_fused_step, n_batches)
    assert exec_._n_forward == 0, exec_._n_forward
    assert exec_._n_fwd_bwd == 0, exec_._n_fwd_bwd
    # and the fused path must actually learn
    score = dict(mod.score(mx.io.NDArrayIter(X, y, batch_size=20), "acc"))
    assert score["accuracy"] > 0.9, score


def test_fused_and_host_update_paths_agree():
    """Fused in-step optimizer update ≡ the host updater path (same math,
    one dispatch instead of 1 + P)."""
    X, y = _toy_problem(n=100)
    net = mx.models.get_mlp(num_classes=2, hidden=(8,))
    params = {}
    for tag, env in (("fused", "1"), ("host", "0")):
        import os
        os.environ["MXNET_MODULE_FUSED"] = env
        try:
            mx.random.seed(42)
            train = mx.io.NDArrayIter(X, y, batch_size=20)
            mod = mx.mod.Module(net, context=mx.cpu())
            mod.fit(train, optimizer="sgd",
                    optimizer_params={"learning_rate": 0.1,
                                      "momentum": 0.9, "wd": 1e-3},
                    initializer=mx.init.Uniform(0.1), num_epoch=3)
            params[tag] = {k: v.asnumpy()
                           for k, v in mod.get_params()[0].items()}
        finally:
            del os.environ["MXNET_MODULE_FUSED"]
    for k in params["fused"]:
        np.testing.assert_allclose(params["fused"][k], params["host"][k],
                                   rtol=1e-4, atol=1e-5)


def test_sharded_multi_device_fused_fit():
    """VERDICT r2 #3: Module.fit over a device list runs ONE fused dispatch
    per step on a mesh (data sharded, params replicated) — the in-step
    collapse of kvstore device gradient reduction (comm.h:186-345)."""
    X, y = _toy_problem()
    n_batches = len(X) // 40
    train = mx.io.NDArrayIter(X, y, batch_size=40)
    net = mx.models.get_mlp(num_classes=2, hidden=(16,))
    ctxs = [mx.cpu(i) for i in range(8)]
    mod = mx.mod.Module(net, context=ctxs)
    mod.fit(train, kvstore="device", optimizer="sgd",
            optimizer_params={"learning_rate": 0.5, "momentum": 0.9},
            initializer=mx.init.Uniform(0.1), num_epoch=4)
    group = mod._exec_group
    assert group.sharded and len(group.execs) == 1
    exec_ = group.execs[0]
    assert exec_._n_fused_step == 4 * n_batches, (
        exec_._n_fused_step, n_batches)
    assert exec_._n_fwd_bwd == 0
    score = dict(mod.score(mx.io.NDArrayIter(X, y, batch_size=40), "acc"))
    assert score["accuracy"] > 0.9, score


def test_sharded_fused_step_hlo_has_all_reduce():
    """The compiled sharded step must carry the gradient all-reduce over
    the dp mesh axis (assert on lowered text, VERDICT r2 #3 done-bar)."""
    net = mx.models.get_mlp(num_classes=2, hidden=(8,))
    ctxs = [mx.cpu(i) for i in range(8)]
    mod = mx.mod.Module(net, context=ctxs)
    mod.bind(data_shapes=[("data", (32, 10))],
             label_shapes=[("softmax_label", (32,))])
    mod.init_params()
    mod.init_optimizer(kvstore="device")
    assert mod._kv_inline and mod._fused_step_ok()
    hlo = mod._exec_group.fused_step_hlo(mod._optimizer)
    assert "all-reduce" in hlo


def test_sharded_matches_single_device():
    """Same data, same init: 8-device sharded training must produce the
    same parameters as single-device (the all-reduced grad equals the
    full-batch grad)."""
    X, y = _toy_problem(n=128)
    net = mx.models.get_mlp(num_classes=2, hidden=(8,))
    results = {}
    for tag, ctx in (("one", mx.cpu()),
                     ("mesh", [mx.cpu(i) for i in range(8)])):
        mx.random.seed(11)
        train = mx.io.NDArrayIter(X, y, batch_size=32)
        mod = mx.mod.Module(net, context=ctx)
        mod.fit(train, kvstore="device" if tag == "mesh" else None,
                optimizer="sgd",
                optimizer_params={"learning_rate": 0.2, "momentum": 0.9},
                initializer=mx.init.Uniform(0.1), num_epoch=2)
        results[tag] = {k: v.asnumpy() for k, v in mod.get_params()[0].items()}
    for k in results["one"]:
        np.testing.assert_allclose(results["mesh"][k], results["one"][k],
                                   rtol=2e-4, atol=2e-5)


def test_zoo_builders_deterministic_names():
    """Auto-named zoo builders must produce identical parameter names on
    every build (NameManager scope per get_symbol) — checkpoint load in a
    fresh process depends on it."""
    from mxnet_tpu.models import alexnet, googlenet, inception_bn
    for mod in (alexnet, googlenet, inception_bn):
        first = mod.get_symbol(num_classes=10).list_arguments()
        # bump the ambient manager's counters with an UNNAMED op
        mx.sym.FullyConnected(mx.sym.Variable("noise"), num_hidden=1)
        second = mod.get_symbol(num_classes=10).list_arguments()
        assert first == second, mod.__name__


def test_fused_step_bf16_compute():
    """MXNET_COMPUTE_DTYPE=bfloat16: fwd/bwd run reduced-precision (the
    compiled step carries bf16 math) while master weights stay f32, and
    training still converges."""
    import os
    X, y = _toy_problem(n=120)
    net = mx.models.get_mlp(num_classes=2, hidden=(8,))
    os.environ["MXNET_COMPUTE_DTYPE"] = "bfloat16"
    try:
        mx.random.seed(7)
        train = mx.io.NDArrayIter(X, y, batch_size=30)
        mod = mx.mod.Module(net, context=mx.cpu())
        mod.fit(train, optimizer="sgd",
                optimizer_params={"learning_rate": 0.5},
                initializer=mx.init.Uniform(0.1), num_epoch=10)
        score = dict(mod.score(mx.io.NDArrayIter(X, y, batch_size=30),
                               "acc"))
        assert score["accuracy"] > 0.9, score
        exec_ = mod._exec_group.execs[0]
        assert exec_._n_fused_step > 0
        states = exec_.init_fused_states(mod._optimizer)
        hlo = exec_.lower_fused_step(mod._optimizer, states)
        assert "bf16" in hlo                      # compute in bf16
        args, _ = mod.get_params()
        assert all(v.asnumpy().dtype == np.float32
                   for v in args.values())        # f32 master weights
    finally:
        del os.environ["MXNET_COMPUTE_DTYPE"]


def test_bucketing_on_sharded_mesh():
    """BucketingModule over a device list: each bucket shares the sharded
    mesh group (shared_group copies mesh state, VERDICT r2 review)."""
    batch_size = 16

    def sym_gen(seq_len):
        data = mx.sym.Variable("data")
        label = mx.sym.Variable("softmax_label")
        embed = mx.sym.Embedding(data, name="embed", input_dim=20,
                                 output_dim=6)
        pooled = mx.sym.sum_axis(embed, axis=1)
        fc = mx.sym.FullyConnected(pooled, name="fc", num_hidden=4)
        return (mx.sym.SoftmaxOutput(fc, label=label, name="softmax"),
                ("data",), ("softmax_label",))

    ctxs = [mx.cpu(i) for i in range(8)]
    mod = mx.mod.BucketingModule(sym_gen, default_bucket_key=12,
                                 context=ctxs)
    mod.bind(data_shapes=[("data", (batch_size, 12))],
             label_shapes=[("softmax_label", (batch_size,))])
    mod.init_params(initializer=mx.init.Uniform(0.1))
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1})
    for seq_len in (12, 8, 12, 8):
        batch = mx.io.DataBatch(
            data=[mx.nd.ones((batch_size, seq_len))],
            label=[mx.nd.zeros((batch_size,))],
            provide_data=[("data", (batch_size, seq_len))],
            provide_label=[("softmax_label", (batch_size,))],
            bucket_key=seq_len)
        mod.forward(batch, is_train=True)
        mod.backward()
        mod.update()
    assert mod._curr_module._exec_group.sharded
    assert mod.get_outputs()[0].shape == (batch_size, 4)


def test_checkpoint_cross_api_roundtrip(tmp_path):
    """FeedForward.save -> Module.load and back: one checkpoint format
    across both training APIs (reference model.py:308 contract)."""
    X, y = _toy_problem(n=80)
    model = mx.FeedForward(mx.models.get_mlp(2, (8,)), ctx=mx.cpu(),
                           num_epoch=2, optimizer="sgd", learning_rate=0.3)
    model.fit(X, y)
    prefix = str(tmp_path / "xapi")
    model.save(prefix, 2)

    mod = mx.mod.Module.load(prefix, 2, context=mx.cpu())
    mod.bind(data_shapes=[("data", (16, 10))],
             label_shapes=[("softmax_label", (16,))])
    val = mx.io.NDArrayIter(X, y, batch_size=16)
    acc_mod = dict(mod.score(val, "acc"))["accuracy"]
    acc_ff = model.score(mx.io.NDArrayIter(X, y, batch_size=16))
    assert abs(acc_mod - acc_ff) < 1e-9

    mod.save_checkpoint(prefix + "2", 0)
    back = mx.FeedForward.load(prefix + "2", 0, ctx=mx.cpu())
    assert abs(back.score(mx.io.NDArrayIter(X, y, batch_size=16))
               - acc_ff) < 1e-9


def test_optimizer_states_roundtrip_fused(tmp_path):
    """Momentum state saved mid-training resumes identically: two more
    epochs after a save/load must equal two more epochs without it."""
    X, y = _toy_problem(n=80)

    def run(resume):
        mx.random.seed(3)
        train = mx.io.NDArrayIter(X, y, batch_size=20)
        mod = mx.mod.Module(mx.models.get_mlp(2, (8,)), context=mx.cpu())
        mod.fit(train, optimizer="sgd",
                optimizer_params={"learning_rate": 0.2, "momentum": 0.9},
                initializer=mx.init.Uniform(0.1), num_epoch=2)
        if resume:
            prefix = str(tmp_path / "opt")
            mod.save_checkpoint(prefix, 2, save_optimizer_states=True)
            mod = mx.mod.Module.load(prefix, 2, load_optimizer_states=True,
                                     context=mx.cpu())
            mod.bind(data_shapes=train.provide_data,
                     label_shapes=train.provide_label)
            mod.init_optimizer(optimizer="sgd",
                               optimizer_params={"learning_rate": 0.2,
                                                 "momentum": 0.9})
        train.reset()
        for _ in range(2):
            for b in train:
                mod.forward_backward(b)
                mod.update()
            train.reset()
        return {k: v.asnumpy() for k, v in mod.get_params()[0].items()}

    direct, resumed = run(False), run(True)
    for k in direct:
        np.testing.assert_allclose(resumed[k], direct[k],
                                   rtol=1e-5, atol=1e-6, err_msg=k)


# ----------------------------------------------------------------------
# fit copies batch N+1 while step N runs (prepare / stage_data_batch)
# ----------------------------------------------------------------------
_AHEAD_CONTEXTS = [pytest.param(lambda: mx.cpu(), id="one"),
                   pytest.param(lambda: [mx.cpu(0), mx.cpu(1)], id="mesh2")]
_AHEAD_OPT = {"learning_rate": 0.2, "momentum": 0.9, "wd": 1e-3}


def _ahead_start(ctx, X, y, batch_size=16):
    """A bound module with seeded parameters, its iterator and the
    arguments ``fit`` and a hand-written loop both start from."""
    train = mx.io.NDArrayIter(X, y, batch_size=batch_size)
    mod = mx.mod.Module(mx.models.get_mlp(2, (8,)), context=ctx)
    mod.bind(train.provide_data, train.provide_label)
    mx.random.seed(5)
    mod.init_params(initializer=mx.init.Uniform(0.1))
    arg_params, aux_params = mod.get_params()
    return train, mod, dict(
        arg_params={k: v.copy() for k, v in arg_params.items()},
        aux_params={k: v.copy() for k, v in aux_params.items()},
        kvstore="device" if isinstance(ctx, list) else "local",
        optimizer="sgd", optimizer_params=_AHEAD_OPT)


def _seen(mod):
    """What a callback can read of the step just done, as host copies."""
    group = mod._exec_group
    return {
        "out": mod.get_outputs()[0].asnumpy().copy(),
        "params": {k: v[0].asnumpy().copy() for k, v in zip(
            group.param_names, group.param_arrays)},
        "state": {k: np.asarray(v).copy()
                  for k, v in mod._fused_holder["states"].items()},
        "data": group.data_arrays[0][0][1].asnumpy().copy(),
        "label": group.label_arrays[0][0][1].asnumpy().copy()}


def _hand_loop(ctx, X, y, num_epoch=1):
    """The loop as it stood before the look-ahead: copy at the dispatch,
    metric after it, nothing ahead.  Returns the module, the metric and
    what stood after every step."""
    train, mod, start = _ahead_start(ctx, X, y)
    mod.set_params(start["arg_params"], start["aux_params"])
    mod.init_optimizer(kvstore=start["kvstore"], optimizer="sgd",
                       optimizer_params=_AHEAD_OPT)
    metric = mx.metric.create("ce")
    steps = []
    for _ in range(num_epoch):
        metric.reset()
        for batch in train:
            mod.forward_backward(batch)
            mod.update()
            mod.update_metric(metric, batch.label)
            steps.append(dict(_seen(mod), metric=metric.get()[1]))
        train.reset()
    return mod, metric, steps


def _assert_same(a, b):
    assert set(a) == set(b)
    for k in a:
        if isinstance(a[k], dict):
            _assert_same(a[k], b[k])
        else:
            np.testing.assert_array_equal(a[k], b[k], err_msg=k)


@pytest.mark.parametrize("make_ctx", _AHEAD_CONTEXTS)
def test_fit_look_ahead_is_bit_identical_to_a_hand_loop(make_ctx):
    """Parameters, momentum and metric after k steps of ``fit`` are those
    of forward_backward / update / update_metric written out by hand."""
    X, y = _toy_problem(n=80)
    hand, hand_metric, _ = _hand_loop(make_ctx(), X, y, num_epoch=2)
    train, mod, start = _ahead_start(make_ctx(), X, y)
    metric = mx.metric.create("ce")
    mod.fit(train, eval_metric=metric, num_epoch=2, **start)
    assert mod._exec_group.sharded == isinstance(make_ctx(), list)
    assert mod._exec_group.execs[0]._n_fused_step == 10
    _assert_same(_seen(mod), _seen(hand))
    for got, want in zip(mod.get_params(), hand.get_params()):
        _assert_same({k: v.asnumpy() for k, v in got.items()},
                     {k: v.asnumpy() for k, v in want.items()})
    assert metric.get() == hand_metric.get()


@pytest.mark.parametrize("make_ctx", _AHEAD_CONTEXTS)
def test_fit_callback_at_step_n_sees_step_n(make_ctx):
    """At ``batch_end`` the outputs, parameters, optimizer state and the
    BOUND data and labels are the step's own, though the next batch's
    copy has been issued: step N+1 is not dispatched before it."""
    X, y = _toy_problem(n=80)
    _, _, want = _hand_loop(make_ctx(), X, y)
    train, mod, start = _ahead_start(make_ctx(), X, y)
    got = []

    def batch_end(p):
        group = mod._exec_group
        assert group.execs[0]._n_fused_step == p.nbatch + 1
        # every batch but the epoch's last has the next one staged
        assert (group._staged is None) == (p.nbatch == 4)
        got.append(dict(_seen(mod), metric=p.eval_metric.get()[1]))

    mod.fit(train, eval_metric="ce", num_epoch=1,
            batch_end_callback=batch_end, **start)
    assert len(got) == len(want) == 5
    for k, (a, b) in enumerate(zip(got, want)):
        _assert_same(dict(a, metric=np.float64(a["metric"])),
                     dict(b, metric=np.float64(b["metric"])))
        np.testing.assert_array_equal(a["data"], X[16 * k:16 * (k + 1)])
        np.testing.assert_array_equal(a["label"], y[16 * k:16 * (k + 1)])


class _OneBatchIter(mx.io.DataIter):
    """Hands out ONE DataBatch object again and again, with new contents."""

    def __init__(self, X, y, batch_size):
        super().__init__()
        self.X, self.y, self.batch_size = X, y, batch_size
        self.provide_data = [("data", (batch_size,) + X.shape[1:])]
        self.provide_label = [("softmax_label", (batch_size,))]
        self.batch = mx.io.DataBatch(data=None, label=None, pad=0, index=None)
        self.cursor = 0
        self.handed = 0

    def reset(self):
        self.cursor = 0

    def next(self):
        lo, hi = self.cursor, self.cursor + self.batch_size
        if hi > len(self.X):
            raise StopIteration
        self.cursor = hi
        self.handed += 1
        self.batch.data = [mx.nd.array(self.X[lo:hi])]
        self.batch.label = [mx.nd.array(self.y[lo:hi])]
        return self.batch


def test_fit_metric_gets_its_own_labels_from_a_reused_batch():
    X, y = _toy_problem(n=80)
    _, want_metric, want = _hand_loop(mx.cpu(), X, y)
    _, mod, start = _ahead_start(mx.cpu(), X, y)
    metric = mx.metric.create("ce")
    got = []
    mod.fit(_OneBatchIter(X, y, 16), eval_metric=metric, num_epoch=1,
            batch_end_callback=lambda p: got.append(_seen(mod)), **start)
    assert metric.get() == want_metric.get()
    for a, b in zip(got, want):
        _assert_same(a, {n: b[n] for n in a})
    # the one object was staged and found again at every later dispatch
    assert (mod._exec_group.n_staged, mod._exec_group.n_loaded) == (4, 1)


def test_fit_two_epochs_neither_lose_nor_repeat_a_batch():
    X, y = _toy_problem(n=80)
    train, mod, start = _ahead_start(mx.cpu(), X, y)
    feed = _OneBatchIter(X, y, 16)
    bound, counts = [], []

    def batch_end(p):
        group = mod._exec_group
        bound.append(group.data_arrays[0][0][1].asnumpy().copy())
        counts.append((p.epoch, p.nbatch, group.n_staged, group.n_loaded))

    mod.fit(feed, num_epoch=2, batch_end_callback=batch_end, **start)
    assert feed.handed == 10                    # 5 an epoch, none left over
    for k, data in enumerate(bound):
        lo = 16 * (k % 5)
        np.testing.assert_array_equal(data, X[lo:lo + 16])
    # an epoch's first batch is copied at its dispatch, the others ahead
    assert counts == [(e, b, 4 * e + b, e + 1)
                      for e in range(2) for b in range(5)]
    group = mod._exec_group
    assert group._staged is None
    assert (group.n_staged, group.n_loaded) == (8, 2)


def test_score_after_fit_takes_the_unstaged_path():
    X, y = _toy_problem(n=80)
    train, mod, start = _ahead_start(mx.cpu(), X, y)
    mod.fit(train, num_epoch=1, **start)
    group = mod._exec_group
    assert (group.n_staged, group.n_loaded) == (4, 1)
    train.reset()
    mod.score(train, "acc")
    assert (group.n_staged, group.n_loaded) == (4, 6)
    assert len(mod.predict(train)) == 80
    assert (group.n_staged, group.n_loaded) == (4, 11)


def test_a_stage_is_dropped_by_another_batch_none_and_a_failed_step():
    X, y = _toy_problem(n=48)
    train, mod, start = _ahead_start(mx.cpu(), X, y)
    group = mod._exec_group
    first, second, third = list(train)
    bound = group.data_arrays[0][0][1]
    mod.forward(first, is_train=False)
    # staging binds nothing
    mod.prepare(second)
    assert group._staged[0] is second
    np.testing.assert_array_equal(bound.asnumpy(), X[:16])
    # a user's own loop hands over another batch: copied as ever, and the
    # stage does not outlive it
    mod.forward(third, is_train=False)
    assert group._staged is None
    np.testing.assert_array_equal(bound.asnumpy(), X[32:])
    assert (group.n_staged, group.n_loaded) == (0, 2)
    mod.forward(second, is_train=False)
    np.testing.assert_array_equal(bound.asnumpy(), X[16:32])
    assert (group.n_staged, group.n_loaded) == (0, 3)
    # withdrawn
    mod.prepare(second)
    mod.prepare(None)
    assert group._staged is None
    # a callback that raises ends fit with nothing staged
    train.reset()

    def boom(p):
        assert group._staged is not None
        raise RuntimeError("boom")

    with pytest.raises(RuntimeError, match="boom"):
        mod.fit(train, num_epoch=1, batch_end_callback=boom, **start)
    assert group._staged is None


def test_bucketing_fit_still_trains_with_the_default_prepare():
    """BucketingModule keeps the base class's ``prepare``: its buckets
    copy at the dispatch, and a smaller bucket, whose inputs view the
    largest one's buffer, could hold no copy beside them anyway."""
    batch_size, vocab = 8, 20

    def sym_gen(seq_len):
        data = mx.sym.Variable("data")
        label = mx.sym.Variable("softmax_label")
        embed = mx.sym.Embedding(data, name="embed", input_dim=vocab,
                                 output_dim=6)
        pooled = mx.sym.sum_axis(embed, axis=1)
        fc = mx.sym.FullyConnected(pooled, name="fc", num_hidden=2)
        return (mx.sym.SoftmaxOutput(fc, label=label, name="softmax"),
                ("data",), ("softmax_label",))

    class Feed(mx.io.DataIter):
        default_bucket_key = 12
        provide_data = [("data", (batch_size, 12))]
        provide_label = [("softmax_label", (batch_size,))]

        def __init__(self):
            super().__init__()
            self.batch_size = batch_size
            rng = np.random.RandomState(0)
            self.batches = []
            for i in range(12):
                seq_len = (12, 8)[i % 2]
                tokens = rng.randint(0, vocab // 2, (batch_size, seq_len))
                label = rng.randint(0, 2, (batch_size,))
                # the class decides which half of the vocabulary is used
                tokens = tokens + (vocab // 2) * label[:, None]
                self.batches.append(mx.io.DataBatch(
                    data=[mx.nd.array(tokens.astype(np.float32))],
                    label=[mx.nd.array(label.astype(np.float32))], pad=0,
                    bucket_key=seq_len,
                    provide_data=[("data", (batch_size, seq_len))],
                    provide_label=[("softmax_label", (batch_size,))]))
            self.cursor = 0

        def reset(self):
            self.cursor = 0

        def next(self):
            if self.cursor == len(self.batches):
                raise StopIteration
            self.cursor += 1
            return self.batches[self.cursor - 1]

    feed = Feed()
    mod = mx.mod.BucketingModule(sym_gen, default_bucket_key=12,
                                 context=mx.cpu())
    assert type(mod).prepare is mx.mod.BaseModule.prepare
    mod.fit(feed, eval_metric="acc", num_epoch=6, optimizer="sgd",
            optimizer_params={"learning_rate": 0.5},
            initializer=mx.init.Uniform(0.1))
    feed.reset()
    assert dict(mod.score(feed, "acc"))["accuracy"] > 0.9
    groups = [m._exec_group for m in mod._buckets.values()]
    assert len(groups) == 2
    assert sum(g.n_staged for g in groups) == 0
    assert sum(g.n_loaded for g in groups) == 6 * 12 + 12
    # the smaller bucket's inputs are views: asked to stage, it declines
    small = mod._buckets[8]._exec_group
    assert small.data_arrays[0][0][1]._parent is not None
    small.stage_data_batch(feed.batches[1])
    assert small._staged is None


# ----------------------------------------------------------------------
# the fused step donates what it replaces, and takes nothing shared
# ----------------------------------------------------------------------
def _stepping_module(ctx):
    X, y = _toy_problem(n=64)
    train, mod, start = _ahead_start(ctx, X, y)
    mod.set_params(start["arg_params"], start["aux_params"])
    mod.init_optimizer(kvstore=start["kvstore"], optimizer="sgd",
                       optimizer_params=_AHEAD_OPT)
    return mod, list(train)


def _step(mod, batch):
    mod.forward_backward(batch)
    mod.update()


@pytest.mark.parametrize("make_ctx", _AHEAD_CONTEXTS)
def test_fused_step_outputs_reuse_its_inputs_buffers(make_ctx):
    """Weights, old gradients, auxiliary and optimizer state are donated:
    the lowered step aliases every one of them to an output, so that the
    runtime allocates no buffer for them at the dispatch."""
    import warnings
    mod, batches = _stepping_module(make_ctx())
    exe = mod._exec_group.execs[0]
    with warnings.catch_warnings():
        warnings.simplefilter("error")      # "donated buffers not usable"
        _step(mod, batches[0])
    states = mod._exec_group._ensure_on_mesh(
        (exe.init_fused_states(mod._optimizer),))[0]
    text = exe._lower_fused(mod._optimizer, states).as_text()
    n_wrt = len(exe._get_fused(mod._optimizer)[0])
    assert n_wrt == 4
    n_state = len(jax.tree_util.tree_leaves(states))
    assert text.count("tf.aliasing_output") + text.count(
        "jax.buffer_donor") == 2 * n_wrt + len(exe.aux_dict) + n_state


@pytest.mark.parametrize("make_ctx", _AHEAD_CONTEXTS)
def test_fused_step_gives_away_only_what_is_its_own(make_ctx, monkeypatch):
    """From the second step on every weight, gradient and auxiliary state
    is the step's own output and is handed on as it is; the first step
    after the parameters were set copies instead, and so does any step
    for a buffer that something else still refers to."""
    from mxnet_tpu import executor
    mod, batches = _stepping_module(make_ctx())
    exe = mod._exec_group.execs[0]
    copied = []
    real = executor._consumable

    def counting(arrays, **kw):
        copied.append(sorted(n for n, a in arrays.items()
                             if a._donatable() is None))
        return real(arrays, **kw)

    monkeypatch.setattr(executor, "_consumable", counting)
    _step(mod, batches[0])
    wrt = sorted(exe._get_fused(mod._optimizer)[0])
    assert copied == [wrt, wrt, sorted(exe.aux_dict)]
    del copied[:]
    _step(mod, batches[1])
    assert copied == [[], [], []]
    # something else refers to one weight's buffer: that one is copied
    held = exe.arg_dict["fc1_weight"].copy()
    kept = held.asnumpy().copy()
    del copied[:]
    _step(mod, batches[2])
    assert copied == [["fc1_weight"], [], []]
    np.testing.assert_array_equal(held.asnumpy(), kept)
    # a parameter set from outside is not the step's own any more
    exe.arg_dict["fc2_bias"]._set_data(exe.arg_dict["fc2_bias"].data * 1)
    del copied[:]
    _step(mod, batches[3])
    assert copied == [["fc2_bias"], [], []]     # ``held`` has the old one
    del copied[:]
    _step(mod, batches[0])
    assert copied == [[], [], []]


def test_fused_step_leaves_every_shared_buffer_readable():
    """What a caller holds stays whole over the steps that follow: the
    dict it passed to ``fit``, a ``copy()``, ``get_params()``'s arrays, a
    raw jax array, and an array that ``device_put`` made over the same
    buffer (a replicated put aliases its source's shard)."""
    X, y = _toy_problem(n=64)
    train, mod, start = _ahead_start([mx.cpu(0), mx.cpu(1)], X, y)
    mine = {k: v.asnumpy().copy() for k, v in start["arg_params"].items()}
    seen = {}

    def batch_end(p):
        group = mod._exec_group
        if p.epoch == 0 and p.nbatch == 0:
            exe = group.execs[0]
            seen["copy"] = exe.arg_dict["fc1_weight"].copy()
            seen["raw"] = exe.arg_dict["fc1_bias"].data
            seen["grad"] = exe.grad_dict["fc2_weight"].data
            seen["put"] = jax.device_put(exe.arg_dict["fc2_weight"].data,
                                         group._repl_sharding)
            seen["mom"] = dict(mod._fused_holder["states"])
            seen["then"] = {k: np.asarray(v.data if hasattr(v, "asnumpy")
                                          else v).copy()
                            for k, v in seen.items() if k != "mom"}

    mod.fit(train, num_epoch=2, batch_end_callback=batch_end, **start)
    for k, v in start["arg_params"].items():
        np.testing.assert_array_equal(v.asnumpy(), mine[k])
    for k, want in seen["then"].items():
        got = seen[k]
        np.testing.assert_array_equal(
            np.asarray(got.data if hasattr(got, "asnumpy") else got), want)
    # the optimizer state was donated before this PR and is: a caller
    # reads it inside the callback, as the benchmark does
    assert all(v.is_deleted() for v in seen["mom"].values())
