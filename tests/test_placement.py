"""A context is a placement, not a label: what is bound, served or
trained under ``mx.cpu(3)`` lives and runs on device 3 of the 8-device
CPU mesh — the chip-free stand-in for "asked for the TPU, got the TPU"
(on a chip host jax's default device is the accelerator, so anything
that silently follows the default lands on the wrong device there)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import ndarray as nd
from mxnet_tpu.models import transformer as tf
from mxnet_tpu.predictor import Predictor
from mxnet_tpu.serving import GenerationEngine

CTX = mx.cpu(3)
DEV = {CTX.jax_device}


def _mlp():
    data = mx.sym.Variable("data")
    fc1 = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    act = mx.sym.Activation(fc1, act_type="relu")
    fc2 = mx.sym.FullyConnected(act, num_hidden=4, name="fc2")
    return mx.sym.SoftmaxOutput(fc2, name="softmax")


def _on_dev(arrays):
    return all(a.data.devices() == DEV for a in arrays)


def test_predictor_lives_on_its_context():
    """Weights given as host numpy AND as NDArrays of another context,
    inputs written per request, and outputs: all on cpu(3)."""
    rng = np.random.RandomState(0)
    params = {"fc1_weight": rng.randn(16, 8).astype(np.float32),
              "fc1_bias": nd.zeros((16,)),                  # cpu(0)
              "fc2_weight": nd.array(rng.randn(4, 16).astype(np.float32)),
              "fc2_bias": np.zeros(4, np.float32)}
    pred = Predictor(_mlp().tojson(), params, {"data": (2, 8)}, ctx=CTX)
    x = rng.randn(2, 8).astype(np.float32)
    want = Predictor(_mlp().tojson(), params, {"data": (2, 8)}).forward(
        data=x)[0]
    got = pred.forward(data=x)[0]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    ex = pred._exec
    assert _on_dev(ex.arg_dict.values()) and _on_dev(ex.outputs)
    assert all(o.devices() == DEV for o in pred.forward_async(data=x))
    # a rebind for new shapes keeps the device too
    assert _on_dev(pred.reshape({"data": (4, 8)})._exec.arg_dict.values())


def test_generation_engine_lives_on_its_context():
    """Weights, KV pools, per-step inputs and outputs of every prefill
    and decode executable sit on the engine's device, before and after
    requests have run."""
    cfg = dict(vocab_size=64, num_layers=2, num_heads=2, dim=32)
    full = tf.get_symbol(seq_len=32, **cfg)
    shapes = full.infer_shape(data=(1, 32), softmax_label=(1, 32))[0]
    rng = np.random.RandomState(0)
    params = {n: nd.array(rng.randn(*s).astype(np.float32) * 0.05)
              for n, s in zip(full.list_arguments(), shapes)
              if n not in ("data", "softmax_label")}
    kw = dict(max_seq_len=32, max_new_tokens=4, prompt_buckets="8,16",
              decode_buckets="1,2", kv_blocks=16, kv_block_size=8, **cfg)
    eng = GenerationEngine(params=params, ctx=CTX, **kw)
    prompts = [[1, 2, 3], [4, 5, 6, 7, 8, 9, 10, 11, 12]]
    tokens = eng.generate(prompts)
    assert tokens == GenerationEngine(params=params, **kw).generate(prompts)
    pools = eng.cache.k_pools + eng.cache.v_pools
    assert all(p.devices() == DEV for p in pools)
    for pred in list(eng._prefill.values()) + list(eng._decode.values()):
        assert _on_dev(pred._exec.arg_dict.values())
    pred, inputs, _b = eng.start_decode([])
    assert all(o.devices() == DEV for o in eng.run_async(pred, inputs))


def test_module_trains_on_its_context():
    """Module(context=cpu(3)).fit: parameters, gradients, optimizer
    state and outputs end on device 3 (they used to end uncommitted on
    jax's default device), and the fit still learns."""
    rng = np.random.RandomState(1)
    x = rng.randn(64, 8).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.float32)
    it = mx.io.NDArrayIter(x, y, batch_size=16)
    mod = mx.mod.Module(_mlp(), context=CTX)
    mod.fit(it, num_epoch=4, optimizer="sgd",
            optimizer_params={"learning_rate": 0.5, "momentum": 0.9},
            initializer=mx.init.Xavier())
    ex = mod._exec_group.execs[0]
    assert ex._n_fused_step > 0                  # the fused-step path
    assert _on_dev(ex.arg_dict.values()) and _on_dev(ex.outputs)
    assert _on_dev(ex.grad_dict.values()) and _on_dev(ex.aux_dict.values())
    import jax
    states = jax.tree_util.tree_leaves(mod._fused_holder["states"])
    assert states and all(s.devices() == DEV for s in states)
    it.reset()
    assert mod.score(it, "acc")[0][1] > 0.8


def test_bind_refuses_arrays_of_another_device():
    net = _mlp()
    shapes = dict(zip(net.list_arguments(),
                      net.infer_shape(data=(2, 8))[0]))
    args = {n: nd.zeros(s, ctx=CTX) for n, s in shapes.items()}
    net.bind(CTX, args, grad_req="null")         # all on ctx: binds
    args["fc2_weight"] = nd.zeros(shapes["fc2_weight"], ctx=mx.cpu(1))
    with pytest.raises(mx.base.MXNetError, match="fc2_weight.*cpu\\(3\\)"):
        net.bind(CTX, args, grad_req="null")
    # ... unless the graph places that argument there itself
    net.bind(CTX, args, grad_req="null", group2ctx={"other": mx.cpu(1)})
