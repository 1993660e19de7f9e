"""Mirroring (activation recompute) lowered to per-segment jax.checkpoint.

Reference: MakeBackwardPass builds a mirror map and splices duplicate
nodes so backward reads recomputed activations
(static_graph.cc:396-440); the executor drops mirrored forward nodes
from the backward topo (graph_executor.cc:313-352).  Here the same
need_mirror rules partition the trace into ``jax.checkpoint`` segments:
internals leave the vjp residual set and recompute in backward.
"""
import numpy as np
import pytest

import mxnet_tpu as mx


def _mlp(attr=None, n_layers=5, hidden=64, act="tanh"):
    x = mx.sym.Variable("data")
    h = x
    for i in range(n_layers):
        h = mx.sym.FullyConnected(h, num_hidden=hidden, name="fc%d" % i)
        h = mx.sym.Activation(h, act_type=act, name="act%d" % i,
                              attr=attr or {})
    return mx.sym.SoftmaxOutput(h, mx.sym.Variable("softmax_label"),
                                name="softmax")


def _bind_run(sym, batch=16, dim=64, seed=3):
    ex = sym.simple_bind(mx.cpu(), data=(batch, dim), grad_req="write")
    rs = np.random.RandomState(seed)
    for n, a in ex.arg_dict.items():
        if n not in ("data", "softmax_label"):
            a[:] = rs.rand(*a.shape).astype(np.float32)
    ex.arg_dict["data"][:] = rs.rand(batch, dim).astype(np.float32)
    ex.arg_dict["softmax_label"][:] = rs.randint(
        0, dim, (batch,)).astype(np.float32)
    ex.forward(is_train=True)
    ex.backward()
    return ex


def test_force_mirroring_numerics_and_residuals():
    plain = _bind_run(_mlp())
    mirr = _bind_run(_mlp(attr={"force_mirroring": "true"}))
    assert np.allclose(plain.outputs[0].asnumpy(),
                       mirr.outputs[0].asnumpy(), atol=1e-5)
    for n, g in plain.grad_dict.items():
        assert np.allclose(g.asnumpy(), mirr.grad_dict[n].asnumpy(),
                           atol=1e-5), n
    rp = plain.backward_residual_bytes()
    rm = mirr.backward_residual_bytes()
    if rp is None:
        pytest.skip("saved_residuals introspection unavailable")
    # the mirrored activations left the residual set
    assert rm < rp, (rm, rp)


def test_env_do_mirror(monkeypatch):
    """MXNET_BACKWARD_DO_MIRROR=1 mirrors eligible ops with no attrs at
    all (static_graph.cc:404); FullyConnected stays on the skip list."""
    monkeypatch.setenv("MXNET_BACKWARD_DO_MIRROR", "1")
    mirr = _bind_run(_mlp())
    monkeypatch.delenv("MXNET_BACKWARD_DO_MIRROR")
    plain = _bind_run(_mlp())
    assert np.allclose(plain.outputs[0].asnumpy(),
                       mirr.outputs[0].asnumpy(), atol=1e-5)
    rp = plain.backward_residual_bytes()
    rm = mirr.backward_residual_bytes()
    if rp is None:
        pytest.skip("saved_residuals introspection unavailable")
    assert rm < rp, (rm, rp)


def test_mirror_with_dropout_rng_replay():
    """Dropout inside a mirrored region: the reference excludes Dropout
    from mirroring (its mask would differ on recompute); here the jax
    PRNG key is a segment input so even mirrored neighbours replay the
    SAME randomness — backward must match an unmirrored run
    numerically."""
    def net(attr):
        x = mx.sym.Variable("data")
        h = mx.sym.FullyConnected(x, num_hidden=32, name="fc0")
        h = mx.sym.Activation(h, act_type="relu", name="a0", attr=attr)
        h = mx.sym.Dropout(h, p=0.5, name="drop")
        h = mx.sym.FullyConnected(h, num_hidden=32, name="fc1")
        h = mx.sym.Activation(h, act_type="relu", name="a1", attr=attr)
        return mx.sym.SoftmaxOutput(
            h, mx.sym.Variable("softmax_label"), name="softmax")

    # same PRNG stream for both runs
    mx.random.seed(1234)
    plain = _bind_run(net({}), dim=32)
    mx.random.seed(1234)
    mirr = _bind_run(net({"force_mirroring": "true"}), dim=32)
    assert np.allclose(plain.outputs[0].asnumpy(),
                       mirr.outputs[0].asnumpy(), atol=1e-5)
    for n, g in plain.grad_dict.items():
        assert np.allclose(g.asnumpy(), mirr.grad_dict[n].asnumpy(),
                           atol=1e-5), n


def test_mirror_batchnorm_aux_updates_cross_segment():
    """BatchNorm moving stats computed INSIDE a mirrored segment must
    still land in the executor aux arrays (segment aux updates are
    checkpoint outputs)."""
    def net(attr):
        x = mx.sym.Variable("data")
        h = mx.sym.FullyConnected(x, num_hidden=16, name="fc0")
        h = mx.sym.BatchNorm(h, name="bn0", attr=attr)
        h = mx.sym.Activation(h, act_type="relu", name="a0", attr=attr)
        return mx.sym.SoftmaxOutput(
            h, mx.sym.Variable("softmax_label"), name="softmax")

    plain = _bind_run(net({}), dim=16)
    mirr = _bind_run(net({"force_mirroring": "true"}), dim=16)
    for n, a in plain.aux_dict.items():
        assert np.allclose(a.asnumpy(), mirr.aux_dict[n].asnumpy(),
                           atol=1e-5), n
    # the moving stats actually moved (update happened inside the
    # checkpointed segment)
    mm = mirr.aux_dict["bn0_moving_mean"].asnumpy()
    assert not np.allclose(mm, np.zeros_like(mm))


def test_mirror_monitor_unaffected():
    """A monitor observes every op output: monitored traces run
    unmirrored (a checkpointed callback would double-fire on recompute)
    and values match the mirrored program's."""
    sym = _mlp(attr={"force_mirroring": "true"}, n_layers=2)
    ex = _bind_run(sym)
    seen = {}
    ex.set_monitor_callback(lambda name, arr: seen.setdefault(
        name, arr.asnumpy()))
    ex.forward(is_train=True)
    assert any(k.startswith("act") for k in seen)
    assert np.allclose(seen["softmax_output"],
                       ex.outputs[0].asnumpy(), atol=1e-5)


def test_mirror_on_sharded_trainer_path():
    """The pjit ShardedTrainer traces through the same _build_program,
    so attr-tagged mirroring gives stage-granular recompute on the
    sharded path too (finer than the all-or-nothing remat=True knob);
    numerics must match the unmirrored trainer."""
    import jax
    import numpy as np
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.trainer import ShardedTrainer
    from mxnet_tpu import optimizer as opt_mod

    def run(attr):
        mx.random.seed(11)      # init_params draws from the global stream
        sym = _mlp(attr=attr, n_layers=4, hidden=32)
        mesh = make_mesh(jax.devices()[:2], dp=2)
        opt = opt_mod.create("sgd", learning_rate=0.1, momentum=0.9)
        tr = ShardedTrainer(sym, opt, mesh)
        params, st, aux = tr.init_params(
            {"data": (8, 32)}, label_shapes={"softmax_label": (8,)})
        rs = np.random.RandomState(0)
        host_batch = {
            "data": rs.rand(8, 32).astype(np.float32),
            "softmax_label": rs.randint(0, 32, (8,)).astype(np.float32)}
        batch = tr.shard_batch(host_batch)
        params, st, aux, outs = tr.step(params, st, aux, batch,
                                        rng=jax.random.PRNGKey(7))

        # the recompute signal: residuals jax saves across the trainer's
        # OWN trace (what the fused step differentiates) — shrinks iff
        # the checkpoint segments actually engaged on this path
        from mxnet_tpu.executor import trace_residual_bytes
        host = {k: np.asarray(v) for k, v in params.items()}
        host.update(host_batch)
        resid = trace_residual_bytes(tr._trace, host, dict(aux),
                                     tr.param_names)
        return jax.tree_util.tree_map(np.asarray, params), resid

    p_plain, res_plain = run({})
    p_mirr, res_mirr = run({"force_mirroring": "true"})
    for k in p_plain:
        np.testing.assert_allclose(p_plain[k], p_mirr[k], atol=1e-5,
                                   err_msg=k)
    if res_plain is not None:
        assert res_mirr < res_plain, (res_mirr, res_plain)


def test_resnet_mirror_blocks_numerics_and_residuals():
    """resnet.get_symbol(mirror_blocks=True): whole residual units
    recompute in backward (force_mirroring overrides the conv skip
    list; per-unit mirror_stage splits segments at block boundaries).
    Numerics must match the plain build; the residual set must shrink
    MORE than the env knob's elementwise-only segments would."""
    from mxnet_tpu.models import resnet

    def run(mb):
        sym = resnet.get_symbol(num_classes=10, num_layers=18,
                                image_shape=(3, 32, 32), mirror_blocks=mb)
        ex = sym.simple_bind(mx.cpu(), data=(4, 3, 32, 32),
                             grad_req="write")
        rs = np.random.RandomState(0)
        for n, a in ex.arg_dict.items():
            if n not in ("data", "softmax_label"):
                a[:] = (rs.rand(*a.shape).astype(np.float32) - 0.5) * 0.2
        ex.arg_dict["data"][:] = rs.rand(4, 3, 32, 32).astype(np.float32)
        ex.arg_dict["softmax_label"][:] = rs.randint(
            0, 10, (4,)).astype(np.float32)
        ex.forward(is_train=True)
        ex.backward()
        return ex

    plain = run(False)
    mirr = run(True)
    assert np.allclose(plain.outputs[0].asnumpy(),
                       mirr.outputs[0].asnumpy(), atol=1e-5)
    for n, g in plain.grad_dict.items():
        assert np.allclose(g.asnumpy(), mirr.grad_dict[n].asnumpy(),
                           atol=1e-4), n
    rp = plain.backward_residual_bytes()
    rm = mirr.backward_residual_bytes()
    if rp is None:
        pytest.skip("saved_residuals introspection unavailable")
    # block-granular remat drops well over a third of the residual set
    assert rm < 0.65 * rp, (rm, rp)

    # the attrs really are on the unit ops (and only on unit ops)
    sym = resnet.get_symbol(num_classes=10, num_layers=18,
                            mirror_blocks=True)
    attrs = sym.attr_dict()
    assert attrs.get("stage1_unit1_conv1", {}).get(
        "force_mirroring") == "true"
    assert attrs.get("stage1_unit1_conv1", {}).get(
        "mirror_stage") == "stage1_unit1"
    assert attrs.get("stage2_unit1_bn1", {}).get(
        "mirror_stage") == "stage2_unit1"
    assert "force_mirroring" not in attrs.get("conv0", {})


def test_transformer_mirror_blocks_numerics_and_residuals():
    """transformer.get_symbol(mirror_blocks=True): per-decoder-layer
    recompute; numerics identical, residual set shrinks."""
    from mxnet_tpu.models import transformer

    def run(mb):
        sym = transformer.get_symbol(vocab_size=64, num_layers=2,
                                     num_heads=2, dim=32, seq_len=16,
                                     mirror_blocks=mb)
        ex = sym.simple_bind(mx.cpu(), data=(2, 16),
                             softmax_label=(2, 16), grad_req="write")
        rs = np.random.RandomState(0)
        for n, a in ex.arg_dict.items():
            if n not in ("data", "softmax_label"):
                a[:] = (rs.rand(*a.shape).astype(np.float32) - 0.5) * 0.1
        ex.arg_dict["data"][:] = rs.randint(0, 64, (2, 16)).astype(
            np.float32)
        ex.arg_dict["softmax_label"][:] = rs.randint(
            0, 64, (2, 16)).astype(np.float32)
        ex.forward(is_train=True)
        ex.backward()
        return ex

    plain = run(False)
    mirr = run(True)
    assert np.allclose(plain.outputs[0].asnumpy(),
                       mirr.outputs[0].asnumpy(), atol=1e-5)
    for n, g in plain.grad_dict.items():
        assert np.allclose(g.asnumpy(), mirr.grad_dict[n].asnumpy(),
                           atol=1e-4), n
    rp = plain.backward_residual_bytes()
    rm = mirr.backward_residual_bytes()
    if rp is None:
        pytest.skip("saved_residuals introspection unavailable")
    assert rm < rp, (rm, rp)


_LOWER_SCRIPT = """
import hashlib, sys
sys.path.insert(0, %r)
import jax, jax.numpy as jnp
from mxnet_tpu.executor import _build_program, _zero_key
from mxnet_tpu.models import transformer_mla_moe
# blocks that share variables (the embedding, the head) and hold a dozen
# weights each: what their segments read is a long list
net = transformer_mla_moe.get_symbol(
    vocab_size=64, num_layers=3, dim=32, seq_len=16, num_heads=2,
    q_lora_rank=16, kv_lora_rank=8, qk_nope_head_dim=8, qk_rope_head_dim=8,
    v_head_dim=8, intermediate_size=64, moe_intermediate_size=16,
    n_routed_experts=4, num_experts_per_tok=2, mirror_blocks=True)
prog = _build_program(net, {})
shapes, _, aux_shapes = net.infer_shape(
    data=(1, 16), softmax_label=(1, 16), mtp_label=(1, 16))
_, _, aux_types = net.infer_type()
args = {n: jnp.zeros(s, jnp.float32)
        for n, s in zip(net.list_arguments(), shapes)}
aux = {n: jnp.zeros(s, t) for n, s, t in zip(
    net.list_auxiliary_states(), aux_shapes, aux_types)}
def loss(a):
    outs, _aux = prog.trace(a, aux, _zero_key(), True)
    return sum(jnp.sum(o) for o in outs)
text = jax.jit(jax.grad(loss)).lower(args).as_text()
assert "optimization_barrier" in text
print(hashlib.sha256(text.encode()).hexdigest())
"""


def test_mirrored_step_lowers_to_the_same_text_in_every_process():
    """A segment's inputs must not be ordered by ``id()``: the lowered
    text, and with it the persistent compile cache's key, would differ
    from process to process and the step would compile in every run
    (it did: PERF.md section 6, PR 27)."""
    import os
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    digests = set()
    for hashseed in ("1", "2", "3"):
        # another hash seed moves the interpreter's allocations too
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONHASHSEED=hashseed)
        out = subprocess.run([sys.executable, "-c", _LOWER_SCRIPT % root],
                             env=env, check=True, capture_output=True,
                             text=True)
        digests.add(out.stdout.split()[-1])
    assert len(digests) == 1, digests


# ----------------------------------------------------------------------
# What a mirrored segment keeps: the flash kernel's operands, output and
# softmax statistics, by the names their producer gives them
# (executor.KEPT)
# ----------------------------------------------------------------------
def _kernel_calls(jaxpr, found=None):
    """{kernel name: pallas_call equations} of ``jaxpr``, however deep
    (checkpoints, conds, jits and custom_vjps hold jaxprs of their own)."""
    found = {} if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            name = eqn.params["name"]
            found[name] = found.get(name, 0) + 1
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (tuple, list))
                        else (value,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _kernel_calls(sub, found)
    return found


def _qkv(shape_q, shape_kv, seed=0):
    import jax.numpy as jnp
    rs = np.random.RandomState(seed)
    return tuple(jnp.asarray(rs.randn(*s).astype(np.float32) * 0.5)
                 for s in (shape_q, shape_kv, shape_kv))


def _attention_block(q, k, v, w):
    """What a block does around its kernel: something before, the kernel,
    something after."""
    import jax.numpy as jnp
    from mxnet_tpu.parallel.ring_attention import flash_attention
    out = flash_attention(jnp.tanh(q), k * 1.5, v, causal=True,
                          interpret=True)
    return jnp.sum(jnp.sin(out) * w)


def test_kept_names_are_the_kernels():
    from mxnet_tpu import executor
    from mxnet_tpu.parallel import ring_attention
    assert executor.KEPT == ring_attention.FLASH_RESIDUALS
    assert executor.KEPT == ("flash_q", "flash_k", "flash_v", "flash_out",
                             "flash_lse")


@pytest.mark.parametrize("heads,kv_heads", [(2, 2), (4, 2)])
def test_checkpoint_that_keeps_the_names_runs_the_forward_kernel_once(
        heads, kv_heads):
    import jax
    import jax.numpy as jnp
    from jax._src.ad_checkpoint import saved_residuals
    from mxnet_tpu.executor import (mirror_checkpoint, trace_mirror_kept,
                                    trace_residual_bytes)
    q, k, v = _qkv((1, heads, 128, 16), (1, kv_heads, 128, 16))
    w = jnp.asarray(np.random.RandomState(1).randn(1, heads, 128, 16)
                    .astype(np.float32))
    plain = lambda q, k, v: _attention_block(q, k, v, w)   # noqa: E731
    bare, keeping = jax.checkpoint(plain), mirror_checkpoint(plain)

    # one forward call less in the gradient's program, the backward's as
    # it was
    def grad_calls(fn):
        return _kernel_calls(jax.make_jaxpr(
            jax.grad(fn, argnums=(0, 1, 2)))(q, k, v).jaxpr)
    assert grad_calls(plain) == {"flash_forward": 1, "flash_backward": 1}
    assert grad_calls(bare) == {"flash_forward": 2, "flash_backward": 1}
    assert grad_calls(keeping) == {"flash_forward": 1, "flash_backward": 1}

    # exactly the kernel's five residuals, beside the segment's inputs
    def trace_of(fn):
        return lambda args, aux, _rng, _train: (
            [fn(args["q"], args["k"], args["v"])], aux)
    args = {"q": q, "k": k, "v": v}
    q_bytes, kv_bytes = heads * 128 * 16 * 4, kv_heads * 128 * 16 * 4
    kept = [("flash_q", q_bytes), ("flash_k", kv_bytes),
            ("flash_v", kv_bytes), ("flash_out", q_bytes),
            ("flash_lse", heads * 128 * 4)]
    assert sorted(trace_mirror_kept(trace_of(keeping), args, {},
                                    ("q", "k", "v"))) == sorted(kept)
    assert trace_mirror_kept(trace_of(bare), args, {}, ("q", "k", "v")) == []
    # the segment reads q, k, v and, as a constant, w
    inputs = sum(int(a.size) * 4 for a in (q, k, v, w))
    assert trace_residual_bytes(trace_of(bare), args, {},
                                ("q", "k", "v")) == inputs
    # of its inputs the keeping segment holds q (the tanh's) and w: k and
    # v are read for the kernel alone, whose operands are kept
    assert trace_residual_bytes(trace_of(keeping), args, {},
                                ("q", "k", "v")) \
        == int(q.size + w.size) * 4 + sum(n for _name, n in kept)
    # jax's own listing calls the statistics named (the output, which the
    # forward pass reads on, it lists under the reduce_precision jax puts
    # on such a value)
    described = [d for _a, d in saved_residuals(keeping, q, k, v)]
    assert sum("named 'flash_lse'" in d for d in described) == 1

    # same gradients
    want = jax.grad(plain, argnums=(0, 1, 2))(q, k, v)
    got = jax.grad(keeping, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", want, got):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=1e-6,
                                   atol=1e-7, err_msg="d" + name)


def test_a_name_outside_a_checkpoint_lowers_to_nothing(monkeypatch):
    """An unmirrored step (gpt2m_train_s1024's) must lower as it did before
    the kernel named its output: the text with the names is the text with
    ``checkpoint_name`` taken out (but for the numbers jax gives the
    interpreted kernel's private functions)."""
    import re
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.parallel import ring_attention
    q, k, v = _qkv((1, 2, 128, 16), (1, 2, 128, 16))
    w = jnp.ones((1, 2, 128, 16), jnp.float32)

    def text():
        fn = lambda q, k, v: _attention_block(q, k, v, w)   # noqa: E731
        return re.sub(r"@(\w+?)_\d+\b", r"@\1", jax.jit(jax.grad(
            fn, argnums=(0, 1, 2))).lower(q, k, v).as_text())
    named = text()
    monkeypatch.setattr(ring_attention, "checkpoint_name",
                        lambda x, _name: x)
    assert text() == named


def _routed_block_model(which, mirror):
    from mxnet_tpu.models import transformer_cca_moe, transformer_mla_moe
    if which == "mla":
        net = transformer_mla_moe.get_symbol(
            vocab_size=64, num_layers=2, dim=32, seq_len=128, num_heads=2,
            q_lora_rank=16, kv_lora_rank=8, qk_nope_head_dim=8,
            qk_rope_head_dim=8, v_head_dim=8, intermediate_size=64,
            moe_intermediate_size=16, n_routed_experts=4,
            num_experts_per_tok=2, mirror_blocks=mirror)
        labels = dict(softmax_label=(1, 128), mtp_label=(1, 128))
        # two layers and the prediction module's: three calls of the
        # kernel; q and k (1, 2, 128, 16), v and the output (1, 2, 128, 8),
        # lse (1, 2, 128)
        return net, labels, 3, (2 * 128 * 16 * 4, 2 * 128 * 16 * 4,
                                2 * 128 * 8 * 4, 2 * 128 * 8 * 4,
                                2 * 128 * 4)
    net = transformer_cca_moe.get_symbol(
        vocab_size=64, num_layers=2, dim=32, seq_len=128, num_heads=4,
        num_kv_heads=2, head_dim=8, moe_intermediate_size=16,
        num_experts=4, router_hidden_size=8, mirror_blocks=mirror)
    # four query heads on two key/value heads of 8
    return (net, dict(softmax_label=(1, 128)), 2,
            (4 * 128 * 8 * 4, 2 * 128 * 8 * 4, 2 * 128 * 8 * 4,
             4 * 128 * 8 * 4, 4 * 128 * 4))


@pytest.mark.parametrize("which", ["mla", "cca"])
def test_build_program_keeps_the_kernels_output_in_a_mirrored_block(
        which, monkeypatch):
    """Through ``_build_program``, the dispatch forced to the interpreted
    kernel: a small latent-attention block and a small convolution-mixed
    one, ``mirror_blocks=True``."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.executor import _build_program, _zero_key
    from mxnet_tpu.kernels import common
    monkeypatch.setattr(
        common, "dispatch",
        lambda kernel, _reference, *args: kernel(*args, interpret=True))

    def build(mirror):
        net, labels, layers, sizes = _routed_block_model(which, mirror)
        prog = _build_program(net, {})
        shapes, _, aux_shapes = net.infer_shape(data=(1, 128), **labels)
        _, _, aux_types = net.infer_type()
        rs = np.random.RandomState(0)
        args = {n: jnp.asarray((rs.rand(*s).astype(np.float32) - 0.5) * 0.2)
                for n, s in zip(net.list_arguments(), shapes)}
        for n in ("data",) + tuple(labels):
            args[n] = jnp.asarray(rs.randint(0, 64, (1, 128))
                                  .astype(np.float32))
        aux = {n: jnp.zeros(s, t) for n, s, t in zip(
            net.list_auxiliary_states(), aux_shapes, aux_types)}
        wrt = tuple(n for n in args if n != "data" and n not in labels)

        def loss(w):
            outs, _aux = prog.trace(dict(args, **w), aux, _zero_key(), True)
            return sum(jnp.sum(o * o) for o in outs)
        return prog, args, aux, wrt, loss, layers, sizes

    prog, args, aux, wrt, loss, layers, sizes = build(True)
    w = {n: args[n] for n in wrt}
    assert prog.mirrored
    assert _kernel_calls(jax.make_jaxpr(jax.grad(loss))(w).jaxpr) \
        == {"flash_forward": layers, "flash_backward": layers}
    from mxnet_tpu.executor import KEPT
    assert sorted(prog.mirror_kept(args, aux, wrt)) \
        == sorted(list(zip(KEPT, sizes)) * layers)

    plain, p_args, p_aux, _wrt, p_loss, _layers, _sizes = build(False)
    assert not plain.mirrored and plain.mirror_kept(p_args, p_aux, wrt) == []
    want = jax.grad(p_loss)(w)
    got = jax.grad(loss)(w)
    for n in wrt:
        np.testing.assert_allclose(np.asarray(got[n]), np.asarray(want[n]),
                                   rtol=2e-4, atol=1e-6, err_msg=n)


@pytest.mark.parametrize("which", ["mlp", "mlp_env", "resnet"])
def test_a_segment_that_names_nothing_saves_what_it_saved(which,
                                                          monkeypatch):
    """ResNet's units, MXNET_BACKWARD_DO_MIRROR's runs, an MLP: with the
    policy their residuals are those of a policy-less checkpoint (the
    policy of no names), and nothing is kept by name."""
    from mxnet_tpu import executor
    from mxnet_tpu.models import resnet

    def bound():
        if which == "resnet":
            sym = resnet.get_symbol(num_classes=10, num_layers=18,
                                    image_shape=(3, 32, 32),
                                    mirror_blocks=True)
            return sym.simple_bind(mx.cpu(), data=(4, 3, 32, 32),
                                   grad_req="write")
        attr = {} if which == "mlp_env" else {"force_mirroring": "true"}
        return _mlp(attr=attr).simple_bind(mx.cpu(), data=(16, 64),
                                           grad_req="write")
    if which == "mlp_env":
        monkeypatch.setenv("MXNET_BACKWARD_DO_MIRROR", "1")
    ex = bound()
    assert ex._program.mirrored
    assert ex.mirror_kept() == []
    with_policy = ex.backward_residual_bytes()
    monkeypatch.setattr(executor, "KEPT", ())
    executor._PROGRAM_REGISTRY.clear()
    assert bound().backward_residual_bytes() == with_policy


def test_mirrored_attention_under_a_mesh_keeps_the_names_inside_shard_map(
        monkeypatch):
    """Under a multi-device mesh ``sharded_self_attention`` wraps the kernel
    in ``shard_map``.  Read on the CPU with a forced mesh: the policy sees
    the names inside it — the forward kernel is called once, the five
    values are saved at their global shapes — and the gradients are the
    unmirrored ones."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.executor import mirror_checkpoint, trace_mirror_kept
    from mxnet_tpu.kernels import common
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.ring_attention import (sequence_parallel,
                                                   sharded_self_attention)
    monkeypatch.setattr(
        common, "dispatch",
        lambda kernel, _reference, *args: kernel(*args, interpret=True))
    mesh = make_mesh(jax.devices()[:2], dp=2)
    q, k, v = _qkv((2, 2, 128, 16), (2, 2, 128, 16))

    def block(q, k, v):
        out = sharded_self_attention(jnp.tanh(q), k * 1.5, v, causal=True)
        return jnp.sum(jnp.sin(out))

    kept_block = mirror_checkpoint(block)
    with sequence_parallel(mesh, seq_axis=None):
        plain_calls = _kernel_calls(jax.make_jaxpr(
            jax.grad(block, argnums=(0, 1, 2)))(q, k, v).jaxpr)
        kept_calls = _kernel_calls(jax.make_jaxpr(
            jax.grad(kept_block, argnums=(0, 1, 2)))(q, k, v).jaxpr)
        kept = trace_mirror_kept(
            lambda a, aux, _r, _t: ([kept_block(a["q"], a["k"], a["v"])],
                                    aux),
            {"q": q, "k": k, "v": v}, {}, ("q", "k", "v"))
        want = jax.grad(block, argnums=(0, 1, 2))(q, k, v)
        got = jax.grad(kept_block, argnums=(0, 1, 2))(q, k, v)
    assert plain_calls == {"flash_forward": 1, "flash_backward": 1}
    assert kept_calls == plain_calls, kept_calls
    whole = 2 * 2 * 128 * 16 * 4
    assert sorted(kept) == sorted(
        [("flash_q", whole), ("flash_k", whole), ("flash_v", whole),
         ("flash_out", whole), ("flash_lse", 2 * 2 * 128 * 4)]), kept
    for name, a, b in zip("qkv", want, got):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=1e-6,
                                   atol=1e-7, err_msg="d" + name)
