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
# What a mirrored segment keeps, by the names their producer gives them
# (executor.KEPT): the flash kernel's operands, output and softmax
# statistics; what the delta rule's forward sweep hands on; the routed
# layer's choices, sorted order and, where a backward reads it, its sum
# ----------------------------------------------------------------------
def _equations(jaxpr):
    """Every equation of ``jaxpr``, however deep (checkpoints, conds, jits
    and custom_vjps hold jaxprs of their own)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (tuple, list))
                        else (value,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _equations(sub)


def _kernel_calls(jaxpr):
    """{kernel name: pallas_call equations} of ``jaxpr``."""
    found = {}
    for eqn in _equations(jaxpr):
        if eqn.primitive.name == "pallas_call":
            name = eqn.params["name"]
            found[name] = found.get(name, 0) + 1
    return found


def _count(jaxpr, primitive):
    """Equations of ``primitive`` in ``jaxpr``."""
    return sum(eqn.primitive.name == primitive for eqn in _equations(jaxpr))


def _kept_by_name(fn, leaves):
    """``trace_mirror_kept`` of ``fn(*leaves)``, every leaf differentiated."""
    from mxnet_tpu.executor import trace_mirror_kept
    names = ["leaf%d" % i for i in range(len(leaves))]
    return trace_mirror_kept(
        lambda a, aux, _rng, _train: ([fn(*[a[n] for n in names])], aux),
        dict(zip(names, leaves)), {}, tuple(names))


def _seeded_state(net, shapes, ids=True):
    """``(args, aux)`` of ``net`` bound to ``shapes`` (data and labels):
    small seeded weights, token ids below 64 where ``ids``, auxiliary
    state 0 in its own types."""
    import jax.numpy as jnp
    arg_shapes, _, aux_shapes = net.infer_shape(**shapes)
    _, _, aux_types = net.infer_type()
    rs = np.random.RandomState(0)
    args = {n: jnp.asarray((rs.rand(*s).astype(np.float32) - 0.5) * 0.2)
            for n, s in zip(net.list_arguments(), arg_shapes)}
    if ids:
        for n in shapes:
            args[n] = jnp.asarray(rs.randint(0, 64, shapes[n])
                                  .astype(np.float32))
    aux = {n: jnp.zeros(s, t) for n, s, t in zip(
        net.list_auxiliary_states(), aux_shapes, aux_types)}
    return args, aux


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
    from mxnet_tpu.kernels import delta_rule
    from mxnet_tpu.ops import moe
    from mxnet_tpu.parallel import ring_attention
    assert executor.KEPT == (ring_attention.FLASH_RESIDUALS
                             + delta_rule.DELTA_RESIDUALS
                             + delta_rule.KDA_RESIDUALS
                             + moe.ROUTED_RESIDUALS)
    assert executor.KEPT == (
        "flash_q", "flash_k", "flash_v", "flash_out", "flash_lse",
        "delta_q", "delta_k", "delta_v", "delta_scalars", "delta_out",
        "delta_states",
        "kda_q", "kda_k", "kda_v", "kda_gates", "kda_beta", "kda_out",
        "kda_states",
        "routed_idx", "routed_w", "routed_order", "routed_counts",
        "routed_out")
    assert len(set(executor.KEPT)) == len(executor.KEPT)


def test_counting_what_is_kept_first_leaks_no_tracer(monkeypatch):
    """``trace_mirror_kept`` traces, and may be the process's first user of
    the executor's zero key: the key it leaves behind is an array."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import executor
    monkeypatch.setattr(executor, "_ZERO_KEY", None)
    assert _kept_by_name(lambda x: jnp.sum(jnp.sin(x)), [jnp.ones(4)]) == []
    assert not isinstance(executor._zero_key(), jax.core.Tracer)
    jax.jit(lambda x: x + executor._zero_key()[0])(jnp.ones(2))


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


@pytest.mark.parametrize("which", ["gpt2", "resnet", "hybrid"])
def test_a_step_with_no_mirrored_segment_lowers_to_the_parents_text(
        which, monkeypatch):
    """``gpt2m_train_s1024``'s and ``resnet50_fit_b256``'s shapes of step
    (no mirrored segment; no named producer at all in ResNet), and an
    unmirrored hybrid that holds all three producers: the gradient
    program's text with the names in the source is the text with every
    ``checkpoint_name`` taken out."""
    import re
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.executor import _build_program, _zero_key
    from mxnet_tpu.kernels import common, delta_rule
    from mxnet_tpu.models import resnet
    from mxnet_tpu.ops import moe
    from mxnet_tpu.parallel import ring_attention
    monkeypatch.setattr(
        common, "dispatch",
        lambda kernel, _reference, *args: kernel(*args, interpret=True))
    if which == "gpt2":
        net = mx.models.transformer.get_symbol(
            vocab_size=64, num_layers=2, num_heads=2, dim=32, seq_len=128)
        shapes = dict(data=(1, 128), softmax_label=(1, 128))
    elif which == "resnet":
        net = resnet.get_symbol(num_classes=10, num_layers=18,
                                image_shape=(3, 32, 32))
        shapes = dict(data=(2, 3, 32, 32), softmax_label=(2,))
    else:
        net = _routed_block_model("hybrid", False)[0]
        shapes = dict(data=(1, 128), softmax_label=(1, 128))
    args, aux = _seeded_state(net, shapes, ids=which != "resnet")
    w = {n: args.pop(n) for n in list(args) if n not in shapes}

    def text(names):
        prog = _build_program(net, {})
        assert not prog.mirrored

        def loss(w, batch, aux):
            outs, _aux = prog.trace(dict(batch, **w), aux, _zero_key(), True)
            return sum(jnp.sum(o * o) for o in outs)
        grad = jax.grad(loss)
        assert bool(_count(jax.make_jaxpr(grad)(w, args, aux).jaxpr,
                           "name")) == names
        return re.sub(r"@(\w+?)_\d+\b", r"@\1",
                      jax.jit(grad).lower(w, args, aux).as_text())
    named = text(which != "resnet")
    for module in (ring_attention, delta_rule, moe):
        monkeypatch.setattr(module, "checkpoint_name", lambda x, _name: x)
    assert text(False) == named


def _named(names, sizes, times=1):
    return list(zip(names, sizes)) * times


def _routed_block_model(which, mirror):
    """A small model of each routed family, and what its mirrored blocks
    keep: (net, labels, flash calls, delta-rule layers, [(name, bytes)])."""
    from mxnet_tpu.kernels.delta_rule import DELTA_RESIDUALS
    from mxnet_tpu.models import (transformer_cca_moe, transformer_hybrid_moe,
                                  transformer_mla_moe)
    from mxnet_tpu.ops.moe import ROUTED_RESIDUALS
    from mxnet_tpu.parallel.ring_attention import FLASH_RESIDUALS
    tokens = 128
    if which == "mla":          # JoyAI-shaped
        net = transformer_mla_moe.get_symbol(
            vocab_size=64, num_layers=2, dim=32, seq_len=tokens, num_heads=2,
            q_lora_rank=16, kv_lora_rank=8, qk_nope_head_dim=8,
            qk_rope_head_dim=8, v_head_dim=8, intermediate_size=64,
            moe_intermediate_size=16, n_routed_experts=4,
            num_experts_per_tok=2, mirror_blocks=mirror)
        labels = dict(softmax_label=(1, tokens), mtp_label=(1, tokens))
        # two layers and the prediction module's: three calls of the
        # kernel; q and k (1, 2, 128, 16), v and the output (1, 2, 128, 8),
        # lse (1, 2, 128).  Two routed layers of two choices a token over
        # four experts: the choice, its weights and the sorted order
        # (128 × 2) × 4 bytes, the counts 4 × 4; the sum meets an add and
        # is not kept
        kept = _named(FLASH_RESIDUALS,
                      (2 * tokens * 16 * 4, 2 * tokens * 16 * 4,
                       2 * tokens * 8 * 4, 2 * tokens * 8 * 4,
                       2 * tokens * 4), 3) \
            + _named(ROUTED_RESIDUALS[:4], (tokens * 2 * 4,) * 3 + (16,), 2)
        return net, labels, 3, 0, kept
    if which == "hybrid":       # Qwen3-Next-shaped
        net = transformer_hybrid_moe.get_symbol(
            vocab_size=64, num_layers=2, dim=32, seq_len=tokens,
            full_attention_interval=2, num_heads=2, num_kv_heads=1,
            head_dim=16, linear_num_key_heads=1, linear_num_value_heads=2,
            linear_key_head_dim=128, linear_value_head_dim=128,
            delta_chunk=16, moe_intermediate_size=16,
            shared_expert_intermediate_size=16, num_experts=4,
            num_experts_per_tok=2, mirror_blocks=mirror)
        # one delta-rule layer, one key head on two value heads of 128,
        # eight chunks of 16: q and k 128 × 128, v and o 128 × 2 × 128, the
        # scalars 2 × 2 × 128, the chunk states 2 × 8 × 128 × 128, all
        # float32 here; one attention layer, two query heads on one
        # key/value head of 16; two routed layers whose sum meets an add
        kept = _named(DELTA_RESIDUALS,
                      (tokens * 128 * 4, tokens * 128 * 4,
                       tokens * 2 * 128 * 4, 2 * 2 * tokens * 4,
                       tokens * 2 * 128 * 4, 2 * 8 * 128 * 128 * 4)) \
            + _named(FLASH_RESIDUALS,
                     (2 * tokens * 16 * 4, tokens * 16 * 4, tokens * 16 * 4,
                      2 * tokens * 16 * 4, 2 * tokens * 4)) \
            + _named(ROUTED_RESIDUALS[:4], (tokens * 2 * 4,) * 3 + (16,), 2)
        return net, dict(softmax_label=(1, tokens)), 1, 1, kept
    net = transformer_cca_moe.get_symbol(    # ZAYA1-shaped
        vocab_size=64, num_layers=2, dim=32, seq_len=tokens, num_heads=4,
        num_kv_heads=2, head_dim=8, moe_intermediate_size=16,
        num_experts=4, router_hidden_size=8, mirror_blocks=mirror)
    # four query heads on two key/value heads of 8; one choice a token, and
    # the routed sum (128 × 32) is kept: a learned scale reads it
    kept = _named(FLASH_RESIDUALS,
                  (4 * tokens * 8 * 4, 2 * tokens * 8 * 4, 2 * tokens * 8 * 4,
                   4 * tokens * 8 * 4, 4 * tokens * 4), 2) \
        + _named(ROUTED_RESIDUALS,
                 (tokens * 4,) * 3 + (16, tokens * 32 * 4), 2)
    return net, dict(softmax_label=(1, tokens)), 2, 0, kept


@pytest.mark.parametrize("which", ["mla", "cca", "hybrid"])
def test_build_program_keeps_the_kernels_output_in_a_mirrored_block(
        which, monkeypatch):
    """Through ``_build_program``, the dispatch forced to the interpreted
    kernels: a small latent-attention model (JoyAI's shape), a small
    convolution-mixed one (ZAYA1's) and a small hybrid of the delta rule
    and attention (Qwen3-Next's), ``mirror_blocks=True``.  Each kernel
    once a layer, the grouped products as often as unmirrored, and
    ``mirror_kept`` lists exactly what the blocks hold by name."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.executor import _build_program, _zero_key
    from mxnet_tpu.kernels import common
    monkeypatch.setattr(
        common, "dispatch",
        lambda kernel, _reference, *args: kernel(*args, interpret=True))

    def build(mirror):
        net, labels, flash, delta, kept = _routed_block_model(which, mirror)
        prog = _build_program(net, {})
        args, aux = _seeded_state(net, dict(labels, data=(1, 128)))
        wrt = tuple(n for n in args if n != "data" and n not in labels)

        def loss(w):
            outs, _aux = prog.trace(dict(args, **w), aux, _zero_key(), True)
            return sum(jnp.sum(o * o) for o in outs)
        return prog, args, aux, wrt, loss, flash, delta, kept

    prog, args, aux, wrt, loss, flash, delta, kept = build(True)
    w = {n: args[n] for n in wrt}
    assert prog.mirrored
    program = jax.make_jaxpr(jax.grad(loss))(w).jaxpr
    calls = {"flash_forward": flash, "flash_backward": flash}
    if delta:
        calls.update(gated_delta_forward=delta, gated_delta_backward=delta)
    assert _kernel_calls(program) == calls
    assert sorted(prog.mirror_kept(args, aux, wrt)) == sorted(kept)

    plain, p_args, p_aux, _wrt, p_loss = build(False)[:5]
    assert not plain.mirrored and plain.mirror_kept(p_args, p_aux, wrt) == []
    # no grouped product, sort or top-k is made a second time
    unmirrored = jax.make_jaxpr(jax.grad(p_loss))(w).jaxpr
    for primitive in ("ragged_dot_general", "sort", "top_k"):
        assert _count(program, primitive) == _count(unmirrored, primitive) \
            > 0, primitive
    want = jax.grad(p_loss)(w)
    got = jax.grad(loss)(w)
    for n in wrt:
        np.testing.assert_allclose(np.asarray(got[n]), np.asarray(want[n]),
                                   rtol=2e-4, atol=1e-6, err_msg=n)


# -- the routed layer alone: what follows it decides whether its sum is kept
def _routed_block(ends):
    """``(block, leaves)``: something before a ``RoutedExperts`` layer with
    a shared expert, the layer, and a learned scale or a plain add after it
    (ZAYA1's block end, and JoyAI's and Qwen3-Next's)."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.registry import create_operator
    op = create_operator("RoutedExperts", num_experts=8, hidden_size=16,
                         top_k=2, shared_hidden_size=16,
                         score_func="softmax")
    shapes, _, aux_shapes = op.infer_shape([(64, 32)] + [None] * 7)
    keys = jax.random.split(jax.random.PRNGKey(3), len(shapes) + 1)
    leaves = [0.3 * jax.random.normal(key, shape, dtype=jnp.float32)
              for key, shape in zip(keys, shapes + [(32,)])]
    aux = [jnp.zeros(shape, jnp.float32 if i == 0 else jnp.int32)
           for i, shape in enumerate(aux_shapes)]

    def block(*leaves):
        x = jnp.tanh(leaves[0])
        f = op.forward([x] + list(leaves[1:-1]), aux, True, None)[0][0]
        return x + (f * leaves[-1] if ends == "scale" else f + leaves[-1])
    return block, leaves


@pytest.mark.parametrize("ends", ["scale", "add"])
def test_mirrored_routed_block_walks_its_chunks_as_often_as_unwrapped(ends):
    """A routed block under ``mirror_checkpoint`` runs ``ragged_dot``, the
    sort and the top-k as often as the unwrapped block; a bare
    ``jax.checkpoint`` makes the sort and the top-k again and, where a
    learned scale reads the routed sum, the grouped products of one more
    forward walk.  The sum is kept only there; the gradients are the
    unwrapped block's."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.executor import mirror_checkpoint
    block, leaves = _routed_block(ends)
    wrt = tuple(range(len(leaves)))

    def loss_of(wrap):
        return lambda *a: jnp.sum(jnp.sin(wrap(block)(*a)))

    def counts(wrap):
        jaxpr = jax.make_jaxpr(jax.grad(loss_of(wrap), wrt))(*leaves).jaxpr
        return {p: _count(jaxpr, p)
                for p in ("ragged_dot_general", "sort", "top_k")}
    plain = counts(lambda fn: fn)
    # forward 3 products, backward 3 again and 2 each for their vjp
    assert plain == {"ragged_dot_general": 12, "sort": 2, "top_k": 1}
    assert counts(mirror_checkpoint) == plain
    bare = counts(jax.checkpoint)
    assert bare["sort"] == 3 and bare["top_k"] == 2
    assert bare["ragged_dot_general"] == (15 if ends == "scale" else 12)

    def kept(wrap):
        return sorted(_kept_by_name(loss_of(wrap), leaves))
    slots = 64 * 2 * 4
    want = [("routed_counts", 8 * 4), ("routed_idx", slots),
            ("routed_order", slots), ("routed_w", slots)]
    if ends == "scale":
        want.append(("routed_out", 64 * 32 * 4))
    assert kept(mirror_checkpoint) == sorted(want)
    assert kept(jax.checkpoint) == []

    got = jax.grad(loss_of(mirror_checkpoint), wrt)(*leaves)
    for a, b in zip(got, jax.grad(loss_of(lambda fn: fn), wrt)(*leaves)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6,
                                   atol=1e-7)


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
