"""The gated delta rule's Pallas kernels (``kernels/delta_rule.py``) through
the interpreter on the CPU: outputs and all five gradients against the
recurrence a token at a time and against the XLA form, in float32 and
bfloat16, two value heads a key head, the state crossing chunks and grid
steps; any decay; shapes the kernels refuse and the VMEM they ask for; the
in-kernel triangular inverse; the kernel calls a ``GatedDeltaNet`` block's
gradient program holds — plain, under ``jax.checkpoint`` and under the
executor's mirrored checkpoint; and the block lowered for a TPU on a mesh
of four devices, where the kernels run per device under ``shard_map``."""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (ROOT, os.path.dirname(os.path.abspath(__file__))):
    if path not in sys.path:
        sys.path.insert(0, path)

import mxnet_tpu as mx                                      # noqa: E402,F401
from mxnet_tpu.kernels import delta_rule as dr              # noqa: E402
from mxnet_tpu.ops import linear_attention as la            # noqa: E402
from mxnet_tpu.ops.registry import create_operator          # noqa: E402

F32 = jnp.float32
WRT = (0, 1, 2, 3, 4)
NAMES = ("q", "k", "v", "g", "beta")


def _inputs(seed, S, dtype, B=1, Hk=1, Hv=2, d=128, decay=(-7.0, 0.5)):
    """q, k of ``Hk`` heads, v, g, β of ``Hv``; keys that lean one way, so
    that neighbours' products are far from 0; decays a token from e^-7 to
    e^0.5 unless told otherwise.  And a weight for the loss."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = la.l2_normalise(jax.random.normal(ks[0], (B, S, Hk, d), dtype=F32)) \
        * d ** -0.5
    k = la.l2_normalise(jax.random.normal(ks[1], (B, S, Hk, d), dtype=F32)
                        + 0.3)
    v = jax.random.normal(ks[2], (B, S, Hv, d), dtype=F32)
    g = -jnp.exp(jax.random.uniform(ks[3], (B, S, Hv), F32, *decay))
    beta = jax.nn.sigmoid(2.0 * jax.random.normal(ks[4], (B, S, Hv),
                                                  dtype=F32))
    w = jax.random.normal(ks[5], (B, S, Hv, d), dtype=F32)
    return (q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta), w


def _recurrent(q, k, v, g, beta):
    r = v.shape[2] // q.shape[2]
    q, k = (jnp.repeat(t, r, axis=2) for t in (q, k))
    return la.gated_delta_rule_recurrent(q, k, v, g, beta)


def _err(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.abs(a - b).max() / max(np.abs(b).max(), 1e-30)


def _value_and_grads(fn, args, w):
    return jax.value_and_grad(
        lambda *a: jnp.sum(fn(*a).astype(F32) * w), argnums=WRT)(*args)


# (chunk, tokens): two programs of eight chunks of 64 a head; three of
# eight chunks of 32 — the state crosses chunks and grid steps in both, and
# T is merged from its diagonal blocks twice and once
SHAPES = [(64, 1024), (32, 768)]


@pytest.mark.parametrize("chunk,seq", SHAPES)
@pytest.mark.parametrize("dtype,tol_xla,tol_rec", [
    (jnp.float32, 2e-6, 5e-6), (jnp.bfloat16, 2e-2, 2e-2)],
    ids=["float32", "bfloat16"])
def test_kernels_are_the_rule(chunk, seq, dtype, tol_xla, tol_rec):
    """Outputs and the gradient of every input, two value heads on one key
    head (dq and dk summed over the group), against the XLA form (whose
    rounding points the kernels share: float32 agrees to rounding, bfloat16
    to a step or two of the dtype) and against the recurrence."""
    assert dr.delta_blocks(seq, chunk, 128, 128) == (
        512 if chunk == 64 else 256, chunk)
    args, w = _inputs(0, seq, dtype)
    forms = {
        "kernel": lambda *a: la.gated_delta_rule(*a, chunk=chunk,
                                                 interpret=True),
        "xla": lambda *a: la.gated_delta_rule(*a, chunk=chunk),
        "recurrence": _recurrent}
    out = {n: fn(*args) for n, fn in forms.items()}
    assert out["kernel"].shape == out["xla"].shape == (1, seq, 2, 128)
    assert out["kernel"].dtype == dtype
    assert _err(out["kernel"], out["xla"]) <= tol_xla
    assert _err(out["kernel"], out["recurrence"]) <= tol_rec
    grads = {n: _value_and_grads(fn, args, w)[1] for n, fn in forms.items()}
    for i, name in enumerate(NAMES):
        got = grads["kernel"][i]
        assert got.dtype == args[i].dtype and got.shape == args[i].shape
        assert float(jnp.abs(grads["recurrence"][i]).max()) > 0, name
        assert _err(got, grads["xla"][i]) <= tol_xla, name
        assert _err(got, grads["recurrence"][i]) <= tol_rec, name


@pytest.mark.parametrize("decay", [-1e-3, -1.0, -50.0])
def test_kernels_survive_any_decay(decay):
    """From a state that hardly forgets to one that is gone within a token:
    no NaN, no inf, and still the recurrence — no exp takes a positive
    argument in either kernel."""
    args, w = _inputs(2, 256, F32)
    args = args[:3] + (jnp.full(args[3].shape, decay, F32),) + args[4:]
    out, grads = _value_and_grads(
        lambda *a: la.gated_delta_rule(*a, chunk=32, interpret=True),
        args, w)
    assert np.isfinite(float(out))
    assert all(bool(jnp.isfinite(t).all()) for t in grads)
    want, want_grads = _value_and_grads(_recurrent, args, w)
    assert abs(float(out) - float(want)) <= 1e-4 * abs(float(want))
    for name, got, ref in zip(NAMES, grads, want_grads):
        # at e^-50 a token what reaches g is under 1e-20: nothing to compare
        if name != "g" or float(jnp.abs(ref).max()) > 1e-12:
            assert _err(got, ref) <= 2e-5, name


@pytest.mark.parametrize("seq,chunk,d_k,d_v,group,why", [
    (1024, 48, 128, 128, 1, "a chunk that is no power of two"),
    (1024, 8, 128, 128, 1, "a chunk under a bfloat16 tile's rows"),
    (1024 + 64, 64, 128, 128, 1, "no block of eight chunks divides it"),
    (1024, 64, 64, 128, 1, "keys narrower than a register's lanes"),
    (1024, 64, 128, 192, 1, "values that are no whole registers"),
    (8192, 64, 1024, 1024, 8, "a group's chunk states past all of VMEM"),
])
def test_shapes_the_kernels_refuse(seq, chunk, d_k, d_v, group, why):
    assert dr.delta_blocks(seq, chunk, d_k, d_v, group) is None, why


def test_shapes_the_kernels_take():
    assert dr.delta_blocks(8192, 64, 128, 128, 2) == (512, 64)
    assert dr.delta_blocks(4096, 16, 128, 256) == (512, 16)
    # shorter than a block: the whole sequence, one program a head
    assert dr.delta_blocks(384, 64, 128, 128) == (384, 64)
    assert dr.delta_blocks(32, 64, 128, 128) == (32, 32)


def _backward_need(block, C, d, group, itemsize=2):
    return dr._vmem_need(
        dr._backward_layout(1, block, 1, group, d, d, block, C),
        dr._BACKWARD_SIZES, itemsize, block, C, d, d, group)


def test_vmem_request_comes_from_the_shapes(monkeypatch):
    """The timed shape fits what Mosaic gives unasked and asks for nothing
    (it lowers as it did without a limit); heads of 512, which the chip's
    compiler refuses unasked (PERF.md, PR 34), ask for their working set;
    a budget that the largest block passes takes a smaller one where the
    scalars' tiles allow one, and the reference where none is left."""
    from mxnet_tpu.parallel import ring_attention as ra
    unasked = ra._SCOPED_VMEM_DEFAULT - (1 << 20)
    timed = _backward_need(512, 64, 128, 2)
    assert timed <= unasked
    assert dr._compiler_params(timed).vmem_limit_bytes is None
    wide = _backward_need(512, 64, 512, 2)
    assert unasked < wide <= ra._VMEM_BUDGET
    assert dr._compiler_params(wide).vmem_limit_bytes == wide
    assert dr.delta_blocks(8192, 64, 512, 512, 2) == (512, 64)
    # 16 chunks a block of 256, 8 a block of 128: both are whole tiles
    for block in (512, 256, 128):
        monkeypatch.setattr(ra, "_VMEM_BUDGET", _backward_need(block, 16,
                                                               128, 2))
        assert dr.delta_blocks(8192, 16, 128, 128, 2) == (block, 16)
    monkeypatch.setattr(ra, "_VMEM_BUDGET", _backward_need(128, 16, 128, 2)
                        - 1)
    assert dr.delta_blocks(8192, 16, 128, 128, 2) is None


def test_a_refused_shape_falls_to_the_reference(monkeypatch):
    """Heads of 16 and 8: ``interpret=True`` or not, the XLA form answers,
    to the bit, and no kernel is built."""
    def no_kernel(*_a, **_k):
        raise AssertionError("the kernel was asked")
    monkeypatch.setattr(dr, "gated_delta_rule_kernel", no_kernel)
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    q, k = (la.l2_normalise(jax.random.normal(key, (2, 64, 2, 16),
                                              dtype=F32)) for key in ks[:2])
    v = jax.random.normal(ks[2], (2, 64, 4, 8), dtype=F32)
    g = -jnp.exp(jax.random.uniform(ks[3], (2, 64, 4), F32, -4, 1))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (2, 64, 4), dtype=F32))
    want = la.gated_delta_rule_xla(q, k, v, g, beta, 16)
    for interpret in (None, True):
        got = la.gated_delta_rule(q, k, v, g, beta, chunk=16,
                                  interpret=interpret)
        assert np.array_equal(np.asarray(got), np.asarray(want))
    with pytest.raises(ValueError):
        la.gated_delta_rule(q[:, :, :1].repeat(3, axis=2), k, v, g, beta)


def test_the_reference_repeats_the_key_heads_itself():
    """q and k of H_k heads give what the repeated ones give, to the bit:
    the op hands the rule its key heads once."""
    args, _w = _inputs(4, 128, F32, Hk=2, Hv=4)
    q, k = (jnp.repeat(t, 2, axis=2) for t in args[:2])
    assert np.array_equal(
        np.asarray(la.gated_delta_rule(*args, chunk=32)),
        np.asarray(la.gated_delta_rule(q, k, *args[2:], chunk=32)))


@pytest.mark.parametrize("size", [16, 32, 64])
def test_inverse_inside_the_kernel(size):
    """Substitution in the diagonal blocks and the merges on whole tiles
    invert I + tril(a, −1) as ``unit_lower_inverse`` does, read nothing on
    or above the diagonal, and stay exact where a Neumann series would
    not: every entry below the diagonal 0.9."""
    a = 0.3 * jax.random.normal(jax.random.PRNGKey(size), (size, size),
                                dtype=F32)
    for m in (a, jnp.full((size, size), 0.9, F32)):
        with jax.default_matmul_precision("highest"):
            inv = dr._unit_lower_inverse(m, size)
            back = inv @ (jnp.eye(size, dtype=F32) + jnp.tril(m, -1))
        assert not np.asarray(jnp.triu(inv, 1)).any()
        assert _err(back, jnp.eye(size)) <= 1e-4
        assert _err(inv, la.unit_lower_inverse(m)) <= 1e-4


def test_chunk_scalars_are_the_running_sums():
    g = -jnp.arange(1.0, 9.0, dtype=F32).reshape(1, 8, 1) * jnp.ones((1, 1, 2))
    beta = jnp.full((1, 8, 2), 0.5, F32)
    gb = dr._chunk_scalars(g, beta, 4)
    assert gb.shape == (1, 2, 2, 2, 4)
    assert np.allclose(gb[0, 1, 0], [[-1, -3, -6, -10], [-5, -11, -18, -26]])
    assert np.allclose(gb[0, 0, 1], 0.5)


# -- the op's block: which kernels its gradient program holds -----------------
def _delta_net(chunk=16, batch=1, key_heads=1):
    """A ``GatedDeltaNet`` operator and seeded leaves for it."""
    op = create_operator(
        "GatedDeltaNet", num_key_heads=key_heads,
        num_value_heads=2 * key_heads, key_head_dim=128, value_head_dim=128,
        conv_taps=4, chunk=chunk, eps=1e-6)
    shapes = op.infer_shape([(batch, 128, 32)] + [None] * 7)[0]
    keys = jax.random.split(jax.random.PRNGKey(7), len(shapes))
    return op, [0.3 * jax.random.normal(key, s, dtype=F32)
                for key, s in zip(keys, shapes)]


def _block(chunk=16, batch=1, key_heads=1):
    op, leaves = _delta_net(chunk, batch, key_heads)

    def block(*leaves):
        return jnp.sum(jnp.sin(op.forward(list(leaves), [], True,
                                          None)[0][0]))
    return block, leaves


@pytest.mark.parametrize("wrap,calls", [
    ("plain", {"gated_delta_forward": 1, "gated_delta_backward": 1}),
    ("checkpoint", {"gated_delta_forward": 2, "gated_delta_backward": 1}),
    ("mirror", {"gated_delta_forward": 1, "gated_delta_backward": 1}),
])
def test_gradient_program_of_a_delta_block(monkeypatch, wrap, calls):
    """The forward kernel once where nothing recomputes (the ``fwd`` rule's
    sweep, which also writes the chunk states) and once under the executor's
    mirrored checkpoint, which keeps what the sweep hands on by name
    (``DELTA_RESIDUALS``, whole: the output too, which the gated norm after
    the rule reads); twice under a bare checkpoint; the backward kernel
    once.  The mirrored block's gradients are the plain block's."""
    from mxnet_tpu import executor
    from mxnet_tpu.kernels import common
    from test_mirror import _kept_by_name, _kernel_calls
    monkeypatch.setattr(
        common, "dispatch",
        lambda kernel, _reference, *args: kernel(*args, interpret=True))
    block, leaves = _block()
    fn = {"plain": block, "checkpoint": jax.checkpoint(block),
          "mirror": executor.mirror_checkpoint(block)}[wrap]
    wrt = tuple(range(len(leaves)))
    found = _kernel_calls(jax.make_jaxpr(jax.grad(fn, wrt))(*leaves).jaxpr)
    assert found == calls, found
    assert set(dr.DELTA_RESIDUALS) <= set(executor.KEPT)
    kept = _kept_by_name(fn, leaves)
    if wrap == "checkpoint":
        assert kept == []
    else:
        # one key head on two value heads of 128, 128 tokens in 8 chunks of
        # 16, float32 (unwrapped, a named value is saved like any other)
        assert sorted(kept) == sorted(zip(dr.DELTA_RESIDUALS, (
            128 * 128 * 4, 128 * 128 * 4, 128 * 2 * 128 * 4,
            2 * 2 * 128 * 4, 128 * 2 * 128 * 4, 2 * 8 * 128 * 128 * 4)))
    if wrap == "mirror":
        got = jax.grad(fn, wrt)(*leaves)
        want = jax.grad(block, wrt)(*leaves)
        for a, b in zip(got, want):
            assert _err(a, b) <= 1e-5


def test_block_through_the_kernels_is_the_block_through_the_reference(
        monkeypatch):
    """``GatedDeltaNet`` hands the rule its key heads once; with the
    kernels in ``dispatch``'s place the block's output and every leaf's
    gradient are the reference path's."""
    from mxnet_tpu.kernels import common
    block, leaves = _block(chunk=32)
    wrt = tuple(range(len(leaves)))
    want = jax.value_and_grad(block, wrt)(*leaves)
    monkeypatch.setattr(
        common, "dispatch",
        lambda kernel, _reference, *args: kernel(*args, interpret=True))
    got = jax.value_and_grad(block, wrt)(*leaves)
    assert abs(float(got[0]) - float(want[0])) <= 1e-5 * abs(float(want[0]))
    for a, b in zip(got[1], want[1]):
        assert _err(a, b) <= 2e-5


# -- more than one chip: GSPMD does not partition a Mosaic kernel -------------
MOSAIC = "tpu_custom_call"


@pytest.mark.parametrize("axes,shape,seq_axis,mosaic_calls", [
    (("dp",), (4,), None, 3),
    (("dp", "tp"), (2, 2), None, 3),
    (("dp", "ep"), (2, 2), None, 3),
    (("dp", "sp"), (2, 2), 1, 0),
])
def test_block_on_a_mesh_lowers_for_a_tpu(axes, shape, seq_axis,
                                          mosaic_calls):
    """A ``GatedDeltaNet`` block's value and gradient, checkpointed as the
    model's blocks are, lowered for a TPU over four devices as ``ShardedTrainer``
    lowers its step (under ``attention_scope``): the kernels per device
    under ``shard_map`` — two forward calls and the backward; a bare Mosaic
    call there ends the lowering with "Mosaic kernels cannot be
    automatically partitioned" — and, where the mesh shards the sequence,
    the XLA form and no kernel."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from mxnet_tpu.parallel.ring_attention import attention_scope
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(shape), axes)
    block, leaves = _block(batch=4, key_heads=2)
    data = NamedSharding(mesh, P("dp"))
    args = [jax.ShapeDtypeStruct(t.shape, t.dtype,
                                 sharding=data if i == 0
                                 else NamedSharding(mesh, P()))
            for i, t in enumerate(leaves)]
    wrt = tuple(range(len(leaves)))
    # as the chip runs: the suite's 64-bit mode is no mode Mosaic lowers in
    with jax.enable_x64(False), attention_scope(mesh, seq_axis):
        step = jax.value_and_grad(jax.checkpoint(block), wrt)
        jaxpr = jax.make_jaxpr(step)(*leaves)
        text = jax.jit(step).trace(*args).lower(
            lowering_platforms=("tpu",)).as_text()
    assert text.count(MOSAIC) == mosaic_calls
    assert ("shard_map" in str(jaxpr)) == bool(mosaic_calls)


def _hybrid_block(batch=4, key_heads=2):
    """A delta-rule mixer and a routed layer with their residual adds, as a
    hybrid model's block has them: ``(block, leaves, expert leaves)``."""
    gdn, leaves = _delta_net(batch=batch, key_heads=key_heads)
    moe = create_operator("RoutedExperts", num_experts=4, hidden_size=16,
                          top_k=2, score_func="softmax")
    shapes, _, aux_shapes = moe.infer_shape([(batch * 128, 32)] + [None] * 4)
    keys = jax.random.split(jax.random.PRNGKey(11), len(shapes))
    routed = [0.3 * jax.random.normal(key, shape, dtype=F32)
              for key, shape in zip(keys[1:], shapes[1:])]
    aux = [jnp.zeros(shape, F32 if i == 0 else jnp.int32)
           for i, shape in enumerate(aux_shapes)]
    n = len(leaves)

    def block(*args):
        x = args[0]
        x = x + gdn.forward(list(args[:n]), [], True, None)[0][0]
        f = moe.forward([x.reshape(-1, 32)] + list(args[n:]), aux, True,
                        None)[0][0]
        return x + f.reshape(x.shape)
    return block, leaves + routed, range(n + 1, n + len(routed))


@pytest.mark.parametrize("axes,shape,seq_axis", [
    (("dp",), (4,), None),
    (("dp", "tp"), (2, 2), None),
    (("dp", "ep"), (2, 2), None),
    (("dp", "sp"), (2, 2), 1),
])
def test_mirrored_hybrid_block_on_a_mesh_keeps_the_names(axes, shape,
                                                         seq_axis):
    """The block under the executor's mirrored checkpoint, lowered for a
    TPU over four devices as ``ShardedTrainer`` lowers its step: the rule's
    names are found inside ``shard_map`` (each at its global size) and the
    forward kernel is called once, not twice; the routed layer's names pass
    through GSPMD, the experts sharded over ``ep`` where the mesh has it.
    Where the mesh shards the sequence the rule is the XLA form, which
    names nothing: it has no second kernel call to save."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from mxnet_tpu.executor import mirror_checkpoint
    from mxnet_tpu.parallel.ring_attention import attention_scope
    from test_mirror import _kept_by_name
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(shape), axes)
    block, leaves, experts = _hybrid_block()
    kept_block = mirror_checkpoint(block)

    def loss(*args):
        return jnp.sum(jnp.sin(kept_block(*args)))

    def sharding(i):
        if i == 0:
            return NamedSharding(mesh, P("dp"))
        if i in experts and "ep" in axes:
            return NamedSharding(mesh, P("ep"))
        return NamedSharding(mesh, P())
    args = [jax.ShapeDtypeStruct(t.shape, t.dtype, sharding=sharding(i))
            for i, t in enumerate(leaves)]
    wrt = tuple(range(len(leaves)))
    with jax.enable_x64(False), attention_scope(mesh, seq_axis):
        step = jax.value_and_grad(loss, wrt)
        jaxpr = jax.make_jaxpr(step)(*leaves)
        text = jax.jit(step).trace(*args).lower(
            lowering_platforms=("tpu",)).as_text()
        kept = _kept_by_name(loss, leaves)
    slots = 4 * 128 * 2 * 4
    routed = [("routed_counts", 4 * 4), ("routed_idx", slots),
              ("routed_order", slots), ("routed_w", slots)]
    if seq_axis is not None:
        assert text.count(MOSAIC) == 0 and "shard_map" not in str(jaxpr)
        assert sorted(kept) == routed
        return
    assert text.count(MOSAIC) == 2              # one forward, one backward
    assert "shard_map" in str(jaxpr)
    # two key heads on four value heads of 128.  A residual leaves
    # ``shard_map`` laid over every mesh axis: over ``ep`` the rule is
    # replicated, and both members' copies are counted
    tokens = 4 * 128 * (2 if "ep" in axes else 1)
    assert sorted(kept) == sorted(routed + list(zip(dr.DELTA_RESIDUALS, (
        tokens * 2 * 128 * 4, tokens * 2 * 128 * 4, tokens * 4 * 128 * 4,
        tokens * 4 * 2 * 4, tokens * 4 * 128 * 4,
        tokens // 16 * 4 * 128 * 128 * 4))))


def test_block_on_a_mesh_is_the_block_on_one_device():
    """Batch over dp and key heads over tp, each device with its own block
    of both (the reference inside ``shard_map`` here, as the kernels are on
    the chip): the output and every leaf's gradient are one device's."""
    from jax.sharding import Mesh
    from mxnet_tpu.parallel.ring_attention import attention_scope
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "tp"))
    block, leaves = _block(batch=4, key_heads=2)
    wrt = tuple(range(len(leaves)))
    want = jax.jit(jax.value_and_grad(block, wrt))(*leaves)
    with attention_scope(mesh):
        got = jax.jit(jax.value_and_grad(block, wrt))(*leaves)
    assert abs(float(got[0]) - float(want[0])) <= 1e-5 * abs(float(want[0]))
    for a, b in zip(got[1], want[1]):
        assert _err(a, b) <= 2e-5


def test_kernel_specs_pass_the_tile_validator():
    from mxnet_tpu.analysis import tiling
    for spec in (dr.gated_delta_forward_kernel_spec(),
                 dr.gated_delta_backward_kernel_spec()):
        assert spec["name"].startswith("gated_delta")
        assert tiling.spec_findings(spec) == []
    assert tiling.kernel_spec_issues() == []
    assert "kernels.delta_rule.gated_delta_forward" in tiling.KERNEL_SPECS
    assert "kernels.delta_rule.gated_delta_backward" in tiling.KERNEL_SPECS
