"""The Laguna-family layer set against plain references, at small sizes on
the CPU with seeded weights: both flash kernels under a sliding window (the
block loops' bookkeeping, values and gradients in interpret mode at windows
aligned to a block and not, under a block and past the sequence, at groups
of 6 and 8 query heads), the causal kernels' Mosaic modules unchanged for the
four language-model cells' shapes, YaRN's inverse frequencies, the
half-split rotary's product form against the halves' formula bit for bit, the
``GatedAttention`` op with a window, YaRN and no q/k norm, a mirrored
windowed block that runs its forward kernel once, the same block lowered for
a TPU on a mesh of four, the sigmoid routed layer's shares of the experts,
and the whole model through ``ShardedTrainer.step`` against
``perfbench/reference/laguna.py`` (plain ``jax.numpy``, nothing of
``mxnet_tpu``): row losses and every leaf's gradient."""
import base64
import hashlib
import json
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
from mxnet_tpu.ops import attention as att                  # noqa: E402
from mxnet_tpu.ops import moe                                # noqa: E402
from mxnet_tpu.ops.registry import create_operator, op_cost  # noqa: E402
from mxnet_tpu.parallel import ring_attention as ra          # noqa: E402
from perfbench import flops_laguna                           # noqa: E402
from perfbench.reference import laguna as ref               # noqa: E402

F32 = jnp.float32     # conftest turns x64 on: every draw says its dtype
SEQ = 64
YARN = {"rope_theta": 500000, "rope_type": "yarn", "factor": 64,
        "original_max_position_embeddings": 4096, "beta_slow": 1,
        "beta_fast": 64, "attention_factor": 1.4158883083359672,
        "partial_rotary_factor": 0.5}
CFG = {
    "hidden_size": 32, "vocab_size": 64, "head_dim": 16,
    "num_key_value_heads": 2, "num_hidden_layers": 5,
    "layer_types": ["full_attention"] + ["sliding_attention"] * 3
    + ["full_attention"],
    "num_attention_heads_per_layer": [4, 6, 6, 6, 4],
    "mlp_layer_types": ["dense"] + ["sparse"] * 4,
    "sliding_window": 16, "intermediate_size": 48,
    "moe_intermediate_size": 16, "shared_expert_intermediate_size": 12,
    "num_experts": 4, "num_experts_per_tok": 4,
    "moe_routed_scaling_factor": 2.5, "rms_norm_eps": 1e-6,
    "rope_parameters": {
        "full_attention": YARN,
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}},
    "tie_word_embeddings": False, "gating": True, "attention_bias": False,
    "moe_apply_router_weight_on_input": False,
    # wide enough that a router's scores are told apart in float32
    "initializer_range": 0.3,
    "deployment": {"router_width": 16, "first_expert": 4},
    "program": {"mirror_blocks": True},
}
ATT_LEAVES = ("att_q_weight", "att_k_weight", "att_v_weight",
              "att_out_weight")
MOE_LEAVES = ("moe_router_weight", "moe_expert_gate_weight",
              "moe_expert_up_weight", "moe_expert_down_weight",
              "moe_shared_gate_weight", "moe_shared_up_weight",
              "moe_shared_down_weight")


def _close(a, b, tol=2e-5):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    scale = max(np.abs(b).max(), 1e-30)
    assert np.abs(a - b).max() <= tol * scale, (np.abs(a - b).max(), scale)


def _highest(fn):
    def run(*args, **kw):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kw)
    return run


# -- the block loops: exactly the band, masked only where an edge crosses ----
def _seen(q, k, window):
    return 0 <= q - k < window


@pytest.mark.parametrize("block_q,block_k", [(128, 128), (128, 256),
                                             (256, 128), (512, 512)])
@pytest.mark.parametrize("window", [1, 64, 128, 200, 512, 700, 4096])
def test_window_block_loops_visit_the_band_and_no_more(block_q, block_k,
                                                       window):
    """Forward: a query block's loops cover every key block that holds a
    key one of its rows sees and no other; the middle loop only blocks
    whose every pair is seen.  Backward: the same of a key block's query
    blocks.  Python ints, the kernels' own functions."""
    seq = 2048
    nq, nk = seq // block_q, seq // block_k

    def pairs(qb, kb):
        return [_seen(q, k, window)
                for q in range(qb * block_q, (qb + 1) * block_q, 7)
                for k in range(kb * block_k, (kb + 1) * block_k, 5)] \
            + [_seen(qb * block_q + i, kb * block_k + j, window)
               for i in (0, block_q - 1) for j in (0, block_k - 1)]

    for qb in range(nq):
        first, lo, hi, visited = ra._window_k_blocks(qb, block_q, block_k,
                                                     nk, window)
        assert first <= lo <= hi <= visited <= nk
        for kb in range(nk):
            seen = pairs(qb, kb)
            assert (first <= kb < visited) == any(seen), (qb, kb)
            if lo <= kb < hi:
                assert all(seen), (qb, kb)
    for kb in range(nk):
        visited, lo, hi, end = ra._window_q_blocks(kb, block_q, block_k, nq,
                                                   window)
        assert visited <= lo <= hi <= end <= nq
        for qb in range(nq):
            seen = pairs(qb, kb)
            assert (visited <= qb < end) == any(seen), (qb, kb)
            if lo <= qb < hi:
                assert all(seen), (qb, kb)


# -- both windowed kernels against a plain masked softmax ---------------------
def _plain(q, k, v, window):
    """softmax(q kᵀ / √d) v with query i seeing keys i − W + 1 … i; k and v
    repeated over a group; float32 at ``highest``."""
    group = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(t, group, axis=1) for t in (k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    pos = jnp.arange(q.shape[2])
    lead = pos[:, None] - pos[None, :]
    s = jnp.where((lead >= 0) & (lead < window), s, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v)


def _qkvw(group, seed, seq=512, d=64):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (1, group, seq, d), F32)
    k, v = (jax.random.normal(kk, (1, 1, seq, d), F32) for kk in ks[1:3])
    return q, k, v, jax.random.normal(ks[3], (1, group, seq, d), F32)


@pytest.mark.parametrize("group", [6, 8])
@pytest.mark.parametrize("window", [256, 200, 64, 2048])
def test_window_kernels_against_the_masked_softmax(group, window):
    """Forward and backward in interpret mode, blocks of 128 over 512
    tokens: a window of two blocks, one that ends inside a block, one under
    a block, and one past the sequence — which is the causal kernel's."""
    q, k, v, w = _qkvw(group, window)

    def kernel(q, k, v):
        return ra.flash_attention(q, k, v, causal=True, interpret=True,
                                  window=window, block_q=128, block_k=128)

    def grads(fn):
        return jax.value_and_grad(lambda *a: jnp.sum(fn(*a) * w),
                                  (0, 1, 2))(q, k, v)

    got_o, want_o = _highest(kernel)(q, k, v), _highest(_plain)(q, k, v,
                                                                window)
    _close(got_o, want_o, 2e-6)
    got = _highest(grads)(kernel)[1]
    want = _highest(grads)(lambda *a: _plain(*a, window))[1]
    for a, b in zip(got, want):
        _close(a, b, 2e-6)
    if window >= q.shape[2]:
        causal = _highest(lambda *a: ra.flash_attention(
            *a, causal=True, interpret=True, block_q=128, block_k=128))
        _close(got_o, causal(q, k, v), 1e-6)


def test_window_kernels_in_bfloat16_at_unequal_blocks():
    """bfloat16 operands, query blocks of 128 against key blocks of 256 and
    the other way round: the float32 masked softmax's numbers to a step of
    bfloat16."""
    q, k, v, w = (t.astype(jnp.bfloat16) for t in _qkvw(8, 7))
    for bq, bk in ((128, 256), (256, 128)):
        def kernel(q, k, v):
            return ra.flash_attention(q, k, v, causal=True, interpret=True,
                                      window=300, block_q=bq, block_k=bk)
        got_o, got = jax.value_and_grad(
            lambda *a: jnp.sum((kernel(*a) * w).astype(F32)),
            (0, 1, 2))(q, k, v)
        want_o, want = _highest(jax.value_and_grad(
            lambda *a: jnp.sum(_plain(*a, 300) * w.astype(F32)),
            (0, 1, 2)))(*(t.astype(F32) for t in (q, k, v)))
        assert abs(float(got_o) - float(want_o)) < 3e-2 * abs(float(want_o))
        for a, b in zip(got, want):
            assert a.dtype == jnp.bfloat16
            _close(a.astype(F32), b, 3e-2)


@pytest.mark.parametrize("window", [5, 8, 20])
def test_window_on_the_ring(window):
    """A mesh that shards the sequence runs the ring, whose every step
    takes the same band: chunks of 8 over four devices, a window inside a
    chunk, one chunk, and past two."""
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P
    q, k, v, _w = _qkvw(2, window, seq=32, d=8)
    k, v = (jnp.repeat(t, 2, axis=1) for t in (k, v))
    mesh = Mesh(np.array(jax.devices()[:4]), ("sp",))
    spec = P(None, None, "sp", None)
    ring = shard_map(lambda a, b, c: ra.ring_attention(
        a, b, c, axis_name="sp", causal=True, window=window), mesh=mesh,
        in_specs=(spec,) * 3, out_specs=spec)
    _close(_highest(jax.jit(ring))(q, k, v), _highest(_plain)(q, k, v,
                                                             window), 1e-5)


def test_window_needs_causal_attention():
    q = jnp.zeros((1, 2, 128, 64), F32)
    for causal, window in ((False, 64), (True, 0)):
        with pytest.raises(ValueError):
            ra.flash_attention(q, q, q, causal=causal, window=window)


# -- the causal kernels lower as they did -------------------------------------
def _mosaic_calls(fn, *args):
    """``[(kernel, sha256 of its custom-call configuration)]`` of ``fn``
    lowered for a TPU, the Mosaic module printed without source locations
    (a moved line of the kernels' file is not a change of the kernel)."""
    from jax._src.lib.mlir import ir
    with jax.enable_x64(False):
        low = jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",))
    found = []

    def walk(op):
        for region in op.regions:
            for block in region.blocks:
                for o in block.operations:
                    attrs = o.operation.attributes
                    if o.operation.name == "stablehlo.custom_call" and \
                            ir.StringAttr(attrs["call_target_name"]).value \
                            == "tpu_custom_call":
                        cfg = json.loads(
                            ir.StringAttr(attrs["backend_config"]).value)
                        call = cfg["custom_call_config"]
                        ctx = ir.Context()
                        ctx.allow_unregistered_dialects = True
                        with ctx:
                            call["body"] = ir.Module.parse(base64.b64decode(
                                call["body"])).operation.get_asm(
                                    enable_debug_info=False)
                        found.append((call["body"].split()[1], hashlib.sha256(
                            json.dumps(cfg, sort_keys=True).encode())
                            .hexdigest()[:16]))
                    walk(o.operation)
    walk(low.compiler_ir("stablehlo").operation)
    return found


#: the four language-model cells' attention shapes (q, k, v) and their
#: kernels' configurations as the tree before the sliding window lowered
#: them: a window of None must leave every one to the byte
CAUSAL_CELLS = {
    "gpt2m_train_s1024": (((8, 16, 1024, 64),) * 3, (
        "e7e4d62a276014af", "3ffd163b847bab9f")),
    "joyai_flash_train_s8192": (
        ((1, 32, 8192, 192), (1, 32, 8192, 192), (1, 32, 8192, 128)), (
            "81ea813d5e246541", "f7b76b20729aa7c2")),
    "zaya1_train_s8192": (
        ((1, 8, 8192, 128), (1, 2, 8192, 128), (1, 2, 8192, 128)), (
            "46e358d754745877", "015948a470bc25f8")),
    "qwen3next_train_s8192": (
        ((1, 16, 8192, 256), (1, 2, 8192, 256), (1, 2, 8192, 256)), (
            "73d53e654443ca78", "42e322ff745b3dd4")),
}


@pytest.mark.parametrize("cell", sorted(CAUSAL_CELLS))
def test_causal_kernels_lower_as_before(cell):
    shapes, (fwd, bwd) = CAUSAL_CELLS[cell]

    def loss(q, k, v):
        return jnp.sum(ra.flash_attention(q, k, v, causal=True,
                                          interpret=False).astype(F32))

    args = [jax.ShapeDtypeStruct(s, jnp.bfloat16) for s in shapes]
    assert _mosaic_calls(jax.grad(loss, (0, 1, 2)), *args) == [
        ("@flash_forward", fwd), ("@flash_backward", bwd)]
    windowed = _mosaic_calls(jax.grad(lambda *a: jnp.sum(ra.flash_attention(
        *a, causal=True, interpret=False, window=512).astype(F32)),
        (0, 1, 2)), *args)
    assert [name for name, _ in windowed] == [
        "@flash_window_forward", "@flash_window_backward"]


# -- YaRN ---------------------------------------------------------------------
def test_yarn_inverse_frequencies_against_a_table_worked_by_hand():
    """Laguna-XS.2's full layers: 64 rotated channels (32 pairs), theta
    5e5, factor 64, an original context of 4,096, β_fast 64, β_slow 1.
    Pair i makes 4,096 / (2π · 5e5^(i/32)) turns: 64 turns at i = 5.66, one
    at i = 15.80, so the ramp runs from pair 5 (floor) to pair 16 (ceil):
    pairs 0–5 keep 5e5^(−i/32), pairs 16–31 take it over 64, and pair i
    between them (i − 5)/11 of the way."""
    table = []
    for i in range(32):
        base = 500000.0 ** (-i / 32.0)
        ramp = min(max((i - 5) / 11.0, 0.0), 1.0)
        table.append(base * (1 - ramp) + base / 64.0 * ramp)
    got = att.yarn_inv_freq(64, 500000.0, 64.0, 4096, 64.0, 1.0)
    np.testing.assert_allclose(got, table, rtol=1e-12)
    np.testing.assert_allclose(ref.yarn_inv_freq(64, YARN), table, rtol=1e-12)
    assert got[5] == 500000.0 ** (-5 / 32.0)
    assert got[16] == 500000.0 ** (-16 / 32.0) / 64.0


def test_yarn_rotary_scales_only_the_rotated_channels():
    """cos and sin times the attention factor: a score of the rotated
    channels alone grows by its square, the rest is untouched."""
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 1, 8, 16), F32)
    inv = att.yarn_inv_freq(8, 500000.0, 64.0, 4096, 64.0, 1.0)
    y = att.rotary_half(x, 500000.0, 8, inv, 1.5)
    plain = att.rotary_half(x, 500000.0, 8, inv)
    _close(y[..., :8], 1.5 * plain[..., :8], 1e-6)
    assert np.array_equal(np.asarray(y[..., 8:]), np.asarray(x[..., 8:]))
    # the default arguments are the rotary the other models run
    assert np.array_equal(np.asarray(att.rotary_half(x, 1e4, 8)),
                          np.asarray(att.rotary_half(x, 1e4, 8, None, None)))


# -- the rotary as a product with a signed permutation ------------------------
def _rotary_by_slices(x, theta, rotary_dim, inv_freq=None, scale=None):
    """The half-split rotary as slices and a concatenation: the formula
    ``rotary_half`` computes as a product, kept here as its reference."""
    s, d = x.shape[-2], x.shape[-1]
    half = rotary_dim // 2
    x32 = x.astype(jnp.float32)
    if inv_freq is None:
        inv_freq = 1.0 / (float(theta) ** (
            jnp.arange(0, rotary_dim, 2, dtype=jnp.float32) / rotary_dim))
    else:
        inv_freq = jnp.asarray(inv_freq, jnp.float32)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if scale is not None:
        cos, sin = cos * scale, sin * scale
    a, b = x32[..., :half], x32[..., half:rotary_dim]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x32[..., rotary_dim:]], axis=-1).astype(x.dtype)


def _bits(t):
    t = np.asarray(t)
    return t.view({2: np.uint16, 4: np.uint32}[t.dtype.itemsize])


@pytest.mark.parametrize("yarn", [False, True])
@pytest.mark.parametrize("d,rotary_dim", [(128, 128), (128, 64), (256, 256),
                                          (256, 128)])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, F32])
def test_rotary_product_is_the_slices_formula_to_the_bit(dtype, d,
                                                         rotary_dim, yarn):
    """Value and ``jax.vjp`` gradient equal the slices' formula bit for bit
    (operation by operation: under ``jit`` a compiler may contract a
    product and a sum into one fused multiply-add, and which product it
    takes follows the order of the sum), at a length that is no power of
    two; the channels past the rotated ones come out as they went in."""
    inv, scale = ((att.yarn_inv_freq(rotary_dim, 500000.0, 64.0, 4096,
                                     32.0, 1.0), 1.4158883083359672)
                  if yarn else (None, None))
    key = jax.random.PRNGKey(d + rotary_dim + yarn)
    x = (3 * jax.random.normal(key, (2, 3, 72, d), F32)).astype(dtype)
    g = jax.random.normal(jax.random.fold_in(key, 1), x.shape,
                          F32).astype(dtype)
    y, back = jax.vjp(lambda t: att.rotary_half(t, 500000.0, rotary_dim,
                                                inv, scale), x)
    want, want_back = jax.vjp(lambda t: _rotary_by_slices(
        t, 500000.0, rotary_dim, inv, scale), x)
    assert y.dtype == x.dtype and back(g)[0].dtype == x.dtype
    np.testing.assert_array_equal(_bits(y), _bits(want))
    np.testing.assert_array_equal(_bits(back(g)[0]), _bits(want_back(g)[0]))
    np.testing.assert_array_equal(_bits(y[..., rotary_dim:]),
                                  _bits(x[..., rotary_dim:]))
    np.testing.assert_array_equal(_bits(back(g)[0][..., rotary_dim:]),
                                  _bits(g[..., rotary_dim:]))


def _scoped(jaxpr, scope, prefix=""):
    """``(equation, name stack)`` of every equation of ``jaxpr``, however
    deep, whose name stack holds ``scope``; an inner jaxpr's stack is read
    after its equation's."""
    for eqn in jaxpr.eqns:
        stack = prefix + "/" + str(eqn.source_info.name_stack)
        if scope in stack:
            yield eqn, stack
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (tuple, list))
                        else (value,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _scoped(sub, scope, stack)


@pytest.mark.parametrize("op_type", ["GatedAttention",
                                     "CompressedConvAttention"])
def test_rotary_runs_as_products_without_slices(op_type):
    """In a small attention layer's gradient the rotary scope holds two
    (d, d) products forward (q and k by the half-swap) and four backward
    (q and k by its transpose and by the identity), and no slice or
    concatenation narrower than a head."""
    d = 16
    op = create_operator(op_type, num_heads=4, num_kv_heads=2, head_dim=d,
                         rope_theta=100.0, partial_rotary_factor=0.5)
    shapes = op.infer_shape([(1, 8, 32)] + [None] * 16)[0]
    leaves = [0.1 * jax.random.normal(jax.random.PRNGKey(i), s, F32)
              for i, s in enumerate(shapes)]

    def loss(*leaves):
        return jnp.sum(op.forward(list(leaves), [], True, None)[0][0])

    jaxpr = jax.make_jaxpr(jax.grad(loss, tuple(range(len(leaves)))))(
        *leaves).jaxpr
    passes, narrow = [], []
    for eqn, stack in _scoped(jaxpr, att.ROTARY_NORM):
        shapes = [v.aval.shape for v in eqn.invars + eqn.outvars
                  if hasattr(v.aval, "shape")]
        if eqn.primitive.name == "dot_general" and (d, d) in shapes:
            passes.append("transpose" in stack)
        if eqn.primitive.name in ("slice", "concatenate"):
            narrow += [s for s in shapes if s[-1] % d]
    assert sorted(passes) == [False] * 2 + [True] * 4
    assert narrow == []


# -- the op -------------------------------------------------------------------
def _att_op(kind, heads):
    from mxnet_tpu.models.transformer_swa_moe import attention_args
    args = attention_args(kind, heads, 2, 16, CFG["sliding_window"],
                          CFG["rope_parameters"][kind])
    return create_operator("GatedAttention", eps=1e-6, **args)


def _layer(key, layer):
    params = ref.init_params(CFG, key)
    n = len("layer%d_" % layer)
    return {k[n:]: v for k, v in params.items()
            if k.startswith("layer%d_" % layer)}


@pytest.mark.parametrize("layer", [0, 1])
def test_gated_attention_with_window_and_yarn(layer):
    """The full layer (YaRN on half of each head, every key before a query)
    and a window layer (plain rotary on the whole head, 16 keys) against
    the reference: output and every gradient; no q/k norm leaves."""
    kind, heads = CFG["layer_types"][layer], \
        CFG["num_attention_heads_per_layer"][layer]
    key = jax.random.PRNGKey(20 + layer)
    p = _layer(key, layer)
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, SEQ, 32), dtype=F32)
    leaves = [p[n] for n in ATT_LEAVES]
    op = _att_op(kind, heads)
    assert op.list_arguments() == ["data"] + [n[4:] for n in ATT_LEAVES]
    w = jax.random.normal(jax.random.PRNGKey(21), x.shape, dtype=F32)

    def prog(x, *leaves):
        return op.forward([x] + list(leaves), [], True, None)[0][0]

    def plain(x, *leaves):
        return ref.gated_attention(x, dict(zip(ATT_LEAVES, leaves)), CFG,
                                   kind, heads)

    _close(prog(x, *leaves), _highest(plain)(x, *leaves), 2e-5)
    wrt = tuple(range(1 + len(leaves)))
    got = jax.grad(lambda *a: jnp.sum(prog(*a) * w), wrt)(x, *leaves)
    want = jax.grad(lambda *a: jnp.sum(_highest(plain)(*a) * w), wrt)(
        x, *leaves)
    for name, a, b in zip(("data",) + ATT_LEAVES, got, want):
        assert float(jnp.abs(b).max()) > 0, name
        _close(a, b, 1e-4)
    # each of the two faults moves the layer it belongs to, and only it
    fault = "window" if kind == "sliding_attention" else "yarn"
    for without in ref.FAULTS:
        moved = _highest(lambda *a: ref.gated_attention(
            a[0], dict(zip(ATT_LEAVES, a[1:])), CFG, kind, heads,
            without=(without,)))(x, *leaves)
        gap = float(jnp.abs(moved - plain(x, *leaves)).max())
        assert (gap > 1e-3) == (without == fault), (without, gap)
    shapes = op.infer_shape([(2, SEQ, 32)] + [None] * len(ATT_LEAVES))[0]
    assert [tuple(s) for s in shapes[1:]] == [tuple(a.shape) for a in leaves]
    cost = op_cost(op, shapes, [(2, SEQ, 32)])
    assert cost["mxu"] and cost["flops"] > 0


def test_window_is_a_mask_and_not_a_shorter_sequence():
    """A window layer's output at position t depends on the 16 tokens up
    to t and on no earlier one; the full layer's on every earlier one."""
    for layer in (1, 0):
        kind, heads = CFG["layer_types"][layer], \
            CFG["num_attention_heads_per_layer"][layer]
        p = _layer(jax.random.PRNGKey(30), layer)
        op = _att_op(kind, heads)
        x = jax.random.normal(jax.random.PRNGKey(31), (1, SEQ, 32), dtype=F32)
        leaves = [p[n] for n in ATT_LEAVES]
        jac = jax.jacrev(lambda x: op.forward([x] + leaves, [], True,
                                              None)[0][0][0, 40])(x)
        reach = np.abs(np.asarray(jac)).sum(axis=(0, 1, 3))
        touched = np.nonzero(reach > 0)[0]
        lowest = 40 - 15 if kind == "sliding_attention" else 0
        assert touched.min() == lowest and touched.max() == 40, touched


def test_mirrored_window_block_runs_the_forward_kernel_once(monkeypatch):
    """A window layer's ``GatedAttention`` under the executor's mirrored
    checkpoint: the gradient's program holds one ``flash_window_forward``
    and one ``flash_window_backward`` — the window's residuals carry the
    names ``KEPT`` keeps — and the gradients are the unmirrored block's."""
    from mxnet_tpu.executor import mirror_checkpoint
    from mxnet_tpu.kernels import common
    from test_mirror import _kernel_calls
    monkeypatch.setattr(
        common, "dispatch",
        lambda kernel, _reference, *args: kernel(*args, interpret=True))
    p = _layer(jax.random.PRNGKey(40), 1)
    x = jax.random.normal(jax.random.PRNGKey(41), (1, 128, 32), dtype=F32)
    leaves = [p[n] for n in ATT_LEAVES]
    op = _att_op("sliding_attention", 6)

    def block(x, *leaves):
        return jnp.sum(jnp.sin(op.forward([x] + list(leaves), [], True,
                                          None)[0][0]))

    wrt = tuple(range(1 + len(leaves)))
    for fn, calls in ((block, 1), (jax.checkpoint(block), 2),
                      (mirror_checkpoint(block), 1)):
        found = _kernel_calls(jax.make_jaxpr(jax.grad(fn, wrt))(
            x, *leaves).jaxpr)
        assert found == {"flash_window_forward": calls,
                         "flash_window_backward": 1}, found
    got = jax.grad(mirror_checkpoint(block), wrt)(x, *leaves)
    want = jax.grad(block, wrt)(x, *leaves)
    for a, b in zip(got, want):
        _close(a, b, 1e-5)


@pytest.mark.parametrize("axes,shape", [(("dp",), (4,)),
                                        (("dp", "tp"), (2, 2))])
def test_window_block_on_a_mesh_lowers_for_a_tpu(axes, shape):
    """A checkpointed window block's value and gradient lowered for a TPU
    over four devices as ``ShardedTrainer`` lowers its step (under
    ``attention_scope``): the windowed kernels per device under
    ``shard_map`` — GSPMD does not partition a Mosaic call — the forward
    twice (the plain checkpoint recomputes it) and the backward once."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from mxnet_tpu.parallel.ring_attention import attention_scope
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(shape), axes)
    p = _layer(jax.random.PRNGKey(50), 1)
    op = _att_op("sliding_attention", 6)
    leaves = [jnp.zeros((4, 512, 32), F32)] + [p[n] for n in ATT_LEAVES]

    def block(x, *leaves):
        return jnp.sum(op.forward([x] + list(leaves), [], True, None)[0][0])

    args = [jax.ShapeDtypeStruct(
        t.shape, t.dtype, sharding=NamedSharding(mesh, P("dp") if i == 0
                                                 else P()))
        for i, t in enumerate(leaves)]
    wrt = tuple(range(len(leaves)))
    with jax.enable_x64(False), attention_scope(mesh, None):
        step = jax.value_and_grad(jax.checkpoint(block), wrt)
        jaxpr = str(jax.make_jaxpr(step)(*leaves))
        calls = _mosaic_calls(step, *args)
    assert "shard_map" in jaxpr
    assert sorted(name for name, _ in calls) == [
        "@flash_window_backward", "@flash_window_forward",
        "@flash_window_forward"]


# -- routing: sigmoid over all the experts, eight a token, a shared one ------
def _routed_op(first=4, held=4, shared=12):
    return create_operator(
        "RoutedExperts", num_experts=16, num_local_experts=held,
        first_expert=first, hidden_size=16, top_k=4,
        routed_scaling_factor=2.5, shared_hidden_size=shared)


def _zero_aux(op, tokens=96):
    shapes = op.infer_shape([(tokens, 32)] + [None] * (
        len(op.list_arguments()) - 1))[2]
    types = op.infer_type([np.dtype("float32")] * len(op.list_arguments()))[2]
    return [jnp.zeros(s, t) for s, t in zip(shapes, types)]


def test_four_shares_add_up_to_the_uncut_layer():
    """Experts 4i .. 4i + 3 on chip i of 4, each routing over all 16 on
    sigmoid scores, weights normalised over the chosen and times 2.5: the
    four partial results — the ungated shared expert, which every chip
    computes alike, counted once — add up to the reference's uncut layer,
    every assignment computed exactly once, and the reference given a share
    gives that share's part."""
    key = jax.random.PRNGKey(54)
    p = _layer(key, 1)
    h = jax.random.normal(jax.random.fold_in(key, 1), (96, 32), dtype=F32)
    full = jax.random.split(jax.random.PRNGKey(55), 3)
    p = dict(p, **{
        name: 0.3 * jax.random.normal(k, shape, dtype=F32)
        for name, k, shape in zip(MOE_LEAVES[1:4], full, (
            (16, 16, 32), (16, 16, 32), (16, 32, 16)))})
    stacks = MOE_LEAVES[1:4]
    whole, margin = _highest(ref.routed_layer)(h, p, CFG, first=0, held=16)
    assert float(jnp.min(margin)) > 1e-6        # no near-tie in this draw
    total, counted = jnp.zeros_like(h), 0
    for chip in range(4):
        first = 4 * chip
        cut = {n: p[n][first:first + 4] for n in stacks}
        with_shared = chip == 0
        op = _routed_op(first=first, shared=12 if with_shared else 0)
        leaves = [p["moe_router_weight"]] + [cut[n] for n in stacks]
        if with_shared:
            leaves += [p[n] for n in MOE_LEAVES[4:]]
        outs, aux = op.forward([h] + leaves, _zero_aux(op), True, None)
        part, _ = _highest(ref.routed_layer)(h, dict(p, **cut), CFG,
                                             first=first, held=4,
                                             shared=with_shared)
        _close(outs[0], part, 5e-5)
        total, counted = total + outs[0], counted + int(aux[1][0])
    assert counted == 96 * 4            # four experts a token, each once
    _close(total, whole, 5e-5)
    weights, _ = _highest(ref.routing)(h, p, CFG)
    _close(jnp.sum(weights, axis=-1), jnp.full((96,), 2.5), 1e-6)


@pytest.mark.parametrize("chunk_rows", [32, 128, 384])
def test_routed_chunks_sized_from_the_shapes(chunk_rows):
    """The cell's routed layers walk chunks of twice what a balanced router
    sends the experts held (8,192 tokens, 8 of 256, 32 held: 16,384 rows,
    where the shared default is 8,192 and a step walked one chunk or two),
    and the layer's result, gradients and counters do not depend on how
    its assignments are chunked."""
    from mxnet_tpu.models import transformer_swa_moe as model
    assert model.chunk_rows(8192, 8, 32, 256) == 16384
    net = model.get_symbol(seq_len=8192, num_experts=256, n_local_experts=32,
                           num_experts_per_tok=8)
    assert net.attr_dict()["layer1_moe"]["chunk_rows"] == "16384"
    key = jax.random.PRNGKey(56)
    p = _layer(key, 1)
    h = jax.random.normal(jax.random.fold_in(key, 1), (96, 32), dtype=F32)
    stacks = jax.random.split(jax.random.PRNGKey(57), 3)
    leaves = [p["moe_router_weight"]] + [
        0.3 * jax.random.normal(k, shape, dtype=F32) for k, shape in zip(
            stacks, ((16, 16, 32), (16, 16, 32), (16, 32, 16)))]

    def run(rows):
        op = create_operator(
            "RoutedExperts", num_experts=16, hidden_size=16, top_k=4,
            routed_scaling_factor=2.5, chunk_rows=rows)

        def loss(h, *w):
            outs, aux = op.forward([h] + list(w), _zero_aux(op), True, None)
            return jnp.sum(outs[0] ** 2), (outs[0], aux[1])
        grads, (out, counted) = _highest(jax.grad(
            loss, argnums=tuple(range(5)), has_aux=True))(h, *leaves)
        return out, counted, grads

    out, counted, grads = run(chunk_rows)
    want_out, want_counted, want_grads = run(0)     # 8,192: one chunk
    assert int(counted[0]) == int(want_counted[0]) == 96 * 4
    _close(out, want_out, 1e-6)
    for got, want in zip(grads, want_grads):
        _close(got, want, 1e-5)


# -- the model ----------------------------------------------------------------
def test_layer_pattern_heads_and_routed_layers():
    from mxnet_tpu.models import transformer_swa_moe as model
    from perfbench.drivers.train_step_laguna import symbol_args
    args = symbol_args(CFG, SEQ)
    net = model.get_symbol(**args)
    names = net.list_arguments()
    shapes = dict(zip(names, net.infer_shape(data=(1, SEQ),
                                             softmax_label=(1, SEQ))[0]))
    for i, heads in enumerate(CFG["num_attention_heads_per_layer"]):
        assert shapes["layer%d_att_q_weight" % i] == (heads * 17, 32)
        assert ("layer%d_ffn_gate_weight" % i in names) == (i == 0)
        assert ("layer%d_moe_router_weight" % i in names) == (i > 0)
        assert "layer%d_att_q_norm_gamma" % i not in names
    assert model.routed_layer_names(args["mlp_layer_types"]) == [
        "layer%d_moe" % i for i in range(1, 5)]
    assert {n: tuple(s) for n, s in ref.param_shapes(CFG).items()} == {
        n: tuple(s) for n, s in shapes.items()
        if n not in ("data", "softmax_label")}
    with pytest.raises(ValueError):
        model.get_symbol(**dict(args, mlp_layer_types=["dense"] * 4))


def _model(mirror=True, compute_dtype=None):
    from mxnet_tpu import optimizer as opt_mod
    from mxnet_tpu.models import transformer_swa_moe
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.trainer import ShardedTrainer
    from perfbench.drivers.train_step_laguna import symbol_args
    cfg = dict(CFG, program=dict(CFG["program"], mirror_blocks=mirror))
    net = transformer_swa_moe.get_symbol(**symbol_args(cfg, SEQ))
    batch = 2
    opt = opt_mod.create("sgd", learning_rate=0.5, momentum=0.9, wd=0.0,
                         rescale_grad=1.0 / (batch * SEQ))
    trainer = ShardedTrainer(net, opt, make_mesh(jax.devices()[:1], dp=1),
                             label_names=("softmax_label",),
                             compute_dtype=compute_dtype)
    return trainer, batch


def _one_step(seed=0, mirror=True):
    trainer, batch = _model(mirror)
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, CFG["vocab_size"], (batch, SEQ)).astype(np.int32)
    lab = np.roll(ids, -1, axis=1).astype(np.float32)
    shapes, labels = {"data": (batch, SEQ)}, {"softmax_label": (batch, SEQ)}
    key = jax.random.PRNGKey(70 + seed)
    params = ref.init_params(CFG, key)
    for i, (name, v) in enumerate(sorted(params.items())):
        if name.endswith("_gamma"):     # moved off the seed's 1
            params[name] = v + 0.1 * jax.random.normal(
                jax.random.fold_in(key, 100 + i), v.shape, dtype=F32)
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
    assert float(jnp.min(rows["margin"])) > 1e-6    # no near-tie drawn
    p = np.take_along_axis(np.asarray(outs[0]), lab.reshape(-1, 1)
                           .astype(np.int64), axis=1)[:, 0]
    _close(-np.log(p), rows["main"], 1e-4)
    assert set(grads) == set(mom) == set(ref.param_shapes(CFG))
    for n, g in grads.items():
        assert np.abs(np.asarray(g)).max() > 0, n
        _close(-np.asarray(mom[n]) / 0.5, g, 5e-4)      # m1 = -lr * g
    for i in range(1, 5):
        c = moe.routing_counters(aux, "layer%d_moe" % i)
        assert c["local_assignments"][0] == c["expert_tokens"].sum() > 0


def test_mirror_blocks_gives_the_unmirrored_gradients():
    _w0, mirrored, _aux, outs_m, _ = _one_step(seed=4, mirror=True)
    _w0, plain, _aux, outs_p, _ = _one_step(seed=4, mirror=False)
    _close(outs_m[0], outs_p[0], 5e-5)
    for n in plain:
        _close(mirrored[n], plain[n], 1e-3)


# -- the count of operations --------------------------------------------------
def test_band_pairs_and_the_windowed_step_by_hand():
    """A band of W over S ≥ W positions holds W·S − W(W − 1)/2 pairs: at 8
    over 20, 8·20 − 28 = 132, counted pair by pair here; past the sequence
    it is the causal triangle."""
    assert flops_laguna.band_pairs(20, 8) == sum(
        1 for q in range(20) for k in range(20) if 0 <= q - k < 8) == 132
    assert flops_laguna.band_pairs(20, 40) == 210
    ops, _ = flops_laguna.flash_window_call(1, 64, 8, 8192, 512, 128, 128, 2)
    assert ops == 2 * 64 * (512 * 8192 - 512 * 511 // 2) * 256
