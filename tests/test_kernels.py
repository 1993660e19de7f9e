"""Quantized + fused kernel tier (docs/perf.md "Quantization & fused
kernels"): weight-only int8 quantization end-to-end (array -> symbol
rewrite -> Predictor -> GenerationEngine), decode attention over the
paged KV cache, kernel-or-reference dispatch by placement, bit-identity
of the fused optimizer sweep on the 8-device mesh, MXL-K lint coverage
of the kernel specs, and the benchdiff gate catching a simulated
decode-throughput regression.

Pallas kernels run in interpret mode on the CPU test mesh — the same
trace Mosaic compiles on TPU, so everything but the hardware lowering
is covered.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import ndarray as nd
from mxnet_tpu import parallel
from mxnet_tpu.base import MXNetError
from mxnet_tpu.executor import program_registry_stats
from mxnet_tpu.kernels import fused_opt as fo
from mxnet_tpu.kernels import quantize as qz
from mxnet_tpu.models import transformer as tf
from mxnet_tpu.predictor import Predictor
from mxnet_tpu.serving import GenerationEngine
from mxnet_tpu.test_utils import tpu_lowering_text

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

V, L, H, E, S = 64, 2, 4, 32, 48        # toy LM dims shared by the module


def _cosine(a, b):
    a = np.asarray(a, np.float64).ravel()
    b = np.asarray(b, np.float64).ravel()
    denom = float(np.linalg.norm(a) * np.linalg.norm(b))
    return float(np.dot(a, b)) / denom if denom else 1.0


@pytest.fixture(scope="module")
def lm_params():
    """Random full-model checkpoint (test_generate.py idiom)."""
    full = tf.get_symbol(vocab_size=V, num_layers=L, num_heads=H, dim=E,
                         seq_len=S)
    rng = np.random.RandomState(0)
    shapes = full.infer_shape(data=(1, S), softmax_label=(1, S))[0]
    params = {}
    for name, shp in zip(full.list_arguments(), shapes):
        if name in ("data", "softmax_label"):
            continue
        params[name] = nd.array(rng.randn(*shp).astype(np.float32) * 0.05)
    return params


# ---------------------------------------------------------------------------
# weight-only quantization: array / symbol / params
# ---------------------------------------------------------------------------

def test_quantize_array_roundtrip():
    rng = np.random.RandomState(3)
    w = rng.randn(16, 64).astype(np.float32)
    w[5] = 0.0                                  # all-zero row edge case
    q, scale = qz.quantize_array(w)
    assert q.dtype == np.int8 and q.shape == w.shape
    assert scale.dtype == np.float32 and scale.shape == (16,)
    assert scale[5] == 1.0 and not q[5].any()
    back = qz.dequantize_array(q, scale)
    # symmetric per-row: error bounded by half an int8 step per row
    err = np.abs(back - w)
    assert (err <= scale[:, None] * 0.5 + 1e-7).all()


def test_quantize_array_rejects_non_2d():
    with pytest.raises(MXNetError):
        qz.quantize_array(np.zeros(8, np.float32))


def test_quantized_matmul_kernel_matches_reference():
    """The Pallas dequant-in-registers matmul (interpret mode) against
    the exact jnp reference — including non-block-aligned dims, which
    pick_block must absorb by shrinking to exact divisors."""
    rng = np.random.RandomState(5)
    for m, k, n in ((8, 256, 256), (6, 96, 80), (1, 64, 64)):
        x = jnp.asarray(rng.randn(m, k).astype(np.float32))
        q, scale = qz.quantize_array(rng.randn(n, k).astype(np.float32))
        want = qz.quantized_matmul_reference(x, jnp.asarray(q),
                                             jnp.asarray(scale))
        got = qz.quantized_matmul(x, jnp.asarray(q), jnp.asarray(scale),
                                  interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


def test_quantize_symbol_rewrites_fc_and_remaps():
    data = mx.sym.Variable("data")
    fc1 = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    act = mx.sym.Activation(fc1, act_type="relu")
    fc2 = mx.sym.FullyConnected(act, num_hidden=4, name="fc2")
    net = mx.sym.SoftmaxOutput(fc2, name="softmax")
    names = qz.quantizable_weights(net.tojson())
    assert names == ["fc1_weight", "fc2_weight"]
    qjs, qnames = qz.quantize_symbol(net.tojson())
    assert tuple(names) == qnames
    doc = json.loads(qjs)
    ops = [nd_["op"] for nd_ in doc["nodes"]]
    assert ops.count("QuantizedDense") == 2 and "FullyConnected" not in ops
    rewritten = mx.sym.load_json(qjs)
    args = rewritten.list_arguments()
    assert "fc1_weight_scale" in args and "fc2_weight_scale" in args
    # rule filter: only fc2 when the pattern says so
    assert qz.quantizable_weights(net.tojson(), rules=(r"fc2_.*",)) \
        == ["fc2_weight"]


def test_quantize_params_idempotent():
    rng = np.random.RandomState(1)
    params = {"fc1_weight": rng.randn(8, 16).astype(np.float32),
              "fc1_bias": np.zeros(8, np.float32)}
    once = qz.quantize_params(params, ["fc1_weight"])
    assert once["fc1_weight"].dtype == np.int8
    assert "fc1_weight_scale" in once
    twice = qz.quantize_params(once, ["fc1_weight"])
    assert twice["fc1_weight"] is once["fc1_weight"]


def test_predictor_quantized_cosine(tmp_path):
    data = mx.sym.Variable("data")
    fc1 = mx.sym.FullyConnected(data, num_hidden=32, name="fc1")
    act = mx.sym.Activation(fc1, act_type="relu")
    fc2 = mx.sym.FullyConnected(act, num_hidden=8, name="fc2")
    net = mx.sym.SoftmaxOutput(fc2, name="softmax")
    rng = np.random.RandomState(2)
    params = {"fc1_weight": rng.randn(32, 20).astype(np.float32),
              "fc1_bias": rng.randn(32).astype(np.float32),
              "fc2_weight": rng.randn(8, 32).astype(np.float32),
              "fc2_bias": rng.randn(8).astype(np.float32)}
    x = rng.randn(4, 20).astype(np.float32)
    ref = Predictor(net.tojson(), dict(params), {"data": (4, 20)})
    out_f32 = np.asarray(ref.forward(data=x)[0])
    qp = Predictor(net.tojson(), dict(params), {"data": (4, 20)},
                   quantize="int8")
    out_q = np.asarray(qp.forward(data=x)[0])
    assert _cosine(out_f32, out_q) >= 0.999


def test_predictor_quantize_env_default(monkeypatch, tmp_path):
    monkeypatch.setenv("MXTPU_QUANTIZE", "int8")
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=8, name="fc1")
    rng = np.random.RandomState(4)
    params = {"fc1_weight": rng.randn(8, 12).astype(np.float32),
              "fc1_bias": np.zeros(8, np.float32)}
    pred = Predictor(net.tojson(), dict(params), {"data": (2, 12)})
    assert "QuantizedDense" in pred.symbol.tojson()
    # quantize="" is an explicit opt-out even with the env set
    off = Predictor(net.tojson(), dict(params), {"data": (2, 12)},
                    quantize="")
    assert "QuantizedDense" not in off.symbol.tojson()


# ---------------------------------------------------------------------------
# quantized generation: the serving acceptance gate
# ---------------------------------------------------------------------------

def test_engine_quantized_decode_matches_f32(lm_params):
    """Greedy decode at int8 across mixed prompt lengths: per-step
    logits cosine >= 0.999 vs the f32 engine (tokens are identical on
    this toy LM) and ZERO lowerings in the generation steady state."""
    kw = dict(vocab_size=V, num_layers=L, num_heads=H, dim=E,
              max_seq_len=S, max_new_tokens=6, prompt_buckets=(8, 16),
              decode_buckets=(1, 2, 4), kv_blocks=32, kv_block_size=8)
    prompts = [[3, 5, 7], [2, 4, 6, 8, 10, 1], [9] * 11]

    ref = GenerationEngine(params=dict(lm_params), **kw)
    ref.collect_logits = True
    ref_tokens = ref.generate(prompts)
    ref_logits = ref.last_logits

    eng = GenerationEngine(params=dict(lm_params), quantize="int8", **kw)
    assert eng.serving_dtype == "int8"
    eng.collect_logits = True
    before = program_registry_stats()["lowerings"]
    q_tokens = eng.generate(prompts)
    assert program_registry_stats()["lowerings"] == before
    q_logits = eng.last_logits

    assert q_tokens == ref_tokens
    worst = min(_cosine(a, b)
                for rrows, qrows in zip(ref_logits, q_logits)
                for a, b in zip(rrows, qrows))
    assert worst >= 0.999, worst


def test_engine_quantize_env_and_optout(monkeypatch, lm_params):
    monkeypatch.setenv("MXTPU_QUANTIZE", "int8")
    kw = dict(vocab_size=V, num_layers=L, num_heads=H, dim=E,
              max_seq_len=S, max_new_tokens=2, prompt_buckets=(8,),
              decode_buckets=(1,), kv_blocks=16, kv_block_size=8)
    eng = GenerationEngine(params=dict(lm_params), **kw)
    assert eng.serving_dtype == "int8"
    off = GenerationEngine(params=dict(lm_params), quantize="", **kw)
    assert off.serving_dtype != "int8"


# ---------------------------------------------------------------------------
# decode attention over the paged KV cache
# ---------------------------------------------------------------------------

def test_paged_decode_attention_matches_dense():
    """The block-table gather against plain per-sequence attention over
    the same keys laid out contiguously: positions on block boundaries,
    a single token, and a full table."""
    from mxnet_tpu.ops.attention import paged_decode_attention
    b, h, d, nb, bs, mb = 4, 4, 32, 16, 8, 4
    rng = np.random.RandomState(11)
    q = rng.randn(b, h, d).astype(np.float32)
    k_pool = rng.randn(nb, bs, h, d).astype(np.float32)
    v_pool = rng.randn(nb, bs, h, d).astype(np.float32)
    table = rng.choice(nb, size=(b, mb), replace=False).astype(np.int32)
    pos = np.array([1, bs, bs + 1, mb * bs - 1], np.int32)
    got = np.asarray(paged_decode_attention(
        jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
        jnp.asarray(table), jnp.asarray(pos)))
    for i in range(b):
        n = pos[i] + 1
        keys = k_pool[table[i]].reshape(mb * bs, h, d)[:n]
        vals = v_pool[table[i]].reshape(mb * bs, h, d)[:n]
        s = np.einsum("hd,thd->ht", q[i], keys) / np.sqrt(d)
        p = np.exp(s - s.max(axis=-1, keepdims=True))
        p /= p.sum(axis=-1, keepdims=True)
        want = np.einsum("ht,thd->hd", p, vals)
        np.testing.assert_allclose(got[i], want, rtol=2e-5, atol=2e-5)


def test_engine_kernel_path(lm_params):
    kw = dict(vocab_size=V, num_layers=L, num_heads=H, dim=E,
              max_seq_len=S, max_new_tokens=3, prompt_buckets=(8,),
              decode_buckets=(1, 2), kv_blocks=16, kv_block_size=8)
    eng = GenerationEngine(params=dict(lm_params), **kw)
    assert eng.kernel_path() == "gather"
    assert eng.stats()["kernel_path"] == "gather"


# ---------------------------------------------------------------------------
# block picking and kernel-or-reference dispatch
# ---------------------------------------------------------------------------

def test_pick_block_never_falls_back_to_whole_dim():
    """A dim with no aligned divisor used to become ONE block (38 MB of
    VMEM for an LM head of 50,257 rows): now it is an aligned block with
    a trailing partial step."""
    from mxnet_tpu.kernels.common import pick_block
    assert pick_block(50257, 128, 512) == 512        # no divisor: partial
    assert pick_block(199665, 8, 512) == 512         # resnet50 bucket rows
    assert pick_block(768, 128, 512) == 384          # exact divisor wins
    assert pick_block(3072, 128, 512) == 512
    assert pick_block(100, 128, 512) == 100          # fits: whole dim


def test_quantized_matmul_ragged_edges():
    """M and N run a trailing partial block, K is zero-padded — none of
    M=300, N=1000, K=600 divides its block."""
    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.randn(300, 600).astype(np.float32))
    w_q, scale = qz.quantize_array(rng.randn(1000, 600).astype(np.float32))
    want = qz.quantized_matmul_reference(x, jnp.asarray(w_q),
                                         jnp.asarray(scale))
    got = qz.quantized_matmul(x, jnp.asarray(w_q), jnp.asarray(scale),
                              interpret=True)
    assert got.shape == (300, 1000)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-3)


def test_kernel_dispatch_follows_placement():
    """Each default-path kernel is chosen by the platform its step is
    lowered FOR: Mosaic in the TPU lowering, none in the cpu lowering —
    which runs, and equals the reference."""
    rng = np.random.RandomState(6)
    x = jnp.asarray(rng.randn(8, 256).astype(np.float32))
    w_q, scale = qz.quantize_array(rng.randn(384, 256).astype(np.float32))
    w_q, scale = jnp.asarray(w_q), jnp.asarray(scale)
    qmm = jax.jit(qz.quantized_matmul)
    opt, params, grads, state = _leaf_case("sgd")

    def sweep(params, grads, state):
        return fo.fused_apply(opt, params, grads, state, 0.05, 0.0, 2.0,
                              mode="kernel")

    for fn, args in ((qmm, (x, w_q, scale)),
                     (jax.jit(sweep), (params, grads, state))):
        assert "tpu_custom_call" in tpu_lowering_text(fn, *args)
        assert "tpu_custom_call" not in fn.lower(*args).as_text()
    np.testing.assert_array_equal(
        np.asarray(qmm(x, w_q, scale)),
        np.asarray(qz.quantized_matmul_reference(x, w_q, scale)))
    got_w, _ = jax.jit(sweep)(params, grads, state)
    want_w, _ = jax.jit(lambda p, g, s: fo.fused_apply(
        opt, p, g, s, 0.05, 0.0, 2.0, mode="1"))(params, grads, state)
    for n in params:
        np.testing.assert_array_equal(np.asarray(got_w[n]),
                                      np.asarray(want_w[n]))


# ---------------------------------------------------------------------------
# fused optimizer sweep
# ---------------------------------------------------------------------------

def test_fused_opt_mode_parsing(monkeypatch):
    monkeypatch.delenv("MXTPU_FUSED_OPT", raising=False)
    assert fo.fused_opt_mode() == ""
    monkeypatch.setenv("MXTPU_FUSED_OPT", "1")
    assert fo.fused_opt_mode() == "1"
    monkeypatch.setenv("MXTPU_FUSED_OPT", "kernel")
    assert fo.fused_opt_mode() == "kernel"
    assert fo.fused_opt_mode("") == ""          # explicit beats env
    with pytest.raises(MXNetError):
        fo.fused_opt_mode("bogus")


def test_supports_fused_elementwise_only():
    assert fo.supports_fused(mx.optimizer.create("sgd"))
    assert fo.supports_fused(mx.optimizer.create("adam"))
    assert fo.supports_fused(mx.optimizer.create("nag"))
    assert not fo.supports_fused(mx.optimizer.create("lamb"))
    assert not fo.supports_fused(mx.optimizer.create("sgld"))
    with pytest.raises(MXNetError):
        fo.fused_apply(mx.optimizer.create("lamb"), {}, {}, {}, 0.1,
                       0.0, 1)


def test_plan_buckets_covers_and_splits_by_dtype():
    params = {"a": jnp.zeros((4, 4), jnp.float32),
              "b": jnp.zeros((8,), jnp.float32),
              "c": jnp.zeros((2, 2), jnp.bfloat16)}
    buckets = fo.plan_buckets(params)
    flat = sorted(n for b in buckets for n in b)
    assert flat == ["a", "b", "c"]
    for bucket in buckets:
        dts = {str(params[n].dtype) for n in bucket}
        assert len(dts) == 1


def _leaf_case(opt_name, seed=9):
    opt = mx.optimizer.create(opt_name, learning_rate=0.05)
    rng = np.random.RandomState(seed)
    shapes = {"w0": (5,), "w1": (3, 7), "w2": (2, 4, 8), "w3": (129,)}
    params = {n: jnp.asarray(rng.randn(*s).astype(np.float32))
              for n, s in shapes.items()}
    grads = {n: jnp.asarray(rng.randn(*s).astype(np.float32))
             for n, s in shapes.items()}
    state = {n: opt.create_state_arrays(s, jnp.float32)
             for n, s in shapes.items()}
    return opt, params, grads, state


@pytest.mark.parametrize("opt_name", ["sgd", "adam"])
def test_fused_apply_bit_identical_to_leafwise(opt_name):
    """Fused concat-update-slice == per-leaf tree-map, bitwise, even
    with tiny buckets forcing several sweeps per dtype group."""
    opt, params, grads, state = _leaf_case(opt_name)
    lr, wd, t = 0.05, 0.01, jnp.asarray(3.0, jnp.float32)
    want_w, want_s = {}, {}
    for n in params:
        want_w[n], want_s[n] = opt.update_fn(params[n], grads[n],
                                             state[n], lr, wd, t)
    got_w, got_s = fo.fused_apply(opt, params, grads, state, lr, wd, t,
                                  nbytes=256, mode="1")
    for n in params:
        np.testing.assert_array_equal(np.asarray(got_w[n]),
                                      np.asarray(want_w[n]))
        a = jax.tree_util.tree_leaves(want_s[n])
        b = jax.tree_util.tree_leaves(got_s[n])
        assert len(a) == len(b)
        for x, y in zip(a, b):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_fused_apply_kernel_mode_matches_xla_mode():
    """The Pallas sweep (interpret) over padded (rows, 128) sheets must
    agree bitwise with the plain fused XLA path — the padding rows drop
    cleanly on unflatten."""
    opt, params, grads, state = _leaf_case("adam", seed=13)
    w1, s1 = fo.fused_apply(opt, params, grads, state, 0.05, 0.0, 2.0,
                            mode="1")
    w2, s2 = fo.fused_apply(opt, params, grads, state, 0.05, 0.0, 2.0,
                            mode="kernel", interpret=True)
    for n in params:
        np.testing.assert_array_equal(np.asarray(w1[n]),
                                      np.asarray(w2[n]))
        for x, y in zip(jax.tree_util.tree_leaves(s1[n]),
                        jax.tree_util.tree_leaves(s2[n])):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _mlp():
    data = mx.sym.Variable("data")
    fc1 = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    act = mx.sym.Activation(fc1, act_type="relu")
    fc2 = mx.sym.FullyConnected(act, num_hidden=4, name="fc2")
    return mx.sym.SoftmaxOutput(fc2, name="softmax")


def test_trainer_fused_opt_bit_identical_on_mesh(monkeypatch):
    """MXTPU_FUSED_OPT=1 on the dp=8 mesh: params AND optimizer state
    bitwise equal to the per-leaf tree-map path after several steps —
    the acceptance criterion for the fused step."""
    net = _mlp()

    def run(fused):
        if fused:
            monkeypatch.setenv("MXTPU_FUSED_OPT", "1")
        else:
            monkeypatch.delenv("MXTPU_FUSED_OPT", raising=False)
        opt = mx.optimizer.create("sgd", learning_rate=0.1,
                                  momentum=0.9, rescale_grad=1.0 / 16)
        tr = parallel.ShardedTrainer(net, opt, parallel.auto_mesh())
        assert tr._fused_opt == ("1" if fused else "")
        mx.random.seed(7)
        params, opt_state, aux = tr.init_params(
            {"data": (16, 8)}, label_shapes={"softmax_label": (16,)})
        rng = np.random.RandomState(1)
        x = rng.randn(16, 8).astype(np.float32)
        y = (rng.rand(16) * 4).astype(np.float32)
        batch = tr.shard_batch({"data": x, "softmax_label": y})
        for _ in range(4):
            params, opt_state, aux, _outs = tr.step(params, opt_state,
                                                    aux, batch)
        return ({k: np.asarray(v) for k, v in params.items()},
                jax.tree_util.tree_map(np.asarray, opt_state))

    p_ref, s_ref = run(fused=False)
    p_fused, s_fused = run(fused=True)
    for k in p_ref:
        np.testing.assert_array_equal(p_ref[k], p_fused[k])
    a = jax.tree_util.tree_leaves(s_ref)
    b = jax.tree_util.tree_leaves(s_fused)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)


def test_trainer_lamb_refuses_fused(monkeypatch):
    monkeypatch.setenv("MXTPU_FUSED_OPT", "1")
    opt = mx.optimizer.create("lamb", learning_rate=0.01)
    tr = parallel.ShardedTrainer(_mlp(), opt, parallel.auto_mesh())
    assert tr._fused_opt == ""


# ---------------------------------------------------------------------------
# MXL-K coverage of the new kernel specs
# ---------------------------------------------------------------------------

def test_kernel_specs_registered_and_lint_clean():
    from mxnet_tpu.analysis.tiling import (KERNEL_SPECS,
                                           _ensure_builtin_specs,
                                           kernel_spec_issues)
    _ensure_builtin_specs()
    for name in ("kernels.quantize.quantized_matmul",
                 "kernels.fused_opt.sweep"):
        assert name in KERNEL_SPECS, name
    assert kernel_spec_issues() == []


def test_mis_tiled_qmm_spec_is_flagged():
    """A deliberately regressed copy of the quantized-matmul spec — the
    out block shrunk to a PARTIAL 64-lane tile — must trip MXL-K002
    while the registered spec stays clean."""
    from mxnet_tpu.analysis import analyze
    from mxnet_tpu.analysis.tiling import (register_kernel_spec,
                                           unregister_kernel_spec)
    bad = qz.qmm_kernel_spec()
    for blk in bad["blocks"]:
        if blk["role"] == "out":
            blk["block"] = (blk["block"][0], 64)    # 64 < lane granule
            blk["array"] = (blk["array"][0], 1024)  # ...and partial
    register_kernel_spec("test.qmm_mis_tiled", bad)
    try:
        issues = analyze(None, select={"MXL-K002"})
        hits = [i for i in issues if i.rule_id == "MXL-K002"]
        assert hits and any("out" in i.message for i in hits), issues
    finally:
        unregister_kernel_spec("test.qmm_mis_tiled")
    assert not analyze(None, select={"MXL-K*"})     # registry clean again


# ---------------------------------------------------------------------------
# benchdiff: the decode-regression fixture
# ---------------------------------------------------------------------------

def test_benchdiff_flags_decode_regression(tmp_path):
    """The sentry contract for the quantized-serving BENCH line: a
    simulated 20% tokens/sec drop against the committed-schema baseline
    exits 1; matching or improved throughput exits 0."""
    baseline = {"n": 6, "cmd": "serve_bench --generate", "rc": 0,
                "parsed": {"metric": "serve_tokens_per_sec",
                           "value": 1000.0, "unit": "tok/s",
                           "ttft_ms": {"p50": 2.0, "p95": 9.0},
                           "itl_ms": {"p50": 1.0, "p95": 3.0}}}
    bpath = str(tmp_path / "BENCH_gen.json")
    with open(bpath, "w") as f:
        json.dump(baseline, f)

    def run(metrics):
        return subprocess.run(
            [sys.executable, os.path.join(_ROOT, "tools", "benchdiff.py"),
             "--baseline", bpath, "--metrics", json.dumps(metrics)],
            cwd=_ROOT, capture_output=True, text=True, timeout=180)

    proc = run({"serve_tokens_per_sec": 800.0})     # -20%: flags
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "serve_tokens_per_sec" in proc.stdout
    proc = run({"serve_tokens_per_sec": 1000.0, "serve_ttft_ms_p95": 9.0})
    assert proc.returncode == 0, proc.stdout + proc.stderr
    proc = run({"serve_tokens_per_sec": 1200.0,     # faster but ttft blew up
                "serve_ttft_ms_p95": 12.0})
    assert proc.returncode == 1, proc.stdout + proc.stderr
