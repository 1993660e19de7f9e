"""C ABI smoke test: builds lib/libmxtpu_capi.so + a real C consumer
(tests/capi/capi_smoke.c) and runs it — the proof that the reference's
language-binding story (c_api.h over opaque handles) survives the TPU
rewrite.  Skips cleanly when no compiler/python headers are available.
"""
import os
import shutil
import subprocess
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.skipif(shutil.which("g++") is None or
                    shutil.which("make") is None,
                    reason="no native toolchain")
def test_capi_smoke(tmp_path):
    build = subprocess.run(["make", "-s", "lib/capi_smoke"], cwd=_ROOT,
                           capture_output=True, text=True, timeout=300)
    if build.returncode != 0 and "Python.h" in (build.stderr or ""):
        pytest.skip("python headers unavailable")
    assert build.returncode == 0, build.stderr[-2000:]

    # a symbol + params for the bind/forward and predictor legs
    import mxnet_tpu as mx
    sym = mx.models.get_mlp(num_classes=2, hidden=(8,))
    sym_path = str(tmp_path / "mlp-symbol.json")
    sym.save(sym_path)
    mod = mx.mod.Module(sym, context=mx.cpu())
    mod.bind(data_shapes=[("data", (2, 10))],
             label_shapes=[("softmax_label", (2,))])
    mod.init_params(mx.init.Uniform(0.1))
    mod.save_checkpoint(str(tmp_path / "mlp"), 0)

    env = dict(os.environ)
    env["MXTPU_SYMBOL_JSON"] = sym_path
    env["MXTPU_PARAMS_FILE"] = str(tmp_path / "mlp-0000.params")
    env["PYTHONPATH"] = _ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([os.path.join(_ROOT, "lib", "capi_smoke")],
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 0, (proc.stdout, proc.stderr[-1500:])
    assert "CAPI SMOKE OK" in proc.stdout
    assert "forward:" in proc.stdout
    assert "predict:" in proc.stdout


@pytest.mark.skipif(shutil.which("g++") is None or
                    shutil.which("make") is None,
                    reason="no native toolchain")
def test_capi_threads():
    """Second-thread MX* calls must not deadlock (the embedded
    interpreter's startup GIL is parked) and per-thread last-error stays
    isolated (TLS contract)."""
    build = subprocess.run(["make", "-s", "lib/capi_threads"], cwd=_ROOT,
                           capture_output=True, text=True, timeout=300)
    if build.returncode != 0 and "Python.h" in (build.stderr or ""):
        pytest.skip("python headers unavailable")
    assert build.returncode == 0, build.stderr[-2000:]
    env = dict(os.environ)
    env["PYTHONPATH"] = _ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([os.path.join(_ROOT, "lib", "capi_threads")],
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 0, (proc.stdout, proc.stderr[-1500:])
    assert "CAPI THREADS OK" in proc.stdout


@pytest.mark.skipif(shutil.which("g++") is None or
                    shutil.which("make") is None,
                    reason="no native toolchain")
def test_capi_parity(tmp_path):
    """The reference-surface completion: every remaining MX* family —
    NDArray extras, symbol listing/CSR inference/grad, atomic-symbol
    info, func describe/invoke-ex, full Bind + monitor, kvstore
    roles/server loop, data-iter index, Rtc, and a custom op implemented
    entirely in C through the CustomOpPropCreator struct protocol."""
    build = subprocess.run(["make", "-s", "lib/capi_parity"], cwd=_ROOT,
                           capture_output=True, text=True, timeout=300)
    if build.returncode != 0 and "Python.h" in (build.stderr or ""):
        pytest.skip("python headers unavailable")
    assert build.returncode == 0, build.stderr[-2000:]

    import mxnet_tpu as mx
    sym = mx.models.get_mlp(num_classes=2, hidden=(8,))
    sym_path = str(tmp_path / "mlp-symbol.json")
    sym.save(sym_path)
    mod = mx.mod.Module(sym, context=mx.context.cpu())
    mod.bind(data_shapes=[("data", (2, 10))],
             label_shapes=[("softmax_label", (2,))])
    mod.init_params(mx.init.Uniform(0.1))
    mod.save_checkpoint(str(tmp_path / "mlp"), 0)

    env = dict(os.environ)
    env["MXTPU_SYMBOL_JSON"] = sym_path
    env["MXTPU_PARAMS_FILE"] = str(tmp_path / "mlp-0000.params")
    env["MXTPU_SCRATCH"] = str(tmp_path)
    env["PYTHONPATH"] = _ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([os.path.join(_ROOT, "lib", "capi_parity")],
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, (proc.stdout, proc.stderr[-2000:])
    assert "capi_parity OK" in proc.stdout


def test_attr_listing_reference_format():
    """Deep attr keys use the reference's '_' namespace separator
    (symbol.cc:19,526) and propagate node attrs onto aux-state names
    (symbol.cc:532-538) — the wire format C consumers parse."""
    import mxnet_tpu as mx
    from mxnet_tpu import capi_impl

    data = mx.sym.Variable("data")
    bn = mx.sym.BatchNorm(data, name="bn0", attr={"ctx_group": "dev1"})
    pairs = capi_impl.symbol_attr_pairs(bn, deep=1)
    d = dict(zip(pairs[0::2], pairs[1::2]))
    assert d.get("bn0_ctx_group") == "dev1"
    # aux propagation: every aux state of bn0 carries the node's attrs
    for aux in ("moving_mean", "moving_var"):
        assert d.get("bn0_%s_ctx_group" % aux) == "dev1", sorted(d)
    assert not any("$" in k for k in d)


def test_infer_type_complete_includes_aux():
    """MXSymbolInferType's complete flag must account for aux states."""
    import mxnet_tpu as mx
    from mxnet_tpu import capi_impl

    data = mx.sym.Variable("data")
    net = mx.sym.BatchNorm(mx.sym.FullyConnected(
        data, num_hidden=4, name="fc"), name="bn0")
    _arg, _out, aux_t, complete = capi_impl.symbol_infer_type_arrays(
        net, ["data"], [0])        # 0 = float32 flag
    # all aux inferable here -> complete stays 1 and auxes are typed
    assert complete == 1 and all(t != -1 for t in aux_t)
