"""Smoke tests over the example/ tree (parity: tests/python/train)."""
import pytest
import os
import subprocess
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(cwd, *argv, timeout=420):
    env = dict(os.environ)
    # hermetic: this checkout only, on the cpu
    env["PYTHONPATH"] = REPO
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable] + list(argv), cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=timeout)


def test_train_mnist_synthetic():
    r = _run(os.path.join(REPO, "example/image-classification"),
             "train_mnist.py", "--network", "mlp", "--num-epochs", "1",
             "--batch-size", "64", "--synthetic", "--lr", "0.05")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "Train-accuracy" in (r.stderr + r.stdout)


def test_rcnn_end2end_smoke():
    r = _run(os.path.join(REPO, "example/rcnn"), "train_end2end.py",
             "--steps", "1", "--image-size", "64", "--rois", "8")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "smoke OK" in (r.stderr + r.stdout)


def test_bucket_sentence_iter():
    sys.path.insert(0, os.path.join(REPO, "example/rnn"))
    try:
        from bucket_io import BucketSentenceIter, synthetic_corpus
    finally:
        sys.path.pop(0)
    sents = synthetic_corpus(num_sentences=100, vocab_size=30)
    it = BucketSentenceIter(sents, batch_size=8, buckets=[8, 16, 24, 32])
    seen = 0
    for batch in it:
        seen += 1
        assert batch.data[0].shape == (8, batch.bucket_key)
        lbl = batch.label[0].asnumpy()
        dat = batch.data[0].asnumpy()
        np.testing.assert_allclose(lbl[:, :-1], dat[:, 1:])
    assert seen > 0


def test_gan_example_learns():
    """example/gan/dcgan.py: adversarial Modules (G trained through D's
    input grads) — the generator must spread toward the data mixture."""
    import importlib.util
    import os
    path = os.path.join(os.path.dirname(__file__), "..", "example", "gan",
                        "dcgan.py")
    spec = importlib.util.spec_from_file_location("gan_example", path)
    gan = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gan)
    # train() pins all RNGs from `seed`, so this run is order-independent
    samples, _ = gan.train(epochs=300, seed=0, log=False)
    std = samples.std(axis=0)
    # data mixture spread is ~(2.0, 1.0); collapsed generators sit near 0
    assert std[0] > 0.5 and std[1] > 0.25, std


def test_opencv_plugin_roundtrip():
    import numpy as np
    from mxnet_tpu.plugin import opencv as cv
    from mxnet_tpu.image import imencode
    img = np.random.RandomState(0).randint(0, 255, (24, 32, 3), np.uint8)
    buf = imencode(img, img_fmt=".png")
    dec = cv.imdecode(buf)
    assert dec.shape == (24, 32, 3)
    np.testing.assert_array_equal(dec.asnumpy(), img)   # png is lossless
    small = cv.imresize(dec, 16, 12)
    assert small.shape == (12, 16, 3)
    padded = cv.copy_make_border(dec, 2, 2, 3, 3, fill_value=7)
    assert padded.shape == (28, 38, 3)
    assert (padded.asnumpy()[:2] == 7).all()


def _load_example(rel, name):
    import importlib.util
    import os
    path = os.path.join(os.path.dirname(__file__), "..", "example", rel)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_autoencoder_example_reconstructs():
    ae = _load_example("autoencoder/autoencoder.py", "ae_example")
    mse, power = ae.train(epochs=15)
    assert mse < 0.1 * power, (mse, power)


def test_adversary_fgsm_example():
    fg = _load_example("adversary/fgsm.py", "fgsm_example")
    clean, adv = fg.run(eps=0.3, epochs=6)
    assert clean > 0.9
    assert adv < clean - 0.2, (clean, adv)


def test_neural_style_example_descends():
    ns = _load_example("neural-style/neural_style.py", "ns_example")
    hist = ns.run(steps=40)
    assert hist[-1] < hist[0] * 0.5, (hist[0], hist[-1])


def test_stochastic_depth_example():
    """Module-level residual gating (reference example/stochastic-depth):
    SequentialModule of StochasticDepthModules learns, and eval runs with
    every block active."""
    r = _run(os.path.join(REPO, "example/stochastic-depth"),
             "sd_mnist.py", "--epochs", "6")
    assert r.returncode == 0, r.stderr[-1500:]
    assert "final eval-acc" in r.stdout


def test_warpctc_example():
    """CTC training (reference example/warpctc): loss descends and greedy
    decode recovers the labels exactly."""
    r = _run(os.path.join(REPO, "example/warpctc"), "lstm_ocr.py")
    assert r.returncode == 0, r.stderr[-1500:]
    assert "OK warpctc example" in r.stdout


def test_caffe_example():
    """CaffeOp/CaffeLoss net + converted prototxt net both train."""
    r = _run(os.path.join(REPO, "example/caffe"), "caffe_net.py")
    assert r.returncode == 0, r.stderr[-1500:]
    assert "OK caffe example" in r.stdout


def test_torch_example():
    """torch module + criterion embedded in a native graph co-train."""
    r = _run(os.path.join(REPO, "example/torch"), "torch_net.py")
    assert r.returncode == 0, r.stderr[-1500:]
    assert "OK torch example" in r.stdout


def test_svm_example():
    """SVMOutput hinge-loss head trains (reference example/svm_mnist)."""
    r = _run(os.path.join(REPO, "example/svm_mnist"), "svm_mnist.py")
    assert r.returncode == 0, r.stderr[-1500:]
    assert "OK svm example" in r.stdout


def test_multitask_example():
    """Two loss heads via sym.Group + per-head metric."""
    r = _run(os.path.join(REPO, "example/multi-task"), "multitask_mlp.py")
    assert r.returncode == 0, r.stderr[-1500:]
    assert "OK multi-task example" in r.stdout


def test_module_example():
    """Explicit bind/forward/backward/update loop + fit with checkpoint
    and resume (reference example/module)."""
    r = _run(os.path.join(REPO, "example/module"), "mnist_mlp.py")
    assert r.returncode == 0, r.stderr[-1500:]
    assert "OK module example" in r.stdout


def test_bilstm_sort_example():
    """Bidirectional RNN learns to sort (reference example/bi-lstm-sort)."""
    r = _run(os.path.join(REPO, "example/bi-lstm-sort"), "sort_io.py")
    assert r.returncode == 0, r.stderr[-1500:]
    assert "OK bi-lstm-sort example" in r.stdout


def test_sgld_example():
    """SGLD posterior sampling: mean near truth, nonzero spread."""
    r = _run(os.path.join(REPO, "example/bayesian-methods"), "sgld_demo.py")
    assert r.returncode == 0, r.stderr[-1500:]
    assert "OK sgld example" in r.stdout


def test_text_cnn_example():
    """Kim-CNN text classifier (reference example/cnn_text_classification)."""
    r = _run(os.path.join(REPO, "example/cnn_text_classification"),
             "text_cnn.py")
    assert r.returncode == 0, r.stderr[-1500:]
    assert "OK text-cnn example" in r.stdout


def test_fcn_example():
    """FCN segmentation: Deconvolution (bilinear-init) + Crop +
    multi_output softmax trained end-to-end (reference example/fcn-xs)."""
    r = _run(os.path.join(REPO, "example/fcn-xs"), "fcn_toy.py")
    assert r.returncode == 0, r.stderr[-1500:]
    assert "OK fcn example" in r.stdout


def test_nce_example():
    """NCE: true class outscores sampled noise via per-candidate logistic
    losses over Embedding + batch_dot (reference example/nce-loss)."""
    r = _run(os.path.join(REPO, "example/nce-loss"), "nce_demo.py")
    assert r.returncode == 0, r.stderr[-1500:]
    assert "OK nce example" in r.stdout


def test_dec_example():
    """Deep Embedded Clustering: AE pretrain + KL refinement with an
    external cotangent improves cluster accuracy (reference example/dec)."""
    r = _run(os.path.join(REPO, "example/dec"), "dec_toy.py")
    assert r.returncode == 0, r.stderr[-1500:]
    assert "OK dec example" in r.stdout


def test_glregression_example():
    """Linear/logistic/MAE regression heads (reference example/GLRegression)."""
    r = _run(os.path.join(REPO, "example/GLRegression"), "glregression.py")
    assert r.returncode == 0, r.stderr[-1500:]
    assert "OK glregression example" in r.stdout


def test_mlloss_example():
    """Contrastive metric loss via MakeLoss + siamese shared weights
    (reference example/MLLoss)."""
    r = _run(os.path.join(REPO, "example/MLLoss"), "metric_loss.py")
    assert r.returncode == 0, r.stderr[-1500:]
    assert "OK mlloss example" in r.stdout


def test_python_howto_scripts():
    """The three how-to walkthroughs run clean (reference
    example/python-howto): custom DataIter, Monitor stats, multi-output
    symbols + get_internals."""
    for script, marker in [("data_iter.py", "OK data_iter howto"),
                           ("monitor_weights.py", "OK monitor howto"),
                           ("multiple_outputs.py",
                            "OK multiple_outputs howto")]:
        r = _run(os.path.join(REPO, "example/python-howto"), script)
        assert r.returncode == 0, (script, r.stderr[-1200:])
        assert marker in r.stdout, script


def test_rtc_example():
    """Runtime-compiled Pallas / traceable kernels on NDArrays."""
    r = _run(os.path.join(REPO, "example/rtc"), "pallas_kernel.py")
    assert r.returncode == 0, r.stderr[-1500:]
    assert "OK rtc example" in r.stdout


def test_moe_example():
    """Expert-parallel MoE training over a dp x ep mesh."""
    r = _run(os.path.join(REPO, "example/moe"), "moe_ep.py")
    assert r.returncode == 0, r.stderr[-1500:]
    assert "OK moe example" in r.stdout


def test_cpp_predict_example(tmp_path):
    """example/cpp: standalone C++ predictor over the MXPred ABI (role
    parity: reference example/cpp/image-classification)."""
    import shutil
    if shutil.which("g++") is None or shutil.which("make") is None:
        import pytest
        pytest.skip("no native toolchain")
    build = subprocess.run(["make", "-s", "capi"], cwd=REPO,
                           capture_output=True, text=True, timeout=300)
    if build.returncode != 0 and "Python.h" in (build.stderr or ""):
        import pytest
        pytest.skip("python headers unavailable")
    assert build.returncode == 0, build.stderr[-1500:]

    ex_dir = os.path.join(REPO, "example/cpp/image-classification")
    build = subprocess.run(["make", "-s"], cwd=ex_dir, capture_output=True,
                           text=True, timeout=300)
    assert build.returncode == 0, build.stderr[-1500:]

    import json
    import mxnet_tpu as mx
    sym = mx.models.get_mlp(num_classes=10, hidden=(16,))
    mod = mx.mod.Module(sym, context=mx.context.cpu())
    mod.bind(data_shapes=[("data", (1, 32))],
             label_shapes=[("softmax_label", (1,))])
    mod.init_params(mx.init.Xavier())
    mod.save_checkpoint(str(tmp_path / "mlp"), 0)
    (tmp_path / "shapes.json").write_text(json.dumps({"data": [1, 32]}))

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    r = subprocess.run(
        [os.path.join(ex_dir, "image-classification-predict"),
         str(tmp_path / "mlp-symbol.json"),
         str(tmp_path / "mlp-0000.params"),
         str(tmp_path / "shapes.json")],
        env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, (r.stdout, r.stderr[-1500:])
    assert "CPP PREDICT OK" in r.stdout
    assert "predicted class:" in r.stdout


def test_notebooks_execute(tmp_path):
    """example/notebooks: every code cell runs top-to-bottom (role
    parity: the reference's notebook tutorials, kept executable)."""
    import json
    import glob
    nbs = sorted(glob.glob(os.path.join(REPO, "example/notebooks/*.ipynb")))
    assert len(nbs) >= 2
    for path in nbs:
        nb = json.load(open(path))
        code = "\n\n".join(
            "".join(c["source"]) for c in nb["cells"]
            if c["cell_type"] == "code")
        script = tmp_path / (os.path.basename(path) + ".py")
        script.write_text(code)
        r = _run(str(tmp_path), str(script))
        assert r.returncode == 0, (path, r.stderr[-2000:])


def test_gru_bucketing_example():
    """example/rnn/gru_bucketing.py trains hermetically on the synthetic
    corpus (GRU cell parity with the reference's gru_bucketing)."""
    r = _run(os.path.join(REPO, "example/rnn"), "gru_bucketing.py",
             "--num-epochs", "1", "--batch-size", "8", "--num-hidden",
             "16", "--num-embed", "16", "--num-gru-layer", "1",
             "--buckets", "8,16")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "Perplexity" in (r.stderr + r.stdout)


def test_lstm_inference_model_matches_unrolled():
    """rnn_model.py: stepwise stateful inference reproduces the
    unrolled network's per-position distributions exactly (states carry
    correctly through the one-step executor)."""
    import importlib.util
    import mxnet_tpu as mx
    from mxnet_tpu.models.lstm import lstm_unroll

    spec = importlib.util.spec_from_file_location(
        "rnn_model", os.path.join(REPO, "example/rnn/rnn_model.py"))
    rnn_model = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(rnn_model)

    V, H, E, L, S = 30, 12, 8, 2, 5
    rng = np.random.RandomState(1)

    unrolled = lstm_unroll(L, S, V, num_hidden=H, num_embed=E, num_label=V)
    shapes = {"data": (1, S), "softmax_label": (1, S)}
    shapes.update({"l%d_init_c" % i: (1, H) for i in range(L)})
    shapes.update({"l%d_init_h" % i: (1, H) for i in range(L)})
    exe = unrolled.simple_bind(mx.context.cpu(), grad_req="null", **shapes)
    weights = {}
    for name, arr in exe.arg_dict.items():
        if name in ("data", "softmax_label") or name.endswith(
                ("_init_c", "_init_h")):
            continue
        w = rng.uniform(-0.2, 0.2, arr.shape).astype(np.float32)
        arr[:] = w
        weights[name] = mx.nd.array(w)
    toks = rng.randint(0, V, size=S).astype(np.float32)
    exe.arg_dict["data"][:] = toks[None, :]
    want = exe.forward()[0].asnumpy()          # (S, V): row t = position t

    model = rnn_model.LSTMInferenceModel(L, V, H, E, V,
                                         arg_params=weights)
    for t in range(S):
        got = model.forward(np.array([toks[t]], np.float32),
                            new_seq=(t == 0))[0]
        assert np.allclose(got, want[t], atol=1e-5), t


@pytest.mark.slow
def test_memcost_mirroring_example():
    """Activation recompute demo (reference example/memcost): asserts the
    mirrored step recomputes in backward, shrinks the fwd->bwd residual
    set, and leaves numerics unchanged — a demo that CAN fail."""
    r = _run(os.path.join(REPO, "example/memcost"),
             "inception_memcost.py", "--batch-size", "2",
             "--image-size", "64")
    assert r.returncode == 0, r.stderr[-1500:]
    assert "memcost demo OK" in r.stderr + r.stdout


def test_gpipe_example():
    """Pipeline-parallel LM demo: pipelined == sequential, trains."""
    r = _run(os.path.join(REPO, "example/pipeline"), "gpipe_lm.py",
             "--steps", "15")
    assert r.returncode == 0, r.stderr[-1500:]
    assert "gpipe demo OK" in r.stderr + r.stdout


def test_long_context_example():
    """Long-context LM demo: sp ring attention == single-device
    numerics, per-layer remat shrinks residuals, trains."""
    r = _run(os.path.join(REPO, "example/long-context"),
             "train_lm_long.py", "--steps", "10")
    assert r.returncode == 0, r.stderr[-1500:]
    assert "long-context demo OK" in r.stderr + r.stdout
