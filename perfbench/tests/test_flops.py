"""``flops.py`` against hand counts, and ``peaks.json``."""
import pytest

from perfbench import common, flops

RESNET = common.load_json(common.named_file("configs", "resnet50"))
GPT2M = common.load_json(common.named_file("configs", "gpt2-medium"))


def test_resnet50_forward_macs_by_hand():
    # stem: 112*112 outputs x 64 filters x (3*7*7)
    stem = 112 * 112 * 64 * 147
    # stage 1 at 56x56, in 64 -> mid 64 -> out 256, three units
    s1 = 56 * 56 * (64 * 64 + 64 * 64 * 9 + 64 * 256 + 64 * 256) \
        + 2 * 56 * 56 * (256 * 64 + 64 * 64 * 9 + 64 * 256)
    # stage 2: first unit reduces at 56x56, then 28x28; mid 128, out 512
    s2 = 56 * 56 * 256 * 128 + 28 * 28 * (128 * 128 * 9 + 128 * 512
                                           + 256 * 512) \
        + 3 * 28 * 28 * (512 * 128 + 128 * 128 * 9 + 128 * 512)
    s3 = 28 * 28 * 512 * 256 + 14 * 14 * (256 * 256 * 9 + 256 * 1024
                                           + 512 * 1024) \
        + 5 * 14 * 14 * (1024 * 256 + 256 * 256 * 9 + 256 * 1024)
    s4 = 14 * 14 * 1024 * 512 + 7 * 7 * (512 * 512 * 9 + 512 * 2048
                                          + 1024 * 2048) \
        + 2 * 7 * 7 * (2048 * 512 + 512 * 512 * 9 + 512 * 2048)
    fc = 2048 * 1000
    want = stem + s1 + s2 + s3 + s4 + fc
    got = flops.resnet_forward_macs(RESNET)
    assert got == want
    # the usual figure for ResNet-50 is about 4.1 G multiply-adds; this
    # arrangement runs each stage's first 1x1 before the stride (+0.2 G)
    assert 4.0e9 < got < 4.5e9
    assert flops.resnet_train_step_flops(RESNET, 256) == 6 * want * 256


def test_lm_counts_by_hand():
    e, inner, layers, vocab = 1024, 4096, 24, 50257
    params = layers * (4 * e * e + 2 * e * inner) + e * vocab
    assert flops.lm_matmul_params(GPT2M) == params == 353_453_056
    # attention: 12 L d s per token of a training step, causal half
    assert flops.lm_attention_flops_per_token(GPT2M, 1024, causal=False) \
        == 12 * layers * e * 1024
    assert flops.lm_attention_flops_per_token(GPT2M, 1024, causal=True) \
        == 6 * layers * e * 1024
    step = flops.lm_train_step_flops(GPT2M, 8, 1024)
    assert step == (6 * params + 6 * layers * e * 1024) * 8192
    assert 18.5e12 < step < 19.0e12          # ISSUE 24: "about 19.8 TF" full


def test_flash_forward_call_and_roofline():
    ops, nbytes = flops.flash_forward_call(8, 16, 1024, 1024, 64, 2,
                                           causal=True)
    assert ops == 4 * 8 * 16 * 1024 * 1024 * 64 // 2
    assert nbytes == 2 * 8 * 16 * 64 * 4096 + 4 * 8 * 16 * 1024
    t, bound = flops.roofline_seconds(ops, nbytes, 197e12, 819e9)
    assert bound == "compute" and t == pytest.approx(ops / 197e12)
    t, bound = flops.roofline_seconds(1e6, 1e9, 197e12, 819e9)
    assert bound == "memory" and t == pytest.approx(1e9 / 819e9)


def test_peaks_table():
    v5e = flops.load_peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["int8_ops_per_s"] == 393e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        flops.load_peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        flops.load_peaks("_source")


def test_a_configuration_names_its_count():
    # the reader resolves "package.module:function" and calls it with the
    # configuration and the run's counters; a bad name is an error
    from perfbench.readers import step_mfu_train
    ctx = {"config": GPT2M, "counters": {"steps": 10, "batch": 8,
                                         "seq": 1024},
           "peaks": flops.load_peaks("TPU v5 lite"), "chips": 1,
           "elapsed_s": 2.0}
    want = 100.0 * flops.lm_train_step_flops(GPT2M, 8, 1024) * 10 / 2.0 \
        / 197e12
    assert step_mfu_train.read(ctx) == pytest.approx(want)
    ctx["config"] = RESNET
    ctx["counters"] = {"steps": 4, "batch": 256}
    assert step_mfu_train.read(ctx) == pytest.approx(
        100.0 * flops.resnet_train_step_flops(RESNET, 256) * 4 / 2.0
        / 197e12)
    for bad in ("lm", "perfbench.flops:no_such_count", "no.such.module:f"):
        ctx["config"] = dict(GPT2M, flops=bad)
        with pytest.raises((ValueError, AttributeError, ImportError)):
            step_mfu_train.read(ctx)
