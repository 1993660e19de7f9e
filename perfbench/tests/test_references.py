"""The plain references meet the program at a tiny size on the CPU, so that
the comparison on the chip is not the first time they do.

With the program computing in float32 (the configuration's bfloat16 switched
off for the test) program and reference are the same mathematics: losses,
first gradients and three steps' change agree to rounding.  With bfloat16 on,
as the cells run it, they agree to bfloat16's rounding.
"""
from helpers import tiny_driver

F32 = {"training": {"compute_dtype": "float32"}}


def _gaps(driver):
    from perfbench.drivers.train_fit import training_values
    driver.setup()
    prog = driver.program_readings()
    driver.release()
    return training_values(prog, driver.reference_readings())


def test_lm_training_reference_matches_program_in_float32():
    gaps = _gaps(tiny_driver("gpt2m_train_s1024", config_override=F32))
    assert max(gaps.values()) < 1e-3, gaps


def test_resnet_training_reference_matches_program_in_float32():
    # 16 images of 128 x 128: large enough that BatchNorm has samples to
    # average over.  The forward pass, the median leaf and the worst of the
    # convolution and classifier weights read as the same mathematics
    # should: a wrong formula (weight decay left off a leaf, a stride in the
    # wrong place) reads as several per cent or more on them.  The worst
    # leaf of all is an early BatchNorm gain or shift, whose gradient a
    # freshly initialised 50-layer network amplifies rounding into: float32
    # rounding alone moves its norm by up to 6e-3 here, and three steps at
    # lr 0.1 carry that to 0.02-0.05 on its change, which is why the cell
    # compares the median leaf and the worst weight leaf (PERF.md section 2).
    big = {"training": {"compute_dtype": "float32"}, "image_size": 128,
           "program": {"symbol_args": {"image_shape": [3, 128, 128]}}}
    gaps = _gaps(tiny_driver("resnet50_fit_b256", config_override=big,
                             cell_override={"traffic": {"batch": 16}}))
    assert gaps["loss_gap_step1"] < 1e-5, gaps
    assert gaps["logprob_diff"] < 1e-4, gaps
    assert gaps["grad_norm_gap_median"] < 2e-3, gaps
    assert gaps["grad_norm_gap_matrices"] < 2e-3, gaps
    assert gaps["delta_norm_gap_median"] < 1e-2, gaps
    assert gaps["delta_norm_gap_matrices"] < 2e-2, gaps
    assert gaps["grad_norm_gap"] < 2e-2, gaps
    assert gaps["delta_norm_gap"] < 0.2, gaps


def test_lm_training_reference_matches_program_in_bfloat16():
    gaps = _gaps(tiny_driver("gpt2m_train_s1024"))
    norms = {k: v for k, v in gaps.items() if "diff" not in k}
    assert max(norms.values()) < 5e-2, gaps
    # first-order in bfloat16's rounding, and ReLU decisions flip
    assert gaps["row_loss_diff"] < 4e-2, gaps


def test_lm_reference_follows_weight_decay():
    # the first gradient is read from the momentum as g = -m1/lr - wd*w0:
    # with weight decay on, program and reference still agree in float32
    gaps = _gaps(tiny_driver("gpt2m_train_s1024", config_override={
        "training": {"compute_dtype": "float32", "wd": 0.01}}))
    assert max(gaps.values()) < 1e-3, gaps
