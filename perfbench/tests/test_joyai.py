"""What PR 27 added to the benchmark: the counts of ``flops_joyai.py`` by
hand, the reference meeting the program at the cell's rehearsal sizes, the
cell's rehearsal run, the control and planted faults reading
``correct: false``, the routing readers, and the four-chip cell's reference
over several devices against the one-device one."""
import contextlib

import numpy as np
import pytest

from helpers import tiny_driver, tiny_env

from perfbench import common, flops_joyai, run

CELL = "joyai_flash_train_s8192"
CFG = common.load_json(common.named_file("configs", "joyai-llm-flash"))
F32 = {"training": {"compute_dtype": "float32"}}


# -- the counts ------------------------------------------------------------
def test_forward_counts_by_hand():
    e, heads = 2048, 32
    proj = (e * 1536 + 1536 * heads * 192 + e * (512 + 64)
            + 512 * heads * (128 + 128) + heads * 128 * e)
    assert flops_joyai.attention_projection_macs(CFG) == proj == 26_345_472
    # 8,192 keys, causal half, 32 heads, 192 wide scores and 128 wide values
    scores = 4096 * heads * (192 + 128)
    assert flops_joyai.attention_score_macs(CFG, 8192) == scores
    assert flops_joyai.local_assignments_per_token(CFG) == 8 * 16 / 256
    routed = e * 256 + 3 * e * 768 + 0.5 * 3 * e * 768
    assert flops_joyai.routed_layer_macs(CFG) == routed
    dense = proj + scores + 3 * e * 7168
    block = proj + scores + routed
    want = dense + 4 * block + (2 * e * e + block) + 2 * e * 16160
    assert flops_joyai.forward_macs_per_token(CFG, 8192) == want
    assert 1.13e9 < 2 * want < 1.14e9        # 1.13 GFLOP a token forward
    step = flops_joyai.train_step(CFG, {"batch": 1, "seq": 8192})
    assert step == 6 * want * 8192 and 27.8e12 < step < 27.9e12


def test_parameters_and_state_by_hand():
    from perfbench.reference import joyai_llm_flash as ref
    shapes = ref.param_shapes(CFG)
    count = sum(int(np.prod(s)) for s in shapes.values())
    attention = 26_345_472 + 2 * 2048 + 1536 + 512      # and its norms
    routed = 2048 * 256 + 17 * 3 * 2048 * 768
    assert count == (attention + 3 * 2048 * 7168) + 4 * (attention + routed) \
        + (attention + routed + 2 * 2048 * 2048 + 3 * 2048) \
        + 2 * 16160 * 2048 + 2048
    assert 680.0e6 < count < 681.0e6
    assert 9.5e9 < 14 * count < 9.6e9        # bytes of training state


def test_kernel_counts_by_hand():
    ops, nbytes = flops_joyai.flash_forward_call(1, 32, 8192, 8192, 192, 128,
                                                 2, causal=True)
    assert ops == 32 * 8192 * 8192 * (192 + 128)      # 2 x half the square
    assert nbytes == 2 * 32 * 8192 * (192 + 192 + 128 + 128) + 4 * 32 * 8192
    ops, nbytes = flops_joyai.expert_product_call(4096, 16, 2048, 768, 2)
    assert ops == 2 * 4096 * 2048 * 768
    assert nbytes == 2 * (4096 * (2048 + 768) + 16 * 2048 * 768)


def test_configuration_keeps_every_published_width():
    bench = common.load_json(common.ROOT, "BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == "joyai-llm-flash")
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                "vocab_size"]
    published = {"hidden_size": 2048, "num_attention_heads": 32,
                 "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
                 "qk_head_dim": 192, "v_head_dim": 128, "q_lora_rank": 1536,
                 "kv_lora_rank": 512, "intermediate_size": 7168,
                 "moe_intermediate_size": 768, "num_experts_per_tok": 8,
                 "routed_scaling_factor": 2.5, "rope_theta": 32000000,
                 "n_shared_experts": 1, "num_nextn_predict_layers": 1}
    assert {k: CFG[k] for k in published} == published
    assert CFG["deployment"]["router_width"] == 256
    assert CFG["published"] == {"num_hidden_layers": 40,
                                "n_routed_experts": 256,
                                "vocab_size": 129280}
    assert CFG["vocab_size"] * 8 == 129280 and CFG["n_routed_experts"] == 16


# -- the reference meets the program -----------------------------------------
def _gaps(driver):
    from perfbench.drivers.train_step_blocks import blocks_values
    driver.setup()
    prog = driver.program_readings()
    driver.release()
    return blocks_values(prog, driver.reference_readings())


def test_reference_matches_program_in_float32():
    gaps = _gaps(tiny_driver(CELL, config_override=F32))
    assert max(gaps.values()) < 2e-3, gaps


def test_reference_matches_program_in_bfloat16():
    gaps = _gaps(tiny_driver(CELL))
    assert gaps["row_loss_diff"] < 5e-2 and gaps["mtp_row_loss_diff"] < 5e-2
    assert gaps["grad_norm_gap"] < 5e-2 and gaps["delta_norm_gap"] < 5e-2


# -- the cell through the harness ---------------------------------------------
def _run(seed=7, seconds=1.0, trace=False):
    import jax
    env = tiny_env(CELL, seed=seed)
    bench = common.load_json(common.ROOT, "BENCHMARK.json")
    entry = common.cell_entry(bench, CELL)
    return run.run_cell(bench, entry, env.cell, env.config, seed, seconds,
                        trace, jax.devices()[:1], None, True)


def test_rehearsal_run_is_correct_and_counts_its_routing():
    line = _run(seed=2147483659)
    assert line["correct"] is True, line["compared"]
    assert set(line["metrics"]) == {"step_ms", "setup_s"}
    assert set(line["compared"]) == {"row_loss_diff", "grad_norm_gap",
                                     "delta_norm_gap", "compiled_in_window",
                                     "failed_requests"}


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_fails(seed):
    from perfbench import calibrate
    import importlib
    import jax
    env = tiny_env(CELL, seed=seed)
    assert env.config["training"]["control"] == "float8_e4m3fn"
    bench = common.load_json(common.ROOT, "BENCHMARK.json")
    line = calibrate.through_run_cell(
        run, importlib.import_module("perfbench.drivers."
                                     + env.cell["driver"]),
        "control", bench, common.cell_entry(bench, CELL), env.cell,
        env.config, seed, 0.5, jax.devices()[:1], True)
    assert line["correct"] is False, line["compared"]


@contextlib.contextmanager
def _patched_step(fault):
    from mxnet_tpu.parallel.trainer import ShardedTrainer
    real = ShardedTrainer.step
    ShardedTrainer.step = fault(real)
    try:
        yield
    finally:
        ShardedTrainer.step = real


def _unchanged(real):
    import jax
    import jax.numpy as jnp

    def step(self, params, opt_state, aux, batch, rng=None):
        kept = jax.tree_util.tree_map(jnp.copy, (params, opt_state, aux))
        _p, _o, _a, outs = real(self, params, opt_state, aux, batch, rng)
        return kept[0], kept[1], kept[2], outs
    return step


def _half_sequence_twice(real):
    import jax.numpy as jnp

    def step(self, params, opt_state, aux, batch, rng=None):
        def twice(a):
            half = a.shape[1] // 2
            return jnp.concatenate([a[:, :half], a[:, :half]], axis=1)
        return real(self, params, opt_state, aux,
                    {k: twice(v) for k, v in batch.items()}, rng)
    return step


@pytest.mark.parametrize("fault", [_unchanged, _half_sequence_twice])
def test_planted_faults_are_caught(fault):
    with _patched_step(fault):
        line = _run()
    assert line["correct"] is False, line["compared"]


def test_wrong_share_of_experts_is_caught(monkeypatch):
    """A wrong router: the layer told it holds experts 4.. where the
    configuration says 0.. (the same weights): tokens reach other experts
    than the reference's."""
    from perfbench.drivers import train_step_blocks as blocks
    real = blocks.symbol_args
    monkeypatch.setattr(blocks, "symbol_args", lambda cfg, seq: dict(
        real(cfg, seq), first_expert=4))
    line = _run()
    assert line["correct"] is False, line["compared"]


# -- the routing readers -------------------------------------------------------
def test_routing_readers_by_hand():
    from perfbench.readers import routing_counters
    ctx = {"counters": {"steps": 10, "assignments_per_step": 8 * 8192,
                        "routed_layers": [
        {"layer": "a", "local_assignments": 40960,
         "expert_tokens": [2560] * 16, "peak_tokens_sum": 2560,
         "peak_tokens_max": 256},
        {"layer": "b", "local_assignments": 40960,
         "expert_tokens": [2560] * 16, "peak_tokens_sum": 5120,
         "peak_tokens_max": 600}]}}
    assert routing_counters.read(ctx, "local_assignment_pct") == 6.25
    assert routing_counters.read(ctx, "load_max_over_mean") == 1.5
    assert routing_counters.read({"counters": {"steps": 3}},
                                 "local_assignment_pct") is None


def test_kernel_readers_return_nothing_without_their_events():
    from perfbench.readers import (expert_matmul_roofline,
                                   flash_forward_roofline_mla)

    class Trace(object):
        def first_chip_ops(self):
            return [("fusion.1", 0, 10)]

    ctx = {"trace": Trace(), "config": CFG, "peaks": None, "chips": 1,
           "counters": {"steps": 3, "batch": 1, "seq": 8192}}
    assert flash_forward_roofline_mla.read(ctx) is None
    assert expert_matmul_roofline.read(ctx) is None


def test_traced_rehearsal_reads_the_counter_metrics():
    line = _run(trace=True)
    assert line["correct"] is True, line["compared"]
    assert 0 < line["metrics"]["moe_local_assignment_pct"]["value"] <= 100
    assert line["metrics"]["expert_load_max_over_mean"]["value"] >= 1.0


# -- the four-chip cell's reference -------------------------------------------
def test_reference_over_several_devices_is_the_one_device_reference():
    import jax
    if len(jax.devices()) < 4:
        pytest.skip("needs four (virtual) devices")
    from perfbench.drivers import train_fit, train_fit_dp
    cell = {"traffic": {"batch": 8}}
    env = tiny_env("resnet50_fit_dp4", cell_override=cell)
    env.devices = jax.devices()[:4]
    many, one = train_fit_dp.Driver(env), train_fit.Driver(env)
    for d in (many, one):
        d.setup()
        d.release()
    a, b = many.reference_readings(), one.reference_readings()
    # the first step to rounding; the next two to what a 50-layer
    # BatchNorm network makes of a different order of summation
    assert np.allclose(a["loss"][0], b["loss"][0], rtol=1e-5)
    assert np.allclose(a["loss"], b["loss"], rtol=1e-3)
    assert np.allclose(a["logp"], b["logp"], atol=1e-4)
    # per leaf, against that leaf's norm or the median leaf's: the leaves
    # BatchNorm's scale invariance leaves without a gradient are rounding
    assert max(common.leaf_gaps(a["grad"], b["grad"]).values()) < 1e-3
