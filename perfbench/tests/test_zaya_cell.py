"""What PR 31 added to the benchmark: the counts of ``flops_zaya.py`` by
hand, the configuration against the published one, the reference meeting the
program at the cell's rehearsal sizes, the cell's rehearsal run, the control
and planted faults reading ``correct: false``, and the new readers — on
hand-made events and on the small recorded trace."""
import contextlib
import os

import numpy as np
import pytest

from helpers import tiny_driver, tiny_env

from perfbench import common, flops, flops_zaya, run
from perfbench import trace_reduce as tr

CELL = "zaya1_train_s8192"
CFG = common.load_json(common.named_file("configs", "zaya1-8b"))
F32 = {"training": {"compute_dtype": "float32"}}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
HERE = os.path.dirname(os.path.abspath(__file__))


# -- the counts ------------------------------------------------------------
def test_forward_counts_by_hand():
    e, hq, hkv, d = 2048, 8, 2, 128
    proj = e * hq * d + 2 * e * hkv * d + hq * d * e
    assert flops_zaya.attention_projection_macs(CFG) == proj == 5_242_880
    # two taps a channel, then two d x d matrices a head, on 8 + 2 heads
    conv = (hq + hkv) * d * 2 + (hq + hkv) * 2 * d * d
    assert flops_zaya.convolution_macs(CFG) == conv == 330_240
    # 8,192 keys, causal half, 8 query heads, 128 wide scores and values
    scores = 4096 * hq * (d + d)
    assert flops_zaya.attention_score_macs(CFG, 8192) == scores == 8_388_608
    router = e * 256 + 2 * 256 * 256 + 256 * 16
    assert flops_zaya.router_macs(CFG) == router == 659_456
    assert flops_zaya.local_assignments_per_token(CFG) == 1 * 8 / 16
    assert flops_zaya.expert_macs(CFG) == 3 * e * 2048
    layer = proj + conv + scores + router + 0.5 * 3 * e * 2048
    assert flops_zaya.layer_macs(CFG, 8192) == layer
    assert 20.9e6 < layer < 21.0e6
    # attention's scores are 40 % of a layer, the expected experts 30 %
    assert 0.39 < scores / layer < 0.41 and 0.29 < 6_291_456 / layer < 0.31
    want = 6 * layer + e * 32784
    assert flops_zaya.forward_macs_per_token(CFG, 8192) == want
    step = flops_zaya.train_step(CFG, {"batch": 1, "seq": 8192})
    assert step == 6 * want * 8192 and 9.4e12 < step < 9.5e12
    # the head is 35 % of the step
    assert 0.34 < e * 32784 / want < 0.36
    # the counted rows in place of the expected ones
    assert flops_zaya.layer_macs(CFG, 8192, local_per_token=1.0) \
        == layer + 0.5 * 3 * e * 2048


def test_parameters_and_state_by_hand():
    from perfbench.reference import zaya1 as ref
    shapes = ref.param_shapes(CFG)
    count = sum(int(np.prod(s)) for s in shapes.values())
    attention = 5_242_880 + 2 * (8 + 2) * 128 + 2 * (8 + 2) * 128 * 128 + 2
    router = 659_456 + 256                  # and its norm's gain
    experts = 8 * 3 * 2048 * 2048
    vectors = 2 * 2048 + 8 * 2048           # two norms, four scale-and-bias
    layer = attention + router + experts + vectors
    # the first layer's router takes no stream, so has no gain for it
    assert count == 6 * layer + 5 + 32784 * 2048 + 2048
    assert 708.6e6 < count < 708.7e6
    assert 9.9e9 < 14 * count < 9.95e9      # bytes of training state
    assert "lm_head_weight" not in shapes   # tied
    assert "layer0_router_state_gain" not in shapes
    assert shapes["layer1_router_state_gain"] == (1,)


def test_kernel_counts_by_hand():
    ops, nbytes = flops_zaya.flash_forward_call(1, 8, 2, 8192, 8192, 128, 128,
                                                2, causal=True)
    assert ops == 8 * 8192 * 8192 * (128 + 128)       # 2 x half the square
    # q and o by 8 heads, k and v by 2, a float32 lse a query row
    assert nbytes == 2 * 8192 * 256 * (8 + 2) + 4 * 8 * 8192
    ops_b, nbytes_b = flops_zaya.flash_backward_call(
        1, 8, 2, 8192, 8192, 128, 128, 2, causal=True)
    assert ops_b == 2 * ops
    # q, dq, o, do by 8 heads; k, dk, v, dv by 2
    assert nbytes_b == 2 * 2 * 8192 * 256 * (8 + 2) + 4 * 8 * 8192
    for call, want in ((flops_zaya.flash_forward_call, 0.6976e-3),
                       (flops_zaya.flash_backward_call, 1.3953e-3)):
        t, bound = flops.roofline_seconds(
            *call(1, 8, 2, 8192, 8192, 128, 128, 2), 197e12, 819e9)
        assert bound == "compute" and t == pytest.approx(want, rel=1e-3)
    # ungrouped, the bytes are flops_joyai's and flops_flash_backward's
    from perfbench import flops_flash_backward, flops_joyai
    assert flops_zaya.flash_forward_call(1, 4, 4, 512, 512, 64, 64, 2) \
        == flops_joyai.flash_forward_call(1, 4, 512, 512, 64, 64, 2)
    assert flops_zaya.flash_backward_call(1, 4, 4, 512, 512, 64, 64, 2) \
        == flops_flash_backward.flash_backward_call(1, 4, 512, 512, 64, 64, 2)


def test_configuration_keeps_every_published_key():
    import json
    bench = common.load_json(common.ROOT, "BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == "zaya1-8b")
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    assert entry["source"] == CFG["source"]
    published = {
        "hidden_size": 2048, "num_attention_heads": 8,
        "num_key_value_heads": 2, "head_dim": 128, "cca_time0": 2,
        "cca_time1": 2, "moe_intermediate_size": 2048,
        "num_experts_per_tok": 1, "router_hidden_size": 256,
        "partial_rotary_factor": 0.5, "rms_norm_eps": 1e-05,
        "tie_word_embeddings": True, "hidden_act": "silu",
        "attention_bias": False, "lm_head_bias": False,
        "max_position_embeddings": 131072, "sliding_window": None,
        "model_type": "zaya"}
    assert {k: CFG[k] for k in published} == published
    assert CFG["layer_types"] == ["hybrid"] * 40
    assert CFG["rope_parameters"]["hybrid"] == {
        "partial_rotary_factor": 0.5, "rope_theta": 5000000,
        "rope_type": "default"}
    assert CFG["published"] == {"num_hidden_layers": 40, "num_experts": 16,
                                "vocab_size": 262272}
    assert CFG["vocab_size"] * 8 == 262272
    assert CFG["num_experts"] * CFG["deployment"]["chips_sharing_a_layer"] \
        == CFG["deployment"]["router_width"] == 16
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):         # every number of the catalog's entry
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "ZAYA1-8B")
        for key, value in row["config"].items():
            if key not in entry["reduced"]:
                assert CFG[key] == value, key
    # every equation config.json does not fix names where it comes from
    assert len(CFG["assumed"]) >= 10
    assert all("arXiv" in a or "config.json" in a for a in CFG["assumed"])


# -- the reference meets the program -----------------------------------------
def _gaps(driver):
    from perfbench.drivers.train_fit import training_values
    driver.setup()
    prog = driver.program_readings()
    driver.release()
    return training_values(prog, driver.reference_readings())


def test_reference_matches_program_in_float32():
    """float32 on the CPU, both sides: what is left is the order of
    summation — but for tokens whose two best experts tie to under it (the
    seeded routers' logits are of the order of 0.01), so the forward number
    is held to 2e-3 and the norms, which a flipped token moves, to 1e-2."""
    gaps = _gaps(tiny_driver(CELL, config_override=F32))
    assert gaps["row_loss_diff"] < 2e-3, gaps
    assert gaps["grad_norm_gap"] < 1e-2 and gaps["delta_norm_gap"] < 1e-2, \
        gaps


def test_reference_matches_program_in_bfloat16():
    """bfloat16 rounding at the rehearsal's width 64: a few per cent, as
    ``joyai_flash_train_s8192`` reads at its rehearsal sizes."""
    gaps = _gaps(tiny_driver(CELL))
    assert gaps["row_loss_diff"] < 5e-2, gaps
    assert gaps["grad_norm_gap"] < 5e-2 and gaps["delta_norm_gap"] < 5e-2, \
        gaps


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
    assert set(line["compared"]) == {
        "row_loss_diff", "grad_norm_gap", "delta_norm_gap",
        "grad_norm_gap_median", "delta_norm_gap_median", "expert_grad_diff",
        "compiled_in_window", "failed_requests"}


def _stand_in(variant, seed):
    """A whole rehearsal run with ``variant`` of the reference in the
    program's place where ``correct`` reads the program."""
    from perfbench import calibrate
    import importlib
    import jax
    env = tiny_env(CELL, seed=seed)
    bench = common.load_json(common.ROOT, "BENCHMARK.json")
    return calibrate.through_run_cell(
        run, importlib.import_module("perfbench.drivers."
                                     + env.cell["driver"]),
        variant, bench, common.cell_entry(bench, CELL), env.cell,
        env.config, seed, 0.5, jax.devices()[:1], True)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_fails(seed):
    assert CFG["training"]["control"] == "float8_e4m3fn"
    line = _stand_in("control", seed)
    assert line["correct"] is False, line["compared"]


@pytest.mark.parametrize("seed", [1, 2])
def test_the_wrong_share_of_the_experts_fails(seed):
    """Experts 2-5 of 8 held where the configuration says 0-3: every norm
    reads as a sound run's (either half gets as many tokens), the number
    that follows the tokens does not."""
    line = _stand_in("wrong_share", seed)
    assert line["correct"] is False, line["compared"]
    over = {n for n, v in line["compared"].items() if v["value"] > v["limit"]}
    assert over == {"expert_grad_diff"}, line["compared"]


@pytest.mark.parametrize("seed", [1, 2])
def test_the_reference_in_bfloat16_passes(seed):
    """The plain reference with its products' operands rounded to the
    configuration's own precision is a sound program."""
    line = _stand_in("bf16", seed)
    assert line["correct"] is True, line["compared"]


def test_cancellation_and_the_leaves_it_leaves_out():
    import jax.numpy as jnp
    from perfbench.drivers.train_step_zaya import (CANCEL_SHARE,
                                                   cancelling_leaves)
    from perfbench.reference import zaya1
    same = jnp.ones((64, 2))
    assert float(zaya1.cancellation(same)) == pytest.approx(1.0)
    assert float(zaya1.cancellation(same * jnp.array([[1.0], [-1.0]] * 32))) \
        == pytest.approx(0.0, abs=1e-6)
    # by hand: terms (3, 0), (-1, 0), (0, 2) sum to (2, 2); sizes 3 + 1 + 2
    terms = jnp.array([[3.0, 0.0], [-1.0, 0.0], [0.0, 2.0]])
    assert float(zaya1.cancellation(terms)) == pytest.approx(8 ** 0.5 / 6)
    assert cancelling_leaves({"a": 0.5 * CANCEL_SHARE, "b": 2 * CANCEL_SHARE,
                              "c": 1.0}) == {"a"}
    # the terms' sum is the leaf's gradient times the leaf: the reference's
    # own key temperatures and stream gains at the rehearsal sizes
    driver = tiny_driver(CELL, config_override=F32)
    driver.setup()
    driver.release()
    ref = driver.reference_readings()
    layers = int(driver.env.config["num_hidden_layers"])
    assert set(ref["cancel"]) == (
        {"layer%d_att_k_temp" % i for i in range(layers)}
        | {"layer%d_router_state_gain" % i for i in range(1, layers)})
    assert all(0.0 < c <= 1.0 for c in ref["cancel"].values())


def test_expert_sketch_follows_the_gradient():
    import jax
    from perfbench.reference import zaya1
    a, b = (jax.random.normal(jax.random.PRNGKey(i), (4, 64, 256))
            for i in (1, 2))
    sk = {n: zaya1.expert_sketch({"layer0_moe_expert_down_weight": g,
                                  "layer0_att_q_weight": g[0]})
          for n, g in (("a", a), ("b", a + 0.5 * b))}
    assert set(sk["a"]) == {"layer0_moe_expert_down_weight"}
    a_s, b_s = (np.asarray(sk[n]["layer0_moe_expert_down_weight"])
                for n in "ab")
    assert a_s.shape == (4, 64)
    # |difference| / |a| is 0.5 for the gradients; the sketch's estimate
    assert np.linalg.norm(b_s - a_s) / np.linalg.norm(a_s) \
        == pytest.approx(0.5, rel=0.2)


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


def test_parent_without_the_cell_fails_at_once():
    """``common.cell_entry`` on a benchmark that lists no such cell: an
    exit, not a hang (what the parent commit does with this cell's name)."""
    bench = common.load_json(common.ROOT, "BENCHMARK.json")
    bench["workloads"] = [w for w in bench["workloads"]
                          if w["name"] != CELL]
    with pytest.raises(SystemExit, match="lists no cell"):
        common.cell_entry(bench, CELL)


# -- the readers ---------------------------------------------------------------
class _Trace(object):
    def __init__(self, events):
        self.events = events

    def first_chip_ops(self):
        return self.events


def _ctx(events, config=CFG, peaks=PEAKS, **counters):
    c = {"steps": 2, "batch": 1, "seq": 8192}
    c.update(counters)
    return {"trace": _Trace(events), "config": config, "peaks": peaks,
            "chips": 1, "counters": c}


def test_grouped_rooflines_on_hand_made_events():
    from perfbench.readers import flash_roofline_gqa as reader
    fwd_ns = 0.6976e-3 * 1e9
    bwd_ns = 1.3953e-3 * 1e9
    events = [("%fusion.7 = f32[8] fusion(...)", 0, 900),
              ("%flash_forward = bf16[8] custom-call(...)", 1000,
               int(2 * fwd_ns)),
              ("%flash_forward.1 = bf16[8] custom-call(...)", 5e9,
               int(2 * fwd_ns)),
              ("%flash_backward = bf16[8] custom-call(...)", 1e10,
               int(4 * bwd_ns)),
              ("%flash_backward.3 = bf16[8] custom-call(...)", 2e10,
               int(4 * bwd_ns))]
    assert reader.read(_ctx(events), "forward") == pytest.approx(50.0,
                                                                 rel=1e-3)
    assert reader.read(_ctx(events), "backward") == pytest.approx(25.0,
                                                                  rel=1e-3)
    # a call of two kernels counts once
    two = [("%flash_backward_dq = custom-call(...)", 0, int(2 * bwd_ns)),
           ("%flash_backward_dkdv = custom-call(...)", 1e10,
            int(2 * bwd_ns))]
    assert reader.read(_ctx(two), "backward") == pytest.approx(25.0,
                                                               rel=1e-3)
    with pytest.raises(ValueError):
        reader.read(_ctx(events), "sideways")


def test_readers_return_nothing_where_there_is_nothing_to_read():
    from perfbench.readers import (expert_matmul_roofline_top1,
                                   flash_roofline_gqa)
    plain = [("%fusion.1 = fusion(...)", 0, 10)]
    for direction in ("forward", "backward"):
        assert flash_roofline_gqa.read(_ctx(plain), direction) is None
        assert flash_roofline_gqa.read(
            _ctx([("%flash_forward = custom-call(...)", 0, 10),
                  ("%flash_backward = custom-call(...)", 20, 10)],
                 peaks=None), direction) is None
    # a configuration without grouped heads (the parent's cells)
    gpt2 = common.load_json(common.named_file("configs", "gpt2-medium"))
    events = [("%flash_forward = custom-call(...)", 0, 10)]
    assert flash_roofline_gqa.read(_ctx(events, config=gpt2),
                                   "forward") is None
    assert expert_matmul_roofline_top1.read(_ctx(plain)) is None
    ragged = [("%ragged-dot-none.1 = custom-call(...)", 0, 1000)]
    assert expert_matmul_roofline_top1.read(_ctx(ragged)) is None  # no counts
    joyai = common.load_json(common.named_file("configs", "joyai-llm-flash"))
    layers = [{"layer": "a", "local_assignments": 8192,
               "expert_tokens": [1024] * 8, "peak_tokens_sum": 2048,
               "peak_tokens_max": 1024}]
    assert expert_matmul_roofline_top1.read(
        _ctx(ragged, config=joyai, routed_layers=layers)) is None


def test_expert_roofline_from_the_counted_rows():
    from perfbench import flops_joyai
    from perfbench.readers import expert_matmul_roofline_top1 as reader
    layers = [{"layer": "layer%d_moe" % i, "local_assignments": 2 * rows,
               "expert_tokens": [rows // 4] * 8, "peak_tokens_sum": rows,
               "peak_tokens_max": rows // 2}
              for i, rows in enumerate((4096, 2048))]
    # 3,072 rows a step and layer against eight 2,048 x 2,048 matrices
    ops, nbytes = flops_joyai.expert_product_call(3072, 8, 2048, 2048, 2)
    least_ns = flops.roofline_seconds(ops, nbytes, 197e12, 819e9)[0] * 1e9
    events = [("%%ragged-dot-none.%d = custom-call(...)" % i, i * 1e9,
               int(2 * least_ns)) for i in range(6)]
    got = reader.read(_ctx(events, routed_layers=layers))
    assert got == pytest.approx(50.0, rel=1e-3)


def test_routing_readers_take_the_top1_counters():
    from perfbench.readers import routing_counters
    ctx = {"counters": {"steps": 10, "assignments_per_step": 8192,
                        "routed_layers": [
        {"layer": "a", "local_assignments": 40960,
         "expert_tokens": [5120] * 8, "peak_tokens_sum": 5120,
         "peak_tokens_max": 512},
        {"layer": "b", "local_assignments": 40960,
         "expert_tokens": [5120] * 8, "peak_tokens_sum": 10240,
         "peak_tokens_max": 1200}]}}
    assert routing_counters.read(ctx, "local_assignment_pct") == 50.0
    assert routing_counters.read(ctx, "load_max_over_mean") == 1.5


def test_readers_on_the_small_recorded_trace():
    """``small_trace.xplane.pb.gz`` (a TPU v5e; three ``flash_forward.1``
    events of 1,901 + 2,050 + 2,112 ns and no backward kernel): the forward
    reader reads three calls' least time over their device time, the
    backward reader and the expert reader nothing."""
    from perfbench.readers import (expert_matmul_roofline_top1,
                                   flash_roofline_gqa)
    data = tr.load(os.path.join(HERE, "small_trace.xplane.pb.gz"))
    ctx = _ctx(tr.device_lines(data)["/device:TPU:0"], seq=512)
    least = flops.roofline_seconds(
        *flops_zaya.flash_forward_call(1, 8, 2, 512, 512, 128, 128, 2),
        197e12, 819e9)[0]
    want = 100.0 * least * 3 / ((1901 + 2050 + 2112) * 1e-9)
    assert flash_roofline_gqa.read(ctx, "forward") == pytest.approx(
        want, rel=1e-6)
    assert flash_roofline_gqa.read(ctx, "backward") is None
    assert expert_matmul_roofline_top1.read(ctx) is None


def test_metric_files_agree_with_benchmark_json():
    bench = common.load_json(common.ROOT, "BENCHMARK.json")
    names = ["flash_forward_roofline.gqa", "flash_backward_roofline.gqa",
             "expert_matmul_roofline.top1", "expert_load_max_over_mean.top1",
             "moe_local_assignment_pct.top1"]
    assert [m["name"] for m in bench["per_layer"][-5:]] == names
    for m in bench["per_layer"][-5:]:
        spec = common.load_json(common.named_file("metrics", m["name"]))
        assert spec["workloads"] == m["workloads"] == [CELL]
        for key in ("unit", "better", "moves", "source", "layer"):
            assert spec[key] == m[key], (m["name"], key)
    step_ms = next(m for m in bench["end_to_end"] if m["name"] == "step_ms")
    assert step_ms["workloads"][-1] == CELL
    entry = common.cell_entry(bench, CELL)
    assert entry["chips"] == 1 and len(entry["why"]) <= 200
    assert "2x its share" in entry["why"]


def test_traced_rehearsal_reads_the_counter_metrics():
    line = _run(trace=True)
    assert line["correct"] is True, line["compared"]
    assert 0 < line["metrics"]["moe_local_assignment_pct.top1"]["value"] <= 100
    assert line["metrics"]["expert_load_max_over_mean.top1"]["value"] >= 1.0
    assert "moe_local_assignment_pct" not in line["metrics"]    # JoyAI's
