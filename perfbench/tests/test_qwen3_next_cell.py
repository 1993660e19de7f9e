"""What PR 33 added to the benchmark: the counts of ``flops_qwen3_next.py`` by
hand (at the published and at the rehearsal sizes), the configuration
against the published one, the reference meeting the program at the cell's
rehearsal sizes, the cell's rehearsal run, the control and the planted faults
— the wrong share of the experts, the delta rule without its correction —
reading ``correct: false``, and ``delta_rule_roofline``'s reader on hand-made
events.  Metric entries are found by name, not by position."""
import os

import numpy as np
import pytest

from helpers import tiny_driver, tiny_env

from perfbench import common, flops, flops_qwen3_next as count, run

CELL = "qwen3next_train_s8192"
CFG = common.load_json(common.named_file("configs", "qwen3-next-80b-a3b"))
TINY = common.merged(CFG, CFG["rehearse"])
F32 = {"training": {"compute_dtype": "float32"}}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW_METRICS = ["flash_forward_roofline.gqa256",
               "flash_backward_roofline.gqa256",
               "expert_matmul_roofline.top10",
               "moe_local_assignment_pct.top10",
               "expert_load_max_over_mean.top10", "delta_rule_roofline"]


# -- the counts ------------------------------------------------------------
def test_forward_counts_by_hand():
    e = 2048
    # q, k of 16 x 128 and v, z of 32 x 128; b, a; out; four taps a channel
    delta = e * (2 * 2048 + 2 * 4096) + e * 64 + 4096 * e + 4 * 8192
    assert count.delta_projection_macs(CFG) == delta == 33_718_272
    assert count.delta_rule_ops_per_token(CFG) == 7 * 32 * 128 * 128
    # q with its gate (16 x 512), k and v (2 x 256 each), out
    att = e * 8192 + 2 * e * 512 + 4096 * e
    assert count.attention_projection_macs(CFG) == att == 27_262_976
    assert count.local_assignments_per_token(CFG) == 10 * 32 / 512
    routed = e * 512 + 0.625 * 3 * e * 512 + 3 * e * 512 + e
    assert count.routed_layer_macs(CFG, 0.625) == routed
    scores = 4096 * 16 * 2 * 256                # causal half of 8,192 keys
    per_token = 2 * e * 18992 \
        + 3 * (2 * delta + 7 * 32 * 128 * 128 + 2 * routed) \
        + 2 * (att + scores) + 2 * routed
    assert count.forward_ops_per_token(CFG, 8192) == per_token
    step = count.train_step(CFG, {"batch": 1, "seq": 8192})
    assert step == 3 * per_token * 8192 and 1.13e13 < step < 1.14e13
    # the kinds: three delta-rule layers, then the attention layer
    assert count.layer_kinds(CFG) == ["linear_attention"] * 3 \
        + ["full_attention"]
    # the counted rows in place of the expected ones: 6,000 a layer and step
    layers = [{"local_assignments": 3 * 6000}] * 4
    counters = {"batch": 1, "seq": 8192, "steps": 3, "routed_layers": layers}
    assert count.local_assignments_per_token(CFG, counters) \
        == pytest.approx(6000 / 8192)
    assert count.train_step(CFG, counters) - step == pytest.approx(
        3 * 4 * 2 * (6000 - 5120) * 3 * e * 512, rel=1e-9)


def test_counts_at_the_rehearsal_sizes_by_hand():
    """Width 64, heads 4 on 2 of 16, delta heads 2 and 4 of 16, experts of
    32 (4 of 16 held, 4 a token), 256 rows of vocabulary, 128 tokens."""
    e = 64
    delta = e * (2 * 32 + 2 * 64) + e * 8 + 64 * e + 4 * (2 * 32 + 64)
    rule = 7 * 4 * 16 * 16
    att = e * 2 * 64 + 2 * e * 32 + 64 * e
    scores = 64 * 4 * 2 * 16
    routed = e * 16 + 1.0 * 3 * e * 32 + 3 * e * 32 + e
    per_token = 2 * e * 256 + 3 * (2 * delta + rule + 2 * routed) \
        + 2 * (att + scores) + 2 * routed
    assert count.forward_ops_per_token(TINY, 128) == per_token
    assert count.train_step(TINY, {"batch": 1, "seq": 128}) \
        == 3 * per_token * 128


def test_parameters_and_state_by_hand():
    from perfbench.reference import qwen3_next as ref
    shapes = ref.param_shapes(CFG)
    total = sum(int(np.prod(s)) for s in shapes.values())
    e = 2048
    moe = 512 * e + 32 * 3 * e * 512 + 3 * e * 512 + e
    delta = 12288 * e + 64 * e + 4 * 8192 + 32 + 32 + 128 + e * 4096
    att = 8192 * e + 2 * 512 * e + 256 + 256 + e * 4096
    assert total == 3 * (delta + moe + 2 * e) + (att + moe + 2 * e) \
        + 2 * 18992 * e + e
    assert 625.6e6 < total < 625.8e6            # 625.7 M held
    assert 8.75e9 < 14 * total < 8.77e9         # bytes of training state
    assert shapes["lm_head_weight"] == shapes["tok_embed_weight"]
    assert "layer3_gdn_A_log" not in shapes and "layer3_att_q_weight" in shapes
    assert shapes["layer0_moe_shared_score_weight"] == (1, e)


def test_kernel_counts_by_hand():
    ops, nbytes = count.delta_rule_call(1, 16, 32, 8192, 128, 128, 2)
    assert ops == 7 * 8192 * 32 * 128 * 128
    # q and k by 16 heads, v and o by 32, g and beta float32 a value head
    assert nbytes == 8192 * (2 * (2 * 2048 + 2 * 4096) + 8 * 32)
    ops_b, nbytes_b = count.delta_rule_call(1, 16, 32, 8192, 128, 128, 2,
                                            backward=True)
    assert (ops_b, nbytes_b) == (2 * ops, 2 * nbytes)
    t, bound = flops.roofline_seconds(ops, nbytes, 197e12, 819e9)
    assert bound == "memory" and t == pytest.approx(0.2484e-3, rel=1e-3)
    # the attention kernels at 16 heads of 256 on 2: flops_zaya's count
    from perfbench import flops_zaya
    ops, nbytes = flops_zaya.flash_forward_call(1, 16, 2, 8192, 8192, 256,
                                                256, 2)
    assert ops == 16 * 8192 * 8192 * 512
    assert nbytes == 2 * 8192 * 512 * (16 + 2) + 4 * 16 * 8192


def test_configuration_keeps_every_published_key():
    import json
    bench = common.load_json(common.ROOT, "BENCHMARK.json")
    entry = next(c for c in bench["configs"]
                 if c["name"] == "qwen3-next-80b-a3b")
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    assert entry["source"] == CFG["source"]
    assert entry["file"] == "perfbench/configs/qwen3-next-80b-a3b.json"
    published = {
        "hidden_size": 2048, "num_attention_heads": 16,
        "num_key_value_heads": 2, "head_dim": 256,
        "linear_num_key_heads": 16, "linear_num_value_heads": 32,
        "linear_key_head_dim": 128, "linear_value_head_dim": 128,
        "linear_conv_kernel_dim": 4, "full_attention_interval": 4,
        "moe_intermediate_size": 512, "shared_expert_intermediate_size": 512,
        "num_experts_per_tok": 10, "norm_topk_prob": True,
        "partial_rotary_factor": 0.25, "rope_theta": 10000000,
        "rms_norm_eps": 1e-06, "tie_word_embeddings": False,
        "hidden_act": "silu", "model_type": "qwen3_next",
        "intermediate_size": 5120, "max_position_embeddings": 262144}
    assert {k: CFG[k] for k in published} == published
    assert CFG["published"] == {"num_hidden_layers": 48, "num_experts": 512,
                                "vocab_size": 151936}
    assert CFG["vocab_size"] * 8 == 151936
    dep = CFG["deployment"]
    assert CFG["num_experts"] * dep["chips_sharing_a_layer"] \
        == dep["router_width"] == 512
    assert CFG["num_hidden_layers"] == CFG["full_attention_interval"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):         # every number of the catalog's entry
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
        assert row["source_url"] == CFG["source"]
        for key, value in row["config"].items():
            if key not in entry["reduced"]:
                assert CFG[key] == value, key
    assert len(CFG["assumed"]) >= 10
    assert "no prediction module" in CFG["departures"].lower() \
        or "prediction module" in CFG["departures"]


def test_metric_entries_by_name():
    bench = common.load_json(common.ROOT, "BENCHMARK.json")
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_METRICS:
        spec = common.load_json(common.named_file("metrics", name))
        entry = by_name[name]
        assert spec["workloads"] == entry["workloads"] == [CELL], name
        for key in ("unit", "better", "moves", "source", "layer"):
            assert spec[key] == entry[key], (name, key)
    step_ms = next(m for m in bench["end_to_end"] if m["name"] == "step_ms")
    assert CELL in step_ms["workloads"]
    entry = common.cell_entry(bench, CELL)
    assert entry["chips"] == 1 and len(entry["why"]) <= 200
    assert "16x a chip's share" in entry["why"]
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    reported = {m["name"] for m in run.metrics_of(
        bench, "per_layer", CELL, {"step_ms", "setup_s"})}
    assert reported == set(NEW_METRICS) | {
        "device_idle_pct.train", "step_mfu_pct.train",
        "step_dispatch_ms.train"}


# -- the reference meets the program -----------------------------------------
def _gaps(driver):
    from perfbench.drivers.train_step_zaya import zaya_values
    driver.setup()
    prog = driver.program_readings()
    driver.release()
    return zaya_values(prog, driver.reference_readings())


def test_reference_matches_program_in_float32():
    """float32 on the CPU, both sides: what is left is the order of
    summation — but for tokens whose tenth and eleventh experts tie to
    under it (the seeded routers' logits are of the order of 0.01)."""
    gaps = _gaps(tiny_driver(CELL, config_override=F32))
    assert gaps["row_loss_diff"] < 2e-3, gaps
    assert gaps["grad_norm_gap"] < 1e-2 and gaps["delta_norm_gap"] < 1e-2, \
        gaps
    assert gaps["expert_grad_diff"] < 0.1, gaps


def test_reference_matches_program_in_bfloat16():
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


def test_rehearsal_through_run_py(capsys):
    """The command the driver runs, at the rehearsal sizes: one result line,
    last, stamped as a rehearsal."""
    import json
    rc = run.main(["--workload", CELL, "--seed", "4000000007", "--seconds",
                   "1", "--trace", "0", "--rehearse"])
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    line = json.loads(out[-1])
    assert line["rehearsal"] is True and line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0


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


@pytest.mark.parametrize("seed", [1, 2])
def test_control_fails(seed):
    assert CFG["training"]["control"] == "float8_e4m3fn"
    line = _stand_in("control", seed)
    assert line["correct"] is False, line["compared"]


@pytest.mark.parametrize("seed", [1, 2])
def test_the_wrong_share_of_the_experts_fails(seed):
    """Experts 2-5 of 16 held where the configuration says 0-3."""
    line = _stand_in("wrong_share", seed)
    assert line["correct"] is False, line["compared"]
    assert line["compared"]["expert_grad_diff"]["value"] \
        > line["compared"]["expert_grad_diff"]["limit"]


@pytest.mark.parametrize("seed", [1, 2])
def test_the_missing_delta_correction_fails(seed):
    """The reference with δ_t = β_t v_t in the program's place: the new
    mechanism left out reads ``correct: false``."""
    line = _stand_in("no_delta", seed)
    assert line["correct"] is False, line["compared"]


@pytest.mark.parametrize("seed", [1, 2])
def test_the_reference_in_bfloat16_passes(seed):
    line = _stand_in("bf16", seed)
    assert line["correct"] is True, line["compared"]


def test_parent_without_the_model_fails_at_once():
    """The parent commit has this PR's benchmark files laid over it and no
    ``models.transformer_hybrid_moe``: set-up raises on the import, it does
    not hang."""
    driver = tiny_driver(CELL, config_override={
        "program": {"module": "mxnet_tpu.models.no_such_model"}})
    with pytest.raises(ImportError):
        driver.setup()


# -- delta_rule_roofline's reader ----------------------------------------------
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


def _least_ns():
    """Three delta-rule layers, forward and backward, one step."""
    total = 0.0
    for backward in (False, True):
        total += flops.roofline_seconds(
            *count.delta_rule_call(1, 16, 32, 8192, 128, 128, 2,
                                   backward=backward), 197e12, 819e9)[0]
    return 3 * total * 1e9


def test_delta_rule_roofline_reads_the_loops_that_hold_no_grouped_product():
    from perfbench.readers import delta_rule_roofline as reader
    least = _least_ns()                 # a step; the window holds two
    scan = int(10 * 2 * least / 4)      # four scans: 10 % together
    events = [
        ("%fusion.7 = f32[8] fusion(...)", 0, 900),
        ("%while.1 = (f32[8]) while(...)", 1000, scan),
        ("%fusion.9 = f32[8] fusion(...)", 1500, 200),      # inside it
        ("%while.2 = (f32[8]) while(...)", 1e9, scan),
        # a routed layer's chunk loop: holds the grouped product
        ("%while.3 = (f32[8]) while(...)", 2e9, 5 * scan),
        ("%ragged-dot-none.4 = bf16[8] custom-call(...)", 2e9 + 10, 4000),
        ("%while.5 = (f32[8]) while(...)", 3e9, scan),
        # a loop inside a counted loop is not counted again
        ("%while.6 = (f32[8]) while(...)", 3e9 + 10, scan // 2),
        ("%while.7 = (f32[8]) while(...)", 4e9, scan),
    ]
    found = reader.rule_events(events)
    assert [tr_name(n) for n, _s, _d in found] == [
        "while.1", "while.2", "while.5", "while.7"]
    assert reader.read(_ctx(events)) == pytest.approx(10.0, rel=1e-3)


def tr_name(text):
    from perfbench import trace_reduce
    return trace_reduce.op_name(text)


def test_delta_rule_roofline_prefers_a_kernel_of_that_name():
    from perfbench.readers import delta_rule_roofline as reader
    least = _least_ns()
    events = [
        ("%while.1 = (f32[8]) while(...)", 0, 10 ** 9),
        ("%gated_delta_rule_fwd = bf16[8] custom-call(...)", 2e9,
         int(2 * least)),
        ("%gated_delta_rule_bwd.1 = bf16[8] custom-call(...)", 4e9,
         int(2 * least))]
    assert len(reader.rule_events(events)) == 2
    assert reader.read(_ctx(events)) == pytest.approx(50.0, rel=1e-3)


def test_delta_rule_roofline_returns_nothing_where_there_is_nothing():
    from perfbench.readers import delta_rule_roofline as reader
    loops = [("%while.1 = (f32[8]) while(...)", 0, 1000)]
    assert reader.read(_ctx([("%fusion.1 = fusion(...)", 0, 10)])) is None
    assert reader.read(_ctx(loops, peaks=None)) is None
    assert reader.read(_ctx(loops, steps=0)) is None
    # every loop holds a grouped product: nothing is the rule's
    assert reader.read(_ctx(loops + [
        ("%ragged-dot-none.1 = custom-call(...)", 10, 100)])) is None
    # configurations without delta-rule layers (the parent's cells)
    for name in ("gpt2-medium", "joyai-llm-flash", "zaya1-8b"):
        other = common.load_json(common.named_file("configs", name))
        assert reader.read(_ctx(loops, config=other)) is None
    no_linear = dict(CFG, full_attention_interval=1)
    assert reader.read(_ctx(loops, config=no_linear)) is None


def test_the_other_new_metrics_read_this_configuration():
    """The readers PR 31 wrote take heads, widths and the experts held from
    this configuration's keys."""
    from perfbench import flops_joyai, flops_zaya
    from perfbench.readers import (expert_matmul_roofline_top1,
                                   flash_roofline_gqa, routing_counters)
    fwd = flops.roofline_seconds(*flops_zaya.flash_forward_call(
        1, 16, 2, 8192, 8192, 256, 256, 2), 197e12, 819e9)[0] * 1e9
    events = [("%flash_forward = bf16[8] custom-call(...)", 0, int(2 * fwd))]
    assert flash_roofline_gqa.read(_ctx(events), "forward") \
        == pytest.approx(50.0, rel=1e-3)
    layers = [{"layer": "layer%d_moe" % i, "local_assignments": 2 * 5120,
               "expert_tokens": [320] * 32, "peak_tokens_sum": 2 * 240,
               "peak_tokens_max": 250} for i in range(4)]
    ops, nbytes = flops_joyai.expert_product_call(5120, 32, 2048, 512, 2)
    least = flops.roofline_seconds(ops, nbytes, 197e12, 819e9)[0] * 1e9
    ragged = [("%%ragged-dot-none.%d = custom-call(...)" % i, i * 1e9,
               int(4 * least)) for i in range(6)]
    assert expert_matmul_roofline_top1.read(
        _ctx(ragged, routed_layers=layers)) == pytest.approx(25.0, rel=1e-3)
    ctx = _ctx([], routed_layers=layers, assignments_per_step=81920)
    assert routing_counters.read(ctx, "local_assignment_pct") == 6.25
    assert routing_counters.read(ctx, "load_max_over_mean") == 1.5


def test_traced_rehearsal_reads_the_counter_metrics():
    line = _run(trace=True)
    assert line["correct"] is True, line["compared"]
    assert 0 < line["metrics"]["moe_local_assignment_pct.top10"]["value"] \
        <= 100
    assert line["metrics"]["expert_load_max_over_mean.top10"]["value"] >= 1.0
    assert "moe_local_assignment_pct.top1" not in line["metrics"]   # ZAYA1's
