"""What the Laguna-XS.2 cell adds to the benchmark: the counts of
``flops_laguna.py`` by hand, the configuration against the published one,
the reference meeting the program at the cell's rehearsal sizes, the cell's
rehearsal run, the control and the planted faults — the wrong share of the
experts, window layers that see every key, full layers without YaRN —
reading ``correct: false``, and the new readers on hand-made events and
scope tables.  Metric entries are found by name, not by position."""
import numpy as np
import pytest

from helpers import tiny_driver, tiny_env

from perfbench import common, flops, flops_laguna as count, run

CELL = "laguna_train_s8192"
CFG = common.load_json(common.named_file("configs", "laguna-xs2"))
TINY = common.merged(CFG, CFG["rehearse"])
F32 = {"training": {"compute_dtype": "float32"}}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW_METRICS = ["flash_window_forward_roofline",
               "flash_window_backward_roofline",
               "window_attention_device_pct.train",
               "moe_local_assignment_pct.top8",
               # accepted readers, of the layers this cell shares
               "flash_forward_roofline.gqa6", "flash_backward_roofline.gqa6",
               "expert_matmul_roofline.top8",
               "expert_load_max_over_mean.top8",
               "device_scoped_pct.train.laguna",
               "recompute_device_pct.train.laguna",
               "update_device_pct.train.laguna",
               "norm_device_pct.train.laguna"]


# -- the counts ------------------------------------------------------------
def test_forward_counts_by_hand():
    """Width 2,048, 8 key/value heads of 128; 48 query heads on the two
    full layers, 64 on the three window layers; 32 of 256 experts held, 8
    a token: one assignment a token on average."""
    e = 2048

    def att(heads):     # q with its gate (a column a head), k, v, out
        return e * heads * 129 + 2 * e * 8 * 128 + heads * 128 * e

    full = 4096 * 48 * 2 * 128                  # causal half of 8,192 keys
    pairs = 512 * 8192 - 512 * 511 // 2         # the band's, exactly
    window = pairs / 8192.0 * 64 * 2 * 128
    routed = e * 256 + 1.0 * 3 * e * 512 + 3 * e * 512
    per_token = 2 * e * 12544 + 2 * (att(48) + full + 3 * e * 8192) \
        + 3 * 2 * (att(64) + window + routed) + 2 * (att(48) + full + routed)
    assert count.forward_ops_per_token(CFG, 8192) == pytest.approx(
        per_token, rel=1e-12)
    forward = per_token * 8192
    assert 6.56e12 < forward < 6.58e12          # 6.57 TFLOP a step
    # the windows' attention 0.40 of it, the two full layers' 1.65
    assert 0.39e12 < 3 * 2 * window * 8192 < 0.41e12
    assert 1.64e12 < 2 * 2 * full * 8192 < 1.66e12
    assert count.train_step(CFG, {"batch": 1, "seq": 8192}) \
        == pytest.approx(3 * forward, rel=1e-12)
    # the counted assignments in place of the expected ones
    layers = [{"local_assignments": 3 * 9000}] * 4
    counters = {"batch": 1, "seq": 8192, "steps": 3, "routed_layers": layers}
    assert count.train_step(CFG, counters) - 3 * forward == pytest.approx(
        3 * 4 * 2 * (9000 - 8192) * 3 * e * 512, rel=1e-9)


def test_windowed_kernel_counts_by_hand():
    pairs = 512 * 8192 - 512 * 511 // 2
    ops, nbytes = count.flash_window_call(1, 64, 8, 8192, 512, 128, 128, 2)
    assert ops == 2 * 64 * pairs * 256
    assert nbytes == 2 * 8192 * 256 * (64 + 8) + 4 * 64 * 8192
    ops_b, nbytes_b = count.flash_window_call(1, 64, 8, 8192, 512, 128, 128,
                                              2, backward=True)
    assert ops_b == 2 * ops and nbytes_b == 2 * nbytes - 4 * 64 * 8192
    t, bound = flops.roofline_seconds(ops, nbytes, 197e12, 819e9)
    assert bound == "compute" and t == pytest.approx(0.6759e-3, rel=1e-3)


def test_parameters_and_state_by_hand():
    from perfbench.reference import laguna as ref
    shapes = ref.param_shapes(CFG)
    total = sum(int(np.prod(s)) for s in shapes.values())
    e = 2048

    def att(heads):
        return heads * 129 * e + 2 * 1024 * e + heads * 128 * e

    routed = 256 * e + 32 * 3 * e * 512 + 3 * e * 512
    assert total == (att(48) + 3 * 8192 * e) + 3 * (att(64) + routed) \
        + (att(48) + routed) + 5 * 2 * e + 2 * 12544 * e + e
    assert 691.5e6 < total < 691.7e6            # 691.6 M held
    assert 9.6e9 < 14 * total < 9.7e9           # bytes of training state
    assert shapes["layer1_att_q_weight"] == (64 * 129, e)
    assert shapes["layer4_att_q_weight"] == (48 * 129, e)
    assert "layer0_moe_router_weight" not in shapes
    assert "layer1_att_q_norm_gamma" not in shapes


def test_configuration_keeps_every_published_key():
    import json
    bench = common.load_json(common.ROOT, "BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == "laguna-xs2")
    assert entry["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size", "layer_types",
        "mlp_layer_types", "num_attention_heads_per_layer"]
    assert entry["source"] == CFG["source"]
    published = {
        "hidden_size": 2048, "num_attention_heads": 48,
        "num_key_value_heads": 8, "head_dim": 128,
        "intermediate_size": 8192, "moe_intermediate_size": 512,
        "shared_expert_intermediate_size": 512, "num_experts_per_tok": 8,
        "moe_routed_scaling_factor": 2.5, "sliding_window": 512,
        "partial_rotary_factor": 0.5, "rms_norm_eps": 1e-06,
        "tie_word_embeddings": False, "gating": True,
        "max_position_embeddings": 262144, "model_type": "laguna"}
    assert {k: CFG[k] for k in published} == published
    assert CFG["published"]["num_hidden_layers"] == 40
    assert CFG["vocab_size"] * 8 == CFG["published"]["vocab_size"] == 100352
    dep = CFG["deployment"]
    assert CFG["num_experts"] * dep["chips_sharing_a_layer"] \
        == dep["router_width"] == CFG["published"]["num_experts"] == 256
    assert CFG["layer_types"] == ["full_attention"] + [
        "sliding_attention"] * 3 + ["full_attention"]
    assert CFG["num_attention_heads_per_layer"] == [48, 64, 64, 64, 48]
    assert CFG["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert json.dumps(CFG["rope_parameters"]["full_attention"],
                      sort_keys=True) == json.dumps({
                          "rope_theta": 500000, "rope_type": "yarn",
                          "factor": 64,
                          "original_max_position_embeddings": 4096,
                          "beta_slow": 1, "beta_fast": 64,
                          "attention_factor": 1.4158883083359672,
                          "partial_rotary_factor": 0.5}, sort_keys=True)


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
    assert step_ms["workloads"][-1] == CELL
    entry = common.cell_entry(bench, CELL)
    assert entry["chips"] == 1 and len(entry["why"]) <= 200
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
    summation — but for tokens whose eighth and ninth experts tie to under
    it (the seeded routers' logits are of the order of 0.01)."""
    gaps = _gaps(tiny_driver(CELL, config_override=F32))
    assert gaps["row_loss_diff"] < 2e-3, gaps
    assert gaps["grad_norm_gap"] < 1e-2 and gaps["delta_norm_gap"] < 1e-2, \
        gaps
    assert gaps["expert_grad_diff"] < 0.1, gaps


# -- the cell through the harness ---------------------------------------------
def _run(seed=7, seconds=1.0, trace=False):
    import jax
    env = tiny_env(CELL, seed=seed)
    bench = common.load_json(common.ROOT, "BENCHMARK.json")
    entry = common.cell_entry(bench, CELL)
    return run.run_cell(bench, entry, env.cell, env.config, seed, seconds,
                        trace, jax.devices()[:1], None, True)


def test_rehearsal_run_is_correct():
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


@pytest.mark.parametrize("variant,seen_by", [
    ("control", "row_loss_diff"),
    ("wrong_share", "expert_grad_diff"),
    ("no_window", "row_loss_diff"),
    ("no_yarn", "grad_norm_gap")])
def test_the_control_and_each_fault_fail(variant, seen_by):
    """The float8 control; experts 2-3 and 4-5 of 16 held where the
    configuration says 0-3; window layers that see every key before a
    query; full layers at plain rotary with no attention factor — each in
    the program's place reads ``correct: false``, and by the number named
    (at these sizes, read on the CPU; PERF.md section 2 has the chip's
    readings at 8,192 tokens)."""
    line = _stand_in(variant, 3)
    assert line["correct"] is False, line["compared"]
    row = line["compared"][seen_by]
    assert row["value"] > row["limit"], line["compared"]


def test_parent_without_the_model_fails_at_once():
    """The parent commit has this PR's benchmark files laid over it and no
    ``models.transformer_swa_moe``: set-up raises on the import, it does
    not hang."""
    driver = tiny_driver(CELL, config_override={
        "program": {"module": "mxnet_tpu.models.no_such_model"}})
    with pytest.raises(ImportError):
        driver.setup()


# -- the new readers ----------------------------------------------------------
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


def test_window_rooflines_read_the_windowed_events_alone():
    """Two steps of three window layers: six forward events at twice the
    least time each read 50 %; the backward's two kernels make three calls
    a step of each; the causal kernels' events are not read."""
    from perfbench.readers import flash_window_roofline as reader
    least = {b: flops.roofline_seconds(*count.flash_window_call(
        1, 64, 8, 8192, 512, 128, 128, 2, backward=b), 197e12, 819e9)[0]
        * 1e9 for b in (False, True)}
    fwd = [("%%flash_window_forward.%d = bf16[8] custom-call(...)" % i,
            i * 1e6, int(2 * least[False])) for i in range(6)]
    other = [("%flash_forward.1 = bf16[8] custom-call(...)", 9e9, 10 ** 9),
             ("%flash_backward.1 = bf16[8] custom-call(...)", 9e9, 10 ** 9)]
    bwd = [("%%flash_window_backward%s.%d = bf16[8] custom-call(...)"
            % (part, i), i * 1e6, int(2 * least[True]))
           for i in range(6) for part in ("", "_dq")]
    assert reader.read(_ctx(fwd + other), "forward") \
        == pytest.approx(50.0, rel=1e-3)
    assert reader.read(_ctx(bwd + other), "backward") \
        == pytest.approx(25.0, rel=1e-3)
    assert reader.read(_ctx(other), "forward") is None
    assert reader.read(_ctx(fwd, peaks=None), "forward") is None
    for name in ("gpt2-medium", "qwen3-next-80b-a3b"):
        assert reader.read(_ctx(fwd, config=common.load_json(
            common.named_file("configs", name))), "forward") is None


def test_window_attention_share_reads_the_window_layers_nodes():
    """From a scope table as ``device_scope`` reduces it: the three window
    layers' attention nodes over the chip's busy time, every pass; the full
    layers' attention and the other nodes are not counted."""
    from perfbench.readers import device_scope, window_attention_device
    table = {"by_node": {"layer0_att": 100.0, "layer1_att": 30.0,
                         "layer2_att": 20.0, "layer3_att": 10.0,
                         "layer4_att": 100.0, "layer1_moe": 500.0}}
    ctx = _ctx([])
    ctx[device_scope._KEPT] = (table, 1000.0)
    assert window_attention_device.read(ctx) == pytest.approx(6.0)
    ctx[device_scope._KEPT] = None          # nothing to read: the parent
    assert window_attention_device.read(ctx) is None
    other = _ctx([], config=common.load_json(common.named_file(
        "configs", "gpt2-medium")))
    other[device_scope._KEPT] = (table, 1000.0)
    assert window_attention_device.read(other) is None


def test_traced_rehearsal_reads_the_counter_metric():
    """At the rehearsal sizes on the CPU no kernel runs, so the rooflines
    stay out of the line; the routing counters are read — 4 of 16 experts
    held, so about a quarter of the assignments."""
    line = _run(trace=True)
    assert line["correct"] is True, line["compared"]
    share = line["metrics"]["moe_local_assignment_pct.top8"]["value"]
    assert 10 < share < 40
    assert "flash_window_forward_roofline" not in line["metrics"]
