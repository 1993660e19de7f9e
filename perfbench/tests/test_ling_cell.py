"""What the Ling-3.0-flash-VL cell adds to the benchmark: the counts of
``flops_ling.py`` by hand, the configuration against the published one, the
reference meeting the program at the cell's rehearsal sizes, the cell's
rehearsal run, the control and the planted faults — the wrong share of the
experts, a decay a head in place of a decay a channel, routing with no group
limit — reading ``correct: false``, and the new readers on hand-made events
and scope tables.  Metric entries are found by name, not by position."""
import numpy as np
import pytest

from helpers import tiny_driver, tiny_env

from perfbench import common, flops, flops_ling as count, run

CELL = "ling_flash_train_s8192"
CFG = common.load_json(common.named_file("configs", "ling-3.0-flash-vl"))
F32 = {"training": {"compute_dtype": "float32"}}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW_METRICS = ["kda_forward_roofline", "kda_backward_roofline",
               "kda_device_pct.train", "moe_local_assignment_pct.ling",
               "recompute_device_pct.train.ling"]


# -- the counts ------------------------------------------------------------
def test_forward_counts_by_hand():
    """Width 2,560, 32 heads of 128; five KDA layers and the latent one
    (q/k 192, v 128, a latent of 512); 8 of 512 experts held, 8 a token: an
    eighth of an assignment a token on average; 19,648 rows of the head."""
    e, hd = 2560, 32 * 128
    kda = e * 5 * hd + e * 32 + hd * e + 4 * 3 * hd
    rule = 7 * 32 * 128 * 128
    mla = e * 32 * 192 + e * 576 + 512 * 32 * 256 + e * 32 + 32 * 128 * e \
        + 4096 * 32 * (192 + 128)
    routed = e * 512 + 0.125 * 3 * e * 768 + 3 * e * 768
    per_token = 2 * e * 19648 + 5 * (2 * kda + rule) + 2 * mla \
        + 2 * 3 * e * 6144 + 5 * 2 * routed
    assert count.forward_ops_per_token(CFG, 8192) == pytest.approx(
        per_token, rel=1e-12)
    forward = per_token * 8192
    assert 8.77e12 < forward < 8.78e12          # 8.774 TFLOP a step
    assert 5.16e12 < 5 * 2 * kda * 8192 < 5.17e12   # the KDA projections
    assert count.train_step(CFG, {"batch": 1, "seq": 8192}) \
        == pytest.approx(3 * forward, rel=1e-12)
    layers = [{"local_assignments": 3 * 1100}] * 5
    counters = {"batch": 1, "seq": 8192, "steps": 3, "routed_layers": layers}
    assert count.train_step(CFG, counters) - 3 * forward == pytest.approx(
        3 * 5 * 2 * (1100 - 1024) * 3 * e * 768, rel=1e-9)


def test_kda_call_counts_by_hand():
    """The rule over one layer: 7 d_k d_v a token and head; q, k, v in
    bfloat16, g float32 a channel, β float32 a head read and o written —
    memory-bound at v5e's peaks (0.49 ms); the backward twice the
    operations, the reads and do, the five gradients written."""
    ops, nbytes = count.kda_call(1, 32, 8192, 128, 128, 2)
    assert ops == 7 * 8192 * 32 * 128 * 128
    assert nbytes == 8192 * 32 * (2 * 3 * 128 + 4 * 128 + 4 + 2 * 128)
    ops_b, nbytes_b = count.kda_call(1, 32, 8192, 128, 128, 2, backward=True)
    assert ops_b == 2 * ops
    assert nbytes_b == 8192 * 32 * (2 * (2 * 3 * 128 + 4 * 128 + 4)
                                    + 2 * 128)
    t, bound = flops.roofline_seconds(ops, nbytes, 197e12, 819e9)
    assert bound == "memory" and t == pytest.approx(0.4929e-3, rel=1e-3)


def test_parameters_and_state_by_hand():
    from perfbench.reference import ling_flash as ref
    shapes = ref.param_shapes(CFG)
    total = sum(int(np.prod(s)) for s in shapes.values())
    e, hd = 2560, 4096
    kda = 6 * hd * e + 32 * e + 3 * 4 * hd + 32 + hd + 128
    mla = 32 * 192 * e + 192 + 576 * e + 512 + 512 * 32 * 256 + 32 * e \
        + e * 32 * 128
    routed = 512 * e + 8 * 3 * e * 768 + 3 * e * 768
    assert total == 5 * kda + mla + 3 * 6144 * e + 5 * routed \
        + 6 * 2 * e + 2 * 19648 * e + e
    assert 766.9e6 < total < 767.1e6            # 767.0 M held
    assert 10.7e9 < 14 * total < 10.8e9         # bytes of training state
    assert shapes["layer4_att_q_weight"] == (32 * 192, e)
    assert shapes["layer0_kda_f_weight"] == (hd, e)
    assert "layer0_moe_router_weight" not in shapes
    assert shapes["layer5_moe_router_weight"] == (512, e)


def test_configuration_keeps_every_published_key():
    bench = common.load_json(common.ROOT, "BENCHMARK.json")
    entry = next(c for c in bench["configs"]
                 if c["name"] == "ling-3.0-flash-vl")
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size", "first_k_dense_replace"]
    assert entry["source"] == CFG["source"]
    published = {
        "hidden_size": 2560, "num_attention_heads": 32,
        "num_key_value_heads": 32, "head_dim": 128,
        "intermediate_size": 6144, "moe_intermediate_size": 768,
        "moe_shared_expert_intermediate_size": 768, "num_experts_per_tok": 8,
        "n_group": 8, "topk_group": 4, "routed_scaling_factor": 2.5,
        "kv_lora_rank": 512, "q_lora_rank": None, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "v_head_dim": 128, "rotary_dim": 64,
        "rope_theta": 6000000, "layer_group_size": 6, "kda_lower_bound": -5,
        "kda_safe_gate": True, "short_conv_kernel_size": 4,
        "rms_norm_eps": 1e-06, "max_position_embeddings": 131072,
        "score_function": "sigmoid", "norm_topk_prob": True}
    assert {k: CFG[k] for k in published} == published
    pub = CFG["published"]
    assert pub["num_hidden_layers"] == 42 and pub["first_k_dense_replace"] == 2
    assert CFG["vocab_size"] * 8 == pub["vocab_size"] == 157184
    dep = CFG["deployment"]
    assert CFG["num_experts"] * dep["chips_sharing_a_layer"] \
        == dep["router_width"] == pub["num_experts"] == 512
    assert CFG["published_layers"] == [1, 2, 3, 4, 5, 6]
    assert CFG["layer_mixers"] == ["kda"] * 4 + ["mla", "kda"]
    assert CFG["mlp_layer_types"] == ["dense"] + ["sparse"] * 5
    assert len(CFG["expert_swiglu_limit_list"]) == 42
    assert not any(CFG["expert_swiglu_limit_list"][i]
                   for i in CFG["published_layers"])


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
    reported = {m["name"] for m in run.metrics_of(
        bench, "per_layer", CELL, {"step_ms", "setup_s"})}
    assert reported == set(NEW_METRICS) | {
        "device_idle_pct.train", "step_mfu_pct.train",
        "step_dispatch_ms.train"}


# -- the reference meets the program -----------------------------------------
def test_reference_matches_program_in_float32():
    """float32 on the CPU, both sides: what is left is the order of
    summation — but for tokens whose eighth and ninth experts tie to under
    it."""
    from perfbench.drivers.train_step_zaya import zaya_values
    driver = tiny_driver(CELL, config_override=F32)
    driver.setup()
    prog = driver.program_readings()
    driver.release()
    gaps = zaya_values(prog, driver.reference_readings())
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
    ("scalar_decay", "grad_norm_gap"),
    ("no_group_limit", "expert_grad_diff")])
def test_the_control_and_each_fault_fail(variant, seen_by):
    """The float8 control; experts 2-3 and 4-5 of 32 held where the
    configuration says 0-3; each KDA head decaying by its channels' mean;
    the top-8 over all experts with no group limit — each in the program's
    place reads ``correct: false``, and by the number named (at these
    sizes, read on the CPU; PERF.md section 2 has the chip's readings at
    8,192 tokens)."""
    line = _stand_in(variant, 3)
    assert line["correct"] is False, line["compared"]
    row = line["compared"][seen_by]
    assert row["value"] > row["limit"], line["compared"]


def test_parent_without_the_model_fails_at_once():
    """The parent commit has this PR's benchmark files laid over it and no
    ``models.transformer_kda_mla_moe``: set-up raises on the import, it
    does not hang."""
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


def test_kda_rooflines_read_their_own_kernel_alone():
    """Two steps of five KDA layers: ten forward events at twice the least
    time each read 50 %, ten backward events at four times 25 %; the scalar
    rule's events and the flash kernels' are not read."""
    from perfbench.readers import kda_roofline as reader
    least = {b: flops.roofline_seconds(*count.kda_call(
        1, 32, 8192, 128, 128, 2, backward=b), 197e12, 819e9)[0] * 1e9
        for b in (False, True)}
    fwd = [("%%kda_forward.%d = bf16[8] custom-call(...)" % i, i * 1e6,
            int(2 * least[False])) for i in range(10)]
    bwd = [("%%kda_backward.%d = bf16[8] custom-call(...)" % i, i * 1e6,
            int(4 * least[True])) for i in range(10)]
    other = [("%gated_delta_forward.1 = bf16[8] custom-call(...)", 9e9,
              10 ** 9),
             ("%flash_forward.1 = bf16[8] custom-call(...)", 9e9, 10 ** 9)]
    assert reader.read(_ctx(fwd + bwd + other), "forward") \
        == pytest.approx(50.0, rel=1e-3)
    assert reader.read(_ctx(fwd + bwd + other), "backward") \
        == pytest.approx(25.0, rel=1e-3)
    assert reader.read(_ctx(other), "forward") is None
    assert reader.read(_ctx(fwd, peaks=None), "forward") is None
    for name in ("gpt2-medium", "qwen3-next-80b-a3b"):
        assert reader.read(_ctx(fwd, config=common.load_json(
            common.named_file("configs", name))), "forward") is None


def test_kda_share_reads_the_kda_layers_nodes():
    """From a scope table as ``device_scope`` reduces it: the five KDA
    layers' mixer nodes over the chip's busy time, every pass; the latent
    layer's attention and the other nodes are not counted."""
    from perfbench.readers import device_scope, kda_device
    table = {"by_node": {"layer0_kda": 100.0, "layer1_kda": 30.0,
                         "layer2_kda": 20.0, "layer3_kda": 10.0,
                         "layer4_att": 100.0, "layer5_kda": 40.0,
                         "layer1_moe": 500.0}}
    ctx = _ctx([])
    ctx[device_scope._KEPT] = (table, 1000.0)
    assert kda_device.read(ctx) == pytest.approx(20.0)
    ctx[device_scope._KEPT] = None          # nothing to read: the parent
    assert kda_device.read(ctx) is None
    other = _ctx([], config=common.load_json(common.named_file(
        "configs", "qwen3-next-80b-a3b")))
    other[device_scope._KEPT] = (table, 1000.0)
    assert kda_device.read(other) is None


def test_traced_rehearsal_reads_the_counter_metric():
    """At the rehearsal sizes on the CPU no kernel runs, so the rooflines
    stay out of the line; the routing counters are read — 4 of 32 experts
    held, 8 a token in 4 of 8 groups: about an eighth of the
    assignments."""
    line = _run(trace=True)
    assert line["correct"] is True, line["compared"]
    share = line["metrics"]["moe_local_assignment_pct.ling"]["value"]
    assert 5 < share < 25
    assert "kda_forward_roofline" not in line["metrics"]
