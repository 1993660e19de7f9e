"""Chip-free autotuner (mxnet_tpu/analysis/autotune.py +
tools/autotune.py): the v5e ResNet-50 ceiling table is a pinned
regression fixture, infeasible configs are pruned BEFORE pricing,
sweeps memoize per-graph analysis, manifests are deterministic, and
the replay loop fits a measured-vs-predicted correction."""
import json
import os
import time

import pytest

from mxnet_tpu.analysis import autotune as at
from mxnet_tpu.analysis import static_ceiling_summary, static_mfu_ceiling

from test_examples import _run, REPO as ROOT

AUTOTUNE = os.path.join(ROOT, "tools", "autotune.py")


def _resnet50():
    from mxnet_tpu.models import resnet
    return resnet.get_symbol(num_classes=1000, num_layers=50)


# ----------------------------------------------------------------------
# the pinned v5e table (docs/mfu_gap.md): the calibrated
# MXL-R model must keep reproducing the compiled AOT ceilings
# ----------------------------------------------------------------------
V5E_TABLE = [
    # batch, compiled mfu ceiling, compiled TF/step (docs/mfu_gap.md)
    (64, 0.193, 1.572),
    (256, 0.293, 6.282),
    (512, 0.331, 12.564),
]


@pytest.mark.parametrize("batch,ceiling,tflops", V5E_TABLE)
def test_v5e_resnet50_ceiling_table_fixture(batch, ceiling, tflops):
    rep = static_mfu_ceiling(_resnet50(),
                             {"data": (batch, 3, 224, 224)},
                             device_kind="v5e",
                             compute_dtype="bfloat16", grad_req="write")
    assert abs(rep["mfu_ceiling"] - ceiling) <= 0.01, \
        "b%d: %.4f vs compiled %.3f" % (batch, rep["mfu_ceiling"],
                                        ceiling)
    assert abs(rep["flops_per_step"] / 1e12 - tflops) <= 0.05
    # the calibrated traffic model stays transparent: raw per-op bytes
    # and the calibration constants ride along in the report
    assert rep["calibration"] is not None
    assert set(rep["calibration"]) == {"fusion_factor",
                                       "staging_bytes_per_param"}
    assert rep["op_hbm_bytes_per_step"] > 0
    assert rep["param_count"] > 25e6


def test_ceiling_table_is_batch_monotone():
    reps = [static_mfu_ceiling(_resnet50(),
                               {"data": (b, 3, 224, 224)},
                               device_kind="v5e",
                               compute_dtype="bfloat16",
                               grad_req="write")["mfu_ceiling"]
            for b, _c, _t in V5E_TABLE]
    assert reps[0] < reps[1] < reps[2]


def test_static_ceiling_summary_shared_path():
    out = static_ceiling_summary(_resnet50(),
                                 {"data": (256, 3, 224, 224)},
                                 device_kind="v5e",
                                 compute_dtype="bfloat16",
                                 grad_req="write")
    assert abs(out["static_mfu_ceiling"] - 0.293) <= 0.01
    assert out["static_bound"] == "bandwidth"
    assert out["static_tflops_per_step"] > 6
    # never raises: a broken graph comes back as an error key
    bad = static_ceiling_summary(42, {})
    assert "static_mfu_ceiling_error" in bad


# ----------------------------------------------------------------------
# search: ranking, pruning-before-pricing, memoization
# ----------------------------------------------------------------------
def test_search_ranks_b512_first_above_b256():
    res = at.search("resnet50", device_kind="v5e")
    assert res["entries"], "search produced no feasible configs"
    ranked_batches = [e["config"]["batch"] for e in res["entries"]]
    assert ranked_batches[0] == 512
    assert ranked_batches.index(512) < ranked_batches.index(256)
    top = res["entries"][0]["predicted"]["mfu_ceiling"]
    assert abs(top - 0.331) <= 0.01
    # equal-ceiling tie (b512 remat vs plain) breaks on HBM headroom
    b512 = [e for e in res["entries"] if e["config"]["batch"] == 512]
    assert len(b512) == 2
    assert b512[0]["predicted"]["hbm_headroom_gb"] >= \
        b512[1]["predicted"]["hbm_headroom_gb"]


def test_hbm_infeasible_pruned_without_pricing():
    memo = at.GraphMemo(device_kind="v5e")
    space = at.parse_space("batch=1024;remat=none")
    res = at.search("resnet50", device_kind="v5e", space=space,
                    memo=memo)
    assert res["counts"]["priced"] == 0
    assert res["counts"]["pruned"] == 1
    assert res["pruned"][0]["reason"].startswith("mxl-m:")
    # rejected BEFORE pricing: the memoized context ran the memory
    # report but the roofline was never computed for it
    (_key, ctx), = memo._ctxs.items()
    assert "memory" in ctx.cache
    assert "roofline_report" not in ctx.cache


def test_mxlk_illegal_tile_pruned_without_any_analysis():
    memo = at.GraphMemo(device_kind="v5e")
    space = at.parse_space("batch=64;remat=none;dtype=int8;"
                           "serve_block=8")
    res = at.search("resnet50", device_kind="v5e", space=space,
                    memo=memo)
    assert res["counts"]["priced"] == 0
    assert res["pruned"][0]["reason"].startswith("mxl-k:")
    # the tile gate is graph-free: no symbol was even built
    assert memo.stats == {"symbols_built": 0, "analyses": 0,
                          "memo_hits": 0}


def test_legal_int8_serve_block_prices_in_inference_mode():
    space = at.parse_space("batch=64;remat=none;dtype=int8;"
                           "serve_block=32")
    res = at.search("resnet50", device_kind="v5e", space=space)
    assert len(res["entries"]) == 1
    pred = res["entries"][0]["predicted"]
    assert pred["mode"] == "inference"
    assert pred["mfu_ceiling"] > 0


def test_sweep_memoizes_each_distinct_graph_once():
    space = at.parse_space(
        "batch=64,128,256,512;remat=none,blocks;"
        "bucket_mb=5,25,50;prefetch=1,2,4;"
        "serve_buckets=none,1-8-32,1-16-64")
    configs = at.space_configs(space)
    assert len(configs) >= 200
    t0 = time.time()
    res = at.search("resnet50", device_kind="v5e", space=space)
    elapsed = time.time() - t0
    c = res["counts"]
    assert c["total"] == len(configs)
    # 4 batches x 2 remat policies = 8 distinct graphs, 2 symbols;
    # every other axis is graph-free and memo-hits
    assert c["analyses"] == 8
    assert c["symbols_built"] == 2
    assert c["memo_hits"] > c["analyses"]
    assert elapsed < 60, "sweep took %.1fs" % elapsed


def test_transformer_dp2tp2_search_prices_with_ici_bytes():
    space = at.parse_space("batch=8,16;remat=none;sharding=dp2tp2")
    res = at.search("transformer", device_kind="v5e", space=space)
    assert res["entries"], [p["reason"] for p in res["pruned"]]
    for e in res["entries"]:
        assert e["predicted"]["ici_bytes"], \
            "sharded config should move ICI bytes"


# ----------------------------------------------------------------------
# grammar
# ----------------------------------------------------------------------
def test_parse_sharding_grammar():
    base = {"dp": 1, "tp": 1, "pp": 1, "ep": 1, "fsdp": False}
    assert at.parse_sharding("dp1") == base
    assert at.parse_sharding("dp2tp2") == dict(base, dp=2, tp=2)
    assert at.parse_sharding("fsdp8") == dict(base, dp=8, fsdp=True)
    assert at.parse_sharding("tp4") == dict(base, tp=4)
    # pipeline + expert axes ride the same grammar (MXL-E configs)
    assert at.parse_sharding("dp2pp4") == dict(base, dp=2, pp=4)
    assert at.parse_sharding("ep4") == dict(base, ep=4)
    assert at.parse_sharding("dp2pp2ep2") == dict(base, dp=2, pp=2,
                                                  ep=2)
    # the canonical parser lives with the sharding rules; the tuner
    # re-exports the same function
    from mxnet_tpu.parallel import parse_sharding as canonical
    assert canonical is at.parse_sharding
    with pytest.raises(ValueError):
        at.parse_sharding("zp3")
    with pytest.raises(ValueError):
        at.parse_sharding("pp4dp2")  # grammar order is fixed


def test_parse_space_rejects_unknown_axis():
    with pytest.raises(ValueError):
        at.parse_space("bogus=1")
    sp = at.parse_space("batch=32;serve_block=none,16")
    assert sp["batch"] == (32,)
    assert sp["serve_block"] == (None, 16)
    # unnamed axes keep their defaults
    assert sp["remat"] == at.default_space()["remat"]


# ----------------------------------------------------------------------
# manifest determinism + correction re-ranking
# ----------------------------------------------------------------------
def test_manifest_is_deterministic():
    outs = []
    for _ in range(2):
        res = at.search("resnet50", device_kind="v5e")
        man = at.build_manifest(res, top_k=4,
                                provenance={"tool": "test"})
        outs.append(at.canonical_json(man))
    assert outs[0] == outs[1]
    man = json.loads(outs[0])
    assert man["manifest_hash"]
    assert len(man["configs"]) == 4
    for entry in man["configs"]:
        assert entry["bench_cmd"].endswith("python bench.py")
        assert ("BENCH_AUTOTUNE_CONFIG_ID=%s" % entry["config_id"]) \
            in entry["bench_cmd"]


def test_config_id_is_content_hash():
    cfg = dict(zip(at.AXES, (256, "none", "dp1", "bfloat16", 25, 2,
                             None, None, None, 8, None, None)))
    assert len(cfg) == len(at.AXES)
    cfg["model"] = "resnet50"
    a = at.config_id(cfg)
    assert a == at.config_id(dict(cfg))
    cfg2 = dict(cfg, batch=512)
    assert a != at.config_id(cfg2)
    # the new pipeline/MoE axes are part of the hashed identity
    assert a != at.config_id(dict(cfg, stages=4))
    assert a != at.config_id(dict(cfg, experts=8))
    assert a.startswith("at-")


def test_manifest_deterministic_over_pipeline_and_moe_axes():
    # the new pp/MoE axes must not break same-inputs -> byte-identical
    # manifests: two independent sweeps (fresh memo each) over stages,
    # microbatches, experts and capacity_factor
    outs = []
    for _ in range(2):
        space = at.parse_space(
            "batch=8;remat=none;sharding=dp2pp2,ep4;microbatches=4,8;"
            "experts=none,8;capacity_factor=none,1.25")
        res = at.search("transformer_moe", device_kind="v5e",
                        space=space)
        man = at.build_manifest(res, top_k=16,
                                provenance={"tool": "test"})
        outs.append(at.canonical_json(man))
    assert outs[0] == outs[1]
    man = json.loads(outs[0])
    # a pipelined entry carries its simulated bubble and the pipeline
    # bench envs; an MoE entry carries the expert envs
    piped = [c for c in man["configs"]
             if c["config"]["sharding"] == "dp2pp2"]
    assert piped, [c["config"] for c in man["configs"]]
    for c in piped:
        assert c["predicted"]["bubble_fraction"] is not None
        assert "BENCH_PP_STAGES=2" in c["bench_cmd"]
        assert ("BENCH_MICROBATCHES=%d"
                % c["config"]["microbatches"]) in c["bench_cmd"]
    moe = [c for c in man["configs"] if c["config"]["experts"]]
    for c in moe:
        assert "BENCH_MOE_EXPERTS=8" in c["bench_cmd"]


def test_mxl_e_infeasible_pruned_before_pricing():
    memo = at.GraphMemo(device_kind="v5e")
    # 6 experts over an ep=4 mesh axis: MXL-E006 (indivisible experts)
    # must reject the config before the roofline prices it
    space = at.parse_space("batch=8;remat=none;sharding=ep4;"
                           "experts=6;capacity_factor=1.25")
    res = at.search("transformer_moe", device_kind="v5e", space=space,
                    memo=memo)
    assert res["counts"]["priced"] == 0
    assert res["counts"]["pruned"] == 1
    assert res["pruned"][0]["reason"].startswith("mxl-e:")
    assert "MXL-E006" not in res["pruned"][0]["reason"]  # message only
    # pruned BEFORE pricing: the memoized context ran the schedule
    # rules but the roofline report was never computed
    (_key, ctx), = memo._ctxs.items()
    assert "autotune_mxl_e" in ctx.cache
    assert "roofline_report" not in ctx.cache
    # the schedule gate memoizes: re-pruning the same config re-uses
    # the cached rule run (analyses stays 1)
    assert at.prune_config("transformer_moe", res["pruned"][0]["config"],
                           memo, res["hbm_budget_bytes"]) \
        .startswith("mxl-e:")
    assert memo.stats["analyses"] == 1


def test_pipeline_config_priced_with_bubble_scaled_ceiling():
    # a feasible pp=2 transformer prices with a 1F1B bubble fraction
    # and a ceiling strictly below the unpipelined one
    memo = at.GraphMemo(device_kind="v5e")
    space = at.parse_space("batch=8;remat=none;sharding=dp2,dp2pp2")
    res = at.search("transformer", device_kind="v5e", space=space,
                    memo=memo)
    by_shard = {e["config"]["sharding"]: e["predicted"]
                for e in res["entries"]}
    assert set(by_shard) == {"dp2", "dp2pp2"}, \
        [p["reason"] for p in res["pruned"]]
    assert by_shard["dp2"]["bubble_fraction"] is None
    bubble = by_shard["dp2pp2"]["bubble_fraction"]
    assert 0.0 < bubble < 1.0
    assert by_shard["dp2pp2"]["mfu_ceiling"] < \
        by_shard["dp2"]["mfu_ceiling"]


def test_fit_correction_and_rerank():
    # one point -> ratio; several -> least squares
    ratio = at.fit_correction([(0.30, 0.24)])
    assert ratio["kind"] == "ratio"
    assert abs(at.apply_correction(ratio, 0.30) - 0.24) < 1e-9
    lin = at.fit_correction([(0.30, 0.25), (0.20, 0.10), (0.10, 0.05)])
    assert lin["kind"] == "linear"
    assert lin["a"] > 0
    # measured numbers that invert the predicted order re-rank it
    entries = [
        {"config_id": "at-a", "rank": 1,
         "predicted": {"mfu_ceiling": 0.30}},
        {"config_id": "at-b", "rank": 2,
         "predicted": {"mfu_ceiling": 0.25}},
    ]
    inverting = at.fit_correction([(0.30, 0.10), (0.25, 0.20)])
    order = [e["config_id"] for e in at.rerank(entries, inverting)]
    assert order == ["at-b", "at-a"]
    # no correction: stable original order
    order = [e["config_id"] for e in at.rerank(entries, None)]
    assert order == ["at-a", "at-b"]


# ----------------------------------------------------------------------
# CLI: manifest emit + fixture replay with the slo gate
# ----------------------------------------------------------------------
@pytest.mark.slow
def test_cli_search_and_fixture_replay(tmp_path):
    man_path = tmp_path / "manifest.json"
    proc = _run(ROOT, AUTOTUNE, "--model", "resnet50",
                "--device-kind", "v5e", "--top-k", "3",
                "-o", str(man_path))
    assert proc.returncode == 0, proc.stderr
    man = json.loads(man_path.read_text())
    assert man["configs"][0]["config"]["batch"] == 512
    assert man["provenance"]["tool"] == "tools/autotune.py"

    # dry-run prints one command sheet line per config
    proc = _run(ROOT, AUTOTUNE, "--replay", str(man_path))
    assert proc.returncode == 0, proc.stderr
    lines = [ln for ln in proc.stdout.splitlines() if "bench.py" in ln]
    assert len(lines) == 3
    assert all("BENCH_AUTOTUNE_MANIFEST_HASH=%s" % man["manifest_hash"]
               in ln for ln in lines)

    # fixture replay: measured numbers feed the slo gate + correction
    runs = [{"metric": "resnet50_train_images_per_sec",
             "value": 2.0, "unit": "images/sec",
             "mfu": round(0.8 * c["predicted"]["mfu_ceiling"], 4),
             "autotune_config_id": c["config_id"]}
            for c in man["configs"]]
    runs_path = tmp_path / "runs.json"
    runs_path.write_text(json.dumps(runs))
    report_path = tmp_path / "report.json"
    proc = _run(ROOT, AUTOTUNE, "--replay", str(man_path),
                "--results", str(runs_path),
                "--baseline", os.path.join(ROOT, "BENCH_r05.json"),
                "--report", str(report_path), "--fail-on-regression")
    assert proc.returncode == 0, proc.stderr + proc.stdout
    report = json.loads(report_path.read_text())
    assert report["manifest_hash"] == man["manifest_hash"]
    assert report["regressions"] == 0
    assert report["correction"]["n"] == 3
    assert all(r["status"] == "ok" for r in report["runs"])
    assert all("mfu_gap" in r for r in report["runs"])

    # a regressed measured number trips the gate (rc 1)
    runs[0]["value"] = 0.2
    runs_path.write_text(json.dumps(runs))
    proc = _run(ROOT, AUTOTUNE, "--replay", str(man_path),
                "--results", str(runs_path),
                "--baseline", os.path.join(ROOT, "BENCH_r05.json"),
                "--fail-on-regression")
    assert proc.returncode == 1, proc.stderr + proc.stdout


def test_bench_stamps_autotune_ids(monkeypatch):
    import bench
    monkeypatch.setenv("BENCH_AUTOTUNE_CONFIG_ID", "at-test123456")
    monkeypatch.setenv("BENCH_AUTOTUNE_MANIFEST_HASH", "deadbeef")
    payload = {"metric": "x", "value": 1.0}
    bench._stamp_autotune(payload)
    assert payload["autotune_config_id"] == "at-test123456"
    assert payload["autotune_manifest_hash"] == "deadbeef"
    monkeypatch.delenv("BENCH_AUTOTUNE_CONFIG_ID")
    monkeypatch.delenv("BENCH_AUTOTUNE_MANIFEST_HASH")
    clean = {"metric": "x"}
    bench._stamp_autotune(clean)
    assert "autotune_config_id" not in clean


def test_parse_log_mfu_gap_and_config_id_columns(tmp_path):
    ev = tmp_path / "events-rank0.jsonl"
    ev.write_text(
        json.dumps({"kind": "step", "epoch": 1, "dur_ms": 100.0,
                    "samples_per_sec": 640.0}) + "\n" +
        json.dumps({"kind": "summary", "source": "bench", "mfu": 0.28,
                    "static_mfu_ceiling": 0.3297,
                    "autotune_config_id": "at-0888f23e57"}) + "\n")
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "parse_log", os.path.join(ROOT, "tools", "parse_log.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    rows = mod.parse_telemetry(str(ev))
    row = rows[1]
    assert abs(row["mfu-gap"] - 0.0497) < 1e-6
    assert row["autotune-config-id"] == "at-0888f23e57"
