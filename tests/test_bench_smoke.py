"""chip_smoke.py and bench.py measure an accelerator or nothing.

With no TPU both exit non-zero within seconds and print no result (a
CPU number is never written under a device metric's name; bench.py has
no orchestrator, fallback or replay tier left to do so).  What CAN run
here is chip_smoke.py's explicit rehearsal: the same four phases at
tiny sizes on fake host devices, kernels interpreted."""
import json
import os
import subprocess
import sys
import time

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script, *argv, timeout=600, cwd=_ROOT, **env_extra):
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu", "PYTHONPATH": _ROOT})
    env.pop("XLA_FLAGS", None)
    env.update(env_extra)
    return subprocess.run([sys.executable, script] + list(argv), env=env,
                          cwd=cwd, capture_output=True, text=True,
                          timeout=timeout)


@pytest.mark.parametrize("script", ["chip_smoke.py", "bench.py"])
def test_no_chip_means_no_result(script):
    t0 = time.monotonic()
    p = _run(os.path.join(_ROOT, script), timeout=120)
    assert p.returncode not in (0, None), p.stdout
    assert time.monotonic() - t0 < 60
    assert "no TPU" in p.stderr
    assert not [l for l in p.stdout.splitlines() if l.startswith("{")]


def test_chip_smoke_alone_fails(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else of the
    repo the script must fail, not report."""
    import shutil
    shutil.copy(os.path.join(_ROOT, "chip_smoke.py"), tmp_path)
    for flags in ((), ("--rehearse",)):
        p = _run(str(tmp_path / "chip_smoke.py"), *flags, timeout=120,
                 cwd=str(tmp_path), PYTHONPATH="")
        assert p.returncode != 0
        assert '"ok"' not in p.stdout


def test_bench_has_no_orchestrator_left():
    sys.path.insert(0, _ROOT)
    import bench
    for gone in ("orchestrate", "_run_child", "_run_graceful",
                 "_session_harvest", "_probe_backend", "_measure_smoke"):
        assert not hasattr(bench, gone), gone
    src = open(os.path.join(_ROOT, "bench.py")).read()
    for knob in ("BENCH_FORCE_PLATFORM", "BENCH_FALLBACK",
                 "BENCH_ALLOW_REPLAY", "BENCH_SMOKE", "MXTPU_BENCH_CHILD"):
        assert knob not in src, knob
    # the peak table stays: chip_smoke.py and the roofline read it
    assert bench._lookup_peak_tflops("TPU v5 lite")[0] == 197.0
    assert bench._lookup_peak_hbm("TPU v5 lite")[0] == 819.0
    assert bench._lookup_peak_tflops("TPU v9 imaginary")[0] is None


@pytest.mark.slow
def test_chip_smoke_rehearsal_passes(tmp_path):
    """All four phases on two fake host devices: the n>1 checks (every
    array on both devices, an all-reduce in both compiled train steps)
    are rehearsed too.  The output is marked as a rehearsal."""
    p = _run(os.path.join(_ROOT, "chip_smoke.py"), "--rehearse",
             timeout=900,
             XLA_FLAGS="--xla_force_host_platform_device_count=2",
             JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    assert p.returncode == 0, p.stderr[-3000:]
    lines = [json.loads(l) for l in p.stdout.splitlines()
             if l.startswith("{")]
    assert lines[-1] == {"ok": True, "rehearsal": True,
                         "device": {"platform": "cpu", "kind": "cpu",
                                    "count": 2}}
    phases = {l["phase"]: l for l in lines if "phase" in l}
    assert list(phases) == ["train/resnet50", "train/lm", "serve/lm",
                            "kernels"]
    assert all(l["ok"] for l in phases.values())
    assert phases["serve/lm"]["lowerings_after_warmup"] == 0
    assert phases["serve/lm"]["logits_cosine_min"] >= 0.999
    assert "compile cache: %s" % (tmp_path / "cache") in p.stdout
