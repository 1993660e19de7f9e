"""Observability subsystem tests (ISSUE 4).

Covers the event log (off-by-default, buffering, rotation), spans,
counters/percentiles, the aggregate report builder, mxtop --json, the
Speedometer/StepTimer/Monitor satellites, the <2% overhead acceptance
bound, and the 2-process telemetry drill (tier-1 promotion of
tests/nightly/dist_telemetry.py).
"""
import json
import logging
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import observability as obs
from mxnet_tpu.observability import aggregate, counters, events, spans

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _telemetry_off(monkeypatch):
    """Each test starts with telemetry off and a pristine singleton."""
    monkeypatch.delenv("MXTPU_TELEMETRY", raising=False)
    monkeypatch.delenv("MXTPU_TELEMETRY_DIR", raising=False)
    monkeypatch.delenv("MXTPU_RUN_ID", raising=False)
    events.refresh()      # get() rate-limits env probes; force recheck
    counters.reset()
    yield
    events.refresh()      # fold env restoration into the singleton
    counters.reset()


def _enable(monkeypatch, tmp_path, run_id="testrun"):
    d = str(tmp_path / "tel")
    monkeypatch.setenv("MXTPU_TELEMETRY", "1")
    monkeypatch.setenv("MXTPU_TELEMETRY_DIR", d)
    monkeypatch.setenv("MXTPU_RUN_ID", run_id)
    events.refresh()
    return d


# ----------------------------------------------------------------------
# events.py
# ----------------------------------------------------------------------
def test_disabled_by_default():
    assert not events.enabled()
    assert events.get() is None
    events.emit("step", step=1, dur_ms=1.0)      # must be a silent no-op
    events.flush()
    assert events.last_fault() is None


def test_emit_flush_roundtrip(monkeypatch, tmp_path):
    d = _enable(monkeypatch, tmp_path)
    events.emit("step", step=1, dur_ms=5.0)
    events.emit("fault", step=2, fault="sentinel_skip", phase="sentinel")
    events.flush()
    path = os.path.join(d, "events-rank00000.jsonl")
    assert os.path.exists(path)
    recs = [json.loads(l) for l in open(path)]
    assert [r["kind"] for r in recs] == ["step", "fault"]
    for r in recs:
        assert r["run_id"] == "testrun"
        assert r["rank"] == 0
        assert isinstance(r["wall_ms"], int)
    assert events.last_fault()["fault"] == "sentinel_skip"


def test_emit_is_buffered_not_written(monkeypatch, tmp_path):
    d = _enable(monkeypatch, tmp_path)
    log = events.get()
    log.emit("step", step=1, dur_ms=1.0)
    # nothing on disk until a flush (the emit hot path does no IO)
    assert not os.path.exists(log.path) \
        or os.path.getsize(log.path) == 0
    log.flush()
    assert os.path.getsize(log.path) > 0


def test_rotation_bounds_file(tmp_path):
    log = events.EventLog(str(tmp_path), rank=3, run_id="r",
                          max_bytes=4096)
    for i in range(500):
        log.emit("step", step=i, dur_ms=1.23456, pad="x" * 40)
        if i % 50 == 0:
            log.flush()
    log.close()
    assert os.path.exists(log.path + ".1")           # one predecessor
    assert os.path.getsize(log.path) <= 4096 + 8192  # bounded
    # both generations merge in read_events
    recs = aggregate.read_events(str(tmp_path))
    assert all(r["rank"] == 3 for r in recs)


def test_env_rebuild_swaps_log(monkeypatch, tmp_path):
    _enable(monkeypatch, tmp_path, run_id="a")
    first = events.get()
    monkeypatch.setenv("MXTPU_RUN_ID", "b")
    second = events.refresh()
    assert first is not second
    assert second.run_id == "b"


# ----------------------------------------------------------------------
# spans.py
# ----------------------------------------------------------------------
def test_span_without_telemetry_keeps_ring_and_skips_log():
    # telemetry off: the span still lands in the in-memory ring (and in
    # any profiler trace); only the event log is skipped
    assert events.get() is None
    spans.reset()
    with spans.span("step", step=3):
        pass
    (rec,) = spans.snapshot()
    assert rec["name"] == "step" and rec["step"] == 3
    assert rec["parent_id"] is None and rec["t1_ns"] >= rec["t0_ns"]


def test_span_records_duration(monkeypatch, tmp_path):
    d = _enable(monkeypatch, tmp_path)
    with spans.span("ckpt_save", step=7, extra="x"):
        time.sleep(0.01)
    events.flush()
    recs = aggregate.read_events(d)
    assert len(recs) == 1
    rec = recs[0]
    assert rec["kind"] == "span" and rec["name"] == "ckpt_save"
    assert rec["step"] == 7 and rec["extra"] == "x"
    assert rec["dur_ms"] >= 9.0


def test_timed_iter(monkeypatch, tmp_path):
    d = _enable(monkeypatch, tmp_path)
    out = list(spans.timed_iter([1, 2, 3], name="data_wait"))
    assert out == [1, 2, 3]
    events.flush()
    recs = aggregate.read_events(d)
    assert [r["name"] for r in recs] == ["data_wait"] * 3


# ----------------------------------------------------------------------
# counters.py
# ----------------------------------------------------------------------
def test_percentile():
    vals = list(range(1, 101))
    assert counters.percentile(vals, 50) == 50 or \
        counters.percentile(vals, 50) == 51
    assert counters.percentile(vals, 95) in (95, 96)
    assert counters.percentile([], 50) is None
    assert counters.percentile([4.0], 95) == 4.0


def test_step_stats_snapshot():
    st = counters.StepStats(batch_size=32)
    for i in range(100):
        st.observe(0.010 + (0.010 if i == 99 else 0.0), step=i)
    snap = st.snapshot()
    assert snap["steps"] == 100 and snap["last_step"] == 99
    assert snap["step_ms_p50"] == pytest.approx(10.0, rel=0.01)
    assert snap["step_ms_p95"] == pytest.approx(10.0, rel=0.01)
    assert snap["step_ms_ema"] > 10.0          # the spike moved the EMA
    assert snap["samples_per_sec"] == pytest.approx(32 / 0.0101, rel=0.01)


def test_collective_bytes_from_cost_model():
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=8, name="fc")
    from mxnet_tpu import parallel
    rep = counters.collective_bytes(net, parallel.auto_mesh(),
                                    shapes={"data": (16, 4)})
    assert rep is None or "total_bytes" in rep


# ----------------------------------------------------------------------
# aggregate.py report builder
# ----------------------------------------------------------------------
def _mk(kind, rank, wall_ms, **f):
    return dict(run_id="r", rank=rank, kind=kind, wall_ms=wall_ms,
                step=f.pop("step", None), **f)


def test_build_report_straggler_and_faults():
    recs = []
    t = 1000
    for step in range(10):
        recs.append(_mk("step", 0, t, step=step, dur_ms=10.0,
                        samples_per_sec=100.0))
        recs.append(_mk("step", 1, t + 1, step=step, dur_ms=30.0,
                        samples_per_sec=40.0))
        t += 40
    recs.append(_mk("fault", 1, t, step=9, fault="sentinel_skip"))
    recs.append(_mk("ckpt", 0, t + 1, step=9, phase="commit"))
    recs.append(_mk("counter", 0, t + 2, name="heartbeat_ages",
                    ages={"0": 1.5, "1": 2.5}))
    rep = aggregate.build_report(recs)
    pod = rep["pod"]
    assert pod["step_ms_p50"] is not None
    assert pod["step_ms_p95"] is not None
    assert pod["samples_per_sec"] == pytest.approx(140.0)
    # straggler gap = max(mean) - median(mean) = 30 - 20 = 10
    assert pod["straggler_gap_ms"] == pytest.approx(10.0)
    assert rep["per_rank"]["0"]["heartbeat_age_s"] == 1.5
    assert rep["per_rank"]["1"]["heartbeat_age_s"] == 2.5
    assert rep["per_rank"]["1"]["last_fault"]["fault"] == "sentinel_skip"
    kinds = [r["kind"] for r in rep["incidents"]]
    assert kinds == ["fault", "ckpt"]


def test_build_report_elastic_generation_rollup():
    """Elastic records are incidents AND set the pod's current
    generation/world (newest wins) plus each rank's adopted
    generation (docs/resilience.md "Elasticity")."""
    recs = [
        _mk("step", 0, 1000, step=0, dur_ms=10.0),
        _mk("elastic", 0, 1001, event="propose", generation=1,
            world_size=2, reason="dead_node", from_world=3),
        _mk("elastic", 1, 1002, event="adopt", generation=1,
            world_size=2, reason="dead_node", from_world=3),
        _mk("elastic", 0, 1003, event="resume", generation=2,
            world_size=3),
    ]
    rep = aggregate.build_report(recs)
    pod = rep["pod"]
    assert pod["generation"] == 2
    assert pod["world_size"] == 3
    assert pod["last_elastic"]["event"] == "resume"
    assert rep["per_rank"]["0"]["generation"] == 2
    assert rep["per_rank"]["1"]["generation"] == 1
    assert [r["kind"] for r in rep["incidents"]] == ["elastic"] * 3


def test_read_events_skips_torn_lines(tmp_path):
    p = tmp_path / "events-rank00000.jsonl"
    p.write_text('{"kind":"step","rank":0,"wall_ms":2}\n'
                 '{"kind":"st')                       # torn final write
    recs = aggregate.read_events(str(tmp_path))
    assert len(recs) == 1


def test_timeline_around():
    recs = [{"i": i} for i in range(20)]
    win = aggregate.timeline_around(recs, 10, before=2, after=3)
    assert [r["i"] for r in win] == [8, 9, 10, 11, 12, 13]


# ----------------------------------------------------------------------
# mxtop CLI
# ----------------------------------------------------------------------
def test_mxtop_json(monkeypatch, tmp_path):
    d = _enable(monkeypatch, tmp_path)
    for i in range(5):
        obs.record_step(i, 0.01, batch_size=8)
    events.emit("fault", step=3, fault="watchdog_timeout", phase="step")
    events.flush()
    env = dict(os.environ)
    env.pop("MXTPU_TELEMETRY", None)     # mxtop reads files, not env
    out = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "tools", "mxtop.py"),
         d, "--json"], capture_output=True, text=True, env=env,
        timeout=120)
    assert out.returncode == 0, out.stderr
    rep = json.loads(out.stdout)
    assert rep["pod"]["step_ms_p50"] is not None
    assert "mfu" in rep["pod"]
    assert rep["per_rank"]["0"]["last_fault"]["fault"] == \
        "watchdog_timeout"


def test_mxtop_surfaces_elastic_generation(monkeypatch, tmp_path):
    """The pod report shows the current generation/world and --fault
    timelines anchor on elastic transitions too."""
    d = _enable(monkeypatch, tmp_path)
    obs.record_step(0, 0.01, batch_size=8)
    events.emit("elastic", event="propose", generation=1, world_size=2,
                reason="dead_node", from_world=3)
    events.emit("elastic", event="resume", generation=1, world_size=2)
    events.flush()
    env = dict(os.environ)
    env.pop("MXTPU_TELEMETRY", None)
    mxtop = os.path.join(_ROOT, "tools", "mxtop.py")
    out = subprocess.run([sys.executable, mxtop, d],
                         capture_output=True, text=True, env=env,
                         timeout=120)
    assert out.returncode == 0, out.stderr
    assert "elastic generation 1" in out.stdout, out.stdout
    assert "world size 2" in out.stdout
    out = subprocess.run([sys.executable, mxtop, d, "--fault"],
                         capture_output=True, text=True, env=env,
                         timeout=120)
    assert out.returncode == 0, out.stderr
    assert "elastic propose generation 1 (world 2)" in out.stdout, \
        out.stdout
    assert "elastic resume generation 1 (world 2)" in out.stdout


# ----------------------------------------------------------------------
# wiring: fit loops, resilience seams
# ----------------------------------------------------------------------
def _tiny_fit(**fit_kw):
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=4, name="fc")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    rng = np.random.RandomState(0)
    X = rng.rand(40, 8).astype(np.float32)
    y = rng.randint(0, 4, (40,))
    it = mx.io.NDArrayIter(X, y, batch_size=10)
    model = mx.FeedForward(net, ctx=mx.context.cpu(), num_epoch=1,
                           learning_rate=0.1)
    logging.disable(logging.CRITICAL)
    try:
        model.fit(X=it, **fit_kw)
    finally:
        logging.disable(logging.NOTSET)
    return model


def test_feedforward_fit_emits_steps_and_data_wait(monkeypatch, tmp_path):
    d = _enable(monkeypatch, tmp_path)
    _tiny_fit()
    events.flush()
    recs = aggregate.read_events(d)
    steps = [r for r in recs if r["kind"] == "step"]
    waits = [r for r in recs if r["kind"] == "span"
             and r["name"] == "data_wait"]
    assert len(steps) == 4 and len(waits) == 4
    assert all(r["batch_size"] == 10 for r in steps)


def test_sentinel_skip_emits_fault(monkeypatch, tmp_path):
    d = _enable(monkeypatch, tmp_path)
    from mxnet_tpu.resilience import Sentinel
    s = Sentinel()
    s.check(1, loss=float("nan"))
    events.flush()
    recs = aggregate.read_events(d)
    faults = [r for r in recs if r["kind"] == "fault"]
    assert len(faults) == 1
    assert faults[0]["fault"] == "sentinel_skip"
    assert faults[0]["verdict"] == "skip-nonfinite"
    assert faults[0]["step"] == 1


def test_watchdog_timeout_emits_fault(monkeypatch, tmp_path):
    d = _enable(monkeypatch, tmp_path)
    from mxnet_tpu.resilience import ResilienceError, run_with_timeout
    with pytest.raises(ResilienceError):
        run_with_timeout(lambda: time.sleep(2.0), 0.1, phase="t",
                         step=5)
    events.flush()
    faults = [r for r in aggregate.read_events(d)
              if r["kind"] == "fault"]
    assert faults and faults[0]["fault"] == "watchdog_timeout"
    assert faults[0]["phase"] == "t" and faults[0]["step"] == 5


def test_retry_emits_fault(monkeypatch, tmp_path):
    d = _enable(monkeypatch, tmp_path)
    from mxnet_tpu.resilience import RetryPolicy, retry_call
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 2:
            raise RuntimeError("connection refused")
        return "ok"

    assert retry_call(flaky, RetryPolicy(max_tries=3),
                      sleep=lambda s: None) == "ok"
    events.flush()
    faults = [r for r in aggregate.read_events(d)
              if r["kind"] == "fault"]
    assert faults and faults[0]["fault"] == "retry"
    assert faults[0]["attempt"] == 1


def test_classic_save_checkpoint_emits_ckpt(monkeypatch, tmp_path):
    d = _enable(monkeypatch, tmp_path)
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=2, name="fc")
    args = {"fc_weight": mx.nd.array(np.ones((2, 3), np.float32)),
            "fc_bias": mx.nd.array(np.zeros(2, np.float32))}
    mx.model.save_checkpoint(str(tmp_path / "m"), 1, net, args, {})
    events.flush()
    recs = aggregate.read_events(d)
    ckpts = [r for r in recs if r["kind"] == "ckpt"]
    assert ckpts and ckpts[0]["phase"] == "commit"
    assert ckpts[0]["format"] == "classic"
    assert any(r["kind"] == "span" and r["name"] == "ckpt_save"
               for r in recs)


def test_exit_for_restart_flushes_fault(monkeypatch, tmp_path):
    """exit_for_restart must drain the telemetry buffer before
    os._exit (which skips atexit) — run in a child process."""
    d = str(tmp_path / "tel")
    code = (
        "import os\n"
        "from mxnet_tpu.resilience import ResilienceError, "
        "exit_for_restart\n"
        "err = ResilienceError('boom', phase='drill', step=42, "
        "kind='timeout')\n"
        "exit_for_restart(err)\n")
    env = {k: v for k, v in os.environ.items()}
    env.update(MXTPU_TELEMETRY="1", MXTPU_TELEMETRY_DIR=d,
               MXTPU_RUN_ID="x", JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 3
    recs = aggregate.read_events(d)
    faults = [r for r in recs if r["kind"] == "fault"]
    assert faults and faults[-1]["fault"] == "exit_restart"
    assert faults[-1]["step"] == 42


def test_sharded_trainer_step_records(monkeypatch, tmp_path):
    d = _enable(monkeypatch, tmp_path)
    from mxnet_tpu import parallel
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=4, name="fc")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    mesh = parallel.auto_mesh()
    opt = mx.optimizer.create("sgd", learning_rate=0.1)
    tr = parallel.ShardedTrainer(net, opt, mesh)
    mx.random.seed(0)
    params, opt_state, aux = tr.init_params(
        {"data": (16, 8)}, label_shapes={"softmax_label": (16,)})
    rng = np.random.RandomState(0)
    batch = tr.shard_batch(
        {"data": rng.rand(16, 8).astype(np.float32),
         "softmax_label": (rng.rand(16) * 4).astype(np.float32)})
    for _ in range(3):
        params, opt_state, aux, _out = tr.step(params, opt_state, aux,
                                               batch)
    tr.emit_telemetry_counters(step_time_s=0.01)
    events.flush()
    recs = aggregate.read_events(d)
    steps = [r for r in recs if r["kind"] == "step"]
    assert len(steps) == 3
    assert all(r["batch_size"] == 16 for r in steps)
    assert any(r["kind"] == "span" and r["name"] == "h2d" for r in recs)
    cost = [r for r in recs if r["kind"] == "counter"
            and r.get("name") == "trainer_cost"]
    assert cost and cost[0]["flops_per_step"] > 0


# ----------------------------------------------------------------------
# satellites: Speedometer, StepTimer, Monitor
# ----------------------------------------------------------------------
class _Param(object):
    def __init__(self, epoch, nbatch):
        self.epoch = epoch
        self.nbatch = nbatch
        self.eval_metric = None
        self.locals = None


def test_speedometer_uses_actual_batch_count(monkeypatch, caplog):
    """After a mid-stream (re)start the window is shorter than
    ``frequent``; speed must use the true batch count."""
    sp = mx.callback.Speedometer(batch_size=10, frequent=4)
    now = [1000.0]
    monkeypatch.setattr(time, "time", lambda: now[0])
    sp(_Param(0, 3))                       # init tick at batch 3
    now[0] += 1.0
    with caplog.at_level(logging.INFO):
        sp(_Param(0, 4))                   # only ONE batch elapsed
    assert "Speed: 10.00 samples/sec" in caplog.text  # 1*10/1s, not 4*10


def test_speedometer_auto_reset_false():
    class Metric(object):
        def __init__(self):
            self.resets = 0

        def get_name_value(self):
            return [("acc", 0.5)]

        def reset(self):
            self.resets += 1

    m = Metric()
    sp = mx.callback.Speedometer(batch_size=2, frequent=1,
                                 auto_reset=False)
    p = _Param(0, 1)
    p.eval_metric = m
    sp(p)
    p = _Param(0, 2)
    p.eval_metric = m
    time.sleep(0.001)
    sp(p)
    assert m.resets == 0
    sp2 = mx.callback.Speedometer(batch_size=2, frequent=1)
    p = _Param(0, 1)
    p.eval_metric = m
    sp2(p)
    p = _Param(0, 2)
    p.eval_metric = m
    time.sleep(0.001)
    sp2(p)
    assert m.resets == 1                   # default resets per report


def test_speedometer_emits_telemetry(monkeypatch, tmp_path):
    d = _enable(monkeypatch, tmp_path)
    sp = mx.callback.Speedometer(batch_size=6, frequent=1)
    sp(_Param(0, 1))
    time.sleep(0.002)
    sp(_Param(0, 2))
    events.flush()
    recs = [r for r in aggregate.read_events(d)
            if r.get("source") == "speedometer"]
    assert len(recs) == 1
    assert recs[0]["batch_size"] == 6
    assert recs[0]["samples_per_sec"] > 0


def test_steptimer_summary_percentiles():
    t = mx.profiler.StepTimer(batch_size=16)
    for dur in [0.01] * 94 + [0.10] * 6:
        t.times.append(dur)
    s = t.summary(skip_first=0)
    assert s["steps"] == 100
    assert s["p50_s"] == pytest.approx(0.01)
    assert s["p95_s"] == pytest.approx(0.10)
    assert s["samples_per_sec"] > 0
    assert mx.profiler.StepTimer().summary() == {}


def test_monitor_nonfinite_first_nan_localized():
    """alarm_nonfinite records the FIRST poisoned tensor by name."""
    mon = mx.monitor.Monitor(interval=1, alarm_nonfinite=True)
    mon.activated = True
    mon._record("clean", mx.nd.array(np.ones(4, np.float32)))
    mon._record("first_bad",
                mx.nd.array(np.array([np.nan, 1.0], np.float32)))
    mon._record("second_bad",
                mx.nd.array(np.array([np.inf], np.float32)))
    assert len(mon.nonfinite_records) == 2
    _step, name, _stat = mon.nonfinite_records[0]
    assert name == "first_bad"


def test_monitor_nonfinite_bounded_to_100():
    mon = mx.monitor.Monitor(interval=1, alarm_nonfinite=True)
    mon.activated = True
    bad = mx.nd.array(np.array([np.nan], np.float32))
    for i in range(250):
        mon._record("bad_%d" % i, bad)
    assert len(mon.nonfinite_records) == 100
    # the record window keeps the MOST RECENT entries
    assert mon.nonfinite_records[-1][1] == "bad_249"


# ----------------------------------------------------------------------
# acceptance: overhead bound
# ----------------------------------------------------------------------
def test_enabled_overhead_under_2_percent(monkeypatch, tmp_path):
    """The enabled emit path (tuple append, no IO) must add <2% to a
    trivial-but-real step loop.

    Methodology: the hook is purely additive host code, so the loop's
    overhead IS the per-call cost of ``record_step``.  Measure the real
    step time and the hook cost as separate per-sample medians instead
    of A/B-ing two whole loops — on a shared box the BLAS wall time
    swings far more than 2% between runs, and a subtraction of two
    noisy aggregates can't resolve the bound, while each median is
    stable."""
    a = np.random.RandomState(0).rand(512, 512)

    def work():
        # a few ms of real numpy work — the smallest credible "step"
        return (a @ a).sum()

    _enable(monkeypatch, tmp_path)
    obs.record_step(0, 0.001)              # build the log + flusher
    for _ in range(10):                    # warm the BLAS path
        work()
    steps = []
    for _ in range(50):
        t0 = time.perf_counter()
        work()
        steps.append(time.perf_counter() - t0)
    steps.sort()
    step_s = steps[len(steps) // 2]

    costs = []
    for i in range(2000):                  # flusher runs alongside
        t0 = time.perf_counter()
        obs.record_step(i, 0.001, batch_size=8)
        costs.append(time.perf_counter() - t0)
    events.flush()
    costs.sort()
    cost_s = costs[len(costs) // 2]

    ratio = (step_s + cost_s) / step_s
    assert ratio < 1.02, \
        "telemetry overhead %.1f%% (hook %.1fus on a %.2fms step)" \
        % ((ratio - 1) * 100, cost_s * 1e6, step_s * 1e3)
    # the bound above was measured WITH the metrics registry live:
    # global StepStats feeds the mxtpu_step_ms histogram on every
    # record_step, so prove the registry actually saw the samples
    from mxnet_tpu.observability import metrics as _metrics
    fed = sum(h.cumulative.count
              for h in _metrics.registry().histograms("mxtpu_step_ms"))
    assert fed >= 2000


# ----------------------------------------------------------------------
# acceptance: the 2-process drill (tier-1 promotion)
# ----------------------------------------------------------------------
def _launch(script, tmp_path, n=2, port=9901, extra_env=None,
            expect_rc=0):
    cmd = [sys.executable, os.path.join(_ROOT, "tools", "launch.py"),
           "-n", str(n), "--launcher", "local", "--workdir", _ROOT,
           "--port", str(port),
           sys.executable, os.path.join("tests", "nightly", script)]
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    env.update(extra_env or {})
    proc = subprocess.run(cmd, cwd=_ROOT, env=env, timeout=420,
                          stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                          text=True)
    assert proc.returncode == expect_rc, (proc.returncode,
                                          proc.stdout[-2000:])
    return proc.stdout


def test_dist_telemetry_drill(tmp_path):
    """Acceptance: 2-process CPU run with telemetry on produces
    per-rank JSONL whose merged mxtop --json report carries step-time
    p50/p95, samples/sec, straggler gap, per-rank heartbeat age, and
    the injected sentinel -> watchdog -> ckpt incidents in order."""
    tel_dir = str(tmp_path / "tel")
    prefix = str(tmp_path / "drillckpt")
    out = _launch("dist_telemetry.py", tmp_path, port=9903,
                  extra_env={"MXTPU_TELEMETRY": "1",
                             "MXTPU_TELEMETRY_DIR": tel_dir,
                             "MXTPU_RUN_ID": "drill",
                             "MXTPU_SENTINEL": "1",
                             "MXTPU_FAULT_SPEC": "step=2:kind=nan",
                             "MXTPU_TEL_PREFIX": prefix})
    assert out.count("TELEMETRY DRILL OK") == 2, out[-1500:]

    # per-rank JSONL exists for both ranks
    for rank in (0, 1):
        assert os.path.exists(os.path.join(
            tel_dir, "events-rank%05d.jsonl" % rank)), os.listdir(tel_dir)

    # merged mxtop --json report carries the acceptance fields
    proc = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "tools", "mxtop.py"),
         tel_dir, "--json"], capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    rep = json.loads(proc.stdout)
    assert sorted(rep["ranks"]) == [0, 1]
    assert rep["run_ids"] == ["drill"]
    pod = rep["pod"]
    assert pod["step_ms_p50"] is not None
    assert pod["step_ms_p95"] is not None
    assert pod["samples_per_sec"] is not None
    assert pod["straggler_gap_ms"] is not None
    assert "mfu" in pod
    for rank in ("0", "1"):
        age = rep["per_rank"][rank]["heartbeat_age_s"]
        assert age is not None and age < 300

    # the injected incident story, in order, on every rank:
    # sentinel_skip (the NaN batch) -> watchdog_timeout -> ckpt commit
    records = aggregate.read_events(tel_dir)
    for rank in (0, 1):
        mine = [r for r in records if r.get("rank") == rank]
        sent = [i for i, r in enumerate(mine)
                if r["kind"] == "fault"
                and r.get("fault") == "sentinel_skip"]
        wdog = [i for i, r in enumerate(mine)
                if r["kind"] == "fault"
                and r.get("fault") == "watchdog_timeout"]
        assert sent, "rank %d missing sentinel_skip" % rank
        assert wdog, "rank %d missing watchdog_timeout" % rank
        assert sent[0] < wdog[0]
    ckpt = [r for r in records if r["kind"] == "ckpt"
            and r.get("phase") == "commit"]
    assert ckpt and ckpt[0]["rank"] == 0
    wdog_wall = max(r["wall_ms"] for r in records
                    if r["kind"] == "fault"
                    and r.get("fault") == "watchdog_timeout")
    assert ckpt[0]["wall_ms"] >= wdog_wall

    # collective traffic from the dist_sync push path made it in
    assert any(r["kind"] == "collective" for r in records)

    # parse_log.py reads the telemetry dir directly
    proc = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "tools", "parse_log.py"),
         tel_dir], capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert "step-ms" in proc.stdout


# ----------------------------------------------------------------------
# ISSUE 19: sketch-backed StepStats + exact fleet/pod sketch merges
# ----------------------------------------------------------------------
def test_step_stats_sketch_backing():
    """StepStats percentiles come from a mergeable sketch and the
    snapshot carries the serialized sketch for pod rollups."""
    from mxnet_tpu.observability.metrics import QuantileSketch
    st = counters.StepStats(batch_size=8)
    for i in range(50):
        st.observe(0.010 + 0.0001 * i, step=i)
    snap = st.snapshot()
    assert "step_sketch" in snap
    back = QuantileSketch.from_dict(snap["step_sketch"])
    assert back.count == 50
    assert back.quantile(0.5) == pytest.approx(snap["step_ms_p50"],
                                               abs=1e-3)


def test_fleet_rollup_sketch_merge_exact():
    """Acceptance: the fleet-wide latency percentiles are the EXACT
    sketch-merge of per-replica streams — identical to one sketch fed
    the concatenated stream, never an average of percentiles."""
    from mxnet_tpu.observability.metrics import QuantileSketch
    from mxnet_tpu.serving.telemetry import fleet_report
    import random
    rng = random.Random(19)
    recs, all_lats = [], []
    t = 1000.0
    for replica in range(3):
        for batch in range(20):
            lats = [rng.lognormvariate(3.0, 0.8) for _ in range(8)]
            all_lats.extend(lats)
            recs.append(dict(kind="serve", replica=replica,
                             model="echo", n_requests=len(lats),
                             lat_ms=lats, wall_ms=t))
            t += 10.0
    fl = fleet_report(recs)
    assert len(fl["replicas"]) == 3
    whole = QuantileSketch()
    whole.extend(all_lats)
    lat = fl["latency_ms"]
    assert lat["p50"] == round(whole.percentile(50), 3)
    assert lat["p95"] == round(whole.percentile(95), 3)
    assert lat["p99"] == round(whole.percentile(99), 3)


def test_pod_rollup_merges_step_sketches():
    """build_report's pod p50/p95 come from merging per-rank step
    sketches — identical to one sketch over every rank's durations."""
    from mxnet_tpu.observability.metrics import QuantileSketch
    recs = []
    t = 1000
    durs = {0: 10.0, 1: 30.0}
    for step in range(20):
        for rank in (0, 1):
            recs.append(_mk("step", rank, t + rank, step=step,
                            dur_ms=durs[rank]))
        t += 40
    report = aggregate.build_report(recs)
    whole = QuantileSketch(alpha=counters.StepStats.SKETCH_ALPHA)
    for rank in (0, 1):
        whole.extend([durs[rank]] * 20)
    assert report["pod"]["step_ms_p50"] == \
        pytest.approx(whole.percentile(50), abs=1e-3)
    assert report["pod"]["step_ms_p95"] == \
        pytest.approx(whole.percentile(95), abs=1e-3)
    for s in report["per_rank"].values():
        assert "step_sketch" in s


def test_build_report_slo_rollup_and_mxtop_pane():
    """slo_alert / slo_recommendation records roll up into
    report['slo'] and mxtop renders the SLO pane from it."""
    import io
    recs = [
        _mk("step", 0, 1000, step=0, dur_ms=10.0),
        _mk("slo_alert", 0, 1010, metric="mxtpu_serve_latency_ms",
            tier="page", edge="fire", target=250.0, budget=0.01,
            threshold_burn=14.0, windows_s=[60, 10],
            burns={"60": 31.2, "10": 48.0}, at=1.01, source="mxserve"),
        _mk("counter", 0, 1011, name="slo_recommendation",
            action="recommend_grow", gen=1,
            metric="mxtpu_serve_latency_ms", reason="page-tier burn"),
        _mk("slo_alert", 0, 1050, metric="mxtpu_serve_latency_ms",
            tier="page", edge="clear", target=250.0, budget=0.01,
            threshold_burn=14.0, windows_s=[60, 10],
            burns={"60": 0.4, "10": 0.0}, at=1.05, source="mxserve"),
    ]
    report = aggregate.build_report(recs)
    slo = report["slo"]
    assert slo["alerts"] == 1            # fire edges only
    assert slo["page_alerts"] == 1
    assert slo["active"] == []           # the clear closed it
    assert slo["last_alert"]["edge"] == "clear"
    assert slo["recommendations"] == 1
    assert slo["last_recommendation"]["action"] == "recommend_grow"
    sys.path.insert(0, os.path.join(_ROOT, "tools"))
    import mxtop
    buf = io.StringIO()
    mxtop.render_slo(report, stream=buf)
    text = buf.getvalue()
    assert "SLO" in text
    assert "recommend_grow" in text


def test_metrics_exposition_from_serving_telemetry(monkeypatch):
    """The always-on serving feed: emit_batch lands in the registry
    and render_prometheus exposes it (what GET /metrics serves)."""
    from mxnet_tpu.observability import metrics as _metrics
    from mxnet_tpu.serving import telemetry as stel
    _metrics.reset_registry()
    stel.emit_batch(model="echo", bucket=8, n_requests=4, n_samples=8,
                    occupancy=0.5, padding_waste=0.5, queue_depth=2,
                    queue_wait_ms=1.0, pack_ms=0.1, device_ms=4.0,
                    unpack_ms=0.1, lat_ms=[5.0, 9.0, 12.0, 30.0])
    text = _metrics.render_prometheus()
    rows = _metrics.parse_prometheus(text)
    vals = {(n, tuple(sorted(l.items()))): v for n, l, v in rows}
    assert vals[("mxtpu_serve_requests_total", ())] == 4.0
    assert vals[("mxtpu_serve_batches_total", ())] == 1.0
    assert vals[("mxtpu_serve_queue_depth", ())] == 2.0
    assert any(n == "mxtpu_serve_latency_ms" for n, _, _ in rows)
    _metrics.reset_registry()
