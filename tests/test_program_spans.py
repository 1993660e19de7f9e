"""The program's own spans: always on, kept in a bounded in-memory ring,
annotated into any profiler trace, logged only with telemetry on.

Covers ``observability/spans.py`` (ring, parent links, ``steps`` /
``self_ns`` / ``snapshot``), the call sites in ``Module.fit`` /
``Module.score`` / ``ShardedTrainer.step``, and ``tools/idle_gaps.py``.
Nothing here depends on how long anything takes.
"""
import collections
import glob
import logging
import os
import sys
import threading

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import observability as obs
from mxnet_tpu.observability import (aggregate, counters, events, flight,
                                     phases, spans)

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def fit_children(k, n, fused=True):
    """The children of step ``k``'s root in an epoch of ``n`` steps: the
    first batch is copied at its dispatch, every later one while the step
    before it runs, and the last step's fetch finds the epoch's end."""
    return ((["h2d"] if k == 1 else []) + ["step_dispatch"]
            + ([] if fused else ["update"]) + ["data_wait"]
            + (["h2d"] if k < n else []) + ["metric", "batch_end"])


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
    """Telemetry off, an empty ring, a pristine log singleton."""
    for var in ("MXTPU_TELEMETRY", "MXTPU_TELEMETRY_DIR", "MXTPU_RUN_ID",
                "MXTPU_TRACE"):
        monkeypatch.delenv(var, raising=False)
    events.refresh()
    counters.reset()
    spans.reset()
    yield
    events.refresh()
    counters.reset()
    spans.reset()


def _enable(monkeypatch, tmp_path):
    d = str(tmp_path / "tel")
    monkeypatch.setenv("MXTPU_TELEMETRY", "1")
    monkeypatch.setenv("MXTPU_TELEMETRY_DIR", d)
    monkeypatch.setenv("MXTPU_RUN_ID", "spans")
    events.refresh()
    return d


def _net():
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=4, name="fc")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _iter(batches=3, batch_size=10):
    rng = np.random.RandomState(0)
    X = rng.rand(batches * batch_size, 8).astype(np.float32)
    y = rng.randint(0, 4, (batches * batch_size,))
    return mx.io.NDArrayIter(X, y, batch_size=batch_size)


def _fit(mod=None, **kw):
    mod = mod or mx.mod.Module(_net(), context=mx.cpu())
    kw.setdefault("batch_end_callback", lambda p: None)
    logging.disable(logging.CRITICAL)
    try:
        mod.fit(_iter(), num_epoch=1, eval_metric="acc",
                optimizer_params={"learning_rate": 0.1}, **kw)
    finally:
        logging.disable(logging.NOTSET)
    return mod


def _feed(rows):
    """Hand-made closed spans: (id, parent_id, name, t0_ns, t1_ns)."""
    for sid, parent, name, t0, t1 in rows:
        spans._ring.append((sid, parent, name, None, t0, t1, 1))


# ----------------------------------------------------------------------
# the ring
# ----------------------------------------------------------------------
def test_ring_keeps_closing_order_and_fields():
    with spans.span("outer", step=7):
        with spans.span("inner"):
            pass
    with spans.span("next"):
        pass
    snap = spans.snapshot()
    assert [r["name"] for r in snap] == ["inner", "outer", "next"]
    inner, outer, nxt = snap
    assert inner["parent_id"] == outer["id"]
    assert outer["parent_id"] is None and nxt["parent_id"] is None
    assert outer["step"] == 7 and inner["step"] is None
    assert outer["id"] < inner["id"] < nxt["id"]       # ids by opening
    assert outer["t0_ns"] <= inner["t0_ns"] <= inner["t1_ns"] \
        <= outer["t1_ns"] <= nxt["t0_ns"]
    assert {r["thread"] for r in snap} == {threading.get_ident()}
    assert set(snap[0]) == {"id", "parent_id", "name", "step", "t0_ns",
                            "t1_ns", "thread"}


def test_ring_is_bounded_and_drops_the_oldest(monkeypatch):
    assert spans._ring.maxlen == spans.RING_CAPACITY >= 1024
    monkeypatch.setattr(spans, "_ring", collections.deque(maxlen=4))
    for i in range(7):
        with spans.span("s%d" % i):
            pass
    assert [r["name"] for r in spans.snapshot()] == ["s3", "s4", "s5", "s6"]


def test_span_survives_an_exception_inside_it():
    with pytest.raises(ValueError):
        with spans.span("outer"):
            with spans.span("inner"):
                raise ValueError("boom")
    assert [r["name"] for r in spans.snapshot()] == ["inner", "outer"]
    with spans.span("after"):       # the thread's stack is balanced again
        pass
    assert spans.snapshot()[-1]["parent_id"] is None


def test_self_ns_on_a_hand_nested_case():
    _feed([(2, 1, "data_wait", 100, 150),
           (4, 3, "metric_sync", 420, 480),
           (3, 1, "metric", 400, 500),
           (5, 1, "step_dispatch", 200, 300),
           (1, None, "fit_step", 0, 1000)])
    (root,) = spans.steps(1)
    assert root.name == "fit_step" and root.dur_ns == 1000
    # children by start time, whatever order they closed in
    assert [c.name for c in root.children] == ["data_wait", "step_dispatch",
                                               "metric"]
    assert [r.name for r in root.walk()] == [
        "fit_step", "data_wait", "step_dispatch", "metric", "metric_sync"]
    assert spans.self_ns(root) == 1000 - 50 - 100 - 100
    (metric,) = root.named("metric")
    assert spans.self_ns(metric) == 100 - 60
    assert spans.self_ns(metric.children[0]) == 60


def test_parents_are_per_thread():
    inside = threading.Event()
    done = threading.Event()

    def worker():
        with spans.span("worker_outer"):
            with spans.span("worker_inner"):
                inside.set()
                assert done.wait(10)

    t = threading.Thread(target=worker)
    with spans.span("main_outer"):
        t.start()
        assert inside.wait(10)
        with spans.span("main_inner"):      # opened while the worker's are
            pass
        done.set()
        t.join(10)
        assert not t.is_alive()
    by_name = {r["name"]: r for r in spans.snapshot()}
    assert by_name["worker_outer"]["parent_id"] is None
    assert by_name["worker_inner"]["parent_id"] == \
        by_name["worker_outer"]["id"]
    assert by_name["main_inner"]["parent_id"] == by_name["main_outer"]["id"]
    assert by_name["worker_outer"]["thread"] != by_name["main_outer"]["thread"]


def test_steps_takes_roots_with_a_dispatch_newest_last():
    _feed([(1, None, "step_dispatch", 0, 10),           # a trainer's step
           (2, None, "h2d", 20, 30),                    # no dispatch: no step
           (4, 3, "data_wait", 40, 45),
           (3, None, "fit_step", 40, 50),               # the epoch's end
           (6, 5, "step_dispatch", 60, 70),
           (5, None, "fit_step", 55, 90),
           (8, 7, "orphan_of_an_open_span", 95, 96)])   # 7 has not closed
    got = spans.steps(5)
    assert [r.id for r in got] == [1, 5]
    assert [r.id for r in spans.steps(1)] == [5]
    assert spans.steps(0) == []


def test_steps_drops_a_root_the_full_ring_may_have_cut(monkeypatch):
    monkeypatch.setattr(spans, "_ring", collections.deque(maxlen=4))
    _feed([(2, 1, "data_wait", 0, 10),          # will be dropped
           (3, 1, "step_dispatch", 10, 20),
           (1, None, "fit_step", 0, 30),
           (5, 4, "step_dispatch", 40, 50),
           (4, None, "fit_step", 35, 60)])
    assert len(spans._ring) == 4
    # root 1 started before the oldest kept record closed: not whole
    assert [r.id for r in spans.steps(2)] == [4]


def test_registry_names_the_new_phases():
    from mxnet_tpu import profiler
    for name in ("fit_step", "step_dispatch", "update", "metric",
                 "metric_sync", "batch_end", "epoch_end"):
        assert phases.is_canonical(name)
        assert name in spans.SPAN_NAMES and name in profiler.PHASES
    assert phases.STEP_DISPATCH == "step_dispatch"
    assert phases.METRIC_SYNC == "metric_sync"


# ----------------------------------------------------------------------
# Module.fit / score, telemetry off
# ----------------------------------------------------------------------
def test_fit_leaves_one_root_per_step_in_order():
    assert events.get() is None
    _fit()
    got = spans.steps(10)
    assert len(got) == 3
    for k, root in enumerate(got, 1):
        assert root.name == "fit_step" and root.parent_id is None
        assert root.step == k
        assert [c.name for c in root.children] == fit_children(k, 3)
        (metric,) = root.named("metric")
        assert metric.children and \
            {c.name for c in metric.children} == {"metric_sync"}
        times = [t for c in root.children for t in (c.t0_ns, c.t1_ns)]
        assert times == sorted(times)
        assert root.t0_ns <= times[0] and times[-1] <= root.t1_ns
        assert 0 <= spans.self_ns(root) <= root.dur_ns
        # the fetch inside step k's root is batch k+1's, and so is the
        # copy that follows it: issued once step k has been dispatched
        (dispatch,) = root.named("step_dispatch")
        (wait,) = root.named("data_wait")
        assert wait.step == k + 1 and wait.t0_ns >= dispatch.t1_ns
        ahead = [c for c in root.named("h2d") if c.t0_ns >= wait.t1_ns]
        assert len(ahead) == (1 if k < 3 else 0)
    # the first batch's fetch stands before the first root; the fetch
    # that found the epoch's end is in the last step's root, which has a
    # dispatch like any other; then the parameters' round trip through
    # the host, under a span of its own
    roots = [r for r in spans.snapshot() if r["parent_id"] is None]
    assert [r["name"] for r in roots] == \
        ["data_wait"] + ["fit_step"] * 3 + ["epoch_end"]
    assert roots[0]["step"] == 1
    assert roots[0]["t1_ns"] <= roots[1]["t0_ns"]
    assert roots[-1]["step"] == 3


def test_a_second_fit_is_told_apart_though_step_numbers_repeat():
    mod = _fit()
    first = [r.id for r in spans.steps(3)]
    _fit(mod)
    second = spans.steps(3)
    assert [r.step for r in second] == [1, 2, 3]       # numbers start again
    assert min(r.id for r in second) > max(first)
    assert [r.id for r in spans.steps(6)] == first + [r.id for r in second]


def test_non_fused_fit_has_an_update_span(monkeypatch):
    monkeypatch.setenv("MXNET_MODULE_FUSED", "0")
    _fit()
    for k, root in enumerate(spans.steps(3), 1):
        assert [c.name for c in root.children] == fit_children(
            k, 3, fused=False)


def test_fit_with_the_sentinel_on(monkeypatch):
    monkeypatch.setenv("MXTPU_SENTINEL", "1")
    _fit()
    assert len(spans.steps(3)) == 3


def test_score_shares_the_spans():
    mod = _fit()
    spans.reset()
    mod.score(_iter(batches=2), "acc")
    names = [r["name"] for r in spans.snapshot() if r["parent_id"] is None]
    assert names == ["h2d", "step_dispatch", "metric"] * 2
    assert [r.name for r in spans.steps(5)] == ["step_dispatch"] * 2


# ----------------------------------------------------------------------
# ShardedTrainer.step
# ----------------------------------------------------------------------
def _trainer_steps(n=3, **kw):
    from mxnet_tpu import parallel
    opt = mx.optimizer.create("sgd", learning_rate=0.1)
    tr = parallel.ShardedTrainer(_net(), opt, parallel.auto_mesh(), **kw)
    mx.random.seed(0)
    params, opt_state, aux = tr.init_params(
        {"data": (16, 8)}, label_shapes={"softmax_label": (16,)})
    rng = np.random.RandomState(0)
    batch = tr.shard_batch(
        {"data": rng.rand(16, 8).astype(np.float32),
         "softmax_label": (rng.rand(16) * 4).astype(np.float32)})
    for _ in range(n):
        params, opt_state, aux, _out = tr.step(params, opt_state, aux, batch)
    return tr


def test_trainer_steps_are_parentless_dispatches():
    _trainer_steps(3)
    got = spans.steps(3)
    assert [r.name for r in got] == ["step_dispatch"] * 3
    assert all(r.parent_id is None and not r.children for r in got)
    assert [r.step for r in got] == [1, 2, 3]
    assert [r["name"] for r in spans.snapshot()][0] == "h2d"
    assert len(spans.steps(4)) == 3         # no more than there are


def test_trainer_dispatch_is_spanned_inside_the_timeout_guard():
    _trainer_steps(2, step_timeout_s=60.0)
    got = spans.steps(2)
    assert [r.step for r in got] == [1, 2]
    # the guard runs the dispatch on its own thread: still a root there
    assert all(r.parent_id is None for r in got)
    assert all(r.thread != threading.get_ident() for r in got)


def test_trainer_step_records_come_from_the_span(monkeypatch, tmp_path):
    d = _enable(monkeypatch, tmp_path)
    _trainer_steps(3)
    events.flush()
    recs = aggregate.read_events(d)
    steps = [r for r in recs if r["kind"] == "step"]
    disp = [r for r in recs if r["kind"] == "span"
            and r["name"] == "step_dispatch"]
    assert [r["step"] for r in steps] == [1, 2, 3] == \
        [r["step"] for r in disp]
    assert all(r["timing"] == "dispatch" and r["batch_size"] == 16
               for r in steps)
    # one pair of clock reads, rounded once on each path
    assert [r["dur_ms"] for r in steps] == pytest.approx(
        [r["dur_ms"] for r in disp], abs=0.0011)


# ----------------------------------------------------------------------
# the profiler's trace
# ----------------------------------------------------------------------
def test_profiler_trace_holds_the_spans_on_the_callers_line(tmp_path):
    import jax
    from jax.profiler import ProfileData
    mod = _fit()                    # compiled before the capture
    spans.reset()
    assert events.get() is None
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation("test.caller"):
            _fit(mod)
    finally:
        jax.profiler.stop_trace()
    (pb,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    lines = [line for plane in ProfileData.from_file(pb).planes
             if plane.name.startswith("/host:CPU") for line in plane.lines]
    ring = collections.defaultdict(list)
    for root in spans.steps(3):
        for rec in root.walk():
            ring[rec.name].append(rec.dur_ns)
    for name in ("step_dispatch", "metric_sync"):
        holding = [line for line in lines
                   if any(ev.name == "mx." + name for ev in line.events)]
        assert len(holding) == 1
        (line,) = holding
        assert any(ev.name == "test.caller" for ev in line.events)
        traced = [ev.duration_ns for ev in sorted(
            line.events, key=lambda ev: ev.start_ns)
            if ev.name == "mx." + name]
        assert len(traced) == len(ring[name]) > 0
        for in_trace, in_ring in zip(traced, ring[name]):
            # the annotation opens before the ring's first clock read and
            # closes after its second: same interval, a few microseconds
            # wider (the slack allows for a preempted test machine)
            assert in_ring <= in_trace <= in_ring + 50e6


# ----------------------------------------------------------------------
# telemetry on: the log's records keep their shape
# ----------------------------------------------------------------------
def test_log_records_keep_their_shape(monkeypatch, tmp_path):
    d = _enable(monkeypatch, tmp_path)
    with spans.span("ckpt_save", step=7, extra="x"):
        pass
    events.flush()
    (rec,) = aggregate.read_events(d)
    assert set(rec) == {"kind", "name", "step", "dur_ms", "extra", "rank",
                        "run_id", "wall_ms"}
    assert rec["kind"] == "span" and rec["name"] == "ckpt_save"
    assert rec["step"] == 7 and rec["extra"] == "x"
    assert isinstance(rec["dur_ms"], float)
    # and the ring got the same span
    assert [r["name"] for r in spans.snapshot()] == ["ckpt_save"]


def test_log_records_carry_trace_ids_only_under_mxtpu_trace(monkeypatch,
                                                            tmp_path):
    from mxnet_tpu.observability import trace
    d = _enable(monkeypatch, tmp_path)
    with spans.span("step"):
        pass
    monkeypatch.setenv("MXTPU_TRACE", "1")
    trace.refresh()
    try:
        with spans.span("step"):
            with spans.span("allreduce"):
                pass
    finally:
        monkeypatch.delenv("MXTPU_TRACE")
        trace.refresh()
    events.flush()
    plain, inner, outer = [r for r in aggregate.read_events(d)
                           if r["kind"] == "span"]
    assert "span_id" not in plain and "trace_id" not in plain
    assert inner["parent_span"] == outer["span_id"]


def test_fit_log_has_steps_spans_and_no_record_of_the_end_fetch(
        monkeypatch, tmp_path):
    d = _enable(monkeypatch, tmp_path)
    _fit()
    events.flush()
    recs = aggregate.read_events(d)
    by_name = collections.Counter(r["name"] for r in recs
                                  if r["kind"] == "span")
    assert by_name["fit_step"] == by_name["data_wait"] == 3
    assert by_name["step_dispatch"] == by_name["metric"] == 3
    # one copy at the first dispatch, two issued a batch ahead
    h2d = [r for r in recs if r["kind"] == "span" and r["name"] == "h2d"]
    assert [r.get("ahead") for r in h2d] == [None, 1, 1]
    steps = [r for r in recs if r["kind"] == "step"]
    assert [r["step"] for r in steps] == [1, 2, 3]
    assert all(r["timing"] == "iteration" and r["batch_size"] == 10
               for r in steps)
    # the step record's duration is its fit_step span's
    assert [r["dur_ms"] for r in steps] == pytest.approx(
        [r["dur_ms"] for r in recs
         if r["kind"] == "span" and r["name"] == "fit_step"], abs=0.0011)
    # the ring holds the end fetch all the same, in the last step's root
    assert sum(r["name"] == "data_wait" for r in spans.snapshot()) == 4
    assert sum(r["name"] == "fit_step" for r in spans.snapshot()) == 3


def test_timed_iter_spans_every_fetch_and_logs_the_items(monkeypatch,
                                                         tmp_path):
    assert list(spans.timed_iter([1, 2])) == [1, 2]     # telemetry off
    assert [r["name"] for r in spans.snapshot()] == ["data_wait"] * 3
    d = _enable(monkeypatch, tmp_path)
    n = [0]

    def step():
        n[0] += 1
        return n[0]

    assert list(spans.timed_iter("ab", name="fetch", step_from=step)) == \
        ["a", "b"]
    events.flush()
    recs = aggregate.read_events(d)
    assert [(r["name"], r["step"]) for r in recs] == [("fetch", 1),
                                                      ("fetch", 2)]


def test_overlap_report_leaves_out_spans_inside_whole_iterations():
    def rec(kind, wall_ms, dur_ms, **kw):
        return dict(kind=kind, wall_ms=wall_ms, dur_ms=dur_ms, rank=0, **kw)

    steps = [rec("step", 1000.0 * i, 1000.0, timing="iteration")
             for i in range(1, 6)]
    in_loop = [rec("span", 1000.0 * i + 100.0, 90.0, name="h2d")
               for i in range(1, 5)]
    producer = [rec("span", 1000.0 * i + 500.0, 400.0, name="data_wait",
                    **{"async": 1}) for i in range(1, 5)]
    serial = obs.overlap_report(steps + in_loop)
    assert serial["overlap_ratio"] == pytest.approx(1.0)
    fed = obs.overlap_report(steps + in_loop + producer)
    assert fed["overlap_ratio"] == pytest.approx(1.4)
    assert fed["phase_ms"] == {"data_wait": pytest.approx(1600.0)}


# ----------------------------------------------------------------------
# the flight dump, and tools/idle_gaps.py
# ----------------------------------------------------------------------
def test_flight_dump_carries_the_last_spans(tmp_path):
    import json
    with spans.span("ckpt_save", step=3):
        pass
    rec = flight.FlightRecorder(depth=8)
    path = rec.dump("test", directory=str(tmp_path))
    with open(path) as f:
        doc = json.load(f)
    assert [s["name"] for s in doc["spans"]] == ["ckpt_save"]
    assert doc["spans"][0]["step"] == 3


@pytest.fixture()
def idle_gaps_tool():
    sys.path.insert(0, os.path.join(_ROOT, "tools"))
    try:
        import idle_gaps
        yield idle_gaps
    finally:
        sys.path.remove(os.path.join(_ROOT, "tools"))


def test_idle_gaps_charges_the_innermost_span(idle_gaps_tool):
    host = [("mx.fit_step", 0.0, 100.0), ("mx.data_wait", 0.0, 10.0),
            ("mx.metric", 40.0, 50.0), ("mx.metric_sync", 45.0, 30.0)]
    assert idle_gaps_tool.innermost_timeline(host) == [
        (0.0, 10.0, "mx.data_wait"), (10.0, 40.0, "mx.fit_step"),
        (40.0, 45.0, "mx.metric"), (45.0, 75.0, "mx.metric_sync"),
        (75.0, 90.0, "mx.metric"), (90.0, 100.0, "mx.fit_step")]
    charged = idle_gaps_tool.innermost_charges(
        [(5.0, 20.0), (50.0, 95.0), (98.0, 120.0)], host)
    assert charged == {"mx.data_wait": 5.0, "mx.fit_step": 17.0,
                       "mx.metric_sync": 25.0, "mx.metric": 15.0,
                       "(no host span)": 20.0}
    assert idle_gaps_tool.innermost_charges([(0.0, 5.0)], []) == {
        "(no host span)": 5.0}


def test_idle_gaps_reads_a_recorded_capture(idle_gaps_tool, capsys):
    small = os.path.join(_ROOT, "perfbench", "tests",
                         "small_trace.xplane.pb.gz")
    rep = idle_gaps_tool.report(idle_gaps_tool.trace_reduce.load(small))
    (chip,) = rep
    assert chip == "/device:TPU:0"
    r = rep[chip]
    assert 0 < r["idle_s"] <= r["window_s"]
    # recorded before the program annotated its spans: nothing is named
    assert r["by_span"] == [["(no host span)", pytest.approx(r["idle_s"])]]
    assert r["named_share"] == pytest.approx(0.0)
    assert idle_gaps_tool.main([small]) == 0
    assert "(no host span)" in capsys.readouterr().out


# ----------------------------------------------------------------------
# what a step costs the host beside its one dispatch: no other program
# ----------------------------------------------------------------------
def _stepper_trainer():
    from mxnet_tpu import parallel
    opt = mx.optimizer.create("sgd", learning_rate=0.1, momentum=0.9,
                              wd=1e-4)
    tr = parallel.ShardedTrainer(_net(), opt, parallel.auto_mesh())
    mx.random.seed(0)
    state = list(tr.init_params({"data": (16, 8)},
                                label_shapes={"softmax_label": (16,)}))
    rng = np.random.RandomState(0)
    batch = tr.shard_batch(
        {"data": rng.rand(16, 8).astype(np.float32),
         "softmax_label": (rng.rand(16) * 4).astype(np.float32)})

    def step():
        state[:] = tr.step(*state, batch)[:3]
    return step, "train_step"


def _stepper_module():
    mod = mx.mod.Module(_net(), context=mx.cpu())
    it = _iter(batches=1, batch_size=16)
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params(mx.init.Uniform(0.1))
    mod.init_optimizer(optimizer="sgd", optimizer_params={
        "learning_rate": 0.1, "momentum": 0.9, "wd": 1e-4})
    batch = next(iter(it))
    exe = mod._exec_group.execs[0]

    def step():
        n = exe._n_fused_step
        mod.forward_backward(batch)
        mod.update()
        assert exe._n_fused_step == n + 1
    return step, "step"


@pytest.mark.parametrize("make", [_stepper_trainer, _stepper_module],
                         ids=["ShardedTrainer.step", "Executor.fused_step"])
def test_a_step_compiles_one_program_and_none_for_its_scalars(make):
    """``lr``, ``wd`` and the update count ride with the jitted call as
    host numbers: with every cache emptied, three steps compile the step
    and nothing else — no ``convert_element_type`` (what ``jnp.float32(lr)``
    is: a device program and a transfer of its own, every step) and no
    ``PRNGKey`` for a graph that draws nothing."""
    import jax

    class Names(logging.Handler):
        def __init__(self):
            super().__init__()
            self.names = []

        def emit(self, record):
            msg = record.getMessage()
            if msg.startswith("Compiling "):
                self.names.append(msg.split()[1])

    step, name = make()
    step()                  # binds, and makes what a process makes once
    seen = Names()
    logger = logging.getLogger("jax")
    logger.addHandler(seen)
    jax.clear_caches()
    try:
        with jax.log_compiles():
            for _ in range(3):
                step()
    finally:
        logger.removeHandler(seen)
    assert seen.names == ["jit(%s)" % name]
