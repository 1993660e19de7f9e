"""The readers of the program's span ring, on rings fed by hand.

Three steps, times in nanoseconds (``fit_step`` roots 1, 10, 20; a
``metric`` holds two ``metric_sync``; step 10 also has an ``update`` that
holds an ``allreduce``):

    step 1   fit_step 0..1000          data_wait 0..100        h2d 100..300
             step_dispatch 300..400    metric 400..900
             metric_sync 410..800 and 810..850
    step 10  fit_step 1000..2200       data_wait 1000..1020    h2d 1020..1320
             step_dispatch 1320..1500  update 1500..1600 (allreduce 1510..1590)
             metric 1600..2100         metric_sync 1610..2000 and 2010..2050
    step 20  fit_step 2200..3000       data_wait 2200..2210    h2d 2210..2410
             step_dispatch 2410..2470  metric 2470..2900
             metric_sync 2480..2800 and 2810..2890

By hand: ``step_dispatch`` self times 100 + 180 + 60 = 340 ns over 3 steps;
``data_wait`` + ``h2d`` (100 + 200) + (20 + 300) + (10 + 200) = 830 ns;
``metric_sync`` (390 + 40) + (390 + 40) + (320 + 80) = 1260 ns; host gaps:
step 10's dispatch ends at 1500, step 1's last sync at 850: 650; step 20's
dispatch ends at 2470, step 10's last sync at 2050: 420; mean 535 ns.
"""
import json

import pytest

from mxnet_tpu.observability import spans
from perfbench import common
from perfbench.readers import host_gap, program_span

STEPS = [
    (2, 1, "data_wait", 0, 100), (3, 1, "h2d", 100, 300),
    (4, 1, "step_dispatch", 300, 400),
    (6, 5, "metric_sync", 410, 800), (7, 5, "metric_sync", 810, 850),
    (5, 1, "metric", 400, 900), (1, None, "fit_step", 0, 1000),

    (11, 10, "data_wait", 1000, 1020), (12, 10, "h2d", 1020, 1320),
    (13, 10, "step_dispatch", 1320, 1500),
    (15, 14, "allreduce", 1510, 1590), (14, 10, "update", 1500, 1600),
    (17, 16, "metric_sync", 1610, 2000), (18, 16, "metric_sync", 2010, 2050),
    (16, 10, "metric", 1600, 2100), (10, None, "fit_step", 1000, 2200),

    (21, 20, "data_wait", 2200, 2210), (22, 20, "h2d", 2210, 2410),
    (23, 20, "step_dispatch", 2410, 2470),
    (25, 24, "metric_sync", 2480, 2800), (26, 24, "metric_sync", 2810, 2890),
    (24, 20, "metric", 2470, 2900), (20, None, "fit_step", 2200, 3000),
    # the fetch that ended the epoch, then something unrelated
    (31, 30, "data_wait", 3000, 3050), (30, None, "fit_step", 3000, 3060),
    (40, None, "ckpt_save", 3100, 3900),
]
BARE = [(1, None, "h2d", 0, 50), (2, None, "step_dispatch", 100, 130),
        (3, None, "step_dispatch", 200, 250), (4, None, "step_dispatch",
                                               300, 340)]


def feed(rows):
    spans.reset()
    for sid, parent, name, t0, t1 in rows:
        spans._ring.append((sid, parent, name, None, t0, t1, 1))


@pytest.fixture(autouse=True)
def _empty_ring():
    spans.reset()
    yield
    spans.reset()


def ctx(steps):
    return {"counters": {"steps": steps}}


def metric_args(name):
    spec = common.load_json(common.named_file("metrics", name))
    return spec["reader"], spec.get("args", {})


@pytest.mark.parametrize("metric, by_hand_ns", [
    ("step_dispatch_ms.train", 340 / 3),
    ("feed_ms.train", 830 / 3),
    ("metric_sync_ms.train", 1260 / 3)])
def test_program_span_gives_the_number_worked_by_hand(metric, by_hand_ns):
    reader, args = metric_args(metric)
    assert reader == "program_span"
    feed(STEPS)
    assert program_span.read(ctx(3), **args) == pytest.approx(by_hand_ns
                                                              * 1e-6)


def test_self_time_leaves_out_what_children_cover():
    feed(STEPS)
    # update 100 ns with an 80 ns allreduce inside: 20 ns of its own
    assert program_span.read(ctx(3), names=["update"]) == \
        pytest.approx(20e-6 / 3)
    assert program_span.read(ctx(3), names=["metric"]) == pytest.approx(
        ((500 - 430) + (500 - 430) + (430 - 400)) * 1e-6 / 3)


def test_host_gap_has_one_value_fewer_than_steps():
    assert metric_args("host_gap_ms.train") == ("host_gap", {})
    feed(STEPS)
    assert host_gap.read(ctx(3)) == pytest.approx((650 + 420) / 2 * 1e-6)
    assert host_gap.read(ctx(2)) == pytest.approx(420e-6)   # the last two
    assert host_gap.read(ctx(1)) is None                    # no pair


def test_the_window_is_the_last_steps_by_links_not_by_numbers():
    feed(STEPS)
    # the last two step roots: 10 and 20 (180 + 60 ns of dispatch)
    assert program_span.read(ctx(2), names=["step_dispatch"]) == \
        pytest.approx(240e-6 / 2)


def test_bare_dispatches_are_steps_with_nothing_to_feed_or_sync():
    feed(BARE)
    assert program_span.read(ctx(3), names=["step_dispatch"]) == \
        pytest.approx((30 + 50 + 40) * 1e-6 / 3)
    # no step holds a span of these names
    assert program_span.read(ctx(3), names=["data_wait", "h2d"]) is None
    assert program_span.read(ctx(3), names=["metric_sync"]) is None
    assert host_gap.read(ctx(3)) is None


@pytest.mark.parametrize("read", [
    lambda c: program_span.read(c, names=["step_dispatch"]),
    host_gap.read])
def test_none_on_an_empty_ring_and_on_too_few_steps(read):
    assert read(ctx(3)) is None                 # empty ring
    assert read({"counters": {}}) is None       # a driver that counts none
    feed(STEPS)
    assert read(ctx(3)) is not None
    assert read(ctx(4)) is None                 # never a mean over the rest


@pytest.mark.parametrize("read", [
    lambda c: program_span.read(c, names=["step_dispatch"]),
    host_gap.read])
def test_none_from_a_program_that_keeps_no_ring(read, monkeypatch):
    feed(STEPS)
    monkeypatch.delattr(spans, "steps")         # a tree from before the ring
    assert read(ctx(3)) is None


def test_benchmark_json_lists_the_four_metrics_last():
    bench = common.load_json(common.ROOT, "BENCHMARK.json")
    names = ["step_dispatch_ms.train", "feed_ms.train",
             "metric_sync_ms.train", "host_gap_ms.train"]
    assert [m["name"] for m in bench["per_layer"]][-4:] == names
    for entry in bench["per_layer"][-4:]:
        spec = common.load_json(common.named_file("metrics", entry["name"]))
        for key in ("layer", "unit", "better", "moves", "source"):
            assert spec[key] == entry[key]
        assert spec.get("workloads") == entry.get("workloads")
        assert (entry["unit"], entry["better"], entry["moves"]) == \
            ("ms", "lower", "step_ms")
    assert json.dumps(bench)        # plain data
