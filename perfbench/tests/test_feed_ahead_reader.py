"""``readers/feed_ahead.py`` on rings fed by hand, times in nanoseconds.

AHEAD: four ``fit_step`` roots as ``Module.fit`` leaves them since it
copies one batch ahead.  The epoch's first batch is copied at its own
dispatch (``h2d`` 0..40 before ``step_dispatch`` 40..100) and the next
batch's fetch and copy follow the dispatch (``data_wait`` 100..110,
``h2d`` 110..150); roots 10 and 20 hold only the copy issued ahead; root
30's fetch finds the end, so it holds no copy at all.  Roots 1, 10 and 20
of the three counted hold an ``h2d`` that begins after their dispatch
ended: 3 of 3, 100 %.

AT_DISPATCH: the same steps from a program that copies every batch at its
dispatch: every ``h2d`` ends where its ``step_dispatch`` begins: 0 %.

MIXED: root 10's copy was not issued ahead (a batch the stage did not
hold): 1 of 2 counted roots, 50 %.
"""
import pytest

from mxnet_tpu.observability import spans
from perfbench import common
from perfbench.readers import feed_ahead

AHEAD = [
    (2, None, "data_wait", -20, -10),           # the epoch's first fetch
    (3, 1, "h2d", 0, 40), (4, 1, "step_dispatch", 40, 100),
    (5, 1, "data_wait", 100, 110), (6, 1, "h2d", 110, 150),
    (8, 7, "metric_sync", 160, 900), (7, 1, "metric", 150, 950),
    (1, None, "fit_step", 0, 1000),

    (11, 10, "step_dispatch", 1000, 1060),
    (12, 10, "data_wait", 1060, 1070), (13, 10, "h2d", 1070, 1110),
    (15, 14, "metric_sync", 1120, 1900), (14, 10, "metric", 1110, 1950),
    (10, None, "fit_step", 1000, 2000),

    (21, 20, "step_dispatch", 2000, 2060),
    (22, 20, "data_wait", 2060, 2070), (23, 20, "h2d", 2070, 2110),
    (25, 24, "metric_sync", 2120, 2900), (24, 20, "metric", 2110, 2950),
    (20, None, "fit_step", 2000, 3000),

    (31, 30, "step_dispatch", 3000, 3060),
    (32, 30, "data_wait", 3060, 3070),          # found the end: no copy
    (34, 33, "metric_sync", 3080, 3900), (33, 30, "metric", 3070, 3950),
    (30, None, "fit_step", 3000, 4000),
    (40, None, "epoch_end", 4000, 4500),
]
AT_DISPATCH = [
    (2, 1, "data_wait", 0, 10), (3, 1, "h2d", 10, 50),
    (4, 1, "step_dispatch", 50, 400),
    (6, 5, "metric_sync", 410, 900), (5, 1, "metric", 400, 950),
    (1, None, "fit_step", 0, 1000),

    (11, 10, "data_wait", 1000, 1010), (12, 10, "h2d", 1010, 1050),
    (13, 10, "step_dispatch", 1050, 1400),
    (15, 14, "metric_sync", 1410, 1900), (14, 10, "metric", 1400, 1950),
    (10, None, "fit_step", 1000, 2000),

    (21, 20, "data_wait", 2000, 2010), (22, 20, "h2d", 2010, 2050),
    (23, 20, "step_dispatch", 2050, 2400),
    (25, 24, "metric_sync", 2410, 2900), (24, 20, "metric", 2400, 2950),
    (20, None, "fit_step", 2000, 3000),
    # the fetch that ended the epoch: a root with no dispatch, no step
    (31, 30, "data_wait", 3000, 3050), (30, None, "fit_step", 3000, 3060),
]
MIXED = [
    (3, 1, "step_dispatch", 0, 60), (4, 1, "h2d", 70, 110),
    (1, None, "fit_step", 0, 1000),
    (11, 10, "h2d", 1000, 1040), (12, 10, "step_dispatch", 1040, 1100),
    (10, None, "fit_step", 1000, 2000),
    (21, 20, "step_dispatch", 2000, 2060), (20, None, "fit_step", 2000, 3000),
]
BARE = [(1, None, "h2d", 0, 50), (2, None, "step_dispatch", 100, 130),
        (3, None, "step_dispatch", 200, 250)]


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


@pytest.mark.parametrize("rows, steps, by_hand", [
    (AHEAD, 4, 100.0),          # roots 1, 10, 20 of the three counted
    (AHEAD, 3, 100.0),          # the last three roots: 10, 20 of two
    (AT_DISPATCH, 3, 0.0),      # every copy precedes its dispatch
    (MIXED, 3, 50.0),           # root 1 ahead, root 10 at its dispatch
    (BARE, 2, 0.0)])            # a trainer's steps: no copy in any root
def test_share_of_roots_whose_copy_follows_their_dispatch(rows, steps,
                                                          by_hand):
    feed(rows)
    assert feed_ahead.read(ctx(steps)) == pytest.approx(by_hand)


def test_the_windows_last_root_is_not_counted():
    # root 30 holds no copy (its fetch found the end): counted, it would
    # make 3 of 4.  And a window of one step has no root to count
    feed(AHEAD)
    assert feed_ahead.read(ctx(4)) == pytest.approx(100.0)
    assert feed_ahead.read(ctx(1)) is None


def test_a_copy_that_begins_before_the_dispatch_ended_is_not_ahead():
    feed([(2, 1, "step_dispatch", 0, 100), (3, 2, "h2d", 40, 60),
          (1, None, "fit_step", 0, 200),
          (11, 10, "step_dispatch", 200, 300),
          (10, None, "fit_step", 200, 400)])
    assert feed_ahead.read(ctx(2)) == pytest.approx(0.0)


def test_none_without_the_ring_and_on_too_few_steps(monkeypatch):
    assert feed_ahead.read(ctx(3)) is None              # empty ring
    assert feed_ahead.read({"counters": {}}) is None    # counts no steps
    feed(AHEAD)
    assert feed_ahead.read(ctx(5)) is None      # never a share of the rest
    monkeypatch.delattr(spans, "steps")         # a tree from before the ring
    assert feed_ahead.read(ctx(4)) is None


def test_the_metrics_file_and_the_benchmarks_entry_agree():
    spec = common.load_json(common.named_file("metrics",
                                              "feed_ahead_pct.train"))
    assert (spec["reader"], spec.get("args", {})) == ("feed_ahead", {})
    bench = common.load_json(common.ROOT, "BENCHMARK.json")
    (entry,) = [m for m in bench["per_layer"]
                if m["name"] == "feed_ahead_pct.train"]
    for key in ("layer", "unit", "better", "moves", "source", "workloads"):
        assert spec[key] == entry[key]
    assert entry["workloads"] == ["resnet50_fit_b256", "resnet50_fit_dp4"]
    assert (entry["unit"], entry["better"], entry["moves"]) == \
        ("%", "higher", "step_ms")
