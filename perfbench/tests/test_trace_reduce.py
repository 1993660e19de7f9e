"""The trace reduction, checked by hand on a small recorded trace.

``small_trace.xplane.pb.gz`` was recorded on a TPU v5e by
``record_small_trace.py``: three launches of one jitted step (a Pallas kernel
named ``flash_forward``, then a matmul+tanh fusion), each after a 20 ms host
span ``host_prep``.  Its ``XLA Ops`` line, read by hand (start ns, duration):

    launch 1  copy-start 66192846 13 | flash_forward.1 66192860 1901 |
              copy-done 66194762 3   | convolution_tanh_fusion 66194766 3121
    launch 2  copy-start 87168270 13 | flash_forward.1 87168285 2050 |
              copy-done 87170336 3   | convolution_tanh_fusion 87170340 3146
    launch 3  copy-start 109144649 13| flash_forward.1 109144664 2112 |
              copy-done 109146778 3  | convolution_tanh_fusion 109146782 3129

No two of them overlap, so the busy union is their sum.
"""
import os

import pytest

from perfbench import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
PLANE = "/device:TPU:0"


@pytest.fixture(scope="module")
def data():
    return tr.load(os.path.join(HERE, "small_trace.xplane.pb.gz"))


def test_device_lines_and_names(data):
    ops = tr.device_lines(data)[PLANE]
    assert len(ops) == 12
    assert [tr.op_name(n) for n, _s, _d in ops[:4]] == [
        "copy-start", "flash_forward.1", "copy-done",
        "convolution_tanh_fusion"]
    modules = tr.device_lines(data, tr.MODULES_LINE)[PLANE]
    assert len(modules) == 3 and modules[0][0].startswith("jit_step(")


def test_busy_union_is_the_hand_sum(data):
    ops = tr.device_lines(data)[PLANE]
    per_launch = [13 + 1901 + 3 + 3121, 13 + 2050 + 3 + 3146,
                  13 + 2112 + 3 + 3129]
    assert tr.busy_union(ops) == sum(per_launch) == 15507


def test_per_name_time_and_kernel_events(data):
    ops = tr.device_lines(data)[PLANE]
    totals = tr.self_times(ops)
    assert totals["flash_forward.1"] == 1901 + 2050 + 2112
    assert totals["convolution_tanh_fusion"] == 3121 + 3146 + 3129
    assert totals["copy-start"] == 39 and totals["copy-done"] == 9
    kernel = tr.kernel_events(ops, "flash_forward")
    assert [d for _n, _s, d in kernel] == [1901, 2050, 2112]
    assert tr.kernel_events(ops, "flash") == []      # a name, not a prefix


def test_gaps_are_charged_to_the_host_span_over_them(data):
    ops = tr.device_lines(data)[PLANE]
    window = (66_000_000.0, 110_000_000.0)
    gaps = tr.idle_gaps(ops, window)
    assert sum(e - s for s, e in gaps) == 44_000_000 - 15507
    # the long gaps are the two between launches and the lead-in
    long = sorted(((e - s), s) for s, e in gaps)[-3:]
    assert long[-1][0] == 109144649 - (87170340 + 3146)
    assert long[-2][0] == 87168270 - (66194766 + 3121)
    spans = tr.host_spans(data, names={"host_prep", "launch"})
    assert [n for n, _s, _d in spans] == ["host_prep", "launch"] * 3
    charged = tr.attribute_gaps(gaps, spans)
    # both gaps between launches lie under a host_prep sleep; so does the
    # lead-in (the first sleep ends at 67.4 ms on the host's clock)
    assert charged["host_prep"] >= long[-1][0] + long[-2][0]
    assert sum(charged.values()) == 44_000_000 - 15507


def test_summary_over_a_window(data):
    window = (66_000_000.0, 110_000_000.0)
    s = tr.TraceSummary(data, window, span_prefix="host_")
    assert s.n_chips == 1
    assert s.busy_s == pytest.approx(15507e-9)
    assert s.window_s == pytest.approx(0.044)
    bd = s.breakdown()
    assert bd["device_ops"][0][0] == "convolution_tanh_fusion"
    assert bd["device_ops"][0][1] == pytest.approx(9396e-9)
    assert bd["idle_gaps"][0][0] == "host_prep"


def test_pure_reductions_on_hand_made_events():
    evs = [("%while.1 = ...", 0.0, 100.0), ("%a = ...", 10.0, 20.0),
           ("%b.2 = ...", 40.0, 10.0), ("%c = ...", 150.0, 50.0)]
    assert tr.busy_union(evs) == 150.0
    assert tr.self_times(evs) == {"while.1": 70.0, "a": 20.0, "b.2": 10.0,
                                  "c": 50.0}
    assert tr.idle_gaps(evs, (0.0, 250.0)) == [(100.0, 150.0), (200.0, 250.0)]
    assert tr.clip(evs, (20.0, 45.0)) == [("%while.1 = ...", 20.0, 25.0),
                                          ("%a = ...", 20.0, 10.0),
                                          ("%b.2 = ...", 40.0, 5.0)]
    charged = tr.attribute_gaps([(100.0, 150.0), (200.0, 250.0)],
                                [("x", 90.0, 30.0), ("y", 115.0, 40.0)])
    assert charged == {"y": 50.0, "(no host span)": 50.0}
    assert tr.top({"p": 2e9, "q": 3e9}, n=1) == [["q", 3.0]]
    assert tr.by_stem({"fusion.1": 1.0, "fusion.22": 2.0, "fusion": 4.0,
                       "copy-done": 3.0}) == {"fusion": 7.0, "copy-done": 3.0}
