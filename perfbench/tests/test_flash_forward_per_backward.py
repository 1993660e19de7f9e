"""The ``flash_forward_per_backward`` reader on hand-made event lists."""
import pytest

from perfbench import common
from perfbench.readers import flash_forward_per_backward as reader


class _Trace(object):
    def __init__(self, events):
        self.events = events

    def first_chip_ops(self):
        return self.events


def _events(forward, backward_names):
    """``forward`` calls of the forward kernel among other events, and one
    backward event for each name in ``backward_names``, numbered as XLA
    numbers the instances of an instruction."""
    out = [("%fusion.7 = f32[8] fusion(...)", 0, 900),
           ("%flash_forward_helper = f32[8] fusion(...)", 10, 5)]
    for i in range(forward):
        name = "flash_forward" + (".%d" % i if i else "")
        out.append(("%%%s = bf16[8] custom-call(...)" % name, 1000 * i, 500))
    for i, name in enumerate(backward_names):
        out.append(("%%%s = bf16[8] custom-call(...)" % name,
                    10 ** 6 + 1000 * i, 700))
    return out


def _numbered(stem, n):
    return [stem + (".%d" % i if i else "") for i in range(n)]


@pytest.mark.parametrize("forward,backward,want", [
    (12, _numbered("flash_backward", 6), 2.0),   # a block runs it again
    (6, _numbered("flash_backward", 6), 1.0),    # its output is kept
    (24, _numbered("flash_backward", 24), 1.0),  # nothing is recomputed
    # a backward of two kernels is one call: 12 events, 6 calls
    (12, _numbered("flash_backward_dq", 6)
     + _numbered("flash_backward_dkv", 6), 2.0),
    (6, _numbered("flash_backward_dq", 6)
     + _numbered("flash_backward_dkv", 6), 1.0),
])
def test_forward_events_over_backward_calls(forward, backward, want):
    ctx = {"trace": _Trace(_events(forward, backward))}
    assert reader.read(ctx) == pytest.approx(want)


@pytest.mark.parametrize("forward,backward", [
    (0, []),                                     # no attention kernel
    (6, []),                                     # a backward that is no kernel
    (0, _numbered("flash_backward", 6)),
])
def test_nothing_to_read(forward, backward):
    assert reader.read({"trace": _Trace(_events(forward, backward))}) is None


def test_metric_file_and_entry_agree():
    bench = common.load_json(common.ROOT, "BENCHMARK.json")
    entry, = [m for m in bench["per_layer"]
              if m["name"] == "flash_forward_per_backward"]
    spec = common.load_json(common.named_file("metrics", entry["name"]))
    assert spec["reader"] == "flash_forward_per_backward"
    for key in ("layer", "unit", "better", "moves", "source", "workloads"):
        assert spec[key] == entry[key], key
    reported = {m["name"]: m.get("workloads") for m in bench["end_to_end"]}
    assert set(entry["workloads"]) <= set(reported[entry["moves"]])
