"""The ``device_scope`` reader on hand-fed events and a hand-made record."""
import pytest

from perfbench import common
from perfbench.readers import device_scope as reader

device_scopes = pytest.importorskip("mxnet_tpu.observability.device_scopes")

NODES = {"ln1": "LayerNorm", "att": "MultiHeadAttention", "bn": "BatchNorm"}
SCOPES = {
    "fusion.1": "jit(step)/jvp(ln1)/mul",
    "fusion.2": "jit(step)/transpose(jvp(ln1))/mul",
    "fusion.3": "jit(step)/transpose(jvp())/checkpoint/"
                "rematted_computation/att/proj_in/dot_general",
    "flash_forward": "jit(step)/jvp(att)/kernel/jit(call)/pallas_call",
    "fusion.4": "jit(step)/update/sub",
    "fusion.5": "jit(step)/jvp(bn)/reduce_sum",
    "copy.1": "jit(step)/jvp()/convert_element_type",
    "while.1": "jit(step)/jvp(att)/while",
    "fusion.6": "jit(step)/jvp(att)/while/body/mul",
}
METRICS = ("device_scoped_pct.train", "recompute_device_pct.train",
           "update_device_pct.train", "batchnorm_device_pct.train",
           "norm_device_pct.train")


class _Trace(object):
    def __init__(self, ops, modules):
        self.ops = {"/device:TPU:0": ops}
        self.modules = {"/device:TPU:0": modules}

    def first_chip_ops(self):
        return self.ops["/device:TPU:0"]


def _op(name, start, dur):
    return ("%%%s = f32[8] fusion(...)" % name, float(start), float(dur))


def _window():
    """Two executions of the step (0-1000, 2000-3000) around another
    program (1000-1400) whose instruction is called ``fusion.1`` too."""
    modules = [("jit_step(123)", 0.0, 1000.0), ("jit_loss(7)", 1000.0, 400.0),
               ("jit_step(123)", 2000.0, 1000.0)]
    ops = []
    for base in (0, 2000):
        ops += [_op("fusion.1", base, 100),             # LayerNorm forward
                _op("flash_forward", base + 100, 100),
                _op("while.1", base + 200, 200),        # encloses fusion.6
                _op("fusion.6", base + 250, 100),
                _op("fusion.5", base + 400, 100),       # BatchNorm forward
                _op("fusion.3", base + 500, 100),       # recompute
                _op("fusion.2", base + 600, 100),       # LayerNorm backward
                _op("fusion.4", base + 700, 100),       # update
                _op("copy.1", base + 800, 100),         # no node
                _op("fusion.99", base + 900, 100)]      # not in the map
    ops.append(_op("fusion.1", 1000, 400))              # the other program
    return ops, modules


@pytest.fixture
def registered(monkeypatch):
    records = device_scopes._RECORDS.__class__(maxlen=16)
    monkeypatch.setattr(device_scopes, "_RECORDS", records)
    record = device_scopes.StepRecord("jit_step", nodes=NODES, scopes=SCOPES)
    records.append(record)
    return record


def _ctx(ops, modules):
    return {"trace": _Trace(ops, modules), "counters": {"steps": 2}}


def _read(ctx, metric):
    spec = common.load_json(common.named_file("metrics", metric))
    assert spec["reader"] == "device_scope"
    return reader.read(ctx, **spec.get("args", {}))


def test_the_five_shares(registered, capsys):
    ctx = _ctx(*_window())
    busy = 2 * 1000.0 + 400.0           # the other program counts as busy
    got = {m: _read(ctx, m) for m in METRICS}
    # scoped: all of a step but the copy and the unmapped fusion
    assert got["device_scoped_pct.train"] == pytest.approx(
        100 * 2 * 800 / busy)
    assert got["recompute_device_pct.train"] == pytest.approx(
        100 * 2 * 100 / busy)
    assert got["update_device_pct.train"] == pytest.approx(
        100 * 2 * 100 / busy)
    assert got["batchnorm_device_pct.train"] == pytest.approx(
        100 * 2 * 100 / busy)
    assert got["norm_device_pct.train"] == pytest.approx(
        100 * 2 * 200 / busy)
    assert sum(got[m] for m in METRICS[1:]) <= got[METRICS[0]] <= 100.0
    out = capsys.readouterr().out.splitlines()
    assert len(out) == 1                # one log line, printed once
    assert out[0].startswith("[perfbench device_scope] jit_step")
    assert "LayerNorm forward 0.00" in out[0]


def test_another_modules_events_are_left_out(registered):
    ops, modules = _window()
    table, busy = reader.reduce_window(_ctx(ops, modules))
    assert busy == 2400.0
    assert table["total_ns"] == 2000.0          # the step's own events
    assert table["by_node"]["ln1"] == 400.0     # not the other fusion.1


def test_a_while_is_not_counted_twice(registered):
    table, _ = reader.reduce_window(_ctx(*_window()))
    # while.1 spans 200 ns, its body's fusion.6 covers 100 of them
    assert table["by_node"]["att"] == 2 * (100 + 200 + 100)
    assert table["by_type_phase"][("MultiHeadAttention", "forward")] == 600.0
    assert table["by_sub"][("MultiHeadAttention", "kernel")] == 200.0
    assert table["unscoped"] == {"copy.1": 200.0, "fusion.99": 200.0}


def test_zero_is_a_share_once_events_joined(registered):
    ops, modules = _window()
    ops = [e for e in ops if "fusion.3" not in e[0]]    # nothing recomputed
    assert _read(_ctx(ops, modules), "recompute_device_pct.train") == 0.0


def test_none_without_a_registered_step(monkeypatch):
    monkeypatch.setattr(device_scopes, "_RECORDS",
                        device_scopes._RECORDS.__class__(maxlen=16))
    assert _read(_ctx(*_window()), "device_scoped_pct.train") is None


def test_none_without_a_match(registered):
    ops, modules = _window()
    # the step's module never ran in the window
    other = [m for m in modules if m[0].startswith("jit_loss")]
    assert _read(_ctx(ops, other), "device_scoped_pct.train") is None
    # it ran, but no event of it is in the map
    strangers = [_op("fusion.77", 10, 50)]
    assert _read(_ctx(strangers, modules), "update_device_pct.train") is None
    assert _read(_ctx([], modules), "update_device_pct.train") is None


def test_none_on_a_tree_from_before_the_module(registered, monkeypatch):
    """Laid over the parent: the import fails, every metric is left out."""
    import builtins
    real = builtins.__import__

    def no_registry(name, globals=None, locals=None, fromlist=(), level=0):
        if name == "mxnet_tpu.observability" and "device_scopes" in (
                fromlist or ()):
            raise ImportError("no device_scopes")
        return real(name, globals, locals, fromlist, level)

    monkeypatch.setattr(builtins, "__import__", no_registry)
    for metric in METRICS:
        assert _read(_ctx(*_window()), metric) is None


def test_none_on_a_summary_without_module_events(registered):
    class Old(object):
        def first_chip_ops(self):
            return _window()[0]

    ctx = {"trace": Old(), "counters": {"steps": 2}}
    assert _read(ctx, "device_scoped_pct.train") is None


@pytest.mark.parametrize("metric", METRICS)
def test_metric_file_and_entry_agree(metric):
    bench = common.load_json(common.ROOT, "BENCHMARK.json")
    entry = next(m for m in bench["per_layer"] if m["name"] == metric)
    spec = common.load_json(common.named_file("metrics", metric))
    for key in ("layer", "unit", "better", "moves", "source", "workloads"):
        assert spec[key] == entry[key], key
    assert entry["unit"] == "%" and entry["source"] == "device_trace"
    cells = {w["name"] for w in bench["workloads"]}
    assert set(entry["workloads"]) <= cells
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
