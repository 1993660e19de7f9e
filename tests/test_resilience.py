"""Resilience subsystem tests: the fault-injection recovery matrix.

Every recovery path in mxnet_tpu.resilience (docs/resilience.md) is
exercised here on the CPU mesh with deterministically injected faults:
NaN gradients, checkpoint-write crashes, hung steps, dead-node
reports, plus the 2-worker kill-and-resume smoke (the full drill stays
in tests/nightly/dist_resume.py; phases A+B run here too, promoted to
tier-1).
"""
import glob
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import parallel, resilience
from mxnet_tpu.resilience import (CheckpointManager, FaultSpec,
                                  InjectedFault, ResilienceError,
                                  RetryPolicy, Sentinel, Watchdog,
                                  faultinject, latest_classic_epoch,
                                  parse_fault_spec, retry_call,
                                  run_with_timeout)

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_faults(monkeypatch):
    """Each test starts and ends with no armed fault specs."""
    monkeypatch.delenv("MXTPU_FAULT_SPEC", raising=False)
    faultinject.reset()
    yield
    faultinject.reset()


def _arm(monkeypatch, spec):
    monkeypatch.setenv("MXTPU_FAULT_SPEC", spec)
    faultinject.reset()


# ----------------------------------------------------------------------
# fault-spec grammar
# ----------------------------------------------------------------------
def test_parse_fault_spec_grammar():
    specs = parse_fault_spec(
        "step=3:kind=hang:seconds=60;step=9:kind=ckpt_crash")
    assert len(specs) == 2
    assert specs[0].kind == "hang" and specs[0].step == 3 \
        and specs[0].seconds == 60.0 and specs[0].seam == "step"
    assert specs[1].kind == "ckpt_crash" and specs[1].seam == "ckpt_commit"

    (s,) = parse_fault_spec("kind=dead_node:n=2:rank=0")
    assert s.n == 2 and s.rank == 0 and s.seam == "dead_node"

    (s,) = parse_fault_spec("kind=nan:sticky=1")
    assert s.sticky and s.seam == "batch"
    assert parse_fault_spec("") == []


def test_parse_fault_spec_rejects_garbage():
    with pytest.raises(ValueError):
        parse_fault_spec("kind=frobnicate")
    with pytest.raises(ValueError):
        parse_fault_spec("step=3")                  # no kind
    with pytest.raises(ValueError):
        parse_fault_spec("kind=nan:wat=1")          # unknown key
    with pytest.raises(ValueError):
        parse_fault_spec("kind")                    # not key=value


def test_fault_spec_fires_once_unless_sticky():
    once = FaultSpec("nan", step=2)
    assert not once.matches("batch", step=1)
    assert once.matches("batch", step=2)
    once.fired = True
    assert not once.matches("batch", step=2)

    sticky = FaultSpec("nan", sticky=True)
    sticky.fired = True
    assert sticky.matches("batch", step=7)


def test_maybe_fault_env_round_trip(monkeypatch):
    _arm(monkeypatch, "step=2:kind=ckpt_crash:seam=ckpt_write")
    assert resilience.maybe_fault("ckpt_write", step=1) is None
    with pytest.raises(InjectedFault):
        resilience.maybe_fault("ckpt_write", step=2)
    # consumed: does not fire twice
    assert resilience.maybe_fault("ckpt_write", step=2) is None


def test_poison_nan_keeps_int_arrays():
    f = resilience.poison_nan(np.ones(3, np.float32))
    assert np.isnan(f).all()
    i = resilience.poison_nan(np.arange(3))
    assert (i == np.arange(3)).all()


# ----------------------------------------------------------------------
# checkpoint manager: atomic, versioned, pruned
# ----------------------------------------------------------------------
def _tree():
    return {"w": jnp.arange(8, dtype=jnp.float32),
            "b": jnp.zeros((3,), jnp.float32)}


def test_ckptmgr_save_latest_prune_auto_resume(tmp_path):
    from mxnet_tpu.parallel.ckpt import abstract_like
    mgr = CheckpointManager(str(tmp_path / "run"), keep=2)
    assert mgr.latest_step() is None
    assert mgr.auto_resume(abstract_like(_tree())) is None

    for step in (1, 2, 5):
        tree = {"w": jnp.arange(8, dtype=jnp.float32) * step,
                "b": jnp.zeros((3,), jnp.float32)}
        mgr.save(tree, step)
    assert mgr.all_steps() == [2, 5]           # keep-last-2 pruned step 1
    assert mgr.latest_step() == 5

    restored, step = mgr.auto_resume(abstract_like(_tree()))
    assert step == 5
    assert np.allclose(np.asarray(restored["w"]), np.arange(8) * 5)

    with pytest.raises(ValueError):
        mgr.save(_tree(), 5)                   # step already committed


def test_ckptmgr_injected_crash_keeps_prior_checkpoint(tmp_path,
                                                       monkeypatch):
    """Acceptance (b): a crash mid-save leaves latest_step() at the
    prior intact checkpoint; the partial write is swept later."""
    mgr = CheckpointManager(str(tmp_path / "run"), keep=0)
    mgr.save(_tree(), 1)

    # crash between the durable tmp write and the commit rename
    _arm(monkeypatch, "kind=ckpt_crash")
    with pytest.raises(InjectedFault):
        mgr.save({"w": jnp.ones(8), "b": jnp.ones(3)}, 2)
    assert mgr.latest_step() == 1              # tmp garbage is invisible
    leftovers = [n for n in os.listdir(mgr.directory)
                 if n.startswith("tmp.")]
    assert leftovers, "expected the uncommitted tmp write on disk"

    # crash BEFORE the write: nothing new on disk either
    _arm(monkeypatch, "kind=ckpt_crash:seam=ckpt_write")
    with pytest.raises(InjectedFault):
        mgr.save({"w": jnp.ones(8), "b": jnp.ones(3)}, 3)
    assert mgr.latest_step() == 1

    # next incarnation saves fine and sweeps the stale tmp
    monkeypatch.delenv("MXTPU_FAULT_SPEC")
    faultinject.reset()
    mgr.save({"w": jnp.ones(8), "b": jnp.ones(3)}, 4)
    assert mgr.latest_step() == 4
    assert not [n for n in os.listdir(mgr.directory)
                if n.startswith("tmp.")]


def test_ocp_save_overwrite_is_atomic(tmp_path, monkeypatch):
    """The flat (non-versioned) ocp_save must never clobber the
    existing checkpoint before the replacement is durable."""
    from mxnet_tpu.parallel.ckpt import ocp_save, ocp_restore, abstract_like
    path = str(tmp_path / "ck")
    ocp_save(path, _tree(), 7)

    _arm(monkeypatch, "kind=ckpt_crash")       # between write and commit
    with pytest.raises(InjectedFault):
        ocp_save(path, {"w": jnp.ones(8), "b": jnp.ones(3)}, 8)
    tree, step = ocp_restore(path, abstract_like(_tree()))
    assert step == 7                           # old checkpoint intact
    assert np.allclose(np.asarray(tree["w"]), np.arange(8))

    monkeypatch.delenv("MXTPU_FAULT_SPEC")
    faultinject.reset()
    ocp_save(path, {"w": jnp.ones(8), "b": jnp.ones(3)}, 8)
    tree, step = ocp_restore(path, abstract_like(_tree()))
    assert step == 8 and np.allclose(np.asarray(tree["w"]), 1.0)


def test_latest_classic_epoch_and_module_load_latest(tmp_path):
    prefix = str(tmp_path / "cls")
    assert latest_classic_epoch(prefix) is None
    mod, epoch = mx.mod.Module.load_latest(prefix)
    assert mod is None and epoch is None

    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=2,
                              name="fc"), name="softmax")
    args = {"fc_weight": mx.nd.array(np.ones((2, 4), np.float32)),
            "fc_bias": mx.nd.array(np.zeros(2, np.float32))}
    mx.model.save_checkpoint(prefix, 1, net, args, {})
    mx.model.save_checkpoint(prefix, 3, net, args, {})
    assert latest_classic_epoch(prefix) == 3

    mod, epoch = mx.mod.Module.load_latest(prefix)
    assert epoch == 3 and mod is not None
    assert set(mod._arg_params) == {"fc_weight", "fc_bias"}


# ----------------------------------------------------------------------
# watchdog
# ----------------------------------------------------------------------
def test_run_with_timeout_passthrough_and_timeout():
    assert run_with_timeout(lambda: 41 + 1, 5.0, phase="quick") == 42
    assert run_with_timeout(lambda: 7, None, phase="off") == 7
    with pytest.raises(ZeroDivisionError):
        run_with_timeout(lambda: 1 / 0, 5.0, phase="err")

    t0 = time.monotonic()
    with pytest.raises(ResilienceError) as exc:
        run_with_timeout(lambda: time.sleep(30), 0.3, phase="stuck",
                         step=12)
    assert time.monotonic() - t0 < 5.0         # bounded, not 30s
    err = exc.value
    assert err.kind == "timeout" and err.phase == "stuck" \
        and err.step == 12 and err.timeout_s == 0.3
    assert "phase=stuck" in str(err) and "step=12" in str(err)


def test_watchdog_monitor_fires_on_stall():
    fired = []
    wd = Watchdog(timeout_s=0.3, phase="loop", on_timeout=fired.append,
                  poll_s=0.05)
    with wd:
        wd.feed(step=1)
        time.sleep(0.1)
        wd.feed(step=2)                        # progress: no fire
        assert not wd.fired
        time.sleep(0.8)                        # stall
    assert wd.fired and len(fired) == 1
    err = fired[0]
    assert err.kind == "stall" and err.step == 2 and err.phase == "loop"


def test_watchdog_disabled_without_timeout():
    wd = Watchdog(timeout_s=None, on_timeout=lambda e: None)
    with wd:
        assert wd._thread is None              # unarmed: no monitor


def test_exit_for_restart_subprocess_exits_3():
    """Acceptance (c), exit-code half: the watchdog abort path must
    produce exit code 3 (docs/resilience.md contract)."""
    code = (
        "import time\n"
        "from mxnet_tpu.resilience import run_with_timeout\n"
        "run_with_timeout(lambda: time.sleep(60), 0.2, phase='step',\n"
        "                 step=4, on_timeout='exit')\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=_ROOT + os.pathsep + os.environ.get(
                   "PYTHONPATH", ""))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          timeout=300, capture_output=True, text=True)
    assert proc.returncode == resilience.EXIT_RESTART, proc.stderr[-2000:]
    assert "RESILIENCE ABORT" in proc.stderr
    assert "phase=step" in proc.stderr and "step=4" in proc.stderr


# ----------------------------------------------------------------------
# retry
# ----------------------------------------------------------------------
def test_retry_call_transient_then_success():
    calls, naps = [], []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise RuntimeError("connection refused (transient)")
        return "up"

    policy = RetryPolicy(max_tries=4, base_delay_s=0.5)
    assert retry_call(flaky, policy, sleep=naps.append) == "up"
    assert len(calls) == 3
    assert naps == [0.5, 1.0]                  # exponential, deterministic


def test_retry_call_nonretryable_propagates_immediately():
    calls = []

    def broken():
        calls.append(1)
        raise ValueError("num_processes mismatch")  # deterministic config bug

    with pytest.raises(ValueError):
        retry_call(broken, RetryPolicy(max_tries=5), sleep=lambda s: None)
    assert len(calls) == 1                     # no retry for non-transient


def test_retry_call_exhausts_and_raises_last():
    calls = []

    def always_down():
        calls.append(1)
        raise RuntimeError("deadline exceeded")

    with pytest.raises(RuntimeError):
        retry_call(always_down, RetryPolicy(max_tries=3),
                   sleep=lambda s: None)
    assert len(calls) == 3


# ----------------------------------------------------------------------
# host-side sentinel
# ----------------------------------------------------------------------
def test_sentinel_skips_nonfinite_and_backs_off():
    s = Sentinel()
    scale0 = s.loss_scale.scale
    assert s.check(1, loss=0.9) == "ok"
    assert s.check(2, loss=float("nan")) == "skip-nonfinite"
    assert s.loss_scale.scale == scale0 / 2
    assert s.check(3, grad_norm=float("inf")) == "skip-nonfinite"
    assert s.check(4, loss=0.8) == "ok"
    assert s.last_good_step == 4
    assert [rec[0] for rec in s.skipped] == [2, 3]


def test_sentinel_spike_detection():
    s = Sentinel(spike_factor=100.0, warmup_steps=3)
    for step in range(1, 6):
        assert s.check(step, loss=1.0) == "ok"
    assert s.check(6, loss=1e6) == "skip-spike"
    assert s.check(7, loss=1.1) == "ok"


def test_sentinel_escalates_after_max_consecutive_skips():
    s = Sentinel(max_consecutive_skips=3)
    s.check(1, loss=1.0)
    s.check(2, loss=float("nan"))
    s.check(3, loss=float("nan"))
    with pytest.raises(ResilienceError) as exc:
        s.check(4, loss=float("nan"))
    assert exc.value.kind == "numeric"


def test_dynamic_loss_scale_growth_and_clamp():
    from mxnet_tpu.resilience.sentinel import DynamicLossScale
    ls = DynamicLossScale(init=4.0, growth_interval=2, min_scale=1.0,
                          max_scale=8.0)
    ls.good(); ls.good()
    assert ls.scale == 8.0
    ls.good(); ls.good()
    assert ls.scale == 8.0                     # clamped at max
    for _ in range(5):
        ls.bad()
    assert ls.scale == 1.0                     # clamped at min


def test_sentinel_grad_norm_module_structure():
    g = [[mx.nd.array(np.array([3.0, 4.0], np.float32))],
         [None]]
    assert abs(Sentinel.grad_norm(g) - 5.0) < 1e-6
    g_bad = [[mx.nd.array(np.array([np.nan], np.float32))]]
    assert np.isnan(Sentinel.grad_norm(g_bad))


# ----------------------------------------------------------------------
# fused trainer: compiled sentinel gate + injected faults
# ----------------------------------------------------------------------
def _mlp(bn=False):
    data = mx.sym.Variable("data")
    fc1 = mx.sym.FullyConnected(data, num_hidden=8, name="fc1")
    if bn:      # auxiliary states for the sentinel's gate to keep
        fc1 = mx.sym.BatchNorm(fc1, name="bn")
    act = mx.sym.Activation(fc1, act_type="relu")
    fc2 = mx.sym.FullyConnected(act, num_hidden=4, name="fc2")
    return mx.sym.SoftmaxOutput(fc2, name="softmax")


def _trainer(sentinel=False, step_timeout_s=None, lr=0.5, bn=False):
    mesh = parallel.make_mesh(jax.devices()[:2], dp=2)
    opt = mx.optimizer.create("sgd", learning_rate=lr, momentum=0.9,
                              rescale_grad=1.0 / 16)
    tr = parallel.ShardedTrainer(_mlp(bn), opt, mesh, sentinel=sentinel,
                                 step_timeout_s=step_timeout_s)
    mx.random.seed(3)
    params, opt_state, aux = tr.init_params(
        {"data": (16, 8)}, label_shapes={"softmax_label": (16,)})
    rng = np.random.RandomState(0)
    x = rng.randn(16, 8).astype(np.float32)
    y = (x.sum(axis=1) > 0).astype(np.float32)
    batch = tr.shard_batch({"data": x, "softmax_label": y})
    return tr, params, opt_state, aux, batch


def _host(params):
    return {k: np.asarray(jax.device_get(v)) for k, v in params.items()}


@pytest.mark.parametrize("fused", ["", "1"])
def test_trainer_sentinel_skips_injected_nan_step(monkeypatch, fused):
    """Acceptance (a): NaN injected at step k -> that step is skipped
    (params, optimizer state and auxiliary states bit-identical), loss
    scale halves, training continues — the one gate behind the per-leaf
    update and behind ``MXTPU_FUSED_OPT=1``'s sweep."""
    monkeypatch.setenv("MXTPU_FUSED_OPT", fused)
    tr, params, opt_state, aux, batch = _trainer(sentinel=True, bn=True)
    assert tr._fused_opt == fused
    _arm(monkeypatch, "step=3:kind=nan")

    def host(*trees):
        return [np.asarray(a) for a in jax.tree_util.tree_leaves(trees)]

    scale0 = None
    for step in range(1, 6):
        before = host(params, opt_state, aux)
        n_params = len(params)
        params, opt_state, aux, outs = tr.step(params, opt_state, aux,
                                               batch)
        after = host(params, opt_state, aux)
        stats = tr.sentinel_stats()
        if step == 1:
            scale0 = stats["scale"]
        if step == 3:
            assert len(before) == len(after) == 2 * n_params + len(aux)
            for b, a in zip(before, after):
                assert np.array_equal(b, a), \
                    "poisoned step %d must move nothing" % step
            assert not np.isfinite(np.asarray(outs[0])).all()
            assert stats["skipped"] == 1
            assert stats["scale"] == scale0 / 2
        else:
            # (a bias ahead of BatchNorm has no gradient: not every leaf)
            moved = [not np.array_equal(b, a)
                     for b, a in zip(before, after)]
            assert any(moved[:n_params]) and any(moved[-len(aux):]) \
                and any(moved[n_params:-len(aux)]), \
                "clean step %d should update params, state, aux" % step
            assert np.isfinite(np.asarray(outs[0])).all()
    stats = tr.sentinel_stats()
    assert stats["skipped"] == 1 and stats["last_good"] == 5


def test_trainer_sentinel_off_matches_plain_step():
    """The sentinel-off trainer is byte-identical to the pre-resilience
    step (no scaled cotangents, no gating)."""
    tr_a, pa, oa, aa, batch = _trainer(sentinel=False)
    tr_b, pb, ob, ab, _ = _trainer(sentinel=True)
    for _ in range(3):
        pa, oa, aa, _ = tr_a.step(pa, oa, aa, batch)
        pb, ob, ab, _ = tr_b.step(pb, ob, ab, batch)
    ha, hb = _host(pa), _host(pb)
    for name in ha:
        assert np.allclose(ha[name], hb[name], rtol=1e-5, atol=1e-6), name


def test_trainer_sentinel_learns():
    tr, params, opt_state, aux, batch = _trainer(sentinel=True)
    y = None
    for _ in range(30):
        params, opt_state, aux, outs = tr.step(params, opt_state, aux,
                                               batch)
    stats = tr.sentinel_stats()
    assert stats["skipped"] == 0
    pred = np.asarray(outs[0]).argmax(axis=1)
    x = np.asarray(jax.device_get(batch["data"]))
    labels = (x.sum(axis=1) > 0).astype(np.int64)
    assert (pred == labels).mean() > 0.9


def test_trainer_watchdog_catches_injected_hang(monkeypatch):
    """Acceptance (c): an injected hang inside the step converts into a
    structured ResilienceError within the timeout."""
    tr, params, opt_state, aux, batch = _trainer(step_timeout_s=1.0)
    # step 1 compiles + runs clean; step 2 hangs
    params, opt_state, aux, _ = tr.step(params, opt_state, aux, batch)
    _arm(monkeypatch, "step=2:kind=hang:seconds=20")
    t0 = time.monotonic()
    with pytest.raises(ResilienceError) as exc:
        tr.step(params, opt_state, aux, batch)
    assert time.monotonic() - t0 < 10.0
    err = exc.value
    assert err.kind == "timeout" and err.phase == "train_step" \
        and err.step == 2 and err.rank == 0


def test_trainer_slow_step_under_timeout_succeeds(monkeypatch):
    tr, params, opt_state, aux, batch = _trainer(step_timeout_s=30.0)
    params, opt_state, aux, _ = tr.step(params, opt_state, aux, batch)
    _arm(monkeypatch, "step=2:kind=slow:seconds=0.2")
    params, opt_state, aux, _ = tr.step(params, opt_state, aux, batch)
    assert tr.num_update == 2                  # slow but not stuck


def test_trainer_versioned_checkpoint_auto_resume(tmp_path):
    ckdir = str(tmp_path / "ckpts")
    tr, params, opt_state, aux, batch = _trainer()
    for _ in range(2):
        params, opt_state, aux, _ = tr.step(params, opt_state, aux, batch)
    tr.save_checkpoint_versioned(ckdir, params, opt_state, aux, keep=3)
    params, opt_state, aux, _ = tr.step(params, opt_state, aux, batch)
    tr.save_checkpoint_versioned(ckdir, params, opt_state, aux, keep=3)
    assert tr.latest_step(ckdir) == 3
    want = _host(params)

    tr2, _, _, _, _ = _trainer()
    resumed = tr2.auto_resume(ckdir, {"data": (16, 8)},
                              label_shapes={"softmax_label": (16,)})
    assert resumed is not None
    p2, o2, a2, step = resumed
    assert step == 3 and tr2.num_update == 3
    got = _host(p2)
    for name in want:
        assert np.allclose(want[name], got[name]), name

    # fresh directory -> None (the "first boot" branch)
    tr3, _, _, _, _ = _trainer()
    assert tr3.auto_resume(str(tmp_path / "fresh"), {"data": (16, 8)},
                           label_shapes={"softmax_label": (16,)}) is None


# ----------------------------------------------------------------------
# host training loops: sentinel + poisoned grads
# ----------------------------------------------------------------------
def test_feedforward_sentinel_survives_injected_nan(monkeypatch,
                                                    tmp_path):
    """The classic fit loop keeps training through an injected NaN
    batch when MXTPU_SENTINEL=1 (grad-norm gate skips the update)."""
    rng = np.random.RandomState(0)
    X = rng.randn(60, 6).astype(np.float32)
    y = (X.sum(axis=1) > 0).astype(np.float32)
    net = mx.models.get_mlp(num_classes=2, hidden=(8,))

    monkeypatch.setenv("MXTPU_SENTINEL", "1")
    _arm(monkeypatch, "step=2:kind=nan")
    model = mx.FeedForward(net, ctx=mx.context.cpu(), num_epoch=8,
                           optimizer="sgd", learning_rate=0.3,
                           initializer=mx.init.Uniform(0.1))
    model.fit(mx.io.NDArrayIter(X, y, batch_size=20))
    # params survived the poisoned step: finite and usable
    for name, arr in model.arg_params.items():
        assert np.isfinite(arr.asnumpy()).all(), name
    acc = model.score(mx.io.NDArrayIter(X, y, batch_size=20))
    assert acc > 0.65


def test_feedforward_without_sentinel_is_poisoned(monkeypatch):
    """Control for the test above: the same injected NaN without the
    sentinel propagates into the parameters — the failure the sentinel
    exists to stop."""
    rng = np.random.RandomState(0)
    X = rng.randn(60, 6).astype(np.float32)
    y = (X.sum(axis=1) > 0).astype(np.float32)
    net = mx.models.get_mlp(num_classes=2, hidden=(8,))
    _arm(monkeypatch, "step=2:kind=nan")
    model = mx.FeedForward(net, ctx=mx.context.cpu(), num_epoch=1,
                           optimizer="sgd", learning_rate=0.3,
                           initializer=mx.init.Uniform(0.1))
    model.fit(mx.io.NDArrayIter(X, y, batch_size=20))
    assert any(not np.isfinite(a.asnumpy()).all()
               for a in model.arg_params.values())


def test_feedforward_fit_checkpoint_and_auto_resume(tmp_path):
    rng = np.random.RandomState(0)
    X = rng.randn(40, 6).astype(np.float32)
    y = (X.sum(axis=1) > 0).astype(np.float32)
    net = mx.models.get_mlp(num_classes=2, hidden=(8,))
    prefix = str(tmp_path / "ff")

    model = mx.FeedForward(net, ctx=mx.context.cpu(), num_epoch=2,
                           optimizer="sgd", learning_rate=0.1,
                           initializer=mx.init.Uniform(0.1))
    model.fit(mx.io.NDArrayIter(X, y, batch_size=20),
              checkpoint_prefix=prefix)
    assert latest_classic_epoch(prefix) == 2   # do_checkpoint auto-wired

    resumed = mx.FeedForward(net, ctx=mx.context.cpu(), num_epoch=3,
                             optimizer="sgd", learning_rate=0.1,
                             initializer=mx.init.Uniform(0.1))
    resumed.fit(mx.io.NDArrayIter(X, y, batch_size=20),
                checkpoint_prefix=prefix, resume="auto")
    assert resumed.begin_epoch == 2            # picked up where A stopped
    assert latest_classic_epoch(prefix) == 3

    with pytest.raises(mx.base.MXNetError):
        mx.FeedForward(net, ctx=mx.context.cpu(), num_epoch=1).fit(
            mx.io.NDArrayIter(X, y, batch_size=20), resume="auto")


# ----------------------------------------------------------------------
# kvstore fault surface
# ----------------------------------------------------------------------
class _FakeClient(object):
    def __init__(self):
        self.kv = {}

    def key_value_set(self, key, value, allow_overwrite=False):
        self.kv[key] = value

    def key_value_dir_get(self, prefix):
        return [(k, v) for k, v in self.kv.items()
                if k.startswith(prefix)]


def test_num_dead_nodes_timeout_and_expiry(monkeypatch):
    from mxnet_tpu import kvstore as kvs
    clock = {"now": 1000.0}
    fake = _FakeClient()
    monkeypatch.setattr(kvs, "_now", lambda: clock["now"])
    monkeypatch.setattr(kvs, "_dist_client", lambda: fake)

    kv = kvs.KVStore("dist_sync")              # _created = 1000.0
    fake.kv["mxtpu_hb/0"] = repr(1000.0)
    assert kv.num_dead_nodes(node_id=0, timeout=10.0) == 0
    clock["now"] = 1011.0                      # stamp is now stale
    assert kv.num_dead_nodes(node_id=0, timeout=10.0) == 1
    fake.kv["mxtpu_hb/0"] = repr(1010.5)       # peer beat again: alive
    assert kv.num_dead_nodes(node_id=0, timeout=10.0) == 0

    # missing stamp: grace until `timeout` after store creation
    assert kv.num_dead_nodes(node_id=1, timeout=20.0) == 0
    clock["now"] = 1030.0
    assert kv.num_dead_nodes(node_id=1, timeout=20.0) == 1
    # non-dist stores never report deaths
    assert kvs.KVStore("local").num_dead_nodes() == 0


def test_num_dead_nodes_injected_dead_node(monkeypatch):
    from mxnet_tpu import kvstore as kvs
    _arm(monkeypatch, "kind=dead_node:n=2")
    kv = kvs.KVStore("dist_sync")
    assert kv.num_dead_nodes() == 2
    assert kv.num_dead_nodes() == 0            # spec consumed


def test_heartbeat_idempotent_and_stoppable(monkeypatch):
    from mxnet_tpu import kvstore as kvs
    fake = _FakeClient()
    monkeypatch.setattr(kvs, "_dist_client", lambda: fake)
    try:
        kvs._start_heartbeat()
        t = kvs._HB_STATE["thread"]
        assert t is not None and t.is_alive()
        kvs._start_heartbeat()                 # idempotent: same thread
        assert kvs._HB_STATE["thread"] is t
        assert t.daemon, "heartbeat must never block interpreter exit"
        deadline = time.time() + 5
        while not fake.kv and time.time() < deadline:
            time.sleep(0.01)
        assert any(k.startswith("mxtpu_hb/") for k in fake.kv)
    finally:
        kvs._stop_heartbeat()
    assert not t.is_alive()
    assert kvs._HB_STATE["thread"] is None
    # restartable after a stop (fresh store in the same process)
    kvs._start_heartbeat()
    assert kvs._HB_STATE["thread"].is_alive()
    kvs._stop_heartbeat()


def test_kvstore_barrier_watchdog_single_process(monkeypatch):
    """With one process the barrier is a no-op even when armed."""
    monkeypatch.setenv("MXTPU_STEP_TIMEOUT_S", "1.0")
    kv = mx.kvstore.KVStore("dist_sync")
    kv.barrier()                               # must not raise or hang


# ----------------------------------------------------------------------
# monitor nonfinite alarm
# ----------------------------------------------------------------------
def test_monitor_alarm_nonfinite():
    mon = mx.monitor.Monitor(interval=1, alarm_nonfinite=True)
    mon.activated = True
    mon._record("clean", mx.nd.array(np.ones(4, np.float32)))
    assert mon.nonfinite_records == []
    mon._record("poisoned",
                mx.nd.array(np.array([1.0, np.inf], np.float32)))
    assert len(mon.nonfinite_records) == 1
    step, name, _stat = mon.nonfinite_records[0]
    assert name == "poisoned"


# ----------------------------------------------------------------------
# 2-worker kill-and-resume smoke (tier-1 promotion of the nightly
# drill: phases A+B of tests/nightly/dist_resume.py)
# ----------------------------------------------------------------------
def _launch(script, n=2, port=9899, extra_env=None, expect_rc=0):
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


def test_kill_and_resume_smoke(tmp_path):
    """Acceptance (d): kill one worker; the survivor detects it and
    exits with the restart signal (launcher propagates 3); the
    restarted job resumes from the checkpoint, replays the identical
    batch order, and the loss keeps improving."""
    prefix = str(tmp_path / "resume")
    out = _launch("dist_resume.py", port=9899,
                  extra_env={"MXTPU_FAULT_RANK": "1",
                             "MXTPU_RESUME_PREFIX": prefix},
                  expect_rc=3)
    assert "detected 1 dead node" in out, out[-1500:]
    assert os.path.exists(prefix + "-0001.params")
    out = _launch("dist_resume.py", port=9900,
                  extra_env={"MXTPU_RESUME": "1",
                             "MXTPU_RESUME_PREFIX": prefix})
    assert out.count("resume OK") == 2, out[-1500:]


# ----------------------------------------------------------------------
# elastic re-mesh: liveness identities, ledger/fence, decision protocol
# ----------------------------------------------------------------------
from mxnet_tpu.resilience import elastic  # noqa: E402


def test_dead_nodes_returns_sorted_identities(monkeypatch):
    from mxnet_tpu import kvstore as kvs
    clock = {"now": 1000.0}
    fake = _FakeClient()
    monkeypatch.setattr(kvs, "_now", lambda: clock["now"])
    monkeypatch.setattr(kvs, "_dist_client", lambda: fake)
    monkeypatch.setattr(kvs.jax, "process_count", lambda: 3)
    kv = kvs.KVStore("dist_sync")
    fake.kv["mxtpu_hb/0"] = repr(1000.0)
    fake.kv["mxtpu_hb/1"] = repr(1000.0)
    fake.kv["mxtpu_hb/2"] = repr(1000.0)
    assert kv.dead_nodes(timeout=10.0) == []
    clock["now"] = 1011.0
    fake.kv["mxtpu_hb/1"] = repr(1010.0)       # only 1 kept beating
    assert kv.dead_nodes(timeout=10.0) == [0, 2]
    assert kv.dead_nodes(node_id=2, timeout=10.0) == [2]
    assert kv.dead_nodes(node_id=1, timeout=10.0) == []
    assert kv.num_dead_nodes(timeout=10.0) == 2
    assert kvs.KVStore("local").dead_nodes() == []


def test_elastic_ledger_round_trip_and_knobs(tmp_path, monkeypatch):
    monkeypatch.setenv("MXTPU_ELASTIC_DIR", str(tmp_path))
    assert elastic.read_ledger() is None       # fresh: unreadable = None
    verdict = {"generation": 3, "world_size": 2, "members": [0, 1],
               "reason": "dead_node", "from_world": 3}
    elastic.write_ledger(verdict)
    assert elastic.read_ledger() == verdict
    assert not os.path.exists(elastic.ledger_path() + ".tmp")
    # generation(): env stamp wins, ledger is the fallback
    monkeypatch.delenv("MXTPU_ELASTIC_GENERATION", raising=False)
    assert elastic.generation() == 3
    monkeypatch.setenv("MXTPU_ELASTIC_GENERATION", "5")
    assert elastic.generation() == 5
    # capacity file: absent -> default, garbage -> default
    assert elastic.capacity() is None
    with open(elastic.capacity_path(), "w") as f:
        f.write("2\n")
    assert elastic.capacity() == 2
    monkeypatch.setenv("MXTPU_ELASTIC_MIN_WORLD", "2")
    assert elastic.min_world() == 2
    monkeypatch.setenv("MXTPU_ELASTIC_TARGET_WORLD", "4")
    assert elastic.target_world() == 4


def test_generation_fence_stale_raises(tmp_path, monkeypatch):
    monkeypatch.setenv("MXTPU_ELASTIC_DIR", str(tmp_path))
    monkeypatch.setenv("MXTPU_ELASTIC_GENERATION", "0")
    elastic.write_ledger({"generation": 1, "world_size": 2})
    # not elastic -> never fences (plain jobs must be unaffected)
    monkeypatch.delenv("MXTPU_ELASTIC", raising=False)
    elastic.check_generation_fence()
    monkeypatch.setenv("MXTPU_ELASTIC", "1")
    with pytest.raises(ResilienceError) as ei:
        elastic.check_generation_fence()
    assert ei.value.kind == "stale_generation"
    # at or past the agreed generation: clean
    monkeypatch.setenv("MXTPU_ELASTIC_GENERATION", "1")
    elastic.check_generation_fence()


class _FakeElasticKV(object):
    def __init__(self, rank, num_workers, dead=()):
        self.rank = rank
        self.num_workers = num_workers
        self._dead = sorted(dead)

    def dead_nodes(self, node_id=None, timeout=None):
        return list(self._dead)


class _FakePollClient(object):
    def __init__(self):
        self.kv = {}

    def key_value_set(self, key, value, allow_overwrite=False):
        self.kv[key] = value

    def blocking_key_value_get(self, key, timeout_ms):
        if key in self.kv:
            return self.kv[key]
        raise RuntimeError("DEADLINE_EXCEEDED waiting for %s" % key)

    def key_value_delete(self, key):
        self.kv.pop(key, None)


@pytest.fixture
def _elastic_env(tmp_path, monkeypatch):
    monkeypatch.setenv("MXTPU_ELASTIC", "1")
    monkeypatch.setenv("MXTPU_ELASTIC_DIR", str(tmp_path))
    monkeypatch.setenv("MXTPU_ELASTIC_GENERATION", "0")
    monkeypatch.setenv("MXTPU_ELASTIC_TARGET_WORLD", "3")
    client = _FakePollClient()
    monkeypatch.setattr(elastic, "_kv_client", lambda: client)
    return client


def test_poll_remesh_shrink_verdict(_elastic_env, monkeypatch):
    client = _elastic_env
    kv = _FakeElasticKV(0, 3, dead=[2])
    verdict = elastic.poll_remesh(kv, elastic.recover_round(2),
                                  dead_timeout=6.0)
    assert verdict["generation"] == 1
    assert verdict["world_size"] == 2
    assert verdict["members"] == [0, 1]
    assert verdict["reason"] == "dead_node"
    assert verdict["from_world"] == 3
    # ledger persisted BEFORE publication; key carries generation+round
    assert elastic.read_ledger() == verdict
    key = "mxtpu_elastic/poll/0/recover-2"
    assert json.loads(client.kv[key]) == verdict
    # a survivor adopting the same round reads the identical verdict
    kv1 = _FakeElasticKV(1, 3)
    assert elastic.poll_remesh(kv1, elastic.recover_round(2),
                               timeout_s=1.0) == verdict


def test_poll_remesh_grow_toward_capacity_capped_at_target(
        _elastic_env, tmp_path):
    with open(elastic.capacity_path(), "w") as f:
        f.write("5")                           # more than we ever want
    kv = _FakeElasticKV(0, 2)
    verdict = elastic.poll_remesh(kv, 7)
    assert verdict["reason"] == "grow"
    assert verdict["world_size"] == 3          # capped at target, not 5
    assert verdict["members"] == [0, 1, 2]


def test_poll_remesh_no_verdict_publishes_marker(_elastic_env):
    client = _elastic_env
    kv = _FakeElasticKV(0, 3)
    assert elastic.poll_remesh(kv, 4) is None
    assert client.kv["mxtpu_elastic/poll/0/4"] == "none"
    # the no-op marker is what non-coordinators read: no race, no guess
    assert elastic.poll_remesh(_FakeElasticKV(1, 3), 4,
                               timeout_s=1.0) is None


def test_poll_remesh_orphan_raises_for_restart(_elastic_env):
    kv = _FakeElasticKV(1, 3)                  # coordinator never writes
    with pytest.raises(ResilienceError) as ei:
        elastic.poll_remesh(kv, 9, timeout_s=0.1)
    assert ei.value.kind == "remesh_orphan"


def test_restore_mismatch_names_every_leaf_host_format(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=0, payload_format="host")
    tree = {"w": np.ones((4, 4), np.float32),
            "b": np.zeros((4,), np.float32)}
    mgr.save(tree, 1)
    got, step = mgr.restore({"w": np.zeros((4, 4), np.float32),
                             "b": np.zeros((4,), np.float32)})
    assert step == 1
    assert np.array_equal(got["w"], tree["w"])
    with pytest.raises(ResilienceError) as ei:
        mgr.restore({"w": np.zeros((2, 4), np.float32),
                     "b": np.zeros((4,), np.float64)})
    err = ei.value
    assert err.kind == "restore_mismatch"
    msg = str(err)
    assert "w" in msg and "(2, 4)" in msg      # the mismatched leaf,
    assert "b" in msg and "float64" in msg     # named with its want/got


def test_restore_mismatch_orbax_format(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=0)
    mgr.save({"w": np.arange(8, dtype=np.float32)}, 2)
    with pytest.raises(ResilienceError) as ei:
        mgr.restore({"w": np.zeros((3,), np.float32)})
    assert ei.value.kind == "restore_mismatch"
    assert "w" in str(ei.value)
    # structure mismatch (absent leaf) is named too, not an opaque diff
    with pytest.raises(ResilienceError) as ei:
        mgr.restore({"w": np.zeros((8,), np.float32),
                     "extra": np.zeros((1,), np.float32)})
    assert "extra" in str(ei.value)


def test_checkpoint_world_size_round_trip(tmp_path):
    """Satellite: save under dp=2, restore under dp=1, re-save, restore
    under dp=2 — orbax reshards on restore and every leaf survives
    bit-identical through both world-size changes."""
    d1, d2 = str(tmp_path / "a"), str(tmp_path / "b")
    tr, params, opt_state, aux, batch = _trainer()
    for _ in range(2):
        params, opt_state, aux, _ = tr.step(params, opt_state, aux, batch)
    tr.save_checkpoint_versioned(d1, params, opt_state, aux, keep=0)
    want = _host(params)

    mesh1 = parallel.make_mesh(jax.devices()[:1], dp=1)
    opt = mx.optimizer.create("sgd", learning_rate=0.5, momentum=0.9,
                              rescale_grad=1.0 / 16)
    tr1 = parallel.ShardedTrainer(_mlp(), opt, mesh1)
    resumed = tr1.auto_resume(d1, {"data": (16, 8)},
                              label_shapes={"softmax_label": (16,)})
    assert resumed is not None
    p1, o1, a1, step = resumed
    assert step == 2
    mid = _host(p1)
    for name in want:
        assert np.array_equal(want[name], mid[name]), name
    tr1.save_checkpoint_versioned(d2, p1, o1, a1, keep=0)

    tr2, _, _, _, _ = _trainer()               # back to dp=2
    p2, o2, a2, step2 = tr2.auto_resume(
        d2, {"data": (16, 8)}, label_shapes={"softmax_label": (16,)})
    assert step2 == 2
    got = _host(p2)
    for name in want:
        assert np.array_equal(want[name], got[name]), name
    # and the re-grown trainer still steps under the restored layout
    tr2.step(p2, o2, a2, batch)


def test_ndarrayiter_partition_tiles_dataset():
    X = np.arange(100, dtype=np.float32).reshape(100, 1)
    for shuffle in (False, True):
        for nw in (1, 2, 3, 5):
            for epoch in (0, 1, 4):
                parts = []
                for r in range(nw):
                    it = mx.io.NDArrayIter(X, batch_size=10,
                                           shuffle=shuffle, seed=11,
                                           num_parts=nw, part_index=r)
                    it.set_state({"epoch": epoch, "cursor": -10})
                    parts.append([int(i) for i in it.idx])
                flat = sorted(i for p in parts for i in p)
                assert flat == list(range(100)), (shuffle, nw, epoch)
    # stride partition of the SAME global permutation: a world-size
    # change reassigns samples but never changes the epoch's order
    a = mx.io.NDArrayIter(X, batch_size=10, shuffle=True, seed=11,
                          num_parts=2, part_index=0).idx
    b = mx.io.NDArrayIter(X, batch_size=10, shuffle=True, seed=11,
                          num_parts=2, part_index=1).idx
    full = mx.io.NDArrayIter(X, batch_size=10, shuffle=True, seed=11).idx
    order = np.empty(100, dtype=full.dtype)
    order[0::2], order[1::2] = a, b
    assert np.array_equal(order, full)


def test_ndarrayiter_partition_validation():
    X = np.zeros((20, 1), np.float32)
    with pytest.raises(mx.base.MXNetError):
        mx.io.NDArrayIter(X, batch_size=5, num_parts=2, part_index=2)
    with pytest.raises(mx.base.MXNetError):
        mx.io.NDArrayIter(X, batch_size=5, num_parts=0)
    with pytest.raises(mx.base.MXNetError):
        mx.io.NDArrayIter(X, batch_size=5, shuffle=True,
                          num_parts=2, part_index=0)   # needs seed


def test_remesh_axis_math():
    lm = parallel.LogicalMesh(dp=4, tp=2)
    assert dict(parallel.remesh(lm, total=6).shape) == {"dp": 3, "tp": 2}
    with pytest.raises(ValueError):
        parallel.remesh(lm, total=5)           # tp=2 doesn't divide 5
    with pytest.raises(ValueError):
        parallel.remesh(parallel.LogicalMesh(tp=2), total=4)  # no dp
    with pytest.raises(ValueError):
        parallel.remesh(lm)                    # LogicalMesh needs total=
    m = parallel.make_mesh(jax.devices()[:4], dp=2, tp=2)
    m2 = parallel.remesh(m, devices=jax.devices()[:6])
    assert dict(m2.shape) == {"dp": 3, "tp": 2}
    assert m2.devices is not None              # a live mesh, bindable


# ----------------------------------------------------------------------
# 3-worker shrink/grow drill (tier-1 promotion of
# tests/nightly/dist_elastic.py under the elastic supervise loop)
# ----------------------------------------------------------------------
def _launch_raw(cmd_args, extra_env=None, expect_rc=0, timeout=420):
    cmd = [sys.executable, os.path.join(_ROOT, "tools", "launch.py")] \
        + cmd_args
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    env.update(extra_env or {})
    proc = subprocess.run(cmd, cwd=_ROOT, env=env, timeout=timeout,
                          stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                          text=True)
    assert proc.returncode == expect_rc, (proc.returncode,
                                          proc.stdout[-3000:])
    return proc.stdout


@pytest.mark.slow
def test_elastic_shrink_grow_drill(tmp_path):
    """The ISSUE 7 acceptance drill: 3 workers, one dies mid-training ->
    survivors agree one generation-stamped shrink verdict, re-mesh to
    world 2 and resume from the latest checkpoint; capacity returns ->
    grow verdict back to world 3; every transition leaves the agreed
    generation in the ledger and propose/adopt/resume telemetry with
    matching generations on all ranks; post-transition loss
    trajectories are bit-identical to fresh fixed-world runs from the
    same checkpoints."""
    edir = str(tmp_path / "elastic")
    tdir = os.path.join(edir, "telemetry")
    drill = os.path.join("tests", "nightly", "dist_elastic.py")
    _launch_raw(["-n", "3", "--launcher", "local", "--workdir", _ROOT,
                 "--port", "9906", "--elastic", "--min-world", "2",
                 "--elastic-dir", edir, "--max-restarts", "4",
                 sys.executable, drill],
                extra_env={"MXTPU_STEP_TIMEOUT_S": "12",
                           "MXTPU_TELEMETRY_DIR": tdir})

    # final agreement: generation 2, grown back to world 3
    with open(os.path.join(edir, "LEDGER.json")) as f:
        led = json.load(f)
    assert led["generation"] == 2 and led["world_size"] == 3
    assert led["reason"] == "grow"

    # one loss row per epoch, worlds 3,3 -> 2 -> 3,3 across generations
    with open(os.path.join(edir, "losses-elastic.jsonl")) as f:
        rows = [json.loads(line) for line in f]
    assert [r["epoch"] for r in rows] == [0, 1, 2, 3, 4]
    assert [r["world"] for r in rows] == [3, 3, 2, 3, 3]
    assert [r["generation"] for r in rows] == [0, 0, 1, 2, 2]

    # every completed epoch's partitions tile the dataset exactly:
    # no sample dropped or duplicated through either transition
    for gen, epoch, world in ((0, 0, 3), (0, 1, 3), (1, 2, 2),
                              (2, 3, 3), (2, 4, 3)):
        idx = []
        for r in range(world):
            p = os.path.join(edir, "part-g%d-e%03d-r%02d.json"
                             % (gen, epoch, r))
            with open(p) as f:
                d = json.load(f)
            assert d["world"] == world
            idx += d["indices"]
        assert sorted(idx) == list(range(240)), (gen, epoch)

    # telemetry: propose/adopt pairs agree on generation+world+reason,
    # and each incarnation emitted a resume for its whole world
    recs = []
    for path in glob.glob(os.path.join(tdir, "events-rank*.jsonl*")):
        with open(path) as f:
            recs += [json.loads(line) for line in f if line.strip()]
    el = [r for r in recs if r.get("kind") == "elastic"]
    props = {(r["generation"], r["world_size"], r["reason"])
             for r in el if r["event"] == "propose"}
    adopts = {(r["generation"], r["world_size"], r["reason"])
              for r in el if r["event"] == "adopt"}
    assert props == {(1, 2, "dead_node"), (2, 3, "grow")}
    assert adopts == props
    resumes = [(r["generation"], r["world_size"])
               for r in el if r["event"] == "resume"]
    assert resumes.count((0, 3)) == 3
    assert resumes.count((1, 2)) == 2
    assert resumes.count((2, 3)) == 3

    # loss trajectory after each transition == a fresh fixed-world run
    # resumed from the same checkpoint (the agreement protocol must not
    # perturb the math)
    for world, step, stop, port in ((2, 2, 3, "9912"), (3, 3, 5, "9913")):
        _launch_raw(["-n", str(world), "--launcher", "local",
                     "--workdir", _ROOT, "--port", port,
                     sys.executable, drill],
                    extra_env={"MXTPU_ELASTIC_DIR": edir,
                               "MXTPU_ELASTIC_REFERENCE": "1",
                               "MXTPU_RESUME_STEP": str(step),
                               "MXTPU_STOP_EPOCH": str(stop)})
        ref = os.path.join(edir, "losses-ref-w%d-s%d.jsonl" % (world,
                                                               step))
        with open(ref) as f:
            ref_rows = [json.loads(line) for line in f]
        assert ref_rows, "reference run recorded no losses"
        by_epoch = {r["epoch"]: r for r in rows}
        for r in ref_rows:
            assert r["loss"] == by_epoch[r["epoch"]]["loss"], \
                (world, r["epoch"])


# ----------------------------------------------------------------------
# warm elasticity: redundant host-memory hot state
# (docs/resilience.md "Warm elasticity")
# ----------------------------------------------------------------------
from mxnet_tpu.resilience import hotstate  # noqa: E402
from mxnet_tpu.resilience.hotstate import HotStateUnavailable  # noqa: E402


def _warm_env(tmp_path, monkeypatch, **env):
    monkeypatch.setenv("MXTPU_WARM_REMESH", "1")
    monkeypatch.setenv("MXTPU_HANDOFF_DIR", str(tmp_path / "handoff"))
    for var in ("MXTPU_NUM_HOSTS", "MXTPU_HOST_INDEX",
                "MXTPU_HOTSTATE_BUDDIES", "MXTPU_ELASTIC_GENERATION"):
        monkeypatch.delenv(var, raising=False)
    for key, val in env.items():
        monkeypatch.setenv(key, val)


def _warm_tree(scale=1.0):
    return {"params": {"w": np.arange(12, dtype=np.float32)
                       .reshape(3, 4) * scale,
                       "b": np.ones(4, np.float32) * scale},
            "opt_state": {"m": np.zeros((3, 4), np.float32)}}


def _warm_abstract():
    return {"params": {"w": np.zeros((3, 4), np.float32),
                       "b": np.zeros(4, np.float32)},
            "opt_state": {"m": np.zeros((3, 4), np.float32)}}


def test_hotstate_snapshot_warm_resume_roundtrip(tmp_path, monkeypatch):
    _warm_env(tmp_path, monkeypatch)
    tree = _warm_tree()
    hotstate.snapshot(tree, step=5)
    out, step, meta = hotstate.warm_resume(_warm_abstract())
    assert step == 5 and meta["n_payloads"] == 1
    for group in ("params", "opt_state"):
        for leaf, want in tree[group].items():
            assert np.array_equal(out[group][leaf], want), (group, leaf)
    # without an abstract target the manifests' own nesting comes back
    out2, step2, _ = hotstate.warm_resume(None)
    assert step2 == 5
    assert np.array_equal(out2["params"]["b"], tree["params"]["b"])


def test_hotstate_newest_complete_step_wins(tmp_path, monkeypatch):
    _warm_env(tmp_path, monkeypatch)
    hotstate.snapshot(_warm_tree(scale=1.0), step=3)
    hotstate.snapshot(_warm_tree(scale=7.0), step=9)
    out, step, _ = hotstate.warm_resume(_warm_abstract())
    assert step == 9
    assert np.array_equal(out["params"]["w"],
                          _warm_tree(scale=7.0)["params"]["w"])


def test_hotstate_disabled_and_cold_verdicts(tmp_path, monkeypatch):
    _warm_env(tmp_path, monkeypatch)
    # empty handoff area -> cold verdict, reason no_payloads
    verdict = hotstate.decide_sources()
    assert verdict == {"mode": "cold", "reason": "no_payloads"}
    with pytest.raises(HotStateUnavailable) as ei:
        hotstate.warm_resume(_warm_abstract())
    assert ei.value.reason == "cold_verdict"
    # a group missing one rank's payload never satisfies the directory
    hotstate._write_payload(
        {"params/w": [([[0, 3], [0, 4]],
                       np.zeros((3, 4), np.float32))]},
        step=4, rank=0, world=2, host=0, namespace="train")
    assert hotstate.decide_sources()["reason"] == "incomplete"
    # the knob itself off -> structured "disabled", nothing read
    monkeypatch.setenv("MXTPU_WARM_REMESH", "0")
    assert not hotstate.warm_enabled()
    with pytest.raises(HotStateUnavailable) as ei:
        hotstate.warm_resume(_warm_abstract())
    assert ei.value.reason == "disabled"


def test_hotstate_buddy_lands_off_host_and_survives_host_loss(
        tmp_path, monkeypatch):
    """4 ranks on 2 simulated hosts; burning host 1 leaves every rank's
    sharded state reconstructible from host 0 (owns + buddy replicas)."""
    _warm_env(tmp_path, monkeypatch, MXTPU_NUM_HOSTS="2")
    # contiguous-block host map, and buddies never on their own host
    assert [hotstate.host_index(r, 4) for r in range(4)] == [0, 0, 1, 1]
    assert hotstate.buddy_hosts(0, 4) == [1]
    assert hotstate.buddy_hosts(3, 4) == [0]
    w = np.arange(16, dtype=np.float32).reshape(4, 4)
    for rank in range(4):
        hotstate._write_payload(
            {"params/w": [([[rank, rank + 1], [0, 4]],
                           w[rank:rank + 1])]},
            step=7, rank=rank, world=4,
            host=hotstate.host_index(rank, 4), namespace="train")
    hotstate.simulate_host_loss(1)
    verdict = hotstate.decide_sources()
    assert verdict["mode"] == "warm" and verdict["step"] == 7
    assert verdict["n_buddy"] == 2          # ranks 2,3 serve via buddies
    out, step, meta = hotstate.load_sources(
        verdict, {"params": {"w": np.zeros((4, 4), np.float32)}})
    assert step == 7 and meta["n_payloads"] == 4
    assert np.array_equal(out["params"]["w"], w)


def test_hotstate_buddy_loss_seam_drops_redundancy(tmp_path, monkeypatch):
    _warm_env(tmp_path, monkeypatch, MXTPU_NUM_HOSTS="2")
    _arm(monkeypatch, "kind=buddy_loss:rank=0")
    hotstate.snapshot(_warm_tree(), step=2, rank=0, world=2)
    recs = hotstate.scan()
    assert {r["source"] for r in recs} == {"own"}   # replica push lost
    # own host burns -> nothing left to serve rank 0 -> cold
    hotstate.simulate_host_loss(0)
    assert hotstate.decide_sources()["mode"] == "cold"


def test_hotstate_corrupt_payload_is_rejected_by_crc(tmp_path,
                                                     monkeypatch):
    _warm_env(tmp_path, monkeypatch)
    hotstate.snapshot(_warm_tree(), step=5)
    _arm(monkeypatch, "kind=corrupt:rank=0")
    with pytest.raises(HotStateUnavailable) as ei:
        hotstate.warm_resume(_warm_abstract())
    assert ei.value.reason == "crc_mismatch"
    # the fault fired once: the next attempt reads clean bytes
    out, step, _ = hotstate.warm_resume(_warm_abstract())
    assert step == 5
    assert np.array_equal(out["params"]["b"], np.ones(4, np.float32))


def test_hotstate_snapshot_crash_seam_raises_injected(tmp_path,
                                                      monkeypatch):
    _warm_env(tmp_path, monkeypatch)
    _arm(monkeypatch, "kind=snapshot_crash:step=3")
    with pytest.raises(InjectedFault):
        hotstate.snapshot(_warm_tree(), step=3)
    assert hotstate.scan() == []            # nothing half-written


def test_hotstate_target_mismatch_names_leaf(tmp_path, monkeypatch):
    _warm_env(tmp_path, monkeypatch)
    hotstate.snapshot(_warm_tree(), step=1)
    bad = _warm_abstract()
    bad["params"]["w"] = np.zeros((5, 4), np.float32)
    with pytest.raises(HotStateUnavailable) as ei:
        hotstate.warm_resume(bad)
    assert ei.value.reason == "target_mismatch"
    assert "params/w" in str(ei.value)


def test_trainer_warm_elastic_resume_and_checkpoint_fallback(
        tmp_path, monkeypatch):
    """ShardedTrainer.elastic_resume: the warm rung re-places the
    handoff tree with the trainer's shardings and never opens a
    checkpoint; a corrupt payload degrades to the checkpoint rung with
    the fallback reason in the resume telemetry."""
    _warm_env(tmp_path, monkeypatch)
    ckdir = str(tmp_path / "ckpts")
    shapes = {"data": (16, 8)}
    lbl = {"softmax_label": (16,)}
    tr, params, opt_state, aux, batch = _trainer()
    for _ in range(2):
        params, opt_state, aux, _ = tr.step(params, opt_state, aux, batch)
    tr.save_checkpoint_versioned(ckdir, params, opt_state, aux)
    tr.hotstate_snapshot(params, opt_state, aux)
    want = _host(params)

    events = []
    monkeypatch.setattr(elastic, "emit_transition",
                        lambda event, **f: events.append((event, f)))
    tr2, _, _, _, _ = _trainer()
    got = tr2.elastic_resume(ckdir, shapes, label_shapes=lbl,
                             source="warm")
    assert got is not None
    p2, _, _, step = got
    assert step == 2 and tr2.num_update == 2
    for name, arr in _host(p2).items():
        assert np.array_equal(want[name], arr), name
    (event, fields), = [e for e in events if e[0] == "resume"]
    assert fields["path"] == "warm" and fields["fallback_reason"] is None
    assert fields["n_payloads"] == 1

    # corrupt the payload -> CRC rejects -> checkpoint rung, reason kept
    events.clear()
    _arm(monkeypatch, "kind=corrupt")
    tr3, _, _, _, _ = _trainer()
    got = tr3.elastic_resume(ckdir, shapes, label_shapes=lbl,
                             source="auto")
    assert got is not None and got[3] == 2
    for name, arr in _host(got[0]).items():
        assert np.array_equal(want[name], arr), name
    (event, fields), = [e for e in events if e[0] == "resume"]
    assert fields["path"] == "cold"
    assert fields["fallback_reason"] == "crc_mismatch"


# ----------------------------------------------------------------------
# auto_resume corruption fallback (satellite: a committed checkpoint
# damaged after the fact must not end the run while an older one works)
# ----------------------------------------------------------------------
def test_auto_resume_walks_back_past_corrupt_latest(tmp_path):
    from mxnet_tpu.parallel.ckpt import abstract_like
    mgr = CheckpointManager(str(tmp_path / "run"), keep=0,
                            payload_format="host")
    for step in (1, 2):
        mgr.save({"w": jnp.arange(8, dtype=jnp.float32) * step}, step)
    # truncate the newest manifest: simulated post-commit damage
    manifest = os.path.join(mgr.step_path(2), "host_ckpt.json")
    with open(manifest, "w") as f:
        f.write('{"step": 2, "keys"')
    restored, step = mgr.auto_resume(
        abstract_like({"w": jnp.zeros(8, jnp.float32)}))
    assert step == 1
    assert np.allclose(np.asarray(restored["w"]), np.arange(8))

    # every kept version bad -> structured restore_corrupt, not a crash
    manifest1 = os.path.join(mgr.step_path(1), "host_ckpt.json")
    with open(manifest1, "w") as f:
        f.write("not json")
    with pytest.raises(ResilienceError) as ei:
        mgr.auto_resume(abstract_like({"w": jnp.zeros(8, jnp.float32)}))
    assert ei.value.kind == "restore_corrupt"
    assert ei.value.phase == "ckpt_restore"


def _read_elastic_events(tdir):
    recs = []
    for path in glob.glob(os.path.join(tdir, "events-rank*.jsonl*")):
        with open(path) as f:
            recs += [json.loads(line) for line in f if line.strip()]
    return recs


@pytest.mark.slow
def test_warm_shrink_grow_drill(tmp_path):
    """The warm-elasticity acceptance drill: the SAME shrink/grow
    timeline as test_elastic_shrink_grow_drill but with
    MXTPU_WARM_REMESH=1 — every transition resumes from the host-memory
    handoff area (the victim's host RAM burns with it; its state is
    served by the off-host ring buddy), ZERO checkpoint reads happen on
    any resume, and the loss trajectory is still bit-identical to
    fixed-world reference runs from the same steps."""
    edir = str(tmp_path / "elastic")
    tdir = os.path.join(edir, "telemetry")
    drill = os.path.join("tests", "nightly", "dist_elastic.py")
    _launch_raw(["-n", "3", "--launcher", "local", "--workdir", _ROOT,
                 "--port", "9916", "--elastic", "--min-world", "2",
                 "--elastic-dir", edir, "--max-restarts", "4", "--warm",
                 sys.executable, drill],
                extra_env={"MXTPU_STEP_TIMEOUT_S": "12",
                           "MXTPU_TELEMETRY_DIR": tdir})

    with open(os.path.join(edir, "LEDGER.json")) as f:
        led = json.load(f)
    assert led["generation"] == 2 and led["world_size"] == 3

    with open(os.path.join(edir, "losses-elastic.jsonl")) as f:
        rows = [json.loads(line) for line in f]
    assert [r["epoch"] for r in rows] == [0, 1, 2, 3, 4]
    assert [r["world"] for r in rows] == [3, 3, 2, 3, 3]

    recs = _read_elastic_events(tdir)
    el = [r for r in recs if r.get("kind") == "elastic"]
    # acceptance: the warm path never opened a checkpoint — zero ckpt
    # resume (or corrupt-skip) events across the whole timeline
    ckpt_reads = [r for r in recs if r.get("kind") == "ckpt"
                  and r.get("phase") in ("resume", "restore_corrupt_skip")]
    assert ckpt_reads == [], ckpt_reads
    # both post-transition incarnations resumed warm, every rank
    resumes = [r for r in el if r["event"] == "resume"]
    warm = [(r["generation"], r["world_size"]) for r in resumes
            if r.get("path") == "warm"]
    assert warm.count((1, 2)) == 2
    assert warm.count((2, 3)) == 3
    for r in resumes:
        if r["generation"] >= 1:
            assert r.get("path") == "warm", r
            assert not r.get("fallback_reason"), r
    # each stable point host-offloaded (snapshot events with bytes and
    # off-host buddy placement), and the handoff area is where the env
    # says it is
    snaps = [r for r in el if r["event"] == "snapshot"]
    assert snaps and all(s["bytes"] > 0 for s in snaps)
    assert any(s["buddies"] and s["host"] not in s["buddies"]
               for s in snaps)
    assert os.path.isdir(os.path.join(edir, "handoff", "train"))

    # warm resumes are bit-identical to fixed-world reference runs
    # restored from the same steps (checkpoints exist for references
    # even though the elastic run never read them)
    for world, step, stop, port in ((2, 2, 3, "9917"), (3, 3, 5, "9918")):
        _launch_raw(["-n", str(world), "--launcher", "local",
                     "--workdir", _ROOT, "--port", port,
                     sys.executable, drill],
                    extra_env={"MXTPU_ELASTIC_DIR": edir,
                               "MXTPU_ELASTIC_REFERENCE": "1",
                               "MXTPU_RESUME_STEP": str(step),
                               "MXTPU_STOP_EPOCH": str(stop)})
        ref = os.path.join(edir, "losses-ref-w%d-s%d.jsonl" % (world,
                                                               step))
        with open(ref) as f:
            ref_rows = [json.loads(line) for line in f]
        assert ref_rows, "reference run recorded no losses"
        by_epoch = {r["epoch"]: r for r in rows}
        for r in ref_rows:
            assert r["loss"] == by_epoch[r["epoch"]]["loss"], \
                (world, r["epoch"])


@pytest.mark.slow
def test_warm_corrupt_shard_falls_back_to_checkpoint(tmp_path):
    """Structured degradation: a corrupt handoff payload on rank 0
    fails the CRC at warm-resume time, and that rank alone falls back
    to the versioned checkpoint — resume completes at the same step,
    with the fallback reason named in its elastic telemetry."""
    edir = str(tmp_path / "elastic")
    tdir = os.path.join(edir, "telemetry")
    drill = os.path.join("tests", "nightly", "dist_elastic.py")
    _launch_raw(["-n", "3", "--launcher", "local", "--workdir", _ROOT,
                 "--port", "9919", "--elastic", "--min-world", "2",
                 "--elastic-dir", edir, "--max-restarts", "4", "--warm",
                 sys.executable, drill],
                extra_env={"MXTPU_STEP_TIMEOUT_S": "12",
                           "MXTPU_TELEMETRY_DIR": tdir,
                           "MXTPU_DRILL_EPOCHS": "3",
                           "MXTPU_DRILL_GROW": "",
                           "MXTPU_FAULT_SPEC": "kind=corrupt:rank=0"})

    with open(os.path.join(edir, "losses-elastic.jsonl")) as f:
        rows = [json.loads(line) for line in f]
    assert [r["epoch"] for r in rows] == [0, 1, 2]
    assert [r["world"] for r in rows] == [3, 3, 2]

    el = [r for r in _read_elastic_events(tdir)
          if r.get("kind") == "elastic"]
    gen1 = [r for r in el if r["event"] == "resume"
            and r["generation"] == 1]
    assert len(gen1) == 2, gen1
    paths = sorted((r.get("path"), r.get("fallback_reason"))
                   for r in gen1)
    # rank 0's payload read is corrupted -> checkpoint rung, named
    # reason; the untouched rank stays warm.  Both land on step 2.
    assert paths == [("cold", "crc_mismatch"), ("warm", None)], paths
    assert all(r["step"] == 2 for r in gen1)


@pytest.mark.slow
def test_multihost_warm_shrink_grow_drill(tmp_path):
    """Multi-host simulation: 4 workers over 2 simulated hosts
    (contiguous block mapping).  Killing rank 3 burns host 1's whole
    handoff RAM — ranks 2 and 3's own payloads vanish together — and
    the survivors still warm-resume the full tree from host 0's owns +
    ring-buddy replicas, bit-identical to a cold reference."""
    edir = str(tmp_path / "elastic")
    tdir = os.path.join(edir, "telemetry")
    drill = os.path.join("tests", "nightly", "dist_elastic.py")
    _launch_raw(["-n", "4", "--launcher", "local", "--workdir", _ROOT,
                 "--port", "9921", "--elastic", "--min-world", "3",
                 "--elastic-dir", edir, "--max-restarts", "4", "--warm",
                 sys.executable, drill],
                extra_env={"MXTPU_STEP_TIMEOUT_S": "12",
                           "MXTPU_TELEMETRY_DIR": tdir,
                           "MXTPU_NUM_HOSTS": "2",
                           "MXTPU_DRILL_EPOCHS": "4",
                           "MXTPU_DRILL_KILL": "0:1:3",
                           "MXTPU_DRILL_GROW": "1:2:4"})

    with open(os.path.join(edir, "losses-elastic.jsonl")) as f:
        rows = [json.loads(line) for line in f]
    assert [r["epoch"] for r in rows] == [0, 1, 2, 3]
    assert [r["world"] for r in rows] == [4, 4, 3, 4]

    recs = _read_elastic_events(tdir)
    ckpt_reads = [r for r in recs if r.get("kind") == "ckpt"
                  and r.get("phase") in ("resume", "restore_corrupt_skip")]
    assert ckpt_reads == [], ckpt_reads
    el = [r for r in recs if r.get("kind") == "elastic"]
    warm = [(r["generation"], r["world_size"])
            for r in el if r["event"] == "resume"
            and r.get("path") == "warm"]
    assert warm.count((1, 3)) == 3
    assert warm.count((2, 4)) == 4

    # warm losses bit-identical to cold (fixed-world, checkpoint-
    # restored) references through both transitions
    for world, step, stop, port in ((3, 2, 3, "9922"), (4, 3, 4, "9923")):
        _launch_raw(["-n", str(world), "--launcher", "local",
                     "--workdir", _ROOT, "--port", port,
                     sys.executable, drill],
                    extra_env={"MXTPU_ELASTIC_DIR": edir,
                               "MXTPU_ELASTIC_REFERENCE": "1",
                               "MXTPU_RESUME_STEP": str(step),
                               "MXTPU_STOP_EPOCH": str(stop)})
        ref = os.path.join(edir, "losses-ref-w%d-s%d.jsonl" % (world,
                                                               step))
        with open(ref) as f:
            ref_rows = [json.loads(line) for line in f]
        assert ref_rows, "reference run recorded no losses"
        by_epoch = {r["epoch"]: r for r in rows}
        for r in ref_rows:
            assert r["loss"] == by_epoch[r["epoch"]]["loss"], \
                (world, r["epoch"])
