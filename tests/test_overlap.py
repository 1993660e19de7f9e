"""Overlap machinery (docs/perf.md "Overlap"): DevicePrefetcher,
AsyncLauncher, gradient bucketing, and the persistent compile cache.

All CPU-only: the prefetcher/launcher are host threads, bucketing is
identity math checked numerically, and the compile cache is asserted
through its lowering counter — none of it needs a chip.
"""
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import parallel
from mxnet_tpu.parallel import overlap


# ---------------------------------------------------------------------------
# DevicePrefetcher
# ---------------------------------------------------------------------------

def _slow_feed(n, fetch_s):
    for i in range(n):
        time.sleep(fetch_s)
        yield i


def test_prefetcher_hides_fetch_time():
    """With fetch and 'compute' each t seconds, serial is 2nt; the
    prefetcher pipelines them to ~nt.  Assert well under serial."""
    n, t = 8, 0.02
    pf = overlap.DevicePrefetcher(_slow_feed(n, t), depth=2)
    try:
        got = []
        t0 = time.perf_counter()
        for _ in range(n):
            got.append(next(pf))
            time.sleep(t)           # stands in for device compute
        wall = time.perf_counter() - t0
    finally:
        pf.close()
    assert got == list(range(n))
    serial = 2.0 * n * t
    assert wall < 0.8 * serial, (wall, serial)


def test_prefetcher_exhaustion_and_close_idempotent():
    pf = overlap.DevicePrefetcher(iter(range(3)), depth=2)
    assert [next(pf) for _ in range(3)] == [0, 1, 2]
    with pytest.raises(StopIteration):
        next(pf)
    pf.close()
    pf.close()


def test_prefetcher_propagates_producer_error():
    def bad():
        yield 1
        raise ValueError("boom in producer")

    pf = overlap.DevicePrefetcher(bad(), depth=2)
    try:
        with pytest.raises(ValueError, match="boom in producer"):
            for _ in range(3):
                next(pf)
    finally:
        pf.close()


def test_prefetcher_place_fn_runs_on_producer():
    placed = []

    def place(x):
        placed.append(x)
        return jnp.asarray(x)

    pf = overlap.DevicePrefetcher(iter([1.0, 2.0]), place_fn=place)
    try:
        a = next(pf)
        assert isinstance(a, jax.Array) and float(a) == 1.0
        assert float(next(pf)) == 2.0
        assert placed == [1.0, 2.0]
    finally:
        pf.close()


def test_prefetch_preserves_batch_stream():
    """Same iterator state machine with and without the prefetcher:
    identical batch order, data, labels, and pads across epochs
    (including the reset() at the epoch boundary)."""
    rng = np.random.RandomState(42)
    data = rng.rand(22, 3).astype(np.float32)   # 22 % 4 != 0: pads too
    label = np.arange(22, dtype=np.float32)

    def collect(it):
        out = []
        while True:
            try:
                b = it.next()
            except StopIteration:
                break
            out.append((b.data[0].asnumpy().copy(),
                        b.label[0].asnumpy().copy(), b.pad))
        return out

    plain = mx.io.NDArrayIter(data, label, batch_size=4)
    pf = overlap.DevicePrefetcher(
        mx.io.NDArrayIter(data, label, batch_size=4))
    try:
        for _epoch in range(2):
            a, b = collect(plain), collect(pf)
            assert len(a) == len(b) > 0
            for (da, la, pa), (db, lb, pb) in zip(a, b):
                np.testing.assert_array_equal(da, db)
                np.testing.assert_array_equal(la, lb)
                assert pa == pb
            plain.reset()
            pf.reset()
    finally:
        pf.close()


def test_prefetcher_reset_mid_epoch():
    """reset() drains the in-flight batches and rewinds — the stream
    restarts from batch 0, not from wherever the producer had raced
    ahead to."""
    data = np.arange(64, dtype=np.float32).reshape(16, 4)
    pf = overlap.DevicePrefetcher(
        mx.io.NDArrayIter(data, batch_size=4), depth=3)
    try:
        first = pf.next().data[0].asnumpy().copy()
        pf.reset()
        again = pf.next().data[0].asnumpy().copy()
        np.testing.assert_array_equal(first, again)
    finally:
        pf.close()


@pytest.mark.parametrize("ctx", [
    pytest.param(lambda: mx.cpu(), id="one"),
    pytest.param(lambda: [mx.cpu(0), mx.cpu(1)], id="mesh2")])
def test_fit_prefetch_composes_with_the_look_ahead(ctx):
    """``prefetch=True`` puts the HOST fetch on a thread; ``fit`` copies
    one batch ahead to the DEVICE either way.  Same batches, same order:
    parameters, momentum and metric are bit-identical."""
    rng = np.random.RandomState(3)
    X = rng.randn(96, 10).astype(np.float32)
    y = (X.sum(axis=1) > 0).astype(np.float32)
    ran = {}
    for prefetch in (False, True):
        mx.random.seed(9)
        mod = mx.mod.Module(mx.models.get_mlp(2, (8,)), context=ctx())
        metric = mx.metric.create("ce")
        mod.fit(mx.io.NDArrayIter(X, y, batch_size=16), eval_metric=metric,
                kvstore="device", optimizer="sgd",
                optimizer_params={"learning_rate": 0.2, "momentum": 0.9},
                initializer=mx.init.Uniform(0.1), num_epoch=2,
                prefetch=prefetch)
        group = mod._exec_group
        # the thread changes who fetches, not where the copy is issued
        assert (group.n_staged, group.n_loaded) == (10, 2)
        assert group._staged is None
        ran[prefetch] = (
            {k: v.asnumpy() for k, v in mod.get_params()[0].items()},
            {k: np.asarray(v) for k, v in
             mod._fused_holder["states"].items()},
            metric.get())
    for got, want in zip(ran[True][:2], ran[False][:2]):
        assert set(got) == set(want)
        for k in want:
            np.testing.assert_array_equal(got[k], want[k], err_msg=k)
    assert ran[True][2] == ran[False][2]


# ---------------------------------------------------------------------------
# AsyncLauncher
# ---------------------------------------------------------------------------

def test_async_launcher_fifo_and_barrier():
    seen = []
    launcher = overlap.AsyncLauncher(name="t")
    try:
        for i in range(20):
            launcher.submit(lambda i=i: seen.append(i))
        launcher.wait_all(timeout=10)
        assert seen == list(range(20)), "single worker must preserve order"
    finally:
        launcher.close()


def test_async_launcher_reraises_first_error():
    launcher = overlap.AsyncLauncher(name="t")
    try:
        launcher.submit(lambda: (_ for _ in ()).throw(RuntimeError("first")))
        launcher.submit(lambda: None)
        with pytest.raises(RuntimeError, match="first"):
            launcher.wait_all(timeout=10)
    finally:
        launcher.close()


# ---------------------------------------------------------------------------
# gradient bucketing
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mb", [0.0001, 0.001, 0.1, 25.0])
def test_partition_buckets_covers_every_grad_once(mb):
    shapes = [(3,), (128, 128), (1000,), (7, 11), (2048, 64), (5,), (1,)]
    sized = [("g%d" % i, int(np.prod(s)) * 4)
             for i, s in enumerate(shapes)]
    buckets = partitioned = overlap.partition_buckets(
        sized, bucket_nbytes=int(mb * (1 << 20)))
    flat = [k for b in partitioned for k in b]
    assert flat == [k for k, _ in sized], "order-preserving, each exactly once"
    assert all(b for b in buckets), "no empty buckets"
    target = int(mb * (1 << 20))
    for b in buckets:
        size = sum(n for k, n in sized if k in b)
        # only a single oversize item may exceed the target
        assert size <= target or len(b) == 1


def test_partition_buckets_disabled_is_single_bucket():
    sized = [("a", 100), ("b", 200)]
    assert overlap.partition_buckets(sized, bucket_nbytes=0) == [["a", "b"]]


def test_interleave_grad_buckets_is_identity_math():
    rng = np.random.RandomState(3)
    grads = {"w%d" % i: jnp.asarray(rng.randn(64, 64).astype(np.float32))
             for i in range(6)}

    def f(gs):
        out = overlap.interleave_grad_buckets(gs, bucket_nbytes=64 * 64 * 4)
        assert set(out) == set(gs)
        return out

    out = jax.jit(f)(grads)
    for k in grads:
        np.testing.assert_array_equal(np.asarray(out[k]),
                                      np.asarray(grads[k]))


def test_kvstore_bucketed_push_matches_sync_push(monkeypatch):
    """push_async + wait_all (bucketed, async worker) must be
    numerically identical to the plain sync push."""
    monkeypatch.setenv("MXTPU_BUCKET_MB", "0.001")  # force many buckets
    shape = (16, 16)
    rng = np.random.RandomState(0)
    vals = {k: [mx.nd.array(rng.randn(*shape).astype(np.float32))
                for _ in range(3)] for k in (5, 7, 11, 13)}

    def run(asynchronous):
        kv = mx.kv.create()
        for k in vals:
            kv.init(k, mx.nd.zeros(shape))
        for k, vs in vals.items():
            if asynchronous:
                kv.push_async(k, list(vs))
            else:
                kv.push(k, list(vs))
        if asynchronous:
            kv.wait_all()
        out = {}
        for k in vals:
            o = mx.nd.empty(shape)
            kv.pull(k, out=o)
            out[k] = o.asnumpy()
        return out

    a, b = run(False), run(True)
    for k in vals:
        np.testing.assert_array_equal(a[k], b[k])


# ---------------------------------------------------------------------------
# compile cache
# ---------------------------------------------------------------------------

def _mlp():
    data = mx.sym.Variable("data")
    fc1 = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    act = mx.sym.Activation(fc1, act_type="relu")
    fc2 = mx.sym.FullyConnected(act, num_hidden=4, name="fc2")
    return mx.sym.SoftmaxOutput(fc2, name="softmax")


def _run_trainer_step(net, mesh):
    opt = mx.optimizer.create("sgd", learning_rate=0.1,
                              rescale_grad=1.0 / 16)
    tr = parallel.ShardedTrainer(net, opt, mesh)
    mx.random.seed(0)
    params, opt_state, aux = tr.init_params(
        {"data": (16, 8)}, label_shapes={"softmax_label": (16,)})
    rng = np.random.RandomState(1)
    batch = tr.shard_batch({
        "data": rng.randn(16, 8).astype(np.float32),
        "softmax_label": (rng.rand(16) * 4).astype(np.float32)})
    params, opt_state, aux, outs = tr.step(params, opt_state, aux, batch)
    return np.asarray(outs[0])


def test_second_trainer_bind_skips_lowering():
    """Two ShardedTrainers over the same (graph, shapes, mesh, rules,
    optimizer hypers): the second adopts the cached jitted step — the
    lowering counter must not move, and outputs must agree."""
    overlap.compile_cache_clear()
    net = _mlp()
    mesh = parallel.auto_mesh()
    o1 = _run_trainer_step(net, mesh)
    st1 = overlap.compile_cache_stats()
    assert st1["lowerings"] >= 1
    o2 = _run_trainer_step(net, mesh)
    st2 = overlap.compile_cache_stats()
    assert st2["lowerings"] == st1["lowerings"], \
        "identical second bind must not lower again: %s -> %s" % (st1, st2)
    assert st2["hits"] >= st1["hits"] + 1
    np.testing.assert_allclose(o1, o2, rtol=1e-6, atol=1e-7)


def test_different_optimizer_hypers_miss_cache():
    """Changed learning rate -> different baked constants -> the key
    must miss (correctness over reuse)."""
    overlap.compile_cache_clear()
    net = _mlp()
    mesh = parallel.auto_mesh()
    _run_trainer_step(net, mesh)
    st1 = overlap.compile_cache_stats()

    opt = mx.optimizer.create("sgd", learning_rate=0.5,
                              rescale_grad=1.0 / 16)
    tr = parallel.ShardedTrainer(net, opt, mesh)
    params, opt_state, aux = tr.init_params(
        {"data": (16, 8)}, label_shapes={"softmax_label": (16,)})
    rng = np.random.RandomState(1)
    batch = tr.shard_batch({
        "data": rng.randn(16, 8).astype(np.float32),
        "softmax_label": (rng.rand(16) * 4).astype(np.float32)})
    tr.step(params, opt_state, aux, batch)
    st2 = overlap.compile_cache_stats()
    assert st2["lowerings"] == st1["lowerings"] + 1


def test_executor_program_registry_hits_fresh_symbol():
    """A structurally identical but FRESH Symbol (rebind-after-rebuild)
    reuses the traced program via the graph-hash registry."""
    overlap.compile_cache_clear()

    def build():
        d = mx.sym.Variable("data")
        w = mx.sym.Variable("w")
        return mx.sym.FullyConnected(data=d, weight=w, no_bias=True,
                                     num_hidden=4, name="fc")

    build().simple_bind(mx.cpu(), data=(2, 3), w=(4, 3))
    st1 = overlap.compile_cache_stats()
    build().simple_bind(mx.cpu(), data=(2, 3), w=(4, 3))
    st2 = overlap.compile_cache_stats()
    assert st2["lowerings"] == st1["lowerings"]
    assert st2["hits"] == st1["hits"] + 1


def test_cache_key_components_change_key():
    k0 = overlap.cache_key("a", (1, 2), "x")
    assert k0 == overlap.cache_key("a", (1, 2), "x"), "deterministic"
    assert k0 != overlap.cache_key("a", (1, 3), "x")
    assert k0 != overlap.cache_key("a", (1, 2), "y")


# ---------------------------------------------------------------------------
# overlap_report
# ---------------------------------------------------------------------------

def _rec(kind, wall_ms, dur_ms, name=None, rank=0):
    r = {"kind": kind, "wall_ms": wall_ms, "dur_ms": dur_ms, "rank": rank}
    if name:
        r["name"] = name
    return r


def test_overlap_report_serial_vs_overlapped():
    from mxnet_tpu.observability import overlap_report
    # serial: steps tile the wall exactly, no spans inside the window
    serial = [_rec("step", 1000.0 * i, 1000.0) for i in range(1, 6)]
    rep = overlap_report(serial)
    assert rep["steps"] == 5
    assert abs(rep["overlap_ratio"] - 1.0) < 1e-6
    # overlapped: producer data_wait spans land INSIDE the same wall
    # (a span stamped past the last step record is outside the window)
    overlapped = serial + [
        _rec("span", 1000.0 * i + 500.0, 900.0, name="data_wait")
        for i in range(2, 5)]
    rep2 = overlap_report(overlapped)
    assert rep2["overlap_ratio"] > 1.5
    assert rep2["phase_ms"]["data_wait"] == pytest.approx(2700.0)
    assert rep2["phase_p50_ms"]["data_wait"] == pytest.approx(900.0)


def test_overlap_report_excludes_first_step_and_outside_spans():
    from mxnet_tpu.observability import overlap_report
    recs = [
        _rec("step", 0.0, 60000.0),          # compile step: bounds only
        _rec("step", 61000.0, 1000.0),
        _rec("step", 62000.0, 1000.0),
        # span before the window: excluded
        _rec("span", -5.0, 500.0, name="data_wait"),
    ]
    rep = overlap_report(recs)
    assert rep["serial_ms"] == pytest.approx(2000.0)
    assert rep["wall_ms"] == pytest.approx(62000.0)


def test_overlap_report_too_few_steps():
    from mxnet_tpu.observability import overlap_report
    rep = overlap_report([_rec("step", 0.0, 10.0)])
    assert rep["overlap_ratio"] is None
