"""Overlap machinery: async device feed, gradient bucketing, compile cache.

Three serial phases the telemetry spans (PR 4) measure but the trainer
loops never hid:

1. **Async device feed** — :class:`DevicePrefetcher` wraps any
   ``DataIter`` and runs ``next()`` + the host→device placement for
   batch N+1 on a background thread while step N executes.  XLA
   dispatch is async, so the host is idle during device compute; the
   producer thread fills that idle time.  The producer emits the same
   ``data_wait``/``h2d`` span names the serial path does (tagged
   ``async=1``) so before/after span reports are directly comparable,
   and the consumer-side ``data_wait`` collapses to a queue pop.

2. **Bucketed allreduce over backward** — :func:`partition_buckets`
   fuses gradients into size-targeted buckets (``MXTPU_BUCKET_MB``,
   default 25 MB) in reverse-topo order (the order backward produces
   them), and :func:`interleave_grad_buckets` chains per-bucket
   ``lax.optimization_barrier`` ties inside the traced step so XLA's
   latency-hiding scheduler sees one collective per bucket — emitted as
   soon as that bucket's gradients exist — instead of one fused
   tail-end collective after the whole backward.  The barriers are
   mathematically identity: losses are bit-identical with bucketing on
   or off.  The per-key kvstore path reuses the same partitioner and
   gets true async dispatch through :class:`AsyncLauncher` (a single
   FIFO worker, so the collective ORDER is identical on every rank —
   the rank-divergence shape MXL-D exists to catch never arises).

3. **Persistent compile cache** — a process-global registry keyed on
   (graph hash from the canonical ``Symbol.tojson`` serialization, arg
   shapes/dtypes/shardings, mesh shape, sharding rules, compute dtype,
   jax version) so a second ``ShardedTrainer`` bind, a bucketing-module
   rebind, or an elastic re-mesh resume at a previously-seen world size
   reuses the traced/lowered artifact instead of re-paying lowering.
   :func:`enable_persistent_cache` additionally keeps JAX's on-disk
   compilation cache on (where ``JAX_COMPILATION_CACHE_DIR`` says, else
   ``<checkout>/.jax_cache``) so even a fresh process skips XLA
   compilation proper.

Knobs: ``MXTPU_PREFETCH`` / ``prefetch=`` (off by default),
``MXTPU_PREFETCH_DEPTH`` (default 2, double buffering),
``MXTPU_BUCKET_MB`` (default 25; ``0`` disables bucketing).
"""
from __future__ import annotations

import hashlib
import os
import queue as _queue
import threading

from ..base import collective_seam

__all__ = [
    "DevicePrefetcher", "AsyncLauncher",
    "partition_buckets", "interleave_grad_buckets", "bucket_bytes",
    "prefetch_enabled", "prefetch_depth",
    "cache_key", "graph_fingerprint", "abstract_fingerprint",
    "rules_fingerprint",
    "optimizer_fingerprint", "compile_cache_get", "compile_cache_put",
    "compile_cache_stats", "compile_cache_clear", "note_lowering",
    "note_hit",
    "enable_persistent_cache",
]


# ---------------------------------------------------------------------------
# env knobs
# ---------------------------------------------------------------------------

_TRUE = ("1", "true", "yes", "on")


def prefetch_enabled(explicit=None):
    """Resolve the prefetch switch: an explicit ``prefetch=`` argument
    wins; otherwise ``MXTPU_PREFETCH``."""
    if explicit is not None:
        return bool(explicit)
    return os.environ.get("MXTPU_PREFETCH", "").lower() in _TRUE


def prefetch_depth(explicit=None):
    """Queue depth for the async feed (``MXTPU_PREFETCH_DEPTH``,
    default 2 = double buffering).  Clamped to >= 1."""
    if explicit is not None:
        return max(1, int(explicit))
    try:
        return max(1, int(os.environ.get("MXTPU_PREFETCH_DEPTH", "2")))
    except ValueError:
        return 2


def bucket_bytes(explicit_mb=None):
    """Gradient-bucket size target in BYTES (``MXTPU_BUCKET_MB``,
    default 25 MB — the DDP-proven sweet spot between collective launch
    overhead and overlap granularity).  0 disables bucketing."""
    if explicit_mb is None:
        try:
            explicit_mb = float(os.environ.get("MXTPU_BUCKET_MB", "25"))
        except ValueError:
            explicit_mb = 25.0
    if explicit_mb <= 0:
        return 0
    return int(explicit_mb * (1 << 20))


# ---------------------------------------------------------------------------
# (1) async device feed
# ---------------------------------------------------------------------------

class _Stop(object):
    """Queue sentinel: end of epoch."""
    __slots__ = ()


class _Raised(object):
    __slots__ = ("exc",)

    def __init__(self, exc):
        self.exc = exc


class DevicePrefetcher(object):
    """Double-buffered async device feed over any ``DataIter``.

    A single background producer thread pulls batch N+1 from ``it`` and
    (optionally) places it on device via ``place_fn`` — e.g. a closure
    over :func:`mxnet_tpu.parallel.sharding.put_local_sharded` — while
    the consumer runs step N.  One producer + a FIFO queue keeps batch
    order exactly the serial order, so training curves are bit-identical
    with prefetch on or off.

    Spans: the producer times the inner fetch as ``data_wait`` and the
    placement as ``h2d`` (both tagged ``async=1``); the consumer's
    queue pop is what the fit loops' existing ``data_wait`` timer now
    sees — near zero when overlap works.  ``overlap_report`` divides the
    summed phase time by step wall time to prove it.

    DataIter surface: ``next``/``iter``/``reset``/``iter_next`` plus
    ``provide_data``/``provide_label``/``batch_size`` passthrough, so it
    drops into ``FeedForward.fit`` / ``BaseModule.fit`` unchanged.
    ``reset()`` is idempotent: it stops the producer, drains in-flight
    batches, resets the inner iter, and restarts.  ``close()`` joins the
    thread for good (also runs at interpreter exit via io.py's
    producer registry, and on ``__del__``).
    """

    def __init__(self, it, place_fn=None, depth=None, name=None):
        self._it = it if hasattr(it, "__next__") else iter(it)
        self._resettable = it if hasattr(it, "reset") else None
        self._place_fn = place_fn
        self._depth = prefetch_depth(depth)
        self._name = name or "prefetch"
        self._queue = _queue.Queue(maxsize=self._depth)
        self._stop = threading.Event()
        self._thread = None
        self._closed = False
        self._n = 0
        self._start()

    # -- producer ----------------------------------------------------------

    def _start(self):
        from .. import io as _io
        if _io._SHUTTING_DOWN or self._closed:
            return
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._produce, name="mxtpu-%s" % self._name, daemon=True)
        _io._register_producer(self._thread)
        self._thread.start()

    def _produce(self):
        from .. import io as _io
        from ..observability import span
        try:
            while not self._stop.is_set() and not _io._SHUTTING_DOWN:
                try:
                    with span("data_wait", step=self._n, **{"async": 1}):
                        batch = next(self._it)
                except StopIteration:
                    self._put(_Stop())
                    return
                if self._place_fn is not None:
                    with span("h2d", step=self._n, **{"async": 1}):
                        batch = self._place_fn(batch)
                self._put(batch)
        except BaseException as exc:        # surfaced at the consumer
            self._put(_Raised(exc))

    def _put(self, item):
        """Blocking put that stays responsive to stop/shutdown."""
        from .. import io as _io
        while not self._stop.is_set() and not _io._SHUTTING_DOWN:
            try:
                self._queue.put(item, timeout=0.1)
                return
            except _queue.Full:
                continue

    # -- consumer (DataIter protocol) --------------------------------------

    def __iter__(self):
        return self

    def __next__(self):
        from ..observability import span
        if self._thread is None:
            self._start()                   # restarted after reset/epoch end
        if self._thread is None:            # interpreter shutting down
            raise StopIteration
        with span("data_wait", step=self._n):
            item = self._queue.get()
        if isinstance(item, _Stop):
            self._join()
            raise StopIteration
        if isinstance(item, _Raised):
            self._join()
            raise item.exc
        # single consumer owns the counter; the producer only reads it
        # for span step labels, where staleness is harmless
        # mxl: thread-shared-ok (MXL-Q001)
        self._n += 1
        return item

    def next(self):
        return self.__next__()

    def iter_next(self):
        try:
            self._cur = self.next()
            return True
        except StopIteration:
            return False

    def getdata(self):
        return self._cur.data

    def getlabel(self):
        return self._cur.label

    def getpad(self):
        return getattr(self._cur, "pad", None)

    def getindex(self):
        return getattr(self._cur, "index", None)

    @property
    def provide_data(self):
        return self._it.provide_data

    @property
    def provide_label(self):
        return self._it.provide_label

    @property
    def batch_size(self):
        return getattr(self._it, "batch_size", 0)

    # -- lifecycle ---------------------------------------------------------

    def _drain(self):
        while True:
            try:
                self._queue.get_nowait()
            except _queue.Empty:
                return

    def _join(self, timeout=10.0):
        t, self._thread = self._thread, None
        if t is None:
            return
        self._stop.set()
        self._drain()                       # unblock a producer mid-put
        while t.is_alive():
            self._drain()
            t.join(timeout=0.1)
            timeout -= 0.1
            if timeout <= 0:
                break

    def reset(self):
        """Idempotent: drain in-flight batches, reset the inner iter,
        restart the producer.  Safe to call mid-epoch or twice in a
        row (every epoch boundary in the fit loops does)."""
        self._join()
        self._drain()
        if self._resettable is not None:
            self._resettable.reset()
        if not self._closed:
            self._start()

    def close(self):
        """Join the producer for good; the inner iter's ``close`` (if
        any) runs too.  Idempotent."""
        self._closed = True
        self._join()
        self._drain()
        inner_close = getattr(self._it, "close", None)
        if callable(inner_close):
            try:
                inner_close()
            except Exception:
                pass

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class AsyncLauncher(object):
    """Single-worker FIFO executor for the per-key kvstore allreduce
    path: ``submit()`` returns immediately, ``wait_all()`` barriers
    before the optimizer update and re-raises the first failure.

    ONE worker thread on purpose: collectives submitted in push order
    run in push order, identical on every rank — concurrency comes from
    overlapping the host-side launch with the caller's remaining
    backward/step work, not from reordering collectives (which would be
    an MXL-D001 rank-divergence hazard on the coordination-KV path).
    The worker is started lazily and parks on an event when idle."""

    def __init__(self, name="kv-async"):
        self._name = name
        self._queue = _queue.Queue()
        self._pending = 0
        self._lock = threading.Lock()
        self._idle = threading.Condition(self._lock)
        self._exc = None
        self._thread = None

    def _ensure_thread(self):
        from .. import io as _io
        if self._thread is not None and self._thread.is_alive():
            return True
        if _io._SHUTTING_DOWN:
            return False
        self._thread = threading.Thread(
            target=self._run, name="mxtpu-%s" % self._name, daemon=True)
        _io._register_producer(self._thread)
        self._thread.start()
        return True

    def _run(self):
        from .. import io as _io
        while not _io._SHUTTING_DOWN:
            try:
                fn = self._queue.get(timeout=0.2)
            except _queue.Empty:
                continue
            if fn is None:
                return
            try:
                fn()
            except BaseException as exc:
                with self._lock:
                    if self._exc is None:
                        self._exc = exc
            finally:
                with self._lock:
                    self._pending -= 1
                    if self._pending == 0:
                        self._idle.notify_all()

    def submit(self, fn):
        """Queue ``fn`` for the worker; falls back to running inline
        when the interpreter is shutting down (never drops work)."""
        with self._lock:
            self._pending += 1
        if not self._ensure_thread():
            try:
                fn()
            finally:
                with self._lock:
                    self._pending -= 1
                    if self._pending == 0:
                        self._idle.notify_all()
            return
        self._queue.put(fn)

    def pending(self):
        """Closures submitted but not yet finished — how the serving
        scheduler senses pipeline idleness (dispatch eagerly when the
        worker has nothing in flight) without a second signal path."""
        with self._lock:
            return self._pending

    def wait_all(self, timeout=None):
        """Block until every submitted closure finished; re-raise the
        first exception any of them hit."""
        with self._lock:
            if self._pending and not self._idle.wait_for(
                    lambda: self._pending == 0, timeout=timeout):
                raise TimeoutError(
                    "%s: %d async kv operations still pending after %ss"
                    % (self._name, self._pending, timeout))
            exc, self._exc = self._exc, None
        if exc is not None:
            raise exc

    def close(self):
        self._queue.put(None)
        t = self._thread
        if t is not None:
            t.join(timeout=5.0)
        self._thread = None


# ---------------------------------------------------------------------------
# (2) gradient bucketing
# ---------------------------------------------------------------------------

def partition_buckets(sized_items, bucket_nbytes=None):
    """Greedy size-targeted partition of ``[(key, nbytes), ...]`` into
    ``[[key, ...], ...]`` buckets, preserving input order.

    Every key lands in exactly one bucket; a single item larger than
    the target gets its own bucket.  Pure and deterministic in the
    input — callers pass the same ordered list on every rank, so the
    bucket layout (and therefore the collective schedule derived from
    it) is rank-uniform by construction.  ``bucket_nbytes`` of 0 (or a
    0 ``MXTPU_BUCKET_MB``) means bucketing is off: everything lands in
    one all-covering bucket, which callers treat as "use the unbucketed
    path"."""
    if bucket_nbytes is None:
        bucket_nbytes = bucket_bytes()
    items = list(sized_items)
    if not items:
        return []
    if bucket_nbytes <= 0:
        return [[k for k, _ in items]]
    buckets, cur, cur_bytes = [], [], 0
    for key, nbytes in items:
        nbytes = int(nbytes or 0)
        if cur and cur_bytes + nbytes > bucket_nbytes:
            buckets.append(cur)
            cur, cur_bytes = [], 0
        cur.append(key)
        cur_bytes += nbytes
    if cur:
        buckets.append(cur)
    return buckets


def _nbytes(x):
    try:
        import numpy as _np
        return int(_np.dtype(x.dtype).itemsize) * int(
            _np.prod(x.shape, dtype=_np.int64)) if x.shape else \
            int(_np.dtype(x.dtype).itemsize)
    except Exception:
        return 0


@collective_seam
def interleave_grad_buckets(named_grads, order=None, bucket_nbytes=None):
    """Chain per-bucket ``lax.optimization_barrier`` ties over a traced
    gradient dict so XLA schedules each bucket's (implicit, sharding-
    inserted) allreduce as soon as the bucket's gradients exist.

    ``named_grads``: ``{name: traced array}``.  ``order``: gradient
    production order — reverse-topo, i.e. LAST layer's grads first, the
    order backward emits them; defaults to ``reversed(named_grads)``
    (dicts preserve argument insertion order, and arguments are topo
    order).  Bucket i+1's barrier takes bucket i's first output as an
    extra operand, creating a pure data dependency that forces the
    scheduler to finalize (and reduce) bucket i before it may finalize
    bucket i+1 — collectives interleave with the remaining backward
    instead of fusing at the tail.  ``optimization_barrier`` is the
    identity function: results are bit-identical bucketed or not.

    Returns a new dict (same keys).  Falls back to the input untouched
    when bucketing is disabled, there's ≤ 1 bucket, or this jax lacks
    ``optimization_barrier``.

    Certified rank-uniform (``@collective_seam``): ``optimization_barrier``
    is NOT a collective (a local scheduling fence), and every input to
    the early returns and the bucket layout — env knob, grad names,
    shapes, dtypes, jax version — is identical on all ranks, so the
    traced program (and the collectives XLA derives from its shardings)
    cannot diverge."""
    if bucket_nbytes is None:
        bucket_nbytes = bucket_bytes()
    if bucket_nbytes <= 0 or len(named_grads) < 2:
        return named_grads
    try:
        from jax import lax
        barrier = lax.optimization_barrier
    except Exception:
        return named_grads
    if order is None:
        order = list(reversed(list(named_grads)))
    sized = [(k, _nbytes(named_grads[k])) for k in order
             if k in named_grads]
    buckets = partition_buckets(sized, bucket_nbytes)
    if len(buckets) < 2:
        return named_grads
    # trace-time (host) record of the bucket schedule: bucket index IS
    # the collective launch order XLA derives, so mxtrace can label the
    # in-step allreduces without runtime hooks inside the compiled step
    try:
        from ..observability import events as _events
        sizes = {k: n for k, n in sized}
        _events.emit(
            "counter", name="grad_buckets", n_buckets=len(buckets),
            bucket_nbytes=[sum(sizes.get(k, 0) for k in b)
                           for b in buckets],
            bucket_keys=[len(b) for b in buckets])
    except Exception:
        pass
    out = dict(named_grads)
    prev = None
    for keys in buckets:
        vals = tuple(out[k] for k in keys)
        if prev is None:
            vals = barrier(vals)
        else:
            vals, _ = barrier((vals, prev))
        for k, v in zip(keys, vals):
            out[k] = v
        prev = vals[0]
    return out


# ---------------------------------------------------------------------------
# (3) compile cache
# ---------------------------------------------------------------------------

_CACHE = {}
_CACHE_LOCK = threading.Lock()
_STATS = {"hits": 0, "misses": 0, "lowerings": 0}


def _stable_repr(part):
    """Deterministic textual form of one key component.  Dicts are
    sorted; everything else relies on repr being value-determined
    (shapes, dtypes, strings, numbers, tuples of those)."""
    if isinstance(part, dict):
        return "{" + ",".join(
            "%s:%s" % (_stable_repr(k), _stable_repr(v))
            for k, v in sorted(part.items(), key=lambda kv: str(kv[0]))) + "}"
    if isinstance(part, (list, tuple)):
        return "[" + ",".join(_stable_repr(p) for p in part) + "]"
    return repr(part)


def cache_key(*parts):
    """sha256 over the stable repr of the parts — the one keying rule
    every cached artifact (trainer jit, executor program) shares."""
    h = hashlib.sha256()
    for part in parts:
        h.update(_stable_repr(part).encode("utf-8", "replace"))
        h.update(b"\x00")
    return h.hexdigest()


def graph_fingerprint(symbol):
    """Graph hash from the canonical ``Symbol.tojson`` serialization —
    the same deterministic topo-ordered JSON the MXL lint passes key
    on, so two structurally identical Symbols (e.g. a bucketing
    module's per-bucket re-bind of the same net) collide on purpose."""
    return hashlib.sha256(
        symbol.tojson().encode("utf-8")).hexdigest()


def abstract_fingerprint(tree):
    """Stable string over a pytree of abstract values: shapes, dtypes,
    and shardings — exactly what decides whether a lowered artifact is
    reusable."""
    try:
        import jax
        leaves = jax.tree_util.tree_leaves(tree)
    except Exception:
        leaves = tree if isinstance(tree, (list, tuple)) else [tree]
    parts = []
    for leaf in leaves:
        parts.append("%s%s/%s" % (
            getattr(leaf, "shape", None), getattr(leaf, "dtype", None),
            getattr(leaf, "sharding", None)))
    return ";".join(parts)


def rules_fingerprint(rules):
    """Value-determined form of a ShardingRules (or None): regex
    patterns + rule-fn qualnames.  Default object repr would embed the
    instance id and spuriously MISS for logically identical rules."""
    if rules is None:
        return "none"
    try:
        return ";".join(
            "%s->%s" % (prog.pattern,
                        getattr(fn, "__qualname__", repr(fn)))
            for prog, fn in rules._rules)
    except Exception:
        return repr(rules)


def optimizer_fingerprint(optimizer):
    """Class name + every scalar hyperparameter, sorted.  The trainer
    closures bake hypers as compile-time constants, so two optimizers
    differing in any scalar must MISS the cache."""
    if optimizer is None:
        return "none"
    attrs = []
    for k in sorted(vars(optimizer)) if hasattr(optimizer, "__dict__") \
            else []:
        v = getattr(optimizer, k, None)
        if isinstance(v, (int, float, bool, str, type(None))):
            attrs.append("%s=%r" % (k, v))
    return "%s(%s)" % (type(optimizer).__name__, ",".join(attrs))


def compile_cache_get(key):
    with _CACHE_LOCK:
        hit = _CACHE.get(key)
        if hit is not None:
            _STATS["hits"] += 1
        else:
            _STATS["misses"] += 1
        return hit


def compile_cache_put(key, value):
    with _CACHE_LOCK:
        _CACHE[key] = value


def note_lowering(n=1):
    """Count one fresh trace/lower — the thing the cache exists to
    avoid; tests assert this stays flat across a second identical
    bind.  The retrace sentry (``observability.retrace``,
    ``MXTPU_RETRACE_SENTRY=1``) wraps this function: after a serving
    warmup boundary every call is counted as a contract violation and
    attributed to the divergent cache-key ingredient."""
    with _CACHE_LOCK:
        _STATS["lowerings"] += n


def note_hit(n=1):
    """Count a cache hit recorded outside compile_cache_get (the
    executor's program registry keeps its own table but shares these
    counters so one stats call covers both caches)."""
    with _CACHE_LOCK:
        _STATS["hits"] += n


def compile_cache_stats():
    with _CACHE_LOCK:
        return dict(_STATS)


def compile_cache_clear():
    with _CACHE_LOCK:
        _CACHE.clear()
        for k in _STATS:
            _STATS[k] = 0


#: <checkout>/.jax_cache — computed from this file, never from the cwd:
#: the directory is part of JAX's cache key, so one that moves never hits
_CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_persistent_cache():
    """Make sure XLA compilations persist on disk, and return where.

    The ONE place the package decides the cache directory (Executor,
    ShardedTrainer and the tools all call it; idempotent).  Whoever runs
    the program steers it from outside: with ``JAX_COMPILATION_CACHE_DIR``
    set JAX already uses that directory and nothing is touched here.
    Unset, the cache lives in ``<checkout>/.jax_cache`` (gitignored).
    A fresh process then skips XLA compilation proper; the in-process
    registry above additionally skips tracing/lowering."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    if jax.config.jax_compilation_cache_dir != _CHECKOUT_CACHE:
        jax.config.update("jax_compilation_cache_dir", _CHECKOUT_CACHE)
    return _CHECKOUT_CACHE
