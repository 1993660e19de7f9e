"""KVStore: parameter synchronization.

TPU-native counterpart of the reference's kvstore stack (``src/kvstore/``,
``python/mxnet/kvstore.py``; SURVEY §2 KVStore rows).  Same string factory
(`kvstore.cc:17-45`) and Python API (init/push/pull/set_updater/rank/
num_workers/barrier/set_optimizer) so user scripts are unchanged, but the
communication design is inverted for TPU:

- The reference moves gradients through an explicit CPU/GPU reduction tree
  (comm.h) or a parameter-server (ps-lite RPC).  On TPU the *fast path* is an
  ``lax.psum`` over the device mesh **inside the compiled training step**
  (``parallel/``); this module is (a) the API-compatible host-side store used
  by Module/FeedForward when ``update_on_kvstore`` and by the kvstore unit
  tests, and (b) the factory that tells the trainer which collective scope
  ('device' = chips in this process, 'dist*' = whole pod) to psum over.
- ``dist_sync`` worker identity comes from ``jax.distributed`` /
  ``jax.process_index()`` (the ps-lite scheduler/rendezvous equivalent,
  SURVEY §2.10) instead of DMLC_ROLE env + ps-lite.  ``dist_async`` has no
  ICI analog (SURVEY §5 "Distributed communication backend"): we accept the
  type and run it with dist_sync semantics, documented divergence.

Aggregation math runs as one jitted XLA computation per shape (tree-sum +
assign), not per-pair engine ops.
"""
from __future__ import annotations

import pickle
import time

import jax
import jax.numpy as jnp

from .base import MXNetError, collective_seam
from .ndarray import NDArray

__all__ = ["KVStore", "create"]


@jax.jit
def _tree_sum(values):
    out = values[0]
    for v in values[1:]:
        out = out + v
    return out


def _key_list(key):
    if isinstance(key, (int, str)):
        return [key], True
    return list(key), False


def _group_values(keys, values, single):
    """Normalize values to one list-of-NDArray per key (kvstore_local.h
    GroupKVPairs analog)."""
    if single:
        if isinstance(values, NDArray):
            return [[values]]
        return [list(values)]
    if len(values) == len(keys) and all(
            isinstance(v, NDArray) for v in values):
        return [[v] for v in values]
    if len(values) % len(keys) == 0 and all(
            isinstance(v, NDArray) for v in values):
        # flat list, len = num_keys * num_devices, reference grouping
        per = len(values) // len(keys)
        return [values[i * per:(i + 1) * per] for i in range(len(keys))]
    out = []
    for v in values:
        out.append([v] if isinstance(v, NDArray) else list(v))
    assert len(out) == len(keys)
    return out


class KVStore(object):
    """Host-side key-value store (parity: python/mxnet/kvstore.py KVStore).

    Semantics matched to the reference's local store:
    - ``init`` sets the initial weight once per key (rank 0 broadcast in dist).
    - ``push`` sums the pushed copies (the multi-device gradient reduce),
      then either runs the updater on (merged_grad, stored_weight) or
      *assigns* the merged value to the store (default updater is assign,
      kvstore_local.h).
    - ``pull`` broadcasts the stored weight into every out array.
    """

    def __init__(self, kvtype="local"):
        self.type = kvtype
        self._store = {}
        self._updater = None
        self._barrier_before_exit = True
        self._created = _now()
        self._dead_hold = {"last": [], "since": None}  # KV-blip hold
        self._ar_seq = 0         # kv-fallback allreduce round counter
        self._async = None       # lazy overlap.AsyncLauncher (push_async)
        self._bucket = []        # pending (key, merged) grads
        self._bucket_nbytes = 0

    # -- identity (include/mxnet/kvstore.h:222-241) -----------------------
    @property
    def rank(self):
        if self.type.startswith("dist"):
            return jax.process_index()
        return 0

    @property
    def num_workers(self):
        if self.type.startswith("dist"):
            return jax.process_count()
        return 1

    # -- core ops ----------------------------------------------------------
    def init(self, key, value):
        keys, single = _key_list(key)
        groups = _group_values(keys, value, single)
        for k, vals in zip(keys, groups):
            if k in self._store:
                raise MXNetError("key %r already initialized" % (k,))
            # init() happens-before any push/pull: the async FIFO
            # worker only sees _store after a later submit()
            # mxl: thread-shared-ok (MXL-Q001)
            self._store[k] = NDArray(vals[0].data)

    def push(self, key, value, priority=0):
        keys, single = _key_list(key)
        groups = _group_values(keys, value, single)
        for k, vals in zip(keys, groups):
            if k not in self._store:
                raise MXNetError("key %r not initialized" % (k,))
            merged = vals[0].data if len(vals) == 1 else \
                _tree_sum([v.data for v in vals])
            merged = self._allreduce(merged)
            if self._updater is not None:
                self._updater(k, NDArray(merged), self._store[k])
            else:
                self._store[k]._set_data(merged)

    def pull(self, key, out=None, priority=0):
        assert out is not None
        keys, single = _key_list(key)
        groups = _group_values(keys, out, single)
        for k, outs in zip(keys, groups):
            if k not in self._store:
                raise MXNetError("key %r not initialized" % (k,))
            src = self._store[k].data
            for o in outs:
                o._set_data(src)

    # -- async + bucketed push (docs/perf.md "Overlap") --------------------
    def push_async(self, key, value, priority=0):
        """:meth:`push` that returns before the cross-worker reduce.

        The per-device merge runs inline (cheap, and it frees the
        caller's grad buffers for donation), then the merged gradient
        joins the pending BUCKET.  A bucket flushes — one fused
        allreduce + the per-key updater, on a single background worker
        — as soon as its size crosses ``MXTPU_BUCKET_MB``, so early
        keys' collectives run while the caller is still merging later
        keys.  Call :meth:`wait_all` before reading the store back
        (``pull``).  Push order, bucket layout, and flush order are
        functions of (key order, shapes, dtypes) only — identical on
        every rank, so the collective schedule cannot diverge."""
        keys, single = _key_list(key)
        groups = _group_values(keys, value, single)
        for k, vals in zip(keys, groups):
            if k not in self._store:
                raise MXNetError("key %r not initialized" % (k,))
            merged = vals[0].data if len(vals) == 1 else \
                _tree_sum([v.data for v in vals])
            self._bucket_add(k, merged)

    def wait_all(self, timeout=None):
        """Barrier for every outstanding :meth:`push_async`: flush the
        partial tail bucket, then block until the worker drained the
        queue (re-raising the first failure).  The store is only
        guaranteed consistent for ``pull`` after this returns."""
        self._flush_bucket()
        if self._async is not None:
            self._async.wait_all(
                timeout if timeout is not None else _collective_timeout_s())

    def _bucket_add(self, k, merged):
        from .parallel.overlap import bucket_bytes
        target = bucket_bytes()
        nbytes = int(getattr(merged, "nbytes", 0) or 0)
        # only same-dtype grads fuse into one flat collective
        if self._bucket and (target <= 0
                             or self._bucket[-1][1].dtype != merged.dtype
                             or self._bucket_nbytes + nbytes > target):
            self._flush_bucket()
        self._bucket.append((k, merged))
        self._bucket_nbytes += nbytes
        if target <= 0 or self._bucket_nbytes >= target:
            self._flush_bucket()

    def _flush_bucket(self):
        items, self._bucket, self._bucket_nbytes = self._bucket, [], 0
        if not items:
            return
        if self._async is None:
            from .parallel.overlap import AsyncLauncher
            self._async = AsyncLauncher(name="kv-async")
        self._async.submit(lambda: self._bucket_allreduce(items))

    @collective_seam
    def _bucket_allreduce(self, items):
        """One bucket's worth of work, on the async worker: fuse the
        merged grads into a single flat tensor, allreduce ONCE, split
        back, apply the updater per key.  Elementwise sums are
        unchanged by the concatenation, so results are bit-identical
        to the per-key path.  Runs strictly FIFO on one worker thread:
        every rank executes the same collectives in the same order."""
        if len(items) == 1:
            k, merged = items[0]
            self._apply_merged(k, self._allreduce(merged))
            return
        flats = [jnp.ravel(m) for _, m in items]
        fused = self._allreduce(jnp.concatenate(flats))
        offset = 0
        for k, merged in items:
            size = int(merged.size)
            part = jax.lax.dynamic_slice_in_dim(fused, offset, size)
            self._apply_merged(k, jnp.reshape(part, merged.shape))
            offset += size

    def _apply_merged(self, k, merged):
        if self._updater is not None:
            self._updater(k, NDArray(merged), self._store[k])
        else:
            self._store[k]._set_data(merged)

    def _allreduce(self, merged):
        """Cross-worker gradient sum for dist types.

        With one process this is the identity; in a multi-host pod each
        worker's tensor becomes one shard of a global array and a jitted
        sum reduces it — XLA runs the actual all-reduce over ICI/DCN, so
        no host ever materializes num_workers copies (the criticism of
        the old process_allgather path).  The *performant* pod path never
        calls this at all: Module folds the psum into the compiled step
        (update_on_kvstore=False ≡ in-step update, SURVEY §5 mapping).
        """
        if not (self.type.startswith("dist") and jax.process_count() > 1):
            return merged
        from .observability import spans as _spans, events as _events
        from .observability import trace as _trace, flight as _flight
        nbytes = getattr(merged, "nbytes", None)
        timeout = _collective_timeout_s()
        # rank-uniform sequence number: @collective_seam guarantees every
        # rank launches its collectives in the same order, so (op, seq)
        # names ONE pod-wide collective — the handle the flight-recorder
        # ledger and mxtrace's cross-rank flow stitching key on
        seq = _trace.next_seq("allreduce")
        _flight.collective_begin(
            "allreduce", seq, participants=list(range(self.num_workers)),
            bytes=nbytes, rank=self.rank)
        t0 = time.perf_counter()
        with _spans.span("allreduce"):
            if timeout:
                # a peer that died mid-push leaves everyone else wedged
                # in the collective forever; the watchdog bounds that to
                # a structured abort + restart (docs/resilience.md)
                from .resilience import run_with_timeout
                out = run_with_timeout(
                    lambda: self._allreduce_dist(merged), timeout,
                    phase="kvstore_push", rank=self.rank)
            else:
                out = self._allreduce_dist(merged)
        # only a COMPLETED collective leaves the pending ledger: on the
        # exception path the entry survives into the flight dump, naming
        # the hung (op, seq) for the postmortem
        _flight.collective_end("allreduce", seq)
        _events.emit("collective", op="allreduce", seq=seq, bytes=nbytes,
                     dur_ms=round((time.perf_counter() - t0) * 1e3, 3),
                     num_workers=self.num_workers, **_trace.ids())
        return out

    @collective_seam
    def _allreduce_dist(self, merged):
        # Pick the path ONCE, cluster-wide.  A per-process probe could
        # split workers between two different collectives and deadlock the
        # pod (probe failing on a subset), so rank 0 probes and publishes
        # the verdict through the coordination-service KV (the same
        # channel the heartbeats use); every other rank reads that single
        # decision before its first allreduce.
        enabled = _CSUM_CACHE.get("enabled")
        if enabled is None:
            enabled = self._decide_csum_path()
            _CSUM_CACHE["enabled"] = enabled
        if enabled:
            return _collective_sum(merged)
        return self._kv_allreduce(merged)

    @collective_seam
    def _kv_allreduce(self, merged):
        """Backend-free gradient sum through the coordination-service KV.

        Used when the compile-only probe says the backend cannot build
        cross-process XLA programs at all (multi-process CPU — where
        the resilience drills run — rejects them, and so does the
        process_allgather fallback, which is itself a jitted
        multi-process computation).  Each rank publishes its tensor
        under a per-round key and sums everyone's; string RPC only, so
        it works on any backend.  Slow — a correctness/testing path,
        never the pod fast path (that is the in-step psum)."""
        client = _dist_client()
        if client is None:
            return merged
        import numpy as _onp
        seq = self._ar_seq
        # allreduce runs either inline or on the single async FIFO
        # worker, never both at once — the mode is fixed per store
        # mxl: thread-shared-ok (MXL-Q001)
        self._ar_seq += 1
        host = _onp.asarray(jax.device_get(merged))
        client.key_value_set("mxtpu_ar/%d/%d" % (seq, self.rank),
                             _encode_array(host), allow_overwrite=True)
        timeout_ms = int((_collective_timeout_s() or 600.0) * 1000.0)
        total = None
        for r in range(self.num_workers):
            a = host if r == self.rank else _decode_array(
                client.blocking_key_value_get(
                    "mxtpu_ar/%d/%d" % (seq, r), timeout_ms))
            total = a if total is None else total + a
        # clear this rank's round-(seq-2) key: every peer finished round
        # seq-1 (which required reading this rank's seq-2 round first)
        # before it could contribute to the current round
        if seq >= 2:
            try:
                client.key_value_delete(
                    "mxtpu_ar/%d/%d" % (seq - 2, self.rank))
            except Exception:
                pass
        return jnp.asarray(total)

    @staticmethod
    @collective_seam
    def _decide_csum_path():
        """Cluster-wide collective-vs-allgather decision: rank 0 probes the
        XLA collective and publishes the verdict in the coordination KV;
        every rank acts on that one answer (never a local probe that could
        diverge across workers)."""
        import logging
        client = _dist_client()
        key = "mxtpu_csum/enabled"
        if client is not None and jax.process_index() != 0:
            # retry the read, then fail LOUDLY: guessing here could put
            # this rank in a different collective than the rest of the
            # pod — a silent permanent hang, the exact bug this
            # cluster-wide decision exists to eliminate
            last_exc = None
            for timeout_ms in (60_000, 240_000):
                try:
                    val = client.blocking_key_value_get(key, timeout_ms)
                    return val == "1"
                except Exception as exc:  # noqa: BLE001
                    last_exc = exc
            raise MXNetError(
                "kvstore: could not read rank-0's collective-path verdict "
                "(%r); refusing to guess (a wrong guess deadlocks the pod)"
                % (last_exc,))
        try:
            # compile-only probe: executing the collective needs every
            # rank, but lowering+compiling the program is local, and it is
            # the compile step that surfaces backend/version asymmetry
            _compile_collective_sum_probe()
            enabled = True
        except Exception as exc:  # noqa: BLE001
            logging.warning(
                "kvstore: XLA collective sum unavailable (%r); the cluster "
                "will use the coordination-service KV fallback", exc)
            enabled = False
        if client is not None:
            try:
                client.key_value_set(key, "1" if enabled else "0",
                                     allow_overwrite=True)
            except Exception:
                pass
        return enabled

    # -- updater / optimizer ----------------------------------------------
    def set_updater(self, updater):
        """Parity: kvstore.py _set_updater."""
        # configured before training pushes work onto the async FIFO;
        # a later swap takes effect on the next submitted bucket
        # mxl: thread-shared-ok (MXL-Q001)
        self._updater = updater

    _set_updater = set_updater

    def set_optimizer(self, optimizer):
        """Parity: kvstore.py:231 set_optimizer — in the reference this
        pickles the optimizer to PS servers (command 0); on TPU there are no
        servers, so the updater runs in-process (≡ server-side update)."""
        from .optimizer import get_updater
        # round-trip through pickle to preserve the reference's contract that
        # the optimizer must be serializable for the server
        optimizer = pickle.loads(pickle.dumps(optimizer))
        self.set_updater(get_updater(optimizer))

    # -- fault surface (kvstore.h:242 get_num_dead_node parity) ------------
    def dead_nodes(self, node_id=None, timeout=None):
        """Sorted ranks whose liveness heartbeat is stale/missing.

        The identity-bearing form of :meth:`num_dead_nodes`: the
        elastic re-mesh protocol (``resilience.elastic``) needs to know
        WHICH workers died to propose the survivor membership, and
        ``mxtop`` wants names, not a count.  Every dist worker runs a
        heartbeat thread stamping ``mxtpu_hb/<rank>`` in the jax
        coordination service (started by ``create('dist_*')``); this is
        a non-blocking key scan, safe to call while peers are down.

        ``node_id`` narrows the check to one rank (None = all workers).
        ``timeout`` defaults to 5 heartbeat intervals — enough slack
        for RPC jitter and modest cross-host clock skew.  Returns
        ``[]`` for non-dist stores.

        "KV unreachable" is NOT "ranks dead": while the coordination
        service itself does not answer, this holds the last verdict
        for up to ``timeout`` seconds (a blip must not fabricate
        deaths), then re-raises the structured
        :class:`~mxnet_tpu.resilience.netkv.KVUnreachable` so restart
        watchdogs fire on the real condition — a lost coordination
        plane — rather than reading every rank as dead.  Injected
        ``dead_node`` faults report the highest ``n`` ranks
        (synthesized identities — the injector knows a count, not
        names).
        """
        if timeout is None:
            timeout = 5 * _HB_INTERVAL
        if not self.type.startswith("dist"):
            return []
        from .resilience.faultinject import maybe_fault
        spec = maybe_fault("dead_node")
        if spec is not None and spec.kind == "dead_node":
            # synthesize exactly n identities even when the injected
            # count exceeds the real world (single-process tests assert
            # the count the spec asked for)
            world = max(self.num_workers, int(spec.n))
            fake = list(range(world))[-int(spec.n):] \
                if int(spec.n) > 0 else []
            if node_id is not None:
                return [r for r in fake if r == node_id]
            return fake
        client = _dist_client()
        if client is None:
            return []
        ranks = [node_id] if node_id is not None \
            else range(self.num_workers)
        from .resilience.netkv import KVUnreachable
        try:
            dead = scan_dead_ranks(client, ranks, self._created,
                                   timeout)
        except KVUnreachable:
            since = self._dead_hold["since"]
            if since is None:
                since = _now()
                self._dead_hold["since"] = since
            if _now() - since <= timeout:
                held = self._dead_hold["last"]
                return [r for r in held if r == node_id] \
                    if node_id is not None else list(held)
            raise                   # outage outlived the grace window
        self._dead_hold["since"] = None
        if node_id is None:
            self._dead_hold["last"] = list(dead)
        return dead

    def num_dead_nodes(self, node_id=None, timeout=None):
        """Count of stale workers (parity:
        ``KVStore::get_num_dead_node(node_id, timeout)``,
        include/mxnet/kvstore.h:242, impl kvstore_dist.h:149-158 over
        ps-lite heartbeats).  Thin wrapper over :meth:`dead_nodes` —
        same liveness scan, identities dropped."""
        return len(self.dead_nodes(node_id=node_id, timeout=timeout))

    get_num_dead_node = num_dead_nodes

    # -- misc --------------------------------------------------------------
    def barrier(self):
        """Global worker barrier (parity kvstore.h:249; ps Postoffice barrier).

        Under ``MXTPU_STEP_TIMEOUT_S`` a barrier a dead peer will never
        join raises :class:`~mxnet_tpu.resilience.ResilienceError`
        instead of hanging forever."""
        if self.type.startswith("dist") and jax.process_count() > 1:
            timeout = _collective_timeout_s()

            def _sync():
                global_barrier("kv_barrier", timeout_s=timeout)

            from .observability import spans as _spans
            from .observability import trace as _trace, flight as _flight
            seq = _trace.next_seq("barrier")
            _flight.collective_begin(
                "barrier", seq,
                participants=list(range(self.num_workers)),
                rank=self.rank)
            with _spans.span("kv_barrier"):
                if timeout:
                    from .resilience import run_with_timeout
                    run_with_timeout(_sync, timeout,
                                     phase="kvstore_barrier",
                                     rank=self.rank)
                else:
                    _sync()
            _flight.collective_end("barrier", seq)

    def _barrier(self):
        self.barrier()

    def _send_command_to_servers(self, head, body):
        """No servers on TPU; commands are accepted and ignored (kSyncMode
        etc. are implicit in the collective design)."""

    def save_optimizer_states(self, fname):
        if self._updater is None:
            raise MXNetError("Cannot save states for distributed training")
        with open(fname, "wb") as fout:
            opt = getattr(self._updater, "optimizer", None)
            states = getattr(self._updater, "states", None)
            fout.write(pickle.dumps((opt, _states_to_host(states))))

    def load_optimizer_states(self, fname):
        with open(fname, "rb") as fin:
            opt, states = pickle.loads(fin.read())
        from .optimizer import get_updater
        updater = get_updater(opt)
        if states:
            updater.states.update(_states_from_host(states))
        self.set_updater(updater)


def _states_to_host(states):
    if states is None:
        return None
    return {k: jax.tree_util.tree_map(
        lambda a: a.asnumpy() if isinstance(a, NDArray) else a, v)
        for k, v in states.items()}


def _states_from_host(states):
    return {k: jax.tree_util.tree_map(
        lambda a: NDArray(a) if a is not None else None, v)
        for k, v in states.items()}


_HB_PREFIX = "mxtpu_hb/"
_HB_INTERVAL = 2.0


def scan_dead_ranks(client, ranks, created, timeout, prefix=_HB_PREFIX):
    """Sorted members of ``ranks`` whose ``<prefix><rank>`` heartbeat
    stamp is stale or missing — the liveness scan shared by
    :meth:`KVStore.dead_nodes` (jax coordination client) and the fleet
    serving router (any ``resilience.netkv.CoordKV``).  ``client`` is
    anything with ``key_value_dir_get``; ``created`` is the scanner's
    own start time (missing stamps only count as dead once the peer has
    had ``timeout`` seconds since then to write one — the startup-grace
    rule).

    An unreachable KV raises a structured
    :class:`~mxnet_tpu.resilience.netkv.KVUnreachable` — it NEVER
    reports ranks dead.  "The coordination plane did not answer" says
    nothing about any rank; translating it into deaths is how a
    2-second network blip becomes a fleet-wide shrink.  Callers hold
    their last verdict within their grace window and escalate past it
    (docs/resilience.md "KV fault discipline")."""
    try:
        entries = dict(client.key_value_dir_get(prefix))
    except Exception as exc:
        from .resilience.netkv import KVUnreachable
        if isinstance(exc, KVUnreachable):
            raise
        try:
            from . import observability as _obs
            _obs.emit("fault", fault="kv_unreachable", op="dir",
                      backend=type(client).__name__, error=repr(exc))
        except Exception:
            pass
        raise KVUnreachable(
            "heartbeat scan: kv backend %s unreachable: %r"
            % (type(client).__name__, exc), op="dir")
    now = _now()
    dead = []
    for r in ranks:
        stamp = entries.get("%s%d" % (prefix, r))
        if stamp is None:
            if now - created > timeout:
                dead.append(r)
        elif now - float(stamp) > timeout:
            dead.append(r)
    return sorted(dead)


_CSUM_CACHE = {}


def _now():
    """Wall clock behind the liveness math — module-level so tests can
    monkeypatch it to step time deterministically."""
    import time as _time
    return _time.time()


def _collective_timeout_s():
    """Watchdog timeout for kvstore collectives (MXTPU_STEP_TIMEOUT_S)."""
    from .resilience import step_timeout_s
    return step_timeout_s()


_BARRIER_STATE = {"xla_ok": None, "seq": {}}


@collective_seam
def _decide_barrier_path():
    """Cluster-wide XLA-vs-RPC barrier decision, mirroring
    ``_decide_csum_path``: rank 0 compile-probes the cross-process
    collective (local, no execution) and publishes the verdict in the
    coordination KV; every rank acts on that one answer.  A local
    run-and-see probe is banned here: a transient first-call failure
    (e.g. a timeout caused by one dead or slow peer) would flip only
    the probing rank to the RPC barrier while its peers keep fencing
    on XLA — a permanent pod deadlock."""
    import logging
    client = _dist_client()
    key = "mxtpu_barrier/xla_ok"
    if client is not None and jax.process_index() != 0:
        last_exc = None
        for timeout_ms in (60_000, 240_000):
            try:
                return client.blocking_key_value_get(key, timeout_ms) == "1"
            except Exception as exc:  # noqa: BLE001
                last_exc = exc
        raise MXNetError(
            "kvstore: could not read rank-0's barrier-path verdict (%r); "
            "refusing to guess (a wrong guess deadlocks the pod)"
            % (last_exc,))
    try:
        # the backends that reject sync_global_devices are exactly the
        # ones that cannot compile cross-process XLA programs at all
        # (multi-process CPU, where the resilience drills run)
        _compile_collective_sum_probe()
        ok = True
    except Exception as exc:  # noqa: BLE001
        logging.warning(
            "kvstore: XLA device barrier unavailable (%r); the cluster "
            "will fence via the coordination-service barrier RPC", exc)
        ok = False
    if client is not None:
        try:
            client.key_value_set(key, "1" if ok else "0",
                                 allow_overwrite=True)
        except Exception:
            pass
    return ok


@collective_seam
def global_barrier(tag, timeout_s=None):
    """Cross-process barrier that works on any backend.

    Prefers ``sync_global_devices`` (a device-level fence); backends
    that cannot run multi-process XLA programs fall back to the
    coordination-service ``wait_at_barrier`` RPC.  The choice is made
    ONCE, cluster-wide (rank 0 probes and publishes), so no rank can
    end up in a different barrier implementation than its peers — and
    once made, failures of the chosen barrier propagate to the caller
    instead of silently switching paths.
    """
    if jax.process_count() <= 1:
        return
    if _BARRIER_STATE["xla_ok"] is None:
        _BARRIER_STATE["xla_ok"] = _decide_barrier_path()
    if _BARRIER_STATE["xla_ok"]:
        from jax.experimental import multihost_utils
        multihost_utils.sync_global_devices("mxtpu_" + tag)
        return
    client = _dist_client()
    if client is None:
        return
    n = _BARRIER_STATE["seq"].get(tag, 0) + 1
    _BARRIER_STATE["seq"][tag] = n
    timeout_ms = int((timeout_s or 600.0) * 1000.0)
    client.wait_at_barrier("mxtpu_%s_%d" % (tag, n), timeout_ms)


def _encode_array(arr):
    """Array -> coordination-KV string: `dtype|shape|base64(bytes)`."""
    import base64
    import numpy as _onp
    arr = _onp.asarray(arr)
    shape = ",".join(str(d) for d in arr.shape)
    return "%s|%s|%s" % (arr.dtype.str, shape,
                         base64.b64encode(arr.tobytes(order="C")).decode("ascii"))


def _decode_array(text):
    import base64
    import numpy as _onp
    dtype, shape, payload = text.split("|", 2)
    shape = tuple(int(d) for d in shape.split(",")) if shape else ()
    buf = base64.b64decode(payload)
    return _onp.frombuffer(buf, dtype=_onp.dtype(dtype)).reshape(shape)


@collective_seam
def _collective_sum(value):
    """Sum ``value`` across processes with an XLA collective: each
    process's tensor is one shard of a (n_proc, ...) global array; a
    jitted sum over the worker axis lowers to an all-reduce."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    if "mesh" not in _CSUM_CACHE:
        mesh = _csum_mesh()
        # idempotent memo: a concurrent double-build computes the same
        # mesh/jit twice, last write wins harmlessly
        # mxl: thread-shared-ok (MXL-Q001)
        _CSUM_CACHE["mesh"] = mesh
        _CSUM_CACHE["sum"] = jax.jit(
            lambda x: jnp.sum(x, axis=0),
            out_shardings=NamedSharding(mesh, P()))
    mesh = _CSUM_CACHE["mesh"]
    value = jnp.asarray(value)
    sharding = NamedSharding(mesh, P("w", *([None] * value.ndim)))
    garr = jax.make_array_from_process_local_data(sharding, value[None])
    out = _CSUM_CACHE["sum"](garr)
    # replicated over the mesh: this process's addressable copy
    return jnp.asarray(out.addressable_data(0))


def _csum_mesh():
    """One-device-per-process mesh used by the cross-worker sum."""
    from jax.sharding import Mesh
    import numpy as _onp

    per_proc = {}
    for d in jax.devices():
        per_proc.setdefault(d.process_index, d)
    devs = [per_proc[p] for p in sorted(per_proc)]
    return Mesh(_onp.asarray(devs), ("w",))


def _compile_collective_sum_probe():
    """AOT-compile (but do not run) the cross-worker sum program.  Raises
    on any backend that cannot lower the collective; safe to call on one
    rank because no execution happens."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = _csum_mesh()
    fn = jax.jit(lambda x: jnp.sum(x, axis=0),
                 out_shardings=NamedSharding(mesh, P()))
    shape = jax.ShapeDtypeStruct(
        (len(mesh.devices), 1), jnp.float32,
        sharding=NamedSharding(mesh, P("w", None)))
    fn.lower(shape).compile()


def _dist_client():
    """The jax coordination-service client, or None."""
    try:
        from jax._src import distributed as _dist
        return _dist.global_state.client
    except Exception:
        return None


_HB_STATE = {"thread": None, "stop": None}


def _start_heartbeat(client=None, rank=None):
    """Background liveness stamping for num_dead_nodes (ps-lite heartbeat
    analog).  Idempotent per process; the thread is a daemon AND is
    stopped via atexit, so interpreter shutdown can neither hang joining
    it nor race it against a torn-down coordination client.

    ``client``/``rank`` default to the jax coordination service and
    ``jax.process_index()``; fleet serving replicas inject their own
    file-backed KV client and replica index so the SAME stamping/scan
    machinery tracks replica liveness without a jax.distributed pod."""
    t = _HB_STATE["thread"]
    if t is not None and t.is_alive():
        return
    if client is None:
        client = _dist_client()
    if client is None:
        return
    import atexit
    import threading
    import time as _time
    if rank is None:
        rank = jax.process_index()
    key = "%s%d" % (_HB_PREFIX, int(rank))
    stop = threading.Event()

    def _beat():
        while not stop.is_set():
            try:
                client.key_value_set(key, repr(_time.time()),
                                     allow_overwrite=True)
            except Exception:
                # KV blip (partition, flap, coordinator restart):
                # keep trying — a thread that exits here never stamps
                # again, so a healed 5 s partition would read as this
                # rank dead forever after.  A genuinely torn-down
                # cluster ends the loop via the stop event instead.
                pass
            # Event.wait, not sleep: _stop_heartbeat returns promptly
            # instead of waiting out the remainder of an interval
            stop.wait(_HB_INTERVAL)

    t = threading.Thread(target=_beat, daemon=True,
                         name="mxtpu-kv-heartbeat")
    t.start()
    if _HB_STATE["thread"] is None:          # register atexit hook once
        atexit.register(_stop_heartbeat)
    _HB_STATE["thread"] = t
    _HB_STATE["stop"] = stop


def _stop_heartbeat():
    """Signal the heartbeat thread to exit and wait (bounded) for it."""
    t, stop = _HB_STATE["thread"], _HB_STATE["stop"]
    if stop is not None:
        stop.set()
    if t is not None and t.is_alive():
        t.join(2 * _HB_INTERVAL)
    _HB_STATE["thread"] = None
    _HB_STATE["stop"] = None


_VALID_TYPES = ("local", "local_update_cpu", "local_allreduce_cpu",
                "local_allreduce_device", "device",
                "dist_sync", "dist_async", "dist_sync_device",
                "dist_async_device")


def _maybe_init_distributed():
    """Join the jax.distributed cluster described by tools/launch.py's env
    contract (MXTPU_COORDINATOR / MXTPU_NUM_WORKERS / MXTPU_WORKER_RANK).

    The ps-lite rendezvous analog (SURVEY §3.4): the reference reads
    DMLC_PS_ROOT_URI + DMLC_ROLE and dials the scheduler; here every worker
    dials the jax coordinator (process 0).  No-op when the env vars are
    absent (single-process dist, used by unit tests) or when the cluster is
    already initialized (e.g. by user code on a TPU pod).
    """
    import os
    coord = os.environ.get("MXTPU_COORDINATOR")
    if not coord:
        return
    # elastic generation fence BEFORE dialing (docs/resilience.md):
    # a straggler from a superseded incarnation must exit for restart,
    # not join (or corrupt the rendezvous of) the new pod
    from .resilience import elastic
    elastic.check_generation_fence()
    if getattr(_maybe_init_distributed, "_done", False):
        return
    if jax.distributed.is_initialized():
        _maybe_init_distributed._done = True
        return
    missing = [k for k in ("MXTPU_NUM_WORKERS", "MXTPU_WORKER_RANK")
               if k not in os.environ]
    if missing:
        raise MXNetError(
            "partially-configured distributed launch: MXTPU_COORDINATOR is "
            "set but %s %s missing. tools/launch.py exports all three "
            "(MXTPU_COORDINATOR, MXTPU_NUM_WORKERS, MXTPU_WORKER_RANK); "
            "set them together or unset MXTPU_COORDINATOR for single-"
            "process mode." % (" and ".join(missing),
                               "is" if len(missing) == 1 else "are"))
    try:
        # rendezvous is the one retryable distributed phase: a worker
        # routinely dials before the coordinator is listening.  Retry
        # transient connect/deadline failures with backoff; anything
        # deterministic (bad config) propagates on the first attempt.
        from .resilience import RetryPolicy, retry_call
        retry_call(
            lambda: jax.distributed.initialize(
                coordinator_address=coord,
                num_processes=int(os.environ["MXTPU_NUM_WORKERS"]),
                process_id=int(os.environ["MXTPU_WORKER_RANK"])),
            policy=RetryPolicy(), phase="jax.distributed.initialize")
    except RuntimeError as exc:
        raise MXNetError(
            "kvstore.create('dist_*') must run before any jax/NDArray "
            "work in a launched worker (jax.distributed.initialize needs "
            "an uninitialized backend): %s" % exc)
    _maybe_init_distributed._done = True


def create(name="local"):
    """String factory (parity: kvstore.cc:17-45 + kvstore.py:360 create)."""
    if not isinstance(name, str):
        raise TypeError("name must be a string")
    base = name.lower()
    if base not in _VALID_TYPES and not any(
            t in base for t in ("local", "device", "dist")):
        raise MXNetError("unknown KVStore type %r" % name)
    if base.startswith("dist"):
        _maybe_init_distributed()
        _start_heartbeat()
    store = KVStore(base)
    if base.startswith("dist"):
        # teach the flight recorder who is alive: a hung-collective dump
        # can then say which participant never showed up, not just that
        # seq K is stuck (the heartbeat scan is non-blocking)
        try:
            from .observability import flight as _flight
            _flight.set_liveness_probe(lambda: store.dead_nodes())
        except Exception:
            pass
    return store
