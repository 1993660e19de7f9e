"""Block-paged KV cache for generative serving (PagedAttention-style).

The decode-side memory manager: keys/values for every active sequence
live in fixed-size **blocks** inside one pool per layer, and a
per-sequence **block table** names which pool blocks hold its tokens —
so admitting, growing, and finishing sequences never moves cache bytes
and never changes a compiled program's shapes (vLLM's PagedAttention,
SOSP'23).  Two halves:

- **Device pools** (functional state): per layer one K and one V array
  shaped ``(num_blocks, block_size, num_heads, head_dim)``.  They flow
  through the decode/prefill executors as ordinary inputs and come back
  as outputs (``CachedMultiHeadAttention`` appends via a scatter), so a
  generation step stays jit-pure and the arrays round-trip between
  steps without host copies.
- **Host allocator** (this class): a free list of block ids with
  reserve-at-admission semantics.  A sequence's whole block budget —
  ``ceil((prompt_len + max_new_tokens) / block_size)`` — is claimed
  before the request is queued; insufficient blocks raise
  :class:`CacheExhausted` (structured 429 backpressure carrying
  ``blocks_free``) instead of an allocation failure mid-decode.

Block 0 is the **trash block**: never allocated, never read.  Padded
batch rows and padded prompt positions route their scatter writes to it
so every cache update is a static-shape ``.at[].set`` — no dynamic
masking, no recompiles, and clobbering is harmless by construction.

Tile legality is static: the per-head view of a block is
``(block_size, head_dim)`` — the lane (last) dim covers the full
``head_dim`` array dim (legal at any size; Mosaic pads), and the
sublane dim is ``block_size``, which the default of 32 makes a legal
partial tiling for float32 (8), bfloat16 (16), AND int8 (32) granules.
The layout registers through :func:`~mxnet_tpu.analysis.tiling.
register_kernel_spec` so ``mxlint`` / the MXL-K sweep checks it on
every run — including the int8 variant the quantized tier will want.

Sharding: :func:`cache_sharding_rules` maps ``*_k_cache``/``*_v_cache``
names to ``PartitionSpec(None, None, "tp", None)`` — heads split across
tp ranks, the same seam the head-parallel attention policy uses for
``qkv_weight`` — via the ordered-regex :class:`~mxnet_tpu.parallel.
sharding.ShardingRules` machinery, so a tp>1 mesh splits the pools
without code changes.
"""
from __future__ import annotations

import os as _os
import threading

import numpy as _np

from ..base import MXNetError
from ..analysis.tiling import register_kernel_spec

__all__ = ["KVCacheConfig", "PagedKVCache", "CacheExhausted",
           "kv_blocks", "kv_block_size", "max_new_tokens",
           "cache_kernel_spec", "cache_sharding_rules", "TRASH_BLOCK"]

#: block id reserved as the write target for padded positions/rows;
#: never allocated to a sequence, never read by attention
TRASH_BLOCK = 0


def kv_blocks(explicit=None):
    """Pool size in blocks (``MXTPU_SERVE_KV_BLOCKS``, default 256,
    including the reserved trash block)."""
    if explicit is not None:
        return int(explicit)
    try:
        return int(_os.environ.get("MXTPU_SERVE_KV_BLOCKS", "256"))
    except ValueError:
        return 256


def kv_block_size(explicit=None):
    """Tokens per cache block (``MXTPU_SERVE_KV_BLOCK_SIZE``, default
    32 — the int8 sublane granule, so one setting is tile-legal at
    float32, bfloat16, and int8)."""
    if explicit is not None:
        return int(explicit)
    try:
        return int(_os.environ.get("MXTPU_SERVE_KV_BLOCK_SIZE", "32"))
    except ValueError:
        return 32


def max_new_tokens(explicit=None):
    """Per-request generation cap (``MXTPU_SERVE_MAX_NEW_TOKENS``,
    default 64) — also the decode half of the admission block budget."""
    if explicit is not None:
        return int(explicit)
    try:
        return int(_os.environ.get("MXTPU_SERVE_MAX_NEW_TOKENS", "64"))
    except ValueError:
        return 64


class CacheExhausted(MXNetError):
    """Admission-time block-budget rejection.  Structured like
    :class:`~mxnet_tpu.serving.batcher.ServerBusy` (the server maps it
    to a 429 whose payload carries ``blocks_free``) so cache pressure
    is backpressure, never an OOM mid-flight."""

    def __init__(self, blocks_needed, blocks_free, blocks_total):
        self.blocks_needed = int(blocks_needed)
        self.blocks_free = int(blocks_free)
        self.blocks_total = int(blocks_total)
        super(CacheExhausted, self).__init__(
            "kv cache exhausted: need %d blocks, %d free of %d"
            % (self.blocks_needed, self.blocks_free, self.blocks_total))

    def to_dict(self):
        return {"error": "kv_cache_exhausted",
                "blocks_needed": self.blocks_needed,
                "blocks_free": self.blocks_free,
                "blocks_total": self.blocks_total}


class KVCacheConfig(object):
    """Static shape of one model's cache: pool and table geometry.

    ``max_seq_len`` is the per-sequence ceiling (prompt + generated);
    it fixes the block-table width so every executor shape is static.
    """

    def __init__(self, num_layers, num_heads, head_dim, max_seq_len,
                 num_blocks=None, block_size=None, dtype="float32"):
        self.num_layers = int(num_layers)
        self.num_heads = int(num_heads)
        self.head_dim = int(head_dim)
        self.max_seq_len = int(max_seq_len)
        self.block_size = kv_block_size(block_size)
        self.num_blocks = kv_blocks(num_blocks)
        self.dtype = _np.dtype(dtype)
        if self.block_size < 1 or self.num_blocks < 2:
            raise MXNetError(
                "kv cache needs block_size >= 1 and num_blocks >= 2 "
                "(block 0 is reserved), got block_size=%d num_blocks=%d"
                % (self.block_size, self.num_blocks))
        # fail at config time, not in a Mosaic error on the chip: a
        # partial (block_size, head_dim) tiling needs the sublane dim
        # on the dtype granule (tiling.min_tile)
        from ..analysis.tiling import min_tile
        sub, _lanes = min_tile(self.dtype)
        if self.block_size % sub:
            raise MXNetError(
                "kv block_size %d is not a multiple of the %s sublane "
                "granule %d (MXL-K001)"
                % (self.block_size, self.dtype.name, sub))
        self.blocks_per_seq = -(-self.max_seq_len // self.block_size)

    @property
    def pool_shape(self):
        return (self.num_blocks, self.block_size, self.num_heads,
                self.head_dim)

    def blocks_for(self, n_tokens):
        """Blocks covering ``n_tokens`` cache slots."""
        return max(1, -(-int(n_tokens) // self.block_size))

    def to_dict(self):
        return {"num_layers": self.num_layers,
                "num_heads": self.num_heads, "head_dim": self.head_dim,
                "max_seq_len": self.max_seq_len,
                "block_size": self.block_size,
                "num_blocks": self.num_blocks,
                "blocks_per_seq": self.blocks_per_seq,
                "dtype": self.dtype.name}


def cache_kernel_spec(config=None, dtype=None):
    """MXL-K spec for the paged-cache layout: the per-head view of the
    pool is ``(total_slots, head_dim)`` tiled in ``(block_size,
    head_dim)`` blocks — the exact window a flash-decode kernel would
    declare as its BlockSpec.  ``dtype`` overrides the config's (the CI
    sweep asserts bf16 and int8 legality of the same geometry)."""
    cfg = config or KVCacheConfig(num_layers=1, num_heads=8, head_dim=64,
                                  max_seq_len=kv_block_size() * 4)
    dt = _np.dtype(dtype or cfg.dtype).name
    array = (cfg.num_blocks * cfg.block_size, cfg.head_dim)
    block = (cfg.block_size, cfg.head_dim)
    return {
        "name": "paged_kv_cache[%s]" % dt,
        "origin": "mxnet_tpu/serving/kvcache.py",
        "grid": (cfg.num_blocks,),
        "blocks": [
            {"role": "in", "name": "k_block", "block": block,
             "array": array, "dtype": dt},
            {"role": "in", "name": "v_block", "block": block,
             "array": array, "dtype": dt},
        ],
    }


register_kernel_spec(
    "paged_kv_cache",
    lambda: [cache_kernel_spec(dtype=dt)
             for dt in ("float32", "bfloat16", "int8")])


def cache_sharding_rules(tp_axis="tp", mesh=None):
    """ShardingRules splitting cache pools head-wise over ``tp_axis``
    (pool dim 2) — the SNIPPETS match_partition_rules pattern: ordered
    regexes over array names, first match wins."""
    from jax.sharding import PartitionSpec as P
    from ..parallel.sharding import ShardingRules
    return ShardingRules([
        (r".*_(k|v)_cache$",
         lambda shape, m, _a=tp_axis: P(None, None, _a, None)),
        (r".*block_table$", lambda shape, m: P(*([None] * len(shape)))),
    ], mesh=mesh)


class _Sequence(object):
    __slots__ = ("seq_id", "blocks", "table_row", "n_reserved")

    def __init__(self, seq_id, blocks, table_row):
        self.seq_id = seq_id
        self.blocks = blocks
        self.table_row = table_row
        self.n_reserved = len(blocks)


class PagedKVCache(object):
    """Host-side block allocator + owner of the device pools.

    Thread-safe (the batcher scheduler and the server's admission path
    both touch it).  Pools are plain jax arrays handed to/from the
    executors; :meth:`set_pools` installs the functional update a step
    returned.
    """

    def __init__(self, config, ctx=None, init_pools=True):
        from ..context import cpu
        self.config = config
        #: where the pools live — the device of the executors they feed
        self.ctx = ctx if ctx is not None else cpu()
        self._lock = threading.Lock()
        self._free = list(range(config.num_blocks - 1, TRASH_BLOCK, -1))
        self._seqs = {}
        self._high_water = 0
        self.k_pools = []
        self.v_pools = []
        if init_pools:
            import jax.numpy as jnp
            dev = self.ctx.jax_device
            shape = config.pool_shape
            dt = config.dtype
            for _ in range(config.num_layers):
                self.k_pools.append(jnp.zeros(shape, dtype=dt, device=dev))
                self.v_pools.append(jnp.zeros(shape, dtype=dt, device=dev))

    # -- allocation --------------------------------------------------------

    def blocks_total(self):
        return self.config.num_blocks - 1          # trash block excluded

    def blocks_free(self):
        with self._lock:
            return len(self._free)

    def blocks_used(self):
        with self._lock:
            return self.blocks_total() - len(self._free)

    def can_admit(self, n_tokens):
        with self._lock:
            return self.config.blocks_for(n_tokens) <= len(self._free)

    def allocate(self, seq_id, n_tokens):
        """Reserve the whole ``n_tokens`` block budget for ``seq_id``
        and return its block-table row (``(blocks_per_seq,)`` int32,
        unused slots pointing at the trash block).  Raises
        :class:`CacheExhausted` without side effects when the free list
        is short — admission-time backpressure, so a running decode can
        never hit an out-of-blocks condition."""
        need = self.config.blocks_for(n_tokens)
        if n_tokens > self.config.max_seq_len:
            raise MXNetError(
                "sequence of %d tokens exceeds max_seq_len %d"
                % (n_tokens, self.config.max_seq_len))
        with self._lock:
            if seq_id in self._seqs:
                raise MXNetError("sequence %r already allocated" % (seq_id,))
            if need > len(self._free):
                raise CacheExhausted(need, len(self._free),
                                     self.blocks_total())
            blocks = [self._free.pop() for _ in range(need)]
            row = _np.full((self.config.blocks_per_seq,), TRASH_BLOCK,
                           dtype=_np.int32)
            row[:need] = blocks
            self._seqs[seq_id] = _Sequence(seq_id, blocks, row)
            self._high_water = max(
                self._high_water, self.blocks_total() - len(self._free))
            return row.copy()

    def table_row(self, seq_id):
        with self._lock:
            seq = self._seqs.get(seq_id)
            if seq is None:
                raise MXNetError("unknown sequence %r" % (seq_id,))
            return seq.table_row.copy()

    def free(self, seq_id):
        """Return a finished sequence's blocks to the free list (LIFO —
        freshly-freed blocks are reused first, keeping the pool's hot
        footprint small).  Idempotent-unfriendly on purpose: freeing an
        unknown id is a bookkeeping bug and raises."""
        with self._lock:
            seq = self._seqs.pop(seq_id, None)
            if seq is None:
                raise MXNetError("unknown sequence %r" % (seq_id,))
            self._free.extend(reversed(seq.blocks))
            return len(seq.blocks)

    def active(self):
        with self._lock:
            return sorted(self._seqs)

    # -- device pools ------------------------------------------------------

    def set_pools(self, k_pools, v_pools):
        """Install the functional update a prefill/decode step returned
        (new pool arrays; the old ones are dropped)."""
        if len(k_pools) != self.config.num_layers \
                or len(v_pools) != self.config.num_layers:
            raise MXNetError("pool update has %d/%d layers, want %d"
                             % (len(k_pools), len(v_pools),
                                self.config.num_layers))
        self.k_pools = list(k_pools)
        self.v_pools = list(v_pools)

    def shard_pools(self, mesh, tp_axis="tp"):
        """Place the pools on ``mesh`` per :func:`cache_sharding_rules`
        (heads over tp).  No-op sharding-wise on a 1-device mesh, but
        always returns the applied PartitionSpec for inspection."""
        import jax
        from jax.sharding import NamedSharding
        rules = cache_sharding_rules(tp_axis=tp_axis, mesh=mesh)
        spec = rules.match("layer0_k_cache", self.config.pool_shape)
        sharding = NamedSharding(mesh, spec)
        self.k_pools = [jax.device_put(p, sharding) for p in self.k_pools]
        self.v_pools = [jax.device_put(p, sharding) for p in self.v_pools]
        return spec

    # -- stats -------------------------------------------------------------

    def occupancy(self):
        with self._lock:
            total = self.blocks_total()
            return (total - len(self._free)) / float(total) if total else 0.0

    def stats(self):
        with self._lock:
            total = self.blocks_total()
            used = total - len(self._free)
            return {"blocks_total": total, "blocks_used": used,
                    "blocks_free": len(self._free),
                    "occupancy": round(used / float(total), 4)
                    if total else 0.0,
                    "seqs_active": len(self._seqs),
                    "blocks_high_water": self._high_water,
                    "block_size": self.config.block_size}
