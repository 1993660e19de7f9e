#!/usr/bin/env python
"""Measure gradient-aggregation bandwidth.

Parity: tools/bandwidth/measure.py:16-40 — the reference times kvstore
push+pull over GPUs for varying sizes; here the same experiment times the
TPU-native equivalent: an XLA psum over every visible device (ICI), plus
the host-side kvstore push/pull path for comparison.

Reported bandwidth follows the reference's convention: each measurement
moves ``2 * (n-1)/n * bytes`` per device (allreduce lower bound).
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np


def measure_psum(sizes, repeat):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map

    devs = jax.devices()
    n = len(devs)
    mesh = Mesh(np.array(devs), ("dp",))
    results = []
    for size in sizes:
        elems = size // 4
        x = jnp.ones((n, elems), jnp.float32)

        @jax.jit
        def allreduce(x):
            return shard_map(
                lambda v: jax.lax.psum(v, "dp"),
                mesh=mesh, in_specs=P("dp", None),
                out_specs=P("dp", None))(x)

        allreduce(x).block_until_ready()  # compile
        t0 = time.perf_counter()
        for _ in range(repeat):
            out = allreduce(x)
        out.block_until_ready()
        dt = (time.perf_counter() - t0) / repeat
        moved = 2 * (n - 1) / max(n, 1) * size
        results.append((size, dt, moved / dt / 1e9))
    return n, results


def measure_kvstore(sizes, repeat):
    import mxnet_tpu as mx
    kv = mx.kv.create("local")
    results = []
    for i, size in enumerate(sizes):
        elems = size // 4
        a = mx.nd.ones((elems,))
        b = mx.nd.zeros((elems,))
        kv.init(i, a)
        kv.push(i, a)
        kv.pull(i, out=b)
        b.wait_to_read()
        t0 = time.perf_counter()
        for _ in range(repeat):
            kv.push(i, a)
            kv.pull(i, out=b)
        b.wait_to_read()
        dt = (time.perf_counter() - t0) / repeat
        results.append((size, dt, 2 * size / dt / 1e9))
    return results


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--sizes", type=str,
                        default="1048576,16777216,134217728",
                        help="bytes per tensor, comma separated")
    parser.add_argument("--repeat", type=int, default=10)
    parser.add_argument("--skip-kvstore", action="store_true")
    args = parser.parse_args()
    sizes = [int(s) for s in args.sizes.split(",")]

    n, res = measure_psum(sizes, args.repeat)
    print("== psum allreduce over %d device(s) (ICI path) ==" % n)
    for size, dt, bw in res:
        print("size %10d B  time %8.3f ms  busbw %7.2f GB/s"
              % (size, dt * 1e3, bw))

    if not args.skip_kvstore:
        print("== kvstore local push+pull (host path) ==")
        for size, dt, bw in measure_kvstore(sizes, args.repeat):
            print("size %10d B  time %8.3f ms  busbw %7.2f GB/s"
                  % (size, dt * 1e3, bw))


if __name__ == "__main__":
    main()
