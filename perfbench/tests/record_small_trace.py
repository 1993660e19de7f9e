#!/usr/bin/env python3
"""Records the small device trace that test_trace_reduce.py checks the
reduction on.  Run once on the chip (``python perfbench/tests/record_small_trace.py
<outdir>``); the result is kept beside the test as ``small_trace.xplane.pb.gz``.

Three launches of one jitted step (a Pallas kernel named ``flash_forward``
and a matmul), each preceded by a host span ``host_prep`` that sleeps, so the
trace has device work, idle gaps and a host span to charge them to.
"""
import glob
import gzip
import os
import shutil
import sys
import time


def main(out_dir):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def kern(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0 + 1.0

    def step(x, w):
        y = pl.pallas_call(kern, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
                           name="flash_forward")(x)
        return jnp.tanh(y @ w)

    jstep = jax.jit(step)
    x = jnp.ones((512, 512), jnp.float32)
    w = jnp.ones((512, 512), jnp.float32) * 0.01
    jstep(x, w).block_until_ready()
    trace_dir = os.path.join(out_dir, "trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(trace_dir)
    for i in range(3):
        with jax.profiler.TraceAnnotation("host_prep"):
            time.sleep(0.02)
        with jax.profiler.TraceAnnotation("launch"):
            x = jstep(x, w)
        x.block_until_ready()
    jax.profiler.stop_trace()
    pb = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))[0]
    print("trace", pb, os.path.getsize(pb))
    with open(pb, "rb") as f, gzip.open(os.path.join(out_dir, "small_trace.xplane.pb.gz"), "wb") as g:
        g.write(f.read())
    from jax.profiler import ProfileData
    data = ProfileData.from_file(pb)
    for plane in data.planes:
        print("PLANE", repr(plane.name))
        for line in plane.lines:
            evs = list(line.events)
            print("  LINE", repr(line.name), len(evs))
            for ev in evs[:6]:
                print("     ", repr(ev.name), ev.start_ns, ev.duration_ns)
    shutil.rmtree(trace_dir, ignore_errors=True)


if __name__ == "__main__":
    main(sys.argv[1] if len(sys.argv) > 1 else "chiprun_out/small_trace")
