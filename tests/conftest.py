"""Test harness config: fake an 8-device TPU-like mesh on CPU.

This is the analog of the reference's multi-`mx.cpu(i)` trick
(tests/python/unittest/test_multi_device_exec.py): XLA's host platform is
forced to expose 8 devices so sharding/collective paths run without real
chips (SURVEY §4 "Implication for the TPU build").
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

# the suite never touches an accelerator, even when something imported
# jax before the env vars above were set
jax.config.update("jax_platforms", "cpu")

# fp64 for numeric-gradient checks (reference CPU tests run fp64 numpy refs)
jax.config.update("jax_enable_x64", True)

# MXTPU_LOCKCHECK=1 (serving/resilience CI legs): patch the lock
# factories BEFORE any package module builds its runtime state, so
# every package lock is traced and a live lock-order inversion raises
# ResilienceError(kind="lock_order") instead of deadlocking the suite.
from mxnet_tpu.observability import locktrace as _locktrace  # noqa: E402

_locktrace.maybe_install()

# MXTPU_RETRACE_SENTRY=1 (serving/resilience CI legs): wrap the
# lowering counter and the program-registry miss path so every
# post-warmup lowering is counted and attributed to the divergent
# cache-key ingredient (the zero-steady-state-lowerings contract's
# runtime witness — docs/perf.md, analysis MXL-X).
from mxnet_tpu.observability import retrace as _retrace  # noqa: E402

_retrace.maybe_install()
