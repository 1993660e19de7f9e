"""Device contexts mapped onto JAX devices.

Mirrors ``include/mxnet/base.h:85-170`` (Context) and
``python/mxnet/context.py`` of the reference, extended with the ``tpu``
device type that is this framework's reason to exist.

Mapping rules:
- ``cpu(i)``        -> i-th JAX cpu device (XLA host platform). With
  ``--xla_force_host_platform_device_count=N`` multiple cpu ids exist, which is
  the analog of the reference's multi-``mx.cpu(i)`` test trick
  (tests/python/unittest/test_multi_device_exec.py:19-32).
- ``tpu(i)``        -> i-th accelerator device.
- ``gpu(i)``        -> alias for accelerator too: reference scripts that say
  ``mx.gpu(0)`` run unchanged on a TPU chip (north-star "context-string
  change only").
- ``cpu_pinned(i)`` -> cpu (pinned memory is meaningless under XLA host).

A context is a placement, not a label: ``tpu()``/``gpu()`` raise when the
process has no accelerator (the reference's ``gpu(0)`` fails the same way
on a CPU-only build), and every array created or written under a context
lives on that context's device (``ndarray.NDArray._set_data``).
"""
from __future__ import annotations

import threading

from .base import MXNetError

__all__ = ["Context", "cpu", "gpu", "tpu", "cpu_pinned", "current_context",
           "num_gpus", "num_tpus", "default_device_context"]


class Context:
    """Device context. Constructed as Context('tpu', 0) or via cpu()/gpu()/tpu().

    Parity: Context at include/mxnet/base.h:85; python/mxnet/context.py:10.
    """

    # numbering matches the reference for 1..3; tpu is new.
    devtype2str = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 4: "tpu"}
    devstr2type = {"cpu": 1, "gpu": 2, "cpu_pinned": 3, "tpu": 4}

    _default_ctx = threading.local()

    def __init__(self, device_type, device_id=0):
        if isinstance(device_type, Context):
            self.device_typeid = device_type.device_typeid
            self.device_id = device_type.device_id
        else:
            if device_type not in Context.devstr2type:
                raise MXNetError("unknown device type %s" % device_type)
            self.device_typeid = Context.devstr2type[device_type]
            self.device_id = device_id
        self._old_ctx = None

    @property
    def device_type(self) -> str:
        return Context.devtype2str[self.device_typeid]

    def __eq__(self, other):
        return (isinstance(other, Context)
                and self.device_typeid == other.device_typeid
                and self.device_id == other.device_id)

    def __hash__(self):
        return hash((self.device_typeid, self.device_id))

    def __str__(self):
        return "%s(%d)" % (self.device_type, self.device_id)

    __repr__ = __str__

    # -- jax integration ---------------------------------------------------
    @property
    def jax_device(self):
        """Resolve to a concrete jax.Device (lazy; raises if absent).

        Under multi-process (jax.distributed) only THIS process's devices
        are addressable, so contexts index local_devices — the reference's
        dev_id is likewise host-local (a worker's gpu(0) is its own GPU).
        """
        import jax

        if self.device_type in ("cpu", "cpu_pinned"):
            try:
                devs = jax.local_devices(backend="cpu")
            except RuntimeError as exc:
                # JAX_PLATFORMS names the accelerator alone: the host
                # backend does not exist beside it
                raise MXNetError(
                    "%s: jax has no cpu backend in this process (%s); "
                    "list it beside the accelerator, e.g. "
                    "JAX_PLATFORMS=tpu,cpu" % (self, exc)) from exc
        else:
            # gpu and tpu both mean "the accelerator platform".
            devs = _accelerator_devices()
            local = [d for d in devs
                     if d.process_index == jax.process_index()]
            if devs and not local:
                raise MXNetError(
                    "%s: no addressable accelerator on this process "
                    "(cluster has %d remote devices); use the host-local "
                    "device ids of this worker" % (self, len(devs)))
            devs = local
            if not devs:
                raise MXNetError(
                    "%s: this process has no accelerator (jax.devices() is "
                    "%s); use mx.cpu()" % (self, jax.devices()))
        if self.device_id >= len(devs):
            raise MXNetError(
                "%s: device_id %d out of range (%d %s devices visible)"
                % (self, self.device_id, len(devs), self.device_type))
        return devs[self.device_id]

    # -- `with` scoping (python/mxnet/context.py:40-58) --------------------
    def __enter__(self):
        if not hasattr(Context._default_ctx, "value"):
            Context._default_ctx.value = Context("cpu", 0)
        self._old_ctx = Context._default_ctx.value
        Context._default_ctx.value = self
        return self

    def __exit__(self, ptype, value, trace):
        Context._default_ctx.value = self._old_ctx


def _accelerator_devices():
    import jax

    return [d for d in jax.devices() if d.platform != "cpu"]


def cpu(device_id=0) -> Context:
    return Context("cpu", device_id)


def cpu_pinned(device_id=0) -> Context:
    return Context("cpu_pinned", device_id)


def gpu(device_id=0) -> Context:
    """Reference-compat alias: targets the accelerator (TPU) platform."""
    return Context("gpu", device_id)


def tpu(device_id=0) -> Context:
    return Context("tpu", device_id)


def num_gpus() -> int:
    return len(_accelerator_devices())


def num_tpus() -> int:
    return len(_accelerator_devices())


def default_device_context() -> Context:
    """The accelerator when this process has one, else the host: what the
    example scripts and the serving tools bind to when the user names no
    device."""
    return tpu() if num_tpus() > 0 else cpu()


def current_context() -> Context:
    if not hasattr(Context._default_ctx, "value"):
        Context._default_ctx.value = Context("cpu", 0)
    return Context._default_ctx.value
