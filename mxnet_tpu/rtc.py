"""Runtime-compiled custom kernels.

Parity: python/mxnet/rtc.py — the reference's ``Rtc`` compiles CUDA C
source through NVRTC at runtime and runs it on NDArrays.  The TPU-native
equivalent compiles a *Pallas kernel* (or any jax-traceable function) at
runtime through XLA — same role (user-supplied kernels without rebuilding
the framework), hardware-appropriate language (python Pallas instead of
CUDA C strings; there is no TPU source-string compiler to shell out to).

    def kern(x_ref, y_ref, o_ref):
        o_ref[...] = x_ref[...] + 2.0 * y_ref[...]

    rtc = mx.rtc.Rtc(kern, n_outputs=1)
    (out,) = rtc.push([a, b])          # a, b: NDArray

``Rtc.push`` mirrors the reference's push(ins, outs, grid, block) —
grid/block become the Pallas grid spec, owned by the kernel itself here.
"""
from __future__ import annotations

import os
import warnings

import jax

from .base import MXNetError
from .ndarray import NDArray

__all__ = ["Rtc"]


def _tile_lint(in_shapes, in_dtypes, out_shapes, out_dtypes, mode):
    """Static Mosaic tile check of the whole-array blocks this wrapper
    hands to pallas_call — catches doomed layouts (1-D refs, odd last
    dims on partial tiles) before XLA ever sees the kernel.  ``mode``:
    "warn" emits GraphLintWarning, "error" raises, "off" skips."""
    if mode == "off":
        return
    from .analysis.tiling import block_findings
    from .analysis import GraphLintWarning
    findings = []
    for i, (shp, dt) in enumerate(zip(in_shapes, in_dtypes)):
        findings += block_findings(tuple(shp), tuple(shp), str(dt),
                                   "in%d" % i)
    for i, (shp, dt) in enumerate(zip(out_shapes, out_dtypes)):
        findings += block_findings(tuple(shp), tuple(shp), str(dt),
                                   "out%d" % i)
    for rule_id, severity, message in findings:
        text = "[%s] rtc pallas kernel: %s" % (rule_id, message)
        if mode == "error" and severity == "error":
            raise MXNetError(text)
        warnings.warn(text, GraphLintWarning, stacklevel=3)


class Rtc(object):
    """Runtime-compiled kernel wrapper.

    Parameters
    ----------
    fn : either a jax-traceable function ``fn(*arrays) -> array|tuple``
        (``pallas=False``), or a Pallas kernel body taking
        ``(*in_refs, *out_refs)`` (``pallas=True``) run with whole-array
        blocks in VMEM.
    n_outputs : number of outputs.
    out_shapes / out_dtypes : required for the pallas path when output
        shape differs from input 0's shape/dtype.
    """

    def __init__(self, fn, n_outputs=1, pallas=False, out_shapes=None,
                 out_dtypes=None, interpret=None):
        self._fn = fn
        self._n_out = int(n_outputs)
        self._pallas = bool(pallas)
        self._out_shapes = out_shapes
        self._out_dtypes = out_dtypes
        self._interpret = interpret
        self._compiled = {}

    def _build(self, in_shapes, in_dtypes, interpret):
        if not self._pallas:
            fn = self._fn

            def run(*xs):
                out = fn(*xs)
                return out if isinstance(out, tuple) else (out,)

            return jax.jit(run)

        import jax.experimental.pallas as pl

        out_shapes = self._out_shapes or [in_shapes[0]] * self._n_out
        out_dtypes = self._out_dtypes or [in_dtypes[0]] * self._n_out
        out_spec = tuple(jax.ShapeDtypeStruct(tuple(s), d)
                         for s, d in zip(out_shapes, out_dtypes))

        # MXTPU_RTC_LINT: warn|error|off.  Default lints only the real-
        # Mosaic path — interpret mode has no tile rules to violate, and
        # CPU test runs stay quiet.
        lint_mode = os.environ.get("MXTPU_RTC_LINT",
                                   "off" if interpret else "warn")
        _tile_lint(in_shapes, in_dtypes, out_shapes, out_dtypes,
                   lint_mode)

        call = pl.pallas_call(self._fn, out_shape=out_spec,
                              interpret=interpret)
        return jax.jit(lambda *xs: call(*xs))

    def push(self, ins, grid_dims=None, block_dims=None):
        """Run the kernel on NDArray inputs; returns tuple of NDArrays.

        grid_dims/block_dims are accepted for API parity with the
        reference (rtc.py push) but ignored: Pallas owns its grid."""
        if not ins:
            raise MXNetError("Rtc.push needs at least one input")
        xs = [i.data if isinstance(i, NDArray) else i for i in ins]
        interpret = self._interpret
        if interpret is None:
            # Mosaic where the inputs (hence the computation) live on a
            # TPU, the Pallas interpreter anywhere else
            interpret = not any(d.platform == "tpu"
                                for x in xs if isinstance(x, jax.Array)
                                for d in x.devices())
        interpret = bool(interpret)
        key = (interpret,) + tuple(
            (tuple(x.shape), str(x.dtype)) for x in xs)
        if key not in self._compiled:
            self._compiled[key] = self._build(
                [tuple(x.shape) for x in xs], [x.dtype for x in xs],
                interpret)
        outs = self._compiled[key](*xs)
        ctx = next((i.context for i in ins if isinstance(i, NDArray)), None)
        return tuple(NDArray(o, ctx=ctx) for o in outs)
