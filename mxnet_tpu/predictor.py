"""Standalone inference API.

Parity: src/c_api/c_predict_api.cc + amalgamation (the reference's
predict-only surface for deployment: load symbol JSON + params blob, set
inputs, forward, read outputs — no training machinery).  One XLA
computation per input shape, cached, so repeated predict calls hit the
compile cache (the reference pre-allocates one executor; XLA's cache is
the equivalent).
"""
from __future__ import annotations

import numpy as _np

from .base import MXNetError
from . import ndarray as nd
from . import symbol as sym
from .context import Context, cpu

__all__ = ["Predictor", "load_ndarray_file"]


def load_ndarray_file(fname_or_bytes):
    """Parity: MXNDListCreate (c_predict_api.cc): load a saved named-array
    file (the `prefix-0000.params` format) into a dict.

    Accepts a path (``str`` or ``os.PathLike``) or the raw file bytes.
    Bytes spill through a named temp file because ``nd.load`` wants a
    path; the temp file is created ``delete=False`` so the handle can be
    closed before reloading (Windows can't reopen a still-open
    NamedTemporaryFile), and the unlink tolerates the Windows-style
    failure where the file is still mapped by the reader."""
    import os
    if isinstance(fname_or_bytes, (bytes, bytearray)):
        import tempfile
        tmp = None
        try:
            with tempfile.NamedTemporaryFile(delete=False,
                                             suffix=".params") as f:
                tmp = f.name
                f.write(fname_or_bytes)
            return nd.load(tmp)
        finally:
            if tmp is not None:
                try:
                    os.unlink(tmp)
                except OSError:
                    pass          # Windows: reader may still hold a map
    return nd.load(os.fspath(fname_or_bytes))


class Predictor(object):
    """Parity: MXPredCreate / MXPredForward / MXPredGetOutput.

    Parameters
    ----------
    symbol_json : str — symbol JSON text or path ending in .json
    param_file : str | bytes | dict — params file/bytes ('arg:'/'aux:'
        prefixed names, the save_checkpoint format) or a plain dict
    input_shapes : dict name -> shape
    ctx : Context every input, weight and output lives on (default
        cpu(), like the reference's MXPredCreate dev_type)
    quantize : None | "int8" | "fp8_e4m3" — weight-only quantization:
        rewrite matched FullyConnected nodes to QuantizedDense
        (kernels/quantize.py) and quantize the corresponding params.
        Defaults to the MXTPU_QUANTIZE env var; idempotent when handed
        an already-quantized symbol/params pair (the GenerationEngine
        quantizes params once and every bucket Predictor reuses them).
    """

    def __init__(self, symbol_json, param_file, input_shapes, ctx=None,
                 quantize=None):
        import os
        # compilation rides the PR-8 caches: the cross-symbol program
        # registry (executor._PROGRAM_REGISTRY, graph-hash keyed) makes
        # a SECOND Predictor over the same symbol/ctx reuse the traced
        # program with zero new lowerings; the on-disk XLA cache the
        # Executor enables lets even a fresh process skip compilation
        if isinstance(symbol_json, os.PathLike):
            symbol_json = os.fspath(symbol_json)
        if isinstance(symbol_json, str) and symbol_json.endswith(".json"):
            self.symbol = sym.load(symbol_json)
        else:
            self.symbol = sym.load_json(symbol_json)
        ctx = ctx or cpu()
        if not isinstance(ctx, Context):
            ctx = Context(ctx)

        if isinstance(param_file, dict):
            raw = param_file
        else:
            raw = load_ndarray_file(param_file)
        arg_params, aux_params = {}, {}
        for k, v in raw.items():
            if k.startswith("arg:"):
                arg_params[k[4:]] = v
            elif k.startswith("aux:"):
                aux_params[k[4:]] = v
            else:
                arg_params[k] = v

        if quantize is None:
            quantize = os.environ.get("MXTPU_QUANTIZE", "") or None
        self._quantize = quantize
        if quantize:
            from .kernels import quantize as _q
            qjs, qnames = _q.quantize_symbol(self.symbol.tojson(),
                                             qdtype=quantize)
            if qnames:
                self.symbol = sym.load_json(qjs)
                arg_params = _q.quantize_params(arg_params, qnames,
                                                qdtype=quantize)

        self._input_names = list(input_shapes)
        arg_names = self.symbol.list_arguments()
        # args in neither inputs nor params (a loss head's label slot)
        # bind as inferred-shape zeros — the reference predictor does the
        # same (c_predict_api.cc:149-170 allocates every arg at its
        # inferred shape and copies params over where present)
        inferred = {}
        try:
            arg_shapes, _, _ = self.symbol.infer_shape_partial(**input_shapes)
            if arg_shapes is not None:
                inferred = dict(zip(arg_names, arg_shapes))
        except Exception:
            pass
        # plain-numpy dicts are allowed: nd.on_context wraps them so the
        # executor's .data access yields a jax array (np.ndarray.data is
        # a memoryview), preserving dtype (int8/fp8 for quantized); a
        # param already on ctx is shared, not copied — every bucket
        # Predictor of one server binds the same weights
        args = {}
        for name in arg_names:
            if name in input_shapes:
                args[name] = nd.zeros(input_shapes[name], ctx=ctx)
            elif name in arg_params:
                args[name] = arg_params[name] = nd.on_context(
                    arg_params[name], ctx)
            elif inferred.get(name) is not None:
                args[name] = nd.zeros(inferred[name], ctx=ctx)
            else:
                raise MXNetError("Predictor: missing parameter %r" % name)
        aux = {}
        for name in self.symbol.list_auxiliary_states():
            if name not in aux_params:
                raise MXNetError("Predictor: missing aux state %r" % name)
            aux[name] = aux_params[name] = nd.on_context(
                aux_params[name], ctx)
        self._exec = self.symbol.bind(ctx, args, aux_states=aux,
                                      grad_req="null")
        self._ctx = ctx
        self._arg_params = arg_params
        self._aux_params = aux_params

    def set_input(self, name, value):
        """Parity MXPredSetInput (incl. its size validation)."""
        if name not in self._input_names:
            raise MXNetError("unknown input %r (inputs: %s)"
                             % (name, self._input_names))
        value = _np.asarray(value)
        want = self._exec.arg_dict[name].shape
        if tuple(value.shape) != tuple(want):
            raise MXNetError(
                "input %r has shape %s but the predictor was bound with "
                "%s (use reshape() for new shapes)"
                % (name, value.shape, want))
        self._exec.arg_dict[name][:] = value

    def forward(self, **inputs):
        """Set any given inputs, run, return list of numpy outputs."""
        for k, v in inputs.items():
            self.set_input(k, v)
        return [o.asnumpy() for o in self._exec.forward(is_train=False)]

    def forward_async(self, **inputs):
        """Dispatch one forward and return the RAW device arrays without
        blocking on execution (XLA dispatch is async; conversion — e.g.
        ``numpy.asarray(out)`` — is what blocks).

        Unlike :meth:`forward`, the returned arrays are NOT the
        executor's in-place output slots: each call owns its results, so
        a pipeline may dispatch batch N+1 while batch N's arrays are
        still being read — the serving batcher's overlap seam."""
        for k, v in inputs.items():
            self.set_input(k, v)
        ex = self._exec
        ex._n_forward += 1
        arg_values = {n: a.data for n, a in ex.arg_dict.items()}
        aux_values = {n: a.data for n, a in ex.aux_dict.items()}
        if ex._needs_rng:
            from . import random as _random
            rng = _random.next_key()
        else:
            from .executor import _zero_key
            rng = _zero_key()
        outs, _aux = ex._jit_forward(arg_values, aux_values, rng,
                                     is_train=False)
        return list(outs)

    @staticmethod
    def compile_stats():
        """Compile-cache counters ({"hits", "misses", "lowerings"} plus
        the program-registry size) — how tests prove a second Predictor
        construction (or a warmed serving bucket) performed zero new
        lowerings."""
        from .executor import program_registry_stats
        return program_registry_stats()

    def get_output(self, index):
        """Parity MXPredGetOutput."""
        return self._exec.outputs[index].asnumpy()

    def reshape(self, input_shapes):
        """Parity MXPredReshape: rebind for new input shapes (compile
        cache keyed on shape, SURVEY §7 stage 5)."""
        return Predictor(self.symbol.tojson(),
                         dict(self._arg_params,
                              **{"aux:" + k: v
                                 for k, v in self._aux_params.items()}),
                         input_shapes, self._ctx)
