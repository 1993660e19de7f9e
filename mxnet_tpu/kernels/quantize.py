"""Weight-only quantization: per-channel int8/fp8 params + dequant matmul.

Three pieces, each usable alone:

- :func:`quantize_array` / :func:`quantize_params` — per-output-channel
  symmetric quantization of 2-D matmul weights: ``w (N, K) float32`` →
  ``(q (N, K) int8, scale (N,) float32)`` with ``scale = absmax / 127``
  per row (fp8-e4m3 uses the dtype's own max, 448, where the jax build
  carries the dtype; gated otherwise).
- :func:`quantize_symbol` — graph rewrite over the reference JSON
  layout (``nodes``/``arg_nodes``/``heads``): every ``FullyConnected``
  node whose weight variable matches the rules becomes a
  ``QuantizedDense`` node with a spliced-in ``<weight>_scale`` variable.
  ``Predictor(quantize="int8")`` and ``GenerationEngine`` drive this, so
  serving binds the quantized graph through the same program registry —
  zero steady-state lowerings, one extra traced program per bucket.
- :func:`quantized_matmul` — the compute body ``QuantizedDense`` lowers
  to: a (32,128)-tiled Pallas matmul that loads int8 weight blocks,
  widens them in registers, accumulates in float32 on the MXU, and
  applies the per-channel scale as the epilogue of the last k step
  (weight-only w8a16/w8a32: activations stay wide, so accuracy is the
  rounding of w alone — docs/perf.md "Quantization & fused kernels").

Accuracy contract: per-channel symmetric int8 keeps each weight row's
relative rounding error <= 1/254; greedy decode against the f32
reference stays token-identical or within a per-step logits cosine of
0.999 (asserted by tests/test_kernels.py and the serve_bench
``--check-logits`` gate).
"""
from __future__ import annotations

import functools
import json
import re

import numpy as _np

from ..base import MXNetError
from ..analysis.tiling import register_kernel_spec
from .common import cdiv, dispatch, pick_block

__all__ = ["QDTYPES", "storage_dtype", "quantize_array",
           "dequantize_array", "quantize_params", "quantizable_weights",
           "quantize_symbol", "quantized_matmul",
           "quantized_matmul_reference", "qmm_kernel_spec"]

#: supported weight dtypes -> symmetric clip range max
QDTYPES = {"int8": 127.0, "fp8_e4m3": 448.0}

#: default rule set: every FullyConnected weight (attention projections
#: live inside the fused attention ops and stay wide)
DEFAULT_RULES = (r".*",)


def storage_dtype(qdtype):
    """numpy dtype storing quantized weights for ``qdtype``."""
    if qdtype == "int8":
        return _np.dtype(_np.int8)
    if qdtype == "fp8_e4m3":
        import jax.numpy as jnp
        f8 = getattr(jnp, "float8_e4m3fn", None)
        if f8 is None:
            raise MXNetError(
                "quantize: this jax build has no float8_e4m3fn dtype; "
                "use quantize='int8'")
        return _np.dtype(f8)
    raise MXNetError("quantize: unknown qdtype %r (have: %s)"
                     % (qdtype, sorted(QDTYPES)))


def _to_numpy(v):
    if hasattr(v, "asnumpy"):
        return v.asnumpy()
    return _np.asarray(v)


def quantize_array(w, qdtype="int8"):
    """Per-output-channel symmetric quantization of a 2-D weight.

    ``w (N, K)`` → ``(q (N, K) storage_dtype, scale (N,) float32)``
    with ``dequant = q.astype(f32) * scale[:, None]``.  All-zero rows
    get scale 1.0 (quantizes to zeros, dequantizes to zeros).
    """
    w = _np.asarray(_to_numpy(w), dtype=_np.float32)
    if w.ndim != 2:
        raise MXNetError("quantize_array wants a 2-D weight, got shape %s"
                         % (w.shape,))
    qmax = QDTYPES[qdtype] if qdtype in QDTYPES else None
    st = storage_dtype(qdtype)
    absmax = _np.max(_np.abs(w), axis=1)
    scale = _np.where(absmax > 0, absmax / qmax, 1.0).astype(_np.float32)
    scaled = w / scale[:, None]
    if qdtype == "int8":
        q = _np.clip(_np.rint(scaled), -qmax, qmax).astype(st)
    else:
        q = scaled.astype(st)
    return q, scale


def dequantize_array(q, scale):
    """Inverse of :func:`quantize_array` (float32)."""
    return _np.asarray(q, dtype=_np.float32) * \
        _np.asarray(scale, dtype=_np.float32)[:, None]


def _compile_rules(rules):
    return [re.compile(r) for r in (rules or DEFAULT_RULES)]


def quantizable_weights(symbol_json, rules=None):
    """Weight-variable names of ``FullyConnected`` nodes in a symbol
    JSON whose names match ``rules`` (regex fullmatch, first match
    wins) — the exact set :func:`quantize_symbol` will rewrite."""
    data = json.loads(symbol_json)
    pats = _compile_rules(rules)
    names = []
    for node in data["nodes"]:
        if node["op"] != "FullyConnected" or len(node["inputs"]) < 2:
            continue
        widx = node["inputs"][1][0]
        wnode = data["nodes"][widx]
        if wnode["op"] not in ("null", "None"):
            continue                      # computed weight: leave wide
        if any(p.fullmatch(wnode["name"]) for p in pats):
            names.append(wnode["name"])
    return sorted(set(names))


def quantize_symbol(symbol_json, rules=None, qdtype="int8"):
    """Rewrite ``FullyConnected`` -> ``QuantizedDense`` in a symbol JSON.

    Matched FC nodes change op to ``QuantizedDense`` (same
    ``num_hidden``/``no_bias`` attrs plus ``qdtype``) and gain a
    ``<weight>_scale`` variable input spliced between weight and bias.
    Returns ``(new_json_str, quantized_weight_names)``.  Node indices
    are remapped (scale variables insert before their consumer), so
    ``arg_nodes``/``heads``/``inputs`` all stay consistent with
    ``symbol.load_json``'s sequential-build contract.
    """
    storage_dtype(qdtype)                 # fail early on fp8-less builds
    data = json.loads(symbol_json)
    names = set(quantizable_weights(symbol_json, rules))
    if not names:
        return symbol_json, ()

    nodes = data["nodes"]
    new_nodes = []
    remap = {}                            # old index -> new index
    scale_index = {}                      # weight name -> new scale index
    for i, node in enumerate(nodes):
        node = dict(node)
        node["inputs"] = [[remap[j], cj] + rest
                          for j, cj, *rest in node["inputs"]]
        if node["op"] == "FullyConnected":
            widx = node["inputs"][1][0]
            wname = new_nodes[widx]["name"] if widx < len(new_nodes) else None
            if wname in names:
                if wname not in scale_index:
                    scale_index[wname] = len(new_nodes)
                    new_nodes.append({"op": "null",
                                      "name": wname + "_scale",
                                      "attr": {}, "inputs": []})
                node["op"] = "QuantizedDense"
                node["attr"] = dict(node.get("attr") or {},
                                    qdtype=qdtype)
                node["inputs"] = (node["inputs"][:2]
                                  + [[scale_index[wname], 0]]
                                  + node["inputs"][2:])
        remap[i] = len(new_nodes)
        new_nodes.append(node)

    data["nodes"] = new_nodes
    data["arg_nodes"] = [i for i, n in enumerate(new_nodes)
                         if n["op"] in ("null", "None")]
    data["heads"] = [[remap[i], ci] + rest
                     for i, ci, *rest in data["heads"]]
    return json.dumps(data, indent=2), tuple(sorted(names))


def quantize_params(params, names, qdtype="int8"):
    """Quantize the listed weights of a params dict (name -> array).

    Returns a NEW dict where each listed weight is replaced by its
    quantized storage array and a ``<name>_scale`` float32 entry rides
    next to it; everything else passes through untouched.  Idempotent:
    a weight already in the storage dtype (scales present) is skipped,
    so re-binding an already-quantized dict is free.
    """
    st = storage_dtype(qdtype)
    out = dict(params)
    for name in names:
        if name not in out:
            continue
        w = _to_numpy(out[name])
        if w.dtype == st and (name + "_scale") in out:
            continue
        q, scale = quantize_array(w, qdtype=qdtype)
        out[name] = q
        out[name + "_scale"] = scale
    return out


# ----------------------------------------------------------------------
# the dequant-in-registers matmul kernel
# ----------------------------------------------------------------------
def _qmm_block_layout(m, k, n, bm, bk, bn, qdtype, xdtype):
    """(block, array, dtype) triples of the pallas_call, inputs
    (x, w, scale) then output — the ONE place the kernel's block shapes
    live, shared by the call and the registered MXL-K spec."""
    in_blocks = [
        ((bm, bk), (m, k), str(xdtype)),     # x activations (wide)
        ((bn, bk), (n, k), str(qdtype)),     # w row-major (N, K) quantized
        ((1, bn), (1, n), "float32"),        # per-output-channel scale
    ]
    out_blocks = [((bm, bn), (m, n), "float32")]
    return in_blocks, out_blocks


def _qmm_blocks(m, k, n, xdtype, qdtype, block_m, block_n, block_k):
    sub_x = {1: 32, 2: 16}.get(_np.dtype(xdtype).itemsize, 8)
    sub_w = {1: 32, 2: 16}.get(storage_dtype(qdtype).itemsize
                               if qdtype in QDTYPES
                               else _np.dtype(qdtype).itemsize, 8)
    bm = pick_block(m, sub_x, block_m)
    bn = pick_block(n, max(sub_w, 128), block_n)   # bn is also a lane dim
    bk = pick_block(k, 128, block_k)               # lane dim for x and w
    return bm, bk, bn


def _qmm_kernel(x_ref, w_ref, s_ref, o_ref, *, n_k_blocks):
    """Grid (m_blocks, n_blocks, k_blocks).  The output block is
    revisited across the k dimension: zeroed at k==0, accumulated in
    float32, and scaled per output channel on the last k step — the
    dequant happens in registers (int8 block widened right before the
    MXU dot), never in HBM."""
    import jax.numpy as jnp
    from jax import lax
    import jax.experimental.pallas as pl

    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...].astype(jnp.float32)          # (bm, bk)
    w = w_ref[...].astype(jnp.float32)          # (bn, bk), widened here
    o_ref[...] += lax.dot_general(
        x, w, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)     # (bm, bn)

    @pl.when(kk == n_k_blocks - 1)
    def _epilogue():
        o_ref[...] = o_ref[...] * s_ref[...]    # scale (1, bn) broadcast


def quantized_matmul_reference(x, w_q, scale):
    """Exact jnp fallback: widen, contract, scale.  ``x (M, K)``,
    ``w_q (N, K)`` quantized, ``scale (N,)`` → ``(M, N)`` in x's dtype."""
    import jax.numpy as jnp
    from jax import lax
    y = lax.dot_general(
        x.astype(jnp.float32), w_q.astype(jnp.float32),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    return (y * scale[None, :].astype(jnp.float32)).astype(x.dtype)


def quantized_matmul(x, w_q, scale, block_m=256, block_n=512, block_k=512,
                     interpret=None):
    """Weight-only quantized matmul ``x (M, K) @ w_q (N, K).T * scale``.

    The Pallas kernel where the computation is placed on a TPU, the jnp
    reference elsewhere (``common.dispatch``); an explicit ``interpret``
    runs the kernel either way.  Both accumulate in float32 and cast
    back to x's dtype.
    """
    def kernel(x, w_q, scale, interpret=False):
        return _qmm_call(x, w_q, scale, block_m, block_n, block_k,
                         interpret)

    if interpret is not None:
        return kernel(x, w_q, scale, interpret=bool(interpret))
    return dispatch(kernel, quantized_matmul_reference, x, w_q, scale)


def _qmm_call(x, w_q, scale, block_m, block_n, block_k, interpret):
    """The pallas_call.  M and N only index outputs, so their trailing
    blocks may be partial (an LM head of 50,257 rows needs no padded
    copy of the weight); K is reduced over, so it is tiled exactly or
    zero-padded."""
    import jax
    import jax.numpy as jnp
    import jax.experimental.pallas as pl

    (m, k), (n, _k2) = x.shape, w_q.shape
    bm, bk, bn = _qmm_blocks(m, k, n, x.dtype, str(w_q.dtype), block_m,
                             block_n, block_k)
    n_k_blocks = cdiv(k, bk)
    if k % bk:
        pad = ((0, 0), (0, n_k_blocks * bk - k))
        x, w_q, k = jnp.pad(x, pad), jnp.pad(w_q, pad), n_k_blocks * bk
    in_blocks, out_blocks = _qmm_block_layout(m, k, n, bm, bk, bn,
                                              w_q.dtype, x.dtype)
    kernel = functools.partial(_qmm_kernel, n_k_blocks=n_k_blocks)
    out = pl.pallas_call(
        kernel,
        grid=(cdiv(m, bm), cdiv(n, bn), n_k_blocks),
        in_specs=[
            pl.BlockSpec(in_blocks[0][0], lambda i, j, kk: (i, kk)),
            pl.BlockSpec(in_blocks[1][0], lambda i, j, kk: (j, kk)),
            pl.BlockSpec(in_blocks[2][0], lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec(out_blocks[0][0], lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct(out_blocks[0][1], jnp.float32),
        name="quantized_matmul",
        interpret=interpret,
    )(x, w_q, scale.reshape(1, n).astype(jnp.float32))
    return out.astype(x.dtype)


def qmm_kernel_spec(m=256, k=1024, n=1024, block_m=256, block_n=512,
                    block_k=512, qdtype="int8", dtype="float32"):
    """MXL-K spec for the quantized matmul at one (activation, weight)
    dtype pair — built from the SAME layout helper the pallas_call uses.
    ``dtype`` is the activation/accumulator side (the CI sweep runs
    f32/bf16/int8); the weight block is always the quantized dtype."""
    qd = "int8" if qdtype == "fp8_e4m3" else qdtype
    bm, bk, bn = _qmm_blocks(m, k, n, dtype, qd, block_m, block_n, block_k)
    in_blocks, out_blocks = _qmm_block_layout(m, k, n, bm, bk, bn, qd,
                                              dtype)
    roles = [("in", "x"), ("in", "w_q"), ("in", "scale")]
    blocks = [{"role": r, "name": nm, "block": blk, "array": arr,
               "dtype": dt}
              for (r, nm), (blk, arr, dt) in zip(roles, in_blocks)]
    blocks.append({"role": "out", "name": "out",
                   "block": out_blocks[0][0], "array": out_blocks[0][1],
                   "dtype": out_blocks[0][2]})
    return {"name": "quantized_matmul[%s,w:%s]" % (dtype, qd),
            "origin": "mxnet_tpu/kernels/quantize.py",
            "grid": (cdiv(m, bm), cdiv(n, bn), cdiv(k, bk)),
            "blocks": blocks}


register_kernel_spec(
    "kernels.quantize.quantized_matmul",
    lambda: [qmm_kernel_spec(dtype=dt)
             for dt in ("float32", "bfloat16", "int8")])
