"""Quantized + fused kernel tier (docs/perf.md "Quantization & fused
kernels").

Two legs, each a Pallas kernel with an exact jnp reference and an MXL-K
spec registered through ``analysis.tiling.KERNEL_SPECS``; which of the
two a call runs is decided by where the computation is placed
(:func:`.common.dispatch`):

- :mod:`.quantize` — per-channel int8/fp8 weight-only quantization
  (params + symbol rewrite) and the dequant-in-registers matmul behind
  the ``QuantizedDense`` op;
- :mod:`.fused_opt` — the bucketed flatten/update/unflatten optimizer
  sweep replacing the per-leaf tree-map (``MXTPU_FUSED_OPT``).

(The flash-attention kernels are in ``parallel/ring_attention.py``;
:mod:`.delta_rule` holds the gated delta rule's forward and backward
kernels, which ``ops/linear_attention.py`` dispatches to.)

Importing this package registers every kernel spec, so ``mxlint`` /
``Symbol.validate()`` statically tile-check every block layout the
kernels use (``analysis.tiling._ensure_builtin_specs`` imports it for
the same reason).
"""
from . import quantize, fused_opt, delta_rule                  # noqa: F401
from .quantize import (quantize_params, quantize_symbol,       # noqa: F401
                       quantizable_weights, quantized_matmul)
from .fused_opt import fused_apply, fused_opt_mode, supports_fused  # noqa: F401,E501

__all__ = ["quantize", "fused_opt", "delta_rule",
           "quantize_params", "quantize_symbol", "quantizable_weights",
           "quantized_matmul",
           "fused_apply", "fused_opt_mode", "supports_fused"]
