"""Shared plumbing for the Pallas kernel tier.

Every kernel in this package follows the ``parallel/ring_attention``
contract: a jnp reference implementation (exact, runs anywhere), a
Pallas kernel (TPU), and ONE rule deciding which of the two a call uses,
centralized here so the kernels cannot drift:

- an EXPLICIT ``interpret`` argument wins: ``True`` exercises the kernel
  through the Pallas interpreter (tests, off-TPU), ``False`` forces the
  Mosaic path;
- otherwise :func:`dispatch` picks by where the computation is PLACED:
  the kernel when it runs on a TPU, the reference anywhere else.  The
  question is never "is there a TPU in this process" — a ``mx.cpu()``
  executor on a chip host must get the reference, and a compile-only
  lowering against a TPU topology (tools/aot_*.py, cpu host backend)
  must get the Mosaic call.
"""
from __future__ import annotations

import os as _os

__all__ = ["dispatch", "pick_block", "cdiv", "env_flag"]


def env_flag(name, default=""):
    """Env knob value, lower-cased; '' when unset."""
    return _os.environ.get(name, default).strip().lower()


def dispatch(kernel, reference, *args):
    """``kernel(*args)`` where the computation is placed on a TPU,
    ``reference(*args)`` everywhere else.

    Under a trace the choice is ``lax.platform_dependent``: both are
    staged out and the one for the platform the enclosing computation
    is lowered FOR is kept, so the compiler never sees the other.
    Eager calls are decided by where the operands live."""
    import jax
    from jax import lax
    leaves = jax.tree_util.tree_leaves(args)
    if any(isinstance(a, jax.core.Tracer) for a in leaves):
        return lax.platform_dependent(*args, tpu=kernel, default=reference)
    on_tpu = any(d.platform == "tpu" for a in leaves
                 if isinstance(a, jax.Array) for d in a.devices())
    return (kernel if on_tpu else reference)(*args)


def cdiv(a, b):
    return -(-int(a) // int(b))


def pick_block(dim, granule, target):
    """Block extent for tiling ``dim``, never larger than ``target``.

    The whole dim when it fits (a block covering its array dim is legal
    at any size — Mosaic pads it).  Otherwise a granule multiple: the
    largest exact divisor of ``dim`` no smaller than half the target,
    else the aligned target itself, which leaves a trailing partial
    block.  Callers run ``cdiv(dim, block)`` grid steps; a partial
    block reads unspecified values past the edge and its out-of-range
    writes are dropped, so it is safe on a dim that only indexes
    outputs and must be zero-padded on a dim that is reduced over.
    (The old fallback was the WHOLE dim: an LM head of 50,257 rows or a
    25M-element optimizer bucket asked for more VMEM than the chip has.)
    """
    dim = int(dim)
    if dim <= target:
        return dim
    top = (target // granule) * granule
    c = top
    while 2 * c >= top and c >= granule:
        if dim % c == 0:
            return c
        c -= granule
    return top
