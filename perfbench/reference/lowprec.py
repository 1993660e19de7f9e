"""Lower-precision stand-ins used only by the controls of ``correct``.

The 8-bit floating-point control is the usual fp8 training recipe: every
matrix product and convolution takes its two operands in ``float8_e4m3fn``
with one scale per tensor (amax -> 448) in the forward pass
(``fake_quant``), and the gradient of its output in ``float8_e5m2``
(amax -> 57344) in the backward pass (``grad_quant``); sums accumulate in
float32 and everything else stays float32.  ``"bfloat16"`` rounds to
bfloat16.  ``None`` is the identity: the reference proper.
"""
import functools

import jax
import jax.numpy as jnp


def fake_quant(x, kind):
    if kind is None:
        return x
    if kind == "bfloat16":
        q = x.astype(jnp.bfloat16).astype(x.dtype)
    elif kind == "float8_e4m3fn":
        amax = jnp.max(jnp.abs(x))
        scale = jnp.where(amax > 0, 448.0 / amax, 1.0)
        q = (x * scale).astype(jnp.float8_e4m3fn).astype(x.dtype) / scale
    else:
        raise ValueError("unknown low-precision kind %r" % (kind,))
    return x + jax.lax.stop_gradient(q - x)


def _round_e5m2(x):
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, 57344.0 / amax, 1.0)
    return (x * scale).astype(jnp.float8_e5m2).astype(x.dtype) / scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def grad_quant(y, kind):
    """Identity going forward; going backward the gradient of ``y`` is
    rounded as the low-precision recipe would hold it."""
    return y


def _gq_fwd(y, kind):
    return y, None


def _gq_bwd(kind, _res, ct):
    if kind == "float8_e4m3fn":
        return (_round_e5m2(ct),)
    if kind == "bfloat16":
        return (ct.astype(jnp.bfloat16).astype(ct.dtype),)
    return (ct,)


grad_quant.defvjp(_gq_fwd, _gq_bwd)
