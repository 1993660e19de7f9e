"""Test helpers (parity: python/mxnet/test_utils.py).

``check_numeric_gradient`` / ``check_symbolic_forward`` /
``check_symbolic_backward`` mirror the reference harness used across
tests/python/unittest/test_operator.py; ``check_consistency`` compares the
interpret (eager) path against the compiled path — the TPU analog of the
reference's cpu-vs-gpu consistency harness (SURVEY §4).
"""
from __future__ import annotations

import numpy as _np

from .base import MXNetError
from .context import cpu
from .ndarray import NDArray, array, zeros

__all__ = ["reldiff", "same", "assert_almost_equal", "numeric_grad",
           "check_numeric_gradient", "check_symbolic_forward",
           "check_symbolic_backward", "default_context", "rand_ndarray",
           "check_consistency", "tpu_lowering_text"]

_DEFAULT_RTOL = 1e-4
_DEFAULT_ATOL = 1e-6


def default_context():
    return cpu(0)


def reldiff(a, b):
    diff = _np.abs(a - b).sum()
    norm = (_np.abs(a) + _np.abs(b)).sum() + 1e-12
    return diff / norm


def same(a, b):
    return _np.array_equal(a, b)


def assert_almost_equal(a, b, rtol=_DEFAULT_RTOL, atol=_DEFAULT_ATOL, names=("a", "b")):
    if isinstance(a, NDArray):
        a = a.asnumpy()
    if isinstance(b, NDArray):
        b = b.asnumpy()
    if not _np.allclose(a, b, rtol=rtol, atol=atol):
        idx = _np.unravel_index(_np.argmax(_np.abs(a - b)), a.shape)
        raise AssertionError(
            "%s and %s differ: max abs err %g at %s (%g vs %g)"
            % (names[0], names[1], _np.abs(a - b).max(), idx, a[idx], b[idx]))


def rand_ndarray(shape, ctx=None, scale=1.0):
    return array(_np.random.uniform(-scale, scale, size=shape).astype(_np.float32),
                 ctx=ctx)


def _bind(sym, location, aux_states=None, grad_req="write", ctx=None):
    ctx = ctx or default_context()
    arg_names = sym.list_arguments()
    if isinstance(location, dict):
        args = {k: array(v, ctx=ctx) if not isinstance(v, NDArray) else v
                for k, v in location.items()}
    else:
        args = {n: array(v, ctx=ctx) if not isinstance(v, NDArray) else v
                for n, v in zip(arg_names, location)}
    grads = {n: zeros(a.shape, ctx=ctx) for n, a in args.items()}
    aux = None
    if aux_states is not None:
        aux_names = sym.list_auxiliary_states()
        if isinstance(aux_states, dict):
            aux = {k: array(v, ctx=ctx) if not isinstance(v, NDArray) else v
                   for k, v in aux_states.items()}
        else:
            aux = {n: array(v, ctx=ctx) if not isinstance(v, NDArray) else v
                   for n, v in zip(aux_names, aux_states)}
    return sym.bind(ctx, args, grads, grad_req, aux)


def check_symbolic_forward(sym, location, expected, rtol=1e-4, atol=1e-6,
                           aux_states=None, ctx=None, is_train=False):
    exe = _bind(sym, location, aux_states, ctx=ctx)
    outs = exe.forward(is_train=is_train)
    for out, exp in zip(outs, expected):
        assert_almost_equal(out.asnumpy(), exp, rtol, atol,
                            names=("forward", "expected"))
    return outs


def check_symbolic_backward(sym, location, out_grads, expected, rtol=1e-4,
                            atol=1e-6, aux_states=None, grad_req="write",
                            ctx=None):
    exe = _bind(sym, location, aux_states, grad_req=grad_req, ctx=ctx)
    exe.forward(is_train=True)
    exe.backward([array(g) if not isinstance(g, NDArray) else g
                  for g in out_grads])
    if isinstance(expected, dict):
        for name, exp in expected.items():
            assert_almost_equal(exe.grad_dict[name].asnumpy(), exp, rtol, atol,
                                names=("grad_" + name, "expected"))
    else:
        for name, exp in zip(sym.list_arguments(), expected):
            if exp is None:
                continue
            assert_almost_equal(exe.grad_dict[name].asnumpy(), exp, rtol, atol,
                                names=("grad_" + name, "expected"))
    return exe


def numeric_grad(f, x, eps=1e-4):
    """Central-difference gradient of scalar f at numpy array x."""
    grad = _np.zeros_like(x)
    it = _np.nditer(x, flags=["multi_index"])
    while not it.finished:
        idx = it.multi_index
        orig = x[idx]
        x[idx] = orig + eps
        fp = f(x)
        x[idx] = orig - eps
        fm = f(x)
        x[idx] = orig
        grad[idx] = (fp - fm) / (2 * eps)
        it.iternext()
    return grad


def check_numeric_gradient(sym, location, aux_states=None, numeric_eps=1e-3,
                           rtol=1e-2, atol=1e-3, grad_nodes=None, ctx=None):
    """Compare AD gradients vs central differences on sum(outputs)
    (parity: test_utils.check_numeric_gradient)."""
    ctx = ctx or default_context()
    arg_names = sym.list_arguments()
    if not isinstance(location, dict):
        location = dict(zip(arg_names, location))
    location = {k: (v.asnumpy() if isinstance(v, NDArray)
                    else _np.asarray(v, dtype=_np.float64))
                for k, v in location.items()}
    grad_nodes = grad_nodes or [n for n in arg_names]

    exe = _bind(sym, {k: v.astype(_np.float32) for k, v in location.items()},
                aux_states, ctx=ctx)
    exe.forward(is_train=True)
    out_grads = [array(_np.ones(o.shape, dtype=_np.float32)) for o in exe.outputs]
    exe.backward(out_grads)

    # one extra executor reused across all perturbed evals (rebinding per
    # eval would pay jit dispatch setup hundreds of times)
    probe = _bind(sym, {k: v.astype(_np.float32) for k, v in location.items()},
                  aux_states, ctx=ctx)

    for name in grad_nodes:
        def f(xnew, _name=name):
            outs = probe.forward(is_train=True,
                                 **{_name: xnew.astype(_np.float32)})
            return sum(float(o.asnumpy().sum()) for o in outs)

        ngrad = numeric_grad(f, location[name].copy(), eps=numeric_eps)
        agrad = exe.grad_dict[name].asnumpy()
        assert_almost_equal(agrad, ngrad.astype(_np.float32), rtol, atol,
                            names=("autograd_" + name, "numeric_" + name))


def check_consistency(sym, location, ctx_list=None, aux_states=None,
                      dtypes=(_np.float32,), rtol=1e-3, atol=1e-4,
                      grad_req="write", scale=1.0):
    """Cross-configuration consistency harness.

    Parity: test_utils.check_consistency (the reference compares cpu vs
    gpu executors across dtypes, tests/python/gpu/test_operator_gpu.py).
    The TPU analog compares, for each dtype:
      - the compiled path (jit executor) on each ctx in ``ctx_list``
        (default: every distinct jax platform visible), and
      - the interpret path (jax.disable_jit) on the first ctx,
    asserting outputs and input gradients agree with the first
    configuration.  Returns the list of (outputs, grads) per config.
    """
    import jax
    from .context import Context, cpu as _cpu, tpu as _tpu

    if ctx_list is None:
        platforms = {d.platform for d in jax.devices()}
        ctx_list = [_cpu()]
        if platforms - {"cpu"}:
            ctx_list.append(_tpu())

    arg_names = sym.list_arguments()
    if not isinstance(location, dict):
        location = dict(zip(arg_names, location))

    results = []
    for dtype in dtypes:
        loc = {k: _np.asarray(v, dtype=dtype) * scale
               for k, v in location.items()}
        configs = [("compiled:%s" % c, c, False) for c in ctx_list]
        configs.append(("interpret:%s" % ctx_list[0], ctx_list[0], True))
        base = None
        for tag, ctx, interpret in configs:
            def run():
                exe = _bind(sym, loc, aux_states, grad_req=grad_req,
                            ctx=ctx)
                outs = [o.asnumpy()
                        for o in exe.forward(is_train=True)]
                exe.backward([array(_np.ones_like(o)) for o in outs])
                grads = {n: exe.grad_dict[n].asnumpy()
                         for n in arg_names
                         if exe.grad_dict.get(n) is not None}
                return outs, grads
            if interpret:
                with jax.disable_jit():
                    got = run()
            else:
                got = run()
            if base is None:
                base = (tag, got)
            else:
                b_tag, (b_outs, b_grads) = base
                outs, grads = got
                for i, (a, b) in enumerate(zip(outs, b_outs)):
                    assert_almost_equal(a, b, rtol, atol,
                                        names=(tag, b_tag))
                for n in b_grads:
                    assert_almost_equal(grads[n], b_grads[n], rtol, atol,
                                        names=("grad(%s)@%s" % (n, tag),
                                               "grad(%s)@%s" % (n, b_tag)))
            results.append((tag, got))
    return results


def tpu_lowering_text(jitted, *args):
    """StableHLO text of ``jitted`` lowered FOR a TPU from any host (no
    chip, no libtpu): what a test greps for ``tpu_custom_call`` to see
    that a step placed on a TPU carries its Mosaic kernels.  Traced with
    x64 off — the suite enables it, and Mosaic has no 64-bit types."""
    import jax
    with jax.enable_x64(False):
        return jitted.trace(*args).lower(
            lowering_platforms=("tpu",)).as_text()
