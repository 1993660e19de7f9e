"""NDArray tests (modeled on reference tests/python/unittest/test_ndarray.py)."""
import os
import tempfile

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import ndarray as nd


def reldiff(a, b):
    diff = np.sum(np.abs(a - b))
    norm = np.sum(np.abs(a)) + 1e-8
    return diff / norm


def test_creation():
    a = nd.zeros((3, 4))
    assert a.shape == (3, 4)
    assert a.dtype == np.float32
    assert np.all(a.asnumpy() == 0)
    b = nd.ones((2, 3), dtype=np.float64)
    assert b.asnumpy().dtype == np.float64
    c = nd.full((2, 2), 3.5)
    assert np.all(c.asnumpy() == 3.5)
    d = nd.array([[1, 2], [3, 4]])
    assert d.shape == (2, 2)
    e = nd.arange(0, 10, 2)
    assert np.allclose(e.asnumpy(), np.arange(0, 10, 2))


def test_elementwise():
    rng = np.random.RandomState(0)
    x = rng.rand(4, 5).astype(np.float32)
    y = rng.rand(4, 5).astype(np.float32)
    a, b = nd.array(x), nd.array(y)
    assert reldiff((a + b).asnumpy(), x + y) < 1e-6
    assert reldiff((a - b).asnumpy(), x - y) < 1e-6
    assert reldiff((a * b).asnumpy(), x * y) < 1e-6
    assert reldiff((a / b).asnumpy(), x / y) < 1e-5
    assert reldiff((a + 2).asnumpy(), x + 2) < 1e-6
    assert reldiff((2 - a).asnumpy(), 2 - x) < 1e-6
    assert reldiff((-a).asnumpy(), -x) < 1e-6
    assert reldiff((a ** 2).asnumpy(), x ** 2) < 1e-5


def test_inplace():
    x = np.ones((3, 3), dtype=np.float32)
    a = nd.array(x)
    a += 2
    assert np.all(a.asnumpy() == 3)
    a *= 2
    assert np.all(a.asnumpy() == 6)
    a -= 1
    assert np.all(a.asnumpy() == 5)
    a /= 5
    assert np.all(a.asnumpy() == 1)


def test_slice_view_aliasing():
    """Reference semantics: slices are views into the parent chunk
    (include/mxnet/ndarray.h:241-275)."""
    a = nd.zeros((4, 3))
    s = a[1:3]
    s[:] = 7
    out = a.asnumpy()
    assert np.all(out[1:3] == 7)
    assert np.all(out[0] == 0) and np.all(out[3] == 0)
    # writes to parent visible through the view
    a[:] = 1
    assert np.all(s.asnumpy() == 1)
    # at() view
    row = a.at(2)
    row[:] = 5
    assert np.all(a.asnumpy()[2] == 5)


def test_setitem():
    a = nd.zeros((4, 3))
    a[1] = 2.0
    assert np.all(a.asnumpy()[1] == 2)
    a[2:4] = nd.ones((2, 3))
    assert np.all(a.asnumpy()[2:4] == 1)


def test_reshape_view():
    a = nd.array(np.arange(12, dtype=np.float32).reshape(3, 4))
    b = a.reshape((4, 3))
    assert b.shape == (4, 3)
    b[:] = 0
    assert np.all(a.asnumpy() == 0)
    c = a.reshape((2, -1))
    assert c.shape == (2, 6)


def test_copyto():
    a = nd.array(np.arange(6, dtype=np.float32).reshape(2, 3))
    b = nd.zeros((2, 3))
    a.copyto(b)
    assert np.allclose(b.asnumpy(), a.asnumpy())
    c = a.copyto(mx.cpu(0))
    assert np.allclose(c.asnumpy(), a.asnumpy())
    d = a.copy()
    d += 1
    assert not np.allclose(d.asnumpy(), a.asnumpy())


def test_registered_functions():
    rng = np.random.RandomState(1)
    x = rng.rand(3, 4).astype(np.float32) + 0.5
    a = nd.array(x)
    assert reldiff(nd.sqrt(a).asnumpy(), np.sqrt(x)) < 1e-5
    assert reldiff(nd.exp(a).asnumpy(), np.exp(x)) < 1e-5
    assert reldiff(nd.log(a).asnumpy(), np.log(x)) < 1e-5
    assert reldiff(nd.square(a).asnumpy(), x ** 2) < 1e-5
    assert reldiff(nd.clip(a, 0.6, 0.9).asnumpy(), np.clip(x, 0.6, 0.9)) < 1e-6
    assert reldiff(nd.sum(a).asnumpy(), x.sum()) < 1e-5
    assert reldiff(nd.norm(a).asnumpy(), np.sqrt((x ** 2).sum())) < 1e-5
    assert reldiff(nd.transpose(a).asnumpy(), x.T) < 1e-6


def test_dot():
    rng = np.random.RandomState(2)
    x = rng.rand(3, 4).astype(np.float32)
    y = rng.rand(4, 5).astype(np.float32)
    assert reldiff(nd.dot(nd.array(x), nd.array(y)).asnumpy(), x.dot(y)) < 1e-4
    bx = rng.rand(2, 3, 4).astype(np.float32)
    by = rng.rand(2, 4, 5).astype(np.float32)
    assert reldiff(nd.batch_dot(nd.array(bx), nd.array(by)).asnumpy(),
                   np.matmul(bx, by)) < 1e-4


def test_onehot_and_choose():
    idx = nd.array(np.array([1, 0, 2], dtype=np.float32))
    out = nd.zeros((3, 3))
    nd.onehot_encode(idx, out)
    expect = np.eye(3, dtype=np.float32)[[1, 0, 2]]
    assert np.allclose(out.asnumpy(), expect)
    mat = nd.array(np.arange(9, dtype=np.float32).reshape(3, 3))
    picked = nd.choose_element_0index(mat, idx)
    assert np.allclose(picked.asnumpy(), [1, 3, 8])


def test_save_load():
    rng = np.random.RandomState(3)
    arrays = [nd.array(rng.rand(3, 4).astype(np.float32)),
              nd.array(rng.rand(5,).astype(np.float32))]
    with tempfile.TemporaryDirectory() as d:
        fname = os.path.join(d, "test.params")
        nd.save(fname, arrays)
        loaded = nd.load(fname)
        assert len(loaded) == 2
        for a, b in zip(arrays, loaded):
            assert np.allclose(a.asnumpy(), b.asnumpy())
        named = {"w": arrays[0], "b": arrays[1]}
        nd.save(fname, named)
        loaded = nd.load(fname)
        assert set(loaded) == {"w", "b"}
        assert np.allclose(loaded["w"].asnumpy(), arrays[0].asnumpy())


def test_scalar_and_compare():
    a = nd.array(np.array([[2.0]], dtype=np.float32))
    assert a.asscalar() == 2.0
    x = nd.array(np.array([1.0, 2.0, 3.0], dtype=np.float32))
    y = nd.array(np.array([2.0, 2.0, 2.0], dtype=np.float32))
    assert np.allclose((x > y).asnumpy(), [0, 0, 1])
    assert np.allclose((x == y).asnumpy(), [0, 1, 0])


def test_broadcast():
    a = nd.array(np.arange(3, dtype=np.float32).reshape(1, 3))
    b = nd.broadcast_to(a, (4, 3))
    assert b.shape == (4, 3)
    assert np.all(b.asnumpy() == np.broadcast_to(np.arange(3), (4, 3)))
    c = nd.broadcast_axis(a, axis=0, size=5)
    assert c.shape == (5, 3)


def test_context():
    a = nd.zeros((2, 2), ctx=mx.cpu(0))
    assert a.context == mx.cpu(0)
    b = a.as_in_context(mx.cpu(1))
    assert b.context == mx.cpu(1)
    assert np.allclose(a.asnumpy(), b.asnumpy())
    assert b.data.devices() == {mx.cpu(1).jax_device}
    # a context is a placement: with no accelerator in the process the
    # accelerator contexts raise instead of landing on the host
    for ctx in (mx.gpu(0), mx.tpu(0)):
        with pytest.raises(mx.base.MXNetError, match="no accelerator"):
            nd.zeros((2, 2), ctx=ctx)
    assert mx.num_tpus() == 0
    assert mx.context.default_device_context() == mx.cpu(0)


def test_write_keeps_device():
    """Every way of writing into an array leaves it on its context's
    device (assignment, views, in-place arithmetic, copyto)."""
    dev = mx.cpu(3).jax_device
    a = nd.zeros((2, 2), ctx=mx.cpu(3))
    a[:] = 1
    assert a.data.devices() == {dev}
    a[0][:] = nd.ones((2,)) * 5          # value computed on cpu(0)
    assert a.data.devices() == {dev}
    a += nd.ones((2, 2))
    assert a.data.devices() == {dev}
    nd.array(np.arange(4.0).reshape(2, 2)).copyto(a)
    assert a.data.devices() == {dev} and a.context == mx.cpu(3)
    assert np.allclose(a.asnumpy(), [[0, 1], [2, 3]])


def test_waitall():
    a = nd.ones((10, 10))
    b = a * 2
    nd.waitall()
    assert np.all(b.asnumpy() == 2)
