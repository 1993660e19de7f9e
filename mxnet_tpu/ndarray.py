"""NDArray: the imperative n-d array on XLA buffers.

TPU-native reimplementation of the reference's NDArray
(``include/mxnet/ndarray.h:31-369``, ``src/ndarray/ndarray.cc``,
``python/mxnet/ndarray.py``).  Key design translation (SURVEY §7 stage 2):

- The reference pairs every array with an Engine variable and pushes each
  mutation through a threaded dependency engine (ndarray.cc:96-352).  On TPU,
  XLA's async dispatch *is* the dependency engine: every jax op returns
  immediately with a future-backed buffer and data dependencies serialize
  execution.  ``wait_to_read`` maps to ``block_until_ready``.
- In-place mutation (``+=``, ``a[1:3] = x``) has no native XLA analog; we keep
  reference *aliasing semantics* with write-through views: ``a[i]``/``slice``
  return views holding a getter/setter pair onto the parent buffer; writes
  rebind the parent's buffer via ``.at[].set()`` (donation makes this cheap
  under jit) and reads always see the parent's current buffer.
- The per-op registered-function table (``NDArrayFunctionReg``,
  include/mxnet/ndarray.h:508) becomes plain module functions; the same
  compute bodies are shared with the symbolic op registry so imperative and
  symbolic results agree (mirrors how simple-ops register into both paths,
  src/operator/operator_util.cc:87-120).
"""
from __future__ import annotations

import struct
import sys
import threading
import weakref

import numpy as _np

from .base import MXNetError, mx_real_t, dtype_np_to_mx, dtype_mx_to_np
from .context import Context, current_context

__all__ = [
    "NDArray", "zeros", "ones", "empty", "full", "array", "arange",
    "concatenate", "load", "save", "waitall", "onehot_encode", "imdecode",
    "on_context",
]

import jax
import jax.numpy as jnp

# weak registry of this framework's arrays; waitall() blocks on these
# instead of scanning the process-wide jax heap
_LIVE = weakref.WeakSet()
# Guards _LIVE snapshot/insert: background threads (PrefetchingIter
# workers, async-checkpoint engine callbacks) create NDArrays while
# waitall iterates, and WeakSet raises on concurrent mutation.
_LIVE_LOCK = threading.Lock()


def _on_device(data, dev):
    """``data`` as a jax array whose storage includes ``dev``.  Arrays
    already there — mesh-sharded ones included — pass through untouched."""
    if isinstance(data, jax.Array) and dev in data.devices():
        return data
    return jax.device_put(data, dev)


class NDArray:
    """An n-dimensional array whose storage lives on a JAX device.

    Parity: include/mxnet/ndarray.h:31.  Unlike the reference there is no
    explicit Chunk{Storage::Handle, Engine::Var}; the jax.Array plays both
    roles (buffer + dependency token).
    """

    __slots__ = ("_storage", "_ctx", "_writable", "_parent", "_getter",
                 "_setter", "_fresh", "__weakref__")

    def __init__(self, data, ctx=None, writable=True, _parent=None,
                 _getter=None, _setter=None):
        with _LIVE_LOCK:
            _LIVE.add(self)
        self._parent = _parent
        self._getter = _getter
        self._setter = _setter
        self._writable = writable
        self._fresh = None
        if _parent is not None:
            self._storage = None
            self._ctx = _parent._ctx
            return
        if isinstance(data, NDArray):
            data = data.data
        if not isinstance(data, (jax.Array, _np.ndarray)):
            data = jnp.asarray(data)
        ctx = ctx if ctx is not None else current_context()
        self._storage = _on_device(data, ctx.jax_device)
        self._ctx = ctx

    # ------------------------------------------------------------------
    # storage access (views resolve through the parent lazily => aliasing)
    # ------------------------------------------------------------------
    @property
    def data(self):
        """Current jax.Array value (resolves views against the live parent)."""
        if self._parent is not None:
            return self._getter(self._parent.data)
        return self._storage

    def _set_data(self, value):
        """Rebind the underlying buffer; views write through to the parent.

        This is the moral equivalent of an engine write-dependency push
        (threaded_engine.cc:53-79): in XLA, rebinding to a new buffer whose
        computation depends on the old one gives the same serialization.
        The new buffer stays on this array's context: a value computed
        elsewhere (another context, jax's default device) is moved here.
        """
        if not self._writable:
            raise MXNetError("trying to write to a read-only NDArray")
        if self._parent is not None:
            value = jnp.asarray(value, dtype=self.dtype)
            if value.shape != self.shape:
                value = jnp.broadcast_to(value, self.shape)
            self._parent._set_data(self._setter(self._parent.data, value))
        else:
            self._storage = self._placed(value)

    def _placed(self, value):
        """``value`` as :meth:`_set_data` stores it in an array that is no
        view: of this array's dtype and shape, on its context's device.
        Handed back to :meth:`_set_data` it is bound as it is, so a copy
        can be issued ahead of the rebind (``executor_group``'s stage)."""
        # a host value bound for storage is shaped on the host, so it
        # reaches the context's device in one transfer
        xp = jnp if isinstance(value, jax.Array) else _np
        value = xp.asarray(value, dtype=self.dtype)
        if value.shape != self.shape:
            value = xp.broadcast_to(value, self.shape)
        return _on_device(value, self._ctx.jax_device)

    def _bind_fresh(self, value):
        """:meth:`_set_data` for a buffer that a compiled call has just
        produced for this array alone (a train step's new weight), marked
        as such so that :meth:`_donatable` may hand it on."""
        self._set_data(value)
        self._fresh = None if self._parent is not None \
            else weakref.ref(self._storage)

    def _donatable(self):
        """This array's buffer if a call may consume (donate) it, else
        ``None`` (hand the call a copy).  jax arrays are immutable, so
        buffers are shared freely: ``copy``, ``copyto``, ``set_params``
        and a caller's own dict bind one jax array twice, a zero-copy host
        view holds it, and ``device_put`` makes new arrays over the old
        buffer.  Donating a shared buffer would delete it under the
        others.  So only a buffer that :meth:`_bind_fresh` bound and that
        nothing else refers to is given away (the source system's engine
        asks the same of a chunk before it writes in place)."""
        buf = self._storage         # ``None`` in a view
        fresh = self._fresh
        # held here: the slot, ``buf``, and getrefcount's own argument
        if buf is not None and fresh is not None and fresh() is buf \
                and sys.getrefcount(buf) <= 3:
            return buf
        return None

    # ------------------------------------------------------------------
    # basic properties
    # ------------------------------------------------------------------
    @property
    def shape(self):
        return tuple(self.data.shape)

    @property
    def size(self):
        n = 1
        for s in self.shape:
            n *= s
        return n

    @property
    def ndim(self):
        return len(self.shape)

    @property
    def dtype(self):
        return _np.dtype(self.data.dtype)

    @property
    def context(self) -> Context:
        return self._ctx

    @property
    def writable(self):
        return self._writable

    def __repr__(self):
        return "<NDArray %s @%s>" % ("x".join(str(s) for s in self.shape), self._ctx)

    def __len__(self):
        if not self.shape:
            raise TypeError("len() of unsized object")
        return self.shape[0]

    # ------------------------------------------------------------------
    # sync points (engine WaitToRead/WaitToWrite/WaitForAll parity,
    # include/mxnet/ndarray.h:108-124)
    # ------------------------------------------------------------------
    def wait_to_read(self):
        self.data.block_until_ready()

    def wait_to_write(self):
        self.data.block_until_ready()

    # ------------------------------------------------------------------
    # host interop
    # ------------------------------------------------------------------
    def asnumpy(self):
        """Blocking copy to host numpy (the reference's big sync point)."""
        return _np.asarray(jax.device_get(self.data))

    def asscalar(self):
        if self.size != 1:
            raise MXNetError("the array is not a scalar (shape %s)" % (self.shape,))
        return self.asnumpy().reshape(())[()]

    def astype(self, dtype):
        res = empty(self.shape, ctx=self._ctx, dtype=dtype)
        self.copyto(res)
        return res

    # ------------------------------------------------------------------
    # copy / context movement (CopyFromTo, src/ndarray/ndarray.cc:286)
    # ------------------------------------------------------------------
    def copyto(self, other):
        if isinstance(other, NDArray):
            if other is self:
                return other
            other._set_data(self.data.astype(other.dtype))
            return other
        if isinstance(other, Context):
            ret = NDArray(self.data, ctx=other)
            return ret
        raise MXNetError("copyto does not support type %s" % type(other))

    def copy(self):
        return self.copyto(self._ctx)

    def as_in_context(self, context):
        if self._ctx == context:
            return self
        return self.copyto(context)

    # ------------------------------------------------------------------
    # views: slice/at/reshape (zero-copy in the reference,
    # include/mxnet/ndarray.h:241-275; here write-through views)
    # ------------------------------------------------------------------
    def slice(self, start, stop):
        start, stop = int(start), int(stop)
        return NDArray(None, _parent=self, _getter=lambda d: d[start:stop],
                       _setter=lambda d, v: d.at[start:stop].set(v),
                       writable=self._writable)

    def at(self, idx):
        idx = int(idx)
        return NDArray(None, _parent=self, _getter=lambda d: d[idx],
                       _setter=lambda d, v: d.at[idx].set(v),
                       writable=self._writable)

    def reshape(self, shape):
        shape = tuple(int(s) for s in shape)
        # -1 wildcard
        if any(s == -1 for s in shape):
            known = 1
            for s in shape:
                if s != -1:
                    known *= s
            shape = tuple(self.size // known if s == -1 else s for s in shape)
        if _np.prod(shape, dtype=_np.int64) != self.size:
            raise MXNetError("reshape size mismatch %s -> %s" % (self.shape, shape))
        parent_shape = self.shape
        return NDArray(None, _parent=self,
                       _getter=lambda d: d.reshape(shape),
                       _setter=lambda d, v: v.reshape(parent_shape),
                       writable=self._writable)

    @property
    def T(self):
        return transpose(self)

    # ------------------------------------------------------------------
    # indexing
    # ------------------------------------------------------------------
    def __getitem__(self, key):
        if isinstance(key, int):
            return self.at(key)
        if isinstance(key, slice):
            if key.step is not None and key.step != 1:
                raise MXNetError("slice step not supported")
            start = key.start if key.start is not None else 0
            stop = key.stop if key.stop is not None else self.shape[0]
            return self.slice(start, stop)
        raise MXNetError("NDArray only supports int and contiguous slice indexing; "
                         "use .asnumpy() for fancy indexing")

    def __setitem__(self, key, value):
        if not self._writable:
            raise MXNetError("trying to write to a read-only NDArray")
        if isinstance(key, slice) and key.start is None and key.stop is None:
            if isinstance(value, NDArray):
                value = value.data
            self._set_data(value)
            return
        view = self[key]
        if isinstance(value, NDArray):
            value = value.data
        view._set_data(value)

    # ------------------------------------------------------------------
    # arithmetic (imperative path; parity src/ndarray/ndarray.cc:96-225)
    # ------------------------------------------------------------------
    def _binary(self, other, fn, reverse=False):
        rhs = other.data if isinstance(other, NDArray) else other
        lhs = self.data
        if reverse:
            lhs, rhs = rhs, lhs
        return NDArray(fn(lhs, rhs), ctx=self._ctx)

    def __add__(self, other):
        return self._binary(other, jnp.add)

    __radd__ = __add__

    def __sub__(self, other):
        return self._binary(other, jnp.subtract)

    def __rsub__(self, other):
        return self._binary(other, jnp.subtract, reverse=True)

    def __mul__(self, other):
        return self._binary(other, jnp.multiply)

    __rmul__ = __mul__

    def __truediv__(self, other):
        return self._binary(other, jnp.divide)

    def __rtruediv__(self, other):
        return self._binary(other, jnp.divide, reverse=True)

    __div__ = __truediv__
    __rdiv__ = __rtruediv__

    def __pow__(self, other):
        return self._binary(other, jnp.power)

    def __rpow__(self, other):
        return self._binary(other, jnp.power, reverse=True)

    def __neg__(self):
        return NDArray(-self.data, ctx=self._ctx)

    def __eq__(self, other):
        return self._binary(other, lambda a, b: (a == b).astype(a.dtype))

    def __ne__(self, other):
        return self._binary(other, lambda a, b: (a != b).astype(a.dtype))

    def __gt__(self, other):
        return self._binary(other, lambda a, b: (a > b).astype(a.dtype))

    def __ge__(self, other):
        return self._binary(other, lambda a, b: (a >= b).astype(a.dtype))

    def __lt__(self, other):
        return self._binary(other, lambda a, b: (a < b).astype(a.dtype))

    def __le__(self, other):
        return self._binary(other, lambda a, b: (a <= b).astype(a.dtype))

    def __hash__(self):
        return id(self)

    def __bool__(self):
        raise MXNetError("NDArray truth value is ambiguous; use .asscalar()")

    # in-place: rebind buffer (write-through for views)
    def _inplace(self, other, fn):
        rhs = other.data if isinstance(other, NDArray) else other
        self._set_data(fn(self.data, rhs))
        return self

    def __iadd__(self, other):
        return self._inplace(other, jnp.add)

    def __isub__(self, other):
        return self._inplace(other, jnp.subtract)

    def __imul__(self, other):
        return self._inplace(other, jnp.multiply)

    def __itruediv__(self, other):
        return self._inplace(other, jnp.divide)

    __idiv__ = __itruediv__


# ----------------------------------------------------------------------
# creation functions (python/mxnet/ndarray.py zeros/ones/array/... parity)
# ----------------------------------------------------------------------
def _as_shape(shape):
    if isinstance(shape, int):
        return (shape,)
    return tuple(int(s) for s in shape)


def empty(shape, ctx=None, dtype=mx_real_t):
    return NDArray(jnp.empty(_as_shape(shape), dtype=dtype), ctx=ctx)


def zeros(shape, ctx=None, dtype=mx_real_t):
    return NDArray(jnp.zeros(_as_shape(shape), dtype=dtype), ctx=ctx)


def ones(shape, ctx=None, dtype=mx_real_t):
    return NDArray(jnp.ones(_as_shape(shape), dtype=dtype), ctx=ctx)


def full(shape, val, ctx=None, dtype=mx_real_t):
    return NDArray(jnp.full(_as_shape(shape), val, dtype=dtype), ctx=ctx)


def array(source_array, ctx=None, dtype=None):
    if isinstance(source_array, NDArray):
        src = source_array.data
        return NDArray(src.astype(dtype or src.dtype), ctx=ctx)
    # host data goes straight to the context's device (one transfer, not a
    # detour through jax's default device)
    src = _np.asarray(source_array)
    dtype = dtype or (src.dtype if src.dtype != _np.float64 else mx_real_t)
    return NDArray(src.astype(dtype, copy=False), ctx=ctx)


def on_context(value, ctx):
    """``value`` (an NDArray of any context, or host data) as an NDArray
    on ``ctx``; an NDArray already there is returned itself, so callers
    that bind the same weights many times share one copy."""
    if isinstance(value, NDArray):
        return value.as_in_context(ctx)
    return array(value, ctx=ctx)


def arange(start, stop=None, step=1.0, repeat=1, ctx=None, dtype=mx_real_t):
    arr = jnp.arange(start, stop, step, dtype=dtype)
    if repeat != 1:
        arr = jnp.repeat(arr, repeat)
    return NDArray(arr, ctx=ctx)


def concatenate(arrays, axis=0, always_copy=True):
    if not always_copy and len(arrays) == 1:
        return arrays[0]
    return NDArray(jnp.concatenate([a.data for a in arrays], axis=axis),
                   ctx=arrays[0].context)


def waitall():
    """Block until all launched work completes (Engine::WaitForAll parity):
    drains the host-side dependency engine (prefetch/decode/checkpoint
    pushes), then blocks on every live NDArray's buffer — a weak registry
    of this framework's arrays, not a scan of the whole process heap."""
    from . import engine as _engine
    eng = _engine._ENGINE
    if eng is not None:
        eng.wait_for_all()
    with _LIVE_LOCK:
        live = list(_LIVE)
    for arr in live:
        data = arr._storage
        if data is not None and hasattr(data, "block_until_ready"):
            try:
                data.block_until_ready()
            except Exception:
                pass


# ----------------------------------------------------------------------
# registered functions (parity: src/ndarray/ndarray.cc:783-944 table)
# ----------------------------------------------------------------------
def _unary(fn):
    def wrapped(data, out=None):
        res = fn(data.data)
        if out is not None:
            out._set_data(res)
            return out
        return NDArray(res, ctx=data.context)
    return wrapped


sqrt = _unary(jnp.sqrt)
rsqrt = _unary(lambda x: 1.0 / jnp.sqrt(x))
exp = _unary(jnp.exp)
log = _unary(jnp.log)
cos = _unary(jnp.cos)
sin = _unary(jnp.sin)
abs = _unary(jnp.abs)  # noqa: A001 - parity with mx.nd.abs
sign = _unary(jnp.sign)
round = _unary(jnp.round)  # noqa: A001
ceil = _unary(jnp.ceil)
floor = _unary(jnp.floor)
square = _unary(jnp.square)


def negative(data, out=None):
    return _unary(jnp.negative)(data, out)


def dot(lhs, rhs, out=None):
    """2-D matrix product (simple op ``dot``, src/operator/matrix_op*)."""
    res = jnp.dot(lhs.data, rhs.data, preferred_element_type=lhs.dtype)
    if out is not None:
        out._set_data(res)
        return out
    return NDArray(res, ctx=lhs.context)


def batch_dot(lhs, rhs, out=None):
    res = jnp.matmul(lhs.data, rhs.data)
    if out is not None:
        out._set_data(res)
        return out
    return NDArray(res, ctx=lhs.context)


def clip(data, a_min, a_max, out=None):
    res = jnp.clip(data.data, a_min, a_max)
    if out is not None:
        out._set_data(res)
        return out
    return NDArray(res, ctx=data.context)


def add(lhs, rhs):
    """Elementwise sum, either operand NDArray or scalar (reference
    ndarray.py add)."""
    return lhs + rhs if isinstance(lhs, NDArray) else rhs + lhs


def subtract(lhs, rhs):
    if isinstance(lhs, NDArray):
        return lhs - rhs
    return rhs.__rsub__(lhs)


def multiply(lhs, rhs):
    return lhs * rhs if isinstance(lhs, NDArray) else rhs * lhs


def divide(lhs, rhs):
    if isinstance(lhs, NDArray):
        return lhs / rhs
    return rhs.__rtruediv__(lhs)


true_divide = divide


def power(lhs, rhs):
    if isinstance(lhs, NDArray):
        return lhs ** rhs
    return rhs.__rpow__(lhs)


def maximum(lhs, rhs):
    l = lhs.data if isinstance(lhs, NDArray) else lhs
    r = rhs.data if isinstance(rhs, NDArray) else rhs
    ctx = lhs.context if isinstance(lhs, NDArray) else rhs.context
    return NDArray(jnp.maximum(l, r), ctx=ctx)


def minimum(lhs, rhs):
    l = lhs.data if isinstance(lhs, NDArray) else lhs
    r = rhs.data if isinstance(rhs, NDArray) else rhs
    ctx = lhs.context if isinstance(lhs, NDArray) else rhs.context
    return NDArray(jnp.minimum(l, r), ctx=ctx)


def sum(data, axis=None, keepdims=False):  # noqa: A001
    return NDArray(jnp.sum(data.data, axis=axis, keepdims=keepdims), ctx=data.context)


def max(data, axis=None, keepdims=False):  # noqa: A001
    return NDArray(jnp.max(data.data, axis=axis, keepdims=keepdims), ctx=data.context)


def min(data, axis=None, keepdims=False):  # noqa: A001
    return NDArray(jnp.min(data.data, axis=axis, keepdims=keepdims), ctx=data.context)


def argmax(data, axis=None, keepdims=False):
    res = jnp.argmax(data.data, axis=axis, keepdims=keepdims).astype(data.dtype)
    return NDArray(res, ctx=data.context)


def argmax_channel(data):
    """argmax over axis 1 (channel), parity with the reference simple op."""
    return NDArray(jnp.argmax(data.data, axis=1).astype(data.dtype), ctx=data.context)


def norm(data):
    return NDArray(jnp.sqrt(jnp.sum(jnp.square(data.data))), ctx=data.context)


def transpose(data, axes=None):
    return NDArray(jnp.transpose(data.data, axes=axes), ctx=data.context)


def swapaxes(data, dim1, dim2):
    return NDArray(jnp.swapaxes(data.data, dim1, dim2), ctx=data.context)


def expand_dims(data, axis):
    return NDArray(jnp.expand_dims(data.data, axis), ctx=data.context)


def flip(data, axis):
    return NDArray(jnp.flip(data.data, axis), ctx=data.context)


def crop(data, begin, end):
    idx = tuple(slice(b, e) for b, e in zip(begin, end))
    return NDArray(data.data[idx], ctx=data.context)


def slice_axis(data, axis, begin, end):
    idx = [slice(None)] * data.ndim
    if end is None or end == 0:
        end = data.shape[axis]
    idx[axis] = slice(begin, end)
    return NDArray(data.data[tuple(idx)], ctx=data.context)


def broadcast_to(data, shape):
    return NDArray(jnp.broadcast_to(data.data, _as_shape(shape)), ctx=data.context)


def broadcast_axis(data, axis, size):
    axes = axis if isinstance(axis, (list, tuple)) else (axis,)
    sizes = size if isinstance(size, (list, tuple)) else (size,)
    shape = list(data.shape)
    for ax, s in zip(axes, sizes):
        shape[ax] = s
    return broadcast_to(data, shape)


def smooth_l1(data, scalar=1.0):
    """Huber-ish loss used by Faster R-CNN (src/operator/smooth_l1_unary*)."""
    sigma2 = scalar * scalar
    x = data.data
    res = jnp.where(jnp.abs(x) < 1.0 / sigma2,
                    0.5 * sigma2 * jnp.square(x),
                    jnp.abs(x) - 0.5 / sigma2)
    return NDArray(res, ctx=data.context)


def softmax_cross_entropy(data, label):
    """Simple op ``softmax_cross_entropy`` (scalar output)."""
    logits = data.data
    lab = label.data.astype(jnp.int32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, lab[:, None], axis=-1)
    return NDArray(jnp.sum(nll), ctx=data.context)


def onehot_encode(indices, out):
    """_onehot_encode (ndarray.cc:795): out[i, indices[i]] = 1."""
    depth = out.shape[1]
    res = jax.nn.one_hot(indices.data.astype(jnp.int32), depth, dtype=out.dtype)
    out._set_data(res)
    return out


def choose_element_0index(lhs, rhs, out=None):
    """out[i] = lhs[i, rhs[i]] (ndarray.cc registered fn)."""
    idx = rhs.data.astype(jnp.int32)
    res = jnp.take_along_axis(lhs.data, idx[:, None], axis=1)[:, 0]
    if out is not None:
        out._set_data(res)
        return out
    return NDArray(res, ctx=lhs.context)


def fill_element_0index(lhs, mhs, rhs, out=None):
    """out = lhs with out[i, rhs[i]] = mhs[i] (three-operand fill)."""
    idx = rhs.data.astype(jnp.int32)
    res = lhs.data.at[jnp.arange(lhs.shape[0]), idx].set(mhs.data)
    if out is not None:
        out._set_data(res)
        return out
    return NDArray(res, ctx=lhs.context)


def elementwise_sum(arrays, out=None):
    """ElementwiseSum (src/ndarray/ndarray.cc:352)."""
    res = arrays[0].data
    for a in arrays[1:]:
        res = res + a.data
    if out is not None:
        out._set_data(res)
        return out
    return NDArray(res, ctx=arrays[0].context)


add_n = elementwise_sum


def imdecode(str_img, clip_rect=(0, 0, 0, 0), out=None, index=0, channels=3, mean=None):
    """Decode an image buffer (gated: needs PIL or cv2; parity _imdecode)."""
    import io as _io
    try:
        from PIL import Image  # type: ignore
        img = _np.asarray(Image.open(_io.BytesIO(str_img)).convert("RGB"))
    except ImportError:
        raise MXNetError("imdecode requires PIL (not available)")
    img = img.transpose(2, 0, 1).astype(mx_real_t)  # HWC -> CHW
    if mean is not None:
        img = img - mean.asnumpy()
    if clip_rect != (0, 0, 0, 0):
        x0, y0, x1, y1 = clip_rect
        img = img[:, y0:y1, x0:x1]
    res = array(img[None])
    if out is not None:
        out._set_data(res.data)
        return out
    return res


# ----------------------------------------------------------------------
# save / load (parity: src/ndarray/ndarray.cc:637-700; magic 0x112)
# ----------------------------------------------------------------------
_MAGIC = 0x112
_RESERVED = 0


def _write_str(fo, s):
    b = s.encode("utf-8")
    fo.write(struct.pack("<Q", len(b)))
    fo.write(b)


def _read_str(fi):
    (n,) = struct.unpack("<Q", fi.read(8))
    return fi.read(n).decode("utf-8")


def _save_one(fo, arr: NDArray):
    # TShape: uint32 ndim + uint32 dims (mshadow layout)
    fo.write(struct.pack("<I", arr.ndim))
    fo.write(struct.pack("<%dI" % arr.ndim, *arr.shape))
    # Context: int32 dev_type, int32 dev_id (include/mxnet/base.h:85)
    fo.write(struct.pack("<ii", arr.context.device_typeid, arr.context.device_id))
    # type flag + raw data
    npy = arr.asnumpy()
    fo.write(struct.pack("<i", dtype_np_to_mx(npy.dtype)))
    fo.write(npy.tobytes())


def _load_one(fi) -> NDArray:
    (ndim,) = struct.unpack("<I", fi.read(4))
    shape = struct.unpack("<%dI" % ndim, fi.read(4 * ndim)) if ndim else ()
    dev_type, dev_id = struct.unpack("<ii", fi.read(8))
    (flag,) = struct.unpack("<i", fi.read(4))
    dtype = dtype_mx_to_np(flag)
    count = int(_np.prod(shape, dtype=_np.int64)) if shape else 1
    buf = fi.read(count * dtype.itemsize)
    npy = _np.frombuffer(buf, dtype=dtype).reshape(shape)
    # arrays load onto the default context (GPU/TPU arrays were staged via CPU)
    return array(npy, dtype=dtype)


def save(fname, data):
    """Save NDArrays (save_checkpoint file format): a str->NDArray dict,
    a list of arrays, or a list of (name, array) pairs.  Caller's order
    is the file's order, duplicates included — the reference
    MXNDArraySave writes names exactly as given."""
    if isinstance(data, NDArray):
        data = [data]
    names = []
    arrays = []
    if isinstance(data, dict):
        for k in data:
            names.append(k)
            arrays.append(data[k])
    elif data and all(isinstance(item, tuple) and len(item) == 2
                      for item in data):
        for k, v in data:
            names.append(k)
            arrays.append(v)
    else:
        arrays = list(data)
    from .stream import open_uri
    with open_uri(fname, "wb") as fo:
        fo.write(struct.pack("<QQ", _MAGIC, _RESERVED))
        fo.write(struct.pack("<Q", len(arrays)))
        for arr in arrays:
            _save_one(fo, arr)
        fo.write(struct.pack("<Q", len(names)))
        for name in names:
            _write_str(fo, name)


def load_raw(fname):
    """-> (names, arrays) exactly as stored — duplicates and file order
    preserved (the C ABI's MXNDArrayLoad contract)."""
    from .stream import open_uri
    with open_uri(fname, "rb") as fi:
        magic, _ = struct.unpack("<QQ", fi.read(16))
        if magic != _MAGIC:
            raise MXNetError("invalid NDArray file %s (bad magic)" % fname)
        (n,) = struct.unpack("<Q", fi.read(8))
        arrays = [_load_one(fi) for _ in range(n)]
        (m,) = struct.unpack("<Q", fi.read(8))
        names = [_read_str(fi) for _ in range(m)]
    return names, arrays


def load(fname):
    names, arrays = load_raw(fname)
    if names:
        return dict(zip(names, arrays))
    return arrays
