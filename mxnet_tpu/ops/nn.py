"""Neural-network operators (the reference's OperatorProperty op set).

Parity: src/operator/*-inl.h (SURVEY §2 "Neural-net operators", 42 ops).
TPU-first translation: every body is a jax-traceable function — convolution
is ``lax.conv_general_dilated`` (lowered by XLA straight onto the MXU instead
of im2col+GEMM, convolution-inl.h:85-162), pooling is ``lax.reduce_window``,
BatchNorm keeps the reference's aux-state contract
(moving_mean/moving_var, batch_norm-inl.h:49,89) via functional aux updates.
"""
from __future__ import annotations

import functools

import numpy as _np

import jax
import jax.numpy as jnp
from jax import lax

from ..base import MXNetError
from ..dparam import Field, ParamStruct
from .registry import (OperatorProperty, register_op, require_known,
                       contract_sharding, dedup_axes, reshape_carry)


# ----------------------------------------------------------------------
# Activation / LeakyReLU / SoftmaxActivation
# ----------------------------------------------------------------------
class _ActivationParam(ParamStruct):
    act_type = Field(str, required=True,
                     enum=("relu", "sigmoid", "tanh", "softrelu", "silu",
                           "gelu"))


@register_op("Activation")
class Activation(OperatorProperty):
    """activation-inl.h; cuDNN fast path -> XLA fuses these into neighbors."""
    param_cls = _ActivationParam

    _FNS = {
        "relu": jax.nn.relu,
        "sigmoid": jax.nn.sigmoid,
        "tanh": jnp.tanh,
        "softrelu": jax.nn.softplus,
        "silu": jax.nn.silu,        # x·sigmoid(x): the gate of a gated FFN
        # the tanh approximation (``gelu_new`` / ``gelu_pytorch_tanh``)
        "gelu": functools.partial(jax.nn.gelu, approximate=True),
    }

    def forward(self, inputs, aux, is_train, rng):
        return [self._FNS[self.param.act_type](inputs[0])], None


class _LeakyReLUParam(ParamStruct):
    act_type = Field(str, default="leaky", enum=("leaky", "elu", "prelu", "rrelu"))
    slope = Field(float, default=0.25)
    lower_bound = Field(float, default=0.125)
    upper_bound = Field(float, default=0.334)


@register_op("LeakyReLU")
class LeakyReLU(OperatorProperty):
    """leaky_relu-inl.h; prelu carries a learnable per-channel gamma arg."""
    param_cls = _LeakyReLUParam
    need_rng = True

    def list_arguments(self):
        if self.param.act_type == "prelu":
            return ["data", "gamma"]
        return ["data"]

    def infer_shape(self, in_shapes):
        data = in_shapes[0]
        if data is None:
            require_known("LeakyReLU", in_shapes[:1], ["data"])
        if self.param.act_type == "prelu":
            gamma = (data[1],)
            return [data, gamma], [data], []
        return [data], [data], []

    def forward(self, inputs, aux, is_train, rng):
        p = self.param
        x = inputs[0]
        if p.act_type == "leaky":
            out = jnp.where(x > 0, x, p.slope * x)
        elif p.act_type == "elu":
            out = jnp.where(x > 0, x, p.slope * (jnp.exp(x) - 1.0))
        elif p.act_type == "prelu":
            gamma = inputs[1].reshape((1, -1) + (1,) * (x.ndim - 2))
            out = jnp.where(x > 0, x, gamma * x)
        else:  # rrelu: random slope in train, mean slope in test
            if is_train and rng is not None:
                slope = jax.random.uniform(rng, x.shape, minval=p.lower_bound,
                                           maxval=p.upper_bound, dtype=x.dtype)
            else:
                slope = (p.lower_bound + p.upper_bound) / 2.0
            out = jnp.where(x > 0, x, slope * x)
        return [out], None


class _SoftmaxActivationParam(ParamStruct):
    mode = Field(str, default="instance", enum=("instance", "channel"))


@register_op("SoftmaxActivation")
class SoftmaxActivation(OperatorProperty):
    param_cls = _SoftmaxActivationParam

    def forward(self, inputs, aux, is_train, rng):
        x = inputs[0]
        if self.param.mode == "channel":
            return [jax.nn.softmax(x, axis=1)], None
        flat = x.reshape((x.shape[0], -1))
        return [jax.nn.softmax(flat, axis=-1).reshape(x.shape)], None


# ----------------------------------------------------------------------
# FullyConnected
# ----------------------------------------------------------------------
class _FCParam(ParamStruct):
    num_hidden = Field(int, required=True, lower=1)
    no_bias = Field(bool, default=False)


@register_op("FullyConnected")
class FullyConnected(OperatorProperty):
    """fully_connected-inl.h:46: y = x_2d · Wᵀ + b, weight (num_hidden, D)."""
    param_cls = _FCParam
    mxu = True

    def list_arguments(self):
        return ["data", "weight"] if self.param.no_bias else ["data", "weight", "bias"]

    def infer_shape(self, in_shapes):
        data = in_shapes[0]
        if data is None:
            require_known("FullyConnected", in_shapes[:1], ["data"])
        num_in = int(_np.prod(data[1:], dtype=_np.int64))
        nh = self.param.num_hidden
        shapes = [data, (nh, num_in)]
        if not self.param.no_bias:
            shapes.append((nh,))
        return shapes, [(data[0], nh)], []

    def forward(self, inputs, aux, is_train, rng):
        x = inputs[0].reshape((inputs[0].shape[0], -1))
        w = inputs[1]
        y = jnp.dot(x, w.T, preferred_element_type=x.dtype)
        if not self.param.no_bias:
            y = y + inputs[2]
        return [y], None

    def cost_mxu_dims(self, in_shapes, out_shapes):
        data = in_shapes[0]
        num_in = int(_np.prod(data[1:], dtype=_np.int64))
        return [(int(data[0]), num_in, int(self.param.num_hidden))]

    def cost_flops(self, in_shapes, out_shapes):
        (m, k, n), = self.cost_mxu_dims(in_shapes, out_shapes)
        bias = m * n if not self.param.no_bias else 0
        return float(2 * m * k * n + bias)

    def infer_sharding(self, in_specs, in_shapes, out_shapes, mesh_shape):
        data, weight = in_specs[0], in_specs[1]
        # forward flattens data[1:]: any sharded non-batch dim is part of
        # the contraction against weight dim 1
        c_idx = next((i for i in range(1, len(data)) if data[i]), None)
        d_c = data[c_idx] if c_idx is not None else ()
        w_c = weight[1] if len(weight) > 1 else ()
        reduce, notes, conflict = contract_sharding(
            d_c, w_c, 0, 1, "FullyConnected")
        required = [None] * len(in_specs)
        if conflict:
            req = list(data)
            req[c_idx] = w_c
            required[0] = tuple(req)
        batch = data[0] if data else ()
        cols = dedup_axes(weight[0] if weight else (), batch)
        if not self.param.no_bias and len(required) > 2:
            required[2] = (cols,)
        out = {"out": [(tuple(batch), cols)], "in": required}
        if reduce:
            out["reduce"] = reduce
        if notes:
            out["notes"] = notes
        return out


class _QuantizedDenseParam(ParamStruct):
    num_hidden = Field(int, required=True, lower=1)
    no_bias = Field(bool, default=False)
    qdtype = Field(str, default="int8", enum=("int8", "fp8_e4m3"))


@register_op("QuantizedDense")
class QuantizedDense(OperatorProperty):
    """Weight-only quantized FullyConnected: y = x_2d · dequant(Wq)ᵀ + b.

    Produced by ``kernels.quantize.quantize_symbol`` rewriting matched
    FullyConnected nodes; weight rides in the quantized storage dtype
    with a per-output-channel float32 ``scale`` argument spliced in at
    index 2.  Forward lowers to ``kernels.quantize.quantized_matmul``
    (Pallas dequant-in-registers on TPU, exact jnp reference elsewhere);
    cost rules price the MXU dims at the quantized dtype so rooflines
    use the int8/fp8 peak tables.
    """
    param_cls = _QuantizedDenseParam
    mxu = True

    def list_arguments(self):
        args = ["data", "weight", "scale"]
        if not self.param.no_bias:
            args.append("bias")
        return args

    def infer_shape(self, in_shapes):
        data = in_shapes[0]
        if data is None:
            require_known("QuantizedDense", in_shapes[:1], ["data"])
        num_in = int(_np.prod(data[1:], dtype=_np.int64))
        nh = self.param.num_hidden
        shapes = [data, (nh, num_in), (nh,)]
        if not self.param.no_bias:
            shapes.append((nh,))
        return shapes, [(data[0], nh)], []

    def infer_type(self, in_types):
        from ..kernels.quantize import storage_dtype
        st = _np.dtype(storage_dtype(self.param.qdtype))
        f32 = _np.dtype(_np.float32)
        wide = next((t for i, t in enumerate(in_types)
                     if t is not None and i not in (1, 2)), None)
        types = [wide, st, f32]
        if not self.param.no_bias:
            types.append(wide)
        return types, [wide], []

    def forward(self, inputs, aux, is_train, rng):
        from ..kernels.quantize import quantized_matmul
        x = inputs[0].reshape((inputs[0].shape[0], -1))
        y = quantized_matmul(x, inputs[1], inputs[2])
        if not self.param.no_bias:
            y = y + inputs[3]
        return [y], None

    # compute dtype of the MXU contraction (roofline prices peaks at it)
    def cost_compute_dtype(self, in_shapes, out_shapes):
        return "fp8" if self.param.qdtype == "fp8_e4m3" else "int8"

    def cost_mxu_dims(self, in_shapes, out_shapes):
        data = in_shapes[0]
        num_in = int(_np.prod(data[1:], dtype=_np.int64))
        return [(int(data[0]), num_in, int(self.param.num_hidden))]

    def cost_flops(self, in_shapes, out_shapes):
        (m, k, n), = self.cost_mxu_dims(in_shapes, out_shapes)
        extra = m * n                       # scale epilogue
        if not self.param.no_bias:
            extra += m * n
        return float(2 * m * k * n + extra)

    def infer_sharding(self, in_specs, in_shapes, out_shapes, mesh_shape):
        data, weight = in_specs[0], in_specs[1]
        c_idx = next((i for i in range(1, len(data)) if data[i]), None)
        d_c = data[c_idx] if c_idx is not None else ()
        w_c = weight[1] if len(weight) > 1 else ()
        reduce, notes, conflict = contract_sharding(
            d_c, w_c, 0, 1, "QuantizedDense")
        required = [None] * len(in_specs)
        if conflict:
            req = list(data)
            req[c_idx] = w_c
            required[0] = tuple(req)
        batch = data[0] if data else ()
        cols = dedup_axes(weight[0] if weight else (), batch)
        # scale (and bias) are per-output-channel rows: follow cols
        if len(required) > 2:
            required[2] = (cols,)
        if not self.param.no_bias and len(required) > 3:
            required[3] = (cols,)
        out = {"out": [(tuple(batch), cols)], "in": required}
        if reduce:
            out["reduce"] = reduce
        if notes:
            out["notes"] = notes
        return out


# ----------------------------------------------------------------------
# Convolution / Deconvolution
# ----------------------------------------------------------------------
class _ConvParam(ParamStruct):
    kernel = Field(tuple, required=True)
    stride = Field(tuple, default=None)
    dilate = Field(tuple, default=None)
    pad = Field(tuple, default=None)
    num_filter = Field(int, required=True, lower=1)
    num_group = Field(int, default=1, lower=1)
    no_bias = Field(bool, default=False)
    workspace = Field(int, default=1024, doc="ignored (XLA plans memory)")
    cudnn_tune = Field(str, default=None, doc="ignored (XLA autotunes)")
    cudnn_off = Field(bool, default=False, doc="ignored")

    def spatial(self):
        k = tuple(self.kernel)
        nd = len(k)
        s = tuple(self.stride) if self.stride else (1,) * nd
        d = tuple(self.dilate) if self.dilate else (1,) * nd
        p = tuple(self.pad) if self.pad else (0,) * nd
        return k, s, d, p


def _conv_dnums(nd):
    # NC + spatial; weights OI + spatial
    spatial = "DHW"[-nd:] if nd <= 3 else None
    if spatial is None:
        raise MXNetError("conv supports 1-3 spatial dims")
    return ("NC" + spatial, "OI" + spatial, "NC" + spatial)


@register_op("Convolution")
class Convolution(OperatorProperty):
    """convolution-inl.h:85-162 (im2col+GEMM there) -> one XLA conv here.

    Weight layout (num_filter, C/num_group, *kernel) = OIHW, matching the
    reference so checkpoints interchange.
    """
    param_cls = _ConvParam
    mxu = True

    def list_arguments(self):
        return ["data", "weight"] if self.param.no_bias else ["data", "weight", "bias"]

    def _out_spatial(self, in_spatial):
        k, s, d, p = self.param.spatial()
        out = []
        for i, (ins, ks, ss, ds, ps) in enumerate(zip(in_spatial, k, s, d, p)):
            eff_k = (ks - 1) * ds + 1
            out.append((ins + 2 * ps - eff_k) // ss + 1)
        return tuple(out)

    def infer_shape(self, in_shapes):
        data = in_shapes[0]
        if data is None:
            require_known("Convolution", in_shapes[:1], ["data"])
        p = self.param
        k, _, _, _ = p.spatial()
        if len(data) != len(k) + 2:
            raise MXNetError("Convolution: data ndim %d vs kernel %s" % (len(data), k))
        wshape = (p.num_filter, data[1] // p.num_group) + k
        shapes = [data, wshape]
        if not p.no_bias:
            shapes.append((p.num_filter,))
        out = (data[0], p.num_filter) + self._out_spatial(data[2:])
        return shapes, [out], []

    def forward(self, inputs, aux, is_train, rng):
        p = self.param
        k, s, d, pad = p.spatial()
        dn = lax.conv_dimension_numbers(inputs[0].shape, inputs[1].shape,
                                        _conv_dnums(len(k)))
        y = lax.conv_general_dilated(
            inputs[0], inputs[1], window_strides=s,
            padding=[(pp, pp) for pp in pad], rhs_dilation=d,
            dimension_numbers=dn, feature_group_count=p.num_group,
            preferred_element_type=inputs[0].dtype)
        if not p.no_bias:
            y = y + inputs[2].reshape((1, -1) + (1,) * len(k))
        return [y], None

    def infer_sharding(self, in_specs, in_shapes, out_shapes, mesh_shape):
        data, weight = in_specs[0], in_specs[1]
        # input channels (data dim 1 x weight dim 1) are the contraction;
        # spatial dims stay replicated (halo exchange is out of scope)
        d_c = data[1] if len(data) > 1 else ()
        w_c = weight[1] if len(weight) > 1 else ()
        reduce, notes, conflict = contract_sharding(
            d_c, w_c, 0, 1, "Convolution")
        required = [None] * len(in_specs)
        if conflict:
            req = list(data)
            req[1] = w_c
            required[0] = tuple(req)
        batch = data[0] if data else ()
        cols = dedup_axes(weight[0] if weight else (), batch)
        if not self.param.no_bias and len(required) > 2:
            required[2] = (cols,)
        spec = (tuple(batch), cols) + ((),) * (len(out_shapes[0]) - 2)
        out = {"out": [spec], "in": required}
        if reduce:
            out["reduce"] = reduce
        if notes:
            out["notes"] = notes
        return out

    def cost_mxu_dims(self, in_shapes, out_shapes):
        # XLA lowers the conv as an im2col matmul per group:
        # (batch*out_spatial) x (C/g * prod(kernel)) x (filters/g)
        p = self.param
        data, out = in_shapes[0], out_shapes[0]
        k, _, _, _ = p.spatial()
        m = int(data[0] * _np.prod(out[2:], dtype=_np.int64))
        kk = int((data[1] // p.num_group) * _np.prod(k, dtype=_np.int64))
        return [(m, kk, p.num_filter // p.num_group)] * p.num_group

    def cost_flops(self, in_shapes, out_shapes):
        flops = sum(2 * m * k * n for m, k, n in
                    self.cost_mxu_dims(in_shapes, out_shapes))
        if not self.param.no_bias:
            flops += int(_np.prod(out_shapes[0], dtype=_np.int64))
        return float(flops)


class _DeconvParam(_ConvParam):
    adj = Field(tuple, default=None)
    target_shape = Field(tuple, default=None)


@register_op("Deconvolution")
class Deconvolution(OperatorProperty):
    """deconvolution-inl.h: transposed conv. Weight (C, num_filter/group, *k)."""
    param_cls = _DeconvParam
    mxu = True

    def list_arguments(self):
        return ["data", "weight"] if self.param.no_bias else ["data", "weight", "bias"]

    def _out_spatial(self, in_spatial):
        p = self.param
        k, s, d, pad = p.spatial()
        adj = tuple(p.adj) if p.adj else (0,) * len(k)
        out = []
        for ins, ks, ss, ds, ps, aj in zip(in_spatial, k, s, d, pad, adj):
            eff_k = (ks - 1) * ds + 1
            out.append(ss * (ins - 1) + eff_k - 2 * ps + aj)
        return tuple(out)

    def infer_shape(self, in_shapes):
        data = in_shapes[0]
        if data is None:
            require_known("Deconvolution", in_shapes[:1], ["data"])
        p = self.param
        k, _, _, _ = p.spatial()
        wshape = (data[1], p.num_filter // p.num_group) + k
        shapes = [data, wshape]
        if not p.no_bias:
            shapes.append((p.num_filter,))
        out = (data[0], p.num_filter) + self._out_spatial(data[2:])
        return shapes, [out], []

    def forward(self, inputs, aux, is_train, rng):
        p = self.param
        if p.num_group != 1:
            raise MXNetError("Deconvolution: num_group > 1 not yet supported")
        k, s, d, pad = p.spatial()
        nd = len(k)
        # gradient-of-conv formulation: dilate lhs by stride, flip kernel
        w = jnp.swapaxes(inputs[1], 0, 1)  # (C, F, *k) -> (F, C, *k)
        w = jnp.flip(w, axis=tuple(range(2, 2 + nd)))
        eff_k = tuple((kk - 1) * dd + 1 for kk, dd in zip(k, d))
        padding = [(ek - 1 - pp, ek - 1 - pp) for ek, pp in zip(eff_k, pad)]
        dn = lax.conv_dimension_numbers(inputs[0].shape, w.shape, _conv_dnums(nd))
        y = lax.conv_general_dilated(
            inputs[0], w, window_strides=(1,) * nd, padding=padding,
            lhs_dilation=s, rhs_dilation=d, dimension_numbers=dn,
            preferred_element_type=inputs[0].dtype)
        if not p.no_bias:
            y = y + inputs[2].reshape((1, -1) + (1,) * nd)
        return [y], None

    def cost_mxu_dims(self, in_shapes, out_shapes):
        # transposed conv: one MAC per input element per (filter, tap)
        p = self.param
        data = in_shapes[0]
        k, _, _, _ = p.spatial()
        m = int(data[0] * _np.prod(data[2:], dtype=_np.int64))
        g = p.num_group
        return [(m, data[1] // g,
                 int((p.num_filter // g) * _np.prod(k, dtype=_np.int64)))] * g

    def cost_flops(self, in_shapes, out_shapes):
        flops = sum(2 * m * k * n for m, k, n in
                    self.cost_mxu_dims(in_shapes, out_shapes))
        if not self.param.no_bias:
            flops += int(_np.prod(out_shapes[0], dtype=_np.int64))
        return float(flops)


# ----------------------------------------------------------------------
# Pooling
# ----------------------------------------------------------------------
class _PoolingParam(ParamStruct):
    kernel = Field(tuple, required=True)
    pool_type = Field(str, default="max", enum=("max", "avg", "sum"))
    stride = Field(tuple, default=None)
    pad = Field(tuple, default=None)
    global_pool = Field(bool, default=False)
    pooling_convention = Field(str, default="valid", enum=("valid", "full"))


@register_op("Pooling")
class Pooling(OperatorProperty):
    """pooling-inl.h -> lax.reduce_window (XLA lowers to TPU windowed reduce)."""
    param_cls = _PoolingParam

    def _conf(self, in_spatial):
        p = self.param
        if p.global_pool:
            k = tuple(in_spatial)
            return k, k, (0,) * len(k)
        k = tuple(p.kernel)
        s = tuple(p.stride) if p.stride else (1,) * len(k)
        pad = tuple(p.pad) if p.pad else (0,) * len(k)
        return k, s, pad

    def _out_spatial(self, in_spatial):
        k, s, pad = self._conf(in_spatial)
        out = []
        for ins, ks, ss, ps in zip(in_spatial, k, s, pad):
            if self.param.pooling_convention == "full":
                o = int(_np.ceil((ins + 2 * ps - ks) / ss)) + 1
            else:
                o = (ins + 2 * ps - ks) // ss + 1
            out.append(max(o, 1))
        return tuple(out)

    def infer_shape(self, in_shapes):
        data = in_shapes[0]
        if data is None:
            require_known("Pooling", in_shapes, ["data"])
        out = data[:2] + self._out_spatial(data[2:])
        return [data], [out], []

    def forward(self, inputs, aux, is_train, rng):
        x = inputs[0]
        nd = x.ndim - 2
        k, s, pad = self._conf(x.shape[2:])
        out_sp = self._out_spatial(x.shape[2:])
        # padding incl. 'full' convention: pad the high side enough for ceil
        pads = []
        for i in range(nd):
            lo = pad[i]
            hi = (out_sp[i] - 1) * s[i] + k[i] - x.shape[2 + i] - lo
            pads.append((lo, max(hi, pad[i])))
        window = (1, 1) + k
        strides = (1, 1) + s
        padding = ((0, 0), (0, 0)) + tuple(pads)
        pt = self.param.pool_type
        if pt == "max":
            init = -jnp.inf
            out = lax.reduce_window(x, init, lax.max, window, strides, padding)
        else:
            out = lax.reduce_window(x, 0.0, lax.add, window, strides, padding)
            if pt == "avg":
                out = out / float(_np.prod(k))
        return [out.astype(x.dtype)], None

    def cost_flops(self, in_shapes, out_shapes):
        k, _s, _p = self._conf(in_shapes[0][2:])
        return float(_np.prod(out_shapes[0], dtype=_np.int64)
                     * _np.prod(k, dtype=_np.int64))

    def cost_reduce_len(self, in_shapes, out_shapes):
        if self.param.pool_type == "max":
            return None     # max accumulation is exact in any dtype
        k, _s, _p = self._conf(in_shapes[0][2:])
        return int(_np.prod(k, dtype=_np.int64))


# ----------------------------------------------------------------------
# BatchNorm
# ----------------------------------------------------------------------
class _BatchNormParam(ParamStruct):
    eps = Field(float, default=1e-3)
    momentum = Field(float, default=0.9)
    fix_gamma = Field(bool, default=True)
    use_global_stats = Field(bool, default=False)


@register_op("BatchNorm", aliases=("CuDNNBatchNorm",))
class BatchNorm(OperatorProperty):
    """batch_norm-inl.h. Aux moving_mean/moving_var updated functionally in
    train mode (the reference mutates them in Backward; same steady state)."""
    param_cls = _BatchNormParam

    def list_arguments(self):
        return ["data", "gamma", "beta"]

    def list_auxiliary_states(self):
        return ["moving_mean", "moving_var"]

    def infer_shape(self, in_shapes):
        data = in_shapes[0]
        if data is None:
            require_known("BatchNorm", in_shapes[:1], ["data"])
        c = (data[1],)
        return [data, c, c], [data], [c, c]

    def forward(self, inputs, aux, is_train, rng):
        p = self.param
        x, gamma, beta = inputs
        moving_mean, moving_var = aux
        if p.fix_gamma:
            gamma = jnp.ones_like(gamma)
        red_axes = (0,) + tuple(range(2, x.ndim))
        bshape = (1, -1) + (1,) * (x.ndim - 2)
        if is_train and not p.use_global_stats:
            mean = jnp.mean(x, axis=red_axes)
            var = jnp.var(x, axis=red_axes)
            new_mean = p.momentum * moving_mean + (1 - p.momentum) * mean
            new_var = p.momentum * moving_var + (1 - p.momentum) * var
            aux_updates = [new_mean, new_var]
        else:
            mean, var = moving_mean, moving_var
            mean = lax.stop_gradient(mean)
            var = lax.stop_gradient(var)
            aux_updates = None
        inv = lax.rsqrt(var + p.eps)
        out = (x - mean.reshape(bshape)) * inv.reshape(bshape) * \
            gamma.reshape(bshape) + beta.reshape(bshape)
        return [out], aux_updates

    def infer_sharding(self, in_specs, in_shapes, out_shapes, mesh_shape):
        data = in_specs[0]
        chan = data[1] if len(data) > 1 else ()
        return {"out": [tuple(data)],
                "in": [None, (chan,), (chan,)]}


# ----------------------------------------------------------------------
# Dropout
# ----------------------------------------------------------------------
class _DropoutParam(ParamStruct):
    p = Field(float, default=0.5, lower=0.0, upper=1.0)


@register_op("Dropout")
class Dropout(OperatorProperty):
    """dropout-inl.h: scale-at-train inverted dropout."""
    param_cls = _DropoutParam
    need_rng = True

    def forward(self, inputs, aux, is_train, rng):
        x = inputs[0]
        p = self.param.p
        if not is_train or p <= 0.0:
            return [x], None
        keep = jax.random.bernoulli(rng, 1.0 - p, x.shape)
        return [jnp.where(keep, x / (1.0 - p), 0.0).astype(x.dtype)], None


# ----------------------------------------------------------------------
# shape manipulators: Flatten / Reshape / Concat / SliceChannel / SwapAxis / Cast
# ----------------------------------------------------------------------
@register_op("Flatten")
class Flatten(OperatorProperty):
    def infer_shape(self, in_shapes):
        require_known("Flatten", in_shapes, ["data"])
        d = in_shapes[0]
        return in_shapes, [(d[0], int(_np.prod(d[1:], dtype=_np.int64)))], []

    def forward(self, inputs, aux, is_train, rng):
        return [inputs[0].reshape((inputs[0].shape[0], -1))], None

    def infer_sharding(self, in_specs, in_shapes, out_shapes, mesh_shape):
        return {"out": [reshape_carry(in_specs[0], in_shapes[0],
                                      out_shapes[0], mesh_shape)]}


class _ReshapeParam(ParamStruct):
    shape = Field(tuple, default=None, doc="0 keeps input dim, -1 infers")
    target_shape = Field(tuple, default=None, doc="legacy exact shape")
    keep_highest = Field(bool, default=False)


@register_op("Reshape")
class Reshape(OperatorProperty):
    param_cls = _ReshapeParam

    def _target(self, in_shape):
        p = self.param
        if p.shape is None and p.target_shape is None:
            raise MXNetError("Reshape needs shape or target_shape")
        size = int(_np.prod(in_shape, dtype=_np.int64))
        if p.shape is not None:
            out = []
            for i, s in enumerate(p.shape):
                if s == 0:
                    out.append(in_shape[i])
                else:
                    out.append(s)
        else:
            out = list(p.target_shape)
            if p.keep_highest:
                out[0] = in_shape[0]
            elif out and out[0] == 0:
                out[0] = -1
        if -1 in out:
            known = int(_np.prod([s for s in out if s != -1], dtype=_np.int64))
            out[out.index(-1)] = size // known
        tgt = tuple(int(s) for s in out)
        if int(_np.prod(tgt, dtype=_np.int64)) != size:
            raise MXNetError("Reshape %s -> %s size mismatch" % (in_shape, tgt))
        return tgt

    def infer_shape(self, in_shapes):
        require_known("Reshape", in_shapes, ["data"])
        return in_shapes, [self._target(in_shapes[0])], []

    def forward(self, inputs, aux, is_train, rng):
        return [inputs[0].reshape(self._target(inputs[0].shape))], None

    def infer_sharding(self, in_specs, in_shapes, out_shapes, mesh_shape):
        return {"out": [reshape_carry(in_specs[0], in_shapes[0],
                                      out_shapes[0], mesh_shape)]}


class _ConcatParam(ParamStruct):
    num_args = Field(int, required=True, lower=1)
    dim = Field(int, default=1)


@register_op("Concat")
class Concat(OperatorProperty):
    param_cls = _ConcatParam

    def list_arguments(self):
        return ["arg%d" % i for i in range(self.param.num_args)]

    def infer_shape(self, in_shapes):
        known = [s for s in in_shapes if s is not None]
        if not known:
            require_known("Concat", in_shapes, self.list_arguments())
        dim = self.param.dim
        # all dims except `dim` must agree; missing inputs can't be filled
        require_known("Concat", in_shapes, self.list_arguments())
        out = list(in_shapes[0])
        out[dim] = sum(s[dim] for s in in_shapes)
        return in_shapes, [tuple(out)], []

    def forward(self, inputs, aux, is_train, rng):
        return [jnp.concatenate(inputs, axis=self.param.dim)], None


class _SliceChannelParam(ParamStruct):
    num_outputs = Field(int, required=True, lower=1)
    axis = Field(int, default=1)
    squeeze_axis = Field(bool, default=False)


@register_op("SliceChannel")
class SliceChannel(OperatorProperty):
    param_cls = _SliceChannelParam

    def list_outputs(self):
        return ["output%d" % i for i in range(self.param.num_outputs)]

    def infer_shape(self, in_shapes):
        require_known("SliceChannel", in_shapes, ["data"])
        p = self.param
        d = list(in_shapes[0])
        if d[p.axis] % p.num_outputs:
            raise MXNetError("SliceChannel: dim %d not divisible by %d"
                             % (d[p.axis], p.num_outputs))
        d[p.axis] //= p.num_outputs
        if p.squeeze_axis and d[p.axis] == 1:
            d.pop(p.axis)
        return in_shapes, [tuple(d)] * p.num_outputs, []

    def forward(self, inputs, aux, is_train, rng):
        p = self.param
        outs = jnp.split(inputs[0], p.num_outputs, axis=p.axis)
        if p.squeeze_axis:
            outs = [jnp.squeeze(o, axis=p.axis) for o in outs]
        return outs, None


class _SwapAxisParam(ParamStruct):
    dim1 = Field(int, default=0)
    dim2 = Field(int, default=0)


@register_op("SwapAxis")
class SwapAxis(OperatorProperty):
    param_cls = _SwapAxisParam

    def infer_shape(self, in_shapes):
        require_known("SwapAxis", in_shapes, ["data"])
        s = list(in_shapes[0])
        p = self.param
        s[p.dim1], s[p.dim2] = s[p.dim2], s[p.dim1]
        return in_shapes, [tuple(s)], []

    def forward(self, inputs, aux, is_train, rng):
        return [jnp.swapaxes(inputs[0], self.param.dim1, self.param.dim2)], None


class _CastParam(ParamStruct):
    dtype = Field(str, required=True)


@register_op("Cast")
class Cast(OperatorProperty):
    param_cls = _CastParam

    def infer_type(self, in_types):
        out = _np.dtype(self.param.dtype)
        known = [t for t in in_types if t is not None]
        return [known[0] if known else None], [out], []

    def forward(self, inputs, aux, is_train, rng):
        return [inputs[0].astype(_np.dtype(self.param.dtype))], None


# ----------------------------------------------------------------------
# BlockGrad / ElementWiseSum / Embedding
# ----------------------------------------------------------------------
@register_op("BlockGrad")
class BlockGrad(OperatorProperty):
    """block_grad-inl.h: identity fwd, zero grad -> lax.stop_gradient."""

    def forward(self, inputs, aux, is_train, rng):
        return [lax.stop_gradient(inputs[0])], None


class _EWSumParam(ParamStruct):
    num_args = Field(int, required=True, lower=1)


@register_op("ElementWiseSum", aliases=("add_n",))
class ElementWiseSum(OperatorProperty):
    param_cls = _EWSumParam

    def list_arguments(self):
        return ["arg%d" % i for i in range(self.param.num_args)]

    def infer_shape(self, in_shapes):
        known = [s for s in in_shapes if s is not None]
        if not known:
            require_known("ElementWiseSum", in_shapes, self.list_arguments())
        filled = [known[0] if s is None else s for s in in_shapes]
        return filled, [known[0]], []

    def forward(self, inputs, aux, is_train, rng):
        out = inputs[0]
        for x in inputs[1:]:
            out = out + x
        return [out], None


class _EmbeddingParam(ParamStruct):
    input_dim = Field(int, required=True, lower=1)
    output_dim = Field(int, required=True, lower=1)


@register_op("Embedding")
class Embedding(OperatorProperty):
    """embedding-inl.h: weight rows gathered by integer ids."""
    param_cls = _EmbeddingParam

    def list_arguments(self):
        return ["data", "weight"]

    def infer_shape(self, in_shapes):
        data = in_shapes[0]
        if data is None:
            require_known("Embedding", in_shapes[:1], ["data"])
        p = self.param
        w = (p.input_dim, p.output_dim)
        return [data, w], [tuple(data) + (p.output_dim,)], []

    def forward(self, inputs, aux, is_train, rng):
        ids = inputs[0].astype(jnp.int32)
        return [jnp.take(inputs[1], ids, axis=0)], None

    def cost_bytes_elements(self, in_shapes, out_shapes):
        # gather: ids + the gathered rows in and out, not the full table
        return float(_np.prod(in_shapes[0], dtype=_np.int64)
                     + 2 * _np.prod(out_shapes[0], dtype=_np.int64))

    def infer_sharding(self, in_specs, in_shapes, out_shapes, mesh_shape):
        data, weight = in_specs[0], in_specs[1]
        used = [a for e in data for a in e]
        feat = dedup_axes(weight[1] if len(weight) > 1 else (), used)
        out = {"out": [tuple(data) + (feat,)]}
        vocab = tuple(weight[0] if weight else ())
        if vocab:
            # vocab-sharded table: each shard gathers local hits only and
            # the partial one-hot matmul is psummed across the axis
            out["reduce"] = {vocab: "vocab-sharded Embedding lookup: each "
                                    "shard contributes rows it owns"}
        return out


# ----------------------------------------------------------------------
# normalization extras: LRN / L2Normalization
# ----------------------------------------------------------------------
class _LRNParam(ParamStruct):
    alpha = Field(float, default=1e-4)
    beta = Field(float, default=0.75)
    knorm = Field(float, default=2.0)
    nsize = Field(int, required=True)


@register_op("LRN")
class LRN(OperatorProperty):
    """lrn-inl.h: cross-channel local response normalization."""
    param_cls = _LRNParam

    def forward(self, inputs, aux, is_train, rng):
        p = self.param
        x = inputs[0]
        sq = jnp.square(x)
        half = p.nsize // 2
        window = (1, p.nsize) + (1,) * (x.ndim - 2)
        pads = ((0, 0), (half, p.nsize - 1 - half)) + ((0, 0),) * (x.ndim - 2)
        ssum = lax.reduce_window(sq, 0.0, lax.add, window, (1,) * x.ndim, pads)
        norm = jnp.power(p.knorm + (p.alpha / p.nsize) * ssum, -p.beta)
        return [(x * norm).astype(x.dtype)], None


class _L2NormParam(ParamStruct):
    eps = Field(float, default=1e-10)
    mode = Field(str, default="instance", enum=("instance", "channel", "spatial"))


@register_op("L2Normalization")
class L2Normalization(OperatorProperty):
    param_cls = _L2NormParam

    def forward(self, inputs, aux, is_train, rng):
        p = self.param
        x = inputs[0]
        if p.mode == "instance":
            axes = tuple(range(1, x.ndim))
        elif p.mode == "channel":
            axes = (1,)
        else:  # spatial
            axes = tuple(range(2, x.ndim))
        norm = jnp.sqrt(jnp.sum(jnp.square(x), axis=axes, keepdims=True) + p.eps)
        return [x / norm], None


# ----------------------------------------------------------------------
# UpSampling / Crop
# ----------------------------------------------------------------------
class _UpSamplingParam(ParamStruct):
    scale = Field(int, required=True, lower=1)
    num_filter = Field(int, default=0)
    sample_type = Field(str, required=True, enum=("nearest", "bilinear"))
    num_args = Field(int, default=1)
    multi_input_mode = Field(str, default="concat", enum=("concat", "sum"))


@register_op("UpSampling")
class UpSampling(OperatorProperty):
    """upsampling-inl.h: nearest repeat / bilinear resize (jax.image)."""
    param_cls = _UpSamplingParam

    def list_arguments(self):
        return ["arg%d" % i for i in range(self.param.num_args)]

    def infer_shape(self, in_shapes):
        require_known("UpSampling", in_shapes, self.list_arguments())
        p = self.param
        d = in_shapes[0]
        oh, ow = d[2] * p.scale, d[3] * p.scale
        c = d[1]
        if p.num_args > 1 and p.multi_input_mode == "concat":
            c = sum(s[1] for s in in_shapes)
        return in_shapes, [(d[0], c, oh, ow)], []

    def _up(self, x):
        p = self.param
        if p.sample_type == "nearest":
            return jnp.repeat(jnp.repeat(x, p.scale, axis=2), p.scale, axis=3)
        tgt = (x.shape[0], x.shape[1], x.shape[2] * p.scale, x.shape[3] * p.scale)
        return jax.image.resize(x, tgt, method="bilinear")

    def forward(self, inputs, aux, is_train, rng):
        p = self.param
        ups = []
        base_h = inputs[0].shape[2] * p.scale
        base_w = inputs[0].shape[3] * p.scale
        for x in inputs:
            scale = base_h // x.shape[2]
            if scale == p.scale:
                ups.append(self._up(x))
            else:
                tgt = (x.shape[0], x.shape[1], base_h, base_w)
                ups.append(jax.image.resize(x, tgt, method="nearest"))
        if len(ups) == 1:
            return [ups[0]], None
        if p.multi_input_mode == "concat":
            return [jnp.concatenate(ups, axis=1)], None
        out = ups[0]
        for u in ups[1:]:
            out = out + u
        return [out], None


class _CropParam(ParamStruct):
    num_args = Field(int, required=True, lower=1, upper=2)
    offset = Field(tuple, default=(0, 0), length=2)
    h_w = Field(tuple, default=(0, 0), length=2)
    center_crop = Field(bool, default=False)


@register_op("Crop")
class Crop(OperatorProperty):
    """crop-inl.h: crop data to h_w or to the 2nd input's spatial shape."""
    param_cls = _CropParam

    def list_arguments(self):
        if self.param.num_args == 2:
            return ["data", "crop_like"]
        return ["data"]

    def _out_hw(self, in_shapes):
        p = self.param
        if p.num_args == 2:
            return in_shapes[1][2:4]
        return tuple(p.h_w)

    def infer_shape(self, in_shapes):
        require_known("Crop", in_shapes, self.list_arguments())
        d = in_shapes[0]
        oh, ow = self._out_hw(in_shapes)
        return in_shapes, [(d[0], d[1], oh, ow)], []

    def forward(self, inputs, aux, is_train, rng):
        p = self.param
        x = inputs[0]
        if p.num_args == 2:
            oh, ow = inputs[1].shape[2:4]
        else:
            oh, ow = p.h_w
        if p.center_crop:
            y0 = (x.shape[2] - oh) // 2
            x0 = (x.shape[3] - ow) // 2
        else:
            y0, x0 = p.offset
        return [x[:, :, y0:y0 + oh, x0:x0 + ow]], None
