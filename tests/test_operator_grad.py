"""Gradient coverage for EVERY registered operator.

Parity target: the reference's numeric-gradient suite
(tests/python/unittest/test_operator.py, check_numeric_gradient usage
throughout).  Three tiers:

- GRAD_SPECS: ops whose backward is d(forward) — checked against central
  differences (check_numeric_gradient on sum(outputs)).
- CONTRACT_SPECS: ops whose backward deliberately is NOT d(forward)
  (custom_vjp loss layers, BlockGrad, element_mask's gradient-free mask)
  — checked against the reference's documented backward formula.
- EXEMPT: ops with no gradient story (samplers, host-callback infra),
  each with the reason recorded.

test_every_registered_op_has_gradient_coverage closes the loop: any op
registered without an entry in one of the three tables fails the suite.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import symbol as sym
from mxnet_tpu.test_utils import (assert_almost_equal, check_numeric_gradient,
                                  check_symbolic_backward)

rng = np.random.RandomState(777)


def _f64(*shape):
    return rng.uniform(-1, 1, size=shape).astype(np.float64)


def _pos64(*shape):
    return rng.uniform(0.5, 2.0, size=shape).astype(np.float64)


def _away_from_zero(*shape):
    """Values in ±[0.25, 1.0]: keeps |x| kinks (abs/relu/leaky) away from
    the numeric-diff epsilon."""
    mag = rng.uniform(0.25, 1.0, size=shape)
    return (mag * np.where(rng.rand(*shape) > 0.5, 1.0, -1.0)).astype(np.float64)


def _distinct64(*shape):
    """All-distinct values: max/min/pool_max subgradients are exact."""
    n = int(np.prod(shape))
    vals = rng.permutation(n).astype(np.float64) / n + rng.uniform(0, 1e-3)
    return vals.reshape(shape)


def _separated_pair(*shape):
    """(a, b) with |a-b| >= 0.3 everywhere: elementwise max/min never
    flips inside the numeric-diff epsilon."""
    a = _f64(*shape)
    offs = np.where(rng.rand(*shape) > 0.5, 1.0, -1.0) * rng.uniform(
        0.3, 0.8, size=shape)
    return a, a + offs


V = sym.Variable

# ---------------------------------------------------------------------------
# Tier 1: backward == d(forward); checked vs central differences.
# name -> (symbol builder, location dict, kwargs for check_numeric_gradient)
# ---------------------------------------------------------------------------
GRAD_SPECS = {
    # elementwise binary (+ broadcast aliases)
    "_Plus": lambda: (V("a") + V("b"), {"a": _f64(3, 4), "b": _f64(3, 4)}, {}),
    "_Minus": lambda: (V("a") - V("b"), {"a": _f64(3, 4), "b": _f64(3, 4)}, {}),
    "_Mul": lambda: (V("a") * V("b"), {"a": _f64(3, 4), "b": _f64(3, 4)}, {}),
    "_Div": lambda: (V("a") / V("b"), {"a": _f64(3, 4), "b": _pos64(3, 4)}, {}),
    "_Power": lambda: (V("a") ** V("b"),
                       {"a": _pos64(3, 4), "b": _f64(3, 4)}, {}),
    "_Maximum": lambda: (sym._Maximum(V("a"), V("b")),
                         dict(zip("ab", _separated_pair(3, 4))), {}),
    "_Minimum": lambda: (sym._Minimum(V("a"), V("b")),
                         dict(zip("ab", _separated_pair(3, 4))), {}),
    # scalar variants
    "_PlusScalar": lambda: (V("a") + 1.5, {"a": _f64(3, 4)}, {}),
    "_MinusScalar": lambda: (V("a") - 1.5, {"a": _f64(3, 4)}, {}),
    "_RMinusScalar": lambda: (1.5 - V("a"), {"a": _f64(3, 4)}, {}),
    "_MulScalar": lambda: (V("a") * 2.5, {"a": _f64(3, 4)}, {}),
    "_DivScalar": lambda: (V("a") / 2.5, {"a": _f64(3, 4)}, {}),
    "_RDivScalar": lambda: (2.5 / V("a"), {"a": _pos64(3, 4)}, {}),
    "_PowerScalar": lambda: (V("a") ** 2.0, {"a": _pos64(3, 4)}, {}),
    "_RPowerScalar": lambda: (sym._RPowerScalar(V("a"), scalar=2.0),
                              {"a": _f64(3, 4)}, {}),
    "_MaximumScalar": lambda: (sym._MaximumScalar(V("a"), scalar=0.1),
                               {"a": _away_from_zero(3, 4)}, {}),
    "_MinimumScalar": lambda: (sym._MinimumScalar(V("a"), scalar=0.1),
                               {"a": _away_from_zero(3, 4)}, {}),
    # unary math
    "sqrt": lambda: (sym.sqrt(V("a")), {"a": _pos64(3, 4)}, {}),
    "rsqrt": lambda: (sym.rsqrt(V("a")), {"a": _pos64(3, 4)}, {}),
    "exp": lambda: (sym.exp(V("a")), {"a": _f64(3, 4)}, {}),
    "log": lambda: (sym.log(V("a")), {"a": _pos64(3, 4)}, {}),
    "cos": lambda: (sym.cos(V("a")), {"a": _f64(3, 4)}, {}),
    "sin": lambda: (sym.sin(V("a")), {"a": _f64(3, 4)}, {}),
    "abs": lambda: (sym.abs(V("a")), {"a": _away_from_zero(3, 4)}, {}),
    "square": lambda: (sym.square(V("a")), {"a": _f64(3, 4)}, {}),
    "negative": lambda: (sym.negative(V("a")), {"a": _f64(3, 4)}, {}),
    "_copy": lambda: (sym._copy(V("a")), {"a": _f64(3, 4)}, {}),
    "_CrossDeviceCopy": lambda: (sym._CrossDeviceCopy(V("a")),
                                 {"a": _f64(3, 4)}, {}),
    "smooth_l1": lambda: (sym.smooth_l1(V("a"), scalar=1.0),
                          # keep |x| off the transition point 1/sigma^2
                          {"a": np.array([[-2.0, -0.5, 0.3, 1.7]])}, {}),
    # reductions
    "sum": lambda: (sym.sum(V("a"), axis=(1,)), {"a": _f64(2, 3, 4)}, {}),
    "max": lambda: (sym.max(V("a"), axis=(1,)), {"a": _distinct64(2, 3, 4)}, {}),
    "min": lambda: (sym.min(V("a"), axis=(1,)), {"a": _distinct64(2, 3, 4)}, {}),
    "norm": lambda: (sym.norm(V("a")), {"a": _pos64(3, 4)}, {}),
    # matrix
    "dot": lambda: (sym.dot(V("a"), V("b")),
                    {"a": _f64(3, 4), "b": _f64(4, 2)}, {}),
    "batch_dot": lambda: (sym.batch_dot(V("a"), V("b")),
                          {"a": _f64(2, 3, 4), "b": _f64(2, 4, 2)}, {}),
    # shape manipulation
    "transpose": lambda: (sym.transpose(V("a"), axes=(1, 0, 2)),
                          {"a": _f64(2, 3, 4)}, {}),
    "expand_dims": lambda: (sym.expand_dims(V("a"), axis=1),
                            {"a": _f64(3, 4)}, {}),
    "flip": lambda: (sym.flip(V("a"), axis=1), {"a": _f64(3, 4)}, {}),
    "slice_axis": lambda: (sym.slice_axis(V("a"), axis=1, begin=1, end=3),
                           {"a": _f64(3, 4)}, {}),
    "Reshape": lambda: (sym.Reshape(V("a"), shape=(2, 12)),
                        {"a": _f64(2, 3, 4)}, {}),
    "Flatten": lambda: (sym.Flatten(V("a")), {"a": _f64(2, 3, 4)}, {}),
    "SwapAxis": lambda: (sym.SwapAxis(V("a"), dim1=0, dim2=2),
                         {"a": _f64(2, 3, 4)}, {}),
    "Concat": lambda: (sym.Concat(V("a"), V("b"), dim=1, name="cc"),
                       {"a": _f64(2, 3), "b": _f64(2, 2)}, {}),
    "SliceChannel": lambda: (sym.SliceChannel(V("a"), num_outputs=2,
                                              name="sc"),
                             {"a": _f64(2, 4)}, {}),
    "Crop": lambda: (sym.Crop(V("a"), num_args=1, h_w=(3, 3), name="cr"),
                     {"a": _f64(1, 2, 5, 5)}, {}),
    "broadcast_axis": lambda: (sym.broadcast_axis(V("a"), axis=(0,), size=(3,)),
                               {"a": _f64(1, 4)}, {}),
    "broadcast_to": lambda: (sym.broadcast_to(V("a"), shape=(3, 4)),
                             {"a": _f64(1, 4)}, {}),
    "ElementWiseSum": lambda: (sym.ElementWiseSum(V("a"), V("b"), V("c"),
                                                  name="ews"),
                               {"a": _f64(3, 4), "b": _f64(3, 4),
                                "c": _f64(3, 4)}, {}),
    "element_mask": lambda: (sym.element_mask(V("a"), V("m")),
                             {"a": _f64(4, 3),
                              "m": np.array([1.0, 0.0, 1.0, 1.0])},
                             {"grad_nodes": ["a"]}),
    "Cast": lambda: (sym.Cast(V("a"), dtype="float32"), {"a": _f64(3, 4)}, {}),
    # nn layers
    "Activation": lambda: (sym.Activation(V("a"), act_type="sigmoid"),
                           {"a": _f64(3, 4)}, {}),
    "LeakyReLU": lambda: (sym.LeakyReLU(V("a"), act_type="leaky", slope=0.25),
                          {"a": _away_from_zero(3, 4)}, {}),
    "SoftmaxActivation": lambda: (sym.SoftmaxActivation(V("a")),
                                  {"a": _f64(3, 4)}, {}),
    "FullyConnected": lambda: (
        sym.FullyConnected(V("a"), num_hidden=3, name="fc"),
        {"a": _f64(2, 4), "fc_weight": _f64(3, 4), "fc_bias": _f64(3)}, {}),
    "Convolution": lambda: (
        sym.Convolution(V("a"), kernel=(3, 3), num_filter=2, pad=(1, 1),
                        name="cv"),
        {"a": _f64(1, 2, 4, 4), "cv_weight": _f64(2, 2, 3, 3),
         "cv_bias": _f64(2)},
        {"rtol": 5e-2, "atol": 5e-2}),
    "Deconvolution": lambda: (
        sym.Deconvolution(V("a"), kernel=(3, 3), num_filter=2, pad=(1, 1),
                          name="dc"),
        {"a": _f64(1, 2, 4, 4), "dc_weight": _f64(2, 2, 3, 3),
         "dc_bias": _f64(2)},
        {"rtol": 5e-2, "atol": 5e-2}),
    "Pooling": lambda: (
        sym.Pooling(V("a"), kernel=(2, 2), stride=(2, 2), pool_type="avg"),
        {"a": _f64(1, 2, 4, 4)}, {}),
    "BatchNorm": lambda: (
        sym.BatchNorm(V("a"), fix_gamma=False, name="bn"),
        {"a": _f64(4, 3), "bn_gamma": _pos64(3), "bn_beta": _f64(3)},
        {"aux_states": [np.zeros(3, np.float32), np.ones(3, np.float32)],
         "rtol": 5e-2, "atol": 5e-2}),
    "LayerNorm": lambda: (
        sym.LayerNorm(V("a"), name="ln"),
        {"a": _f64(4, 3), "ln_gamma": _pos64(3), "ln_beta": _f64(3)},
        {"rtol": 5e-2, "atol": 5e-2}),
    "LRN": lambda: (sym.LRN(V("a"), nsize=3),
                    {"a": _pos64(1, 4, 3, 3)}, {"rtol": 5e-2, "atol": 5e-2}),
    "L2Normalization": lambda: (sym.L2Normalization(V("a")),
                                {"a": _f64(2, 3, 2)},
                                {"rtol": 5e-2, "atol": 5e-2}),
    "Dropout": lambda: (sym.Dropout(V("a"), p=0.0), {"a": _f64(3, 4)}, {}),
    "Embedding": lambda: (
        sym.Embedding(V("ids"), input_dim=4, output_dim=3, name="em"),
        {"ids": np.array([1.0, 0.0, 3.0, 2.0]), "em_weight": _f64(4, 3)},
        {"grad_nodes": ["em_weight"]}),
    "UpSampling": lambda: (
        sym.UpSampling(V("a"), scale=2, sample_type="nearest", num_args=1),
        {"a": _f64(1, 2, 3, 3)}, {}),
    "Correlation": lambda: (
        sym.Correlation(V("a"), V("b"), kernel_size=1, max_displacement=1,
                        pad_size=1),
        {"a": _f64(1, 2, 4, 4), "b": _f64(1, 2, 4, 4)},
        {"rtol": 5e-2, "atol": 5e-2}),
    "SpatialTransformer": lambda: (
        sym.SpatialTransformer(V("a"), V("loc"), target_shape=(4, 4),
                               transform_type="affine",
                               sampler_type="bilinear"),
        {"a": _f64(1, 2, 4, 4),
         "loc": np.array([[0.9, 0.05, 0.03, -0.05, 1.1, 0.07]])},
        {"rtol": 5e-2, "atol": 5e-2}),
    "ROIPooling": lambda: (
        sym.ROIPooling(V("a"), V("rois"), pooled_size=(2, 2),
                       spatial_scale=1.0),
        {"a": _distinct64(1, 2, 6, 6),
         "rois": np.array([[0.0, 0.0, 0.0, 5.0, 5.0]])},
        {"grad_nodes": ["a"], "rtol": 5e-2, "atol": 5e-2}),
    "RNN": lambda: (
        sym.RNN(V("a"), state_size=3, num_layers=1, mode="lstm", name="rn"),
        {"a": _f64(3, 2, 3),
         "rn_parameters": rng.uniform(-0.4, 0.4,
                                      (3 * (3 + 3 + 2) * 4,)),
         "rn_state": np.zeros((1, 2, 3)),
         "rn_state_cell": np.zeros((1, 2, 3))},
        {"grad_nodes": ["a", "rn_parameters"], "rtol": 5e-2, "atol": 5e-2}),
    "MoE": lambda: (
        sym.MoE(V("a"), num_experts=2, hidden_size=4, name="mo"),
        # gate logits get a wide margin (scaled gate weights on
        # well-spread tokens) so routing never flips inside the
        # numeric-diff epsilon and the top-1 mask stays constant
        {"a": _distinct64(6, 4) * 2.0,
         "mo_gate_weight": np.array([[3.0, 0, 0, 0], [0, 3.0, 0, 0]]),
         "mo_expert_fc1_weight": _f64(2, 4, 4) * 0.4,
         "mo_expert_fc1_bias": _f64(2, 4) * 0.1 + 0.5,
         "mo_expert_fc2_weight": _f64(2, 4, 4) * 0.4,
         "mo_expert_fc2_bias": _f64(2, 4) * 0.1},
        {"rtol": 5e-2, "atol": 5e-3}),
    "MultiHeadAttention": lambda: (
        sym.MultiHeadAttention(V("a"), num_heads=2, use_flash=False,
                               name="mh"),
        {"a": _f64(1, 3, 4), "mh_qkv_weight": _f64(12, 4) * 0.4,
         "mh_qkv_bias": _f64(12) * 0.1, "mh_out_weight": _f64(4, 4) * 0.4,
         "mh_out_bias": _f64(4) * 0.1},
        {"rtol": 5e-2, "atol": 5e-2}),
    "RMSNorm": lambda: (
        sym.RMSNorm(V("a"), name="rn"),
        {"a": _f64(4, 3), "rn_gamma": _pos64(3)},
        {"rtol": 5e-2, "atol": 5e-2}),
    "MultiHeadLatentAttention": lambda: (
        sym.MultiHeadLatentAttention(
            V("a"), num_heads=2, q_lora_rank=4, kv_lora_rank=4,
            qk_nope_head_dim=2, qk_rope_head_dim=2, v_head_dim=2,
            rope_theta=100.0, name="la"),
        {"a": _f64(1, 4, 6), "la_q_a_weight": _f64(4, 6) * 0.6,
         "la_q_a_norm_gamma": _pos64(4), "la_q_b_weight": _f64(8, 4) * 0.6,
         "la_kv_a_weight": _f64(6, 6) * 0.6,
         "la_kv_a_norm_gamma": _pos64(4), "la_kv_b_weight": _f64(8, 4) * 0.6,
         "la_out_weight": _f64(6, 4) * 0.6},
        {"rtol": 5e-2, "atol": 5e-2}),
    "RoutedExperts": lambda: (
        sym.RoutedExperts(V("a"), num_experts=4, hidden_size=4, top_k=2,
                          shared_hidden_size=4, routed_scaling_factor=2.5,
                          name="re"),
        # every token is positive in each coordinate, so experts 0 and 1
        # score over a half and experts 2 and 3 under it: the choice never
        # flips inside the numeric-diff epsilon, the weights still move
        {"a": (_distinct64(6, 4) + 0.2) * 2.0,
         "re_router_weight": np.diag([3.0, 3.0, -3.0, -3.0]),
         "re_expert_gate_weight": _f64(4, 4, 4) * 0.4,
         "re_expert_up_weight": _f64(4, 4, 4) * 0.4,
         "re_expert_down_weight": _f64(4, 4, 4) * 0.4,
         "re_shared_gate_weight": _f64(4, 4) * 0.4,
         "re_shared_up_weight": _f64(4, 4) * 0.4,
         "re_shared_down_weight": _f64(4, 4) * 0.4},
        {"rtol": 5e-2, "atol": 5e-3}),
    "CompressedConvAttention": lambda: (
        sym.CompressedConvAttention(
            V("a"), num_heads=4, num_kv_heads=2, head_dim=4,
            rope_theta=100.0, partial_rotary_factor=0.5, name="ca"),
        {"a": _f64(1, 5, 6), "ca_q_weight": _f64(16, 6) * 0.6,
         "ca_k_weight": _f64(8, 6) * 0.6, "ca_v_weight": _f64(8, 6) * 0.6,
         "ca_q_conv0_weight": _f64(2, 16) * 0.5 + 0.5,
         "ca_q_conv1_weight": _f64(2, 4, 4, 4) * 0.5,
         "ca_k_conv0_weight": _f64(2, 8) * 0.5 + 0.5,
         "ca_k_conv1_weight": _f64(2, 2, 4, 4) * 0.5,
         "ca_k_temp": _pos64(2), "ca_out_weight": _f64(6, 16) * 0.6},
        {"rtol": 5e-2, "atol": 5e-2}),
    # a softmax router, two of four a token, and a gated shared
    # expert: tokens positive in each coordinate keep experts 0 and 1 on
    # top, so the choice never flips inside the numeric-diff epsilon
    "RoutedExperts[softmax]": lambda: (
        sym.RoutedExperts(V("a"), num_experts=4, hidden_size=4, top_k=2,
                          score_func="softmax", shared_hidden_size=4,
                          shared_gate=True, name="rs"),
        {"a": (_distinct64(6, 4) + 0.2) * 2.0,
         "rs_router_weight": np.diag([3.0, 2.0, -3.0, -3.0]),
         "rs_expert_gate_weight": _f64(4, 4, 4) * 0.4,
         "rs_expert_up_weight": _f64(4, 4, 4) * 0.4,
         "rs_expert_down_weight": _f64(4, 4, 4) * 0.4,
         "rs_shared_gate_weight": _f64(4, 4) * 0.4,
         "rs_shared_up_weight": _f64(4, 4) * 0.4,
         "rs_shared_down_weight": _f64(4, 4) * 0.4,
         "rs_shared_score_weight": _f64(1, 4) * 0.6},
        {"rtol": 5e-2, "atol": 5e-3}),
    "GatedAttention": lambda: (
        sym.GatedAttention(
            V("a"), num_heads=4, num_kv_heads=2, head_dim=4,
            rope_theta=100.0, partial_rotary_factor=0.5, name="ga"),
        {"a": _f64(1, 5, 6), "ga_q_weight": _f64(32, 6) * 0.6,
         "ga_k_weight": _f64(8, 6) * 0.6, "ga_v_weight": _f64(8, 6) * 0.6,
         "ga_q_norm_gamma": _pos64(4), "ga_k_norm_gamma": _pos64(4),
         "ga_out_weight": _f64(6, 16) * 0.6},
        {"rtol": 5e-2, "atol": 5e-2}),
    # eight tokens in chunks of four: the state crosses a chunk's edge
    "GatedDeltaNet": lambda: (
        sym.GatedDeltaNet(
            V("a"), num_key_heads=2, num_value_heads=4, key_head_dim=4,
            value_head_dim=2, conv_taps=3, chunk=4, name="gd"),
        {"a": _f64(1, 8, 6), "gd_in_proj_qkvz_weight": _f64(32, 6) * 0.6,
         "gd_in_proj_ba_weight": _f64(8, 6) * 0.6,
         "gd_conv_weight": _f64(3, 24) * 0.5 + 0.5,
         "gd_A_log": _f64(4) * 0.5, "gd_dt_bias": _f64(4) * 0.5,
         "gd_norm_gamma": _pos64(2), "gd_out_weight": _f64(6, 8) * 0.6},
        {"rtol": 5e-2, "atol": 5e-2}),
    # eight tokens in chunks of four, a decay a key channel
    "KimiDeltaAttention": lambda: (
        sym.KimiDeltaAttention(
            V("a"), num_heads=2, head_dim=2, conv_taps=3, chunk=4,
            name="kd"),
        {"a": _f64(1, 8, 6), "kd_q_weight": _f64(4, 6) * 0.6,
         "kd_k_weight": _f64(4, 6) * 0.6, "kd_v_weight": _f64(4, 6) * 0.6,
         "kd_q_conv_weight": _f64(3, 4) * 0.5 + 0.5,
         "kd_k_conv_weight": _f64(3, 4) * 0.5 + 0.5,
         "kd_v_conv_weight": _f64(3, 4) * 0.5 + 0.5,
         "kd_f_weight": _f64(4, 6) * 0.6, "kd_A_log": _f64(2) * 0.5,
         "kd_dt_bias": _f64(4) * 0.5, "kd_b_weight": _f64(2, 6) * 0.6,
         "kd_g_weight": _f64(4, 6) * 0.6, "kd_norm_gamma": _pos64(2),
         "kd_out_weight": _f64(6, 4) * 0.6},
        {"rtol": 5e-2, "atol": 5e-2}),
    # a direct query, a norm on q, half-split rotary, a gate a head
    "MultiHeadLatentAttention[direct]": lambda: (
        sym.MultiHeadLatentAttention(
            V("a"), num_heads=2, q_lora_rank=0, kv_lora_rank=4,
            qk_nope_head_dim=2, qk_rope_head_dim=2, v_head_dim=2,
            rope_theta=100.0, rope_interleave=False, q_norm=True,
            gate="headwise", name="ld"),
        {"a": _f64(1, 4, 6), "ld_q_weight": _f64(8, 6) * 0.6,
         "ld_q_norm_gamma": _pos64(4), "ld_kv_a_weight": _f64(6, 6) * 0.6,
         "ld_kv_a_norm_gamma": _pos64(4), "ld_kv_b_weight": _f64(8, 4) * 0.6,
         "ld_gate_weight": _f64(2, 6) * 0.6,
         "ld_out_weight": _f64(6, 4) * 0.6},
        {"rtol": 5e-2, "atol": 5e-2}),
    # two groups of two, one kept: experts 0 and 1 score over a half and
    # lead their group, so neither group nor expert flips inside epsilon
    "RoutedExperts[groups]": lambda: (
        sym.RoutedExperts(V("a"), num_experts=4, hidden_size=4, top_k=2,
                          n_group=2, topk_group=1, shared_hidden_size=4,
                          routed_scaling_factor=2.5, name="rg"),
        {"a": (_distinct64(6, 4) + 0.2) * 2.0,
         "rg_router_weight": np.diag([3.0, 3.0, -3.0, -3.0]),
         "rg_expert_gate_weight": _f64(4, 4, 4) * 0.4,
         "rg_expert_up_weight": _f64(4, 4, 4) * 0.4,
         "rg_expert_down_weight": _f64(4, 4, 4) * 0.4,
         "rg_shared_gate_weight": _f64(4, 4) * 0.4,
         "rg_shared_up_weight": _f64(4, 4) * 0.4,
         "rg_shared_down_weight": _f64(4, 4) * 0.4},
        {"rtol": 5e-2, "atol": 5e-3}),
    # the scores sum to one a token: they are weighed by a fixed "w" so
    # that their gradient shows in the sum of the outputs
    "MLPRouter": lambda: (
        (lambda r: sym.Group([r[0] * V("w"), r[1]]))(sym.MLPRouter(
            V("a"), state=V("s"), num_experts=4, hidden_size=3, name="ro")),
        {"a": _f64(5, 6), "s": _f64(5, 3), "w": _f64(5, 4),
         "ro_state_gain": _pos64(1), "ro_down_weight": _f64(3, 6) * 0.6,
         "ro_norm_gamma": _pos64(3), "ro_fc1_weight": _f64(3, 3),
         "ro_fc2_weight": _f64(3, 3), "ro_out_weight": _f64(4, 3)},
        {"grad_nodes": ["a", "s", "ro_state_gain", "ro_down_weight",
                        "ro_norm_gamma", "ro_fc1_weight", "ro_fc2_weight",
                        "ro_out_weight"], "rtol": 5e-2, "atol": 5e-3}),
    "SequenceLast": lambda: (sym.SequenceLast(V("a")),
                             {"a": _f64(4, 2, 3)}, {}),
    "SequenceReverse": lambda: (sym.SequenceReverse(V("a")),
                                {"a": _f64(4, 2, 3)}, {}),
    "SequenceMask": lambda: (sym.SequenceMask(V("a")),
                             {"a": _f64(4, 2, 3)}, {}),
    "softmax_cross_entropy": lambda: (
        sym.softmax_cross_entropy(V("a"), V("l")),
        {"a": _f64(3, 4), "l": np.array([0.0, 2.0, 1.0])},
        {"grad_nodes": ["a"], "rtol": 5e-2, "atol": 5e-2}),
}

# ---------------------------------------------------------------------------
# Tier 2: backward is a documented contract, not d(forward).
# name -> callable running the contract check.
# ---------------------------------------------------------------------------


def _contract_blockgrad():
    a = _f64(3, 4).astype(np.float32)
    s = sym.BlockGrad(V("x"))
    check_symbolic_backward(s, [a], [np.ones_like(a)], [np.zeros_like(a)])


def _contract_softmax_output():
    data = _f64(4, 5).astype(np.float32)
    label = np.array([0, 2, 4, 1], np.float32)
    e = np.exp(data - data.max(1, keepdims=True))
    p = e / e.sum(1, keepdims=True)
    onehot = np.eye(5, dtype=np.float32)[label.astype(int)]
    # head gradient deliberately NOT ones: backward must ignore it
    og = np.full_like(data, 3.0)
    check_symbolic_backward(sym.SoftmaxOutput(V("x"), name="sm"),
                            [data, label], [og], {"x": p - onehot},
                            rtol=1e-3)


def _contract_svm_output():
    data = _f64(3, 4).astype(np.float32)
    label = np.array([1, 0, 3], np.float32)
    s = sym.SVMOutput(V("x"), name="svm", margin=0.5, use_linear=True)
    scores = data
    lab = label.astype(int)
    grad = np.zeros_like(scores)
    for i in range(3):
        sl = scores[i, lab[i]]
        for k in range(4):
            if k == lab[i]:
                continue
            if scores[i, k] - sl + 0.5 > 0:
                grad[i, k] = 1.0
                grad[i, lab[i]] -= 1.0
    og = np.full_like(data, 9.0)  # must be ignored
    check_symbolic_backward(s, [data, label], [og], {"x": grad}, rtol=1e-3)


def _contract_regressions():
    data = _f64(4, 3).astype(np.float32)
    label = _f64(4, 3).astype(np.float32)
    og = np.full_like(data, 7.0)  # must be ignored
    check_symbolic_backward(sym.LinearRegressionOutput(V("x"), name="a"),
                            [data, label], [og], {"x": data - label},
                            rtol=1e-3)
    sig = 1 / (1 + np.exp(-data))
    check_symbolic_backward(sym.LogisticRegressionOutput(V("x"), name="b"),
                            [data, label], [og], {"x": sig - label},
                            rtol=1e-3)
    check_symbolic_backward(sym.MAERegressionOutput(V("x"), name="c"),
                            [data, label], [og],
                            {"x": np.sign(data - label)}, rtol=1e-3)


def _contract_makeloss():
    a = _f64(3, 4).astype(np.float32)
    og = np.full_like(a, 5.0)  # must be ignored: grad is grad_scale
    check_symbolic_backward(sym.MakeLoss(V("x"), grad_scale=2.0),
                            [a], [og], [np.full_like(a, 2.0)])


def _contract_kl_sparse_reg():
    data = _pos64(4, 3).astype(np.float32) * 0.3
    s = sym.IdentityAttachKLSparseReg(V("x"), sparseness_target=0.1,
                                      penalty=0.01, momentum=0.0)
    avg = data.mean(axis=0)
    pen = 0.01 * (-0.1 / (avg + 1e-8) + 0.9 / (1 - avg + 1e-8))
    og = np.ones_like(data)
    check_symbolic_backward(s, [data], [og], {"x": og + pen[None, :]},
                            aux_states=[np.zeros(3, np.float32)], rtol=1e-3)


def _contract_element_mask():
    a = _f64(4, 3).astype(np.float32)
    m = np.array([1, 0, 1, 0], np.float32)
    og = np.ones((4, 3), np.float32)
    check_symbolic_backward(sym.element_mask(V("x"), V("m")), [a, m], [og],
                            {"m": np.zeros_like(m)})


def _contract_zero_grad_unaries():
    """Piecewise-constant ops: gradient is identically zero (matches the
    reference kernels, e.g. sign_grad/round have no backward)."""
    a = _away_from_zero(3, 4).astype(np.float32)
    og = np.ones_like(a)
    for s in (sym.sign(V("x")), sym.round(V("x")), sym.ceil(V("x")),
              sym.floor(V("x"))):
        check_symbolic_backward(s, [a], [og], [np.zeros_like(a)])


def _contract_argmax_channel():
    a = _distinct64(3, 4).astype(np.float32)
    og = np.ones((3,), np.float32)
    check_symbolic_backward(sym.argmax_channel(V("x")), [a], [og],
                            [np.zeros_like(a)])


CONTRACT_SPECS = {
    "BlockGrad": _contract_blockgrad,
    "SoftmaxOutput": _contract_softmax_output,
    "SVMOutput": _contract_svm_output,
    "LinearRegressionOutput": _contract_regressions,
    "LogisticRegressionOutput": _contract_regressions,
    "MAERegressionOutput": _contract_regressions,
    "MakeLoss": _contract_makeloss,
    "IdentityAttachKLSparseReg": _contract_kl_sparse_reg,
    "element_mask": _contract_element_mask,
    "sign": _contract_zero_grad_unaries,
    "round": _contract_zero_grad_unaries,
    "ceil": _contract_zero_grad_unaries,
    "floor": _contract_zero_grad_unaries,
    "argmax_channel": _contract_argmax_channel,
}

# ---------------------------------------------------------------------------
# Tier 3: no gradient story, with reasons.
# ---------------------------------------------------------------------------
EXEMPT = {
    "_sample_uniform": "random sampler: no inputs to differentiate",
    "_sample_normal": "random sampler: no inputs to differentiate",
    "Custom": "host-callback op: fwd+bwd covered by tests/test_custom_op.py",
    "_Native": "legacy host-callback op: covered by tests/test_custom_op.py",
    "CachedMultiHeadAttention":
        "serving-only prefill/decode op with no backward (generation "
        "graphs are inference-only); forward equivalence against the "
        "trainable attention path is pinned by tests/test_generate.py::"
        "test_decode_matches_full_forward",
    "QuantizedDense":
        "inference-only weight-quantized FullyConnected (quantize_symbol "
        "rewrites predict/serve graphs, never training graphs — training "
        "keeps f32 FullyConnected); forward equivalence vs the f32 path "
        "is pinned by tests/test_kernels.py::"
        "test_predictor_quantized_cosine",
}


@pytest.mark.parametrize("name", sorted(GRAD_SPECS))
def test_numeric_gradient(name):
    s, location, kwargs = GRAD_SPECS[name]()
    kwargs.setdefault("rtol", 2e-2)
    kwargs.setdefault("atol", 2e-3)
    aux = kwargs.pop("aux_states", None)
    check_numeric_gradient(s, location, aux_states=aux, **kwargs)


@pytest.mark.parametrize("name", sorted(CONTRACT_SPECS))
def test_backward_contract(name):
    CONTRACT_SPECS[name]()


def test_every_registered_op_has_gradient_coverage():
    """The audit: no op may be registered without a gradient check or a
    recorded exemption."""
    from mxnet_tpu.ops.registry import OP_REGISTRY
    # dedupe aliases: one class == one op, any of its names may be covered
    by_class = {}
    for name, cls in OP_REGISTRY._entries.values():
        by_class.setdefault(cls, []).append(name)
    covered = set(GRAD_SPECS) | set(CONTRACT_SPECS) | set(EXEMPT)
    covered_lower = {c.lower() for c in covered}
    missing = sorted(
        names[0] for names in by_class.values()
        if not any(n.lower() in covered_lower for n in names))
    assert not missing, (
        "registered ops without gradient coverage (add to GRAD_SPECS, "
        "CONTRACT_SPECS, or EXEMPT with a reason): %s" % missing)
