"""The Ling-3.0 hybrid layer set against plain references, at small sizes on
the CPU with seeded weights: Kimi delta attention's two Pallas kernels in
interpret mode against the per-channel recurrence (values and all five
gradients, at one, two and three chunks, a chunk's decay at the bound on
every token), its XLA form, the scalar rule's Mosaic modules unchanged at
Qwen3-Next's shapes, group-limited routing worked by hand, latent attention
with a direct query and a gate a head, the KDA op, a mirrored KDA block that
runs its forward kernel once, the same block lowered for a TPU on a mesh of
four, the routed layer's shares of the experts, and the whole model through
``ShardedTrainer.step`` against ``perfbench/reference/ling_flash.py`` (plain
``jax.numpy``, nothing of ``mxnet_tpu``): row losses and every leaf's
gradient."""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import mxnet_tpu as mx                                      # noqa: E402,F401
from mxnet_tpu.kernels import delta_rule                    # noqa: E402
from mxnet_tpu.ops import linear_attention as la            # noqa: E402
from mxnet_tpu.ops import moe                               # noqa: E402
from mxnet_tpu.ops.registry import create_operator          # noqa: E402
from perfbench.reference import ling_flash as ref           # noqa: E402
from test_laguna import _mosaic_calls                       # noqa: E402
from test_mirror import _kept_by_name, _kernel_calls        # noqa: E402

F32 = jnp.float32     # conftest turns x64 on: every draw says its dtype
SEQ = 64
CFG = {
    "hidden_size": 32, "vocab_size": 64, "num_attention_heads": 2,
    "head_dim": 16, "short_conv_kernel_size": 4, "kda_lower_bound": -5,
    "kda_safe_gate": True, "no_kda_lora": True, "use_kda_lora": False,
    "num_kv_heads_for_linear_attn": 0, "q_lora_rank": None,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "rotary_dim": 8,
    "v_head_dim": 16, "kv_lora_rank": 16, "rope_theta": 6000000,
    "intermediate_size": 48, "moe_intermediate_size": 16,
    "moe_shared_expert_intermediate_size": 12, "num_experts": 4,
    "num_experts_per_tok": 4, "n_group": 4, "topk_group": 2,
    "routed_scaling_factor": 2.5, "rms_norm_eps": 1e-6,
    "score_function": "sigmoid", "norm_topk_prob": True,
    # layers 3-5 of the pattern: KDA, KDA, latent attention
    "num_hidden_layers": 3, "layer_group_size": 6,
    "first_k_dense_replace": 1, "published_layers": [3, 4, 5],
    "layer_mixers": ["kda", "kda", "mla"],
    "mlp_layer_types": ["dense", "sparse", "sparse"],
    "expert_swiglu_limit_list": [0] * 42,
    "share_expert_swiglu_limit_list": [0] * 42,
    # wide enough that a router's scores are told apart in float32
    "initializer_range": 0.3,
    "deployment": {"router_width": 16, "first_expert": 4},
    "program": {"mirror_blocks": True, "delta_chunk": 16},
}
MLA_LEAVES = ("att_q_weight", "att_q_norm_gamma", "att_kv_a_weight",
              "att_kv_a_norm_gamma", "att_kv_b_weight", "att_gate_weight",
              "att_out_weight")
MOE_LEAVES = ("moe_router_weight", "moe_expert_gate_weight",
              "moe_expert_up_weight", "moe_expert_down_weight",
              "moe_shared_gate_weight", "moe_shared_up_weight",
              "moe_shared_down_weight")


def _close(a, b, tol=2e-5):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    scale = max(np.abs(b).max(), 1e-30)
    assert np.abs(a - b).max() <= tol * scale, (np.abs(a - b).max(), scale)


def _highest(fn):
    def run(*args, **kw):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kw)
    return run


def _rule_inputs(key, S, H, d, at_bound=()):
    """q, k (unit norm, q times d^-1/2), v, g in (−5, 0) — and −5 on every
    token of the chunks of 64 named in ``at_bound`` — β, and a cotangent."""
    ks = jax.random.split(key, 6)
    q = la.l2_normalise(jax.random.normal(ks[0], (1, S, H, d), F32)) \
        * d ** -0.5
    k = la.l2_normalise(jax.random.normal(ks[1], (1, S, H, d), F32))
    v = jax.random.normal(ks[2], (1, S, H, d), F32)
    g = -5.0 * jax.nn.sigmoid(2.0 * jax.random.normal(ks[3], (1, S, H, d),
                                                      F32))
    for c in at_bound:
        g = g.at[:, 64 * c:64 * (c + 1)].set(-5.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (1, S, H), F32))
    return q, k, v, g, beta, jax.random.normal(ks[5], (1, S, H, d), F32)


def _out_and_grads(fn, q, k, v, g, beta, do):
    """(o, dq, dk, dv, dg, dβ) of ``fn`` against the cotangent ``do``."""
    with jax.default_matmul_precision("highest"):
        o, vjp = jax.vjp(fn, q, k, v, g, beta)
        return (o,) + vjp(do)


# -- the KDA rule -------------------------------------------------------------
@pytest.mark.parametrize("chunks", [1, 2, 3])
def test_kda_kernels_against_the_recurrence(chunks):
    """Both kernels in interpret mode, 128-wide heads, chunks of 64: one
    chunk alone (a tile of 64 rows), two side by side in one tile, three in
    three tiles — the middle one with the decay at the bound, −5 on every
    channel of every token, where the key factor of a sub-block reaches
    e^75: the output and all five gradients are finite and the
    recurrence's."""
    S = 64 * chunks
    at_bound = (1,) if chunks == 3 else ()
    args = _rule_inputs(jax.random.PRNGKey(chunks), S, 1, 128, at_bound)
    blocks = delta_rule.kda_blocks(S, 64, 128, 128, 4)
    assert blocks == (S, 64)
    with jax.enable_x64(False):
        got = jax.jit(lambda *a: _out_and_grads(
            lambda *b: delta_rule.kda_rule_kernel(*b, *blocks,
                                                  interpret=True), *a))(*args)
        want = jax.jit(lambda *a: _out_and_grads(la.kda_rule_recurrent,
                                                 *a))(*args)
    for a, b in zip(got, want):
        assert np.isfinite(np.asarray(a)).all()
        _close(a, b, 1e-4)


def test_kda_xla_form_is_the_recurrence():
    """Four chunks of 16 carried by the scan, two heads."""
    args = _rule_inputs(jax.random.PRNGKey(10), SEQ, 2, 16)
    got = jax.jit(lambda *a: _out_and_grads(
        lambda *b: la.kda_rule_xla(*b, 16), *a))(*args)
    want = jax.jit(lambda *a: _out_and_grads(la.kda_rule_recurrent,
                                             *a))(*args)
    for a, b in zip(got, want):
        _close(a, b, 1e-5)


def test_scalar_kernels_lower_as_before():
    """The gated delta rule's two Mosaic modules at Qwen3-Next's shapes (16
    key heads on 32 value heads of 128, 8,192 tokens) are the tree's before
    KDA to the byte, source locations stripped."""
    def loss(q, k, v, g, b):
        return jnp.sum(la.gated_delta_rule(q, k, v, g, b, interpret=False)
                       .astype(F32))

    qk = jax.ShapeDtypeStruct((1, 8192, 16, 128), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((1, 8192, 32, 128), jnp.bfloat16)
    s = jax.ShapeDtypeStruct((1, 8192, 32), F32)
    assert _mosaic_calls(jax.grad(loss, (0, 1, 2, 3, 4)), qk, qk, v, s, s) \
        == [("@gated_delta_forward", "f321ad95c3efd6d9"),
            ("@gated_delta_backward", "d5ac7ad6f1d61d02")]


# -- group-limited routing ----------------------------------------------------
def test_route_topk_groups_by_hand():
    """Eight experts in four groups of two, two groups kept, two experts a
    token.  Group scores (the sum of a group's two): 1.0, 0.86, 1.1, 0.35;
    groups 2 and 0 are kept, so expert 2 (0.85, the second best overall)
    is out and expert 4 (0.8) in; the weights are the chosen scores over
    their sum, times the scaling.  A bias moves the choice, not the
    weights.  With one group the call is the plain top-k to the bit."""
    scores = jnp.asarray([[0.9, 0.1, 0.85, 0.01, 0.8, 0.3, 0.2, 0.15]], F32)
    zero = jnp.zeros((8,), F32)
    idx, w = moe.route_topk(scores, zero, 2, 2.5, n_group=4, topk_group=2)
    assert idx.tolist() == [[0, 4]]
    _close(w, jnp.asarray([[0.9, 0.8]]) / 1.7 * 2.5, 1e-6)
    plain_idx, plain_w = moe.route_topk(scores, zero, 2, 2.5)
    assert plain_idx.tolist() == [[0, 2]]
    # a bias of 0.3 on group 1 makes its score 1.46: groups 1 and 2 are
    # kept, expert 0 (0.9) is out; expert 2 leads on 1.15, expert 4 follows
    bias = jnp.asarray([0, 0, 0.3, 0.3, 0, 0, 0, 0], F32)
    idx, w = moe.route_topk(scores, bias, 2, 1.0, n_group=4, topk_group=2)
    assert idx.tolist() == [[2, 4]]
    _close(w, jnp.asarray([[0.85, 0.8]]) / 1.65, 1e-6)
    # one group: the same choice and weights as before groups existed
    many = jax.random.uniform(jax.random.PRNGKey(3), (64, 32), F32)
    a = moe.route_topk(many, jnp.zeros((32,), F32), 8, 2.5)
    b = moe.route_topk(many, jnp.zeros((32,), F32), 8, 2.5, n_group=1,
                       topk_group=1)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    jaxpr = str(jax.make_jaxpr(lambda s: moe.route_topk(
        s, jnp.zeros((32,), F32), 8))(many))
    assert jaxpr.count("top_k") == 1
    # the reference's own group limit chooses the same experts
    cfg = dict(CFG, deployment={"router_width": 8, "first_expert": 0},
               n_group=4, topk_group=2, num_experts_per_tok=2,
               routed_scaling_factor=2.5)
    logits = jnp.log(scores / (1 - scores + 1e-30))
    weights, _ = ref.routing(jnp.ones((1, 8), F32), {
        "moe_router_weight": jnp.diag(logits[0])}, cfg)
    assert np.nonzero(np.asarray(weights[0]))[0].tolist() == [0, 4]


# -- the mixers ---------------------------------------------------------------
def _layer(key, i):
    return ref._leaves(ref.init_params(CFG, key), "layer%d" % i)


def _mla_op():
    return create_operator(
        "MultiHeadLatentAttention", num_heads=2, q_lora_rank=0,
        kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, rope_theta=6e6, rope_interleave=False, q_norm=True,
        gate="headwise")


def test_latent_attention_against_the_plain_formula():
    """Latent attention with a direct query, an RMSNorm on q a head,
    half-split rotary and a sigmoid gate a head, against the plain formula:
    the output and the gradient of every leaf and of the input.  (The KDA
    op is held to the reference's by the whole model's test.)"""
    op, leaves = _mla_op(), MLA_LEAVES
    assert list(op.list_arguments()[1:]) == [n.split("_", 1)[1]
                                             for n in leaves]
    p = _layer(jax.random.PRNGKey(20), 2)
    p = {n: v + 0.1 * jax.random.normal(jax.random.PRNGKey(21), v.shape, F32)
         if n.endswith("_gamma") else v for n, v in p.items()}
    x = jax.random.normal(jax.random.PRNGKey(22), (1, 32, 32), F32)
    w = jax.random.normal(jax.random.PRNGKey(23), (1, 32, 32), F32)

    def program(x, *ws):
        return jnp.sum(op.forward([x] + list(ws), [], True, None)[0][0] * w)

    def plain_loss(x, *ws):
        return jnp.sum(ref.latent_attention(x, dict(zip(leaves, ws)), CFG)
                       * w)

    ws = [p[n] for n in leaves]
    argn = tuple(range(1 + len(ws)))
    got = jax.jit(_highest(jax.value_and_grad(program, argn)))(x, *ws)
    want = jax.jit(_highest(jax.value_and_grad(plain_loss, argn)))(x, *ws)
    _close(got[0], want[0], 1e-5)
    for a, b in zip(got[1], want[1]):
        assert np.abs(np.asarray(b)).max() > 0
        _close(a, b, 2e-4)


def test_mla_default_keeps_its_arguments():
    """The low-rank latent attention's inputs are as they were; the new
    fields only add inputs where they are set."""
    op = create_operator("MultiHeadLatentAttention", num_heads=2,
                         q_lora_rank=8, kv_lora_rank=16, qk_nope_head_dim=16,
                         qk_rope_head_dim=8, v_head_dim=16)
    assert op.list_arguments() == [
        "data", "q_a_weight", "q_a_norm_gamma", "q_b_weight", "kv_a_weight",
        "kv_a_norm_gamma", "kv_b_weight", "out_weight"]
    assert _mla_op().list_arguments() == ["data"] + [
        n.split("_", 1)[1] for n in MLA_LEAVES]


def _kda_block(op):
    def block(x, *leaves):
        return jnp.sum(op.forward([x] + list(leaves), [], True, None)[0][0])
    return block


def test_mirrored_kda_block_runs_the_forward_kernel_once(monkeypatch):
    """A KDA layer under the executor's mirrored checkpoint: the gradient's
    program holds one ``kda_forward`` and one ``kda_backward`` — the rule's
    residuals carry the names ``KEPT`` keeps, and ``mirror_kept`` lists
    them — where a plain checkpoint runs the forward kernel twice."""
    from mxnet_tpu.executor import KEPT, mirror_checkpoint
    from mxnet_tpu.kernels import common
    assert set(delta_rule.KDA_RESIDUALS) <= set(KEPT)
    monkeypatch.setattr(
        common, "dispatch",
        lambda kernel, _reference, *args: kernel(*args, interpret=True))
    op = create_operator("KimiDeltaAttention", num_heads=1, head_dim=128)
    shapes = op.infer_shape([(1, 128, 32)] + [None] * 13)[0]
    args = [jnp.zeros(s, F32) for s in shapes]
    block = _kda_block(op)
    wrt = tuple(range(len(args)))
    for fn, calls in ((block, 1), (jax.checkpoint(block), 2),
                      (mirror_checkpoint(block), 1)):
        found = _kernel_calls(jax.make_jaxpr(jax.grad(fn, wrt))(*args).jaxpr)
        assert found == {"kda_forward": calls, "kda_backward": 1}, found
    # what the mirrored segment keeps by name (``mirror_kept``): the rule's
    # residuals, and nothing of them where the checkpoint keeps no name
    kept = {name for name, _ in _kept_by_name(mirror_checkpoint(block),
                                              args)}
    assert kept == set(delta_rule.KDA_RESIDUALS), kept
    assert _kept_by_name(jax.checkpoint(block), args) == []


@pytest.mark.parametrize("axes,shape", [(("dp",), (4,)),
                                        (("dp", "tp"), (2, 2))])
def test_kda_block_on_a_mesh_lowers_for_a_tpu(axes, shape):
    """A checkpointed KDA block's value and gradient lowered for a TPU over
    four devices as ``ShardedTrainer`` lowers its step (under
    ``attention_scope``): the KDA kernels per device under ``shard_map`` —
    GSPMD does not partition a Mosaic call — the forward twice (the plain
    checkpoint recomputes it) and the backward once."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from mxnet_tpu.parallel.ring_attention import attention_scope
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(shape), axes)
    op = create_operator("KimiDeltaAttention", num_heads=2, head_dim=128)
    shapes = op.infer_shape([(4, 512, 32)] + [None] * 13)[0]
    leaves = [jnp.zeros(s, F32) for s in shapes]
    block = _kda_block(op)
    args = [jax.ShapeDtypeStruct(
        t.shape, t.dtype, sharding=NamedSharding(mesh, P("dp") if i == 0
                                                 else P()))
        for i, t in enumerate(leaves)]
    wrt = tuple(range(len(leaves)))
    with jax.enable_x64(False), attention_scope(mesh, None):
        step = jax.value_and_grad(jax.checkpoint(block), wrt)
        jaxpr = str(jax.make_jaxpr(step)(*leaves))
        calls = _mosaic_calls(step, *args)
    assert "shard_map" in jaxpr
    assert sorted(name for name, _ in calls) == [
        "@kda_backward", "@kda_forward", "@kda_forward"]


# -- routing: sigmoid, group-limited, eight a token, a shared one -------------
def _routed_op(first=4, held=4, shared=12):
    return create_operator(
        "RoutedExperts", num_experts=16, num_local_experts=held,
        first_expert=first, hidden_size=16, top_k=4, n_group=4,
        topk_group=2, routed_scaling_factor=2.5, shared_hidden_size=shared)


def _zero_aux(op, tokens=96):
    shapes = op.infer_shape([(tokens, 32)] + [None] * (
        len(op.list_arguments()) - 1))[2]
    types = op.infer_type([np.dtype("float32")] * len(op.list_arguments()))[2]
    return [jnp.zeros(s, t) for s, t in zip(shapes, types)]


def test_four_shares_add_up_to_the_uncut_layer():
    """Experts 4i .. 4i + 3 on chip i of 4, each routing over all 16 in
    four groups of four, the two best groups kept: the four partial results
    — the shared expert counted once — add up to the reference's uncut
    layer, every assignment computed exactly once, and the reference given
    a share gives that share's part."""
    key = jax.random.PRNGKey(54)
    p = _layer(key, 1)
    h = jax.random.normal(jax.random.fold_in(key, 1), (96, 32), dtype=F32)
    full = jax.random.split(jax.random.PRNGKey(55), 3)
    p = dict(p, **{
        name: 0.3 * jax.random.normal(k, shape, dtype=F32)
        for name, k, shape in zip(MOE_LEAVES[1:4], full, (
            (16, 16, 32), (16, 16, 32), (16, 32, 16)))})
    stacks = MOE_LEAVES[1:4]
    whole, margin = jax.jit(_highest(lambda h, p: ref.routed_layer(
        h, p, CFG, first=0, held=16)))(h, p)
    assert float(jnp.min(margin)) > 1e-6        # no near-tie in this draw
    total, counted = jnp.zeros_like(h), 0
    for chip in range(4):
        first = 4 * chip
        cut = {n: p[n][first:first + 4] for n in stacks}
        with_shared = chip == 0
        op = _routed_op(first=first, shared=12 if with_shared else 0)
        leaves = [p["moe_router_weight"]] + [cut[n] for n in stacks]
        if with_shared:
            leaves += [p[n] for n in MOE_LEAVES[4:]]
        outs, aux = jax.jit(lambda h, leaves, aux: op.forward(
            [h] + leaves, aux, True, None))(h, leaves, _zero_aux(op))
        part, _ = jax.jit(_highest(lambda h, p: ref.routed_layer(
            h, p, CFG, first=first, held=4, shared=with_shared)))(
                h, dict(p, **cut))
        _close(outs[0], part, 5e-5)
        total, counted = total + outs[0], counted + int(aux[1][0])
    assert counted == 96 * 4            # four experts a token, each once
    _close(total, whole, 5e-5)
    limited, _ = jax.jit(_highest(lambda h, p: ref.routing(h, p, CFG)))(h, p)
    free, _ = jax.jit(_highest(lambda h, p: ref.routing(
        h, p, CFG, without=("group_limit",))))(h, p)
    assert np.any((np.asarray(limited) > 0) != (np.asarray(free) > 0))
    # at most two groups of four hold a token's choices
    groups = np.asarray(limited).reshape(96, 4, 4).any(axis=-1).sum(axis=-1)
    assert groups.max() <= 2


# -- the model ----------------------------------------------------------------
def test_layer_pattern_and_parameters():
    from mxnet_tpu.models import transformer_kda_mla_moe as model
    from perfbench.drivers.train_step_ling import symbol_args
    args = symbol_args(CFG, SEQ)
    net = model.get_symbol(**args)
    names = net.list_arguments()
    shapes = dict(zip(names, net.infer_shape(data=(1, SEQ),
                                             softmax_label=(1, SEQ))[0]))
    assert model.routed_layer_names(args["mlp_layer_types"]) == [
        "layer1_moe", "layer2_moe"]
    assert [model.mixer_node(i, m) for i, m in enumerate(
        CFG["layer_mixers"])] == ["layer0_kda", "layer1_kda", "layer2_att"]
    assert {n: tuple(s) for n, s in ref.param_shapes(CFG).items()} == {
        n: tuple(s) for n, s in shapes.items()
        if n not in ("data", "softmax_label")}
    for bad in (dict(CFG, layer_mixers=["kda"] * 3),
                dict(CFG, expert_swiglu_limit_list=[4] * 42),
                dict(CFG, q_lora_rank=64)):
        with pytest.raises(ValueError):
            symbol_args(bad, SEQ)


def _one_step(seed=0, mirror=True):
    from mxnet_tpu import optimizer as opt_mod
    from mxnet_tpu.models import transformer_kda_mla_moe
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.trainer import ShardedTrainer
    from perfbench.drivers.train_step_ling import symbol_args
    cfg = dict(CFG, program=dict(CFG["program"], mirror_blocks=mirror))
    net = transformer_kda_mla_moe.get_symbol(**symbol_args(cfg, SEQ))
    batch = 2
    opt = opt_mod.create("sgd", learning_rate=0.5, momentum=0.9, wd=0.0,
                         rescale_grad=1.0 / (batch * SEQ))
    trainer = ShardedTrainer(net, opt, make_mesh(jax.devices()[:1], dp=1),
                             label_names=("softmax_label",))
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, CFG["vocab_size"], (batch, SEQ)).astype(np.int32)
    lab = np.roll(ids, -1, axis=1).astype(np.float32)
    shapes, labels = {"data": (batch, SEQ)}, {"softmax_label": (batch, SEQ)}
    key = jax.random.PRNGKey(70 + seed)
    params = ref.init_params(CFG, key)
    for i, (name, v) in enumerate(sorted(params.items())):
        if name.endswith(("_gamma", "_dt_bias")):     # moved off the seed's
            params[name] = v + 0.1 * jax.random.normal(
                jax.random.fold_in(key, 100 + i), v.shape, dtype=F32)
    mom = {n: jnp.zeros_like(a) for n, a in params.items()}
    aux = trainer.init_aux(shapes, labels)
    feed = trainer.shard_batch({"data": ids, "softmax_label": lab})
    w0 = {n: np.asarray(a) for n, a in params.items()}
    _p, new_mom, new_aux, outs = trainer.step(params, mom, aux, feed)
    return w0, new_mom, new_aux, outs, (ids, lab)


def test_whole_model_losses_and_per_leaf_gradients():
    w0, mom, aux, outs, (ids, lab) = _one_step()
    w = {n: jnp.asarray(a) for n, a in w0.items()}
    (_loss, rows), grads = jax.jit(_highest(jax.value_and_grad(
        lambda w, ids, lab: ref.loss_fn(w, ids, lab, CFG), has_aux=True)))(
            w, jnp.asarray(ids), jnp.asarray(lab))
    assert float(jnp.min(rows["margin"])) > 1e-6    # no near-tie drawn
    p = np.take_along_axis(np.asarray(outs[0]), lab.reshape(-1, 1)
                           .astype(np.int64), axis=1)[:, 0]
    _close(-np.log(p), rows["main"], 1e-4)
    assert set(grads) == set(mom) == set(ref.param_shapes(CFG))
    for n, g in grads.items():
        assert np.abs(np.asarray(g)).max() > 0, n
        _close(-np.asarray(mom[n]) / 0.5, g, 5e-4)      # m1 = -lr * g
    for i in (1, 2):
        c = moe.routing_counters(aux, "layer%d_moe" % i)
        assert c["local_assignments"][0] == c["expert_tokens"].sum() > 0
