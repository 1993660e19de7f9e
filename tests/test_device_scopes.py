"""observability/device_scopes.py: the scopes a step is lowered under, the
map from a compiled step's instructions to them, and the join with device
events (docs/observability.md, "Device time by scope")."""
import contextlib
import gc
import json
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.observability import device_scopes as ds
from mxnet_tpu.observability import phases

# -- parse --------------------------------------------------------------------
HLO = """HloModule jit_step, is_scheduled=true, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

FileNames
1 "/root/repo/mxnet_tpu/executor.py"

%fused_computation.1 (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0:T(256)} parameter(0)
  ROOT %multiply.3 = f32[8]{0:T(256)} multiply(%param_0.1, %param_0.1), metadata={op_name="jit(step)/jvp(ln1)/mul" stack_frame_id=3}
}

%fused_computation.2 (param_0.2: f32[8]) -> f32[8] {
  %param_0.2 = f32[8]{0} parameter(0)
  %fusion.9 = f32[8]{0} fusion(%param_0.2), kind=kLoop, calls=%fused_computation.1
  ROOT %add.7 = f32[8]{0} add(%fusion.9, %param_0.2), metadata={op_name="jit(step)/update/add"}
}

%region_0.5 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.1 = f32[] add(%a, %b), metadata={op_name="jit(step)/jvp(ln1)/reduce_sum"}
}

%body.3 (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]{0}) parameter(0)
  %get-tuple-element.1 = f32[8]{0} get-tuple-element(%p), index=1
  %fusion.4 = f32[8]{0} fusion(%get-tuple-element.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/jvp(moe)/dispatch/while/body/experts/mul" stack_frame_id=7}
  %ragged-dot-none.5 = bf16[8]{0} custom-call(%get-tuple-element.1), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  ROOT %tuple.2 = (s32[], f32[8]{0}) tuple(%get-tuple-element.1, %fusion.4)
}

ENTRY %main.9 (Arg_0.1: f32[8]) -> f32[8] {
  %Arg_0.1 = f32[8]{0:T(256)} parameter(0), metadata={op_name="wrt[\\'w\\']"}
  %fusion.1 = f32[8]{0:T(256)} fusion(%Arg_0.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/jvp(ln1)/mul" stack_frame_id=3}
  %while.1 = (s32[], f32[8]{0}) while(%tuple.0), condition=%cond.2, body=%body.3, metadata={op_name="jit(step)/jvp(moe)/dispatch/while"}
  %flash_forward.2 = bf16[8]{0} custom-call(%fusion.1), custom_call_target="tpu_custom_call", backend_config={"custom_call_config": {"body": "TUzv"}}, metadata={op_name="jit(step)/jvp(att)/kernel/jit(call)/flash_forward/pallas_call" stack_frame_id=9}
  %copy.3 = f32[8]{0} copy(%fusion.1)
  ROOT %fusion.2 = f32[8]{0} fusion(%copy.3), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(step)/update/add"}
}
"""
KERNEL = "jit(step)/jvp(att)/kernel/jit(call)/flash_forward/pallas_call"


@pytest.mark.parametrize("instruction,op_name", [
    ("fusion.1", "jit(step)/jvp(ln1)/mul"),         # an entry fusion
    ("fusion.2", "jit(step)/update/add"),           # the ROOT
    ("while.1", "jit(step)/jvp(moe)/dispatch/while"),
    # a while body's instructions are events of their own
    ("fusion.4", "jit(step)/jvp(moe)/dispatch/while/body/experts/mul"),
    ("flash_forward.2", KERNEL),                    # braces before metadata
    # no scope of its own: its user's
    ("copy.3", "jit(step)/update/copy.3"),
    ("get-tuple-element.1",
     "jit(step)/jvp(moe)/dispatch/while/body/experts/get-tuple-element.1"),
    # the compiler's own name, no user with a scope: the loop's, and the
    # sub-scope phases.COMPILER_NAMED gives the name
    ("ragged-dot-none.5",
     "jit(step)/jvp(moe)/dispatch/while/body/experts/ragged-dot-none"),
    ("Arg_0.1", "wrt[\\'w\\']"),
    ("add.1", "jit(step)/jvp(ln1)/reduce_sum"),     # a called computation
])
def test_parse_keeps_every_event(instruction, op_name):
    assert ds.parse(HLO)[instruction] == op_name


@pytest.mark.parametrize("inside_a_fusion", [
    "multiply.3", "param_0.1", "add.7", "fusion.9"])
def test_parse_leaves_fusion_bodies_out(inside_a_fusion):
    assert inside_a_fusion not in ds.parse(HLO)


def test_parse_says_what_a_fusion_holds_beside_its_root():
    held = {}
    ds.parse(HLO, held)
    assert held == {
        "fusion.1": {"", "jit(step)/jvp(ln1)/mul"},
        "fusion.4": {"", "jit(step)/jvp(ln1)/mul"},
        # through the fusion nested in it
        "fusion.2": {"", "jit(step)/update/add", "jit(step)/jvp(ln1)/mul"}}
    record = ds.StepRecord("jit_step", nodes=NODES)
    record.compiled_text = lambda: HLO
    assert record.held() == {
        "fusion.1": [("forward", "ln1")], "fusion.4": [("forward", "ln1")],
        "fusion.2": [("forward", "ln1"), ("update", None)]}


def test_parse_reads_an_unoptimized_module_too():
    text = ("HloModule jit_f, entry_computation_layout={(f32[2]{0})}\n\n"
            "inner.1 {\n  x.2 = f32[2]{0} parameter(0)\n"
            "  ROOT y.3 = f32[2]{0} sine(x.2), "
            "metadata={op_name=\"jit(f)/n/sin\"}\n}\n\n"
            "ENTRY main.4 {\n  a.5 = f32[2]{0} parameter(0)\n"
            "  ROOT c.6 = f32[2]{0} call(a.5), to_apply=inner.1\n}\n")
    assert ds.parse(text) == {"x.2": "", "y.3": "jit(f)/n/sin", "a.5": "",
                              "c.6": ""}


# -- classify -----------------------------------------------------------------
NODES = {"ln1": "LayerNorm", "att": "MultiHeadAttention",
         "moe": "RoutedExperts", "gdn": "GatedDeltaNet", "mul": "_Mul"}


@pytest.mark.parametrize("op_name,want", [
    ("jit(step)/jvp(ln1)/mul", ("forward", "ln1", None)),
    ("jit(step)/transpose(jvp(ln1))/reduce_sum", ("backward", "ln1", None)),
    # a mirrored segment: its backward, and its second forward
    ("jit(train_step)/transpose(jvp(jvp()))/checkpoint/ln1/mul",
     ("backward", "ln1", None)),
    ("jit(train_step)/transpose(jvp(jvp()))/checkpoint/"
     "rematted_computation/ln1/mul", ("recompute", "ln1", None)),
    ("jit(step)/update/sub", ("update", None, None)),
    ("jit(train_step)/grad_sync/optimization_barrier",
     ("grad_sync", None, None)),
    (KERNEL, ("forward", "att", "kernel")),
    # the innermost sub-scope: the grouped product inside the chunk loop
    ("jit(step)/jvp(moe)/dispatch/while/body/experts/ragged_dot",
     ("forward", "moe", "experts")),
    ("jit(s)/transpose(jvp())/checkpoint/rematted_computation/gdn/kernel/"
     "shard_map/cond/branch_0_fun/gated_delta_rule/jit(_forward_call)/"
     "gated_delta_forward/pallas_call", ("recompute", "gdn", "kernel")),
    # no node of the graph
    ("jit(step)/jvp()/convert_element_type", ("other", None, None)),
    ("jit(step)/transpose(jvp())/convert_element_type",
     ("other", None, None)),
    ("wrt['w']", ("other", None, None)),
    ("", ("other", None, None)),
    # the last component is the primitive, never a scope
    ("jit(step)/jvp()/mul", ("other", None, None)),
    ("jit(step)/jvp(ln1)/update", ("forward", "ln1", None)),
])
def test_classify(op_name, want):
    assert ds.classify(op_name, NODES) == want
    assert want[0] in phases.DEVICE_PHASES
    assert want[2] is None or want[2] in phases.DEVICE_SUBSCOPES


# -- inside, table, lines -----------------------------------------------------
def _record():
    record = ds.StepRecord("jit_step", nodes=NODES)
    record.compiled_text = lambda: HLO
    return record


def test_inside_keeps_the_steps_own_events():
    runs = [("jit_step(1)", 0.0, 100.0), ("jit_other(2)", 100.0, 50.0),
            ("jit_step(1)", 200.0, 100.0)]
    events = [("%fusion.1 = ...", 10.0, 5.0), ("%fusion.1 = ...", 110.0, 5.0),
              ("%fusion.2 = ...", 290.0, 20.0), ("%copy.3 = ...", 300.0, 1.0)]
    assert ds.inside(events, runs, "jit_step") == [events[0], events[2]]
    assert ds.inside(events, runs, "jit_loss") == []
    assert ds.module_of("jit_step(1234)") == "jit_step"


def test_table_charges_type_phase_node_and_the_rest():
    tab = ds.table({"fusion.1": 30.0, "fusion.2": 20.0, "while.1": 5.0,
                    "fusion.4": 15.0, "flash_forward.2": 10.0,
                    "copy.3": 7.0, "fusion.77": 3.0}, _record())
    assert tab["total_ns"] == 90.0 and tab["joined_ns"] == 87.0
    # the copy is charged to the update that uses it
    assert tab["by_phase"] == {"forward": 60.0, "update": 27.0}
    assert tab["by_type_phase"] == {
        ("LayerNorm", "forward"): 30.0, ("RoutedExperts", "forward"): 20.0,
        ("MultiHeadAttention", "forward"): 10.0}
    assert tab["by_node"] == {"ln1": 30.0, "moe": 20.0, "att": 10.0}
    assert tab["by_sub"] == {("RoutedExperts", "dispatch"): 5.0,
                             ("RoutedExperts", "experts"): 15.0,
                             ("MultiHeadAttention", "kernel"): 10.0}
    assert tab["unscoped"] == {"fusion.77": 3.0}
    # the update's fusion and the routed layer's hold LayerNorm's work
    assert tab["held"] == {"LayerNorm": 35.0}
    assert tab["scoped_ns"] == 87.0
    text = ds.lines(tab, steps=1)
    assert text[0].startswith("device 0.00 ms a step, scoped 96.7 %")
    assert "LayerNorm forward" in text[1] and "ln1" in text[2]
    assert text[4] == "held in fusions charged elsewhere: LayerNorm 0.00"


def test_dump_and_load(tmp_path):
    path = str(tmp_path / "step.json")
    ds.dump(path, _record())
    again = ds.load(path)
    assert (again.module, again.nodes) == ("jit_step", NODES)
    assert again.scopes() == ds.parse(HLO)
    assert again.held() == _record().held()
    assert json.load(open(path))["module"] == "jit_step"


# -- the program's steps ------------------------------------------------------
V, S, B = 32, 16, 4


def _net(layers=2):
    return mx.models.transformer.get_symbol(
        vocab_size=V, num_layers=layers, num_heads=2, dim=16, seq_len=S,
        mirror_blocks=True)


def _routed_net():
    from mxnet_tpu.models import transformer_mla_moe
    return transformer_mla_moe.get_symbol(
        vocab_size=V, num_layers=2, dim=32, seq_len=S, num_heads=2,
        q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8,
        qk_rope_head_dim=8, v_head_dim=12, intermediate_size=48,
        moe_intermediate_size=16, n_routed_experts=8, n_local_experts=8,
        num_experts_per_tok=2, num_nextn_predict_layers=0,
        mirror_blocks=True)


def _batch(batch=B):
    r = np.random.RandomState(0)
    return (r.randint(0, V, (batch, S)).astype(np.float32),
            r.randint(0, V, (batch, S)).astype(np.float32))


def _fit(net, batch=B):
    """One step of ``Module.fit``'s fused step; the executor."""
    data, label = _batch(batch)
    mod = mx.mod.Module(net, context=mx.cpu(0))
    mod.fit(mx.io.NDArrayIter(data, label, batch_size=batch), num_epoch=1,
            optimizer="sgd", eval_metric="ce",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
            initializer=mx.init.Xavier())
    exe = mod._exec_group.execs[0]
    assert exe._n_fused_step >= 1
    return exe


def _trainer(net, mesh_shape=(1,), axes=("dp",), batch=B, seq=S):
    """A trainer whose step is on record without having run: abstract
    state, as ``tools/aot_longcontext_check.py`` lowers it."""
    from jax.sharding import Mesh
    from mxnet_tpu import optimizer as opt_mod
    from mxnet_tpu.parallel.trainer import ShardedTrainer
    n = int(np.prod(mesh_shape))
    mesh = Mesh(np.array(jax.devices()[:n]).reshape(mesh_shape), axes)
    tr = ShardedTrainer(net, opt_mod.create("sgd", learning_rate=0.1,
                                            momentum=0.9), mesh)
    shape = (batch, seq)
    params, state, aux = tr.abstract_state(
        {"data": shape}, label_shapes={"softmax_label": shape})
    rep = tr._replicated()
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))

    def arg(shp, dtype, sharding=rep):
        return jax.ShapeDtypeStruct(shp, dtype, sharding=sharding)

    tr._abstract_args = (
        params, state, aux,
        {"data": arg(shape, jnp.float32, tr.batch_sharding(shape)),
         "softmax_label": arg(shape, jnp.float32, tr.batch_sharding(shape))},
        arg(key.shape, key.dtype), arg((), jnp.float32),
        arg((), jnp.float32), arg((), jnp.int32))
    return tr


def _module_name(text):
    """As a trace's ``XLA Modules`` line prints it (before the
    fingerprint): the name on the compiled text's first line."""
    return re.match(r"HloModule\s+([^\s,]+)", text).group(1)


def _phases(record):
    return {found[0] for found in record.classified().values()}


def _arithmetic_nodes(record):
    """The nodes that lower to arithmetic of their own (a reshape is a
    bitcast, and an addition fuses into its consumer's root)."""
    return {n for n, t in record.nodes.items()
            if t in ("LayerNorm", "RMSNorm", "MultiHeadAttention",
                     "MultiHeadLatentAttention", "FullyConnected",
                     "RoutedExperts", "SoftmaxOutput", "Embedding")}


def test_executor_fused_step_is_on_record_by_node_and_phase():
    before = len(ds.records())
    exe = _fit(_net())
    record = exe.device_scopes()
    assert record is ds.latest() and len(ds.records()) == before + 1
    assert record.module == "jit_step"
    assert record.nodes["layer0_att"] == "MultiHeadAttention"
    text = record.lower().compile().as_text()
    assert _module_name(text) == record.module
    assert {"forward", "recompute", "backward", "update"} <= _phases(record)
    seen = {found[1] for found in record.classified().values()}
    assert _arithmetic_nodes(record) <= seen
    # each layer's attention in its own node, in all three passes
    by_node = {}
    for phase, node, sub in record.classified().values():
        if sub == "kernel":
            by_node.setdefault(node, set()).add(phase)
    assert by_node == {
        "layer0_att": {"forward", "recompute", "backward"},
        "layer1_att": {"forward", "recompute", "backward"}}


@pytest.mark.parametrize("mesh_shape,axes", [
    ((1,), ("dp",)), ((2, 2), ("dp", "tp"))])
def test_trainer_step_is_on_record_by_node_and_phase(mesh_shape, axes):
    tr = _trainer(_routed_net(), mesh_shape, axes)
    record = tr.device_scopes()
    assert record is tr.device_scopes() is ds.latest()
    assert record.module == "jit_train_step"
    assert _module_name(record.lower().compile().as_text()) \
        == record.module
    assert {"forward", "recompute", "backward", "update"} <= _phases(record)
    seen = {found[1] for found in record.classified().values()}
    assert _arithmetic_nodes(record) <= seen
    subs = {(record.nodes[node], sub)
            for _p, node, sub in record.classified().values() if sub}
    assert {("MultiHeadLatentAttention", s)
            for s in phases.ATTENTION_SCOPES} <= subs
    assert {("RoutedExperts", s) for s in phases.ROUTED_SCOPES} <= subs


def test_gradient_buckets_lower_under_grad_sync():
    tr = _trainer(_net(), (2, 2), ("dp", "tp"))
    assert tr._bucket_grads
    tr2 = _trainer(_net())
    assert not tr2._bucket_grads
    from mxnet_tpu.parallel import overlap
    # small buckets, so that the tiny model has more than one
    real = overlap.bucket_bytes
    overlap.bucket_bytes = lambda explicit_mb=None: 1024
    try:
        text = tr.device_scopes().lower().as_text(debug_info=True)
    finally:
        overlap.bucket_bytes = real
    found = [m for m in re.findall(r'loc\("([^"]*)"', text)
             if "optimization_barrier" in m]
    assert found and all(
        ds.classify(m, tr.device_scopes().nodes)[0] == "grad_sync"
        for m in found)


def _stripped(text):
    """A compiled module without its metadata and the source tables that
    stand before the first computation.  What the partitioner makes anew
    inside an inlined function it names after that function
    (``jit_silu_.6``), which jax names after the name stack around the
    call (``jvp(jit(silu))`` where no node's scope takes the ``jvp``):
    those names are made alike; every other instruction keeps its own."""
    text = re.sub(r",?\s*metadata=\{[^}]*\}", "", text)
    text = re.sub(r"%(?:jvp_)?jit_[A-Za-z_]+\.\d+", "%inlined", text)
    head, sep, rest = text.partition("\n\n%")
    return text.splitlines()[0] + sep + rest


@pytest.mark.parametrize("build", [
    lambda: _fit(_net()).device_scopes(),
    lambda: _trainer(_net()).device_scopes(),
    lambda: _trainer(_routed_net()).device_scopes(),
    lambda: _trainer(_routed_net(), (2, 2), ("dp", "tp")).device_scopes(),
], ids=["fit", "trainer", "trainer-routed", "trainer-routed-dp2tp2"])
def test_scopes_are_metadata_alone(build, monkeypatch):
    """The optimized step with the scopes is the one without them, once
    metadata is stripped: not a fusion moved."""
    record = build()
    with_scopes = record.compiled_text()
    names = " ".join(re.findall(r'op_name="([^"]*)"', with_scopes))
    assert "jvp(layer0_" in names and "/update/" in names
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    jax.clear_caches()
    without = record.compiled_text()
    names = " ".join(re.findall(r'op_name="([^"]*)"', without))
    assert "jvp(layer0_" not in names and "/update/" not in names
    assert _stripped(with_scopes) == _stripped(without)


def test_scopes_come_from_a_compile_of_their_own(monkeypatch):
    """jax keys its compile cache without the metadata, and hands
    ``lowered.compile()`` the running executable from memory: a step
    that ran an executable of other scopes still maps its own."""
    exe = _fit(_net(layers=1))
    record = exe.device_scopes()
    scopes = record.scopes()
    assert any("jvp(layer0_att)" in op for op in scopes.values())
    key = "jax_compilation_cache_include_metadata_in_key"
    assert getattr(jax.config, key) is False        # put back
    assert record.scopes() is scopes                # kept, not compiled again


def test_record_outlives_its_trainer_and_holds_no_array():
    from mxnet_tpu import optimizer as opt_mod
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.trainer import ShardedTrainer
    net = _net(layers=1)
    first = len(ds.records())
    records = []
    for batch in (B, 2 * B):        # a rebind at another shape
        tr = ShardedTrainer(net, opt_mod.create("sgd", learning_rate=0.1),
                            make_mesh(jax.devices()[:1], dp=1))
        params, state, aux = tr.init_params(
            {"data": (batch, S)}, label_shapes={"softmax_label": (batch, S)},
            initializer=mx.init.Xavier(rnd_type="gaussian"))
        data, label = _batch(batch)
        assert tr.device_scopes() is None       # before the first step
        out = tr.step(params, state, aux, tr.shard_batch(
            {"data": data, "softmax_label": label}))
        records.append(tr.device_scopes())
        del tr, params, state, aux, out
    gc.collect()
    assert ds.records()[first:] == records and ds.latest() is records[1]
    assert records[0].args[3]["data"].shape == (B, S)
    assert records[1].args[3]["data"].shape == (2 * B, S)
    for record in records:
        leaves = jax.tree_util.tree_leaves(
            (record.args, record.nodes, record.module))
        assert not any(isinstance(x, (jax.Array, np.ndarray))
                       for x in leaves)
        assert {"forward", "backward", "update"} <= _phases(record)
    assert "_scopes" in vars(records[0]) and "text" not in vars(records[0])


# -- lowered for a TPU: each layer's kernel in its own node and pass ----------
def _hybrid_net():
    from mxnet_tpu.models import transformer_hybrid_moe
    return transformer_hybrid_moe.get_symbol(
        vocab_size=64, num_layers=4, dim=256, seq_len=512,
        full_attention_interval=2, num_heads=4, num_kv_heads=2,
        head_dim=128, linear_num_key_heads=2, linear_num_value_heads=4,
        linear_key_head_dim=128, linear_value_head_dim=128, num_experts=8,
        n_local_experts=8, num_experts_per_tok=2, mirror_blocks=True)


def _kernel_sites(text):
    """``[(kernel, op_name of its call site)]`` of a module lowered for a
    TPU (StableHLO with locations).  A kernel's call is jitted, so its
    Mosaic call sits in a private function and carries a name relative to
    it; a ``shard_map``'s body starts a name stack of its own.  XLA joins
    them where it inlines (the chip's compiler, run on this model through
    ``tools/aot_longcontext_check.py``'s path: every kernel's ``op_name``
    begins with its call site's): the join is made here as it is there."""
    locs = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    at_end = re.compile(r"loc\((#loc\d+)\)\s*$")
    holds = {}
    for name, body in re.findall(
            r"func\.func private @(\w+)\((.*?)\n  \}", text, re.S):
        kernel = re.search(r'@tpu_custom_call.*?kernel_name = "(\w+)"', body,
                           re.S)
        if kernel:
            holds[name] = kernel.group(1)
    lines = text.splitlines()
    regions, opened = [], []        # (first line, last line, name)
    for i, line in enumerate(lines):
        if "sdy.manual_computation(" in line:
            opened.append(i)
        elif opened and re.match(r"\s*\} : ", line) \
                and lines[i - 1].lstrip().startswith("sdy.return"):
            regions.append((opened.pop(), i,
                            locs[at_end.search(line).group(1)]))
    out = []
    for i, line in enumerate(lines):
        call = re.search(r"call @(\w+)\(", line)
        if not call or call.group(1) not in holds:
            continue
        name = locs[at_end.search(line).group(1)]
        around = [r for r in regions if r[0] < i < r[1]]
        if around:
            name = max(around)[2] + "/" + name
        out.append((holds[call.group(1)], name))
    return out


@pytest.mark.parametrize("mesh_shape,axes", [
    ((1,), ("dp",)), ((2, 2), ("dp", "tp"))])
def test_each_layers_kernel_is_charged_to_its_own_node_and_pass(mesh_shape,
                                                                axes):
    """The inner-``jit`` hazard: both flash kernels and the delta rule's
    are jitted once per shape.  A cached lowering that kept the first call
    site's scope would charge every layer's kernel to layer 0's forward."""
    tr = _trainer(_hybrid_net(), mesh_shape, axes, seq=512)
    record = tr.device_scopes()
    with jax.enable_x64(False), record.context():
        text = record.jitted.trace(*record.args).lower(
            lowering_platforms=("tpu",)).as_text(debug_info=True)
    found = {}
    for kernel, name in _kernel_sites(text):
        phase, node, sub = ds.classify(name + "/pallas_call", record.nodes)
        assert sub == "kernel", name
        found.setdefault(kernel, []).append((node, phase))
    gdn, att = ("layer0_gdn", "layer2_gdn"), ("layer1_att", "layer3_att")
    # what either forward kernel hands its backward is kept
    # (executor.KEPT): no mirrored block's recomputation runs the rule's
    # again
    assert sorted(found["gated_delta_forward"]) == sorted(
        [(n, "forward") for n in gdn])
    assert sorted(found["gated_delta_backward"]) == sorted(
        [(n, "backward") for n in gdn])
    assert sorted(found["flash_backward"]) == sorted(
        [(n, "backward") for n in att])
    forward = sorted(found["flash_forward"])
    assert [s for s in forward if s[1] == "forward"] == [
        (n, "forward") for n in att]
    assert {p for _n, p in forward} <= {"forward", "recompute"}
