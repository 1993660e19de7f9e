"""mxnet_tpu/analysis/: every lint rule gets a positive hit on a
known-bad graph AND stays silent on the bundled clean models; plus the
three wiring surfaces (Symbol.validate, the Executor validate= knob,
analyze_json for saved graphs)."""
import json
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import analysis
from mxnet_tpu.analysis import (GraphIssue, GraphLintWarning, analyze,
                                analyze_json, max_severity)
from mxnet_tpu.base import MXNetError


def _ids(issues):
    return {i.rule_id for i in issues}


def _only(issues, rule_id):
    return [i for i in issues if i.rule_id == rule_id]


# ----------------------------------------------------------------------
# clean models: no false positives
# ----------------------------------------------------------------------
@pytest.mark.parametrize("builder,shapes", [
    (lambda: mx.models.get_mlp(), {"data": (32, 784)}),
    (lambda: mx.models.get_alexnet(), {"data": (2, 3, 224, 224)}),
])
def test_clean_models_have_no_findings(builder, shapes):
    issues = builder().validate(shapes=shapes)
    assert issues == [], analysis.format_issues(issues)


def test_clean_model_without_shapes_only_info():
    """No shape hints: unknown shapes are expected, so MXL-S001 reports
    at info severity and nothing else fires."""
    issues = mx.models.get_mlp().validate()
    assert all(i.severity == "info" for i in issues), issues
    assert _ids(issues) <= {"MXL-S001"}


# ----------------------------------------------------------------------
# MXL-S / MXL-T: shape & dtype re-verification
# ----------------------------------------------------------------------
def test_s001_unknown_shape_is_warning_with_hints():
    net = mx.models.get_mlp()
    # a hint that leaves fc weights underdetermined: batch dim only
    issues = net.validate(select={"MXL-S001"})
    assert _only(issues, "MXL-S001"), "expected unknown-shape findings"


def test_s002_contradictory_shapes():
    data = mx.sym.Variable("data")
    fc = mx.sym.FullyConnected(data=data, num_hidden=5, name="fc")
    bad = fc + data          # (N, 5) + (N, 784): contradiction
    issues = bad.validate(data=(8, 784))
    hits = _only(issues, "MXL-S002")
    assert hits and all(i.severity == "error" for i in hits)
    # errors sort first
    assert issues[0].severity == "error"


def test_t001_mixed_float_widths():
    a = mx.sym.Variable("a")
    b = mx.sym.Variable("b")
    out = a + b
    issues = out.validate(shapes={"a": (4, 4), "b": (4, 4)},
                          type_dict={"a": np.float32, "b": jnp.bfloat16})
    hits = _only(issues, "MXL-T001")
    assert len(hits) == 1 and hits[0].severity == "warning"
    assert "bfloat16" in hits[0].message
    # uniform dtypes: silent
    clean = out.validate(shapes={"a": (4, 4), "b": (4, 4)},
                         type_dict={"a": np.float32, "b": np.float32})
    assert not _only(clean, "MXL-T001")


def test_t002_infer_type_failure():
    data = mx.sym.Variable("data")
    fc = mx.sym.FullyConnected(data=data, num_hidden=3, name="fc")

    def boom(in_types):
        raise TypeError("synthetic infer_type failure")

    fc._heads[0][0].op.infer_type = boom
    issues = fc.validate(data=(2, 8), select={"MXL-T002"})
    hits = _only(issues, "MXL-T002")
    assert hits and hits[0].severity == "error"
    assert "synthetic" in hits[0].message


# ----------------------------------------------------------------------
# MXL-G: dead / unused / alias / duplicate names
# ----------------------------------------------------------------------
def _saved_graph_with_orphans():
    """mlp JSON + one orphan op node (dead) + one orphan variable."""
    graph = json.loads(mx.models.get_mlp().tojson())
    n = len(graph["nodes"])
    graph["nodes"].append({"op": "null", "name": "orphan_var",
                           "attr": {}, "inputs": []})
    graph["nodes"].append({"op": "Flatten", "name": "orphan_op",
                           "attr": {}, "inputs": [[n, 0]]})
    graph["arg_nodes"].append(n)
    return graph


def test_g001_g002_dead_nodes_in_saved_graph():
    issues = analyze_json(_saved_graph_with_orphans())
    dead = _only(issues, "MXL-G001")
    unused = _only(issues, "MXL-G002")
    assert [i.node for i in dead] == ["orphan_op"]
    assert [i.node for i in unused] == ["orphan_var"]
    # the clean round-trip has neither
    assert not _ids(analyze_json(mx.models.get_mlp().tojson())) & \
        {"MXL-G001", "MXL-G002"}


def test_g002_ignored_bind_dict_keys():
    net = mx.models.get_mlp()
    issues = analyze(net, args={"data": None, "not_an_arg": None},
                     select={"MXL-G002"})
    hits = _only(issues, "MXL-G002")
    assert len(hits) == 1 and "not_an_arg" in hits[0].message


def test_g003_output_aliases_input():
    data = mx.sym.Variable("data")
    fc = mx.sym.FullyConnected(data=data, num_hidden=2, name="fc")
    grouped = mx.sym.Group([fc, data])      # head 1 is a bare variable
    hits = _only(grouped.validate(), "MXL-G003")
    assert hits and hits[0].node == "data"
    dup = mx.sym.Group([fc, fc])            # duplicate head
    assert _only(dup.validate(), "MXL-G003")


def test_g004_duplicate_node_names():
    a = mx.sym.Variable("x")
    f1 = mx.sym.FullyConnected(data=a, num_hidden=2, name="same")
    f2 = mx.sym.FullyConnected(data=f1, num_hidden=2, name="same")
    hits = _only(f2.validate(), "MXL-G004")
    assert hits and hits[0].severity == "error"
    assert "same" in hits[0].message


# ----------------------------------------------------------------------
# MXL-B: bind contract
# ----------------------------------------------------------------------
def _two_var_sum():
    a = mx.sym.Variable("a")
    b = mx.sym.Variable("b")
    return a + b


def test_b001_shared_grad_buffer():
    net = _two_var_sum()
    g = mx.nd.zeros((4,))
    issues = analyze(net, args_grad={"a": g, "b": g}, grad_req="write")
    hits = _only(issues, "MXL-B001")
    assert {i.node for i in hits} == {"a", "b"}
    assert all(i.severity == "error" for i in hits)
    # grad_req='add' on shared buffers is the supported pattern
    assert not _only(analyze(net, args_grad={"a": g, "b": g},
                             grad_req="add"), "MXL-B001")


def test_b002_partial_args_grad():
    net = _two_var_sum()
    issues = analyze(net, args_grad={"a": mx.nd.zeros((4,))},
                     grad_req="write")
    hits = _only(issues, "MXL-B002")
    assert [i.node for i in hits] == ["b"]
    # all-None args_grad = intentional forward-only: silent
    assert not _only(analyze(net, grad_req="write"), "MXL-B002")


def test_b003_aux_name_collision():
    data = mx.sym.Variable("data")
    bn1 = mx.sym.BatchNorm(data=data, name="bn")
    bn2 = mx.sym.BatchNorm(data=bn1, name="bn")
    issues = analyze(bn2, grad_req="write")
    assert _only(issues, "MXL-B003")
    assert _only(issues, "MXL-G004")    # same root cause, both surfaced


def test_b004_invalid_grad_req():
    issues = analyze(_two_var_sum(), grad_req="wirte")   # typo'd "write"
    hits = _only(issues, "MXL-B004")
    assert hits and all(i.severity == "error" for i in hits)


def test_b005_unmapped_ctx_group():
    with mx.AttrScope(ctx_group="dev1"):
        net = _two_var_sum()
    issues = analyze(net, group2ctx={"dev2": mx.cpu()})
    assert _only(issues, "MXL-B005")
    # empty group2ctx: the attrs are inert, no finding
    assert not _only(analyze(net), "MXL-B005")


# ----------------------------------------------------------------------
# MXL-L: TPU lowering lint
# ----------------------------------------------------------------------
def test_l001_unsupported_platform():
    data = mx.sym.Variable("data")
    fc = mx.sym.FullyConnected(data=data, num_hidden=2, name="fc")
    fc._heads[0][0].op.unsupported_platforms = ("tpu",)
    hits = _only(fc.validate(target="tpu"), "MXL-L001")
    assert hits and hits[0].severity == "error"
    assert not _only(fc.validate(target="cpu"), "MXL-L001")


def test_l001_unregistered_op_in_saved_graph():
    graph = json.loads(mx.models.get_mlp().tojson())
    for spec in graph["nodes"]:
        if spec["op"] == "FullyConnected":
            spec["op"] = "NoSuchOp"
            break
    issues = analyze_json(graph)
    hits = _only(issues, "MXL-L001")
    assert hits and "NoSuchOp" in hits[0].message


class _LintDemoProp(mx.operator.CustomOpProp):
    def __init__(self):
        super().__init__(need_top_grad=True)

    def list_arguments(self):
        return ["data"]

    def infer_shape(self, in_shape):
        return in_shape, [in_shape[0]], []


mx.operator.register("analysis_lintdemo")(_LintDemoProp)


def test_l002_l003_host_callback():
    data = mx.sym.Variable("data")
    plain = mx.sym.Custom(data=data, op_type="analysis_lintdemo")
    out = mx.sym.FullyConnected(data=plain, num_hidden=2, name="fc")
    issues = out.validate(data=(2, 4))
    assert _only(issues, "MXL-L003")          # info: fusion barrier
    assert not _only(issues, "MXL-L002")      # not mirrored: no error

    mirrored = mx.sym.Custom(data=data, op_type="analysis_lintdemo",
                             attr={"force_mirroring": "1"})
    out2 = mx.sym.FullyConnected(data=mirrored, num_hidden=2, name="fc")
    hits = _only(out2.validate(data=(2, 4)), "MXL-L002")
    assert hits and hits[0].severity == "error"


def test_l004_sharding_axes_vs_mesh():
    from jax.sharding import Mesh, PartitionSpec as P
    from mxnet_tpu.parallel.sharding import ShardingRules
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "tp"))
    net = mx.models.get_mlp()
    bad = ShardingRules([(r".*_weight", lambda s, m: P("mp", None))])
    hits = _only(net.validate(data=(8, 784), mesh=mesh,
                              sharding_rules=bad), "MXL-L004")
    assert hits and all(i.severity == "error" for i in hits)
    assert "mp" in hits[0].message
    ok = ShardingRules([(r"fc1_weight", lambda s, m: P(None, "tp"))])
    assert not _only(net.validate(data=(8, 784), mesh=mesh,
                                  sharding_rules=ok), "MXL-L004")


# ----------------------------------------------------------------------
# framework: suppression, select/skip, ordering, issue type
# ----------------------------------------------------------------------
def test_suppression_via_node_attr():
    data = mx.sym.Variable("data")
    quiet = mx.sym.Custom(data=data, op_type="analysis_lintdemo",
                          attr={"force_mirroring": "1",
                                "__lint_ignore__": "MXL-L002,MXL-L003"})
    out = mx.sym.FullyConnected(data=quiet, num_hidden=2, name="fc")
    issues = out.validate(data=(2, 4))
    assert not _ids(issues) & {"MXL-L002", "MXL-L003"}

    all_quiet = mx.sym.Custom(data=data, op_type="analysis_lintdemo",
                              attr={"force_mirroring": "1",
                                    "__lint_ignore__": "all"})
    out2 = mx.sym.FullyConnected(data=all_quiet, num_hidden=2, name="fc")
    assert not _ids(out2.validate(data=(2, 4))) & {"MXL-L002", "MXL-L003"}


def test_select_and_skip():
    net = mx.models.get_mlp()
    only = net.validate(select={"MXL-S001"})
    assert _ids(only) <= {"MXL-S001"}
    skipped = net.validate(skip={"MXL-S001"})
    assert "MXL-S001" not in _ids(skipped)


def test_issue_type_and_ordering():
    i = GraphIssue("MXL-X999", "warning", "node1", "msg")
    assert i.as_dict() == {"rule_id": "MXL-X999", "severity": "warning",
                           "node": "node1", "message": "msg"}
    assert "MXL-X999" in repr(i)
    assert max_severity([]) is None
    assert max_severity([i]) == "warning"
    # registry sanity: every registered rule id is well-formed & unique
    ids = list(analysis.RULE_REGISTRY)
    assert len(ids) == len(set(ids))
    assert all(r.startswith("MXL-") for r in ids)
    assert all(analysis.RULE_REGISTRY[r].severity in analysis.SEVERITIES
               for r in ids)


# ----------------------------------------------------------------------
# Executor wiring: validate="warn"|"error"|"off"
# ----------------------------------------------------------------------
def _bad_bind_kwargs():
    net = _two_var_sum()
    g = mx.nd.zeros((4,))
    args = {"a": mx.nd.zeros((4,)), "b": mx.nd.zeros((4,))}
    return net, dict(args=args, args_grad={"a": g, "b": g},
                     grad_req="write")


def test_bind_validate_default_warns():
    net, kw = _bad_bind_kwargs()
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        exe = net.bind(mx.cpu(), **kw)
    lint = [w for w in rec if issubclass(w.category, GraphLintWarning)]
    assert len(lint) == 1 and "MXL-B001" in str(lint[0].message)
    assert {i.rule_id for i in exe.bind_issues} >= {"MXL-B001"}


def test_bind_validate_error_raises():
    net, kw = _bad_bind_kwargs()
    with pytest.raises(MXNetError, match="MXL-B001"):
        net.bind(mx.cpu(), validate="error", **kw)


def test_bind_validate_off_is_silent():
    net, kw = _bad_bind_kwargs()
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        exe = net.bind(mx.cpu(), validate="off", **kw)
    assert not [w for w in rec
                if issubclass(w.category, GraphLintWarning)]
    assert exe.bind_issues == []


def test_bind_validate_env_default(monkeypatch):
    monkeypatch.setenv("MXTPU_BIND_VALIDATE", "error")
    net, kw = _bad_bind_kwargs()
    with pytest.raises(MXNetError, match="bind validation failed"):
        net.bind(mx.cpu(), **kw)


def test_bind_validate_bad_mode_rejected():
    net, kw = _bad_bind_kwargs()
    with pytest.raises(MXNetError, match="validate"):
        net.bind(mx.cpu(), validate="loud", **kw)


def test_clean_bind_emits_no_warning():
    net = mx.models.get_mlp()
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        exe = net.simple_bind(mx.cpu(), data=(8, 784))
    assert not [w for w in rec
                if issubclass(w.category, GraphLintWarning)]
    assert exe.bind_issues == []


# ----------------------------------------------------------------------
# MXL-P/M/C: SPMD propagation, memory, collective audit
# ----------------------------------------------------------------------
def _mesh22():
    from mxnet_tpu.parallel import LogicalMesh
    return LogicalMesh(dp=2, tp=2)


def _transformer():
    from mxnet_tpu.models.transformer import get_symbol
    return get_symbol(vocab_size=512, num_layers=2, num_heads=4, dim=64,
                      seq_len=64), {"data": (2, 64), "softmax_label": (2, 64)}


def test_spmd_transformer_clean_under_mesh():
    """The bundled transformer under dp=2,tp=2 has no sharding errors:
    only the expected row-parallel psum (info) and the one-sided
    contractions the default policy leaves open (warning)."""
    net, shapes = _transformer()
    issues = net.validate(shapes=shapes, mesh=_mesh22())
    assert max_severity(issues) != "error", analysis.format_issues(issues)
    assert _only(issues, "MXL-P004")
    assert _only(issues, "MXL-C003")
    # and the communication report prices the implied collectives
    ctxs = []
    analyze(net, shapes=shapes, mesh=_mesh22(), _ctx_out=ctxs)
    comm = analysis.comm_report(ctxs[0])
    assert comm["complete"] and comm["total_bytes"] > 0
    assert comm["by_kind"]["reduce"]["count"] >= 1


def _mis_sharded():
    """fc1 col-parallel makes its output tp-sharded on dim 1; fc2's rule
    claims dp on the same contraction dim -> forced reshard (MXL-P001)."""
    from jax.sharding import PartitionSpec as P
    from mxnet_tpu.parallel.sharding import ShardingRules
    data = mx.sym.Variable("data")
    fc1 = mx.sym.FullyConnected(data=data, num_hidden=8, name="fc1")
    fc2 = mx.sym.FullyConnected(data=fc1, num_hidden=4, name="fc2")
    net = mx.sym.SoftmaxOutput(data=fc2, name="softmax")
    rules = ShardingRules([
        (r"fc1_weight", lambda s, m: P("tp", None)),
        (r"fc2_weight", lambda s, m: P(None, "dp")),
        (r".*_bias", lambda s, m: P(None)),
    ])
    return net, {"data": (8, 16), "softmax_label": (8,)}, rules


def test_p001_mis_sharded_graph_errors_with_bytes():
    net, shapes, rules = _mis_sharded()
    ctxs = []
    issues = analyze(net, shapes=shapes, mesh=_mesh22(),
                     sharding_rules=rules, _ctx_out=ctxs)
    hits = _only(issues, "MXL-P001")
    assert hits and all(i.severity == "error" for i in hits)
    assert hits[0].node == "fc2"
    assert "reshard" in hits[0].message
    resh = analysis.comm_report(ctxs[0])["by_kind"]["reshard"]
    assert resh["bytes"] > 0
    # without the conflicting rules the same graph is reshard-free
    clean = analyze(net, shapes=shapes, mesh=_mesh22())
    assert not _only(clean, "MXL-P001")


def test_p002_sharded_value_consumed_replicated():
    from jax.sharding import PartitionSpec as P
    from mxnet_tpu.parallel.sharding import ShardingRules
    data = mx.sym.Variable("data")
    fc = mx.sym.FullyConnected(data=data, num_hidden=8, name="fc1")
    net = mx.sym.SoftmaxOutput(data=fc, name="softmax")
    # weight replicated but bias tp-sharded: the add needs it whole
    rules = ShardingRules([(r"fc1_weight", lambda s, m: P(None, None)),
                           (r"fc1_bias", lambda s, m: P("tp"))])
    issues = analyze(net, shapes={"data": (8, 16), "softmax_label": (8,)},
                     mesh=_mesh22(), sharding_rules=rules)
    hits = _only(issues, "MXL-P002")
    assert hits and hits[0].severity == "warning"
    assert "all-gather" in hits[0].message


def test_p003_non_divisible_param_degrades_to_replicated():
    data = mx.sym.Variable("data")
    fc = mx.sym.FullyConnected(data=data, num_hidden=3, name="fc1")
    net = mx.sym.SoftmaxOutput(data=fc, name="softmax")
    # (3, 5) has no dim divisible by tp=2: the default policy degrades
    issues = analyze(net, shapes={"data": (4, 5), "softmax_label": (4,)},
                     mesh=_mesh22())
    hits = _only(issues, "MXL-P003")
    assert any(i.node == "fc1_weight" for i in hits)
    assert all(i.severity == "info" for i in hits)
    assert "replicated" in hits[0].message


def test_p004_row_parallel_contraction_psum():
    from jax.sharding import PartitionSpec as P
    from mxnet_tpu.parallel.sharding import ShardingRules
    data = mx.sym.Variable("data")
    fc1 = mx.sym.FullyConnected(data=data, num_hidden=8, name="fc1")
    fc2 = mx.sym.FullyConnected(data=fc1, num_hidden=4, name="fc2")
    net = mx.sym.SoftmaxOutput(data=fc2, name="softmax")
    rules = ShardingRules([(r"fc1_weight", lambda s, m: P("tp", None)),
                           (r"fc2_weight", lambda s, m: P(None, "tp")),
                           (r".*_bias", lambda s, m: P(None))])
    issues = analyze(net, shapes={"data": (8, 16), "softmax_label": (8,)},
                     mesh=_mesh22(), sharding_rules=rules)
    hits = _only(issues, "MXL-P004")
    assert any(i.node == "fc2" for i in hits)
    assert "psum" in hits[0].message
    assert not _only(issues, "MXL-P001")


def test_c003_one_sided_contraction():
    from jax.sharding import PartitionSpec as P
    from mxnet_tpu.parallel.sharding import ShardingRules
    data = mx.sym.Variable("data")
    fc = mx.sym.FullyConnected(data=data, num_hidden=8, name="fc1")
    net = mx.sym.SoftmaxOutput(data=fc, name="softmax")
    # only the weight's contraction dim is sharded: XLA must gather
    rules = ShardingRules([(r"fc1_weight", lambda s, m: P(None, "tp")),
                           (r"fc1_bias", lambda s, m: P(None))])
    issues = analyze(net, shapes={"data": (8, 16), "softmax_label": (8,)},
                     mesh=_mesh22(), sharding_rules=rules)
    hits = _only(issues, "MXL-C003")
    assert hits and hits[0].node == "fc1"
    assert hits[0].severity == "warning"


def test_c001_kvstore_scope():
    from mxnet_tpu.parallel import LogicalMesh
    net = mx.models.get_mlp()
    # unknown type: error even without a mesh
    issues = analyze(net, shapes={"data": (8, 784)}, kvstore="bogus")
    hits = _only(issues, "MXL-C001")
    assert hits and hits[0].severity == "error"
    # device-scope kvstore under a pod-sized mesh: silently local
    big = LogicalMesh(dp=64, tp=4)
    issues = analyze(net, shapes={"data": (8, 784)}, kvstore="device",
                     mesh=big)
    hits = _only(issues, "MXL-C001")
    assert hits and hits[0].severity == "error"
    assert "dist_sync" in hits[0].message
    # dist_async: documented sync-semantics divergence, warning only
    issues = analyze(net, shapes={"data": (8, 784)}, kvstore="dist_async",
                     mesh=big)
    hits = _only(issues, "MXL-C001")
    assert hits and hits[0].severity == "warning"
    # a matching scope is silent
    issues = analyze(net, shapes={"data": (8, 784)}, kvstore="dist_sync",
                     mesh=big)
    assert not _only(issues, "MXL-C001")


def test_c002_collective_across_pipeline_stage():
    from jax.sharding import PartitionSpec as P
    from mxnet_tpu.parallel.sharding import ShardingRules
    data = mx.sym.Variable("data")
    with mx.AttrScope(ctx_group="stage0"):
        fc1 = mx.sym.FullyConnected(data=data, num_hidden=8, name="fc1")
    with mx.AttrScope(ctx_group="stage1"):
        fc2 = mx.sym.FullyConnected(data=fc1, num_hidden=4, name="fc2")
    net = mx.sym.SoftmaxOutput(data=fc2, name="softmax")
    rules = ShardingRules([(r"fc1_weight", lambda s, m: P("tp", None)),
                           (r"fc2_weight", lambda s, m: P(None, "tp")),
                           (r".*_bias", lambda s, m: P(None))])
    shapes = {"data": (8, 16), "softmax_label": (8,)}
    issues = analyze(net, shapes=shapes, mesh=_mesh22(),
                     sharding_rules=rules)
    hits = _only(issues, "MXL-C002")
    assert hits and hits[0].node == "fc2"
    assert "pipeline" in hits[0].message
    # a single-stage graph never trips the audit
    single = _mis_sharded()[0]
    assert not _only(analyze(single, shapes=shapes, mesh=_mesh22()),
                     "MXL-C002")


def test_m001_peak_hbm_over_budget():
    net = mx.models.get_mlp()
    issues = net.validate(data=(8, 784), mesh=_mesh22(), hbm_bytes=1024)
    hits = _only(issues, "MXL-M001")
    assert hits and hits[0].severity == "error"
    assert "exceeds the budget" in hits[0].message
    assert "params" in hits[0].message       # breakdown included
    # generous budget: silent
    ok = net.validate(data=(8, 784), mesh=_mesh22(), hbm_bytes=1 << 40)
    assert not _only(ok, "MXL-M001")


def test_m002_big_replicated_param():
    from jax.sharding import PartitionSpec as P
    from mxnet_tpu.parallel.sharding import ShardingRules
    net = mx.models.get_mlp()
    repl = ShardingRules([(r".*", lambda s, m: P(*([None] * len(s))))])
    issues = net.validate(data=(8, 784), mesh=_mesh22(),
                          sharding_rules=repl, hbm_bytes=1_500_000)
    hits = _only(issues, "MXL-M002")
    assert any(i.node == "fc1_weight" for i in hits)
    assert all(i.severity == "warning" for i in hits)
    # sharded by the default policy: nothing to reclaim
    sharded = net.validate(data=(8, 784), mesh=_mesh22(),
                           hbm_bytes=1_500_000)
    assert not _only(sharded, "MXL-M002")


def test_memory_estimate_matches_analytic():
    """Training-mode peak on a graph small enough to price by hand:
    the estimate must land within the documented 2% tolerance (it is
    exact here — no mirroring, no fusion credit taken)."""
    from mxnet_tpu.analysis import AnalysisContext, peak_hbm_report
    from mxnet_tpu.parallel import LogicalMesh
    data = mx.sym.Variable("data")
    fc1 = mx.sym.FullyConnected(data=data, num_hidden=16, name="fc1")
    act = mx.sym.Activation(data=fc1, act_type="relu", name="relu1")
    fc2 = mx.sym.FullyConnected(data=act, num_hidden=2, name="fc2")
    net = mx.sym.SoftmaxOutput(data=fc2, name="softmax")
    ctx = AnalysisContext(net, shapes={"data": (4, 8),
                                      "softmax_label": (4,)},
                          mesh=LogicalMesh(dp=1), grad_req="write")
    rep = peak_hbm_report(ctx)
    params = 4 * (16 * 8 + 16 + 2 * 16 + 2 + 4 * 8 + 4)  # + data + label
    grads = 4 * (16 * 8 + 16 + 2 * 16 + 2)
    acts = 4 * (4 * 16 + 4 * 16 + 4 * 2 + 4 * 2)
    assert rep["mode"] == "training" and rep["complete"]
    assert rep["params_bytes"] == params
    assert rep["grads_bytes"] == grads
    assert rep["activations_bytes"] == acts
    analytic = params + grads + acts
    assert abs(rep["peak_bytes"] - analytic) <= 0.02 * analytic
    # inference mode: no grads, liveness peak <= sum of activations
    infer = AnalysisContext(net, shapes={"data": (4, 8),
                                         "softmax_label": (4,)},
                            mesh=LogicalMesh(dp=1), grad_req="null")
    irep = peak_hbm_report(infer)
    assert irep["mode"] == "inference"
    assert irep["grads_bytes"] == 0
    assert irep["activations_bytes"] <= acts
    assert irep["peak_bytes"] < rep["peak_bytes"]


def test_spmd_rules_respect_lint_ignore():
    from jax.sharding import PartitionSpec as P
    from mxnet_tpu.parallel.sharding import ShardingRules
    rules = ShardingRules([(r"fc1_weight", lambda s, m: P(None, "tp")),
                           (r"fc1_bias", lambda s, m: P(None))])
    shapes = {"data": (8, 16), "softmax_label": (8,)}

    def build(attr):
        data = mx.sym.Variable("data")
        fc = mx.sym.FullyConnected(data=data, num_hidden=8, name="fc1",
                                   attr=attr)
        return mx.sym.SoftmaxOutput(data=fc, name="softmax")

    loud = analyze(build(None), shapes=shapes, mesh=_mesh22(),
                   sharding_rules=rules)
    assert _only(loud, "MXL-C003")
    quiet = analyze(build({"__lint_ignore__": "MXL-C003"}), shapes=shapes,
                    mesh=_mesh22(), sharding_rules=rules)
    assert not _only(quiet, "MXL-C003")


def test_wildcard_select_isolates_spmd_family():
    net, shapes, rules = _mis_sharded()
    issues = analyze(net, shapes=shapes, mesh=_mesh22(),
                     sharding_rules=rules, select={"MXL-P*"})
    assert issues
    assert all(i.rule_id.startswith("MXL-P") for i in issues)
    skipped = analyze(net, shapes=shapes, mesh=_mesh22(),
                      sharding_rules=rules, skip={"MXL-P*"})
    assert not any(i.rule_id.startswith("MXL-P") for i in skipped)


# ----------------------------------------------------------------------
# MXL-K: static Mosaic tile-rule validation of Pallas kernel specs
# ----------------------------------------------------------------------
def test_k_min_tile_table():
    from mxnet_tpu.analysis.tiling import min_tile
    assert min_tile("float32") == (8, 128)
    assert min_tile("bfloat16") == (16, 128)
    assert min_tile("int8") == (32, 128)


def test_k_registered_flash_spec_is_clean():
    """The FIXED flash kernel (lse row stored once per _LSE_ROWS) must
    lint clean — including its head_dim=64 lane dims, legal because the
    blocks cover the whole array dim (Mosaic pads the single tile)."""
    from mxnet_tpu.analysis.tiling import (KERNEL_SPECS,
                                           _ensure_builtin_specs,
                                           kernel_spec_issues)
    _ensure_builtin_specs()
    assert "parallel.ring_attention.flash_forward" in KERNEL_SPECS
    assert "parallel.ring_attention.flash_backward" in KERNEL_SPECS
    assert kernel_spec_issues() == []


@pytest.mark.parametrize("shape", [
    (128, 1024, 64, None),          # gpt2m_train_s1024
    (32, 8192, 192, 128),           # joyai_flash_train_s8192
    (4, 384, 64, None),             # 128-blocks
])
def test_k_flash_backward_spec_follows_the_call(shape):
    """The backward's registered layout is the call's own: blocks from
    ``_flash_blocks`` for the shapes, q/do/dq whole per batch·head, k,
    v, dk, dv by key blocks, lse and delta as (1, block_q) rows — and it
    lints clean at both LM cells' shapes."""
    from mxnet_tpu.analysis.tiling import spec_findings
    from mxnet_tpu.parallel.ring_attention import (
        _flash_blocks, flash_backward_kernel_spec)
    bh, seq, d, d_v = shape
    spec = flash_backward_kernel_spec(bh, seq, seq, d, head_dim_v=d_v)
    block_q, block_k = _flash_blocks(seq, seq)
    assert spec["name"] == "flash_backward"
    assert spec["grid"] == (bh, seq // block_k)
    blocks = {b["name"]: b for b in spec["blocks"]}
    assert list(blocks) == ["q", "k", "v", "do", "lse", "delta",
                            "dq", "dk", "dv"]
    assert blocks["q"]["block"] == blocks["dq"]["block"] == (None, seq, d)
    assert blocks["dv"]["block"] == (None, block_k, d_v or d)
    assert blocks["lse"]["block"] == (None, seq // block_q, 1, block_q)
    assert blocks["delta"]["dtype"] == "float32"
    assert spec_findings(spec) == []


def test_k_flash_lse_regression_fixture():
    """Regression fixture for the round-5 flash bug: the lse stats row
    was written through a 1-D ``(block_q,)`` block, which Mosaic rejects
    (no lane dim to tile).  MXL-K001 must report a spec with that
    layout; the registered (fixed) spec stays clean (test above)."""
    from mxnet_tpu.analysis.tiling import (register_kernel_spec,
                                           unregister_kernel_spec)
    from mxnet_tpu.parallel.ring_attention import flash_kernel_spec
    bad = flash_kernel_spec()
    for blk in bad["blocks"]:
        if blk["name"] == "lse":          # regress to the pre-fix layout
            blk["block"] = (None, 128)    # (block_q,) after squeezing
            blk["array"] = (8, 512)
    register_kernel_spec("test.flash_forward_prefix_bug", bad)
    try:
        issues = analyze(None, select={"MXL-K001"})
        hits = _only(issues, "MXL-K001")
        assert hits and all(i.severity == "error" for i in hits)
        assert any("lse" in i.message for i in hits), hits
    finally:
        unregister_kernel_spec("test.flash_forward_prefix_bug")
    assert not analyze(None, select={"MXL-K*"})   # registry clean again


def test_k_rules_silent_off_tpu_target():
    from mxnet_tpu.analysis.tiling import (register_kernel_spec,
                                           unregister_kernel_spec)
    register_kernel_spec("test.bad_rank1", {
        "name": "bad_rank1", "grid": (4,),
        "blocks": [{"role": "out", "name": "o", "block": (128,),
                    "array": (512,), "dtype": "float32"}]})
    try:
        assert analyze(None, select={"MXL-K*"}, target="cpu") == []
        assert _only(analyze(None, select={"MXL-K*"}), "MXL-K001")
    finally:
        unregister_kernel_spec("test.bad_rank1")


def test_k002_partial_lane_tiling_off_granule():
    from mxnet_tpu.analysis.tiling import block_findings
    rules = {r for r, _s, _m in block_findings((8, 64), (8, 256),
                                               "float32")}
    assert rules == {"MXL-K002"}


def test_k003_grid_padding_is_warning_only():
    from mxnet_tpu.analysis.tiling import block_findings
    out = block_findings((40, 128), (250, 128), "float32")
    assert [(r, s) for r, s, _m in out] == [("MXL-K003", "warning")]


def test_k004_block_exceeds_array():
    from mxnet_tpu.analysis.tiling import block_findings
    out = block_findings((16, 256), (8, 128), "float32")
    assert {r for r, _s, _m in out} == {"MXL-K004"}


def test_k_whole_array_blocks_legal_at_any_size():
    from mxnet_tpu.analysis.tiling import block_findings
    # flash kernel shape: full-array lane dim of 64 (< 128) is fine
    assert block_findings((None, 128, 64), (8, 512, 64),
                          "bfloat16") == []
    # and rtc-style whole-array 2-D blocks of any shape are fine
    assert block_findings(None, (3, 5), "float32") == []


# ----------------------------------------------------------------------
# MXL-R: static roofline / MFU ceiling
# ----------------------------------------------------------------------
def _big_fc(num_hidden=4096, k=4096, batch=1024):
    data = mx.sym.Variable("data")
    fc = mx.sym.FullyConnected(data=data, num_hidden=num_hidden,
                               name="fc")
    return fc, {"data": (batch, k)}


def test_r_static_resnet50_b256_ceiling_matches_measured_table():
    """docs/mfu_gap.md, b256 row: XLA cost analysis says 6.28 TF/step
    and the v5e roofline caps MFU at 0.293.  The chip-free static model
    must reproduce both without lowering anything."""
    from mxnet_tpu.models.resnet import get_symbol
    rep = analysis.static_mfu_ceiling(
        get_symbol(num_classes=1000, num_layers=50),
        {"data": (256, 3, 224, 224)})
    assert rep["complete"], rep
    assert rep["bound"] == "bandwidth"
    assert abs(rep["flops_per_step"] / 1e12 - 6.28) < 0.1, rep
    assert abs(rep["mfu_ceiling"] - 0.293) <= 0.03, rep


def test_r_mxu_padding_waste():
    from mxnet_tpu.analysis.roofline import mxu_padding_waste
    assert mxu_padding_waste([(256, 256, 256)], "bfloat16") == 0.0
    # k and n each pad 64 -> 128: the MXU does 4x the useful work
    assert mxu_padding_waste([(256, 64, 64)], "bfloat16") == 0.75


def test_r002_padding_waste_flagged():
    sym, shapes = _big_fc(num_hidden=192, k=4096, batch=32768)
    issues = analyze(sym, shapes=shapes, select={"MXL-R002"})
    hits = _only(issues, "MXL-R002")
    assert hits and "pads" in hits[0].message


def test_r003_fp32_dot_only_fires_at_fp32():
    sym, shapes = _big_fc()
    at32 = analyze(sym, shapes=shapes, select={"MXL-R003"},
                   compute_dtype="float32")
    assert _only(at32, "MXL-R003")
    at16 = analyze(sym, shapes=shapes, select={"MXL-R003"})  # bf16 dflt
    assert not at16


def test_r004_long_bf16_reduction():
    sym, shapes = _big_fc(num_hidden=1024, k=8192, batch=2048)
    issues = analyze(sym, shapes=shapes, select={"MXL-R004"})
    hits = _only(issues, "MXL-R004")
    assert hits and "accumulates over 8192" in hits[0].message
    # the same contraction at f32 accumulation is safe
    assert not analyze(sym, shapes=shapes, select={"MXL-R004"},
                       compute_dtype="float32")


def test_r005_graph_summary_and_significance_floor():
    sym, shapes = _big_fc()
    issues = analyze(sym, shapes=shapes, select={"MXL-R005"})
    hits = _only(issues, "MXL-R005")
    assert hits and hits[0].severity == "info"
    assert "MFU ceiling" in hits[0].message
    # a toy graph stays below the 1e10-flops floor: no findings at all
    tiny, tiny_shapes = _big_fc(num_hidden=8, k=16, batch=4)
    assert analyze(tiny, shapes=tiny_shapes, select={"MXL-R*"}) == []


def test_r_rules_silent_off_tpu_target():
    sym, shapes = _big_fc()
    assert analyze(sym, shapes=shapes, select={"MXL-R*"},
                   target="cpu") == []
