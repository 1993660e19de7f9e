"""tools/aot_audit.py + tools/aot_longcontext_check.py: AOT compiles of
the fused step through the real XLA:TPU pipeline via jax's compile-only
topology path (no chip).

Every libtpu-touching check runs in a SUBPROCESS: the local libtpu
serves one process at a time and holds its lock for the process
lifetime — an in-process topology would poison later tests that expect
a free plugin (test_tools.py's PJRT C runner pins an exact
Client_Create failure).  The end-to-end compiles are slow (~minutes)
and gated behind MXTPU_SLOW=1 (nightly tier)."""
import json
import os
import subprocess
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_ROOT, "tools"))

import aot_audit  # noqa: E402  (parser helpers only — no jax import)


def _run(args, timeout):
    env = dict(os.environ)
    env["PYTHONPATH"] = _ROOT
    return subprocess.run([sys.executable] + args, env=env, cwd=_ROOT,
                          capture_output=True, text=True, timeout=timeout)


@pytest.mark.slow
def test_topology_mesh_compile_only_devices():
    if os.environ.get("MXTPU_AOT_TOPOLOGY", "1") in ("0", "off", "no"):
        pytest.skip("topology probe disabled (MXTPU_AOT_TOPOLOGY=0)")
    code = ("import jax, sys\n"
            "jax.config.update('jax_platforms', 'cpu')\n"
            "sys.path.insert(0, %r)\n"
            "import aot_audit\n"
            "mesh = aot_audit._topology_mesh('v5e:2x2')\n"
            "assert mesh is None or ('TPU' in getattr(\n"
            "    mesh.devices.flat[0], 'device_kind', ''))\n"
            "print('NONE' if mesh is None else 'OK')\n"
            % os.path.join(_ROOT, "tools"))
    # a half-installed libtpu can HANG inside get_topology_desc rather
    # than fail — bound the probe and treat a timeout like "unavailable"
    # (set MXTPU_AOT_TOPOLOGY=0 to skip the spawn entirely)
    try:
        p = _run(["-c", code], timeout=60)
    except subprocess.TimeoutExpired:
        pytest.skip("local TPU PJRT topology probe hung (no usable "
                    "libtpu); set MXTPU_AOT_TOPOLOGY=0 to skip the probe")
    assert p.returncode == 0, p.stderr[-1500:]
    if "NONE" in p.stdout:
        pytest.skip("local TPU PJRT topology unavailable (no libtpu)")
    assert "OK" in p.stdout


def test_entry_breakdown_parser():
    hlo = """
HloModule m

%fused_computation {
  %p = bf16[8,8]{1,0} parameter(0)
  ROOT %t = bf16[8,8]{1,0} transpose(%p), dimensions={1,0}
}

ENTRY %main (p0: bf16[8,8]) -> bf16[8,8] {
  %p0 = bf16[8,8]{1,0:T(8,128)(2,1)} parameter(0)
  %f1 = bf16[8,8]{1,0:T(8,128)(2,1)} fusion(%p0), kind=kLoop, calls=%fused_computation
  %ft = (bf16[8,8]{1,0}, f32[4,4]{1,0}) fusion(%f1), kind=kOutput, calls=%fused_computation
  %g0 = bf16[8,8]{1,0} get-tuple-element(%ft), index=0
  %c1 = f32[4,4]{1,0} copy(%g0)
  ROOT %f2 = bf16[8,8]{1,0} fusion(%g0), kind=kLoop, calls=%fused_computation
}
"""
    ranked = aot_audit.entry_breakdown(hlo)
    by_op = {r["op"]: r for r in ranked}
    # three fusions; the tuple-typed one contributes both members
    assert by_op["fusion"]["count"] == 3
    assert ranked[0]["op"] == "fusion"
    assert by_op["copy"]["count"] == 1
    # excluded: fusion-internal ops, zero-copy views, input parameters
    assert "transpose" not in by_op
    assert "get-tuple-element" not in by_op
    assert "parameter" not in by_op


@pytest.mark.skipif(not os.environ.get("MXTPU_SLOW"),
                    reason="TPU AOT compile takes minutes (MXTPU_SLOW=1)")
def test_aot_audit_tiny_end_to_end():
    p = _run([os.path.join(_ROOT, "tools", "aot_audit.py"),
              "--batch", "2", "--layers", "18"], timeout=1800)
    if p.returncode == 2:
        pytest.skip("local TPU PJRT topology unavailable")
    assert p.returncode == 0, p.stderr[-1500:]
    line = [l for l in p.stdout.splitlines() if l.startswith("{")][-1]
    out = json.loads(line)["audit"][0]
    assert out["stablehlo_conv_dtypes"].get("bf16", 0) > 0
    assert set(out["stablehlo_conv_dtypes"]) == {"bf16"}
    assert out["temp_bytes"] > 0 and out["model_tflops_per_step"] > 0


@pytest.mark.slow
def test_every_shipped_kernel_compiles_under_mosaic():
    """The chip-free check that found what tier-1 cannot: interpret
    mode has no tile rules and no VMEM, the chip's own compiler does.
    Every Pallas kernel the tree ships, at chip_smoke.py's phase 2-3
    widths — including the LM head of 50,257 and the ResNet-50-sized
    optimizer bucket the whole-dimension block fallback used to refuse
    — compiles, with the kernel chosen by the TPU lowering alone."""
    p = _run([os.path.join(_ROOT, "tools", "aot_longcontext_check.py"),
              "--kernels-only"], timeout=900)
    if p.returncode == 2:
        pytest.skip("local TPU PJRT topology unavailable")
    line = [l for l in p.stdout.splitlines() if l.startswith("{")][-1]
    report = json.loads(line)
    kernels = report["kernels"]
    refused = {k: v for k, v in kernels.items() if not v["ok"]}
    assert not refused and p.returncode == 0, (refused, p.stderr[-1500:])
    # on a mesh of four the gated delta rule's kernels run per device
    # (shard_map): the forward and the backward, as on one
    assert report.get("delta_rule_dp2tp2_mosaic_calls", 2) == 2
    # a mirrored hybrid step: each kernel once a layer, under its own pass —
    # no forward kernel under ``recompute`` (executor.KEPT)
    for mesh, found in report["hybrid_kernel_scopes"].items():
        assert found == {"flash_backward backward": 2,
                         "flash_forward forward": 2,
                         "gated_delta_backward backward": 2,
                         "gated_delta_forward forward": 2}, (mesh, found)
    # the forward kernel and the backward kernel, one Mosaic call each
    for want in ("flash_fwd_bwd[float32]", "flash_fwd_bwd[bfloat16]",
                 "flash_fwd_bwd[bfloat16,latent]"):
        assert kernels[want]["mosaic_calls"] == 2, want
    for want in ("quantized_matmul[float32,8x768->50257]",
                 "quantized_matmul[bfloat16,256x768->3072]",
                 "fused_opt_sweep[float32,25557032]"):
        assert kernels[want]["mosaic_calls"] >= 1, want


@pytest.mark.skipif(not os.environ.get("MXTPU_SLOW"),
                    reason="TPU AOT compile takes minutes (MXTPU_SLOW=1)")
def test_longcontext_paths_compile_under_mosaic():
    """Every kernel, the transformer fused step, and the ring-attention
    dp2xsp2 step through the REAL Mosaic pipeline; the ppermute ring
    must survive into the compiled HLO."""
    p = _run([os.path.join(_ROOT, "tools", "aot_longcontext_check.py")],
             timeout=2400)
    if p.returncode == 2:
        pytest.skip("local TPU PJRT topology unavailable")
    assert p.returncode == 0, p.stderr[-1500:]
    line = [l for l in p.stdout.splitlines() if l.startswith("{")][-1]
    out = json.loads(line)
    assert all(v["ok"] for v in out["kernels"].values())
    assert out["transformer_tf_per_step"] > 0
    # lowered for a TPU, the fused step's MHA goes through the pallas
    # kernel (a Mosaic custom call per layer), not attention_reference —
    # nothing forces it
    assert out["transformer_mosaic_calls"] >= 2
    assert out["dp4_mosaic_calls"] >= 2 and out["dp4_all_reduces"] > 0
    assert out["ring_collective_permutes"] > 0
