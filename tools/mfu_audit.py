#!/usr/bin/env python
"""Structural MFU audit of the fused ResNet training step.

Answers, from the OPTIMIZED compiled program (no chip needed — the
lowering/fusion structure is identical; only physical layout assignment
and measured time need hardware):

- are all convolutions bf16 (MXU rate) end-to-end?
- how many logical transposes survived fusion?
- is buffer donation aliasing params in place?
- what arithmetic intensity does XLA's cost analysis predict, and what
  MFU ceiling does the HBM roofline imply per batch size?

Usage: python tools/mfu_audit.py [--batch 64,128,256] [--layers 50]
Prints one human section per batch + a final JSON line for tooling.
"""
import argparse
import json
import os
import re
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def audit(batch, layers, dtype):
    import numpy as np
    import jax
    from mxnet_tpu.models import resnet
    from mxnet_tpu import optimizer as opt_mod
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.trainer import ShardedTrainer

    devices = jax.devices()
    mesh = make_mesh(devices, dp=len(devices))
    sym = resnet.get_symbol(num_classes=1000, num_layers=layers)
    optimizer = opt_mod.create("sgd", learning_rate=0.1, momentum=0.9,
                               wd=1e-4, rescale_grad=1.0 / batch)
    trainer = ShardedTrainer(sym, optimizer, mesh, compute_dtype=dtype)
    params, opt_state, aux = trainer.init_params(
        {"data": (batch, 3, 224, 224)},
        label_shapes={"softmax_label": (batch,)})
    import jax.numpy as jnp
    from mxnet_tpu.observability.device_scopes import abstractify
    batch_abstract = {
        "data": jax.ShapeDtypeStruct((batch, 3, 224, 224), jnp.float32),
        "softmax_label": jax.ShapeDtypeStruct((batch,), jnp.float32),
    }
    # lower WITHOUT executing (a real batch-256 fwd+bwd on a CPU-only
    # box takes minutes and tens of GB): hand _lower() the abstract
    # step-arg pytree the first executed step would have recorded
    step_args = (params, opt_state, aux, batch_abstract,
                 jax.random.PRNGKey(0), jnp.float32(0.1),
                 jnp.float32(1e-4), jnp.int32(1))
    trainer._abstract_args = jax.tree_util.tree_map(
        lambda a: a if isinstance(a, jax.ShapeDtypeStruct)
        else abstractify(a), step_args)
    lowered = trainer._lower()
    # STRUCTURAL audit on the backend-neutral StableHLO: what the program
    # asks for.  (The compiled text below is per-backend: XLA:CPU upcasts
    # bf16 convs to f32 and packs its own layout transposes — on-chip the
    # same script shows the Mosaic lowering.)
    shlo = lowered.as_text()
    convs = re.findall(r"stablehlo\.convolution.*?->\s*tensor<[^>]*x(\w+)>",
                       shlo)
    conv_dtypes = {}
    for ty in convs:
        conv_dtypes[ty] = conv_dtypes.get(ty, 0) + 1
    transposes = len(re.findall(r"stablehlo\.transpose", shlo))
    dots = len(re.findall(r"stablehlo\.dot", shlo))

    compiled = lowered.compile()
    hlo = compiled.as_text()
    fusions = len(re.findall(r"\bfusion\(", hlo))
    backend_transposes = len(re.findall(r"\btranspose\(", hlo))

    # reuse the trainer's own introspection (it carries the list-unwrap
    # and None handling bench.py learned the hard way)
    cost = trainer.compiled_step_cost_analysis() or {}
    flops = float(cost.get("flops") or 0.0)
    byts = float(cost.get("bytes accessed") or 0.0)
    intensity = flops / byts if byts else None

    mem = compiled.memory_analysis()
    donated = getattr(mem, "alias_size_in_bytes", 0) or 0

    platform = devices[0].platform
    out = {
        "batch": batch,
        "conv_count": len(convs),
        "conv_dtypes": conv_dtypes,          # StableHLO (backend-neutral)
        "logical_transposes": transposes,    # StableHLO
        "dots": dots,
        "backend": platform,
        "backend_fusions": fusions,
        "backend_transposes": backend_transposes,
        "model_tflops_per_step": round(flops / 1e12, 3),
        "bytes_gb_per_step": round(byts / 1e9, 3),
        "arith_intensity_flops_per_byte": (round(intensity, 1)
                                           if intensity else None),
        "donation_alias_bytes": int(donated) if donated else 0,
    }
    # Roofline ceiling on a v5e (197 bf16 TFLOP/s, 819 GB/s): the step
    # can't exceed min(1, intensity / (peak_flops/peak_bw)) of peak.
    # Only meaningful when cost analysis comes from the TPU backend —
    # XLA:CPU's fusion/layout choices inflate bytes-accessed ~50x.
    if intensity and platform == "tpu":
        ridge = 197e12 / 819e9   # ≈ 240 flops/byte
        out["v5e_roofline_mfu_ceiling"] = round(min(1.0, intensity / ridge),
                                                3)
    # Chip-free cross-check: the analyzer's MXL-R roofline prices the
    # same graph without lowering anything — agreement with the compiled
    # cost analysis above validates the static model (docs/mfu_gap.md).
    # Shared summary path with bench.py / the autotuner; it never
    # raises, so the audit can't die on analyzer bugs.
    from mxnet_tpu.analysis import static_ceiling_summary
    out.update(static_ceiling_summary(
        sym, {"data": (batch, 3, 224, 224)}, compute_dtype=dtype))
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", default="64,128,256")
    ap.add_argument("--layers", type=int, default=50)
    ap.add_argument("--dtype", default="bfloat16")
    args = ap.parse_args()
    results = []
    for b in (int(x) for x in args.batch.split(",")):
        r = audit(b, args.layers, args.dtype)
        results.append(r)
        print("batch %d: %d convs %s | logical transposes=%d | "
              "[%s backend: fusions=%d transposes=%d] | %.2f TF/step, "
              "%.2f GB/step, intensity=%s fl/B, v5e ceiling=%s, "
              "donated=%s"
              % (b, r["conv_count"], r["conv_dtypes"],
                 r["logical_transposes"], r["backend"],
                 r["backend_fusions"], r["backend_transposes"],
                 r["model_tflops_per_step"], r["bytes_gb_per_step"],
                 r["arith_intensity_flops_per_byte"],
                 r.get("v5e_roofline_mfu_ceiling"),
                 bool(r["donation_alias_bytes"])))
        if "static_mfu_ceiling" in r:
            print("batch %d: static MXL-R roofline: %.2f TF/step, "
                  "ceiling=%s (%s-bound) — chip-free cross-check of the "
                  "compiled numbers above"
                  % (b, r["static_tflops_per_step"],
                     r["static_mfu_ceiling"], r["static_bound"]))
    print(json.dumps({"audit": results}))


if __name__ == "__main__":
    main()
