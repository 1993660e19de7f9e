#!/usr/bin/env python
"""Mosaic-compile every shipped Pallas kernel and the long-context stack
with no chip.

Same compile-only topology path as tools/aot_audit.py (the chip's own
compiler, not the chip).  Interpret mode has no tile rules and no VMEM,
so tier-1 cannot see what Mosaic refuses; this check can, and costs no
chip time:

1. every Pallas kernel the tree ships, at the widths chip_smoke.py runs
   them (its phases 2-3): the flash-attention forward and backward
   kernels (s1024 d64; s8192 at latent attention's 192/128, at grouped
   heads of 128 and of 256), the gated delta rule's forward and backward
   kernels (s8192, 16 key heads on 32 value heads of 128; and, with four
   devices, per device on a dp2 × tp2 mesh), Kimi delta attention's
   (s8192, 32 heads of 128, a decay a key channel; on the mesh too), the
   weight-only quantized matmul at
   GPT-2-small FFN shapes and at the LM head of 50,257, and the fused
   optimizer sweep at one bucket the size of ResNet-50's parameters;
2. the transformer fused train step (models/transformer.py), on one
   chip and data-parallel over four — its compiled text must carry the
   Mosaic custom call: the flash kernel is chosen because the step is
   lowered for a TPU, nothing is forced, and on a mesh it runs per
   device under shard_map (GSPMD cannot partition a Mosaic kernel);
3. the ring-attention dp×sp fused step — the compiled HLO must carry
   the ppermute ring (collective-permute ops), proving the sequence-
   parallel schedule survives XLA:TPU lowering.

With the kernels (so under ``--kernels-only`` too) goes one small step that
holds them all: a hybrid of the delta rule and attention over routed
experts with ``mirror_blocks`` (four layers, heads of 128, 512 tokens), on
one device and, with four, on dp2 × tp2 — each layer's kernel must read
under its own node and pass (``hybrid_kernel_scopes``): every forward
kernel under ``forward`` alone, since a mirrored block keeps what a kernel
hands its backward (``executor.KEPT``) and its recomputation calls none.

Prints one JSON line; exit 2 = topology unavailable (callers SKIP), 1 =
a kernel was refused or a step lost its Mosaic call / ring.
Run serially: the local libtpu serves ONE process at a time.

Usage: python tools/aot_longcontext_check.py [--full] [--kernels-only]
  (--full compiles the fused steps at the bench-sized L8 d512 s1024
   config; default is a small config that compiles in ~2-4 min.  The
   kernels always compile at full width.)
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

MOSAIC = "tpu_custom_call"


def kernel_cases():
    """(name, fn, abstract args) for every shipped Pallas kernel at
    chip_smoke.py's phase 2-3 widths.  ``fn`` takes the kernel-or-
    reference decision itself (no ``interpret``), so compiling it for a
    TPU device is also the proof that placement selects the kernel."""
    import jax
    import jax.numpy as jnp
    import chip_smoke       # the widths live there: one list, no drift
    from mxnet_tpu import optimizer as opt_mod
    from mxnet_tpu.kernels import fused_opt, quantize
    from mxnet_tpu.parallel.ring_attention import flash_attention
    widths = chip_smoke.FULL["kernels"]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    def flash_loss(q, k, v):
        out = flash_attention(q, k, v, causal=True)
        return jnp.sum(out.astype(jnp.float32))

    cases = []
    for dt in (jnp.float32, jnp.bfloat16):
        qkv = sds(widths["flash"], dt)         # train/lm: b8 h8 s1024 d64
        cases.append(("flash_fwd_bwd[%s]" % jnp.dtype(dt).name,
                      jax.grad(flash_loss, argnums=(0, 1, 2)),
                      (qkv, qkv, qkv)))
    # latent attention: 8,192 keys, q/k 192 wide, v 128 — the most VMEM
    # either kernel is asked for (q, do, dq whole per batch·head)
    b, h, s, d_qk, d_v = widths["flash_latent"]
    qk, v = sds((b, h, s, d_qk), jnp.bfloat16), sds((b, h, s, d_v),
                                                    jnp.bfloat16)
    cases.append(("flash_fwd_bwd[bfloat16,latent]",
                  jax.grad(flash_loss, argnums=(0, 1, 2)), (qk, qk, v)))
    # grouped queries: 8 heads of 128 on 2 key/value heads, 8,192 keys —
    # the backward holds a group's q, do and dq whole, four heads' worth
    b, h, h_kv, s, d = widths["flash_grouped"]
    q, kv = sds((b, h, s, d), jnp.bfloat16), sds((b, h_kv, s, d),
                                                 jnp.bfloat16)
    cases.append(("flash_fwd_bwd[bfloat16,grouped]",
                  jax.grad(flash_loss, argnums=(0, 1, 2)), (q, kv, kv)))
    # 16 heads of 256 on 2: k and v whole are past the VMEM a kernel gets
    # unasked (the forward asks), a group's q, do, dq past all of it (the
    # backward splits the group over programs)
    b, h, h_kv, s, d = widths["flash_gqa256"]
    q, kv = sds((b, h, s, d), jnp.bfloat16), sds((b, h_kv, s, d),
                                                 jnp.bfloat16)
    cases.append(("flash_fwd_bwd[bfloat16,gqa256]",
                  jax.grad(flash_loss, argnums=(0, 1, 2)), (q, kv, kv)))
    # 64 heads of 128 on 8 under a sliding window of 512: the windowed
    # kernels, the backward's group of eight over two programs
    b, h, h_kv, s, d, window = widths["flash_window"]
    q, kv = sds((b, h, s, d), jnp.bfloat16), sds((b, h_kv, s, d),
                                                 jnp.bfloat16)

    def window_loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, window=window)
        return jnp.sum(out.astype(jnp.float32))

    cases.append(("flash_fwd_bwd[bfloat16,window%d]" % window,
                  jax.grad(window_loss, argnums=(0, 1, 2)), (q, kv, kv)))
    # the gated delta rule at the timed shape: 8,192 tokens, 16 key heads on
    # 32 value heads of 128 — the forward that writes the chunk states and
    # the backward, chosen by ``dispatch`` because the call is lowered for a
    # TPU
    from mxnet_tpu.ops.linear_attention import gated_delta_rule
    b, _s, h_k, h_v, d_k, d_v, _short = widths["delta_rule"]

    def rule_loss(*args):
        return jnp.sum(gated_delta_rule(*args).astype(jnp.float32))

    qk, v = sds((b, 8192, h_k, d_k), jnp.bfloat16), sds((b, 8192, h_v, d_v),
                                                        jnp.bfloat16)
    scalar = sds((b, 8192, h_v), jnp.float32)
    cases.append(("gated_delta_fwd_bwd[bfloat16,16on32]",
                  jax.grad(rule_loss, argnums=(0, 1, 2, 3, 4)),
                  (qk, qk, v, scalar, scalar)))
    # Kimi delta attention at Ling's timed shape: 8,192 tokens, 32 heads of
    # 128, a decay a key channel
    from mxnet_tpu.ops.linear_attention import kda_rule
    b, _s, h, d, _short = widths["kda_rule"]

    def kda_loss(*args):
        return jnp.sum(kda_rule(*args).astype(jnp.float32))

    qkv, g = sds((b, 8192, h, d), jnp.bfloat16), sds((b, 8192, h, d),
                                                     jnp.float32)
    cases.append(("kda_fwd_bwd[bfloat16,32x128]",
                  jax.grad(kda_loss, argnums=(0, 1, 2, 3, 4)),
                  (qkv, qkv, qkv, g, sds((b, 8192, h), jnp.float32))))
    # serve/lm (GPT-2 small): decode rows 8, prefill rows 256
    for m, k, n in widths["qmm"]:
        for dt in (jnp.float32, jnp.bfloat16):
            cases.append((
                "quantized_matmul[%s,%dx%d->%d]"
                % (jnp.dtype(dt).name, m, k, n),
                quantize.quantized_matmul,
                (sds((m, k), dt), sds((n, k), jnp.int8),
                 sds((n,), jnp.float32))))
    # one bucket the size of ResNet-50's parameters
    numel = 25557032
    opt = opt_mod.create("sgd", learning_rate=0.1, momentum=0.9)

    def sweep(w, g, s):
        nw, ns = fused_opt.fused_apply(
            opt, {"w": w}, {"w": g}, {"w": s}, 0.1, 1e-4, 1,
            nbytes=1 << 40, mode="kernel")
        return nw["w"], ns["w"]

    vec = sds((numel,), jnp.float32)
    cases.append(("fused_opt_sweep[float32,%d]" % numel, sweep,
                  (vec, vec, vec)))
    return cases


def compile_kernels(device):
    """Compile each kernel case for ``device``; returns
    ``{name: {"ok", "mosaic_calls" | "error"}}``."""
    import jax
    from jax.sharding import SingleDeviceSharding

    sharding = SingleDeviceSharding(device)
    report = {}
    for name, fn, args in kernel_cases():
        args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)
                for a in args]
        try:
            text = jax.jit(fn).lower(*args).compile().as_text()
        except Exception as exc:  # noqa: BLE001 — a refusal IS the finding
            report[name] = {"ok": False,
                            "error": str(exc).strip().splitlines()[0][:300]}
            continue
        calls = text.count(MOSAIC)
        report[name] = {"ok": calls > 0, "mosaic_calls": calls}
    return report


def compile_delta_rule_on_mesh(devs, kda=False):
    """Mosaic calls in the gated delta rule's gradient — or, with ``kda``,
    Kimi delta attention's — compiled for a dp2 × tp2 mesh of ``devs``,
    traced under ``attention_scope`` as ``ShardedTrainer`` traces its step:
    GSPMD cannot partition a Mosaic kernel, so the rule must carry both
    kernels per device under shard_map, the batch split over dp and the key
    heads over tp."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    import chip_smoke
    from mxnet_tpu.ops.linear_attention import gated_delta_rule, kda_rule
    from mxnet_tpu.parallel.ring_attention import attention_scope
    mesh = Mesh(np.array(devs[:4]).reshape(2, 2), ("dp", "tp"))
    widths = chip_smoke.FULL["kernels"]
    if kda:
        _b, _s, h_v, d_v, _short = widths["kda_rule"]
        h_k, d_k = h_v, d_v
    else:
        _b, _s, h_k, h_v, d_k, d_v, _short = widths["delta_rule"]
    rows = NamedSharding(mesh, P("dp"))

    def sds(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct((2, 8192) + shape, dtype, sharding=rows)

    def rule_loss(*args):
        rule = kda_rule if kda else gated_delta_rule
        return jnp.sum(rule(*args).astype(jnp.float32))

    scalar = sds(h_v, dtype=jnp.float32)
    g = sds(h_k, d_k, dtype=jnp.float32) if kda else scalar
    with attention_scope(mesh):
        text = jax.jit(jax.grad(rule_loss, argnums=(0, 1, 2, 3, 4))).lower(
            sds(h_k, d_k), sds(h_k, d_k), sds(h_v, d_v), g,
            scalar).compile().as_text()
    return text.count(MOSAIC)


def compile_step(sym, opt, mesh, batch, seq_len, seq_axis=None):
    """``sym``'s fused train step compiled for ``mesh`` through
    ``ShardedTrainer._lower`` (which engages the attention scope), from
    abstract state: nothing is placed on a device."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.parallel.trainer import ShardedTrainer
    tr = ShardedTrainer(sym, opt, mesh, compute_dtype="bfloat16",
                        seq_axis=seq_axis)
    shp = (batch, seq_len)
    params, o, a = tr.abstract_state(
        {"data": shp}, label_shapes={"softmax_label": shp})
    repl = tr._replicated()
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    b = {"data": jax.ShapeDtypeStruct(shp, jnp.int32,
                                      sharding=tr.batch_sharding(shp)),
         "softmax_label": jax.ShapeDtypeStruct(
             shp, jnp.float32, sharding=tr.batch_sharding(shp))}
    tr._abstract_args = (
        params, o, a, b,
        jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=repl),
        jax.ShapeDtypeStruct((), jnp.float32, sharding=repl),
        jax.ShapeDtypeStruct((), jnp.float32, sharding=repl),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=repl))
    return tr._lower().compile()


def kernel_scopes(text, sym):
    """``{"<kernel> <pass>": nodes}`` of a compiled step's text: under how
    many graph nodes each of our kernels reads in each pass.  The kernels'
    calls are jitted, and XLA joins a call site's name with the callee's
    where it inlines (observability/device_scopes.py)."""
    from mxnet_tpu.observability import device_scopes
    nodes = device_scopes.graph_nodes(sym)
    found = {}
    for name, op_name in device_scopes.parse(text).items():
        if name.startswith(("flash_", "gated_delta_", "kda_")):
            phase, node, _sub = device_scopes.classify(op_name, nodes)
            found.setdefault("%s %s" % (name.split(".")[0], phase),
                             set()).add(node)
    return {k: len(v) for k, v in sorted(found.items())}


#: what the small mirrored hybrid step must read on every mesh: two
#: delta-rule layers and two attention layers, each kernel once a layer,
#: the forward ones under ``forward`` alone
HYBRID_SCOPES = {"flash_backward backward": 2, "flash_forward forward": 2,
                 "gated_delta_backward backward": 2,
                 "gated_delta_forward forward": 2}


def hybrid_kernel_scopes(devs):
    """``{mesh: kernel_scopes}`` of the small mirrored hybrid step
    (``HYBRID_SCOPES`` is what each must read)."""
    import numpy as np
    from jax.sharding import Mesh
    from mxnet_tpu import optimizer as opt_mod
    from mxnet_tpu.models import transformer_hybrid_moe
    sym = transformer_hybrid_moe.get_symbol(
        vocab_size=64, num_layers=4, dim=256, seq_len=512,
        full_attention_interval=2, num_heads=4, num_kv_heads=2,
        head_dim=128, linear_num_key_heads=2, linear_num_value_heads=4,
        linear_key_head_dim=128, linear_value_head_dim=128, num_experts=8,
        n_local_experts=8, num_experts_per_tok=2, mirror_blocks=True)
    opt = opt_mod.create("sgd", learning_rate=0.1, momentum=0.9)
    meshes = {"one_device": Mesh(np.array(devs[:1]), ("dp",))}
    if len(devs) >= 4:
        meshes["dp2tp2"] = Mesh(np.array(devs[:4]).reshape(2, 2),
                                ("dp", "tp"))
    return {name: kernel_scopes(
        compile_step(sym, opt, mesh, 4, 512).as_text(), sym)
        for name, mesh in meshes.items()}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--topology", default="v5e:2x2")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--kernels-only", action="store_true")
    args = ap.parse_args()

    import numpy as np
    import jax
    import jax.numpy as jnp
    jax.config.update("jax_platforms", "cpu")   # never touch a live chip
    from jax.sharding import Mesh
    from aot_audit import topology_devices

    devs = topology_devices(args.topology)
    if devs is None:
        print(json.dumps({"error": "topology unavailable",
                          "topology": args.topology}))
        return 2
    out = {"topology": args.topology,
           "device_kind": str(getattr(devs[0], "device_kind", ""))}

    # 1. every shipped kernel at full width
    out["kernels"] = compile_kernels(devs[0])
    ok = all(r["ok"] for r in out["kernels"].values())
    if len(devs) >= 4:
        out["delta_rule_dp2tp2_mosaic_calls"] = compile_delta_rule_on_mesh(
            devs)
        out["kda_rule_dp2tp2_mosaic_calls"] = compile_delta_rule_on_mesh(
            devs, kda=True)
        ok = ok and out["delta_rule_dp2tp2_mosaic_calls"] == 2 \
            and out["kda_rule_dp2tp2_mosaic_calls"] == 2
    out["hybrid_kernel_scopes"] = hybrid_kernel_scopes(devs)
    ok = ok and all(found == HYBRID_SCOPES
                    for found in out["hybrid_kernel_scopes"].values())
    if args.kernels_only:
        print(json.dumps(out))
        return 0 if ok else 1

    # 2 + 3. transformer fused step, single-chip and dp x sp ring
    from mxnet_tpu.models import transformer
    from mxnet_tpu import optimizer as opt_mod

    if args.full:
        cfg = dict(vocab_size=8192, num_layers=8, num_heads=8, dim=512,
                   seq_len=1024)
        batch = 8
    else:
        cfg = dict(vocab_size=256, num_layers=2, num_heads=4, dim=64,
                   seq_len=256)
        batch = 4
    sym = transformer.get_symbol(**cfg)
    opt = opt_mod.create("sgd", learning_rate=0.1, momentum=0.9,
                         rescale_grad=1.0 / (batch * cfg["seq_len"]))

    def compile_for(mesh, seq_axis):
        return compile_step(sym, opt, mesh, batch, cfg["seq_len"], seq_axis)

    mesh1 = Mesh(np.array(devs[:1]), ("dp",))
    compiled = compile_for(mesh1, seq_axis=None)
    ca = compiled.cost_analysis() or {}
    out["transformer_tf_per_step"] = round(
        float(ca.get("flops") or 0) / 1e12, 3)
    out["transformer_temp_mb"] = round(
        compiled.memory_analysis().temp_size_in_bytes / 1e6)
    # the flash path must appear in the fused step itself: one Mosaic
    # call per layer, selected by the TPU lowering alone
    out["transformer_mosaic_calls"] = compiled.as_text().count(MOSAIC)
    ok = ok and out["transformer_mosaic_calls"] >= cfg["num_layers"]

    # ... and each call under its own layer's scope and pass
    want_scopes = {"flash_backward backward": cfg["num_layers"],
                   "flash_forward forward": cfg["num_layers"]}
    out["transformer_kernel_scopes"] = kernel_scopes(compiled.as_text(), sym)
    ok = ok and out["transformer_kernel_scopes"] == want_scopes

    if len(devs) >= 4:
        # data parallel over four chips: GSPMD cannot partition a Mosaic
        # kernel, so the step must carry it per device (under shard_map)
        # next to the gradient all-reduce — what refused to lower on the
        # four-chip host in PR 21
        text = compile_for(Mesh(np.array(devs[:4]), ("dp",)),
                           seq_axis=None).as_text()
        out["dp4_mosaic_calls"] = text.count(MOSAIC)
        out["dp4_all_reduces"] = text.count("all-reduce")
        out["dp4_kernel_scopes"] = kernel_scopes(text, sym)
        ok = ok and out["dp4_mosaic_calls"] >= cfg["num_layers"] \
            and out["dp4_all_reduces"] > 0 \
            and out["dp4_kernel_scopes"] == want_scopes
        mesh4 = Mesh(np.array(devs[:4]).reshape(2, 2), ("dp", "sp"))
        c4 = compile_for(mesh4, seq_axis=1)
        out["ring_collective_permutes"] = c4.as_text().count(
            "collective-permute")
        ok = ok and out["ring_collective_permutes"] > 0
    else:
        out["ring_note"] = ("topology has %d device(s); dp2xsp2 ring "
                            "needs 4 — skipped" % len(devs))

    print(json.dumps(out))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
