#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, every visible chip, the two main paths through the entry
points a user calls, at the full width of models the repo supports
(seeded random weights), each checked by the repo's own means:

1. train/resnet50 — ``models.resnet`` at 50 layers, 1,000 classes, 224²,
   bf16 compute, 64 images per chip: once through ``mx.mod.Module(net,
   context=[mx.tpu(i) ...]).fit`` (the README's entry point, executor.py's
   fused step) and once through ``ShardedTrainer.step`` (bench.py's path).
2. train/lm — ``models.transformer`` at bench.py's LM default (8 layers,
   512 wide, 8 heads, sequence 1,024, vocabulary 8,192, 8 sequences per
   chip, bf16) through ``ShardedTrainer``; the compiled step must carry
   the Mosaic flash-attention call.
3. serve/lm — ``ModelServer.add_generative_model`` with the decoder at
   GPT-2-small widths (12 layers, 768 wide, 12 heads, vocabulary 50,257,
   1,024 positions), two prompt and two decode buckets, a handful of
   mixed-length requests through ``srv.generate``; logits through the
   paged cache against the uncached full forward.  One device.
4. kernels — every Pallas kernel the tree ships, ``interpret=False``, at
   the widths of phases 2-3, against its jnp reference.

Each phase must pass; none is wrapped in a ``try`` that lets the run go
on.  Exit 0 and a last stdout line ``{"ok": true, "device": {...}}`` mean
all four passed on a TPU.  With no TPU the script exits 2 within seconds
and prints no result.  Needs no network; starts no other process.

``--rehearse`` is the CPU rehearsal of the same code at tiny sizes with
the kernels interpreted (run it under ``JAX_PLATFORMS=cpu`` before
spending chip time).  It is never chosen by the program, and what it
prints is marked ``"rehearsal": true`` — not a chip result.
"""
import argparse
import functools
import json
import os
import sys
import time

import numpy as np

MOSAIC = "tpu_custom_call"

FULL = {
    "resnet": dict(layers=50, classes=1000, image=224, per_chip=64, steps=6,
                   lr=0.02),
    "lm": dict(layers=8, dim=512, heads=8, seq=1024, vocab=8192,
               per_chip=8, steps=6, lr=0.1),
    "serve": dict(layers=12, dim=768, heads=12, vocab=50257,
                  positions=1024, prompt_buckets=(64, 256),
                  decode_buckets=(1, 4), max_new=8, kv_blocks=128,
                  kv_block_size=32,
                  prompt_lengths=(5, 40, 64, 100, 200, 256, 17, 130)),
    "kernels": dict(flash=(8, 8, 1024, 64), flash_latent=(1, 32, 8192, 192, 128),
                    flash_grouped=(1, 8, 2, 8192, 128),
                    flash_gqa256=(1, 16, 2, 8192, 256),
                    flash_window=(1, 64, 8, 8192, 128, 512),
                    delta_rule=(1, 4096, 16, 32, 128, 128, 1024),
                    kda_rule=(1, 4096, 32, 128, 1024),
                    qmm=((8, 768, 3072), (256, 768, 3072), (8, 3072, 768),
                         (8, 768, 50257), (256, 768, 50257))),
}
TINY = {
    "resnet": dict(layers=18, classes=10, image=32, per_chip=4, steps=4,
                   lr=0.02),
    "lm": dict(layers=2, dim=64, heads=4, seq=128, vocab=256, per_chip=2,
               steps=4, lr=0.1),
    "serve": dict(layers=2, dim=64, heads=4, vocab=128, positions=64,
                  prompt_buckets=(8, 16), decode_buckets=(1, 2), max_new=4,
                  kv_blocks=32, kv_block_size=8,
                  prompt_lengths=(3, 8, 12, 16, 5)),
    "kernels": dict(flash=(1, 2, 256, 8), flash_latent=(1, 2, 256, 24, 16),
                    flash_grouped=(1, 4, 2, 256, 16),
                    flash_gqa256=(1, 8, 1, 256, 32),
                    flash_window=(1, 8, 1, 256, 16, 48),
                    delta_rule=(1, 128, 1, 2, 128, 128, 128),
                    kda_rule=(1, 128, 1, 128, 128),
                    qmm=((8, 256, 384), (300, 600, 1000))),
}


class SmokeFailure(AssertionError):
    """A phase's check did not hold."""


def require(cond, what):
    if not cond:
        raise SmokeFailure(what)


class Compiles(object):
    """Counts XLA executables obtained (compiled, or loaded from the
    persistent cache) and the seconds that took, from jax's own
    monitoring events — the set-up cost a phase paid, and the proof a
    steady window paid none."""

    def __init__(self):
        import jax.monitoring as monitoring
        self.n = 0
        self.seconds = 0.0
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += seconds

    def _event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return (self.n, self.seconds, self.cache_hits)

    def since(self, snap):
        return {"executables": self.n - snap[0],
                "compile_s": round(self.seconds - snap[1], 1),
                "cache_hits": self.cache_hits - snap[2]}


class Env(object):
    """What every phase needs to know about where it runs."""

    def __init__(self, jax, rehearse):
        self.jax = jax
        self.rehearse = rehearse
        self.devices = jax.devices()
        self.n = len(self.devices)
        self.compiles = Compiles()

    def ctx(self, i=0):
        import mxnet_tpu as mx
        return mx.cpu(i) if self.rehearse else mx.tpu(i)

    def on_all_devices(self, arrays, what):
        """Every array sits on exactly the visible devices (one chip: on
        that chip; n chips: on all n)."""
        want = set(self.devices)
        for a in arrays:
            require(set(a.devices()) == want,
                    "%s: an array lives on %s, not on %s"
                    % (what, sorted(map(str, a.devices())),
                       sorted(map(str, want))))

    def on_device(self, arrays, dev, what):
        for a in arrays:
            require(set(a.devices()) == {dev},
                    "%s: an array lives on %s, not on %s"
                    % (what, sorted(map(str, a.devices())), dev))


def _check_losses(losses, what):
    require(all(np.isfinite(v) for v in losses),
            "%s: loss not finite: %s" % (what, losses))
    require(losses[-1] < losses[0],
            "%s: loss after %d steps on a fixed batch (%.4f) is not below "
            "the first (%.4f): %s" % (what, len(losses), losses[-1],
                                      losses[0], losses))


def _cross_entropy(jnp, probs, labels):
    """Mean CE of softmax rows vs integer labels, on the device."""
    p = jnp.take_along_axis(probs.astype(jnp.float32),
                            labels.astype(jnp.int32)[:, None], axis=1)
    return float(-jnp.mean(jnp.log(p + 1e-8)))


# ----------------------------------------------------------------------
# phase 1: train/resnet50
# ----------------------------------------------------------------------
def phase_train_resnet(env, cfg):
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu.models import resnet
    from mxnet_tpu import optimizer as opt_mod
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.trainer import ShardedTrainer

    jax = env.jax
    batch = cfg["per_chip"] * env.n
    shape = (batch, 3, cfg["image"], cfg["image"])
    rng = np.random.RandomState(0)
    data = rng.rand(*shape).astype(np.float32)
    label = rng.randint(0, cfg["classes"], (batch,)).astype(np.float32)
    net = resnet.get_symbol(num_classes=cfg["classes"],
                            num_layers=cfg["layers"],
                            image_shape=(3, cfg["image"], cfg["image"]))
    out = {"global_batch": batch}

    # (a) Module.fit: one fixed batch per epoch, so the metric of epoch
    # k is the loss of step k on that batch
    mx.random.seed(0)
    os.environ["MXNET_COMPUTE_DTYPE"] = "bfloat16"
    try:
        mod = mx.mod.Module(net, context=[env.ctx(i) for i in range(env.n)])
        losses = []
        mod.fit(mx.io.NDArrayIter(data, label, batch_size=batch),
                eval_metric="ce", num_epoch=cfg["steps"],
                kvstore="device" if env.n > 1 else "local",
                optimizer="sgd",
                optimizer_params={"learning_rate": cfg["lr"],
                                  "momentum": 0.9, "wd": 1e-4},
                initializer=mx.init.Xavier(),
                batch_end_callback=lambda p: losses.append(
                    float(p.eval_metric.get()[1])))
        _check_losses(losses, "resnet/Module.fit")
        group = mod._exec_group
        exe = group.execs[0]
        require(exe._n_fused_step == cfg["steps"],
                "Module.fit took %d fused steps, want %d"
                % (exe._n_fused_step, cfg["steps"]))
        states = jax.tree_util.tree_leaves(mod._fused_holder["states"])
        require(states, "Module.fit kept no optimizer state")
        params = [exe.arg_dict[n].data for n in group.param_names]
        env.on_all_devices(params + states + [o.data for o in exe.outputs],
                           "resnet/Module.fit params, optimizer state and "
                           "outputs")
        if env.n > 1:
            require(group.sharded, "Module did not build the mesh executor")
            require("all-reduce" in group.fused_step_hlo(mod._optimizer),
                    "Module fused step carries no all-reduce over %d "
                    "devices" % env.n)
        out["module_fit_loss"] = [round(v, 4) for v in losses]
    finally:
        os.environ.pop("MXNET_COMPUTE_DTYPE", None)
    del mod, group, exe, states, params

    # (b) ShardedTrainer.step: bench.py's path
    mx.random.seed(0)
    mesh = make_mesh(env.devices, dp=env.n)
    opt = opt_mod.create("sgd", learning_rate=cfg["lr"], momentum=0.9,
                         wd=1e-4, rescale_grad=1.0 / batch)
    trainer = ShardedTrainer(net, opt, mesh, compute_dtype="bfloat16")
    params, opt_state, aux = trainer.init_params(
        {"data": shape}, label_shapes={"softmax_label": (batch,)},
        initializer=mx.init.Xavier())
    placed = trainer.shard_batch({"data": data, "softmax_label": label})
    losses = []
    for _ in range(cfg["steps"]):
        params, opt_state, aux, outs = trainer.step(params, opt_state, aux,
                                                    placed)
        losses.append(_cross_entropy(jnp, outs[0], placed["softmax_label"]))
    _check_losses(losses, "resnet/ShardedTrainer")
    require(trainer.donation_verified() is True,
            "resnet/ShardedTrainer: donated buffers were not aliased")
    env.on_all_devices(
        jax.tree_util.tree_leaves((params, opt_state, aux, outs)),
        "resnet/ShardedTrainer params, optimizer state and outputs")
    if env.n > 1:
        require("all-reduce" in trainer._compiled().as_text(),
                "ShardedTrainer step carries no all-reduce over %d devices"
                % env.n)
    out["trainer_loss"] = [round(v, 4) for v in losses]
    return out


# ----------------------------------------------------------------------
# phase 2: train/lm
# ----------------------------------------------------------------------
def phase_train_lm(env, cfg):
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu.models import transformer
    from mxnet_tpu import optimizer as opt_mod
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.trainer import ShardedTrainer

    jax = env.jax
    batch, seq, vocab = cfg["per_chip"] * env.n, cfg["seq"], cfg["vocab"]
    mx.random.seed(0)
    net = transformer.get_symbol(vocab_size=vocab, num_layers=cfg["layers"],
                                 num_heads=cfg["heads"], dim=cfg["dim"],
                                 seq_len=seq)
    opt = opt_mod.create("sgd", learning_rate=cfg["lr"], momentum=0.9,
                         rescale_grad=1.0 / (batch * seq))
    trainer = ShardedTrainer(net, opt, make_mesh(env.devices, dp=env.n),
                             compute_dtype="bfloat16")
    params, opt_state, aux = trainer.init_params(
        {"data": (batch, seq)}, label_shapes={"softmax_label": (batch, seq)})
    rng = np.random.RandomState(0)
    placed = trainer.shard_batch({
        "data": rng.randint(0, vocab, (batch, seq)).astype(np.int32),
        "softmax_label": rng.randint(0, vocab,
                                     (batch, seq)).astype(np.float32)})
    labels = placed["softmax_label"].reshape(-1)
    losses = []
    for _ in range(cfg["steps"]):
        params, opt_state, aux, outs = trainer.step(params, opt_state, aux,
                                                    placed)
        losses.append(_cross_entropy(jnp, outs[0], labels))
    _check_losses(losses, "lm/ShardedTrainer")
    require(trainer.donation_verified() is True,
            "lm/ShardedTrainer: donated buffers were not aliased")
    env.on_all_devices(
        jax.tree_util.tree_leaves((params, opt_state, outs)),
        "lm/ShardedTrainer params, optimizer state and outputs")
    text = trainer._compiled().as_text()
    out = {"global_batch": batch, "loss": [round(v, 4) for v in losses],
           "mosaic_calls": text.count(MOSAIC)}
    if env.n > 1:
        require("all-reduce" in text,
                "lm step carries no all-reduce over %d devices" % env.n)
    if env.rehearse:
        out["mosaic_note"] = ("cpu-placed step: attention_reference, "
                              "checked on the chip only")
    else:
        # one per layer: the flash kernel ran, not attention_reference
        require(out["mosaic_calls"] >= cfg["layers"],
                "lm step carries %d Mosaic custom calls, want one per "
                "layer (%d): attention fell to the reference"
                % (out["mosaic_calls"], cfg["layers"]))
    return out, params


# ----------------------------------------------------------------------
# phase 3: serve/lm
# ----------------------------------------------------------------------
def _seeded_lm_params(mx, cfg, ctx):
    """GPT-2-style seeded init of the decoder's weights, on ``ctx``."""
    from mxnet_tpu.models import transformer
    full = transformer.get_symbol(
        vocab_size=cfg["vocab"], num_layers=cfg["layers"],
        num_heads=cfg["heads"], dim=cfg["dim"], seq_len=cfg["positions"])
    shapes = full.infer_shape(
        data=(1, cfg["positions"]),
        softmax_label=(1, cfg["positions"]))[0]
    mx.random.seed(1)
    init = mx.init.Normal(0.02)
    params = {}
    for name, shape in zip(full.list_arguments(), shapes):
        if name in ("data", "softmax_label"):
            continue
        params[name] = mx.nd.zeros(shape, ctx=ctx)
        init(name, params[name])
    return full, params


def _centered_cosine(a, b):
    """Cosine of two logit rows after removing each one's mean: softmax
    is shift-invariant, so this compares logits with log-probabilities
    exactly, and a constant offset cannot hide a mismatch."""
    a = np.asarray(a, np.float64).ravel()
    b = np.asarray(b, np.float64).ravel()
    a, b = a - a.mean(), b - b.mean()
    return float(np.dot(a, b) / (np.linalg.norm(a) * np.linalg.norm(b)))


def phase_serve_lm(env, cfg):
    import mxnet_tpu as mx
    from mxnet_tpu.predictor import Predictor
    from mxnet_tpu.serving import ModelServer

    ctx = env.ctx(0)
    dev = ctx.jax_device
    full, params = _seeded_lm_params(mx, cfg, ctx)
    arch = dict(vocab_size=cfg["vocab"], num_layers=cfg["layers"],
                num_heads=cfg["heads"], dim=cfg["dim"])
    rng = np.random.RandomState(2)
    prompts = [rng.randint(1, cfg["vocab"], size=n).tolist()
               for n in cfg["prompt_lengths"]]
    out = {"device": str(dev),
           "note": "serving uses one device (%d visible)" % env.n}

    srv = ModelServer()
    try:
        engine = srv.add_generative_model(
            "lm", params, max_seq_len=cfg["positions"],
            max_new_tokens=cfg["max_new"],
            prompt_buckets=cfg["prompt_buckets"],
            decode_buckets=cfg["decode_buckets"],
            kv_blocks=cfg["kv_blocks"], kv_block_size=cfg["kv_block_size"],
            ctx=ctx, **arch)
        require(len(engine.prompt_buckets) == 2
                and len(engine.decode_buckets) == 2,
                "want two prompt and two decode buckets, got %s / %s"
                % (engine.prompt_buckets, engine.decode_buckets))
        steady = env.compiles.snapshot()
        pending = [srv.generate("lm", p, max_new_tokens=cfg["max_new"])
                   for p in prompts]
        for (future, _stream), prompt in zip(pending, prompts):
            res = future.result(timeout=300.0)
            require(len(res["tokens"]) == cfg["max_new"]
                    and res["n_prompt"] == len(prompt)
                    and res["finish_reason"] == "length",
                    "request of %d tokens did not complete: %s"
                    % (len(prompt), res))
        stats = srv.stats()["models"]["lm"]
        window = env.compiles.since(steady)
        require(stats["lowerings_since_warmup"] == 0
                and window["executables"] == 0,
                "serving steady state was not compile-free: %d registry "
                "lowerings, %d XLA executables after warmup"
                % (stats["lowerings_since_warmup"], window["executables"]))
        out.update(requests=len(prompts),
                   tokens=stats["tokens_generated"],
                   lowerings_after_warmup=stats["lowerings_since_warmup"],
                   prompt_buckets=list(engine.prompt_buckets),
                   decode_buckets=list(engine.decode_buckets))
    finally:
        # the scheduler thread owns the engine while the server is open:
        # the checks below drive the engine directly, so they come after
        srv.close()

    # placement: weights, pools, per-step inputs and outputs of every
    # executable, after real traffic
    env.on_device(engine.cache.k_pools + engine.cache.v_pools, dev,
                  "serve/lm KV pools")
    for fam, preds in (("prefill", engine._prefill),
                       ("decode", engine._decode)):
        for bucket, pred in preds.items():
            env.on_device([a.data for a in pred._exec.arg_dict.values()],
                          dev, "serve/lm %s[%d] inputs and weights"
                          % (fam, bucket))
            env.on_device([o.data for o in pred._exec.outputs
                           if o is not None], dev,
                          "serve/lm %s[%d] bound outputs" % (fam, bucket))
    pred, inputs, _b = engine.start_decode([])
    env.on_device(engine.run_async(pred, inputs), dev,
                  "serve/lm decode outputs")
    pred = engine._prefill[engine.prompt_buckets[0]]
    env.on_device(engine.run_async(pred, {
        k: np.zeros(pred._exec.arg_dict[k].shape, np.float32)
        for k in ("data", "pos_ids", "seq_pos", "block_table")}), dev,
        "serve/lm prefill outputs")

    # logits through the paged cache vs the uncached full forward: step
    # 0 comes out of prefill, step 1 out of a decode step that read the
    # prompt's k/v back from the pools
    probe = [prompts[0], prompts[2], prompts[-1]]
    engine.collect_logits = True
    generated = engine.generate(probe, max_new_tokens=2)
    ref = Predictor(full.tojson(), params,
                    {"data": (1, cfg["positions"]),
                     "softmax_label": (1, cfg["positions"])}, ctx=ctx)
    worst = 1.0
    for prompt, toks, rows in zip(probe, generated, engine.last_logits):
        seq = list(prompt) + [toks[0]]
        data = np.zeros((1, cfg["positions"]), np.float32)
        data[0, :len(seq)] = seq
        probs = ref.forward(
            data=data,
            softmax_label=np.zeros((1, cfg["positions"]), np.float32))[0]
        require(len(rows) == 2, "probe kept %d logit rows, want 2"
                % len(rows))
        for step, row in enumerate(rows):
            want = np.log(probs[len(prompt) - 1 + step] + 1e-30)
            worst = min(worst, _centered_cosine(row, want))
    require(worst >= 0.999,
            "paged-cache logits vs uncached full forward: min cosine "
            "%.6f < 0.999" % worst)
    out["logits_cosine_min"] = round(worst, 6)
    return out


# ----------------------------------------------------------------------
# phase 4: kernels
# ----------------------------------------------------------------------
def _rel_err(got, want):
    """max |got - want| over max |want|."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1e-30))


def phase_kernels(env, cfg, lm_params):
    """Each shipped Pallas kernel once, compiled by Mosaic
    (``interpret=False``; interpreted in the rehearsal), against its jnp
    reference evaluated at the highest matmul precision.  Tolerances are
    max-error over max-magnitude: 2e-2 for float32 operands (the MXU's
    default f32 path rounds through bf16 passes), 4e-2 for bfloat16,
    1e-6 for the elementwise optimizer sweep."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import optimizer as opt_mod
    from mxnet_tpu.executor import mirror_checkpoint
    from mxnet_tpu.kernels import fused_opt, quantize
    from mxnet_tpu.parallel.ring_attention import (attention_reference,
                                                   flash_attention)
    interpret = bool(env.rehearse)
    dev = env.devices[0]
    rng = np.random.RandomState(3)
    out = {}

    def put(a):
        return jax.device_put(a, dev)

    def highest(fn, *args):
        with jax.default_matmul_precision("highest"):
            return jax.jit(fn)(*args)

    # flash-attention forward (+ the backward kernel its stats feed):
    # 64-wide heads in both dtypes, latent attention's shape — q and k
    # 192 wide, v 128, 8,192 keys — and grouped queries' — 8 heads of 128
    # on 2 key/value heads, and 16 heads of 256 on 2 (the forward asks for
    # VMEM, the backward splits each group of eight over four programs),
    # 8,192 keys, and 64 heads of 128 on 8 under a sliding window of 512
    # (the windowed kernels) — in bfloat16.  The reference goes
    # by query blocks, each against the keys up to its end, so that 8,192
    # keys of 32 heads fit beside the kernel's own buffers; it repeats
    # k and v over a group, which the kernels do not.
    b, h, s, d = cfg["flash"]
    lb, lh, ls, l_qk, l_v = cfg["flash_latent"]
    gb, gh, gkv, gs, gd = cfg["flash_grouped"]
    wb, wh, wkv, ws, wd = cfg["flash_gqa256"]
    sb, sh, skv, ss, sd, window = cfg["flash_window"]
    cases = [((b, h, h, s, d, d), jnp.float32, 2e-2, "", None),
             ((b, h, h, s, d, d), jnp.bfloat16, 4e-2, "", None),
             ((lb, lh, lh, ls, l_qk, l_v), jnp.bfloat16, 4e-2, ",latent",
              None),
             ((gb, gh, gkv, gs, gd, gd), jnp.bfloat16, 4e-2, ",grouped",
              None),
             ((wb, wh, wkv, ws, wd, wd), jnp.bfloat16, 4e-2, ",gqa256",
              None),
             ((sb, sh, skv, ss, sd, sd), jnp.bfloat16, 4e-2,
              ",window%d" % window, window)]
    for (b, h, h_kv, s, d_qk, d_v), dt, tol, tag, window in cases:
        q = put(jnp.asarray(rng.randn(b, h, s, d_qk), dt))
        k = put(jnp.asarray(rng.randn(b, h_kv, s, d_qk), dt))
        v = put(jnp.asarray(rng.randn(b, h_kv, s, d_v), dt))

        def loss(fn, q, k, v):
            o = fn(q, k, v)
            return jnp.sum(jnp.sin(o.astype(jnp.float32))), o

        def kernel(q, k, v, window=window):
            return flash_attention(q, k, v, causal=True,
                                   interpret=interpret, window=window)

        def reference(q, k, v, step=min(1024, s), window=window):
            q, k, v = (t.astype(jnp.float32) for t in (q, k, v))
            return jnp.concatenate([
                jax.checkpoint(functools.partial(
                    attention_reference, causal=True, q_offset=i,
                    window=window))(
                        q[..., i:i + step, :], k[..., :i + step, :],
                        v[..., :i + step, :])
                for i in range(0, s, step)], axis=-2)

        grad = lambda fn: jax.value_and_grad(    # noqa: E731
            lambda q, k, v: loss(fn, q, k, v), argnums=(0, 1, 2),
            has_aux=True)
        (_, got_o), got_g = jax.jit(grad(kernel))(q, k, v)
        (_, want_o), want_g = highest(grad(reference), q, k, v)
        errs = [_rel_err(got_o, want_o)] + [
            _rel_err(g, w) for g, w in zip(got_g, want_g)]
        name = "flash_attention[%s%s]" % (jnp.dtype(dt).name, tag)
        require(max(errs) <= tol, "%s: out/dq/dk/dv errors %s exceed %g"
                % (name, errs, tol))
        out[name] = round(max(errs), 5)
        if window is not None and not interpret:
            # placed on the chip, the windowed call must be the kernels
            text = jax.jit(grad(kernel)).lower(q, k, v).compile().as_text()
            require(text.count(MOSAIC) >= 2 and "flash_window" in text,
                    "%s: the gradient program holds %d Mosaic calls, not "
                    "the windowed kernels" % (name, text.count(MOSAIC)))

        # a mirrored block around the kernel, as the executor lowers one:
        # its gradient is the unmirrored block's, with the tanh recomputed
        # and the kernel not.  The two programs compile the backward's
        # float32 row sums (delta) apart, so a ds may round to its other
        # neighbour: one step of the dtype at most (dv, which reads no
        # delta, came out equal to the bit)
        def block(q, k, v):
            return kernel(q, jnp.tanh(k), v)

        _, plain_g = jax.jit(grad(block))(q, k, v)
        _, kept_g = jax.jit(grad(mirror_checkpoint(block)))(q, k, v)
        errs = [_rel_err(g, w) for g, w in zip(kept_g, plain_g)]
        step = max(1e-6, 2 * float(jnp.finfo(dt).eps))
        require(max(errs) <= step,
                "%s mirrored: dq/dk/dv differ from the unmirrored block's "
                "by %s (a step is %g)" % (name, errs, step))
        out[name + ",mirrored"] = round(max(errs), 5)
        del q, k, v, got_o, got_g, want_o, want_g, plain_g, kept_g

    # the gated delta rule: what the op runs against the recurrence a token
    # at a time, at the timed head geometry (16 key heads on 32 value heads
    # of 128: the grouped index, dq and dk summed over a group) — the Pallas
    # kernels, which ``dispatch`` picks because the call is placed on the
    # chip (the compiled call must hold them; interpreted in the rehearsal),
    # and beside them the XLA form they replace there, both held to the
    # recurrence: outputs over the long sequence, every gradient over a
    # short one that still crosses a grid step (the recurrence's backward
    # keeps a state a token).  float32 operands multiply at the chip's
    # default precision in both forms, so both dtypes are held to rounding
    # of bfloat16's order.
    from mxnet_tpu.ops import linear_attention as la
    rb, rs, rhk, rh, r_dk, r_dv, r_short = cfg["delta_rule"]
    rule = functools.partial(la.gated_delta_rule, interpret=True) \
        if interpret else la.gated_delta_rule
    forms = (("kernel", rule), ("xla", la.gated_delta_rule_xla))

    def recurrence(q, k, *rest):
        q, k = (jnp.repeat(t, rh // rhk, axis=2) for t in (q, k))
        return la.gated_delta_rule_recurrent(q, k, *rest)

    def rule_loss(fn, *a):
        return jnp.sum(jnp.sin(fn(*a).astype(jnp.float32)))

    for dt, tol in ((jnp.float32, 2e-2), (jnp.bfloat16, 4e-2)):
        q = la.l2_normalise(rng.randn(rb, rs, rhk, r_dk)) * r_dk ** -0.5
        k = la.l2_normalise(rng.randn(rb, rs, rhk, r_dk) + 0.5)
        q, k = put(q.astype(dt)), put(k.astype(dt))
        v = put(jnp.asarray(rng.randn(rb, rs, rh, r_dv), dt))
        g = put(-jnp.exp(jnp.asarray(rng.uniform(-6, 2, (rb, rs, rh)),
                                     jnp.float32)))
        beta = put(jax.nn.sigmoid(jnp.asarray(rng.randn(rb, rs, rh),
                                              jnp.float32)))
        args = (q, k, v, g, beta)
        short = tuple(t[:, :r_short] for t in args)
        wrt = (0, 1, 2, 3, 4)
        want = highest(recurrence, *args)
        want_g = highest(jax.grad(functools.partial(rule_loss, recurrence),
                                  wrt), *short)
        if not interpret:
            text = jax.jit(jax.grad(functools.partial(rule_loss, rule),
                                    wrt)).lower(*short).compile().as_text()
            require(text.count(MOSAIC) >= 2,
                    "gated_delta_rule: placed on the chip, its gradient "
                    "program holds %d Mosaic calls, not the forward and "
                    "backward kernels" % text.count(MOSAIC))
        for form, fn in forms:
            name = "gated_delta_rule[%s,%s]" % (jnp.dtype(dt).name, form)
            err = _rel_err(jax.jit(fn)(*args), want)
            require(err <= tol, "%s: output error %g against the "
                    "recurrence exceeds %g" % (name, err, tol))
            got_g = jax.jit(jax.grad(functools.partial(rule_loss, fn),
                                     wrt))(*short)
            errs = [_rel_err(a, b) for a, b in zip(got_g, want_g)]
            require(max(errs) <= 2 * tol, "%s: dq/dk/dv/dg/dbeta errors %s "
                    "against the recurrence exceed %g"
                    % (name, errs, 2 * tol))
            out[name] = round(max([err] + errs), 5)
            del got_g
        del q, k, v, g, beta, args, short, want, want_g

    # Kimi delta attention: the rule with a decay a key channel, at Ling's
    # head geometry (32 heads of 128), the same way — its two kernels and
    # its XLA form against the recurrence — with the decay drawn across its
    # bounded range and at the bound, −5, on every channel of the second
    # chunk, where a sub-block's key factor reaches e^75
    kb, ks, kh, kd, k_short = cfg["kda_rule"]
    kda = functools.partial(la.kda_rule, interpret=True) if interpret \
        else la.kda_rule
    for dt, tol in ((jnp.float32, 2e-2), (jnp.bfloat16, 4e-2)):
        q = la.l2_normalise(rng.randn(kb, ks, kh, kd)) * kd ** -0.5
        k = la.l2_normalise(rng.randn(kb, ks, kh, kd) + 0.5)
        q, k = put(q.astype(dt)), put(k.astype(dt))
        v = put(jnp.asarray(rng.randn(kb, ks, kh, kd), dt))
        g = -5.0 * jax.nn.sigmoid(jnp.asarray(
            2 * rng.randn(kb, ks, kh, kd), jnp.float32))
        g = put(g.at[:, 64:128].set(-5.0))
        beta = put(jax.nn.sigmoid(jnp.asarray(rng.randn(kb, ks, kh),
                                              jnp.float32)))
        args = (q, k, v, g, beta)
        short = tuple(t[:, :k_short] for t in args)
        wrt = (0, 1, 2, 3, 4)
        want = highest(la.kda_rule_recurrent, *args)
        want_g = highest(jax.grad(functools.partial(
            rule_loss, la.kda_rule_recurrent), wrt), *short)
        if not interpret:
            text = jax.jit(jax.grad(functools.partial(rule_loss, kda),
                                    wrt)).lower(*short).compile().as_text()
            require(text.count(MOSAIC) >= 2,
                    "kda_rule: placed on the chip, its gradient program "
                    "holds %d Mosaic calls, not the forward and backward "
                    "kernels" % text.count(MOSAIC))
        for form, fn in (("kernel", kda), ("xla", la.kda_rule_xla)):
            name = "kda_rule[%s,%s]" % (jnp.dtype(dt).name, form)
            err = _rel_err(jax.jit(fn)(*args), want)
            require(err <= tol, "%s: output error %g against the "
                    "recurrence exceeds %g" % (name, err, tol))
            got_g = jax.jit(jax.grad(functools.partial(rule_loss, fn),
                                     wrt))(*short)
            errs = [_rel_err(a, b) for a, b in zip(got_g, want_g)]
            require(max(errs) <= 2 * tol, "%s: dq/dk/dv/dg/dbeta errors %s "
                    "against the recurrence exceed %g"
                    % (name, errs, 2 * tol))
            out[name] = round(max([err] + errs), 5)
            del got_g
        del q, k, v, g, beta, args, short, want, want_g

    # weight-only quantized matmul, FFN shapes and the LM head
    for m, k, n in cfg["qmm"]:
        w_q, scale = quantize.quantize_array(
            rng.randn(n, k).astype(np.float32) * 0.02)
        w_q, scale = put(w_q), put(scale)
        for dt, tol in ((jnp.float32, 2e-2), (jnp.bfloat16, 4e-2)):
            x = put(jnp.asarray(rng.randn(m, k), dt))
            got = jax.jit(lambda x, w, s: quantize.quantized_matmul(
                x, w, s, interpret=interpret))(x, w_q, scale)
            want = highest(quantize.quantized_matmul_reference,
                           x.astype(jnp.float32), w_q, scale)
            name = "quantized_matmul[%s,%dx%d->%d]" % (
                jnp.dtype(dt).name, m, k, n)
            err = _rel_err(got, want)
            require(got.shape == (m, n) and err <= tol,
                    "%s: error %g exceeds %g" % (name, err, tol))
            out[name] = round(err, 5)

    # fused optimizer sweep over the phase-2 LM's parameter tree
    params = {n: put(np.asarray(a)) for n, a in lm_params.items()}
    grads = {n: put(rng.randn(*a.shape).astype(np.float32) * 0.01)
             for n, a in params.items()}
    opt = opt_mod.create("sgd", learning_rate=0.1, momentum=0.9)
    state = {n: put(jnp.asarray(rng.randn(*a.shape), jnp.float32) * 0.01)
             for n, a in params.items()}

    def sweep(mode, itp):
        return jax.jit(lambda p, g, s: fused_opt.fused_apply(
            opt, p, g, s, 0.1, 1e-4, 3, mode=mode, interpret=itp))(
                params, grads, state)

    got_w, got_s = sweep("kernel", interpret)
    want_w, want_s = sweep("1", None)
    err = max(_rel_err(a, b) for a, b in zip(
        jax.tree_util.tree_leaves((got_w, got_s)),
        jax.tree_util.tree_leaves((want_w, want_s))))
    numel = sum(int(np.prod(a.shape)) for a in params.values())
    name = "fused_opt_sweep[float32,%d]" % numel
    require(err <= 1e-6, "%s: error %g exceeds 1e-6" % (name, err))
    out[name] = err
    return out


# ----------------------------------------------------------------------
def main():
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at tiny sizes, kernels "
                         "interpreted; needs JAX_PLATFORMS=cpu")
    args = ap.parse_args()

    t_start = time.perf_counter()
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if args.rehearse:
        if platform != "cpu":
            sys.stderr.write("chip_smoke.py --rehearse is the CPU "
                             "rehearsal: run it under JAX_PLATFORMS=cpu\n")
            return 2
    elif platform != "tpu":
        sys.stderr.write("chip_smoke.py: no TPU (jax.devices() is %s); "
                         "nothing was run\n" % (devices,))
        return 2

    import bench
    from mxnet_tpu import libinfo
    from mxnet_tpu.parallel import enable_persistent_cache
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    peaks = {}
    if not args.rehearse:
        # an unknown device kind is an error, not None
        for key, (val, note) in (
                ("bf16_tflops", bench._lookup_peak_tflops(device["kind"])),
                ("int8_tops", bench._lookup_peak_tflops(device["kind"],
                                                        "int8")),
                ("hbm_gbps", bench._lookup_peak_hbm(device["kind"]))):
            require(val is not None, note)
            peaks[key] = val
    print(json.dumps({"device": device, "peaks_per_chip": peaks,
                      "rehearsal": args.rehearse}), flush=True)
    # lib/ is ignored build output: a fresh checkout builds it here
    require(libinfo.find_lib() is not None,
            "native library missing: " + libinfo.describe())
    print("host runtime: %s" % libinfo.describe(), flush=True)
    print("compile cache: %s" % enable_persistent_cache(), flush=True)

    env = Env(jax, args.rehearse)
    cfg = TINY if args.rehearse else FULL
    summary = {}
    lm_params = None
    for name, phase in (
            ("train/resnet50", lambda: phase_train_resnet(env,
                                                          cfg["resnet"])),
            ("train/lm", lambda: phase_train_lm(env, cfg["lm"])),
            ("serve/lm", lambda: phase_serve_lm(env, cfg["serve"])),
            ("kernels", lambda: phase_kernels(env, cfg["kernels"],
                                              lm_params))):
        t0 = time.perf_counter()
        snap = env.compiles.snapshot()
        result = phase()
        if name == "train/lm":
            result, lm_params = result
        result.update(env.compiles.since(snap))
        result["seconds"] = round(time.perf_counter() - t0, 1)
        summary[name] = {k: result[k] for k in
                         ("seconds", "compile_s", "executables",
                          "cache_hits")}
        print(json.dumps({"phase": name, "ok": True, **result}),
              flush=True)

    total = env.compiles.since((0, 0.0, 0))
    print(json.dumps({"phases": summary, "total_s": round(
        time.perf_counter() - t_start, 1), **total,
        "rehearsal": args.rehearse}), flush=True)
    final = {"ok": True, "device": device}
    if args.rehearse:
        final["rehearsal"] = True       # not a chip result
    print(json.dumps(final), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
