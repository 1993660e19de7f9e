"""Driver ``train_step_ling``: the language model of the Ling-3.0 hybrid
layer family — Kimi delta attention on five of every six layers and latent
attention with a direct query on the sixth, a leading dense layer, then
sigmoid-routed experts chosen within the best groups, with an ungated shared
one, an untied head — trained through ``ShardedTrainer.step`` on a mesh of
the cell's chips.

Everything but set-up and the reference's faults is
``train_step_laguna``'s (and so ``train_step_zaya``'s comparison and
``train_step_blocks``'s timed loop and routing counters).  What differs is
what those drivers tie to their families: the builder's arguments come from
this configuration's per-layer lists — checked against the layer rule of the
published model and the layers held — and the reference can be asked for
this family's planted faults: each head decaying by the mean of its
channels (``scalar_decay``) and the top-k over all experts with no group
limit (``no_group_limit``).
"""
import importlib

import numpy as np

from .. import common, traffic
from . import train_fit
from . import train_step_laguna
from .train_step_blocks import _first_half

#: the faults ``calibrate.py`` can put in the program's place in this cell
#: beside ``train_step_zaya``'s variants (the wrong share of the experts among
#: them), and what each leaves out of the reference
FAULTS = {"scalar_decay": ("channel_decay",),
          "no_group_limit": ("group_limit",)}
for _name in FAULTS:
    train_fit.VARIANTS.setdefault(_name, {"fault": _name})


def symbol_args(cfg, seq):
    """The program's builder arguments, from the configuration's keys.
    Refuses what the program does not build: a low-rank query, a clamped
    SwiGLU on a layer held, another score, gate or head."""
    dep = cfg["deployment"]
    n = int(cfg["num_hidden_layers"])
    held = [int(i) for i in cfg["published_layers"][:n]]
    mixers, mlps = list(cfg["layer_mixers"][:n]), list(
        cfg["mlp_layer_types"][:n])
    group = int(cfg["layer_group_size"])
    if mixers != ["mla" if (i + 1) % group == 0 else "kda" for i in held] \
            or mlps != ["dense" if j < int(cfg["first_k_dense_replace"])
                        else "sparse" for j in range(n)]:
        raise ValueError("the per-layer lists differ from the layer rule "
                         "at layers %s" % held)
    limits = [cfg[k][i] for k in ("expert_swiglu_limit_list",
                                  "share_expert_swiglu_limit_list")
              for i in held]
    if any(limits) or cfg["q_lora_rank"] is not None \
            or cfg["score_function"] != "sigmoid" \
            or not cfg["norm_topk_prob"] or not cfg["kda_safe_gate"] \
            or not cfg["no_kda_lora"] or cfg["use_kda_lora"] \
            or int(cfg["num_kv_heads_for_linear_attn"]) not in (
                0, int(cfg["num_attention_heads"])) \
            or int(cfg["rotary_dim"]) != int(cfg["qk_rope_head_dim"]):
        raise ValueError("the program builds an unclamped SwiGLU, a direct "
                         "query, a sigmoid router over normalised weights, "
                         "a bounded full-rank KDA gate on every head and "
                         "rotary on the whole rotary slice")
    return dict(
        vocab_size=int(cfg["vocab_size"]), seq_len=int(seq),
        dim=int(cfg["hidden_size"]), mixer_types=mixers,
        mlp_layer_types=mlps, num_heads=int(cfg["num_attention_heads"]),
        head_dim=int(cfg["head_dim"]),
        conv_taps=int(cfg["short_conv_kernel_size"]),
        kda_lower_bound=float(cfg["kda_lower_bound"]),
        delta_chunk=int(cfg["program"].get("delta_chunk", 64)),
        kv_lora_rank=int(cfg["kv_lora_rank"]),
        qk_nope_head_dim=int(cfg["qk_nope_head_dim"]),
        qk_rope_head_dim=int(cfg["qk_rope_head_dim"]),
        v_head_dim=int(cfg["v_head_dim"]),
        rope_theta=float(cfg["rope_theta"]),
        intermediate_size=int(cfg["intermediate_size"]),
        moe_intermediate_size=int(cfg["moe_intermediate_size"]),
        shared_expert_intermediate_size=int(
            cfg["moe_shared_expert_intermediate_size"]),
        num_experts=int(dep["router_width"]),
        n_local_experts=int(cfg["num_experts"]),
        first_expert=int(dep["first_expert"]),
        num_experts_per_tok=int(cfg["num_experts_per_tok"]),
        n_group=int(cfg["n_group"]), topk_group=int(cfg["topk_group"]),
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        rms_norm_eps=float(cfg["rms_norm_eps"]),
        mirror_blocks=bool(cfg["program"].get("mirror_blocks", False)))


class Driver(train_step_laguna.Driver):

    def setup(self):
        """``train_step_laguna``'s set-up with this family's builder
        arguments."""
        import jax
        import jax.numpy as jnp
        from mxnet_tpu import optimizer as opt_mod
        from mxnet_tpu.parallel import make_mesh
        from mxnet_tpu.parallel.trainer import ShardedTrainer
        env, cfg, tr = self.env, self.env.config, self.env.traffic
        opt = cfg["training"]
        batch, seq = int(tr["batch"]), int(tr["seq"])
        self.batch, self.seq = batch, seq
        model = importlib.import_module(cfg["program"]["module"])
        args = symbol_args(cfg, seq)
        net = model.get_symbol(**args)
        self.lr, self.mu, self.wd = (float(opt["learning_rate"]),
                                     float(opt["momentum"]), float(opt["wd"]))
        optimizer = opt_mod.create("sgd", learning_rate=self.lr,
                                   momentum=self.mu, wd=self.wd,
                                   rescale_grad=1.0 / (batch * seq))
        mesh = make_mesh(env.devices, dp=len(env.devices))
        trainer = ShardedTrainer(net, optimizer, mesh,
                                 label_names=self.LABELS,
                                 compute_dtype=opt["compute_dtype"])
        shapes = self.ref.param_shapes(cfg)
        batch_shapes = {"softmax_label": (batch, seq)}
        have, _aux = trainer._shape_maps({"data": (batch, seq)},
                                         batch_shapes)
        have = {n: tuple(have[n]) for n in trainer.param_names}
        if have != {n: tuple(s) for n, s in shapes.items()}:
            raise RuntimeError("the program's parameters differ from the "
                               "configuration's: %s" % sorted(
                                   set(have.items()) ^ set(shapes.items())))
        self.key = common.jax_key(env.seed)
        shard = {n: trainer.param_sharding(n, s) for n, s in shapes.items()}

        def init(k):
            w = self.ref.init_params(cfg, k)
            return w, {n: jnp.zeros_like(a) for n, a in w.items()}

        self._init = jax.jit(init, out_shardings=(shard, shard))
        params, opt_state = self._init(self.key)
        self.routed = model.routed_layer_names(args["mlp_layer_types"])
        self.host_pool = traffic.token_batches(tr, cfg, env.seed)
        # the program's own auxiliary state: router bias 0, counters 0
        aux = trainer.init_aux({"data": (batch, seq)}, batch_shapes)
        self.pool = [trainer.shard_batch({"data": ids, "softmax_label": lab})
                     for ids, lab in self.host_pool]

        lr, wd = self.lr, self.wd

        @jax.jit
        def row_losses(probs, labels):
            p = jnp.take_along_axis(
                probs, labels.reshape(-1, 1).astype(jnp.int32), axis=1)
            return -jnp.log(p.astype(jnp.float32) + 1e-30)[:, 0]

        @jax.jit
        def first_gradient(mom, k):
            # the gradient as the optimizer got it, from its state after
            # one step: m1 = -lr * (g + wd * w0)
            first, _ = init(k)
            g = {n: -m / lr - wd * first[n] for n, m in mom.items()}
            return ({n: jnp.linalg.norm(v.ravel()) for n, v in g.items()},
                    self.ref.expert_sketch(g))

        @jax.jit
        def delta_norms(w, k):
            first, _ = init(k)
            return {n: jnp.linalg.norm((w[n] - first[n]).ravel())
                    for n in w}

        self._delta_norms = delta_norms
        self.losses, self.first = [], {}
        state = (params, opt_state, aux)
        for i in range(int(tr["warmup_steps"])):
            b = self.pool[i % len(self.pool)]
            params, opt_state, aux, outs = trainer.step(*state, b)
            state = (params, opt_state, aux)
            if i < 3:
                rows = row_losses(outs[0], b["softmax_label"])
                self.losses.append(jnp.mean(rows))
            if i == 0:
                self.first["row_loss"] = rows
                self.first["grad"], self.first["sketch"] = first_gradient(
                    opt_state, self.key)
            if i == 2:
                self.first["delta"] = delta_norms(params, self.key)
            del outs
        jax.block_until_ready(state)
        self.trainer, self.state = trainer, state
        self.n_done = int(tr["warmup_steps"])

    def reference_readings(self, lowprec=None, fault=None):
        """The reference's three steps from the seed (it donates its state:
        the old and the new do not fit side by side), one leaf a name."""
        import jax.numpy as jnp
        cfg = self.env.config
        if fault == "wrong_share":      # half of the experts held are others
            dep = cfg["deployment"]
            cfg = dict(cfg, deployment=dict(
                dep, first_expert=int(dep["first_expert"])
                + int(cfg["num_experts"]) // 2))
        w, m = self._init(self.key)
        out = {"loss": [], "rank": {n: a.ndim for n, a in w.items()},
               "cancel": {}}            # no leaf is a sum of cancelling terms
        step = self.ref.make_train_step(
            cfg, self.lr, self.mu, self.wd, lowprec=lowprec,
            without=FAULTS.get(fault, ()))
        for i in range(3):
            ids, lab = self.host_pool[i % len(self.host_pool)]
            if fault == "half_batch":   # the mean over the first half
                ids, lab = _first_half(ids), _first_half(lab)
            rows, first, w, m = step(w, m, jnp.asarray(ids), jnp.asarray(lab))
            if fault == "unchanged":    # the state it got: the seed's
                del w, m
                w, m = self._init(self.key)
            out["loss"].append(float(jnp.mean(rows["main"])))
            if i == 0:
                out["row_loss"] = np.asarray(rows["main"])
                out["margin"] = np.asarray(rows["margin"])
                out["grad"] = {n: float(v) for n, v in first["grad"].items()}
                out["sketch"] = {n: np.asarray(v)
                                 for n, v in first["sketch"].items()}
            del rows, first
        del m
        out["delta"] = {n: float(v) for n, v in
                        self._delta_norms(w, self.key).items()}
        return out
