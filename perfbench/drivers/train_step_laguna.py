"""Driver ``train_step_laguna``: a language model of the Laguna layer family —
sliding-window and full gated attention by layer, each layer with its own
query heads and rotary, a leading dense layer, then sigmoid-routed experts
with an ungated shared one, an untied head — trained through
``ShardedTrainer.step`` on a mesh of the cell's chips.

Everything but set-up and the reference's faults is
``train_step_qwen3_next``'s (and so ``train_step_zaya``'s comparison and
``train_step_blocks``'s timed loop and routing counters).  What differs is
what those drivers tie to their families: the builder's arguments come from
this configuration's per-layer lists, only the ``sparse`` layers route, and
the reference can be asked for this family's planted faults — window layers
that attend to every key before a query (``no_window``) and full layers with
plain rotary and no attention factor (``no_yarn``).
"""
import importlib

import numpy as np

from .. import common, traffic
from . import train_fit
from . import train_step_qwen3_next
from .train_step_blocks import _first_half

#: the faults ``calibrate.py`` can put in the program's place in this cell
#: beside ``train_step_zaya``'s variants (the wrong share of the experts among
#: them), and what each leaves out of the reference
FAULTS = {"no_window": ("window",), "no_yarn": ("yarn",)}
for _name in FAULTS:
    train_fit.VARIANTS.setdefault(_name, {"fault": _name})


def symbol_args(cfg, seq):
    """The program's builder arguments, from the configuration's keys."""
    dep = cfg["deployment"]
    n = int(cfg["num_hidden_layers"])
    if cfg["tie_word_embeddings"] or not cfg["gating"] \
            or cfg["attention_bias"] \
            or cfg["moe_apply_router_weight_on_input"]:
        raise ValueError("the program gates attention, has a head of its "
                         "own, no attention bias, and weights the experts' "
                         "outputs")
    return dict(
        vocab_size=int(cfg["vocab_size"]), seq_len=int(seq),
        dim=int(cfg["hidden_size"]),
        layer_types=list(cfg["layer_types"][:n]),
        num_attention_heads_per_layer=[
            int(h) for h in cfg["num_attention_heads_per_layer"][:n]],
        mlp_layer_types=list(cfg["mlp_layer_types"][:n]),
        num_kv_heads=int(cfg["num_key_value_heads"]),
        head_dim=int(cfg["head_dim"]),
        sliding_window=int(cfg["sliding_window"]),
        rope_parameters={kind: dict(cfg["rope_parameters"][kind])
                         for kind in set(cfg["layer_types"][:n])},
        intermediate_size=int(cfg["intermediate_size"]),
        moe_intermediate_size=int(cfg["moe_intermediate_size"]),
        shared_expert_intermediate_size=int(
            cfg["shared_expert_intermediate_size"]),
        num_experts=int(dep["router_width"]),
        n_local_experts=int(cfg["num_experts"]),
        first_expert=int(dep["first_expert"]),
        num_experts_per_tok=int(cfg["num_experts_per_tok"]),
        routed_scaling_factor=float(cfg["moe_routed_scaling_factor"]),
        rms_norm_eps=float(cfg["rms_norm_eps"]),
        mirror_blocks=bool(cfg["program"].get("mirror_blocks", False)))


class Driver(train_step_qwen3_next.Driver):

    def setup(self):
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
