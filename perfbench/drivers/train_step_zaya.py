"""Driver ``train_step_zaya``: a language model of the ZAYA1 layer family —
attention in a compressed latent with grouped query heads, a top-1 routed
layer whose router is an MLP with a stream of its own, a head tied to the
embedding — trained through ``ShardedTrainer.step`` on a mesh of the cell's
chips.

The timed loop, the routing counters' hand-over and the calibration are
``train_step_blocks``'s (and so ``train_step``'s loop under them).  What
differs is what that driver ties to its family: the builder's arguments come
from this configuration's keys, a batch carries one label, nothing is read of
a second head, the reference's step takes no second label — and the routers'
balancing bias is not 0: set-up seeds it from the seeded weights and the
first batch (``reference.balancing_bias``) and hands the same numbers to the
program, as its auxiliary state, and to the reference.  That pass is the
benchmark's making of a checkpoint's β, not the program's set-up: its
seconds are logged and taken out of ``setup_s``.

``correct`` compares the worst leaf's norms, as the other language models'
cells do, but for the leaves whose gradient the reference itself reads as a
sum of cancelling terms (:func:`cancelling_leaves`), and beside the norms a
number that follows which token went to which expert (``expert_grad_diff``).
"""
import importlib
import time

import numpy as np

from .. import common, traffic
from . import train_fit
from .train_fit import host_readings, leaf_table, training_values
from .train_step_blocks import Driver as _BlocksDriver, _first_half

#: a leaf is left out of the worst-leaf comparison where the reference reads
#: its gradient as under a tenth of the sum of its terms' sizes: bfloat16's
#: step (2^-8) over such a ratio is 0.04 or more, half the limit and up
CANCEL_SHARE = 0.1

# what ``calibrate.py`` can put in the program's place in this cell beside
# the variants every training cell has: the reference with the operands of
# its products rounded to the configuration's own precision (all else stays
# float32), and the reference holding the wrong share of the experts
train_fit.VARIANTS.setdefault("bf16", {"lowprec": "bfloat16"})
train_fit.VARIANTS.setdefault("wrong_share", {"fault": "wrong_share"})


def symbol_args(cfg, seq):
    """The program's builder arguments, from the configuration's keys."""
    dep = cfg["deployment"]
    rope = cfg["rope_parameters"]["hybrid"]
    if cfg["layer_types"][:int(cfg["num_hidden_layers"])] \
            != ["hybrid"] * int(cfg["num_hidden_layers"]):
        raise ValueError("the program builds 'hybrid' layers only")
    if not cfg["tie_word_embeddings"] or cfg["hidden_act"] != "silu":
        raise ValueError("the program ties the head and gates with silu")
    return dict(
        vocab_size=int(cfg["vocab_size"]), seq_len=int(seq),
        num_layers=int(cfg["num_hidden_layers"]),
        dim=int(cfg["hidden_size"]),
        num_heads=int(cfg["num_attention_heads"]),
        num_kv_heads=int(cfg["num_key_value_heads"]),
        head_dim=int(cfg["head_dim"]),
        cca_time0=int(cfg["cca_time0"]), cca_time1=int(cfg["cca_time1"]),
        rope_theta=float(rope["rope_theta"]),
        partial_rotary_factor=float(rope["partial_rotary_factor"]),
        moe_intermediate_size=int(cfg["moe_intermediate_size"]),
        num_experts=int(dep["router_width"]),
        n_local_experts=int(cfg["num_experts"]),
        first_expert=int(dep["first_expert"]),
        num_experts_per_tok=int(cfg["num_experts_per_tok"]),
        router_hidden_size=int(cfg["router_hidden_size"]),
        rms_norm_eps=float(cfg["rms_norm_eps"]),
        mirror_blocks=bool(cfg["program"].get("mirror_blocks", False)))


class Driver(_BlocksDriver):
    LABELS = ("softmax_label",)

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
        net = model.get_symbol(**symbol_args(cfg, seq))
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
        self.routed = model.routed_layer_names(int(cfg["num_hidden_layers"]))
        self.host_pool = traffic.token_batches(tr, cfg, env.seed)
        # the program's own auxiliary state, counters 0, and in the place
        # of its zero balancing bias the seeded one, rounded to the compute
        # dtype the step casts it to
        aux = trainer.init_aux({"data": (batch, seq)}, batch_shapes)
        t_bias = time.perf_counter()
        self.bias = jax.block_until_ready(
            jax.jit(lambda w, ids: self.ref.balancing_bias(
                cfg, w, ids, jnp.dtype(opt["compute_dtype"])))(
                    params, jnp.asarray(self.host_pool[0][0])))
        self.bias_seconds = time.perf_counter() - t_bias
        env.log("the balancing bias seeded in %.1f s (not set-up)"
                % self.bias_seconds)
        for name, beta in zip(self.routed, self.bias):
            key = name + "_router_bias"
            aux[key] = jax.device_put(beta.astype(aux[key].dtype),
                                      aux[key].sharding)
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

    def window(self, seconds):
        res = super().window(seconds)
        res["t_first"] -= self.bias_seconds     # see the module's text
        return res

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
        out = {"loss": [], "rank": {n: a.ndim for n, a in w.items()}}
        step = self.ref.make_train_step(cfg, self.lr, self.mu, self.wd,
                                        lowprec=lowprec)
        for i in range(3):
            ids, lab = self.host_pool[i % len(self.host_pool)]
            if fault == "half_batch":   # the mean over the first half
                ids, lab = _first_half(ids), _first_half(lab)
            rows, first, w, m = step(w, m, jnp.asarray(ids),
                                     jnp.asarray(lab), self.bias)
            if fault == "unchanged":    # the state it got: the seed's
                del w, m
                w, m = self._init(self.key)
            out["loss"].append(float(jnp.mean(rows["main"])))
            if i == 0:
                out["row_loss"] = np.asarray(rows["main"])
                out["margin"] = np.asarray(rows["margin"])
                out["grad"] = {n: float(v) for n, v in first["grad"].items()}
                out["cancel"] = {n: float(v)
                                 for n, v in first["cancel"].items()}
                out["sketch"] = {n: np.asarray(v)
                                 for n, v in first["sketch"].items()}
            del rows, first
        del m
        out["delta"] = {n: float(v) for n, v in
                        self._delta_norms(w, self.key).items()}
        return out

    def program_readings(self):
        out = host_readings(self.losses, self.first)
        out["sketch"] = {n: np.asarray(v)
                         for n, v in self.first["sketch"].items()}
        return out

    def check(self):
        prog, ref = self.program_readings(), self.reference_readings()
        limits = self.env.limits
        values = zaya_values(prog, ref)
        self.env.log("read, not compared: " + "  ".join(
            "%s=%.4g" % (n, v) for n, v in values.items() if n not in limits))
        self.env.log("left out of the worst leaf, by the reference's "
                     "cancellation: " + "  ".join(
                         "%s=%.3g" % (n, ref["cancel"][n])
                         for n in sorted(cancelling_leaves(ref["cancel"]))))
        # how many tokens rounding can send to another expert, and with
        # half of the experts absent between "computed" and "left out":
        # the reference's chosen score leads the runner-up by under 1e-3
        self.env.log("tokens whose expert leads the runner-up by under 1e-3 "
                     "in some layer of the reference: %.2f %%"
                     % (100.0 * np.mean(ref["margin"] < 1e-3)))
        return [(name, values[name], limit)
                for name, limit in limits.items()]

    def calibration(self, variants):
        """``train_fit.calibrate_training`` over :func:`zaya_values`, with
        the reference's cancellation beside the table of leaves."""
        ref = self.reference_readings()
        out = {}
        for name in ["prog"] + list(variants):
            t0 = time.perf_counter()
            stand = self.program_readings() if name == "prog" else \
                self.reference_readings(**train_fit.variant_args(self, name))
            out[name] = {"values": zaya_values(stand, ref),
                         "loss": stand["loss"], "ref_loss": ref["loss"],
                         "leaves": leaf_table(stand, ref),
                         "cancel": ref["cancel"]}
            self.env.log("%s read in %.1f s" % (name,
                                                time.perf_counter() - t0))
        return out


def cancelling_leaves(cancel, share=CANCEL_SHARE):
    """Leaves whose gradient the reference reads as a sum of cancelling
    terms (``reference.zaya1.cancellation`` under ``share``): rounding
    moves such a sum by the precision's step over that ratio, so its norm
    says nothing of the program.  A rule on the reference's gradient, as
    ``common.dead_leaves`` is, not a list of names."""
    return {n for n, c in cancel.items() if c < share}


def zaya_values(prog, ref):
    """``train_fit.training_values`` with the worst leaf (``grad_norm_gap``,
    ``delta_norm_gap``) taken over the leaves that are not
    :func:`cancelling_leaves`, and ``expert_grad_diff``: the norm of the
    difference between the program's and the reference's first gradient of
    a layer's routed experts (their sketches, ``reference.expert_sketch``)
    against the norm of the reference's, the worst layer's."""
    values = training_values(prog, ref)
    skip = cancelling_leaves(ref["cancel"])
    values["grad_norm_gap"] = max(common.leaf_gaps(
        prog["grad"], ref["grad"], skip=skip).values())
    values["delta_norm_gap"] = max(common.leaf_gaps(
        prog["delta"], ref["delta"],
        skip=skip | common.dead_leaves(ref["grad"])).values())
    values["expert_grad_diff"] = max(
        float(np.linalg.norm(prog["sketch"][n] - sketch)
              / max(np.linalg.norm(sketch), 1e-30))
        for n, sketch in ref["sketch"].items())
    return values
