"""Driver ``train_step_blocks``: a language model of named blocks — latent
attention, routed experts, a prediction module — trained through
``ShardedTrainer.step`` on a mesh of the cell's chips.

The timed loop is ``train_step``'s: the batch resident on the device (a pool
of seeded token batches, cycled), one step queued behind the one that runs.
What differs is what ``train_step`` ties to GPT-2: the program's symbol is
built from the configuration's own keys, a batch carries a second label (the
token after the next one, for the prediction module), the step has auxiliary
state (the routers' correction bias and the routing counters), the reference
keeps one leaf a name (nothing is stacked) and donates its state, and the
routing counters are read once, after the window, and handed to ``counters``.
"""
import importlib
import time

import numpy as np

from .. import common, traffic
from .train_fit import host_readings
from .train_step import Driver as _StepDriver


def symbol_args(cfg, seq):
    """The program's builder arguments, from the configuration's keys."""
    dep = cfg["deployment"]
    if not cfg["norm_topk_prob"] or cfg["scoring_func"] != "sigmoid":
        raise ValueError("the program's routed layer scores with a sigmoid "
                         "and normalises the chosen weights")
    return dict(
        vocab_size=int(cfg["vocab_size"]), seq_len=int(seq),
        num_layers=int(cfg["num_hidden_layers"]),
        first_k_dense=int(cfg["first_k_dense_replace"]),
        dim=int(cfg["hidden_size"]),
        num_heads=int(cfg["num_attention_heads"]),
        q_lora_rank=int(cfg["q_lora_rank"]),
        kv_lora_rank=int(cfg["kv_lora_rank"]),
        qk_nope_head_dim=int(cfg["qk_nope_head_dim"]),
        qk_rope_head_dim=int(cfg["qk_rope_head_dim"]),
        v_head_dim=int(cfg["v_head_dim"]),
        rope_theta=float(cfg["rope_theta"]),
        intermediate_size=int(cfg["intermediate_size"]),
        moe_intermediate_size=int(cfg["moe_intermediate_size"]),
        n_routed_experts=int(dep["router_width"]),
        n_local_experts=int(cfg["n_routed_experts"]),
        first_expert=int(dep["first_expert"]),
        n_shared_experts=int(cfg["n_shared_experts"]),
        num_experts_per_tok=int(cfg["num_experts_per_tok"]),
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        rms_norm_eps=float(cfg["rms_norm_eps"]),
        num_nextn_predict_layers=int(cfg["num_nextn_predict_layers"]),
        mtp_loss_weight=float(cfg["mtp_loss_weight"]),
        mirror_blocks=bool(cfg["program"].get("mirror_blocks", False)))


class Driver(_StepDriver):
    LABELS = ("softmax_label", "mtp_label")

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
        batch_shapes = {n: (batch, seq) for n in self.LABELS}
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
        # the program's own auxiliary state: router bias 0, counters 0
        aux = trainer.init_aux({"data": (batch, seq)}, batch_shapes)
        self.routed = model.routed_layer_names(
            int(cfg["num_hidden_layers"]), int(cfg["first_k_dense_replace"]),
            int(cfg["num_nextn_predict_layers"]))
        self.host_pool = [
            (ids, lab, np.roll(lab, -1, axis=1))
            for ids, lab in traffic.token_batches(tr, cfg, env.seed)]
        self.pool = [trainer.shard_batch(
            {"data": ids, "softmax_label": lab, "mtp_label": lab2})
            for ids, lab, lab2 in self.host_pool]

        lr, wd = self.lr, self.wd

        @jax.jit
        def loss_of(probs, labels):
            p = jnp.take_along_axis(
                probs, labels.reshape(-1, 1).astype(jnp.int32), axis=1)
            rows = -jnp.log(p.astype(jnp.float32) + 1e-30)[:, 0]
            return jnp.mean(rows), rows

        @jax.jit
        def grad_norms(mom, k):
            # the gradient as the optimizer got it, from its state after
            # one step: m1 = -lr * (g + wd * w0)
            first, _ = init(k)
            return {n: jnp.linalg.norm((-m / lr - wd * first[n]).ravel())
                    for n, m in mom.items()}

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
                loss, rows = loss_of(outs[0], b["softmax_label"])
                self.losses.append(loss)
            if i == 0:
                self.first["row_loss"] = rows
                self.first["mtp_row_loss"] = loss_of(outs[1],
                                                     b["mtp_label"])[1]
                self.first["grad"] = grad_norms(opt_state, self.key)
            if i == 2:
                self.first["delta"] = delta_norms(params, self.key)
            del outs
        jax.block_until_ready(state)
        self.trainer, self.state = trainer, state
        self.n_done = int(tr["warmup_steps"])

    def _routing(self):
        """{routed layer: its counters} as the device holds them now."""
        from mxnet_tpu.ops.moe import routing_counters
        return {name: routing_counters(self.state[2], name)
                for name in self.routed}

    def window(self, seconds):
        before = self._routing()
        res = super().window(seconds)
        after = self._routing()         # one read, the window has closed
        steps = max(1, res["counters"]["steps"])
        top_k = int(self.env.config["num_experts_per_tok"])
        layers = []
        for name in self.routed:
            local = int(after[name]["local_assignments"][0]
                        - before[name]["local_assignments"][0])
            layers.append({
                "layer": name, "local_assignments": local,
                "expert_tokens": (after[name]["expert_tokens"]
                                  - before[name]["expert_tokens"]).tolist(),
                "peak_tokens_sum": int(after[name]["peak_tokens_sum"][0]
                                       - before[name]["peak_tokens_sum"][0]),
                "peak_tokens_max": int(after[name]["peak_tokens_max"][0])})
        res["counters"].update({
            "assignments_per_step": self.batch * self.seq * top_k,
            "routed_layers": layers})
        self.env.log("routing, per step and layer: %s" % "  ".join(
            "%s %.0f" % (r["layer"], r["local_assignments"] / steps)
            for r in layers))
        return res

    def reference_readings(self, lowprec=None, fault=None):
        """The reference's three steps from the seed (it donates its state:
        the old and the new do not fit side by side), one leaf a name."""
        import jax.numpy as jnp
        cfg = self.env.config
        w, m = self._init(self.key)
        out = {"loss": [], "rank": {n: a.ndim for n, a in w.items()}}
        step = self.ref.make_train_step(cfg, self.lr, self.mu, self.wd,
                                        lowprec=lowprec)
        for i in range(3):
            ids, lab, lab2 = self.host_pool[i % len(self.host_pool)]
            if fault == "half_batch":   # the mean over the first half
                ids, lab, lab2 = (_first_half(a) for a in (ids, lab, lab2))
            rows, norms, w, m = step(w, m, jnp.asarray(ids),
                                     jnp.asarray(lab), jnp.asarray(lab2))
            if fault == "unchanged":    # the state it got: the seed's
                del w, m
                w, m = self._init(self.key)
            out["loss"].append(float(jnp.mean(rows["main"])))
            if i == 0:
                out["row_loss"] = np.asarray(rows["main"])
                out["mtp_row_loss"] = np.asarray(rows["mtp"])
                out["margin"] = np.asarray(rows["margin"])
                out["grad"] = {n: float(v) for n, v in norms.items()}
            del rows, norms
        del m
        out["delta"] = {n: float(v) for n, v in
                        self._delta_norms(w, self.key).items()}
        return out

    def program_readings(self):
        out = host_readings(self.losses, self.first)
        out["mtp_row_loss"] = np.asarray(self.first["mtp_row_loss"],
                                         np.float32)
        return out

    def check(self):
        return check_blocks(self)

    def calibration(self, variants):
        from .train_fit import leaf_table, variant_args
        ref = self.reference_readings()
        out = {}
        for name in ["prog"] + list(variants):
            t0 = time.perf_counter()
            stand = self.program_readings() if name == "prog" else \
                self.reference_readings(**variant_args(self, name))
            out[name] = {"values": blocks_values(stand, ref),
                         "loss": stand["loss"], "ref_loss": ref["loss"],
                         "leaves": leaf_table(stand, ref)}
            self.env.log("%s read in %.1f s" % (name,
                                                time.perf_counter() - t0))
        return out


def _first_half(a):
    """The first half of a batch's sequences, or of its one sequence."""
    return a[:len(a) // 2] if len(a) > 1 else a[:, :a.shape[1] // 2]


def blocks_values(prog, ref):
    """``train_fit.training_values`` and, beside ``row_loss_diff`` (the
    main head), ``mtp_row_loss_diff``: the same number of the prediction
    module's head, which only this family has."""
    from .train_fit import relative_diff, training_values
    values = training_values(prog, ref)
    values["mtp_row_loss_diff"] = relative_diff(prog["mtp_row_loss"],
                                                ref["mtp_row_loss"])
    return values


def check_blocks(driver):
    """As ``train_fit.check_training``, over ``blocks_values``."""
    prog, ref = driver.program_readings(), driver.reference_readings()
    limits = driver.env.limits
    values = blocks_values(prog, ref)
    driver.env.log("read, not compared: " + "  ".join(
        "%s=%.4g" % (n, v) for n, v in values.items() if n not in limits))
    # how many tokens rounding can send elsewhere: the reference's last
    # chosen score leads the first one left out by under a thousandth
    driver.env.log("tokens whose last choice leads by under 1e-3 in some "
                   "routed layer of the reference: %.2f %%"
                   % (100.0 * np.mean(ref["margin"] < 1e-3)))
    return [(name, values[name], limit) for name, limit in limits.items()]
