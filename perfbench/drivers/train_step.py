"""Driver ``train_step``: a language model trained through
``ShardedTrainer.step`` on a mesh of the cell's chips.

The batch is resident on the device (a pool of seeded token batches, cycled),
so the feed is not what this driver measures.  Set-up builds one trainer and
its state from the seed, drives it through its first steps (what ``correct``
compares) and hands the same trainer and state to the window.  The window's
loop keeps one step queued behind the one that runs: after queueing a step it
waits for the one before it, as a training loop that logs its loss does.
"""
import collections
import importlib
import time

import numpy as np

from .. import common, traffic
from .train_fit import (calibrate_training, check_training,
                        host_readings)


class Driver(object):
    def __init__(self, env):
        self.env = env
        self.ref = common.reference_module(env.config)

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
        net = model.get_symbol(seq_len=seq, **cfg["program"]["symbol_args"])
        self.lr, self.mu, self.wd = (float(opt["learning_rate"]),
                                     float(opt["momentum"]), float(opt["wd"]))
        optimizer = opt_mod.create("sgd", learning_rate=self.lr,
                                   momentum=self.mu, wd=self.wd,
                                   rescale_grad=1.0 / (batch * seq))
        mesh = make_mesh(env.devices, dp=len(env.devices))
        trainer = ShardedTrainer(net, optimizer, mesh,
                                 compute_dtype=opt["compute_dtype"])
        shapes = self.ref.param_shapes(cfg, positions=seq)
        have, _aux = trainer._shape_maps(
            {"data": (batch, seq)}, {"softmax_label": (batch, seq)})
        have = {n: tuple(have[n]) for n in trainer.param_names}
        if have != {n: tuple(s) for n, s in shapes.items()}:
            raise RuntimeError("the program's parameters differ from the "
                               "configuration's: %s" % sorted(
                                   set(have.items()) ^ set(shapes.items())))
        self.key = common.jax_key(env.seed)
        shard = {n: trainer.param_sharding(n, s) for n, s in shapes.items()}

        def init(k):
            w = self.ref.init_params(cfg, k, positions=seq)
            return w, {n: jnp.zeros_like(a) for n, a in w.items()}

        self._init = jax.jit(init, out_shardings=(shard, shard))
        params, opt_state = self._init(self.key)
        aux = {}
        self.host_pool = traffic.token_batches(tr, cfg, env.seed)
        self.pool = [trainer.shard_batch({"data": ids, "softmax_label": lab})
                     for ids, lab in self.host_pool]

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
                self.first["grad"] = grad_norms(opt_state, self.key)
            if i == 2:
                self.first["delta"] = delta_norms(params, self.key)
            del outs
        jax.block_until_ready(state)
        self.trainer, self.state = trainer, state
        self.n_done = int(tr["warmup_steps"])

    def window(self, seconds):
        import jax
        trainer, state, pool = self.trainer, self.state, self.pool
        span = self.env.span
        inflight = collections.deque()
        steps = 0
        i = self.n_done
        t0 = time.perf_counter()
        deadline = t0 + seconds
        while time.perf_counter() < deadline:
            with span("step_dispatch"):
                params, opt_state, aux, outs = trainer.step(
                    *state, pool[i % len(pool)])
            state = (params, opt_state, aux)
            inflight.append(outs[0])
            del outs
            i += 1
            steps += 1
            if len(inflight) > 1:
                with span("wait_step"):
                    jax.block_until_ready(inflight.popleft())
        with span("wait_last"):
            jax.block_until_ready((state, list(inflight)))
        elapsed = time.perf_counter() - t0
        inflight.clear()
        self.state = state
        self.n_done = i
        return {"attempted": steps, "failed": 0, "t_first": t0,
                "elapsed_s": elapsed,
                "e2e": {"step_ms": 1e3 * elapsed / max(1, steps)},
                "counters": {"steps": steps, "batch": self.batch,
                             "seq": self.seq}}

    def release(self):
        self.trainer = self.state = self.pool = None

    def reference_readings(self, lowprec=None, fault=None):
        """As ``train_fit``'s: the reference's three steps from the seed.
        The reference keeps the layers stacked; norms are taken per layer
        and unstacked to the checkpoint's leaves."""
        import jax
        import jax.numpy as jnp
        cfg = self.env.config
        n_layer = int(cfg["n_layer"])
        params, _ = self._init(self.key)
        out = {"loss": [], "rank": {n: a.ndim for n, a in params.items()}}
        outer, stacked = self.ref.stack_layers(params, n_layer)
        first = (outer, stacked)
        del params
        m_outer = jax.tree_util.tree_map(jnp.zeros_like, outer)
        m_stacked = jax.tree_util.tree_map(jnp.zeros_like, stacked)
        step = self.ref.make_train_step(cfg, self.lr, self.mu, self.wd,
                                        lowprec=lowprec)

        def leaf_norms(o, s):
            flat = self.ref.unstack_layers(
                {k: jnp.linalg.norm(v.ravel()) for k, v in o.items()},
                {k: jnp.sqrt(jnp.sum(jnp.square(v).reshape(n_layer, -1),
                                     axis=1)) for k, v in s.items()})
            return {n: float(v) for n, v in flat.items()}

        for i in range(3):
            ids, lab = self.host_pool[i % len(self.host_pool)]
            if fault == "half_batch":
                ids, lab = ids[:len(ids) // 2], lab[:len(lab) // 2]
            loss, rows, (g_o, g_s), new_w, new_m = step(
                outer, stacked, m_outer, m_stacked, jnp.asarray(ids),
                jnp.asarray(lab))
            if fault != "unchanged":
                (outer, stacked), (m_outer, m_stacked) = new_w, new_m
            del new_w, new_m
            out["loss"].append(float(loss))
            if i == 0:
                out["row_loss"] = np.asarray(rows)
                out["grad"] = leaf_norms(g_o, g_s)
            del g_o, g_s, rows
        out["delta"] = leaf_norms(
            {k: outer[k] - first[0][k] for k in outer},
            {k: stacked[k] - first[1][k] for k in stacked})
        return out

    def program_readings(self):
        return host_readings(self.losses, self.first)

    def check(self):
        return check_training(self)

    def calibration(self, variants):
        return calibrate_training(self, variants)
