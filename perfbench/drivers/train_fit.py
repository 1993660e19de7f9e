"""Driver ``train_fit``: an image classifier trained through ``Module.fit``.

The window drives ``mx.mod.Module(...).fit`` itself with a benchmark-owned
``DataIter`` that cycles a pool of seeded host float32 batches, so every
step pays the host-to-device copy of its batch and the metric's read-back,
as ``fit`` users pay them.  Set-up builds the module, runs a warm-up epoch
through the same ``fit`` (its first three steps are what ``correct``
compares) and hands the same module to the timed epoch, which ends when the
clock runs out.
"""
import importlib
import os
import time

import numpy as np

from .. import common, traffic


class _PoolIter(object):
    """Cycles the pool; an epoch ends after ``limit`` batches or when
    ``deadline`` (a ``perf_counter`` time) has passed."""

    def __init__(self, mx, pool, env, on_stop):
        self._env = env
        self._on_stop = on_stop
        self._batches = [
            mx.io.DataBatch(data=[mx.nd.array(x)], label=[mx.nd.array(y)],
                            pad=0, index=None) for x, y in pool]
        x, y = pool[0]
        self.batch_size = x.shape[0]
        self.provide_data = [("data", tuple(x.shape))]
        self.provide_label = [("softmax_label", tuple(y.shape))]
        self.served = 0
        self.limit = None
        self.deadline = None
        self._seconds = None
        self.t_first = None

    def arm(self, limit=None, seconds=None):
        self.served = 0
        self.limit = limit
        self.deadline = None
        self._seconds = seconds
        self.t_first = None

    def reset(self):
        pass

    def __iter__(self):
        return self

    def __next__(self):
        return self.next()

    def next(self):
        with self._env.span("next_batch"):
            now = time.perf_counter()
            if self.t_first is None:
                self.t_first = now
                if self._seconds is not None:
                    self.deadline = now + self._seconds
            if (self.limit is not None and self.served >= self.limit) or \
                    (self.deadline is not None and now >= self.deadline):
                self._on_stop()
                raise StopIteration
            batch = self._batches[self.served % len(self._batches)]
            self.served += 1
            return batch


class Driver(object):
    def __init__(self, env):
        self.env = env
        self.ref = common.reference_module(env.config)

    # -- set-up ---------------------------------------------------------
    def setup(self):
        import jax
        import jax.numpy as jnp
        import mxnet_tpu as mx
        env, cfg, tr = self.env, self.env.config, self.env.traffic
        opt = cfg["training"]
        os.environ["MXNET_COMPUTE_DTYPE"] = opt["compute_dtype"]
        model = importlib.import_module(cfg["program"]["module"])
        net = model.get_symbol(**cfg["program"]["symbol_args"])
        self.pool = traffic.image_batches(tr, cfg, env.seed)
        contexts = [env.ctx(i) for i in range(len(env.devices))]
        ctx = contexts[0]
        dev = ctx.jax_device
        self.key = common.jax_key(env.seed)
        self._init = jax.jit(
            lambda k: self.ref.init_params(cfg, k),
            out_shardings=jax.sharding.SingleDeviceSharding(dev))
        w0 = self._init(self.key)
        self.mod = mx.mod.Module(net, context=contexts)
        self.lr, self.mu, self.wd = (float(opt["learning_rate"]),
                                     float(opt["momentum"]), float(opt["wd"]))
        self._fit_args = dict(
            eval_metric=mx.metric.create("ce"),
            kvstore=tr.get("kvstore", "local"),
            optimizer="sgd",
            optimizer_params={"learning_rate": self.lr, "momentum": self.mu,
                              "wd": self.wd},
            initializer=mx.init.Xavier(),
            arg_params={n: mx.nd.NDArray(a, ctx=ctx) for n, a in w0.items()},
            batch_end_callback=self._batch_end)
        metric = self._fit_args["eval_metric"]
        if env.tracing:
            inner = metric.update

            def traced_update(labels, preds):
                with env.span("update_metric"):
                    return inner(labels, preds)
            metric.update = traced_update
        self._metric = metric
        self._seen = (0.0, 0)
        self.losses = []
        self.first = {}
        self._window_end = None
        self.it = _PoolIter(mx, self.pool, env, self._stopped)

        lr, wd = self.lr, self.wd
        decayed = self._decayed

        @jax.jit
        def grad_norms(mom, w):
            # the gradient as the optimizer got it, from its state after
            # one step: m1 = -lr * (g + wd * w0)
            return {n: jnp.linalg.norm((-mom[n] / lr
                                        - (wd if decayed(n) else 0.0) * w[n]
                                        ).astype(jnp.float32).ravel())
                    for n in mom}

        @jax.jit
        def delta_norms(w, w_first):
            return {n: jnp.linalg.norm((w[n] - w_first[n]).ravel())
                    for n in w_first}

        self._grad_norms, self._delta_norms = grad_norms, delta_norms
        self._w0 = w0
        self.it.arm(limit=int(tr["warmup_steps"]))
        self.mod.fit(self.it, num_epoch=1, **self._fit_args)
        wrt = set(self._w0)
        got = set(self.mod._exec_group.param_names)
        if got != wrt:
            raise RuntimeError("the program's parameters differ from the "
                               "configuration's: %s" % sorted(got ^ wrt))
        self._w0 = None

    @staticmethod
    def _decayed(name):
        # the program's rule on this path: Module.fit's optimizer is made
        # without wd multipliers, so every learnable leaf is decayed
        return True

    def _batch_end(self, p):
        with self.env.span("batch_end"):
            total, count = self._metric.sum_metric, self._metric.num_inst
            if p.nbatch == 0:
                self._seen = (0.0, 0)
            if len(self.losses) < 3 and p.epoch == 0:
                self.losses.append((total - self._seen[0])
                                   / max(1, count - self._seen[1]))
                self._seen = (total, count)
                exe = self.mod._exec_group.execs[0]
                if p.nbatch == 0:
                    import jax
                    mom = dict(self.mod._fused_holder["states"])
                    if len(self.env.devices) > 1:   # beside the mesh's state
                        self._w0 = {n: jax.device_put(a, mom[n].sharding)
                                    for n, a in self._w0.items()}
                    self.first["grad"] = self._grad_norms(mom, self._w0)
                    probs = np.asarray(self.mod.get_outputs()[0].data,
                                       np.float32)
                    self.first["logp"] = np.log(probs + 1e-30)
                if p.nbatch == 2:
                    w = {n: exe.arg_dict[n].data for n in self._w0}
                    self.first["delta"] = self._delta_norms(w, self._w0)

    def _stopped(self):
        import jax
        outs = self.mod.get_outputs()
        jax.block_until_ready([o.data for o in outs])
        self._window_end = time.perf_counter()

    # -- the timed window --------------------------------------------------
    def window(self, seconds):
        self.it.arm(seconds=seconds)
        self.mod.fit(self.it, begin_epoch=1, num_epoch=2, **self._fit_args)
        steps = self.it.served
        elapsed = self._window_end - self.it.t_first
        exe = self.mod._exec_group.execs[0]
        return {"attempted": steps, "failed": 0, "t_first": self.it.t_first,
                "elapsed_s": elapsed,
                "e2e": {"step_ms": 1e3 * elapsed / max(1, steps)},
                "counters": {"steps": steps, "batch": self.it.batch_size,
                             "fused_steps": exe._n_fused_step}}

    def release(self):
        self.mod = None
        self.it = None
        self._fit_args = None
        os.environ.pop("MXNET_COMPUTE_DTYPE", None)

    # -- correct -----------------------------------------------------------
    def reference_readings(self, lowprec=None, fault=None, perturb=None):
        """The reference's three steps from the same seed: the losses, the
        first step's log-probabilities (every image and class), the first
        gradient's norm and the three-step change's norm per leaf.

        What only ``calibrate.py`` and the tests ask for, to put the result
        in the program's place: ``lowprec`` makes this the control (its
        products in 8-bit floating point); ``fault`` = "half_batch" plants
        that fault (the mean taken over the first half of every batch),
        "unchanged" the step that returns its state as it got it;
        ``perturb`` multiplies every pixel by 1 + perturb * (a seeded
        standard normal), which shows how the numbers are conditioned."""
        import jax
        import jax.numpy as jnp
        cfg = self.env.config
        params = self._init(self.key)
        first = params
        mom = jax.tree_util.tree_map(jnp.zeros_like, params)
        step = self.ref.make_train_step(cfg, self.lr, self.mu, self.wd,
                                        self._decayed, lowprec=lowprec)
        out = {"loss": [], "rank": {n: a.ndim for n, a in params.items()}}
        for i in range(3):
            x, y = self.pool[i % len(self.pool)]
            if fault == "half_batch":
                x, y = x[:len(x) // 2], y[:len(y) // 2]
            if perturb:
                noise = np.random.default_rng([self.env.seed, 77, i])
                x = x * (1.0 + perturb * noise.standard_normal(
                    x.shape, dtype=np.float32))
            loss, logp, grads, new_params, new_mom = step(
                params, mom, jnp.asarray(x), jnp.asarray(y))
            if fault != "unchanged":
                params, mom = new_params, new_mom
            del new_params, new_mom
            out["loss"].append(float(loss))
            if i == 0:
                out["logp"] = np.asarray(logp)
                out["grad"] = {n: float(jnp.linalg.norm(g.ravel()))
                               for n, g in grads.items()}
            del grads, logp
        out["delta"] = {n: float(v) for n, v in
                        self._delta_norms(params, first).items()}
        return out

    def program_readings(self):
        return host_readings(self.losses, self.first)

    def check(self):
        return check_training(self)

    def calibration(self, variants):
        return calibrate_training(self, variants)


def relative_diff(theirs, ours):
    """The norm of ``theirs - ours`` against the norm of ``ours`` about its
    mean, over the rows both have."""
    n = min(len(theirs), len(ours))
    theirs, ours = np.asarray(theirs[:n]), np.asarray(ours[:n])
    return float(np.linalg.norm(theirs - ours)
                 / max(np.linalg.norm(ours - ours.mean()), 1e-30))


def leaf_table(prog, ref):
    """Per leaf, every number that ``training_values`` reduces: what
    ``calibrate.py`` keeps, so that a limit can be looked at leaf by leaf."""
    return {n: [ref["rank"][n], ref["grad"][n], prog["grad"][n],
                ref["delta"][n], prog["delta"][n]] for n in ref["grad"]}


def compare_training(prog, ref, limits):
    """[(name, value, limit)] of a training cell, one row for each number
    that the cell's ``limits`` name (``training_values`` has them all)."""
    values = training_values(prog, ref)
    return [(name, values[name], limit) for name, limit in limits.items()]


def training_values(prog, ref):
    """Every number a training cell can compare, by name.

    ``loss_gap_step<k>``  |program's loss - reference's| / reference's.
    The first step's forward pass before the mean that hides its rounding
    in the loss: the norm of the difference between the program's and the
    reference's, against the norm of the reference's about its mean.
    First-order in rounding, and free of the activation decisions that make
    gradients jump.  Whichever of the two the driver reads:
    ``row_loss_diff``     of each row's loss (token positions);
    ``logprob_diff``      of the log-probabilities, every image and class.
    Per leaf, the gap between the program's norm and the reference's,
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger:
    ``grad_norm_gap``     of the first gradient as the optimizer got it;
    ``delta_norm_gap``    of the parameters' change over three steps; leaves
                          whose reference gradient is nought to rounding are
                          left out (``common.dead_leaves``).
    Each is the worst leaf's; with ``_median`` the median leaf's; with
    ``_matrices`` the worst over the leaves of two or more dimensions, the
    operands of the products and convolutions.
    """
    values = {}
    for i, (a, b) in enumerate(zip(prog["loss"], ref["loss"])):
        values["loss_gap_step%d" % (i + 1)] = abs(a - b) / abs(b)
    for i in range(len(prog["loss"]), 3):
        values["loss_gap_step%d" % (i + 1)] = float("inf")
    for name, key in (("row_loss_diff", "row_loss"),
                      ("logprob_diff", "logp")):
        if key in ref:
            values[name] = relative_diff(prog[key], ref[key])
    per_leaf = {
        "grad_norm_gap": common.leaf_gaps(prog["grad"], ref["grad"]),
        "delta_norm_gap": common.leaf_gaps(
            prog["delta"], ref["delta"],
            skip=common.dead_leaves(ref["grad"]))}
    for name, gaps in per_leaf.items():
        values[name] = max(gaps.values())
        values[name + "_median"] = common.median(gaps.values())
        values[name + "_matrices"] = max(
            v for n, v in gaps.items() if ref["rank"][n] >= 2)
    return values


def check_training(driver):
    """The rows of ``correct`` for a training driver; what is read but not
    compared (PERF.md says why) goes on a log line."""
    prog, ref = driver.program_readings(), driver.reference_readings()
    limits = driver.env.limits
    driver.env.log("read, not compared: " + "  ".join(
        "%s=%.4g" % (n, v) for n, v in training_values(prog, ref).items()
        if n not in limits))
    return compare_training(prog, ref, limits)


def host_readings(losses, first):
    """The program's readings as plain numbers (fetched from the device)."""
    out = {"loss": [float(v) for v in losses],
           "grad": {n: float(v) for n, v in first["grad"].items()},
           "delta": {n: float(v) for n, v in first["delta"].items()}}
    for key in ("row_loss", "logp"):
        if key in first:
            out[key] = np.asarray(first[key], np.float32)
    return out


VARIANTS = {"control": None,            # the configuration's "control"
            "half_batch": {"fault": "half_batch"},
            "unchanged": {"fault": "unchanged"},
            "perturbed": {"perturb": 1e-6}}


def variant_args(driver, name):
    args = VARIANTS[name]
    return args if args is not None else {
        "lowprec": driver.env.config["training"]["control"]}


def calibrate_training(driver, variants):
    """What ``calibrate.py`` records for one seed of a training cell: the
    numbers and the per-leaf table of the program against the reference,
    and of each of ``variants`` (names of ``VARIANTS``: the reference in a
    lower precision, with a fault planted, or perturbed) put in the
    program's place."""
    ref = driver.reference_readings()
    out = {}
    for name in ["prog"] + list(variants):
        t0 = time.perf_counter()
        stand = driver.program_readings() if name == "prog" else \
            driver.reference_readings(**variant_args(driver, name))
        out[name] = {"values": training_values(stand, ref),
                     "loss": stand["loss"], "ref_loss": ref["loss"],
                     "leaves": leaf_table(stand, ref)}
        driver.env.log("%s read in %.1f s" % (name,
                                              time.perf_counter() - t0))
    return out
