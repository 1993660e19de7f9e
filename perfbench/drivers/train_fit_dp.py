"""Driver ``train_fit_dp``: ``train_fit`` for a cell of several chips whose
global batch is too large for the plain reference on one of them.

The timed path is ``train_fit``'s, unchanged: ``Module.fit`` over one
context per chip.  Only the reference differs: its float32 step at 1,024
images needs 16.8 GB on one chip (temporaries 15.7 GB by the compiler's own
account; the chip refused it, PERF.md section 6, PR 27), so the same
``jax.numpy`` step is given its batch split over the cell's chips by rows
and its weights replicated.  The mathematics is the one-device step's —
BatchNorm's means run over the whole batch, the compiler adds the sums
across chips — and nothing of ``mxnet_tpu`` is in it.
"""
import numpy as np

from .train_fit import Driver as _FitDriver


class Driver(_FitDriver):
    def reference_readings(self, lowprec=None, fault=None, perturb=None):
        """As ``train_fit``'s, the batch rows split over the chips."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        cfg = self.env.config
        mesh = Mesh(np.array(self.env.devices), ("rows",))
        whole, by_rows = NamedSharding(mesh, P()), NamedSharding(mesh,
                                                                 P("rows"))
        params = jax.device_put(self._init(self.key), whole)
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
                params, mom, jax.device_put(x, by_rows),
                jax.device_put(y, by_rows))
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
