"""GPipe-style microbatched pipeline parallelism over a ``pp`` mesh axis.

Beyond-reference scaling: the reference's model parallelism is manual
placement (``ctx_group``/``group2ctx``, graph_executor.cc AssignContext)
with no schedule — stage 1 idles while stage 0 computes.  This module
implements the TPU-native pipeline: a stack of identical blocks is
sharded over ``pp`` (each member holds ``L/K`` consecutive layers'
parameters), the batch is split into microbatches, and activations flow
stage-to-stage through ``lax.ppermute`` inside ``shard_map`` — the
single-program collective schedule XLA compiles to direct ICI sends.
Bubbles are the classic GPipe ``(K-1)/(M+K-1)`` fraction; gradients flow
back through the transposed permutes (jax differentiates the collective)
so fwd+bwd+update stays ONE XLA dispatch, like every other trainer here.

Embedding and head run replicated on every member (cheap vs the block
stack; keeps the schedule single-program).  Composes with a ``dp`` axis:
microbatches carry the dp-sharded batch through the pipeline unchanged.

Layer-map note: this is the jax-native scaling layer (like
ring_attention.py), below the Symbol compatibility surface; the
symbol-level ``ctx_group`` path remains for reference parity.
"""
from __future__ import annotations

import numpy as _np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from jax import shard_map as _shard_map


def shard_map(f, **kw):
    return _shard_map(f, check_vma=False, **kw)


from .mesh import make_mesh  # noqa: F401  (re-exported convenience)
from ..train_step import apply_updates, preprocess_grads

__all__ = ["pipeline_apply", "GPipeTrainer", "build_1f1b_tables",
           "schedule_occupancy"]


def _identity_perm(k):
    return [(i, (i + 1) % k) for i in range(k)]


def _reverse_perm(k):
    return [(i, (i - 1) % k) for i in range(k)]


# ----------------------------------------------------------------------
# 1F1B (one-forward-one-backward) schedule tables
# ----------------------------------------------------------------------
def build_1f1b_tables(k, m):
    """Lock-step 1F1B schedule for ``k`` stages x ``m`` microbatches.

    Returns ``(kind, mb)`` numpy int32 arrays of shape ``[S, k]`` where
    slot table entry ``kind[t, s]`` is 0 idle / 1 forward / 2 backward
    (mid stage) / 3 backward (last stage, initiates the microbatch's
    gradient from its loss) and ``mb[t, s]`` the microbatch index.

    Construction is the standard synchronous 1F1B greedy: each stage
    prefers a backward whose gradient has arrived, else a forward whose
    activation has arrived — capped at ``k - s`` in-flight microbatches
    (the activation stash the analyzer prices).  A payload sent at slot
    ``t`` is usable from slot ``t + 1`` (one ``ppermute`` per slot).
    """
    k, m = int(k), int(m)
    if k < 1 or m < 1:
        raise ValueError("1F1B needs k >= 1 stages and m >= 1 "
                         "microbatches (got k=%d m=%d)" % (k, m))
    f_slot = [[None] * m for _ in range(k)]
    b_slot = [[None] * m for _ in range(k)]
    f_done = [0] * k
    b_done = [0] * k
    kind_rows, mb_rows = [], []
    t = 0
    while min(b_done) < m:
        krow, mrow = [0] * k, [0] * k
        for s in range(k):
            jb, jf = b_done[s], f_done[s]
            can_b = jb < m and (
                (s == k - 1 and f_slot[s][jb] is not None
                 and f_slot[s][jb] < t) or
                (s < k - 1 and b_slot[s + 1][jb] is not None
                 and b_slot[s + 1][jb] < t))
            can_f = jf < m and (f_done[s] - b_done[s]) < (k - s) and (
                s == 0 or (f_slot[s - 1][jf] is not None
                           and f_slot[s - 1][jf] < t))
            if can_b:
                krow[s] = 3 if s == k - 1 else 2
                mrow[s] = jb
                b_slot[s][jb] = t
                b_done[s] += 1
            elif can_f:
                krow[s] = 1
                mrow[s] = jf
                f_slot[s][jf] = t
                f_done[s] += 1
        kind_rows.append(krow)
        mb_rows.append(mrow)
        t += 1
        if t > 4 * (m + k) + 8:  # the greedy above always terminates;
            raise RuntimeError(   # belt-and-braces against table bugs
                "1F1B schedule did not converge for k=%d m=%d" % (k, m))
    return (_np.asarray(kind_rows, dtype=_np.int32),
            _np.asarray(mb_rows, dtype=_np.int32))


def schedule_occupancy(k, m, schedule="1f1b", fwd_time=1.0, bwd_time=2.0):
    """Measured bubble fraction of the lock-step schedule the trainer
    actually executes: slot-occupancy of the compiled program's static
    tables, time-weighted (backward ~ 2x forward by default), with each
    slot's wall time set by its slowest member (the per-slot
    ``ppermute`` is a barrier).  Independent of the analyzer's
    event-driven simulator — the CPU-mesh drill compares the two."""
    if schedule == "1f1b":
        kind, _ = build_1f1b_tables(k, m)
    elif schedule == "gpipe":
        # GPipe: m+k-1 fill/drain fwd ticks then the mirrored bwd ticks
        kind = _np.zeros((2 * (m + k - 1), k), dtype=_np.int32)
        for t in range(m + k - 1):
            for s in range(k):
                if s <= t < s + m:
                    kind[t, s] = 1
                    kind[2 * (m + k - 1) - 1 - t, s] = 3
    else:
        raise ValueError("unknown schedule %r" % (schedule,))
    w = _np.where(kind == 0, 0.0,
                  _np.where(kind == 1, float(fwd_time), float(bwd_time)))
    total = float(w.max(axis=1).sum())
    busy = float(w.sum())
    bubble = 1.0 - busy / (kind.shape[1] * total) if total else 0.0
    return {"slots": int(kind.shape[0]), "busy_time": busy,
            "total_time": total, "bubble_fraction": bubble}


def pipeline_apply(block_fn, local_params, microbatches, *, axis="pp"):
    """Run the microbatch stream through the pipeline.  CALL INSIDE
    shard_map (manual mode) over ``axis``.

    block_fn : (layer_params, h) -> h for ONE block.
    local_params : this member's stacked layer params, leading dim
        L/K (consecutive layers; member i holds layers [i*L/K, ...)).
    microbatches : [M, mb, ...] microbatch stream (same array on every
        member; member 0 is the injector).
    Returns [M, mb, ...] outputs of the LAST stage, valid on every
    member (final ppermute broadcast-rotates the drained outputs; we
    collect on the last member then rotate once to member 0 and rely on
    the caller's psum/where; here we simply return what each member
    drained — the caller masks by axis_index == K-1).
    """
    k = lax.axis_size(axis)
    idx = lax.axis_index(axis)
    m = microbatches.shape[0]
    ticks = m + k - 1

    def local_stack(h):
        def body(carry, layer_params):
            return block_fn(layer_params, carry), None
        out, _ = lax.scan(body, h, local_params)
        return out

    zero = jnp.zeros_like(microbatches[0])

    def tick(carry, t):
        state, outputs = carry
        # stage 0 injects microbatch t (clamped index keeps the gather
        # in-bounds during the drain ticks; the value is masked off)
        inject = lax.dynamic_index_in_dim(
            microbatches, jnp.clip(t, 0, m - 1), 0, keepdims=False)
        h_in = jnp.where(idx == 0, inject, state)
        h_out = local_stack(h_in)
        # last stage banks microbatch t-(K-1) once the fill is done
        out_slot = jnp.clip(t - (k - 1), 0, m - 1)
        bank = jnp.logical_and(idx == k - 1, t >= k - 1)
        outputs = lax.dynamic_update_index_in_dim(
            outputs,
            jnp.where(bank,
                      h_out,
                      lax.dynamic_index_in_dim(outputs, out_slot, 0,
                                               keepdims=False)),
            out_slot, 0)
        # rotate activations to the next stage for the next tick
        state = lax.ppermute(h_out, axis, _identity_perm(k))
        return (state, outputs), None

    outputs0 = jnp.zeros((m,) + zero.shape, zero.dtype)
    (_, outputs), _ = lax.scan(tick, (zero, outputs0),
                               jnp.arange(ticks))
    # make the drained outputs identical on every member: only the last
    # stage banked real values, so a masked psum broadcasts them
    outputs = lax.psum(jnp.where(idx == k - 1, outputs, 0.0), axis)
    return outputs


def _pipeline_1f1b(block_fn, layers_p, stream, batch_mbs, head_loss_fn,
                   head_p, kind_tab, mb_tab, *, axis="pp"):
    """Interleaved 1F1B forward+backward over the microbatch stream.
    CALL INSIDE shard_map over ``axis``.

    Walks the static slot tables from :func:`build_1f1b_tables`: each
    slot a member runs one forward, one backward (recompute-based: the
    stash holds stage INPUTS, ``K - stage_idx`` in flight, and backward
    re-runs the local stack under ``jax.vjp``), or idles; activations
    rotate forward and gradients rotate backward through one
    ``ppermute`` pair per slot.  The last stage turns each drained
    microbatch into its loss and seed gradient immediately (the 1F1B
    point: drain backward work early, cap the stash).

    Returns ``(loss_sum, g_layers, g_head, dstream)`` — per-member
    partials: ``loss_sum``/``g_head`` live on the last member,
    ``dstream`` (gradient w.r.t. the injected stream, ``[M, mb, ...]``)
    on member 0, ``g_layers`` on every member for its own layers.  All
    unscaled: the caller divides by M for the microbatch mean.
    """
    k = lax.axis_size(axis)
    idx = lax.axis_index(axis)
    m = stream.shape[0]
    depth = min(m, k + 1)  # stash ring: <= k in flight, +1 for the
    kind_j = jnp.asarray(kind_tab)  # slot where an arrival overlaps a
    mb_j = jnp.asarray(mb_tab)      # not-yet-drained predecessor

    def local_stack(lp, h):
        def body(carry, layer_params):
            return block_fn(layer_params, carry), None
        out, _ = lax.scan(body, h, lp)
        return out

    zero_mb = jnp.zeros_like(stream[0])
    zeros_layers = jax.tree_util.tree_map(jnp.zeros_like, layers_p)
    zeros_head = jax.tree_util.tree_map(jnp.zeros_like, head_p)

    def slot(carry, t):
        (stash, gstash, recv_h, recv_g, g_layers, g_head, loss_sum,
         dstream) = carry
        my_kind = kind_j[t, idx]
        j = mb_j[t, idx]
        # -- arrivals sent at slot t-1 go straight into the rings -----
        tm1 = jnp.maximum(t - 1, 0)
        pidx, nidx = (idx - 1) % k, (idx + 1) % k
        pk, pj = kind_j[tm1, pidx], mb_j[tm1, pidx]
        store_f = (t > 0) & (idx > 0) & (pk == 1)
        cur = lax.dynamic_index_in_dim(stash, pj % depth, 0,
                                       keepdims=False)
        stash = lax.dynamic_update_index_in_dim(
            stash, jnp.where(store_f, recv_h, cur), pj % depth, 0)
        nk, nj = kind_j[tm1, nidx], mb_j[tm1, nidx]
        store_g = (t > 0) & (idx < k - 1) & (nk >= 2)
        curg = lax.dynamic_index_in_dim(gstash, nj % depth, 0,
                                        keepdims=False)
        gstash = lax.dynamic_update_index_in_dim(
            gstash, jnp.where(store_g, recv_g, curg), nj % depth, 0)
        # -- stage 0 injects (and stashes, for its own backward) ------
        inject = lax.dynamic_index_in_dim(stream, j, 0, keepdims=False)
        cur0 = lax.dynamic_index_in_dim(stash, j % depth, 0,
                                        keepdims=False)
        stash = lax.dynamic_update_index_in_dim(
            stash, jnp.where((idx == 0) & (my_kind == 1), inject, cur0),
            j % depth, 0)
        x_b = lax.dynamic_index_in_dim(stash, j % depth, 0,
                                       keepdims=False)
        x_f = jnp.where(idx == 0, inject, x_b)
        g_in = lax.dynamic_index_in_dim(gstash, j % depth, 0,
                                        keepdims=False)
        batch_mb = jax.tree_util.tree_map(
            lambda a: lax.dynamic_index_in_dim(a, j, 0, keepdims=False),
            batch_mbs)

        def _idle(op):
            return (zero_mb, zero_mb, zeros_layers, zeros_head,
                    jnp.zeros((), stream.dtype))

        def _fwd(op):
            xf, _, _, _ = op
            return (local_stack(layers_p, xf), zero_mb, zeros_layers,
                    zeros_head, jnp.zeros((), stream.dtype))

        def _bwd_mid(op):
            _, xb, gi, _ = op
            _, pull = jax.vjp(
                lambda lp, xx: local_stack(lp, xx), layers_p, xb)
            g_l, g_x = pull(gi)
            return (zero_mb, g_x, g_l, zeros_head,
                    jnp.zeros((), stream.dtype))

        def _bwd_last(op):
            _, xb, _, bmb = op
            def f(lp, hp, xx):
                return head_loss_fn(hp, local_stack(lp, xx), bmb)
            loss_j, pull = jax.vjp(f, layers_p, head_p, xb)
            g_l, g_h, g_x = pull(jnp.ones_like(loss_j))
            return (zero_mb, g_x, g_l, g_h,
                    loss_j.astype(stream.dtype))

        h_send, g_send, g_l_d, g_h_d, loss_d = lax.switch(
            my_kind, [_idle, _fwd, _bwd_mid, _bwd_last],
            (x_f, x_b, g_in, batch_mb))
        g_layers = jax.tree_util.tree_map(jnp.add, g_layers, g_l_d)
        g_head = jax.tree_util.tree_map(jnp.add, g_head, g_h_d)
        loss_sum = loss_sum + loss_d
        # member 0's backward output is dLoss/d stream[j]
        curd = lax.dynamic_index_in_dim(dstream, j, 0, keepdims=False)
        dstream = lax.dynamic_update_index_in_dim(
            dstream, jnp.where((idx == 0) & (my_kind == 2), g_send,
                               curd), j, 0)
        recv_h = lax.ppermute(h_send, axis, _identity_perm(k))
        recv_g = lax.ppermute(g_send, axis, _reverse_perm(k))
        return (stash, gstash, recv_h, recv_g, g_layers, g_head,
                loss_sum, dstream), None

    init = (jnp.zeros((depth,) + zero_mb.shape, zero_mb.dtype),
            jnp.zeros((depth,) + zero_mb.shape, zero_mb.dtype),
            zero_mb, zero_mb, zeros_layers, zeros_head,
            jnp.zeros((), stream.dtype),
            jnp.zeros((m,) + zero_mb.shape, zero_mb.dtype))
    (_, _, _, _, g_layers, g_head, loss_sum, dstream), _ = lax.scan(
        slot, init, jnp.arange(kind_tab.shape[0]))
    return loss_sum, g_layers, g_head, dstream


class GPipeTrainer:
    """Microbatched pipeline trainer for repeated-block models.

    Parameters
    ----------
    embed_fn / block_fn / head_loss_fn : pure functions
        ``embed_fn(embed_params, batch) -> h`` (token/patch embedding),
        ``block_fn(layer_params, h) -> h`` (ONE block; applied L times
        from stacked params), ``head_loss_fn(head_params, h, batch) ->
        scalar loss`` (mean over the microbatch).
    params : dict with keys ``embed``, ``layers`` (stacked [L, ...]
        pytree), ``head``.
    mesh : mesh with a ``pp`` axis (optionally ``dp``).
    num_microbatches : M; the global batch must divide into M * dp.
    optimizer : mxnet_tpu optimizer (its jitted ``update_fn`` is reused).

    One ``step()`` = fwd + bwd + update in a single XLA dispatch, with
    the pipeline schedule inside.
    """

    def __init__(self, embed_fn, block_fn, head_loss_fn, params, mesh,
                 optimizer, num_microbatches=4, schedule="gpipe"):
        if "pp" not in mesh.axis_names:
            raise ValueError("GPipeTrainer needs a 'pp' mesh axis")
        if schedule not in ("gpipe", "1f1b"):
            raise ValueError("schedule must be 'gpipe' or '1f1b', got %r"
                             % (schedule,))
        if schedule == "1f1b" and mesh.shape["pp"] < 2:
            raise ValueError("1f1b schedule needs pp >= 2")
        self.schedule = schedule
        self.mesh = mesh
        self.pp = mesh.shape["pp"]
        self.dp = mesh.shape.get("dp", 1)
        self.m = int(num_microbatches)
        self.optimizer = optimizer
        n_layers = jax.tree_util.tree_leaves(params["layers"])[0].shape[0]
        if n_layers % self.pp:
            raise ValueError("pp (%d) must divide layers (%d)"
                             % (self.pp, n_layers))
        self.n_layers = n_layers

        layer_spec = P("pp")     # shard the stacked-layer dim
        self._shardings = {
            "embed": jax.tree_util.tree_map(
                lambda _: NamedSharding(mesh, P()), params["embed"]),
            "layers": jax.tree_util.tree_map(
                lambda _: NamedSharding(mesh, layer_spec),
                params["layers"]),
            "head": jax.tree_util.tree_map(
                lambda _: NamedSharding(mesh, P()), params["head"]),
        }
        self.params = {
            k: jax.tree_util.tree_map(
                lambda a, s: jax.device_put(jnp.asarray(a), s),
                params[k], self._shardings[k])
            for k in ("embed", "layers", "head")
        }
        # optimizer state per param LEAF (create_state_arrays may return
        # None, an array, or a pytree e.g. Adam's (m, v)); each state
        # array inherits its param's sharding (pp-sharded layer stacks
        # keep their momentum pp-sharded)
        def _leaf_state(p):
            s = optimizer.create_state_arrays(p.shape, p.dtype)
            if s is None:
                return None
            return jax.tree_util.tree_map(
                lambda a: jax.device_put(jnp.asarray(a), p.sharding), s)
        self.opt_state = {
            k: [_leaf_state(p)
                for p in jax.tree_util.tree_leaves(self.params[k])]
            for k in self.params
        }
        self._embed_fn = embed_fn
        self._block_fn = block_fn
        self._head_loss_fn = head_loss_fn
        self._jit_step = None
        self.num_update = 0

    # -- the fused pipelined step --------------------------------------
    def _build(self):
        if self.schedule == "1f1b":
            return self._build_1f1b()
        mesh, m, pp, dp = self.mesh, self.m, self.pp, self.dp
        embed_fn, block_fn = self._embed_fn, self._block_fn
        head_loss_fn = self._head_loss_fn
        has_dp = "dp" in mesh.axis_names and dp > 1
        batch_axes = ("dp",) if has_dp else ()

        def loss_fn(params, batch):
            # manual-mode SPMD: inside, arrays are the per-member shards
            def inner(embed_p, layers_p, head_p, local_batch):
                h = embed_fn(embed_p, local_batch)
                mb = h.shape[0] // m
                stream = h.reshape((m, mb) + h.shape[1:])
                outs = pipeline_apply(block_fn, layers_p, stream)
                h_out = outs.reshape(h.shape)
                loss = head_loss_fn(head_p, h_out, local_batch)
                if has_dp:
                    loss = lax.pmean(loss, "dp")
                return loss

            in_specs = (jax.tree_util.tree_map(lambda _: P(),
                                               params["embed"]),
                        jax.tree_util.tree_map(lambda _: P("pp"),
                                               params["layers"]),
                        jax.tree_util.tree_map(lambda _: P(),
                                               params["head"]),
                        jax.tree_util.tree_map(
                            lambda _: P(*batch_axes), batch))
            fn = shard_map(inner, mesh=mesh, in_specs=in_specs,
                           out_specs=P())
            return fn(params["embed"], params["layers"], params["head"],
                      batch)

        return self._jit_update(jax.value_and_grad(loss_fn))

    def _jit_update(self, loss_and_grads):
        """The jitted step of either schedule: ``loss_and_grads(params,
        batch)``, then the optimizer's update over each group's leaves,
        params and optimizer state donated."""
        optimizer = self.optimizer

        def step(params, opt_state, batch, lr, wd, num_update):
            loss, grads = loss_and_grads(params, batch)
            new_params, new_state = {}, {}
            for k in params:
                flat_p, treedef = jax.tree_util.tree_flatten(params[k])
                flat_g = dict(enumerate(jax.tree_util.tree_leaves(grads[k])))
                new_p, new_s = apply_updates(
                    optimizer, dict(enumerate(flat_p)),
                    preprocess_grads(optimizer, flat_g),
                    dict(enumerate(opt_state[k])), lr, wd, num_update)
                new_params[k] = jax.tree_util.tree_unflatten(
                    treedef, [new_p[i] for i in range(len(flat_p))])
                new_state[k] = [new_s.get(i) for i in range(len(flat_p))]
            return new_params, new_state, loss

        return jax.jit(step, donate_argnums=(0, 1))

    def _build_1f1b(self):
        """The 1F1B step: the GPipe path's signature and update
        (:meth:`_jit_update`), but fwd+bwd run interleaved per microbatch
        through :func:`_pipeline_1f1b` (manual vjp schedule) instead of
        ``jax.value_and_grad`` over the fwd-only pipeline.  The loss is
        the mean of per-microbatch head losses, accumulated in
        microbatch order — bit-identical to
        :meth:`sequential_loss_microbatched`."""
        mesh, m, pp, dp = self.mesh, self.m, self.pp, self.dp
        embed_fn, block_fn = self._embed_fn, self._block_fn
        head_loss_fn = self._head_loss_fn
        has_dp = "dp" in mesh.axis_names and dp > 1
        batch_axes = ("dp",) if has_dp else ()
        kind_tab, mb_tab = build_1f1b_tables(pp, m)

        def loss_and_grads(params, batch):
            def inner(embed_p, layers_p, head_p, local_batch):
                k = lax.axis_size("pp")
                idx = lax.axis_index("pp")
                h = embed_fn(embed_p, local_batch)
                mb = h.shape[0] // m
                stream = h.reshape((m, mb) + h.shape[1:])
                batch_mbs = jax.tree_util.tree_map(
                    lambda a: a.reshape((m, a.shape[0] // m)
                                        + a.shape[1:]), local_batch)
                loss_sum, g_layers, g_head, dstream = _pipeline_1f1b(
                    block_fn, layers_p, stream, batch_mbs, head_loss_fn,
                    head_p, kind_tab, mb_tab)
                # broadcast the single-member partials (masked psums add
                # exact zeros from the other members)
                loss = lax.psum(jnp.where(idx == k - 1, loss_sum, 0.0),
                                "pp") / m
                g_head = jax.tree_util.tree_map(
                    lambda g: lax.psum(
                        jnp.where(idx == k - 1, g, 0.0), "pp") / m,
                    g_head)
                dstream = lax.psum(jnp.where(idx == 0, dstream, 0.0),
                                   "pp")
                g_layers = jax.tree_util.tree_map(
                    lambda g: g / m, g_layers)
                # embed backward at the full local batch
                _, pull_e = jax.vjp(
                    lambda ep: embed_fn(ep, local_batch), embed_p)
                (g_embed,) = pull_e(dstream.reshape(h.shape) / m)
                grads = {"embed": g_embed, "layers": g_layers,
                         "head": g_head}
                if has_dp:
                    loss = lax.pmean(loss, "dp")
                    grads = jax.tree_util.tree_map(
                        lambda g: lax.pmean(g, "dp"), grads)
                return loss, grads

            in_specs = (jax.tree_util.tree_map(lambda _: P(),
                                               params["embed"]),
                        jax.tree_util.tree_map(lambda _: P("pp"),
                                               params["layers"]),
                        jax.tree_util.tree_map(lambda _: P(),
                                               params["head"]),
                        jax.tree_util.tree_map(
                            lambda _: P(*batch_axes), batch))
            out_specs = (P(), {"embed": jax.tree_util.tree_map(
                                   lambda _: P(), params["embed"]),
                               "layers": jax.tree_util.tree_map(
                                   lambda _: P("pp"), params["layers"]),
                               "head": jax.tree_util.tree_map(
                                   lambda _: P(), params["head"])})
            fn = shard_map(inner, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs)
            return fn(params["embed"], params["layers"], params["head"],
                      batch)

        return self._jit_update(loss_and_grads)

    def schedule_occupancy(self):
        """Measured schedule occupancy (bubble fraction etc.) of the
        lock-step tables this trainer's compiled step executes."""
        return schedule_occupancy(self.pp, self.m, self.schedule)

    def step(self, batch):
        """One pipelined train step on a host batch dict; returns loss."""
        rows = jax.tree_util.tree_leaves(batch)[0].shape[0]
        if rows % (self.m * self.dp):
            raise ValueError(
                "batch rows (%d) must divide into num_microbatches (%d) "
                "* dp (%d)" % (rows, self.m, self.dp))
        if self._jit_step is None:
            self._jit_step = self._build()
            try:  # one schedule record per run, for mxtop/parse_log
                from ..observability import events as _events
                if _events.enabled():
                    occ = self.schedule_occupancy()
                    _events.emit("schedule", schedule=self.schedule,
                                 stages=self.pp, microbatches=self.m,
                                 bubble_fraction=round(
                                     occ["bubble_fraction"], 4))
            except Exception:
                pass
        self.num_update += 1
        opt = self.optimizer
        lr = (opt.lr_scheduler(self.num_update)
              if opt.lr_scheduler is not None else opt.lr)
        batch_dev = jax.tree_util.tree_map(
            lambda a: jax.device_put(
                jnp.asarray(a),
                NamedSharding(self.mesh,
                              P("dp") if "dp" in self.mesh.axis_names
                              and self.dp > 1 else P())), batch)
        self.params, self.opt_state, loss = self._jit_step(
            self.params, self.opt_state, batch_dev, _np.float32(lr),
            _np.float32(opt.wd), _np.int32(self.num_update))
        return float(loss)

    # -- checkpoint / resume (same orbax layout as ShardedTrainer) ----
    def save_checkpoint(self, path):
        """Write params + optimizer state + update counter, sharded:
        each host writes only its own shards (the pp-sharded layer
        stacks stay distributed end-to-end)."""
        from .ckpt import ocp_save
        return ocp_save(path, {"params": self.params,
                               "opt_state": self.opt_state},
                        self.num_update)

    def load_checkpoint(self, path):
        """Restore in place with this trainer's shardings; the update
        counter resumes (lr schedules / Adam bias correction continue
        where they stopped)."""
        from .ckpt import abstract_like, ocp_restore
        restored, step = ocp_restore(
            path, {"params": abstract_like(self.params),
                   "opt_state": abstract_like(self.opt_state)})
        self.params = restored["params"]
        self.opt_state = restored["opt_state"]
        self.num_update = step
        return self

    # -- symbol-language entry ----------------------------------------
    @classmethod
    def from_block_symbol(cls, block_sym, *, n_layers, mesh, optimizer,
                          embed_fn, head_loss_fn, embed_params,
                          head_params, input_shape, data_name="data",
                          initializer=None, num_microbatches=4,
                          seed=0, schedule="gpipe"):
        """Build the pipeline from ONE block defined in the Symbol
        language: the block symbol (e.g. FC->Activation residual cell,
        or a transformer block built from mx.sym ops) is traced into
        ``block_fn`` and replicated ``n_layers`` times with
        independently-initialized stacked parameters.

        Constraints (raise otherwise): the block must be aux-free (no
        BatchNorm moving stats — pipeline microbatches would race the
        update) and rng-free (no Dropout), and must map ``data_name``
        -> single output of the same shape (a residual-style cell).
        ``input_shape`` is the per-microbatch activation shape
        EXCLUDING the leading batch dim.
        """
        from ..executor import _build_program
        from .. import initializer as init_mod

        if block_sym.list_auxiliary_states():
            raise ValueError("pipeline block must be aux-free (found %s)"
                             % block_sym.list_auxiliary_states())
        program = _build_program(block_sym, {})
        if program.needs_rng:
            raise ValueError("pipeline block must be rng-free (Dropout "
                             "etc. not supported in the microbatch "
                             "schedule)")
        args = block_sym.list_arguments()
        if data_name not in args:
            raise ValueError("block symbol has no input %r" % data_name)
        param_names = [n for n in args if n != data_name]

        if not param_names:
            raise ValueError("pipeline block has no parameters: nothing "
                             "to stack over %d layers" % n_layers)

        # shapes at a probe batch of 1 (batch dim drops out of params)
        arg_shapes, out_shapes, _aux = block_sym.infer_shape(
            **{data_name: (1,) + tuple(input_shape)})
        if arg_shapes is None:
            raise ValueError(
                "pipeline block shapes are underdetermined from input "
                "%s: every parameter shape must follow from %r"
                % (tuple(input_shape), data_name))
        if len(out_shapes) != 1 or tuple(out_shapes[0][1:]) != tuple(
                input_shape):
            raise ValueError(
                "pipeline block must map %s -> one output of the same "
                "shape (got %s from %s)" % (input_shape, out_shapes,
                                            input_shape))
        shapes = dict(zip(args, arg_shapes))

        from .. import ndarray as nd_mod
        from .. import random as random_mod
        init = initializer or init_mod.Xavier()
        # a local PRNG stream: initializers draw via random.next_key(),
        # so seed-then-restore keeps the caller's global mx.random state
        # untouched by construction
        saved_key = random_mod._get_key()
        random_mod.seed(seed)
        try:
            stacked = {}
            for n in param_names:
                layers = []
                for _li in range(n_layers):
                    arr = nd_mod.zeros(shapes[n])
                    init(n, arr)
                    layers.append(arr.asnumpy())
                stacked[n] = _np.stack(layers)
        finally:
            random_mod._state.key = saved_key

        def block_fn(lp, h):
            merged = dict(lp)
            merged[data_name] = h
            outs, _aux_out = program.trace(merged, {},
                                           jax.random.PRNGKey(0), True)
            return outs[0]

        params = {"embed": embed_params, "layers": stacked,
                  "head": head_params}
        return cls(embed_fn, block_fn, head_loss_fn, params, mesh,
                   optimizer, num_microbatches=num_microbatches,
                   schedule=schedule)

    # reference (unpipelined) loss for testing/validation
    def sequential_loss(self, batch):
        params_host = jax.tree_util.tree_map(_np.asarray, self.params)

        def f(params):
            h = self._embed_fn(params["embed"], batch)

            def body(carry, layer_params):
                return self._block_fn(layer_params, carry), None
            h, _ = lax.scan(body, h, params["layers"])
            return self._head_loss_fn(params["head"], h, batch)
        return float(f(params_host))

    def sequential_loss_microbatched(self, batch):
        """Unpipelined reference for the 1F1B loss: full batch through
        the layer stack on one device, then the mean of per-microbatch
        head losses accumulated in microbatch order — the exact float
        summation the 1F1B schedule performs, so the two agree
        bit-for-bit."""
        params_host = jax.tree_util.tree_map(_np.asarray, self.params)
        m = self.m

        def f(params):
            h = self._embed_fn(params["embed"], batch)

            def body(carry, layer_params):
                return self._block_fn(layer_params, carry), None
            h, _ = lax.scan(body, h, params["layers"])
            hm = h.reshape((m, h.shape[0] // m) + h.shape[1:])
            batch_mbs = jax.tree_util.tree_map(
                lambda a: _np.reshape(
                    _np.asarray(a),
                    (m, a.shape[0] // m) + tuple(a.shape[1:])), batch)
            loss_sum = jnp.zeros((), hm.dtype)
            for j in range(m):
                bmb = jax.tree_util.tree_map(lambda a: a[j], batch_mbs)
                loss_sum = loss_sum + self._head_loss_fn(
                    params["head"], hm[j], bmb).astype(hm.dtype)
            return loss_sum / m
        return float(f(params_host))
