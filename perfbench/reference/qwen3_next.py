"""Plain reference of the decoder in ``configs/qwen3-next-80b-a3b.json``.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernels, no sorting, no
grouped products, no chunked scan — the gated delta rule is the per-token
recurrence, keys and values are repeated over a group of query heads.
Independent of ``mxnet_tpu``: it imports nothing of the program and is handed
only what the benchmark made from the seed.

Architecture (``config.json`` of Qwen/Qwen3-Next-80B-A3B-Instruct fixes every
width; what it does not fix is listed in the configuration file under
``assumed``, from the ``qwen3_next`` model of the ``transformers`` library,
which the config's ``model_type`` names).  x is a (B, S, E) stream,
RMSNorm(x) = x / sqrt(mean(x²) + eps) · g with g seeded 1 (the family writes
(1 + w), w seeded 0: the same function and gradient at ``wd`` 0), no bias in
any product.

- Layer i (0-based): x = x + Mixer(RMSNorm₁(x)); x = x + MoE(RMSNorm₂(x)).
  The mixer is gated attention where (i + 1) % ``full_attention_interval``
  = 0, else the gated delta rule.
- Gated delta rule (H_k = ``linear_num_key_heads`` of d_k, H_v =
  ``linear_num_value_heads`` of d_v, r = H_v / H_k): [q, k, v, z] = u W_qkvz
  laid out a key head as q (d_k), k (d_k), v (r·d_v), z (r·d_v); [b, a] =
  u W_ba a key head as b (r), a (r).  [q ‖ k ‖ v] through a depthwise causal
  convolution of ``linear_conv_kernel_dim`` taps (left-padded with zeros, no
  bias; the last tap reads the current token), then SiLU.  q and k
  L2-normalised a head, x · rsqrt(Σx² + 1e-6), q times d_k^−½; value head j
  reads key head j // r.  β = sigmoid(b), g = −exp(A_log) · softplus(a +
  dt_bias) a value head.  Per value head, S (d_k × d_v) from 0:
      S ← exp(g_t) S;  δ_t = β_t (v_t − Sᵀ k_t);  S ← S + k_t δ_tᵀ;
      o_t = Sᵀ q_t
  y = (RMSNorm(o_t) ⊙ silu(z_t)) W_out, the norm over a head's d_v channels
  with one gain vector for all heads.
- Gated attention (H query heads on H_kv key/value heads of d): [q_h ‖
  gate_h] = u W_q a head; k, v = u W_k, u W_v; RMSNorm over d on every q and
  k head (a gain vector each); rotary, half-split, on the first
  ``partial_rotary_factor``·d channels, theta ``rope_theta``; causal
  softmax(q kᵀ / √d) v, query head h on key/value head h // (H / H_kv);
  y = (o ⊙ sigmoid(gate)) W_o.
- MoE(u): p = softmax(u W_r) over all ``deployment.router_width`` experts;
  the ``num_experts_per_tok`` largest; w = p / Σ_chosen p; Σ w_e E_e(u) over
  the chosen experts that are among the ``num_experts`` held here (from
  ``deployment.first_expert``) — the other experts' part is another chip's —
  plus sigmoid(u w_s) · E_shared(u).  E(u) = (silu(u W_g) ⊙ u W_u) W_d.
  Experts are a plain loop (a scan) over those held, each applied to every
  token and masked by its weight.
- Head: RMSNorm -> ``lm_head_weight`` (untied); mean token cross-entropy.

Memory: every layer is rematerialised (``jax.checkpoint``); the recurrence
runs by blocks of ``RULE_BLOCK`` tokens, each rematerialised in the backward
pass (8,192 states of 32 × 64 KB are never whole: one state a block is
kept); attention runs by query blocks and the head by blocks of rows.

Weight names and layouts are the program's checkpoint's (``*_weight`` is
``(out_features, in_features)``, expert stacks lead with the expert), listed
by :func:`param_shapes`.
"""
import math

import jax
import jax.numpy as jnp

from .lowprec import fake_quant, grad_quant

Q_BLOCK = 256       # queries scored at a time
HEAD_ROWS = 1024    # rows of logits made at a time
RULE_BLOCK = 64     # tokens of the recurrence rematerialised together

#: what a test or a planted fault can leave out of the delta-rule layer
RULE_STEPS = ("delta", "decay", "conv", "qk_norm", "out_gate")


def _dims(cfg):
    return dict(
        e=int(cfg["hidden_size"]), v=int(cfg["vocab_size"]),
        heads=int(cfg["num_attention_heads"]),
        kv=int(cfg["num_key_value_heads"]), d=int(cfg["head_dim"]),
        hk=int(cfg["linear_num_key_heads"]),
        hv=int(cfg["linear_num_value_heads"]),
        dk=int(cfg["linear_key_head_dim"]),
        dv=int(cfg["linear_value_head_dim"]),
        taps=int(cfg["linear_conv_kernel_dim"]),
        moe=int(cfg["moe_intermediate_size"]),
        shared=int(cfg["shared_expert_intermediate_size"]),
        held=int(cfg["num_experts"]),
        width=int(cfg["deployment"]["router_width"]),
        first=int(cfg["deployment"]["first_expert"]),
        top_k=int(cfg["num_experts_per_tok"]),
        interval=int(cfg["full_attention_interval"]),
        layers=int(cfg["num_hidden_layers"]))


def layer_kinds(cfg):
    d = _dims(cfg)
    return ["full_attention" if (i + 1) % d["interval"] == 0
            else "linear_attention" for i in range(d["layers"])]


def _layer_shapes(d, kind):
    e = d["e"]
    shapes = {
        "norm1_gamma": (e,), "norm2_gamma": (e,),
        "moe_router_weight": (d["width"], e),
        "moe_expert_gate_weight": (d["held"], d["moe"], e),
        "moe_expert_up_weight": (d["held"], d["moe"], e),
        "moe_expert_down_weight": (d["held"], e, d["moe"]),
        "moe_shared_gate_weight": (d["shared"], e),
        "moe_shared_up_weight": (d["shared"], e),
        "moe_shared_down_weight": (e, d["shared"]),
        "moe_shared_score_weight": (1, e)}
    if kind == "full_attention":
        hq, hk, w = d["heads"], d["kv"], d["d"]
        shapes.update({
            "att_q_weight": (2 * hq * w, e), "att_k_weight": (hk * w, e),
            "att_v_weight": (hk * w, e), "att_q_norm_gamma": (w,),
            "att_k_norm_gamma": (w,), "att_out_weight": (e, hq * w)})
    else:
        keys, values = d["hk"] * d["dk"], d["hv"] * d["dv"]
        shapes.update({
            "gdn_in_proj_qkvz_weight": (2 * keys + 2 * values, e),
            "gdn_in_proj_ba_weight": (2 * d["hv"], e),
            "gdn_conv_weight": (d["taps"], 2 * keys + values),
            "gdn_A_log": (d["hv"],), "gdn_dt_bias": (d["hv"],),
            "gdn_norm_gamma": (d["dv"],),
            "gdn_out_weight": (e, values)})
    return shapes


def param_shapes(cfg, positions=None):
    """{name: shape} of every weight, in the checkpoint's names
    (``positions`` is taken and ignored: rotary needs no table)."""
    d = _dims(cfg)
    shapes = {"tok_embed_weight": (d["v"], d["e"]),
              "final_norm_gamma": (d["e"],),
              "lm_head_weight": (d["v"], d["e"])}
    for i, kind in enumerate(layer_kinds(cfg)):
        for leaf, shape in _layer_shapes(d, kind).items():
            shapes["layer%d_%s" % (i, leaf)] = shape
    return shapes


def init_params(cfg, key, positions=None, dtype=jnp.float32):
    """Seeded weights, made on the device in one traced call: matrices, the
    convolution and the embedding normal(0, ``initializer_range``); norm
    gains 1; ``A_log`` = log U(0, 16); ``dt_bias`` as the configuration's
    ``dt_bias_init`` says: a number (the library's 1), or ``{"dt_min",
    "dt_max"}``: the inverse softplus of a step log-uniform between them
    (Mamba-2's seed, arXiv:2405.21060)."""
    shapes = param_shapes(cfg)
    std = float(cfg["initializer_range"])
    dt_init = cfg.get("dt_bias_init", 1.0)
    keys = jax.random.split(key, len(shapes))
    out = {}
    for k, (name, shape) in zip(keys, sorted(shapes.items())):
        if name.endswith("_gamma"):
            w = jnp.ones(shape, jnp.float32)
        elif name.endswith("_A_log"):
            w = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1e-4, 16.0))
        elif name.endswith("_dt_bias"):
            if isinstance(dt_init, dict):
                lo, hi = (math.log(float(dt_init[n]))
                          for n in ("dt_min", "dt_max"))
                dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32, lo, hi))
                w = dt + jnp.log(-jnp.expm1(-dt))
            else:
                w = jnp.full(shape, float(dt_init), jnp.float32)
        else:
            w = std * jax.random.normal(k, shape, jnp.float32)
        out[name] = w.astype(dtype)
    return out


def _rms_norm(x, gamma, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * gamma


def _linear(x, w, lowprec):
    y = fake_quant(x, lowprec) @ fake_quant(w, lowprec).T
    return grad_quant(y, lowprec) if lowprec else y


def _previous(z, n=1):
    """z (B, S, ...) as ``n`` tokens earlier, zeros before the first."""
    if n == 0:
        return z
    return jnp.concatenate([jnp.zeros_like(z[:, :n]), z[:, :-n]], axis=1)


def rotary_half(x, theta, rotary_dim):
    """x (B, H, S, d): the first ``rotary_dim`` channels turned, channel i
    with i + rotary_dim/2, by position · theta^(−2i/rotary_dim)."""
    s, half = x.shape[-2], rotary_dim // 2
    inv_freq = theta ** (-jnp.arange(0, rotary_dim, 2, dtype=jnp.float32)
                         / rotary_dim)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half], x[..., half:rotary_dim]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., rotary_dim:]], axis=-1)


def _attend(q, k, v, lowprec):
    """Causal softmax(q kᵀ / √d) v; q (B, H, S, d), k and v (B, H_kv, S, d)
    repeated over each group of H / H_kv query heads; by query blocks, one
    shape for all of them, rematerialised in the backward pass."""
    s, scale = q.shape[-2], 1.0 / jnp.sqrt(float(q.shape[-1]))
    group = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(t, group, axis=1) for t in (k, v))
    q, k, v = (fake_quant(t, lowprec) for t in (q, k, v))
    step = min(Q_BLOCK, s)

    @jax.checkpoint
    def block(q_b, start):
        scores = jnp.einsum("bhqd,bhkd->bhqk", q_b, k) * scale
        if lowprec:
            scores = grad_quant(scores, lowprec)
        qpos = start + jnp.arange(step)[:, None]
        kpos = jnp.arange(s)[None, :]
        scores = jnp.where(qpos >= kpos, scores, -jnp.inf)
        probs = fake_quant(jax.nn.softmax(scores, axis=-1), lowprec)
        out = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
        return grad_quant(out, lowprec) if lowprec else out

    blocks = q.reshape(q.shape[:2] + (s // step, step, q.shape[-1]))
    outs = jax.lax.map(lambda a: block(*a),
                       (jnp.moveaxis(blocks, 2, 0),
                        jnp.arange(0, s, step)))
    return jnp.moveaxis(outs, 0, 2).reshape(q.shape[:-1] + (v.shape[-1],))


def gated_attention(u, p, cfg, lowprec=None):
    """(B, S, E) -> (B, S, E); ``p`` holds the ``att_*`` leaves."""
    d = _dims(cfg)
    hq, hk, w = d["heads"], d["kv"], d["d"]
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    rot = int(round(float(cfg["partial_rotary_factor"]) * w))
    b, s, _ = u.shape
    qg = _linear(u, p["att_q_weight"], lowprec).reshape(b, s, hq, 2 * w)
    q, gate = qg[..., :w], qg[..., w:]
    k = _linear(u, p["att_k_weight"], lowprec).reshape(b, s, hk, w)
    v = _linear(u, p["att_v_weight"], lowprec).reshape(b, s, hk, w)
    q = _rms_norm(q, p["att_q_norm_gamma"], eps).transpose(0, 2, 1, 3)
    k = _rms_norm(k, p["att_k_norm_gamma"], eps).transpose(0, 2, 1, 3)
    o = _attend(rotary_half(q, theta, rot), rotary_half(k, theta, rot),
                v.transpose(0, 2, 1, 3), lowprec)
    o = o.transpose(0, 2, 1, 3) * jax.nn.sigmoid(gate)
    return _linear(o.reshape(b, s, hq * w), p["att_out_weight"], lowprec)


def delta_rule(q, k, v, g, beta, without=()):
    """The recurrence, a token at a time, in float32 with no matrix unit in
    it (products are elementwise, sums exact to float32): q, k (B, S, H,
    d_k), v (B, S, H, d_v), g and beta (B, S, H) -> o (B, S, H, d_v).
    Blocks of ``RULE_BLOCK`` tokens are rematerialised in the backward pass.
    ``without`` may name "delta" (δ_t = β_t v_t: the state's answer to k_t
    is not taken off — the planted fault) and "decay" (g = 0)."""
    b, s, h, dk = q.shape
    block = min(RULE_BLOCK, s)
    if "decay" in without:
        g = jnp.zeros_like(g)

    def token(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = state * jnp.exp(g_t)[..., None, None]
        mem = 0.0 if "delta" in without else \
            jnp.sum(state * k_t[..., :, None], axis=-2)
        delta = (v_t - mem) * b_t[..., None]
        state = state + k_t[..., :, None] * delta[..., None, :]
        return state, jnp.sum(state * q_t[..., :, None], axis=-2)

    @jax.checkpoint
    def tokens(state, xs):
        return jax.lax.scan(token, state, xs)

    def blocks(t):      # (B, S, ...) -> (S / block, block, B, ...)
        t = jnp.moveaxis(t, 1, 0)
        return t.reshape((s // block, block) + t.shape[1:])

    state = jnp.zeros((b, h, dk, v.shape[-1]), v.dtype)
    _, o = jax.lax.scan(tokens, state, tuple(
        blocks(t) for t in (q, k, v, g, beta)))
    return jnp.moveaxis(o.reshape((s,) + o.shape[2:]), 0, 1)


def gated_delta_net(u, p, cfg, lowprec=None, without=()):
    """(B, S, E) -> (B, S, E); ``p`` holds the ``gdn_*`` leaves.  ``without``
    names steps of ``RULE_STEPS`` to leave out (tests: each must matter;
    "delta" is the benchmark's planted fault)."""
    d = _dims(cfg)
    hk, hv, dk, dv = d["hk"], d["hv"], d["dk"], d["dv"]
    r = hv // hk
    b, s, _ = u.shape
    qkvz = _linear(u, p["gdn_in_proj_qkvz_weight"], lowprec) \
        .reshape(b, s, hk, 2 * dk + 2 * r * dv)
    ba = _linear(u, p["gdn_in_proj_ba_weight"], lowprec) \
        .reshape(b, s, hk, 2 * r)
    q, k = qkvz[..., :dk], qkvz[..., dk:2 * dk]
    v = qkvz[..., 2 * dk:2 * dk + r * dv]
    z = qkvz[..., 2 * dk + r * dv:].reshape(b, s, hv, dv)
    mixed = jnp.concatenate([t.reshape(b, s, -1) for t in (q, k, v)], -1)
    if "conv" not in without:
        taps = p["gdn_conv_weight"]
        mixed = sum(_previous(mixed, d["taps"] - 1 - j) * taps[j]
                    for j in range(d["taps"]))
    mixed = jax.nn.silu(mixed)
    q = mixed[..., :hk * dk].reshape(b, s, hk, dk)
    k = mixed[..., hk * dk:2 * hk * dk].reshape(b, s, hk, dk)
    v = mixed[..., 2 * hk * dk:].reshape(b, s, hv, dv)
    if "qk_norm" not in without:
        q, k = (t * jax.lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True)
                                  + 1e-6) for t in (q, k))
    q = q * dk ** -0.5
    q, k = (jnp.repeat(t, r, axis=2) for t in (q, k))
    beta = jax.nn.sigmoid(ba[..., :r].reshape(b, s, hv))
    g = -jnp.exp(p["gdn_A_log"]) * jax.nn.softplus(
        ba[..., r:].reshape(b, s, hv) + p["gdn_dt_bias"])
    q, k, v = (fake_quant(t, lowprec) for t in (q, k, v))
    o = delta_rule(q, k, v, g, beta, without)
    if lowprec:
        o = grad_quant(o, lowprec)
    o = _rms_norm(o, p["gdn_norm_gamma"], float(cfg["rms_norm_eps"]))
    if "out_gate" not in without:
        o = o * jax.nn.silu(z)
    return _linear(o.reshape(b, s, hv * dv), p["gdn_out_weight"], lowprec)


def _gated_ffn(x, w_gate, w_up, w_down, lowprec):
    return _linear(jax.nn.silu(_linear(x, w_gate, lowprec))
                   * _linear(x, w_up, lowprec), w_down, lowprec)


def routing(u, p, cfg, lowprec=None):
    """u (T, E) -> (w (T, width): the normalised weight of every chosen
    expert, 0 elsewhere; (T,) margin: how far the last chosen score lies
    above the first one left out)."""
    d = _dims(cfg)
    probs = jax.nn.softmax(_linear(u, p["moe_router_weight"], lowprec), -1)
    best, idx = jax.lax.top_k(probs, d["top_k"] + 1)
    chosen = jnp.any(idx[:, :d["top_k"], None] == jnp.arange(d["width"]),
                     axis=1)
    w = jnp.where(chosen, probs, 0.0)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w, best[:, d["top_k"] - 1] - best[:, d["top_k"]]


def routed_layer(u, p, cfg, lowprec=None, first=None, held=None,
                 shared=True):
    """u (T, E) -> ((T, E), (T,) margin).  ``first``/``held`` default to
    the configuration's share; the shares test passes others, and counts
    the shared expert once (``shared``)."""
    d = _dims(cfg)
    first = d["first"] if first is None else first
    held = d["held"] if held is None else held
    w, margin = routing(u, p, cfg, lowprec)

    @jax.checkpoint
    def expert(y, leaves):
        w_e, w_gate, w_up, w_down = leaves
        return y + w_e[:, None] * _gated_ffn(u, w_gate, w_up, w_down,
                                             lowprec), None

    y, _ = jax.lax.scan(
        expert, jnp.zeros_like(u),
        (w[:, first:first + held].T, p["moe_expert_gate_weight"][:held],
         p["moe_expert_up_weight"][:held], p["moe_expert_down_weight"][:held]))
    if shared:
        gate = jax.nn.sigmoid(_linear(u, p["moe_shared_score_weight"],
                                      lowprec))
        y = y + gate * _gated_ffn(u, p["moe_shared_gate_weight"],
                                  p["moe_shared_up_weight"],
                                  p["moe_shared_down_weight"], lowprec)
    return y, margin


def layer(x, p, cfg, kind, lowprec=None, without=()):
    """One layer on (B, S, E): -> (x, (B·S,) routing margin)."""
    eps = float(cfg["rms_norm_eps"])
    b, s, e = x.shape
    u = _rms_norm(x, p["norm1_gamma"], eps)
    if kind == "full_attention":
        x = x + gated_attention(u, p, cfg, lowprec)
    else:
        x = x + gated_delta_net(u, p, cfg, lowprec, without)
    u = _rms_norm(x, p["norm2_gamma"], eps).reshape(b * s, e)
    f, margin = routed_layer(u, p, cfg, lowprec)
    return x + f.reshape(b, s, e), margin


def _leaves(params, prefix):
    n = len(prefix) + 1
    return {k[n:]: v for k, v in params.items() if k.startswith(prefix + "_")}


def _head_rows(x, gamma, table, labels, cfg, lowprec):
    """Each position's cross-entropy (row-major) through the final norm and
    the head, ``HEAD_ROWS`` rows of logits at a time (rematerialised)."""
    e = x.shape[-1]
    x = _rms_norm(x, gamma, float(cfg["rms_norm_eps"])).reshape(-1, e)
    labels = labels.reshape(-1).astype(jnp.int32)
    step = min(HEAD_ROWS, x.shape[0])
    # one scale for the whole operand, as the control's recipe has it
    x, table = fake_quant(x, lowprec), fake_quant(table, lowprec)

    @jax.checkpoint
    def rows(x_b, lab_b):
        logits = x_b @ table.T
        if lowprec:
            logits = grad_quant(logits, lowprec)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, lab_b[:, None], axis=-1)[:, 0]

    out = jax.lax.map(lambda a: rows(*a), (x.reshape(-1, step, e),
                                           labels.reshape(-1, step)))
    return out.reshape(-1)


def loss_fn(params, ids, labels, cfg, lowprec=None, without=()):
    """(the mean token cross-entropy, {"main": each position's loss,
    "margin": each position's least routing margin over the layers})."""
    x = params["tok_embed_weight"][ids]
    margin = jnp.full((ids.size,), jnp.inf, x.dtype)
    for i, kind in enumerate(layer_kinds(cfg)):
        x, m = jax.checkpoint(
            lambda x, p, kind=kind: layer(x, p, cfg, kind, lowprec, without))(
            x, _leaves(params, "layer%d" % i))
        margin = jnp.minimum(margin, m)
    rows = {"main": _head_rows(x, params["final_norm_gamma"],
                               params["lm_head_weight"], labels, cfg,
                               lowprec),
            "margin": jax.lax.stop_gradient(margin)}
    return jnp.mean(rows["main"]), rows


def expert_sketch(grads):
    """{layer's routed experts' down projection: its gradient (held, E,
    moe) times one fixed vector -> (held, E)}.  Small enough to keep, and
    the norm of the difference of two sketches against the norm of one
    estimates that of the gradients themselves: a number that follows which
    token went to which expert, where a norm cannot tell one share of the
    experts from another."""
    out = {}
    for name, g in grads.items():
        if name.endswith("_moe_expert_down_weight"):
            probe = jax.random.normal(jax.random.PRNGKey(0), g.shape[-1:],
                                      jnp.float32)
            out[name] = g.astype(jnp.float32) @ probe
    return out


def make_train_step(cfg, lr, momentum, wd, lowprec=None, without=()):
    """One SGD-momentum step as the config states it
    (``m = momentum*m - lr*(g + wd*w); w = w + m``):
    ``step(w, m, ids, labels) -> (rows, readings, new w, new m)``.
    ``w`` and ``m`` are donated (626 M float32 parameters: the old and the
    new state do not fit one chip side by side), and the gradient leaves
    the step as ``readings``: ``grad`` {leaf: ‖g‖} and ``sketch``
    (:func:`expert_sketch`).  ``without``: see :func:`delta_rule`."""

    def step(w, m, ids, labels):
        with jax.default_matmul_precision("highest"):
            (_loss, rows), g = jax.value_and_grad(
                lambda w: loss_fn(w, ids, labels, cfg, lowprec, without),
                has_aux=True)(w)
        readings = {
            "grad": {k: jnp.linalg.norm(v.ravel()) for k, v in g.items()},
            "sketch": expert_sketch(g)}
        new_m = {k: momentum * m[k] - lr * (g[k] + wd * w[k]) for k in w}
        new_w = {k: w[k] + new_m[k] for k in w}
        return rows, readings, new_w, new_m

    return jax.jit(step, donate_argnums=(0, 1))
