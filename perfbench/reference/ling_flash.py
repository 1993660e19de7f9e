"""Plain reference of the decoder in ``configs/ling-3.0-flash-vl.json``.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernels, no sorting, no
grouped products, no chunked scan — Kimi delta attention is the per-token
recurrence with a decay a key channel.  Independent of ``mxnet_tpu``: it
imports nothing of the program and is handed only what the benchmark made
from the seed.

Architecture (the language model of inclusionAI/Ling-3.0-flash-VL; its
``config.json`` fixes every width, what it does not fix is listed in the
configuration file under ``assumed``).  x is a (B, S, E) stream,
RMSNorm(x) = x / sqrt(mean(x²) + eps) · g, no bias in any product.  Layer i
reads its kind from the configuration's per-layer lists (``layer_mixers``,
``mlp_layer_types``):

- x = x + Mixer(RMSNorm₁(x)); x = x + FFN(RMSNorm₂(x)).
- KDA (H = ``num_attention_heads`` heads of d = ``head_dim`` for q, k, v):
  q, k, v = u W_q, u W_k, u W_v, each through its own depthwise causal
  convolution of ``short_conv_kernel_size`` taps (left-padded with zeros,
  no bias; the last tap reads the current token), then SiLU; q and k
  L2-normalised a head, x · rsqrt(Σx² + 1e-6), q times d^−½;
  g = ``kda_lower_bound`` · sigmoid(exp(A_log) ⊙ (u W_f + dt_bias)) a
  channel; β = sigmoid(u W_b) a head.  Per head, S (d × d) from 0:
      S ← Diag(e^{g_t}) S;  δ_t = β_t (v_t − Sᵀ k_t);  S ← S + k_t δ_tᵀ;
      o_t = Sᵀ q_t
  y = (RMSNorm(o_t) ⊙ sigmoid(u W_g)) W_o, the norm over a head's d
  channels with one gain vector for all heads.
- Latent attention (H heads): q = u W_q (H · (nope + rope)), RMS-normed a
  head over its whole width (one gain vector); c = RMSNorm(u W_kv_a[:r])
  (r = ``kv_lora_rank``) and one rotary key u W_kv_a[r:] shared by all
  heads; [k_nope ‖ v] = c W_kv_b a head; rotary half-split on q's last
  ``qk_rope_head_dim`` channels and on the shared key, theta
  ``rope_theta``; causal softmax(q kᵀ / √(nope + rope)) v; y = [o_h ·
  sigmoid(u w_h)]_h W_o.
- FFN: ``dense`` — (silu(u W_g) ⊙ u W_u) W_d of ``intermediate_size``;
  ``sparse`` — s = sigmoid(u W_r) over all ``deployment.router_width``
  experts; the choice on s + b (b = 0): the experts fall into ``n_group``
  groups in order, a group scores the sum of its two best, the
  ``topk_group`` best groups are kept and the ``num_experts_per_tok``
  largest among their experts chosen; w = s at the chosen / Σ_chosen s ·
  ``routed_scaling_factor``; Σ w_e E_e(u) over the chosen experts that are
  among the ``num_experts`` held here (from ``deployment.first_expert``) —
  the other experts' part is another chip's — plus E_shared(u), ungated.
  Experts are a plain loop (a scan) over those held, each applied to every
  token and masked by its weight.
- Head: RMSNorm -> ``lm_head_weight`` (untied); mean token cross-entropy.

Memory: every layer is rematerialised (``jax.checkpoint``); the recurrence
runs by blocks of ``RULE_BLOCK`` tokens, each rematerialised in the backward
pass; attention runs by query blocks and the head by blocks of rows.

Weight names and layouts are the program's checkpoint's (``*_weight`` is
``(out_features, in_features)``, expert stacks lead with the expert), listed
by :func:`param_shapes`.
"""
import jax
import jax.numpy as jnp

from .lowprec import fake_quant, grad_quant

Q_BLOCK = 256       # queries scored at a time
HEAD_ROWS = 1024    # rows of logits made at a time
RULE_BLOCK = 64     # tokens of the recurrence rematerialised together

#: what a test or a planted fault can leave out: the decay a channel (each
#: head decays by the mean of its channels) and the group limit (the top-k
#: over all experts)
FAULTS = ("channel_decay", "group_limit")


def _dims(cfg):
    n = int(cfg["num_hidden_layers"])
    return dict(
        e=int(cfg["hidden_size"]), v=int(cfg["vocab_size"]),
        h=int(cfg["num_attention_heads"]), d=int(cfg["head_dim"]),
        taps=int(cfg["short_conv_kernel_size"]),
        bound=float(cfg["kda_lower_bound"]),
        nope=int(cfg["qk_nope_head_dim"]), rope=int(cfg["qk_rope_head_dim"]),
        dv=int(cfg["v_head_dim"]), rkv=int(cfg["kv_lora_rank"]),
        inner=int(cfg["intermediate_size"]),
        moe=int(cfg["moe_intermediate_size"]),
        shared=int(cfg["moe_shared_expert_intermediate_size"]),
        held=int(cfg["num_experts"]),
        width=int(cfg["deployment"]["router_width"]),
        first=int(cfg["deployment"]["first_expert"]),
        top_k=int(cfg["num_experts_per_tok"]),
        groups=int(cfg["n_group"]), kept=int(cfg["topk_group"]),
        layers=list(zip(cfg["layer_mixers"][:n],
                        cfg["mlp_layer_types"][:n])))


def _layer_shapes(d, mixer, mlp):
    e, h, w = d["e"], d["h"], d["d"]
    shapes = {"norm1_gamma": (e,), "norm2_gamma": (e,)}
    if mixer == "kda":
        conv = (d["taps"], h * w)
        shapes.update({
            "kda_q_weight": (h * w, e), "kda_k_weight": (h * w, e),
            "kda_v_weight": (h * w, e), "kda_q_conv_weight": conv,
            "kda_k_conv_weight": conv, "kda_v_conv_weight": conv,
            "kda_f_weight": (h * w, e), "kda_A_log": (h,),
            "kda_dt_bias": (h * w,), "kda_b_weight": (h, e),
            "kda_g_weight": (h * w, e), "kda_norm_gamma": (w,),
            "kda_out_weight": (e, h * w)})
    else:
        qk = d["nope"] + d["rope"]
        shapes.update({
            "att_q_weight": (h * qk, e), "att_q_norm_gamma": (qk,),
            "att_kv_a_weight": (d["rkv"] + d["rope"], e),
            "att_kv_a_norm_gamma": (d["rkv"],),
            "att_kv_b_weight": (h * (d["nope"] + d["dv"]), d["rkv"]),
            "att_gate_weight": (h, e), "att_out_weight": (e, h * d["dv"])})
    if mlp == "dense":
        shapes.update({"ffn_gate_weight": (d["inner"], e),
                       "ffn_up_weight": (d["inner"], e),
                       "ffn_down_weight": (e, d["inner"])})
    else:
        shapes.update({
            "moe_router_weight": (d["width"], e),
            "moe_expert_gate_weight": (d["held"], d["moe"], e),
            "moe_expert_up_weight": (d["held"], d["moe"], e),
            "moe_expert_down_weight": (d["held"], e, d["moe"]),
            "moe_shared_gate_weight": (d["shared"], e),
            "moe_shared_up_weight": (d["shared"], e),
            "moe_shared_down_weight": (e, d["shared"])})
    return shapes


def param_shapes(cfg, positions=None):
    """{name: shape} of every weight, in the checkpoint's names
    (``positions`` is taken and ignored: rotary needs no table)."""
    d = _dims(cfg)
    shapes = {"tok_embed_weight": (d["v"], d["e"]),
              "final_norm_gamma": (d["e"],),
              "lm_head_weight": (d["v"], d["e"])}
    for i, (mixer, mlp) in enumerate(d["layers"]):
        for leaf, shape in _layer_shapes(d, mixer, mlp).items():
            shapes["layer%d_%s" % (i, leaf)] = shape
    return shapes


def init_params(cfg, key, positions=None, dtype=jnp.float32):
    """Seeded weights, made on the device in one traced call: matrices, the
    convolutions and the embedding normal(0, ``initializer_range``); norm
    gains 1; ``A_log`` = log U(1, 16); ``dt_bias`` 0."""
    shapes = param_shapes(cfg)
    std = float(cfg["initializer_range"])
    keys = jax.random.split(key, len(shapes))
    out = {}
    for k, (name, shape) in zip(keys, sorted(shapes.items())):
        if name.endswith("_gamma"):
            w = jnp.ones(shape, jnp.float32)
        elif name.endswith("_A_log"):
            w = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
        elif name.endswith("_dt_bias"):
            w = jnp.zeros(shape, jnp.float32)
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


def kda_rule(q, k, v, g, beta):
    """The recurrence, a token at a time, in float32 with no matrix unit in
    it (products are elementwise, sums exact to float32): q, k, g (B, S,
    H, d_k), v (B, S, H, d_v), beta (B, S, H) -> o (B, S, H, d_v).  Blocks
    of ``RULE_BLOCK`` tokens are rematerialised in the backward pass."""
    b, s, h, dk = q.shape
    block = min(RULE_BLOCK, s)

    def token(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = state * jnp.exp(g_t)[..., :, None]
        mem = jnp.sum(state * k_t[..., :, None], axis=-2)
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


def kda(u, p, cfg, lowprec=None, without=()):
    """(B, S, E) -> (B, S, E); ``p`` holds the ``kda_*`` leaves."""
    d = _dims(cfg)
    h, w = d["h"], d["d"]
    b, s, _ = u.shape

    def mixed(name):    # projection, convolution, SiLU: (B, S, H, d)
        z = _linear(u, p["kda_%s_weight" % name], lowprec)
        taps = p["kda_%s_conv_weight" % name]
        z = sum(_previous(z, d["taps"] - 1 - j) * taps[j]
                for j in range(d["taps"]))
        return jax.nn.silu(z).reshape(b, s, h, w)

    q, k, v = mixed("q"), mixed("k"), mixed("v")
    q, k = (t * jax.lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6)
            for t in (q, k))
    q = q * w ** -0.5
    f = _linear(u, p["kda_f_weight"], lowprec).reshape(b, s, h, w)
    g = d["bound"] * jax.nn.sigmoid(
        jnp.exp(p["kda_A_log"])[:, None]
        * (f + p["kda_dt_bias"].reshape(h, w)))
    if "channel_decay" in without:      # a head's decay, its channels' mean
        g = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)
    beta = jax.nn.sigmoid(_linear(u, p["kda_b_weight"], lowprec))
    q, k, v = (fake_quant(t, lowprec) for t in (q, k, v))
    o = kda_rule(q, k, v, g, beta)
    if lowprec:
        o = grad_quant(o, lowprec)
    o = _rms_norm(o, p["kda_norm_gamma"], float(cfg["rms_norm_eps"]))
    o = o * jax.nn.sigmoid(_linear(u, p["kda_g_weight"], lowprec)
                           .reshape(b, s, h, w))
    return _linear(o.reshape(b, s, h * w), p["kda_out_weight"], lowprec)


def rotary_half(x, theta):
    """x (..., S, r): channel i turned with i + r/2 by position ·
    theta^(−2i/r)."""
    s, r = x.shape[-2], x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :r // 2], x[..., r // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attend(q, k, v, lowprec):
    """Causal softmax(q kᵀ / √d) v; q, k (B, H, S, d), v (B, H, S, d_v); by
    query blocks, one shape for all of them, rematerialised in the backward
    pass."""
    s, scale = q.shape[-2], 1.0 / jnp.sqrt(float(q.shape[-1]))
    q, k, v = (fake_quant(t, lowprec) for t in (q, k, v))
    step = min(Q_BLOCK, s)

    @jax.checkpoint
    def block(q_b, start):
        scores = jnp.einsum("bhqd,bhkd->bhqk", q_b, k) * scale
        if lowprec:
            scores = grad_quant(scores, lowprec)
        qpos = start + jnp.arange(step)[:, None]
        scores = jnp.where(qpos >= jnp.arange(s)[None, :], scores, -jnp.inf)
        probs = fake_quant(jax.nn.softmax(scores, axis=-1), lowprec)
        out = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
        return grad_quant(out, lowprec) if lowprec else out

    blocks = q.reshape(q.shape[:2] + (s // step, step, q.shape[-1]))
    outs = jax.lax.map(lambda a: block(*a),
                       (jnp.moveaxis(blocks, 2, 0),
                        jnp.arange(0, s, step)))
    return jnp.moveaxis(outs, 0, 2).reshape(q.shape[:-1] + (v.shape[-1],))


def latent_attention(u, p, cfg, lowprec=None):
    """(B, S, E) -> (B, S, E); ``p`` holds the ``att_*`` leaves."""
    d = _dims(cfg)
    h, nope, rope, dv, rkv = d["h"], d["nope"], d["rope"], d["dv"], d["rkv"]
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    b, s, _ = u.shape
    q = _linear(u, p["att_q_weight"], lowprec).reshape(b, s, h, nope + rope)
    q = _rms_norm(q, p["att_q_norm_gamma"], eps).transpose(0, 2, 1, 3)
    ckv = _linear(u, p["att_kv_a_weight"], lowprec)
    c = _rms_norm(ckv[..., :rkv], p["att_kv_a_norm_gamma"], eps)
    kv = _linear(c, p["att_kv_b_weight"], lowprec) \
        .reshape(b, s, h, nope + dv).transpose(0, 2, 1, 3)
    k_rope = rotary_half(ckv[..., rkv:], theta)[:, None]    # (B, 1, S, r)
    q = jnp.concatenate([q[..., :nope], rotary_half(q[..., nope:], theta)],
                        axis=-1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_rope, (b, h, s, rope))], axis=-1)
    o = _attend(q, k, kv[..., nope:], lowprec).transpose(0, 2, 1, 3)
    o = o * jax.nn.sigmoid(_linear(u, p["att_gate_weight"], lowprec)
                           )[..., None]
    return _linear(o.reshape(b, s, h * dv), p["att_out_weight"], lowprec)


def _gated_ffn(x, w_gate, w_up, w_down, lowprec):
    return _linear(jax.nn.silu(_linear(x, w_gate, lowprec))
                   * _linear(x, w_up, lowprec), w_down, lowprec)


def routing(u, p, cfg, lowprec=None, without=()):
    """u (T, E) -> (w (T, width): the weight of every chosen expert, 0
    elsewhere; (T,) margin: how far the last chosen lies above the first
    left out — of the groups' scores where the groups limit the choice,
    and of the experts' among those kept — the lesser of the two)."""
    d = _dims(cfg)
    scores = jax.nn.sigmoid(_linear(u, p["moe_router_weight"], lowprec))
    choice = scores                     # + the correction bias, 0 here
    t = choice.shape[0]
    group_margin = jnp.full((t,), jnp.inf, choice.dtype)
    if "group_limit" not in without and d["groups"] > 1:
        grouped = choice.reshape(t, d["groups"], -1)
        two = jnp.sort(grouped, axis=-1)[..., -2:]
        ranked = jnp.sort(jnp.sum(two, axis=-1), axis=-1)   # (T, groups)
        cut = ranked[:, -d["kept"]]
        kept = jnp.sum(two, axis=-1) >= cut[:, None]
        choice = jnp.where(kept[..., None], grouped, -jnp.inf).reshape(t, -1)
        group_margin = cut - ranked[:, -d["kept"] - 1]
    best, idx = jax.lax.top_k(choice, d["top_k"] + 1)
    chosen = jnp.any(idx[:, :d["top_k"], None] == jnp.arange(d["width"]),
                     axis=1)
    w = jnp.where(chosen, scores, 0.0)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) \
        * float(cfg["routed_scaling_factor"])
    return w, jnp.minimum(best[:, d["top_k"] - 1] - best[:, d["top_k"]],
                          group_margin)


def routed_layer(u, p, cfg, lowprec=None, first=None, held=None,
                 shared=True, without=()):
    """u (T, E) -> ((T, E), (T,) margin).  ``first``/``held`` default to
    the configuration's share; the shares test passes others, and counts
    the shared expert once (``shared``)."""
    d = _dims(cfg)
    first = d["first"] if first is None else first
    held = d["held"] if held is None else held
    w, margin = routing(u, p, cfg, lowprec, without)

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
        y = y + _gated_ffn(u, p["moe_shared_gate_weight"],
                           p["moe_shared_up_weight"],
                           p["moe_shared_down_weight"], lowprec)
    return y, margin


def layer(x, p, cfg, mixer, mlp, lowprec=None, without=()):
    """One layer on (B, S, E): -> (x, (B·S,) routing margin, +inf in the
    dense layer)."""
    eps = float(cfg["rms_norm_eps"])
    b, s, e = x.shape
    u = _rms_norm(x, p["norm1_gamma"], eps)
    if mixer == "kda":
        x = x + kda(u, p, cfg, lowprec, without)
    else:
        x = x + latent_attention(u, p, cfg, lowprec)
    u = _rms_norm(x, p["norm2_gamma"], eps).reshape(b * s, e)
    if mlp == "dense":
        f = _gated_ffn(u, p["ffn_gate_weight"], p["ffn_up_weight"],
                       p["ffn_down_weight"], lowprec)
        margin = jnp.full((b * s,), jnp.inf, x.dtype)
    else:
        f, margin = routed_layer(u, p, cfg, lowprec, without=without)
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
    for i, (mixer, mlp) in enumerate(_dims(cfg)["layers"]):
        x, m = jax.checkpoint(
            lambda x, p, mixer=mixer, mlp=mlp: layer(
                x, p, cfg, mixer, mlp, lowprec, without))(
            x, _leaves(params, "layer%d" % i))
        margin = jnp.minimum(margin, m)
    rows = {"main": _head_rows(x, params["final_norm_gamma"],
                               params["lm_head_weight"], labels, cfg,
                               lowprec),
            "margin": jax.lax.stop_gradient(margin)}
    return jnp.mean(rows["main"]), rows


def expert_sketch(grads):
    """{layer's routed experts' down projection: its gradient (held, E,
    moe) times one fixed vector -> (held, E)}: a number that follows which
    token went to which expert, where a norm cannot tell one share of the
    experts from another (``reference.qwen3_next.expert_sketch``)."""
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
    ``w`` and ``m`` are donated (767 M float32 parameters: the old and the
    new state do not fit one chip side by side), and the gradient leaves
    the step as ``readings``: ``grad`` {leaf: ‖g‖} and ``sketch``
    (:func:`expert_sketch`).  ``without``: names of :data:`FAULTS`."""
    unknown = set(without) - set(FAULTS)
    if unknown:
        raise ValueError("no such fault: %s" % sorted(unknown))

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
