"""Plain reference of the decoder in ``configs/zaya1-8b.json``.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernels, no sorting, no
grouped products, keys and values repeated over a group of query heads.
Independent of ``mxnet_tpu``: it imports nothing of the program and is handed
only what the benchmark made from the seed.

Architecture (``config.json`` of Zyphra/ZAYA1-8B fixes every width; the
equations are those the configuration file lists under ``assumed``, from
arXiv:2510.04476 section 3 and the ZAYA1 report arXiv:2511.17127).  x is a
(B, S, E) stream, RMSNorm(x) = x / sqrt(mean(x²) + eps) · g, no bias in any
product.  H query heads on H_kv key/value heads of d channels, g = H / H_kv.

- Layer l, two streams in and out — the residual x and the router's s:
  h = (a1 ⊙ x + b1) + (c1 ⊙ CCA(RMSNorm₁(x)) + e1);
  y = (a2 ⊙ h + b2) + (c2 ⊙ MoE(RMSNorm₂(h), s_{l−1}) + e2).
- CCA(u): (1) q̃ = u W_q, k̃ = u W_k, ṽ = u W_v;  (2) value shift: the first
  half of v's channels is ṽ_t's, the second half ṽ_{t−1}'s (zeros at t = 0);
  (3) on q̃ and on k̃, weights of their own, both causal: conv0 depthwise,
  z⁰_t = w_0 ⊙ z_{t−1} + w_1 ⊙ z_t, then conv1 by head,
  z¹_t[h] = z⁰_{t−1}[h] A_h + z⁰_t[h] B_h;  (4) q-k mean of the values before
  the convolutions: m_q[h] = ½(q̃[h] + k̃[h // g]), m_k[j] = ½(k̃[j] + mean of
  q̃ over group j);  q′ = conv(q̃) + m_q, k′ = conv(k̃) + m_k;  (5) q̂ =
  q′ / rms(q′), k̂ = τ_j · k′ / rms(k′) per head (rms with eps, no gain);
  (6) rotary on the first ``partial_rotary_factor``·d channels of each head,
  channel i paired with i + half of them, ``rope_parameters.hybrid``'s
  theta;  (7)
  softmax(q̂ k̂ᵀ / √d) v causal, query head h on key/value head h // g; heads
  concatenated -> W_o.
- MoE(u, s_{l−1}): r = u W_d; s_l = r + γ_l · s_{l−1} (layer 0: s_0 = r, no
  γ); z = W_3 gelu(W_2 gelu(W_1 RMSNorm(s_l))), tanh-approximated GELU;
  p = softmax(z) over all ``deployment.router_width`` experts;
  e* = argmax(p + β), β the balancing bias, seeded by
  :func:`balancing_bias` and fixed; MoE = p_{e*} · Expert_{e*}(u) if e* is
  one of the ``num_experts`` held here (from ``deployment.first_expert``),
  else 0: the other experts' part is another chip's.  Expert(u) =
  (silu(u W_g) ⊙ u W_u) W_o'.  Experts are a plain loop (a scan) over those
  held, each applied to every token and masked by its gate.
- Head: RMSNorm -> the embedding matrix (tied); mean token cross-entropy.

Memory: every layer is rematerialised (``jax.checkpoint``), attention runs by
query blocks, each against all the keys under the causal mask, and the head
by blocks of rows, so that the 8,192-token step over 32,784 logits fits one
chip beside nothing else.

Weight names and layouts are the program's checkpoint's (``*_weight`` is
``(out_features, in_features)``, expert stacks lead with the expert), listed
by :func:`param_shapes`.
"""
import jax
import jax.numpy as jnp

from .lowprec import fake_quant, grad_quant

Q_BLOCK = 256       # queries scored at a time
HEAD_ROWS = 1024    # rows of logits made at a time

#: the steps of CCA a test can leave out, to see that each one matters
CCA_STEPS = ("value_shift", "conv", "qk_mean", "qk_norm", "rotary")


def _dims(cfg):
    return dict(
        e=int(cfg["hidden_size"]), v=int(cfg["vocab_size"]),
        heads=int(cfg["num_attention_heads"]),
        kv=int(cfg["num_key_value_heads"]), d=int(cfg["head_dim"]),
        t0=int(cfg["cca_time0"]), t1=int(cfg["cca_time1"]),
        moe=int(cfg["moe_intermediate_size"]),
        held=int(cfg["num_experts"]),
        width=int(cfg["deployment"]["router_width"]),
        first=int(cfg["deployment"]["first_expert"]),
        r=int(cfg["router_hidden_size"]),
        layers=int(cfg["num_hidden_layers"]))


def _layer_shapes(d, first_layer):
    e, hq, hk, w = d["e"], d["heads"], d["kv"], d["d"]
    shapes = {
        "norm1_gamma": (e,), "norm2_gamma": (e,),
        "att_q_weight": (hq * w, e), "att_k_weight": (hk * w, e),
        "att_v_weight": (hk * w, e),
        "att_q_conv0_weight": (d["t0"], hq * w),
        "att_q_conv1_weight": (d["t1"], hq, w, w),
        "att_k_conv0_weight": (d["t0"], hk * w),
        "att_k_conv1_weight": (d["t1"], hk, w, w),
        "att_k_temp": (hk,), "att_out_weight": (e, hq * w),
        "router_down_weight": (d["r"], e), "router_norm_gamma": (d["r"],),
        "router_fc1_weight": (d["r"], d["r"]),
        "router_fc2_weight": (d["r"], d["r"]),
        "router_out_weight": (d["width"], d["r"]),
        "moe_expert_gate_weight": (d["held"], d["moe"], e),
        "moe_expert_up_weight": (d["held"], d["moe"], e),
        "moe_expert_down_weight": (d["held"], e, d["moe"])}
    if not first_layer:
        shapes["router_state_gain"] = (1,)
    for name in ("res1", "att_out", "res2", "moe_out"):
        shapes[name + "_scale"] = (e,)
        shapes[name + "_bias"] = (e,)
    return shapes


def param_shapes(cfg, positions=None):
    """{name: shape} of every weight, in the checkpoint's names
    (``positions`` is taken and ignored: rotary needs no table).  The
    embedding is the head: one leaf."""
    d = _dims(cfg)
    shapes = {"tok_embed_weight": (d["v"], d["e"]),
              "final_norm_gamma": (d["e"],)}
    for i in range(d["layers"]):
        for leaf, shape in _layer_shapes(d, i == 0).items():
            shapes["layer%d_%s" % (i, leaf)] = shape
    return shapes


def init_params(cfg, key, positions=None, dtype=jnp.float32):
    """Seeded weights, made on the device in one traced call: matrices and
    the embedding normal(0, ``initializer_range``); norm gains and residual
    scales 1; key temperatures ``key_temperature_init`` (1 where the
    configuration has no such key); residual biases 0; the router's stream
    gain 0.5; the convolutions the identity (last tap 1 or I, earlier taps
    0) plus normal(0, ``initializer_range``)."""
    shapes = param_shapes(cfg)
    std = float(cfg["initializer_range"])
    temperature = float(cfg.get("key_temperature_init", 1.0))
    keys = jax.random.split(key, len(shapes))
    out = {}
    for k, (name, shape) in zip(keys, sorted(shapes.items())):
        noise = std * jax.random.normal(k, shape, jnp.float32)
        if name.endswith("_k_temp"):
            w = jnp.full(shape, temperature, jnp.float32)
        elif name.endswith(("_gamma", "_scale")):
            w = jnp.ones(shape, jnp.float32)
        elif name.endswith("_bias"):
            w = jnp.zeros(shape, jnp.float32)
        elif name.endswith("_state_gain"):
            w = jnp.full(shape, 0.5, jnp.float32)
        elif name.endswith("_conv0_weight"):
            w = noise.at[-1].add(1.0)
        elif name.endswith("_conv1_weight"):
            w = noise.at[-1].add(jnp.eye(shape[-1], dtype=jnp.float32))
        else:
            w = noise
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


def conv_pair(z, w0, w1, lowprec=None):
    """z (B, S, H, d) through conv0 (depthwise, ``w0`` (taps, H·d)) and
    conv1 (a head's channels mixed, ``w1`` (taps, H, d, d)); the last tap
    reads the current token."""
    b, s, h, d = z.shape
    t0, t1 = w0.shape[0], w1.shape[0]
    taps0 = w0.reshape(t0, h, d)
    z0 = sum(_previous(z, t0 - 1 - j) * taps0[j] for j in range(t0))
    out = 0.0
    for j in range(t1):
        y = jnp.einsum("bshc,hcd->bshd",
                       fake_quant(_previous(z0, t1 - 1 - j), lowprec),
                       fake_quant(w1[j], lowprec))
        out = out + (grad_quant(y, lowprec) if lowprec else y)
    return out


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


def cca(u, p, cfg, lowprec=None, without=()):
    """(B, S, E) -> (B, S, E); ``p`` holds the ``att_*`` leaves.  ``without``
    names steps of ``CCA_STEPS`` to leave out (tests: each must matter)."""
    d = _dims(cfg)
    hq, hk, w = d["heads"], d["kv"], d["d"]
    g = hq // hk
    eps = float(cfg["rms_norm_eps"])
    theta = float(cfg["rope_parameters"]["hybrid"]["rope_theta"])
    b, s, _ = u.shape
    q = _linear(u, p["att_q_weight"], lowprec).reshape(b, s, hq, w)
    k = _linear(u, p["att_k_weight"], lowprec).reshape(b, s, hk, w)
    v = _linear(u, p["att_v_weight"], lowprec)
    if "value_shift" not in without:
        half = hk * w // 2
        v = jnp.concatenate([v[..., :half], _previous(v[..., half:])], -1)
    q_new, k_new = q, k
    if "conv" not in without:
        q_new = conv_pair(q, p["att_q_conv0_weight"],
                          p["att_q_conv1_weight"], lowprec)
        k_new = conv_pair(k, p["att_k_conv0_weight"],
                          p["att_k_conv1_weight"], lowprec)
    if "qk_mean" not in without:
        grouped = q.reshape(b, s, hk, g, w)
        q_new = q_new + 0.5 * (grouped + k[:, :, :, None, :]
                               ).reshape(b, s, hq, w)
        k_new = k_new + 0.5 * (k + jnp.mean(grouped, axis=3))
    q, k = q_new, k_new
    if "qk_norm" not in without:
        q = _rms_norm(q, 1.0, eps)
        k = _rms_norm(k, 1.0, eps) * p["att_k_temp"][:, None]
        if "att_k_temp_terms" in p:     # ones, (B·S, H_kv)
            k = k * p["att_k_temp_terms"].reshape(b, s, hk, 1)
    q, k = q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3)
    if "rotary" not in without:
        rot = int(round(float(cfg["partial_rotary_factor"]) * w))
        q, k = rotary_half(q, theta, rot), rotary_half(k, theta, rot)
    o = _attend(q, k, v.reshape(b, s, hk, w).transpose(0, 2, 1, 3), lowprec)
    o = o.transpose(0, 2, 1, 3).reshape(b, s, hq * w)
    return _linear(o, p["att_out_weight"], lowprec)


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(0.7978845608028654
                                     * (x + 0.044715 * x ** 3)))


def router(u, state, p, cfg, lowprec=None):
    """u (T, E), the previous layer's stream ``state`` (T, R) or None ->
    (p (T, width) the softmax over all experts, the stream s_l)."""
    s = _linear(u, p["router_down_weight"], lowprec)
    if state is not None:
        s = s + p["router_state_gain"] * state \
            * p.get("router_state_gain_terms", 1.0)    # ones, (T, 1)
    z = _rms_norm(s, p["router_norm_gamma"], float(cfg["rms_norm_eps"]))
    z = _gelu(_linear(z, p["router_fc1_weight"], lowprec))
    z = _gelu(_linear(z, p["router_fc2_weight"], lowprec))
    return jax.nn.softmax(_linear(z, p["router_out_weight"], lowprec),
                          axis=-1), s


def _gated_ffn(x, w_gate, w_up, w_down, lowprec):
    return _linear(jax.nn.silu(_linear(x, w_gate, lowprec))
                   * _linear(x, w_up, lowprec), w_down, lowprec)


def experts(u, probs, p, cfg, lowprec=None, first=None, held=None, bias=None):
    """u (T, E) and the router's scores ``probs`` (T, width) -> ((T, E),
    (T,) margin: how far the chosen expert's biased score lies above the
    runner-up's).  e* = argmax(probs + ``bias``) (β: steers the choice,
    weighs nothing; None is 0); the gate is probs at e*.  ``first``/``held``
    default to the configuration's share; the share test passes others."""
    d = _dims(cfg)
    first = d["first"] if first is None else first
    held = d["held"] if held is None else held
    best, idx = jax.lax.top_k(probs if bias is None else probs + bias, 2)
    gate = jnp.where(idx[:, :1] == jnp.arange(probs.shape[-1]), probs, 0.0)

    @jax.checkpoint
    def expert(y, leaves):
        g_e, w_gate, w_up, w_down = leaves
        return y + g_e[:, None] * _gated_ffn(u, w_gate, w_up, w_down,
                                             lowprec), None

    y, _ = jax.lax.scan(
        expert, jnp.zeros_like(u),
        (gate[:, first:first + held].T, p["moe_expert_gate_weight"][:held],
         p["moe_expert_up_weight"][:held], p["moe_expert_down_weight"][:held]))
    return y, best[:, 0] - best[:, 1]


def routed_layer(u, state, p, cfg, lowprec=None, first=None, held=None,
                 bias=None):
    """u (T, E) -> ((T, E), the stream, (T,) margin): the router, then the
    experts held on its scores."""
    probs, state = router(u, state, p, cfg, lowprec)
    y, margin = experts(u, probs, p, cfg, lowprec, first, held, bias)
    return y, state, margin


def attention_sublayer(x, p, cfg, lowprec=None):
    att = cca(_rms_norm(x, p["norm1_gamma"], float(cfg["rms_norm_eps"])), p,
              cfg, lowprec)
    return (p["res1_scale"] * x + p["res1_bias"]) \
        + (p["att_out_scale"] * att + p["att_out_bias"])


def routed_sublayer(x, p, cfg, route):
    """The second half of a layer on (B, S, E): ``route(u)`` -> (f (T, E),
    *rest) gives the routed layer's result; -> (x, *rest)."""
    b, s, e = x.shape
    u = _rms_norm(x, p["norm2_gamma"], float(cfg["rms_norm_eps"]))
    f, *rest = route(u.reshape(b * s, e))
    x = (p["res2_scale"] * x + p["res2_bias"]) \
        + (p["moe_out_scale"] * f.reshape(b, s, e) + p["moe_out_bias"])
    return (x, *rest)


def layer(x, state, p, cfg, lowprec=None, bias=None):
    """One layer on (B, S, E) and the router's stream: -> (x, stream,
    (B·S,) routing margin)."""
    x = attention_sublayer(x, p, cfg, lowprec)
    return routed_sublayer(
        x, p, cfg, lambda u: routed_layer(u, state, p, cfg, lowprec,
                                          bias=bias))


def balance(probs, rounds=512):
    """β (width,) with which argmax(probs + β) sends every expert the same
    number of the T tokens scored in ``probs`` (T, width): β falls where
    an expert is over its share and rises where it is under, by steps that
    start at the scores' spread and shrink by 2 % a round."""
    n = probs.shape[-1]
    probs = probs.astype(jnp.float32)
    scale = jnp.std(probs)

    def step(r, beta):
        chosen = jnp.argmax(probs + beta, axis=-1)
        share = jnp.mean(chosen[:, None] == jnp.arange(n), axis=0,
                         dtype=jnp.float32)
        size = scale * jnp.float32(0.98) ** r.astype(jnp.float32)
        return beta - size * (share * n - 1.0)

    return jax.lax.fori_loop(0, rounds, step, jnp.zeros((n,), jnp.float32))


def balancing_bias(cfg, params, ids, dtype=jnp.float32):
    """The routers' balancing bias β (layers, width) as this benchmark
    seeds it: one forward pass over ``ids`` from the seeded weights, each
    layer's β set by :func:`balance` on that layer's scores before the
    layer routes with it (the configuration's ``assumed`` says why).
    Rounded to ``dtype``, the program's compute dtype, so that both sides
    add the same numbers."""
    with jax.default_matmul_precision("highest"):
        x = params["tok_embed_weight"][ids]
        state, out = None, []
        for i in range(_dims(cfg)["layers"]):
            p = _leaves(params, "layer%d" % i)
            x = attention_sublayer(x, p, cfg)

            def route(u, p=p, state=state):
                probs, new_state = router(u, state, p, cfg)
                beta = balance(probs).astype(dtype).astype(jnp.float32)
                return experts(u, probs, p, cfg, bias=beta)[0], new_state, beta

            x, state, beta = routed_sublayer(x, p, cfg, route)
            out.append(beta)
    return jnp.stack(out)


def _leaves(params, prefix):
    n = len(prefix) + 1
    return {k[n:]: v for k, v in params.items() if k.startswith(prefix + "_")}


def _head_rows(x, gamma, table, labels, cfg, lowprec):
    """Each position's cross-entropy (row-major) through the final norm and
    the tied head, ``HEAD_ROWS`` rows of logits at a time (rematerialised:
    8,192 × 32,784 float32 logits and their softmax are never whole)."""
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


def loss_fn(params, ids, labels, cfg, lowprec=None, bias=None):
    """(the mean token cross-entropy, {"main": each position's loss,
    "margin": each position's least routing margin over the layers}).
    ``bias`` (layers, width): the routers' balancing bias β (None: 0)."""
    d = _dims(cfg)
    x = params["tok_embed_weight"][ids]
    margin = jnp.full((ids.size,), jnp.inf, x.dtype)
    state = None
    for i in range(d["layers"]):
        x, state, m = jax.checkpoint(
            lambda x, state, p, beta: layer(x, state, p, cfg, lowprec, beta))(
            x, state, _leaves(params, "layer%d" % i),
            None if bias is None else bias[i])
        margin = jnp.minimum(margin, m)
    rows = {"main": _head_rows(x, params["final_norm_gamma"],
                               params["tok_embed_weight"], labels, cfg,
                               lowprec),
            "margin": jax.lax.stop_gradient(margin)}
    return jnp.mean(rows["main"]), rows


def position_terms(cfg, batch, seq):
    """{leaf: ones} for every leaf that is one number a head or a layer
    multiplied in at every position — the key temperatures τ (B·S, H_kv)
    and the stream gains γ (B·S, 1).  Multiplied in beside its leaf
    (``<leaf>_terms`` among the weights), the gradient of the ones is the
    leaf's gradient before its sum over the positions, times the leaf."""
    d = _dims(cfg)
    out = {}
    for i in range(d["layers"]):
        out["layer%d_att_k_temp" % i] = jnp.ones((batch * seq, d["kv"]),
                                                 jnp.float32)
        if i:
            out["layer%d_router_state_gain" % i] = jnp.ones(
                (batch * seq, 1), jnp.float32)
    return out


def cancellation(terms):
    """|the sum of a leaf's ``terms`` (positions, heads) over the positions|
    over the sum of the terms' sizes: 1 where every position pulls the same
    way, 1/sqrt(the positions) where they pull at random.  Rounding moves
    each term by its size times the precision's step, so the sum by up to
    that step over this ratio: what ``train_step_zaya.cancelling_leaves``
    goes by."""
    return jnp.linalg.norm(jnp.sum(terms, axis=0)) / jnp.maximum(
        jnp.sum(jnp.linalg.norm(terms, axis=-1)), 1e-30)


def expert_sketch(grads):
    """{layer's routed experts' down projection: its gradient (held, E,
    moe) times one fixed vector -> (held, E)}.  Small enough to keep, and
    the norm of the difference of two sketches against the norm of one
    estimates that of the gradients themselves (16,384 numbers a layer): a
    number that follows which token went to which expert, where a norm
    cannot tell one share of the experts from another."""
    out = {}
    for name, g in grads.items():
        if name.endswith("_moe_expert_down_weight"):
            probe = jax.random.normal(jax.random.PRNGKey(0), g.shape[-1:],
                                      jnp.float32)
            out[name] = g.astype(jnp.float32) @ probe
    return out


def make_train_step(cfg, lr, momentum, wd, lowprec=None):
    """One SGD-momentum step as the config states it
    (``m = momentum*m - lr*(g + wd*w); w = w + m``):
    ``step(w, m, ids, labels, bias) -> (rows, readings, new w, new m)``
    (``bias``: the routers' β, (layers, width), fixed).
    ``w`` and ``m`` are donated (709 M float32 parameters: the old and the
    new state do not fit one chip side by side), and the gradient leaves
    the step as ``readings``: ``grad`` {leaf: ‖g‖}, ``cancel`` {leaf:
    :func:`cancellation`} for the leaves of :func:`position_terms`, and
    ``sketch`` (:func:`expert_sketch`)."""

    def step(w, m, ids, labels, bias):
        ones = {k + "_terms": v
                for k, v in position_terms(cfg, *ids.shape).items()}
        with jax.default_matmul_precision("highest"):
            (_loss, rows), (g, terms) = jax.value_and_grad(
                lambda w, t: loss_fn({**w, **t}, ids, labels, cfg, lowprec,
                                     bias), argnums=(0, 1), has_aux=True)(
                w, ones)
        readings = {
            "grad": {k: jnp.linalg.norm(v.ravel()) for k, v in g.items()},
            "cancel": {k[:-len("_terms")]: cancellation(v)
                       for k, v in terms.items()},
            "sketch": expert_sketch(g)}
        new_m = {k: momentum * m[k] - lr * (g[k] + wd * w[k]) for k in w}
        new_w = {k: w[k] + new_m[k] for k in w}
        return rows, readings, new_w, new_m

    return jax.jit(step, donate_argnums=(0, 1))
