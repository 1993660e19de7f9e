"""Plain reference of the decoder in ``configs/joyai-llm-flash.json``.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernels, no sorting, no
grouped products.  Independent of ``mxnet_tpu``: it imports nothing of the
program and is handed only what the benchmark made from the seed.

Architecture, as the config file states it (the keys are those of the
DeepSeek-V3 layer; equations from arXiv:2412.19437 sections 2.1–2.2 and the
``config.json`` the file names).  x is a (B, S, hidden) stream,
RMSNorm(x) = x / sqrt(mean(x²) + eps) · g, no bias anywhere.

- Block: x ← x + Attn(RMSNorm₁(x)); x ← x + FFN(RMSNorm₂(x)).  The first
  ``first_k_dense_replace`` blocks have the dense FFN
  W_down(silu(W_gate h) ⊙ W_up h) of ``intermediate_size``; every later
  one the routed layer.
- Latent attention: c_q = RMSNorm(h W_qa); q = c_q W_qb, per head
  [q_nope ; q_rope].  [c_kv ; k_rope] = h W_kva; c_kv ← RMSNorm(c_kv);
  per head [k_nope ; v] = c_kv W_kvb.  Rotary (``rope_theta``, no scaling,
  ``rope_interleave``: the pairs (2i, 2i+1) are brought to halves, then
  rotate-half) on q_rope per head and on the one k_rope all heads share.
  softmax(q kᵀ / sqrt(qk_nope + qk_rope)) v, causal; heads concatenated
  -> W_o.
- Routed layer: s = sigmoid(h W_r) over all ``deployment.router_width``
  experts; the ``num_experts_per_tok`` largest of s + b chosen (b: the
  correction bias, 0 and fixed here); w = s at the chosen, / (Σw + 1e-20)
  · ``routed_scaling_factor``; y = Σᵢ wᵢ Eᵢ(h) over the chosen experts
  *held here* (``n_routed_experts`` of them, from ``deployment.first_expert``)
  + E_shared(h): the other experts' part is another chip's and is left out.
  Experts are a plain loop (a scan) over those held, each applied to every
  token and masked by its weight.
- Head: RMSNorm -> ``lm_head_weight`` (untied); mean token cross-entropy.
- Prediction module (depth 1): h′ᵢ = W_eh [RMSNorm_h(hᵢ) ; RMSNorm_e(Emb(tᵢ₊₁))]
  with hᵢ the main stream before the final norm and Emb the main table; one
  routed block; RMSNorm -> the main head's weights; cross-entropy against
  tᵢ₊₂.  Loss = L_main + ``mtp_loss_weight`` · L_mtp.

Memory: every block is rematerialised (``jax.checkpoint``) and attention
runs by query blocks, each against all the keys under the causal mask, so
that the 8,192-token step fits one chip beside nothing else.

Weight names and layouts are the program's checkpoint's (``*_weight`` is
``(out_features, in_features)``, expert stacks lead with the expert),
listed by :func:`param_shapes`.
"""
import jax
import jax.numpy as jnp

from .lowprec import fake_quant, grad_quant

Q_BLOCK = 256       # queries scored at a time


def _dims(cfg):
    return dict(
        e=int(cfg["hidden_size"]), v=int(cfg["vocab_size"]),
        heads=int(cfg["num_attention_heads"]),
        rq=int(cfg["q_lora_rank"]), rkv=int(cfg["kv_lora_rank"]),
        nope=int(cfg["qk_nope_head_dim"]), rope=int(cfg["qk_rope_head_dim"]),
        dv=int(cfg["v_head_dim"]), inner=int(cfg["intermediate_size"]),
        moe=int(cfg["moe_intermediate_size"]),
        held=int(cfg["n_routed_experts"]),
        width=int(cfg["deployment"]["router_width"]),
        first=int(cfg["deployment"]["first_expert"]),
        shared=int(cfg["n_shared_experts"]),
        layers=int(cfg["num_hidden_layers"]),
        dense=int(cfg["first_k_dense_replace"]),
        mtp=int(cfg["num_nextn_predict_layers"]))


def _attention_shapes(d):
    return {"norm1_gamma": (d["e"],),
            "att_q_a_weight": (d["rq"], d["e"]),
            "att_q_a_norm_gamma": (d["rq"],),
            "att_q_b_weight": (d["heads"] * (d["nope"] + d["rope"]), d["rq"]),
            "att_kv_a_weight": (d["rkv"] + d["rope"], d["e"]),
            "att_kv_a_norm_gamma": (d["rkv"],),
            "att_kv_b_weight": (d["heads"] * (d["nope"] + d["dv"]), d["rkv"]),
            "att_out_weight": (d["e"], d["heads"] * d["dv"]),
            "norm2_gamma": (d["e"],)}


def _routed_shapes(d):
    s = d["shared"] * d["moe"]
    return {"moe_router_weight": (d["width"], d["e"]),
            "moe_expert_gate_weight": (d["held"], d["moe"], d["e"]),
            "moe_expert_up_weight": (d["held"], d["moe"], d["e"]),
            "moe_expert_down_weight": (d["held"], d["e"], d["moe"]),
            "moe_shared_gate_weight": (s, d["e"]),
            "moe_shared_up_weight": (s, d["e"]),
            "moe_shared_down_weight": (d["e"], s)}


def param_shapes(cfg, positions=None):
    """{name: shape} of every weight, in the checkpoint's names
    (``positions`` is taken and ignored: rotary needs no table)."""
    d = _dims(cfg)
    shapes = {"tok_embed_weight": (d["v"], d["e"]),
              "final_norm_gamma": (d["e"],),
              "lm_head_weight": (d["v"], d["e"])}
    for i in range(d["layers"]):
        leaves = dict(_attention_shapes(d))
        if i < d["dense"]:
            leaves.update({"ffn_gate_weight": (d["inner"], d["e"]),
                           "ffn_up_weight": (d["inner"], d["e"]),
                           "ffn_down_weight": (d["e"], d["inner"])})
        else:
            leaves.update(_routed_shapes(d))
        for leaf, shape in leaves.items():
            shapes["layer%d_%s" % (i, leaf)] = shape
    if d["mtp"]:
        shapes.update({"mtp_hnorm_gamma": (d["e"],),
                       "mtp_enorm_gamma": (d["e"],),
                       "mtp_proj_weight": (d["e"], 2 * d["e"]),
                       "mtp_final_norm_gamma": (d["e"],)})
        for leaf, shape in dict(_attention_shapes(d),
                                **_routed_shapes(d)).items():
            shapes["mtp_" + leaf] = shape
    return shapes


def init_params(cfg, key, positions=None, dtype=jnp.float32):
    """Seeded weights, made on the device in one traced call: matrices and
    the embedding normal(0, ``initializer_range``), norm gains 1."""
    shapes = param_shapes(cfg)
    std = float(cfg["initializer_range"])
    keys = jax.random.split(key, len(shapes))
    out = {}
    for k, (name, shape) in zip(keys, sorted(shapes.items())):
        if name.endswith("_gamma"):
            out[name] = jnp.ones(shape, dtype)
        else:
            out[name] = (std * jax.random.normal(k, shape, jnp.float32)
                         ).astype(dtype)
    return out


def _rms_norm(x, gamma, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * gamma


def _linear(x, w, lowprec):
    y = fake_quant(x, lowprec) @ fake_quant(w, lowprec).T
    return grad_quant(y, lowprec) if lowprec else y


def _gated_ffn(x, w_gate, w_up, w_down, lowprec):
    return _linear(jax.nn.silu(_linear(x, w_gate, lowprec))
                   * _linear(x, w_up, lowprec), w_down, lowprec)


def rotary_interleaved(x, theta):
    """x (..., S, D), D even.  Pair i of position p — the components
    (2i, 2i+1) — is turned by the angle p · theta^(−2i/D); the result is
    laid out as halves, all first components then all second ones."""
    s, d = x.shape[-2], x.shape[-1]
    first, second = x[..., 0::2], x[..., 1::2]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([first * cos - second * sin,
                            second * cos + first * sin], axis=-1)


def _attend(q, k, v, lowprec):
    """Causal softmax(q kᵀ / sqrt(width of q)) v on (B, H, S, ·), by query
    blocks: one block of queries at a time is scored against every key and
    masked (one shape for all blocks, so that one compiled body serves
    them), and rematerialised in the backward pass."""
    s, scale = q.shape[-2], 1.0 / jnp.sqrt(float(q.shape[-1]))
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


def latent_attention(h, p, cfg, lowprec=None):
    """(B, S, hidden) -> (B, S, hidden); ``p`` holds the ``att_*`` leaves."""
    d = _dims(cfg)
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    b, s, _ = h.shape
    heads, nope, rope, dv = d["heads"], d["nope"], d["rope"], d["dv"]

    def split(t, width):
        return t.reshape(b, s, heads, width).transpose(0, 2, 1, 3)

    c_q = _rms_norm(_linear(h, p["att_q_a_weight"], lowprec),
                    p["att_q_a_norm_gamma"], eps)
    q = split(_linear(c_q, p["att_q_b_weight"], lowprec), nope + rope)
    q = jnp.concatenate([q[..., :nope],
                         rotary_interleaved(q[..., nope:], theta)], axis=-1)
    c = _linear(h, p["att_kv_a_weight"], lowprec)
    c_kv = _rms_norm(c[..., :d["rkv"]], p["att_kv_a_norm_gamma"], eps)
    k_rope = rotary_interleaved(c[..., d["rkv"]:], theta)      # (B, S, rope)
    kv = split(_linear(c_kv, p["att_kv_b_weight"], lowprec), nope + dv)
    k = jnp.concatenate(
        [kv[..., :nope],
         jnp.broadcast_to(k_rope[:, None], (b, heads, s, rope))], axis=-1)
    o = _attend(q, k, kv[..., nope:], lowprec)
    o = o.transpose(0, 2, 1, 3).reshape(b, s, heads * dv)
    return _linear(o, p["att_out_weight"], lowprec)


def route(h, w_router, bias, top_k, scaling, lowprec=None):
    """((T, width) weights, zero off the chosen experts; (T,) margin: how
    far the last chosen score lies above the first one left out)."""
    scores = jax.nn.sigmoid(_linear(h, w_router, lowprec))
    biased = scores + bias
    best, idx = jax.lax.top_k(biased, top_k + 1)
    chosen = jnp.any(idx[:, :top_k, None] == jnp.arange(scores.shape[-1]),
                     axis=1)
    w = jnp.where(chosen, scores, 0.0)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * scaling
    return w, best[:, top_k - 1] - best[:, top_k]


def routed_layer(h, p, cfg, lowprec=None, first=None, held=None):
    """(T, hidden) -> ((T, hidden), (T,) margin).  ``first``/``held``
    default to the configuration's share; the share test passes others."""
    d = _dims(cfg)
    first = d["first"] if first is None else first
    held = d["held"] if held is None else held
    w, margin = route(h, p["moe_router_weight"],
                      jnp.zeros((d["width"],), h.dtype),
                      int(cfg["num_experts_per_tok"]),
                      float(cfg["routed_scaling_factor"]), lowprec)

    @jax.checkpoint
    def expert(y, leaves):
        w_e, gate, up, down = leaves
        return y + w_e[:, None] * _gated_ffn(h, gate, up, down, lowprec), None

    y, _ = jax.lax.scan(
        expert, jnp.zeros_like(h),
        (w[:, first:first + held].T, p["moe_expert_gate_weight"][:held],
         p["moe_expert_up_weight"][:held], p["moe_expert_down_weight"][:held]))
    if d["shared"]:
        y = y + shared_expert(h, p, lowprec)
    return y, margin


def shared_expert(h, p, lowprec=None):
    return _gated_ffn(h, p["moe_shared_gate_weight"],
                      p["moe_shared_up_weight"], p["moe_shared_down_weight"],
                      lowprec)


def block(x, p, cfg, dense, lowprec=None):
    """One block on (B, S, hidden): -> (x, (B·S,) routing margin)."""
    eps = float(cfg["rms_norm_eps"])
    b, s, e = x.shape
    x = x + latent_attention(_rms_norm(x, p["norm1_gamma"], eps), p, cfg,
                             lowprec)
    h = _rms_norm(x, p["norm2_gamma"], eps).reshape(b * s, e)
    if dense:
        f = _gated_ffn(h, p["ffn_gate_weight"], p["ffn_up_weight"],
                       p["ffn_down_weight"], lowprec)
        margin = jnp.full((b * s,), jnp.inf, x.dtype)
    else:
        f, margin = routed_layer(h, p, cfg, lowprec)
    return x + f.reshape(b, s, e), margin


def _leaves(params, prefix):
    n = len(prefix) + 1
    return {k[n:]: v for k, v in params.items() if k.startswith(prefix + "_")}


def _head_rows(x, gamma, params, labels, cfg, lowprec):
    """Each position's cross-entropy (row-major) through a final norm and
    the head (rematerialised: two heads' logits do not wait side by side
    for the backward pass)."""
    @jax.checkpoint
    def rows(x, gamma, head):
        x = _rms_norm(x, gamma, float(cfg["rms_norm_eps"]))
        logp = jax.nn.log_softmax(_linear(x, head, lowprec), axis=-1)
        return -jnp.take_along_axis(
            logp, labels[..., None].astype(jnp.int32), axis=-1).ravel()

    return rows(x, gamma, params["lm_head_weight"])


def loss_fn(params, ids, labels, mtp_labels, cfg, lowprec=None):
    """(L_main + mtp_loss_weight · L_mtp,
    {"main": each position's main loss, "mtp": the module's,
     "margin": each position's least routing margin over the routed
     layers of the main stream}).  ``labels`` are the next tokens,
    ``mtp_labels`` the ones after those."""
    d = _dims(cfg)
    x = params["tok_embed_weight"][ids]
    margin = jnp.full((ids.size,), jnp.inf, x.dtype)
    for i in range(d["layers"]):
        x, m = jax.checkpoint(
            lambda x, p, dense=i < d["dense"]: block(x, p, cfg, dense,
                                                     lowprec))(
            x, _leaves(params, "layer%d" % i))
        margin = jnp.minimum(margin, m)
    rows = {"main": _head_rows(x, params["final_norm_gamma"], params, labels,
                               cfg, lowprec),
            "margin": jax.lax.stop_gradient(margin)}
    loss = jnp.mean(rows["main"])
    if d["mtp"]:
        eps = float(cfg["rms_norm_eps"])
        nxt = params["tok_embed_weight"][labels.astype(jnp.int32)]
        both = jnp.concatenate(
            [_rms_norm(x, params["mtp_hnorm_gamma"], eps),
             _rms_norm(nxt, params["mtp_enorm_gamma"], eps)], axis=-1)
        h = _linear(both, params["mtp_proj_weight"], lowprec)
        h, _ = jax.checkpoint(
            lambda h, p: block(h, p, cfg, False, lowprec))(
            h, _leaves(params, "mtp"))
        rows["mtp"] = _head_rows(h, params["mtp_final_norm_gamma"], params,
                                 mtp_labels, cfg, lowprec)
        loss = loss + float(cfg["mtp_loss_weight"]) * jnp.mean(rows["mtp"])
    return loss, rows


def make_train_step(cfg, lr, momentum, wd, lowprec=None):
    """One SGD-momentum step as the config states it
    (``m = momentum*m - lr*(g + wd*w); w = w + m``):
    ``step(w, m, ids, labels, mtp_labels) -> (rows, {leaf: ‖g‖}, new w,
    new m)``.  ``w`` and ``m`` are donated (680 M float32 parameters: the
    old and the new state do not fit one chip side by side), and the
    gradient leaves the step as its per-leaf norms only."""

    def step(w, m, ids, labels, mtp_labels):
        with jax.default_matmul_precision("highest"):
            (_loss, rows), g = jax.value_and_grad(loss_fn, has_aux=True)(
                w, ids, labels, mtp_labels, cfg, lowprec)
        norms = {k: jnp.linalg.norm(v.ravel()) for k, v in g.items()}
        new_m = {k: momentum * m[k] - lr * (g[k] + wd * w[k]) for k in w}
        new_w = {k: w[k] + new_m[k] for k in w}
        return rows, norms, new_w, new_m

    return jax.jit(step, donate_argnums=(0, 1))
