"""Plain reference of the decoder in ``configs/laguna-xs2.json``.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernels, no sorting, no
grouped products; keys and values are repeated over a group of query heads.
Independent of ``mxnet_tpu``: it imports nothing of the program and is handed
only what the benchmark made from the seed.

Architecture (``config.json`` of poolside/Laguna-XS.2 fixes every width;
what it does not fix is listed in the configuration file under
``assumed``).  x is a (B, S, E) stream, RMSNorm(x) = x / sqrt(mean(x²) +
eps) · g, no bias in any product.  Layer i reads its kind from the
configuration's per-layer lists (``layer_types``,
``num_attention_heads_per_layer``, ``mlp_layer_types``):

- x = x + Attn(RMSNorm₁(x)); x = x + FFN(RMSNorm₂(x)).
- Attn (H query heads of the layer on H_kv key/value heads of d): [q_h ‖
  g_h] = u W_q a head, g_h one number; k, v = u W_k, u W_v; no norm on q or k; rotary,
  half-split, on the first ``partial_rotary_factor``·d channels of a head
  as ``rope_parameters[kind]`` says — YaRN's inverse frequencies
  (:func:`yarn_inv_freq`) with cos and sin times ``attention_factor`` on
  full layers, plain rotary on window layers; softmax(q kᵀ / √d) v, query
  head h on key/value head h // (H / H_kv), causal, and on window layers
  query i sees keys i − W + 1 … i (W = ``sliding_window``); y = [o_h ·
  sigmoid(g_h)]_h W_o.
- FFN: ``dense`` — (silu(u W_g) ⊙ u W_u) W_d of ``intermediate_size``;
  ``sparse`` — s = sigmoid(u W_r) over all ``deployment.router_width``
  experts; the ``num_experts_per_tok`` largest chosen; w = s at the chosen
  / Σ_chosen s · ``moe_routed_scaling_factor``; Σ w_e E_e(u) over the
  chosen experts that are among the ``num_experts`` held here (from
  ``deployment.first_expert``) — the other experts' part is another chip's
  — plus E_shared(u), ungated.  Experts are a plain loop (a scan) over those
  held, each applied to every token and masked by its weight.
- Head: RMSNorm -> ``lm_head_weight`` (untied); mean token cross-entropy.

Memory: every layer is rematerialised (``jax.checkpoint``); attention runs
by query blocks — on window layers each block against the W + block keys of
its band only — and the head by blocks of rows.

Weight names and layouts are the program's checkpoint's (``*_weight`` is
``(out_features, in_features)``, expert stacks lead with the expert), listed
by :func:`param_shapes`.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np

from .lowprec import fake_quant, grad_quant

Q_BLOCK = 256       # queries scored at a time
HEAD_ROWS = 1024    # rows of logits made at a time

#: what a test or a planted fault can leave out: the sliding window (window
#: layers attend to every key before a query) and YaRN (plain rotary at the
#: full layers' theta, no attention factor)
FAULTS = ("window", "yarn")


def _dims(cfg):
    n = int(cfg["num_hidden_layers"])
    return dict(
        e=int(cfg["hidden_size"]), v=int(cfg["vocab_size"]),
        kv=int(cfg["num_key_value_heads"]), d=int(cfg["head_dim"]),
        inner=int(cfg["intermediate_size"]),
        moe=int(cfg["moe_intermediate_size"]),
        shared=int(cfg["shared_expert_intermediate_size"]),
        held=int(cfg["num_experts"]),
        width=int(cfg["deployment"]["router_width"]),
        first=int(cfg["deployment"]["first_expert"]),
        top_k=int(cfg["num_experts_per_tok"]),
        window=int(cfg["sliding_window"]),
        layers=list(zip(cfg["layer_types"][:n],
                        (int(h) for h in
                         cfg["num_attention_heads_per_layer"][:n]),
                        cfg["mlp_layer_types"][:n])))


def _layer_shapes(d, heads, mlp):
    e, w, kv = d["e"], d["d"], d["kv"]
    shapes = {
        "norm1_gamma": (e,), "norm2_gamma": (e,),
        "att_q_weight": (heads * (w + 1), e), "att_k_weight": (kv * w, e),
        "att_v_weight": (kv * w, e), "att_out_weight": (e, heads * w)}
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
    for i, (_kind, heads, mlp) in enumerate(d["layers"]):
        for leaf, shape in _layer_shapes(d, heads, mlp).items():
            shapes["layer%d_%s" % (i, leaf)] = shape
    return shapes


def init_params(cfg, key, positions=None, dtype=jnp.float32):
    """Seeded weights, made on the device in one traced call: matrices and
    the embedding normal(0, ``initializer_range``); norm gains 1."""
    shapes = param_shapes(cfg)
    std = float(cfg["initializer_range"])
    keys = jax.random.split(key, len(shapes))
    out = {}
    for k, (name, shape) in zip(keys, sorted(shapes.items())):
        if name.endswith("_gamma"):
            w = jnp.ones(shape, jnp.float32)
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


def yarn_inv_freq(rotary_dim, rope):
    """YaRN (arXiv:2309.00071, section 3.2, "NTK-by-parts"): the rotary's
    r / 2 pairs, pair i at wavelength λ_i = 2π theta^(2i/r).  Over the
    original context L a pair makes L / λ_i turns; pairs that make more
    than β_fast keep their frequency, pairs that make fewer than β_slow
    take it divided by ``factor``, and between the two the pairs blend
    along a ramp that is linear in i, its ends rounded outwards to whole
    pairs (down for β_fast, up for β_slow).  Built from that text, in
    float64."""
    r, theta = int(rotary_dim), float(rope["rope_theta"])
    factor = float(rope["factor"])
    length = float(rope["original_max_position_embeddings"])
    i = np.arange(r // 2, dtype=np.float64)
    base = theta ** (-2.0 * i / r)

    def pair_making(turns):     # i with L / λ_i = turns
        return r * math.log(length / (2 * math.pi * turns)) \
            / (2 * math.log(theta))

    lo = max(math.floor(pair_making(float(rope["beta_fast"]))), 0)
    hi = min(math.ceil(pair_making(float(rope["beta_slow"]))), r - 1)
    keep = 1.0 - np.clip((i - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    return base * keep + base / factor * (1.0 - keep)


def rotary_half(x, inv_freq, rotary_dim, scale=1.0):
    """x (B, H, S, d): the first ``rotary_dim`` channels turned, channel i
    with i + rotary_dim/2, by position · inv_freq[i]; cos and sin times
    ``scale``."""
    s, half = x.shape[-2], rotary_dim // 2
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv_freq, jnp.float32)[None, :]
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    a, b = x[..., :half], x[..., half:rotary_dim]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., rotary_dim:]], axis=-1)


def rotary(x, cfg, kind, without=()):
    """The layer kind's rotary on (B, H, S, d)."""
    rope = cfg["rope_parameters"][kind]
    d = x.shape[-1]
    r = int(round(float(rope.get("partial_rotary_factor", 1.0)) * d))
    theta = float(rope["rope_theta"])
    if rope.get("rope_type", "default") == "yarn" and "yarn" not in without:
        return rotary_half(x, yarn_inv_freq(r, rope), r,
                           float(rope["attention_factor"]))
    return rotary_half(x, theta ** (-np.arange(0, r, 2) / float(r)), r)


def _attend(q, k, v, window, lowprec):
    """softmax(q kᵀ / √d) v under the causal mask — with ``window`` W query
    i sees keys i − W + 1 … i only; q (B, H, S, d), k and v (B, H_kv, S,
    d) repeated over each group of H / H_kv query heads; by query blocks,
    one shape for all of them, rematerialised in the backward pass.  Under
    a window a block scores only the W + block keys of its band."""
    s, scale = q.shape[-2], 1.0 / jnp.sqrt(float(q.shape[-1]))
    group = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(t, group, axis=1) for t in (k, v))
    q, k, v = (fake_quant(t, lowprec) for t in (q, k, v))
    step = min(Q_BLOCK, s)
    banded = window is not None and window < s
    if banded:      # W positions of zeros in front: a band never starts < 0
        pad = ((0, 0), (0, 0), (window, 0), (0, 0))
        k, v = jnp.pad(k, pad), jnp.pad(v, pad)
    span = window + step if banded else s

    @jax.checkpoint
    def block(q_b, start):
        lead = start - window if banded else 0      # position of key 0
        k_b, v_b = (jax.lax.dynamic_slice_in_dim(t, start if banded else 0,
                                                 span, axis=2)
                    for t in (k, v))
        scores = jnp.einsum("bhqd,bhkd->bhqk", q_b, k_b) * scale
        if lowprec:
            scores = grad_quant(scores, lowprec)
        qpos = start + jnp.arange(step)[:, None]
        kpos = lead + jnp.arange(span)[None, :]
        seen = qpos >= kpos
        if window is not None:
            seen = seen & (qpos - kpos < window) & (kpos >= 0)
        scores = jnp.where(seen, scores, -jnp.inf)
        probs = fake_quant(jax.nn.softmax(scores, axis=-1), lowprec)
        out = jnp.einsum("bhqk,bhkd->bhqd", probs, v_b)
        return grad_quant(out, lowprec) if lowprec else out

    blocks = q.reshape(q.shape[:2] + (s // step, step, q.shape[-1]))
    outs = jax.lax.map(lambda a: block(*a),
                       (jnp.moveaxis(blocks, 2, 0),
                        jnp.arange(0, s, step)))
    return jnp.moveaxis(outs, 0, 2).reshape(q.shape[:-1] + (v.shape[-1],))


def gated_attention(u, p, cfg, kind, heads, lowprec=None, without=()):
    """(B, S, E) -> (B, S, E); ``p`` holds the ``att_*`` leaves."""
    d = _dims(cfg)
    hk, w = d["kv"], d["d"]
    b, s, _ = u.shape
    qg = _linear(u, p["att_q_weight"], lowprec).reshape(b, s, heads, w + 1)
    q, gate = qg[..., :w], qg[..., w:]
    k = _linear(u, p["att_k_weight"], lowprec).reshape(b, s, hk, w)
    v = _linear(u, p["att_v_weight"], lowprec).reshape(b, s, hk, w)
    q, k = (rotary(t.transpose(0, 2, 1, 3), cfg, kind, without)
            for t in (q, k))
    window = d["window"] if kind == "sliding_attention" \
        and "window" not in without else None
    o = _attend(q, k, v.transpose(0, 2, 1, 3), window, lowprec)
    o = o.transpose(0, 2, 1, 3) * jax.nn.sigmoid(gate)
    return _linear(o.reshape(b, s, heads * w), p["att_out_weight"], lowprec)


def _gated_ffn(x, w_gate, w_up, w_down, lowprec):
    return _linear(jax.nn.silu(_linear(x, w_gate, lowprec))
                   * _linear(x, w_up, lowprec), w_down, lowprec)


def routing(u, p, cfg, lowprec=None):
    """u (T, E) -> (w (T, width): the weight of every chosen expert, 0
    elsewhere; (T,) margin: how far the last chosen score lies above the
    first one left out)."""
    d = _dims(cfg)
    scores = jax.nn.sigmoid(_linear(u, p["moe_router_weight"], lowprec))
    best, idx = jax.lax.top_k(scores, d["top_k"] + 1)
    chosen = jnp.any(idx[:, :d["top_k"], None] == jnp.arange(d["width"]),
                     axis=1)
    w = jnp.where(chosen, scores, 0.0)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) \
        * float(cfg["moe_routed_scaling_factor"])
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
        y = y + _gated_ffn(u, p["moe_shared_gate_weight"],
                           p["moe_shared_up_weight"],
                           p["moe_shared_down_weight"], lowprec)
    return y, margin


def layer(x, p, cfg, kind, heads, mlp, lowprec=None, without=()):
    """One layer on (B, S, E): -> (x, (B·S,) routing margin, +inf in the
    dense layer)."""
    eps = float(cfg["rms_norm_eps"])
    b, s, e = x.shape
    u = _rms_norm(x, p["norm1_gamma"], eps)
    x = x + gated_attention(u, p, cfg, kind, heads, lowprec, without)
    u = _rms_norm(x, p["norm2_gamma"], eps).reshape(b * s, e)
    if mlp == "dense":
        f = _gated_ffn(u, p["ffn_gate_weight"], p["ffn_up_weight"],
                       p["ffn_down_weight"], lowprec)
        margin = jnp.full((b * s,), jnp.inf, x.dtype)
    else:
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
    for i, (kind, heads, mlp) in enumerate(_dims(cfg)["layers"]):
        x, m = jax.checkpoint(
            lambda x, p, kind=kind, heads=heads, mlp=mlp: layer(
                x, p, cfg, kind, heads, mlp, lowprec, without))(
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
    ``w`` and ``m`` are donated (692 M float32 parameters: the old and the
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
