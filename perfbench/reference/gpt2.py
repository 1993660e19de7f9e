"""Plain reference of the decoder-only LM in ``configs/gpt2-medium.json``.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernels, no cache, no
batching tricks.  Independent of ``mxnet_tpu``: it imports nothing of the
program and is handed only what the benchmark made from the seed (weights,
token ids).

Architecture (GPT-2, Radford et al. 2019, as the config file states it):
token + learned position embeddings; ``n_layer`` pre-LayerNorm blocks of
causal multi-head self-attention and a two-matrix FFN, each with a residual
connection; a final LayerNorm; an output head.  Departures from the
published model, both stated in the config file and followed here because
the reference follows the file: the FFN activation is ReLU where GPT-2 has
``gelu_new``; the head has its own weight and bias where GPT-2 ties it to
the token embedding.

Weight names and layouts are the checkpoint's (``<layer>_weight`` is
``(out_features, in_features)``), listed by :func:`param_shapes`.
"""
import jax
import jax.numpy as jnp

from .lowprec import fake_quant, grad_quant

LN_EPS = 1e-5
LAYER_LEAVES = ("ln1_gamma", "ln1_beta", "att_qkv_weight", "att_qkv_bias",
                "att_out_weight", "att_out_bias", "ln2_gamma", "ln2_beta",
                "ffn1_weight", "ffn1_bias", "ffn2_weight", "ffn2_bias")


def param_shapes(cfg, positions=None):
    """{name: shape} of every weight, in the checkpoint's names."""
    e, v = int(cfg["n_embd"]), int(cfg["vocab_size"])
    inner = int(cfg["n_inner"])
    pos = int(positions or cfg["n_positions"])
    shapes = {"tok_embed_weight": (v, e), "pos_embed_weight": (pos, e)}
    per_layer = {"ln1_gamma": (e,), "ln1_beta": (e,),
                 "att_qkv_weight": (3 * e, e), "att_qkv_bias": (3 * e,),
                 "att_out_weight": (e, e), "att_out_bias": (e,),
                 "ln2_gamma": (e,), "ln2_beta": (e,),
                 "ffn1_weight": (inner, e), "ffn1_bias": (inner,),
                 "ffn2_weight": (e, inner), "ffn2_bias": (e,)}
    for i in range(int(cfg["n_layer"])):
        for leaf, shape in per_layer.items():
            shapes["layer%d_%s" % (i, leaf)] = shape
    shapes.update({"final_ln_gamma": (e,), "final_ln_beta": (e,),
                   "lm_head_weight": (v, e), "lm_head_bias": (v,)})
    return shapes


def init_params(cfg, key, positions=None, dtype=jnp.float32):
    """Seeded weights, made on the device in one traced call: matrices and
    embeddings normal(0, 0.02) (GPT-2's ``initializer_range``), LayerNorm
    gains 1, every bias and shift 0."""
    shapes = param_shapes(cfg, positions)
    keys = jax.random.split(key, len(shapes))
    out = {}
    for k, (name, shape) in zip(keys, sorted(shapes.items())):
        if name.endswith("_gamma"):
            out[name] = jnp.ones(shape, dtype)
        elif name.endswith("_bias") or name.endswith("_beta"):
            out[name] = jnp.zeros(shape, dtype)
        else:
            out[name] = (0.02 * jax.random.normal(k, shape, jnp.float32)
                         ).astype(dtype)
    return out


def _layer_norm(x, gamma, beta):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * gamma + beta


def _linear(x, w, b, lowprec):
    y = fake_quant(x, lowprec) @ fake_quant(w, lowprec).T
    return (grad_quant(y, lowprec) if lowprec else y) + b


def _block(x, p, n_head, lowprec):
    b, s, e = x.shape
    d = e // n_head
    h = _layer_norm(x, p["ln1_gamma"], p["ln1_beta"])
    qkv = _linear(h, p["att_qkv_weight"], p["att_qkv_bias"], lowprec)
    q, k, v = jnp.split(qkv, 3, axis=-1)

    def heads(t):
        return t.reshape(b, s, n_head, d).transpose(0, 2, 1, 3)

    q, k, v = (fake_quant(heads(t), lowprec) for t in (q, k, v))
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(float(d))
    if lowprec:
        scores = grad_quant(scores, lowprec)
    mask = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(mask, scores, -jnp.inf)
    probs = fake_quant(jax.nn.softmax(scores, axis=-1), lowprec)
    att = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
    if lowprec:
        att = grad_quant(att, lowprec)
    att = att.transpose(0, 2, 1, 3).reshape(b, s, e)
    x = x + _linear(att, p["att_out_weight"], p["att_out_bias"], lowprec)
    h = _layer_norm(x, p["ln2_gamma"], p["ln2_beta"])
    h = jax.nn.relu(_linear(h, p["ffn1_weight"], p["ffn1_bias"], lowprec))
    return x + _linear(h, p["ffn2_weight"], p["ffn2_bias"], lowprec)


def stack_layers(params, n_layer):
    """(outer leaves, {leaf: (n_layer, ...) stacked}) so that one scanned
    block serves every layer."""
    outer = {k: v for k, v in params.items() if not k.startswith("layer")}
    stacked = {leaf: jnp.stack([params["layer%d_%s" % (i, leaf)]
                                for i in range(n_layer)])
               for leaf in LAYER_LEAVES}
    return outer, stacked


def unstack_layers(outer, stacked):
    out = dict(outer)
    for leaf, arr in stacked.items():
        for i in range(arr.shape[0]):
            out["layer%d_%s" % (i, leaf)] = arr[i]
    return out


def logits_fn(outer, stacked, ids, n_head, lowprec=None):
    """(B, S, vocab) logits of token ids (B, S): the full forward pass,
    layer by layer (each block rematerialised, so a long batch fits)."""
    s = ids.shape[1]
    x = outer["tok_embed_weight"][ids] + outer["pos_embed_weight"][:s][None]

    @jax.checkpoint
    def body(x, p):
        return _block(x, p, n_head, lowprec), None

    x, _ = jax.lax.scan(body, x, stacked)
    x = _layer_norm(x, outer["final_ln_gamma"], outer["final_ln_beta"])
    return _linear(x, outer["lm_head_weight"], outer["lm_head_bias"],
                   lowprec)


def loss_fn(outer, stacked, ids, labels, n_head, lowprec=None):
    """(mean next-token cross-entropy over every position of the batch,
    each position's, row-major)."""
    logits = logits_fn(outer, stacked, ids, n_head, lowprec)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    rows = -jnp.take_along_axis(logp, labels[..., None].astype(jnp.int32),
                                axis=-1).ravel()
    return jnp.mean(rows), rows


def make_train_step(cfg, lr, momentum, wd, lowprec=None):
    """One SGD-momentum step as the config states it
    (``m = momentum*m - lr*(g + wd*w); w = w + m``): returns
    ``step(outer, stacked, mom_outer, mom_stacked, ids, labels) ->
    (loss, each position's loss, grads, new weights, new momentum)`` on
    stacked trees."""
    n_head = int(cfg["n_head"])

    @jax.jit
    def step(outer, stacked, m_outer, m_stacked, ids, labels):
        with jax.default_matmul_precision("highest"):
            (loss, rows), (g_o, g_s) = jax.value_and_grad(
                loss_fn, argnums=(0, 1), has_aux=True)(
                    outer, stacked, ids, labels, n_head, lowprec)

        def upd(w, g, m):
            m = momentum * m - lr * (g + wd * w)
            return w + m, m

        new_o = {k: upd(outer[k], g_o[k], m_outer[k]) for k in outer}
        new_s = {k: upd(stacked[k], g_s[k], m_stacked[k]) for k in stacked}
        return (loss, rows, (g_o, g_s),
                ({k: v[0] for k, v in new_o.items()},
                 {k: v[0] for k, v in new_s.items()}),
                ({k: v[1] for k, v in new_o.items()},
                 {k: v[1] for k, v in new_s.items()}))

    return step
