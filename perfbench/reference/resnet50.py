"""Plain reference of the ResNet in ``configs/resnet50.json``.

Straightforward ``jax.numpy``/``lax`` in float32 under
``jax.default_matmul_precision("highest")``; independent of ``mxnet_tpu``.

Architecture, as the config file states it: He et al. (arXiv:1512.03385)
table 1, 50-layer column — a 7x7/2 stem, 3x3/2 max-pool, four stages of
[3, 4, 6, 3] bottleneck units with 256/512/1024/2048 output channels, global
average pool, a 1000-way classifier — in the pre-activation arrangement
(BN-ReLU-conv, He et al. arXiv:1603.05027) that the source system's
``symbol_resnet.py`` builds and the program follows: a BatchNorm on the raw
input with its gain fixed at 1, the stride on each stage's first 3x3, and
the projection shortcut taken from the unit's first activation.  BatchNorm
uses the batch's own (biased) statistics, eps 2e-5.

Weights are the checkpoint's names and layouts: convolutions OIHW on NCHW
data, ``fc1_weight`` (classes, features).
"""
import jax
import jax.numpy as jnp
from jax import lax

from .lowprec import fake_quant, grad_quant

BN_EPS = 2e-5


def _units(cfg):
    """[(name, in_channels, filters, stride, has_projection)]."""
    out = []
    cin = int(cfg["stem_filters"])
    for stage, (n, filt) in enumerate(zip(cfg["units"],
                                           cfg["stage_filters"])):
        for u in range(n):
            stride = 1 if (stage == 0 or u > 0) else 2
            out.append(("stage%d_unit%d" % (stage + 1, u + 1), cin, filt,
                        stride, u == 0))
            cin = filt
    return out


def param_shapes(cfg):
    c0, k = int(cfg["stem_filters"]), int(cfg["stem_kernel"])
    cin = int(cfg["in_channels"])
    shapes = {"bn_data_gamma": (cin,), "bn_data_beta": (cin,),
              "conv0_weight": (c0, cin, k, k),
              "bn0_gamma": (c0,), "bn0_beta": (c0,)}
    ratio = int(cfg["bottleneck_ratio"])
    for name, cin_u, filt, _stride, proj in _units(cfg):
        mid = filt // ratio
        shapes.update({
            name + "_bn1_gamma": (cin_u,), name + "_bn1_beta": (cin_u,),
            name + "_conv1_weight": (mid, cin_u, 1, 1),
            name + "_bn2_gamma": (mid,), name + "_bn2_beta": (mid,),
            name + "_conv2_weight": (mid, mid, 3, 3),
            name + "_bn3_gamma": (mid,), name + "_bn3_beta": (mid,),
            name + "_conv3_weight": (filt, mid, 1, 1)})
        if proj:
            shapes[name + "_sc_weight"] = (filt, cin_u, 1, 1)
    last = int(cfg["stage_filters"][-1])
    shapes.update({"bn1_gamma": (last,), "bn1_beta": (last,),
                   "fc1_weight": (int(cfg["num_classes"]), last),
                   "fc1_bias": (int(cfg["num_classes"]),)})
    return shapes


def init_params(cfg, key, dtype=jnp.float32):
    """Seeded weights in one traced call: convolutions and the classifier
    He-normal over their fan-in, BatchNorm gains 1, shifts and the bias 0;
    the last convolution of every residual branch is scaled by the
    configuration's ``init.branch_out_scale`` (1 where it gives none)."""
    shapes = param_shapes(cfg)
    branch = float(cfg.get("init", {}).get("branch_out_scale", 1.0))
    keys = jax.random.split(key, len(shapes))
    out = {}
    for k, (name, shape) in zip(keys, sorted(shapes.items())):
        if name.endswith("_gamma"):
            out[name] = jnp.ones(shape, dtype)
        elif name.endswith("_beta") or name.endswith("_bias"):
            out[name] = jnp.zeros(shape, dtype)
        else:
            fan_in = 1
            for d in shape[1:]:
                fan_in *= d
            gain = branch if name.endswith("_conv3_weight") else 1.0
            out[name] = (gain * jnp.sqrt(2.0 / fan_in)
                         * jax.random.normal(k, shape, jnp.float32)
                         ).astype(dtype)
    return out


def _bn(x, gamma, beta, fix_gamma=False):
    mean = jnp.mean(x, axis=(0, 2, 3), keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=(0, 2, 3), keepdims=True)
    y = (x - mean) / jnp.sqrt(var + BN_EPS)
    if not fix_gamma:
        y = y * gamma.reshape(1, -1, 1, 1)
    return y + beta.reshape(1, -1, 1, 1)


def _conv(x, w, stride, pad, lowprec):
    y = lax.conv_general_dilated(
        fake_quant(x, lowprec), fake_quant(w, lowprec),
        window_strides=(stride, stride), padding=((pad, pad), (pad, pad)),
        dimension_numbers=("NCHW", "OIHW", "NCHW"))
    return grad_quant(y, lowprec) if lowprec else y


def _unit(x, p, name, stride, proj, lowprec):
    a1 = jax.nn.relu(_bn(x, p[name + "_bn1_gamma"], p[name + "_bn1_beta"]))
    c1 = _conv(a1, p[name + "_conv1_weight"], 1, 0, lowprec)
    a2 = jax.nn.relu(_bn(c1, p[name + "_bn2_gamma"], p[name + "_bn2_beta"]))
    c2 = _conv(a2, p[name + "_conv2_weight"], stride, 1, lowprec)
    a3 = jax.nn.relu(_bn(c2, p[name + "_bn3_gamma"], p[name + "_bn3_beta"]))
    c3 = _conv(a3, p[name + "_conv3_weight"], 1, 0, lowprec)
    short = _conv(a1, p[name + "_sc_weight"], stride, 0, lowprec) \
        if proj else x
    return c3 + short


def logits_fn(params, images, cfg, lowprec=None):
    """(N, classes) logits of NCHW float32 images.  Every unit, and every
    stage around its units, is rematerialised in the backward pass, so a
    whole 256-image batch fits beside nothing else on one chip (BatchNorm
    couples the rows, so the batch cannot be cut into blocks)."""
    p = params

    @jax.checkpoint
    def stem(x):
        x = _bn(x, p["bn_data_gamma"], p["bn_data_beta"], fix_gamma=True)
        x = _conv(x, p["conv0_weight"], int(cfg["stem_stride"]),
                  int(cfg["stem_kernel"]) // 2, lowprec)
        x = jax.nn.relu(_bn(x, p["bn0_gamma"], p["bn0_beta"]))
        return lax.reduce_window(
            x, -jnp.inf, lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
            ((0, 0), (0, 0), (1, 1), (1, 1)))

    x = stem(images)
    units = _units(cfg)
    start = 0
    for n in cfg["units"]:
        group = units[start:start + n]
        start += n

        def stage(x, group=group):
            for name, _cin, _filt, stride, proj in group:
                x = jax.checkpoint(
                    lambda x, name=name, stride=stride, proj=proj:
                    _unit(x, p, name, stride, proj, lowprec))(x)
            return x

        x = jax.checkpoint(stage)(x)
    x = jax.nn.relu(_bn(x, p["bn1_gamma"], p["bn1_beta"]))
    x = jnp.mean(x, axis=(2, 3))
    y = fake_quant(x, lowprec) @ fake_quant(p["fc1_weight"], lowprec).T
    return (grad_quant(y, lowprec) if lowprec else y) + p["fc1_bias"]


def loss_fn(params, images, labels, cfg, lowprec=None):
    """(mean softmax cross-entropy over the batch, the (N, classes)
    log-probabilities it was taken from)."""
    logp = jax.nn.log_softmax(logits_fn(params, images, cfg, lowprec))
    rows = -jnp.take_along_axis(logp, labels[:, None].astype(jnp.int32),
                                axis=1)[:, 0]
    return jnp.mean(rows), logp


def make_train_step(cfg, lr, momentum, wd, decayed, lowprec=None):
    """One SGD-momentum step as the config states it
    (``m = momentum*m - lr*(g + wd*w); w = w + m``, weight decay on the
    leaves for which ``decayed(name)`` holds): ``step(params, mom, images,
    labels) -> (loss, log-probabilities, grads, new params, new mom)``."""
    names = sorted(param_shapes(cfg))
    wds = {n: (wd if decayed(n) else 0.0) for n in names}

    @jax.jit
    def step(params, mom, images, labels):
        with jax.default_matmul_precision("highest"):
            (loss, logp), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, images, labels, cfg_static, lowprec)
        new_p, new_m = {}, {}
        for n in params:
            m = momentum * mom[n] - lr * (grads[n] + wds[n] * params[n])
            new_m[n] = m
            new_p[n] = params[n] + m
        return loss, logp, grads, new_p, new_m

    cfg_static = cfg
    return step
