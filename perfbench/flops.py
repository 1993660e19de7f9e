"""Operations and bytes an algorithm needs, from shapes alone.

The yardstick for every ``*_mfu*`` and ``*_roofline`` metric.  Nothing here
looks at a compiled program: XLA's cost analysis does not see inside Mosaic
calls and counts recomputation (PERF.md, verdict on ``bench.py``), so the
benchmark counts what the mathematics needs and divides by measured time.

Conventions (stated once, used everywhere):

- one multiply-add = 2 operations;
- a training step = forward + backward = 3 x the forward's operations
  (recomputation is not counted);
- causal attention counts the lower triangle only (half of S x S): that is
  what the algorithm needs, whatever a kernel chooses to compute and mask.
"""
import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def load_peaks(device_kind, path=None):
    """Peaks of one chip by ``device_kind``.  Unknown kind -> KeyError."""
    with open(path or os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError("no peaks for device_kind %r in peaks.json (have %s)"
                       % (device_kind, sorted(k for k in table
                                              if not k.startswith("_"))))
    return table[device_kind]


# ----------------------------------------------------------------------
# ResNet (bottleneck, as perfbench/configs/resnet50.json describes it)
# ----------------------------------------------------------------------
def resnet_forward_macs(cfg):
    """Multiply-adds of one image's forward pass: convolutions and the
    classifier.  BatchNorm, ReLU, pooling and the residual adds are not
    matrix work and are left out, as the usual 4.1 G figure leaves them.

    The graph is the pre-activation bottleneck the program builds: in a
    stage's first unit the 1x1 reduce runs at the input resolution and the
    3x3 carries the stride; the projection shortcut is 1x1 with the stride.
    """
    h = w = int(cfg["image_size"])
    cin = int(cfg["in_channels"])
    macs = 0
    k = int(cfg["stem_kernel"])
    s = int(cfg["stem_stride"])
    stem = int(cfg["stem_filters"])
    h, w = h // s, w // s
    macs += h * w * stem * cin * k * k
    h, w = h // int(cfg["stem_pool_stride"]), w // int(cfg["stem_pool_stride"])
    cin = stem
    for stage, (units, filt) in enumerate(zip(cfg["units"],
                                               cfg["stage_filters"])):
        for u in range(units):
            stride = 1 if (stage == 0 or u > 0) else 2
            mid = filt // int(cfg["bottleneck_ratio"])
            macs += h * w * cin * mid                      # 1x1 reduce
            ho, wo = h // stride, w // stride
            macs += ho * wo * mid * mid * 9                # 3x3
            macs += ho * wo * mid * filt                   # 1x1 expand
            if u == 0:
                macs += ho * wo * cin * filt               # projection
            h, w, cin = ho, wo, filt
    macs += cin * int(cfg["num_classes"])
    return macs


def resnet_train_step_flops(cfg, batch):
    return 3 * 2 * resnet_forward_macs(cfg) * int(batch)


def resnet_train_step(cfg, counters):
    """Operations of one training step from a run's counters: what a
    configuration names under ``"flops"`` (``common.named_function``)."""
    return resnet_train_step_flops(cfg, counters["batch"])


# ----------------------------------------------------------------------
# decoder-only LM (as perfbench/configs/gpt2-medium.json describes it)
# ----------------------------------------------------------------------
def lm_matmul_params(cfg):
    """Weights that take part in a matrix product for every token: the
    four projections and two FFN matrices of each layer, and the head.
    Embedding tables are gathers, biases and norms are vector work."""
    e = int(cfg["n_embd"])
    inner = int(cfg["n_inner"])
    per_layer = 3 * e * e + e * e + 2 * e * inner
    return int(cfg["n_layer"]) * per_layer + e * int(cfg["vocab_size"])


def lm_attention_flops_per_token(cfg, seq, causal=True, train=True):
    """Score and value products per token at context ``seq``: forward is
    2 products x 2 ops x seq x n_embd per layer (= 4 L d s), a training
    step three times that (12 L d s); causal halves it."""
    full = 4 * int(cfg["n_layer"]) * int(cfg["n_embd"]) * int(seq)
    if train:
        full *= 3
    return full // 2 if causal else full


def lm_train_step_flops(cfg, batch, seq, causal=True):
    per_token = 6 * lm_matmul_params(cfg) \
        + lm_attention_flops_per_token(cfg, seq, causal=causal, train=True)
    return per_token * int(batch) * int(seq)


def lm_train_step(cfg, counters):
    """As ``resnet_train_step``, for the LM: causal attention counted as
    its lower triangle."""
    return lm_train_step_flops(cfg, counters["batch"], counters["seq"],
                               causal=True)


# ----------------------------------------------------------------------
# flash-attention forward, one call
# ----------------------------------------------------------------------
def flash_forward_call(batch, heads, seq_q, seq_k, head_dim, itemsize,
                       causal=True):
    """(operations, bytes) one forward call needs: QK^T and PV, and one
    read of q, k, v plus one write of o and of a float32 log-sum-exp per
    query row."""
    ops = 4 * batch * heads * seq_q * seq_k * head_dim
    if causal:
        ops //= 2
    nbytes = itemsize * batch * heads * head_dim * (2 * seq_q + 2 * seq_k) \
        + 4 * batch * heads * seq_q
    return ops, nbytes


def roofline_seconds(ops, nbytes, peak_flops, peak_bytes_per_s):
    """Least time the chip could take and which bound holds."""
    t_ops = ops / float(peak_flops)
    t_mem = nbytes / float(peak_bytes_per_s)
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
