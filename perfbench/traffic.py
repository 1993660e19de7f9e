"""The one traffic generator: reads a cell's ``traffic`` parameters and
makes, from ``--seed``, what the cell's driver feeds the system.

Sizes are fixed in the cell file and only the contents (token ids, pixels)
come from ``--seed``, so runs with different seeds do the same amount of work.

Kinds of traffic (``traffic["kind"]``):

``image_batches``   a pool of host float32 NCHW batches with integer labels,
                    cycled by the driver (``pool``, ``batch``);
``token_batches``   a pool of (ids, next-token labels) batches (``pool``,
                    ``batch``, ``seq``);
"""
import numpy as np


def _rng(seed, stream):
    return np.random.default_rng([int(seed), int(stream)])


def image_batches(traffic, config, seed):
    """[(images float32 (B,C,H,W) in [0,1), labels float32 (B,))] on the
    host, ``pool`` of them."""
    rng = _rng(seed, 1)
    b, c, s = int(traffic["batch"]), int(config["in_channels"]), \
        int(config["image_size"])
    out = []
    for _ in range(int(traffic["pool"])):
        images = rng.random((b, c, s, s), dtype=np.float32)
        labels = rng.integers(0, int(config["num_classes"]), (b,))
        out.append((images, labels.astype(np.float32)))
    return out


def token_batches(traffic, config, seed):
    """[(ids int32 (B,S), labels float32 (B,S))]: uniform ids, the label of
    a position is the next position's id (the last one wraps)."""
    rng = _rng(seed, 2)
    b, s = int(traffic["batch"]), int(traffic["seq"])
    out = []
    for _ in range(int(traffic["pool"])):
        ids = rng.integers(0, int(config["vocab_size"]), (b, s),
                           dtype=np.int64)
        labels = np.roll(ids, -1, axis=1)
        out.append((ids.astype(np.int32), labels.astype(np.float32)))
    return out
