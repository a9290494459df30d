"""The paper's CNN family (arXiv:1503.05743, Figs. 2 and 4) as the
harness drives it: the program's config and gradient task for a
configuration file, the rows the reference follows, and the counts.

A configuration names this module by ``"program": "cnn"``.  The rows are
Gaussian class-cluster images of ``image_size`` x ``image_size`` x
``in_channels``, made from the seed with the same arithmetic as the
program's ``repro.data.clustered_images`` but without it, so that the
reference sees the clients' rows without taking them from the program.
"""
from __future__ import annotations

import numpy as np

import traffic

#: the gradient program's stable name on the profiler's timeline
GRAD_PROGRAM = "cnn_loss_and_grads"
#: the name the gradient task is registered under
TASK_NAME = "cnn_grad_shard"


def program_config(cfg: dict):
    """The program's ``CNNConfig`` for a configuration file."""
    from repro.configs.paper_cnn import CNNConfig, ConvSpec
    return CNNConfig(name=cfg["name"], image_size=cfg["image_size"],
                     in_channels=cfg["in_channels"],
                     num_classes=cfg["num_classes"],
                     convs=tuple(ConvSpec(out_channels=c["out_channels"],
                                          kernel=c["kernel"], pool=c["pool"])
                                 for c in cfg["convs"]),
                     fc_hidden=tuple(cfg["fc_hidden"]),
                     batch_size=cfg["batch_size"])


def rows_per_shard(cfg: dict) -> int:
    return cfg["batch_size"]


def grad_task(cfg: dict, pcfg, n_rows: int, seed: int):
    """The program's picklable gradient task over ``n_rows`` rows made
    from ``seed``; a shard is a ``(lo, hi)`` slice of them."""
    from repro.models.cnn import CnnGradShard
    return CnnGradShard(pcfg, n_rows=n_rows, seed=seed)


def clustered_images(n: int, *, num_classes: int = 10, image_size: int = 32,
                     channels: int = 3, seed: int = 0, spread: float = 0.35,
                     means_seed: int = 1234):
    """Gaussian class-cluster images and their labels: the rows the
    clients train on, made from ``seed``."""
    rng = np.random.default_rng(seed)
    means = np.random.default_rng(means_seed).normal(
        0.0, 1.0, (num_classes, image_size, image_size, channels))
    labels = rng.integers(0, num_classes, size=n)
    imgs = (means[labels]
            + rng.normal(0.0, spread,
                         (n, image_size, image_size, channels)))
    return imgs.astype(np.float32), labels.astype(np.int32)


def round_rows(cfg: dict, mix: dict, seed: int, rounds: int):
    """``(images, labels)`` of each of the first ``rounds`` rounds, all
    of their shards together."""
    batch = rows_per_shard(cfg)
    images, labels = clustered_images(
        traffic.dataset_rows(mix, batch), num_classes=cfg["num_classes"],
        image_size=cfg["image_size"], channels=cfg["in_channels"],
        seed=seed)
    out = []
    for t in range(rounds):
        shards = traffic.round_shards(mix, batch, t)
        lo, hi = shards[0][0], shards[-1][1]
        out.append((images[lo:hi], labels[lo:hi]))
    return out


# -- counts, from the configuration's sizes alone ----------------------------
#
# A multiply-add is two operations.


def layers(cfg: dict) -> list[dict]:
    """Every weighted layer with its multiply-adds per sample.

    Convolutions are "SAME"-padded at stride 1, each followed by a
    ``pool`` x ``pool`` max-pool; the classifier is a chain of dense
    layers from the flattened features to ``num_classes``."""
    out = []
    size, cin = cfg["image_size"], cfg["in_channels"]
    for conv in cfg["convs"]:
        k, cout = conv["kernel"], conv["out_channels"]
        out.append({"kind": "conv", "weights": k * k * cin * cout,
                    "bias": cout, "macs": size * size * k * k * cin * cout})
        size //= conv["pool"]
        cin = cout
    dims = [size * size * cin, *cfg["fc_hidden"], cfg["num_classes"]]
    for d_in, d_out in zip(dims, dims[1:]):
        out.append({"kind": "fc", "weights": d_in * d_out, "bias": d_out,
                    "macs": d_in * d_out})
    return out


def param_count(cfg: dict) -> int:
    """Parameters the server step updates: all of the network's."""
    return sum(l["weights"] + l["bias"] for l in layers(cfg))


def forward_flops_per_sample(cfg: dict) -> int:
    """Operations of the convolutions and dense layers of one sample's
    forward pass.  Bias, activation, pooling and the softmax are left
    out, as is usual for a model's operation count."""
    return 2 * sum(l["macs"] for l in layers(cfg))


def train_flops_per_sample(cfg: dict) -> int:
    """Forward and backward operations of one sample: the forward pass,
    the weight gradient of every layer, and the input gradient of every
    layer but the first (the images need none)."""
    ls = layers(cfg)
    fwd = 2 * sum(l["macs"] for l in ls)
    return fwd + fwd + 2 * sum(l["macs"] for l in ls[1:])
