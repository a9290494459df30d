"""The one traffic generator: turns a traffic mix's parameters, a
configuration and a seed into the rounds' shards and the clients' rows.

A mix (``bench/traffic/<mix>.json``) gives:

* ``clients``: remote clients that connect to the server, each with
  ``client_speed`` (0: compute as fast as the host allows);
* ``shards_per_round``: M, the shards of ``batch_size`` rows each round
  hands out, and the gradients the server step folds;
* ``straggler_policy``, ``barrier_k``: how the round's barrier closes;
* ``distinct_rounds``: rounds of distinct rows before the rows repeat,
  so that the first rounds, which the reference follows, all differ;
* ``members``, ``queue_shards``, ``lease_timeout_s``,
  ``round_timeout_s``: the federation's members, ticket-queue shards,
  lease timeout and the limit on one round;
* ``redistribute_min_s``: how long a leased ticket stays out before an
  idle client may run it again (the paper's speculative redistribution;
  each such copy is a whole gradient computed again on the host).
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

REQUIRED = ("clients", "shards_per_round", "client_speed",
            "straggler_policy", "barrier_k", "distinct_rounds", "members",
            "queue_shards", "lease_timeout_s", "redistribute_min_s",
            "round_timeout_s")


def load(path: Path) -> dict:
    mix = json.loads(Path(path).read_text())
    missing = [k for k in REQUIRED if k not in mix]
    if missing:
        raise KeyError(f"traffic mix {path} lacks {missing}")
    if mix["distinct_rounds"] < 3:
        raise ValueError(f"traffic mix {path}: distinct_rounds must be at "
                         f"least 3, the rounds the reference follows")
    return mix


def dataset_rows(mix: dict, batch: int) -> int:
    return mix["shards_per_round"] * batch * mix["distinct_rounds"]


def round_shards(mix: dict, batch: int, round_index: int
                 ) -> list[tuple[int, int]]:
    """The ``(lo, hi)`` row slices of round ``round_index``'s shards."""
    m = mix["shards_per_round"]
    base = (round_index % mix["distinct_rounds"]) * m * batch
    return [(base + i * batch, base + (i + 1) * batch) for i in range(m)]


def clustered_images(n: int, *, num_classes: int = 10, image_size: int = 32,
                     channels: int = 3, seed: int = 0, spread: float = 0.35,
                     means_seed: int = 1234):
    """Gaussian class-cluster images and their labels: the rows the
    clients train on, made from ``seed`` (the same arithmetic as the
    program's ``repro.data.clustered_images``, kept here so that the
    reference sees the rows without taking them from the program)."""
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
    batch = cfg["batch_size"]
    images, labels = clustered_images(
        dataset_rows(mix, batch), num_classes=cfg["num_classes"],
        image_size=cfg["image_size"], channels=cfg["in_channels"],
        seed=seed)
    out = []
    for t in range(rounds):
        lo = round_shards(mix, batch, t)[0][0]
        hi = round_shards(mix, batch, t)[-1][1]
        out.append((images[lo:hi], labels[lo:hi]))
    return out
