"""The one traffic generator: turns a traffic mix's parameters into the
rounds' shards, the row slices the clients train on.  What a row holds,
and how many rows a shard takes, is the configuration's program
module's (``programs/<program>.py``).

A mix (``bench/traffic/<mix>.json``) gives:

* ``clients``: remote clients that connect to the server, each with
  ``client_speed`` (0: compute as fast as the host allows);
* ``shards_per_round``: M, the shards each round hands out, and the
  gradients the server step folds;
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

