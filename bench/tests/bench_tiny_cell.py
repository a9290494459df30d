"""Tiny cells for the CPU tests, each written from files alone under a
temporary directory:

* ``tiny.tiny4``: a configuration of the paper CNN with ``FABRIC_CNN``-
  sized shapes and the server-step kernel in interpret mode, its program
  module, a four-client traffic mix, and a real cell's limits;
* ``tiny.mlp4``: a configuration of another model, a two-layer MLP, with
  its own program module, reference, traffic mix and limits
  (``tiny_mlp/``), as a configuration of a new model joins the benchmark.
"""
import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
TINY = "tiny.tiny4"
TINY_MLP = "tiny.mlp4"
MLP_FILES = Path(__file__).resolve().parent / "tiny_mlp"


def with_cell(name: str, config: str, mix: str) -> dict:
    """``BENCHMARK.json``'s spec with the cell ``name`` added, its
    configuration's file at ``configs/<config>.json``, in every metric."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": config, "source": "test",
                            "file": f"configs/{config}.json", "reduced": [],
                            "why": "test"})
    spec["workloads"].append({"name": name, "config": config,
                              "traffic": mix, "chips": 1, "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(name)
    return spec


def add_tiny_cell(tmp: Path, limits_of: str = "fig2.paper16"):
    """Write a tiny configuration, its program module, traffic mix and
    limits under ``tmp`` and return the benchmark spec with the cell
    added."""
    for d in ("configs", "programs", "traffic", "checks"):
        (tmp / d).mkdir()
    config = json.loads((BENCH / "configs" / "paper_cnn_fig2.json")
                        .read_text())
    config.update(name="tiny_cnn", image_size=16, fc_hidden=[],
                  convs=[{"out_channels": 8, "kernel": 5, "pool": 2}] * 2,
                  batch_size=32, server_step="interpret", params=None)
    (tmp / "configs" / "tiny_cnn.json").write_text(json.dumps(config))
    shutil.copy(BENCH / "configs" / f"{config['reference']}.py",
                tmp / "configs")
    shutil.copy(BENCH / "programs" / f"{config['program']}.py",
                tmp / "programs")
    mix = json.loads((BENCH / "traffic" / "paper16.json").read_text())
    mix.update(clients=4, shards_per_round=4)
    (tmp / "traffic" / "tiny4.json").write_text(json.dumps(mix))
    shutil.copy(BENCH / "checks" / f"{limits_of}.json",
                tmp / "checks" / f"{TINY}.json")
    return with_cell(TINY, "tiny_cnn", "tiny4")


def add_tiny_mlp_cell(tmp: Path):
    """Copy the tiny MLP's configuration, program module, reference,
    traffic mix and limits under ``tmp`` and return the benchmark spec
    with its cell added."""
    shutil.copytree(MLP_FILES, tmp, dirs_exist_ok=True,
                    ignore=shutil.ignore_patterns("__pycache__"))
    return with_cell(TINY_MLP, "tiny_mlp", "tiny_mlp4")
