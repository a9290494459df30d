"""A tiny cell for the CPU tests, written from data files alone: a
configuration with ``FABRIC_CNN``-sized shapes and the server-step kernel
in interpret mode, a four-client traffic mix, and a real cell's limits."""
import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
TINY = "tiny.tiny4"


def add_tiny_cell(tmp: Path, limits_of: str = "fig2.paper16"):
    """Write a tiny configuration, traffic mix and limits under ``tmp``
    and return the benchmark spec with the cell added."""
    for d in ("configs", "traffic", "checks"):
        (tmp / d).mkdir()
    config = json.loads((BENCH / "configs" / "paper_cnn_fig2.json")
                        .read_text())
    config.update(name="tiny_cnn", image_size=16, fc_hidden=[],
                  convs=[{"out_channels": 8, "kernel": 5, "pool": 2}] * 2,
                  batch_size=32, server_step="interpret", params=None)
    (tmp / "configs" / "tiny_cnn.json").write_text(json.dumps(config))
    shutil.copy(BENCH / "configs" / f"{config['reference']}.py",
                tmp / "configs")
    mix = json.loads((BENCH / "traffic" / "paper16.json").read_text())
    mix.update(clients=4, shards_per_round=4)
    (tmp / "traffic" / "tiny4.json").write_text(json.dumps(mix))
    shutil.copy(BENCH / "checks" / f"{limits_of}.json",
                tmp / "checks" / f"{TINY}.json")
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny_cnn", "source": "test",
                            "file": "configs/tiny_cnn.json", "reduced": [],
                            "why": "test"})
    spec["workloads"].append({"name": TINY, "config": "tiny_cnn",
                              "traffic": "tiny4", "chips": 1, "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(TINY)
    return spec
