"""CPU rehearsal of the benchmark.

A tiny cell is added from data files alone (a configuration with
``FABRIC_CNN``-sized shapes and the server-step kernel in interpret
mode, a four-client traffic mix and its limits) and run through the
harness's functions, past the look for a chip.  ``run_cell.py`` itself
must refuse to run without a TPU, and without the program beside it.
"""
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402

from bench_tiny_cell import TINY, add_tiny_cell  # noqa: E402


@pytest.fixture
def tiny_cell(tmp_path):
    return harness.load_cell(add_tiny_cell(tmp_path), TINY, tmp_path,
                             tmp_path)


def test_a_cell_added_from_data_files_runs_through_the_harness(
        tiny_cell, capsys):
    line = harness.run(tiny_cell, seed=2**31 + 77, seconds=0.5, trace=False,
                       t_start=time.perf_counter())
    harness.emit(line)
    out, err = capsys.readouterr()
    last = json.loads(out.strip().splitlines()[-1])
    assert list(last) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] % 4 == 0 and last["attempted"] >= 4
    assert set(last["metrics"]) == {"samples_per_s", "round_p90_s",
                                    "setup_s"}
    assert all(m["value"] > 0 for m in last["metrics"].values())
    assert last["metrics"]["samples_per_s"]["unit"] == "samples/s"
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        last["device"])
    assert last["device"]["platform"] == "cpu"
    names = ["loss_gap", "grad_gap", "delta_gap", "grad_diff", "delta_diff",
             "stale"]
    assert list(last["checks"]) == names
    assert all(c["value"] <= c["limit"] for c in last["checks"].values())
    tail = err.strip().splitlines()[-len(names):]
    assert [ln.split(":")[0] for ln in tail] == [f"check {n}" for n in names]


def _run_cell(cwd: Path, script: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, str(script), "--workload", "fig2.paper16",
         "--seed", "3000000001", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_cell_without_a_tpu_exits_non_zero_with_no_result():
    proc = _run_cell(ROOT, BENCH / "run_cell.py")
    assert proc.returncode != 0
    assert "{" not in proc.stdout
    assert "TPU" in proc.stderr


def test_run_cell_without_the_program_exits_non_zero(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    proc = _run_cell(tmp_path, tmp_path / "bench" / "run_cell.py")
    assert proc.returncode != 0
    assert "{" not in proc.stdout
    assert "No module named 'repro'" in proc.stderr
