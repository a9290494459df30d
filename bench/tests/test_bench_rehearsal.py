"""CPU rehearsal of the benchmark.

Tiny cells are added from files alone (``bench_tiny_cell.py``: a tiny
configuration of the paper CNN, and one of a two-layer MLP with its own
program module and reference) and run through the harness's functions,
past the look for a chip, with no file under ``bench/`` changed.  The
tiny CNN cell reads what it read before configurations named their
program module.  ``run_cell.py`` itself must refuse to run without a
TPU, and without the program beside it.
"""
import hashlib
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

from bench_tiny_cell import (TINY, TINY_MLP, add_tiny_cell,  # noqa: E402
                             add_tiny_mlp_cell)

DATA = BENCH / "tests" / "data"


def _bench_files() -> dict[str, str]:
    return {str(p.relative_to(BENCH)): hashlib.sha256(p.read_bytes())
            .hexdigest() for p in sorted(BENCH.rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


@pytest.fixture(params=[(TINY, add_tiny_cell),
                        (TINY_MLP, add_tiny_mlp_cell)], ids=[TINY, TINY_MLP])
def tiny_cell(request, tmp_path):
    name, add = request.param
    return harness.load_cell(add(tmp_path), name, tmp_path, tmp_path)


def test_a_cell_added_from_data_files_runs_through_the_harness(
        tiny_cell, capsys):
    before = _bench_files()
    line = harness.run(tiny_cell, seed=2**31 + 77, seconds=0.5, trace=False,
                       t_start=time.perf_counter())
    harness.emit(line)
    assert _bench_files() == before
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


def test_a_configuration_naming_a_missing_program_fails_at_load_cell(
        tmp_path):
    spec = add_tiny_mlp_cell(tmp_path)
    path = tmp_path / "configs" / "tiny_mlp.json"
    path.write_text(json.dumps(dict(json.loads(path.read_text()),
                                    program="no_such_model")))
    missing = tmp_path / "programs" / "no_such_model.py"
    with pytest.raises(FileNotFoundError, match=str(missing)):
        harness.load_cell(spec, TINY_MLP, tmp_path, tmp_path)


_CHILD = """
import json, os, sys, tempfile, time
from pathlib import Path
os.sched_setaffinity(0, {min(os.sched_getaffinity(0))})
bench = Path(sys.argv[1])
sys.path[:0] = [str(bench), str(bench / "tests"), str(bench.parent / "src")]
import harness
from bench_tiny_cell import TINY, add_tiny_cell
out = {}
with tempfile.TemporaryDirectory() as d:
    cell = harness.load_cell(add_tiny_cell(Path(d)), TINY, Path(d), Path(d))
    for seed in map(int, sys.argv[2:]):
        line = harness.run(cell, seed=seed, seconds=0.3, trace=False,
                           t_start=time.perf_counter(), say=lambda m: None)
        out[str(seed)] = {k: c["value"] for k, c in line["checks"].items()}
print(json.dumps(out))
"""


def test_the_tiny_cell_reads_as_before_configurations_named_programs(
        tmp_path):
    before = json.loads((DATA / "tiny_cnn_before_programs.json").read_text())
    cell = harness.load_cell(add_tiny_cell(tmp_path), TINY, tmp_path,
                             tmp_path)
    for seed, sha in before["rows_sha256"].items():
        h = hashlib.sha256()
        for x, y in cell.program.round_rows(cell.config, cell.traffic,
                                            int(seed), 3):
            h.update(x.tobytes())
            h.update(y.tobytes())
        assert h.hexdigest() == sha, seed
    # the recorded checks come from one core with XLA's CPU backend
    # single-threaded, where its sums run in a fixed order
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_cpu_multi_thread_eigen=false")
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD, str(BENCH), *before["checks"]],
        env=env, capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert json.loads(proc.stdout.splitlines()[-1]) == before["checks"]


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
