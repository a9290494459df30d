"""The benchmark's yardsticks that need no trace: operation and byte
counts from shapes against hand counts, through the CNN's program module
(``programs/cnn.py``) and through ``flops.py``, the peaks table, the
traffic generator, and the reference's rows against the program's."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import flops  # noqa: E402
import harness  # noqa: E402
import traffic  # noqa: E402

cnn = harness.program_module("cnn")


def _config(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("name, params, fwd_mflop", [
    ("paper_cnn_fig4", 609_258, 25.63),
    ("paper_cnn_fig2", 22_466, 7.84),
])
def test_counts_match_the_hand_counts(name, params, fwd_mflop):
    cfg = _config(name)
    assert cfg["program"] == "cnn"
    assert cnn.param_count(cfg) == params == cfg["params"]
    assert cnn.forward_flops_per_sample(cfg) / 1e6 == pytest.approx(
        fwd_mflop, abs=0.005)
    # backward: a weight gradient per layer, an input gradient per layer
    # but the first, each as many operations as the layer's forward
    first = cnn.layers(cfg)[0]["macs"] * 2
    fwd = cnn.forward_flops_per_sample(cfg)
    assert cnn.train_flops_per_sample(cfg) == 3 * fwd - first
    m = 16
    assert flops.server_step_bytes(cnn, cfg, m) == (m + 4) * params * 4
    assert flops.server_step_flops(cnn, cfg, m) == (2 * m + 6) * params


def test_fig4_conv_shapes_by_hand():
    ls = cnn.layers(_config("paper_cnn_fig4"))
    assert [l["macs"] for l in ls] == [
        32 * 32 * 5 * 5 * 3 * 32, 16 * 16 * 5 * 5 * 32 * 32,
        8 * 8 * 5 * 5 * 32 * 64, 1024 * 512, 512 * 10]


def test_peaks_are_keyed_by_device_kind():
    peaks = harness.load_peaks("TPU v5 lite")
    assert peaks["bf16_flops_per_s"] == 197e12
    assert peaks["hbm_bytes_per_s"] == 819e9
    assert "cloud.google.com" in json.loads(
        (BENCH / "peaks.json").read_text())["source"]
    with pytest.raises(KeyError, match="not in peaks.json"):
        harness.load_peaks("TPU v9 imaginary")


def test_rounds_take_distinct_rows_then_repeat():
    mix = json.loads((BENCH / "traffic" / "paper16.json").read_text())
    shards = [traffic.round_shards(mix, 50, t) for t in range(4)]
    assert all(len(s) == 16 for s in shards)
    rows = [set(range(lo, hi)) for s in shards[:3] for lo, hi in s]
    assert len(set().union(*rows)) == sum(len(r) for r in rows) == 2400
    assert shards[3] == shards[0]
    assert traffic.dataset_rows(mix, 50) == 2400


def test_the_reference_sees_the_programs_rows():
    from repro.data import clustered_images
    seed = 2**31 + 9
    ours = cnn.clustered_images(64, image_size=8, seed=seed)
    theirs = clustered_images(64, image_size=8, seed=seed)
    for a, b in zip(ours, theirs):
        np.testing.assert_array_equal(a, b)
