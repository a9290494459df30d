"""Property tests for the protocol-v2 wire codecs (repro.core.wire).

Two invariants carry the whole binary protocol:

  * **Codec identity** — ``decode_binary(*encode_binary(x))`` is
    bit-exact for every pytree of arrays (any dtype including bfloat16,
    empty arrays, 0-d shapes, nested dicts/lists/tuples/dataclasses).
  * **Delta identity** — for ANY publish history, a client that applies
    the registry's changed-leaves delta to its cached full payload ends
    up bit-for-bit identical to a client that downloaded the full
    payload.  Deltas are an optimisation, never an approximation.
"""
import dataclasses

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st
from hypothesis.extra.numpy import array_shapes, arrays

from repro.core.distributor import (DELTA_HISTORY, HttpServerBase,
                                    build_delta_fetched)
from repro.core.split_parallel import TrainState
from repro.core.wire import (DeltaApplyError, ProtocolError, apply_delta,
                             decode_binary, encode_binary, flatten_tree,
                             leaf_equal)

try:
    import ml_dtypes
    BF16 = np.dtype(ml_dtypes.bfloat16)
except ImportError:          # pragma: no cover - jax always ships ml_dtypes
    ml_dtypes = None
    BF16 = None


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def assert_trees_bitequal(a, b):
    """Structural + bit-exact leaf equality (NaN == NaN)."""
    fa, fb = flatten_tree(a), flatten_tree(b)
    assert fa.keys() == fb.keys()
    for path in fa:
        assert leaf_equal(fa[path], fb[path]), path


def roundtrip(obj):
    manifest, buffer = encode_binary(obj)
    # the manifest must survive a JSON hop (it rides in the header frame)
    import json
    manifest = json.loads(json.dumps(manifest))
    return decode_binary(manifest, buffer)


# ---------------------------------------------------------------------------
# codec identity
# ---------------------------------------------------------------------------


NUMERIC_DTYPES = ["float32", "float64", "float16", "int8", "int32",
                  "int64", "uint8", "uint16"]


@settings(max_examples=60, deadline=None)
@given(arrays(dtype=st.sampled_from(NUMERIC_DTYPES),
              shape=array_shapes(min_dims=0, max_dims=4, min_side=0,
                                 max_side=5)))
def test_roundtrip_single_array(arr):
    out = roundtrip(arr)
    assert out.dtype == arr.dtype and out.shape == arr.shape
    assert out.tobytes() == arr.tobytes()


@settings(max_examples=40, deadline=None)
@given(st.lists(arrays(dtype=st.sampled_from(NUMERIC_DTYPES),
                       shape=array_shapes(min_dims=0, max_dims=3,
                                          min_side=0, max_side=4)),
                min_size=0, max_size=6),
       st.integers(min_value=-5, max_value=5))
def test_roundtrip_mixed_pytree(arrs, scalar):
    obj = {"arrays": arrs,
           "nested": {"t": tuple(arrs[:2]), "s": scalar, "none": None},
           "strings": ["alpha", "beta"], "flag": True}
    assert_trees_bitequal(roundtrip(obj), obj)


def test_roundtrip_bfloat16_bitexact():
    if BF16 is None:
        pytest.skip("ml_dtypes unavailable")
    rng = np.random.default_rng(7)
    arr = rng.standard_normal((17, 3)).astype(BF16)
    out = roundtrip(arr)
    assert out.dtype == BF16 and out.shape == arr.shape
    assert out.tobytes() == arr.tobytes()


def test_roundtrip_special_floats_bitexact():
    arr = np.array([np.nan, np.inf, -np.inf, -0.0, np.finfo(np.float32).tiny],
                   np.float32)
    out = roundtrip(arr)
    assert out.tobytes() == arr.tobytes()          # NaN payload preserved
    # -0.0 stays -0.0 (sign bit survives, which == comparison would hide)
    assert np.signbit(out[3])


def test_roundtrip_empty_and_zero_dim_arrays():
    for arr in (np.zeros((0,), np.float32), np.zeros((3, 0, 2), np.int64),
                np.float32(0).reshape(())):
        out = roundtrip(np.asarray(arr))
        assert out.dtype == arr.dtype and out.shape == np.shape(arr)


def test_roundtrip_train_state_dataclass():
    if BF16 is None:
        pytest.skip("ml_dtypes unavailable")
    rng = np.random.default_rng(3)
    params = {"conv1": {"w": rng.standard_normal((5, 5, 3, 16)).astype(BF16),
                        "b": np.zeros((16,), BF16)},
              "fc": {"w": rng.standard_normal((320, 10)).astype(BF16),
                     "b": np.zeros((10,), BF16)}}
    state = TrainState(params=params, head=None, head_stale=None,
                       opt_state={"m": [np.ones((4,), np.float32)]},
                       head_opt_state=None, prev_features=None,
                       prev_labels=None, prev_mask=None,
                       step=np.int32(11))
    out = roundtrip(state)
    assert isinstance(out, TrainState)
    assert_trees_bitequal(out, state)


def test_jax_arrays_decode_as_numpy():
    import jax.numpy as jnp
    obj = {"w": jnp.arange(6, dtype=jnp.float32).reshape(2, 3)}
    out = roundtrip(obj)
    assert isinstance(out["w"], np.ndarray)
    assert out["w"].tobytes() == np.asarray(obj["w"]).tobytes()


def test_encode_rejects_object_arrays():
    with pytest.raises((ProtocolError, Exception)):
        manifest, buffer = encode_binary(np.array([object()], dtype=object))
        decode_binary(manifest, buffer)


# ---------------------------------------------------------------------------
# flatten / apply_delta algebra
# ---------------------------------------------------------------------------


def _tree_strategy():
    leaf = st.one_of(st.integers(min_value=-99, max_value=99),
                     arrays(dtype=st.sampled_from(["float32", "int32"]),
                            shape=array_shapes(min_dims=1, max_dims=2,
                                               min_side=1, max_side=3)))
    return st.lists(leaf, min_size=1, max_size=5).map(
        lambda leaves: {"items": leaves,
                        "pair": (leaves[0], len(leaves)),
                        "meta": {"n": len(leaves)}})


@settings(max_examples=40, deadline=None)
@given(_tree_strategy())
def test_apply_full_delta_reconstructs_tree(tree):
    flat = flatten_tree(tree)
    rebuilt = apply_delta(tree, flat)          # splice every leaf onto itself
    assert_trees_bitequal(rebuilt, tree)


@settings(max_examples=40, deadline=None)
@given(_tree_strategy(), st.integers(min_value=0, max_value=1_000_000))
def test_apply_partial_delta_only_touches_changed_paths(tree, seed):
    rng = np.random.default_rng(seed)
    flat = flatten_tree(tree)
    paths = sorted(flat.keys())
    chosen = [p for p in paths if rng.random() < 0.5]
    delta = {p: (np.asarray(flat[p]) + 1 if hasattr(flat[p], "dtype")
                 else flat[p]) for p in chosen}
    out = flatten_tree(apply_delta(tree, delta))
    for p in paths:
        expect = delta[p] if p in delta else flat[p]
        assert leaf_equal(out[p], expect), p


def test_apply_delta_rejects_unknown_paths():
    with pytest.raises(DeltaApplyError):
        apply_delta({"a": 1}, {((0, "missing"),): 2})
    with pytest.raises(DeltaApplyError):
        apply_delta({"a": [1, 2]}, {((0, "a"), (1, 5)): 9})


def test_apply_delta_is_copy_on_write():
    base = {"hot": np.zeros((2,), np.float32), "cold": np.ones((2,),
                                                               np.float32)}
    out = apply_delta(base, {((0, "hot"),): np.full((2,), 7, np.float32)})
    assert out["cold"] is base["cold"]             # untouched leaf shared
    assert float(base["hot"][0]) == 0.0            # base never mutated


# ---------------------------------------------------------------------------
# delta-encode -> apply == full payload, over real publish histories
# ---------------------------------------------------------------------------


def _publish(rng, n_leaves):
    """A random full payload with n_leaves float32 leaf arrays."""
    return {"params": {f"l{i}": rng.standard_normal(4).astype(np.float32)
                       for i in range(n_leaves)},
            "round": int(rng.integers(0, 1000))}


@settings(max_examples=25, deadline=None)
@given(st.integers(min_value=2, max_value=DELTA_HISTORY + 3),
       st.integers(min_value=0, max_value=1_000_000))
def test_delta_vs_full_bitexact_over_random_history(n_publishes, seed):
    """For every (publish history, client base version) pair: applying the
    served delta to the base payload == the current full payload,
    bit-exact — or the registry refuses the delta (outside the horizon /
    structure change) and the client takes a full download."""
    rng = np.random.default_rng(seed)
    reg = HttpServerBase()
    history = []                               # [(version, full_value)]
    value = _publish(rng, n_leaves=4)
    for _ in range(n_publishes):
        # mutate a random subset of leaves (sometimes none -> pure re-tag)
        value = {"params": {k: (rng.standard_normal(4).astype(np.float32)
                                if rng.random() < 0.4 else v)
                            for k, v in value["params"].items()},
                 "round": int(rng.integers(0, 1000))}
        reg.add_static("w", value)
        history.append((reg.static_version("w"),
                        flatten_tree(value)))
    current_version, current_flat = history[-1]
    for base_version, base_flat in history[:-1]:
        got = reg.serve_static_versioned("w", base_version, delta=True)
        if got.delta_base is None:
            # horizon fallback: full payload, still the current value
            assert got.version == current_version
            assert flatten_tree(got.value).keys() == current_flat.keys()
            continue
        assert got.delta_base == base_version
        base_value = {"params": {}, "round": None}
        rebuilt = apply_delta(
            {"params": {k[-1][1]: v for k, v in base_flat.items()
                        if k[0] == (0, "params")},
             "round": base_flat[((0, "round"),)]},
            got.value)
        flat = flatten_tree(rebuilt)
        assert flat.keys() == current_flat.keys()
        for p in flat:
            assert leaf_equal(flat[p], current_flat[p]), p
        del base_value


def test_delta_refused_past_history_horizon():
    reg = HttpServerBase()
    reg.add_static("w", {"a": np.zeros(2, np.float32)})
    first = reg.static_version("w")
    for i in range(DELTA_HISTORY + 2):         # push `first` out the window
        reg.add_static("w", {"a": np.full(2, i, np.float32)})
    got = reg.serve_static_versioned("w", first, delta=True)
    assert got.delta_base is None and got.value is not None


def test_delta_refused_across_structure_change():
    reg = HttpServerBase()
    reg.add_static("w", {"a": np.zeros(2, np.float32)})
    v1 = reg.static_version("w")
    reg.add_static("w", {"a": np.zeros(2, np.float32),
                         "b": np.ones(2, np.float32)})   # new leaf: reset
    got = reg.serve_static_versioned("w", v1, delta=True)
    assert got.delta_base is None and set(got.value) == {"a", "b"}


def test_delta_skips_unchanged_leaves():
    reg = HttpServerBase()
    big = np.zeros((64,), np.float32)
    reg.add_static("w", {"frozen": big, "hot": np.zeros(2, np.float32)})
    v1 = reg.static_version("w")
    reg.add_static("w", {"frozen": big, "hot": np.ones(2, np.float32)})
    got = reg.serve_static_versioned("w", v1, delta=True)
    assert got.delta_base == v1
    assert set(got.value) == {((0, "hot"),)}   # only the changed leaf ships
    assert reg.delta_count["w"] == 1


def test_build_delta_fetched_none_cases():
    assert build_delta_fetched(None, 5, 3) is None          # no state
    reg = HttpServerBase()
    reg.add_static("w", {"a": 1})
    state = reg._static_delta["w"]
    v = reg.static_version("w")
    assert build_delta_fetched(state, v, None) is None      # unconditional
    assert build_delta_fetched(state, v, v) is None         # already current
    assert build_delta_fetched(state, v, v + 99) is None    # unknown base
