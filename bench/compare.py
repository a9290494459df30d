"""The comparison that decides a run's ``correct``.

The set-up drives the program's own training loop through its first
three rounds; the reference (``configs/<reference>.py``) follows the same
three rounds from the same weights and rows.  Each number below is held
to its limit in ``checks/<workload>.json``:

* ``loss_gap``: the largest relative gap of a round's loss;
* ``grad_gap``: the first round's mean gradient as the optimizer got it,
  read from the program's AdaGrad accumulator after one round
  (``acc = g**2``, so ``|g| = sqrt(acc)`` element by element);
* ``delta_gap``: the parameters' change over the three rounds;
* ``grad_diff``, ``delta_diff``: the same two, by the norm of the
  difference;
* ``stale``: gradients computed against an earlier round's weights.

The ``_gap`` numbers take the worst leaf of ``| |program leaf| -
|reference leaf| | / max(|reference leaf|, median reference leaf)``, the
gap between the two norms.  That gap is a random projection of the
rounding error and swings from seed to seed: over a dozen seeds a
float8 control reads no more than the sound bfloat16 program's largest
reading.  So the ``_diff`` numbers, the worst leaf of ``|program leaf -
reference leaf| / max(|reference leaf|, median reference leaf)`` (for
the gradient, of its magnitudes), separate the two; the ``_gap`` numbers
stay for the state left unchanged.  Both change numbers leave out leaves
whose reference gradient is under a thousandth of the median leaf's:
AdaGrad moves those by round-off alone.
"""
from __future__ import annotations

import numpy as np

NUMBERS = ("loss_gap", "grad_gap", "delta_gap", "grad_diff", "delta_diff",
           "stale")
QUIET_LEAF = 1e-3


def leaves(tree, path: str = "") -> dict:
    """{"convs/0/w": array, ...} of a tree of dicts and lists."""
    if isinstance(tree, dict):
        items = [(str(k), tree[k]) for k in sorted(tree)]
    elif isinstance(tree, (list, tuple)):
        items = [(str(i), v) for i, v in enumerate(tree)]
    else:
        return {path: np.asarray(tree, np.float64)}
    out = {}
    for k, v in items:
        out.update(leaves(v, f"{path}/{k}" if path else k))
    return out


def readings(losses, first_grad, params0, params3) -> dict:
    """What the comparison needs of one side: the three rounds' losses,
    the magnitudes of the first round's gradient, and the change of the
    parameters over the three rounds, leaf by leaf."""
    after = leaves(params3)
    return {"losses": [float(l) for l in losses],
            "grad": {k: np.abs(v) for k, v in leaves(first_grad).items()},
            "delta": {k: after[k] - v for k, v in leaves(params0).items()}}


def accumulator_grad(acc) -> dict:
    """The magnitudes of the gradient whose square an AdaGrad accumulator
    that started at 0 holds after one step."""
    return {k: np.sqrt(np.maximum(v, 0.0)) for k, v in leaves(acc).items()}


def _worst(program: dict, reference: dict, keys, measure) -> float:
    if sorted(program) != sorted(reference):
        raise KeyError(f"leaf sets differ: {sorted(program)} vs "
                       f"{sorted(reference)}")
    norms = {k: float(np.linalg.norm(v)) for k, v in reference.items()}
    floor = float(np.median(list(norms.values())))
    return max(measure(program[k], reference[k]) / max(norms[k], floor)
               for k in keys)


def _gap(p, r) -> float:
    return abs(float(np.linalg.norm(p)) - float(np.linalg.norm(r)))


def _diff(p, r) -> float:
    return float(np.linalg.norm(p - r))


def numbers(program: dict, reference: dict, stale: int) -> dict[str, float]:
    """Every compared number of a run, from both sides' readings."""
    grad, delta = reference["grad"], reference["delta"]
    norms = {k: float(np.linalg.norm(v)) for k, v in grad.items()}
    median = float(np.median(list(norms.values())))
    moving = [k for k, v in norms.items() if v >= QUIET_LEAF * median]
    loss_gap = max(abs(p - r) / abs(r) for p, r in
                   zip(program["losses"], reference["losses"], strict=True))
    return {"loss_gap": loss_gap,
            "grad_gap": _worst(program["grad"], grad, grad, _gap),
            "delta_gap": _worst(program["delta"], delta, moving, _gap),
            "grad_diff": _worst(program["grad"], grad, grad, _diff),
            "delta_diff": _worst(program["delta"], delta, moving, _diff),
            "stale": float(stale)}


def verdict(values: dict[str, float], limits: dict[str, float]) -> bool:
    """True when every number is within its limit (``stale`` is exact)."""
    return all(values[k] <= limits[k] for k in NUMBERS)
