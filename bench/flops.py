"""Operations and bytes of the benchmark's programs, counted from shapes.

Counts follow the configuration file's sizes alone, so a change to the
program cannot change them.  What a model counts (its parameters, its
operations per sample) is its program module's
(``programs/<program>.py``); the server step's bytes and operations
follow from the parameters it updates.  A multiply-add is two
operations.
"""
from __future__ import annotations

F32_BYTES = 4


def server_step_bytes(program, cfg: dict, members: int) -> int:
    """HBM bytes one server step has to move at the least: ``members``
    float32 gradients, the parameters and the accumulator read, the new
    parameters and accumulator written.  ``program`` is the cell's
    program module, whose ``param_count`` counts what the step updates."""
    return (members + 4) * program.param_count(cfg) * F32_BYTES


def server_step_flops(program, cfg: dict, members: int) -> int:
    """Operations of one server step per parameter: a multiply-add per
    member for the weighted mean, then the square, add, rsqrt, two
    multiplies and the subtract of the AdaGrad update."""
    return (2 * members + 6) * program.param_count(cfg)
