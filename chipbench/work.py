"""The algorithm's work: operations and bytes of the two Hermite passes.

Counted once from the reference's formulas (``reference.pass1_pair`` and
``pass2_pair``): each add, subtract, multiply, divide, square root, compare
and select is one operation.  Per pair that is the displacements, the pair
term and one add per summed component.  ``tests/test_work.py`` traces the
reference's pair functions and counts their operations against these
numbers.

Only useful pairs count (active targets x all sources, as the simulator's
``force_evals`` counter reports them): launched tiles, padding rows and
masked targets never count as work, so a change to compaction or tiling
cannot raise the figure.  Bytes are the least the algorithm must move in
float32 (the kernels' input and output type): each source read once per
pass and event, each active target read and written once per pass.
"""

from __future__ import annotations

#: displacements dr, dv (6) + pair term (36) + sums of acc, jerk, pot (7)
OPS_PASS1 = 49
#: displacements dr, dv, da (9) + pair term (70) + sum of snap (3)
OPS_PASS2 = 82
OPS_PER_PAIR = OPS_PASS1 + OPS_PASS2

F32 = 4
#: pass 1: a source is pos, vel, mass; a target reads pos, vel and writes
#: acc, jerk, pot
SRC1, TGT1 = 7 * F32, (6 + 7) * F32
#: pass 2: a source is pos, vel, acc, mass; a target reads pos, vel, acc
#: and writes snap
SRC2, TGT2 = 10 * F32, (9 + 3) * F32


def ops(pairs_per_pass: float) -> float:
    """Operations of both passes over ``pairs_per_pass`` useful pairs."""
    return pairs_per_pass * OPS_PER_PAIR


def bytes_moved(pairs_per_pass: float, events: float, n: int,
                chips: int = 1) -> float:
    """Least bytes of both passes over ``events`` force evaluations that
    together touch ``pairs_per_pass`` pairs of ``n`` sources; each of
    ``chips`` reads every source."""
    active = pairs_per_pass / n
    return events * n * chips * (SRC1 + SRC2) + active * (TGT1 + TGT2)


def roofline_seconds(pairs_per_pass: float, events: float, n: int,
                     peaks: dict, chips: int = 1) -> tuple:
    """(least seconds, which bound) on ``chips`` chips of ``peaks``."""
    compute = ops(pairs_per_pass) / (peaks["vpu_f32_ops_per_s"] * chips)
    memory = bytes_moved(pairs_per_pass, events, n, chips) \
        / (peaks["hbm_bytes_per_s"] * chips)
    return (compute, "vpu") if compute >= memory else (memory, "hbm")
