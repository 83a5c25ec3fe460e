"""Comparison of the program's readings with the reference's."""
from __future__ import annotations

import statistics


def counted_leaves(ref_grad: dict, floor: float = 1e-3) -> list:
    """Leaves whose reference gradient norm is at least ``floor`` times the
    median leaf's: the others move by round-off alone."""
    med = statistics.median(ref_grad.values())
    return sorted(k for k, v in ref_grad.items() if v >= floor * med)


def norm_gap(prog: dict, ref: dict, leaves: list) -> tuple:
    """(worst gap, leaf): |‖prog‖ - ‖ref‖| of a leaf over the larger of
    the reference's norm of that leaf and of the median leaf."""
    med = statistics.median(ref[k] for k in leaves)
    worst, name = -1.0, None
    for k in leaves:
        g = abs(prog[k] - ref[k]) / max(ref[k], med)
        if g > worst:
            worst, name = g, k
    return worst, name
