"""Operations and bytes of each Pallas kernel call, from its shapes.

Each function returns ``(flops, bytes)`` of the HBM traffic the
algorithm needs for one call.
"""
from __future__ import annotations

LANE = 128


def dbl_apply_flat2d(rows: int, *, itemsize: int = 4,
                     momentum: bool = False) -> tuple:
    """``p' = p - lr * g`` over a ``(rows, 128)`` flat store: reads p and
    g, writes p (and reads/writes the velocity with momentum); one
    multiply-add per element (two with momentum)."""
    n = rows * LANE
    arrays = 5 if momentum else 3
    return (4 if momentum else 2) * n, arrays * n * itemsize

