"""Row-block choice for (rows, lanes) Pallas operands.

The Pallas TPU lowering refuses a block whose second-to-last dimension
is neither a multiple of 8 nor the whole array extent, so "largest
divisor of rows <= want" is not enough: 199,665 rows (ResNet-50's flat
parameter buffer) has 51 as that divisor.  Every row-tiled kernel
chooses its block here.
"""
from __future__ import annotations

SUBLANE = 8


def row_block(rows, want):
    """Rows per grid step: the whole extent when it fits in ``want``,
    else the largest divisor of ``rows`` that is a multiple of 8 and at
    most ``want``; ``None`` when there is none (the caller pads the
    operand or declines the kernel)."""
    if rows <= want:
        return rows
    b = want - want % SUBLANE
    while b >= SUBLANE:
        if rows % b == 0:
            return b
        b -= SUBLANE
    return None
