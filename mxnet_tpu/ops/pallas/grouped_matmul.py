"""Grouped matmul over rows sorted by group (Pallas/TPU).

``lhs`` (rows, k) holds the rows of ``groups`` groups one after another,
``group_sizes`` (groups,) int32 says how many each has, and group ``g``'s
rows are multiplied by ``rhs[g]`` (k, n): the expert matmul of a routed
layer whose assignments are sorted by expert (``parallel/moe.py``).

The kernel is JAX's own ``megablox`` grouped matmul
(``jax.experimental.pallas.ops.tpu.megablox``): a grid over (n tiles,
row tiles a group touches, k tiles), the group of every row tile
scalar-prefetched, a tile that two groups share visited once for each
and stored under a row mask.  This module chooses its tiles from the
call's shape, pads the rows to whole tiles and zeroes the rows past the
last group, which the kernel leaves unwritten (``jax.lax.ragged_dot``,
the XLA reference, gives zeros there).  bfloat16 or float32 in, float32
accumulation and result.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# a row tile.  Timed on a v5e at Mellum2's widths (PERF.md section 6,
# PR 32): 16,384 sorted rows over 64 groups of 2,304 x 896 take 0.91-0.93
# ms at 128 or 256 rows a tile with k and n whole, 1.1-1.4 ms with k in
# two or three tiles or 512 rows, and ``ragged_dot`` 4.0 ms
ROW_TILE = 256
# what a kernel's buffers may take of a v5e core's fast memory
VMEM_BYTES = 16 * 2 ** 20


def _tiles_of(extent):
    """The multiples of 128 that divide ``extent``, largest first; the
    whole extent where there is none."""
    return [t for t in range(extent - extent % 128, 0, -128)
            if extent % t == 0] or [extent]


def tiling(rows, k, n, itemsize=2):
    """(row tile, k tile, n tile) for a call of this shape: k and n whole
    where two buffers of each operand and of the float32 result and the
    accumulator fit (the sums then run in ``ragged_dot``'s order), else
    the largest tiles that do, k cut first."""
    tm = min(ROW_TILE, -(-rows // 128) * 128)

    def fits(tk, tn):
        return 2 * itemsize * (tm * tk + tk * tn) + 12 * tm * tn \
            <= VMEM_BYTES
    for tn in _tiles_of(n):
        for tk in _tiles_of(k):
            if fits(tk, tn):
                return tm, tk, tn
    return tm, _tiles_of(k)[-1], _tiles_of(n)[-1]


def grouped_matmul_reference(lhs, rhs, group_sizes):
    """The XLA path and the oracle: ``jax.lax.ragged_dot``."""
    return jax.lax.ragged_dot(lhs, rhs, group_sizes,
                              preferred_element_type=jnp.float32)


def grouped_matmul_pallas(lhs, rhs, group_sizes, interpret=False):
    """``lhs`` (rows, k), ``rhs`` (groups, k, n), ``group_sizes``
    (groups,) int32 with a sum of at most ``rows`` -> (rows, n)
    float32."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm
    rows, k = lhs.shape
    tiles = tiling(rows, k, rhs.shape[2], lhs.dtype.itemsize)
    padded = -(-rows // tiles[0]) * tiles[0]
    if padded != rows:
        lhs = jnp.pad(lhs, ((0, padded - rows), (0, 0)))
    out = gmm(lhs, rhs, group_sizes, jnp.float32, tiles,
              interpret=interpret)
    in_a_group = jnp.arange(padded, dtype=jnp.int32)[:, None] \
        < jnp.sum(group_sizes)
    return jnp.where(in_a_group, out, 0.0)[:rows]
