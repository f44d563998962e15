"""Dtype-group flatten/concat bucketing.

The host collectives coalesce a whole tensor list into ONE contiguous
buffer per dtype (``distributed.host_allreduce_bucketed``) instead of
one RPC per tensor, and the numerics sentinel's in-graph finite check
(``analysis.numerics.finite_tree``) reduces over the same grouping of
*traced* jax arrays, so the machinery lives here once: group by dtype
preserving input order, flatten each group into one 1-D buffer, split
results back to the original shapes.

The helpers are array-module agnostic: pass ``xp=numpy`` for host
buffers (collectives) or ``xp=jax.numpy`` for traced buffers (the
compiled train step).
"""
from __future__ import annotations

from typing import Any, Dict, List, Sequence, Tuple

__all__ = ["dtype_groups", "flatten_group", "split_group"]


def dtype_groups(arrays: Sequence[Any]) -> List[Tuple[Any, List[int]]]:
    """Group ``arrays`` by dtype, preserving first-seen order.

    Returns ``[(dtype, [index, ...]), ...]`` where indices point into the
    input sequence in their original order -- the contract the host
    collectives rely on to reassemble results.
    """
    order: List[Any] = []
    groups: Dict[Any, List[int]] = {}
    for i, a in enumerate(arrays):
        dt = a.dtype
        if dt not in groups:
            groups[dt] = []
            order.append(dt)
        groups[dt].append(i)
    return [(dt, groups[dt]) for dt in order]


def flatten_group(arrays: Sequence[Any], idxs: Sequence[int], xp) -> Any:
    """One contiguous 1-D buffer holding ``arrays[i].ravel()`` for every
    ``i`` in ``idxs``, concatenated in order.  A single-element group
    skips the concat (it would copy)."""
    flat = [arrays[i].ravel() for i in idxs]
    return xp.concatenate(flat) if len(flat) > 1 else flat[0]


def split_group(buf: Any, shapes: Sequence[Tuple[int, ...]]) -> List[Any]:
    """Split a flat buffer produced by :func:`flatten_group` back into
    pieces of the given ``shapes`` (works on numpy and jax arrays --
    basic slicing + reshape only)."""
    out = []
    off = 0
    for shape in shapes:
        n = 1
        for d in shape:
            n *= int(d)
        out.append(buf[off:off + n].reshape(shape))
        off += n
    return out
