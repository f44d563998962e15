"""Registry entry + selection point for the grouped matmul of a routed
expert layer.

The kernel lives in ``ops/pallas/grouped_matmul.py`` (JAX's ``megablox``
grouped matmul, its tiles chosen from the call's shape); this module
promotes it into the kernel tier with the standard contract:
``registry.choose`` is the ONE selection point, ``jax.lax.ragged_dot``
is the XLA fallback and the numerics oracle, and off the chip a forced
Pallas path runs in ``interpret=True`` mode.

A caller that leaves the choice open gets the kernel where it was
measured against ``ragged_dot`` and won: a layer of many whole experts
(64 groups of 2,304 x 896, the prefill of ``mellum2_serve_closed32``;
PERF.md section 6, PR 32).  A layer that holds a few of many experts (12
groups of 7,168 x 2,048, ``kimi_k2_serve_closed32``) stays on
``ragged_dot``, which its cell's numbers were taken with.
"""
from __future__ import annotations

from .registry import KernelSpec, register_kernel

# the fewest groups at which the open choice takes the kernel: the one
# shape both paths were timed at on the chip
AUTO_MIN_GROUPS = 64


def _supports(groups=0, k=0, n=0, **_kw):
    if groups >= 1 and k >= 1 and n >= 1:
        return True, ""
    return False, ("grouped matmul needs positive groups/k/n (groups=%r, "
                   "k=%r, n=%r)" % (groups, k, n))


def _auto(groups=0, **_kw):
    return groups >= AUTO_MIN_GROUPS


def _xla_reference(lhs, rhs, group_sizes):
    from ..ops.pallas.grouped_matmul import grouped_matmul_reference
    return grouped_matmul_reference(lhs, rhs, group_sizes)


register_kernel(KernelSpec(
    name="grouped_matmul",
    doc="Rows sorted by group times each group's own matrix "
        "(ops/pallas/grouped_matmul.py, JAX's megablox kernel): the "
        "expert matmul of a routed layer, a row tile that two groups "
        "share visited once for each.  XLA fallback is "
        "jax.lax.ragged_dot.  A caller that leaves the choice open gets "
        "the kernel from 64 groups on, and on a TPU only.",
    supports=_supports,
    auto_predicate=_auto,
    xla_ref=_xla_reference,
))


def grouped_matmul(lhs, rhs, group_sizes, use_pallas=None):
    """THE grouped-matmul entry: select pallas-vs-XLA through the
    registry and run it.  ``lhs`` (rows, k), its rows sorted by group;
    ``rhs`` (groups, k, n); ``group_sizes`` (groups,) int32, their sum
    at most ``rows`` -> (rows, n) float32, zero in the rows past the
    last group."""
    from . import registry as _registry
    groups, k, n = (int(s) for s in rhs.shape)
    choice = _registry.choose("grouped_matmul", force=use_pallas,
                              groups=groups, k=k, n=n)
    if choice.use_pallas:
        from ..ops.pallas.grouped_matmul import grouped_matmul_pallas
        return grouped_matmul_pallas(lhs, rhs, group_sizes,
                                     interpret=choice.interpret)
    return _xla_reference(lhs, rhs, group_sizes)
