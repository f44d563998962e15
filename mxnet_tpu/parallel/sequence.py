"""Sequence/context parallelism: ring attention over a mesh axis.

The reference scales sequence length only by padding/bucketing within one
device's memory (SURVEY.md §5 "long-context": BucketingModule + fused RNN
kernels).  A TPU-native framework owes more: ring attention shards the
SEQUENCE over a mesh axis, each device holding seq/n of Q/K/V.  KV blocks
rotate around the ring via ``lax.ppermute`` (neighbor hops on the ICI
torus) while each device folds every block into a running online-softmax
(max, sum, acc) carry -- attention memory stays O(seq/n * d) per device
and comm overlaps compute block-by-block.

Composes with data parallelism: mesh {'dp': a, 'sp': b}, batch sharded on
``dp``, sequence on ``sp``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from ..base import MXNetError

__all__ = ["ring_attention", "ring_attention_sharded"]

_NEG_INF = -1e30


def _ring_attention_local(q, k, v, *, axis_name, causal, scale, seq_per):
    """Per-device body (inside shard_map): q/k/v are (bh, seq_local, d)."""
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    bh, sl, d = q.shape
    qf = q.astype(jnp.float32)

    rows_local = jax.lax.broadcasted_iota(jnp.int32, (sl, sl), 0)
    cols_local = jax.lax.broadcasted_iota(jnp.int32, (sl, sl), 1)
    my_row0 = idx * seq_per

    def block(carry, _):
        m, l, acc, kb, vb, src = carry
        s = jax.lax.dot_general(
            qf, kb.astype(jnp.float32), (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale
        if causal:
            rows_g = my_row0 + rows_local
            cols_g = src * seq_per + cols_local
            s = jnp.where(rows_g[None] >= cols_g[None], s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + jax.lax.dot_general(
            p, vb.astype(jnp.float32), (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        # rotate KV one hop around the ring (ICI neighbor transfer)
        perm = [(i, (i + 1) % n) for i in range(n)]
        kb = jax.lax.ppermute(kb, axis_name, perm)
        vb = jax.lax.ppermute(vb, axis_name, perm)
        src = (src - 1) % n
        return (m_new, l_new, acc_new, kb, vb, src), None

    m0 = jnp.full((bh, sl, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((bh, sl, 1), jnp.float32)
    acc0 = jnp.zeros((bh, sl, d), jnp.float32)
    (m, l, acc, _, _, _), _ = jax.lax.scan(
        block, (m0, l0, acc0, k, v, idx), None, length=n)
    out = acc / jnp.maximum(l, 1e-30)
    return out.astype(q.dtype)


def ring_attention(q, k, v, mesh, axis_name="sp", causal=False, scale=None):
    """Sequence-parallel attention: q/k/v (bh, seq, d) with ``seq`` sharded
    over ``mesh[axis_name]``; returns same-sharded output."""
    if axis_name not in mesh.shape:
        raise MXNetError("mesh has no axis %r" % axis_name)
    n = mesh.shape[axis_name]
    bh, seq, d = q.shape
    if seq % n:
        raise MXNetError("seq %d not divisible by %s=%d" % (seq, axis_name, n))
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    seq_per = seq // n
    body = functools.partial(_ring_attention_local, axis_name=axis_name,
                             causal=causal, scale=scale, seq_per=seq_per)
    spec = P(None, axis_name, None)
    fn = shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                   out_specs=spec, check_vma=False)
    return fn(q, k, v)


def ring_attention_sharded(q, k, v, mesh, axis_name="sp", causal=False,
                           scale=None):
    """Convenience wrapper taking/returning framework NDArrays, placing
    inputs seq-sharded on the mesh first."""
    from ..ndarray import NDArray
    sh = NamedSharding(mesh, P(None, axis_name, None))
    qd = jax.device_put(q._data if isinstance(q, NDArray) else q, sh)
    kd = jax.device_put(k._data if isinstance(k, NDArray) else k, sh)
    vd = jax.device_put(v._data if isinstance(v, NDArray) else v, sh)
    out = jax.jit(functools.partial(ring_attention, mesh=mesh,
                                    axis_name=axis_name, causal=causal,
                                    scale=scale))(qd, kd, vd)
    return NDArray(out)
