"""Decode-step paged attention (Pallas/TPU): one query token per
sequence attends over block-gathered K/V from the serving tier's
:class:`~mxnet_tpu.serving.decode.kvcache.PagedKVCache`.

The prefill kernels (``flash_attention.py``) stream CONTIGUOUS K/V; at
decode time a sequence's K/V is scattered over cache blocks named by
its block table, so the kernel walks the table -- online softmax across
blocks, exactly the flash discipline, but the block index is data (the
table row), not the grid position.  The XLA reference gathers the
table's blocks with one ``take`` and runs a masked softmax -- it is the
CPU fallback and the numerics oracle the registry's interpret-mode
contract is tested against.

Layout: q ``(slots, heads, head_dim)``; per-layer cache slabs
``(num_blocks, block_size, heads, head_dim)``; ``block_tables``
``(slots, max_blocks)`` int32; ``context_lens`` ``(slots, 1)`` int32
(tokens 0..ctx-1 are live).  fp32 accumulation regardless of cache
dtype.  The block table and context lengths are scalar-prefetched into
SMEM and the grid is ``(slots, max_blocks)``: each step's K/V block is
the ONE cache block the table names, copied HBM->VMEM by the pipeline,
so VMEM holds two blocks per operand whatever the cache size.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

NEG_INF = -1e30

try:  # pallas import kept lazy-safe: CPU-only builds fall back to XLA
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    _HAS_PALLAS = True
except Exception:  # pragma: no cover
    _HAS_PALLAS = False


# ----------------------------------------------------------------------
# XLA reference / fallback
# ----------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("scale",))
def paged_attention_reference(q, k_cache, v_cache, block_tables,
                              context_lens, scale=1.0):
    """Gather-then-softmax reference: ``take`` the table's blocks into
    a contiguous ``(slots, max_blocks*block_size, heads, d)`` view and
    mask positions past each slot's context length."""
    s_, h, d = q.shape
    nb, bs, _, _ = k_cache.shape
    mb = block_tables.shape[1]
    k = jnp.take(k_cache, block_tables, axis=0)        # (s, mb, bs, h, d)
    v = jnp.take(v_cache, block_tables, axis=0)
    k = k.reshape(s_, mb * bs, h, d).astype(jnp.float32)
    v = v.reshape(s_, mb * bs, h, d).astype(jnp.float32)
    qf = q.astype(jnp.float32)
    scores = jnp.einsum("shd,sthd->sht", qf, k) * scale
    pos = jnp.arange(mb * bs, dtype=jnp.int32)
    live = pos[None, None, :] < context_lens.reshape(s_, 1, 1)
    scores = jnp.where(live, scores, NEG_INF)
    m = jnp.max(scores, axis=-1, keepdims=True)
    p = jnp.exp(scores - m)
    l = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    out = jnp.einsum("sht,sthd->shd", p / l, v)
    return out.astype(q.dtype)


# ----------------------------------------------------------------------
# Pallas kernel: grid (slots, table blocks), online softmax carried in
# VMEM scratch across a slot's blocks
# ----------------------------------------------------------------------

def _decode_kernel(bt_ref, ctx_ref, q_ref, k_ref, v_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, block_size, scale):
    slot = pl.program_id(0)
    j = pl.program_id(1)
    ctx = ctx_ref[slot]

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(j * block_size < ctx)
    def _():
        q = q_ref[0].astype(jnp.float32)          # (heads, d)
        heads = q.shape[0]
        k = k_ref[0].astype(jnp.float32)          # (bs, heads, d)
        v = v_ref[0].astype(jnp.float32)
        # (heads, 1, d) x (heads, bs, d) -> (heads, 1, bs): one query
        # row per head against the block's keys
        s = jax.lax.dot_general(
            q[:, None, :], k.transpose(1, 0, 2),
            (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)[:, 0, :] * scale
        tpos = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (heads, block_size), 1)
        s = jnp.where(tpos < ctx, s, NEG_INF)     # (heads, bs)
        m = m_ref[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1,
                                                  keepdims=True)
        # (heads, 1, bs) x (heads, bs, d) -> (heads, d)
        pv = jax.lax.dot_general(
            p[:, None, :], v.transpose(1, 0, 2),
            (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)[:, 0, :]
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_attention_pallas(q, k_cache, v_cache, block_tables,
                           context_lens, scale=1.0, interpret=False):
    """q (slots, heads, d); caches (nb, bs, heads, d); block_tables
    (slots, mb) int32; context_lens (slots, 1) int32 -> (slots, heads,
    d)."""
    slots, heads, d = q.shape
    _nb, bs, _, _ = k_cache.shape
    mb = block_tables.shape[1]

    def kv_block(s, j, bt, ctx):
        # steps past the slot's last live block name that block again:
        # an unchanged block index is not re-fetched, so dead steps
        # cost no DMA (the body skips them)
        last = jnp.maximum(ctx[s] - 1, 0) // bs
        return (bt[s, jnp.minimum(j, last)], 0, 0, 0)

    def q_block(s, j, bt, ctx):
        return (s, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(slots, mb),
        in_specs=[pl.BlockSpec((1, heads, d), q_block),
                  pl.BlockSpec((1, bs, heads, d), kv_block),
                  pl.BlockSpec((1, bs, heads, d), kv_block)],
        out_specs=pl.BlockSpec((1, heads, d), q_block),
        scratch_shapes=[pltpu.VMEM((heads, 1), jnp.float32),
                        pltpu.VMEM((heads, 1), jnp.float32),
                        pltpu.VMEM((heads, d), jnp.float32)])
    return pl.pallas_call(
        functools.partial(_decode_kernel, block_size=bs, scale=scale),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
    )(block_tables, context_lens.reshape(slots), q, k_cache, v_cache)
