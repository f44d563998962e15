# A verbatim copy of mxnet_tpu/ops/pallas/mla_paged_attention.py as it stood
# while its grid was (slots, max_blocks / pages), the whole table of every
# slot (commit 1e5ef1e): tests/test_serving_latent_moe.py holds today's
# kernel, whose grid is the live page groups, to this one bit for bit.
# Only the import of NEG_INF is made absolute.  Not a test module, and
# nothing in the program imports it.
"""Decode-step attention over a paged cache of LATENT rows (Pallas/TPU).

A latent-attention (MLA) decoder keeps ONE row per token per layer:
``[c_kv | k_rope]``, the compressed K/V after its norm and the rotated
key slice that every head shares (``serving/decode/latent_moe.py``).
With the K up-projection absorbed into the query and the V
up-projection into the output, every head attends over that same row:

    score[h, t] = (q[h] . row[t]) * scale        over all lanes
    out[h]      = softmax_t(score[h]) @ row[:, :v_width]

so keys and values are the SAME bytes -- each live token is read once a
layer for all heads, where per-head K and V (``paged_attention.py``) are
read once a head.  ``q`` carries the absorbed query in the first
``v_width`` lanes and the rotated query slice after it; lanes past the
row's own width hold zeros in the query and in the cache, so the score
runs over whole 128-lane tiles.

Layout: q ``(slots, heads, lanes)``; one layer's slab ``(num_blocks,
block_size, lanes)``; ``block_tables`` ``(slots, max_blocks)`` int32;
``context_lens`` ``(slots, 1)`` int32 (tokens 0..ctx-1 are live);
output ``(slots, heads, v_width)``.  Scores and the softmax are
float32; the two matmuls take the cache's dtype in and accumulate in
float32.

The grid is ``(slots, max_blocks / pages)``: a step walks ``pages``
blocks of the slot's table, each the ONE cache block the table names,
copied HBM->VMEM by the pipeline (the slab is passed ``pages`` times,
each with its own index map).  A decode step of 32 slots with a
16,384-token table is 8,192 blocks a layer of which some 1,300 are
live; at one block a step the dead steps' launch time alone is longer
than the live rows take to read, so a step takes several, as one run
of rows: one matmul and one softmax update a step.  Pages and steps
past the slot's last live block name that block again: an unchanged
block index is not fetched again, the position mask takes a dead page
out and the body skips a dead step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from mxnet_tpu.ops.pallas.paged_attention import NEG_INF

try:  # pallas import kept lazy-safe: CPU-only builds fall back to XLA
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
except Exception:  # pragma: no cover
    pl = pltpu = None

# table blocks one grid step walks, the largest that divides the table
PAGES = (8, 4, 2, 1)


# ----------------------------------------------------------------------
# XLA reference / fallback
# ----------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("v_width", "scale"))
def mla_paged_attention_reference(q, cache, block_tables, context_lens,
                                  v_width, scale=1.0):
    """Gather-then-softmax reference: ``take`` the table's blocks into a
    contiguous ``(slots, max_blocks*block_size, lanes)`` view and mask
    positions past each slot's context length.  float32 throughout."""
    s_, _h, lanes = q.shape
    _nb, bs, _ = cache.shape
    mb = block_tables.shape[1]
    rows = jnp.take(cache, block_tables, axis=0)        # (s, mb, bs, l)
    rows = rows.reshape(s_, mb * bs, lanes).astype(jnp.float32)
    scores = jnp.einsum("shl,stl->sht", q.astype(jnp.float32), rows,
                        preferred_element_type=jnp.float32) * scale
    pos = jnp.arange(mb * bs, dtype=jnp.int32)
    live = pos[None, None, :] < context_lens.reshape(s_, 1, 1)
    scores = jnp.where(live, scores, NEG_INF)
    m = jnp.max(scores, axis=-1, keepdims=True)
    p = jnp.exp(scores - m)
    l = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    out = jnp.einsum("sht,stv->shv", p / l, rows[..., :v_width],
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


# ----------------------------------------------------------------------
# Pallas kernel: grid (slots, table blocks / pages), online softmax
# carried in VMEM scratch across a slot's blocks
# ----------------------------------------------------------------------

def _decode_kernel(bt_ref, ctx_ref, q_ref, *refs, block_size, pages,
                   v_width, scale):
    row_refs, o_ref = refs[:pages], refs[pages]
    m_ref, l_ref, acc_ref = refs[pages + 1:]
    slot = pl.program_id(0)
    j = pl.program_id(1)
    ctx = ctx_ref[slot]

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    start = j * pages * block_size

    @pl.when(start < ctx)
    def _():
        q = q_ref[0]                                  # (heads, lanes)
        heads = q.shape[0]
        # the step's pages as ONE run of rows: one matmul of pages *
        # block_size columns and one softmax update, not ``pages`` small
        # ones.  A page past the slot's last live block holds that block
        # again; the position mask takes it out
        rows = row_refs[0][0] if pages == 1 else jnp.concatenate(
            [r[0] for r in row_refs], axis=0)         # (pages * bs, lanes)
        s = jax.lax.dot_general(
            q, rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        tpos = start + jax.lax.broadcasted_iota(
            jnp.int32, (heads, pages * block_size), 1)
        s = jnp.where(tpos < ctx, s, NEG_INF)
        m = m_ref[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1,
                                                  keepdims=True)
        # the values are the same rows' first v_width lanes
        pv = jax.lax.dot_general(
            p.astype(rows.dtype), rows[:, :v_width],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)       # (heads, v_width)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("v_width", "scale", "interpret"))
def mla_paged_attention_pallas(q, cache, block_tables, context_lens,
                               v_width, scale=1.0, interpret=False):
    """q (slots, heads, lanes); cache (nb, bs, lanes); block_tables
    (slots, mb) int32; context_lens (slots, 1) int32 -> (slots, heads,
    v_width)."""
    slots, heads, lanes = q.shape
    _nb, bs, _ = cache.shape
    mb = block_tables.shape[1]
    pages = next(p for p in PAGES if mb % p == 0)

    def row_block(k):
        def index(s, j, bt, ctx):
            # steps past the slot's last live block name that block
            # again: an unchanged block index is not re-fetched, so
            # dead steps cost no DMA (the body skips them)
            last = jnp.maximum(ctx[s] - 1, 0) // bs
            return (bt[s, jnp.minimum(j * pages + k, last)], 0, 0)
        return pl.BlockSpec((1, bs, lanes), index)

    def q_block(s, j, bt, ctx):
        return (s, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(slots, mb // pages),
        in_specs=[pl.BlockSpec((1, heads, lanes), q_block)]
        + [row_block(k) for k in range(pages)],
        out_specs=pl.BlockSpec((1, heads, v_width), q_block),
        scratch_shapes=[pltpu.VMEM((heads, 1), jnp.float32),
                        pltpu.VMEM((heads, 1), jnp.float32),
                        pltpu.VMEM((heads, v_width), jnp.float32)])
    return pl.pallas_call(
        functools.partial(_decode_kernel, block_size=bs, pages=pages,
                          v_width=v_width, scale=scale),
        out_shape=jax.ShapeDtypeStruct((slots, heads, v_width), q.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
    )(block_tables, context_lens.reshape(slots), q, *([cache] * pages))
