# A verbatim copy of mxnet_tpu/ops/pallas/paged_attention.py as it stood
# before PR 32 gave the kernel grouped-query heads and a window (commit
# 68e136b): tests/test_serving_window_moe.py holds the ungrouped,
# unwindowed call of today's kernel to this one bit for bit.  Not a test
# module, and nothing in the program imports it.
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
dtype.

The grid is the LIVE page groups of the call and nothing else: one
step walks ``pages`` table blocks of one slot, ``pages`` the largest of
8, 4, 2, 1 that divides the table, and a slot takes as many steps as
its context has page groups (at least one), so the grid's size is a
traced number: 16 slots of some 330 tokens with a table 64 wide are 49
steps of 8 pages, where one 16-token block a step over the whole table
was 1,024 of which two thirds were dead.  On a v5e a grid step costs
about 76 ns for every operand it has, whatever it moves, so a dead step
is not free and neither is a page: the steps, not the bytes, were the
kernel's time.  Which slot and group a step is, and which cache block
each of its pages names, is worked out beforehand in plain XLA
(:func:`_live_steps`: the same for every layer of a decode step, so
it is computed once a step) and scalar-prefetched into SMEM, so an
index map is one load.  The K slab and the V slab are each passed
``pages`` times with an index map of their own, each page the ONE
cache block the table names, copied HBM->VMEM by the pipeline: VMEM
holds two page groups per operand whatever the cache size.  A page
past the slot's last live block names the block its operand already
holds (the pipeline does not fetch an unchanged index again) and the
position mask takes it out: a call fetches its live blocks and no
other.

A page is used in the cache's own order, ``(token, head)`` rows of
``head_dim`` lanes, with no relayout: q against ALL of a page's rows is
one matmul, ``(heads, d) x (block_size * heads, d)^T``, in which row
``(t, h')`` is head ``h``'s key only where ``h' == h``.  The mask keeps
that diagonal and the live positions, the step's pages are ONE run of
columns for one running maximum / sum / accumulator update, and the
masked probabilities (exactly 0 off the diagonal) times the page's rows
are the step's values.  That spends ``heads`` times the MXU work the
scores need, on a unit that is otherwise idle, to save putting heads
first (a relayout of every block).
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
# Pallas kernel: one grid step a live page group, online softmax carried
# in VMEM scratch across a slot's groups
# ----------------------------------------------------------------------

# table blocks one grid step walks, the largest that divides the table
PAGES = (8, 4, 2, 1)


def _live_steps(block_tables, ctx, bs, pages):
    """The call's grid: ``(steps, slot, group, blocks)``.  Step ``i`` is
    page group ``group[i]`` of slot ``slot[i]``, the slots in order and
    each with the groups its context reaches (one for an empty one, so
    every slot's output is written); ``blocks[k, i]`` is the cache block
    of its page ``k``.  A page past the slot's last live block names
    what operand ``k`` held at the last step that reached it, whichever
    slot that was.  Rows from ``steps`` on are never run."""
    slots, mb = block_tables.shape
    span = pages * bs
    groups = jnp.maximum((ctx + span - 1) // span, 1)            # (slots,)
    ends = jnp.cumsum(groups)
    i = jnp.arange(slots * (mb // pages), dtype=jnp.int32)
    slot = jnp.minimum(jnp.searchsorted(ends, i, side="right",
                                        method="compare_all"),
                       slots - 1).astype(jnp.int32)
    group = i - (ends - groups)[slot]
    at = group[:, None] * pages + jnp.arange(pages, dtype=jnp.int32)
    live = at * bs < ctx[slot][:, None]                    # (steps, pages)
    named = block_tables[slot[:, None], jnp.minimum(at, mb - 1)]
    held = jax.lax.cummax(jnp.where(live, i[:, None], 0), axis=0)
    blocks = jnp.take_along_axis(named, held, axis=0)
    return ends[-1], slot, group, blocks.T


def _decode_kernel(ctx_ref, slot_ref, group_ref, blocks_ref, q_ref, *refs,
                   block_size, pages, scale):
    k_refs, v_refs = refs[:pages], refs[pages:2 * pages]
    o_ref, m_ref, l_ref, acc_ref = refs[2 * pages:]
    i = pl.program_id(0)
    ctx = ctx_ref[slot_ref[i]]
    start = group_ref[i] * pages * block_size

    @pl.when(start == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(start < ctx)
    def _():
        q = q_ref[0].astype(jnp.float32)              # (heads, d)
        heads, d = q.shape
        rows = block_size * heads
        # a page in the cache's own order, (token, head) rows of d lanes:
        # no relayout.  q against ALL of a page's rows is one matmul,
        # (heads, d) x (rows, d)^T; row (t, h') is head h's key where
        # h' == h, and the mask keeps that diagonal and the live tokens
        col = jax.lax.broadcasted_iota(jnp.int32, (heads, rows), 1)
        head = jax.lax.broadcasted_iota(jnp.int32, (heads, rows), 0)
        tok = col // heads
        own = col - tok * heads == head
        s = []
        for k in range(pages):
            sk = jax.lax.dot_general(
                q, k_refs[k][0].astype(jnp.float32).reshape(rows, d),
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            live = own & (start + k * block_size + tok < ctx)
            s.append(jnp.where(live, sk, NEG_INF))
        # the step's pages as ONE run of columns: one softmax update
        s = s[0] if pages == 1 else jnp.concatenate(s, axis=1)
        m = m_ref[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)                        # 0 off the diagonal
        alpha = jnp.exp(m - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1,
                                                  keepdims=True)
        pv = jnp.zeros(acc_ref.shape, jnp.float32)
        for k in range(pages):
            pv += jax.lax.dot_general(
                p[:, k * rows:(k + 1) * rows],
                v_refs[k][0].astype(jnp.float32).reshape(rows, d),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)   # (heads, d)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = m_new

    # the slot's last group: the next step is another slot's
    @pl.when(start + pages * block_size >= ctx)
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
    pages = next(p for p in PAGES if mb % p == 0)
    ctx = context_lens.reshape(slots)
    steps, slot, group, blocks = _live_steps(block_tables, ctx, bs, pages)

    def kv_block(k):
        return pl.BlockSpec(
            (1, bs, heads, d),
            lambda i, ctx, slot, group, blocks: (blocks[k, i], 0, 0, 0))

    def q_block(i, ctx, slot, group, blocks):
        return (slot[i], 0, 0)

    kv_specs = [kv_block(k) for k in range(pages)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(steps,),
        in_specs=[pl.BlockSpec((1, heads, d), q_block)] + kv_specs * 2,
        out_specs=pl.BlockSpec((1, heads, d), q_block),
        scratch_shapes=[pltpu.VMEM((heads, 1), jnp.float32),
                        pltpu.VMEM((heads, 1), jnp.float32),
                        pltpu.VMEM((heads, d), jnp.float32)])
    return pl.pallas_call(
        functools.partial(_decode_kernel, block_size=bs, pages=pages,
                          scale=scale),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
    )(ctx, slot, group, blocks, q,
      *([k_cache] * pages), *([v_cache] * pages))
