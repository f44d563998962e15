"""Registry entry + selection point for decode-step paged attention.

The kernel bodies live in ``ops/pallas/paged_attention.py`` (one query
token per slot, online softmax across the slot's block-table blocks);
this module promotes them into the kernel tier with the standard
contract: ``registry.choose`` is the ONE selection point, the XLA
gather-then-softmax reference is the fallback and the numerics oracle,
and on non-TPU backends a forced Pallas path runs in ``interpret=True``
mode so tier-1 exercises the real kernel body.

:func:`paged_attention` is the call surface the generative decode model
uses -- selection happens at trace time, so the decision is baked into
each compiled decode executable like every other static op param.
"""
from __future__ import annotations

from .registry import KernelSpec, register_kernel


def _supports(heads=0, head_dim=0, block_size=0, kv_heads=None,
              window=None, **_kw):
    kv_heads = heads if kv_heads is None else kv_heads
    if not (heads >= 1 and head_dim >= 1 and block_size >= 1):
        return False, ("paged attention needs positive heads/head_dim/"
                       "block_size (heads=%r, head_dim=%r, block_size=%r)"
                       % (heads, head_dim, block_size))
    if kv_heads < 1 or heads % kv_heads:
        return False, ("paged attention needs the query heads to be a "
                       "multiple of the K/V heads (heads=%r, kv_heads=%r)"
                       % (heads, kv_heads))
    if window is not None and window < 1:
        return False, "paged attention needs a window >= 1 (%r)" % (window,)
    return True, ""


def _xla_reference(q, k_cache, v_cache, block_tables, context_lens,
                   scale=1.0, window=None, block_size=None):
    from ..ops.pallas.paged_attention import paged_attention_reference
    return paged_attention_reference(q, k_cache, v_cache, block_tables,
                                     context_lens, scale=scale,
                                     window=window, block_size=block_size)


register_kernel(KernelSpec(
    name="paged_attention",
    doc="Decode-step attention over a paged KV cache "
        "(ops/pallas/paged_attention.py): one query token per slot "
        "walks its block table with online softmax, so decode HBM "
        "traffic is the slot's live context only -- no contiguous "
        "(or padded-to-max) K/V copy per step.  Query heads may share "
        "K/V heads (grouped-query attention) and a slot's live "
        "positions may be its last `window` alone, over a ring table.  "
        "XLA fallback gathers the table's blocks and runs a masked "
        "softmax.",
    supports=_supports,
    xla_ref=_xla_reference,
))


def paged_attention(q, k_cache, v_cache, block_tables, context_lens,
                    scale=1.0, use_pallas=None, window=None,
                    block_size=None):
    """THE decode-attention entry: select pallas-vs-XLA through the
    registry and run it.  ``q`` (slots, heads, d); per-layer cache
    slabs (num_blocks, block_size, kv_heads, d), or with the heads
    folded into a block's rows (num_blocks, block_size * kv_heads, d)
    and ``block_size`` given; ``heads`` a multiple of ``kv_heads``
    (query head ``h`` reads K/V head ``h // (heads // kv_heads)``);
    ``block_tables`` (slots, max_blocks) int32; ``context_lens`` (slots,
    1) int32.  ``window``: a slot attends over its last ``window``
    positions only and ``block_tables`` is a ring (position ``p`` in
    entry ``(p // block_size) % max_blocks``)."""
    from . import registry as _registry
    heads, head_dim = int(q.shape[1]), int(q.shape[2])
    if k_cache.ndim == 4:
        block_size, kv_heads = int(k_cache.shape[1]), int(k_cache.shape[2])
    else:
        block_size = int(block_size)
        kv_heads = int(k_cache.shape[1]) // block_size
    choice = _registry.choose("paged_attention", force=use_pallas,
                              heads=heads, head_dim=head_dim,
                              block_size=block_size, kv_heads=kv_heads,
                              window=window)
    if choice.use_pallas:
        from ..ops.pallas.paged_attention import paged_attention_pallas
        return paged_attention_pallas(q, k_cache, v_cache, block_tables,
                                      context_lens, scale=scale,
                                      interpret=choice.interpret,
                                      window=window, block_size=block_size)
    return _xla_reference(q, k_cache, v_cache, block_tables,
                          context_lens, scale=scale, window=window,
                          block_size=block_size)
