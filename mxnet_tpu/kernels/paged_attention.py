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


def _supports(heads=0, head_dim=0, block_size=0, **_kw):
    if heads >= 1 and head_dim >= 1 and block_size >= 1:
        return True, ""
    return False, ("paged attention needs positive heads/head_dim/"
                   "block_size (heads=%r, head_dim=%r, block_size=%r)"
                   % (heads, head_dim, block_size))


def _xla_reference(q, k_cache, v_cache, block_tables, context_lens,
                   scale=1.0):
    from ..ops.pallas.paged_attention import paged_attention_reference
    return paged_attention_reference(q, k_cache, v_cache, block_tables,
                                     context_lens, scale=scale)


register_kernel(KernelSpec(
    name="paged_attention",
    doc="Decode-step attention over a paged KV cache "
        "(ops/pallas/paged_attention.py): one query token per slot "
        "walks its block table with online softmax, so decode HBM "
        "traffic is the slot's live context only -- no contiguous "
        "(or padded-to-max) K/V copy per step.  XLA fallback gathers "
        "the table's blocks and runs a masked softmax.",
    supports=_supports,
    xla_ref=_xla_reference,
))


def paged_attention(q, k_cache, v_cache, block_tables, context_lens,
                    scale=1.0, use_pallas=None):
    """THE decode-attention entry: select pallas-vs-XLA through the
    registry and run it.  ``q`` (slots, heads, d); per-layer cache
    slabs (num_blocks, block_size, heads, d); ``block_tables`` (slots,
    max_blocks) int32; ``context_lens`` (slots, 1) int32."""
    from . import registry as _registry
    heads, head_dim = int(q.shape[1]), int(q.shape[2])
    block_size = int(k_cache.shape[1])
    choice = _registry.choose("paged_attention", force=use_pallas,
                              heads=heads, head_dim=head_dim,
                              block_size=block_size)
    if choice.use_pallas:
        from ..ops.pallas.paged_attention import paged_attention_pallas
        return paged_attention_pallas(q, k_cache, v_cache, block_tables,
                                      context_lens, scale=scale,
                                      interpret=choice.interpret)
    return _xla_reference(q, k_cache, v_cache, block_tables,
                          context_lens, scale=scale)
