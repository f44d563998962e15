"""Registry entry + selection point for decode-step attention over a
paged cache of latent (MLA) rows.

The kernel bodies live in ``ops/pallas/mla_paged_attention.py`` (every
head of a slot attends over the slot's latent rows, which are keys and
values at once); this module promotes them into the kernel tier with
the standard contract, as ``kernels/paged_attention.py`` does for
per-head K and V: ``registry.choose`` is the ONE selection point, the
XLA gather-then-softmax reference is the fallback and the numerics
oracle, and on non-TPU backends a forced Pallas path runs in
``interpret=True`` mode so tier-1 exercises the real kernel body.
"""
from __future__ import annotations

from .registry import KernelSpec, register_kernel


def _supports(heads=0, lanes=0, v_width=0, block_size=0, **_kw):
    if heads >= 1 and block_size >= 1 and 1 <= v_width <= lanes:
        return True, ""
    return False, ("latent paged attention needs positive heads/"
                   "block_size and 1 <= v_width <= lanes (heads=%r, "
                   "lanes=%r, v_width=%r, block_size=%r)"
                   % (heads, lanes, v_width, block_size))


def _xla_reference(q, cache, block_tables, context_lens, v_width,
                   scale=1.0):
    from ..ops.pallas.mla_paged_attention import (
        mla_paged_attention_reference)
    return mla_paged_attention_reference(q, cache, block_tables,
                                         context_lens, v_width=v_width,
                                         scale=scale)


register_kernel(KernelSpec(
    name="mla_paged_attention",
    doc="Decode-step latent attention over a paged cache of MLA rows "
        "(ops/pallas/mla_paged_attention.py): every head's absorbed "
        "query scores the slot's [c_kv | k_rope] rows over all lanes "
        "and the values are the same rows' first lanes, so a live "
        "token is read once a layer for all heads.  XLA fallback "
        "gathers the table's blocks and runs a masked softmax.",
    supports=_supports,
    xla_ref=_xla_reference,
))


def mla_paged_attention(q, cache, block_tables, context_lens, v_width,
                        scale=1.0, use_pallas=None):
    """THE latent decode-attention entry: select pallas-vs-XLA through
    the registry and run it.  ``q`` (slots, heads, lanes); one layer's
    slab (num_blocks, block_size, lanes); ``block_tables`` (slots,
    max_blocks) int32; ``context_lens`` (slots, 1) int32 -> (slots,
    heads, v_width)."""
    from . import registry as _registry
    heads, lanes = int(q.shape[1]), int(q.shape[2])
    choice = _registry.choose("mla_paged_attention", force=use_pallas,
                              heads=heads, lanes=lanes,
                              v_width=int(v_width),
                              block_size=int(cache.shape[1]))
    if choice.use_pallas:
        from ..ops.pallas.mla_paged_attention import (
            mla_paged_attention_pallas)
        return mla_paged_attention_pallas(
            q, cache, block_tables, context_lens, v_width=int(v_width),
            scale=scale, interpret=choice.interpret)
    return _xla_reference(q, cache, block_tables, context_lens,
                          int(v_width), scale=scale)
