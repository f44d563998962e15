"""Registry entry + selection point for the decode step of a KDA
(Kimi Delta Attention) layer: each live slot's recurrent state turned by
one token in place.

The kernel body lives in ``ops/pallas/kda_decode.py``; this module
promotes it into the kernel tier with the standard contract, as
``kernels/mla_paged_attention.py`` does for latent rows:
``registry.choose`` is the ONE selection point, the XLA gather / turn /
scatter reference is the fallback and the numerics oracle, and on
non-TPU backends a forced Pallas path runs in ``interpret=True`` mode so
tier-1 exercises the real kernel body.
"""
from __future__ import annotations

from .registry import KernelSpec, register_kernel


def _supports(heads=0, key=0, value=0, **_kw):
    if heads >= 1 and key % 128 == 0 and value % 8 == 0 and key and value:
        return True, ""
    return False, ("the KDA decode kernel needs the key width in whole "
                   "128-lane tiles and the value width in whole 8-row "
                   "tiles (heads=%r, key=%r, value=%r)"
                   % (heads, key, value))


def _xla_reference(q, k, kb, g, v, state, rows):
    from ..ops.pallas.kda_decode import kda_decode_reference
    return kda_decode_reference(q, k, kb, g, v, state, rows)


register_kernel(KernelSpec(
    name="kda_decode",
    doc="Decode step of a KDA linear-attention layer "
        "(ops/pallas/kda_decode.py): per head, decay the slot's "
        "(value, key) float32 state by exp(g) along the key, apply the "
        "delta update with beta * k, read the output with q; one grid "
        "step a slot, its whole state row read and written once, in "
        "place.  XLA fallback gathers the rows, turns them and "
        "scatters them back.",
    supports=_supports,
    xla_ref=_xla_reference,
))


def kda_decode(q, k, kb, g, v, state, rows, use_pallas=None):
    """THE KDA decode entry: select pallas-vs-XLA through the registry
    and run it.  ``q``, ``k``, ``kb`` (= beta * k), ``g`` (the log
    decay) (slots, heads, key) float32; ``v`` (slots, heads, value)
    float32; ``state`` (rows, heads, value, key); ``rows`` (slots,)
    int32 -> (o (slots, heads, value) float32, state')."""
    from . import registry as _registry
    choice = _registry.choose("kda_decode", force=use_pallas,
                              heads=int(q.shape[1]), key=int(q.shape[2]),
                              value=int(v.shape[2]))
    if choice.use_pallas:
        from ..ops.pallas.kda_decode import kda_decode_pallas
        return kda_decode_pallas(q, k, kb, g, v, state, rows,
                                 interpret=choice.interpret)
    return _xla_reference(q, k, kb, g, v, state, rows)
