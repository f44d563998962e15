"""Registry entry for the flash-attention kernels.

The kernel bodies live in ``ops/pallas/flash_attention.py`` (blockwise
online-softmax forward AND backward, O(seq*d) HBM); this module
promotes them into the kernel tier: ``registry.choose`` is the ONE
selection point (``ops/transformer.py`` asks it), and the
profitability gate carries the measured v5e crossover (seq >= 256,
below which XLA's fused materialized-scores path wins -- see
``ops/transformer.py`` for the per-seq numbers).
"""
from __future__ import annotations

from .registry import KernelSpec, register_kernel

# measured v5e crossover (BERT-base bf16 train, r3): seq 128 pallas 93k
# vs xla 117k tok/s; seq 256 111k vs 107k; seq 1024 81k vs 60k.  These
# readings are OLDER than the kernels as they stand (PR 33: whole-row
# tiles, one backward kernel; seq 512 about twice as fast a layer), so
# the crossover may lie lower now; it stays until a seq-128 cell
# (ROADMAP W4) can say otherwise
AUTO_MIN_SEQ = 256


def _supports(seq=0, block_q=256, block_k=256, **_kw):
    bq, bk = min(block_q, seq), min(block_k, seq)
    if bq > 0 and seq % bq == 0 and seq % bk == 0:
        return True, ""
    return False, ("flash attention needs seq divisible by the block "
                   "sizes (seq=%d, block_q=%d, block_k=%d)"
                   % (seq, block_q, block_k))


def _auto(seq=0, **_kw):
    return seq >= AUTO_MIN_SEQ


def _xla_reference(q, k, v, causal=False, scale=1.0):
    from ..ops.transformer import _attention_reference
    return _attention_reference(q, k, v, causal, scale)


register_kernel(KernelSpec(
    name="flash_attention",
    doc="Blockwise online-softmax attention, forward AND backward "
        "(ops/pallas/flash_attention.py): scores never leave VMEM, "
        "HBM traffic O(seq*d) instead of O(seq^2) both directions; "
        "optional padding mask.  A caller that leaves the choice open "
        "gets the measured seq>=256 crossover, and Pallas on TPU only.",
    supports=_supports,
    auto_predicate=_auto,
    xla_ref=_xla_reference,
))
