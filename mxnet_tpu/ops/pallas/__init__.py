"""Pallas TPU kernel bodies (flash attention, paged and latent paged
decode attention, the grouped matmul of a routed expert layer).

Selection/fallback policy lives in ``mxnet_tpu.kernels`` (the kernel
registry, docs/kernels.md); these modules hold only the kernels.
"""
from .flash_attention import (flash_attention_bwd_pallas,
                              flash_attention_fwd_pallas)

__all__ = ["flash_attention_fwd_pallas", "flash_attention_bwd_pallas"]
