"""``mxnet_tpu.kernels`` -- the Pallas custom-kernel tier.

A registry of hand-written Pallas TPU kernels, each with its XLA
reference (docs/kernels.md).  Four kernels ship through it, the four
the benchmark's device traces name:

- ``flash_attention``: the blockwise online-softmax attention kernels,
  forward and backward (``ops/pallas/flash_attention.py``), behind the
  ``flash_attention`` / ``flash_attention_masked`` ops.
- ``paged_attention``: decode-step attention over the generative
  serving tier's paged KV cache (``ops/pallas/paged_attention.py``):
  one query token per slot walks its block table with online softmax;
  the XLA reference gathers the table's blocks and masks.
- ``mla_paged_attention``: the same walk over a paged cache of latent
  (MLA) rows, which are keys and values at once
  (``ops/pallas/mla_paged_attention.py``).
- ``grouped_matmul``: rows sorted by group times each group's own
  matrix, the expert matmul of a routed layer's prefill
  (``ops/pallas/grouped_matmul.py``, JAX's ``megablox`` kernel);
  the XLA reference is ``jax.lax.ragged_dot``.

Selection (``registry.choose``) is a function of the backend, the
call's shape and the caller's ``force`` / ``use_pallas`` argument:
Pallas compiled on a TPU where the kernel supports the shape and
measured profitable, the XLA reference elsewhere; ``use_pallas=True``
off the chip runs the real kernel body in interpret mode (tier-1),
``use_pallas=False`` is the XLA reference anywhere.
"""
from .registry import (KernelChoice, KernelSpec, available, choose, get,
                       list_kernels, register_kernel)

__all__ = ["KernelChoice", "KernelSpec", "available", "choose", "get",
           "list_kernels", "register_kernel"]
