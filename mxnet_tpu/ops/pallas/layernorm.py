"""Fused LayerNorm forward kernel (Pallas/TPU).

One VMEM pass per row-block: load (block_rows, dim), compute mean/var
in fp32, normalize, scale/shift, write -- where the unfused graph reads
x three times from HBM (mean pass, var pass, normalize pass) before XLA
fusion, this guarantees the single-pass schedule and keeps the
activation bf16 in HBM with fp32 statistics in registers.  Reference
analog: the fused ``LayerNorm`` CUDA kernel in
``src/operator/nn/layer_norm.cu``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .tiling import row_block

try:
    from jax.experimental import pallas as pl
    _HAS_PALLAS = True
except Exception:  # pragma: no cover
    _HAS_PALLAS = False


def _ln_kernel(x_ref, g_ref, b_ref, o_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)              # (block_rows, dim)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mean
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    y = xc * inv * g_ref[...].astype(jnp.float32) + \
        b_ref[...].astype(jnp.float32)
    o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("eps", "block_rows",
                                             "interpret"))
def layernorm_fwd_pallas(x, gamma, beta, eps=1e-5, block_rows=128,
                         interpret=False):
    """LayerNorm over the last dim of a 2-D (rows, dim) input."""
    rows, dim = x.shape
    if rows == 0:
        return x
    block_rows = row_block(rows, block_rows)
    if block_rows is None:
        raise ValueError(
            "layernorm_fwd_pallas tiles rows in blocks that are a "
            "multiple of 8; %d rows has no such divisor" % rows)
    grid = (rows // block_rows,)
    kernel = functools.partial(_ln_kernel, eps=eps)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, dim), lambda i: (i, 0)),
            pl.BlockSpec((dim,), lambda i: (0,)),
            pl.BlockSpec((dim,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, dim), lambda i: (i, 0)),
        interpret=interpret,
    )(x, gamma, beta)
