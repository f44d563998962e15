"""Transformer / BERT attention operators.

TPU-native re-design of the reference's fused BERT kernels
(``src/operator/contrib/transformer.cc :: interleaved_matmul_selfatt_qk,
interleaved_matmul_selfatt_valatt, interleaved_matmul_encdec_qk,
interleaved_matmul_encdec_valatt``).  The interleaved layout -- one
projection tensor (seq, batch, heads * 3 * head_dim) with each head's
q/k/v contiguous -- is kept for API parity; the score scaling
1/sqrt(head_dim) is applied inside the qk op.

``flash_attention`` is the TPU answer to these kernels: a Pallas
blockwise online-softmax kernel (``ops/pallas/flash_attention.py``) that
never materializes the (seq, seq) score matrix in HBM.  Backward is
recompute-based (standard attention math, XLA-fused), trading FLOPs for
memory exactly like ``jax.checkpoint``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .registry import register


# ----------------------------------------------------------------------
# Interleaved-projection ops (reference API parity)
# ----------------------------------------------------------------------

def _split_selfatt(qkv, heads):
    # (seq, batch, heads*3*hd) -> q/k/v each (batch*heads, seq, hd)
    seq, batch, emb3 = qkv.shape
    hd = emb3 // (3 * heads)
    x = qkv.reshape(seq, batch, heads, 3, hd)
    # (batch, heads, seq, hd) order for batched matmul
    q = x[:, :, :, 0].transpose(1, 2, 0, 3).reshape(batch * heads, seq, hd)
    k = x[:, :, :, 1].transpose(1, 2, 0, 3).reshape(batch * heads, seq, hd)
    v = x[:, :, :, 2].transpose(1, 2, 0, 3).reshape(batch * heads, seq, hd)
    return q, k, v, hd


@register("interleaved_matmul_selfatt_qk", args=("queries_keys_values",))
def _interleaved_matmul_selfatt_qk(queries_keys_values, heads=1):
    """Scores = Q·K^T / sqrt(head_dim) from an interleaved qkv projection
    (reference: ``transformer.cc :: interleaved_matmul_selfatt_qk``).
    Input (seq, batch, heads*3*hd); output (batch*heads, seq, seq)."""
    q, k, _, hd = _split_selfatt(queries_keys_values, heads)
    scale = 1.0 / math.sqrt(hd)
    return jax.lax.dot_general(
        q, k, (((2,), (2,)), ((0,), (0,)))) * scale


@register("interleaved_matmul_selfatt_valatt",
          args=("queries_keys_values", "attention"))
def _interleaved_matmul_selfatt_valatt(queries_keys_values, attention,
                                       heads=1):
    """Out = softmax-scores · V, back to (seq, batch, embed) (reference:
    ``interleaved_matmul_selfatt_valatt``)."""
    seq, batch, emb3 = queries_keys_values.shape
    _, _, v, hd = _split_selfatt(queries_keys_values, heads)
    out = jax.lax.dot_general(
        attention, v, (((2,), (1,)), ((0,), (0,))))  # (b*h, seq, hd)
    out = out.reshape(batch, heads, seq, hd).transpose(2, 0, 1, 3)
    return out.reshape(seq, batch, heads * hd)


def _split_encdec(kv, heads):
    seq, batch, emb2 = kv.shape
    hd = emb2 // (2 * heads)
    x = kv.reshape(seq, batch, heads, 2, hd)
    k = x[:, :, :, 0].transpose(1, 2, 0, 3).reshape(batch * heads, seq, hd)
    v = x[:, :, :, 1].transpose(1, 2, 0, 3).reshape(batch * heads, seq, hd)
    return k, v, hd


@register("interleaved_matmul_encdec_qk", args=("queries", "keys_values"))
def _interleaved_matmul_encdec_qk(queries, keys_values, heads=1):
    """Cross-attention scores (reference: ``interleaved_matmul_encdec_qk``).
    queries (qlen, batch, embed); keys_values (kvlen, batch, 2*embed
    interleaved); output (batch*heads, qlen, kvlen)."""
    qlen, batch, emb = queries.shape
    hd = emb // heads
    q = queries.reshape(qlen, batch, heads, hd) \
        .transpose(1, 2, 0, 3).reshape(batch * heads, qlen, hd)
    k, _, _ = _split_encdec(keys_values, heads)
    scale = 1.0 / math.sqrt(hd)
    return jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,)))) * scale


@register("interleaved_matmul_encdec_valatt",
          args=("keys_values", "attention"))
def _interleaved_matmul_encdec_valatt(keys_values, attention, heads=1):
    """Reference: ``interleaved_matmul_encdec_valatt``."""
    kvlen, batch, emb2 = keys_values.shape
    _, v, hd = _split_encdec(keys_values, heads)
    qlen = attention.shape[1]
    out = jax.lax.dot_general(
        attention, v, (((2,), (1,)), ((0,), (0,))))
    out = out.reshape(batch, heads, qlen, hd).transpose(2, 0, 1, 3)
    return out.reshape(qlen, batch, heads * hd)


# ----------------------------------------------------------------------
# Flash attention
# ----------------------------------------------------------------------

def _attention_reference(q, k, v, causal, scale):
    """Plain XLA attention (fallback + backward math).  Matmuls run in
    the input dtype with fp32 accumulation -- the MXU-native mode (a
    bf16 x bf16 product is exact in fp32, so this matches an fp32
    upcast to accumulation-order) -- softmax in fp32."""
    s = jax.lax.dot_general(
        q, k, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32) * scale
    if causal:
        seq_q, seq_k = s.shape[-2], s.shape[-1]
        rows = jax.lax.broadcasted_iota(jnp.int32, (seq_q, seq_k), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (seq_q, seq_k), 1)
        s = jnp.where(rows >= cols, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jax.lax.dot_general(
        p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def _xla_attention_bwd(q, k, v, dout, causal, scale, mask=None):
    # Recompute-based backward (XLA path): rebuild p in fp32, standard
    # attention gradients.
    qf, kf, vf = (t.astype(jnp.float32) for t in (q, k, v))
    s = jax.lax.dot_general(qf, kf, (((2,), (2,)), ((0,), (0,)))) * scale
    if causal:
        seq_q, seq_k = s.shape[-2], s.shape[-1]
        rows = jax.lax.broadcasted_iota(jnp.int32, (seq_q, seq_k), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (seq_q, seq_k), 1)
        s = jnp.where(rows >= cols, s, -1e30)
    if mask is not None:
        s = jnp.where(mask > 0, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    do = dout.astype(jnp.float32)
    dv = jax.lax.dot_general(p, do, (((1,), (1,)), ((0,), (0,))))
    dp = jax.lax.dot_general(do, vf, (((2,), (2,)), ((0,), (0,))))
    ds = p * (dp - jnp.sum(dp * p, axis=-1, keepdims=True))
    dq = jax.lax.dot_general(ds, kf, (((2,), (1,)), ((0,), (0,)))) * scale
    dk = jax.lax.dot_general(ds, qf, (((1,), (1,)), ((0,), (0,)))) * scale
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, causal, scale, block_q, block_k, use_pallas,
           interpret):
    if use_pallas:
        from .pallas.flash_attention import flash_attention_fwd_pallas
        out, _lse = flash_attention_fwd_pallas(
            q, k, v, causal=causal, scale=scale, block_q=block_q,
            block_k=block_k, interpret=interpret)
        return out
    return _attention_reference(q, k, v, causal, scale)


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, use_pallas,
               interpret):
    if use_pallas:
        from .pallas.flash_attention import flash_attention_fwd_pallas
        out, lse = flash_attention_fwd_pallas(
            q, k, v, causal=causal, scale=scale, block_q=block_q,
            block_k=block_k, interpret=interpret)
        return out, (q, k, v, out, lse)
    return _flash(q, k, v, causal, scale, block_q, block_k, use_pallas,
                  interpret), (q, k, v, None, None)


def _flash_bwd(causal, scale, block_q, block_k, use_pallas, interpret,
               res, dout):
    q, k, v, out, lse = res
    if use_pallas and lse is not None:
        # blockwise Pallas backward: O(seq*d) memory, replays score
        # blocks from the saved logsumexp
        from .pallas.flash_attention import flash_attention_bwd_pallas
        delta = jnp.sum(dout.astype(jnp.float32)
                        * out.astype(jnp.float32), axis=-1)
        return flash_attention_bwd_pallas(
            q, k, v, lse, dout, delta, causal=causal, scale=scale,
            block_q=block_q, block_k=block_k, interpret=interpret)
    return _xla_attention_bwd(q, k, v, dout, causal, scale)


_flash.defvjp(_flash_fwd, _flash_bwd)


# masked variant: the padding mask (batch, seq_q, seq_k) rides into the
# kernels; heads is static so programs can map bh -> batch
@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash_masked(q, k, v, maskf, scale, block_q, block_k, use_pallas,
                  heads, interpret):
    if use_pallas:
        from .pallas.flash_attention import flash_attention_fwd_pallas
        out, _lse = flash_attention_fwd_pallas(
            q, k, v, maskf, causal=False, scale=scale, block_q=block_q,
            block_k=block_k, heads=heads, interpret=interpret)
        return out
    m = jnp.repeat(maskf, heads, axis=0)
    return _attention_reference_masked(q, k, v, m, scale)


def _attention_reference_masked(q, k, v, mask_bh, scale):
    s = jax.lax.dot_general(
        q, k, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask_bh > 0, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jax.lax.dot_general(
        p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def _flash_masked_fwd(q, k, v, maskf, scale, block_q, block_k, use_pallas,
                      heads, interpret):
    if use_pallas:
        from .pallas.flash_attention import flash_attention_fwd_pallas
        out, lse = flash_attention_fwd_pallas(
            q, k, v, maskf, causal=False, scale=scale, block_q=block_q,
            block_k=block_k, heads=heads, interpret=interpret)
        return out, (q, k, v, maskf, out, lse)
    out = _flash_masked(q, k, v, maskf, scale, block_q, block_k,
                        use_pallas, heads, interpret)
    return out, (q, k, v, maskf, None, None)


def _flash_masked_bwd(scale, block_q, block_k, use_pallas, heads,
                      interpret, res, dout):
    q, k, v, maskf, out, lse = res
    if use_pallas and lse is not None:
        from .pallas.flash_attention import flash_attention_bwd_pallas
        delta = jnp.sum(dout.astype(jnp.float32)
                        * out.astype(jnp.float32), axis=-1)
        dq, dk, dv = flash_attention_bwd_pallas(
            q, k, v, lse, dout, delta, maskf, causal=False, scale=scale,
            block_q=block_q, block_k=block_k, heads=heads,
            interpret=interpret)
    else:
        m = jnp.repeat(maskf, heads, axis=0)
        dq, dk, dv = _xla_attention_bwd(q, k, v, dout, False, scale,
                                        mask=m)
    return dq, dk, dv, jnp.zeros_like(maskf)


_flash_masked.defvjp(_flash_masked_fwd, _flash_masked_bwd)


def _kernel_choice(seq, block_q, block_k, use_pallas):
    """THE selection point (docs/kernels.md): one registry consult
    replaces the five ``use_pallas`` branches that used to be scattered
    through this file.  Auto mode carries the measured v5e crossover
    (seq >= 256 -- see ``kernels/flash_attention.py`` for the per-seq
    numbers) and picks the Pallas kernels on TPU only; forced mode runs
    them in interpret mode on CPU so tests exercise the kernel bodies;
    availability and seq/block divisibility are checked once here."""
    from ..kernels import choose
    return choose("flash_attention", force=use_pallas, seq=seq,
                  block_q=block_q, block_k=block_k)


@register("flash_attention", args=("q", "k", "v"))
def _flash_attention_op(q, k, v, causal=False, scale=-1.0, use_pallas=None,
                        block_q=256, block_k=256):
    """Fused scaled-dot-product attention over (batch*heads, seq,
    head_dim) tensors.  ``use_pallas``: True = Pallas kernels (forward
    AND blockwise backward, O(seq*d) memory), False = XLA reference
    path (plain softmax attention, autodiffed by XLA -- the fastest
    short-sequence path), None (default) = the kernel registry's
    policy (``kernels.choose('flash_attention')``): Pallas above the
    measured crossover on TPU, the plain XLA path otherwise -- with no
    custom_vjp wrapper on the fallback, so XLA saves the softmax from
    the forward instead of recomputing it in the backward.
    ``scale < 0`` means 1/sqrt(head_dim)."""
    if scale is None or scale < 0:
        scale = 1.0 / math.sqrt(q.shape[-1])
    causal, scale = bool(causal), float(scale)
    block_q, block_k = int(block_q), int(block_k)
    ch = _kernel_choice(q.shape[1], block_q, block_k, use_pallas)
    if ch.use_pallas:
        # (batch*heads, seq, d) is batch-major: under a dp-sharded
        # batch the kernels run per shard (XLA cannot partition them)
        from ..parallel.mesh import shard_over_batch
        return shard_over_batch(
            lambda q, k, v: _flash(q, k, v, causal, scale, block_q,
                                   block_k, True, ch.interpret),
            q, k, v)
    return _attention_reference(q, k, v, causal, scale)


@register("flash_attention_masked", args=("q", "k", "v", "mask"))
def _flash_attention_masked_op(q, k, v, mask, scale=-1.0, use_pallas=None,
                               heads=1, block_q=256, block_k=256):
    """Masked flash attention: ``mask`` is (batch, seq_q, seq_k) with
    nonzero = attend, shared across the ``heads`` heads folded into
    q/k/v's leading dim.  Same kernel selection rules as
    ``flash_attention`` (one registry consult)."""
    if scale is None or scale < 0:
        scale = 1.0 / math.sqrt(q.shape[-1])
    scale = float(scale)
    block_q, block_k = int(block_q), int(block_k)
    heads = int(heads)
    maskf = mask.astype(jnp.float32)
    ch = _kernel_choice(q.shape[1], block_q, block_k, use_pallas)
    if ch.use_pallas:
        # a shard's rows map onto its OWN slice of the (batch, seq,
        # seq) mask: local bh // heads is the local batch index
        from ..parallel.mesh import shard_over_batch
        return shard_over_batch(
            lambda q, k, v, m: _flash_masked(q, k, v, m, scale, block_q,
                                             block_k, True, heads,
                                             ch.interpret),
            q, k, v, maskf)
    return _attention_reference_masked(
        q, k, v, jnp.repeat(maskf, heads, axis=0), scale)
