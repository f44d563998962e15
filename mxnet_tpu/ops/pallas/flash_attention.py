"""Flash attention kernels (Pallas/TPU): forward AND backward, with
optional padding-mask support.

Replaces the reference's fused BERT attention kernels
(``src/operator/contrib/transformer.cc :: interleaved_matmul_selfatt_*``,
which materialize the (seq, seq) score matrix in HBM) with the blockwise
online-softmax algorithm: scores never leave VMEM, so HBM traffic is
O(seq*d) instead of O(seq^2) in BOTH directions -- the backward replays
score blocks from the forward-saved logsumexp instead of materializing
the fp32 score matrix, which is what makes long-context training
memory-feasible.

Layout: (batch*heads, seq, head_dim); optional mask (batch, seq, seq)
with 1 = attend (``heads`` static so kernels can map bh -> batch).

Precision is read from the operands, the rule of
``ops/transformer.py::_attention_reference``: every matmul takes its
operands in the dtype q, k, v (and dout) arrive in and accumulates in
float32 (``preferred_element_type``), so bf16 operands reach the MXU as
bf16 (one pass) and float32 operands as float32.  ``p`` is rounded to
``v``'s dtype before ``p @ v`` / ``p^T @ dout`` and ``ds`` to ``q``'s
before ``ds^T @ q`` / ``ds @ k``; everything that is not an MXU operand
stays float32: the scores, the running maximum and sum, ``lse``,
``delta``, ``p`` and ``dp`` before their casts, and the ``acc`` / ``dq``
/ ``dk`` / ``dv`` accumulators.

The softmax scale is folded into the q tile (forward) or the k tile
(backward) once a program, and into the finished ``dk`` once, so the
inner loops multiply no (block, block) score tile by it.  A non-causal
call whose whole score row fits VMEM comfortably (``_tiles``: seq 512
is one (512, 512) tile a head) takes all its keys (forward) or queries
(backward) in one tile: a plain softmax, no running-maximum rescale.
Longer and causal calls walk ``block``-sized tiles with the online
softmax; ``block_q`` / ``block_k`` are what such a walk is made of.

The backward is ONE kernel a (bh, kv block): ``s``, ``p``, ``dp`` and
``ds`` are formed once and feed all of ``dv``, ``dk`` and ``dq`` (five
matmuls and one ``exp`` pass, the published backward), ``dq``
accumulated in float32 in VMEM over the kv blocks of one ``bh``.  It
works on the TRANSPOSED tile (kv rows, q columns): ``lse`` and
``delta`` then broadcast along sublanes from the lane-major rows they
are stored in, and four of the five matmuls need no transpose.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

NEG_INF = -1e30

# a (block, seq) float32 score tile up to this size is taken whole; the
# backward keeps four such tiles alive beside its operands
WHOLE_ROW_BYTES = 1 << 20

try:  # pallas import kept lazy-safe: CPU-only builds fall back to XLA
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    _HAS_PALLAS = True
except Exception:  # pragma: no cover
    _HAS_PALLAS = False

_NT = (((1,), (1,)), ((), ()))   # a @ b^T
_NN = (((1,), (0,)), ((), ()))   # a @ b
_TN = (((0,), (0,)), ((), ()))   # a^T @ b


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _scaled(x, scale):
    """``x * scale`` in float32, back in the dtype the MXU is fed."""
    return (x.astype(jnp.float32) * scale).astype(x.dtype)


def _causal(tile, q0, k0, q_axis):
    """``tile`` with the keys after their query at NEG_INF; the tile's
    q positions start at ``q0`` along ``q_axis``, its keys at ``k0``
    along the other."""
    qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, tile.shape, q_axis)
    kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, tile.shape, 1 - q_axis)
    return jnp.where(qpos >= kpos, tile, NEG_INF)


def _tiles(seq, own, walk, causal):
    """Grow a call's tiles to the score budget: ``(own, walk)`` are the
    rows a program owns (q rows in the forward, kv rows in the backward)
    and the rows of the other side it walks at a time.  A non-causal
    call whose (own, seq) score tile fits walks its whole row at once,
    and owns the whole sequence too where (seq, seq) fits; a causal
    call keeps its blocks, because the loop skips the tiles above the
    diagonal that a whole row would compute."""
    if not causal and seq * own * 4 <= WHOLE_ROW_BYTES:
        walk = seq
        if seq * seq * 4 <= WHOLE_ROW_BYTES:
            own = seq
    return own, walk


# ----------------------------------------------------------------------
# forward
# ----------------------------------------------------------------------

def _fwd_kernel(*refs, block_k, causal, scale, seq_len, has_mask):
    if has_mask:
        q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref = refs
        mask_ref = None
    qi = pl.program_id(1)
    q = _scaled(q_ref[0], scale)               # (block_q, d)
    block_q, d = q.shape

    def scores(j):
        k = k_ref[0, pl.ds(j * block_k, block_k), :]
        s = _dot(q, k, _NT)                      # (bq, bk) float32
        if causal:
            s = _causal(s, qi * block_q, j * block_k, 0)
        if mask_ref is not None:
            mblk = mask_ref[0, :, pl.ds(j * block_k, block_k)]
            s = jnp.where(mblk > 0, s, NEG_INF)
        return s

    def pv(p, j):
        v = v_ref[0, pl.ds(j * block_k, block_k), :]
        return _dot(p.astype(v.dtype), v, _NN)

    if block_k == seq_len:
        # every key in one tile: a plain softmax
        s = scores(0)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=-1, keepdims=True)
        acc = pv(p, 0)
    else:
        num_kv = pl.cdiv(seq_len, block_k)
        if causal:
            # only blocks at or left of the diagonal contribute
            num_kv = pl.cdiv((qi + 1) * block_q, block_k)

        def body(j, carry):
            m, l, acc = carry
            s = scores(j)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
            return m_new, l_new, acc * alpha + pv(p, j)

        m0 = jnp.full((block_q, 1), NEG_INF, jnp.float32)
        l0 = jnp.zeros((block_q, 1), jnp.float32)
        acc0 = jnp.zeros((block_q, d), jnp.float32)
        m, l, acc = jax.lax.fori_loop(0, num_kv, body, (m0, l0, acc0))
    l_safe = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / l_safe).astype(o_ref.dtype)
    # logsumexp per row, replicated over 8 sublanes: Mosaic requires the
    # last two block dims be (8, 128)-tileable, so a (1, block_q) row
    # is stored as (8, block_q) and row 0 read back
    row = (m + jnp.log(l_safe))[:, 0]
    lse_ref[0] = jnp.broadcast_to(row[None, :], (8, row.shape[0]))


@functools.partial(jax.jit, static_argnames=(
    "causal", "scale", "block_q", "block_k", "heads", "interpret"))
def flash_attention_fwd_pallas(q, k, v, mask=None, causal=False, scale=1.0,
                               block_q=256, block_k=256, heads=1,
                               interpret=False):
    """q,k,v: (bh, seq, d) [+ mask (b, seq, seq), 1 = attend]
    -> (out (bh, seq, d), lse (bh, seq))."""
    bh, seq, d = q.shape
    block_q = min(block_q, seq)
    block_k = min(block_k, seq)
    assert seq % block_q == 0 and seq % block_k == 0, \
        "flash attention needs seq divisible by block sizes"
    block_q, block_k = _tiles(seq, block_q, block_k, causal)
    grid = (bh, seq // block_q)
    kernel = functools.partial(_fwd_kernel, block_k=block_k, causal=causal,
                               scale=scale, seq_len=seq,
                               has_mask=mask is not None)
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
        pl.BlockSpec((1, seq, d), lambda b, i: (b, 0, 0)),
        pl.BlockSpec((1, seq, d), lambda b, i: (b, 0, 0)),
    ]
    args = [q, k, v]
    if mask is not None:
        # mask is (batch, seq, seq); program b indexes batch = bh // heads
        in_specs.append(pl.BlockSpec(
            (1, block_q, seq), lambda b, i: (b // heads, i, 0)))
        args.append(mask)
    out, lse8 = pl.pallas_call(
        kernel,
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((bh, 8, seq), jnp.float32)],
        grid=grid,
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
                   pl.BlockSpec((1, 8, block_q),
                                lambda b, i: (b, 0, i))],
        interpret=interpret,
    )(*args)
    return out, lse8[:, 0, :]


# ----------------------------------------------------------------------
# backward: one kernel a (bh, kv block), on the transposed score tile
# ----------------------------------------------------------------------

def _bwd_kernel(*refs, block_q, causal, scale, seq_len, has_mask):
    if has_mask:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, maskt_ref,
         dq_ref, dk_ref, dv_ref, dq_acc) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dq_ref, dk_ref, dv_ref, dq_acc) = refs
        maskt_ref = None
    ki = pl.program_id(1)
    k = _scaled(k_ref[0], scale)               # (block_k, d)
    v = v_ref[0]
    block_k, d = k.shape

    @pl.when(ki == 0)
    def _():
        dq_acc[...] = jnp.zeros(dq_acc.shape, jnp.float32)

    def step(j, carry):
        dk, dv = carry
        rows_q = pl.ds(j * block_q, block_q)
        qj = q_ref[0, rows_q, :]
        doj = do_ref[0, rows_q, :]
        st = _dot(k, qj, _NT)                    # (bk, bq) = s^T
        if causal:
            st = _causal(st, j * block_q, ki * block_k, 1)
        if maskt_ref is not None:
            st = jnp.where(maskt_ref[0, :, rows_q] > 0, st, NEG_INF)
        pt = jnp.exp(st - lse_ref[0, 0:1, rows_q])
        dv = dv + _dot(pt.astype(doj.dtype), doj, _NN)
        dpt = _dot(v, doj, _NT)
        dst = (pt * (dpt - delta_ref[0, 0:1, rows_q])).astype(qj.dtype)
        dk = dk + _dot(dst, qj, _NN)
        dq_acc[rows_q, :] += _dot(dst, k, _TN)   # (bq, d): scale is in k
        return dk, dv

    zeros = jnp.zeros((block_k, d), jnp.float32)
    if block_q == seq_len:
        dk, dv = step(0, (zeros, zeros))
    else:
        start_q = 0
        if causal:
            # q rows strictly above the block's first kv column never
            # attend
            start_q = (ki * block_k) // block_q
        dk, dv = jax.lax.fori_loop(start_q, pl.cdiv(seq_len, block_q),
                                   step, (zeros, zeros))
    dk_ref[0] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)

    @pl.when(ki == pl.num_programs(1) - 1)
    def _():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "scale", "block_q", "block_k", "heads", "interpret"))
def flash_attention_bwd_pallas(q, k, v, lse, dout, delta, mask=None,
                               causal=False, scale=1.0, block_q=256,
                               block_k=256, heads=1, interpret=False):
    """Blockwise flash backward -> (dq, dk, dv), O(seq*d) memory.

    ``delta`` is rowsum(dout * out) -- the softmax-jacobian correction,
    computed outside so the saved residuals are just (q, k, v, out, lse).
    """
    bh, seq, d = q.shape
    block_q = min(block_q, seq)
    block_k = min(block_k, seq)
    block_k, block_q = _tiles(seq, block_k, block_q, causal)

    # (bh, seq) row vectors carried in the (bh, 8, seq) sublane-
    # replicated layout the Mosaic tiling rules want (see fwd)
    lse8 = jnp.broadcast_to(lse[:, None, :], (bh, 8, seq))
    delta8 = jnp.broadcast_to(delta[:, None, :], (bh, 8, seq))

    seq_spec = pl.BlockSpec((1, seq, d), lambda b, i: (b, 0, 0))
    kv_spec = pl.BlockSpec((1, block_k, d), lambda b, i: (b, i, 0))
    vec_spec = pl.BlockSpec((1, 8, seq), lambda b, i: (b, 0, 0))

    args = [q, k, v, dout, lse8, delta8]
    in_specs = [seq_spec, kv_spec, kv_spec, seq_spec, vec_spec, vec_spec]
    if mask is not None:
        # the kernel's tile is (kv rows, q columns): the mask transposed
        args.append(jnp.swapaxes(mask, 1, 2))
        in_specs.append(pl.BlockSpec(
            (1, block_k, seq), lambda b, i: (b // heads, i, 0)))

    return pl.pallas_call(
        functools.partial(_bwd_kernel, block_q=block_q, causal=causal,
                          scale=scale, seq_len=seq,
                          has_mask=mask is not None),
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        grid=(bh, seq // block_k),
        in_specs=in_specs,
        # dq's block stays put over the kv axis: written once, at its end
        out_specs=[seq_spec, kv_spec, kv_spec],
        scratch_shapes=[pltpu.VMEM((seq, d), jnp.float32)],
        interpret=interpret,
    )(*args)
