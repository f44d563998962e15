"""Decode step of Kimi Delta Attention (KDA) over a state a sequence
(Pallas/TPU).

A KDA layer (``serving/decode/linear_moe.py``) keeps, for each head of
each sequence, a float32 state ``S`` (key x value, 128 x 128) in place of
rows of keys and values.  A decode step of one token turns it by the
gated delta rule, per head:

    S'  = Diag(exp(g)) S                 (a decay per key channel)
    S   = S' + k (beta (v - S'^T k))^T   (the delta update)
    o   = S^T q

Stored transposed, ``T = S^T`` (value x key), every vector that runs
along the key (``q``, ``k``, ``g``) is a row that broadcasts over the
tile's sublanes, and the two contractions over the key are lane
reductions whose results (``S'^T k``, ``o``) come out as the columns
that ``v`` and the update need.  ``kb = beta * k`` is handed in, so
``T += (v - T' k) kb^T``.

Layout: ``q``, ``k``, ``kb``, ``g`` ``(slots, heads, key)`` float32;
``v`` ``(slots, heads, value)`` float32; ``state`` ``(rows, heads,
value, key)`` (float32 as served; another dtype is read into float32
and rounded once on the way back); ``rows`` ``(slots,)`` int32, the
state row of each slot (padded slots name the scratch row 0).  Output
``o`` ``(slots, heads, value)`` float32 and the state with each named
row turned.

The kernel's grid is the slots: a step fetches the slot's whole row
(every head: 2 MiB at 32 heads of 128 x 128 float32) once, turns it a
head at a time and writes it back once, into the SAME array
(``input_output_aliases``): the state is read and written once a step
and never copied.  Two slots never name the same live row, so no step
reads a row another step writes; padded slots all name the scratch row.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

try:  # pallas import kept lazy-safe: CPU-only builds fall back to XLA
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
except Exception:  # pragma: no cover
    pl = pltpu = None


# ----------------------------------------------------------------------
# XLA reference / fallback
# ----------------------------------------------------------------------

@jax.jit
def kda_decode_reference(q, k, kb, g, v, state, rows):
    """Gather the slots' rows, turn them, scatter them back: float32
    elementwise arithmetic throughout (no matmul, so no matmul
    precision)."""
    f32 = jnp.float32
    t = jnp.take(state, rows, axis=0).astype(f32)   # (s, H, dv, dk)
    t = t * jnp.exp(g)[:, :, None, :]
    pred = jnp.sum(t * k[:, :, None, :], axis=-1)   # (s, H, dv)
    t = t + (v - pred)[..., None] * kb[:, :, None, :]
    o = jnp.sum(t * q[:, :, None, :], axis=-1)
    return o, state.at[rows].set(t.astype(state.dtype))


# ----------------------------------------------------------------------
# Pallas kernel: grid (slots,), a slot's whole row a step
# ----------------------------------------------------------------------

def _decode_kernel(rows_ref, q_ref, k_ref, kb_ref, g_ref, vt_ref, s_ref,
                   ot_ref, s_out_ref):
    del rows_ref                        # only the index maps read it
    heads = q_ref.shape[1]
    for h in range(heads):
        t = s_ref[0, h].astype(jnp.float32)          # (dv, dk)
        t = t * jnp.exp(g_ref[0, h:h + 1, :])        # decay by key
        pred = jnp.sum(t * k_ref[0, h:h + 1, :], axis=-1, keepdims=True)
        t = t + (vt_ref[0, :, h:h + 1] - pred) * kb_ref[0, h:h + 1, :]
        ot_ref[0, :, h:h + 1] = jnp.sum(t * q_ref[0, h:h + 1, :], axis=-1,
                                        keepdims=True)
        s_out_ref[0, h] = t.astype(s_out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def kda_decode_pallas(q, k, kb, g, v, state, rows, interpret=False):
    """q, k, kb, g (slots, heads, key); v (slots, heads, value); state
    (rows, heads, value, key); rows (slots,) int32 -> (o (slots, heads,
    value) float32, state')."""
    slots, heads, dk = q.shape
    dv = v.shape[-1]
    vec = pl.BlockSpec((1, heads, dk), lambda s, rows: (s, 0, 0))
    col = pl.BlockSpec((1, dv, heads), lambda s, rows: (s, 0, 0))
    row = pl.BlockSpec((1, heads, dv, dk),
                       lambda s, rows: (rows[s], 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(slots,),
        in_specs=[vec, vec, vec, vec, col, row],
        out_specs=[col, row])
    ot, state = pl.pallas_call(
        _decode_kernel,
        out_shape=(jax.ShapeDtypeStruct((slots, dv, heads), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)),
        grid_spec=grid_spec,
        # operand 6 (the scalar-prefetched rows are operand 0) is the
        # state: its rows are written where they were read
        input_output_aliases={6: 1},
        interpret=interpret,
    )(rows, q, k, kb, g, jnp.swapaxes(v, 1, 2), state)
    return jnp.swapaxes(ot, 1, 2), state
