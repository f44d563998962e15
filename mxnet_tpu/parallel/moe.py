"""Mixture-of-Experts with expert parallelism (the ``ep`` mesh axis).

TPU-native design: experts are ONE stacked parameter (E, d_in, d_hid)
sharded on its expert axis over ``ep``; routing is a dense one-hot
dispatch einsum, so the token shuffle to expert shards lowers to XLA's
all-to-all over ICI instead of hand-written send/recv.  Capacity is
static (tokens per expert bounded at C), which keeps every shape fixed
for the compiler -- the standard TPU MoE recipe (GShard/Switch), not a
translation of any CPU-style dynamic routing.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .. import telemetry as _telemetry
from ..base import MXNetError
from ..gluon.block import HybridBlock


class MixtureOfExperts(HybridBlock):
    """Top-1 (Switch) MoE feed-forward layer (reference pattern: the
    published Switch-Transformer recipe; the reference framework has no
    MoE -- this is TPU-native net-new surface the ``ep`` axis needs).

    Input (tokens, d_model) -> gate -> dispatch at capacity ->
    per-expert FFN -> combine.  ``shard(mesh)`` places the stacked
    expert weights over the ``ep`` axis.
    """

    def __init__(self, num_experts, d_model, d_hidden, capacity_factor=1.25,
                 mesh=None, axis="ep", **kwargs):
        super().__init__(**kwargs)
        self._E = int(num_experts)
        self._dm = int(d_model)
        self._dh = int(d_hidden)
        self._cf = float(capacity_factor)
        self._mesh = mesh
        self._axis = axis
        from .. import initializer as init_mod
        # per-expert Xavier fan: the generic Xavier rule would read the
        # stacked (E, d_in, d_out) shape as a conv kernel and mis-scale
        bound = float((6.0 / (d_model + d_hidden)) ** 0.5)
        with self.name_scope():
            self.gate = self.params.get(
                "gate", shape=(d_model, num_experts), init="xavier")
            self.w_up = self.params.get(
                "w_up", shape=(num_experts, d_model, d_hidden),
                init=init_mod.Uniform(bound))
            self.w_down = self.params.get(
                "w_down", shape=(num_experts, d_hidden, d_model),
                init=init_mod.Uniform(bound))

    def shard(self, mesh=None):
        from .tensor_parallel import place_param
        mesh = mesh or self._mesh
        if mesh is None:
            raise MXNetError("no mesh to shard over")
        for p, spec in ((self.w_up, P(self._axis, None, None)),
                        (self.w_down, P(self._axis, None, None)),
                        (self.gate, P())):
            place_param(p, mesh, spec)
        return self

    def hybrid_forward(self, F, x, gate=None, w_up=None, w_down=None):
        from ..ndarray import NDArray
        xv = x._data if isinstance(x, NDArray) else x
        gv = gate._data if isinstance(gate, NDArray) else gate
        uv = w_up._data if isinstance(w_up, NDArray) else w_up
        dv = w_down._data if isinstance(w_down, NDArray) else w_down
        out = _moe_forward(xv, gv, uv, dv, self._E, self._cf)
        return NDArray(out) if isinstance(x, NDArray) else out


def _moe_forward(x, gate_w, w_up, w_down, E, capacity_factor):
    """(T, d) tokens -> (T, d); static-capacity top-1 dispatch."""
    T, d = x.shape
    C = max(1, int(capacity_factor * T / E))

    logits = x @ gate_w                               # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    expert = jnp.argmax(probs, axis=-1)               # (T,)
    gate_val = jnp.max(probs, axis=-1)                # (T,)

    # position of each token within its expert's queue
    onehot = jax.nn.one_hot(expert, E, dtype=jnp.int32)       # (T, E)
    pos = jnp.cumsum(onehot, axis=0) * onehot                 # 1-based
    pos_in_expert = jnp.sum(pos, axis=-1) - 1                 # (T,)
    keep = pos_in_expert < C                                  # overflow drops

    # dispatch tensor (T, E, C): token t -> slot (e, c)
    disp = (onehot.astype(x.dtype)[:, :, None]
            * jax.nn.one_hot(jnp.clip(pos_in_expert, 0, C - 1), C,
                             dtype=x.dtype)[:, None, :]
            * keep[:, None, None].astype(x.dtype))
    # all-to-all: (E, C, d) expert inboxes -- XLA shuffles over `ep`
    inbox = jnp.einsum("tec,td->ecd", disp, x)
    h = jax.nn.gelu(jnp.einsum("ecd,edh->ech", inbox, w_up))
    out_e = jnp.einsum("ech,ehd->ecd", h, w_down)
    # combine back to token order, weighted by the gate
    out = jnp.einsum("tec,ecd->td", disp, out_e)
    return out * gate_val[:, None]


def moe_load_balancing_loss(x, gate_w):
    """Auxiliary load-balance loss (Switch eq. 4): E * sum_e f_e * p_e."""
    T = x.shape[0]
    logits = x @ gate_w
    probs = jax.nn.softmax(logits, axis=-1)
    E = probs.shape[-1]
    expert = jnp.argmax(probs, axis=-1)
    frac = jnp.mean(jax.nn.one_hot(expert, E, dtype=probs.dtype), axis=0)
    prob_mean = jnp.mean(probs, axis=0)
    return E * jnp.sum(frac * prob_mean)


# ----------------------------------------------------------------------
# routed experts of an expert-parallel deployment (DeepSeek-V3 style)
# ----------------------------------------------------------------------

def route_top_k(x, gate_w, selection_bias, top_k, scale=1.0,
                scoring="sigmoid", normalize=True):
    """The router of an expert layer over ALL experts.

    ``x`` (tokens, d); ``gate_w`` (d, experts); ``scoring`` how a logit
    becomes a score: ``"sigmoid"`` (DeepSeek-V3) or ``"softmax"`` over
    all the experts (Mixtral and its descendants); ``selection_bias``
    (experts,) or None, added to the scores for the choice only
    (``e_score_correction_bias``).  Returns ``(chosen (tokens, top_k)
    int32, weights (tokens, top_k) float32)``: ``scores = scoring(x
    gate_w)``, ``chosen = top_k(scores + bias)``, ``weights =
    scores[chosen] * scale``, divided first by their sum (+ 1e-20) where
    ``normalize``.  float32 throughout and at matmul precision
    ``highest``: the choice is discontinuous, so a score is not to
    differ from another implementation's by more than float32 rounding.
    """
    if scoring not in ("sigmoid", "softmax"):
        raise MXNetError("route_top_k: scoring is 'sigmoid' or 'softmax', "
                         "got %r" % (scoring,))
    logits = jnp.dot(x.astype(jnp.float32), gate_w.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits) if scoring == "sigmoid" \
        else jax.nn.softmax(logits, axis=-1)
    biased = scores if selection_bias is None \
        else scores + selection_bias.astype(jnp.float32)
    _, chosen = jax.lax.top_k(biased, int(top_k))
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if normalize:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                             + 1e-20)
    return chosen.astype(jnp.int32), weights * scale


# a decode step of at most this many tokens may take every held expert
# over every token (``routed_experts``, ``decode_step=``).  The chip has
# run that route at 16 and at 32 tokens, the decode buckets of the one
# deployment that takes it (PERF.md section 6, PR 32), and at no more:
# the bound is what was measured, not where the roofline would put it
DENSE_TOKENS = 32

# the most tokens one pass of the gather route sorts and combines: its
# buffer of weighed rows is top_k x BLOCK_TOKENS x d float32, 302 MB at
# Mellum2's widths beside a 16,384-token prefill's 0.80 GB of
# temporaries on a chip that is 96% full (PERF.md section 4)
BLOCK_TOKENS = 4096


def routed_experts(x, chosen, weights, w_gate, w_up, w_down, first_expert,
                   live=None, chunk_rows=2048, num_experts=None,
                   decode_step=False, use_pallas=None):
    """The part of an expert layer's output that THIS chip's experts
    give, for an expert layer that is told which experts it holds.

    ``x`` (tokens, d); ``chosen`` / ``weights`` (tokens, top_k) from
    :func:`route_top_k` over all experts; ``w_gate`` / ``w_up``
    (n_held, d, f) and ``w_down`` (n_held, f, d) the stacked weights of
    experts ``first_expert .. first_expert + n_held - 1``, each a gated
    SiLU MLP ``w_down(silu(x w_gate) * (x w_up))``; ``live`` (tokens,)
    bool masks padding tokens out; ``num_experts`` the width of the
    router (all experts, held or not), where the caller knows it.
    Returns ``(y (tokens, d) float32, counts (n_held,) int32)``: ``y[t]
    = sum over the chosen experts held here of weights * Expert(x[t])``
    and ``counts`` the tokens each held expert was given.  What the
    experts held elsewhere would add is left out; on one chip there is
    no exchange and nothing stands in for one.

    No capacity and no dropped token: the assignments that fall on held
    experts are sorted by expert and go through a grouped matmul
    (``kernels.grouped_matmul``: ``jax.lax.ragged_dot``, or on a TPU and
    for a layer of many experts the Pallas kernel; ``use_pallas`` is the
    caller's tri-state as everywhere in the kernel tier) ``chunk_rows``
    sorted rows at a time, in a loop that runs as many chunks as there
    ARE such assignments -- one for a decode step, ``tokens * top_k /
    chunk_rows`` if every token chose only experts held here.  Each
    chunk's rows are weighed in float32 by their router weights, and
    then combined into their tokens' rows by one of two routes, chosen
    from the call's shapes:

    - **a gather**, where this layer holds every expert the router
      scores (``first_expert`` 0, ``n_held >= num_experts``): every
      assignment of a live token is then ours.  The chunks write their
      rows in sorted order into a float32 buffer, contiguous, and after
      the loop each token sums its ``top_k`` rows, found through the
      inverse of the sort, in float32.  The buffer is a row an
      assignment, so sort, matmul and combine run over blocks of at
      most ``BLOCK_TOKENS`` tokens, each sorted on its own.
    - **a scatter-add** into ``y`` inside the loop, for a layer that
      holds a few of many experts (12 of 384: a buffer of every
      assignment would be 32 times the rows computed) or a caller that
      does not give ``num_experts``.  A token's assignments collide
      there, and a TPU runs colliding updates one by one: 1.2 us a
      token a layer in Mellum2's prefill (PERF.md section 5, PR 36).

    Both give the same sums to float32 rounding (the order of a token's
    terms differs); the counters ``moe.combine_gather`` /
    ``moe.combine_scatter`` count the layers traced through each.

    **A decode step that keeps every expert busy** may take a third
    route to the same sum.  A caller whose tokens are one decode step's,
    a slot each, says so by ``decode_step``; every prefill is the
    grouped matmul above.  Where such a step has at most
    ``DENSE_TOKENS`` tokens and the router sends an expert a token or
    more on average (``tokens * top_k >= num_experts``), every held
    expert runs over every token as one batched matmul and a token's row
    is weighed in float32 by its router weight, zero where it did not
    choose the expert.  A token chooses an expert at most once, so that
    is exact with nothing dropped, it reads each expert's weights once
    as the grouped matmul must, and it has no sort, gather or group
    loop: 64 whole experts at 4 tokens each took the grouped matmul
    1.55 ms a matmul where their 264 MB are 0.32 ms of the chip's
    bandwidth (my chip runs, PR 32).  A decode step of a deployment that
    holds a few of many experts (12 of 384 at 0.7 tokens each) stays on
    the grouped matmul, which reads the experts that were chosen alone.
    """
    n_held = w_gate.shape[0]
    tokens, top_k = chosen.shape
    local = chosen - first_expert
    held = (local >= 0) & (local < n_held)
    if live is not None:
        held = held & live[:, None]
    # an assignment's expert here, n_held for one that is not ours
    expert = jnp.where(held, local, n_held)
    if decode_step and num_experts is not None and tokens <= DENSE_TOKENS \
            and tokens * top_k >= num_experts:
        return _every_expert_over_every_token(
            x, expert, weights, w_gate, w_up, w_down)
    gather = num_experts is not None and first_expert == 0 \
        and n_held >= num_experts
    if _telemetry._ENABLED:
        _telemetry.hooks.moe_combine(gather)
    experts = (w_gate, w_up, w_down)
    if not gather:
        return _sorted_chunks(x, expert, weights, experts, chunk_rows,
                              use_pallas, gather=False)
    block = BLOCK_TOKENS
    if tokens <= block:
        return _sorted_chunks(x, expert, weights, experts, chunk_rows,
                              use_pallas, gather=True)
    # whole blocks, the last padded with assignments of no one
    n_blocks = -(-tokens // block)
    pad = n_blocks * block - tokens

    def blocked(a, fill):
        a = jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1),
                    constant_values=fill)
        return a.reshape((n_blocks, block) + a.shape[1:])

    def one_block(_, part):
        return None, _sorted_chunks(*part, experts, chunk_rows, use_pallas,
                                    gather=True)

    _, (y, counts) = jax.lax.scan(
        one_block, None, (blocked(x, 0), blocked(expert, n_held),
                          blocked(weights, 0)))
    return y.reshape(n_blocks * block, -1)[:tokens], \
        jnp.sum(counts, axis=0)


def _sorted_chunks(x, expert, weights, experts, chunk_rows, use_pallas,
                   gather):
    """``routed_experts``' grouped matmul over the assignments of
    ``expert`` (tokens, top_k), ``n_held`` for one that is not ours, and
    its combine: by a gather through the inverse of the sort, or by a
    scatter-add into ``y`` (the routes of its doc)."""
    from ..kernels.grouped_matmul import grouped_matmul
    w_gate, w_up, w_down = experts
    n_held, d, _f = w_gate.shape
    tokens, top_k = expert.shape
    n_assign = tokens * top_k
    expert = expert.reshape(n_assign)
    chunk = min(int(chunk_rows), n_assign)
    n_chunks = -(-n_assign // chunk)
    # the four parts bear a scope each under the caller's (a device
    # trace reads ``h3/experts/gather``): sort, gather, matmul, combine
    with jax.named_scope("sort"):
        # a compare-and-sum, not a scatter-add: every update of a scatter
        # into n_held bins collides, and a TPU runs those one by one
        counts = jnp.sum(
            expert[:, None] == jnp.arange(n_held, dtype=jnp.int32),
            axis=0, dtype=jnp.int32)
        ends = jnp.cumsum(counts)
        starts, total = ends - counts, ends[-1]
        # ours first and grouped by expert; padded to whole chunks
        order = jnp.argsort(expert, stable=True).astype(jnp.int32)
        padded = jnp.pad(order, (0, n_chunks * chunk - n_assign))
    flat_w = weights.reshape(n_assign)

    def one_chunk(c, acc):
        at = c * chunk
        rows = jax.lax.dynamic_slice(padded, (at,), (chunk,))
        ours = at + jnp.arange(chunk, dtype=jnp.int32) < total
        token = rows // top_k
        # this chunk's share of every expert's group
        sizes = jnp.clip(ends - at, 0, chunk) - jnp.clip(starts - at, 0,
                                                          chunk)
        with jax.named_scope("gather"):
            xs = jnp.take(x, token, axis=0)
        with jax.named_scope("matmul"):
            gate = grouped_matmul(xs, w_gate, sizes, use_pallas)
            up = grouped_matmul(xs, w_up, sizes, use_pallas)
            hidden = (jax.nn.silu(gate) * up).astype(x.dtype)
            out = grouped_matmul(hidden, w_down, sizes, use_pallas)
        with jax.named_scope("combine"):
            # rows past the last of ours belong to no group
            out = jnp.where(ours[:, None],
                            out * jnp.take(flat_w, rows)[:, None], 0.0)
            if gather:
                # in sorted order at the chunk's own rows: no collision
                return jax.lax.dynamic_update_slice(acc, out, (at, 0))
            return acc.at[token].add(out)

    acc = jax.lax.fori_loop(
        0, -(-total // chunk), one_chunk,
        jnp.zeros((n_chunks * chunk if gather else tokens, d), jnp.float32))
    if not gather:
        return acc, counts
    with jax.named_scope("combine"):
        # where each assignment's row lies in the buffer; rows past the
        # last of ours belong to no one, so a row is chosen by where
        inv = jnp.argsort(order).astype(jnp.int32).reshape(tokens, top_k)
        ours = (expert < n_held).reshape(tokens, top_k)
        y = _gather_rows(acc, inv, ours)
    return y, counts


# sorted rows one step of the combine gathers: 4,096 float32 rows of
# 2,304 (37.7 MB), which the compiler keeps in the core's fast memory
# for the sum that reads them.  Gathered all at once, a block's rows
# (302 MB at Mellum2's widths) went to HBM and back, 907 MB of
# temporaries for one layer (compiled for a described v5e, PR 37)
COMBINE_ROWS = 4096


def _gather_rows(buf, inv, ours):
    """``y[t] = sum over k of where(ours[t, k], buf[inv[t, k]], 0)`` in
    float32 (``buf`` (rows, d) float32; ``inv`` / ``ours`` (tokens,
    top_k)), ``COMBINE_ROWS`` gathered rows a step."""
    tokens, top_k = inv.shape
    tile = min(tokens, max(1, COMBINE_ROWS // top_k))
    n_tiles = -(-tokens // tile)
    pad = ((0, n_tiles * tile - tokens), (0, 0))
    inv, ours = jnp.pad(inv, pad), jnp.pad(ours, pad)

    def one_tile(i, y):
        at = i * tile
        rows = jnp.take(buf, jax.lax.dynamic_slice(
            inv, (at, 0), (tile, top_k)).reshape(tile * top_k), axis=0)
        ok = jax.lax.dynamic_slice(ours, (at, 0), (tile, top_k))
        part = jnp.sum(jnp.where(ok[:, :, None],
                                 rows.reshape(tile, top_k, -1), 0.0),
                       axis=1)
        return jax.lax.dynamic_update_slice(y, part, (at, 0))

    y = jax.lax.fori_loop(
        0, n_tiles, one_tile,
        jnp.zeros((n_tiles * tile, buf.shape[1]), jnp.float32))
    return y[:tokens]


def _every_expert_over_every_token(x, expert, weights, w_gate, w_up, w_down):
    """``routed_experts`` for a decode step that keeps every expert busy
    (its doc): ``expert`` (tokens, top_k) is each assignment's expert
    here, ``n_held`` for one that is not ours."""
    n_held = w_gate.shape[0]
    ours = expert[:, :, None] == jnp.arange(n_held, dtype=jnp.int32)
    # a token's weight for each held expert, zero where it did not choose it
    weight = jnp.sum(jnp.where(ours, weights[:, :, None], 0.0), axis=1)
    gate = jnp.einsum("td,edf->etf", x, w_gate,
                      preferred_element_type=jnp.float32)
    up = jnp.einsum("td,edf->etf", x, w_up,
                    preferred_element_type=jnp.float32)
    hidden = (jax.nn.silu(gate) * up).astype(x.dtype)
    out = jnp.einsum("etf,efd->etd", hidden, w_down,
                     preferred_element_type=jnp.float32)
    # float32 times float32, as the grouped matmul weighs its rows: a
    # matmul at the default precision may round both to bfloat16 first
    y = jnp.sum(out * weight.T[:, :, None], axis=0)
    return y, jnp.sum(ours, axis=(0, 1), dtype=jnp.int32)
