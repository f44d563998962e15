"""What the pure-function decoder specs of this package have in common:
the pieces of a present-day decoder block that ``latent_moe.py``
(latent attention, sigmoid router) and ``window_moe.py`` (grouped-query
attention in window and full layers, softmax router) both run, written
once.

- :func:`rms_norm`, :func:`dot` (float32 accumulation), :func:`swiglu`.
- :func:`yarn_inv_freq` and :func:`rotate`: rotary positions, plain or
  YaRN-scaled, over adjacent pairs ``(2j, 2j+1)`` or over halves ``(j,
  j + dim/2)`` (the ``rotate_half`` convention).
- :func:`causal_attention`: a prefill's attention in blocks with a
  running maximum, K and V kept per K/V head, optionally inside a
  sliding window.
- :func:`routed_ffn` and :func:`new_moe_stats`: the router, the routed
  experts held here (``parallel.moe``) and the counts a program returns
  beside its token.
- :func:`draw_params`: seeded weights, one jitted draw a tensor.

Weights and activations are in the spec's dtype (bfloat16 as served);
statistics, the router, the softmax and the rotary tables are float32.
"""
from __future__ import annotations

import math

import numpy as np

__all__ = ["rms_norm", "dot", "swiglu", "yarn_inv_freq", "rotate",
           "causal_attention", "routed_ffn", "new_moe_stats",
           "draw_params"]

# a prefill's attention scores are computed Q_BLOCK query rows by
# K_BLOCK keys at a time
Q_BLOCK = 256
K_BLOCK = 1024


def rms_norm(x, w, eps):
    """``w * x / sqrt(mean(x^2) + eps)``, statistics in float32, the
    result in ``x``'s dtype."""
    import jax
    import jax.numpy as jnp
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)
            * w.astype(jnp.float32)).astype(x.dtype)


def dot(a, w):
    """a (..., k) x w (k, n), float32 accumulation and result."""
    import jax.numpy as jnp
    return jnp.dot(a, w, preferred_element_type=jnp.float32)


def swiglu(x, w_gate, w_up, w_down):
    """``w_down(silu(x w_gate) * (x w_up))``, float32 out."""
    import jax
    hidden = (jax.nn.silu(dot(x, w_gate)) * dot(x, w_up)).astype(x.dtype)
    return dot(hidden, w_down)


def yarn_inv_freq(dim, theta, scaling=None):
    """Inverse frequencies of the ``dim // 2`` rotary pairs, float64.

    Plain rotary: ``theta^(-2j/dim)``.  With YaRN ``scaling`` (``factor``,
    ``original_max_position_embeddings``, ``beta_fast``, ``beta_slow``):
    pairs that turn fewer than ``beta_slow`` times over the original
    context are interpolated (divided by ``factor``), pairs that turn
    more than ``beta_fast`` times are kept, with a linear ramp between
    (``modeling_deepseek.DeepseekV3YarnRotaryEmbedding``; the
    ``transformers`` ``_compute_yarn_parameters`` is the same formula)."""
    j = np.arange(dim // 2, dtype=np.float64)
    freq = float(theta) ** (-2.0 * j / dim)
    if not scaling:
        return freq
    factor = float(scaling["factor"])
    original = float(scaling["original_max_position_embeddings"])

    def correction(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(float(theta)))
    low = max(math.floor(correction(scaling["beta_fast"])), 0)
    high = min(math.ceil(correction(scaling["beta_slow"])), dim - 1)
    ramp = np.clip((j - low) / max(high - low, 0.001), 0.0, 1.0)
    # ramp 0: the pair turns often, kept; ramp 1: interpolated
    return freq / factor * ramp + freq * (1.0 - ramp)


def rotate(x, positions, inv_freq, halves=False, factor=1.0):
    """Rotary embedding of ``x`` (..., t, [heads,] dim) at ``positions``
    (..., t): pair ``j`` turns by ``position * inv_freq[j]``.  The pairs
    are the adjacent lanes ``(2j, 2j+1)``, or with ``halves`` the lanes
    ``(j, j + dim/2)`` (``rotate_half``); ``factor`` multiplies cos and
    sin alike (YaRN's ``attention_factor``).  float32 in, float32 out."""
    import jax.numpy as jnp
    angle = positions.astype(jnp.float32)[..., None] \
        * jnp.asarray(inv_freq, jnp.float32)
    if x.ndim == angle.ndim + 1:            # a heads axis before dim
        angle = angle[..., None, :]
    cos, sin = jnp.cos(angle) * factor, jnp.sin(angle) * factor
    xf = x.astype(jnp.float32)
    if halves:
        a, b = jnp.split(xf, 2, axis=-1)
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                               axis=-1)
    pairs = xf.reshape(x.shape[:-1] + (x.shape[-1] // 2, 2))
    even, odd = pairs[..., 0], pairs[..., 1]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def causal_attention(q, k, v, scale, window=None):
    """q (b, t, H, dq), k (b, t, Hkv, dq), v (b, t, Hkv, dv) -> (b, t,
    H * dv), causal; query head ``h`` reads K/V head ``h // (H //
    Hkv)`` and K and V are never expanded to ``H`` heads.  With
    ``window`` key ``j`` is visible to query ``i`` iff ``j <= i`` and
    ``i - j < window``.

    ``Q_BLOCK`` query rows at a time against the keys ``K_BLOCK`` at a
    time, with a running maximum and sum (the flash recurrence at a
    coarse grain, in plain XLA): a query block visits only the key
    blocks at or before it -- with a window only those that reach into
    it, so a long prompt costs a window layer ``t * window`` pairs and
    not ``t^2 / 2`` -- and no reduction runs over more than ``K_BLOCK``
    keys (over 8,192 in one the TPU compiler's softmax fusion took 47 ms
    a block where 4,096 took 1.2; my chip runs, PR 28)."""
    import jax
    import jax.numpy as jnp
    b, t, heads, _ = q.shape
    kv_heads, v_dim = k.shape[2], v.shape[-1]
    group = heads // kv_heads
    powers = (1024, 512, 256, 128, 64, 32, 16, 8, 4, 2, 1)
    qb = next(n for n in powers if n <= Q_BLOCK and t % n == 0)
    kb = next(n for n in powers if n <= K_BLOCK and t % n == 0)
    ks = k.reshape(b, t // kb, kb, kv_heads, -1)
    vs = v.reshape(b, t // kb, kb, kv_heads, -1)

    def rows(args):
        qblk, start = args                      # (b, qb, Hkv, group, .)
        qpos = start + jnp.arange(qb, dtype=jnp.int32)

        def keys(j, carry):
            m, l, acc = carry
            kj = jax.lax.dynamic_index_in_dim(ks, j, 1, keepdims=False)
            vj = jax.lax.dynamic_index_in_dim(vs, j, 1, keepdims=False)
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qblk, kj,
                           preferred_element_type=jnp.float32) * scale
            kpos = j * kb + jnp.arange(kb, dtype=jnp.int32)
            seen = kpos[None, :] <= qpos[:, None]
            if window is not None:
                seen = seen & (qpos[:, None] - kpos[None, :] < window)
            s = jnp.where(seen, s, -1e30)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l = alpha * l + jnp.sum(p, axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p.astype(v.dtype), vj,
                preferred_element_type=jnp.float32)
            return m_new, l, acc

        first = 0 if window is None \
            else jnp.maximum(start - (window - 1), 0) // kb
        m, l, acc = jax.lax.fori_loop(
            first, (start + qb - 1) // kb + 1, keys,
            (jnp.full((b, kv_heads, group, qb), -1e30, jnp.float32),
             jnp.zeros((b, kv_heads, group, qb), jnp.float32),
             jnp.zeros((b, kv_heads, group, qb, v_dim), jnp.float32)))
        out = (acc / l[..., None]).astype(v.dtype)  # (b, Hkv, g, qb, dv)
        return out.transpose(0, 3, 1, 2, 4).reshape(b, qb, heads, v_dim)

    qs = q.reshape(b, t // qb, qb, kv_heads, group, -1).swapaxes(0, 1)
    starts = jnp.arange(0, t, qb, dtype=jnp.int32)
    out = jax.lax.map(rows, (qs, starts))           # (n, b, qb, H, dv)
    return out.swapaxes(0, 1).reshape(b, t, heads * v_dim)


def new_moe_stats():
    """The running counts of a program with expert layers, at zero."""
    import jax.numpy as jnp
    zero = jnp.zeros((), jnp.int32)
    return {"moe_assignments": zero, "moe_assignments_held": zero,
            "moe_expert_tokens_max": zero}


def routed_ffn(layer, x, norm_w, eps, router, router_bias, experts, top_k,
               live, stats, first_expert=0, scoring="sigmoid", scale=1.0,
               normalize=True, decode_step=False, chunk_rows=2048):
    """The routed part of an expert layer on ``x`` (tokens, d): ``(h,
    routed (tokens, d) float32, stats, chosen)`` with ``h =
    rms_norm(x, norm_w, eps)`` the layer's normed input, which a shared
    expert reads too.  ``router`` (d, experts) scores ALL experts
    (``parallel.moe.route_top_k``: ``scoring``, ``router_bias`` or None,
    ``normalize``, ``scale``); ``experts`` = (gate, up, down), the
    stacked weights of the experts held here from ``first_expert`` on
    (``parallel.moe.routed_experts``: sorted, grouped matmul
    ``chunk_rows`` sorted rows at a time, nothing dropped; it is told
    the router's width, from which it sees whether this layer holds
    every expert; ``decode_step``: the tokens are one decode step's, a
    slot each, which allows it the route of a step that keeps every
    expert busy).
    ``live`` (tokens,) bool keeps padding out of every count; ``stats``
    the running counts, returned updated; ``chosen`` (tokens, top_k) the
    experts the router chose.  ``layer`` prefixes the scopes:
    ``<layer>router``, ``<layer>experts``."""
    import jax
    import jax.numpy as jnp
    from ...parallel.moe import route_top_k, routed_experts
    with jax.named_scope(layer + "router"):
        h = rms_norm(x, norm_w, eps)
        chosen, weights = route_top_k(h, router, router_bias, top_k, scale,
                                      scoring=scoring, normalize=normalize)
    with jax.named_scope(layer + "experts"):
        routed, counts = routed_experts(h, chosen, weights, *experts,
                                        first_expert, live=live,
                                        chunk_rows=chunk_rows,
                                        num_experts=router.shape[1],
                                        decode_step=decode_step)
    n_live = jnp.sum(live.astype(jnp.int32))
    stats = dict(
        stats,
        moe_assignments=stats["moe_assignments"] + n_live * top_k,
        moe_assignments_held=stats["moe_assignments_held"]
        + jnp.sum(counts),
        moe_expert_tokens_max=jnp.maximum(stats["moe_expert_tokens_max"],
                                          jnp.max(counts)))
    return h, routed, stats, chosen


def draw_params(shapes, seed, dtype):
    """Flat name->array dict drawn from ``seed`` for ``shapes`` =
    ``{name: (shape, kind)}``; kind "norm" (1 + 0.1 normal), ``("norm",
    gain)`` (a norm whose weights lie about ``gain``: ``gain * (1 + 0.1
    normal)``), "bias" (0.1 normal, float32), ``("log_uniform", lo,
    hi)`` (``log(a)``, ``a`` uniform on ``[lo, hi)``, float32),
    ``("softplus_inv", lo, hi)`` (``softplus^-1(a)``, ``log(a)`` uniform
    on ``[log lo, log hi)``, float32) or the fan-in of a matmul weight
    (normal with standard deviation ``fan_in^-0.5``).  One jitted draw
    per tensor, so that the float32 draw of the largest tensor is the
    most the initialiser adds to the weights."""
    import functools
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnums=(1, 2))
    def draw(key, shape, kind):
        if isinstance(kind, tuple) and kind[0] == "log_uniform":
            return jnp.log(jax.random.uniform(key, shape, jnp.float32,
                                              kind[1], kind[2]))
        if isinstance(kind, tuple) and kind[0] == "softplus_inv":
            a = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                           math.log(kind[1]),
                                           math.log(kind[2])))
            return a + jnp.log(-jnp.expm1(-a))
        z = jax.random.normal(key, shape, jnp.float32)
        if kind == "norm":
            return (1.0 + 0.1 * z).astype(dtype)
        if isinstance(kind, tuple):
            return (kind[1] * (1.0 + 0.1 * z)).astype(dtype)
        if kind == "bias":
            return 0.1 * z
        return (z * float(kind) ** -0.5).astype(dtype)

    key = jax.random.PRNGKey(jnp.uint32(int(seed) % (2 ** 32)))
    return {name: draw(jax.random.fold_in(key, n), shape, kind)
            for n, (name, (shape, kind))
            in enumerate(sorted(shapes.items()))}
