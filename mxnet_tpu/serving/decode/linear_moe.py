"""A hybrid decoder of linear attention (Kimi Delta Attention, KDA) and
latent attention without positions (NoPE MLA) over routed experts, in
pure-function form for the generative engine: the block of
Kimi-Linear-48B-A3B, as ONE chip of an expert-parallel deployment runs
it.

Same duck type as :class:`~.latent_moe.LatentMoEDecoder`, whose latent
attention, feed-forward (dense SwiGLU or routed experts with a shared
expert), embedding and head it takes over as they are (it is a subclass):
``init_params``, ``full_logits`` (``with_routing``), ``prefill_cache``,
``decode_logits``, ``cache_rows``, and beside them the declaration of a
second kind of cache content, ``cache_layers()`` (``"state"`` for a KDA
layer, ``"full"`` for an MLA one) and ``cache_states()``.  A KDA layer
keeps no row a token: it keeps, a sequence, a float32 state of ``heads x
key x value`` and the short convolution's last ``conv - 1`` inputs, both
of a fixed size whatever the context, in the cache's state rows
(``kvcache.py``, "State layers"); only the MLA layers keep latent rows
through a block table.

A KDA layer on the normed input ``u`` (H heads of width d, ``C = H d``;
``linear_attn_config``):

- ``q, k, v = SiLU(CausalDepthwiseConv_K(u W_q | u W_k | u W_v))``, each
  ``hidden -> C``, the convolution without bias over the last ``K``
  inputs of each channel (zeros before the prompt's first token);
- ``q = q / ||q|| * d^-1/2`` and ``k = k / ||k||`` per head;
- ``beta = sigmoid(u W_b)``, one a head; ``g = -exp(A_log[h]) *
  softplus(u W_fa W_fb + dt_bias)``, a log-decay a key channel;
- per head, with ``S`` (key x value) float32, zero at the prompt's
  start: ``S' = Diag(exp(g_t)) S``, ``S = S' + k_t (beta_t (v_t - S'^T
  k_t))^T``, ``o_t = S^T q_t``;
- ``out = W_o(RMSNorm_head(o) * w_norm * sigmoid(u W_ga W_gb))``.

Prefill runs the recurrence in its **chunked form**
(:func:`chunked_delta_rule`), decode one step of it through the
``kda_decode`` kernel-registry entry, which turns each live slot's state
in place (``ops/pallas/kda_decode.py``).  The state is STORED transposed,
``(heads, value, key)``, the layout the kernel works in.

Weights, the cache's latent rows and the convolution's inputs are
bfloat16 (``dtype``); the state is float32 (``STATE_DTYPE``);
q, k, v, the gates and everything of the recurrence are float32; every
matmul accumulates in float32.

Beside the token the programs return ``scan_tokens`` (a prefill: the
prompt's true tokens x KDA layers) or ``state_rows`` (a decode step: the
live slots x KDA layers) and ``latent_grid_steps`` (the latent kernel's
grid steps x MLA layers), and the expert layers' three counts.
"""
from __future__ import annotations

from ...base import MXNetError
from .kvcache import LANE_TILE, lanes_for
from .latent_moe import LatentMoEDecoder

__all__ = ["LinearLatentMoEDecoder", "chunked_delta_rule"]

# the chunk of the prefill's scan
CHUNK = 64
# a KDA layer's own scopes: h<i>/linear_attention/<part>
SCOPE = "linear_attention"
# l2 norm of q and k: x / sqrt(sum(x^2) + eps)
L2_EPS = 1e-6
# what a KDA layer's state is stored in, between the steps of a sequence
STATE_DTYPE = "float32"


def chunked_delta_rule(q, k, v, g, beta, state, chunk=CHUNK):
    """The gated delta rule over a sequence, ``chunk`` tokens at a time.

    q, k, g (b, t, H, dk), v (b, t, H, dv), beta (b, t, H), all float32;
    state (b, H, dk, dv) float32, the state before the first token.
    Returns (o (b, t, H, dv), the state after the last token).  A token
    with ``beta = 0`` and ``g = 0`` leaves the state as it is (padding).

    Within a chunk, with ``b_r = sum_{j<=r} g_j`` (per key channel) and
    ``S_0`` the chunk's first state, the delta updates ``u_j`` solve the
    unit lower-triangular ``(I + Diag(beta) A) U = Diag(beta)(V - (K *
    e^b) S_0)``, ``A_rj = sum_c k_rc k_jc e^(b_rc - b_jc)`` for ``j <
    r``; then ``o_r = (q_r * e^(b_r)) S_0 + sum_{j<=r} (sum_c q_rc k_jc
    e^(b_rc - b_jc)) u_j`` and ``S_C = Diag(e^(b_C)) S_0 + sum_j
    Diag(e^(b_C - b_j)) k_j u_j^T``.  Every decay is formed as the
    exponent of a difference that is at most 0 (``b_r - b_j`` for ``j <=
    r``, ``b_C - b_j``, ``b_r``), never as ``e^(-b)``, which overflows
    float32 under strong decay.  Elementwise float32 and matmuls at
    precision ``highest``."""
    import jax
    import jax.numpy as jnp
    hi = jax.lax.Precision.HIGHEST
    b, t, heads, dk = q.shape
    n = -(-t // chunk)
    pad = n * chunk - t

    def split(a):
        a = jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
        a = a.reshape((b, n, chunk, heads) + a.shape[3:])
        return jnp.moveaxis(jnp.moveaxis(a, 1, 0), 3, 2)   # (n, b, H, C, .)

    row = jnp.arange(chunk)
    incl = row[:, None] >= row[None, :]
    strict = (row[:, None] > row[None, :]).astype(jnp.float32)

    def one_chunk(s0, xs):
        qc, kc, vc, gc, bc = xs         # (b, H, C, .), bc (b, H, C)
        cum = jnp.cumsum(gc, axis=-2)
        diff = cum[..., :, None, :] - cum[..., None, :, :]  # b_r - b_j
        decay = jnp.exp(jnp.where(incl[..., None], diff, -jnp.inf))
        a = jnp.sum(kc[..., :, None, :] * kc[..., None, :, :] * decay,
                    axis=-1) * strict
        p = jnp.sum(qc[..., :, None, :] * kc[..., None, :, :] * decay,
                    axis=-1)
        rhs = bc[..., None] * (vc - jnp.einsum(
            "bhrc,bhcv->bhrv", kc * jnp.exp(cum), s0, precision=hi))
        lower = jnp.eye(chunk, dtype=jnp.float32) + bc[..., None] * a
        u = jax.lax.linalg.triangular_solve(
            lower, rhs, left_side=True, lower=True, unit_diagonal=True)
        o = jnp.einsum("bhrc,bhcv->bhrv", qc * jnp.exp(cum), s0,
                       precision=hi) \
            + jnp.einsum("bhrj,bhjv->bhrv", p, u, precision=hi)
        last = cum[..., -1:, :]
        s1 = jnp.exp(last)[..., 0, :, None] * s0 + jnp.einsum(
            "bhjc,bhjv->bhcv", kc * jnp.exp(last - cum), u, precision=hi)
        return s1, o

    state, o = jax.lax.scan(
        one_chunk, state,
        (split(q), split(k), split(v), split(g), split(beta[..., None])[..., 0]))
    o = jnp.moveaxis(o, 0, 1)                   # (b, n, H, C, dv)
    o = jnp.moveaxis(o, 3, 2).reshape(b, n * chunk, heads, -1)
    return o[:, :t], state


class LinearLatentMoEDecoder(LatentMoEDecoder):
    """Decoder-only spec with KDA and NoPE latent-attention layers over
    routed experts: geometry + pure functions; parameters live OUTSIDE
    the object, as with ``TinyGPT``.

    The constructor takes the published config's keys (the latent
    attention's under their DeepSeek-V3 names, ``q_lora_rank`` None),
    ``linear_attn_config`` as published (``kda_layers`` and
    ``full_attn_layers`` 1-indexed over this chip's layers,
    ``num_heads``, ``head_dim``, ``short_conv_kernel_size``), the share
    this chip holds (``first_expert``, ``n_held``, ``vocab_size``,
    ``num_hidden_layers``) and ``max_seq``."""

    def __init__(self, vocab_size, hidden_size, num_hidden_layers,
                 num_attention_heads, kv_lora_rank, qk_nope_head_dim,
                 qk_rope_head_dim, v_head_dim, intermediate_size,
                 moe_intermediate_size, n_routed_experts,
                 num_experts_per_tok, n_shared_experts,
                 first_k_dense_replace, routed_scaling_factor,
                 linear_attn_config, q_lora_rank=None, rms_norm_eps=1e-5,
                 first_expert=0, n_held=None, max_seq=4096,
                 dtype="bfloat16", chunk=CHUNK):
        super().__init__(
            vocab_size, hidden_size, num_hidden_layers, num_attention_heads,
            q_lora_rank, kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim,
            v_head_dim, intermediate_size, moe_intermediate_size,
            n_routed_experts, num_experts_per_tok, n_shared_experts,
            first_k_dense_replace, routed_scaling_factor, rope_theta=None,
            rms_norm_eps=rms_norm_eps, first_expert=first_expert,
            n_held=n_held, max_seq=max_seq, dtype=dtype, mla_use_nope=True)
        lin = linear_attn_config
        kda = {int(n) - 1 for n in lin["kda_layers"]}
        full = {int(n) - 1 for n in lin["full_attn_layers"]}
        if kda & full or kda | full != set(range(self.num_layers)):
            raise MXNetError(
                "linear_attn_config must name every one of the %d layers "
                "once, as a KDA or a full-attention layer: kda_layers %r, "
                "full_attn_layers %r" % (self.num_layers, lin["kda_layers"],
                                         lin["full_attn_layers"]))
        self.kda_layers = tuple(sorted(kda))
        self.lin_heads = int(lin["num_heads"])
        self.lin_dim = int(lin["head_dim"])
        self.conv = int(lin["short_conv_kernel_size"])
        self.chunk = int(chunk)
        # a layer's index among the layers of its kind: its arrays in the
        # cache's lists
        self._slot = {}
        for kind in ("state", "full"):
            for n, i in enumerate(i for i in range(self.num_layers)
                                  if self.layer_kind(i) == kind):
                self._slot[i] = n

    # -- the cache's declaration ----------------------------------------
    def layer_kind(self, i):
        return "state" if i in self.kda_layers else "full"

    def cache_layers(self):
        """The kind of each layer: a KDA layer keeps a state a sequence,
        an MLA layer latent rows through a block table."""
        return [self.layer_kind(i) for i in range(self.num_layers)]

    def cache_states(self):
        """What ONE sequence keeps in ONE KDA layer: the state, stored
        transposed (heads, value, key), and the convolution's last
        ``conv - 1`` inputs of the q, k and v channels, flat, in rows of
        128 lanes (zeros past the end): a sequence's inputs then lie in
        whole tiles of their own and a step's update is one scatter of
        contiguous rows, where one flat row a sequence strides over every
        tile of the array (a loop of single-row writes on the TPU: 2.8 ms
        a step at 128 slots)."""
        rows = lanes_for(self._conv_width()) // LANE_TILE
        return {"kda_state": ((self.lin_heads, self.lin_dim, self.lin_dim),
                              STATE_DTYPE),
                "kda_conv": ((rows, LANE_TILE), self.dtype)}

    def _conv_width(self):
        return (self.conv - 1) * 3 * self.lin_heads * self.lin_dim

    def _conv_rows(self, flat):
        """(..., (conv - 1) * 3C) -> the stored (..., rows, 128)."""
        import jax.numpy as jnp
        rows, lanes = self.cache_states()["kda_conv"][0]
        pad = rows * lanes - flat.shape[-1]
        flat = jnp.pad(flat, [(0, 0)] * (flat.ndim - 1) + [(0, pad)])
        return flat.reshape(flat.shape[:-1] + (rows, lanes))

    # -- params ---------------------------------------------------------
    def param_shapes(self):
        """The latent-attention decoder's, with each KDA layer's
        attention in place of its latent attention.  Kinds as
        :func:`~.blocks.draw_params` takes them: ``A_log`` is ``log(A)``
        with ``A`` uniform on [1, 16) and ``dt_bias`` the inverse
        softplus of a rate log-uniform on [0.001, 0.1) (the published
        modelling code's initialiser), float32 both."""
        out = super().param_shapes()
        d, h, w = self.units, self.lin_heads, self.lin_dim
        c = h * w
        for i in self.kda_layers:
            pre = "h%d_" % i
            for name in [n for n in out if n.startswith(pre)
                         and n[len(pre):] in self._mla_names()]:
                del out[name]
            out.update({
                pre + "attn_norm": ((d,), "norm"),
                pre + "wq": ((d, c), d), pre + "wk": ((d, c), d),
                pre + "wv": ((d, c), d),
                pre + "conv_w": ((self.conv, 3 * c), self.conv),
                pre + "f_a": ((d, w), d), pre + "f_b": ((w, c), w),
                pre + "dt_bias": ((c,), ("softplus_inv", 0.001, 0.1)),
                pre + "A_log": ((h,), ("log_uniform", 1.0, 16.0)),
                pre + "b_proj": ((d, h), d),
                pre + "g_a": ((d, w), d), pre + "g_b": ((w, c), w),
                pre + "o_norm": ((w,), "norm"),
                pre + "wo": ((c, d), c)})
        return out

    @staticmethod
    def _mla_names():
        return {"attn_norm", "wq", "wqa", "q_norm", "wqb", "wkva",
                "kv_norm", "wkvb", "wo"}

    # -- a KDA layer ------------------------------------------------------
    def _kda_inputs(self, p, pre, layer, x):
        """x (..., d) -> (u, the convolution's inputs (..., 3C) in the
        activations' dtype)."""
        import jax
        import jax.numpy as jnp
        with jax.named_scope(layer + SCOPE + "/proj"):
            u = self._rms(x, p[pre + "attn_norm"])
            return u, jnp.concatenate(
                [self._dot(u, p[pre + n]).astype(x.dtype)
                 for n in ("wq", "wk", "wv")], axis=-1)

    def _kda_qkv(self, conv_out):
        """SiLU'd convolution outputs (..., 3C) float32 -> q, k, v (...,
        H, d), q and k l2-normed a head, q scaled by d^-1/2."""
        import jax
        import jax.numpy as jnp
        shape = conv_out.shape[:-1] + (3 * self.lin_heads, self.lin_dim)
        q, k, v = jnp.split(conv_out.reshape(shape), 3, axis=-2)

        def unit(a):
            return a * jax.lax.rsqrt(
                jnp.sum(a * a, axis=-1, keepdims=True) + L2_EPS)
        return unit(q) * self.lin_dim ** -0.5, unit(k), v

    def _kda_gates(self, p, pre, layer, u):
        """u (..., d) -> beta (..., H), the log decay g (..., H, d) and
        the output gate (..., H, d), float32."""
        import jax
        import jax.numpy as jnp
        heads = u.shape[:-1] + (self.lin_heads, self.lin_dim)
        with jax.named_scope(layer + SCOPE + "/gate"):
            beta = jax.nn.sigmoid(self._dot(u, p[pre + "b_proj"]))
            f = self._dot(self._dot(u, p[pre + "f_a"]).astype(u.dtype),
                          p[pre + "f_b"]) + p[pre + "dt_bias"]
            g = -jnp.exp(p[pre + "A_log"])[:, None] \
                * jax.nn.softplus(f).reshape(heads)
            gate = jax.nn.sigmoid(self._dot(
                self._dot(u, p[pre + "g_a"]).astype(u.dtype),
                p[pre + "g_b"])).reshape(heads)
        return beta, g, gate

    def _kda_out(self, p, pre, layer, x, o, gate):
        """o (..., H, d) float32 -> x + W_o(norm(o) * gate)."""
        import jax
        with jax.named_scope(layer + SCOPE + "/norm"):
            o = (self._rms(o, p[pre + "o_norm"]) * gate).astype(x.dtype)
        with jax.named_scope(layer + SCOPE + "/proj"):
            return x + self._dot(o.reshape(x.shape[:-1] + (-1,)),
                                 p[pre + "wo"]).astype(x.dtype)

    def _kda_prefill(self, p, i, x, live):
        """Layer ``i`` over whole prompts, the chunked form: x (b, t, d),
        live (b, t) -> (x + Attn(norm(x)), (the state after the last
        live token (b, H, value, key), the convolution's last inputs (b,
        (conv - 1) * 3C)))."""
        import jax
        import jax.numpy as jnp
        pre, layer = "h%d_" % i, "h%d/" % i
        b, t = x.shape[:2]
        u, inputs = self._kda_inputs(p, pre, layer, x)
        with jax.named_scope(layer + SCOPE + "/conv"):
            # zeros before the first token; the taps in time order
            padded = jnp.pad(inputs, [(0, 0), (self.conv - 1, 0), (0, 0)])
            w = p[pre + "conv_w"].astype(jnp.float32)
            out = sum(padded[:, n:n + t].astype(jnp.float32) * w[n]
                      for n in range(self.conv))
            q, k, v = self._kda_qkv(jax.nn.silu(out))
            # the last conv - 1 inputs up to the last live token
            last = jnp.sum(live.astype(jnp.int32), axis=1) - 1
            tail = jax.vmap(lambda a, at: jax.lax.dynamic_slice_in_dim(
                a, at + 1, self.conv - 1, axis=0))(padded, last)
        beta, g, gate = self._kda_gates(p, pre, layer, u)
        with jax.named_scope(layer + SCOPE + "/recurrence"):
            # a padded position takes nothing in and lets nothing decay
            beta = jnp.where(live[..., None], beta, 0.0)
            g = jnp.where(live[..., None, None], g, 0.0)
            zero = jnp.zeros((b, self.lin_heads, self.lin_dim,
                              self.lin_dim), jnp.float32)
            o, state = chunked_delta_rule(q, k, v, g, beta, zero,
                                          self.chunk)
            state = jnp.swapaxes(state, -1, -2)         # (b, H, dv, dk)
        x = self._kda_out(p, pre, layer, x, o, gate)
        return x, (state, tail.reshape(b, -1))

    def _kda_decode(self, p, i, x, state, conv, rows):
        """Layer ``i`` in a decode step: x (s, d); this layer's state
        array and convolution array; rows (s,) the slots' state rows ->
        (x + Attn(norm(x)), state', conv')."""
        import jax
        import jax.numpy as jnp
        from ...kernels.kda_decode import kda_decode
        pre, layer = "h%d_" % i, "h%d/" % i
        s = x.shape[0]
        u, inputs = self._kda_inputs(p, pre, layer, x)
        with jax.named_scope(layer + SCOPE + "/conv"):
            window = jnp.concatenate(
                [jnp.take(conv, rows, axis=0).reshape(s, -1)[
                    :, :self._conv_width()].reshape(s, self.conv - 1, -1),
                 inputs[:, None]], axis=1)
            conv = conv.at[rows].set(
                self._conv_rows(window[:, 1:].reshape(s, -1)))
            out = jnp.sum(window.astype(jnp.float32)
                          * p[pre + "conv_w"].astype(jnp.float32), axis=1)
            q, k, v = self._kda_qkv(jax.nn.silu(out))
        beta, g, gate = self._kda_gates(p, pre, layer, u)
        with jax.named_scope(layer + SCOPE + "/recurrence"):
            o, state = kda_decode(q, k, beta[..., None] * k, g, v, state,
                                  rows)
        return self._kda_out(p, pre, layer, x, o, gate), state, conv

    # -- full causal forward (reference + prefill) ----------------------
    def _forward(self, params, tokens, live):
        """tokens (b, t) -> (hidden (b, t, d) before the final norm, each
        layer's cache content (an MLA layer's latent rows (b, t, kv_rank
        + rope), a KDA layer's (state, convolution tail)), stats, the
        experts every expert layer's router chose (b, t, top_k))."""
        import jax.numpy as jnp
        b, t = tokens.shape
        positions = self._positions(tokens)
        x = self._embed(params, tokens)
        stats, caches, routing = self._new_stats(), [], []
        for i in range(self.num_layers):
            if self.layer_kind(i) == "state":
                x, cache = self._kda_prefill(params, i, x, live)
            else:
                x, cache = self._mla_prefill(params, i, x, positions)
            caches.append(cache)
            flat, stats, chosen = self._ffn(
                params, i, x.reshape(b * t, -1), live.reshape(b * t), stats)
            x = flat.reshape(b, t, -1)
            if chosen is not None:
                routing.append(chosen.reshape(b, t, self.top_k))
        stats = dict(stats, scan_tokens=jnp.sum(live.astype(jnp.int32))
                     * len(self.kda_layers))
        return x, caches, stats, tuple(routing)

    def prefill_kv(self, params, tokens, last):
        raise MXNetError("a model with state layers writes its cache "
                         "itself: prefill_cache")

    def prefill_cache(self, params, slabs, tokens, last, table,
                      block_size, every_position=False):
        """tokens (1, t), ``last`` the index of the prompt's last token;
        ``table`` ``{"full": (width,), "state": (1,)}`` -> (its logits
        (vocab,), slabs', stats): each MLA layer's latent rows of the
        prompt written through the block table, each KDA layer's final
        state and convolution tail into the sequence's state row, whole
        (what an earlier sequence left in that row is gone).

        ``every_position=True`` (what a check of the served path judges;
        no engine program asks for it) returns the logits of every
        position (1, t, vocab) and, last, the experts each expert layer
        chose, as ``full_logits(..., with_routing=True)`` does; those
        past ``last`` are of padding."""
        import jax
        import jax.numpy as jnp
        from .kvcache import write_prompt
        t = tokens.shape[1]
        live = (jnp.arange(t, dtype=jnp.int32) <= last)[None]
        x, caches, stats, routing = self._forward(params, tokens, live)
        logits = self._head(params, x if every_position
                            else jnp.take(x[0], last, axis=0))
        out = {name: list(arrays) for name, arrays in slabs.items()}
        row = table["state"][0]
        with jax.named_scope("mx.kv_scatter"):
            for i, cache in enumerate(caches):
                j = self._slot[i]
                if self.layer_kind(i) == "full":
                    out["latent"][j] = write_prompt(
                        out["latent"][j], cache[0], table["full"], last + 1,
                        block_size)
                    continue
                state, tail = cache
                out["kda_state"][j] = out["kda_state"][j].at[row].set(
                    state[0].astype(out["kda_state"][j].dtype))
                out["kda_conv"][j] = out["kda_conv"][j].at[row].set(
                    self._conv_rows(tail[0]).astype(
                        out["kda_conv"][j].dtype))
        out = {name: tuple(a) for name, a in out.items()}
        return (logits, out, stats, routing) if every_position \
            else (logits, out, stats)

    # -- decode step over the cache -------------------------------------
    def decode_logits(self, params, slabs, token_ids, positions,
                      block_tables, block_size, live=None,
                      with_routing=False):
        """One decode step for a slot batch.  ``slabs`` ``{"latent": (an
        MLA layer's slab, ...), "kda_state": (a KDA layer's state array,
        ...), "kda_conv": (...)}``; ``block_tables`` ``{"full": (s,
        max_blocks), "state": (s, 1)}`` int32 (a padded slot's state row
        is the scratch row).  Returns (next_token (s,), logits (s,
        vocab) float32, slabs', stats), and with ``with_routing`` last
        the experts each expert layer chose, one (s, top_k) array a
        layer."""
        import jax
        import jax.numpy as jnp
        from ...ops.pallas.mla_paged_attention import grid_steps
        tables = block_tables["full"]
        rows = block_tables["state"][:, 0]
        out = {name: list(arrays) for name, arrays in slabs.items()}
        x, blk, off, ctx, live = self._decode_prologue(
            params, token_ids, positions, tables, block_size, live)
        stats, routing = self._new_stats(), []
        for i in range(self.num_layers):
            j = self._slot[i]
            if self.layer_kind(i) == "state":
                x, out["kda_state"][j], out["kda_conv"][j] = \
                    self._kda_decode(params, i, x, out["kda_state"][j],
                                     out["kda_conv"][j], rows)
            else:
                x, out["latent"][j] = self._mla_decode(
                    params, i, x, positions, out["latent"][j], blk, off,
                    tables, ctx)
            x, stats, chosen = self._ffn(params, i, x, live, stats)
            if chosen is not None:
                routing.append(chosen)
        logits = self._head(params, x)
        with jax.named_scope("mx.lm_head"):
            next_token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        stats = dict(stats, state_rows=jnp.sum(live.astype(jnp.int32))
                     * len(self.kda_layers),
                     latent_grid_steps=grid_steps(tables, ctx, block_size)
                     * (self.num_layers - len(self.kda_layers)))
        out = (next_token, logits,
               {name: tuple(a) for name, a in out.items()}, stats)
        return out + (tuple(routing),) if with_routing else out

    def __repr__(self):
        return ("LinearLatentMoEDecoder(vocab=%d, units=%d, layers=%d "
                "(KDA %s), experts %d..%d of %d, max_seq=%d)" % (
                    self.vocab_size, self.units, self.num_layers,
                    list(self.kda_layers), self.first_expert,
                    self.first_expert + self.n_held, self.num_experts,
                    self.max_seq))

