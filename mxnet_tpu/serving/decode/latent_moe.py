"""A DeepSeek-V3-style decoder (latent attention, routed experts) in
pure-function form for the generative engine: the block of
Kimi-K2-Instruct and its relatives, as ONE chip of an expert-parallel
deployment runs it.

Same duck type as :class:`~.model.TinyGPT` -- ``init_params``,
``full_logits``, ``prefill_kv``, ``decode_logits``, ``max_seq``,
``num_layers``, ``cache_rows`` (``full_logits`` can also say which
experts each token was sent to) -- so ``ModelRegistry.register_generative``
and :class:`~.engine.DecodeEngine` serve it with no side entry.  What
differs is the block:

- **RMS norm**, ``w * x / sqrt(mean(x^2) + eps)``, statistics in float32.
- **Latent attention (MLA)**.  Queries come through a low-rank pair
  (``wqa``, norm, ``wqb``); keys and values through ONE compressed row a
  token, ``c_kv = norm(x wkva[:, :rank])``, beside ONE rotated key slice
  that all heads share, ``k_rope = rope(x wkva[:, rank:])``.  The paged
  cache keeps exactly that: ``cache_rows()`` declares a single row
  ``latent`` of ``kv_lora_rank + qk_rope_head_dim`` values where a
  GPT-style block keeps per-head K and V.  Two forms of attention read
  it, over one set of weights:

  * **prefill** expands ``[k_nope | v] = c_kv wkvb`` per head and runs
    causal attention in blocks (``_Q_BLOCK`` query rows by ``_K_BLOCK``
    keys of scores at a time, so a prompt of thousands of tokens fits);
  * **decode** absorbs the K up-projection into the query (``q' =
    q_nope W_uk^T``) and the V up-projection into the output (``o =
    (p c_kv) W_uv``) and attends over the latent rows themselves through
    the ``mla_paged_attention`` kernel-registry entry: every live token
    is read once a layer for all heads.

- **Rotary positions** on the ``qk_rope_head_dim`` slice only, adjacent
  pairs ``(2j, 2j+1)`` rotated, YaRN-scaled frequencies
  (:func:`yarn_inv_freq`); the softmax scale carries YaRN's ``mscale``
  squared (:func:`attention_scale`).  With ``mla_use_nope`` the slice
  is not rotated at all (Kimi Linear's latent attention: no positional
  encoding anywhere), and with ``q_lora_rank`` None the query is ONE
  projection ``wq`` of the normed input, without the low-rank pair.
- **Feed-forward**: the first ``first_k_dense_replace`` layers a dense
  gated-SiLU MLP; every later one an **expert layer**: a router over ALL
  ``n_routed_experts`` (sigmoid scores, a selection bias, top-k,
  normalised, scaled), the routed experts THIS chip holds
  (``first_expert .. first_expert + n_held``) through
  ``parallel.moe.routed_experts`` (sorted, grouped matmul, nothing
  dropped), and the shared expert, which every chip computes.  What the
  experts held elsewhere would add is left out: the partial sum is what
  goes on to the next layer, as on one chip of the deployment before
  its exchange.
- **Untied head** over the ``vocab_size`` rows held here.

Weights and the cache are bfloat16 (``dtype``); every matmul accumulates
in float32; norm statistics, the router (matmul at precision
``highest``, sigmoid, top-k), the softmax and the rotary tables are
float32.

The prefill and decode programs return, beside the token, three counts
(``stats``): ``moe_assignments`` (live tokens x experts per token x
expert layers), ``moe_assignments_held`` (those that fell on experts
held here) and ``moe_expert_tokens_max`` (the largest count one held
expert of one layer was given); a decode step also ``latent_grid_steps``
(the grid steps of the latent kernel: its slots' live page groups x MLA
layers).  The engine fetches them with the token.
"""
from __future__ import annotations

import math

import numpy as np

from ...base import MXNetError
from . import blocks
from .blocks import yarn_inv_freq

__all__ = ["LatentMoEDecoder", "yarn_inv_freq", "attention_scale"]


def _yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def attention_scale(qk_head_dim, scaling=None):
    """The softmax scale: ``qk_head_dim^-0.5``, times YaRN's
    ``mscale(factor, mscale_all_dim)`` squared where the config scales
    all dimensions."""
    scale = float(qk_head_dim) ** -0.5
    if scaling and scaling.get("mscale_all_dim"):
        m = _yarn_mscale(float(scaling["factor"]),
                         float(scaling["mscale_all_dim"]))
        scale *= m * m
    return scale


class LatentMoEDecoder:
    """Decoder-only transformer spec with latent attention and routed
    experts: geometry + pure functions; parameters live OUTSIDE the
    object (a flat ``{name: array}`` dict), as with ``TinyGPT``.

    The constructor takes the published config's keys, and the share of
    the deployment this chip holds: ``first_expert`` and ``n_held`` of
    the ``n_routed_experts`` the router scores, ``vocab_size`` rows of
    the vocabulary, ``num_hidden_layers`` layers, and ``max_seq``, the
    longest context served."""

    def __init__(self, vocab_size, hidden_size, num_hidden_layers,
                 num_attention_heads, q_lora_rank, kv_lora_rank,
                 qk_nope_head_dim, qk_rope_head_dim, v_head_dim,
                 intermediate_size, moe_intermediate_size,
                 n_routed_experts, num_experts_per_tok, n_shared_experts,
                 first_k_dense_replace, routed_scaling_factor,
                 rope_theta, rope_scaling=None, rms_norm_eps=1e-6,
                 first_expert=0, n_held=None, max_seq=4096,
                 dtype="bfloat16", mla_use_nope=False):
        self.vocab_size = int(vocab_size)
        self.units = int(hidden_size)
        self.num_layers = int(num_hidden_layers)
        self.num_heads = int(num_attention_heads)
        self.q_rank = None if q_lora_rank is None else int(q_lora_rank)
        self.kv_rank = int(kv_lora_rank)
        self.nope = int(qk_nope_head_dim)
        self.rope = int(qk_rope_head_dim)
        self.v_dim = int(v_head_dim)
        self.ffn = int(intermediate_size)
        self.expert_ffn = int(moe_intermediate_size)
        self.num_experts = int(n_routed_experts)
        self.top_k = int(num_experts_per_tok)
        self.n_shared = int(n_shared_experts)
        self.dense_layers = int(first_k_dense_replace)
        self.routed_scale = float(routed_scaling_factor)
        self.eps = float(rms_norm_eps)
        self.first_expert = int(first_expert)
        self.n_held = int(self.num_experts if n_held is None else n_held)
        self.max_seq = int(max_seq)
        self.dtype = str(dtype)
        if self.rope % 2:
            raise MXNetError("rotary slice must be even, got %d"
                             % self.rope)
        if not 0 <= self.first_expert <= self.first_expert + self.n_held \
                <= self.num_experts or self.top_k > self.num_experts:
            raise MXNetError(
                "experts %d..%d held of %d, %d per token"
                % (self.first_expert, self.first_expert + self.n_held,
                   self.num_experts, self.top_k))
        self.scale = attention_scale(self.nope + self.rope, rope_scaling)
        # None: the shared key slice and its query slice are not rotated
        self.inv_freq = None if mla_use_nope else yarn_inv_freq(
            self.rope, rope_theta, rope_scaling).astype(np.float32)

    def cache_rows(self):
        """What one token keeps in one layer of the paged cache: the
        compressed K/V after its norm, then the rotated shared key."""
        return {"latent": (self.kv_rank + self.rope,)}

    def is_expert_layer(self, i):
        return i >= self.dense_layers

    # -- params ---------------------------------------------------------
    def param_shapes(self):
        """{name: (shape, kind)}; kind "norm" (about 1), "bias" (the
        router's selection bias, float32) or the fan-in of a matmul
        weight."""
        d, h = self.units, self.num_heads
        out = {"embed": ((self.vocab_size, d), 1),
               "norm_f": ((d,), "norm"),
               "head": ((d, self.vocab_size), d)}
        for i in range(self.num_layers):
            pre = "h%d_" % i
            q_width = h * (self.nope + self.rope)
            out.update({pre + "wq": ((d, q_width), d)}
                       if self.q_rank is None else {
                pre + "wqa": ((d, self.q_rank), d),
                pre + "q_norm": ((self.q_rank,), "norm"),
                pre + "wqb": ((self.q_rank, q_width), self.q_rank)})
            out.update({
                pre + "attn_norm": ((d,), "norm"),
                pre + "wkva": ((d, self.kv_rank + self.rope), d),
                pre + "kv_norm": ((self.kv_rank,), "norm"),
                pre + "wkvb": ((self.kv_rank,
                                h * (self.nope + self.v_dim)),
                               self.kv_rank),
                pre + "wo": ((h * self.v_dim, d), h * self.v_dim),
                pre + "ffn_norm": ((d,), "norm")})
            if not self.is_expert_layer(i):
                out.update({
                    pre + "w_gate": ((d, self.ffn), d),
                    pre + "w_up": ((d, self.ffn), d),
                    pre + "w_down": ((self.ffn, d), self.ffn)})
                continue
            f, fs = self.expert_ffn, self.expert_ffn * self.n_shared
            out.update({
                pre + "router": ((d, self.num_experts), d),
                pre + "router_bias": ((self.num_experts,), "bias"),
                pre + "shared_gate": ((d, fs), d),
                pre + "shared_up": ((d, fs), d),
                pre + "shared_down": ((fs, d), fs),
                pre + "experts_gate": ((self.n_held, d, f), d),
                pre + "experts_up": ((self.n_held, d, f), d),
                pre + "experts_down": ((self.n_held, f, d), f)})
        return out

    def init_params(self, seed=0):
        """Flat name->array dict drawn from ``seed``
        (:func:`~.blocks.draw_params`): matmul weights normal with
        standard deviation ``fan_in^-0.5``, norm weights 1 + 0.1 normal,
        the selection bias 0.1 normal (it is trained in a published
        model; here it only has to move some choices)."""
        return blocks.draw_params(self.param_shapes(), seed, self.dtype)

    # -- shared pieces --------------------------------------------------
    def _rms(self, x, w):
        return blocks.rms_norm(x, w, self.eps)

    _dot = staticmethod(blocks.dot)
    _swiglu = staticmethod(blocks.swiglu)

    def _rotate(self, x, positions):
        """Rotary embedding of ``x`` (..., t, [heads,] rope) at
        ``positions`` (..., t): adjacent pairs (2j, 2j+1) turn by
        ``position * inv_freq[j]``.  float32 in, float32 out; ``x``
        itself where the model has no positional encoding."""
        if self.inv_freq is None:
            return x
        return blocks.rotate(x, positions, self.inv_freq)

    def _q_latent(self, p, pre, x, positions):
        """x (..., t, d) -> q_nope (..., t, H, nope), q_rope (..., t, H,
        rope), both in the activations' dtype, q_rope rotated, and the
        normed input that the K/V side shares."""
        import jax.numpy as jnp
        h = self._rms(x, p[pre + "attn_norm"])
        if self.q_rank is None:
            q = self._dot(h, p[pre + "wq"])
        else:
            c_q = self._rms(self._dot(h, p[pre + "wqa"]).astype(x.dtype),
                            p[pre + "q_norm"])
            q = self._dot(c_q, p[pre + "wqb"])
        q = q.reshape(
            x.shape[:-1] + (self.num_heads, self.nope + self.rope))
        q_nope, q_rope = jnp.split(q, [self.nope], axis=-1)
        return (q_nope.astype(x.dtype),
                self._rotate(q_rope, positions).astype(x.dtype), h)

    def _kv_latent(self, p, pre, h, positions):
        """The normed input h (..., t, d) -> the token's cache row
        (..., t, kv_rank + rope): ``[norm(c_kv) | rope(k_rope)]``."""
        import jax.numpy as jnp
        kva = self._dot(h, p[pre + "wkva"])
        c_kv, k_rope = jnp.split(kva, [self.kv_rank], axis=-1)
        c_kv = self._rms(c_kv.astype(h.dtype), p[pre + "kv_norm"])
        k_rope = self._rotate(k_rope, positions).astype(h.dtype)
        return jnp.concatenate([c_kv, k_rope], axis=-1)

    def _up_projections(self, p, pre):
        """``wkvb`` per head: W_uk, W_uv, each (kv_rank, H, .)."""
        w = p[pre + "wkvb"].reshape(self.kv_rank, self.num_heads,
                                    self.nope + self.v_dim)
        return w[..., :self.nope], w[..., self.nope:]

    def _ffn(self, p, i, x, live, stats):
        """x (tokens, d) -> (x + FFN(norm(x)), stats, chosen): ``live``
        (tokens,) bool; ``stats`` the running counts, returned updated;
        ``chosen`` (tokens, top_k) the experts the router chose, None
        for a dense layer."""
        import jax
        pre, layer = "h%d_" % i, "h%d/" % i
        if not self.is_expert_layer(i):
            with jax.named_scope(layer + "mlp"):
                h = self._rms(x, p[pre + "ffn_norm"])
                return x + self._swiglu(
                    h, p[pre + "w_gate"], p[pre + "w_up"],
                    p[pre + "w_down"]).astype(x.dtype), stats, None
        h, routed, stats, chosen = blocks.routed_ffn(
            layer, x, p[pre + "ffn_norm"], self.eps, p[pre + "router"],
            p[pre + "router_bias"],
            (p[pre + "experts_gate"], p[pre + "experts_up"],
             p[pre + "experts_down"]), self.top_k, live, stats,
            first_expert=self.first_expert, scale=self.routed_scale)
        with jax.named_scope(layer + "shared_expert"):
            shared = self._swiglu(h, p[pre + "shared_gate"],
                                  p[pre + "shared_up"],
                                  p[pre + "shared_down"])
            x = x + (routed + shared).astype(x.dtype)
        return x, stats, chosen

    def _new_stats(self):
        if self.num_layers <= self.dense_layers:
            return {}
        return blocks.new_moe_stats()

    # -- full causal forward (reference + prefill) ----------------------
    def _mla_prefill(self, params, i, x, positions):
        """Layer ``i``'s latent attention over a whole prompt, the
        expanded form: x (b, t, d) -> (x + Attn(norm(x)), the layer's
        latent rows (b, t, kv_rank + rope))."""
        import jax
        import jax.numpy as jnp
        scope = jax.named_scope
        b, t = x.shape[:2]
        pre, layer = "h%d_" % i, "h%d/" % i
        with scope(layer + "q_latent"):
            q_nope, q_rope, h = self._q_latent(params, pre, x, positions)
        with scope(layer + "kv_latent"):
            row = self._kv_latent(params, pre, h, positions)
            c_kv, k_rope = jnp.split(row, [self.kv_rank], axis=-1)
            kv = self._dot(c_kv, params[pre + "wkvb"]).astype(
                x.dtype).reshape(b, t, self.num_heads,
                                 self.nope + self.v_dim)
            k_nope, v = jnp.split(kv, [self.nope], axis=-1)
            k = jnp.concatenate(
                [k_nope, jnp.broadcast_to(
                    k_rope[:, :, None, :],
                    (b, t, self.num_heads, self.rope))], axis=-1)
        with scope(layer + "attention"):
            att = blocks.causal_attention(
                jnp.concatenate([q_nope, q_rope], axis=-1), k, v,
                self.scale)
        with scope(layer + "proj"):
            x = x + self._dot(att, params[pre + "wo"]).astype(x.dtype)
        return x, row

    def _positions(self, tokens):
        import jax.numpy as jnp
        b, t = tokens.shape
        return jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))

    def _embed(self, params, tokens):
        import jax
        import jax.numpy as jnp
        with jax.named_scope("mx.embed"):
            return jnp.take(params["embed"], tokens, axis=0)

    def _forward(self, params, tokens, live):
        """tokens (b, t) -> (hidden (b, t, d) before the final norm, the
        latent rows of every layer (b, t, kv_rank + rope), stats, the
        experts every expert layer's router chose (b, t, top_k))."""
        b, t = tokens.shape
        positions = self._positions(tokens)
        x = self._embed(params, tokens)
        stats, latents, routing = self._new_stats(), [], []
        for i in range(self.num_layers):
            x, row = self._mla_prefill(params, i, x, positions)
            latents.append(row)
            flat, stats, chosen = self._ffn(
                params, i, x.reshape(b * t, -1), live.reshape(b * t), stats)
            x = flat.reshape(b, t, -1)
            if chosen is not None:
                routing.append(chosen.reshape(b, t, self.top_k))
        return x, latents, stats, tuple(routing)

    def _head(self, params, x):
        import jax
        with jax.named_scope("mx.lm_head"):
            return self._dot(self._rms(x, params["norm_f"]),
                             params["head"])

    def full_logits(self, params, tokens, with_routing=False):
        """Reference causal forward (the expanded attention form, no
        cache): tokens (b, t) int32 -> logits (b, t, vocab) float32.

        ``with_routing=True`` returns ``(logits, routing)``: beside the
        logits the experts each token was sent to, one (b, t, top_k)
        int32 array an expert layer, over ALL experts.  top-k is
        discontinuous, so two implementations that round differently
        choose differently for some tokens; a comparison of logits that
        is to see rounding and not those choices takes them from here,
        as it takes the tokens from the stream.  They come from the SAME
        computation as the logits (another program over the same weights
        rounds differently in places and chooses otherwise for a token
        in a thousand; my chip runs, PR 28)."""
        import jax.numpy as jnp
        x, _latents, _stats, routing = self._forward(
            params, tokens, jnp.ones(tokens.shape, bool))
        logits = self._head(params, x)
        return (logits, routing) if with_routing else logits

    def prefill_kv(self, params, tokens, last):
        """tokens (1, t), ``last`` the index of the prompt's last token
        -> (its logits (vocab,), {"latent": (layer 0's (t, kv_rank +
        rope), ...)}, stats).  Tokens past ``last`` are padding: they
        count in no expert's load."""
        import jax.numpy as jnp
        t = tokens.shape[1]
        live = (jnp.arange(t, dtype=jnp.int32) <= last)[None]
        x, latents, stats, _routing = self._forward(params, tokens, live)
        logits = self._head(params, jnp.take(x[0], last, axis=0))
        return logits, {"latent": tuple(r[0] for r in latents)}, stats

    # -- decode step over the paged cache -------------------------------
    def _decode_prologue(self, params, token_ids, positions, block_tables,
                         block_size, live):
        """``(x, blk, off, ctx, live)`` of a decode step: the tokens'
        embeddings, the block and offset each slot's new row goes to,
        the context lengths (s, 1) and the live slots."""
        import jax
        import jax.numpy as jnp
        s = token_ids.shape[0]
        with jax.named_scope("mx.embed"):
            blk = jnp.take_along_axis(
                block_tables, (positions // block_size)[:, None],
                axis=1)[:, 0]
            off = positions % block_size
            ctx = (positions + 1).astype(jnp.int32).reshape(s, 1)
            if live is None:
                live = jnp.ones((s,), bool)
            x = jnp.take(params["embed"], token_ids, axis=0)
        return x, blk, off, ctx, live

    def _mla_decode(self, params, i, x, positions, slab, blk, off,
                    block_tables, ctx):
        """Layer ``i``'s latent attention in a decode step, the absorbed
        form: writes the slots' new rows into ``slab`` (the layer's
        latent slab) and hands it whole to the kernel; returns (x +
        Attn(norm(x)), slab')."""
        import jax
        import jax.numpy as jnp
        from ...kernels.mla_paged_attention import mla_paged_attention
        from .kvcache import slab_rows
        scope = jax.named_scope
        s = x.shape[0]
        pre, layer = "h%d_" % i, "h%d/" % i
        with scope(layer + "q_latent"):
            q_nope, q_rope, h = self._q_latent(params, pre, x, positions)
        with scope(layer + "kv_latent"):
            row = self._kv_latent(params, pre, h, positions)
        with scope(layer + "kv_write"):
            slab = slab.at[blk, off].set(slab_rows(row, slab))
        with scope(layer + "absorb"):
            w_uk, w_uv = self._up_projections(params, pre)
            q_abs = jnp.einsum("shn,chn->shc", q_nope, w_uk,
                               preferred_element_type=jnp.float32)
            q = slab_rows(jnp.concatenate(
                [q_abs.astype(x.dtype), q_rope], axis=-1), slab)
        with scope(layer + "attention"):
            att = mla_paged_attention(q, slab, block_tables, ctx,
                                      v_width=self.kv_rank,
                                      scale=self.scale)
        with scope(layer + "absorb"):
            att = jnp.einsum("shc,chv->shv", att.astype(x.dtype), w_uv,
                             preferred_element_type=jnp.float32)
            att = att.astype(x.dtype).reshape(
                s, self.num_heads * self.v_dim)
        with scope(layer + "proj"):
            x = x + self._dot(att, params[pre + "wo"]).astype(x.dtype)
        return x, slab

    def decode_logits(self, params, slabs, token_ids, positions,
                      block_tables, block_size, live=None):
        """One decode step for a slot batch, the absorbed form.

        token_ids, positions (s,) int32; ``slabs`` ``{"latent": (one
        (num_blocks, block_size, lanes >= kv_rank + rope) array a
        layer)}``; block_tables (s, max_blocks) int32; ``live`` (s,)
        bool, the slots that hold a sequence (None: all of them): a
        padded slot counts in no expert's load.  Returns
        (next_token (s,), logits (s, vocab) float32, slabs', stats).

        Layer ``i`` writes the ``s`` new rows into ``slabs["latent"][i]``
        and hands that array, whole lanes and all, to the attention
        kernel: the query is padded with zeros to the slab's lanes, so
        nothing is cut out of a slab."""
        import jax
        import jax.numpy as jnp
        from ...ops.pallas.mla_paged_attention import grid_steps
        latent = list(slabs["latent"])
        x, blk, off, ctx, live = self._decode_prologue(
            params, token_ids, positions, block_tables, block_size, live)
        stats = self._new_stats()
        for i in range(self.num_layers):
            x, latent[i] = self._mla_decode(params, i, x, positions,
                                            latent[i], blk, off,
                                            block_tables, ctx)
            x, stats, _chosen = self._ffn(params, i, x, live, stats)
        logits = self._head(params, x)
        with jax.named_scope("mx.lm_head"):
            next_token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        # the latent kernel's schedule is the same in every layer
        stats = dict(stats, latent_grid_steps=grid_steps(
            block_tables, ctx, block_size) * self.num_layers)
        return next_token, logits, {"latent": tuple(latent)}, stats

    def __repr__(self):
        return ("LatentMoEDecoder(vocab=%d, units=%d, layers=%d, heads=%d,"
                " experts %d..%d of %d, max_seq=%d)" % (
                    self.vocab_size, self.units, self.num_layers,
                    self.num_heads, self.first_expert,
                    self.first_expert + self.n_held, self.num_experts,
                    self.max_seq))
