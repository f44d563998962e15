"""A decoder with grouped-query attention in sliding-window and full
layers and an expert feed-forward in every layer, in pure-function form
for the generative engine: the block of Mellum2-12B-A2.5B-Instruct
(``model_type mellum``) and its relatives.

Same duck type as :class:`~.model.TinyGPT` and
:class:`~.latent_moe.LatentMoEDecoder` -- ``init_params``,
``full_logits``, ``prefill_kv``, ``decode_logits``, ``max_seq``,
``num_layers``, ``cache_rows`` -- so ``ModelRegistry.register_generative``
and :class:`~.engine.DecodeEngine` serve it with no side entry.  What it
declares beyond them is the KIND of each layer (``cache_layers()``,
``sliding_window``, ``cache_fold_heads``), from which the engine builds
one ``PagedKVCache`` with two pools and hands the programs a block
table a kind.  The block:

- **RMS norm** before attention and before the feed-forward, a final one
  before the untied head (``blocks.rms_norm``).
- **Grouped-query attention**: ``num_attention_heads`` query heads of
  ``head_dim`` read ``num_key_value_heads`` K/V heads, query head ``h``
  the K/V head ``h // group``.  K and V stay per K/V head everywhere:
  the cache row is ``(kv_heads, head_dim)``, prefill attends in blocks
  with a running maximum (``blocks.causal_attention``) and decode through
  the ``paged_attention`` kernel-registry entry.
- **Two kinds of layer** (``layer_types``): ``full_attention`` sees every
  earlier position; ``sliding_attention`` the current token and the
  ``sliding_window - 1`` before it.  In the cache a window layer keeps a
  bounded ring of blocks a sequence (``kvcache.py``), its prefill visits
  only the key blocks that reach into a query block's window, and its
  decode call fetches the pages of its window and no other.
- **Rotary positions** over the whole head, pairs ``(j, j + head_dim/2)``
  (``rotate_half``), from ``rope_parameters`` BY LAYER TYPE: plain
  frequencies, or YaRN-scaled ones whose cos and sin both carry
  ``attention_factor`` (so a score carries its square).
- **Expert layer** in every layer: ``softmax`` over all ``num_experts``,
  top-k, renormalised (``norm_topk_prob``), no selection bias, no scale,
  no shared expert; all experts are held here
  (``parallel.moe.routed_experts`` with ``first_expert`` 0).

Weights and the cache are bfloat16 (``dtype``); every matmul accumulates
in float32; norm statistics, the router (matmul at precision
``highest``, softmax, top-k), the attention softmax and the rotary
tables are float32.

The prefill and decode programs return, beside the token, five counts
(``stats``): ``moe_assignments``, ``moe_assignments_held``,
``moe_expert_tokens_max`` (as ``LatentMoEDecoder``), and ``kv_rows_full``
= the sum over live slots of the context length, ``kv_rows_window`` = the
sum of ``min(context, sliding_window)``: the cache rows ONE layer of each
kind had to read in the call.
"""
from __future__ import annotations

import numpy as np

from ...base import MXNetError
from . import blocks
from .kvcache import FULL, WINDOW, write_tokens

__all__ = ["WindowMoEDecoder"]

_KINDS = {"full_attention": FULL, "sliding_attention": WINDOW}
# sorted assignments the expert matmuls take at a time.  Every expert is
# held here, so every row of a chunk is live and a larger chunk only
# saves launches: one layer over 2,048 tokens took 6.0 ms at 2,048 rows a
# chunk and 5.1 ms at 8,192 or 16,384 (PERF.md section 6, PR 32); at
# 8,192 a chunk's temporaries are some 190 MB
EXPERT_CHUNK_ROWS = 8192


class WindowMoEDecoder:
    """Decoder-only transformer spec with grouped-query attention in
    window and full layers and routed experts: geometry + pure
    functions; parameters live OUTSIDE the object (a flat ``{name:
    array}`` dict), as with ``TinyGPT``.

    The constructor takes the published config's keys.  ``layer_types``
    names every layer (``"sliding_attention"`` / ``"full_attention"``);
    ``rope_parameters`` maps each of those two names to ``{"rope_type":
    "default" | "yarn", "rope_theta", ...}`` (YaRN: ``factor``,
    ``original_max_position_embeddings``, ``beta_fast``, ``beta_slow``,
    ``attention_factor``); ``vocab_size`` is the rows of the vocabulary
    held here and ``max_seq`` the longest context served."""

    # K/V heads are fewer than a bfloat16 tile has sublanes: the cache
    # lays them into the block's rows (kvcache.py, "Folded heads")
    cache_fold_heads = True

    def __init__(self, vocab_size, hidden_size, num_attention_heads,
                 num_key_value_heads, head_dim, layer_types, sliding_window,
                 rope_parameters, moe_intermediate_size, num_experts,
                 num_experts_per_tok, norm_topk_prob=True,
                 rms_norm_eps=1e-6, max_seq=4096, dtype="bfloat16"):
        self.vocab_size = int(vocab_size)
        self.units = int(hidden_size)
        self.num_heads = int(num_attention_heads)
        self.kv_heads = int(num_key_value_heads)
        self.head_dim = int(head_dim)
        self.layer_types = tuple(layer_types)
        self.num_layers = len(self.layer_types)
        self.sliding_window = int(sliding_window)
        self.expert_ffn = int(moe_intermediate_size)
        self.num_experts = int(num_experts)
        self.top_k = int(num_experts_per_tok)
        self.norm_topk = bool(norm_topk_prob)
        self.eps = float(rms_norm_eps)
        self.max_seq = int(max_seq)
        self.dtype = str(dtype)
        if set(self.layer_types) - set(_KINDS):
            raise MXNetError("layer_types are %s, got %r"
                             % (sorted(_KINDS), self.layer_types))
        if self.num_heads % self.kv_heads or self.head_dim % 2 \
                or not 1 <= self.top_k <= self.num_experts:
            raise MXNetError(
                "%d query heads over %d K/V heads of %d, %d experts of "
                "%d a token" % (self.num_heads, self.kv_heads,
                                self.head_dim, self.top_k,
                                self.num_experts))
        self.scale = float(self.head_dim) ** -0.5
        # by layer type: (inverse frequencies, what cos and sin carry)
        self.rope = {}
        for kind in set(self.layer_types):
            rp = rope_parameters[kind]
            yarn = rp.get("rope_type", "default") == "yarn"
            if not yarn and rp.get("rope_type", "default") != "default":
                raise MXNetError("rope_type %r" % (rp["rope_type"],))
            self.rope[kind] = (
                blocks.yarn_inv_freq(self.head_dim, rp["rope_theta"],
                                     rp if yarn else None
                                     ).astype(np.float32),
                float(rp.get("attention_factor", 1.0)) if yarn else 1.0)

    # -- what the cache is told -------------------------------------------
    def cache_rows(self):
        """What one token keeps in one layer of the paged cache."""
        return {"k": (self.kv_heads, self.head_dim),
                "v": (self.kv_heads, self.head_dim)}

    def cache_layers(self):
        """The kind of each layer's cache: ``"window"`` (a bounded ring
        of blocks a sequence) or ``"full"``."""
        return tuple(_KINDS[t] for t in self.layer_types)

    # -- params ---------------------------------------------------------
    def param_shapes(self):
        """{name: (shape, kind)}; kind "norm" (about 1) or the fan-in of
        a matmul weight."""
        d, f, e = self.units, self.expert_ffn, self.num_experts
        q, kv = self.num_heads * self.head_dim, self.kv_heads * self.head_dim
        out = {"embed": ((self.vocab_size, d), 1),
               "norm_f": ((d,), "norm"),
               "head": ((d, self.vocab_size), d)}
        for i in range(self.num_layers):
            pre = "h%d_" % i
            out.update({
                pre + "attn_norm": ((d,), "norm"),
                pre + "wq": ((d, q), d),
                pre + "wk": ((d, kv), d),
                pre + "wv": ((d, kv), d),
                pre + "wo": ((q, d), q),
                pre + "ffn_norm": ((d,), "norm"),
                pre + "router": ((d, e), d),
                pre + "experts_gate": ((e, d, f), d),
                pre + "experts_up": ((e, d, f), d),
                pre + "experts_down": ((e, f, d), f)})
        return out

    def init_params(self, seed=0):
        """Flat name->array dict drawn from ``seed``
        (:func:`~.blocks.draw_params`)."""
        return blocks.draw_params(self.param_shapes(), seed, self.dtype)

    # -- the block's pieces ---------------------------------------------
    def _qkv(self, p, i, x, positions):
        """x (..., t, d) -> q (..., t, H, hd), k, v (..., t, Hkv, hd) in
        the activations' dtype, q and k rotated by layer ``i``'s table."""
        import jax
        pre, layer = "h%d_" % i, "h%d/" % i
        with jax.named_scope(layer + "qkv"):
            h = blocks.rms_norm(x, p[pre + "attn_norm"], self.eps)
            lead = x.shape[:-1]
            q = blocks.dot(h, p[pre + "wq"]).reshape(
                lead + (self.num_heads, self.head_dim))
            k = blocks.dot(h, p[pre + "wk"]).reshape(
                lead + (self.kv_heads, self.head_dim))
            v = blocks.dot(h, p[pre + "wv"]).astype(x.dtype).reshape(
                lead + (self.kv_heads, self.head_dim))
        with jax.named_scope(layer + "rope"):
            inv_freq, factor = self.rope[self.layer_types[i]]
            q = blocks.rotate(q, positions, inv_freq, halves=True,
                              factor=factor).astype(x.dtype)
            k = blocks.rotate(k, positions, inv_freq, halves=True,
                              factor=factor).astype(x.dtype)
        return q, k, v

    def _window(self, i):
        return self.sliding_window \
            if self.layer_types[i] == "sliding_attention" else None

    def _attention_scope(self, i):
        return "h%d/attention_%s" % (i, _KINDS[self.layer_types[i]])

    def _ffn(self, p, i, x, live, stats, decode_step=False):
        """x (tokens, d) -> (x + MoE(norm(x)), stats, chosen)."""
        pre = "h%d_" % i
        _h, routed, stats, chosen = blocks.routed_ffn(
            "h%d/" % i, x, p[pre + "ffn_norm"], self.eps, p[pre + "router"],
            None, (p[pre + "experts_gate"], p[pre + "experts_up"],
                   p[pre + "experts_down"]), self.top_k, live, stats,
            scoring="softmax", normalize=self.norm_topk,
            decode_step=decode_step, chunk_rows=EXPERT_CHUNK_ROWS)
        return x + routed.astype(x.dtype), stats, chosen

    def _new_stats(self, ctx):
        """The running counts; ``ctx`` the live slots' context lengths
        (0 for a slot that holds none)."""
        import jax.numpy as jnp
        return dict(blocks.new_moe_stats(),
                    kv_rows_full=jnp.sum(ctx),
                    kv_rows_window=jnp.sum(
                        jnp.minimum(ctx, self.sliding_window)))

    def _head(self, params, x):
        import jax
        with jax.named_scope("mx.lm_head"):
            return blocks.dot(blocks.rms_norm(x, params["norm_f"], self.eps),
                              params["head"])

    # -- full causal forward (reference + prefill) ----------------------
    def _forward(self, params, tokens, live):
        """tokens (b, t) -> (hidden (b, t, d) before the final norm, the
        K and V rows of every layer (b, t, Hkv, hd), stats, the experts
        every layer's router chose (b, t, top_k))."""
        import jax
        import jax.numpy as jnp
        scope = jax.named_scope
        b, t = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32),
                                     (b, t))
        with scope("mx.embed"):
            x = jnp.take(params["embed"], tokens, axis=0)
        stats = self._new_stats(jnp.sum(live.astype(jnp.int32), axis=1))
        keys, values, routing = [], [], []
        for i in range(self.num_layers):
            q, k, v = self._qkv(params, i, x, positions)
            keys.append(k)
            values.append(v)
            with scope(self._attention_scope(i)):
                att = blocks.causal_attention(q, k, v, self.scale,
                                              window=self._window(i))
            with scope("h%d/proj" % i):
                x = x + blocks.dot(att, params["h%d_wo" % i]).astype(x.dtype)
            flat, stats, chosen = self._ffn(
                params, i, x.reshape(b * t, -1), live.reshape(b * t), stats)
            x = flat.reshape(b, t, -1)
            routing.append(chosen.reshape(b, t, self.top_k))
        return x, keys, values, stats, tuple(routing)

    def full_logits(self, params, tokens, with_routing=False):
        """Reference causal forward (no cache): tokens (b, t) int32 ->
        logits (b, t, vocab) float32.  ``with_routing=True`` returns
        ``(logits, routing)``: beside the logits the experts each token
        was sent to, one (b, t, top_k) int32 array a layer, from the SAME
        computation (``LatentMoEDecoder.full_logits`` says why)."""
        import jax.numpy as jnp
        x, _k, _v, _stats, routing = self._forward(
            params, tokens, jnp.ones(tokens.shape, bool))
        logits = self._head(params, x)
        return (logits, routing) if with_routing else logits

    def prefill_kv(self, params, tokens, last):
        """tokens (1, t), ``last`` the index of the prompt's last token
        -> (its logits (vocab,), {"k": (layer 0's (t, Hkv, hd), ...),
        "v": ...}, stats).  Tokens past ``last`` are padding: they count
        in no expert's load.  Every layer's rows are whole; the engine
        keeps of a window layer's what its ring holds."""
        import jax.numpy as jnp
        t = tokens.shape[1]
        live = (jnp.arange(t, dtype=jnp.int32) <= last)[None]
        x, keys, values, stats, _routing = self._forward(params, tokens,
                                                         live)
        logits = self._head(params, jnp.take(x[0], last, axis=0))
        return logits, {"k": tuple(k[0] for k in keys),
                        "v": tuple(v[0] for v in values)}, stats

    # -- decode step over the paged cache -------------------------------
    def decode_logits(self, params, slabs, token_ids, positions,
                      block_tables, block_size, live=None):
        """One decode step for a slot batch.

        token_ids, positions (s,) int32; ``slabs`` ``{"k": (one array a
        layer), "v": ...}``, a layer's (num_blocks of its kind,
        block_size * Hkv, lanes >= hd) with the heads folded into the
        block's rows, or (num_blocks, block_size, Hkv, lanes);
        ``block_tables`` ``{"full": (s, blocks of the longest sequence),
        "window": (s, ring)}`` int32, or one array where every layer is
        full; ``live`` (s,) bool, the slots that hold a sequence (None:
        all of them).  Returns (next_token (s,), logits (s, vocab)
        float32, slabs', stats).

        Layer ``i`` writes the ``s`` new rows into its own slabs through
        the table of its kind -- a window layer's at ring entry
        ``(position // block_size) % ring`` -- and attends through the
        ``paged_attention`` entry, a window layer over its last
        ``sliding_window`` positions alone."""
        import jax
        import jax.numpy as jnp
        from ...kernels.paged_attention import paged_attention
        scope = jax.named_scope
        s, d = token_ids.shape[0], self.head_dim
        keys, values = list(slabs["k"]), list(slabs["v"])
        tables = block_tables if isinstance(block_tables, dict) \
            else {FULL: block_tables}
        with scope("mx.embed"):
            if live is None:
                live = jnp.ones((s,), bool)
            ctx = (positions + 1).astype(jnp.int32).reshape(s, 1)
            x = jnp.take(params["embed"], token_ids, axis=0)
        stats = self._new_stats(jnp.where(live, ctx[:, 0], 0))
        for i, kind in enumerate(self.cache_layers()):
            q, k, v = self._qkv(params, i, x, positions)
            table = tables[kind]
            with scope("h%d/kv_write" % i):
                keys[i] = write_tokens(keys[i], k, table, positions,
                                       block_size)
                values[i] = write_tokens(values[i], v, table, positions,
                                         block_size)
            with scope(self._attention_scope(i)):
                att = paged_attention(
                    q, keys[i][..., :d], values[i][..., :d], table, ctx,
                    scale=self.scale, window=self._window(i),
                    block_size=block_size)
                att = att.reshape(s, self.num_heads * d).astype(x.dtype)
            with scope("h%d/proj" % i):
                x = x + blocks.dot(att, params["h%d_wo" % i]).astype(x.dtype)
            x, stats, _chosen = self._ffn(params, i, x, live, stats,
                                          decode_step=True)
        logits = self._head(params, x)
        with scope("mx.lm_head"):
            next_token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return (next_token, logits,
                {"k": tuple(keys), "v": tuple(values)}, stats)

    def __repr__(self):
        return ("WindowMoEDecoder(vocab=%d, units=%d, layers=%d (%d window "
                "of %d), heads=%d/%d, experts %d of %d, max_seq=%d)" % (
                    self.vocab_size, self.units, self.num_layers,
                    self.cache_layers().count(WINDOW), self.sliding_window,
                    self.num_heads, self.kv_heads, self.top_k,
                    self.num_experts, self.max_seq))
