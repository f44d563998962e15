"""A dense decoder whose stack of layers is run several times over ONE
set of weights, with a learned exit gate, in pure-function form for the
generative engine: the looped language model of Ouro-2.6B
(``model_type ouro``) and its relatives.

The duck type of :class:`~.model.TinyGPT`,
:class:`~.latent_moe.LatentMoEDecoder` and
:class:`~.window_moe.WindowMoEDecoder` -- ``init_params``,
``full_logits``, ``decode_logits``, ``max_seq``, ``num_layers``,
``cache_rows``, and ``prefill_cache`` where they have ``prefill_kv`` (a
prefill is one or the other: this one writes its rows itself, inside its
loop) -- so ``ModelRegistry.register_generative`` and
:class:`~.engine.DecodeEngine` serve it with no side entry.  What it
declares beyond them is ``cache_passes``: the stack of ``num_layers``
weight layers is applied ``total_ut_steps`` times a token, and the K and
V of pass ``t`` of layer ``l`` are a cache entry of their own (the
published cache index is ``t * num_layers + l``), so the cache has
``cache_passes * num_layers`` layers over ``num_layers`` layers of
weights.  The engine builds the one
``PagedKVCache`` from that (``kvcache.py``, "Passes"): layer ``l``'s slab
holds the blocks of all its passes, pass ``t``'s at ``t * num_blocks +
block``, so a sequence's one block table names every pass's rows and a
program reaches pass ``t`` by adding ``t * num_blocks`` to the table --
the decode kernel's block index is data.  The block:

- **Four RMS norms a layer** (the sandwich): ``a = x + N2(Attn(N1(x)))``,
  ``x = a + N4(MLP(N3(a)))``; one final norm, applied at the end of EVERY
  pass, whose output ``h_t`` is what the next pass starts from.  A norm's
  weight is the size of what it hands on, so ``N2`` and ``N4`` say how
  much a sub-layer adds to the stream: ``init_params`` draws them about
  ``(2 * num_layers) ** -0.5``, so that the ``2 * num_layers`` branches
  of a pass add up to about the size of the state the pass started from
  (the depth-scaled residual of GPT-2's initialisation).  Drawn about 1
  they bury that state under ten times its size a pass, and the map from
  one pass's state to the next magnifies a rounding 2.4 x a pass, which
  no trained model of this kind does (PERF.md, PR 34).
- **Attention** with rotary positions over the whole head, pairs ``(j, j
  + head_dim/2)`` (``rotate_half``), the same positions in every pass;
  K and V stay per K/V head (``blocks.causal_attention`` in prefill, the
  ``paged_attention`` kernel-registry entry in decode).  A query of pass
  ``t`` reads the keys and values earlier positions produced in pass
  ``t`` of that layer, never another pass's.
- **SwiGLU** feed-forward, no bias anywhere in a layer.
- **The passes are a loop of the compiled program** (``jax.lax.scan``
  over ``t``) in prefill and in decode, with the cache's slabs in the
  loop's carry and written in place: the program is one stack of layers
  long whatever ``total_ut_steps`` is.
- **The exit gate**: ``g_t = sigmoid(w_g . h_t + b_g)``; ``p_1 = g_1``,
  ``p_t = g_t prod_{s<t}(1 - g_s)``, ``p_T = prod_{s<T}(1 - g_s)``; ``tau``
  is the first pass whose cumulative ``p_1 + .. + p_t`` reaches
  ``early_exit_threshold`` (the last pass where none does) and the
  untied head reads ``h_tau``.  EVERY pass is always run: ``tau`` picks a
  hidden state per token and skips no work, because a skipped pass would
  leave its cache entry without the token's row.
  ``total_ut_steps = 1`` is a plain pre/post-norm decoder: its one pass
  is its exit whatever the gate says.

Weights, the cache and the residual stream are bfloat16 (``dtype``);
every matmul accumulates in float32; norm statistics, the attention
softmax, the rotary tables and the gate are float32.

The prefill and decode programs return, beside the token, four counts
(``stats``): ``ut_passes`` (passes run for the call's live slots),
``exit_step_sum`` (the sum of ``tau`` over them), ``exit_early`` (how many
have ``tau < total_ut_steps``) and ``kv_rows`` (the live context rows ONE
cache layer had to read).
"""
from __future__ import annotations

from ...base import MXNetError
from . import blocks
from .kvcache import write_prompt, write_tokens

__all__ = ["LoopedDecoder", "PASS_LOOP"]

# the scope of the loop over passes in every program of this spec
PASS_LOOP = "mx.ut_loop"


class LoopedDecoder:
    """Decoder-only transformer spec whose layers run ``total_ut_steps``
    times a token: geometry + pure functions; parameters live OUTSIDE
    the object (a flat ``{name: array}`` dict), as with ``TinyGPT``.

    The constructor takes the published config's keys; ``vocab_size`` is
    the rows of the vocabulary held here and ``max_seq`` the longest
    context served."""

    def __init__(self, vocab_size, hidden_size, num_attention_heads,
                 num_key_value_heads, head_dim, intermediate_size,
                 num_hidden_layers, total_ut_steps=1,
                 early_exit_threshold=1.0, rope_theta=10000.0,
                 rms_norm_eps=1e-6, max_seq=4096, dtype="bfloat16"):
        self.vocab_size = int(vocab_size)
        self.units = int(hidden_size)
        self.num_heads = int(num_attention_heads)
        self.kv_heads = int(num_key_value_heads)
        self.head_dim = int(head_dim)
        self.ffn = int(intermediate_size)
        self.num_layers = int(num_hidden_layers)
        self.passes = int(total_ut_steps)
        self.exit_threshold = float(early_exit_threshold)
        self.eps = float(rms_norm_eps)
        self.max_seq = int(max_seq)
        self.dtype = str(dtype)
        if self.num_heads % self.kv_heads or self.head_dim % 2 \
                or self.passes < 1 or self.num_layers < 1:
            raise MXNetError(
                "%d query heads over %d K/V heads of %d, %d layers run "
                "%d times" % (self.num_heads, self.kv_heads, self.head_dim,
                              self.num_layers, self.passes))
        self.scale = float(self.head_dim) ** -0.5
        self.inv_freq = blocks.yarn_inv_freq(
            self.head_dim, rope_theta).astype("float32")

    # -- what the cache is told -------------------------------------------
    def cache_rows(self):
        """What one token keeps in one layer of the paged cache."""
        return {"k": (self.kv_heads, self.head_dim),
                "v": (self.kv_heads, self.head_dim)}

    @property
    def cache_passes(self):
        """Cache layers a layer of weights keeps: one a pass."""
        return self.passes

    # -- params ---------------------------------------------------------
    def param_shapes(self):
        """{name: (shape, kind)}; kind "norm" (about 1), ``("norm",
        gain)`` (about ``gain``: the second norm of each sandwich, module
        doc), "bias" or the fan-in of a matmul weight (stored (in,
        out))."""
        d, f = self.units, self.ffn
        branch = ("norm", (2.0 * self.num_layers) ** -0.5)
        q, kv = self.num_heads * self.head_dim, self.kv_heads * self.head_dim
        out = {"embed": ((self.vocab_size, d), 1),
               "norm_f": ((d,), "norm"),
               "gate_w": ((d, 1), d),
               "gate_b": ((1,), "bias"),
               "head": ((d, self.vocab_size), d)}
        for i in range(self.num_layers):
            pre = "h%d_" % i
            out.update({
                pre + "attn_norm": ((d,), "norm"),
                pre + "wq": ((d, q), d),
                pre + "wk": ((d, kv), d),
                pre + "wv": ((d, kv), d),
                pre + "wo": ((q, d), q),
                pre + "attn_out_norm": ((d,), branch),
                pre + "ffn_norm": ((d,), "norm"),
                pre + "w_gate": ((d, f), d),
                pre + "w_up": ((d, f), d),
                pre + "w_down": ((f, d), f),
                pre + "ffn_out_norm": ((d,), branch)})
        return out

    def init_params(self, seed=0):
        """Flat name->array dict drawn from ``seed``
        (:func:`~.blocks.draw_params`)."""
        return blocks.draw_params(self.param_shapes(), seed, self.dtype)

    # -- the block's pieces ---------------------------------------------
    def _qkv(self, p, i, x, positions):
        """x (..., t, d) -> q (..., t, H, hd), k, v (..., t, Hkv, hd) in
        the activations' dtype, q and k rotated."""
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
            q = blocks.rotate(q, positions, self.inv_freq,
                              halves=True).astype(x.dtype)
            k = blocks.rotate(k, positions, self.inv_freq,
                              halves=True).astype(x.dtype)
        return q, k, v

    def _rest_of_layer(self, p, i, x, att):
        """``a = x + N2(att W_o)``, ``a + N4(MLP(N3(a)))``: the sandwich's
        second norms sit inside the residual branches, on the float32
        results of their matmuls."""
        import jax
        pre, layer = "h%d_" % i, "h%d/" % i
        with jax.named_scope(layer + "proj"):
            a = (x + blocks.rms_norm(blocks.dot(att, p[pre + "wo"]),
                                     p[pre + "attn_out_norm"], self.eps)
                 ).astype(x.dtype)
        with jax.named_scope(layer + "mlp"):
            h = blocks.rms_norm(a, p[pre + "ffn_norm"], self.eps)
            y = blocks.swiglu(h, p[pre + "w_gate"], p[pre + "w_up"],
                              p[pre + "w_down"])
            return (a + blocks.rms_norm(y, p[pre + "ffn_out_norm"],
                                        self.eps)).astype(x.dtype)

    def _gate(self, p, state, h, t):
        """The exit rule after pass ``t`` (from 0) on its hidden state
        ``h`` (..., d): ``state`` holds, per token, the hidden state of
        the pass it exits at (``h``), that pass (``tau``, 0 while none),
        ``prod_{s<=t}(1 - g_s)`` (``survive``) and ``C_t`` (``cdf``).
        Returns the new state and ``p_t`` (...,)."""
        import jax
        import jax.numpy as jnp
        with jax.named_scope("mx.exit_gate"):
            last = t == self.passes - 1
            g = jax.nn.sigmoid(
                jnp.sum(h.astype(jnp.float32)
                        * p["gate_w"][:, 0].astype(jnp.float32), axis=-1)
                + p["gate_b"][0].astype(jnp.float32))
            p_t = jnp.where(last, state["survive"], g * state["survive"])
            cdf = state["cdf"] + p_t
            exits = (state["tau"] == 0) \
                & ((cdf >= self.exit_threshold) | last)
            return {"h": jnp.where(exits[..., None], h, state["h"]),
                    "tau": jnp.where(exits, t + 1, state["tau"]),
                    "survive": state["survive"] * (1.0 - g),
                    "cdf": cdf}, p_t

    def _run(self, params, x, positions, keys, values, attend):
        """The passes over ``x`` (..., d), a loop of the program.
        ``attend(t, i, q, k, v, keys_i, values_i) -> (att, keys_i',
        values_i')`` is layer ``i``'s attention in pass ``t`` (a traced
        number) and whatever it writes into that layer's slabs, which
        ride in the loop's carry.  Returns the exit state (``_gate``),
        ``p`` (passes, ...) and the slabs."""
        import jax
        import jax.numpy as jnp

        def one_pass(carry, t):
            x, gate, keys, values = carry
            keys, values = list(keys), list(values)
            for i in range(self.num_layers):
                q, k, v = self._qkv(params, i, x, positions)
                att, keys[i], values[i] = attend(t, i, q, k, v, keys[i],
                                                 values[i])
                x = self._rest_of_layer(params, i, x, att)
            with jax.named_scope("mx.final_norm"):
                h = blocks.rms_norm(x, params["norm_f"], self.eps)
            gate, p_t = self._gate(params, gate, h, t)
            return (h, gate, tuple(keys), tuple(values)), p_t

        lead = x.shape[:-1]
        gate = {"h": jnp.zeros_like(x),
                "tau": jnp.zeros(lead, jnp.int32),
                "survive": jnp.ones(lead, jnp.float32),
                "cdf": jnp.zeros(lead, jnp.float32)}
        with jax.named_scope(PASS_LOOP):
            (_h, gate, keys, values), p = jax.lax.scan(
                one_pass, (x, gate, tuple(keys), tuple(values)),
                jnp.arange(self.passes, dtype=jnp.int32))
        return gate, p, keys, values

    def _stats(self, tau, ctx):
        """The counts of a call: ``tau`` and ``ctx`` (slots,) of its live
        slots' exit passes and context lengths, 0 for a slot that holds
        no sequence."""
        import jax.numpy as jnp
        live = (ctx > 0).astype(jnp.int32)
        return {"ut_passes": self.passes * jnp.sum(live),
                "exit_step_sum": jnp.sum(tau * live),
                "exit_early": jnp.sum(live * (tau < self.passes)),
                "kv_rows": jnp.sum(ctx)}

    def _head(self, params, h):
        import jax
        with jax.named_scope("mx.lm_head"):
            return blocks.dot(h, params["head"])

    # -- full causal forward (reference + prefill) ----------------------
    def _forward(self, params, tokens, cache=None):
        """tokens (b, t) -> (exit state, p (passes, b, t), slabs).
        ``cache`` = (slabs, table (width,), true_len, block_size): the
        one sequence's rows are written into the slabs pass by pass,
        inside the loop (b = 1)."""
        import jax
        import jax.numpy as jnp
        b, t = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32),
                                     (b, t))
        with jax.named_scope("mx.embed"):
            x = jnp.take(params["embed"], tokens, axis=0)
        keys = values = (None,) * self.num_layers
        if cache is not None:
            slabs, table, true_len, block_size = cache
            keys, values = slabs["k"], slabs["v"]
            per_pass = keys[0].shape[0] // self.passes

        def attend(p, i, q, k, v, ks, vs):
            if cache is not None:
                with jax.named_scope("h%d/kv_write" % i):
                    tb = table + p * per_pass
                    ks = write_prompt(ks, k[0], tb, true_len, block_size)
                    vs = write_prompt(vs, v[0], tb, true_len, block_size)
            with jax.named_scope("h%d/attention_full" % i):
                return blocks.causal_attention(q, k, v, self.scale), ks, vs

        gate, p, keys, values = self._run(params, x, positions, keys,
                                          values, attend)
        return gate, p, {"k": keys, "v": values}

    def full_logits(self, params, tokens, with_exit=False):
        """Reference causal forward (no cache): tokens (b, t) int32 ->
        logits (b, t, vocab) float32.  ``with_exit=True`` returns
        ``(logits, tau, p)``: beside the logits the pass each position
        exits at (b, t) int32, from 1, and the exit distribution (b, t,
        passes) float32, from the SAME computation."""
        import jax.numpy as jnp
        gate, p, _slabs = self._forward(params, tokens)
        logits = self._head(params, gate["h"])
        return (logits, gate["tau"], jnp.moveaxis(p, 0, -1)) if with_exit \
            else logits

    def _prefilled(self, params, gate, last):
        """(logits of the prompt's last token, stats) of one sequence."""
        import jax.numpy as jnp
        logits = self._head(params, jnp.take(gate["h"][0], last, axis=0))
        tau = jnp.take(gate["tau"][0], last, axis=0)
        return logits, self._stats(tau[None], (last + 1)[None])

    def prefill_cache(self, params, slabs, tokens, last, table,
                      block_size):
        """The prefill of ONE sequence written straight into the cache:
        tokens (1, t), ``last`` the index of the prompt's last token,
        ``table`` (width,) its block table -> (its logits (vocab,),
        slabs', stats).  Pass ``t`` of layer ``l`` writes the prompt's
        rows into layer ``l``'s slabs through ``table + t * num_blocks``,
        inside the loop over passes; padding goes to the scratch
        block."""
        gate, _p, slabs = self._forward(
            params, tokens, cache=(slabs, table, last + 1, block_size))
        logits, stats = self._prefilled(params, gate, last)
        return logits, slabs, stats

    # -- decode step over the paged cache -------------------------------
    def decode_logits(self, params, slabs, token_ids, positions,
                      block_tables, block_size, live=None):
        """One decode step for a slot batch.

        token_ids, positions (s,) int32; ``slabs`` ``{"k": (one array a
        layer of WEIGHTS), "v": ...}``, each (passes * num_blocks,
        block_size, Hkv, lanes >= hd); ``block_tables`` (s, blocks of
        the longest sequence) int32; ``live`` (s,) bool, the slots that
        hold a sequence (None: all of them).  Returns (next_token (s,),
        logits (s, vocab) float32, slabs', stats).

        Pass ``t`` of layer ``i`` writes the ``s`` new rows into layer
        ``i``'s slabs through ``block_tables + t * num_blocks`` and
        attends through the ``paged_attention`` entry over the same
        table; the head runs once, on each slot's ``h_tau``."""
        import jax
        import jax.numpy as jnp
        from ...kernels.paged_attention import paged_attention
        s, d = token_ids.shape[0], self.head_dim
        per_pass = slabs["k"][0].shape[0] // self.passes
        with jax.named_scope("mx.embed"):
            if live is None:
                live = jnp.ones((s,), bool)
            ctx = (positions + 1).astype(jnp.int32).reshape(s, 1)
            x = jnp.take(params["embed"], token_ids, axis=0)

        def attend(p, i, q, k, v, ks, vs):
            tb = block_tables + p * per_pass
            with jax.named_scope("h%d/kv_write" % i):
                ks = write_tokens(ks, k, tb, positions, block_size)
                vs = write_tokens(vs, v, tb, positions, block_size)
            with jax.named_scope("h%d/attention_full" % i):
                att = paged_attention(q, ks[..., :d], vs[..., :d], tb, ctx,
                                      scale=self.scale)
                return att.reshape(s, self.num_heads * d).astype(
                    x.dtype), ks, vs

        gate, _p, keys, values = self._run(
            params, x, positions, slabs["k"], slabs["v"], attend)
        logits = self._head(params, gate["h"])
        with jax.named_scope("mx.lm_head"):
            next_token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return (next_token, logits, {"k": keys, "v": values},
                self._stats(gate["tau"], jnp.where(live, ctx[:, 0], 0)))

    def __repr__(self):
        return ("LoopedDecoder(vocab=%d, units=%d, layers=%d x %d passes, "
                "heads=%d/%d, exit at %g, max_seq=%d)" % (
                    self.vocab_size, self.units, self.num_layers,
                    self.passes, self.num_heads, self.kv_heads,
                    self.exit_threshold, self.max_seq))
