"""A GPT-style decoder in pure-function form for the generative engine.

The training-side transformer stack (``gluon/nn/transformer.py``) is an
encoder: full-sequence forwards, no cache.  Autoregressive serving
needs the SAME weights runnable in two compiled shapes -- a **prefill**
(whole prompt, causal, emits every position's K/V) and a **decode
step** (one token per slot, attending over the paged cache) -- so the
model here is a plain params-dict + pure functions, the
``fn(params, x)`` shape every servable source already lands in:

- :meth:`TinyGPT.full_logits` -- the reference full causal forward
  (pre-LN blocks, GELU MLP, tied unembedding); also the single-shot
  numerics oracle :meth:`reference_decode` loops over.
- :meth:`TinyGPT.prefill_kv` -- the same forward, additionally
  returning every layer's per-position K/V so the engine can scatter
  the prompt into cache blocks inside ONE compiled program.
- :meth:`TinyGPT.decode_logits` -- one token per slot: project q/k/v,
  scatter the new K/V into the slot's block-table position of THAT
  LAYER's slab, attend over it through the ``paged_attention``
  kernel-registry entry.
- :meth:`TinyGPT.cache_rows` -- what one token keeps in one layer of
  the paged cache (``k`` and ``v``, per head): the engine builds its
  ``PagedKVCache`` from this declaration and hands the slabs to the
  two methods above as one pytree ``{name: (layer 0's, ...)}``.

This is the duck type the engine serves; ``latent_moe.LatentMoEDecoder``
(latent attention, routed experts) is the other spec that has it.

Everything is fp32-accumulated and greedy-decodable: the engine's
continuous-batching tests hold decode tokens bit-identical between a
solo run and a join-mid-batch run, which per-slot row-independent math
(layernorm, per-head attention, row-wise matmul) preserves.
"""
from __future__ import annotations

import numpy as np

from ...base import MXNetError

__all__ = ["TinyGPT", "tiny_gpt"]


class TinyGPT:
    """Decoder-only transformer spec: geometry + pure functions.

    Parameters live OUTSIDE the object (a flat ``{name: jnp array}``
    dict from :meth:`init_params` or a checkpoint restore), so hot-swap
    re-registration is just "same TinyGPT, new dict".
    """

    def __init__(self, vocab_size=128, units=32, num_layers=2,
                 num_heads=2, max_seq=64, ffn_mult=4):
        if units % num_heads:
            raise MXNetError("TinyGPT: units %d not divisible by heads "
                             "%d" % (units, num_heads))
        self.vocab_size = int(vocab_size)
        self.units = int(units)
        self.num_layers = int(num_layers)
        self.num_heads = int(num_heads)
        self.head_dim = self.units // self.num_heads
        self.max_seq = int(max_seq)
        self.ffn = int(ffn_mult) * self.units
        self.scale = 1.0 / float(np.sqrt(self.head_dim))

    # -- params ---------------------------------------------------------
    def init_params(self, seed=0):
        """Flat name->array dict (embedding tied to the unembedding)."""
        import jax
        import jax.numpy as jnp
        key = jax.random.PRNGKey(seed)
        p = {}

        def nrm(key, shape, scale):
            return (jax.random.normal(key, shape, jnp.float32) * scale)

        ks = jax.random.split(key, 2 + 4 * self.num_layers)
        p["embed"] = nrm(ks[0], (self.vocab_size, self.units), 0.08)
        p["pos_embed"] = nrm(ks[1], (self.max_seq, self.units), 0.02)
        for i in range(self.num_layers):
            k0, k1, k2, k3 = ks[2 + 4 * i: 6 + 4 * i]
            pre = "h%d_" % i
            p[pre + "ln1_g"] = jnp.ones((self.units,), jnp.float32)
            p[pre + "ln1_b"] = jnp.zeros((self.units,), jnp.float32)
            p[pre + "wqkv"] = nrm(k0, (self.units, 3 * self.units),
                                  0.08)
            p[pre + "wo"] = nrm(k1, (self.units, self.units), 0.08)
            p[pre + "ln2_g"] = jnp.ones((self.units,), jnp.float32)
            p[pre + "ln2_b"] = jnp.zeros((self.units,), jnp.float32)
            p[pre + "w1"] = nrm(k2, (self.units, self.ffn), 0.08)
            p[pre + "b1"] = jnp.zeros((self.ffn,), jnp.float32)
            p[pre + "w2"] = nrm(k3, (self.ffn, self.units), 0.08)
            p[pre + "b2"] = jnp.zeros((self.units,), jnp.float32)
        p["lnf_g"] = jnp.ones((self.units,), jnp.float32)
        p["lnf_b"] = jnp.zeros((self.units,), jnp.float32)
        return p

    # -- shared pieces --------------------------------------------------
    @staticmethod
    def _ln(x, g, b):
        import jax.numpy as jnp
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + 1e-5) * g + b

    @staticmethod
    def _gelu(x):
        import jax
        return jax.nn.gelu(x, approximate=True)

    def _mlp(self, p, pre, x):
        import jax.numpy as jnp
        h = self._gelu(jnp.dot(x, p[pre + "w1"]) + p[pre + "b1"])
        return jnp.dot(h, p[pre + "w2"]) + p[pre + "b2"]

    def _split_heads(self, t):
        # (..., units) -> (..., heads, head_dim)
        return t.reshape(t.shape[:-1]
                         + (self.num_heads, self.head_dim))

    # -- full causal forward (reference + prefill) ----------------------
    def _forward(self, params, tokens, collect_kv):
        import jax
        import jax.numpy as jnp
        scope = jax.named_scope
        b, t = tokens.shape
        with scope("mx.embed"):
            x = jnp.take(params["embed"], tokens, axis=0) \
                + params["pos_embed"][:t][None]
        causal = jnp.tril(jnp.ones((t, t), bool))
        kvs = []
        for i in range(self.num_layers):
            pre = "h%d_" % i
            layer = "h%d/" % i
            with scope(layer + "qkv"):
                h = self._ln(x, params[pre + "ln1_g"],
                             params[pre + "ln1_b"])
                qkv = jnp.dot(h, params[pre + "wqkv"])
                q, k, v = jnp.split(qkv, 3, axis=-1)
                q = self._split_heads(q)               # (b, t, H, D)
                k = self._split_heads(k)
                v = self._split_heads(v)
            if collect_kv:
                kvs.append((k, v))
            with scope(layer + "attention"):
                s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * self.scale
                s = jnp.where(causal[None, None], s, -1e30)
                w = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
                w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True),
                                    1e-30)
                att = jnp.einsum("bhqk,bkhd->bqhd", w, v)
                att = att.reshape(b, t, self.units)
            with scope(layer + "proj"):
                x = x + jnp.dot(att, params[pre + "wo"])
            with scope(layer + "mlp"):
                h2 = self._ln(x, params[pre + "ln2_g"],
                              params[pre + "ln2_b"])
                x = x + self._mlp(params, pre, h2)
        with scope("mx.lm_head"):
            x = self._ln(x, params["lnf_g"], params["lnf_b"])
            logits = jnp.dot(x, params["embed"].T)     # tied unembedding
        return (logits, kvs) if collect_kv else logits

    def full_logits(self, params, tokens):
        """Reference causal forward: tokens (b, t) int32 -> logits
        (b, t, vocab)."""
        return self._forward(params, tokens, collect_kv=False)

    def cache_rows(self):
        """What one token keeps in one layer of the paged cache."""
        return {"k": (self.num_heads, self.head_dim),
                "v": (self.num_heads, self.head_dim)}

    def prefill_kv(self, params, tokens, last):
        """tokens (1, t), ``last`` the index of the prompt's last token
        -> (its logits (vocab,), rows, stats): ``rows`` is ``{"k":
        (layer 0's, ...), "v": ...}``, each array (t, heads, head_dim);
        ``stats`` the counts a step fetches beside its token (none
        here)."""
        import jax
        import jax.numpy as jnp
        logits, kvs = self._forward(params, tokens, collect_kv=True)
        with jax.named_scope("mx.lm_head"):
            logits = jnp.take(logits[0], last, axis=0)
        return (logits, {"k": tuple(k[0] for k, _v in kvs),
                         "v": tuple(v[0] for _k, v in kvs)}, {})

    # -- decode step over the paged cache -------------------------------
    def decode_logits(self, params, slabs, token_ids, positions,
                      block_tables, block_size, live=None):
        """One decode step for a slot batch.

        token_ids (s,) int32; positions (s,) int32 (where each new
        token is written, = its context length - 1); ``slabs`` is
        ``{"k": ..., "v": ...}`` with one slab per layer, each
        (num_blocks, block_size, heads, lanes >= head_dim) of which the
        first head_dim lanes are used (``PagedKVCache`` pads them to
        whole tiles, a plain array need not); block_tables (s,
        max_blocks) int32; ``live`` (s,) bool marks the slots that hold a
        sequence, for a model that counts its tokens (this one does not
        read it).  Returns (next_token (s,) int32, logits (s, vocab),
        slabs', stats), the slabs as tuples in layer order and ``stats``
        empty (``prefill_kv``).

        Layer ``i`` writes its ``s`` new rows into ``slabs["k"][i]`` and
        hands that array's head_dim lanes to the attention kernel (a
        view of the same tiles on a TPU): no slab is cut out of another
        or stacked, so a caller that donates the pytree (the
        engine does) gets a step that updates the cache in place.  The
        padded slots of a bucket all write (scratch block, offset 0):
        the scatter's indices are not unique and nothing is promised
        to XLA about them.
        """
        import jax
        import jax.numpy as jnp
        from ...kernels.paged_attention import paged_attention
        from .kvcache import slab_rows
        scope = jax.named_scope
        s, d = token_ids.shape[0], self.head_dim
        kv_keys, kv_values = list(slabs["k"]), list(slabs["v"])
        with scope("mx.embed"):
            blk = jnp.take_along_axis(
                block_tables, (positions // block_size)[:, None],
                axis=1)[:, 0]                           # (s,)
            off = positions % block_size
            ctx = (positions + 1).astype(jnp.int32).reshape(s, 1)
            x = jnp.take(params["embed"], token_ids, axis=0) \
                + jnp.take(params["pos_embed"], positions, axis=0)
        for i in range(self.num_layers):
            pre = "h%d_" % i
            layer = "h%d/" % i
            with scope(layer + "qkv"):
                h = self._ln(x, params[pre + "ln1_g"],
                             params[pre + "ln1_b"])
                qkv = jnp.dot(h, params[pre + "wqkv"])
                q, k, v = jnp.split(qkv, 3, axis=-1)
                q = self._split_heads(q)                # (s, H, D)
                k = self._split_heads(k)
                v = self._split_heads(v)
            # scatter the new token's K/V into its cache position;
            # padded slots carry all-scratch tables so their writes
            # land in the reserved scratch block
            with scope(layer + "kv_write"):
                kv_keys[i] = kv_keys[i].at[blk, off].set(
                    slab_rows(k, kv_keys[i]))
                kv_values[i] = kv_values[i].at[blk, off].set(
                    slab_rows(v, kv_values[i]))
            with scope(layer + "attention"):
                att = paged_attention(q, kv_keys[i][..., :d],
                                      kv_values[i][..., :d],
                                      block_tables, ctx, scale=self.scale)
                att = att.reshape(s, self.units).astype(x.dtype)
            with scope(layer + "proj"):
                x = x + jnp.dot(att, params[pre + "wo"])
            with scope(layer + "mlp"):
                h2 = self._ln(x, params[pre + "ln2_g"],
                              params[pre + "ln2_b"])
                x = x + self._mlp(params, pre, h2)
        with scope("mx.lm_head"):
            x = self._ln(x, params["lnf_g"], params["lnf_b"])
            logits = jnp.dot(x, params["embed"].T)
            next_token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return (next_token, logits,
                {"k": tuple(kv_keys), "v": tuple(kv_values)}, {})

    # -- single-shot oracle ---------------------------------------------
    def reference_decode(self, params, prompt, max_new_tokens,
                         eos_id=None):
        """Greedy single-shot decode: one FULL forward per token, no
        cache -- the numerics oracle the engine's tokens are gated
        against (CI ``serving_decode`` stage)."""
        import jax.numpy as jnp
        tokens = [int(t) for t in prompt]
        out = []
        for _ in range(int(max_new_tokens)):
            logits = self.full_logits(
                params, jnp.asarray([tokens], jnp.int32))
            nxt = int(jnp.argmax(logits[0, -1]))
            out.append(nxt)
            tokens.append(nxt)
            if eos_id is not None and nxt == eos_id:
                break
        return out

    def __repr__(self):
        return ("TinyGPT(vocab=%d, units=%d, layers=%d, heads=%d, "
                "max_seq=%d)" % (self.vocab_size, self.units,
                                 self.num_layers, self.num_heads,
                                 self.max_seq))


def tiny_gpt(vocab_size=128, units=32, num_layers=2, num_heads=2,
             max_seq=64):
    """The CI/test-sized GPT-style decoder."""
    return TinyGPT(vocab_size=vocab_size, units=units,
                   num_layers=num_layers, num_heads=num_heads,
                   max_seq=max_seq)
