"""Ouro-2.6B (``model_type ouro``, ByteDance's looped language model,
arXiv:2510.25741), served whole on one chip: how the benchmark deploys it
through the program's generative-serving path, its plain float32
reference, and the shape functions of what a window served.

The equations (from the published ``config.json`` and the paper; d =
hidden_size, H = num_attention_heads = num_key_value_heads, hd = head_dim,
I = intermediate_size, L = num_hidden_layers, T = total_ut_steps; no bias
in any projection of a layer):

- ``x_0 = E[token]`` (no scale).  For pass ``t = 1..T``, for layer ``l =
  0..L-1``, with the SAME weights in every pass: ``a = x + N2_l(Attn_l(
  N1_l(x)))``, ``x = a + N4_l(MLP_l(N3_l(a)))``: four RMS norms a layer,
  one before and one after each sub-layer, the second inside the residual
  branch (``input_layernorm``, ``input_layernorm_2``,
  ``post_attention_layernorm``, ``post_attention_layernorm_2`` in that
  order).  ``RMSNorm(x) = w * x / sqrt(mean(x^2) + eps)``.
- After layer ``L-1`` of pass ``t``: ``h_t = Norm_f(x)``, ONE final norm
  used by every pass, and ``h_t`` is what pass ``t+1`` starts from.
- ``Attn_l(x)``: ``q = x W_q``, ``k = x W_k``, ``v = x W_v`` (d -> H x hd
  each); ``q, k = RoPE(., position)`` over all hd lanes, pairs ``(j, j +
  hd/2)`` (``rotate_half``), ``f_j = theta^(-2j/hd)``, no scaling, the
  same positions in every pass; ``score = q . k / sqrt(hd)``, causal,
  softmax, ``out = concat_heads(p v) W_o``.  The K and V of pass ``t`` of
  layer ``l`` are their own cache entry (the published cache index is ``t
  * L + l``): a query of pass ``t`` reads what the earlier positions
  produced in pass ``t`` of layer ``l``, never another pass's.
- ``MLP_l(x) = W_down(silu(W_gate x) * (W_up x))``.
- Exit gate: ``g_t = sigmoid(w_g . h_t + b_g)`` (d -> 1).  ``p_1 = g_1``,
  ``p_t = g_t prod_{s<t}(1 - g_s)`` for ``t < T``, ``p_T = prod_{s<T}(1 -
  g_s)``; ``C_t = p_1 + .. + p_t``; ``tau`` = the first ``t`` with ``C_t >=
  early_exit_threshold``, ``T`` where none; ``logits = W_head h_tau``
  (untied head).  Every pass is always run: ``tau`` picks a hidden state
  and skips no work.

Departures from the published code, each of them in the reference too:
matmul weights are stored (in, out), the transpose of a torch
``Linear.weight``; no q/k norm and no attention bias (the config names
neither); bfloat16 serving (``assumed`` in the configuration's file).

The reference shares no code with ``mxnet_tpu``: no cache, no kernel, the
mask written out, a Python loop over passes and layers.  It works a layer
and a query block at a time, so that what it adds to the device beside
the served bfloat16 weights (which it reads, never copies) is one
sequence's activations, one layer's weights in float32 and its float32
logits.
"""
import math

import numpy as np

KIND = "serve"

# query rows of attention scores the reference computes at a time
_REF_Q_BLOCK = 128


def _spec(cfg):
    """``LoopedDecoder`` at the configuration's widths."""
    from perfbench.harness.spec import SpecError
    try:
        from mxnet_tpu.serving.decode import LoopedDecoder
    except ImportError as e:    # a program from before the model spec
        raise SpecError("this program cannot run the configuration: %s"
                        % e) from None
    keys = ("vocab_size", "hidden_size", "num_attention_heads",
            "num_key_value_heads", "head_dim", "intermediate_size",
            "num_hidden_layers", "total_ut_steps", "early_exit_threshold",
            "rope_theta", "rms_norm_eps")
    return LoopedDecoder(max_seq=cfg["max_position_embeddings"],
                         dtype=cfg["serving_dtype"],
                         **{k: cfg[k] for k in keys})


def build_model(cfg, seed):
    """(model, params): the spec and its weights, drawn from the seed by
    the model's own jitted initialiser, on the device."""
    model = _spec(cfg)
    return model, model.init_params(seed)


def deploy(registry, name, model, params, cfg):
    """Register the model behind ``ModelRegistry.register_generative`` with
    the configuration's deployment (buckets and the cache: ``num_blocks``
    a CACHE layer; the engine sizes the slabs from the model's
    ``cache_passes``)."""
    dep = cfg["deployment"]
    return registry.register_generative(
        name, model, params=params,
        prefill_buckets=dep["prefill_buckets"],
        decode_buckets=dep["decode_buckets"],
        block_size=dep["block_size"], num_blocks=dep["num_blocks"],
        kv_dtype=dep["kv_dtype"])


# ----------------------------------------------------------------------
# plain reference
# ----------------------------------------------------------------------

# ours -> the published name, in the order ``layer`` takes them
_NAMES = (("attn_norm", "input_layernorm.weight"),
          ("wq", "self_attn.q_proj.weight"),
          ("wk", "self_attn.k_proj.weight"),
          ("wv", "self_attn.v_proj.weight"),
          ("wo", "self_attn.o_proj.weight"),
          ("attn_out_norm", "input_layernorm_2.weight"),
          ("ffn_norm", "post_attention_layernorm.weight"),
          ("w_gate", "mlp.gate_proj.weight"),
          ("w_up", "mlp.up_proj.weight"),
          ("w_down", "mlp.down_proj.weight"),
          ("ffn_out_norm", "post_attention_layernorm_2.weight"))


def reference_params(params, cfg):
    """The served arrays THEMSELVES under the published names (no copy is
    made on the device); see the module's note on their layout."""
    out = {"model.embed_tokens.weight": params["embed"],
           "model.norm.weight": params["norm_f"],
           "model.early_exit_gate.weight": params["gate_w"],
           "model.early_exit_gate.bias": params["gate_b"],
           "lm_head.weight": params["head"]}
    for i in range(cfg["num_hidden_layers"]):
        for ours, theirs in _NAMES:
            out["model.layers.%d.%s" % (i, theirs)] = \
                params["h%d_%s" % (i, ours)]
    return out


# the storage precision next below the configuration's bfloat16.  A
# reference asked for at this "precision" rounds every matmul weight to
# float8_e4m3fn as a plain cast would (three mantissa bits; below 2^-6 the
# format's subnormal step of 2^-9; no scale), in float32 arithmetic
# throughout.  It is the CONTROL of the check's precision: held against it
# the program has to come out as not correct
CONTROL_PRECISION = "float8_e4m3fn"
# the CONTROL of the check's sight of the loop: float32 arithmetic
# throughout with one pass fewer than the configuration states
CONTROL_ONE_PASS_FEWER = "ut3"


def exit_distribution(gates):
    """``[p_1, .., p_T]`` from the gates ``[g_1, .., g_T]``: ``p_t = g_t
    prod_{s<t}(1 - g_s)``, the last pass taking what is left."""
    survive, out = gates[0] * 0.0 + 1.0, []
    for g in gates[:-1]:
        out.append(g * survive)
        survive = survive * (1.0 - g)
    return out + [survive]


def _reference(cfg, control=False, passes=None):
    """``forward(p, tokens, rows=None)`` of the plain reference over one
    sequence: tokens (t,) -> (logits (t, vocab), tau (t,), p (t,
    passes)); a list given as ``rows`` receives the ``(k, v)`` (t, K, hd)
    that each pass of each layer attended over, k rotated, in the
    published cache order ``pass * L + layer``.  ``control`` rounds every
    matmul weight to float8_e4m3fn first; ``passes`` overrides
    total_ut_steps."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    group = heads // kv_heads
    passes = int(passes or cfg["total_ut_steps"])
    threshold = float(cfg["early_exit_threshold"])
    inv_freq = float(cfg["rope_theta"]) ** (
        -2.0 * np.arange(hd // 2, dtype=np.float64) / hd)

    def rms(x, w):
        return w.astype(f32) * x / jnp.sqrt(
            jnp.mean(x * x, axis=-1, keepdims=True) + eps)

    def stored(w):
        w = w.astype(f32)
        if control:
            # spelled out, not a pair of casts: the TPU compiler removes a
            # cast down and up again (excess precision is allowed to it)
            normal = jnp.clip(jax.lax.reduce_precision(w, 8, 3), -448.0,
                              448.0)
            w = jnp.where(jnp.abs(w) < 2.0 ** -6,
                          jnp.round(w * 2.0 ** 9) * 2.0 ** -9, normal)
        return w

    def rope(x):
        """x (t, n, hd): lanes (j, j + hd/2) turned by position * f_j."""
        angle = jnp.arange(x.shape[0], dtype=f32)[:, None] \
            * jnp.asarray(inv_freq, f32)
        cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
        x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               -1)

    def attend(q, k, v):
        """q (t, H, hd), k, v (t, K, hd), q and k rotated -> (t, H * hd),
        the mask ``j <= i`` written out, a block of query rows at a
        time."""
        t = q.shape[0]
        block = next(n for n in (_REF_Q_BLOCK, 64, 16, 4, 2, 1)
                     if t % n == 0)
        j = jnp.arange(t)

        def rows(args):
            qb, start = args                        # (block, K, group, hd)
            i = start + jnp.arange(block)
            score = jnp.einsum("qkgd,tkd->kgqt", qb, k) / math.sqrt(hd)
            score = jnp.where(j[None, :] <= i[:, None], score, -jnp.inf)
            out = jnp.einsum("kgqt,tkd->qkgd", jax.nn.softmax(score, -1), v)
            return out.reshape(block, heads * hd)

        out = jax.lax.map(rows, (
            q.reshape(t // block, block, kv_heads, group, hd),
            jnp.arange(0, t, block)))
        return out.reshape(t, heads * hd)

    @jax.jit
    def layer(x, n1, wq, wk, wv, wo, n2, n3, w_gate, w_up, w_down, n4):
        t = x.shape[0]
        h = rms(x, n1)
        q = rope((h @ stored(wq)).reshape(t, heads, hd))
        k = rope((h @ stored(wk)).reshape(t, kv_heads, hd))
        v = (h @ stored(wv)).reshape(t, kv_heads, hd)
        a = x + rms(attend(q, k, v) @ stored(wo), n2)
        h = rms(a, n3)
        y = (jax.nn.silu(h @ stored(w_gate)) * (h @ stored(w_up))) \
            @ stored(w_down)
        return a + rms(y, n4), k, v

    @jax.jit
    def leave(hs, w_g, b_g, head):
        """hs (T, t, d) -> logits of ``h_tau``, tau, p."""
        gates = [jax.nn.sigmoid(h @ stored(w_g)[:, 0] + b_g.astype(f32)[0])
                 for h in hs]
        p = jnp.stack(exit_distribution(gates))             # (T, t)
        reached = jnp.cumsum(p, axis=0) >= threshold
        reached = reached.at[-1].set(True)
        tau = jnp.argmax(reached, axis=0)                   # from 0
        h_tau = jnp.take_along_axis(hs, tau[None, :, None], axis=0)[0]
        return h_tau @ stored(head), tau + 1, p.T

    def forward(p, tokens, rows=None):
        x = p["model.embed_tokens.weight"][tokens].astype(f32)
        hs = []
        for _t in range(passes):
            for i in range(cfg["num_hidden_layers"]):
                pre = "model.layers.%d." % i
                x, k, v = layer(x, *(p[pre + theirs]
                                     for _ours, theirs in _NAMES))
                if rows is not None:
                    rows.append((k, v))
            x = rms(x, p["model.norm.weight"])
            hs.append(x)
        return leave(jnp.stack(hs), p["model.early_exit_gate.weight"],
                     p["model.early_exit_gate.bias"], p["lm_head.weight"])

    return forward


def _arithmetic(precision):
    """(JAX's matmul precision, the float8 control, the passes override)
    of a reference's name."""
    control = precision == CONTROL_PRECISION
    fewer = precision == CONTROL_ONE_PASS_FEWER
    return ("highest" if control or fewer else precision), control, fewer


def make_exit_reference(cfg, precision="highest"):
    """``forward(ref_params, tokens (t,), rows=None) -> (logits (t,
    vocab), tau (t,) from 1, p (t, passes))`` of one sequence: what
    ``make_reference`` keeps the logits of.  A list given as ``rows``
    receives every cache layer's ``(k, v)`` (``_reference``)."""
    import jax
    arithmetic, control, fewer = _arithmetic(precision)
    forward = _reference(cfg, control,
                         cfg["total_ut_steps"] - 1 if fewer else None)

    def one(ref_params, tokens, rows=None):
        with jax.default_matmul_precision(arithmetic):
            return forward(ref_params, tokens, rows)
    return one


def make_reference(cfg, precision="highest"):
    """``logits(ref_params, tokens)``: the float32 forward of the equations
    above; ``tokens`` (batch, t) int -> logits (batch, t, vocab) float32,
    left on the device.  ``precision`` is JAX's matmul precision:
    "highest" is float32 arithmetic throughout, "bfloat16" rounds the
    operands of every matmul to bfloat16 once and accumulates in float32;
    ``CONTROL_PRECISION`` is float32 arithmetic over weights rounded to
    float8_e4m3fn, and ``CONTROL_ONE_PASS_FEWER`` float32 arithmetic with
    ``total_ut_steps - 1`` passes."""
    import jax.numpy as jnp
    forward = make_exit_reference(cfg, precision)

    def logits(ref_params, tokens):
        tokens = jnp.asarray(tokens, jnp.int32)
        return jnp.stack([forward(ref_params, row)[0] for row in tokens])
    return logits


# ----------------------------------------------------------------------
# shape functions
# ----------------------------------------------------------------------

def cache_layers(cfg):
    """Cache entries a token keeps: one a pass a layer."""
    return cfg["total_ut_steps"] * cfg["num_hidden_layers"]


def kv_bytes_per_token(cfg):
    """Bytes of K and V one token holds in the cache over all its
    ``total_ut_steps x num_hidden_layers`` cache layers."""
    import jax.numpy as jnp
    width = jnp.dtype(cfg["deployment"]["kv_dtype"]).itemsize
    return cache_layers(cfg) * 2 * cfg["num_key_value_heads"] \
        * cfg["head_dim"] * width


def paged_attention_cost(cfg, context_tokens):
    """(FLOPs, HBM bytes) decode-step attention has to do over
    ``context_tokens`` live context tokens in total (summed over the slots
    of every step counted): every cache layer (a kernel call each, T x L a
    step) scores a token over head_dim lanes a query head and weighs
    head_dim lanes of it, 4 FLOPs a lane, and reads its K and V once.  The
    query and output rows are 1/context of that and left out."""
    flops = 4 * cache_layers(cfg) * cfg["num_attention_heads"] \
        * cfg["head_dim"] * context_tokens
    return flops, kv_bytes_per_token(cfg) * context_tokens


def matmul_params(cfg):
    """Weights a token's matmuls pass through in ONE layer: the four
    attention projections and the three of the SwiGLU."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, k = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return 2 * d * h * hd + 2 * d * k * hd + 3 * d * cfg["intermediate_size"]


def loop_weight_bytes(cfg):
    """Bytes of layer weights ONE pass through the stack has to read, as
    stored: every layer's matmul weights and its four norms (2 B a
    parameter in bfloat16).  A decode step reads them ``total_ut_steps``
    times whatever its batch."""
    import jax.numpy as jnp
    width = jnp.dtype(cfg["serving_dtype"]).itemsize
    return cfg["num_hidden_layers"] * width * (
        matmul_params(cfg) + 4 * cfg["hidden_size"])


def served_flops(cfg, decode_tokens, decode_context_tokens, prompt_lens):
    """FLOPs the model needs for what a window served: ``decode_tokens``
    decode steps' tokens over ``decode_context_tokens`` of live context in
    total, and one prefill for each of ``prompt_lens``.  A token's matmuls
    are two FLOPs a weight of every layer in every pass (``T * L * 2 * (4
    d^2 + 3 d I)``); attention 4 FLOPs a lane a visible pair a cache layer
    (``4 * T * L * d`` a context token in decode, ``m (m + 1) / 2`` pairs
    a prompt of ``m``); the head ``2 d V`` and the gate ``2 d T`` once an
    emitted token, the only position whose logits are needed.  Padding and
    logits of other positions do not count."""
    layers = cache_layers(cfg)
    d = cfg["hidden_size"]
    per_token = layers * 2 * matmul_params(cfg)
    pair = 4 * layers * cfg["num_attention_heads"] * cfg["head_dim"]
    leave = 2 * d * cfg["vocab_size"] + 2 * d * cfg["total_ut_steps"]
    causal_pairs = sum(m * (m + 1) // 2 for m in prompt_lens)
    return ((decode_tokens + sum(prompt_lens)) * per_token
            + (decode_tokens + len(prompt_lens)) * leave
            + pair * (decode_context_tokens + causal_pairs))
