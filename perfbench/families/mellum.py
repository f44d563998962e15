"""Mellum2-12B-A2.5B-Instruct (``model_type mellum``, JetBrains' code
model), served as one pipeline stage of a four-chip deployment: how the
benchmark deploys it through the program's generative-serving path, its
plain float32 reference, and the shape functions of what a window served.

The equations (from the published ``config.json`` alone; d = hidden_size,
H = num_attention_heads, K = num_key_value_heads, hd = head_dim, no bias
anywhere):

- Block, every layer alike (``mlp_layer_types`` is "sparse" throughout;
  ``intermediate_size`` is used by no layer): ``h = x + Attn_i(RMSNorm(x))``,
  ``y = h + MoE(RMSNorm(h))``, ``RMSNorm(x) = w * x / sqrt(mean(x^2) +
  eps)``; a final RMSNorm, then an untied head.
- Attention: ``q = x W_q`` (d -> H x hd), ``k = x W_k``, ``v = x W_v``
  (d -> K x hd); ``q, k = RoPE_i(., position)`` over all hd lanes, pairs
  ``(j, j + hd/2)`` (``rotate_half``); query head ``h`` reads K/V head ``h
  // (H / K)``; ``score = q . k / sqrt(hd)``; key ``j`` is visible to
  query ``i`` iff ``j <= i``, and on a ``sliding_attention`` layer also ``i
  - j < sliding_window`` (the current token and the ``sliding_window - 1``
  before it); softmax; ``out = concat_heads(p v) W_o``.
- RoPE, ``f_j = theta^(-2j/hd)``, by ``rope_parameters[layer type]``.
  ``rope_type`` default: ``inv_freq_j = f_j``, cos and sin unscaled.  YaRN:
  ``corr(b) = hd * ln(original / (2 pi b)) / (2 ln theta)``, ``low =
  floor(corr(beta_fast))``, ``high = ceil(corr(beta_slow))``, ``ramp_j =
  clip((j - low) / (high - low), 0, 1)``, ``inv_freq_j = (f_j / factor) *
  ramp_j + f_j * (1 - ramp_j)``; cos and sin are both multiplied by
  ``attention_factor``, so a score carries its square.
- Expert layer: ``p = softmax(float32(x) W_g)`` over all experts; ``chosen
  = top_k(p)``; ``w = p[chosen] / sum(p[chosen])`` (``norm_topk_prob``);
  ``MoE(x) = sum_i w_i Expert_i(x)``, ``Expert(x) = W_down(silu(W_gate x) *
  (W_up x))``.  No shared expert, no selection bias, no scaling factor.

The share: the first ``num_hidden_layers`` entries of ``layer_types`` (two
whole periods of three window layers and a full one), every expert of each,
and rows 0 .. ``vocab_size`` - 1 of the published vocabulary: the logits
are over the rows held.

Departures from the published code, each of them in the reference too:

- matmul weights are stored (in, out), the transpose of a torch
  ``Linear.weight``, and a layer's experts as ONE stacked array under
  ``...mlp.experts.<proj>.weight`` (index e is expert e, the published
  ``...mlp.experts.<e>.<proj>.weight``);
- no q/k norm (the config names none), no MTP head (the config has no key
  for one), bfloat16 serving (``assumed`` in the configuration's file).

The reference shares no code with ``mxnet_tpu``: no cache, no kernel, the
mask written out, every expert a plain gather of the tokens that chose it
(or a dense pass where one expert was chosen by more than the gather
holds).  It works a layer and a query block at a time, so that what it adds
to the device beside the served bfloat16 weights (which it reads, never
copies) is one sequence's activations and its float32 logits.
"""
import math

import numpy as np

KIND = "serve"

# query rows of attention scores the reference computes at a time
_REF_Q_BLOCK = 128
# an expert of the reference gathers at most 1 / _REF_SHARE of the tokens
# (twice an even router's load); a layer in which one expert was chosen by
# more runs every expert over every token
_REF_SHARE = 4

SLIDING, FULL = "sliding_attention", "full_attention"


def layer_types(cfg):
    """The kind of each layer held here: the first ``num_hidden_layers`` of
    the published list."""
    return list(cfg["layer_types"][:cfg["num_hidden_layers"]])


def _spec(cfg):
    """``WindowMoEDecoder`` at the configuration's widths and share."""
    from perfbench.harness.spec import SpecError
    try:
        from mxnet_tpu.serving.decode import WindowMoEDecoder
    except ImportError as e:    # a program from before the model spec
        raise SpecError("this program cannot run the configuration: %s"
                        % e) from None
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "sliding_window", "rope_parameters",
            "moe_intermediate_size", "num_experts", "num_experts_per_tok",
            "norm_topk_prob", "rms_norm_eps", "vocab_size")
    return WindowMoEDecoder(
        layer_types=layer_types(cfg), max_seq=cfg["max_position_embeddings"],
        dtype=cfg["serving_dtype"], **{k: cfg[k] for k in keys})


# what the program's router chose in the forward the check judged LAST:
# "tokens" (batch, t) and "routing", one (batch, t, top_k) array a layer.
# The check runs the program over a chunk of streams and then each
# reference over the same chunk, so the last forward is the one a
# reference is asked about
_JUDGED = {}


class _Checked:
    """The program's spec as the check sees it: every attribute is the
    spec's own, and ``full_logits``, the forward the check judges, also
    keeps which experts its router chose (the program returns them beside
    the logits, from the same computation), for the reference that breaks
    its near-ties their way (``make_reference``)."""

    def __init__(self, model):
        self.spec = model

    def __getattr__(self, name):
        return getattr(self.spec, name)

    def full_logits(self, params, tokens):
        import jax
        logits, routing = self.spec.full_logits(params, tokens,
                                                with_routing=True)
        jax.debug.callback(_keep_judged, tokens, routing)
        return logits


def _keep_judged(tokens, routing):
    _JUDGED["tokens"] = np.asarray(tokens)
    _JUDGED["routing"] = [np.asarray(r) for r in routing]


def _judged_routing(tokens):
    """The program's choices over ``tokens`` (batch, t), for each row one
    (t, top_k) array a layer."""
    import jax
    jax.effects_barrier()               # the judged forward's callback
    if not np.array_equal(_JUDGED.get("tokens"), np.asarray(tokens)):
        raise ValueError(
            "a %r reference follows the forward of the program that was "
            "judged last, and that was not over these tokens: run the "
            "model of build_model() over them first" % SERVED_TIES)
    return [[layer[j] for layer in _JUDGED["routing"]]
            for j in range(len(tokens))]


def build_model(cfg, seed):
    """(model, params): the spec (as the check sees it) and its weights,
    drawn from the seed by the model's own jitted initialiser, on the
    device."""
    model = _spec(cfg)
    return _Checked(model), model.init_params(seed)


def deploy(registry, name, model, params, cfg):
    """Register the model behind ``ModelRegistry.register_generative`` with
    the configuration's deployment (buckets and both pools of the cache)."""
    dep = cfg["deployment"]
    return registry.register_generative(
        name, getattr(model, "spec", model), params=params,
        prefill_buckets=dep["prefill_buckets"],
        decode_buckets=dep["decode_buckets"],
        block_size=dep["block_size"], num_blocks=dep["num_blocks"],
        window_blocks=dep["window_blocks"], kv_dtype=dep["kv_dtype"])


# ----------------------------------------------------------------------
# plain reference
# ----------------------------------------------------------------------

_NAMES = {"attn_norm": "input_layernorm.weight",
          "wq": "self_attn.q_proj.weight",
          "wk": "self_attn.k_proj.weight",
          "wv": "self_attn.v_proj.weight",
          "wo": "self_attn.o_proj.weight",
          "ffn_norm": "post_attention_layernorm.weight",
          "router": "mlp.gate.weight",
          "experts_gate": "mlp.experts.gate_proj.weight",
          "experts_up": "mlp.experts.up_proj.weight",
          "experts_down": "mlp.experts.down_proj.weight"}


def reference_params(params, cfg):
    """The served arrays THEMSELVES under the published names (no copy is
    made on the device); see the module's note on their layout."""
    out = {"model.embed_tokens.weight": params["embed"],
           "model.norm.weight": params["norm_f"],
           "lm_head.weight": params["head"]}
    for i in range(cfg["num_hidden_layers"]):
        for ours, theirs in _NAMES.items():
            out["model.layers.%d.%s" % (i, theirs)] = \
                params["h%d_%s" % (i, ours)]
    return out


def yarn_parameters(cfg):
    """``(low, high)`` of the YaRN ramp of the full-attention layers, by
    the formula above."""
    rp, dim = cfg["rope_parameters"][FULL], cfg["head_dim"]

    def corr(b):
        return dim * math.log(rp["original_max_position_embeddings"]
                              / (2 * math.pi * b)) \
            / (2 * math.log(rp["rope_theta"]))
    return (max(math.floor(corr(rp["beta_fast"])), 0),
            min(math.ceil(corr(rp["beta_slow"])), dim - 1))


def rope_table(cfg, kind):
    """``(inv_freq (hd/2,) float64, factor)`` of one layer type: the pairs'
    inverse frequencies and what cos and sin are multiplied by."""
    rp, dim = cfg["rope_parameters"][kind], cfg["head_dim"]
    j = np.arange(dim // 2, dtype=np.float64)
    f = float(rp["rope_theta"]) ** (-2.0 * j / dim)
    if rp.get("rope_type", "default") != "yarn":
        return f, 1.0
    low, high = yarn_parameters(cfg)
    ramp = np.clip((j - low) / max(high - low, 0.001), 0.0, 1.0)
    return (f / rp["factor"]) * ramp + f * (1.0 - ramp), \
        float(rp["attention_factor"])


# the storage precision next below the configuration's bfloat16.  A
# reference asked for at this "precision" rounds every matmul weight to
# float8_e4m3fn as a plain cast would (three mantissa bits; below 2^-6 the
# format's subnormal step of 2^-9; no scale), in float32 arithmetic
# throughout.  It is the CONTROL of the check's precision: held against it
# the program has to come out as not correct
CONTROL_PRECISION = "float8_e4m3fn"
# the CONTROL of the check's sight of the window: float32 arithmetic
# throughout with every layer's mask the causal one alone
CONTROL_NO_WINDOW = "no_window"


def _reference(cfg, control=False, windowed=True):
    """``(layer, forward)`` of the plain reference: ``layer(p, i, x)`` is
    block ``i`` on ``x`` (t, d) float32, ``forward(p, tokens)`` the logits
    (t, vocab) of one sequence.  ``control`` rounds every matmul weight to
    float8_e4m3fn first; ``windowed`` False takes the window off."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    group = heads // kv_heads
    top_k, experts = cfg["num_experts_per_tok"], cfg["num_experts"]
    window = cfg["sliding_window"]
    tie_eps = float(cfg.get("check", {}).get("tie_eps", 0.0))
    kinds = layer_types(cfg)
    tables = {kind: rope_table(cfg, kind) for kind in set(kinds)}

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

    @jax.jit
    def mm(x, w):
        return x @ stored(w)

    def rope(x, inv_freq, factor):
        """x (t, n, hd): lanes (j, j + hd/2) turned by position *
        inv_freq_j, cos and sin times ``factor``."""
        angle = jnp.arange(x.shape[0], dtype=f32)[:, None] \
            * jnp.asarray(inv_freq, f32)
        cos = (jnp.cos(angle) * factor)[:, None, :]
        sin = (jnp.sin(angle) * factor)[:, None, :]
        x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               -1)

    def attend(q, k, v, kind):
        """q (t, H, hd), k, v (t, K, hd), not yet rotated -> (t, H * hd):
        the mask is ``j <= i``, and on a sliding layer ``i - j < window``."""
        inv_freq, factor = tables[kind]
        sliding = windowed and kind == SLIDING

        @jax.jit
        def run(q, k, v):
            t = q.shape[0]
            q, k = rope(q, inv_freq, factor), rope(k, inv_freq, factor)
            block = next(n for n in (_REF_Q_BLOCK, 64, 16, 4, 2, 1)
                         if t % n == 0)
            j = jnp.arange(t)

            def rows(args):
                qb, start = args                    # (block, K, group, hd)
                i = start + jnp.arange(block)
                score = jnp.einsum("qkgd,tkd->kgqt", qb, k) / math.sqrt(hd)
                mask = j[None, :] <= i[:, None]
                if sliding:
                    mask = mask & (i[:, None] - j[None, :] < window)
                score = jnp.where(mask, score, -jnp.inf)
                out = jnp.einsum("kgqt,tkd->qkgd",
                                 jax.nn.softmax(score, -1), v)
                return out.reshape(block, heads * hd)

            out = jax.lax.map(rows, (
                q.reshape(t // block, block, kv_heads, group, hd),
                jnp.arange(0, t, block)))
            return out.reshape(t, heads * hd)
        return run(q, k, v)

    @jax.jit
    def route(x, w_g, served=None):
        """Per token, the weight with which each expert enters (t,
        experts), zero where it was not chosen, and a tally of the ties.
        ``served`` (t, top_k): the experts the PROGRAM chose.  Each of them
        gets ``tie_eps`` added to THIS router's own probability before its
        own top_k: where its probabilities put no expert the program left
        out more than ``tie_eps`` above one that it chose, the choice is
        the program's (the near-tie is broken its way); a served expert
        that lies further down is not followed, and this router's own
        choice stands in its place."""
        prob = jax.nn.softmax(jnp.matmul(
            x, stored(w_g), precision=jax.lax.Precision.HIGHEST), -1)
        _, chosen = jax.lax.top_k(prob, top_k)
        tally = None
        if served is not None:
            own = chosen
            is_served = (served[:, :, None] == jnp.arange(experts)).any(1)
            _, chosen = jax.lax.top_k(prob + tie_eps * is_served, top_k)
            # by how much this router's own probabilities put an expert
            # the program left out above one that it chose
            short = jnp.maximum(
                jnp.max(jnp.where(is_served, -jnp.inf, prob), -1)
                - jnp.min(jnp.take_along_axis(prob, served, -1), -1), 0.0)
            followed = (jnp.sort(chosen) == jnp.sort(served)).all(-1)
            tally = {
                "moved": jnp.sum((jnp.sort(chosen) != jnp.sort(own)).any(-1)),
                "not_followed": jnp.sum(~followed),
                "shortfall": jnp.max(short)}
        w = jnp.take_along_axis(prob, chosen, -1)
        if cfg["norm_topk_prob"]:
            w = w / w.sum(-1, keepdims=True)
        return jnp.sum(w[:, :, None]
                       * (chosen[:, :, None] == jnp.arange(experts)), 1), tally

    def expert(x, w_gate, w_up, w_down):
        return (jax.nn.silu(x @ stored(w_gate)) * (x @ stored(w_up))) \
            @ stored(w_down)

    @jax.jit
    def dense_expert(h, weight, w_gate, w_up, w_down):
        """One expert over every token, weighted (zero where not chosen)."""
        return weight[:, None] * expert(h, w_gate, w_up, w_down)

    @jax.jit
    def gathered_expert(h, weight, w_gate, w_up, w_down):
        """One expert over the tokens that chose it alone: a plain gather of
        at most t / _REF_SHARE rows, their outputs added back in place."""
        t = h.shape[0]
        rows = jnp.nonzero(weight > 0, size=max(t // _REF_SHARE, 1),
                           fill_value=t)[0]
        out = jnp.take(weight, rows, mode="fill", fill_value=0.0)[:, None] \
            * expert(jnp.take(h, rows, axis=0, mode="fill", fill_value=0.0),
                     w_gate, w_up, w_down)
        return jnp.zeros_like(h).at[rows].add(out, mode="drop")

    def layer(p, i, x, served=None, tallies=None):
        pre = "model.layers.%d." % i
        t = x.shape[0]
        h = rms(x, p[pre + "input_layernorm.weight"])
        q = mm(h, p[pre + "self_attn.q_proj.weight"]).reshape(t, heads, hd)
        k = mm(h, p[pre + "self_attn.k_proj.weight"]).reshape(t, kv_heads, hd)
        v = mm(h, p[pre + "self_attn.v_proj.weight"]).reshape(t, kv_heads, hd)
        x = x + mm(attend(q, k, v, kinds[i]),
                   p[pre + "self_attn.o_proj.weight"])
        h = rms(x, p[pre + "post_attention_layernorm.weight"])
        weight, tally = route(h, p[pre + "mlp.gate.weight"], served)
        if tally is not None:
            tallies.append(tally)
        stacks = [p[pre + "mlp.experts.%s_proj.weight" % n]
                  for n in ("gate", "up", "down")]
        # the gather holds a quarter of the tokens; an expert that more of
        # them chose (a seed's lopsided router) takes the dense pass
        busiest = int(jnp.max(jnp.sum(weight > 0, axis=0)))
        one = gathered_expert if busiest <= max(t // _REF_SHARE, 1) \
            else dense_expert
        y = jnp.zeros_like(x)
        for e in range(experts):
            y = y + one(h, weight[:, e], *(w[e] for w in stacks))
        return x + y

    def forward(p, tokens, served=None):
        """``served``: the program's choice for each layer in turn, or None
        for the reference's own.  Returns the logits and each layer's
        tally of ties (``route``)."""
        x = p["model.embed_tokens.weight"][tokens].astype(f32)
        tallies = []
        for i in range(cfg["num_hidden_layers"]):
            x = layer(p, i, x, None if served is None else served[i],
                      tallies)
        return mm(rms(x, p["model.norm.weight"]),
                  p["lm_head.weight"]), tallies

    return layer, forward


SERVED_TIES = "served_ties."


def make_reference(cfg, precision="highest"):
    """``logits(ref_params, tokens)``: the float32 forward of the equations
    above over the layers, experts and vocabulary rows held; ``tokens``
    (batch, t) int -> logits (batch, t, vocab) float32, left on the device.
    ``precision`` is JAX's matmul precision: "highest" is float32
    arithmetic throughout, "bfloat16" rounds the operands of every matmul
    to bfloat16 once and accumulates in float32; ``CONTROL_PRECISION`` is
    float32 arithmetic over weights rounded to float8_e4m3fn, and
    ``CONTROL_NO_WINDOW`` float32 arithmetic with every layer full.

    Prefixed ``served_ties.`` the reference breaks its router's NEAR-TIES
    the way the program did (``families/kimi_k2.py`` has the device's
    history): top-8 of 64 after a softmax is discontinuous, the program's
    bfloat16 activations move a probability a little, and a token that
    swaps one expert for another lies an expert's output from a reference
    that keeps its own choice.  The router stays the reference's own: it
    scores all experts itself, and an expert the program chose counts only
    where the reference's own probability puts it within ``check.tie_eps``
    of its own top 8; the weights are its own probabilities of the experts
    so chosen.  A program whose router is wrong chooses experts that lie
    further down than that, is not followed, and is then a swap away from
    the reference on every such token.  The program's choices are those of
    the forward the check judged last (``_Checked``); each sequence's tally
    of ties goes to standard error."""
    import sys

    import jax
    import jax.numpy as jnp
    ties = precision.startswith(SERVED_TIES)
    precision = precision[len(SERVED_TIES):] if ties else precision
    control = precision == CONTROL_PRECISION
    windowed = precision != CONTROL_NO_WINDOW
    _layer, forward = _reference(cfg, control, windowed)
    arithmetic = precision if windowed and not control else "highest"

    def logits(ref_params, tokens):
        tokens = jnp.asarray(tokens, jnp.int32)
        routing = _judged_routing(tokens) if ties else [None] * len(tokens)
        out = []
        with jax.default_matmul_precision(arithmetic):
            for row, served in zip(tokens, routing):
                row_logits, tallies = forward(ref_params, row, served)
                out.append(row_logits)
                for n, tally in enumerate(jax.device_get(tallies)):
                    print("router_ties %s%s layer %d tokens %d %s"
                          % (SERVED_TIES, precision, n, len(row), " ".join(
                              "%s %.6g" % kv for kv in sorted(tally.items()))),
                          file=sys.stderr)
        return jnp.stack(out)
    return logits


def reference_layer(cfg, precision="highest"):
    """``layer(ref_params, i, x)``: block ``i`` of the reference alone, x
    (t, d) float32 -> (t, d)."""
    import jax
    layer, _forward = _reference(cfg)

    def one(ref_params, i, x):
        with jax.default_matmul_precision(precision):
            return layer(ref_params, i, x)
    return one


# ----------------------------------------------------------------------
# shape functions
# ----------------------------------------------------------------------

def _layers_by_kind(cfg):
    kinds = layer_types(cfg)
    return kinds.count(FULL), kinds.count(SLIDING)


def kv_bytes_per_token(cfg):
    """Bytes one token holds in ONE layer's K and V as stored: 2 x
    num_key_value_heads x head_dim values.  (A layer, not all of them: how
    many layers hold a token depends on how far back it lies.)"""
    import jax.numpy as jnp
    width = jnp.dtype(cfg["deployment"]["kv_dtype"]).itemsize
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * width


def attention_cost(cfg, rows_full, rows_window):
    """(FLOPs, HBM bytes) the decode steps' attention has to do where ONE
    full layer had ``rows_full`` cache rows to read and ONE window layer
    ``rows_window`` (the sums of the programs' ``kv_rows_full`` /
    ``kv_rows_window`` over the steps counted): a row is read once a layer
    (K and V, ``kv_bytes_per_token``) and every query head scores it over
    head_dim lanes and weighs head_dim lanes of it.  The query and output
    rows are 1/context of that and left out."""
    n_full, n_window = _layers_by_kind(cfg)
    rows = n_full * rows_full + n_window * rows_window
    flops = 2 * cfg["num_attention_heads"] * 2 * cfg["head_dim"]
    return flops * rows, kv_bytes_per_token(cfg) * rows


def matmul_params(cfg):
    """Weights a token's matmuls pass through in a layer, by part."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, k = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return {"attention": 2 * d * h * hd + 2 * d * k * hd,
            "router": d * cfg["num_experts"],
            "expert": 3 * d * cfg["moe_intermediate_size"]}


def window_pairs(m, window):
    """Visible (query, key) pairs of a prompt of ``m`` tokens on a window
    layer: ``sum_i min(i + 1, window)``."""
    w = min(m, window)
    return w * (w + 1) // 2 + (m - w) * window


def served_flops(cfg, decode_tokens, decode_context_tokens, prompt_lens):
    """FLOPs the model needs for what a window served on THIS chip:
    ``decode_tokens`` decode steps' tokens over ``decode_context_tokens`` of
    live context in total, and one prefill for each of ``prompt_lens``.  A
    token's pass through a layer is two FLOPs a weight of attention's four
    projections, of the router and of ``num_experts_per_tok`` experts; the
    head once a token emitted; attention 2 x H x 2 x head_dim a visible
    pair: in prefill ``m (m + 1) / 2`` pairs on a full layer and
    ``window_pairs(m)`` on a window layer, in decode the context on a full
    layer and on a window layer ``min(decode_context_tokens, decode_tokens
    x sliding_window)``.  The harness hands the family a SUM of contexts
    and not the contexts, so that last term counts a token whose context is
    under the window at the others' excess: an over-count of the window
    layers' decode attention that stays under 1% of the whole at this mix
    (decode attention is some 3% of a window's FLOPs, the window layers'
    share of it a quarter).  Padding and logits of other positions do not
    count."""
    n = matmul_params(cfg)
    layers = cfg["num_hidden_layers"]
    n_full, n_window = _layers_by_kind(cfg)
    window = cfg["sliding_window"]
    per_token = 2 * layers * (n["attention"] + n["router"]
                              + cfg["num_experts_per_tok"] * n["expert"])
    head = 2 * cfg["hidden_size"] * cfg["vocab_size"]
    pair = 2 * cfg["num_attention_heads"] * 2 * cfg["head_dim"]
    prefill_pairs = sum(n_full * m * (m + 1) // 2
                        + n_window * window_pairs(m, window)
                        for m in prompt_lens)
    decode_pairs = n_full * decode_context_tokens + n_window * min(
        decode_context_tokens, decode_tokens * window)
    return ((decode_tokens + sum(prompt_lens)) * per_token
            + (decode_tokens + len(prompt_lens)) * head
            + pair * (prefill_pairs + decode_pairs))
