"""Kimi-K2-Instruct (``model_type kimi_k2``, the DeepSeek-V3 block),
served as ONE chip of an expert-parallel deployment: how the benchmark
deploys it through the program's generative-serving path, its plain
float32 reference, and the shape functions of what a window served.

The equations (``modeling_deepseek.py`` with this configuration's
numbers; d = hidden_size, H = heads):

- Block: ``h = x + Attn(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``,
  ``RMSNorm(x) = w * x / sqrt(mean(x^2) + eps)``; a final RMSNorm, then an
  untied head.  The first ``first_k_dense_replace`` layers' FFN is a dense
  SwiGLU, ``W_down(silu(W_gate x) * (W_up x))``; the rest are expert
  layers.
- Attention: ``c_q = RMSNorm(x W_qa)``; ``[q_nope | q_rope] = c_q W_qb``
  per head; ``[c_kv | k_rope] = x W_kva`` (``k_rope`` ONE vector shared by
  all heads); ``c_kv = RMSNorm(c_kv)``; ``[k_nope | v] = c_kv W_kvb`` per
  head; ``q_rope, k_rope = RoPE(., position)``; ``score = (q_nope . k_nope
  + q_rope . k_rope) * s``, causal softmax, ``out = concat_heads(p v)
  W_o``; ``s = (nope + rope)^-0.5 * m^2``, ``m = 0.1 * mscale_all_dim *
  ln(factor) + 1``.
- RoPE over the ``rope``-wide slice with YaRN: ``f_j = theta^(-2j/rope)``;
  ``corr(b) = rope * ln(original / (2 pi b)) / (2 ln theta)``, ``low =
  floor(corr(beta_fast))``, ``high = ceil(corr(beta_slow))``, ``ramp_j =
  clip((j - low) / (high - low), 0, 1)``, ``inv_freq_j = (f_j / factor)
  * ramp_j + f_j * (1 - ramp_j)``; the cos/sin factor ``mscale /
  mscale_all_dim`` ratio is 1 for this configuration.
- Expert layer: ``scores = sigmoid(float32(x) W_g)``; ``chosen =
  top_k(scores + e_score_correction_bias)`` (``n_group = topk_group = 1``:
  no group limit); ``w = scores[chosen] / (sum + 1e-20) *
  routed_scaling_factor``; ``FFN(x) = sum_i w_i Expert_i(x) + Shared(x)``,
  every expert and the shared expert a SwiGLU of ``moe_intermediate_size``.

The share: the router scores ALL ``published.n_routed_experts``; only
experts ``first .. first + n_routed_experts`` (the configuration's count
is the number HELD) are summed, the shared expert once, and the logits are
over the ``vocab_size`` rows held.  What the absent experts would add is
left out, here as in the program.

Departures from the published code, each of them in the reference too:

- matmul weights are stored (in, out), the transpose of a torch
  ``Linear.weight``, and the held experts of a layer as ONE stacked array
  under ``...mlp.experts.<proj>.weight`` (index j is expert ``first + j``);
- the published code first de-interleaves the rotary slice and then
  rotates halves; rotating the adjacent pairs (2j, 2j+1) in place gives the
  same vector up to ONE permutation common to q and k, so every score is
  the same;
- bfloat16 storage where the release is block-fp8 (``assumed``).

The reference shares no code with ``mxnet_tpu``: the expanded attention
form only, no cache, no kernel, a dense pass of every held expert over all
tokens.  It works a layer, a query block and an expert at a time, so that
what it adds to the device beside the served bfloat16 weights (which it
reads, never copies) stays under a gigabyte or so.
"""
import math

import numpy as np

KIND = "serve"

# query rows of attention scores the reference computes at a time
_REF_Q_BLOCK = 128
# tokens a feed-forward of the reference takes at a time
_REF_ROWS = 2048


def _first_expert(cfg):
    return cfg["deployment"]["expert_rank"] * cfg["n_routed_experts"]


def _spec(cfg):
    """``LatentMoEDecoder`` at the configuration's widths and share."""
    from perfbench.harness.spec import SpecError
    try:
        from mxnet_tpu.serving.decode import LatentMoEDecoder
    except ImportError as e:    # a program from before the model spec
        raise SpecError("this program cannot run the configuration: %s"
                        % e) from None
    keys = ("hidden_size", "num_attention_heads", "q_lora_rank",
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "intermediate_size", "moe_intermediate_size",
            "num_experts_per_tok", "n_shared_experts",
            "first_k_dense_replace", "routed_scaling_factor", "rope_theta",
            "rope_scaling", "rms_norm_eps", "num_hidden_layers",
            "vocab_size")
    return LatentMoEDecoder(
        n_routed_experts=cfg["published"]["n_routed_experts"],
        first_expert=_first_expert(cfg), n_held=cfg["n_routed_experts"],
        max_seq=cfg["max_position_embeddings"],
        dtype=cfg["serving_dtype"], **{k: cfg[k] for k in keys})


# what the program's router chose in the forward the check judged LAST:
# "tokens" (batch, t) and "routing", one (batch, t, top_k) array an expert
# layer.  The check runs the program over a chunk of streams and then each
# reference over the same chunk, so the last forward is the one a
# reference is asked about
_JUDGED = {}


class _Checked:
    """The program's spec as the check sees it: every attribute is the
    spec's own, and ``full_logits``, the forward the check judges, also
    keeps which experts its router chose (the program returns them beside
    the logits, from the same computation: ``with_routing``), for the
    reference that breaks its near-ties their way (``make_reference``)."""

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
    (t, top_k) array an expert layer."""
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
    """(model, params): the spec (as the check sees it) and its weights
    (and the router's selection bias), drawn from the seed by the model's
    own jitted initialiser, on the device."""
    model = _spec(cfg)
    return _Checked(model), model.init_params(seed)


def deploy(registry, name, model, params, cfg):
    """Register the model behind ``ModelRegistry.register_generative`` with
    the configuration's deployment (buckets and cache)."""
    dep = cfg["deployment"]
    return registry.register_generative(
        name, getattr(model, "spec", model), params=params,
        prefill_buckets=dep["prefill_buckets"],
        decode_buckets=dep["decode_buckets"],
        block_size=dep["block_size"], num_blocks=dep["num_blocks"],
        kv_dtype=dep["kv_dtype"])


# ----------------------------------------------------------------------
# plain reference
# ----------------------------------------------------------------------

_ATTN = {"attn_norm": "input_layernorm.weight",
         "wqa": "self_attn.q_a_proj.weight",
         "q_norm": "self_attn.q_a_layernorm.weight",
         "wqb": "self_attn.q_b_proj.weight",
         "wkva": "self_attn.kv_a_proj_with_mqa.weight",
         "kv_norm": "self_attn.kv_a_layernorm.weight",
         "wkvb": "self_attn.kv_b_proj.weight",
         "wo": "self_attn.o_proj.weight",
         "ffn_norm": "post_attention_layernorm.weight"}
_DENSE = {"w_gate": "mlp.gate_proj.weight", "w_up": "mlp.up_proj.weight",
          "w_down": "mlp.down_proj.weight"}
_MOE = {"router": "mlp.gate.weight",
        "router_bias": "mlp.gate.e_score_correction_bias",
        "shared_gate": "mlp.shared_experts.gate_proj.weight",
        "shared_up": "mlp.shared_experts.up_proj.weight",
        "shared_down": "mlp.shared_experts.down_proj.weight",
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
        names = dict(_ATTN, **(_DENSE if i < cfg["first_k_dense_replace"]
                               else _MOE))
        for ours, theirs in names.items():
            out["model.layers.%d.%s" % (i, theirs)] = \
                params["h%d_%s" % (i, ours)]
    return out


def yarn_inv_freq(cfg):
    """The rotary pairs' inverse frequencies, by the formula above."""
    dim, theta = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    sc = cfg["rope_scaling"]
    j = np.arange(dim // 2, dtype=np.float64)
    f = theta ** (-2.0 * j / dim)

    def corr(b):
        return dim * math.log(sc["original_max_position_embeddings"]
                              / (2 * math.pi * b)) / (2 * math.log(theta))
    low = max(math.floor(corr(sc["beta_fast"])), 0)
    high = min(math.ceil(corr(sc["beta_slow"])), dim - 1)
    ramp = np.clip((j - low) / max(high - low, 0.001), 0.0, 1.0)
    return (f / sc["factor"]) * ramp + f * (1.0 - ramp)


def softmax_scale(cfg):
    sc = cfg["rope_scaling"]
    m = 0.1 * sc["mscale_all_dim"] * math.log(sc["factor"]) + 1.0
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 \
        * m * m


# the storage precision next below the configuration's bfloat16.  A
# reference asked for at this "precision" rounds every matmul weight to
# float8_e4m3fn as a plain cast would (three mantissa bits; below 2^-6 the
# format's subnormal step of 2^-9; no scale), in float32 arithmetic
# throughout.  It is the CONTROL of the check: held against it the program
# has to come out as not correct
CONTROL_PRECISION = "float8_e4m3fn"


def _reference(cfg, control=False):
    """``(layer, forward)`` of the plain reference: ``layer(p, i, x)`` is
    block ``i`` on ``x`` (t, d) float32, ``forward(p, tokens)`` the logits
    (t, vocab) of one sequence.  ``control`` rounds every matmul weight
    to float8_e4m3fn first."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    heads, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    v_dim, rank = cfg["v_head_dim"], cfg["kv_lora_rank"]
    top_k, held = cfg["num_experts_per_tok"], cfg["n_routed_experts"]
    first, scaling = _first_expert(cfg), cfg["routed_scaling_factor"]
    tie_eps = float(cfg.get("check", {}).get("tie_eps", 0.0))
    inv_freq = jnp.asarray(yarn_inv_freq(cfg), f32)
    s = softmax_scale(cfg)

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

    def rotate(x, pos):
        """x (t, ..., rope): pairs (2j, 2j+1) turned by pos * inv_freq_j."""
        angle = pos.astype(f32)[:, None] * inv_freq
        angle = angle.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (-1,))
        x0, x1 = x[..., 0::2], x[..., 1::2]
        out = jnp.stack([x0 * jnp.cos(angle) - x1 * jnp.sin(angle),
                         x1 * jnp.cos(angle) + x0 * jnp.sin(angle)], -1)
        return out.reshape(x.shape)

    @jax.jit
    def qkv(c_q, kva, w_qn, w_kvn):
        """The latents -> q (t, H, nope + rope), the normed c_kv and the
        rotated shared key."""
        t = c_q.shape[0]
        pos = jnp.arange(t)
        c_kv = rms(kva[:, :rank], w_kvn)
        k_rope = rotate(kva[:, rank:], pos)
        return rms(c_q, w_qn), c_kv, k_rope

    @jax.jit
    def attend(q, kv, k_rope):
        """q (t, H, nope + rope) not yet rotated; kv (t, H, nope + v);
        k_rope (t, rope) rotated -> (t, H * v), causal."""
        t = q.shape[0]
        pos = jnp.arange(t)
        q = jnp.concatenate([q[..., :nope], rotate(q[..., nope:], pos)], -1)
        k = jnp.concatenate(
            [kv[..., :nope],
             jnp.broadcast_to(k_rope[:, None, :], (t, heads, rope))], -1)
        v = kv[..., nope:]
        block = next(n for n in (_REF_Q_BLOCK, 64, 16, 4, 2, 1) if t % n == 0)

        def rows(args):
            qb, start = args
            score = jnp.einsum("qhd,khd->hqk", qb, k) * s
            mask = pos[None, :] <= (start + jnp.arange(block))[:, None]
            score = jnp.where(mask[None], score, -jnp.inf)
            return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(score, -1), v)

        out = jax.lax.map(rows, (q.reshape(t // block, block, heads, -1),
                                 jnp.arange(0, t, block)))
        return out.reshape(t, heads * v_dim)

    def swiglu(x, w_gate, w_up, w_down):
        # a matmul at a time, _REF_ROWS tokens at a time: one weight in
        # float32 and the dense layer's hidden rows of one part are the
        # most it adds
        edges = np.linspace(0, x.shape[0], -(-x.shape[0] // _REF_ROWS) + 1
                            ).astype(int)
        return jnp.concatenate([
            mm(jax.nn.silu(mm(x[a:b], w_gate)) * mm(x[a:b], w_up), w_down)
            for a, b in zip(edges[:-1], edges[1:])])

    @jax.jit
    def route(x, w_g, bias, served=None):
        """Per token, the weight with which each HELD expert enters (t,
        held), zero where it was not chosen, and a tally of the ties.
        ``served`` (t, top_k): the experts the PROGRAM chose.  Each of them
        gets ``tie_eps`` added to THIS router's own biased score before
        its own top_k: where its scores put no expert the program left
        out more than ``tie_eps`` above one that it chose, the choice is
        the program's (the near-tie is broken its way); a served expert
        that lies further down is not followed, and this router's own
        choice stands in its place."""
        scores = jax.nn.sigmoid(jnp.matmul(
            x, stored(w_g), precision=jax.lax.Precision.HIGHEST))
        biased = scores + bias
        _, chosen = jax.lax.top_k(biased, top_k)
        tally = None
        if served is not None:
            own = chosen
            is_served = (served[:, :, None]
                         == jnp.arange(biased.shape[-1])).any(1)
            _, chosen = jax.lax.top_k(biased + tie_eps * is_served, top_k)
            # by how much this router's own scores put an expert the
            # program left out above one that it chose
            short = jnp.maximum(
                jnp.max(jnp.where(is_served, -jnp.inf, biased), -1)
                - jnp.min(jnp.take_along_axis(biased, served, -1), -1), 0.0)
            followed = (jnp.sort(chosen) == jnp.sort(served)).all(-1)
            tally = {
                "moved": jnp.sum((jnp.sort(chosen) != jnp.sort(own)).any(-1)),
                "not_followed": jnp.sum(~followed),
                "shortfall": jnp.max(short)}
        w = jnp.take_along_axis(scores, chosen, -1)
        w = w / (w.sum(-1, keepdims=True) + 1e-20) * scaling
        ours = first + jnp.arange(held)
        return jnp.sum(w[:, :, None] * (chosen[:, :, None] == ours), 1), tally

    def layer(p, i, x, served=None, tallies=None):
        pre = "model.layers.%d." % i
        h = rms(x, p[pre + "input_layernorm.weight"])
        c_q, c_kv, k_rope = qkv(
            mm(h, p[pre + "self_attn.q_a_proj.weight"]),
            mm(h, p[pre + "self_attn.kv_a_proj_with_mqa.weight"]),
            p[pre + "self_attn.q_a_layernorm.weight"],
            p[pre + "self_attn.kv_a_layernorm.weight"])
        t = x.shape[0]
        q = mm(c_q, p[pre + "self_attn.q_b_proj.weight"]).reshape(
            t, heads, nope + rope)
        kv = mm(c_kv, p[pre + "self_attn.kv_b_proj.weight"]).reshape(
            t, heads, nope + v_dim)
        x = x + mm(attend(q, kv, k_rope), p[pre + "self_attn.o_proj.weight"])
        h = rms(x, p[pre + "post_attention_layernorm.weight"])
        if i < cfg["first_k_dense_replace"]:
            return x + swiglu(h, p[pre + "mlp.gate_proj.weight"],
                              p[pre + "mlp.up_proj.weight"],
                              p[pre + "mlp.down_proj.weight"])
        weight, tally = route(
            h, p[pre + "mlp.gate.weight"],
            p[pre + "mlp.gate.e_score_correction_bias"], served)
        if tally is not None:
            tallies.append(tally)
        y = swiglu(h, *(p[pre + "mlp.shared_experts.%s_proj.weight" % n]
                        for n in ("gate", "up", "down")))
        stacks = [p[pre + "mlp.experts.%s_proj.weight" % n]
                  for n in ("gate", "up", "down")]
        for j in range(held):       # every held expert over every token
            y = y + weight[:, j:j + 1] * swiglu(h, *(w[j] for w in stacks))
        return x + y

    def forward(p, tokens, served=None):
        """``served``: the program's choice for each expert layer in
        turn, or None for the reference's own.  Returns the logits and
        each expert layer's tally of ties (``route``)."""
        x = p["model.embed_tokens.weight"][tokens].astype(f32)
        dense = cfg["first_k_dense_replace"]
        tallies = []
        for i in range(cfg["num_hidden_layers"]):
            x = layer(p, i, x, None if served is None or i < dense
                      else served[i - dense], tallies)
        return mm(rms(x, p["model.norm.weight"]),
                  p["lm_head.weight"]), tallies

    return layer, forward


SERVED_TIES = "served_ties."


def make_reference(cfg, precision="highest"):
    """``logits(ref_params, tokens)``: the float32 forward of the equations
    above, given this chip's share; ``tokens`` (batch, t) int -> logits
    (batch, t, vocab) float32, left on the device.  ``precision`` is JAX's
    matmul precision: "highest" is float32 arithmetic throughout,
    "bfloat16" rounds the operands of every matmul to bfloat16 once and
    accumulates in float32; ``CONTROL_PRECISION`` is float32 arithmetic
    over weights rounded to float8_e4m3fn.

    Prefixed ``served_ties.`` the reference breaks its router's NEAR-TIES
    the way the program did.  top-8 of 384 is discontinuous: the program's
    bfloat16 activations move a score by some 1e-3, enough to choose
    otherwise than float32 ones for 4-9% of the tokens of a layer, and a
    token that gains or loses a HELD expert lies a whole expert's output
    from the reference (1.0-1.3 in the logits, where rounding is
    0.04-0.12; my chip runs, PR 28).  The router stays the reference's
    own: it scores all experts itself, and an expert the program chose
    counts only where the reference's own biased score puts it within
    ``check.tie_eps`` of its own top 8; the weights are its own scores of
    the experts so chosen.  A program whose router is wrong (another bias,
    another k, another activation) chooses experts that lie further down
    than that, is not followed, and is then a swap away from the reference
    on every such token.  The program's choices are those of the forward
    the check judged last (``_Checked``); each sequence's tally of ties
    goes to standard error."""
    import sys

    import jax
    import jax.numpy as jnp
    ties = precision.startswith(SERVED_TIES)
    precision = precision[len(SERVED_TIES):] if ties else precision
    control = precision == CONTROL_PRECISION
    _layer, forward = _reference(cfg, control)

    def logits(ref_params, tokens):
        tokens = jnp.asarray(tokens, jnp.int32)
        routing = _judged_routing(tokens) if ties else [None] * len(tokens)
        out = []
        with jax.default_matmul_precision("highest" if control
                                          else precision):
            for row, served in zip(tokens, routing):
                row_logits, tallies = forward(ref_params, row, served)
                out.append(row_logits)
                for n, tally in enumerate(jax.device_get(tallies)):
                    print("router_ties %s%s expert_layer %d tokens %d %s"
                          % (SERVED_TIES, precision, n, len(row), " ".join(
                              "%s %.6g" % kv for kv in sorted(tally.items()))),
                          file=sys.stderr)
        return jnp.stack(out)
    return logits


def reference_layer(cfg, precision="highest"):
    """``layer(ref_params, i, x)``: block ``i`` of the reference alone, x
    (t, d) float32 -> (t, d); what the shares-add-up test compares."""
    import jax
    layer, _forward = _reference(cfg)

    def one(ref_params, i, x):
        with jax.default_matmul_precision(precision):
            return layer(ref_params, i, x)
    return one


# ----------------------------------------------------------------------
# shape functions
# ----------------------------------------------------------------------

def kv_bytes_per_token(cfg):
    """Bytes one token holds in the latent cache over all layers, as
    stored: (kv_lora_rank + qk_rope_head_dim) values a layer.  The slab's
    padding to whole 128-lane tiles is not the token's."""
    import jax.numpy as jnp
    width = jnp.dtype(cfg["deployment"]["kv_dtype"]).itemsize
    return cfg["num_hidden_layers"] * width \
        * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])


def paged_attention_cost(cfg, context_tokens):
    """(FLOPs, HBM bytes) the decode step's latent attention has to do
    over ``context_tokens`` live context tokens in total (summed over the
    slots of every step counted): each live token's row is read ONCE a
    layer for all heads, every head scores it over rank + rope lanes and
    weighs its first rank lanes.  The query and output rows are 1/context
    of that and left out, as are the absorbing matmuls (they are not the
    kernel's)."""
    row = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    flops = 2 * cfg["num_attention_heads"] * (row + cfg["kv_lora_rank"]) \
        * cfg["num_hidden_layers"] * context_tokens
    return flops, kv_bytes_per_token(cfg) * context_tokens


def matmul_params(cfg):
    """Weights a token's matmuls pass through, by part: attention's five
    projections, the dense FFN, the router, one expert (the shared one has
    ``n_shared_experts`` of them)."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    attn = (d * cfg["q_lora_rank"] + cfg["q_lora_rank"] * h * qk
            + d * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
            + cfg["kv_lora_rank"] * h
            * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
            + h * cfg["v_head_dim"] * d)
    return {"attention": attn, "dense_ffn": 3 * d * cfg["intermediate_size"],
            "router": d * cfg["published"]["n_routed_experts"],
            "expert": 3 * d * cfg["moe_intermediate_size"]}


def served_flops(cfg, decode_tokens, decode_context_tokens, prompt_lens):
    """FLOPs the model needs for what a window served on THIS chip:
    ``decode_tokens`` decode steps' tokens over ``decode_context_tokens``
    of live context in total, and one prefill for each of ``prompt_lens``.
    A token's pass through a layer is two FLOPs a weight of attention's
    projections and of the dense FFN (dense layers) or of the router, the
    shared expert and its EXPECTED share of routed experts under even
    routing, ``num_experts_per_tok * held / published`` of one expert
    (expert layers); attention is ``paged_attention_cost`` a decode context
    token and 2 * H * (qk + v) a causal pair in prefill, where keys and
    values are expanded per head; the head once a token emitted.  Padding,
    logits of other positions and the absorbing matmuls do not count."""
    n = matmul_params(cfg)
    layers, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    held_share = cfg["num_experts_per_tok"] * cfg["n_routed_experts"] \
        / cfg["published"]["n_routed_experts"]
    per_token = 2 * (
        layers * n["attention"] + dense * n["dense_ffn"]
        + (layers - dense) * (n["router"] + n["expert"]
                              * (cfg["n_shared_experts"] + held_share)))
    head = 2 * cfg["hidden_size"] * cfg["vocab_size"]
    pair = 2 * cfg["num_attention_heads"] * layers * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
        + cfg["v_head_dim"])
    causal_pairs = sum(m * (m + 1) // 2 for m in prompt_lens)
    return ((decode_tokens + sum(prompt_lens)) * per_token
            + (decode_tokens + len(prompt_lens)) * head
            + paged_attention_cost(cfg, decode_context_tokens)[0]
            + pair * causal_pairs)
