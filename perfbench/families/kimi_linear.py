"""Kimi-Linear-48B-A3B-Instruct (``model_type kimi_linear``, Moonshot's
hybrid of linear and latent attention, arXiv:2510.26692), served as ONE
chip of an expert-parallel deployment: how the benchmark deploys it
through the program's generative-serving path, its plain float32
reference, and the shape functions of what a window served.

The equations (the published ``config.json`` and the paper's modelling
code with this configuration's numbers; d = hidden_size, layers
0-indexed here, the config's lists 1-indexed):

- Block: ``h = x + Attn_i(RMSNorm(x))``, ``y = h + FFN_i(RMSNorm(h))``,
  ``RMSNorm(x) = w * x / sqrt(mean(x^2) + eps)``; a final RMSNorm, then an
  untied head.  ``Attn_i`` is KDA where ``i + 1`` is in
  ``linear_attn_config.kda_layers``, latent attention (MLA) where it is in
  ``full_attn_layers``.
- KDA (H = ``linear_attn_config.num_heads`` heads of dk = dv =
  ``head_dim``, C = H dk, K = ``short_conv_kernel_size``), on the normed
  input u: ``q, k, v = SiLU(Conv_K(u W_q | u W_k | u W_v))``, each d -> C,
  ``Conv_K`` causal and depthwise without bias, ``y_t = sum_n w[n]
  x_(t-K+1+n)`` (zeros before the first token); ``q = q / sqrt(|q|^2 +
  1e-6) * dk^-1/2``, ``k = k / sqrt(|k|^2 + 1e-6)`` a head; ``beta =
  sigmoid(u W_b)`` a head; ``g = -exp(A_log[h]) * softplus(u W_fa W_fb +
  dt_bias)`` a key channel; per head, S (dk x dv) from 0: ``S' =
  Diag(exp(g_t)) S``, ``S = S' + k_t (beta_t (v_t - S'^T k_t))^T``, ``o_t
  = S^T q_t``; ``out = W_o(RMSNorm_dv(o) * sigmoid(u W_ga W_gb))``, the
  norm's weight ``o_norm`` shared by the heads.
- MLA without positions (``mla_use_nope``), ``q_lora_rank`` null: ``q =
  u W_q`` per head ``[q_nope | q_pe]`` (nope + rope); ``[c | k_pe] = u
  W_kva``, ``c = RMSNorm(c)``; ``[k_nope | v] = c W_kvb`` per head;
  ``score = (q_nope . k_nope + q_pe . k_pe) / sqrt(nope + rope)``, k_pe
  ONE unrotated vector shared by the heads, causal softmax, ``out =
  concat_heads(p v) W_o``.
- FFN: the first ``first_k_dense_replace`` layers a SwiGLU,
  ``W_down(silu(W_gate x) * (W_up x))``; every later one ``scores =
  sigmoid(float32(x) W_r)`` over all ``published.num_experts``, ``chosen =
  top_k(scores + bias)``, ``w = scores[chosen] / sum *
  routed_scaling_factor``, ``sum_i w_i Expert_i(x) + Shared(x)``, every
  expert a SwiGLU of ``moe_intermediate_size``.

The share: the router scores all 256; only experts ``first .. first +
n_routed_experts`` (the configuration's count is the number HELD) are
summed, the shared expert once, and the logits are over the
``vocab_size`` rows held.  What the absent experts would add is left out,
here as in the program.

Departures from the published code, each of them in the reference too:
matmul weights are stored (in, out), the transpose of a torch
``Linear.weight``; the held experts of a layer as ONE stacked array; the
three convolutions as one ``(K, 3C)`` array (``[q | k | v]`` channels, tap
``K - 1`` on the current input: torch's ``(C, 1, K)`` weights transposed);
bfloat16 storage (``assumed``).

The reference shares no code with ``mxnet_tpu``: KDA as the
TOKEN-BY-TOKEN recurrence (a scan over the sequence, not the chunked
form), MLA in its expanded form, no cache, no kernel, a dense pass of
every held expert over all tokens.
"""
import numpy as np

from perfbench.families import kimi_k2

KIND = "serve"

# query rows of attention scores the reference computes at a time
_REF_Q_BLOCK = 128
# tokens a feed-forward of the reference takes at a time
_REF_ROWS = 2048
# the storage precision next below bfloat16 (kimi_k2's, spelled out the
# same way): the CONTROL of the check
CONTROL_PRECISION = kimi_k2.CONTROL_PRECISION
SERVED_TIES = kimi_k2.SERVED_TIES
# a KDA state's value as the program stores it: float32
# (``linear_moe.STATE_DTYPE``; the configuration's assumed.arithmetic)
_STATE_BYTES = 4


def kda_layers(cfg):
    """0-indexed layers that are KDA layers."""
    return sorted(n - 1 for n in cfg["linear_attn_config"]["kda_layers"])


def mla_layers(cfg):
    return sorted(n - 1
                  for n in cfg["linear_attn_config"]["full_attn_layers"])


def _first_expert(cfg):
    return cfg["deployment"]["expert_rank"] * cfg["n_routed_experts"]


def _spec(cfg):
    """``LinearLatentMoEDecoder`` at the configuration's widths and share."""
    from perfbench.harness.spec import SpecError
    try:
        from mxnet_tpu.serving.decode import LinearLatentMoEDecoder
    except ImportError as e:    # a program from before the model spec
        raise SpecError("this program cannot run the configuration: %s"
                        % e) from None
    keys = ("vocab_size", "hidden_size", "num_hidden_layers",
            "num_attention_heads", "kv_lora_rank", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim", "intermediate_size",
            "moe_intermediate_size", "num_experts_per_tok",
            "n_shared_experts", "first_k_dense_replace",
            "routed_scaling_factor", "linear_attn_config", "q_lora_rank",
            "rms_norm_eps")
    model = LinearLatentMoEDecoder(
        n_routed_experts=cfg["published"]["num_experts"],
        first_expert=_first_expert(cfg), n_held=cfg["n_routed_experts"],
        max_seq=cfg["model_max_length"], dtype=cfg["serving_dtype"],
        **{k: cfg[k] for k in keys})
    # the state's precision is part of the configuration, and its logits
    # do not show it: a bfloat16 state read the float32 one's gaps on the
    # chip (PERF.md, PR 38), so the program's declaration is held to it
    stored = np.dtype(model.cache_states()["kda_state"][1])
    if stored.itemsize != _STATE_BYTES or stored.kind != "f":
        raise SpecError("this program stores the KDA state in %s; the "
                        "configuration's arithmetic is a float32 state"
                        % stored)
    return model


# decode steps the check takes past the judged ones: a stream whose last
# generated tokens are id 0 reads shorter than it is (the check pads
# with 0), and these still decode its end
_EXTRA_STEPS = 8


class _Served:
    """The program's spec as the check sees it: every attribute is the
    spec's own, and ``full_logits``, the forward the check judges, is the
    SERVED path: per stream the spec's ``prefill_cache`` over its prompt,
    then its ``decode_logits`` a token at a time through a cache of the
    deployment's kinds and dtypes (latent rows of the MLA layers through
    a block table, the KDA layers' state row), at the engine's smallest
    decode bucket with the stream in slot 0 and the others padded.  These
    are the functions the engine compiles, the ``kda_decode`` kernel and
    the state stored as the engine stores it between steps among them;
    the logits of the decode steps are the ones the check judges at the
    generated tokens.  The experts the router chose go with them to the
    reference that follows the program's near-ties
    (``kimi_k2._keep_judged``).

    The check hands each stream as its prompt and ``check.max_new``
    generated tokens, padded with 0 to ``check.width``: the length is
    where the last nonzero token lies, the decode starts ``max_new``
    before it and takes ``_EXTRA_STEPS`` more (every split of a stream
    into a prefill and decode steps is a forward of the same model, so a
    generated 0 at the end only moves where decode starts)."""

    def __init__(self, model, cfg):
        from mxnet_tpu.serving.decode.kvcache import PagedKVCache
        import jax
        self.spec = model
        dep = cfg["deployment"]
        self._block = dep["block_size"]
        self._slots = min(dep["decode_buckets"])
        self._steps = cfg["check"]["max_new"] + _EXTRA_STEPS
        cache = PagedKVCache(
            model.num_layers, model.cache_rows(), self._block,
            -(-model.max_seq // self._block) + 1,
            dtype=dep["kv_dtype"], kinds=model.cache_layers(),
            states=model.cache_states(), state_rows=2)
        self._width = cache.blocks_needed(model.max_seq)
        self._shapes = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), cache.slabs)
        del cache

    def __getattr__(self, name):
        return getattr(self.spec, name)

    def full_logits(self, params, tokens):
        import jax
        import jax.numpy as jnp
        b, t = tokens.shape
        if self._steps > t:
            raise ValueError("check.width %d is under the %d decode steps "
                             "the check takes" % (t, self._steps))
        nonzero = tokens != 0
        length = jnp.where(nonzero.any(1),
                           t - jnp.argmax(nonzero[:, ::-1], axis=1), 1)
        start = jnp.clip(length - (self._steps - _EXTRA_STEPS), 1,
                         t - self._steps)
        rows = [self._served_row(params, tokens[j], start[j])
                for j in range(b)]
        logits = jnp.stack([r[0] for r in rows])
        routing = tuple(jnp.stack(layer) for layer in zip(*(r[1]
                                                            for r in rows)))
        jax.debug.callback(kimi_k2._keep_judged, tokens, routing)
        decoded = jnp.stack([jax.lax.dynamic_slice_in_dim(
            logits[j], start[j], self._steps) for j in range(b)])
        jax.debug.callback(_keep_decoded, tokens, start, length, decoded)
        return logits

    def _served_row(self, params, row, start):
        """One stream: (logits (t, vocab), one (t, top_k) routing a
        expert layer), positions before ``start`` from the prefill,
        ``start`` to ``start + steps`` from decode steps."""
        import jax
        import jax.numpy as jnp
        spec, slots = self.spec, self._slots
        slabs = jax.tree_util.tree_map(
            lambda a: jnp.zeros(a.shape, a.dtype), self._shapes)
        # blocks 1.. in order, state row 1; 0 is scratch in both
        table = {"full": jnp.arange(1, self._width["full"] + 1,
                                    dtype=jnp.int32),
                 "state": jnp.ones((1,), jnp.int32)}
        logits, slabs, _stats, routing = spec.prefill_cache(
            params, slabs, row[None], start - 1, table, self._block,
            every_position=True)
        first = jnp.arange(slots) == 0
        tables = {kind: jnp.where(first[:, None], a[None], 0)
                  for kind, a in table.items()}

        def step(slabs, k):
            pos = start + k
            ids = jnp.where(first, jnp.take(row, pos), 0).astype(jnp.int32)
            _tok, out, slabs, _stats, chosen = spec.decode_logits(
                params, slabs, ids, jnp.where(first, pos, 0), tables,
                self._block, first, with_routing=True)
            return slabs, (out[0], tuple(c[0] for c in chosen))

        _slabs, (dec, dec_routing) = jax.lax.scan(
            step, slabs, jnp.arange(self._steps))
        logits = jax.lax.dynamic_update_slice_in_dim(logits[0], dec, start,
                                                     axis=0)
        routing = tuple(jax.lax.dynamic_update_slice_in_dim(
            r[0], d.astype(r.dtype), start, axis=0)
            for r, d in zip(routing, dec_routing))
        return logits, routing


# the decode steps' logits of the forward the check judged last, for the
# line the reference prints of them
_DECODED = {}


def _keep_decoded(tokens, start, length, decoded):
    _DECODED.update(tokens=np.asarray(tokens), start=np.asarray(start),
                    length=np.asarray(length), logits=np.asarray(decoded))


def _stream_length(tokens, j):
    """Stream ``j``'s length as the judged forward read it, or None."""
    if not np.array_equal(_DECODED.get("tokens"), np.asarray(tokens)):
        return None
    return int(_DECODED["length"][j])


def _print_decoded_gap(tokens, j, row_logits, precision, file):
    """One line on ``file``: the largest gap between the judged forward's
    decode steps and this reference, over the stream's positions that
    the decode steps computed (what the check's one gap mixes with the
    prefill's)."""
    import jax
    if not np.array_equal(_DECODED.get("tokens"), np.asarray(tokens)):
        return
    start, length = int(_DECODED["start"][j]), int(_DECODED["length"][j])
    got = _DECODED["logits"][j]
    want = np.asarray(jax.device_get(
        row_logits[start:start + got.shape[0]]))
    n = max(0, min(length - start, got.shape[0]))
    print("decoded_gap %s stream %d length %d decode_from %d positions %d "
          "gap %.6g" % (precision, j, length, start, n,
                        float(np.abs(got[:n] - want[:n]).max())
                        if n else 0.0), file=file)


def build_model(cfg, seed):
    """(model, params): the spec as the check sees it (``_Served``: the
    forward it judges is the served prefill and decode path, with the
    program's router choices kept) and its weights, drawn from the seed
    by the model's own jitted initialiser, on the device."""
    model = _spec(cfg)
    return _Served(model, cfg), model.init_params(seed)


def deploy(registry, name, model, params, cfg):
    """Register the model behind ``ModelRegistry.register_generative`` with
    the configuration's deployment: buckets, the latent rows' blocks and
    the KDA layers' state rows."""
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

_NORMS = {"attn_norm": "input_layernorm.weight",
          "ffn_norm": "post_attention_layernorm.weight"}
_MLA = {"wq": "self_attn.q_proj.weight",
        "wkva": "self_attn.kv_a_proj_with_mqa.weight",
        "kv_norm": "self_attn.kv_a_layernorm.weight",
        "wkvb": "self_attn.kv_b_proj.weight",
        "wo": "self_attn.o_proj.weight"}
_KDA = {"wq": "self_attn.q_proj.weight", "wk": "self_attn.k_proj.weight",
        "wv": "self_attn.v_proj.weight",
        "f_a": "self_attn.f_a_proj.weight",
        "f_b": "self_attn.f_b_proj.weight",
        "dt_bias": "self_attn.dt_bias", "A_log": "self_attn.A_log",
        "b_proj": "self_attn.b_proj.weight",
        "g_a": "self_attn.g_a_proj.weight",
        "g_b": "self_attn.g_b_proj.weight",
        "o_norm": "self_attn.o_norm.weight",
        "wo": "self_attn.o_proj.weight"}
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
    made on the device but the three convolutions' slices of their one
    array, 72 KiB a layer); see the module's note on their layout."""
    out = {"model.embed_tokens.weight": params["embed"],
           "model.norm.weight": params["norm_f"],
           "lm_head.weight": params["head"]}
    kda = set(kda_layers(cfg))
    width = cfg["linear_attn_config"]["num_heads"] \
        * cfg["linear_attn_config"]["head_dim"]
    for i in range(cfg["num_hidden_layers"]):
        names = dict(_NORMS, **(_KDA if i in kda else _MLA))
        names.update(_DENSE if i < cfg["first_k_dense_replace"] else _MOE)
        pre = "model.layers.%d." % i
        for ours, theirs in names.items():
            out[pre + theirs] = params["h%d_%s" % (i, ours)]
        if i in kda:
            conv = params["h%d_conv_w" % i]
            for n, part in enumerate("qkv"):
                out[pre + "self_attn.%s_conv1d.weight" % part] = \
                    conv[:, n * width:(n + 1) * width]
    return out


def _reference(cfg, control=False):
    """``(layer, forward)`` of the plain reference: ``layer(p, i, x)`` is
    block ``i`` on ``x`` (t, d) float32, ``forward(p, tokens)`` the logits
    (1, t, vocab) of one sequence.  ``control`` rounds every matmul weight
    to float8_e4m3fn first."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    hi = jax.lax.Precision.HIGHEST
    heads, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    v_dim, rank = cfg["v_head_dim"], cfg["kv_lora_rank"]
    lin = cfg["linear_attn_config"]
    lh, ld, conv = lin["num_heads"], lin["head_dim"], \
        lin["short_conv_kernel_size"]
    top_k, held = cfg["num_experts_per_tok"], cfg["n_routed_experts"]
    first, scaling = _first_expert(cfg), cfg["routed_scaling_factor"]
    tie_eps = float(cfg.get("check", {}).get("tie_eps", 0.0))
    kda = set(kda_layers(cfg))
    s = (nope + rope) ** -0.5

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

    @jax.jit
    def attend(q, kv, k_pe):
        """q (t, H, nope + rope); kv (t, H, nope + v); k_pe (t, rope),
        none of them rotated -> (t, H * v), causal."""
        t = q.shape[0]
        pos = jnp.arange(t)
        k = jnp.concatenate(
            [kv[..., :nope],
             jnp.broadcast_to(k_pe[:, None, :], (t, heads, rope))], -1)
        v = kv[..., nope:]
        block = next(n for n in (_REF_Q_BLOCK, 64, 16, 4, 2, 1)
                     if t % n == 0)

        def rows(args):
            qb, start = args
            score = jnp.einsum("qhd,khd->hqk", qb, k) * s
            mask = pos[None, :] <= (start + jnp.arange(block))[:, None]
            score = jnp.where(mask[None], score, -jnp.inf)
            return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(score, -1), v)

        out = jax.lax.map(rows, (q.reshape(t // block, block, heads, -1),
                                 jnp.arange(0, t, block)))
        return out.reshape(t, heads * v_dim)

    @jax.jit
    def short_conv(x, w):
        """x (t, C), w (K, C): y_t = sum_n w[n] x_(t-K+1+n), then SiLU."""
        t = x.shape[0]
        xp = jnp.concatenate([jnp.zeros((conv - 1, x.shape[1]), f32), x])
        return jax.nn.silu(sum(xp[n:n + t] * w[n].astype(f32)
                               for n in range(conv)))

    @jax.jit
    def recurrence(q, k, v, g, beta):
        """The gated delta rule a token at a time: q, k, g (t, H, dk), v
        (t, H, dv), beta (t, H) -> o (t, H, dv)."""
        def step(state, xs):
            qt, kt, vt, gt, bt = xs
            state = jnp.exp(gt)[:, :, None] * state
            pred = jnp.einsum("hkv,hk->hv", state, kt, precision=hi)
            state = state + kt[:, :, None] \
                * (bt[:, None] * (vt - pred))[:, None, :]
            return state, jnp.einsum("hkv,hk->hv", state, qt, precision=hi)
        _, o = jax.lax.scan(step, jnp.zeros((lh, ld, ld), f32),
                            (q, k, v, g, beta))
        return o

    def unit(x):
        return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    def kda_attention(p, pre, h):
        t = h.shape[0]
        q, k, v = (short_conv(mm(h, p[pre + "self_attn.%s_proj.weight" % n]),
                              p[pre + "self_attn.%s_conv1d.weight" % n]
                              ).reshape(t, lh, ld) for n in "qkv")
        q, k = unit(q) * ld ** -0.5, unit(k)
        beta = jax.nn.sigmoid(mm(h, p[pre + "self_attn.b_proj.weight"]))
        g = -jnp.exp(p[pre + "self_attn.A_log"].astype(f32))[:, None] \
            * jax.nn.softplus(
                mm(mm(h, p[pre + "self_attn.f_a_proj.weight"]),
                   p[pre + "self_attn.f_b_proj.weight"])
                + p[pre + "self_attn.dt_bias"].astype(f32)).reshape(t, lh, ld)
        o = recurrence(q, k, v, g, beta)
        gate = jax.nn.sigmoid(mm(mm(h, p[pre + "self_attn.g_a_proj.weight"]),
                                 p[pre + "self_attn.g_b_proj.weight"]))
        o = rms(o, p[pre + "self_attn.o_norm.weight"]).reshape(t, -1) * gate
        return mm(o, p[pre + "self_attn.o_proj.weight"])

    def mla_attention(p, pre, h):
        t = h.shape[0]
        q = mm(h, p[pre + "self_attn.q_proj.weight"]).reshape(
            t, heads, nope + rope)
        kva = mm(h, p[pre + "self_attn.kv_a_proj_with_mqa.weight"])
        c = rms(kva[:, :rank], p[pre + "self_attn.kv_a_layernorm.weight"])
        kv = mm(c, p[pre + "self_attn.kv_b_proj.weight"]).reshape(
            t, heads, nope + v_dim)
        return mm(attend(q, kv, kva[:, rank:]),
                  p[pre + "self_attn.o_proj.weight"])

    def swiglu(x, w_gate, w_up, w_down):
        # a matmul at a time, _REF_ROWS tokens at a time
        edges = np.linspace(0, x.shape[0], -(-x.shape[0] // _REF_ROWS) + 1
                            ).astype(int)
        return jnp.concatenate([
            mm(jax.nn.silu(mm(x[a:b], w_gate)) * mm(x[a:b], w_up), w_down)
            for a, b in zip(edges[:-1], edges[1:])])

    @jax.jit
    def route(x, w_g, bias, served=None, count=None):
        """Per token, the weight with which each HELD expert enters (t,
        held), zero where it was not chosen, and a tally of the ties;
        ``served`` (t, top_k), the experts the PROGRAM chose, each given
        ``tie_eps`` over this router's own biased score before its own
        top_k (``kimi_k2.make_reference`` says why)."""
        scores = jax.nn.sigmoid(jnp.matmul(x, stored(w_g), precision=hi))
        biased = scores + bias
        _, chosen = jax.lax.top_k(biased, top_k)
        tally = None
        if served is not None:
            own = chosen
            is_served = (served[:, :, None]
                         == jnp.arange(biased.shape[-1])).any(1)
            _, chosen = jax.lax.top_k(biased + tie_eps * is_served, top_k)
            short = jnp.maximum(
                jnp.max(jnp.where(is_served, -jnp.inf, biased), -1)
                - jnp.min(jnp.take_along_axis(biased, served, -1), -1), 0.0)
            followed = (jnp.sort(chosen) == jnp.sort(served)).all(-1)
            # the stream's own tokens: what follows is the check's padding
            mine = jnp.arange(x.shape[0]) < (x.shape[0] if count is None
                                             else count)
            tally = {
                "moved": jnp.sum(mine & (jnp.sort(chosen)
                                         != jnp.sort(own)).any(-1)),
                "not_followed": jnp.sum(mine & ~followed),
                "shortfall": jnp.max(jnp.where(mine, short, 0.0))}
        w = jnp.take_along_axis(scores, chosen, -1)
        w = w / (w.sum(-1, keepdims=True) + 1e-20) * scaling
        ours = first + jnp.arange(held)
        return jnp.sum(w[:, :, None] * (chosen[:, :, None] == ours), 1), tally

    # each attention one program: what it makes over a check's 8,448
    # tokens (a KDA layer's ten float32 (t, 4096) arrays, 1.4 GB) are its
    # temporaries, not arrays held beside the program's cache
    attention = {True: jax.jit(kda_attention, static_argnums=1),
                 False: jax.jit(mla_attention, static_argnums=1)}

    def layer(p, i, x, served=None, tallies=None, count=None):
        pre = "model.layers.%d." % i
        h = rms(x, p[pre + "input_layernorm.weight"])
        x = x + attention[i in kda](p, pre, h)
        h = rms(x, p[pre + "post_attention_layernorm.weight"])
        if i < cfg["first_k_dense_replace"]:
            return x + swiglu(h, p[pre + "mlp.gate_proj.weight"],
                              p[pre + "mlp.up_proj.weight"],
                              p[pre + "mlp.down_proj.weight"])
        weight, tally = route(
            h, p[pre + "mlp.gate.weight"],
            p[pre + "mlp.gate.e_score_correction_bias"], served, count)
        if tally is not None:
            tallies.append(tally)
        y = swiglu(h, *(p[pre + "mlp.shared_experts.%s_proj.weight" % n]
                        for n in ("gate", "up", "down")))
        stacks = [p[pre + "mlp.experts.%s_proj.weight" % n]
                  for n in ("gate", "up", "down")]
        for j in range(held):       # every held expert over every token
            y = y + weight[:, j:j + 1] * swiglu(h, *(w[j] for w in stacks))
        return x + y

    def forward(p, tokens, served=None, count=None):
        x = p["model.embed_tokens.weight"][tokens].astype(f32)
        dense = cfg["first_k_dense_replace"]
        tallies = []
        for i in range(cfg["num_hidden_layers"]):
            x = layer(p, i, x, None if served is None or i < dense
                      else served[i - dense], tallies, count)
        # (1, t, vocab): a check's one stream is handed back as it is
        # made, not copied into a batch of one (0.69 GB at its width)
        return mm(rms(x, p["model.norm.weight"])[None],
                  p["lm_head.weight"]), tallies

    return layer, forward


def make_reference(cfg, precision="highest"):
    """``logits(ref_params, tokens)``: the float32 forward of the equations
    above, given this chip's share; ``tokens`` (batch, t) int -> logits
    (batch, t, vocab) float32, left on the device.  ``precision`` is JAX's
    matmul precision ("highest": float32 arithmetic throughout);
    ``CONTROL_PRECISION`` is float32 arithmetic over weights rounded to
    float8_e4m3fn.  Prefixed ``served_ties.`` the reference breaks its
    router's near-ties the way the program did, as
    ``kimi_k2.make_reference`` explains (top-8 of 256 is as discontinuous
    as top-8 of 384); each sequence's tally of ties goes to standard
    error."""
    import sys

    import jax
    import jax.numpy as jnp
    ties = precision.startswith(SERVED_TIES)
    precision = precision[len(SERVED_TIES):] if ties else precision
    control = precision == CONTROL_PRECISION
    _layer, forward = _reference(cfg, control)

    def logits(ref_params, tokens):
        tokens = jnp.asarray(tokens, jnp.int32)
        routing = kimi_k2._judged_routing(tokens) if ties \
            else [None] * len(tokens)
        out = []
        with jax.default_matmul_precision("highest" if control
                                          else precision):
            for j, (row, served) in enumerate(zip(tokens, routing)):
                row_logits, tallies = forward(ref_params, row, served,
                                              _stream_length(tokens, j))
                out.append(row_logits)
                if ties:
                    _print_decoded_gap(tokens, j, row_logits[0],
                                       SERVED_TIES + precision, sys.stderr)
                for n, tally in enumerate(jax.device_get(tallies)):
                    print("router_ties %s%s expert_layer %d tokens %d %s"
                          % (SERVED_TIES, precision, n, len(row), " ".join(
                              "%s %.6g" % kv for kv in sorted(tally.items()))),
                          file=sys.stderr)
        return out[0] if len(out) == 1 else jnp.concatenate(out)
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

def _itemsize(dtype):
    import jax.numpy as jnp
    return jnp.dtype(dtype).itemsize


def kv_bytes_per_token(cfg):
    """Bytes one token holds in the latent cache over the MLA layers, as
    stored: (kv_lora_rank + qk_rope_head_dim) values a layer.  A KDA
    layer holds nothing a token (its state is a sequence's)."""
    return len(mla_layers(cfg)) * _itemsize(cfg["deployment"]["kv_dtype"]) \
        * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])


def state_bytes_per_sequence(cfg):
    """Bytes one sequence holds over the KDA layers: the (H, dv, dk)
    state and the convolution's last K - 1 inputs of 3C channels."""
    lin = cfg["linear_attn_config"]
    width = lin["num_heads"] * lin["head_dim"]
    return len(kda_layers(cfg)) * (
        lin["num_heads"] * lin["head_dim"] ** 2
        * _STATE_BYTES
        + (lin["short_conv_kernel_size"] - 1) * 3 * width
        * _itemsize(cfg["serving_dtype"]))


def paged_attention_cost(cfg, context_tokens):
    """(FLOPs, HBM bytes) the decode step's latent attention has to do
    over ``context_tokens`` live context tokens in total: each live
    token's row read ONCE an MLA layer for all heads, every head scoring
    it over rank + rope lanes and weighing its first rank lanes (as
    ``kimi_k2.paged_attention_cost``, over the MLA layers alone)."""
    row = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    flops = 2 * cfg["num_attention_heads"] * (row + cfg["kv_lora_rank"]) \
        * len(mla_layers(cfg)) * context_tokens
    return flops, kv_bytes_per_token(cfg) * context_tokens


def recurrence_cost(cfg, state_rows):
    """(FLOPs, HBM bytes) of ``state_rows`` KDA decode recurrences (a
    live slot in a KDA layer each): the (H, dv, dk) state read and
    written ONCE, and the token's q, k, v, log decay g and beta read and
    its output o written, in float32.  FLOPs a head: the decay (dk dv),
    S'^T k (2 dk dv), the rank-one update (2 dk dv), S^T q (2 dk dv)."""
    lin = cfg["linear_attn_config"]
    h, d = lin["num_heads"], lin["head_dim"]
    state = h * d * d * _STATE_BYTES
    vectors = 4 * (4 * h * d + h + h * d)
    return 7 * h * d * d * state_rows, (2 * state + vectors) * state_rows


def matmul_params(cfg):
    """Weights a token's matmuls pass through, by part: an MLA layer's
    attention, a KDA layer's (its five projections and the two low-rank
    gates), the dense FFN, the router, one expert (the shared one has
    ``n_shared_experts`` of them)."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    lin = cfg["linear_attn_config"]
    lh, ld = lin["num_heads"], lin["head_dim"]
    c = lh * ld
    mla = (d * h * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"])
           + d * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
           + cfg["kv_lora_rank"] * h
           * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
           + h * cfg["v_head_dim"] * d)
    kda = 3 * d * c + c * d + 2 * (d * ld + ld * c) + d * lh
    return {"mla": mla, "kda": kda,
            "dense_ffn": 3 * d * cfg["intermediate_size"],
            "router": d * cfg["published"]["num_experts"],
            "expert": 3 * d * cfg["moe_intermediate_size"]}


def served_flops(cfg, decode_tokens, decode_context_tokens, prompt_lens):
    """FLOPs the model needs for what a window served on THIS chip:
    ``decode_tokens`` decode steps' tokens over ``decode_context_tokens``
    of live context in total, and one prefill for each of ``prompt_lens``.
    A token's pass through a layer is two FLOPs a weight of its
    attention's projections and of the dense FFN (dense layers) or of the
    router, the shared expert and its EXPECTED share of routed experts
    under even routing (expert layers); a KDA layer adds its convolution
    (2 K FLOPs a channel of 3C) and one step of the recurrence
    (``recurrence_cost``) a token, prefill or decode, whatever form
    computes it; MLA is ``paged_attention_cost`` a decode context token
    and 2 H (nope + rope + v) a causal pair in prefill; the head once a
    token emitted.  Padding and the absorbing matmuls do not count."""
    n = matmul_params(cfg)
    lin = cfg["linear_attn_config"]
    layers, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    n_kda, n_mla = len(kda_layers(cfg)), len(mla_layers(cfg))
    held_share = cfg["num_experts_per_tok"] * cfg["n_routed_experts"] \
        / cfg["published"]["num_experts"]
    per_token = 2 * (
        n_kda * n["kda"] + n_mla * n["mla"] + dense * n["dense_ffn"]
        + (layers - dense) * (n["router"] + n["expert"]
                              * (cfg["n_shared_experts"] + held_share))) \
        + n_kda * (2 * lin["short_conv_kernel_size"] * 3
                   * lin["num_heads"] * lin["head_dim"]
                   + recurrence_cost(cfg, 1)[0])
    head = 2 * cfg["hidden_size"] * cfg["vocab_size"]
    pair = 2 * cfg["num_attention_heads"] * n_mla * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
        + cfg["v_head_dim"])
    causal_pairs = sum(m * (m + 1) // 2 for m in prompt_lens)
    return ((decode_tokens + sum(prompt_lens)) * per_token
            + (decode_tokens + len(prompt_lens)) * head
            + paged_attention_cost(cfg, decode_context_tokens)[0]
            + pair * causal_pairs)
