"""GPT-2, served: how the benchmark deploys it through the program's
generative-serving path, its plain float32 reference, and the shape
functions of a decode step.

The program's ``TinyGPT`` is GPT-2's block (pre-LN, learned positions,
tanh-GELU, tied unembedding, eps 1e-5) without the two attention bias
vectors.  The reference below is GPT-2 as published (Radford et al. 2019;
``modeling_gpt2``) with ``c_attn.bias`` and the attention ``c_proj.bias``
given as explicit zeros; it shares no code with ``mxnet_tpu``.
"""
import numpy as np

KIND = "serve"


def build_model(cfg, seed):
    """(model spec, params): ``TinyGPT`` at the configuration's sizes and
    its own initialiser, run as one jitted call on the device with the
    seed as an argument (the eager call's draws to the last rounding, one program
    for every seed instead of a hundred small ones)."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.serving.decode import TinyGPT
    if cfg["n_inner"] != 4 * cfg["n_embd"]:
        raise ValueError("TinyGPT's MLP is 4 x units wide; n_inner=%r"
                         % cfg["n_inner"])
    model = TinyGPT(vocab_size=cfg["vocab_size"], units=cfg["n_embd"],
                    num_layers=cfg["n_layer"], num_heads=cfg["n_head"],
                    max_seq=cfg["n_positions"])
    return model, jax.jit(model.init_params)(jnp.uint32(seed % (2 ** 32)))


def deploy(registry, name, model, params, cfg):
    """Register the model behind ``ModelRegistry.register_generative`` with
    the configuration's deployment (buckets and cache)."""
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

def reference_params(params, cfg):
    """The served parameter dict under GPT-2's published names, with the
    two bias vectors ``TinyGPT`` lacks as zeros."""
    import jax.numpy as jnp
    e = cfg["n_embd"]
    out = {"wte": params["embed"], "wpe": params["pos_embed"],
           "ln_f.g": params["lnf_g"], "ln_f.b": params["lnf_b"]}
    for i in range(cfg["n_layer"]):
        src, dst = "h%d_" % i, "h%d." % i
        out[dst + "ln_1.g"] = params[src + "ln1_g"]
        out[dst + "ln_1.b"] = params[src + "ln1_b"]
        out[dst + "attn.c_attn.w"] = params[src + "wqkv"]
        out[dst + "attn.c_attn.b"] = jnp.zeros((3 * e,), jnp.float32)
        out[dst + "attn.c_proj.w"] = params[src + "wo"]
        out[dst + "attn.c_proj.b"] = jnp.zeros((e,), jnp.float32)
        out[dst + "ln_2.g"] = params[src + "ln2_g"]
        out[dst + "ln_2.b"] = params[src + "ln2_b"]
        out[dst + "mlp.c_fc.w"] = params[src + "w1"]
        out[dst + "mlp.c_fc.b"] = params[src + "b1"]
        out[dst + "mlp.c_proj.w"] = params[src + "w2"]
        out[dst + "mlp.c_proj.b"] = params[src + "b2"]
    return out


def make_reference(cfg, precision="highest"):
    """``logits(ref_params, tokens)``: the float32 GPT-2 causal forward,
    jitted once; ``tokens`` (batch, t) int -> logits (batch, t, vocab),
    left on the device.  ``precision`` is JAX's matmul precision:
    "highest" is float32 arithmetic throughout, "bfloat16" rounds the
    operands of every matmul to bfloat16 and accumulates in float32 --
    what a float32 matmul is on a TPU unless the program asks for more."""
    import jax
    import jax.numpy as jnp

    heads, eps = cfg["n_head"], cfg["layer_norm_epsilon"]

    def ln(x, g, b):
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + eps) * g + b

    def gelu_new(x):
        return 0.5 * x * (1.0 + jnp.tanh(
            np.sqrt(2.0 / np.pi) * (x + 0.044715 * x ** 3)))

    def forward(p, tokens):
        b, t = tokens.shape
        x = p["wte"][tokens] + p["wpe"][:t][None]
        d = x.shape[-1] // heads
        mask = jnp.tril(jnp.ones((t, t), bool))
        for i in range(cfg["n_layer"]):
            pre = "h%d." % i
            h = ln(x, p[pre + "ln_1.g"], p[pre + "ln_1.b"])
            qkv = h @ p[pre + "attn.c_attn.w"] + p[pre + "attn.c_attn.b"]
            q, k, v = (a.reshape(b, t, heads, d)
                       for a in jnp.split(qkv, 3, axis=-1))
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
            s = jnp.where(mask[None, None], s, jnp.finfo(jnp.float32).min)
            a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
            x = x + a.reshape(b, t, heads * d) @ p[pre + "attn.c_proj.w"] \
                + p[pre + "attn.c_proj.b"]
            h = ln(x, p[pre + "ln_2.g"], p[pre + "ln_2.b"])
            h = gelu_new(h @ p[pre + "mlp.c_fc.w"] + p[pre + "mlp.c_fc.b"])
            x = x + h @ p[pre + "mlp.c_proj.w"] + p[pre + "mlp.c_proj.b"]
        x = ln(x, p["ln_f.g"], p["ln_f.b"])
        return x @ p["wte"].T

    jitted = jax.jit(forward)

    def logits(ref_params, tokens):
        with jax.default_matmul_precision(precision):
            return jitted(ref_params, jnp.asarray(tokens, jnp.int32))
    return logits


# ----------------------------------------------------------------------
# shape functions
# ----------------------------------------------------------------------

def kv_bytes_per_token(cfg):
    """Bytes of K and V one token holds in the cache over all layers."""
    width = np.dtype(cfg["deployment"]["kv_dtype"]).itemsize
    return 2 * cfg["n_layer"] * cfg["n_embd"] * width


def paged_attention_cost(cfg, context_tokens):
    """(FLOPs, HBM bytes) decode-step attention has to do over
    ``context_tokens`` live context tokens in total (summed over the slots
    of every step counted): q.K and p.V are 4 FLOPs per token per channel
    per layer, and each live token's K and V are read once per layer.
    The query and output rows are 1/context of that and left out."""
    flops = 4 * cfg["n_layer"] * cfg["n_embd"] * context_tokens
    return flops, kv_bytes_per_token(cfg) * context_tokens


def served_flops(cfg, decode_tokens, decode_context_tokens, prompt_lens):
    """FLOPs the model needs for what a window served: ``decode_tokens``
    decode steps' tokens over ``decode_context_tokens`` of live context in
    total, and one prefill for each of ``prompt_lens``.  A token's pass
    through a layer is 12 x n_embd^2 multiply-adds in its four matmuls (qkv
    3, proj 1, MLP 4 + 4) and 4 FLOPs per context token per channel in
    attention; the unembedding is counted once per emitted token, the only
    position whose logits are needed.  What a program computes beyond that
    (a prefill bucket's padding, logits of every position) does not count."""
    e, layers = cfg["n_embd"], cfg["n_layer"]
    per_token = layers * 24 * e * e
    head = 2 * e * cfg["vocab_size"]
    prompt_tokens = sum(prompt_lens)
    causal_pairs = sum(n * (n + 1) // 2 for n in prompt_lens)
    return ((decode_tokens + prompt_tokens) * per_token
            + (decode_tokens + len(prompt_lens)) * head
            + 4 * layers * e * (decode_context_tokens + causal_pairs))
