"""BERT with the masked-LM head, trained: how the benchmark builds it
through the program's entry points, its plain float32 reference, and the
shape functions that count what a step has to compute.

The build is ``chip_smoke.py::build_bert``'s recipe (PR 21):
``gluon.model_zoo`` -> ``Trainer`` (Adam) -> ``TrainStep`` under
``amp.scope("bfloat16")``.  The reference below is written from the
paper's equations (post-LN encoder, erf-GELU, learned positions) in plain
``jax.numpy`` and shares no code with ``mxnet_tpu``.
"""
import re

import numpy as np

KIND = "train"
AMP_DTYPE = "bfloat16"


class Trainable:
    """What the train driver needs of a built configuration.  ``step`` and
    ``mesh`` are set by ``make_step``: placing the parameters on a mesh
    comes after the one-device forward that ``correct`` is decided on."""

    def __init__(self, net, loss_fn, ctx):
        self.net, self.loss_fn, self.ctx = net, loss_fn, ctx
        self.step = self.mesh = None


def build_model(cfg, seed, platform):
    """The model and its loss block for ``cfg``, weights drawn from
    ``seed``, on one device."""
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon.model_zoo.bert import BERTModel

    # gluon's initializers draw from numpy's global stream, the rest from
    # mx.random (chip_smoke.py::seed_everything)
    np.random.seed(seed % (2 ** 32))
    mx.random.seed(seed % (2 ** 31))
    ctx = mx.tpu() if platform == "tpu" else mx.cpu()
    net = BERTModel(vocab_size=cfg["vocab_size"], units=cfg["hidden_size"],
                    hidden_size=cfg["intermediate_size"],
                    num_layers=cfg["num_hidden_layers"],
                    num_heads=cfg["num_attention_heads"],
                    max_length=cfg["max_position_embeddings"],
                    type_vocab_size=cfg["type_vocab_size"],
                    dropout=cfg["hidden_dropout_prob"])
    net.initialize(ctx=ctx)
    net.hybridize()
    vocab = cfg["vocab_size"]
    ce = gluon.loss.SoftmaxCrossEntropyLoss()

    class MLMLoss(gluon.HybridBlock):
        def hybrid_forward(self, F, outs, labels):
            mlm, _nsp = outs
            return ce(mlm.reshape((-1, vocab)), labels.reshape((-1,)))

    return Trainable(net, MLMLoss(), ctx)


def make_step(built, cfg, chips):
    """Trainer and compiled-step object over ``chips`` chips (a ``dp``
    mesh where there is more than one)."""
    from mxnet_tpu import gluon
    from mxnet_tpu.parallel import TrainStep, make_mesh
    built.mesh = make_mesh({"dp": chips}) if chips > 1 else None
    trainer = gluon.Trainer(built.net.collect_params(), cfg["optimizer"],
                            {"learning_rate": cfg["learning_rate"]},
                            kvstore=None)
    built.step = TrainStep(built.net, built.loss_fn, trainer,
                           mesh=built.mesh)
    return built


def make_batches(cfg, global_batch, seed, count):
    """``count`` distinct host batches ``(ids, labels)`` of token ids,
    float32 as ``chip_smoke.py::bert_batch`` stages them."""
    rng = np.random.RandomState(seed % (2 ** 32))
    shape = (global_batch, cfg["seq_len"])
    return [(rng.randint(0, cfg["vocab_size"], shape).astype(np.float32),
             rng.randint(0, cfg["vocab_size"], shape).astype(np.float32))
            for _ in range(count)]


def system_token_losses(built, ids, labels):
    """The system's forward loss per token on a host sample, under the
    AMP policy the trainer uses, as a float32 numpy vector."""
    import mxnet_tpu as mx
    from mxnet_tpu import amp
    with amp.scope(AMP_DTYPE):
        out = built.loss_fn(built.net(mx.nd.array(ids, ctx=built.ctx)),
                            mx.nd.array(labels, ctx=built.ctx))
    return np.asarray(out.asnumpy(), np.float32).reshape(-1)


# ----------------------------------------------------------------------
# plain reference
# ----------------------------------------------------------------------

_CELL = re.compile(r"transformerencodercell(\d+)_(.+)$")


def reference_params(net):
    """The net's parameter values under the reference's own names."""
    top = {"embedding0_weight": "word", "embedding1_weight": "type",
           "dense0_weight": "pool_w", "dense0_bias": "pool_b",
           "dense1_weight": "nsp_w", "dense1_bias": "nsp_b",
           "dense2_weight": "mlm_w", "dense2_bias": "mlm_b",
           "layernorm0_gamma": "mlm_ln_g", "layernorm0_beta": "mlm_ln_b",
           "dense3_weight": "dec_w", "dense3_bias": "dec_b"}
    enc = {"position_weight": "pos", "layernorm0_gamma": "emb_ln_g",
           "layernorm0_beta": "emb_ln_b"}
    cell = {"multiheadattention0_qkv_weight": "qkv_w",
            "multiheadattention0_qkv_bias": "qkv_b",
            "multiheadattention0_out_weight": "out_w",
            "multiheadattention0_out_bias": "out_b",
            "layernorm0_gamma": "ln1_g", "layernorm0_beta": "ln1_b",
            "positionwiseffn0_dense0_weight": "ffn1_w",
            "positionwiseffn0_dense0_bias": "ffn1_b",
            "positionwiseffn0_dense1_weight": "ffn2_w",
            "positionwiseffn0_dense1_bias": "ffn2_b",
            "layernorm1_gamma": "ln2_g", "layernorm1_beta": "ln2_b"}
    out = {}
    for name, p in net.collect_params().items():
        value = p.data()._data
        rest = re.sub(r"^bertmodel\d+_", "", name)
        m = _CELL.search(rest)
        if m:
            out["l%d_%s" % (int(m.group(1)), cell[m.group(2)])] = value
        elif "transformerencoder" in rest:
            out[enc[re.sub(r"^transformerencoder\d+_", "", rest)]] = value
        else:
            out[top[rest]] = value
    return out


def reference_token_losses(params, ids, labels, cfg):
    """Float32 BERT encoder + MLM head + softmax cross-entropy per token,
    every matmul at ``precision=highest``.  ``params`` as
    ``reference_params`` names them; ``ids``/``labels`` integer arrays
    (batch, seq).  Returns a numpy vector of batch*seq losses."""
    import jax
    import jax.numpy as jnp

    heads = cfg["num_attention_heads"]
    eps = cfg["layer_norm_eps"]

    def ln(x, g, b):
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + eps) * g + b

    def dense(x, w, b):
        return jnp.dot(x, w.T) + b

    def gelu(x):
        return 0.5 * x * (1.0 + jax.lax.erf(x / np.sqrt(2.0)))

    def forward(p, ids, labels):
        p = {k: jnp.asarray(v, jnp.float32) for k, v in p.items()}
        b, s = ids.shape
        x = p["word"][ids] + p["pos"][:s][None]
        x = ln(x, p["emb_ln_g"], p["emb_ln_b"])
        d = x.shape[-1] // heads
        for i in range(cfg["num_hidden_layers"]):
            pre = "l%d_" % i
            qkv = dense(x, p[pre + "qkv_w"], p[pre + "qkv_b"])
            q, k, v = (t.reshape(b, s, heads, d)
                       for t in jnp.split(qkv, 3, axis=-1))
            scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
            att = jnp.einsum("bhqk,bkhd->bqhd",
                             jax.nn.softmax(scores, axis=-1), v)
            att = dense(att.reshape(b, s, heads * d),
                        p[pre + "out_w"], p[pre + "out_b"])
            x = ln(x + att, p[pre + "ln1_g"], p[pre + "ln1_b"])
            ffn = dense(gelu(dense(x, p[pre + "ffn1_w"], p[pre + "ffn1_b"])),
                        p[pre + "ffn2_w"], p[pre + "ffn2_b"])
            x = ln(x + ffn, p[pre + "ln2_g"], p[pre + "ln2_b"])
        h = ln(gelu(dense(x, p["mlm_w"], p["mlm_b"])),
               p["mlm_ln_g"], p["mlm_ln_b"])
        logits = dense(h, p["dec_w"], p["dec_b"])
        logp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)
        return -picked.reshape(-1)

    with jax.default_matmul_precision("highest"):
        out = jax.jit(forward)(params, jnp.asarray(ids, jnp.int32),
                               jnp.asarray(labels, jnp.int32))
    return np.asarray(out, np.float32)


# ----------------------------------------------------------------------
# shape functions: what the algorithm has to compute
# ----------------------------------------------------------------------

def forward_flops_per_token(cfg):
    """Multiply-adds x 2 of one token's forward pass at ``seq_len``:
    the 12 layers' projections and FFN, attention's two products over the
    sequence, and the MLM head over the whole vocabulary (this model zoo
    scores every position).  Embedding lookups, layer norms, softmax and
    the per-sequence NSP head are left out (under 0.1%)."""
    h, i, s = cfg["hidden_size"], cfg["intermediate_size"], cfg["seq_len"]
    layer = 2 * h * 3 * h + 2 * h * h + 2 * 2 * h * i + 4 * s * h
    head = 2 * h * h + 2 * h * cfg["vocab_size"]
    return cfg["num_hidden_layers"] * layer + head


def train_flops_per_token(cfg):
    """Forward plus backward = 3 x forward; nothing recomputed counts."""
    return 3 * forward_flops_per_token(cfg)


def flash_attention_step_cost(cfg, batch):
    """(FLOPs, HBM bytes) the flash-attention forward and backward of one
    train step on one chip have to do, ``batch`` sequences on the chip.
    Forward: S=QK^T and PV, 4*s*s*d per head.  Backward as published
    (Dao et al. 2022): S recomputed, dV, dP, dQ, dK = 10*s*s*d per head.
    Bytes: each of q, k, v, o (forward) and q, k, v, o, do, dq, dk, dv
    (backward) crosses HBM once in bf16; the float32 row statistics are
    1/32 of that and left out."""
    s = cfg["seq_len"]
    heads = cfg["num_attention_heads"]
    d = cfg["hidden_size"] // heads
    per_head_flops = (4 + 10) * s * s * d
    per_head_bytes = (4 + 8) * s * d * 2
    n = cfg["num_hidden_layers"] * batch * heads
    return n * per_head_flops, n * per_head_bytes
