"""Transformer ops, attention layers, BERT (BASELINE config 3).

Reference analogs: ``tests/python/unittest/test_operator.py`` transformer
op tests, GluonNLP BERT tests."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon


def _naive_mha(qkv, heads):
    seq, b, emb3 = qkv.shape
    hd = emb3 // (3 * heads)
    x = qkv.reshape(seq, b, heads, 3, hd)
    q = np.transpose(x[:, :, :, 0], (1, 2, 0, 3)).reshape(b * heads, seq, hd)
    k = np.transpose(x[:, :, :, 1], (1, 2, 0, 3)).reshape(b * heads, seq, hd)
    v = np.transpose(x[:, :, :, 2], (1, 2, 0, 3)).reshape(b * heads, seq, hd)
    s = np.einsum("bqd,bkd->bqk", q, k) / np.sqrt(hd)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    o = np.einsum("bqk,bkd->bqd", p, v)
    return s, np.transpose(o.reshape(b, heads, seq, hd),
                           (2, 0, 1, 3)).reshape(seq, b, heads * hd)


def test_interleaved_selfatt_matches_naive():
    rng = np.random.RandomState(0)
    seq, b, h, hd = 6, 2, 3, 4
    qkv = rng.randn(seq, b, h * 3 * hd).astype(np.float32)
    scores_ref, out_ref = _naive_mha(qkv, h)
    scores = mx.nd.interleaved_matmul_selfatt_qk(mx.nd.array(qkv), heads=h)
    np.testing.assert_allclose(scores.asnumpy(), scores_ref, rtol=1e-4,
                               atol=1e-5)
    att = mx.nd.softmax(scores, axis=-1)
    out = mx.nd.interleaved_matmul_selfatt_valatt(mx.nd.array(qkv), att,
                                                  heads=h)
    np.testing.assert_allclose(out.asnumpy(), out_ref, rtol=1e-4, atol=1e-5)


def test_interleaved_encdec_matches_naive():
    rng = np.random.RandomState(1)
    qlen, kvlen, b, h, hd = 5, 7, 2, 2, 4
    q = rng.randn(qlen, b, h * hd).astype(np.float32)
    kv = rng.randn(kvlen, b, h * 2 * hd).astype(np.float32)
    scores = mx.nd.interleaved_matmul_encdec_qk(mx.nd.array(q),
                                                mx.nd.array(kv), heads=h)
    x = kv.reshape(kvlen, b, h, 2, hd)
    kn = np.transpose(x[:, :, :, 0], (1, 2, 0, 3)).reshape(b * h, kvlen, hd)
    vn = np.transpose(x[:, :, :, 1], (1, 2, 0, 3)).reshape(b * h, kvlen, hd)
    qn = np.transpose(q.reshape(qlen, b, h, hd),
                      (1, 2, 0, 3)).reshape(b * h, qlen, hd)
    s_ref = np.einsum("bqd,bkd->bqk", qn, kn) / np.sqrt(hd)
    np.testing.assert_allclose(scores.asnumpy(), s_ref, rtol=1e-4, atol=1e-5)
    att = mx.nd.softmax(scores, axis=-1)
    out = mx.nd.interleaved_matmul_encdec_valatt(mx.nd.array(kv), att,
                                                 heads=h)
    p = att.asnumpy()
    o_ref = np.einsum("bqk,bkd->bqd", p, vn)
    o_ref = np.transpose(o_ref.reshape(b, h, qlen, hd),
                         (2, 0, 1, 3)).reshape(qlen, b, h * hd)
    np.testing.assert_allclose(out.asnumpy(), o_ref, rtol=1e-4, atol=1e-5)


def test_flash_attention_xla_matches_naive_and_grads():
    rng = np.random.RandomState(2)
    q = rng.randn(4, 8, 16).astype(np.float32)
    k = rng.randn(4, 8, 16).astype(np.float32)
    v = rng.randn(4, 8, 16).astype(np.float32)
    qn, kn, vn = mx.nd.array(q), mx.nd.array(k), mx.nd.array(v)
    out = mx.nd.flash_attention(qn, kn, vn)
    s = np.einsum("bqd,bkd->bqk", q, k) / 4.0
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    ref = np.einsum("bqk,bkd->bqd", p, v)
    np.testing.assert_allclose(out.asnumpy(), ref, rtol=1e-4, atol=1e-5)
    # causal
    outc = mx.nd.flash_attention(qn, kn, vn, causal=True).asnumpy()
    sc = np.where(np.tril(np.ones((8, 8))) > 0, s, -1e30)
    pc = np.exp(sc - sc.max(-1, keepdims=True))
    pc /= pc.sum(-1, keepdims=True)
    np.testing.assert_allclose(outc, np.einsum("bqk,bkd->bqd", pc, v),
                               rtol=1e-4, atol=1e-5)
    # custom-vjp gradients vs finite differences on a scalar loss
    for t in (qn, kn, vn):
        t.attach_grad()
    with autograd.record():
        o = mx.nd.flash_attention(qn, kn, vn)
        loss = (o * o).sum()
    loss.backward()
    eps = 1e-3
    qpert = q.copy()
    qpert[0, 0, 0] += eps
    o1 = mx.nd.flash_attention(mx.nd.array(qpert), kn, vn)
    l1 = float((o1 * o1).sum().asscalar())
    l0 = float(loss.asscalar())
    fd = (l1 - l0) / eps
    np.testing.assert_allclose(float(qn.grad.asnumpy()[0, 0, 0]), fd,
                               rtol=5e-2, atol=1e-2)


def test_flash_attention_pallas_interpret_matches_xla():
    """Run the actual Pallas kernel in interpreter mode (CPU) against the
    XLA reference path."""
    from mxnet_tpu.ops.pallas.flash_attention import \
        flash_attention_fwd_pallas
    from mxnet_tpu.ops.transformer import _attention_reference
    rng = np.random.RandomState(3)
    import jax
    import jax.numpy as jnp
    cpu = jax.devices("cpu")[0]
    q = jax.device_put(jnp.asarray(rng.randn(2, 16, 8).astype(np.float32)), cpu)
    k = jax.device_put(jnp.asarray(rng.randn(2, 16, 8).astype(np.float32)), cpu)
    v = jax.device_put(jnp.asarray(rng.randn(2, 16, 8).astype(np.float32)), cpu)
    for causal in (False, True):
        out, _lse = flash_attention_fwd_pallas(
            q, k, v, causal=causal, scale=0.3, block_q=8, block_k=8,
            interpret=True)
        ref = _attention_reference(q, k, v, causal, 0.3)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)


def test_multihead_attention_layer_masked_vs_unmasked():
    mx.random.seed(0)
    layer = gluon.nn.MultiHeadAttention(units=16, num_heads=4)
    layer.initialize(ctx=mx.cpu())
    rng = np.random.RandomState(4)
    x = mx.nd.array(rng.randn(2, 6, 16).astype(np.float32))
    out = layer(x)
    assert out.shape == (2, 6, 16)
    # full-ones mask must match the unmasked (flash) path
    mask = mx.nd.ones((2, 6, 6))
    out_masked = layer(x, mask)
    np.testing.assert_allclose(out_masked.asnumpy(), out.asnumpy(),
                               rtol=1e-4, atol=1e-5)


def test_transformer_encoder_shapes_and_grad():
    mx.random.seed(0)
    enc = gluon.nn.TransformerEncoder(units=16, hidden_size=32,
                                      num_layers=2, num_heads=2,
                                      max_length=32)
    enc.initialize(ctx=mx.cpu())
    x = mx.nd.array(np.random.RandomState(5).randn(2, 8, 16)
                    .astype(np.float32))
    names = list(enc.collect_params().keys())
    assert len(names) == len(set(names))
    out = enc(x)
    assert out.shape == (2, 8, 16)
    for p in enc.collect_params().values():
        p._data.attach_grad() if False else None
    tr = gluon.Trainer(enc.collect_params(), "sgd",
                       {"learning_rate": 0.1}, kvstore=None)
    with autograd.record():
        l = (enc(x) ** 2.0).mean()
    l.backward()
    tr.step(2)


def test_bert_small_pretrain_step_and_hybridize():
    mx.random.seed(0)
    net = gluon.model_zoo.bert_small(vocab_size=500, max_length=64)
    net.initialize(ctx=mx.cpu())
    rng = np.random.RandomState(6)
    ids = mx.nd.array(rng.randint(0, 500, (2, 16)).astype(np.float32))
    tt = mx.nd.zeros((2, 16))
    mlm, nsp = net(ids, tt)
    assert mlm.shape == (2, 16, 500)
    assert nsp.shape == (2, 2)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    tr = gluon.Trainer(net.collect_params(), "adam",
                       {"learning_rate": 1e-3}, kvstore=None)
    labels = mx.nd.array(rng.randint(0, 500, (2, 16)).astype(np.float32))
    nsp_labels = mx.nd.array(np.array([0, 1], np.float32))
    losses = []
    for _ in range(8):
        with autograd.record():
            mlm, nsp = net(ids, tt)
            l = loss_fn(mlm.reshape((-1, 500)), labels.reshape((-1,))) \
                .mean() + loss_fn(nsp, nsp_labels).mean()
        l.backward()
        tr.step(2)
        losses.append(float(l.asscalar()))
    assert losses[-1] < losses[0]
    net.hybridize()
    mlm2, nsp2 = net(ids, tt)
    assert mlm2.shape == (2, 16, 500)


def test_bert_trainstep_compiled():
    """BERT through the fused TrainStep (the bench path)."""
    from mxnet_tpu.parallel import TrainStep
    mx.random.seed(0)
    net = gluon.model_zoo.bert_small(vocab_size=200, max_length=32,
                                     dropout=0.0)
    net.initialize(ctx=mx.cpu())
    net.hybridize()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    class MLMLoss(gluon.HybridBlock):
        def hybrid_forward(self, F, outs, labels):
            mlm, nsp = outs
            v = mlm.shape[-1]
            return loss_fn(mlm.reshape((-1, v)), labels.reshape((-1,)))

    tr = gluon.Trainer(net.collect_params(), "adam",
                       {"learning_rate": 1e-3}, kvstore=None)
    step = TrainStep(net, MLMLoss(), tr)
    rng = np.random.RandomState(7)
    ids = mx.nd.array(rng.randint(0, 200, (4, 16)).astype(np.float32))
    labels = mx.nd.array(rng.randint(0, 200, (4, 16)).astype(np.float32))
    first = float(step(ids, labels).asscalar())
    for _ in range(5):
        last = float(step(ids, labels).asscalar())
    assert last < first
