"""Gluon behavior (reference: ``tests/python/unittest/test_gluon.py``)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon
from mxnet_tpu.gluon import nn
from mxnet_tpu.test_utils import assert_almost_equal


def test_parameter_basic():
    p = gluon.Parameter("w", shape=(3, 4))
    p.initialize(init="ones")
    assert p.data().shape == (3, 4)
    assert (p.data().asnumpy() == 1).all()
    assert p.grad() is not None
    p.set_data(mx.nd.zeros((3, 4)))
    assert (p.data().asnumpy() == 0).all()


def test_parameter_deferred():
    p = gluon.Parameter("w", shape=(5, 0), allow_deferred_init=True)
    p.initialize()
    with pytest.raises(Exception):
        p.data()
    p.shape = (5, 7)
    p._finish_deferred_init()
    assert p.data().shape == (5, 7)


def test_dense_forward_shapes():
    layer = nn.Dense(8, in_units=4)
    layer.initialize()
    out = layer(mx.nd.ones((2, 4)))
    assert out.shape == (2, 8)
    # deferred in_units
    layer2 = nn.Dense(8)
    layer2.initialize()
    assert layer2(mx.nd.ones((2, 6))).shape == (2, 8)
    assert layer2.weight.shape == (8, 6)


def test_dense_no_flatten():
    layer = nn.Dense(8, flatten=False)
    layer.initialize()
    out = layer(mx.nd.ones((2, 3, 6)))
    assert out.shape == (2, 3, 8)


def test_sequential_and_children():
    net = nn.HybridSequential()
    net.add(nn.Dense(4), nn.Dense(2))
    net.initialize()
    assert len(net) == 2
    assert net(mx.nd.ones((1, 3))).shape == (1, 2)
    params = net.collect_params()
    assert len(params) == 4  # 2 weights + 2 biases


def test_hybridize_matches_imperative():
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu"), nn.Dense(4))
    net.initialize()
    x = mx.nd.random.normal(shape=(8, 10))
    y_imp = net(x).asnumpy()
    net.hybridize()
    y_hyb = net(x).asnumpy()
    # atol covers TPU MXU bf16-accumulation differences between the eager
    # per-op and fused jit paths (reference relaxes similarly for gpu)
    np.testing.assert_allclose(y_hyb, y_imp, rtol=1e-2, atol=5e-4)


def test_hybridize_shape_respecialization():
    net = nn.Dense(4, in_units=3)
    net.initialize()
    net.hybridize()
    assert net(mx.nd.ones((2, 3))).shape == (2, 4)
    assert net(mx.nd.ones((5, 3))).shape == (5, 4)  # second specialization
    assert len(net._cached_entries) == 2


def test_hybrid_training_gradients():
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu"), nn.Dense(4))
    net.initialize()
    net.hybridize()
    x = mx.nd.random.normal(shape=(8, 10))
    with autograd.record():
        loss = net(x).sum()
    loss.backward()
    for p in net.collect_params().values():
        g = p.data()._grad
        assert g is not None
    # compare hybrid grads vs imperative grads
    net2 = nn.HybridSequential()
    net2.add(nn.Dense(16, activation="relu"), nn.Dense(4))
    net2.initialize()
    # copy params
    from conftest import paired_params
    for p1, p2 in paired_params(net, net2):
        p2.set_data(p1.data())
    with autograd.record():
        loss2 = net2(x).sum()
    loss2.backward()
    for p1, p2 in paired_params(net, net2):
        np.testing.assert_allclose(p2.data()._grad.asnumpy(),
                                   p1.data()._grad.asnumpy(),
                                   rtol=5e-3, atol=1e-5)


def test_batchnorm_layer_stats():
    net = nn.BatchNorm(in_channels=4)
    net.initialize()
    x = mx.nd.random.normal(shape=(16, 4), scale=2.0)
    with autograd.record():
        net(x)
    rm = net.running_mean.data().asnumpy()
    assert not np.allclose(rm, 0)  # updated toward batch mean


@pytest.mark.parametrize("layout,axis", [("NCHW", 1), ("NHWC", 3)])
@pytest.mark.parametrize("training", [True, False],
                         ids=["training", "inference"])
def test_hybrid_sequential_is_its_children_in_turn(layout, axis, training):
    """A hybridized Conv-BatchNorm-relu stack gives the output and the
    running statistics of the three blocks called one after another:
    nothing between the children is paired, fused or skipped."""
    def blocks():
        return [nn.Conv2D(8, 3, padding=1, layout=layout),
                nn.BatchNorm(axis=axis), nn.Activation("relu")]

    def in_turn(chain, x):
        for b in chain:
            x = b(x)
        return x

    shape = (4, 3, 6, 6) if layout == "NCHW" else (4, 6, 6, 3)
    xs = [mx.nd.array(np.random.RandomState(i).randn(*shape)
                      .astype(np.float32) * 2 + 1) for i in range(2)]
    net = nn.HybridSequential()
    net.add(*blocks())
    net.initialize()
    net(xs[0])                          # inference: shapes, no stats
    ref = blocks()
    for b in ref:
        b.initialize()
    in_turn(ref, xs[0])
    for got, want in zip(ref, net):
        for q, p in zip(got.collect_params().values(),
                        want.collect_params().values()):
            q.set_data(p.data())
    net.hybridize()
    bn, ref_bn = net[1], ref[1]
    for x in xs:                        # the second pass runs compiled
        if training:
            with autograd.record():
                out, want = net(x), in_turn(ref, x)
        else:
            out, want = net(x), in_turn(ref, x)
        assert_almost_equal(out.asnumpy(), want.asnumpy(), rtol=1e-5,
                            atol=1e-5)
        assert out.asnumpy().min() >= 0.0
        for stat in ("running_mean", "running_var"):
            assert_almost_equal(getattr(bn, stat).data().asnumpy(),
                                getattr(ref_bn, stat).data().asnumpy(),
                                rtol=1e-5, atol=1e-6)
        moved = not np.allclose(bn.running_mean.data().asnumpy(), 0)
        assert moved == training


def test_trainer_step_decreases_loss():
    net = nn.HybridSequential()
    net.add(nn.Dense(32, activation="relu"), nn.Dense(2))
    net.initialize()
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.5})
    x = mx.nd.random.normal(shape=(32, 8))
    y = mx.nd.array(np.random.randint(0, 2, 32))
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    losses = []
    for _ in range(60):
        with autograd.record():
            l = loss_fn(net(x), y).mean()
        l.backward()
        trainer.step(1)
        losses.append(l.asscalar())
    assert losses[-1] < losses[0] * 0.5


def test_trainer_states_roundtrip(tmp_path):
    net = nn.Dense(4, in_units=3)
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1, "momentum": 0.9})
    x = mx.nd.ones((2, 3))
    with autograd.record():
        l = net(x).sum()
    l.backward()
    trainer.step(1)
    f = str(tmp_path / "t.states")
    trainer.save_states(f)
    trainer.load_states(f)


def test_save_load_parameters(tmp_path):
    net = nn.HybridSequential()
    net.add(nn.Dense(8, in_units=4), nn.Dense(2, in_units=8))
    net.initialize()
    f = str(tmp_path / "net.params")
    net.save_parameters(f)
    net2 = nn.HybridSequential()
    net2.add(nn.Dense(8, in_units=4), nn.Dense(2, in_units=8))
    net2.load_parameters(f)
    x = mx.nd.random.normal(shape=(2, 4))
    assert_almost_equal(net(x), net2(x), rtol=1e-5, atol=1e-6)


def test_save_load_deferred(tmp_path):
    net = nn.Dense(4)
    net.initialize()
    net(mx.nd.ones((1, 7)))
    f = str(tmp_path / "d.params")
    net.save_parameters(f)
    net2 = nn.Dense(4)
    net2.load_parameters(f)
    assert net2.weight.shape == (4, 7)
    assert net2(mx.nd.ones((2, 7))).shape == (2, 4)


def test_losses():
    pred = mx.nd.array([[1., 2., 3.], [3., 2., 1.]])
    label = mx.nd.array([2., 0.])
    l = gluon.loss.SoftmaxCrossEntropyLoss()(pred, label)
    e = np.exp([[1, 2, 3], [3, 2, 1]])
    p = e / e.sum(-1, keepdims=True)
    expect = -np.log([p[0, 2], p[1, 0]])
    assert_almost_equal(l, expect, rtol=1e-4)

    l2 = gluon.loss.L2Loss()(mx.nd.array([1., 2.]), mx.nd.array([0., 0.]))
    assert_almost_equal(l2, [0.5, 2.0], rtol=1e-5)

    l1 = gluon.loss.L1Loss()(mx.nd.array([1., -2.]), mx.nd.array([0., 0.]))
    assert_almost_equal(l1, [1.0, 2.0], rtol=1e-5)

    bce = gluon.loss.SigmoidBinaryCrossEntropyLoss()(
        mx.nd.array([0.0]), mx.nd.array([1.0]))
    assert_almost_equal(bce, [np.log(2)], rtol=1e-4)


def test_huber_hinge():
    h = gluon.loss.HuberLoss()(mx.nd.array([2.0]), mx.nd.array([0.0]))
    assert_almost_equal(h, [1.5], rtol=1e-5)
    hg = gluon.loss.HingeLoss()(mx.nd.array([0.5]), mx.nd.array([1.0]))
    assert_almost_equal(hg, [0.5], rtol=1e-5)


def test_conv_block():
    net = nn.HybridSequential()
    net.add(nn.Conv2D(8, 3, padding=1, activation="relu"),
            nn.MaxPool2D(2, 2),
            nn.Conv2D(16, 3, padding=1),
            nn.BatchNorm(),
            nn.Activation("relu"),
            nn.GlobalAvgPool2D(),
            nn.Flatten(),
            nn.Dense(10))
    net.initialize()
    x = mx.nd.random.normal(shape=(2, 3, 16, 16))
    assert net(x).shape == (2, 10)
    net.hybridize()
    assert net(x).shape == (2, 10)


def test_embedding_layer():
    emb = nn.Embedding(10, 4)
    emb.initialize()
    out = emb(mx.nd.array([1, 2, 3], dtype="int32"))
    assert out.shape == (3, 4)


def test_block_repr_and_summary():
    net = nn.HybridSequential()
    net.add(nn.Dense(4, in_units=3))
    net.initialize()
    assert "Dense" in repr(net)
    s = net.summary(mx.nd.ones((1, 3)))
    assert "Total params" in s


def test_dropout_behavior():
    d = nn.Dropout(0.5)
    d.initialize()
    x = mx.nd.ones((100, 100))
    out_eval = d(x)
    assert (out_eval.asnumpy() == 1).all()
    with autograd.record():
        out_train = d(x)
    zeros = (out_train.asnumpy() == 0).mean()
    assert 0.3 < zeros < 0.7


def test_lstm_layer():
    lstm = gluon.rnn.LSTM(16, num_layers=2)
    lstm.initialize()
    x = mx.nd.random.normal(shape=(5, 3, 8))  # TNC
    out = lstm(x)
    assert out.shape == (5, 3, 16)
    states = lstm.begin_state(batch_size=3)
    out, new_states = lstm(x, states)
    assert out.shape == (5, 3, 16)
    assert new_states[0].shape == (2, 3, 16)
    assert new_states[1].shape == (2, 3, 16)


def test_gru_bidirectional():
    gru = gluon.rnn.GRU(8, num_layers=1, bidirectional=True)
    gru.initialize()
    x = mx.nd.random.normal(shape=(4, 2, 5))
    out = gru(x)
    assert out.shape == (4, 2, 16)


def test_lstm_cell_unroll():
    cell = gluon.rnn.LSTMCell(8)
    cell.initialize()
    x = mx.nd.random.normal(shape=(2, 5, 4))  # NTC
    outputs, states = cell.unroll(5, x, layout="NTC")
    assert outputs.shape == (2, 5, 8)
    assert states[0].shape == (2, 8)


def test_lstm_trains():
    lstm = gluon.rnn.LSTM(8)
    lstm.initialize()
    x = mx.nd.random.normal(shape=(4, 2, 5))
    with autograd.record():
        loss = lstm(x).sum()
    loss.backward()
    p = lstm.collect_params()
    some_grad = [pp.data()._grad for pp in p.values()][0]
    assert float(abs(some_grad.asnumpy()).sum()) > 0


def test_prelu_swish():
    p = nn.PReLU()
    p.initialize()
    x = mx.nd.array([[-1.0, 2.0]])
    assert p(x).shape == (1, 2)
    s = nn.Swish()
    out = s(mx.nd.array([0.0]))
    assert abs(out.asscalar()) < 1e-6
