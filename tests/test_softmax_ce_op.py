"""The sparse-label softmax cross-entropy as one op (``sparse_softmax_ce``)
and ``gluon.loss.SoftmaxCrossEntropyLoss`` over it.

The benchmark's ``correct`` holds a train cell to ONE forward loss, so the
backward is guarded here: value and gradient against ``jax.grad`` of the
spelled-out ``-take_along_axis(log_softmax(x.astype(f32)))``, what the op
keeps between its forward and its backward, which inputs take the op and
which keep ``log_softmax`` + ``pick`` / ``sum``, and a compiled
``TrainStep`` on ``bert_small`` under bf16 AMP against a loss block that
spells the old lines out."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import amp, autograd, gluon
from mxnet_tpu.ops.nn import _sparse_softmax_ce_core
from mxnet_tpu.parallel import TrainStep, make_mesh

VOCAB = 250                      # 30,522's shape in small: not 128's multiple
DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
          "float16": jnp.float16}
# a gradient is rounded once, from float32 to the logits' dtype
GRAD_TOL = {"float32": 2e-6, "bfloat16": 2 ** -8, "float16": 2 ** -10}


def _reference(x, label):
    """Per-row loss, float32 inside: what the loss computed before the op."""
    idx = jnp.clip(label.astype(jnp.int32), 0, x.shape[-1] - 1)
    logp = jax.nn.log_softmax(x.astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(logp, idx[..., None], axis=-1)[..., 0]


def _case(shape, dtype, seed=0, labels="in_range"):
    rs = np.random.RandomState(seed)
    x = jnp.asarray(rs.randn(*shape, VOCAB) * 4.0, DTYPES[dtype])
    lab = rs.randint(0, VOCAB, shape)
    if labels == "out_of_range":
        lab.flat[0], lab.flat[-1] = VOCAB + 7, -3
    g = jnp.asarray(rs.rand(*shape) + 0.5, jnp.float32)
    return x, jnp.asarray(lab, jnp.float32), g


@pytest.mark.parametrize("labels", ["in_range", "out_of_range"])
@pytest.mark.parametrize("scale", [1.0, 1024.0])
@pytest.mark.parametrize("shape", [(6,), (2, 3)])
@pytest.mark.parametrize("dtype", list(DTYPES))
def test_value_and_gradient_are_the_spelled_out_ones(dtype, shape, scale,
                                                     labels):
    """Float32, bf16 and fp16 logits, 2-D and 3-D, a class count that is
    no multiple of 128, labels clipped into range, an upstream gradient
    that differs a row and carries a loss scale."""
    x, lab, g = _case(shape, dtype, labels=labels)
    g = g * scale
    loss, vjp = jax.vjp(lambda a: _sparse_softmax_ce_core(a, lab), x)
    (grad,) = vjp(g)
    want_loss, want_vjp = jax.vjp(lambda a: _reference(a, lab),
                                  x.astype(jnp.float32))
    (want_grad,) = want_vjp(g)
    assert loss.dtype == jnp.float32 and loss.shape == shape
    np.testing.assert_allclose(loss, want_loss, rtol=1e-6, atol=2e-6)
    assert grad.dtype == x.dtype and grad.shape == x.shape
    np.testing.assert_allclose(
        np.asarray(grad, np.float32),
        np.asarray(want_grad.astype(x.dtype), np.float32),
        rtol=GRAD_TOL[dtype], atol=GRAD_TOL[dtype] * scale * 1e-3)


@pytest.mark.parametrize("dtype", list(DTYPES))
def test_nothing_float32_over_the_classes_is_kept_for_the_backward(dtype):
    """The residuals are the logits as they came, the labels and one
    float32 a row: what made the old lines write 2 GB a step."""
    x, lab, _ = _case((16,), dtype)
    # a vjp function is a pytree whose leaves are what it closed over
    _, vjp = jax.vjp(lambda a: jnp.sum(_sparse_softmax_ce_core(a, lab)), x)
    shapes = [(tuple(leaf.shape), leaf.dtype)
              for leaf in jax.tree_util.tree_leaves(vjp)
              if hasattr(leaf, "shape")]
    assert shapes, "a custom_vjp with no residual would recompute the loss"
    wide = [(s, d) for s, d in shapes
            if int(np.prod(s)) >= 16 * VOCAB and d != x.dtype]
    assert not wide, wide
    assert sum(1 for s, _ in shapes if int(np.prod(s)) >= 16 * VOCAB) == 1


def _spelled_out(F, pred, label, axis=-1):
    return -F.pick(F.log_softmax(pred, axis=axis), label, axis=axis,
                   keepdims=True)


@pytest.mark.parametrize("weight,weighted", [(1.0, False), (0.5, True)])
@pytest.mark.parametrize("ndim", [2, 3])
def test_the_loss_block_equals_log_softmax_and_pick(ndim, weight, weighted):
    """Imperative ``autograd.record``: loss and gradient of the block
    against the lines it held before, with ``weight`` and
    ``sample_weight`` applied to the per-row loss after the op."""
    shape = (6,) if ndim == 2 else (2, 3)
    x, lab, g = _case(shape, "float32", seed=3)
    sw = mx.nd.array(np.asarray(g)[..., None]) if weighted else None
    ce = gluon.loss.SoftmaxCrossEntropyLoss(weight=weight)

    def run(fn):
        a = mx.nd.array(np.asarray(x))
        a.attach_grad()
        with autograd.record():
            out = fn(a)
        out.backward()
        return out.asnumpy(), a.grad.asnumpy()

    def old(a):
        loss = _spelled_out(mx.nd, a, mx.nd.array(np.asarray(lab)))
        if sw is not None:
            loss = mx.nd.broadcast_mul(loss, sw)
        return mx.nd.mean(loss * weight, axis=0, exclude=True)

    got = run(lambda a: ce(a, mx.nd.array(np.asarray(lab)), sw))
    want = run(old)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(got[1], want[1], rtol=1e-5, atol=1e-7)


@pytest.fixture()
def counters():
    was = mx.telemetry.enabled()
    mx.telemetry.enable()

    def read():
        return (mx.telemetry.counter("loss.softmax_ce_fused").value,
                mx.telemetry.counter("loss.softmax_ce_fallback").value)
    yield read
    if not was:
        mx.telemetry.disable()


def _fallback_cases():
    rs = np.random.RandomState(5)
    x = rs.randn(4, 5, 7).astype(np.float32)
    lab = rs.randint(0, 7, (4, 5)).astype(np.float32)
    logp = np.asarray(jax.nn.log_softmax(x, axis=-1))
    dense = np.eye(7, dtype=np.float32)[lab.astype(int)]
    picked = -np.take_along_axis(logp, lab.astype(int)[..., None], -1)
    lab1 = rs.randint(0, 5, (4, 7)).astype(np.float32)
    logp1 = np.asarray(jax.nn.log_softmax(x, axis=1))
    picked1 = -np.take_along_axis(logp1, lab1.astype(int)[:, None, :], 1)
    return {
        "dense_labels": (dict(sparse_label=False), x, dense,
                         picked.mean(axis=(1, 2))),
        "from_logits": (dict(from_logits=True), logp, lab,
                        picked.mean(axis=(1, 2))),
        "axis_1": (dict(axis=1), x, lab1, picked1.mean(axis=(1, 2))),
    }


@pytest.mark.parametrize("case", ["dense_labels", "from_logits", "axis_1"])
def test_the_other_inputs_keep_their_lines(case, counters):
    """Dense labels, ``from_logits`` and a class axis that is not the last
    return what they returned, and count as the fall-back."""
    kwargs, pred, label, want = _fallback_cases()[case]
    fused0, fallback0 = counters()
    got = gluon.loss.SoftmaxCrossEntropyLoss(**kwargs)(
        mx.nd.array(pred), mx.nd.array(label)).asnumpy()
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    assert counters() == (fused0, fallback0 + 1)


@pytest.mark.parametrize("axis", [-1, 2])
def test_sparse_labels_over_the_last_axis_take_the_op(axis, counters):
    """The last axis by either name; under a hybridized block the call is
    counted when it is traced."""
    x, lab, _ = _case((2, 3), "float32", seed=9)
    fused0, fallback0 = counters()
    ce = gluon.loss.SoftmaxCrossEntropyLoss(axis=axis)
    ce.hybridize()
    for _ in range(2):
        got = ce(mx.nd.array(np.asarray(x)), mx.nd.array(np.asarray(lab)))
    np.testing.assert_allclose(
        got.asnumpy(), np.asarray(_reference(x, lab)).mean(axis=1),
        rtol=1e-6, atol=1e-6)
    fused, fallback = counters()
    assert fallback == fallback0 and fused > fused0


def test_the_symbolic_trace_holds_the_op():
    """``hybrid_forward`` with ``F = mx.sym`` (export): the graph names
    the one op, and evaluates to the imperative loss."""
    from mxnet_tpu import symbol as sym
    from mxnet_tpu.symbol.symbol import _eval_symbol
    x, lab, _ = _case((6,), "float32", seed=11)
    ce = gluon.loss.SoftmaxCrossEntropyLoss()
    out = ce(sym.var("pred"), sym.var("label"))
    assert "sparse_softmax_ce" in out.tojson()
    (got,) = _eval_symbol(out, {"pred": mx.nd.array(np.asarray(x)),
                                "label": mx.nd.array(np.asarray(lab))})
    np.testing.assert_allclose(got.asnumpy(), np.asarray(_reference(x, lab)),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("dtype", ["bfloat16", "float16"])
def test_amp_hands_the_op_the_logits_as_they_are(dtype):
    """Not in ``FP32_OPS``: under an AMP scope the op sees the compute
    dtype and its gradient comes back in it, where ``log_softmax`` is
    handed a float32 copy."""
    from mxnet_tpu.amp import lists
    assert "sparse_softmax_ce" not in lists.FP32_OPS
    assert "log_softmax" in lists.FP32_OPS
    x, lab, _ = _case((6,), dtype, seed=13)
    a = mx.nd.array(np.asarray(x, np.float32)).astype(dtype)
    a.attach_grad()
    with amp.scope(dtype):
        with autograd.record():
            loss = gluon.loss.SoftmaxCrossEntropyLoss()(
                a, mx.nd.array(np.asarray(lab)))
        loss.backward()
    assert str(loss.dtype) == "float32"
    assert str(a.grad.dtype) == dtype
    np.testing.assert_allclose(loss.asnumpy(),
                               np.asarray(_reference(x, lab)),
                               rtol=1e-6, atol=2e-6)


class _SpelledOutLoss(gluon.HybridBlock):
    """The masked-LM loss as the lines stood before the op."""

    def __init__(self, vocab, fused):
        super().__init__()
        self._vocab, self._fused = vocab, fused
        self._ce = gluon.loss.SoftmaxCrossEntropyLoss()

    def hybrid_forward(self, F, outs, labels):
        pred = outs[0].reshape((-1, self._vocab))
        labels = labels.reshape((-1,))
        if self._fused:
            return self._ce(pred, labels)
        return F.mean(_spelled_out(F, pred, labels), axis=0, exclude=True)


def _train_bert_small(fused, mesh):
    from mxnet_tpu.gluon.model_zoo.bert import bert_small
    vocab, batch, seq = 250, 8, 16
    mx.random.seed(21)
    np.random.seed(21)
    net = bert_small(vocab_size=vocab, max_length=seq, dropout=0.0)
    net.initialize(ctx=mx.cpu())
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.02}, kvstore=None)
    net(mx.nd.array(np.zeros((1, seq), np.float32)))   # deferred shapes
    start = [p.data().asnumpy() for p in net.collect_params().values()]
    step = TrainStep(net, _SpelledOutLoss(vocab, fused), trainer, mesh=mesh)
    rs = np.random.RandomState(4)
    losses = []
    with amp.scope("bfloat16"):
        for _ in range(3):
            ids = rs.randint(0, vocab, (batch, seq)).astype(np.float32)
            lab = rs.randint(0, vocab, (batch, seq)).astype(np.float32)
            losses.append(float(step(mx.nd.array(ids),
                                     mx.nd.array(lab)).asscalar()))
    # in the net's own order (a name carries a count of the nets built):
    # where each parameter ended and how far it moved
    params = [(name, p.data().asnumpy(), p.data().asnumpy() - p0)
              for (name, p), p0 in zip(net.collect_params().items(), start)]
    return losses, params


@pytest.mark.parametrize("where", ["one_device", "dp_mesh"])
def test_a_compiled_train_step_moves_as_with_the_old_lines(where):
    """``bert_small`` under ``amp.scope("bfloat16")``, three SGD steps:
    losses and updated parameters with the op against a loss block that
    spells out ``log_softmax`` + ``pick``, within bf16's rounding, on one
    device and on a ``dp`` mesh of the host's devices."""
    mesh = None if where == "one_device" else make_mesh(
        {"dp": 4}, devices=jax.devices("cpu")[:4])
    got_losses, got = _train_bert_small(True, mesh)
    want_losses, want = _train_bert_small(False, mesh)
    np.testing.assert_allclose(got_losses, want_losses, rtol=2e-3)
    assert len(got) == len(want)
    for (name, a, _), (_, b, moved) in zip(got, want):
        # the two programs round a bf16 sum here and there in another
        # order: hold them to 3% of the parameter's largest move
        np.testing.assert_allclose(
            a, b, rtol=0, atol=0.03 * np.abs(moved).max() + 1e-7,
            err_msg=name)
