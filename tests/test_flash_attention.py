"""Flash attention: Pallas forward AND backward kernels, masked variant
(reference: ``src/operator/contrib/transformer.cc`` fused attention).

Kernels run in interpret mode on the CPU test backend; the same code
compiles on TPU.  Every check is against the plain XLA reference and
its autodiff.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.ops.pallas import flash_attention as fa
from mxnet_tpu.ops.transformer import (_attention_reference,
                                       _attention_reference_masked)

pytestmark = pytest.mark.skipif(not fa._HAS_PALLAS,
                                reason="no pallas on this backend")


@pytest.fixture(autouse=True)
def _exact_matmuls():
    # the CPU backend runs fp32 matmuls in reduced precision on
    # avx512-bf16 hosts; force exact so kernel-vs-reference comparisons
    # measure the algorithm, not the hardware's fast path
    with jax.default_matmul_precision("highest"):
        yield

BH, SEQ, D, HEADS = 4, 64, 16, 2
B = BH // HEADS


def _qkv(seed=0):
    rng = np.random.RandomState(seed)
    return [jnp.asarray(rng.randn(BH, SEQ, D).astype(np.float32) * 0.5)
            for _ in range(3)]


def _mask(seed=1):
    rng = np.random.RandomState(seed)
    valid = rng.randint(SEQ // 2, SEQ + 1, (B,))
    m = np.zeros((B, SEQ, SEQ), np.float32)
    for i, n in enumerate(valid):
        m[i, :, :n] = 1.0
    return jnp.asarray(m)


def _ref_masked(q, k, v, mask, scale):
    s = jnp.einsum("bqd,bkd->bqk", q, k) * scale
    m = jnp.repeat(mask, HEADS, axis=0)
    s = jnp.where(m > 0, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p, v)


@pytest.mark.parametrize("causal", [False, True])
def test_fwd_matches_reference(causal):
    q, k, v = _qkv()
    scale = 1.0 / np.sqrt(D)
    out, lse = fa.flash_attention_fwd_pallas(
        q, k, v, causal=causal, scale=scale, block_q=32, block_k=32,
        interpret=True)
    want = _attention_reference(q, k, v, causal, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-5)
    # lse really is the log-sum-exp of the (masked) score rows
    s = jnp.einsum("bqd,bkd->bqk", q, k) * scale
    if causal:
        rows = np.arange(SEQ)[:, None]
        cols = np.arange(SEQ)[None, :]
        s = jnp.where(jnp.asarray(rows >= cols), s, -1e30)
    want_lse = jax.scipy.special.logsumexp(s, axis=-1)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want_lse),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_bwd_matches_autodiff(causal):
    q, k, v = _qkv(2)
    scale = 1.0 / np.sqrt(D)

    def ref_loss(q, k, v):
        out = _attention_reference(q, k, v, causal, scale)
        return jnp.sum(out * jnp.cos(out))  # nontrivial cotangent

    dq_ref, dk_ref, dv_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)

    out, lse = fa.flash_attention_fwd_pallas(
        q, k, v, causal=causal, scale=scale, block_q=32, block_k=32,
        interpret=True)
    dout = jnp.cos(out) - out * jnp.sin(out)
    delta = jnp.sum(dout * out, axis=-1)
    dq, dk, dv = fa.flash_attention_bwd_pallas(
        q, k, v, lse, dout, delta, causal=causal, scale=scale,
        block_q=32, block_k=32, interpret=True)
    np.testing.assert_allclose(np.asarray(dq), np.asarray(dq_ref),
                               rtol=2e-3, atol=2e-4)
    np.testing.assert_allclose(np.asarray(dk), np.asarray(dk_ref),
                               rtol=2e-3, atol=2e-4)
    np.testing.assert_allclose(np.asarray(dv), np.asarray(dv_ref),
                               rtol=2e-3, atol=2e-4)


def test_masked_fwd_bwd_match_reference():
    q, k, v = _qkv(3)
    mask = _mask()
    scale = 1.0 / np.sqrt(D)

    out, lse = fa.flash_attention_fwd_pallas(
        q, k, v, mask, causal=False, scale=scale, block_q=32, block_k=32,
        heads=HEADS, interpret=True)
    want = _ref_masked(q, k, v, mask, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-5)

    def ref_loss(q, k, v):
        return jnp.sum(jnp.tanh(_ref_masked(q, k, v, mask, scale)))

    dq_ref, dk_ref, dv_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    dout = 1.0 - jnp.tanh(want) ** 2
    delta = jnp.sum(dout * out, axis=-1)
    dq, dk, dv = fa.flash_attention_bwd_pallas(
        q, k, v, lse, dout, delta, mask, causal=False, scale=scale,
        block_q=32, block_k=32, heads=HEADS, interpret=True)
    np.testing.assert_allclose(np.asarray(dq), np.asarray(dq_ref),
                               rtol=2e-3, atol=2e-4)
    np.testing.assert_allclose(np.asarray(dk), np.asarray(dk_ref),
                               rtol=2e-3, atol=2e-4)
    np.testing.assert_allclose(np.asarray(dv), np.asarray(dv_ref),
                               rtol=2e-3, atol=2e-4)


def test_op_level_masked_grad_matches_xla_path():
    """The registered op's custom_vjp (XLA fallback on CPU) agrees with
    autodiff through the unfused reference."""
    rng = np.random.RandomState(4)
    q = mx.nd.array(rng.randn(BH, SEQ, D).astype(np.float32))
    k = mx.nd.array(rng.randn(BH, SEQ, D).astype(np.float32))
    v = mx.nd.array(rng.randn(BH, SEQ, D).astype(np.float32))
    mask = mx.nd.array(np.asarray(_mask()))
    from mxnet_tpu import autograd
    for t in (q, k, v):
        t.attach_grad()
    with autograd.record():
        out = mx.nd.flash_attention_masked(q, k, v, mask, heads=HEADS,
                                           use_pallas=False)
        loss = (out * out).sum()
    loss.backward()

    qj, kj, vj = (jnp.asarray(t.asnumpy()) for t in (q, k, v))
    scale = 1.0 / np.sqrt(D)

    def ref_loss(qj, kj, vj):
        o = _ref_masked(qj, kj, vj, jnp.asarray(mask.asnumpy()), scale)
        return jnp.sum(o * o)

    g = jax.grad(ref_loss, argnums=(0, 1, 2))(qj, kj, vj)
    for got, want in zip((q.grad, k.grad, v.grad), g):
        np.testing.assert_allclose(got.asnumpy(), np.asarray(want),
                                   rtol=2e-3, atol=2e-3)


def test_mha_masked_uses_flash_path():
    """MultiHeadAttention with a mask and dropout=0 routes through the
    masked flash op and still matches the score-materializing path."""
    from mxnet_tpu.gluon.nn.transformer import MultiHeadAttention
    rng = np.random.RandomState(5)
    x = mx.nd.array(rng.randn(B, SEQ, 32).astype(np.float32))
    mask_np = np.asarray(_mask())
    mask = mx.nd.array(mask_np)

    att_flash = MultiHeadAttention(32, HEADS, dropout=0.0, use_flash=False)
    att_flash.initialize(ctx=mx.cpu())
    att_flash.hybridize()
    out1 = att_flash(x, mask).asnumpy()

    att_drop = MultiHeadAttention(32, HEADS, dropout=0.5, use_flash=False)
    att_drop.initialize(ctx=mx.cpu())
    # same weights; dropout path only activates in training mode
    from conftest import paired_params
    for p1, p2 in paired_params(att_flash, att_drop):
        p2.set_data(p1.data())
    out2 = att_drop(x, mask).asnumpy()
    np.testing.assert_allclose(out1, out2, rtol=2e-4, atol=2e-5)


# ----------------------------------------------------------------------
# bf16 operands: what the BERT cells give the kernels under AMP
# ----------------------------------------------------------------------

def _rel(got, want):
    got, want = (np.asarray(t, np.float32) for t in (got, want))
    return float(np.abs(got - want).max() / np.abs(want).max())


# largest error over the largest reference value.  Each limit is set from
# what the XLA path (``_attention_reference`` and its autodiff on the
# SAME bf16 operands) reads against the float32 reference of the
# bf16-rounded values, the reading beside it; none is looser than twice
# its reading, which the test holds too
_BF16_LIMITS = {
    #                  limit     XLA path reads
    ("plain", "out"): 0.0070,   # 0.00359
    ("plain", "dq"): 0.0058,    # 0.00291
    ("plain", "dk"): 0.0051,    # 0.00260
    ("plain", "dv"): 0.0068,    # 0.00345
    ("causal", "out"): 0.0036,  # 0.00183
    ("causal", "dq"): 0.0052,   # 0.00261
    ("causal", "dk"): 0.0043,   # 0.00216
    ("causal", "dv"): 0.0050,   # 0.00253
    ("masked", "out"): 0.0072,  # 0.00364
    ("masked", "dq"): 0.0069,   # 0.00349
    ("masked", "dk"): 0.0065,   # 0.00326
    ("masked", "dv"): 0.0074,   # 0.00373
}


@pytest.fixture
def blockwise(monkeypatch):
    """No score budget: a non-causal call walks ``block``-sized tiles
    with the online softmax, as a long sequence does.  The budget is
    read while a wrapper is traced and jit keeps its traces, so the
    wrappers are handed out undecorated."""
    monkeypatch.setattr(fa, "WHOLE_ROW_BYTES", 0)
    return (fa.flash_attention_fwd_pallas.__wrapped__,
            fa.flash_attention_bwd_pallas.__wrapped__)


@pytest.mark.parametrize("direction", ["forward", "backward"])
@pytest.mark.parametrize("kind", ["plain", "causal", "masked"])
def test_bf16_operands_match_float32_reference(kind, direction):
    """bf16 q, k, v (and dout) reach the kernels as bf16; the result is
    held to the float32 reference of the same bf16-rounded values as
    closely as the XLA path that rounds ``p`` the same way.  At this
    size a non-causal call is one whole tile a head."""
    _check_bf16(kind, direction)


@pytest.mark.parametrize("direction", ["forward", "backward"])
@pytest.mark.parametrize("kind", ["plain", "masked"])
def test_bf16_operands_blockwise(kind, direction, blockwise):
    """The same operands and limits through the blockwise walk."""
    fwd, _bwd = blockwise
    q = jax.ShapeDtypeStruct((BH, SEQ, D), jnp.bfloat16)
    walk = jax.make_jaxpr(lambda q: fwd(q, q, q, block_q=32, block_k=32))(q)
    assert "scan" in {e.primitive.name for e in _eqns(walk.jaxpr)}
    _check_bf16(kind, direction, *blockwise)


def _check_bf16(kind, direction, fwd=fa.flash_attention_fwd_pallas,
                bwd=fa.flash_attention_bwd_pallas):
    rng = np.random.RandomState(7)
    q, k, v, dout = (jnp.asarray(rng.randn(BH, SEQ, D) * 0.5, jnp.bfloat16)
                     for _ in range(4))
    scale = 1.0 / np.sqrt(D)
    causal = kind == "causal"
    mask = _mask(7) if kind == "masked" else None

    def ref(q, k, v):
        if mask is None:
            return _attention_reference(q, k, v, causal, scale)
        return _attention_reference_masked(
            q, k, v, jnp.repeat(mask, HEADS, axis=0), scale)

    f32 = [t.astype(jnp.float32) for t in (q, k, v, dout)]
    want, vjp = jax.vjp(ref, *f32[:3])
    xla, vjp_xla = jax.vjp(ref, q, k, v)
    out, lse = fwd(q, k, v, mask, causal=causal, scale=scale, block_q=32,
                   block_k=32, heads=HEADS, interpret=True)
    assert out.dtype == jnp.bfloat16 and lse.dtype == jnp.float32
    if direction == "forward":
        names, got, wants, xlas = ["out"], [out], [want], [xla]
    else:
        delta = jnp.sum(f32[3] * out.astype(jnp.float32), axis=-1)
        got = bwd(q, k, v, lse, dout, delta, mask, causal=causal,
                  scale=scale, block_q=32, block_k=32, heads=HEADS,
                  interpret=True)
        assert all(g.dtype == jnp.bfloat16 for g in got)
        names, wants, xlas = ["dq", "dk", "dv"], vjp(f32[3]), vjp_xla(dout)
    for name, g, w, x in zip(names, got, wants, xlas):
        limit = _BF16_LIMITS[kind, name]
        assert limit <= 2 * _rel(x, w), (name, limit, _rel(x, w))
        assert _rel(g, w) <= limit, (name, _rel(g, w), limit)


# ----------------------------------------------------------------------
# what the MXU is fed, read from the traced kernels (no chip needed)
# ----------------------------------------------------------------------

def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it (the
    ``pallas_call``'s kernel, loop and ``pl.when`` bodies)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for val in eqn.params.values():
            for sub in (val if isinstance(val, (list, tuple)) else [val]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


def _kernel_dots(fn, *args):
    dots = [e for e in _eqns(jax.make_jaxpr(fn)(*args).jaxpr)
            if e.primitive.name == "dot_general"]
    return [tuple(v.aval.dtype for v in e.invars) + (e.outvars[0].aval.dtype,)
            for e in dots]


@pytest.mark.parametrize("kind", ["plain", "causal", "masked"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_matmuls_take_the_operands_dtype(dtype, kind):
    """The counter of the mechanism: every matmul inside the kernels
    takes q, k, v and dout in the dtype they arrive in (bf16 under AMP,
    so one MXU pass) and accumulates in float32; a forward has two, a
    backward the published five."""
    seq, d = 512, 64
    qkv = jax.ShapeDtypeStruct((2 * HEADS, seq, d), dtype)
    vec = jax.ShapeDtypeStruct((2 * HEADS, seq), jnp.float32)
    mask = ([jax.ShapeDtypeStruct((2, seq, seq), jnp.float32)]
            if kind == "masked" else [])
    kw = dict(causal=kind == "causal", scale=0.125, heads=HEADS)
    fwd = _kernel_dots(
        lambda q, k, v, *m: fa.flash_attention_fwd_pallas(q, k, v, *m, **kw),
        qkv, qkv, qkv, *mask)
    bwd = _kernel_dots(
        lambda q, k, v, lse, do, delta, *m: fa.flash_attention_bwd_pallas(
            q, k, v, lse, do, delta, *m, **kw),
        qkv, qkv, qkv, vec, qkv, vec, *mask)
    assert len(fwd) == 2 and len(bwd) == 5
    want = (jnp.dtype(dtype), jnp.dtype(dtype), jnp.dtype(jnp.float32))
    assert set(fwd + bwd) == {want}
