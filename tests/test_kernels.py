"""Kernel tier: the registry's choice table (backend x shape x the
caller's ``force``, and nothing else), the flash-attention op-level
pallas path (incl. the masked backward and the per-shard wrapping), and
the fallback proof with Pallas monkeypatched unavailable.

Kernels run in interpret mode on the CPU test backend (the tests pass
``use_pallas=True`` / ``force=True``; the registry's off-chip policy
then interprets the real kernel bodies); the same code compiles on TPU.
Every numerics check is against the XLA reference path and its autodiff.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import autograd, kernels
from mxnet_tpu.kernels import registry as kreg

pytestmark = pytest.mark.skipif(not kernels.available(),
                                reason="no pallas on this backend")


@pytest.fixture(autouse=True)
def _exact_matmuls():
    # kernel-vs-reference comparisons measure the algorithm, not the
    # CPU backend's reduced-precision matmul fast path
    with jax.default_matmul_precision("highest"):
        yield


# ----------------------------------------------------------------------
# registry selection policy
# ----------------------------------------------------------------------

# each kernel at a shape its benchmark cell calls it with, and one its
# ``supports`` gate rejects (with a word of the gate's reason)
CELL_SHAPES = {
    # bert_train_*: seq 512 in 256 x 256 blocks
    "flash_attention": (dict(seq=512, block_q=256, block_k=256),
                        dict(seq=100, block_q=32, block_k=32),
                        "divisible"),
    # gpt2m_serve_*: 16 heads of 64, blocks of 16 tokens
    "paged_attention": (dict(heads=16, head_dim=64, block_size=16),
                        dict(heads=16, head_dim=64, block_size=0),
                        "positive"),
    # kimi_k2_serve_closed32: 64 heads over 576-value rows in 640
    # lanes, 512 of them values, blocks of 64 tokens
    "mla_paged_attention": (
        dict(heads=64, lanes=640, v_width=512, block_size=64),
        dict(heads=64, lanes=512, v_width=576, block_size=64),
        "v_width"),
    # mellum2_serve_closed32: a prefill's sorted rows over 64 whole
    # experts of 2,304 x 896
    "grouped_matmul": (dict(groups=64, k=2304, n=896),
                       dict(groups=0, k=2304, n=896), "positive"),
    # kimi_linear_serve_closed128: 32 heads of (128, 128) float32 state
    "kda_decode": (dict(heads=32, key=128, value=128),
                   dict(heads=32, key=72, value=128), "128-lane"),
}
KERNEL_NAMES = sorted(CELL_SHAPES)

# (backend, pallas importable, shape, force) -> (pallas, interpret, reason)
SITUATIONS = {
    "tpu-open": ("tpu", True, "cell", None, (True, False, "tpu")),
    "tpu-rejected-shape-forced": ("tpu", True, "rejected", True,
                                  (False, False, None)),
    "cpu-open": ("cpu", True, "cell", None, (False, False, "cpu backend")),
    "cpu-forced": ("cpu", True, "cell", True, (True, True, "interpret")),
    "tpu-forced-xla": ("tpu", True, "cell", False,
                       (False, False, "caller forced XLA")),
    "no-pallas-forced": ("tpu", False, "cell", True,
                         (False, False, "unavailable")),
}


def _situate(monkeypatch, backend, has_pallas=True):
    monkeypatch.setattr(kreg, "_backend", lambda: backend)
    monkeypatch.setattr(kreg, "_has_pallas", lambda: has_pallas)


def test_registry_lists_the_five_kernels():
    assert kernels.list_kernels() == KERNEL_NAMES


@pytest.mark.parametrize("situation", sorted(SITUATIONS))
@pytest.mark.parametrize("name", KERNEL_NAMES)
def test_choose_is_a_function_of_backend_shape_and_force(
        monkeypatch, name, situation):
    backend, has_pallas, which, force, want = SITUATIONS[situation]
    cell, rejected, gate_word = CELL_SHAPES[name]
    _situate(monkeypatch, backend, has_pallas)
    ch = kernels.choose(name, force=force,
                        **(cell if which == "cell" else rejected))
    use_pallas, interpret, reason = want
    assert (ch.use_pallas, ch.interpret) == (use_pallas, interpret), ch
    assert (reason or gate_word) in ch.reason, ch.reason


@pytest.mark.parametrize("force,use_pallas", [(None, False), (True, True)])
def test_flash_below_its_crossover_is_the_callers_to_force(
        monkeypatch, force, use_pallas):
    """seq 128 < AUTO_MIN_SEQ on a TPU: XLA when the caller leaves the
    choice open, the compiled kernel when it asks for it."""
    _situate(monkeypatch, "tpu")
    ch = kernels.choose("flash_attention", force=force, seq=128,
                        block_q=256, block_k=256)
    assert (ch.use_pallas, ch.interpret) == (use_pallas, False), ch
    assert ("tpu" if use_pallas else "auto policy") in ch.reason


@pytest.mark.parametrize("force,use_pallas", [(None, False), (True, True)])
def test_a_layer_of_few_held_experts_keeps_ragged_dot_unless_asked(
        monkeypatch, force, use_pallas):
    """kimi_k2_serve_closed32's 12 groups of 7,168 x 2,048 on a TPU: the
    open choice is ``ragged_dot``, which its cell was measured with."""
    _situate(monkeypatch, "tpu")
    ch = kernels.choose("grouped_matmul", force=force, groups=12, k=7168,
                        n=2048)
    assert (ch.use_pallas, ch.interpret) == (use_pallas, False), ch
    assert ("tpu" if use_pallas else "auto policy") in ch.reason


@pytest.mark.parametrize("value", ["0", "1"])
@pytest.mark.parametrize("name", KERNEL_NAMES)
def test_the_environment_cannot_move_a_choice(monkeypatch, name, value):
    """MXNET_TPU_KERNELS was the tier's switch until PR 30; set to
    either of its values it changes no choice, on or off the chip."""
    cell = CELL_SHAPES[name][0]
    for backend in ("tpu", "cpu"):
        _situate(monkeypatch, backend)
        monkeypatch.delenv("MXNET_TPU_KERNELS", raising=False)
        unset = [kernels.choose(name, force=f, **cell)
                 for f in (None, True, False)]
        monkeypatch.setenv("MXNET_TPU_KERNELS", value)
        assert [kernels.choose(name, force=f, **cell)
                for f in (None, True, False)] == unset, backend


def _flash_case(rng):
    q, k, v = (jnp.asarray(rng.randn(4, 64, 16), jnp.float32)
               for _ in range(3))

    def wrapper():
        return mx.nd.flash_attention(
            mx.nd.NDArray(q), mx.nd.NDArray(k), mx.nd.NDArray(v),
            use_pallas=True, block_q=32, block_k=32)._data
    return wrapper, (q, k, v), dict(causal=False, scale=0.25)


def _paged_case(rng):
    from mxnet_tpu.kernels.paged_attention import paged_attention
    q = jnp.asarray(rng.randn(2, 2, 16), jnp.float32)
    kc, vc = (jnp.asarray(rng.randn(12, 4, 2, 16), jnp.float32)
              for _ in range(2))
    bt = jnp.asarray(rng.randint(1, 12, (2, 5)), jnp.int32)
    cl = jnp.asarray([[7], [18]], jnp.int32)
    args = (q, kc, vc, bt, cl)
    return (lambda: paged_attention(*args, scale=0.25, use_pallas=True),
            args, dict(scale=0.25))


def _latent_case(rng):
    from mxnet_tpu.kernels.mla_paged_attention import mla_paged_attention
    q = jnp.asarray(rng.randn(2, 4, 128), jnp.float32)
    rows = jnp.asarray(rng.randn(12, 4, 128), jnp.float32)
    bt = jnp.asarray(rng.randint(1, 12, (2, 4)), jnp.int32)
    cl = jnp.asarray([[5], [16]], jnp.int32)
    args = (q, rows, bt, cl, 8)
    return (lambda: mla_paged_attention(*args, scale=0.1,
                                        use_pallas=True),
            args, dict(scale=0.1))


def _grouped_case(rng):
    from mxnet_tpu.kernels.grouped_matmul import grouped_matmul
    lhs = jnp.asarray(rng.randn(40, 32), jnp.float32)
    rhs = jnp.asarray(rng.randn(5, 32, 16), jnp.float32)
    args = (lhs, rhs, jnp.asarray([7, 0, 20, 1, 9], jnp.int32))
    return (lambda: grouped_matmul(*args, use_pallas=True), args, {})


@pytest.mark.parametrize("name,case", [
    ("flash_attention", _flash_case), ("paged_attention", _paged_case),
    ("mla_paged_attention", _latent_case),
    ("grouped_matmul", _grouped_case)])
def test_without_pallas_every_wrapper_is_its_xla_reference(
        monkeypatch, name, case):
    """The fallback proof: with Pallas monkeypatched away a wrapper that
    is asked for its kernel computes its registered XLA reference, bit
    for bit, and the kernel body is never entered."""
    import importlib
    monkeypatch.setattr(kreg, "_has_pallas", lambda: False)
    body = importlib.import_module("mxnet_tpu.ops.pallas." + name)

    def entered(*_a, **_kw):
        raise AssertionError("%s entered its Pallas body" % name)
    for fn in [n for n in dir(body) if n.endswith("_pallas")]:
        monkeypatch.setattr(body, fn, entered)
    wrapper, args, kw = case(np.random.RandomState(0))
    want = kernels.get(name).xla_ref(*args, **kw)
    np.testing.assert_array_equal(np.asarray(wrapper()),
                                  np.asarray(want))


# ----------------------------------------------------------------------
# flash attention through the registry (op level, pallas interpret)
# ----------------------------------------------------------------------

BH, SEQ, D, HEADS = 4, 64, 16, 2
B = BH // HEADS


def _mask_np(seed=1):
    rng = np.random.RandomState(seed)
    valid = rng.randint(SEQ // 2, SEQ + 1, (B,))
    m = np.zeros((B, SEQ, SEQ), np.float32)
    for i, n in enumerate(valid):
        m[i, :, :n] = 1.0
    return m


def test_masked_flash_op_pallas_backward_matches_xla():
    """The previously untested path: the op-level masked flash
    attention with the PALLAS kernels selected (interpret on CPU),
    forward AND custom-vjp backward, against the XLA reference path."""
    from mxnet_tpu.ops.transformer import _attention_reference_masked
    rng = np.random.RandomState(4)
    mnp = _mask_np()
    arrs = [rng.randn(BH, SEQ, D).astype(np.float32) for _ in range(3)]

    def run(use_pallas):
        q, k, v = (mx.nd.array(a) for a in arrs)
        mask = mx.nd.array(mnp)
        for t in (q, k, v):
            t.attach_grad()
        with autograd.record():
            out = mx.nd.flash_attention_masked(
                q, k, v, mask, heads=HEADS, use_pallas=use_pallas,
                block_q=32, block_k=32)
            loss = (out * out).sum()
        loss.backward()
        return (out.asnumpy(), q.grad.asnumpy(), k.grad.asnumpy(),
                v.grad.asnumpy())

    got = run(True)          # pallas interpret: fwd + blockwise bwd
    want = run(False)        # XLA reference custom-vjp
    for a, b, name in zip(got, want, ("out", "dq", "dk", "dv")):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-3,
                                   err_msg=name)
    ref = _attention_reference_masked(
        jnp.asarray(arrs[0]), jnp.asarray(arrs[1]), jnp.asarray(arrs[2]),
        jnp.repeat(jnp.asarray(mnp), HEADS, axis=0), 1.0 / np.sqrt(D))
    np.testing.assert_allclose(got[0], np.asarray(ref), rtol=2e-4,
                               atol=2e-4)


def test_causal_flash_op_pallas_matches_xla():
    rng = np.random.RandomState(5)
    arrs = [rng.randn(BH, SEQ, D).astype(np.float32) for _ in range(3)]

    def run(use_pallas):
        q, k, v = (mx.nd.array(a) for a in arrs)
        for t in (q, k, v):
            t.attach_grad()
        with autograd.record():
            out = mx.nd.flash_attention(q, k, v, causal=True,
                                        use_pallas=use_pallas,
                                        block_q=32, block_k=32)
            loss = (out * out).sum()
        loss.backward()
        return out.asnumpy(), q.grad.asnumpy()

    got = run(True)
    want = run(False)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-3)


def test_flash_kernels_run_per_shard_under_a_sharded_batch():
    """XLA cannot partition a Mosaic custom call (the TPU lowering
    refuses one inside a partitioned program), so while a program whose
    batch is dp-sharded is traced the flash ops run their kernels per
    shard through shard_map -- plain and masked, forward and backward,
    equal to the unsharded result."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from mxnet_tpu.ops.transformer import (_flash_attention_masked_op,
                                           _flash_attention_op)
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.mesh import batch_sharded
    rng = np.random.RandomState(6)
    q, k, v = (jnp.asarray(rng.randn(BH, SEQ, D), jnp.float32)
               for _ in range(3))
    mask = jnp.asarray(_mask_np())

    def loss(q, k, v, mask):
        a = _flash_attention_op.fcompute(q, k, v, use_pallas=True,
                                         block_q=32, block_k=32)
        b = _flash_attention_masked_op.fcompute(
            q, k, v, mask, use_pallas=True, heads=HEADS, block_q=32,
            block_k=32)
        return jnp.sum(a * a) + jnp.sum(b * b)

    grad = jax.value_and_grad(loss, argnums=(0, 1, 2))
    want = grad(q, k, v, mask)

    mesh = make_mesh({"dp": B})          # one batch row per device
    rows = NamedSharding(mesh, P("dp"))

    def sharded(q, k, v, mask):
        with batch_sharded(mesh, "dp"):
            return grad(q, k, v, mask)

    fn = jax.jit(sharded, in_shardings=(rows,) * 4)
    got = fn(q, k, v, mask)
    assert "manual_computation" in fn.lower(q, k, v, mask).as_text()
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)
    # outside the scope (or on a one-device axis) nothing is wrapped
    assert "manual_computation" not in jax.jit(grad).lower(
        q, k, v, mask).as_text()


def test_flash_selection_is_the_registry(monkeypatch):
    """One selection point: monkeypatching the registry's choose drives
    the op -- no residual per-call-site use_pallas branching."""
    calls = []
    real = kreg.choose

    def spy(name, force=None, **kw):
        ch = real(name, force=force, **kw)
        calls.append((name, force, ch.use_pallas))
        return ch
    # the op resolves `kernels.choose` at call time: patching the
    # package attribute intercepts every selection
    monkeypatch.setattr(kernels, "choose", spy)
    rng = np.random.RandomState(0)
    q = mx.nd.array(rng.randn(BH, SEQ, D).astype(np.float32))
    mx.nd.flash_attention(q, q, q, use_pallas=False)
    assert calls and calls[-1][0] == "flash_attention"
