"""Kernel tier (ISSUE 11): registry selection policy, fused BN+ReLU
numerics + vjp, flash-attention op-level pallas path (incl. the masked
backward), the bucket-flattened LARS/LAMB optimizer update, fallback
proof with Pallas monkeypatched unavailable, and the perf-audit
``remedy`` wiring.

Kernels run in interpret mode on the CPU test backend
(MXNET_TPU_KERNELS=1 + the registry's non-TPU policy); the same code
compiles on TPU.  Every numerics check is against the XLA reference
path and its autodiff.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, kernels
from mxnet_tpu.kernels import fused_bn_relu as fbr
from mxnet_tpu.kernels import optimizer_update as kopt
from mxnet_tpu.kernels import registry as kreg

pytestmark = pytest.mark.skipif(not kernels.available(),
                                reason="no pallas on this backend")


@pytest.fixture(autouse=True)
def _exact_matmuls():
    # kernel-vs-reference comparisons measure the algorithm, not the
    # CPU backend's reduced-precision matmul fast path
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture()
def kernels_on(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_KERNELS", "1")


# ----------------------------------------------------------------------
# registry selection policy
# ----------------------------------------------------------------------

def test_registry_lists_the_three_kernels():
    names = kernels.list_kernels()
    for want in ("fused_bn_relu", "flash_attention", "bucket_optimizer"):
        assert want in names, names


def test_choose_off_mode_kills_everything(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_KERNELS", "0")
    for name, kw in (("flash_attention",
                      dict(seq=512, block_q=256, block_k=256)),
                     ("fused_bn_relu", dict(axis=3, ndim=4)),
                     ("bucket_optimizer", {})):
        ch = kernels.choose(name, **kw)
        assert not ch.use_pallas and "MXNET_TPU_KERNELS=0" in ch.reason


def test_choose_auto_policy(monkeypatch):
    monkeypatch.delenv("MXNET_TPU_KERNELS", raising=False)
    # flash below the measured crossover: declined regardless of backend
    ch = kernels.choose("flash_attention", seq=128, block_q=256,
                        block_k=256)
    assert not ch.use_pallas and "auto policy" in ch.reason
    # bucket optimizer is opt-in: auto never selects it
    assert not kernels.choose("bucket_optimizer").use_pallas
    # above the crossover on CPU: XLA fallback with the backend named
    if jax.default_backend() != "tpu":
        ch = kernels.choose("flash_attention", seq=512, block_q=256,
                            block_k=256)
        assert not ch.use_pallas and "backend" in ch.reason


def test_choose_forced_runs_interpret_off_tpu(kernels_on):
    ch = kernels.choose("fused_bn_relu", axis=3, ndim=4)
    assert ch.use_pallas
    if jax.default_backend() != "tpu":
        assert ch.interpret


def test_supports_gate_beats_force(kernels_on):
    # NCHW input: the NHWC-native kernel must decline even when forced
    ch = kernels.choose("fused_bn_relu", force=True, axis=1, ndim=4)
    assert not ch.use_pallas and "NHWC" in ch.reason
    # non-divisible seq: flash declines
    ch = kernels.choose("flash_attention", force=True, seq=100,
                        block_q=32, block_k=32)
    assert not ch.use_pallas and "divisible" in ch.reason


def test_fallback_when_pallas_unavailable(monkeypatch, kernels_on):
    """The fallback proof: with Pallas monkeypatched away, every choice
    lands on the XLA path and the fused op still computes correctly."""
    monkeypatch.setattr(kreg, "_has_pallas", lambda: False)
    ch = kernels.choose("fused_bn_relu", force=True, axis=3, ndim=4)
    assert not ch.use_pallas and "unavailable" in ch.reason
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(2, 3, 3, 8).astype(np.float32))
    g = jnp.asarray(rng.rand(8).astype(np.float32) + 0.5)
    b = jnp.asarray(rng.randn(8).astype(np.float32))
    mm = jnp.zeros(8, jnp.float32)
    mv = jnp.ones(8, jnp.float32)
    out, _, _ = fbr.fused_bn_relu(x, g, b, mm, mv, fix_gamma=False,
                                  axis=3, training=True)
    ro, _, _ = fbr.xla_reference(x, g, b, mm, mv, fix_gamma=False,
                                 axis=3, training=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ro),
                               rtol=1e-6, atol=1e-6)


def test_remedy_mapping():
    assert kernels.remedy_for("unfused-elementwise") == \
        "kernels.fused_bn_relu"
    assert kernels.remedy_for("transpose-share") == \
        "kernels.fused_bn_relu"
    assert kernels.remedy_for("memory-bound") == \
        "kernels.flash_attention"
    assert kernels.remedy_for("no-such-kind") is None


def test_features_row(kernels_on):
    assert mx.runtime.Features().is_enabled("KERNELS")


def test_env_var_registered():
    from mxnet_tpu import env
    assert "MXNET_TPU_KERNELS" in env.REGISTRY


# ----------------------------------------------------------------------
# fused BN+ReLU: numerics + grad vs the XLA reference
# ----------------------------------------------------------------------

def _bn_inputs(seed=0, c=16, dtype=np.float32):
    rng = np.random.RandomState(seed)
    x = jnp.asarray((rng.randn(4, 5, 5, c) * 2 + 1).astype(dtype))
    gamma = jnp.asarray(rng.rand(c).astype(np.float32) + 0.5)
    beta = jnp.asarray(rng.randn(c).astype(np.float32))
    mm = jnp.asarray((rng.randn(c) * 0.1).astype(np.float32))
    mv = jnp.asarray(rng.rand(c).astype(np.float32) + 0.5)
    return x, gamma, beta, mm, mv


@pytest.mark.parametrize("training,use_global,fix_gamma", [
    (True, False, False), (True, False, True),
    (False, False, False), (True, True, False)])
def test_bn_relu_fwd_matches_reference(kernels_on, training, use_global,
                                       fix_gamma):
    x, gamma, beta, mm, mv = _bn_inputs()
    kw = dict(fix_gamma=fix_gamma, use_global_stats=use_global, axis=3,
              training=training)
    out, nm, nv = fbr.fused_bn_relu(x, gamma, beta, mm, mv, **kw)
    ro, rm, rv = fbr.xla_reference(x, gamma, beta, mm, mv, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ro),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(nm), np.asarray(rm),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(nv), np.asarray(rv),
                               rtol=1e-5, atol=1e-6)
    assert np.asarray(out).min() >= 0.0        # the relu epilogue


def test_bn_relu_grads_match_reference(kernels_on):
    """The custom-vjp backward (relu mask + training-stats backward
    folded into one dx pass) against autodiff of the unfused path."""
    x, gamma, beta, mm, mv = _bn_inputs(2)

    def loss(fn, x, g, b):
        o, _, _ = fn(x, g, b, mm, mv, fix_gamma=False, axis=3,
                     training=True)
        return jnp.sum(o * jnp.cos(o))         # nontrivial cotangent

    gf = jax.grad(lambda *a: loss(fbr.fused_bn_relu, *a),
                  argnums=(0, 1, 2))(x, gamma, beta)
    gr = jax.grad(lambda *a: loss(fbr.xla_reference, *a),
                  argnums=(0, 1, 2))(x, gamma, beta)
    for a, b, name in zip(gf, gr, ("dx", "dgamma", "dbeta")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5, err_msg=name)


def test_bn_relu_bf16_activations_fp32_stats(kernels_on):
    """bf16 in, fp32 batch statistics: the running stats match the
    reference's fp32 accumulation and the output dtype stays bf16."""
    import jax.numpy as jnp2
    x, gamma, beta, mm, mv = _bn_inputs(3)
    xb = x.astype(jnp2.bfloat16)
    out, nm, nv = fbr.fused_bn_relu(xb, gamma, beta, mm, mv,
                                    fix_gamma=False, axis=3,
                                    training=True)
    ro, rm, rv = fbr.xla_reference(xb, gamma, beta, mm, mv,
                                   fix_gamma=False, axis=3,
                                   training=True)
    assert out.dtype == jnp2.bfloat16
    assert nm.dtype == jnp2.float32 and nv.dtype == jnp2.float32
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ro, np.float32),
                               rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(nm), np.asarray(rm),
                               rtol=1e-4, atol=1e-5)


def test_gluon_fusion_site_pairs_bn_relu(kernels_on):
    """HybridSequential pairs BatchNorm + relu Activation through the
    fused op; the training trajectory (params AND running stats) stays
    identical to the unfused path."""
    x = mx.nd.array(np.random.RandomState(0)
                    .rand(2, 6, 6, 3).astype(np.float32))
    y = mx.nd.array(np.random.RandomState(1)
                    .rand(2, 4).astype(np.float32))

    def train3(on, monkey=None):
        import os
        if on:
            os.environ["MXNET_TPU_KERNELS"] = "1"
        else:
            os.environ.pop("MXNET_TPU_KERNELS", None)
        try:
            np.random.seed(0)
            mx.random.seed(0)
            net = gluon.nn.HybridSequential()
            net.add(gluon.nn.Conv2D(8, 3, padding=1, layout="NHWC"),
                    gluon.nn.BatchNorm(axis=3),
                    gluon.nn.Activation("relu"),
                    gluon.nn.Flatten(), gluon.nn.Dense(4))
            net.initialize(ctx=mx.cpu(), force_reinit=True)
            net.hybridize()
            tr = gluon.Trainer(net.collect_params(), "sgd",
                               {"learning_rate": 0.1}, kvstore=None)
            lf = gluon.loss.L2Loss()
            for _ in range(3):
                with autograd.record():
                    loss = lf(net(x), y).mean()
                loss.backward()
                tr.step(2)
            return (float(loss.asscalar()),
                    [p.data().asnumpy()
                     for p in net.collect_params().values()])
        finally:
            os.environ["MXNET_TPU_KERNELS"] = "1"
    l_off, p_off = train3(False)
    l_on, p_on = train3(True)
    assert abs(l_off - l_on) < 1e-5
    for a, b in zip(p_on, p_off):
        np.testing.assert_allclose(a, b, rtol=5e-4, atol=5e-5)


def test_fusion_plan_inactive_without_env(monkeypatch):
    monkeypatch.delenv("MXNET_TPU_KERNELS", raising=False)
    from mxnet_tpu.gluon.nn.basic_layers import _bn_relu_fusion_plan
    bn = gluon.nn.BatchNorm(axis=3)
    act = gluon.nn.Activation("relu")
    plan = _bn_relu_fusion_plan([bn, act])
    assert plan == [(bn, False), (act, False)]
    monkeypatch.setenv("MXNET_TPU_KERNELS", "1")
    plan = _bn_relu_fusion_plan([bn, act])
    assert plan == [(bn, True)]
    # a non-relu activation never pairs
    tanh = gluon.nn.Activation("tanh")
    assert _bn_relu_fusion_plan([bn, tanh]) == [(bn, False),
                                                (tanh, False)]


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


def test_masked_flash_op_pallas_backward_matches_xla(kernels_on):
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


def test_causal_flash_op_pallas_matches_xla(kernels_on):
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


def test_flash_kernels_run_per_shard_under_a_sharded_batch(kernels_on):
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
        a = _flash_attention_op.fcompute(q, k, v, block_q=32, block_k=32)
        b = _flash_attention_masked_op.fcompute(
            q, k, v, mask, heads=HEADS, block_q=32, block_k=32)
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


# ----------------------------------------------------------------------
# bucket-flattened optimizer update
# ----------------------------------------------------------------------

def _param_set(seed=0):
    rng = np.random.RandomState(seed)
    shapes = [(7, 5), (16,), (3, 4, 2), (9,)]
    ws = [jnp.asarray(rng.randn(*s).astype(np.float32)) for s in shapes]
    gs = [jnp.asarray(rng.randn(*s).astype(np.float32)) for s in shapes]
    ss = [jnp.asarray((rng.randn(*s) * 0.1).astype(np.float32))
          for s in shapes]
    return ws, gs, ss


def test_lars_bucket_matches_per_param_ops(kernels_on):
    """One flat buffer reproduces nd.lars_update / nd.sgd_mom_update
    per tensor, including the skip list, clip, and both momentum sign
    conventions (state stays checkpoint-compatible)."""
    from mxnet_tpu import nd
    ws, gs, ms = _param_set()
    lrs = [0.1, 0.2, 0.05, 0.15]
    wds = [1e-4, 0.0, 1e-4, 5e-5]
    skips = [False, True, False, True]
    ref_w, ref_m = [], []
    for i in range(4):
        if skips[i]:
            w2, m2 = nd.sgd_mom_update(
                nd.NDArray(ws[i]), nd.NDArray(gs[i]), nd.NDArray(ms[i]),
                momentum=0.9, lr=lrs[i], wd=wds[i], rescale_grad=0.5,
                clip_gradient=1.0)
        else:
            w2, m2 = nd.lars_update(
                nd.NDArray(ws[i]), nd.NDArray(gs[i]), nd.NDArray(ms[i]),
                momentum=0.9, eta=0.001, epsilon=1e-9, lr=lrs[i],
                wd=wds[i], rescale_grad=0.5, clip_gradient=1.0)
        ref_w.append(w2.asnumpy())
        ref_m.append(m2.asnumpy())
    nws, nms = kopt.lars_bucket_update(
        ws, gs, ms, lrs, wds, skips, momentum=0.9, eta=0.001,
        epsilon=1e-9, rescale=0.5, clip=1.0)
    for i in range(4):
        np.testing.assert_allclose(np.asarray(nws[i]), ref_w[i],
                                   rtol=2e-5, atol=2e-6)
        sign = -1.0 if skips[i] else 1.0
        np.testing.assert_allclose(sign * np.asarray(nms[i]),
                                   sign * ref_m[i], rtol=2e-5,
                                   atol=2e-6)


def test_lamb_bucket_matches_per_param_ops(kernels_on):
    from mxnet_tpu import nd
    ws, gs, means = _param_set(1)
    _ws2, _gs2, vrs = _param_set(2)
    vrs = [jnp.abs(v) * 0.1 for v in vrs]
    lrs = [0.1, 0.2, 0.05, 0.15]
    wds = [1e-4, 0.0, 1e-4, 5e-5]
    t = 3
    ref_w, ref_m, ref_v = [], [], []
    for i in range(4):
        g2, m2, v2 = nd.lamb_update_phase1(
            nd.NDArray(ws[i]), nd.NDArray(gs[i]), nd.NDArray(means[i]),
            nd.NDArray(vrs[i]), beta1=0.9, beta2=0.999, epsilon=1e-6,
            t=t, bias_correction=True, wd=wds[i], rescale_grad=0.5,
            clip_gradient=1.0)
        w2 = nd.lamb_update_phase2(
            nd.NDArray(ws[i]), g2, nd.NDArray(ws[i]).norm(), g2.norm(),
            lr=lrs[i], lower_bound=0.01, upper_bound=10.0)
        ref_w.append(w2.asnumpy())
        ref_m.append(m2.asnumpy())
        ref_v.append(v2.asnumpy())
    nws, nmn, nvr = kopt.lamb_bucket_update(
        ws, gs, means, vrs, lrs, wds, t, beta1=0.9, beta2=0.999,
        epsilon=1e-6, bias_correction=True, lower_bound=0.01,
        upper_bound=10.0, rescale=0.5, clip=1.0)
    for i in range(4):
        np.testing.assert_allclose(np.asarray(nws[i]), ref_w[i],
                                   rtol=2e-5, atol=2e-6)
        np.testing.assert_allclose(np.asarray(nmn[i]), ref_m[i],
                                   rtol=2e-5, atol=2e-6)
        np.testing.assert_allclose(np.asarray(nvr[i]), ref_v[i],
                                   rtol=2e-5, atol=2e-7)


def test_bucket_groups_by_dtype(kernels_on):
    """Mixed-dtype parameter sets flatten into one buffer PER dtype
    (the shared mxnet_tpu.bucketing grouping)."""
    rng = np.random.RandomState(0)
    ws = [jnp.asarray(rng.randn(8).astype(np.float32)),
          jnp.asarray(rng.randn(4, 4).astype(np.float16)),
          jnp.asarray(rng.randn(6).astype(np.float32))]
    gs = [jnp.asarray(rng.randn(*w.shape).astype(w.dtype)) for w in ws]
    ms = [jnp.zeros_like(w) for w in ws]
    nws, nms = kopt.lars_bucket_update(
        ws, gs, ms, [0.1] * 3, [0.0] * 3, [False] * 3)
    for w, nw, nm in zip(ws, nws, nms):
        assert nw.dtype == w.dtype and nw.shape == w.shape
        assert nm.dtype == w.dtype


def test_trainstep_bucket_matches_loop():
    """The compiled train step with MXNET_TPU_KERNELS=1 (bucketed
    update) follows the identical trajectory as the per-parameter
    update loop, for LARS and LAMB."""
    import os
    from mxnet_tpu.parallel import TrainStep
    x = mx.nd.array(np.random.RandomState(0)
                    .rand(8, 16).astype(np.float32))
    y = mx.nd.array(np.random.RandomState(1)
                    .rand(8, 4).astype(np.float32))

    def run(optname, kw, on):
        if on:
            os.environ["MXNET_TPU_KERNELS"] = "1"
        else:
            os.environ.pop("MXNET_TPU_KERNELS", None)
        try:
            np.random.seed(0)
            mx.random.seed(0)
            net = gluon.nn.HybridSequential()
            net.add(gluon.nn.Dense(16, activation="relu"),
                    gluon.nn.Dense(4))
            net.initialize(ctx=mx.cpu())
            net.hybridize()
            tr = gluon.Trainer(net.collect_params(), optname, kw,
                               kvstore=None)
            step = TrainStep(net, gluon.loss.L2Loss(), tr, mesh=None)
            return [float(step(x, y).asscalar()) for _ in range(4)]
        finally:
            os.environ.pop("MXNET_TPU_KERNELS", None)

    for name, kw in (("lars", {"learning_rate": 0.05, "momentum": 0.9}),
                     ("lamb", {"learning_rate": 0.01})):
        l_off = run(name, kw, False)
        l_on = run(name, kw, True)
        assert all(abs(a - b) < 2e-5 for a, b in zip(l_off, l_on)), \
            (name, l_off, l_on)


def test_flat_lars_custom_vjp_matches_autodiff(kernels_on):
    """The flat kernel's custom-vjp backward equals autodiff of the
    plain math (trust folded into the lr input)."""
    rng = np.random.RandomState(0)
    W = jnp.asarray(rng.randn(300).astype(np.float32))
    G = jnp.asarray(rng.randn(300).astype(np.float32))
    M = jnp.asarray((rng.randn(300) * 0.1).astype(np.float32))
    lr = jnp.full((300,), 0.1, jnp.float32)
    wd = jnp.full((300,), 1e-4, jnp.float32)
    sg = jnp.ones((300,), jnp.float32)

    def f(impl_pallas, W, G, M):
        nw, nm = kopt._flat_lars(W, G, M, lr, wd, sg,
                                 jnp.float32(0.5), 0.9, 0.0,
                                 impl_pallas, impl_pallas)
        return jnp.sum(nw * nw) + jnp.sum(nm)

    def f_plain(W, G, M):
        nw, nm = kopt._lars_math(W, G, M, lr, wd, sg,
                                 jnp.float32(0.5), 0.9, 0.0)
        return jnp.sum(nw * nw) + jnp.sum(nm)

    want = jax.grad(f_plain, argnums=(0, 1, 2))(W, G, M)
    for impl in (True, False):
        got = jax.grad(lambda *a: f(impl, *a), argnums=(0, 1, 2))(W, G, M)
        for a, b in zip(got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)


# ----------------------------------------------------------------------
# perf-audit remedy wiring
# ----------------------------------------------------------------------

def test_perf_advisories_carry_remedy():
    from mxnet_tpu.analysis import perf
    metrics = {"transpose_share": 0.5,
               "unfused_elementwise_share": 0.3,
               "unfused_elementwise_count": 4, "pad_waste": 0.0,
               "intensity": 100.0, "flops": 1e9, "bytes": 1e7}
    counters = {"transpose_ops": {"scope": 123}}
    adv = perf._advisories_for("lbl", metrics, counters, ridge=10.0,
                               thresholds=perf.THRESHOLDS)
    by_kind = {a["kind"]: a for a in adv}
    assert by_kind["unfused-elementwise"]["remedy"] == \
        "kernels.fused_bn_relu"
    assert by_kind["transpose-share"]["remedy"] == \
        "kernels.fused_bn_relu"
    # memory-bound advisory names the flash kernel
    metrics2 = dict(metrics, transpose_share=0.0,
                    unfused_elementwise_share=0.0, intensity=0.1)
    adv2 = perf._advisories_for("lbl", metrics2, counters, ridge=10.0,
                                thresholds=perf.THRESHOLDS)
    by_kind2 = {a["kind"]: a for a in adv2}
    assert by_kind2["memory-bound"]["remedy"] == \
        "kernels.flash_attention"


def test_perf_diff_renders_remedy():
    from mxnet_tpu.analysis import perf
    base = {"schema": perf.AUDIT_SCHEMA, "executables": {}}
    cur = {"schema": perf.AUDIT_SCHEMA, "executables": {
        "train_step:Net": {
            "metrics": {"transpose_share": 0.0,
                        "unfused_elementwise_share": 0.4,
                        "pad_waste": 0.0, "intensity": 1.0},
            "advisories": [{"kind": "unfused-elementwise",
                            "category": "elementwise_fusion",
                            "share": 0.4, "op_names": [],
                            "remedy": "kernels.fused_bn_relu",
                            "message": "40% unfused"}]}}}
    diags = perf.diff_audit(base, cur)
    assert any("remedy: kernels.fused_bn_relu" in d.message
               for d in diags), [d.message for d in diags]


# ----------------------------------------------------------------------
# bench probe (real, slow): the kernel-tier HLO diff contract
# ----------------------------------------------------------------------

@pytest.mark.slow
def test_bench_kernels_diff_real_probe(monkeypatch):
    import os
    import sys
    monkeypatch.syspath_prepend(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    import bench
    diff = bench._kernels_diff("resnet")
    assert diff is not None
    for key in ("probe", "after_interpret", "before", "after", "delta"):
        assert key in diff, key
    assert diff["before"]["bytes_total"] > 0
