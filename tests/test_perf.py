"""perflint (ISSUE 10): per-rule static fixtures, the compiled-HLO
audit contract on a transpose-seeded toy executable, the perf-baseline
round trip, the model_zoo layout threading, and regression tests for
the ride-along bugfixes (bench e2e constructor cleanup, bench
subprocess diagnostics, bulk enqueue stale-resolution outside the
lock, ImageIter's __main__.__file__ confinement)."""
import json
import os
import sys
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import analysis as an
from mxnet_tpu import gluon
from mxnet_tpu.analysis import perf

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _rules_of(diags):
    return sorted({d.rule for d in diags})


def _lint(src):
    return an.lint_source(src, "probe.py")


# ----------------------------------------------------------------------
# static rules: one positive and one negative fixture per rule
# ----------------------------------------------------------------------

def test_layout_hostile_conv_fires_and_explicit_layout_silent():
    bad = (
        "def build(nn):\n"
        "    net.add(nn.Conv2D(32, kernel_size=3))\n"
        "    net.add(nn.MaxPool2D(2))\n"
        "    net.add(nn.GlobalAvgPool2D())\n"
    )
    diags = _lint(bad)
    assert _rules_of(diags) == ["layout-hostile-conv"]
    assert len(diags) == 3
    good = (
        "def build(nn, layout):\n"
        "    net.add(nn.Conv2D(32, kernel_size=3, layout=layout))\n"
        "    net.add(nn.MaxPool2D(2, layout='NHWC'))\n"
        "    net.add(nn.Dense(64))\n"          # Dense has no layout
    )
    assert _lint(good) == []


def test_layout_hostile_conv_kwargs_splat_not_decidable():
    src = (
        "def build(nn, kw):\n"
        "    net.add(nn.Conv2D(32, 3, **kw))\n"
    )
    assert _lint(src) == []


def test_pad_waste_fires_with_did_you_mean_and_aligned_silent():
    bad = "def build(nn, layout):\n    nn.Dense(500)\n"
    diags = _lint(bad)
    assert _rules_of(diags) == ["pad-waste"]
    assert "did you mean 512" in diags[0].message
    # aligned, non-literal, and structurally-small dims all pass
    good = (
        "def build(nn, c, layout):\n"
        "    nn.Dense(512)\n"
        "    nn.Dense(c)\n"
        "    nn.Dense(10)\n"                   # class head: < 16
        "    nn.Conv2D(64, 3, layout=layout)\n"
    )
    assert _lint(good) == []
    # sublane-misaligned conv channels name the sublane multiple
    d = _lint("def f(nn, layout):\n"
              "    nn.Conv2D(20, 5, layout=layout)\n")
    assert _rules_of(d) == ["pad-waste"]
    assert "did you mean 24" in d[0].message


def test_python_loop_unroll_fires_in_traced_scopes_only():
    bad = (
        "class M:\n"
        "    def hybrid_forward(self, F, x):\n"
        "        for i in range(8):\n"
        "            x = F.relu(x)\n"
        "        for cell in self.cells:\n"
        "            x = cell(x)\n"
        "        return x\n"
    )
    diags = _lint(bad)
    assert _rules_of(diags) == ["python-loop-unroll"]
    assert len(diags) == 2
    good = (
        "class M:\n"
        "    def hybrid_forward(self, F, x):\n"
        "        for i in range(2):\n"         # below unroll threshold
        "            x = F.relu(x)\n"
        "        return x\n"
        "def driver(step, x, y):\n"
        "    for _ in range(100):\n"           # eager driver loop: fine
        "        loss = train(x, y)\n"
        "    return loss\n"
    )
    assert _lint(good) == []


def test_python_loop_unroll_fires_in_jitted_step_fn():
    bad = (
        "import jax\n"
        "def train_step(pvals, x):\n"
        "    for i in range(16):\n"
        "        x = x * 2\n"
        "    return x\n"
        "fn = jax.jit(train_step, donate_argnums=(0,))\n"
    )
    assert "python-loop-unroll" in _rules_of(_lint(bad))


def test_scalar_recompile_fires_outside_dynamic_set_only():
    bad = (
        "def update(nd, w, g, scale):\n"
        "    return nd.cast_scale(w, g, loss_scale=scale)\n"
    )
    diags = _lint(bad)
    assert _rules_of(diags) == ["scalar-recompile"]
    assert "loss_scale" in diags[0].message
    good = (
        "def update(nd, w, g, cur_lr, scale):\n"
        "    a = nd.sgd_update(w, g, lr=cur_lr)\n"   # lr IS dynamic
        "    b = nd.cast_scale(w, g, loss_scale=2.0)\n"  # literal: one key
        "    helper(loss_scale=scale)\n"             # not an op invoke
        "    return a, b\n"
    )
    assert _lint(good) == []


def test_eager_in_step_loop_fires_and_ingest_exempt():
    bad = (
        "def train(step, nd, batches):\n"
        "    for x, y in batches:\n"
        "        x = nd.transpose(x, axes=(0, 2, 3, 1))\n"
        "        loss = step(x, y)\n"
        "    return loss\n"
    )
    diags = _lint(bad)
    assert _rules_of(diags) == ["eager-in-step-loop"]
    assert "nd.transpose" in diags[0].message
    good = (
        "def train(step, mx, shards):\n"
        "    for s in shards:\n"
        "        x = mx.nd.array(s)\n"          # ingest: exempt
        "        loss = step(x)\n"
        "    for s in shards:\n"
        "        y = mx.nd.transpose(s)\n"      # no step() in this loop\n"
        "    return loss, y\n"
    )
    assert _lint(good) == []


def test_perf_rule_suppression_directive():
    src = ("def build(nn, layout):\n"
           "    nn.Dense(500)  # mxlint: disable=pad-waste\n")
    assert _lint(src) == []


def test_perf_rules_registered_and_self_lint_clean():
    for rid in ("layout-hostile-conv", "pad-waste", "python-loop-unroll",
                "scalar-recompile", "eager-in-step-loop", "perf-drift"):
        assert rid in an.RULES, rid
    # the armed-rules acceptance: the model code the rules forced into
    # shape stays clean (full --self runs in CI; model_zoo+bench here)
    diags = an.lint_paths([os.path.join(REPO, "mxnet_tpu", "gluon",
                                        "model_zoo"),
                           os.path.join(REPO, "bench.py")])
    assert [d.format() for d in diags] == []


# ----------------------------------------------------------------------
# compiled audit: advisory contract on a transpose-seeded toy
# ----------------------------------------------------------------------

def _register_toy(label, fn, *args):
    import jax
    from mxnet_tpu.profiling import store
    jfn = jax.jit(fn)
    jfn(*args)
    store.register((label,), label, jfn, args)
    return jfn


def test_perf_audit_transpose_advisory_contract():
    import jax.numpy as jnp
    from mxnet_tpu import profiling
    profiling.reset()
    _register_toy("toy:transpose",
                  lambda x: jnp.transpose(x, (1, 0)) + 0.0,
                  jnp.ones((256, 512), jnp.float32))
    audit = perf.perf_audit(peaks=(5e11, 5e10))
    assert audit["schema"] == perf.AUDIT_SCHEMA
    ex = audit["executables"]["toy:transpose"]
    assert ex["metrics"]["transpose_share"] > 0.9
    kinds = {a["kind"]: a for a in ex["advisories"]}
    assert "transpose-share" in kinds
    adv = kinds["transpose-share"]
    assert adv["category"] == "transpose_layout"
    assert adv["share"] > 0.9
    assert any("transpose" in nm for nm in adv["op_names"])
    # ranked advisories carry the executable name
    assert any(a["executable"] == "toy:transpose" and
               a["kind"] == "transpose-share"
               for a in audit["advisories"])
    profiling.reset()


def test_perf_audit_compute_bound_matmul_clean():
    import jax.numpy as jnp
    from mxnet_tpu import profiling
    profiling.reset()
    _register_toy("toy:matmul",
                  lambda a, b: a @ b,
                  jnp.ones((256, 256), jnp.float32),
                  jnp.ones((256, 256), jnp.float32))
    # generous peaks: ridge tiny, so a tile-aligned matmul audits clean
    audit = perf.perf_audit(peaks=(1e9, 1e12))
    ex = audit["executables"]["toy:matmul"]
    assert ex["advisories"] == [], ex
    assert ex["metrics"]["pad_waste"] == 0.0
    assert ex["metrics"]["flops"] > 0
    profiling.reset()


def test_audit_hlo_text_counters_direct():
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.transpose(x, (1, 0)).copy())
    x = jnp.ones((128, 128), jnp.float32)
    text = f.lower(x).compile().as_text()
    c = perf.audit_hlo_text(text)
    assert c["bytes_total"] > 0
    assert c["category_bytes"]["transpose_layout"] > 0
    assert c["mxu_padded_bytes"] == 0        # no conv/dot in the module


# ----------------------------------------------------------------------
# baseline round trip: bless -> self-diff zero -> seeded regression
# ----------------------------------------------------------------------

def test_perf_baseline_round_trip(tmp_path):
    import jax.numpy as jnp
    from mxnet_tpu import profiling
    profiling.reset()
    _register_toy("toy:roundtrip",
                  lambda x: jnp.transpose(x, (1, 0)) + 0.0,
                  jnp.ones((128, 256), jnp.float32))
    base_path = str(tmp_path / "perf_baseline.json")
    base = perf.save_audit(base_path, perf.perf_audit(peaks=(5e11, 5e10)))
    assert perf.load_audit(base_path)["schema"] == perf.AUDIT_SCHEMA

    # self-diff: zero drift, CLI exit 0
    assert perf.diff_audit(base, base) == []
    assert an.main(["--perf-diff", base_path, base_path]) == 0

    # seeded transpose regression: grown share + unblessed advisory kind
    cur = json.loads(json.dumps(base))
    row = cur["executables"]["toy:roundtrip"]
    row["metrics"]["transpose_share"] = \
        base["executables"]["toy:roundtrip"]["metrics"][
            "transpose_share"] + 0.1
    row["advisories"].append({"kind": "hlo-pad-waste",
                              "category": "conv_dot", "share": 0.5,
                              "op_names": [], "message": "seeded"})
    cur_path = str(tmp_path / "current.json")
    with open(cur_path, "w") as f:
        json.dump(cur, f)
    diags = perf.diff_audit(base, perf.load_audit(cur_path))
    kinds = {d.rule for d in diags}
    assert kinds == {"perf-drift"}
    msgs = "\n".join(d.message for d in diags)
    assert "transpose_share grew" in msgs
    assert "hlo-pad-waste" in msgs
    assert an.main(["--perf-diff", base_path, cur_path]) == 1

    # improvements pass: smaller share, advisory gone
    better = json.loads(json.dumps(base))
    better["executables"]["toy:roundtrip"]["metrics"][
        "transpose_share"] = 0.0
    better["executables"]["toy:roundtrip"]["advisories"] = []
    assert perf.diff_audit(base, better) == []
    profiling.reset()


def test_perf_audit_schema_reject(tmp_path):
    p = tmp_path / "bogus.json"
    p.write_text(json.dumps({"schema": "nope", "executables": {}}))
    with pytest.raises(ValueError, match="mxperf.audit.v1"):
        perf.load_audit(str(p))
    assert an.main(["--perf-diff", str(p), str(p)]) == 2


def test_committed_perf_baseline_is_loadable():
    base = perf.load_audit(os.path.join(REPO, "ci", "perf_baseline.json"))
    labels = set(base["executables"])
    assert "train_step:PerfLeNet" in labels
    assert "hybrid:ResNetV1" in labels


# ----------------------------------------------------------------------
# model_zoo layout threading (the layout-hostile-conv fixes)
# ----------------------------------------------------------------------

def _pair_and_copy(a, b):
    """Copy a's weights into b, permuting conv kernels OIHW -> OHWI."""
    from conftest import paired_params
    for pa, pb in paired_params(a, b):
        w = pa.data().asnumpy()
        if w.ndim == 4 and "conv" in pa.name:
            w = np.transpose(w, (0, 2, 3, 1))
        assert pb.shape == w.shape, (pa.name, pb.shape, w.shape)
        pb.set_data(mx.nd.array(w))


def test_densenet_nhwc_matches_nchw():
    """Tiny DenseNet: covers BatchNorm axis AND the dense-block concat
    following layout.index('C')."""
    from mxnet_tpu.gluon.model_zoo.vision.densenet import DenseNet
    rng = np.random.RandomState(0)
    x = rng.randn(2, 3, 16, 16).astype(np.float32)

    a = DenseNet(8, 4, [2, 2], classes=7, layout="NCHW")
    a.initialize(ctx=mx.cpu())
    ya = a(mx.nd.array(x)).asnumpy()

    b = DenseNet(8, 4, [2, 2], classes=7, layout="NHWC")
    b.initialize(ctx=mx.cpu())
    xb = mx.nd.array(np.transpose(x, (0, 2, 3, 1)))
    b(xb)                                    # materialize deferred shapes
    _pair_and_copy(a, b)
    yb = b(xb).asnumpy()
    np.testing.assert_allclose(ya, yb, rtol=1e-4, atol=1e-4)


def test_fire_and_mixed_blocks_nhwc_match_nchw():
    """SqueezeNet fire paths + inception towers: the two remaining
    concat-on-channels code paths."""
    from mxnet_tpu.gluon.model_zoo.vision.inception import (_Mixed,
                                                            _Tower)
    from mxnet_tpu.gluon.model_zoo.vision.squeezenet import _FirePaths
    rng = np.random.RandomState(1)
    x = rng.randn(2, 4, 8, 8).astype(np.float32)

    for build in (
            lambda lo: _FirePaths(8, 8, layout=lo),
            lambda lo: _Mixed([_Tower([(8, 1, 1, 0)], layout=lo),
                               _Tower([(4, 3, 1, 1)], layout=lo)],
                              layout=lo)):
        a = build("NCHW")
        a.initialize(ctx=mx.cpu())
        ya = a(mx.nd.array(x)).asnumpy()
        b = build("NHWC")
        b.initialize(ctx=mx.cpu())
        xb = mx.nd.array(np.transpose(x, (0, 2, 3, 1)))
        b(xb)
        _pair_and_copy(a, b)
        yb = b(xb).asnumpy()
        np.testing.assert_allclose(ya, np.transpose(yb, (0, 3, 1, 2)),
                                   rtol=1e-4, atol=1e-4)


def test_model_zoo_layout_kwarg_accepted_everywhere():
    """Every vision constructor takes layout= (the threading contract);
    construction alone must not raise."""
    from mxnet_tpu.gluon.model_zoo import vision
    for ctor in (vision.alexnet, vision.vgg11, vision.squeezenet1_1,
                 vision.densenet121, vision.mobilenet0_25,
                 vision.mobilenet_v2_0_25, vision.inception_v3,
                 vision.resnet18_v1):
        net = ctor(classes=10, layout="NHWC")
        assert net is not None


@pytest.mark.slow
def test_mobilenet_nhwc_matches_nchw():
    """Depthwise/grouped convs through the channels-last path."""
    from mxnet_tpu.gluon.model_zoo.vision.mobilenet import MobileNet
    rng = np.random.RandomState(2)
    x = rng.randn(1, 3, 32, 32).astype(np.float32)
    a = MobileNet(multiplier=0.25, classes=7, layout="NCHW")
    a.initialize(ctx=mx.cpu())
    ya = a(mx.nd.array(x)).asnumpy()
    b = MobileNet(multiplier=0.25, classes=7, layout="NHWC")
    b.initialize(ctx=mx.cpu())
    xb = mx.nd.array(np.transpose(x, (0, 2, 3, 1)))
    b(xb)
    _pair_and_copy(a, b)
    yb = b(xb).asnumpy()
    np.testing.assert_allclose(ya, yb, rtol=1e-4, atol=1e-4)


# ----------------------------------------------------------------------
# satellite regressions: bench e2e constructor cleanup + subprocess tail
# ----------------------------------------------------------------------

def _bench_mod():
    sys.path.insert(0, REPO)
    try:
        import bench
    finally:
        sys.path.pop(0)
    return bench


def test_cpu_subprocess_failure_raises_with_stderr_tail():
    bench = _bench_mod()
    with pytest.raises(RuntimeError) as ei:
        bench._cpu_subprocess_value("bench.no_such_function()",
                                    timeout=120)
    msg = str(ei.value)
    assert "exited" in msg and "AttributeError" in msg


def test_bench_e2e_constructor_failure_cleans_up(monkeypatch):
    """A constructor failing inside bench_resnet50_e2e must propagate
    immediately (no producer deadlock) with the tmp dir removed and the
    telemetry enable-state restored (ADVICE round-5 medium)."""
    import glob
    import mxnet_tpu.image as image_mod
    from mxnet_tpu import telemetry
    from mxnet_tpu.base import MXNetError
    import mxnet_tpu.gluon.model_zoo.vision as vision_mod
    bench = _bench_mod()

    def tiny_net(**kw):
        net = gluon.nn.HybridSequential()
        net.add(gluon.nn.Flatten(), gluon.nn.Dense(8))
        return net

    class BoomIter:
        def __init__(self, *a, **kw):
            raise MXNetError("seeded ImageIter constructor failure")

    monkeypatch.setattr(vision_mod, "resnet50_v1", tiny_net)
    monkeypatch.setattr(image_mod, "ImageIter", BoomIter)
    was_enabled = telemetry.enabled()
    before = set(glob.glob("/tmp/mxtpu_bench_e2e_*"))
    t0 = time.time()
    with pytest.raises(MXNetError, match="seeded ImageIter"):
        bench.bench_resnet50_e2e(batch_size=2, n_images=4, epochs=1)
    assert time.time() - t0 < 120          # surfaced, not a hang
    assert telemetry.enabled() == was_enabled
    assert set(glob.glob("/tmp/mxtpu_bench_e2e_*")) == before


# ----------------------------------------------------------------------
# satellite regression: bulk enqueue resolves stale inputs off-lock
# ----------------------------------------------------------------------

def test_bulk_enqueue_stale_wait_does_not_hold_lock():
    """An enqueue whose input belongs to another region's in-flight
    execution must park on that region's done event WITHOUT holding the
    global bulk lock -- other threads' eager dispatch keeps flowing."""
    import jax.numpy as jnp
    from mxnet_tpu.ndarray import bulk
    if not bulk.enabled():
        pytest.skip("bulking disabled")
    bulk.flush()

    fnc = lambda x: x + 1.0  # noqa: E731
    tag = "perrequire_stale_probe"
    x0 = jnp.ones((4,), jnp.float32)
    warm = bulk.enqueue(fnc, tag, (x0,))       # warmup: concrete out
    assert not isinstance(warm, bulk.LazyData)

    reg = bulk._Region()                       # an "executing" region
    ld = bulk.LazyData((4,), jnp.float32, 0, region=reg)
    out = {}

    def worker():
        out["val"] = bulk.enqueue(fnc, tag, (ld,))

    t = threading.Thread(target=worker, daemon=True)
    t.start()
    time.sleep(0.3)                            # let it park on reg.done
    assert t.is_alive()
    got = bulk._LOCK.acquire(blocking=False)   # lock must be free
    assert got, "enqueue holds the bulk lock while waiting on a region"
    bulk._LOCK.release()
    ld._concrete = jnp.zeros((4,), jnp.float32)
    reg.done.set()
    t.join(timeout=10)
    assert not t.is_alive()
    res = bulk.materialize(out["val"])
    np.testing.assert_allclose(np.asarray(res), np.ones((4,)))
    bulk.flush()


def test_bulk_enqueue_recomputes_descr_after_resolution():
    """A resolved LazyData input keys the region as a concrete array
    ('arr'), not as 'lazyaval' -- the region replay cache cannot split
    on how the same value arrived."""
    import jax.numpy as jnp
    from mxnet_tpu.ndarray import bulk
    if not bulk.enabled():
        pytest.skip("bulking disabled")
    bulk.flush()

    fnc = lambda x: x * 2.0  # noqa: E731
    tag = "perfdescr_probe"
    x0 = jnp.ones((4,), jnp.float32)
    bulk.enqueue(fnc, tag, (x0,))              # warmup
    ld = bulk.enqueue(fnc, tag, (x0,))         # pending LazyData
    assert isinstance(ld, bulk.LazyData)
    bulk.flush()                               # resolves ld
    assert ld._concrete is not None
    out = bulk.enqueue(fnc, tag, (ld,))        # resolved input
    with bulk._LOCK:
        assert bulk._key_parts, "expected a pending entry"
        descr = bulk._key_parts[-1][3]
    assert descr[0][0] == "arr", descr
    np.testing.assert_allclose(np.asarray(bulk.materialize(out)),
                               4 * np.ones((4,)))
    bulk.flush()


def test_bulk_enqueue_failed_stale_input_reraises():
    """A LazyData poisoned by a prior failed flush re-raises ITS error
    when used as an input to a later enqueue."""
    import jax.numpy as jnp
    from mxnet_tpu.ndarray import bulk
    if not bulk.enabled():
        pytest.skip("bulking disabled")
    bulk.flush()
    fnc = lambda x: x + 1.0  # noqa: E731
    tag = "perffail_probe"
    x0 = jnp.ones((2,), jnp.float32)
    bulk.enqueue(fnc, tag, (x0,))              # warmup
    poisoned = bulk.LazyData((2,), jnp.float32, 0,
                             region=bulk._Region())
    poisoned._error = RuntimeError("seeded upstream failure")
    with pytest.raises(RuntimeError, match="seeded upstream"):
        bulk.enqueue(fnc, tag, (poisoned,))
    bulk.flush()


# ----------------------------------------------------------------------
# satellite regression: ImageIter restores __main__.__file__ on close
# ----------------------------------------------------------------------

def test_imageiter_restores_main_file_on_close(tmp_path):
    from mxnet_tpu import recordio
    from mxnet_tpu.image import ImageIter

    path = str(tmp_path / "probe")
    rec = recordio.MXIndexedRecordIO(path + ".idx", path + ".rec", "w")
    rng = np.random.RandomState(0)
    for i in range(8):
        img = rng.randint(0, 255, (8, 8, 3), dtype=np.uint8)
        rec.write_idx(i, recordio.pack(
            recordio.IRHeader(0, float(i), i, 0), img.tobytes()))
    rec.close()

    main_mod = sys.modules["__main__"]
    had_file = hasattr(main_mod, "__file__")
    orig = getattr(main_mod, "__file__", None)
    bogus = str(tmp_path / "definitely_missing_main.py")
    main_mod.__file__ = bogus
    try:
        it = ImageIter(4, (3, 8, 8), path_imgrec=path + ".rec",
                       preprocess_procs=2, dtype="uint8",
                       aug_list=[])
        try:
            # the spawn workaround is CONFINED: removed while the pool
            # lives (respawned workers must not see the bogus path)...
            assert not hasattr(main_mod, "__file__")
            d, labels, pad = it.next_np()
            assert d.shape == (4, 3, 8, 8)
        finally:
            it.close()
        # ...and restored exactly once the pool is dead
        assert getattr(main_mod, "__file__", None) == bogus
    finally:
        if had_file:
            main_mod.__file__ = orig
        elif hasattr(main_mod, "__file__"):
            del main_mod.__file__
