"""Compiled-step cost accounting (ISSUE 6): CostReport capture across
the compiled dispatch paths, category attribution summing to XLA
totals, stable fingerprints, roofline bound labels, the mxprof CLI's
report/diff contract, host spans through the one span call (the step
timeline's successor), and the satellite surfaces
(profiler.dumps, telemetry instruments, Features row)."""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import gluon, obs, profiling
from mxnet_tpu.profiling import cli, cost, hlo, roofline


@pytest.fixture()
def ring():
    """The one span ring armed and empty; disarmed and emptied after."""
    obs.trace.clear()
    obs.enable_tracing()
    yield obs
    obs.disable_tracing()
    obs.trace.clear()


@pytest.fixture()
def prof():
    """Profiling armed with a clean store; fully torn down after."""
    profiling.reset()
    profiling.enable()
    yield profiling
    profiling.disable()
    profiling.reset()


def _tiny_fn(width):
    def f(x, w1, w2):
        h = jnp.maximum(x @ w1, 0.0)
        return jnp.tanh(h @ w2).sum()
    return f


def _tiny_args(width):
    return (jnp.ones((8, 16)), jnp.ones((16, width)),
            jnp.ones((width, 4)))


# -- core: analysis, reconciliation, fingerprint -----------------------

def test_cost_report_nonzero_and_categories_sum_to_totals():
    rep = cost.analyze_jit(jax.jit(_tiny_fn(32)), _tiny_args(32),
                           label="tiny")
    assert rep is not None
    assert rep["schema"] == cost.SCHEMA
    assert rep["totals"]["flops"] > 0
    assert rep["totals"]["bytes_accessed"] > 0
    assert rep["categories"]["conv_dot"]["flops"] > 0
    f_sum = sum(c["flops"] for c in rep["categories"].values())
    b_sum = sum(c["bytes"] for c in rep["categories"].values())
    assert abs(f_sum - rep["totals"]["flops"]) < 1
    assert abs(b_sum - rep["totals"]["bytes_accessed"]) < 1
    # memory section is populated and internally consistent
    m = rep["memory"]
    assert m["argument_bytes"] > 0
    assert m["peak_hbm_bytes"] >= m["temp_bytes"]


def test_fingerprint_stable_across_identical_recompiles():
    args = _tiny_args(32)
    r1 = cost.analyze_jit(jax.jit(_tiny_fn(32)), args)
    # a FRESH jit of structurally identical code (new trace, new
    # compile, different source line) must fingerprint identically
    r2 = cost.analyze_jit(jax.jit(_tiny_fn(32)), args)
    assert r1["fingerprint"] == r2["fingerprint"]
    # and a different program must not
    r3 = cost.analyze_jit(jax.jit(_tiny_fn(64)), _tiny_args(64))
    assert r3["fingerprint"] != r1["fingerprint"]


def test_fingerprints_ignore_named_scopes():
    """A ``jax.named_scope`` changes ``op_name`` metadata and the
    source-position tables, nothing the chip runs: the compiled-HLO
    fingerprint and the serving layer's StableHLO fingerprint of the
    same function with and without one are alike, so a scope cannot turn
    a compile-cache hit into a miss."""
    from mxnet_tpu.serving.cache import stablehlo_fingerprint

    def plain(x, w):
        return jnp.tanh(x @ w).sum()

    def scoped(x, w):
        with jax.named_scope("mx.loss"):
            return jnp.tanh(x @ w).sum()

    args = (jnp.ones((8, 16)), jnp.ones((16, 4)))
    lowered = [jax.jit(f).lower(*args) for f in (plain, scoped)]
    assert "mx.loss" in lowered[1].as_text(debug_info=True)
    texts = [lo.compile().as_text() for lo in lowered]
    assert cost.fingerprint(texts[0]) == cost.fingerprint(texts[1])
    for debug_info in (False, True):
        fps = {stablehlo_fingerprint(lo.as_text(debug_info=debug_info))
               for lo in lowered}
        assert len(fps) == 1, debug_info
    other = jax.jit(scoped).lower(jnp.ones((8, 16)), jnp.ones((16, 8)))
    assert cost.fingerprint(other.compile().as_text()) \
        != cost.fingerprint(texts[1])


def test_hlo_parser_attributes_conv_and_layout():
    def f(x, w):
        y = jax.lax.conv_general_dilated(x, w, (1, 1), "SAME")
        return y.transpose(0, 2, 3, 1).sum()
    rep = cost.analyze_jit(jax.jit(f),
                           (jnp.zeros((2, 3, 8, 8)),
                            jnp.zeros((4, 3, 3, 3))), label="conv")
    cats = rep["categories"]
    assert cats["conv_dot"]["flops"] > 0
    # NCHW->NHWC relayout shows up as data movement
    assert cats["transpose_layout"]["instructions"] > 0
    # provenance: best-effort from op_name metadata (XLA may drop it on
    # rewritten instructions, so assert shape, not full coverage)
    assert rep["provenance"], "op_name provenance missing"
    for p in rep["provenance"]:
        assert p["flops"] > 0 and p["category"] in hlo.CATEGORIES


def test_no_peak_is_assumed():
    rep = cost.analyze_jit(jax.jit(_tiny_fn(32)), _tiny_args(32))
    assert roofline.device_peaks() is None               # CPU: absent
    assert roofline.build(rep, step_time_s=1e-3) is None
    assert roofline.device_peaks("TPU v5 lite") == (197e12, 819e9)
    with pytest.raises(mx.MXNetError, match="no published peak"):
        roofline.device_peaks("TPU v9 imaginary")


def test_roofline_labels_every_category():
    rep = cost.analyze_jit(jax.jit(_tiny_fn(32)), _tiny_args(32))
    rl = roofline.build(rep, step_time_s=1e-3, peak_flops=197e12,
                        peak_bytes_per_s=819e9)
    assert rl["mfu"] >= 0
    assert rl["categories"], "empty roofline category section"
    for cat, v in rl["categories"].items():
        assert v["bound"] in ("compute", "memory"), (cat, v)
        assert 0.0 <= v["time_share"] <= 1.0
    # a known-compute-bound synthetic: huge intensity forces 'compute'
    fake = {"device": "TPU v5e", "totals": {"flops": 1e12,
                                            "bytes_accessed": 1e3},
            "categories": {"conv_dot": {"flops": 10**12, "bytes": 10**3,
                                        "instructions": 1}},
            "memory": {"peak_hbm_bytes": 0}}
    rl2 = roofline.build(fake, 1.0)
    assert rl2["peak_flops"] == 197e12
    assert rl2["categories"]["conv_dot"]["bound"] == "compute"


# -- capture paths -----------------------------------------------------

def test_eager_jit_path_captured(prof):
    x = mx.nd.ones((4, 5))
    y = mx.nd.clip(x, a_min=0.111, a_max=5.222)
    y.asnumpy()
    reps = prof.reports()
    assert any(r["label"] == "eager:clip" and r["kind"] == "eager_jit"
               for r in reps)


def test_hybrid_cache_path_captured(prof):
    net = gluon.nn.Dense(3)
    net.initialize()
    net.hybridize()
    net(mx.nd.ones((2, 7))).asnumpy()   # deferred init: imperative
    out = net(mx.nd.ones((2, 7)))       # compiled cache path
    out.asnumpy()
    reps = prof.reports()
    hyb = [r for r in reps if r["kind"] == "hybrid_cache"]
    assert hyb and hyb[0]["label"].startswith("hybrid:Dense")
    assert hyb[0]["totals"]["flops"] > 0


def test_executor_path_captured(prof):
    a = mx.sym.Variable("a")
    b = mx.sym.Variable("b")
    c = mx.sym.dot(a, b)
    ex = c.bind(mx.cpu(), {"a": mx.nd.ones((4, 8)),
                           "b": mx.nd.ones((8, 2))})
    ex.forward()
    reps = prof.reports()
    assert any(r["label"] == "executor.eval" for r in reps)


def test_train_step_captured_with_step_and_roofline(prof, v5e_peaks):
    from mxnet_tpu.parallel import TrainStep
    net = gluon.nn.Dense(4)
    net.initialize()
    net.hybridize()
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1}, kvstore=None)
    step = TrainStep(net, gluon.loss.L2Loss(), tr, mesh=None)
    x = mx.nd.array(np.random.rand(8, 16).astype(np.float32))
    y = mx.nd.array(np.random.rand(8, 4).astype(np.float32))
    for _ in range(3):
        step(x, y)
    reps = {r["label"]: r for r in prof.reports()}
    rep = reps.get("train_step:Dense")
    assert rep is not None
    assert rep["step"]["count"] == 3
    assert rep["roofline"] is not None
    for v in rep["roofline"]["categories"].values():
        assert v["bound"] in ("compute", "memory")
    # capture is lazy: the store holds at most one report per compiled
    # program however many steps ran
    assert rep["totals"]["flops"] > 0


def test_disabled_mode_captures_nothing():
    profiling.reset()
    assert not profiling.enabled()
    x = mx.nd.ones((3, 3))
    (x * 2 + 1).asnumpy()
    assert profiling.reports() == []


# -- CLI: report + diff ------------------------------------------------

def _save_run(tmp_path, width, sub):
    rep = cost.analyze_jit(jax.jit(_tiny_fn(width)), _tiny_args(width),
                           label="tiny")
    d = tmp_path / sub
    d.mkdir()
    path = d / "tiny.cost.json"
    path.write_text(json.dumps(rep))
    return str(path)


def test_mxprof_diff_zero_on_identical_and_flags_widened_dot(tmp_path,
                                                             capsys):
    old = _save_run(tmp_path, 32, "old")
    new = _save_run(tmp_path, 128, "new")
    # identical -> exit 0
    assert cli.main(["diff", old, old]) == 0
    out = capsys.readouterr().out
    assert "no drift" in out
    # widened layer -> exit non-zero naming the dot category
    rc = cli.main(["diff", old, new])
    out = capsys.readouterr().out
    assert rc == 1
    assert "conv_dot" in out
    # machine-readable form carries the same verdict
    rc = cli.main(["diff", old, new, "--json"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert any(d["scope"] == "category:conv_dot" and
               d["field"] == "flops" for d in out["drifts"])


def test_mxprof_report_renders_saved_store(tmp_path, prof, capsys):
    net = gluon.nn.Dense(2)
    net.initialize()
    net.hybridize()
    net(mx.nd.ones((2, 3))).asnumpy()
    combined = prof.save_reports(str(tmp_path))
    assert os.path.basename(combined) == "report.json"
    assert cli.main(["report", "--dir", str(tmp_path), "--json"]) == 0
    agg = json.loads(capsys.readouterr().out)
    assert agg["executables"]
    assert sum(v["flops"] for v in agg["categories"].values()) > 0
    # human rendering mentions every populated category
    assert cli.main(["report", "--dir", str(tmp_path)]) == 0
    human = capsys.readouterr().out
    assert "conv_dot" in human and "executables:" in human


def test_mxprof_report_empty_dir_fails_gate(tmp_path, capsys):
    assert cli.main(["report", "--dir", str(tmp_path)]) == 1


def test_mxprof_diff_self_zero_with_repeated_labels(tmp_path, prof,
                                                    capsys):
    """Two layers of the same op type produce two executables with the
    SAME label (`eager:FullyConnected` twice); a report diffed against
    itself must still align each with itself and report zero drift
    (caught live: a label-keyed dict paired the first against the
    last)."""
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(32, activation="relu"), gluon.nn.Dense(4))
    net.initialize()
    net(mx.nd.ones((2, 16))).asnumpy()       # two FullyConnected shapes
    labels = [r["label"] for r in prof.reports()]
    assert labels.count("eager:FullyConnected") == 2
    path = os.path.join(prof.save_reports(str(tmp_path)))
    assert cli.main(["diff", path, path]) == 0
    assert "no drift" in capsys.readouterr().out


# -- host spans: the one span call and the one exporter ----------------

def test_span_records_and_exports_chrome_trace(tmp_path, ring):
    """What the step timeline did, through ``obs.span`` and
    ``obs.export_chrome_trace``: a span with its attributes lands in the
    ring and in the Chrome JSON."""
    with obs.span("phase1", detail="x"):
        pass
    with obs.span("marker"):
        pass
    names = [r["name"] for r in obs.spans()]
    assert "phase1" in names and "marker" in names
    path = tmp_path / "trace.json"
    trace = obs.export_chrome_trace(str(path))
    assert trace["traceEvents"]
    loaded = json.loads(path.read_text())
    assert loaded["traceEvents"][0]["ph"] == "X"
    span_ev = next(e for e in loaded["traceEvents"]
                   if e["name"] == "phase1")
    assert span_ev["ph"] == "X" and span_ev["dur"] >= 0
    assert span_ev["args"]["detail"] == "x"


def test_train_step_lands_as_a_span(ring):
    from mxnet_tpu.parallel import TrainStep
    net = gluon.nn.Dense(2)
    net.initialize()
    net.hybridize()
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1}, kvstore=None)
    step = TrainStep(net, gluon.loss.L2Loss(), tr, mesh=None)
    step(mx.nd.ones((4, 3)), mx.nd.ones((4, 2)))
    by = {r["name"]: r for r in obs.spans()}
    assert by["mx.train_step"]["attrs"] == {"step": 1, "items": 4}
    for part in ("prep", "dispatch", "rebind"):
        assert by["mx.train_step." + part]["parent"] \
            == by["mx.train_step"]["span"]
    names = [e["name"] for e in obs.export_chrome_trace()["traceEvents"]]
    assert "mx.train_step" in names and "mx.train_step.dispatch" in names


# -- satellites wired through ------------------------------------------

def test_telemetry_profiling_instruments(prof):
    from mxnet_tpu import telemetry
    was = telemetry.enabled()
    telemetry.enable()
    telemetry.reset("profiling.")
    try:
        mx.nd.clip(mx.nd.ones((2, 2)), a_min=0.017, a_max=9.3).asnumpy()
        reps = prof.reports()
        assert reps
        assert telemetry.counter("profiling.reports").value >= 1
        ev = telemetry.event("profiling.capture")
        assert ev.count >= 1 and ev.recent[-1]["label"].startswith(
            "eager:")
    finally:
        if not was:
            telemetry.disable()


def test_runtime_features_profiling_row(prof):
    feats = mx.runtime.Features()
    assert feats.is_enabled("PROFILING")
    profiling.disable()
    assert not mx.runtime.Features().is_enabled("PROFILING")


@pytest.mark.slow
def test_resnet_bf16_train_step_cost_report(v5e_peaks):
    """Acceptance shape (ISSUE 6): a bf16 ResNet train step's
    CostReport has conv/dot-dominated per-category FLOPs/bytes summing
    to the executable totals, and the roofline labels every category
    compute- or memory-bound.  resnet18 @ 32px keeps CPU compile
    tolerable; the program structure (convs + BN fusions + relayouts)
    matches the bench's resnet50 headline step."""
    from mxnet_tpu import amp
    from mxnet_tpu.gluon.model_zoo.vision import resnet18_v1
    from mxnet_tpu.parallel import TrainStep
    net = resnet18_v1()
    net.initialize()
    net.hybridize()
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1}, kvstore=None)
    step = TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(), tr,
                     mesh=None)
    x = mx.nd.array(np.random.rand(2, 3, 32, 32).astype(np.float32))
    y = mx.nd.array(np.zeros((2,), np.float32))
    with amp.scope("bfloat16"):
        step(x, y)
        rep = profiling.report_for(step, label="resnet_bf16",
                                   step_time_s=0.05, items_per_step=2)
    assert rep["totals"]["flops"] > 1e8
    f_sum = sum(c["flops"] for c in rep["categories"].values())
    b_sum = sum(c["bytes"] for c in rep["categories"].values())
    assert abs(f_sum - rep["totals"]["flops"]) < 1
    assert abs(b_sum - rep["totals"]["bytes_accessed"]) < 1
    # a ResNet step is MXU-dominated
    assert rep["categories"]["conv_dot"]["flops_share"] > 0.5
    for cat, v in rep["roofline"]["categories"].items():
        assert v["bound"] in ("compute", "memory"), (cat, v)


def test_report_for_train_step_helper(v5e_peaks):
    """bench.py's artifact path: report_for on a dispatched TrainStep
    works without the store (profiling disabled)."""
    from mxnet_tpu.parallel import TrainStep
    net = gluon.nn.Dense(2)
    net.initialize()
    net.hybridize()
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1}, kvstore=None)
    step = TrainStep(net, gluon.loss.L2Loss(), tr, mesh=None)
    assert profiling.report_for(step) is None     # nothing dispatched
    step(mx.nd.ones((4, 3)), mx.nd.ones((4, 2)))
    rep = profiling.report_for(step, label="bench_probe",
                               step_time_s=0.01, items_per_step=4)
    assert rep["label"] == "bench_probe"
    assert rep["roofline"]["items_per_sec"] == 400.0
    f_sum = sum(c["flops"] for c in rep["categories"].values())
    assert abs(f_sum - rep["totals"]["flops"]) < 1
