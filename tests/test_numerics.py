"""mxnumerics (ISSUE 16): per-rule static fixtures for the five
precision rules, the compiled-HLO precision audit contract (handcrafted
HLO text -- XLA:CPU widens bf16 dots, so the half-accum counters need a
deterministic module), the numerics-baseline round trip, the SARIF
export, and the runtime non-finite sentinel: zero-touch when disarmed,
fused check + first-offender attribution when armed, chaos-NaN
detection through TrainStep and ContinuousTrainer, and scaler/sentinel
same-step agreement."""
import json
import os

import numpy as np
import pytest

import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import amp, chaos, gluon, telemetry
from mxnet_tpu import analysis as an
from mxnet_tpu.analysis import numerics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _rules_of(diags):
    return sorted({d.rule for d in diags})


def _lint(src):
    return an.lint_source(src, "probe.py")


@pytest.fixture(autouse=True)
def _numerics_state():
    """Snapshot/restore the sentinel flag and the /statusz counters."""
    prev_check = numerics._CHECK
    prev_state = dict(numerics._STATE)
    yield
    numerics._CHECK = prev_check
    numerics._STATE.clear()
    numerics._STATE.update(prev_state)


# ----------------------------------------------------------------------
# static rules: one positive and one negative fixture per rule
# ----------------------------------------------------------------------

def test_bf16_reduce_fires_and_fp32_accum_silent():
    bad = (
        "class M:\n"
        "    def hybrid_forward(self, F, x):\n"
        "        h = x.astype('bfloat16')\n"
        "        return h.sum(axis=-1)\n"
    )
    diags = _lint(bad)
    assert _rules_of(diags) == ["bf16-sensitive-reduce"]
    assert "Did you mean" in diags[0].message
    good = (
        "class M:\n"
        "    def hybrid_forward(self, F, x):\n"
        "        h = x.astype('bfloat16')\n"
        "        a = h.astype('float32').sum(axis=-1)\n"
        "        b = F.sum(h, dtype='float32')\n"
        "        c = jnp.sum(h, preferred_element_type=jnp.float32)\n"
        "        return a, b, c\n"
    )
    assert _lint(good) == []


def test_bf16_reduce_fires_in_jitted_step_fn():
    bad = (
        "import jax\n"
        "def step_fn(params, x):\n"
        "    h = x.astype('bfloat16')\n"
        "    return h.mean()\n"
        "fn = jax.jit(step_fn, donate_argnums=(0,))\n"
    )
    assert "bf16-sensitive-reduce" in _rules_of(_lint(bad))
    # the same reduction in a plain eager helper is not gated
    eager = (
        "def helper(x):\n"
        "    h = x.astype('bfloat16')\n"
        "    return h.mean()\n"
    )
    assert _lint(eager) == []


def test_unscaled_half_loss_fires_and_amp_scaled_silent():
    bad = (
        "def train(net, loss_fn, x, y):\n"
        "    out = net(x).astype('float16')\n"
        "    loss = loss_fn(out, y).mean()\n"
        "    loss.backward()\n"
    )
    diags = _lint(bad)
    assert _rules_of(diags) == ["unscaled-half-loss"]
    assert "amp.scale_loss" in diags[0].message
    good = (
        "def train(net, loss_fn, trainer, x, y):\n"
        "    out = net(x).astype('float16')\n"
        "    loss = loss_fn(out, y).mean()\n"
        "    with amp.scale_loss(loss, trainer) as scaled:\n"
        "        scaled.backward()\n"
    )
    assert _lint(good) == []
    # fp32 loss never fires
    fp32 = (
        "def train(net, loss_fn, x, y):\n"
        "    loss = loss_fn(net(x), y).mean()\n"
        "    loss.backward()\n"
    )
    assert _lint(fp32) == []


def test_half_optimizer_state_fires_and_fp32_silent():
    bad = (
        "def create_state(self, index, weight):\n"
        "    return zeros(weight.shape, dtype='float16')\n"
    )
    diags = _lint(bad)
    assert _rules_of(diags) == ["half-optimizer-state"]
    assert "float32" in diags[0].message
    # state-named assignment outside a create_state fn also fires
    named = (
        "def setup(self, shape):\n"
        "    self.running_mean = zeros(shape, dtype='bfloat16')\n"
    )
    assert _rules_of(_lint(named)) == ["half-optimizer-state"]
    good = (
        "def create_state(self, index, weight):\n"
        "    return zeros(weight.shape, dtype='float32')\n"
        "def activations(shape):\n"
        "    return zeros(shape, dtype='bfloat16')\n"  # not state
    )
    assert _lint(good) == []


def test_implicit_downcast_tiny_const_and_narrowing_cast():
    bad = (
        "class M:\n"
        "    def hybrid_forward(self, F, x):\n"
        "        h = x.astype('bfloat16')\n"
        "        y = h + 1e-6\n"
        "        acc = h.astype('float32')\n"
        "        out = acc.astype('bfloat16')\n"
        "        return y, out\n"
    )
    diags = _lint(bad)
    assert _rules_of(diags) == ["implicit-downcast"]
    assert len(diags) == 2
    msgs = "\n".join(d.message for d in diags)
    assert "weak-typed" in msgs          # form (a): absorbed constant
    assert "narrows" in msgs             # form (b): fp32 -> half cast
    good = (
        "class M:\n"
        "    def hybrid_forward(self, F, x):\n"
        "        h = x.astype('bfloat16')\n"
        "        y = h + 0.5\n"                       # representable
        "        z = h.astype('float32') + 1e-6\n"    # upcast first
        "        return y, z\n"
    )
    assert _lint(good) == []


def test_nonfinite_guard_fires_and_eps_guard_silent():
    bad = (
        "import jax\n"
        "def step_fn(params, x):\n"
        "    return jnp.log(x)\n"
        "fn = jax.jit(step_fn, donate_argnums=(0,))\n"
    )
    diags = _lint(bad)
    assert _rules_of(diags) == ["nonfinite-guard-missing"]
    assert "log" in diags[0].message
    good = (
        "import jax\n"
        "def step_fn(params, x, var, eps):\n"
        "    a = jnp.log(x + eps)\n"
        "    b = jnp.log(jnp.maximum(x, 1e-6))\n"
        "    c = jnp.rsqrt(var + 1e-5)\n"
        "    return a, b, c\n"
        "fn = jax.jit(step_fn, donate_argnums=(0,))\n"
    )
    assert _lint(good) == []


def test_numerics_rule_suppression_directive():
    src = (
        "import jax\n"
        "def step_fn(params, x):\n"
        "    return jnp.log(x)  # mxlint: disable=nonfinite-guard-missing\n"
        "fn = jax.jit(step_fn, donate_argnums=(0,))\n"
    )
    assert _lint(src) == []


def test_numerics_rules_registered_and_fixed_tree_clean():
    for rid in ("bf16-sensitive-reduce", "unscaled-half-loss",
                "half-optimizer-state", "implicit-downcast",
                "nonfinite-guard-missing", "numerics-drift"):
        assert rid in an.RULES, rid
    # the armed-rules acceptance: the nn code the BN-stats fix
    # brought into shape lints clean WITHOUT suppressions (full --self
    # runs in CI)
    diags = an.lint_paths([
        os.path.join(REPO, "mxnet_tpu", "ops", "nn.py"),
        os.path.join(REPO, "mxnet_tpu", "gluon", "model_zoo"),
    ])
    assert [d.format() for d in diags] == []


# ----------------------------------------------------------------------
# compiled audit: counters on a handcrafted module (deterministic --
# XLA:CPU widens bf16 dots, so real lowerings can't pin half-accum)
# ----------------------------------------------------------------------

_TOY_HLO = """HloModule toy

%add.1 (a: bf16[], b: bf16[]) -> bf16[] {
  %a = bf16[] parameter(0)
  %b = bf16[] parameter(1)
  ROOT %s = bf16[] add(bf16[] %a, bf16[] %b)
}

ENTRY %main.1 (p0: bf16[64,64], p1: bf16[64,64]) -> bf16[64] {
  %p0 = bf16[64,64]{1,0} parameter(0)
  %p1 = bf16[64,64]{1,0} parameter(1)
  %dot.1 = bf16[64,64]{1,0} dot(bf16[64,64]{1,0} %p0, bf16[64,64]{1,0} %p1), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(f)/dot_general"}
  %zero = bf16[] constant(0)
  %red.1 = bf16[64]{0} reduce(bf16[64,64]{1,0} %dot.1, bf16[] %zero), dimensions={0}, to_apply=%add.1, metadata={op_name="jit(f)/reduce_sum"}
  %cv.1 = f32[64]{0} convert(bf16[64]{0} %red.1), metadata={op_name="jit(f)/convert"}
  ROOT %cv.2 = bf16[64]{0} convert(f32[64]{0} %cv.1)
}
"""


def test_audit_hlo_numerics_counters_direct():
    c = numerics.audit_hlo_numerics(_TOY_HLO)
    # the bf16-accumulated dot: operand AND output dtype are half
    assert c["half_dot_bytes"] == c["mxu_bytes"] > 0
    assert c["half_dots"] == {"jit(f)/dot_general": c["half_dot_bytes"]}
    # the all-bf16 reduction, with op_name provenance
    assert c["half_reduce_bytes"] == c["reduce_bytes"] > 0
    assert list(c["half_reduces"]) == ["jit(f)/reduce_sum"]
    # convert traffic books per scope
    assert c["convert_bytes"] > 0
    assert "jit(f)/convert" in c["convert_ops"]
    m = numerics._metrics_of(c)
    assert m["half_accum_dot_share"] == 1.0
    assert m["half_reduce_share"] == 1.0
    kinds = [a["kind"] for a in numerics._advisories_for(
        "toy", m, c, numerics.THRESHOLDS)]
    assert set(kinds) == {"half-accum-dot", "half-reduce"}
    # the widened twin (fp32 accumulator) books NO half-dot bytes
    wide = _TOY_HLO.replace("%dot.1 = bf16[64,64]{1,0}",
                            "%dot.1 = f32[64,64]{1,0}")
    cw = numerics.audit_hlo_numerics(wide)
    assert cw["half_dot_bytes"] == 0
    assert cw["mxu_bytes"] > 0


def test_audit_pred_reduce_is_not_a_half_reduce():
    # any/all folds (the sentinel's own isfinite reduction) are
    # pred-typed: no accumulation precision to lose
    text = (
        "HloModule sentinel\n\n"
        "ENTRY %main.1 (p0: pred[4096]) -> pred[] {\n"
        "  %p0 = pred[4096]{0} parameter(0)\n"
        "  %t = pred[] constant(true)\n"
        "  ROOT %r = pred[] reduce(pred[4096]{0} %p0, pred[] %t), "
        "dimensions={0}, to_apply=%and.1\n"
        "}\n"
    )
    c = numerics.audit_hlo_numerics(text)
    assert c["reduce_bytes"] > 0
    assert c["half_reduce_bytes"] == 0


def _register_toy(label, fn, *args):
    import jax
    from mxnet_tpu.profiling import store
    jfn = jax.jit(fn)
    jfn(*args)
    store.register((label,), label, jfn, args)
    return jfn


def test_numerics_audit_registry_walk_and_convert_storm():
    from mxnet_tpu import profiling
    profiling.reset()
    # XLA:CPU widens the bf16 matmul through converts: on this backend
    # the toy audits as a convert-storm (>= 15% of bytes)
    _register_toy("toy:bf16mm",
                  lambda a, b: (a @ b).sum(axis=0),
                  jnp.ones((64, 64), jnp.bfloat16),
                  jnp.ones((64, 64), jnp.bfloat16))
    audit = numerics.numerics_audit()
    assert audit["schema"] == numerics.AUDIT_SCHEMA
    assert audit["thresholds"]["convert_share"] == 0.15
    ex = audit["executables"]["toy:bf16mm"]
    for key in ("convert_share", "half_accum_dot_share",
                "half_reduce_share", "bytes_total"):
        assert key in ex["metrics"]
    kinds = {a["kind"] for a in ex["advisories"]}
    assert "convert-storm" in kinds
    # ranked advisories carry the executable label
    assert any(a["executable"] == "toy:bf16mm"
               and a["kind"] == "convert-storm"
               for a in audit["advisories"])
    profiling.reset()


# ----------------------------------------------------------------------
# baseline round trip: bless -> self-diff zero -> seeded regression
# ----------------------------------------------------------------------

def test_numerics_baseline_round_trip(tmp_path):
    from mxnet_tpu import profiling
    profiling.reset()
    _register_toy("toy:numrt",
                  lambda a, b: (a @ b).sum(axis=0),
                  jnp.ones((64, 64), jnp.bfloat16),
                  jnp.ones((64, 64), jnp.bfloat16))
    base_path = str(tmp_path / "numerics_baseline.json")
    base = numerics.save_audit(base_path)
    assert numerics.load_audit(base_path)["schema"] == \
        numerics.AUDIT_SCHEMA

    # self-diff: zero drift, CLI exit 0
    assert numerics.diff_audit(base, base) == []
    assert an.main(["--numerics-diff", base_path, base_path]) == 0

    # seeded regression: grown share + unblessed advisory kind
    cur = json.loads(json.dumps(base))
    row = cur["executables"]["toy:numrt"]
    row["metrics"]["convert_share"] = \
        base["executables"]["toy:numrt"]["metrics"]["convert_share"] \
        + 0.1
    row["advisories"].append({"kind": "half-accum-dot", "share": 0.5,
                              "op_names": [], "message": "seeded"})
    cur_path = str(tmp_path / "current.json")
    with open(cur_path, "w") as f:
        json.dump(cur, f)
    diags = numerics.diff_audit(base, numerics.load_audit(cur_path))
    assert _rules_of(diags) == ["numerics-drift"]
    msgs = "\n".join(d.message for d in diags)
    assert "convert_share grew" in msgs
    assert "half-accum-dot" in msgs
    assert an.main(["--numerics-diff", base_path, cur_path]) == 1

    # improvements pass silently
    better = json.loads(json.dumps(base))
    better["executables"]["toy:numrt"]["metrics"]["convert_share"] = 0.0
    better["executables"]["toy:numrt"]["advisories"] = []
    assert numerics.diff_audit(base, better) == []
    profiling.reset()


def test_numerics_audit_schema_reject(tmp_path):
    p = tmp_path / "bogus.json"
    p.write_text(json.dumps({"schema": "nope", "executables": {}}))
    with pytest.raises(ValueError, match="mxnumerics.audit.v1"):
        numerics.load_audit(str(p))
    assert an.main(["--numerics-diff", str(p), str(p)]) == 2


def test_numerics_diff_tolerance_env(monkeypatch):
    base = {"executables": {"e": {"metrics": {"convert_share": 0.0},
                                  "advisories": []}}}
    cur = {"executables": {"e": {"metrics": {"convert_share": 0.3},
                                 "advisories": []}}}
    assert numerics.diff_audit(base, cur, tol=0.5) == []
    assert len(numerics.diff_audit(base, cur, tol=0.02)) == 1
    monkeypatch.setenv("MXNET_TPU_NUMERICS_AUDIT_TOL", "0.5")
    assert numerics.diff_audit(base, cur) == []


def test_committed_numerics_baseline_is_loadable():
    base = numerics.load_audit(
        os.path.join(REPO, "ci", "numerics_baseline.json"))
    labels = set(base["executables"])
    assert "train_step:NumLeNet" in labels
    assert "train_step:ResNetV1" in labels


# ----------------------------------------------------------------------
# SARIF export (ISSUE 16 satellite)
# ----------------------------------------------------------------------

def test_sarif_round_trip(tmp_path):
    diags = _lint("import jax\n"
                  "def step_fn(params, x):\n"
                  "    h = x.astype('bfloat16')\n"
                  "    return jnp.log(h.sum())\n"
                  "fn = jax.jit(step_fn, donate_argnums=(0,))\n")
    assert len(diags) >= 2            # bf16 reduce + unguarded log
    log = an.to_sarif(diags)
    assert log["version"] == "2.1.0"
    assert log["$schema"].endswith("sarif-schema-2.1.0.json")
    run = log["runs"][0]
    assert run["tool"]["driver"]["name"] == "mxlint"
    results = run["results"]
    assert {r["ruleId"] for r in results} == set(_rules_of(diags))
    for r in results:
        assert r["level"] in ("error", "warning")
        assert r["message"]["text"]
        loc = r["locations"][0]["physicalLocation"]
        assert loc["artifactLocation"]["uri"] == "probe.py"
        assert isinstance(loc["region"]["startLine"], int)
    # rule metadata covers every ruleId present
    rule_ids = {m["id"] for m in run["tool"]["driver"]["rules"]}
    assert rule_ids == {r["ruleId"] for r in results}
    # write/read round trip
    out = str(tmp_path / "findings.sarif")
    assert an.write_sarif(out, diags) == log
    with open(out) as f:
        assert json.load(f) == log


def test_cli_sarif_export_and_exit_contract(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("import jax\n"
                   "def step_fn(params, x):\n"
                   "    return jnp.log(x)\n"
                   "fn = jax.jit(step_fn, donate_argnums=(0,))\n")
    out = tmp_path / "out.sarif"
    # exit code is still the lint verdict; the SARIF file is a side
    # artifact
    assert an.main([str(bad), "--sarif", str(out), "--json"]) == 1
    with open(out) as f:
        log = json.load(f)
    assert [r["ruleId"] for r in log["runs"][0]["results"]] == \
        ["nonfinite-guard-missing"]
    clean = tmp_path / "clean.py"
    clean.write_text("def f(x):\n    return x\n")
    out2 = tmp_path / "clean.sarif"
    assert an.main([str(clean), "--sarif", str(out2), "--json"]) == 0
    with open(out2) as f:
        assert json.load(f)["runs"][0]["results"] == []


# ----------------------------------------------------------------------
# runtime sentinel: primitives
# ----------------------------------------------------------------------

def test_finite_tree_and_finite_all():
    clean = [jnp.ones((4, 4), jnp.float32),
             jnp.ones((8,), jnp.bfloat16),
             jnp.arange(3)]                      # int leaf: skipped
    assert bool(numerics.finite_tree(clean))
    assert bool(numerics.finite_all(clean))
    assert bool(numerics.finite_tree([]))
    dirty = clean + [jnp.array([1.0, np.nan], jnp.float32)]
    assert not bool(numerics.finite_tree(dirty))
    assert not bool(numerics.finite_all(dirty))
    # NDArray wrappers unwrap
    assert not bool(numerics.finite_all(
        [mx.nd.array(np.array([np.inf], np.float32))]))


def test_attribute_nonfinite_reports_nan_before_inf():
    named = [("a", jnp.ones((2,))),
             ("b", jnp.array([1.0, np.inf], jnp.float32)),
             ("c", jnp.array([np.nan], jnp.float32))]
    assert numerics.attribute_nonfinite(named) == ("c", "nan")
    assert numerics.attribute_nonfinite(named[:2]) == ("b", "inf")
    assert numerics.attribute_nonfinite([("a", jnp.ones((2,)))]) is None
    # int arrays are skipped even when huge
    assert numerics.attribute_nonfinite(
        [("i", jnp.array([2 ** 31 - 1]))]) is None


def test_sentinel_disarmed_is_zero_touch():
    class Boom:
        def __iter__(self):
            raise AssertionError("disarmed sentinel touched its input")

    numerics._set_check(False)
    assert numerics.finite_sentinel(Boom()) is True


def test_finite_sentinel_raises_with_attribution_and_status_row():
    numerics._set_check(True)
    checks0 = numerics._STATE["checks"]
    assert numerics.finite_sentinel([("w", jnp.ones((4,)))], step=7) \
        is True
    assert numerics._STATE["checks"] == checks0 + 1
    with pytest.raises(numerics.NonFiniteError) as ei:
        numerics.finite_sentinel(
            [("w", jnp.ones((4,))),
             ("g", jnp.array([np.nan, 1.0], jnp.float32))], step=9)
    e = ei.value
    assert (e.param, e.step, e.kind) == ("g", 9, "nan")
    assert "pre-step values" in str(e)
    row = numerics.status_row()
    assert row["armed"] is True
    assert row["checks"] == checks0 + 2
    assert row["last"] == {"param": "g", "step": 9, "kind": "nan"}


def test_poison_nd_preserves_wrapper_and_skips_ints():
    x = mx.nd.ones((2, 3))
    p = numerics.poison_nd(x)
    assert isinstance(p, type(x))
    flat = p.asnumpy().ravel()
    assert np.isnan(flat[0]) and np.isfinite(flat[1:]).all()
    ix = jnp.arange(4)
    assert numerics.poison_nd(ix) is ix


def test_numerics_telemetry_instruments_catalogued():
    from mxnet_tpu.telemetry import hooks
    rows = {i.name: i for i in hooks.INSTRUMENTS}
    assert rows["numerics.checks"].kind == "counter"
    assert rows["numerics.check_time"].kind == "timer"
    assert rows["numerics.nonfinite_steps"].kind == "counter"
    assert rows["numerics.nonfinite"].kind == "event"


def test_statusz_carries_numerics_row():
    from mxnet_tpu.obs import status
    row = status.statusz()["numerics"]
    assert set(row) == {"armed", "checks", "nonfinite", "last"}
    assert row["armed"] == numerics.check_enabled()


def test_runtime_features_numerics_row(monkeypatch):
    from mxnet_tpu import runtime
    monkeypatch.setenv("MXNET_TPU_NUMERICS_CHECK", "1")
    assert runtime.Features().is_enabled("NUMERICS")
    monkeypatch.delenv("MXNET_TPU_NUMERICS_CHECK")
    assert not runtime.Features().is_enabled("NUMERICS")


def test_numerics_env_vars_registered():
    from mxnet_tpu import env
    desc = env.describe()
    assert "MXNET_TPU_NUMERICS_CHECK" in desc
    assert "MXNET_TPU_NUMERICS_AUDIT_TOL" in desc
    _val, default, _doc = desc["MXNET_TPU_NUMERICS_AUDIT_TOL"]
    assert default == 0.02


# ----------------------------------------------------------------------
# chaos-NaN detection through the training surfaces
# ----------------------------------------------------------------------

@pytest.fixture
def _clean_chaos():
    chaos.reset()
    yield
    chaos.disarm()
    chaos.reset()


def _mlp(seed=0):
    mx.random.seed(seed)
    np.random.seed(seed)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(16, activation="relu"), gluon.nn.Dense(4))
    net.initialize(ctx=mx.cpu())
    net.hybridize()
    return net


def test_trainstep_chaos_nan_attribution_and_weight_restore(_clean_chaos):
    from mxnet_tpu.parallel import TrainStep
    net = _mlp(seed=11)
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1}, kvstore=None)
    step = TrainStep(net, gluon.loss.L2Loss(), trainer)
    rng = np.random.RandomState(3)
    x = mx.nd.array(rng.randn(8, 8).astype(np.float32))
    y = mx.nd.array(rng.randn(8, 4).astype(np.float32))
    numerics._set_check(True)
    pnames = set(net.collect_params())
    with chaos.scenario(seed=0):
        chaos.on("numerics.nonfinite", numerics.poison_action, nth=2)
        step(x, y)                               # step 1: clean
        before = {p.name: p.data().asnumpy().copy()
                  for p in net.collect_params().values()}
        with pytest.raises(numerics.NonFiniteError) as ei:
            step(x, y)                           # step 2: poisoned
    e = ei.value
    assert e.kind == "nan"
    assert e.step == 2
    assert e.param in pnames | {"loss"}
    # the branchless overflow-skip kept the pre-step weights
    for p in net.collect_params().values():
        np.testing.assert_array_equal(before[p.name],
                                      p.data().asnumpy())
    row = numerics.status_row()
    assert row["nonfinite"] >= 1
    assert row["last"]["kind"] == "nan"


def test_trainstep_sentinel_and_scaler_agree_same_step(_clean_chaos):
    """The fp16 LossScaler and the sentinel see the SAME fused finite
    bit: one poisoned step halves the scale, skips the update, AND
    raises the typed attribution error."""
    from mxnet_tpu.parallel import TrainStep
    net = _mlp(seed=13)
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1}, kvstore=None)
    amp.init_trainer(trainer, amp.LossScaler(init_scale=8.0,
                                             scale_window=10 ** 9))
    step = TrainStep(net, gluon.loss.L2Loss(), trainer)
    rng = np.random.RandomState(5)
    x = mx.nd.array(rng.randn(8, 8).astype(np.float32))
    y = mx.nd.array(rng.randn(8, 4).astype(np.float32))
    numerics._set_check(True)
    net(x)                            # materialize deferred params
    before = {p.name: p.data().asnumpy().copy()
              for p in net.collect_params().values()}
    with chaos.scenario(seed=0):
        chaos.on("numerics.nonfinite", numerics.poison_action, nth=1)
        with pytest.raises(numerics.NonFiniteError) as ei:
            step(x, y)
    assert ei.value.step == 1
    assert trainer._amp_loss_scaler.loss_scale == 4.0   # halved
    for p in net.collect_params().values():
        np.testing.assert_array_equal(before[p.name],
                                      p.data().asnumpy())


def test_continuous_trainer_sentinel_catches_chaos_nan(
        tmp_path, _clean_chaos):
    from mxnet_tpu.chaos import scenarios
    from mxnet_tpu.serving.loop import ContinuousTrainer
    net, trainer, loss_fn, (x, y) = scenarios.train_fixtures(seed=0)
    ct = ContinuousTrainer(net, trainer, loss_fn,
                           lambda step: (x, y),
                           str(tmp_path / "ck"), publish_every=5)
    numerics._set_check(True)
    with chaos.scenario(seed=0):
        chaos.on("numerics.nonfinite", numerics.poison_action, nth=2)
        assert ct.run_steps(1) is not None       # step 1: clean
        with pytest.raises(numerics.NonFiniteError) as ei:
            ct.run_steps(1)                      # step 2: poisoned
    e = ei.value
    assert e.kind == "nan"
    assert e.step == 2
    assert e.param in {p.name for p in trainer._params}


def test_trainstep_disarmed_sentinel_trains_through_chaos(_clean_chaos):
    """Disarmed (the default), the sentinel costs one flag check and a
    poisoned step trains through silently (the where-select still skips
    it) -- detection is strictly opt-in."""
    from mxnet_tpu.parallel import TrainStep
    net = _mlp(seed=17)
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1}, kvstore=None)
    step = TrainStep(net, gluon.loss.L2Loss(), trainer)
    rng = np.random.RandomState(7)
    x = mx.nd.array(rng.randn(8, 8).astype(np.float32))
    y = mx.nd.array(rng.randn(8, 4).astype(np.float32))
    numerics._set_check(False)
    nonfinite0 = numerics._STATE["nonfinite"]
    with chaos.scenario(seed=0):
        chaos.on("numerics.nonfinite", numerics.poison_action, nth=1)
        step(x, y)                               # poisoned, no raise
        step(x, y)
    assert numerics._STATE["nonfinite"] == nonfinite0
    for p in net.collect_params().values():
        assert np.isfinite(p.data().asnumpy()).all()


# ----------------------------------------------------------------------
# BatchNorm bf16 running stats accumulate in fp32 (ISSUE 16 satellite)
# ----------------------------------------------------------------------

def test_batch_norm_bf16_stats_blend_in_fp32():
    from mxnet_tpu.ops import nn as ops_nn
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(4, 3, 5, 5).astype(np.float32)) * 100.0
    gamma = jnp.ones((3,), jnp.float32)
    beta = jnp.zeros((3,), jnp.float32)
    mm = jnp.asarray(rng.randn(3).astype(np.float32)).astype(jnp.bfloat16)
    mv = jnp.abs(jnp.asarray(rng.randn(3).astype(np.float32))) \
        .astype(jnp.bfloat16) + 1.0
    out, new_mean, new_var = ops_nn._batch_norm.fcompute(
        x, gamma, beta, mm, mv, momentum=0.9, fix_gamma=False,
        training=True)
    # aux dtype preserved
    assert new_mean.dtype == jnp.bfloat16
    assert new_var.dtype == jnp.bfloat16
    # the EMA equals the fp32 blend rounded ONCE to bf16 (same shifted
    # one-pass moments, recomputed here in fp32)
    c = np.asarray(mm, np.float32).reshape(1, 3, 1, 1)
    yv = np.asarray(x, np.float32) - c
    mean_y = yv.mean(axis=(0, 2, 3))
    m2 = (yv * yv).mean(axis=(0, 2, 3))
    mean = mean_y + c.reshape(3)
    var = np.maximum(m2 - mean_y * mean_y, 0.0)
    ref_mean = (0.9 * np.asarray(mm, np.float32) + 0.1 * mean) \
        .astype(jnp.bfloat16.dtype)
    ref_var = (0.9 * np.asarray(mv, np.float32) + 0.1 * var) \
        .astype(jnp.bfloat16.dtype)
    np.testing.assert_allclose(
        np.asarray(new_mean, np.float32),
        ref_mean.astype(np.float32), rtol=2 ** -7)
    np.testing.assert_allclose(
        np.asarray(new_var, np.float32),
        ref_var.astype(np.float32), rtol=2 ** -7)


def test_batch_norm_bf16_eval_adds_eps_in_fp32():
    """In bf16, var + 1e-5 == var exactly; the eval path must upcast
    BEFORE the eps add.  With var == 1.0 the difference is visible at
    fp32 output precision on large activations."""
    from mxnet_tpu.ops import nn as ops_nn
    eps = 1e-5
    x = jnp.full((2, 1, 8, 8), 1000.0, jnp.float32)
    one = jnp.ones((1,), jnp.float32)
    zero = jnp.zeros((1,), jnp.float32)
    out, _m, _v = ops_nn._batch_norm.fcompute(
        x, one, zero, zero.astype(jnp.bfloat16),
        one.astype(jnp.bfloat16), eps=eps, momentum=0.9,
        fix_gamma=False, training=False)
    ref = 1000.0 / np.sqrt(np.float32(1.0) + np.float32(eps))
    wrong = 1000.0                     # eps absorbed: 1/sqrt(1.0)
    got = float(np.asarray(out).ravel()[0])
    assert abs(got - ref) < 1e-3
    assert abs(got - wrong) > 1e-3
