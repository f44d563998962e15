"""Sharding sanitizer (ISSUE 7): SPMD spec linter + donation auditor
fixtures, the compiled collective-contract round trip on the
data_parallel.TrainStep LeNet path, and the transfer-guard wiring."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

import mxnet_tpu as mx
from mxnet_tpu import analysis as an
from mxnet_tpu import gluon
from mxnet_tpu.analysis import sharding
from mxnet_tpu.parallel import TrainStep, make_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _rules_of(diags):
    return sorted({d.rule for d in diags})


def _lint(src):
    return an.lint_source(src, "probe.py")


# ----------------------------------------------------------------------
# mesh-axis-unknown (project rule: declarations span the linted tree)
# ----------------------------------------------------------------------

def test_mesh_axis_unknown_fires_and_declared_twin_silent(tmp_path):
    (tmp_path / "a.py").write_text(
        "from mxnet_tpu.parallel import make_mesh\n"
        "from jax.sharding import PartitionSpec as P\n"
        "mesh = make_mesh({'dp': 8})\n"
        "good = P('dp', None)\n"
        "bad = P('dpp')\n")
    diags = sharding.audit_sharding([str(tmp_path)])
    assert _rules_of(diags) == ["mesh-axis-unknown"]
    assert len(diags) == 1 and diags[0].line == 5
    assert "did you mean" in diags[0].message


def test_mesh_axis_declarations_cross_files(tmp_path):
    # the axis is declared in ANOTHER file of the batch -- like
    # mesh.py declaring what data_parallel.py uses
    (tmp_path / "decl.py").write_text(
        "from jax.sharding import Mesh\n"
        "def build(devs):\n"
        "    return Mesh(devs, ('rows', 'cols'))\n")
    (tmp_path / "use.py").write_text(
        "from jax.sharding import PartitionSpec\n"
        "spec = PartitionSpec('rows', 'cols')\n")
    assert sharding.audit_sharding([str(tmp_path)]) == []
    # linted alone, the use has no declaration and no canonical match
    assert _rules_of(sharding.audit_sharding(
        [str(tmp_path / "use.py")])) == ["mesh-axis-unknown"]


def test_mesh_axis_resolves_variables_and_canonical_roles(tmp_path):
    # param defaults / self._axis attributes resolve; the canonical
    # AXIS_ROLES vocabulary (dp/tp/pp/sp/ep) needs no declaration
    (tmp_path / "v.py").write_text(
        "from jax.sharding import PartitionSpec as P\n"
        "def ring(x, axis_name='sp'):\n"
        "    return P(None, axis_name, None)\n"
        "class Layer:\n"
        "    def __init__(self, axis='tp'):\n"
        "        self._axis = axis\n"
        "    def spec(self):\n"
        "        return P(self._axis, None)\n")
    assert sharding.audit_sharding([str(tmp_path)]) == []
    (tmp_path / "w.py").write_text(
        "from jax.sharding import PartitionSpec as P\n"
        "def ring(x, axis_name='zz9'):\n"
        "    return P(None, axis_name)\n")
    diags = sharding.audit_sharding([str(tmp_path / "w.py")])
    assert _rules_of(diags) == ["mesh-axis-unknown"]
    assert "'zz9'" in diags[0].message


def test_mesh_axis_suppression_comment(tmp_path):
    (tmp_path / "s.py").write_text(
        "from jax.sharding import PartitionSpec as P\n"
        "x = P('experimental9')  # mxlint: disable=mesh-axis-unknown\n")
    assert sharding.audit_sharding([str(tmp_path)]) == []


def test_parallel_package_axes_all_declared():
    """The real tree: every PartitionSpec axis in parallel/, gluon, and
    dataio resolves against the canonical vocabulary + mesh builds."""
    paths = [os.path.join(REPO, "mxnet_tpu")]
    assert sharding.audit_sharding(paths) == []


# ----------------------------------------------------------------------
# shard-map-spec-arity
# ----------------------------------------------------------------------

def test_shard_map_arity_fires_and_clean_twin_silent():
    bad = (
        "from jax import shard_map\n"
        "def body(q, k):\n"
        "    return q\n"
        "def run(mesh, spec):\n"
        "    return shard_map(body, mesh=mesh,\n"
        "                     in_specs=(spec, spec, spec),\n"
        "                     out_specs=spec)\n")
    diags = _lint(bad)
    assert _rules_of(diags) == ["shard-map-spec-arity"]
    assert "2 positional arg(s)" in diags[0].message
    good = bad.replace("(spec, spec, spec)", "(spec, spec)")
    assert _lint(good) == []


def test_shard_map_arity_resolves_partial_bodies():
    # sequence.py's idiom: functools.partial binding keyword-only args
    # must NOT reduce the positional arity
    src = (
        "import functools\n"
        "from jax import shard_map\n"
        "def body(q, k, v, *, scale):\n"
        "    return q\n"
        "def run(mesh, spec):\n"
        "    b = functools.partial(body, scale=2.0)\n"
        "    return shard_map(b, mesh=mesh, in_specs=(spec, spec, spec),\n"
        "                     out_specs=spec)\n")
    assert _lint(src) == []
    # a positionally-consumed arg DOES reduce arity
    src2 = src.replace("functools.partial(body, scale=2.0)",
                       "functools.partial(body, None, scale=2.0)")
    assert _rules_of(_lint(src2)) == ["shard-map-spec-arity"]


def test_shard_map_out_specs_tuple_arity():
    bad = (
        "from jax import shard_map\n"
        "def body(q, k):\n"
        "    return q, k, q\n"
        "def run(mesh, spec):\n"
        "    return shard_map(body, mesh=mesh, in_specs=(spec, spec),\n"
        "                     out_specs=(spec,))\n")
    diags = _lint(bad)
    assert _rules_of(diags) == ["shard-map-spec-arity"]
    assert "returns a 3-tuple" in diags[0].message
    good = bad.replace("out_specs=(spec,)", "out_specs=(spec, spec, spec)")
    assert _lint(good) == []


def test_shard_map_arity_real_parallel_files_clean():
    """The in-repo shard_map call sites (ring attention, pipeline) must
    satisfy their own arity rule."""
    for rel in ("mxnet_tpu/parallel/sequence.py",
                "mxnet_tpu/parallel/pipeline.py"):
        diags = an.lint_file(os.path.join(REPO, rel))
        assert [d for d in diags if d.rule == "shard-map-spec-arity"] \
            == [], rel


# ----------------------------------------------------------------------
# undonated-train-state
# ----------------------------------------------------------------------

def test_undonated_train_state_fires_and_donated_twin_silent():
    bad = ("import jax\n"
           "def train_step(pvals, svals, data):\n"
           "    return pvals\n"
           "f = jax.jit(train_step)\n")
    diags = _lint(bad)
    assert _rules_of(diags) == ["undonated-train-state"]
    good = bad.replace("jax.jit(train_step)",
                       "jax.jit(train_step, donate_argnums=(0, 1))")
    assert _lint(good) == []


def test_undonated_fires_on_state_params_without_step_name():
    bad = ("import jax\n"
           "def apply(pvals, x):\n"
           "    return x\n"
           "f = jax.jit(apply)\n")
    assert _rules_of(_lint(bad)) == ["undonated-train-state"]
    # non-state params, non-step name: silent
    ok = ("import jax\n"
          "def apply(x, y):\n"
          "    return x + y\n"
          "f = jax.jit(apply)\n")
    assert _lint(ok) == []


def test_undonated_accepts_jit_kwargs_splat_donation():
    # the parallel.data_parallel idiom: donation assigned into the
    # kwargs dict the jit call splats
    src = ("import jax\n"
           "def build(donate):\n"
           "    def step_fn(pvals, svals):\n"
           "        return pvals\n"
           "    jit_kwargs = {}\n"
           "    if donate:\n"
           "        jit_kwargs['donate_argnums'] = (0, 1)\n"
           "    return jax.jit(step_fn, **jit_kwargs)\n")
    assert _lint(src) == []


def test_undonated_train_state_repo_sites_justified():
    """data_parallel donates; the Executor/hybridize/predictor caches
    carry justified suppressions -- the whole tree lints clean with the
    rule armed (the ISSUE 7 donation-sweep acceptance)."""
    for rel in ("mxnet_tpu/parallel/data_parallel.py",
                "mxnet_tpu/executor.py",
                "mxnet_tpu/gluon/block.py",
                "mxnet_tpu/predictor.py"):
        diags = an.lint_file(os.path.join(REPO, rel))
        assert [d for d in diags if d.rule == "undonated-train-state"] \
            == [], rel


# ----------------------------------------------------------------------
# donated-reuse
# ----------------------------------------------------------------------

def test_donated_reuse_fires_and_rebound_twin_silent():
    bad = ("import jax\n"
           "def go(w, g):\n"
           "    f = jax.jit(lambda a, b: a + b, donate_argnums=(0,))\n"
           "    out = f(w, g)\n"
           "    return w + out\n")
    diags = _lint(bad)
    assert _rules_of(diags) == ["donated-reuse"]
    assert "'w'" in diags[0].message
    # using the returned array (or rebinding the name) is the fix
    good = ("import jax\n"
            "def go(w, g):\n"
            "    f = jax.jit(lambda a, b: a + b, donate_argnums=(0,))\n"
            "    w = f(w, g)\n"
            "    return w + g\n")
    assert _lint(good) == []
    # reading the NON-donated operand is fine
    good2 = bad.replace("return w + out", "return g + out")
    assert _lint(good2) == []


# ----------------------------------------------------------------------
# implicit-reshard
# ----------------------------------------------------------------------

_RESHARD_BAD = (
    "import jax\n"
    "from jax.sharding import NamedSharding, PartitionSpec as P\n"
    "def loop(xs, mesh):\n"
    "    sh = NamedSharding(mesh, P('dp'))\n"
    "    out = []\n"
    "    for x in xs:\n"
    "        out.append(jax.device_put(x, sh))\n"
    "    return out\n")


def test_implicit_reshard_fires_and_guarded_twin_silent():
    assert _rules_of(_lint(_RESHARD_BAD)) == ["implicit-reshard"]
    guarded = _RESHARD_BAD.replace(
        "        out.append(jax.device_put(x, sh))\n",
        "        if not x.sharding.is_equivalent_to(sh, x.ndim):\n"
        "            x = jax.device_put(x, sh)\n"
        "        out.append(x)\n")
    assert _lint(guarded) == []
    # hoisted out of the loop: placement happens once, fine
    hoisted = (
        "import jax\n"
        "from jax.sharding import NamedSharding, PartitionSpec as P\n"
        "def place(x, mesh):\n"
        "    return jax.device_put(x, NamedSharding(mesh, P('dp')))\n")
    assert _lint(hoisted) == []


# ----------------------------------------------------------------------
# compiled layer: collective profile + contract round trip
# ----------------------------------------------------------------------

_HLO_FIXTURE = """\
HloModule probe

ENTRY %main (p0: f32[16,8]) -> f32[16,8] {
  %p0 = f32[16,8] parameter(0)
  %ag = f32[16,64] all-gather(f32[16,8] %p0), dimensions={1}
  %ar = f32[16,8] all-reduce(f32[16,8] %p0), to_apply=%add
  %ars = f32[16,8] all-reduce-start(f32[16,8] %ar)
  %ard = f32[16,8] all-reduce-done(f32[16,8] %ars)
  %pid = u32[] partition-id()
  ROOT %out = f32[16,8] add(f32[16,8] %ar, f32[16,8] %ard)
}
"""


def test_collective_profile_counts_kinds_and_bytes():
    prof = sharding.collective_profile(_HLO_FIXTURE)
    # start/done pairs count once; partition-id is metadata, not traffic
    assert prof["all-reduce"]["count"] == 2
    assert prof["all-gather"]["count"] == 1
    assert prof["all-gather"]["bytes"] == 16 * 64 * 4
    assert "partition-id" not in prof


def _lenet():
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Conv2D(6, 5, padding=2, activation="relu"),
            gluon.nn.MaxPool2D(2),
            gluon.nn.Conv2D(16, 3, activation="relu"),
            gluon.nn.MaxPool2D(2),
            gluon.nn.Flatten(),
            gluon.nn.Dense(32, activation="relu"),
            gluon.nn.Dense(10))
    net.initialize(ctx=mx.cpu())
    net.hybridize()
    return net


def test_collective_contract_round_trip_lenet(tmp_path):
    """The CI shardlint gate's exact shape: LeNet TrainStep over a dp
    mesh -> baseline write -> self-diff zero -> a seeded spec mismatch
    (param sharded where it must be replicated) is flagged naming the
    executable."""
    from mxnet_tpu import profiling
    from jax.sharding import NamedSharding, PartitionSpec as P
    profiling.reset()
    profiling.enable()
    try:
        mesh = make_mesh({"dp": 8}, devices=jax.devices("cpu")[:8])
        net = _lenet()
        tr = gluon.Trainer(net.collect_params(), "sgd",
                           {"learning_rate": 0.1}, kvstore=None)
        step = TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(), tr,
                         mesh=mesh)
        rng = np.random.RandomState(0)
        x = mx.nd.array(rng.rand(16, 1, 16, 16).astype(np.float32))
        y = mx.nd.array(rng.randint(0, 10, (16,)).astype(np.float32))
        step(x, y)

        base_path = str(tmp_path / "baseline.json")
        base = sharding.save_contract(base_path)
        label = "train_step:HybridSequential"
        assert label in base["executables"]
        # the blessed collectives are the gradient psums: all-reduce
        # only, nothing else
        assert set(base["executables"][label]) == {"all-reduce"}, \
            base["executables"][label]
        # self-diff must be zero drift (both via API and via the CLI
        # file path CI uses)
        assert sharding.diff_contract(base, base) == []
        assert an.main(["--collective-diff", base_path, base_path]) == 0

        # seeded spec mismatch: shard a weight over dp (params must be
        # replicated) and rebuild -- GSPMD inserts resharding traffic.
        # Picked structurally (gluon's auto-name counter is process-
        # global, so name-based selection is order-fragile): the
        # Dense(32) weight, whose leading dim divides the 8-way mesh.
        dense = [c for c in net._children.values()
                 if isinstance(c, gluon.nn.Dense)][0]
        p = dense.weight
        p._data._data = jax.device_put(p._data._data,
                                       NamedSharding(mesh, P("dp")))
        step._cache.clear()
        step(x, y)
        cur_path = str(tmp_path / "current.json")
        cur = sharding.save_contract(cur_path)
        diags = sharding.diff_contract(base, cur)
        assert diags, "seeded spec mismatch not flagged"
        assert any(label in d.message for d in diags)
        assert an.main(["--collective-diff", base_path, cur_path]) == 1
    finally:
        profiling.disable()
        profiling.reset()


def test_contract_schema_and_load_rejects_foreign_json(tmp_path):
    p = tmp_path / "x.json"
    p.write_text(json.dumps({"schema": "other", "executables": {}}))
    with pytest.raises(ValueError, match="mxshard.collectives.v1"):
        sharding.load_contract(str(p))
    rc = an.main(["--collective-diff", str(p), str(p)])
    assert rc == 2


def test_diff_contract_new_executable_and_growth_flagged():
    base = {"schema": sharding.CONTRACT_SCHEMA, "executables": {
        "step": {"all-reduce": {"count": 2, "bytes": 100}}}}
    # growth of a blessed kind
    cur = {"schema": sharding.CONTRACT_SCHEMA, "executables": {
        "step": {"all-reduce": {"count": 3, "bytes": 150}}}}
    diags = sharding.diff_contract(base, cur)
    assert len(diags) == 1 and "2 -> 3" in diags[0].message
    # a brand-new executable with collectives is unblessed
    cur2 = {"schema": sharding.CONTRACT_SCHEMA, "executables": {
        "other": {"all-gather": {"count": 1, "bytes": 10}}}}
    diags2 = sharding.diff_contract(base, cur2)
    assert len(diags2) == 1 and "unblessed" in diags2[0].message
    # FEWER collectives than blessed is an improvement, not drift
    cur3 = {"schema": sharding.CONTRACT_SCHEMA, "executables": {
        "step": {"all-reduce": {"count": 1, "bytes": 50}}}}
    assert sharding.diff_contract(base, cur3) == []


# ----------------------------------------------------------------------
# transfer guard
# ----------------------------------------------------------------------

def test_transfer_guard_clean_step_passes_and_seeded_leak_raises():
    """The steady-state compiled step is guard-clean (scalar feeds ride
    explicit device_put), and a seeded IMPLICIT in-step host transfer
    raises -- the ISSUE 7 acceptance fixture."""
    mesh = make_mesh({"dp": 8}, devices=jax.devices("cpu")[:8])
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(16, activation="relu"), gluon.nn.Dense(10))
    net.initialize(ctx=mx.cpu())
    net.hybridize()
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1}, kvstore=None)
    step = TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(), tr,
                     mesh=mesh)
    rng = np.random.RandomState(0)
    x = mx.nd.array(rng.rand(16, 8).astype(np.float32))
    y = mx.nd.array(rng.randint(0, 10, (16,)).astype(np.float32))
    step(x, y)                        # compile + state init, unguarded
    with sharding.transfer_guard("disallow"):
        for _ in range(2):
            loss = step(x, y)         # clean steady state: must pass
        loss._data.block_until_ready()
    # seeded leak: a Python scalar mixed into eager dispatch is an
    # implicit host->device transfer every step
    with pytest.raises(Exception, match="[Dd]isallowed"):
        with sharding.transfer_guard("disallow"):
            bad = loss * 1.5
            bad._data.block_until_ready()


def test_transfer_guard_run_steps_clean():
    mesh = make_mesh({"dp": 8}, devices=jax.devices("cpu")[:8])
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(8))
    net.initialize(ctx=mx.cpu())
    net.hybridize()
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1}, kvstore=None)
    step = TrainStep(net, gluon.loss.L2Loss(), tr, mesh=mesh)
    rng = np.random.RandomState(1)
    xs = mx.nd.array(rng.rand(2, 16, 4).astype(np.float32))
    ys = mx.nd.array(rng.rand(2, 16, 8).astype(np.float32))
    step.run_steps(xs, ys)            # warmup compile
    with sharding.transfer_guard("disallow"):
        losses = step.run_steps(xs, ys)
        losses._data.block_until_ready()
    assert losses.shape == (2,)


def test_transfer_guard_env_wiring_and_bad_mode():
    out = subprocess.run(
        [sys.executable, "-c",
         "import mxnet_tpu, jax; print(jax.config.jax_transfer_guard)"],
        env={**os.environ, "MXNET_TPU_TRANSFER_GUARD": "log",
             "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=240, cwd=REPO)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == "log"
    from mxnet_tpu.base import MXNetError
    with pytest.raises(MXNetError, match="TRANSFER_GUARD"):
        sharding.install_transfer_guard("definitely-not-a-mode")


# ----------------------------------------------------------------------
# donation accounting (the peak-HBM side of the donation sweep)
# ----------------------------------------------------------------------

def test_donated_step_aliases_state_and_mxprof_accounts_it():
    """The donation the `undonated-train-state` rule enforces is real
    in the compiled program: TrainStep(donate=True)'s HLO carries the
    input_output_alias directive (absent without donation), and
    mxprof's peak-HBM formula credits whatever alias bytes the backend
    reports (peak = arg + out + temp - alias) so the donation sweep is
    drift-checkable.  (XLA:CPU under forced multi-device reports
    alias_bytes=0 even for aliased programs, so the byte-level
    inequality is asserted only through the formula, not across the
    two programs.)"""
    from mxnet_tpu import profiling

    def build(donate):
        net = gluon.nn.HybridSequential()
        net.add(gluon.nn.Dense(64, activation="relu"),
                gluon.nn.Dense(32))
        net.initialize(ctx=mx.cpu())
        net.hybridize()
        tr = gluon.Trainer(net.collect_params(), "sgd",
                           {"learning_rate": 0.1}, kvstore=None)
        step = TrainStep(net, gluon.loss.L2Loss(), tr, mesh=None,
                         donate=donate)
        rng = np.random.RandomState(0)
        x = mx.nd.array(rng.rand(8, 16).astype(np.float32))
        y = mx.nd.array(rng.rand(8, 32).astype(np.float32))
        step(x, y)
        fn, args = step._last_call
        text = fn.lower(*args).compile().as_text()
        return profiling.report_for(step), text

    donated, donated_text = build(True)
    undonated, undonated_text = build(False)
    assert donated is not None and undonated is not None
    assert "input_output_alias" in donated_text
    assert "input_output_alias" not in undonated_text
    for rep in (donated, undonated):
        m = rep["memory"]
        assert m["peak_hbm_bytes"] == max(
            0, m["argument_bytes"] + m["output_bytes"]
            + m["temp_bytes"] - m["alias_bytes"])


# ----------------------------------------------------------------------
# registration / env / Features surfaces
# ----------------------------------------------------------------------

def test_sharding_rules_registered_and_listed(capsys):
    ids = {"mesh-axis-unknown", "shard-map-spec-arity",
           "undonated-train-state", "donated-reuse", "implicit-reshard",
           "collective-drift"}
    assert ids <= set(an.RULES)
    assert an.main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rid in ids:
        assert rid in out


def test_env_vars_registered():
    from mxnet_tpu import env
    assert env.get("MXNET_TPU_SHARD_CHECK") is False
    assert env.get("MXNET_TPU_TRANSFER_GUARD") == ""


def test_features_shard_check_row(monkeypatch):
    feats = mx.runtime.Features()
    assert "SHARD_CHECK" in feats
    assert feats.is_enabled("SHARD_CHECK") is False
    monkeypatch.setenv("MXNET_TPU_SHARD_CHECK", "1")
    assert mx.runtime.Features().is_enabled("SHARD_CHECK") is True
