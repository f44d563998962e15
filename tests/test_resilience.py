"""Distributed resilience tier tests (ISSUE 15): typed collective
failures with rank attribution, cross-process chaos replay
(MXNET_TPU_CHAOS_SPEC), the rank-death-safe sharded commit (manifest
never renamed past a dead rank -- the cross-rank manifest-last
invariant), and the elastic restart supervisor.

The multi-process tests spawn REAL gloo worlds (the recipe of
docs/distributed.md) but WITHOUT tools/launch.py's fail-fast teardown,
so a survivor gets to raise -- and assert on -- its typed
BarrierTimeout before anything kills it.
"""
import os
import socket
import subprocess
import sys
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import chaos, telemetry
from mxnet_tpu import distributed as dist
from mxnet_tpu.base import MXNetError
from mxnet_tpu.checkpoint import CheckpointManager, sharded
from mxnet_tpu.obs import status as obs_status
from mxnet_tpu.serving.loop import ContinuousTrainer
from mxnet_tpu.supervisor import Supervisor

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def counters():
    telemetry.enable()
    yield telemetry
    telemetry.disable()


@pytest.fixture(autouse=True)
def _clean_chaos():
    chaos.reset()
    yield
    chaos.disarm()
    chaos.reset()


@pytest.fixture()
def fake_world(monkeypatch):
    """A fake 2-rank coordination world: rank 0 is us, the KV store is
    an in-process dict with the real deadline/directory-delete
    semantics, and the lockstep state is reset around the test."""
    class FakeKV:
        def __init__(self):
            self.store = {}
            self.deleted = []

        def key_value_set_bytes(self, key, val):
            self.store[key] = bytes(val)

        def blocking_key_value_get_bytes(self, key, timeout_ms):
            if key in self.store:
                return self.store[key]
            raise RuntimeError(
                "DEADLINE_EXCEEDED: GetKeyValue() timed out with key: "
                "%s and duration: %dms" % (key, timeout_ms))

        def key_value_delete(self, key):
            self.deleted.append(key)
            if key.endswith("/"):      # directory semantics
                for k in [k for k in self.store if k.startswith(key)]:
                    del self.store[k]
            else:
                self.store.pop(key, None)

    kv = FakeKV()
    monkeypatch.setattr(dist, "world", lambda: (2, 0))
    monkeypatch.setattr(dist, "_client", lambda: kv)
    monkeypatch.setattr(dist, "_seq", [0])
    monkeypatch.setattr(dist, "_my_old_keys", [])
    monkeypatch.setattr(dist, "_PREV_GEN_SWEPT", [False])
    return kv


# ---------------------------------------------------------------------
# chaos spec: serialize, scope, replay (satellite: cross-process chaos)
# ---------------------------------------------------------------------

def test_make_spec_arm_from_spec_roundtrip():
    spec = chaos.make_spec(seed=7, rules=[
        {"point": "a.b", "action": "raise", "nth": 2},
        {"point": "c.d", "action": "kill", "rank": 1},
    ])
    assert chaos.arm_from_spec(spec, rank=0, generation=0) is True
    assert chaos.armed()
    chaos.fail_point("a.b")                    # hit 1: no fire
    with pytest.raises(chaos.ChaosInjected) as e:
        chaos.fail_point("a.b")                # hit 2: fires
    assert e.value.point == "a.b"
    # the kill rule is scoped to rank 1 -- rank 0 must not have it
    chaos.fail_point("c.d")


def test_spec_rules_scope_by_rank_and_generation():
    spec = chaos.make_spec(rules=[
        {"point": "p", "rank": 1},
        {"point": "q", "generation": 0},
        {"point": "r", "generation": 1},
    ])
    chaos.arm_from_spec(spec, rank=1, generation=1)
    with pytest.raises(chaos.ChaosInjected):
        chaos.fail_point("p")                  # rank matches
    chaos.fail_point("q")                      # generation 0 only: inert
    with pytest.raises(chaos.ChaosInjected):
        chaos.fail_point("r")                  # generation matches


def test_arm_from_spec_env_is_explicit_opt_in(monkeypatch):
    """MXNET_TPU_CHAOS_SPEC in the environment arms NOTHING by itself
    -- production stays env-inert; only the explicit harness call
    replays it (and picks rank/generation from the launcher env)."""
    spec = chaos.make_spec(rules=[{"point": "x.y", "rank": 1}])
    monkeypatch.setenv("MXNET_TPU_CHAOS_SPEC", spec)
    monkeypatch.setenv("MXNET_TPU_PROC_ID", "1")
    chaos.fail_point("x.y")                    # env alone: inert
    assert not chaos.armed()
    assert chaos.arm_from_spec() is True       # the explicit call
    with pytest.raises(chaos.ChaosInjected):
        chaos.fail_point("x.y")


def test_arm_from_spec_empty_and_bad_action():
    assert chaos.arm_from_spec("") is False
    assert chaos.arm_from_spec("   ") is False
    assert not chaos.armed()
    with pytest.raises(MXNetError):
        chaos.make_spec(rules=[{"point": "p", "action": "explode"}])
    with pytest.raises(MXNetError):
        chaos.make_spec(rules=[{"action": "raise"}])   # no point
    # dict actions decode
    chaos.arm_from_spec(chaos.make_spec(rules=[
        {"point": "s", "action": {"sleep": 0.0}},
        {"point": "t", "action": {"truncate": {"fname": "f", "keep": 4}}},
    ]))
    chaos.fail_point("s")                      # sleep(0) fires harmlessly


# ---------------------------------------------------------------------
# typed failures + bounded KV retry (satellite: _kv_get/wait_at_barrier)
# ---------------------------------------------------------------------

def test_typed_error_hierarchy_and_fields():
    e = dist.BarrierTimeout("boom", tag="ckpt_written", seq=4,
                            ranks=[1, 3], elapsed_s=6.0,
                            presumed_dead=[3])
    assert isinstance(e, dist.RankFailure)
    assert isinstance(e, MXNetError)
    assert e.tag == "ckpt_written" and e.seq == 4
    assert e.ranks == (1, 3) and e.presumed_dead == (3,)
    assert e.elapsed_s == 6.0


def test_kv_attempt_retries_transient_then_succeeds(counters):
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise RuntimeError("UNAVAILABLE: socket closed")
        return 7

    assert dist._kv_attempt(flaky, "get:k", "test", 1) == 7
    assert len(calls) == 3
    # the tolerated transients are survival-counted against the
    # host-collective fail point (the injected-AND-survived pair)
    assert chaos.stats()["survived"]["dist.collective"] == 2


def test_kv_attempt_deadline_is_not_retried():
    calls = []

    def dead():
        calls.append(1)
        raise RuntimeError("DEADLINE_EXCEEDED: GetKeyValue() timed out")

    with pytest.raises(dist._KVTimeout):
        dist._kv_attempt(dead, "get:k", "test", 1)
    assert len(calls) == 1                     # immediate, no retry


def test_kv_attempt_exhausted_raises_rank_failure():
    def always():
        raise RuntimeError("UNAVAILABLE: nope")

    with pytest.raises(dist.RankFailure) as e:
        dist._kv_attempt(always, "set:k", "broadcast", 9)
    assert e.value.tag == "broadcast" and e.value.seq == 9
    assert "3 attempt(s)" in str(e.value)


def test_injected_fault_at_collective_is_absorbed_by_retry(counters):
    """A chaos RAISE at dist.collective sits INSIDE the retry domain:
    injected weather is tolerated exactly like real weather."""
    chaos.arm(0)
    chaos.on("dist.collective", nth=1, action=chaos.RAISE)
    assert dist._kv_attempt(lambda: 42, "get:k", "allreduce", 1) == 42
    st = chaos.stats()
    assert st["injected"]["dist.collective"] == 1
    assert st["survived"]["dist.collective"] == 1
    assert counters.counter("chaos.injected").value == 1


# ---------------------------------------------------------------------
# attributed barrier (fake world)
# ---------------------------------------------------------------------

def test_barrier_completes_when_peer_acks(fake_world, monkeypatch):
    monkeypatch.setenv("MXNET_TPU_DIST_BARRIER_TIMEOUT_MS", "2000")
    fake_world.store["mxbar/g0/t/1/1"] = b"ok"
    dist.barrier("t")                          # seq 1; must not raise
    assert "mxbar/g0/t/1/0" in fake_world.store


def test_barrier_timeout_names_missing_rank(fake_world, monkeypatch,
                                            counters):
    monkeypatch.setenv("MXNET_TPU_DIST_BARRIER_TIMEOUT_MS", "300")
    with pytest.raises(dist.BarrierTimeout) as e:
        dist.barrier("ckpt_written")
    err = e.value
    assert err.ranks == (1,)
    assert err.tag == "ckpt_written" and err.seq == 1
    assert err.elapsed_s is not None
    # no lease was ever beaten for rank 1 -> presumed dead
    assert err.presumed_dead == (1,)
    assert "rank(s) [1]" in str(err)
    assert counters.counter("dist.rank_failures").value == 1


def test_barrier_abort_ack_fails_fast_with_rank_failure(fake_world,
                                                        monkeypatch):
    monkeypatch.setenv("MXNET_TPU_DIST_BARRIER_TIMEOUT_MS", "5000")
    fake_world.store["mxbar/g0/ckpt_written/1/1"] = b"abort:IOError"
    t0 = time.monotonic()
    with pytest.raises(dist.RankFailure) as e:
        dist.barrier("ckpt_written")
    assert not isinstance(e.value, dist.BarrierTimeout)
    assert e.value.ranks == (1,)
    # the abort ack short-circuits: nowhere near the 5 s bound
    assert time.monotonic() - t0 < 2.0


def test_post_abort_consumes_lockstep_seq(fake_world):
    dist.post_abort("ckpt_written", reason="ChaosInjected")
    assert dist._seq[0] == 1
    assert fake_world.store["mxbar/g0/ckpt_written/1/0"] \
        .startswith(b"abort")


def test_generation_sweep_deletes_previous_gen_keys(fake_world,
                                                    monkeypatch):
    monkeypatch.setenv("MXNET_TPU_GENERATION", "2")
    monkeypatch.setenv("MXNET_TPU_DIST_BARRIER_TIMEOUT_MS", "300")
    fake_world.store["mxbar/g1/old/3/1"] = b"ok"
    fake_world.store["mxlive/g1/1"] = b"123.0"
    fake_world.store["mxbar/g2/t/1/1"] = b"ok"     # current gen: kept
    dist.barrier("t")
    assert "mxbar/g1/" in fake_world.deleted
    assert "mxlive/g1/" in fake_world.deleted
    assert "mxkv_ar/g1/" in fake_world.deleted
    assert not any(k.startswith("mxbar/g1/") for k in fake_world.store)
    # one-shot latch: a second barrier does not re-sweep
    ndel = len(fake_world.deleted)
    fake_world.store["mxbar/g2/t/2/1"] = b"ok"
    dist.barrier("t")
    assert not any(d == "mxbar/g1/" for d in fake_world.deleted[ndel:])


def test_lease_beat_age_and_stale(fake_world, monkeypatch):
    assert dist.beat_lease() is True
    assert "mxlive/g0/0" in fake_world.store
    age = dist.lease_age(0)
    assert age is not None and age < 5.0
    assert dist.lease_age(1) is None           # never beaten
    # backdate our own lease past the ttl
    fake_world.store["mxlive/g0/0"] = repr(time.time() - 60).encode()
    assert dist.stale_ranks(ttl_s=10.0) == [0, 1]
    assert dist.stale_ranks(ttl_s=10.0, ranks=[1]) == [1]


def test_lease_beater_is_none_single_process():
    assert dist.lease_beater() is None
    assert dist.beat_lease() is False


# ---------------------------------------------------------------------
# rank-death-safe sharded commit (single-process surface)
# ---------------------------------------------------------------------

def _params(scale=1.0):
    return {"w": mx.nd.array(np.arange(8, dtype=np.float32) * scale)}


def test_sharded_abort_on_injected_shard_write_fault(tmp_path, counters):
    """A RAISE at the shard write aborts the save CLEANLY: staging
    swept, commit_aborted counted, survived paired with the injecting
    point, and the manager keeps working afterwards."""
    mgr = CheckpointManager(str(tmp_path), sharded=True)
    mgr.save(1, {"params": _params()})
    chaos.arm(0)
    chaos.on("checkpoint.sharded.shard_write", nth=1, action=chaos.RAISE)
    with pytest.raises(chaos.ChaosInjected):
        mgr.save(2, {"params": _params(2.0)})
    chaos.disarm()
    assert mgr.latest_step() == 1
    assert not any(d.endswith(".shared.tmp")
                   for d in os.listdir(str(tmp_path)))
    assert counters.counter("checkpoint.commit_aborted").value == 1
    st = chaos.stats()
    assert st["injected"]["checkpoint.sharded.shard_write"] == 1
    assert st["survived"]["checkpoint.sharded.shard_write"] == 1
    chaos.reset()
    mgr.save(3, {"params": _params(3.0)})      # the manager recovered
    assert mgr.latest_step() == 3


def test_sharded_commit_kill_leaves_staging_next_manager_sweeps(
        tmp_path, counters):
    """A KILL at the merged-manifest commit (the coordinator dying) in
    a subprocess: exit 137, the shared staging dir is stranded WITHOUT
    a manifest inside the step namespace, the next manager init sweeps
    it (owner pid is dead), and discovery falls back one step."""
    code = r"""
import sys
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import chaos
from mxnet_tpu.checkpoint import CheckpointManager

root = sys.argv[1]
mgr = CheckpointManager(root, sharded=True)
p = {"w": mx.nd.array(np.arange(8, dtype=np.float32))}
mgr.save(1, {"params": p})
chaos.arm(0)
chaos.on("checkpoint.sharded.commit", nth=1, action=chaos.KILL)
mgr.save(2, {"params": p})
raise SystemExit("kill did not fire")
"""
    out = subprocess.run(
        [sys.executable, "-c", code, str(tmp_path)],
        capture_output=True, text=True, timeout=180,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": REPO + os.pathsep
             + os.environ.get("PYTHONPATH", "")})
    assert out.returncode == 137, (out.stdout[-800:], out.stderr[-800:])
    leftovers = [d for d in os.listdir(str(tmp_path))
                 if d.endswith(".shared.tmp")]
    assert leftovers == ["step_00000002.shared.tmp"], leftovers
    mgr = CheckpointManager(str(tmp_path), sharded=True)  # init sweeps
    assert not any(d.endswith(".shared.tmp")
                   for d in os.listdir(str(tmp_path)))
    assert mgr.latest_step() == 1
    assert chaos.stats()["survived"][
        "checkpoint.sharded.shard_write"] >= 1   # the sweep's credit


def test_sweep_shared_staging_owner_liveness(tmp_path):
    root = str(tmp_path)
    # dead owner: a pid from an already-exited (reaped) subprocess
    proc = subprocess.Popen([sys.executable, "-c", "pass"])
    proc.wait()
    dead_pid = proc.pid
    d1 = os.path.join(root, "step_00000001.shared.tmp")
    os.makedirs(d1)
    open(os.path.join(d1, ".owner.%d" % dead_pid), "w").close()
    # live owner (us), with a dead rank's interior tmp crumb
    d2 = os.path.join(root, "step_00000002.shared.tmp")
    os.makedirs(d2)
    open(os.path.join(d2, ".owner.%d" % os.getpid()), "w").close()
    crumb = os.path.join(d2, "params.shard00001.params.%d.tmp"
                         % dead_pid)
    open(crumb, "w").close()
    # markerless dir (a pre-marker writer): swept
    d3 = os.path.join(root, "step_00000003.shared.tmp")
    os.makedirs(d3)
    removed = sharded.sweep_shared_staging(root)
    assert d1 in removed and d3 in removed
    assert os.path.isdir(d2) and not os.path.exists(crumb)
    assert crumb in removed


def test_disarmed_fail_points_make_zero_visits(tmp_path, monkeypatch):
    """The acceptance contract: disarmed chaos on the new sites costs
    ONE module-flag check -- a full sharded save makes zero calls into
    the chaos visit machinery."""
    from mxnet_tpu.chaos import core as chaos_core
    calls = []
    real_visit = chaos_core._visit
    monkeypatch.setattr(chaos_core, "_visit",
                        lambda *a: calls.append(a) or real_visit(*a))
    mgr = CheckpointManager(str(tmp_path), sharded=True)
    mgr.save(1, {"params": _params()})
    assert mgr.latest_step() == 1
    assert calls == []


def test_single_process_trainer_never_touches_leases(tmp_path,
                                                     monkeypatch):
    """Disabled-supervisor overhead contract: a single-process
    ContinuousTrainer binds no lease beater and makes zero calls into
    distributed.beat_lease (one attribute check per step)."""
    from mxnet_tpu.chaos import scenarios
    calls = []
    monkeypatch.setattr(dist, "beat_lease",
                        lambda: calls.append(1))
    net, trainer, loss_fn, data = scenarios.train_fixtures(seed=0)
    ct = ContinuousTrainer(net, trainer, loss_fn, data,
                           str(tmp_path / "ck"), publish_every=2)
    assert ct._lease_beat is None
    ct.run_steps(2)
    ct.close()
    assert calls == []


def test_publish_policy_continue_vs_raise(tmp_path):
    from mxnet_tpu.chaos import scenarios
    net, trainer, loss_fn, data = scenarios.train_fixtures(seed=0)
    ct = ContinuousTrainer(net, trainer, loss_fn, data,
                           str(tmp_path / "ck"), publish_every=1,
                           on_publish_error="continue")
    boom = dist.RankFailure("peer died", tag="ckpt_written", ranks=[1])
    fails = [2]                     # fail the publish of step 2 only

    real = ct.manager.save_training

    def flaky(step, *a, **kw):
        if step in fails:
            raise boom
        return real(step, *a, **kw)

    ct.manager.save_training = flaky
    with pytest.warns(RuntimeWarning, match="continuing past it"):
        ct.run_steps(3)             # survives the failed publish
    assert ct.step == 3
    assert ct.published_step == 3
    assert ct.manager.latest_step() == 3
    ct.close()

    # policy "raise" (the supervised default) surfaces the typed error
    ct2 = ContinuousTrainer(net, trainer, loss_fn, data,
                            str(tmp_path / "ck2"), publish_every=1)
    ct2.manager.save_training = flaky
    fails[0] = ct2.step + 2
    with pytest.raises(dist.RankFailure):
        ct2.run_steps(3)
    with pytest.raises(MXNetError):
        ContinuousTrainer(net, trainer, loss_fn, data,
                          str(tmp_path / "ck3"),
                          on_publish_error="shrug")


# ---------------------------------------------------------------------
# elastic restart supervisor
# ---------------------------------------------------------------------

def _gen_worker(fail_gen, fail_rank, exit_code=3):
    return (
        "import os,sys\n"
        "g=int(os.environ['MXNET_TPU_GENERATION'])\n"
        "r=int(os.environ['MXNET_TPU_PROC_ID'])\n"
        "print('WORKER g%%d r%%d' %% (g, r))\n"
        "sys.exit(%d if (g==%d and r==%d) else 0)\n"
        % (exit_code, fail_gen, fail_rank))


def test_supervisor_relaunches_with_bumped_generation(counters):
    obs_status.reset()
    sup = Supervisor([sys.executable, "-c", _gen_worker(0, 1)], 2,
                     max_restarts=2, grace_s=3)
    assert sup.run() == 0
    assert sup.restarts == 1 and sup.generation == 1
    assert not sup.generation_down
    assert counters.counter("supervisor.restarts").value == 1
    assert chaos.stats()["survived"]["supervisor.rank_exit"] == 1
    ready, reasons = obs_status.health()
    assert ready, reasons


def test_supervisor_budget_exhaustion_flips_healthz(counters):
    obs_status.reset()
    sup = Supervisor([sys.executable, "-c", "import sys; sys.exit(2)"],
                     2, max_restarts=1, grace_s=2)
    assert sup.run() == 2
    assert sup.exhausted and sup.generation_down
    assert sup.restarts == 1
    assert counters.counter("supervisor.budget_exhausted").value == 1
    ready, reasons = obs_status.health()
    assert not ready
    assert "restart_budget_exhausted:1" in reasons
    snap = obs_status.statusz()
    assert snap["supervisors"] == [{"generation": 1, "restarts": 1,
                                    "down": True, "exhausted": True}]
    obs_status.reset()


def test_launch_py_supervise_cli():
    worker = ("import os,sys;"
              "g=int(os.environ['MXNET_TPU_GENERATION']);"
              "r=int(os.environ['MXNET_TPU_PROC_ID']);"
              "print('W g%d r%d'%(g,r));"
              "sys.exit(5 if (g==0 and r==0) else 0)")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "launch.py"),
         "-n", "2", "--supervise", "--max-restarts", "2",
         "--grace", "5", sys.executable, "-c", worker],
        capture_output=True, text=True, timeout=120,
        env={**os.environ,
             "PYTHONPATH": REPO + os.pathsep
             + os.environ.get("PYTHONPATH", "")})
    assert out.returncode == 0, (out.stdout[-1500:], out.stderr[-500:])
    assert "relaunching generation 1" in out.stdout
    assert "[g1.0] W g1 r0" in out.stdout
    assert "[g1.1] W g1 r1" in out.stdout


# ---------------------------------------------------------------------
# REAL multi-process gloo scenarios (seeded cross-process chaos)
# ---------------------------------------------------------------------

_GLOO_PRELUDE = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import chaos, telemetry
from mxnet_tpu import distributed as dist
from mxnet_tpu.checkpoint import CheckpointManager

outdir = sys.argv[1]
assert mx.distributed_init() is True
nproc, rank = dist.world()
telemetry.enable()
chaos.arm_from_spec()        # replay the launcher's seeded scenario
mgr = CheckpointManager(outdir + "/ckpts")
params = {"w": mx.nd.array(np.arange(8, dtype=np.float32))}
"""


def _spawn_world(tmp_path, script, n, extra_env, timeout=240):
    """Launch ``n`` ranks WITHOUT fail-fast teardown (unlike
    tools/launch.py) so survivors can finish their typed-error
    handling; returns ``[(rc, output), ...]`` by rank."""
    path = tmp_path / "worker.py"
    path.write_text(script)
    s = socket.socket()
    s.bind(("", 0))
    coord = "127.0.0.1:%d" % s.getsockname()[1]
    s.close()
    procs = []
    for rank in range(n):
        env = {**os.environ,
               "PYTHONPATH": REPO + os.pathsep
               + os.environ.get("PYTHONPATH", ""),
               "JAX_PLATFORMS": "cpu",
               "MXNET_TPU_COORDINATOR": coord,
               "MXNET_TPU_NUM_PROCS": str(n),
               "MXNET_TPU_PROC_ID": str(rank),
               "MXNET_TPU_DIST_BARRIER_TIMEOUT_MS": "6000",
               "MXNET_TPU_DIST_LEASE_TTL_S": "3",
               **extra_env}
        procs.append(subprocess.Popen(
            [sys.executable, "-u", str(path), str(tmp_path)],
            env=env, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True))
    out = []
    deadline = time.time() + timeout
    for p in procs:
        try:
            text, _ = p.communicate(timeout=max(1, deadline - time.time()))
        except subprocess.TimeoutExpired:
            p.kill()
            text, _ = p.communicate()
        out.append((p.returncode, text))
    return out


_SURVIVOR_TAIL = r"""
try:
    mgr.save(2, {"params": params})
except dist.BarrierTimeout as e:
    assert 1 in e.ranks, e.ranks
    assert e.tag == EXPECT_TAG, e.tag
    assert 1 in e.presumed_dead, e.presumed_dead   # lease went stale
    assert e.elapsed_s is not None and e.elapsed_s < 10.0
    assert mgr.latest_step() == 1, mgr.all_steps()     # one-step fallback
    assert not os.path.isdir(mgr.step_dir(2)), "manifest committed!"
    assert not any(d.endswith(".shared.tmp")
                   for d in os.listdir(outdir + "/ckpts")), "staging left"
    assert telemetry.counter("checkpoint.commit_aborted").value == 1
    assert telemetry.counter("dist.rank_failures").value >= 1
    surv = chaos.stats()["survived"]
    assert surv.get(SURVIVED_POINT), surv
    print("SURVIVOR_OK rank=%d tag=%s ranks=%s dead=%s" % (
        rank, e.tag, list(e.ranks), list(e.presumed_dead)), flush=True)
    dist.failfast_exit(0)
raise SystemExit("kill did not fire (rank %d)" % rank)
"""


@pytest.mark.skipif(os.environ.get("MXNET_TPU_SKIP_DIST") == "1",
                    reason="dist tests disabled")
def test_two_process_kill_mid_shard_write_gloo(tmp_path):
    """Chaos-KILL rank 1 mid-shard-write of step 2's save: the
    survivor raises a typed BarrierTimeout at the 'written' barrier
    NAMING rank 1 (presumed dead by its stale lease), the staging is
    swept, no manifest exists, and discovery falls back one step."""
    script = _GLOO_PRELUDE + r"""
mgr.save(1, {"params": params})
dist.barrier("step1_done")
assert mgr.latest_step() == 1
EXPECT_TAG = "ckpt_written"
SURVIVED_POINT = "checkpoint.sharded.barrier.written"
""" + _SURVIVOR_TAIL
    spec = chaos.make_spec(seed=0, rules=[
        {"point": "checkpoint.sharded.shard_write", "action": "kill",
         "nth": 2, "rank": 1}])
    results = _spawn_world(tmp_path, script, 2,
                           {"MXNET_TPU_CHAOS_SPEC": spec})
    assert results[1][0] == 137, results[1][1][-1500:]
    assert results[0][0] == 0, results[0][1][-1500:]
    assert "SURVIVOR_OK rank=0 tag=ckpt_written" in results[0][1]


@pytest.mark.skipif(os.environ.get("MXNET_TPU_SKIP_DIST") == "1",
                    reason="dist tests disabled")
def test_two_process_kill_between_barriers_gloo(tmp_path):
    """THE acceptance scenario: chaos-KILL rank 1 between the
    'written' and 'committed' barriers of step 2's sharded save.  The
    merged manifest was already STAGED by rank 0 -- but the commit
    gate means it is never renamed in: no step-2 dir exists, the
    survivor's typed BarrierTimeout names rank 1 within the bound,
    and latest_step() falls back one step."""
    script = _GLOO_PRELUDE + r"""
mgr.save(1, {"params": params})
dist.barrier("step1_done")
assert mgr.latest_step() == 1
EXPECT_TAG = "ckpt_committed"
SURVIVED_POINT = "checkpoint.sharded.barrier.committed"
""" + _SURVIVOR_TAIL
    spec = chaos.make_spec(seed=0, rules=[
        {"point": "checkpoint.sharded.barrier.committed",
         "action": "kill", "nth": 2, "rank": 1}])
    results = _spawn_world(tmp_path, script, 2,
                           {"MXNET_TPU_CHAOS_SPEC": spec})
    assert results[1][0] == 137, results[1][1][-1500:]
    assert results[0][0] == 0, results[0][1][-1500:]
    assert "SURVIVOR_OK rank=0 tag=ckpt_committed" in results[0][1]


@pytest.mark.slow
@pytest.mark.skipif(os.environ.get("MXNET_TPU_SKIP_DIST") == "1",
                    reason="dist tests disabled")
def test_four_process_kill_during_stage_gloo(tmp_path):
    """4-rank world, rank 2 killed AT the 'stage' rendezvous of the
    first save: every survivor aborts cleanly with a BarrierTimeout
    naming rank 2 and sweeps the staging."""
    script = _GLOO_PRELUDE + r"""
try:
    mgr.save(1, {"params": params})
except dist.BarrierTimeout as e:
    assert 2 in e.ranks, e.ranks
    assert e.tag == "ckpt_stage", e.tag
    assert telemetry.counter("checkpoint.commit_aborted").value == 1
    assert not any(d.endswith(".shared.tmp")
                   for d in os.listdir(outdir + "/ckpts"))
    surv = chaos.stats()["survived"]
    assert surv.get("checkpoint.sharded.barrier.stage"), surv
    print("SURVIVOR_OK rank=%d ranks=%s" % (rank, list(e.ranks)),
          flush=True)
    dist.failfast_exit(0)
raise SystemExit("kill did not fire (rank %d)" % rank)
"""
    spec = chaos.make_spec(seed=0, rules=[
        {"point": "checkpoint.sharded.barrier.stage", "action": "kill",
         "nth": 1, "rank": 2}])
    results = _spawn_world(tmp_path, script, 4,
                           {"MXNET_TPU_CHAOS_SPEC": spec})
    assert results[2][0] == 137, results[2][1][-1500:]
    for r in (0, 1, 3):
        assert results[r][0] == 0, (r, results[r][1][-1500:])
        assert "SURVIVOR_OK rank=%d" % r in results[r][1]


_ELASTIC_WORKER = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import chaos, telemetry
from mxnet_tpu import distributed as dist
from mxnet_tpu.chaos import scenarios
from mxnet_tpu.serving.loop import ContinuousTrainer

outdir = sys.argv[1]
assert mx.distributed_init() is True
nproc, rank = dist.world()
gen = dist.generation()
telemetry.enable()
chaos.arm_from_spec()            # generation-scoped: inert in gen 1

# identical replicated params on every rank (the SPMD contract the
# one-program path gets from its init-time broadcast): seed the init
np.random.seed(0)
mx.random.seed(0)
net, trainer, loss_fn, data = scenarios.train_fixtures(seed=0)
ct = ContinuousTrainer(net, trainer, loss_fn, data, outdir + "/ckpts",
                       publish_every=1)
ckpt = ct.resume()

def dump_params(tag):
    arrs = {k: p._reduce().asnumpy() for k, p in
            net._collect_params_with_prefix().items()}
    np.savez(outdir + "/%s_rank%d.npz" % (tag, rank), **arrs)

if gen == 0:
    assert ckpt is None
    ct.run_steps(1)                  # publish step 1 (verified)
    dump_params("step1")             # the bit-identical reference
    try:
        ct.run_steps(2)              # step-2 publish: rank 1 dies
    except dist.BarrierTimeout as e:
        assert 1 in e.ranks, e.ranks
        assert ct.manager.latest_step() == 1, ct.manager.all_steps()
        print("SURVIVOR_ABORT rank=%d %s: %s" % (
            rank, type(e).__name__, e), flush=True)
        dist.failfast_exit(3)        # surface to the supervisor
    raise SystemExit("kill did not fire (rank %d)" % rank)

assert gen == 1, gen
assert ckpt is not None and ckpt.step == 1, ckpt
side = np.load(outdir + "/step1_rank%d.npz" % rank)
for k, p in sorted(net._collect_params_with_prefix().items()):
    a = p.data().asnumpy()
    b = side[k]
    assert a.dtype == b.dtype and a.tobytes() == b.tobytes(), k
print("RESUME_BIT_IDENTICAL rank=%d generation=%d step=%d"
      % (rank, gen, ckpt.step), flush=True)
ct.run_steps(2)                      # steps 2..3 publish clean
# rank 0 renames AFTER the commit gate: rendezvous before reading
# (the read-after-save contract of checkpoint/sharded.py)
dist.barrier("gen1_steps_done")
assert ct.manager.latest_step() == 3, ct.manager.all_steps()
ct.close()
print("GEN1_DONE rank=%d" % rank, flush=True)
"""


@pytest.mark.slow
@pytest.mark.skipif(os.environ.get("MXNET_TPU_SKIP_DIST") == "1",
                    reason="dist tests disabled")
def test_supervised_elastic_restart_bit_identical_gloo(tmp_path):
    """The full ISSUE-15 loop through tools/launch.py --supervise:
    rank 1 chaos-KILLed between the 'written' and 'committed' barriers
    of step 2's publish (generation 0), the survivor aborts with a
    typed error and exits, the supervisor relaunches generation 1, and
    both ranks resume with parameters BIT-IDENTICAL to the last
    verified step."""
    worker = tmp_path / "elastic_worker.py"
    worker.write_text(_ELASTIC_WORKER)
    spec = chaos.make_spec(seed=0, rules=[
        {"point": "checkpoint.sharded.barrier.committed",
         "action": "kill", "nth": 2, "rank": 1, "generation": 0}])
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "launch.py"),
         "-n", "2", "--supervise", "--max-restarts", "2",
         "--grace", "30",
         sys.executable, "-u", str(worker), str(tmp_path)],
        capture_output=True, text=True, timeout=420,
        env={**os.environ,
             "PYTHONPATH": REPO + os.pathsep
             + os.environ.get("PYTHONPATH", ""),
             "JAX_PLATFORMS": "cpu",
             "MXNET_TPU_CHAOS_SPEC": spec,
             "MXNET_TPU_DIST_BARRIER_TIMEOUT_MS": "8000",
             "MXNET_TPU_DIST_LEASE_TTL_S": "4"})
    assert out.returncode == 0, (out.stdout[-3000:], out.stderr[-800:])
    assert "SURVIVOR_ABORT rank=0 BarrierTimeout" in out.stdout
    assert "relaunching generation 1" in out.stdout
    assert "RESUME_BIT_IDENTICAL rank=0 generation=1 step=1" in out.stdout
    assert "RESUME_BIT_IDENTICAL rank=1 generation=1 step=1" in out.stdout
    assert out.stdout.count("GEN1_DONE") == 2
