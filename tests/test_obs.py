"""Observability ops plane tests (ISSUE 13): context-propagated
tracing with the zero-call disabled contract, serving/training span
reconciliation against the telemetry counters, the crash-safe flight
recorder (including a real os._exit subprocess), the /healthz //statusz
/metrics introspection server, the watcher-suspension event, the
multi-rank skew summarizer, and the generated instrument index."""
import ast
import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, obs, telemetry
from mxnet_tpu.chaos import scenarios
from mxnet_tpu.obs import flight
from mxnet_tpu.serving.loop import ContinuousTrainer, RegistryWatcher
from mxnet_tpu.telemetry import cli as tcli
from mxnet_tpu.telemetry import hooks as thooks


@pytest.fixture(autouse=True)
def _clean_obs():
    """Every test starts with tracing off, empty rings, no recorder,
    no server, and a clean status board (obs state is process-global
    by design, like telemetry)."""
    obs.disable_tracing()
    obs.trace.clear()
    obs.status.reset()
    flight.uninstall()
    telemetry.disable()
    telemetry.registry().clear()
    yield
    obs.disable_tracing()
    obs.trace.clear()
    obs.status.reset()
    flight.uninstall()
    obs.server.stop()
    telemetry.disable()
    if telemetry._jsonl_sink is not None:
        telemetry.registry().detach(telemetry._jsonl_sink)
        telemetry._jsonl_sink.close()
        telemetry._jsonl_sink = None
    telemetry.registry().clear()


def _spans_by_name():
    out = {}
    for s in obs.spans():
        out.setdefault(s["name"], []).append(s)
    return out


# ---------------------------------------------------------------------
# trace core
# ---------------------------------------------------------------------

def test_trace_context_parenting_and_restore():
    obs.enable_tracing()
    with obs.start_trace("root") as rc:
        assert obs.current().trace_id == rc.trace_id
        with obs.span("child") as cc:
            assert cc.trace_id == rc.trace_id
            assert obs.current().span_id == cc.span_id
        assert obs.current().span_id == rc.span_id
    assert obs.current() is None
    spans = obs.spans()
    assert [s["name"] for s in spans] == ["child", "root"]
    child, root = spans
    assert child["parent"] == rc.span_id
    assert root["parent"] is None
    assert child["trace"] == root["trace"] == rc.trace_id
    assert child["dur"] >= 0


def test_contextvar_isolation_across_threads():
    obs.enable_tracing()
    seen = {}

    def worker():
        seen["ctx"] = obs.current()      # no inherited context
        with obs.span("t2"):
            seen["inner"] = obs.current()

    with obs.start_trace("main"):
        t = threading.Thread(target=worker)
        t.start()
        t.join()
        assert obs.current() is not None
    assert seen["ctx"] is None           # threads don't leak context
    assert seen["inner"] is not None


def test_fresh_context_adopts_current_trace():
    obs.enable_tracing()
    with obs.start_trace("outer") as rc:
        ctx = obs.trace.fresh_context()
        assert ctx.trace_id == rc.trace_id
        assert ctx.span_id != rc.span_id
    ctx2 = obs.trace.fresh_context()
    assert ctx2.trace_id != rc.trace_id  # no active trace -> new one


def test_span_ring_bounded():
    obs.enable_tracing()
    cap = obs.trace._MAX_SPANS
    ctx = obs.TraceContext("t" * 16, "s" * 16)
    for i in range(cap + 100):
        obs.record_span("spam", ctx, t0=0.0, dur=0.0)
    assert len(obs.spans()) <= cap
    assert obs.trace.dropped() > 0


def test_chrome_export_shape(tmp_path):
    obs.enable_tracing()
    with obs.start_trace("root"):
        with obs.span("inner", step=3):
            pass
    path = str(tmp_path / "trace.json")
    doc = obs.export_chrome_trace(path)
    with open(path) as f:
        assert json.load(f) == doc
    evs = doc["traceEvents"]
    assert len(evs) == 2
    for ev in evs:
        assert ev["ph"] == "X"
        assert ev["args"]["trace"] and ev["args"]["span"]
    inner = [e for e in evs if e["name"] == "inner"][0]
    assert inner["args"]["parent"]
    assert inner["args"]["step"] == 3


# ---------------------------------------------------------------------
# the zero-call disabled contract (the PR-2 proof, for tracing)
# ---------------------------------------------------------------------

def _exercise_traced_paths(tmp_path, tag):
    net, trainer, loss_fn, data = scenarios.train_fixtures(seed=0)
    ct = ContinuousTrainer(net, trainer, loss_fn, data,
                           str(tmp_path / ("ck_%s" % tag)),
                           publish_every=1)
    ct.run_steps(1)
    reg = mx.serving.ModelRegistry(compile_cache=False)
    watcher = RegistryWatcher(reg, "m", ct.manager, scenarios.make_mlp(),
                              input_shape=(8,), buckets=(1, 2),
                              max_wait_ms=2)
    watcher.poll_once()
    reg.infer("m", np.zeros(8, np.float32), timeout=30)
    reg.shutdown(drain=True)
    watcher.close()
    ct.close()


def test_tracing_disabled_makes_zero_trace_calls(tmp_path, monkeypatch):
    """The acceptance-criteria proof: with tracing off, the serving
    path, the training loop, the watcher, and checkpoint commit make
    ZERO calls into obs.trace -- each site costs its one module-flag
    check."""
    calls = []
    for name in ("begin_span", "end_span", "record_span",
                 "fresh_context"):
        orig = getattr(obs.trace, name)

        def counted(*a, _name=name, _orig=orig, **kw):
            calls.append(_name)
            return _orig(*a, **kw)

        monkeypatch.setattr(obs.trace, name, counted)
        if hasattr(obs, name):          # package-level re-exports
            monkeypatch.setattr(obs, name, counted)

    assert not obs.tracing_enabled()
    _exercise_traced_paths(tmp_path, "off")
    assert calls == [], "trace hooks fired while disabled: %r" % calls

    obs.enable_tracing()
    _exercise_traced_paths(tmp_path, "on")
    fired = set(calls)
    assert {"begin_span", "end_span", "record_span",
            "fresh_context"} <= fired, sorted(fired)


# ---------------------------------------------------------------------
# serving path spans
# ---------------------------------------------------------------------

def test_serving_spans_reconcile_with_counters():
    telemetry.enable()
    obs.enable_tracing()
    net = scenarios.make_mlp()
    reg = mx.serving.ModelRegistry(compile_cache=False)
    reg.register("m", block=net, input_shape=(8,), buckets=(1, 2, 4),
                 max_wait_ms=5)
    for _ in range(6):
        reg.infer("m", np.random.RandomState(0).rand(8)
                  .astype(np.float32), timeout=30)
    reg.shutdown(drain=True)
    by = _spans_by_name()
    requests = telemetry.counter("serving.requests").value
    batches = telemetry.counter("serving.batches").value
    assert len(by["serving.queue_wait"]) == requests == 6
    assert len(by["serving.request"]) == requests
    assert len(by["serving.respond"]) == requests
    for name in ("serving.batch", "serving.batch_assembly",
                 "serving.dispatch", "serving.device_get"):
        assert len(by[name]) == batches, name
    # dispatch + device_get span walls == the dispatch_time timer
    span_wall = sum(s["dur"] for s in by["serving.dispatch"]) \
        + sum(s["dur"] for s in by["serving.device_get"])
    assert abs(span_wall
               - telemetry.timer("serving.dispatch_time").sum) < 1e-6
    # fan-in links: every request root span is linked by some batch
    req_ids = {s["span"] for s in by["serving.request"]}
    linked = set()
    for b in by["serving.batch"]:
        linked.update(b.get("links", ()))
    assert linked == req_ids
    # queue/respond spans are children of their request root
    parents = {s["parent"] for s in by["serving.queue_wait"]}
    assert parents <= req_ids


def test_submit_joins_callers_trace():
    """A client that roots its own trace sees the request spans land in
    THAT trace -- end-to-end causality across the thread hop."""
    obs.enable_tracing()
    net = scenarios.make_mlp()
    reg = mx.serving.ModelRegistry(compile_cache=False)
    reg.register("m", block=net, input_shape=(8,), buckets=(1,),
                 max_wait_ms=2)
    with obs.start_trace("client") as rc:
        fut = reg.submit("m", np.zeros(8, np.float32), timeout=30)
        fut.result(timeout=30)
    reg.shutdown(drain=True)
    reqs = _spans_by_name()["serving.request"]
    assert any(s["trace"] == rc.trace_id for s in reqs)


# ---------------------------------------------------------------------
# training loop spans
# ---------------------------------------------------------------------

def test_training_loop_span_chain(tmp_path):
    obs.enable_tracing()
    net, trainer, loss_fn, data = scenarios.train_fixtures(seed=0)
    ct = ContinuousTrainer(net, trainer, loss_fn, data,
                           str(tmp_path / "ck"), publish_every=2)
    ct.run_steps(4)
    reg = mx.serving.ModelRegistry(compile_cache=False)
    watcher = RegistryWatcher(reg, "m", ct.manager, scenarios.make_mlp(),
                              input_shape=(8,), buckets=(1, 2),
                              max_wait_ms=2)
    assert watcher.poll_once() == 4
    by = _spans_by_name()
    assert len(by["train.step"]) == 4
    assert len(by["train.publish"]) == 2
    assert len(by["checkpoint.commit"]) == 2
    assert len(by["serving.watcher.discover"]) == 1
    assert len(by["serving.swap"]) == 1
    # the causal chain: commit under publish under step; warm/install
    # under the watcher's swap span
    by_id = {s["span"]: s for s in obs.spans()}
    pub = by["train.publish"][0]
    assert by_id[pub["parent"]]["name"] == "train.step"
    com = by["checkpoint.commit"][0]
    assert by_id[com["parent"]]["name"] == "train.publish"
    for child in ("serving.register.warm", "serving.register.install"):
        sp = by[child][0]
        assert by_id[sp["parent"]]["name"] == "serving.swap"
        assert sp["trace"] == by["serving.swap"][0]["trace"]
    reg.shutdown(drain=True)
    watcher.close()
    ct.close()


def test_spans_stream_to_jsonl_and_summarize_folds(tmp_path):
    telemetry.enable()
    obs.enable_tracing()
    path = str(tmp_path / "run.jsonl")
    telemetry.attach_jsonl(path)
    with obs.start_trace("work"):
        with obs.span("phase"):
            pass
    telemetry.flush()
    agg = tcli.summarize_file(path)
    assert agg["spans"]["phase"]["count"] == 1
    assert agg["spans"]["work"]["count"] == 1
    assert agg["rank"] == 0
    # raw records carry the trace wiring + the rank tag
    recs = [json.loads(line) for line in open(path)]
    spans = [r for r in recs if r["kind"] == "span"]
    assert {s["name"] for s in spans} == {"work", "phase"}
    assert all("rank" in s and "trace" in s and "span" in s
               for s in spans)


# ---------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------

def test_flight_ring_roundtrip_and_wrap(tmp_path):
    path = str(tmp_path / "x.bbox")
    rec = flight.FlightRecorder(path, capacity=4096)
    for i in range(400):
        rec.note("spam", i=i)
    rec.sync()
    out = flight.read(path)
    assert out, "empty ring"
    assert len(out) < 400                      # wrapped: oldest gone
    assert out[-1]["payload"]["i"] == 399      # newest survives
    idx = [r["payload"]["i"] for r in out]
    assert idx == sorted(idx)                  # order preserved
    rec.close()


def test_flight_is_a_telemetry_sink(tmp_path):
    telemetry.enable()
    rec = flight.install(str(tmp_path / "x.bbox"), capacity=8192)
    telemetry.event("myevent").emit(k=1)
    telemetry.timer("mytimer").observe(0.001)
    rec.sync()
    names = [r.get("name") for r in flight.read(rec.path)]
    assert "myevent" in names and "mytimer" in names


def test_flight_survives_os_exit_kill(tmp_path):
    """The acceptance gate: a chaos KILL mid-commit leaves a readable
    dump whose final events include the injected fault and the
    in-flight trace -- proven with a REAL os._exit(137) subprocess."""
    bbox = str(tmp_path / "crash.bbox")
    code = (
        "import mxnet_tpu as mx\n"
        "from mxnet_tpu import chaos, obs, telemetry\n"
        "telemetry.enable(); obs.enable_tracing()\n"
        "obs.install_blackbox(%r, capacity=65536)\n"
        "mgr = mx.checkpoint.CheckpointManager(%r)\n"
        "chaos.arm(seed=0)\n"
        "chaos.on('checkpoint.commit.pre_manifest', nth=2,\n"
        "         action=chaos.KILL)\n"
        "mgr.save(1, {'blob': b'one'})\n"
        "mgr.save(2, {'blob': b'two'})\n"     # dies mid-commit
        "raise SystemExit('kill did not fire')\n"
        % (bbox, str(tmp_path / "ck")))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 137, (out.returncode, out.stderr[-500:])
    recs = flight.read(bbox)
    assert recs, "ring empty after KILL"
    last = recs[-1]
    assert last["name"] == "chaos.kill"
    assert last["payload"]["point"] == "checkpoint.commit.pre_manifest"
    # the in-flight trace: the kill landed inside checkpoint.commit
    assert last["payload"]["trace"] and last["payload"]["span"]
    names = [r.get("name") for r in recs]
    assert "chaos.inject" in names             # the injected fault event
    spans = [r for r in recs if r.get("kind") == "span"]
    assert any(s["name"] == "checkpoint.commit" for s in spans)


def test_sigusr2_snapshots_thread_stacks(tmp_path):
    rec = flight.install(str(tmp_path / "x.bbox"), capacity=131072)
    os.kill(os.getpid(), signal.SIGUSR2)
    deadline = time.time() + 10
    while time.time() < deadline:              # signal delivery is async
        recs = [r for r in flight.read(rec.path)
                if r.get("name") == "obs.sigusr2"]
        if recs:
            break
        time.sleep(0.01)  # mxlint: disable=sleep-poll
    assert recs, "SIGUSR2 left no stack snapshot"
    stacks = recs[-1]["payload"]["stacks"]
    assert any("MainThread" in label for label in stacks)
    assert "test_sigusr2" in "".join(stacks.values())


def test_preemption_signal_marks_blackbox(tmp_path):
    from mxnet_tpu import preemption
    rec = flight.install(str(tmp_path / "x.bbox"), capacity=65536)
    net = scenarios.make_mlp()
    handler = preemption.install(str(tmp_path / "job"), net,
                                 save_in_handler=True)
    try:
        os.kill(os.getpid(), signal.SIGTERM)
        deadline = time.time() + 10
        marks = []
        while time.time() < deadline:
            marks = [r for r in flight.read(rec.path)
                     if r.get("name") == "preemption.signal"]
            if marks:
                break
            time.sleep(0.01)  # mxlint: disable=sleep-poll
        assert marks, "preemption handler left no blackbox mark"
        assert marks[-1]["payload"]["signum"] == int(signal.SIGTERM)
        assert handler.saved
    finally:
        handler.uninstall()


def test_flight_rejects_non_ring_and_tiny_capacity(tmp_path):
    bad = tmp_path / "notaring"
    bad.write_bytes(b"hello world, definitely not a ring header")
    with pytest.raises(mx.MXNetError):
        flight.read(str(bad))
    with pytest.raises(mx.MXNetError):
        flight.FlightRecorder(str(tmp_path / "t.bbox"), capacity=16)


# ---------------------------------------------------------------------
# introspection server + status board
# ---------------------------------------------------------------------

def _get(port, path):
    try:
        r = urllib.request.urlopen(
            "http://127.0.0.1:%d%s" % (port, path), timeout=10)
        return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def test_healthz_flips_on_watcher_suspension(tmp_path):
    port = obs.serve(0)
    code, body = _get(port, "/healthz")
    assert code == 200 and json.loads(body)["status"] == "READY"
    watcher = RegistryWatcher(mx.serving.ModelRegistry(
        compile_cache=False), "m", str(tmp_path / "ck"),
        scenarios.make_mlp(), input_shape=(8,))
    code, _ = _get(port, "/healthz")
    assert code == 200                        # healthy watcher: READY
    with watcher._lock:
        watcher._suspended = True             # the failure-budget state
    code, body = _get(port, "/healthz")
    body = json.loads(body)
    assert code == 503 and body["status"] == "NOT_READY"
    assert "watcher_suspended:m" in body["reasons"]
    watcher.close()


def test_healthz_flags_writer_failures_and_queue_saturation():
    telemetry.enable()
    ready, reasons = obs.status.health()
    assert ready
    telemetry.counter("checkpoint.write_failures").inc()
    ready, reasons = obs.status.health()
    assert not ready and reasons == ["checkpoint_write_failures:1"]
    telemetry.registry().clear()
    reg = mx.serving.ModelRegistry(compile_cache=False)
    reg.register("m", block=scenarios.make_mlp(), input_shape=(8,),
                 buckets=(1,), max_queue=0)   # always saturated
    ready, reasons = obs.status.health()
    assert not ready and "queue_saturated:m" in reasons
    reg.shutdown(drain=True)


def test_statusz_and_metrics_endpoints(tmp_path):
    telemetry.enable()
    port = obs.serve(0)
    net, trainer, loss_fn, data = scenarios.train_fixtures(seed=0)
    ct = ContinuousTrainer(net, trainer, loss_fn, data,
                           str(tmp_path / "ck"), publish_every=1)
    ct.run_steps(1)
    reg = mx.serving.ModelRegistry(compile_cache=False)
    watcher = RegistryWatcher(reg, "m", ct.manager, scenarios.make_mlp(),
                              input_shape=(8,), buckets=(1, 2),
                              max_wait_ms=2)
    assert watcher.poll_once() == 1
    code, body = _get(port, "/statusz")
    st = json.loads(body)
    assert code == 200
    assert st["served_step"] == 1 and st["published_step"] == 1
    assert st["watchers"][0]["name"] == "m"
    assert st["trainers"][0]["step"] == 1
    assert st["servables"][0]["name"] == "m"
    assert st["heartbeats"]                   # the loop beat
    assert st["swap_history"][-1]["ok"] is True
    code, prom = _get(port, "/metrics")
    assert code == 200
    assert b"mxnet_tpu_serving_swaps 1" in prom
    code, _ = _get(port, "/nope")
    assert code == 404
    reg.shutdown(drain=True)
    watcher.close()
    ct.close()


# ---------------------------------------------------------------------
# satellite: watcher suspension is an alertable event
# ---------------------------------------------------------------------

def test_watcher_suspension_emits_terminal_event(tmp_path):
    from mxnet_tpu import chaos
    telemetry.enable()
    net, trainer, loss_fn, data = scenarios.train_fixtures(seed=0)
    ct = ContinuousTrainer(net, trainer, loss_fn, data,
                           str(tmp_path / "ck"), publish_every=1)
    ct.run_steps(1)
    reg = mx.serving.ModelRegistry(compile_cache=False)
    watcher = RegistryWatcher(reg, "m", ct.manager, scenarios.make_mlp(),
                              input_shape=(8,), buckets=(1,),
                              swap_retries=0, failure_budget=1)
    with chaos.scenario(seed=0):
        chaos.on("serving.swap", action=chaos.RAISE)
        with pytest.warns(RuntimeWarning):
            assert watcher.poll_once() is None
    assert watcher.suspended
    assert telemetry.counter(
        "serving.watcher_suspensions").value == 1
    ev = telemetry.event("serving.watcher_suspended").recent[-1]
    assert ev["model"] == "m" and ev["step"] == 1 and ev["budget"] == 1
    watcher.close()
    ct.close()


# ---------------------------------------------------------------------
# satellite: bench env-health lands in telemetry
# ---------------------------------------------------------------------

def test_bench_env_health_records_gauges():
    import bench
    telemetry.enable()
    flag = bench._mark_env_health({"dispatch_roundtrip_us": 123.4,
                                   "h2d_mb_per_s": 55.0})
    assert flag is False
    assert telemetry.gauge("env.dispatch_roundtrip_us").value == 123.4
    assert telemetry.gauge("env.h2d_mb_per_s").value == 55.0
    ev = telemetry.event("env.health").recent[-1]
    assert ev["dispatch_roundtrip_us"] == 123.4
    # a 90 ms round trip flips degraded AND still records the number
    flag = bench._mark_env_health({"dispatch_roundtrip_us": 90000.0})
    assert flag is True
    assert telemetry.gauge("env.dispatch_roundtrip_us").value == 90000.0
    # telemetry off: the probe marks the flag with zero instrument calls
    telemetry.disable()
    telemetry.registry().clear()
    assert bench._mark_env_health({"dispatch_roundtrip_us": 1.0}) is False
    assert telemetry.registry().get("env.dispatch_roundtrip_us") is None


# ---------------------------------------------------------------------
# multi-rank summarize + skew (satellite + tentpole part 4)
# ---------------------------------------------------------------------

def _rank_file(tmp_path, rank, step_s, n=5):
    path = str(tmp_path / ("r%d.jsonl" % rank))
    sink = telemetry.JsonlSink(path, rank=rank)
    reg = telemetry.Registry()
    reg.attach(sink)
    t = reg.timer("trainer.step_time")
    for _ in range(n):
        t.observe(step_s)
    reg.flush()
    sink.close()
    return path


def test_jsonl_records_carry_rank_tag(tmp_path):
    path = _rank_file(tmp_path, 3, 0.01)
    recs = [json.loads(line) for line in open(path)]
    assert recs and all(r["rank"] == 3 for r in recs)
    assert tcli.summarize_file(path)["rank"] == 3


def test_multi_rank_skew_and_straggler_flag(tmp_path):
    p0 = _rank_file(tmp_path, 0, 0.010)
    p1 = _rank_file(tmp_path, 1, 0.011)
    p2 = _rank_file(tmp_path, 2, 0.030)       # the straggler
    agg = tcli.summarize_files([p0, p1, p2])
    assert [r["rank"] for r in agg["ranks"]] == [0, 1, 2]
    sk = agg["skew"]
    assert sk["straggler"] and sk["straggler_ranks"] == [2]
    assert sk["max_over_median"] == pytest.approx(30 / 11, rel=1e-3)
    # balanced ranks: no straggler
    agg = tcli.summarize_files([p0, p1])
    assert not agg["skew"]["straggler"]
    assert agg["skew"]["max_over_median"] == pytest.approx(1.1,
                                                           rel=1e-3)


def test_summarize_multi_file_cli_contract(tmp_path, capsys):
    p0 = _rank_file(tmp_path, 0, 0.010)
    p1 = _rank_file(tmp_path, 1, 0.030)
    assert tcli.main(["summarize", p0, p1, "--json"]) == 0
    agg = json.loads(capsys.readouterr().out)
    assert agg["skew"]["straggler_ranks"] == [1]
    assert tcli.main(["summarize", p0, p1]) == 0
    out = capsys.readouterr().out
    assert "STRAGGLER" in out and "rank" in out
    # a missing rank file fails the whole summarize (exit 1)
    assert tcli.main(["summarize", p0,
                      str(tmp_path / "missing.jsonl")]) == 1


# ---------------------------------------------------------------------
# satellite: blackbox CLI exit-code contract (mxlint convention)
# ---------------------------------------------------------------------

def test_blackbox_cli_exit_codes(tmp_path, capsys):
    path = str(tmp_path / "x.bbox")
    rec = flight.FlightRecorder(path, capacity=8192)
    rec.note("chaos.kill", point="p")
    rec.sync()
    assert tcli.main(["blackbox", path]) == 0          # success
    assert "chaos.kill" in capsys.readouterr().out
    assert tcli.main(["blackbox", path, "--json"]) == 0
    recs = json.loads(capsys.readouterr().out)
    assert recs[-1]["name"] == "chaos.kill"
    rec.close()
    # missing file -> 1
    assert tcli.main(["blackbox", str(tmp_path / "nope.bbox")]) == 1
    # a ring with zero records -> 1 (nothing to render is a failed gate)
    empty = flight.FlightRecorder(str(tmp_path / "e.bbox"),
                                  capacity=8192)
    empty.close()
    assert tcli.main(["blackbox", str(tmp_path / "e.bbox")]) == 1
    # not a ring at all -> 1, not a traceback
    bad = tmp_path / "garbage"
    bad.write_bytes(b"x" * 64)
    assert tcli.main(["blackbox", str(bad)]) == 1
    # usage errors -> 2
    assert tcli.main([]) == 2
    capsys.readouterr()


# ---------------------------------------------------------------------
# satellite: the generated instrument index cannot drift
# ---------------------------------------------------------------------

def _repo_root():
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_observability_doc_matches_generator():
    path = os.path.join(_repo_root(), "docs", "observability.md")
    with open(path) as f:
        text = f.read()
    begin, end = thooks._INDEX_BEGIN, thooks._INDEX_END
    assert begin in text and end in text
    inside = text.split(begin, 1)[1].split(end, 1)[0]
    assert inside.strip("\n") == thooks.instrument_index_md().strip("\n"), \
        "docs/observability.md instrument index is stale -- run " \
        "python -c 'from mxnet_tpu.telemetry import hooks; " \
        "hooks.update_observability_doc()'"


def test_every_hook_literal_is_catalogued():
    """AST sweep of telemetry/hooks.py: every literal instrument name
    passed to reg.counter/gauge/timer/event must appear in INSTRUMENTS
    (dynamic `prefix + key` families must have a `<placeholder>` row),
    so a new hook cannot ship unindexed."""
    catalogued = {ii.name for ii in thooks.INSTRUMENTS}
    prefixes = {ii.name.split("<", 1)[0] for ii in thooks.INSTRUMENTS
                if "<" in ii.name}
    src = open(thooks.__file__.rstrip("c")).read()
    tree = ast.parse(src)
    checked = 0
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in ("counter", "gauge", "timer",
                                       "event")
                and node.args):
            continue
        arg = node.args[0]
        # take only the NAME positions: a bare literal, both arms of a
        # conditional, or the literal prefix of a `"x." + key` concat
        if isinstance(arg, ast.IfExp):
            cands = [arg.body, arg.orelse]
        elif isinstance(arg, ast.BinOp):
            cands = [arg.left]
        else:
            cands = [arg]
        for const in cands:
            if not (isinstance(const, ast.Constant)
                    and isinstance(const.value, str)):
                continue
            name = const.value
            if "%" in name:                   # e.g. "checkpoint.%ss"
                continue
            checked += 1
            if name.endswith("."):            # dynamic family prefix
                assert name in prefixes or any(
                    c.startswith(name) for c in catalogued), \
                    "uncatalogued instrument family %r" % name
            else:
                assert name in catalogued, \
                    "uncatalogued instrument %r" % name
    assert checked > 60, "AST sweep found too few instruments (%d)" \
        % checked


def test_kind_consistency_between_catalogue_and_doc():
    md = thooks.instrument_index_md()
    for ii in thooks.INSTRUMENTS:
        assert "`%s` | %s" % (ii.name, ii.kind) in md
        assert ii.kind in ("counter", "gauge", "timer", "event")


# ---------------------------------------------------------------------
# wiring: env vars + feature row
# ---------------------------------------------------------------------

def test_obs_env_vars_registered():
    desc = mx.env.describe()
    for var in ("MXNET_TPU_OBS_TRACE", "MXNET_TPU_OBS_BLACKBOX",
                "MXNET_TPU_OBS_BLACKBOX_KB", "MXNET_TPU_OBS_PORT"):
        assert var in desc, var
    assert mx.env.get("MXNET_TPU_OBS_PORT") == 0
    assert mx.env.get("MXNET_TPU_OBS_BLACKBOX_KB") == 256


def test_obs_trace_feature_row():
    assert not mx.runtime.Features().is_enabled("OBS_TRACE")
    obs.enable_tracing()
    assert mx.runtime.Features().is_enabled("OBS_TRACE")
    obs.disable_tracing()
    assert not mx.runtime.Features().is_enabled("OBS_TRACE")
