"""Goodput ledger (ISSUE 14): per-window step-time attribution with a
hard reconciliation contract, rolling MFU, and the regression sentinel
with its env/publish guards.

The acceptance contracts live here: categories sum to window wall
within tolerance on every window; a seeded input stall classifies
input-bound (and the sentinel NAMES input_wait); a seeded slow-dispatch
run under a degraded env gauge classifies degraded-env, NOT regression;
edge windows (zero-step, first-window, publish-spanning) never divide
by zero or flag spuriously.
"""
import json
import os
import time

import numpy as np
import pytest

from mxnet_tpu import telemetry
from mxnet_tpu.obs import goodput
from mxnet_tpu.obs.goodput import CATEGORIES, StepLedger


@pytest.fixture()
def telem():
    """Telemetry armed with a clean slate for the instruments the
    ledger reads/writes; restores the prior enable state."""
    was = telemetry.enabled()
    telemetry.enable()
    for prefix in ("goodput.", "profiling.", "trainer.", "feed.",
                   "data.", "dispatch.", "checkpoint.", "compile.",
                   "env."):
        telemetry.reset(prefix)
    yield telemetry
    for prefix in ("goodput.", "env."):
        telemetry.reset(prefix)
    goodput.reset()
    if not was:
        telemetry.disable()


def _spin(seconds):
    """Sleep-free wall burn (sleep granularity on loaded CI boxes can
    exceed the window walls these tests build)."""
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        pass


def _window(led, per_step, steps=4, pad=0.002):
    """Drive one window: observe per-category seconds per step and burn
    at least as much wall so attribution can never overshoot."""
    out = None
    for _ in range(steps):
        total = 0.0
        for name, v in per_step.items():
            telemetry.timer(name).observe(v)
            total += v
        _spin(total + pad)
        out = led.step() or out
    return out


# -- attribution + reconciliation --------------------------------------

def test_window_attribution_and_reconciliation(telem):
    led = StepLedger(window_steps=4)
    w = _window(led, {"profiling.step_time": 0.008,
                      "feed.consumer_wait": 0.002})
    assert w is not None and w["steps"] == 4
    cats = w["categories"]
    assert cats["device_compute"]["seconds"] == pytest.approx(0.032,
                                                              rel=1e-3)
    assert cats["input_wait"]["seconds"] == pytest.approx(0.008,
                                                          rel=1e-3)
    rec = w["reconciliation"]
    assert rec["ok"] and rec["error"] == 0.0
    # categories + other sum EXACTLY to wall (other is the remainder)
    assert rec["sum_s"] == pytest.approx(rec["wall_s"], abs=1e-5)
    assert set(cats) == set(CATEGORIES)
    shares = sum(c["share"] for c in cats.values())
    assert shares == pytest.approx(1.0, abs=1e-6)


def test_overshoot_fails_reconciliation(telem):
    """Attributed time exceeding wall (double counting) is the ONE way
    the contract can fail -- and it must fail loudly, not clamp."""
    led = StepLedger(window_steps=1, tol=0.25)
    telemetry.timer("profiling.step_time").observe(30.0)  # >> wall
    w = led.step()
    assert not w["reconciliation"]["ok"]
    assert w["reconciliation"]["error"] > 0.25
    assert w["categories"]["other"]["seconds"] == 0.0


def test_trainer_and_profiling_step_time_both_count(telem):
    """Eager loops record trainer.step_time, compiled TrainSteps record
    profiling.step_time; both land in device_compute."""
    led = StepLedger(window_steps=2)
    w = _window(led, {"trainer.step_time": 0.005}, steps=2)
    assert w["categories"]["device_compute"]["seconds"] == \
        pytest.approx(0.01, rel=1e-3)


# -- verdicts ----------------------------------------------------------

def test_input_stall_classified_input_bound(telem):
    """Acceptance: a seeded input stall reads input-bound, with the
    feed-supply percentage in the verdict sentence."""
    led = StepLedger(window_steps=4)
    w = _window(led, {"profiling.step_time": 0.004,
                      "feed.consumer_wait": 0.012})
    assert w["verdict"]["bound"] == "input"
    assert w["verdict"]["detail"].startswith("input-bound: feed supplies")
    assert "25%" in w["verdict"]["detail"]   # 0.004 / 0.016


def test_compute_bound_and_checkpoint_bound_verdicts(telem):
    led = StepLedger(window_steps=2)
    w = _window(led, {"profiling.step_time": 0.02}, steps=2)
    assert w["verdict"]["bound"] == "compute"
    w = _window(led, {"profiling.step_time": 0.004,
                      "checkpoint.save_time": 0.01}, steps=2)
    assert w["verdict"]["bound"] == "checkpoint"


# -- edge windows (satellite) ------------------------------------------

def test_zero_step_window_is_idle_not_crash(telem):
    """Serving-only windows: no steps, no division by zero, no
    sentinel, reconciliation still holds."""
    led = StepLedger(window_steps=4)
    _spin(0.005)
    w = led.flush()
    assert w["steps"] == 0
    assert w["verdict"]["bound"] == "idle"
    assert w["reconciliation"]["ok"]
    assert w["regressions"] == []
    assert w["mfu"] is None
    for c in w["categories"].values():
        assert c["per_step_s"] is None


def test_first_window_has_no_baseline_no_regression(telem):
    """The very first window -- even a pathological one -- cannot flag
    (no baseline yet)."""
    led = StepLedger(window_steps=2)
    w = _window(led, {"feed.consumer_wait": 0.05}, steps=2)
    assert w["regressions"] == []


def test_publish_window_no_spurious_checkpoint_regression(telem):
    """A window spanning a checkpoint publish expects its
    checkpoint_stall spike: guarded, not flagged."""
    led = StepLedger(window_steps=2, min_baseline=2)
    for _ in range(3):                       # healthy baseline windows
        _window(led, {"profiling.step_time": 0.004}, steps=2)
    led.note_publish()
    w = _window(led, {"profiling.step_time": 0.004,
                      "checkpoint.save_time": 0.03}, steps=2)
    assert w["publishes"] == 1
    assert w["regressions"] == []
    # the SAME spike without a publish in the window DOES flag
    w2 = _window(led, {"profiling.step_time": 0.004,
                       "checkpoint.save_time": 0.03}, steps=2)
    assert [r["category"] for r in w2["regressions"]] == \
        ["checkpoint_stall"]


# -- the sentinel ------------------------------------------------------

def test_sentinel_names_the_category_that_moved(telem):
    led = StepLedger(window_steps=4, min_baseline=3)
    for _ in range(4):                       # baseline: healthy feed
        w = _window(led, {"profiling.step_time": 0.005,
                          "feed.consumer_wait": 0.001})
        assert w["regressions"] == []
    w = _window(led, {"profiling.step_time": 0.005,
                      "feed.consumer_wait": 0.02})   # 20x stall
    cats = [r["category"] for r in w["regressions"]]
    assert cats == ["input_wait"], w["regressions"]
    r = w["regressions"][0]
    assert r["per_step_s"] == pytest.approx(0.02, rel=0.05)
    assert r["ratio"] and r["ratio"] > 5
    # published as the named event + counter
    ev = telemetry.event("goodput.regression").recent[-1]
    assert ev["category"] == "input_wait"
    assert telemetry.counter("goodput.regressions").value >= 1


def test_sentinel_ignores_insignificant_jitter(telem):
    """A category that doubles but moves < 5% of the window wall is
    jitter, not a regression."""
    led = StepLedger(window_steps=4, min_baseline=3)
    for _ in range(4):
        _window(led, {"profiling.step_time": 0.01,
                      "feed.consumer_wait": 0.0001})
    w = _window(led, {"profiling.step_time": 0.01,
                      "feed.consumer_wait": 0.0003})
    assert w["regressions"] == []


def test_env_guard_degraded_env_not_regression(telem):
    """Acceptance (the r05 lesson): a slow-dispatch window while the
    env health gauge reads degraded is reported as environment --
    goodput.env_degraded -- and NEVER as a regression; the baseline
    stays clean of the degraded sample."""
    led = StepLedger(window_steps=4, min_baseline=3)
    for _ in range(4):
        _window(led, {"profiling.step_time": 0.004})
    base_before = led.baseline()["device_compute"]["mean"]
    # the bench health probe's gauge says the environment degraded
    telemetry.gauge("env.dispatch_roundtrip_us").set(90000.0)
    w = _window(led, {"profiling.step_time": 0.015})  # ~4x slower
    assert w["env_degraded"] is True
    assert w["regressions"] == []
    assert telemetry.counter("goodput.env_degraded_windows").value == 1
    ev = telemetry.event("goodput.env_degraded").recent[-1]
    assert ev["dispatch_roundtrip_us"] == 90000.0
    assert led.baseline()["device_compute"]["mean"] == \
        pytest.approx(base_before)
    # environment recovers: the same slowdown now IS a regression
    telemetry.gauge("env.dispatch_roundtrip_us").set(2.0)
    w2 = _window(led, {"profiling.step_time": 0.015})
    assert w2["env_degraded"] is False
    assert [r["category"] for r in w2["regressions"]] == \
        ["device_compute"]


def test_env_degraded_threshold_matches_bench_flag():
    """The sentinel's env guard and bench.py's per-line degraded_env
    flag derive from ONE constant, so they cannot disagree."""
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    try:
        import bench
    finally:
        sys.path.pop(0)
    assert bench._DEGRADED_RTT_US == goodput.DEGRADED_RTT_US
    assert goodput.env_degraded(90000.0) is True
    assert goodput.env_degraded(2.0) is False


# -- MFU ---------------------------------------------------------------

def test_no_mfu_without_a_device_peak(telem):
    """A CPU has no published peak and none is assumed: the window
    carries its flops and no MFU."""
    led = StepLedger(window_steps=4, flops_per_step=1e9)
    w = _window(led, {"profiling.step_time": 0.005})
    assert w["flops"] == pytest.approx(4e9)
    assert w["mfu"] is None


def test_mfu_from_flops_per_step(telem, v5e_peaks):
    from mxnet_tpu.profiling import roofline
    led = StepLedger(window_steps=4, flops_per_step=1e12)
    w = _window(led, {"profiling.step_time": 0.005})
    peak, _bw = roofline.device_peaks()
    assert w["flops"] == pytest.approx(4e12)
    assert w["mfu"] == pytest.approx(4e12 / w["wall_s"] / peak, rel=0.01)
    assert telemetry.gauge("goodput.mfu").value == w["mfu"]


def test_mfu_from_profiling_store(telem, v5e_peaks):
    """flops_per_step resolves from the captured TrainStep's CostReport
    (the 'executable's cost report' MFU source the issue names)."""
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, profiling
    from mxnet_tpu.parallel import TrainStep
    was = profiling.enabled()
    profiling.enable()
    try:
        profiling.reset()
        net = gluon.nn.Dense(4)
        net.initialize()
        net.hybridize()
        tr = gluon.Trainer(net.collect_params(), "sgd",
                           {"learning_rate": 0.1}, kvstore=None)
        step = TrainStep(net, gluon.loss.L2Loss(), tr, mesh=None)
        step(mx.nd.array(np.ones((8, 6), np.float32)),
             mx.nd.array(np.ones((8, 4), np.float32)))
        fps = profiling.flops_per_step()        # first train_step kind
        assert fps and fps > 0
        assert profiling.flops_per_step("no-such-label") is None
        led = StepLedger(window_steps=2, flops_per_step=fps)
        w = _window(led, {"profiling.step_time": 0.004}, steps=2)
        assert w["mfu"] is not None and w["flops"] == \
            pytest.approx(2 * fps)
    finally:
        profiling.reset()
        if not was:
            profiling.disable()


def test_mfu_callable_and_failure_tolerated(telem):
    led = StepLedger(window_steps=2)
    led.flops_per_step = lambda: (_ for _ in ()).throw(RuntimeError())
    w = _window(led, {"profiling.step_time": 0.004}, steps=2)
    assert w["mfu"] is None                   # failed callable = no MFU


# -- publication + status ----------------------------------------------

def test_window_publishes_goodput_instruments(telem):
    led = StepLedger(window_steps=2)
    _window(led, {"profiling.step_time": 0.006,
                  "feed.consumer_wait": 0.002}, steps=2)
    assert telemetry.counter("goodput.windows").value == 1
    assert telemetry.counter("goodput.steps").value == 2
    assert telemetry.timer("goodput.device_compute_s").count == 1
    assert telemetry.timer("goodput.device_compute_s").sum == \
        pytest.approx(0.012, rel=1e-3)
    assert telemetry.gauge("goodput.input_wait_share").value > 0
    ev = telemetry.event("goodput.window").recent[-1]
    for key in ("index", "steps", "wall_s", "shares", "verdict",
                "bound", "reconciled", "env_degraded"):
        assert key in ev, key
    assert set(ev["shares"]) == set(CATEGORIES)


def test_line_summary_shape(telem):
    led = StepLedger(window_steps=2)
    w = _window(led, {"profiling.step_time": 0.006}, steps=2)
    line = goodput.line_summary(w)
    assert set(line) == {"steps", "wall_s", "mfu", "shares", "verdict",
                         "bound", "reconciled", "env_degraded"}
    json.dumps(line)                          # JSONL-safe
    assert goodput.line_summary(None) is None


def test_statusz_carries_latest_window(telem):
    from mxnet_tpu.obs import status
    goodput.reset()
    led = goodput.ledger(window_steps=2)
    _window(led, {"profiling.step_time": 0.004}, steps=2)
    st = status.statusz()
    assert st["goodput"] is not None
    assert st["goodput"]["steps"] == 2
    goodput.reset()


def test_windows_ring_bounded(telem):
    led = StepLedger(window_steps=1, history=5)
    for _ in range(8):
        telemetry.timer("profiling.step_time").observe(0.0005)
        led.step()
    wins = led.windows()
    assert len(wins) == 5
    assert wins[-1]["index"] == 7


# -- loop wiring -------------------------------------------------------

def test_continuous_trainer_ticks_process_ledger(telem, tmp_path,
                                                 monkeypatch):
    from mxnet_tpu import obs
    from mxnet_tpu.chaos import scenarios
    from mxnet_tpu.serving.loop import ContinuousTrainer
    goodput.reset()
    monkeypatch.setenv("MXNET_TPU_OBS_GOODPUT_WINDOW", "3")
    obs.enable_goodput()
    try:
        net, trainer, loss_fn, data = scenarios.train_fixtures(seed=0)
        ct = ContinuousTrainer(net, trainer, loss_fn, data,
                               str(tmp_path), publish_every=3)
        ct.run_steps(7)
        ct.close()
    finally:
        obs.disable_goodput()
    wins = goodput.ledger().windows()
    # 2 full windows of 3 + the tail window flushed by close()
    assert len(wins) == 3
    assert [w["steps"] for w in wins] == [3, 3, 1]
    assert wins[-1]["reason"] == "close"
    # the publish guard was marked on the publishing windows
    assert wins[0]["publishes"] == 1 and wins[1]["publishes"] == 1
    for w in wins:
        assert w["reconciliation"]["ok"]
    goodput.reset()


def test_disabled_mode_makes_zero_ledger_calls(tmp_path, monkeypatch):
    """The telemetry zero-overhead contract, applied to the goodput
    hooks: with the flag off, the loop never touches obs.goodput."""
    from mxnet_tpu import obs
    from mxnet_tpu.chaos import scenarios
    from mxnet_tpu.serving.loop import ContinuousTrainer
    assert not obs.goodput_enabled()
    calls = []
    monkeypatch.setattr(goodput, "ledger",
                        lambda **kw: calls.append(kw))
    net, trainer, loss_fn, data = scenarios.train_fixtures(seed=0)
    ct = ContinuousTrainer(net, trainer, loss_fn, data, str(tmp_path),
                           publish_every=2)
    ct.run_steps(4)
    ct.close()
    assert calls == []


def test_host_sync_timer_records_seconds(telem):
    import mxnet_tpu as mx
    telemetry.reset("dispatch.")
    mx.nd.array(np.ones((4,), np.float32)).asnumpy()
    t = telemetry.registry().get("dispatch.host_sync_time")
    assert t is not None and t.count >= 1
    assert telemetry.counter("dispatch.host_sync.asnumpy").value >= 1


# -- summarize CLI -----------------------------------------------------

def _ledger_run_jsonl(path, stall_s, rank=None, step_s=0.004):
    """One rank's JSONL: 2 windows of 4 steps with the given per-step
    input stall (written through the real sink + ledger)."""
    from mxnet_tpu.telemetry import JsonlSink
    for prefix in ("goodput.", "trainer.", "feed.", "profiling."):
        telemetry.reset(prefix)
    sink = telemetry.registry().attach(JsonlSink(str(path), rank=rank))
    try:
        led = StepLedger(window_steps=4)
        for _ in range(2):
            _window(led, {"trainer.step_time": step_s,
                          "feed.consumer_wait": stall_s})
        led.flush()           # zero-step tail (the trainer-close shape)
        telemetry.flush()
    finally:
        telemetry.registry().detach(sink)
        sink.close()


def test_summarize_goodput_section_and_verdict_line(telem, tmp_path):
    from mxnet_tpu.telemetry import cli as tcli
    path = tmp_path / "run.jsonl"
    _ledger_run_jsonl(path, stall_s=0.012)
    agg = tcli.summarize_file(str(path))
    gp = agg["goodput"]
    assert gp["windows"] == 3 and gp["steps"] == 8
    # the verdict comes from the last ACTIVE window -- the zero-step
    # tail flush must not mask it with "idle"
    assert gp["bound"] == "input"
    assert gp["verdict"].startswith("input-bound: feed supplies")
    assert gp["categories"]["input_wait"]["total_s"] == \
        pytest.approx(0.096, rel=0.01)
    assert gp["categories"]["input_wait"]["share"] > \
        gp["categories"]["device_compute"]["share"]
    text = tcli._render_human(agg)
    assert "bottleneck: input-bound: feed supplies" in text
    assert "goodput: 3 windows / 8 steps" in text


def test_per_rank_skew_names_the_category(telem, tmp_path):
    """ISSUE 14 satellite: the multi-file skew verdict names WHICH
    category differs on the slow rank (rank 1 input_wait ~Nx median),
    not just that it is slow."""
    from mxnet_tpu.telemetry import cli as tcli
    r0, r1 = tmp_path / "r0.jsonl", tmp_path / "r1.jsonl"
    _ledger_run_jsonl(r0, stall_s=0.001, rank=0)     # healthy rank
    # rank 1 is slow (2x step wall trips the skew flag) but the CAUSE
    # is the 20x input stall -- the attribution must name input_wait,
    # not just repeat "slow"
    _ledger_run_jsonl(r1, stall_s=0.02, rank=1, step_s=0.008)
    agg = tcli.summarize_files([str(r0), str(r1)], skew_threshold=1.25)
    sk = agg["skew"]
    assert sk["straggler"] and sk["straggler_ranks"] == [1]
    attr = sk["category_attribution"]
    assert len(attr) == 1
    assert attr[0]["rank"] == 1
    assert attr[0]["category"] == "input_wait"
    assert attr[0]["ratio"] > 3
    text = tcli._render_ranks(agg)
    assert "rank 1 slow: input_wait" in text


def test_balanced_ranks_no_attribution(telem, tmp_path):
    from mxnet_tpu.telemetry import cli as tcli
    r0, r1 = tmp_path / "r0.jsonl", tmp_path / "r1.jsonl"
    _ledger_run_jsonl(r0, stall_s=0.004, rank=0)
    _ledger_run_jsonl(r1, stall_s=0.004, rank=1)
    agg = tcli.summarize_files([str(r0), str(r1)])
    assert not agg["skew"]["straggler"]
    assert agg["skew"]["category_attribution"] == []


# -- registration ------------------------------------------------------

def test_env_vars_registered():
    from mxnet_tpu import env as _env
    for name in ("MXNET_TPU_OBS_GOODPUT", "MXNET_TPU_OBS_GOODPUT_WINDOW",
                 "MXNET_TPU_OBS_GOODPUT_TOL",
                 "MXNET_TPU_OBS_GOODPUT_MAD_K"):
        assert name in _env.REGISTRY, name
    assert _env.get("MXNET_TPU_OBS_GOODPUT_WINDOW") == 20


def test_features_row():
    import mxnet_tpu as mx
    from mxnet_tpu import obs
    assert mx.runtime.Features().is_enabled("OBS_GOODPUT") \
        == obs.goodput_enabled()
