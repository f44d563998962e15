"""The load generators against a stand-in for the served interface: a
closed loop never has more requests in flight than clients, an open loop
sends on its schedule and records how late it ran, and the window's
numbers come from the client-side records."""
import contextlib
import json
import os
import threading
import time
import types

import pytest

from perfbench.harness import serve_driver
from perfbench.harness.spec import SpecError, sized
from perfbench.harness.traffic import ServeTraffic

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _mix(name):
    with open(os.path.join(REPO, "perfbench", "traffic", name + ".json")) as f:
        return sized(json.load(f), rehearse=True)


class FakeStream:
    """``fault`` after ``fault_after`` tokens: "error" raises, "short" ends
    the stream early, "stall" sends nothing more until cancelled."""

    def __init__(self, n, gap, on_end, fault=None, fault_after=30):
        self._n, self._gap, self._on_end = n, gap, on_end
        self._fault, self._fault_after = fault, fault_after
        self.cancelled = False
        self._sent = 0

    def __iter__(self):
        return self

    def __next__(self):
        faulty = self._fault and self._sent >= self._fault_after
        if faulty and self._fault == "error":
            self._on_end()
            raise RuntimeError("engine failed")
        while faulty and self._fault == "stall" and not self.cancelled:
            time.sleep(0.002)
        if self.cancelled or self._sent >= self._n \
                or (faulty and self._fault == "short"):
            self._on_end()
            raise StopIteration
        time.sleep(self._gap)
        self._sent += 1
        return 7

    def cancel(self):
        self.cancelled = True


class FakeRegistry:
    """Counts the streams in flight; each token takes ``gap`` seconds."""

    def __init__(self, gap=0.002, shed_every=None, fault=None):
        self.gap, self.shed_every, self.fault = gap, shed_every, fault
        self.in_flight = self.max_in_flight = self.calls = 0
        self._lock = threading.Lock()

    def _end(self):
        with self._lock:
            self.in_flight -= 1

    def generate(self, name, prompt, max_new):
        from mxnet_tpu.serving.batcher import ServingQueueFull
        with self._lock:
            self.calls += 1
            if self.shed_every and self.calls % self.shed_every == 0:
                raise ServingQueueFull("full")
            self.in_flight += 1
            self.max_in_flight = max(self.max_in_flight, self.in_flight)
        # the first stream is the faulty one
        fault = self.fault if self.calls == 1 else None
        return FakeStream(max_new, self.gap, self._end, fault)


def _span(_name):
    return contextlib.nullcontext()


def test_closed_loop_never_exceeds_its_clients():
    reg = FakeRegistry()
    load = serve_driver.Load(reg, ServeTraffic(_mix("closed_loop"), 50, 1),
                             _span)
    load.start_closed(3)
    time.sleep(0.5)
    left = load.finish(time.perf_counter())
    assert left == []
    assert reg.max_in_flight == 3 and reg.in_flight == 0
    done = [r for r in load.records if r.done.is_set() and not r.failed]
    assert len(done) > 6
    assert all(len(r.tokens) == r.max_new for r in done
               if not r.stream.cancelled)


def test_open_loop_keeps_its_schedule_and_records_lateness():
    mix = _mix("open_loop")
    traffic = ServeTraffic(mix, 50, 1)
    reg = FakeRegistry()
    load = serve_driver.Load(reg, traffic, _span)
    t_begin = time.perf_counter()
    load.start_open(t_begin)
    time.sleep(0.6)
    t_end = time.perf_counter()
    assert load.finish(t_end) == []
    recs = sorted(load.records, key=lambda r: r.t_due)
    # due times are the traffic's own offsets, whatever the system did
    want = traffic.arrival_offsets()
    for r in recs:
        assert r.t_due - t_begin == pytest.approx(next(want), abs=1e-9)
    late = [r.t_submit - r.t_due for r in recs]
    assert all(x >= 0 for x in late) and max(late) < 0.25
    rate = mix["rate_per_s"]
    assert len(recs) == pytest.approx(0.6 * rate, rel=0.5)


def test_a_shed_request_counts_as_failed_and_has_no_latency():
    reg = FakeRegistry(shed_every=2)
    load = serve_driver.Load(reg, ServeTraffic(_mix("closed_loop"), 50, 1),
                             _span)
    load.start_closed(2)
    time.sleep(0.3)
    t1 = time.perf_counter()
    load.finish(t1)
    shed = [r for r in load.records if r.failed == "shed"]
    assert shed and all(not r.token_times for r in shed)
    lines, run = [], _fake_run()
    run.log.measurement = lambda event, **kw: lines.append(kw)
    t0 = t1 - 0.25
    serve_driver._window_metrics(run, load.records, t0, t1)
    due = [r for r in load.records if t0 <= r.t_due < t1]
    # in flight at some moment of the window, whenever due
    live = [r for r in load.records if r.t_due < t1 and r.t_end >= t0]
    assert run.attempted == len(live) >= len(due)
    assert run.failed == len([r for r in live if r.failed]) > 0
    assert run.why_incorrect == []      # shedding is the system's right
    assert lines[0]["ttft_samples"] == len([r for r in due if r.token_times])
    tokens = sum(1 for r in load.records for t in r.token_times
                 if t0 <= t < t1)
    # the rate runs from the first token at or after t0 to the first at or
    # after t1
    times = sorted(t for r in load.records for t in r.token_times if t >= t0)
    first, last = times[0], next((t for t in times if t >= t1), t1)
    assert tokens - 2 <= sum(1 for t in times if first <= t < last) <= tokens
    assert run.end_to_end["serve_tokens_per_s"] == pytest.approx(
        sum(1 for t in times if first <= t < last) / (last - first))
    assert run.end_to_end["itl_p95_ms"] >= 1e3 * reg.gap * 0.9


def _fake_run(mix=None):
    run = types.SimpleNamespace(
        mix=mix or {}, end_to_end={}, counters={}, attempted=0, failed=0,
        window_s=None, setup_seconds=lambda now: 1.0, why_incorrect=[],
        printed={})
    run.log = types.SimpleNamespace(
        measurement=lambda event, **kw: run.printed.update(kw))
    run.incorrect = run.why_incorrect.append
    return run


@pytest.mark.parametrize("fault", ["error", "short", "stall"])
def test_a_stream_from_before_the_window_that_fails_in_it_is_counted(
        fault, monkeypatch):
    """Every token of a window may come from streams submitted before it
    (a request can outlast the window): such a stream counts as attempted,
    and one that errors, ends short or stops getting tokens counts as
    failed and makes the run incorrect."""
    monkeypatch.setattr(serve_driver, "DRAIN_TIMEOUT_S", 0.3)
    mix = dict(_mix("closed_loop"),
               output_len={"median": 500, "sigma": 0.1, "min": 400,
                           "max": 600})
    reg = FakeRegistry(gap=0.01, fault=fault)
    load = serve_driver.Load(reg, ServeTraffic(mix, 50, 1), _span)
    load.start_closed(2)
    time.sleep(0.2)
    t0 = time.perf_counter()
    time.sleep(0.25)            # the first stream breaks after 30 tokens
    t1 = time.perf_counter()
    assert load.finish(t1) == []
    first = min(load.records, key=lambda r: r.t_submit)
    assert first.t_due < t0 < first.token_times[-1] < t1
    assert first.failed.startswith({"stall": "stalled"}.get(fault, fault))
    run = _fake_run()
    serve_driver._window_metrics(run, load.records, t0, t1)
    assert run.attempted >= 2 and run.failed == 1
    assert len(run.why_incorrect) == 1 and first.failed in \
        run.why_incorrect[0]


def test_window_counts_the_context_every_decode_step_read():
    rec = serve_driver.StreamRecord(
        types.SimpleNamespace(index=0, prompt=[1] * 10, max_new=4), 0.0)
    rec.t_submit = 0.0
    rec.token_times = [0.1, 0.2, 0.3, 0.4]
    rec.done.set()
    run = _fake_run()
    serve_driver._window_metrics(run, [rec], 0.0, 1.0)
    # token 0 is the prefill's; tokens 1..3 read contexts of 11, 12, 13
    assert run.counters["decode_context_tokens"] == 11 + 12 + 13
    assert run.counters["tokens_in_window"] == 4
    # four tokens from 0.1 to (no token after t1, so) t1 = 1.0
    assert run.end_to_end["serve_tokens_per_s"] == pytest.approx(4 / 0.9)
    assert run.counters["in_flight_intervals"] == [(0.0, 0.4)]
    assert run.end_to_end["ttft_p95_ms"] == pytest.approx(100.0)
    assert run.end_to_end["itl_p95_ms"] == pytest.approx(100.0)
    # no stream got a token from 0.4 s to the close: the window's hole
    assert run.printed["longest_silence_ms"] == pytest.approx(600.0)
    assert run.printed["longest_silence_at_s"] == pytest.approx(0.4)
    assert run.printed["itl_max_ms"] == pytest.approx(100.0)


def test_the_share_within_the_mixs_limit_counts_every_arrival_of_the_window():
    """``ttft_ok_share``: of the requests due in the window, those whose
    first token came within the mix's ``ttft_limit_ms`` of when they were
    due.  A shed request and one still waiting miss; a mix without the
    limit has no such metric."""
    def rec(i, t_due, first, failed=None):
        r = serve_driver.StreamRecord(
            types.SimpleNamespace(index=i, prompt=[1] * 4, max_new=2), t_due)
        r.t_submit = t_due
        r.token_times = [] if first is None else [first, first + 0.01]
        r.failed = failed
        r.t_end = t_due + 1.0
        r.done.set()
        return r
    records = [rec(0, 0.10, 0.15), rec(1, 0.20, 0.2999), rec(2, 0.30, 0.45),
               rec(3, 0.40, None, "shed"), rec(4, 0.50, None),
               rec(5, 1.50, 1.51)]           # due after the window
    run = _fake_run({"ttft_limit_ms": 100})
    serve_driver._window_metrics(run, records, 0.0, 1.0)
    assert run.end_to_end["ttft_ok_share"] == pytest.approx(100.0 * 2 / 5)
    run = _fake_run()
    serve_driver._window_metrics(run, records[:3], 0.0, 1.0)
    assert "ttft_ok_share" not in run.end_to_end


@pytest.mark.parametrize("shift", [0.0, 0.3, 0.5, 0.77])
def test_the_rate_does_not_depend_on_where_the_window_cuts_a_step(shift):
    """16 streams that each get a token every 0.78 s, all at once: the
    rate is 16 / 0.78 wherever a 30 s window happens to start."""
    step, records = 0.78, []
    for i in range(16):
        rec = serve_driver.StreamRecord(
            types.SimpleNamespace(index=i, prompt=[1] * 10, max_new=999),
            0.0)
        rec.t_submit = 0.0
        rec.token_times = [k * step + i * 1e-5 for k in range(1, 60)]
        records.append(rec)
    run = _fake_run()
    serve_driver._window_metrics(run, records, 5.0 + shift, 35.0 + shift)
    assert run.end_to_end["serve_tokens_per_s"] \
        == pytest.approx(16 / step, rel=1e-4)


def test_a_checked_sequence_longer_than_the_checks_width_is_a_spec_error():
    """Cut to ``check.width`` a longer sequence was judged on empty slices
    and passed; now the run fails before a stream is sent, naming both."""
    reg = FakeRegistry()
    traffic = ServeTraffic(_mix("open_loop"), 50, 1)
    longest = max(n for n, _out in traffic.sizes[:8])
    cfg = {"check": {"streams": 8, "max_new": 6, "width": longest + 5,
                     "chunk": 2, "references": []}}
    run = types.SimpleNamespace(family=None, cfg=cfg)
    load = serve_driver.Load(reg, traffic, _span)
    with pytest.raises(SpecError) as err:
        serve_driver.check_streams(run, None, None, load, traffic)
    assert "%d tokens long" % (longest + 6) in str(err.value)
    assert "check.width is %d" % (longest + 5) in str(err.value)
    assert reg.calls == 0
