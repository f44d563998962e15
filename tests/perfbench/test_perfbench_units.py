"""The benchmark's own arithmetic: percentiles, traffic, shape functions,
the trace reduction, the last line.  No chip, no process of its own."""
import json
import math
import os
import re
import threading
import types

import numpy as np
import pytest

from perfbench.harness import report, stats, xplane
from perfbench.harness.peaks import DEVICE_PEAKS, NoChip, device_peaks
from perfbench.harness.spec import Cell, SpecError, sized
from perfbench.harness.traffic import (ServeTraffic, gap_population,
                                       length_population)
from perfbench.harness.xplane import Event

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = json.load(open(os.path.join(REPO, "BENCHMARK.json")))


def _config(name):
    return json.load(open(os.path.join(REPO, "perfbench", "configs",
                                       name + ".json")))


def _mix(name):
    return json.load(open(os.path.join(REPO, "perfbench", "traffic",
                                       name + ".json")))


# -- percentiles -----------------------------------------------------------

@pytest.mark.parametrize("q", [0, 25, 50, 90, 95, 99, 100])
def test_percentile_is_numpys(q):
    xs = list(np.random.RandomState(q).rand(137))
    assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def test_percentile_of_nothing_is_none():
    assert stats.percentile([], 95) is None and stats.median([]) is None


@pytest.mark.parametrize("n,want", [
    (9, None), (19, None), (20, 50), (99, 50), (100, 90), (199, 90),
    (200, 95), (999, 95), (1000, 99), (10000, 99.9)])
def test_highest_percentile_with_ten_samples_beyond(n, want):
    assert stats.highest_supported(n) == want


def test_samples_beyond():
    assert stats.samples_beyond(200, 95) == 10
    assert stats.samples_beyond(199, 95) == 9


# -- traffic ---------------------------------------------------------------

@pytest.mark.parametrize("mix", ["closed_loop", "open_loop"])
def test_same_seed_same_requests_other_seed_same_sizes(mix):
    m = sized(_mix(mix), rehearse=False)
    big = 3000000001                      # more than 32 signed bits hold
    a, b, c = (ServeTraffic(m, 50257, s) for s in (big, big, 5))
    ra = [a.next_request() for _ in range(40)]
    rb = [b.next_request() for _ in range(40)]
    assert [(r.prompt, r.max_new) for r in ra] \
        == [(r.prompt, r.max_new) for r in rb]
    assert a.sizes != c.sizes and sorted(a.sizes) == sorted(c.sizes)
    assert a.gaps == b.gaps
    if a.gaps is not None:
        assert a.gaps != c.gaps and sorted(a.gaps) == sorted(c.gaps)


def test_lengths_keep_to_the_stated_distribution_and_limits():
    m = _mix("closed_loop")
    p = length_population(m["prompt_len"])
    o = length_population(m["output_len"])
    assert len(p) == len(o) == 96
    assert min(p) >= 16 and max(p) <= 768 and min(o) >= 16 and max(o) <= 256
    assert np.median(p) == pytest.approx(192, rel=0.03)
    assert np.median(o) == pytest.approx(96, rel=0.03)
    # every request fits the model's context
    assert max(p) + max(o) <= _config("gpt2-medium")["n_positions"]
    assert max(p) <= max(_config("gpt2-medium")["deployment"]
                         ["prefill_buckets"])


def test_arrival_gaps_are_poisson_at_the_stated_rate():
    gaps = gap_population(8.0, 400)
    assert sum(gaps) / len(gaps) == pytest.approx(1 / 8.0)
    # exponential gaps: the standard deviation equals the mean
    assert np.std(gaps) / np.mean(gaps) == pytest.approx(1.0, rel=0.25)
    assert gaps == gap_population(8.0, 400)


def test_arrival_offsets_cycle_without_end():
    t = ServeTraffic(sized(_mix("open_loop"), False), 100, 1)
    it = t.arrival_offsets()
    offs = [next(it) for _ in range(3 * len(t.gaps))]
    assert all(b > a for a, b in zip(offs, offs[1:]))
    assert offs[len(t.gaps) - 1] == pytest.approx(sum(t.gaps))


def test_a_mix_with_a_cycle_is_one_sequence_that_the_seed_only_starts():
    """Gaps and sizes stay paired in the file's order; two seeds differ by
    where they start; any window of ``cycle / rate_per_s`` seconds holds
    each arrival of the sequence exactly once."""
    mix = dict(sized(_mix("open_loop"), rehearse=False),
               rate_per_s=8.0, cycle=240)
    a, b = (ServeTraffic(mix, 50257, s) for s in (3000000001, 7))
    seq_a, seq_b = list(zip(a.sizes, a.gaps)), list(zip(b.sizes, b.gaps))
    assert len(seq_a) == 240 and seq_a != seq_b
    start = seq_b.index(seq_a[0])
    assert seq_b[start:] + seq_b[:start] == seq_a
    assert sum(a.gaps) == pytest.approx(30.0)
    offsets = a.arrival_offsets()
    due = [next(offsets) for _ in range(3 * 240)]
    for t0 in (0.001, 3.7, 11.1, 29.99):
        inside = [i % 240 for i, t in enumerate(due) if t0 <= t < t0 + 30.0]
        assert sorted(inside) == list(range(240))
    # the lengths are the stated distributions' own quantiles
    assert sorted(p for p, _o in a.sizes) \
        == sorted(length_population(mix["prompt_len"], 240))


def test_requests_are_handed_out_once_across_threads():
    t = ServeTraffic(sized(_mix("closed_loop"), True), 100, 1)
    got = []

    def take():
        for _ in range(50):
            got.append(t.next_request().index)
    threads = [threading.Thread(target=take) for _ in range(8)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=30)
    assert sorted(got) == list(range(400))


def test_closed_clients_equal_the_engines_slots():
    dep = _config("gpt2-medium")["deployment"]
    assert _mix("closed_loop")["clients"] == dep["slots"] \
        == max(dep["decode_buckets"])
    assert dep["num_blocks"] == dep["slots"] * (1024 // dep["block_size"]) + 1


# -- shape functions ---------------------------------------------------------

def test_bert_forward_is_237_mflop_a_token_at_seq_512():
    from perfbench.families import bert_mlm
    cfg = _config("bert-base-mlm-s512")
    per_layer = (2 * 768 * 2304 + 2 * 768 * 768 + 2 * 2 * 768 * 3072
                 + 4 * 512 * 768)
    head = 2 * 768 * 768 + 2 * 768 * 30522
    assert bert_mlm.forward_flops_per_token(cfg) == 12 * per_layer + head
    assert bert_mlm.forward_flops_per_token(cfg) / 1e6 \
        == pytest.approx(236.8, abs=0.1)
    assert 2 * 768 * 30522 / 1e6 == pytest.approx(46.9, abs=0.05)
    assert bert_mlm.train_flops_per_token(cfg) \
        == 3 * bert_mlm.forward_flops_per_token(cfg)


def test_flash_attention_cost_by_hand():
    from perfbench.families import bert_mlm
    cfg = _config("bert-base-mlm-s512")
    flops, nbytes = bert_mlm.flash_attention_step_cost(cfg, 32)
    heads = 12 * 32 * 12                  # layers x sequences x heads
    assert flops == heads * 14 * 512 * 512 * 64
    assert nbytes == heads * 12 * 512 * 64 * 2


def test_gpt2_medium_token_holds_196608_bytes_of_kv():
    from perfbench.families import gpt2
    cfg = _config("gpt2-medium")
    assert gpt2.kv_bytes_per_token(cfg) == 196608 == 2 * 24 * 1024 * 4
    flops, nbytes = gpt2.paged_attention_cost(cfg, 1000)
    assert nbytes == 196608000 and flops == 4 * 24 * 1024 * 1000


def test_gpt2_medium_served_flops_by_hand():
    """One decode token over a context of 300 and one prompt of 100."""
    from perfbench.families import gpt2
    cfg = _config("gpt2-medium")
    layer = 24 * 1024 * 1024            # 12 x n_embd^2 multiply-adds
    head = 2 * 1024 * 50257
    assert gpt2.served_flops(cfg, 1, 300, []) \
        == 24 * layer + head + 4 * 24 * 1024 * 300
    assert gpt2.served_flops(cfg, 0, 0, [100]) \
        == 100 * 24 * layer + head + 4 * 24 * 1024 * (100 * 101 // 2)
    assert gpt2.served_flops(cfg, 0, 0, []) == 0


def test_a_compared_number_is_kept_beside_its_limit():
    from perfbench.harness.runctx import Run
    lines = []
    run = Run.__new__(Run)
    run.correct, run.compared = True, {}
    run.log = types.SimpleNamespace(line=lambda **kw: lines.append(kw))
    run.compare("logit_gap.bfloat16", 0.0, 0.3, "logits")
    assert run.correct and not lines
    run.compare("token_margin.bfloat16", 0.7, 0.6, "a token's margin")
    run.compare("nan_is_over", float("nan"), 1.0, "not a number")
    assert not run.correct and len(lines) == 2
    assert "a token's margin" in lines[0]["why"] and "0.6" in lines[0]["why"]
    assert run.compared["token_margin.bfloat16"] == (0.7, 0.6)
    assert list(run.compared) == ["logit_gap.bfloat16",
                                  "token_margin.bfloat16", "nan_is_over"]


def test_peaks_are_the_programs_and_unknown_kinds_are_errors():
    from mxnet_tpu.profiling import roofline
    for kind, row in DEVICE_PEAKS.items():
        assert roofline.DEVICE_PEAKS[kind] == row
    assert device_peaks("TPU v5 lite") == (197e12, 819e9)
    with pytest.raises(NoChip):
        device_peaks("TPU v9 imaginary")
    with pytest.raises(NoChip):
        device_peaks("cpu")


# -- the trace reduction -------------------------------------------------------

D0, D1 = "/device:TPU:0", "/device:TPU:1"


def _ev(plane, line, name, start, dur, detail=""):
    return Event(plane, line, name, float(start), float(dur), detail)


TRACE = [
    _ev(D0, "XLA Modules", "jit_step_fn(123)", 100, 400),
    _ev(D0, "XLA Modules", "jit_step_fn(123)", 600, 300),
    _ev(D0, "XLA Modules", "jit_other(9)", 950, 10),
    _ev(D0, "XLA Ops", "fusion.1", 100, 100),
    _ev(D0, "XLA Ops", "while.2", 200, 250),            # parent ...
    _ev(D0, "XLA Ops", "flash_fwd.3", 220, 80, "(bf16[8,8]) custom-call("),
    _ev(D0, "XLA Ops", "all-reduce.4", 400, 50),          # ... and children
    _ev(D0, "XLA Ops", "fusion.1", 600, 120, "f32[4] fusion(%flash_fwd.3)"),
    _ev(D0, "XLA Ops", "flash_fwd.3", 720, 60, "(bf16[8,8]) custom-call("),
    _ev(D0, "XLA Ops", "all-reduce.4", 800, 100),
    _ev(D1, "XLA Ops", "fusion.1", 100, 50),
    _ev(xplane.HOST_PLANE, "main", "perfbench.window", 0, 1000),
    _ev(xplane.HOST_PLANE, "main", "perfbench.step_dispatch", 0, 100),
    _ev(xplane.HOST_PLANE, "main", "perfbench.loss_fetch", 450, 450),
    _ev(xplane.HOST_PLANE, "main", "perfbench.feed_next", 460, 20),
]


def test_busy_is_the_union_of_op_intervals():
    ops = xplane.on_device(TRACE, 0, xplane.OPS_LINE)
    # [100,200) + [200,450) + [600,720) + [720,780) + [800,900)
    assert xplane.union_intervals(ops) == [(100, 450), (600, 780),
                                           (800, 900)]
    assert xplane.busy_ns(ops) == 350 + 180 + 100
    assert xplane.device_ids(TRACE) == [0, 1]
    assert xplane.busy_ns(xplane.on_device(TRACE, 1, xplane.OPS_LINE)) == 50


def test_clipping_to_the_window():
    ops = xplane.on_device(TRACE, 0, xplane.OPS_LINE)
    assert xplane.busy_ns(xplane.clip(ops, (150, 650))) == 300 + 50
    assert xplane.window_of(TRACE, "perfbench.window") == (0, 1000)
    assert xplane.window_of(TRACE, "perfbench.nothing") is None


def test_time_per_name_and_top_ops():
    ops = xplane.on_device(TRACE, 0, xplane.OPS_LINE)
    by = xplane.time_by_name(ops)
    assert by["fusion.1"] == 220 and by["flash_fwd.3"] == 140
    assert by["all-reduce.4"] == 150 and by["while.2"] == 250
    top = xplane.top_ops(ops, 2)
    assert top == [["while.2", 250 / 1e9],
                   ["fusion.1 = f32[4] fusion(%flash_fwd.3)", 220 / 1e9]]


def test_step_module_durations_and_per_step_kernel_time():
    runs = xplane.module_runs(TRACE, 0, "^jit_step_fn")
    assert [r.dur_ns for r in runs] == [400, 300]
    assert stats.median([r.dur_ns for r in runs]) == 350
    ops = xplane.on_device(TRACE, 0, xplane.OPS_LINE)
    # by the instruction's own name, not by an operand that mentions it
    flash = xplane.matching(ops, "flash")
    assert [e.name for e in flash] == ["flash_fwd.3"] * 2
    assert xplane.per_run_ns(flash, runs) == [80, 60]
    coll = xplane.matching(ops, "^all-reduce|^all-gather")
    assert xplane.per_run_ns(coll, runs) == [50, 100]


def test_idle_gaps_go_to_what_the_host_was_doing():
    ops = xplane.on_device(TRACE, 0, xplane.OPS_LINE)
    spans = [e for e in xplane.host_spans(TRACE, "perfbench.")
             if e.name != "perfbench.window"]
    gaps = dict(xplane.idle_gaps(ops, (0, 1000), spans, "nobody"))
    # idle: [0,100) dispatch; [450,600): 20 of it feed_next (innermost),
    # 130 loss_fetch; [780,800) loss_fetch; [900,1000) nobody
    assert gaps == {"perfbench.step_dispatch": 100 / 1e9,
                    "perfbench.feed_next": 20 / 1e9,
                    "perfbench.loss_fetch": 150 / 1e9,
                    "nobody": 100 / 1e9}
    total = sum(gaps.values())
    assert total == pytest.approx((1000 - xplane.busy_ns(ops)) / 1e9)


XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 400000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 2 offset_ps: 0 duration_ps: 100000 }
    events { metadata_id: 3 offset_ps: 50000 duration_ps: 100000 } }
  event_metadata { key: 1 value { id: 1 name: "jit_step_fn(7)" } }
  event_metadata { key: 2 value { id: 2
    name: "%flash_fwd.1 = (bf16[384,512,64]{2,1,0}) custom-call(bf16[8] %p)" } }
  event_metadata { key: 3 value { id: 3
    name: "%fusion.2 = f32[8]{0} fusion(bf16[8] %flash_fwd.1), kind=kLoop" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "main" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 500000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 500000 } }
  event_metadata { key: 1 value { id: 1 name: "perfbench.window" } }
  event_metadata { key: 2 value { id: 2 name: "$some python frame" } }
}
"""


def test_reader_on_a_hand_built_xspace():
    from jax.profiler import ProfileData
    events = xplane.read_events(ProfileData.from_text_proto(XSPACE))
    names = sorted(e.name for e in events)
    assert names == ["flash_fwd.1", "fusion.2", "jit_step_fn(7)",
                     "perfbench.window"]      # foreign host events dropped
    ops = xplane.on_device(events, 0, xplane.OPS_LINE)
    assert xplane.busy_ns(ops) == pytest.approx(150)       # union, in ns
    assert [e.name for e in xplane.matching(ops, "flash")] == ["flash_fwd.1"]
    assert ops[0].detail.startswith("(bf16[384,512,64]{2,1,0}) custom-call(")
    runs = xplane.module_runs(events, 0, "^jit_step_fn")
    assert [r.dur_ns for r in runs] == [pytest.approx(400)]
    w0, w1 = xplane.window_of(events, "perfbench.window")
    assert w1 - w0 == pytest.approx(500)
    assert ops[0].start_ns == pytest.approx(w0)    # one clock for both


# -- finding things by name, and the last line -----------------------------------

@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_finds_its_files(workload):
    cell = Cell(REPO, workload)
    assert cell.family().KIND in ("train", "serve")
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2 and cell.per_layer
    for m in cell.per_layer:
        assert callable(cell.layer_reader(m["name"]))
        assert m["moves"] in e2e        # the cell reports what it moves
    kind = cell.family().KIND
    assert cell.traffic["kind"] in {
        "train": ("train_stream",),
        "serve": ("closed_loop", "open_loop")}[kind]


def test_unknown_names_are_spec_errors():
    with pytest.raises(SpecError):
        Cell(REPO, "no_such_cell")
    with pytest.raises(SpecError):
        Cell(REPO, "bert_train_1chip").layer_reader("no_such_metric")


def test_rehearsal_sizes_lie_over_the_real_ones():
    cfg = _config("gpt2-medium")
    assert sized(cfg, False)["n_embd"] == 1024
    assert sized(cfg, True)["n_embd"] == 32
    assert "rehearse" not in sized(cfg, True)


def _fake_run(trace=None):
    cell = Cell(REPO, "bert_train_1chip")
    run = types.SimpleNamespace(
        cell=cell, stamp={"platform": "tpu", "kind": "TPU v5 lite",
                          "count": 1},
        correct=True, attempted=50, failed=0, trace=trace,
        compared={"loss_gap_mean": (0.001, 0.02)},
        memory_peak_bytes=9000000000,
        end_to_end={"train_tokens_per_s": 91234.5678, "setup_s": 41.25,
                    "something_else": 1.0})
    return run


def test_last_line_has_the_contracts_keys():
    run = _fake_run()
    out = report.result(run, report.end_to_end_metrics(run))
    assert list(out) == ["correct", "attempted", "failed", "metrics",
                         "device", "compared"]     # ``compared`` comes last
    assert out["compared"] == {"loss_gap_mean": {"value": 0.001,
                                                 "limit": 0.02}}
    assert out["metrics"] == {
        "train_tokens_per_s": {"value": 91234.5678, "unit": "tokens/s"},
        "setup_s": {"value": 41.25, "unit": "s"}}
    assert out["device"] == {"platform": "tpu", "kind": "TPU v5 lite",
                             "count": 1, "memory_peak_bytes": 9000000000}
    json.loads(json.dumps(out))
    del run.end_to_end["setup_s"]
    with pytest.raises(report.MissingMetric):
        report.end_to_end_metrics(run)


def test_traced_last_line_carries_busy_and_window():
    from perfbench.harness.runctx import TraceView
    run = _fake_run(TraceView(TRACE, chips=1))
    out = report.result(run, {}, {"device_ops": [], "idle_gaps": []})
    assert out["device"]["busy_s"] == pytest.approx(630 / 1e9)
    assert out["device"]["window_s"] == pytest.approx(1000 / 1e9)
    assert sorted(out["breakdown"]) == ["device_ops", "idle_gaps"]


def test_a_trace_without_the_window_span_is_refused():
    """No second way to place the window: the extent of the device's own
    activity would leave the idle time at either end out of the share."""
    from perfbench.harness.runctx import TraceView
    with pytest.raises(RuntimeError, match="perfbench.window"):
        TraceView([e for e in TRACE if e.name != "perfbench.window"],
                  chips=1)


def test_a_declared_metric_the_run_did_not_produce_is_named():
    cell = Cell(REPO, "gpt2m_serve_closed16")
    lines = []
    run = types.SimpleNamespace(
        cell=cell, trace=None, memory_peak_bytes=None, counters={},
        samples={"decode.step_time": [0.5, 0.7, 0.9],
                 # the window admitted nothing, the pre-roll did
                 "decode.prefill_time": [],
                 "decode.prefill_time@load": [0.04, 0.05, 0.06]},
        log=types.SimpleNamespace(line=lambda **kw: lines.append(kw)))
    out = report.per_layer_metrics(run)
    assert out["decode_step_ms.serve"]["value"] == pytest.approx(700.0)
    assert out["prefill_ms.serve"]["value"] == pytest.approx(50.0)
    assert lines[-1]["event"] == "per_layer"
    assert lines[-1]["produced"] == sorted(out)
    declared = {m["name"] for m in cell.per_layer}
    assert set(lines[-1]["missing"]) == declared - set(out)
    assert "device_idle_share.serve" in lines[-1]["missing"]


# -- BENCHMARK.json against the contract's letter ----------------------------------

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _line(s):
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


KINDS = ("configs", "workloads", "end_to_end", "per_layer")


def contract(bench):
    """The contract's rules on the parsed file alone, with no file read:
    a copy of ``BENCHMARK.json`` with cells appended is held to them too
    (``test_perfbench_entries.py``)."""
    keys_and_sizes(bench)
    for kind in KINDS:
        names_and_units(bench, kind)
    hang_together(bench)


def keys_and_sizes(bench):
    assert sorted(bench) == ["command", "configs", "end_to_end", "paths",
                             "per_layer", "run_seconds", "workloads"]
    assert 1 <= bench["run_seconds"] <= 51
    assert isinstance(bench["run_seconds"], int)
    cells = len(bench["workloads"])
    assert 1 <= cells <= 24
    # a full check with all 24 cells fits the driver's 43,200 s
    assert (2 + 14 * 24) * (bench["run_seconds"] + 60) + 24 * 2 * 90 \
        + 1200 <= 43200
    assert all(_line(w) for w in bench["command"])
    assert 1 <= len(bench["paths"]) <= 16
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, cells // 4)


def test_benchmark_json_keys_and_sizes():
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 65536
    keys_and_sizes(BENCH)


def names_and_units(bench, kind):
    entries = bench[kind]
    names = [e["name"] for e in entries]
    assert len(set(names)) == len(names)
    for e in entries:
        assert NAME.match(e["name"]), e["name"]
        if "unit" in e:
            assert UNIT.match(e["unit"]), e["unit"]
            assert e["better"] in ("lower", "higher")
            assert e["source"] in SOURCES
        for key in ("why", "layer", "source"):
            if key in e and kind in ("configs", "workloads", "per_layer"):
                assert _line(e[key]), (e["name"], key)
    allowed = {
        "configs": {"name", "source", "file", "reduced", "why"},
        "workloads": {"name", "config", "traffic", "chips", "why"},
        "end_to_end": {"name", "unit", "better", "bound", "source",
                       "workloads"},
        "per_layer": {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"}}[kind]
    for e in entries:
        assert set(e) <= allowed, (e["name"], set(e) - allowed)


@pytest.mark.parametrize("kind", KINDS)
def test_names_and_units_use_only_the_allowed_characters(kind):
    names_and_units(BENCH, kind)


def hang_together(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    cells = {w["name"]: w for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert {w["config"] for w in cells.values()} == set(configs)
    pairs = [(w["config"], w["traffic"]) for w in cells.values()]
    assert len(set(pairs)) == len(pairs)
    files = [c["file"] for c in configs.values()]
    assert len(set(files)) == len(files)
    for c in configs.values():
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        for key in c["reduced"]:        # never a width
            assert not re.search(r"_dim$|_rank$|hidden_size|intermediate|"
                                 r"n_embd|n_inner|head", key)
    for w in cells.values():
        assert w["chips"] in (1, 4)
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in e2e.values():
        assert 0 < m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
        assert set(m.get("workloads", cells)) <= set(cells)
    for m in bench["per_layer"]:
        assert "bound" not in m and m["moves"] in e2e
        where = set(m.get("workloads", cells))
        moved = set(e2e[m["moves"]].get("workloads", cells))
        assert where <= moved, m["name"]
    for name in cells:
        mine = [m for m in e2e.values()
                if name in m.get("workloads", cells)]
        assert len(mine) >= 2
        assert any(name in m.get("workloads", cells)
                   for m in bench["per_layer"])


def test_cells_configs_and_metrics_hang_together():
    hang_together(BENCH)
    for c in BENCH["configs"]:
        on_disk = json.load(open(os.path.join(REPO, c["file"])))
        assert on_disk["reduced"] == c["reduced"]
        assert on_disk["source"] == c["source"]


def test_every_file_under_paths_is_named_from_a_names_characters():
    for path in BENCH["paths"]:
        for base, dirs, files in os.walk(os.path.join(REPO, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                if f.endswith(".pyc"):
                    continue
                rel = os.path.relpath(os.path.join(base, f), REPO)
                assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel), rel


MIX_SUFFIXES = (".json", ".jsonl", ".toml", ".txt", ".csv")


@pytest.mark.parametrize("file", sorted(os.listdir(
    os.path.join(REPO, "perfbench", "traffic"))))
def test_a_traffic_mix_is_a_data_file(file):
    name, suffix = os.path.splitext(file)
    assert suffix in MIX_SUFFIXES and NAME.match(name), file
    assert suffix == ".json", "the one generator reads JSON: %s" % file
    mix = _mix(name)
    assert mix["kind"] in ("train_stream", "closed_loop", "open_loop")
    assert isinstance(mix["what"], str)
    # a traced window of the whole 30 s is millions of events and tens of
    # GB of host memory once a step takes 11 ms (PERF.md, PR 26)
    assert 0 < mix["trace_seconds"] <= 6
    if mix["kind"] == "open_loop":
        assert isinstance(mix["rate_per_s"], (int, float))
        assert not math.isnan(mix["rate_per_s"])
    if "cycle" in mix:      # a window holds the whole sequence once
        assert mix["cycle"] == mix["rate_per_s"] * BENCH["run_seconds"]
