"""``perfbench/harness/program_trace.py`` and the readers it serves, on
hand-built events: from an ``op_name`` to a scope, self time, the
program's scope map matched through the executions on ``XLA Modules``,
``unscoped``, occupancy weighting, exposed = collective minus cover, the
host loop, and a reader that finds nothing.  No chip, no process."""
import json
import os
import types

import pytest

from perfbench.harness import program_trace as pt
from perfbench.harness import xplane
from perfbench.harness.spec import Cell
from perfbench.harness.xplane import Event

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
D0 = "/device:TPU:0"
NEW = {"loss_head_ms.train", "optimizer_ms.train", "finite_check_ms.train",
       "step_host_ms.train", "kv_write_ms.serve", "host_loop_ms.serve",
       "slot_occupancy.serve", "collective_ms.train",
       "exposed_collective_ms.train"}


def _op(name, start, dur, *scope):
    return pt.Op(name, float(start), float(dur), tuple(scope))


def _span(name, start, dur, **attrs):
    return pt.Span(name, "engine", float(start), float(dur), attrs)


# -- from an op_name to a scope ----------------------------------------------

@pytest.mark.parametrize("op_name,want", [
    ("jit(step_fn)/transpose(jvp(encoder))/cell3/attention/jit(f)/"
     "dot_general", ("encoder", "cell3", "attention")),
    ("jit(step_fn)/jvp(mx.loss)/jit(f)/jit(log_softmax)/reduce_max",
     ("mx.loss",)),
    ("jit(step_fn)/mx.optimizer/jit(_where)/select_n", ("mx.optimizer",)),
    ("jit(step_fn)/mx.optimizer/mul;jit(step_fn)", ("mx.optimizer",)),
    ("jit(call)/call_exported/jit(_decode_impl)/h7/kv_write/scatter",
     ("h7", "kv_write")),
    ("jit(step_fn)/slice", ()),           # traced under no scope
    ("jit(step_fn)/jvp()/add", ()),
    ("reduce_sum", ()), ("", ()), (None, ())])
def test_scope_of_an_op_name(op_name, want):
    assert pt.scope_of(op_name) == want


def test_layers_sum_under_a_star():
    assert pt.starred(("h7", "kv_write")) == "h*/kv_write"
    assert pt.starred(("encoder", "cell11", "ffn")) == "encoder/cell*/ffn"
    assert pt.starred(("mx.loss",)) == "mx.loss"
    assert pt.starred(("ln_1",)) == "ln*"


# -- the program's map, matched through the executions ------------------------

PROGRAMS = {
    "train_step:Net": {"module": "jit_step_fn", "scopes": {
        "fusion.1": "jit(step_fn)/jvp(mlm_decoder)/jit(f)/dot_general",
        "fusion.2": "jit(step_fn)/mx.optimizer/mul"}},
    "model:decode:16": {"module": "jit_call", "scopes": {
        "fusion.1": "jit(call)/call_exported/jit(_decode_impl)/h0/kv_write/"
                    "scatter",
        "fusion.9": "jit(call)/call_exported/jit(_decode_impl)/mx.lm_head/"
                    "argmax"}},
    "model:prefill:64": {"module": "jit_call", "scopes": {
        "fusion.1": "jit(call)/call_exported/jit(_prefill_impl)/"
                    "mx.kv_scatter/scatter",
        "fusion.7": "jit(call)/call_exported/jit(_prefill_impl)/h0/attention"
                    "/exp"}},
}


def test_an_event_is_matched_to_its_program_through_the_execution():
    """``fusion.1`` is three different instructions in three programs: the
    execution the event runs inside says which."""
    modules = [(0, 100, "jit_step_fn(11)"), (100, 200, "jit_call(22)"),
               (200, 300, "jit_call(33)"), (300, 400, "jit_other(44)")]
    raw = [("%fusion.1 = f32[8] fusion()", 10.0, 5.0),
           ("%fusion.2 = f32[8] fusion()", 20.0, 5.0),
           ("%copy.3 = f32[8] copy()", 30.0, 5.0),
           ("%fusion.1 = f32[8] fusion()", 110.0, 5.0),
           ("%fusion.9 = s32[8] fusion()", 120.0, 5.0),
           ("%fusion.1 = f32[8] fusion()", 210.0, 5.0),
           ("%fusion.7 = f32[8] fusion()", 220.0, 5.0),
           ("%fusion.1.remat_uncompressed = f32[8] copy()", 230.0, 5.0),
           ("%fusion.1 = f32[8] fusion()", 310.0, 5.0),
           ("%fusion.1 = f32[8] fusion()", 500.0, 5.0)]
    ops, matched = pt.scoped_ops(raw, modules, PROGRAMS)
    assert matched == {"jit_step_fn(11)": ["train_step:Net", 3, 2],
                       "jit_call(22)": ["model:decode:16", 2, 2],
                       "jit_call(33)": ["model:prefill:64", 3, 2],
                       "jit_other(44)": [None, 1, 0]}
    assert [(o.name, o.scope) for o in ops] == [
        ("fusion.1", ("mlm_decoder",)), ("fusion.2", ("mx.optimizer",)),
        ("copy.3", ()),
        ("fusion.1", ("h0", "kv_write")), ("fusion.9", ("mx.lm_head",)),
        ("fusion.1", ("mx.kv_scatter",)), ("fusion.7", ("h0", "attention")),
        # the compiler's copy of a scoped value: named after it, no op_name
        ("fusion.1.remat_uncompressed", ()),
        ("fusion.1", ()),        # a program nobody noted
        ("fusion.1", ())]        # outside every execution
    assert pt.program_of("jit_call(22)", {"fusion.9"}, PROGRAMS) \
        == "model:decode:16"
    assert pt.program_of("jit_call(22)", {"nothing.0"}, PROGRAMS) is None
    # a program without scopes (the parent of the PR that added them)
    assert not any(o.scope for o in pt.scoped_ops(raw, modules, {})[0])


# -- self time, by scope, unscoped -------------------------------------------

OPS = [
    _op("fusion.1", 0, 100, "mlm_decoder"),
    _op("while.2", 100, 300, "encoder"),             # parent ...
    _op("fusion.3", 120, 80, "encoder", "cell0", "attention"),
    _op("fusion.4", 220, 100, "encoder", "cell1", "attention"),  # ... kids
    _op("copy.5", 400, 50),                          # the compiler's own
    _op("fusion.6", 450, 30, "mx.optimizer"),
    _op("copy.7", 480, 10),
    _op("fusion.remat", 490, 10),
]


def test_self_time_does_not_count_a_loop_body_twice():
    self_ns = {op.name: ns for op, ns in pt.self_times(OPS)}
    assert self_ns["while.2"] == 300 - 80 - 100
    assert self_ns["fusion.3"] == 80 and self_ns["fusion.1"] == 100
    assert sum(self_ns.values()) == 500       # the busy time, once


def test_device_time_by_scope_with_unscoped_and_its_largest():
    timed = pt.self_times(OPS)
    top = pt.by_scope(timed, steps=2)
    assert top == {"mlm_decoder": 50.0, "encoder": 150.0,
                   "mx.optimizer": 15.0, pt.UNSCOPED: 35.0}
    part = pt.by_scope(timed, 2, key=lambda s: pt.starred(s[:3]))
    assert part["encoder/cell*/attention"] == 90.0
    assert part["encoder"] == 60.0            # the loop's own time
    assert pt.largest_unscoped(timed, 2, n=2) == [
        ["copy.5", 25.0 / 1e6], ["copy.7", 5.0 / 1e6]]
    assert pt.scoped_ns(timed, 2, r"^mx\.optimizer(/|$)") == 15.0
    assert pt.scoped_ns(timed, 2, r"(^|/)attention(/|$)") == 90.0
    assert pt.scoped_ns(timed, 2, r"^mx\.loss(/|$)") is None


def test_ops_are_counted_inside_whole_steps_only():
    steps = [(100, 400), (450, 500)]
    assert [o.name for o in pt.inside(OPS, steps)] == [
        "while.2", "fusion.3", "fusion.4", "fusion.6", "copy.7",
        "fusion.remat"]


# -- spans: occupancy, the host loop -------------------------------------------

def test_occupancy_is_weighted_by_time_inside_the_window():
    steps = [_span("mx.decode.step", 0, 100, n=16, bucket=16, max_slots=16),
             _span("mx.decode.step", 100, 300, n=4, bucket=8, max_slots=16),
             # half of it lies past the window's end
             _span("mx.decode.step", 400, 200, n=8, bucket=8, max_slots=16),
             _span("mx.decode.step", 900, 50, n=16, bucket=16, max_slots=16)]
    got = pt.occupancy(steps, (0, 500))
    assert got == pytest.approx(
        100.0 * (1.0 * 100 + 0.25 * 300 + 0.5 * 100) / 500)
    assert pt.occupancy(steps, (2000, 3000)) is None
    assert pt.occupancy([_span("mx.decode.step", 0, 10)], (0, 500)) is None


def test_exposed_is_collective_minus_cover():
    coll = [Event(D0, "XLA Ops", "all-reduce.1", 100.0, 100.0, ""),
            Event(D0, "Async XLA Ops", "all-reduce.1", 90.0, 120.0, ""),
            Event(D0, "XLA Ops", "all-reduce.2", 400.0, 50.0, "")]
    others = [Event(D0, "XLA Ops", "fusion.1", 0.0, 120.0, ""),
              Event(D0, "XLA Ops", "fusion.2", 150.0, 20.0, ""),
              Event(D0, "XLA Ops", "fusion.3", 205.0, 100.0, ""),
              Event(D0, "XLA Ops", "fusion.4", 440.0, 100.0, "")]
    # collectives cover [90, 210) and [400, 450); others leave bare
    # [120, 150), [170, 205) and [400, 440)
    assert pt.exposed_ns(coll, others) == 30 + 35 + 40
    assert pt.exposed_ns(coll, []) == 120 + 50
    assert pt.exposed_ns([], others) == 0
    assert pt.subtract([(0, 10)], [(0, 10)]) == []
    assert pt.subtract([(0, 10), (20, 30)], [(5, 25)]) == [(0, 5), (25, 30)]


# -- the readers, through the harness's own lookup -----------------------------

def _reader(cell, name):
    return Cell(REPO, cell).layer_reader(name)


def _run(view, kind="serve", cfg=None, trace=None):
    lines = []
    return types.SimpleNamespace(
        _program_trace=view, family=types.SimpleNamespace(KIND=kind),
        cfg=cfg or {}, trace=trace, tracing=True,
        log=types.SimpleNamespace(
            line=lambda **kw: lines.append(kw),
            measurement=lambda event, **kw: lines.append(kw)),
        lines=lines)


def _view(spans=(), ops=(), window=(0.0, 1000.0), steps=()):
    view = pt.ProgramTrace(list(spans), list(ops), window)
    view.steps = list(steps)
    return view


def test_serving_readers_on_hand_built_spans():
    spans = [
        _span("mx.decode.step", 0, 400, n=16, bucket=16, max_slots=16),
        _span("mx.decode.step.build", 0, 10),
        _span("mx.decode.step.call", 10, 380),
        _span("mx.decode.step.emit", 390, 10),
        _span("mx.decode.admit", 400, 100),
        _span("mx.decode.prefill", 400, 100, bucket=64, prompt=40),
        _span("mx.decode.prefill.call", 410, 80),
        _span("mx.decode.step", 500, 400, n=8, bucket=8, max_slots=16),
        _span("mx.decode.step.call", 510, 380),
        # cut by the window's end: a quarter of it lies inside
        _span("mx.decode.step", 900, 400, n=8, bucket=8, max_slots=16),
        _span("mx.decode.step.call", 910, 380)]
    ops = [_op("fusion.1", 20, 100, "h0", "kv_write"),
           _op("fusion.2", 120, 60, "h1", "kv_write"),
           _op("copy.3", 180, 200),
           _op("fusion.1", 520, 80, "h0", "kv_write"),
           _op("copy.3", 600, 280),
           _op("fusion.1", 920, 70, "h0", "kv_write")]
    view = _view(spans, ops, window=(0.0, 1000.0),
                 steps=[(10, 390), (510, 890)])     # whole calls only
    run = _run(view)
    cell = "gpt2m_serve_closed16"
    kv = _reader(cell, "kv_write_ms.serve")(run)
    assert kv == pytest.approx((100 + 60 + 80) / 2 / 1e6)
    occ = _reader(cell, "slot_occupancy.serve")(run)
    assert occ == pytest.approx(100.0 * (400 + 200 + 50) / 900)
    host = _reader(cell, "host_loop_ms.serve")(run)
    # from the first whole step's start to the last whole step's end:
    # [0, 900), two steps, their calls and the prefill's inside
    assert host == pytest.approx((900 - (380 + 80 + 380)) / 2 / 1e6)


def test_queue_wait_reader_on_hand_built_spans():
    """The median of ``waited_us`` over the admissions of the traced
    window; below twenty of them, nothing."""
    waits = [_span("mx.decode.queue_wait", 10 + 20 * i, 1,
                   waited_us=1000 * (i + 1)) for i in range(40)]
    outside = [_span("mx.decode.queue_wait", 1500, 1, waited_us=10 ** 9),
               _span("mx.decode.queue_wait", 900, 1)]      # no attribute
    read = _reader("gpt2m_serve_open_r80", "queue_wait_ms.serve")
    got = read(_run(_view(waits + outside)))
    assert got == pytest.approx(20.5)       # the median of 1..40 ms
    assert read(_run(_view(waits[:19] + outside))) is None
    assert read(_run(None)) is None


def test_the_breakdown_names_idle_time_by_the_programs_spans():
    """``breakdown.idle_gaps`` (the ledger's only trace): the program's
    ``mx.`` spans stand beside the benchmark's, the innermost wins, and
    only what no span covers goes to the driver's default name."""
    from perfbench.harness import report
    from perfbench.harness.runctx import TraceView
    events = [
        Event(xplane.HOST_PLANE, "main", "perfbench.window", 0.0, 1000.0, ""),
        Event(xplane.HOST_PLANE, "client", "perfbench.submit", 300.0, 20.0,
              ""),
        Event(D0, "XLA Ops", "fusion.1", 100.0, 100.0, ""),
        Event(D0, "XLA Ops", "fusion.2", 500.0, 300.0, "")]
    spans = [_span("mx.decode.step", 0, 450, n=16, bucket=16, max_slots=16),
             _span("mx.decode.step.build", 0, 50),
             _span("mx.decode.step.call", 50, 350),
             _span("mx.decode.step.emit", 400, 50),
             _span("mx.decode.step", 480, 420, n=16, bucket=16,
                   max_slots=16),
             _span("mx.decode.step.call", 490, 400)]
    run = _run(_view(spans), trace=TraceView(events, chips=1))
    parts = report.breakdown(run, "engine host loop")
    gaps = dict(parts["idle_gaps"])
    # idle: [0,100) build 50 + call 50; [200,500): call 200 less the 20 of
    # the client's submit inside it, emit 50, nobody's [450,480) 30, then
    # the next step's own 10 before its call and the call's 10; [800,1000):
    # call 90, step 10, nobody's 100
    assert gaps == pytest.approx({
        "mx.decode.step.build": 50 / 1e9, "mx.decode.step.call": 330 / 1e9,
        "perfbench.submit": 20 / 1e9, "mx.decode.step.emit": 50 / 1e9,
        "mx.decode.step": 20 / 1e9, "engine host loop": 130 / 1e9})
    assert parts["device_ops"][0] == ["fusion.2", 300 / 1e9]
    # a program without spans (the parent of PR 25): the default takes all
    bare = report.breakdown(_run(_view(), trace=TraceView(events, chips=1)),
                            "engine host loop")
    assert dict(bare["idle_gaps"]) == pytest.approx({
        "engine host loop": 580 / 1e9, "perfbench.submit": 20 / 1e9})


def test_training_readers_on_hand_built_events():
    spans = [_span("mx.train_step", 0, 30, step=1, items=32),
             _span("mx.train_step.dispatch", 10, 5),
             _span("mx.train_step", 200, 50, step=2, items=32),
             _span("mx.train_step", 500, 40, step=3, items=32),
             _span("mx.train_step", 980, 40, step=4, items=32),   # cut
             _span("mx.feed.stage", 5, 20, bytes=131072)]
    ops = [_op("fusion.1", 0, 100, "mlm_decoder"),
           _op("fusion.2", 100, 50, "mx.loss"),
           _op("fusion.3", 150, 40, "encoder", "cell0", "ffn"),
           _op("fusion.4", 190, 30, "mx.optimizer"),
           _op("fusion.5", 220, 20, "mx.finite_check"),
           _op("fusion.1", 500, 120, "mlm_decoder"),
           _op("fusion.4", 620, 50, "mx.optimizer")]
    run = _run(_view(spans, ops, steps=[(0, 400), (500, 900)]), "train")
    cell = "bert_train_1chip"
    assert _reader(cell, "loss_head_ms.train")(run) \
        == pytest.approx((100 + 50 + 120) / 2 / 1e6)
    assert _reader(cell, "optimizer_ms.train")(run) \
        == pytest.approx((30 + 50) / 2 / 1e6)
    assert _reader(cell, "finite_check_ms.train")(run) \
        == pytest.approx(20 / 2 / 1e6)
    assert _reader(cell, "step_host_ms.train")(run) \
        == pytest.approx(40 / 1e6)         # median of the whole spans
    # the configuration may name its head's scopes
    run.cfg = {"trace": {"loss_head": r"^encoder(/|$)"}}
    assert _reader(cell, "loss_head_ms.train")(run) \
        == pytest.approx(40 / 2 / 1e6)


def test_exposed_collective_reader_on_hand_built_events():
    from perfbench.harness.runctx import TraceView
    events = [
        Event(xplane.HOST_PLANE, "main", "perfbench.window", 0.0, 1000.0, ""),
        Event(D0, "XLA Modules", "jit_step_fn(7)", 0.0, 400.0, ""),
        Event(D0, "XLA Modules", "jit_step_fn(7)", 500.0, 400.0, ""),
        Event(D0, "XLA Ops", "fusion.1", 0.0, 300.0, ""),
        Event(D0, "Async XLA Ops", "all-reduce.4", 250.0, 150.0, ""),
        Event(D0, "XLA Ops", "fusion.1", 500.0, 300.0, ""),
        Event(D0, "XLA Ops", "all-reduce.5", 780.0, 70.0, ""),
        Event(D0, "XLA Ops", "fusion.2", 850.0, 50.0, "")]
    cfg = {"trace": {"step_module": "^jit_step_fn",
                     "collectives": "^(all-reduce|all-gather)"}}
    run = _run(None, "train", cfg, TraceView(events, chips=1))
    cell = "bert_train_dp4"
    total = _reader(cell, "collective_ms.train")(run)
    exposed = _reader(cell, "exposed_collective_ms.train")(run)
    # step 1: [250, 400) of which [300, 400) is bare; step 2: [780, 850)
    # of which [800, 850) is bare
    assert total == pytest.approx((150 + 70) / 2 / 1e6)
    assert exposed == pytest.approx((100 + 50) / 2 / 1e6)
    assert exposed <= total


@pytest.mark.parametrize("metric", sorted(NEW - {
    "collective_ms.train", "exposed_collective_ms.train"}))
def test_a_reader_that_finds_nothing_returns_none(metric):
    """The parent's trace: no ``mx.`` span, no scoped op.  And a run
    without a trace at all."""
    cell = next(w for m in BENCH["per_layer"] if m["name"] == metric
                for w in m["workloads"])
    kind = "train" if metric.endswith(".train") else "serve"
    bare = _view(ops=[_op("fusion.1", 0, 100), _op("copy.2", 100, 100)],
                 steps=[(0, 400)])
    assert _reader(cell, metric)(_run(bare, kind)) is None
    assert _reader(cell, metric)(_run(None, kind)) is None


def test_collective_readers_find_nothing_without_a_trace():
    for metric in ("collective_ms.train", "exposed_collective_ms.train"):
        assert _reader("bert_train_dp4", metric)(_run(None, "train")) is None


# -- the whole path on a hand-built XSpace --------------------------------------

XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 400000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 2 offset_ps: 0 duration_ps: 100000 }
    events { metadata_id: 3 offset_ps: 100000 duration_ps: 100000 }
    events { metadata_id: 4 offset_ps: 200000 duration_ps: 50000 } }
  event_metadata { key: 1 value { id: 1 name: "jit_step_fn(7)" } }
  event_metadata { key: 2 value { id: 2
    name: "%fusion.1 = f32[8]{0} fusion(f32[8] %p), kind=kLoop" } }
  event_metadata { key: 3 value { id: 3
    name: "%copy.2 = f32[8]{0} copy(f32[8] %fusion.1)" } }
  event_metadata { key: 4 value { id: 4
    name: "%fusion.3 = f32[8]{0} fusion(f32[8] %copy.2), kind=kLoop" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "main" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 500000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 300000
             stats { metadata_id: 1 int64_value: 3 }
             stats { metadata_id: 2 int64_value: 32 } }
    events { metadata_id: 3 offset_ps: 0 duration_ps: 500000 } }
  event_metadata { key: 1 value { id: 1 name: "perfbench.window" } }
  event_metadata { key: 2 value { id: 2 name: "mx.train_step" } }
  event_metadata { key: 3 value { id: 3 name: "$some python frame" } }
  stat_metadata { key: 1 value { id: 1 name: "step" } }
  stat_metadata { key: 2 value { id: 2 name: "items" } }
}
"""


def test_read_profile_on_a_hand_built_xspace():
    from jax.profiler import ProfileData
    programs = {"train_step:Net": {"module": "jit_step_fn", "scopes": {
        "fusion.1": "jit(step_fn)/jvp(mlm_decoder)/dot_general",
        "fusion.3": "jit(step_fn)/jvp(mx.loss)/exp"}}}
    spans, ops, matched, window = pt.read_profile(
        ProfileData.from_text_proto(XSPACE), "perfbench.window", programs)
    assert matched == {"jit_step_fn(7)": ["train_step:Net", 3, 2]}
    assert window[1] - window[0] == pytest.approx(500)
    assert [(s.name, s.attrs) for s in spans] == [
        ("mx.train_step", {"step": 3, "items": 32})]
    assert [(o.name, o.scope) for o in ops] == [
        ("fusion.1", ("mlm_decoder",)), ("copy.2", ()),
        ("fusion.3", ("mx.loss",))]
    # no window span: nothing places the ops
    assert pt.read_profile(ProfileData.from_text_proto(XSPACE),
                           "no.such.span")[1:] == ([], {}, None)


# -- BENCHMARK.json declares the new metrics and the new cell ---------------------

def test_the_new_metrics_and_the_four_chip_cell_are_declared():
    by = {m["name"]: m for m in BENCH["per_layer"]}
    assert NEW <= set(by)
    cells = {w["name"]: w for w in BENCH["workloads"]}
    assert cells["bert_train_dp4"]["chips"] == 4
    assert cells["bert_train_dp4"]["config"] == "bert-base-mlm-s512-dp4"
    # each lists at least the cells it named at PR 25; a later cell that
    # reports it is appended
    train = {"bert_train_1chip", "bert_train_dp4"}
    for name in ("loss_head_ms.train", "optimizer_ms.train",
                 "finite_check_ms.train", "step_host_ms.train"):
        assert train <= set(by[name]["workloads"])
    for name in ("kv_write_ms.serve", "host_loop_ms.serve",
                 "slot_occupancy.serve"):
        assert "gpt2m_serve_closed16" in by[name]["workloads"]
    for name in ("collective_ms.train", "exposed_collective_ms.train"):
        assert "bert_train_dp4" in by[name]["workloads"]
        assert "bert_train_1chip" not in by[name]["workloads"]
    # every metric the one-chip train cell reports, the four-chip one does
    for m in BENCH["per_layer"] + BENCH["end_to_end"]:
        if "bert_train_1chip" in m.get("workloads", ()):
            assert "bert_train_dp4" in m["workloads"], m["name"]
    # and likewise among the cells of one configuration: what the first of
    # them reports, the others report (each may add what only it has)
    for config in BENCH["configs"]:
        first, *others = [w["name"] for w in BENCH["workloads"]
                          if w["config"] == config["name"]]
        for m in BENCH["per_layer"] + BENCH["end_to_end"]:
            if first in m.get("workloads", ()):
                assert set(others) <= set(m["workloads"]), m["name"]
    layers = {m["layer"] for m in BENCH["per_layer"]}
    assert by["step_host_ms.train"]["layer"] \
        == by["step_device_ms.train"]["layer"]
    assert by["host_loop_ms.serve"]["layer"] \
        == by["decode_step_ms.serve"]["layer"]
    assert len(layers) == 7
