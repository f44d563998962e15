"""``perfbench/harness/serve_programs.py`` and the four readers it serves, on
hand-built tuples: the serving programs' executions by the names the program
gives them (``jit_mx_<kind>_b<bucket>`` on ``XLA Modules``), whole inside the
window and clipped to it, a prefill joined to the ``mx.decode.prefill`` span
that holds it, what a stream waits when a prefill stands before its token,
the parts of a prefill by scope, and a parent-style trace (every module
``jit_call``) that gives None with a reason.  Then the four entries of
``BENCHMARK.json``, and one rehearsal that declares them missing.  No chip."""
import json
import os
import types

import pytest

import _entries
from perfbench.harness import program_trace as pt
from perfbench.harness import serve_programs as sp
from perfbench.harness import xplane
from perfbench.harness.spec import Cell

from test_perfbench_command import _copy_of_the_benchmark, _records, _run

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
D0 = "/device:TPU:0"
CELLS = ["gpt2m_serve_closed16", "gpt2m_serve_open_r80",
         "kimi_k2_serve_closed32", "mellum2_serve_closed32"]
NEW = {"decode_device_ms.serve": "ms", "prefill_device_share.serve": "%",
       "prefill_us_per_token.serve": "us",
       "prefill_attention_us_per_token.serve": "us"}
WINDOW = (0.0, 1000e6)

# (name, start_ns, dur_ns): three decode steps of 5 ms, a 64-token prefill of
# 20 ms, one of 256 tokens of 60 ms, a second 64-token one of 30 ms, a decode
# step cut by the window's end, a 256-token prefill cut by its start, and a
# program of somebody else's
MODULES = [
    ("jit_mx_prefill_b256(9)", -40e6, 100e6),
    ("jit_mx_decode_b16(1)", 100e6, 5e6),
    ("jit_mx_prefill_b64(2)", 110e6, 20e6),
    ("jit_mx_decode_b16(1)", 130e6, 5e6),
    ("jit_mx_prefill_b256(3)", 200e6, 60e6),
    ("jit_mx_decode_b8(4)", 260e6, 7e6),
    ("jit_mx_prefill_b64(2)", 300e6, 30e6),
    ("jit_convert_element_type(5)", 400e6, 10e6),
    ("jit_mx_decode_b16(1)", 998e6, 5e6)]


def _span(name, start, dur, **attrs):
    # a trace's stats arrive as the profiler gives them: ints here, and a
    # string where the reader has to cope
    return pt.Span(name, "engine", float(start), float(dur), attrs)


def _op(name, start, dur, *scope):
    return pt.Op(name, float(start), float(dur), tuple(scope))


PREFILL_SPANS = [
    _span("mx.decode.prefill", 108e6, 30e6, bucket=64, prompt=40, live=3,
          behind=1),
    _span("mx.decode.prefill", 195e6, 70e6, bucket=256, prompt="200",
          live=15, behind=1),
    # opens after its execution started: holds nothing whole
    _span("mx.decode.prefill", 305e6, 40e6, bucket=64, prompt=64, live=1,
          behind=0)]


# -- executions by name and bucket -------------------------------------------

def test_executions_are_found_by_kind_and_bucket_whole_inside_the_window():
    prefills = sp.named_executions(MODULES, "prefill", WINDOW)
    assert prefills == [sp.Execution(110e6, 20e6, 64),
                        sp.Execution(200e6, 60e6, 256),
                        sp.Execution(300e6, 30e6, 64)]
    decodes = sp.named_executions(MODULES, "decode", WINDOW)
    assert [(e.start_ns, e.bucket) for e in decodes] == [
        (100e6, 16), (130e6, 16), (260e6, 8)]
    # without a window: every one, the cut ones too, in time order
    assert len(sp.named_executions(MODULES, "prefill")) == 4
    assert sp.named_executions(MODULES, "prefill")[0].start_ns == -40e6
    assert len(sp.named_executions(MODULES, "decode")) == 4
    assert sp.named_executions(MODULES, "step", WINDOW) == []
    # a kind is the whole word between ``mx_`` and ``_b``
    assert sp.named_executions([("jit_mx_prefill_b8(1)", 0.0, 1.0)],
                               "pre") == []


def test_device_time_is_clipped_to_the_window():
    # 60 of the first prefill's 100 ms lie inside; the others whole
    assert sp.clipped_ns(MODULES, "prefill", WINDOW) \
        == pytest.approx((60 + 20 + 60 + 30) * 1e6)
    # 2 of the last decode step's 5 ms lie inside
    assert sp.clipped_ns(MODULES, "decode", WINDOW) \
        == pytest.approx((5 + 5 + 7 + 2) * 1e6)
    assert sp.clipped_ns(MODULES, "decode", (2000e6, 3000e6)) == 0.0


def test_an_execution_is_joined_to_the_span_that_holds_it_whole():
    joined = sp.join(sp.named_executions(MODULES, "prefill", WINDOW),
                     PREFILL_SPANS)
    assert [(e.bucket, s and s.attrs["live"]) for e, s in joined] == [
        (64, 3), (256, 15), (64, None)]
    assert sp.join([], PREFILL_SPANS) == []
    assert [s for _e, s in sp.join(
        sp.named_executions(MODULES, "prefill", WINDOW), [])] == [None] * 3
    # padding: (64 - 40) + (256 - 200) of 64 + 256 computed tokens
    assert sp.padded_share(joined) == pytest.approx(80 / 320)
    # 3 streams x 20 ms + 15 streams x 60 ms
    assert sp.streams_held_ms(joined) == pytest.approx(60 + 900)
    assert sp.padded_share(joined[2:]) is None
    assert sp.streams_held_ms(joined[2:]) is None


def test_each_bucket_reads_its_own_median_and_its_time_a_token():
    got = sp.by_bucket(sp.named_executions(MODULES, "prefill", WINDOW))
    assert got == {64: [2, pytest.approx(25.0),
                        pytest.approx(25e3 / 64)],
                   256: [1, pytest.approx(60.0),
                         pytest.approx(60e3 / 256)]}
    assert sp.by_bucket([]) == {}


def test_what_a_stream_waits_when_a_prefill_stands_before_its_token():
    def step(start, dur, after, overlapped=1):
        return _span("mx.decode.step", start, dur, n=4,
                     after_prefill=after, overlapped=overlapped)
    steps = [step(0, 10, 0), step(11, 10, 0), step(22, 9, 0),
             # the prefill stood between 31 and 95: the held step's span
             # opens late, its gap is counted from the last token's arrival
             step(95, 10, 1), step(106, 10, 0),
             step(200, 10, 1), step(215, 5, 0),
             # dispatched with nothing in flight: an idle moment before it
             step(600, 10, 0, overlapped=0), step(611, 10, 0),
             # ends past the window
             step(995, 10, 1)]
    got = sp.held_step_ms(steps, (0.0, 1000.0))
    assert got["after_prefill"] == 2 and got["other_steps"] == 5
    assert got["median"] == pytest.approx((74 + 94) / 2 / 1e6)
    assert got["longest"] == pytest.approx(94 / 1e6)
    # the other gaps: 11, 10, 11, 10, 11
    assert got["other_median"] == pytest.approx(11 / 1e6)
    # spans without the attribute (the parent's): nothing
    bare = [_span("mx.decode.step", 10 * i, 9, n=4, overlapped=1)
            for i in range(5)]
    assert sp.held_step_ms(bare, (0.0, 1000.0)) is None
    only_plain = sp.held_step_ms(steps[:3], (0.0, 1000.0))
    assert only_plain["median"] is None and only_plain["other_steps"] == 2


@pytest.mark.parametrize("scope,name,want", [
    (("h3", "experts", "while", "body", "gather"), "fusion.1",
     "h*/experts/gather"),
    (("h3", "experts", "sort"), "sort.2", "h*/experts/sort"),
    (("h3", "experts", "while", "cond"), "compare.3", "h*/experts"),
    (("h11", "attention", "while", "body", "closed_call", "while", "body",
      "bhgqk,bkhd->bhgqd"), "fusion.4", "h*/attention/bhgqk,bkhd->bhgqd"),
    (("h0", "attention_window", "while", "body"), "fusion.5",
     "h*/attention_window"),
    (("mx.kv_scatter",), "scatter.6", "mx.kv_scatter"),
    ((), "copy.7", "unscoped"),
    # traced inside a loop and under no scope of the program's
    (("while", "body"), "add.9", "unscoped"),
    # the compiler's grouped-matmul kernel bears no scope: the experts' own
    ((), "ragged-dot-none.8", "h*/experts/matmul")])
def test_a_prefills_parts_leave_the_loops_components_out(scope, name, want):
    assert sp.part_of(_op(name, 0, 1, *scope)) == want
    assert sp.is_attention(_op(name, 0, 1, *scope)) \
        == ("attention" in want)


# -- the view and the four readers -------------------------------------------

OPS = [
    # inside the 64-token prefill at 110-130 ms
    _op("fusion.1", 110e6, 4e6, "h0", "attention"),
    _op("while.2", 114e6, 12e6, "h0", "experts"),           # the chunk loop
    _op("fusion.3", 115e6, 3e6, "h0", "experts", "while", "body", "gather"),
    _op("gmm.4", 118e6, 6e6, "h0", "experts", "while", "body", "matmul"),
    _op("fusion.5", 124e6, 2e6, "h0", "experts", "while", "body", "combine"),
    _op("copy.6", 126e6, 4e6),
    # inside the 256-token prefill at 200-260 ms
    _op("fusion.1", 200e6, 30e6, "h0", "attention_full"),
    _op("fusion.7", 230e6, 20e6, "h1", "attention_window", "while", "body"),
    _op("ragged-dot-none.8", 250e6, 10e6),
    # a decode step's attention and the cut prefill's: not counted
    _op("fusion.9", 100e6, 5e6, "h0", "attention"),
    _op("fusion.1", 10e6, 40e6, "h0", "attention")]


def _fake_run(modules=MODULES, spans=PREFILL_SPANS, ops=OPS, matched=None,
              window=WINDOW, busy=()):
    from perfbench.harness.runctx import TraceView
    lines = []
    events = [xplane.Event(xplane.HOST_PLANE, "main", "perfbench.window",
                           window[0], window[1] - window[0], "")]
    events += [xplane.Event(D0, xplane.MODULES_LINE, name, start, dur, "")
               for name, start, dur in modules]
    events += [xplane.Event(D0, xplane.OPS_LINE, "busy.%d" % i, start, dur,
                            "") for i, (start, dur) in enumerate(busy)]
    run = types.SimpleNamespace(
        trace=TraceView(events, chips=1) if modules else None, tracing=True,
        log=types.SimpleNamespace(
            line=lambda **kw: lines.append(kw),
            measurement=lambda event, **kw: lines.append(
                dict(kw, event=event))))
    run._program_trace = pt.ProgramTrace(list(spans), list(ops), window,
                                         matched)
    return run, lines


def _reader(name, cell="kimi_k2_serve_closed32"):
    return Cell(REPO, cell).layer_reader(name)


def test_the_four_readers_on_a_hand_built_run():
    run, lines = _fake_run(busy=[(0.0, 330e6), (998e6, 2e6)])
    assert _reader("decode_device_ms.serve")(run) == pytest.approx(5.0)
    assert _reader("prefill_device_share.serve")(run) \
        == pytest.approx(100.0 * 170 / 1000)
    tokens = 64 + 256 + 64
    assert _reader("prefill_us_per_token.serve")(run) \
        == pytest.approx((20 + 60 + 30) * 1e3 / tokens)
    assert _reader("prefill_attention_us_per_token.serve")(run) \
        == pytest.approx((4 + 30 + 20) * 1e3 / tokens)
    # one line, printed once, whatever the number of readers
    line, = [ln for ln in lines if ln.get("event") == "prefill_programs"]
    assert line["found"] is True and line["joined"] == 2
    assert line["by_bucket"][64][0] == 2 and line["by_bucket"][256][0] == 1
    assert line["longest_ms"] == pytest.approx(60.0)
    assert line["padded_share"] == pytest.approx(0.25)
    assert line["streams_held_ms"] == pytest.approx(960.0)
    assert line["decode_executions"] == 3
    # the chunk loop's own time is what its body leaves of it
    assert line["by_part_us_a_token"] == pytest.approx({
        "h*/attention_full": 30e3 / tokens,
        "h*/attention_window": 20e3 / tokens,
        "h*/experts/matmul": (6 + 10) * 1e3 / tokens,
        "h*/attention": 4e3 / tokens, "unscoped": 4e3 / tokens,
        "h*/experts/gather": 3e3 / tokens,
        "h*/experts/combine": 2e3 / tokens, "h*/experts": 1e3 / tokens})
    shares = line["device_shares"]
    assert shares["prefill"] == pytest.approx(17.0)
    assert shares["decode"] == pytest.approx(1.9)
    assert shares["idle"] == pytest.approx(100.0 - 33.2)
    assert shares["sum"] == pytest.approx(17.0 + 1.9 + 66.8)
    assert line["other_programs_share"] == pytest.approx(
        {"jit_convert_element_type": 1.0})
    assert line["mismatched_programs"] == []
    # executions(run, kind): what a later reader of a serving step takes
    assert [e.bucket for e in sp.executions(run, "decode")] == [16, 16, 8]
    assert [(e.bucket, s is not None)
            for e, s in sp.executions(run, "prefill")] == [
        (64, True), (256, True), (64, False)]


def test_a_module_matched_to_another_label_is_named():
    matched = {"jit_mx_decode_b16(1)": ["model:decode:16", 9, 9],
               "jit_mx_prefill_b64(2)": ["model:prefill:256", 9, 7],
               "jit_mx_prefill_b256(3)": [None, 4, 0],
               "jit_convert_element_type(5)": [None, 1, 0]}
    run, lines = _fake_run(matched=matched)
    assert sp.load(run) is not None
    assert lines[-1]["mismatched_programs"] == [
        "jit_mx_prefill_b256(3)", "jit_mx_prefill_b64(2)"]


def test_a_window_without_a_prefill_reads_no_share_and_no_token():
    decode_only = [m for m in MODULES if "decode" in m[0]]
    run, lines = _fake_run(modules=decode_only)
    assert _reader("prefill_device_share.serve")(run) == 0.0
    assert _reader("prefill_us_per_token.serve")(run) is None
    assert _reader("prefill_attention_us_per_token.serve")(run) is None
    assert _reader("decode_device_ms.serve")(run) == pytest.approx(5.0)
    assert lines[-1]["by_part_us_a_token"] is None
    # scopes the program does not have: the attention reader finds nothing
    run, _ = _fake_run(ops=[o for o in OPS if not sp.is_attention(o)])
    assert _reader("prefill_us_per_token.serve")(run) is not None
    assert _reader("prefill_attention_us_per_token.serve")(run) is None


@pytest.mark.parametrize("metric", sorted(NEW))
def test_a_parent_style_trace_gives_none_and_says_why(metric):
    """The parent's program: every serving module is ``jit_call``.  And a
    run without a device trace (a rehearsal, ``--trace 0``)."""
    parent = [("jit_call(%d)" % (i % 3), start, dur)
              for i, (_name, start, dur) in enumerate(MODULES)]
    run, lines = _fake_run(modules=parent)
    assert _reader(metric)(run) is None
    assert _reader(metric)(run) is None
    said = [ln for ln in lines if ln.get("event") == "prefill_programs"]
    assert len(said) == 1 and said[0]["found"] is False
    assert "jit_mx_<kind>_b<bucket>" in said[0]["why"]
    run, lines = _fake_run(modules=())
    assert _reader(metric)(run) is None
    assert "no device trace" in lines[-1]["why"]
    assert sp.executions(run, "decode") is None


# -- BENCHMARK.json declares the four ------------------------------------------

def entries(bench):
    """What the benchmark holds of the four, whatever later cells were
    appended to their lists."""
    for m in _entries.metrics_in_order(
            bench, ["ut_passes_per_token.serve"] + list(NEW))[1:]:
        assert {k: v for k, v in m.items() if k != "workloads"} == {
            "name": m["name"], "unit": NEW[m["name"]], "better": "lower",
            "source": "device_trace",
            "layer": "serving engine (serving/decode/engine.py)",
            "moves": "serve_tokens_per_s"}
        # the cells that had them first, then whichever were appended
        assert m["workloads"][:len(CELLS)] == CELLS
    # the cells of one configuration report them together
    for cell in CELLS:
        assert set(NEW) <= _entries.reported(bench, cell)


def test_the_four_metrics_are_appended_entries():
    entries(BENCH)
    for name in NEW:
        assert callable(Cell(REPO, CELLS[0]).layer_reader(name))


def test_a_rehearsal_declares_the_four_as_missing(tmp_path):
    """A CPU trace has no device plane: no execution to find, and the
    line says so."""
    root = str(_copy_of_the_benchmark(tmp_path))
    out = _run(["--workload", "gpt2m_serve_closed16", "--seed", "3000000011",
                "--seconds", "1", "--trace", "1", "--rehearse"], root=root,
               pythonpath=REPO)
    assert out.returncode == 0, out.stderr[-3000:]
    records = _records(out)
    by = {r["event"]: r for r in records}
    assert by["rehearsed"]["correct"] is True
    assert set(NEW) <= set(by["per_layer"]["missing"])
    assert not set(NEW) & set(by["rehearsed"]["metrics"])
    said = [r for r in records if r["event"] == "prefill_programs"]
    assert len(said) == 1 and said[0]["found"] is False
    assert "no device trace" in said[0]["why"]
