"""The program's names in a profiler trace (ISSUE 25): the ``mx.`` host
spans of ``TrainStep``, ``DeviceFeed`` and ``DecodeEngine`` through the
one span call, as a ``jax.profiler`` session that anyone started sees
them; the scopes inside the compiled train and decode programs; and the
off cost: with no session and tracing off a site makes no ``obs`` ring
call."""
import collections
import glob
import itertools
import os
import time

import numpy as np
import pytest

import jax

import mxnet_tpu as mx
from mxnet_tpu import gluon, obs
from mxnet_tpu.dataio import DeviceFeed
from mxnet_tpu.parallel import TrainStep
from mxnet_tpu.serving.decode import DecodeEngine, tiny_gpt

MODEL = tiny_gpt(vocab_size=32, units=16, num_layers=2, num_heads=2,
                 max_seq=32)
ENGINE_KW = dict(prefill_buckets=(8,), decode_buckets=(1, 2),
                 block_size=4, num_blocks=32, max_queue=8)

Span = collections.namedtuple("Span", "name line start end attrs")


class TwoLayer(gluon.HybridBlock):
    def __init__(self, **kw):
        super().__init__(**kw)
        with self.name_scope():
            self.body = gluon.nn.Dense(8, activation="relu", in_units=3)
            self.head = gluon.nn.Dense(2, in_units=8)

    def hybrid_forward(self, F, x):
        return self.head(self.body(x))


def _train_step():
    net = TwoLayer()
    net.initialize()
    net.hybridize()
    tr = gluon.Trainer(net.collect_params(), "adam",
                       {"learning_rate": 0.01}, kvstore=None)
    return TrainStep(net, gluon.loss.L2Loss(), tr, mesh=None)


def _train_loop(step, steps):
    batches = [(np.ones((4, 3), np.float32), np.ones((4, 2), np.float32))]
    feed = DeviceFeed(itertools.cycle(batches))
    try:
        for _ in range(steps):
            loss = step(next(feed))
        return float(loss.asscalar())
    finally:
        feed.close()


def _engine():
    eng = DecodeEngine(MODEL, MODEL.init_params(0), **ENGINE_KW)
    eng.warmup()
    eng.start()
    return eng


def _traced(tmp_path, work):
    """Run ``work()`` inside a ``jax.profiler`` session of the test's own
    (no switch of the program's is touched) and return the ``mx.`` host
    events of the trace."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        work()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    spans = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        # a line is a thread; several may carry the same name
        for thread, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("mx."):
                    spans.append(Span(ev.name, thread, ev.start_ns,
                                      ev.start_ns + ev.duration_ns,
                                      dict(ev.stats)))
    return spans


def _children(spans, parent):
    """The spans of the parent's thread that lie inside it and inside no
    other span that lies inside it: its children by time, as a profiler's
    viewer nests them."""
    within = [s for s in spans if s is not parent and s.line == parent.line
              and parent.start <= s.start and s.end <= parent.end]
    return [s for s in within
            if not any(o is not s and o.start <= s.start and s.end <= o.end
                       for o in within)]


def _named(spans, name):
    return [s for s in spans if s.name == name]


@pytest.fixture(autouse=True)
def _ring_off():
    obs.disable_tracing()
    obs.trace.clear()
    yield
    obs.disable_tracing()
    obs.trace.clear()


def test_a_profiler_trace_of_a_train_loop_holds_the_programs_spans(tmp_path):
    step = _train_step()
    _train_loop(step, 1)                    # compile outside the session
    assert not obs.tracing_enabled()
    spans = _traced(tmp_path, lambda: _train_loop(step, 3))
    steps = _named(spans, "mx.train_step")
    assert len(steps) == 3
    assert [s.attrs["step"] for s in steps] == [2, 3, 4]
    assert {s.attrs["items"] for s in steps} == {4}
    for s in steps:
        assert [c.name for c in _children(spans, s)] == [
            "mx.train_step.prep", "mx.train_step.dispatch",
            "mx.train_step.rebind"]
    staged = _named(spans, "mx.feed.stage")
    assert staged and all(s.attrs["bytes"] == 4 * 3 * 4 + 4 * 2 * 4
                          for s in staged)
    # the producer's thread is not the loop's
    assert {s.line for s in staged}.isdisjoint({s.line for s in steps})
    assert obs.spans() == []                # the ring stayed off


def test_a_profiler_trace_of_a_decode_engine_holds_one_span_a_step(tmp_path):
    eng = _engine()
    try:
        eng.submit([3, 7, 1], 2).tokens()   # every shape has run once
        spans = _traced(
            tmp_path, lambda: eng.submit([3, 7, 1, 4], 5).tokens())
    finally:
        eng.close(drain=False)
    admit, = _named(spans, "mx.decode.admit")
    assert [c.name for c in _children(spans, admit)] == [
        "mx.decode.queue_wait", "mx.decode.prefill"]
    wait, prefill = _children(spans, admit)
    assert wait.attrs["waited_us"] >= 0
    assert prefill.attrs["bucket"] == 8 and prefill.attrs["prompt"] == 4
    assert [c.name for c in _children(spans, prefill)] == [
        "mx.decode.prefill.build", "mx.decode.prefill.call",
        "mx.decode.prefill.emit"]
    # five tokens: one from the prefill, four decode steps, ONE span each
    steps = _named(spans, "mx.decode.step")
    assert len(steps) == 4
    for s in steps:
        assert (s.attrs["n"], s.attrs["bucket"], s.attrs["max_slots"]) \
            == (1, 1, 2)
        assert [c.name for c in _children(spans, s)] == [
            "mx.decode.step.build", "mx.decode.step.call",
            "mx.decode.step.emit"]
    assert {s.line for s in spans} == {admit.line}      # the engine's thread
    assert not [s for s in spans if "decode_step" in s.name]
    # the first step after the engine ran empty had nothing before it;
    # each of the others was dispatched behind its unfetched predecessor
    assert [s.attrs["overlapped"] for s in sorted(
        steps, key=lambda s: s.start)] == [0, 1, 1, 1]


def _a_prefill_beside_a_running_stream(tmp_path):
    """The ``mx.`` spans of a trace in which one request is admitted
    while another decodes."""
    from mxnet_tpu import chaos
    eng = _engine()
    try:
        eng.submit([3, 7, 1], 2).tokens()   # every shape has run once

        def work():
            with chaos.scenario(seed=0):
                chaos.on("serving.decode.step",
                         action=lambda ctx: time.sleep(0.005))
                first = eng.submit([3, 7, 1, 4], 12)
                next(first)
                next(first)     # a step's token: its successor is in flight
                assert len(eng.submit([5, 5, 6], 3).tokens()) == 3
                first.tokens()

        return _traced(tmp_path, work)
    finally:
        eng.close(drain=False)


def test_a_prefill_beside_running_streams_holds_their_next_step(tmp_path):
    """A request admitted while another decodes: its prefill is
    dispatched, the running stream's next step behind it, and only then
    is the first token waited for -- two ``.call`` spans with the step
    between them, neither holding the other (a reader that adds up the
    ``.call`` spans counts no moment twice)."""
    spans = _a_prefill_beside_a_running_stream(tmp_path)
    lone, beside = sorted(_named(spans, "mx.decode.prefill"),
                          key=lambda s: s.start)
    assert [c.name for c in _children(spans, lone)] == [
        "mx.decode.prefill.build", "mx.decode.prefill.call",
        "mx.decode.prefill.emit"]
    kids = _children(spans, beside)
    assert [c.name for c in kids] == [
        "mx.decode.prefill.build", "mx.decode.prefill.call",
        "mx.decode.step", "mx.decode.prefill.call",
        "mx.decode.prefill.emit"]
    step = kids[2]
    assert (step.attrs["n"], step.attrs["overlapped"]) == (1, 1)
    assert [c.name for c in _children(spans, step)] == [
        "mx.decode.step.build", "mx.decode.step.call",
        "mx.decode.step.emit"]
    # the joiner steps from the turn after that one
    later = [s for s in _named(spans, "mx.decode.step")
             if s.start > beside.end]
    assert later[0].attrs["n"] == 1 and later[1].attrs["n"] == 2


def test_a_prefill_says_whom_it_held_and_the_step_behind_it_says_so(
        tmp_path):
    """``live`` and ``behind`` on ``mx.decode.prefill``: the streams with
    a token still to dispatch and whether a decode step was in flight at
    its dispatch.  ``after_prefill`` on ``mx.decode.step``: 1 on the span
    of the step that ran BEHIND the prefill (the one whose tokens arrive a
    prefill late), not on the turn that dispatched it."""
    spans = _a_prefill_beside_a_running_stream(tmp_path)
    lone, beside = sorted(_named(spans, "mx.decode.prefill"),
                          key=lambda s: s.start)
    assert (lone.attrs["live"], lone.attrs["behind"]) == (0, 0)
    assert (beside.attrs["live"], beside.attrs["behind"]) == (1, 1)
    assert (beside.attrs["bucket"], beside.attrs["prompt"]) == (8, 3)
    steps = sorted(_named(spans, "mx.decode.step"), key=lambda s: s.start)
    nested, = [s for s in steps
               if beside.start <= s.start and s.end <= beside.end]
    # the nested turn delivers the step that was in flight BEFORE the
    # prefill; the step it dispatched is delivered by the next span
    assert nested.attrs["after_prefill"] == 0
    held = steps[steps.index(nested) + 1]
    assert held.start > beside.end and held.attrs["after_prefill"] == 1
    assert [s.attrs["after_prefill"] for s in steps
            if s is not held] == [0] * (len(steps) - 1)


def test_a_step_dispatched_by_a_prefills_own_turn_runs_behind_it(tmp_path):
    """Nothing in flight and a stream to step (the first request's prefill
    has just ended, the second is admitted in the same pass): the second
    prefill's turn dispatches the first stream's step behind the prefill,
    and that step's own span carries ``after_prefill``; its successor,
    dispatched in the same turn, does not."""
    eng = DecodeEngine(MODEL, MODEL.init_params(0), **ENGINE_KW)
    eng.warmup()
    try:
        first = eng.submit([3, 7, 1], 4)
        second = eng.submit([5, 5, 6], 2)   # both pending before the loop

        def work():
            eng.start()
            assert len(first.tokens()) == 4 and len(second.tokens()) == 2

        spans = _traced(tmp_path, work)
    finally:
        eng.close(drain=False)
    lone, beside = sorted(_named(spans, "mx.decode.prefill"),
                          key=lambda s: s.start)
    assert (lone.attrs["live"], lone.attrs["behind"]) == (0, 0)
    assert (beside.attrs["live"], beside.attrs["behind"]) == (1, 0)
    steps = sorted(_named(spans, "mx.decode.step"), key=lambda s: s.start)
    assert beside.start <= steps[0].start and steps[0].end <= beside.end
    assert [s.attrs["after_prefill"] for s in steps] \
        == [1] + [0] * (len(steps) - 1)
    assert steps[0].attrs["overlapped"] == 0


@pytest.mark.parametrize("route", ["plain", "exported"])
def test_a_compiled_serving_program_bears_its_kind_and_bucket(route,
                                                              tmp_path):
    """Both routes of ``compile_through`` compile a module called
    ``jit_mx_<kind>_b<bucket>``: what a device trace calls the program's
    executions, and what ``obs.program_scopes()`` says of each label."""
    from mxnet_tpu.serving.cache import CompileCache
    cache = CompileCache(str(tmp_path)) if route == "exported" else None
    label = "named_" + route
    eng = DecodeEngine(MODEL, MODEL.init_params(0), label=label, cache=cache,
                       **ENGINE_KW)
    eng.warmup()
    noted = obs.program_scopes()
    assert {k: v["module"] for k, v in noted.items()
            if k.startswith(label + ":")} == {
        label + ":prefill:8": "jit_mx_prefill_b8",
        label + ":decode:1": "jit_mx_decode_b1",
        label + ":decode:2": "jit_mx_decode_b2"}
    for kind, bucket in (("prefill", 8), ("decode", 1), ("decode", 2)):
        head = eng._programs.get((kind, bucket)).as_text().split("\n")[0]
        assert head.startswith("HloModule jit_mx_%s_b%d," % (kind, bucket))
    exported = any("call_exported" in op for op in
                   noted[label + ":decode:2"]["scopes"].values())
    assert exported == (route == "exported")
    # the names are the wrapper's alone: the scopes inside are as they were
    assert any("/h0/kv_write/" in op for op in
               noted[label + ":decode:2"]["scopes"].values())
    # a second engine reads the artifacts back under the same names
    if cache is not None:
        again = DecodeEngine(MODEL, MODEL.init_params(0), label=label + "2",
                             cache=cache, **ENGINE_KW)
        again.warmup()
        assert again._programs.fingerprints == eng._programs.fingerprints
        assert obs.program_scopes()[label + "2:prefill:8"]["module"] \
            == "jit_mx_prefill_b8"


def test_compile_throughs_other_caller_keeps_its_module_name(tmp_path):
    """``name`` is optional: ``BucketExecutorPool`` leaves it out and its
    export wrapper is the module it was."""
    import jax.numpy as jnp
    from mxnet_tpu.profiling.hlo import module_name
    from mxnet_tpu.serving.cache import (CompileCache, compile_through,
                                         named, stablehlo_fingerprint)
    specs = (jax.ShapeDtypeStruct((4,), jnp.float32),)
    cache = CompileCache(str(tmp_path))
    for name, want in ((None, "jit_call"), ("mx_x_b4", "jit_mx_x_b4")):
        jfn = jax.jit(named(lambda x: x * 2, name) if name
                      else (lambda x: x * 2))
        lowered = jfn.lower(*specs)
        call = compile_through(cache, stablehlo_fingerprint(
            lowered.as_text()), jfn, lowered, specs, name=name)
        assert module_name(call.as_text()) == want
        assert float(call(jnp.ones((4,), jnp.float32))[0]) == 2.0


def test_the_prefill_counters_add_up_to_the_buckets():
    """``decode.prefill.tokens`` + ``decode.prefill.padded_tokens`` is the
    tokens the device computed: a bucket a prefill."""
    from mxnet_tpu import telemetry
    telemetry.enable()
    telemetry.reset("decode.")
    eng = _engine()
    try:
        for prompt in ([3, 7, 1], [1, 2, 3, 4, 5, 6, 7, 8], [9]):
            eng.submit(prompt, 2).tokens()
        reg = telemetry.registry()
        assert reg.counter("decode.prefills").value == 3
        assert reg.counter("decode.prefill.tokens").value == 3 + 8 + 1
        assert reg.counter("decode.prefill.padded_tokens").value \
            == 3 * 8 - (3 + 8 + 1)
    finally:
        eng.close(drain=False)
        telemetry.reset("decode.")
        telemetry.disable()


def test_the_step_spans_and_counters_say_how_often_the_loop_overlaps():
    """``overlapped`` on every ``mx.decode.step`` span and the counters
    ``decode.steps_overlapped`` / ``decode.tokens_discarded`` beside
    ``decode.steps``: two streams, one of which ends by EOS with the next
    step dispatched."""
    from mxnet_tpu import telemetry
    params = MODEL.init_params(0)
    ref = MODEL.reference_decode(params, [1, 2, 3, 4], 8)
    eos = ref[1]                            # [4, 6, 6, ...]: ends at two
    assert eos != ref[0]
    telemetry.enable()
    telemetry.reset("decode.")
    obs.enable_tracing()
    eng = _engine()
    try:
        long = eng.submit([3, 7, 1], 6)
        short = eng.submit([1, 2, 3, 4], 8, eos_id=eos)
        assert short.tokens() == ref[:2] and len(long.tokens()) == 6
        eng.close(drain=True)
        steps = sorted((s for s in obs.spans()
                        if s["name"] == "mx.decode.step"),
                       key=lambda s: s["t0"])
        reg = telemetry.registry()
        assert reg.counter("decode.steps").value == len(steps)
        flags = [s["attrs"]["overlapped"] for s in steps]
        assert set(flags) <= {0, 1} and flags[0] == 0
        assert reg.counter("decode.steps_overlapped").value == sum(flags)
        assert sum(flags) >= len(steps) - 2
        # the step dispatched before the EOS reached the host carried the
        # ended stream: one token nobody gets
        assert reg.counter("decode.tokens_discarded").value == 1
        assert reg.counter("decode.tokens").value \
            == sum(s["attrs"]["n"] for s in steps) - 1 == (6 - 1) + (2 - 1)
    finally:
        eng.close(drain=False)
        obs.disable_tracing()
        telemetry.reset("decode.")
        telemetry.disable()


def test_with_no_session_and_tracing_off_a_site_makes_no_ring_call(
        monkeypatch):
    """The zero-overhead test's way (tests/test_obs.py): count every call
    into the ring's surface while the train loop, the feed and the engine
    run with tracing off.  The sites call ``obs.span`` and nothing else,
    and ``obs.span`` records nothing."""
    calls = []
    for name in ("begin_span", "end_span", "record_span", "fresh_context"):
        orig = getattr(obs.trace, name)

        def counted(*a, _name=name, _orig=orig, **kw):
            calls.append(_name)
            return _orig(*a, **kw)

        monkeypatch.setattr(obs.trace, name, counted)
        if hasattr(obs, name):
            monkeypatch.setattr(obs, name, counted)

    def work():
        _train_loop(_train_step(), 2)
        eng = _engine()
        try:
            assert len(eng.submit([3, 7, 1], 3).tokens()) == 3
        finally:
            eng.close(drain=False)

    work()
    assert calls == [] and obs.spans() == []
    obs.enable_tracing()
    work()
    assert {"begin_span", "end_span", "record_span",
            "fresh_context"} <= set(calls)
    names = {s["name"] for s in obs.spans()}
    assert {"mx.train_step", "mx.feed.stage", "mx.decode.step",
            "mx.decode.prefill.call", "serving.request"} <= names


def test_the_ring_keeps_parents_and_the_chrome_export_holds_them():
    obs.enable_tracing()
    _train_loop(_train_step(), 2)
    by = collections.defaultdict(list)
    for s in obs.spans():
        by[s["name"]].append(s)
    steps = by["mx.train_step"]
    assert len(steps) == 2
    for part in ("prep", "dispatch", "rebind"):
        assert [s["parent"] for s in by["mx.train_step." + part]] \
            == [s["span"] for s in steps]
    assert by["mx.feed.stage"][0]["attrs"]["bytes"] == 80
    doc = obs.export_chrome_trace()
    events = {e["name"]: e for e in doc["traceEvents"]}
    assert events["mx.train_step"]["ph"] == "X"
    assert events["mx.train_step"]["args"]["items"] == 4
    assert events["mx.train_step.dispatch"]["args"]["parent"] \
        == events["mx.train_step"]["args"]["span"]
    assert doc["otherData"]["producer"] == "mxnet_tpu.obs.trace"


def test_a_span_that_began_on_another_thread_counts_from_there():
    import time
    obs.enable_tracing()
    t_submit = time.perf_counter() - 0.25
    with obs.span("mx.decode.queue_wait", since=t_submit, links=["abc"]):
        pass
    rec, = obs.spans()
    assert rec["t0"] == t_submit and 0.25 <= rec["dur"] < 1.0
    assert rec["links"] == ["abc"]


def _lowered_text(fn, shapes):
    # what the compiler is given: every op with the scopes it was traced
    # under (the compiled text carries them as op_name metadata, but a
    # hit in the persistent compile cache returns the text of whichever
    # process compiled the program first)
    return fn.lower(*shapes).as_text(debug_info=True)


def test_the_compiled_train_step_carries_the_scopes():
    step = _train_step()
    step(mx.nd.ones((4, 3)), mx.nd.ones((4, 2)))
    text = _lowered_text(*step._last_call)
    for scope in ("jvp(mx.loss)/", "transpose(jvp(mx.loss))/",
                  "mx.finite_check/", "mx.optimizer/", "jvp(body)/",
                  "jvp(head)/", "transpose(jvp(head))/"):
        assert scope in text, scope
    compiled = step._last_call[0].lower(*step._last_call[1]).compile()
    assert 'op_name="jit(step_fn)/' in compiled.as_text()


def test_the_scan_body_carries_the_scopes():
    step = _train_step()
    step.run_steps(mx.nd.ones((2, 4, 3)), mx.nd.ones((2, 4, 2)))
    text = _lowered_text(*step._last_call)
    for scope in ("jvp(mx.loss)/", "mx.finite_check/", "mx.optimizer/",
                  "jvp(body)/"):
        assert scope in text, scope


def test_the_decode_and_prefill_programs_carry_the_scopes():
    eng = DecodeEngine(MODEL, MODEL.init_params(0), **ENGINE_KW)
    prefill, decode = eng._specs()
    text = _lowered_text(jax.jit(eng._decode_impl), decode[2])
    for scope in ("mx.embed/", "h0/qkv/", "h0/kv_write/", "h1/kv_write/",
                  "h0/attention/", "h0/proj/", "h1/mlp/", "mx.lm_head/"):
        assert scope in text, scope
    text = _lowered_text(jax.jit(eng._prefill_impl), prefill[8])
    for scope in ("mx.embed/", "h1/attention/", "mx.kv_scatter/",
                  "mx.lm_head/"):
        assert scope in text, scope


def test_an_eager_call_enters_no_scope(monkeypatch):
    """Scopes are trace-time only: an eager forward of a block and its
    children never reaches ``jax.named_scope``."""
    entered = []
    real = jax.named_scope

    def counted(name):
        entered.append(name)
        return real(name)

    net = TwoLayer()
    net.initialize()
    monkeypatch.setattr(jax, "named_scope", counted)
    net(mx.nd.ones((4, 3))).asnumpy()
    assert entered == []
    net.hybridize()
    net(mx.nd.ones((4, 3))).asnumpy()       # traced: children named
    assert entered and set(entered) == {"body", "head"}
    traced = list(entered)
    net(mx.nd.ones((4, 3))).asnumpy()       # cached: nothing more
    assert entered == traced


# -- the program's scope maps, for a reader of a device trace -----------------

def test_program_scopes_map_instruction_names_to_op_names():
    """A device trace names an executed instruction and not its scope:
    the program notes each program it compiles and maps instruction name
    -> op_name from the compiled text."""
    step = _train_step()
    step(mx.nd.ones((4, 3)), mx.nd.ones((4, 2)))
    eng = DecodeEngine(MODEL, MODEL.init_params(0), label="spans_test",
                       **ENGINE_KW)
    eng.warmup()
    noted = obs.program_scopes()
    train = noted["train_step:TwoLayer"]
    assert train["module"] == "jit_step_fn"
    names = set(train["scopes"].values())
    assert any("/mx.optimizer/" in n for n in names)
    assert any("/mx.finite_check/" in n for n in names)
    assert any("jvp(head)" in n for n in names)
    decode = noted["spans_test:decode:2"]
    assert any("/h0/kv_write/" in n for n in decode["scopes"].values())
    assert "spans_test:prefill:8" in noted
    # every name is an instruction of the compiled text
    text = step._last_call[0].lower(*step._last_call[1]).compile().as_text()
    assert all(("%" + name + " ") in text for name in train["scopes"])


def test_noted_programs_are_bounded_and_the_newest_kept():
    cap = obs.trace._MAX_PROGRAMS
    for i in range(cap + 5):
        obs.note_program("bounded_test:%d" % i, lambda: "HloModule m\n")
    labels = [k for k in obs.trace._programs if k.startswith("bounded_test")]
    assert len(obs.trace._programs) <= cap
    assert labels[-1] == "bounded_test:%d" % (cap + 4)
    assert "bounded_test:0" not in labels
    # a program whose text cannot be had is left out, not raised
    obs.note_program("bounded_test:broken", lambda: 1 / 0)
    assert "bounded_test:broken" not in obs.program_scopes()
    for k in [k for k in obs.trace._programs if k.startswith("bounded_")]:
        del obs.trace._programs[k]


HLO_TEXT = """HloModule jit_step_fn, is_scheduled=true

%fused_computation.1 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %mul.1 = f32[8]{0} multiply(%p, %p), metadata={op_name="jit(step_fn)/mx.optimizer/mul" stack_frame_id=4}
  ROOT %add.2 = f32[8]{0} add(%mul.1, %p), metadata={op_name="jit(step_fn)/mx.optimizer/add" stack_frame_id=5}
}

%fused_computation.2 (p: f32[8]) -> f32[8] {
  %p.1 = f32[8]{0} parameter(0)
  %exp.1 = f32[8]{0} exponential(%p.1), metadata={op_name="jit(step_fn)/jvp(mx.loss)/exp"}
  ROOT %neg.2 = f32[8]{0} negate(%exp.1), metadata={op_name="jit(step_fn)/mx.optimizer/neg"}
}

ENTRY %main.1 (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0), metadata={op_name="x"}
  %fusion.1 = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation.1
  %fusion.2 = f32[8]{0} fusion(%fusion.1), kind=kLoop, calls=%fused_computation.2
  %copy.3 = f32[8]{0} copy(%fusion.2)
  ROOT %fusion.4 = f32[8]{0} fusion(%copy.3), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step_fn)/mx.finite_check/and"}
}
"""


def test_scope_map_of_a_compiled_text():
    from mxnet_tpu.profiling import hlo
    assert hlo.module_name(HLO_TEXT) == "jit_step_fn"
    scopes = hlo.scope_map(HLO_TEXT)
    assert scopes["fusion.4"] == "jit(step_fn)/mx.finite_check/and"
    assert scopes["mul.1"] == "jit(step_fn)/mx.optimizer/mul"
    # what the compiler left without metadata is left out, not guessed
    assert not {"fusion.1", "fusion.2", "copy.3"} & set(scopes)


def test_the_noted_programs_compile_with_their_scopes_in_the_cache_key():
    """JAX leaves op names out of its persistent cache's key by default,
    so a cached executable may carry another version's scopes into a
    trace.  The programs a trace is read by are compiled with them in
    (and with source paths relative to the checkout); everything else
    keeps the default, under which the per-layer programs of an eager
    pass share one entry."""
    import re
    from mxnet_tpu.base import scopes_in_cache_key
    name = "jax_compilation_cache_include_metadata_in_key"
    assert getattr(jax.config, name) is False
    seen = []
    real = jax.config.update

    def spy(key, value):
        if key == name:
            seen.append(value)
        return real(key, value)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax.config, "update", spy)
        step = _train_step()
        step(mx.nd.ones((4, 3)), mx.nd.ones((4, 2)))    # builds: in the key
        assert seen == [True, False]
        step(mx.nd.ones((4, 3)), mx.nd.ones((4, 2)))    # cached: untouched
        assert seen == [True, False]
        eng = DecodeEngine(MODEL, MODEL.init_params(0), **ENGINE_KW)
        eng.warmup()                                    # three programs
        assert seen == [True, False] * 4
    with scopes_in_cache_key():
        assert getattr(jax.config, name) is True
    assert getattr(jax.config, name) is False
    pattern = jax.config.jax_hlo_source_file_canonicalization_regex
    root = os.path.dirname(os.path.dirname(os.path.abspath(mx.__file__)))
    inside = os.path.join(root, "mxnet_tpu", "gluon", "block.py")
    assert re.sub(pattern, "", inside) == os.path.join(
        "mxnet_tpu", "gluon", "block.py")
    assert re.sub(pattern, "", "/elsewhere/train.py") == "/elsewhere/train.py"
