"""Passes through the stack of layers that one emitted token costs: the
``ut_passes`` attribute of the ``mx.decode.step`` spans that lie whole
inside the traced window (the count the decode program of a model that
runs its layers several times returns beside its tokens -- passes run for
the step's live slots -- and the engine puts on the span; the same number
it adds to the counter ``decode.ut.passes``), over those steps' live
slots (``n``: each emits one token).  ``total_ut_steps`` while every pass
is run for every token; what a change that skips the passes a token's gate
has made needless would move, with ``exit_early`` beside it on the
measurement line.  A program that returns no such count has nothing to
read."""
from perfbench.harness import program_trace


def read(run):
    view = program_trace.load(run)
    if view is None:
        return None
    steps = [s.attrs for s, ns in view.in_window("mx.decode.step")
             if ns == s.dur_ns and "ut_passes" in s.attrs]
    tokens = sum(float(a["n"]) for a in steps)
    if not tokens:
        return None
    passes = sum(float(a["ut_passes"]) for a in steps)
    run.log.measurement(
        "ut_passes", steps=len(steps), tokens=tokens, ut_passes=passes,
        exit_early=sum(float(a.get("exit_early", 0)) for a in steps),
        exit_step_mean=sum(float(a.get("exit_step_sum", 0))
                           for a in steps) / tokens)
    return passes / tokens
