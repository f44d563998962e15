"""The engine thread's time a decode step outside a device call: from
the start of the first ``mx.decode.step`` span of the traced window to
the end of the last, the time not inside a ``mx.decode.step.call`` or
``mx.decode.prefill.call`` span (dispatch to the return of
``device_get``), over the decode steps in between.  It holds the batch
building, the emit loop, admission and any wait for work.  (The step in
flight when the profiler starts is not in the trace: a span that began
before the session is dropped, so the stretch is counted from the first
step the trace holds.)  What the device idles inside a call, while the
program is launched and the tokens are fetched, is not here: the
``idle_by_program_span`` line splits the idle time by span."""
from perfbench.harness import program_trace


def read(run):
    view = program_trace.load(run)
    if view is None:
        return None
    steps = [s for s, ns in view.in_window("mx.decode.step")
             if ns == s.dur_ns]
    if not steps:
        return None
    stretch = (min(s.start_ns for s in steps),
               max(s.start_ns + s.dur_ns for s in steps))
    in_call = sum(ns for name in ("mx.decode.step.call",
                                  "mx.decode.prefill.call")
                  for _s, ns in program_trace.clipped(view.named(name),
                                                      stretch))
    return (stretch[1] - stretch[0] - in_call) / len(steps) / 1e6
