"""How long an admitted request had waited in the engine's queue: the
median, in ms, of ``waited_us`` over the program's ``mx.decode.queue_wait``
spans of the traced window (one a request, written as it is admitted:
submit to admission, so a request that finds a free slot still waits for
the step boundary).  Only an open loop has a queue.  The median, because a
4 s window at 8 requests/s holds some 32 admissions and supports no higher
percentile: their 95th read 6 to 192 ms between runs of one program
(PERF.md, PR 27).  Left out below twenty."""
from perfbench.harness import program_trace, stats


def read(run):
    view = program_trace.load(run)
    if view is None:
        return None
    waited = [float(s.attrs["waited_us"]) / 1e3
              for s, _ns in view.in_window("mx.decode.queue_wait")
              if "waited_us" in s.attrs]
    if len(waited) < 20:
        return None
    return stats.median(waited)
