"""What the host spends on one train step: median duration of the
program's ``mx.train_step`` span (``TrainStep.__call__`` from its first
line to its return: preparation, dispatch, rebinding) over the spans
inside the traced window.  Where it nears ``step_device_ms.train`` the
host sets the pace."""
from perfbench.harness import program_trace, stats


def read(run):
    view = program_trace.load(run)
    if view is None:
        return None
    whole = [s.dur_ns for s, ns in view.in_window("mx.train_step")
             if ns == s.dur_ns]
    return stats.median(whole) / 1e6 if whole else None
