"""Median duration of the train step's compiled program on device 0, from
the device trace's ``XLA Modules`` line.  The configuration's
``trace.step_module`` is the pattern that finds the program."""
from perfbench.harness import stats, xplane


def read(run):
    if run.trace is None:
        return None
    runs = xplane.module_runs(run.trace.events, run.trace.devices[0],
                              run.cfg["trace"]["step_module"])
    if not runs:
        return None
    return stats.median([e.dur_ns for e in runs]) / 1e6
