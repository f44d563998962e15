"""Median duration of the decode step's compiled program on device 0: the
executions called ``jit_mx_decode_b<bucket>`` on the device trace's ``XLA
Modules`` line that lie whole inside the traced window.  The serving twin of
``step_device_ms.train``; ``decode_step_ms.serve`` is the host loop's period
around it.  A program that does not name its serving programs has nothing
to read."""
from perfbench.harness import serve_programs, stats


def read(run):
    steps = serve_programs.executions(run, "decode")
    if not steps:
        return None
    return stats.median([e.dur_ns for e in steps]) / 1e6
