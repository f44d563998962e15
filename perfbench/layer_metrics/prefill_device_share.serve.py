"""Share of the traced window in which device 0 ran a prefill: the device time
of the executions called ``jit_mx_prefill_b<bucket>`` (``XLA Modules``),
clipped to the window, over the window.  With the decode executions' share
and ``device_idle_share.serve`` it comes to the whole window; the
``prefill_programs`` line prints the three and their sum.  A window that
admitted nothing reads 0; a program that does not name its serving programs
has nothing to read."""
from perfbench.harness import serve_programs


def read(run):
    found = serve_programs.load(run)
    return None if found is None else found.share(found.prefill_ns)
