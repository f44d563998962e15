"""Device time a train step spends deciding whether every gradient is
finite: self time of the ``XLA Ops`` events scoped under
``mx.finite_check`` (``analysis/numerics.py::finite_tree`` inside the
compiled step), mean over the whole steps of the traced window."""
from perfbench.harness import program_trace


def read(run):
    view = program_trace.load(run)
    return None if view is None \
        else view.scoped_ms(r"^mx\.finite_check(/|$)")
