"""Device time a train step spends in the optimizer update: self time of
the ``XLA Ops`` events scoped under ``mx.optimizer`` (``TrainStep``'s
update loop with its ``where``-selects), mean over the whole steps of the
traced window."""
from perfbench.harness import program_trace


def read(run):
    view = program_trace.load(run)
    return None if view is None else view.scoped_ms(r"^mx\.optimizer(/|$)")
