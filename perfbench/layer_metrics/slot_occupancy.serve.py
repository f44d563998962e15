"""How full the decode batch ran: time-weighted mean over the traced
window of ``n / max_slots``, the live sequences of a step over the
engine's largest decode bucket, from the attributes of the program's
``mx.decode.step`` spans."""
from perfbench.harness import program_trace


def read(run):
    view = program_trace.load(run)
    if view is None:
        return None
    return program_trace.occupancy(view.named("mx.decode.step"),
                                   view.window)
