"""Device time a train step spends in the model's output head and the
loss, forward and backward: self time of the ``XLA Ops`` events whose
scope lies under ``mx.loss`` (``TrainStep``'s scope around the loss
function) or under a child block of the model named after its head
(BERT's ``mlm_transform``, ``mlm_ln``, ``mlm_decoder``), mean over the
whole steps of the traced window.  The configuration may name its head's
scopes in ``trace.loss_head``."""
from perfbench.harness import program_trace

LOSS_HEAD = r"(^|/)(mx\.loss|mlm_[a-z]+|lm_head|output_head)(/|$)"


def read(run):
    view = program_trace.load(run)
    if view is None:
        return None
    return view.scoped_ms(run.cfg.get("trace", {}).get("loss_head",
                                                       LOSS_HEAD))
