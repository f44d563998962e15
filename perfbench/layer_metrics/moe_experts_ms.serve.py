"""Device time a decode step spends in its expert layers' feed-forward:
self time of the ``XLA Ops`` events scoped under ``h<i>/router``,
``h<i>/experts`` (the sort, the gathers and the scatter-add around the
grouped matmul over the experts held) and ``h<i>/shared_expert``, and of
the grouped matmuls themselves, summed over the layers, mean over the
decode steps that lie whole inside the traced window.  The TPU compiler
turns ``jax.lax.ragged_dot`` into a kernel of its own whose ``op_name`` is
``ragged-dot-none`` and no longer the scope it was traced under (3.3 of a
step's 11.6 device ms were ``unscoped`` for that; my chip runs, PR 28), so
those are found by their instruction's name: only the expert layers issue
them.  A program without such scopes has nothing to read."""
import re

from perfbench.harness import program_trace

SCOPES = re.compile(r"(^|/)(router|experts|shared_expert)(/|$)")
GROUPED_MATMUL = re.compile(r"^ragged-dot")


def read(run):
    view = program_trace.load(run)
    if view is None or not view.steps:
        return None
    found = [ns for op, ns in view.timed()
             if (op.scope and SCOPES.search("/".join(op.scope)))
             or (not op.scope and GROUPED_MATMUL.match(op.name))]
    return sum(found) / len(view.steps) / 1e6 if found else None
