"""Device time of ONE pass through the stack of layers in a decode step of
a model that runs its layers several times: the summed duration of the
pass loops in the decode program's executions that lie whole inside the
traced window, over those executions and over ``total_ut_steps``.

Such a model makes the passes a loop of its compiled programs, under the
scope ``mx.ut_loop`` (``mxnet_tpu/serving/decode/looped.py``).  On the
device's ops line the loop is one ``while`` event an execution, scoped
``mx.ut_loop``, with the instructions of every pass nested inside its
interval: the layers' own (``mx.ut_loop/while/body/closed_call/h<i>/qkv``
...) and what the compiler added without a scope (the waits for the
weights it fetches ahead).  The decode program's executions are found
through the programs the trace's modules were matched to
(``program_trace``'s ``matched``: ``<model>:decode:<bucket>``), not
through ``program_trace``'s step intervals, which take a step to be a
``.call`` span and read low since one step is always in flight (PERF.md
section 7).

A line beside the value splits a pass by the part of a layer and gives
the floor under it: the least time for the layer weights' bytes, which a
pass has to read whatever its batch (the family's ``loop_weight_bytes``),
at the chip's published bandwidth.  That floor is on the line and not a
metric of its own because no part of the loop owns the weight stream: the
compiler fetches each weight ahead of its matmul into VMEM, under the
attention kernel too, so over the matmuls and the waits the stream read
102.3% of the bandwidth and over the loop less its attention 100.4% (my
chip runs, PR 34), and over the whole loop it is this metric again.  A
program without the loop's scope has nothing to read."""
import collections

from perfbench.harness import program_trace, xplane
from perfbench.harness.peaks import device_peaks

LOOP_SCOPE = "mx.ut_loop"


def decode_loops(run):
    """``(executions, loop_ns, timed)`` of the traced window, or None
    where the trace has none: the decode program's executions that lie
    whole inside the window, the summed duration of their pass loops (the
    ``while`` events scoped ``mx.ut_loop`` alone), and the ``(op, self
    ns)`` pairs of every op inside those loops."""
    view = program_trace.load(run)
    if view is None or run.trace is None:
        return None
    tr = run.trace
    t0, t1 = view.window
    decode = {module for module, (label, _seen, _known)
              in view.matched.items() if label and ":decode:" in label}
    runs = [(e.start_ns, e.start_ns + e.dur_ns) for e in xplane.on_device(
        tr.events, tr.devices[0], xplane.MODULES_LINE)
        if e.name in decode and e.start_ns >= t0
        and e.start_ns + e.dur_ns <= t1]
    loops = [op for op in program_trace.inside(view.ops, runs)
             if op.scope == (LOOP_SCOPE,)]
    if not runs or not loops:
        return None
    within = program_trace.inside(
        view.ops, [(op.start_ns, op.start_ns + op.dur_ns) for op in loops])
    return (len(runs), sum(op.dur_ns for op in loops),
            [(op, ns) for op, ns in program_trace.self_times(within)
             if op.scope != (LOOP_SCOPE,)])


def part_of(op):
    """The part of a layer an op inside the loop belongs to (``qkv``,
    ``attention_full``, ...: the component after ``h<i>``), its own scope
    for what follows the layers (``mx.final_norm``, ``mx.exit_gate``), the
    loop's for the loop's own bookkeeping, ``unscoped`` for what the
    compiler added."""
    if not op.scope:
        return program_trace.UNSCOPED
    for i, part in enumerate(op.scope[:-1]):
        if part[:1] == "h" and part[1:].isdigit():
            return op.scope[i + 1]
    last = op.scope[-1]
    return last if last.startswith("mx.") else LOOP_SCOPE


def read(run):
    loops = decode_loops(run)
    passes = run.cfg.get("total_ut_steps")
    if loops is None or not passes:
        return None
    executions, loop_ns, timed = loops
    a_pass = executions * passes * 1e6              # ns -> ms a pass
    by_part = collections.Counter()
    for op, ns in timed:
        by_part[part_of(op)] += ns
    weight_bytes = getattr(run.family, "loop_weight_bytes", None)
    run.log.measurement(
        "pass_loop", executions=executions, passes=passes,
        loop_ms_a_step=loop_ns / executions / 1e6,
        by_part_ms_a_pass={k: v / a_pass
                           for k, v in by_part.most_common()},
        weights_least_ms_a_pass=None if weight_bytes is None else
        1e3 * weight_bytes(run.cfg) / device_peaks(run.stamp["kind"])[1])
    return loop_ns / a_pass
