"""The device's time and the host's idle time in the program's own terms.

The program (``mxnet_tpu``) names its work in two ways that reach any
``jax.profiler`` trace of the process:

* **scopes** inside the compiled programs (``jax.named_scope``:
  ``mx.loss``, ``mx.optimizer``, ``encoder/cell3/attention``,
  ``h7/kv_write``), which XLA keeps as the ``op_name`` of every
  instruction it derives from the traced operation;
* **spans** on the host (``mxnet_tpu.obs.span``: ``mx.train_step``,
  ``mx.decode.step.call``, ``mx.feed.stage``), which are
  ``jax.profiler.TraceAnnotation`` events with their attributes.

This module opens the traced run's ``.xplane.pb`` once more (the
harness's own reader keeps neither an event's stats nor a host span that
is not the benchmark's), gives every ``XLA Ops`` event of device 0 its
scope and keeps the ``mx.`` host spans with their attributes.  The
result is cached on the run; the first reader that asks prints the two
lines ``device_by_scope`` and ``idle_by_program_span``.  Every reduction
works on plain tuples and is tested on hand-built ones.

Where an op's scope comes from (PERF.md, PR 25): on the v5e an ``XLA Ops``
event carries no stat with the instruction's ``op_name`` and its name is
the instruction's text without ``metadata={...}``, so the scope comes from
the program's own map from instruction name to ``op_name``, which it makes
from the compiled text of the programs it built
(``mxnet_tpu.obs.program_scopes``); the event is matched to a program
through the ``XLA Modules`` execution it runs inside.  An instruction the
compiler made itself (a layout copy, a rematerialised fusion) has no
``op_name`` and is ``unscoped``: it is never guessed into a phase.

A program without scopes or spans (the parent of the PR that added
them) gives a trace without them: ``load`` then returns a view whose
readers find nothing, each returns None, and a line says why.
"""
import collections
import re

from . import stats, xplane

Span = collections.namedtuple("Span", "name line start_ns dur_ns attrs")
Op = collections.namedtuple("Op", "name start_ns dur_ns scope")

SPAN_PREFIX = "mx."
UNSCOPED = "unscoped"
IDLE_DEFAULT = "outside the program's spans"
# jit(f), pjit(f), call_exported: a traced function, not a scope
_FUNCTION = re.compile(r"^(p?jit\(.*\)|call_exported)$")
# jvp(x), transpose(jvp(x)), vmap(x) ...: a transformation of scope x
_TRANSFORM = re.compile(r"^[A-Za-z_]+\((.*)\)$")
_LAYER = re.compile(r"^([A-Za-z_]+?)_?\d+$")


# ----------------------------------------------------------------------
# from an op_name to a scope
# ----------------------------------------------------------------------

def scope_of(op_name):
    """The scope path of an instruction's ``op_name`` as a tuple, () where
    it has none.  ``jit(step_fn)/transpose(jvp(encoder))/cell3/attention/
    jit(f)/dot_general`` -> ``("encoder", "cell3", "attention")``: the
    last component is the primitive, ``jit(...)`` components are function
    names, and a transformation wrapper stands for the scope inside it."""
    if not op_name:
        return ()
    parts = op_name.split(";")[0].split("/")[:-1]
    out = []
    for part in parts:
        while True:
            if _FUNCTION.match(part):
                part = ""
                break
            m = _TRANSFORM.match(part)
            if not m:
                break
            part = m.group(1)
        if part:
            out.append(part)
    return tuple(out)


def starred(scope):
    """``("h7", "kv_write")`` -> ``"h*/kv_write"``: a layer's index gives
    way to a star, so that the layers sum."""
    return "/".join(_LAYER.sub(r"\1*", part) for part in scope)


# ----------------------------------------------------------------------
# reductions on plain tuples
# ----------------------------------------------------------------------

def self_times(ops):
    """``[(op, self_ns)]``: an op's duration less that of the ops nested
    directly inside it.  The ops line shows a ``while`` or ``conditional``
    and, inside its interval, the instructions of its body; sums of self
    times do not count those twice."""
    out, stack = [], []            # stack of [op, end_ns, children_ns]

    def close(until):
        while stack and stack[-1][1] <= until:
            op, _end, children = stack.pop()
            out.append((op, max(op.dur_ns - children, 0.0)))

    for op in sorted(ops, key=lambda o: (o.start_ns, -o.dur_ns)):
        close(op.start_ns)
        if stack:
            stack[-1][2] += op.dur_ns
        stack.append([op, op.start_ns + op.dur_ns, 0.0])
    close(float("inf"))
    return out


def inside(ops, intervals):
    """The ops that start inside one of ``intervals`` = [(start, end)]."""
    intervals = sorted(intervals)
    out, i = [], 0
    for op in sorted(ops, key=lambda o: o.start_ns):
        while i < len(intervals) and intervals[i][1] <= op.start_ns:
            i += 1
        if i < len(intervals) and intervals[i][0] <= op.start_ns:
            out.append(op)
    return out


def by_scope(timed, steps, key=lambda scope: scope[0]):
    """``{key(scope): self ns a step}`` over ``timed`` = ``self_times(ops)``,
    ``unscoped`` for an op without a scope.  ``key`` takes the scope path
    (a non-empty tuple)."""
    total = collections.Counter()
    for op, self_ns in timed:
        total[key(op.scope) if op.scope else UNSCOPED] += self_ns
    return {k: v / steps for k, v in total.items()}


def scoped_ns(timed, steps, pattern):
    """Self ns a step of the ops whose scope path, joined by ``/``,
    matches the regular expression; None where none does."""
    rx = re.compile(pattern)
    found = [ns for op, ns in timed
             if op.scope and rx.search("/".join(op.scope))]
    return sum(found) / steps if found else None


def largest_unscoped(timed, steps, n=3):
    """``[[name, self ms a step]]`` of the ``n`` largest unscoped ops."""
    total = collections.Counter()
    for op, self_ns in timed:
        if not op.scope:
            total[op.name] += self_ns
    return [[name, ns / steps / 1e6] for name, ns in total.most_common(n)]


def clipped(spans, window):
    """``[(span, ns inside the window)]`` for the spans that overlap it."""
    t0, t1 = window
    out = []
    for s in spans:
        ns = min(s.start_ns + s.dur_ns, t1) - max(s.start_ns, t0)
        if ns > 0:
            out.append((s, ns))
    return out


def occupancy(step_spans, window):
    """Time-weighted mean of ``n / max_slots`` over the parts of the step
    spans inside the window, in percent; None without such a span."""
    num = den = 0.0
    for s, ns in clipped(step_spans, window):
        try:
            share = float(s.attrs["n"]) / float(s.attrs["max_slots"])
        except (KeyError, ValueError, ZeroDivisionError):
            continue
        num += share * ns
        den += ns
    return 100.0 * num / den if den else None


def subtract(intervals, cover):
    """The parts of merged ``intervals`` that merged ``cover`` leaves
    bare: ``[(start, end)]``."""
    out, j = [], 0
    cover = list(cover)
    for s, t in intervals:
        at = s
        while j < len(cover) and cover[j][1] <= at:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < t:
            c0, c1 = cover[k]
            if c0 > at:
                out.append((at, c0))
            at = max(at, c1)
            if at >= t:
                break
            k += 1
        if at < t:
            out.append((at, t))
    return out


def as_events(spans):
    """The spans as the ``xplane.Event`` tuples that ``xplane.idle_gaps``
    takes."""
    return [xplane.Event(xplane.HOST_PLANE, s.line, s.name, s.start_ns,
                         s.dur_ns, "") for s in spans]


def exposed_ns(collectives, others):
    """Nanoseconds in which a collective ran and none of ``others`` did."""
    bare = subtract(xplane.union_intervals(collectives),
                    xplane.union_intervals(others))
    return sum(t - s for s, t in bare)


# ----------------------------------------------------------------------
# the view of one traced run
# ----------------------------------------------------------------------

class ProgramTrace:
    """``spans``: the ``mx.`` host spans; ``ops``: device 0's ``XLA Ops``
    events as ``Op`` with their scopes; ``steps``: the intervals on the
    device's clock that hold one step's operations each; ``matched``:
    ``scoped_ops``'s account of which noted program each executed one
    was taken for."""

    def __init__(self, spans, ops, window, matched=None):
        self.spans, self.ops, self.window = spans, ops, window
        self.matched = matched or {}
        self.steps = []
        self._timed = None

    def named(self, name):
        return [s for s in self.spans if s.name == name]

    def in_window(self, name):
        """``[(span, ns inside the window)]`` of the spans called so."""
        return clipped(self.named(name), self.window)

    def timed(self):
        """``self_times`` of the ops inside the whole steps, made once."""
        if self._timed is None:
            self._timed = self_times(inside(self.ops, self.steps))
        return self._timed

    def scoped_ms(self, pattern):
        """Device ms a step under the scopes that match, or None."""
        if not self.steps:
            return None
        ns = scoped_ns(self.timed(), len(self.steps), pattern)
        return None if ns is None else ns / 1e6


def program_of(module, op_names, programs):
    """The label of the noted program that the executions called
    ``module`` (``jit_call(536...)``) ran: of the programs whose HLO
    module bears that name, the one whose map knows most of the
    instruction names seen; None where none knows any."""
    base = module.split("(")[0]
    best, known = None, 0
    for label, prog in sorted(programs.items()):
        if prog.get("module") != base:
            continue
        n = len(op_names & prog["scopes"].keys())
        if n > known:
            best, known = label, n
    return best


def scoped_ops(raw_ops, modules, programs):
    """``([Op], matched)`` from ``raw_ops`` = [(event name, start, dur)],
    ``modules`` = [(start, end, name)] (the device's executions of
    compiled programs) and the program's scope maps.  ``matched`` says,
    for each program executed, which noted program it was taken for and
    how many of the distinct instruction names seen that one's map knows:
    ``{module: [label, seen, known]}``."""
    modules = sorted(modules)
    raw_ops = sorted(raw_ops, key=lambda o: o[1])
    owner, i = [], 0
    for _text, start, _dur in raw_ops:
        while i < len(modules) and modules[i][1] <= start:
            i += 1
        owner.append(modules[i][2] if i < len(modules)
                     and modules[i][0] <= start else None)
    names = [xplane.split_hlo_text(o[0])[0] for o in raw_ops]
    seen = collections.defaultdict(set)
    for name, module in zip(names, owner):
        if module is not None:
            seen[module].add(name)
    chosen = {module: program_of(module, found, programs)
              for module, found in seen.items()}
    ops = []
    for (_text, start, dur), name, module in zip(raw_ops, names, owner):
        label = chosen.get(module)
        op_name = programs[label]["scopes"].get(name) if label else None
        ops.append(Op(name, start, dur, scope_of(op_name)))
    matched = {module: [label, len(seen[module]),
                        len(seen[module] & programs[label]["scopes"].keys())
                        if label else 0]
               for module, label in chosen.items()}
    return ops, matched


def read_profile(profile, window_span, programs=None):
    """(spans, ops, matched, window) of a ``jax.profiler.ProfileData``:
    the host plane's ``mx.`` events with their stats as attributes, the
    events of device 0's ``XLA Ops`` line that start inside the window
    with their scopes, and the window itself, (start_ns, end_ns) of the
    one host span called ``window_span`` (None, and no ops, without
    it).  ``programs`` is ``mxnet_tpu.obs.program_scopes()``."""
    spans, window = [], []
    device0 = None
    for plane in profile.planes:
        m = xplane.DEVICE_PLANE.match(plane.name)
        if m and (device0 is None or int(m.group(1)) < device0[0]):
            device0 = (int(m.group(1)), plane)
        if plane.name != xplane.HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append(Span(ev.name, line.name,
                                      float(ev.start_ns),
                                      float(ev.duration_ns),
                                      dict(ev.stats)))
                elif ev.name == window_span:
                    window.append((float(ev.start_ns),
                                   float(ev.start_ns + ev.duration_ns)))
    window = window[0] if len(window) == 1 else None
    raw_ops, modules = [], []
    if device0 is not None and window is not None:
        t0, t1 = window
        for line in device0[1].lines:
            if line.name == xplane.MODULES_LINE:
                modules = [(float(ev.start_ns),
                            float(ev.start_ns + ev.duration_ns), ev.name)
                           for ev in line.events]
            elif line.name == xplane.OPS_LINE:
                raw_ops = [(ev.name, float(ev.start_ns),
                            float(ev.duration_ns)) for ev in line.events
                           if t0 <= ev.start_ns < t1]
    ops, matched = scoped_ops(raw_ops, modules, programs or {})
    return spans, ops, matched, window


def noted_programs():
    """What the program knows of its compiled programs' scopes
    (``mxnet_tpu.obs.program_scopes``), {} where it has no such thing."""
    try:
        from mxnet_tpu import obs
        return obs.program_scopes()
    except (ImportError, AttributeError):
        return {}


def _step_intervals(run, view):
    """One interval a step on the device's clock, whole steps only.  A
    training cell: the executions of the step's compiled program inside
    the window (``XLA Modules``, the configuration's
    ``trace.step_module``).  A serving cell: the ``mx.decode.step.call``
    spans inside the window -- the call returns from ``device_get``, so
    the step's operations run inside it."""
    t0, t1 = view.window
    if run.family.KIND == "train":
        if run.trace is None:
            return []
        runs = xplane.module_runs(run.trace.events, run.trace.devices[0],
                                  run.cfg["trace"]["step_module"])
        found = [(r.start_ns, r.start_ns + r.dur_ns) for r in runs]
    else:
        found = [(s.start_ns, s.start_ns + s.dur_ns)
                 for s in view.named("mx.decode.step.call")]
    return [(s, t) for s, t in found if s >= t0 and t <= t1]


def load(run):
    """The ``ProgramTrace`` of a traced run, read once; None where the
    run took no trace (``--trace 0``) or the trace has no window.  A
    rehearsal's trace has host spans and no device plane: the readers of
    spans find theirs, the readers of scopes nothing."""
    if hasattr(run, "_program_trace"):
        return run._program_trace
    run._program_trace = None
    import os
    from jax.profiler import ProfileData
    from .runctx import WINDOW_SPAN
    path = None
    if getattr(run, "tracing", False):
        try:
            path = xplane.find_xplane(os.path.join(
                run.root, ".perfbench_out", "trace", run.cell.name))
        except FileNotFoundError:
            pass
    if path is None:
        run.log.line(event="program_trace", found=False,
                     why="the run took no trace")
        return None
    programs = noted_programs()
    spans, ops, matched, window = read_profile(
        ProfileData.from_file(path), WINDOW_SPAN, programs)
    if window is None:
        run.log.line(event="program_trace", found=False,
                     why="the trace holds no %r span" % WINDOW_SPAN)
        return None
    view = ProgramTrace(spans, ops, window, matched)
    view.steps = _step_intervals(run, view)
    run._program_trace = view
    _print(run, view)
    return view


def _ms_largest_first(ns_by_name):
    return {k: v / 1e6 for k, v in sorted(ns_by_name.items(),
                                          key=lambda kv: -kv[1])}


def _print(run, view):
    scoped = sum(1 for op in view.ops if op.scope)
    run.log.line(event="program_trace", found=bool(view.spans or scoped),
                 host_spans=len(view.spans), device_ops=len(view.ops),
                 scoped_ops=scoped, steps=len(view.steps),
                 executed_programs=view.matched,
                 why=None if (view.spans and scoped) else
                 "the program wrote %s" % (
                     "no mx. span and no scope" if not (view.spans or scoped)
                     else "no mx. span" if not view.spans else "no scope"))
    if view.steps and scoped:
        timed, n = view.timed(), len(view.steps)
        top = by_scope(timed, n)
        total = sum(top.values())
        run.log.measurement(
            "device_by_scope", steps=n, total_ms=total / 1e6,
            by_top_scope_ms=_ms_largest_first(top),
            by_part_ms=_ms_largest_first(
                by_scope(timed, n, key=lambda s: starred(s[:3]))),
            unscoped_share=top.get(UNSCOPED, 0.0) / total if total else None,
            unscoped_largest_ms=largest_unscoped(timed, n))
    if view.spans and run.trace is not None:
        device_ops = run.trace.ops(run.trace.devices[0])
        run.log.measurement(
            "idle_by_program_span",
            idle_gaps=xplane.idle_gaps(device_ops, view.window,
                                       as_events(view.spans),
                                       IDLE_DEFAULT, 12),
            span_ms_median={name: stats.median(
                [s.dur_ns for s in view.named(name)]) / 1e6
                for name in sorted({s.name for s in view.spans})})
