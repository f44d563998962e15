"""The serving programs' executions on the device, under the names the
program gives them.

The decode engine compiles one program a prefill bucket and one a decode
bucket and calls each module ``mx_<kind>_b<bucket>``, so the device
trace's ``XLA Modules`` line reads ``jit_mx_prefill_b2048(<id>)`` for an
execution of the 2,048-token prefill and ``jit_mx_decode_b16(<id>)`` for a
decode step of 16 slots.  This module finds those executions on device 0,
joins a prefill's to the ``mx.decode.prefill`` span that contains it (the
span says what was computed, ``bucket`` and ``prompt``, and whom it held:
``live``, the streams with a token still to dispatch), takes the ops
inside the prefills from ``program_trace`` with their scopes, and prints
one line, ``prefill_programs``, on first use.  Four per-layer metrics read
the result (``decode_device_ms.serve``, ``prefill_device_share.serve``,
``prefill_us_per_token.serve``, ``prefill_attention_us_per_token.serve``).

A token of a prefill is a PADDED token: the device computes ``bucket``
tokens whatever the prompt's length, and ``padded_share`` on the line says
how much of that was padding.

Every reduction works on plain tuples and is tested on hand-built ones.
A program that does not name its serving programs (the parent of the PR
that did: every module is ``jit_call``) gives nothing to find: ``load``
returns None, every reader returns None, and the line says why.
"""
import collections
import re

from . import program_trace, stats, xplane

Execution = collections.namedtuple("Execution", "start_ns dur_ns bucket")

PREFILL_SPAN = "mx.decode.prefill"
STEP_SPAN = "mx.decode.step"
ATTENTION = {"attention", "attention_full", "attention_window"}
EXPERT_MATMUL = "h*/experts/matmul"
_NAMED = re.compile(r"^jit_mx_([A-Za-z]+)_b(\d+)")
# what a loop adds to the scope path of the instructions in its body
_LOOP = {"while", "body", "cond", "closed_call"}
# the TPU compiler's own kernel for ``jax.lax.ragged_dot``: its op_name is
# not the scope it was traced under, and only the routed experts issue it
# (``moe_experts_ms.serve`` finds it by this name too)
_GROUPED_MATMUL = re.compile(r"^ragged-dot")


# ----------------------------------------------------------------------
# reductions on plain tuples
# ----------------------------------------------------------------------

def named_executions(modules, kind, window=None):
    """The executions of the ``kind`` programs (``"prefill"``,
    ``"decode"``) among ``modules`` = [(name, start_ns, dur_ns)], in time
    order as ``Execution``; with a ``window`` = (start_ns, end_ns) only
    those that lie whole inside it."""
    out = []
    for name, start, dur in modules:
        m = _NAMED.match(name)
        if not m or m.group(1) != kind:
            continue
        if window is not None and not (window[0] <= start
                                       and start + dur <= window[1]):
            continue
        out.append(Execution(float(start), float(dur), int(m.group(2))))
    return sorted(out)


def _inside_ns(start, dur, window):
    return max(min(start + dur, window[1]) - max(start, window[0]), 0.0)


def clipped_ns(modules, kind, window):
    """Nanoseconds of the ``kind`` programs' executions inside the
    window: one cut by an edge counts with its part inside."""
    return sum(_inside_ns(e.start_ns, e.dur_ns, window)
               for e in named_executions(modules, kind))


def join(executions, spans):
    """``[(execution, span)]``: each execution with the span that holds
    it whole (the prefill is dispatched inside its span's first ``.call``
    and its output fetched inside the last), None where none does."""
    spans = sorted(spans, key=lambda s: s.start_ns)
    out, i = [], 0
    for e in sorted(executions):
        while i < len(spans) and spans[i].start_ns + spans[i].dur_ns \
                < e.start_ns + e.dur_ns:
            i += 1
        held = i < len(spans) and spans[i].start_ns <= e.start_ns
        out.append((e, spans[i] if held else None))
    return out


def _attr(span, name):
    try:
        return int(span.attrs[name])
    except (KeyError, TypeError, ValueError):
        return None


def by_bucket(executions):
    """``{bucket: [executions, median device ms, device us a padded
    token]}``."""
    durs = collections.defaultdict(list)
    for e in executions:
        durs[e.bucket].append(e.dur_ns)
    return {b: [len(d), stats.median(d) / 1e6, sum(d) / len(d) / b / 1e3]
            for b, d in sorted(durs.items())}


def padded_share(joined):
    """Padding's share of the tokens the joined prefills computed: the sum
    of ``bucket - prompt`` over the sum of ``bucket``; None where no span
    says its prompt."""
    pairs = [(e.bucket, _attr(s, "prompt")) for e, s in joined
             if s is not None and _attr(s, "prompt") is not None]
    if not pairs:
        return None
    return sum(b - p for b, p in pairs) / float(sum(b for b, _p in pairs))


def streams_held_ms(joined):
    """Stream-milliseconds the prefills stood before running streams: the
    sum of ``live`` x device time; None where no span says ``live``."""
    held = [_attr(s, "live") * e.dur_ns for e, s in joined
            if s is not None and _attr(s, "live") is not None]
    return sum(held) / 1e6 if held else None


def held_step_ms(step_spans, window):
    """What a running stream waits for its next token when a prefill
    stands before it, event by event: over the ``mx.decode.step`` spans
    whole inside the window, the time from the end of the span before to
    the end of this one (token arrival to token arrival; the span itself
    opens after the prefill's token is in), for the spans with
    ``after_prefill`` 1 the median and the longest, beside the median
    where it is 0.  Only spans with ``overlapped`` 1 count: a step
    dispatched with nothing in flight follows an idle moment, not a
    token.  None where no span carries ``after_prefill``."""
    spans = sorted(step_spans, key=lambda s: s.start_ns)
    gaps = {0: [], 1: []}
    for before, s in zip(spans, spans[1:]):
        flag = _attr(s, "after_prefill")
        if flag not in gaps or _attr(s, "overlapped") != 1 \
                or before.start_ns < window[0] \
                or s.start_ns + s.dur_ns > window[1]:
            continue
        gaps[flag].append((s.start_ns + s.dur_ns
                           - before.start_ns - before.dur_ns) / 1e6)
    if not (gaps[0] or gaps[1]):
        return None
    return {"after_prefill": len(gaps[1]),
            "median": stats.median(gaps[1]) if gaps[1] else None,
            "longest": max(gaps[1]) if gaps[1] else None,
            "other_steps": len(gaps[0]),
            "other_median": stats.median(gaps[0]) if gaps[0] else None}


def part_of(op):
    """The part of the program an op belongs to, as ``program_trace``'s
    ``by_part_ms`` names parts (the first three components, a layer's
    index a star), the components a loop adds left out:
    ``h3/experts/while/body/gather`` -> ``h*/experts/gather``,
    ``h3/attention/while/body/closed_call/while/body`` ->
    ``h*/attention``.  An op without a scope of the program's (none, or
    a loop's components alone) is ``unscoped``, but for the compiler's
    grouped-matmul kernel, which is the experts' matmul."""
    parts = tuple(p for p in op.scope if p not in _LOOP)[:3]
    if parts:
        return program_trace.starred(parts)
    return EXPERT_MATMUL if _GROUPED_MATMUL.match(op.name) \
        else program_trace.UNSCOPED


def is_attention(op):
    return bool(ATTENTION.intersection(op.scope))


# ----------------------------------------------------------------------
# the view of one traced run
# ----------------------------------------------------------------------

class ServePrograms:
    """``prefills``: ``[(Execution, span or None)]`` of the prefill
    executions whole inside the window; ``decodes``: the decode steps'
    ``[Execution]`` likewise; ``prefill_ns`` / ``decode_ns``: each kind's
    device time clipped to the window; ``timed``: ``(op, self ns)`` of
    the ops inside the whole prefill executions; ``tokens``: the padded
    tokens those computed."""

    def __init__(self, modules, window, prefill_spans=(), ops=()):
        self.window = window
        self.prefills = join(named_executions(modules, "prefill", window),
                             prefill_spans)
        self.decodes = named_executions(modules, "decode", window)
        self.prefill_ns = clipped_ns(modules, "prefill", window)
        self.decode_ns = clipped_ns(modules, "decode", window)
        self.tokens = sum(e.bucket for e, _s in self.prefills)
        self.timed = program_trace.self_times(program_trace.inside(
            ops, [(e.start_ns, e.start_ns + e.dur_ns)
                  for e, _s in self.prefills]))

    def share(self, ns):
        return 100.0 * ns / (self.window[1] - self.window[0])

    def prefill_us_per_token(self, keep=None):
        """Device us a padded token of the whole prefill executions; with
        ``keep`` the self time of the ops it keeps alone, None where it
        keeps none."""
        if not self.tokens:
            return None
        if keep is None:
            ns = sum(e.dur_ns for e, _s in self.prefills)
        else:
            found = [ns for op, ns in self.timed if keep(op)]
            if not found:
                return None
            ns = sum(found)
        return ns / self.tokens / 1e3

    def by_part_us_a_token(self):
        total = collections.Counter()
        for op, ns in self.timed:
            total[part_of(op)] += ns
        return {k: v / self.tokens / 1e3 for k, v in total.most_common()}


def _modules(run):
    tr = run.trace
    return [(e.name, e.start_ns, e.dur_ns) for e in xplane.on_device(
        tr.events, tr.devices[0], xplane.MODULES_LINE)]


def load(run):
    """The ``ServePrograms`` of a traced run, made once; None, with a line
    that says why, where the run has no device trace or the trace no
    execution under the program's names."""
    if hasattr(run, "_serve_programs"):
        return run._serve_programs
    run._serve_programs = None
    view = program_trace.load(run)
    why = None
    if view is None or run.trace is None:
        why = "the run has no device trace"
    else:
        modules = _modules(run)
        if not any(_NAMED.match(name) for name, _s, _d in modules):
            why = ("no execution on %r is called jit_mx_<kind>_b<bucket>: "
                   "the program does not name its serving programs"
                   % xplane.MODULES_LINE)
    if why is not None:
        run.log.line(event="prefill_programs", found=False, why=why)
        return None
    found = ServePrograms(modules, view.window, view.named(PREFILL_SPAN),
                          view.ops)
    run._serve_programs = found
    _print(run, view, found, modules)
    return found


def executions(run, kind):
    """The ``kind`` programs' executions whole inside the traced window:
    ``[Execution]`` for ``"decode"``, ``[(Execution, span or None)]`` for
    ``"prefill"``; None where ``load`` finds nothing."""
    found = load(run)
    if found is None:
        return None
    return found.prefills if kind == "prefill" else found.decodes


def _print(run, view, found, modules):
    tr = run.trace
    idle = 100.0 * (1.0 - tr.busy_s(tr.devices[0]) / tr.window_s)
    prefill, decode = found.share(found.prefill_ns), \
        found.share(found.decode_ns)
    # what else ran on the device, where the three do not come to 100
    others = collections.Counter()
    for name, start, dur in modules:
        if not _NAMED.match(name):
            others[name.split("(")[0]] += _inside_ns(start, dur, view.window)
    mismatched = sorted(
        module for module, (label, _seen, _known) in view.matched.items()
        if _NAMED.match(module) and not (label or "").endswith(
            ":%s:%s" % _NAMED.match(module).groups()))
    executions = [e for e, _s in found.prefills]
    run.log.measurement(
        "prefill_programs", found=True,
        by_bucket=by_bucket(executions),
        joined=sum(1 for _e, s in found.prefills if s is not None),
        padded_share=padded_share(found.prefills),
        longest_ms=max(e.dur_ns for e in executions) / 1e6
        if executions else None,
        streams_held_ms=streams_held_ms(found.prefills),
        held_step_ms=held_step_ms(view.named(STEP_SPAN), view.window),
        by_part_us_a_token=found.by_part_us_a_token()
        if found.tokens else None,
        decode_executions=len(found.decodes),
        device_shares={"prefill": prefill, "decode": decode, "idle": idle,
                       "sum": prefill + decode + idle},
        other_programs_share={k: found.share(v)
                              for k, v in others.most_common(3) if v},
        mismatched_programs=mismatched)
