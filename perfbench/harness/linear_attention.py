"""The linear-attention (KDA) layers' device time in the serving programs.

A model with KDA layers (``mxnet_tpu/serving/decode/linear_moe.py``) puts
each layer's work under ``h<i>/linear_attention/<part>``, the parts
``proj``, ``conv``, ``gate``, ``recurrence`` and ``norm``.  This module
takes the serving programs' executions from ``serve_programs`` (the
``jit_mx_decode_b*`` / ``jit_mx_prefill_b*`` executions whole inside the
traced window, found by the names the engine gives its programs; not
``program_trace``'s step intervals, which read low since PR 31), the ops
inside them with their scopes from ``program_trace``, and sums each
execution's self time by part.  Two per-layer metrics read the result
(``linear_attention_ms.serve``, ``linear_attention_roofline.serve``),
and on first use it prints one line, ``linear_attention``: for decode
each part's device ms a step (mean over the executions) and for prefill
each part's device us a padded token, beside the steps' ``state_rows``.

A program without those scopes (another model, or the parent of the PR
that added them) gives nothing to find: ``load`` returns None, the
readers return None, and the line says why.  Every reduction works on
plain tuples and is tested on hand-built ones.
"""
import bisect
import collections

from . import program_trace, serve_programs, stats

SCOPE = "linear_attention"
RECURRENCE = "recurrence"
STEP_SPAN = "mx.decode.step"


def part_of(op):
    """The part of a KDA layer an op belongs to (the component after
    ``linear_attention`` in its scope), None for any other op."""
    scope = op.scope
    for i, part in enumerate(scope[:-1]):
        if part == SCOPE:
            return scope[i + 1]
    return None


def by_execution(ops, executions):
    """``[Counter(part -> self ns)]``, one an execution of ``executions``
    (``[Execution]``, in time order), of the KDA layers' ops that start
    inside it."""
    spans = [(e.start_ns, e.start_ns + e.dur_ns) for e in executions]
    starts = [s for s, _e in spans]
    out = [collections.Counter() for _ in executions]
    for op, ns in program_trace.self_times(program_trace.inside(ops, spans)):
        part = part_of(op)
        if part is None:
            continue
        n = bisect.bisect_right(starts, op.start_ns) - 1
        if n >= 0 and op.start_ns < spans[n][1]:
            out[n][part] += ns
    return out


def state_rows_a_step(step_spans, window):
    """Mean ``state_rows`` (live slots x KDA layers) of the
    ``mx.decode.step`` spans whole inside ``window`` that carry it; None
    where none does."""
    rows = []
    for s in step_spans:
        if window[0] <= s.start_ns and s.start_ns + s.dur_ns <= window[1]:
            try:
                rows.append(float(s.attrs["state_rows"]))
            except (KeyError, TypeError, ValueError):
                pass
    return sum(rows) / len(rows) if rows else None


class LinearAttention:
    """``decode``: ``[Counter]`` a decode execution; ``prefill``: the
    same a prefill execution; ``prefill_tokens``: the padded tokens
    those prefills computed; ``state_rows``: mean ``state_rows`` a
    decode step."""

    def __init__(self, found, ops, step_spans):
        self.decode = by_execution(ops, found.decodes)
        prefills = [e for e, _s in found.prefills]
        self.prefill = by_execution(ops, prefills)
        self.prefill_tokens = sum(e.bucket for e in prefills)
        self.state_rows = state_rows_a_step(step_spans, found.window)

    def decode_ms(self):
        """Median over the decode executions of their KDA self time."""
        found = [sum(c.values()) for c in self.decode if c]
        return stats.median(found) / 1e6 if found else None

    def recurrence_ms(self):
        """Mean over the decode executions of the recurrence's time."""
        found = [c[RECURRENCE] for c in self.decode if c]
        return sum(found) / len(found) / 1e6 if found else None

    def parts(self):
        decode = collections.Counter()
        for c in self.decode:
            decode.update(c)
        prefill = collections.Counter()
        for c in self.prefill:
            prefill.update(c)
        n = sum(1 for c in self.decode if c)
        return ({k: v / n / 1e6 for k, v in decode.most_common()}
                if n else None,
                {k: v / self.prefill_tokens / 1e3
                 for k, v in prefill.most_common()}
                if self.prefill_tokens and prefill else None)


def load(run):
    """The ``LinearAttention`` of a traced run, made once; None, with a
    line that says why, where there is nothing to read."""
    if hasattr(run, "_linear_attention"):
        return run._linear_attention
    run._linear_attention = None
    found = serve_programs.load(run)
    view = program_trace.load(run)
    why = None
    if found is None or view is None:
        why = "no serving program's executions under its own name"
    else:
        got = LinearAttention(found, view.ops, view.named(STEP_SPAN))
        if not any(got.decode):
            why = "no op of a decode execution is scoped %r" % SCOPE
    if why is not None:
        run.log.line(event="linear_attention", found=False, why=why)
        return None
    decode, prefill = got.parts()
    run.log.measurement(
        "linear_attention", found=True,
        decode_executions=len(got.decode),
        decode_ms_a_step=decode, prefill_us_a_padded_token=prefill,
        prefill_executions=len(got.prefill),
        state_rows_a_step=got.state_rows)
    run._linear_attention = got
    return got
