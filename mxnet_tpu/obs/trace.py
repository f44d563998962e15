"""Context-propagated trace/span IDs: the Dapper-style causality layer
(ISSUE 13 tentpole).

``mx.telemetry`` answers *how much* (counters, histograms); this module
answers *which request* and *in what order*: every unit of work carries
a ``TraceContext`` (trace id + span id) in a ``contextvars.ContextVar``,
child spans record their parent, and cross-thread fan-in (the serving
batcher assembling many requests into one compiled dispatch) is modeled
as **span links** -- the batch span names every request span it serves,
exactly the Dapper/OpenTelemetry shape.

Recording surfaces:

- :class:`span` -- THE span call of the instrumented framework paths
  (``TrainStep``, ``DeviceFeed``, ``DecodeEngine``, ``mx.profiler.scope``)
  and of user code.  It always enters a
  ``jax.profiler.TraceAnnotation``: the native ``TraceMe`` is a no-op
  while no profiler session runs, and while one runs -- whoever started
  it -- the span is in its trace, on the device trace's clock.  While
  ``obs._TRACE_ENABLED`` it additionally records into the ring below
  (parent id, trace id, links).  A site calls it and tests no switch.
- :func:`trace` -- context manager that opens a root trace;
- :func:`begin_span` / :func:`end_span` and :func:`record_span` -- the
  ring-only hook surface of the older sites (call sites guard with
  ``obs._TRACE_ENABLED``, the same zero-overhead contract as
  ``telemetry._ENABLED``, proven by tests/test_obs.py) and of
  cross-thread spans with explicit timing.

Every finished ring span lands in (1) a bounded in-process ring (the
flight recorder and :func:`export_chrome_trace`, the one Chrome
exporter, read it) and (2) the attached telemetry sinks as a streamed
``{"kind": "span", ...}`` JSONL record (``mxtelemetry summarize`` folds
them).
"""
from __future__ import annotations

import collections
import contextlib
import contextvars
import os
import threading
import time
import uuid

import jax
from jax.profiler import TraceAnnotation as _Annotation

from .. import obs as _obs
from .. import sync as _sync

__all__ = [
    "TraceContext", "current", "new_id", "trace", "span",
    "begin_span", "end_span", "record_span", "spans", "clear",
    "export_chrome_trace", "note_program", "program_scopes",
]

# bounded span ring: a multi-hour run must not grow host memory
_MAX_SPANS = 16_384

_CTX = contextvars.ContextVar("mxtpu_trace", default=None)
_lock = _sync.Lock(name="obs.spans")
_spans = []
_dropped = 0


class TraceContext:
    """One (trace_id, span_id) position in a trace tree."""

    __slots__ = ("trace_id", "span_id")

    def __init__(self, trace_id, span_id):
        self.trace_id = trace_id
        self.span_id = span_id

    def child(self):
        """A fresh span position under the same trace."""
        return TraceContext(self.trace_id, new_id())

    def __repr__(self):
        return "TraceContext(trace=%s, span=%s)" % (self.trace_id,
                                                    self.span_id)


def new_id():
    """16-hex-char random id (uuid4-derived; no global RNG state)."""
    return uuid.uuid4().hex[:16]


def current():
    """The active TraceContext of this thread/task, or None."""
    return _CTX.get()


def fresh_context():
    """Current context if one is active, else a brand-new root trace --
    what a request boundary (serving submit) uses so externally-traced
    and untraced clients both get causality."""
    ctx = _CTX.get()
    if ctx is not None:
        return TraceContext(ctx.trace_id, new_id())
    return TraceContext(new_id(), new_id())


class _OpenSpan:
    __slots__ = ("name", "ctx", "parent_id", "t0", "t_wall", "attrs",
                 "token", "links")

    def __init__(self, name, ctx, parent_id, attrs, token):
        self.name = name
        self.ctx = ctx
        self.parent_id = parent_id
        self.t0 = time.perf_counter()
        self.t_wall = time.time()
        self.attrs = attrs
        self.token = token
        self.links = None


def begin_span(name, **attrs):
    """Open a span as a child of the current context and make it the
    current context.  Returns the open-span token for :func:`end_span`.
    The framework hook surface: call sites guard with
    ``if _obs._TRACE_ENABLED`` so the disabled cost is one flag check."""
    parent = _CTX.get()
    if parent is not None:
        ctx = parent.child()
        parent_id = parent.span_id
    else:
        ctx = TraceContext(new_id(), new_id())
        parent_id = None
    token = _CTX.set(ctx)
    return _OpenSpan(name, ctx, parent_id, attrs or None, token)


def end_span(open_span, **extra_attrs):
    """Close a span opened by :func:`begin_span`: restore the previous
    context and record the finished span."""
    _CTX.reset(open_span.token)
    attrs = open_span.attrs
    if extra_attrs:
        attrs = dict(attrs or {}, **extra_attrs)
    record_span(open_span.name, open_span.ctx,
                parent_id=open_span.parent_id,
                t0=open_span.t0,
                dur=time.perf_counter() - open_span.t0,
                t_wall=open_span.t_wall, attrs=attrs,
                links=open_span.links)
    return open_span.ctx


class span:
    """``with obs.span("mx.layer.what", n=3): ...`` -- the one span call.

    Always a ``jax.profiler.TraceAnnotation(name, **attrs)`` (nothing
    while no profiler session runs); while ``obs._TRACE_ENABLED`` also a
    child span of the current context in the ring, whose context the
    ``with`` yields (None otherwise).  ``links`` are span ids this span
    serves without being their child (a decode step and its requests);
    ``since`` is a ``perf_counter`` reading from which the ring's span
    counts where the work began earlier, on another thread (a queue
    wait: the annotation then carries ``waited_us``); ``hlo=True`` also
    enters ``jax.named_scope(name)``, so ops traced inside carry the
    name in their ``op_name`` (``mx.profiler.scope``)."""

    __slots__ = ("_name", "_attrs", "_links", "_since", "_ann", "_hlo",
                 "_open")

    def __init__(self, name, links=None, since=None, hlo=False, **attrs):
        self._name = name
        self._attrs = attrs
        self._links = links
        self._since = since
        if since is not None:
            attrs = dict(attrs, waited_us=int(
                1e6 * (time.perf_counter() - since)))
        self._ann = _Annotation(name, **attrs)
        self._hlo = jax.named_scope(name) if hlo else None
        self._open = None

    def __enter__(self):
        self._ann.__enter__()
        if self._hlo is not None:
            self._hlo.__enter__()
        if not _obs._TRACE_ENABLED:
            return None
        sp = self._open = begin_span(self._name, **self._attrs)
        if self._links:
            sp.links = list(self._links)
        if self._since is not None:
            sp.t_wall -= sp.t0 - self._since
            sp.t0 = self._since
        return sp.ctx

    def set(self, **attrs):
        """Attributes known only once the work is under way (bytes
        staged): onto the annotation and, in the ring, the span."""
        self._ann.set_metadata(**attrs)
        if self._open is not None:
            self._open.attrs = dict(self._open.attrs or {}, **attrs)

    def __exit__(self, *exc):
        if self._open is not None:
            end_span(self._open)
            self._open = None
        if self._hlo is not None:
            self._hlo.__exit__(*exc)
        self._ann.__exit__(*exc)
        return False


@contextlib.contextmanager
def trace(name="trace", trace_id=None, **attrs):
    """Open a new root trace (or adopt ``trace_id``) for the enclosed
    block.  The root span records on exit like any other."""
    ctx = TraceContext(trace_id or new_id(), new_id())
    token = _CTX.set(ctx)
    t0 = time.perf_counter()
    t_wall = time.time()
    try:
        yield ctx
    finally:
        _CTX.reset(token)
        record_span(name, ctx, parent_id=None, t0=t0,
                    dur=time.perf_counter() - t0, t_wall=t_wall,
                    attrs=attrs or None)


def record_span(name, ctx, parent_id=None, t0=None, dur=0.0,
                t_wall=None, attrs=None, links=None):
    """Record one finished span with explicit timing -- the surface for
    cross-thread spans whose begin and end live on different threads
    (queue wait measured by the batcher worker from the submit mark).

    ``t0`` is on the perf_counter clock (Chrome-trace placement);
    ``t_wall`` is wall time (JSONL ``t`` field, cross-process merge).
    ``links`` carries span ids this span serves but is not a child of
    (batcher fan-in).
    """
    global _dropped
    rec = {
        "kind": "span",
        "name": name,
        "trace": ctx.trace_id,
        "span": ctx.span_id,
        "parent": parent_id,
        "t": t_wall if t_wall is not None else time.time(),
        "t0": t0 if t0 is not None else time.perf_counter(),
        "dur": float(dur),
    }
    if attrs:
        rec["attrs"] = attrs
    if links:
        rec["links"] = list(links)
    with _lock:
        if len(_spans) >= _MAX_SPANS:
            del _spans[:_MAX_SPANS // 10]
            _dropped += _MAX_SPANS // 10
        _spans.append(rec)
    # stream to the attached telemetry sinks (JSONL run log, flight
    # recorder); Registry._stream is sink fan-out only -- it does not
    # require telemetry to be enabled, so tracing stands alone
    from .. import telemetry as _telemetry
    _telemetry.registry()._stream(rec)
    return rec


# ---------------------------------------------------------------------
# scopes of the compiled programs, for a reader of a device trace
# ---------------------------------------------------------------------
# A device trace names an executed instruction (``fusion.810``) and not
# the ``jax.named_scope`` path it was traced under; that lives in the
# compiled module's metadata.  The paths that compile a program of their
# own (TrainStep, the decode engine) leave a way to its compiled text
# here, and whoever holds a trace of the process asks for the map.
# Bounded, newest kept: a process that builds programs without end (hot
# swaps) does not keep them all alive.
_MAX_PROGRAMS = 32
_programs = collections.OrderedDict()     # label -> () -> compiled HLO text


def note_program(label, text_fn):
    """Remember how to get the compiled HLO text of the program called
    ``label`` (one dict insert; nothing is computed here)."""
    with _lock:
        _programs.pop(label, None)
        _programs[label] = text_fn
        while len(_programs) > _MAX_PROGRAMS:
            _programs.popitem(last=False)


def program_scopes():
    """``{label: {"module": HLO module name, "scopes": {instruction
    name: op_name}}}`` for the noted programs (``profiling.hlo.scope_map``
    of each one's compiled text; the module name is what a device trace
    calls the program's executions).  Computed when asked -- after a
    traced window, never inside one; a program whose text cannot be had
    is left out."""
    from ..profiling.hlo import module_name, scope_map
    with _lock:
        noted = list(_programs.items())
    out = {}
    for label, text_fn in noted:
        try:
            text = text_fn()
            out[label] = {"module": module_name(text),
                          "scopes": scope_map(text)}
        except Exception:
            continue
    return out


def spans():
    """Snapshot of the bounded span ring (oldest first)."""
    with _lock:
        return list(_spans)


def dropped():
    return _dropped


def clear():
    global _dropped
    with _lock:
        del _spans[:]
        _dropped = 0


def export_chrome_trace(path=None):
    """Chrome trace-event JSON of the span ring: complete ('X') events
    with trace/span/parent ids in ``args``, loadable in Perfetto or
    chrome://tracing.  Written to ``path`` when given; the dict is
    returned either way."""
    import json
    evs = []
    for rec in spans():
        args = {"trace": rec["trace"], "span": rec["span"]}
        if rec.get("parent"):
            args["parent"] = rec["parent"]
        if rec.get("links"):
            args["links"] = rec["links"]
        if rec.get("attrs"):
            args.update(rec["attrs"])
        evs.append({"name": rec["name"], "ph": "X",
                    "ts": rec["t0"] * 1e6, "dur": rec["dur"] * 1e6,
                    "pid": os.getpid(), "tid": threading.get_ident(),
                    "args": args})
    doc = {"traceEvents": evs, "displayTimeUnit": "ms",
           "otherData": {"producer": "mxnet_tpu.obs.trace",
                         "dropped_spans": _dropped}}
    if path:
        with open(path, "w") as f:
            json.dump(doc, f)
    return doc
