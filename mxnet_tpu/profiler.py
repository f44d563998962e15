"""Profiler (reference: ``python/mxnet/profiler.py`` over
``src/profiler/profiler.cc``).

TPU-native design: the heavy lifting is ``jax.profiler`` -- XLA already
records per-op device timelines, HBM usage, and host/device transfer
events into a TensorBoard-loadable trace, which replaces the reference's
hand-rolled chrome-tracing writer.  This module supplies the reference's
control surface (``set_config / set_state / start / stop / dump``) plus
named scopes that executors and the imperative dispatcher enter so
framework-level structure (op names, cached-graph steps) shows up in the
device trace.
"""
from __future__ import annotations

import contextlib
import os

from .base import MXNetError

_config = {
    "filename": "profile.json",   # reference arg; dir is derived from it
    "profile_all": False,
    "profile_symbolic": True,
    "profile_imperative": True,
    "profile_memory": True,
    "profile_api": True,
    "aggregate_stats": False,
}
_state = "stop"
_trace_dir = None
_scopes_enabled = False


def set_config(**kwargs):
    """Reference: ``profiler.set_config``.  ``filename`` determines the
    trace output directory (its dirname; traces are TensorBoard format,
    not a single json)."""
    unknown = set(kwargs) - set(_config)
    if unknown:
        raise MXNetError("profiler.set_config: unknown options %r"
                         % sorted(unknown))
    _config.update(kwargs)


def set_state(state="stop", profile_process="worker"):
    """Reference: ``profiler.set_state('run'|'stop')``."""
    global _state, _trace_dir, _scopes_enabled
    if state not in ("run", "stop"):
        raise MXNetError("profiler state must be 'run' or 'stop'")
    if state == "run" and _state == "stop":
        import jax
        _trace_dir = os.path.dirname(
            os.path.abspath(_config["filename"])) or "."
        _trace_dir = os.path.join(_trace_dir, "mxnet_tpu_profile")
        os.makedirs(_trace_dir, exist_ok=True)
        jax.profiler.start_trace(_trace_dir)
        _scopes_enabled = True
        _state = "run"
    elif state == "stop" and _state == "run":
        import jax
        jax.profiler.stop_trace()
        _scopes_enabled = False
        _state = "stop"


def start(profile_process="worker"):
    """Reference: ``profiler.start``."""
    set_state("run", profile_process)


def stop(profile_process="worker"):
    """Reference: ``profiler.stop``."""
    set_state("stop", profile_process)


def pause(profile_process="worker"):
    """Scopes off; device trace keeps running (closest analog)."""
    global _scopes_enabled
    _scopes_enabled = False


def resume(profile_process="worker"):
    global _scopes_enabled
    if _state == "run":
        _scopes_enabled = True


def dump(finished=True, profile_process="worker"):
    """Reference: ``profiler.dump`` -- finalize the trace to disk.  The
    trace directory (TensorBoard `plugins/profile` layout) is returned."""
    if _state == "run" and finished:
        stop()
    return _trace_dir


_DUMPS_SORT_KEYS = ("total", "avg", "min", "max", "count", "flops",
                    "bytes", "peak_hbm")


def dumps(reset=False, format="table", sort_by="total", ascending=False):
    """Reference: ``profiler.dumps`` (aggregate stats) -- REAL per-
    executable aggregates from the mx.profiling CostReport store, not a
    pointer string.  One row per captured compiled program: step
    count/total/avg (host wall), FLOPs, bytes accessed, peak HBM.

    ``sort_by`` follows the reference's keys (``total``/``avg``/
    ``min``/``max``/``count`` over step time) plus cost-side keys
    (``flops``/``bytes``/``peak_hbm``); ``format`` is ``table`` or
    ``json``; ``reset=True`` clears the store after rendering."""
    if sort_by not in _DUMPS_SORT_KEYS:
        raise MXNetError("profiler.dumps: sort_by must be one of %s"
                         % (_DUMPS_SORT_KEYS,))
    if format not in ("table", "json"):
        raise MXNetError("profiler.dumps: format must be 'table' or "
                         "'json'")
    from . import profiling
    rows = []
    for rep in profiling.reports():
        st = rep.get("step") or {}
        count = st.get("count", 0)
        total = st.get("total_s", 0.0) or 0.0
        rows.append({
            "name": rep["label"],
            "count": count,
            "total": total,
            "avg": (total / count) if count else 0.0,
            "min": st.get("min_s") or 0.0,
            "max": st.get("max_s") or 0.0,
            "flops": rep["totals"]["flops"],
            "bytes": rep["totals"]["bytes_accessed"],
            "peak_hbm": rep["memory"]["peak_hbm_bytes"],
        })
    rows.sort(key=lambda r: r[sort_by], reverse=not ascending)
    if reset:
        profiling.reset()
    if format == "json":
        import json
        return json.dumps(rows, indent=1, sort_keys=True)
    lines = ["Profile Statistics (mx.profiling cost reports):",
             "%-36s %8s %12s %12s %14s %14s %12s"
             % ("Name", "Count", "Total(ms)", "Avg(ms)", "FLOPs",
                "Bytes", "PeakHBM")]
    for r in rows:
        lines.append("%-36s %8d %12.3f %12.3f %14.3g %14.3g %12d"
                     % (r["name"][:36], r["count"], 1e3 * r["total"],
                        1e3 * r["avg"], r["flops"], r["bytes"],
                        r["peak_hbm"]))
    if not rows:
        lines.append("(no cost reports captured; enable with "
                     "MXNET_TPU_PROFILING=1 / mx.profiling.enable())")
    return "\n".join(lines)


def state():
    return _state


def scope(name):
    """Named region, through the one span call (``obs.span``): a
    ``jax.profiler.TraceAnnotation`` in the device trace (reference:
    profiler scope in ``MXNET_PROFILER_SCOPE``) AND -- via
    ``jax.named_scope`` -- the ``op_name`` metadata of any HLO traced
    inside it, which is how framework provenance reaches the
    mx.profiling CostReport's per-scope attribution.  With obs tracing
    armed it is also a span in the ``obs`` ring.  A no-op while the
    profiler is stopped or paused."""
    if not _scopes_enabled:
        return contextlib.nullcontext()
    from . import obs as _obs
    return _obs.span(name, hlo=True)


class Profiler:
    """Context manager sugar: ``with mx.profiler.Profiler(filename=...):``"""

    def __init__(self, **config):
        if config:
            set_config(**config)

    def __enter__(self):
        start()
        return self

    def __exit__(self, *exc):
        stop()


class Domain:
    """Reference: ``profiler.Domain`` -- a named grouping for custom
    objects."""

    def __init__(self, name):
        self.name = name

    def __str__(self):
        return self.name


def _region_name(a, b):
    """Reference calling conventions: ``Task(domain, name)`` /
    ``Frame(domain, name)`` take the Domain first; ``Event(name)`` takes
    just a name.  Accept both orders."""
    if b is None:
        return str(a)
    return "%s::%s" % (a, b) if isinstance(a, Domain) else str(b)


class _NamedRegion:
    """Base for the reference's custom profiler objects (``Task``,
    ``Frame``, ``Event``): start/stop (or ``with``) brackets a named
    region in the device trace."""

    def __init__(self, domain_or_name, name=None):
        self.name = _region_name(domain_or_name, name)
        self._cm = None

    def start(self):
        if _scopes_enabled:
            from . import obs as _obs
            self._cm = _obs.span(self.name)
            self._cm.__enter__()

    def stop(self):
        if self._cm is not None:
            self._cm.__exit__(None, None, None)
            self._cm = None

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()


class Task(_NamedRegion):
    """Reference: ``profiler.Task``."""


class Frame(_NamedRegion):
    """Reference: ``profiler.Frame``."""


class Event(_NamedRegion):
    """Reference: ``profiler.Event``."""


# profiler counters live in the telemetry registry under this prefix,
# so they show up in telemetry sinks/snapshots and ``profiler.reset()``
# can clear exactly them
_COUNTER_PREFIX = "profiler."


class Counter:
    """Named counter (reference: ``profiler.Counter(domain, name,
    value)``).  Re-constructing an existing name attaches to it without
    resetting (reference semantics).

    Backed by the ``mx.telemetry`` registry (one store, visible in every
    telemetry sink) instead of the former class-global dict, which
    leaked values across instances AND across tests with no way to
    clear them; ``profiler.reset()`` now zeroes all profiler counters.
    """

    def __init__(self, domain_or_name, name=None, value=None):
        from . import telemetry
        self.name = _region_name(domain_or_name, name)
        self._counter = telemetry.counter(_COUNTER_PREFIX + self.name)
        if value is not None:
            self._counter.set(value)

    def set_value(self, value):
        self._counter.set(value)

    def increment(self, delta=1):
        self._counter.inc(delta)

    def decrement(self, delta=1):
        self._counter.dec(delta)

    @property
    def value(self):
        return self._counter.value


def reset():
    """Zero every ``profiler.Counter`` (test isolation; the former
    class-global dict had no reset and leaked across tests)."""
    from . import telemetry
    telemetry.reset(prefix=_COUNTER_PREFIX)


def marker(name, scope="process"):
    """Instant event (reference: ``profiler.Marker``/``set_marker``):
    recorded as a zero-length annotation."""
    if _scopes_enabled:
        from . import obs as _obs
        with _obs.span("marker:" + name):
            pass


# reference env: start profiling at import when requested; the trace
# only hits disk at stop, so flush at interpreter exit
if os.environ.get("MXNET_PROFILER_AUTOSTART", "0") != "0":
    import atexit
    set_state("run")
    atexit.register(stop)
