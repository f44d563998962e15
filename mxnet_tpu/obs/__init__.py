"""Ops plane for the always-on loop (ISSUE 13): request/step tracing,
a crash-safe flight recorder, and live HTTP introspection.

``mx.telemetry`` (counters/histograms) says *how much*; ``mx.obs`` says
*which one and why*:

- **tracing** (``obs.trace``): context-propagated trace/span IDs
  threaded through the serving path (submit -> queue wait -> batch
  assembly -> compiled dispatch -> device_get -> respond, batcher
  fan-in recorded as span links) and the training loop (step ->
  publish -> checkpoint commit -> watcher discover -> warm -> install),
  exported as Chrome-trace JSON and streamed into the telemetry JSONL;
- **flight recorder** (``obs.flight``): a bounded mmap'd ring of the
  last records that survives ``os._exit``/SIGKILL, dumped automatically
  from the preemption handler, the chaos KILL path, and a SIGUSR2
  stack-snapshot hook; render with ``mxtelemetry blackbox <file>``;
- **introspection** (``obs.server``): ``/healthz`` (watcher failure
  budget + writer errors + queue saturation), ``/metrics`` (Prometheus
  exposition), ``/statusz`` (served/published step, swap history,
  heartbeats) on ``MXNET_TPU_OBS_PORT``;
- **goodput ledger** (``obs.goodput``, ISSUE 14): per-window step-time
  attribution (device_compute / input_wait / host_sync /
  checkpoint_stall / recompile / other, reconciled to window wall),
  a rolling MFU gauge, and an EWMA+MAD regression sentinel guarded by
  the env.* health gauges; armed by ``MXNET_TPU_OBS_GOODPUT=1`` /
  ``obs.enable_goodput()``;
- **fleet plane** (``obs.fleet`` + ``obs.alerts``, ISSUE 17): endpoint
  discovery via ``MXNET_TPU_OBS_ENDPOINTS_DIR`` (atomic publish,
  dead-pid sweep), a scrape client + :class:`~mxnet_tpu.obs.fleet.\
FleetMonitor` aggregating /healthz //metrics //statusz across replicas
  (merged latency histograms -- never averaged p99s), and a burn-rate
  SLO :class:`~mxnet_tpu.obs.alerts.AlertEngine` behind ``/alertz``
  and ``mxtelemetry fleet``.

Tracing is gated exactly like telemetry: disabled (the default), every
instrumented site pays ONE module-flag check (``obs._TRACE_ENABLED``)
and makes zero calls into ``obs.trace`` -- proven by
tests/test_obs.py::test_tracing_disabled_makes_zero_trace_calls.
Enable with ``MXNET_TPU_OBS_TRACE=1`` or ``obs.enable_tracing()``.
"""
from __future__ import annotations

import os

from . import alerts, flight, goodput, status, trace
from .trace import (TraceContext, begin_span, current, end_span,
                    export_chrome_trace, note_program, program_scopes,
                    record_span, span, spans)
from .trace import trace as start_trace

__all__ = [
    "enable_tracing", "disable_tracing", "tracing_enabled",
    "enable_goodput", "disable_goodput", "goodput_enabled",
    "start_trace", "span", "begin_span", "end_span", "record_span",
    "current", "spans", "export_chrome_trace", "TraceContext",
    "note_program", "program_scopes",
    "flight", "goodput", "status", "server", "serve",
    "install_blackbox", "fleet", "alerts",
]

# THE flag every traced hot path checks (one module-attribute read).
# Mutate only through enable_tracing()/disable_tracing().
_TRACE_ENABLED = False

# THE flag the goodput-ledger hook sites check (ContinuousTrainer's
# step/publish loop); same zero-overhead contract as _TRACE_ENABLED.
_GOODPUT_ENABLED = False


def enable_tracing():
    """Arm the trace hooks (idempotent)."""
    global _TRACE_ENABLED
    _TRACE_ENABLED = True


def disable_tracing():
    """Disarm the trace hooks; recorded spans are kept."""
    global _TRACE_ENABLED
    _TRACE_ENABLED = False


def tracing_enabled():
    return _TRACE_ENABLED


def enable_goodput():
    """Arm the goodput-ledger loop hooks (idempotent; the ledger reads
    telemetry instruments, so enable telemetry too for non-empty
    category attribution)."""
    global _GOODPUT_ENABLED
    _GOODPUT_ENABLED = True


def disable_goodput():
    """Disarm the goodput hooks; recorded windows are kept."""
    global _GOODPUT_ENABLED
    _GOODPUT_ENABLED = False


def goodput_enabled():
    return _GOODPUT_ENABLED


def install_blackbox(path=None, capacity=None):
    """Install the process flight recorder (see ``obs.flight``)."""
    return flight.install(path, capacity=capacity)


def serve(port=None):
    """Start the introspection HTTP server (see ``obs.server``)."""
    from . import server as _server
    return _server.serve(port)


from . import server  # noqa: E402  (handler imports status above)
from . import fleet  # noqa: E402  (imports alerts + sync above)

# env arming (same != "0" convention as telemetry)
if os.environ.get("MXNET_TPU_OBS_TRACE", "0") != "0":
    enable_tracing()
if os.environ.get("MXNET_TPU_OBS_GOODPUT", "0") != "0":
    enable_goodput()
_env_blackbox = os.environ.get("MXNET_TPU_OBS_BLACKBOX", "")
if _env_blackbox:
    flight.install(_env_blackbox)
_env_port = os.environ.get("MXNET_TPU_OBS_PORT", "")
if _env_port and _env_port != "0":
    server.serve(int(_env_port))
