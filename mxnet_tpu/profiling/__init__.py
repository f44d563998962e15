"""Compiled-step cost accounting (ISSUE 6 tentpole).

``mx.telemetry`` (ISSUE 2) counts host-side events; ``mx.profiler``
wraps ``jax.profiler``'s TensorBoard traces.  Neither can say which
HLOs eat the chip.  This subsystem is the missing device-cost layer --
the TPU-native rebirth of the reference's ``src/profiler/profiler.cc``
per-op stats, rebuilt on XLA's own cost model:

- Every compiled executable the framework dispatches (eager-jit cache,
  hybridize cache, ``Executor``, ``parallel.TrainStep``) is captured
  into a :class:`CostReport`: XLA's ``cost_analysis()`` totals (FLOPs,
  bytes accessed) + ``memory_analysis()`` (argument/output/temp HBM,
  peak estimate) + a per-HLO-**category** breakdown (conv/dot,
  collective, transpose-layout, elementwise/fusion, other) attributed
  by parsing the compiled HLO text, reconciled so categories sum
  exactly to the executable totals.
- An analytic roofline turns measured step time + CostReport into
  achieved-vs-peak compute and bandwidth per category, labeling each
  category compute- or memory-bound -- MFU decomposed.
- The ``mxprof`` CLI (``report`` / ``diff``) renders report artifacts
  and names the categories whose FLOPs/bytes/peak-HBM drifted between
  two runs -- the regression-attribution contract of ROADMAP item 2.

Enable with ``MXNET_TPU_PROFILING=1`` or ``mx.profiling.enable()``.
Disabled (the default), every hook is one module-flag check.  With
``MXNET_TPU_PROFILING_DIR`` set, reports are persisted there at exit
(and by ``save_reports()``).
"""
from __future__ import annotations

import os

__all__ = [
    "enable", "disable", "enabled", "reset",
    "capture_jit", "record_step", "reports", "combined_report",
    "save_reports", "report_for", "report_dir", "flops_per_step",
    "CATEGORIES",
]

# Hot-path gate: instrumented modules check this one module attribute
# (same contract as telemetry._ENABLED) and make zero calls when off.
_ENABLED = False

# HLO cost categories (docs/profiling.md); re-exported from hlo.py at
# first use -- kept literal here so importing the gate stays stdlib-only.
CATEGORIES = ("conv_dot", "collective", "transpose_layout",
              "elementwise_fusion", "other")

_atexit_armed = False


def enable():
    """Turn the capture hooks on (idempotent)."""
    global _ENABLED
    _ENABLED = True
    _arm_atexit()


def disable():
    """Turn the capture hooks off; captured reports are kept."""
    global _ENABLED
    _ENABLED = False


def enabled():
    return _ENABLED


def report_dir():
    """Report directory from ``MXNET_TPU_PROFILING_DIR`` (empty string
    when unset -- callers pass an explicit dir then)."""
    return os.environ.get("MXNET_TPU_PROFILING_DIR", "")


def _arm_atexit():
    """With a report dir configured, persist everything captured when
    the process exits (the JSONL-sink analog for cost reports)."""
    global _atexit_armed
    if _atexit_armed or not report_dir():
        return
    import atexit

    def _flush():
        try:
            if _ENABLED:
                save_reports()
        except Exception:
            pass
    atexit.register(_flush)
    _atexit_armed = True


# -- capture surface (called by the instrumented hot paths) ------------

def capture_jit(label, fn, args, key=None, kind="jit", **meta):
    """Register a jitted callable + example args for lazy cost
    analysis.  Dedupes on ``key``; the expensive lower+compile+parse
    happens at ``reports()`` / ``save_reports()`` time, never on the
    training hot path.  ``args`` are abstracted to ShapeDtypeStructs
    immediately, so no device buffer is kept alive."""
    from . import store
    store.register(key if key is not None else (label,), label, fn, args,
                   kind=kind, **meta)


def record_step(label, seconds, items=None):
    """Record one measured step wall time for ``label`` (feeds the
    roofline's achieved-vs-peak numbers)."""
    from . import store
    store.record_step(label, seconds, items=items)


def reports():
    """Materialize every pending capture and return the list of
    CostReport dicts (step stats + roofline attached where known)."""
    from . import store
    return store.reports()


def combined_report():
    """One combined report dict (steps + executables + category
    rollup) -- the artifact ``mxprof report``/``diff`` consume."""
    from . import store
    return store.combined()


def flops_per_step(label=None):
    """FLOPs of one dispatch of the labeled captured executable
    (default: the first train_step) -- the goodput ledger's
    window-flops source.  None when nothing matches."""
    from . import store
    return store.flops_per_step(label)


def save_reports(dirpath=None):
    """Write per-executable ``*.cost.json`` files plus the combined
    ``report.json`` under ``dirpath`` (default: the env report dir).
    Returns the combined report path."""
    from . import store
    return store.save(dirpath)


def reset():
    """Drop captured reports, pending specs and step times (test
    isolation)."""
    from . import store
    store.clear()


def report_for(obj, label=None, step_time_s=None, items_per_step=None):
    """CostReport for an object exposing ``_last_call = (fn, args)``
    (``parallel.TrainStep`` does) or for a ``(fn, args)`` tuple.
    Synchronous -- used by bench.py to persist artifacts without the
    store.  Returns None when nothing was dispatched yet."""
    from . import cost, roofline
    last = obj if isinstance(obj, tuple) else getattr(obj, "_last_call",
                                                     None)
    if last is None:
        return None
    fn, args = last
    rep = cost.analyze_jit(fn, args, label=label or "train_step")
    if rep is not None and step_time_s:
        rep["step"] = {"count": 1, "mean_s": step_time_s,
                       "min_s": step_time_s, "max_s": step_time_s,
                       "total_s": step_time_s}
        rep["roofline"] = roofline.build(rep, step_time_s,
                                         items_per_step=items_per_step)
    return rep


# env arming (read directly, matching the package's != "0" convention;
# the typed registry view lives in mxnet_tpu/env.py).
# MXNET_TPU_SHARD_CHECK rides the same capture surface: the sharding
# sanitizer's collective-contract audit (analysis/sharding.py) reads
# registered executables from this store, so arming it arms capture.
if os.environ.get("MXNET_TPU_PROFILING", "0") != "0" or \
        os.environ.get("MXNET_TPU_SHARD_CHECK", "0") != "0":
    enable()
