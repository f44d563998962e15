"""Runtime feature detection (reference: ``python/mxnet/runtime.py ::
Features`` over ``src/libinfo.cc``).

The reference reports compile-time flags (CUDA, MKLDNN, OPENMP, ...).
Here features are runtime properties of the JAX/XLA substrate: which
PJRT backends are live, whether a TPU is attached, which optional
subsystems (Pallas kernels, native recordio) loaded.
"""
from __future__ import annotations

from collections import namedtuple

Feature = namedtuple("Feature", ["name", "enabled"])


def _detect():
    import jax

    def has_backend(name):
        try:
            return len(jax.devices(name)) > 0
        except Exception:
            return False

    tpu = has_backend("tpu")
    feats = {
        "TPU": tpu,
        "GPU": has_backend("gpu"),
        "CPU": True,
        "CUDA": False,          # by design: XLA/PJRT, not CUDA
        "CUDNN": False,
        "MKLDNN": False,        # XLA:CPU is the CPU backend
        "XLA": True,
        "PALLAS": _try_import("jax.experimental.pallas"),
        "BF16": True,           # native MXU dtype
        "INT64_TENSOR_SIZE": True,
        "SIGNAL_HANDLER": True,
        # cheap probe -- must not trigger a blocking g++ build
        "NATIVE_RECORDIO": _native_built(),
        "DIST_KVSTORE": True,   # jax.distributed + collectives
        "OPENMP": False,
        "F16C": True,
        # runtime telemetry subsystem (mx.telemetry): reports the LIVE
        # enable state, so feature_list() answers "is this run
        # instrumented" rather than "was it compiled in"
        "TELEMETRY": _telemetry_enabled(),
        # concurrency sanitizer (mx.sync): LIVE arm state, same
        # contract as the TELEMETRY row
        "TSAN": _tsan_enabled(),
        # compiled-step cost accounting (mx.profiling): LIVE enable
        # state, same contract as the TELEMETRY row
        "PROFILING": _profiling_enabled(),
        # sharding sanitizer compiled layer (analysis.sharding):
        # whether MXNET_TPU_SHARD_CHECK armed collective-contract
        # capture for this run
        "SHARD_CHECK": _shard_check_enabled(),
        # chaos fault injection (mx.chaos): LIVE arm state -- True only
        # inside a chaos.arm()/chaos.scenario() window, never in a
        # production process (no env var arms it)
        "CHAOS": _chaos_armed(),
        # non-finite sentinel (analysis.numerics): whether
        # MXNET_TPU_NUMERICS_CHECK armed the fused per-step isfinite
        # check + first-offender attribution for this run
        "NUMERICS": _numerics_check_enabled(),
        # live-buffer leak sentinel (analysis.memory): whether
        # MXNET_TPU_MEMORY_WATCH armed the per-window live-array
        # census + leak sentinel for this run
        "MEMORY_WATCH": _memory_watch_enabled(),
        # request/step tracing (mx.obs): LIVE arm state, same contract
        # as the TELEMETRY row
        "OBS_TRACE": _obs_tracing(),
        # goodput ledger (mx.obs.goodput): LIVE arm state of the
        # per-window step-time attribution + regression sentinel
        "OBS_GOODPUT": _obs_goodput(),
        # fleet observability plane (mx.obs.fleet): whether this
        # process publishes a discovery endpoint or runs a
        # FleetMonitor (MXNET_TPU_OBS_ENDPOINTS_DIR or a live monitor)
        "FLEET": _fleet_active(),
    }
    return {k: Feature(k, bool(v)) for k, v in feats.items()}


def _telemetry_enabled():
    from . import telemetry
    return telemetry.enabled()


def _obs_tracing():
    from . import obs
    return obs.tracing_enabled()


def _obs_goodput():
    from . import obs
    return obs.goodput_enabled()


def _fleet_active():
    from .obs import fleet
    return fleet.active()


def _tsan_enabled():
    from . import sync
    return sync.tsan_enabled()


def _profiling_enabled():
    from . import profiling
    return profiling.enabled()


def _chaos_armed():
    from . import chaos
    return chaos.armed()


def _shard_check_enabled():
    # env-read directly (the sharding module's shard_check_enabled()
    # reads the same variable); importing mxnet_tpu.analysis here would
    # drag the whole lint stack into feature probing
    import os
    return os.environ.get("MXNET_TPU_SHARD_CHECK", "0") != "0"


def _numerics_check_enabled():
    # env-read directly (analysis.numerics.check_enabled() reads the
    # same variable at import); importing mxnet_tpu.analysis here would
    # drag the whole lint stack into feature probing
    import os
    return os.environ.get("MXNET_TPU_NUMERICS_CHECK", "0") != "0"


def _memory_watch_enabled():
    # env-read directly (analysis.memory.watch_enabled() reads the
    # same variable at import); importing mxnet_tpu.analysis here would
    # drag the whole lint stack into feature probing
    import os
    return os.environ.get("MXNET_TPU_MEMORY_WATCH", "0") != "0"


def _try_import(mod):
    import importlib
    try:
        importlib.import_module(mod)
        return True
    except Exception:
        return False


def _native_built():
    try:
        from ._native import available
        return available()
    except Exception:
        return False


class Features(dict):
    """Reference: ``mx.runtime.Features()`` -- mapping of feature name to
    Feature(name, enabled) with ``is_enabled``."""

    def __init__(self):
        super().__init__(_detect())

    def is_enabled(self, feature_name):
        feature_name = feature_name.upper()
        if feature_name not in self:
            raise RuntimeError("unknown feature %r" % feature_name)
        return self[feature_name].enabled

    def __repr__(self):
        return "[%s]" % ", ".join(
            "✔ %s" % k if v.enabled else "✖ %s" % k
            for k, v in sorted(self.items()))


def feature_list():
    """Reference: ``libinfo_features``."""
    return list(Features().values())


def env_vars():
    """Every registered ``MXNET_*`` env var with its current (typed)
    value, default, and doc -- backed by the ``mx.env`` registry, so
    this listing and the generated doc page cannot drift from what the
    code reads."""
    from . import env as _env
    return _env.describe()
