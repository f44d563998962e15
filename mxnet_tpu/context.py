"""Device contexts: cpu / tpu (gpu maps to the accelerator if present).

TPU-native re-design of the reference's ``python/mxnet/context.py ::
Context, cpu(), gpu(), current_context()`` and ``include/mxnet/base.h ::
Context``.  A Context names a JAX device; NDArrays are placed on it with
``jax.device_put`` and ops run where their inputs live (XLA's async runtime
replaces the reference's per-device engine worker threads).
"""
from __future__ import annotations

import threading

import jax

from .base import MXNetError

__all__ = ["Context", "cpu", "gpu", "tpu", "cpu_pinned", "current_context",
           "num_gpus", "num_tpus", "gpu_memory_info", "DeviceType"]


class DeviceType:
    kCPU = 1
    kGPU = 2  # alias for the accelerator in this build
    kTPU = 2
    kCPUPinned = 3
    kCPUShared = 5


_DEVTYPE_NAMES = {1: "cpu", 2: "tpu", 3: "cpu_pinned", 5: "cpu_shared"}
_DEVTYPE_IDS = {"cpu": 1, "gpu": 2, "tpu": 2, "cpu_pinned": 3, "cpu_shared": 5}


def _accelerator_platforms():
    # Platforms that count as the "gpu/tpu" device type, in preference order.
    return ("tpu", "gpu", "cuda", "rocm")


def _jax_devices_for(dev_type_name):
    # Addressable devices only: in a multi-process world jax.devices()
    # spans every host, and placing data on another process's device is
    # an error (contexts are per-worker, like the reference).  Real
    # backend-initialization failures propagate with their root cause;
    # only "this platform is absent" is treated as empty.
    local = jax.local_devices()
    if dev_type_name == "cpu":
        cpus = [d for d in local if d.platform == "cpu"]
        if not cpus:
            try:
                cpus = jax.local_devices(backend="cpu")
            except RuntimeError:
                cpus = []
        return cpus
    for plat in _accelerator_platforms():
        devs = [d for d in local if d.platform == plat]
        if devs:
            return devs
    return []


class Context:
    """A device context (reference: ``context.py :: Context``).

    Supports the reference's thread-local ``with ctx:`` stack.  ``tpu`` is
    the first-class accelerator type per the north star; ``gpu`` is accepted
    as an alias so reference scripts run unchanged.
    """

    _default_ctx = threading.local()

    def __init__(self, device_type, device_id=0):
        if isinstance(device_type, Context):
            self.device_typeid = device_type.device_typeid
            self.device_id = device_type.device_id
        else:
            if device_type not in _DEVTYPE_IDS:
                raise MXNetError("unknown device type %r" % (device_type,))
            self.device_typeid = _DEVTYPE_IDS[device_type]
            self.device_id = device_id

    @property
    def device_type(self):
        return _DEVTYPE_NAMES[self.device_typeid]

    def __eq__(self, other):
        return isinstance(other, Context) and \
            self.device_typeid == other.device_typeid and \
            self.device_id == other.device_id

    def __hash__(self):
        return hash((self.device_typeid, self.device_id))

    def __repr__(self):
        return "%s(%d)" % (self.device_type, self.device_id)

    __str__ = __repr__

    def __enter__(self):
        if not hasattr(Context._default_ctx, "stack"):
            Context._default_ctx.stack = []
        Context._default_ctx.stack.append(self)
        return self

    def __exit__(self, *args):
        Context._default_ctx.stack.pop()

    # -- JAX mapping ---------------------------------------------------
    def jax_device(self):
        """The jax.Device this context names (raises if absent)."""
        name = "cpu" if self.device_typeid in (1, 3, 5) else "tpu"
        devs = _jax_devices_for(name)
        if not devs:
            raise MXNetError("no %s device available" % name)
        if self.device_id >= len(devs):
            raise MXNetError("%s(%d) out of range: %d device(s) present"
                             % (name, self.device_id, len(devs)))
        return devs[self.device_id]

    def empty_cache(self):
        """Reference: ``Context.empty_cache`` -- XLA manages HBM; no-op."""

    def memory_info(self):
        """(bytes_in_use, bytes_limit) for this device (reference:
        ``mx.context.gpu_memory_info``).  PJRT owns the allocator; this
        is its accounting surface.  A backend that keeps no statistics
        (the CPU client answers ``None``) reads (0, 0); a device that
        cannot be reached raises."""
        stats = self.jax_device().memory_stats()
        if not stats:
            return (0, 0)
        return (int(stats.get("bytes_in_use", 0)),
                int(stats.get("bytes_limit",
                              stats.get("bytes_reservable_limit", 0))))


def cpu(device_id=0):
    return Context("cpu", device_id)


def cpu_pinned(device_id=0):
    return Context("cpu_pinned", device_id)


def gpu(device_id=0):
    """Accelerator context; alias of :func:`tpu` in this build."""
    return Context("gpu", device_id)


def tpu(device_id=0):
    """First-class TPU context (the north star's ``mx.tpu()``)."""
    return Context("tpu", device_id)


def num_gpus():
    return len(_jax_devices_for("tpu"))


def num_tpus():
    return len(_jax_devices_for("tpu"))


def current_context():
    """Reference: ``context.py :: current_context`` (thread-local stack)."""
    stack = getattr(Context._default_ctx, "stack", None)
    if stack:
        return stack[-1]
    return Context("cpu", 0)


def gpu_memory_info(device_id=0):
    """(free, total) bytes on the accelerator (reference:
    ``mx.context.gpu_memory_info``; here the TPU's HBM accounting).
    (0, 0) when the backend reports no usable limit."""
    used, limit = tpu(device_id).memory_info()
    if limit <= 0:
        return (0, 0)
    return (max(limit - used, 0), limit)
