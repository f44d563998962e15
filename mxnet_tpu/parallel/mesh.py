"""Device mesh utilities.

TPU-native replacement for the reference's device topology layer
(``src/kvstore/gpu_topology.h`` tree schedules, NCCL communicators):
on TPU the ICI torus is addressed through a ``jax.sharding.Mesh`` and
XLA emits the collectives, so "topology-aware scheduling" reduces to
picking mesh axes (SURVEY.md §2.4).
"""
from __future__ import annotations

import contextlib
import threading
from collections import OrderedDict

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..base import MXNetError

__all__ = ["make_mesh", "Mesh", "NamedSharding", "PartitionSpec",
           "local_devices", "default_mesh", "global_mesh", "AXIS_ROLES",
           "put_replicated", "stage_process_local", "batch_sharded",
           "shard_over_batch"]

# Canonical mesh-axis vocabulary.  Axis names are arbitrary strings to
# XLA, but the parallel layers, the docs, and the sharding sanitizer
# (mxnet_tpu.analysis.sharding, rule ``mesh-axis-unknown``) all speak
# these five roles; a PartitionSpec naming an axis outside this table
# AND outside every Mesh/make_mesh construction in the linted tree is
# flagged, because XLA silently replicates over unknown axes instead of
# sharding.  Project-specific axes are declared simply by building a
# mesh with them.
AXIS_ROLES = OrderedDict([
    ("dp", "data parallel: batch dim sharded, gradients psum over ICI"),
    ("tp", "tensor (model) parallel: Megatron column/row weight splits"),
    ("pp", "pipeline parallel: stacked stage params, ppermute ring"),
    ("sp", "sequence/context parallel: ring-attention KV rotation"),
    ("ep", "expert parallel: stacked MoE experts, all-to-all dispatch"),
])


def local_devices(platform=None):
    if platform:
        try:
            return [d for d in jax.devices() if d.platform == platform] or \
                jax.devices(platform)
        except RuntimeError:
            return []
    return jax.devices()


def make_mesh(axes, devices=None):
    """Build a Mesh from ``{'dp': 4, 'tp': 2}``-style axis sizes.

    ``-1`` for one axis means "all remaining devices".  Axis order follows
    insertion order; put the fastest-varying (innermost, highest-bandwidth)
    axis last, as the scaling-book recipe recommends for ICI.
    """
    axes = OrderedDict(axes)
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    sizes = list(axes.values())
    if sizes.count(-1) > 1:
        raise MXNetError("only one mesh axis may be -1")
    known = int(np.prod([s for s in sizes if s != -1])) if sizes else 1
    if -1 in sizes:
        if n % known:
            raise MXNetError("cannot infer -1 axis: %d devices not divisible "
                             "by %d" % (n, known))
        sizes[sizes.index(-1)] = n // known
    total = int(np.prod(sizes))
    if total > n:
        raise MXNetError("mesh wants %d devices, only %d available"
                         % (total, n))
    mesh_devices = np.asarray(devices[:total]).reshape(sizes)
    return Mesh(mesh_devices, tuple(axes.keys()))


_default_mesh = None


def default_mesh():
    """A 1-D data-parallel mesh over all devices (cached)."""
    global _default_mesh
    if _default_mesh is None or \
            _default_mesh.devices.size != len(jax.devices()):
        _default_mesh = make_mesh({"dp": -1})
    return _default_mesh


_global_meshes = {}


def global_mesh(axes=None):
    """The ONE mesh a multi-host SPMD program runs over: every device
    of every process in the ``jax.distributed`` world (``jax.devices()``
    spans hosts once ``distributed_init`` ran).  Default axes:
    ``{"dp": -1}`` -- pure data parallel; pass e.g.
    ``{"dp": -1, "tp": 2}`` for a 2-D data x model mesh.  Cached per
    (axes, world size), so every caller -- ``TrainStep``, ``DeviceFeed``,
    checkpoint resharding -- agrees on one device order
    (docs/distributed.md)."""
    axes = OrderedDict(axes if axes is not None else {"dp": -1})
    if "dp" not in axes:
        raise MXNetError("global_mesh needs a 'dp' axis (got %r)"
                         % list(axes))
    key = (tuple(axes.items()), len(jax.devices()))
    mesh = _global_meshes.get(key)
    if mesh is None:
        mesh = _global_meshes[key] = make_mesh(axes)
    return mesh


def put_replicated(x, sharding):
    """Place one host/device value replicated onto a (possibly
    multi-host) sharding.  Single-process this is ``jax.device_put``;
    in a multi-process world a host value cannot be device_put onto
    non-addressable devices, so the global array is assembled from this
    process's addressable shards -- callers must have synchronized the
    value across ranks first (``distributed.host_broadcast_bucketed``),
    or ranks silently diverge."""
    if getattr(sharding, "is_fully_addressable", True):
        return jax.device_put(x, sharding)
    x = np.asarray(x)
    # make_array_from_callback's internal batched_device_put counts as
    # an IMPLICIT transfer under jax.transfer_guard("disallow"), but
    # this call IS the library's explicit placement primitive (morally
    # jax.device_put, which the guard exempts) -- allow it locally so
    # the guard stays armable over the steady-state step loop
    with jax.transfer_guard("allow"):
        return jax.make_array_from_callback(x.shape, sharding,
                                            lambda idx: x[idx])


def stage_process_local(x, sharding):
    """Land one PROCESS-LOCAL batch shard as its slice of the global
    array (``jax.make_array_from_process_local_data``): every process
    contributes its local batch and the result is the (nproc x local)
    global batch sharded per ``sharding``.  Single-process (or already
    correctly sharded) inputs take the plain ``device_put`` path.  The
    staging half of the one-program SPMD contract -- batches arrive
    pre-sharded, the compiled step never re-transfers."""
    if isinstance(x, jax.Array) and \
            x.sharding.is_equivalent_to(sharding, x.ndim):
        return x
    if getattr(sharding, "is_fully_addressable", True):
        return jax.device_put(
            x if isinstance(x, jax.Array) else np.asarray(x), sharding)
    x = np.asarray(x)
    # explicit staging primitive: see put_replicated's guard note
    with jax.transfer_guard("allow"):
        return jax.make_array_from_process_local_data(sharding, x)


# ----------------------------------------------------------------------
# Kernels under a sharded batch
# ----------------------------------------------------------------------
# XLA's SPMD partitioner splits ordinary HLO over the mesh by itself,
# but not a Mosaic (Pallas/TPU) custom call: the TPU lowering refuses
# one inside a partitioned program ("Mosaic kernels cannot be
# automatically partitioned").  ``TrainStep`` therefore says, while its
# step is traced, which mesh axis the batch is split over, and a kernel
# whose leading dimension is batch-major runs itself per shard.

_batch_scope = threading.local()


@contextlib.contextmanager
def batch_sharded(mesh, axis_name):
    """Trace-time scope: the batch of the program being traced is
    sharded over ``axis_name`` of ``mesh`` (no-op for ``mesh=None``)."""
    prev = getattr(_batch_scope, "value", None)
    _batch_scope.value = (mesh, axis_name) if mesh is not None else None
    try:
        yield
    finally:
        _batch_scope.value = prev


def shard_over_batch(fn, *arrays):
    """``fn(*arrays)`` -- run per batch shard through ``jax.shard_map``
    when the program being traced shards its batch over more than one
    device (:func:`batch_sharded`), plainly otherwise.  Every array's
    leading dimension must be batch-major; so must the result's."""
    scope = getattr(_batch_scope, "value", None)
    if scope is None or scope[0].shape[scope[1]] == 1:
        return fn(*arrays)
    mesh, axis = scope
    spec = PartitionSpec(axis)
    return jax.shard_map(fn, mesh=mesh, in_specs=(spec,) * len(arrays),
                         out_specs=spec, check_vma=False)(*arrays)
