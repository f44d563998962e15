"""KVStore: the parameter-store API over XLA collectives.

TPU-native re-design of the reference's ``src/kvstore/`` stack
(``kvstore_local.h :: KVStoreLocal``, ``comm.h :: CommDevice``,
``kvstore_dist.h :: KVStoreDist`` + ps-lite, ``kvstore_nccl.h``).

Design (SURVEY.md §5 "Distributed communication backend"):

- ``local`` / ``device`` / ``nccl``: single-process.  There are no
  per-device gradient copies to reduce -- data-parallel gradients live as
  ONE sharded jax.Array whose reduction happened *inside* the compiled
  step via ``psum`` over ICI (see ``mxnet_tpu/parallel``).  Push/pull
  therefore aggregates pushed versions and applies the optimizer, giving
  the reference's ``update_on_kvstore`` semantics without a comm step.
- ``dist_sync`` / ``dist_device_sync`` / ``dist_async``: multi-process.
  ``jax.distributed`` + PJRT replace the ps-lite scheduler/Van.  On the
  TRAINING HOT PATH the dist kvstore is a **veneer over the compiled
  SPMD step** (docs/distributed.md): ``parallel.TrainStep`` over the
  global mesh reduces gradients IN-GRAPH (GSPMD inserts the
  ``all-reduce``; XLA's latency-hiding scheduler overlaps it with
  backprop), so ``push``/``pull`` move ZERO host bytes per step --
  what remains of the kvstore's dist role is the init-time rank-0
  parameter broadcast (``Trainer._sync_initial_params``, one bucketed
  collective) and optimizer-state save/load.  The eager
  ``push``/``pull``/``pushpull`` verbs below still reduce across
  processes (host collectives, bucketed via ``pushpull_bucket``) for
  reference-API compatibility and non-compiled loops.  The
  "server-side optimizer" of the reference (``kvstore_dist_server.h ::
  DataHandleEx``) becomes a replicated update after the allreduce --
  same contract (workers see identical post-update weights), no server
  role needed.
  ``dist_async`` shares this path by DESIGN: the reference's async mode
  exists to hide ps-lite server latency by applying per-worker pushes
  without aggregation (stale weights as the price); with XLA's async
  dispatch the allreduce itself is non-blocking until a sync point, so
  the latency-hiding is already had WITHOUT giving up synchronous
  semantics -- async here means async dispatch, not weight staleness.
- Gradient compression hook mirrors ``gradient_compression.cc`` (2bit with
  error feedback) as a pre-allreduce transform.
"""
from __future__ import annotations

import pickle
import time

import numpy as np

import jax
import jax.numpy as jnp

from . import telemetry as _telemetry
from .base import MXNetError
from .ndarray import NDArray
from .ndarray import sparse as _sp
from . import optimizer as opt

__all__ = ["KVStore", "create"]


def _allreduce_across_processes(x):
    """Sum a host-local array across all processes (DCN path): backend
    collectives on multi-process backends (TPU pods), the coordination
    service otherwise (``distributed.host_allreduce``)."""
    from .distributed import host_allreduce, world
    if world()[0] == 1:
        return x
    return host_allreduce(x, average=False)


def _value_nbytes(value):
    """Payload size of a pushed/pulled value from shape/dtype metadata
    only -- never forces a device sync.  Lists sum; sparse and exotic
    values degrade to 0 rather than sync or raise."""
    if isinstance(value, (list, tuple)):
        return sum(_value_nbytes(v) for v in value)
    try:
        shape, dtype = value.shape, value.dtype
        return int(np.prod(shape)) * np.dtype(dtype).itemsize \
            if shape else np.dtype(dtype).itemsize
    except Exception:
        return 0


class _TwoBitCompression:
    """2-bit gradient compression with error feedback (reference:
    ``src/kvstore/gradient_compression.cc``)."""

    def __init__(self, threshold=0.5):
        self.threshold = float(threshold)
        self._residual = {}

    def compress_decompress(self, key, grad):
        r = self._residual.get(key)
        g = grad if r is None else grad + r
        t = self.threshold
        q = jnp.where(g >= t, t, jnp.where(g <= -t, -t, 0.0))
        self._residual[key] = g - q
        return q


class KVStore:
    """Reference: ``include/mxnet/kvstore.h :: KVStore`` /
    ``python/mxnet/kvstore.py``."""

    def __init__(self, kv_type="local"):
        self.type = kv_type
        self._store = {}      # key -> NDArray (the "server" copy)
        self._updater = None
        self._optimizer = None
        self._opt_states = {}
        self._compression = None
        self._is_dist = kv_type.startswith("dist")

    # -- topology ------------------------------------------------------
    @property
    def rank(self):
        from .distributed import world
        return world()[1] if self._is_dist else 0

    @property
    def num_workers(self):
        from .distributed import world
        return world()[0] if self._is_dist else 1

    # -- core API ------------------------------------------------------
    def _keyify(self, key):
        return key if isinstance(key, (str, int)) else str(key)

    def init(self, key, value):
        if isinstance(key, (list, tuple)):
            for k, v in zip(key, value):
                self.init(k, v)
            return
        key = self._keyify(key)
        if key in self._store:
            return
        self._store[key] = value.copy() if isinstance(value, NDArray) \
            else NDArray(value)

    def _merge(self, value):
        """Sum a list of pushed values (the reference's CommDevice
        reduce).  All-row-sparse lists merge by row union, staying
        sparse; any dense operand densifies the sum.  Returns a raw
        jnp array for dense results, a sparse array otherwise."""
        if isinstance(value, (list, tuple)):
            if any(isinstance(v, _sp.RowSparseNDArray) for v in value):
                merged = value[0]
                for v in value[1:]:
                    merged = _sp.elemwise_add(merged, v)
                return merged._data if isinstance(merged, NDArray) \
                    else merged
            merged = value[0]._data
            for v in value[1:]:
                merged = merged + v._data
            return merged
        if isinstance(value, _sp.BaseSparseNDArray):
            return value
        return value._data

    def _reduce_for_update(self, key, value):
        """Merge + compress + cross-process reduce one pushed value.
        Returns ``(merged, sparse_grad)``; sparse grads skip compression
        and densify before the dist collective (row unions differ per
        worker; the collective needs a static shape)."""
        merged = self._merge(value)
        sparse_grad = isinstance(merged, _sp.BaseSparseNDArray)
        if not sparse_grad and self._compression is not None:
            merged = self._compression.compress_decompress(key, merged)
        if self._is_dist and sparse_grad:
            merged = merged.todense()._data
            sparse_grad = False
        if self._is_dist:
            merged = _allreduce_across_processes(merged)
        return merged, sparse_grad

    def push(self, key, value, priority=0):
        if isinstance(key, (list, tuple)):
            for k, v in zip(key, value):
                self.push(k, v, priority)
            return
        key = self._keyify(key)
        if key not in self._store:
            raise MXNetError("kvstore key %r not initialized" % key)
        if _telemetry._ENABLED:
            _telemetry.hooks.kv_op("push", _value_nbytes(value))
        merged, sparse_grad = self._reduce_for_update(key, value)
        if self._updater is not None:
            grad = merged if sparse_grad else NDArray(merged)
            self._updater(key, grad, self._store[key])
        else:
            pending = getattr(self, "_pending", None)
            if pending is None:
                self._pending = pending = {}
            if key not in pending:
                pending[key] = merged
            elif sparse_grad or isinstance(pending[key],
                                           _sp.BaseSparseNDArray):
                a, b = pending[key], merged
                a = NDArray(a) if not isinstance(
                    a, (_sp.BaseSparseNDArray, NDArray)) else a
                b = NDArray(b) if not isinstance(
                    b, (_sp.BaseSparseNDArray, NDArray)) else b
                s = _sp.elemwise_add(a, b)
                pending[key] = s if isinstance(
                    s, _sp.BaseSparseNDArray) else s._data
            else:
                pending[key] = pending[key] + merged

    def pull(self, key, out=None, priority=0, ignore_sparse=True):
        if isinstance(key, (list, tuple)):
            for k, o in zip(key, out):
                self.pull(k, o, priority)
            return
        key = self._keyify(key)
        if key not in self._store:
            raise MXNetError("kvstore key %r not initialized" % key)
        if _telemetry._ENABLED:
            _telemetry.hooks.kv_op("pull", _value_nbytes(self._store[key]))
        pending = getattr(self, "_pending", {})
        if self._updater is None and key in pending:
            src = pending.pop(key)
            if isinstance(src, _sp.BaseSparseNDArray):
                src = src.todense()._data
        else:
            src = self._store[key]._data
        outs = out if isinstance(out, (list, tuple)) else [out]
        for o in outs:
            o._data = src
        return out

    def pushpull(self, key, value, out=None, priority=0):
        """Fused push+pull (reference: ``MXKVStorePushPullEx``).

        Without an optimizer this is allreduce semantics on gradients:
        out <- sum over workers(value).
        """
        if isinstance(key, (list, tuple)):
            outs = out if out is not None else [None] * len(key)
            for k, v, o in zip(key, value, outs):
                self.pushpull(k, v, o, priority)
            return
        key = self._keyify(key)
        # allreduce wall time is DISPATCH time under async XLA; the
        # reduce itself overlaps compute and only lands at a sync point
        t0 = time.perf_counter() if _telemetry._ENABLED else None
        merged, sparse_grad = self._reduce_for_update(key, value)
        if self._updater is not None:
            if key not in self._store:
                raise MXNetError("kvstore key %r not initialized" % key)
            grad = merged if sparse_grad else NDArray(merged)
            self._updater(key, grad, self._store[key])
            result = self._store[key]._data
        else:
            result = merged.todense()._data if sparse_grad else merged
        if t0 is not None:
            _telemetry.hooks.kv_op("pushpull", _value_nbytes(value),
                                   time.perf_counter() - t0)
        if out is not None:
            outs = out if isinstance(out, (list, tuple)) else [out]
            for o in outs:
                o._data = result
        return out

    def pushpull_bucket(self, keys, values, outs, priority=0):
        """Bucketed fused push+pull over a LIST of keys: dense values
        merge per key, coalesce into one flattened buffer per dtype,
        and cross the process boundary in ONE collective
        (``distributed.host_allreduce_bucketed``) instead of one RPC
        per tensor -- the legacy eager path's analog of the compiled
        step's single in-graph all-reduce.  Telemetry records ONE
        ``kvstore.pushpull`` call for the whole bucket (the call-count
        drop ``kv.bytes`` proves).  Keys with sparse gradients or an
        installed updater fall back to per-key :meth:`pushpull`."""
        keys = [self._keyify(k) for k in keys]
        t0 = time.perf_counter() if _telemetry._ENABLED else None
        dense_idx, merged_vals = [], []
        for j, (key, value) in enumerate(zip(keys, values)):
            if self._updater is not None:
                self.pushpull(key, value, outs[j], priority)
                continue
            merged, sparse_grad = self._merge(value), False
            sparse_grad = isinstance(merged, _sp.BaseSparseNDArray)
            if sparse_grad:
                merged = merged.todense()._data
            if self._compression is not None:
                merged = self._compression.compress_decompress(key, merged)
            dense_idx.append(j)
            merged_vals.append(merged)
        if not dense_idx:
            return outs
        from .distributed import world
        if self._is_dist and world()[0] > 1:
            from .distributed import host_allreduce_bucketed
            merged_vals = host_allreduce_bucketed(merged_vals)
        total = 0
        for j, res in zip(dense_idx, merged_vals):
            res = res._data if isinstance(res, NDArray) else res
            total += _value_nbytes(values[j])
            os_ = outs[j] if isinstance(outs[j], (list, tuple)) \
                else [outs[j]]
            for o in os_:
                o._data = res
        if t0 is not None:
            _telemetry.hooks.kv_op("pushpull", total,
                                   time.perf_counter() - t0)
        return outs

    def row_sparse_pull(self, key, out=None, priority=0, row_ids=None):
        """Pull ONLY the requested rows (reference: ``PullRowSparse``).
        Moves k rows, not the full table: the embedding-scale win the
        row-sparse type exists for.  ``out`` may be a RowSparseNDArray
        (filled sparsely) or a dense NDArray (rows scattered, rest 0);
        with ``out=None`` a RowSparseNDArray is returned."""
        key = self._keyify(key)
        if key not in self._store:
            raise MXNetError("kvstore key %r not initialized" % key)
        if row_ids is None:
            return self.pull(key, out, priority)
        from .distributed import _place
        rows = row_ids._data if isinstance(row_ids, NDArray) else row_ids
        full = self._store[key]._data
        # dedup host-side (reference PullRowSparse dedups): duplicate ids
        # would double rows under the sparse todense() scatter-add.
        # Place the ids WITH the table: an unplaced jnp.asarray would
        # put them on the DEFAULT device, which need not be the
        # table's, and every pull would pay a cross-device copy before
        # the gather.  A DEVICE target, not
        # the table's sharding -- the 1-D id vector can't take a
        # dim-partitioned rank-2 sharding.
        dev = next(iter(full.devices())) \
            if isinstance(full, jax.Array) else None
        rows = _place(np.unique(np.asarray(rows).astype(np.int32)), dev)
        picked_rows = full[rows]                      # (k, ...) gather only
        if out is None:
            return _sp.RowSparseNDArray(picked_rows, rows,
                                        full.shape, full.dtype)
        outs = out if isinstance(out, (list, tuple)) else [out]
        for o in outs:
            if isinstance(o, _sp.RowSparseNDArray):
                o._rs_data = picked_rows
                o._rs_indices = rows
            else:
                o._data = jnp.zeros_like(full).at[rows].set(picked_rows)
        return out

    # -- optimizer on the store (reference: server-side optimizer) -----
    def set_optimizer(self, optimizer):
        """Reference: ``KVStore.set_optimizer`` -- pickles the optimizer to
        servers; here it installs the updater on the replicated store."""
        pickled = pickle.dumps(optimizer)  # keep the serialization contract
        self._optimizer = pickle.loads(pickled)
        self._updater = opt.get_updater(self._optimizer)

    def set_gradient_compression(self, compression_params):
        ctype = compression_params.get("type", "2bit")
        if ctype != "2bit":
            raise MXNetError("unsupported compression type %r" % ctype)
        self._compression = _TwoBitCompression(
            compression_params.get("threshold", 0.5))

    def save_optimizer_states(self, fname, dump_optimizer=False):
        if self._updater is None:
            raise MXNetError("no optimizer set on kvstore")
        # atomic tmp+fsync+rename (mx.checkpoint): never a torn .states
        from .checkpoint.core import atomic_write_bytes
        atomic_write_bytes(fname, self._updater.get_states(dump_optimizer))

    def load_optimizer_states(self, fname):
        if self._updater is None:
            raise MXNetError("no optimizer set on kvstore")
        with open(fname, "rb") as f:
            self._updater.set_states(f.read())

    def barrier(self):
        if self._is_dist:
            from .distributed import barrier
            barrier("kvstore_barrier")


def create(name="local"):
    """Reference: ``kvstore.create``; accepted types: local, device, nccl,
    dist_sync, dist_device_sync, dist_async, dist."""
    if name not in ("local", "device", "nccl", "dist", "dist_sync",
                    "dist_async", "dist_device_sync", "horovod"):
        raise MXNetError("unknown kvstore type %r" % name)
    return KVStore(name)
