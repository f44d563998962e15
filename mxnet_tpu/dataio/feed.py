"""Device feed: overlapped host->device staging behind any data source.

The reference hides input cost behind compute with
``iter_prefetcher.h :: PrefetcherIter`` plus engine-ordered copies; the
host-side analogs here (``io.PrefetchingIter``,
``DataLoader._threaded_iter``) only overlap *decode*, so every training
loop still paid a synchronous ``device_put`` per batch on the consumer
thread.  ``DeviceFeed`` moves that transfer off the hot path:

- a background producer thread pulls host batches from the wrapped
  source and issues **async** ``jax.device_put`` (PJRT returns
  immediately; the DMA proceeds while the consumer trains the previous
  batch), through a bounded double buffer (``depth``, default 2);
- batches ship in their COMPACT dtype (uint8 stays uint8 over the
  wire); a jitted :class:`~mxnet_tpu.dataio.transforms.DeviceTransform`
  does cast/normalize/flip/crop after landing;
- with a ``mesh``/``sharding``, staging lands shards directly
  (``jax.make_array_from_process_local_data`` when running
  multi-process, ``device_put`` with the sharding otherwise);
- error/shutdown semantics follow the checkpoint/bulk precedent:
  producer exceptions re-raise at the consumer's next ``next()``,
  ``close()`` joins the thread, ``reset()`` restarts cleanly -- no
  leaked daemon state between epochs.  The producer holds the feed
  only through a *weak* reference while idle/blocked, and a
  ``weakref.finalize`` stops it when the consumer abandons iteration
  mid-epoch without ``close()`` (GC), so a full staging buffer can
  never strand the thread (ISSUE 5 satellite; leak test in
  tests/test_dataio.py).

Telemetry (``feed.*`` instruments, docs/observability.md): producer
busy time, consumer wait, bytes staged, and the per-epoch overlap
fraction ``1 - wait/busy`` -- the library form of the number
``bench_resnet50_e2e`` used to hand-roll.
"""
from __future__ import annotations

import os
import queue
import threading
import time
import weakref

import numpy as np

import jax
from jax.sharding import NamedSharding, PartitionSpec

from .. import obs as _obs
from .. import sync as _sync
from .. import telemetry as _telemetry
from ..base import MXNetError
from ..context import Context
from ..ndarray import NDArray
from .. import random as _random_mod

__all__ = ["DeviceFeed", "DeviceBatch"]

_END = object()


def _feed_depth(depth):
    if depth is not None:
        return max(1, int(depth))
    return max(1, int(os.environ.get("MXNET_TPU_FEED_DEPTH", "2")))


def _feed_compact(compact):
    if compact is not None:
        return bool(compact)
    return os.environ.get("MXNET_TPU_FEED_COMPACT", "1") != "0"


class DeviceBatch:
    """One device-resident batch yielded by :class:`DeviceFeed`.

    ``arrays`` are post-transform NDArrays on the target device/sharding;
    ``raw`` keeps the staged (pre-transform, compact-dtype) jax arrays so
    callers can retain cheap uint8 slabs and re-expand on device later
    (``DeviceFeed.apply_transform``).  Unpacks like the host loader's
    tuple: ``for x, y in feed`` works.
    """

    __slots__ = ("arrays", "pad", "raw")

    def __init__(self, arrays, pad=0, raw=None):
        self.arrays = tuple(arrays)
        self.pad = pad
        self.raw = raw

    @property
    def data(self):
        return self.arrays[0]

    @property
    def label(self):
        return self.arrays[1] if len(self.arrays) > 1 else None

    def __iter__(self):
        return iter(self.arrays)

    def __getitem__(self, i):
        return self.arrays[i]

    def __len__(self):
        return len(self.arrays)

    def __repr__(self):
        return "DeviceBatch(%s, pad=%d)" % (
            ", ".join("%sx%s" % (a.shape, a.dtype) for a in self.arrays),
            self.pad)


class DeviceFeed:
    """Wrap any batch source into an overlapped device-resident stream.

    ``source`` may be a legacy ``DataIter`` (``.next()`` ->
    ``DataBatch``), an ``ImageIter`` (its ``next_np`` zero-copy path is
    used), a ``gluon.data.DataLoader``, or any iterable/iterator of
    host batches (arrays or tuples of arrays).

    One of ``ctx``/``mesh``/``sharding`` picks the landing placement:
    a :class:`~mxnet_tpu.context.Context` (default: first accelerator,
    else cpu), a ``jax.sharding.Mesh`` (batch axis sharded over
    ``axis_name``), or an explicit ``NamedSharding``.

    The feed is itself an iterator: ``next()`` blocks on the staging
    queue, applies the jitted ``transform`` to the data component, and
    returns a :class:`DeviceBatch`.  ``reset()`` restarts the producer
    (resetting a resettable source) for the next epoch; ``close()``
    joins the thread.
    """

    def __init__(self, source, ctx=None, mesh=None, sharding=None,
                 transform=None, depth=None, compact=None, batch_axis=0,
                 axis_name="dp"):
        self._source = source
        self._depth = _feed_depth(depth)
        self._compact = _feed_compact(compact)
        self.transform = transform
        self._batch_axis = batch_axis
        self._axis_name = axis_name
        self._mesh = mesh
        self._sharding = sharding
        self._device = None
        if sharding is None and mesh is None:
            if ctx is None:
                from ..context import num_tpus, tpu, cpu
                ctx = tpu() if num_tpus() else cpu()
            self._device = ctx.jax_device() if isinstance(ctx, Context) \
                else ctx
        self._queue = None
        self._thread = None
        self._stop = None
        self._error = None
        self._finalizer = None
        # producer busy / consumer wait / bytes staged / batches --
        # always maintained (a few float adds per BATCH, not per op) so
        # overlap_frac() works with telemetry off; mirrored into the
        # feed.* instruments when telemetry is on.  Producer and
        # consumer both write, so every access holds the stats lock.
        self._stats = {"producer_busy": 0.0, "consumer_wait": 0.0,
                       "bytes_staged": 0, "batches": 0}
        self._stats_lock = _sync.Lock(name="feed.stats")
        self._start()

    # -- placement -----------------------------------------------------
    def _placement(self, ndim):
        """Landing target for one staged leaf of rank ``ndim``."""
        if self._sharding is not None:
            return self._sharding
        if self._mesh is not None:
            spec = [None] * ndim
            if ndim:
                spec[self._batch_axis] = self._axis_name
            return NamedSharding(self._mesh, PartitionSpec(*spec))
        return self._device

    def _stage(self, x):
        """Issue the async transfer for one leaf; returns
        ``(device_array, bytes_staged)``."""
        if isinstance(x, NDArray):
            x = x._data
        if isinstance(x, jax.Array):
            target = self._placement(x.ndim)
            if not isinstance(target, NamedSharding) \
                    and target in x.devices():
                return x, 0          # already resident: no re-transfer
            return jax.device_put(x, target), x.nbytes
        x = np.ascontiguousarray(x)
        if self._precast is not None and x.dtype != self._precast:
            # compact staging disabled: pay the cast (and the fat
            # transfer) host-side, mainly for A/B numerics runs
            x = x.astype(self._precast)
        target = self._placement(x.ndim)
        if isinstance(target, NamedSharding):
            # the shared SPMD staging path (parallel.mesh): on a
            # multi-host global mesh the local batch lands as its slice
            # of the global array via make_array_from_process_local_data
            # -- the same pre-sharded batches TrainStep consumes with
            # no re-transfer (docs/distributed.md)
            from ..parallel.mesh import stage_process_local
            return stage_process_local(x, target), x.nbytes
        return jax.device_put(x, target), x.nbytes

    @property
    def _precast(self):
        if self._compact or self.transform is None:
            return None
        return getattr(self.transform, "dtype", None)

    # -- source normalization ------------------------------------------
    def _make_next_batch(self):
        """One-batch step ``() -> (tuple_of_host_arrays, pad)`` closing
        over the *source* only -- never the feed.  The producer derefs
        the feed weakly per batch, so a consumer that abandons
        iteration (GC without close()) releases the thread instead of
        being kept alive by it."""
        src = self._source
        if hasattr(src, "next_np"):          # ImageIter zero-copy path
            def next_batch():
                data, labels, pad = src.next_np()
                return (data, labels), pad
        elif hasattr(src, "next") and hasattr(src, "reset"):  # DataIter
            def next_batch():
                batch = src.next()
                arrays = tuple(batch.data) + tuple(batch.label or ())
                return arrays, getattr(batch, "pad", 0) or 0
        else:
            it = self._src_iter

            def next_batch():
                item = next(it)
                if isinstance(item, (tuple, list)):
                    return tuple(item), 0
                return (item,), 0
        return next_batch

    # -- producer ------------------------------------------------------
    @staticmethod
    def _producer_put(q, stop, item):
        """Blocking put that stays responsive to close()/finalize."""
        while not stop.is_set():
            try:
                q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def _start(self):
        self._queue = q = queue.Queue(self._depth)
        self._stop = stop = _sync.Event(name="feed.stop")
        self._error = None
        # a plain iterable is consumed through one iterator per epoch
        self._src_iter = iter(self._source) \
            if not (hasattr(self._source, "next_np")
                    or hasattr(self._source, "next")) else None
        next_batch = self._make_next_batch()
        wself = weakref.ref(self)

        def run():
            from .. import chaos as _chaos
            out = _END
            try:
                while not stop.is_set():
                    # chaos fail point on the input path (ISSUE 14):
                    # a seeded sleep rule here stalls the producer so
                    # the goodput ledger's input_wait category must
                    # dominate -- CI's obs stage injects it and gates
                    # that the sentinel names input_wait.  Disarmed:
                    # one module-flag check.
                    _chaos.fail_point("feed.produce")
                    # busy window = host batch production (decode/
                    # batchify) + async transfer issue; the blocking
                    # put below is backpressure, not work, and stays
                    # outside it
                    t0 = time.perf_counter()
                    stage = _obs.span("mx.feed.stage")
                    with stage:
                        try:
                            arrays, pad = next_batch()
                        except StopIteration:
                            break
                        feed = wself()
                        if feed is None:     # consumer GC'd mid-epoch
                            return
                        staged, nbytes = [], 0
                        for a in arrays:
                            d, nb = feed._stage(a)
                            staged.append(d)
                            nbytes += nb
                        stage.set(bytes=nbytes)
                    busy = time.perf_counter() - t0
                    with feed._stats_lock:
                        feed._stats["producer_busy"] += busy
                        feed._stats["bytes_staged"] += nbytes
                        feed._stats["batches"] += 1
                    # drop the strong ref BEFORE the blocking put: while
                    # parked on a full buffer this thread must not be
                    # what keeps the feed alive
                    feed = None
                    if _telemetry._ENABLED:
                        _telemetry.hooks.feed_produce(busy, nbytes)
                    if not DeviceFeed._producer_put(
                            q, stop, (tuple(staged), pad)):
                        return
            except BaseException as e:  # re-raised at consumer next()
                out = e
            DeviceFeed._producer_put(q, stop, out)

        self._thread = threading.Thread(target=run, daemon=True,
                                        name="mxnet_tpu.DeviceFeed")
        # GC of an abandoned feed wakes the producer out of a full
        # buffer; close() detaches this and does the full join
        self._finalizer = weakref.finalize(self, _release_producer,
                                           q, stop)
        self._thread.start()

    # -- consumer ------------------------------------------------------
    def __iter__(self):
        return self

    def __next__(self):
        return self.next()

    def next(self):
        if self._error is not None:
            raise self._error
        t0 = time.perf_counter()
        try:
            item = self._queue.get_nowait()
        except queue.Empty:
            with _obs.span("mx.feed.wait"):
                item = self._queue.get()
        wait = time.perf_counter() - t0
        with self._stats_lock:
            self._stats["consumer_wait"] += wait
        if _telemetry._ENABLED:
            _telemetry.hooks.feed_wait(wait)
        if item is _END:
            self._finish_epoch()
            raise StopIteration
        if isinstance(item, BaseException):
            self._error = item
            self._finish_epoch()
            raise item
        staged, pad = item
        arrays = list(staged)
        if self.transform is not None:
            arrays[0] = self.transform(arrays[0], _random_mod.next_key())
        return DeviceBatch([NDArray(a) for a in arrays], pad=pad,
                           raw=staged)

    def _finish_epoch(self):
        th, self._thread = self._thread, None
        if th is not None:
            th.join(timeout=10)
        frac = self.overlap_frac()
        if _telemetry._ENABLED:
            _telemetry.hooks.feed_overlap(frac)

    def apply_transform(self, staged):
        """Re-run the jitted transform on a retained raw (compact) device
        array -- lets callers keep uint8 slabs resident and expand per
        use (the bench's staged-epochs pattern)."""
        if self.transform is None:
            return staged
        return self.transform(staged, _random_mod.next_key())

    # -- stats ---------------------------------------------------------
    def stats(self):
        """Copy of the feed counters (seconds / bytes / batches)."""
        with self._stats_lock:
            return dict(self._stats)

    def overlap_frac(self):
        """Share of producer (decode+transfer) time hidden behind
        consumer compute: ``1 - consumer_wait / producer_busy``."""
        with self._stats_lock:
            busy = self._stats["producer_busy"]
            wait = self._stats["consumer_wait"]
        if busy <= 0:
            return 0.0
        return max(0.0, 1.0 - wait / busy)

    # -- lifecycle -----------------------------------------------------
    def reset(self):
        """Stop the in-flight epoch (if any), reset a resettable source,
        and restart the producer for the next epoch."""
        self.close()
        if hasattr(self._source, "reset"):
            self._source.reset()
        elif self._src_iter is not None:
            # a bare iterator cannot be rewound; an iterable can
            try:
                iter(self._source)
            except TypeError:
                raise MXNetError(
                    "DeviceFeed.reset: source is not resettable")
        self._start()

    def close(self):
        """Join the producer thread; idempotent, safe mid-epoch."""
        if self._finalizer is not None:
            self._finalizer.detach()
        if self._stop is not None:
            self._stop.set()
        # drain so a producer blocked on put() wakes promptly
        if self._queue is not None:
            try:
                while True:
                    self._queue.get_nowait()
            except queue.Empty:
                pass
        th, self._thread = self._thread, None
        if th is not None:
            th.join(timeout=10)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def _release_producer(q, stop):
    """``weakref.finalize`` callback shared by the staged-feed classes:
    stop the producer of an iterator its consumer abandoned, and drain
    the buffer so a put() parked on a full queue wakes immediately.
    Deliberately holds NO reference to the feed -- that is the point."""
    stop.set()
    try:
        while True:
            q.get_nowait()
    except queue.Empty:
        pass
