"""Per-bucket AOT executor pool.

A servable forward is one pure function ``fn(params, x) -> tuple(outs)``
over a *fixed* per-bucket batch shape.  At registration the pool lowers
and compiles one executable per padded-shape bucket (checking the
persistent :class:`~mxnet_tpu.serving.cache.CompileCache` first) and
runs each once on zeros -- so by the time a request can reach the
batcher, every shape class it can dispatch is already compiled and no
request ever pays a first-compile.

The compiled executables are registered with ``mx.profiling``'s store
(when capture is armed), so serving programs show up in ``mxprof
report`` and the sharding sanitizer's collective contract like any
training step.
"""
from __future__ import annotations

import time

import numpy as np

from .. import telemetry as _telemetry
from ..base import MXNetError
from .cache import compile_through, stablehlo_fingerprint

__all__ = ["BucketExecutorPool"]


class BucketExecutorPool:
    """AOT-compiled executables over padded batch buckets.

    Parameters
    ----------
    pure_fn : callable ``(params_dict, x) -> tuple(jax arrays)``
    params : dict name -> device array, fed to every call
    input_shape : per-sample shape (no batch dim)
    dtype : input dtype
    buckets : ascending batch-size buckets; requests pad to the
        smallest bucket that fits
    cache : CompileCache or None
    label : provenance label for profiling capture
    """

    def __init__(self, pure_fn, params, input_shape, dtype, buckets,
                 cache=None, label="servable"):
        self._fn = pure_fn
        self._params = params
        self.input_shape = tuple(int(s) for s in input_shape)
        self.dtype = np.dtype(dtype)
        self.buckets = tuple(sorted(set(int(b) for b in buckets)))
        if not self.buckets or self.buckets[0] < 1:
            raise MXNetError("serving: buckets must be positive ints, "
                             "got %r" % (buckets,))
        self._cache = cache
        self._label = label
        self._compiled = {}       # bucket -> callable(params, x)
        self._fingerprints = {}   # bucket -> fingerprint
        self._num_outputs = None

    @property
    def max_bucket(self):
        return self.buckets[-1]

    def bucket_for(self, n):
        """Smallest bucket that holds ``n`` samples."""
        for b in self.buckets:
            if b >= n:
                return b
        raise MXNetError("serving: batch of %d exceeds the largest "
                         "bucket %d" % (n, self.max_bucket))

    def compiled_buckets(self):
        return sorted(self._compiled)

    def fingerprint(self, bucket):
        return self._fingerprints.get(bucket)

    # -- build ----------------------------------------------------------
    def warmup(self):
        """Compile every bucket and execute each once on zeros; returns
        total warm-up seconds.  After this no request shape class can
        trigger a compile."""
        import jax
        t0 = time.perf_counter()
        zeros = {b: np.zeros((b,) + self.input_shape, self.dtype)
                 for b in self.buckets}
        for b in self.buckets:
            call = self._build(b)
            outs = call(self._params, zeros[b])
            jax.block_until_ready(outs)
            if self._num_outputs is None:
                self._num_outputs = len(outs)
        dt = time.perf_counter() - t0
        if _telemetry._ENABLED:
            _telemetry.hooks.serving_warmup(self._label, dt,
                                            len(self.buckets))
        return dt

    def hbm_plan(self, device_hbm_bytes=None):
        """Predict peak HBM per bucket (``analysis.memory.hbm_plan``):
        two real compiles anchored at the smallest bucket fit the
        const+per-item line, every bucket is extrapolated along it, and
        ``largest_fit_bucket`` answers what ``device_hbm_bytes`` can
        actually serve.  Compiles hit jax's executable cache when the
        buckets are already warm."""
        import jax
        from ..analysis import memory as _memory
        pspecs = {n: jax.ShapeDtypeStruct(v.shape, v.dtype)
                  for n, v in self._params.items()}
        b0 = self.buckets[0]
        xspec = jax.ShapeDtypeStruct((b0,) + self.input_shape,
                                     self.dtype)
        return _memory.hbm_plan(
            "serving:%s" % self._label,
            device_hbm_bytes=device_hbm_bytes, buckets=self.buckets,
            batch_size=b0, fn=jax.jit(self._fn),
            args=(pspecs, xspec))

    def _build(self, bucket):
        import jax
        if bucket in self._compiled:
            return self._compiled[bucket]
        pspecs = {n: jax.ShapeDtypeStruct(v.shape, v.dtype)
                  for n, v in self._params.items()}
        xspec = jax.ShapeDtypeStruct((bucket,) + self.input_shape,
                                     self.dtype)
        jfn = jax.jit(self._fn)
        lowered = jfn.lower(pspecs, xspec)
        key = stablehlo_fingerprint(lowered.as_text())
        call = compile_through(self._cache, key, jfn, lowered,
                               (pspecs, xspec))
        self._compiled[bucket] = call
        self._fingerprints[bucket] = key
        self._register_profiling(bucket, jfn, (pspecs, xspec))
        return call

    def _register_profiling(self, bucket, jfn, specs):
        from .. import profiling as _profiling
        if not _profiling._ENABLED:
            return
        from ..profiling import store as _store
        _store.register("serving:%s:b%d" % (self._label, bucket),
                        "serving:%s:b%d" % (self._label, bucket),
                        jfn, specs, kind="serving")

    # -- dispatch -------------------------------------------------------
    def call(self, bucket, x):
        """Run the ``bucket`` executable on a host/device batch ``x``
        (already padded to the bucket).  Returns the output tuple."""
        call = self._compiled.get(bucket)
        if call is None:           # unregistered bucket: compile lazily
            call = self._build(bucket)
        return call(self._params, x)

    @property
    def num_outputs(self):
        return self._num_outputs
