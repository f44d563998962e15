"""Multi-tenant model registry: sources -> servable handles.

A :class:`Servable` is one deployed model: a pure forward function +
device-resident weights, an AOT-compiled per-bucket executor pool
(warmed at registration), and a dynamic batcher with its own worker
thread and bounded queue.  The :class:`ModelRegistry` owns many of them
by name -- the multi-tenant surface a serving process exposes.

Model sources (all land in the same ``fn(params, x) -> outs`` shape):

- **Gluon block** (``block=``): ``HybridBlock.functionalize`` --
  the same pure-function extraction the compiled trainer and the
  ``.mxa`` edge export use.
- **symbol+params** (``symbol=`` / ``params=``): a ``-symbol.json``
  graph (path or Symbol) evaluated through the symbol executor, with
  the reference's ``arg:``/``aux:`` key prefixes accepted.
- **ONNX** (``onnx=``): ``mx.onnx.import_model`` -- including
  third-party protobufs, not just our own exports.
- **checkpoint** (``checkpoint=`` + ``block=``): params restored from a
  PR-3 manifest-verified :class:`~mxnet_tpu.checkpoint.CheckpointManager`
  step (the newest intact step by default) into the block, then served
  as a block source.
"""
from __future__ import annotations

import numpy as np

from .. import chaos as _chaos
from .. import obs as _obs
from .. import telemetry as _telemetry
from ..base import MXNetError
from .batcher import DynamicBatcher, ServableClosed
from .cache import CompileCache
from .executor import BucketExecutorPool

__all__ = ["ModelRegistry", "Servable"]


def _default_buckets():
    from .. import env as _env
    spec = _env.get("MXNET_TPU_SERVING_BUCKETS")
    try:
        buckets = tuple(int(tok) for tok in str(spec).split(",") if tok)
    except ValueError as e:
        raise MXNetError("MXNET_TPU_SERVING_BUCKETS=%r is not a "
                         "comma-separated int list" % (spec,)) from e
    return buckets


def _strip_prefixes(params):
    return {(k.split(":", 1)[1] if ":" in k else k): v
            for k, v in params.items()}


def _device_value(v):
    """Any array-ish (NDArray / numpy / jax) -> jax array."""
    import jax.numpy as jnp
    data = getattr(v, "_data", v)
    return jnp.asarray(np.asarray(data) if not hasattr(data, "dtype")
                       else data)


class Servable:
    """One deployed model: executor pool + dynamic batcher."""

    def __init__(self, name, pool, batcher, source):
        self.name = name
        self.source = source
        self._pool = pool
        self._batcher = batcher

    # -- client surface -------------------------------------------------
    def submit(self, x, timeout=None):
        """Queue one sample; returns a ``concurrent.futures.Future``."""
        return self._batcher.submit(x, timeout=timeout)

    def infer(self, x, timeout=None):
        """Blocking single-sample inference: submit + wait.  The
        ``timeout`` bounds the whole round trip (queue wait included)."""
        fut = self.submit(x, timeout=timeout)
        return fut.result(timeout=timeout)

    # -- introspection --------------------------------------------------
    @property
    def buckets(self):
        return self._pool.buckets

    @property
    def input_shape(self):
        return self._pool.input_shape

    @property
    def dtype(self):
        return self._pool.dtype

    def fingerprint(self, bucket):
        return self._pool.fingerprint(bucket)

    def queue_depth(self):
        return self._batcher.queue_depth()

    @property
    def queue_capacity(self):
        """Bounded queue depth past which submits shed (the
        `/healthz` queue-saturation signal reads depth vs this)."""
        return self._batcher.max_queue

    @property
    def closed(self):
        return self._batcher.closed

    def close(self, drain=True):
        self._batcher.close(drain=drain)

    def __repr__(self):
        return "Servable(%r, source=%r, buckets=%r, input=%r)" % (
            self.name, self.source, self.buckets, self.input_shape)


class ModelRegistry:
    """Name -> Servable store; the multi-tenant serving surface.

    ::

        reg = mx.serving.ModelRegistry()
        reg.register("lenet", block=net, input_shape=(1, 28, 28))
        y = reg.infer("lenet", x)          # dynamically batched
        reg.shutdown(drain=True)
    """

    def __init__(self, cache_dir=None, compile_cache=True):
        from .. import sync as _sync
        self._lock = _sync.Lock(name="serving.registry")
        self._servables = {}
        self._cache = CompileCache(cache_dir) if compile_cache else None
        _obs.status.register_registry(self)   # weak: /healthz, /statusz

    # -- registration ---------------------------------------------------
    def register(self, name, block=None, symbol=None, params=None,
                 onnx=None, checkpoint=None, step=None, input_shape=None,
                 dtype="float32", input_name=None, buckets=None,
                 max_wait_ms=None, max_queue=None, warmup=True):
        """Load a model from one source into a warm servable handle.

        Exactly one of ``block``, ``symbol``, ``onnx`` must be given
        (``checkpoint`` composes with ``block``).  ``input_shape`` is
        the per-sample shape (no batch dim) and is required for every
        source.  Registration compiles and warms every bucket, so no
        request pays a first-compile; re-registering a name drains and
        replaces the previous servable.
        """
        if input_shape is None:
            raise MXNetError("serving.register needs input_shape "
                             "(per-sample, no batch dim)")
        sources = [s is not None for s in (block, symbol, onnx)]
        if checkpoint is not None and block is None:
            raise MXNetError("checkpoint= needs block= for the "
                             "architecture (a manifest stores params)")
        if sum(sources) != 1:
            raise MXNetError("serving.register needs exactly one of "
                             "block= / symbol= / onnx=")
        if checkpoint is not None:
            self._restore_checkpoint(block, checkpoint, step)
            source = "checkpoint"
        elif block is not None:
            source = "block"
        elif onnx is not None:
            source = "onnx"
        else:
            source = "symbol"
        if block is not None:
            fn, pvals = self._from_block(block, input_shape, dtype)
        else:
            if onnx is not None:
                from ..onnx import import_model
                sym, arg_params, aux_params = import_model(onnx)
                pdict = {}
                pdict.update(arg_params)
                pdict.update(aux_params)
            else:
                sym, pdict = self._load_symbol(symbol, params)
            fn, pvals = self._from_symbol(sym, pdict, input_name)

        buckets = tuple(buckets) if buckets else _default_buckets()
        pool = BucketExecutorPool(fn, pvals, input_shape, dtype, buckets,
                                  cache=self._cache, label=name)
        if warmup:
            _w = _obs.begin_span("serving.register.warm", model=name) \
                if _obs._TRACE_ENABLED else None
            try:
                pool.warmup()
            finally:
                if _w is not None:
                    _obs.end_span(_w)
            self._validate_hbm(name, pool)
        # chaos: an abort here (after the expensive warm-up, before the
        # install) models every way a swap dies late; the previous
        # servable MUST keep serving untouched -- the watcher's
        # retry/backoff and failure budget hang off this contract
        _chaos.fail_point("serving.swap", model=name)
        _i = _obs.begin_span("serving.register.install", model=name) \
            if _obs._TRACE_ENABLED else None
        try:
            batcher = DynamicBatcher(pool, label=name,
                                     max_wait_ms=max_wait_ms,
                                     max_queue=max_queue)
            servable = Servable(name, pool, batcher, source)
            with self._lock:
                old = self._servables.get(name)
                self._servables[name] = servable
            if old is not None:
                old.close(drain=True)
        finally:
            if _i is not None:
                _obs.end_span(_i)
        if _telemetry._ENABLED:
            _telemetry.hooks.serving_model(name, source, len(buckets))
        return servable

    def _validate_hbm(self, name, pool):
        """HBM bucket validation (ISSUE 20): when the backend reports a
        device memory limit, predict every bucket's peak HBM along the
        hbm_plan line and warn on buckets that cannot fit --
        registration still succeeds (an oversized bucket may never be
        dispatched), but the operator hears it BEFORE an OOM does the
        telling.  No-op on backends without memory_stats (CPU)."""
        from ..analysis import memory as _memory
        limit = _memory.device_hbm_bytes()
        if not limit:
            return None
        try:
            plan = pool.hbm_plan(limit)
        except Exception:
            return None             # planning must never block a swap
        bad = [str(b["batch"]) for b in plan["buckets"]
               if b["fits"] is False]
        if bad:
            import warnings
            warnings.warn(
                "servable %r: predicted peak HBM exceeds the device "
                "limit for bucket(s) %s (largest fitting bucket: %s); "
                "see analysis.memory.hbm_plan / docs/memory.md"
                % (name, ", ".join(bad), plan["largest_fit_bucket"]),
                RuntimeWarning, stacklevel=3)
        return plan

    def register_generative(self, name, model, params=None,
                            checkpoint=None, step=None,
                            prefill_buckets=None, decode_buckets=None,
                            block_size=None, num_blocks=None,
                            max_queue=None, warmup=True,
                            kv_dtype="float32", window_blocks=None):
        """Deploy an autoregressive decoder as a generative servable.

        ``model`` is the pure-function spec
        (:class:`~mxnet_tpu.serving.decode.TinyGPT`-shaped); weights
        come from ``params=`` (a flat name->array dict) or
        ``checkpoint=`` (a :class:`~mxnet_tpu.checkpoint.\
CheckpointManager` root whose step carries a ``params`` item).
        Registration warms every prefill and decode bucket, then
        installs; re-registering a name swaps mid-decode safely -- the
        old engine drains its half-generated sequences to completion on
        its own executables while the replacement takes new requests
        (zero dropped, ``chaos.survived.serving.decode_swap``).
        """
        from .decode.engine import DecodeEngine, GenerativeServable
        if (params is None) == (checkpoint is None):
            raise MXNetError("register_generative needs exactly one "
                             "of params= / checkpoint=")
        if checkpoint is not None:
            params = self._restore_params(checkpoint, step)
        pvals = {k: _device_value(v) for k, v in params.items()}
        engine = DecodeEngine(model, pvals,
                              prefill_buckets=prefill_buckets,
                              decode_buckets=decode_buckets,
                              block_size=block_size,
                              num_blocks=num_blocks,
                              max_queue=max_queue, cache=self._cache,
                              label=name, kv_dtype=kv_dtype,
                              window_blocks=window_blocks)
        if warmup:
            _w = _obs.begin_span("serving.register.warm", model=name) \
                if _obs._TRACE_ENABLED else None
            try:
                engine.warmup()
            finally:
                if _w is not None:
                    _obs.end_span(_w)
        # same late-abort contract as register(): a chaos fault here
        # (warmed, not yet installed) must leave the old servable --
        # and every sequence it is mid-way through generating --
        # untouched
        _chaos.fail_point("serving.swap", model=name)
        _i = _obs.begin_span("serving.register.install", model=name) \
            if _obs._TRACE_ENABLED else None
        try:
            engine.start()
            servable = GenerativeServable(name, engine)
            with self._lock:
                old = self._servables.get(name)
                self._servables[name] = servable
            if old is not None:
                # drain=True keeps STEPPING the old engine until every
                # half-generated sequence finishes on the old weights
                live = old.close(drain=True)
                if live:
                    _chaos.survived("serving.decode_swap",
                                    "drained %d live" % live)
        finally:
            if _i is not None:
                _obs.end_span(_i)
        if _telemetry._ENABLED:
            _telemetry.hooks.serving_model(
                name, "generative",
                len(engine.prefill_buckets)
                + len(engine.decode_buckets))
        return servable

    @staticmethod
    def _restore_params(checkpoint, step):
        from ..checkpoint import CheckpointManager
        mgr = checkpoint if isinstance(checkpoint, CheckpointManager) \
            else CheckpointManager(checkpoint)
        ckpt = mgr.restore(step=step)
        if ckpt is None:
            raise MXNetError("serving: no intact checkpoint under %r"
                             % mgr.root)
        if "params" not in ckpt.items:
            raise MXNetError(
                "serving: checkpoint step %d has no 'params' item "
                "(items: %s)" % (ckpt.step, sorted(ckpt.items)))
        return ckpt.items["params"]

    @staticmethod
    def _restore_checkpoint(block, checkpoint, step):
        from ..checkpoint import CheckpointManager
        mgr = checkpoint if isinstance(checkpoint, CheckpointManager) \
            else CheckpointManager(checkpoint)
        ckpt = mgr.restore_training(block, step=step)
        if ckpt is None:
            raise MXNetError("serving: no intact checkpoint under %r"
                             % mgr.root)
        return ckpt

    @staticmethod
    def _from_block(block, input_shape, dtype):
        import jax
        if not hasattr(block, "functionalize"):
            raise MXNetError("serving: block= expects a HybridBlock")
        if any(p._data is None for p in block._all_params()):
            # materialize deferred params with one probe forward (the
            # export_compiled idiom)
            from .. import ndarray as nd
            probe = nd.zeros((1,) + tuple(input_shape)).astype(dtype)
            block(probe)
        pure_fn, pnames, pmap = block.functionalize(training=False)
        pvals = {n: pmap[n].data()._data for n in pnames}
        key = jax.random.PRNGKey(0)

        def fn(params, x):
            outs, _aux = pure_fn(params, [x], key)
            return tuple(outs)

        return fn, pvals

    @staticmethod
    def _load_symbol(symbol, params):
        from .. import ndarray as nd
        from ..symbol import symbol as sym_mod
        sym = sym_mod.load(symbol) if isinstance(symbol, str) else symbol
        if isinstance(params, str):
            params = nd.load(params)
        return sym, _strip_prefixes(dict(params or {}))

    @staticmethod
    def _from_symbol(sym, params, input_name):
        from ..symbol.symbol import _eval_symbol
        arg_names = sym.list_arguments()
        aux_names = set(sym.list_auxiliary_states())
        inputs = [n for n in arg_names
                  if n not in params and n not in aux_names]
        if input_name is None:
            if len(inputs) != 1:
                raise MXNetError(
                    "serving: graph has inputs %r; pass input_name= to "
                    "pick the batched one (others must be in params)"
                    % (inputs,))
            input_name = inputs[0]
        elif input_name not in arg_names:
            raise MXNetError("serving: unknown input %r (arguments: %s)"
                             % (input_name, arg_names))
        missing = [n for n in aux_names if n not in params]
        if missing:
            raise MXNetError("serving: aux states %r missing from "
                             "params" % (missing,))
        pvals = {n: _device_value(v) for n, v in params.items()}

        def fn(pv, x):
            feed = dict(pv)
            feed[input_name] = x
            outs = _eval_symbol(sym, feed)
            return tuple(o._data for o in outs)

        return fn, pvals

    # -- lookup / client ------------------------------------------------
    def servable(self, name):
        with self._lock:
            s = self._servables.get(name)
        if s is None:
            raise MXNetError("serving: no servable %r (registered: %s)"
                             % (name, self.names()))
        return s

    def names(self):
        with self._lock:
            return sorted(self._servables)

    def submit(self, name, x, timeout=None):
        """Queue one sample on the named servable.  A concurrent
        re-register (hot swap) can close the handle between the lookup
        and the submit; the replacement is already installed by then,
        so the lookup retries against it -- a swap is invisible to
        registry-path clients (zero dropped requests, proven under
        chaos in tests/test_chaos.py)."""
        for _ in range(8):
            s = self.servable(name)
            try:
                return s.submit(x, timeout=timeout)
            except ServableClosed:
                with self._lock:
                    cur = self._servables.get(name)
                if cur is None or cur is s:
                    raise               # really closed, not swapped
        raise ServableClosed(
            "serving: servable %r kept closing mid-submit (flapping "
            "re-registration?)" % name)

    def infer(self, name, x, timeout=None):
        fut = self.submit(name, x, timeout=timeout)
        return fut.result(timeout=timeout)

    def generate(self, name, prompt, max_new_tokens, eos_id=None,
                 timeout=None):
        """Stream generated tokens from the named generative servable
        (an iterator of ints -- the
        :class:`~mxnet_tpu.serving.decode.GenerationStream`).  Same
        swap-race retry as :meth:`submit`: a hot swap between lookup
        and admit lands the request on the replacement."""
        for _ in range(8):
            s = self.servable(name)
            if not hasattr(s, "generate"):
                raise MXNetError("serving: servable %r (source=%r) is "
                                 "not generative" % (name, s.source))
            try:
                return s.generate(prompt, max_new_tokens,
                                  eos_id=eos_id, timeout=timeout)
            except ServableClosed:
                with self._lock:
                    cur = self._servables.get(name)
                if cur is None or cur is s:
                    raise               # really closed, not swapped
        raise ServableClosed(
            "serving: servable %r kept closing mid-generate (flapping "
            "re-registration?)" % name)

    # -- lifecycle ------------------------------------------------------
    def unregister(self, name, drain=True):
        with self._lock:
            s = self._servables.pop(name, None)
        if s is None:
            raise MXNetError("serving: no servable %r" % name)
        s.close(drain=drain)

    def shutdown(self, drain=True):
        """Close every servable (draining by default) -- the graceful
        process-shutdown path."""
        with self._lock:
            servables = list(self._servables.values())
            self._servables.clear()
        for s in servables:
            s.close(drain=drain)

    def __contains__(self, name):
        with self._lock:
            return name in self._servables

    def __len__(self):
        with self._lock:
            return len(self._servables)
