"""Gluon Block / HybridBlock: the layer system and the hybridize engine.

TPU-native re-design of ``python/mxnet/gluon/block.py :: Block,
HybridBlock`` and the CachedOp executor
(``src/imperative/cached_op.cc :: CachedOp::Forward/Backward``).

The hybridize engine here IS the XLA path: ``hybridize()`` swaps the
imperative per-op dispatch for a shape-specialized ``jax.jit`` cache.

- Trace: the block's imperative forward runs once with tracer-wrapped
  NDArrays (parameters bound to traced values), capturing a pure function
  ``(params, inputs, rng_key) -> (outputs, aux_updates)``.  This replaces
  the reference's Symbol-proxy trace of ``hybrid_forward(F, ...)``.
- Aux state (BatchNorm running stats): mutations during trace are captured
  as extra functional outputs and rebound after each call -- the engine's
  mutable aux vars, done the XLA way.
- Randomness (Dropout): stateful-rng ops draw from a traced key stream; a
  fresh key is an explicit argument each call, keeping the compiled
  function pure.
- Backward: under ``autograd.record()`` the whole compiled graph becomes
  ONE tape node.  Forward runs as ``jit(vjp(pure_fn))`` returning a
  residual-carrying VJP pytree; backward is a second jitted call consuming
  it.  This mirrors CachedOp contributing its full graph to the tape
  (SURVEY.md §3.2) with both directions XLA-fused.
- Shape specialization: each (shapes, dtypes, train-flag) gets its own
  compiled entry -- the jit-cache answer to BucketingModule.
"""
from __future__ import annotations

import re
import threading
from collections import OrderedDict

import numpy as np

import jax

from .. import autograd
from .. import ndarray as nd_mod
from .. import profiling as _profiling
from .. import random as _random_mod
from .. import telemetry as _telemetry
from ..base import MXNetError
from ..context import current_context
from ..ndarray import NDArray
from ..ndarray.ndarray import _is_traced
from .parameter import (DeferredInitializationError, Parameter, ParameterDict,
                        shape_is_known)

_naming = threading.local()


def _naming_state():
    if not hasattr(_naming, "counters"):
        _naming.counters = [{}]
        _naming.prefixes = [""]
    return _naming


def _block_counters():
    return _naming_state().counters[-1]


_trace_tls = threading.local()


def _active_trace():
    return getattr(_trace_tls, "trace", None)


def _child_scope_names(block, out=None):
    """``{id(child): its name in its parent}`` for every block under
    ``block`` (a shared child keeps the first name it was found by)."""
    out = {} if out is None else out
    for name, child in block._children.items():
        if id(child) not in out:
            out[id(child)] = name
            _child_scope_names(child, out)
    return out


class _TraceContext:
    """Collects aux-state writes made while tracing a hybrid graph, and
    names each child block's ops after the child (``jax.named_scope``:
    ``op_name`` metadata of the HLO, so a device trace of the compiled
    program reads ``.../encoder/cell3/attention/...``)."""

    def __init__(self, block=None):
        self.aux_updates = OrderedDict()  # Parameter -> NDArray(tracer)
        self.scope_names = _child_scope_names(block) \
            if block is not None else {}

    def record_aux(self, param, data):
        self.aux_updates[param] = data

    def __enter__(self):
        self._prev = getattr(_trace_tls, "trace", None)
        _trace_tls.trace = self
        return self

    def __exit__(self, *a):
        _trace_tls.trace = self._prev


class Block:
    """Base container (reference: ``Block``)."""

    def __init__(self, prefix=None, params=None):
        self._empty_init()
        st = _naming_state()
        counters = st.counters[-1]
        if prefix is None:
            # Auto names are scoped: a block created inside a parent's
            # name_scope() gets the parent prefix prepended (reference
            # semantics -- keeps repeated submodules' params distinct).
            hint = type(self).__name__.lower()
            idx = counters.get(hint, 0)
            counters[hint] = idx + 1
            prefix = st.prefixes[-1] + "%s%d_" % (hint, idx)
        self._prefix = prefix
        self._scope_params = ParameterDict(prefix, shared=params)

    def _empty_init(self):
        # set via object.__setattr__ to dodge our __setattr__ hooks
        object.__setattr__(self, "_children", OrderedDict())
        object.__setattr__(self, "_reg_params", OrderedDict())
        object.__setattr__(self, "_forward_hooks", [])
        object.__setattr__(self, "_forward_pre_hooks", [])

    # -- attribute registration ---------------------------------------
    def __setattr__(self, name, value):
        if isinstance(value, Block):
            self._children[name] = value
        elif isinstance(value, Parameter):
            self._reg_params[name] = value
        object.__setattr__(self, name, value)

    @property
    def prefix(self):
        return self._prefix

    @property
    def name(self):
        return self._prefix.rstrip("_")

    @property
    def params(self):
        return self._scope_params

    def name_scope(self):
        import contextlib

        @contextlib.contextmanager
        def _scope():
            st = _naming_state()
            st.counters.append({})
            st.prefixes.append(self._prefix)
            try:
                yield self
            finally:
                st.counters.pop()
                st.prefixes.pop()
        return _scope()

    # -- parameter management -----------------------------------------
    def collect_params(self, select=None):
        """All parameters of self and descendants (reference:
        ``Block.collect_params``)."""
        out = ParameterDict(self._scope_params.prefix)
        pattern = re.compile(select) if select else None
        for p in self._all_params():
            if pattern is None or pattern.match(p.name):
                out._params[p.name] = p
        return out

    def _all_params(self, seen=None):
        seen = seen if seen is not None else set()
        for p in self._reg_params.values():
            if id(p) not in seen:
                seen.add(id(p))
                yield p
        for p in self._scope_params.values():
            if id(p) not in seen:
                seen.add(id(p))
                yield p
        for child in self._children.values():
            yield from child._all_params(seen)

    def initialize(self, init=None, ctx=None, verbose=False, force_reinit=False):
        self.collect_params().initialize(init, ctx, verbose, force_reinit)

    def cast(self, dtype):
        """Cast parameters recursively (reference: ``Block.cast``).
        Subclasses may override with the same signature to adjust the
        dtype for their subtree (BatchNorm keeps statistics fp32)."""
        self._cast_impl(dtype, set())

    def _cast_impl(self, dtype, seen):
        for child in self._children.values():
            if type(child).cast is not Block.cast:
                # overriding subclass: honor its public hook (it
                # recurses its own subtree via super().cast)
                child.cast(dtype)
            else:
                child._cast_impl(dtype, seen)
        for p in list(self._reg_params.values()) + \
                list(self._scope_params.values()):
            if id(p) not in seen:
                seen.add(id(p))
                p.cast(dtype)

    def apply(self, fn):
        for child in self._children.values():
            child.apply(fn)
        fn(self)
        return self

    # -- structural save/load (reference: Block.save_parameters) ------
    def _collect_params_with_prefix(self, prefix=""):
        if prefix:
            prefix += "."
        ret = {prefix + k: v for k, v in self._reg_params.items()}
        for name, child in self._children.items():
            ret.update(child._collect_params_with_prefix(prefix + name))
        return ret

    def save_parameters(self, filename, deduplicate=False):
        params = self._collect_params_with_prefix()
        arg = {k: p._reduce() for k, p in params.items() if p._data is not None
               or p._deferred_init is None}
        nd_mod.save(filename, arg)

    def load_parameters(self, filename, ctx=None, allow_missing=False,
                        ignore_extra=False, cast_dtype=False, dtype_source="current"):
        loaded = nd_mod.load(filename)
        params = self._collect_params_with_prefix()
        if not loaded and not params:
            return
        # accept both structural names and full prefixed names
        if loaded and not any(k in params for k in loaded):
            by_name = {p.name: p for p in params.values()}
            if any(k in by_name for k in loaded):
                params = by_name
        for name in loaded:
            if name not in params:
                if not ignore_extra:
                    raise MXNetError(
                        "parameter %r in file not found in Block; set "
                        "ignore_extra=True to skip" % name)
                continue
        if not allow_missing:
            for name in params:
                if name not in loaded:
                    raise MXNetError(
                        "parameter %r missing from file; set "
                        "allow_missing=True to skip" % name)
        for name, data in loaded.items():
            if name not in params:
                continue
            p = params[name]
            if p._data is None:
                p._shape = data.shape
                p._deferred_init = None
                p._data = data.as_in_context(
                    (ctx[0] if isinstance(ctx, (list, tuple)) else ctx)
                    or current_context())
                if p.dtype is not None and np.dtype(p.dtype) != data.dtype \
                        and not cast_dtype:
                    p._data = p._data.astype(p.dtype)
                if p._grad_req != "null":
                    p._init_grad()
            else:
                p.set_data(data.astype(p.dtype))

    # -- hooks ---------------------------------------------------------
    def register_forward_hook(self, hook):
        self._forward_hooks.append(hook)
        return hook

    def register_forward_pre_hook(self, hook):
        self._forward_pre_hooks.append(hook)
        return hook

    # -- call ----------------------------------------------------------
    def __call__(self, *args):
        for hook in self._forward_pre_hooks:
            hook(self, args)
        tr = _active_trace()
        scope = tr.scope_names.get(id(self)) if tr is not None else None
        if scope is not None:
            # traced into a compiled program: the child's ops carry its
            # name.  An eager call never gets here.
            with jax.named_scope(scope):
                out = self.forward(*args)
        else:
            out = self.forward(*args)
        for hook in self._forward_hooks:
            hook(self, args, out)
        import sys
        npx = sys.modules.get("mxnet_tpu.numpy_extension")
        if npx is not None and npx.is_np_array():
            # npx.set_np(): blocks speak mx.np (reference semantics)
            from ..numpy import _view
            if isinstance(out, (list, tuple)):
                out = type(out)(_view(o) for o in out)
            else:
                out = _view(out)
        return out

    def forward(self, *args):
        raise NotImplementedError

    def hybridize(self, active=True, **kwargs):
        """No-op on plain Blocks except recursing into children
        (reference behavior)."""
        for child in self._children.values():
            child.hybridize(active, **kwargs)

    def summary(self, *inputs):
        lines = ["-" * 64,
                 "%-30s %-20s %s" % ("Layer", "Output", "Params"),
                 "=" * 64]
        total = 0

        def hook(block, inp, out):
            nonlocal total
            n = sum(int(np.prod(p.shape)) for p in block._reg_params.values()
                    if p.shape and shape_is_known(p.shape))
            total += n
            shape = out.shape if isinstance(out, NDArray) else "-"
            lines.append("%-30s %-20s %d" % (type(block).__name__, shape, n))

        handles = []
        for child in self._children.values():
            handles.append((child, hook))
            child._forward_hooks.append(hook)
        try:
            self(*inputs)
        finally:
            for child, h in handles:
                child._forward_hooks.remove(h)
        lines.append("=" * 64)
        lines.append("Total params (direct children): %d" % total)
        return "\n".join(lines)

    def __repr__(self):
        lines = [type(self).__name__ + "("]
        for name, child in self._children.items():
            lines.append("  (%s): %s" % (name, repr(child).replace("\n", "\n  ")))
        lines.append(")")
        return "\n".join(lines)


# Static fields of the compiled-entry cache key built in
# ``HybridBlock._call_cached``.  Op params are intentionally absent:
# they are baked into each trace as compile-time constants.  The
# retrace auditor (``mxnet_tpu.analysis.retrace``) cross-references
# this tuple against the op registry's param specs -- keep it in sync
# with the ``key = ...`` expression below.
_CACHE_KEY_STATIC = ("training", "amp_policy", "shape", "dtype")


def _cache_key_diff(new_key, old_keys):
    """Field-labeled diff of a fresh hybridize cache key against the
    closest existing entry -- the payload of the runtime retrace event
    (``telemetry.hooks.compile_event``).  Labels follow
    ``_CACHE_KEY_STATIC`` plus per-argument position, so a log line says
    e.g. ``changed=['arg0.shape']`` (bucketing) vs ``['training']``
    (train/eval duality) vs ``['amp_policy']``."""
    if not old_keys:
        return []
    # closest = most leading fields shared
    def score(k):
        n = 0
        for a, b in zip(k, new_key):
            if a == b:
                n += 1
        return n
    prev = max(old_keys, key=score)
    changed = []
    if prev[0] != new_key[0]:
        changed.append("training")
    if prev[1] != new_key[1]:
        changed.append("amp_policy")
    if len(prev) != len(new_key):
        changed.append("n_args")
    for i, (a, b) in enumerate(zip(prev[2:], new_key[2:])):
        if a[0] != b[0]:
            changed.append("arg%d.shape" % i)
        if a[1] != b[1]:
            changed.append("arg%d.dtype" % i)
    return changed


class _CacheEntry:
    """One compiled specialization of a hybridized block."""

    __slots__ = ("fwd_eval", "fwd_vjp", "bwd", "param_names", "diff_names",
                 "aux_params", "single_output", "_nondiff_names")

    def __init__(self):
        self.fwd_eval = None
        self.fwd_vjp = None
        self.bwd = None
        self.param_names = []
        self.diff_names = []
        self.aux_params = []
        self.single_output = True
        self._nondiff_names = []


class HybridBlock(Block):
    """Imperative/compiled dual-mode block (reference: ``HybridBlock``)."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        object.__setattr__(self, "_active", False)
        object.__setattr__(self, "_cached_entries", {})
        object.__setattr__(self, "_flags", {})

    def hybridize(self, active=True, static_alloc=False, static_shape=False,
                  **kwargs):
        """Enable the compiled path (reference: ``HybridBlock.hybridize``;
        static_alloc/static_shape are implied by XLA and kept for API
        compatibility)."""
        object.__setattr__(self, "_active", active)
        object.__setattr__(self, "_cached_entries", {})
        self._flags.update({"static_alloc": static_alloc,
                            "static_shape": static_shape, **kwargs})
        for child in self._children.values():
            child.hybridize(active, static_alloc=static_alloc,
                            static_shape=static_shape, **kwargs)

    def infer_shape(self, *args):
        """Layer-specific deferred-shape rule; layers override
        (reference: ``HybridBlock.infer_shape`` via symbolic inference)."""
        raise MXNetError(
            "%s: cannot infer parameter shapes; either give explicit "
            "in_units/in_channels or override infer_shape"
            % type(self).__name__)

    # imperative composition used both eagerly and under trace
    def _forward_impl(self, *args):
        try:
            params = {k: p.data() for k, p in self._reg_params.items()}
        except DeferredInitializationError:
            self._infer_and_finish(*args)
            params = {k: p.data() for k, p in self._reg_params.items()}
        return self.hybrid_forward(nd_mod, *args, **params)

    def _infer_and_finish(self, *args):
        self.infer_shape(*args)
        for p in self._reg_params.values():
            if p._deferred_init is not None:
                p._finish_deferred_init()

    def forward(self, *args):
        from ..symbol.symbol import Symbol
        if any(isinstance(a, Symbol) for a in args):
            return self._symbolic_forward(*args)
        if self._active and _active_trace() is None and \
                all(isinstance(a, NDArray) for a in args):
            return self._call_cached(*args)
        return self._forward_impl(*args)

    def _symbolic_forward(self, *args):
        """Dual-F trace with F = mx.sym (reference: hybrid_forward's
        Symbol mode, used by export)."""
        from .. import symbol as sym_mod
        params = {k: p.var() for k, p in self._reg_params.items()}
        return self.hybrid_forward(sym_mod, *args, **params)

    def hybrid_forward(self, F, *args, **kwargs):
        raise NotImplementedError

    def export(self, path, epoch=0):
        """Serialize params for deployment (reference:
        ``HybridBlock.export`` writes ``-symbol.json`` + ``.params``;
        the graph side is provided by ``mxnet_tpu.symbol`` tracing)."""
        from ..symbol.export import export_block
        return export_block(self, path, epoch)

    def optimize_for(self, x, backend=None, **kwargs):
        self.hybridize()
        return self(x)

    def functionalize(self, training=True):
        """Return ``(pure_fn, param_names, params)`` where
        ``pure_fn(pvals: dict, ivals: list, rng_key) -> (outs, aux)`` is the
        block's forward as a pure jax function -- the building block for
        both the CachedOp cache and the multi-device pjit trainer
        (``mxnet_tpu.parallel``)."""
        params = [p for p in self._all_params() if p._data is not None]
        pmap = {p.name: p for p in params}
        block = self

        def pure_fn(pvals, ivals, rng_key):
            tr = _TraceContext(block)
            with tr, _random_mod.traced_stream(rng_key), \
                    autograd.pause(train_mode=training):
                for name, p in pmap.items():
                    p._trace_data = NDArray(pvals[name])
                try:
                    outs = block._forward_impl(*[NDArray(v) for v in ivals])
                finally:
                    aux = [(p, d) for p, d in tr.aux_updates.items()]
                    for p in pmap.values():
                        p._trace_data = None
            single = not isinstance(outs, (tuple, list))
            outs = [outs] if single else list(outs)
            aux_vals = {p.name: d._data for p, d in aux}
            return tuple(o._data for o in outs), aux_vals

        return pure_fn, [p.name for p in params], pmap

    # -- the CachedOp engine -------------------------------------------
    def _call_cached(self, *args):
        # first call may need deferred shape inference: run imperative once
        deferred = any(p._deferred_init is not None for p in self._all_params())
        if deferred:
            return self._forward_impl(*args)
        training = autograd.is_training()
        recording = autograd.is_recording()
        from .. import amp as _amp
        key = (training, _amp.policy_token()) + \
            tuple((a.shape, str(a.dtype)) for a in args)
        entry = self._cached_entries.get(key)
        if entry is None:
            if _telemetry._ENABLED:
                import time as _time
                old_keys = list(self._cached_entries)
                t0 = _time.perf_counter()
                entry = self._build_cache(args, training)
                _telemetry.hooks.compile_event(
                    "hybrid_cache",
                    seconds=_time.perf_counter() - t0,
                    retrace=bool(old_keys),
                    block=type(self).__name__,
                    cache_size=len(old_keys) + 1,
                    changed=_cache_key_diff(key, old_keys))
            else:
                entry = self._build_cache(args, training)
            self._cached_entries[key] = entry
        import contextlib
        from .. import profiler as _profiler
        scope = _profiler.scope("mx.cachedop:%s" % type(self).__name__) \
            if _profiler._scopes_enabled else contextlib.nullcontext()
        with scope:
            return self._run_cached(entry, args, recording)

    def _build_cache(self, args, training):
        """Trace the imperative forward into a pure jax function and jit it
        (reference: ``_build_cache`` -> ``CachedOp`` construction)."""
        entry = _CacheEntry()
        params = [p for p in self._all_params() if p._data is not None]
        entry.param_names = [p.name for p in params]
        pmap = {p.name: p for p in params}
        block = self

        def pure_fn(pvals, ivals, rng_key):
            tr = _TraceContext(block)
            with tr, _random_mod.traced_stream(rng_key), \
                    autograd.pause(train_mode=training):
                for name, p in pmap.items():
                    p._trace_data = NDArray(pvals[name])
                try:
                    outs = block._forward_impl(
                        *[NDArray(v) for v in ivals])
                finally:
                    aux = [(p, d) for p, d in tr.aux_updates.items()]
                    for p in pmap.values():
                        p._trace_data = None
            single = not isinstance(outs, (tuple, list))
            outs = [outs] if single else list(outs)
            aux_vals = {p.name: d._data for p, d in aux}
            return tuple(o._data for o in outs), aux_vals, single

        # probe trace via eval_shape to discover outputs/aux without compute
        pvals = {p.name: p._data._data for p in params}
        ivals = [a._data for a in args]
        probe_key = jax.random.PRNGKey(0)
        single_flag = [True]
        aux_names = [None]

        def fn2(pvals, ivals, rng_key):
            outs, aux, single = pure_fn(pvals, ivals, rng_key)
            single_flag[0] = single
            aux_names[0] = list(aux.keys())
            return outs, aux

        jax.eval_shape(fn2, pvals, ivals, probe_key)
        entry.single_output = single_flag[0]
        entry.aux_params = [pmap[n] for n in aux_names[0]]
        entry.diff_names = [p.name for p in params
                            if p._grad_req != "null" and
                            p.name not in aux_names[0]]
        diff_set = set(entry.diff_names)
        nondiff_names = [n for n in entry.param_names if n not in diff_set]

        def eval_fn(pvals, ivals, rng_key):
            outs, aux = fn2(pvals, ivals, rng_key)
            return outs, aux

        # no donation by design: pvals are the Parameter._data buffers
        # and the forward returns activations, not updated params -- the
        # inputs must survive the call (the donated whole-step program
        # is parallel.TrainStep, which rebinds its outputs)
        entry.fwd_eval = jax.jit(eval_fn)  # mxlint: disable=undonated-train-state

        def fwd_vjp(diff, nondiff, ivals, rng_key):
            def inner(d, i):
                merged = dict(nondiff)
                merged.update(d)
                return fn2(merged, i, rng_key)
            return jax.vjp(inner, diff, ivals)

        # same: diff/nondiff stay bound to Parameters across fwd+bwd (and
        # retain_graph backward may pull the residuals twice)
        entry.fwd_vjp = jax.jit(fwd_vjp)  # mxlint: disable=undonated-train-state
        entry.bwd = jax.jit(lambda vjp, cts: vjp(cts))
        entry._nondiff_names = nondiff_names
        return entry

    def _run_cached(self, entry, args, recording):
        import jax.numpy as jnp
        params = {n: p for n, p in
                  ((p.name, p) for p in self._all_params())
                  if n in set(entry.param_names)}
        pvals = {n: params[n]._data._data for n in entry.param_names}
        ivals = [a._data for a in args]
        rng_key = _random_mod.next_key()

        diff_vals = {n: pvals[n] for n in entry.diff_names}
        nondiff_vals = {n: pvals[n] for n in entry._nondiff_names}

        tracked_inputs = [a for a in args if a._is_tracked()]
        do_grad = recording and (entry.diff_names or tracked_inputs)
        if do_grad:
            (outs, aux), vjp = entry.fwd_vjp(diff_vals, nondiff_vals, ivals,
                                             rng_key)
        else:
            outs, aux = entry.fwd_eval(pvals, ivals, rng_key)
        if _profiling._ENABLED:
            # lazy cost capture (mx.profiling): keyed on the same
            # static fields as the hybridize cache, so each compiled
            # specialization yields exactly one CostReport
            ckey = ("hybrid", type(self).__name__, bool(do_grad)) + \
                tuple((a.shape, str(a.dtype)) for a in args)
            if do_grad:
                _profiling.capture_jit(
                    "hybrid:%s:train" % type(self).__name__,
                    entry.fwd_vjp,
                    (diff_vals, nondiff_vals, ivals, rng_key),
                    key=ckey, kind="hybrid_cache")
            else:
                _profiling.capture_jit(
                    "hybrid:%s" % type(self).__name__, entry.fwd_eval,
                    (pvals, ivals, rng_key), key=ckey,
                    kind="hybrid_cache")

        # rebind aux state (functional running stats -> parameter)
        for p in entry.aux_params:
            new = aux[p.name]
            grad = p._data._grad
            req = p._data._grad_req
            p._data = NDArray(new)
            p._data._grad = grad
            p._data._grad_req = req

        out_nds = [NDArray(o) for o in outs]

        if do_grad:
            diff_params = [params[n] for n in entry.diff_names]
            tape_inputs = [p._data for p in diff_params] + list(args)
            aux_zero_spec = {k: (v.shape, v.dtype) for k, v in aux.items()}
            n_outs = len(out_nds)
            bwd = entry.bwd
            diff_names = entry.diff_names

            def vjp_fn(cts):
                from ..ndarray import bulk as _bulk
                if not isinstance(cts, (tuple, list)):
                    cts = (cts,)
                # cotangents may be pending bulked-eager placeholders
                cts = tuple(_bulk.materialize(c) for c in cts)
                aux_cts = {k: jnp.zeros(s, d)
                           for k, (s, d) in aux_zero_spec.items()}
                d_diff, d_inputs = bwd(vjp, (tuple(cts), aux_cts))
                return tuple(d_diff[n] for n in diff_names) + tuple(d_inputs)

            node = autograd.TapeNode(tape_inputs, vjp_fn, n_outs,
                                     name=type(self).__name__ + "_cached")
            node._out_avals = [(o.shape, o.dtype) for o in out_nds]
            for i, o in enumerate(out_nds):
                o._ag_node = node
                o._ag_out_index = i
        return out_nds[0] if entry.single_output else out_nds


class SymbolBlock(HybridBlock):
    """Run a loaded symbolic graph as a block (reference: ``SymbolBlock``)."""

    def __init__(self, outputs, inputs, params=None):
        super().__init__(prefix="", params=None)
        self._outputs = outputs
        self._inputs = inputs if isinstance(inputs, (list, tuple)) else [inputs]
        self._arg_params = params or {}
        for name, arr in self._arg_params.items():
            p = Parameter(name, shape=arr.shape, dtype=arr.dtype)
            p._data = arr if isinstance(arr, NDArray) else NDArray(arr)
            self._reg_params[name] = p
            self._scope_params._params[name] = p

    @staticmethod
    def imports(symbol_file, input_names, param_file=None, ctx=None):
        from ..symbol import load as sym_load
        sym = sym_load(symbol_file)
        params = nd_mod.load(param_file) if param_file else {}
        # strip the reference's "arg:"/"aux:" key prefixes
        params = {(k.split(":", 1)[1] if ":" in k else k): v
                  for k, v in params.items()}
        if isinstance(input_names, str):
            input_names = [input_names]
        return SymbolBlock(sym, input_names, params)

    def forward(self, *args):
        from ..symbol.symbol import _eval_symbol
        feed = dict(zip(self._inputs, args))
        for name, p in self._reg_params.items():
            feed[name] = p.data()
        outs = _eval_symbol(self._outputs, feed)
        return outs[0] if len(outs) == 1 else outs
