"""NDArray: the imperative tensor.

TPU-native re-design of the reference's ``src/ndarray/ndarray.cc ::
NDArray`` and ``python/mxnet/ndarray/ndarray.py``.  An NDArray wraps a
``jax.Array``.  JAX/PJRT's async dispatch replaces the reference's
dependency engine (SURVEY.md L1): op calls return immediately with a
future-backed array; ``asnumpy()`` / ``wait_to_read()`` are the sync
points, where device-side errors surface (the reference's
``MXNDArraySyncCopyToCPU`` contract).

Mutation semantics (`a += b`, ``a[...] = v``, optimizer updates) are
version-rebinding: the Python object stays, its ``_data`` handle moves to a
new functional array (donation lets XLA reuse the buffer).  Basic-slice
*views* therefore copy rather than alias -- the one intentional divergence
from the reference, documented here.
"""
from __future__ import annotations

import contextlib
import functools
import os
import struct
import time

import numpy as np

import jax
import jax.numpy as jnp

from .. import autograd
from .. import profiling as _profiling
from .. import random as _random_mod
from .. import telemetry as _telemetry
from ..base import MXNetError
from ..context import Context, current_context
from ..ops.registry import Op, get_op
from . import bulk

__all__ = ["NDArray", "array", "empty", "zeros", "ones", "full", "arange",
           "concat", "concatenate", "save", "load", "invoke", "waitall",
           "moveaxis", "from_jax", "onehot_encode"]

_MX_DTYPE_TO_FLAG = {
    np.dtype("float32"): 0, np.dtype("float64"): 1, np.dtype("float16"): 2,
    np.dtype("uint8"): 3, np.dtype("int32"): 4, np.dtype("int8"): 5,
    np.dtype("int64"): 6,
}
_FLAG_TO_MX_DTYPE = {v: k for k, v in _MX_DTYPE_TO_FLAG.items()}
# bfloat16 is TPU-native; give it a flag outside the reference's range.
_MX_DTYPE_TO_FLAG[np.dtype(jnp.bfloat16.dtype)] = 100
_FLAG_TO_MX_DTYPE[100] = np.dtype(jnp.bfloat16.dtype)


def waitall():
    """Block until all async work completes (reference:
    ``mx.nd.waitall`` / ``Engine::WaitForAll``).

    Device-side errors raised by in-flight computations surface HERE, at
    the sync point -- the reference's contract (``threaded_engine.cc ::
    OnCompleteStatic`` re-throws captured exceptions at WaitForAll /
    WaitToRead).  Errors from deleted arrays whose computations already
    failed cannot be resurrected, but every live array's pending work is
    drained and the first failure propagates.
    """
    t0 = time.perf_counter() if _telemetry._ENABLED else None
    bulk.flush()
    jax.effects_barrier()
    for d in jax.live_arrays():
        if isinstance(d, jax.core.Tracer):
            continue
        d.block_until_ready()
    if t0 is not None:
        _telemetry.hooks.host_sync("waitall", time.perf_counter() - t0)


def _is_traced(x):
    return isinstance(x, jax.core.Tracer)


def _amp_active():
    import sys
    amp_mod = sys.modules.get("mxnet_tpu.amp")
    return amp_mod is not None and amp_mod.is_active()


class NDArray:
    """An n-dimensional array on a device context."""

    __slots__ = ("_buf", "_grad", "_grad_req", "_ag_node", "_ag_out_index",
                 "__weakref__")

    def __init__(self, data, ctx=None):
        if isinstance(data, NDArray):
            data = data._buf
        if isinstance(data, bulk.LazyData):
            if data._concrete is not None:
                data = data._concrete
            elif ctx is not None:
                data = jax.device_put(data.materialize(), ctx.jax_device())
        elif ctx is not None and not _is_traced(data):
            data = jax.device_put(jnp.asarray(data), ctx.jax_device())
        elif not isinstance(data, jax.Array) and not _is_traced(data):
            data = jnp.asarray(data)
        self._buf = data
        self._grad = None
        self._grad_req = "write"
        self._ag_node = None
        self._ag_out_index = 0

    # -- data handle ---------------------------------------------------
    # ``_data`` is the concrete jax.Array handle; reading it is a sync
    # point for the bulked eager queue (the reference's WaitToRead).
    # Shape/dtype queries go through ``_buf`` and never force execution.
    @property
    def _data(self):
        buf = self._buf
        if isinstance(buf, bulk.LazyData):
            buf = buf.materialize()
            self._buf = buf
        return buf

    @_data.setter
    def _data(self, value):
        if isinstance(value, bulk.LazyData) and value._concrete is not None:
            value = value._concrete
        self._buf = value

    # -- basic properties ---------------------------------------------
    @property
    def shape(self):
        return tuple(self._buf.shape)

    @property
    def dtype(self):
        return np.dtype(self._buf.dtype)

    @property
    def size(self):
        return int(np.prod(self.shape)) if self.shape else 1

    @property
    def ndim(self):
        return len(self._buf.shape)

    @property
    def stype(self):
        return "default"

    @property
    def context(self):
        if _is_traced(self._data):
            return current_context()
        # for a multi-host global array (SPMD global mesh), the context
        # must name a device THIS process can address, by its LOCAL
        # ordinal -- a raw global device id indexes out of the
        # per-worker device list Context.jax_device resolves against
        sharding = getattr(self._data, "sharding", None)
        addr = getattr(sharding, "addressable_devices", None)
        dev = min(addr, key=lambda d: d.id) if addr else \
            next(iter(self._data.devices()))
        name = "cpu" if dev.platform == "cpu" else "tpu"
        from ..context import _jax_devices_for
        try:
            ordinal = _jax_devices_for(name).index(dev)
        except ValueError:
            ordinal = dev.id
        return Context(name, ordinal)

    ctx = context

    @property
    def grad(self):
        return self._grad

    @property
    def T(self):
        return transpose(self)

    # -- sync / conversion --------------------------------------------
    def asnumpy(self):
        """Blocking copy to host (reference: ``MXNDArraySyncCopyToCPU``)."""
        if _telemetry._ENABLED:
            t0 = time.perf_counter()
            out = np.asarray(self._data)
            _telemetry.hooks.host_sync("asnumpy",
                                       time.perf_counter() - t0)
            return out
        return np.asarray(self._data)

    def __array__(self, dtype=None, copy=None):
        """NumPy conversion protocol: one bulk device fetch.  Without
        this, np.asarray falls back to elementwise ``__getitem__`` --
        N separate device gathers, each a full round-trip on a remote
        device."""
        if copy is False:
            raise ValueError(
                "converting an NDArray to numpy always copies from the "
                "device buffer; copy=False cannot be satisfied")
        a = self.asnumpy()
        if dtype is not None:
            a = a.astype(dtype, copy=False)
        return a

    def asscalar(self):
        if self.size != 1:
            raise MXNetError("asscalar: array is not scalar-sized")
        return self.asnumpy().reshape(()).item()

    def item(self):
        return self.asscalar()

    def __float__(self):
        return float(self.asscalar())

    def __int__(self):
        return int(self.asscalar())

    def __bool__(self):
        if self.size == 0:
            return False
        if self.size == 1:
            return bool(self.asscalar())
        raise MXNetError("ambiguous truth value of multi-element NDArray")

    def __len__(self):
        if not self.shape:
            raise MXNetError("len() of 0-d NDArray")
        return self.shape[0]

    def wait_to_read(self):
        if _telemetry._ENABLED:
            t0 = time.perf_counter()
            if not _is_traced(self._data):
                self._data.block_until_ready()
            _telemetry.hooks.host_sync("wait_to_read",
                                       time.perf_counter() - t0)
            return
        if not _is_traced(self._data):
            self._data.block_until_ready()

    def wait_to_write(self):
        self.wait_to_read()

    def tolist(self):
        return self.asnumpy().tolist()

    def astype(self, dtype, copy=True):
        return NDArray(self._data.astype(np.dtype(dtype)))

    def copy(self):
        return NDArray(jnp.array(self._data))

    def copyto(self, other):
        """Copy to another array or context (reference: ``CopyFromTo``)."""
        if isinstance(other, Context):
            return NDArray(jax.device_put(self._data, other.jax_device()))
        if isinstance(other, NDArray):
            other._data = jax.device_put(self._data, next(iter(other._data.devices()))) \
                if not _is_traced(other._data) else self._data
            return other
        raise MXNetError("copyto: bad target %r" % (other,))

    def as_in_context(self, ctx):
        if ctx == self.context:
            return self
        return NDArray(jax.device_put(self._data, ctx.jax_device()))

    as_in_ctx = as_in_context

    def as_nd_ndarray(self):
        return self

    def tostype(self, stype):
        if stype != "default":
            raise MXNetError("sparse storage not supported in this build")
        return self

    # -- autograd ------------------------------------------------------
    def attach_grad(self, grad_req="write", stype=None):
        """Allocate a gradient buffer (reference: ``ndarray.py ::
        attach_grad``); marks this array as a differentiable leaf,
        detaching it from any previously recorded graph."""
        self._grad = NDArray(jnp.zeros_like(self._data))
        self._grad_req = grad_req
        self._ag_node = None
        self._ag_out_index = 0

    def detach(self):
        out = NDArray(self._data)
        return out

    def backward(self, out_grad=None, retain_graph=False, train_mode=True):
        autograd.backward([self], [out_grad] if out_grad is not None else None,
                          retain_graph=retain_graph, train_mode=train_mode)

    def _is_tracked(self):
        return self._ag_node is not None or \
            (self._grad is not None and self._grad_req != "null")

    # -- indexing ------------------------------------------------------
    def __getitem__(self, key):
        if isinstance(key, NDArray):
            kd = key._data
            if kd.dtype == jnp.bool_:
                return NDArray(self._data[np.asarray(kd)])
            return NDArray(jnp.take(self._data, kd.astype(jnp.int32), axis=0))
        key = tuple(k._data if isinstance(k, NDArray) else k for k in key) \
            if isinstance(key, tuple) else key
        return NDArray(self._data[key])

    def __setitem__(self, key, value):
        if isinstance(value, NDArray):
            value = value._data
        if isinstance(key, NDArray):
            key = key._data.astype(jnp.int32)
        if isinstance(key, tuple):
            key = tuple(k._data if isinstance(k, NDArray) else k for k in key)
        if key is Ellipsis or (isinstance(key, slice) and key == slice(None)):
            v = jnp.broadcast_to(jnp.asarray(value, dtype=self._data.dtype),
                                 self.shape)
            # keep the array on its committed device (a bare jnp.asarray
            # would land on the default device)
            if not _is_traced(self._data) and not _is_traced(v):
                v = jax.device_put(v, next(iter(self._data.devices())))
            self._data = v
        else:
            self._data = self._data.at[key].set(value)

    # -- arithmetic (rebinding in-place forms) ------------------------
    def _binop(self, other, opname, reverse=False):
        if not isinstance(other, NDArray) and np.isscalar(other):
            # scalar operand -> dedicated *_scalar op (reference
            # semantics); keeps the scalar a compile-time param instead
            # of a per-call host->device transfer
            sop = _SCALAR_OP.get((opname, reverse))
            if sop is not None:
                return invoke(get_op(sop), [self],
                              {"scalar": float(other)})
        if isinstance(other, NDArray):
            rhs = other
        elif _is_traced(self._data) or len(self._data.devices()) != 1:
            rhs = NDArray(jnp.asarray(other, dtype=self._data.dtype))
        else:
            arr = np.asarray(other, dtype=self._data.dtype)
            rhs = NDArray(jax.device_put(
                arr, next(iter(self._data.devices()))))
        lhs = self
        if reverse:
            lhs, rhs = rhs, lhs
        return invoke(get_op(opname), [lhs, rhs], {})

    def __add__(self, o):
        return self._binop(o, "elemwise_add")

    __radd__ = __add__

    def __sub__(self, o):
        return self._binop(o, "elemwise_sub")

    def __rsub__(self, o):
        return self._binop(o, "elemwise_sub", reverse=True)

    def __mul__(self, o):
        return self._binop(o, "elemwise_mul")

    __rmul__ = __mul__

    def __truediv__(self, o):
        return self._binop(o, "elemwise_div")

    def __rtruediv__(self, o):
        return self._binop(o, "elemwise_div", reverse=True)

    def __mod__(self, o):
        return self._binop(o, "broadcast_mod")

    def __pow__(self, o):
        return self._binop(o, "broadcast_power")

    def __rpow__(self, o):
        return self._binop(o, "broadcast_power", reverse=True)

    def __matmul__(self, o):
        return invoke(get_op("dot"), [self, o], {})

    def __neg__(self):
        return invoke(get_op("negative"), [self], {})

    def __abs__(self):
        return invoke(get_op("abs"), [self], {})

    def __eq__(self, o):
        return self._binop(o, "broadcast_equal")

    def __ne__(self, o):
        return self._binop(o, "broadcast_not_equal")

    def __gt__(self, o):
        return self._binop(o, "broadcast_greater")

    def __ge__(self, o):
        return self._binop(o, "broadcast_greater_equal")

    def __lt__(self, o):
        return self._binop(o, "broadcast_lesser")

    def __le__(self, o):
        return self._binop(o, "broadcast_lesser_equal")

    __hash__ = object.__hash__

    def _inplace_guard(self):
        # Mirrors the reference's restriction: in-place writes to an array
        # that participates in a recorded graph would corrupt the tape.
        if autograd.is_recording() and self._is_tracked():
            raise MXNetError(
                "in-place operation on an array that requires grad inside "
                "autograd.record() is not allowed; use out-of-place ops")

    def __iadd__(self, o):
        self._inplace_guard()
        self._data = self.__add__(o)._data
        self._ag_node = None
        return self

    def __isub__(self, o):
        self._inplace_guard()
        self._data = self.__sub__(o)._data
        self._ag_node = None
        return self

    def __imul__(self, o):
        self._inplace_guard()
        self._data = self.__mul__(o)._data
        self._ag_node = None
        return self

    def __itruediv__(self, o):
        self._inplace_guard()
        self._data = self.__truediv__(o)._data
        self._ag_node = None
        return self

    def __repr__(self):
        if _is_traced(self._data):
            return "<NDArray traced %s %s>" % (self.shape, self.dtype)
        return "%s\n<NDArray %s @%s>" % (
            np.array2string(self.asnumpy(), precision=4, suppress_small=True),
            "x".join(str(s) for s in self.shape) or "scalar", self.context)

    # -- common method forms of ops (subset of the generated surface) --
    def reshape(self, *shape, **kwargs):
        if len(shape) == 1 and isinstance(shape[0], (tuple, list)):
            shape = tuple(shape[0])
        return invoke(get_op("Reshape"), [self], {"shape": shape, **kwargs})

    def reshape_like(self, other):
        return invoke(get_op("reshape_like"), [self, other], {})

    def flatten(self):
        return invoke(get_op("Flatten"), [self], {})

    def transpose(self, axes=None):
        return invoke(get_op("transpose"), [self], {"axes": axes})

    def swapaxes(self, dim1, dim2):
        return invoke(get_op("swapaxes"), [self], {"dim1": dim1, "dim2": dim2})

    def expand_dims(self, axis):
        return invoke(get_op("expand_dims"), [self], {"axis": axis})

    def squeeze(self, axis=None):
        return invoke(get_op("squeeze"), [self], {"axis": axis})

    def broadcast_to(self, shape):
        return invoke(get_op("broadcast_to"), [self], {"shape": shape})

    def broadcast_like(self, other):
        return invoke(get_op("broadcast_like"), [self, other], {})

    def sum(self, axis=None, keepdims=False):
        return invoke(get_op("sum"), [self], {"axis": axis, "keepdims": keepdims})

    def mean(self, axis=None, keepdims=False):
        return invoke(get_op("mean"), [self], {"axis": axis, "keepdims": keepdims})

    def prod(self, axis=None, keepdims=False):
        return invoke(get_op("prod"), [self], {"axis": axis, "keepdims": keepdims})

    def max(self, axis=None, keepdims=False):
        return invoke(get_op("max"), [self], {"axis": axis, "keepdims": keepdims})

    def min(self, axis=None, keepdims=False):
        return invoke(get_op("min"), [self], {"axis": axis, "keepdims": keepdims})

    def argmax(self, axis=None):
        return invoke(get_op("argmax"), [self], {"axis": axis})

    def argmin(self, axis=None):
        return invoke(get_op("argmin"), [self], {"axis": axis})

    def norm(self, ord=2, axis=None, keepdims=False):
        return invoke(get_op("norm"), [self], {"ord": ord, "axis": axis,
                                               "keepdims": keepdims})

    def clip(self, a_min, a_max):
        return invoke(get_op("clip"), [self], {"a_min": a_min, "a_max": a_max})

    def abs(self):
        return invoke(get_op("abs"), [self], {})

    def sqrt(self):
        return invoke(get_op("sqrt"), [self], {})

    def square(self):
        return invoke(get_op("square"), [self], {})

    def exp(self):
        return invoke(get_op("exp"), [self], {})

    def log(self):
        return invoke(get_op("log"), [self], {})

    def sigmoid(self):
        return invoke(get_op("sigmoid"), [self], {})

    def tanh(self):
        return invoke(get_op("tanh"), [self], {})

    def relu(self):
        return invoke(get_op("relu"), [self], {})

    def softmax(self, axis=-1):
        return invoke(get_op("softmax"), [self], {"axis": axis})

    def log_softmax(self, axis=-1):
        return invoke(get_op("log_softmax"), [self], {"axis": axis})

    def take(self, indices, axis=0, mode="clip"):
        return invoke(get_op("take"), [self, indices], {"axis": axis, "mode": mode})

    def pick(self, index, axis=-1, keepdims=False):
        return invoke(get_op("pick"), [self, index], {"axis": axis, "keepdims": keepdims})

    def one_hot(self, depth, **kw):
        return invoke(get_op("one_hot"), [self], {"depth": depth, **kw})

    def topk(self, axis=-1, k=1, ret_typ="indices", is_ascend=False):
        return invoke(get_op("topk"), [self], {"axis": axis, "k": k,
                                               "ret_typ": ret_typ,
                                               "is_ascend": is_ascend})

    def sort(self, axis=-1, is_ascend=True):
        return invoke(get_op("sort"), [self], {"axis": axis, "is_ascend": is_ascend})

    def argsort(self, axis=-1, is_ascend=True):
        return invoke(get_op("argsort"), [self], {"axis": axis, "is_ascend": is_ascend})

    def flip(self, axis):
        return invoke(get_op("reverse"), [self], {"axis": axis})

    def tile(self, reps):
        return invoke(get_op("tile"), [self], {"reps": reps})

    def repeat(self, repeats, axis=None):
        return invoke(get_op("repeat"), [self], {"repeats": repeats, "axis": axis})

    def slice_axis(self, axis, begin, end):
        return invoke(get_op("slice_axis"), [self], {"axis": axis, "begin": begin,
                                                     "end": end})

    def zeros_like(self):
        return invoke(get_op("zeros_like"), [self], {})

    def ones_like(self):
        return invoke(get_op("ones_like"), [self], {})


# ----------------------------------------------------------------------
# Op dispatch
# ----------------------------------------------------------------------

def _wrap_outputs(op, raw, inputs_for_tape, vjp_fn, params):
    multi = isinstance(raw, (tuple, list))
    raws = list(raw) if multi else [raw]
    outs = [NDArray(r) for r in raws]
    if vjp_fn is not None:
        node = autograd.TapeNode(inputs_for_tape, vjp_fn, len(raws),
                                 name=op.name)
        node._out_avals = [(tuple(r.shape), r.dtype) for r in raws]
        ndiff = op.num_diff_outputs if op.num_diff_outputs is not None else len(raws)
        for i, o in enumerate(outs):
            if i < ndiff:
                o._ag_node = node
                o._ag_out_index = i
    return outs if multi else outs[0]


# scalar-operand op table for NDArray._binop (reference: the
# ``_plus_scalar``-family ops backing ndarray's operator overloads)
_SCALAR_OP = {
    ("elemwise_add", False): "_plus_scalar",
    ("elemwise_add", True): "_plus_scalar",
    ("elemwise_sub", False): "_minus_scalar",
    ("elemwise_sub", True): "_rminus_scalar",
    ("elemwise_mul", False): "_mul_scalar",
    ("elemwise_mul", True): "_mul_scalar",
    ("elemwise_div", False): "_div_scalar",
    ("elemwise_div", True): "_rdiv_scalar",
    ("broadcast_power", False): "_power_scalar",
    ("broadcast_power", True): "_rpower_scalar",
    ("broadcast_mod", False): "_mod_scalar",
    ("broadcast_equal", False): "_equal_scalar",
    ("broadcast_equal", True): "_equal_scalar",
    ("broadcast_not_equal", False): "_not_equal_scalar",
    ("broadcast_not_equal", True): "_not_equal_scalar",
    ("broadcast_greater", False): "_greater_scalar",
    ("broadcast_greater", True): "_lesser_scalar",
    ("broadcast_greater_equal", False): "_greater_equal_scalar",
    ("broadcast_greater_equal", True): "_lesser_equal_scalar",
    ("broadcast_lesser", False): "_lesser_scalar",
    ("broadcast_lesser", True): "_greater_scalar",
    ("broadcast_lesser_equal", False): "_lesser_equal_scalar",
    ("broadcast_lesser_equal", True): "_greater_equal_scalar",
}


# ----------------------------------------------------------------------
# Eager dispatch jit cache (SURVEY §7 hard-part #1): every imperative op
# call runs through a persistent compiled primitive keyed on
# (op, arg shapes/dtypes, params, amp policy), so non-hybridized training
# pays one XLA executable launch instead of tens of µs of Python+trace
# per op.  The reference's analog is the engine's cached fcompute path.
# ----------------------------------------------------------------------
_EAGER_JIT_CACHE = {}
_EAGER_JIT_ENABLED = os.environ.get("MXNET_TPU_EAGER_JIT", "1") != "0"


def _canon_param(v):
    if isinstance(v, (list, tuple)):
        return tuple(_canon_param(x) for x in v)
    if isinstance(v, np.ndarray):
        return ("__np__", v.shape, str(v.dtype), v.tobytes())
    return v


# Params that vary per call (per-step lr/wd schedules, step counters,
# arbitrary `x + c` scalars): traced as weak-typed jit arguments so a
# new VALUE does not mean a new XLA compilation.  Everything else
# (flags, shapes, clip thresholds with Python control flow) stays
# static in the key.  The retrace auditor (mxnet_tpu.analysis.retrace)
# cross-references this set against the registry's param specs.
_DYNAMIC_PARAMS = frozenset(("lr", "wd", "rescale_grad", "scalar", "t"))


def _eager_jit_fn(op, params, present, total_args):
    """Return ``(jfn, dyn_names, sig)`` -- a cached jitted callable, the
    names of params it takes as traced scalars, and the cache key -- or
    ``(None, (), None)`` when the call is unjittable (unhashable
    params)."""
    if not _EAGER_JIT_ENABLED:
        return None, (), None
    dyn_names = tuple(sorted(
        k for k in params
        if k in _DYNAMIC_PARAMS and isinstance(params[k], (int, float))
        and not isinstance(params[k], bool)))
    try:
        psig = tuple(sorted((k, _canon_param(v))
                            for k, v in params.items()
                            if k not in dyn_names))
        hash(psig)
    except TypeError:
        return None, (), None
    from .. import amp as _amp
    amp_token = _amp.policy_token() if _amp_active() else None
    sig = (op.name, present, total_args, psig, dyn_names, amp_token)
    entry = _EAGER_JIT_CACHE.get(sig)
    if entry is None:
        fcompute = op.fcompute
        stateful = op.stateful_rng
        opname = op.name
        static_kwargs = {k: v for k, v in params.items()
                         if k not in dyn_names}
        do_amp = amp_token is not None

        def f(dyn_vals, *pd):
            if stateful:
                rng_key, pd = pd[0], pd[1:]
            full = [None] * total_args
            for i, d in zip(present, pd):
                full[i] = d
            if do_amp:
                from .. import amp as _amp2
                # casts INSIDE the differentiated function: the cast vjp
                # returns fp32 gradients (master weights for free)
                full = _amp2.apply_op_casts(opname, full)
            kwargs = dict(static_kwargs)
            kwargs.update(zip(dyn_names, dyn_vals))
            if stateful:
                return fcompute(rng_key, *full, **kwargs)
            return fcompute(*full, **kwargs)

        entry = (jax.jit(f), f, stateful)
        # suppression invariant: sig space = op set x call arities x
        # static-param values actually used -- program-bounded, and a
        # recorded backward resolves its forward through this table
        # (_eager_bwd_fn), so LRU eviction here would KeyError an
        # in-flight autograd tape.  Retrace growth is observable via
        # the compile_event cache_size payload instead.
        _EAGER_JIT_CACHE[sig] = entry  # mxlint: disable=unbounded-shape-cache
        if _telemetry._ENABLED:
            _emit_eager_compile(sig)
    return entry[0], dyn_names, sig


def _emit_eager_compile(sig):
    """A fresh eager-dispatch cache entry was created: emit a compile
    event.  When the op already holds a same-arity entry, this is a
    RETRACE -- a static param (or the amp policy) changed value, the
    exact class of recompile-per-step regression the static auditor
    flagged for LAMB's ``t`` -- and the payload names the params that
    differ so the log says *why* XLA compiled again."""
    opname, present, total, psig, _dyn, amp_token = sig
    prior = [s for s in _EAGER_JIT_CACHE
             if s[0] == opname and s[1] == present and s[2] == total
             and s is not sig]
    changed = []
    if prior:
        prev = prior[-1]
        prev_ps, cur_ps = dict(prev[3]), dict(psig)
        changed = sorted(str(k) for k in set(prev_ps) | set(cur_ps)
                         if prev_ps.get(k) != cur_ps.get(k))
        if prev[5] != amp_token:
            changed.append("amp_policy")
    _telemetry.hooks.compile_event(
        "eager_jit", retrace=bool(prior), op=opname,
        cache_size=len(_EAGER_JIT_CACHE), changed=changed)


# Per-sig cached BACKWARD executables for recorded eager ops.  Without
# this every `autograd.record()`-scoped op call pays a fresh jax.vjp
# trace -- the dominant term of the imperative/hybridized gap (SURVEY
# §7 hard-part #1).  The cached backward is recompute-based (jax.vjp of
# the forward inside one jit, cotangents applied in the same program):
# the op's residuals are rebuilt from its inputs, trading a little FLOP
# for never tracing at dispatch time -- the per-op analog of
# ``jax.checkpoint``.
_EAGER_BWD_CACHE = {}


def _eager_bwd_fn(sig):
    bwd = _EAGER_BWD_CACHE.get(sig)
    if bwd is None:
        _jfn, f, stateful = _EAGER_JIT_CACHE[sig]

        def b(dyn_vals, key, pd, cts):
            if stateful:
                def fwd(*p):
                    return f(dyn_vals, key, *p)
            else:
                def fwd(*p):
                    return f(dyn_vals, *p)
            _, pull = jax.vjp(fwd, *pd)
            return pull(cts)

        bwd = jax.jit(b)
        # suppression invariant: strictly a subset of _EAGER_JIT_CACHE's
        # sig space (only recorded ops), bounded by the same program
        # invariant documented there.
        _EAGER_BWD_CACHE[sig] = bwd  # mxlint: disable=unbounded-shape-cache
    return bwd


def invoke(op: Op, tensor_args, kwargs, out=None):
    """Dispatch one op eagerly (reference: ``Imperative::Invoke`` in
    ``src/imperative/imperative.cc``; shape/type inference + engine push
    collapse into a single traced JAX call here)."""
    if _telemetry._ENABLED:
        _telemetry.hooks.op_dispatch(op.name)
    kwargs = dict(kwargs)
    kwargs.pop("name", None)
    params = op.param_defaults()
    for k, v in kwargs.items():
        if k not in params and not any(p.name == k for p in op.params):
            raise MXNetError("op %s: unknown argument %r" % (op.name, k))
        params[k] = v
    if any(p.name == "training" for p in op.params) and "training" not in kwargs:
        params["training"] = autograd.is_training()

    # single-device reference only: committing a converted operand to
    # one device of a SHARDED operand's set would break the jit call
    ref_device = None
    for a in tensor_args:
        if not isinstance(a, NDArray):
            continue
        b = a._buf
        if isinstance(b, bulk.LazyData):
            if b._concrete is not None:
                b = b._concrete
            elif b.device is not None:
                ref_device = b.device
                break
            else:
                continue
        if not _is_traced(b) and len(b.devices()) == 1:
            ref_device = next(iter(b.devices()))
            break
    nds = []
    datas = []
    for a in tensor_args:
        if a is None:
            nds.append(None)
            datas.append(None)
        elif isinstance(a, NDArray):
            nds.append(a)
            b = a._buf
            if isinstance(b, bulk.LazyData) and b._concrete is not None:
                b = b._concrete
                a._buf = b
            datas.append(b)
        else:
            # place converted operands WITH the tensor operands -- the
            # default device need not be theirs, and a stray
            # cross-device transfer per op call is a host round trip
            raw = np.asarray(a)
            nd = NDArray(jax.device_put(raw, ref_device)
                         if ref_device is not None else jnp.asarray(raw))
            nds.append(nd)
            datas.append(nd._data)

    key = _random_mod.next_key() if op.stateful_rng else None

    present = tuple(i for i, d in enumerate(datas) if d is not None)
    pdatas = [datas[i] for i in present]

    jfn, dyn_names, sig = _eager_jit_fn(op, params, present, len(datas))
    if jfn is not None:
        dyn_vals = tuple(float(params[n]) for n in dyn_names)
        call = functools.partial(jfn, dyn_vals, key) if op.stateful_rng \
            else functools.partial(jfn, dyn_vals)
    else:
        # unjittable params (rare): eager fallback -- needs concrete data
        datas = [bulk.materialize(d) for d in datas]
        fn = functools.partial(op.fcompute, key) if op.stateful_rng \
            else op.fcompute

        def call(*pd):
            full = list(datas)
            for i, d in zip(present, pd):
                full[i] = bulk.materialize(d)
            if _amp_active():
                from .. import amp as _amp
                full = _amp.apply_op_casts(op.name, full)
            return fn(*full, **params)

    # bulked dispatch: append to the pending region instead of launching
    # one XLA program per op (reference: engine op bulking)
    bulkable = (jfn is not None and bulk.enabled()
                and not any(_is_traced(d) for d in pdatas))

    def dispatch():
        if bulkable:
            args = ((dyn_vals, key) + tuple(pdatas)) if op.stateful_rng \
                else ((dyn_vals,) + tuple(pdatas))
            return bulk.enqueue(jfn, sig, args, device=ref_device)
        return call(*pdatas)

    from .. import profiler as _profiler
    scope = _profiler.scope("mx." + op.name) \
        if _profiler._scopes_enabled else contextlib.nullcontext()
    recording = autograd.is_recording() and any(
        n is not None and n._is_tracked() for n in nds)
    with scope:
        if recording:
            if jfn is not None:
                # cached-executable forward + cached recompute-based
                # backward: no tracing on either pass after warmup
                raw = dispatch()
                bwd = _eager_bwd_fn(sig)
                pd_tuple = tuple(pdatas)
                dv, kk = dyn_vals, key

                def vjp_fn(cts):
                    cts_flat, _ = jax.tree_util.tree_flatten(
                        cts, is_leaf=lambda x: isinstance(x, bulk.LazyData))
                    traced = any(_is_traced(x) for x in pd_tuple) or \
                        any(_is_traced(x) for x in cts_flat)
                    if bulk.enabled() and not traced:
                        # backward bulking: the cached bwd executable
                        # joins the pending region like any forward op.
                        # Traced operands (backward replayed under an
                        # outer jax trace) must NOT enter the module
                        # queue: they would leak out of the trace and
                        # x.devices() on a tracer raises -- mirror the
                        # forward's bulkable guard and call directly.
                        return bulk.enqueue(bwd, ("bwd", sig),
                                            (dv, kk, pd_tuple, cts))
                    pd = tuple(bulk.materialize(x) for x in pd_tuple)
                    return bwd(dv, kk, pd, bulk.materialize_tree(cts))
            else:
                raw, pull = jax.vjp(
                    call, *[bulk.materialize(d) for d in pdatas])

                def vjp_fn(cts, _pull=pull):
                    # same LazyData hazard as the jitted path: bulked
                    # cotangents must be concrete before the raw pull
                    return _pull(bulk.materialize_tree(cts))
            tape_inputs = [nds[i] for i in present]
            result = _wrap_outputs(op, raw, tape_inputs, vjp_fn, params)
        else:
            raw = dispatch()
            result = _wrap_outputs(op, raw, None, None, params)

    if _profiling._ENABLED and jfn is not None and \
            not any(_is_traced(d) for d in pdatas):
        # lazy cost capture (mx.profiling): a dict insert keyed on the
        # eager-jit cache sig; lower+compile+parse happens at report
        # time, never here.  LazyData operands are fine -- they carry
        # aval shape/dtype and the store abstracts everything to
        # ShapeDtypeStructs on registration; excluding them made
        # capture depend on whether the dispatch rode the bulk queue,
        # which varies with process-global cache warmth (a test-order
        # flake: a warm FullyConnected cache dropped the second layer's
        # report)
        cargs = ((dyn_vals, key) + tuple(pdatas)) if op.stateful_rng \
            else ((dyn_vals,) + tuple(pdatas))
        _profiling.capture_jit("eager:%s" % op.name, jfn, cargs,
                               key=("eager", sig), kind="eager_jit")

    if out is not None:
        src = result if not isinstance(result, list) else result[0]
        out._buf = src._buf
        out._ag_node = src._ag_node
        out._ag_out_index = src._ag_out_index
        return out
    return result


# ----------------------------------------------------------------------
# Creation functions (reference: init_op.cc + ndarray.py module funcs)
# ----------------------------------------------------------------------

def _resolve_ctx(ctx):
    return ctx if ctx is not None else current_context()


def array(source_array, ctx=None, dtype=None):
    """Create an NDArray from any array-like (reference: ``mx.nd.array``)."""
    if isinstance(source_array, NDArray):
        source_array = source_array.asnumpy()
    arr = np.asarray(source_array)
    if dtype is None:
        dtype = np.float32 if arr.dtype == np.float64 else arr.dtype
    arr = arr.astype(dtype)
    return NDArray(jax.device_put(arr, _resolve_ctx(ctx).jax_device()))


def from_jax(x):
    return NDArray(x)


def empty(shape, ctx=None, dtype="float32"):
    return zeros(shape, ctx, dtype)


def zeros(shape, ctx=None, dtype="float32", **kwargs):
    shape = (shape,) if isinstance(shape, int) else tuple(shape)
    return NDArray(jax.device_put(jnp.zeros(shape, np.dtype(dtype)),
                                  _resolve_ctx(ctx).jax_device()))


def ones(shape, ctx=None, dtype="float32", **kwargs):
    shape = (shape,) if isinstance(shape, int) else tuple(shape)
    return NDArray(jax.device_put(jnp.ones(shape, np.dtype(dtype)),
                                  _resolve_ctx(ctx).jax_device()))


def full(shape, val, ctx=None, dtype="float32"):
    shape = (shape,) if isinstance(shape, int) else tuple(shape)
    return NDArray(jax.device_put(jnp.full(shape, val, np.dtype(dtype)),
                                  _resolve_ctx(ctx).jax_device()))


def arange(start, stop=None, step=1.0, repeat=1, ctx=None, dtype="float32"):
    out = jnp.arange(start, stop, step, np.dtype(dtype))
    if repeat > 1:
        out = jnp.repeat(out, repeat)
    return NDArray(jax.device_put(out, _resolve_ctx(ctx).jax_device()))


def moveaxis(data, source, destination):
    return NDArray(jnp.moveaxis(data._data, source, destination))


def onehot_encode(indices, out):
    depth = out.shape[-1]
    res = invoke(get_op("one_hot"), [indices], {"depth": depth})
    out._data = res._data
    return out


def concat(*data, dim=1):
    return invoke(get_op("Concat"), list(data), {"dim": dim})


def concatenate(arrays, axis=0):
    return invoke(get_op("Concat"), list(arrays), {"dim": axis})


# ----------------------------------------------------------------------
# Serialization: the reference's .params container
# (reference: src/ndarray/ndarray.cc :: NDArray::Save/Load, magic numbers
# kMXAPINDArrayListMagic=0x112, NDARRAY_V2_MAGIC=0xF993FAC9).  Binary
# layout follows the reference's dmlc::Stream order; exact byte-for-byte
# compatibility could not be verified against the (empty) mount -- the
# format below is self-consistent and documented.
# ----------------------------------------------------------------------

_LIST_MAGIC = 0x112
_ND_MAGIC = 0xF993FAC9


def _save_one(f, arr):
    # accepts host numpy arrays too: the checkpoint subsystem's async
    # writer serializes device_get snapshots off-thread, and wrapping
    # them back into NDArray would round-trip through the device
    a = arr.asnumpy() if isinstance(arr, NDArray) else np.asarray(arr)
    f.write(struct.pack("<I", _ND_MAGIC))
    f.write(struct.pack("<i", 0))  # storage type: dense
    f.write(struct.pack("<I", a.ndim))
    for d in a.shape:
        f.write(struct.pack("<q", d))
    f.write(struct.pack("<ii", 1, 0))  # dev_type=cpu, dev_id
    f.write(struct.pack("<i", _MX_DTYPE_TO_FLAG[np.dtype(a.dtype)]))
    buf = np.ascontiguousarray(a)
    if buf.dtype == np.dtype(jnp.bfloat16.dtype):
        f.write(buf.view(np.uint16).tobytes())
    else:
        f.write(buf.tobytes())


def _load_one(f) -> NDArray:
    magic, = struct.unpack("<I", f.read(4))
    if magic != _ND_MAGIC:
        raise MXNetError("bad NDArray magic 0x%x" % magic)
    struct.unpack("<i", f.read(4))  # stype
    ndim, = struct.unpack("<I", f.read(4))
    shape = tuple(struct.unpack("<q", f.read(8))[0] for _ in range(ndim))
    struct.unpack("<ii", f.read(8))
    flag, = struct.unpack("<i", f.read(4))
    dtype = _FLAG_TO_MX_DTYPE[flag]
    n = int(np.prod(shape)) if shape else 1
    if flag == 100:
        raw = np.frombuffer(f.read(n * 2), dtype=np.uint16).view(
            np.dtype(jnp.bfloat16.dtype))
    else:
        raw = np.frombuffer(f.read(n * dtype.itemsize), dtype=dtype)
    return NDArray(jnp.asarray(raw.reshape(shape)))


def save(fname, data):
    """Save NDArrays (or host numpy arrays) to the reference's
    ``.params`` container format (reference: ``mx.nd.save`` /
    ``c_api.cc :: MXNDArraySave``).

    This is the serialization *primitive*: it writes ``fname`` in
    place.  State-checkpoint callers must wrap it in
    ``mx.checkpoint.core.commit`` for torn-write safety (the
    bare-state-write lint rule enforces this at call sites).
    """
    if isinstance(data, NDArray):
        data, names = [data], []
    elif isinstance(data, dict):
        names = list(data.keys())
        data = [data[k] for k in names]
    else:
        data, names = list(data), []
    with open(fname, "wb") as f:  # mxlint: disable=bare-state-write
        f.write(struct.pack("<Q", _LIST_MAGIC))
        f.write(struct.pack("<Q", 0))
        f.write(struct.pack("<Q", len(data)))
        for arr in data:
            _save_one(f, arr)
        f.write(struct.pack("<Q", len(names)))
        for n in names:
            b = n.encode("utf-8")
            f.write(struct.pack("<Q", len(b)))
            f.write(b)


def load(fname):
    """Load a ``.params`` container (reference: ``mx.nd.load``)."""
    with open(fname, "rb") as f:
        magic, = struct.unpack("<Q", f.read(8))
        if magic != _LIST_MAGIC:
            raise MXNetError("bad .params magic 0x%x" % magic)
        struct.unpack("<Q", f.read(8))
        count, = struct.unpack("<Q", f.read(8))
        arrays = [_load_one(f) for _ in range(count)]
        nnames, = struct.unpack("<Q", f.read(8))
        names = []
        for _ in range(nnames):
            ln, = struct.unpack("<Q", f.read(8))
            names.append(f.read(ln).decode("utf-8"))
    if names:
        return dict(zip(names, arrays))
    return arrays


def transpose(data, axes=None):
    return invoke(get_op("transpose"), [data], {"axes": axes})
