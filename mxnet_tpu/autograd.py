"""Imperative autograd: record / pause / backward / Function.

TPU-native re-design of the reference's C++ tape
(``src/imperative/imperative.cc :: Imperative::RecordOp / Backward``,
Python face ``python/mxnet/autograd.py``).  Design:

- While recording, every op dispatch calls ``jax.vjp`` on its pure compute
  function, storing the residual-holding ``vjp_fn`` on a tape node.  This
  replaces the reference's nnvm ``Gradient`` pass: the backward graph is
  the chain of recorded vjp closures, executed eagerly in reverse
  topological order (gradients themselves are jax arrays, so the whole
  backward still runs async on-device).
- Only arrays reachable from a ``attach_grad()`` leaf are tracked, matching
  the reference's pruning of non-grad paths.
"""
from __future__ import annotations

import threading
from typing import List, Optional

from .base import MXNetError

_state = threading.local()


def _st():
    if not hasattr(_state, "recording"):
        _state.recording = False
        _state.training = False
    return _state


def is_recording():
    return _st().recording


def is_training():
    return _st().training


def set_recording(is_record):
    prev = _st().recording
    _state.recording = bool(is_record)
    return prev


def set_training(train_mode):
    prev = _st().training
    _state.training = bool(train_mode)
    return prev


class _RecordingStateScope:
    def __init__(self, is_record, train_mode):
        self._is_record = is_record
        self._train = train_mode
        self._prev = None

    def __enter__(self):
        st = _st()
        self._prev = (st.recording, st.training)
        if self._is_record is not None:
            _state.recording = self._is_record
        if self._train is not None:
            _state.training = self._train
        return self

    def __exit__(self, *args):
        _state.recording, _state.training = self._prev


def record(train_mode=True):
    """Scope in which ops are recorded for backward (reference:
    ``autograd.py :: record``)."""
    return _RecordingStateScope(True, train_mode)


def pause(train_mode=False):
    """Scope in which recording is suspended (reference: ``pause``)."""
    return _RecordingStateScope(False, train_mode)


def train_mode():
    return _RecordingStateScope(None, True)


def predict_mode():
    return _RecordingStateScope(None, False)


class TapeNode:
    """One recorded op: inputs, vjp closure, per-output cotangent slots."""

    __slots__ = ("inputs", "vjp_fn", "num_outputs", "out_grads", "name",
                 "_out_avals")

    def __init__(self, inputs, vjp_fn, num_outputs, name=""):
        self.inputs = inputs          # list[NDArray] (tracked or leaf)
        self.vjp_fn = vjp_fn          # cotangents -> input cotangents
        self.num_outputs = num_outputs
        self.out_grads: List[Optional[object]] = [None] * num_outputs
        self.name = name
        self._out_avals = []


def mark_variables(variables, gradients, grad_reqs="write"):
    """Attach gradient buffers to arrays (reference: ``mark_variables``)."""
    if isinstance(grad_reqs, str):
        grad_reqs = [grad_reqs] * len(variables)
    for v, g, req in zip(variables, gradients, grad_reqs):
        v._grad = g
        v._grad_req = req
        v._ag_node = None


def _toposort(head_arrays):
    """Reverse-topological order of tape nodes reachable from heads.

    Iterative DFS: tape length is unbounded (e.g. a long imperative RNN
    unroll records thousands of sequential ops), so recursion would hit
    the Python stack limit.
    """
    order = []
    seen = set()
    for arr in head_arrays:
        root = getattr(arr, "_ag_node", None)
        if root is None or id(root) in seen:
            continue
        stack = [(root, False)]
        while stack:
            node, expanded = stack.pop()
            if expanded:
                order.append(node)
                continue
            if id(node) in seen:
                continue
            seen.add(id(node))
            stack.append((node, True))
            for inp in reversed(node.inputs):
                src = getattr(inp, "_ag_node", None)
                if src is not None and id(src) not in seen:
                    stack.append((src, False))
    return order[::-1]


_LAZY_ADD = None


def _ct_add(a, b):
    """Cotangent accumulation, lazy-aware: pending bulked cotangents add
    inside the queue instead of forcing a flush."""
    from .ndarray import bulk
    if isinstance(a, bulk.LazyData) or isinstance(b, bulk.LazyData):
        global _LAZY_ADD
        if _LAZY_ADD is None:
            import jax as _jax
            _LAZY_ADD = _jax.jit(lambda x, y: x + y)
        return bulk.enqueue(_LAZY_ADD, "ct_add", (a, b))
    return a + b


def backward(heads, head_grads=None, retain_graph=False, train_mode=True):
    """Run backward from head arrays, accumulating into leaf ``.grad``.

    Reference: ``Imperative::Backward`` (``src/imperative/imperative.cc``);
    grad_req semantics ('write'/'add'/'null') per
    ``include/mxnet/op_attr_types.h :: OpReqType``.
    """
    import jax
    import jax.numpy as jnp
    from .ndarray import NDArray

    def _ones_on(data):
        # seed cotangents ON the head's device, COMMITTED: an
        # uncommitted seed lets linear-op transposes (sum/broadcast take
        # only the cotangent) run on the default device, which need
        # not be the head's -- one cross-device copy per backward node
        devs = data.devices()
        if len(devs) == 1:
            dev = next(iter(devs))
            with jax.default_device(dev):
                return jax.device_put(jnp.ones(data.shape, data.dtype),
                                      dev)
        return jnp.ones_like(data)

    if isinstance(heads, NDArray):
        heads = [heads]
    if head_grads is None:
        head_grads = [None] * len(heads)
    elif isinstance(head_grads, NDArray):
        head_grads = [head_grads]

    # Per-backward accumulation buffers: within one backward pass gradients
    # from multiple paths always sum; grad_req only governs how the final
    # sum combines with the existing .grad ('write' replaces, 'add' adds).
    leaf_acc = {}  # id(arr) -> (arr, summed cotangent)

    def _to_leaf(arr, ct):
        if getattr(arr, "_grad_req", "write") == "null":
            return
        key = id(arr)
        if key in leaf_acc:
            leaf_acc[key] = (arr, _ct_add(leaf_acc[key][1], ct))
        else:
            leaf_acc[key] = (arr, ct)

    # Seed cotangents on the producing nodes.
    for h, hg in zip(heads, head_grads):
        node = getattr(h, "_ag_node", None)
        if node is None:
            if getattr(h, "_grad", None) is not None:
                # head is itself a leaf: d head / d head = 1
                g = _ones_on(h._data) if hg is None else hg._data
                _to_leaf(h, g)
                continue
            raise MXNetError(
                "cannot differentiate: array is not part of a recorded "
                "computation (call inside autograd.record())")
        idx = h._ag_out_index
        g = _ones_on(h._data) if hg is None else hg._data
        node.out_grads[idx] = g if node.out_grads[idx] is None \
            else _ct_add(node.out_grads[idx], g)

    for node in _toposort(heads):
        if all(g is None for g in node.out_grads):
            continue
        if node.vjp_fn is None:
            raise MXNetError(
                "backward through a graph that was already freed; pass "
                "retain_graph=True to backward() to allow repeated calls")
        dev = next((next(iter(g.devices())) for g in node.out_grads
                    if g is not None and hasattr(g, "devices")
                    and len(g.devices()) == 1), None)

        def _zeros(shp, dt):
            if dev is not None:
                with jax.default_device(dev):
                    return jax.device_put(jnp.zeros(shp, dt), dev)
            return jnp.zeros(shp, dt)

        cts = tuple(
            g if g is not None else _zeros(shp, dt)
            for g, (shp, dt) in zip(node.out_grads, node._out_avals))
        in_cts = node.vjp_fn(cts if node.num_outputs > 1 else cts[0])
        if not isinstance(in_cts, (tuple, list)):
            in_cts = (in_cts,)
        for inp, ct in zip(node.inputs, in_cts):
            if ct is None:
                continue
            if getattr(ct, "dtype", None) is not None and ct.dtype.name == "float0":
                continue
            src = getattr(inp, "_ag_node", None)
            if src is not None:
                i = inp._ag_out_index
                src.out_grads[i] = ct if src.out_grads[i] is None \
                    else _ct_add(src.out_grads[i], ct)
            elif getattr(inp, "_grad", None) is not None:
                _to_leaf(inp, ct)
        # Cotangent slots always reset (a second backward must not see
        # this pass's partial sums); vjp closures survive only on request.
        node.out_grads = [None] * node.num_outputs
        if not retain_graph:
            node.vjp_fn = None

    for arr, ct in leaf_acc.values():
        if arr._grad_req == "add":
            arr._grad._data = _ct_add(arr._grad._data, ct)
        else:
            arr._grad._data = ct


def grad(heads, variables, head_grads=None, retain_graph=None,
         create_graph=False, train_mode=True):
    """Compute and return gradients w.r.t. ``variables`` (reference:
    ``autograd.py :: grad``).  First-order only in this build."""
    from .ndarray import NDArray
    if create_graph:
        raise MXNetError("create_graph=True (higher-order) not supported yet; "
                         "use gluon hybridize + jax.grad composition instead")
    single = isinstance(variables, NDArray)
    vars_ = [variables] if single else list(variables)
    saved = [(v._grad, getattr(v, "_grad_req", "write")) for v in vars_]
    import jax.numpy as jnp
    for v in vars_:
        z = jnp.zeros_like(v._data)
        g = NDArray(z)
        v._grad = g
        v._grad_req = "add"
    backward(heads, head_grads, retain_graph=bool(retain_graph), train_mode=train_mode)
    outs = [v._grad for v in vars_]
    for v, (og, oreq) in zip(vars_, saved):
        v._grad = og
        v._grad_req = oreq
    return outs[0] if single else outs


def get_symbol(x):
    raise MXNetError("autograd.get_symbol is not supported: use "
                     "HybridBlock.export / Symbol tracing instead")


class Function:
    """Custom differentiable function with user-defined forward/backward
    (reference: ``autograd.py :: Function``)."""

    def __init__(self):
        self._saved = None

    def save_for_backward(self, *args):
        self._saved = args

    @property
    def saved_tensors(self):
        return self._saved

    def forward(self, *inputs):
        raise NotImplementedError

    def backward(self, *output_grads):
        raise NotImplementedError

    def __call__(self, *inputs):
        from .ndarray import NDArray
        with pause():
            outputs = self.forward(*inputs)
        single = not isinstance(outputs, (tuple, list))
        outs = [outputs] if single else list(outputs)
        if is_recording() and any(getattr(i, "_is_tracked", lambda: False)()
                                  for i in inputs if isinstance(i, NDArray)):
            func = self

            def vjp_fn(cts):
                if not isinstance(cts, (tuple, list)):
                    cts = (cts,)
                ct_nd = [NDArray(c) for c in cts]
                with pause():
                    in_grads = func.backward(*ct_nd)
                if not isinstance(in_grads, (tuple, list)):
                    in_grads = (in_grads,)
                return tuple(g._data if isinstance(g, NDArray) else g
                             for g in in_grads)

            node = TapeNode([i for i in inputs if isinstance(i, NDArray)],
                            vjp_fn, len(outs), name=type(self).__name__)
            node._out_avals = [(o.shape, o.dtype) for o in outs]
            for i, o in enumerate(outs):
                o._ag_node = node
                o._ag_out_index = i
        return outs[0] if single else outs
