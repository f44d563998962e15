"""Data parallelism over a ``jax.sharding.Mesh``.

TPU-native re-design of the reference's data-parallel stack
(``src/kvstore/comm.h :: CommDevice`` in-process reduce,
``python/mxnet/module/executor_group.py :: DataParallelExecutorGroup``
batch slicing, NCCL allreduce):

- The reference keeps one parameter/gradient copy per GPU and reduces
  between them.  Here there is ONE logical ``jax.Array`` per tensor:
  parameters are *replicated* over the mesh, the batch is *sharded* over
  the ``dp`` axis, and XLA's SPMD partitioner inserts the gradient
  ``psum`` over ICI inside the compiled step -- the comm/compute overlap
  the reference gets from engine-ordered NCCL calls falls out of XLA's
  latency-hiding scheduler.
- ``TrainStep`` compiles forward + loss + backward + optimizer update
  into ONE donated-buffer XLA program: the answer to the reference's
  bulked CachedOp forward/backward plus fused ``multi_sgd_update``
  (``src/operator/optimizer_op.cc``) in a single dispatch.
"""
from __future__ import annotations

import contextlib
import time

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..base import MXNetError, scopes_in_cache_key
from ..ndarray import NDArray
from .. import obs as _obs
from .. import profiling as _profiling
from .. import random as _random_mod
from .mesh import (batch_sharded, global_mesh, put_replicated,
                   stage_process_local)

__all__ = ["replicate_block", "shard_batch", "split_and_load", "TrainStep"]


def _replicated(mesh):
    return NamedSharding(mesh, P())


def _feed_scalar(val, dtype, sharding=None):
    """Per-step host scalar feed (step counter, scheduled lr/wd,
    rescale) as an EXPLICIT device transfer, landed replicated on the
    mesh when one is given.  ``jnp.asarray`` would bind a
    convert_element_type on the Python value -- an IMPLICIT transfer
    that ``transfer_guard("disallow")`` rejects -- and an unplaced feed
    would be resharded device-to-device at dispatch; the guard must
    stay armable over the steady-state step loop so only genuine leaks
    raise (docs/sharding.md).  ``put_replicated`` keeps this valid on a
    multi-host global mesh (the scalar is identical on every rank)."""
    x = np.asarray(val, dtype)
    return put_replicated(x, sharding) if sharding is not None \
        else jax.device_put(x)


def _batch_sharding(mesh, ndim, batch_axis=0, axis_name="dp"):
    spec = [None] * ndim
    spec[batch_axis] = axis_name
    return NamedSharding(mesh, P(*spec))


def replicate_block(block_or_params, mesh):
    """Place every initialized parameter (and its grad buffer) replicated
    over the mesh.  The reference analog is ``ParameterDict.reset_ctx`` to
    a list of contexts; one replicated jax.Array replaces the per-device
    copy list.

    On a multi-host global mesh the value must be IDENTICAL on every
    rank before global placement (each process contributes its
    addressable shards): every not-yet-placed parameter is first synced
    from rank 0 through ONE bucketed host broadcast, then assembled
    into the global replicated array."""
    params = block_or_params
    if hasattr(params, "collect_params"):
        params = params.collect_params()
    sh = _replicated(mesh)
    todo = []
    for p in params.values():
        p._sharding = sh  # consumed by Parameter._finish_init for deferred
        if p._data is None:
            continue
        if not p._data._data.sharding.is_equivalent_to(
                sh, p._data._data.ndim):
            todo.append(p)
    if todo and not getattr(sh, "is_fully_addressable", True):
        from ..distributed import host_broadcast_bucketed
        synced = host_broadcast_bucketed(
            [np.asarray(p._data._data) for p in todo])
        for p, v in zip(todo, synced):
            p._data._data = put_replicated(np.asarray(v), sh)
            if p._data._grad is not None:
                p._data._grad._data = put_replicated(
                    np.asarray(p._data._grad._data), sh)
    else:
        for p in todo:
            p._data._data = jax.device_put(p._data._data, sh)
            if p._data._grad is not None:
                p._data._grad._data = jax.device_put(p._data._grad._data,
                                                     sh)
    return block_or_params


def shard_batch(data, mesh, batch_axis=0, axis_name="dp"):
    """Shard one batch array over the mesh's data-parallel axis.

    Returns an NDArray backed by a single global jax.Array whose shards
    live on the mesh devices (the reference's
    ``DataParallelExecutorGroup`` batch slicing, done by sharding).  On
    a multi-host mesh the input is this process's LOCAL batch and the
    result is the (nproc x local) global batch
    (``mesh.stage_process_local``)."""
    x = data._data if isinstance(data, NDArray) else jnp.asarray(data)
    sh = _batch_sharding(mesh, x.ndim, batch_axis, axis_name)
    if getattr(sh, "is_fully_addressable", True):
        n = mesh.shape[axis_name]
        if x.shape[batch_axis] % n:
            raise MXNetError(
                "batch axis %d (size %d) not divisible by %s=%d"
                % (batch_axis, x.shape[batch_axis], axis_name, n))
    return NDArray(stage_process_local(x, sh))


def split_and_load(data, ctx_list=None, mesh=None, batch_axis=0,
                   even_split=True):
    """Reference: ``gluon.utils.split_and_load`` -- slice a batch across
    devices.  With ``mesh`` given, returns a one-element list holding a
    single mesh-sharded NDArray (the TPU-idiomatic form); with
    ``ctx_list``, returns per-context slices (API compatibility)."""
    from ..ndarray import array as nd_array
    if mesh is not None:
        return [shard_batch(data, mesh, batch_axis)]
    if not ctx_list:
        raise MXNetError("split_and_load needs ctx_list or mesh")
    if isinstance(data, NDArray):
        data = data.asnumpy()
    data = np.asarray(data)
    n = len(ctx_list)
    size = data.shape[batch_axis]
    if even_split and size % n:
        raise MXNetError("batch size %d not divisible by %d contexts"
                         % (size, n))
    step = size // n
    slices = []
    for i, ctx in enumerate(ctx_list):
        lo = i * step
        hi = (i + 1) * step if i < n - 1 else size
        idx = [slice(None)] * data.ndim
        idx[batch_axis] = slice(lo, hi)
        slices.append(nd_array(data[tuple(idx)], ctx=ctx))
    return slices


# ----------------------------------------------------------------------
# Functional optimizer update (traced)
# ----------------------------------------------------------------------

class _TracedCount(dict):
    """Stands in for ``Optimizer._index_update_count`` during tracing so
    the per-step counter ``t`` is a traced input, not a baked constant."""

    def __init__(self, t):
        super().__init__()
        self._t = t

    def __getitem__(self, k):
        return self._t

    def __contains__(self, k):
        return True


@contextlib.contextmanager
def _scalar_feed(opt, t, lr_by_idx, wd_by_idx, rescale):
    """Route every host-side scalar the optimizer reads (step count,
    scheduled lr, wd, rescale_grad) to traced inputs, so one compiled
    step stays valid across steps and lr schedules."""
    orig = (opt._update_count, opt._get_lr, opt._get_wd,
            opt._index_update_count, opt.rescale_grad)
    opt._update_count = lambda index: None
    opt._index_update_count = _TracedCount(t)
    opt._get_lr = lambda index: lr_by_idx[index]
    opt._get_wd = lambda index: wd_by_idx[index]
    opt.rescale_grad = rescale
    try:
        yield
    finally:
        (opt._update_count, opt._get_lr, opt._get_wd,
         opt._index_update_count, opt.rescale_grad) = orig


def _wrap_state(s):
    if s is None:
        return None
    if isinstance(s, (tuple, list)):
        return tuple(_wrap_state(x) for x in s)
    if isinstance(s, NDArray):
        return NDArray(s._data)
    # raw jax array / tracer leaf (inside jit): wrap so the optimizer's
    # NDArray-rebinding update code works unchanged under trace
    return NDArray(s)


def _select_state(pred, new, old):
    """Elementwise select between updated and original optimizer state
    trees (new leaves are NDArray-wrapped, old leaves raw arrays)."""
    if new is None:
        return None
    if isinstance(new, (tuple, list)):
        return tuple(_select_state(pred, n, o) for n, o in zip(new, old))
    nv = new._data if isinstance(new, NDArray) else new
    ov = old._data if isinstance(old, NDArray) else old
    import jax.numpy as _jnp
    return _jnp.where(pred, nv, ov)


def _state_leaves(s):
    if s is None:
        return []
    if isinstance(s, (tuple, list)):
        out = []
        for x in s:
            out.extend(_state_leaves(x))
        return out
    if isinstance(s, NDArray):
        return [s]
    return []


class TrainStep:
    """One fully-compiled SPMD training step.

    ``step = TrainStep(net, loss_fn, trainer, mesh)`` then
    ``loss = step(data, label)``: forward, loss, backward, and the
    optimizer update for every parameter run as a single XLA program with
    parameter/state buffers donated.  With a mesh, the batch is sharded
    over ``dp`` and gradients come out replicated via an XLA-inserted
    ``psum`` over ICI.

    Uses the Trainer's own optimizer and updater state, so
    ``trainer.save_states()`` / lr schedules keep working, and
    interleaves with eager ``trainer.step()`` if needed.
    """

    def __init__(self, block, loss_fn, trainer, mesh=None, batch_axis=0,
                 axis_name="dp", donate=True):
        self._block = block
        self._loss_fn = loss_fn
        self._trainer = trainer
        if mesh is None and jax.process_count() > 1:
            # multi-host world: default to ONE SPMD program over the
            # global mesh -- gradients allreduce in-graph (GSPMD psum),
            # the kvstore is an init-time veneer (docs/distributed.md)
            mesh = global_mesh()
        self._mesh = mesh
        self._batch_axis = batch_axis
        self._axis_name = axis_name
        self._donate = donate
        self._cache = {}
        if mesh is not None:
            replicate_block(block, mesh)

    # -- state plumbing ------------------------------------------------
    def _ensure_states(self):
        tr = self._trainer
        if not tr._kv_initialized:
            tr._init_kvstore()
        elif getattr(tr._kvstore, "_is_dist", False):
            # late deferred-init params (materialized by the probe
            # forward) still need the one-time rank-0 sync; bucketed,
            # init-time only -- the step itself moves no host bytes
            tr._sync_initial_params()
        upd = tr._updater
        opt = tr._optimizer
        for i, p in enumerate(tr._params):
            if p.grad_req == "null" or p._data is None:
                continue
            if i not in upd.states:
                upd.states[i] = opt.create_state_multi_precision(i, p.data())
        if self._mesh is not None:
            sh = _replicated(self._mesh)
            for s in upd.states.values():
                for leaf in _state_leaves(s):
                    if not leaf._data.sharding.is_equivalent_to(sh, leaf._data.ndim):
                        leaf._data = put_replicated(leaf._data, sh)

    def _diff_indices(self):
        tr = self._trainer
        return [i for i, p in enumerate(tr._params)
                if p.grad_req != "null" and p._data is not None]

    def _stage_io(self, data, label, shift=0):
        """Stage one (data, label) pair for dispatch.  Host batches land
        through the EXPLICIT staging primitives (guard-clean under
        ``transfer_guard("disallow")``); device arrays reshard only when
        their sharding differs from the target.  With a mesh the batch
        axis shards over ``dp`` -- and on a multi-host global mesh the
        input is this process's LOCAL batch, staged as its slice of the
        global batch (``mesh.stage_process_local``), so the compiled
        step is ONE SPMD program over pre-sharded inputs."""
        if self._mesh is None:
            if not isinstance(data, NDArray):
                data = NDArray(jnp.asarray(data))
            if not isinstance(label, NDArray):
                label = NDArray(jnp.asarray(label))
            return data, label
        dx = data._data if isinstance(data, NDArray) else data
        lx = label._data if isinstance(label, NDArray) else label
        if not isinstance(dx, jax.Array):
            dx = np.asarray(dx)
        if not isinstance(lx, jax.Array):
            lx = np.asarray(lx)
        if getattr(dx, "ndim", 0):
            want = _batch_sharding(self._mesh, dx.ndim,
                                   self._batch_axis + shift,
                                   self._axis_name)
            lsh = _batch_sharding(self._mesh, lx.ndim, shift,
                                  self._axis_name)
            dx = stage_process_local(dx, want)
            lx = stage_process_local(lx, lsh)
        return NDArray(dx), NDArray(lx)

    # -- compilation ---------------------------------------------------
    def _build(self, ivals, training):
        tr = self._trainer
        opt = tr._optimizer
        block = self._block
        loss_fn = self._loss_fn
        idxs = self._diff_indices()
        pure_fn, pnames, pmap = block.functionalize(training=training)
        name_by_idx = {i: tr._params[i].name for i in idxs}
        # entered by the traced bodies themselves, so every (re)trace
        # -- the dispatch, an AOT lowering for cost analysis -- tells
        # kernels XLA cannot partition which axis the batch is split over
        scope = batch_sharded(self._mesh, self._axis_name)

        @scope
        def step_fn(pvals, svals, data, label, rng, t, lrs, wds, rescale,
                    loss_scale):
            def loss_of(diff_pvals):
                merged = dict(pvals)
                merged.update(diff_pvals)
                outs, aux = pure_fn(merged, [data], rng)
                out_nd = [NDArray(o) for o in outs]
                with jax.named_scope("mx.loss"):
                    l = loss_fn(out_nd[0] if len(out_nd) == 1 else out_nd,
                                NDArray(label))
                    ldata = l._data if isinstance(l, NDArray) else l
                    # Sum (not mean): the reference seeds backward with
                    # ones over the batch loss and rescales by
                    # 1/batch_size in the optimizer (Trainer.step
                    # semantics).  loss_scale is the fp16 AMP scale (1.0
                    # otherwise); rescale folds in its inverse.
                    return (jnp.sum(ldata) * loss_scale,
                            (jnp.mean(ldata), aux))

            diff_pvals = {name_by_idx[i]: pvals[name_by_idx[i]] for i in idxs}
            grads_and_aux = jax.value_and_grad(loss_of, has_aux=True)(
                diff_pvals)
            (_, (mean_loss, aux)), grads = grads_and_aux

            # Branchless fp16 overflow skip: if any gradient is non-finite
            # the select below keeps the old weights/states (the XLA
            # answer to the reference's skip-update-on-overflow).  ONE
            # fused isfinite-reduction over the dtype-bucketed gradient
            # set (the numerics sentinel's in-graph form) -- one boolean
            # output, no extra host sync on the clean path.
            from ..analysis import numerics as _numerics
            with jax.named_scope("mx.finite_check"):
                all_finite = _numerics.finite_tree(
                    jax.tree_util.tree_leaves(grads))

            with jax.named_scope("mx.optimizer"):
                lr_map = {i: lrs[k] for k, i in enumerate(idxs)}
                wd_map = {i: wds[k] for k, i in enumerate(idxs)}
            # Start from the full pvals: every parameter buffer is donated,
            # so every one must come back out (unchanged ones alias
            # through), or frozen params would be left deleted.
            new_w = dict(pvals)
            new_s = {}
            with _scalar_feed(opt, t, lr_map, wd_map, rescale), \
                    jax.named_scope("mx.optimizer"):
                for i in idxs:
                    nm = name_by_idx[i]
                    w = NDArray(pvals[nm])
                    g = NDArray(grads[nm])
                    s = _wrap_state(svals.get(i))
                    opt.update_multi_precision(i, w, g, s)
                    new_w[nm] = jnp.where(all_finite, w._data, pvals[nm])
                    new_s[i] = _select_state(all_finite, s, svals.get(i))
            return new_w, new_s, aux, mean_loss, all_finite

        @scope
        def probe_fn(pvals, data, label, rng, loss_scale):
            # failure-path attribution (numerics sentinel): recompute
            # the gradients from the SAME params/batch/rng -- on a
            # non-finite step the where-select above kept the old
            # weights, so pvals reproduce the faulting step exactly --
            # and hand them back for a host-side per-parameter scan.
            # Never donated, compiled lazily on first non-finite step.
            def loss_of(diff_pvals):
                merged = dict(pvals)
                merged.update(diff_pvals)
                outs, aux = pure_fn(merged, [data], rng)
                out_nd = [NDArray(o) for o in outs]
                l = loss_fn(out_nd[0] if len(out_nd) == 1 else out_nd,
                            NDArray(label))
                ldata = l._data if isinstance(l, NDArray) else l
                return jnp.sum(ldata) * loss_scale, jnp.mean(ldata)

            diff_pvals = {name_by_idx[i]: pvals[name_by_idx[i]]
                          for i in idxs}
            (_, mean_loss), grads = jax.value_and_grad(
                loss_of, has_aux=True)(diff_pvals)
            return grads, mean_loss

        jit_kwargs = {}
        if self._mesh is not None:
            mesh = self._mesh
            rep = _replicated(mesh)

            def rep_tree(tree):
                return jax.tree_util.tree_map(lambda _: rep, tree)

            data_sh = _batch_sharding(mesh, len(ivals[0].shape),
                                      self._batch_axis, self._axis_name)
            label_sh = _batch_sharding(mesh, len(ivals[1].shape),
                                       0, self._axis_name)
            jit_kwargs["in_shardings"] = (
                None, None, data_sh, label_sh, rep, rep, rep, rep, rep, rep)
        if self._donate:
            jit_kwargs["donate_argnums"] = (0, 1)
        # the attribution probe must NOT donate: it re-reads the live
        # param buffers after a failed step
        return (jax.jit(step_fn, **jit_kwargs),
                jax.jit(probe_fn),  # mxlint: disable=undonated-train-state
                idxs, pnames, pmap)

    # -- multi-step scan ----------------------------------------------
    def _build_scan(self, ivals, training):
        """Compile a ``lax.scan`` over K training steps: one dispatch runs
        K full fwd+bwd+update iterations on stacked batches (K, B, ...).

        TPU-idiomatic epoch inner loop: removes per-step host dispatch
        entirely (the reference's analog is engine-queued bulk execution;
        here the loop itself is on device).
        """
        fn_single, _probe, idxs, pnames, pmap = self._build(
            [NDArray(ivals[0]._data[0]), NDArray(ivals[1]._data[0])],
            training)
        aux_names = None

        def scan_fn(pvals, svals, datas, labels, rng, t0, lrs, wds,
                    rescale, loss_scale):
            k = datas.shape[0]

            def body(carry, xs):
                pv, sv, t = carry
                data, label, key = xs
                new_w, new_s, aux, mean_loss, _fin = fn_single(
                    pv, sv, data, label, key, t, lrs, wds, rescale,
                    loss_scale)
                # thread updated BN running stats back in for the next step
                new_w = dict(new_w)
                for n, v in aux.items():
                    new_w[n] = v
                return (new_w, new_s, t + 1), mean_loss

            keys = jax.random.split(rng, k)
            (pv, sv, t), losses = jax.lax.scan(
                body, (pvals, svals, t0), (datas, labels, keys))
            return pv, sv, t, losses

        jit_kwargs = {}
        if self._mesh is not None:
            mesh = self._mesh
            rep = _replicated(mesh)
            data_sh = _batch_sharding(mesh, ivals[0]._data.ndim,
                                      self._batch_axis + 1, self._axis_name)
            label_sh = _batch_sharding(mesh, ivals[1]._data.ndim, 1,
                                       self._axis_name)
            jit_kwargs["in_shardings"] = (
                None, None, data_sh, label_sh, rep, rep, rep, rep, rep, rep)
        if self._donate:
            jit_kwargs["donate_argnums"] = (0, 1)
        return jax.jit(scan_fn, **jit_kwargs), idxs, pnames, pmap

    def run_steps(self, data, label, batch_size=None):
        """Run K training steps in ONE compiled dispatch.

        ``data``/``label`` carry a leading steps axis: (K, B, ...).
        Returns the per-step mean losses as an NDArray of shape (K,).
        BatchNorm running stats, optimizer state, and the step counter all
        thread through the on-device loop.
        """
        tr = self._trainer
        opt = tr._optimizer
        if getattr(tr, "_amp_loss_scaler", None) is not None:
            raise MXNetError(
                "run_steps does not support fp16 dynamic loss scaling "
                "(the scaler's growth/backoff counters live on the host); "
                "use bf16 AMP or per-step __call__ for fp16")
        step = _obs.span("mx.train_step", step=opt.num_update + 1)
        with step:
            return self._run_steps(step, data, label, batch_size)

    def _run_steps(self, step, data, label, batch_size):
        from .. import amp as _amp
        from ..ndarray import bulk as _bulk
        tr = self._trainer
        opt = tr._optimizer
        with _obs.span("mx.train_step.prep"):
            for p in tr._params:
                if p._data is not None and p.dtype is not None \
                        and p._data._data.dtype != p.dtype:
                    p.cast(p.dtype)
            self._ensure_states()
            # leading axis is the step index; batch axis shifts right by 1
            data, label = self._stage_io(data, label, shift=1)
            if any(p._deferred_init is not None
                   for p in self._block._all_params()):
                from .. import autograd as _ag
                with _ag.pause():
                    self._block(NDArray(data._data[0]))
                self._ensure_states()
            k = data.shape[0]
            key = ("scan", tuple(data.shape), str(data.dtype),
                   tuple(label.shape), str(label.dtype), _amp.policy_token())
            entry = self._cache.get(key)
            built = entry is None
            if built:
                entry = self._build_scan([data, label], True)
                self._cache[key] = entry
            fn, idxs, pnames, pmap = entry

            t_start = opt._index_update_count.get(
                idxs[0], opt.begin_num_update) + 1 if idxs else opt.num_update
            # lr/wd are read from the schedule at the BLOCK START and held for
            # the K in-scan steps (the schedule is host-side Python, so it
            # cannot be traced per step); callers with fast-moving schedules
            # should pick K accordingly
            num_update_at_start = max(opt.num_update, t_start)
            saved_num_update = opt.num_update
            opt.num_update = num_update_at_start
            rep = _replicated(self._mesh) if self._mesh is not None else None
            lrs = _feed_scalar([opt._get_lr(i) for i in idxs], np.float32,
                               rep)
            wds = _feed_scalar([opt._get_wd(i) for i in idxs], np.float32,
                               rep)
            opt.num_update = saved_num_update
            for i in idxs:
                opt._index_update_count[i] = \
                    opt._index_update_count.get(i, opt.begin_num_update) + k
                opt.num_update = max(opt._index_update_count[i],
                                     opt.num_update)
            t = _feed_scalar(t_start, np.int32, rep)
            bs = batch_size if batch_size is not None \
                else data.shape[self._batch_axis + 1]
            step.set(items=k * bs)
            rescale = _feed_scalar(tr._scale / bs, np.float32, rep)
            loss_scale = _feed_scalar(1.0, np.float32, rep)
            upd = tr._updater
            pvals = {n: pmap[n]._data._data for n in pnames}
            svals = {i: jax.tree_util.tree_map(
                lambda x: x._data if isinstance(x, NDArray) else x,
                upd.states.get(i),
                is_leaf=lambda x: isinstance(x, NDArray) or x is None)
                for i in idxs}
            rng = _random_mod.next_key()
            args = (pvals, svals, data._data, label._data, rng, t, lrs, wds,
                    rescale, loss_scale)
            self._last_call = (fn, jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args))
            if built:
                self._note_program("train_scan", fn, args)
        with _obs.span("mx.train_step.dispatch"):
            # the jit donates the param/state buffers; any still-pending
            # bulked-eager region referencing them must execute first
            _bulk.flush()
            t0p = time.perf_counter() if _profiling._ENABLED else None
            with scopes_in_cache_key() if built \
                    else contextlib.nullcontext():
                new_w, new_s, _t, losses = fn(*args)
            if t0p is not None:
                label = "train_scan:%s" % type(self._block).__name__
                self._profiling_hook(label, fn,
                                     time.perf_counter() - t0p, k * bs)
        with _obs.span("mx.train_step.rebind"):
            for n in pnames:
                pmap[n]._data._data = new_w[n]
            for i in idxs:
                s = upd.states.get(i)
                flat_new = jax.tree_util.tree_leaves(new_s[i])
                for leaf, nv in zip(_state_leaves(s), flat_new):
                    leaf._data = nv
            # aux (running stats) were threaded inside new_w; rebind
            # Parameters
            for p in self._block._all_params():
                if p.name in pnames and p.grad_req == "null" \
                        and p._data is not None:
                    grad = p._data._grad
                    p._data = NDArray(new_w[p.name])
                    p._data._grad = grad
        return NDArray(losses)

    def _profiling_hook(self, label, fn, dispatch_s, items):
        """mx.profiling capture for one dispatched step: register the
        compiled program for lazy cost analysis and feed the roofline's
        step clock.  On a synchronous backend (CPU CI) the dispatch wall
        IS the step time; on async TPU dispatch the steady-state loop is
        back-pressured by buffer donation, so per-call wall converges to
        step time -- callers with externally synced windows can refine
        via ``profiling.record_step``.  (The step's host spans are
        ``mx.train_step`` and its children, through ``obs.span``.)"""
        _profiling.capture_jit(label, fn, self._last_call[1],
                               key=("train_step", id(fn)),
                               kind="train_step")
        _profiling.record_step(label, dispatch_s, items=items)

    def _note_program(self, kind, fn, args):
        """Leave ``obs.program_scopes`` a way to this program's compiled
        text: lowered for the arguments' own shardings, it is the program
        that runs, so the lowering hits the jit's own compile or the
        persistent cache."""
        specs = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=a.sharding), args)

        def text():
            with scopes_in_cache_key():     # the key it was compiled under
                return fn.lower(*specs).compile().as_text()

        _obs.note_program("%s:%s" % (kind, type(self._block).__name__),
                          text)

    def cost_analysis(self):
        """XLA's cost analysis of the most recently dispatched compiled
        program -- ``{"flops": ..., "bytes accessed": ..., ...}`` or None.
        Powers the bench's MFU report.  Cheap after the first call: the
        lowering hits the jit compile cache."""
        if getattr(self, "_last_call", None) is None:
            return None
        fn, arg_shapes = self._last_call
        try:
            return dict(fn.lower(*arg_shapes).compile().cost_analysis())
        except Exception:
            return None

    # -- call ----------------------------------------------------------
    def __call__(self, data, label=None, batch_size=None):
        if label is None:
            # a fed batch (dataio.DeviceFeed) carries device-resident
            # data+label; unpack without any re-transfer
            from ..dataio import DeviceBatch
            if isinstance(data, DeviceBatch):
                data, label = data.data, data.label
            if label is None:
                raise MXNetError(
                    "TrainStep needs (data, label) or a DeviceBatch "
                    "with a label component")
        step = _obs.span("mx.train_step",
                         step=self._trainer._optimizer.num_update + 1)
        with step:
            return self._step(step, data, label, batch_size)

    def _step(self, step, data, label, batch_size):
        from .. import autograd as _ag
        from ..ndarray import bulk as _bulk
        tr = self._trainer
        opt = tr._optimizer
        with _obs.span("mx.train_step.prep"):
            # value dtype must match the declared Parameter dtype BEFORE
            # optimizer states are created from it (a drifted value would
            # bake mismatched state dtypes in for the whole run);
            # Parameter.cast also reallocates the grad buffer
            for p in tr._params:
                if p._data is not None and p.dtype is not None \
                        and p._data._data.dtype != p.dtype:
                    p.cast(p.dtype)
            self._ensure_states()
            data, label = self._stage_io(data, label)
            if any(p._deferred_init is not None
                   for p in self._block._all_params()):
                # materialize deferred shapes with one eager forward;
                # Parameter._sharding (set by replicate_block) places them
                # replicated on the mesh
                with _ag.pause():
                    self._block(data)
                self._ensure_states()

            from ..analysis import numerics as _numerics
            from .. import chaos as _chaos
            # numerics.nonfinite chaos point: poison_action marks the box
            # and THIS step injects the NaN into its own batch, so the
            # fault flows through forward/backward and must be caught by
            # the sentinel, not the injector (docs/numerics.md)
            _box = {}
            _chaos.fail_point("numerics.nonfinite", box=_box,
                              step=opt.num_update + 1)
            if _box.get("poison"):
                data = _numerics.poison_nd(data)

            training = True
            from .. import amp as _amp
            key = (tuple(data.shape), str(data.dtype), tuple(label.shape),
                   str(label.dtype), training, _amp.policy_token())
            entry = self._cache.get(key)
            built = entry is None
            if built:
                entry = self._build([data, label], training)
                self._cache[key] = entry
            fn, probe, idxs, pnames, pmap = entry

            # host-side per-step bookkeeping (matches Optimizer._update_count)
            for i in idxs:
                opt._index_update_count[i] = \
                    opt._index_update_count.get(i, opt.begin_num_update) + 1
                opt.num_update = max(opt._index_update_count[i],
                                     opt.num_update)
            rep = _replicated(self._mesh) if self._mesh is not None else None
            t = _feed_scalar(opt._index_update_count[idxs[0]] if idxs else
                             opt.num_update, np.int32, rep)
            lrs = _feed_scalar([opt._get_lr(i) for i in idxs], np.float32,
                               rep)
            wds = _feed_scalar([opt._get_wd(i) for i in idxs], np.float32,
                               rep)
            bs = batch_size if batch_size is not None \
                else data.shape[self._batch_axis]
            step.set(items=bs)
            scaler = getattr(tr, "_amp_loss_scaler", None)
            ls = scaler.loss_scale if scaler is not None else 1.0
            rescale = _feed_scalar(tr._scale / bs / ls, np.float32, rep)
            loss_scale = _feed_scalar(ls, np.float32, rep)

            upd = tr._updater
            pvals = {n: pmap[n]._data._data for n in pnames}
            svals = {i: jax.tree_util.tree_map(
                lambda x: x._data if isinstance(x, NDArray) else x,
                upd.states.get(i),
                is_leaf=lambda x: isinstance(x, NDArray) or x is None)
                for i in idxs}
            rng = _random_mod.next_key()

            args = (pvals, svals, data._data, label._data, rng, t, lrs, wds,
                    rescale, loss_scale)
            self._last_call = (fn, jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args))
            if built:
                self._note_program("train_step", fn, args)
        with _obs.span("mx.train_step.dispatch"):
            # the jit donates the param/state buffers; any still-pending
            # bulked-eager region referencing them must execute first
            _bulk.flush()
            t0p = time.perf_counter() if _profiling._ENABLED else None
            # a program's first dispatch compiles it: with its scopes in
            # the cache key, so that a trace reads this version's names
            with scopes_in_cache_key() if built \
                    else contextlib.nullcontext():
                new_w, new_s, aux, mean_loss, all_finite = fn(*args)
            if t0p is not None:
                label = "train_step:%s" % type(self._block).__name__
                self._profiling_hook(label, fn,
                                     time.perf_counter() - t0p, bs)
        finite_host = None
        if scaler is not None:
            # host sync only in fp16 mode: the scaler's growth/backoff
            # counters live on the host (reference LossScaler semantics)
            finite_host = bool(np.asarray(all_finite))
            scaler.update_scale(not finite_host)

        with _obs.span("mx.train_step.rebind"):
            # rebind updated weights/states/aux into the framework objects
            # (ALL params: buffers were donated, unchanged ones aliased
            # through)
            for n in pnames:
                pmap[n]._data._data = new_w[n]
            for i in idxs:
                s = upd.states.get(i)
                flat_new = jax.tree_util.tree_leaves(new_s[i])
                for leaf, nv in zip(_state_leaves(s), flat_new):
                    leaf._data = nv
            for p in self._block._all_params():
                if p.name in aux:
                    grad = p._data._grad if p._data is not None else None
                    p._data = NDArray(aux[p.name])
                    p._data._grad = grad

        if _numerics.check_enabled():
            # the sentinel reads the ONE boolean the compiled step
            # already produced (shared with the fp16 scaler's fetch);
            # framework state was rebound above -- on a non-finite step
            # the where-select kept the pre-step weights, so raising
            # here leaves the model consistent and restartable
            t0s = time.perf_counter()
            if finite_host is None:
                finite_host = bool(np.asarray(all_finite))
            _numerics.note_check(time.perf_counter() - t0s)
            if not finite_host:
                step_no = opt.num_update
                # attribution pass: recompute this step's gradients
                # from the restored params + the same batch/rng, then
                # scan per-parameter host-side (failure path only)
                grads, probe_loss = probe(new_w, args[2], args[3],
                                          args[4], args[9])
                names = [tr._params[i].name for i in idxs]
                named = [(nm, grads[nm]) for nm in names if nm in grads]
                hit = _numerics.attribute_nonfinite(
                    named + [("loss", probe_loss)])
                param, kind = hit if hit is not None else (
                    "<unattributed>", "nonfinite")
                _numerics.record_nonfinite(param, step_no, kind)
                raise _numerics.NonFiniteError(param, step_no, kind)
        return NDArray(mean_loss)
