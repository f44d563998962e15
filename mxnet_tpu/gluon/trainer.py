"""Gluon Trainer (reference: ``python/mxnet/gluon/trainer.py``).

Applies an Optimizer to a set of Parameters after backward.  KVStore
integration: gradients reduce across devices through the KVStore API
(which on TPU is ICI collectives -- ``mxnet_tpu/kvstore.py``) before the
update, preserving the reference's ``update_on_kvstore`` semantics.
"""
from __future__ import annotations

import time

from .. import optimizer as opt
from .. import obs as _obs
from .. import telemetry as _telemetry
from ..base import MXNetError
from .parameter import Parameter, ParameterDict


class Trainer:
    def __init__(self, params, optimizer, optimizer_params=None, kvstore="device",
                 compression_params=None, update_on_kvstore=None):
        if isinstance(params, (dict, ParameterDict)):
            params = list(params.values())
        if not isinstance(params, (list, tuple)):
            raise MXNetError("params must be a dict/list of Parameters")
        self._params = []
        self._param2idx = {}
        for i, p in enumerate(params):
            if not isinstance(p, Parameter):
                raise MXNetError("non-Parameter in Trainer params: %r" % (p,))
            self._params.append(p)
            self._param2idx[p.name] = i
        self._scale = 1.0
        optimizer_params = optimizer_params or {}
        if isinstance(optimizer, opt.Optimizer):
            self._optimizer = optimizer
        else:
            param_dict = {i: p for i, p in enumerate(self._params)}
            self._optimizer = opt.create(optimizer, param_dict=param_dict,
                                         **optimizer_params)
        self._updater = opt.get_updater(self._optimizer)
        self._kvstore = None
        self._update_on_kvstore = update_on_kvstore
        self._kv_initialized = False
        self._kvstore_spec = kvstore
        self._compression_params = compression_params

    @property
    def learning_rate(self):
        return self._optimizer.learning_rate

    @property
    def optimizer(self):
        return self._optimizer

    def set_learning_rate(self, lr):
        self._optimizer.set_learning_rate(lr)

    def _init_kvstore(self):
        from .. import kvstore as kvs
        spec = self._kvstore_spec
        if spec is None:
            self._kvstore = None
        elif isinstance(spec, str):
            self._kvstore = kvs.create(spec) if spec else None
        else:
            self._kvstore = spec
        if self._kvstore is not None and self._compression_params:
            self._kvstore.set_gradient_compression(self._compression_params)
        self._dist_synced = set()
        self._sync_initial_params()
        self._kv_initialized = True

    def _sync_initial_params(self):
        """Reference semantics (kvstore_dist.h :: Init + Pull): rank
        0's initial weights are pushed to the servers and every worker
        pulls them back, so all ranks START identical even though each
        process's initializer drew from its own entropy.  Serverless
        analog: broadcast from rank 0.  Runs per step so params whose
        deferred init materializes LATER still get synced exactly once
        (the reference inits kvstore keys lazily per-param too).

        SPMD assumption (same as the reference's lazy kv.init, which is
        also a collective): deferred params must materialize at the
        SAME step on every rank -- host_broadcast is a world
        collective, so asymmetric materialization would desequence the
        collectives."""
        if self._kvstore is None or \
                not getattr(self._kvstore, "_is_dist", False):
            return
        from ..distributed import host_broadcast_bucketed, world
        if world()[0] <= 1:
            return
        todo = [p for p in self._params
                if p.name not in self._dist_synced and p._data is not None]
        if not todo:
            return
        # ONE flattened collective for the whole parameter set instead
        # of one RPC per tensor; results land back on each input's own
        # sharding (distributed._result_device), so mesh-sharded params
        # keep their layout
        synced = host_broadcast_bucketed([p._data._data for p in todo],
                                         root=0)
        for p, v in zip(todo, synced):
            p._data._data = v
            self._dist_synced.add(p.name)

    def _check_and_rescale_grad(self, scale):
        self._optimizer.rescale_grad = scale

    def step(self, batch_size, ignore_stale_grad=False):
        """allreduce (via kvstore/collectives) + optimizer update
        (reference: ``Trainer.step``)."""
        t0 = time.perf_counter() if _telemetry._ENABLED else None
        try:
            with _obs.span("mx.trainer.step", batch=batch_size):
                self._step_impl(batch_size, ignore_stale_grad)
        finally:
            if t0 is not None:
                _telemetry.hooks.trainer_step(time.perf_counter() - t0,
                                              batch_size)

    def _step_impl(self, batch_size, ignore_stale_grad):
        if not self._kv_initialized:
            self._init_kvstore()
        self._optimizer.rescale_grad = self._scale / batch_size
        self._allreduce_grads()
        scaler = getattr(self, "_amp_loss_scaler", None)
        if scaler is not None:
            # fp16 AMP: fold 1/loss_scale into the update's rescale
            # (unless amp.unscale already divided the grads), and skip the
            # whole update on overflow.  The check runs on POST-allreduce
            # gradients: the cross-device sum itself can overflow
            # (reference: amp.init_trainer + LossScaler semantics).
            if not getattr(self, "_amp_unscaled", False):
                self._optimizer.rescale_grad /= scaler.loss_scale
            self._amp_unscaled = False
            grads = [p._data._grad for p in self._params
                     if p.grad_req != "null" and p._data is not None
                     and p._data._grad is not None]
            overflow = scaler.has_overflow(grads)
            scaler.update_scale(overflow)
            if overflow:
                return
        self._update(ignore_stale_grad)

    def allreduce_grads(self):
        if not self._kv_initialized:
            self._init_kvstore()
        self._allreduce_grads()

    def _allreduce_grads(self):
        if self._kvstore is None:
            return
        self._sync_initial_params()   # late deferred-init params
        live = [(i, p) for i, p in enumerate(self._params)
                if p.grad_req != "null" and p._data is not None
                and p._data._grad is not None]
        if getattr(self._kvstore, "_is_dist", False):
            # legacy eager path (the hot path is the compiled SPMD
            # TrainStep, which never reaches here): ONE bucketed
            # collective for the whole gradient set, not one per tensor
            self._kvstore.pushpull_bucket(
                [i for i, _ in live], [p._data._grad for _, p in live],
                [p._data._grad for _, p in live])
            return
        for i, p in live:
            self._kvstore.pushpull(i, p._data._grad, out=p._data._grad)

    def update(self, batch_size, ignore_stale_grad=False):
        if not self._kv_initialized:
            self._init_kvstore()
        self._optimizer.rescale_grad = self._scale / batch_size
        self._update(ignore_stale_grad)

    def _update(self, ignore_stale_grad=False):
        updatable = []
        for i, p in enumerate(self._params):
            if p.grad_req == "null" or p._data is None:
                continue
            if p._data._grad is None:
                if ignore_stale_grad:
                    continue
                raise MXNetError("parameter %s has no gradient; run "
                                 "backward first" % p.name)
            updatable.append((i, p))
        if self._try_fused_update(updatable):
            return
        for i, p in updatable:
            self._updater(i, p._data._grad, p._data)

    def _try_fused_update(self, updatable):
        """Group plain-SGD updates into ``multi_sgd(_mom)_update`` calls so
        an N-layer model costs O(N / aggregate_num) dispatches instead of
        O(N) (reference: ``optimizer_op.cc :: multi_sgd_update`` +
        ``MXNET_OPTIMIZER_AGGREGATION_SIZE``)."""
        import os
        from .. import ndarray as nd
        o = self._optimizer
        if type(o) is not opt.SGD or o.multi_precision or len(updatable) < 2:
            return False
        agg = int(os.environ.get("MXNET_OPTIMIZER_AGGREGATION_SIZE", 60))
        if agg < 2:
            return False
        upd = self._updater
        clip = o.clip_gradient if o.clip_gradient is not None else -1.0
        for s in range(0, len(updatable), agg):
            chunk = updatable[s:s + agg]
            lrs, wds = [], []
            for i, p in chunk:
                o._update_count(i)
                lrs.append(o._get_lr(i))
                wds.append(o._get_wd(i))
            n = len(chunk)
            if o.momentum != 0.0:
                for i, p in chunk:
                    if i not in upd.states:
                        upd.states[i] = \
                            o.create_state_multi_precision(i, p._data)
                data = []
                for i, p in chunk:
                    data += [p._data, p._data._grad, upd.states[i]]
                outs = nd.multi_sgd_mom_update(
                    *data, lrs=tuple(lrs), wds=tuple(wds),
                    momentum=o.momentum, rescale_grad=o.rescale_grad,
                    clip_gradient=clip, num_weights=n)
                for k, (i, p) in enumerate(chunk):
                    p._data._data = outs[k]._data
                    upd.states[i]._data = outs[n + k]._data
            else:
                data = []
                for i, p in chunk:
                    data += [p._data, p._data._grad]
                outs = nd.multi_sgd_update(
                    *data, lrs=tuple(lrs), wds=tuple(wds),
                    rescale_grad=o.rescale_grad, clip_gradient=clip,
                    num_weights=n)
                for k, (i, p) in enumerate(chunk):
                    p._data._data = outs[k]._data
        return True

    def get_states(self):
        """Optimizer state as an opaque bytes blob (what
        ``CheckpointManager`` stores for the ``trainer`` item)."""
        return self._updater.get_states(dump_optimizer=False)

    def set_states(self, states):
        self._updater.set_states(states)

    def save_states(self, fname):
        """Reference: ``Trainer.save_states`` -- optimizer state blob.
        Committed atomically (tmp+fsync+rename via mx.checkpoint): a
        SIGKILL mid-write can no longer leave a truncated .states file
        that loads garbage."""
        from ..checkpoint.core import atomic_write_bytes
        atomic_write_bytes(fname, self.get_states())

    def load_states(self, fname):
        with open(fname, "rb") as f:
            self.set_states(f.read())
