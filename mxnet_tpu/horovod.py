"""Horovod-style data-parallel API (reference: the ``mxnet+horovod``
integration -- ``hvd.init/rank/size/DistributedTrainer/broadcast_parameters``
pattern from the reference's large-batch examples).

TPU-native mapping: there is no MPI ring to manage -- processes join the
``jax.distributed`` world (one call), and the reduction primitives are
XLA collectives.  The API shape is kept so reference training scripts
port by changing the import.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from .base import MXNetError
from .distributed import distributed_init
from .gluon.trainer import Trainer
from .ndarray import NDArray

_initialized = False


def init():
    """``hvd.init()``: join the multi-process world (env-driven; no-op
    when single-process)."""
    global _initialized
    distributed_init()
    _initialized = True


def rank():
    from .distributed import world
    return world()[1]


def size():
    from .distributed import world
    return world()[0]


def local_rank():
    return 0  # one process per host-slice in the jax runtime model


def allreduce(tensor, average=True, name=None):
    """Sum (or mean) a host-local array across workers."""
    from .distributed import host_allreduce, world
    x = tensor._data if isinstance(tensor, NDArray) else jnp.asarray(tensor)
    if world()[0] > 1:
        x = host_allreduce(x, average=average)
    return NDArray(x)


def grouped_allreduce(tensors, average=True, name=None):
    """``hvd.grouped_allreduce``: reduce a LIST of tensors in ONE
    flattened collective per dtype (``host_allreduce_bucketed``)
    instead of one RPC each -- the bucketed form metric/overflow
    reductions should use."""
    from .distributed import host_allreduce_bucketed, world
    vals = [t._data if isinstance(t, NDArray) else jnp.asarray(t)
            for t in tensors]
    if world()[0] > 1:
        vals = host_allreduce_bucketed(vals, average=average)
    return [NDArray(v) for v in vals]


def broadcast_parameters(params, root_rank=0):
    """Make every worker start from root's weights (reference:
    ``hvd.broadcast_parameters``) -- ONE bucketed collective for the
    whole parameter set, not one RPC per tensor."""
    from .distributed import host_broadcast_bucketed, world
    if world()[0] == 1:
        return
    items = list(params.items() if hasattr(params, "items") else params)
    # pass the device arrays through: the bucketed broadcast places
    # results back on each input's device/sharding (an np.asarray here
    # would land results on the DEFAULT device, which need not be the
    # parameter's)
    arrs = [(p.data() if hasattr(p, "data") else p) for _name, p in items]
    out = host_broadcast_bucketed([a._data for a in arrs], root=root_rank)
    for a, v in zip(arrs, out):
        a._data = v


class DistributedTrainer(Trainer):
    """``hvd.DistributedTrainer``: a Gluon Trainer whose gradients
    average across the process world before each update."""

    def __init__(self, params, optimizer, optimizer_params=None, **kwargs):
        super().__init__(params, optimizer, optimizer_params,
                         kvstore=None, **kwargs)
        from .distributed import world
        if not _initialized and world()[0] > 1:
            raise MXNetError("call horovod.init() first")

    def step(self, batch_size, ignore_stale_grad=False):
        from .distributed import world
        if world()[0] > 1:
            grads = [p.grad() for p in self._params
                     if p.grad_req != "null" and p._data is not None
                     and p._data._grad is not None]  # stale-grad guard
            reduced = grouped_allreduce(grads, average=True)
            for g, r in zip(grads, reduced):
                g._data = r._data
        super().step(batch_size, ignore_stale_grad)
