#!/usr/bin/env python
"""Multi-process data-parallel training with a dist_sync kvstore
(reference: ``example/image-classification/train_mnist.py`` run under
``tools/launch.py`` with ``--kv-store dist_sync``).

Each worker trains on its own shard of the data; gradients allreduce
across processes through the kvstore before every update, and rank 0's
initial weights are broadcast so all ranks train the same model.

Run (2 workers on one host):

    python tools/launch.py -n 2 python examples/dist_sync_train.py

Workers print per-epoch loss; after training every rank holds
byte-identical parameters (asserted).
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.abspath(__file__)), ".."))

import numpy as np                          # noqa: E402

import mxnet_tpu as mx                      # noqa: E402
from mxnet_tpu import autograd, gluon       # noqa: E402


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--epochs", type=int, default=3)
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--lr", type=float, default=0.05)
    p.add_argument("--samples", type=int, default=256)
    args = p.parse_args()

    mx.distributed_init()
    kv = mx.kv.create("dist_sync")
    rank, nworker = kv.rank, kv.num_workers

    # synthetic regression task; the DATA is sharded by rank
    # (num_parts/part_index semantics), the TARGET FUNCTION is shared
    rng = np.random.RandomState(0)
    w_true = rng.randn(16, 1).astype(np.float32)
    xs = rng.randn(args.samples, 16).astype(np.float32)
    ys = xs @ w_true
    shard_x = xs[rank::nworker]
    shard_y = ys[rank::nworker]
    # every rank must run the SAME number of steps: trainer.step is a
    # collective, so uneven shards would desequence the allreduces --
    # truncate to the minimum shard length
    common = len(xs) // nworker
    shard_x, shard_y = shard_x[:common], shard_y[:common]

    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(32, activation="relu"), gluon.nn.Dense(1))
    net.initialize(ctx=mx.cpu())
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": args.lr},
                            kvstore="dist_sync")
    loss_fn = gluon.loss.L2Loss()

    n = len(shard_x)
    for epoch in range(args.epochs):
        total, nbatch = 0.0, 0
        for s in range(0, n, args.batch_size):
            x = mx.nd.array(shard_x[s:s + args.batch_size])
            y = mx.nd.array(shard_y[s:s + args.batch_size])
            with autograd.record():
                loss = loss_fn(net(x), y).mean()
            loss.backward()
            trainer.step(1)
            total += float(loss.asnumpy())
            nbatch += 1
        print("[rank %d] epoch %d loss %.4f"
              % (rank, epoch, total / max(1, nbatch)), flush=True)

    # every rank must hold identical weights (allreduced training)
    from mxnet_tpu.distributed import host_allreduce
    for name, param in sorted(net.collect_params().items()):
        local = np.float64(param.data().asnumpy())
        summed = np.asarray(host_allreduce(local))
        np.testing.assert_allclose(summed, nworker * local, rtol=1e-6,
                                   err_msg=name)
    kv.barrier()
    print("[rank %d] TRAINED OK (replicated weights verified)" % rank,
          flush=True)


if __name__ == "__main__":
    main()
