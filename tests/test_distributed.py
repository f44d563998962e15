"""Multi-process distributed tests: REAL 2-process runs through
tools/launch.py + jax.distributed (reference: the nightly dist_sync
kvstore tests run via dmlc launcher)."""
import os
import subprocess
import sys

import pytest

import mxnet_tpu as mx

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_WORKER = r"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
import numpy as np
import mxnet_tpu as mx

assert mx.distributed_init() is True
from mxnet_tpu.distributed import world
# the COORDINATION world spans both workers (the backend itself may
# stay single-process on CPU jaxlib without gloo -- host collectives
# ride the coordination service instead)
assert world()[0] == 2

# dist kvstore: each worker pushes rank+1; allreduce sums to 3
kv = mx.kv.create("dist_sync")
assert kv.num_workers == 2
kv.init("w", mx.nd.zeros((4,)))
g = mx.nd.ones((4,)) * (kv.rank + 1)
out = mx.nd.zeros((4,))
kv.pushpull("w", g, out=out)
np.testing.assert_allclose(out.asnumpy(), np.full(4, 3.0))

# horovod-style API over the same world
from mxnet_tpu import horovod as hvd
hvd.init()
assert hvd.size() == 2
s = hvd.allreduce(mx.nd.ones((3,)) * (hvd.rank() + 1), average=False)
np.testing.assert_allclose(s.asnumpy(), np.full(3, 3.0))
m = hvd.allreduce(mx.nd.ones((3,)) * (hvd.rank() + 1), average=True)
np.testing.assert_allclose(m.asnumpy(), np.full(3, 1.5))

# broadcast: every worker ends with root's weights
w = mx.nd.ones((2, 2)) * (hvd.rank() + 7)
class _P:
    def data(self):
        return w
hvd.broadcast_parameters([("w", _P())], root_rank=0)
np.testing.assert_allclose(w.asnumpy(), np.full((2, 2), 7.0))

kv.barrier()
print("WORKER_OK rank=%d" % kv.rank)
"""


_DEEP_WORKER = r"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu.ndarray import sparse as sp

assert mx.distributed_init() is True
N = 3

# --- dist_async: server-side optimizer, replicated updates ----------
# (async = async DISPATCH in this design: same converged weights as
# dist_sync, no staleness; see kvstore.py module docstring)
kv = mx.kv.create("dist_async")
assert kv.num_workers == N
rank = kv.rank
kv.init("w", mx.nd.zeros((4,)))
kv.set_optimizer(mx.optimizer.SGD(learning_rate=0.1))
expected = np.zeros(4, np.float32)
for it in range(2):
    g = mx.nd.ones((4,)) * (rank + 1)
    kv.push("w", g)                       # allreduce-sum: 1+2+3 = 6
    expected -= 0.1 * 6.0
out = mx.nd.zeros((4,))
kv.pull("w", out=out)
np.testing.assert_allclose(out.asnumpy(), expected, rtol=1e-5)

# --- bigarray: a ~2 MB value through the dist pushpull path ----------
# (reference shards big arrays across servers at BIGARRAY_BOUND; the
# serverless allreduce has no shard split, but the transport must
# carry server-scale values correctly)
big = np.arange(512 * 1024, dtype=np.float32) / 1e6
bout = mx.nd.zeros((512 * 1024,))
kv2 = mx.kv.create("dist_sync")
kv2.init("big", mx.nd.zeros((512 * 1024,)))
kv2.pushpull("big", mx.nd.array(big), out=bout)
np.testing.assert_allclose(bout.asnumpy(), big * N, rtol=1e-6)

# --- row_sparse over dist: row-union merge, then dist reduce ---------
kv3 = mx.kv.create("dist_sync")
kv3.init("emb", mx.nd.zeros((6, 2)))
rows = np.array([rank, rank + 1], np.int64)
vals = np.full((2, 2), float(rank + 1), np.float32)
g = sp.RowSparseNDArray(vals, rows, (6, 2))
rout = mx.nd.zeros((6, 2))
kv3.pushpull("emb", g, out=rout)
dense = np.zeros((6, 2), np.float32)
for r in range(N):
    dense[r] += r + 1
    dense[r + 1] += r + 1
np.testing.assert_allclose(rout.asnumpy(), dense, rtol=1e-6)

# row_sparse_pull moves only the requested rows of the stored table
kv3.set_optimizer(mx.optimizer.SGD(learning_rate=1.0))
kv3.push("emb", g)                        # emb <- -1.0 * dense
picked = kv3.row_sparse_pull("emb", row_ids=mx.nd.array([1, 2]))
assert isinstance(picked, sp.RowSparseNDArray)
np.testing.assert_allclose(np.asarray(picked.indices), [1, 2])
np.testing.assert_allclose(np.asarray(picked.data), -dense[1:3],
                           rtol=1e-6)

# --- 2-bit compression with error feedback over the dist path --------
kv4 = mx.kv.create("dist_sync")
kv4.set_gradient_compression({"type": "2bit", "threshold": 0.5})
kv4.init("c", mx.nd.zeros((3,)))
cout = mx.nd.zeros((3,))
# round 1: |0.3| < threshold -> every worker sends 0, residual keeps 0.3
kv4.pushpull("c", mx.nd.ones((3,)) * 0.3, out=cout)
np.testing.assert_allclose(cout.asnumpy(), np.zeros(3), atol=1e-7)
# round 2: residual 0.3 + 0.3 = 0.6 >= threshold -> each sends 0.5
kv4.pushpull("c", mx.nd.ones((3,)) * 0.3, out=cout)
np.testing.assert_allclose(cout.asnumpy(), np.full(3, 0.5 * N),
                           rtol=1e-6)

kv.barrier()
print("DEEP_WORKER_OK rank=%d" % rank)
"""


_TRAINER_WORKER = r"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon

assert mx.distributed_init() is True
from mxnet_tpu.distributed import world
nproc, rank = world()
assert nproc == 2

# the standard distributed UX: gluon Trainer over a dist_sync kvstore,
# each rank feeding DIFFERENT data; gradients allreduce before the
# update so every rank must end with IDENTICAL weights
net = gluon.nn.HybridSequential()
net.add(gluon.nn.Dense(8, activation="relu"), gluon.nn.Dense(1))
net.initialize(ctx=mx.cpu())
net.hybridize()
tr = gluon.Trainer(net.collect_params(), "sgd",
                   {"learning_rate": 0.05}, kvstore="dist_sync")
loss_fn = gluon.loss.L2Loss()
rng = np.random.RandomState(100 + rank)      # per-rank data
w = np.random.RandomState(0).randn(5, 1).astype(np.float32)  # shared
xn = rng.randn(32, 5).astype(np.float32)
x = mx.nd.array(xn)
y = mx.nd.array(xn @ w)
first = last = None
for i in range(40):
    with autograd.record():
        l = loss_fn(net(x), y).mean()
    l.backward()
    tr.step(1)
    v = float(l.asnumpy())
    first = v if first is None else first
    last = v
assert last < first / 2, (first, last)

# weights identical across ranks: hash-reduce must equal 2x the local
from mxnet_tpu.distributed import host_allreduce
for name, p in sorted(net.collect_params().items()):
    local = np.asarray(p.data().asnumpy(), np.float64)
    summed = np.asarray(host_allreduce(local))
    np.testing.assert_allclose(summed, 2.0 * local, rtol=1e-6,
                               err_msg=name)

# --- legacy Module path: fit-style loop with kvstore='dist_sync' -----
data = mx.sym.Variable("data")
fc = mx.sym.FullyConnected(data, num_hidden=8, name="fc1")
act = mx.sym.Activation(fc, act_type="relu", name="relu1")
out = mx.sym.FullyConnected(act, num_hidden=4, name="fc2")
smx = mx.sym.SoftmaxOutput(out, name="softmax")
mod = mx.mod.Module(smx, context=mx.cpu())
mod.bind(data_shapes=[("data", (16, 6))],
         label_shapes=[("softmax_label", (16,))])
mod.init_params(initializer=mx.init.Xavier())
mod.init_optimizer(kvstore="dist_sync", optimizer="sgd",
                   optimizer_params={"learning_rate": 0.1})
mrng = np.random.RandomState(300 + rank)      # per-rank data
for i in range(6):
    batch = mx.io.DataBatch(
        data=[mx.nd.array(mrng.randn(16, 6).astype(np.float32))],
        label=[mx.nd.array(mrng.randint(0, 4, 16).astype(np.float32))])
    mod.forward(batch, is_train=True)
    mod.backward()
    mod.update()
args, _aux = mod.get_params()
for name in sorted(args):
    local = np.asarray(args[name].asnumpy(), np.float64)
    summed = np.asarray(host_allreduce(local))
    np.testing.assert_allclose(summed, 2.0 * local, rtol=1e-6,
                               err_msg="module:" + name)

print("TRAINER_WORKER_OK rank=%d loss %.4f -> %.4f" % (rank, first, last))
"""


_GLOO_WORKER = r"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
import numpy as np
import mxnet_tpu as mx

assert mx.distributed_init() is True
from mxnet_tpu import distributed as dist

# THE POD BRANCH, for real (ISSUE 7 satellite): with
# gloo CPU collectives wired by distributed_init, the BACKEND world is
# multi-process -- jax.process_count() matches the launcher world --
# so host_allreduce/host_broadcast take the process_allgather /
# broadcast_one_to_all path a TPU pod takes, NOT the O(N*P)
# coordination-service KV fallback.
assert jax.process_count() == 2, \
    "backend world is %d, not 2: the gloo collectives did not come up" \
    % jax.process_count()
nproc, rank = dist.world()
assert nproc == 2

out = dist.host_allreduce(np.ones((4,), np.float32) * (rank + 1))
np.testing.assert_allclose(np.asarray(out), np.full(4, 3.0))
mean = dist.host_allreduce(np.ones((2,), np.float32) * (rank + 1),
                           average=True)
np.testing.assert_allclose(np.asarray(mean), np.full(2, 1.5))
bc = dist.host_broadcast(np.full((3,), float(rank), np.float32))
np.testing.assert_allclose(np.asarray(bc), np.zeros(3))

# proof the fallback never ran: its one-shot warning latch is untouched
assert dist._KV_FALLBACK_WARNED[0] is False, \
    "host collectives fell back to the coordination-service KV path"
print("GLOO_WORKER_OK rank=%d" % rank)
"""


_SPMD_WORKER = r"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["MXNET_TPU_SHARD_CHECK"] = "1"     # arm executable capture
os.environ["MXNET_TPU_TELEMETRY"] = "1"
import jax
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import gluon, telemetry
from mxnet_tpu import distributed as dist
from mxnet_tpu.analysis import sharding
from mxnet_tpu.parallel import TrainStep, global_mesh

# THE TENTPOLE (ISSUE 9): multi-host data-parallel training is ONE
# jit-compiled SPMD program over the global mesh -- gradients
# allreduced IN-GRAPH by GSPMD, kvstore a veneer whose push/pull move
# zero host bytes on the hot path.
assert mx.distributed_init() is True
assert jax.process_count() == 2, \
    "backend world is %d, not 2: gloo collectives did not come up" \
    % jax.process_count()
nproc, rank = dist.world()
assert nproc == 2

mesh = global_mesh()
assert mesh.shape["dp"] == 2 and not mesh.devices.flatten()[0] is None

net = gluon.nn.HybridSequential()
net.add(gluon.nn.Dense(16, activation="relu"), gluon.nn.Dense(4))
net.initialize(ctx=mx.cpu())
net.hybridize()
tr = gluon.Trainer(net.collect_params(), "sgd",
                   {"learning_rate": 0.05, "momentum": 0.9},
                   kvstore="dist_sync")
step = TrainStep(net, gluon.loss.L2Loss(), tr)  # mesh=None -> global mesh
assert step._mesh is mesh

rng = np.random.RandomState(100 + rank)          # per-rank LOCAL batch
w = np.random.RandomState(0).randn(8, 4).astype(np.float32)
x = rng.randn(8, 8).astype(np.float32)
y = (x @ w).astype(np.float32)

l0 = float(np.asarray(step(x, y)._data))         # compile + init sync
telemetry.reset("kvstore.")
# steady state under the transfer guard: host batches land through the
# EXPLICIT staging primitives, nothing implicit crosses host<->device
with sharding.transfer_guard("disallow"):
    for _ in range(10):
        loss = step(x, y)
    last = float(np.asarray(loss._data))
assert last < l0, (l0, last)

# the staged batch is the GLOBAL (nproc x local) batch, dp-sharded
assert step._last_call[1][2].shape[0] == 16, step._last_call[1][2].shape

# hot path moved ZERO host bytes through the kvstore...
for verb in ("push", "pull", "pushpull", "bytes"):
    assert telemetry.counter("kvstore." + verb).value == 0, verb
# ...and never touched the coordination-service KV fallback
assert dist._KV_FALLBACK_WARNED[0] is False

# the compiled program's collective contract carries the in-graph
# gradient all-reduce: every gradient byte (XLA may combine the four
# gradients and the mean loss into one op, so the COUNT is its choice)
cc = sharding.collective_contract()
kinds = cc["executables"]["train_step:HybridSequential"]
grad_bytes = sum(4 * p.data().size for p in net.collect_params().values())
assert kinds.get("all-reduce", {}).get("bytes", 0) >= grad_bytes, kinds

# post-update weights identical on every rank
for name, p in sorted(net.collect_params().items()):
    local = np.asarray(p.data()._data).astype(np.float64)
    summed = np.asarray(dist.host_allreduce(local))
    np.testing.assert_allclose(summed, 2.0 * local, rtol=1e-6,
                               err_msg=name)
print("SPMD_WORKER_OK rank=%d allreduce=%d" % (rank,
      kinds["all-reduce"]["count"]))
"""


_SPMD4_WORKER = r"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["MXNET_TPU_SHARD_CHECK"] = "1"
import jax
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import gluon
from mxnet_tpu import distributed as dist
from mxnet_tpu.analysis import sharding
from mxnet_tpu.checkpoint import CheckpointManager
from mxnet_tpu.parallel import TrainStep, global_mesh
from jax.sharding import NamedSharding, PartitionSpec as P

CKDIR = os.environ["MXNET_TPU_TEST_CKDIR"]
assert mx.distributed_init() is True
assert jax.process_count() == 4, jax.process_count()
nproc, rank = dist.world()
assert nproc == 4

mesh = global_mesh()
assert mesh.shape["dp"] == 4

net = gluon.nn.HybridSequential()
net.add(gluon.nn.Dense(8, activation="relu"), gluon.nn.Dense(2))
net.initialize(ctx=mx.cpu())
net.hybridize()
tr = gluon.Trainer(net.collect_params(), "sgd",
                   {"learning_rate": 0.1}, kvstore="dist_sync")
step = TrainStep(net, gluon.loss.L2Loss(), tr)
rng = np.random.RandomState(10 + rank)
x = rng.randn(4, 6).astype(np.float32)           # per-rank local batch
y = rng.randn(4, 2).astype(np.float32)
for _ in range(3):
    loss = step(x, y)
float(np.asarray(loss._data))

# the 4-way program carries the same in-graph gradient all-reduce
# (bytes, not op count: XLA combines the gradients into one op)
cc = sharding.collective_contract()
kinds = cc["executables"]["train_step:HybridSequential"]
grad_bytes = sum(4 * p.data().size for p in net.collect_params().values())
assert kinds.get("all-reduce", {}).get("bytes", 0) >= grad_bytes, kinds

# PR-3 sharded checkpoint over the GLOBAL mesh: every rank writes only
# its replica_id==0 addressable shards, rank 0 commits; restore
# reassembles and reshards onto the CURRENT global mesh
params = {p.name: p.data() for p in net.collect_params().values()}
want = {k: np.asarray(v._data) for k, v in params.items()}
mgr = CheckpointManager(CKDIR, sharded=True)
mgr.save(1, {"params": params}, metadata={"world": nproc})
dist.barrier("ckpt_saved")
assert mgr.latest_step() == 1

sh = NamedSharding(mesh, P())
ckpt = mgr.restore(sharding=lambda item, key, shape: sh)
for k, v in sorted(ckpt.items["params"].items()):
    arr = v._data
    assert arr.sharding.is_equivalent_to(sh, arr.ndim), (k, arr.sharding)
    assert len(arr.sharding.device_set) == 4, k
    np.testing.assert_allclose(np.asarray(arr), want[k], rtol=1e-6,
                               err_msg=k)
dist.barrier("ckpt_restored")
print("SPMD4_WORKER_OK rank=%d" % rank)
"""


def _scrub_device_count(flags):
    import re
    return re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                  flags).strip()


def _launch(script_path, n, env):
    # coordinator startup can race the free-port probe on a busy
    # machine; retry once before calling it a failure
    out = None
    for attempt in range(2):
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "launch.py"),
             "-n", str(n), sys.executable, "-u", str(script_path)],
            capture_output=True, text=True, timeout=300, env=env)
        if out.returncode == 0:
            break
    return out


@pytest.mark.skipif(os.environ.get("MXNET_TPU_SKIP_DIST") == "1",
                    reason="dist tests disabled")
def test_two_process_dist_kvstore(tmp_path):
    script = tmp_path / "worker.py"
    script.write_text(_WORKER)
    env = {**os.environ, "PYTHONPATH": REPO + os.pathsep +
           os.environ.get("PYTHONPATH", "")}
    out = _launch(script, 2, env)
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-2000:])
    assert out.stdout.count("WORKER_OK") == 2


@pytest.mark.skipif(os.environ.get("MXNET_TPU_SKIP_DIST") == "1",
                    reason="dist tests disabled")
def test_three_process_dist_kvstore_deep(tmp_path):
    """3-process run covering dist_async updates, a ~2 MB bigarray
    value, row_sparse push + row_sparse_pull, and 2-bit compression
    with error feedback -- all over the real launcher + jax.distributed
    (reference: ``tests/nightly/dist_sync_kvstore.py``)."""
    script = tmp_path / "deep_worker.py"
    script.write_text(_DEEP_WORKER)
    env = {**os.environ, "PYTHONPATH": REPO + os.pathsep +
           os.environ.get("PYTHONPATH", "")}
    out = _launch(script, 3, env)
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-2000:])
    assert out.stdout.count("DEEP_WORKER_OK") == 3


@pytest.mark.skipif(os.environ.get("MXNET_TPU_SKIP_DIST") == "1",
                    reason="dist tests disabled")
def test_two_process_gluon_trainer_dist_sync(tmp_path):
    """End-to-end distributed TRAINING through the standard UX:
    gluon.Trainer(kvstore='dist_sync'), per-rank data, replicated
    post-update weights (reference: the dist kvstore training loop in
    example/image-classification/common/fit.py)."""
    script = tmp_path / "trainer_worker.py"
    script.write_text(_TRAINER_WORKER)
    env = {**os.environ, "PYTHONPATH": REPO + os.pathsep +
           os.environ.get("PYTHONPATH", "")}
    out = _launch(script, 2, env)
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-2000:])
    assert out.stdout.count("TRAINER_WORKER_OK") == 2


@pytest.mark.skipif(os.environ.get("MXNET_TPU_SKIP_DIST") == "1",
                    reason="dist tests disabled")
def test_two_process_backend_collectives_gloo(tmp_path):
    """The real `process_allgather` branch of distributed.host_allreduce
    runs in-suite: gloo CPU collectives make the backend world
    multi-process (jax.process_count() == launcher world), and the
    KV-fallback warning latch proves the coordinator-funnel path was
    never taken (ISSUE 7 satellite; it used to be dead code)."""
    script = tmp_path / "gloo_worker.py"
    script.write_text(_GLOO_WORKER)
    env = {**os.environ, "PYTHONPATH": REPO + os.pathsep +
           os.environ.get("PYTHONPATH", "")}
    out = _launch(script, 2, env)
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-2000:])
    assert out.stdout.count("GLOO_WORKER_OK") == 2


@pytest.mark.skipif(os.environ.get("MXNET_TPU_SKIP_DIST") == "1",
                    reason="dist tests disabled")
def test_two_process_spmd_train_step_gloo(tmp_path):
    """ISSUE 9 tentpole: the dist train step is ONE compiled SPMD
    program over the global mesh -- its collective contract lists the
    in-graph gradient all-reduce, kv push/pull byte counters stay at
    ZERO across steps (the kvstore is a veneer; the hot path moves no
    host bytes), the KV-fallback warn latch stays cold, and the
    steady-state loop runs under transfer_guard('disallow')."""
    script = tmp_path / "spmd_worker.py"
    script.write_text(_SPMD_WORKER)
    env = {**os.environ, "PYTHONPATH": REPO + os.pathsep +
           os.environ.get("PYTHONPATH", ""),
           # one device per rank: the suite's 8-virtual-device flag
           # would make the global mesh 2x8 instead of 2
           "XLA_FLAGS": _scrub_device_count(os.environ.get("XLA_FLAGS",
                                                           ""))}
    out = _launch(script, 2, env)
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-2000:])
    assert out.stdout.count("SPMD_WORKER_OK") == 2


@pytest.mark.skipif(os.environ.get("MXNET_TPU_SKIP_DIST") == "1",
                    reason="dist tests disabled")
def test_four_process_spmd_checkpoint_reshard_gloo(tmp_path):
    """The pod branch at 4 ranks: same one-program contract, plus PR-3
    sharded checkpoint save/restore resharding across the new global
    mesh (each rank writes its replica_id==0 shards, rank 0 commits,
    restore reassembles onto the CURRENT 4-way mesh)."""
    script = tmp_path / "spmd4_worker.py"
    script.write_text(_SPMD4_WORKER)
    env = {**os.environ, "PYTHONPATH": REPO + os.pathsep +
           os.environ.get("PYTHONPATH", ""),
           "MXNET_TPU_TEST_CKDIR": str(tmp_path / "ckpts"),
           "XLA_FLAGS": _scrub_device_count(os.environ.get("XLA_FLAGS",
                                                           ""))}
    out = _launch(script, 4, env)
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-2000:])
    assert out.stdout.count("SPMD4_WORKER_OK") == 4


def test_horovod_single_process_api():
    from mxnet_tpu import horovod as hvd
    hvd.init()
    assert hvd.size() >= 1 and hvd.rank() >= 0
    x = hvd.allreduce(mx.nd.ones((2,)) * 4, average=True)
    assert x.asnumpy().tolist() == [4.0, 4.0]
    # DistributedTrainer degenerates to Trainer when single-process
    from mxnet_tpu import gluon
    net = gluon.nn.Dense(2)
    net.initialize()
    net(mx.nd.ones((1, 3)))
    tr = hvd.DistributedTrainer(net.collect_params(), "sgd",
                                {"learning_rate": 0.1})
    assert tr.learning_rate == 0.1
