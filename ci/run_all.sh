#!/usr/bin/env bash
# Single CI entry point (reference: ci/docker/runtime_functions.sh --
# the one script that gates a change).  Stages:
#   lint  -> compile-level sanity over the whole package
#   suite -> full pytest run (8 virtual CPU devices, same as a PR gate)
#   examples -> the runnable examples smoke-tested via their test file
#   telemetry -> 3-step smoke train (fed through mx.dataio.DeviceFeed)
#                with the JSONL sink on, then the summarize CLI must
#                report non-empty step/compile/feed data
#   checkpoint -> save-every-step smoke train, simulated preemption
#                 (kill-mid-write corruption of the newest step),
#                 resume must fall back to the previous good step and
#                 the telemetry JSONL must record the restore event
#   tsan -> threaded smoke train + the threaded test files under
#           MXNET_TPU_TSAN=1 (lock-order sanitizer + deadlock watchdog
#           armed), including the injected-deadlock fixtures
#   profiling -> 3-step smoke train with cost accounting on; mxprof
#                report must show non-empty step + category sections
#                and mxprof diff of the run against itself must report
#                zero drift (the regression-attribution contract)
#   serving -> register a LeNet servable, fire concurrent requests
#              from threads; gates: mean batch occupancy > 1 (dynamic
#              batching is real), zero dropped responses after a
#              graceful drain, per-request numerics vs the direct
#              forward, and a non-empty `serving` section (ordered
#              p50<=p99 percentiles) from the summarize CLI
#   chaos -> the always-on loop under injected faults (docs/chaos.md,
#            fixed seed): the chaos test file, then a REAL
#            kill-mid-commit (subprocess dies with os._exit between the
#            staged data files and the manifest commit -> discovery
#            must cost one step, never the job, and the next manager
#            sweeps the orphaned staging dir), a torn-publish hot-swap
#            scenario (watcher must quarantine the corrupt step and
#            keep serving the previous verified one, zero dropped
#            requests), and a batcher flood (sheds counted, accepted
#            requests all complete, tail bounded by the queue depth)
#   chaos_dist -> distributed resilience gate (docs/chaos.md multi-host
#                 section, seed 0): a REAL 2-proc supervised run where
#                 rank 1 is chaos-KILLed between the "written" and
#                 "committed" barriers of a sharded publish -- the
#                 survivor must abort with a typed BarrierTimeout
#                 naming rank 1 within the bound, NO merged manifest
#                 may exist, the elastic supervisor must relaunch
#                 generation 1, and both ranks must resume parameters
#                 BIT-IDENTICAL to the last verified step; plus the
#                 restart-budget exhaustion path gated NOT_READY
#   spmd -> one-program multi-host gate (docs/distributed.md): a REAL
#           2-process gloo smoke train through tools/launch.py -- the
#           dist train step must be ONE compiled SPMD program whose
#           steady-state steps run under transfer_guard("disallow"),
#           kv push/pull byte counters must stay ZERO across steps
#           (kvstore is a veneer; gradients all-reduce in-graph), and
#           rank 0's collective contract must match the committed
#           ci/sharding_baseline.json (the gradient all-reduce is
#           blessed; anything else fails naming executable+kind)
#   perflint -> TPU performance linter gates (docs/perf_lint.md): the
#               full-tree static pass with all five perf rules armed
#               (layout-hostile-conv, pad-waste, python-loop-unroll,
#               scalar-recompile, eager-in-step-loop), then a LeNet
#               TrainStep + ResNet18-thumbnail forward smoke whose
#               compiled-HLO efficiency audit (transpose share,
#               unfused elementwise bytes, MXU pad waste, intensity)
#               must show zero drift against the committed
#               ci/perf_baseline.json (mxlint --perf-diff)
#   shardlint -> sharding sanitizer gates (docs/sharding.md): the
#                full-tree static pass (mesh axes, shard_map arity,
#                donation audit, implicit reshard), then a LeNet
#                TrainStep smoke over an 8-way dp mesh whose GSPMD
#                collectives must match the committed
#                ci/sharding_baseline.json exactly (an unblessed
#                all-gather fails naming the executable and kind),
#                with the steady-state steps run under
#                transfer_guard("disallow") and a seeded implicit
#                host transfer proven to raise
#   numlint -> numerics sanitizer gates (docs/numerics.md): the
#              full-tree static pass (five dtype-hazard rules armed),
#              then a LeNet TrainStep + bf16-ResNet18 TrainStep smoke
#              under MXNET_TPU_NUMERICS_CHECK=1 -- two clean sentinel
#              steps, then a chaos-seeded NaN at step 3 must raise
#              NonFiniteError naming a real parameter -- whose
#              compiled-HLO precision audit (half-accumulated dots,
#              convert storms, bf16 reductions) must show zero drift
#              against the committed ci/numerics_baseline.json
#              (mxlint --numerics-diff)
#   memlint -> memory-pressure sanitizer gates (docs/memory.md): the
#              full-tree static pass (five HBM-hazard rules armed:
#              device-ref-accumulation, unbounded-shape-cache,
#              host-materialize-large, retained-temp-across-step,
#              feed-depth-unbounded), then a LeNet TrainStep smoke
#              whose peak-HBM audit must show zero drift against the
#              committed ci/memory_baseline.json (mxlint
#              --memory-diff), a SEEDED +50% peak regression that must
#              exit 1, an hbm_plan anchor check (predicted == compiled
#              at both probe buckets), and the leak-sentinel gate
#              under MXNET_TPU_MEMORY_WATCH=1 (seed 0): clean windows
#              must never flag, chaos-pinned arrays must flag within
#              3 windows naming the pinned shape bucket
#   kernels -> Pallas kernel tier gates (docs/kernels.md): the
#              interpret-mode kernel tests (the registry's choice
#              table, flash op-level pallas path incl. the masked
#              backward, paged and latent paged decode attention),
#              then an explicit fallback proof (Pallas monkeypatched
#              away -> every choice lands on XLA, numerics intact)
#   obs -> observability ops plane (docs/observability.md): a traced
#          smoke train+serve run whose request spans must reconcile
#          with the serving.requests/batches counters and whose
#          dispatch+device_get span walls must equal the
#          serving.dispatch_time timer; a chaos KILL mid-commit
#          (seed 0) with the flight recorder installed -- the process
#          dies 137 and the blackbox dump's final events must name the
#          injected fault and the in-flight trace; a /healthz flip
#          gate -- READY while the watcher is good, NOT_READY after
#          the swap failure budget suspends it; and the goodput gate
#          -- a ContinuousTrainer fed through a DeviceFeed with a
#          chaos sleep injected on feed.produce must close windows
#          whose reconciliation (categories sum to wall within tol)
#          holds on EVERY window, read input-bound, and emit a
#          goodput.regression event NAMING input_wait
#   fleet -> fleet observability gate (docs/observability.md fleet
#            section, seed 0): a REAL 2-replica supervised serving
#            fleet discovered through MXNET_TPU_OBS_ENDPOINTS_DIR;
#            rank 1 is chaos-KILLed mid-flood (serving.dispatch) --
#            the FleetMonitor's replica_down alert must FIRE naming
#            rank 1 + generation 0, the supervisor relaunch must
#            RESOLVE it, every replica that drains reports zero
#            accepted-request drops, and the `mxtelemetry fleet` CLI
#            exit codes gate both ways (0 on the healthy relaunched
#            fleet, 1 once the endpoints are withdrawn)
#   bench -> bench.py import + dry entry (no device time burned)
#   wheel -> build a wheel, install into a clean venv, import + smoke
#
# Usage: ci/run_all.sh [stage...]   (default: all stages in order)
set -euo pipefail
cd "$(dirname "$0")/.."

stages=("$@")
[ ${#stages[@]} -eq 0 ] && stages=(lint suite examples telemetry checkpoint tsan profiling perflint shardlint numlint memlint kernels spmd serving serving_decode chaos chaos_dist obs fleet bench wheel)

log() { printf '\n== %s ==\n' "$1"; }

run_lint() {
    log "lint: byte-compile every source file"
    python -m compileall -q mxnet_tpu tools benchmark bench.py \
        __graft_entry__.py
    log "lint: incremental pass (changed files vs committed baseline)"
    # the pre-commit-speed path: only `git diff` files are linted and
    # findings recorded in the committed baseline stay suppressed, so
    # this stage stays fast as the rule count grows (docs/analysis.md)
    python -m mxnet_tpu.analysis --changed \
        --baseline ci/lint_baseline.json --json
    log "lint: mxnet_tpu.analysis full self-check (trace safety + concurrency + retrace audit)"
    # the authoritative gate, same pass developers run as `mxlint
    # --self` -- CI and the CLI cannot drift; exits non-zero on any
    # violation, --json keeps the record machine-readable
    python -m mxnet_tpu.analysis --self --json
}

run_suite() {
    log "suite: full pytest"
    python -m pytest tests/ -q
}

run_examples() {
    log "examples: smoke via tests/test_examples.py"
    python -m pytest tests/test_examples.py -q
}

run_telemetry() {
    log "telemetry: 3-step smoke train -> JSONL -> summarize gate"
    tjsonl=$(mktemp /tmp/mxtpu_telemetry_ci.XXXXXX.jsonl)
    JAX_PLATFORMS=cpu MXNET_TPU_TELEMETRY=1 \
        MXNET_TPU_TELEMETRY_JSONL="$tjsonl" python - <<'EOF'
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, telemetry

net = gluon.nn.Dense(4)
net.initialize()
net.hybridize()
trainer = gluon.Trainer(net.collect_params(), "sgd",
                        {"learning_rate": 0.1})
ds = gluon.data.ArrayDataset(
    mx.nd.array(np.random.rand(12, 8).astype(np.float32)),
    mx.nd.array(np.random.rand(12, 4).astype(np.float32)))
# the device-feed path (ISSUE 4): batches stage through
# mx.dataio.DeviceFeed, so the summarize gate below can assert a
# non-empty feed section alongside the host-loader instruments
loader = gluon.data.DataLoader(ds, batch_size=4, ctx=mx.cpu())
loss_fn = gluon.loss.L2Loss()
for x, y in loader:                     # 3 steps
    with autograd.record():
        loss = loss_fn(net(x), y)
    loss.backward()
    trainer.step(4)
loss.asnumpy()
telemetry.flush()
print("smoke train done:", telemetry.counter("trainer.steps").value,
      "steps")
EOF
    # the CLI must exit 0 and report non-empty step/compile sections
    python -m mxnet_tpu.telemetry summarize "$tjsonl" --json > "$tjsonl.agg"
    python - "$tjsonl.agg" <<'EOF'
import json, sys
agg = json.load(open(sys.argv[1]))
assert agg["records"] > 0, "empty telemetry log"
assert agg["steps"]["count"] >= 3, agg["steps"]
assert agg["compile"]["count"] > 0, agg["compile"]
assert agg["kvstore"]["bytes"] > 0, agg["kvstore"]
assert agg["data"]["batches"] >= 3, agg["data"]
assert agg["feed"]["batches"] >= 3, agg["feed"]
assert agg["feed"]["bytes_staged"] > 0, agg["feed"]
assert agg["feed"]["producer_busy_s"] is not None, agg["feed"]
print("telemetry gate ok: %d steps, %d compiles, %d kv bytes, "
      "%d fed batches"
      % (agg["steps"]["count"], agg["compile"]["count"],
         agg["kvstore"]["bytes"], agg["feed"]["batches"]))
EOF
    rm -f "$tjsonl" "$tjsonl.agg"
}

run_checkpoint() {
    log "checkpoint: train+save every step -> preempt -> verified resume"
    ckdir=$(mktemp -d /tmp/mxtpu_ckpt_ci.XXXXXX)
    # phase 1: 3 steps, a managed save per step, then a simulated
    # preemption: the newest step's params are truncated (the on-disk
    # state a SIGKILL mid-write leaves) and the process dies abruptly
    JAX_PLATFORMS=cpu MXNET_TPU_TELEMETRY=1 \
        MXNET_TPU_TELEMETRY_JSONL="$ckdir/run.jsonl" \
        python - "$ckdir" <<'EOF'
import os, sys
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon

ckdir = sys.argv[1]
mgr = mx.checkpoint.CheckpointManager(os.path.join(ckdir, "ckpts"))
net = gluon.nn.Dense(4)
net.initialize(); net.hybridize()
tr = gluon.Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1},
                   kvstore=None)
loss_fn = gluon.loss.L2Loss()
rng = np.random.RandomState(0)
x = mx.nd.array(rng.rand(4, 8).astype(np.float32))
y = mx.nd.array(rng.rand(4, 4).astype(np.float32))
for step in range(1, 4):                  # 3 steps, save EVERY step
    with autograd.record():
        loss = loss_fn(net(x), y)
    loss.backward()
    tr.step(4)
    mgr.save_training(step, net, tr, metadata={"step": step})
assert mgr.latest_step() == 3
# simulated preemption: SIGKILL lands mid-write of a 4th checkpoint --
# fake the torn on-disk state by truncating the newest step's params
with open(os.path.join(mgr.step_dir(3), "params.params"), "r+b") as f:
    f.truncate(8)
print("phase-1 trained 3 steps, tore step 3", flush=True)
os._exit(0)                               # abrupt exit: no atexit, no flush
EOF
    # phase 2: fresh process resumes; must fall back to step 2
    JAX_PLATFORMS=cpu MXNET_TPU_TELEMETRY=1 \
        MXNET_TPU_TELEMETRY_JSONL="$ckdir/run.jsonl" \
        python - "$ckdir" <<'EOF'
import os, sys, warnings
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, telemetry

ckdir = sys.argv[1]
mgr = mx.checkpoint.CheckpointManager(os.path.join(ckdir, "ckpts"))
net = gluon.nn.Dense(4)
net.initialize(); net.hybridize()
x = mx.nd.array(np.zeros((4, 8), np.float32))
net(x)
tr = gluon.Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1},
                   kvstore=None)
with warnings.catch_warnings():
    warnings.simplefilter("ignore", RuntimeWarning)   # the torn step 3
    ckpt = mgr.restore_training(net, tr)
assert ckpt is not None, "resume found no checkpoint"
assert ckpt.step == 2, "expected fallback to step 2, got %r" % ckpt.step
assert ckpt.metadata["step"] == 2
# step continuity: training resumes at the step after the checkpoint
y = mx.nd.array(np.zeros((4, 4), np.float32))
loss_fn = gluon.loss.L2Loss()
for step in range(ckpt.step + 1, ckpt.step + 3):
    with autograd.record():
        loss = loss_fn(net(x), y)
    loss.backward()
    tr.step(4)
    mgr.save_training(step, net, tr, metadata={"step": step})
assert mgr.latest_step() == 4
telemetry.flush()
print("phase-2 resumed at step %d, continued to %d"
      % (ckpt.step, mgr.latest_step()), flush=True)
EOF
    # gate: the shared JSONL must record the restore event
    python - "$ckdir/run.jsonl" <<'EOF'
import json, sys
actions = []
for line in open(sys.argv[1]):
    rec = json.loads(line)
    if rec.get("kind") == "event" and rec.get("name") == "checkpoint":
        actions.append((rec.get("payload") or {}).get("action"))
assert "restore" in actions, "no restore event in telemetry: %s" % actions
# phase 1's buffered lines died with os._exit (as they would under a
# real SIGKILL); phase 2's post-resume saves must be here
assert actions.count("save") >= 2, actions
print("checkpoint gate ok: %d saves, %d restores recorded"
      % (actions.count("save"), actions.count("restore")))
EOF
    rm -rf "$ckdir"
}

run_tsan() {
    log "tsan: threaded smoke train under the concurrency sanitizer"
    # same shape as the telemetry smoke train, but with the lock-order
    # sanitizer + deadlock watchdog armed: a silent A/B inversion or a
    # stuck producer raises here instead of hanging a real run
    JAX_PLATFORMS=cpu MXNET_TPU_TSAN=1 MXNET_TPU_TSAN_WATCHDOG_S=60 \
        MXNET_TPU_TELEMETRY=1 python - <<'EOF'
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, sync, telemetry

assert sync.tsan_enabled(), "MXNET_TPU_TSAN=1 did not arm the sanitizer"
seeded = sync.seed_static_order()
net = gluon.nn.Dense(4)
net.initialize()
net.hybridize()
trainer = gluon.Trainer(net.collect_params(), "sgd",
                        {"learning_rate": 0.1})
ds = gluon.data.ArrayDataset(
    mx.nd.array(np.random.rand(16, 8).astype(np.float32)),
    mx.nd.array(np.random.rand(16, 4).astype(np.float32)))
# threaded end to end: worker-pool decode + DeviceFeed staging
loader = gluon.data.DataLoader(ds, batch_size=4, num_workers=2,
                               ctx=mx.cpu())
loss_fn = gluon.loss.L2Loss()
for x, y in loader:                     # 4 steps
    with autograd.record():
        loss = loss_fn(net(x), y)
    loss.backward()
    trainer.step(4)
loss.asnumpy()
assert not sync.recorded_reports(), sync.recorded_reports()
print("tsan smoke train ok: %d steps, %d static edges seeded, "
      "order graph %r"
      % (telemetry.counter("trainer.steps").value, seeded,
         sync.order_graph()))
EOF
    log "tsan: threaded test files under MXNET_TPU_TSAN=1"
    # the tier-1 threaded suites must stay green with the sanitizer
    # armed, and tests/test_sync.py carries the injected-deadlock
    # fixture the watchdog must catch with a both-stacks report
    JAX_PLATFORMS=cpu MXNET_TPU_TSAN=1 MXNET_TPU_TSAN_WATCHDOG_S=60 \
        python -m pytest tests/test_sync.py tests/test_dataio.py \
        tests/test_checkpoint.py tests/test_telemetry.py \
        tests/test_serving.py tests/test_chaos.py tests/test_obs.py \
        tests/test_resilience.py tests/test_numerics.py \
        tests/test_memory.py tests/test_fleet.py \
        -q -m 'not slow'
    log "tsan: gloo multi-process tests under MXNET_TPU_TSAN=1"
    # the launched workers inherit the env, so the 2-/4-proc gloo SPMD
    # paths (ISSUE 9) run with the lock sanitizer armed end to end
    JAX_PLATFORMS=cpu MXNET_TPU_TSAN=1 MXNET_TPU_TSAN_WATCHDOG_S=120 \
        python -m pytest tests/test_distributed.py -q -k "gloo or spmd"
}

run_profiling() {
    log "profiling: smoke train with cost accounting -> mxprof gates"
    pdir=$(mktemp -d /tmp/mxtpu_prof_ci.XXXXXX)
    JAX_PLATFORMS=cpu MXNET_TPU_PROFILING=1 MXNET_TPU_TELEMETRY=1 \
        MXNET_TPU_PROFILING_DIR="$pdir" python - <<'EOF'
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import gluon, profiling
from mxnet_tpu.parallel import TrainStep

assert profiling.enabled(), "MXNET_TPU_PROFILING=1 did not arm capture"
net = gluon.nn.Dense(4)
net.initialize(); net.hybridize()
tr = gluon.Trainer(net.collect_params(), "lars",
                   {"learning_rate": 0.1}, kvstore=None)
step = TrainStep(net, gluon.loss.L2Loss(), tr, mesh=None)
rng = np.random.RandomState(0)
x = mx.nd.array(rng.rand(8, 16).astype(np.float32))
y = mx.nd.array(rng.rand(8, 4).astype(np.float32))
for _ in range(3):                       # 3 steps (trace-safe LARS)
    loss = step(x, y)
loss.asnumpy()
path = profiling.save_reports()
print("profiling smoke train done ->", path)
EOF
    # gate 1: the report must carry non-empty step + category sections
    python -m mxnet_tpu.profiling report --dir "$pdir" --json > "$pdir/agg.json"
    python - "$pdir/agg.json" <<'EOF'
import json, sys
agg = json.load(open(sys.argv[1]))
assert agg["executables"], "no executables in cost report"
assert agg["steps"], "no step section in cost report"
assert any(st.get("count", 0) >= 3 for st in agg["steps"].values()), \
    agg["steps"]
assert sum(v["flops"] for v in agg["categories"].values()) > 0, \
    agg["categories"]
for rep in agg["executables"]:
    tf = rep["totals"]["flops"]
    s = sum(c["flops"] for c in rep["categories"].values())
    assert abs(s - tf) < 1, (rep["label"], s, tf)
    rl = rep.get("roofline")
    if rl:
        for cat, v in rl["categories"].items():
            assert v["bound"] in ("compute", "memory"), (cat, v)
print("profiling gate ok: %d executables, %d step labels, "
      "%.0f total flops"
      % (len(agg["executables"]), len(agg["steps"]),
         sum(v["flops"] for v in agg["categories"].values())))
EOF
    # gate 2: a run diffed against itself must report ZERO drift
    python -m mxnet_tpu.profiling diff "$pdir/report.json" "$pdir/report.json"
    rm -rf "$pdir"
}

run_perflint() {
    log "perflint: full-tree static pass (five perf rules armed)"
    # same framework as the lint stage; running --self here keeps the
    # stage self-contained when invoked alone (ci/run_all.sh perflint)
    python -m mxnet_tpu.analysis --self --json
    log "perflint: compiled-audit zero-drift gate (LeNet TrainStep + ResNet18 forward)"
    pfdir=$(mktemp -d /tmp/mxtpu_perf_ci.XXXXXX)
    JAX_PLATFORMS=cpu MXNET_TPU_PROFILING=1 python - "$pfdir" <<'EOF'
import os, sys
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import gluon, profiling
from mxnet_tpu.analysis import perf
from mxnet_tpu.gluon.model_zoo.vision import resnet18_v1
from mxnet_tpu.parallel import TrainStep

pfdir = sys.argv[1]
assert profiling.enabled(), "MXNET_TPU_PROFILING=1 did not arm capture"


class PerfLeNet(gluon.nn.HybridSequential):
    """Named so the audit row is stable across CI runs."""


net = PerfLeNet()
net.add(gluon.nn.Conv2D(8, 5, padding=2, activation="relu",
                        layout="NCHW"),
        gluon.nn.MaxPool2D(2, layout="NCHW"),
        gluon.nn.Flatten(),
        gluon.nn.Dense(32, activation="relu"),
        gluon.nn.Dense(10))
net.initialize(ctx=mx.cpu())
net.hybridize()
tr = gluon.Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1},
                   kvstore=None)
step = TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(), tr,
                 mesh=None)
rng = np.random.RandomState(0)
x = mx.nd.array(rng.rand(8, 1, 16, 16).astype(np.float32))
y = mx.nd.array(rng.randint(0, 10, (8,)).astype(np.float32))
for _ in range(2):
    loss = step(x, y)
loss.asnumpy()

res = resnet18_v1(classes=10, thumbnail=True)
res.initialize(ctx=mx.cpu())
res.hybridize()
rx = mx.nd.array(rng.rand(2, 3, 32, 32).astype(np.float32))
res(rx).asnumpy()     # first pass runs eagerly (deferred shape init)
res(rx).asnumpy()     # second pass compiles the whole net: hybrid:ResNetV1

audit = perf.save_audit(os.path.join(pfdir, "current.json"))
labels = set(audit["executables"])
assert "train_step:PerfLeNet" in labels, labels
assert "hybrid:ResNetV1" in labels, labels
print("perflint smoke ok: %d executables audited, %d advisories"
      % (len(labels), len(audit["advisories"])))
EOF
    # gate: efficiency metrics vs the committed baseline -- a grown
    # transpose/unfused/pad-waste share or an unblessed advisory exits
    # 1 naming executable + kind; improvements pass
    python -m mxnet_tpu.analysis --perf-diff \
        ci/perf_baseline.json "$pfdir/current.json" --json
    rm -rf "$pfdir"
}

run_numlint() {
    log "numlint: full-tree static pass (five dtype-hazard rules armed)"
    # the numerics rules ride the same framework as the lint stage;
    # running --self here keeps this stage self-contained when invoked
    # alone (ci/run_all.sh numlint)
    python -m mxnet_tpu.analysis --self --json
    log "numlint: sentinel + precision-audit gate (LeNet + bf16 ResNet18 TrainStep)"
    nmdir=$(mktemp -d /tmp/mxtpu_num_ci.XXXXXX)
    JAX_PLATFORMS=cpu MXNET_TPU_PROFILING=1 MXNET_TPU_NUMERICS_CHECK=1 \
        python - "$nmdir" <<'EOF'
import os, sys
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import amp, chaos, gluon, profiling
from mxnet_tpu.analysis import numerics
from mxnet_tpu.gluon.model_zoo.vision import resnet18_v1
from mxnet_tpu.parallel import TrainStep

nmdir = sys.argv[1]
assert profiling.enabled(), "MXNET_TPU_PROFILING=1 did not arm capture"
assert numerics.check_enabled(), \
    "MXNET_TPU_NUMERICS_CHECK=1 did not arm the sentinel"
assert mx.runtime.Features().is_enabled("NUMERICS")


class NumLeNet(gluon.nn.HybridSequential):
    """Named so the audit row is stable across CI runs."""


net = NumLeNet()
net.add(gluon.nn.Conv2D(8, 5, padding=2, activation="relu",
                        layout="NCHW"),
        gluon.nn.MaxPool2D(2, layout="NCHW"),
        gluon.nn.Flatten(),
        gluon.nn.Dense(32, activation="relu"),
        gluon.nn.Dense(10))
net.initialize(ctx=mx.cpu())
net.hybridize()
tr = gluon.Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1},
                   kvstore=None)
step = TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(), tr,
                 mesh=None)
rng = np.random.RandomState(0)
x = mx.nd.array(rng.rand(8, 1, 16, 16).astype(np.float32))
y = mx.nd.array(rng.randint(0, 10, (8,)).astype(np.float32))

# the detection gate: two clean sentinel-checked steps, then a
# chaos-seeded NaN at step 3 must surface as a typed NonFiniteError
# naming a REAL parameter, caught by the sentinel (the injector only
# poisons the batch; the fault flows through forward/backward)
with chaos.scenario(seed=0):
    chaos.on("numerics.nonfinite", numerics.poison_action, nth=3)
    for _ in range(2):
        loss = step(x, y)
    loss.asnumpy()
    try:
        step(x, y)
        raise SystemExit("chaos NaN at step 3 did not raise NonFiniteError")
    except numerics.NonFiniteError as e:
        pnames = {p.name for p in tr._params}
        assert e.param in pnames, (e.param, pnames)
        assert e.step == 3, e.step
        assert e.kind == "nan", e.kind
        print("sentinel gate ok: NonFiniteError(%s, step=%s, %s)"
              % (e.param, e.step, e.kind))
row = numerics.status_row()
assert row["checks"] >= 3 and row["nonfinite"] == 1 \
    and row["last"]["kind"] == "nan", row

# bf16 half of the audit: the same net shape trained under amp bf16 +
# a bf16 ResNet18 TrainStep give the auditor real half-precision HLO
res = resnet18_v1(classes=10, thumbnail=True)
res.initialize(ctx=mx.cpu())
res.hybridize()
rtr = gluon.Trainer(res.collect_params(), "sgd", {"learning_rate": 0.1},
                    kvstore=None)
rstep = TrainStep(res, gluon.loss.SoftmaxCrossEntropyLoss(), rtr,
                  mesh=None)
rx = mx.nd.array(rng.rand(2, 3, 32, 32).astype(np.float32))
ry = mx.nd.array(rng.randint(0, 10, (2,)).astype(np.float32))
with amp.scope("bfloat16"):
    for _ in range(2):
        rloss = rstep(rx, ry)
rloss.asnumpy()

audit = numerics.save_audit(os.path.join(nmdir, "current.json"))
labels = set(audit["executables"])
assert "train_step:NumLeNet" in labels, labels
assert "train_step:ResNetV1" in labels, labels
print("numlint smoke ok: %d executables audited, %d advisories"
      % (len(labels), len(audit["advisories"])))
EOF
    # gate: precision metrics vs the committed baseline -- a grown
    # half-accum-dot/convert-storm/half-reduce share or an unblessed
    # advisory exits 1 naming executable + kind; improvements pass
    python -m mxnet_tpu.analysis --numerics-diff \
        ci/numerics_baseline.json "$nmdir/current.json" --json
    rm -rf "$nmdir"
}

run_memlint() {
    log "memlint: full-tree static pass (five HBM-hazard rules armed)"
    # the memory rules ride the same framework as the lint stage;
    # running --self here keeps this stage self-contained when invoked
    # alone (ci/run_all.sh memlint)
    python -m mxnet_tpu.analysis --self --json
    log "memlint: peak-HBM audit + hbm_plan + leak-sentinel gate (LeNet TrainStep, seed 0)"
    mmdir=$(mktemp -d /tmp/mxtpu_mem_ci.XXXXXX)
    # PYTHONHASHSEED is pinned: hash ordering feeds the flattened
    # argument order of the train step, and XLA's input-output alias
    # assignment (alias_bytes, hence peak) depends on it -- the
    # committed baseline is blessed under the same seed (docs/memory.md)
    JAX_PLATFORMS=cpu MXNET_TPU_PROFILING=1 MXNET_TPU_MEMORY_WATCH=1 \
        PYTHONHASHSEED=0 python - "$mmdir" <<'EOF'
import os, sys
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import chaos, gluon, profiling
from mxnet_tpu.analysis import memory
from mxnet_tpu.parallel import TrainStep

mmdir = sys.argv[1]
assert profiling.enabled(), "MXNET_TPU_PROFILING=1 did not arm capture"
assert memory.watch_enabled(), \
    "MXNET_TPU_MEMORY_WATCH=1 did not arm the live-buffer watch"
assert mx.runtime.Features().is_enabled("MEMORY_WATCH")


class MemLeNet(gluon.nn.HybridSequential):
    """Named so the audit row is stable across CI runs."""


net = MemLeNet()
net.add(gluon.nn.Conv2D(8, 5, padding=2, activation="relu",
                        layout="NCHW"),
        gluon.nn.MaxPool2D(2, layout="NCHW"),
        gluon.nn.Flatten(),
        gluon.nn.Dense(32, activation="relu"),
        gluon.nn.Dense(10))
net.initialize(ctx=mx.cpu())
net.hybridize()
tr = gluon.Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1},
                   kvstore=None)
step = TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(), tr,
                 mesh=None)
rng = np.random.RandomState(0)
x = mx.nd.array(rng.rand(4, 1, 16, 16).astype(np.float32))
y = mx.nd.array(rng.randint(0, 10, (4,)).astype(np.float32))
for _ in range(2):
    loss = step(x, y)
loss.asnumpy()

audit = memory.save_audit(os.path.join(mmdir, "current.json"))
labels = set(audit["executables"])
assert "train_step:MemLeNet" in labels, labels
print("memlint audit ok: %d executables, %d advisories"
      % (len(labels), len(audit["advisories"])))

# hbm_plan anchor gate: the predicted peak at both probe buckets must
# match a real compile -- the extrapolation line is anchored on real
# compiles, so the planner cannot silently drift from the backend
fn, arg_shapes = step._last_call
plan = memory.hbm_plan(
    "train_step:MemLeNet", buckets=(4, 8), batch_size=4,
    fn=fn, args=arg_shapes,
    device_hbm_bytes=memory.device_hbm_bytes() or (16 << 30))
pred = {r["batch"]: r["predicted_peak_hbm_bytes"]
        for r in plan["buckets"]}
for b in (4, 8):
    measured = memory.executable_memory(
        fn.lower(*memory._resize_batch(arg_shapes, 4, b))
        .compile())["peak_hbm_bytes"]
    assert abs(pred[b] - measured) <= 1, (b, pred[b], measured)
print("memlint hbm_plan ok: const %d B + %d B/item, largest fit %s"
      % (plan["const_bytes"], plan["per_item_bytes"],
         plan["largest_fit_bucket"]))

# leak-sentinel gate (seed 0): clean windows must never flag;
# chaos-pinned arrays must flag within 3 windows naming the pinned
# shape bucket (the SENTINEL, not the injector, catches the leak)
sent = memory.sentinel(window_steps=1, min_baseline=3,
                       min_growth_frac=0.01)
chaos.reset()
chaos.on("memory.leak", memory.pin_action)
for i in range(5):                      # disarmed: the point no-ops
    chaos.fail_point("memory.leak", step=i)
    sent.step()
assert memory._STATE["leaks"] == 0, "clean windows flagged a leak"
nbytes = int(memory._STATE["live_bytes"] * 0.3) + (16 << 20)
chaos.arm(seed=0)
flagged_at = None
for i in range(6):
    chaos.fail_point("memory.leak", step=i, nbytes=nbytes)
    sent.step()
    if memory._STATE["leaks"]:
        flagged_at = i
        break
chaos.disarm()
chaos.reset()
assert flagged_at is not None and flagged_at < 3, \
    "chaos-pinned growth not flagged within 3 windows"
leak = memory._STATE["last_leak"]
assert leak["bucket"] == "(%d,)/float32" % max(1, nbytes // 4), leak
print("memlint sentinel ok: leak flagged at window %d naming %s "
      "(+%d B)" % (flagged_at, leak["bucket"], leak["growth_bytes"]))
EOF
    # gate: peak HBM vs the committed baseline -- a grown peak or an
    # unblessed executable/advisory exits 1 naming executable + kind;
    # shrinkage passes
    python -m mxnet_tpu.analysis --memory-diff \
        ci/memory_baseline.json "$mmdir/current.json" --json
    # the gate must also CATCH: a seeded +50% peak regression exits 1
    python - "$mmdir" <<'EOF'
import json, sys
mmdir = sys.argv[1]
with open(mmdir + "/current.json") as f:
    cur = json.load(f)
for row in cur["executables"].values():
    row["metrics"]["peak_hbm_bytes"] = \
        int(row["metrics"]["peak_hbm_bytes"] * 1.5)
with open(mmdir + "/regress.json", "w") as f:
    json.dump(cur, f)
EOF
    if python -m mxnet_tpu.analysis --memory-diff \
        ci/memory_baseline.json "$mmdir/regress.json" --json \
        > /dev/null; then
        echo "memlint: seeded +50% peak-HBM regression was NOT caught"
        exit 1
    fi
    echo "memlint: seeded peak regression caught (exit 1, as gated)"
    rm -rf "$mmdir"
}

run_shardlint() {
    log "shardlint: full-tree sharding pass (mesh axes, shard_map arity, donation, reshard)"
    # the sharding rules ride the same framework as the lint stage;
    # running --self here keeps this stage self-contained when invoked
    # alone (ci/run_all.sh shardlint)
    python -m mxnet_tpu.analysis --self --json
    log "shardlint: collective-contract + transfer-guard gate (LeNet TrainStep over dp mesh)"
    sdir=$(mktemp -d /tmp/mxtpu_shard_ci.XXXXXX)
    JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
        MXNET_TPU_SHARD_CHECK=1 python - "$sdir" <<'EOF'
import os, sys
import numpy as np
import jax
import mxnet_tpu as mx
from mxnet_tpu import gluon, profiling
from mxnet_tpu.analysis import sharding
from mxnet_tpu.parallel import TrainStep, make_mesh

sdir = sys.argv[1]
assert profiling.enabled(), "MXNET_TPU_SHARD_CHECK=1 did not arm capture"
assert mx.runtime.Features().is_enabled("SHARD_CHECK")

mesh = make_mesh({"dp": 8})
net = gluon.nn.HybridSequential()
net.add(gluon.nn.Conv2D(6, 5, padding=2, activation="relu"),
        gluon.nn.MaxPool2D(2),
        gluon.nn.Conv2D(16, 3, activation="relu"),
        gluon.nn.MaxPool2D(2),
        gluon.nn.Flatten(),
        gluon.nn.Dense(32, activation="relu"),
        gluon.nn.Dense(10))
net.initialize(ctx=mx.cpu())
net.hybridize()
tr = gluon.Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1},
                   kvstore=None)
step = TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(), tr, mesh=mesh)
rng = np.random.RandomState(0)
x = mx.nd.array(rng.rand(16, 1, 16, 16).astype(np.float32))
y = mx.nd.array(rng.randint(0, 10, (16,)).astype(np.float32))
step(x, y)                               # compile + state init, unguarded

# steady-state steps under the transfer guard: the compiled step must
# be free of IMPLICIT host transfers (scalar feeds ride device_put)
with sharding.transfer_guard("disallow"):
    for _ in range(2):
        loss = step(x, y)
    loss._data.block_until_ready()

# and a seeded in-step leak must raise -- the guard is live, not a no-op
try:
    with sharding.transfer_guard("disallow"):
        (loss * 1.5)._data.block_until_ready()   # py scalar -> implicit h2d
except Exception:
    pass
else:
    raise SystemExit("transfer guard did not catch the seeded host transfer")

cur = sharding.save_contract(os.path.join(sdir, "current.json"))
label = "train_step:HybridSequential"
assert label in cur["executables"], cur["executables"].keys()
print("shardlint smoke ok: %s collectives %s"
      % (label, cur["executables"][label]))
EOF
    # gate: the smoke's GSPMD collectives vs the committed baseline --
    # an unblessed kind or a grown count exits 1 naming executable+kind
    python -m mxnet_tpu.analysis --collective-diff \
        ci/sharding_baseline.json "$sdir/current.json" --json
    rm -rf "$sdir"
}

run_spmd() {
    log "spmd: 2-proc gloo one-program smoke train (transfer guard + zero kv bytes)"
    pdir=$(mktemp -d /tmp/mxtpu_spmd_ci.XXXXXX)
    cat > "$pdir/spmd_worker.py" <<'EOF'
import os, sys, re
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = re.sub(
    r"--xla_force_host_platform_device_count=\d+", "",
    os.environ.get("XLA_FLAGS", "")).strip()   # one device per rank
import jax
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import gluon, telemetry
from mxnet_tpu import distributed as dist
from mxnet_tpu.analysis import sharding
from mxnet_tpu.parallel import TrainStep, global_mesh

outdir = sys.argv[1]
assert mx.distributed_init() is True
assert jax.process_count() == 2, jax.process_count()
nproc, rank = dist.world()


class SpmdSmokeNet(gluon.nn.HybridSequential):
    """Named so the dist executable gets its own blessed baseline row."""


net = SpmdSmokeNet()
net.add(gluon.nn.Dense(16, activation="relu"), gluon.nn.Dense(4))
net.initialize(ctx=mx.cpu())
net.hybridize()
tr = gluon.Trainer(net.collect_params(), "sgd",
                   {"learning_rate": 0.05, "momentum": 0.9},
                   kvstore="dist_sync")
step = TrainStep(net, gluon.loss.L2Loss(), tr)   # auto global mesh
assert step._mesh.shape["dp"] == 2

rng = np.random.RandomState(100 + rank)
w = np.random.RandomState(0).randn(8, 4).astype(np.float32)
x = rng.randn(8, 8).astype(np.float32)           # per-rank LOCAL batch
y = (x @ w).astype(np.float32)
l0 = float(np.asarray(step(x, y)._data))         # compile + init sync
telemetry.reset("kvstore.")
with sharding.transfer_guard("disallow"):        # steady state, guarded
    for _ in range(8):
        loss = step(x, y)
    last = float(np.asarray(loss._data))
assert last < l0, (l0, last)
for verb in ("push", "pull", "pushpull", "bytes"):
    assert telemetry.counter("kvstore." + verb).value == 0, \
        "kv.%s moved host bytes on the hot path" % verb
assert dist._KV_FALLBACK_WARNED[0] is False, "KV fallback latch warm"
if rank == 0:
    cur = sharding.save_contract(os.path.join(outdir, "current.json"))
    kinds = cur["executables"]["train_step:SpmdSmokeNet"]
    assert "all-reduce" in kinds, kinds
dist.barrier("spmd_ci_done")
print("SPMD_CI_OK rank=%d loss %.4f -> %.4f" % (rank, l0, last))
EOF
    JAX_PLATFORMS=cpu MXNET_TPU_SHARD_CHECK=1 MXNET_TPU_TELEMETRY=1 \
        PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}" \
        python tools/launch.py -n 2 python -u "$pdir/spmd_worker.py" "$pdir"
    log "spmd: collective-baseline diff gate (rank 0's dist executable)"
    # the gradient all-reduce is blessed in ci/sharding_baseline.json;
    # an unblessed kind or a grown count exits 1 naming executable+kind
    python -m mxnet_tpu.analysis --collective-diff \
        ci/sharding_baseline.json "$pdir/current.json" --json
    rm -rf "$pdir"
}

run_serving() {
    log "serving: concurrent-load smoke (dynamic batching + graceful drain)"
    svjsonl=$(mktemp /tmp/mxtpu_serving_ci.XXXXXX.jsonl)
    svcache=$(mktemp -d /tmp/mxtpu_serving_cache.XXXXXX)
    JAX_PLATFORMS=cpu MXNET_TPU_TELEMETRY=1 \
        MXNET_TPU_TELEMETRY_JSONL="$svjsonl" \
        MXNET_TPU_SERVING_CACHE_DIR="$svcache" python - <<'EOF'
import threading
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import gluon, telemetry

# a LeNet servable, registered from a Gluon block (buckets warmed at
# registration: no request below pays a first-compile)
net = gluon.nn.HybridSequential()
net.add(gluon.nn.Conv2D(8, kernel_size=5, activation="relu"),
        gluon.nn.MaxPool2D(2, 2),
        gluon.nn.Flatten(),
        gluon.nn.Dense(32, activation="relu"),
        gluon.nn.Dense(10))
net.initialize(); net.hybridize()
net(mx.nd.array(np.zeros((1, 1, 28, 28), np.float32)))

reg = mx.serving.ModelRegistry()
s = reg.register("lenet", block=net, input_shape=(1, 28, 28),
                 buckets=(1, 2, 4, 8), max_wait_ms=50, max_queue=256)

# concurrent requests from threads: the dynamic batcher must assemble
# real micro-batches (mean occupancy > 1), and the graceful drain must
# lose NO in-flight response
n_threads, per_thread = 4, 8
results = [[None] * per_thread for _ in range(n_threads)]
barrier = threading.Barrier(n_threads)
sample = np.random.RandomState(0).rand(1, 28, 28).astype(np.float32)

def client(tid):
    barrier.wait()
    futs = [s.submit(sample, timeout=30) for _ in range(per_thread)]
    for i, f in enumerate(futs):
        results[tid][i] = f.result(timeout=30)

threads = [threading.Thread(target=client, args=(t,), daemon=True)
           for t in range(n_threads)]
for t in threads:
    t.start()
for t in threads:
    t.join()
reg.shutdown(drain=True)          # graceful drain

dropped = sum(1 for row in results for r in row if r is None)
assert dropped == 0, "%d responses dropped after graceful drain" % dropped
want = net(mx.nd.array(sample[None])).asnumpy()[0]
for row in results:
    for r in row:
        np.testing.assert_allclose(r, want, rtol=1e-4, atol=1e-4)
batches = telemetry.counter("serving.batches").value
responses = telemetry.counter("serving.responses").value
occ = responses / batches
assert occ > 1, "mean batch occupancy %.2f (no dynamic batching)" % occ
telemetry.flush()
print("serving smoke ok: %d responses in %d batches (occupancy %.2f)"
      % (responses, batches, occ))
EOF
    # gate: the summarize CLI must report a non-empty serving section
    python -m mxnet_tpu.telemetry summarize "$svjsonl" --json > "$svjsonl.agg"
    python - "$svjsonl.agg" <<'EOF'
import json, sys
agg = json.load(open(sys.argv[1]))
sv = agg["serving"]
assert sv["requests"] >= 32, sv
assert sv["responses"] == sv["requests"], sv
assert sv["batches"] > 0 and sv["mean_occupancy"] > 1, sv
assert sv["shed"] == 0 and sv["timeouts"] == 0, sv
assert sv["latency_p50_s"] is not None and sv["latency_p99_s"] is not None, sv
assert sv["latency_p50_s"] <= sv["latency_p99_s"], sv
print("serving gate ok: %d requests, occupancy %.2f, p99 %.1fms"
      % (sv["requests"], sv["mean_occupancy"], 1e3 * sv["latency_p99_s"]))
EOF
    rm -rf "$svjsonl" "$svjsonl.agg" "$svcache"
}

run_serving_decode() {
    log "serving_decode: generative tier smoke (continuous batching + paged KV cache + mid-decode swap)"
    gdcache=$(mktemp -d /tmp/mxtpu_gdec_cache.XXXXXX)
    JAX_PLATFORMS=cpu MXNET_TPU_TELEMETRY=1 \
        MXNET_TPU_SERVING_CACHE_DIR="$gdcache" python - <<'EOF'
import threading
import time

import mxnet_tpu as mx
from mxnet_tpu import chaos, telemetry
from mxnet_tpu.serving.decode import tiny_gpt

model = tiny_gpt(vocab_size=32, units=16, num_layers=2, num_heads=2,
                 max_seq=32)
p0 = model.init_params(0)
reg = mx.serving.ModelRegistry()
reg.register_generative("gpt", model, params=p0,
                        prefill_buckets=(8,), decode_buckets=(1, 2, 4),
                        block_size=4, num_blocks=64, max_queue=16)

# staggered concurrent streams: joins happen at step boundaries of a
# RUNNING batch, and every stream must be bit-identical to the
# single-shot full-forward reference (the numerics oracle).  Decode
# steps are throttled (chaos sleep, seed 0) so the stagger lands every
# later stream INSIDE the running batch deterministically.
prompts = [[3, 7, 1, 9, 2], [5, 5, 6], [1, 2, 3, 4], [9, 8, 7]]
solo = [model.reference_decode(p0, p, 10) for p in prompts]
results = [None] * len(prompts)

def client(i):
    time.sleep(0.01 * i)
    results[i] = list(reg.generate("gpt", prompts[i], 10))

with chaos.scenario(seed=0):
    chaos.on("serving.decode.step", action=lambda ctx: time.sleep(0.02))
    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

dropped = sum(1 for r in results if r is None or len(r) != 10)
assert dropped == 0, "%d streams dropped/truncated" % dropped
for i, r in enumerate(results):
    assert r == solo[i], "stream %d diverged from the oracle" % i
tokens = telemetry.counter("decode.tokens").value
steps = telemetry.counter("decode.steps").value
assert tokens > steps, \
    "no continuous batching: %d tokens in %d steps" % (tokens, steps)
sv = reg.servable("gpt")
assert sv.kvcache_stats()["blocks_in_use"] == 0, sv.kvcache_stats()

# mid-decode hot-swap chaos gate at seed 0: throttled decode steps pin
# a half-generated sequence across the swap; it must drain to
# completion on the OLD weights (zero dropped) while new requests land
# on the new servable
p1 = model.init_params(1)
with chaos.scenario(seed=0):
    chaos.on("serving.decode.step", action=lambda ctx: time.sleep(0.03))
    stream = reg.generate("gpt", [3, 7, 1, 9, 2], 20)
    first = next(stream)
    reg.register_generative("gpt", model, params=p1,
                            prefill_buckets=(8,),
                            decode_buckets=(1, 2, 4), block_size=4,
                            num_blocks=64, max_queue=16)
    drained = [first] + list(stream)
    assert drained == model.reference_decode(p0, [3, 7, 1, 9, 2], 20), \
        "mid-swap sequence diverged from old-weight oracle"
    assert chaos.stats()["survived"].get("serving.decode_swap") == 1, \
        chaos.stats()["survived"]
    fresh = list(reg.generate("gpt", [3, 7, 1], 5))
    assert fresh == model.reference_decode(p1, [3, 7, 1], 5), \
        "post-swap request did not use the new weights"
occ = tokens / steps
reg.shutdown(drain=True)
print("serving_decode gate ok: %d tokens in %d steps (occupancy %.2f), "
      "mid-decode swap drained, 0 dropped" % (tokens, steps, occ))
EOF
    rm -rf "$gdcache"
}

run_chaos() {
    log "chaos: deterministic fault-injection tests (quick tier)"
    JAX_PLATFORMS=cpu python -m pytest tests/test_chaos.py -q -m 'not slow'
    chdir=$(mktemp -d /tmp/mxtpu_chaos_ci.XXXXXX)
    log "chaos: REAL kill-mid-commit (seed 0) -> one-step rollback gate"
    # phase 1: a trainer publishing every step dies SIGKILL-shaped
    # (os._exit 137) between the staged data files and the manifest
    # commit of step 3 -- the staged dir must never become loadable
    set +e
    JAX_PLATFORMS=cpu python - "$chdir" <<'EOF'
import sys
import mxnet_tpu as mx
from mxnet_tpu import chaos
from mxnet_tpu.chaos import scenarios
from mxnet_tpu.serving.loop import ContinuousTrainer

net, trainer, loss_fn, data = scenarios.train_fixtures(seed=0)
ct = ContinuousTrainer(net, trainer, loss_fn, data,
                       sys.argv[1] + "/ckpts", publish_every=1)
chaos.arm(seed=0)
chaos.on("checkpoint.commit.pre_manifest", nth=3, action=chaos.KILL)
ct.run_steps(3)                         # dies mid-commit of step 3
raise SystemExit("chaos KILL did not fire")
EOF
    rc=$?
    set -e
    [ "$rc" -eq 137 ] || { echo "expected exit 137, got $rc"; exit 1; }
    # phase 2: a fresh process (the restarted job + the serving side)
    # must see step 2 as the newest verified step, sweep the orphaned
    # staging dir, and hot-swap the servable to step 2
    JAX_PLATFORMS=cpu MXNET_TPU_TELEMETRY=1 python - "$chdir" <<'EOF'
import os, sys
import mxnet_tpu as mx
from mxnet_tpu import serving, telemetry
from mxnet_tpu.chaos import scenarios
from mxnet_tpu.serving.loop import RegistryWatcher

root = sys.argv[1] + "/ckpts"
assert any(d.endswith(".tmp") for d in os.listdir(root)), \
    "kill left no staging dir -- the scenario tested nothing"
mgr = mx.checkpoint.CheckpointManager(root)     # init sweeps dead tmps
assert not any(d.endswith(".tmp") for d in os.listdir(root))
assert mgr.latest_step() == 2, mgr.all_steps()
reg = serving.ModelRegistry(compile_cache=False)
watcher = RegistryWatcher(reg, "model", mgr, scenarios.make_mlp(),
                          input_shape=(8,), buckets=(1, 2),
                          max_wait_ms=2)
assert watcher.poll_once() == 2
assert telemetry.counter("serving.swaps").value == 1
import numpy as np
out = reg.infer("model", np.zeros(8, np.float32), timeout=30)
assert out is not None
reg.shutdown(drain=True); watcher.close()
print("kill-mid-commit gate ok: rolled back to step 2, tmp swept, "
      "servable swapped")
EOF
    log "chaos: torn-publish hot-swap scenario (seed 0) -> quarantine + zero dropped"
    JAX_PLATFORMS=cpu MXNET_TPU_TELEMETRY=1 python - "$chdir" <<'EOF'
import sys
from mxnet_tpu import telemetry
from mxnet_tpu.chaos import scenarios

rep = scenarios.hotswap_scenario(sys.argv[1] + "/torn", torn=True,
                                 seed=0)
assert rep["second_swap_step"] is None, rep
assert rep["served_step"] == 2, rep             # the rollback gate
assert rep["quarantined"] == ["step_00000004.corrupt"], rep
assert rep["errors"] == [] and rep["shed"] == 0, rep
assert rep["completed"] == rep["requests"] > 0, rep   # zero dropped
assert rep["completed_after_swap"] >= 1, rep
assert rep["chaos"]["injected"]["checkpoint.commit.post_commit"] == 1
assert telemetry.counter("checkpoint.quarantined").value == 1
assert telemetry.counter("chaos.injected").value == 1
assert telemetry.counter("chaos.survived").value >= 1
print("torn-publish gate ok: quarantined, served step %d, "
      "%d/%d requests completed"
      % (rep["served_step"], rep["completed"], rep["requests"]))
EOF
    log "chaos: batcher flood scenario (seed 0) -> shed counted, tail bounded"
    JAX_PLATFORMS=cpu MXNET_TPU_TELEMETRY=1 python - <<'EOF'
from mxnet_tpu import telemetry
from mxnet_tpu.chaos import scenarios

rep = scenarios.flood_scenario(seed=0, max_queue=4, clients=8,
                               per_client=8, hold_s=0.03)
assert rep["shed"] > 0, "flood did not overflow the bounded queue"
assert rep["errors"] == [], rep["errors"]       # sheds are DISTINCT
assert rep["completed"] + rep["shed"] == rep["requests"], rep
assert rep["completed"] > 0, rep                # in-flight completed
assert rep["max_latency_s"] < rep["latency_bound_s"], rep
assert telemetry.counter("serving.shed").value == rep["shed"]
print("flood gate ok: %d sheds, %d completed, max latency %.0fms "
      "(bound %.0fms)"
      % (rep["shed"], rep["completed"], 1e3 * rep["max_latency_s"],
         1e3 * rep["latency_bound_s"]))
EOF
    log "chaos: distributed resilience tests (typed failures, spec replay, supervisor)"
    JAX_PLATFORMS=cpu python -m pytest tests/test_resilience.py -q \
        -m 'not slow'
    rm -rf "$chdir"
}

run_chaos_dist() {
    log "chaos_dist: 2-proc kill-mid-sharded-commit -> abort -> supervised relaunch -> bit-identical resume (seed 0)"
    cdir=$(mktemp -d /tmp/mxtpu_chaos_dist.XXXXXX)
    cat > "$cdir/worker.py" <<'EOF'
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import chaos, telemetry
from mxnet_tpu import distributed as dist
from mxnet_tpu.chaos import scenarios
from mxnet_tpu.serving.loop import ContinuousTrainer

outdir = sys.argv[1]
assert mx.distributed_init() is True
nproc, rank = dist.world()
gen = dist.generation()
telemetry.enable()
chaos.arm_from_spec()            # EXPLICIT harness opt-in; the rule is
                                 # rank-1 + generation-0 scoped
# identical replicated params on every rank (the SPMD init contract)
np.random.seed(0)
mx.random.seed(0)
net, trainer, loss_fn, data = scenarios.train_fixtures(seed=0)
ct = ContinuousTrainer(net, trainer, loss_fn, data, outdir + "/ckpts",
                       publish_every=1)
ckpt = ct.resume()

def dump_params(tag):
    arrs = {k: p._reduce().asnumpy() for k, p in
            net._collect_params_with_prefix().items()}
    np.savez(outdir + "/%s_rank%d.npz" % (tag, rank), **arrs)

if gen == 0:
    assert ckpt is None
    ct.run_steps(1)              # publish step 1 (verified)
    dump_params("step1")         # the bit-identical reference
    try:
        ct.run_steps(2)          # step-2 publish: rank 1 dies between
                                 # the "written" and "committed" barriers
    except dist.BarrierTimeout as e:
        assert 1 in e.ranks, e.ranks
        assert e.tag == "ckpt_committed", e.tag
        assert ct.manager.latest_step() == 1, ct.manager.all_steps()
        assert not os.path.isdir(ct.manager.step_dir(2)), \
            "merged manifest committed past a dead rank!"
        assert telemetry.counter("checkpoint.commit_aborted").value == 1
        print("SURVIVOR_ABORT rank=%d %s: %s" % (
            rank, type(e).__name__, e), flush=True)
        dist.failfast_exit(3)    # surface to the supervisor per policy
    raise SystemExit("chaos kill did not fire (rank %d)" % rank)

assert gen == 1, gen
assert ckpt is not None and ckpt.step == 1, ckpt
side = np.load(outdir + "/step1_rank%d.npz" % rank)
for k, p in sorted(net._collect_params_with_prefix().items()):
    a = p.data().asnumpy()
    b = side[k]
    assert a.dtype == b.dtype and a.tobytes() == b.tobytes(), k
print("RESUME_BIT_IDENTICAL rank=%d generation=%d step=%d"
      % (rank, gen, ckpt.step), flush=True)
ct.run_steps(2)                  # steps 2..3 publish clean
dist.barrier("gen1_steps_done")  # rename visibility (read-after-save)
assert ct.manager.latest_step() == 3, ct.manager.all_steps()
ct.close()
print("GEN1_DONE rank=%d" % rank, flush=True)
EOF
    spec=$(JAX_PLATFORMS=cpu python - <<'EOF'
from mxnet_tpu import chaos
print(chaos.make_spec(seed=0, rules=[
    {"point": "checkpoint.sharded.barrier.committed",
     "action": "kill", "nth": 2, "rank": 1, "generation": 0}]))
EOF
)
    JAX_PLATFORMS=cpu MXNET_TPU_CHAOS_SPEC="$spec" \
        MXNET_TPU_DIST_BARRIER_TIMEOUT_MS=8000 \
        MXNET_TPU_DIST_LEASE_TTL_S=4 \
        PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}" \
        python tools/launch.py -n 2 --supervise --max-restarts 2 \
        --grace 30 python -u "$cdir/worker.py" "$cdir" \
        | tee "$cdir/out.log"
    # the gates: typed abort naming the dead rank, one relaunch, and a
    # bit-identical resume on BOTH ranks of generation 1
    grep -q "SURVIVOR_ABORT rank=0 BarrierTimeout" "$cdir/out.log"
    grep -q "rank(s) \[1\]" "$cdir/out.log"
    grep -q "relaunching generation 1" "$cdir/out.log"
    grep -q "RESUME_BIT_IDENTICAL rank=0 generation=1 step=1" "$cdir/out.log"
    grep -q "RESUME_BIT_IDENTICAL rank=1 generation=1 step=1" "$cdir/out.log"
    [ "$(grep -c GEN1_DONE "$cdir/out.log")" -eq 2 ]
    log "chaos_dist: restart-budget exhaustion -> /healthz NOT_READY gate"
    JAX_PLATFORMS=cpu MXNET_TPU_TELEMETRY=1 python - <<'EOF'
import sys
from mxnet_tpu import telemetry
from mxnet_tpu.obs import status
from mxnet_tpu.supervisor import Supervisor

sup = Supervisor([sys.executable, "-c", "import sys; sys.exit(2)"], 2,
                 max_restarts=1, grace_s=2)
rc = sup.run()
assert rc == 2 and sup.exhausted and sup.restarts == 1, (rc, sup.restarts)
assert telemetry.counter("supervisor.restarts").value == 1
assert telemetry.counter("supervisor.budget_exhausted").value == 1
ready, reasons = status.health()
assert not ready and "restart_budget_exhausted:1" in reasons, reasons
print("budget-exhaustion gate ok: NOT_READY reasons =", reasons)
EOF
    rm -rf "$cdir"
}

run_kernels() {
    log "kernels: interpret-mode kernel tests (registry + numerics + vjp + fallback)"
    # the tests pass use_pallas=True / force=True themselves, so the
    # CPU backend runs the REAL Pallas kernel bodies in interpret mode
    JAX_PLATFORMS=cpu python -m pytest tests/test_kernels.py \
        tests/test_flash_attention.py -q -m 'not slow'
    log "kernels: fallback proof (Pallas unavailable -> XLA, numerics intact)"
    JAX_PLATFORMS=cpu python - <<'EOF'
import numpy as np
import jax.numpy as jnp
from mxnet_tpu import kernels
from mxnet_tpu.kernels import registry as kreg
from mxnet_tpu.kernels.paged_attention import paged_attention

# simulate a build without pallas: every choice must land on XLA
kreg._has_pallas = lambda: False
for name, kw in (("flash_attention",
                  dict(seq=512, block_q=256, block_k=256)),
                 ("paged_attention",
                  dict(heads=16, head_dim=64, block_size=16)),
                 ("mla_paged_attention",
                  dict(heads=64, lanes=640, v_width=512,
                       block_size=64)),
                 ("grouped_matmul", dict(groups=64, k=2304, n=896))):
    ch = kernels.choose(name, force=True, **kw)
    assert not ch.use_pallas, (name, ch)
    assert "unavailable" in ch.reason, ch.reason
rng = np.random.RandomState(0)
q = jnp.asarray(rng.randn(2, 2, 16).astype(np.float32))
kc = jnp.asarray(rng.randn(12, 4, 2, 16).astype(np.float32))
vc = jnp.asarray(rng.randn(12, 4, 2, 16).astype(np.float32))
bt = jnp.asarray(rng.randint(1, 12, (2, 5)), jnp.int32)
cl = jnp.asarray([[7], [18]], jnp.int32)
out = paged_attention(q, kc, vc, bt, cl, scale=0.25, use_pallas=True)
ref = kernels.get("paged_attention").xla_ref(q, kc, vc, bt, cl,
                                             scale=0.25)
np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
print("fallback proof ok: 4 kernels decline, wrapper == XLA reference")
EOF
}

run_obs() {
    log "obs: traced train+serve smoke -> span/counter reconciliation gate"
    obsdir=$(mktemp -d /tmp/mxtpu_obs_ci.XXXXXX)
    JAX_PLATFORMS=cpu MXNET_TPU_TELEMETRY=1 MXNET_TPU_OBS_TRACE=1 \
        MXNET_TPU_TELEMETRY_JSONL="$obsdir/run.jsonl" python - <<'EOF'
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import obs, telemetry
from mxnet_tpu.chaos import scenarios
from mxnet_tpu.serving.loop import ContinuousTrainer

assert obs.tracing_enabled(), "MXNET_TPU_OBS_TRACE=1 did not arm tracing"
assert mx.runtime.Features().is_enabled("OBS_TRACE")
import tempfile
net, trainer, loss_fn, data = scenarios.train_fixtures(seed=0)
ct = ContinuousTrainer(net, trainer, loss_fn, data,
                       tempfile.mkdtemp(), publish_every=2)
ct.run_steps(4)                          # 4 traced steps, 2 publishes
reg = mx.serving.ModelRegistry(compile_cache=False)
reg.register("m", block=scenarios.make_mlp(), input_shape=(8,),
             buckets=(1, 2, 4), max_wait_ms=5, max_queue=64)
sample = np.random.RandomState(0).rand(8).astype(np.float32)
for _ in range(10):
    reg.infer("m", sample, timeout=30)
reg.shutdown(drain=True); ct.close()
telemetry.flush()
print("traced smoke done:",
      len(obs.spans()), "spans recorded")
EOF
    python - "$obsdir/run.jsonl" <<'EOF'
import json, sys
from mxnet_tpu.telemetry import cli as tcli
agg = tcli.summarize_file(sys.argv[1])
sp, c, t = agg["spans"], agg["counters"], agg["timers"]
# causality <-> counters: one queue-wait + request span per accepted
# request, one batch span per compiled dispatch
assert sp["serving.queue_wait"]["count"] == c["serving.requests"], \
    (sp.get("serving.queue_wait"), c.get("serving.requests"))
assert sp["serving.request"]["count"] == c["serving.requests"]
assert sp["serving.batch"]["count"] == c["serving.batches"]
# span walls <-> timer telemetry: dispatch + device_get spans cover
# EXACTLY the window the serving.dispatch_time timer observed
span_wall = sp["serving.dispatch"]["sum"] + sp["serving.device_get"]["sum"]
timer_wall = t["serving.dispatch_time"]["sum"]
assert abs(span_wall - timer_wall) < 1e-4, (span_wall, timer_wall)
# the training side of the causal tree
assert sp["train.step"]["count"] == 4, sp.get("train.step")
assert sp["train.publish"]["count"] == 2
assert sp["checkpoint.commit"]["count"] == 2
print("obs trace gate ok: %d request spans reconcile, dispatch wall "
      "%.3fms == timer %.3fms" % (sp["serving.request"]["count"],
                                  1e3 * span_wall, 1e3 * timer_wall))
EOF
    log "obs: chaos KILL mid-commit (seed 0) -> blackbox postmortem gate"
    set +e
    JAX_PLATFORMS=cpu MXNET_TPU_TELEMETRY=1 MXNET_TPU_OBS_TRACE=1 \
        MXNET_TPU_OBS_BLACKBOX="$obsdir/crash.bbox" python - "$obsdir" <<'EOF'
import sys
from mxnet_tpu import chaos, obs
from mxnet_tpu.chaos import scenarios
from mxnet_tpu.serving.loop import ContinuousTrainer

assert obs.flight.installed() is not None, "blackbox did not install"
net, trainer, loss_fn, data = scenarios.train_fixtures(seed=0)
ct = ContinuousTrainer(net, trainer, loss_fn, data,
                       sys.argv[1] + "/ckpts", publish_every=1)
chaos.arm(seed=0)
chaos.on("checkpoint.commit.pre_manifest", nth=2, action=chaos.KILL)
ct.run_steps(2)                          # dies mid-commit of step 2
raise SystemExit("chaos KILL did not fire")
EOF
    rc=$?
    set -e
    [ "$rc" -eq 137 ] || { echo "expected exit 137, got $rc"; exit 1; }
    # the blackbox CLI must render it, and the machine gate must find
    # the injected fault + the in-flight trace as the FINAL events
    python -m mxnet_tpu.telemetry blackbox "$obsdir/crash.bbox"
    python - "$obsdir/crash.bbox" <<'EOF'
import sys
from mxnet_tpu.obs import flight
recs = flight.read(sys.argv[1])
assert recs, "empty blackbox after a KILL"
last = recs[-1]
assert last.get("name") == "chaos.kill", last
assert last["payload"]["point"] == "checkpoint.commit.pre_manifest"
# the in-flight trace: the kill landed inside the traced
# step->publish->commit chain, so the dump names the dying span
assert last["payload"].get("trace") and last["payload"].get("span"), last
names = [r.get("name") for r in recs]
assert "chaos.inject" in names, "injected-fault event missing from ring"
spans = [r for r in recs if r.get("kind") == "span"]
assert any(s["name"] == "train.step" for s in spans), \
    "no traced spans in the ring"
print("obs blackbox gate ok: %d records, final=%s point=%s"
      % (len(recs), last["name"], last["payload"]["point"]))
EOF
    log "obs: /healthz READY -> NOT_READY flip under the swap failure budget"
    JAX_PLATFORMS=cpu MXNET_TPU_TELEMETRY=1 python - "$obsdir" <<'EOF'
import json, sys, urllib.request, warnings
import mxnet_tpu as mx
from mxnet_tpu import chaos, obs, telemetry
from mxnet_tpu.chaos import scenarios
from mxnet_tpu.serving.loop import ContinuousTrainer, RegistryWatcher

def get(port, path):
    try:
        r = urllib.request.urlopen("http://127.0.0.1:%d%s" % (port, path))
        return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())

port = obs.serve(0)                      # ephemeral: CI-safe
root = sys.argv[1] + "/health_ckpts"
net, trainer, loss_fn, data = scenarios.train_fixtures(seed=0)
ct = ContinuousTrainer(net, trainer, loss_fn, data, root, publish_every=1)
ct.run_steps(1)
reg = mx.serving.ModelRegistry(compile_cache=False)
watcher = RegistryWatcher(reg, "m", ct.manager, scenarios.make_mlp(),
                          input_shape=(8,), buckets=(1, 2),
                          max_wait_ms=2, swap_retries=0,
                          failure_budget=1)
assert watcher.poll_once() == 1
code, body = get(port, "/healthz")
assert code == 200 and body["status"] == "READY", (code, body)
prom = urllib.request.urlopen(
    "http://127.0.0.1:%d/metrics" % port).read().decode()
assert "mxnet_tpu_serving_swaps 1" in prom, prom[:400]
code, st = get(port, "/statusz")
assert st["served_step"] == 1 and st["watchers"][0]["name"] == "m", st
# now every install aborts: publish a new step, let the watcher
# exhaust its budget (retries=0, budget=1) and suspend
ct.run_steps(1)
chaos.arm(seed=0)
chaos.on("serving.swap", action=chaos.RAISE)
with warnings.catch_warnings():
    warnings.simplefilter("ignore", RuntimeWarning)
    assert watcher.poll_once() is None
chaos.disarm(); chaos.reset()
assert watcher.suspended
assert telemetry.counter("serving.watcher_suspensions").value == 1
ev = telemetry.event("serving.watcher_suspended").recent[-1]
assert ev["model"] == "m", ev
code, body = get(port, "/healthz")
assert code == 503 and body["status"] == "NOT_READY", (code, body)
assert any(r.startswith("watcher_suspended:m") for r in body["reasons"])
reg.shutdown(drain=True); watcher.close(); ct.close(); obs.server.stop()
print("obs healthz gate ok: READY -> NOT_READY on suspension "
      "(reasons=%s)" % body["reasons"])
EOF
    log "obs: goodput gate -- injected feed stall must read input-bound"
    JAX_PLATFORMS=cpu MXNET_TPU_TELEMETRY=1 MXNET_TPU_OBS_GOODPUT=1 \
        MXNET_TPU_OBS_GOODPUT_WINDOW=4 python - <<'EOF'
import tempfile
import mxnet_tpu as mx
from mxnet_tpu import chaos, obs, telemetry
from mxnet_tpu.chaos import scenarios
from mxnet_tpu.dataio import DeviceFeed
from mxnet_tpu.obs import goodput
from mxnet_tpu.serving.loop import ContinuousTrainer

assert obs.goodput_enabled(), "MXNET_TPU_OBS_GOODPUT=1 did not arm"
assert mx.runtime.Features().is_enabled("OBS_GOODPUT")
net, trainer, loss_fn, (x, y) = scenarios.train_fixtures(seed=0)
xn, yn = x.asnumpy(), y.asnumpy()


def batches():
    while True:
        yield (xn, yn)


# the PRODUCT wiring: ContinuousTrainer ticks the process ledger every
# step; its data callable pulls staged batches off a DeviceFeed, so
# the feed.produce chaos rule below starves the consumer for real
feed = DeviceFeed(batches(), ctx=mx.cpu())


def data(step):
    b = next(feed)
    return b.data, b.label


ct = ContinuousTrainer(net, trainer, loss_fn, data,
                       tempfile.mkdtemp(), publish_every=10 ** 6)
ct.run_steps(20)                         # 5 healthy windows = baseline
led = goodput.ledger()
healthy = led.windows()
assert len(healthy) == 5, len(healthy)
# injected chaos stall on the input path: input_wait must dominate
chaos.arm(seed=0)
chaos.on("feed.produce", action=chaos.sleep(0.03))
ct.run_steps(12)                         # 3 stalled windows
chaos.disarm(); chaos.reset()
ct.close()
feed.close()
wins = led.windows()
# the reconciliation contract holds on EVERY window (sum == wall
# within tol; only overshoot/double-counting can break it)
for w in wins:
    assert w["reconciliation"]["ok"], w["reconciliation"]
stalled = [w for w in wins[5:] if w["steps"]]
assert stalled, "no stalled windows closed"
last = stalled[-1]
assert last["verdict"]["bound"] == "input", last["verdict"]
assert last["categories"]["input_wait"]["share"] > 0.5, \
    last["categories"]
# the sentinel NAMED the category that moved
regs = telemetry.event("goodput.regression").recent
assert any(r["category"] == "input_wait" for r in regs), regs
assert telemetry.counter("goodput.env_degraded_windows").value == 0
print("obs goodput gate ok: %d windows reconciled, verdict=%r, "
      "sentinel named input_wait"
      % (len(wins), last["verdict"]["detail"]))
EOF
    rm -rf "$obsdir"
}

run_fleet() {
    log "fleet: 2-replica kill-mid-flood -> replica_down fires -> relaunch resolves (seed 0)"
    fdir=$(mktemp -d /tmp/mxtpu_fleet_ci.XXXXXX)
    cat > "$fdir/replica.py" <<'EOF'
import os, sys, time
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import chaos, gluon, obs, telemetry

workdir = sys.argv[1]
rank = int(os.environ.get("MXNET_TPU_PROC_ID", "0"))
gen = int(os.environ.get("MXNET_TPU_GENERATION", "0"))
telemetry.enable()
chaos.arm_from_spec()            # the kill rule is rank-1 gen-0 scoped

net = gluon.nn.Dense(4)
net.initialize(); net.hybridize()
net(mx.nd.array(np.zeros((1, 8), np.float32)))
reg = mx.serving.ModelRegistry()
s = reg.register("mlp", block=net, input_shape=(8,),
                 buckets=(1, 2, 4), max_wait_ms=20, max_queue=256)
port = obs.serve(0)              # publishes r<rank>.<pid>.json
print("SERVING rank=%d gen=%d port=%d" % (rank, gen, port), flush=True)

if rank == 1 and gen == 0:
    # flood only after rank 0 drained: the chaos kill then lands in a
    # window where rank 0's zero-drop accounting is already banked
    deadline = time.time() + 120
    while not os.path.exists(workdir + "/rank0_done"):
        time.sleep(0.05)
        assert time.time() < deadline, "rank0_done never appeared"

sample = np.random.RandomState(0).rand(8).astype(np.float32)
futs = [s.submit(sample, timeout=30) for _ in range(40)]
for f in futs:                   # every ACCEPTED request must answer
    assert f.result(timeout=30) is not None
print("FLOOD_OK rank=%d gen=%d dropped=0" % (rank, gen), flush=True)
if rank == 0 and gen == 0:
    open(workdir + "/rank0_done", "w").close()
# park until the harness says stop; gen-0 survivors instead die by the
# supervisor's kill-tree when the chaos kill triggers the relaunch
deadline = time.time() + 300
while not os.path.exists(workdir + "/stop"):
    time.sleep(0.1)
    assert time.time() < deadline, "stop never appeared"
reg.shutdown(drain=True)
obs.server.stop()                # withdraws the endpoint file
print("CLEAN_EXIT rank=%d gen=%d" % (rank, gen), flush=True)
EOF
    JAX_PLATFORMS=cpu PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}" \
        python - "$fdir" <<'EOF' | tee "$fdir/out.log"
import json, os, subprocess, sys, threading, time
os.environ.setdefault("JAX_PLATFORMS", "cpu")
from mxnet_tpu import chaos
from mxnet_tpu.obs.fleet import FleetMonitor
from mxnet_tpu.supervisor import Supervisor

workdir = sys.argv[1]
eps = os.path.join(workdir, "eps")
spec = chaos.make_spec(seed=0, rules=[
    {"point": "serving.dispatch", "action": "kill", "nth": 5,
     "rank": 1, "generation": 0}])
env = dict(os.environ, JAX_PLATFORMS="cpu", MXNET_TPU_TELEMETRY="1",
           MXNET_TPU_CHAOS_SPEC=spec)
sup = Supervisor([sys.executable, "-u", workdir + "/replica.py",
                  workdir], 2, max_restarts=2, grace_s=3,
                 env=env, endpoints_dir=eps)
rc = []
th = threading.Thread(target=lambda: rc.append(sup.run()), daemon=True)
th.start()

mon = FleetMonitor(eps, scrape_ms=100, ttl_s=5.0, timeout_s=2.0,
                   retries=0)
deadline = time.time() + 240
# phase 1: the chaos kill must FIRE replica_down naming rank+gen
fired = None
while time.time() < deadline and fired is None:
    mon.poll_once()
    for a in mon.engine.firing():
        if a.rule == "replica_down" and "rank 1" in a.reason:
            fired = a
    time.sleep(0.1)
assert fired is not None, "replica_down never fired for rank 1"
assert "generation 0" in fired.reason, fired.reason
print("FLEET_FIRED: %s" % fired.reason, flush=True)
# phase 2: the supervisor relaunch must RESOLVE it
while time.time() < deadline and mon.engine.firing():
    mon.poll_once()
    time.sleep(0.1)
assert not mon.engine.firing(), \
    "still firing after relaunch: %r" % mon.engine.firing()
assert any(h["rule"] == "replica_down" and h["state"] == "resolved"
           for h in mon.engine.history()), mon.engine.history()
agg = mon.last["aggregate"]
assert agg["up"] == 2 and agg["down"] == 0, agg
gens = {r["rank"]: r["generation"] for r in mon.last["replicas"]}
assert gens == {0: 1, 1: 1}, gens
mon.close()
print("FLEET_RESOLVED: generation 1 up on both ranks", flush=True)
# gate the CLI exit-code contract both ways: 0 on the healthy
# relaunched fleet...
cp = subprocess.run([sys.executable, "-m", "mxnet_tpu.telemetry",
                     "fleet", eps, "--rounds", "2",
                     "--interval-ms", "100"],
                    env=env, capture_output=True, text=True)
sys.stdout.write(cp.stdout)
assert cp.returncode == 0, (cp.returncode, cp.stdout, cp.stderr)
print("FLEET_CLI_HEALTHY_EXIT_0", flush=True)
open(os.path.join(workdir, "stop"), "w").close()
th.join(timeout=120)
assert rc and rc[0] == 0, "supervisor rc %r" % (rc,)
# ...and 1 once every endpoint is withdrawn (nothing scrapeable)
cp = subprocess.run([sys.executable, "-m", "mxnet_tpu.telemetry",
                     "fleet", eps],
                    env=env, capture_output=True, text=True)
assert cp.returncode == 1, (cp.returncode, cp.stdout, cp.stderr)
print("FLEET_CLI_EMPTY_EXIT_1", flush=True)
print("FLEET_STAGE_OK", flush=True)
EOF
    # the gates, re-checked off the transcript: zero-drop floods on
    # every drained replica, the fire->resolve arc, both CLI exits
    grep -q "FLOOD_OK rank=0 gen=0 dropped=0" "$fdir/out.log"
    grep -q "FLOOD_OK rank=0 gen=1 dropped=0" "$fdir/out.log"
    grep -q "FLOOD_OK rank=1 gen=1 dropped=0" "$fdir/out.log"
    grep -q "FLEET_FIRED:.*rank 1 generation 0" "$fdir/out.log"
    grep -q "relaunching generation 1" "$fdir/out.log"
    grep -q "FLEET_RESOLVED" "$fdir/out.log"
    [ "$(grep -c "CLEAN_EXIT" "$fdir/out.log")" -eq 2 ]
    grep -q "FLEET_CLI_HEALTHY_EXIT_0" "$fdir/out.log"
    grep -q "FLEET_CLI_EMPTY_EXIT_1" "$fdir/out.log"
    grep -q "FLEET_STAGE_OK" "$fdir/out.log"
    rm -rf "$fdir"
}

run_bench() {
    log "bench: harness self-check (no device time)"
    python - <<'EOF'
import bench
# the driver contract: main exists, headline fns are callable, and the
# budget machinery is wired
assert callable(bench.main)
assert callable(bench.bench_resnet50_scan)
assert callable(bench.bench_bert_base)
assert bench._BUDGET_S > 0
print("bench harness ok")
EOF
}

run_wheel() {
    log "wheel: build + clean-target install + import smoke"
    rm -rf dist
    # --no-isolation: this environment has zero egress; setuptools
    # comes from the ambient site-packages
    python -m build --wheel --no-isolation --outdir dist >/dev/null
    whl=$(ls dist/*.whl)
    # clean-target install (a nested venv cannot see this venv's
    # site-packages for jax/numpy); run OUTSIDE the repo dir so the
    # installed wheel, not the source tree, is what imports
    target=$(mktemp -d /tmp/mxtpu_wheel_ci.XXXXXX)
    python -m pip install --no-deps -q --target "$target" "$whl"
    (cd /tmp && PYTHONPATH="$target:${PYTHONPATH:-}" python - <<'EOF'
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon
assert mx.nd.ones((2, 2)).asnumpy().sum() == 4.0
net = gluon.nn.Dense(3)
net.initialize()
x = mx.nd.array(np.ones((2, 4), np.float32))
with autograd.record():
    y = net(x).sum()
y.backward()
import mxnet_tpu
assert "mxtpu_wheel_ci" in mxnet_tpu.__file__, mxnet_tpu.__file__
print("wheel import + train smoke ok:", mxnet_tpu.__file__)
EOF
    )
    rm -rf "$target"
}

for s in "${stages[@]}"; do
    "run_$s"
done
log "ALL STAGES GREEN"
