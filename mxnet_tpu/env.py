"""Typed registry of every ``MXNET_*`` environment variable the
framework reads (reference: ``docs/static_site/src/pages/api/faq/
env_var.md`` -- the reference documents its env vars on one page; here
the page is generated from this registry, so it cannot go stale).

Use ``mx.env.describe()`` for the rendered table, ``mx.env.get(name)``
for a typed read, and ``mx.env.generate_doc(path)`` to (re)write
``docs/env_vars.md``.
"""
from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Any, Callable

from .base import MXNetError

__all__ = ["EnvVar", "REGISTRY", "get", "describe", "generate_doc"]


@dataclass(frozen=True)
class EnvVar:
    name: str
    type: Callable
    default: Any
    doc: str

    def read(self):
        raw = os.environ.get(self.name)
        if raw is None:
            return self.default
        try:
            if self.type is bool:
                # match the package's actual read convention: every
                # boolean site tests != "0" (see e.g. ndarray.py's
                # MXNET_TPU_EAGER_JIT), so only "0" disables
                return raw != "0"
            return self.type(raw)
        except (TypeError, ValueError) as e:
            raise MXNetError("env var %s=%r is not a valid %s"
                             % (self.name, raw, self.type.__name__)) from e


_VARS = [
    EnvVar("MXNET_ENGINE_TYPE", str, "",
           "Set to 'NaiveEngine' to make every op dispatch block until "
           "the result is ready (reference semantics: synchronous debug "
           "engine).  Default: async XLA dispatch."),
    EnvVar("MXNET_TPU_EAGER_JIT", bool, True,
           "Per-op persistent jit cache for eager NDArray ops.  '0' "
           "falls back to uncached dispatch (debugging)."),
    EnvVar("MXNET_TPU_NATIVE", bool, True,
           "Build/load the native C++ components (recordio engine, "
           "predict runtime).  '0' forces the pure-Python paths."),
    EnvVar("MXNET_TPU_NATIVE_CACHE", str, "",
           "Directory where on-demand native builds are cached.  "
           "Unset: .mxnet_tpu_cache/native inside the checkout."),
    EnvVar("MXNET_OPTIMIZER_AGGREGATION_SIZE", int, 60,
           "Max tensors fused into one multi-tensor optimizer update "
           "(reference: same knob)."),
    EnvVar("MXNET_PROFILER_AUTOSTART", bool, False,
           "'1' starts the profiler at import (reference: same knob)."),
    EnvVar("MXNET_TPU_COORDINATOR", str, "",
           "host:port of the jax.distributed coordination service; set "
           "by tools/launch.py for multi-process jobs."),
    EnvVar("MXNET_TPU_NUM_PROCS", int, 1,
           "World size of the multi-process job (set by the launcher)."),
    EnvVar("MXNET_TPU_PROC_ID", int, 0,
           "This process's rank (set by the launcher)."),
    EnvVar("MXNET_CHECKPOINT_ON_SIGTERM", str, "",
           "Checkpoint prefix used by mx.preemption.install() when no "
           "prefix argument is given: SIGTERM drains pending work and "
           "writes <prefix>-preempt.params/.states/.meta before exit."),
    EnvVar("MXNET_TPU_EAGER_BULK", bool, True,
           "Bulked eager dispatch: queue eager ops and replay the whole "
           "pending region as ONE jitted program at the next sync point "
           "(the reference's MXNET_EXEC_BULK_EXEC_TRAIN analog).  '0' "
           "dispatches each eager op individually."),
    EnvVar("MXNET_TPU_BENCH_BUDGET_S", float, 1500.0,
           "Wall-clock budget (seconds) for bench.py: headline metrics "
           "emit first, and optional configs that would exceed the "
           "budget print a skipped line instead of running (so the "
           "bench can never outlive the driver's timeout)."),
    EnvVar("MXNET_TPU_GRAPH_CHECK", bool, False,
           "'1' runs the static graph checker (mxnet_tpu.analysis) on "
           "every Executor bind/simple_bind, raising GraphCheckError "
           "with every problem at once (unknown ops, dangling or "
           "duplicate inputs, shape contradictions) before any device "
           "time is spent.  Per-bind override: bind(..., check=True)."),
    EnvVar("MXNET_TPU_TELEMETRY", bool, False,
           "'1' enables the runtime telemetry subsystem (mx.telemetry) "
           "at import: counters/timers/events over op dispatch, "
           "compile caches, trainer steps, kvstore traffic, the input "
           "pipeline, AMP, and preemption checkpoints.  Off (the "
           "default), every hook is a single module-flag check with "
           "zero instrument calls.  Runtime toggle: "
           "mx.telemetry.enable()/disable()."),
    EnvVar("MXNET_TPU_TELEMETRY_JSONL", str, "",
           "Path of the telemetry JSONL run log.  When set, a JSONL "
           "sink is attached at import (events and timer samples "
           "stream; the aggregate snapshot lands at exit or "
           "mx.telemetry.flush()) -- analyze offline with 'python -m "
           "mxnet_tpu.telemetry summarize <path>'.  Implies nothing "
           "about MXNET_TPU_TELEMETRY: set both to record."),
    EnvVar("MXNET_TPU_CKPT_ASYNC", bool, False,
           "'1' makes CheckpointManager saves asynchronous by default: "
           "params/optimizer state snapshot to host at save() (after a "
           "waitall drain), then serialize/fsync/commit on a background "
           "thread so training overlaps the I/O.  At most one save is "
           "in flight; writer errors re-raise at the next save/wait.  "
           "Per-manager override: CheckpointManager(async_save=...)."),
    EnvVar("MXNET_TPU_CKPT_MAX_TO_KEEP", int, 0,
           "Default retention for CheckpointManager: keep at most this "
           "many step checkpoints, deleting the oldest after each save "
           "(steps matching keep_every_n_steps are exempt).  0 keeps "
           "everything.  Per-manager override: "
           "CheckpointManager(max_to_keep=...)."),
    EnvVar("MXNET_TPU_FEED_DEPTH", int, 2,
           "Default bounded-queue depth of mx.dataio.DeviceFeed: how "
           "many staged (device-resident) batches the background "
           "producer may run ahead of the consumer.  2 = classic "
           "double buffering; raise it when per-batch producer time is "
           "bursty (decode spikes).  Per-feed override: "
           "DeviceFeed(depth=...)."),
    EnvVar("MXNET_TPU_FEED_COMPACT", bool, True,
           "Ship feed batches host->device in their compact source "
           "dtype (uint8 stays uint8 -- 4x less wire traffic than its "
           "float32 cast) and expand on device via the feed's jitted "
           "transform.  '0' pre-casts host-side to the transform's "
           "target dtype before staging (A/B numerics debugging).  "
           "Per-feed override: DeviceFeed(compact=...)."),
    EnvVar("MXNET_TPU_TSAN", bool, False,
           "'1' arms the concurrency sanitizer (mxnet_tpu.sync): every "
           "Lock/RLock/Condition/Event the framework creates records "
           "per-thread acquisition stacks, maintains the lock-order "
           "graph (seeded from the static analysis pass) and raises "
           "LockOrderError on an A/B-B/A inversion, and time-bounds "
           "every untimed blocking acquisition/wait with a deadlock "
           "watchdog that dumps all thread stacks.  Off (the default), "
           "the factories return raw threading primitives -- zero "
           "overhead.  CI runs the threaded test files under this flag "
           "(ci/run_all.sh tsan)."),
    EnvVar("MXNET_TPU_TSAN_WATCHDOG_S", float, 20.0,
           "Deadlock-watchdog budget (seconds) for untimed lock "
           "acquisitions and Condition/Event waits under "
           "MXNET_TPU_TSAN=1.  On expiry the sanitizer raises "
           "DeadlockError carrying every thread's stack plus the "
           "held-locks table (who holds what, acquired where)."),
    EnvVar("MXNET_TPU_PROFILING", bool, False,
           "'1' enables compiled-step cost accounting (mx.profiling) "
           "at import: every compiled executable (eager-jit cache, "
           "hybridize cache, Executor, TrainStep) is captured for "
           "lazy XLA cost/memory analysis with a per-HLO-category "
           "breakdown, and TrainStep dispatch walls feed the "
           "roofline.  Off (the default), every hook is a single module-flag "
           "check.  Runtime toggle: mx.profiling.enable()/disable(); "
           "render with the mxprof CLI."),
    EnvVar("MXNET_TPU_PROFILING_DIR", str, "",
           "Directory for mx.profiling CostReport artifacts.  When "
           "set (with profiling enabled), per-executable *.cost.json "
           "files plus the combined report.json are written at "
           "interpreter exit (and by mx.profiling.save_reports()); "
           "'mxprof report'/'mxprof diff' consume them.  Unset: "
           "nothing auto-persists; save_reports(dir) still works."),
    EnvVar("MXNET_TPU_SHARD_CHECK", bool, False,
           "'1' arms the sharding sanitizer's compiled layer "
           "(mxnet_tpu.analysis.sharding): every compiled executable "
           "is registered (via the mx.profiling capture surface, which "
           "this flag also enables) so analysis.sharding."
           "collective_contract()/save_contract() can extract per-"
           "executable GSPMD collective counts/bytes, and CI's "
           "shardlint stage can diff them against the committed "
           "ci/sharding_baseline.json -- failing, with the executable "
           "and collective kind named, when a mismatched PartitionSpec "
           "turns into resharding all-gathers."),
    EnvVar("MXNET_TPU_TRANSFER_GUARD", str, "",
           "When set, applied to jax's transfer guard at import "
           "(jax.config jax_transfer_guard): one of allow | log | "
           "disallow | log_explicit | disallow_explicit.  'disallow' "
           "makes IMPLICIT host<->device transfers inside the step "
           "(a Python scalar leaking into dispatch, an un-placed index "
           "array) raise instead of silently stalling the pipeline; "
           "explicit device_put/staging keeps working.  Scoped "
           "version: analysis.sharding.transfer_guard(mode)."),
    EnvVar("MXNET_TPU_SERVING_BUCKETS", str, "1,2,4,8,16,32",
           "Default padded batch buckets (comma-separated ascending "
           "batch sizes) for mx.serving servables: a micro-batch of n "
           "requests pads to the smallest bucket >= n, and every "
           "bucket's executable is AOT-compiled and warmed at "
           "registration.  Per-servable override: "
           "ModelRegistry.register(buckets=...)."),
    EnvVar("MXNET_TPU_SERVING_MAX_WAIT_MS", float, 5.0,
           "Micro-batch assembly deadline (milliseconds) for the "
           "mx.serving dynamic batcher: a batch dispatches as soon as "
           "the largest bucket fills OR the oldest queued request has "
           "waited this long.  Lower = tighter tail latency, higher = "
           "better occupancy.  Per-servable override: "
           "ModelRegistry.register(max_wait_ms=...)."),
    EnvVar("MXNET_TPU_SERVING_QUEUE", int, 256,
           "Bounded request-queue depth per mx.serving servable.  A "
           "submit against a full queue raises ServingQueueFull "
           "(counted in serving.shed) instead of growing latency "
           "without bound -- the load-shedding/backpressure contract.  "
           "Per-servable override: ModelRegistry.register("
           "max_queue=...)."),
    EnvVar("MXNET_TPU_SERVING_KV_BLOCK", int, 16,
           "Tokens per KV-cache block in the generative decode tier "
           "(mx.serving.decode).  Smaller blocks waste less memory on "
           "partial tails (internal fragmentation is at worst one "
           "block per sequence) but widen block tables; larger blocks "
           "amortize table walks.  Per-model override: "
           "register_generative(block_size=...)."),
    EnvVar("MXNET_TPU_SERVING_KV_BLOCKS", int, 512,
           "Total preallocated KV-cache blocks per generative "
           "servable (block 0 is a reserved scratch block for padded "
           "slots).  Together with MXNET_TPU_SERVING_KV_BLOCK this is "
           "the serving memory budget: admission sheds "
           "(ServingQueueFull, kvcache.alloc_failures) when a "
           "request's whole prompt+max_new budget cannot be covered.  "
           "Per-model override: register_generative(num_blocks=...)."),
    EnvVar("MXNET_TPU_SERVING_DECODE_BUCKETS", str, "1,2,4,8",
           "Slot-count buckets for the continuous-batching decode "
           "step: each compiles one AOT executable at registration, "
           "live sequences pad to the smallest bucket that fits, and "
           "the largest bucket bounds concurrent sequences.  "
           "Per-model override: register_generative("
           "decode_buckets=...)."),
    EnvVar("MXNET_TPU_SERVING_PREFILL_BUCKETS", str, "16,32,64,128",
           "Prompt-length buckets for generative prefill: a prompt "
           "pads to the smallest bucket >= its length (largest bucket "
           "= longest admissible prompt), one warmed executable per "
           "bucket.  Per-model override: register_generative("
           "prefill_buckets=...)."),
    EnvVar("MXNET_TPU_SERVING_CACHE_DIR", str, "",
           "Directory of the persistent serving compile cache "
           "(unset: .mxnet_tpu_cache/serving inside the checkout): "
           "per-bucket servable programs serialized via jax.export, "
           "keyed on the normalized-StableHLO fingerprint, so a new "
           "serving process warms registration from disk.  Disable "
           "per-registry with ModelRegistry(compile_cache=False)."),
    EnvVar("MXNET_TPU_SERVING_PREDICTOR_CACHE", int, 8,
           "LRU bound on mx.Predictor's per-input-shape jit cache: at "
           "most this many compiled shape classes stay resident; the "
           "least-recently-used program is dropped beyond it (counted "
           "in serving.compile_evictions).  Per-predictor override: "
           "Predictor(jit_cache_size=...)."),
    EnvVar("MXNET_TPU_PERF_AUDIT_TOL", float, 0.02,
           "Absolute growth tolerance for the perf auditor's share "
           "metrics (transpose share, unfused-elementwise share, MXU "
           "pad waste) when diffing a perf audit against the blessed "
           "ci/perf_baseline.json (mxlint --perf-diff / "
           "analysis.perf.diff_audit).  A metric grown past baseline + "
           "tolerance errors naming the executable; improvements pass "
           "(docs/perf_lint.md)."),
    EnvVar("MXNET_TPU_NUMERICS_CHECK", bool, False,
           "'1' arms the non-finite sentinel "
           "(analysis.numerics.finite_sentinel): TrainStep and "
           "ContinuousTrainer fold ONE fused isfinite-reduction over "
           "the dtype-bucketed gradients into each step (one boolean, "
           "one device_get) and on the first non-finite step run an "
           "attribution pass naming WHICH parameter went NaN/Inf, "
           "raising NonFiniteError(param, step, kind) with the weights "
           "still at their pre-step values.  '0' (default): one "
           "module-flag check, zero per-step work (docs/numerics.md)."),
    EnvVar("MXNET_TPU_NUMERICS_AUDIT_TOL", float, 0.02,
           "Absolute growth tolerance for the numerics auditor's share "
           "metrics (half-accumulated dot/conv bytes, convert-storm "
           "bytes, all-half reductions) when diffing against the "
           "blessed ci/numerics_baseline.json (mxlint --numerics-diff "
           "/ analysis.numerics.diff_audit).  A metric grown past "
           "baseline + tolerance errors naming the executable; "
           "improvements pass (docs/numerics.md)."),
    EnvVar("MXNET_TPU_MEMORY_WATCH", bool, False,
           "'1' arms the live-buffer leak sentinel "
           "(analysis.memory.LeakSentinel): ContinuousTrainer censuses "
           "jax.live_arrays() at every goodput-window boundary "
           "(memory.live_bytes / memory.live_arrays gauges) and flags "
           "monotonic live-bytes growth past the EWMA+MAD baseline, "
           "naming the top-growing shape/dtype bucket -- "
           "publish-guarded, so checkpoint snapshot spikes never "
           "flag.  '0' (default): one module-flag check, zero "
           "per-step work (docs/memory.md)."),
    EnvVar("MXNET_TPU_MEMORY_AUDIT_TOL", float, 0.02,
           "Relative growth tolerance for peak_hbm_bytes when diffing "
           "a memory audit against the blessed ci/memory_baseline.json "
           "(mxlint --memory-diff / analysis.memory.diff_audit).  An "
           "executable whose peak HBM grew past baseline x (1 + tol) "
           "errors naming it; shrinkage passes (docs/memory.md)."),
    EnvVar("MXNET_TPU_CKPT_QUARANTINE", bool, True,
           "Checkpoint discovery quarantine: a step that fails "
           "manifest/CRC verification during "
           "CheckpointManager.latest_step() is renamed "
           "step_<N>.corrupt (counted in checkpoint.quarantined) "
           "instead of silently skipped, so rollbacks are visible to "
           "operators and the torn bytes survive as evidence.  '0' "
           "restores skip-only discovery.  Per-manager override: "
           "CheckpointManager(quarantine=...)."),
    EnvVar("MXNET_TPU_CKPT_WRITE_RETRIES", int, 2,
           "How many times the async checkpoint writer retries a "
           "failed background write (exponential backoff from "
           "MXNET_TPU_CKPT_RETRY_BACKOFF_S) before surfacing the "
           "error through the checkpoint.write_failed telemetry event "
           "and the next save()/wait_until_finished().  0 disables "
           "retries.  Per-writer override: AsyncWriter(retries=...)."),
    EnvVar("MXNET_TPU_CKPT_RETRY_BACKOFF_S", float, 0.25,
           "Initial backoff (seconds) between async checkpoint write "
           "retries; doubles per attempt."),
    EnvVar("MXNET_TPU_CHAOS_SEED", int, 0,
           "Default seed for mx.chaos.arm(): per-rule probability "
           "streams derive from (seed, fail point, rule index), so a "
           "chaos scenario replays identically for a fixed seed.  "
           "Chaos is only ever armed programmatically "
           "(chaos.arm()/chaos.scenario()); no env var can arm fail "
           "points in a production process."),
    EnvVar("MXNET_TPU_SERVING_POLL_S", float, 0.5,
           "RegistryWatcher poll interval (seconds): how often the "
           "checkpoint root is scanned for a newer verified step to "
           "hot-swap into the servable.  Per-watcher override: "
           "RegistryWatcher(poll_s=...)."),
    EnvVar("MXNET_TPU_SERVING_SWAP_RETRIES", int, 2,
           "How many times a RegistryWatcher retries an aborted "
           "hot-swap (exponential backoff from "
           "MXNET_TPU_SERVING_SWAP_BACKOFF_S) before marking the step "
           "bad and keeping the previous model in service.  "
           "Per-watcher override: RegistryWatcher(swap_retries=...)."),
    EnvVar("MXNET_TPU_SERVING_SWAP_BACKOFF_S", float, 0.25,
           "Initial backoff (seconds) between hot-swap retries; "
           "doubles per attempt."),
    EnvVar("MXNET_TPU_SERVING_SWAP_BUDGET", int, 3,
           "RegistryWatcher failure budget: after this many "
           "CONSECUTIVE steps fail to swap (each already retried), "
           "the watcher suspends itself with a warning instead of "
           "flapping -- the last good model keeps serving until an "
           "operator intervenes.  Per-watcher override: "
           "RegistryWatcher(failure_budget=...)."),
    EnvVar("MXNET_TPU_OBS_TRACE", bool, False,
           "'1' arms request/step tracing (mx.obs): context-propagated "
           "trace/span IDs through the serving path (submit -> queue "
           "wait -> batch assembly -> compiled dispatch -> device_get "
           "-> respond, batcher fan-in as span links) and the training "
           "loop (step -> publish -> checkpoint commit -> watcher "
           "discover -> warm -> install), streamed into the telemetry "
           "JSONL as span records and exportable as Chrome-trace JSON "
           "(obs.export_chrome_trace).  Off (the default), every "
           "traced site is a single module-flag check with zero trace "
           "calls.  Runtime toggle: obs.enable_tracing()/"
           "disable_tracing()."),
    EnvVar("MXNET_TPU_OBS_BLACKBOX", str, "",
           "Path of the crash-safe flight recorder (mx.obs.flight).  "
           "When set, an mmap'd ring of the most recent telemetry "
           "records/spans is installed at import and survives "
           "os._exit/SIGKILL; it is marked+msync'd automatically from "
           "the preemption handler, the chaos KILL path, and SIGUSR2 "
           "(which also snapshots every thread's stack).  Render with "
           "'mxtelemetry blackbox <path>'."),
    EnvVar("MXNET_TPU_OBS_BLACKBOX_KB", int, 256,
           "Flight-recorder ring capacity in KiB (the final-seconds "
           "window an operator gets after a crash).  Per-recorder "
           "override: obs.install_blackbox(capacity=...)."),
    EnvVar("MXNET_TPU_OBS_PORT", int, 0,
           "Port of the live-introspection HTTP server (mx.obs."
           "server, localhost): /healthz (watcher failure budget + "
           "async-writer failures + queue saturation -> READY/"
           "NOT_READY), /metrics (Prometheus exposition of the live "
           "registry), /statusz (served/published step, swap history, "
           "bucket occupancy, per-rank heartbeats).  0 (default) = "
           "not started; obs.serve(0) binds an ephemeral port."),
    EnvVar("MXNET_TPU_OBS_GOODPUT", bool, False,
           "'1' arms the goodput ledger (mx.obs.goodput): the "
           "ContinuousTrainer loop ticks a per-process StepLedger that "
           "decomposes every rolling window of training steps into "
           "device_compute / input_wait / host_sync / checkpoint_stall "
           "/ recompile / other (reconciled to window wall within "
           "MXNET_TPU_OBS_GOODPUT_TOL), publishes a rolling MFU gauge, "
           "and runs the EWMA+MAD regression sentinel (goodput.* "
           "instruments, /statusz goodput section).  Needs "
           "MXNET_TPU_TELEMETRY=1 for non-empty attribution.  Off "
           "(default): one module-flag check per loop step.  Runtime "
           "toggle: obs.enable_goodput()/disable_goodput()."),
    EnvVar("MXNET_TPU_OBS_GOODPUT_WINDOW", int, 20,
           "Training steps per goodput-ledger window: the attribution "
           "granularity AND the sentinel's sample size.  Smaller = "
           "faster regression detection, noisier baselines.  "
           "Per-ledger override: StepLedger(window_steps=...)."),
    EnvVar("MXNET_TPU_OBS_GOODPUT_TOL", float, 0.25,
           "Reconciliation tolerance of the goodput ledger: the "
           "attributed categories may exceed the window wall by at "
           "most this fraction before the window's reconciliation "
           "contract reads failed ('other' absorbs undershoot, so "
           "only overshoot -- double counting -- can violate it).  "
           "CI gates ok on every window (ci/run_all.sh obs)."),
    EnvVar("MXNET_TPU_OBS_GOODPUT_MAD_K", float, 4.0,
           "Regression-sentinel sensitivity: a category regresses when "
           "its per-step seconds exceed EWMA mean + this many EWMA "
           "absolute deviations (and the move is at least 5% of the "
           "window wall).  Per-ledger override: "
           "StepLedger(mad_k=...)."),
    EnvVar("MXNET_TPU_CHAOS_SPEC", str, "",
           "Serialized chaos scenario (chaos.make_spec() JSON: seed + "
           "rules with per-rank/per-generation scoping) for launched "
           "multi-process test harnesses.  NEVER arms anything by "
           "itself: a worker replays it only by explicitly calling "
           "chaos.arm_from_spec(), so production processes stay inert "
           "with the variable present (the env-inert contract of "
           "chaos.arm())."),
    EnvVar("MXNET_TPU_GENERATION", int, 0,
           "Supervisor generation id of this worker world, bumped by "
           "the elastic restart supervisor (tools/launch.py "
           "--supervise) on every relaunch.  Namespaces every "
           "coordination-KV key (barriers, collectives, liveness "
           "leases), and the new generation's first rendezvous sweeps "
           "the previous generation's keys."),
    EnvVar("MXNET_TPU_DIST_BARRIER_TIMEOUT_MS", int, 60000,
           "Default bound on every attributed barrier rendezvous "
           "(distributed.barrier and the sharded-checkpoint commit "
           "gates).  On expiry survivors raise a typed BarrierTimeout "
           "naming the missing rank(s) -- never a raw jaxlib "
           "DEADLINE_EXCEEDED.  Per-call override: "
           "barrier(timeout_ms=...)."),
    EnvVar("MXNET_TPU_DIST_LEASE_TTL_S", float, 10.0,
           "Liveness-lease staleness bound: a rank whose "
           "mxlive/g<gen>/<rank> coordination key is older than this "
           "(or absent) is reported 'presumed dead' in "
           "BarrierTimeout/RankFailure attribution.  The training "
           "loop beats the lease every step; every barrier entry "
           "refreshes it too."),
    EnvVar("MXNET_TPU_DIST_KV_RETRIES", int, 2,
           "Bounded retries (doubling backoff from 50 ms) for "
           "TRANSIENT coordination-KV errors in host collectives and "
           "barriers.  Deadline expiries are not transient -- they "
           "attribute a missing peer and raise typed errors "
           "immediately.  0 disables retries."),
    EnvVar("MXNET_TPU_SUPERVISOR_RESTARTS", int, 3,
           "Elastic-restart budget: how many times the supervisor "
           "(tools/launch.py --supervise / mxnet_tpu.supervisor) "
           "relaunches the world after a rank death before going "
           "terminal (supervisor.exhausted event, /healthz NOT_READY)."
           "  Per-supervisor override: Supervisor(max_restarts=...)."),
    EnvVar("MXNET_TPU_SUPERVISOR_GRACE_S", float, 15.0,
           "After the first rank exit of a generation, how long the "
           "supervisor waits for the survivors to notice (typed "
           "BarrierTimeout) and exit on their own before killing the "
           "process tree.  Set it above "
           "MXNET_TPU_DIST_BARRIER_TIMEOUT_MS so survivor logs carry "
           "the attributed error."),
    EnvVar("MXNET_TPU_EAGER_BULK_MAX", int, 512,
           "Capacity flush threshold for the bulked eager queue: a "
           "pending region is flushed once it reaches this many ops, "
           "bounding host memory for loops that never sync (reference: "
           "MXNET_EXEC_BULK_EXEC_MAX_NODE_TRAIN)."),
    EnvVar("MXNET_TPU_OBS_ENDPOINTS_DIR", str, "",
           "Fleet endpoint-discovery directory (obs.fleet): every obs "
           "server atomically publishes its {pid, rank, generation, "
           "port} there on serve() and a FleetMonitor discovers the "
           "replica set from it.  The supervisor threads this into "
           "every launched world so relaunched generations "
           "re-register automatically.  Empty (default) disables "
           "publication."),
    EnvVar("MXNET_TPU_OBS_SCRAPE_MS", float, 1000.0,
           "FleetMonitor scrape interval in milliseconds.  The "
           "presumed-down TTL defaults to 3x this, so a replica that "
           "stops answering is declared down within ~3 scrape "
           "rounds."),
    EnvVar("MXNET_TPU_OBS_ALERT_RULES", str, "",
           "JSON list of SLO alert-rule overrides merged onto the "
           "stock rules by name (obs.alerts.parse_rules): e.g. "
           "'[{\"name\": \"p99_latency_ms\", \"threshold\": 250}]'.  "
           "Unparseable specs raise loudly -- a silently-ignored "
           "alert config is the worst failure mode an alerting plane "
           "can have."),
]

REGISTRY = {v.name: v for v in _VARS}


def get(name):
    """Typed read of a registered env var (raises for unknown names, so
    typos fail loudly instead of silently defaulting)."""
    if name not in REGISTRY:
        raise MXNetError("unknown env var %r; registered: %s"
                         % (name, ", ".join(sorted(REGISTRY))))
    return REGISTRY[name].read()


def describe():
    """{name: (current_value, default, doc)} for every registered var."""
    return {v.name: (v.read(), v.default, v.doc) for v in _VARS}


def generate_doc(path=None):
    """Render the env-var reference page (reference: env_var.md)."""
    lines = ["# Environment variables",
             "",
             "Generated from `mxnet_tpu/env.py` -- the registry the "
             "framework actually reads, so this page cannot go stale.",
             "",
             "| Variable | Type | Default | Description |",
             "|---|---|---|---|"]
    for v in _VARS:
        lines.append("| `%s` | %s | `%r` | %s |"
                     % (v.name, v.type.__name__, v.default, v.doc))
    text = "\n".join(lines) + "\n"
    if path:
        with open(path, "w") as f:
            f.write(text)
    return text
