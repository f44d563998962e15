"""Benchmark harness (driver contract + BASELINE.md configs).

Measures steady-state training throughput on the available accelerator
(the one real TPU chip under the driver; CPU otherwise):

- config 1: LeNet-style convnet, MNIST shapes, hybridized Gluon
- config 2: ResNet-50 v1, synthetic ImageNet batches (the headline)

Each config times the FULL training step (forward + loss + backward +
optimizer update) as one compiled program (``mxnet_tpu.parallel.TrainStep``)
with device-resident synthetic data, after warmup.  Reference analog:
``example/image-classification/common/fit.py :: Speedometer`` samples/sec.

Prints one progress JSON object per config, then the final parseable line:
``{"metric": ..., "value": ..., "unit": ..., "vs_baseline": ...}``.
vs_baseline denominator: BASELINE.md's A100 anchor for MXNet-CUDA
ResNet-50 (~3000 img/s with DALI+AMP; unverified memory anchor).
"""
import json
import os as _os
import statistics
import time

import numpy as np

# Self-budget: the bench must NEVER outlive the
# driver's time allowance again.  Headline metrics emit first; every
# optional config is gated on the remaining budget and prints a
# {"skipped": ...} line instead of dying at rc=124.
_T_START = time.monotonic()
_BUDGET_S = float(_os.environ.get("MXNET_TPU_BENCH_BUDGET_S", "1500"))


def _remaining():
    return _BUDGET_S - (time.monotonic() - _T_START)


def _budget_ok(metric, est_s):
    """True if ``est_s`` seconds still fit the budget; else emits the
    skip line for ``metric`` and returns False."""
    if _remaining() < est_s:
        print(json.dumps({"metric": metric, "skipped": True,
                          "reason": "bench budget: %.0fs remaining < "
                                    "%.0fs estimate"
                                    % (max(_remaining(), 0), est_s)}))
        return False
    return True


def _ctx():
    import mxnet_tpu as mx
    return mx.tpu() if mx.num_tpus() else mx.cpu()


# CostReport artifact paths by tag, filled by the bench fns and read by
# main()'s extra_fn so the JSONL line carries the artifact it sits
# next to (ISSUE 6: regression-attributable headline numbers)
_COST_ARTIFACTS = {}


def _persist_cost_report(tag, step, step_time_s=None,
                         items_per_step=None):
    """Persist the compiled step's CostReport (per-HLO-category FLOPs/
    bytes + roofline at the measured step time) next to the bench's
    JSONL output.  Never raises: a failed capture costs the artifact,
    not the benchmark."""
    try:
        from mxnet_tpu import profiling
        rep = profiling.report_for(step, label=tag,
                                   step_time_s=step_time_s,
                                   items_per_step=items_per_step)
        if rep is None:
            return None
        outdir = _os.environ.get("MXNET_TPU_PROFILING_DIR") \
            or "bench_artifacts"
        _os.makedirs(outdir, exist_ok=True)
        path = _os.path.join(outdir, tag + ".cost.json")
        with open(path, "w") as f:
            json.dump(rep, f, indent=1, sort_keys=True)
        _COST_ARTIFACTS[tag] = path
        return path
    except Exception:
        return None


# Every emitted JSONL line carries `degraded_env`, derived ONCE from the
# health probe's dispatch round trip, so a slow host or a contended
# machine is never read as a model regression.
_DEGRADED_RTT_US = 10000.0
_ENV_DEGRADED = {"flag": None}     # None until the health probe ran


def _mark_env_health(health):
    """Derive the degraded-environment flag from the env_health probe
    (dispatch_roundtrip threshold); returns the flag for the line.
    The threshold is THE goodput sentinel's env guard
    (obs.goodput.env_degraded / DEGRADED_RTT_US), so the per-line flag
    and a goodput.env_degraded event can never disagree
    (test_bench_contract).  The probe numbers also land as telemetry
    gauges (env.dispatch_roundtrip_us / env.h2d_mb_per_s) so the basis
    of a degraded_env verdict survives in summarize output and the
    flight-recorder dump, not just this process's stdout."""
    rtt = health.get("dispatch_roundtrip_us")
    try:
        from mxnet_tpu.obs import goodput as _goodput
        flag = _goodput.env_degraded(rtt) if rtt is not None else False
    except Exception:
        flag = bool(rtt is not None and rtt > _DEGRADED_RTT_US)
    _ENV_DEGRADED["flag"] = flag
    try:
        from mxnet_tpu import telemetry as _telemetry
        if _telemetry._ENABLED and rtt is not None:
            _telemetry.hooks.env_health(rtt,
                                        health.get("h2d_mb_per_s"))
    except Exception:
        pass                  # health marking must never fail a bench
    return _ENV_DEGRADED["flag"]


# ----------------------------------------------------------------------
# goodput breakdowns (ISSUE 14): the scan/LARS/e2e lines carry the
# StepLedger's per-category wall attribution + bottleneck verdict, so
# the synthetic-vs-e2e gap is auto-attributed in the artifact itself.
# ----------------------------------------------------------------------

_GOODPUT = {}                 # tag -> compact goodput line summary


def _goodput_begin():
    """Open a StepLedger over a measured window (arming telemetry +
    profiling if off, so the category instruments record); returns
    ``(ledger, restore_fn)``, or ``(None, noop)`` when obs is
    unavailable -- a failed ledger costs the breakdown, never the
    benchmark."""
    try:
        from mxnet_tpu import profiling, telemetry
        from mxnet_tpu.obs import goodput as _gp
        was_t = telemetry.enabled()
        was_p = profiling.enabled()
        telemetry.enable()
        profiling.enable()
        ledger = _gp.StepLedger(window_steps=1 << 30)  # manual flush

        def restore():
            if not was_t:
                telemetry.disable()
            if not was_p:
                profiling.disable()
        return ledger, restore
    except Exception:
        return None, lambda: None


def _goodput_end(tag, ledger, restore, steps):
    """Close the measured window and stash the compact breakdown for
    the JSONL line under ``tag``; never fatal."""
    try:
        if ledger is None:
            return None
        from mxnet_tpu.obs import goodput as _gp
        ledger.step(steps)
        win = ledger.flush(reason="bench")
        _GOODPUT[tag] = _gp.line_summary(win)
        return _GOODPUT[tag]
    except Exception:
        return None
    finally:
        restore()


def _goodput_extra(tag):
    """extra_fn fields: the goodput breakdown riding the JSONL line."""
    gp = _GOODPUT.get(tag)
    return {"goodput": gp} if gp else {}


def _cost_extra(tag):
    """extra_fn fields for the emitted JSONL line: artifact path plus
    the top category + its roofline bound, so the line itself says
    where the FLOPs went."""
    path = _COST_ARTIFACTS.get(tag)
    if not path:
        return {}
    try:
        with open(path) as f:
            rep = json.load(f)
        top = max(rep["categories"],
                  key=lambda c: rep["categories"][c]["flops"])
        extra = {"cost_report": path, "hlo_top_category": top}
        rl = rep.get("roofline")
        if rl and top in rl.get("categories", {}):
            extra["top_category_bound"] = rl["categories"][top]["bound"]
        return extra
    except Exception:
        return {"cost_report": path}


def bench_env_health(h2d_mb=64, pingpong=20):
    """Environment-health probe, emitted BEFORE any other compute:
    host->device bandwidth and the dispatch round trip.  Lets a swing
    in a dispatch-bound config be attributed to the environment inside
    the artifact itself."""
    import jax
    import jax.numpy as jnp
    dev = jax.devices()[0]
    # two-stage probe: a 4 MB scout first -- on a very slow link the
    # full probe alone would eat a minute of budget; the big transfer
    # only runs when the scout says the link is fast enough that
    # latency would skew a small sample
    t0 = time.perf_counter()
    y = jax.device_put(np.zeros(1024 * 1024, np.float32), dev)
    float(y[0])                      # value fetch = trustworthy barrier
    scout_dt = time.perf_counter() - t0
    if scout_dt < 0.5:
        buf = np.zeros(h2d_mb * 1024 * 1024 // 4, np.float32)
        t0 = time.perf_counter()
        y = jax.device_put(buf, dev)
        float(y[0])
        h2d_mb_s = h2d_mb / (time.perf_counter() - t0)
    else:
        h2d_mb_s = 4 / scout_dt
    f = jax.jit(lambda v: v + 1.0)
    x = jax.device_put(jnp.zeros(()), dev)
    float(f(x))                      # compile outside the window
    t0 = time.perf_counter()
    for _ in range(pingpong):
        x = f(x)
        float(x)
    lat_us = (time.perf_counter() - t0) / pingpong * 1e6
    return {"h2d_mb_per_s": round(h2d_mb_s, 1),
            "dispatch_roundtrip_us": round(lat_us, 1)}


def _cpu_subprocess_value(expr, timeout=600):
    """Evaluate ``expr`` (a bench.* call) in a fresh interpreter pinned
    to the CPU backend and return its printed float.  A chip belongs to
    one process: this parent has touched JAX and holds it, so the only
    child it may start is one that never asks for it."""
    import subprocess
    import sys
    code = ("import sys; sys.path.insert(0, %r); import bench; "
            "print(%s)" % (_os.path.dirname(_os.path.abspath(__file__)),
                           expr))
    env = dict(_os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=timeout)
    if out.returncode != 0:
        # carry the real cause, not an IndexError from empty stdout
        tail = "\n".join((out.stderr or "").strip().splitlines()[-12:])
        raise RuntimeError(
            "bench subprocess for %s exited %d; stderr tail:\n%s"
            % (expr, out.returncode, tail or "<empty>"))
    return float(out.stdout.strip().splitlines()[-1])


def _bench_train(net, loss_fn, data_shape, label_shape, n_classes,
                 batch_size, lr=0.05, warmup=5, iters=30, dtype="float32"):
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.parallel import TrainStep

    import contextlib
    from mxnet_tpu import amp
    ctx = _ctx()
    net.initialize(ctx=ctx, force_reinit=True)
    net.hybridize()
    # mixed precision: params stay fp32, MXU ops run in the target dtype
    amp_ctx = amp.scope(dtype) if dtype != "float32" \
        else contextlib.nullcontext()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": lr, "momentum": 0.9},
                            kvstore=None)
    step = TrainStep(net, loss_fn, trainer, mesh=None)
    # synthetic inputs are GENERATED ON-DEVICE (mx.nd.random is
    # jax.random-backed): host->device staging has nothing to do with
    # the training throughput this config measures
    x = mx.nd.random.normal(shape=data_shape, ctx=ctx)
    y = mx.nd.random.randint(0, n_classes, shape=label_shape,
                             ctx=ctx).astype("float32")
    with amp_ctx:
        for _ in range(warmup):
            step(x, y)
        # Synchronize via a scalar host fetch.  Steps are chained
        # through the parameters, so fetching the last loss drains the
        # queue.
        float(step(x, y).asscalar())
        t0 = time.perf_counter()
        last = None
        for _ in range(iters):
            last = step(x, y)
        float(last.asscalar())
        dt = time.perf_counter() - t0
    return batch_size * iters / dt


def _lenet_net(layout="NCHW"):
    from mxnet_tpu import gluon
    net = gluon.nn.HybridSequential()
    # reference LeNet-5 dims (20/50/500) kept verbatim so the bench line
    # stays comparable across rounds; the tile padding they cost is the
    # linter's point, not this net's
    net.add(gluon.nn.Conv2D(20, kernel_size=5, activation="relu",  # mxlint: disable=pad-waste
                            layout=layout),
            gluon.nn.MaxPool2D(2, 2, layout=layout),
            gluon.nn.Conv2D(50, kernel_size=5, activation="relu",  # mxlint: disable=pad-waste
                            layout=layout),
            gluon.nn.MaxPool2D(2, 2, layout=layout),
            gluon.nn.Flatten(),
            gluon.nn.Dense(500, activation="relu"),  # mxlint: disable=pad-waste
            gluon.nn.Dense(10))
    return net


def bench_lenet(batch_size=256):
    from mxnet_tpu import gluon
    net = _lenet_net()
    return _bench_train(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                        (batch_size, 1, 28, 28), (batch_size,), 10,
                        batch_size, warmup=5, iters=50)


def bench_lenet_scan(batch_size=256, k=50, reps=3):
    """Config 1 with the compiled K-step loop: the per-step variant's
    throughput tracks the host's dispatch round trip; this one is
    dispatch-independent -- K steps per host round-trip -- so it
    measures the MODEL, not the dispatch path."""
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.parallel import TrainStep

    ctx = _ctx()
    net = _lenet_net()
    net.initialize(ctx=ctx, force_reinit=True)
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.05, "momentum": 0.9},
                            kvstore=None)
    step = TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(), trainer,
                     mesh=None)
    x = mx.nd.random.normal(shape=(k, batch_size, 1, 28, 28), ctx=ctx)
    y = mx.nd.random.randint(0, 10, shape=(k, batch_size),
                             ctx=ctx).astype("float32")
    step.run_steps(x, y)
    float(step.run_steps(x, y).asnumpy()[-1])
    wins = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = step.run_steps(x, y)
        float(out.asnumpy()[-1])
        wins.append(batch_size * k / (time.perf_counter() - t0))
    return statistics.median(wins)


def bench_lenet_imperative(batch_size=256, iters=30):
    """Config 1's stated mode: NON-hybridized eager training -- every op
    call dispatches through the persistent per-op jit cache (SURVEY §7
    hard-part #1).  Measured honestly (r3): with LOCAL dispatch (CPU
    backend, uncontended) the eager loop is ~3.3x slower than the
    hybridized one -- per-op execution forgoes XLA fusion and
    materializes every intermediate, the usual eager/compiled gap.
    The driver artifact carries both numbers
    (``lenet_imperative_local_dispatch_cpu``)."""
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon

    ctx = _ctx()
    net = _lenet_net()
    net.initialize(ctx=ctx, force_reinit=True)   # NOT hybridized
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.05, "momentum": 0.9},
                            kvstore=None)
    rng = np.random.RandomState(0)
    x = mx.nd.array(rng.randn(batch_size, 1, 28, 28).astype(np.float32),
                    ctx=ctx)
    y = mx.nd.array(rng.randint(0, 10, (batch_size,)).astype(np.float32),
                    ctx=ctx)

    def step():
        with autograd.record():
            loss = loss_fn(net(x), y).mean()
        loss.backward()
        trainer.step(batch_size)
        return loss

    for _ in range(5):
        step()
    float(step().asscalar())
    t0 = time.perf_counter()
    last = None
    for _ in range(iters):
        last = step()
    float(last.asscalar())
    return batch_size * iters / (time.perf_counter() - t0)


def bench_resnet50(batch_size=128, dtype="float32"):
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon.model_zoo.vision import resnet50_v1
    net = resnet50_v1()
    return _bench_train(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                        (batch_size, 3, 224, 224), (batch_size,), 1000,
                        batch_size, warmup=5, iters=20, dtype=dtype)


def _hbm_sweep_step(batch):
    """One compiled ResNet train step at ``batch`` (ResNet-50 NCHW on
    TPU, the thumbnail ResNet-18 off-TPU so the sweep stays runnable in
    dev); returns the executed TrainStep, whose ``_last_call`` carries
    the (jitted fn, abstract args) pair hbm_plan anchors on."""
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.parallel import TrainStep
    ctx = _ctx()
    rng = np.random.RandomState(0)
    if mx.num_tpus() > 0:
        from mxnet_tpu.gluon.model_zoo.vision import resnet50_v1
        net = resnet50_v1()
        x_shape = (batch, 3, 224, 224)
    else:
        from mxnet_tpu.gluon.model_zoo.vision import resnet18_v1
        net = resnet18_v1(classes=10, thumbnail=True, layout="NHWC")
        x_shape = (batch, 32, 32, 3)
    net.initialize(ctx=ctx)
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1}, kvstore=None)
    step = TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                     trainer, mesh=None)
    x = mx.nd.array(rng.rand(*x_shape).astype(np.float32), ctx=ctx)
    y = mx.nd.array(rng.randint(0, 10, (batch,)).astype(np.float32),
                    ctx=ctx)
    step(x, y)
    return step


def bench_batch_hbm_sweep(buckets=None, hbm_budget_bytes=None):
    """ROADMAP item 1's "sweep batch at fixed HBM budget", as an
    instrument (ISSUE 20): fit ``analysis.memory.hbm_plan``'s
    const+per-item peak-HBM line from two anchor compiles of the
    ResNet train step, then for EVERY bucket put the plan's predicted
    peak next to the real compile's measured peak -- the emitted line
    is the planner's accuracy contract, and ``largest_fit_bucket``
    answers the ROADMAP question under the budget (the device's
    reported HBM when it reports one, a 16 GB stand-in otherwise)."""
    import mxnet_tpu as mx
    from mxnet_tpu.analysis import memory as _memory
    on_tpu = mx.num_tpus() > 0
    if buckets is None:
        buckets = (64, 128, 256, 512) if on_tpu else (2, 4, 8)
    buckets = tuple(sorted(int(b) for b in buckets))
    b0 = buckets[0]
    if hbm_budget_bytes is None:
        hbm_budget_bytes = _memory.device_hbm_bytes() or (16 << 30)
    step = _hbm_sweep_step(b0)
    fn, arg_shapes = step._last_call
    plan = _memory.hbm_plan("bench:resnet-hbm-sweep",
                            device_hbm_bytes=int(hbm_budget_bytes),
                            buckets=buckets, batch_size=b0,
                            fn=fn, args=arg_shapes)
    rows = []
    for brec in plan["buckets"]:
        b = brec["batch"]
        measured = _memory.executable_memory(
            fn.lower(*_memory._resize_batch(arg_shapes, b0, b))
            .compile())["peak_hbm_bytes"]
        predicted = brec["predicted_peak_hbm_bytes"]
        rows.append({
            "batch": b,
            "predicted_peak_hbm_bytes": predicted,
            "measured_peak_hbm_bytes": measured,
            "rel_error": (round((predicted - measured) / measured, 4)
                          if measured else None),
            "fits": brec["fits"],
        })
    return {
        "probe": ("resnet50v1-nchw-sgd-224" if on_tpu
                  else "resnet18v1-nhwc-sgd-thumbnail"),
        "hbm_budget_bytes": int(hbm_budget_bytes),
        "const_bytes": plan["const_bytes"],
        "per_item_bytes": plan["per_item_bytes"],
        "buckets": rows,
        "largest_fit_bucket": plan["largest_fit_bucket"],
    }


def _peak_flops():
    """Peak bf16 FLOP/s of this device from THE peaks table
    (profiling.roofline.DEVICE_PEAKS); None on a CPU, an error for an
    accelerator kind the table does not hold."""
    from mxnet_tpu.profiling import roofline
    peaks = roofline.device_peaks()
    return peaks[0] if peaks is not None else None


def bench_resnet50_scan(batch_size=256, k=10, dtype="bfloat16", reps=4):
    """ResNet-50 with the compiled multi-step train loop
    (``TrainStep.run_steps``): K full steps per dispatch -- the
    TPU-idiomatic inner loop, no per-step host round-trip.  Returns
    (median img/s, mfu_or_None, per-window img/s list) -- each rep is
    its own measured window so the artifact carries dispersion."""
    import contextlib
    import mxnet_tpu as mx
    from mxnet_tpu import amp, gluon
    from mxnet_tpu.gluon.model_zoo.vision import resnet50_v1
    from mxnet_tpu.parallel import TrainStep

    ctx = _ctx()
    net = resnet50_v1()
    net.initialize(ctx=ctx)
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.05, "momentum": 0.9},
                            kvstore=None)
    step = TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(), trainer,
                     mesh=None)
    # on-device synthetic data: staging (k, 256, 3, 224, 224) fp32
    # from the host measures nothing this config is about
    x = mx.nd.random.normal(shape=(k, batch_size, 3, 224, 224), ctx=ctx)
    y = mx.nd.random.randint(0, 1000, shape=(k, batch_size),
                             ctx=ctx).astype("float32")
    amp_ctx = amp.scope(dtype) if dtype != "float32" \
        else contextlib.nullcontext()
    with amp_ctx:
        step.run_steps(x, y)
        float(step.run_steps(x, y).asnumpy()[-1])
        # goodput ledger over the measured reps ONLY (the single-step
        # flop-count compile below would pollute the recompile
        # category); the window's breakdown rides the JSONL line
        ledger, _restore_gp = _goodput_begin()
        wins = []
        for _ in range(reps):
            t0 = time.perf_counter()
            out = step.run_steps(x, y)
            float(out.asnumpy()[-1])
            wins.append(batch_size * k / (time.perf_counter() - t0))
        _goodput_end("resnet50_bf16", ledger, _restore_gp,
                     steps=k * reps)
        # single-step program for an honest per-step flop count (the scan
        # program reports its loop body once); slice ON DEVICE -- an
        # asnumpy here would fetch the whole (k, B, ...) tensor
        step(x[0], y[0])
        ca = step.cost_analysis()
    med = statistics.median(wins)
    dt = batch_size / med
    mfu = None
    peak = _peak_flops()
    if ca and ca.get("flops") and peak:
        mfu = round(ca["flops"] / dt / peak, 4)
    if "resnet50_bf16" in _GOODPUT:
        _GOODPUT["resnet50_bf16"]["mfu"] = mfu
    # persist the per-HLO cost accounting of the measured single-step
    # program next to the JSONL line (ISSUE 6 / ROADMAP item 2)
    _persist_cost_report("resnet50_bf16", step, step_time_s=dt,
                         items_per_step=batch_size)
    return med, mfu, [round(w, 1) for w in wins]


def bench_resnet50_lars(batch_size=512, k=10, dtype="bfloat16", reps=3):
    """BASELINE config 5: bf16 AMP + LARS large-batch ResNet-50 --
    the large-batch scaling recipe (layer-wise trust ratios keep SGD
    stable at batch sizes where plain momentum diverges), measured on
    the compiled K-step loop like the headline config.  Returns
    (median img/s, mfu_or_None, per-window img/s list)."""
    import contextlib
    import mxnet_tpu as mx
    from mxnet_tpu import amp, gluon
    from mxnet_tpu.gluon.model_zoo.vision import resnet50_v1
    from mxnet_tpu.parallel import TrainStep

    ctx = _ctx()
    net = resnet50_v1()
    net.initialize(ctx=ctx)
    net.hybridize()
    # the trace-safe fused LARS (opt.create('lars') is pinned to the
    # in-graph impl by test); skip_list keeps bias/gamma/beta on the
    # plain momentum path as the reference does
    trainer = gluon.Trainer(net.collect_params(), "lars",
                            {"learning_rate": 0.1, "momentum": 0.9,
                             "eta": 0.001}, kvstore=None)
    step = TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(), trainer,
                     mesh=None)
    x = mx.nd.random.normal(shape=(k, batch_size, 3, 224, 224), ctx=ctx)
    y = mx.nd.random.randint(0, 1000, shape=(k, batch_size),
                             ctx=ctx).astype("float32")
    amp_ctx = amp.scope(dtype) if dtype != "float32" \
        else contextlib.nullcontext()
    with amp_ctx:
        step.run_steps(x, y)
        float(step.run_steps(x, y).asnumpy()[-1])
        ledger, _restore_gp = _goodput_begin()
        wins = []
        for _ in range(reps):
            t0 = time.perf_counter()
            out = step.run_steps(x, y)
            float(out.asnumpy()[-1])
            wins.append(batch_size * k / (time.perf_counter() - t0))
        _goodput_end("resnet50_lars_bf16", ledger, _restore_gp,
                     steps=k * reps)
        step(x[0], y[0])
        ca = step.cost_analysis()
    med = statistics.median(wins)
    dt = batch_size / med
    mfu = None
    peak = _peak_flops()
    if ca and ca.get("flops") and peak:
        mfu = round(ca["flops"] / dt / peak, 4)
    if "resnet50_lars_bf16" in _GOODPUT:
        _GOODPUT["resnet50_lars_bf16"]["mfu"] = mfu
    _persist_cost_report("resnet50_lars_bf16", step, step_time_s=dt,
                         items_per_step=batch_size)
    return med, mfu, [round(w, 1) for w in wins]


def bench_multichip_scaling(device_counts=(1, 2, 4, 8),
                            batch_per_device=32, iters=6, warmup=2,
                            devices=None):
    """Device-count scaling line (ISSUE 9): the SAME convnet trains as
    ONE compiled SPMD program (``parallel.TrainStep``) over a 1/2/4/8
    device ``dp`` mesh at fixed per-device batch; each row reports
    img/s, per-device parallel efficiency vs the 1-device run, and the
    compiled step's IN-GRAPH collective kinds/bytes pulled from the
    sharding sanitizer (``analysis.sharding.collective_profile``) --
    the gradient all-reduce GSPMD inserted, not host kvstore traffic.
    On CPU the virtual devices share one host's cores, so efficiency
    documents the contention floor; on a pod the same line measures the
    ICI. Returns the list of row dicts."""
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.analysis.sharding import collective_profile
    from mxnet_tpu.parallel import TrainStep, make_mesh, shard_batch
    import jax

    devices = list(devices if devices is not None else jax.devices())
    rng = np.random.RandomState(0)
    rows, base_img_s = [], None
    for n in device_counts:
        if n > len(devices):
            rows.append({"n_devices": n,
                         "skipped": "only %d devices" % len(devices)})
            continue
        mesh = make_mesh({"dp": n}, devices=devices[:n])
        net = gluon.nn.HybridSequential()
        net.add(gluon.nn.Conv2D(8, kernel_size=3, padding=1,
                                activation="relu", layout="NCHW"),
                gluon.nn.MaxPool2D(2, layout="NCHW"),
                gluon.nn.Flatten(),
                gluon.nn.Dense(32, activation="relu"),
                gluon.nn.Dense(10))
        net.initialize(ctx=mx.cpu(), force_reinit=True)
        net.hybridize()
        trainer = gluon.Trainer(net.collect_params(), "sgd",
                                {"learning_rate": 0.05, "momentum": 0.9},
                                kvstore=None)
        step = TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                         trainer, mesh=mesh)
        batch = batch_per_device * n
        x = shard_batch(rng.randn(batch, 3, 16, 16).astype(np.float32),
                        mesh)
        y = shard_batch(rng.randint(0, 10, batch).astype(np.float32),
                        mesh)
        for _ in range(warmup):
            step(x, y)
        float(np.asarray(step(x, y)._data))     # drain before the window
        t0 = time.perf_counter()
        last = None
        for _ in range(iters):
            last = step(x, y)
        float(np.asarray(last._data))
        dt = time.perf_counter() - t0
        img_s = batch * iters / dt
        fn, args = step._last_call
        prof = collective_profile(fn.lower(*args).compile().as_text())
        row = {"n_devices": n,
               "img_per_s": round(img_s, 1),
               "per_device_img_per_s": round(img_s / n, 1),
               "collectives": prof,
               "collective_bytes": sum(rec["bytes"]
                                       for rec in prof.values())}
        if base_img_s is None:
            base_img_s = img_s / n
            row["efficiency"] = 1.0
        else:
            row["efficiency"] = round(img_s / n / base_img_s, 3)
        rows.append(row)
    return rows


def _multichip_scaling_rows(device_counts=(1, 2, 4, 8), timeout=600):
    """Run the scaling sweep in a fresh CPU subprocess with enough
    virtual host devices (the calling process may own a single real
    chip; the sweep needs a 1..8-device ladder and must not disturb
    this process's backend)."""
    import re
    import subprocess
    import sys
    n_max = max(device_counts)
    env = dict(_os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   env.get("XLA_FLAGS", ""))
    env["XLA_FLAGS"] = (flags +
                        " --xla_force_host_platform_device_count=%d"
                        % n_max).strip()
    code = ("import sys, json; sys.path.insert(0, %r); import bench; "
            "print(json.dumps(bench.bench_multichip_scaling(%r)))"
            % (_os.path.dirname(_os.path.abspath(__file__)),
               tuple(device_counts)))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=timeout)
    if out.returncode != 0:
        raise RuntimeError(out.stderr[-500:])
    return json.loads(out.stdout.strip().splitlines()[-1])


def bench_serving(offered_qps=(100, 400, 1600), duration_s=2.0,
                  clients=8, buckets=(1, 2, 4, 8, 16), max_wait_ms=3.0):
    """Serving-tier latency-vs-QPS curve (ISSUE 8 bench contract).

    A LeNet servable behind the PRODUCT serving path
    (``mx.serving.ModelRegistry``: AOT per-bucket executables + dynamic
    batcher) takes open-loop traffic from ``clients`` threads at each
    offered rate for ``duration_s``; per level the curve records
    achieved QPS, p50/p95/p99 latency, mean batch occupancy (from the
    ``serving.*`` telemetry counters), and shed count -- the knee where
    p99 lifts off IS the capacity number a capacity planner needs.
    """
    import threading
    import mxnet_tpu as mx
    from mxnet_tpu import telemetry

    net = _lenet_net()
    net.initialize(force_reinit=True)
    net.hybridize()
    x0 = mx.nd.array(np.zeros((1, 1, 28, 28), np.float32))
    net(x0)
    reg = mx.serving.ModelRegistry(compile_cache=False)
    servable = reg.register("lenet", block=net, input_shape=(1, 28, 28),
                            buckets=buckets, max_wait_ms=max_wait_ms,
                            max_queue=1024)
    was_enabled = telemetry.enabled()
    telemetry.enable()
    sample = np.random.RandomState(0) \
        .rand(1, 28, 28).astype(np.float32)
    curve = []
    try:
        for rate in offered_qps:
            telemetry.reset("serving.")
            latencies = []          # list.append is GIL-atomic
            shed = [0]
            interval = clients / float(rate)

            def client():
                t_end = time.perf_counter() + duration_s
                while time.perf_counter() < t_end:
                    t0 = time.perf_counter()
                    try:
                        servable.infer(sample, timeout=2.0)
                        latencies.append(time.perf_counter() - t0)
                    except Exception:
                        shed[0] += 1
                    pace = interval - (time.perf_counter() - t0)
                    if pace > 0:
                        # open-loop rate pacing, not state polling: the
                        # sleep IS the offered-QPS control variable
                        time.sleep(pace)  # mxlint: disable=sleep-poll

            threads = [threading.Thread(target=client, daemon=True)
                       for _ in range(clients)]
            t_start = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall = time.perf_counter() - t_start
            lats = sorted(latencies)

            def pct(q):
                return round(1e3 * lats[min(len(lats) - 1,
                                            int(q * len(lats)))], 3) \
                    if lats else None
            batches = telemetry.counter("serving.batches").value
            responses = telemetry.counter("serving.responses").value
            curve.append({
                "offered_qps": rate,
                "qps": round(len(lats) / wall, 1) if wall > 0 else None,
                "p50_ms": pct(0.50), "p95_ms": pct(0.95),
                "p99_ms": pct(0.99),
                "mean_occupancy": round(responses / batches, 3)
                if batches else None,
                "shed": shed[0] + telemetry.counter("serving.shed").value,
            })
    finally:
        reg.shutdown(drain=True)
        if not was_enabled:
            telemetry.disable()
    return curve


def bench_serving_hotswap(duration_s=2.0, clients=4, buckets=(1, 2, 4, 8),
                          max_wait_ms=3.0, publish_every=2):
    """Hot-swap cost under live traffic (ISSUE 12 bench contract).

    A servable behind the PRODUCT always-on loop
    (``serving.ContinuousTrainer`` publishing atomic checkpoints +
    ``serving.RegistryWatcher`` re-registering the servable) takes
    open-loop traffic from ``clients`` threads; mid-run the trainer
    publishes a newer step and the watcher hot-swaps it in
    (warm-compile the replacement while the old one serves, install,
    drain).  Recorded: the swap wall (checkpoint-visible -> new step
    serving), p50/p99 split into during-swap vs steady windows (a
    request is "during" when its lifetime overlaps the swap), and the
    zero-dropped contract (``dropped`` must be 0 -- registry-path
    clients never see the swap).  Runs on CPU.
    """
    import shutil
    import tempfile
    import threading
    import mxnet_tpu as mx
    from mxnet_tpu.chaos import scenarios as _scen
    from mxnet_tpu.serving.loop import ContinuousTrainer, RegistryWatcher

    root = tempfile.mkdtemp(prefix="mxtpu_hotswap_bench_")
    reg = None
    try:
        net, trainer, loss_fn, data = _scen.train_fixtures(seed=0)
        ct = ContinuousTrainer(net, trainer, loss_fn, data, root,
                               publish_every=publish_every)
        reg = mx.serving.ModelRegistry(compile_cache=False)
        watcher = RegistryWatcher(reg, "model", ct.manager,
                                  _scen.make_mlp(), input_shape=(8,),
                                  buckets=buckets, swap_retries=0,
                                  max_wait_ms=max_wait_ms,
                                  max_queue=1024)
        ct.run_steps(publish_every)
        watcher.poll_once()                  # initial servable
        records = []          # (t_submit, latency); append is GIL-atomic
        dropped = [0]
        stop = threading.Event()
        sample = np.random.RandomState(0).rand(8).astype(np.float32)

        def client():
            while not stop.is_set():
                t0 = time.perf_counter()
                try:
                    reg.infer("model", sample, timeout=10)
                    records.append((t0, time.perf_counter() - t0))
                except Exception:
                    dropped[0] += 1
                # open-loop pacing, not state polling
                time.sleep(0.001)  # mxlint: disable=sleep-poll

        threads = [threading.Thread(target=client, daemon=True)
                   for _ in range(clients)]
        for t in threads:
            t.start()
        time.sleep(duration_s * 0.4)         # steady window (old model)
        ct.run_steps(publish_every)          # publish the newer step
        t_swap0 = time.perf_counter()
        swapped = watcher.poll_once()        # restore+warm+install+drain
        t_swap1 = time.perf_counter()
        time.sleep(duration_s * 0.4)         # steady window (new model)
        stop.set()
        for t in threads:
            t.join()
        ct.close()
        watcher.close()
        reg.shutdown(drain=True)
        reg = None
        during = [lat for (t0, lat) in records
                  if t0 <= t_swap1 and t0 + lat >= t_swap0]
        steady = [lat for (t0, lat) in records
                  if not (t0 <= t_swap1 and t0 + lat >= t_swap0)]

        def pct(lats, q):
            lats = sorted(lats)
            return round(1e3 * lats[min(len(lats) - 1,
                                        int(q * len(lats)))], 3) \
                if lats else None

        return {
            "swap_step": swapped,
            "swap_latency_ms": round(1e3 * (t_swap1 - t_swap0), 3),
            "p50_steady_ms": pct(steady, 0.50),
            "p99_steady_ms": pct(steady, 0.99),
            "p50_during_swap_ms": pct(during, 0.50),
            "p99_during_swap_ms": pct(during, 0.99),
            "requests": len(records) + dropped[0],
            "requests_during_swap": len(during),
            "dropped": dropped[0],
        }
    finally:
        if reg is not None:
            reg.shutdown(drain=True)
        shutil.rmtree(root, ignore_errors=True)


def bench_serving_decode(duration_s=2.0, clients=4, max_new=24,
                         decode_buckets=(1, 2, 4, 8),
                         prefill_buckets=(8, 16)):
    """Generative serving throughput + token-latency tail (ISSUE 18
    bench contract).

    A tiny GPT behind the PRODUCT generative path
    (``ModelRegistry.register_generative`` + ``generate()``: bucketed
    prefill/decode AOT executables, paged KV cache, continuous
    batching) takes closed-loop streaming traffic from ``clients``
    threads for ``duration_s``.  Recorded: decoded tokens/s, TTFT
    p50/p99 (submit -> first token, through the product stream), and
    inter-token p50/p99 across all streams -- the two numbers a
    generative SLO is written against -- plus mean step occupancy
    (tokens/steps from the ``decode.*`` counters) and the shed count.
    Runs on CPU.
    """
    import threading
    import mxnet_tpu as mx
    from mxnet_tpu import telemetry
    from mxnet_tpu.serving.decode import tiny_gpt

    model = tiny_gpt(vocab_size=64, units=32, num_layers=2,
                     num_heads=2, max_seq=64)
    params = model.init_params(0)
    reg = mx.serving.ModelRegistry(compile_cache=False)
    reg.register_generative("gpt", model, params=params,
                            prefill_buckets=prefill_buckets,
                            decode_buckets=decode_buckets,
                            block_size=8, num_blocks=256,
                            max_queue=64)
    was_enabled = telemetry.enabled()
    telemetry.enable()
    telemetry.reset("decode.")
    rng = np.random.RandomState(0)
    prompts = [list(rng.randint(1, 64, size=n)) for n in (3, 5, 8, 12)]
    ttfts, gaps = [], []      # list.append is GIL-atomic
    tokens = [0]
    shed = [0]
    try:
        stop = time.perf_counter() + duration_s

        def client(tid):
            i = 0
            while time.perf_counter() < stop:
                t0 = time.perf_counter()
                prev = None
                try:
                    stream = reg.generate(
                        "gpt", prompts[(tid + i) % len(prompts)],
                        max_new, timeout=30)
                    for _tok in stream:
                        now = time.perf_counter()
                        if prev is None:
                            ttfts.append(now - t0)
                        else:
                            gaps.append(now - prev)
                        prev = now
                        tokens[0] += 1
                except Exception:
                    shed[0] += 1
                i += 1

        threads = [threading.Thread(target=client, args=(t,),
                                    daemon=True)
                   for t in range(clients)]
        t_start = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t_start

        def pct(lats, q):
            lats = sorted(lats)
            return round(1e3 * lats[min(len(lats) - 1,
                                        int(q * len(lats)))], 3) \
                if lats else None

        steps = telemetry.counter("decode.steps").value
        decoded = telemetry.counter("decode.tokens").value
        return {
            "tokens_per_s": round(tokens[0] / wall, 1)
            if wall > 0 else None,
            "streams": len(ttfts),
            "ttft_p50_ms": pct(ttfts, 0.50),
            "ttft_p99_ms": pct(ttfts, 0.99),
            "inter_token_p50_ms": pct(gaps, 0.50),
            "inter_token_p99_ms": pct(gaps, 0.99),
            "mean_occupancy": round(decoded / steps, 3)
            if steps else None,
            "shed": shed[0],
        }
    finally:
        reg.shutdown(drain=True)
        if not was_enabled:
            telemetry.disable()


def bench_bert_base(batch_size=16, seq_len=128, vocab=30522,
                    dtype="float32", use_flash=None, iters=20,
                    windows=1):
    """BERT-base masked-LM pretraining step (config 3).
    Returns (median tokens/s, mfu_or_None, per-window tokens/s list);
    ``windows`` splits ``iters`` into that many separately-synced
    measurement windows for dispersion."""
    import contextlib
    import mxnet_tpu as mx
    from mxnet_tpu import amp, gluon
    from mxnet_tpu.parallel import TrainStep

    ctx = _ctx()
    mx.random.seed(0)
    net = gluon.model_zoo.bert_base(vocab_size=vocab, max_length=seq_len,
                                    dropout=0.0, use_flash=use_flash)
    net.initialize(ctx=ctx)
    net.hybridize()
    ce = gluon.loss.SoftmaxCrossEntropyLoss()

    class MLMLoss(gluon.HybridBlock):
        def hybrid_forward(self, F, outs, labels):
            mlm, _nsp = outs
            return ce(mlm.reshape((-1, vocab)), labels.reshape((-1,)))

    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 1e-4}, kvstore=None)
    step = TrainStep(net, MLMLoss(), trainer, mesh=None)
    # on-device synthetic tokens (see bench_resnet50_scan's comment)
    ids = mx.nd.random.randint(0, vocab, shape=(batch_size, seq_len),
                               ctx=ctx).astype("float32")
    labels = mx.nd.random.randint(0, vocab, shape=(batch_size, seq_len),
                                  ctx=ctx).astype("float32")
    amp_ctx = amp.scope(dtype) if dtype != "float32" \
        else contextlib.nullcontext()
    with amp_ctx:
        for _ in range(5):
            step(ids, labels)
        float(step(ids, labels).asscalar())
        per_win = max(1, iters // max(1, windows))
        wins = []
        for _ in range(max(1, windows)):
            t0 = time.perf_counter()
            last = None
            for _ in range(per_win):
                last = step(ids, labels)
            float(last.asscalar())
            wins.append(batch_size * seq_len * per_win
                        / (time.perf_counter() - t0))
        ca = step.cost_analysis()
    med = statistics.median(wins)
    mfu = None
    peak = _peak_flops()
    if ca and ca.get("flops") and peak:
        mfu = round(ca["flops"] * med / (batch_size * seq_len) / peak, 4)
    _persist_cost_report("bert_base_seq%d_%s" % (seq_len, dtype), step,
                         step_time_s=batch_size * seq_len / med,
                         items_per_step=batch_size * seq_len)
    return med, mfu, [round(w, 1) for w in wins]


def _build_rec(path, n, fmt="jpg", hw=256, crop=224, seed=0):
    """Synthetic .rec dataset for the pipeline benchmarks.

    Images are natural-like (low-frequency content + mild noise), not
    uniform noise: noise JPEGs are pathological for the entropy coder
    (~2x the decode cost of a photo), which would understate pipeline
    throughput."""
    import mxnet_tpu as mx
    from mxnet_tpu import recordio
    from mxnet_tpu.image.image import _resize_np
    rng = np.random.RandomState(seed)
    rec = recordio.MXIndexedRecordIO(path + ".idx", path + ".rec", "w")
    for i in range(n):
        base = rng.randint(0, 255, (16, 16, 3), dtype=np.uint8)
        img = _resize_np(base, hw, hw).astype(np.int16)
        img += rng.randint(-8, 9, img.shape, dtype=np.int16)
        img = np.clip(img, 0, 255).astype(np.uint8)
        header = recordio.IRHeader(0, float(i % 1000), i, 0)
        if fmt == "raw":
            rec.write_idx(i, recordio.pack(
                header, img[:crop, :crop].tobytes()))
        else:
            rec.write_idx(i, recordio.pack_img(header, img, quality=90))
    rec.close()
    return path + ".rec"


def _pipeline_epoch_rate(rec, batch_size, dtype, epochs=3, **iter_kw):
    from mxnet_tpu.image import ImageIter
    it = ImageIter(batch_size, (3, 224, 224), path_imgrec=rec,
                   dtype=dtype, **iter_kw)
    try:
        count = 0
        t0 = time.perf_counter()
        for _ in range(epochs):
            it.reset()
            try:
                while True:
                    d, _l, _pad = it.next_np()
                    count += d.shape[0]
            except StopIteration:
                pass
        return count / (time.perf_counter() - t0)
    finally:
        it.close()


def bench_pipeline(n=512, batch_size=64, threads=2):
    """Input pipeline host throughput (reference bar:
    ``iter_image_recordio_2.cc`` threaded decode).  Returns
    (jpeg_img_per_s, raw_uint8_img_per_s, scaling) where ``scaling``
    maps worker configs (threads=N / procs=N) to jpeg img/s -- the
    measured scaling table.  Numbers are per-host; this box has one
    core, so the process-pool rows document the contention floor rather
    than the multi-core ceiling."""
    import shutil
    import tempfile
    tmp = tempfile.mkdtemp(prefix="mxtpu_bench_rec_")
    try:
        rec_jpg = _build_rec(_os.path.join(tmp, "jpg"), n, "jpg")
        rec_raw = _build_rec(_os.path.join(tmp, "raw"), n, "raw")
        scaling = {}
        for label, kw in (("threads=1", dict(preprocess_threads=0)),
                          ("threads=2", dict(preprocess_threads=2)),
                          ("threads=4", dict(preprocess_threads=4)),
                          ("procs=2", dict(preprocess_procs=2)),
                          ("procs=4", dict(preprocess_procs=4))):
            scaling[label] = round(_pipeline_epoch_rate(
                rec_jpg, batch_size, "float32", **kw), 1)
        jpeg = max(scaling.values())
        raw = _pipeline_epoch_rate(rec_raw, batch_size, "uint8",
                                   preprocess_threads=threads)
        return jpeg, raw, scaling
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def bench_resnet50_e2e(batch_size=256, n_images=2048, dtype="bfloat16",
                       epochs=4, feed_depth=2):
    """End-to-end ResNet-50 training fed by the REAL input pipeline
    (raw-record uint8 decode through ImageIter), not synthetic tensors.

    The staging now runs on the LIBRARY path (ISSUE 4):
    ``mxnet_tpu.dataio.DeviceFeed`` wraps the uint8 ImageIter -- a
    background producer issues async ``jax.device_put`` through a
    bounded double buffer while the compiled train step consumes the
    previous batch, and the feed's jitted ``DeviceTransform`` casts
    uint8 -> compute dtype after landing (reference:
    ``iter_prefetcher.h``).  Epoch 0 streams decode -> stage -> train;
    the compact staged batches (``DeviceBatch.raw``) are retained on
    device, so later epochs are pure compute.  The timed window covers
    everything from the first decoded record to the last step's sync.

    Returns ``(img/s, staging_overlap_frac, goodput)`` where the
    overlap fraction -- the share of producer (decode+transfer) time
    hidden behind training compute, ``1 - consumer_wait /
    producer_busy`` -- is computed from the library's ``feed.*``
    telemetry instruments (docs/observability.md), not bench-local
    accounting, and ``goodput`` is the StepLedger's per-category wall
    attribution + bottleneck verdict over the timed window (ISSUE 14:
    the e2e-vs-synthetic gap is auto-attributed -- an input-bound
    verdict here names decode/transfer with numbers instead of a
    hand-read of feed counters).
    """
    import contextlib
    import shutil
    import tempfile
    import mxnet_tpu as mx
    from mxnet_tpu import amp, gluon, telemetry
    from mxnet_tpu.dataio import DeviceFeed, DeviceTransform
    from mxnet_tpu.gluon.model_zoo.vision import resnet50_v1
    from mxnet_tpu.image import ImageIter
    from mxnet_tpu.parallel import TrainStep

    import jax.numpy as jnp
    ctx = _ctx()
    compute_dtype = jnp.bfloat16 if dtype == "bfloat16" else jnp.float32

    tmp = tempfile.mkdtemp(prefix="mxtpu_bench_e2e_")
    # EVERY constructor (.rec build, net compile warmup, ImageIter,
    # DeviceFeed) runs inside the try: a failure surfaces immediately
    # with the tmp dir removed and telemetry state restored, instead of
    # leaking state or -- in the pre-ISSUE-4 producer-thread shape of
    # this bench -- deadlocking the consumer (ADVICE round-5 medium)
    it = None
    feed = None
    was_enabled = telemetry.enabled()
    try:
        rec = _build_rec(_os.path.join(tmp, "train"), n_images, "raw")

        # compile the train step BEFORE the timed window (on zeros) so
        # the stream measures steady-state training, not compilation
        net = resnet50_v1()
        net.initialize(ctx=ctx)
        net.hybridize()
        trainer = gluon.Trainer(net.collect_params(), "sgd",
                                {"learning_rate": 0.05, "momentum": 0.9},
                                kvstore=None)
        step = TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                         trainer, mesh=None)
        amp_ctx = amp.scope(dtype) if dtype != "float32" \
            else contextlib.nullcontext()

        it = ImageIter(batch_size, (3, 224, 224), path_imgrec=rec,
                       preprocess_threads=0, dtype="uint8")
        telemetry.enable()             # source of the overlap fraction
        telemetry.reset("feed.")
        feed = DeviceFeed(it, ctx=ctx, depth=feed_depth,
                          transform=DeviceTransform(dtype=dtype))
        with amp_ctx:
            zx = mx.nd.NDArray(jnp.zeros((batch_size, 3, 224, 224),
                                         jnp.uint8).astype(compute_dtype))
            zy = mx.nd.NDArray(jnp.zeros((batch_size,), jnp.float32))
            for _ in range(3):
                step(zx, zy)
            float(step(zx, zy).asscalar())

            # goodput ledger over the timed window (decode -> stage ->
            # train): the breakdown rides the e2e JSONL line
            ledger, _restore_gp = _goodput_begin()
            if ledger is not None:
                ledger.flops_per_step = \
                    lambda: (step.cost_analysis() or {}).get("flops")
            count = 0
            last = None
            staged = []
            t_start = time.perf_counter()
            for batch in feed:            # epoch 0: streaming
                last = step(batch)
                count += batch_size
                # retain the COMPACT (uint8) staged arrays, not the
                # float expansion -- 4x less HBM
                staged.append((batch.raw[0], batch.label))
            for _ in range(epochs - 1):   # staged epochs: pure compute
                for raw, y in staged:
                    x = mx.nd.NDArray(feed.apply_transform(raw))
                    last = step(x, y)
                    count += batch_size
            float(last.asscalar())
            dt = time.perf_counter() - t_start
            goodput = _goodput_end("resnet50_e2e", ledger, _restore_gp,
                                   steps=count // batch_size)
        busy = telemetry.timer("feed.producer_busy").sum
        wait = telemetry.timer("feed.consumer_wait").sum
        overlap = max(0.0, 1.0 - wait / busy) if busy > 0 else 0.0
    finally:
        if feed is not None:
            feed.close()
        if it is not None:
            it.close()
        if not was_enabled:
            telemetry.disable()
        shutil.rmtree(tmp, ignore_errors=True)
    return count / dt, round(overlap, 3), goodput


def _e2e_line(batch_size, dtype="bfloat16", **kw):
    """The e2e config's line fields: rate + overlap + the goodput
    breakdown, measured in this process (the one that holds the
    chip)."""
    rate, overlap, goodput = bench_resnet50_e2e(batch_size,
                                                dtype=dtype, **kw)
    return {"img_per_s": round(rate, 1),
            "staging_overlap_frac": overlap,
            "goodput": goodput}



def _print_line(rec):
    """Emit one JSONL record carrying the degraded-environment flag
    (bench-hygiene contract: no emitted measurement without it)."""
    rec.setdefault("degraded_env", _ENV_DEGRADED["flag"])
    print(json.dumps(rec))

def _emit_with_retry(metric, fn, attempts=2, unit="tokens/s",
                     extra=None, extra_fn=None):
    """Run fn() with retries; emit one JSON line either way, keyed by the
    SAME metric name on success and failure.  ``extra_fn`` is called
    after a successful run for fields computed during it."""
    for attempt in range(attempts):
        try:
            val = fn()
            rec = {"metric": metric, "value": round(val, 1), "unit": unit,
                   "vs_baseline": None,
                   "degraded_env": _ENV_DEGRADED["flag"]}
            if extra:
                rec.update(extra)
            if extra_fn is not None:
                rec.update(extra_fn())
            print(json.dumps(rec))
            return val
        except Exception as e:
            if attempt == attempts - 1:
                print(json.dumps({"metric": metric,
                                  "error": str(e)[:200],
                                  "degraded_env": _ENV_DEGRADED["flag"]}))
            else:
                time.sleep(5)
    return None


def main():
    """Emission order is the contract: environment health first (its
    transfer reading precedes any compute), then the HEADLINE metrics -- ResNet bf16-scan + MFU,
    BERT bf16 + MFU, and the final vs_baseline line -- then the
    budget-gated garnish (LeNet, fp32, pipeline, e2e, seq sweep).  A
    driver timeout can only ever cost the garnish."""
    import mxnet_tpu as mx
    on_tpu = mx.num_tpus() > 0
    # CPU fallback keeps the harness runnable in dev; shrink the work.
    if on_tpu:
        lenet_bs, rn_bs = 256, 128
    else:
        lenet_bs, rn_bs = 64, 8

    # -- 0: environment health (fresh process, before any compute) ----
    try:
        health = bench_env_health(h2d_mb=64 if on_tpu else 8)
        health.update({"metric": "env_health", "budget_s": _BUDGET_S,
                       "degraded_env": _mark_env_health(health)})
        print(json.dumps(health))
    except Exception as e:
        print(json.dumps({"metric": "env_health", "error": str(e)[:200],
                          "degraded_env": None}))

    # -- 1: headline ResNet (compiled K-step loop, bf16, dispersion) --
    rn_scan = None
    rn_out = {}

    def _run_scan():
        med, mfu, wins = bench_resnet50_scan(
            rn_bs * 2 if on_tpu else rn_bs, k=10 if on_tpu else 2,
            dtype="bfloat16" if on_tpu else "float32",
            reps=4 if on_tpu else 2)
        rn_out["mfu"], rn_out["wins"] = mfu, wins
        return med
    rn_scan = _emit_with_retry(
        "resnet50_imagenet_train_bf16_scan", _run_scan, attempts=2,
        unit="img/s",
        extra_fn=lambda: {"mfu": rn_out.get("mfu"),
                          "min": min(rn_out.get("wins") or [0]),
                          "max": max(rn_out.get("wins") or [0]),
                          "windows": rn_out.get("wins"),
                          **_cost_extra("resnet50_bf16"),
                          **_goodput_extra("resnet50_bf16")})

    # -- 2: headline BERT (bs=256 is the single-chip knee, r4) --------
    def _emit_bert(metric, bs, seq, dt_name, iters, windows=1,
                   attempts=2):
        out = {}

        def run():
            tok, mfu, wins = bench_bert_base(bs, seq, dtype=dt_name,
                                             iters=iters,
                                             windows=windows)
            out["mfu"], out["wins"] = mfu, wins
            return tok

        def extra():
            rec = {"mfu": out.get("mfu"), "seq_len": seq,
                   "batch_size": bs,
                   **_cost_extra("bert_base_seq%d_%s" % (seq, dt_name))}
            if windows > 1:
                rec.update({"min": min(out["wins"]),
                            "max": max(out["wins"]),
                            "windows": out["wins"]})
            return rec
        return _emit_with_retry(metric, run, attempts=attempts,
                                extra_fn=extra)

    if on_tpu:
        _emit_bert("bert_base_pretrain_bfloat16", 256, 128,
                   "bfloat16", 12, windows=3)
    else:
        _emit_bert("bert_base_pretrain_float32", 2, 32, "float32", 3)

    # -- 3: the final vs_baseline line, emitted BEFORE any garnish ----
    # BASELINE.md anchor: MXNet-CUDA A100 ResNet-50 ~3000 img/s (AMP+DALI)
    headline = rn_scan
    if headline is None:
        # scan path failed twice: fall back to the per-step program so
        # the headline line still carries a real number
        try:
            headline = bench_resnet50(rn_bs * 2 if on_tpu else rn_bs,
                                      dtype="bfloat16")
        except Exception:
            headline = None
    baseline = 3000.0
    print(json.dumps({"metric": "resnet50_imagenet_train",
                      "value": round(headline, 1) if headline else None,
                      "unit": "img/s",
                      "vs_baseline": round(headline / baseline, 4)
                      if headline else None,
                      "degraded_env": _ENV_DEGRADED["flag"]}))

    # -- garnish (budget-gated; order = value per second) -------------
    # BASELINE config 5: bf16 AMP + LARS large-batch (the last named
    # BASELINE config without a bench line)
    if _budget_ok("resnet50_imagenet_train_bf16_lars_largebatch", 300):
        lars_out = {}

        def _run_lars():
            med, mfu, wins = bench_resnet50_lars(
                512 if on_tpu else rn_bs, k=10 if on_tpu else 2,
                dtype="bfloat16" if on_tpu else "float32",
                reps=3 if on_tpu else 1)
            lars_out["mfu"], lars_out["wins"] = mfu, wins
            return med
        _emit_with_retry(
            "resnet50_imagenet_train_bf16_lars_largebatch", _run_lars,
            attempts=1, unit="img/s",
            extra={"batch_size": 512 if on_tpu else rn_bs,
                   "optimizer": "lars"},
            extra_fn=lambda: {"mfu": lars_out.get("mfu"),
                              "windows": lars_out.get("wins"),
                              **_cost_extra("resnet50_lars_bf16"),
                              **_goodput_extra("resnet50_lars_bf16")})

    # MULTICHIP scaling line (ISSUE 9 bench contract): 1/2/4/8-device
    # SPMD train step, per-host efficiency + in-graph collective bytes
    if _budget_ok("multichip_scaling", 240):
        try:
            rows = _multichip_scaling_rows()
            _print_line({"metric": "multichip_scaling",
                         "unit": "img/s", "scaling": rows,
                         "vs_baseline": None})
        except Exception as e:
            _print_line({"metric": "multichip_scaling",
                         "error": str(e)[:200]})

    # batch-at-fixed-HBM sweep (ISSUE 20 bench contract: ROADMAP
    # item 1's sweep, predicted-vs-measured peak HBM per bucket)
    if _budget_ok("batch_hbm_sweep", 180):
        try:
            rec = bench_batch_hbm_sweep()
            _print_line({"metric": "batch_hbm_sweep", "unit": "bytes",
                         "vs_baseline": None, **rec})
        except Exception as e:
            _print_line({"metric": "batch_hbm_sweep",
                         "error": str(e)[:200]})

    # serving tier: latency-vs-QPS curve (ISSUE 8 bench contract)
    if _budget_ok("serving_latency_qps", 120):
        try:
            curve = bench_serving(
                offered_qps=(100, 400, 1600) if on_tpu else (50, 200),
                duration_s=2.0 if on_tpu else 1.0,
                clients=8 if on_tpu else 4)
            _print_line({"metric": "serving_latency_qps",
                         "curve": curve, "unit": "qps/ms",
                         "vs_baseline": None})
        except Exception as e:
            _print_line({"metric": "serving_latency_qps",
                         "error": str(e)[:200]})

    # always-on loop: hot-swap cost under live traffic (ISSUE 12 bench
    # contract: swap latency + p99-during-swap, zero dropped)
    if _budget_ok("serving_hotswap", 90):
        try:
            rec = bench_serving_hotswap(
                duration_s=3.0 if on_tpu else 2.0)
            _print_line({"metric": "serving_hotswap", "unit": "ms",
                         "vs_baseline": None, **rec})
        except Exception as e:
            _print_line({"metric": "serving_hotswap",
                         "error": str(e)[:200]})

    # generative tier: tokens/s + TTFT + inter-token tail through the
    # PRODUCT decode path (ISSUE 18 bench contract)
    if _budget_ok("serving_decode", 90):
        try:
            rec = bench_serving_decode(
                duration_s=3.0 if on_tpu else 2.0)
            _print_line({"metric": "serving_decode",
                         "unit": "tokens/s", "vs_baseline": None,
                         **rec})
        except Exception as e:
            _print_line({"metric": "serving_decode",
                         "error": str(e)[:200]})

    if _budget_ok("lenet_mnist_train", 120):
        _emit_with_retry("lenet_mnist_train",
                         lambda: bench_lenet(lenet_bs), attempts=1,
                         unit="img/s")

    if _budget_ok("lenet_mnist_train_scan", 120):
        _emit_with_retry(
            "lenet_mnist_train_scan",
            lambda: bench_lenet_scan(lenet_bs, k=50 if on_tpu else 4,
                                     reps=3 if on_tpu else 1),
            attempts=1, unit="img/s")

    if _budget_ok("lenet_mnist_train_imperative", 120):
        _emit_with_retry(
            "lenet_mnist_train_imperative",
            lambda: bench_lenet_imperative(lenet_bs,
                                           iters=30 if on_tpu else 5),
            attempts=1, unit="img/s")

    if on_tpu and _budget_ok("lenet_imperative_local_dispatch_cpu", 180):
        # Evidence for the dispatch-gap claim: the same imperative loop
        # on the CPU backend.  Run in CPU-pinned subprocesses so the CPU
        # backend can't disturb this process (and the children never
        # ask for the chip this process holds).
        try:
            val = _cpu_subprocess_value(
                "bench.bench_lenet_imperative(64, iters=20)")
            val2 = _cpu_subprocess_value("bench.bench_lenet(64)")
            _print_line({"metric":
                         "lenet_imperative_local_dispatch_cpu",
                         "value": round(val, 1), "unit": "img/s",
                         "vs_baseline": None,
                         "hybridized_local_cpu": round(val2, 1),
                         "imperative_over_hybridized":
                         round(val / val2, 3)})
        except Exception as e:
            _print_line({"metric": "lenet_imperative_local_dispatch",
                         "error": str(e)[:200]})

    if _budget_ok("resnet50_imagenet_train_fp32", 180):
        _emit_with_retry("resnet50_imagenet_train_fp32",
                         lambda: bench_resnet50(rn_bs), attempts=1,
                         unit="img/s")

    if _budget_ok("pipeline", 240):
        try:
            jpeg_ips, raw_ips, scaling = bench_pipeline(
                n=512 if on_tpu else 128, threads=2)
            _print_line({"metric": "pipeline_jpeg_decode",
                         "value": round(jpeg_ips, 1),
                         "unit": "img/s/host",
                         "host_cores": _os.cpu_count(),
                         "scaling": scaling,
                         "vs_baseline": None})
            _print_line({"metric": "pipeline_raw_uint8",
                         "value": round(raw_ips, 1),
                         "unit": "img/s/host",
                         "host_cores": _os.cpu_count(),
                         "vs_baseline": None})
        except Exception as e:
            _print_line({"metric": "pipeline", "error": str(e)[:200]})

    if on_tpu and _budget_ok("resnet50_imagenet_train_e2e_bf16", 600):
        try:
            # in-process: a chip belongs to one process, and this one
            # holds it.  The line carries rate + overlap + the goodput
            # breakdown, so the e2e-vs-synthetic gap arrives
            # auto-attributed (ISSUE 14).
            rec = _e2e_line(rn_bs * 2, dtype="bfloat16")
            _print_line({"metric": "resnet50_imagenet_train_e2e_bf16",
                         "value": rec["img_per_s"], "unit": "img/s",
                         "staging_overlap_frac":
                         rec["staging_overlap_frac"],
                         "goodput": rec.get("goodput"),
                         "vs_baseline": None})
        except Exception as e:
            _print_line({"metric": "resnet50_imagenet_train_e2e_bf16",
                         "error": str(e)[:200]})

    if on_tpu:
        # seq sweep: captures the XLA/Pallas crossover in the artifact
        # (auto path: seq 128 -> plain XLA attention, seq >= 256 ->
        # Pallas flash kernels)
        if _budget_ok("bert_base_pretrain_seq512_bf16", 300):
            _emit_bert("bert_base_pretrain_seq512_bf16", 64, 512,
                       "bfloat16", 10, attempts=1)
        if _budget_ok("bert_base_pretrain_seq1024_bf16_flash", 600):
            # long-context config: seq 1024 is where the Pallas flash
            # fwd+bwd kernels pull away from XLA (81k vs 60k tok/s, r3)
            _emit_bert("bert_base_pretrain_seq1024_bf16_flash", 16,
                       1024, "bfloat16", 10, attempts=1)

    print(json.dumps({"metric": "bench_complete",
                      "elapsed_s": round(time.monotonic() - _T_START, 1),
                      "budget_s": _BUDGET_S}))


if __name__ == "__main__":
    main()
