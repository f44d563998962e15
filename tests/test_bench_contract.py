"""Driver-contract tests for bench.py (an earlier artifact died at
rc=124 with the headline lines unprinted; this locks the headline-first
emission order and the self-budget so that regression class cannot ship
silently)."""
import json
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def bench_mod(monkeypatch):
    monkeypatch.syspath_prepend(REPO)   # cleaned up at teardown
    import bench
    # stub every device-touching benchmark
    monkeypatch.setattr(bench, "bench_env_health",
                        lambda **k: {"h2d_mb_per_s": 1.0,
                                     "dispatch_roundtrip_us": 2.0})
    monkeypatch.setattr(bench, "bench_resnet50_scan",
                        lambda *a, **k: (2600.0, 0.29, [2590.0, 2610.0]))
    monkeypatch.setattr(bench, "bench_bert_base",
                        lambda *a, **k: (126000.0, 0.43,
                                         [125000.0, 127000.0]))
    monkeypatch.setattr(bench, "bench_lenet", lambda *a, **k: 30000.0)
    monkeypatch.setattr(bench, "bench_resnet50_lars",
                        lambda *a, **k: (2400.0, 0.27, [2390.0, 2410.0]))
    monkeypatch.setattr(bench, "bench_serving",
                        lambda *a, **k: [
                            {"offered_qps": 100, "qps": 99.0,
                             "p50_ms": 3.0, "p95_ms": 5.0, "p99_ms": 7.0,
                             "mean_occupancy": 2.5, "shed": 0}])
    monkeypatch.setattr(bench, "bench_serving_hotswap",
                        lambda *a, **k: {
                            "swap_step": 4, "swap_latency_ms": 120.0,
                            "p50_steady_ms": 3.0, "p99_steady_ms": 7.0,
                            "p50_during_swap_ms": 3.5,
                            "p99_during_swap_ms": 9.0,
                            "requests": 1000,
                            "requests_during_swap": 80, "dropped": 0})
    monkeypatch.setattr(bench, "bench_serving_decode",
                        lambda *a, **k: {
                            "tokens_per_s": 4200.0, "streams": 60,
                            "ttft_p50_ms": 8.0, "ttft_p99_ms": 20.0,
                            "inter_token_p50_ms": 2.0,
                            "inter_token_p99_ms": 6.0,
                            "mean_occupancy": 3.1, "shed": 0})
    monkeypatch.setattr(bench, "bench_lenet_imperative",
                        lambda *a, **k: 25000.0)
    monkeypatch.setattr(bench, "bench_resnet50", lambda *a, **k: 1500.0)
    monkeypatch.setattr(bench, "bench_pipeline",
                        lambda *a, **k: (1500.0, 5000.0, {}))
    monkeypatch.setattr(bench, "_cpu_subprocess_value",
                        lambda *a, **k: 1000.0)
    monkeypatch.setattr(bench, "bench_batch_hbm_sweep",
                        lambda *a, **k: {
                            "probe": "resnet50v1-nchw-sgd-224",
                            "hbm_budget_bytes": 16 << 30,
                            "const_bytes": 98000000,
                            "per_item_bytes": 2000000,
                            "buckets": [
                                {"batch": 64,
                                 "predicted_peak_hbm_bytes": 226000000,
                                 "measured_peak_hbm_bytes": 230000000,
                                 "rel_error": -0.0174, "fits": True}],
                            "largest_fit_bucket": 64})
    monkeypatch.setattr(bench, "_multichip_scaling_rows",
                        lambda *a, **k: [
                            {"n_devices": 1, "img_per_s": 1000.0,
                             "per_device_img_per_s": 1000.0,
                             "efficiency": 1.0, "collectives": {},
                             "collective_bytes": 0},
                            {"n_devices": 2, "img_per_s": 1800.0,
                             "per_device_img_per_s": 900.0,
                             "efficiency": 0.9,
                             "collectives": {"all-reduce":
                                             {"count": 7,
                                              "bytes": 67884}},
                             "collective_bytes": 67884}])
    # the e2e config runs in-process (one process per chip) and its
    # line carries rate + overlap + goodput breakdown (ISSUE 14)
    _e2e_goodput = {
        "steps": 32, "wall_s": 4.1, "mfu": 0.21,
        "shares": {"device_compute": 0.41, "input_wait": 0.46,
                   "host_sync": 0.02, "checkpoint_stall": 0.0,
                   "recompile": 0.0, "other": 0.11},
        "verdict": "input-bound: feed supplies 47% of device demand",
        "bound": "input", "reconciled": True, "env_degraded": False}
    monkeypatch.setattr(
        bench, "_e2e_line",
        lambda *a, **k: {"img_per_s": 2000.0,
                         "staging_overlap_frac": 0.8,
                         "goodput": _e2e_goodput})
    # the scan/LARS configs stash their ledger windows here (stubbed
    # fns skip the real ledger; the shape is the contract)
    monkeypatch.setattr(bench, "_GOODPUT", {
        "resnet50_bf16": {
            "steps": 40, "wall_s": 3.9, "mfu": 0.29,
            "shares": {"device_compute": 0.93, "input_wait": 0.0,
                       "host_sync": 0.01, "checkpoint_stall": 0.0,
                       "recompile": 0.0, "other": 0.06},
            "verdict": "compute-bound: device busy 93% of wall",
            "bound": "compute", "reconciled": True,
            "env_degraded": False},
        "resnet50_lars_bf16": {
            "steps": 30, "wall_s": 3.2, "mfu": 0.27,
            "shares": {"device_compute": 0.9, "input_wait": 0.0,
                       "host_sync": 0.01, "checkpoint_stall": 0.0,
                       "recompile": 0.0, "other": 0.09},
            "verdict": "compute-bound: device busy 90% of wall",
            "bound": "compute", "reconciled": True,
            "env_degraded": False}})
    # _emit_with_retry sleeps between real retries; stubs don't need it
    monkeypatch.setattr(bench.time, "sleep", lambda s: None)
    import mxnet_tpu as mx
    monkeypatch.setattr(mx, "num_tpus", lambda: 1)
    return bench


def _metrics(capsys):
    lines = [json.loads(ln) for ln in
             capsys.readouterr().out.strip().splitlines()]
    return [ln["metric"] for ln in lines], lines


def test_headline_lines_emit_first(bench_mod, capsys):
    bench_mod.main()
    metrics, lines = _metrics(capsys)
    # the contract: health, then resnet scan + bert + vs_baseline,
    # BEFORE any garnish -- a driver timeout can only cost the tail
    assert metrics[0] == "env_health"
    assert metrics[1] == "resnet50_imagenet_train_bf16_scan"
    assert metrics[2] == "bert_base_pretrain_bfloat16"
    assert metrics[3] == "resnet50_imagenet_train"
    by = {ln["metric"]: ln for ln in lines}
    scan = by["resnet50_imagenet_train_bf16_scan"]
    assert scan["mfu"] == 0.29 and scan["min"] and scan["max"]
    bert = by["bert_base_pretrain_bfloat16"]
    assert bert["mfu"] == 0.43 and "windows" in bert
    head = by["resnet50_imagenet_train"]
    assert head["vs_baseline"] == round(2600.0 / 3000.0, 4)
    assert metrics[-1] == "bench_complete"


def test_every_emitted_line_carries_degraded_env(bench_mod, capsys):
    """ISSUE 11 satellite (bench hygiene): every emitted JSONL line
    carries a `degraded_env` boolean derived from the env_health
    probe's dispatch_roundtrip threshold, so a degraded environment
    can never be read as a perf regression."""
    bench_mod.main()
    _names, lines = _metrics(capsys)
    for ln in lines:
        if ln["metric"] == "bench_complete" or ln.get("skipped"):
            continue
        assert "degraded_env" in ln, ln["metric"]
    by = {ln["metric"]: ln for ln in lines}
    # the stub probe reports a 2us dispatch RTT: healthy
    assert by["env_health"]["degraded_env"] is False
    assert by["resnet50_imagenet_train_bf16_scan"]["degraded_env"] is False
    assert by["resnet50_imagenet_train"]["degraded_env"] is False


def test_degraded_env_flips_on_slow_dispatch(bench_mod, capsys,
                                             monkeypatch):
    """A dispatch round trip far past the threshold (~90ms) marks
    EVERY line degraded, headline included."""
    monkeypatch.setattr(bench_mod, "bench_env_health",
                        lambda **k: {"h2d_mb_per_s": 1.0,
                                     "dispatch_roundtrip_us": 90000.0})
    bench_mod.main()
    _names, lines = _metrics(capsys)
    by = {ln["metric"]: ln for ln in lines}
    assert by["env_health"]["degraded_env"] is True
    assert by["resnet50_imagenet_train_bf16_scan"]["degraded_env"] is True
    assert by["resnet50_imagenet_train"]["degraded_env"] is True


def test_budget_exhaustion_skips_garnish_only(bench_mod, capsys,
                                              monkeypatch):
    monkeypatch.setattr(bench_mod, "_BUDGET_S", 0.001)
    bench_mod.main()
    metrics, lines = _metrics(capsys)
    # headline metrics always emit regardless of budget
    assert metrics[1] == "resnet50_imagenet_train_bf16_scan"
    assert metrics[3] == "resnet50_imagenet_train"
    skipped = [ln for ln in lines if ln.get("skipped")]
    assert skipped, "optional configs must emit skip lines, not die"
    for ln in skipped:
        assert "budget" in ln["reason"]
    # nothing headline may be in the skipped set
    names = {ln["metric"] for ln in skipped}
    assert not names & {"resnet50_imagenet_train_bf16_scan",
                        "bert_base_pretrain_bfloat16",
                        "resnet50_imagenet_train", "env_health"}


def test_batch_hbm_sweep_line_contract(bench_mod, capsys):
    """ISSUE 20 bench contract (ROADMAP item 1's sweep): the
    batch_hbm_sweep line carries predicted-vs-measured peak HBM per
    bucket, the fitted const/per-item line, the budget, the largest
    fitting bucket -- and the degraded_env flag like every line."""
    bench_mod.main()
    _names, lines = _metrics(capsys)
    by = {ln["metric"]: ln for ln in lines}
    rec = by["batch_hbm_sweep"]
    assert "degraded_env" in rec
    assert rec["hbm_budget_bytes"] > 0
    assert rec["const_bytes"] >= 0 and rec["per_item_bytes"] >= 0
    for b in rec["buckets"]:
        assert {"batch", "predicted_peak_hbm_bytes",
                "measured_peak_hbm_bytes", "rel_error",
                "fits"} <= set(b)
    assert rec["largest_fit_bucket"] == 64


def test_batch_hbm_sweep_is_hbm_plan_driven(monkeypatch):
    """The sweep's predictions must come from analysis.memory.hbm_plan
    and its measurements from executable_memory (the planner's accuracy
    contract) -- not bench-local extrapolation.  Uses the UNPATCHED
    module (the bench_mod fixture stubs the function)."""
    import inspect
    monkeypatch.syspath_prepend(REPO)
    import bench
    src = inspect.getsource(bench.bench_batch_hbm_sweep)
    assert "hbm_plan" in src
    assert "executable_memory" in src
    assert "device_hbm_bytes" in src


def test_e2e_runs_on_library_device_feed(bench_mod):
    """ISSUE 4: the e2e config must measure the PRODUCT's staging path
    (mxnet_tpu.dataio.DeviceFeed), not bench-local scaffolding -- no
    private producer thread, no hand-rolled slab queue, and the overlap
    fraction must come from the feed.* telemetry instruments."""
    import inspect
    src = inspect.getsource(bench_mod.bench_resnet50_e2e)
    assert "DeviceFeed" in src
    assert "threading.Thread" not in src
    assert "slab_q" not in src
    assert "feed.producer_busy" in src and "feed.consumer_wait" in src


def test_headline_configs_persist_cost_reports(monkeypatch):
    """ISSUE 6: the ResNet-50 and BERT configs must persist CostReport
    artifacts next to their JSONL lines via the library path
    (mx.profiling.report_for), not bench-local accounting.  Uses the
    UNPATCHED module (the bench_mod fixture stubs these functions)."""
    import inspect
    monkeypatch.syspath_prepend(REPO)
    import bench
    src = inspect.getsource(bench.bench_resnet50_scan)
    assert "_persist_cost_report" in src
    src = inspect.getsource(bench.bench_bert_base)
    assert "_persist_cost_report" in src
    src = inspect.getsource(bench._persist_cost_report)
    assert "profiling.report_for" in src


def test_cost_report_schema_locked(bench_mod, tmp_path, monkeypatch,
                                   v5e_peaks):
    """The persisted artifact's schema is the mxprof contract: totals,
    reconciled categories, memory, roofline with bound labels."""
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.parallel import TrainStep
    monkeypatch.setenv("MXNET_TPU_PROFILING_DIR", str(tmp_path))
    net = gluon.nn.Dense(4)
    net.initialize()
    net.hybridize()
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1}, kvstore=None)
    step = TrainStep(net, gluon.loss.L2Loss(), tr, mesh=None)
    step(mx.nd.array(np.ones((8, 6), np.float32)),
         mx.nd.array(np.ones((8, 4), np.float32)))
    path = bench_mod._persist_cost_report("contract_probe", step,
                                          step_time_s=0.01,
                                          items_per_step=8)
    assert path and os.path.isfile(path)
    rep = json.load(open(path))
    assert rep["schema"] == "mxprof.cost_report.v1"
    for key in ("label", "fingerprint", "totals", "memory",
                "categories", "provenance", "roofline"):
        assert key in rep, key
    assert set(rep["categories"]) == {
        "conv_dot", "collective", "transpose_layout",
        "elementwise_fusion", "other"}
    f_sum = sum(c["flops"] for c in rep["categories"].values())
    assert abs(f_sum - rep["totals"]["flops"]) < 1
    for v in rep["roofline"]["categories"].values():
        assert v["bound"] in ("compute", "memory")
    # and the emitted line's extra fields resolve from the artifact
    extra = bench_mod._cost_extra("contract_probe")
    assert extra["cost_report"] == path
    assert extra["hlo_top_category"] in rep["categories"]


def test_lars_baseline_config5_emits(bench_mod, capsys):
    """ISSUE 8 satellite: BASELINE config 5 (bf16 AMP + LARS
    large-batch ResNet-50) emits img/s + MFU into the BENCH JSONL."""
    bench_mod.main()
    _metrics_list, lines = _metrics(capsys)
    by = {ln["metric"]: ln for ln in lines}
    rec = by["resnet50_imagenet_train_bf16_lars_largebatch"]
    assert rec["value"] == 2400.0 and rec["unit"] == "img/s"
    assert rec["mfu"] == 0.27 and rec["optimizer"] == "lars"
    assert rec["windows"] == [2390.0, 2410.0]


def test_lars_and_serving_use_library_paths(monkeypatch):
    """Source contract on the UNPATCHED module: the LARS config trains
    through the registered 'lars' optimizer, and bench_serving drives
    the product serving path (mx.serving.ModelRegistry + serving.*
    telemetry), not bench-local scaffolding."""
    import inspect
    monkeypatch.syspath_prepend(REPO)
    import bench
    src = inspect.getsource(bench.bench_resnet50_lars)
    assert '"lars"' in src and "TrainStep" in src
    assert "_persist_cost_report" in src
    sv = inspect.getsource(bench.bench_serving)
    assert "ModelRegistry" in sv
    assert "serving.batches" in sv and "serving.responses" in sv


def test_serving_curve_emits(bench_mod, capsys):
    """The bench contract: a latency-vs-QPS curve rides one JSONL line
    with per-level percentiles and occupancy."""
    bench_mod.main()
    _metrics_list, lines = _metrics(capsys)
    by = {ln["metric"]: ln for ln in lines}
    rec = by["serving_latency_qps"]
    assert isinstance(rec["curve"], list) and rec["curve"]
    level = rec["curve"][0]
    for key in ("offered_qps", "qps", "p50_ms", "p95_ms", "p99_ms",
                "mean_occupancy", "shed"):
        assert key in level, key


def test_serving_hotswap_line_emits(bench_mod, capsys):
    """ISSUE 12 bench contract: the hot-swap line carries swap latency,
    p99-during-swap vs steady, and the zero-dropped count."""
    bench_mod.main()
    _metrics_list, lines = _metrics(capsys)
    by = {ln["metric"]: ln for ln in lines}
    rec = by["serving_hotswap"]
    assert rec["unit"] == "ms"
    for key in ("swap_step", "swap_latency_ms", "p99_during_swap_ms",
                "p99_steady_ms", "p50_during_swap_ms", "p50_steady_ms",
                "requests_during_swap", "dropped"):
        assert key in rec, key
    assert rec["dropped"] == 0


def test_serving_decode_line_emits(bench_mod, capsys):
    """ISSUE 18 bench contract: the generative-tier line carries
    tokens/s, TTFT and inter-token percentiles, occupancy, and shed."""
    bench_mod.main()
    _metrics_list, lines = _metrics(capsys)
    by = {ln["metric"]: ln for ln in lines}
    rec = by["serving_decode"]
    assert rec["unit"] == "tokens/s"
    for key in ("tokens_per_s", "streams", "ttft_p50_ms",
                "ttft_p99_ms", "inter_token_p50_ms",
                "inter_token_p99_ms", "mean_occupancy", "shed"):
        assert key in rec, key
    assert "degraded_env" in rec


def test_serving_decode_bench_uses_product_path(monkeypatch):
    """Source contract on the UNPATCHED module: the generative bench
    streams through ModelRegistry.register_generative/generate and
    reads the decode.* telemetry counters, not bench-local
    scaffolding."""
    import inspect
    monkeypatch.syspath_prepend(REPO)
    import bench
    src = inspect.getsource(bench.bench_serving_decode)
    assert "register_generative" in src and "reg.generate" in src
    assert "decode.steps" in src and "decode.tokens" in src


def test_hotswap_bench_uses_product_loop(monkeypatch):
    """Source contract on the UNPATCHED module: the hot-swap bench
    drives the PRODUCT always-on loop (ContinuousTrainer publishing
    checkpoints + RegistryWatcher re-registering), not bench-local
    scaffolding."""
    import inspect
    monkeypatch.syspath_prepend(REPO)
    import bench
    src = inspect.getsource(bench.bench_serving_hotswap)
    assert "ContinuousTrainer" in src and "RegistryWatcher" in src
    assert "poll_once" in src


def test_multichip_scaling_line_emits(bench_mod, capsys):
    """ISSUE 9 bench contract: the MULTICHIP scaling line rides one
    JSONL line with img/s, per-device efficiency, and in-graph
    collective bytes per device count."""
    bench_mod.main()
    _metrics_list, lines = _metrics(capsys)
    by = {ln["metric"]: ln for ln in lines}
    rec = by["multichip_scaling"]
    assert rec["unit"] == "img/s"
    rows = rec["scaling"]
    assert [r["n_devices"] for r in rows] == [1, 2]
    for r in rows:
        for key in ("img_per_s", "per_device_img_per_s", "efficiency",
                    "collectives", "collective_bytes"):
            assert key in r, key
    # multi-device rows must carry the in-graph gradient all-reduce
    assert rows[1]["collectives"]["all-reduce"]["bytes"] > 0


def test_multichip_scaling_real_two_device(monkeypatch):
    """The UNSTUBBED sweep on the suite's virtual devices: the 2-device
    compiled step's collective profile lists the GSPMD gradient
    all-reduce with non-zero bytes (in-graph, not host kvstore)."""
    monkeypatch.syspath_prepend(REPO)
    import bench
    rows = bench.bench_multichip_scaling(device_counts=(1, 2),
                                         batch_per_device=8, iters=2,
                                         warmup=1)
    assert rows[0]["collective_bytes"] == 0
    assert rows[0]["efficiency"] == 1.0
    two = rows[1]
    assert two["n_devices"] == 2
    assert two["collectives"]["all-reduce"]["count"] > 0
    assert two["collective_bytes"] > 0
    assert two["img_per_s"] > 0 and two["efficiency"] > 0


def test_scan_and_e2e_lines_carry_goodput_breakdown(bench_mod, capsys):
    """ISSUE 14 acceptance: the scan, LARS, and e2e lines carry the
    StepLedger breakdown (per-category shares + the attribution
    verdict), so the synthetic-vs-e2e gap is auto-attributed -- the
    e2e stub reads input-bound while the synthetic scan reads
    compute-bound, which IS the r04 1258-vs-2474 attribution."""
    bench_mod.main()
    _names, lines = _metrics(capsys)
    by = {ln["metric"]: ln for ln in lines}
    for metric, bound in (
            ("resnet50_imagenet_train_bf16_scan", "compute"),
            ("resnet50_imagenet_train_bf16_lars_largebatch", "compute"),
            ("resnet50_imagenet_train_e2e_bf16", "input")):
        gp = by[metric].get("goodput")
        assert gp, "%s line missing goodput" % metric
        for key in ("steps", "wall_s", "shares", "verdict", "bound",
                    "reconciled", "env_degraded"):
            assert key in gp, (metric, key)
        assert gp["bound"] == bound, (metric, gp)
        assert set(gp["shares"]) == {
            "device_compute", "input_wait", "host_sync",
            "checkpoint_stall", "recompile", "other"}
    e2e = by["resnet50_imagenet_train_e2e_bf16"]["goodput"]
    assert "feed supplies" in e2e["verdict"]


def test_e2e_bench_runs_the_ledger(monkeypatch):
    """Source contract on the UNPATCHED module: the e2e config measures
    through the library StepLedger (obs.goodput), not bench-local
    accounting, and the scan/LARS configs do the same."""
    import inspect
    monkeypatch.syspath_prepend(REPO)
    import bench
    for fn in (bench.bench_resnet50_e2e, bench.bench_resnet50_scan,
               bench.bench_resnet50_lars):
        src = inspect.getsource(fn)
        assert "_goodput_begin" in src and "_goodput_end" in src, \
            fn.__name__
    src = inspect.getsource(bench._goodput_begin)
    assert "StepLedger" in src
    src = inspect.getsource(bench._goodput_end)
    assert "line_summary" in src


def test_degraded_env_flag_agrees_with_goodput_env_guard(monkeypatch):
    """ISSUE 14 satellite (contract-locked): the JSONL degraded_env
    flag and the sentinel's goodput.env_degraded event derive from ONE
    threshold -- when the env guard trips, both say degraded; when
    healthy, both say healthy."""
    import numpy as np  # noqa: F401
    from mxnet_tpu import telemetry
    from mxnet_tpu.obs import goodput
    monkeypatch.syspath_prepend(REPO)
    import bench
    was = telemetry.enabled()
    telemetry.enable()
    monkeypatch.setattr(bench, "_ENV_DEGRADED", {"flag": None})
    try:
        telemetry.reset("goodput.")
        telemetry.reset("env.")
        # degraded environment: the probe marks the line degraded AND
        # sets the gauge the sentinel's env guard reads
        flag = bench._mark_env_health(
            {"dispatch_roundtrip_us": 90000.0, "h2d_mb_per_s": 1.0})
        assert flag is True
        led = goodput.StepLedger(window_steps=2)
        telemetry.timer("profiling.step_time").observe(0.004)
        win = led.step(2)
        assert win["env_degraded"] is flag is True
        assert telemetry.counter(
            "goodput.env_degraded_windows").value == 1
        ev = telemetry.event("goodput.env_degraded").recent[-1]
        assert ev["dispatch_roundtrip_us"] == 90000.0
        assert win["regressions"] == []       # env, never regression
        # healthy probe: both sides flip together
        flag = bench._mark_env_health(
            {"dispatch_roundtrip_us": 2.0, "h2d_mb_per_s": 100.0})
        telemetry.timer("profiling.step_time").observe(0.004)
        win = led.step(2)
        assert win["env_degraded"] is flag is False
        assert telemetry.counter(
            "goodput.env_degraded_windows").value == 1
    finally:
        telemetry.reset("goodput.")
        telemetry.reset("env.")
        if not was:
            telemetry.disable()


def test_scan_failure_falls_back_for_headline(bench_mod, capsys,
                                              monkeypatch):
    def boom(*a, **k):
        raise RuntimeError("compile dropped")
    monkeypatch.setattr(bench_mod, "bench_resnet50_scan", boom)
    monkeypatch.setattr(bench_mod, "_BUDGET_S", 0.001)
    bench_mod.main()
    metrics, lines = _metrics(capsys)
    by = {ln["metric"]: ln for ln in lines}
    # the final line still carries a real number from the fallback
    head = by["resnet50_imagenet_train"]
    assert head["value"] == 1500.0
    assert head["vs_baseline"] == 0.5


def test_children_of_the_bench_never_ask_for_the_chip(monkeypatch):
    """One process per chip: main() has touched JAX and holds it, so
    every child bench.py starts is pinned to the CPU backend."""
    import inspect
    monkeypatch.syspath_prepend(REPO)
    import bench
    starters = (bench._cpu_subprocess_value, bench._multichip_scaling_rows)
    for fn in starters:
        assert 'env["JAX_PLATFORMS"] = "cpu"' in inspect.getsource(fn), \
            fn.__name__
    assert inspect.getsource(bench).count("subprocess.run(") == \
        len(starters)
