"""``ouro_serve_closed16``: the configuration's file against the catalog's
values key by key, the cell, the mix and the two metrics held by index,
a rehearsal of the cell's command on the CPU (and of the command held to
each control), the shape functions against hand-worked numbers, and the
two readers on hand-built runs."""
import json
import os
import types

import pytest

import _entries
from perfbench.families import ouro
from perfbench.harness import program_trace, xplane
from perfbench.harness.spec import Cell, SpecError, sized
from perfbench.harness.traffic import length_population

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "ouro_serve_closed16"
CONFIG = "ouro-2.6b"
MIX = "closed_loop_p64"
# huggingface.co/ByteDance/Ouro-2.6B, config.json (the catalog's row)
PUBLISHED = {
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
    "max_position_embeddings": 65536, "max_window_layers": 48,
    "model_type": "ouro", "num_attention_heads": 16,
    "num_hidden_layers": 48, "num_key_value_heads": 16,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False,
    "total_ut_steps": 4, "early_exit_threshold": 1,
    "use_sliding_window": False, "vocab_size": 49152}


def _bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _config(rehearse=False):
    with open(os.path.join(REPO, "perfbench", "configs",
                           CONFIG + ".json")) as f:
        return sized(json.load(f), rehearse)


def _mix():
    with open(os.path.join(REPO, "perfbench", "traffic",
                           MIX + ".json")) as f:
        return json.load(f)


# ---------------------------------------------------------------------
# the configuration's file, the mix and the cell
# ---------------------------------------------------------------------

def test_every_published_key_is_in_the_file_and_only_the_context_is_cut():
    cfg = _config()
    assert cfg["reduced"] == ["max_position_embeddings"]
    for key, value in PUBLISHED.items():
        assert cfg[key] == (512 if key == "max_position_embeddings"
                            else value), key
    assert cfg["published"] == {"max_position_embeddings": 65536}
    assert cfg["family"] == "ouro" and cfg["serving_dtype"] == "bfloat16"
    # nothing of the model is cut: every layer, every pass, every row
    assert len(cfg["layer_types"]) == cfg["num_hidden_layers"] == 48
    assert ouro.cache_layers(cfg) == 192
    for key in ("norms", "final_norm", "exit_gate", "qk_norm_and_bias",
                "rotary", "cache", "arithmetic", "decoding", "layout",
                "max_position_embeddings"):
        assert cfg["assumed"][key].strip(), key
    entry = _bench()["configs"][5]
    assert entry["name"] == CONFIG and entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    assert "model_type ouro" in entry["source"]
    assert "whole: 48 layers x 4 passes, full vocabulary" in entry["source"]
    assert entry["source"].startswith(
        "https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json")


def test_the_deployment_holds_the_mixs_longest_request_in_every_slot():
    cfg, mix = _config(), _mix()
    dep = cfg["deployment"]
    assert (dep["chips"], dep["chips_sharing_a_layer"]) == (1, 1)
    assert dep["slots"] == mix["clients"] == max(dep["decode_buckets"]) == 16
    assert dep["decode_buckets"] == [8, 16]
    assert dep["prefill_buckets"] == [32, 64, 128]
    assert dep["block_size"] == 64 and dep["kv_dtype"] == "bfloat16"
    longest = mix["prompt_len"]["max"] + mix["output_len"]["max"]
    assert longest == 320 <= cfg["max_position_embeddings"] == 8 * 64
    assert dep["num_blocks"] == 16 * -(-longest // 64) + 1 == 81
    assert max(dep["prefill_buckets"]) == mix["prompt_len"]["max"]
    for key in ("what", "cache_rule", "peak_hbm_measured_how"):
        assert dep[key].strip(), key
    limit = 16909336064                 # a v5e's memory_stats() limit
    assert 0.25 * limit <= dep["peak_hbm_measured_bytes"] <= 0.96 * limit
    # the check's streams fit its width, every control is a reference
    chk = cfg["check"]
    assert chk["streams"] == 8 and chk["max_new"] == 64
    assert mix["prompt_len"]["max"] + chk["max_new"] <= chk["width"] == 320
    assert chk["why"].strip()
    assert cfg["trace"] == {"paged_attention": "^paged_attention_pallas"}


def test_the_mix_is_the_issues_letter_for_letter():
    mix = _mix()
    assert mix["kind"] == "closed_loop" and mix["clients"] == 16
    assert mix["prompt_len"] == {"median": 64, "sigma": 0.6, "min": 16,
                                 "max": 128}
    assert mix["output_len"] == {"median": 128, "sigma": 0.4, "min": 32,
                                 "max": 192}
    assert mix["preroll_s"] == 8 and mix["trace_seconds"] == 4
    assert "rehearse" in mix and mix["what"].strip()
    prompts = length_population(mix["prompt_len"])
    outputs = length_population(mix["output_len"])
    assert (min(prompts), max(prompts)) == (16, 128)
    assert (min(outputs), max(outputs)) == (46, 192)
    # prompts shorter than answers: the reasoning shape
    assert sum(prompts) / 96 < 80 < 130 < sum(outputs) / 96


def entries(bench):
    """What the benchmark holds of the cell, whatever later cells were
    appended after it."""
    entry = _entries.entry_at(bench, "workloads", CELL, 6)
    assert (entry["config"], entry["traffic"], entry["chips"]) \
        == (CONFIG, MIX, 1)
    _entries.entry_at(bench, "configs", CONFIG, 5)
    assert _entries.reported(bench, CELL, "end_to_end") \
        == {"serve_tokens_per_s", "setup_s"}
    new = ["loop_pass_ms.serve", "ut_passes_per_token.serve"]
    engine = "serving engine (serving/decode/engine.py)"
    want = [("ms", "lower", "device_trace", engine),
            ("passes", "lower", "program_counter", engine)]
    for m, (unit, better, source, name) in zip(_entries.metrics_in_order(
            bench, ["attention_window_ms.serve"] + new)[1:], want):
        assert (m["unit"], m["better"], m["source"], m["layer"]) \
            == (unit, better, source, name)
        assert m["moves"] == "serve_tokens_per_s"
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    _entries.first_of_its_own(bench, CELL, new)
    assert _entries.reported(bench, CELL) == set(new) | {
        "mfu.serve", "paged_attention_roofline.serve",
        "decode_step_ms.serve", "prefill_ms.serve",
        "device_idle_share.serve", "peak_hbm_gb.serve", "itl_p95_ms.closed",
        "kv_write_ms.serve", "host_loop_ms.serve", "slot_occupancy.serve",
        "attention_full_ms.serve", "cache_hit_share.setup",
        "decode_device_ms.serve", "prefill_device_share.serve",
        "prefill_us_per_token.serve", "prefill_attention_us_per_token.serve"}
    # appended to each list: behind every cell the benchmark had
    _entries.after_earlier_cells(bench, CELL,
                                 _entries.names(bench["workloads"][:6]))
    _entries.among_four_chip_cells(bench, "bert_train_dp4")


def test_the_cell_and_its_two_metrics_are_appended_entries():
    cell = Cell(REPO, CELL)
    assert cell.chips == 1 and cell.family() is ouro
    entries(_bench())
    for reader in ("loop_pass_ms.serve", "ut_passes_per_token.serve"):
        assert callable(cell.layer_reader(reader))


# ---------------------------------------------------------------------
# the command, rehearsed
# ---------------------------------------------------------------------

def test_a_program_without_the_model_fails_the_cell_cleanly(monkeypatch):
    import mxnet_tpu.serving.decode as decode
    monkeypatch.delattr(decode, "LoopedDecoder")
    with pytest.raises(SpecError, match="this program cannot run the "
                                        "configuration"):
        ouro.build_model(_config(rehearse=True), 0)


def test_the_cells_command_rehearses_and_counts_four_passes_a_token(
        tmp_path):
    from test_perfbench_command import (_copy_of_the_benchmark, _records,
                                        _run)
    root = str(_copy_of_the_benchmark(tmp_path))
    out = _run(["--workload", CELL, "--seed", "3000034007", "--seconds",
                "1", "--trace", "1", "--rehearse"], root=root,
               pythonpath=REPO)
    assert out.returncode == 0, out.stderr[-3000:]
    assert out.stdout.rstrip().endswith("REHEARSAL")
    by = {r["event"]: r for r in _records(out)}
    assert by["rehearsed"]["correct"] is True
    assert by["rehearsed"]["failed"] == 0
    check = by["reference_check"]
    assert check["reference_precision"] == "highest"
    assert check["tokens_judged"] == check["tokens_equal_reference_argmax"]
    assert check["logit_gap_system_vs_reference"] < 1e-4
    # the span's counts feed the reader on a CPU too; the device's do not
    assert "ut_passes_per_token.serve" in by["per_layer"]["produced"]
    assert "loop_pass_ms.serve" in by["per_layer"]["missing"]
    assert set(by["ut_passes"]["fields"]) >= {"ut_passes", "exit_early"}


@pytest.mark.parametrize("control", [ouro.CONTROL_PRECISION,
                                     ouro.CONTROL_ONE_PASS_FEWER])
def test_the_cell_held_to_a_control_reference_is_not_correct(tmp_path,
                                                             control):
    """The whole command, rehearsed from a copy whose configuration lists
    a control in place of the reference: weights through float8, or one
    pass fewer."""
    from test_perfbench_command import (_copy_of_the_benchmark, _records,
                                        _run)
    root = _copy_of_the_benchmark(tmp_path)
    path = root / "perfbench/configs" / (CONFIG + ".json")
    cfg = json.load(open(path))
    refs = cfg["rehearse"]["check"]["references"]
    assert [r["precision"] for r in refs] == ["highest"]
    refs[0]["precision"] = control
    with open(path, "w") as f:
        json.dump(cfg, f)
    out = _run(["--workload", CELL, "--seed", "7", "--seconds", "1",
                "--trace", "0", "--rehearse"], root=str(root),
               pythonpath=REPO)
    assert out.returncode == 0, out.stderr[-3000:]
    records = _records(out)
    why = [r["why"] for r in records if r["event"] == "incorrect"]
    assert why and all(control in w for w in why), why
    assert {r["event"]: r for r in records}["rehearsed"]["correct"] is False


def test_the_float8_control_rounds_its_weights_through_float8():
    import jax.numpy as jnp
    import numpy as np
    cfg = _config(rehearse=True)
    model, params = ouro.build_model(cfg, 2)
    ref_params = ouro.reference_params(params, cfg)
    rounded = {k: (v.astype(jnp.float8_e4m3fn).astype(v.dtype)
                   if v.ndim == 2 and "embed" not in k else v)
               for k, v in ref_params.items()}
    tokens = jnp.asarray(np.random.RandomState(1).randint(0, 128, 24))
    control = ouro.make_exit_reference(cfg, ouro.CONTROL_PRECISION)(
        ref_params, tokens)[0]
    plain = ouro.make_exit_reference(cfg)
    np.testing.assert_allclose(control, plain(rounded, tokens)[0],
                               atol=1e-5)
    assert float(jnp.abs(control - plain(ref_params, tokens)[0]).max()) \
        > 0.05


# ---------------------------------------------------------------------
# shape functions, by hand
# ---------------------------------------------------------------------

def test_a_token_keeps_1572864_bytes_and_a_layer_has_51_million_weights():
    cfg = _config()
    assert ouro.kv_bytes_per_token(cfg) == 192 * 2 * 16 * 128 * 2 \
        == 1572864
    # attention 4 x 2048^2 = 16.78 M, SwiGLU 3 x 2048 x 5632 = 34.60 M
    assert ouro.matmul_params(cfg) == 16777216 + 34603008
    # with the four norms 51.39 M a layer; 48 layers in bfloat16: 4.93 GB
    assert ouro.loop_weight_bytes(cfg) == 48 * 2 * (51380224 + 8192) \
        == 4933287936
    # the cache: 192 cache layers x 81 blocks x 64 tokens x 8,192 B
    dep = cfg["deployment"]
    assert ouro.cache_layers(cfg) * dep["num_blocks"] * dep["block_size"] \
        * 2 * 16 * 128 * 2 == 8153726976


def test_paged_attention_cost_by_hand():
    cfg = _config()
    flops, nbytes = ouro.paged_attention_cost(cfg, 1000)
    # 4 x 48 kernel calls a step, 4 FLOPs a lane of 16 heads of 128
    assert flops == 4 * 4 * 48 * 16 * 128 * 1000 == 1572864000
    assert nbytes == 1572864 * 1000


def test_served_flops_by_hand():
    cfg = _config()
    matmuls = 4 * 48 * 2 * (4 * 2048 ** 2 + 3 * 2048 * 5632)
    assert matmuls == 19730006016
    leave = 2 * 2048 * 49152 + 2 * 2048 * 4
    pair = 4 * 4 * 48 * 2048
    # one decode token over a context of 100
    assert ouro.served_flops(cfg, 1, 100, []) \
        == matmuls + leave + pair * 100
    # one prompt of 10 tokens: 55 visible pairs, one emitted token
    assert ouro.served_flops(cfg, 0, 0, [10]) \
        == 10 * matmuls + leave + pair * 55
    assert ouro.served_flops(cfg, 3, 700, [10, 20]) \
        == 33 * matmuls + 5 * leave + pair * (700 + 55 + 210)


# ---------------------------------------------------------------------
# the readers, on hand-built runs
# ---------------------------------------------------------------------

D0 = "/device:TPU:0"
LOOP = ("mx.ut_loop",)
BODY = LOOP + ("while", "body", "closed_call")


def _fake_run(spans=(), ops=(), modules=(), matched=None, family=ouro):
    from perfbench.harness.runctx import TraceView
    lines = []
    events = [xplane.Event(xplane.HOST_PLANE, "main", "perfbench.window",
                           0.0, 1e9, "")]
    events += [xplane.Event(D0, xplane.MODULES_LINE, name, start, dur, "")
               for name, start, dur in modules]
    run = types.SimpleNamespace(
        cell=Cell(REPO, CELL), cfg=_config(), family=family,
        trace=TraceView(events, chips=1) if modules else None, counters={},
        tracing=True,
        stamp={"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
        log=types.SimpleNamespace(
            line=lambda **kw: lines.append(kw),
            measurement=lambda event, **kw: lines.append(
                dict(kw, event=event))))
    run._program_trace = program_trace.ProgramTrace(
        list(spans), list(ops), (0.0, 1e9), matched)
    return run, lines


def _two_steps_and_a_prefill():
    """Two decode executions of 40 ms whose loops take 36, and a prefill
    whose loop must not count; in each decode loop 20 ms of matmuls, 4 of
    waits for weights, 8 of attention, 2 of the rest, 2 of the loop's
    own."""
    op = program_trace.Op
    modules = [("jit_call(1)", 0.0, 40e6), ("jit_call(2)", 50e6, 30e6),
               ("jit_call(1)", 100e6, 40e6),
               # cut by the window's end: not whole, not counted
               ("jit_call(1)", 990e6, 40e6)]
    matched = {"jit_call(1)": ["perfbench_model:decode:16", 9, 9],
               "jit_call(2)": ["perfbench_model:prefill:64", 9, 9]}
    ops = []
    for start in (0.0, 50e6, 100e6, 990e6):
        at = start + 2e6
        ops.append(op("while.6", at, 36e6, LOOP))
        for name, dur, scope in (
                ("fusion.1", 6e6, BODY + ("h0", "qkv")),
                ("fusion.2", 4e6, BODY + ("h47", "proj")),
                ("fusion.3", 10e6, BODY + ("h3", "mlp")),
                ("slice-done.4", 4e6, ()),
                ("paged_attention_pallas.5", 8e6,
                 BODY + ("h3", "attention_full")),
                ("fusion.6", 1e6, BODY + ("h3", "rope")),
                ("fusion.7", 1e6, BODY + ("mx.exit_gate",))):
            ops.append(op(name, at, dur, scope))
            at += dur
        ops.append(op("fusion.9", start + 38e6, 1e6, ("mx.lm_head",)))
    return modules, matched, ops


def test_loop_pass_ms_is_the_loops_time_a_decode_execution_a_pass():
    read = Cell(REPO, CELL).layer_reader("loop_pass_ms.serve")
    modules, matched, ops = _two_steps_and_a_prefill()
    run, lines = _fake_run(ops=ops, modules=modules, matched=matched)
    assert read(run) == pytest.approx(36.0 / 4)
    line = lines[-1]
    assert line["event"] == "pass_loop" and line["executions"] == 2
    assert line["loop_ms_a_step"] == pytest.approx(36.0)
    assert line["by_part_ms_a_pass"] == pytest.approx(
        {"mlp": 2.5, "attention_full": 2.0, "qkv": 1.5, "proj": 1.0,
         "unscoped": 1.0, "rope": 0.25, "mx.exit_gate": 0.25})
    # the floor under a pass: the layer weights once at 819 GB/s
    assert line["weights_least_ms_a_pass"] == pytest.approx(
        1e3 * 4933287936 / 819e9)
    run, lines = _fake_run(ops=ops, modules=modules, matched=matched,
                           family=types.SimpleNamespace())
    assert read(run) == pytest.approx(9.0)
    assert lines[-1]["weights_least_ms_a_pass"] is None
    # a program without the loop's scope (another model, the parent), a
    # run without a trace: nothing to read, nothing raised
    bare = [o for o in ops if o.scope[:1] != LOOP]
    assert read(_fake_run(ops=bare, modules=modules,
                          matched=matched)[0]) is None
    assert read(_fake_run(ops=ops)[0]) is None
    assert read(_fake_run(ops=ops, modules=modules, matched={})[0]) is None


def test_ut_passes_per_token_reads_the_whole_steps_counts():
    read = Cell(REPO, CELL).layer_reader("ut_passes_per_token.serve")
    span = program_trace.Span
    spans = [span("mx.decode.step", "engine", 100.0, 50.0,
                  {"n": "16", "ut_passes": "64", "exit_early": "0",
                   "exit_step_sum": "64"}),
             span("mx.decode.step", "engine", 200.0, 50.0,
                  {"n": "12", "ut_passes": "48", "exit_early": "2",
                   "exit_step_sum": "45"}),
             # not whole inside the window, and a prefill: left out
             span("mx.decode.step", "engine", 1e9 - 10, 50.0,
                  {"n": "16", "ut_passes": "6400"}),
             span("mx.decode.prefill", "engine", 300.0, 50.0,
                  {"ut_passes": "4"})]
    run, lines = _fake_run(spans=spans)
    assert read(run) == pytest.approx(4.0)
    assert lines[-1]["exit_early"] == 2 and lines[-1]["steps"] == 2
    assert lines[-1]["exit_step_mean"] == pytest.approx(109 / 28)
    plain = [span("mx.decode.step", "engine", 100.0, 50.0, {"n": "4"})]
    assert read(_fake_run(spans=plain)[0]) is None
    assert read(_fake_run()[0]) is None
