"""The command itself, each in a process of its own: a rehearsal of every
cell end to end on the CPU (four virtual devices for the four-chip cell),
a measurement that refuses a machine without a TPU, and a throw-away
configuration, traffic mix, cell and per-layer metric added as new files
to a copy, which the harness runs with no edit to a file that was there."""
import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
CELLS = {w["name"]: w for w in BENCH["workloads"]}


def _run(args, root=REPO, env_extra=None, pythonpath=None):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)       # the command asks for its own devices
    env["BENCH_RUN"] = "anything"    # the driver's; the command ignores it
    if pythonpath is None:
        env.pop("PYTHONPATH", None)
    else:
        env["PYTHONPATH"] = pythonpath
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, os.path.join(root, "perfbench", "run.py")] + args,
        env=env, cwd=root, capture_output=True, text=True, timeout=600)


def _records(out):
    lines = out.stdout.strip().splitlines()
    assert lines[-1] == "REHEARSAL", out.stdout[-2000:] + out.stderr[-3000:]
    return [json.loads(ln) for ln in lines[:-1]]


@pytest.mark.parametrize("cell,trace", [(name, 1) for name in CELLS] + [
    (name, 0) for name, w in CELLS.items() if w["chips"] == 1])
def test_rehearsal_runs_the_whole_command(cell, trace):
    out = _run(["--workload", cell, "--seed", "3000000001", "--seconds", "1",
                "--trace", str(trace), "--rehearse"])
    assert out.returncode == 0, out.stderr[-3000:]
    records = _records(out)
    chips = CELLS[cell]["chips"]
    for r in records:       # every line names the device it ran on
        assert r["platform"] == "cpu" and r["device_kind"] == "cpu"
        assert r["device_count"] == chips
    by = {r["event"]: r for r in records}
    assert by["start"]["workload"] == cell
    assert by["reference_check"]
    assert "incorrect" not in by, by.get("incorrect")
    # a rehearsal prints no number under a device metric's name
    assert by["window"]["rehearsal"] == "values withheld"
    done = by["rehearsed"]
    assert done["correct"] is True and done["failed"] == 0
    assert done["attempted"] > 0
    assert done["keys"] == ["attempted", "compared", "correct", "device",
                            "failed", "metrics"]
    mine = [m["name"] for m in BENCH["end_to_end" if not trace
                                     else "per_layer"]
            if cell in m.get("workloads", CELLS)]
    assert set(done["metrics"]) <= set(mine)
    if not trace:
        assert done["metrics"] == sorted(mine)
    else:       # what needs no device trace is there already on a CPU
        assert "cache_hit_share.setup" in done["metrics"]


def test_a_measurement_without_a_tpu_fails_and_prints_nothing():
    out = _run(["--workload", "bert_train_1chip", "--seed", "1",
                "--seconds", "1", "--trace", "0"])
    assert out.returncode not in (0, None)
    assert out.stdout.strip() == ""
    assert "needs a TPU" in out.stderr


def test_an_unknown_cell_fails_and_prints_nothing():
    out = _run(["--workload", "no_such_cell", "--rehearse"])
    assert out.returncode != 0 and out.stdout.strip() == ""


def _copy_of_the_benchmark(tmp_path):
    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(REPO, "perfbench"), root / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return root


def _files_under(root):
    """Every file of the copy but BENCHMARK.json, with its bytes."""
    out = {}
    for base, _dirs, files in os.walk(root):
        for f in files:
            p = os.path.join(base, f)
            if not p.endswith("BENCHMARK.json"):
                out[p] = open(p, "rb").read()
    return out


def test_alone_in_a_directory_the_command_fails_and_prints_nothing(tmp_path):
    root = _copy_of_the_benchmark(tmp_path)
    out = _run(["--workload", "bert_train_1chip", "--seed", "1",
                "--seconds", "1", "--trace", "0"], root=str(root))
    assert out.returncode != 0 and out.stdout.strip() == ""
    assert "the program is not here" in out.stderr


THROWAWAY_READER = '''
"""A throw-away per-layer metric: steps counted by the train driver."""


def read(run):
    return float(run.counters["steps"])
'''


def test_new_cell_config_mix_and_metric_are_files_and_entries(tmp_path):
    root = _copy_of_the_benchmark(tmp_path)
    before = _files_under(root)

    # a configuration: BERT's family at another (tiny) size, its own file
    cfg = json.load(open(root / "perfbench/configs/bert-base-mlm-s512.json"))
    cfg["rehearse"]["num_hidden_layers"] = 2
    cfg["source"] = "https://example.org/throwaway"
    with open(root / "perfbench/configs/throwaway-bert.json", "w") as f:
        json.dump(cfg, f)
    # a traffic mix: a data file
    mix = json.load(open(root / "perfbench/traffic/train_stream.json"))
    mix["rehearse"]["loss_fetch_every"] = 3
    with open(root / "perfbench/traffic/throwaway_stream.json", "w") as f:
        json.dump(mix, f)
    # a per-layer metric: a reader of its own
    with open(root / "perfbench/layer_metrics/steps_counted.throwaway.py",
              "w") as f:
        f.write(THROWAWAY_READER)
    # and the entries
    bench = json.load(open(root / "BENCHMARK.json"))
    bench["configs"].append({
        "name": "throwaway-bert", "source": cfg["source"],
        "file": "perfbench/configs/throwaway-bert.json",
        "reduced": cfg["reduced"], "why": "test"})
    bench["workloads"].append({
        "name": "throwaway_cell", "config": "throwaway-bert",
        "traffic": "throwaway_stream", "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "train_tokens_per_s":
            m["workloads"].append("throwaway_cell")
    bench["per_layer"].append({
        "name": "steps_counted.throwaway", "unit": "steps",
        "better": "higher", "source": "program_counter",
        "layer": "compiled train step", "moves": "train_tokens_per_s",
        "workloads": ["throwaway_cell"]})
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)

    out = _run(["--workload", "throwaway_cell", "--seed", "4", "--seconds",
                "1", "--trace", "1", "--rehearse"], root=str(root),
               pythonpath=REPO)
    assert out.returncode == 0, out.stderr[-3000:]
    done = {r["event"]: r for r in _records(out)}["rehearsed"]
    assert done["correct"] is True
    assert "steps_counted.throwaway" in done["metrics"]
    assert "cache_hit_share.setup" in done["metrics"]   # no 'workloads' key
    # no file that was there was edited
    for p, content in before.items():
        assert open(p, "rb").read() == content, p


def _add_a_throwaway_serving_cell(root, tail=None, prefix="throwaway"):
    """To the copy at ``root``: a serving configuration, an open-loop mix
    and a cell of the two as new files and entries, the cell listed on
    every metric that lists ``gpt2m_serve_closed16`` (and, with ``tail``,
    on a new end-to-end metric of that name).  Returns the cell's name."""
    config, traffic, cell = (prefix + "-gpt2", prefix + "_open",
                             prefix + "_serve")
    cfg = json.load(open(root / "perfbench/configs/gpt2-medium.json"))
    cfg["source"] = "https://example.org/" + config
    cfg["rehearse"]["n_layer"] = 1
    with open(root / ("perfbench/configs/%s.json" % config), "w") as f:
        json.dump(cfg, f)
    mix = json.load(open(root / "perfbench/traffic/open_loop.json"))
    mix["rate_per_s"] = 0.5 * mix["rate_per_s"]
    mix["cycle"] = int(mix["rate_per_s"] * BENCH["run_seconds"])
    mix["rehearse"]["rate_per_s"] = 30.0
    with open(root / ("perfbench/traffic/%s.json" % traffic), "w") as f:
        json.dump(mix, f)
    bench = json.load(open(root / "BENCHMARK.json"))
    bench["configs"].append({
        "name": config, "source": cfg["source"],
        "file": "perfbench/configs/%s.json" % config,
        "reduced": cfg["reduced"], "why": "test"})
    bench["workloads"].append({
        "name": cell, "config": config, "traffic": traffic, "chips": 1,
        "why": "test"})
    if tail:
        bench["end_to_end"].append({
            "name": tail, "unit": "ms", "better": "lower", "bound": 0.1,
            "source": "host_clock", "workloads": [cell]})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "gpt2m_serve_closed16" in m.get("workloads", []):
            m["workloads"].append(cell)
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    return cell


def test_an_open_loop_cell_and_its_tail_metric_are_entries_only(tmp_path):
    """A serving configuration, an open-loop mix, a cell of the two and an
    end-to-end tail that no cell reports yet (the gap between tokens) are
    new files and entries: the generator and the driver are here, and the
    command runs the cell with no edit to a file that was there."""
    root = _copy_of_the_benchmark(tmp_path)
    before = _files_under(root)
    cell = _add_a_throwaway_serving_cell(root, tail="itl_p95_ms")
    for trace, want in ((0, ["itl_p95_ms", "serve_tokens_per_s",
                             "setup_s"]), (1, None)):
        out = _run(["--workload", cell, "--seed", "9",
                    "--seconds", "1", "--trace", str(trace), "--rehearse"],
                   root=str(root), pythonpath=REPO)
        assert out.returncode == 0, out.stderr[-3000:]
        done = {r["event"]: r for r in _records(out)}["rehearsed"]
        assert done["correct"] is True and done["attempted"] > 0
        if want:
            assert sorted(done["metrics"]) == want
        else:
            assert {"decode_step_ms.serve", "prefill_ms.serve",
                    "itl_p95_ms.closed", "host_loop_ms.serve",
                    "slot_occupancy.serve"} <= set(done["metrics"])
    for p, content in before.items():
        assert open(p, "rb").read() == content, p


STATIC_TESTS = [
    "test_perfbench_units.py",
    "test_perfbench_program_trace.py::"
    "test_the_new_metrics_and_the_four_chip_cell_are_declared",
    "test_perfbench_program_rehearsal.py::"
    "test_every_cell_reads_the_programs_names_or_says_why_not"]


def test_the_benchmarks_own_tests_take_a_new_serving_cell_as_data(tmp_path):
    """The serving twin of the test above it, for the tests themselves: a
    later PR may add files and entries and may not edit ``tests/perfbench``
    (it is one of ``paths``).  So with a throw-away serving configuration,
    mix and cell added to a copy, the static tests of this directory (the
    letter of BENCHMARK.json, the declarations, the cell enumeration) pass
    from the copy, and no file that was there differs."""
    root = _copy_of_the_benchmark(tmp_path)
    shutil.copytree(os.path.dirname(os.path.abspath(__file__)),
                    root / "tests" / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _files_under(root)
    _add_a_throwaway_serving_cell(root)
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("PYTEST_")}
    # ``perfbench`` from the copy (the working directory), the program
    # from the repo
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    out = subprocess.run(
        [sys.executable, "-m", "pytest", "-v", "-p", "no:cacheprovider",
         "-p", "no:xdist", "-p", "no:randomly"]
        + [os.path.join("tests", "perfbench", t) for t in STATIC_TESTS],
        env=env, cwd=str(root), capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout[-4000:] + out.stderr[-2000:]
    # the copy was looked at: its cell and its mix are among the cases
    for case in ("test_every_cell_finds_its_files[throwaway_serve]",
                 "test_a_traffic_mix_is_a_data_file[throwaway_open.json]",
                 "test_every_cell_reads_the_programs_names_or_says_why_not"
                 "[throwaway_serve]"):
        assert case + " PASSED" in out.stdout, case
    for p, content in before.items():
        assert open(p, "rb").read() == content, p


@pytest.mark.parametrize("precision", ["highest", "bfloat16"])
def test_each_listed_reference_is_held(tmp_path, precision):
    """The serving check holds the program to every reference the
    configuration lists: a tolerance nothing can meet on one of them makes
    the run incorrect and names that reference."""
    root = _copy_of_the_benchmark(tmp_path)
    path = root / "perfbench/configs/gpt2-medium.json"
    cfg = json.load(open(path))
    for ref in cfg["rehearse"]["check"]["references"]:
        if ref["precision"] == precision:
            ref["logit_tolerance"] = 0.0
    with open(path, "w") as f:
        json.dump(cfg, f)
    out = _run(["--workload", "gpt2m_serve_closed16", "--seed", "7",
                "--seconds", "1", "--trace", "0", "--rehearse"],
               root=str(root), pythonpath=REPO)
    assert out.returncode == 0, out.stderr[-3000:]
    records = _records(out)
    why = [r["why"] for r in records if r["event"] == "incorrect"]
    assert why and all(precision in w for w in why), why
    assert {r["event"]: r for r in records}["rehearsed"]["correct"] is False


def test_the_four_chip_cell_runs_on_four_virtual_devices(tmp_path):
    """``bert-base-mlm-s512-dp4`` over ``make_mesh({"dp": 4})``.  Where
    BENCHMARK.json does not hold its cell (PERF.md, open questions), the
    cell and ``collective_ms.train`` are entries added to a copy: the
    configuration file and the reader are here."""
    root, name = REPO, "bert_train_dp4"
    if name not in CELLS:
        root = str(_copy_of_the_benchmark(tmp_path))
        path = os.path.join(root, "BENCHMARK.json")
        bench = json.load(open(path))
        cfg = json.load(open(os.path.join(
            root, "perfbench/configs/bert-base-mlm-s512-dp4.json")))
        bench["configs"].append({
            "name": "bert-base-mlm-s512-dp4", "source": cfg["source"],
            "file": "perfbench/configs/bert-base-mlm-s512-dp4.json",
            "reduced": cfg["reduced"], "why": "test"})
        bench["workloads"].append({
            "name": name, "config": "bert-base-mlm-s512-dp4",
            "traffic": "train_stream", "chips": 4, "why": "test"})
        for m in bench["end_to_end"] + bench["per_layer"]:
            if "bert_train_1chip" in m.get("workloads", []):
                m["workloads"].append(name)
        bench["per_layer"].append({
            "name": "collective_ms.train", "unit": "ms", "better": "lower",
            "source": "device_trace", "layer": "mesh / SPMD",
            "moves": "train_tokens_per_s", "workloads": [name]})
        with open(path, "w") as f:
            json.dump(bench, f)
    out = _run(["--workload", name, "--seed", "2", "--seconds", "1",
                "--trace", "1", "--rehearse"], root=root, pythonpath=REPO)
    assert out.returncode == 0, out.stderr[-3000:]
    records = _records(out)
    assert all(r["device_count"] == 4 for r in records)
    done = {r["event"]: r for r in records}["rehearsed"]
    assert done["correct"] is True and done["attempted"] > 0
