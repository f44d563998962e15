"""A rehearsal of every cell declares the metrics that read the program's
own spans and scopes: the readers of host spans produce theirs from the
CPU trace (``mx.`` spans are in any trace of the process), the readers of
device scopes are named as missing, and a line says what the program's
trace held.  Each in a process of its own."""
import pytest

from test_perfbench_command import (BENCH, CELLS, REPO,
                                    _copy_of_the_benchmark, _records, _run)

FROM_SPANS = {"bert_train_1chip": {"step_host_ms.train"},
              "bert_train_dp4": {"step_host_ms.train"},
              "gpt2m_serve_closed16": {"host_loop_ms.serve",
                                       "slot_occupancy.serve"}}
FROM_SCOPES = {"bert_train_1chip": {"loss_head_ms.train",
                                    "optimizer_ms.train",
                                    "finite_check_ms.train"},
               "bert_train_dp4": {"loss_head_ms.train", "optimizer_ms.train",
                                  "finite_check_ms.train",
                                  "collective_ms.train",
                                  "exposed_collective_ms.train"},
               "gpt2m_serve_closed16": {"kv_write_ms.serve"}}


def test_the_three_cells_are_there():
    assert set(CELLS) == set(FROM_SPANS) == set(FROM_SCOPES)


@pytest.mark.parametrize("cell", sorted(FROM_SPANS))
def test_a_rehearsal_declares_the_programs_metrics(cell, tmp_path):
    # from a copy: the trace lands under the command's own root, and the
    # rehearsals of test_perfbench_command.py may be running beside this
    root = str(_copy_of_the_benchmark(tmp_path))
    out = _run(["--workload", cell, "--seed", "3000000007", "--seconds", "1",
                "--trace", "1", "--rehearse"], root=root, pythonpath=REPO)
    assert out.returncode == 0, out.stderr[-3000:]
    by = {r["event"]: r for r in _records(out)}
    assert by["rehearsed"]["correct"] is True
    layer = by["per_layer"]
    declared = {m["name"] for m in BENCH["per_layer"]
                if cell in m.get("workloads", CELLS)}
    assert set(layer["produced"]) | set(layer["missing"]) == declared
    assert FROM_SPANS[cell] | FROM_SCOPES[cell] <= declared
    assert FROM_SPANS[cell] <= set(layer["produced"])
    # no device plane on a CPU: nothing to scope, and the line says so
    assert FROM_SCOPES[cell] <= set(layer["missing"])
    seen = by["program_trace"]
    assert seen["found"] is True and seen["host_spans"] > 0
    assert seen["device_ops"] == 0 and seen["why"]
