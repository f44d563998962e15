"""A rehearsal of every cell declares the metrics that read the program's
own spans and scopes: the readers of host spans produce theirs from the
CPU trace (``mx.`` spans are in any trace of the process), the readers of
device scopes are named as missing, and a line says what the program's
trace held.  Each in a process of its own.

The expectations are keyed by metric, not by cell: a cell is held to the
part of each set that ``BENCHMARK.json`` declares for it, so a cell that
a later PR adds as files and entries is rehearsed here with no edit."""
import json
import os

import pytest

from test_perfbench_command import (BENCH, CELLS, REPO,
                                    _copy_of_the_benchmark, _records, _run)

# fed by the program's host spans: a CPU trace holds them
FROM_SPANS = {"step_host_ms.train", "host_loop_ms.serve",
              "slot_occupancy.serve"}
# fed by the scopes inside the compiled programs: only a device plane has
# operations to scope
FROM_SCOPES = {"loss_head_ms.train", "optimizer_ms.train",
               "finite_check_ms.train", "collective_ms.train",
               "exposed_collective_ms.train", "kv_write_ms.serve"}


def _declared(cell):
    return {m["name"] for m in BENCH["per_layer"]
            if cell in m.get("workloads", CELLS)}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_every_cell_reads_the_programs_names_or_says_why_not(cell):
    """A cell declares at least one metric fed by the program's spans or
    scopes; one that cannot says why under ``trace.no_program_metrics``
    of its configuration's file."""
    if _declared(cell) & (FROM_SPANS | FROM_SCOPES):
        return
    config = next(c for c in BENCH["configs"]
                  if c["name"] == CELLS[cell]["config"])
    on_disk = json.load(open(os.path.join(REPO, config["file"])))
    why = on_disk.get("trace", {}).get("no_program_metrics")
    assert isinstance(why, str) and why.strip(), cell


@pytest.mark.parametrize("cell", sorted(CELLS))
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
    declared = _declared(cell)
    assert set(layer["produced"]) | set(layer["missing"]) == declared
    assert FROM_SPANS & declared <= set(layer["produced"])
    # no device plane on a CPU: nothing to scope, and the line says so
    assert FROM_SCOPES & declared <= set(layer["missing"])
    seen = by["program_trace"]
    assert seen["found"] is True and seen["host_spans"] > 0
    assert seen["device_ops"] == 0 and seen["why"]
