"""Run one cell of the benchmark once.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``'s ``workloads``.  Its
configuration, traffic mix and per-layer metrics are files under
``perfbench/`` that the harness finds by the names in that entry, so a
later PR adds a cell by adding files and entries and edits nothing here.
The last line of standard output is the result (``harness/report.py``).
``--rehearse`` runs the same code at the configuration's tiny size on the
CPU, prints ``REHEARSAL`` and no result line.
"""
import os
import sys
import time

T_PROCESS_START = time.perf_counter()
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

if __name__ == "__main__":
    from perfbench.harness.cli import main
    sys.exit(main(sys.argv[1:], t_start=T_PROCESS_START, root=REPO_ROOT))
