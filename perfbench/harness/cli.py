"""Parse the command line, find the cell, run it, print the result."""
import argparse
import os
import sys
import traceback

from . import report
from .spec import Cell, SpecError, sized

# exit codes other than 0: nothing is printed on the last line
EXIT_SPEC, EXIT_NO_PROGRAM, EXIT_NO_CHIP, EXIT_FAILED = 2, 3, 4, 5


def parse(argv):
    ap = argparse.ArgumentParser(
        prog="perfbench/run.py",
        description="Run one cell of BENCHMARK.json once.")
    ap.add_argument("--workload", required=True,
                    help="name of an entry of BENCHMARK.json's workloads")
    ap.add_argument("--seed", type=int, default=0,
                    help="inputs and weights are made from it")
    ap.add_argument("--seconds", type=float, default=10.0,
                    help="length of the measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0,
                    help="1: trace a window and print the per-layer metrics")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU run at the tiny size; prints REHEARSAL and "
                         "no result")
    return ap.parse_args(argv)


def _four_virtual_devices(chips):
    flag = "--xla_force_host_platform_device_count"
    if chips > 1 and flag not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + " %s=%d" % (flag, chips)).strip()


def main(argv, t_start, root):
    args = parse(argv)
    try:
        cell = Cell(root, args.workload)
    except SpecError as e:
        print("perfbench: %s" % e, file=sys.stderr)
        return EXIT_SPEC
    if args.rehearse:
        _four_virtual_devices(cell.chips)
    try:
        import jax  # noqa: F401
        import mxnet_tpu  # noqa: F401  (places the compile cache)
    except ImportError as e:
        print("perfbench: the program is not here: %s" % e, file=sys.stderr)
        return EXIT_NO_PROGRAM

    from . import train_driver, serve_driver
    from .compilelog import CompileLog
    from .peaks import (NoChip, device_stamp, memory_peak_bytes,
                        memory_stats)
    from .runctx import Log, Run
    try:
        stamp = device_stamp(cell.chips, args.rehearse)
    except NoChip as e:
        print("perfbench: %s" % e, file=sys.stderr)
        return EXIT_NO_CHIP

    log = Log(stamp, args.rehearse)
    family = cell.family()
    run = Run(cell, sized(cell.config, args.rehearse),
              sized(cell.traffic, args.rehearse), family, stamp, args, root,
              t_start, log)
    log.line(event="start", workload=cell.name, config=cell.config["name"],
             traffic=cell.traffic["name"], chips=cell.chips, seed=args.seed,
             seconds=args.seconds, trace=args.trace, rehearse=args.rehearse,
             compile_cache_dir=jax.config.jax_compilation_cache_dir)
    compile_log = CompileLog()
    driver = {"train": train_driver, "serve": serve_driver}[family.KIND]
    try:
        driver.run_cell(run, compile_log)
        mem = memory_stats(cell.chips)
        run.memory_peak_bytes = memory_peak_bytes(mem)
        log.line(event="memory", memory_stats=mem)
        if args.trace:
            metrics = report.per_layer_metrics(run)
            parts = report.breakdown(run, driver.IDLE_DEFAULT,
                                     driver.extra_spans(run)) \
                if run.trace is not None else None
        else:
            metrics, parts = report.end_to_end_metrics(run), None
    except SpecError as e:
        print("perfbench: %s" % e, file=sys.stderr)
        return EXIT_SPEC
    except Exception:
        traceback.print_exc()
        return EXIT_FAILED
    finally:
        compile_log.close()
    out = report.result(run, metrics, parts)
    if args.rehearse:
        log.line(event="rehearsed", correct=out["correct"],
                 attempted=out["attempted"], failed=out["failed"],
                 metrics=sorted(out["metrics"]), keys=sorted(out))
        print("REHEARSAL", flush=True)
        return 0
    report.print_result(out)
    return 0
