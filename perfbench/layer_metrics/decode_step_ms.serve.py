"""Median of the engine's own timer ``decode.step_time`` over the window:
host clock around one decode call that ends in ``jax.device_get``."""
from perfbench.harness import stats


def read(run):
    values = run.samples.get("decode.step_time")
    return 1e3 * stats.median(values) if values else None
