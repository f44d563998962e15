"""Median of the engine's own timer ``decode.prefill_time`` over the load,
the pre-roll included: host clock around one batch-1 prefill call.  The
pre-roll admits every client's first request, so there are readings even
where the window itself admits none."""
from perfbench.harness import stats


def read(run):
    values = run.samples.get("decode.prefill_time@load")
    return 1e3 * stats.median(values) if values else None
