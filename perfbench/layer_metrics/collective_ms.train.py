"""Time the collective operations of one train step take on device 0
(median over the steps traced): the union of their intervals on the ops
line and on the line of asynchronous operations, where an all-reduce
that XLA runs beside the backward pass shows its whole span.  Part of it
overlaps compute: this is time in collectives, not exposed time."""
from perfbench.harness import stats, xplane


def read(run):
    if run.trace is None:
        return None
    tr, pat = run.trace, run.cfg["trace"]
    dev = tr.devices[0]
    runs = xplane.module_runs(tr.events, dev, pat["step_module"])
    ops = xplane.matching(
        xplane.on_device(tr.events, dev, xplane.OPS_LINE)
        + xplane.on_device(tr.events, dev, xplane.ASYNC_OPS_LINE),
        pat["collectives"])
    if not runs or not ops:
        return None
    per_step = [xplane.busy_ns(xplane.clip(
        ops, (r.start_ns, r.start_ns + r.dur_ns))) for r in runs]
    return stats.median(per_step) / 1e6
