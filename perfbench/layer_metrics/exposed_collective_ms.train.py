"""Collective time of one train step that nothing hides: the intervals of
the collective operations on device 0 (the ops line and the line of
asynchronous operations, the configuration's ``trace.collectives``) less
what other operations on that device's ops line cover, median over the
steps traced.  ``collective_ms.train`` is the whole of those intervals;
this is the part the step waits for."""
from perfbench.harness import program_trace, stats, xplane


def read(run):
    if run.trace is None:
        return None
    tr, pat = run.trace, run.cfg["trace"]
    dev = tr.devices[0]
    runs = xplane.module_runs(tr.events, dev, pat["step_module"])
    on_ops_line = xplane.on_device(tr.events, dev, xplane.OPS_LINE)
    collectives = xplane.matching(
        on_ops_line + xplane.on_device(tr.events, dev,
                                       xplane.ASYNC_OPS_LINE),
        pat["collectives"])
    if not runs or not collectives:
        return None
    names = {e.name for e in collectives}
    others = [e for e in on_ops_line if e.name not in names]
    per_step = []
    for r in runs:
        step = (r.start_ns, r.start_ns + r.dur_ns)
        per_step.append(program_trace.exposed_ns(
            xplane.clip(collectives, step), xplane.clip(others, step)))
    return stats.median(per_step) / 1e6
