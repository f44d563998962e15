"""Readers that more than one per-layer metric shares: the same quantity
stands under two names where the cells that report it report different
end-to-end metrics (``device_idle_share.train`` moves the training rate,
``device_idle_share.serve`` the serving rate)."""


def device_idle_share(run):
    """Share of the traced window in which no operation ran on device 0:
    one minus the union of the op intervals over the window.  A line
    above the result names the worst device where the cell has several."""
    if run.trace is None:
        return None
    tr = run.trace
    idle = {d: 100.0 * (1.0 - tr.busy_s(d) / tr.window_s)
            for d in tr.devices}
    if len(idle) > 1:
        worst = max(idle, key=idle.get)
        run.log.measurement("device_idle", worst_device=worst,
                            worst_idle_share=idle[worst], by_device=idle)
    return idle[tr.devices[0]]


def peak_hbm_gb(run):
    """Peak device memory after the window on the fullest chip, in GB
    (1e9 bytes): ``harness/peaks.py::memory_peak_bytes``."""
    if run.memory_peak_bytes is None:
        return None
    return run.memory_peak_bytes / 1e9
