"""The flash-attention kernels' share of their roofline in one train
step: the least time the chip could take for the FLOPs and bytes the
forward and backward have to do (the family's shape function; the larger
of FLOPs over peak and bytes over peak bandwidth) over the summed device
time of the kernels' events in the step (median over the steps traced)."""
from perfbench.harness import stats, xplane
from perfbench.harness.peaks import device_peaks


def read(run):
    if run.trace is None:
        return None
    tr, pat = run.trace, run.cfg["trace"]
    runs = xplane.module_runs(tr.events, tr.devices[0], pat["step_module"])
    ops = xplane.matching(tr.ops(tr.devices[0], clipped=False),
                          pat["flash_kernels"])
    if not runs or not ops:
        return None
    kernel_s = stats.median(xplane.per_run_ns(ops, runs)) / 1e9
    flops, nbytes = run.family.flash_attention_step_cost(
        run.cfg, run.counters["batch_per_chip"])
    peak_flops, peak_bw = device_peaks(run.stamp["kind"])
    by_flops, by_bytes = flops / peak_flops, nbytes / peak_bw
    run.log.measurement("roofline", kernel="flash_attention",
                 bound="compute" if by_flops >= by_bytes else "memory",
                 least_ms=1e3 * max(by_flops, by_bytes),
                 kernel_ms=1e3 * kernel_s, events_per_step=len(ops) / len(runs))
    return 100.0 * max(by_flops, by_bytes) / kernel_s
