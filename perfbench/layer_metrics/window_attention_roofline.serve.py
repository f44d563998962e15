"""The paged-attention kernel's share of its roofline where the layers are
of two kinds, over the decode steps of the traced window: the least time
for the cache rows those steps HAD to read -- ``kv_rows_full`` in each
full layer and ``kv_rows_window`` in each window layer, the counts the
decode program returns beside its tokens and the engine puts on the
``mx.decode.step`` span, summed over the steps that lie whole inside the
window -- by the family's ``attention_cost`` and the chip's published
peaks, over the summed device time of the events that the configuration's
``trace.paged_attention`` names.  The kernel's events of a step that the
window cuts count in the time and not in the rows, so the share reads a
little low (a step in some eighty).  A program that returns no such count
(or a cell whose family has no ``attention_cost``) has nothing to read."""
from perfbench.harness import program_trace, xplane
from perfbench.harness.peaks import device_peaks


def read(run):
    view = program_trace.load(run)
    cost = getattr(run.family, "attention_cost", None)
    if view is None or run.trace is None or cost is None:
        return None
    steps = [s.attrs for s, ns in view.in_window("mx.decode.step")
             if ns == s.dur_ns and "kv_rows_full" in s.attrs]
    tr = run.trace
    ops = xplane.matching(tr.ops(tr.devices[0]),
                          run.cfg["trace"]["paged_attention"])
    if not steps or not ops:
        return None
    rows_full = sum(float(a["kv_rows_full"]) for a in steps)
    rows_window = sum(float(a["kv_rows_window"]) for a in steps)
    flops, nbytes = cost(run.cfg, rows_full, rows_window)
    kernel_s = sum(e.dur_ns for e in ops) / 1e9
    peak_flops, peak_bw = device_peaks(run.stamp["kind"])
    by_flops, by_bytes = flops / peak_flops, nbytes / peak_bw
    run.log.measurement("roofline", kernel="paged_attention (window + full)",
                        bound="compute" if by_flops >= by_bytes else "memory",
                        least_ms=1e3 * max(by_flops, by_bytes),
                        kernel_ms=1e3 * kernel_s, events=len(ops),
                        steps=len(steps), rows_full=rows_full,
                        rows_window=rows_window)
    return 100.0 * max(by_flops, by_bytes) / kernel_s
