"""The paged-attention kernel's share of its roofline over the decode
steps of the traced window: the least time for the K and V of the live
contexts (every token a stream received in the window came from a step
that read its whole context in every layer) over the summed device time
of the kernel's events in the window."""
from perfbench.harness import xplane
from perfbench.harness.peaks import device_peaks


def read(run):
    if run.trace is None:
        return None
    tr = run.trace
    ops = xplane.matching(tr.ops(tr.devices[0]),
                          run.cfg["trace"]["paged_attention"])
    if not ops or not run.counters.get("decode_context_tokens"):
        return None
    kernel_s = sum(e.dur_ns for e in ops) / 1e9
    flops, nbytes = run.family.paged_attention_cost(
        run.cfg, run.counters["decode_context_tokens"])
    peak_flops, peak_bw = device_peaks(run.stamp["kind"])
    by_flops, by_bytes = flops / peak_flops, nbytes / peak_bw
    run.log.measurement("roofline", kernel="paged_attention",
                 bound="compute" if by_flops >= by_bytes else "memory",
                 least_ms=1e3 * max(by_flops, by_bytes),
                 kernel_ms=1e3 * kernel_s, events=len(ops))
    return 100.0 * max(by_flops, by_bytes) / kernel_s
