"""The KDA decode recurrence's share of its roofline: the least time,
at the chip's published peaks, for the family's ``recurrence_cost`` of
a decode step's ``state_rows`` (live slots x KDA layers: the count the
decode program returns beside its tokens and the engine puts on the
``mx.decode.step`` span, mean over the steps whole inside the traced
window) -- each slot's float32 state read and written once, its q, k,
v, decay and beta read and its output written -- over the device self
time of the ops scoped ``h<i>/linear_attention/recurrence`` a decode
execution (mean over the ``jit_mx_decode_b<bucket>`` executions whole
inside the window).  It reads the same work whatever implements the
step: the kernel, or the XLA gather, turn and scatter.  A program
without the count or the scope has nothing to read."""
from perfbench.harness import linear_attention
from perfbench.harness.peaks import device_peaks


def read(run):
    found = linear_attention.load(run)
    cost = getattr(run.family, "recurrence_cost", None)
    if found is None or cost is None or not found.state_rows \
            or not found.recurrence_ms():
        return None
    flops, nbytes = cost(run.cfg, found.state_rows)
    peak_flops, peak_bw = device_peaks(run.stamp["kind"])
    by_flops, by_bytes = flops / peak_flops, nbytes / peak_bw
    kernel_ms = found.recurrence_ms()
    run.log.measurement("roofline", kernel="linear_attention",
                        bound="compute" if by_flops >= by_bytes
                        else "memory",
                        least_ms=1e3 * max(by_flops, by_bytes),
                        kernel_ms=kernel_ms,
                        state_rows_a_step=found.state_rows)
    return 100.0 * 1e3 * max(by_flops, by_bytes) / kernel_ms
