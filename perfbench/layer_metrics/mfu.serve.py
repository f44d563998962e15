"""The whole serving step's share of the chip's peak: the FLOPs the model
needs for the tokens of the window's rate (the family's shape function:
every decode token over its live context, every prompt whose first token
is among them; padding and recomputation do not count), over the window
of the rate, chips and the published peak.  A decode step at batch 16
reads 1.42 GB of weights for 16 tokens' worth of FLOPs, so this stands
near 1%: it bounds what the kernels' rooflines claim beside it, and
rises only if more tokens leave the chip a second."""
from perfbench.harness.peaks import device_peaks


def read(run):
    if not run.counters.get("tokens_in_window") \
            or run.stamp["platform"] != "tpu":
        return None
    peak_flops, _bw = device_peaks(run.stamp["kind"])
    need = run.family.served_flops(
        run.cfg, run.counters["served_decode_tokens"],
        run.counters["served_context_tokens"],
        run.counters["served_prompt_lens"])
    return 100.0 * need / (run.window_s * run.chips * peak_flops)
