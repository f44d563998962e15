"""Model FLOP/s utilisation: the FLOPs one token's forward and backward
passes require (the family's shape function; recomputation does not count)
times the tokens per second of the window, over chips times the published
peak."""
from perfbench.harness.peaks import device_peaks


def read(run):
    if run.stamp["platform"] != "tpu":
        return None
    peak_flops, _bw = device_peaks(run.stamp["kind"])
    need = run.family.train_flops_per_token(run.cfg)
    return 100.0 * need * run.counters["tokens_per_s"] \
        / (run.chips * peak_flops)
