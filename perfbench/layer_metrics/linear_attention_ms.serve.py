"""Device time a decode step spends in its linear-attention (KDA) layers:
the self time of the ops scoped ``h<i>/linear_attention/...`` (the
projections, the short convolution, the gates, the recurrence, the
output norm) inside each ``jit_mx_decode_b<bucket>`` execution whole
inside the traced window, summed over the layers, median over the
executions (``harness/linear_attention.py``, which also prints each
part's time a decode step and a prefill token).  A program without
those scopes has nothing to read."""
from perfbench.harness import linear_attention


def read(run):
    found = linear_attention.load(run)
    return None if found is None else found.decode_ms()
