"""95th percentile of the gaps between consecutive tokens of one stream in
the traced window, read on the client's side.  A per-layer metric in the
closed-loop cell, not an end-to-end one: whether two or more prefills fall
into a window decides whether their stalls reach the 95th percentile, so
between seeds it reads 779 or 820 ms (PERF.md, PR 23).  Left out below
twenty gaps."""


def read(run):
    if run.counters.get("itl_samples", 0) < 20:
        return None
    return run.counters["itl_p95_ms"]
