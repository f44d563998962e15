"""Median time to first token in the traced window of an open loop, from
when each request was due, read on the client's side.  A per-layer metric
and the median, not the tail the operator watches: a 4 s traced window at
8 requests/s holds some 32 arrivals, which support no higher percentile,
and over an untraced 30 s window the 95th percentile spreads 24-118%
between runs of one program on this host (PERF.md, PR 27), so no bound
the benchmark may set could hold it as an end-to-end metric; every run's
``window`` line prints it.  Left out below twenty arrivals."""


def read(run):
    if run.counters.get("ttft_samples", 0) < 20:
        return None
    return run.counters["ttft_p50_ms"]
