"""Share of set-up's compile requests that JAX's persistent cache
answered (its own log records, as ``chip_smoke.py::CacheLog`` reads
them)."""


def read(run):
    requests = run.counters.get("compile_requests_setup")
    if not requests:
        return None
    return 100.0 * run.counters["cache_hits_setup"] / requests
