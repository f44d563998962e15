"""The chip's published peaks and the device stamp of a run.

The table is a copy of ``mxnet_tpu/profiling/roofline.py::DEVICE_PEAKS``
(PR 21).  It is copied, not imported: the program may change, the
yardstick may not.  Source: Google Cloud TPU documentation, the
system-architecture page of each generation ("TPU v5e": 197 TFLOP/s bf16,
819 GB/s of HBM bandwidth).  JAX names a v5e chip ``TPU v5 lite``.
"""

# device_kind -> (peak bf16 FLOP/s, peak HBM bytes/s) of one chip
DEVICE_PEAKS = {
    "TPU v5 lite": (197e12, 819e9),
    "TPU v5e": (197e12, 819e9),
    "TPU v5": (459e12, 2765e9),
    "TPU v4": (275e12, 1228e9),
}


class NoChip(Exception):
    """The run cannot be a measurement: no TPU, too few chips, or a chip
    whose peaks are not in the table."""


def device_peaks(device_kind):
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise NoChip("no published peak for device kind %r; add it to "
                     "perfbench/harness/peaks.py with its source"
                     % (device_kind,)) from None


def device_stamp(chips, rehearse):
    """``{"platform", "kind", "count"}`` as JAX reports them, after
    checking that this process may measure (or rehearse) the cell.  A
    measurement never falls back to the CPU."""
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if rehearse:
        if platform != "cpu":
            raise NoChip("--rehearse is the CPU rehearsal; run it under "
                         "JAX_PLATFORMS=cpu (platform is %r)" % platform)
    else:
        if platform != "tpu":
            raise NoChip("the benchmark needs a TPU: JAX reports platform "
                         "%r.  --rehearse runs the code on the CPU at a "
                         "tiny size." % platform)
        device_peaks(devices[0].device_kind)
    if len(devices) < chips:
        raise NoChip("the cell needs %d chip(s), JAX reports %d"
                     % (chips, len(devices)))
    return {"platform": platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_stats(chips):
    """``memory_stats()`` of each chip the cell used, as JAX reports them
    ({} where the backend reports none: the CPU)."""
    import jax
    return [dict(d.memory_stats() or {}) for d in jax.devices()[:chips]]


def memory_peak_bytes(stats):
    """Peak bytes on the fullest chip: ``peak_bytes_in_use``, the
    allocator's high-water mark of live arrays, plus
    ``peak_bytes_reserved`` where the backend reports it -- on a TPU a
    running program's temporaries are reserved beside the allocator's
    arrays and are not in the first figure.  The two high-water marks
    need not fall at the same moment, so the sum is an upper bound of the
    true peak (and the larger of the two a lower one); the ``memory`` line
    of every run prints both.  None where the backend reports neither."""
    peaks = [s["peak_bytes_in_use"] + s.get("peak_bytes_reserved", 0)
             for s in stats if "peak_bytes_in_use" in s]
    return max(peaks) if peaks else None
