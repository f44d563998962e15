"""Analytic roofline: measured step time x CostReport -> bound labels.

Given a CostReport and a measured step time, computes achieved FLOP/s
and bytes/s against the device's peak compute and HBM bandwidth, and
labels every HLO category compute- or memory-bound by comparing its
arithmetic intensity (FLOPs per byte moved) with the device's ridge
point ``peak_flops / peak_bandwidth``.  This is how an aggregate MFU
number decomposes into "the convs are compute-bound at X%, the
layout ops are pure bandwidth": the ceiling analysis ROADMAP item 2
asks for.

Peaks come from ONE table keyed by ``device_kind``.  A CPU has no
entry and gets no roofline (a host's peak is not assumed); an
accelerator kind the table does not hold is an error, not a default.
"""
from __future__ import annotations

from ..base import MXNetError

# device_kind (as jax reports it) -> (peak bf16 FLOP/s, peak HBM
# bytes/s) of one chip.  Source: Google Cloud TPU documentation, the
# system-architecture page of each generation ("TPU v5e": 197 TFLOP/s
# bf16, 819 GB/s; "TPU v5p": 459 TFLOP/s, 2,765 GB/s; "TPU v4": 275
# TFLOP/s, 1,228 GB/s; "TPU v3": 123 TFLOP/s, 900 GB/s; "TPU v2": 45
# TFLOP/s, 700 GB/s).  jax names a v5e chip "TPU v5 lite" and a v5p
# chip "TPU v5".
DEVICE_PEAKS = {
    "TPU v5 lite": (197e12, 819e9),
    "TPU v5e": (197e12, 819e9),
    "TPU v5": (459e12, 2765e9),
    "TPU v4": (275e12, 1228e9),
    "TPU v3": (123e12, 900e9),
    "TPU v2": (45e12, 700e9),
}


def device_peaks(device_kind=None):
    """``(peak_flops, peak_bytes_per_s)`` of the current (or named)
    device kind; ``None`` on a CPU; raises for an accelerator the table
    does not know."""
    if device_kind is None:
        import jax
        device_kind = jax.devices()[0].device_kind
    if device_kind.lower() == "cpu":
        return None
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise MXNetError(
            "no published peak for device kind %r; add it to "
            "profiling.roofline.DEVICE_PEAKS with its source (known: %s)"
            % (device_kind, ", ".join(sorted(DEVICE_PEAKS)))) from None


def build(report, step_time_s, peak_flops=None, peak_bytes_per_s=None,
          items_per_step=None):
    """Roofline section dict for ``report`` at ``step_time_s``, or
    ``None`` where the device has no peak (CPU) and none is passed."""
    fl, bw = device_peaks(report.get("device")) or (None, None)
    if peak_flops is not None:
        fl = peak_flops
    if peak_bytes_per_s is not None:
        bw = peak_bytes_per_s
    if fl is None or bw is None:
        return None
    step_time_s = max(float(step_time_s), 1e-12)
    tot_f = report["totals"]["flops"]
    tot_b = report["totals"]["bytes_accessed"]
    achieved_f = tot_f / step_time_s
    achieved_b = tot_b / step_time_s
    ridge = fl / bw
    cats = {}
    time_est = {}
    for name, c in report["categories"].items():
        f, b = c["flops"], c["bytes"]
        if f == 0 and b == 0:
            continue
        intensity = (f / b) if b else float("inf")
        bound = "compute" if intensity >= ridge else "memory"
        # the category's floor time under the roofline model: whichever
        # wall (compute or bandwidth) it hits first
        time_est[name] = max(f / fl, b / bw)
        cats[name] = {"intensity": round(intensity, 3)
                      if intensity != float("inf") else None,
                      "bound": bound}
    t_total = sum(time_est.values()) or 1.0
    for name, t in time_est.items():
        cats[name]["time_share"] = round(t / t_total, 4)
        cats[name]["floor_s"] = round(t, 9)
    out = {
        "step_time_s": step_time_s,
        "peak_flops": fl,
        "peak_bytes_per_s": bw,
        "ridge_intensity": round(ridge, 3),
        "achieved_flops_per_s": achieved_f,
        "achieved_bytes_per_s": achieved_b,
        "mfu": round(achieved_f / fl, 4),
        "bandwidth_util": round(achieved_b / bw, 4),
        # the roofline's floor for this program on this chip: the
        # measured/floor ratio says how much headroom is model-side
        "floor_step_s": round(t_total if time_est else 0.0, 9),
        "categories": cats,
    }
    if items_per_step:
        out["items_per_step"] = items_per_step
        out["items_per_sec"] = round(items_per_step / step_time_s, 1)
    return out
