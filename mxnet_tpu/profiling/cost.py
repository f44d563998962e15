"""CostReport: XLA cost/memory analysis + per-category attribution.

One report per compiled executable, keyed by a fingerprint of the
post-optimization HLO (normalized: trace metadata, the source-position
tables and the module name are stripped, so identical programs recompiled -- or retraced from a
fresh ``jax.jit`` of the same code -- fingerprint identically).

The per-category numbers are the ``hlo.py`` analytic estimates
RECONCILED against XLA's executable totals: each category is scaled by
``total/estimate`` and rounded, with the remainder pinned on the
largest category, so ``sum(categories[*].flops) == round(totals.flops)``
exactly (the ``mxprof report`` contract).  The raw estimates are kept
under ``estimates`` for debugging attribution drift.
"""
from __future__ import annotations

import hashlib
import re

from . import hlo

SCHEMA = "mxprof.cost_report.v1"

_NORM_METADATA = re.compile(r",?\s*metadata=\{[^}]*\}")
_NORM_MODULE = re.compile(r"^HloModule\s+\S+", re.MULTILINE)
# the source-position tables HLO text carries outside metadata={...}:
# a header line, then numbered entries up to a blank line
_NORM_TABLES = re.compile(
    r"^(?:FileNames|FunctionNames|FileLocations|StackFrames)\n"
    r"(?:\d+ .*\n)*\n*", re.MULTILINE)


def fingerprint(text):
    """Stable identity of a compiled program: sha256 of the HLO text
    with volatile parts (module name, ``metadata={...}`` with its
    ``op_name`` scopes, the source-position tables) normalized away, so
    neither a moved line nor a ``jax.named_scope`` changes it."""
    norm = _NORM_METADATA.sub("", text)
    norm = _NORM_TABLES.sub("", norm)
    norm = _NORM_MODULE.sub("HloModule <norm>", norm)
    return hashlib.sha256(norm.encode()).hexdigest()[:16]


def _reconcile(cats, total, key):
    """Scale category ``key`` estimates so they sum exactly to
    ``total`` (int).  Zero-estimate cases dump the whole total on
    'other' -- visible, not hidden."""
    total = int(round(total))
    est = {c: cats[c][key] for c in hlo.CATEGORIES}
    est_sum = sum(est.values())
    if total <= 0:
        return {c: 0 for c in hlo.CATEGORIES}
    if est_sum <= 0:
        out = {c: 0 for c in hlo.CATEGORIES}
        out["other"] = total
        return out
    out = {c: int(round(v * total / est_sum)) for c, v in est.items()}
    drift = total - sum(out.values())
    out[max(out, key=out.get)] += drift
    return out


def analyze_compiled(compiled, label="executable", kind="jit", **meta):
    """Build a CostReport dict from a ``jax.stages.Compiled``."""
    import jax

    totals = {"flops": 0.0, "bytes_accessed": 0.0, "transcendentals": 0.0}
    try:
        ca = dict(compiled.cost_analysis() or {})
        totals["flops"] = float(ca.get("flops", 0.0))
        totals["bytes_accessed"] = float(ca.get("bytes accessed", 0.0))
        totals["transcendentals"] = float(ca.get("transcendentals", 0.0))
    except Exception:
        ca = {}

    memory = {}
    try:
        ms = compiled.memory_analysis()
        memory = {
            "argument_bytes": int(ms.argument_size_in_bytes),
            "output_bytes": int(ms.output_size_in_bytes),
            "temp_bytes": int(ms.temp_size_in_bytes),
            "alias_bytes": int(ms.alias_size_in_bytes),
            "generated_code_bytes": int(ms.generated_code_size_in_bytes),
        }
        # aliased (donated) buffers are counted in both argument and
        # output totals but exist once on the chip
        memory["peak_hbm_bytes"] = max(
            0, memory["argument_bytes"] + memory["output_bytes"]
            + memory["temp_bytes"] - memory["alias_bytes"])
    except Exception:
        memory = {"argument_bytes": 0, "output_bytes": 0, "temp_bytes": 0,
                  "alias_bytes": 0, "generated_code_bytes": 0,
                  "peak_hbm_bytes": 0}

    text = ""
    try:
        text = compiled.as_text()
    except Exception:
        pass
    attributed = hlo.analyze(text) if text else \
        {"categories": {c: {"flops": 0, "bytes": 0, "instructions": 0}
                        for c in hlo.CATEGORIES}, "provenance": []}
    est = attributed["categories"]
    # no XLA totals (some backends): the analytic estimate IS the total
    if not totals["flops"]:
        totals["flops"] = float(sum(c["flops"] for c in est.values()))
    if not totals["bytes_accessed"]:
        totals["bytes_accessed"] = float(sum(c["bytes"]
                                             for c in est.values()))

    flops_rec = _reconcile(est, totals["flops"], "flops")
    bytes_rec = _reconcile(est, totals["bytes_accessed"], "bytes")
    tf, tb = max(totals["flops"], 1.0), max(totals["bytes_accessed"], 1.0)
    categories = {
        c: {"flops": flops_rec[c], "bytes": bytes_rec[c],
            "instructions": est[c]["instructions"],
            "flops_share": round(flops_rec[c] / tf, 4),
            "bytes_share": round(bytes_rec[c] / tb, 4)}
        for c in hlo.CATEGORIES}

    try:
        device = jax.devices()[0].device_kind
        backend = jax.default_backend()
    except Exception:
        device, backend = "unknown", "unknown"

    return {
        "schema": SCHEMA,
        "label": label,
        "kind": kind,
        "fingerprint": fingerprint(text) if text else "",
        "device": device,
        "backend": backend,
        "totals": totals,
        "memory": memory,
        "categories": categories,
        "estimates": {c: {"flops": est[c]["flops"],
                          "bytes": est[c]["bytes"]}
                      for c in hlo.CATEGORIES},
        "provenance": attributed["provenance"],
        "step": None,
        "roofline": None,
        **({"meta": meta} if meta else {}),
    }


def analyze_jit(fn, args, label="executable", kind="jit", **meta):
    """Lower+compile ``fn`` on abstracted ``args`` and analyze.  Hits
    the jit executable cache when ``fn`` was already dispatched on the
    same avals, so this never doubles real compile work.  Returns None
    when the function cannot be lowered (e.g. args gone stale)."""
    import jax

    def _abstract(x):
        if hasattr(x, "shape") and hasattr(x, "dtype") and \
                not isinstance(x, jax.ShapeDtypeStruct):
            return jax.ShapeDtypeStruct(x.shape, x.dtype)
        return x
    try:
        specs = jax.tree_util.tree_map(_abstract, args)
        compiled = fn.lower(*specs).compile()
    except Exception:
        return None
    return analyze_compiled(compiled, label=label, kind=kind, **meta)
