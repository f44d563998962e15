"""Pallas kernel registry + selection policy (the fused-operator half
of the blueprint: "fused operators ... become Pallas custom-calls").

One place decides, per kernel and call shape, whether the hand-written
Pallas implementation or the XLA reference path runs -- replacing the
per-call-site ``use_pallas`` branching that used to live in
``ops/transformer.py``.  The policy, in order:

1. ``MXNET_TPU_KERNELS=0``  -> XLA fallback everywhere (kill switch).
2. Pallas unimportable       -> XLA fallback (CPU-only minimal builds).
3. The kernel's ``supports`` predicate rejects the call shape (e.g.
   flash attention needs seq divisible by the block sizes, fused BN
   needs channels-last) -> XLA fallback with the reason recorded.
4. ``MXNET_TPU_KERNELS`` unset (auto): the kernel's ``auto_predicate``
   gates profitability (flash attention's measured seq>=256 crossover;
   the BN fusion sites and the bucketed optimizer stay off -- they are
   opt-in tier features), then the Pallas path is selected only when
   the default backend is a TPU.
5. ``MXNET_TPU_KERNELS=1``: the Pallas path is forced; on a non-TPU
   backend the kernel runs in ``interpret=True`` mode so tier-1 tests
   exercise the REAL kernel bodies instead of the fallback.

``remedy_for(kind)`` maps a perf-audit advisory kind (docs/perf_lint.md)
to the registered kernel that addresses it -- ``perf_audit()`` attaches
it to each advisory so "unfused-elementwise >= 15%" names its fix.
"""
from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

from ..base import MXNetError

__all__ = ["KernelSpec", "KernelChoice", "register_kernel", "get",
           "list_kernels", "mode", "enabled", "available", "choose",
           "remedy_for", "describe"]


def _has_pallas() -> bool:
    # module-level probe (monkeypatch target for the fallback-proof
    # tests/CI stage: patching this to False must drive every choice to
    # the XLA path)
    try:
        from jax.experimental import pallas  # noqa: F401
        return True
    except Exception:  # pragma: no cover - pallas ships with jax
        return False


def _backend() -> str:
    # a backend that fails to initialise (chip held by another process)
    # raises here: answering "cpu" would turn it into "XLA path chosen"
    import jax
    return jax.default_backend()


@dataclass(frozen=True)
class KernelChoice:
    """One selection decision: which implementation runs and why."""
    use_pallas: bool
    interpret: bool
    reason: str

    def __bool__(self) -> bool:
        return self.use_pallas


@dataclass
class KernelSpec:
    """One registered Pallas kernel with its XLA fallback contract."""
    name: str
    doc: str
    # HLO categories whose traffic the kernel removes (mxprof vocabulary)
    categories: Tuple[str, ...] = ()
    # perf-audit advisory kinds this kernel is the remedy for
    remedies: Tuple[str, ...] = ()
    # (**shape_kwargs) -> (ok, reason): correctness constraints only
    supports: Optional[Callable] = None
    # (**shape_kwargs) -> bool: profitability gate for auto mode
    auto_predicate: Optional[Callable] = None
    # the XLA reference implementation (fallback + numerics oracle)
    xla_ref: Optional[Callable] = None
    extra: Dict = field(default_factory=dict)

    def __repr__(self):
        return "KernelSpec(%s)" % self.name


KERNELS: Dict[str, KernelSpec] = {}


def register_kernel(spec: KernelSpec) -> KernelSpec:
    if spec.name in KERNELS and KERNELS[spec.name] is not spec:
        raise MXNetError("duplicate kernel registration %r" % spec.name)
    KERNELS[spec.name] = spec
    return spec


def _ensure_registered():
    # importing the kernel modules registers their specs; lazy so that
    # `import mxnet_tpu` does not pull pallas machinery upfront
    from . import (flash_attention, fused_bn_relu,  # noqa: F401
                   mla_paged_attention, optimizer_update,
                   paged_attention)


def get(name: str) -> KernelSpec:
    _ensure_registered()
    try:
        return KERNELS[name]
    except KeyError:
        raise MXNetError("unknown kernel %r; registered: %s"
                         % (name, ", ".join(sorted(KERNELS)))) from None


def list_kernels() -> List[str]:
    _ensure_registered()
    return sorted(KERNELS)


def mode() -> str:
    """'auto' (env unset), 'off' (MXNET_TPU_KERNELS=0), 'on' (any other
    value) -- read per call so tests/bench can flip the tier around a
    trace (decisions are baked into each compiled program at trace
    time, like every other static op param)."""
    raw = os.environ.get("MXNET_TPU_KERNELS", "")
    if raw == "":
        return "auto"
    return "off" if raw == "0" else "on"


def enabled() -> bool:
    """Whether the Pallas tier may be selected at all."""
    return mode() != "off" and _has_pallas()


def available() -> bool:
    """Whether Pallas itself is importable on this build."""
    return _has_pallas()


def choose(name: str, force: Optional[bool] = None, **shape_kw) \
        -> KernelChoice:
    """THE selection point: decide pallas-vs-XLA for one kernel call.

    ``force`` mirrors the legacy per-op ``use_pallas`` tri-state:
    ``True`` forces the Pallas path (still subject to availability and
    the correctness ``supports`` gate; interpret mode on non-TPU),
    ``False`` forces the XLA fallback, ``None`` applies the env policy.
    """
    spec = get(name)
    if force is False:
        return KernelChoice(False, False, "caller forced XLA path")
    m = mode()
    if force is None and m == "off":
        return KernelChoice(False, False, "MXNET_TPU_KERNELS=0")
    if not _has_pallas():
        return KernelChoice(False, False,
                            "pallas unavailable -> XLA fallback")
    if spec.supports is not None:
        ok, why = spec.supports(**shape_kw)
        if not ok:
            return KernelChoice(False, False, why)
    if force is None and m == "auto" and spec.auto_predicate is not None \
            and not spec.auto_predicate(**shape_kw):
        return KernelChoice(False, False,
                            "auto policy declined (%s)" % name)
    backend = _backend()
    if backend == "tpu":
        return KernelChoice(True, False, "tpu backend")
    if force or m == "on":
        return KernelChoice(
            True, True,
            "interpret-mode kernel on %s backend" % backend)
    return KernelChoice(False, False,
                        "auto: %s backend -> XLA fallback" % backend)


def remedy_for(kind: str) -> Optional[str]:
    """The registered kernel remedying a perf-audit advisory ``kind``
    (e.g. ``'unfused-elementwise' -> 'kernels.fused_bn_relu'``), or
    None when no kernel covers it."""
    _ensure_registered()
    for name in sorted(KERNELS):
        if kind in KERNELS[name].remedies:
            return "kernels." + name
    return None


def describe() -> Dict[str, Dict]:
    """{name: {doc, categories, remedies, choice}} -- the fallback
    matrix docs/kernels.md renders, with each kernel's current
    no-shape-constraints selection decision."""
    _ensure_registered()
    out = {}
    for name, spec in sorted(KERNELS.items()):
        ch = choose(name) if spec.supports is None else None
        out[name] = {
            "doc": spec.doc,
            "categories": list(spec.categories),
            "remedies": list(spec.remedies),
            "mode": mode(),
            "choice": None if ch is None else
            {"use_pallas": ch.use_pallas, "interpret": ch.interpret,
             "reason": ch.reason},
        }
    return out
