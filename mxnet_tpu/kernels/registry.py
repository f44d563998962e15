"""Pallas kernel registry + selection policy (the fused-operator half
of the blueprint: "fused operators ... become Pallas custom-calls").

One place decides, per kernel and call shape, whether the hand-written
Pallas implementation or the XLA reference path runs.  The choice is a
function of the backend, the call's shape and the caller's ``force``
argument, and of nothing else.  In order (docs/kernels.md):

1. ``force is False``        -> XLA reference.
2. Pallas unimportable       -> XLA reference (CPU-only minimal builds).
3. The kernel's ``supports`` predicate rejects the call shape (e.g.
   flash attention needs seq divisible by the block sizes) -> XLA
   reference with the reason recorded.
4. ``force is None`` and the kernel's ``auto_predicate`` declines
   (flash attention's measured seq>=256 crossover) -> XLA reference.
5. The default backend is a TPU -> Pallas, compiled by Mosaic.
6. ``force is True`` off the chip -> Pallas in ``interpret=True`` mode,
   so tier-1 tests exercise the REAL kernel bodies.
7. Otherwise                 -> XLA reference.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, List, Optional

from ..base import MXNetError

__all__ = ["KernelSpec", "KernelChoice", "register_kernel", "get",
           "list_kernels", "available", "choose"]


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
    # (**shape_kwargs) -> (ok, reason): correctness constraints only
    supports: Optional[Callable] = None
    # (**shape_kwargs) -> bool: profitability gate when the caller
    # leaves the choice open (force=None)
    auto_predicate: Optional[Callable] = None
    # the XLA reference implementation (fallback + numerics oracle)
    xla_ref: Optional[Callable] = None

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
    from . import (flash_attention, grouped_matmul,  # noqa: F401
                   kda_decode, mla_paged_attention, paged_attention)


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


def available() -> bool:
    """Whether Pallas itself is importable on this build."""
    return _has_pallas()


def choose(name: str, force: Optional[bool] = None, **shape_kw) \
        -> KernelChoice:
    """THE selection point: decide pallas-vs-XLA for one kernel call.

    ``force`` is the caller's ``use_pallas`` tri-state: ``True`` asks
    for the Pallas path (still subject to availability and the
    correctness ``supports`` gate; interpret mode on non-TPU),
    ``False`` asks for the XLA reference, ``None`` leaves it to the
    kernel's profitability gate and the backend.
    """
    spec = get(name)
    if force is False:
        return KernelChoice(False, False, "caller forced XLA path")
    if not _has_pallas():
        return KernelChoice(False, False,
                            "pallas unavailable -> XLA fallback")
    if spec.supports is not None:
        ok, why = spec.supports(**shape_kw)
        if not ok:
            return KernelChoice(False, False, why)
    if force is None and spec.auto_predicate is not None \
            and not spec.auto_predicate(**shape_kw):
        return KernelChoice(False, False,
                            "auto policy declined (%s)" % name)
    backend = _backend()
    if backend == "tpu":
        return KernelChoice(True, False, "tpu backend")
    if force:
        return KernelChoice(
            True, True,
            "interpret-mode kernel on %s backend" % backend)
    return KernelChoice(False, False,
                        "auto: %s backend -> XLA fallback" % backend)
