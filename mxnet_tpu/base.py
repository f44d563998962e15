"""Base utilities: errors, registries, naming.

TPU-native re-design of the reference's ``python/mxnet/base.py`` and
dmlc-core error machinery (reference: ``python/mxnet/base.py :: check_call,
MXNetError``; ``3rdparty/dmlc-core/include/dmlc/logging.h``).  There is no C
ABI boundary here: the compute substrate is JAX/XLA, so errors are native
Python exceptions raised at op-call or sync points.
"""
from __future__ import annotations

import contextlib
import os
import re

# Everything the program builds or caches at run time (compiled XLA
# programs, serving export artifacts, the native .so) goes under this
# one git-ignored directory of the checkout, never under ``~``: what
# runs is then built from the files git tracks and nothing else.
CACHE_ROOT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".mxnet_tpu_cache")


def compile_cache_dir(environ=os.environ):
    """Where this checkout keeps JAX's persistent compile cache, or
    ``None`` where ``JAX_COMPILATION_CACHE_DIR`` is exported: JAX reads
    that itself and nothing is set in code.  One fixed path otherwise --
    the path is part of JAX's cache key, so a directory that moves
    between runs never hits."""
    if environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    return os.path.join(CACHE_ROOT, "xla")


@contextlib.contextmanager
def scopes_in_cache_key():
    """Compile what runs inside with its metadata in the persistent
    cache's key.  A cached executable carries the op names
    (``jax.named_scope`` paths) of whichever process compiled it, and
    JAX leaves them out of the key by default, so a device trace may read
    another version's scopes, or none.  The programs whose scopes a trace
    is read by (``obs.note_program``: the compiled train step, the decode
    and prefill programs) are compiled under this; everything else keeps
    JAX's default, under which the per-layer programs of an eager pass
    share one cache entry."""
    import jax
    name = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, name)
    jax.config.update(name, True)
    try:
        yield
    finally:
        jax.config.update(name, before)


class MXNetError(RuntimeError):
    """Framework error type (reference: ``base.py :: MXNetError``).

    Raised for shape/type inference failures, bad op arguments, and errors
    surfaced at synchronization points (``asnumpy``, ``wait_to_read``) --
    mirroring the reference's async error propagation contract
    (``src/engine/threaded_engine.cc :: OnCompleteStatic``).
    """


def check_call(ret):
    """Compatibility no-op: there is no flat C ABI in the TPU build."""
    return ret


_CAMEL_RE1 = re.compile(r"(.)([A-Z][a-z]+)")
_CAMEL_RE2 = re.compile(r"([a-z0-9])([A-Z])")


def camel_to_snake(name: str) -> str:
    s = _CAMEL_RE1.sub(r"\1_\2", name)
    return _CAMEL_RE2.sub(r"\1_\2", s).lower()


class _NameManager:
    """Auto-naming scope (reference: ``python/mxnet/name.py :: NameManager``)."""

    _current = None

    def __init__(self):
        self._counter = {}

    def get(self, name, hint):
        if name is not None:
            return name
        idx = self._counter.get(hint, 0)
        self._counter[hint] = idx + 1
        return "%s%d" % (hint, idx)

    @classmethod
    def current(cls):
        if cls._current is None:
            cls._current = _NameManager()
        return cls._current

    def __enter__(self):
        self._old = _NameManager._current
        _NameManager._current = self
        return self

    def __exit__(self, *args):
        _NameManager._current = self._old


def build_param_doc(params) -> str:
    """Render an op's typed parameter list as a numpydoc section.

    TPU-native analog of the reference's dmlc::Parameter ``__DOC__``
    generation (``3rdparty/dmlc-core/include/dmlc/parameter.h``): the op
    registry is self-describing and Python signatures/docstrings are
    generated from it at import time.
    """
    lines = ["Parameters", "----------"]
    for p in params:
        lines.append("%s : %s, optional, default=%r" % (p.name, p.type_str, p.default)
                     if p.has_default else "%s : %s, required" % (p.name, p.type_str))
        if p.doc:
            lines.append("    " + p.doc)
    return "\n".join(lines)
