"""Persistent on-disk compile cache for serving executables.

Each per-bucket servable program is AOT-lowered at registration; the
lowered StableHLO text, normalized the same way ``profiling/cost.py``
normalizes compiled HLO (module name and source-location metadata
stripped), fingerprints the program.  The serialized ``jax.export``
artifact is committed under that fingerprint, so the *next* process
that registers the same model/bucket deserializes a portable program
instead of re-tracing Python -- and, stacked on the framework-wide
persistent XLA compilation cache (``JAX_COMPILATION_CACHE_DIR``, or
``.mxnet_tpu_cache/xla`` in the checkout), its warm-up compile is
served from disk too.

Artifacts are committed through ``checkpoint.core.atomic_write_bytes``
(tmp+fsync+rename), so a process killed mid-store can never leave a
truncated artifact where a loadable one would be trusted.
"""
from __future__ import annotations

import os
import re

from .. import telemetry as _telemetry

__all__ = ["CompileCache", "compile_through", "named",
           "stablehlo_fingerprint"]

# StableHLO normalization: jax stamps every op line with a loc(#locN)
# reference and appends a #locN = loc("file":line:col) table; the module
# name carries the traced function's name.  None of those affect the
# program, all of them vary across processes/refactors.
_LOC_REF = re.compile(r"\s*loc\(#loc\d*\)")
# an argument's inline location: loc("x") -- a name, never nested
_LOC_INLINE = re.compile(r"\s*loc\(\"[^\"]*\"\)")
_LOC_DEF = re.compile(r"^#loc\d*\s*=\s*loc\(.*\)\s*$", re.MULTILINE)
_LOC_BARE = re.compile(r"^#loc\s*=\s*loc\(.*\)\s*$", re.MULTILINE)
_MODULE = re.compile(r"^module @\S+", re.MULTILINE)


def stablehlo_fingerprint(text):
    """Stable identity of a lowered (StableHLO) program -- the PR-6
    normalized-HLO fingerprint applied at the serving layer: locations
    and the module name are normalized away, then the profiling
    subsystem's fingerprint hashes the rest."""
    from ..profiling.cost import fingerprint
    norm = _LOC_DEF.sub("", text)
    norm = _LOC_BARE.sub("", norm)
    norm = _LOC_REF.sub("", norm)
    norm = _LOC_INLINE.sub("", norm)
    norm = _MODULE.sub("module @<norm>", norm)
    return fingerprint(norm)


def default_cache_dir():
    from .. import env as _env
    from ..base import CACHE_ROOT
    return os.path.expanduser(_env.get("MXNET_TPU_SERVING_CACHE_DIR")) \
        or os.path.join(CACHE_ROOT, "serving")


def named(fn, name):
    """``fn`` behind a function called ``name``: ``jax.jit`` calls the
    module it compiles ``jit_<name>``, and a device trace calls the
    module's executions that (``XLA Modules``)."""
    def call(*args):
        return fn(*args)
    call.__name__ = call.__qualname__ = name
    return call


def compile_through(cache, key, jfn, lowered, specs, donate_argnums=(),
                    name=None):
    """AOT-compile one servable program whose lowering is ``lowered``
    (``jfn.lower(*specs)``, fingerprint ``key``).  With a cache, the
    program compiled is the ``jax.export`` wrapper of the artifact --
    the SAME wrapper whether the artifact was just made or read back,
    so the process that reads it back finds this compile in the
    persistent XLA cache; it is compiled here, not at the first
    request.  A program that cannot be exported is compiled as is.
    The wrapper's module is ``jit_<name>``, which a caller that named
    ``jfn``'s function so gives again (None: ``jit_call``).

    ``donate_argnums`` are the arguments ``jfn`` donates: the wrapper
    is a jit of its own, so it has to be told too, or the program the
    cache path compiles copies what the plain path writes in place."""
    import jax
    if cache is not None:
        exported = cache.get(key)
        if exported is None:
            try:
                from jax import export as jexport
                exported = jexport.export(jfn)(*specs)
                cache.put(key, exported)
            except Exception:
                exported = None
        if exported is not None:
            call = named(exported.call, name) if name else exported.call
            return jax.jit(call, donate_argnums=donate_argnums
                           ).lower(*specs).compile()
    return lowered.compile()


class CompileCache:
    """Fingerprint-keyed store of serialized ``jax.export`` artifacts.

    ``get(key)`` returns the deserialized ``Exported`` (or None);
    ``put(key, exported)`` commits its serialization atomically.  A
    corrupt or version-incompatible artifact reads as a miss, never an
    error -- the cache can only ever cost a recompile.
    """

    def __init__(self, root=None):
        self.root = os.fspath(root) if root else default_cache_dir()
        os.makedirs(self.root, exist_ok=True)

    def _path(self, key):
        return os.path.join(self.root, key + ".mxe")

    def get(self, key):
        path = self._path(key)
        try:
            with open(path, "rb") as f:
                blob = f.read()
            from jax import export as jexport
            exported = jexport.deserialize(blob)
        except Exception:
            self._record(hit=False)
            return None
        self._record(hit=True)
        return exported

    def put(self, key, exported):
        from ..checkpoint.core import atomic_write_bytes
        try:
            atomic_write_bytes(self._path(key), exported.serialize())
        except Exception:
            return None
        return self._path(key)

    def __contains__(self, key):
        return os.path.exists(self._path(key))

    @staticmethod
    def _record(hit):
        if _telemetry._ENABLED:
            _telemetry.hooks.serving_compile_cache(hit)
