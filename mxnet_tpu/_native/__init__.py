"""Native runtime components, compiled on demand.

The C++ sources live next to this file; at first import they are built
with the system toolchain (g++ -O2 -shared -fPIC) into a cached shared
library, loaded via ctypes.  No native toolchain, or a failed build,
degrades gracefully: callers get ``None`` and use the pure-Python path.
Set ``MXNET_TPU_NATIVE=0`` to force the Python path.
"""
from __future__ import annotations

import ctypes
import os
import subprocess
import warnings

_LIB = None
_TRIED = False

_SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    "recordio_native.cc")


def _cache_dir():
    from ..base import CACHE_ROOT
    d = os.environ.get("MXNET_TPU_NATIVE_CACHE") \
        or os.path.join(CACHE_ROOT, "native")
    os.makedirs(d, exist_ok=True)
    return d


def _build(src, out):
    """Compile under an flock, into a temp file renamed atomically into
    place: N launcher workers may import cold-cache simultaneously, and
    a half-written .so must never be dlopen'd (or truncate a mapping
    another process already holds)."""
    import fcntl
    lock_path = out + ".lock"
    with open(lock_path, "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        try:
            # another process may have finished the build while we waited
            if os.path.exists(out) and \
                    os.path.getmtime(out) >= os.path.getmtime(src):
                return
            tmp = "%s.%d.tmp" % (out, os.getpid())
            cmd = ["g++", "-O2", "-shared", "-fPIC", "-std=c++17",
                   "-pthread", src, "-o", tmp]
            proc = subprocess.run(cmd, capture_output=True, text=True,
                                  timeout=120)
            if proc.returncode != 0:
                raise RuntimeError("native build failed:\n%s"
                                   % proc.stderr[-2000:])
            os.replace(tmp, out)
        finally:
            fcntl.flock(lock, fcntl.LOCK_UN)


def available():
    """Cheap probe: is a current .so already built AND loadable?  Never
    compiles -- diagnostics (runtime.Features) must not block on g++."""
    if _LIB is not None:
        return True
    if os.environ.get("MXNET_TPU_NATIVE", "1") == "0":
        return False
    so = os.path.join(_cache_dir(), "librecordio_native.so")
    if not (os.path.exists(so)
            and os.path.getmtime(so) >= os.path.getmtime(_SRC)):
        return False
    try:
        ctypes.CDLL(so)   # a stale half-written .so must not report ✔
        return True
    except OSError:
        return False


def load():
    """Return the loaded native library, or None when unavailable."""
    global _LIB, _TRIED
    if _TRIED:
        return _LIB
    _TRIED = True
    if os.environ.get("MXNET_TPU_NATIVE", "1") == "0":
        return None
    try:
        so = os.path.join(_cache_dir(), "librecordio_native.so")
        if (not os.path.exists(so)
                or os.path.getmtime(so) < os.path.getmtime(_SRC)):
            _build(_SRC, so)
        lib = ctypes.CDLL(so)
        lib.rio_open.restype = ctypes.c_void_p
        lib.rio_open.argtypes = [ctypes.c_char_p, ctypes.c_int]
        lib.rio_close.argtypes = [ctypes.c_void_p]
        lib.rio_tell.restype = ctypes.c_long
        lib.rio_tell.argtypes = [ctypes.c_void_p]
        lib.rio_seek.restype = ctypes.c_int
        lib.rio_seek.argtypes = [ctypes.c_void_p, ctypes.c_long]
        lib.rio_flush.restype = ctypes.c_int
        lib.rio_flush.argtypes = [ctypes.c_void_p]
        lib.rio_write.restype = ctypes.c_int
        lib.rio_write.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                  ctypes.c_long]
        # out-pointers are void*: a c_char_p restype/arg would make
        # ctypes copy to Python bytes and lose the malloc'd pointer,
        # so rio_free would free a Python-owned buffer (heap abort)
        lib.rio_read.restype = ctypes.c_long
        lib.rio_read.argtypes = [ctypes.c_void_p,
                                 ctypes.POINTER(ctypes.c_void_p)]
        lib.rio_free.argtypes = [ctypes.c_void_p]
        lib.rio_read_batch.restype = ctypes.c_int
        lib.rio_read_batch.argtypes = [
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_long), ctypes.c_int,
            ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_long),
            ctypes.c_int]
        _LIB = lib
    except Exception as e:  # no toolchain / build error: Python fallback
        warnings.warn("mxnet_tpu native components unavailable (%s); "
                      "using pure-Python recordio" % e)
        _LIB = None
    return _LIB


# ----------------------------------------------------------------------
# C predict runtime (predict_native.cc -- reference: c_predict_api.cc)
# ----------------------------------------------------------------------

_PRED_LIB = None
_PRED_TRIED = False

_PRED_SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "predict_native.cc")


def predict_so_path():
    """Path the predict runtime builds to (for linking C consumers)."""
    return os.path.join(_cache_dir(), "libmxtpu_predict.so")


def load_predict():
    """Build-on-demand loader for the C predict runtime; returns the
    ctypes library or None (no toolchain / build failure)."""
    global _PRED_LIB, _PRED_TRIED
    if _PRED_TRIED:
        return _PRED_LIB
    _PRED_TRIED = True
    if os.environ.get("MXNET_TPU_NATIVE", "1") == "0":
        return None
    try:
        so = predict_so_path()
        if (not os.path.exists(so)
                or os.path.getmtime(so) < os.path.getmtime(_PRED_SRC)):
            _build(_PRED_SRC, so)
        lib = ctypes.CDLL(so)
        lib.MXPredGetLastError.restype = ctypes.c_char_p
        lib.MXPredCreate.restype = ctypes.c_int
        lib.MXPredCreate.argtypes = [ctypes.c_char_p, ctypes.c_int64,
                                     ctypes.POINTER(ctypes.c_void_p)]
        lib.MXPredCreateFromFile.restype = ctypes.c_int
        lib.MXPredCreateFromFile.argtypes = [
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_void_p)]
        lib.MXPredSetInput.restype = ctypes.c_int
        lib.MXPredSetInput.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_float),
            ctypes.POINTER(ctypes.c_int64), ctypes.c_int]
        lib.MXPredForward.restype = ctypes.c_int
        lib.MXPredForward.argtypes = [ctypes.c_void_p]
        lib.MXPredGetOutputShape.restype = ctypes.c_int
        lib.MXPredGetOutputShape.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int)]
        lib.MXPredGetOutput.restype = ctypes.c_int
        lib.MXPredGetOutput.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.POINTER(ctypes.c_float),
            ctypes.c_int64]
        lib.MXPredFree.argtypes = [ctypes.c_void_p]
        _PRED_LIB = lib
    except Exception as e:  # degrade gracefully, like the recordio engine
        warnings.warn("native predict runtime unavailable: %s" % e)
        _PRED_LIB = None
    return _PRED_LIB
