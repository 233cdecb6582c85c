"""Native-library loader + ctypes surface (≙ python/mxnet/base.py _load_lib
over the reference's libmxnet.so C API, include/mxnet/c_api.h).

The native runtime (`libmxtpu_rt.so`, sources under src/) provides the async
dependency engine, pooled storage manager, thread pool and RecordIO reader/
writer.  It is auto-built with g++ on first import if missing or stale
(stale = the sha256 of the build inputs differs from the one stored
beside the library — content, not mtimes);
callers must tolerate ``LIB is None`` (pure-Python fallbacks) so the package
still imports on machines without a toolchain.
"""
from __future__ import annotations

import ctypes
import os
import subprocess
import sys

__all__ = ["LIB", "check_call", "MXTpuError", "lib_path"]

_CUR = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_CUR)
_LIB_PATH = os.path.join(_CUR, "lib", "libmxtpu_rt.so")
_STAMP_PATH = _LIB_PATH + ".inputs.sha256"


def _build_inputs():
    """Everything the native build reads: all sources/headers under src/
    and include/ (globbed, not hand-listed — a hand-kept list here once
    went stale and produced partial rebuilds)."""
    import glob
    out = []
    for pat in ("Makefile", "src/*.cc", "src/*.h", "include/mxtpu/*.h"):
        out.extend(glob.glob(os.path.join(_ROOT, pat)))
    return sorted(out)


def _inputs_digest() -> str:
    """sha256 over the names and bytes of the build inputs.  Stored
    beside the library when it is built, so staleness is a matter of
    content: a copy or checkout that resets mtimes rebuilds nothing."""
    import hashlib
    h = hashlib.sha256()
    for path in _build_inputs():
        try:
            with open(path, "rb") as f:
                data = f.read()
        except OSError:      # deleted between glob and read (branch switch)
            continue
        h.update(os.path.relpath(path, _ROOT).encode() + b"\0")
        h.update(data + b"\0")
    return h.hexdigest()


class MXTpuError(RuntimeError):
    """Error raised from the native runtime (≙ mxnet.base.MXNetError)."""


def _needs_build() -> bool:
    if not os.path.exists(_LIB_PATH):
        return True
    try:
        with open(_STAMP_PATH) as f:
            return f.read().strip() != _inputs_digest()
    except OSError:          # library without a stamp: built by hand
        return True


def _build() -> bool:
    # Delegate to the Makefile: it owns the FULL source list plus the
    # OpenCV / embedded-CPython feature detection.  A private 3-file
    # compile here once clobbered the full lib with a featureless one —
    # the build recipe must live in exactly one place.  make targets a
    # process-private temp path (LIB= override) renamed atomically over
    # the real one, so a concurrent import never dlopens a half-written
    # .so.  Concurrent builders serialise on flock, which the kernel
    # releases even if the holder is SIGKILLed (no stale-lock limbo).
    if not os.path.exists(os.path.join(_ROOT, "Makefile")):
        return os.path.exists(_LIB_PATH)
    os.makedirs(os.path.dirname(_LIB_PATH), exist_ok=True)
    import fcntl
    lock_fd = os.open(f"{_LIB_PATH}.lock", os.O_CREAT | os.O_WRONLY, 0o644)
    try:
        fcntl.flock(lock_fd, fcntl.LOCK_EX)     # blocks while another builds
        if not _needs_build():                   # the winner already built it
            return True
        tmp = f"{_LIB_PATH}.{os.getpid()}.tmp"
        try:
            digest = _inputs_digest()
            subprocess.run(
                ["make", "-C", _ROOT, "-B",
                 f"LIB={os.path.relpath(tmp, _ROOT)}"],
                check=True, capture_output=True, timeout=300)
            os.replace(tmp, _LIB_PATH)
            with open(f"{_STAMP_PATH}.{os.getpid()}.tmp", "w") as f:
                f.write(digest + "\n")
            os.replace(f.name, _STAMP_PATH)
            return True
        except Exception as e:  # toolchain missing / compile error → fallback
            sys.stderr.write(f"[mxnet_tpu] native build skipped: {e}\n")
            try:
                os.unlink(tmp)
            except OSError:
                pass
            return os.path.exists(_LIB_PATH)
    finally:
        os.close(lock_fd)


def _load():
    if os.environ.get("MXNET_TPU_NO_NATIVE"):
        return None
    try:
        if _needs_build() and not _build():
            return None
        lib = ctypes.CDLL(_LIB_PATH)
    except Exception as e:
        sys.stderr.write(f"[mxnet_tpu] native lib unavailable: {e}\n")
        return None
    lib.MXTGetLastError.restype = ctypes.c_char_p
    return lib


LIB = _load()


def lib_path():
    return _LIB_PATH if LIB is not None else None


def check_call(ret: int):
    """Raise on non-zero return, carrying the native error message
    (≙ mxnet.base.check_call → MXGetLastError)."""
    if ret != 0:
        msg = LIB.MXTGetLastError().decode("utf-8", "replace") if LIB else "?"
        raise MXTpuError(msg)


# Shared ctypes signatures (None-safe: only set when the lib loaded).
if LIB is not None:
    LIB.MXTEngineCreate.argtypes = [ctypes.c_int, ctypes.c_int,
                                    ctypes.POINTER(ctypes.c_void_p)]
    LIB.MXTEngineFree.argtypes = [ctypes.c_void_p]
    LIB.MXTEngineNewVariable.argtypes = [ctypes.c_void_p,
                                         ctypes.POINTER(ctypes.c_int64)]
    LIB.MXTEngineDeleteVariable.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    LIB.MXTEngineWaitForVar.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    LIB.MXTEngineWaitForAll.argtypes = [ctypes.c_void_p]
    LIB.MXTEngineNumExecuted.argtypes = [ctypes.c_void_p,
                                         ctypes.POINTER(ctypes.c_int64)]
    LIB.MXTStorageCreate.argtypes = [ctypes.c_int, ctypes.c_size_t,
                                     ctypes.POINTER(ctypes.c_void_p)]
    LIB.MXTStorageFree.argtypes = [ctypes.c_void_p]
    LIB.MXTStorageAlloc.argtypes = [ctypes.c_void_p, ctypes.c_size_t,
                                    ctypes.POINTER(ctypes.c_void_p)]
    LIB.MXTStorageRelease.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
    LIB.MXTStorageDirectFree.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
    LIB.MXTStorageReleaseAll.argtypes = [ctypes.c_void_p]
    LIB.MXTStorageStats.argtypes = [ctypes.c_void_p] + \
        [ctypes.POINTER(ctypes.c_size_t)] * 4
    LIB.MXTRecordIOWriterCreate.argtypes = [ctypes.c_char_p,
                                            ctypes.POINTER(ctypes.c_void_p)]
    LIB.MXTRecordIOWriterFree.argtypes = [ctypes.c_void_p]
    LIB.MXTRecordIOWriteRecord.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                           ctypes.c_size_t]
    LIB.MXTRecordIOWriterTell.argtypes = [ctypes.c_void_p,
                                          ctypes.POINTER(ctypes.c_size_t)]
    LIB.MXTRecordIOReaderCreate.argtypes = [ctypes.c_char_p,
                                            ctypes.POINTER(ctypes.c_void_p)]
    LIB.MXTRecordIOReaderFree.argtypes = [ctypes.c_void_p]
    LIB.MXTRecordIOReadRecord.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_void_p),
        ctypes.POINTER(ctypes.c_size_t)]
    LIB.MXTRecordIOReaderSeek.argtypes = [ctypes.c_void_p, ctypes.c_size_t]
    LIB.MXTRecordIOReaderTell.argtypes = [ctypes.c_void_p,
                                          ctypes.POINTER(ctypes.c_size_t)]
