"""JIT build + ctypes load of the native host library.

Reference: op_builder/builder.py:526,545 — JIT compile of csrc sources
into a per-version cache, with ``is_compatible()`` probing and graceful
fallback. pybind11 is unavailable in this image, so the library exposes a
plain C ABI consumed via ctypes; sources live in csrc/ at the repo root.

Cache key = SHA1 of all sources + compiler id, so editing a .cpp
invalidates the cached .so (same contract as TORCH_EXTENSIONS_DIR
rebuilds).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import shutil
import subprocess
import threading
from typing import Optional

from deepspeed_tpu.utils.logging import logger

_SOURCES = ("aio/dstpu_aio.cpp", "adam/dstpu_cpu_adam.cpp")
_LIB_BASENAME = "libdstpu_native.so"

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_build_error: Optional[str] = None


def _csrc_dir() -> str:
    here = os.path.dirname(os.path.abspath(__file__))
    return os.path.normpath(os.path.join(here, "..", "..", "..", "csrc"))


def _cache_dir() -> str:
    root = os.environ.get("DSTPU_CACHE_DIR",
                          os.path.join(os.path.expanduser("~"), ".cache",
                                       "deepspeed_tpu"))
    return os.path.join(root, "native")


def _source_hash(paths) -> str:
    h = hashlib.sha1()
    for p in paths:
        with open(p, "rb") as f:
            h.update(f.read())
    cxx = shutil.which(os.environ.get("CXX", "g++")) or "none"
    h.update(cxx.encode())
    return h.hexdigest()[:16]


def build_native_lib(verbose: bool = False) -> Optional[ctypes.CDLL]:
    """Compile (cached) and load the native library; None if unavailable
    (host-offload callers then run their numpy path — every reason is
    logged once, so the slower implementation is never silent)."""
    global _lib, _build_error
    with _lock:
        if _lib is not None:
            return _lib
        if _build_error is not None:
            return None
        cxx = shutil.which(os.environ.get("CXX", "g++"))
        if cxx is None:
            _build_error = "no C++ compiler found"
            logger.warning(_build_error)
            return None
        srcs = [os.path.join(_csrc_dir(), s) for s in _SOURCES]
        missing = [s for s in srcs if not os.path.exists(s)]
        if missing:
            _build_error = f"missing sources: {missing}"
            logger.warning(_build_error)
            return None
        tag = _source_hash(srcs)
        out_dir = os.path.join(_cache_dir(), tag)
        so_path = os.path.join(out_dir, _LIB_BASENAME)
        if not os.path.exists(so_path):
            os.makedirs(out_dir, exist_ok=True)
            # per-process tmp name: concurrent builds (multi-process launch
            # sharing $HOME) must not write through the same inode
            tmp = f"{so_path}.tmp.{os.getpid()}"
            cmd = [cxx, "-O3", "-shared", "-fPIC", "-std=c++17", "-fopenmp",
                   "-march=native", *srcs, "-o", tmp, "-lpthread"]
            try:
                subprocess.run(cmd, check=True, capture_output=not verbose)
            except subprocess.CalledProcessError:
                # -march=native can fail on exotic hosts; retry portable.
                cmd = [c for c in cmd if c != "-march=native"]
                try:
                    subprocess.run(cmd, check=True, capture_output=not verbose)
                except subprocess.CalledProcessError as e:
                    _build_error = f"native build failed: {e}"
                    logger.warning(_build_error)
                    return None
            os.replace(tmp, so_path)
        try:
            _lib = ctypes.CDLL(so_path)
        except OSError as e:
            _build_error = f"dlopen failed: {e}"
            logger.warning(_build_error)
            return None
        _declare(_lib)
        return _lib


def native_available() -> bool:
    return build_native_lib() is not None


def native_status() -> str:
    """For dstpu-report: 'built' or the failure reason."""
    if build_native_lib() is not None:
        return "built"
    return _build_error or "not built"


def _declare(lib: ctypes.CDLL) -> None:
    c = ctypes
    i64, vp, cp = c.c_int64, c.c_void_p, c.c_char_p
    f32p = c.POINTER(c.c_float)
    u16p = c.POINTER(c.c_uint16)
    flt, i32 = c.c_float, c.c_int

    lib.dstpu_aio_create.restype = vp
    lib.dstpu_aio_create.argtypes = [i32, i32, i32]
    lib.dstpu_aio_create2.restype = vp
    lib.dstpu_aio_create2.argtypes = [i32, i32, i32, i32]
    lib.dstpu_aio_backend.restype = i32
    lib.dstpu_aio_backend.argtypes = [vp]
    lib.dstpu_aio_destroy.argtypes = [vp]
    for name in ("dstpu_aio_pread", "dstpu_aio_sync_pread"):
        fn = getattr(lib, name)
        fn.restype = i32
        fn.argtypes = [vp, vp, i64, cp, i64]
    for name in ("dstpu_aio_pwrite", "dstpu_aio_sync_pwrite"):
        fn = getattr(lib, name)
        fn.restype = i32
        fn.argtypes = [vp, vp, i64, cp, i64]
    lib.dstpu_aio_wait.restype = i32
    lib.dstpu_aio_wait.argtypes = [vp]
    lib.dstpu_aio_bytes_read.restype = i64
    lib.dstpu_aio_bytes_read.argtypes = [vp]
    lib.dstpu_aio_bytes_written.restype = i64
    lib.dstpu_aio_bytes_written.argtypes = [vp]
    lib.dstpu_alloc_pinned.restype = vp
    lib.dstpu_alloc_pinned.argtypes = [i64]
    lib.dstpu_free_pinned.argtypes = [vp, i64]

    lib.dstpu_adam_step.argtypes = [f32p, f32p, f32p, f32p, i64, flt, flt,
                                    flt, flt, flt, i32, i32, i32, u16p]
    lib.dstpu_adam_step_bf16grad.argtypes = [f32p, u16p, f32p, f32p, i64,
                                             flt, flt, flt, flt, flt, i32,
                                             i32, i32, u16p]
    lib.dstpu_lion_step.argtypes = [f32p, f32p, f32p, i64, flt, flt, flt,
                                    flt, u16p]
    lib.dstpu_adagrad_step.argtypes = [f32p, f32p, f32p, i64, flt, flt, flt,
                                       u16p]
    lib.dstpu_f32_to_bf16.argtypes = [f32p, u16p, i64]
    lib.dstpu_bf16_to_f32.argtypes = [u16p, f32p, i64]
    lib.dstpu_sq_norm.restype = ctypes.c_double
    lib.dstpu_sq_norm.argtypes = [f32p, i64]
