"""paddle_tpu.native — C++ runtime components (ctypes-loaded).

Ref parity: the reference keeps its data ingestion in C++
(paddle/fluid/framework/data_feed.cc); this package holds the TPU build's
native pieces. The library is compiled on demand with the system g++ into
a per-version cache and loaded via ctypes (no pybind11 dependency).

Public surface:
  available()                     -> bool (toolchain + build ok)
  build_errors()                  -> {component: message} for builds that
                                     were attempted and FAILED
  gather_rows(src, indices)       -> np.ndarray, == src[indices] but
                                     GIL-free and multi-threaded
  gather_images_u8_chw(src, idx, scale, shift)
                                  -> f32 NCHW batch from u8 NHWC storage
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "datafeed.cc")

_lock = threading.Lock()
_lib = None
_build_error: str | None = None


def _cache_dir():
    root = os.environ.get("PADDLE_TPU_CACHE",
                          os.path.join(os.path.expanduser("~"), ".cache",
                                       "paddle_tpu"))
    os.makedirs(root, exist_ok=True)
    return root


def _compile(src, prefix, extra_flags=()):
    """Hash-keyed g++ build shared by every native component.

    -march=native binaries are host-specific: the cache key includes the
    machine/processor/compiler so a shared cache dir never serves a
    binary with illegal instructions to a different CPU generation."""
    import platform

    h = hashlib.sha256()
    with open(src, "rb") as f:
        h.update(f.read())
    h.update(platform.machine().encode())
    h.update(platform.processor().encode())
    try:
        h.update(subprocess.run(["g++", "--version"], capture_output=True,
                                text=True).stdout.encode())
    except OSError:
        pass
    digest = h.hexdigest()[:16]
    so = os.path.join(_cache_dir(), f"{prefix}-{digest}.so")
    if not os.path.exists(so):
        tmp = so + f".tmp{os.getpid()}"
        cmd = ["g++", "-O3", "-march=native", *extra_flags, "-shared",
               "-fPIC", "-pthread", "-std=c++17", src, "-o", tmp]
        subprocess.run(cmd, check=True, capture_output=True, text=True)
        os.replace(tmp, so)
    return ctypes.CDLL(so)


def _build():
    lib = _compile(_SRC, "libptfeed", ("-funroll-loops",))
    i64p = ctypes.POINTER(ctypes.c_int64)
    for name, ptr_t in [
        ("pt_gather_rows_f32", ctypes.POINTER(ctypes.c_float)),
        ("pt_gather_rows_u8", ctypes.POINTER(ctypes.c_uint8)),
        ("pt_gather_rows_i64", i64p),
        ("pt_gather_rows_i32", ctypes.POINTER(ctypes.c_int32)),
    ]:
        fn = getattr(lib, name)
        fn.argtypes = [ptr_t, ctypes.c_int64, i64p, ctypes.c_int64, ptr_t,
                       ctypes.c_int]
        fn.restype = None
    g = lib.pt_gather_u8hwc_to_f32chw
    g.argtypes = [ctypes.POINTER(ctypes.c_uint8), i64p, ctypes.c_int64,
                  ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
                  ctypes.c_float, ctypes.c_float,
                  ctypes.POINTER(ctypes.c_float), ctypes.c_int]
    g.restype = None
    return lib


_NO_COMPILER = "no g++ on this machine"


def _try_build(build):
    """(lib, None) or (None, why).  A machine without a compiler is a
    supported configuration (the numpy paths serve); a compiler that
    ran and failed, or a library that will not load, is a defect that
    `build_errors()` reports."""
    try:
        return build(), None
    except FileNotFoundError:
        return None, _NO_COMPILER
    except subprocess.CalledProcessError as e:
        return None, f"{e}\n{e.stderr or ''}".strip()
    except OSError as e:
        return None, str(e)


def build_errors() -> dict:
    """Native builds that were attempted and failed, by component;
    empty when every attempted build worked or there is no compiler."""
    with _lock:
        found = {"datafeed": _build_error, "ps_table": _ps_build_error}
    return {k: v for k, v in found.items()
            if v is not None and v != _NO_COMPILER}


def _get_lib():
    global _lib, _build_error
    with _lock:
        if _lib is None and _build_error is None:
            _lib, _build_error = _try_build(_build)
        return _lib


def available() -> bool:
    return _get_lib() is not None


_GATHER = {
    np.dtype(np.float32): ("pt_gather_rows_f32", ctypes.c_float),
    np.dtype(np.uint8): ("pt_gather_rows_u8", ctypes.c_uint8),
    np.dtype(np.int64): ("pt_gather_rows_i64", ctypes.c_int64),
    np.dtype(np.int32): ("pt_gather_rows_i32", ctypes.c_int32),
}


def _check_indices(idx, n):
    """Numpy fancy-index semantics before the C++ kernel: wrap negatives,
    raise IndexError out of range (instead of reading OOB memory)."""
    if idx.size == 0:
        return idx
    lo, hi = int(idx.min()), int(idx.max())
    if lo < -n or hi >= n:
        bad = lo if lo < -n else hi
        raise IndexError(
            f"index {bad} is out of bounds for axis 0 with size {n}")
    if lo < 0:
        idx = np.where(idx < 0, idx + n, idx)
    return np.ascontiguousarray(idx)


def _nthreads(default=None):
    if default is not None:
        return default
    try:
        from ..framework.flags import flag

        n = int(flag("FLAGS_paddle_num_threads"))
        if n > 1:
            return n
    except Exception:  # noqa: BLE001 — flags optional here
        pass
    return min(8, os.cpu_count() or 1)


def gather_rows(src: np.ndarray, indices, nthreads=None) -> np.ndarray:
    """out[i] = src[indices[i]] — parallel C++ copy for supported dtypes,
    numpy fancy-indexing fallback otherwise."""
    lib = _get_lib()
    src = np.ascontiguousarray(src)
    idx = np.ascontiguousarray(np.asarray(indices, dtype=np.int64))
    if lib is None or src.dtype not in _GATHER or src.ndim < 1:
        return src[idx]
    idx = _check_indices(idx, src.shape[0])
    name, ctype = _GATHER[src.dtype]
    row = int(np.prod(src.shape[1:], dtype=np.int64)) if src.ndim > 1 else 1
    out = np.empty((idx.shape[0],) + src.shape[1:], dtype=src.dtype)
    getattr(lib, name)(
        src.ctypes.data_as(ctypes.POINTER(ctype)), row,
        idx.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        idx.shape[0], out.ctypes.data_as(ctypes.POINTER(ctype)),
        _nthreads(nthreads))
    return out


def gather_images_u8_chw(src: np.ndarray, indices, scale=1.0 / 255.0,
                         shift=0.0, nthreads=None) -> np.ndarray:
    """f32 NCHW batch from u8 NHWC image storage, normalised in the same
    pass (the ToTensor+Normalize hot loop)."""
    lib = _get_lib()
    idx = np.ascontiguousarray(np.asarray(indices, dtype=np.int64))
    if lib is None or src.dtype != np.uint8 or src.ndim != 4:
        batch = src[idx].astype(np.float32) * scale + shift
        return np.transpose(batch, (0, 3, 1, 2))
    src = np.ascontiguousarray(src)
    idx = _check_indices(idx, src.shape[0])
    n = idx.shape[0]
    _, h, w, c = src.shape
    out = np.empty((n, c, h, w), dtype=np.float32)
    lib.pt_gather_u8hwc_to_f32chw(
        src.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        idx.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        n, h, w, c, ctypes.c_float(scale), ctypes.c_float(shift),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        _nthreads(nthreads))
    return out


# ---------------------------------------------------------------------------
# native sparse parameter table (ps_table.cc; ref
# paddle/fluid/distributed/table/common_sparse_table.cc)
# ---------------------------------------------------------------------------

_PS_SRC = os.path.join(_HERE, "ps_table.cc")
_ps_lib = None
_ps_build_error: str | None = None


def _build_ps():
    lib = _compile(_PS_SRC, "libpstable")
    i64p = ctypes.POINTER(ctypes.c_int64)
    f32p = ctypes.POINTER(ctypes.c_float)
    lib.pst_create.argtypes = [ctypes.c_int64, ctypes.c_float,
                               ctypes.c_float, ctypes.c_uint64]
    lib.pst_create.restype = ctypes.c_void_p
    lib.pst_free.argtypes = [ctypes.c_void_p]
    lib.pst_size.argtypes = [ctypes.c_void_p]
    lib.pst_size.restype = ctypes.c_int64
    lib.pst_pull.argtypes = [ctypes.c_void_p, i64p, ctypes.c_int64, f32p]
    lib.pst_push_sgd.argtypes = [ctypes.c_void_p, i64p, ctypes.c_int64,
                                 f32p, ctypes.c_float]
    lib.pst_push_adagrad.argtypes = [ctypes.c_void_p, i64p, ctypes.c_int64,
                                     f32p, ctypes.c_float, ctypes.c_float]
    lib.pst_push_delta.argtypes = [ctypes.c_void_p, i64p, ctypes.c_int64,
                                   f32p]
    lib.pst_export.argtypes = [ctypes.c_void_p, i64p, f32p]
    lib.pst_import.argtypes = [ctypes.c_void_p, i64p, ctypes.c_int64, f32p]
    # SSD spill variant (ref ssd_sparse_table.h)
    lib.pst_ssd_create.argtypes = [
        ctypes.c_int64, ctypes.c_float, ctypes.c_float, ctypes.c_uint64,
        ctypes.c_int64, ctypes.c_char_p, ctypes.c_int]
    lib.pst_ssd_create.restype = ctypes.c_void_p
    lib.pst_ssd_free.argtypes = [ctypes.c_void_p]
    for name in ("pst_ssd_size", "pst_ssd_resident", "pst_ssd_spilled"):
        fn = getattr(lib, name)
        fn.argtypes = [ctypes.c_void_p]
        fn.restype = ctypes.c_int64
    lib.pst_ssd_pull.argtypes = [ctypes.c_void_p, i64p, ctypes.c_int64,
                                 f32p]
    lib.pst_ssd_push_sgd.argtypes = [ctypes.c_void_p, i64p,
                                     ctypes.c_int64, f32p, ctypes.c_float]
    lib.pst_ssd_push_adagrad.argtypes = [
        ctypes.c_void_p, i64p, ctypes.c_int64, f32p, ctypes.c_float,
        ctypes.c_float]
    lib.pst_ssd_push_delta.argtypes = [ctypes.c_void_p, i64p,
                                       ctypes.c_int64, f32p]
    lib.pst_ssd_export.argtypes = [ctypes.c_void_p, i64p, f32p]
    lib.pst_ssd_export.restype = ctypes.c_int64
    lib.pst_ssd_import.argtypes = [ctypes.c_void_p, i64p, ctypes.c_int64,
                                   f32p]
    return lib


def ps_table_lib():
    """The compiled sparse-table library, or None (numpy fallback)."""
    global _ps_lib, _ps_build_error
    with _lock:
        if _ps_lib is None and _ps_build_error is None:
            _ps_lib, _ps_build_error = _try_build(_build_ps)
        return _ps_lib
