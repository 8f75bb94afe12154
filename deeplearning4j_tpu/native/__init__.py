"""Native runtime loader — builds and binds libdl4j_native (C++17).

The reference's data plane is native (DataVec record readers, the custom
MNIST binary reader under `datasets/mnist/`, MagicQueue prefetch); here the
equivalents live in `dl4j_native.cpp`, compiled on first use with the host
toolchain and bound with ctypes (no pybind11 in the image). Everything has
a pure-Python fallback — `native_available()` gates the fast path, exactly
like the reference's runtime cuDNN-helper probe
(`ConvolutionLayer.initializeHelper` pattern).
"""
from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading
from typing import Optional

import numpy as np

log = logging.getLogger("deeplearning4j_tpu")

__all__ = ["native_available", "lib", "idx_read_native", "csv_read_native",
           "u8_to_f32", "image_decode_native", "PrefetchRing"]

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "dl4j_native.cpp")
_LOCK = threading.Lock()
_LIB: Optional[ctypes.CDLL] = None
_TRIED = False


# <checkout>/.native_build (git-ignored): the library is built from what the
# checkout holds and never outlives it
_BUILD_DIR = os.path.join(os.path.dirname(os.path.dirname(_HERE)),
                          ".native_build")


def _lib_path() -> str:
    """Library path keyed by the source's content hash: an edited
    `dl4j_native.cpp` gets a new name, so a stale build is never loaded."""
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    os.makedirs(_BUILD_DIR, exist_ok=True)
    return os.path.join(_BUILD_DIR, f"libdl4j_native-{digest}.so")


def _build(dest: str) -> bool:
    # build to a temp file in the same dir, then atomically os.replace:
    # concurrent builders don't corrupt each other, and a long-running
    # process with the old .so mmapped keeps its (unlinked) inode instead
    # of taking SIGBUS from an in-place truncate
    tmp = f"{dest}.build.{os.getpid()}"
    base = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread",
            _SRC, "-o", tmp]
    try:
        # zlib is only needed by the PNG decoder: if the dev files are
        # missing, fall back to a zlib-free build (PNG -> PIL) instead of
        # losing the whole native tier
        out = subprocess.run(base + ["-lz"], capture_output=True, text=True,
                             timeout=180)
        if out.returncode != 0:
            out = subprocess.run(base + ["-DDL4J_NO_ZLIB"],
                                 capture_output=True, text=True, timeout=180)
        if out.returncode != 0:
            log.warning("native build failed:\n%s", out.stderr[-2000:])
            return False
        os.replace(tmp, dest)
        return True
    except (OSError, subprocess.TimeoutExpired) as e:
        log.warning("native build unavailable: %s", e)
        return False
    finally:
        try:
            if os.path.exists(tmp):
                os.remove(tmp)
        except OSError:
            pass


def _bind(lib: ctypes.CDLL):
    c_char_p, c_int, c_i64 = ctypes.c_char_p, ctypes.c_int, ctypes.c_int64
    u8_p = ctypes.POINTER(ctypes.c_uint8)
    f32_p = ctypes.POINTER(ctypes.c_float)
    i64_p = ctypes.POINTER(c_i64)
    lib.idx_header.argtypes = [c_char_p, ctypes.POINTER(c_int),
                               ctypes.POINTER(c_int), i64_p]
    lib.idx_header.restype = c_int
    lib.idx_payload.argtypes = [c_char_p, u8_p, c_i64]
    lib.idx_payload.restype = c_i64
    lib.u8_to_f32.argtypes = [u8_p, f32_p, c_i64, ctypes.c_float,
                              ctypes.c_float]
    lib.u8_to_f32.restype = None
    lib.u8_binarize_f32.argtypes = [u8_p, f32_p, c_i64, c_int]
    lib.u8_binarize_f32.restype = None
    lib.csv_shape.argtypes = [c_char_p, c_int, i64_p, i64_p]
    lib.csv_shape.restype = c_int
    lib.csv_parse_f32.argtypes = [c_char_p, c_int, f32_p, c_i64, c_i64]
    lib.csv_parse_f32.restype = c_i64
    lib.csv_parse_alloc.argtypes = [c_char_p, c_int,
                                    ctypes.POINTER(f32_p), i64_p, i64_p]
    lib.csv_parse_alloc.restype = c_i64
    lib.csv_free.argtypes = [f32_p]
    lib.csv_free.restype = None
    lib.ring_open.argtypes = [c_char_p, c_i64, c_i64, c_i64, c_i64, c_int]
    lib.ring_open.restype = ctypes.c_void_p
    lib.ring_next.argtypes = [ctypes.c_void_p, u8_p]
    lib.ring_next.restype = c_i64
    lib.ring_close.argtypes = [ctypes.c_void_p]
    lib.ring_close.restype = None
    lib.ring_error.argtypes = [ctypes.c_void_p]
    lib.ring_error.restype = c_int
    int_p = ctypes.POINTER(c_int)
    lib.image_decode_alloc.argtypes = [c_char_p, ctypes.POINTER(u8_p),
                                       int_p, int_p, int_p]
    lib.image_decode_alloc.restype = c_int
    lib.image_free.argtypes = [u8_p]
    lib.image_free.restype = None
    lib.dl4j_native_abi.argtypes = []
    lib.dl4j_native_abi.restype = c_int


def _load() -> Optional[ctypes.CDLL]:
    global _LIB, _TRIED
    with _LOCK:
        if _LIB is not None or _TRIED:
            return _LIB
        _TRIED = True
        if os.environ.get("DL4J_TPU_DISABLE_NATIVE", "").strip().lower() \
                in ("1", "true", "yes", "on"):
            return None
        try:
            path = _lib_path()
            if not os.path.exists(path):
                # the one-time cc build MUST complete under _LOCK:
                # concurrent importers have nothing to do until the
                # artifact exists, and exactly-once is the point
                if not _build(path):  # graftlint: disable=blocking-call-under-lock
                    return None
            lib = ctypes.CDLL(path)
            _bind(lib)
            if lib.dl4j_native_abi() != 2:
                return None
            _LIB = lib
        except Exception as e:   # ANY probe failure degrades to pure Python
            log.warning("native tier unavailable: %s", e)
            return None
        return _LIB


def native_available() -> bool:
    return _load() is not None


def lib() -> ctypes.CDLL:
    l = _load()
    if l is None:
        raise RuntimeError("dl4j_native is not available on this host")
    return l


# ---------------------------------------------------------------------------
# numpy-facing wrappers
# ---------------------------------------------------------------------------

_IDX_DTYPES = {0x08: (np.uint8, 1), 0x09: (np.int8, 1), 0x0B: (">i2", 2),
               0x0C: (">i4", 4), 0x0D: (">f4", 4), 0x0E: (">f8", 8)}


def idx_read_native(path: str) -> np.ndarray:
    """Read an (uncompressed) IDX file via the native decoder."""
    l = lib()
    dtype = ctypes.c_int()
    ndim = ctypes.c_int()
    dims = (ctypes.c_int64 * 8)()
    rc = l.idx_header(path.encode(), ctypes.byref(dtype), ctypes.byref(ndim),
                      dims)
    if rc != 0:
        raise ValueError(f"bad IDX file {path!r} (rc={rc})")
    if dtype.value not in _IDX_DTYPES:
        raise ValueError(f"unknown IDX dtype 0x{dtype.value:02x}")
    np_dtype, itemsize = _IDX_DTYPES[dtype.value]
    shape = tuple(dims[i] for i in range(ndim.value))
    n = int(np.prod(shape)) * itemsize
    # validate the untrusted header against the real file size BEFORE
    # allocating (a corrupt header must not drive a multi-TiB np.empty),
    # and reject trailing garbage like the pure-Python parser does
    expected = 4 + 4 * ndim.value + n
    actual = os.path.getsize(path)
    if actual != expected:
        raise ValueError(
            f"{path}: payload size {actual - 4 - 4 * ndim.value} != shape "
            f"{shape} ({n} bytes expected)")
    buf = np.empty(n, np.uint8)
    got = l.idx_payload(path.encode(),
                        buf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
                        n)
    if got != n:
        raise ValueError(f"IDX payload short read: {got} != {n}")
    return buf.view(np_dtype).reshape(shape)


def csv_read_native(path: str, skip_rows: int = 0) -> np.ndarray:
    """Parse a numeric CSV into a float32 [rows, cols] array (single file
    read; ragged rows are an error, matching the numpy fallback)."""
    l = lib()
    rows = ctypes.c_int64()
    cols = ctypes.c_int64()
    buf = ctypes.POINTER(ctypes.c_float)()
    rc = l.csv_parse_alloc(path.encode(), skip_rows, ctypes.byref(buf),
                           ctypes.byref(rows), ctypes.byref(cols))
    if rc == -5:
        raise ValueError(f"{path}: ragged CSV (rows have differing field "
                         "counts)")
    if rc != 0:
        raise ValueError(f"cannot read CSV {path!r} (rc={rc})")
    try:
        n = rows.value * cols.value
        out = np.ctypeslib.as_array(buf, shape=(n,)).astype(
            np.float32, copy=True).reshape(rows.value, cols.value) \
            if n else np.empty((rows.value, cols.value), np.float32)
    finally:
        if buf:  # free even for 0-element results (malloc(0) may be non-NULL)
            l.csv_free(buf)
    return out


def u8_to_f32(src: np.ndarray, scale: float = 1.0 / 255.0,
              shift: float = 0.0, binarize: bool = False,
              threshold: int = 30) -> np.ndarray:
    """Normalize a uint8 payload to float32 natively (reference
    MnistDataFetcher normalization/binarize flags)."""
    l = lib()
    src = np.ascontiguousarray(src, np.uint8)
    out = np.empty(src.shape, np.float32)
    sp = src.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))
    dp = out.ctypes.data_as(ctypes.POINTER(ctypes.c_float))
    if binarize:
        l.u8_binarize_f32(sp, dp, src.size, threshold)
    else:
        l.u8_to_f32(sp, dp, src.size, scale, shift)
    return out


def image_decode_native(path: str) -> Optional[np.ndarray]:
    """Decode PNG/BMP/PPM/PGM natively -> uint8 [H, W, C] in ONE pass.
    Returns None for formats the native tier doesn't cover (JPEG etc., or
    PNG on a zlib-free build) — the caller falls back to PIL. Raises
    ValueError on corrupt files."""
    l = lib()
    w, h, ch = ctypes.c_int(), ctypes.c_int(), ctypes.c_int()
    buf = ctypes.POINTER(ctypes.c_uint8)()
    rc = l.image_decode_alloc(path.encode(), ctypes.byref(buf),
                              ctypes.byref(w), ctypes.byref(h),
                              ctypes.byref(ch))
    if rc == -2:
        return None
    if rc == -1:
        raise FileNotFoundError(path)
    if rc != 0:
        raise ValueError(f"corrupt image file {path!r} (rc={rc})")
    try:
        n = h.value * w.value * ch.value
        out = np.ctypeslib.as_array(buf, shape=(n,)).copy().reshape(
            h.value, w.value, ch.value)
    finally:
        if buf:
            l.image_free(buf)
    return out


class PrefetchRing:
    """Background C++ thread streaming fixed-size records from a binary file
    into a ring of pre-decoded batch buffers (MagicQueue analog). Iterate
    with next_batch() until it returns None (epoch end)."""

    def __init__(self, path: str, record_bytes: int, total_records: int,
                 batch_records: int, header_bytes: int = 0, slots: int = 3):
        self._lib = lib()
        self.record_bytes = int(record_bytes)
        self.batch_records = int(batch_records)
        self._h = self._lib.ring_open(
            path.encode(), header_bytes, record_bytes, total_records,
            batch_records, slots)
        if not self._h:
            raise OSError(f"cannot open {path!r}")
        self._buf = np.empty(self.batch_records * self.record_bytes,
                             np.uint8)

    def next_batch(self) -> Optional[np.ndarray]:
        got = self._lib.ring_next(
            self._h,
            self._buf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)))
        if got == 0:
            return None
        if got < 0:
            raise IOError(f"prefetch ring error {got}")
        n = int(got)
        return (self._buf[:n * self.record_bytes]
                .reshape(n, self.record_bytes).copy())

    def close(self):
        if getattr(self, "_h", None):
            self._lib.ring_close(self._h)
            self._h = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
