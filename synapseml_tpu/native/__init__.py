"""Native runtime bindings.

The reference ships native engines inside jars and extracts them at
runtime (reference: core/env/NativeLoader.java:28-90 — jar → tmpdir →
``System.load``).  The analogue here: the C++ loader compiles ON FIRST
USE with the toolchain baked into the image (``g++ -O3 -shared``) into a
per-user cache directory keyed by source hash, then binds over ctypes —
no wheel step, no pybind11.  Every entry point has a numpy fallback so
the framework degrades gracefully where a toolchain is absent.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import tempfile
import threading
from typing import Optional, Tuple

import numpy as np

_SRC = os.path.join(os.path.dirname(__file__), "loader.cpp")
_TEXT_SRC = os.path.join(os.path.dirname(__file__), "textproc.cpp")
_LOCK = threading.Lock()
_LIB: Optional[ctypes.CDLL] = None
_LIB_FAILED = False
_TEXTLIB: Optional[ctypes.CDLL] = None
_TEXTLIB_FAILED = False


def _cache_dir() -> str:
    root = os.environ.get("SYNAPSEML_TPU_NATIVE_CACHE") or os.path.join(
        os.path.expanduser("~"), ".cache", "synapseml_tpu", "native")
    os.makedirs(root, exist_ok=True)
    return root


def _compile_source(src_path: str, stem: str) -> Optional[str]:
    with open(src_path, "rb") as f:
        src = f.read()
    tag = hashlib.sha256(src).hexdigest()[:16]
    out = os.path.join(_cache_dir(), f"lib{stem}_{tag}.so")
    if os.path.exists(out):
        return out
    tmp = out + f".tmp{os.getpid()}"
    cmd = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread",
           src_path, "-o", tmp]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=180)
    except (OSError, subprocess.SubprocessError):
        return None
    os.replace(tmp, out)
    return out


def _get_lib() -> Optional[ctypes.CDLL]:
    global _LIB, _LIB_FAILED
    if _LIB is not None or _LIB_FAILED:
        return _LIB
    with _LOCK:
        if _LIB is not None or _LIB_FAILED:
            return _LIB
        path = _compile_source(_SRC, "smlloader")
        if path is None:
            _LIB_FAILED = True
            return None
        lib = ctypes.CDLL(path)
        i64p = ctypes.POINTER(ctypes.c_int64)
        f32p = ctypes.POINTER(ctypes.c_float)
        lib.sml_csv_dims.argtypes = [ctypes.c_char_p, ctypes.c_int,
                                     ctypes.c_char, i64p, i64p]
        lib.sml_csv_dims.restype = ctypes.c_int
        lib.sml_csv_read_f32.argtypes = [ctypes.c_char_p, ctypes.c_int,
                                         ctypes.c_char, ctypes.c_int64,
                                         ctypes.c_int64, f32p, ctypes.c_int]
        lib.sml_csv_read_f32.restype = ctypes.c_int
        lib.sml_colstore_write.argtypes = [ctypes.c_char_p, f32p,
                                           ctypes.c_int64, ctypes.c_int64]
        lib.sml_colstore_write.restype = ctypes.c_int
        lib.sml_colstore_dims.argtypes = [ctypes.c_char_p, i64p, i64p]
        lib.sml_colstore_dims.restype = ctypes.c_int
        lib.sml_colstore_read.argtypes = [ctypes.c_char_p, f32p,
                                          ctypes.c_int64, ctypes.c_int64]
        lib.sml_colstore_read.restype = ctypes.c_int
        u8p = ctypes.POINTER(ctypes.c_uint8)
        lib.sml_bin_u8.argtypes = [f32p, ctypes.c_int64, ctypes.c_int64,
                                   f32p, ctypes.c_int64, u8p, ctypes.c_int]
        lib.sml_bin_u8.restype = ctypes.c_int
        _LIB = lib
        return _LIB


def native_available() -> bool:
    return _get_lib() is not None


def _read_header(path: str, delim: str) -> Tuple[bool, list]:
    with open(path, "r", errors="replace") as f:
        first = f.readline().rstrip("\r\n")
    fields = first.split(delim)

    def numeric(s: str) -> bool:
        try:
            float(s)
            return True
        except ValueError:
            return s.strip() == ""

    has_header = not all(numeric(x) for x in fields)
    names = (fields if has_header
             else [f"f{i}" for i in range(len(fields))])
    return has_header, names


def read_csv_matrix(path: str, delim: str = ",",
                    n_threads: int = 0) -> Tuple[np.ndarray, list]:
    """(rows, cols) float32 matrix + column names.  Native path: mmap +
    multithreaded parse; fallback: numpy.genfromtxt."""
    has_header, names = _read_header(path, delim)
    lib = _get_lib()
    if lib is not None:
        rows = ctypes.c_int64()
        cols = ctypes.c_int64()
        rc = lib.sml_csv_dims(path.encode(), int(has_header),
                              delim.encode(), ctypes.byref(rows),
                              ctypes.byref(cols))
        if rc == 0:
            r, c = rows.value, cols.value
            out = np.empty((c, r), np.float32)  # column-major blocks
            rc = lib.sml_csv_read_f32(
                path.encode(), int(has_header), delim.encode(), r, c,
                out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
                int(n_threads))
            if rc >= 0:
                return out.T, names[:c]
    mat = np.genfromtxt(path, delimiter=delim,
                        skip_header=1 if has_header else 0,
                        dtype=np.float32, ndmin=2)
    return mat, names[:mat.shape[1]]


def bin_columns_u8(features: np.ndarray, upper_bounds: np.ndarray,
                   max_bin: int, n_threads: int = 0) -> np.ndarray:
    """Quantile-bin raw (n, F) float32 features → (n, F) uint8 bins
    (NaN → 0, content bins 1..max_bin).  Native path: row-blocked
    multithreaded binary search; fallback: threaded numpy searchsorted.
    The uint8 result is the array shipped to the device — 4× less
    host→device traffic than raw floats."""
    if not 1 <= max_bin <= 255:
        raise ValueError(
            f"bin_columns_u8 requires max_bin in [1, 255], got {max_bin}; "
            "use BinMapper.transform (int32) for wider bin ranges")
    features = np.ascontiguousarray(features, np.float32)
    upper_bounds = np.ascontiguousarray(upper_bounds, np.float32)
    n, f = features.shape
    out = np.empty((n, f), np.uint8)
    lib = _get_lib()
    if lib is not None:
        rc = lib.sml_bin_u8(
            features.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), n, f,
            upper_bounds.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            max_bin, out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            int(n_threads))
        if rc == 0:
            return out

    def one(j):
        col = features[:, j]
        idx = np.searchsorted(upper_bounds[j, :max_bin], col, side="left")
        b = np.minimum(idx, max_bin - 1).astype(np.uint8) + 1
        b[np.isnan(col)] = 0
        out[:, j] = b

    from concurrent.futures import ThreadPoolExecutor
    if n * f > 1 << 20:
        with ThreadPoolExecutor() as pool:
            list(pool.map(one, range(f)))
    else:
        for j in range(f):
            one(j)
    return out


def write_colstore(path: str, matrix: np.ndarray) -> None:
    m = np.ascontiguousarray(np.asarray(matrix, np.float32).T)  # col blocks
    lib = _get_lib()
    if lib is not None:
        rc = lib.sml_colstore_write(
            path.encode(), m.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            matrix.shape[0], matrix.shape[1])
        if rc == 0:
            return
    with open(path, "wb") as f:
        f.write(b"SMLC")
        f.write(np.uint32(1).tobytes())
        f.write(np.int64(matrix.shape[0]).tobytes())
        f.write(np.int64(matrix.shape[1]).tobytes())
        f.write(m.tobytes())


def read_colstore(path: str) -> np.ndarray:
    lib = _get_lib()
    if lib is not None:
        rows = ctypes.c_int64()
        cols = ctypes.c_int64()
        if lib.sml_colstore_dims(path.encode(), ctypes.byref(rows),
                                 ctypes.byref(cols)) == 0:
            out = np.empty((cols.value, rows.value), np.float32)
            if lib.sml_colstore_read(
                    path.encode(),
                    out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
                    rows.value, cols.value) == 0:
                return out.T
    with open(path, "rb") as f:
        magic = f.read(4)
        if magic != b"SMLC":
            raise IOError(f"{path}: not an SMLC column store")
        version = int(np.frombuffer(f.read(4), np.uint32)[0])
        rows = int(np.frombuffer(f.read(8), np.int64)[0])
        cols = int(np.frombuffer(f.read(8), np.int64)[0])
        if version == 1:
            data = np.frombuffer(f.read(rows * cols * 4), np.float32)
        elif version == 2:
            # v2 stores bf16 bit patterns (io.colstore.write_matrix
            # dtype="bf16"); upcast exactly like ChunkedColumnSource
            bits = np.frombuffer(f.read(rows * cols * 2), np.uint16)
            data = (bits.astype(np.uint32) << 16).view(np.float32)
        else:
            raise IOError(f"{path}: unknown SMLC version {version}")
    return data.reshape(cols, rows).T


def _get_textlib() -> Optional[ctypes.CDLL]:
    global _TEXTLIB, _TEXTLIB_FAILED
    if _TEXTLIB is not None or _TEXTLIB_FAILED:
        return _TEXTLIB
    with _LOCK:
        if _TEXTLIB is not None or _TEXTLIB_FAILED:
            return _TEXTLIB
        path = _compile_source(_TEXT_SRC, "smltextproc")
        if path is None:
            _TEXTLIB_FAILED = True
            return None
        lib = ctypes.CDLL(path)
        i64p = ctypes.POINTER(ctypes.c_int64)
        i32p = ctypes.POINTER(ctypes.c_int32)
        f32p = ctypes.POINTER(ctypes.c_float)
        u32p = ctypes.POINTER(ctypes.c_uint32)
        u8p = ctypes.POINTER(ctypes.c_uint8)
        lib.sml_murmur3_batch.argtypes = [ctypes.c_char_p, i64p,
                                          ctypes.c_int64, ctypes.c_uint32,
                                          u32p, ctypes.c_int]
        lib.sml_murmur3_batch.restype = None
        lib.sml_vw_count.argtypes = [ctypes.c_char_p, i64p, ctypes.c_int64,
                                     ctypes.c_uint32, i64p, ctypes.c_int]
        lib.sml_vw_count.restype = None
        lib.sml_vw_parse.argtypes = [ctypes.c_char_p, i64p, ctypes.c_int64,
                                     ctypes.c_uint32, ctypes.c_int, i64p,
                                     i32p, i32p, f32p, f32p, f32p, u8p,
                                     ctypes.c_int]
        lib.sml_vw_parse.restype = None
        lib.sml_coo_densify.argtypes = [i32p, i32p, f32p, ctypes.c_int64,
                                        f32p, ctypes.c_int64, ctypes.c_int]
        lib.sml_coo_densify.restype = None
        _TEXTLIB = lib
        return _TEXTLIB


def _concat_utf8(strings) -> Tuple[bytes, np.ndarray]:
    enc = [s.encode("utf-8") if isinstance(s, str) else bytes(s)
           for s in strings]
    offsets = np.zeros(len(enc) + 1, np.int64)
    if enc:
        np.cumsum([len(b) for b in enc], out=offsets[1:])
    return b"".join(enc), offsets


def _p(arr, ctype):
    return arr.ctypes.data_as(ctypes.POINTER(ctype))


def murmur3_batch(strings, seed: int = 0,
                  n_threads: int = 0) -> Optional[np.ndarray]:
    """Hash a batch of strings natively -> uint32 array; None if the
    toolchain is unavailable (callers fall back to the Python hasher)."""
    lib = _get_textlib()
    if lib is None:
        return None
    buf, offsets = _concat_utf8(strings)
    n = len(offsets) - 1
    out = np.empty(n, np.uint32)
    lib.sml_murmur3_batch(buf, _p(offsets, ctypes.c_int64), n,
                          ctypes.c_uint32(seed & 0xFFFFFFFF),
                          _p(out, ctypes.c_uint32), n_threads)
    return out


def vw_parse_batch(lines, num_bits: int, seed: int = 0, n_threads: int = 0):
    """Parse VW-format lines natively.  Returns (rows, idxs, vals, labels,
    weights, has_label) COO arrays, or None without a toolchain."""
    lib = _get_textlib()
    if lib is None:
        return None
    buf, offsets = _concat_utf8(str(l) for l in lines)
    n = len(offsets) - 1
    counts = np.zeros(n, np.int64)
    seed32 = ctypes.c_uint32(seed & 0xFFFFFFFF)
    lib.sml_vw_count(buf, _p(offsets, ctypes.c_int64), n, seed32,
                     _p(counts, ctypes.c_int64), n_threads)
    starts = np.zeros(n + 1, np.int64)
    np.cumsum(counts, out=starts[1:])
    total = int(starts[-1])
    rows = np.empty(total, np.int32)
    idxs = np.empty(total, np.int32)
    vals = np.empty(total, np.float32)
    labels = np.empty(n, np.float32)
    weights = np.empty(n, np.float32)
    has = np.empty(n, np.uint8)
    lib.sml_vw_parse(buf, _p(offsets, ctypes.c_int64), n, seed32,
                     int(num_bits), _p(starts, ctypes.c_int64),
                     _p(rows, ctypes.c_int32), _p(idxs, ctypes.c_int32),
                     _p(vals, ctypes.c_float), _p(labels, ctypes.c_float),
                     _p(weights, ctypes.c_float), _p(has, ctypes.c_uint8),
                     n_threads)
    return rows, idxs, vals, labels, weights, has


def coo_densify(rows: np.ndarray, idxs: np.ndarray, vals: np.ndarray,
                out: np.ndarray) -> bool:
    """out[row, idx] += val natively (rows must be sorted, as the VW
    parser emits them).  Returns False without a toolchain."""
    lib = _get_textlib()
    if lib is None:
        return False
    assert out.dtype == np.float32 and out.flags.c_contiguous
    lib.sml_coo_densify(_p(rows, ctypes.c_int32), _p(idxs, ctypes.c_int32),
                        _p(vals, ctypes.c_float), len(rows),
                        _p(out, ctypes.c_float), out.shape[1], 0)
    return True


__all__ = ["coo_densify", "murmur3_batch", "native_available",
           "read_csv_matrix", "read_colstore", "vw_parse_batch",
           "write_colstore"]
