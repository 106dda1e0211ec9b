"""Native (C++) data-path acceleration, loaded via ctypes.

The reference's data layer is all C++ (src/utils/shard.cc, the protobuf
Record codec, tools/data_loader/); this package is its native counterpart
here: `shardcodec.cc` scans shard files, decodes/encodes proto2 Records,
and materializes whole datasets without Python in the per-record loop.

The library builds on demand with g++ (one small TU, ~1s) into this
directory; every entry point degrades to the pure-Python codec in
singa_tpu.data when the toolchain or platform is unavailable (a failed
build says so once, as a warning), so the framework stays importable
everywhere. `singa_tpu.data.pipeline` routes through `load_dataset`
automatically.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import warnings

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "shardcodec.cc")
_LIB = os.path.join(_DIR, "libshardcodec.so")
_LMDB_SRC = os.path.join(_DIR, "lmdbcodec.cc")
_LMDB_LIB = os.path.join(_DIR, "liblmdbcodec.so")

_lib: ctypes.CDLL | None = None
_tried = False
_lmdb_lib: ctypes.CDLL | None = None
_lmdb_tried = False


def _build(src: str, lib: str) -> bool:
    try:
        subprocess.run(
            ["g++", "-O2", "-shared", "-fPIC", "-o", lib, src],
            check=True,
            capture_output=True,
            timeout=120,
        )
        return True
    except (OSError, subprocess.SubprocessError) as e:
        stderr = getattr(e, "stderr", None) or b""
        warnings.warn(
            f"native codec build of {os.path.basename(src)} failed "
            f"({e}) {stderr.decode(errors='replace')[-300:]}— the "
            "pure-Python codec serves instead",
            RuntimeWarning,
            stacklevel=2,
        )
        return False


def _load(src: str, lib_path: str) -> ctypes.CDLL | None:
    """Build (if stale) + dlopen one codec library; None if unavailable."""
    if not os.path.exists(lib_path) or os.path.getmtime(
        lib_path
    ) < os.path.getmtime(src):
        if not _build(src, lib_path):
            return None
    try:
        return ctypes.CDLL(lib_path)
    except OSError:
        return None


def get_lib() -> ctypes.CDLL | None:
    """Load (building if needed) the shard codec; None if unavailable."""
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    _tried = True
    lib = _load(_SRC, _LIB)
    if lib is None:
        return None
    lib.sc_scan.restype = ctypes.c_int64
    lib.sc_scan.argtypes = [ctypes.c_char_p, ctypes.POINTER(ctypes.c_uint64)]
    lib.sc_load_dataset_alloc.restype = ctypes.c_int64
    lib.sc_load_dataset_alloc.argtypes = [
        ctypes.c_char_p,
        ctypes.POINTER(ctypes.POINTER(ctypes.c_float)),
        ctypes.POINTER(ctypes.POINTER(ctypes.c_int32)),
        ctypes.POINTER(ctypes.c_int32),
        ctypes.c_int32,
        ctypes.POINTER(ctypes.c_int32),
    ]
    lib.sc_free.restype = None
    lib.sc_free.argtypes = [ctypes.c_void_p]
    lib.sc_write_records.restype = ctypes.c_int64
    lib.sc_write_records.argtypes = [
        ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_uint8),
        ctypes.POINTER(ctypes.c_int32),
        ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int32),
        ctypes.c_int32,
        ctypes.c_int64,
        ctypes.c_int32,
    ]
    _lib = lib
    return _lib


def get_lmdb_lib() -> ctypes.CDLL | None:
    """Load (building if needed) the LMDB codec; None if unavailable."""
    global _lmdb_lib, _lmdb_tried
    if _lmdb_lib is not None or _lmdb_tried:
        return _lmdb_lib
    _lmdb_tried = True
    lib = _load(_LMDB_SRC, _LMDB_LIB)
    if lib is None:
        return None
    lib.lc_load_dataset.restype = ctypes.c_int64
    lib.lc_load_dataset.argtypes = [
        ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_void_p),
        ctypes.POINTER(ctypes.POINTER(ctypes.c_float)),
        ctypes.POINTER(ctypes.POINTER(ctypes.c_int32)),
        ctypes.POINTER(ctypes.c_int32),
    ]
    lib.lc_free_result.restype = None
    lib.lc_free_result.argtypes = [ctypes.c_void_p]
    _lmdb_lib = lib
    return _lmdb_lib


def load_lmdb_dataset(path: str) -> tuple[np.ndarray, np.ndarray] | None:
    """Walk + decode a whole Caffe LMDB natively (the reference's
    liblmdb/libprotobuf path, layer.cc:237-328). -> (images float32
    (N, C, H, W), labels int32 (N,)), or None when the native path can't
    serve it — the caller falls back to singa_tpu.data.lmdbio, which
    either decodes (dupsort-free DBs, no toolchain needed) or raises the
    descriptive error (mixed per-record geometry)."""
    lib = get_lmdb_lib()
    if lib is None:
        return None
    handle = ctypes.c_void_p()
    pixels_p = ctypes.POINTER(ctypes.c_float)()
    labels_p = ctypes.POINTER(ctypes.c_int32)()
    shape_buf = (ctypes.c_int32 * 3)()
    count = lib.lc_load_dataset(
        path.encode(), ctypes.byref(handle), ctypes.byref(pixels_p),
        ctypes.byref(labels_p), shape_buf,
    )
    if count <= 0:
        return None
    try:
        shape = tuple(shape_buf[i] for i in range(3))
        sample = int(np.prod(shape))
        images = np.ctypeslib.as_array(pixels_p, (int(count), sample)).copy()
        labels = np.ctypeslib.as_array(labels_p, (int(count),)).copy()
    finally:
        lib.lc_free_result(handle)
    return images.reshape((int(count), *shape)), labels


def available() -> bool:
    return get_lib() is not None


def rebuild() -> bool:
    """Drop any prebuilt library and build both codecs from the tracked
    ``.cc`` sources; -> whether the shard codec loaded. For callers
    that must not trust a ``.so`` that came with the tree (git ignores
    them; a copy of the tree as it stands on disk does not)."""
    global _lib, _tried, _lmdb_lib, _lmdb_tried
    for lib in (_LIB, _LMDB_LIB):
        if os.path.exists(lib):
            os.remove(lib)
    _lib = _lmdb_lib = None
    _tried = _lmdb_tried = False
    get_lmdb_lib()
    return available()


def scan(path: str) -> tuple[int, int] | None:
    """(complete_tuple_count, valid_end_offset), or None if unavailable."""
    lib = get_lib()
    if lib is None:
        return None
    end = ctypes.c_uint64(0)
    n = lib.sc_scan(path.encode(), ctypes.byref(end))
    if n < 0:
        return None
    return int(n), int(end.value)


def load_dataset(path: str) -> tuple[np.ndarray, np.ndarray] | None:
    """Decode all records of a uniform-shape shard in native code.

    One file read end-to-end: the library scans, decodes, and returns
    malloc'd dense arrays which are copied into numpy and freed.
    -> (images float32 (N, *shape), labels int32 (N,)), or None when the
    native path can't serve this shard (falls back to Python — e.g. mixed
    per-record shapes).
    """
    lib = get_lib()
    if lib is None:
        return None
    pixels_p = ctypes.POINTER(ctypes.c_float)()
    labels_p = ctypes.POINTER(ctypes.c_int32)()
    shape_buf = (ctypes.c_int32 * 8)()
    ndim = ctypes.c_int32(0)
    count = lib.sc_load_dataset_alloc(
        path.encode(),
        ctypes.byref(pixels_p),
        ctypes.byref(labels_p),
        shape_buf,
        8,
        ctypes.byref(ndim),
    )
    if count <= 0:
        return None  # absent/empty/non-uniform: Python path handles it
    try:
        shape = tuple(shape_buf[i] for i in range(ndim.value))
        sample = int(np.prod(shape))
        images = np.ctypeslib.as_array(pixels_p, (int(count), sample)).copy()
        labels = np.ctypeslib.as_array(labels_p, (int(count),)).copy()
    finally:
        lib.sc_free(pixels_p)
        lib.sc_free(labels_p)
    return images.reshape((int(count), *shape)), labels


def write_records(
    path: str,
    images: np.ndarray,
    labels: np.ndarray,
    start_index: int = 0,
    append: bool = False,
) -> int | None:
    """Encode + write uint8 image records natively; None if unavailable."""
    lib = get_lib()
    if lib is None:
        return None
    images = np.ascontiguousarray(images, dtype=np.uint8)
    labels = np.ascontiguousarray(labels, dtype=np.int32)
    shape = (ctypes.c_int32 * (images.ndim - 1))(*images.shape[1:])
    n = lib.sc_write_records(
        path.encode(),
        images.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        labels.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        len(images),
        shape,
        images.ndim - 1,
        start_index,
        1 if append else 0,
    )
    return int(n) if n >= 0 else None
