"""ctypes loader for the native runtime library (native/libsrtpu.so).

Reference analog: the JNI boundary to the cudf/nvcomp native code
(§2.12) — kept out of the compute path (that's XLA's) and limited to the
host runtime pieces the reference also kept native: currently the LZ4
shuffle codec. Builds on demand with g++ and degrades to None when the
toolchain or library is unavailable, so pure-python deployments still work.
"""
from __future__ import annotations

import ctypes
import os
import threading
from typing import Optional

_LOCK = threading.Lock()
_LIB: Optional[ctypes.CDLL] = None
_TRIED = False
#: why the library is unavailable (compiler stderr / loader error) — kept
#: so callers can REPORT a missing host decoder instead of guessing
_LOAD_ERROR: Optional[str] = None


def _load() -> Optional[ctypes.CDLL]:
    global _LIB, _TRIED, _LOAD_ERROR
    with _LOCK:
        if _TRIED:
            return _LIB
        _TRIED = True
        try:
            root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
            import sys

            sys.path.insert(0, os.path.join(root, "native"))
            try:
                from build import build  # type: ignore[import-not-found]
            finally:
                sys.path.pop(0)
            path = build()
            lib = ctypes.CDLL(path)
            lib.srtpu_lz4_bound.restype = ctypes.c_int
            lib.srtpu_lz4_bound.argtypes = [ctypes.c_int]
            lib.srtpu_lz4_compress.restype = ctypes.c_int
            lib.srtpu_lz4_compress.argtypes = [
                ctypes.c_char_p, ctypes.c_int,
                ctypes.POINTER(ctypes.c_char), ctypes.c_int]
            lib.srtpu_lz4_decompress.restype = ctypes.c_int
            lib.srtpu_lz4_decompress.argtypes = [
                ctypes.c_char_p, ctypes.c_int,
                ctypes.POINTER(ctypes.c_char), ctypes.c_int]
            lib.srtpu_pq_hybrid_decode.restype = ctypes.c_int64
            lib.srtpu_pq_hybrid_decode.argtypes = [
                ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
                ctypes.c_int32, ctypes.c_int64, ctypes.c_int32,
                ctypes.c_void_p]
            lib.srtpu_pq_binary_dict.restype = ctypes.c_int64
            lib.srtpu_pq_binary_dict.argtypes = [
                ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64]
            lib.srtpu_pq_plan_chunk.restype = ctypes.c_void_p
            lib.srtpu_pq_plan_chunk.argtypes = [
                ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
                ctypes.c_int64, ctypes.c_int32, ctypes.c_int32,
                ctypes.c_int64, ctypes.c_int32, ctypes.c_int32,
                ctypes.c_void_p]
            lib.srtpu_pq_plan_take.restype = None
            lib.srtpu_pq_plan_take.argtypes = [ctypes.c_void_p] * 7
            _LIB = lib
        except Exception as e:
            _LIB = None
            stderr = getattr(e, "stderr", None)
            _LOAD_ERROR = (stderr.decode(errors="replace").strip()
                           if stderr else f"{type(e).__name__}: {e}")
        return _LIB


def available() -> bool:
    return _load() is not None


def load_error() -> Optional[str]:
    """The build/load failure that made the library unavailable (None
    when it loaded, or before the first load attempt)."""
    return _LOAD_ERROR


def lz4_compress(data: bytes) -> bytes:
    lib = _load()
    if lib is None:
        raise RuntimeError("native library unavailable (g++ build failed?)")
    if not data:
        return b""
    cap = lib.srtpu_lz4_bound(len(data))
    buf = ctypes.create_string_buffer(cap)
    n = lib.srtpu_lz4_compress(data, len(data), buf, cap)
    if n <= 0:
        raise RuntimeError("lz4 compression failed")
    return buf.raw[:n]


def pq_hybrid_decode(data, pos: int, end: int, bw: int, n: int, out):
    """Expand one parquet RLE/bit-packed hybrid stream into ``out`` (a
    contiguous numpy array of u8/u16/i32, len >= n). Returns the byte
    position after the stream or None when the native library is
    unavailable; raises ValueError on malformed input. Releases the GIL
    for the duration of the decode."""
    lib = _load()
    if lib is None:
        return None
    import numpy as np

    src = np.frombuffer(data, np.uint8)  # zero-copy view (bytes or mmap)
    rc = lib.srtpu_pq_hybrid_decode(
        src.ctypes.data, pos, min(end, src.shape[0]), bw, n,
        out.dtype.itemsize, out.ctypes.data)
    if rc < 0:
        raise ValueError(f"malformed hybrid stream (bw={bw}, n={n})")
    return int(rc)


def pq_binary_dict(raw: bytes, count: int, offsets, chars) -> Optional[int]:
    """Parse a BYTE_ARRAY PLAIN dictionary page into offsets/chars numpy
    arrays. Returns total char bytes, None when the library is
    unavailable; raises ValueError on malformed input."""
    lib = _load()
    if lib is None:
        return None
    import numpy as np

    src = np.frombuffer(raw, np.uint8)
    rc = lib.srtpu_pq_binary_dict(
        src.ctypes.data, src.shape[0], count,
        offsets.ctypes.data, chars.ctypes.data, chars.shape[0])
    if rc < 0:
        raise ValueError("malformed binary dictionary page")
    return int(rc)


#: the codecs and physical types srtpu_pq_plan_chunk takes, by its numbers
PQ_PLAN_CODECS = {"UNCOMPRESSED": 0, "SNAPPY": 1}
_PQ_PHYS = {"INT32": 0, "INT64": 1, "FLOAT": 2, "DOUBLE": 3, "BOOLEAN": 4,
            "BYTE_ARRAY": 5}
#: the words of srtpu_pq_plan_chunk's info[] (its enum Info)
_PLAN_INFO = ("reason", "detail", "pages", "validity", "kind", "count",
              "width", "dict", "dict_chars", "string_bytes")
_CODE_DTYPES = {1: "uint8", 2: "uint16", 4: "int32"}


def pq_plan_chunk(data, off: int, end: int, codec: str, phys: str,
                  value_dtype, num_values: int, max_def: int,
                  has_nulls: bool) -> Optional[dict]:
    """Plan one parquet column chunk, the pages in ``data[off:end)``, in
    one native call that holds no GIL (``data``: the file's bytes or its
    mmap, read in place). Returns None when the library is unavailable,
    else a dict: ``reason`` (0, or why the chunk was refused, with
    ``detail``), ``pages`` walked and, where planned, ``validity`` (bool a
    row, or None), ``codes`` (the narrowest dtype that holds them),
    ``plain`` (the PLAIN pages' bytes), ``dict_values`` (of
    ``value_dtype``), ``dict_offsets`` and ``dict_chars`` (a BYTE_ARRAY
    dictionary), ``string_bytes`` (sum of its lengths over the codes),
    each None where the chunk has none."""
    lib = _load()
    if lib is None:
        return None
    import numpy as np

    src = np.frombuffer(data, np.uint8)  # zero-copy view (bytes or mmap)
    info = np.zeros(len(_PLAN_INFO), np.int64)
    handle = lib.srtpu_pq_plan_chunk(
        src.ctypes.data, src.shape[0], off, end, PQ_PLAN_CODECS[codec],
        _PQ_PHYS[phys], num_values, max_def, int(has_nulls),
        info.ctypes.data)
    got = dict(zip(_PLAN_INFO, info.tolist()))
    if not handle:
        return got
    out = dict.fromkeys(("validity", "codes", "plain", "dict_values",
                         "dict_offsets", "dict_chars"))
    try:
        if got["validity"] >= 0:
            out["validity"] = np.empty(got["validity"], np.bool_)
        if got["kind"] == 2:
            out["plain"] = np.empty(got["count"], np.uint8)
        else:
            out["codes"] = np.empty(got["count"] if got["kind"] else 0,
                                    _CODE_DTYPES[got["width"]])
        if got["dict"] >= 0:
            if phys == "BYTE_ARRAY":
                out["dict_offsets"] = np.empty(got["dict"] + 1, np.int64)
                out["dict_chars"] = np.zeros(max(1, got["dict_chars"]),
                                             np.uint8)
            else:
                out["dict_values"] = np.empty(got["dict"], value_dtype)
    finally:
        lib.srtpu_pq_plan_take(handle, *(
            None if a is None else a.ctypes.data for a in out.values()))
    if out["plain"] is not None:
        out["plain"] = out["plain"].tobytes()
    got.update(out)
    if got["string_bytes"] < 0:
        got["string_bytes"] = None
    return got


def lz4_decompress(data: bytes, out_size: int) -> bytes:
    lib = _load()
    if lib is None:
        raise RuntimeError("native library unavailable (g++ build failed?)")
    if out_size == 0:
        return b""
    buf = ctypes.create_string_buffer(out_size)
    n = lib.srtpu_lz4_decompress(data, len(data), buf, out_size)
    if n != out_size:
        raise ValueError(f"lz4 payload corrupt ({n} != {out_size})")
    return buf.raw
