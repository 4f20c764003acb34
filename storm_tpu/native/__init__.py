"""Native (C++) acceleration layer, loaded via ctypes with Python fallback.

The reference's hot-path marshalling was Jackson JSON parse + JNI float-array
copies (InferenceBolt.java:76-86). Here the equivalent is a C++ shared library
(``libstormtpu.so``) that parses ``{"instances": ...}`` payloads straight into
a contiguous float32 buffer handed to NumPy zero-copy. The library is built
from the sources beside this file (``make -C storm_tpu/native``; the ``.so``
is not tracked), so a library that loads has every symbol. Where it has not
been built, every entry point takes the pure-Python implementation —
functionality is identical, only slower; ``native_available()`` says which
one runs, and ``chip_smoke.py`` builds before it measures.
"""

from __future__ import annotations

import ctypes
import os
from pathlib import Path
from typing import Optional

import numpy as np

_LIB_PATH = Path(__file__).parent / "libstormtpu.so"
_lib: Optional[ctypes.CDLL] = None
_load_attempted = False

import threading

_tls = threading.local()

_MAX_RANK = 8


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _load_attempted
    if _load_attempted:
        return _lib
    _load_attempted = True
    if os.environ.get("STORM_TPU_NO_NATIVE"):
        return None
    if not _LIB_PATH.exists():
        return None
    try:
        lib = ctypes.CDLL(str(_LIB_PATH))
        lib.stpu_parse_instances.restype = ctypes.c_void_p
        lib.stpu_parse_instances.argtypes = [
            ctypes.c_char_p,
            ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_int64),  # out shape[_MAX_RANK]
            ctypes.POINTER(ctypes.c_int32),  # out rank
            ctypes.POINTER(ctypes.c_char_p),  # out error message
        ]
        lib.stpu_free.restype = None
        lib.stpu_free.argtypes = [ctypes.c_void_p]
        lib.stpu_format_predictions.restype = ctypes.c_void_p
        lib.stpu_format_predictions.argtypes = [
            ctypes.c_void_p,  # float* data
            ctypes.c_int64,  # n
            ctypes.c_int64,  # k
            ctypes.POINTER(ctypes.c_size_t),  # out length
        ]
        lib.stpu_tensor_encode.restype = ctypes.c_void_p
        lib.stpu_tensor_encode.argtypes = [
            ctypes.c_void_p,  # data
            ctypes.c_int,  # dtype code
            ctypes.c_int,  # ndim
            ctypes.POINTER(ctypes.c_int64),  # shape
            ctypes.POINTER(ctypes.c_size_t),  # out length
        ]
        lib.stpu_tensor_decode.restype = ctypes.c_int
        lib.stpu_tensor_decode.argtypes = [
            ctypes.c_void_p,  # buf (address; caller keeps the buffer alive)
            ctypes.c_size_t,  # len
            ctypes.POINTER(ctypes.c_int),  # out dtype
            ctypes.POINTER(ctypes.c_int),  # out ndim
            ctypes.POINTER(ctypes.c_int64),  # out shape[_MAX_RANK]
            ctypes.POINTER(ctypes.c_size_t),  # out body offset
            ctypes.POINTER(ctypes.c_size_t),  # out body length
        ]
        lib.stpu_crc32c.restype = ctypes.c_uint32
        lib.stpu_crc32c.argtypes = [
            ctypes.c_char_p,
            ctypes.c_size_t,
            ctypes.c_uint32,
        ]
        _lib = lib
    except OSError:
        _lib = None
    return _lib


def native_available() -> bool:
    return _load() is not None


def parse_instances_native(payload: str | bytes) -> Optional[np.ndarray]:
    """Parse an ``{"instances": ...}`` JSON payload with the C++ parser.

    Returns ``None`` when the native library is unavailable (caller falls back
    to the Python path). Raises :class:`storm_tpu.api.schema.SchemaError` on a
    malformed payload, same as the Python path.
    """
    lib = _load()
    if lib is None:
        return None
    from storm_tpu.api.schema import SchemaError

    if isinstance(payload, str):
        payload = payload.encode("utf-8")
    # Out-params are reused per thread — allocating fresh ctypes objects per
    # call measurably showed up in the per-message profile.
    tl = _tls
    try:
        shape, rank, rank_ref, err, err_ref = tl.bufs
    except AttributeError:
        shape = (ctypes.c_int64 * _MAX_RANK)()
        rank = ctypes.c_int32(0)
        err = ctypes.c_char_p(None)
        tl.bufs = (shape, rank, ctypes.byref(rank), err, ctypes.byref(err))
        shape, rank, rank_ref, err, err_ref = tl.bufs
    err.value = None
    ptr = lib.stpu_parse_instances(payload, len(payload), shape, rank_ref, err_ref)
    if not ptr:
        msg = err.value.decode("utf-8", "replace") if err.value else "native parse failed"
        raise SchemaError(msg)
    shp = tuple(int(shape[i]) for i in range(rank.value))
    n = 1
    for s in shp:
        n *= s
    # Single memmove out of the C buffer into a NumPy-owned array (the
    # previous as_array+np.array dance cost ~35us/msg in wrapper overhead).
    out = np.empty(n, np.float32)
    ctypes.memmove(out.ctypes.data, ptr, n * 4)
    lib.stpu_free(ptr)
    return out.reshape(shp)


# Dtype codes shared with arrow_tensor.cpp (enum DType).
_DTYPE_TO_CODE = {
    np.dtype(np.float32): 0,
    np.dtype(np.float64): 1,
    np.dtype(np.float16): 2,
    np.dtype(np.uint8): 3,
    np.dtype(np.int8): 4,
    np.dtype(np.uint16): 5,
    np.dtype(np.int16): 6,
    np.dtype(np.uint32): 7,
    np.dtype(np.int32): 8,
    np.dtype(np.uint64): 9,
    np.dtype(np.int64): 10,
}
_CODE_TO_DTYPE = {v: k for k, v in _DTYPE_TO_CODE.items()}


def encode_tensor_native(x: np.ndarray) -> Optional[bytes]:
    """Encode a NumPy array as an Arrow IPC tensor message with the C++
    marshaller (SURVEY.md §2.2: the zero-copy host↔engine boundary). Returns
    ``None`` when the native library is unavailable or the dtype is outside
    Arrow's tensor element types (caller falls back to pyarrow)."""
    lib = _load()
    if lib is None:
        return None
    code = _DTYPE_TO_CODE.get(x.dtype)
    if code is None or x.ndim < 1 or x.ndim > _MAX_RANK:
        return None
    x = np.ascontiguousarray(x)
    shape = (ctypes.c_int64 * _MAX_RANK)(*x.shape, *([0] * (_MAX_RANK - x.ndim)))
    length = ctypes.c_size_t(0)
    ptr = lib.stpu_tensor_encode(
        x.ctypes.data, code, x.ndim, shape, ctypes.byref(length)
    )
    if not ptr:
        return None
    out = ctypes.string_at(ptr, length.value)
    lib.stpu_free(ptr)
    return out


_RC_UNSUPPORTED = 100  # valid Arrow tensor, but a layout we don't view raw


def decode_tensor_native(buf) -> Optional[np.ndarray]:
    """Decode an Arrow IPC tensor message with the C++ parser.

    ``buf`` may be ``bytes``, ``bytearray``, or ``memoryview`` (any buffer
    object). The returned array is a zero-copy view over ``buf``'s body
    bytes. Returns ``None`` when the native library is unavailable OR the
    message is valid but uses a layout the raw-view path doesn't support
    (e.g. Fortran-order strides) — callers fall back to pyarrow. Raises
    ``ValueError`` on genuinely malformed input."""
    lib = _load()
    if lib is None:
        return None
    # frombuffer accepts any buffer object without copying and keeps `buf`
    # alive via the returned array's .base chain.
    raw = np.frombuffer(buf, dtype=np.uint8)
    dtype = ctypes.c_int(0)
    ndim = ctypes.c_int(0)
    shape = (ctypes.c_int64 * _MAX_RANK)()
    body_off = ctypes.c_size_t(0)
    body_len = ctypes.c_size_t(0)
    rc = lib.stpu_tensor_decode(
        raw.ctypes.data,
        raw.size,
        ctypes.byref(dtype),
        ctypes.byref(ndim),
        shape,
        ctypes.byref(body_off),
        ctypes.byref(body_len),
    )
    if rc == _RC_UNSUPPORTED:
        return None
    if rc != 0:
        raise ValueError(f"malformed Arrow tensor message (native rc={rc})")
    dt = _CODE_TO_DTYPE[dtype.value]
    shp = tuple(int(shape[i]) for i in range(ndim.value))
    view = raw[body_off.value : body_off.value + body_len.value]
    return view.view(dt).reshape(shp)


def format_predictions_native(arr: np.ndarray) -> Optional[str]:
    """Serialize an (N, K) float array to ``{"predictions": [[...]]}`` with
    the C++ writer. Returns ``None`` when unavailable (caller falls back to
    the Python path)."""
    lib = _load()
    if lib is None:
        return None
    a = np.ascontiguousarray(arr, dtype=np.float32)
    if a.ndim == 1:
        a = a[None, :]
    if a.ndim != 2:
        return None
    length = ctypes.c_size_t(0)
    ptr = lib.stpu_format_predictions(
        a.ctypes.data, a.shape[0], a.shape[1], ctypes.byref(length)
    )
    if not ptr:
        return None
    s = ctypes.string_at(ptr, length.value).decode("ascii")
    lib.stpu_free(ptr)
    return s


# ---------------------------------------------------------------------------
# CRC32C (Castagnoli) — Kafka record-batch v2 checksum
# ---------------------------------------------------------------------------

_CRC32C_TABLE = None


def _crc32c_py(data: bytes, crc: int = 0) -> int:
    """Pure-Python table fallback (same polynomial as crc32c.cpp)."""
    global _CRC32C_TABLE
    if _CRC32C_TABLE is None:
        table = []
        for i in range(256):
            c = i
            for _ in range(8):
                c = (c >> 1) ^ 0x82F63B78 if c & 1 else c >> 1
            table.append(c)
        _CRC32C_TABLE = table
    crc ^= 0xFFFFFFFF
    for b in data:
        crc = (crc >> 8) ^ _CRC32C_TABLE[(crc ^ b) & 0xFF]
    return crc ^ 0xFFFFFFFF


def crc32c(data: bytes, crc: int = 0) -> int:
    """CRC32C over ``data`` (incremental: pass a previous result as crc)."""
    lib = _load()
    if lib is not None:
        return lib.stpu_crc32c(data, len(data), crc)
    return _crc32c_py(data, crc)
