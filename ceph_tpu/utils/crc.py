"""CRC32C (Castagnoli) with a native kernel + pure-Python fallback.

Python-native equivalent of the reference's crc32c facade (reference
src/common/crc32c.h choosing intel-fast / aarch64 / sctp at runtime):
``crc32c(data, crc=0)`` dispatches to native/crc32c.cc (built on
demand for this host, utils/nativebuild.py, like the GF kernels) and
falls back to a table-driven Python implementation when no compiler
is present.
"""
from __future__ import annotations

import ctypes
import threading
from typing import List, Optional

from . import nativebuild

_lib: Optional[ctypes.CDLL] = None
_tried = False
_lock = threading.Lock()


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        lib = nativebuild.load("crc32c.cc", "libceph_tpu_crc32c")
        if lib is None:
            return None
        lib.crc32c_init()
        lib.crc32c.restype = ctypes.c_uint32
        lib.crc32c.argtypes = [ctypes.c_uint32,
                               ctypes.POINTER(ctypes.c_uint8),
                               ctypes.c_size_t]
        lib.crc32c_blocks.restype = None
        lib.crc32c_blocks.argtypes = [ctypes.POINTER(ctypes.c_uint8),
                                      ctypes.c_size_t, ctypes.c_size_t,
                                      ctypes.POINTER(ctypes.c_uint32)]
        _lib = lib
        return _lib


# -- pure-python fallback (table-driven, reference crc32c_sctp) --------
_PY_TABLE: Optional[list] = None


def _py_table() -> list:
    global _PY_TABLE
    if _PY_TABLE is None:
        poly = 0x82F63B78
        tbl = []
        for i in range(256):
            c = i
            for _ in range(8):
                c = (poly ^ (c >> 1)) if (c & 1) else (c >> 1)
            tbl.append(c)
        _PY_TABLE = tbl
    return _PY_TABLE


def _py_crc32c(data, crc: int) -> int:
    tbl = _py_table()
    c = crc ^ 0xFFFFFFFF
    for b in data:
        c = tbl[(c ^ b) & 0xFF] ^ (c >> 8)
    return c ^ 0xFFFFFFFF


def available_native() -> bool:
    return _load() is not None


def crc32c(data, crc: int = 0) -> int:
    """Running CRC32C over any bytes-like; chain by passing the
    previous value.  Writable buffers (bytearray, memoryview, uint8
    ndarray) are checksummed in place; immutable bytes need the ctypes
    copy (from_buffer rejects them)."""
    lib = _load()
    if lib is None:
        if not isinstance(data, (bytes, bytearray)):
            data = bytes(data)
        return _py_crc32c(data, crc)
    n = len(data)
    try:
        buf = (ctypes.c_uint8 * n).from_buffer(data)
    except (TypeError, ValueError, BufferError):
        buf = (ctypes.c_uint8 * n).from_buffer_copy(data)
    return lib.crc32c(crc, buf, n)


def crc32c_blocks(buf, block_len: int) -> List[int]:
    """The CRC32C of every ``block_len`` bytes of ``buf``, whose
    length is a multiple of it, in ONE native call: the caller gives
    up the interpreter lock once however many blocks there are.  A
    writable ``buf`` is read in place, as by crc32c."""
    mv = memoryview(buf).cast("B")
    n, ragged = divmod(len(mv), block_len)
    if ragged:
        raise ValueError(f"{len(mv)} bytes are not whole blocks of "
                         f"{block_len}")
    if not n:
        return []
    # the loader's lock only until the library is there
    lib = _lib if _lib is not None else _load()
    if lib is None:
        return [_py_crc32c(mv[i * block_len:(i + 1) * block_len], 0)
                for i in range(n)]
    try:
        data = (ctypes.c_uint8 * len(mv)).from_buffer(mv)
    except (TypeError, ValueError, BufferError):
        data = (ctypes.c_uint8 * len(mv)).from_buffer_copy(mv)
    out = (ctypes.c_uint32 * n)()
    lib.crc32c_blocks(data, block_len, n, out)
    return out[:]
