"""ctypes bindings for the native columnar runtime.

Auto-builds libcitus_tpu_native.so with make on first use (a few
seconds, cached); every caller must tolerate ``LIB is None`` and fall
back to the pure-Python path, so the framework works even without a
toolchain.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading

_HERE = os.path.dirname(os.path.abspath(__file__))
_SO = os.path.join(_HERE, "libcitus_tpu_native.so")
_lock = threading.Lock()
_attempted = False

LIB = None

CODEC_IDS = {"none": 0, "zstd": 1, "lz4": 2, "zlib": 3}

#: what ``ct_decode_batch`` reports about its pool through its nullable
#: ``stats`` out-array, in the array's order (columnar_native.cpp)
DECODE_STATS = ("read_ms", "decompress_ms", "busy_max_ms", "threads")


def _try_build() -> bool:
    src = os.path.join(_HERE, "columnar_native.cpp")
    if os.path.exists(_SO) and os.path.getmtime(_SO) >= os.path.getmtime(src):
        return True
    try:
        subprocess.run(["make", "-C", _HERE], capture_output=True, timeout=120,
                       check=True)
        return os.path.exists(_SO)
    except Exception:
        return False


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    i64, i32 = ctypes.c_int64, ctypes.c_int32
    u8p = ctypes.POINTER(ctypes.c_uint8)
    i64p = ctypes.POINTER(ctypes.c_int64)
    lib.ct_decompress.restype = i64
    lib.ct_decompress.argtypes = [i32, u8p, i64, u8p, i64]
    lib.ct_compress.restype = i64
    lib.ct_compress.argtypes = [i32, u8p, i64, u8p, i64, i32]
    lib.ct_compress_bound.restype = i64
    lib.ct_compress_bound.argtypes = [i32, i64]
    lib.ct_read_streams.restype = i64
    lib.ct_read_streams.argtypes = [ctypes.c_char_p, i32, i64, i64p, i64p,
                                    i64p, i64p, u8p, i64, u8p, i64]
    lib.ct_read_streams_mt.restype = i64
    lib.ct_read_streams_mt.argtypes = [ctypes.c_char_p, i32, i64, i64p, i64p,
                                       i64p, i64p, u8p, i64, i32]
    i32p = ctypes.POINTER(i32)
    lib.ct_decode_batch.restype = i64
    lib.ct_decode_batch.argtypes = [
        i32, ctypes.POINTER(ctypes.c_char_p), i32p, i64, i32p, i64p, i64p,
        i64p, i32p, i64p, i32, ctypes.POINTER(ctypes.c_void_p), i64p, i32,
        ctypes.POINTER(ctypes.c_double)]
    lib.ct_unpack_bits.restype = None
    lib.ct_unpack_bits.argtypes = [u8p, i64, u8p]
    lib.ct_version.restype = i32
    lib.ct_version.argtypes = []
    return lib


def get_lib():
    """The bound native library, or None when unavailable."""
    global LIB, _attempted
    if LIB is not None:
        return LIB
    with _lock:
        if LIB is not None or _attempted:
            return LIB
        _attempted = True
        # lint: disable=BLK01 -- one-shot native build: the lock exists precisely to run make exactly once
        if _try_build():
            try:
                LIB = _bind(ctypes.CDLL(_SO))
            except OSError:
                LIB = None
    return LIB
