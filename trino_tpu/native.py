"""ctypes bindings for the native (C++) runtime pieces.

pybind11 isn't in the image, so the native library (native/pagefile.cpp —
zlib page framing, validity bitmaps, page-file scanning) binds through
ctypes.  The shared object is a build product, never a tracked file:
``load()`` compiles it from the source on first use with the baked-in
toolchain (and again when the source is newer) and keeps it next to the
source.  A failed build is an error for the caller, not a ``None``.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading

__all__ = ["load", "lib_path"]

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SRC = os.path.join(_ROOT, "native", "pagefile.cpp")
_SO = os.path.join(_ROOT, "native", "libpagefile.so")

_lock = threading.Lock()
_lib = None


def lib_path() -> str:
    return _SO


def _build() -> None:
    """Compile to a private name and rename into place, so concurrent
    first users (xdist workers, worker processes) never load a half-written
    object."""
    tmp = f"{_SO}.{os.getpid()}.tmp"
    errors = []
    for cc in ("c++", "g++"):
        try:
            proc = subprocess.run(
                [cc, "-O3", "-shared", "-fPIC", "-o", tmp, _SRC, "-lz"],
                capture_output=True, text=True, timeout=120)
        except (OSError, subprocess.TimeoutExpired) as e:
            errors.append(f"{cc}: {e}")
            continue
        if proc.returncode == 0:
            os.replace(tmp, _SO)
            return
        errors.append(f"{cc}: rc={proc.returncode} {proc.stderr[-2000:]}")
    raise RuntimeError(
        f"native page-file library failed to build from {_SRC}: "
        + "; ".join(errors))


def load():
    """The loaded CDLL with typed signatures; raises when the library
    cannot be built or loaded."""
    global _lib
    with _lock:
        if _lib is not None:
            return _lib
        if not os.path.exists(_SO) or (
                os.path.getmtime(_SRC) > os.path.getmtime(_SO)):
            _build()
        lib = ctypes.CDLL(_SO)
        u8p = ctypes.POINTER(ctypes.c_uint8)
        i64 = ctypes.c_int64
        lib.ttp_deflate.argtypes = [u8p, i64, u8p, i64, ctypes.c_int]
        lib.ttp_deflate.restype = i64
        lib.ttp_deflate_bound.argtypes = [i64]
        lib.ttp_deflate_bound.restype = i64
        lib.ttp_inflate.argtypes = [u8p, i64, u8p, i64]
        lib.ttp_inflate.restype = i64
        lib.ttp_pack_bits.argtypes = [u8p, i64, u8p]
        lib.ttp_pack_bits.restype = None
        lib.ttp_unpack_bits.argtypes = [u8p, i64, u8p]
        lib.ttp_unpack_bits.restype = None
        lib.ttp_scan_frames.argtypes = [ctypes.c_char_p,
                                        ctypes.POINTER(i64), i64]
        lib.ttp_scan_frames.restype = i64
        lib.ttp_read_frame.argtypes = [ctypes.c_char_p, i64, i64, u8p]
        lib.ttp_read_frame.restype = i64
        _lib = lib
        return _lib
