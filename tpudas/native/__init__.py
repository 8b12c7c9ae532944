"""Native (C++) ingest runtime, bound via ctypes.

``load_streamio()`` compiles ``streamio.cpp`` on first use (g++ -O3,
cached next to the source, keyed on a hash of the source, so a binary
built from other source is never loaded) and returns a ctypes
handle, or ``None`` when no toolchain is available / compilation fails /
``TPUDAS_NO_NATIVE=1``. Callers in :mod:`tpudas.io.tdas` fall back to a
pure-numpy implementation of the same format, so the framework is fully
functional without a compiler — the native path is the performance
runtime, not a hard dependency.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

_SRC = os.path.join(os.path.dirname(__file__), "streamio.cpp")

_lock = threading.Lock()
_cached: tuple[bool, ctypes.CDLL | None] | None = None


def lib_path() -> str | None:
    """Where the library built from the current ``streamio.cpp`` lives
    (``libstreamio.<sha256 prefix>.so``), or None without a source."""
    try:
        with open(_SRC, "rb") as fh:
            digest = hashlib.sha256(fh.read()).hexdigest()[:16]
    except OSError:
        return None
    return os.path.join(
        os.path.dirname(_SRC), f"libstreamio.{digest}.so"
    )


def _compile(lib: str) -> bool:
    if os.path.exists(lib):
        return True
    # compile to a process-unique temp in the same directory: concurrent
    # processes (pytest-xdist, parallel streaming jobs) would otherwise
    # interleave g++ writes on one shared inode and os.replace could
    # publish a corrupted .so that then gets cached for process lifetime
    tmp = f"{lib}.{os.getpid()}.tmp"
    cmd = [
        "g++",
        "-O3",
        "-std=c++17",
        "-shared",
        "-fPIC",
        "-pthread",
        _SRC,
        "-o",
        tmp,
    ]
    try:
        try:
            proc = subprocess.run(
                cmd, capture_output=True, text=True, timeout=120
            )
        except (OSError, subprocess.TimeoutExpired):
            return False
        if proc.returncode != 0:
            return False
        try:
            os.replace(tmp, lib)
        except OSError:
            return False
        return True
    finally:
        # g++ may leave a partial object on any failure path above
        if os.path.exists(tmp):
            try:
                os.remove(tmp)
            except OSError:
                pass


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    u64, u32, f32, f64 = (
        ctypes.c_uint64,
        ctypes.c_uint32,
        ctypes.c_float,
        ctypes.c_double,
    )
    p = ctypes.POINTER
    lib.tdas_write.restype = ctypes.c_int
    lib.tdas_write.argtypes = [
        ctypes.c_char_p, u64, u64, u32, u32, u32, f32, f64, f64,
        ctypes.c_void_p,
    ]
    lib.tdas_read_header.restype = ctypes.c_int
    lib.tdas_read_header.argtypes = [
        ctypes.c_char_p, p(u64), p(u64), p(u32), p(u32), p(u32), p(f32),
        p(f64), p(f64),
    ]
    lib.tdas_read_block.restype = ctypes.c_int
    lib.tdas_read_block.argtypes = [
        ctypes.c_char_p, u64, u64, u32, u32, p(f32), ctypes.c_int,
    ]
    i32 = ctypes.c_int
    lib.tdas_assemble_window.restype = i32
    lib.tdas_assemble_window.argtypes = [
        p(ctypes.c_char_p), p(u64), p(u64), p(u64), i32, u32, u32,
        p(f32), i32, p(i32), p(i32),
    ]
    lib.tdas_assemble_window_raw.restype = i32
    lib.tdas_assemble_window_raw.argtypes = [
        p(ctypes.c_char_p), p(u64), p(u64), p(u64), i32, u32, u32,
        u32, ctypes.c_void_p, i32, p(i32), p(i32),
    ]
    lib.tdas_plan_window_chunks.restype = i32
    lib.tdas_plan_window_chunks.argtypes = [
        p(u64), p(u64), p(u64), i32, u64, p(u64), i32,
    ]
    return lib


def load_streamio() -> ctypes.CDLL | None:
    """The compiled native library, or None (fallback mode)."""
    global _cached
    with _lock:
        if _cached is not None:
            return _cached[1]
        if os.environ.get("TPUDAS_NO_NATIVE") == "1":
            _cached = (False, None)
            return None
        lib = None
        path = lib_path()
        if path is not None and _compile(path):
            try:
                lib = _bind(ctypes.CDLL(path))
            except OSError:
                lib = None
        _cached = (lib is not None, lib)
        return lib
