"""Loader for the C++ native runtime kernels (`gtnative.cpp`).

Compiles the shared library on first import (g++, cached next to the
source), then binds it via ctypes. If no toolchain is available the
package still works: `lib` is None and callers (grandine_tpu.core.hashing,
grandine_tpu.crypto.bls.g2_from_bytes_batch) fall back to their
pure-Python paths.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "src", "gtnative.cpp")
_SO = os.path.join(_DIR, "_gtnative.so")
_STAMP = _SO + ".srchash"  # content hash of the source the .so was built from

_lock = threading.Lock()
lib = None
shani = False


def _read(path: str) -> bytes | None:
    try:
        with open(path, "rb") as f:
            return f.read()
    except OSError:
        return None


def _build() -> bool:
    """(Re)build the .so whenever the stamped source hash doesn't match.

    Keyed on a content hash, not mtimes: on a fresh clone git gives the
    source near-identical mtimes to any stray binary, and a stale or
    foreign-platform .so must never silently serve the consensus-critical
    hashing path. A missing source degrades to the hashlib fallback."""
    src = _read(_SRC)
    if src is None:
        return False
    src_hash = hashlib.sha256(src).hexdigest().encode()
    if os.path.exists(_SO) and _read(_STAMP) == src_hash:
        return True
    tmp = f"{_SO}.{os.getpid()}.tmp"  # per-process name: parallel first
    # imports must not interleave writes into one file
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", "-o", tmp, _SRC]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, _SO)
        with open(f"{_STAMP}.{os.getpid()}.tmp", "wb") as f:
            f.write(src_hash)
        os.replace(f"{_STAMP}.{os.getpid()}.tmp", _STAMP)
    except (subprocess.SubprocessError, FileNotFoundError, OSError):
        for leftover in (tmp, f"{_STAMP}.{os.getpid()}.tmp"):
            try:
                os.unlink(leftover)
            except OSError:
                pass
        return os.path.exists(_SO) and _read(_STAMP) == src_hash
    return True


def _bind():
    global lib, shani
    if lib is not None:
        return lib
    with _lock:
        if lib is not None:
            return lib
        if not _build():
            return None
        try:
            L = ctypes.CDLL(_SO)
            # c_char_p lets a Python bytes object pass zero-copy; outputs
            # are writable create_string_buffer()s (c_char_p compatible).
            cp = ctypes.c_char_p
            L.gt_init.restype = ctypes.c_int
            L.gt_sha256.argtypes = [cp, ctypes.c_uint64, cp]
            L.gt_hash_pairs.argtypes = [cp, ctypes.c_uint64, cp]
            L.gt_merkleize.argtypes = [cp, ctypes.c_uint64, ctypes.c_int, cp]
            L.gt_merkleize.restype = ctypes.c_int
            L.gt_merkleize_many.argtypes = [
                cp, ctypes.c_uint64, ctypes.c_uint64, ctypes.c_int, cp]
            L.gt_merkleize_many.restype = ctypes.c_int
            L.gt_mix_in_length.argtypes = [cp, ctypes.c_uint64, cp]
            L.gt_zero_hash.argtypes = [ctypes.c_int, cp]
            L.gt_crc32c.argtypes = [cp, ctypes.c_uint64]
            L.gt_crc32c.restype = ctypes.c_uint32
            L.gt_g2_decompress_batch.argtypes = [cp, ctypes.c_uint64, cp, cp]
            L.gt_g2_decompress_batch.restype = None
            shani = bool(L.gt_init())
        except (OSError, AttributeError):
            # missing/stale-ABI cached .so: degrade to hashlib fallback
            return None
        lib = L
        return lib


_bind()


def out_buf(n: int) -> ctypes.Array:
    """Writable output buffer for a gt_* call; read result via `.raw`."""
    return ctypes.create_string_buffer(n)


def available() -> bool:
    return lib is not None
