"""Loader for the native (C++) components under native/build/.

`native/build/` is not tracked, so the libs are built from the tracked
sources on first use (`make -C native`, which rebuilds what is missing or
older than its source).  Every native component has a pure-Python twin;
when the build cannot run the twins are used, and that is said once,
loudly — which implementation served a request must never depend on an
untracked directory in silence.
"""

from __future__ import annotations

import ctypes
import fcntl
import logging
import os
import subprocess
from typing import Dict, Optional

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_NATIVE = os.path.join(_ROOT, "native")
_BUILD = os.path.join(_NATIVE, "build")

_build_checked = False


def _ensure_built() -> None:
    """Run `make -C native` once per process, under a file lock so that
    concurrent processes (xdist workers, a fleet of workers starting
    together) never load a half-written library."""
    global _build_checked
    if _build_checked:
        return
    _build_checked = True
    try:
        os.makedirs(_BUILD, exist_ok=True)
        with open(os.path.join(_BUILD, ".lock"), "w") as lock:
            fcntl.flock(lock, fcntl.LOCK_EX)
            proc = subprocess.run(
                ["make", "-C", _NATIVE, "all"], capture_output=True,
                text=True, timeout=600,
            )
        err = proc.stderr.strip()[-500:] if proc.returncode else ""
    except (OSError, subprocess.TimeoutExpired) as e:
        err = repr(e)
    if err:
        logging.getLogger(__name__).warning(
            "`make -C %s` failed (%s); the PYTHON TWINS of the block "
            "hasher and the radix index are in use unless a previously "
            "built library loads", _NATIVE, err,
        )


def status() -> Dict[str, str]:
    """Which implementation each native component resolved to."""
    return {
        "radix_index": "native" if radix_lib() is not None else "python",
        "block_hash": "native" if tokens_lib() is not None else "python",
    }


_radix_lib: Optional[ctypes.CDLL] = None


def radix_lib() -> Optional[ctypes.CDLL]:
    """The libdynamo_radix.so handle, or None when it cannot be built."""
    global _radix_lib
    if _radix_lib is not None:
        return _radix_lib
    _ensure_built()
    path = os.path.join(_BUILD, "libdynamo_radix.so")
    if not os.path.exists(path):
        return None
    lib = ctypes.CDLL(path)
    u64p = ctypes.POINTER(ctypes.c_uint64)
    i64p = ctypes.POINTER(ctypes.c_int64)
    lib.radix_create.restype = ctypes.c_void_p
    lib.radix_destroy.argtypes = [ctypes.c_void_p]
    lib.radix_apply_stored.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, u64p, ctypes.c_int64]
    lib.radix_apply_removed.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, u64p, ctypes.c_int64]
    lib.radix_remove_worker.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    lib.radix_num_blocks.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    lib.radix_num_blocks.restype = ctypes.c_int64
    lib.radix_num_workers.argtypes = [ctypes.c_void_p]
    lib.radix_num_workers.restype = ctypes.c_int64
    lib.radix_find_matches.argtypes = [
        ctypes.c_void_p, u64p, ctypes.c_int64, i64p, i64p, ctypes.c_int64]
    lib.radix_find_matches.restype = ctypes.c_int64
    lib.radix_worker_hashes.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, u64p, ctypes.c_int64]
    lib.radix_worker_hashes.restype = ctypes.c_int64
    lib.radix_workers.argtypes = [ctypes.c_void_p, i64p, ctypes.c_int64]
    lib.radix_workers.restype = ctypes.c_int64
    _radix_lib = lib
    return lib


_tokens_lib: Optional[ctypes.CDLL] = None
_tokens_lib_missing = False


def tokens_lib() -> Optional[ctypes.CDLL]:
    """The libdynamo_tokens.so handle (chained block hashing), or None."""
    global _tokens_lib, _tokens_lib_missing
    if _tokens_lib is not None or _tokens_lib_missing:
        return _tokens_lib
    _ensure_built()
    path = os.path.join(_BUILD, "libdynamo_tokens.so")
    if not os.path.exists(path):
        _tokens_lib_missing = True
        return None
    lib = ctypes.CDLL(path)
    u8p = ctypes.POINTER(ctypes.c_uint8)
    u32p = ctypes.POINTER(ctypes.c_uint32)
    u64p = ctypes.POINTER(ctypes.c_uint64)
    lib.dyn_hash_bytes.argtypes = [u8p, ctypes.c_uint64]
    lib.dyn_hash_bytes.restype = ctypes.c_uint64
    lib.dyn_block_hashes.argtypes = [
        u32p, ctypes.c_uint64, ctypes.c_uint64, ctypes.c_uint64, u64p]
    lib.dyn_block_hashes.restype = ctypes.c_uint64
    _tokens_lib = lib
    return lib
