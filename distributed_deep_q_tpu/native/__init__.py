"""Native (C++) replay core — build + ctypes loader.

The reference's native layer is external (Caffe C++/CUDA, ALE; SURVEY.md
§2.1); its own replay loops are Python. Here the PER sum-tree descent — the
one host-side pointer-chasing hot loop (SURVEY §7.3 item 2) — has a C++
implementation compiled on first use with the baked-in g++ toolchain
(no pybind11 in the image, so the ABI is plain C via ctypes).

Entry points of ``replay_core.cpp``: ``st_set`` / ``st_sample_stratified``
(sum tree, ``replay/prioritized.py``), ``staged_append`` (columnar ingest,
``replay/columnar.py``) and ``crc32c_update`` (the CRC-32C of every wire
frame and snapshot file, ``utils/durability.crc32c``; the CPU's own
instruction where it has one, a slicing-by-8 table loop otherwise, which
``crc32c_update_portable`` runs alone for the tests). The library is a
``ctypes.CDLL``, so every call gives the interpreter lock up while it runs.

``load()`` returns the ctypes lib or None (missing compiler, failed build);
callers fall back to the numpy implementation, which remains the semantic
reference. The artifact is named by a hash of ``replay_core.cpp``, so what
loads was always built from the source that sits beside it — a stale or
foreign ``.so`` that rides along in a copied tree (mtimes do not survive a
copy) simply never matches. ``backend()`` says which implementation ran.
"""

from __future__ import annotations

import ctypes
import functools
import glob
import hashlib
import os
import subprocess
import threading

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "replay_core.cpp")

_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_tried = False

_c_double_p = ctypes.POINTER(ctypes.c_double)
_c_int64_p = ctypes.POINTER(ctypes.c_int64)
_c_u8p = ctypes.POINTER(ctypes.c_uint8)
_c_u8pp = ctypes.POINTER(_c_u8p)


@functools.cache
def _artifact() -> str:
    """Path of the shared object for the source this process started
    with (hashed once)."""
    with open(_SRC, "rb") as fh:
        digest = hashlib.sha256(fh.read()).hexdigest()[:16]
    return os.path.join(_HERE, f"_replay_core.{digest}.so")


def _build(so: str) -> bool:
    """Compile to a process-unique temp path, then rename into place —
    atomic on POSIX, so concurrent builders (supervisor-spawned actor
    processes all importing replay) can never leave a half-written .so.
    Artifacts of other source revisions are removed afterwards."""
    tmp = f"{so}.{os.getpid()}.tmp"
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17",
           "-o", tmp, _SRC]
    try:
        subprocess.run(  # ddq: allow(blocking.under-lock) — build-once
            # gate: _lock exists to make the first caller compile while
            # the rest wait; nothing hot shares this module lock
            cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, so)
    except (OSError, subprocess.SubprocessError):
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return False
    for old in glob.glob(os.path.join(_HERE, "_replay_core*.so")):
        if old != so:
            try:
                os.unlink(old)
            except OSError:
                pass
    return True


def load() -> ctypes.CDLL | None:
    """Build (if needed) and load the native core; None on any failure."""
    global _lib, _tried
    so = _artifact()  # reads the source: off-lock, same answer for all
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if not os.path.exists(so) and not _build(so):
            return None
        try:
            lib = ctypes.CDLL(so)
        except OSError:
            # cached artifact unloadable (foreign arch, corrupt file):
            # rebuild once before giving up
            if not _build(so):
                return None
            try:
                lib = ctypes.CDLL(so)
            except OSError:
                return None
        lib.st_set.argtypes = [_c_double_p, ctypes.c_int64, _c_int64_p,
                               _c_double_p, ctypes.c_int64]
        lib.st_set.restype = None
        lib.st_sample_stratified.argtypes = [
            _c_double_p, ctypes.c_int64, _c_double_p, _c_int64_p,
            ctypes.c_int64]
        lib.st_sample_stratified.restype = None
        lib.staged_append.argtypes = [
            _c_u8pp, _c_u8pp, _c_int64_p, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int64]
        lib.staged_append.restype = ctypes.c_int64
        # the address as a plain integer: crc32c hands over whatever
        # memory the caller's buffer already occupies
        for fn in (lib.crc32c_update, lib.crc32c_update_portable):
            fn.argtypes = [ctypes.c_uint32, ctypes.c_void_p, ctypes.c_int64]
            fn.restype = ctypes.c_uint32
        _lib = lib
        return _lib


def backend() -> str:
    """Which replay core this process runs: ``"native"`` (the C++ ``.so``
    built from ``replay_core.cpp``) or ``"numpy"`` (the fallback)."""
    return "native" if load() is not None else "numpy"


def as_double_p(a) -> _c_double_p:
    return a.ctypes.data_as(_c_double_p)


def as_int64_p(a) -> _c_int64_p:
    return a.ctypes.data_as(_c_int64_p)


def as_uint8_p(a) -> _c_u8p:
    return a.ctypes.data_as(_c_u8p)


def uint8_pp(ptrs) -> _c_u8pp:
    """Pack an iterable of c_uint8 pointers into the pointer-array
    argument ``staged_append`` takes for its dst/src column tables."""
    arr = (_c_u8p * len(ptrs))(*ptrs)
    return ctypes.cast(arr, _c_u8pp)
