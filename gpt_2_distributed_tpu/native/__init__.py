"""Native (C) runtime components, ctypes-bound with graceful fallback.

The reference's host runtime is native by inheritance (torch's C++
DataLoader workers, pinned-memory transfer — SURVEY.md §2.3); this package
is the framework's first-party equivalent for the pieces that matter on a
TPU-VM host. Currently: the batched token-window gather on the data-loading
hot path (``window_gather.c``).

Build model: the shared object is compiled ON DEMAND from the checked-in C
source with whatever C compiler the host has (cc/gcc/clang), cached next to
the source under a name that carries a hash of that source, and loaded with
ctypes — no pybind11, no setuptools extension step, no numpy C API. The
object is git-ignored; a tree copied with one lying in it (file times do not
survive every copy) can therefore only ever load the build of the source it
holds. Hosts without a compiler simply report
``available() == False`` and callers use their pure-numpy path; behavior is
identical either way (asserted by tests/test_native.py).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import sysconfig
import threading

import numpy as np

_SRC = os.path.join(os.path.dirname(__file__), "window_gather.c")

_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_tried = False


def _compiler() -> str | None:
    for cand in (os.environ.get("CC"), sysconfig.get_config_var("CC"),
                 "cc", "gcc", "clang"):
        if not cand:
            continue
        exe = cand.split()[0]
        from shutil import which

        if which(exe):
            return cand
    return None


def so_path() -> str | None:
    """Where the build of the CURRENT source is cached, or None when the
    source is missing."""
    try:
        with open(_SRC, "rb") as f:
            digest = hashlib.sha1(f.read()).hexdigest()[:12]
    except OSError:
        return None
    return os.path.join(os.path.dirname(_SRC), f"_window_gather-{digest}.so")


def _build_and_load() -> ctypes.CDLL | None:
    so = so_path()
    if so is None:
        return None
    try:
        if os.path.exists(so):
            return ctypes.CDLL(so)
    except OSError:
        # Foreign cached .so (other arch/glibc): fall through to a rebuild,
        # or to the numpy path below.
        pass
    cc = _compiler()
    if cc is None:
        return None
    # Per-process tmp name: two processes building concurrently must not
    # interleave compiler output in one file — os.replace then guarantees
    # whichever finishes last installs a COMPLETE object.
    tmp = f"{so}.{os.getpid()}.tmp"
    cmd = cc.split() + ["-O3", "-shared", "-fPIC", "-o", tmp, _SRC]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, so)
        return ctypes.CDLL(so)
    except (subprocess.SubprocessError, OSError):
        return None
    finally:
        # A failed/timed-out compile must not leak one orphan tmp per pid.
        if os.path.exists(tmp):
            try:
                os.unlink(tmp)
            except OSError:
                pass


def _get_lib() -> ctypes.CDLL | None:
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    with _lock:
        if _lib is None and not _tried:
            lib = _build_and_load()
            if lib is not None:
                lib.gather_windows.restype = ctypes.c_int64
                lib.gather_windows.argtypes = [
                    ctypes.c_void_p, ctypes.c_int64,
                    ctypes.c_void_p, ctypes.c_int64,
                    ctypes.c_int64, ctypes.c_void_p,
                ]
            _lib = lib
            _tried = True
    return _lib


def available() -> bool:
    """True when the native gather compiled and loaded on this host."""
    return _get_lib() is not None


def describe() -> str:
    """Which gather path the dataloader takes on this host, for banners."""
    return "native (C)" if available() else "numpy (no C compiler)"


def gather_windows(
    tokens: np.ndarray,    # uint16 memmap/array, the whole shard
    offsets: np.ndarray,   # int64 window starts
    window_len: int,
) -> tuple[np.ndarray, int]:
    """Gather ``len(offsets)`` windows of ``window_len`` tokens in one native
    call (GIL released for the copy+scan). Returns ``(out [N, window_len]
    uint16, max_token_id)``. Raises IndexError on an out-of-range offset.

    Callers must check :func:`available` first; this function assumes the
    library loaded.
    """
    lib = _get_lib()
    assert lib is not None, "native gather not available — check available()"
    tokens = np.ascontiguousarray(tokens, dtype=np.uint16)
    offsets = np.ascontiguousarray(offsets, dtype=np.int64)
    out = np.empty((offsets.size, window_len), dtype=np.uint16)
    max_id = lib.gather_windows(
        tokens.ctypes.data, tokens.size,
        offsets.ctypes.data, offsets.size,
        window_len, out.ctypes.data,
    )
    if max_id < 0:
        raise IndexError(
            f"window offset out of range for shard of {tokens.size} tokens"
        )
    return out, int(max_id)
