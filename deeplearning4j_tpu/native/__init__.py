"""Native host-runtime helpers (libdatavec_native, C++ via ctypes).

SURVEY §7.1.2's stance — "native where the reference is native" — applied to
the ONE place host CPU still sits on the training path in this architecture:
ETL loops feeding the device (the reference's equivalents live in libnd4j's
CPU helpers and DataVec's native image loaders). The device compute path is
XLA; these helpers accelerate corpus scanning / pair generation.

Build-on-first-use: compiled with g++ into the package dir, loaded with
ctypes (no pybind11 in this image). Every caller MUST tolerate
``available() == False`` and fall back to the numpy path — toolchain absence
degrades performance, never correctness.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import warnings
from typing import Optional, Tuple

import numpy as np

_HERE = os.path.dirname(__file__)
_SRC = os.path.join(_HERE, "datavec_native.cpp")

_lib = None
_tried = False
_error: Optional[str] = None


# Sanitizer build flavor (SURVEY §5.2: ASAN/UBSAN flavors for native code,
# the analog of libnd4j's SD_SANITIZE CMake toggle). Set
# DL4J_TPU_NATIVE_SANITIZE=address|undefined BEFORE first use; the
# sanitized .so needs the matching runtime preloaded in the host process
# (LD_PRELOAD=$(g++ -print-file-name=libasan.so)) — see
# tests/test_native.py::TestSanitizerFlavor for the harness.
_SANITIZE = os.environ.get("DL4J_TPU_NATIVE_SANITIZE", "")


def _so_path() -> str:
    return os.path.join(
        _HERE, f"libdatavec_native{'_' + _SANITIZE if _SANITIZE else ''}.so")


def _build() -> Optional[str]:
    """Compile the library from ``datavec_native.cpp``; returns None on
    success, else what went wrong."""
    flags = ["-O3"]
    if _SANITIZE:
        flags = ["-O1", "-g", f"-fsanitize={_SANITIZE}",
                 "-fno-omit-frame-pointer"]
    cmd = ["g++", *flags, "-shared", "-fPIC", "-std=c++17", _SRC,
           "-o", _so_path()]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
    except FileNotFoundError:
        return "g++ not found"
    except subprocess.TimeoutExpired:
        return "g++ timed out after 120 s"
    except subprocess.CalledProcessError as e:
        return (f"g++ exited {e.returncode}: "
                f"{e.stderr.decode(errors='replace')[-2000:]}")
    return None


def _unavailable(why: str) -> None:
    """The numpy fallback is correct but slower: say once why it is on."""
    global _error
    _error = why
    warnings.warn(f"libdatavec_native unavailable ({why}); host ETL falls "
                  "back to numpy", RuntimeWarning, stacklevel=4)


def _load():
    global _lib, _tried
    if _tried:
        return _lib
    _tried = True
    so = _so_path()
    if not os.path.exists(so) or \
            os.path.getmtime(so) < os.path.getmtime(_SRC):
        err = _build()
        if err is not None:
            _unavailable(err)
            return None
    try:
        lib = ctypes.CDLL(so)
    except OSError as e:
        _unavailable(f"dlopen failed: {e}")
        return None
    lib.sg_pairs.restype = ctypes.c_int64
    lib.sg_pairs.argtypes = [
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int64, ctypes.c_int32, ctypes.c_void_p, ctypes.c_uint64,
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32),
        ctypes.c_int64]
    lib.tokenize_spans.restype = ctypes.c_int64
    lib.tokenize_spans.argtypes = [
        ctypes.c_char_p, ctypes.c_int64, ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int64), ctypes.c_int64]
    _lib = lib
    return _lib


def available() -> bool:
    return _load() is not None


def load_error() -> Optional[str]:
    """Why :func:`available` is False (build or load failure), else None."""
    _load()
    return _error


def sg_pairs(ids: np.ndarray, offsets: np.ndarray, window: int,
             keep: Optional[np.ndarray], seed: int
             ) -> Tuple[np.ndarray, np.ndarray]:
    """Skip-gram (center, context) pairs for a corpus chunk — the word2vec
    host hot loop in one native call. ids int32 concatenated sentences;
    offsets int64 [n_sent+1]."""
    lib = _load()
    assert lib is not None, "native library unavailable; guard with available()"
    ids = np.ascontiguousarray(ids, dtype=np.int32)
    offsets = np.ascontiguousarray(offsets, dtype=np.int64)
    cap = int(2 * window * max(ids.size, 1))
    centers = np.empty(cap, dtype=np.int32)
    contexts = np.empty(cap, dtype=np.int32)
    keep_ptr = None
    if keep is not None:
        keep = np.ascontiguousarray(keep, dtype=np.float64)
        keep_ptr = keep.ctypes.data_as(ctypes.c_void_p)
    n = lib.sg_pairs(
        ids.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        len(offsets) - 1, window, keep_ptr, seed,
        centers.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        contexts.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)), cap)
    return centers[:n], contexts[:n]


def tokenize(text: str):
    """Whitespace tokens of a (possibly huge) string in one native pass."""
    lib = _load()
    assert lib is not None, "native library unavailable; guard with available()"
    raw = text.encode("utf-8")
    cap = max(len(raw) // 2 + 1, 16)
    starts = np.empty(cap, dtype=np.int64)
    lens = np.empty(cap, dtype=np.int64)
    n = lib.tokenize_spans(
        raw, len(raw),
        starts.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        lens.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), cap)
    return [raw[starts[i]:starts[i] + lens[i]].decode("utf-8")
            for i in range(n)]
