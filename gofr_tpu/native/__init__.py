"""ctypes loader for the native runtime helpers.

The shared library is built from gofr_native.cc on first use when a C++
toolchain is present, into libgofr_native-<hash of the source>.so beside it.
The name carries the source's hash because a file's time does not survive a
copy of the tree: a library built from other source is never loaded, and a
checkout that carries no library (git commits only the source) builds its
own. Every consumer degrades to its pure-Python path when `available()` is
False — a machine without a toolchain still serves — and `status()` says
which of the two it is, so that a build which should have worked and did
not is an error somebody sees (chip_smoke.py fails on it).

API:
  available() -> bool
  status() -> dict                  — built / fallback, and why
  BPECore(merge_triples)   — id-level greedy BPE merges (hot encode loop)
  pad_batch(rows, max_len, pad_id) -> np.ndarray[int32]
  utf8_complete_prefix(buf) -> int
  propose_draft(history, d) -> list[int]  — speculative prompt-lookup scan
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import shutil
import subprocess
import threading
from typing import List, Optional, Sequence, Tuple

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "gofr_native.cc")

_lib = None
_load_lock = threading.Lock()
_load_attempted = False
_load_error: Optional[str] = None

_i32p = ctypes.POINTER(ctypes.c_int32)
_i64p = ctypes.POINTER(ctypes.c_int64)


def _cxx() -> Optional[str]:
    return shutil.which(os.environ.get("CXX", "g++"))


def _so_path() -> str:
    with open(_SRC, "rb") as fp:
        digest = hashlib.sha256(fp.read()).hexdigest()[:16]
    return os.path.join(_DIR, f"libgofr_native-{digest}.so")


def _build(so: str) -> Optional[str]:
    """Compile the source to `so`; returns an error text, or None."""
    tmp = f"{so}.tmp.{os.getpid()}"
    try:
        result = subprocess.run(
            [_cxx(), "-O3", "-std=c++17", "-fPIC", "-shared", "-o", tmp, _SRC],
            capture_output=True, timeout=120)
        if result.returncode != 0:
            return result.stderr.decode(errors="replace")[-2000:]
        os.replace(tmp, so)    # a concurrent loader sees all of it or none
    except (OSError, subprocess.TimeoutExpired) as exc:
        return f"{type(exc).__name__}: {exc}"
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)
    for stale in glob.glob(os.path.join(_DIR, "libgofr_native*.so")):
        if stale != so:        # libraries of earlier sources
            try:
                os.remove(stale)
            except OSError:
                pass
    return None


def _bind(lib) -> None:
    lib.gn_version.restype = ctypes.c_char_p
    lib.gn_bpe_new.restype = ctypes.c_void_p
    lib.gn_bpe_new.argtypes = [ctypes.c_int32, _i32p, _i32p, _i32p]
    lib.gn_bpe_free.argtypes = [ctypes.c_void_p]
    lib.gn_bpe_encode.restype = ctypes.c_int32
    lib.gn_bpe_encode.argtypes = [ctypes.c_void_p, _i32p, ctypes.c_int32, _i32p]
    lib.gn_pad_batch.restype = ctypes.c_int32
    lib.gn_pad_batch.argtypes = [_i32p, _i64p, ctypes.c_int32, ctypes.c_int32,
                                 ctypes.c_int32, _i32p]
    lib.gn_utf8_complete_prefix.restype = ctypes.c_int32
    lib.gn_utf8_complete_prefix.argtypes = [ctypes.POINTER(ctypes.c_uint8),
                                            ctypes.c_int32]
    lib.gn_propose_draft.restype = ctypes.c_int32
    lib.gn_propose_draft.argtypes = [_i32p, ctypes.c_int32, ctypes.c_int32,
                                     _i32p]


def _load():
    global _lib, _load_attempted, _load_error
    if _lib is not None:  # fast path: no lock once loaded (hot callers)
        return _lib
    with _load_lock:
        if _lib is not None or _load_attempted:
            return _lib
        _load_attempted = True
        so = _so_path()
        if not os.path.exists(so):
            if _cxx() is None:
                _load_error = "no C++ toolchain"
                return None
            _load_error = _build(so)
            if _load_error is not None:
                return None
        try:
            lib = ctypes.CDLL(so)
            _bind(lib)
        except (OSError, AttributeError) as exc:
            _load_error = f"{type(exc).__name__}: {exc}"
            return None
        _lib = lib
        return _lib


def available() -> bool:
    return _load() is not None


def status() -> dict:
    """Which implementation serves and why: {"native": bool, "toolchain":
    bool, "error": text or None}. native False with a toolchain present is
    a build or load that failed — an error, not a fallback to accept."""
    return {"native": available(), "toolchain": _cxx() is not None,
            "error": None if _lib is not None else _load_error}


def version() -> str:
    lib = _load()
    return lib.gn_version().decode() if lib else "unavailable"


class BPECore:
    """Native greedy BPE over token ids.

    merge_triples: ordered [(left_id, right_id, merged_id)] — index is rank.
    """

    def __init__(self, merge_triples: Sequence[Tuple[int, int, int]]):
        lib = _load()
        if lib is None:
            raise RuntimeError("gofr_native unavailable (no C++ toolchain?)")
        self._lib = lib
        arr = np.asarray(merge_triples, dtype=np.int32).reshape(-1, 3)
        left = np.ascontiguousarray(arr[:, 0])
        right = np.ascontiguousarray(arr[:, 1])
        merged = np.ascontiguousarray(arr[:, 2])
        self._handle = lib.gn_bpe_new(
            len(arr), left.ctypes.data_as(_i32p), right.ctypes.data_as(_i32p),
            merged.ctypes.data_as(_i32p))

    def encode(self, ids: Sequence[int]) -> List[int]:
        src = np.asarray(ids, dtype=np.int32)
        if src.size == 0:
            return []
        src = np.ascontiguousarray(src)
        out = np.empty(src.size, dtype=np.int32)
        n = self._lib.gn_bpe_encode(self._handle, src.ctypes.data_as(_i32p),
                                    src.size, out.ctypes.data_as(_i32p))
        return out[:n].tolist()

    def __del__(self):
        handle = getattr(self, "_handle", None)
        if handle:
            self._lib.gn_bpe_free(handle)
            self._handle = None


def pad_batch(rows: Sequence[Sequence[int]], max_len: int,
              pad_id: int = 0) -> Optional[np.ndarray]:
    """Pack variable-length token rows into a padded [n, max_len] int32 matrix.

    Overlong rows keep their tail. Returns None when the library is missing
    (callers fall back to numpy loops).
    """
    lib = _load()
    if lib is None:
        return None
    lengths = np.asarray([len(r) for r in rows], dtype=np.int64)
    flat = (np.concatenate([np.asarray(r, dtype=np.int32) for r in rows])
            if len(rows) and lengths.sum() else np.empty(0, dtype=np.int32))
    flat = np.ascontiguousarray(flat)
    out = np.empty((len(rows), max_len), dtype=np.int32)
    rc = lib.gn_pad_batch(flat.ctypes.data_as(_i32p),
                          lengths.ctypes.data_as(_i64p), len(rows), max_len,
                          pad_id, out.ctypes.data_as(_i32p))
    if rc != 0:
        raise ValueError("gn_pad_batch failed (negative length or max_len)")
    return out


def utf8_complete_prefix(buf: bytes) -> int:
    """Bytes of `buf` that form whole UTF-8 codepoints (SSE chunk boundary)."""
    lib = _load()
    if lib is None:
        # pure-Python mirror of the C algorithm: back up over at most three
        # continuation bytes; an incomplete-but-valid tail sequence is cut,
        # anything invalid counts as complete (replacement char on decode)
        if not buf:
            return 0
        i = len(buf) - 1
        back = 0
        while i > 0 and (buf[i] & 0xC0) == 0x80 and back < 3:
            i -= 1
            back += 1
        lead = buf[i]
        if (lead & 0x80) == 0:
            need = 1
        elif (lead & 0xE0) == 0xC0:
            need = 2
        elif (lead & 0xF0) == 0xE0:
            need = 3
        elif (lead & 0xF8) == 0xF0:
            need = 4
        else:
            return len(buf)
        return len(buf) if i + need <= len(buf) else i
    arr = (ctypes.c_uint8 * len(buf)).from_buffer_copy(buf) if buf else \
        (ctypes.c_uint8 * 1)()
    return lib.gn_utf8_complete_prefix(arr, len(buf))


def propose_draft(history, d: int) -> Optional[List[int]]:
    """Prompt-lookup draft: tokens that followed the most recent earlier
    occurrence of history's trailing bigram (speculative decoding's host
    side). Returns None when the library is missing (callers fall back to
    the pure-Python scan in the engine)."""
    lib = _load()
    if lib is None:
        return None
    n = len(history)
    if n < 3 or d <= 0:
        return []
    hist = np.ascontiguousarray(np.asarray(history, dtype=np.int32))
    out = np.empty(d, dtype=np.int32)
    count = lib.gn_propose_draft(hist.ctypes.data_as(_i32p), n, d,
                                 out.ctypes.data_as(_i32p))
    return out[:count].tolist()
