"""Build + bind the native runtime core (runtime/native/fdt_native.cc).

The library is compiled on demand with g++ and bound through ctypes — no
pybind11 dependency in this environment.  The build is keyed on a hash
of the SOURCE BYTES (``_build/libfdt_native-<hash>.so``): mtimes do not
survive a copy of the tree, and a library whose hash does not match the
source in the checkout is never loaded.  Every entry point has a
pure-Python fallback in data/, so the framework works even without a
toolchain; when the library IS available the data path uses it (see
data/agnews.py / data/loader.py call sites).  ``status()`` says which of
the three happened: ``built``, ``reused <hash>`` or ``no compiler:
Python fallback``.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import sys
import threading
from typing import List, Optional, Tuple

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "native", "fdt_native.cc")
_BUILD_DIR = os.path.join(_HERE, "_build")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_load_failed = False
_status = ""


def _source_digest() -> str:
    with open(_SRC, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()[:12]


def _build(lib_path: str) -> bool:
    os.makedirs(_BUILD_DIR, exist_ok=True)
    # build beside the target and rename: concurrent builders (xdist
    # workers) each publish a whole file, never a half-written one
    tmp = f"{lib_path}.{os.getpid()}.tmp"
    cmd = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", _SRC, "-o", tmp]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, lib_path)
        return True
    except Exception:
        if os.path.exists(tmp):
            os.remove(tmp)
        return False


def status() -> str:
    """How the library was obtained: ``built``, ``reused <hash>`` or
    ``no compiler: Python fallback`` (loads it on first call)."""
    load()
    return _status


def _set_status(msg: str) -> None:
    global _status
    _status = msg
    print(f"[native] {msg}", file=sys.stderr)


def load() -> Optional[ctypes.CDLL]:
    """The bound library, built from the checkout's source unless a
    build of exactly these source bytes exists; None on failure."""
    global _lib, _load_failed
    if _lib is not None or _load_failed:
        return _lib
    with _lock:
        if _lib is not None or _load_failed:
            return _lib
        try:
            digest = _source_digest()
            lib_path = os.path.join(_BUILD_DIR,
                                    f"libfdt_native-{digest}.so")
            if os.path.exists(lib_path):
                how = f"reused {digest}"
            elif _build(lib_path):
                how = "built"
            else:
                _load_failed = True
                _set_status("no compiler: Python fallback")
                return None
            lib = ctypes.CDLL(lib_path)
            lib.fdt_crc32.restype = ctypes.c_uint32
            lib.fdt_crc32.argtypes = [ctypes.c_char_p, ctypes.c_int64]
            lib.fdt_clean_text.restype = ctypes.c_int64
            lib.fdt_clean_text.argtypes = [ctypes.c_char_p, ctypes.c_char_p,
                                           ctypes.c_int64]
            lib.fdt_encode_batch.restype = ctypes.c_int32
            lib.fdt_encode_batch.argtypes = [
                ctypes.POINTER(ctypes.c_char_p), ctypes.c_int32,
                ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
                ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
                ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32)]
            lib.fdt_gather_u8.restype = ctypes.c_int32
            lib.fdt_gather_u8.argtypes = [
                ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64),
                ctypes.c_int32, ctypes.c_int64, ctypes.c_char_p]
            lib.fdt_wp_load.restype = ctypes.c_int32
            lib.fdt_wp_load.argtypes = [ctypes.c_char_p, ctypes.c_int64]
            lib.fdt_stopwords.restype = ctypes.c_int64
            lib.fdt_stopwords.argtypes = [ctypes.c_char_p, ctypes.c_int64]
            lib.fdt_wp_encode_batch.restype = ctypes.c_int32
            lib.fdt_wp_encode_batch.argtypes = [
                ctypes.c_int32, ctypes.POINTER(ctypes.c_char_p),
                ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
                ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
                ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32)]
            _lib = lib
            _set_status(how)
        except Exception as e:
            _load_failed = True
            _set_status(f"no compiler: Python fallback ({e!r})")
    return _lib


def available() -> bool:
    return load() is not None


def crc32(data: bytes) -> int:
    lib = load()
    if lib is None:
        import zlib
        return zlib.crc32(data)
    return lib.fdt_crc32(data, len(data))


def clean_text(text: str) -> Optional[str]:
    """Native clean_text; None when the library is unavailable (caller
    falls back to the Python implementation)."""
    lib = load()
    if lib is None:
        return None
    raw = text.encode("utf-8", "ignore")
    cap = max(len(raw) + 16, 64)
    buf = ctypes.create_string_buffer(cap)
    n = lib.fdt_clean_text(raw, buf, cap)
    if n < 0:                       # shouldn't happen: cleaning only shrinks
        cap = -int(n)
        buf = ctypes.create_string_buffer(cap)
        n = lib.fdt_clean_text(raw, buf, cap)
        if n < 0:
            return None
    return buf.raw[:n].decode("utf-8", "ignore")


def stopwords() -> Optional[frozenset]:
    """The native core's vendored stopword list; None when the library is
    unavailable.  Used by tests to pin byte-parity with data/agnews.py."""
    lib = load()
    if lib is None:
        return None
    cap = 4096
    buf = ctypes.create_string_buffer(cap)
    n = lib.fdt_stopwords(buf, cap)
    if n < 0:
        cap = -int(n)
        buf = ctypes.create_string_buffer(cap)
        n = lib.fdt_stopwords(buf, cap)
        if n < 0:
            return None
    return frozenset(buf.raw[:n].decode("utf-8").split("\n"))


def encode_batch(texts: List[str], max_len: int, vocab_size: int,
                 pad_id: int = 0, cls_id: int = 101, sep_id: int = 102,
                 reserved: int = 999
                 ) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """Native HashTokenizer batch encode of CLEANED texts.
    Returns (tokens [n, max_len] int32, lens [n] int32) or None."""
    lib = load()
    if lib is None:
        return None
    n = len(texts)
    tokens = np.empty((n, max_len), np.int32)
    lens = np.empty((n,), np.int32)
    arr = (ctypes.c_char_p * n)(*[t.encode("utf-8", "ignore") for t in texts])
    rc = lib.fdt_encode_batch(
        arr, n, max_len, vocab_size, pad_id, cls_id, sep_id, reserved,
        tokens.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        lens.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
    if rc != 0:
        return None
    return tokens, lens


def wp_load(vocab_lines: List[str]) -> Optional[int]:
    """Register a WordPiece vocab (id = list index) with the native core;
    returns a handle, or None when the library is unavailable.  The caller
    owns the handle (register once per tokenizer, not per batch)."""
    lib = load()
    if lib is None:
        return None
    blob = "\n".join(vocab_lines).encode("utf-8")
    h = lib.fdt_wp_load(blob, len(blob))
    return None if h < 0 else h


def wp_encode_batch(handle: int, texts: List[str], max_len: int,
                    cls_id: int, sep_id: int, unk_id: int, pad_id: int
                    ) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """Native WordPiece batch encode of CLEANED ([a-z0-9' ]) texts.
    Returns (tokens [n, max_len] int32, lens [n] int32), or None when the
    library is unavailable or a text needs the full-Unicode Python path."""
    lib = load()
    if lib is None:
        return None
    n = len(texts)
    tokens = np.empty((n, max_len), np.int32)
    lens = np.empty((n,), np.int32)
    try:
        arr = (ctypes.c_char_p * n)(*[t.encode("ascii") for t in texts])
    except UnicodeEncodeError:
        return None
    rc = lib.fdt_wp_encode_batch(
        handle, arr, n, max_len, cls_id, sep_id, unk_id, pad_id,
        tokens.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        lens.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
    if rc != 0:
        return None
    return tokens, lens


def gather_u8(src: np.ndarray, indices: np.ndarray) -> Optional[np.ndarray]:
    """dst[i] = src[indices[i]] for a C-contiguous uint8 array; None when
    the library is unavailable."""
    lib = load()
    if lib is None or src.dtype != np.uint8 or not src.flags.c_contiguous:
        return None
    idx = np.ascontiguousarray(indices, np.int64)
    row_bytes = int(np.prod(src.shape[1:])) * src.itemsize
    dst = np.empty((len(idx),) + src.shape[1:], np.uint8)
    lib.fdt_gather_u8(
        src.ctypes.data_as(ctypes.c_char_p),
        idx.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        len(idx), row_bytes, dst.ctypes.data_as(ctypes.c_char_p))
    return dst
