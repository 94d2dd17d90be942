"""Native host runtime: lazy-built C++ core with ctypes bindings.

The reference's whole runtime is native Rust; here the host-side hot paths
(UTF-8 batch packing, UAX#29-lite word segmentation, n-gram duplicate scans,
byte-level BPE counting — see ``src/textblaster_native.cpp``) are C++,
compiled on first use with the toolchain baked into the image.  A stamp
next to the built library holds a hash of the source, the compiler flags and
this machine's CPU, so a checkout copied to another machine never loads an
object built with ``-march=native`` for a different CPU: it rebuilds the
library in place (about 2 s).  Everything
has a pure-Python/numpy fallback (``textblaster_tpu/utils/text.py``), which
stays the semantic source of truth: parity tests assert the two produce
identical results.

Set ``TEXTBLASTER_NATIVE=0`` to force the Python paths.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import platform
import subprocess
import tempfile
import threading
from typing import List, Optional, Tuple

import numpy as np

logger = logging.getLogger(__name__)

__all__ = [
    "available",
    "pack_utf8",
    "utf8_lengths",
    "word_spans_native",
    "dup_ngram_bytes",
    "top_ngram_bytes",
    "dup_items",
    "BpeCounter",
]

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "src", "textblaster_native.cpp")
_SO = os.path.join(_DIR, "libtextblaster_native.so")
_STAMP = _SO + ".key"
_FLAGS = ("-O3", "-march=native", "-std=c++17", "-fPIC", "-shared")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False

_i64 = ctypes.c_int64
_p_u8 = np.ctypeslib.ndpointer(dtype=np.uint8, flags="C_CONTIGUOUS")
_p_i32 = np.ctypeslib.ndpointer(dtype=np.int32, flags="C_CONTIGUOUS")
_p_i64 = np.ctypeslib.ndpointer(dtype=np.int64, flags="C_CONTIGUOUS")


def _cpu_signature() -> bytes:
    """What ``-march=native`` compiles for: the machine and its CPU flags."""
    sig = platform.machine()
    try:
        with open("/proc/cpuinfo", encoding="utf-8", errors="replace") as f:
            for line in f:
                if line.startswith(("flags", "Features", "model name")):
                    sig += "\n" + line.strip()
    except OSError:  # pragma: no cover - non-Linux host
        pass
    return sig.encode("utf-8")


def _build_key(cxx: str) -> str:
    """Names the library this source, these flags and this CPU build."""
    h = hashlib.sha256()
    with open(_SRC, "rb") as f:
        h.update(f.read())
    h.update(" ".join((cxx,) + _FLAGS).encode("utf-8"))
    h.update(_cpu_signature())
    return h.hexdigest()


def _built(key: str) -> bool:
    try:
        with open(_STAMP, encoding="utf-8") as f:
            return f.read() == key and os.path.exists(_SO)
    except OSError:
        return False


def _build(cxx: str, key: str) -> bool:
    # Build, then stamp, each under a temporary name renamed into place:
    # concurrent first users (test workers) never load a half-written
    # object, and a rebuild replaces the library instead of adding one.
    fd, tmp = tempfile.mkstemp(dir=_DIR, suffix=".tmp")
    os.close(fd)
    try:
        cmd = [cxx, *_FLAGS, "-o", tmp, _SRC]
        try:
            proc = subprocess.run(cmd, capture_output=True, text=True, timeout=120)
        except (OSError, subprocess.TimeoutExpired) as e:
            logger.warning("native build failed to run: %s", e)
            return False
        if proc.returncode != 0:
            logger.warning("native build failed:\n%s", proc.stderr[-2000:])
            return False
        os.replace(tmp, _SO)
        with open(tmp, "w", encoding="utf-8") as f:
            f.write(key)
        os.replace(tmp, _STAMP)
        return True
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if os.environ.get("TEXTBLASTER_NATIVE", "1") == "0":
            return None
        cxx = os.environ.get("CXX", "g++")
        key = _build_key(cxx)
        if not _built(key) and not _build(cxx, key):
            return None
        try:
            lib = ctypes.CDLL(_SO)
        except OSError as e:
            logger.warning("native library failed to load: %s", e)
            return None

        lib.tb_pack_utf8.argtypes = [_p_u8, _p_i64, _i64, _p_i32, _p_i32, _i64, _i64]
        lib.tb_pack_utf8.restype = None
        lib.tb_utf8_lengths.argtypes = [_p_u8, _p_i64, _i64, _p_i32]
        lib.tb_utf8_lengths.restype = None
        lib.tb_word_spans.argtypes = [_p_i32, _i64, _p_u8, _p_i32, _i64]
        lib.tb_word_spans.restype = _i64
        lib.tb_dup_ngram_bytes.argtypes = [_p_i32, _p_i32, _i64, _i64]
        lib.tb_dup_ngram_bytes.restype = _i64
        lib.tb_top_ngram_bytes.argtypes = [_p_i32, _p_i32, _i64, _i64]
        lib.tb_top_ngram_bytes.restype = _i64
        lib.tb_dup_items.argtypes = [
            _p_i32,
            _p_i32,
            _i64,
            ctypes.POINTER(ctypes.c_int64),
        ]
        lib.tb_dup_items.restype = _i64
        lib.tb_bpe_new.argtypes = [_p_u8, _i64]
        lib.tb_bpe_new.restype = ctypes.c_void_p
        lib.tb_bpe_set_table.argtypes = [ctypes.c_void_p, _p_u8, _i64]
        lib.tb_bpe_set_table.restype = None
        lib.tb_bpe_free.argtypes = [ctypes.c_void_p]
        lib.tb_bpe_free.restype = None
        lib.tb_bpe_count.argtypes = [ctypes.c_void_p, _p_u8, _i64]
        lib.tb_bpe_count.restype = _i64
        _lib = lib
        return _lib


def available() -> bool:
    """True when the compiled library is (or can be) loaded."""
    return _load() is not None


# --- packing ----------------------------------------------------------------


def pack_utf8(
    data: np.ndarray, offsets: np.ndarray, max_len: int, batch_size: int
) -> Tuple[np.ndarray, np.ndarray]:
    """Decode ``n_docs`` UTF-8 documents (Arrow layout: ``data`` bytes +
    ``offsets``) into a zero-padded ``[batch_size, max_len] int32`` codepoint
    tensor.  Returns ``(cps, lengths)``; ``lengths[i] < 0`` flags an
    over-length document (row zeroed, magnitude = its codepoint count)."""
    lib = _load()
    assert lib is not None
    n_docs = offsets.shape[0] - 1
    assert n_docs <= batch_size
    cps = np.zeros((batch_size, max_len), dtype=np.int32)
    lengths = np.zeros(batch_size, dtype=np.int32)
    data = np.ascontiguousarray(data, dtype=np.uint8)
    offsets = np.ascontiguousarray(offsets, dtype=np.int64)
    if n_docs > 0:
        lib.tb_pack_utf8(data, offsets, n_docs, cps, lengths, max_len, max_len)
    return cps, lengths


def utf8_lengths(data: np.ndarray, offsets: np.ndarray) -> np.ndarray:
    """Codepoint count per document without decoding (for bucketing)."""
    lib = _load()
    assert lib is not None
    n_docs = offsets.shape[0] - 1
    out = np.zeros(n_docs, dtype=np.int32)
    data = np.ascontiguousarray(data, dtype=np.uint8)
    offsets = np.ascontiguousarray(offsets, dtype=np.int64)
    if n_docs > 0:
        lib.tb_utf8_lengths(data, offsets, n_docs, out)
    return out


# --- segmentation + duplicate scans ----------------------------------------


def word_spans_native(cps: np.ndarray, cls: np.ndarray) -> Optional[np.ndarray]:
    """Word (start, end) spans as an ``[n, 2] int32`` array, or ``None`` when
    the native library is unavailable.  Semantics identical to
    ``utils.text.word_spans``."""
    lib = _load()
    if lib is None:
        return None
    n = cps.shape[0]
    cps = np.ascontiguousarray(cps, dtype=np.int32)
    cls = np.ascontiguousarray(cls, dtype=np.uint8)
    max_spans = n + 1
    out = np.empty(2 * max_spans, dtype=np.int32)
    count = lib.tb_word_spans(cps, n, cls, out, max_spans)
    if count < 0:  # cannot happen (spans <= n), but keep the fallback seam
        return None
    return out[: 2 * count].reshape(-1, 2)


def dup_ngram_bytes(cps: np.ndarray, spans: np.ndarray, n: int) -> int:
    """find_all_duplicate over word spans (utils.text semantics)."""
    lib = _load()
    assert lib is not None
    cps = np.ascontiguousarray(cps, dtype=np.int32)
    spans = np.ascontiguousarray(spans.reshape(-1), dtype=np.int32)
    return int(lib.tb_dup_ngram_bytes(cps, spans, spans.shape[0] // 2, n))


def top_ngram_bytes(cps: np.ndarray, spans: np.ndarray, n: int) -> int:
    """find_top_duplicate over space-joined n-grams of the word spans."""
    lib = _load()
    assert lib is not None
    cps = np.ascontiguousarray(cps, dtype=np.int32)
    spans = np.ascontiguousarray(spans.reshape(-1), dtype=np.int32)
    return int(lib.tb_top_ngram_bytes(cps, spans, spans.shape[0] // 2, n))


def dup_items(cps: np.ndarray, spans: np.ndarray) -> Tuple[int, int]:
    """find_duplicates over item spans: (dup_elems, dup_utf8_bytes)."""
    lib = _load()
    assert lib is not None
    cps = np.ascontiguousarray(cps, dtype=np.int32)
    spans = np.ascontiguousarray(spans.reshape(-1), dtype=np.int32)
    elems = ctypes.c_int64(0)
    bytes_ = lib.tb_dup_items(
        cps, spans, spans.shape[0] // 2, ctypes.byref(elems)
    )
    return int(elems.value), int(bytes_)


# --- BPE --------------------------------------------------------------------


class BpeCounter:
    """Byte-level BPE token counter (GPT-2 family) over local merges.txt.

    The native analogue of the HF-tokenizers core used by TokenCounter
    (token_counter.rs:8-43 parity for token *counting* — ids are not needed
    for ``metadata["token_count"]``).
    """

    def __init__(self, merges_text: str) -> None:
        lib = _load()
        if lib is None:
            raise RuntimeError("native library unavailable")
        blob = np.frombuffer(merges_text.encode("utf-8"), dtype=np.uint8).copy()
        self._lib = lib
        self._handle = lib.tb_bpe_new(blob, blob.shape[0])
        from ..utils.chartables import char_table

        self._table = np.ascontiguousarray(char_table())
        lib.tb_bpe_set_table(self._handle, self._table, self._table.shape[0])

    @classmethod
    def from_file(cls, merges_path: str) -> "BpeCounter":
        with open(merges_path, encoding="utf-8") as f:
            return cls(f.read())

    def count(self, text: str) -> int:
        data = np.frombuffer(text.encode("utf-8"), dtype=np.uint8)
        if data.shape[0] == 0:
            return 0
        data = np.ascontiguousarray(data)
        return int(self._lib.tb_bpe_count(self._handle, data, data.shape[0]))

    def __del__(self) -> None:
        handle = getattr(self, "_handle", None)
        if handle:
            self._lib.tb_bpe_free(handle)
            self._handle = None
