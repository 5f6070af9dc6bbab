"""ctypes bindings to the framework's native C++ engines (native/*.cpp).

Reference parity: the reference's non-matmul native components are Rust
(tantivy text index, connectors); here they are C++ behind a C ABI. Where
the toolchain cannot build one, callers fall back to the pure-Python
engine (the reference the tests compare against) — and the loader says so
once, at WARNING, naming the build error: the fallback is an order of
magnitude slower and must not pass for the native path."""

from __future__ import annotations

import ctypes
import logging
import threading
from typing import Any

from pathway_tpu.native.build import NativeBuildError, ensure_built

log = logging.getLogger("pathway_tpu.native")

_text_index_lib = None
_text_index_err: Exception | None = None
_load_lock = threading.Lock()


def _load_text_index():
    global _text_index_lib, _text_index_err
    if _text_index_lib is not None or _text_index_err is not None:
        return _text_index_lib
    with _load_lock:
        if _text_index_lib is not None or _text_index_err is not None:
            return _text_index_lib
        try:
            lib = ctypes.CDLL(ensure_built("text_index"))
        except (NativeBuildError, OSError) as e:  # no toolchain, ro fs, …
            _text_index_err = e
            log.warning("native text index unavailable, using the "
                        "pure-Python BM25 engine: %s", e)
            return None
        lib.ti_new.restype = ctypes.c_void_p
        lib.ti_new.argtypes = [ctypes.c_double, ctypes.c_double,
                               ctypes.c_int32, ctypes.c_int32]
        lib.ti_free.argtypes = [ctypes.c_void_p]
        lib.ti_add.argtypes = [ctypes.c_void_p, ctypes.c_uint64,
                               ctypes.c_uint64, ctypes.c_uint64,
                               ctypes.c_char_p]
        lib.ti_remove.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
        lib.ti_len.restype = ctypes.c_uint64
        lib.ti_len.argtypes = [ctypes.c_void_p]
        lib.ti_search.restype = ctypes.c_int32
        lib.ti_search.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int32,
            ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(ctypes.c_double)]
        lib.ti_save_size.restype = ctypes.c_int64
        lib.ti_save_size.argtypes = [ctypes.c_void_p]
        lib.ti_save.restype = ctypes.c_int64
        lib.ti_save.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                ctypes.c_int64]
        lib.ti_load.restype = ctypes.c_void_p
        lib.ti_load.argtypes = [ctypes.c_char_p, ctypes.c_int64]
        _text_index_lib = lib
        return lib


def text_index_available() -> bool:
    return _load_text_index() is not None


_wordpiece_lib = None
_wordpiece_err: Exception | None = None


def _load_wordpiece():
    global _wordpiece_lib, _wordpiece_err
    if _wordpiece_lib is not None or _wordpiece_err is not None:
        return _wordpiece_lib
    with _load_lock:
        if _wordpiece_lib is not None or _wordpiece_err is not None:
            return _wordpiece_lib
        try:
            lib = ctypes.CDLL(ensure_built("wordpiece"))
        except (NativeBuildError, OSError) as e:
            _wordpiece_err = e
            log.warning("native WordPiece unavailable, using the "
                        "pure-Python tokenizer: %s", e)
            return None
        lib.wp_new.restype = ctypes.c_void_p
        lib.wp_new.argtypes = [ctypes.c_char_p, ctypes.c_int64,
                               ctypes.c_int32]
        lib.wp_free.argtypes = [ctypes.c_void_p]
        lib.wp_encode_batch.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_int64), ctypes.c_int32, ctypes.c_int32,
            ctypes.c_int32, ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
            ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32)]
        _wordpiece_lib = lib
        return lib


def wordpiece_available() -> bool:
    return _load_wordpiece() is not None


class NativeWordPiece:
    """Batch WordPiece tokenizer over the C++ engine (native/wordpiece.cpp).
    One C call per batch; ids match the pure-Python reference
    implementation in pathway_tpu/models/tokenizer.py."""

    def __init__(self, vocab: list[str], do_lower: bool = True):
        lib = _load_wordpiece()
        if lib is None:
            raise NativeBuildError(
                f"native wordpiece unavailable: {_wordpiece_err}")
        self._lib = lib
        blob = "\n".join(vocab).encode("utf-8")
        self._h = lib.wp_new(blob, len(blob), 1 if do_lower else 0)

    def __del__(self):
        lib = getattr(self, "_lib", None)
        h = getattr(self, "_h", None)
        if lib is not None and h:
            lib.wp_free(h)
            self._h = None

    def encode_batch(self, texts: list[bytes], max_len: int, cls_id: int,
                     sep_id: int, unk_id: int, pad_id: int):
        import numpy as np

        n = len(texts)
        offsets = np.zeros(n + 1, dtype=np.int64)
        for i, t in enumerate(texts):
            offsets[i + 1] = offsets[i] + len(t)
        blob = b"".join(texts)
        out_ids = np.empty((n, max_len), dtype=np.int32)
        out_lens = np.empty(n, dtype=np.int32)
        self._lib.wp_encode_batch(
            self._h, blob, offsets.ctypes.data_as(
                ctypes.POINTER(ctypes.c_int64)), n, max_len,
            cls_id, sep_id, unk_id, pad_id,
            out_ids.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            out_lens.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
        return out_ids, out_lens


class NativeTextIndex:
    """Thin RAII wrapper over the C++ BM25 engine (u64 doc ids).

    ``lowercase`` / ``stem`` configure the tokenizer pipeline (the
    reference's tantivy tokenizer options: raw vs lowercased vs en_stem);
    ``save_bytes``/``load_bytes`` round-trip the index for on-disk
    persistence."""

    def __init__(self, k1: float = 1.2, b: float = 0.75, *,
                 lowercase: bool = True, stem: bool = False):
        lib = _load_text_index()
        if lib is None:
            raise NativeBuildError(
                f"native text index unavailable: {_text_index_err}")
        self._lib = lib
        self._h = lib.ti_new(k1, b, 1 if lowercase else 0,
                             1 if stem else 0)

    def __del__(self):
        lib = getattr(self, "_lib", None)
        h = getattr(self, "_h", None)
        if lib is not None and h:
            lib.ti_free(h)
            self._h = None

    def add(self, doc_id: int, text: str,
            tie_hi: int = 0, tie_lo: int = 0) -> None:
        # (tie_hi, tie_lo) = the engine Pointer's 128 bits; equal-score
        # hits rank by it so native and Python BM25 engines agree
        self._lib.ti_add(self._h, doc_id, tie_hi, tie_lo, text.encode())

    def remove(self, doc_id: int) -> None:
        self._lib.ti_remove(self._h, doc_id)

    def __len__(self) -> int:
        return int(self._lib.ti_len(self._h))

    def search(self, query: str, k: int) -> list[tuple[int, float]]:
        ids = (ctypes.c_uint64 * k)()
        scores = (ctypes.c_double * k)()
        n = self._lib.ti_search(self._h, query.encode(), k, ids, scores)
        return [(int(ids[i]), float(scores[i])) for i in range(n)]

    def save_bytes(self) -> bytes:
        size = int(self._lib.ti_save_size(self._h))
        buf = ctypes.create_string_buffer(size)
        written = int(self._lib.ti_save(self._h, buf, size))
        if written < 0:
            raise RuntimeError("text index save failed")
        return buf.raw[:written]

    @classmethod
    def load_bytes(cls, blob: bytes) -> "NativeTextIndex":
        lib = _load_text_index()
        if lib is None:
            raise NativeBuildError(
                f"native text index unavailable: {_text_index_err}")
        h = lib.ti_load(blob, len(blob))
        if not h:
            raise RuntimeError("text index load failed: corrupt buffer")
        self = cls.__new__(cls)
        self._lib = lib
        self._h = h
        return self
