"""ctypes loader for the native data pipeline (libmv_data).

The library is optional: it is built on first use when a toolchain is
present, else callers fall back to the pure-Python/numpy implementations
(``available()`` reports which path is active, :func:`build_failure` why
a build did not produce a library). See mv_data.cpp for what lives here
and why.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import platform
import subprocess
import threading
from typing import Dict, List, Optional, Tuple

import numpy as np

from multiverso_tpu.utils import log

_DIR = os.path.dirname(os.path.abspath(__file__))
_lib = None
_lock = threading.Lock()
_build_failed = False

_CXX_FLAGS = ("-O3", "-std=c++17", "-fPIC", "-shared", "-march=native")
_build_failures: Dict[str, str] = {}


def _host_id() -> str:
    """What ``-march=native`` resolved against: this host's CPU model and
    instruction-set flags. A library built on another CPU may use
    instructions this one lacks, so the host is part of the build key."""
    try:
        with open("/proc/cpuinfo") as f:
            seen: Dict[str, str] = {}
            for line in f:
                key = line.split(":", 1)[0].strip()
                if key in ("model name", "flags", "Features"):
                    seen.setdefault(key, line)
        if seen:
            return "".join(seen[k] for k in sorted(seen))
    except OSError:
        pass
    return platform.machine() + platform.processor()


def artefact_path(so_name: str, src_name: str,
                  extra_flags: Tuple[str, ...] = ()) -> str:
    """Where the build of ``native/<src_name>`` for THIS source, compiler,
    flag set and host lives: ``native/<stem>.<key>.so``. The key in the
    name is the stamp — a library whose name does not carry the current
    key (a stale build, one copied from another machine, one made by
    hand) is never loaded."""
    h = hashlib.sha256()
    with open(os.path.join(_DIR, src_name), "rb") as f:
        h.update(f.read())
    h.update("\0".join((os.environ.get("CXX", "g++"), *_CXX_FLAGS,
                        *extra_flags, _host_id())).encode())
    return os.path.join(
        _DIR, f"{so_name.removesuffix('.so')}.{h.hexdigest()[:16]}.so")


def build_failure(so_name: str) -> Optional[str]:
    """Why the last build of ``so_name`` produced no library (None when it
    did, or was never attempted)."""
    return _build_failures.get(so_name)


def build_and_load(so_name: str, src_name: str,
                   extra_flags: Tuple[str, ...] = (),
                   timeout: int = 180) -> Optional[ctypes.CDLL]:
    """Build ``native/<src_name>`` into :func:`artefact_path` unless that
    exact build already exists (atomic rename so concurrent workers never
    load a half-written .so), then CDLL it. One implementation for every
    native helper's build-on-first-use path (this module and ps/native).
    Returns None when no loadable library resulted; the reason — with the
    compiler's stderr when it ran — is logged and kept for
    :func:`build_failure`."""
    so = artefact_path(so_name, src_name, extra_flags)
    if not os.path.exists(so):
        tmp = f"{so}.build.{os.getpid()}"
        cmd = [os.environ.get("CXX", "g++"), *_CXX_FLAGS, *extra_flags,
               "-o", tmp, os.path.join(_DIR, src_name)]
        try:
            subprocess.run(cmd, check=True, capture_output=True, text=True,
                           timeout=timeout)
            os.replace(tmp, so)
        except FileNotFoundError:
            _build_failures[so_name] = f"no C++ compiler ({cmd[0]!r})"
            log.info("native %s not built: %s; the Python plane serves",
                     so_name, _build_failures[so_name])
            return None
        except (subprocess.SubprocessError, OSError) as e:
            stderr = getattr(e, "stderr", None) or ""
            _build_failures[so_name] = f"{e}\n{stderr[-2000:]}".strip()
            log.error("native %s BUILD FAILED; the Python plane serves: %s",
                      so_name, _build_failures[so_name])
            return None
        finally:
            if os.path.exists(tmp):
                os.remove(tmp)
    try:
        lib = ctypes.CDLL(so)
    except OSError as e:
        _build_failures[so_name] = f"built library failed to load: {e}"
        log.error("native %s: %s", so_name, _build_failures[so_name])
        return None
    _build_failures.pop(so_name, None)
    return lib


def _try_load() -> Optional[ctypes.CDLL]:
    global _lib, _build_failed
    with _lock:
        if _lib is not None:
            return _lib
        if _build_failed:
            return None
        lib = build_and_load("libmv_data.so", "mv_data.cpp")
        if lib is None:
            _build_failed = True
            return None
        c_i64, c_i32, c_u64, c_dbl = (ctypes.c_int64, ctypes.c_int32,
                                      ctypes.c_uint64, ctypes.c_double)
        p = ctypes.POINTER
        lib.mv_corpus_load.restype = ctypes.c_void_p
        lib.mv_corpus_load.argtypes = [ctypes.c_char_p, c_i64, c_i64]
        lib.mv_corpus_free.argtypes = [ctypes.c_void_p]
        lib.mv_corpus_vocab_size.restype = c_i64
        lib.mv_corpus_vocab_size.argtypes = [ctypes.c_void_p]
        lib.mv_corpus_size.restype = c_i64
        lib.mv_corpus_size.argtypes = [ctypes.c_void_p]
        lib.mv_corpus_total_tokens.restype = c_i64
        lib.mv_corpus_total_tokens.argtypes = [ctypes.c_void_p]
        lib.mv_corpus_counts.argtypes = [ctypes.c_void_p, p(c_i64)]
        lib.mv_corpus_ids.argtypes = [ctypes.c_void_p, p(c_i32)]
        lib.mv_corpus_word.restype = ctypes.c_char_p
        lib.mv_corpus_word.argtypes = [ctypes.c_void_p, c_i64]
        lib.mv_subsample.restype = c_i64
        lib.mv_subsample.argtypes = [p(c_i32), c_i64, p(c_i64), c_i64,
                                     c_dbl, c_u64, p(c_i32)]
        lib.mv_generate_pairs.restype = c_i64
        lib.mv_generate_pairs.argtypes = [p(c_i32), c_i64, c_i32, c_u64,
                                          c_i32, p(c_i32), p(c_i32)]
        lib.mv_parse_libsvm_line.restype = c_i32
        lib.mv_parse_libsvm_line.argtypes = [ctypes.c_char_p, c_i64,
                                             p(ctypes.c_float), c_i64]
        _lib = lib
        return _lib


def available() -> bool:
    return _try_load() is not None


class NativeCorpus:
    """Opaque handle over mv_corpus_load: tokenized, pruned, encoded corpus."""

    def __init__(self, path: str, min_count: int = 5,
                 max_vocab: Optional[int] = None):
        lib = _try_load()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        self._h = lib.mv_corpus_load(path.encode(), min_count,
                                     max_vocab or 0)
        if not self._h:
            raise IOError(f"mv_corpus_load failed for {path!r}")

    def __del__(self):
        if getattr(self, "_h", None):
            self._lib.mv_corpus_free(self._h)
            self._h = None

    @property
    def vocab_size(self) -> int:
        return self._lib.mv_corpus_vocab_size(self._h)

    @property
    def total_tokens(self) -> int:
        return self._lib.mv_corpus_total_tokens(self._h)

    def counts(self) -> np.ndarray:
        out = np.zeros(self.vocab_size, dtype=np.int64)
        self._lib.mv_corpus_counts(
            self._h, out.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)))
        return out

    def ids(self) -> np.ndarray:
        out = np.zeros(self._lib.mv_corpus_size(self._h), dtype=np.int32)
        self._lib.mv_corpus_ids(
            self._h, out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
        return out

    def words(self) -> List[str]:
        return [self._lib.mv_corpus_word(self._h, i).decode()
                for i in range(self.vocab_size)]


def subsample(ids: np.ndarray, counts: np.ndarray, t: float = 1e-4,
              seed: int = 0) -> np.ndarray:
    lib = _try_load()
    if lib is None:
        raise RuntimeError("native library unavailable")
    ids = np.ascontiguousarray(ids, dtype=np.int32)
    counts = np.ascontiguousarray(counts, dtype=np.int64)
    out = np.zeros(ids.size, dtype=np.int32)
    i32p, i64p = (ctypes.POINTER(ctypes.c_int32),
                  ctypes.POINTER(ctypes.c_int64))
    m = lib.mv_subsample(ids.ctypes.data_as(i32p), ids.size,
                         counts.ctypes.data_as(i64p), counts.size,
                         t, seed, out.ctypes.data_as(i32p))
    return out[:m].copy()


def generate_pairs(ids: np.ndarray, window: int, seed: int = 0,
                   dynamic: bool = True) -> Tuple[np.ndarray, np.ndarray]:
    lib = _try_load()
    if lib is None:
        raise RuntimeError("native library unavailable")
    ids = np.ascontiguousarray(ids, dtype=np.int32)
    cap = 2 * window * max(ids.size, 1)
    centers = np.zeros(cap, dtype=np.int32)
    contexts = np.zeros(cap, dtype=np.int32)
    i32p = ctypes.POINTER(ctypes.c_int32)
    m = lib.mv_generate_pairs(ids.ctypes.data_as(i32p), ids.size, window,
                              seed, 1 if dynamic else 0,
                              centers.ctypes.data_as(i32p),
                              contexts.ctypes.data_as(i32p))
    return centers[:m].copy(), contexts[:m].copy()


def parse_libsvm_line(line: bytes, dim: int) -> Optional[Tuple[int, np.ndarray]]:
    lib = _try_load()
    if lib is None:
        raise RuntimeError("native library unavailable")
    x = np.zeros(dim, dtype=np.float32)
    label = lib.mv_parse_libsvm_line(
        line, len(line), x.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        dim)
    if label == -(1 << 31):
        return None
    return label, x
