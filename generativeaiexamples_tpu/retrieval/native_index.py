"""ctypes bindings for the native C++ ANN index (native/vecindex.cpp).

The reference gets native ANN from external FAISS/Milvus binaries
(reference: common/utils.py:85,196-217); this module owns the in-repo
equivalent: a flat/IVF-flat C++ library compiled on first use with the
system toolchain and loaded via ctypes (no pybind11 in this image). If
the toolchain is unavailable the caller falls back to the numpy/TPU
matmul path (retrieval/tpu_store.py), so serving never hard-depends on
a compiler.
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Optional, Tuple

import numpy as np

from generativeaiexamples_tpu.utils import get_logger

logger = get_logger(__name__)

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_NATIVE_DIR = os.path.join(_REPO_ROOT, "native")
_SRC_PATH = os.path.join(_NATIVE_DIR, "vecindex.cpp")
# No -march=native: the build directory is gitignored but travels with
# copies of the tree, and a library tuned to the build host's CPU can
# SIGILL on the next one. The artefact's NAME carries a digest of the
# source and the flags, so a stale or foreign-flag build is never
# loaded — mtimes do not survive a copy and are not consulted.
_CXXFLAGS = ("-O3", "-ffast-math", "-fPIC", "-shared", "-std=c++17")

_BUILD_LOCK = threading.Lock()
_LIB: Optional[ctypes.CDLL] = None

METRIC_IP = 0
METRIC_L2 = 1


class NativeUnavailable(RuntimeError):
    pass


def _so_path() -> str:
    h = hashlib.sha256(" ".join(_CXXFLAGS).encode())
    with open(_SRC_PATH, "rb") as fh:
        h.update(fh.read())
    return os.path.join(
        _NATIVE_DIR, "build", f"libvecindex-{h.hexdigest()[:16]}.so"
    )


def ensure_built() -> str:
    """Compile the shared library unless the artefact for exactly this
    source and these flags exists; returns its path."""
    with _BUILD_LOCK:
        if not os.path.exists(_SRC_PATH):
            raise NativeUnavailable(f"missing source {_SRC_PATH}")
        so_path = _so_path()
        if not os.path.exists(so_path):
            os.makedirs(os.path.dirname(so_path), exist_ok=True)
            # Build beside the target and rename: a concurrent process
            # never loads a half-written library.
            tmp_path = f"{so_path}.{os.getpid()}.tmp"
            cmd = [
                os.environ.get("CXX", "g++"),
                *_CXXFLAGS,
                "-o",
                tmp_path,
                _SRC_PATH,
            ]
            logger.info("Building native vecindex: %s", " ".join(cmd))
            try:
                subprocess.run(cmd, check=True, capture_output=True, timeout=300)
                os.replace(tmp_path, so_path)
            except (subprocess.CalledProcessError, FileNotFoundError, subprocess.TimeoutExpired) as exc:
                detail = getattr(exc, "stderr", b"")
                raise NativeUnavailable(
                    f"native build failed: {exc}: {detail[:500] if detail else ''}"
                ) from exc
    return so_path


def _load_lib() -> ctypes.CDLL:
    global _LIB
    if _LIB is not None:
        return _LIB
    path = ensure_built()
    lib = ctypes.CDLL(path)
    c = ctypes
    lib.vi_create.restype = c.c_void_p
    lib.vi_create.argtypes = [c.c_int, c.c_int, c.c_int]
    lib.vi_free.argtypes = [c.c_void_p]
    lib.vi_is_trained.restype = c.c_int
    lib.vi_is_trained.argtypes = [c.c_void_p]
    lib.vi_count.restype = c.c_int64
    lib.vi_count.argtypes = [c.c_void_p]
    lib.vi_dim.restype = c.c_int
    lib.vi_dim.argtypes = [c.c_void_p]
    lib.vi_train.argtypes = [
        c.c_void_p,
        c.POINTER(c.c_float),
        c.c_int64,
        c.c_int,
        c.c_uint64,
    ]
    lib.vi_add.restype = c.c_int64
    lib.vi_add.argtypes = [c.c_void_p, c.POINTER(c.c_float), c.c_int64]
    lib.vi_search.argtypes = [
        c.c_void_p,
        c.POINTER(c.c_float),
        c.c_int64,
        c.c_int,
        c.c_int,
        c.POINTER(c.c_float),
        c.POINTER(c.c_int64),
    ]
    lib.vi_remove.restype = c.c_int64
    lib.vi_remove.argtypes = [c.c_void_p, c.POINTER(c.c_int64), c.c_int64]
    lib.vi_save.restype = c.c_int
    lib.vi_save.argtypes = [c.c_void_p, c.c_char_p]
    lib.vi_load.restype = c.c_void_p
    lib.vi_load.argtypes = [c.c_char_p]
    _LIB = lib
    return lib


def available() -> bool:
    try:
        _load_lib()
        return True
    except NativeUnavailable:
        return False


def _fptr(arr: np.ndarray):
    return arr.ctypes.data_as(ctypes.POINTER(ctypes.c_float))


class NativeIndex:
    """Flat (nlist=0) or IVF-flat ANN index backed by the C++ library."""

    def __init__(self, dim: int, metric: int = METRIC_IP, nlist: int = 0,
                 _handle: Optional[int] = None):
        self._lib = _load_lib()
        self.dim = dim
        self.metric = metric
        self.nlist = nlist
        self._handle = _handle if _handle is not None else self._lib.vi_create(
            dim, metric, nlist
        )
        self._lock = threading.Lock()

    # -- lifecycle -------------------------------------------------------
    def close(self) -> None:
        with self._lock:
            if self._handle:
                self._lib.vi_free(self._handle)
                self._handle = None

    def __del__(self):  # best effort
        try:
            self.close()
        except Exception:  # noqa: BLE001
            pass

    # -- ops -------------------------------------------------------------
    @property
    def is_trained(self) -> bool:
        return bool(self._lib.vi_is_trained(self._handle))

    def __len__(self) -> int:
        return int(self._lib.vi_count(self._handle))

    def train(self, vectors: np.ndarray, iters: int = 10, seed: int = 1234) -> None:
        vectors = np.ascontiguousarray(vectors, np.float32)
        with self._lock:
            self._lib.vi_train(
                self._handle, _fptr(vectors), vectors.shape[0], iters, seed
            )

    def add(self, vectors: np.ndarray) -> int:
        """Append rows; returns the first assigned sequential id."""
        vectors = np.ascontiguousarray(vectors, np.float32)
        if vectors.ndim != 2 or vectors.shape[1] != self.dim:
            raise ValueError(f"expected [N, {self.dim}], got {vectors.shape}")
        with self._lock:
            first = self._lib.vi_add(self._handle, _fptr(vectors), vectors.shape[0])
        if first < 0:
            raise RuntimeError("index not trained (IVF requires train() before add())")
        return int(first)

    def search(
        self, queries: np.ndarray, k: int, nprobe: int = 8
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Returns (scores [Q, k], ids [Q, k]); missing slots get id -1."""
        queries = np.ascontiguousarray(queries, np.float32)
        if queries.ndim == 1:
            queries = queries[None, :]
        nq = queries.shape[0]
        scores = np.empty((nq, k), np.float32)
        ids = np.empty((nq, k), np.int64)
        with self._lock:
            self._lib.vi_search(
                self._handle,
                _fptr(queries),
                nq,
                k,
                nprobe,
                _fptr(scores),
                ids.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            )
        return scores, ids

    def remove(self, ids) -> int:
        arr = np.ascontiguousarray(ids, np.int64)
        with self._lock:
            return int(
                self._lib.vi_remove(
                    self._handle,
                    arr.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
                    arr.shape[0],
                )
            )

    def save(self, path: str) -> None:
        with self._lock:
            rc = self._lib.vi_save(self._handle, path.encode())
        if rc != 0:
            raise IOError(f"failed to save index to {path}")

    @classmethod
    def load(cls, path: str) -> "NativeIndex":
        lib = _load_lib()
        handle = lib.vi_load(path.encode())
        if not handle:
            raise IOError(f"failed to load index from {path}")
        idx = cls.__new__(cls)
        idx._lib = lib
        idx._handle = handle
        idx.dim = int(lib.vi_dim(handle))
        idx.metric = METRIC_IP
        idx.nlist = 0
        idx._lock = threading.Lock()
        return idx
