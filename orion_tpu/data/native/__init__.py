"""ctypes bindings for the native (C++) token-shard reader.

Compiled on first use with g++ (no network, no pybind11 — plain C ABI +
ctypes, per the toolchain constraints) into the checkout's git-ignored
``.native_build/`` directory, under a name keyed on the SOURCE'S CONTENT: a
copied or freshly unpacked checkout preserves no trustworthy mtimes, and a
binary built from another revision of the source is simply a different file.
Callers treat ImportError/OSError as "native unavailable" and fall back to
the numpy memmap reader (orion_tpu.data.loader._open_reader).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "native_loader.cpp")
_BUILD_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(_DIR))), ".native_build"
)
_BUILD_LOCK = threading.Lock()


def _build() -> str:
    with _BUILD_LOCK:
        with open(_SRC, "rb") as f:
            digest = hashlib.sha256(f.read()).hexdigest()[:16]
        so = os.path.join(_BUILD_DIR, f"libnative_loader-{digest}.so")
        if os.path.exists(so):
            return so
        os.makedirs(_BUILD_DIR, exist_ok=True)
        tmp = so + f".tmp.{os.getpid()}"
        cmd = [
            "g++", "-O3", "-shared", "-fPIC", "-pthread", "-std=c++17",
            _SRC, "-o", tmp,
        ]
        try:
            subprocess.run(cmd, check=True, capture_output=True, text=True)
        except (subprocess.CalledProcessError, FileNotFoundError) as e:
            detail = getattr(e, "stderr", str(e))
            raise ImportError(f"native loader build failed: {detail}") from e
        os.replace(tmp, so)  # atomic: concurrent processes race safely
        return so


def _load() -> ctypes.CDLL:
    lib = ctypes.CDLL(_build())
    lib.otn_open.argtypes = [ctypes.c_char_p]
    lib.otn_open.restype = ctypes.c_void_p
    lib.otn_len_bytes.argtypes = [ctypes.c_void_p]
    lib.otn_len_bytes.restype = ctypes.c_longlong
    lib.otn_gather.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_longlong), ctypes.c_int,
        ctypes.c_int, ctypes.c_int, ctypes.c_void_p, ctypes.c_int,
    ]
    lib.otn_gather.restype = ctypes.c_int
    lib.otn_prefetch.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_longlong), ctypes.c_int,
        ctypes.c_int, ctypes.c_int,
    ]
    lib.otn_prefetch.restype = None
    lib.otn_close.argtypes = [ctypes.c_void_p]
    lib.otn_close.restype = None
    return lib


_lib: ctypes.CDLL | None = None


def _get_lib() -> ctypes.CDLL:
    global _lib
    if _lib is None:
        _lib = _load()
    return _lib


class NativeReader:
    """Reader over a flat token file: len() in elements, gather(), prefetch().

    Drop-in for the numpy reader in orion_tpu.data.loader, with a
    multithreaded native gather and MADV_WILLNEED readahead for the next
    (deterministic) batch.
    """

    def __init__(self, path: str, dtype: np.dtype, num_threads: int = 0):
        self._lib = _get_lib()
        self._h = self._lib.otn_open(os.fsencode(path))
        if not self._h:
            raise OSError(f"native loader could not open {path!r}")
        self.dtype = np.dtype(dtype)
        self.path = path
        self._nthreads = num_threads or min(8, os.cpu_count() or 1)

    def __len__(self) -> int:
        return self._lib.otn_len_bytes(self._h) // self.dtype.itemsize

    def _offsets_arg(self, offsets: np.ndarray):
        offs = np.ascontiguousarray(offsets, dtype=np.int64)
        return offs, offs.ctypes.data_as(ctypes.POINTER(ctypes.c_longlong))

    def gather(self, offsets: np.ndarray, width: int) -> np.ndarray:
        offs, ptr = self._offsets_arg(offsets)
        out = np.empty((len(offs), width), self.dtype)
        rc = self._lib.otn_gather(
            self._h, ptr, len(offs), width, self.dtype.itemsize,
            out.ctypes.data_as(ctypes.c_void_p), self._nthreads,
        )
        if rc != 0:
            raise IndexError(
                f"gather window out of bounds (file has {len(self)} tokens)"
            )
        return out

    def prefetch(self, offsets: np.ndarray, width: int) -> None:
        offs, ptr = self._offsets_arg(offsets)
        self._lib.otn_prefetch(
            self._h, ptr, len(offs), width, self.dtype.itemsize
        )

    def close(self) -> None:
        if self._h:
            self._lib.otn_close(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
