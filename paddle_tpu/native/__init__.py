"""ctypes bindings for the native C++ runtime (native/).

The reference crosses Python↔C++ at pybind (pybind/pybind.cc); here the
boundary is a stable C ABI (native/src/c_api.cc) loaded with ctypes — no
compiled Python extension needed, and the same .so serves the pure-C++
trainer path. Builds on demand with `make` if the .so is missing; every
consumer degrades to a pure-Python fallback when AVAILABLE is False.
"""
from __future__ import annotations

import ctypes
import os
import subprocess
import threading

import numpy as np

_NATIVE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "native")
_SO_PATH = os.path.join(_NATIVE_DIR, "libpaddle_tpu_native.so")

_lib = None
_lock = threading.Lock()


def _build():
    subprocess.run(["make", "-s"], cwd=_NATIVE_DIR, check=True,
                   capture_output=True)


def _load():
    global _lib
    with _lock:
        if _lib is not None:
            return _lib
        if not os.path.exists(_SO_PATH):
            _build()
        lib = ctypes.CDLL(_SO_PATH)
        # signatures
        lib.ptn_pool_create.restype = ctypes.c_void_p
        lib.ptn_pool_create.argtypes = [ctypes.c_uint64]
        lib.ptn_pool_destroy.argtypes = [ctypes.c_void_p]
        lib.ptn_pool_alloc.restype = ctypes.c_void_p
        lib.ptn_pool_alloc.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
        lib.ptn_pool_free.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
        lib.ptn_pool_stats.argtypes = [ctypes.c_void_p] + \
            [ctypes.POINTER(ctypes.c_uint64)] * 4
        lib.ptn_feed_create.restype = ctypes.c_void_p
        lib.ptn_feed_create.argtypes = [
            ctypes.c_int32, ctypes.POINTER(ctypes.c_char_p),
            ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int64, ctypes.c_int32, ctypes.c_int32]
        lib.ptn_feed_destroy.argtypes = [ctypes.c_void_p]
        lib.ptn_feed_add_file.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.ptn_feed_set_shuffle.argtypes = [ctypes.c_void_p,
                                             ctypes.c_int32,
                                             ctypes.c_uint64]
        lib.ptn_feed_start.argtypes = [ctypes.c_void_p, ctypes.c_int32]
        lib.ptn_feed_stop.argtypes = [ctypes.c_void_p]
        lib.ptn_feed_next.restype = ctypes.c_int64
        lib.ptn_feed_next.argtypes = [ctypes.c_void_p,
                                      ctypes.POINTER(ctypes.c_void_p),
                                      ctypes.POINTER(ctypes.c_int64)]
        lib.ptn_feed_samples_parsed.restype = ctypes.c_uint64
        lib.ptn_feed_samples_parsed.argtypes = [ctypes.c_void_p]
        lib.ptn_feed_parse_errors.restype = ctypes.c_uint64
        lib.ptn_feed_parse_errors.argtypes = [ctypes.c_void_p]
        lib.ptn_profiler_push.argtypes = [ctypes.c_char_p]
        lib.ptn_profiler_pop.argtypes = [ctypes.c_char_p]
        lib.ptn_profiler_dump.restype = ctypes.c_int
        lib.ptn_profiler_dump.argtypes = [ctypes.c_char_p]
        lib.ptn_version.restype = ctypes.c_char_p
        _lib = lib
        return lib


# AVAILABLE False = no toolchain or a failed build: consumers fall back
# to Python, and LOAD_ERROR says why (chip_smoke.py prints both).
LOAD_ERROR = None
try:
    _load()
    AVAILABLE = True
except (OSError, subprocess.CalledProcessError) as e:
    AVAILABLE = False
    LOAD_ERROR = f"{type(e).__name__}: {e}"


def version() -> str:
    return _load().ptn_version().decode()


class NativeDataFeed:
    """Multi-threaded MultiSlot-format file feeder (C++ parse + batch).

    Slots: list of (name, dtype, dim) with dtype in {"float32", "int64"}.
    Yields dict name -> np.ndarray [batch, dim]; `<name>.lens` holds the
    pre-pad value count per row (the LoD-metadata replacement).
    """

    def __init__(self, slots, batch_size, capacity=8, drop_last=False):
        self._lib = _load()
        self.slots = [(n, np.dtype(d), int(dim)) for n, d, dim in slots]
        self.batch_size = int(batch_size)
        names = (ctypes.c_char_p * len(slots))(
            *[n.encode() for n, _, _ in self.slots])
        types = (ctypes.c_int32 * len(slots))(
            *[0 if d == np.float32 else 1 for _, d, _ in self.slots])
        dims = (ctypes.c_int64 * len(slots))(
            *[dim for _, _, dim in self.slots])
        self._h = self._lib.ptn_feed_create(
            len(slots), names, types, dims, self.batch_size, capacity,
            1 if drop_last else 0)
        self._started = False

    def add_file(self, path):
        self._lib.ptn_feed_add_file(self._h, path.encode())

    def set_filelist(self, paths):
        for p in paths:
            self.add_file(p)

    def set_shuffle(self, on=True, seed=0):
        self._lib.ptn_feed_set_shuffle(self._h, 1 if on else 0, seed)

    def start(self, n_threads=4):
        self._lib.ptn_feed_start(self._h, n_threads)
        self._started = True

    def stop(self):
        if self._h:
            self._lib.ptn_feed_stop(self._h)
        self._started = False

    @property
    def samples_parsed(self):
        return self._lib.ptn_feed_samples_parsed(self._h)

    @property
    def parse_errors(self):
        return self._lib.ptn_feed_parse_errors(self._h)

    def __iter__(self):
        if not self._started:
            self.start()
        n = len(self.slots)
        while True:
            arrays = [np.zeros((self.batch_size, dim), dtype=d)
                      for _, d, dim in self.slots]
            bufs = (ctypes.c_void_p * n)(
                *[a.ctypes.data_as(ctypes.c_void_p).value for a in arrays])
            lens = np.zeros(n * self.batch_size, dtype=np.int64)
            bs = self._lib.ptn_feed_next(
                self._h, bufs,
                lens.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)))
            if bs == 0:
                self._started = False
                return
            out = {}
            for i, (name, _, _) in enumerate(self.slots):
                out[name] = arrays[i][:bs]
                out[name + ".lens"] = \
                    lens[i * self.batch_size:i * self.batch_size + bs]
            yield out

    def __del__(self):
        try:
            if getattr(self, "_h", None):
                self._lib.ptn_feed_destroy(self._h)
                self._h = None
        except Exception:
            pass


class profiler_scope:
    """RAII host-phase annotation recorded in the native profiler."""

    def __init__(self, name):
        self.name = name.encode()

    def __enter__(self):
        if AVAILABLE:
            _load().ptn_profiler_push(self.name)
        return self

    def __exit__(self, *exc):
        if AVAILABLE:
            _load().ptn_profiler_pop(self.name)
        return False


def profiler_enable():
    if AVAILABLE:
        _load().ptn_profiler_enable()


def profiler_disable():
    if AVAILABLE:
        _load().ptn_profiler_disable()


def profiler_reset():
    if AVAILABLE:
        _load().ptn_profiler_reset()


def profiler_dump(path) -> int:
    if AVAILABLE:
        return _load().ptn_profiler_dump(path.encode())
    return -1
