"""ctypes bindings for the native host runtime (libdeeprec_host.so).

Native C++ is the right tool for the host-side KV store backing multi-tier
embedding storage (DeepRec keeps this layer in C++ too — SURVEY.md §2.1). The
library is built by `make` from the tracked sources on the machine that loads
it — every process goes through `make` once (a no-op when the binary is
current), so a binary that does not match the sources is never what runs. A
pure-numpy fallback keeps the framework functional where the build fails
(behavior-identical, slower); the failure is reported once, with the
compiler's output.
"""
from __future__ import annotations

import ctypes
import os
import subprocess
import threading
import warnings
from typing import Optional, Tuple

import numpy as np

from deeprec_tpu.analysis.annotations import not_thread_safe

_DIR = os.path.dirname(os.path.abspath(__file__))
_SO = os.path.join(_DIR, "libdeeprec_host.so")
_lib: Optional[ctypes.CDLL] = None
_loaded = False
_load_lock = threading.Lock()


def _build_and_load() -> Optional[ctypes.CDLL]:
    try:
        subprocess.run(
            ["make", "-s"], cwd=_DIR, check=True, capture_output=True,
            text=True, timeout=120,
        )
        lib = ctypes.CDLL(_SO)
    except (OSError, subprocess.SubprocessError) as e:
        warnings.warn(
            "deeprec_tpu.native: building libdeeprec_host.so failed, the "
            "numpy fallback takes over (same results, slower): "
            f"{e}\n{getattr(e, 'stderr', None) or ''}",
            RuntimeWarning, stacklevel=3,
        )
        return None
    _configure(lib)
    return lib


def load_library() -> Optional[ctypes.CDLL]:
    """The host library, or None where it cannot be built (the callers'
    numpy path). Builds at most once per process."""
    global _lib, _loaded
    with _load_lock:
        if not _loaded:
            _lib = _build_and_load()
            _loaded = True
    return _lib


def _configure(lib):
    u64, i64p, f32p, i32p, u8p = (
        ctypes.c_uint64,
        np.ctypeslib.ndpointer(np.int64, flags="C"),
        np.ctypeslib.ndpointer(np.float32, flags="C"),
        np.ctypeslib.ndpointer(np.int32, flags="C"),
        np.ctypeslib.ndpointer(np.uint8, flags="C"),
    )
    lib.hkv_create.restype = ctypes.c_void_p
    lib.hkv_create.argtypes = [ctypes.c_int, u64]
    lib.hkv_destroy.argtypes = [ctypes.c_void_p]
    lib.hkv_size.restype = u64
    lib.hkv_size.argtypes = [ctypes.c_void_p]
    lib.hkv_put_batch.argtypes = [ctypes.c_void_p, u64, i64p, f32p, i32p, i32p]
    lib.hkv_get_batch.argtypes = [ctypes.c_void_p, u64, i64p, f32p, i32p, i32p, u8p]
    lib.hkv_erase_batch.argtypes = [ctypes.c_void_p, u64, i64p]
    lib.hkv_export.argtypes = [ctypes.c_void_p, i64p, f32p, i32p, i32p]
    lib.hkv_save.restype = ctypes.c_int
    lib.hkv_save.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.hkv_load.restype = ctypes.c_int
    lib.hkv_load.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    if hasattr(lib, "criteo_parse"):
        lib.criteo_parse.restype = ctypes.c_int64
        lib.criteo_parse.argtypes = [
            ctypes.c_char_p, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int, ctypes.c_int,
            np.ctypeslib.ndpointer(np.float32, flags="C"),
            np.ctypeslib.ndpointer(np.float32, flags="C"),
            np.ctypeslib.ndpointer(np.int32, flags="C"),
            ctypes.POINTER(ctypes.c_int64),
        ]
    if hasattr(lib, "criteo_parse_mt"):
        lib.criteo_parse_mt.restype = ctypes.c_int64
        lib.criteo_parse_mt.argtypes = [
            ctypes.c_char_p, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int, ctypes.c_int, ctypes.c_int,
            np.ctypeslib.ndpointer(np.float32, flags="C"),
            np.ctypeslib.ndpointer(np.float32, flags="C"),
            np.ctypeslib.ndpointer(np.int32, flags="C"),
            ctypes.POINTER(ctypes.c_int64),
        ]


@not_thread_safe
class HostKV:
    """int64 key -> (float32[dim] value, freq, version) host store.

    Native-backed when the .so is available; numpy-dict fallback otherwise.

    NOT thread-safe (neither backend is): the multi-tier choreography
    serializes every access behind MultiTierTable._settle() — background
    rounds own the store exclusively while running. DRT004 (the static
    analyzer) flags any new cross-thread access path.
    """

    def __init__(self, dim: int, initial_capacity: int = 1 << 16):
        self.dim = dim
        self._lib = load_library()
        if self._lib is not None:
            self._h = self._lib.hkv_create(dim, initial_capacity)
            self._fallback = None
        else:
            self._h = None
            self._fallback = {}

    @property
    def native(self) -> bool:
        return self._h is not None

    def __len__(self) -> int:
        if self.native:
            return int(self._lib.hkv_size(self._h))
        return len(self._fallback)

    def put(self, keys, values, freqs=None, versions=None) -> None:
        keys = np.ascontiguousarray(keys, np.int64)
        values = np.ascontiguousarray(values, np.float32).reshape(len(keys), self.dim)
        freqs = np.ascontiguousarray(
            freqs if freqs is not None else np.zeros(len(keys)), np.int32
        )
        versions = np.ascontiguousarray(
            versions if versions is not None else np.full(len(keys), -1), np.int32
        )
        if self.native:
            self._lib.hkv_put_batch(self._h, len(keys), keys, values, freqs, versions)
        else:
            for i, k in enumerate(keys):
                self._fallback[int(k)] = (
                    values[i].copy(), int(freqs[i]), int(versions[i])
                )

    def get(self, keys) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
        """-> (values [n, dim], freqs [n], versions [n], found [n] bool)"""
        keys = np.ascontiguousarray(keys, np.int64)
        n = len(keys)
        values = np.zeros((n, self.dim), np.float32)
        freqs = np.zeros(n, np.int32)
        versions = np.full(n, -1, np.int32)
        found = np.zeros(n, np.uint8)
        if self.native:
            self._lib.hkv_get_batch(self._h, n, keys, values, freqs, versions, found)
        else:
            for i, k in enumerate(keys):
                hit = self._fallback.get(int(k))
                if hit is not None:
                    values[i], freqs[i], versions[i] = hit
                    found[i] = 1
        return values, freqs, versions, found.astype(bool)

    def erase(self, keys) -> None:
        keys = np.ascontiguousarray(keys, np.int64)
        if self.native:
            self._lib.hkv_erase_batch(self._h, len(keys), keys)
        else:
            for k in keys:
                self._fallback.pop(int(k), None)

    def export(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
        n = len(self)
        keys = np.zeros(n, np.int64)
        values = np.zeros((n, self.dim), np.float32)
        freqs = np.zeros(n, np.int32)
        versions = np.zeros(n, np.int32)
        if self.native:
            self._lib.hkv_export(self._h, keys, values, freqs, versions)
        else:
            for i, (k, (v, f, ver)) in enumerate(self._fallback.items()):
                keys[i], values[i], freqs[i], versions[i] = k, v, f, ver
        return keys, values, freqs, versions

    def save(self, path: str) -> None:
        if self.native:
            rc = self._lib.hkv_save(self._h, path.encode())
            if rc != 0:
                raise IOError(f"hkv_save({path}) failed rc={rc}")
        else:
            k, v, f, ver = self.export()
            np.savez(path, keys=k, values=v, freqs=f, versions=ver)

    def load(self, path: str) -> None:
        if self.native:
            rc = self._lib.hkv_load(self._h, path.encode())
            if rc != 0:
                raise IOError(f"hkv_load({path}) failed rc={rc}")
        else:
            d = np.load(path if path.endswith(".npz") else path + ".npz")
            self.put(d["keys"], d["values"], d["freqs"], d["versions"])

    def __del__(self):
        if getattr(self, "_h", None) is not None and self._lib is not None:
            try:
                self._lib.hkv_destroy(self._h)
            except Exception:
                pass


def criteo_parse_native(
    buf: bytes, max_rows: int, num_dense: int = 13, num_cat: int = 26,
    threads: int = 0,
):
    """Parse Criteo TSV bytes with the native parser (multi-threaded when
    the library exports criteo_parse_mt; threads=0 picks the hardware
    count, threads=1 forces the single-thread path).

    Returns (rows, labels, dense, cats, consumed_bytes) or None when the
    native library is unavailable. The id hashing matches
    data/readers._hash_strings exactly, so outputs are interchangeable.
    """
    lib = load_library()
    if lib is None or not hasattr(lib, "criteo_parse"):
        return None
    labels = np.zeros(max_rows, np.float32)
    dense = np.zeros((max_rows, num_dense), np.float32)
    cats = np.zeros((max_rows, num_cat), np.int32)
    consumed = ctypes.c_int64(0)
    if threads != 1 and hasattr(lib, "criteo_parse_mt"):
        rows = lib.criteo_parse_mt(
            buf, len(buf), max_rows, num_dense, num_cat, threads, labels,
            dense.reshape(-1), cats.reshape(-1), ctypes.byref(consumed),
        )
    else:
        rows = lib.criteo_parse(
            buf, len(buf), max_rows, num_dense, num_cat, labels,
            dense.reshape(-1), cats.reshape(-1), ctypes.byref(consumed),
        )
    return int(rows), labels, dense, cats, int(consumed.value)
