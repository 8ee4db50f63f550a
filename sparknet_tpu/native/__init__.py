"""ctypes bindings for the native data plane (libsparknet_native.so).

The framework's native components (ref: SURVEY §2.2 — the reference keeps
its db layer and data transformer in C++; ours live in
``native/sparknet_native.cpp``):

- :class:`RecordDB` — append-only key/value record file with committed-
  snapshot cursors (role of Caffe's LMDB/LevelDB abstraction +
  libccaffe's create_db/write_to_db/commit_db_txn).
- :func:`transform_batch` — multithreaded uint8→float32 crop/mirror/mean
  augmenter (role of data_transformer.cpp's per-sample hot loop).

``build()`` compiles the .so with the in-tree Makefile under a name keyed
on the tracked sources and on this machine, so the library loaded is
always one this machine built from ``native/sparknet_native.cpp``;
``available()`` gates callers so pure-Python paths keep working without a
toolchain.
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import platform
import subprocess
import threading

import numpy as np

_NATIVE_DIR = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", "native"))

_lib = None
_lock = threading.Lock()


def _so_path() -> str:
    """Where this machine's build of the current sources lives.  The
    Makefile compiles with ``-march=native`` and ``*.so`` is untracked,
    so a copied checkout can carry a binary built for another CPU or
    from older sources; keying the name on both means such a file is
    never the one loaded."""
    h = hashlib.sha256()
    for name in ("sparknet_native.cpp", "Makefile"):
        with open(os.path.join(_NATIVE_DIR, name), "rb") as f:
            h.update(f.read())
    try:
        with open("/proc/sys/kernel/random/boot_id") as f:
            machine = f.read().strip()
    except OSError:
        machine = platform.node()
    h.update(machine.encode())
    return os.path.join(
        _NATIVE_DIR, f"libsparknet_native.{h.hexdigest()[:12]}.so")


def build(force: bool = False) -> str:
    """Compile the shared library via make (idempotent) and drop builds
    left by other machines or older sources."""
    so = _so_path()
    with _lock:
        if force or not os.path.exists(so):
            # build beside the target and rename: a concurrent loader
            # (feed worker processes) sees a whole library or none
            tmp = f"{so}.{os.getpid()}.tmp"
            try:
                subprocess.run(
                    ["make", "-C", _NATIVE_DIR, f"SO={os.path.basename(tmp)}"],
                    check=True,
                    capture_output=True,
                )
                os.replace(tmp, so)
            finally:
                if os.path.exists(tmp):
                    os.unlink(tmp)
        for stale in glob.glob(
                os.path.join(_NATIVE_DIR, "libsparknet_native*.so")):
            if stale != so:
                try:
                    os.unlink(stale)
                except OSError:
                    pass  # another process removed it first
    return so


def _load(auto_build: bool = True):
    global _lib
    with _lock:
        if _lib is not None:
            return _lib
    so = _so_path()
    if not os.path.exists(so):
        if not auto_build:
            raise FileNotFoundError(so)
        build()
    with _lock:
        lib = ctypes.CDLL(so)
        lib.sndb_open.restype = ctypes.c_void_p
        lib.sndb_open.argtypes = [ctypes.c_char_p, ctypes.c_int]
        lib.sndb_put.restype = ctypes.c_int
        lib.sndb_put.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int,
            ctypes.c_void_p, ctypes.c_int,
        ]
        lib.sndb_commit.restype = ctypes.c_int
        lib.sndb_commit.argtypes = [ctypes.c_void_p]
        lib.sndb_count.restype = ctypes.c_longlong
        lib.sndb_count.argtypes = [ctypes.c_void_p]
        lib.sndb_close.argtypes = [ctypes.c_void_p]
        lib.sndb_cursor.restype = ctypes.c_void_p
        lib.sndb_cursor.argtypes = [ctypes.c_void_p]
        lib.sndb_next.restype = ctypes.c_int
        lib.sndb_next.argtypes = [
            ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_int),
            ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_int),
        ]
        lib.sndb_cursor_free.argtypes = [ctypes.c_void_p]
        lib.snaug_transform.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.c_int, ctypes.c_void_p, ctypes.c_int, ctypes.c_float,
            ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_ulonglong,
            ctypes.c_void_p, ctypes.c_int,
        ]
        lib.snative_abi_version.restype = ctypes.c_int
        _lib = lib
        return _lib


def available() -> bool:
    """True if the native library is present or buildable."""
    try:
        return _load().snative_abi_version() == 1
    except Exception:
        return False


# ---------------------------------------------------------------- record DB
class RecordDB:
    """Append-only record DB (ref: db::GetDB + Cursor/Transaction,
    caffe/src/caffe/util/db.hpp).  Write mode: put/commit; read mode:
    iterate committed records."""

    def __init__(self, path: str, mode: str = "r"):
        if mode not in ("r", "w"):
            raise ValueError("mode must be 'r' or 'w'")
        self._lib = _load()
        self._h = self._lib.sndb_open(path.encode(), 1 if mode == "w" else 0)
        if not self._h:
            raise OSError(f"cannot open record db {path!r} mode={mode}")
        self.mode = mode
        self.path = path

    def put(self, key: bytes, value: bytes) -> None:
        rc = self._lib.sndb_put(self._h, key, len(key), value, len(value))
        if rc != 0:
            raise OSError("sndb_put failed (read-only handle or IO error)")

    def commit(self) -> None:
        if self._lib.sndb_commit(self._h) != 0:
            raise OSError("sndb_commit failed")

    def __len__(self) -> int:
        return int(self._lib.sndb_count(self._h))

    def __iter__(self):
        for key, value in self.views():
            yield key, bytes(value)

    def views(self):
        """The cursor without the copy of the value: ``(key, value)``
        with ``value`` a read-only memoryview of the handle's own storage
        (``sndb_next`` points into it), valid until the handle closes.
        For a reader that copies each record somewhere itself
        (``createdb.db_minibatches``); ``iter(db)`` hands out ``bytes``
        that outlive the handle."""
        cur = self._lib.sndb_cursor(self._h)
        if not cur:
            raise OSError("cursors require a read-mode handle")
        try:
            k = ctypes.c_void_p()
            kl = ctypes.c_int()
            v = ctypes.c_void_p()
            vl = ctypes.c_int()
            while self._lib.sndb_next(
                cur, ctypes.byref(k), ctypes.byref(kl), ctypes.byref(v), ctypes.byref(vl)
            ):
                value = (ctypes.c_ubyte * vl.value).from_address(v.value or 0)
                yield (
                    ctypes.string_at(k, kl.value),
                    memoryview(value).toreadonly(),
                )
        finally:
            self._lib.sndb_cursor_free(cur)

    def close(self) -> None:
        if self._h:
            self._lib.sndb_close(self._h)
            self._h = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


# ---------------------------------------------------------------- augmenter
def transform_batch(
    images: np.ndarray,
    mean: np.ndarray | None = None,
    mean_values: tuple[float, ...] | None = None,
    scale: float = 1.0,
    crop: int = 0,
    mirror: bool = False,
    train: bool = True,
    seed: int = 0,
    nthreads: int = 0,
) -> np.ndarray:
    """Native multithreaded augmenter over a uint8 NCHW batch; semantics
    match :class:`sparknet_tpu.data.DataTransformer` (mean subtract happens
    pre-crop, like Caffe's mean_file path)."""
    lib = _load()
    x = np.ascontiguousarray(images, np.uint8)
    n, c, h, w = x.shape
    if crop and (crop > h or crop > w):
        # same contract as DataTransformer._crop — never hand the C side an
        # out-of-bounds window
        raise ValueError(f"crop {crop} larger than image {h}x{w}")
    if mean is not None:
        mdata = np.ascontiguousarray(mean, np.float32)
        if mdata.shape != (c, h, w):
            raise ValueError(f"mean shape {mdata.shape} != {(c, h, w)}")
        mean_mode = 2
    elif mean_values:
        mdata = np.asarray(mean_values, np.float32)
        if mdata.size != c:
            raise ValueError("need one mean value per channel")
        mean_mode = 1
    else:
        mdata = np.zeros(1, np.float32)
        mean_mode = 0
    oh = crop if crop else h
    out = np.empty((n, c, oh, oh if crop else w), np.float32)
    lib.snaug_transform(
        x.ctypes.data_as(ctypes.c_void_p), n, c, h, w,
        mdata.ctypes.data_as(ctypes.c_void_p), mean_mode,
        ctypes.c_float(scale), crop, 1 if mirror else 0, 1 if train else 0,
        ctypes.c_ulonglong(seed),
        out.ctypes.data_as(ctypes.c_void_p), nthreads,
    )
    return out
