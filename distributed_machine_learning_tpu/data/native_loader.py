"""ctypes bridge to the C++ prefetching batch loader (native/dataloader.cc).

The reference's host data path is torch's C++ DataLoader with
``pin_memory=True`` (``part2/2a/main.py:162-167``); this is its TPU-native
counterpart — batch assembly and prefetch run in a C++ worker thread
behind a bounded queue, so host gather overlaps device compute without
the GIL in the way.  The shared library is compiled from source on first
use with the system ``g++`` (no pip deps); when no toolchain is
available, ``--loader auto`` callers fall back to the pure-Python
loaders (same batch stream — ``tests/test_native_loader.py`` asserts
byte equality).  The artifact's file name carries a hash of the source
and the compile command, so only a binary built from the source as it
stands is ever loaded — never a stale or foreign one left in the
git-ignored build directory.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from pathlib import Path
from typing import Iterator

import numpy as np

from distributed_machine_learning_tpu.data.cifar10 import Dataset
from distributed_machine_learning_tpu.data.sharding import shard_indices

_SRC = Path(__file__).resolve().parent.parent / "native" / "dataloader.cc"
_BUILD_DIR = _SRC.parent / "_build"
_CXX = ("g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread")

_lib = None
_lib_error: str | None = None
_lib_lock = threading.Lock()


def _lib_path() -> Path:
    """Where the artifact of the CURRENT source + compile command lives."""
    digest = hashlib.sha256(
        _SRC.read_bytes() + b"\0" + " ".join(_CXX).encode()
    ).hexdigest()[:16]
    return _BUILD_DIR / f"libdml_loader.{digest}.so"


def _compile(lib_path: Path) -> None:
    _BUILD_DIR.mkdir(exist_ok=True)
    tmp = lib_path.with_suffix(f".{os.getpid()}.tmp")
    cmd = [*_CXX, str(_SRC), "-o", str(tmp)]
    subprocess.run(cmd, check=True, capture_output=True, text=True)
    os.replace(tmp, lib_path)  # atomic: parallel builders race benignly


def _load():
    """Compile (once) and load the shared library; cache the outcome."""
    global _lib, _lib_error
    with _lib_lock:
        if _lib is not None or _lib_error is not None:
            return _lib
        try:
            lib_path = _lib_path()
            if not lib_path.exists():
                _compile(lib_path)
            lib = ctypes.CDLL(str(lib_path))
            lib.dl_create.restype = ctypes.c_void_p
            lib.dl_create.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
                ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
                ctypes.c_int64,
            ]
            lib.dl_next.restype = ctypes.c_int64
            lib.dl_next.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ]
            lib.dl_destroy.restype = None
            lib.dl_destroy.argtypes = [ctypes.c_void_p]
            _lib = lib
        except (OSError, subprocess.CalledProcessError) as e:
            detail = getattr(e, "stderr", "") or str(e)
            _lib_error = f"native loader unavailable: {detail}"
        return _lib


def native_available() -> bool:
    return _load() is not None


def native_unavailable_reason() -> str | None:
    _load()
    return _lib_error


class NativeBatchLoader:
    """Drop-in for ``loader.BatchLoader`` backed by the C++ worker."""

    def __init__(
        self,
        dataset: Dataset,
        batch_size: int,
        indices: np.ndarray | None = None,
        prefetch: int = 4,
    ):
        if batch_size <= 0:
            raise ValueError(f"batch_size must be positive, got {batch_size}")
        lib = _load()
        if lib is None:
            raise RuntimeError(_lib_error)
        self._lib = lib
        # Contiguous copies pinned to this object: the C++ side reads these
        # buffers for the lifetime of every handle created in __iter__.
        self._images = np.ascontiguousarray(dataset.images, dtype=np.uint8)
        self._labels = np.ascontiguousarray(dataset.labels, dtype=np.int32)
        self._indices = np.ascontiguousarray(
            np.arange(len(dataset)) if indices is None else indices,
            dtype=np.int64,
        )
        self.batch_size = batch_size
        self.prefetch = prefetch
        self._row_bytes = int(np.prod(self._images.shape[1:]))
        self._row_shape = self._images.shape[1:]

    def __len__(self) -> int:
        return (len(self._indices) + self.batch_size - 1) // self.batch_size

    def __iter__(self) -> Iterator[tuple[np.ndarray, np.ndarray]]:
        handle = self._lib.dl_create(
            self._images.ctypes.data, self._labels.ctypes.data,
            self._row_bytes, self._indices.ctypes.data, len(self._indices),
            self.batch_size, self.prefetch,
        )
        if not handle:
            raise RuntimeError("dl_create failed (bad arguments)")
        try:
            while True:
                out_i = np.empty((self.batch_size, *self._row_shape), np.uint8)
                out_l = np.empty((self.batch_size,), np.int32)
                rows = self._lib.dl_next(
                    handle, out_i.ctypes.data, out_l.ctypes.data
                )
                if rows == 0:
                    return
                yield out_i[:rows], out_l[:rows]
        finally:
            self._lib.dl_destroy(handle)


class NativeDistributedBatchLoader(NativeBatchLoader):
    """Drop-in for ``distributed_loader.DistributedBatchLoader``: same
    rank-major global-batch layout (derived from the same
    ``shard_indices`` source of truth), assembled by the C++ worker."""

    def __init__(
        self,
        dataset: Dataset,
        per_rank_batch: int,
        num_ranks: int,
        prefetch: int = 4,
    ):
        if per_rank_batch <= 0 or num_ranks <= 0:
            raise ValueError(
                f"per_rank_batch and num_ranks must be positive, got "
                f"{per_rank_batch}, {num_ranks}"
            )
        rank_indices = np.stack(
            [shard_indices(len(dataset), r, num_ranks) for r in range(num_ranks)]
        )  # [num_ranks, per_rank_count]
        steps = rank_indices.shape[1] // per_rank_batch  # drop_last=True
        b = per_rank_batch
        epoch = np.concatenate(
            [
                rank_indices[:, s * b : (s + 1) * b].reshape(-1)
                for s in range(steps)
            ]
        ) if steps else np.empty((0,), np.int64)
        super().__init__(
            dataset, b * num_ranks, indices=epoch, prefetch=prefetch
        )
        self.per_rank_batch = per_rank_batch
        self.num_ranks = num_ranks
        self.global_batch = b * num_ranks
