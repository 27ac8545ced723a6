"""C++ native loader vs the pure-Python loaders: identical batch streams,
clean mid-epoch abandonment (the 40-iteration cap), and graceful fallback
reporting."""

import numpy as np
import pytest

from distributed_machine_learning_tpu.data.cifar10 import Dataset
from distributed_machine_learning_tpu.data.distributed_loader import (
    DistributedBatchLoader,
)
from distributed_machine_learning_tpu.data.loader import BatchLoader
from distributed_machine_learning_tpu.data.native_loader import (
    NativeBatchLoader,
    NativeDistributedBatchLoader,
    native_available,
    native_unavailable_reason,
)

pytestmark = pytest.mark.skipif(
    not native_available(),
    reason=f"native loader unavailable: {native_unavailable_reason()}",
)


@pytest.fixture(scope="module")
def dataset():
    rng = np.random.default_rng(69143)
    images = rng.integers(0, 256, (103, 32, 32, 3), dtype=np.uint8)
    labels = rng.integers(0, 10, 103).astype(np.int64)
    return Dataset(images=images, labels=labels, synthetic=True)


def _streams_equal(a, b):
    a, b = list(a), list(b)
    assert len(a) == len(b)
    for (ia, la), (ib, lb) in zip(a, b):
        np.testing.assert_array_equal(ia, ib)
        np.testing.assert_array_equal(la, lb)


def test_native_matches_python_loader(dataset):
    _streams_equal(
        NativeBatchLoader(dataset, 16), BatchLoader(dataset, 16, prefetch=0)
    )


def test_native_matches_python_loader_custom_indices(dataset):
    idx = np.arange(len(dataset))[::-1].copy()
    _streams_equal(
        NativeBatchLoader(dataset, 10, indices=idx),
        BatchLoader(dataset, 10, indices=idx, prefetch=0),
    )


def test_native_distributed_matches_python(dataset):
    _streams_equal(
        NativeDistributedBatchLoader(dataset, 8, 4),
        DistributedBatchLoader(dataset, 8, 4),
    )


def test_native_loader_early_abandon(dataset):
    """Breaking mid-epoch (reference's 40-iter cap) must not hang or leak."""
    loader = NativeBatchLoader(dataset, 4, prefetch=2)
    for _ in range(3):
        it = iter(loader)
        next(it)
        next(it)
        it.close()  # generator close → dl_destroy while worker mid-queue


def test_native_loader_reiterable(dataset):
    first = [l.copy() for _, l in NativeBatchLoader(dataset, 16)]
    second = [l.copy() for _, l in NativeBatchLoader(dataset, 16)]
    for a, b in zip(first, second):
        np.testing.assert_array_equal(a, b)


def test_native_rejects_bad_batch(dataset):
    with pytest.raises(ValueError):
        NativeBatchLoader(dataset, 0)
    with pytest.raises(ValueError):
        NativeDistributedBatchLoader(dataset, -1, 4)


def test_only_the_hash_named_artifact_is_loaded(monkeypatch):
    """A stale or foreign binary under the old fixed name is never
    loaded: the artifact's name carries a hash of the source and the
    compile command, and that is the only path ``_load`` opens."""
    import hashlib

    from distributed_machine_learning_tpu.data import native_loader as nl

    stale = nl._BUILD_DIR / "libdml_loader.so"
    stale.write_bytes(b"not a shared library")
    monkeypatch.setattr(nl, "_lib", None)  # force a fresh load decision
    monkeypatch.setattr(nl, "_lib_error", None)
    try:
        digest = hashlib.sha256(
            nl._SRC.read_bytes() + b"\0" + " ".join(nl._CXX).encode()
        ).hexdigest()[:16]
        assert nl._lib_path().name == f"libdml_loader.{digest}.so"
        assert nl._load()._name == str(nl._lib_path())
        assert nl._lib_path() != stale
    finally:
        stale.unlink()
