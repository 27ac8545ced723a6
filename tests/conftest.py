"""Test harness: 8 virtual CPU devices (SURVEY.md §4 test strategy).

Force the host platform and split it into 8 XLA devices so every
distributed test exercises a real 8-way mesh without TPU hardware — the
TPU-native analogue of the reference's 4-node gloo cluster.

``jax.config.update("jax_platforms", ...)`` works as long as no backend
has initialized yet.  XLA_FLAGS must land in os.environ before the CPU
client spins up — which happens at the first ``jax.devices()`` call, i.e.
after this module runs.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# Persistent compilation cache: the suite is dominated by XLA compiles of
# shard_map programs; caching them makes reruns minutes instead of tens
# of minutes.  JAX_COMPILATION_CACHE_DIR places it; otherwise it lives in
# <checkout>/.jax_cache (runtime/compile_cache.py).
from distributed_machine_learning_tpu.runtime.compile_cache import (  # noqa: E402
    configure_compile_cache,
)

configure_compile_cache()

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_collection_modifyitems(config, items):
    """Every custom marker used in tests/ must be registered in
    pytest.ini — tier-1 headroom depends on ``slow``/``faultinject``
    gating, and a typo'd marker (``@pytest.mark.solw``) silently pulls
    a heavy test back into the default run instead of failing loudly.
    pytest core registers its own built-ins (parametrize, skipif, ...)
    through the same ini mechanism, so one registry covers both."""
    registered = {
        line.split(":", 1)[0].split("(", 1)[0].strip()
        for line in config.getini("markers")
    }
    unknown = {}
    for item in items:
        for mark in item.iter_markers():
            if mark.name not in registered:
                unknown.setdefault(mark.name, item.nodeid)
    if unknown:
        raise pytest.UsageError(
            "unregistered pytest marker(s) used in tests/: "
            + "; ".join(f"{name!r} (first use: {nodeid})"
                        for name, nodeid in sorted(unknown.items()))
            + " — register them under [pytest] markers in pytest.ini"
        )


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


@pytest.fixture(scope="session")
def mesh8():
    from distributed_machine_learning_tpu.runtime.mesh import make_mesh

    return make_mesh(8)


@pytest.fixture(scope="session")
def mesh4():
    """4-device mesh — the reference's world size (group25.pdf p.1)."""
    from distributed_machine_learning_tpu.runtime.mesh import make_mesh

    return make_mesh(4)


@pytest.fixture()
def rng():
    return np.random.default_rng(69143)


def shard_map_compat(fn, mesh, in_specs, out_specs, check_vma=False):
    """``jax.shard_map`` with replication checking off by default."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)
