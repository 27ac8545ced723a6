"""psum / gather-scatter collective wrappers on the CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from distributed_machine_learning_tpu.ops.collectives import (
    all_reduce_mean,
    all_reduce_sum,
    gather_scatter_sum,
)


def _per_device(fn):
    def inner(tree):
        local = jax.tree_util.tree_map(lambda x: x[0], tree)
        out = fn(local)
        return jax.tree_util.tree_map(lambda x: x[None], out)

    return inner


def _run(mesh, fn, data):
    wrapped = shard_map(
        _per_device(fn), mesh=mesh, in_specs=P("batch"), out_specs=P("batch"),
        check_vma=False,
    )
    return jax.jit(wrapped)(jax.tree_util.tree_map(jnp.asarray, data))


def test_all_reduce_sum_semantics(mesh8, rng):
    # 2b parity: SUM, never divided by world size (SURVEY.md §2.4).
    data = {"g": rng.standard_normal((8, 5, 3)).astype(np.float32)}
    out = _run(mesh8, lambda t: all_reduce_sum(t, "batch"), data)
    expected = data["g"].sum(axis=0)
    for d in range(8):
        np.testing.assert_allclose(np.asarray(out["g"][d]), expected, rtol=1e-5)


def test_all_reduce_mean_semantics(mesh8, rng):
    data = {"g": rng.standard_normal((8, 4)).astype(np.float32)}
    out = _run(mesh8, lambda t: all_reduce_mean(t, "batch"), data)
    expected = data["g"].mean(axis=0)
    for d in range(8):
        np.testing.assert_allclose(np.asarray(out["g"][d]), expected, rtol=1e-5)


def test_gather_scatter_matches_manual_rank_order_sum(mesh4, rng):
    # 2a postcondition: every rank ends with the rank-ordered sum
    # (part2/2a/main.py:104-116).
    data = {"g": rng.standard_normal((4, 11)).astype(np.float32)}
    out = _run(mesh4, lambda t: gather_scatter_sum(t, "batch"), data)
    expected = data["g"][0] + data["g"][1] + data["g"][2] + data["g"][3]
    for d in range(4):
        np.testing.assert_allclose(np.asarray(out["g"][d]), expected, rtol=1e-5)


def test_cross_replica_equality_invariant(mesh8, rng):
    """The reference's de facto distributed-correctness assertion —
    identical results on every rank (group25.pdf p.5) — as a bitwise test."""
    data = {"g": rng.standard_normal((8, 257)).astype(np.float32)}
    out = _run(mesh8, lambda t: all_reduce_sum(t, "batch"), data)
    base = np.asarray(out["g"][0])
    for d in range(1, 8):
        assert (np.asarray(out["g"][d]) == base).all()
