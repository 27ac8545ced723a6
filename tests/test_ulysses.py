"""Ulysses sequence parallelism correctness: the all-to-all head-sharded
attention (ops/ulysses.py) must reproduce single-device dense causal
attention exactly, and a Ulysses TransformerLM on a sequence-sharded mesh
must match the unsharded dense model — same invariants as the ppermute
ring (tests/test_ring_attention.py), different collective."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from distributed_machine_learning_tpu.ops.ring_attention import (
    dense_self_attention,
)
from distributed_machine_learning_tpu.ops.ulysses import (
    ulysses_self_attention,
)
from distributed_machine_learning_tpu.runtime.mesh import make_mesh

B, L, H, D = 2, 32, 8, 8


@pytest.fixture(scope="module")
def qkv():
    rng = np.random.default_rng(69143)
    shape = (B, L, H, D)
    return tuple(
        jnp.asarray(rng.standard_normal(shape, dtype=np.float32)) for _ in range(3)
    )


@pytest.mark.parametrize(
    "n_shards",
    [2,
     pytest.param(4, marks=pytest.mark.slow),
     pytest.param(8, marks=pytest.mark.slow)],
)
def test_ulysses_matches_dense(qkv, n_shards):
    q, k, v = qkv
    mesh = make_mesh(n_shards, axis_names=("seq",))
    uly = shard_map(
        lambda a, b, c: ulysses_self_attention(a, b, c, "seq", n_shards),
        mesh=mesh,
        in_specs=(P(None, "seq"),) * 3,
        out_specs=P(None, "seq"),
    )
    np.testing.assert_allclose(
        np.asarray(jax.jit(uly)(q, k, v)),
        np.asarray(dense_self_attention(q, k, v)),
        rtol=1e-5,
        atol=1e-6,
    )


def test_ulysses_flash_local_attention_matches_dense(qkv):
    """local_attn='flash': the per-device full-sequence attention runs
    the Pallas kernel — forward and all three gradients must still match
    single-device dense."""
    from distributed_machine_learning_tpu.runtime.mesh import (
        shard_map_no_check,
    )

    q, k, v = qkv
    n_shards = 2
    mesh = make_mesh(n_shards, axis_names=("seq",))
    # shard_map_no_check: pallas_call outputs carry no varying-mesh-axis
    # annotation, so the replication checker must be off (same reason the
    # LM train step uses it).
    uly = jax.jit(shard_map_no_check(
        lambda a, b, c: ulysses_self_attention(
            a, b, c, "seq", n_shards, local_attn="flash"
        ),
        mesh=mesh,
        in_specs=(P(None, "seq"),) * 3,
        out_specs=P(None, "seq"),
    ))
    np.testing.assert_allclose(
        np.asarray(uly(q, k, v)),
        np.asarray(dense_self_attention(q, k, v)),
        rtol=2e-5,
        atol=2e-6,
    )
    cot = jnp.asarray(
        np.random.default_rng(2).standard_normal((B, L, H, D),
                                                 dtype=np.float32)
    )
    g_u = jax.grad(lambda *a: jnp.sum(uly(*a) * cot), argnums=(0, 1, 2))(
        q, k, v
    )
    g_d = jax.grad(
        lambda *a: jnp.sum(dense_self_attention(*a) * cot), argnums=(0, 1, 2)
    )(q, k, v)
    for got, want, name in zip(g_u, g_d, "qkv"):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5,
            err_msg=f"d{name} mismatch",
        )


def test_ulysses_rejects_indivisible_heads(qkv):
    """H=8 over 8 devices is the limit; a 3-head tensor must be refused."""
    q, k, v = (a[:, :, :3] for a in qkv)
    mesh = make_mesh(2, axis_names=("seq",))
    uly = shard_map(
        lambda a, b, c: ulysses_self_attention(a, b, c, "seq", 2),
        mesh=mesh,
        in_specs=(P(None, "seq"),) * 3,
        out_specs=P(None, "seq"),
    )
    with pytest.raises(ValueError, match="divisible"):
        jax.jit(uly)(q, k, v)


def test_ulysses_single_shard_is_dense(qkv):
    q, k, v = qkv
    mesh = make_mesh(1, axis_names=("seq",))
    uly = shard_map(
        lambda a, b, c: ulysses_self_attention(a, b, c, "seq", 1),
        mesh=mesh,
        in_specs=(P(None, "seq"),) * 3,
        out_specs=P(None, "seq"),
    )
    np.testing.assert_allclose(
        np.asarray(jax.jit(uly)(q, k, v)),
        np.asarray(dense_self_attention(q, k, v)),
        rtol=1e-6,
        atol=1e-7,
    )


def test_ulysses_step_builder_validates_heads():
    """make_lm_train_step fails at build time, not first-step trace time."""
    from distributed_machine_learning_tpu.models.transformer import TransformerLM
    from distributed_machine_learning_tpu.train.lm_step import make_lm_train_step

    model = TransformerLM(
        vocab_size=64, d_model=36, n_layers=1, n_heads=6, attn_impl="ulysses"
    )
    mesh = make_mesh(8, axis_names=("batch", "seq"), axis_shape=(2, 4))
    with pytest.raises(ValueError, match="divisible"):
        make_lm_train_step(model, mesh=mesh)


def test_ulysses_lm_step_matches_dense():
    """Full train step: Ulysses LM on a (batch=2, seq=4) mesh takes the
    same first step as the unsharded dense LM (loss + params agree)."""
    from distributed_machine_learning_tpu.models.transformer import TransformerLM
    from distributed_machine_learning_tpu.train.lm_step import (
        init_lm_state,
        make_lm_train_step,
        shard_lm_batch,
    )

    rng = np.random.default_rng(7)
    toks = rng.integers(0, 64, (4, 33))
    x = toks[:, :-1].astype(np.int32)
    y = toks[:, 1:].astype(np.int32)

    dense = TransformerLM(vocab_size=64, d_model=32, n_layers=2, n_heads=8)
    dstate = init_lm_state(dense)
    dstep = make_lm_train_step(dense)
    dstate, dloss = dstep(dstate, jnp.asarray(x), jnp.asarray(y))

    uly = dense.clone(attn_impl="ulysses")
    mesh = make_mesh(8, axis_names=("batch", "seq"), axis_shape=(2, 4))
    ustate = init_lm_state(uly)
    ustep = make_lm_train_step(uly, mesh=mesh)
    ux, uy = shard_lm_batch(mesh, x, y)
    ustate, uloss = ustep(ustate, ux, uy)

    np.testing.assert_allclose(float(uloss), float(dloss), rtol=1e-5)
    flat_d = jax.tree.leaves(dstate.params)
    flat_u = jax.tree.leaves(ustate.params)
    for a, b in zip(flat_d, flat_u):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-5
        )


def test_ulysses_gqa_narrow_path_matches_dense():
    """GQA through Ulysses: the narrow-K/V packed all-to-all path
    (Hkv % n == 0) and the widen-first fallback (Hkv % n != 0) both
    equal unsharded dense attention, on the dense AND flash local
    kernels (flash consumes the narrow K/V natively)."""
    rng = np.random.default_rng(9)
    for Hkv, n, Lg, local in (
        (4, 4, 32, "dense"),   # narrow path, dense local kernel
        (2, 4, 32, "dense"),   # widen-first fallback
        (4, 4, 512, "flash"),  # narrow path, flash local kernel
    ):
        rep = H // Hkv
        q = jnp.asarray(rng.standard_normal((B, Lg, H, D)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((B, Lg, Hkv, D)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((B, Lg, Hkv, D)), jnp.float32)
        ref = dense_self_attention(
            q, jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
        )
        from distributed_machine_learning_tpu.runtime.mesh import (
            shard_map_no_check,
        )

        mesh = make_mesh(n, axis_names=("seq",))
        fn = shard_map_no_check(
            lambda q, k, v, local=local: ulysses_self_attention(
                q, k, v, "seq", n, local_attn=local
            ),
            mesh=mesh,
            in_specs=(P(None, "seq"), P(None, "seq"), P(None, "seq")),
            out_specs=P(None, "seq"),
        )
        out = fn(q, k, v)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4
        )


def test_ulysses_rejects_non_divisor_kv_heads():
    q = jnp.zeros((1, 8, 8, 4))
    kv = jnp.zeros((1, 8, 3, 4))
    with pytest.raises(ValueError, match="multiple of K/V"):
        ulysses_self_attention(q, kv, kv, "seq", 1)
