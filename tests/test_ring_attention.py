"""Ring attention correctness: the sequence-sharded ppermute ring must
reproduce single-device dense causal attention exactly (up to fp32
reduction-order tolerance) — the same property-test discipline as the
gradient ring (tests/test_ring.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from distributed_machine_learning_tpu.ops.ring_attention import (
    dense_self_attention,
    ring_self_attention,
)
from distributed_machine_learning_tpu.runtime.mesh import make_mesh

B, L, H, D = 2, 32, 4, 8


@pytest.fixture(scope="module")
def qkv():
    rng = np.random.default_rng(69143)
    shape = (B, L, H, D)
    return tuple(
        jnp.asarray(rng.standard_normal(shape, dtype=np.float32)) for _ in range(3)
    )


def _naive_causal(q, k, v):
    """O(L²) reference computed with plain softmax per query row."""
    out = np.zeros_like(np.asarray(q))
    qn, kn, vn = (np.asarray(a) for a in (q, k, v))
    scale = 1.0 / np.sqrt(D)
    for b in range(B):
        for h in range(H):
            s = qn[b, :, h] @ kn[b, :, h].T * scale  # [L, L]
            for i in range(L):
                w = np.exp(s[i, : i + 1] - s[i, : i + 1].max())
                w = w / w.sum()
                out[b, i, h] = w @ vn[b, : i + 1, h]
    return out


def test_dense_matches_naive(qkv):
    q, k, v = qkv
    np.testing.assert_allclose(
        np.asarray(dense_self_attention(q, k, v)),
        _naive_causal(q, k, v),
        rtol=1e-5,
        atol=1e-6,
    )


@pytest.mark.parametrize(
    "n_shards",
    [2,
     pytest.param(4, marks=pytest.mark.slow),
     pytest.param(8, marks=pytest.mark.slow)],
)
def test_ring_matches_dense(qkv, n_shards):
    from distributed_machine_learning_tpu.runtime.mesh import make_mesh

    q, k, v = qkv
    mesh = make_mesh(n_shards, axis_names=("seq",))
    ring = shard_map(
        lambda a, b, c: ring_self_attention(a, b, c, "seq", n_shards),
        mesh=mesh,
        in_specs=(P(None, "seq"), P(None, "seq"), P(None, "seq")),
        out_specs=P(None, "seq"),
    )
    out = jax.jit(ring)(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out),
        np.asarray(dense_self_attention(q, k, v)),
        rtol=1e-5,
        atol=1e-6,
    )


def test_ring_bf16_stays_finite(qkv):
    """bf16 QKV with fp32 accumulators: no inf/nan from the NEG_INF mask."""
    from distributed_machine_learning_tpu.runtime.mesh import make_mesh

    q, k, v = (a.astype(jnp.bfloat16) for a in qkv)
    mesh = make_mesh(4, axis_names=("seq",))
    ring = shard_map(
        lambda a, b, c: ring_self_attention(a, b, c, "seq", 4),
        mesh=mesh,
        in_specs=(P(None, "seq"),) * 3,
        out_specs=P(None, "seq"),
    )
    out = np.asarray(jax.jit(ring)(q, k, v), dtype=np.float32)
    assert np.isfinite(out).all()
    assert out.dtype == np.float32 and np.abs(out).max() < 10.0


def test_ring_gqa_narrow_rotation_matches_dense():
    """GQA through the einsum ring: narrow K/V chunks rotate (widened
    only at the local block math) and the result equals unsharded dense
    attention with widened heads."""
    rng = np.random.default_rng(21)
    B, L, H, Hkv, D, n = 2, 32, 8, 2, 8, 4
    rep = H // Hkv
    q = jnp.asarray(rng.standard_normal((B, L, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, L, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, L, Hkv, D)), jnp.float32)
    ref = dense_self_attention(
        q, jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    )
    mesh = make_mesh(n, axis_names=("seq",))
    fn = shard_map(
        lambda q, k, v: ring_self_attention(q, k, v, "seq", n),
        mesh=mesh,
        in_specs=(P(None, "seq"), P(None, "seq"), P(None, "seq")),
        out_specs=P(None, "seq"),
    )
    out = fn(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )
    with pytest.raises(ValueError, match="multiple of K/V"):
        ring_self_attention(q, k[:, :, :1].repeat(3, axis=2)[:, :, :3], v,
                            "seq", 1)
