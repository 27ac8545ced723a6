"""The chunked gated delta rule (``ops/delta_rule.py``) against the
recurrence it restates, forward and gradient."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from distributed_machine_learning_tpu.ops.delta_rule import (
    CHUNK,
    gated_delta_rule,
    gated_delta_rule_recurrent,
)

#: float32 on both sides; the two differ by the order of their sums and by
#: the triangular solve's own rounding (64 steps of forward substitution).
TOL = 2e-5


def _inputs(seed, T, decay_shift=0.0, B=2, H=3, dk=16, dv=24):
    """Unit keys, scaled unit queries, ``g = −exp(shift)·softplus(·)``:
    ``shift`` −8 holds the decay at 1 − 3e-4 a step, +3 drives it to
    1e-27."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (B, T, H, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (B, T, H, dk)))
    v = jax.random.normal(ks[2], (B, T, H, dv))
    g = -jnp.exp(decay_shift) * jax.nn.softplus(
        jax.random.normal(ks[3], (B, T, H)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H)))
    return q, k, v, g, beta


def _rel(a, b):
    return float(jnp.abs(a - b).max() / jnp.abs(b).max())


@pytest.mark.parametrize("T", [CHUNK, 2 * CHUNK, 150, 7])
def test_chunked_rule_matches_the_recurrence(T):
    args = _inputs(0, T)
    assert _rel(gated_delta_rule(*args),
                gated_delta_rule_recurrent(*args)) < TOL


@pytest.mark.parametrize("decay_shift", [-8.0, 0.0, 3.0],
                         ids=["decay_near_1", "decay_mid", "decay_near_0"])
def test_chunked_rule_gradients_match_the_recurrence(decay_shift):
    args = _inputs(1, 150, decay_shift)  # 150: not a multiple of the chunk
    decay = jnp.exp(args[3])
    if decay_shift < 0:
        assert float(decay.min()) > 0.99
    if decay_shift > 0:
        assert float(decay.min()) < 1e-20
    w = jax.random.normal(jax.random.PRNGKey(9), (*args[0].shape[:3], 24))
    grads = [jax.grad(lambda *a: (f(*a) * w).sum(), argnums=range(5))(*args)
             for f in (gated_delta_rule, gated_delta_rule_recurrent)]
    # The decay's own gradient at 1e-27 is a sum of vanishing terms: held
    # to 1e-4 there, everything else to TOL.
    for name, a, b in zip("q k v g beta".split(), *grads):
        assert jnp.isfinite(a).all(), name
        assert _rel(a, b) < (1e-4 if name == "g" else TOL), name


def test_padding_steps_leave_the_state_alone():
    """A length that is no multiple of the chunk gives the prefix of the
    longer run: the padded steps neither write nor decay."""
    args = _inputs(2, 2 * CHUNK)
    short = tuple(a[:, :CHUNK + 5] for a in args)
    assert _rel(gated_delta_rule(*short),
                gated_delta_rule(*args)[:, :CHUNK + 5]) < TOL


def test_bf16_operands_accumulate_in_float32():
    args = _inputs(3, 2 * CHUNK)
    low = tuple(a.astype(jnp.bfloat16) for a in args[:3]) + args[3:]
    out = gated_delta_rule(*low)
    assert out.dtype == jnp.bfloat16
    ref = gated_delta_rule_recurrent(*low)
    assert _rel(out.astype(jnp.float32), ref) < 0.03
