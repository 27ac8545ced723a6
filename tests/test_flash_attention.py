"""Pallas flash attention (interpret mode on the CPU mesh) vs the dense
reference — forward, backward, and inside the full model."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_machine_learning_tpu.models.transformer import TransformerLM
from distributed_machine_learning_tpu.ops.pallas.flash_attention import (
    _dkv_blocks,
    _fwd_blocks,
    _pick,
    flash_self_attention,
)
from distributed_machine_learning_tpu.ops.ring_attention import (
    dense_self_attention,
)


@pytest.fixture(scope="module")
def qkv():
    rng = np.random.default_rng(69143)
    shape = (2, 64, 4, 16)  # [B, L, H, D]
    return tuple(
        jnp.asarray(rng.standard_normal(shape, dtype=np.float32)) for _ in range(3)
    )


def test_flash_matches_dense_forward(qkv):
    q, k, v = qkv
    np.testing.assert_allclose(
        np.asarray(flash_self_attention(q, k, v)),
        np.asarray(dense_self_attention(q, k, v)),
        rtol=1e-5,
        atol=1e-6,
    )


def test_block_picker():
    # Powers of two dividing L, capped at the measured-optimal 512 square
    # (see the sweep notes in _fwd_blocks/_dkv_blocks).
    assert _pick(48, 512) == 16
    assert _fwd_blocks(4096) == (512, 512)
    assert _dkv_blocks(4096) == (512, 512)
    assert _fwd_blocks(64) == (64, 64)
    assert _pick(17, 512) == 1  # prime-ish lengths degrade, don't crash


def test_auto_attn_policy():
    from distributed_machine_learning_tpu.models.transformer import _flash_wins

    assert not _flash_wins(256)  # below the measured crossover
    assert _flash_wins(512) and _flash_wins(4096) and _flash_wins(16384)
    # Sub-1k lengths not divisible by 512 degrade the blocks past the
    # thin @512 margin — dense keeps them.
    assert not _flash_wins(640) and not _flash_wins(768)
    assert not _flash_wins(1040)  # 16·65: pad overhead beats dense's 1.6×
    # From 2048 up the policy is TOTAL: every length dispatches flash
    # (padded when needed) because dense is ≥2× behind or uncompilable.
    assert _flash_wins(2050) and _flash_wins(16640) and _flash_wins(30000)
    # The ring upgrade stays native-tileable only (no pad path there).
    from distributed_machine_learning_tpu.models.transformer import (
        _ring_flash_wins,
    )

    assert _ring_flash_wins(4096) and not _ring_flash_wins(2050)


def test_flash_odd_length(qkv):
    # L=48: largest power-of-two divisor 16 < 128 → the kernel pads to
    # the next 512 multiple and slices back (Mosaic cannot tile a
    # 16-lane residual block).  Padding must be invisible: exact dense
    # parity, forward and backward.
    q, k, v = (a[:, :48] for a in qkv)
    from distributed_machine_learning_tpu.ops.pallas.flash_attention import (
        _needs_pad,
    )

    assert _needs_pad(48) and not _needs_pad(64) and not _needs_pad(16640)
    np.testing.assert_allclose(
        np.asarray(flash_self_attention(q, k, v)),
        np.asarray(dense_self_attention(q, k, v)),
        rtol=1e-5,
        atol=1e-6,
    )
    g = jnp.ones_like(q)
    _, flash_vjp = jax.vjp(flash_self_attention, q, k, v)
    _, dense_vjp = jax.vjp(dense_self_attention, q, k, v)
    for got, want, name in zip(flash_vjp(g), dense_vjp(g), "qkv"):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5,
            err_msg=f"d{name} mismatch through the padded path",
        )


def test_flash_backward_matches_dense(qkv):
    q, k, v = qkv
    cot = jnp.asarray(
        np.random.default_rng(1).standard_normal(q.shape, dtype=np.float32)
    )

    def loss_flash(q, k, v):
        return jnp.sum(flash_self_attention(q, k, v) * cot)

    def loss_dense(q, k, v):
        return jnp.sum(dense_self_attention(q, k, v) * cot)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_dense):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5
        )


def test_flash_model_matches_dense_model():
    tokens = jnp.asarray(
        np.random.default_rng(5).integers(0, 64, (2, 32)), jnp.int32
    )
    dense = TransformerLM(vocab_size=64, d_model=32, n_layers=2, n_heads=4)
    flash = TransformerLM(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, attn_impl="flash"
    )
    params = dense.init(jax.random.PRNGKey(0), tokens)["params"]
    ref = dense.apply({"params": params}, tokens)
    out = flash.apply({"params": params}, tokens)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4
    )


def test_flash_gqa_matches_repeated_dense(rng):
    """GQA-native flash (narrow K/V streamed via divided index maps) ==
    dense attention over explicitly repeated K/V — forward and all three
    gradients (dk/dv group-summed down to the narrow heads)."""
    B, L, H, Hkv, D = 2, 32, 8, 2, 8
    n_rep = H // Hkv
    q = jnp.asarray(rng.standard_normal((B, L, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, L, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, L, Hkv, D)), jnp.float32)
    g = jnp.asarray(rng.standard_normal((B, L, H, D)), jnp.float32)

    def rep(t):
        return jnp.repeat(t, n_rep, axis=2)

    def dense_ref(q, k, v):
        return dense_self_attention(q, rep(k), rep(v))

    out, flash_vjp = jax.vjp(flash_self_attention, q, k, v)
    ref, dense_vjp = jax.vjp(dense_ref, q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)
    for got, want, name in zip(flash_vjp(g), dense_vjp(g), "qkv"):
        assert got.shape == want.shape, name
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5,
            err_msg=f"d{name} mismatch",
        )
    with pytest.raises(ValueError, match="identical shapes"):
        flash_self_attention(q, k[:, :, :1], v)  # k/v head mismatch
    bad_kv = k[:, :, :1][:, :, [0, 0, 0]]  # 3 heads: does not divide 8
    with pytest.raises(ValueError, match="multiple of K/V heads"):
        flash_self_attention(q, bad_kv, bad_kv)


def test_flash_bf16_finite(qkv):
    q, k, v = (a.astype(jnp.bfloat16) for a in qkv)
    out = np.asarray(flash_self_attention(q, k, v), dtype=np.float32)
    assert np.isfinite(out).all()


def test_flash_backward_matches_dense_vjp(rng):
    # The Pallas backward (dq/dkv kernels recomputing from the saved
    # logsumexp) must match the dense XLA VJP on all three gradients.
    from distributed_machine_learning_tpu.ops.pallas.flash_attention import (
        flash_self_attention,
    )
    from distributed_machine_learning_tpu.ops.ring_attention import (
        dense_self_attention,
    )

    B, L, H, D = 2, 32, 2, 8
    q = jnp.asarray(rng.standard_normal((B, L, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, L, H, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, L, H, D)), jnp.float32)
    g = jnp.asarray(rng.standard_normal((B, L, H, D)), jnp.float32)

    _, flash_vjp = jax.vjp(flash_self_attention, q, k, v)
    _, dense_vjp = jax.vjp(dense_self_attention, q, k, v)
    for got, want, name in zip(flash_vjp(g), dense_vjp(g), "qkv"):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5,
            err_msg=f"d{name} mismatch",
        )


def test_flash_grad_through_training_loss(rng):
    # End-to-end: grads of a flash-attention LM loss == dense-attention
    # LM loss grads (same params, same batch).
    from distributed_machine_learning_tpu.models.transformer import TransformerLM
    from distributed_machine_learning_tpu.train.lm_step import init_lm_state
    from distributed_machine_learning_tpu.train.losses import lm_cross_entropy

    toks = jnp.asarray(rng.integers(0, 32, (2, 17)), jnp.int32)

    def grads_for(attn):
        model = TransformerLM(vocab_size=32, d_model=16, n_layers=2,
                              n_heads=2, attn_impl=attn)
        state = init_lm_state(model)

        def loss(p):
            return lm_cross_entropy(
                model.apply({"params": p}, toks[:, :-1], train=True),
                toks[:, 1:],
            )

        return jax.grad(loss)(state.params)

    gf = grads_for("flash")
    gd = grads_for("dense")
    for a, b in zip(jax.tree_util.tree_leaves(gf),
                    jax.tree_util.tree_leaves(gd)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-6)


# --- The fused backward kernel (dq resident in VMEM) against the
# --- two-kernel split it replaces where the head's dq fits.

#: (B, L, H, Hkv, D, Dv): L 384 tiles as 3 × 3 blocks of 128, so every dq
#: row block is revisited by up to three K blocks; L 48 pads to 512.
FUSED_CASES = {
    "mha": (2, 384, 2, 2, 16, 16),
    "gqa4": (1, 384, 4, 1, 16, 16),
    "qk192v128": (1, 384, 2, 2, 192, 128),
    "padded": (2, 48, 2, 1, 16, 16),
}

#: The head shapes the benchmark's cells run, (B, L, H, Hkv, D, Dv).
CELL_SHAPES = {
    "sc2_3b_dp_s4096": (2, 4096, 24, 2, 128, 128),
    "sc2_3b_dp_s1024": (8, 1024, 24, 2, 128, 128),
    "q3next_a3b_dp_s8192": (1, 8192, 16, 2, 256, 256),
    "kanana2_a3b_dp_s8192": (1, 8192, 32, 32, 192, 128),
    # its full-attention layer; the window layers: tests/test_window_moe.py
    "trinity_mini_dp_s16384": (1, 16384, 32, 4, 128, 128),
}


def _flash_grads(monkeypatch, fused: bool, q, k, v, g):
    from distributed_machine_learning_tpu.ops.pallas import flash_attention

    monkeypatch.setattr(flash_attention, "_bwd_fused", lambda *_: fused)
    _, vjp = jax.vjp(flash_self_attention, q, k, v)
    return vjp(g)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", FUSED_CASES)
def test_fused_backward_equals_split(monkeypatch, case, dtype):
    """One kernel owning dq, dk and dv returns what the dQ and dK/dV
    kernels return: the same tiles, operands and order of accumulation."""
    B, L, H, Hkv, D, Dv = FUSED_CASES[case]
    rng = np.random.default_rng(3207)
    q, k, v, g = (
        jnp.asarray(rng.standard_normal(shape), dtype)
        for shape in ((B, L, H, D), (B, L, Hkv, D), (B, L, Hkv, Dv),
                      (B, L, H, Dv))
    )
    fused = _flash_grads(monkeypatch, True, q, k, v, g)
    split = _flash_grads(monkeypatch, False, q, k, v, g)
    # float32 round-off of the accumulators; a bf16 output may land one
    # rounding (2^-8 relative) apart.
    rtol = 1e-6 if dtype == jnp.float32 else 2.0**-7
    for got, want, name in zip(fused, split, ("dq", "dk", "dv")):
        assert got.shape == want.shape and got.dtype == want.dtype, name
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=rtol, atol=1e-6, err_msg=f"{case}: {name}",
        )


@pytest.mark.parametrize("shape, dtype, fused", [
    *((CELL_SHAPES[c][1:2] + CELL_SHAPES[c][4:], jnp.bfloat16, True)
      for c in CELL_SHAPES),
    ((8192, 256, 256), jnp.float32, True),
    ((32768, 128, 128), jnp.bfloat16, True),
    # The head's dq (f32 scratch + double-buffered output block) cannot
    # stay in VMEM: the two-kernel split, O(block) on chip.
    ((65536, 128, 128), jnp.bfloat16, False),
    ((32768, 192, 128), jnp.bfloat16, False),
    ((32768, 128, 128), jnp.float32, False),
])
def test_backward_choice_is_a_function_of_shape_and_dtype(shape, dtype, fused):
    from distributed_machine_learning_tpu.ops.pallas.flash_attention import (
        _FUSED_VMEM_BUDGET,
        _bwd_fused,
    )

    assert _bwd_fused(*shape, dtype) is fused
    assert _FUSED_VMEM_BUDGET <= 64 * 2**20  # well under v5e's 128 MiB


@pytest.mark.parametrize("cell", CELL_SHAPES)
def test_cell_shapes_lower_to_the_fused_kernel(monkeypatch, cell):
    """The program lowered for a TPU at each cell's attention shapes holds
    ``flash_bwd_fused*`` (the name a device trace shows) and neither
    kernel of the split.  Lowering needs no chip and no TPU compiler."""
    import re

    from distributed_machine_learning_tpu.ops.pallas import flash_attention

    monkeypatch.setattr(flash_attention, "_interpret", lambda: False)
    B, L, H, Hkv, D, Dv = CELL_SHAPES[cell]

    def loss(q, k, v):
        return jnp.sum(flash_self_attention(q, k, v).astype(jnp.float32))

    args = [jax.ShapeDtypeStruct(s, jnp.bfloat16)
            for s in ((B, L, H, D), (B, L, Hkv, D), (B, L, Hkv, Dv))]
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    suffix = "" if D == Dv else f"_qk{D}v{Dv}"
    assert set(re.findall(r"flash_\w+", text)) == {
        "flash_fwd" + suffix, "flash_bwd_fused" + suffix}
