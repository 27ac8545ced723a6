"""AOT Mosaic compiles of the backward flash kernels at the cells' real
shapes, of the windowed kernels, forward and backward, at the window
cell's, of the gated delta rule's gradient at the hybrid cell's, and of a
small LM step whose flash calls the ``flash_*_calls`` gauges count, for a
described ``v5e:2x2`` device — no chip.

What interpret mode cannot show: that the fused kernel's resident dq, its
``(1, L, D)`` output block and its ``vmem_limit_bytes`` are legal and fit at
every head shape a cell runs, and at the largest length the chooser still
hands it; a VMEM overflow or an illegal block fails here, off-chip.

Written as the ``on-chip-measurement`` guide's section 2 requires: the
topology is described inside a module-scoped fixture (never at import),
the file skips from there, nothing starts a child process.  The memory
fits of whole steps live in ``tests/benchmark_checks/test_benchmark_aot_fit.py``
(the benchmark's file); where only one process may load the TPU's library
and that file's worker holds it, this file skips.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from tests.test_flash_attention import CELL_SHAPES

#: (B, L, H, Hkv, D, Dv) → which backward the chooser must hand it.
SHAPES = {
    **{cell: (shape, "fused") for cell, shape in CELL_SHAPES.items()},
    # The longest bf16 head of 128 whose dq still fits the budget.
    "fused_at_the_budget": ((1, 32768, 2, 1, 128, 128), "fused"),
    "split_past_the_budget": ((1, 65536, 1, 1, 128, 128), "split"),
}


@pytest.fixture(scope="module")
def v5e():
    """A described v5e:2x2 to compile for, Mosaic kernels compiled (not
    interpreted) and the persistent cache off (a described-device entry
    cannot be read back without a chip, and warns)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    from distributed_machine_learning_tpu.ops.pallas import flash_attention

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe: skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    mp = pytest.MonkeyPatch()
    mp.setattr(flash_attention, "_interpret", lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()
    mp.undo()


@pytest.fixture(scope="module")
def one_chip(v5e):
    return SingleDeviceSharding(v5e.devices[0])


@pytest.mark.parametrize("name", SHAPES)
def test_backward_kernels_compile_for_v5e(one_chip, name):
    from distributed_machine_learning_tpu.ops.pallas.flash_attention import (
        _flash_bwd,
    )

    (B, L, H, Hkv, D, Dv), which = SHAPES[name]
    groups = H // Hkv

    def arg(heads, width, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct((B * heads, L, width), dtype,
                                    sharding=one_chip)

    row = jax.ShapeDtypeStruct((B * H, 1, L), jnp.float32, sharding=one_chip)
    text = jax.jit(lambda *a: _flash_bwd(*a, kv_groups=groups)).lower(
        arg(H, D), arg(Hkv, D), arg(Hkv, Dv), arg(H, Dv), row, row,
    ).compile().as_text()
    assert "tpu_custom_call" in text
    suffix = "" if D == Dv else f"_qk{D}v{Dv}"
    kernels = {base: f"flash_bwd_{base}{suffix}" in text
               for base in ("fused", "dq", "dkv")}
    assert kernels == {"fused": which == "fused", "dq": which == "split",
                       "dkv": which == "split"}


#: (L, H, Hkv, D, window, fused backward?): the window cell's two attention
#: shapes, and the windowed dQ + dK/dV split (no cell's shape reaches it: the
#: chooser is overridden) at a length whose band is cut at both ends.
WINDOW_SHAPES = {
    "trinity_window_layer": (16384, 32, 4, 128, 2048, True),
    "trinity_full_layer": (16384, 32, 4, 128, None, True),
    "windowed_split": (4096, 8, 1, 128, 1024, False),
}


@pytest.mark.parametrize("name", WINDOW_SHAPES)
def test_windowed_kernels_compile_for_v5e(one_chip, monkeypatch, name):
    """Forward and backward through ``flash_self_attention``: the band's
    grids, the index maps clamped on both sides and the ``_w<window>`` names
    pass Mosaic, and the fused kernel's VMEM fits at L 16 384."""
    import re

    from distributed_machine_learning_tpu.ops.pallas import flash_attention

    L, H, Hkv, D, window, fused = WINDOW_SHAPES[name]
    if not fused:
        monkeypatch.setattr(flash_attention, "_bwd_fused", lambda *_: False)

    def loss(q, k, v):
        return jnp.sum(flash_attention.flash_self_attention(
            q, k, v, window=window).astype(jnp.float32))

    args = [jax.ShapeDtypeStruct((1, L, h, D), jnp.bfloat16,
                                 sharding=one_chip) for h in (H, Hkv, Hkv)]
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        *args).compile().as_text()
    suffix = "" if window is None else f"_w{window}"
    backward = ({"flash_bwd_fused"} if fused
                else {"flash_bwd_dq", "flash_bwd_dkv"})
    kernels = re.findall(
        r"(?<![A-Za-z_])flash_(?:fwd|bwd_[a-z]+)(?:_w\d+)?", text)
    assert set(kernels) == {
        base + suffix for base in {"flash_fwd"} | backward}


def test_the_delta_rules_gradient_compiles_for_v5e(one_chip, monkeypatch):
    """``jax.grad`` of ``gated_delta_rule`` at ``q3next_a3b_dp_s8192``'s
    shape: the preparation kernels' blocks, their float32 contractions and
    what a grid step keeps in VMEM pass Mosaic, and the compiled text holds
    the preparation once (the forward pass's, kept for the backward pass),
    its reverse once, the state kernels twice (forward, made again) and
    once, and no triangular solve."""
    from distributed_machine_learning_tpu.ops import delta_rule, hlo
    from distributed_machine_learning_tpu.ops.pallas import (
        common,
        gdn_prepare,
        gdn_state,
    )

    for module in (common, gdn_state, gdn_prepare, delta_rule):
        monkeypatch.setattr(module, "interpret", lambda: False)
    wide = jax.ShapeDtypeStruct((1, 8192, 32, 128), jnp.bfloat16,
                                sharding=one_chip)
    number = jax.ShapeDtypeStruct((1, 8192, 32), jnp.float32,
                                  sharding=one_chip)
    # A loss whose gradient needs the output: a plain sum's would let the
    # compiler drop the forward pass.
    loss = lambda *a: jnp.sum(jnp.square(
        delta_rule.gated_delta_rule(*a).astype(jnp.float32)))
    text = jax.jit(jax.grad(loss, argnums=range(5))).lower(
        wide, wide, wide, number, number).compile().as_text()
    kernels = list(hlo._mosaic_kernels(text))
    assert {name: kernels.count(name) for name in set(kernels)} == {
        "gdn_prepare_fwd": 1, "gdn_prepare_bwd": 1,
        "gdn_state_fwd": 2, "gdn_state_bwd": 1}
    assert hlo.prepare_calls_from_hlo(text) == {
        "gdn_prepare_fwd_calls": 1, "gdn_prepare_bwd_calls": 1,
        "kda_prepare_fwd_calls": 0, "kda_prepare_bwd_calls": 0}
    assert "triangular" not in text.lower()


def test_the_per_channel_rules_gradient_compiles_for_v5e(one_chip,
                                                         monkeypatch):
    """``jax.grad`` of ``gated_delta_rule`` with a decay a key channel at
    ``kimi_linear_dp_s8192``'s shape (8192 tokens, 32 heads of 128): the
    stabilised preparation's sub-block steps, their sublane broadcasts and
    what a grid step of ``kda_prepare``'s head blocks keeps in VMEM pass
    Mosaic (at 8 reverse heads a step they do not: 31.3 MiB of 16), and the
    compiled text holds the per-channel kernels as the scalar rule holds
    its own — preparation once, reverse once — and none of the scalar
    ones."""
    from distributed_machine_learning_tpu.ops import delta_rule, hlo
    from distributed_machine_learning_tpu.ops.pallas import (
        common,
        gdn_prepare,
        gdn_state,
        kda_prepare,
    )

    for module in (common, gdn_state, gdn_prepare, kda_prepare, delta_rule):
        monkeypatch.setattr(module, "interpret", lambda: False)
    wide = jax.ShapeDtypeStruct((1, 8192, 32, 128), jnp.bfloat16,
                                sharding=one_chip)
    decay = jax.ShapeDtypeStruct((1, 8192, 32, 128), jnp.float32,
                                 sharding=one_chip)
    number = jax.ShapeDtypeStruct((1, 8192, 32), jnp.float32,
                                  sharding=one_chip)
    loss = lambda *a: jnp.sum(jnp.square(
        delta_rule.gated_delta_rule(*a).astype(jnp.float32)))
    text = jax.jit(jax.grad(loss, argnums=range(5))).lower(
        wide, wide, wide, decay, number).compile().as_text()
    kernels = list(hlo._mosaic_kernels(text))
    assert {name: kernels.count(name) for name in set(kernels)} == {
        "kda_prepare_fwd": 1, "kda_prepare_bwd": 1,
        "kda_state_fwd": 2, "kda_state_bwd": 1}
    assert hlo.prepare_calls_from_hlo(text) == {
        "gdn_prepare_fwd_calls": 0, "gdn_prepare_bwd_calls": 0,
        "kda_prepare_fwd_calls": 1, "kda_prepare_bwd_calls": 1}
    assert "triangular" not in text.lower()


@pytest.mark.parametrize("remat", [None, "mlp", "block"], ids=str)
def test_a_compiled_step_publishes_its_flash_calls(v5e, remat, tmp_path):
    """``flash_fwd_calls`` == ``flash_bwd_calls`` == the layers that run the
    kernel, whatever is recomputed: what the compiler kept of a four-chip
    data-parallel step, read from its HLO text under an installed
    ``Telemetry`` (the kernels are Mosaic calls only in a program compiled
    for the TPU; nothing here runs)."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from distributed_machine_learning_tpu.models.transformer import (
        TransformerLM,
    )
    from distributed_machine_learning_tpu.telemetry import (
        Telemetry,
        set_telemetry,
    )
    from distributed_machine_learning_tpu.train import lm_step

    layers, seq = 3, 512
    model = TransformerLM(
        vocab_size=256, d_model=256, n_layers=layers, n_heads=2,
        n_kv_heads=1, attn_impl="flash", compute_dtype=jnp.bfloat16,
        remat=remat is not None, remat_policy=remat or "mlp")
    mesh = Mesh(np.array(v5e.devices).reshape(4, 1), ("batch", "seq"))
    rep = NamedSharding(mesh, P())
    state = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep),
        jax.eval_shape(lambda: lm_step.init_lm_state(model)))
    tokens = jax.ShapeDtypeStruct(
        (4, seq), jnp.int32, sharding=NamedSharding(mesh, P("batch", "seq")))
    step = lm_step.make_lm_train_step(model, mesh=mesh)
    telemetry = Telemetry(str(tmp_path), fsync=False)
    previous = set_telemetry(telemetry)
    try:
        step._publish(state, tokens, tokens)
    finally:
        set_telemetry(previous)
        telemetry.close()
    snapshot = telemetry.registry.snapshot()["gauges"]
    gauges = {g["name"]: g["value"] for g in snapshot}
    assert gauges["flash_fwd_calls"] == gauges["flash_bwd_calls"] == layers
    assert gauges["grad_sync_bytes"] > 0
    assert gauges["xla_remat_instructions"] == 0  # it fits as written
    # A recomputed block keeps what its flash call reads and makes, folded:
    # q [B·H, L, D], k and v [B·Hkv, L, D] (a batch row a chip), bf16;
    # ``out`` and the float32 ``lse`` [B·H, 1, L].
    kept = {g["labels"]["tag"]: g["value"] for g in snapshot
            if g["name"] == "remat_kept_bytes"}
    head = 256 // 2
    assert kept == ({} if remat != "block" else {
        "flash_q": layers * 2 * seq * head * 2,
        "flash_k": layers * seq * head * 2, "flash_v": layers * seq * head * 2,
        "flash_out": layers * 2 * seq * head * 2,
        "flash_lse": layers * 2 * seq * 4})
