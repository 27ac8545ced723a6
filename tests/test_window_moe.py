"""``models/window_moe.py`` (``model_type`` ``afmoe``) against its plain
reference at a tiny size; the flash kernels with a window, interpreted,
against dense masked attention; rotation on the window layers and on no
other; the chip's share identity; the balancing rule that moves the selection
bias inside ``train/lm_step.py``; the configuration file against the
published one; and the model on the normal path: ``cli.lm --model-config``."""

from __future__ import annotations

import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import window_moe_lm as reference
from benchmark.reference.transformer_lm import get_leaf, with_leaves
from distributed_machine_learning_tpu.models import hybrid_moe as hm
from distributed_machine_learning_tpu.models import window_moe as wm
from distributed_machine_learning_tpu.models.transformer import _repeat_kv
from distributed_machine_learning_tpu.ops.pallas import flash_attention
from distributed_machine_learning_tpu.ops.pallas.flash_attention import (
    flash_self_attention,
)
from distributed_machine_learning_tpu.ops.ring_attention import (
    dense_self_attention,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SLIDING, FULL = "sliding_attention", "full_attention"
TINY = {
    "model_type": "afmoe", "vocab_size": 97, "hidden_size": 32,
    "num_hidden_layers": 5, "num_dense_layers": 1,
    "layer_types": [SLIDING, SLIDING, SLIDING, SLIDING, FULL],
    "sliding_window": 16, "intermediate_size": 48, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "rope_theta": 10000,
    "num_experts": 8, "num_experts_per_tok": 3, "moe_intermediate_size": 16,
    "num_shared_experts": 1, "route_norm": True, "route_scale": 2.826,
    "score_func": "sigmoid", "n_group": 1, "topk_group": 1,
    "rms_norm_eps": 1e-5, "mup_enabled": True, "load_balance_coeff": 0.001,
    "rope_scaling": None, "tie_word_embeddings": False,
}
#: float32 on both sides at a tiny size: the two differ by the order of
#: their sums and by the sort (1e-6 of a tensor's largest entry was the worst
#: seen).  A dropped term is orders of magnitude off.
TOL = 1e-4


def _name(path):
    return "/".join(str(getattr(k, "key", k)) for k in path)


def _randomized(params, seed):
    """Every leaf redrawn, so that a dropped norm weight or bias shows:
    kernels at half a fan-in scale, norm weights around one, the selection
    bias ±0.2 (beside sigmoid scores that then spread over (0.2, 0.8))."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))

    def draw(key, path, a):
        name, noise = _name(path), jax.random.normal(key, a.shape)
        if name.endswith("e_score_correction_bias"):
            return jax.random.uniform(key, a.shape, minval=-0.2, maxval=0.2)
        if a.ndim == 1:
            return 1.0 + 0.3 * noise
        if "embedding" in name:
            return noise
        return 0.5 * noise / np.sqrt(a.shape[-2])

    return jax.tree_util.tree_unflatten(
        treedef, [draw(k, p, a) for k, (p, a) in zip(keys, leaves)])


def _compared(config, attn_impl="dense", length=71):
    from distributed_machine_learning_tpu.train.losses import lm_cross_entropy

    model = wm.WindowMoELM(wm.WindowMoESizes.from_config(config),
                           attn_impl=attn_impl)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, length), 0, 97)
    targets = jax.random.randint(jax.random.PRNGKey(5), (2, length), 0, 97)
    params = _randomized(
        model.init(jax.random.PRNGKey(1), tokens)["params"], seed=3)

    def loss_and_logits(p):
        logits = model.apply({"params": p}, tokens)
        return lm_cross_entropy(logits, targets), logits

    (loss, logits), grads = jax.value_and_grad(
        loss_and_logits, has_aux=True)(params)
    ref_loss, ref_grads = jax.value_and_grad(
        lambda p: reference.loss(p, config, tokens, targets))(params)
    return dict(model=model, params=params, tokens=tokens, targets=targets,
                logits=logits, loss=loss, grads=grads, ref_loss=ref_loss,
                ref_grads=ref_grads, config=config)


@pytest.fixture(scope="module")
def tiny():
    return _compared(TINY)


def _off(logits, ref):
    return float(jnp.abs(logits - ref).max() / jnp.abs(ref).max())


def _assert_matches_the_reference(run):
    ref = reference.logits(run["params"], run["config"], run["tokens"])
    assert _off(run["logits"], ref) < TOL
    assert float(run["loss"]) == pytest.approx(float(run["ref_loss"]),
                                               rel=1e-6)
    flat = jax.tree_util.tree_flatten_with_path(run["grads"])[0]
    ref_grads = jax.tree_util.tree_leaves(run["ref_grads"])
    assert len(flat) == len(ref_grads)
    worst = {}
    for (path, a), b in zip(flat, ref_grads):
        name = _name(path)
        if name.endswith("e_score_correction_bias"):
            # it picks and never weighs: no gradient on either side
            assert not a.any() and not b.any(), name
            continue
        worst[name] = float(jnp.abs(a - b).max() / jnp.abs(b).max())
    assert max(worst.values()) < TOL, max(worst, key=worst.get)
    return worst


def test_logits_loss_and_every_gradient_match_the_reference(tiny):
    worst = _assert_matches_the_reference(tiny)
    assert len(worst) > 10 * TINY["num_hidden_layers"]
    # four norms a block
    assert {n.split("/")[1] for n in worst if n.startswith("block_2/")
            and n.endswith("layernorm/weight")} == {
        "input_layernorm", "post_attention_layernorm", "pre_mlp_layernorm",
        "post_mlp_layernorm"}


@pytest.mark.parametrize("kind, layer_types", [
    ("a window layer", [SLIDING, SLIDING]), ("a full layer", [FULL, FULL])])
def test_one_kind_of_layer_matches_the_reference(kind, layer_types):
    """A dense and a sparse layer of one attention kind alone, through the
    kernels (interpreted; 71 tokens are padded to 512, the window of 16 cuts
    the one tile's band)."""
    config = {**TINY, "num_hidden_layers": 2, "layer_types": layer_types}
    _assert_matches_the_reference(_compared(config, attn_impl="flash"))


def test_the_whole_model_matches_through_the_kernels(tiny):
    from distributed_machine_learning_tpu.train.losses import lm_cross_entropy

    model = tiny["model"].clone(attn_impl="flash")
    loss, grads = jax.value_and_grad(lambda p: lm_cross_entropy(
        model.apply({"params": p}, tiny["tokens"]), tiny["targets"]))(
        tiny["params"])
    assert float(loss) == pytest.approx(float(tiny["ref_loss"]), rel=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(tiny["ref_grads"])):
        assert float(jnp.abs(a - b).max()) <= TOL * float(
            jnp.abs(b).max()) + 1e-12


def _zeroed(path, columns=slice(None)):
    def change(params, config):
        leaf = get_leaf(params, path)
        return with_leaves(params, {
            path: leaf.at[..., columns].set(0.0)}), config
    return change


def _swapped(params, config):
    """Every layer's kind exchanged and the window opened past the sequence:
    what is visible stays, only WHICH layers rotate is exchanged."""
    other = {SLIDING: FULL, FULL: SLIDING}
    return params, {**config, "sliding_window": 10**6,
                    "layer_types": [other[t] for t in config["layer_types"]]}


@pytest.mark.parametrize("what, change", [
    ("the window", lambda p, c: (p, {**c, "sliding_window": 10**6})),
    ("rotation on the window layers and no other", _swapped),
    ("the gate", _zeroed("block_1/attn/q_proj/kernel", slice(64, None))),
    ("the key norm", _zeroed("block_4/attn/k_norm/weight")),
    ("the norm behind attention",
     _zeroed("block_2/post_attention_layernorm/weight")),
    ("the norm behind the feed-forward",
     _zeroed("block_0/post_mlp_layernorm/weight")),
    ("the embedding's scale", lambda p, c: (p, {**c, "mup_enabled": False})),
    ("the bias in the selection",
     _zeroed("block_1/moe/e_score_correction_bias")),
    ("the scale 2.826", lambda p, c: (p, {**c, "route_scale": 1.0})),
    ("the shared expert", _zeroed("block_2/moe/shared_down_proj/kernel")),
    ("the dense layer", _zeroed("block_0/mlp/down_proj/kernel")),
])
def test_a_dropped_term_breaks_the_tolerance(tiny, what, change):
    """The comparison sees each term: dropping one on the reference's side
    alone moves its logits far past ``TOL`` from the system's."""
    params, config = change(tiny["params"], TINY)
    ref = reference.logits(params, config, tiny["tokens"])
    assert _off(tiny["logits"], ref) > 50 * TOL, what


def test_the_mixer_s_rotation_and_plain_norms_are_the_reference_s():
    """Which layers rotate is the dropped-term test's to show (the layer
    kinds exchanged); here: the rotation itself is the reference's, over all
    of the head, and the head norms' weights start at one (plain)."""
    from distributed_machine_learning_tpu.models.transformer import apply_rope

    q = jax.random.normal(jax.random.PRNGKey(0), (1, 40, 4, 16))
    got = apply_rope(q, jnp.arange(40), 1e4, 16)
    assert float(jnp.abs(got[0] - reference.rope(q[0], 1e4)).max()) < 1e-5
    assert float(jnp.abs(got - q)[:, 1:].max()) > 0.5
    assert np.array_equal(np.asarray(got[:, 0]), np.asarray(q[:, 0]))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 40, 32))
    attn = hm.GatedAttention(
        n_heads=4, n_kv_heads=2, head_dim=16, rotary_dim=0, rope_base=1e4,
        eps=1e-5, attn_impl="dense", compute_dtype=jnp.float32, window=5,
        zero_centred_norm=False)
    params = attn.init(jax.random.PRNGKey(2), x, jnp.arange(40))["params"]
    assert (params["q_norm"]["weight"] == 1.0).all()
    assert (params["k_norm"]["weight"] == 1.0).all()


@pytest.mark.parametrize("policy", ["mlp", "block"])
def test_recomputation_changes_no_number(tiny, policy):
    from distributed_machine_learning_tpu.train.losses import lm_cross_entropy

    model = tiny["model"].clone(remat=True, remat_policy=policy)
    loss, grads = jax.value_and_grad(lambda p: lm_cross_entropy(
        model.apply({"params": p}, tiny["tokens"]), tiny["targets"]))(
        tiny["params"])
    assert float(loss) == pytest.approx(float(tiny["loss"]), rel=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(tiny["grads"])):
        assert float(jnp.abs(a - b).max()) <= 1e-5 * float(jnp.abs(b).max())


# ---------------------------------------------- the kernels with a window

#: (B, L, H, Hkv, D, Dv, window).  L 1024 tiles as 2 × 2 blocks of 512, 1536
#: as 3 × 3, 384 as 3 × 3 of 128 (a tile wholly below the band from the third
#: row on); 200 is padded to 512.
WINDOW_CASES = {
    "w_under_a_block": (1, 1024, 2, 2, 16, 16, 100),
    "w_no_multiple_of_the_block": (1, 1536, 2, 1, 16, 16, 700),
    "w_a_block_and_one": (1, 384, 2, 2, 16, 16, 129),
    "band_of_three_tiles": (1, 384, 1, 1, 16, 16, 200),
    "padded": (2, 200, 2, 1, 16, 16, 77),
    "gqa8": (1, 384, 8, 1, 16, 16, 100),
    "qk24v16": (1, 384, 2, 2, 24, 16, 130),
    "w_one": (1, 256, 1, 1, 16, 16, 1),
}


def _qkvg(B, L, H, Hkv, D, Dv, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return tuple(jax.random.normal(k, s).astype(dtype) for k, s in zip(ks, (
        (B, L, H, D), (B, L, Hkv, D), (B, L, Hkv, Dv), (B, L, H, Dv))))


@pytest.mark.parametrize("backward", ["fused", "split"])
@pytest.mark.parametrize("case", WINDOW_CASES)
def test_windowed_kernels_match_dense_masked_attention(
        monkeypatch, case, backward):
    """Out, dq, dk and dv in interpret mode, through the fused backward
    kernel and through the dQ + dK/dV split."""
    B, L, H, Hkv, D, Dv, window = WINDOW_CASES[case]
    monkeypatch.setattr(flash_attention, "_bwd_fused",
                        lambda *_: backward == "fused")
    q, k, v, ct = _qkvg(B, L, H, Hkv, D, Dv, seed=L + window)
    rep = H // Hkv

    def dense(q, k, v):
        return dense_self_attention(q, _repeat_kv(k, rep), _repeat_kv(v, rep),
                                    window=window)

    out, vjp = jax.vjp(
        lambda *a: flash_self_attention(*a, window=window), q, k, v)
    want, want_vjp = jax.vjp(dense, q, k, v)
    assert out.shape == (B, L, H, Dv)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    for got, ref, name in zip(vjp(ct), want_vjp(ct), ("dq", "dk", "dv")):
        assert got.shape == ref.shape, name
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), rtol=2e-4,
            atol=2e-5 * max(1.0, float(jnp.abs(ref).max())),
            err_msg=f"{case}: {name}")


def test_dense_attention_s_window_is_the_band():
    q, k, v, _ = _qkvg(1, 12, 1, 1, 4, 4)
    scores = jnp.einsum("qd,kd->qk", q[0, :, 0], k[0, :, 0]) / 2.0
    i, j = jnp.arange(12)[:, None], jnp.arange(12)[None, :]
    weights = jax.nn.softmax(
        jnp.where((j <= i) & (j > i - 5), scores, -jnp.inf), -1)
    got = dense_self_attention(q, k, v, window=5)
    assert float(jnp.abs(got[0, :, 0] - weights @ v[0, :, 0]).max()) < 1e-6
    assert (weights > 0).sum(-1).tolist() == [1, 2, 3, 4] + [5] * 8


@pytest.mark.parametrize("window", [None, 1024, 5000], ids=str)
@pytest.mark.parametrize("backward", ["fused", "split"])
def test_no_window_and_a_window_of_the_length_are_the_windowless_call(
        monkeypatch, window, backward):
    """Bit for bit: out and all three gradients."""
    monkeypatch.setattr(flash_attention, "_bwd_fused",
                        lambda *_: backward == "fused")
    q, k, v, ct = _qkvg(1, 1024, 2, 1, 16, 16, seed=9)
    out, vjp = jax.vjp(flash_self_attention, q, k, v)
    got, got_vjp = jax.vjp(
        lambda *a: flash_self_attention(*a, window=window), q, k, v)
    assert np.array_equal(np.asarray(out), np.asarray(got))
    for a, b in zip(vjp(ct), got_vjp(ct)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    text = str(jax.make_jaxpr(
        lambda *a: flash_self_attention(*a, window=window))(q, k, v))
    assert "flash_fwd" in text and "_w" not in "".join(
        re.findall(r"flash_fwd\w*", text))
    with pytest.raises(ValueError, match="at least 1"):
        flash_self_attention(q, k, v, window=0)


def test_the_window_s_grids_tiles_and_names():
    f = flash_attention
    assert f.active_tiles(16384, 2048) == (150, 528)
    assert f.active_tiles(8192, 2048) == (70, 136)
    assert f.active_tiles(16384) == f.active_tiles(16384, 16384) == (528, 528)
    assert f.active_tiles(384, 100) == (5, 6)
    assert f.active_tiles(200, 77) == (1, 1)  # padded to one tile of 512
    # the inner grid axis spans the band: ⌈(w − 1)/block⌉ + 1 tiles
    (n_q, steps), k_block = f._q_major_grid(16384, 512, 512, 2048)
    assert (n_q, steps) == (32, 5)
    assert [int(k_block(9, s)) for s in range(5)] == [5, 6, 7, 8, 9]
    assert [int(k_block(1, s)) for s in range(5)] == [0, 1, 1, 1, 1]
    (n_k, steps), q_block = f._k_major_grid(16384, 512, 512, 2048)
    assert (n_k, steps) == (32, 5)
    assert [int(q_block(9, s)) for s in range(5)] == [9, 10, 11, 12, 13]
    assert [int(q_block(30, s)) for s in range(5)] == [30, 31, 31, 31, 31]
    assert f._q_major_grid(16384, 512, 512, 2049)[0] == (32, 5)
    assert f._q_major_grid(16384, 512, 512, 2050)[0] == (32, 6)
    assert f._q_major_grid(16384, 512, 512, None)[0] == (32, 32)
    assert f._kernel_name("flash_fwd", 128, 128, 2048) == "flash_fwd_w2048"
    assert f._kernel_name("flash_bwd_fused", 192, 128, 4096) \
        == "flash_bwd_fused_qk192v128_w4096"
    assert f._kernel_name("flash_bwd_dq", 128, 128) == "flash_bwd_dq"


@pytest.mark.parametrize("window, names", [
    (2048, {"flash_fwd_w2048", "flash_bwd_fused_w2048"}),
    (None, {"flash_fwd", "flash_bwd_fused"})], ids=["w2048", "windowless"])
def test_the_cell_s_attention_shapes_lower_to_the_named_kernels(
        monkeypatch, window, names):
    """The program lowered for a TPU at the cell's two attention shapes (L
    16 384, 32 query and 4 key/value heads of 128, a window of 2048 and
    none): the names a device trace tells them apart by."""
    monkeypatch.setattr(flash_attention, "_interpret", lambda: False)

    def loss(q, k, v):
        return jnp.sum(flash_self_attention(
            q, k, v, window=window).astype(jnp.float32))

    args = [jax.ShapeDtypeStruct((1, 16384, h, 128), jnp.bfloat16)
            for h in (32, 4, 4)]
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    assert set(re.findall(r"flash_\w+", text)) == names


# ------------------------------------------------ the chip's share

def _moe_share(first, held):
    return hm.SparseMoE(
        router_width=128, held_experts=(first, held), experts_per_token=8,
        d_ff=16, shared_d_ff=16, norm_topk_prob=True,
        compute_dtype=jnp.float32, score_func="sigmoid", selection_bias=True,
        routed_scale=2.826, shared_gate=False, balance_bias=True)


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The guide's share test at the cell's own split: the routed parts that
    eight chips give, holding experts [0, 16) … [112, 128) of the 128, plus
    the shared expert once, are the layer that holds all 128 — in the
    program and in the reference — and every chip counts the same ``c``."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 40, 32))
    full = _randomized(_moe_share(0, 128).init(
        jax.random.PRNGKey(1), x)["params"], seed=2)
    config = {**TINY, "num_experts": 128, "num_experts_per_tok": 8}
    uncut, sown = _moe_share(0, 128).apply({"params": full}, x,
                                           mutable=[hm.STATS_COLLECTION])
    counts = sown[hm.STATS_COLLECTION][hm.ASSIGNMENTS][0]
    assert int(counts.sum()) == 2 * 40 * 8
    ref_uncut = jnp.stack([reference.moe(row, full, config) for row in x])
    assert float(jnp.abs(uncut - ref_uncut).max()) < 1e-5
    r = reference.rounder(None)
    shared = jnp.stack([reference.shared_expert(row, full, r) for row in x])
    total, ref_total = -7.0 * shared, shared
    for first in range(0, 128, 16):
        part = {**full, **{name: full[name][first:first + 16]
                           for name in ("w_gate", "w_up", "w_down")}}
        out, sown = _moe_share(first, 16).apply(
            {"params": part}, x, mutable=[hm.STATS_COLLECTION])
        assert np.array_equal(
            np.asarray(sown[hm.STATS_COLLECTION][hm.ASSIGNMENTS][0]),
            np.asarray(counts))
        total = total + out
        share = {**config, "num_experts": 16, "router_width": 128,
                 "held_experts": [first, 16]}
        ref_total = ref_total + jnp.stack([
            reference.routed_experts(row, part, share, r) for row in x])
    assert float(jnp.abs(total - uncut).max()) < 1e-5
    assert float(jnp.abs(ref_total - ref_uncut).max()) < 1e-5


# ------------------------------------------------ the balancing rule

def test_the_rule_moves_each_expert_against_its_excess_load():
    counts = jnp.asarray([10, 0, 5, 5, 0, 40, 3, 1])  # mean 8
    b = jnp.asarray([0.5, 0.0, -0.25, 0.0, 0.0, 0.0, 0.0, 0.0])
    got = hm.balanced_bias(b, counts, 0.001)
    want = b + 0.001 * jnp.asarray([-1, 1, 1, 1, 1, -1, 1, 1.0])
    assert np.array_equal(np.asarray(got), np.asarray(want))
    assert np.array_equal(np.asarray(got), np.asarray(
        reference.balanced_bias(b, counts, 0.001)))
    even = hm.balanced_bias(b, jnp.full(8, 7), 0.001)  # at the mean: no move
    assert np.array_equal(np.asarray(even), np.asarray(b))


@pytest.fixture(scope="module")
def one_step():
    """One AdamW step of the tiny model through ``make_lm_train_step`` from a
    state whose selection biases were set off zero, and what the model sowed
    on that state's parameters and batch."""
    from distributed_machine_learning_tpu.train.adamw import AdamWConfig
    from distributed_machine_learning_tpu.train.lm_step import (
        init_lm_state,
        make_lm_train_step,
    )

    model = wm.WindowMoELM(wm.WindowMoESizes.from_config(TINY))
    state = init_lm_state(model, config=AdamWConfig(learning_rate=1e-3))
    assert all(not np.asarray(state.params[f"block_{i}"]["moe"][
        "e_score_correction_bias"]).any() for i in range(1, 5))  # drawn zero
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: 0.05 * jax.random.normal(
            jax.random.PRNGKey(len(_name(path))), a.shape)
        if _name(path).endswith("e_score_correction_bias") else a,
        state.params)
    state = state.replace(params=params)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 40), 0, 97)
    targets = jax.random.randint(jax.random.PRNGKey(1), (2, 40), 0, 97)
    before = jax.tree_util.tree_map(np.asarray, params)
    _, sown = model.apply({"params": params}, tokens, train=True,
                          mutable=[hm.STATS_COLLECTION])
    step = make_lm_train_step(model)
    new_state, _ = step(state, tokens, targets)
    return dict(model=model, before=before, tokens=tokens,
                sown=sown[hm.STATS_COLLECTION],
                after=jax.tree_util.tree_map(np.asarray, new_state.params))


@pytest.mark.parametrize("block", ["block_1", "block_2", "block_3", "block_4"])
def test_one_step_moves_the_bias_by_the_rule_on_its_own_counts(
        one_step, block):
    """``b`` after the step is ``b + u · sign(mean(c) − c)`` to the bit, ``c``
    the layer's own counts of that step — so AdamW neither stepped nor
    decayed it — and the program's counts are the reference's."""
    counts = one_step["sown"][block]["moe"][hm.ASSIGNMENTS][0]
    assert int(counts.sum()) == 2 * 40 * 3
    before = one_step["before"][block]["moe"]["e_score_correction_bias"]
    after = one_step["after"][block]["moe"]["e_score_correction_bias"]
    want = before + np.float32(0.001) * np.sign(
        np.float32(counts.mean()) - np.asarray(counts, np.float32))
    assert np.array_equal(after, want.astype(np.float32))
    assert np.abs(after - before).max() == pytest.approx(0.001, rel=1e-3)
    ref = reference.sparse_counts(one_step["before"], TINY,
                                  one_step["tokens"])[block]
    assert np.array_equal(np.asarray(ref), np.asarray(counts))
    # the router beside it took its AdamW step
    assert not np.array_equal(one_step["after"][block]["moe"]["router"][
        "kernel"], one_step["before"][block]["moe"]["router"]["kernel"])


def test_without_a_rate_the_bias_stays_and_nothing_is_counted():
    """No ``load_balance_coeff`` in the file: no rule, no counts sown, and
    the bias is as frozen as kanana's."""
    from distributed_machine_learning_tpu.train.adamw import AdamWConfig
    from distributed_machine_learning_tpu.train.lm_step import (
        init_lm_state,
        make_lm_train_step,
    )

    config = {k: v for k, v in TINY.items() if k != "load_balance_coeff"}
    config.update(num_hidden_layers=2, layer_types=[SLIDING, FULL])
    model = wm.WindowMoELM(wm.WindowMoESizes.from_config(config))
    assert model.param_rules == {}
    assert wm.WindowMoELM(wm.WindowMoESizes.from_config(TINY)).param_rules \
        .keys() == {"e_score_correction_bias"}
    state = init_lm_state(model, config=AdamWConfig())
    bias = np.linspace(-0.05, 0.05, 8, dtype=np.float32)
    params = with_leaves(state.params, {
        "block_1/moe/e_score_correction_bias": jnp.asarray(bias)})
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 24), 0, 97)
    _, sown = model.apply({"params": params}, tokens, train=True,
                          mutable=[hm.STATS_COLLECTION])
    assert hm.ASSIGNMENTS not in sown[hm.STATS_COLLECTION]["block_1"]["moe"]
    assert "moe_bias_abs_mean" not in model.step_stats(
        sown[hm.STATS_COLLECTION])
    new_state, _ = make_lm_train_step(model)(
        state.replace(params=params), tokens, tokens)
    assert np.array_equal(
        np.asarray(new_state.params["block_1"]["moe"][
            "e_score_correction_bias"]), bias)


def test_a_kanana_shaped_model_s_bias_still_does_not_move():
    from distributed_machine_learning_tpu.models import mla_moe as mm
    from distributed_machine_learning_tpu.train.adamw import AdamWConfig
    from distributed_machine_learning_tpu.train.lm_step import (
        init_lm_state,
        make_lm_train_step,
    )
    from tests.test_mla_moe import TINY as KANANA

    model = mm.MLAMoELM(mm.MLAMoESizes.from_config(
        {**KANANA, "num_hidden_layers": 2}))
    assert not hasattr(model, "param_rules")
    state = init_lm_state(model, config=AdamWConfig())
    drawn = np.asarray(state.params["block_1"]["moe"][
        "e_score_correction_bias"])
    assert np.abs(drawn).max() > 0
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 24), 0, 97)
    new_state, _ = make_lm_train_step(model)(state, tokens, tokens)
    assert np.array_equal(np.asarray(new_state.params["block_1"]["moe"][
        "e_score_correction_bias"]), drawn)


def test_a_step_that_returns_no_counts_refuses_a_model_with_a_rule():
    from distributed_machine_learning_tpu.train.adamw import AdamWConfig
    from distributed_machine_learning_tpu.train.lm_step import (
        init_lm_state,
        make_lm_train_step,
        with_dynamic_scale,
    )

    config = {**TINY, "num_hidden_layers": 2, "layer_types": [SLIDING, FULL]}
    model = wm.WindowMoELM(wm.WindowMoESizes.from_config(config))
    state = with_dynamic_scale(init_lm_state(model, config=AdamWConfig()))
    tokens = jnp.zeros((2, 24), jnp.int32)
    with pytest.raises(ValueError, match="returns the counts"):
        make_lm_train_step(model, dynamic_scale=True)(state, tokens, tokens)


def test_the_step_s_counts_carry_the_new_fields(one_step):
    counts = one_step["model"].step_stats(one_step["sown"])
    assert set(counts) == {
        "moe_held_rows", "moe_load_max_over_mean", "moe_dropped_rows",
        "moe_bias_moved_share", "moe_bias_abs_mean", "moe_bias_updates"}
    biases = [one_step["before"][f"block_{i}"]["moe"][
        "e_score_correction_bias"] for i in range(1, 5)]
    assert float(counts["moe_bias_abs_mean"]) == pytest.approx(
        float(np.mean([np.abs(b).mean() for b in biases])), rel=1e-6)
    assert float(counts["moe_bias_updates"]) == 4.0
    # the kernels' tiles are counted where the kernels run
    model = one_step["model"].clone(attn_impl="flash")
    tokens = jnp.zeros((1, 384), jnp.int32)
    shapes = jax.eval_shape(lambda p: model.step_stats(model.apply(
        {"params": p}, tokens, mutable=[hm.STATS_COLLECTION])[1][
        hm.STATS_COLLECTION]), one_step["before"])
    assert {"attn_active_tile_share", "attn_window_calls"} <= set(shapes)


# ------------------------------------------ the configuration file

#: The catalog's row for arcee-ai/Trinity-Mini (``config.json`` as published).
PUBLISHED = {
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 6144,
    "layer_types": [SLIDING, SLIDING, SLIDING, FULL] * 8,
    "load_balance_coeff": 0.001, "max_position_embeddings": 131072,
    "model_type": "afmoe", "moe_intermediate_size": 1024,
    "mup_enabled": True, "n_group": 1, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_expert_groups": 1, "num_experts": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 32,
    "num_key_value_heads": 4, "num_limited_groups": 1,
    "num_shared_experts": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "route_norm": True, "route_scale": 2.826,
    "score_func": "sigmoid", "sliding_window": 2048,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True,
    "vocab_size": 200192}


def _committed(rel):
    with open(os.path.join(REPO, rel), encoding="utf-8") as f:
        return json.load(f)


def test_the_configuration_file_keeps_every_published_width():
    config = _committed("benchmark/configs/trinity_mini_26b_a3b.json")
    changed = sorted(k for k, v in PUBLISHED.items() if config[k] != v)
    assert changed == sorted(config["reduced"]) == [
        "layer_types", "num_dense_layers", "num_experts",
        "num_hidden_layers", "vocab_size"]
    assert config["published"] == {k: PUBLISHED[k] for k in changed}
    # the guide's floors: one whole period of 4 sparse layers behind the
    # dense one, 8 experts at least, an eighth of the vocabulary; the router
    # keeps its width
    assert config["layer_types"] == [SLIDING] * 4 + [FULL]
    assert config["num_hidden_layers"] - config["num_dense_layers"] == 4
    assert config["num_experts"] == 16 >= 8
    assert config["held_experts"] == [0, 16] and config["router_width"] == 128
    assert config["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    for key in ("embedding_scale", "sandwich_norms", "attention", "layout",
                "e_score_correction_bias", "balancing"):
        assert key in config["assumed"], key
    for key in ("departures", "memory", "deployment", "expert_load"):
        assert config[key], key
    sizes = wm.WindowMoESizes.from_config(config)
    assert (sizes.d_model, sizes.n_heads, sizes.n_kv_heads, sizes.head_dim,
            sizes.window, sizes.expert_d_ff, sizes.shared_d_ff,
            sizes.dense_d_ff, sizes.experts_per_token, sizes.routed_scale,
            sizes.rope_base, sizes.balance_rate, sizes.rms_eps) == (
        2048, 32, 4, 128, 2048, 1024, 1024, 6144, 8, 2.826, 1e4, 0.001, 1e-5)
    assert sizes.embed_scale == pytest.approx(2048 ** 0.5)
    manifest = _committed("BENCHMARK.json")
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "trinity_mini_26b_a3b")
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == ("https://huggingface.co/arcee-ai/Trinity-Mini"
                               "/blob/main/config.json")
    assert entry is manifest["configs"][-1]


def test_the_cell_and_its_traffic_are_the_issue_s():
    manifest = _committed("BENCHMARK.json")
    cell = manifest["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        "trinity_mini_dp_s16384", "trinity_mini_26b_a3b",
        "dp_1x16384_remat_lr5e-6", 1)
    assert len(manifest["workloads"]) == 7 and len(manifest["configs"]) == 5
    assert sum(c["chips"] == 4 for c in manifest["workloads"]) == 1
    by_name = {m["name"]: m for m in
               manifest["end_to_end"] + manifest["per_layer"]}
    for name in ("tokens_per_s_chip", "kernel.pallas_ms"):
        assert by_name[name]["workloads"][-1] == cell["name"]
    assert manifest["per_layer"][-1]["name"] == "place.lead_ms"
    traffic = _committed("benchmark/traffic/dp_1x16384_remat_lr5e-6.json")
    assert traffic["argv"] == [
        "--parallel", "dp", "--compute-dtype", "bfloat16", "--attn", "flash",
        "--optimizer", "adamw", "--fused-ce-chunks", "8", "--remat",
        "--remat-policy", "block", "--lr", "5e-6"]
    assert (traffic["seq_len"], traffic["seqs_per_chip"],
            traffic["check_seqs"], traffic["warm_iters"],
            traffic["trace_steps"]) == (16384, 1, 1, 2, 5)


# ------------------------------------------------------- the normal path

CLI_SIZES = {**TINY, "vocab_size": 128, "hidden_size": 64,
             "sliding_window": 100, "num_experts": 4, "router_width": 16,
             "held_experts": [4, 4]}


def test_cli_lm_trains_the_model_from_a_configuration_file(tmp_path, capsys):
    """Three iterations through ``make_lm_train_step`` and ``train_epoch`` on
    the 8 virtual devices, the windowed kernels interpreted (384 tokens: 3 × 3
    tiles of 128, the band of 100 leaves 5 of 6), whole blocks recomputed;
    the counts reach the step rows one step late; AdamW moves the router and
    the rule alone moves the selection bias."""
    from distributed_machine_learning_tpu.cli import lm as cli
    from distributed_machine_learning_tpu.train import lm_step

    config_file = tmp_path / "config.json"
    config_file.write_text(json.dumps(CLI_SIZES))
    result = cli.main([
        "--parallel", "dp", "--model-config", str(config_file), "--seq-len",
        "384", "--batch-size", "8", "--max-iters", "3", "--compute-dtype",
        "bfloat16", "--attn", "flash", "--optimizer", "adamw",
        "--fused-ce-chunks", "2", "--remat", "--remat-policy", "block",
        "--lr", "5e-6", "--telemetry-dir", str(tmp_path / "telemetry")])
    assert "d_model=64 layers=5" in capsys.readouterr().out
    assert isinstance(result.train_step, lm_step._StepWithStats)
    assert int(result.state.step) == 3
    params = result.state.params
    assert "moe" not in params["block_0"] and "mlp" not in params["block_1"]
    assert params["block_1"]["moe"]["w_up"].shape == (4, 64, 16)
    assert params["block_1"]["attn"]["q_proj"]["kernel"].shape == (64, 128)
    assert params["block_1"]["attn"]["k_proj"]["kernel"].shape == (64, 32)
    for block in ("block_1", "block_4"):
        units = np.asarray(
            params[block]["moe"]["e_score_correction_bias"]) / 0.001
        # three steps of ±u (or none, at the mean) from zero
        assert np.abs(units - np.round(units)).max() < 1e-3
        assert 1.0 <= np.abs(units).max() <= 3.0 + 1e-3
    rows = [json.loads(line) for line in
            (tmp_path / "telemetry" / "metrics.jsonl").read_text().splitlines()]
    rows = [r for r in rows if "data_wait_s" in r]
    assert len(rows) == 3 and "moe_held_rows" not in rows[0]
    for row in rows[1:]:
        # 384 tokens a chip x 3 a token x 4 of 16 experts = 288 expected
        assert 120 < row["moe_held_rows"] < 480
        assert row["moe_dropped_rows"] == 0.0
        assert row["attn_active_tile_share"] == pytest.approx(26 / 30)
        assert row["attn_window_calls"] == 4.0
        assert row["moe_bias_updates"] == 4.0
    assert rows[1]["moe_bias_abs_mean"] == 0.0  # step 0 selected with b = 0
    assert 0.0 < rows[2]["moe_bias_abs_mean"] <= 0.001 * (1 + 1e-6)
    prom = (tmp_path / "telemetry" / "metrics.prom").read_text()
    assert "moe_bias_updates_total 8" in prom
    assert "attn_window_calls_total 8" in prom
    assert "moe_dropped_rows_total 0" in prom


@pytest.mark.parametrize("change, message", [
    ({}, "--parallel dp only"),
    ({"score_func": "softmax"}, "score_func"),
    ({"layer_types": [SLIDING, FULL]}, "layer_types"),
    ({"layer_types": [SLIDING] * 4 + ["chunked_attention"]}, "layer_types"),
    ({"num_dense_layers": 5}, "num_dense_layers"),
    ({"held_experts": [0, 8]}, "held_experts"),
])
def test_model_config_refuses_what_it_cannot_honour(tmp_path, change, message):
    from distributed_machine_learning_tpu.cli import lm as cli

    config_file = tmp_path / "config.json"
    config_file.write_text(json.dumps({**CLI_SIZES, **change}))
    parallel = "dp" if change else "ring"
    args = cli.make_parser().parse_args(
        ["--model-config", str(config_file), "--parallel", parallel,
         "--batch-size", "8"])
    with pytest.raises(ValueError, match=message):
        cli.build(args)
