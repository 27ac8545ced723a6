"""``models/preroute_moe.py`` (``model_type`` ``smallthinker``) against its
plain reference at a tiny size; the flash kernels at seven query heads a
key/value head with a window, interpreted, against dense masked attention;
the router reading the block's input and not the attention's output; the
chip's share identity with no shared expert; the ReGLU gate count; and the
four configuration models the benchmark already runs, whose lowering the
settings this model added must leave as it was."""

from __future__ import annotations

import hashlib
import json
import os
import re
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import preroute_moe_lm as reference
from benchmark.reference.transformer_lm import get_leaf, with_leaves
from distributed_machine_learning_tpu.models import hybrid_moe as hm
from distributed_machine_learning_tpu.models import preroute_moe as pm
from distributed_machine_learning_tpu.models.transformer import _repeat_kv
from distributed_machine_learning_tpu.ops.pallas import flash_attention
from distributed_machine_learning_tpu.ops.pallas.flash_attention import (
    FLASH_RESIDUAL_NAMES,
    flash_self_attention,
)
from distributed_machine_learning_tpu.ops.ring_attention import (
    dense_self_attention,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: One period (full NoPE, then three rotated window layers), a window
#: shorter than the sequence, 7 query heads a key/value head, 8 experts
#: top-3, ReGLU.
TINY = {
    "model_type": "smallthinker", "vocab_size": 97, "hidden_size": 64,
    "num_hidden_layers": 4, "sliding_window_layout": [0, 1, 1, 1],
    "rope_layout": [0, 1, 1, 1], "sliding_window_size": 16,
    "num_attention_heads": 7, "num_key_value_heads": 1, "head_dim": 16,
    "rope_theta": 1500000, "moe_num_primary_experts": 8,
    "moe_num_active_primary_experts": 3, "moe_ffn_hidden_size": 32,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "hidden_act": "relu", "rms_norm_eps": 1e-6,
}
#: float32 on both sides at a tiny size: the two differ by the order of
#: their sums and by the sort (a few 1e-6 of a tensor's largest entry in the
#: sibling models).  A dropped term is orders of magnitude off.
TOL = 1e-4


def _name(path):
    return "/".join(str(getattr(k, "key", k)) for k in path)


def _randomized(params, seed):
    """Every leaf drawn, so that a dropped norm weight shows: kernels at
    half a fan-in scale, norm weights around one (``params`` gives the
    shapes: ``jax.eval_shape`` of an init, which compiles nothing)."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))

    def draw(key, path, a):
        noise = jax.random.normal(key, a.shape)
        if a.ndim == 1:
            return 1.0 + 0.3 * noise
        if "embedding" in _name(path):
            return noise
        return 0.5 * noise / np.sqrt(a.shape[-2])

    return jax.tree_util.tree_unflatten(
        treedef, [draw(k, p, a) for k, (p, a) in zip(keys, leaves)])


@pytest.fixture(scope="module")
def tiny():
    from distributed_machine_learning_tpu.train.losses import lm_cross_entropy

    model = pm.PrerouteMoELM(pm.PrerouteMoESizes.from_config(TINY))
    tokens = jax.random.randint(jax.random.PRNGKey(0), (1, 71), 0, 97)
    targets = jax.random.randint(jax.random.PRNGKey(5), (1, 71), 0, 97)
    params = _randomized(jax.eval_shape(
        model.init, jax.random.PRNGKey(1), tokens)["params"], seed=3)

    def loss_and_logits(p):
        logits = model.apply({"params": p}, tokens)
        return lm_cross_entropy(logits, targets), logits

    @jax.jit
    def compared(params):
        (loss, logits), grads = jax.value_and_grad(
            loss_and_logits, has_aux=True)(params)
        ref_loss, ref_grads = jax.value_and_grad(
            lambda p: reference.loss(p, TINY, tokens, targets))(params)
        return (loss, logits, grads, ref_loss, ref_grads,
                reference.logits(params, TINY, tokens),
                reference.reglu_active_share(params, TINY, tokens))

    (loss, logits, grads, ref_loss, ref_grads, ref_logits,
     ref_share) = compared(params)

    @jax.jit
    def routed(params):
        """Every layer's expert ids and the step's counts, as a step sees
        them."""
        _, sown = model.apply({"params": params}, tokens, return_hidden=True,
                              mutable=["moe_routing", hm.STATS_COLLECTION])
        return ({block: layer["moe"]["expert_idx"][0]
                 for block, layer in sown["moe_routing"].items()},
                model.step_stats(sown[hm.STATS_COLLECTION]))

    return dict(model=model, params=params, tokens=tokens, targets=targets,
                logits=logits, loss=loss, grads=grads, ref_loss=ref_loss,
                ref_grads=ref_grads, ref_logits=ref_logits,
                ref_share=ref_share, routed=routed)


def test_logits_loss_and_every_gradient_match_the_reference(tiny):
    ref = tiny["ref_logits"]
    assert float(jnp.abs(tiny["logits"] - ref).max()
                 / jnp.abs(ref).max()) < TOL
    assert float(tiny["loss"]) == pytest.approx(float(tiny["ref_loss"]),
                                                rel=1e-6)
    flat = jax.tree_util.tree_flatten_with_path(
        jax.device_get(tiny["grads"]))[0]
    ref_grads = jax.tree_util.tree_leaves(jax.device_get(tiny["ref_grads"]))
    assert len(flat) == len(ref_grads)
    worst = {_name(path): float(np.abs(a - b).max() / np.abs(b).max())
             for (path, a), b in zip(flat, ref_grads)}
    assert max(worst.values()) < TOL, max(worst, key=worst.get)
    # two norms a block, no query/key norm, no gate, no shared expert
    assert sorted(n[len("block_2/"):] for n in worst
                  if n.startswith("block_2/")) == [
        "attn/k_proj/kernel", "attn/o_proj/kernel", "attn/q_proj/kernel",
        "attn/v_proj/kernel", "input_layernorm/weight", "moe/router/kernel",
        "moe/w_down", "moe/w_gate", "moe/w_up",
        "post_attention_layernorm/weight"]
    assert get_leaf(tiny["params"], "block_0/attn/q_proj/kernel").shape \
        == (64, 7 * 16)


@pytest.mark.parametrize("what", ["kernels", "names at the cell's shape"])
def test_the_kernels_at_seven_query_heads_a_key_head_with_a_window(
        monkeypatch, what):
    """Interpreted: out, dq, dk and dv of the forward and the fused backward
    kernel at group factor 7 with a window of 100 against dense masked
    attention (L 384: 3 × 3 tiles of 128, the band cuts the third row).
    Lowered for a TPU at the cell's shape (L 16 384, 28 query and 4
    key/value heads of 128, the window 4096): the names a device trace
    tells the calls apart by."""
    if what != "kernels":
        monkeypatch.setattr(flash_attention, "_interpret", lambda: False)
        args = [jax.ShapeDtypeStruct((1, 16384, h, 128), jnp.bfloat16)
                for h in (28, 4, 4)]
        text = jax.jit(jax.grad(lambda *a: jnp.sum(flash_self_attention(
            *a, window=4096).astype(jnp.float32)), argnums=(0, 1, 2))).trace(
            *args).lower(lowering_platforms=("tpu",)).as_text()
        assert set(re.findall(r"flash_\w+", text)) == {
            "flash_fwd_w4096", "flash_bwd_fused_w4096"}
        return
    monkeypatch.setattr(flash_attention, "_bwd_fused", lambda *_: True)
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    q, k, v, ct = (jax.random.normal(key, shape) for key, shape in zip(ks, (
        (1, 384, 7, 16), (1, 384, 1, 16), (1, 384, 1, 16), (1, 384, 7, 16))))
    out, vjp = jax.vjp(lambda *a: flash_self_attention(*a, window=100),
                       q, k, v)
    want, want_vjp = jax.vjp(lambda q, k, v: dense_self_attention(
        q, _repeat_kv(k, 7), _repeat_kv(v, 7), window=100), q, k, v)
    # float32 tiles against one softmax: the online rescaling and the
    # blocked sums round apart by ~1e-6 (the window tests' limits)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    for got, ref, name in zip(vjp(ct), want_vjp(ct), ("dq", "dk", "dv")):
        assert got.shape == ref.shape, name
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), rtol=2e-4,
            atol=2e-5 * max(1.0, float(jnp.abs(ref).max())), err_msg=name)


def _changed(params, path, seed, scale):
    leaf = get_leaf(params, path)
    noise = jax.random.normal(jax.random.PRNGKey(seed), leaf.shape)
    return with_leaves(params, {path: leaf * (1.0 + scale * noise)})


@pytest.mark.parametrize("layer", range(4))
def test_the_router_reads_the_block_s_input_not_the_attention(tiny, layer):
    """Layer ``i``'s expert ids stay as they were when its attention's four
    projections change, and move when its input norm's weight changes (the
    router reads ``N₁(x)``).  The attention change does reach the next
    layer's router: this test can see a change of routing."""
    routed, params = tiny["routed"], tiny["params"]
    before = routed(params)[0]
    moved = params
    for name in ("q_proj", "k_proj", "v_proj", "o_proj"):
        moved = _changed(moved, f"block_{layer}/attn/{name}/kernel",
                         seed=layer, scale=0.5)
    after = routed(moved)[0]
    assert np.array_equal(after[f"block_{layer}"], before[f"block_{layer}"])
    if layer < 3:
        assert not np.array_equal(after[f"block_{layer + 1}"],
                                  before[f"block_{layer + 1}"])
    normed = routed(_changed(params, f"block_{layer}/input_layernorm/weight",
                             seed=layer, scale=0.5))[0]
    assert not np.array_equal(normed[f"block_{layer}"],
                              before[f"block_{layer}"])


def _share(first, held):
    return hm.SparseMoE(
        router_width=8, held_experts=(first, held), experts_per_token=3,
        d_ff=32, shared_d_ff=0, norm_topk_prob=True,
        compute_dtype=jnp.float32, activation="relu")


def test_the_four_shares_add_up_to_the_uncut_layer():
    """Four chips of two experts each: the parts they give add up to the
    uncut reference layer of eight experts, routed from ``r``.  No shared
    expert: nothing is computed by every share, and a layer with
    ``shared_d_ff = 0`` has no parameter for one."""
    kx, kr, kp = jax.random.split(jax.random.PRNGKey(11), 3)
    h = jax.random.normal(kx, (2, 40, 64))
    r = jax.random.normal(kr, (2, 40, 64))
    uncut = _randomized(jax.eval_shape(
        lambda: _share(0, 8).init(kp, h, route_from=r))["params"], 4)
    assert sorted(uncut) == ["router", "w_down", "w_gate", "w_up"]

    @jax.jit
    def shares_and_uncut(uncut):
        total, dropped = 0.0, 0.0
        for first in range(0, 8, 2):
            part = {name: (leaf if name == "router"
                           else leaf[first:first + 2])
                    for name, leaf in uncut.items()}
            y, sown = _share(first, 2).apply(
                {"params": part}, h, route_from=r,
                mutable=[hm.STATS_COLLECTION])
            total = total + y
            dropped += sown[hm.STATS_COLLECTION]["layer"][0][2]
        with jax.default_matmul_precision("highest"):
            want = jnp.stack([reference.moe(h[b], r[b], uncut, TINY)
                              for b in range(2)])
        return total, dropped, want

    total, dropped, want = shares_and_uncut(uncut)
    assert float(dropped) == 0.0
    assert float(jnp.abs(total - want).max() / jnp.abs(want).max()) < TOL


def test_the_reglu_count_is_the_reference_s(tiny):
    """``moe_reglu_active_share``: per layer, the held rows' hidden units
    whose gate ``W_gate h`` is above zero over all of them, the mean over
    the layers.  The two sides compute ``W_gate h`` in sums of another
    order, so a unit within rounding of zero may fall on either side: one
    unit moves the mean by 1 / (4 layers · 213 rows · 32 units), and four
    such units are the room."""
    counts = tiny["routed"](tiny["params"])[1]
    want = float(tiny["ref_share"])
    assert 0.3 < want < 0.7  # random gates pass about half
    assert float(counts["moe_reglu_active_share"]) == pytest.approx(
        want, abs=4 / (4 * 213 * 32))
    assert float(counts["moe_dropped_rows"]) == 0.0


# ---------------------------------------- the four configuration models

#: sha256 of ``_lowered``'s text for each cell that runs a configuration
#: model, taken at the commit before ``GatedAttention`` and ``SparseMoE``
#: took their new settings.  A setting that moves an existing model's
#: lowering (one more operation, another order, another dtype) moves this.
LOWERED = {
    "q3next_a3b_dp_s8192":
        "7bfe519399287ea6a3a9abd1e1f182b0e039c8f5672274cea903c95ab7dc6246",
    "kanana2_a3b_dp_s8192":
        "885c84a54175ad48723c7cf88feb2d359b0bc13d2a620ddf97c9b014016a9c86",
    "trinity_mini_dp_s16384":
        "7b84446e8a5039de7c7eeddd16bfec3290e8e10d30c6a6555f97ece449d82e62",
    "kimi_linear_dp_s8192":
        "178a074a407a409703c1391442ad47fb22218f4012c55fa2c8fd2e3302a3ffc6",
}


def _lowered(cell: str) -> str:
    """The forward jaxpr of the cell's model as the benchmark builds it
    (its configuration file and traffic: bf16, flash, recomputation as the
    cell asks), with the sown routing counts and the step's reduction of
    them, traced at the cell's shapes (no array is made).  Function
    addresses are cut from the text."""
    from benchmark import harness
    from benchmark.families import hybrid_moe_lm as hybrid_family

    spec = harness.load_cell(REPO, cell)
    with tempfile.TemporaryDirectory() as scratch:
        path = os.path.join(scratch, "config.json")
        with open(path, "w", encoding="utf-8") as f:
            json.dump(spec["config"], f)
        model, _ = hybrid_family.model_from_argv(
            hybrid_family.argv_for(path, spec["traffic"], 1))
    tokens = jax.ShapeDtypeStruct((1, spec["traffic"]["seq_len"]), jnp.int32)
    params = jax.eval_shape(
        lambda t: model.init(jax.random.PRNGKey(0), t)["params"], tokens)

    def forward(params, tokens):
        hidden, sown = model.apply(
            {"params": params}, tokens, train=True, return_hidden=True,
            mutable=[model.stats_collection, "moe_routing"])
        return (hidden, model.step_stats(sown[model.stats_collection]),
                sown["moe_routing"])

    return re.sub(r" at 0x[0-9a-f]+", "",
                  str(jax.make_jaxpr(forward)(params, tokens)))


@pytest.mark.parametrize("cell", sorted(LOWERED))
def test_an_existing_configuration_model_lowers_as_it_did(monkeypatch, cell):
    """The delta rule, which shares no code with the settings added here and
    costs most of the tracing, is replaced by one stand-in on both sides
    (the digests were taken with it).  The digests were taken with the flash
    kernel's ``(out, lse)`` the only tags: the others whole-block
    recomputation keeps since are made the identity, so that what is
    compared is all but those ``name`` equations."""
    from distributed_machine_learning_tpu.models import kda_moe
    from tests.test_block_remat import untagged

    untagged(monkeypatch, keep=FLASH_RESIDUAL_NAMES)
    for module in (hm, kda_moe):
        monkeypatch.setattr(module, "gated_delta_rule",
                            lambda q, k, v, g, beta: v)
    text = _lowered(cell)
    assert hashlib.sha256(text.encode()).hexdigest() == LOWERED[cell]
