"""``models/mla_moe.py`` (``model_type`` ``deepseek_v3``) against its plain
reference at a tiny size, the flash kernels at a query/key head of 192 and a
value head of 128, bias-corrected routing in ``ops/grouped.py``, the chip's
share identity, the configuration file against the published one, and the
model on the normal path: ``cli.lm --model-config``."""

from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import mla_moe_lm as reference
from benchmark.reference.transformer_lm import get_leaf, with_leaves
from distributed_machine_learning_tpu.models import hybrid_moe as hm
from distributed_machine_learning_tpu.models import mla_moe as mm
from distributed_machine_learning_tpu.ops import grouped

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = {
    "model_type": "deepseek_v3", "vocab_size": 97, "hidden_size": 32,
    "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "intermediate_size": 48, "num_attention_heads": 4,
    "num_key_value_heads": 4, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "kv_lora_rank": 24, "q_lora_rank": None,
    "rope_theta": 1000000, "rope_interleave": True, "n_routed_experts": 8,
    "num_experts_per_tok": 3, "moe_intermediate_size": 16,
    "n_shared_experts": 2, "routed_scaling_factor": 2.448,
    "scoring_func": "sigmoid", "topk_method": "noaux_tc", "n_group": 1,
    "topk_group": 1, "norm_topk_prob": True, "rms_norm_eps": 1e-6,
}
#: float32 on both sides at a tiny size: the two differ by the order of
#: their sums and by the sort (2e-6 of a tensor's largest entry was the worst
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


@pytest.fixture(scope="module")
def tiny():
    from distributed_machine_learning_tpu.train.losses import lm_cross_entropy

    model = mm.MLAMoELM(mm.MLAMoESizes.from_config(TINY))
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 71), 0, 97)
    targets = jax.random.randint(jax.random.PRNGKey(5), (2, 71), 0, 97)
    params = _randomized(
        model.init(jax.random.PRNGKey(1), tokens)["params"], seed=3)

    def loss_and_logits(p):
        logits = model.apply({"params": p}, tokens)
        return lm_cross_entropy(logits, targets), logits

    (loss, logits), grads = jax.value_and_grad(
        loss_and_logits, has_aux=True)(params)
    ref_loss, ref_grads = jax.value_and_grad(
        lambda p: reference.loss(p, TINY, tokens, targets))(params)
    return dict(model=model, params=params, tokens=tokens, targets=targets,
                logits=logits, loss=loss, grads=grads, ref_loss=ref_loss,
                ref_grads=ref_grads)


def _off(logits, ref):
    return float(jnp.abs(logits - ref).max() / jnp.abs(ref).max())


def test_logits_and_loss_match_the_reference(tiny):
    ref = reference.logits(tiny["params"], TINY, tiny["tokens"])
    assert _off(tiny["logits"], ref) < TOL
    assert float(tiny["loss"]) == pytest.approx(float(tiny["ref_loss"]),
                                                rel=1e-6)


def test_every_gradient_matches_the_reference(tiny):
    flat = jax.tree_util.tree_flatten_with_path(tiny["grads"])[0]
    ref = jax.tree_util.tree_leaves(tiny["ref_grads"])
    assert len(flat) == len(ref) > 10 * TINY["num_hidden_layers"]
    worst = {}
    for (path, a), b in zip(flat, ref):
        name = _name(path)
        if name.endswith("e_score_correction_bias"):
            # it picks and never weighs: no gradient on either side
            assert not a.any() and not b.any(), name
            continue
        worst[name] = float(jnp.abs(a - b).max() / jnp.abs(b).max())
    assert max(worst.values()) < TOL, max(worst, key=worst.get)


def _zeroed(path, columns=slice(None)):
    def change(params, config):
        leaf = get_leaf(params, path)
        return with_leaves(params, {
            path: leaf.at[..., columns].set(0.0)}), config
    return change


@pytest.mark.parametrize("what, change", [
    ("the rotary key",
     _zeroed("block_1/attn/kv_a_proj_with_mqa/kernel", slice(24, None))),
    ("the bias in the selection",
     _zeroed("block_1/moe/e_score_correction_bias")),
    ("the scale 2.448",
     lambda p, c: (p, {**c, "routed_scaling_factor": 1.0})),
    ("the shared expert", _zeroed("block_2/moe/shared_down_proj/kernel")),
    ("the latent norm", _zeroed("block_0/attn/kv_a_layernorm/weight")),
    ("the dense layer", _zeroed("block_0/mlp/down_proj/kernel")),
])
def test_a_dropped_term_breaks_the_tolerance(tiny, what, change):
    """The comparison sees each term: dropping one on the reference's side
    alone moves its logits far past ``TOL`` from the system's."""
    params, config = change(tiny["params"], TINY)
    ref = reference.logits(params, config, tiny["tokens"])
    assert _off(tiny["logits"], ref) > 50 * TOL, what


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


def test_rotating_evens_and_odds_is_rotating_adjacent_pairs():
    """The program permutes to ``[evens | odds]`` and rotates half-split
    pairs, the reference rotates the pairs ``(2j, 2j+1)`` in place: one is a
    permutation of the other, so every ``q·k`` agrees."""
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 9, 3, 64))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 9, 1, 64))
    positions = jnp.arange(9)
    got_q = mm.rope_adjacent_pairs(q, positions, 1e6)
    got_k = mm.rope_adjacent_pairs(k, positions, 1e6)
    ref_q, ref_k = reference.rope_pairs(q[0], 1e6), reference.rope_pairs(
        k[0], 1e6)
    perm = jnp.concatenate([jnp.arange(0, 64, 2), jnp.arange(1, 64, 2)])
    assert float(jnp.abs(got_q[0] - ref_q[..., perm]).max()) < 1e-6
    dots = jnp.einsum("bqhd,bkgd->bhqk", got_q, got_k)
    ref_dots = jnp.einsum("qhd,kgd->hqk", ref_q, ref_k)
    assert float(jnp.abs(dots[0] - ref_dots).max()) < 1e-4
    assert (got_q[:, 0] == jnp.concatenate(
        [q[:, 0, :, 0::2], q[:, 0, :, 1::2]], -1)).all()  # position 0


def test_plain_norm_multiplies_by_the_weight_itself():
    x = 3.0 * jax.random.normal(jax.random.PRNGKey(0), (5, 32))
    norm = hm.RMSNorm(1e-6, jnp.float32, zero_centred=False)
    params = norm.init(jax.random.PRNGKey(1), x)["params"]
    assert (params["weight"] == 1.0).all()
    got = norm.apply({"params": {"weight": jnp.full(32, 0.5)}}, x)
    want = 0.5 * x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)
    assert float(jnp.abs(got - want).max()) < 1e-6


# ------------------------------------- the kernels at 192 against 128

@pytest.mark.parametrize("heads, kv_heads, length", [(2, 2, 1024),
                                                     (4, 2, 256),
                                                     (2, 2, 200)])
def test_flash_at_qk_192_and_v_128_matches_dense_attention(
        heads, kv_heads, length):
    """Forward and all three gradients in interpret mode: 2 × 2 tiles of
    512 with one above the diagonal, grouped key/value heads, and a length
    that is padded."""
    from distributed_machine_learning_tpu.models.transformer import _repeat_kv
    from distributed_machine_learning_tpu.ops.pallas.flash_attention import (
        flash_self_attention,
    )
    from distributed_machine_learning_tpu.ops.ring_attention import (
        dense_self_attention,
    )

    ks = jax.random.split(jax.random.PRNGKey(length), 4)
    q = jax.random.normal(ks[0], (1, length, heads, 192))
    k = jax.random.normal(ks[1], (1, length, kv_heads, 192))
    v = jax.random.normal(ks[2], (1, length, kv_heads, 128))
    ct = jax.random.normal(ks[3], (1, length, heads, 128))
    rep = heads // kv_heads

    def dense(q, k, v):
        return dense_self_attention(q, _repeat_kv(k, rep), _repeat_kv(v, rep))

    out, grads = jax.value_and_grad(
        lambda *a: (flash_self_attention(*a) * ct).sum(), argnums=(0, 1, 2))(
        q, k, v)
    want, want_grads = jax.value_and_grad(
        lambda *a: (dense(*a) * ct).sum(), argnums=(0, 1, 2))(q, k, v)
    assert flash_self_attention(q, k, v).shape == (1, length, heads, 128)
    assert float(out) == pytest.approx(float(want), rel=1e-4, abs=1e-2)
    for got, ref in zip(grads, want_grads):
        assert got.shape == ref.shape
        assert float(jnp.abs(got - ref).max()) < 2e-4 * float(
            jnp.abs(ref).max()) + 1e-5


def test_flash_names_the_unequal_heads_and_refuses_a_mismatch():
    from distributed_machine_learning_tpu.ops.pallas import flash_attention

    assert flash_attention._kernel_name("flash_fwd", 128, 128) == "flash_fwd"
    assert flash_attention._kernel_name("flash_bwd_dkv", 192, 128) \
        == "flash_bwd_dkv_qk192v128"
    q = jnp.zeros((1, 128, 2, 192))
    with pytest.raises(ValueError, match="head width"):
        flash_attention.flash_self_attention(
            q, jnp.zeros((1, 128, 2, 128)), jnp.zeros((1, 128, 2, 128)))


# ------------------------------------------- bias-corrected routing

def test_the_bias_changes_the_chosen_set_and_never_a_weight():
    n, e, k = 256, 16, 4
    probs = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(0), (n, e)))
    bias = jax.random.uniform(jax.random.PRNGKey(1), (e,), minval=-0.3,
                              maxval=0.3)
    plain_idx, plain_w = grouped.route_topk(probs, k)
    idx, weights = grouped.route_topk(probs, k, bias=bias)
    # the k largest of probs + bias, weighted by probs alone
    want = np.argsort(-np.asarray(probs + bias), axis=1)[:, :k]
    assert (np.sort(np.asarray(idx), 1) == np.sort(want, 1)).all()
    assert np.array_equal(np.asarray(weights), np.asarray(
        jnp.take_along_axis(probs, idx, -1)))
    moved = (np.sort(np.asarray(idx), 1)
             != np.sort(np.asarray(plain_idx), 1)).any(1)
    assert 0.2 < moved.mean() < 1.0
    assert float(grouped.selection_moved_share(probs, idx)) \
        == pytest.approx(moved.mean())
    assert float(grouped.selection_moved_share(probs, plain_idx)) == 0.0
    # where the set is the same, so is every weight: the bias never weighs
    same = ~moved
    assert np.allclose(np.sort(np.asarray(weights)[same], 1),
                       np.sort(np.asarray(plain_w)[same], 1))
    # a bias that forces expert 3 on every token still weighs it by its score
    forced_idx, forced_w = grouped.route_topk(
        probs, k, True, bias=jnp.zeros(e).at[3].set(10.0), scale=2.448)
    assert (np.asarray(forced_idx)[:, 0] == 3).all()
    assert np.allclose(np.asarray(forced_w.sum(-1)), 2.448, atol=1e-5)
    chosen = jnp.take_along_axis(probs, forced_idx, -1)
    assert np.allclose(np.asarray(forced_w),
                       2.448 * np.asarray(chosen / chosen.sum(-1, keepdims=True)),
                       atol=1e-6)
    # no gradient reaches the bias
    grad = jax.grad(lambda b: grouped.route_topk(
        probs, k, True, bias=b, scale=2.448)[1].sum())(bias)
    assert not grad.any()


def _moe_share(first, held):
    return hm.SparseMoE(
        router_width=16, held_experts=(first, held), experts_per_token=3,
        d_ff=16, shared_d_ff=32, norm_topk_prob=True,
        compute_dtype=jnp.float32, score_func="sigmoid", selection_bias=True,
        routed_scale=2.448, shared_gate=False)


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The guide's share test: the routed parts that eight chips give, each
    holding 2 of the 16 experts, plus the shared experts once, are the layer
    that holds all 16 — in the program and in the reference."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 40, 32))
    full = _randomized(_moe_share(0, 16).init(
        jax.random.PRNGKey(1), x)["params"], seed=2)
    assert "shared_expert_gate" not in full
    config = {**TINY, "n_routed_experts": 16}
    uncut = _moe_share(0, 16).apply({"params": full}, x)
    ref_uncut = jnp.stack([reference.moe(row, full, config) for row in x])
    assert float(jnp.abs(uncut - ref_uncut).max()) < 1e-5
    r = reference.rounder(None)
    shared = jnp.stack([reference.shared_expert(row, full, r) for row in x])
    total, ref_total = -7.0 * shared, shared
    for first in range(0, 16, 2):
        part = {**full, **{name: full[name][first:first + 2]
                           for name in ("w_gate", "w_up", "w_down")}}
        total = total + _moe_share(first, 2).apply({"params": part}, x)
        share = {**config, "n_routed_experts": 2, "router_width": 16,
                 "held_experts": [first, 2]}
        ref_total = ref_total + jnp.stack([
            reference.routed_experts(row, part, share, r) for row in x])
    assert float(jnp.abs(total - uncut).max()) < 1e-5
    assert float(jnp.abs(ref_total - ref_uncut).max()) < 1e-5


# ------------------------------------------ the configuration file

#: The catalog's row for kakaocorp/kanana-2-30b-a3b-instruct-2601
#: (``config.json`` as published, the keys that say something about shape).
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "kv_lora_rank": 512, "max_position_embeddings": 32768,
    "model_type": "deepseek_v3", "moe_intermediate_size": 768,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 128,
    "n_shared_experts": 2, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 6,
    "num_hidden_layers": 48, "num_key_value_heads": 32, "q_lora_rank": None,
    "qk_head_dim": 192, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06, "rope_interleave": True, "rope_scaling": None,
    "rope_theta": 1000000, "routed_scaling_factor": 2.448,
    "scoring_func": "sigmoid", "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 128256}


def test_the_configuration_file_keeps_every_published_width():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "kanana2_30b_a3b.json"), encoding="utf-8") as f:
        config = json.load(f)
    changed = sorted(k for k, v in PUBLISHED.items() if config[k] != v)
    assert changed == sorted(config["reduced"]) == [
        "n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert config["published"] == {k: PUBLISHED[k] for k in changed}
    # the guide's floors: 4 sparse layers behind the dense one, 8 experts,
    # an eighth of the vocabulary; the router keeps its width
    assert config["num_hidden_layers"] - config["first_k_dense_replace"] == 4
    assert config["n_routed_experts"] == 16 >= 8
    assert config["held_experts"] == [0, 16] and config["router_width"] == 128
    assert config["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    sizes = mm.MLAMoESizes.from_config(config)
    assert (sizes.d_model, sizes.n_heads, sizes.qk_nope_dim,
            sizes.qk_rope_dim, sizes.v_dim, sizes.kv_latent_dim,
            sizes.expert_d_ff, sizes.shared_d_ff, sizes.dense_d_ff,
            sizes.experts_per_token, sizes.routed_scale, sizes.rope_base) == (
        2048, 32, 128, 64, 128, 512, 768, 1536, 6144, 6, 2.448, 1e6)
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "kanana2_30b_a3b")
    assert entry["reduced"] == config["reduced"]
    assert "kanana-2-30b-a3b-instruct-2601" in entry["source"]


def test_the_cell_reuses_the_shared_traffic_file():
    """ISSUE 31's argv and shape are ``dp_1x8192.json`` as it stands
    (``q3next_a3b_dp_s8192``'s): the cell names that file and brings none."""
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        cells = json.load(f)["workloads"]
    cell = next(c for c in cells if c["name"] == "kanana2_a3b_dp_s8192")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "kanana2_30b_a3b", "dp_1x8192", 1)
    assert [c["name"] for c in cells if c["traffic"] == "dp_1x8192"] == [
        "q3next_a3b_dp_s8192", "kanana2_a3b_dp_s8192"]
    with open(os.path.join(REPO, "benchmark", "traffic", "dp_1x8192.json"),
              encoding="utf-8") as f:
        traffic = json.load(f)
    assert traffic["argv"] == [
        "--parallel", "dp", "--compute-dtype", "bfloat16", "--attn", "flash",
        "--optimizer", "adamw", "--fused-ce-chunks", "8"]
    assert (traffic["seq_len"], traffic["seqs_per_chip"],
            traffic["check_seqs"], traffic["warm_iters"],
            traffic["trace_steps"]) == (8192, 1, 1, 2, 5)


# ------------------------------------------------------- the normal path

CLI_SIZES = {**TINY, "vocab_size": 128, "hidden_size": 64,
             "n_routed_experts": 4, "router_width": 16,
             "held_experts": [4, 4]}


def test_cli_lm_trains_the_model_from_a_configuration_file(tmp_path, capsys):
    """Three iterations through ``make_lm_train_step`` and ``train_epoch``
    on the 8 virtual devices with the 192/128-shaped kernels interpreted;
    the routing counts reach the step rows one step late; AdamW moves the
    router and leaves the selection bias as it was drawn."""
    from distributed_machine_learning_tpu.cli import lm as cli
    from distributed_machine_learning_tpu.train import lm_step
    from distributed_machine_learning_tpu.train.adamw import AdamWConfig

    config_file = tmp_path / "config.json"
    config_file.write_text(json.dumps(CLI_SIZES))
    result = cli.main([
        "--parallel", "dp", "--model-config", str(config_file), "--seq-len",
        "128", "--batch-size", "8", "--max-iters", "3", "--compute-dtype",
        "bfloat16", "--attn", "flash", "--optimizer", "adamw",
        "--fused-ce-chunks", "2", "--telemetry-dir",
        str(tmp_path / "telemetry")])
    assert "d_model=64 layers=3" in capsys.readouterr().out
    assert isinstance(result.train_step, lm_step._StepWithStats)
    assert int(result.state.step) == 3
    params = result.state.params
    assert "moe" not in params["block_0"] and "mlp" not in params["block_1"]
    assert params["block_1"]["moe"]["w_up"].shape == (4, 64, 16)
    assert params["block_1"]["attn"]["kv_b_proj"]["kernel"].shape \
        == (24, 4 * 32)
    model = mm.MLAMoELM(mm.MLAMoESizes.from_config(CLI_SIZES))
    drawn = lm_step.init_lm_state(model, config=AdamWConfig()).params
    for block in ("block_1", "block_2"):
        moe, moe0 = params[block]["moe"], drawn[block]["moe"]
        assert np.array_equal(np.asarray(moe["e_score_correction_bias"]),
                              np.asarray(moe0["e_score_correction_bias"]))
        assert np.abs(np.asarray(moe0["e_score_correction_bias"])).max() > 0
        assert not np.array_equal(np.asarray(moe["router"]["kernel"]),
                                  np.asarray(moe0["router"]["kernel"]))
    rows = [json.loads(line) for line in
            (tmp_path / "telemetry" / "metrics.jsonl").read_text().splitlines()]
    rows = [r for r in rows if "data_wait_s" in r]
    assert len(rows) == 3 and "moe_held_rows" not in rows[0]
    for row in rows[1:]:
        # 128 tokens a chip x 3 a token x 4 of 16 experts = 96 expected
        assert 40 < row["moe_held_rows"] < 160
        assert row["moe_dropped_rows"] == 0.0
        assert row["moe_load_max_over_mean"] >= 1.0
        assert 0.0 < row["moe_bias_moved_share"] <= 1.0
    prom = (tmp_path / "telemetry" / "metrics.prom").read_text()
    assert "moe_held_rows_total" in prom and "moe_dropped_rows_total 0" in prom


@pytest.mark.parametrize("change, message", [
    ({}, "--parallel dp only"),
    ({"q_lora_rank": 1536}, "q_lora_rank"),
    ({"scoring_func": "softmax"}, "scoring_func"),
    ({"first_k_dense_replace": 3}, "first_k_dense_replace"),
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
