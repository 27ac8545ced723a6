"""``models/hybrid_moe.py`` (``model_type`` ``qwen3_next``) against its plain
reference at a tiny size, the routed front end of ``ops/grouped.py`` (top-k,
a held range of experts, the chip's-share identity), and the model on the
normal path: ``cli.lm --model-config``."""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import hybrid_moe_lm as reference
from distributed_machine_learning_tpu.models import hybrid_moe as hm
from distributed_machine_learning_tpu.ops import grouped

TINY = {
    "model_type": "qwen3_next", "vocab_size": 97, "hidden_size": 32,
    "num_hidden_layers": 4, "full_attention_interval": 4,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "partial_rotary_factor": 0.25, "rope_theta": 10000000,
    "linear_num_key_heads": 2, "linear_num_value_heads": 4,
    "linear_key_head_dim": 8, "linear_value_head_dim": 8,
    "linear_conv_kernel_dim": 4, "num_experts": 8, "num_experts_per_tok": 3,
    "moe_intermediate_size": 16, "shared_expert_intermediate_size": 16,
    "norm_topk_prob": True, "rms_norm_eps": 1e-6,
}
#: float32 on both sides at a tiny size.  The two differ by the order of
#: their sums, by the chunked rule's triangular solve and by the sort; the
#: per-head normalisations (q/‖q‖, rmsnorm of the scan's output) then divide
#: by small numbers and amplify that rounding, most in the gradients of the
#: first layers: 2e-5 of a tensor's largest entry was the worst of five
#: seeds, so the limit is 1e-4.  A dropped term is orders of magnitude off.
TOL = 1e-4


def _name(path):
    return "/".join(str(getattr(k, "key", k)) for k in path)


def _randomized(params, seed):
    """Every leaf redrawn, so that a dropped norm weight, decay or gate
    shows: kernels at half a fan-in scale (the conditioning note above),
    vectors around their neutral value."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))

    def draw(key, path, a):
        name, noise = _name(path), jax.random.normal(key, a.shape)
        if name.endswith("A_log"):
            return jnp.log(jax.random.uniform(key, a.shape, minval=0.05,
                                              maxval=4.0))
        if a.ndim == 1:
            one = name.endswith(("norm_weight", "dt_bias"))
            return 0.3 * noise + (1.0 if one else 0.0)
        if "embedding" in name:
            return noise
        return 0.5 * noise / np.sqrt(a.shape[-2])

    return jax.tree_util.tree_unflatten(
        treedef, [draw(k, p, a) for k, (p, a) in zip(keys, leaves)])


def _against_the_reference(config, tokens_shape):
    from distributed_machine_learning_tpu.train.losses import lm_cross_entropy

    vocab = config["vocab_size"]
    model = hm.HybridMoELM(hm.HybridMoESizes.from_config(config))
    tokens = jax.random.randint(jax.random.PRNGKey(0), tokens_shape, 0, vocab)
    targets = jax.random.randint(jax.random.PRNGKey(5), tokens_shape, 0, vocab)
    params = _randomized(
        model.init(jax.random.PRNGKey(1), tokens)["params"], seed=3)

    def loss_and_logits(p):
        logits = model.apply({"params": p}, tokens)
        return lm_cross_entropy(logits, targets), logits

    (loss, logits), grads = jax.value_and_grad(
        loss_and_logits, has_aux=True)(params)
    ref_loss, ref_grads = jax.value_and_grad(
        lambda p: reference.loss(p, config, tokens, targets))(params)
    return dict(model=model, config=config, params=params, tokens=tokens,
                targets=targets, logits=logits, loss=loss, grads=grads,
                ref_loss=ref_loss, ref_grads=ref_grads)


@pytest.fixture(scope="module")
def tiny():
    return _against_the_reference(TINY, (2, 71))


#: The delta rule at the cell's head widths, where ``ops/delta_rule.py``'s
#: ``state_pass`` picks the Pallas kernels on a TPU: one DeltaNet layer and
#: one attention layer, two chunks (70 steps padded to 128).
WIDE = {**TINY, "num_hidden_layers": 2, "full_attention_interval": 2,
        "linear_num_key_heads": 1, "linear_num_value_heads": 2,
        "linear_key_head_dim": 128, "linear_value_head_dim": 128}


@pytest.fixture(scope="module")
def wide():
    """The model with its state pass through the kernels (interpreted: the
    dispatch would take the scan here, so the test steers it)."""
    from distributed_machine_learning_tpu.ops import delta_rule

    asked = []

    def kernels(*a):
        asked.append(a)
        return "kernel"

    with pytest.MonkeyPatch.context() as m:
        m.setattr(delta_rule, "state_pass", kernels)
        out = _against_the_reference(WIDE, (1, 70))
    assert asked and {a[:3] for a in asked} == {(128, 128, delta_rule.CHUNK)}
    return out


@pytest.fixture(params=["tiny", "wide"])
def compared(request):
    return request.getfixturevalue(request.param)


def test_logits_match_the_reference(compared):
    ref = reference.logits(compared["params"], compared["config"],
                           compared["tokens"])
    assert float(jnp.abs(compared["logits"] - ref).max()
                 / jnp.abs(ref).max()) < TOL


def test_loss_matches_the_reference(compared):
    assert float(compared["loss"]) == pytest.approx(
        float(compared["ref_loss"]), rel=1e-6)


def test_every_gradient_matches_the_reference(compared):
    flat = jax.tree_util.tree_flatten_with_path(compared["grads"])[0]
    ref = jax.tree_util.tree_leaves(compared["ref_grads"])
    layers = compared["config"]["num_hidden_layers"]
    assert len(flat) == len(ref) > 15 * layers
    worst = {_name(p): float(jnp.abs(a - b).max() / jnp.abs(b).max())
             for (p, a), b in zip(flat, ref)}
    assert max(worst.values()) < TOL, max(worst, key=worst.get)


@pytest.mark.parametrize("dropped", [
    "block_0/gdn/A_log", "block_0/gdn/dt_bias", "block_1/gdn/norm_weight",
    "block_3/attn/q_norm/weight", "block_2/moe/shared_expert_gate/kernel",
    "block_0/norm2/weight"])
def test_a_dropped_term_breaks_the_tolerance(tiny, dropped):
    """The comparison sees each of the block's small parameters: zeroing
    one on the system's side alone moves the logits far past ``TOL``."""
    from benchmark.reference.transformer_lm import get_leaf, with_leaves

    broken = with_leaves(tiny["params"], {
        dropped: jnp.zeros_like(get_leaf(tiny["params"], dropped))})
    logits = tiny["model"].apply({"params": broken}, tiny["tokens"])
    ref = reference.logits(tiny["params"], TINY, tiny["tokens"])
    assert float(jnp.abs(logits - ref).max() / jnp.abs(ref).max()) > 50 * TOL


@pytest.mark.parametrize("operand_dtype, least", [
    (jnp.bfloat16, 1e-4), (jnp.float8_e4m3fn, 1e-2)])
def test_the_reference_in_a_lower_precision_is_another_result(
        tiny, operand_dtype, least):
    """``operand_dtype`` (how the benchmark reads "the next precision down")
    rounds the matmul operands forward only: the loss moves, and the
    gradients stay finite and non-zero but leave the float32 ones behind."""
    sample = ("block_0/gdn/in_proj_qkvz/kernel", "block_3/moe/w_gate",
              "lm_head/kernel")
    loss, grads = reference.loss_and_grads(
        tiny["params"], TINY, tiny["tokens"], tiny["targets"], sample,
        operand_dtype)
    assert abs(float(loss) - float(tiny["ref_loss"])) > 1e-6
    from benchmark.reference.transformer_lm import get_leaf

    for path in sample:
        exact = get_leaf(tiny["ref_grads"], path)
        off = float(jnp.abs(grads[path] - exact).max() / jnp.abs(exact).max())
        assert jnp.isfinite(grads[path]).all() and least < off < 1.0, path


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


def test_partial_rotation_touches_a_quarter_of_the_head():
    from distributed_machine_learning_tpu.models.transformer import apply_rope

    x = jax.random.normal(jax.random.PRNGKey(0), (1, 9, 2, 256))
    positions = jnp.arange(9)
    out = apply_rope(x, positions, 1e7, rotary_dim=64)
    assert (out[..., 64:] == x[..., 64:]).all()          # 192 pass
    assert (out[:, 0] == x[:, 0]).all()                  # position 0: no turn
    # every one of the 64 turns somewhere (the slowest pair by 1e-7 rad a
    # position, under an entry's rounding at some positions)
    turned = jnp.abs(out[..., :64] - x[..., :64]).max(axis=(0, 1, 2))
    assert float(turned.min()) > 0
    ref = reference.rope(x[0], 64, 1e7)
    assert float(jnp.abs(out[0] - ref).max()) < 1e-6
    # the defaults are the whole head at base 1e4, as before the argument
    whole = apply_rope(x, positions)
    assert float(jnp.abs(whole - apply_rope(x, positions, 10000.0, 256)).max()) == 0
    assert float(jnp.abs(whole[:, 1:, :, 64:] - x[:, 1:, :, 64:]).max()) > 0


def test_norm_at_zero_weight_is_the_plain_normalisation():
    x = 3.0 * jax.random.normal(jax.random.PRNGKey(0), (5, 32))
    got = hm.RMSNorm(1e-6, jnp.float32).apply(
        {"params": {"weight": jnp.zeros(32)}}, x)
    want = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)
    assert float(jnp.abs(got - want).max()) < 1e-6
    assert float(jnp.abs(jnp.mean(got * got, -1) - 1.0).max()) < 1e-4


# ------------------------------------------------ the routed front end

def _identity_experts(n_experts, d):
    eye = jnp.broadcast_to(jnp.eye(d), (n_experts, d, d))
    return eye, eye


@pytest.mark.parametrize("k, renormalize", [(1, False), (3, True), (10, True)])
def test_top_k_weights_sum_to_one_and_each_assignment_is_computed_once(
        k, renormalize):
    n, d, e = 64, 8, 16
    x = jax.random.normal(jax.random.PRNGKey(0), (n, d))
    probs = jax.nn.softmax(2.0 * jax.random.normal(jax.random.PRNGKey(1),
                                                   (n, e)))
    idx, weights = grouped.route_topk(probs, k, renormalize)
    assert idx.shape == weights.shape == (n, k)
    assert all(len(set(row)) == k for row in np.asarray(idx))
    assert (np.diff(np.asarray(weights), axis=1) <= 0).all()  # descending
    if renormalize:
        assert np.allclose(np.asarray(weights.sum(-1)), 1.0, atol=1e-6)
    else:
        assert np.allclose(np.asarray(weights[:, 0]), np.asarray(probs.max(-1)))
    # Identity experts: y = Σ_j w_j · x, every assignment counted once.
    w_in, w_out = _identity_experts(e, d)
    y, (sizes, dropped) = grouped.grouped_expert_mlp(
        x, idx, weights, w_in, w_out, activation=lambda h: h,
        return_counts=True)
    assert float(jnp.abs(y - weights.sum(-1, keepdims=True) * x).max()) < 1e-5
    assert int(dropped) == 0 and int(sizes.sum()) == n * k
    assert (np.asarray(sizes) == np.bincount(np.asarray(idx).ravel(),
                                             minlength=e)).all()


def test_held_range_computes_its_own_assignments_only():
    n, d, e, k = 48, 8, 16, 4
    x = jax.random.normal(jax.random.PRNGKey(0), (n, d))
    probs = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(1), (n, e)))
    idx, weights = grouped.route_topk(probs, k, True)
    w_in, w_out = _identity_experts(4, d)
    y, (sizes, dropped) = grouped.grouped_expert_mlp(
        x, idx, weights, w_in, w_out, activation=lambda h: h, first_held=8,
        return_counts=True)
    mine = (idx >= 8) & (idx < 12)
    want = jnp.where(mine, weights, 0.0).sum(-1, keepdims=True) * x
    assert float(jnp.abs(y - want).max()) < 1e-5
    assert int(sizes.sum()) == int(mine.sum()) and int(dropped) == 0


def test_rows_past_the_buffer_are_dropped_and_counted():
    n, d, e, k = 48, 8, 4, 2
    x = jax.random.normal(jax.random.PRNGKey(0), (n, d))
    probs = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(1), (n, e)))
    idx, weights = grouped.route_topk(probs, k, True)
    w_in, w_out = _identity_experts(e, d)
    y, (sizes, dropped) = grouped.grouped_expert_mlp(
        x, idx, weights, w_in, w_out, activation=lambda h: h, capacity=80,
        return_counts=True)
    assert int(dropped) == n * k - 80 and int(sizes.sum()) == 80
    # what was computed is right, what was dropped adds nothing
    share = y / x
    assert float(share.min()) > -1e-5 and float(share.max()) < 1 + 1e-5
    grads = jax.grad(lambda x: grouped.grouped_expert_mlp(
        x, idx, weights, w_in, w_out, activation=lambda h: h,
        capacity=80).sum())(x)
    assert jnp.isfinite(grads).all()


def _moe_share(first, held):
    return hm.SparseMoE(router_width=16, held_experts=(first, held),
                        experts_per_token=4, d_ff=16, shared_d_ff=16,
                        norm_topk_prob=True, compute_dtype=jnp.float32)


def test_the_shares_add_up_to_the_uncut_layer():
    """The guide's share test: the routed parts that four chips give, each
    holding 4 of the 16 experts, plus the shared expert once, are the layer
    that holds all 16 — in the program and in the reference."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 40, 32))
    full = _randomized(_moe_share(0, 16).init(
        jax.random.PRNGKey(1), x)["params"], seed=2)
    config = {**TINY, "num_experts": 16, "num_experts_per_tok": 4}
    uncut = _moe_share(0, 16).apply({"params": full}, x)
    ref_uncut = jnp.stack([reference.moe(row, full, config) for row in x])
    assert float(jnp.abs(uncut - ref_uncut).max()) < 1e-5
    shared = jnp.stack([reference.shared_expert(row, full,
                                                reference.rounder(None))
                        for row in x])
    total, ref_total = -3.0 * shared, shared
    for first in (0, 4, 8, 12):
        part = {**full, **{name: full[name][first:first + 4]
                           for name in ("w_gate", "w_up", "w_down")}}
        total = total + _moe_share(first, 4).apply({"params": part}, x)
        share = {**config, "num_experts": 4, "router_width": 16,
                 "held_experts": [first, 4]}
        ref_total = ref_total + jnp.stack([
            reference.routed_experts(row, part, share,
                                     reference.rounder(None)) for row in x])
    assert float(jnp.abs(total - uncut).max()) < 1e-5
    assert float(jnp.abs(ref_total - ref_uncut).max()) < 1e-5


def _switch_grouped_before(tokens, expert_idx, w_in, b_in, w_out, b_out):
    """``ops/grouped.py::grouped_expert_mlp`` as it stood before the routed
    front end (top-1, GELU, biases, a full permutation)."""
    from jax import lax

    order, inv, sizes = grouped.sort_by_expert(expert_idx, w_in.shape[0])
    xs = grouped._permute_rows(tokens, order, inv)
    eids = jnp.take(expert_idx, order, axis=0)
    h = lax.ragged_dot(xs, w_in, sizes)
    h = jax.nn.gelu(h + jnp.take(b_in, eids, axis=0))
    ys = lax.ragged_dot(h, w_out, sizes) + jnp.take(b_out, eids, axis=0)
    return grouped._permute_rows(ys, inv, order)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_switch_top1_grouped_results_are_unchanged_to_the_bit(dtype):
    n, d, f, e = 64, 16, 32, 4
    ks = jax.random.split(jax.random.PRNGKey(0), 7)
    x = jax.random.normal(ks[0], (n, d)).astype(dtype)
    probs = jax.nn.softmax(jax.random.normal(ks[1], (n, e)))
    w_in = (jax.random.normal(ks[2], (e, d, f)) / 4).astype(dtype)
    w_out = (jax.random.normal(ks[4], (e, f, d)) / 6).astype(dtype)
    b_in = jax.random.normal(ks[3], (e, f)).astype(dtype)
    b_out = jax.random.normal(ks[5], (e, d)).astype(dtype)

    def before(x, w_in, b_in, w_out, b_out):
        y = _switch_grouped_before(x, jnp.argmax(probs, -1), w_in, b_in,
                                   w_out, b_out)
        return y * jnp.max(probs, -1)[:, None].astype(dtype)

    def now(x, w_in, b_in, w_out, b_out):
        idx, weights = grouped.route_topk(probs, 1)
        return grouped.grouped_expert_mlp(x, idx, weights, w_in, w_out,
                                          b_in=b_in, b_out=b_out)

    args = (x, w_in, b_in, w_out, b_out)
    same = lambda a, b: np.array_equal(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32))
    assert same(before(*args), now(*args))
    ct = jax.random.normal(ks[6], (n, d))
    grads = [jax.grad(lambda *a: (f(*a).astype(jnp.float32) * ct).sum(),
                      argnums=range(5))(*args) for f in (before, now)]
    assert all(same(a, b) for a, b in zip(*grads))


# ------------------------------------------------------- the normal path

CLI_SIZES = {**TINY, "vocab_size": 128, "hidden_size": 64, "head_dim": 32,
             "num_experts": 4, "router_width": 16, "held_experts": [0, 4]}


def _cli_argv(config_file, tmp_path):
    return ["--parallel", "dp", "--model-config", str(config_file),
            "--seq-len", "128", "--batch-size", "8", "--max-iters", "3",
            "--compute-dtype", "bfloat16", "--attn", "flash", "--optimizer",
            "adamw", "--fused-ce-chunks", "2", "--telemetry-dir",
            str(tmp_path / "telemetry")]


def test_cli_lm_trains_the_model_from_a_configuration_file(tmp_path, capsys):
    """Two timed iterations through ``make_lm_train_step`` and
    ``train_epoch`` on the 8 virtual devices; the routing counts reach the
    step rows one step late, and the registry, without a device read."""
    from distributed_machine_learning_tpu.cli import lm as cli
    from distributed_machine_learning_tpu.train import lm_step

    config_file = tmp_path / "config.json"
    config_file.write_text(json.dumps(CLI_SIZES))
    result = cli.main(_cli_argv(config_file, tmp_path))
    assert "d_model=64 layers=4" in capsys.readouterr().out
    assert isinstance(result.train_step, lm_step._StepWithStats)
    assert int(result.state.step) == 3
    assert "bias" not in result.state.params["lm_head"]
    assert result.state.params["block_0"]["moe"]["w_up"].shape == (4, 64, 16)
    assert result.state.params["block_0"]["moe"]["router"]["kernel"].shape \
        == (64, 16)
    rows = [json.loads(line) for line in
            (tmp_path / "telemetry" / "metrics.jsonl").read_text().splitlines()]
    rows = [r for r in rows if "data_wait_s" in r]
    assert len(rows) == 3 and "moe_held_rows" not in rows[0]
    for row in rows[1:]:
        # 128 tokens a chip x 3 a token x 4 of 16 experts = 96 expected
        assert 60 < row["moe_held_rows"] < 140
        assert row["moe_dropped_rows"] == 0.0
        assert row["moe_load_max_over_mean"] >= 1.0
    prom = (tmp_path / "telemetry" / "metrics.prom").read_text()
    assert "moe_held_rows_total" in prom and "moe_dropped_rows_total 0" in prom


def test_model_config_carries_the_dense_model_s_sizes_too(tmp_path):
    """For any other ``model_type`` the file's sizes replace the size flags
    and bring what no flag states; without the flag nothing changes."""
    from distributed_machine_learning_tpu.cli import lm as cli
    from distributed_machine_learning_tpu.models.transformer import (
        TransformerLM,
    )

    config_file = tmp_path / "dense.json"
    config_file.write_text(json.dumps({
        "model_type": "starcoder2", "hidden_size": 48,
        "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 2, "vocab_size": 64, "intermediate_size": 80,
        "rope_theta": 99999.0, "norm_epsilon": 1e-5}))
    args = cli.make_parser().parse_args(
        ["--model-config", str(config_file), "--batch-size", "8"])
    _, state, _, model, _ = cli.build(args)
    assert isinstance(model, TransformerLM)
    assert (model.d_model, model.n_layers, model.n_heads, model.n_kv_heads,
            model.vocab_size, model.d_ff, model.rope_base, model.ln_eps) \
        == (48, 2, 4, 2, 64, 80, 99999.0, 1e-5)
    assert state.params["block_0"]["fc_in"]["kernel"].shape == (48, 80)
    args = cli.make_parser().parse_args(["--batch-size", "8"])
    assert args.model_config is None
    _, _, _, model, _ = cli.build(args)
    assert (model.d_model, model.d_ff, model.rope_base, model.ln_eps) \
        == (256, None, 10000.0, 1e-6)


def test_model_config_refuses_what_it_cannot_honour(tmp_path):
    from distributed_machine_learning_tpu.cli import lm as cli

    config_file = tmp_path / "config.json"
    config_file.write_text(json.dumps(CLI_SIZES))
    args = cli.make_parser().parse_args(
        ["--model-config", str(config_file), "--parallel", "ring"])
    with pytest.raises(ValueError, match="--parallel dp only"):
        cli.build(args)
    config_file.write_text(json.dumps({**CLI_SIZES, "mlp_only_layers": [0]}))
    args = cli.make_parser().parse_args(
        ["--model-config", str(config_file), "--batch-size", "8"])
    with pytest.raises(ValueError, match="mlp_only_layers"):
        cli.build(args)
    config_file.write_text(json.dumps({"rope_theta": 5.0}))
    args = cli.make_parser().parse_args(
        ["--model-config", str(config_file), "--parallel", "pp"])
    with pytest.raises(ValueError, match="rope_theta"):
        cli.build(args)
