"""``models/kda_moe.py`` (``model_type`` ``kimi_linear``) against its plain
reference at a tiny size — through the delta rule's XLA path and its
per-channel kernels (interpreted) —, latent attention without rotation, the
chip's share identity of the expert layer, and the model on the normal path:
``cli.lm --model-config``."""

from __future__ import annotations

import collections
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import kda_moe_lm as reference
from benchmark.reference.transformer_lm import get_leaf, with_leaves
from distributed_machine_learning_tpu.models import hybrid_moe as hm
from distributed_machine_learning_tpu.models import kda_moe as km
from distributed_machine_learning_tpu.models import mla_moe as mm
from distributed_machine_learning_tpu.ops import delta_rule

TINY = {
    "model_type": "kimi_linear", "vocab_size": 97, "hidden_size": 32,
    "num_hidden_layers": 5, "first_k_dense_replace": 1,
    "intermediate_size": 48, "num_attention_heads": 4,
    "num_key_value_heads": 4, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "kv_lora_rank": 24, "q_lora_rank": None,
    "mla_use_nope": True, "rope_theta": 10000,
    "linear_attn_config": {"full_attn_layers": [4], "kda_layers": [1, 2, 3, 5],
                           "num_heads": 2, "head_dim": 16,
                           "short_conv_kernel_size": 4},
    "num_experts": 8, "num_experts_per_token": 3, "moe_intermediate_size": 16,
    "num_shared_experts": 1, "routed_scaling_factor": 2.446,
    "moe_renormalize": True, "moe_router_activation_func": "sigmoid",
    "num_expert_group": 1, "topk_group": 1, "rms_norm_eps": 1e-5,
}
#: float32 on both sides at a tiny size: the two differ by the order of
#: their sums, the chunked rule's triangular inverse and the sort (a few
#: 1e-6 of a tensor's largest entry).  A dropped term is orders off.
TOL = 1e-4


def _name(path):
    return "/".join(str(getattr(k, "key", k)) for k in path)


def _randomized(params, seed):
    """Every leaf redrawn, so that a dropped norm weight or bias shows:
    kernels at half a fan-in scale, norm weights and the gate's bias around
    one, the selection bias ±0.2; the decay's ``A_log`` on log [1, 16] and
    ``dt_bias`` around zero, so that a chunk's decay spans tens of nats."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))

    def draw(key, path, a):
        name, noise = _name(path), jax.random.normal(key, a.shape)
        if name.endswith("e_score_correction_bias"):
            return jax.random.uniform(key, a.shape, minval=-0.2, maxval=0.2)
        if name.endswith("A_log"):
            return jnp.log(jax.random.uniform(key, a.shape, minval=1.0,
                                              maxval=16.0))
        if name.endswith("dt_bias"):
            return 0.5 * noise
        if name.endswith("conv1d"):
            return 0.5 * noise
        if a.ndim == 1:
            return 1.0 + 0.3 * noise
        if "embedding" in name:
            return noise
        return 0.5 * noise / np.sqrt(a.shape[-2])

    return jax.tree_util.tree_unflatten(
        treedef, [draw(k, p, a) for k, (p, a) in zip(keys, leaves)])


def _run():
    from distributed_machine_learning_tpu.train.losses import lm_cross_entropy

    model = km.KDAMoELM(km.KDAMoESizes.from_config(TINY))
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 71), 0, 97)
    targets = jax.random.randint(jax.random.PRNGKey(5), (2, 71), 0, 97)
    params = _randomized(
        model.init(jax.random.PRNGKey(1), tokens)["params"], seed=3)

    def loss_and_logits(p):
        logits = model.apply({"params": p}, tokens)
        return lm_cross_entropy(logits, targets), logits

    (loss, logits), grads = jax.value_and_grad(
        loss_and_logits, has_aux=True)(params)
    return dict(model=model, params=params, tokens=tokens, targets=targets,
                logits=logits, loss=loss, grads=grads)


@pytest.fixture(scope="module")
def tiny():
    out = _run()
    out["ref_loss"], out["ref_grads"] = jax.value_and_grad(
        lambda p: reference.loss(p, TINY, out["tokens"], out["targets"]))(
        out["params"])
    return out


def _off(a, ref):
    return float(jnp.abs(a - ref).max() / jnp.abs(ref).max())


def test_the_layers_are_the_configuration_s():
    sizes = km.KDAMoESizes.from_config(TINY)
    assert sizes.layer_kinds == ("kda", "kda", "kda", "mla", "kda")
    assert sizes.mla_rope is False and sizes.shared_d_ff == 16
    for layer in range(5):
        assert reference.layer_is_kda(TINY, layer) == (
            sizes.layer_kinds[layer] == "kda")


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
        worst[name] = _off(a, b)
    assert max(worst.values()) < TOL, max(worst, key=worst.get)
    assert "block_0/kda/g_b_proj/bias" in worst
    assert "block_0/kda/dt_bias" in worst


def _changed(path, how):
    def change(params, config):
        return with_leaves(params, {path: how(get_leaf(params, path))}), config
    return change


def _one_decay_a_head(dt_bias):
    """Each head's ``dt_bias`` averaged over its channels: the decay falls
    back to one number a head where the inputs ``x·W_f`` are equal."""
    heads = dt_bias.reshape(TINY["linear_attn_config"]["num_heads"], -1)
    return jnp.broadcast_to(heads.mean(-1, keepdims=True),
                            heads.shape).reshape(-1)


@pytest.mark.parametrize("what, change", [
    ("the output gate's bias",
     _changed("block_0/kda/g_b_proj/bias", jnp.zeros_like)),
    ("a decay a channel", _changed("block_1/kda/dt_bias", _one_decay_a_head)),
    ("the short convolution's past taps",
     _changed("block_2/kda/k_conv1d", lambda w: w.at[:-1].set(0.0))),
    ("the latent norm",
     _changed("block_3/attn/kv_a_layernorm/weight", jnp.zeros_like)),
    ("the shared expert",
     _changed("block_4/moe/shared_down_proj/kernel", jnp.zeros_like)),
    ("the dense layer", _changed("block_0/mlp/down_proj/kernel",
                                 jnp.zeros_like)),
    ("the scale 2.446",
     lambda p, c: (p, {**c, "routed_scaling_factor": 1.0})),
])
def test_a_dropped_term_breaks_the_tolerance(tiny, what, change):
    """The comparison sees each term: dropping one on the reference's side
    alone moves its logits far past ``TOL`` from the system's."""
    params, config = change(tiny["params"], TINY)
    ref = reference.logits(params, config, tiny["tokens"])
    assert _off(tiny["logits"], ref) > 50 * TOL, what


def test_the_kernels_give_the_scan_s_numbers(tiny, monkeypatch):
    """The same model with the rule's per-channel kernels (interpreted:
    the heads are 16 wide, so the dispatch is steered) gives the XLA path's
    loss and gradients."""
    monkeypatch.setattr(delta_rule, "state_pass", lambda *a: "kernel")
    kernels = _run()
    assert _off(kernels["logits"], tiny["logits"]) < 1e-5
    for a, b in zip(jax.tree_util.tree_leaves(kernels["grads"]),
                    jax.tree_util.tree_leaves(tiny["grads"])):
        if b.any():
            assert _off(a, b) < TOL


@pytest.mark.parametrize("remat, forward", [(False, 1), (True, 2)],
                         ids=["kept", "block_recomputed"])
def test_a_recomputed_block_prepares_again(monkeypatch, remat, forward):
    """The rule's forward pass keeps its preparation for the backward pass:
    the gradient's program holds ``kda_prepare_fwd`` once a KDA layer.  A
    block recomputed as a whole (``remat_policy="block"``) keeps none of it
    and makes it again with the block — twice a layer —, and the reverse
    is there once a layer either way.  Traced only, the kernels steered to
    (interpreted: the heads are 16 wide)."""
    from distributed_machine_learning_tpu.train.losses import lm_cross_entropy

    monkeypatch.setattr(delta_rule, "state_pass", lambda *a: "kernel")
    sizes = km.KDAMoESizes.from_config(TINY)
    model = km.KDAMoELM(sizes, remat=remat, remat_policy="block")
    tokens = jax.random.randint(jax.random.PRNGKey(0), (1, 64), 0, 97)
    params = model.init(jax.random.PRNGKey(1), tokens)["params"]
    program = jax.make_jaxpr(jax.grad(lambda p: lm_cross_entropy(
        model.apply({"params": p}, tokens), tokens)))(params)
    calls = collections.Counter(_kernel_calls(program.jaxpr))
    layers = sizes.layer_kinds.count("kda")
    assert layers == 4
    assert calls["kda_prepare_fwd"] == forward * layers
    assert calls["kda_prepare_bwd"] == layers


def _kernel_calls(jaxpr):
    """The kernel name of every ``pallas_call`` the program makes, a
    sub-program shared by several calls (a jitted kernel) counted at each."""
    names = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            names.append(eqn.params["name"])
            continue
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    names += _kernel_calls(sub)
    return names


def test_latent_attention_without_rotation_is_position_free():
    """``rope=False``: no ``mla.rope`` scope and the shared key unrotated,
    so the positions handed to the layer change nothing (the rotating
    layer's output moves with them)."""
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 24, 32))
    layer = mm.MLAttention(
        n_heads=4, qk_nope_dim=16, qk_rope_dim=8, v_dim=16, kv_latent_dim=24,
        rope_base=10000.0, eps=1e-5, attn_impl="dense",
        compute_dtype=jnp.float32, rope=False)
    params = layer.init(jax.random.PRNGKey(1), x, jnp.arange(24))
    same = layer.apply(params, x, jnp.arange(24))
    spread = layer.apply(params, x, 7 * jnp.arange(24))
    assert float(jnp.abs(same - spread).max()) == 0.0
    text = jax.jit(lambda p, x: layer.apply(p, x, jnp.arange(24))).lower(
        params, x).as_text(debug_info=True)
    assert "mla.k_shared" in text and "mla.rope" not in text
    rotated = mm.MLAttention(
        n_heads=4, qk_nope_dim=16, qk_rope_dim=8, v_dim=16, kv_latent_dim=24,
        rope_base=10000.0, eps=1e-5, attn_impl="dense",
        compute_dtype=jnp.float32)
    assert float(jnp.abs(rotated.apply(params, x, jnp.arange(24))
                         - rotated.apply(params, x, 7 * jnp.arange(24))
                         ).max()) > 1e-6  # exactly 0.0 unrotated


def test_the_decay_span_is_the_largest_chunk_sum():
    g = -jnp.abs(jax.random.normal(jax.random.PRNGKey(0), (2, 150, 3, 4)))
    g = g.at[1, 64:128, 2, 3].set(-2.0)  # one channel, one whole chunk
    assert float(delta_rule.chunk_decay_span(g)) == pytest.approx(128.0)
    sown = {"block_0": {"kda": {hm.DECAY_SPAN: (jnp.float32(3.0),)},
                        "moe": {"layer": (jnp.zeros(3),)}},
            "block_1": {"kda": {hm.DECAY_SPAN: (jnp.float32(7.0),)}}}
    assert float(hm.routing_counts(sown)["kda_decay_span"]) == 7.0


def _moe_share(first, held):
    return hm.SparseMoE(
        router_width=16, held_experts=(first, held), experts_per_token=3,
        d_ff=16, shared_d_ff=16, norm_topk_prob=True,
        compute_dtype=jnp.float32, score_func="sigmoid", selection_bias=True,
        routed_scale=2.446, shared_gate=False)


@pytest.mark.parametrize("held", [1, 2, 4, 8, 16])
def test_every_share_of_the_experts_adds_up_to_the_uncut_layer(held):
    """The guide's share test over every way 16 experts divide among chips:
    the routed parts the chips give, each holding ``held``, plus the one
    shared expert counted once, are the layer that holds all 16 — in the
    program and in the reference."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 40, 32))
    full = _randomized(_moe_share(0, 16).init(
        jax.random.PRNGKey(1), x)["params"], seed=2)
    config = {**TINY, "num_experts": 16}
    uncut = _moe_share(0, 16).apply({"params": full}, x)
    ref_uncut = jnp.stack([reference.moe(row, full, config) for row in x])
    assert float(jnp.abs(uncut - ref_uncut).max()) < 1e-5
    r = reference.rounder(None)
    shared = jnp.stack([reference.shared_expert(row, full, r) for row in x])
    chips = 16 // held
    total, ref_total = -(chips - 1) * shared, shared
    for first in range(0, 16, held):
        part = {**full, **{name: full[name][first:first + held]
                           for name in ("w_gate", "w_up", "w_down")}}
        total = total + _moe_share(first, held).apply({"params": part}, x)
        share = {**config, "num_experts": held, "router_width": 16,
                 "held_experts": [first, held]}
        ref_total = ref_total + jnp.stack([
            reference.routed_experts(row, part, share, r) for row in x])
    assert float(jnp.abs(total - uncut).max()) < 1e-5
    assert float(jnp.abs(ref_total - ref_uncut).max()) < 1e-5


# ------------------------------------------------------- the normal path

CLI_SIZES = {**TINY, "vocab_size": 128, "hidden_size": 64,
             "num_experts": 4, "router_width": 16, "held_experts": [4, 4]}


def test_cli_lm_dispatches_kimi_linear_and_trains_it(tmp_path, capsys):
    """Three iterations through ``make_lm_train_step`` and ``train_epoch``
    on the 8 virtual devices: ``kimi_linear`` builds ``KDAMoELM``; the
    routing counts and the chunk decay span reach the step rows one step
    late; AdamW moves the router and leaves the selection bias as drawn."""
    from distributed_machine_learning_tpu.cli import lm as cli
    from distributed_machine_learning_tpu.train import lm_step
    from distributed_machine_learning_tpu.train.adamw import AdamWConfig

    assert cli.CONFIG_MODELS["kimi_linear"] == (
        "kda_moe", "KDAMoELM", "KDAMoESizes")
    config_file = tmp_path / "config.json"
    config_file.write_text(json.dumps(CLI_SIZES))
    argv = ["--parallel", "dp", "--model-config", str(config_file),
            "--seq-len", "128", "--batch-size", "8", "--max-iters", "3",
            "--compute-dtype", "bfloat16", "--attn", "flash", "--optimizer",
            "adamw", "--fused-ce-chunks", "2", "--lr", "5e-6",
            "--telemetry-dir", str(tmp_path / "telemetry")]
    args = cli.make_parser().parse_args(argv)
    model = cli.dp_model(args, cli.read_model_config(args), attn_impl="flash",
                         compute_dtype=jnp.bfloat16, remat=False,
                         remat_policy="mlp")
    assert isinstance(model, km.KDAMoELM)
    result = cli.main(argv)
    assert "d_model=64 layers=5" in capsys.readouterr().out
    assert int(result.state.step) == 3
    params = result.state.params
    assert set(params["block_0"]) == {"norm1", "kda", "norm2", "mlp"}
    assert set(params["block_3"]) == {"norm1", "attn", "norm2", "moe"}
    assert params["block_1"]["kda"]["dt_bias"].shape == (2 * 16,)
    assert params["block_1"]["moe"]["w_up"].shape == (4, 64, 16)
    drawn = lm_step.init_lm_state(
        km.KDAMoELM(km.KDAMoESizes.from_config(CLI_SIZES)),
        config=AdamWConfig()).params
    moe, moe0 = params["block_1"]["moe"], drawn["block_1"]["moe"]
    assert np.array_equal(np.asarray(moe["e_score_correction_bias"]),
                          np.asarray(moe0["e_score_correction_bias"]))
    rows = [json.loads(line) for line in
            (tmp_path / "telemetry" / "metrics.jsonl").read_text().splitlines()]
    rows = [r for r in rows if "data_wait_s" in r]
    assert len(rows) == 3 and "kda_decay_span" not in rows[0]
    for row in rows[1:]:
        # 128 tokens a chip x 3 a token x 4 of 16 experts = 96 expected
        assert 40 < row["moe_held_rows"] < 160
        assert row["moe_dropped_rows"] == 0.0
        assert row["kda_decay_span"] > 10.0
    prom = (tmp_path / "telemetry" / "metrics.prom").read_text()
    assert "moe_held_rows_total" in prom and "moe_dropped_rows_total 0" in prom


@pytest.mark.parametrize("change, message", [
    ({}, "--parallel dp only"),
    ({"q_lora_rank": 1536}, "q_lora_rank"),
    ({"moe_router_activation_func": "softmax"},
     "moe_router_activation_func"),
    ({"linear_attn_config": {**TINY["linear_attn_config"],
                             "kda_layers": [1, 2, 3]}}, "each of the layers"),
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
