"""The ``smallthinker_dp_s16384`` cell's files: the configuration against
the catalog's row and the cut it states, the model it builds at the
published widths and its parameter count, the family's FLOP count, the
cell's registration, the ReGLU metric file, the program's refusals, and a
parent without the model failing at set-up.  Nothing here compiles: the
family's whole run path at a tiny size is its siblings' (``harness``) and
costs a minute a run."""

from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness
from benchmark.families import hybrid_moe_lm as hybrid_family
from benchmark.families import preroute_moe_lm as family
from distributed_machine_learning_tpu.models import preroute_moe as pm

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = "smallthinker_21b_a3b"
CELL = "smallthinker_dp_s16384"
#: The catalog's row for PowerInfer/SmallThinker-21BA3B-Instruct
#: (``config.json`` as published, the keys that say something about shape).
PUBLISHED = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
    "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 28, "num_hidden_layers": 52,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_layout": [0, 1, 1, 1] * 13, "rope_scaling": None,
    "rope_theta": 1500000, "sliding_window_layout": [0, 1, 1, 1] * 13,
    "sliding_window_size": 4096, "tie_word_embeddings": False,
    "vocab_size": 151936,
}


def _config():
    return harness.load_json(os.path.join(
        REPO, "benchmark", "configs", f"{CONFIG}.json"))


def test_the_configuration_is_the_catalog_row_but_what_is_reduced():
    config = _config()
    manifest = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    entry = harness.by_name(manifest["configs"], CONFIG, "config")
    assert entry["reduced"] == config["reduced"] == [
        "num_hidden_layers", "sliding_window_layout", "rope_layout",
        "moe_num_primary_experts", "vocab_size"]
    assert entry["source"] == ("https://huggingface.co/PowerInfer/"
                               "SmallThinker-21BA3B-Instruct/blob/main/"
                               "config.json")
    for key, value in PUBLISHED.items():
        if key not in config["reduced"]:
            assert config[key] == value, key
        else:
            assert config["published"][key] == value, key
    # one whole period of the 52 layers, a quarter of the experts and of
    # the vocabulary
    assert config["num_hidden_layers"] == 4
    assert config["sliding_window_layout"] == config["rope_layout"] \
        == PUBLISHED["rope_layout"][:4] == [0, 1, 1, 1]
    assert config["moe_num_primary_experts"] * 4 == 64
    assert config["vocab_size"] * 4 == PUBLISHED["vocab_size"]
    assert (config["router_width"], config["held_experts"]) == (64, [0, 16])
    assert (config["model_type"], config["hidden_act"]) == (
        "smallthinker", "relu")
    for key in ("router_input", "router_scores", "attention", "rotary",
                "window", "secondary_experts", "auxiliary_loss",
                "model_type", "expert_activation"):
        assert key in config["assumed"], key
    assert {"deployment", "expert_load", "memory", "departures"} <= set(
        config)


def test_the_file_builds_the_model_at_the_published_widths():
    """Through ``cli.lm``'s own path (the family's argv for the cell's
    traffic): the routed-ahead model, flash, bf16, whole blocks recomputed,
    fused CE in 8 chunks; then every parameter, counted from shapes alone:
    656.53 M, as counted by hand from the widths."""
    config = _config()
    traffic = harness.load_json(os.path.join(
        REPO, "benchmark", "traffic", "dp_1x16384_remat_lr5e-6.json"))
    path = os.path.join(REPO, "benchmark", "configs", f"{CONFIG}.json")
    model, chunks = hybrid_family.model_from_argv(
        hybrid_family.argv_for(path, traffic, 1))
    assert isinstance(model, pm.PrerouteMoELM)
    assert (model.attn_impl, model.compute_dtype, model.remat,
            model.remat_policy, chunks) == ("flash", jnp.bfloat16, True,
                                            "block", 8)
    m = model.sizes
    assert (m.d_model, m.n_heads, m.n_kv_heads, m.head_dim, m.window) == (
        2560, 28, 4, 128, 4096)
    assert (m.router_width, m.held_experts, m.experts_per_token,
            m.expert_d_ff) == (64, (0, 16), 6, 768)
    assert m.sliding == m.rotary == (False, True, True, True)
    params = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    count = lambda tree: sum(int(np.prod(a.shape))
                             for a in jax.tree_util.tree_leaves(tree))
    attention = 2560 * 3584 * 2 + 2560 * 512 * 2
    experts = 16 * 3 * 2560 * 768
    layer = attention + 2 * 2560 + 2560 * 64 + experts
    assert count(params["block_1"]) == layer == 115_512_320
    assert count(params) == 4 * layer + 2 * 37984 * 2560 + 2560 \
        == 656_529_920
    assert hybrid_family.params_outside(params) \
        == 656_529_920 - 37984 * 2560 - 4 * experts


def test_the_flop_counts_are_the_configuration_s_arithmetic():
    """``6·outside + 6·L·(k·held/width)·expert + Σ layers' attention`` at the
    published widths: 2.12 GFLOP a token, 38% of it the attention kernels
    (352 MFLOP in the full layer, 154 in each window layer, whose queries
    see k̄ = 3584 keys on average), 27% the head, 10% the routed experts."""
    config = _config()
    T, w = 16384, 4096
    full = family.attention_core_flops_per_token(config, T, 0)
    window = family.attention_core_flops_per_token(config, T, 1)
    assert full == 12 * 28 * 128 * T / 2
    assert window == 12 * 28 * 128 * (w - w * (w - 1) / (2 * T))
    assert round(full / 1e6) == 352 and round(window / 1e6) == 154
    i = np.arange(T)  # k̄ is the mean number of visible keys, counted
    assert np.minimum(i + 1, w).mean() == pytest.approx(
        w - w * (w - 1) / (2 * T))
    assert family.attention_core_flops_per_token(config, 4096, 1) \
        == 12 * 28 * 128 * 2048  # a window the sequence never fills
    outside = 656_529_920 - 37984 * 2560 - 4 * 16 * 3 * 2560 * 768
    got = family.train_flops_per_token(config, outside, T)
    experts = 6 * 4 * (6 * 16 / 64) * 3 * 2560 * 768
    assert got == pytest.approx(6 * outside + experts + full + 3 * window,
                                rel=1e-12)
    assert 2.11e9 < got < 2.13e9
    assert 0.38 < (full + 3 * window) / got < 0.39
    assert 0.27 < 6 * 37984 * 2560 / got < 0.28
    assert 0.10 < experts / got < 0.11


def test_the_cell_and_the_reglu_metric_are_registered():
    manifest = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    cell = harness.by_name(manifest["workloads"], CELL, "cell")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "dp_1x16384_remat_lr5e-6", 1)
    for group, name in (("end_to_end", "tokens_per_s_chip"),
                        ("per_layer", "kernel.pallas_ms")):
        assert manifest[group][[m["name"] for m in manifest[group]].index(
            name)]["workloads"][-1] == CELL
    how = harness.load_json(os.path.join(
        REPO, "benchmark", "metrics", "moe.reglu_active_share.json"))
    assert how == {"reader": "step_row_median",
                   "args": {"field": "moe_reglu_active_share"}}
    from benchmark.readers import step_row_median

    rows = [{"moe_reglu_active_share": v} for v in (0.4, 0.5, 0.45)]
    assert step_row_median.read({"step_rows": rows}, **how["args"]) == 0.45
    # a program that does not count it (the parent): the metric is left out
    assert step_row_median.read({"step_rows": [{"moe_held_rows": 1.0}]},
                                **how["args"]) is None


@pytest.mark.parametrize("change, message", [
    ({"moe_primary_router_apply_softmax": False}, "apply_softmax"),
    ({"held_experts": [0, 8]}, "held_experts"),
    ({"rope_layout": [0, 1, 1]}, "rope_layout"),
    ({"sliding_window_layout": [0, 2, 1, 1]}, "sliding_window_layout"),
    ({"hidden_act": "gelu"}, "hidden_act"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings"),
], ids=lambda v: v if isinstance(v, str) else None)
def test_the_sizes_refuse_what_the_model_cannot_honour(change, message):
    with pytest.raises(ValueError, match=message):
        pm.PrerouteMoESizes.from_config({**_config(), **change})


def test_a_program_without_the_model_fails_at_set_up_before_it_compiles(
        monkeypatch):
    """The parent commit, handed these benchmark files, has no
    ``models/preroute_moe.py``: set-up stops at that import, before
    ``cli.lm`` is asked for anything (no dense ``TransformerLM`` is
    built)."""
    from distributed_machine_learning_tpu.cli import lm as cli

    monkeypatch.setitem(
        sys.modules, "distributed_machine_learning_tpu.models.preroute_moe",
        None)
    monkeypatch.setattr(cli, "main", lambda argv: pytest.fail("cli ran"))
    with pytest.raises(ImportError):
        family.setup(_config(), {}, seed=1)
