"""The ``window_moe_lm`` benchmark family through the harness's whole run path
(``harness.run_cell(require_tpu=False)``) at a tiny size on the CPU: set-up
through ``cli.lm --model-config``, the reference check — the selection bias
that the balancing rule moved in the warm iterations among it —, a window of
``train_epoch``, and the routing, bias and tile counts as per-layer metrics
of a traced run, read by the committed metric files."""

from __future__ import annotations

import json
import os
import shutil
import sys
import time

import numpy as np
import pytest

from benchmark import harness
from benchmark.families import window_moe_lm as family

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "t_window"
SLIDING, FULL = "sliding_attention", "full_attention"
TINY_CONFIG = {
    "family": "window_moe_lm", "model_type": "afmoe", "vocab_size": 128,
    "hidden_size": 64, "num_hidden_layers": 5, "num_dense_layers": 1,
    "layer_types": [SLIDING] * 4 + [FULL], "sliding_window": 100,
    "intermediate_size": 96, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "rope_theta": 10000,
    "num_experts": 4, "router_width": 16, "held_experts": [4, 4],
    "num_experts_per_tok": 3, "moe_intermediate_size": 32,
    "num_shared_experts": 1, "route_norm": True, "route_scale": 2.826,
    "score_func": "sigmoid", "n_group": 1, "topk_group": 1,
    "rms_norm_eps": 1e-5, "mup_enabled": True, "load_balance_coeff": 0.001,
    "rope_scaling": None, "tie_word_embeddings": False}
TINY_TRAFFIC = {
    "argv": ["--parallel", "dp", "--attn", "flash", "--optimizer", "adamw",
             "--fused-ce-chunks", "2", "--remat", "--remat-policy", "block",
             "--lr", "5e-6"],
    "seq_len": 384, "seqs_per_chip": 1, "check_seqs": 1, "warm_iters": 2,
    # device planes only: the interpreted kernels' host events (one a grid
    # step and primitive) would fill the traced window on their own
    "trace_steps": 2, "trace_host_events": False}
ROW_METRICS = (("moe.held_rows", "rows", "higher"),
               ("moe.load_max_over_mean", "ratio", "lower"),
               ("moe.dropped_rows", "rows", "lower"),
               ("moe.bias_moved_pct", "%", "higher"),
               ("moe.bias_abs_mean", "score", "lower"),
               ("attn.active_tile_share", "ratio", "lower"))


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """The committed manifest with one cell of added files: the tiny
    configuration, its traffic, and the committed metric files."""
    root = tmp_path_factory.mktemp("tiny_window_benchmark")
    shutil.copytree(os.path.join(REPO, "benchmark", "metrics"),
                    root / "benchmark" / "metrics")
    for rel, body in (("configs/tiny_window.json", TINY_CONFIG),
                      (f"traffic/{CELL}.json", TINY_TRAFFIC)):
        path = root / "benchmark" / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(body))
    manifest = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    manifest["configs"] = [
        {"name": "tiny_window", "source": "test", "reduced": [],
         "file": "benchmark/configs/tiny_window.json", "why": "test"}]
    manifest["workloads"] = [
        {"name": CELL, "config": "tiny_window", "traffic": CELL, "chips": 1,
         "why": "test"}]
    for group in ("end_to_end", "per_layer"):
        for metric in manifest[group]:
            if "trinity_mini_dp_s16384" in metric.get("workloads", ()):
                metric["workloads"] = [CELL]
    # The row-fed counts as per-layer metrics: the committed metric files,
    # entered the way a benchmark PR will enter them (PERF.md §7 on why
    # BENCHMARK.json does not list them yet).
    manifest["per_layer"] += [
        {"name": name, "unit": unit, "better": better,
         "source": "program_counter",
         "layer": "kernels" if name.startswith("attn.") else "expert layer",
         "moves": "mfu_pct", "workloads": [CELL]}
        for name, unit, better in ROW_METRICS]
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return str(root)


def _printed(capsys, line):
    out = capsys.readouterr().out
    return json.loads(out.split(line + " ")[1].splitlines()[0]), out


def test_the_family_runs_a_cell_at_a_tiny_size(tiny_root, capsys):
    out = harness.run_cell(tiny_root, CELL, seed=2**31 + 33, seconds=3.0,
                           trace=False, t0=time.perf_counter(),
                           require_tpu=False)
    check, printed = _printed(capsys, "bench.check")
    assert check["ok"] is True, printed
    assert check["seq_len"] == 384 and len(check["grad_cosine"]) == 11
    assert 0.0 <= check["top_k_differing_share"] < 0.05
    # two warm iterations of the rule, from zero: every b_e is −2u, 0 or 2u
    assert check["bias_steps"] == 2 and check["bias_rule_violations"] == 0
    assert 0.0 < check["bias_abs_mean"] <= 0.002 * (1 + 1e-6)
    assert check["bias_same_way_every_step_share"] > 0.5
    assert check["bias_undecided_experts"] < 4 * 16 // 2
    assert out["correct"] is True and out["failed"] == 0, printed
    assert out["attempted"] >= 2
    assert set(out["metrics"]) == {"tokens_per_s_chip", "step_ms_p90",
                                   "setup_s"}  # no MFU off the chip
    window = json.loads(printed.split("bench.window ")[1].splitlines()[0])
    assert window["compilations_in_window"] == 0
    assert window["items_per_step"] == 8 * 384  # the 8 virtual devices


@pytest.mark.parametrize("fault, reading", [
    ("the rule's sign turned", "bias_rule_violations"),
    ("the rule never applied", "bias_abs_mean"),
])
def test_a_selection_bias_moved_otherwise_fails_the_check(
        tiny_root, capsys, monkeypatch, fault, reading):
    """The loss and gradient comparison is handed the resident ``b`` on both
    sides and passes whatever moved it: the bias check is what sees a rule
    that pushes load TOWARDS the full experts, or one that is left out."""
    from distributed_machine_learning_tpu.models import hybrid_moe, window_moe

    rule = {"the rule's sign turned": lambda bias, counts, rate:
            hybrid_moe.balanced_bias(bias, counts, -rate),
            "the rule never applied": lambda bias, counts, rate: bias}[fault]
    monkeypatch.setattr(window_moe, "balanced_bias", rule)
    out = harness.run_cell(tiny_root, CELL, seed=2**31 + 33, seconds=1.0,
                           trace=False, t0=time.perf_counter(),
                           require_tpu=False)
    check, printed = _printed(capsys, "bench.check")
    if reading == "bias_rule_violations":
        assert check["bias_rule_violations"] > 0
        assert check["bias_abs_mean"] > 0.0
    else:
        assert check["bias_abs_mean"] == 0.0
    assert check["ok"] is False and out["correct"] is False, printed
    assert min(check["grad_cosine"].values()) >= family.GRAD_COSINE


def test_a_traced_run_reports_the_six_row_fed_metrics(tiny_root, capsys):
    # Eight seconds: see tests/test_hybrid_moe_cell.py on what a traced
    # window needs on a host that runs five other test workers.
    out = harness.run_cell(tiny_root, CELL, seed=13, seconds=8.0, trace=True,
                           t0=time.perf_counter(), require_tpu=False)
    check, printed = _printed(capsys, "bench.check")
    assert check["ok"] is True and out["correct"] is True, printed
    metrics = {name: m["value"] for name, m in out["metrics"].items()}
    assert "moe.held_rows" in metrics, (out["attempted"], sorted(metrics))
    # 384 tokens a chip x 3 a token x 4 of 16 experts held = 288 expected
    assert 120 < metrics["moe.held_rows"] < 480
    assert metrics["moe.load_max_over_mean"] >= 1.0
    assert metrics["moe.dropped_rows"] == 0.0
    assert 0.0 <= metrics["moe.bias_moved_pct"] <= 100.0
    # the rule has moved b by then: at most u a step
    assert 0.0 < metrics["moe.bias_abs_mean"] < 0.001 * (out["attempted"] + 3)
    # 3 x 3 tiles of 128: the band of 100 leaves 5 of 6 in 4 layers of 5
    assert metrics["attn.active_tile_share"] == pytest.approx(26 / 30)
    assert {"data.wait_ms", "place.ms", "loop.dispatch_ms"} <= set(metrics)
    assert "kernel.pallas_ms" not in metrics  # nothing ran on a TPU


def test_a_program_without_the_model_fails_at_set_up_before_it_compiles(
        monkeypatch):
    """The parent commit, handed these benchmark files, has no
    ``models/window_moe.py``: set-up stops at that import, before ``cli.lm``
    is asked for anything."""
    from distributed_machine_learning_tpu.cli import lm as cli

    monkeypatch.setitem(
        sys.modules, "distributed_machine_learning_tpu.models.window_moe",
        None)
    monkeypatch.setattr(cli, "main", lambda argv: pytest.fail("cli ran"))
    with pytest.raises(ImportError):
        family.setup(TINY_CONFIG, TINY_TRAFFIC, seed=1)


def test_the_flop_counts_are_the_issue_s_arithmetic():
    """``6·outside + 6·L_sparse·(k·held/width)·expert + Σ layers' attention``
    at the published widths: 2.44 GFLOP a token, 32% of it the attention
    kernels' (403 MFLOP in the one full layer, 94 in each window layer)."""
    config = harness.load_json(os.path.join(
        REPO, "benchmark", "configs", "trinity_mini_26b_a3b.json"))
    T, w = 16384, 2048
    window = family.attention_core_flops_per_token(config, T, 0)
    full = family.attention_core_flops_per_token(config, T, 4)
    assert full == 12 * 32 * 128 * T / 2
    assert window == 12 * 32 * 128 * (w - w * (w - 1) / (2 * T))
    assert round(full / 1e6) == 403 and round(window / 1e6) == 94
    # k̄ is the mean number of visible keys, counted
    i = np.arange(T)
    assert (np.minimum(i + 1, w)).mean() == pytest.approx(
        w - w * (w - 1) / (2 * T))
    # a window the sequence never fills is no window
    assert family.attention_core_flops_per_token(config, 1024, 0) \
        == 12 * 32 * 128 * 512
    outside = 251_571_456  # parameters outside embedding, experts and bias
    got = family.train_flops_per_token(config, outside, T)
    want = (6 * outside + 6 * 4 * (8 * 16 / 128) * 3 * 2048 * 1024
            + 4 * window + full)
    assert got == pytest.approx(want, rel=1e-12)
    assert 2.43e9 < got < 2.45e9 and 0.31 < (4 * window + full) / got < 0.33
    # the other side of the roofline: 61 824 bytes a token a layer, far under what
    # 819 GB/s moves in the time 197 TFLOP/s need for the window's FLOPs
    moved = family.attention_core_bytes_per_token(config)
    assert moved == 2 * (2 * 4096 + 1024) + 128 + 2 * (2 * 4096 + 1024) \
        + 256 + 2 * 3 * 4096 == 61824
    assert moved / 819e9 < 0.2 * window / 197e12
