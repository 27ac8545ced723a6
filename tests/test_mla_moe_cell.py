"""The ``mla_moe_lm`` benchmark family through the harness's whole run path
(``harness.run_cell(require_tpu=False)``) at a tiny size on the CPU: set-up
through ``cli.lm --model-config``, the reference check, a window of
``train_epoch``, and the routing counts — the share of tokens whose chosen
set the selection bias moved among them — as per-layer metrics of a traced
run."""

from __future__ import annotations

import json
import os
import shutil
import time

import pytest

from benchmark import harness
from benchmark.families import mla_moe_lm as family

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "t_mla"
TINY_CONFIG = {
    "family": "mla_moe_lm", "model_type": "deepseek_v3", "vocab_size": 128,
    "hidden_size": 64, "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "intermediate_size": 96, "num_attention_heads": 4,
    "num_key_value_heads": 4, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "kv_lora_rank": 24, "q_lora_rank": None,
    "rope_theta": 1000000, "rope_interleave": True, "n_routed_experts": 4,
    "router_width": 16, "held_experts": [4, 4], "num_experts_per_tok": 3,
    "moe_intermediate_size": 32, "n_shared_experts": 2,
    "routed_scaling_factor": 2.448, "scoring_func": "sigmoid",
    "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
    "norm_topk_prob": True, "rms_norm_eps": 1e-6}
TINY_TRAFFIC = {
    "argv": ["--parallel", "dp", "--attn", "flash", "--optimizer", "adamw",
             "--fused-ce-chunks", "2"],
    "seq_len": 128, "seqs_per_chip": 1, "check_seqs": 1, "warm_iters": 2,
    "trace_steps": 3}
ROUTING_METRICS = (("moe.held_rows", "rows", "higher"),
                   ("moe.load_max_over_mean", "ratio", "lower"),
                   ("moe.dropped_rows", "rows", "lower"),
                   ("moe.bias_moved_pct", "%", "higher"))


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """The committed manifest with one cell of added files: the tiny
    configuration, its traffic, and the committed metric files."""
    root = tmp_path_factory.mktemp("tiny_mla_benchmark")
    shutil.copytree(os.path.join(REPO, "benchmark", "metrics"),
                    root / "benchmark" / "metrics")
    for rel, body in (("configs/tiny_mla.json", TINY_CONFIG),
                      (f"traffic/{CELL}.json", TINY_TRAFFIC)):
        path = root / "benchmark" / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(body))
    manifest = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    manifest["configs"] = [
        {"name": "tiny_mla", "source": "test", "reduced": [],
         "file": "benchmark/configs/tiny_mla.json", "why": "test"}]
    manifest["workloads"] = [
        {"name": CELL, "config": "tiny_mla", "traffic": CELL, "chips": 1,
         "why": "test"}]
    for group in ("end_to_end", "per_layer"):
        for metric in manifest[group]:
            if "kanana2_a3b_dp_s8192" in metric.get("workloads", ()):
                metric["workloads"] = [CELL]
    # The routing counts as per-layer metrics: the committed metric files,
    # entered the way a benchmark PR will enter them (PERF.md §7 on why
    # BENCHMARK.json does not list them yet).
    manifest["per_layer"] += [
        {"name": name, "unit": unit, "better": better,
         "source": "program_counter", "layer": "expert layer",
         "moves": "mfu_pct", "workloads": [CELL]}
        for name, unit, better in ROUTING_METRICS]
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return str(root)


def _printed(capsys, line):
    out = capsys.readouterr().out
    return json.loads(out.split(line + " ")[1].splitlines()[0]), out


def test_the_family_runs_a_cell_at_a_tiny_size(tiny_root, capsys):
    out = harness.run_cell(tiny_root, CELL, seed=2**31 + 31, seconds=3.0,
                           trace=False, t0=time.perf_counter(),
                           require_tpu=False)
    check, printed = _printed(capsys, "bench.check")
    assert check["ok"] is True, printed
    assert check["seq_len"] == 128 and len(check["grad_cosine"]) == 9
    assert 0.0 <= check["top_k_differing_share"] < 0.05
    assert check["selection_bias_drift"] == 0.0  # b is the seeded draw
    assert out["correct"] is True and out["failed"] == 0, printed
    assert out["attempted"] >= 2
    assert set(out["metrics"]) == {"tokens_per_s_chip", "step_ms_p90",
                                   "setup_s"}  # no MFU off the chip
    window = json.loads(printed.split("bench.window ")[1].splitlines()[0])
    assert window["compilations_in_window"] == 0
    assert window["items_per_step"] == 8 * 128  # the 8 virtual devices


def test_a_selection_bias_the_optimizer_touched_fails_the_check(
        tiny_root, capsys, monkeypatch):
    """With ``frozen_params`` emptied the timed path's ``_update`` hands
    ``b`` to AdamW: no gradient reaches it, the decay alone moves it by
    lr · 0.01 · |b| a step — far inside every other limit of the check,
    which feeds the resident ``b`` to both sides."""
    from distributed_machine_learning_tpu.models.mla_moe import MLAMoELM

    monkeypatch.setattr(MLAMoELM, "frozen_params", ())
    out = harness.run_cell(tiny_root, CELL, seed=2**31 + 31, seconds=1.0,
                           trace=False, t0=time.perf_counter(),
                           require_tpu=False)
    check, printed = _printed(capsys, "bench.check")
    # two warm iterations at 3e-4: at most 2 · 3e-4 · 0.01 · 0.05 = 3e-7
    assert family.BIAS_DRIFT_ATOL < check["selection_bias_drift"] < 3.1e-7
    assert check["ok"] is False and out["correct"] is False, printed
    assert min(check["grad_cosine"].values()) >= family.GRAD_COSINE


def test_a_traced_run_reports_the_four_routing_metrics(tiny_root, capsys):
    # Eight seconds: see tests/test_hybrid_moe_cell.py on what a traced
    # window needs on a host that runs five other test workers.
    out = harness.run_cell(tiny_root, CELL, seed=13, seconds=8.0, trace=True,
                           t0=time.perf_counter(), require_tpu=False)
    check, printed = _printed(capsys, "bench.check")
    assert check["ok"] is True and out["correct"] is True, printed
    metrics = {name: m["value"] for name, m in out["metrics"].items()}
    # 128 tokens a chip x 3 a token x 4 of 16 experts held = 96 expected
    assert 40 < metrics["moe.held_rows"] < 192
    assert metrics["moe.load_max_over_mean"] >= 1.0
    assert metrics["moe.dropped_rows"] == 0.0
    assert 0.0 < metrics["moe.bias_moved_pct"] <= 100.0
    assert out["metrics"]["moe.bias_moved_pct"]["unit"] == "%"
    assert {"data.wait_ms", "place.ms", "loop.dispatch_ms"} <= set(metrics)
    assert "kernel.pallas_ms" not in metrics  # nothing ran on a TPU


def test_the_flop_counts_are_the_issue_s_arithmetic():
    """``6·outside + 6·L_sparse·(k·held/width)·expert + L·3·H·(d_qk +
    d_v)·T`` at the published widths: 2.79 GFLOP a token, 45% of it the
    latent attention kernels'."""
    config = harness.load_json(os.path.join(
        REPO, "benchmark", "configs", "kanana2_30b_a3b.json"))
    core = family.attention_core_flops_per_token(config, 8192)
    assert core == 3 * 32 * (192 + 128) * 8192
    outside = 241_132_032  # parameters outside embedding, experts and bias
    got = family.train_flops_per_token(config, outside, 8192)
    want = (6 * outside + 6 * 4 * (6 * 16 / 128) * 3 * 2048 * 768 + 5 * core)
    assert got == pytest.approx(want, rel=1e-12)
    assert 2.78e9 < got < 2.80e9 and 0.44 < 5 * core / got < 0.46
