"""The ``hybrid_moe_lm`` benchmark family through the harness's whole run
path (``harness.run_cell(require_tpu=False)``) at a tiny size on the CPU:
set-up through ``cli.lm --model-config``, the reference check, a window of
``train_epoch``, and the routing counts as per-layer metrics of a traced
run."""

from __future__ import annotations

import json
import os
import shutil
import time

import pytest

from benchmark import harness
from benchmark.families import hybrid_moe_lm as family

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "t_hybrid"
TINY_CONFIG = {
    "family": "hybrid_moe_lm", "model_type": "qwen3_next",
    "vocab_size": 128, "hidden_size": 64, "num_hidden_layers": 4,
    "full_attention_interval": 4, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 32, "partial_rotary_factor": 0.25,
    "rope_theta": 10000000, "linear_num_key_heads": 2,
    "linear_num_value_heads": 4, "linear_key_head_dim": 16,
    "linear_value_head_dim": 16, "linear_conv_kernel_dim": 4,
    "num_experts": 4, "router_width": 16, "held_experts": [4, 4],
    "num_experts_per_tok": 3, "moe_intermediate_size": 32,
    "shared_expert_intermediate_size": 32, "norm_topk_prob": True,
    "rms_norm_eps": 1e-6}
TINY_TRAFFIC = {
    "argv": ["--parallel", "dp", "--attn", "flash", "--optimizer", "adamw",
             "--fused-ce-chunks", "2"],
    "seq_len": 128, "seqs_per_chip": 1, "check_seqs": 1, "warm_iters": 2,
    "trace_steps": 3}


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """The committed manifest with one cell of added files: the tiny
    configuration, its traffic, and the committed metric files."""
    root = tmp_path_factory.mktemp("tiny_hybrid_benchmark")
    shutil.copytree(os.path.join(REPO, "benchmark", "metrics"),
                    root / "benchmark" / "metrics")
    for rel, body in (("configs/tiny_hybrid.json", TINY_CONFIG),
                      (f"traffic/{CELL}.json", TINY_TRAFFIC)):
        path = root / "benchmark" / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(body))
    manifest = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    manifest["configs"] = [
        {"name": "tiny_hybrid", "source": "test", "reduced": [],
         "file": "benchmark/configs/tiny_hybrid.json", "why": "test"}]
    manifest["workloads"] = [
        {"name": CELL, "config": "tiny_hybrid", "traffic": CELL, "chips": 1,
         "why": "test"}]
    for group in ("end_to_end", "per_layer"):
        for metric in manifest[group]:
            if "q3next_a3b_dp_s8192" in metric.get("workloads", ()):
                metric["workloads"] = [CELL]
    # The routing counts as per-layer metrics: the committed metric files,
    # entered the way a benchmark PR will enter them (PERF.md §7 on why
    # BENCHMARK.json does not list them yet).
    manifest["per_layer"] += [
        {"name": name, "unit": unit, "better": better,
         "source": "program_counter", "layer": "expert layer",
         "moves": "mfu_pct", "workloads": [CELL]}
        for name, unit, better in (
            ("moe.held_rows", "rows", "higher"),
            ("moe.load_max_over_mean", "ratio", "lower"),
            ("moe.dropped_rows", "rows", "lower"))]
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return str(root)


def _printed(capsys, line):
    out = capsys.readouterr().out
    return json.loads(out.split(line + " ")[1].splitlines()[0]), out


def test_the_family_runs_a_cell_at_a_tiny_size(tiny_root, capsys):
    # Three seconds: a step of the 8-device tiny model takes 0.4 s when the
    # host runs five other test workers.
    out = harness.run_cell(tiny_root, CELL, seed=2**31 + 27, seconds=3.0,
                           trace=False, t0=time.perf_counter(),
                           require_tpu=False)
    check, printed = _printed(capsys, "bench.check")
    assert check["ok"] is True, printed
    assert check["seq_len"] == 128 and len(check["grad_cosine"]) == 9
    assert 0.0 <= check["top_k_differing_share"] < 0.05
    assert out["correct"] is True and out["failed"] == 0, printed
    assert out["attempted"] >= 2
    assert set(out["metrics"]) == {"tokens_per_s_chip", "step_ms_p90",
                                   "setup_s"}  # no MFU off the chip
    window = json.loads(printed.split("bench.window ")[1].splitlines()[0])
    assert window["compilations_in_window"] == 0
    assert window["items_per_step"] == 8 * 128  # the 8 virtual devices


def test_a_traced_run_reports_the_routing_counts(tiny_root, capsys):
    # Eight seconds: on a host that runs five other test workers the traced
    # stretch (three steps, the profiler's start and stop) takes up to four,
    # the metrics read the rows after it, and a step's counts reach the row
    # after its own.
    out = harness.run_cell(tiny_root, CELL, seed=11, seconds=8.0, trace=True,
                           t0=time.perf_counter(), require_tpu=False)
    check, printed = _printed(capsys, "bench.check")
    assert check["ok"] is True and out["correct"] is True, printed
    metrics = {name: m["value"] for name, m in out["metrics"].items()}
    # 128 tokens a chip x 3 a token x 4 of 16 experts held = 96 expected
    assert 48 < metrics["moe.held_rows"] < 192
    assert metrics["moe.load_max_over_mean"] >= 1.0
    assert metrics["moe.dropped_rows"] == 0.0
    assert {"data.wait_ms", "place.ms", "loop.dispatch_ms"} <= set(metrics)
    assert "kernel.pallas_ms" not in metrics  # nothing ran on a TPU


def test_the_flop_count_is_the_issue_s_arithmetic():
    """``6·outside + 6·L·(k·held/width)·expert + 6·(H·dh)·T`` an attention
    layer ``+ 18·dk·dv·Hv`` a DeltaNet layer, at the published widths."""
    config = harness.load_json(os.path.join(
        REPO, "benchmark", "configs", "qwen3_next_80b_a3b.json"))
    outside = 184_118_336  # parameters outside embedding and routed experts
    got = family.train_flops_per_token(config, outside, 8192)
    want = (6 * outside + 6 * 4 * (10 * 32 / 512) * 3 * 2048 * 512
            + 6 * 1 * 4096 * 8192 + 3 * 18 * 128 * 128 * 32)
    assert got == pytest.approx(want, rel=1e-12)
    assert 1.37e9 < got < 1.39e9
