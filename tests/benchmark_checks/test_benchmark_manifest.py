"""``BENCHMARK.json`` against its contract, the data-driven look-up, the
benchmark's own arithmetic, and the whole run path at a tiny size on the CPU
(through the Python-level seam ``require_tpu=False``; the command line has no
such switch)."""

from __future__ import annotations

import importlib
import json
import os
import re
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

from benchmark import flops, generate, harness

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _load(rel):
    with open(os.path.join(REPO, rel), encoding="utf-8") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def manifest():
    return _load("BENCHMARK.json")


# ------------------------------------------------------------ the contract

def test_top_level_keys_and_sizes(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= manifest["run_seconds"] <= 51
    assert manifest["paths"] == ["benchmark", "tests/benchmark_checks"]
    assert manifest["command"][-1].startswith("benchmark/")
    assert 1 <= len(manifest["workloads"]) <= 24
    assert 1 <= len(manifest["configs"]) <= 24


def test_names_units_and_lines(manifest):
    names = []
    for group, keys in (
        ("configs", {"name", "source", "file", "reduced", "why"}),
        ("workloads", {"name", "config", "traffic", "chips", "why"}),
        ("end_to_end", {"name", "unit", "better", "bound", "source"}),
        ("per_layer", {"name", "unit", "better", "source", "layer", "moves"}),
    ):
        for entry in manifest[group]:
            assert set(entry) - {"workloads"} == keys, entry["name"]
            assert NAME.match(entry["name"]), entry["name"]
            names.append((group in ("end_to_end", "per_layer"), entry["name"]))
            for key in ("why", "layer", "source"):
                if key in entry:
                    text = entry[key]
                    assert 1 <= len(text) <= 200 and "\n" not in text \
                        and "\t" not in text, (entry["name"], key, len(text))
            if "unit" in entry:
                assert UNIT.match(entry["unit"]), entry["unit"]
                assert entry["better"] in ("lower", "higher")
                assert entry["source"] in SOURCES
    assert len(names) == len(set(names)), "a name is used twice"
    for cell in manifest["workloads"]:
        assert NAME.match(cell["config"]) and NAME.match(cell["traffic"])
        assert cell["chips"] in (1, 4)
    pairs = [(c["config"], c["traffic"]) for c in manifest["workloads"]]
    assert len(pairs) == len(set(pairs))
    for config in manifest["configs"]:
        assert len(config["reduced"]) <= 16
        assert all(NAME.match(k) for k in config["reduced"])
        assert any(c["config"] == config["name"] for c in manifest["workloads"])


def test_bounds_and_sources_of_end_to_end_metrics(manifest):
    by_name = {m["name"]: m for m in manifest["end_to_end"]}
    assert by_name["setup_s"]["bound"] <= 0.1
    for metric in manifest["end_to_end"]:
        assert 0.01 <= metric["bound"] <= 0.1, metric["name"]
        assert metric["source"] in ("host_clock", "device_trace")


def test_every_cell_reports_what_the_contract_asks(manifest):
    for cell in manifest["workloads"]:
        e2e = [m["name"] for m in
               harness.metrics_of(manifest, "end_to_end", cell["name"])]
        assert "setup_s" in e2e and len(e2e) >= 2, cell["name"]
        assert harness.metrics_of(manifest, "per_layer", cell["name"])


def test_every_moves_is_reported_wherever_the_metric_is(manifest):
    cells = [c["name"] for c in manifest["workloads"]]
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    for metric in manifest["per_layer"]:
        target = e2e[metric["moves"]]
        for cell in metric.get("workloads", cells):
            assert cell in cells
            assert cell in target.get("workloads", cells), \
                (metric["name"], "moves", target["name"], "not in", cell)


def test_at_most_a_quarter_of_the_cells_take_four_chips(manifest):
    four = sum(1 for c in manifest["workloads"] if c["chips"] == 4)
    assert four <= max(1, len(manifest["workloads"]) // 4)


def test_every_named_file_exists(manifest):
    for config in manifest["configs"]:
        assert config["file"].startswith("benchmark/")
        body = _load(config["file"])
        importlib.import_module("benchmark.families." + body["family"])
        assert body["reduced"] == config["reduced"]
    files = [c["file"] for c in manifest["configs"]]
    assert len(files) == len(set(files))
    for cell in manifest["workloads"]:
        _load(f"benchmark/traffic/{cell['traffic']}.json")
    for metric in manifest["per_layer"]:
        how = _load(f"benchmark/metrics/{metric['name']}.json")
        reader = importlib.import_module("benchmark.readers." + how["reader"])
        assert callable(reader.read)
    for path in manifest["paths"]:
        for _, _, names in os.walk(os.path.join(REPO, path)):
            for name in names:
                if not name.endswith(".pyc"):
                    assert re.fullmatch(r"[A-Za-z0-9_.\-]+", name), name


def test_starcoder2_widths_are_the_published_ones():
    config = _load("benchmark/configs/starcoder2_3b.json")
    published = {"hidden_size": 3072, "intermediate_size": 12288,
                 "num_attention_heads": 24, "num_key_value_heads": 2,
                 "head_dim": 128, "vocab_size": 49152,
                 "max_position_embeddings": 16384, "sliding_window": 4096}
    for key, value in published.items():
        assert config[key] == value, key
    assert config["published"]["num_hidden_layers"] == 30
    changed = sorted(k for k, v in config["published"].items()
                     if config[k] != v)
    assert changed == sorted(config["reduced"])
    widths = ("size", "_dim", "_rank", "heads", "window")
    assert not any(w in key for key in config["reduced"] for w in widths)


# ---------------------------------------------------- arithmetic and traffic

def test_vgg11_flops_per_image():
    cfg = _load("benchmark/configs/vgg11_cifar10.json")["cfg"]
    assert flops.vgg_train_flops_per_image(cfg) / 1e9 == \
        pytest.approx(0.9166, abs=5e-5)


def test_lm_flops_leave_the_embedding_table_out():
    d, layers, vocab, seq = 3072, 4, 49152, 4096
    block = 12 * d * d  # any count will do: the formula is linear in it
    n_params = vocab * d + layers * block + vocab * d
    got = flops.lm_train_flops_per_token(n_params, vocab * d, layers, d, seq)
    assert got == 6.0 * (layers * block + vocab * d) + 6.0 * layers * d * seq


def test_peaks_table_refuses_an_unlisted_device():
    assert flops.peak_for("TPU v5 lite")["bf16_tflops"] == 197.0
    with pytest.raises(KeyError, match="not in the benchmark's peaks"):
        flops.peak_for("cpu")
    # 197 TFLOP/s at 1 GFLOP an item and 98.5 k items/s is half the peak
    assert flops.mfu_pct(1e9, 98_500.0, "TPU v5 lite") == pytest.approx(50.0)


def test_traffic_is_a_function_of_the_seed_alone():
    params = {"n_images": 64, "height": 32, "width": 32, "channels": 3,
              "classes": 10, "noise": 40}
    big = 2**31 + 12345  # the driver's seeds do not fit 32 signed bits
    a, la = generate.images(big, **params)
    b, lb = generate.images(big, **params)
    c, _ = generate.images(big + 1, **params)
    assert a.dtype == np.uint8 and a.shape == (64, 32, 32, 3)
    assert (a == b).all() and (la == lb).all() and (a != c).any()
    assert c.shape == a.shape
    t1 = next(generate.token_blocks(big, batch=2, seq_len=16, vocab=50))
    t2 = next(generate.token_blocks(big, batch=2, seq_len=16, vocab=50))
    t3 = next(generate.token_blocks(big, batch=2, seq_len=16, vocab=50,
                                    stream=1))
    assert (t1[0] == t2[0]).all() and (t1[0] != t3[0]).any()
    assert (t1[0][:, 1:] == t1[1][:, :-1]).all()  # targets: shifted by one
    assert t1[0].shape == (2, 16) and t1[0].max() < 50


def test_window_counts_only_whole_steps_between_its_marks():
    """Three steps are discarded, the window opens at a ``next()`` and ends
    at the first ``next()`` past the deadline, after a sync."""
    synced = []
    window = harness.Window(iter(range(10**6)), seconds=0.05,
                            sync=lambda: synced.append(time.perf_counter()))
    seen = [next(window) for _ in range(harness.DISCARD_STEPS + 2)]
    assert seen == list(range(harness.DISCARD_STEPS + 2))
    assert window.t_open == window.entries[harness.DISCARD_STEPS]
    time.sleep(0.06)
    with pytest.raises(StopIteration):
        next(window)
    assert len(window.periods) == 2
    assert sum(window.periods) == pytest.approx(window.t_close - window.t_open)
    assert synced and synced[-1] <= window.t_close


def test_step_probe_keeps_losses_and_stays_the_step():
    """The wrapper the loop is handed: same results, every loss kept as a
    device array, the step's own attributes forwarded, and the compiler's
    memory analysis of the program as it was called."""
    import jax
    import jax.numpy as jnp

    step = jax.jit(lambda state, x: (state + x.sum(), x.mean()))
    step.pop_gather_seconds = lambda: 0.25
    probe = harness.StepProbe(step, traced=False)
    state = jnp.zeros(())
    for i in range(3):
        state, loss = probe(state, np.full((4, 4), float(i), np.float32))
    assert float(state) == 48.0 and float(loss) == 2.0
    assert [float(v) for v in probe.losses] == [0.0, 1.0, 2.0]
    assert probe.pop_gather_seconds() == 0.25
    probe.sync()
    analysis = probe.compiled_memory()
    assert analysis.argument_size_in_bytes >= 4 * 4 * 4


# ------------------------------------------------- driven by data, end to end

TINY_DATA = {"n_images": 256, "height": 32, "width": 32, "channels": 3,
             "classes": 10, "noise": 40}
TINY_FILES = {
    "benchmark/configs/tiny_vgg.json": {
        "family": "cnn_part", "model": "vggtest",
        "cfg": [8, "M", 16, "M", 16, "M", 16, "M", 16, "M"],
        "image_size": 32, "num_channels": 3, "num_classes": 10},
    "benchmark/configs/tiny_lm.json": {
        "family": "lm", "hidden_size": 64, "intermediate_size": 256,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "num_hidden_layers": 2, "vocab_size": 128},
    "benchmark/traffic/t_part1.json": {
        "cli": "part1", "argv": ["--loader", "native"], "per_rank_batch": 16,
        "check_batch": 8, "warm_iters": 2, "trace_steps": 3,
        "data": TINY_DATA},
    "benchmark/traffic/t_part3.json": {
        "cli": "part3", "argv": ["--loader", "native"], "per_rank_batch": 4,
        "check_batch": 4, "warm_iters": 2, "trace_steps": 3,
        "data": TINY_DATA},
    "benchmark/traffic/t_lm.json": {
        "argv": ["--parallel", "dp", "--attn", "flash", "--optimizer",
                 "adamw", "--fused-ce-chunks", "2"],
        "seq_len": 128, "seqs_per_chip": 1, "check_seqs": 1, "warm_iters": 2,
        "trace_steps": 3},
    # a metric added as a file: an existing reader, another field
    "benchmark/metrics/loop.block_ms.json": {
        "reader": "step_row_median",
        "args": {"field": "block_s", "scale": 1000.0}},
}


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory, manifest):
    """A benchmark made of added files only: two configurations, three cells
    and one per-layer metric that the committed harness has never seen."""
    root = tmp_path_factory.mktemp("tiny_benchmark")
    shutil.copytree(os.path.join(REPO, "benchmark", "metrics"),
                    root / "benchmark" / "metrics")
    for rel, body in TINY_FILES.items():
        path = root / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(body))
    cells = ["t_part1", "t_part3", "t_lm"]
    tiny = json.loads(json.dumps(manifest))
    tiny["configs"] = [
        {"name": n, "source": "test", "file": f"benchmark/configs/{n}.json",
         "reduced": [], "why": "test"} for n in ("tiny_vgg", "tiny_lm")]
    tiny["workloads"] = [
        {"name": c, "config": "tiny_lm" if c == "t_lm" else "tiny_vgg",
         "traffic": c, "chips": 1, "why": "test"} for c in cells]
    for group in ("end_to_end", "per_layer"):
        for metric in tiny[group]:
            if "workloads" in metric:
                metric["workloads"] = cells
    tiny["per_layer"].append(
        {"name": "loop.block_ms", "unit": "ms", "better": "lower",
         "source": "program_span", "layer": "step loop", "moves": "mfu_pct",
         "workloads": ["t_part3"]})
    (root / "BENCHMARK.json").write_text(json.dumps(tiny))
    return str(root)


def test_added_files_are_found_without_editing_the_harness(tiny_root):
    spec = harness.load_cell(tiny_root, "t_lm")
    assert spec["config"]["hidden_size"] == 64
    assert spec["traffic"]["seq_len"] == 128
    names = [m["name"] for m in harness.metrics_of(
        spec["manifest"], "per_layer", "t_part3")]
    assert "loop.block_ms" in names and "sync.exposed_ms" in names
    with pytest.raises(SystemExit):
        harness.load_cell(tiny_root, "no_such_cell")


def _assert_correct_but_for_the_trend(out, printed):
    """Every ingredient of ``correct`` on its own.  Whether the loss fell is
    not asserted: a tiny net in a one-second window on a loaded host runs a
    handful of steps, too few for a trend; ``correct`` must follow it."""
    check = json.loads(printed.split("bench.check ")[1].splitlines()[0])
    window = json.loads(printed.split("bench.window ")[1].splitlines()[0])
    assert check["ok"] is True, printed
    assert window["compilations_in_window"] == 0
    assert window["guard_skipped"] == 0 and out["failed"] == 0
    fell = window["loss_last_tenth"] <= window["loss_first_tenth"]
    must_fall = "bn_groups" in check  # the CNN family's rule, not the LM's
    assert out["correct"] is (fell or not must_fall), printed


@pytest.mark.parametrize("cell, item", [
    ("t_part1", "images"),   # plain jit: one device
    ("t_part3", "images"),   # shard_map ring over the 8 virtual devices
    ("t_lm", "tokens"),      # dp over the 8 virtual devices, flash + fused CE
])
def test_whole_run_path_at_a_tiny_size(tiny_root, cell, item, capsys):
    out = harness.run_cell(tiny_root, cell, seed=2**31 + 7, seconds=1.0,
                           trace=False, t0=time.perf_counter(),
                           require_tpu=False)
    printed = capsys.readouterr().out
    _assert_correct_but_for_the_trend(out, printed)
    assert out["failed"] == 0 and out["attempted"] >= 3
    assert set(out["metrics"]) == {f"{item}_per_s_chip", "step_ms_p90",
                                   "setup_s"}  # no MFU off the chip
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["device"]["platform"] == "cpu"
    for line in ("bench.device ", "bench.check ", "bench.loss_at ",
                 "bench.window "):
        assert line in printed
    window = json.loads(printed.split("bench.window ")[1].splitlines()[0])
    assert window["steps"] == out["attempted"]


def test_traced_run_reports_per_layer_metrics_and_skips_what_it_cannot_read(
        tiny_root, capsys):
    out = harness.run_cell(tiny_root, "t_part3", seed=5, seconds=1.0,
                           trace=True, t0=time.perf_counter(),
                           require_tpu=False)
    printed = capsys.readouterr().out
    _assert_correct_but_for_the_trend(out, printed)
    # Host spans come from train_epoch's step rows, the added metric among
    # them; nothing ran on a TPU, so every device-trace metric is left out.
    assert {"data.wait_ms", "place.ms", "loop.dispatch_ms",
            "loop.block_ms"} <= set(out["metrics"])
    assert not {"step.device_ms", "device.idle_pct", "sync.exposed_ms",
                "device.peak_hbm_gib"} & set(out["metrics"])
    assert "breakdown" not in out and "busy_s" not in out["device"]
    assert "bench.trace_inventory " in printed


# ----------------------------------------------------------- the command line

def _run(cwd, *extra_env):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **dict(extra_env)}
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "vgg11_part3_w1",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_py_refuses_a_backend_that_is_not_a_tpu():
    done = _run(REPO)
    assert done.returncode != 0
    assert "not a TPU" in done.stderr
    assert '"correct"' not in done.stdout


def test_run_py_fails_in_a_directory_with_the_benchmark_alone(tmp_path,
                                                              manifest):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    for path in manifest["paths"]:
        shutil.copytree(os.path.join(REPO, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    done = _run(str(tmp_path))
    assert done.returncode != 0
    assert '"correct"' not in done.stdout
