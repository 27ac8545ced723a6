"""chip_smoke.py on the CPU: it refuses to run, and every phase function
still works — called here at a tiny size so the file cannot rot between
chip runs (the script itself has no small mode and no CPU mode)."""

import importlib.util
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")

TINY_CNN = ["--model", "vggtest", "--batch-size", "4", "--max-iters", "21",
            "--eval-batches", "2", "--eval-batch-size", "16",
            "--loader", "native"]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_script_exits_nonzero_without_a_tpu():
    proc = subprocess.run(
        [sys.executable, SMOKE], cwd=REPO, capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=120,
    )
    assert proc.returncode != 0
    assert "no TPU found" in proc.stderr
    assert '"ok"' not in proc.stdout  # no result line


def test_part1_phase_tiny(smoke):
    smoke.phase_part1(TINY_CNN)


def test_part3_phase_tiny(smoke, devices):
    fused = TINY_CNN + [
        "--ring-compress", "int8", "--ring-codec-impl", "pallas",
        "--optimizer", "adamw", "--fused-update", "--dist-eval",
    ]
    smoke.phase_part3(TINY_CNN, fused, kernels="interpreted", per_rank=4)


def test_default_checks_fail_on_interpreted_kernels(smoke):
    """What the script passes (kernels="compiled") fails on a run whose
    banner says interpreted or whose lowered step has no Mosaic call."""
    with pytest.raises(smoke.SmokeFailure, match="pallas=compiled"):
        smoke._require_kernel_mode(
            "strategy=ring platform=cpu pallas=interpreted", "compiled")
    with pytest.raises(smoke.SmokeFailure, match="no Mosaic custom call"):
        smoke._require_mosaic("stablehlo.add", "compiled", "step")
    smoke._require_mosaic("custom_call @tpu_custom_call", "compiled", "step")


def test_lm_phase_tiny(smoke, devices):
    smoke.phase_lm(
        ["--parallel", "dp", "--d-model", "32", "--n-heads", "4",
         "--n-kv-heads", "2", "--n-layers", "1", "--vocab", "64",
         "--seq-len", "128", "--batch-size", str(len(devices)),
         "--attn", "flash", "--fused-ce-chunks", "2", "--max-iters", "21"],
        kernels="interpreted",
    )


def test_kernels_phase_tiny(smoke, devices):
    smoke.phase_kernels(
        dict(heads=4, kv_heads=2, head_dim=32, cache_len=128, cache_batch=1,
             cache_pos=100, page_block=16, pages=20, page_positions=(3, 40),
             matmul=(8, 64, 128), codec_len=1237, delta_len=70, delta_heads=1,
             prepare_len=128, prepare_heads=2,
             ring_seq_per_chip=16, ring_batch=1),
        kernels="interpreted",
    )
