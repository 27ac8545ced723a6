"""Nothing may hide the device: where the compile cache lives, which
peak an MFU is computed against, and what a measurement script does when
there is no TPU (ISSUE 22)."""

import os
import subprocess
import sys

import jax
import pytest

from distributed_machine_learning_tpu.runtime import compile_cache
from distributed_machine_learning_tpu.utils.flops import DEVICE_PEAKS, mfu

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _recorded_updates(monkeypatch):
    calls = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda key, value: calls.__setitem__(key, value))
    return calls


def test_cache_dir_placed_from_outside_is_not_set_in_code(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    calls = _recorded_updates(monkeypatch)
    assert compile_cache.configure_compile_cache() == "/somewhere/else"
    assert "jax_compilation_cache_dir" not in calls
    assert "jax_persistent_cache_min_compile_time_secs" in calls


def test_cache_dir_defaults_to_fixed_path_inside_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    calls = _recorded_updates(monkeypatch)
    want = os.path.join(REPO, ".jax_cache")
    assert compile_cache.configure_compile_cache() == want
    assert calls["jax_compilation_cache_dir"] == want


def test_cache_dir_identical_across_processes(tmp_path):
    code = ("from distributed_machine_learning_tpu.runtime.compile_cache "
            "import configure_compile_cache; print(configure_compile_cache())")
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = REPO
    seen = {
        subprocess.run([sys.executable, "-c", code], cwd=cwd, env=env,
                       capture_output=True, text=True, check=True,
                       timeout=120).stdout.strip()
        for cwd in (REPO, str(tmp_path))
    }
    assert seen == {os.path.join(REPO, ".jax_cache")}


def test_peak_table_known_and_unknown_kind():
    assert DEVICE_PEAKS["TPU v5 lite"].bf16_tflops == 197.0
    assert DEVICE_PEAKS["TPU v5 lite"].hbm_gb_per_s == 819.0
    assert mfu(98.5e12, "TPU v5 lite") == pytest.approx(0.5)
    assert mfu(98.5e12, "cpu") is None
    assert mfu(98.5e12, jax.devices()[0].device_kind) is None  # this host


def test_measurement_scripts_refuse_a_cpu_backend():
    """``chip_smoke.py`` is the one root script left that speaks for the
    chip (the benchmark has ``benchmark/harness.py::require_device``)."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")], cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=120,
    )
    assert proc.returncode != 0 and "no TPU found" in proc.stderr
    # no metric line from a CPU run: no phase passed, no JSON verdict
    assert "PASS" not in proc.stdout
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


def test_banner_names_the_device_and_kernel_mode():
    from distributed_machine_learning_tpu.cli.common import device_banner

    assert device_banner(pallas=False) == "platform=cpu device_kind='cpu'"
    assert device_banner(pallas=True).endswith("pallas=interpreted")
