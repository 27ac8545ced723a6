"""Model FLOPs and chip peaks — the benchmark's own arithmetic.

Copied from ``distributed_machine_learning_tpu/utils/flops.py`` with one
correction: the LM count leaves the embedding table out of ``6·P`` (a lookup
is a gather, not a matmul).  A matmul or conv counts multiply and add (×2);
a training step is 3× the forward pass; recomputation is never counted.
"""

from __future__ import annotations

import json
import os

_PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "peaks.json")


def load_peaks(path: str = _PEAKS_FILE) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def peak_for(device_kind: str, peaks: dict | None = None) -> dict:
    """The listed peaks of ``device_kind``; an unlisted device is an error,
    never a default."""
    peaks = load_peaks() if peaks is None else peaks
    if device_kind not in peaks:
        raise KeyError(
            f"device_kind {device_kind!r} is not in the benchmark's peaks "
            f"table ({sorted(peaks)}); no utilization can be stated for it"
        )
    return peaks[device_kind]


def vgg_train_flops_per_image(cfg: list, image_hw: int = 32,
                              in_channels: int = 3, num_classes: int = 10,
                              kernel: int = 3) -> float:
    """3 × forward FLOPs of a VGG ``cfg`` (ints: conv output channels at
    stride 1 and 'same' padding; 'M': 2×2 max-pool) with one Linear head."""
    hw, cin, forward = image_hw, in_channels, 0.0
    for item in cfg:
        if item == "M":
            hw //= 2
            continue
        forward += 2.0 * hw * hw * cin * item * kernel * kernel
        cin = item
    forward += 2.0 * cin * hw * hw * num_classes
    return 3.0 * forward


def lm_train_flops_per_token(n_params: int, n_embedding: int, n_layers: int,
                             d_model: int, seq_len: int) -> float:
    """``6·(P − embedding table)`` for the matmuls plus causal attention:
    two ``T×d`` matmuls a layer, ×2 FLOPs, ×3 for training, at half the
    square because a causal kernel skips what lies above the diagonal:
    ``6·L·d·T``."""
    return 6.0 * (n_params - n_embedding) + 6.0 * n_layers * d_model * seq_len


def mfu_pct(flops_per_item: float, items_per_s_chip: float,
            device_kind: str, peaks: dict | None = None) -> float:
    peak = peak_for(device_kind, peaks)["bf16_tflops"] * 1e12
    return 100.0 * flops_per_item * items_per_s_chip / peak
