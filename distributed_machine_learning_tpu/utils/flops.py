"""Model-FLOPs estimates so every benchmark number carries an MFU.

The reference reports raw wall-clock only (group25.pdf §6); an MFU line
turns a throughput number into a statement about how much of the chip it
uses — the difference between "fast" and "done".  Estimates follow the
standard accounting: a training step costs ~3× the forward pass (forward
+ backward w.r.t. inputs + backward w.r.t. weights); matmul/conv FLOPs
count multiply and add separately (factor 2).
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class DevicePeak:
    """Published per-chip peaks: dense bf16 TFLOP/s and HBM GB/s."""

    bf16_tflops: float
    hbm_gb_per_s: float


# Keyed by the ``device_kind`` string JAX reports.  A kind that is not
# listed has no peak: MFU against the wrong peak is worse than no MFU.
DEVICE_PEAKS = {
    # TPU v5e — Google Cloud documentation, "TPU v5e" (system
    # architecture): 197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s per chip.
    "TPU v5 lite": DevicePeak(bf16_tflops=197.0, hbm_gb_per_s=819.0),
}


def vgg_forward_flops_per_image(
    cfg: list, image_hw: int = 32, in_channels: int = 3,
    num_classes: int = 10, kernel: int = 3,
) -> float:
    """Forward FLOPs/image for a reference-style VGG cfg list
    (ints = conv out-channels, 'M' = 2×2 max-pool halving the spatial dim
    — models/vgg.py:_cfg ≡ part1/model.py:3-8)."""
    hw = image_hw
    cin = in_channels
    total = 0.0
    for item in cfg:
        if item == "M":
            hw //= 2
            continue
        total += 2.0 * hw * hw * cin * item * kernel * kernel
        cin = item
    total += 2.0 * cin * num_classes  # the Linear(512, 10) head
    return total


def vgg_train_flops_per_image(cfg: list, **kw) -> float:
    return 3.0 * vgg_forward_flops_per_image(cfg, **kw)


def transformer_train_flops_per_token(
    n_params: int, n_layers: int, d_model: int, seq_len: int,
    causal: bool = True,
) -> float:
    """~6·P per token for the matmuls (fwd 2P + bwd 4P) plus the
    attention score/value matmuls: 12·L·d·T per token fwd+bwd
    (2 matmuls × 2 FLOPs × T·d each, × 3 for training).

    ``causal=True`` (the default, matching every model in this repo)
    counts the attention term at T/2 — the work a causal kernel actually
    performs, since the flash kernels skip above-diagonal blocks
    entirely (compute AND DMA).  Set ``causal=False`` for the PaLM-style
    full-score-matrix convention; at long context the two differ by up
    to 2× on the attention term, so MFU tables must say which they use
    (``benchmark/flops.py`` counts the causal half too)."""
    attn = 12.0 * n_layers * d_model * seq_len
    if causal:
        attn /= 2.0
    return 6.0 * n_params + attn


def mfu(achieved_flops_per_sec: float, device_kind: str) -> float | None:
    """Model-FLOPs utilization against ``device_kind``'s bf16 peak, or
    None for a kind :data:`DEVICE_PEAKS` does not list."""
    peak = DEVICE_PEAKS.get(device_kind)
    if peak is None:
        return None
    return achieved_flops_per_sec / (peak.bf16_tflops * 1e12)
