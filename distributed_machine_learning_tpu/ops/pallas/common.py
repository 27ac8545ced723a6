"""Shared Pallas plumbing: one interpret-mode knob for every kernel.

Every kernel module (``flash_attention``, ``quant_matmul``,
``ring_codec``, ``fused_adamw``, ``decode_attention``, ...) makes the
same decision the same way:

- ``interpret()`` — whether ``pl.pallas_call`` should run the kernel
  under the Pallas interpreter instead of Mosaic.  Mosaic only compiles
  for TPU, so any non-TPU backend (the 8-virtual-device CPU CI mesh,
  the multi-chip dryrun's virtual CPU devices) interprets; a TPU
  backend compiles.  Historically this predicate lived in
  ``flash_attention._interpret`` and was imported sideways by
  ``quant_matmul`` — it is hoisted here so interpret-mode selection is
  ONE knob for all kernels (the old import path is kept as an alias).
  The CLI run banners print the same decision (``pallas=compiled`` /
  ``pallas=interpreted``, ``cli/common.py::device_banner``).

``pick_block`` is the shared tiling helper (grown in ``quant_matmul``):
the largest multiple-of-``quantum`` divisor of a dimension under a VMEM
target.
"""

from __future__ import annotations

import jax
from jax.experimental.pallas import tpu as pltpu


def interpret() -> bool:
    """True when Pallas kernels must run interpreted (non-TPU backend).

    An explicitly configured default device wins: a process whose
    highest-priority backend is a TPU can still route computations to
    virtual CPU devices (the multi-chip dryrun does exactly that), and
    Mosaic can't compile for CPU — interpret there.  The config also
    accepts plain strings ("cpu", "tpu:0"), so parse those too.
    """
    dev = jax.config.jax_default_device
    if dev is not None:
        platform = (
            dev.platform
            if hasattr(dev, "platform")
            else str(dev).split(":")[0]
        )
        return platform != "tpu"
    return jax.default_backend() != "tpu"


# Alias under the historical private name (flash_attention grew the
# predicate; quant_matmul imported it from there) so both spellings
# resolve to the one definition above.
_interpret = interpret


#: VMEM lane width — the last dim of every kernel tile.
LANES = 128

#: ``lax.dot_general`` dimension numbers of two matrices.
NN = (((1,), (0,)), ((), ()))  # a · b
TN = (((0,), (0,)), ((), ()))  # aᵀ · b
NT = (((1,), (1,)), ((), ()))  # a · bᵀ


def dot(a, b, dims):
    """A product that accumulates in float32 whatever its operands are."""
    import jax.numpy as jnp

    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def padded_lane_rows(length: int, row_quantum: int) -> int:
    """Rows of a ``[rows, LANES]`` view of a flat ``[length]`` vector,
    padded up to ``row_quantum`` (the dtype's sublane tile quantum:
    8 for f32, 16 for bf16, 32 for int8)."""
    lane_rows = -(-max(length, 1) // LANES)
    return -(-lane_rows // row_quantum) * row_quantum


def lane_tiles(a, rows: int, dtype=None):
    """Flat ``[L]`` → zero-padded ``[rows, LANES]`` (optionally cast
    first).  Zero pads are the exact-by-construction convention every
    elementwise kernel here relies on: padded lanes quantize/decode/
    update to exactly zero and are sliced off by the caller."""
    import jax.numpy as jnp

    if dtype is not None:
        a = a.astype(dtype)
    return jnp.pad(a, (0, rows * LANES - a.shape[0])).reshape(rows, LANES)


def tile_compiler_params(semantics) -> dict:
    """``{"compiler_params": pltpu.CompilerParams(...)}`` when Mosaic
    will compile the kernel, ``{}`` under the interpreter (which
    rejects TPU compiler params) — the gate every kernel call spells
    around its ``dimension_semantics``."""
    if not interpret():
        return {"compiler_params": pltpu.CompilerParams(
            dimension_semantics=tuple(semantics))}
    return {}


def pick_block(n: int, target: int, quantum: int) -> int | None:
    """Largest multiple-of-``quantum`` divisor of n that is <= target,
    or n itself when n < quantum (Mosaic accepts a block equal to the
    full array dim)."""
    if n <= quantum:
        return n
    best = None
    b = quantum
    while b <= min(n, target):
        if n % b == 0:
            best = b
        b += quantum
    return best if best is not None else (n if n <= target else None)
