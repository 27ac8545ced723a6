"""Weight-only int8 matmul as a Pallas TPU kernel — the decode bandwidth lever.

Autoregressive decode is bound by HBM reads of the weights, so
halving the weight bytes is a direct tokens/s multiplier.  The catch is
that XLA does NOT fuse an ``int8 → bf16`` convert into a dot operand at
these sizes: ``x @ (q.astype(bf16) * scale)`` inside a decode scan
materializes the dequantized matrix in HBM, *tripling* traffic instead
of halving it (no cell measures decode: ROADMAP X2).  Hence
this kernel: the int8 tile is DMA'd into VMEM (half the bytes of bf16),
converted to bf16 in-register, fed to the MXU with f32 accumulation,
and scaled per output channel on the way out.  HBM never sees a
dequantized byte.

Quantization scheme (``quantize_int8``): symmetric per-output-channel —
``q = round(w / s)`` with ``s = max|w_col| / 127``, the standard
weight-only recipe (per-channel scales cost [K] floats and remove the
worst-case column error of a per-tensor scale).  Matmul error is then
~0.4% RMS relative — well under bf16 activation noise for serving.

Grid: ``(rows // bR, K // bK)`` with the full contraction depth D in
one block — at serving widths (D ≤ 8k) an int8 [D, bK=512] tile is
≤4 MB of VMEM, and one-shot dots avoid a scratch accumulator entirely.
Both grid axes are parallel (no cross-step state).  int8 VMEM tiles
need (32, 128) alignment: D and bK are validated multiples of 32/128.

Reference note: the reference has no inference or quantization surface
at all (its eval is ``test_model``, part1/main.py:62-77); this is
beyond-parity serving capability, same family as inference/generate.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from distributed_machine_learning_tpu.ops.pallas.common import (
    _interpret,
    tile_compiler_params,
)


def quantize_int8(w: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Symmetric per-output-channel int8 quantization of a [D, K] matrix.

    Returns ``(q int8 [D, K], scale f32 [K])`` with
    ``w ≈ q * scale[None, :]``.  An all-zero column gets scale 1 (its
    quantized values are all zero anyway — avoids 0/0).
    """
    w = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(w), axis=0)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(w / scale[None, :]), -127, 127).astype(jnp.int8)
    return q, scale


def _kernel(x_ref, q_ref, s_ref, o_ref):
    acc = jax.lax.dot_general(
        x_ref[...],
        q_ref[...].astype(jnp.bfloat16),
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    o_ref[...] = (acc * s_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


# Tiling helper hoisted to the shared kernel plumbing; the historical
# private name keeps resolving for existing callers.
from distributed_machine_learning_tpu.ops.pallas.common import (  # noqa: E402
    pick_block as _pick_block,
)


@functools.partial(jax.jit, static_argnames=("block_rows", "block_k"))
def int8_matmul(
    x: jax.Array,
    q: jax.Array,
    scale: jax.Array,
    block_rows: int | None = None,
    block_k: int | None = None,
) -> jax.Array:
    """``x @ (q * scale)`` reading the weights as int8.  [R, D] × [D, K]
    → [R, K] in ``x.dtype``; compute is bf16×bf16→f32 on the MXU.
    """
    R, D = x.shape
    D2, K = q.shape
    if D != D2 or scale.shape != (K,):
        raise ValueError(
            f"shape mismatch: x [{R},{D}], q [{D2},{K}], scale {scale.shape}"
        )
    # VMEM budget (per-buffer caps, ×2 for double buffering): the x tile
    # [bR, D] bf16 stays ≤2 MB and the q tile [D, bK] int8 ≤4 MB, so the
    # working set ≈ (2+4+ε)·2 ≈ 13 MB fits the 16 MB VMEM at any D —
    # without the caps a d_ff=8k prefill x-tile alone is 4 MB and Mosaic
    # runs out of scoped VMEM.
    r_cap = max(8, min(256, (1 << 21) // (2 * D)))
    k_cap = max(128, min(512, (1 << 22) // D))
    # Rows tile freely once R is a multiple of 8 (divisor 8 <= r_cap
    # always exists), so an awkward row count — an odd-length prefill —
    # is zero-padded here and sliced back, instead of falling through to
    # one whole-[R, D] tile that blows the VMEM budget above.
    pad_rows = 0
    if R > 8 and R % 8:
        pad_rows = 8 - R % 8
        x = jnp.pad(x, ((0, pad_rows), (0, 0)))
        R += pad_rows
    bR = block_rows or _pick_block(R, r_cap, 8) or R
    bK = block_k or _pick_block(K, k_cap, 128)
    pad_k = 0
    if bK is None and block_k is None:
        # K has no 128-multiple divisor under the cap (e.g. the fused
        # qkv of a d_model=320 model gives K=960): zero-pad the weight
        # columns and scales up to the next 128 multiple — padded
        # columns multiply to exact zeros and are sliced off below —
        # mirroring the row-padding path instead of refusing the width.
        # Inside a scanned decode program the padded weight is loop-
        # invariant and XLA hoists it (verified on the compiled HLO:
        # the s8 pad lives outside the while body, the padded array
        # rides the loop carry) — the copy costs once per program, not
        # per token.
        pad_k = (-K) % 128
        q = jnp.pad(q, ((0, 0), (0, pad_k)))
        scale = jnp.pad(scale, ((0, pad_k),))
        K += pad_k
        bK = _pick_block(K, k_cap, 128)
    if bK is None or K % bK or R % bR:
        raise ValueError(
            f"K={K} must tile by a multiple of 128 and R={R} by the row "
            f"block (got bR={bR}, bK={bK}); pad the operands"
        )
    if D % 32 and D > 32:
        raise ValueError(f"contraction depth D={D} must be a multiple of 32")
    out_dtype = x.dtype
    x = x.astype(jnp.bfloat16)
    grid = (R // bR, K // bK)
    kwargs = tile_compiler_params(("parallel", "parallel"))
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bR, D), lambda r, k: (r, 0)),
            pl.BlockSpec((D, bK), lambda r, k: (0, k)),
            pl.BlockSpec((1, bK), lambda r, k: (0, k)),
        ],
        out_specs=pl.BlockSpec((bR, bK), lambda r, k: (r, k)),
        out_shape=jax.ShapeDtypeStruct((R, K), out_dtype),
        interpret=_interpret(),
        **kwargs,
    )(x, q, scale.reshape(1, K))
    return out[: R - pad_rows, : K - pad_k] if (pad_rows or pad_k) else out
