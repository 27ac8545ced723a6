"""Fused int8 ring-hop codec as Pallas TPU kernels — the round-13 raw-speed lever.

The compressed ring (``ops/ring.py::Int8Scheme``) spells each hop's
dequantize–add–requantize as separate XLA ops: the encode materializes a
dequantized copy of the partial to compute the error-feedback residual
(``v − decode(encode(v))``), and the receive side materializes the
dequantized payload before adding it into the accumulator chunk.
Those intermediates are the codec's whole cost (no cell runs the ring
yet: ROADMAP W3).  This module is the fused spelling: three kernels covering
every local piece of the hop, each one pass over the chunk with the
intermediates held in-register, so **HBM never sees a dequantized
partial**:

- :func:`encode_int8` — quantize a chunk: amax → per-chunk scale →
  ``q = clip(round(v/scale))``, optionally emitting the EF residual
  ``v − q·scale`` as a second output in the same pass (the XLA path
  pays a full decode round-trip for it);
- :func:`decode_add_int8` — one reduce-scatter arrival:
  ``acc + q·scale`` decoded and accumulated in f32 in-register (the
  requantize of the updated partial is the next hop's
  :func:`encode_int8` — encode→accumulate→decode with no dense
  intermediate between them);
- :func:`decode_int8` — the all-gather relay's plain decode.

The arithmetic is OP-FOR-OP the ``Int8Scheme`` XLA path (same amax, same
scale select, same round/clip, same f32 multiply-add), so the fused
codec is held to BITWISE parity with the XLA build — values, wire
payload, and EF residual — in ``tests/test_pallas_fusion.py``; the
wire payload shape/dtype is identical, so the static byte accounting
(``ring_wire_bytes``) and the DML103 HLO audit hold unchanged.

Chunks are flat [L] f32 vectors of arbitrary length: each kernel views
them as [rows, 128] lanes zero-padded to the int8 tile quantum (zero
pads are exact: they never raise the amax, quantize to 0, decode to 0,
and contribute 0 residual — sliced off before anything reaches the
wire).  The encode needs the global amax before any block can quantize,
so its grid is (2, blocks): a max pass, then a quantize pass over the
same tiles, the running per-lane amax carried in VMEM scratch.  Decode kernels
are single-pass with parallel grids, and the accumulator/decode output
aliases its input buffer (``input_output_aliases``) so the in-place add
stays in place.

Dispatch: ``Int8Scheme(impl="pallas")`` — the ``--ring-codec-impl``
knob resolved by ``ops.ring.get_wire_scheme(codec_impl=...)``; flat,
hierarchical inner/outer, and all-gather relay paths all route through
the scheme's ``encode``/``encode_with_residual``/``decode_add``/
``decode`` methods, so one knob moves every hop.  On non-TPU backends
the kernels run under the Pallas interpreter (``ops/pallas/common.py``)
— tier-1 exercises the identical code path the TPU compiles.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from distributed_machine_learning_tpu.ops.pallas.common import (
    LANES as _LANES,
    _interpret,
    lane_tiles,
    padded_lane_rows,
    pick_block,
    pltpu,
    tile_compiler_params,
)

# Low-8-mantissa-bit mask: truncating the scale to 16 significand bits
# makes every decode product EXACT in f32 (|q| ≤ 127 is 7 significant
# bits; 7 + 16 ≤ 24), which is what makes the fused/XLA parity contract
# BITWISE *by construction* — see ``truncate_scale``.  A numpy scalar,
# not a jnp array: inside a kernel trace it stays a literal instead of
# a captured constant (which pallas_call rejects).
_SCALE_MASK = np.uint32(0xFFFFFF00)


def truncate_scale(scale: jax.Array) -> jax.Array:
    """Truncate a positive f32 scale to 16 significand bits (zero the
    low 8 mantissa bits).

    Why: with a full-precision scale, ``q·scale`` rounds — and whether
    a downstream ``v − q·scale`` / ``acc + q·scale`` consumes the
    rounded product or an FMA-contracted exact one is a FUSION-CONTEXT
    decision XLA makes differently for the kernel build and the XLA
    build (``optimization_barrier``, identity ``reduce_precision`` and
    runtime-select fences are all deleted or distributed away by the
    CPU pipeline — measured).  Truncating the scale makes the product
    exact (7-bit ``|q|`` × 16-bit scale ≤ 24 significand bits), so
    contraction cannot change any bit and the two builds agree
    bitwise on every backend, as an arithmetic fact.  The cost is
    ≤ 2⁻¹⁶ relative on the scale — three orders of magnitude below the
    int8 quantization noise it scales.  Integer bit ops only, so the
    truncation itself is fusion-proof.
    """
    bits = jax.lax.bitcast_convert_type(scale, jnp.uint32)
    return jax.lax.bitcast_convert_type(bits & _SCALE_MASK, jnp.float32)


def chunk_scale(amax: jax.Array) -> jax.Array:
    """The ring codec's per-chunk scale from the chunk's ``max|v|``:
    symmetric ``amax/127`` (the serving weight quantizer's recipe —
    ``quantize_int8`` in ``ops/pallas/quant_matmul.py`` — per chunk),
    1.0 for an all-zero chunk (avoids 0/0), mantissa-truncated for the
    exact-product property (:func:`truncate_scale`)."""
    return truncate_scale(
        jnp.where(amax > 0, amax / jnp.float32(127.0), jnp.float32(1.0))
    )


def quantize_chunk_int8(v: jax.Array) -> tuple[jax.Array, jax.Array]:
    """XLA reference implementation of the ring-chunk quantizer:
    ``(q int8 [L], scale f32 [1])`` with ``v ≈ q·scale``.  ONE
    definition of the recipe shared with the fused kernels below (same
    amax, same truncated scale, same round/clip), so the two
    implementations cannot drift — the bitwise parity gate in
    ``tests/test_pallas_fusion.py`` holds them together."""
    v = v.astype(jnp.float32)
    scale = chunk_scale(jnp.max(jnp.abs(v)))
    q = jnp.clip(jnp.round(v / scale), -127, 127).astype(jnp.int8)
    return q, scale.reshape(1)


# int8 VMEM tiles need (32, 128) alignment; padding every chunk to the
# full 32×128 quantum keeps one layout for all three kernels (f32's
# (8, 128) and bf16's (16, 128) divide it).
_ROW_QUANTUM = 32
# Stationary-block target: 512 rows × 128 lanes = 64K elems — 256 KB
# f32 in + 64 KB int8 + 256 KB residual per block stays well under the
# ~2 MB/buffer double-buffered VMEM budget at any chunk size.
_BLOCK_ROWS = 512


def _padded_rows(length: int) -> int:
    return padded_lane_rows(length, _ROW_QUANTUM)


def _as_tiles(v: jax.Array, rows: int) -> jax.Array:
    return lane_tiles(v, rows)


def _block_rows(rows: int) -> int:
    # rows is a multiple of _ROW_QUANTUM, so a quantum-aligned divisor
    # always exists and pick_block cannot return None here.
    return pick_block(rows, _BLOCK_ROWS, _ROW_QUANTUM) or rows


# ---------------------------------------------------------------------------
# Encode: amax pass + quantize pass over the same tiles, one pallas_call.
# ---------------------------------------------------------------------------


def _encode_kernel(v_ref, q_ref, s_ref, *out_refs, with_residual):
    """Grid (2, blocks): phase 0 folds each tile's per-lane |max| into
    the VMEM running amax row; phase 1 quantizes every tile against the
    final scale (and, with_residual, emits ``v − q·scale`` from the
    registers — the decode the XLA path materializes to HBM for the EF
    residual).

    The scale stays a ``[1, 128]`` lane vector until its one scalar
    store: Mosaic bitcasts vectors only (``truncate_scale``), and the
    vector divide broadcasts over sublanes for free."""
    if with_residual:
        err_ref, amax_ref = out_refs
    else:
        (amax_ref,) = out_refs
    phase = pl.program_id(0)
    blk = pl.program_id(1)

    @pl.when((phase == 0) & (blk == 0))
    def _init():
        amax_ref[...] = jnp.zeros_like(amax_ref)

    @pl.when(phase == 0)
    def _max_pass():
        amax_ref[...] = jnp.maximum(
            amax_ref[...],
            jnp.max(jnp.abs(v_ref[...]), axis=0, keepdims=True),
        )

    @pl.when(phase == 1)
    def _quantize_pass():
        scale = chunk_scale(
            jnp.full((1, _LANES), jnp.max(amax_ref[...]), jnp.float32)
        )
        v = v_ref[...]
        q = jnp.clip(jnp.round(v / scale), -127, 127).astype(jnp.int8)
        q_ref[...] = q
        s_ref[0, 0] = jnp.max(scale)  # every lane holds the same value
        if with_residual:
            # q·scale is EXACT (truncate_scale), so this subtraction is
            # FMA-contraction-immune and lands bit-identically to the
            # XLA build's ``v − decode(encode(v))``.
            err_ref[...] = v - q.astype(jnp.float32) * scale


def _encode_call(v: jax.Array, with_residual: bool):
    length = v.shape[0]
    rows = _padded_rows(length)
    tiles = _as_tiles(v.astype(jnp.float32), rows)
    br = _block_rows(rows)
    blocks = rows // br
    in_spec = pl.BlockSpec((br, _LANES), lambda p, b: (b, 0))
    # Phase 0 assigns no output tile: park its output window on block 0
    # (b·0) so nothing is written back until phase 1 has filled it.
    out_tile = pl.BlockSpec((br, _LANES), lambda p, b: (b * p, 0))
    out_shapes = [
        jax.ShapeDtypeStruct((rows, _LANES), jnp.int8),
        jax.ShapeDtypeStruct((1, 1), jnp.float32),
    ]
    # The scale is a scalar: Mosaic stores scalars to SMEM only.
    out_specs = [out_tile, pl.BlockSpec(memory_space=pltpu.SMEM)]
    if with_residual:
        out_shapes.append(jax.ShapeDtypeStruct((rows, _LANES), jnp.float32))
        out_specs.append(out_tile)
    outs = pl.pallas_call(
        functools.partial(_encode_kernel, with_residual=with_residual),
        grid=(2, blocks),
        in_specs=[in_spec],
        out_specs=tuple(out_specs),
        out_shape=tuple(out_shapes),
        scratch_shapes=[pltpu.VMEM((1, _LANES), jnp.float32)],
        interpret=_interpret(),
        # Both axes sequential: phase 1 must see phase 0's amax, and the
        # amax fold itself carries across blocks.
        **tile_compiler_params(("arbitrary", "arbitrary")),
    )(tiles)
    q = outs[0].reshape(-1)[:length]
    scale = outs[1].reshape(1)
    if not with_residual:
        return q, scale
    return q, scale, outs[2].reshape(-1)[:length]


def encode_int8(v: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Fused quantize of a flat f32 chunk → ``(q int8 [L], scale f32
    [1])`` — the exact ``Int8Scheme`` wire payload, computed in one
    kernel."""
    return _encode_call(v, with_residual=False)


def encode_int8_residual(
    v: jax.Array,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused quantize + error-feedback residual: ``(q, scale, err)``
    with ``err = v − q·scale`` emitted from the same registers that
    produced ``q`` — the dequantized copy the XLA path writes to HBM
    just to subtract it never exists here."""
    return _encode_call(v, with_residual=True)


# ---------------------------------------------------------------------------
# Decode / decode-accumulate: single pass, parallel grid, aliased output.
# ---------------------------------------------------------------------------


def _decode_add_kernel(s_ref, q_ref, acc_ref, o_ref):
    # q·scale exact (truncated scale) → the add cannot be perturbed by
    # FMA contraction; bitwise-stable across fusion contexts.
    o_ref[...] = acc_ref[...] + q_ref[...].astype(jnp.float32) * s_ref[0]


def _decode_kernel(s_ref, q_ref, o_ref):
    o_ref[...] = q_ref[...].astype(jnp.float32) * s_ref[0]


def decode_add_int8(
    q: jax.Array, scale: jax.Array, acc: jax.Array
) -> jax.Array:
    """One reduce-scatter arrival, fused: ``acc + q·scale`` with the
    dequantized payload living only in registers.  ``acc`` is aliased
    into the output, so the accumulate is genuinely in place."""
    length = acc.shape[0]
    rows = _padded_rows(length)
    q_t = _as_tiles(q, rows)
    acc_t = _as_tiles(acc.astype(jnp.float32), rows)
    br = _block_rows(rows)
    tile_spec = pl.BlockSpec((br, _LANES), lambda b: (b, 0))
    out = pl.pallas_call(
        _decode_add_kernel,
        grid=(rows // br,),
        in_specs=[
            pl.BlockSpec((1,), lambda b: (0,), memory_space=pltpu.SMEM),
            tile_spec,
            tile_spec,
        ],
        out_specs=tile_spec,
        out_shape=jax.ShapeDtypeStruct((rows, _LANES), jnp.float32),
        input_output_aliases={2: 0},
        interpret=_interpret(),
        **tile_compiler_params(("parallel",)),
    )(scale, q_t, acc_t)
    return out.reshape(-1)[:length]


def decode_int8(q: jax.Array, scale: jax.Array, length: int) -> jax.Array:
    """All-gather relay decode: dense f32 chunk from ``(q, scale)``,
    one pass."""
    rows = _padded_rows(length)
    q_t = _as_tiles(q, rows)
    br = _block_rows(rows)
    tile_spec = pl.BlockSpec((br, _LANES), lambda b: (b, 0))
    out = pl.pallas_call(
        _decode_kernel,
        grid=(rows // br,),
        in_specs=[
            pl.BlockSpec((1,), lambda b: (0,), memory_space=pltpu.SMEM),
            tile_spec,
        ],
        out_specs=tile_spec,
        out_shape=jax.ShapeDtypeStruct((rows, _LANES), jnp.float32),
        interpret=_interpret(),
        **tile_compiler_params(("parallel",)),
    )(scale, q_t)
    return out.reshape(-1)[:length]
