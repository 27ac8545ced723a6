"""Flash-decode: single-token cached attention as a Pallas TPU kernel,
with optional int8-quantized KV cache.

The decode step's attention is a matvec against the whole KV cache —
pure HBM bandwidth — and the XLA einsum path (`_cached_attention`)
reads every one of the S *allocated* slots every step, zeros beyond the
write frontier included; an int8 cache would additionally dequantize
through HBM the way int8 weights do (see quant_matmul.py).  This kernel
fixes both:

- **Frontier clamping**: the K/V block index map clamps to the last
  block containing the current position (a scalar-prefetch value), so
  Pallas elides the DMA for every block past the frontier — reads are
  O(position), not O(allocated cache).  Early in a long-max-tokens
  generation that is nearly the whole cache.
- **In-register int8**: with ``kv_cache_dtype="int8"`` the cache stores
  int8 rows + one f32 scale per (kv head, slot); blocks dequantize in
  VMEM registers after the DMA — HBM traffic halves vs bf16 (quarters
  vs f32), which is the decode speed *and* the 2× longer-context
  memory headroom.

Layout is load-bearing: the cache is **head-major** [B, Hkv, S, D]
(written that way by ``models/transformer.py``), so a K/V block's last
two dims are a full (block_s, D) tile.  The first cut of this kernel
used the activation-order [B, S, Hkv, D] cache, whose (Hkv=4, D) tile
tail pads every slot's 4 sublanes to 8 — measured ~60 GB/s effective
DMA (8× off), with a 4× recovery just from raising Hkv to 16.  Same
grid, same math, head-major tiles: full bandwidth.

Grid ``(B, S/block_s)`` with the S axis innermost (sequential — it
carries the online-softmax scratch); a static Python loop over the ≤16
KV heads runs each per-group [rep, block_s] score tile through the same
``_online_update`` recurrence as the training kernels — one source of
truth for the softmax arithmetic (base-2, f32 state).  The per-head
matmuls are narrow (rep ≤ 16 rows), which costs little here: the
kernel is DMA-bound by construction.  Masking needs only the frontier
block (slots are written in order, so every block below it is fully
valid).  No backward pass: decode is inference.


NOTE (round 4): the kernel's int8-dequant mode is SUPERSEDED in
production by the scale-folding einsum
(models/transformer.py::_cached_attention_quant) — XLA fuses the
s8 convert into the attention dots, so the model dispatch never
routes int8 caches here anymore (a round-4 choice, not calibrated
on v5e: ROADMAP D5).  The mode stays implemented and
tested as the Pallas reference for in-register dequant; the
kernel's production role is long bf16/f32 caches (>= 4k), where
its frontier-clamped O(pos) DMA wins.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_machine_learning_tpu.ops.pallas.flash_attention import (
    _LANES,
    LOG2E,
    NEG_INF,
    _interpret,
    _online_update,
)


def pick_block_s(S: int, target: int = 512) -> int | None:
    """Largest divisor of S that is <= target and a multiple of 128 (or
    S itself when S <= 128): block_s is the lane dim of the f32 scale
    blocks and the sublane dim of the K/V tiles, so 128 keeps every
    block at native tiling.  ``generate.py`` rounds its cache
    allocation to a 512 multiple so serving always tiles."""
    if S <= 128:
        return S
    best = None
    for b in range(128, min(S, target) + 1, 128):
        if S % b == 0:
            best = b
    return best


def decode_flash_qualifies(S: int, min_block: int = 128) -> bool:
    """Dispatch rule for the decode kernel vs the einsum fallback: the
    cache length must tile into full S blocks (tiny test caches and
    awkward lengths stay on the einsum)."""
    b = pick_block_s(S)
    return b is not None and (b >= min_block or b == S)


def _decode_kernel(
    pos_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref,
    *, block_s: int, n_rep: int, scale: float, quant: bool,
):
    si = pl.program_id(1)
    pos = pos_ref[0]
    frontier = pos // block_s

    @pl.when(si == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def _update(masked: bool):
        n_kv = k_ref.shape[1]
        D = k_ref.shape[3]
        H = n_kv * n_rep
        width = n_kv * block_s
        # ONE dot over the flattened [Hkv·bS, D] block computes every
        # (query head, kv head) score pair; off-group pairs — cross
        # terms GQA never attends — are pushed to NEG_INF, so their
        # probabilities are exactly 0 and the single p·V dot below sums
        # only each row's own group.  This replaces a per-head loop of
        # [rep, D] matmuls (rep ≤ 16 rows: all MXU issue latency, ~2 µs
        # of overhead per grid step measured) with two full-width MXU
        # streams; the Hkv× extra MACs are free under the DMA.
        if quant:
            # Dequantize in 3D first (a lane-dim broadcast of the
            # [Hkv, bS] scales — Mosaic cannot shape-cast the scales
            # themselves into the flattened [width] vector), THEN merge
            # the leading dims, which is the same layout-contiguous
            # reshape the bf16 path uses.  The multiply stays in f32
            # (int8 values are exact in f32; so are the scales), so the
            # kernel adds NO rounding beyond the int8 storage itself and
            # matches the f32 einsum fallback's arithmetic — the bf16
            # dequant it replaces cost up to ~0.4% extra relative error.
            # The f32 matmuls this implies are free here: the kernel is
            # DMA-bound by construction (module docstring).
            k3 = k_ref[0].astype(jnp.float32) * ks_ref[0][:, :, None]
            v3 = v_ref[0].astype(jnp.float32) * vs_ref[0][:, :, None]
            k_all = k3.reshape(width, D)
            v_all = v3.reshape(width, D)
        else:
            k_all = k_ref[0].reshape(width, D)  # layout-contiguous
            v_all = v_ref[0].reshape(width, D)
        q_all = q_ref[0, 0]  # [H, D]
        s = jax.lax.dot_general(
            q_all.astype(k_all.dtype), k_all, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * (scale * LOG2E)
        col_group = (
            jax.lax.broadcasted_iota(jnp.int32, (H, width), 1) // block_s
        )
        row_group = (
            jax.lax.broadcasted_iota(jnp.int32, (H, width), 0) // n_rep
        )
        valid = col_group == row_group
        if masked:
            slot = si * block_s + (
                jax.lax.broadcasted_iota(jnp.int32, (H, width), 1) % block_s
            )
            valid = valid & (slot <= pos)
        s = jnp.where(valid, s, NEG_INF)
        # causal=True: _online_update zeroes the NEG_INF entries' p.
        m_new, l_new, acc_new = _online_update(
            s, m_ref[:, 0], l_ref[:, 0], acc_ref[:, :], v_all, causal=True
        )
        acc_ref[:, :] = acc_new
        m_ref[:] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    @pl.when(si < frontier)
    def _interior():
        _update(False)

    @pl.when(si == frontier)
    def _boundary():
        _update(True)

    @pl.when(si == pl.num_programs(1) - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, 0], 1e-30)
        o_ref[0, 0] = (acc_ref[:, :] / l[:, None]).astype(o_ref.dtype)


def cached_flash_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    pos: jax.Array,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
) -> jax.Array:
    """One decode step of attention against the head-major cache.

    ``q``: [B, 1, H, D] at absolute position ``pos`` (scalar int32);
    ``k_cache``/``v_cache``: [B, Hkv, S, D] with slot j holding position
    j, zeros beyond the frontier.  With int8 caches, ``k_scale``/
    ``v_scale`` are the [B, Hkv, S] f32 per-(head, slot) scales.
    Returns [B, 1, H, D] in ``q.dtype`` — same contract (fp32 softmax,
    GQA-native narrow cache) as ``_cached_attention``.
    """
    B, Lq, H, D = q.shape
    if Lq != 1:
        raise ValueError(f"decode kernel is single-token (got Lq={Lq})")
    Hkv, S = k_cache.shape[1], k_cache.shape[2]
    n_rep = H // Hkv
    quant = k_cache.dtype == jnp.int8
    if quant and (k_scale is None or v_scale is None):
        raise ValueError("int8 caches need k_scale/v_scale")
    # int8 favors big streamed blocks: the in-register dequant is VPU
    # work proportional to bytes, so fewer grid steps amortize the
    # per-step fixed cost the dequant adds (measured at 32k: bS 2048 →
    # 291 µs vs 384 µs at 512).  bf16 measured best at 512.
    block_s = pick_block_s(S, target=2048 if quant else 512)
    if block_s is None:
        raise ValueError(
            f"cache length {S} does not tile; check decode_flash_qualifies"
        )
    if not quant:
        # Dummy scale operands keep ONE kernel signature; block index 0
        # never moves, so only 128 lanes per head are ever DMA'd.
        k_scale = jnp.ones((B, Hkv, 128), jnp.float32)
        v_scale = k_scale
    pos_arr = jnp.asarray(pos, jnp.int32).reshape((1,))
    n_blocks = S // block_s

    kv_spec = pl.BlockSpec(
        (1, Hkv, block_s, D),
        lambda b, s, p: (b, 0, jnp.minimum(s, p[0] // block_s), 0),
    )
    scale_spec = pl.BlockSpec(
        (1, Hkv, block_s if quant else 128),
        (lambda b, s, p: (b, 0, jnp.minimum(s, p[0] // block_s)))
        if quant
        else (lambda b, s, p: (b, 0, 0)),
    )
    q_spec = pl.BlockSpec((1, 1, H, D), lambda b, s, p: (b, 0, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, n_blocks),
        in_specs=[q_spec, kv_spec, kv_spec, scale_spec, scale_spec],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((H, _LANES), jnp.float32),  # running max (log2)
            pltpu.VMEM((H, _LANES), jnp.float32),  # running normalizer
            pltpu.VMEM((H, D), jnp.float32),  # output accumulator
        ],
    )
    kernel = functools.partial(
        _decode_kernel,
        block_s=block_s,
        n_rep=n_rep,
        scale=1.0 / (D**0.5),
        quant=quant,
    )
    compiler_params = (
        {}
        if _interpret()
        else {
            "compiler_params": pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")
            )
        }
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, 1, H, D), q.dtype),
        interpret=_interpret(),
        **compiler_params,
    )(pos_arr, q, k_cache, v_cache, k_scale, v_scale)


# ---------------------------------------------------------------------------
# Paged (block-table) decode attention — ISSUE 19
# ---------------------------------------------------------------------------
# The continuous-batching engine (inference/continuous.py) keeps KV
# residency in a SHARED physical pool of fixed-size blocks
# ([num_blocks, Hkv, block_s, D], ops on it managed by
# inference/kv_blocks.py) instead of a per-sequence [B, S, D] slab;
# each in-flight lane w owns a block table mapping its logical block j
# to a physical pool block.  The ragged entry point below is the
# decode dispatch for that layout: one grid where every lane reads its
# OWN frontier-clamped walk of the pool through the scalar-prefetched
# table — the vLLM PagedAttention access pattern on the flash-decode
# kernel above.  Per-lane reads stay O(position); lanes at different
# lengths share one dispatch, which is what makes iteration-level
# batching a single program instead of a per-length group loop.


def _paged_kernel(
    tbl_ref, pos_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
    *, block_s: int, n_rep: int, scale: float,
):
    si = pl.program_id(1)
    pos = pos_ref[pl.program_id(0)]
    frontier = pos // block_s

    @pl.when(si == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def _update(masked: bool):
        n_kv = k_ref.shape[0]
        D = k_ref.shape[2]
        H = n_kv * n_rep
        width = n_kv * block_s
        k_all = k_ref[:].reshape(width, D)
        v_all = v_ref[:].reshape(width, D)
        q_all = q_ref[0, 0]  # [H, D]
        s = jax.lax.dot_general(
            q_all.astype(k_all.dtype), k_all, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * (scale * LOG2E)
        col_group = (
            jax.lax.broadcasted_iota(jnp.int32, (H, width), 1) // block_s
        )
        row_group = (
            jax.lax.broadcasted_iota(jnp.int32, (H, width), 0) // n_rep
        )
        valid = col_group == row_group
        if masked:
            slot = si * block_s + (
                jax.lax.broadcasted_iota(jnp.int32, (H, width), 1) % block_s
            )
            valid = valid & (slot <= pos)
        s = jnp.where(valid, s, NEG_INF)
        m_new, l_new, acc_new = _online_update(
            s, m_ref[:, 0], l_ref[:, 0], acc_ref[:, :], v_all, causal=True
        )
        acc_ref[:, :] = acc_new
        m_ref[:] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    @pl.when(si < frontier)
    def _interior():
        _update(False)

    @pl.when(si == frontier)
    def _boundary():
        _update(True)

    @pl.when(si == pl.num_programs(1) - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, 0], 1e-30)
        o_ref[0, 0] = (acc_ref[:, :] / l[:, None]).astype(o_ref.dtype)


def paged_attention_reference(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    block_tables: jax.Array,
    positions: jax.Array,
) -> jax.Array:
    """XLA reference for :func:`paged_flash_attention` — the gather
    formulation (pool rows indexed by the block table, then the same
    masked fp32-softmax attention as ``_cached_attention``).  The
    continuous-batching engine runs this formulation on every backend;
    the kernel's parity tests pin the two together.

    ``q``: [W, 1, H, D] — one in-flight decode token per lane;
    ``k_pool``/``v_pool``: [num_blocks, Hkv, block_s, D];
    ``block_tables``: [W, max_blocks] int32 physical ids (entries past
    a lane's frontier must be in-range but are never attended);
    ``positions``: [W] int32 — lane w's query slot; it attends cache
    slots 0..positions[w] inclusive.  Returns [W, 1, H, D].
    """
    W, _, H, D = q.shape
    Hkv, block_s = k_pool.shape[1], k_pool.shape[2]
    n_rep = H // Hkv
    mb = block_tables.shape[1]
    S = mb * block_s

    def lane(kv):  # [W, MB, Hkv, bs, D] -> [W, Hkv, MB*bs, D]
        return kv.transpose(0, 2, 1, 3, 4).reshape(W, Hkv, S, D)

    k = lane(k_pool[block_tables])
    v = lane(v_pool[block_tables])
    qg = q.reshape(W, Hkv, n_rep, D)
    s = jnp.einsum(
        "whrd,whsd->whrs", qg.astype(jnp.float32), k.astype(jnp.float32)
    ) / (D ** 0.5)
    slot = jnp.arange(S, dtype=jnp.int32)
    mask = slot[None, :] <= positions[:, None].astype(jnp.int32)
    s = jnp.where(mask[:, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("whrs,whsd->whrd", p, v.astype(jnp.float32))
    return o.reshape(W, 1, H, D).astype(q.dtype)


def paged_flash_qualifies(block_s: int) -> bool:
    """TPU dispatch rule for the paged kernel: pool blocks are the
    kernel's S tiles, so they must be 128-lane multiples on real
    hardware; interpret mode (CPU tests) takes any size."""
    return block_s % 128 == 0 or _interpret()


def paged_flash_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    block_tables: jax.Array,
    positions: jax.Array,
) -> jax.Array:
    """Ragged block-table decode attention as one Pallas dispatch.

    Same contract as :func:`paged_attention_reference`.  Grid
    ``(W, max_blocks)`` with the block axis innermost (it carries the
    online-softmax scratch); BOTH the block table and the per-lane
    positions ride the scalar-prefetch channel, so the K/V index map
    resolves ``table[w, min(j, frontier_w)]`` before the DMA — each
    lane streams only its own O(position) bytes out of the shared
    pool, regardless of how long its neighbors are.  bf16/f32 pools
    only (the int8-pool variant would mirror the quant mode above)."""
    W, Lq, H, D = q.shape
    if Lq != 1:
        raise ValueError(f"paged kernel is single-token (got Lq={Lq})")
    Hkv, block_s = k_pool.shape[1], k_pool.shape[2]
    n_rep = H // Hkv
    mb = block_tables.shape[1]
    if not paged_flash_qualifies(block_s):
        raise ValueError(
            f"pool block_s={block_s} is not a 128 multiple; dispatch "
            "paged_attention_reference instead"
        )
    tbl = jnp.asarray(block_tables, jnp.int32)
    pos = jnp.asarray(positions, jnp.int32).reshape((W,))

    kv_spec = pl.BlockSpec(
        (None, Hkv, block_s, D),
        lambda w, s, tbl, pos: (
            tbl[w, jnp.minimum(s, pos[w] // block_s)], 0, 0, 0
        ),
    )
    q_spec = pl.BlockSpec((1, 1, H, D), lambda w, s, tbl, pos: (w, 0, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(W, mb),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((H, _LANES), jnp.float32),  # running max (log2)
            pltpu.VMEM((H, _LANES), jnp.float32),  # running normalizer
            pltpu.VMEM((H, D), jnp.float32),  # output accumulator
        ],
    )
    kernel = functools.partial(
        _paged_kernel,
        block_s=block_s,
        n_rep=n_rep,
        scale=1.0 / (D**0.5),
    )
    compiler_params = (
        {}
        if _interpret()
        else {
            "compiler_params": pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")
            )
        }
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((W, 1, H, D), q.dtype),
        interpret=_interpret(),
        **compiler_params,
    )(tbl, pos, q, k_pool, v_pool)
