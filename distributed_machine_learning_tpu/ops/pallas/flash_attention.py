"""Flash attention (causal, optionally through a sliding window) as Pallas
TPU kernels — forward AND backward.

The hot op of the transformer family, written for the hardware per the
Pallas playbook (/opt/skills/guides/pallas_guide.md): the L×L score
matrix never hits HBM in either direction, and on-chip memory is
O(block) — plus, in the fused backward, one head's dq.

Forward: grid (batch·heads, Q blocks, K blocks) with the K dimension
innermost, so Pallas streams one [block_k, D] K/V tile into VMEM per
step while the online-softmax running (max, normalizer, accumulator)
triple persists in VMEM scratch across the K steps of each Q block.
Blocks entirely above the causal diagonal skip their compute via
``pl.when`` AND their DMA: the K/V index map clamps the block index to
the last in-range tile, and Pallas elides copies whose block index did
not change between grid steps — so causal masking saves both halves of
the work, not just the FLOPs.  The forward also emits the per-row
logsumexp — the one O(L) residual the backward needs.

Backward: ONE kernel, ``flash_bwd_fused``, grid (BH, K blocks, Q blocks)
with Q innermost.  Per tile it recomputes the score block from Q/K and
the saved logsumexp (``p = exp2(s − lse)``), forms ``ds = p·(dp − Δ)``
with ``dp = dO·Vᵀ`` and ``Δ = rowsum(dO ∘ O)`` precomputed outside, and
feeds all three gradients from that one ``p`` and ``ds``: ``dv += pᵀ·dO``
and ``dk += dsᵀ·Q`` into [block_k, ·] VMEM accumulators that belong to the
K block, ``dq += ds·K`` into a float32 copy of the WHOLE head's dq that
stays in VMEM while the K blocks sweep (8 MiB at L 8192 and a head of 192
or 256; v5e has 128 MiB) and leaves through an output block that moves
with the head alone.  Five matmuls and one exp pass a tile.

The standard two-kernel split (no atomics needed — each kernel owns its
accumulator) remains for a head whose dq cannot stay in VMEM
(``_bwd_fused``: a pure function of L, head widths and dtype):

- **dQ kernel**, grid (BH, Q blocks, K blocks): ``dq += ds·K`` in VMEM
  scratch over the K steps.
- **dK/dV kernel**, grid (BH, K blocks, Q blocks): the same ``s``, ``p``,
  ``dp`` and ``ds`` AGAIN with Q innermost, for ``dv`` and ``dk``.

Seven matmuls and two exp passes a tile: the backward kernels' time
follows their matmuls (see the measured context below), which is why the
fused kernel is the one the cells run.

MXU discipline: matmuls run on the INPUT dtype (bf16 in training) with
``preferred_element_type=f32`` accumulation — a bf16×bf16→f32 matmul is
a single MXU pass, where an f32×f32 matmul costs several (XLA's own
attention runs bf16 too, so anything else loses to dense by
construction).  The online-softmax state (m, l, acc) stays f32.

Blocks are picked per L from an on-chip sweep: 512×512 squares for
every kernel (see ``_fwd_blocks``) — large stationary blocks buy
arithmetic intensity, and the sweep showed the streamed block also
wants to be large (fewer grid steps, bigger MXU tiles) rather than
held at MXU width; smaller powers of two engage only when L demands.

A static ``window`` (sliding-window attention: key j visible to query i iff
``i − window < j ≤ i``) adds a second boundary, the band's lower edge, to
all of the above: tiles below the band are skipped like those above the
diagonal — compute, copy and nearly every grid step, because a windowed
kernel's inner grid axis spans the band's tiles alone — and tiles that the
edge crosses are masked with both bounds.  The three tile classes are drawn
where the window-side helpers stand (``_first_kb``); a call's kernels carry
the window in their names (``flash_fwd_w2048``).  Without a window every
kernel is the program it was.

Total backward traffic is O(L·D) per tensor plus the recomputed block
matmuls — the memory profile that lets long-context training fit, where
the XLA dense VJP would materialize the [H, L, L] probability tensor.

On non-TPU backends the kernels run in interpreter mode, so tests on
the CPU mesh exercise the identical code path the TPU compiles.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl

# Interpret-mode selection is the shared knob of ops/pallas/common.py
# (one decision for every kernel); ``_interpret`` stays importable from
# here — quant_matmul historically imported it from this module, and
# that path keeps working as an alias.
from distributed_machine_learning_tpu.ops.pallas.common import (
    _interpret,
    pltpu,
)

NEG_INF = -1e30
#: ``jax.ad_checkpoint.checkpoint_name`` tags of the forward kernel's two
#: outputs, the O(L²) part of the backward's residuals at O(L) bytes, and of
#: its three inputs as the kernels take them (normed, rotated, folded to
#: ``[B·H, L, D]``, K/V unrepeated): what a recomputed block keeps
#: (``models/transformer.py::whole_block_policy``).
FLASH_RESIDUAL_NAMES = ("flash_out", "flash_lse")
FLASH_INPUT_NAMES = ("flash_q", "flash_k", "flash_v")
_LANES = 128  # VMEM lane width: m/l scratch is (block_q, _LANES)
# The kernels run the softmax in BASE 2: scores are pre-scaled by
# log2(e) so every exp becomes a bare exp2.  m, l's log-offset, and the
# saved lse therefore live in log2 space; probabilities and outputs are
# unchanged because exp2((s·log2e) − m2) == exp(s − m).
#
# Measured context, per active 512 × 512 tile (a call's device time ÷ its
# causal tiles; PERF_LEDGER.jsonl, PR 31 ``breakdown``s, and PERF.md §5):
#
#   head qk / v   forward   dQ + dK/dV (split)   MXU passes, split → fused
#   128 / 128     2.46 µs   3.40 µs              3 + 4 = 7   → 5
#   192 / 128     2.84 µs   2.27 + 2.65 = 4.92   5 + 6 = 11  → 8
#   256 / 256     2.49 µs   2.52 + 3.18 = 5.70   6 + 8 = 14  → 10
#
# (a pass = one 512 × 512 × 128 matmul, 0.34 µs at the chip's 197 TFLOP/s;
# 192 counts as two).  The FORWARD kernel sits on a vector-unit floor of
# ≈ 2.45 µs a tile whatever its matmul load (exp, running max and sum,
# the rescale of the accumulator), 0.4 µs more at the 192-wide tile.  The
# BACKWARD kernels are bound by their matmuls with the exp hidden: the
# split's two exp passes at head 128 cost 1.70 µs each, and its time over
# the three heads is a line of ≈ 0.55 µs a kernel + ≈ 0.33 µs a pass.  So
# the backward got faster by doing fewer: fused, the same calls take 2.38 /
# 3.68 / 4.15 µs a tile where the split takes 3.71 / 5.28 / 5.99 (one chip
# run of both at the cells' shapes, host-timed; PERF.md §6, PR 32), the
# gradients bit for bit the split's.  (exp2 itself measured neutral vs
# exp under Mosaic — its exp is already pow2-based — but base-2 keeps the
# kernels at the floor of what the lowering can emit.)
LOG2E = 1.4426950408889634


def _compiler_params():
    """batch·head and the stationary block axis are parallel; the
    streamed (innermost) axis carries the scratch accumulator between
    steps and must stay sequential."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary")
    )


def _pick(L: int, target: int) -> int:
    """Largest power-of-two block <= target that divides L."""
    b = 1
    for c in (2, 4, 8, 16, 32, 64, 128, 256, 512):
        if c <= target and c <= L and L % c == 0:
            b = c
    return b


def _needs_pad(L: int) -> bool:
    """True when L cannot be tiled legally as-is: Mosaic requires the
    residuals' lane-dim block (== block_q) to be a multiple of 128 or
    the full array dim, so a length whose largest power-of-two divisor
    is <128 (and which isn't itself that divisor) must be padded."""
    bq = _pick(L, 512)
    return not (bq % 128 == 0 or bq == L)


def _padded_len(L: int) -> int:
    """Smallest multiple of 512 (the tuned block size) >= L."""
    return -(-L // 512) * 512


def flash_wins(L: int) -> bool:
    """Length policy shared by every "auto" dispatch: with 512×512
    blocks the flash kernels are taken from 512 context up, and are
    the only option past ~8-16k where dense's L² program stops
    compiling.  XLA dense attention is kept at 256 and at sub-2k
    lengths with degraded blocks: sub-1k lengths not divisible by 512
    forfeit the thin margin at 512, and 1-2k lengths whose largest
    power-of-two divisor is under 128 would pay the pad-to-512-multiple
    overhead (up to (L+511)²/L² ≈ 1.5× at 1k).  From 2048 up flash is
    taken for EVERY length — padded if needed — because the pad
    overhead shrinks quadratically while dense's L² scores grow.  The
    crossovers come from rounds 1-5's timings of older code: not
    calibrated on v5e (ROADMAP D5)."""
    if L >= 2048:
        return True
    if L >= 1024:
        return not _needs_pad(L)
    return L >= 512 and _pick(L, 512) == 512


def _fwd_blocks(L: int) -> tuple[int, int]:
    # Measured sweep on the attached chip (d_model 512, D=64, seq 4k):
    # square 512×512 beats every rectangular candidate — 283k tok/s vs
    # 237k for (512,256), 185k for (512,128) — the bigger streamed block
    # amortizes per-grid-step overhead and the MXU prefers the larger
    # contraction tiles; VMEM stays ~1 MB/core at D=64.
    return _pick(L, 512), _pick(L, 512)


def _dkv_blocks(L: int) -> tuple[int, int]:
    # Same sweep for the dK/dV kernel: (512,512) gives 301k tok/s vs
    # 284k for the old (256,512) and 235k for (256,256).  The fused
    # backward kernel has this grid and takes these blocks as they are.
    return _pick(L, 512), _pick(L, 512)


#: What the fused backward kernel may ask of VMEM: under half of a v5e
#: core's 128 MiB, so the choice never rests on the last MiB of a chip.
_FUSED_VMEM_BUDGET = 48 * 2**20
#: Room for what Mosaic keeps beside the declared buffers: the [block_q,
#: block_k] float32 score, p, dp and ds tiles and their bf16 copies (1 MiB
#: each at 512 × 512) and its own scratch.  An AOT compile for v5e passes
#: with 4 MiB and fails with 2 at head 256, L 8192 (2 and 0 at head 128).
_TILE_TEMPORARIES = 8 * 2**20


def _fused_vmem_bytes(L: int, D: int, Dv: int, dtype) -> int:
    """VMEM the fused backward kernel needs at these shapes, in bytes —
    its ``vmem_limit_bytes``.  The resident dq (float32 scratch plus the
    double-buffered output block, lane-padded), the double-buffered
    Q/K/V/dO tiles, lse/Δ rows and dk/dv output tiles, the two [block_k,
    ·] accumulators, and ``_TILE_TEMPORARIES``."""
    item = jnp.dtype(dtype).itemsize
    block_q, block_k = _dkv_blocks(L)
    d, dv = (-(-w // _LANES) * _LANES for w in (D, Dv))
    resident_dq = L * d * (4 + 2 * item)
    tiles = 2 * item * (block_q + 2 * block_k) * (d + dv)
    rows = 2 * 2 * 8 * max(block_q, _LANES) * 4
    accumulators = 4 * block_k * (d + dv)
    return resident_dq + tiles + rows + accumulators + _TILE_TEMPORARIES


def _bwd_fused(L: int, D: int, Dv: int, dtype) -> bool:
    """Which backward a call gets, from its shapes alone: the fused
    kernel (one score tile, one exp pass and five matmuls a tile, the
    head's dq resident in VMEM) where that fits ``_FUSED_VMEM_BUDGET``;
    the dQ + dK/dV split (seven matmuls, two exp passes, O(block) VMEM)
    past it — bf16 heads of 128 from L 65 536, of 192 or 256 from 32 768."""
    return _fused_vmem_bytes(L, D, Dv, dtype) <= _FUSED_VMEM_BUDGET


def _last_kb(qi, block_q: int, block_k: int):
    """Last K block index intersecting the causal triangle of Q block qi."""
    return ((qi + 1) * block_q - 1) // block_k


def _first_qi(kb, block_q: int, block_k: int):
    """First Q block index intersecting the causal triangle of K block kb."""
    return (kb * block_k) // block_q


# --- The window.  With ``window = w`` key j is visible to query i iff
# --- ``i − w < j ≤ i``: a band of width w under the diagonal.  Per
# --- (Q, K) tile, at L = 8 blocks and a band of 2½ blocks:
#
#        K block →  0 1 2 3 4 5 6 7        e  edge: the diagonal or the
#     Q block 0     e . . . . . . .           band's lower edge crosses the
#             1     i e . . . . . .           tile; masked with both bounds
#             2     e i e . . . . .        i  interior: every pair visible,
#             3     - e i e . . . .           no mask
#             4     - - e i e . . .        .  above the diagonal: skipped
#             5     - - - e i e . .        -  below the band: skipped
#             6     - - - - e i e .
#             7     - - - - - e i e
#
# --- Skipped tiles cost neither compute nor a copy, and almost no grid
# --- step: a windowed kernel's inner grid axis spans only ``_band_steps``
# --- tiles (⌈(w − 1)/block⌉ + 1; 5 at w 2048, block 512, against the 32
# --- of L 16 384) that start at the row's ``_first_kb`` (the column's
# --- ``_first_qi``), so the only empty steps are those of the first rows,
# --- whose band is cut by the sequence's start (the last columns', by its
# --- end): 10 of 160 a head there.  Measured (PERF.md §5): a w 2048 call at
# --- L 16 384 takes 0.295 (forward) / 0.278 (backward) of the windowless
# --- call's time for 0.284 of its tiles.


def _floor0(x):
    """``max(x, 0)`` of a Python int (a grid size) or a traced block index
    (an index map, a kernel)."""
    return max(x, 0) if isinstance(x, int) else jnp.maximum(x, 0)


def _first_kb(qi, block_q: int, block_k: int, window: int):
    """First K block index that a query of Q block qi sees through the
    window (its first row reaches back furthest)."""
    return _floor0(qi * block_q - (window - 1)) // block_k


def _last_qi(kb, block_q: int, block_k: int, window: int):
    """Last Q block index that sees a key of K block kb through the window
    (its last key is seen longest); may lie past the sequence's end."""
    return ((kb + 1) * block_k - 1 + window - 1) // block_q


def _band_steps(n_outer: int, first, last) -> int:
    """Inner grid steps of a windowed kernel: the most tiles of the band in
    any row (column), from the static block counts."""
    return max(last(i) - first(i) + 1 for i in range(n_outer))


def active_tiles(L: int, window: int | None = None) -> tuple[int, int]:
    """(tiles a head's forward kernel computes, tiles of the causal
    triangle) at the kernels' block sizes: 150 of 528 at L 16 384 and a
    window of 2048, all of them without one."""
    L = _padded_len(L) if _needs_pad(L) else L
    block_q, block_k = _fwd_blocks(L)
    rows = range(L // block_q)
    causal = sum(_last_kb(qi, block_q, block_k) + 1 for qi in rows)
    if window is None or window >= L:
        return causal, causal
    return causal - sum(_first_kb(qi, block_q, block_k, window)
                        for qi in rows), causal


def _tile_classes(q_start, k_start, block_q: int, block_k: int,
                  window: int | None = None):
    """(interior, edge) predicates for one (Q, K) tile of a causal
    kernel.  ``interior``: every (q_pos, k_pos) pair is visible — k_pos <=
    q_pos and, with a window, k_pos > q_pos − window — and the tile needs
    NO mask.  ``edge``: some pairs are (the tile straddles the diagonal or
    the band's lower edge) and it must mask.  Tiles above the diagonal or
    below the band match neither and are skipped entirely."""
    interior = k_start + block_k - 1 <= q_start
    active = k_start <= q_start + block_q - 1
    if window is not None:
        interior &= k_start > q_start + block_q - 1 - window
        active &= k_start + block_k - 1 > q_start - window
    return interior, active & jnp.logical_not(interior)


def _dispatch_tiles(do_update, q_start, k_start, block_q: int, block_k: int,
                    causal: bool, window: int | None = None, valid=None):
    """Shared tile dispatch for every flash/ring kernel: causal kernels
    run the mask-free variant on interior tiles (the per-tile
    iota/compare/select mask is VPU work rivaling the tile's MXU time, and
    only tiles on the diagonal or the window's edge need it), the masked
    variant on those, and skip the rest; non-causal kernels run every tile
    mask-free.  ``do_update(tile_masked)`` is the kernel-specific tile
    body; ``valid`` (windowed kernels) is false on a grid step whose block
    index lies past the sequence's end."""
    if not causal:
        do_update(False)
        return
    interior, edge = _tile_classes(q_start, k_start, block_q, block_k, window)
    if valid is not None:
        interior, edge = interior & valid, edge & valid

    @pl.when(interior)
    def _update_full():
        do_update(False)

    @pl.when(edge)
    def _update_diag():
        do_update(True)


def _block_scores(q, k, q_start, k_start, block_q, block_k, scale,
                  window: int | None = None):
    """Masked scaled scores for one (Q, K) tile — shared fwd/bwd.

    The dot runs on the input dtype (bf16 on the training path) with f32
    accumulation: one MXU pass instead of the multi-pass f32 emulation.
    """
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    q_pos = q_start + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    )
    k_pos = k_start + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    )
    visible = q_pos >= k_pos
    if window is not None:
        visible &= k_pos > q_pos - window
    return jnp.where(visible, s, NEG_INF)


def _full_scores(q, k, scale):
    """Unmasked scaled scores (ring steps where every key precedes every
    query)."""
    return jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale


# --- Shared per-tile math (single source of truth for the subtle kernel
# --- arithmetic; the flash kernels here and the ring-flash chunk kernels
# --- in ring_flash_attention.py all call these).


def _tile_scores(q, k, q_start, k_start, block_q, block_k, scale,
                 causal: bool, window: int | None = None):
    """Scores for one tile; callers on the log2-softmax path pass
    ``scale * LOG2E`` so the downstream exps become exp2."""
    if causal:
        return _block_scores(q, k, q_start, k_start, block_q, block_k, scale,
                             window)
    return _full_scores(q, k, scale)


def _rows_dot(a, b):
    """``a·b`` ([bq, bk]·[bk, d]): the operands in ``b``'s dtype, f32 out."""
    return jax.lax.dot_general(
        a.astype(b.dtype), b, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _cols_dot(a, b):
    """``aᵀ·b`` ([bq, bk]ᵀ·[bq, d]): the operands in ``b``'s dtype, f32 out."""
    return jax.lax.dot_general(
        a.astype(b.dtype), b, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _online_update(s, m, l, acc, v, causal: bool):
    """One online-softmax block update of the (m, l, acc) running triple.
    ``s`` fp32 scores [bq, bk] in LOG2 space (pre-scaled by log2e);
    m [bq] log2-space running max; l [bq]; acc [bq, D] fp32."""
    m_new = jnp.maximum(m, s.max(axis=-1))
    alpha = jnp.exp2(m - m_new)
    p = jnp.exp2(s - m_new[:, None])
    if causal:
        # Masked entries must contribute 0 even in a fully-masked row
        # (there s == m_new == NEG_INF and the exp above gives 1, not 0).
        p = jnp.where(s > 0.5 * NEG_INF, p, 0.0)
    l_new = l * alpha + p.sum(axis=-1)
    acc_new = acc * alpha[:, None] + _rows_dot(p, v)
    return m_new, l_new, acc_new


def _p_from_lse(s, lse, causal: bool):
    """``s`` and ``lse`` both in log2 space."""
    p = jnp.exp2(s - lse[:, None])
    if causal:
        p = jnp.where(s > 0.5 * NEG_INF, p, 0.0)
    return p


def _p_ds(s, v, do, lse, delta, scale, causal: bool):
    """(p, ds) of one tile from the saved lse: the arithmetic every
    backward kernel shares — ``p = exp2(s − lse)``, ``dp = dO·Vᵀ``,
    ``ds = p·(dp − Δ)·scale`` (f32)."""
    p = _p_from_lse(s, lse, causal)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    return p, p * (dp - delta[:, None]) * scale


def _dq_contrib(s, k, v, do, lse, delta, scale, causal: bool):
    """dq += ds·K for one tile (backward recompute from the saved lse)."""
    _, ds = _p_ds(s, v, do, lse, delta, scale, causal)
    return _rows_dot(ds, k)


def _dkv_contrib(s, q, v, do, lse, delta, scale, causal: bool):
    """(dk += dsᵀ·Q, dv += pᵀ·dO) for one tile."""
    p, ds = _p_ds(s, v, do, lse, delta, scale, causal)
    return _cols_dot(ds, q), _cols_dot(p, do)


def _dqkv_contrib(s, q, k, v, do, lse, delta, scale, causal: bool):
    """All three of one tile from ONE ``p`` and ``ds``: (ds·K, dsᵀ·Q,
    pᵀ·dO) — what ``_dq_contrib`` and ``_dkv_contrib`` return, for five
    matmuls and one exp pass where the pair makes seven and two."""
    p, ds = _p_ds(s, v, do, lse, delta, scale, causal)
    ds = ds.astype(q.dtype)
    return _rows_dot(ds, k), _cols_dot(ds, q), _cols_dot(p, do)


def _flash_fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref,
    *, block_q, block_k, scale, window=None,
):
    """One (Q block, K block) tile of the online-softmax recurrence.  With
    a window the inner grid axis counts the tiles of the row's band."""
    qi = pl.program_id(1)
    step = pl.program_id(2)
    kb = step if window is None else (
        _first_kb(qi, block_q, block_k, window) + step)
    q_start = qi * block_q
    k_start = kb * block_k

    @pl.when(step == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def _do_update(causal):
        q = q_ref[0]  # [block_q, D], input dtype
        k = k_ref[0]  # [block_k, D]
        v = v_ref[0]
        s = _tile_scores(q, k, q_start, k_start, block_q, block_k, scale * LOG2E,
                         causal=causal, window=window)
        m_new, l_new, acc_new = _online_update(
            s, m_ref[:, 0], l_ref[:, 0], acc_ref[:], v, causal=causal
        )
        acc_ref[:] = acc_new
        m_ref[:] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    # Blocks entirely above the causal diagonal are skipped (their DMA
    # is already elided by the clamped index map).  Blocks entirely
    # BELOW it — the vast majority at long L — run the mask-free
    # variant: the per-tile iota/compare/select mask is pure VPU work
    # that rivals the tile's MXU time, and only tiles straddling the
    # diagonal need it.
    _dispatch_tiles(_do_update, q_start, k_start, block_q, block_k,
                    causal=True, window=window)

    @pl.when(step == pl.num_programs(2) - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, 0], 1e-30)
        o_ref[0] = (acc_ref[:] / l[:, None]).astype(o_ref.dtype)
        # Exact [block_q] logsumexp row — sequence in the LANE dim, one
        # sublane (the splash-attention residual layout).  The r2 kernels
        # stored this 128-lane-replicated; since the backward kernels
        # re-fetch the lse/Δ tiles on every grid step whose block index
        # changes, that replication multiplied the O(L) residual reads
        # by 128× (~17 GB per dK/dV pass at 32k).  The sublane→lane
        # relayout here costs one in-register transpose per Q block.
        # Stored in LOG2 space, matching the kernels' base-2 softmax.
        lse_ref[0] = m_ref[:, 0] + jnp.log2(l)


def _kernel_name(base: str, D: int, Dv: int, window: int | None = None) -> str:
    """The Pallas ``name=`` (the instruction's name in a device trace):
    ``base`` where query/key and value heads are equally wide, else
    ``base_qk<D>v<Dv>``, and ``…_w<window>`` behind either where the call
    has a window, so that a trace tells the kinds of call apart."""
    name = base if D == Dv else f"{base}_qk{D}v{Dv}"
    return name if window is None else f"{name}_w{window}"


def _q_major_grid(L: int, block_q: int, block_k: int, window):
    """(grid's Q and K extents, the K block of grid step (qi, step)) of a
    kernel whose Q block is stationary: the forward and the dQ kernel.  K
    fetches above the diagonal clamp to the diagonal tile: the index
    repeats, so Pallas skips the copy (causal DMA elision).  With a window
    the inner axis spans the band alone and starts at its first tile."""
    n_q, n_k = L // block_q, L // block_k
    if window is None:
        return (n_q, n_k), lambda qi, kb: jnp.minimum(
            kb, _last_kb(qi, block_q, block_k))
    first = functools.partial(_first_kb, block_q=block_q, block_k=block_k,
                              window=window)
    last = functools.partial(_last_kb, block_q=block_q, block_k=block_k)
    return (n_q, _band_steps(n_q, first, last)), lambda qi, step: jnp.minimum(
        first(qi) + step, last(qi))


def _flash_fwd(q, k, v, block_q: int, block_k: int, kv_groups: int = 1,
               window: int | None = None):
    """q: [BHq, L, D], k: [BHq // kv_groups, L, D], v: [BHq // kv_groups,
    L, Dv] → (out [BHq, L, Dv], lse [BHq, 1, L] fp32 — exact rows, not
    lane-replicated).  ``Dv`` may differ from ``D`` (latent attention: a
    query/key head of 192 against a value head of 128); the scale is
    ``D^-½``.

    ``kv_groups > 1`` is grouped-query attention natively: the K/V tile
    index maps divide the batch·head grid index by the group factor, so
    the narrow K/V are streamed as-is — no [BHq, L, D] repeat ever hits
    HBM, cutting K/V read traffic by the group factor.  (Folding puts
    heads fastest-varying, so bh // kv_groups is exactly the query
    head's KV group — the jnp.repeat(axis=2) convention.)"""
    BH, L, D = q.shape
    Dv = v.shape[-1]
    scale = 1.0 / (D**0.5)
    extents, k_block = _q_major_grid(L, block_q, block_k, window)
    grid = (BH, *extents)
    kernel = functools.partial(
        _flash_fwd_kernel, block_q=block_q, block_k=block_k, scale=scale,
        window=window,
    )

    def q_spec(d):
        return pl.BlockSpec(
            (1, block_q, d), lambda bh, qi, kb: (bh, qi, 0),
            memory_space=pltpu.VMEM,
        )

    def k_spec(d):
        return pl.BlockSpec(
            (1, block_k, d),
            lambda bh, qi, kb: (bh // kv_groups, k_block(qi, kb), 0),
            memory_space=pltpu.VMEM,
        )

    # (None, 1, block_q) block of a [BH, 1, L] array: the singleton
    # middle dim satisfies Mosaic's block-shape rule (last two dims
    # (1, block_q) — 1 equals the array dim, block_q % 128 == 0) while
    # keeping the stored residual exact.  Same trick as splash attention.
    lse_spec = pl.BlockSpec(
        (None, 1, block_q), lambda bh, qi, kb: (bh, 0, qi),
        memory_space=pltpu.VMEM,
    )
    scratch = [
        pltpu.VMEM((block_q, _LANES), jnp.float32),  # running max
        pltpu.VMEM((block_q, _LANES), jnp.float32),  # running normalizer
        pltpu.VMEM((block_q, Dv), jnp.float32),  # output accumulator
    ]
    return pl.pallas_call(
        kernel,
        out_shape=(
            jax.ShapeDtypeStruct((BH, L, Dv), q.dtype),
            jax.ShapeDtypeStruct((BH, 1, L), jnp.float32),
        ),
        grid=grid,
        in_specs=[q_spec(D), k_spec(D), k_spec(Dv)],
        out_specs=(q_spec(Dv), lse_spec),
        scratch_shapes=scratch,
        compiler_params=_compiler_params(),
        interpret=_interpret(),
        name=_kernel_name("flash_fwd", D, Dv, window),
    )(q, k, v)


def _flash_bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_acc,
    *, block_q, block_k, scale, window=None,
):
    qi = pl.program_id(1)
    step = pl.program_id(2)
    kb = step if window is None else (
        _first_kb(qi, block_q, block_k, window) + step)
    q_start = qi * block_q
    k_start = kb * block_k

    @pl.when(step == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def _do_update(causal):
        k = k_ref[0]
        v = v_ref[0]
        s = _tile_scores(q_ref[0], k, q_start, k_start, block_q, block_k,
                         scale * LOG2E, causal=causal, window=window)
        dq_acc[:] = dq_acc[:] + _dq_contrib(
            s, k, v, do_ref[0], lse_ref[0], delta_ref[0],
            scale, causal=causal,
        )

    _dispatch_tiles(_do_update, q_start, k_start, block_q, block_k,
                    causal=True, window=window)

    @pl.when(step == pl.num_programs(2) - 1)
    def _finalize():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _k_major_step(kb, step, block_q: int, block_k: int, window, n_q):
    """(Q block, whether it exists) of inner grid step ``step`` of K block
    kb in a kernel whose K block is stationary: the step itself without a
    window; with one, the band's tiles from the diagonal down, the last
    columns' running past the sequence's end."""
    if window is None:
        return step, None
    qi = _first_qi(kb, block_q, block_k) + step
    return qi, qi < n_q


def _flash_bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_acc, dv_acc, *, block_q, block_k, scale, window=None, n_q=None,
):
    kb = pl.program_id(1)
    step = pl.program_id(2)
    qi, valid = _k_major_step(kb, step, block_q, block_k, window, n_q)
    q_start = qi * block_q
    k_start = kb * block_k

    @pl.when(step == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def _do_update(causal):
        q = q_ref[0]
        v = v_ref[0]
        s = _tile_scores(q, k_ref[0], q_start, k_start, block_q, block_k,
                         scale * LOG2E, causal=causal, window=window)
        dk_c, dv_c = _dkv_contrib(
            s, q, v, do_ref[0], lse_ref[0], delta_ref[0],
            scale, causal=causal,
        )
        dk_acc[:] = dk_acc[:] + dk_c
        dv_acc[:] = dv_acc[:] + dv_c

    _dispatch_tiles(_do_update, q_start, k_start, block_q, block_k,
                    causal=True, window=window, valid=valid)

    @pl.when(step == pl.num_programs(2) - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_bwd_fused_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dk_ref, dv_ref,
    dq_acc, dk_acc, dv_acc, *, block_q, block_k, scale, window=None,
    n_q=None,
):
    """One (K block, Q block) tile of all three gradients.  ``dk_acc`` /
    ``dv_acc`` belong to the K block, as in the dK/dV kernel; ``dq_acc``
    is the WHOLE head's dq in float32, resident across the K sweep, and
    ``dq_ref`` the head's output block, written back once a head.  Each
    dq row block is zeroed in the first K column, cast out in the last,
    and gathers its K blocks in ascending order between the two — the
    dQ kernel's order of accumulation.  With a window a row block's first
    K column is the band's (``_first_kb``) and its last the diagonal's."""
    kb = pl.program_id(1)
    step = pl.program_id(2)
    qi, valid = _k_major_step(kb, step, block_q, block_k, window, n_q)
    q_start = qi * block_q
    k_start = kb * block_k
    rows = pl.ds(pl.multiple_of(q_start, block_q), block_q)
    first_column = kb == 0 if window is None else valid & (
        kb == _first_kb(qi, block_q, block_k, window))

    @pl.when(first_column)
    def _init_dq():
        dq_acc[rows, :] = jnp.zeros((block_q, dq_acc.shape[1]), dq_acc.dtype)

    @pl.when(step == 0)
    def _init_dkv():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def _do_update(causal):
        q = q_ref[0]
        k = k_ref[0]
        s = _tile_scores(q, k, q_start, k_start, block_q, block_k,
                         scale * LOG2E, causal=causal, window=window)
        dq_c, dk_c, dv_c = _dqkv_contrib(
            s, q, k, v_ref[0], do_ref[0], lse_ref[0], delta_ref[0],
            scale, causal=causal,
        )
        dq_acc[rows, :] = dq_acc[rows, :] + dq_c
        dk_acc[:] = dk_acc[:] + dk_c
        dv_acc[:] = dv_acc[:] + dv_c

    _dispatch_tiles(_do_update, q_start, k_start, block_q, block_k,
                    causal=True, window=window, valid=valid)

    @pl.when(step == pl.num_programs(2) - 1)
    def _finalize_dkv():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)

    last_column = kb == pl.num_programs(1) - 1 if window is None else (
        valid & (kb == _last_kb(qi, block_q, block_k)))

    @pl.when(last_column)
    def _finalize_dq():
        dq_ref[0, rows, :] = dq_acc[rows, :].astype(dq_ref.dtype)


def _k_major_grid(L: int, block_q: int, block_k: int, window):
    """(grid's K and Q extents, the Q block of grid step (kb, step)) of a
    kernel whose K block is stationary: the dK/dV and the fused kernel.
    Q/dO/lse/Δ fetches above the diagonal clamp to the first in-range tile
    (DMA elision); with a window the inner axis spans the band alone, from
    the diagonal down, and clamps at the band's (or the sequence's) end."""
    n_q, n_k = L // block_q, L // block_k
    first = functools.partial(_first_qi, block_q=block_q, block_k=block_k)
    if window is None:
        return (n_k, n_q), lambda kb, qi: jnp.maximum(qi, first(kb))

    def last(kb):
        return _last_qi(kb, block_q, block_k, window)

    steps = _band_steps(n_k, first, lambda kb: min(last(kb), n_q - 1))
    return (n_k, steps), lambda kb, step: jnp.minimum(
        first(kb) + step, jnp.minimum(last(kb), n_q - 1))


def _k_major_specs(D: int, Dv: int, block_q: int, block_k: int,
                   kv_groups: int, q_block):
    """Block specs of a backward kernel on the grid (BH, K blocks, Q
    blocks), Q innermost: (in_specs for q, k, v, do, lse, Δ; out_specs for
    dk, dv); ``q_block`` is ``_k_major_grid``'s.  K/V input tiles read the
    narrow heads;
    the dk/dv OUTPUTS stay per query head (out_specs use bh as-is) —
    accumulating across a group inside the kernel would serialize the bh
    grid axis, so the group sum happens outside in XLA instead."""

    def q_spec(d):
        return pl.BlockSpec(
            (1, block_q, d),
            lambda bh, kb, qi: (bh, q_block(kb, qi), 0),
            memory_space=pltpu.VMEM,
        )

    def kv_in_spec(d):
        return pl.BlockSpec(
            (1, block_k, d), lambda bh, kb, qi: (bh // kv_groups, kb, 0),
            memory_space=pltpu.VMEM,
        )

    def k_out_spec(d):
        return pl.BlockSpec(
            (1, block_k, d), lambda bh, kb, qi: (bh, kb, 0),
            memory_space=pltpu.VMEM,
        )

    row_spec = pl.BlockSpec(
        (None, 1, block_q),
        lambda bh, kb, qi: (bh, 0, q_block(kb, qi)),
        memory_space=pltpu.VMEM,
    )
    return (
        [q_spec(D), kv_in_spec(D), kv_in_spec(Dv), q_spec(Dv),
         row_spec, row_spec],
        (k_out_spec(D), k_out_spec(Dv)),
    )


def _flash_bwd_split(q, k, v, do, lse, delta, kv_groups: int,
                     window: int | None = None):
    """The two-kernel backward (each kernel owns its accumulator): what a
    head whose dq cannot stay in VMEM takes (``_bwd_fused``)."""
    BH, L, D = q.shape
    Dv = v.shape[-1]
    scale = 1.0 / (D**0.5)

    block_q, block_k = _fwd_blocks(L)  # dQ kernel: Q stationary, like fwd
    extents, k_block = _q_major_grid(L, block_q, block_k, window)

    def q_spec_q(d):
        return pl.BlockSpec(
            (1, block_q, d), lambda bh, qi, kb: (bh, qi, 0),
            memory_space=pltpu.VMEM,
        )

    def k_spec_q(d):
        return pl.BlockSpec(
            (1, block_k, d),
            lambda bh, qi, kb: (bh // kv_groups, k_block(qi, kb), 0),
            memory_space=pltpu.VMEM,
        )

    # lse/Δ ride as exact (1, block_q) rows of [BH, 1, L] — sequence in
    # lanes, no replication; in-kernel use pays one lane→sublane
    # relayout per tile.
    row_spec_q = pl.BlockSpec(
        (None, 1, block_q), lambda bh, qi, kb: (bh, 0, qi),
        memory_space=pltpu.VMEM,
    )
    dq = pl.pallas_call(
        functools.partial(
            _flash_bwd_dq_kernel, block_q=block_q, block_k=block_k,
            scale=scale, window=window,
        ),
        out_shape=jax.ShapeDtypeStruct((BH, L, D), q.dtype),
        grid=(BH, *extents),
        in_specs=[q_spec_q(D), k_spec_q(D), k_spec_q(Dv), q_spec_q(Dv),
                  row_spec_q, row_spec_q],
        out_specs=q_spec_q(D),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=_interpret(),
        name=_kernel_name("flash_bwd_dq", D, Dv, window),
    )(q, k, v, do, lse, delta)

    # dK/dV: K blocks own the accumulators, Q innermost.
    block_q, block_k = _dkv_blocks(L)
    extents, q_block = _k_major_grid(L, block_q, block_k, window)
    in_specs, out_specs = _k_major_specs(D, Dv, block_q, block_k, kv_groups,
                                         q_block)
    dk, dv = pl.pallas_call(
        functools.partial(
            _flash_bwd_dkv_kernel, block_q=block_q, block_k=block_k,
            scale=scale, window=window, n_q=L // block_q,
        ),
        out_shape=(
            jax.ShapeDtypeStruct((BH, L, D), k.dtype),
            jax.ShapeDtypeStruct((BH, L, Dv), v.dtype),
        ),
        grid=(BH, *extents),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, Dv), jnp.float32),
        ],
        compiler_params=_compiler_params(),
        interpret=_interpret(),
        name=_kernel_name("flash_bwd_dkv", D, Dv, window),
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


def _flash_bwd_fused(q, k, v, do, lse, delta, kv_groups: int,
                     window: int | None = None):
    """One kernel for dq, dk and dv: the dK/dV kernel's grid, index maps
    and accumulators, plus the head's dq resident in VMEM (f32 scratch and
    a ``(1, L, D)`` output block whose index moves with bh alone)."""
    BH, L, D = q.shape
    Dv = v.shape[-1]
    block_q, block_k = _dkv_blocks(L)
    extents, q_block = _k_major_grid(L, block_q, block_k, window)
    in_specs, dkv_specs = _k_major_specs(D, Dv, block_q, block_k, kv_groups,
                                         q_block)
    dq_spec = pl.BlockSpec(
        (1, L, D), lambda bh, kb, qi: (bh, 0, 0), memory_space=pltpu.VMEM
    )
    return pl.pallas_call(
        functools.partial(
            _flash_bwd_fused_kernel, block_q=block_q, block_k=block_k,
            scale=1.0 / (D**0.5), window=window, n_q=L // block_q,
        ),
        out_shape=(
            jax.ShapeDtypeStruct((BH, L, D), q.dtype),
            jax.ShapeDtypeStruct((BH, L, D), k.dtype),
            jax.ShapeDtypeStruct((BH, L, Dv), v.dtype),
        ),
        grid=(BH, *extents),
        in_specs=in_specs,
        out_specs=(dq_spec, *dkv_specs),
        scratch_shapes=[
            pltpu.VMEM((L, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, Dv), jnp.float32),
        ],
        # Both inner axes carry accumulators: dk/dv over Q, dq over K.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_fused_vmem_bytes(L, D, Dv, q.dtype),
        ),
        interpret=_interpret(),
        name=_kernel_name("flash_bwd_fused", D, Dv, window),
    )(q, k, v, do, lse, delta)


def _flash_bwd(q, k, v, do, lse, delta, kv_groups: int = 1,
               window: int | None = None):
    """q/lse/delta: [BHq, ...], k: [BHq // kv_groups, L, D], v: [BHq //
    kv_groups, L, Dv], do: [BHq, L, Dv] → (dq [BHq, L, D], dk [BHq, L,
    D], dv [BHq, L, Dv] — PER QUERY HEAD; the caller group-sums dk/dv
    down to the narrow KV heads, one cheap XLA reduction, while the
    kernels never materialize repeated K/V).  The fused kernel where the
    head's dq fits VMEM (``_bwd_fused``), else the two-kernel split."""
    _, L, D = q.shape
    bwd = (_flash_bwd_fused if _bwd_fused(L, D, v.shape[-1], q.dtype)
           else _flash_bwd_split)
    return bwd(q, k, v, do, lse, delta, kv_groups, window)


def _fold(a):
    B, L, H, D = a.shape
    return a.transpose(0, 2, 1, 3).reshape(B * H, L, D)


def _unfold(a, B, H):
    BH, L, D = a.shape
    return a.reshape(B, H, L, D).transpose(0, 2, 1, 3)


def _kv_groups(q, k, v) -> int:
    if k.shape[:-1] != v.shape[:-1] or q.shape[-1] != k.shape[-1]:
        raise ValueError(
            "k and v must have identical shapes up to the head width, and "
            f"q k's head width; got q {q.shape}, k {k.shape}, v {v.shape}"
        )
    H, Hkv = q.shape[2], k.shape[2]
    if H % Hkv:
        raise ValueError(
            f"query heads {H} must be a multiple of K/V heads {Hkv}"
        )
    return H // Hkv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash_core(q, k, v, window):
    return _flash_core_fwd(q, k, v, window)[0]


def _flash_core_fwd(q, k, v, window):
    B, L, H, D = q.shape
    bq, bk = _fwd_blocks(L)
    groups = _kv_groups(q, k, v)
    # The identity outside a ``jax.checkpoint``.  The residuals are the
    # folded inputs the kernels take, so a block recomputed around this call
    # can keep them and the backward pass folds nothing again.
    q, k, v = map(checkpoint_name, (_fold(q), _fold(k), _fold(v)),
                  FLASH_INPUT_NAMES)
    out, lse = _flash_fwd(q, k, v, bq, bk, kv_groups=groups, window=window)
    out, lse = map(checkpoint_name, (out, lse), FLASH_RESIDUAL_NAMES)
    return _unfold(out, B, H), (q, k, v, out, lse)


def _flash_core_bwd(window, res, g):
    q, k, v, out, lse = res  # all folded [BH, L, ·], K/V unrepeated
    B, L, H, _ = g.shape
    D = q.shape[-1]
    groups = q.shape[0] // k.shape[0]
    do = _fold(g)
    # Δ = rowsum(dO ∘ O): O(L·D) elementwise — XLA fuses it; no kernel
    # needed.
    delta = jnp.sum(
        do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    )[:, None, :]  # [BH, 1, L] — exact, same layout as the saved lse
    dq, dk, dv = _flash_bwd(q, k, v, do, lse, delta, kv_groups=groups,
                            window=window)
    dq = _unfold(dq, B, H)
    dk = _unfold(dk, B, H)  # [B, L, H, D] — per query head
    dv = _unfold(dv, B, H)
    if groups > 1:
        # Group-sum down to the narrow KV heads: query heads of one KV
        # group are contiguous (h // groups == kv head), so a reshape
        # exposes the group axis.
        Hkv = H // groups
        dk = dk.reshape(B, L, Hkv, groups, D).sum(axis=3)
        dv = dv.reshape(B, L, Hkv, groups, -1).sum(axis=3)
    return dq, dk, dv


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


def flash_self_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                         window: int | None = None) -> jax.Array:
    """Causal flash attention: q [B, L, H, D] in, [B, L, H, D] out; with
    a value head of another width (``v``: [B, L, Hkv, Dv]; latent
    attention has D = 192, Dv = 128) [B, L, H, Dv] out, scaled ``D^-½``.

    ``window`` (static): key j is visible to query i iff ``i − window < j
    ≤ i`` — sliding-window attention.  Tiles below the band are skipped
    like those above the diagonal, compute, copy and grid step (the drawing
    above ``_first_kb``); a window of L or more is the windowless call, bit
    for bit, and None traces to the program there always was.

    Drop-in for ``ops.ring_attention.dense_self_attention`` on contiguous
    (offset-0) sequences — the unsharded model path.  Both directions run
    as Pallas kernels (the backward recomputes score blocks from the
    forward's saved logsumexp; one kernel where a head's dq fits VMEM).

    Grouped-query attention is native: pass k/v with Hkv < H heads
    (Hkv | H, the ``jnp.repeat``-convention grouping) and the kernels
    stream the narrow K/V directly — no repeated K/V is ever
    materialized in HBM, so K/V read traffic drops by the group factor
    (see ``models/transformer.py``'s flash branch).

    Total over every L: lengths Mosaic cannot tile natively (largest
    power-of-two divisor < 128) are zero-padded up to the next 512
    multiple and the output sliced back.  Zero padding is exact for
    causal attention — padded KEYS sit after every real query (their
    tiles are entirely above the diagonal: skipped), and padded QUERY
    rows are discarded by the slice while contributing zero to dK/dV in
    the backward (their dO rows are zero).  The pad/slice sits OUTSIDE
    the custom_vjp, so JAX's pad/slice VJPs route gradients correctly.
    """
    L = q.shape[1]
    if window is not None:
        if window < 1:
            raise ValueError(f"window must be at least 1, got {window}")
        window = None if window >= L else int(window)
    if not _needs_pad(L):
        return _flash_core(q, k, v, window)
    pad = ((0, 0), (0, _padded_len(L) - L), (0, 0), (0, 0))
    return _flash_core(
        jnp.pad(q, pad), jnp.pad(k, pad), jnp.pad(v, pad), window
    )[:, :L]
