"""Ring FLASH attention: context parallelism with Pallas chunk kernels.

``ops/ring_attention.py`` rotates K/V chunks around the mesh ring and
merges each visiting chunk into an online-softmax running state — but
computes every chunk pair densely, materializing a [B, H, Lc, Lc] score
tensor in HBM per ring step.  This module keeps the identical ring
orchestration (same ``lax.ppermute`` schedule, same online recurrence)
and replaces the per-pair math with the flash kernels: the running
(m, l, acc) triple lives in HBM between steps as O(Lc) state, each ring
step runs one ``pallas_call`` whose score blocks never leave VMEM, and
per-device attention memory drops from O(Lc²) to O(block) — on top of
the O(L/n) sharding win the ring already provides.

The per-tile arithmetic (scores, the online-softmax update, the
backward's ``p``/``ds`` recompute) is imported from
``flash_attention.py`` — ONE source of truth shared with the
single-chunk kernels; only the carry scaffolding (load/store of the
running state across pallas_calls) lives here.

Chunk relationships are resolved OUTSIDE the kernels with ``lax.cond``
on the (dynamic, per-device) visiting rank, so each branch stays a
statically-shaped kernel:

- visiting chunk == own chunk → the diagonal: causal masking, with the
  same DMA-eliding clamped index maps as single-chunk flash;
- visiting chunk strictly earlier → full attention, mask-free variants;
- visiting chunk strictly later → identity on the carry (no kernel).

Backward is the standard ring-flash second pass: Δ = rowsum(dO∘O) and
the forward's per-row logsumexp stay resident with Q; K/V rotate again,
each step adding this device's contribution to the VISITING chunk's
dK/dV (which travel the ring alongside K/V and arrive home after n
steps) and accumulating local dQ.

Grouped-query attention is native end to end: pass k/v with Hkv < H
heads and the NARROW chunks rotate on the ring (ICI traffic and the
traveling dK/dV both shrink by the group factor); the kernels' K/V tile
index maps divide by the group factor exactly like single-chunk flash,
and each step's per-query-head dK/dV contribution is group-summed
before joining the traveling narrow accumulators.

Runs in interpreter mode off-TPU, so the CPU-mesh tests exercise the
exact code path the TPU compiles.  Reference baseline: the einsum ring
(``ops/ring_attention.py``), itself property-tested against dense
attention.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_machine_learning_tpu.ops.pallas.flash_attention import (
    _LANES,
    LOG2E,
    NEG_INF,
    _compiler_params,
    _dkv_blocks,
    _dispatch_tiles,
    _dkv_contrib,
    _dq_contrib,
    _first_qi,
    _fold,
    _fwd_blocks,
    _interpret,
    _kv_groups,
    _last_kb,
    _online_update,
    _tile_scores,
    _unfold,
)


# ---------------------------------------------------------------------------
# Forward: one ring step = one carry-threaded chunk kernel.
# ---------------------------------------------------------------------------


def _chunk_fwd_kernel(
    q_ref, k_ref, v_ref, m_in, l_in, acc_in, m_out, l_out, acc_out,
    m_s, l_s, acc_s, *, block_q, block_k, scale, causal,
):
    """Merge one visiting K/V chunk into the online (m, l, acc) carry.

    Unlike the single-chunk flash kernel, the running triple is carried
    ACROSS calls: read from HBM at the first K step, updated in VMEM
    scratch, written back at the last K step.
    """
    qi = pl.program_id(1)
    kb = pl.program_id(2)
    q_start = qi * block_q
    k_start = kb * block_k

    @pl.when(kb == 0)
    def _load():
        # The HBM carry keeps m/l as exact [Lc] rows (sequence in lanes);
        # expand to the lane-replicated VMEM scratch the online update
        # wants — one relayout per Q block per ring step, in exchange for
        # 128× less carry traffic through HBM between steps.
        m_s[:] = jnp.broadcast_to(m_in[0][:, None], m_s.shape)
        l_s[:] = jnp.broadcast_to(l_in[0][:, None], l_s.shape)
        acc_s[:] = acc_in[0]

    def _do_update(tile_causal):
        v = v_ref[0]
        s = _tile_scores(q_ref[0], k_ref[0], q_start, k_start, block_q,
                         block_k, scale * LOG2E, causal=tile_causal)
        m_new, l_new, acc_new = _online_update(
            s, m_s[:, 0], l_s[:, 0], acc_s[:], v, causal=tile_causal
        )
        acc_s[:] = acc_new
        m_s[:] = jnp.broadcast_to(m_new[:, None], m_s.shape)
        l_s[:] = jnp.broadcast_to(l_new[:, None], l_s.shape)

    _dispatch_tiles(_do_update, q_start, k_start, block_q, block_k,
                    causal=causal)

    @pl.when(kb == pl.num_programs(2) - 1)
    def _store():
        m_out[0] = m_s[:, 0]
        l_out[0] = l_s[:, 0]
        acc_out[0] = acc_s[:]


def _chunk_fwd(q, k, v, carry, *, causal: bool, kv_groups: int = 1):
    """One ring step over folded chunks (q [BHq, Lc, D], k/v
    [BHq // kv_groups, Lc, D]); carry = (m, l, acc) with m/l
    [BHq, 1, Lc] f32 (exact rows) and acc [BHq, Lc, D] f32."""
    m, l, acc = carry
    BH, Lc, D = q.shape
    scale = 1.0 / (D**0.5)
    block_q, block_k = _fwd_blocks(Lc)
    grid = (BH, Lc // block_q, Lc // block_k)
    q_spec = pl.BlockSpec(
        (1, block_q, D), lambda bh, qi, kb: (bh, qi, 0), memory_space=pltpu.VMEM
    )
    if causal:
        # Diagonal step: clamp above-diagonal K/V fetches so their DMAs
        # are elided, same as the single-chunk flash kernels.
        k_spec = pl.BlockSpec(
            (1, block_k, D),
            lambda bh, qi, kb: (
                bh // kv_groups,
                jnp.minimum(kb, _last_kb(qi, block_q, block_k)), 0,
            ),
            memory_space=pltpu.VMEM,
        )
    else:
        k_spec = pl.BlockSpec(
            (1, block_k, D),
            lambda bh, qi, kb: (bh // kv_groups, kb, 0),
            memory_space=pltpu.VMEM,
        )
    row_spec = pl.BlockSpec(
        (None, 1, block_q), lambda bh, qi, kb: (bh, 0, qi),
        memory_space=pltpu.VMEM,
    )
    acc_spec = pl.BlockSpec(
        (1, block_q, D), lambda bh, qi, kb: (bh, qi, 0),
        memory_space=pltpu.VMEM,
    )
    return pl.pallas_call(
        functools.partial(
            _chunk_fwd_kernel, block_q=block_q, block_k=block_k,
            scale=scale, causal=causal,
        ),
        out_shape=(
            jax.ShapeDtypeStruct(m.shape, jnp.float32),
            jax.ShapeDtypeStruct(l.shape, jnp.float32),
            jax.ShapeDtypeStruct(acc.shape, jnp.float32),
        ),
        grid=grid,
        in_specs=[q_spec, k_spec, k_spec, row_spec, row_spec, acc_spec],
        out_specs=(row_spec, row_spec, acc_spec),
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        input_output_aliases={3: 0, 4: 1, 5: 2},
        compiler_params=_compiler_params(),
        interpret=_interpret(),
    )(q, k, v, m, l, acc)


# ---------------------------------------------------------------------------
# Backward: per-step dQ and dK/dV chunk kernels (causal + full variants).
# ---------------------------------------------------------------------------


def _chunk_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_in, dq_out, dq_s,
    *, block_q, block_k, scale, causal,
):
    qi = pl.program_id(1)
    kb = pl.program_id(2)
    q_start = qi * block_q
    k_start = kb * block_k

    @pl.when(kb == 0)
    def _load():
        dq_s[:] = dq_in[0]

    def _do_update(tile_causal):
        k = k_ref[0]
        s = _tile_scores(q_ref[0], k, q_start, k_start, block_q, block_k,
                         scale * LOG2E, causal=tile_causal)
        dq_s[:] = dq_s[:] + _dq_contrib(
            s, k, v_ref[0], do_ref[0], lse_ref[0],
            delta_ref[0], scale, causal=tile_causal,
        )

    _dispatch_tiles(_do_update, q_start, k_start, block_q, block_k,
                    causal=causal)

    @pl.when(kb == pl.num_programs(2) - 1)
    def _store():
        dq_out[0] = dq_s[:]


def _chunk_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_in, dv_in,
    dk_out, dv_out, dk_s, dv_s, *, block_q, block_k, scale, causal,
):
    kb = pl.program_id(1)
    qi = pl.program_id(2)
    q_start = qi * block_q
    k_start = kb * block_k

    @pl.when(qi == 0)
    def _load():
        dk_s[:] = dk_in[0]
        dv_s[:] = dv_in[0]

    def _do_update(tile_causal):
        q = q_ref[0]
        s = _tile_scores(q, k_ref[0], q_start, k_start, block_q, block_k,
                         scale * LOG2E, causal=tile_causal)
        dk_c, dv_c = _dkv_contrib(
            s, q, v_ref[0], do_ref[0], lse_ref[0],
            delta_ref[0], scale, causal=tile_causal,
        )
        dk_s[:] = dk_s[:] + dk_c
        dv_s[:] = dv_s[:] + dv_c

    _dispatch_tiles(_do_update, q_start, k_start, block_q, block_k,
                    causal=causal)

    @pl.when(qi == pl.num_programs(2) - 1)
    def _store():
        dk_out[0] = dk_s[:]
        dv_out[0] = dv_s[:]


def _chunk_dq(q, k, v, do, lse, delta, dq, *, causal: bool,
              kv_groups: int = 1):
    BH, Lc, D = q.shape
    scale = 1.0 / (D**0.5)
    block_q, block_k = _fwd_blocks(Lc)
    q_spec = pl.BlockSpec(
        (1, block_q, D), lambda bh, qi, kb: (bh, qi, 0), memory_space=pltpu.VMEM
    )
    if causal:
        k_spec = pl.BlockSpec(
            (1, block_k, D),
            lambda bh, qi, kb: (
                bh // kv_groups,
                jnp.minimum(kb, _last_kb(qi, block_q, block_k)), 0,
            ),
            memory_space=pltpu.VMEM,
        )
    else:
        k_spec = pl.BlockSpec(
            (1, block_k, D),
            lambda bh, qi, kb: (bh // kv_groups, kb, 0),
            memory_space=pltpu.VMEM,
        )
    row_spec = pl.BlockSpec(
        (None, 1, block_q), lambda bh, qi, kb: (bh, 0, qi),
        memory_space=pltpu.VMEM,
    )
    return pl.pallas_call(
        functools.partial(
            _chunk_dq_kernel, block_q=block_q, block_k=block_k, scale=scale,
            causal=causal,
        ),
        out_shape=jax.ShapeDtypeStruct((BH, Lc, D), jnp.float32),
        grid=(BH, Lc // block_q, Lc // block_k),
        in_specs=[q_spec, k_spec, k_spec, q_spec, row_spec, row_spec,
                  q_spec],
        out_specs=q_spec,
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        input_output_aliases={6: 0},
        compiler_params=_compiler_params(),
        interpret=_interpret(),
    )(q, k, v, do, lse, delta, dq)


def _chunk_dkv(q, k, v, do, lse, delta, dk, dv, *, causal: bool,
               kv_groups: int = 1):
    """dK/dV contributions of this device's Q block to one K/V chunk.

    With ``kv_groups == 1`` the in/out dk/dv are the full-width chunk
    accumulators (in-place).  With groups > 1, dk/dv must be PER QUERY
    HEAD zero buffers [BHq, Lc, D]; the caller group-sums them down to
    the narrow heads before merging into the traveling accumulators.
    """
    BH, Lc, D = q.shape
    scale = 1.0 / (D**0.5)
    block_q, block_k = _dkv_blocks(Lc)
    if causal:
        def _qi_map(bh, kb, qi):
            return bh, jnp.maximum(qi, _first_qi(kb, block_q, block_k)), 0

        def _qi_row_map(bh, kb, qi):
            return bh, 0, jnp.maximum(qi, _first_qi(kb, block_q, block_k))
    else:
        def _qi_map(bh, kb, qi):
            return bh, qi, 0

        def _qi_row_map(bh, kb, qi):
            return bh, 0, qi
    q_spec = pl.BlockSpec(
        (1, block_q, D), _qi_map, memory_space=pltpu.VMEM
    )
    kv_in_spec = pl.BlockSpec(
        (1, block_k, D), lambda bh, kb, qi: (bh // kv_groups, kb, 0),
        memory_space=pltpu.VMEM,
    )
    out_spec = pl.BlockSpec(
        (1, block_k, D), lambda bh, kb, qi: (bh, kb, 0),
        memory_space=pltpu.VMEM,
    )
    row_spec = pl.BlockSpec(
        (None, 1, block_q), _qi_row_map, memory_space=pltpu.VMEM
    )
    return pl.pallas_call(
        functools.partial(
            _chunk_dkv_kernel, block_q=block_q, block_k=block_k, scale=scale,
            causal=causal,
        ),
        out_shape=(
            jax.ShapeDtypeStruct((BH, Lc, D), jnp.float32),
            jax.ShapeDtypeStruct((BH, Lc, D), jnp.float32),
        ),
        grid=(BH, Lc // block_k, Lc // block_q),
        in_specs=[q_spec, kv_in_spec, kv_in_spec, q_spec, row_spec,
                  row_spec, out_spec, out_spec],
        out_specs=(out_spec, out_spec),
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        input_output_aliases={6: 0, 7: 1},
        compiler_params=_compiler_params(),
        interpret=_interpret(),
    )(q, k, v, do, lse, delta, dk, dv)


# ---------------------------------------------------------------------------
# The ring, forward + custom VJP.
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def ring_flash_self_attention(q, k, v, axis_name: str, axis_size: int):
    """Exact causal attention over sequence chunks sharded on
    ``axis_name`` — the flash-kernel ring (see module docstring).

    Must run inside ``shard_map``; q [B, Lc, H, D] and k/v [B, Lc, Hkv,
    D] (Hkv | H — GQA rotates the narrow chunks) are the local chunks,
    global order following the mesh axis.  Per-device attention memory
    is O(block); HBM state between ring steps is O(Lc).
    """
    out, _ = _ring_fwd_impl(q, k, v, axis_name, axis_size)
    return out


def _ring_fwd_impl(q, k, v, axis_name, axis_size):
    n = axis_size
    B, Lc, H, D = q.shape
    groups = _kv_groups(q, k, v)
    qf, kf, vf = _fold(q), _fold(k), _fold(v)
    BH = qf.shape[0]
    rank = lax.axis_index(axis_name)
    carry = (
        jnp.full((BH, 1, Lc), NEG_INF, jnp.float32),
        jnp.zeros((BH, 1, Lc), jnp.float32),
        jnp.zeros((BH, Lc, D), jnp.float32),
    )
    perm = [(i, (i + 1) % n) for i in range(n)]
    kv = (kf, vf)
    for s in range(n):
        kv_rank = (rank - s) % n
        kc, vc = kv
        carry = lax.cond(
            kv_rank == rank,
            lambda c, kc=kc, vc=vc: _chunk_fwd(
                qf, kc, vc, c, causal=True, kv_groups=groups
            ),
            lambda c, kc=kc, vc=vc: lax.cond(
                kv_rank < rank,
                lambda c2: _chunk_fwd(
                    qf, kc, vc, c2, causal=False, kv_groups=groups
                ),
                lambda c2: c2,
                c,
            ),
            carry,
        )
        if s < n - 1:
            kv = lax.ppermute(kv, axis_name, perm)
    m, l, acc = carry
    l1 = jnp.maximum(l, 1e-30)  # [BH, 1, Lc]
    out = (acc / l1[:, 0, :, None]).astype(q.dtype)
    lse = m + jnp.log2(l1)  # [BH, 1, Lc] — exact rows, log2 space
    return _unfold(out, B, H), (q, k, v, out, lse)


def _ring_fwd_vjp(q, k, v, axis_name, axis_size):
    out, res = _ring_fwd_impl(q, k, v, axis_name, axis_size)
    return out, res


def _group_sum(t, B, H, groups):
    """[B·H, Lc, D] per-query-head grads → [B·Hkv, Lc, D] narrow grads
    (query heads of one KV group are contiguous after folding)."""
    BH, Lc, D = t.shape
    Hkv = H // groups
    return (
        t.reshape(B, Hkv, groups, Lc, D).sum(axis=2).reshape(B * Hkv, Lc, D)
    )


def _ring_bwd_vjp(axis_name, axis_size, res, g):
    q, k, v, out_f, lse = res  # out_f/lse already folded [BH, Lc, ...]
    n = axis_size
    B, Lc, H, D = q.shape
    groups = _kv_groups(q, k, v)
    qf, kf, vf = _fold(q), _fold(k), _fold(v)
    do = _fold(g)
    rank = lax.axis_index(axis_name)
    delta = jnp.sum(
        do.astype(jnp.float32) * out_f.astype(jnp.float32), axis=-1
    )[:, None, :]  # [BH, 1, Lc] — exact, same layout as the carried lse

    dq = jnp.zeros(qf.shape, jnp.float32)
    # dK/dV travel WITH their (narrow, under GQA) K/V chunk: after n ring
    # steps (rotating at every step including the last) the accumulated
    # grads land back on the chunk's home device.
    payload = (kf, vf, jnp.zeros(kf.shape, jnp.float32),
               jnp.zeros(vf.shape, jnp.float32))
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step_dkv(kc, vc, dkc, dvc, causal):
        if groups == 1:
            return _chunk_dkv(qf, kc, vc, do, lse, delta, dkc, dvc,
                              causal=causal)
        # GQA: per-query-head contributions into zero buffers, then one
        # cheap group-sum before joining the narrow traveling grads.
        z = jnp.zeros(qf.shape, jnp.float32)
        dk_q, dv_q = _chunk_dkv(qf, kc, vc, do, lse, delta, z, z,
                                causal=causal, kv_groups=groups)
        return (dkc + _group_sum(dk_q, B, H, groups),
                dvc + _group_sum(dv_q, B, H, groups))

    for s in range(n):
        kv_rank = (rank - s) % n
        kc, vc, dkc, dvc = payload

        def diag(dq, dkc, dvc, kc=kc, vc=vc):
            dq2 = _chunk_dq(qf, kc, vc, do, lse, delta, dq, causal=True,
                            kv_groups=groups)
            dk2, dv2 = step_dkv(kc, vc, dkc, dvc, causal=True)
            return dq2, dk2, dv2

        def full(dq, dkc, dvc, kc=kc, vc=vc):
            dq2 = _chunk_dq(qf, kc, vc, do, lse, delta, dq, causal=False,
                            kv_groups=groups)
            dk2, dv2 = step_dkv(kc, vc, dkc, dvc, causal=False)
            return dq2, dk2, dv2

        dq, dkc, dvc = lax.cond(
            kv_rank == rank,
            diag,
            lambda dq, dkc, dvc: lax.cond(
                kv_rank < rank, full, lambda a, b, c: (a, b, c),
                dq, dkc, dvc,
            ),
            dq, dkc, dvc,
        )
        # Rotate on EVERY step so the traveling grads complete the full
        # circle home (n rotations == identity for k/v themselves).
        payload = lax.ppermute((kc, vc, dkc, dvc), axis_name, perm)

    _, _, dk, dv = payload
    Hkv = H // groups
    return (
        _unfold(dq, B, H).astype(q.dtype),
        _unfold(dk, B, Hkv).astype(k.dtype),
        _unfold(dv, B, Hkv).astype(v.dtype),
    )


ring_flash_self_attention.defvjp(_ring_fwd_vjp, _ring_bwd_vjp)
