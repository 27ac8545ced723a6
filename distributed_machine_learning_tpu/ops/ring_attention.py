"""Ring attention: exact causal self-attention over a sequence-sharded mesh.

Long-context support the reference (a 32×32-image CNN stack, zero attention
— SURVEY.md §5 "long-context: none") never had, built the TPU way: the
sequence is sharded over a mesh axis, every device keeps its Q block
resident, and the K/V blocks rotate around the ring via ``lax.ppermute``
(the same ICI ring the bucketed gradient all-reduce in ``ops/ring.py``
rides).  Softmax is computed *online* — running max / normalizer /
accumulator updated per block (the flash-attention recurrence) — so the
full L×L score matrix never materializes and per-device attention memory
is O(L·L/n): context length scales linearly with the number of chips.

The block loop is unrolled over the static ring size, so XLA sees n-1
independent ppermutes it can overlap with each block's einsums — comm
hides behind compute exactly like the gradient ring.

All score/normalizer arithmetic runs in fp32 regardless of the trunk dtype
(bf16 QKV is fine into the MXU; the logsumexp recurrence is not).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30  # large-negative instead of -inf: keeps exp()/where() NaN-free


def _block_scores(q, k, scale):
    """[B, Lq, H, D] × [B, Lk, H, D] → fp32 scores [B, H, Lq, Lk]."""
    return (
        jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
        * scale
    )


def _online_update(carry, q, k, v, q_pos, k_pos, scale):
    """One flash-attention block update of the (m, l, o) running triple."""
    m, l, o = carry
    s = _block_scores(q, k, scale)  # [B, H, Lq, Lk] fp32
    causal = q_pos[:, None] >= k_pos[None, :]  # [Lq, Lk]
    s = jnp.where(causal[None, None], s, NEG_INF)
    m_new = jnp.maximum(m, s.max(axis=-1))  # [B, H, Lq]
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[..., None])  # [B, H, Lq, Lk]
    # Masked entries must contribute 0 even in a fully-masked row (there
    # s == m_new == NEG_INF and the exp above would give 1, not 0).
    p = jnp.where(s > 0.5 * NEG_INF, p, 0.0)
    l_new = l * alpha + p.sum(axis=-1)
    pv = jnp.einsum(
        "bhqk,bkhd->bqhd", p, v.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    o_new = o * alpha.transpose(0, 2, 1)[..., None] + pv
    return m_new, l_new, o_new


def ring_self_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    axis_size: int,
) -> jax.Array:
    """Exact causal attention over sequence chunks sharded on ``axis_name``.

    Must run inside ``shard_map``.  ``q``/``k``/``v`` are the local chunks,
    shape [B, L/n, H, D] with global sequence order following the mesh axis
    order; grouped-query K/V may be NARROW ([B, L/n, Hkv, D], Hkv | H) —
    the narrow chunks are what rotates around the ring (ICI bytes ÷ the
    group factor, same saving as the flash ring), widened only at the
    local block math where XLA fuses the broadcast into the einsums.
    Returns the local output chunk, same shape/dtype as ``q``.
    """
    n = axis_size
    B, Lc, H, D = q.shape
    Hkv = k.shape[2]
    if H % Hkv:
        raise ValueError(
            f"query heads ({H}) must be a multiple of K/V heads ({Hkv})"
        )
    rep = H // Hkv
    scale = 1.0 / (D**0.5)
    rank = lax.axis_index(axis_name)
    q_pos = rank * Lc + jnp.arange(Lc)

    m = jnp.full((B, H, Lc), NEG_INF, jnp.float32)
    l = jnp.zeros((B, H, Lc), jnp.float32)
    o = jnp.zeros((B, Lc, H, D), jnp.float32)
    carry = (m, l, o)

    perm = [(i, (i + 1) % n) for i in range(n)]
    kv = (k, v)

    def widen(t):
        return jnp.repeat(t, rep, axis=2) if rep > 1 else t

    for s in range(n):
        # After s right-shifts this device holds the K/V chunk that
        # originated on rank − s.
        kv_rank = (rank - s) % n
        k_pos = kv_rank * Lc + jnp.arange(Lc)
        carry = _online_update(
            carry, q, widen(kv[0]), widen(kv[1]), q_pos, k_pos, scale
        )
        if s < n - 1:
            kv = lax.ppermute(kv, axis_name, perm)

    m, l, o = carry
    # Fully-masked rows (none, under causal: every q sees at least itself)
    # would have l == 0; guard anyway so the op is safe for future masks.
    out = o / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def dense_self_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    positions: jax.Array | None = None,
    window: int | None = None,
) -> jax.Array:
    """Single-device exact causal attention — the ring op's reference
    semantics (and the attention used when the model runs unsharded).

    [B, L, H, D] in, [B, L, H, D] out.  ``window``: key j is visible to
    query i iff ``i − window < j ≤ i`` (sliding-window attention).
    """
    B, L, H, D = q.shape
    if positions is None:
        positions = jnp.arange(L)
    s = _block_scores(q, k, 1.0 / (D**0.5))
    causal = positions[:, None] >= positions[None, :]
    if window is not None:
        causal &= positions[None, :] > positions[:, None] - window
    s = jnp.where(causal[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum(
        "bhqk,bkhd->bqhd", p, v.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    return out.astype(q.dtype)
