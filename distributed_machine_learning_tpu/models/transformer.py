"""Decoder-only transformer LM with pluggable dense / ring / ulysses /
flash attention.

A model family beyond the reference's capability surface (its only model is
a 32×32 CNN — ``part1/model.py``; SURVEY.md §2.3 records TP/SP/CP as
absent) added because long-context is first-class here: with
``attn_impl="ring"`` the module runs unchanged inside a ``shard_map`` whose
``seq_axis`` shards the sequence across devices, attention becomes the
exact blockwise ring of ``ops/ring_attention.py``, and context length
scales linearly with the number of chips.

TPU-first choices:
- pre-LN blocks, GELU MLP — all weight matmuls are large, static-shape
  einsums that tile straight onto the MXU;
- rotary position embeddings (RoPE): positions enter through a rotation of
  Q/K rather than a learned table, so a sequence-sharded device needs only
  its global position offset (``lax.axis_index``), not an embedding slice;
- bf16 trunk with fp32 logits/softmax (same policy as ``models/vgg.py``);
- zero data-dependent Python control flow — one traced XLA program.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from distributed_machine_learning_tpu.ops.ring_attention import (
    dense_self_attention,
    ring_self_attention,
)


def apply_rope(x: jax.Array, positions: jax.Array, base: float = 10000.0,
               rotary_dim: int | None = None):
    """Rotate [B, L, H, D] by per-position angles; fp32 math, dtype
    preserved.  ``positions``: [L] (one stream position per slot) or
    [B, L] (per-ROW absolute positions — the batched-frontier decode
    path, where each batch row's committed stream has its own length).
    ``rotary_dim``: rotate only the first ``rotary_dim`` dimensions of
    each head (half-split pairs inside that width, frequencies over that
    width) and pass the rest through — partial rotary embeddings; None
    rotates the whole head."""
    if rotary_dim is not None and rotary_dim < x.shape[-1]:
        rotated = apply_rope(x[..., :rotary_dim], positions, base)
        return jnp.concatenate([rotated, x[..., rotary_dim:]], axis=-1)
    d_half = x.shape[-1] // 2
    freqs = base ** (-jnp.arange(d_half, dtype=jnp.float32) / d_half)
    angles = positions.astype(jnp.float32)[..., None] * freqs  # [..., L, Dh/2]
    if positions.ndim == 1:
        cos = jnp.cos(angles)[None, :, None, :]
        sin = jnp.sin(angles)[None, :, None, :]
    else:  # [B, L] per-row positions
        cos = jnp.cos(angles)[:, :, None, :]
        sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    rotated = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return rotated.astype(x.dtype)


def _repeat_kv(t: jax.Array, n_rep: int) -> jax.Array:
    """[B, L, Hkv, D] → [B, L, Hkv·n_rep, D]: expand grouped K/V heads so
    every attention impl sees full-width heads (XLA fuses the broadcast
    into the attention matmuls; only the decode *cache* stays narrow —
    that is GQA's memory win)."""
    if n_rep == 1:
        return t
    return jnp.repeat(t, n_rep, axis=2)


def _cached_mask(s, q_positions, S):
    """Causal frontier mask for the cached-attention einsums.  ``s``:
    [B, Hkv, rep, Lq, S] scores; ``q_positions``: [Lq] (one shared
    stream) or [B, Lq] (per-row frontiers — batched speculative
    decoding)."""
    if q_positions.ndim == 1:
        mask = jnp.arange(S)[None, :] <= q_positions[:, None]  # [Lq, S]
        return jnp.where(mask[None, None, None], s, -jnp.inf)
    mask = (
        jnp.arange(S)[None, None, :] <= q_positions[:, :, None]
    )  # [B, Lq, S]
    return jnp.where(mask[:, None, None], s, -jnp.inf)


def _cached_attention(q, k_cache, v_cache, q_positions):
    """Attention of fresh queries against the full K/V cache, GQA-native.

    ``q``: [B, Lq, H, D] at absolute positions ``q_positions`` ([Lq],
    or [B, Lq] for per-row frontiers — see :func:`_cached_mask`);
    ``k_cache``/``v_cache``: [B, Hkv, S, D] (Hkv | H) where slot j holds
    position j (zeros beyond the write frontier — masked out by
    causality, since unwritten slots all have j > max(q_positions)).
    The head-major cache layout keeps each head's slots contiguous in
    (slot, lane) tiles — the layout the flash-decode kernel DMAs at
    full bandwidth (head-minor [S, Hkv, D] tiles pad Hkv=4 sublanes to
    8, measured 8× slower DMA).  fp32 softmax, dtype preserved —
    matching :func:`dense_self_attention`.

    The query heads are RESHAPED into [Hkv, rep] groups and contracted
    against the narrow cache directly — no widened K/V is ever
    materialized.  Decode is bound by HBM reads of weights + cache, and
    a ``jnp.repeat`` of the cache every step would re-write (and
    re-read) rep× the cache bytes, forfeiting exactly the bandwidth GQA
    buys.
    """
    B, Lq, H, D = q.shape
    Hkv, S = k_cache.shape[1], k_cache.shape[2]
    rep = H // Hkv
    qg = q.astype(jnp.float32).reshape(B, Lq, Hkv, rep, D)
    s = jnp.einsum(
        "bqhrd,bhkd->bhrqk",
        qg,
        k_cache.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    ) * (1.0 / (D**0.5))
    s = _cached_mask(s, q_positions, S)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum(
        "bhrqk,bhkd->bqhrd", p, v_cache.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    return out.reshape(B, Lq, H, D).astype(q.dtype)


def _cached_attention_quant(q, k_int, ks, v_int, vs, q_positions):
    """:func:`_cached_attention` over an int8 cache WITHOUT materializing
    a dequantized f32 copy: the per-slot scales fold into the f32
    score/probability path — ``s·ks`` after the QK einsum, ``p·vs``
    before the PV einsum — algebraically identical to dequantize-then-
    attend, while the int8→f32 convert fuses into the einsums (HBM only
    ever reads the int8 bytes; a materialized f32 cache copy would cost
    4× the traffic the int8 cache exists to save)."""
    B, Lq, H, D = q.shape
    Hkv, S = k_int.shape[1], k_int.shape[2]
    rep = H // Hkv
    qg = q.astype(jnp.float32).reshape(B, Lq, Hkv, rep, D)
    s = jnp.einsum(
        "bqhrd,bhkd->bhrqk", qg, k_int.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    ) * (1.0 / (D**0.5))
    s = s * ks[:, :, None, None, :]  # fold the key scales, f32
    s = _cached_mask(s, q_positions, S)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum(
        "bhrqk,bhkd->bqhrd", p * vs[:, :, None, None, :],
        v_int.astype(jnp.float32), preferred_element_type=jnp.float32,
    )
    return out.reshape(B, Lq, H, D).astype(q.dtype)


# Two-tier int8-KV-cache dispatch (VERDICT r4 item 7): when True,
# single-token int8 decode picks at RUNTIME between the
# frontier-clamped Pallas kernel (early in the stream — it reads
# O(pos) while the einsum reads all S allocated slots) and the
# scale-folding einsum (late).  What decides between them:
#   - the einsum's cost is FLAT in the fill (it reads every allocated
#     slot); the kernel's grows with pos, and its exact-f32 dequant
#     keeps it off the DMA roofline, so the kernel only wins while
#     pos/S_alloc is under a break-even;
#   - the tiered program is a lax.cond over both, so it compiles
#     both: seconds more per serving shape.
# Verdict: default OFF — over any run-to-completion generation the
# mean fill is >= 0.5, so the phase under the break-even is a small
# share of the whole, not worth the compile per serving shape.  The
# workload that inverts this: serving that allocates a generous
# max_new_tokens and usually stops early (fill stays under the
# break-even all request).  The break-even below is from a round-5
# timing at S_alloc=32k (Hkv=8, D=64) of older code, its script gone;
# only tests/test_quant.py flips the switch.
_INT8_TIERED_DISPATCH = False
_INT8_TIER_BREAK_EVEN_PCT = 19  # not calibrated on v5e (ROADMAP D5)


def _flash_wins(L: int) -> bool:
    """attn_impl="auto" policy — delegates to the kernel module's shared
    ``flash_wins`` length rule (not calibrated on v5e: ROADMAP D5)."""
    from distributed_machine_learning_tpu.ops.pallas.flash_attention import (
        flash_wins,
    )

    return flash_wins(L)


def _ring_flash_wins(chunk_len: int) -> bool:
    """ring → ring_flash upgrade policy (one source of truth for the CLI
    and programmatic callers): the per-chunk math is exactly the
    unsharded-flash regime applied to the LOCAL chunk, so the same
    length policy decides — delegate to ``flash_wins``, minus
    the lengths the single-chunk path handles by padding: the ring
    kernels operate on fixed chunk grids with no pad/slice wrapper, so
    a chunk Mosaic cannot tile natively stays on the einsum ring."""
    from distributed_machine_learning_tpu.ops.pallas.flash_attention import (
        _needs_pad,
        flash_wins,
    )

    return flash_wins(chunk_len) and not _needs_pad(chunk_len)


class Attention(nn.Module):
    """Multi-head causal self-attention.

    ``attn_impl``: "dense" (full XLA attention), "ring" (sequence sharded
    over ``seq_axis``, einsum chunk pairs — ``ops/ring_attention.py``),
    "ring_flash" (sequence sharded, flash-kernel chunk pairs —
    ``ops/pallas/ring_flash_attention.py``), "ulysses" (sequence sharded
    via all-to-all head re-sharding — ``ops/ulysses.py``), "flash" (the
    Pallas kernel — ``ops/pallas/flash_attention.py``), or "auto" (flash
    from ``flash_wins``'s 512-context crossover up when the length tiles
    natively, always from 2048 up via the kernel's pad-and-slice path,
    dense below — see ``flash_wins``; for the sharded ring the analogous
    policy is ``_ring_flash_wins``).

    ``decode=True`` switches to KV-cached autoregressive inference: K/V
    land in a ``"cache"`` variable collection sized by the init-time
    input length, and each apply attends its (short) input against the
    whole cache — the O(1)-per-token decode path behind
    ``inference/generate.py``.
    """

    n_heads: int
    attn_impl: str = "dense"  # "dense" | "ring" | "ulysses" | "flash" | "auto"
    seq_axis: str = "seq"
    compute_dtype: Any = jnp.float32
    decode: bool = False
    # Grouped-query attention: K/V get n_kv_heads heads (< n_heads),
    # each shared by n_heads/n_kv_heads query heads; 1 = MQA.  None
    # keeps classic MHA with the fused qkv projection (and its param
    # layout — existing checkpoints are untouched).
    n_kv_heads: int | None = None
    # Decode KV-cache storage dtype (None = the K/V compute dtype).
    # Decode is bound by HBM reads of the cache, so a narrower cache
    # dtype is a direct bandwidth lever; attention math stays fp32
    # either way (_cached_attention upcasts).
    kv_cache_dtype: Any = None
    # When set (a jax.sharding.Mesh), the flash kernel runs inside a
    # fully-manual shard_map with the batch dim sharded over
    # ``flash_batch_axis`` (and, when ``flash_head_axis`` is set, the
    # head dim sharded over it — the Megatron TP layout; heads are
    # independent in flash and GQA groups stay aligned because
    # H_local = groups · Hkv_local on every shard).  This is how flash
    # composes with the GSPMD-partitioned steps (fsdp_pl / EP / TP),
    # whose jit could not otherwise partition the Mosaic custom call.
    # The activations must really be sharded that way (the shard_map
    # constrains them if the partitioner chose otherwise).
    flash_mesh: Any = None
    flash_batch_axis: str = "batch"
    flash_head_axis: str | None = None
    # None = manualize the WHOLE mesh (the GSPMD steps).  The 3-D step
    # calls from inside a region already manual over its pipe axis, so
    # it restricts the wrap to the remaining (batch, model) axes — the
    # union is still every axis, keeping the kernel fully local.
    flash_manual_axes: tuple | None = None
    # "int8" = weight-only quantized projections for serving decode
    # (ops/quant.py); None = full-precision nn.DenseGeneral.
    weight_quant: str | None = None
    # Manual Megatron tensor parallelism for DECODE (shard_map context,
    # parallel/tensor_parallel.py::make_tp_generate_fn): this module is
    # then configured at its LOCAL width (n_heads = H/tp), its
    # out-projection is row-parallel (each device holds the rows of its
    # heads), and the psum below completes the Megatron g-collective.
    # The out-proj bias must be pre-divided by tp (tp_decode_params) so
    # the psum reassembles it exactly.
    tp_axis: str | None = None
    # Explicit per-head width.  None = E // n_heads (the usual rule);
    # the manual-TP decode clone MUST set it to the GLOBAL head dim,
    # since its local n_heads no longer divides E into real head widths.
    head_dim: int | None = None
    # Multi-token decode calls attend the full cache instead of taking
    # the start-0 prefill fast path — speculative decoding's verify
    # pass (inference/speculative.py).  decode=True only.
    decode_continuation: bool = False
    rope_base: float = 10000.0  # the rotation's base (a config's rope_theta)

    @nn.compact
    def __call__(self, x, positions):
        B, L, E = x.shape
        if self.head_dim is None:
            assert E % self.n_heads == 0, "n_heads must divide d_model"
        head_dim = self.head_dim or E // self.n_heads

        def proj(features, axis, name):
            """nn.DenseGeneral, or its int8 twin when weight_quant is on
            (serving decode — ops/quant.py); same name → the quantized
            params from quantize_lm_params land in the same scope."""
            if self.weight_quant == "int8":
                from distributed_machine_learning_tpu.ops.quant import (
                    QuantDenseGeneral,
                )

                feats = features if isinstance(features, tuple) else (features,)
                return QuantDenseGeneral(
                    out_features=feats,
                    n_in_axes=len(axis) if isinstance(axis, tuple) else 1,
                    compute_dtype=self.compute_dtype,
                    name=name,
                )
            return nn.DenseGeneral(
                features=features, axis=axis, dtype=self.compute_dtype,
                name=name,
            )

        if self.n_kv_heads is None or self.n_kv_heads == self.n_heads:
            qkv = proj((3, self.n_heads, head_dim), -1, "qkv")(x)
            q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]  # [B,L,H,Dh]
        else:
            if self.n_heads % self.n_kv_heads:
                raise ValueError(
                    f"n_kv_heads={self.n_kv_heads} must divide "
                    f"n_heads={self.n_heads}"
                )
            q = proj((self.n_heads, head_dim), -1, "q")(x)
            kv = proj((2, self.n_kv_heads, head_dim), -1, "kv")(x)
            k, v = kv[:, :, 0], kv[:, :, 1]  # [B, L, Hkv, Dh]
        q = apply_rope(q, positions, self.rope_base)
        k = apply_rope(k, positions, self.rope_base)
        n_rep = self.n_heads // k.shape[2]
        if self.decode:
            # Cache shape fixes the max sequence length at init time
            # (init runs with a [B, max_len] input — generate.py).  Keys
            # are RoPE-rotated at their absolute position before being
            # written, so cached entries never need re-rotation.
            cache_dtype = self.kv_cache_dtype or k.dtype
            quant_cache = jnp.dtype(cache_dtype) == jnp.int8
            # Head-major cache layout [B, Hkv, S, D]: each head's slots
            # form full (slot, lane) tiles, which is what lets the
            # flash-decode kernel (and the einsum) stream the cache at
            # HBM bandwidth — see _cached_attention's docstring.
            cshape = (k.shape[0], k.shape[2], k.shape[1], k.shape[3])
            ck = self.variable(
                "cache", "cached_key", jnp.zeros, cshape, cache_dtype
            )
            cv = self.variable(
                "cache", "cached_value", jnp.zeros, cshape, cache_dtype
            )
            if quant_cache:
                # int8 KV: one f32 scale per (kv head, slot) beside the
                # int8 rows — written together, folded into the f32
                # score/probability path by the scale-folding einsum
                # (_cached_attention_quant — the measured-fastest int8
                # dispatch at every context; see below).  Cache HBM
                # traffic halves vs bf16; scales are [Hkv, S] floats,
                # noise next to the [Hkv, S, D] rows.
                cks = self.variable(
                    "cache", "cached_key_scale", jnp.zeros, cshape[:3],
                    jnp.float32,
                )
                cvs = self.variable(
                    "cache", "cached_value_scale", jnp.zeros, cshape[:3],
                    jnp.float32,
                )
            if not self.is_initializing():
                # [L] positions: one shared frontier (start scalar).
                # [B, L]: per-ROW frontiers (batched speculative decode)
                # — each row writes its slots at its own offset, via a
                # vmapped slice-update (XLA lowers it to a scatter whose
                # windows are the tiny per-row [Hkv, L, D] fresh K/V —
                # decode-scale, not cache-scale, bytes).
                batched_frontier = positions.ndim == 2
                start = (
                    positions[:, 0] if batched_frontier else positions[0]
                )

                def _write(ref, t, sref=None):
                    t = t.swapaxes(1, 2)  # [B, Hkv, L, D]
                    if quant_cache:
                        amax = jnp.max(
                            jnp.abs(t.astype(jnp.float32)), axis=-1
                        )
                        s = jnp.where(amax > 0, amax / 127.0, 1.0)
                        t = jnp.clip(
                            jnp.round(t.astype(jnp.float32) / s[..., None]),
                            -127, 127,
                        ).astype(jnp.int8)
                        if batched_frontier:
                            sref.value = jax.vmap(
                                lambda c, u, s0: lax.dynamic_update_slice(
                                    c, u, (0, s0)
                                )
                            )(sref.value, s, start)
                        else:
                            sref.value = lax.dynamic_update_slice(
                                sref.value, s, (0, 0, start)
                            )
                    t = t.astype(ref.value.dtype)
                    if batched_frontier:
                        ref.value = jax.vmap(
                            lambda c, u, s0: lax.dynamic_update_slice(
                                c, u, (0, s0, 0)
                            )
                        )(ref.value, t, start)
                    else:
                        ref.value = lax.dynamic_update_slice(
                            ref.value, t, (0, 0, start, 0)
                        )

                _write(ck, k, cks if quant_cache else None)
                _write(cv, v, cvs if quant_cache else None)
                if L > 1 and self.decode_continuation:
                    # Mid-stream multi-token continuation (speculative
                    # decoding's verify pass): the fresh queries attend
                    # the FULL cache — prefix plus the just-written
                    # fresh K/V — causally masked by absolute position.
                    # _cached_attention handles Lq > 1 natively; at the
                    # verify shape (Lq = γ+1, small) the f32 score
                    # tensor is tiny, so no kernel dispatch is needed.
                    if quant_cache:
                        out = _cached_attention_quant(
                            q, ck.value, cks.value, cv.value, cvs.value,
                            positions,
                        )
                    else:
                        out = _cached_attention(
                            q, ck.value, cv.value, positions
                        )
                elif L > 1:
                    # PREFILL (the one multi-token call, at start == 0 —
                    # generate.py's contract; in batched-frontier mode
                    # every row prefills from 0, so row 0's positions
                    # speak for all): the cache was empty, so attention
                    # over the prompt is plain causal self-attention over
                    # the fresh K/V.  Routing it through the training
                    # kernels instead of _cached_attention avoids
                    # materializing the f32 [B, H, L, S] score tensor
                    # against the whole cache (34 GB at an 8k prompt) —
                    # flash when the length qualifies, dense below.
                    if _flash_wins(L):
                        from distributed_machine_learning_tpu.ops.pallas.flash_attention import (  # noqa: E501
                            flash_self_attention,
                        )

                        out = flash_self_attention(q, k, v)
                    else:
                        out = dense_self_attention(
                            q, _repeat_kv(k, n_rep), _repeat_kv(v, n_rep),
                            positions[0] if batched_frontier else positions,
                        )
                else:
                    # Narrow cache straight into GQA-native cached
                    # attention — no repeat, no widened materialization.
                    # Dispatch (not calibrated on v5e: ROADMAP D5):
                    # - int8 caches: ALWAYS the scale-folding einsum
                    #   (_cached_attention_quant) — XLA fuses the s8
                    #   convert into the dot, so HBM reads int8 bytes,
                    #   where the kernel's exact-f32 dequant takes it
                    #   off its DMA-bound point.  The two differ in
                    #   what they read: the einsum reads all S
                    #   ALLOCATED slots, so its cost is flat in the
                    #   fill; the kernel's frontier clamp reads
                    #   O(pos), so its cost grows along the stream and
                    #   it is the cheaper of the two only while pos/S
                    #   is under a break-even fraction of the
                    #   ALLOCATION (_INT8_TIER_BREAK_EVEN_PCT, above).
                    #   Why the einsum is taken at every fill all the
                    #   same: a generation that runs to completion
                    #   starts at the prompt's length Lp and ends at
                    #   the allocation S, so the mean of pos/S over
                    #   ANY full generation is (Lp/S + 1)/2 ≥ 0.5:
                    #   the einsum wins integrated over every
                    #   run-to-completion shape.  The tiered lax.cond
                    #   alternative compiles both paths for every
                    #   serving shape, for a win in the first part of
                    #   the stream only; it is kept available as
                    #   _INT8_TIERED_DISPATCH (above) for the one
                    #   workload that inverts the math: generous
                    #   max_new allocations that usually stop early
                    #   (only tests/test_quant.py turns it on);
                    # - long bf16/f32 caches (≥4k): the flash-decode
                    #   kernel (frontier-clamped O(pos) reads);
                    # - short bf16/f32 caches: the head-major einsum
                    #   (the kernel's per-grid-step overhead loses to
                    #   XLA's single fused op).
                    #   Where "long" starts is decode_flash_qualifies'
                    #   rule (ops/pallas/decode_attention.py); no cell
                    #   decodes yet (ROADMAP X2).
                    from distributed_machine_learning_tpu.ops.pallas.decode_attention import (  # noqa: E501
                        cached_flash_attention,
                        decode_flash_qualifies,
                    )

                    S_alloc = ck.value.shape[2]
                    if quant_cache:
                        if (
                            _INT8_TIERED_DISPATCH
                            and not batched_frontier
                            and decode_flash_qualifies(S_alloc)
                        ):
                            # Runtime two-tier switch: kernel while the
                            # cache is mostly empty, einsum once filled
                            # past the break-even.  Gated off by default
                            # (see _INT8_TIERED_DISPATCH).
                            out = lax.cond(
                                positions[0] * 100
                                < S_alloc * _INT8_TIER_BREAK_EVEN_PCT,
                                lambda q, ki, ks, vi, vs, p:
                                    cached_flash_attention(
                                        q, ki, vi, p[0],
                                        k_scale=ks, v_scale=vs,
                                    ),
                                _cached_attention_quant,
                                q, ck.value, cks.value, cv.value,
                                cvs.value, positions,
                            )
                        else:
                            out = _cached_attention_quant(
                                q, ck.value, cks.value, cv.value,
                                cvs.value, positions,
                            )
                    elif (
                        not batched_frontier
                        and decode_flash_qualifies(S_alloc)
                        and S_alloc >= 4096
                    ):
                        # The flash-decode kernel clamps its DMA at ONE
                        # scalar frontier; per-row frontiers (batched
                        # speculative decode) take the einsum, whose
                        # mask is per-row for free.
                        out = cached_flash_attention(
                            q, ck.value, cv.value, positions[0]
                        )
                    else:
                        out = _cached_attention(
                            q, ck.value, cv.value, positions
                        )
            else:
                # Init-time shape pass (is_initializing): positions may
                # be per-row [B, L] under the batched frontier — row 0
                # speaks for the shapes.
                out = dense_self_attention(
                    q, _repeat_kv(k, n_rep), _repeat_kv(v, n_rep),
                    positions[0] if positions.ndim == 2 else positions,
                )
        elif self.attn_impl == "ring":
            # GQA rotates the NARROW K/V chunks (ICI bytes ÷ the group
            # factor — ring_self_attention widens locally per block).
            out = ring_self_attention(
                q, k, v, self.seq_axis, lax.axis_size(self.seq_axis)
            )
        elif self.attn_impl == "ring_flash":
            from distributed_machine_learning_tpu.ops.pallas.ring_flash_attention import (
                ring_flash_self_attention,
            )

            # GQA rotates the NARROW K/V chunks around the ring (ICI and
            # traveling-gradient traffic shrink by the group factor).
            out = ring_flash_self_attention(
                q, k, v, self.seq_axis, lax.axis_size(self.seq_axis)
            )
        elif self.attn_impl == "ulysses":
            from distributed_machine_learning_tpu.ops.ulysses import (
                ulysses_self_attention,
            )

            # GQA stays NARROW into the all-to-all: when the sequence-
            # axis size divides the KV heads, ulysses packs q (viewed
            # [.., Hkv, rep, D]) with the narrow k/v into ONE collective
            # split on the shared Hkv axis — block alignment by
            # construction, ICI bytes ÷ the group factor; widening
            # happens after the re-shard, or not at all on the flash
            # path (the kernel is GQA-native).
            out = ulysses_self_attention(
                q, k, v, self.seq_axis, lax.axis_size(self.seq_axis)
            )
        elif self.attn_impl == "flash" or (
            self.attn_impl == "auto" and _flash_wins(L)
        ):
            from distributed_machine_learning_tpu.ops.pallas.flash_attention import (
                flash_self_attention,
            )

            # GQA stays narrow: the kernel's K/V index maps divide by the
            # group factor, so no repeated K/V ever hits HBM.
            if self.flash_mesh is not None:
                # Inside a GSPMD-partitioned step (fsdp_pl / EP / TP)
                # the Mosaic custom call has no sharding rules — so run
                # it under a FULLY-manual shard_map over the whole mesh:
                # the kernel then sees LOCAL per-device shapes and never
                # meets the partitioner on ANY axis.  The batch dim
                # shards over flash_batch_axis and (under TP) the head
                # dim over flash_head_axis; activations are replicated
                # over every remaining mesh axis (e.g. EP's expert
                # axis), which the unmentioned-axis convention expresses
                # as-is.  (Manual over a subset of axes would leave the
                # custom call under automatic propagation for the rest —
                # the hazard this wrap exists to remove.)
                from jax.sharding import PartitionSpec as _P

                from distributed_machine_learning_tpu.runtime.mesh import (
                    shard_map_no_check,
                )

                spec = _P(self.flash_batch_axis, None,
                          self.flash_head_axis, None)
                # Nested inside another shard_map (the 3-D step's
                # pipe-manual region), jax requires the CONTEXT abstract
                # mesh — whose axis types record what is already manual
                # — rather than the all-Auto concrete mesh.
                ctx_mesh = jax.sharding.get_abstract_mesh()
                wrap_mesh = (ctx_mesh if getattr(ctx_mesh, "axis_names", ())
                             else self.flash_mesh)
                out = shard_map_no_check(
                    flash_self_attention,
                    mesh=wrap_mesh,
                    in_specs=(spec, spec, spec),
                    out_specs=spec,
                    manual_axes=self.flash_manual_axes,
                )(q, k, v)
            else:
                out = flash_self_attention(q, k, v)
        else:
            out = dense_self_attention(
                q, _repeat_kv(k, n_rep), _repeat_kv(v, n_rep), positions
            )
        y = proj(E, (-2, -1), "out")(out)
        if self.tp_axis is not None:
            y = lax.psum(y, self.tp_axis)
        return y


def _mlp_sublayer(mdl: "Block", h: jax.Array) -> jax.Array:
    """LN2 + feed-forward sub-layer of a Block (residual added by the
    caller).  A module-level function (first arg = the Block) so it can be
    lifted through ``nn.remat`` for the selective-remat policy without
    changing the parameter tree: the same ``ln2``/``fc_in``/``fc_out``
    names land in the same scope whether or not the wrap is applied, so
    checkpoints are layout-compatible across remat policies."""
    d_out = h.shape[-1]
    h = nn.LayerNorm(epsilon=mdl.ln_eps, dtype=mdl.compute_dtype,
                     name="ln2")(h)
    if mdl.mlp_factory is not None:
        return mdl.mlp_factory()(h)
    if mdl.weight_quant == "int8":
        from distributed_machine_learning_tpu.ops.quant import (
            QuantDenseGeneral,
        )

        h = QuantDenseGeneral(
            out_features=(mdl.d_ff,), compute_dtype=mdl.compute_dtype,
            name="fc_in",
        )(h)
        h = nn.gelu(h)
        h = QuantDenseGeneral(
            out_features=(d_out,),
            compute_dtype=mdl.compute_dtype, name="fc_out",
        )(h)
    else:
        h = nn.Dense(mdl.d_ff, dtype=mdl.compute_dtype, name="fc_in")(h)
        h = nn.gelu(h)
        h = nn.Dense(d_out, dtype=mdl.compute_dtype, name="fc_out")(h)
    if mdl.tp_axis is not None:
        # Manual TP decode: fc_in is column-parallel (local d_ff slice),
        # fc_out row-parallel — this psum is Megatron's second
        # g-collective (fc_out's bias pre-divided by tp, as for the
        # attention out-projection).
        h = jax.lax.psum(h, mdl.tp_axis)
    return h


def whole_block_policy():
    """What whole-block recomputation keeps beside the block's input: the
    values whose remaking costs most a byte, each a ``checkpoint_name`` tag
    (listed below), so that the backward pass makes the rest of the
    block again around them:

    - the flash kernel's three inputs as it takes them (normed, rotated,
      folded to ``[B·H, L, D]``, K/V unrepeated) and its two outputs (``out``
      bf16 ``[B·H, L, d_v]``, the float32 ``lse`` ``[B·H, 1, L]``): no O(L²)
      forward kernel, projection, rotation or fold runs a second time;
    - the attention layer's and, under a sandwich norm, the feed-forward's
      outputs ``[B, L, D]``, which the norms after them read;
    - the grouped expert layer's routing (the router's float32 logits, a
      selection bias's chosen experts, the sort's integers, the rows'
      weights), its dispatched rows ``[rows, D]`` and its two
      pre-activations ``[rows, F]``: no sort, gather or up-projection runs
      again, the down-projection does (the combine's reverse reads its
      output);
    - a gated MLP's two pre-activations (the shared expert's, the dense
      layer's).

    A tag exists only where what it names runs — the flash names on the
    flash path, the expert names on ``grouped_expert_mlp``'s — so which of
    them a block keeps follows from what the block runs; a block with none
    keeps its input alone.  What it costs in bytes a layer: ``Block``'s
    docstring; what a step keeps, by tag: the ``remat_kept_bytes`` gauge
    (:func:`kept_bytes`)."""
    from distributed_machine_learning_tpu.models.hybrid_moe import (
        MLP_NAMES,
        SUBLAYER_OUT_NAMES,
    )
    from distributed_machine_learning_tpu.ops.grouped import (
        EXPERT_ROUTING_NAMES,
        EXPERT_ROW_NAMES,
        ROUTE_NAMES,
    )
    from distributed_machine_learning_tpu.ops.pallas.flash_attention import (
        FLASH_INPUT_NAMES,
        FLASH_RESIDUAL_NAMES,
    )

    return jax.checkpoint_policies.save_only_these_names(
        *FLASH_INPUT_NAMES, *FLASH_RESIDUAL_NAMES, *SUBLAYER_OUT_NAMES,
        *ROUTE_NAMES, *EXPERT_ROUTING_NAMES, *EXPERT_ROW_NAMES, *MLP_NAMES)


#: What carries a tag on to its result where its one tagged operand is
#: made into a residual: a SiLU's reverse reads ``σ(g)`` made in a jitted
#: function of the tagged ``g``, a saved float passes ``reduce_precision``.
_TAG_CARRIERS = ("name", "reduce_precision", "convert_element_type", "pjit",
                 "jit")


def kept_bytes(jaxpr) -> dict:
    """``{tag: bytes}`` that a differentiated program's recomputed blocks
    keep beside their inputs, from shapes: every operand of a
    differentiated checkpoint (the backward pass's ``remat2``) that carries
    a ``checkpoint_name`` tag, itself or as what a cast, ``reduce_precision``
    or a jitted function made of one tagged value, summed over the program
    (``jaxpr``: a step's, closed or not; its sub-programs are walked)."""
    totals: dict = {}

    def walk(jaxpr):
        tags: dict = {}
        for eqn in jaxpr.eqns:
            operands = {tags[v] for v in eqn.invars
                        if not hasattr(v, "val") and v in tags}
            if eqn.primitive.name == "name":
                operands = {eqn.params["name"]}
            if eqn.primitive.name == "remat2" and eqn.params["differentiated"]:
                for v in {v for v in eqn.invars if not hasattr(v, "val")}:
                    if v in tags:
                        totals[tags[v]] = totals.get(tags[v], 0) + (
                            v.aval.size * v.aval.dtype.itemsize)
            elif eqn.primitive.name in _TAG_CARRIERS and len(operands) == 1:
                tags.update(dict.fromkeys(eqn.outvars, *operands))
            for value in eqn.params.values():
                for sub in (value if isinstance(value, (tuple, list))
                            else [value]):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        walk(sub)

    walk(getattr(jaxpr, "jaxpr", jaxpr))
    return totals


def remat_whole_block(block_cls):
    """``remat_policy="block"`` of every LM here: ``block_cls`` lifted
    through ``nn.remat`` under :func:`whole_block_policy`."""
    return nn.remat(block_cls, policy=whole_block_policy())


class Block(nn.Module):
    """Pre-LN transformer block.  ``mlp_factory`` swaps the feed-forward
    sub-layer (e.g. for a routed MoE MLP — ``models/moe.py``) while the
    residual/LN/attention wiring stays in one place.

    ``remat_mlp=True`` is the SELECTIVE remat policy: only the LN2+MLP
    sub-layer is checkpointed; the attention path's residuals — including
    the flash kernel's saved ``(out, lse)`` (O(L·D), cheap) — stay
    resident, so the backward pass never re-runs the O(L²) attention
    forward, at ~6·L·E saved activation bytes per layer.  Whole-block
    remat (``remat_whole_block``) makes everything of the block again in
    backward EXCEPT what :func:`whole_block_policy` keeps; here (no tagged
    output, no expert layer) the flash kernel's folded q, k, v and its
    ``(out, lse)``: the norms, the output projection and the MLP run a
    second time (O(L) each), the flash forward kernel, the rotation and the
    folds do not, at ``L·(E + (2·H + 2·H_kv)·d)·2 + 4·H·L`` saved bytes
    per layer where a block that kept its input alone held ``L·E·2``
    (StarCoder2-3B at 4096 tokens: 76 MiB where 24).  In the MoE models'
    blocks the kept set is wider (Trinity-Mini at 16 384 tokens: 866 MiB a
    dense layer, 812 a sparse one, where the input alone is 64 and the
    kernel's pair with it 194).  On the dense attention path no flash
    value is tagged."""

    n_heads: int
    d_ff: int
    attn_impl: str
    seq_axis: str
    compute_dtype: Any
    mlp_factory: Any = None  # () -> nn.Module, or None for the dense MLP
    decode: bool = False
    n_kv_heads: int | None = None
    kv_cache_dtype: Any = None
    flash_mesh: Any = None
    flash_batch_axis: str = "batch"
    flash_head_axis: str | None = None
    flash_manual_axes: tuple | None = None
    weight_quant: str | None = None
    remat_mlp: bool = False
    tp_axis: str | None = None  # manual TP decode (see Attention.tp_axis)
    head_dim: int | None = None  # explicit head width (TP decode clones)
    decode_continuation: bool = False  # verify-pass decode (speculative)
    rope_base: float = 10000.0
    ln_eps: float = 1e-6

    @nn.compact
    def __call__(self, x, positions):
        h = nn.LayerNorm(epsilon=self.ln_eps, dtype=self.compute_dtype,
                         name="ln1")(x)
        x = x + Attention(
            n_heads=self.n_heads,
            attn_impl=self.attn_impl,
            seq_axis=self.seq_axis,
            compute_dtype=self.compute_dtype,
            decode=self.decode,
            n_kv_heads=self.n_kv_heads,
            kv_cache_dtype=self.kv_cache_dtype,
            flash_mesh=self.flash_mesh,
            flash_batch_axis=self.flash_batch_axis,
            flash_head_axis=self.flash_head_axis,
            flash_manual_axes=self.flash_manual_axes,
            weight_quant=self.weight_quant,
            tp_axis=self.tp_axis,
            head_dim=self.head_dim,
            decode_continuation=self.decode_continuation,
            rope_base=self.rope_base,
            name="attn",
        )(h, positions)
        if self.remat_mlp and not self.decode:
            return x + nn.remat(_mlp_sublayer)(self, x)
        return x + _mlp_sublayer(self, x)


class TransformerLM(nn.Module):
    """Causal LM: tokens [B, L(local)] → logits [B, L(local), vocab].

    With ``attn_impl="ring"`` or ``"ulysses"`` (the two sequence-sharded
    context-parallel schemes — ppermute K/V rotation vs all-to-all head
    re-sharding) the module must run inside ``shard_map`` with ``seq_axis``
    bound; it derives its global position offset from ``lax.axis_index`` so
    sequence-sharded and unsharded runs produce identical logits.
    """

    vocab_size: int
    d_model: int = 256
    n_layers: int = 4
    n_heads: int = 8
    d_ff: int | None = None
    attn_impl: str = "dense"
    seq_axis: str = "seq"
    compute_dtype: Any = jnp.float32
    decode: bool = False
    # GQA: n_kv_heads < n_heads shares each K/V head across a group of
    # query heads (1 = MQA) — the decode KV cache shrinks by the group
    # factor.  None = classic MHA (fused qkv param layout).
    n_kv_heads: int | None = None
    # Decode KV-cache storage dtype (None = compute dtype); see
    # ``Attention.kv_cache_dtype``.
    kv_cache_dtype: Any = None
    # Flash-under-GSPMD composition; see ``Attention.flash_mesh``.
    flash_mesh: Any = None
    flash_batch_axis: str = "batch"
    flash_head_axis: str | None = None
    flash_manual_axes: tuple | None = None
    # "int8" = weight-only quantized serving (decode mode only): every
    # kernel-bearing projection reads int8 weights through the Pallas
    # kernel (ops/quant.py; params from quantize_lm_params).  Embeddings
    # stay full precision (a gather).
    weight_quant: str | None = None
    # Manual Megatron TP for DECODE: set by make_tp_generate_fn's
    # shard_map wrap, with the model configured at LOCAL width
    # (n_heads=H/tp, n_kv_heads=Hkv/tp, d_ff=F/tp, head_dim pinned to
    # the global per-head width).  Embed + lm_head + LayerNorms stay
    # replicated (the embed gather reads only B rows per step; sharding
    # lm_head would shard the logits).  Decode-only.
    tp_axis: str | None = None
    head_dim: int | None = None
    # Multi-token decode applies attend the full cache (speculative
    # decoding's verify pass — inference/speculative.py) instead of
    # assuming the start-0 prefill contract.
    decode_continuation: bool = False
    # Per-ROW cache frontiers for decode: the ``idx`` cache variable is
    # [B] instead of a scalar, positions are [B, L], and each row's K/V
    # land at its own offset.  Batched speculative decoding needs this
    # (acceptance length is data-dependent PER ROW); plain generate
    # keeps the scalar (every row's stream has one length).  Prefill
    # must still start every row at 0.
    decode_batched_frontier: bool = False
    remat: bool = False  # jax.checkpoint each block: activation memory
    # drops from O(L·E) per layer to per-block boundaries, recomputing the
    # block in backward — the HBM-for-FLOPs trade that lets long-context
    # (ring/ulysses) runs fit; FLOPs +~33%, memory ÷ ~n_layers.
    # Which remat policy `remat=True` applies:
    #   "mlp" (default)  — selective: checkpoint only the LN2+MLP
    #     sub-layer; attention residuals (incl. the flash kernel's
    #     out+lse) stay saved, so backward never re-runs the O(L²)
    #     attention forward.  ~6·L·E saved bytes/layer.
    #   "block" — whole-block jax.checkpoint (the maximal-savings
    #     fallback): everything of the block is made again in backward
    #     except the flash kernel's out+lse, kept beside the block's
    #     input (~2·L·E bytes/layer with the kernel, ~1·L·E on the dense
    #     path: whole_block_policy).  Use when "mlp" does not fit.
    remat_policy: str = "mlp"
    # What a configuration file states beside the sizes (cli.lm
    # --model-config: rope_theta, norm_epsilon); the defaults are the
    # values the flags-only path has always run.
    rope_base: float = 10000.0
    ln_eps: float = 1e-6

    @nn.compact
    def __call__(self, tokens, *, train: bool = False,
                 return_hidden: bool = False):
        """``return_hidden=True`` returns the post-``ln_f`` hidden states
        [B, L, E] instead of logits, skipping the ``lm_head`` projection —
        the entry point for the fused head+loss (``ops/fused_ce.py``),
        which never materializes [B, L, vocab]."""
        del train  # no dropout/BN — kept for the shared train-step interface
        B, L = tokens.shape
        if self.weight_quant is not None and not self.decode:
            raise ValueError(
                "weight_quant is a serving-decode feature (int8 weights "
                "are not trainable); clone with decode=True — "
                "inference/generate.py does this"
            )
        if self.tp_axis is not None and not self.decode:
            raise ValueError(
                "tp_axis is the manual TP-decode wiring "
                "(make_tp_generate_fn); training-time TP is the GSPMD "
                "step (parallel/tensor_parallel.py)"
            )
        if self.decode:
            if self.attn_impl != "dense":
                raise ValueError(
                    "decode mode runs dense cached attention; clone the "
                    'model with attn_impl="dense" (generate.py does this)'
                )
            # Autoregressive position tracking: one counter for the whole
            # stack (every layer sees the same absolute positions) — or
            # one PER ROW under decode_batched_frontier (batched
            # speculative decoding: rows commit different lengths).
            if self.decode_batched_frontier:
                idx = self.variable(
                    "cache", "idx", lambda: jnp.zeros((B,), jnp.int32)
                )
                start = idx.value  # [B]
                positions = start[:, None] + jnp.arange(L)[None, :]
            else:
                idx = self.variable(
                    "cache", "idx", lambda: jnp.zeros((), jnp.int32)
                )
                start = idx.value
                positions = start + jnp.arange(L)
            if not self.is_initializing():
                idx.value = start + L
        else:
            if self.attn_impl in ("ring", "ring_flash", "ulysses"):
                offset = lax.axis_index(self.seq_axis) * L
            else:
                offset = 0
            positions = offset + jnp.arange(L)
        x = nn.Embed(
            self.vocab_size, self.d_model, dtype=self.compute_dtype, name="embed"
        )(tokens)
        d_ff = self.d_ff or 4 * self.d_model
        # nn.remat must see concrete (non-decode) blocks: the decode path
        # mutates cache variables, which checkpointing cannot replay.
        if self.remat_policy not in ("mlp", "block"):
            raise ValueError(
                f"remat_policy must be 'mlp' or 'block', got "
                f"{self.remat_policy!r}"
            )
        rematting = self.remat and not self.decode
        whole_block = rematting and self.remat_policy == "block"
        block_cls = remat_whole_block(Block) if whole_block else Block
        remat_mlp = rematting and self.remat_policy == "mlp"
        for i in range(self.n_layers):
            x = block_cls(
                n_heads=self.n_heads,
                d_ff=d_ff,
                attn_impl=self.attn_impl,
                seq_axis=self.seq_axis,
                compute_dtype=self.compute_dtype,
                decode=self.decode,
                n_kv_heads=self.n_kv_heads,
                kv_cache_dtype=self.kv_cache_dtype,
                flash_mesh=self.flash_mesh,
                flash_batch_axis=self.flash_batch_axis,
                flash_head_axis=self.flash_head_axis,
                flash_manual_axes=self.flash_manual_axes,
                weight_quant=self.weight_quant,
                remat_mlp=remat_mlp,
                tp_axis=self.tp_axis,
                head_dim=self.head_dim,
                decode_continuation=self.decode_continuation,
                rope_base=self.rope_base,
                ln_eps=self.ln_eps,
                name=f"block_{i}",
            )(x, positions)
        x = nn.LayerNorm(epsilon=self.ln_eps, dtype=self.compute_dtype,
                         name="ln_f")(x)
        if return_hidden:
            return x
        if self.weight_quant == "int8":
            from distributed_machine_learning_tpu.ops.quant import (
                QuantDenseGeneral,
            )

            logits = QuantDenseGeneral(
                out_features=(self.vocab_size,),
                compute_dtype=self.compute_dtype, name="lm_head",
            )(x)
        else:
            logits = nn.Dense(
                self.vocab_size, dtype=self.compute_dtype, name="lm_head"
            )(x)
        return logits.astype(jnp.float32)
