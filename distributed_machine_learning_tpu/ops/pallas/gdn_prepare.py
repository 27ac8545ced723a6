"""The gated delta rule's chunk-local preparation as Pallas TPU kernels.

Everything ``ops/delta_rule.py`` computes before its state pass is local to
one chunk of one head: from ``[C, d]`` tiles of ``q, k, v`` and ``C``
numbers of ``g, β`` it makes ``W, U, attn, q_in, k_out, d`` (that module's
docstring).  As XLA operations batched over all chunks every intermediate —
the ``[C, C]`` decay, the triangular system, its right-hand side and its
solution, the float32 copies of ``k`` and ``v`` — goes to HBM in float32
and comes back, in the forward pass, again where the backward pass makes the
preparation again, and once more transposed.  Here a grid step loads the
tiles of a block of heads once, and everything in between stays in VMEM.

Two kernels, nothing carried between grid steps:

- ``gdn_prepare_fwd`` (:func:`prepare_fwd`): the six outputs, in
  ``gdn_state``'s ``[nc, BH, rows, cols]`` layout.
- ``gdn_prepare_bwd`` (:func:`prepare_bwd`): from the five inputs and the
  six cotangents, the cotangents of the inputs.  It makes the forward
  quantities again (nothing but the inputs is kept) and never
  differentiates through the inversion: with ``T = (I + A)⁻¹``, ``W = T kb``
  and ``U = T vb``, ``dkb = Tᵀ dW``, ``dvb = Tᵀ dU`` and ``dA = −strict(dkb
  Wᵀ + dvb Uᵀ)``.

**The inverse** (:func:`unit_lower_inverse`).  ``jax.scipy``'s
``solve_triangular`` has no Mosaic lowering, and two obvious forms fail:
the nilpotent doubling ``(I − A)(I + A²)(I + A⁴)…`` cancels (for equal keys
and ``β → 1`` the powers reach ``C(62, 31)``), and 64 rows of substitution
are 64 dependent steps.  So: substitution inside the four 16 × 16 diagonal
blocks, all four at once (15 steps on the vector unit; unrolled: as a
``fori_loop`` a forward call took 4.0 ms for 2.9), then the off-diagonal
blocks as matmuls, ``T₂₁ = −T₂₂ A₂₁ T₁₁``, 16 → 32 → 64 — as stable as
substitution (the test's conditioning case), four matmuls deep.  A chunk of
any other size works the same (the last block is what is left).

**The decay.**  ``exp(γ_i − γ_j)`` is as good as the difference, and a
float32 ``γ = cumsum(g)`` that has run to the hundreds (a decay that
vanishes) leaves 1e-5 of it.  ``γ`` is summed in two parts, ``g``'s
multiples of 2⁻¹⁰ (exactly) and the rest (small), so the difference keeps
float32's relative precision (``_local``).

The system, its inverse and the products with it are float32
(``precision=HIGHEST``: Mosaic then contracts in float32 rather than in one
bf16 pass); the score products take their operands in the inputs' dtype and
accumulate in float32, and ``W``, ``attn``, ``q_in``, ``k_out`` round to the
inputs' dtype, as ``delta_rule._prepare`` does — the ``solve_triangular``
form, which stays in the tree as what the tests compare with.

The arithmetic of one head's one chunk is :func:`prepare_chunk` /
:func:`prepare_chunk_bwd`, plain functions of arrays, which the kernels map
over the heads of a block with ``jax.vmap`` and ``ops/delta_rule.py`` maps
over chunks, batch and heads where no kernel runs (anywhere but on a TPU),
so both round at the same places.  ``g`` and ``β`` come as rows,
``[nc, BH, 1, C]``; a number a row (``γ_i``, ``β_i``) is a ``[C, 1]`` column
made from the row with ``broadcasted_iota`` masks (``jnp.cumsum`` has no
Mosaic lowering either, and a transpose of a 64-wide tile is not one to
count on).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from distributed_machine_learning_tpu.ops.pallas.common import (
    NN,
    NT,
    TN,
    dot,
    interpret,
    pick_block,
    pltpu,
    tile_compiler_params,
)

#: Rows of a diagonal block that substitution inverts; the chunk is four.
BASE = 16
#: Heads a grid step.  What a step keeps in VMEM is mostly the values between
#: its loads and stores, every head's at once under ``jax.vmap``: the reverse
#: at 16 heads asks for 20.8 MiB where the default scoped limit is 16.
FWD_HEAD_BLOCK = 16
BWD_HEAD_BLOCK = 8


def _dot32(a, b, dims):
    """A float32 product of float32 operands (Mosaic's default for them is
    one bf16 pass)."""
    f32 = jnp.float32
    return lax.dot_general(a.astype(f32), b.astype(f32), dims,
                           precision=lax.Precision.HIGHEST,
                           preferred_element_type=f32)


def _iotas(C):
    return (lax.broadcasted_iota(jnp.int32, (C, C), 0),
            lax.broadcasted_iota(jnp.int32, (C, C), 1))


def _column(row_vector):
    """A ``[1, C]`` row as the ``[C, 1]`` column of the same numbers."""
    C = row_vector.shape[-1]
    i, j = _iotas(C)
    return jnp.sum(jnp.where(i == j, row_vector, 0.0), axis=1, keepdims=True)


def _row(column):
    """A ``[C, 1]`` column as the ``[1, C]`` row of the same numbers."""
    i, j = _iotas(column.shape[0])
    return jnp.sum(jnp.where(i == j, column, 0.0), axis=0, keepdims=True)


def unit_lower_inverse(A, At, base=BASE):
    """``(I + A)⁻¹`` for a strictly lower triangular float32 ``A`` of ``[C,
    C]``; ``At`` is its transpose (the caller has it for nothing: ``k kᵀ``
    is symmetric).  Any ``C``: the last diagonal block is what is left."""
    C = A.shape[0]
    i, j = _iotas(C)
    same = (i // base) == (j // base)
    # X = T − I inside the diagonal blocks.  Row r of a block solves
    # X[r] = −A[r] − Σ_{c<r} A[r, c] X[c]: rows 0 … r−1 are final when row r
    # is made, and row r of every block is made in the same step.
    X = jnp.where(same, -A, 0.0)
    At_blocks = jnp.where(same, At, 0.0)
    block_row, block_col = (i // base) * base, (j // base) * base

    def substitute(r, X):
        # A[r of its block, c] for the row c of X it multiplies: a column.
        coef = jnp.sum(jnp.where(j == block_row + r, At_blocks, 0.0),
                       axis=1, keepdims=True)
        update = jnp.sum(coef * X, axis=0, keepdims=True)
        return X - jnp.where(i == block_col + r, update, 0.0)

    for r in range(1, min(base, C)):
        X = substitute(r, X)
    T = X + jnp.where(i == j, 1.0, 0.0)
    # Two neighbouring inverted blocks and what lies below the first and
    # left of the second: T₂₁ = −T₂₂ A₂₁ T₁₁, every pair in one product.
    size = base
    while size < C:
        inside = (i // (2 * size)) == (j // (2 * size))
        done = (i // size) == (j // size)
        below = jnp.where(inside & ~done, A, 0.0)
        T = T - _dot32(T, _dot32(below, T, NN), NN)
        size *= 2
    return T


def _local(q, k, g, beta):
    """What both directions need of a chunk before its inverse: masks,
    ``γ`` and ``β`` as columns, the decay and the score products."""
    f32 = jnp.float32
    C = q.shape[0]
    i, j = _iotas(C)
    lower = j <= i
    g = g.astype(f32)
    # γ = cumsum(g) in two parts.  Where the decay vanishes γ is in the
    # hundreds and a float32 γ_i − γ_j is off by 1e-5 of itself, which is
    # the error of exp(γ_i − γ_j) between neighbouring steps, the only ones
    # that still count.  g's multiples of 2⁻¹⁰ add exactly (to 2¹⁴ in 24
    # bits) and what is left of g stays under 2⁻⁴ in sum, so the difference
    # of two γ is right to float32's last place.
    coarse = (g * 1024.0).astype(jnp.int32).astype(f32) / 1024.0
    parts = (coarse, g - coarse)
    columns = [jnp.sum(jnp.where(lower, part, 0.0), axis=1, keepdims=True)
               for part in parts]                        # [C, 1] each
    # γ_C from the row itself: the last row of a column, broadcast along the
    # lanes, is a layout Mosaic fails on.
    ends = [jnp.sum(part, axis=1, keepdims=True) for part in parts]  # [1, 1]
    gamma, gamma_end = sum(columns), sum(ends)
    # γ_i − γ_j, and exp of it for j <= i, masked before the exponential:
    # above the diagonal the difference is positive and may overflow.
    diff = sum(column - _row(column) for column in columns)
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, diff, 0.0)), 0.0)
    return dict(
        strict=j < i, upper=j > i, diff=diff, beta=_column(beta.astype(f32)),
        decay=decay, KK=dot(k, k, NT), QK=dot(q, k, NT),
        e=jnp.exp(gamma), gamma_end=gamma_end,
        e_out=jnp.exp(sum(end - column
                          for end, column in zip(ends, columns))))


def _solved(k, v, c, base):
    """``T`` and the float32 ``W = T (β k exp(γ))``, ``U = T (β v)`` of a
    chunk."""
    f32 = jnp.float32
    A = jnp.where(c["strict"], c["beta"] * c["KK"] * c["decay"], 0.0)
    # (β_i KK_ij D_ij)ᵀ at [i, j] is β_j KK_ij D_ji: D's transpose is
    # exp(γ_j − γ_i) above the diagonal, made there like D below it.
    upper = c["upper"]
    decay_t = jnp.where(upper, jnp.exp(jnp.where(upper, -c["diff"], 0.0)), 0.0)
    T = unit_lower_inverse(A, _row(c["beta"]) * c["KK"] * decay_t, base)
    kb = c["beta"] * k.astype(f32) * c["e"]
    vb = c["beta"] * v.astype(f32)
    return T, _dot32(T, kb, NN), _dot32(T, vb, NN)


def prepare_chunk(q, k, v, g, beta, base=BASE):
    """One head, one chunk.  ``q``, ``k`` [C, dk], ``v`` [C, dv] in the
    operands' dtype; ``g``, ``beta`` [1, C].  Returns ``W`` [C, dk], ``U``
    float32 [C, dv], ``attn`` [C, C], ``q_in``, ``k_out`` [C, dk] and ``d``
    float32 [1, dv] of ``ops/delta_rule.py``'s docstring."""
    f32, dt = jnp.float32, v.dtype
    c = _local(q, k, g, beta)
    _, W, U = _solved(k, v, c, base)
    attn = (c["QK"] * c["decay"]).astype(dt)
    q_in = (q.astype(f32) * c["e"]).astype(dt)
    k_out = (k.astype(f32) * c["e_out"]).astype(dt)
    d = jnp.exp(jnp.broadcast_to(c["gamma_end"], (1, v.shape[-1])))
    return W.astype(dt), U, attn, q_in, k_out, d


def prepare_chunk_bwd(q, k, v, g, beta, dW, dU, dattn, dq_in, dk_out, dd,
                      base=BASE):
    """One head, one chunk, backwards: the cotangents of ``q, k, v`` (the
    operands' dtype) and of ``g, beta`` (float32 [1, C]) from those of
    :func:`prepare_chunk`'s six results."""
    f32, dt = jnp.float32, v.dtype
    C = q.shape[0]
    c = _local(q, k, g, beta)
    T, W, U = _solved(k, v, c, base)
    beta_c, decay, KK, QK, e, e_out = (
        c[n] for n in ("beta", "decay", "KK", "QK", "e", "e_out"))
    qf, kf = q.astype(f32), k.astype(f32)
    dattn, dq_in, dk_out = (a.astype(f32) for a in (dattn, dq_in, dk_out))

    dkb = _dot32(T, dW, TN)
    dvb = _dot32(T, dU, TN)
    # dM = −Tᵀ dT Tᵀ with dT = dW kbᵀ + dU vbᵀ: never through the inverse.
    dA = jnp.where(c["strict"],
                   -(_dot32(dkb, W, NT) + _dot32(dvb, U, NT)), 0.0)
    dKK = dA * beta_c * decay
    dQK = dattn * decay
    E = (dattn * QK + dA * beta_c * KK) * decay          # dD ∘ D

    # QK = q kᵀ and KK = k kᵀ: dQK k, dQKᵀ q and (dKK + dKKᵀ) k, the two
    # score cotangents stacked so that each side is one product.
    both = jnp.concatenate([dQK, dKK], axis=0)           # [2C, C]
    by_k = _dot32(both, kf, NN)                         # dQK k over dKK k
    dq = by_k[:C] + dq_in * e
    dk = (by_k[C:] + _dot32(both, jnp.concatenate([qf, kf], axis=0), TN)
          + dkb * beta_c * e + dk_out * e_out)
    dv = beta_c * dvb

    rows = lambda a: jnp.sum(a, axis=1, keepdims=True)   # [C, ·] -> [C, 1]
    dbeta = (rows(dA * KK * decay) + rows(dkb * e * kf)
             + rows(dvb * v.astype(f32)))
    # Two terms of dγ cancel exactly on paper, D's diagonal (γ_i − γ_i) and
    # the last row's exp(γ_C − γ_C), and are O(1) where the rest vanishes
    # with the decay: left in, their rounding is the error of dg there.
    last = lax.broadcasted_iota(jnp.int32, (C, 1), 0) == C - 1
    out_term = jnp.where(last, 0.0, rows(dk_out * kf) * e_out)
    # γ_C is the last row's: what came through exp(γ_C − γ) and d.
    at_end = (jnp.sum(out_term, axis=0, keepdims=True)
              + jnp.sum(dd * jnp.exp(c["gamma_end"]), axis=1, keepdims=True))
    E = jnp.where(c["strict"], E, 0.0)
    dgamma = (rows(E) - _column(jnp.sum(E, axis=0, keepdims=True))
              + rows(dq_in * qf) * e + rows(dkb * kf) * beta_c * e - out_term
              + jnp.where(last, at_end, 0.0))
    i, j = _iotas(C)
    # γ = cumsum(g): dg_t = Σ_{i >= t} dγ_i, as a row.
    dg = jnp.sum(jnp.where(i >= j, dgamma, 0.0), axis=0, keepdims=True)
    return dq.astype(dt), dk.astype(dt), dv.astype(dt), dg, _row(dbeta)


def _kernel(chunk_fn, n_in, *refs):
    """``chunk_fn`` over the heads of a block: ``refs`` are its ``n_in``
    inputs, then its results."""
    results = jax.vmap(chunk_fn)(*(r[...] for r in refs[:n_in]))
    for ref, result in zip(refs[n_in:], results):
        ref[...] = result


def _call(chunk_fn, name, inputs, outputs, head_block):
    """``pallas_call`` of ``chunk_fn`` over (head blocks, chunks), both
    parallel: every array is ``[nc, BH, rows, cols]`` and a grid step sees
    ``[heads, rows, cols]`` of one chunk."""
    nc, BH = inputs[0].shape[:2]
    heads = pick_block(BH, head_block, 1)
    spec = lambda a: pl.BlockSpec((None, heads, *a.shape[2:]),
                                  lambda h, c: (c, h, 0, 0),
                                  memory_space=pltpu.VMEM)
    return tuple(pl.pallas_call(
        partial(_kernel, chunk_fn, len(inputs)),
        out_shape=outputs,
        grid=(BH // heads, nc),
        in_specs=[spec(a) for a in inputs],
        out_specs=[spec(a) for a in outputs],
        interpret=interpret(),
        name=name,
        **tile_compiler_params(("parallel", "parallel")),
    )(*inputs))


def prepare_fwd(q, k, v, g, beta):
    """The preparation of every chunk.  ``q``, ``k`` [nc, BH, C, dk], ``v``
    [nc, BH, C, dv]; ``g``, ``beta`` float32 [nc, BH, 1, C].  Returns ``W,
    U, attn, q_in, k_out, d`` as ``gdn_state.state_fwd`` takes them."""
    return _prepare_fwd(q, k, v, g, beta, interpreted=interpret(),
                        head_block=FWD_HEAD_BLOCK, base=BASE)


def prepare_bwd(q, k, v, g, beta, dW, dU, dattn, dq_in, dk_out, dd):
    """The cotangents of ``q, k, v, g, beta`` (the last two float32 [nc, BH,
    1, C]) from those of :func:`prepare_fwd`'s six results."""
    return _prepare_bwd(q, k, v, g, beta, dW, dU, dattn, dq_in, dk_out, dd,
                        interpreted=interpret(), head_block=BWD_HEAD_BLOCK,
                        base=BASE)


# Jitted, so that a step's calls — two and one a layer, in every program a
# process traces — share one trace and one lowering of the kernel's body: a
# kernel's body is hundreds of operations, and a process paid 30 s of set-up
# for tracing it nine times a program.  The static arguments are all the
# trace depends on besides the shapes (``interpreted`` only keys the cache).
@partial(jax.jit, static_argnames=("interpreted", "head_block", "base"))
def _prepare_fwd(q, k, v, g, beta, *, interpreted, head_block, base):
    nc, BH, C, dk = q.shape
    dv, dt = v.shape[-1], v.dtype
    shape = lambda rows, cols, dtype: jax.ShapeDtypeStruct(
        (nc, BH, rows, cols), dtype)
    outputs = [shape(C, dk, dt), shape(C, dv, jnp.float32), shape(C, C, dt),
               shape(C, dk, dt), shape(C, dk, dt), shape(1, dv, jnp.float32)]
    return _call(partial(prepare_chunk, base=base), "gdn_prepare_fwd",
                 (q, k, v, g, beta), outputs, head_block)


@partial(jax.jit, static_argnames=("interpreted", "head_block", "base"))
def _prepare_bwd(q, k, v, g, beta, dW, dU, dattn, dq_in, dk_out, dd, *,
                 interpreted, head_block, base):
    like = lambda a, dtype=None: jax.ShapeDtypeStruct(a.shape,
                                                     dtype or a.dtype)
    outputs = [like(q), like(k), like(v), like(g, jnp.float32),
               like(beta, jnp.float32)]
    return _call(partial(prepare_chunk_bwd, base=base), "gdn_prepare_bwd",
                 (q, k, v, g, beta, dW, dU, dattn, dq_in, dk_out, dd),
                 outputs, head_block)
