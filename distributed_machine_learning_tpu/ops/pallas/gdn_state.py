"""The gated delta rule's cross-chunk state pass as Pallas TPU kernels.

``ops/delta_rule.py`` solves every chunk's triangular system at once and is
left with one recurrence over the chunks, whose carry is the state ``S``
(float32 ``[dk, dv]`` a head).  As a ``lax.scan`` that carry lives in HBM:
every chunk step reads it, casts it, writes it back and slices its stacked
inputs, a dozen launched operations around two to four small matmuls.  Here
the grid walks the chunks in order (``"arbitrary"``) and the state stays in
a VMEM scratch buffer for the whole pass; each grid step streams one chunk
of a block of heads past it.

Two kernels, one a recurrence:

- ``gdn_state_fwd`` (:func:`state_fwd`): ``u = U − W·S``, ``S ← S·d +
  k_outᵀ·u``.  With ``q_in`` and ``attn`` it also reads the state,
  ``o = q_in·S + attn·u`` (the forward pass); without them it emits what
  the backward pass needs, the state entering each chunk (float32) and
  ``u`` in the operands' dtype.
- ``gdn_state_bwd`` (:func:`state_bwd`): the reverse recurrence, from the
  last chunk to the first, with the state's cotangent ``dS`` in VMEM:
  ``du = attnᵀ·do + k_out·dS``, ``dS ← dS·d + q_inᵀ·do − Wᵀ·du``; the
  cotangents of the step's inputs are made in the same step, where ``S``,
  ``dS`` and ``u`` are at hand: ``dU = du``, ``dW = −du·Sᵀ``, ``dk_out =
  u·dSᵀ``, ``dq_in = do·Sᵀ``, ``dattn = do·uᵀ``, ``dd = ⟨dS, S⟩`` (float32
  against float32; a lane of ``d`` gets its own column's sum, and the
  broadcast that made the lanes adds them up).

The arithmetic of one head's step is :func:`fwd_step` / :func:`read_out` /
:func:`bwd_step`, plain functions of arrays: the kernels map them over the
heads of the block they load, and ``ops/delta_rule.py``'s ``lax.scan``
fallback maps the same functions over batch and heads, so both round at the
same places —
the state, its decay and every sum float32, the matmul operands in the
inputs' dtype (bf16 on the MXU) with float32 accumulation.

All arrays are chunk-major, ``[nc, BH, rows, cols]`` with batch and heads
folded; ``d`` (``exp(γ_C)``, one number a head a chunk) comes as a row of
``dv`` equal lanes, ``[nc, BH, 1, dv]``, and its cotangent leaves as one.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
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

#: Heads a grid step: 16 heads of 128 × 128 hold the state in 1 MiB and a
#: backward step's double-buffered blocks in 7.5 MiB, under the default
#: scoped VMEM limit (16 MiB).  The kernels run at the pace of HBM (PERF.md
#: §6, PR 30); the block only sets how many grid steps pay the step overhead.
HEAD_BLOCK = 16

def fwd_step(S, W, U, k_out, d):
    """One head, one chunk.  ``S``: the state entering, float32 [dk, dv];
    ``W`` [C, dk], ``k_out`` [C, dk] in the operands' dtype; ``U`` float32
    [C, dv]; ``d`` float32 [1, dv], a row of equal lanes.  Returns the state
    leaving and ``u`` in the operands' dtype."""
    dt = W.dtype
    u = (U - dot(W, S.astype(dt), NN)).astype(dt)
    return S * d + dot(k_out, u, TN), u


def read_out(S, u, q_in, attn):
    """``o = q_in·S + attn·u`` of the chunk that ``S`` enters: float32
    [C, dv]."""
    return dot(q_in, S.astype(u.dtype), NN) + dot(attn, u, NN)


def bwd_step(dS, S, u, W, k_out, q_in, attn, do, d):
    """One head, one chunk, backwards.  ``dS``: the cotangent of the state
    LEAVING the chunk, float32; ``S`` the state entering it, ``u`` its
    corrections (:func:`fwd_step`); ``do`` the cotangent of
    :func:`read_out`'s result, in the operands' dtype.  Returns the
    cotangent of the state entering and the cotangents of ``U, W, k_out,
    q_in, attn`` and of ``d`` as the row of lanes it came as."""
    dt = W.dtype
    S_in, dS_in = S.astype(dt), dS.astype(dt)
    dU = dot(attn, do, TN) + dot(k_out, dS_in, NN)
    du = dU.astype(dt)
    dS_new = dS * d + dot(q_in, do, TN) - dot(W, du, TN)
    return dS_new, (
        dU,
        (-dot(du, S_in, NT)).astype(dt),
        dot(u, dS_in, NT).astype(dt),
        dot(do, S_in, NT).astype(dt),
        dot(do, u, NT).astype(dt),
        jnp.sum(dS * S, axis=0, keepdims=True),  # each lane's own
    )


def _fwd_kernel(*refs, reads):
    if reads:
        W, U, k_out, d, q_in, attn, o, S_acc = refs
    else:
        W, U, k_out, d, S_out, u_out, S_acc = refs

    @pl.when(pl.program_id(1) == 0)
    def _start():
        S_acc[...] = jnp.zeros_like(S_acc)

    S = S_acc[...]
    S_acc[...], u = jax.vmap(fwd_step)(S, W[...], U[...], k_out[...], d[...])
    if reads:
        o[...] = jax.vmap(read_out)(S, u, q_in[...], attn[...]).astype(
            o.dtype)
    else:
        S_out[...] = S
        u_out[...] = u


def _bwd_kernel(S, u, W, k_out, q_in, attn, do, d,
                dU, dW, dk_out, dq_in, dattn, dd, dS_acc):
    @pl.when(pl.program_id(1) == 0)
    def _start():
        dS_acc[...] = jnp.zeros_like(dS_acc)

    dS_acc[...], grads = jax.vmap(bwd_step)(
        dS_acc[...], S[...], u[...], W[...], k_out[...], q_in[...],
        attn[...], do[...], d[...])
    for ref, grad in zip((dU, dW, dk_out, dq_in, dattn, dd), grads):
        ref[...] = grad


def _pass(kernel, name, inputs, outputs, state, reverse):
    """``pallas_call`` over (head blocks, chunks): every array is ``[nc, BH,
    rows, cols]`` and a grid step sees ``[heads, rows, cols]`` of one chunk,
    the chunks in order (``reverse``: last first); ``state`` is the
    float32 scratch the chunk axis carries."""
    nc, BH = inputs[0].shape[:2]
    heads = pick_block(BH, HEAD_BLOCK, 1)
    at = (lambda h, c: (nc - 1 - c, h, 0, 0)) if reverse else (
        lambda h, c: (c, h, 0, 0))
    spec = lambda a: pl.BlockSpec((None, heads, *a.shape[2:]), at,
                                  memory_space=pltpu.VMEM)
    return pl.pallas_call(
        kernel,
        out_shape=outputs,
        grid=(BH // heads, nc),
        in_specs=[spec(a) for a in inputs],
        out_specs=[spec(a) for a in outputs],
        scratch_shapes=[pltpu.VMEM((heads, *state), jnp.float32)],
        interpret=interpret(),
        name=name,
        **tile_compiler_params(("parallel", "arbitrary")),
    )(*inputs)


def state_fwd(W, U, k_out, d, q_in=None, attn=None):
    """The forward state pass.  ``W``, ``k_out`` [nc, BH, C, dk], ``U``
    float32 [nc, BH, C, dv], ``d`` float32 [nc, BH, 1, dv].  With ``q_in``
    [nc, BH, C, dk] and ``attn`` [nc, BH, C, C]: returns ``o`` [nc, BH, C,
    dv] in the operands' dtype.  Without: returns the state entering each
    chunk (float32 [nc, BH, dk, dv]) and ``u`` [nc, BH, C, dv] in the
    operands' dtype."""
    nc, BH, C, dk = W.shape
    dv = U.shape[-1]
    reads = q_in is not None
    u = jax.ShapeDtypeStruct((nc, BH, C, dv), W.dtype)
    if reads:
        inputs, outputs = (W, U, k_out, d, q_in, attn), [u]
    else:
        inputs = (W, U, k_out, d)
        outputs = [jax.ShapeDtypeStruct((nc, BH, dk, dv), jnp.float32), u]
    out = _pass(partial(_fwd_kernel, reads=reads), "gdn_state_fwd", inputs,
                outputs, (dk, dv), reverse=False)
    return out[0] if reads else tuple(out)


def state_bwd(S, u, W, k_out, q_in, attn, do, d):
    """The reverse state pass: ``S`` and ``u`` from :func:`state_fwd`, ``do``
    [nc, BH, C, dv] in the operands' dtype.  Returns the cotangents of ``U``
    (float32), ``W``, ``k_out``, ``q_in``, ``attn`` (the operands' dtype) and
    ``d`` (float32 [nc, BH, 1, dv], lane by lane)."""
    like = lambda a, dtype=None: jax.ShapeDtypeStruct(a.shape,
                                                     dtype or a.dtype)
    outputs = [like(u, jnp.float32), like(W), like(k_out), like(q_in),
               like(attn), like(d)]
    return tuple(_pass(_bwd_kernel, "gdn_state_bwd",
                       (S, u, W, k_out, q_in, attn, do, d), outputs,
                       S.shape[2:], reverse=True))
