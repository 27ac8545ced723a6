"""Gated delta rule — the recurrence of a Gated DeltaNet mixer, in chunks.

Per head, with a state ``S`` of ``[dk, dv]`` that starts at zero, every time
step decays the state, corrects what the state would return for the step's
key towards the step's value, and reads the state with the step's query::

    S <- S · exp(g_t)
    Δ  = β_t (v_t − Sᵀ k_t)
    S <- S + k_t Δᵀ
    o_t = Sᵀ q_t

(``g_t <= 0`` a log-decay, ``β_t`` in (0, 1); the caller normalises and
scales ``q`` and ``k``.)  :func:`gated_delta_rule_recurrent` is that loop, one
step at a time — what the tests compare against.

:func:`gated_delta_rule` computes the same in chunks of ``CHUNK`` steps
(Yang et al., "Gated Delta Networks", arXiv:2412.06464, §3.3; the WY/UT
form).  With ``γ_i = Σ_{t<=i} g_t`` inside a chunk and ``S₀`` the state
entering it, the corrections ``u_i = Δ_i`` of a chunk solve one unit
lower-triangular system::

    (I + tril(β_i · k_i·k_j · exp(γ_i − γ_j), −1)) [W | U] = [β k exp(γ) | β v]
    u = U − W S₀

so the triangular solves of all chunks run at once, batched, and only the
state crosses chunks, in one ``lax.scan`` with four small matmuls a step::

    o  = (q exp(γ)) S₀ + tril(q_i·k_j · exp(γ_i − γ_j)) u
    S₁ = S₀ exp(γ_C) + (k exp(γ_C − γ))ᵀ u

Plain JAX, gradients by autodiff (of a forward pass made again in the
backward pass: :func:`gated_delta_rule`).  The triangular system, the decays and the
state are float32; the matmuls take their operands in the inputs' dtype
(bf16 on the MXU) and accumulate in float32.  A length that is no multiple of
the chunk is padded with steps that leave the state alone (``β = 0, g = 0``).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

CHUNK = 64


def gated_delta_rule_recurrent(q, k, v, g, beta):
    """The recurrence itself, one ``lax.scan`` step a time step.

    ``q``, ``k``: [B, T, H, dk]; ``v``: [B, T, H, dv]; ``g``, ``beta``:
    [B, T, H].  Float32 throughout; returns [B, T, H, dv] float32."""
    f32 = jnp.float32
    q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))
    B, _, H, dk = q.shape

    def step(S, x):
        q_t, k_t, v_t, g_t, b_t = x  # [B, H, d] / [B, H]
        S = S * jnp.exp(g_t)[..., None, None]
        delta = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", S, k_t))
        S = S + k_t[..., :, None] * delta[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t)

    time_major = lambda a: jnp.moveaxis(a, 1, 0)
    S0 = jnp.zeros((B, H, dk, v.shape[-1]), f32)
    _, out = lax.scan(step, S0, tuple(map(time_major, (q, k, v, g, beta))))
    return jnp.moveaxis(out, 0, 1)


def gated_delta_rule(q, k, v, g, beta, chunk: int = CHUNK):
    """The chunked form (module docstring).  Shapes as
    :func:`gated_delta_rule_recurrent`; returns [B, T, H, dv] in
    ``v.dtype``.

    The backward pass keeps the five inputs and computes the chunked
    forward again (``jax.checkpoint``), as a flash-attention kernel
    recomputes its probabilities: the chunk-local systems, their solutions
    and the scan's per-chunk states are float32 arrays of ``[T/C, B, H, C,
    dk + dv]`` and ``[T/C, B, H, dk, dv]`` — some 2 GB a layer at 8192
    tokens and 32 heads of 128, against 0.2 GB of inputs — and cost a few
    small matmuls a chunk to make again."""
    return _chunked(q, k, v, g, beta, chunk)


@partial(jax.checkpoint, static_argnums=(5,))
def _chunked(q, k, v, g, beta, chunk):
    f32 = jnp.float32
    dt = v.dtype
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    pad = -T % chunk
    if pad:
        widths = ((0, 0), (0, pad)) + ((0, 0),) * 2
        q, k, v = (jnp.pad(a, widths) for a in (q, k, v))
        g, beta = (jnp.pad(a, widths[:3]) for a in (g, beta))
    nc = (T + pad) // chunk

    def chunks(a):  # [B, T, H, ...] -> [nc, B, H, chunk, ...]
        a = a.reshape(B, nc, chunk, H, *a.shape[3:])
        return jnp.moveaxis(a, (1, 3), (0, 2))

    q, k, v = chunks(q), chunks(k), chunks(v)
    beta = chunks(beta.astype(f32))[..., None]          # [nc, B, H, C, 1]
    gamma = jnp.cumsum(chunks(g.astype(f32)), axis=-1)  # [nc, B, H, C]
    # exp(γ_i − γ_j) for j <= i, zero above the diagonal.  The difference is
    # masked before the exponential: above the diagonal it is positive and
    # may overflow, which would poison the gradient of the masked entries.
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    diff = gamma[..., :, None] - gamma[..., None, :]
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, diff, 0.0)), 0.0)

    def scores(a, b):  # a_i · b_j, float32 accumulation
        return jnp.einsum("...id,...jd->...ij", a, b,
                          preferred_element_type=f32)

    strict = jnp.tril(decay, -1)
    system = jnp.eye(chunk, dtype=f32) + beta * scores(k, k) * strict
    kf, vf = k.astype(f32), v.astype(f32)
    rhs = jnp.concatenate(
        [beta * kf * jnp.exp(gamma)[..., None], beta * vf], axis=-1)
    solved = jax.scipy.linalg.solve_triangular(
        system, rhs, lower=True, unit_diagonal=True)
    W, U = solved[..., :dk].astype(dt), solved[..., dk:]
    attn = (scores(q, k) * decay).astype(dt)
    q_in = (q.astype(f32) * jnp.exp(gamma)[..., None]).astype(dt)
    gamma_end = gamma[..., -1:]                          # [nc, B, H, 1]
    k_out = (kf * jnp.exp(gamma_end - gamma)[..., None]).astype(dt)

    def matmul(a, b):
        return jnp.matmul(a, b, preferred_element_type=f32)

    def step(S, x):
        W_c, U_c, attn_c, q_c, k_c, decay_c = x
        S_in = S.astype(dt)
        u = U_c - matmul(W_c, S_in)                      # [B, H, C, dv]
        u_in = u.astype(dt)
        out = matmul(q_c, S_in) + matmul(attn_c, u_in)
        S = S * decay_c[..., None] + matmul(jnp.swapaxes(k_c, -1, -2), u_in)
        return S, out

    S0 = jnp.zeros((B, H, dk, dv), f32)
    _, out = lax.scan(
        step, S0, (W, U, attn, q_in, k_out, jnp.exp(gamma_end)))
    out = jnp.moveaxis(out, (0, 2), (1, 3))              # [B, nc, C, H, dv]
    return out.reshape(B, nc * chunk, H, dv)[:, :T].astype(dt)
