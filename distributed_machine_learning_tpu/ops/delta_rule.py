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
form), in three parts.

**Preparation** — everything local to one chunk of one head
(``ops/pallas/gdn_prepare.py``: ``prepare_chunk``).  With ``γ_i = Σ_{t<=i}
g_t`` inside a chunk and ``S₀`` the state entering it, the corrections ``u_i
= Δ_i`` of a chunk solve one unit lower-triangular system::

    (I + tril(β_i · k_i·k_j · exp(γ_i − γ_j), −1)) [W | U] = [β k exp(γ) | β v]
    u = U − W S₀

whose inverse ``T`` is made in blocks (substitution inside 16 × 16 diagonal
blocks, the rest by ``T₂₁ = −T₂₂ A₂₁ T₁₁``), so ``W = T (β k exp(γ))``, ``U =
T (β v)``, and with them ``attn = tril(q_i·k_j · exp(γ_i − γ_j))``, ``q_in = q
exp(γ)``, ``k_out = k exp(γ_C − γ)`` and ``d = exp(γ_C)``.  :func:`_prepare`
is the same with ``jax.scipy``'s ``solve_triangular``, batched over all
chunks: what the tests compare the blocks with.

**The state pass** — all that crosses chunks, one chunk after the other::

    u  = U − W S₀
    o  = q_in S₀ + attn u
    S₁ = S₀ d + k_outᵀ u

**Its reverse** — the backward pass is written by hand (``jax.custom_vjp``),
because it is the same kind of recurrence, walked from the last chunk to the
first with the cotangent ``dS`` of the state as its carry::

    du = attnᵀ do + k_out dS₁
    dS₀ = dS₁ d + q_inᵀ do − Wᵀ du

and ``dU = du``, ``dW = −du S₀ᵀ``, ``dk_out = u dS₁ᵀ``, ``dq_in = do S₀ᵀ``,
``dattn = do uᵀ``, ``dd = ⟨dS₁, S₀⟩`` in the same step.  The backward pass
keeps the five inputs only: it makes the preparation and the forward states
again, and the preparation's own reverse is written by hand too
(``prepare_chunk_bwd``: ``dA = −strict(Tᵀ dW Wᵀ + Tᵀ dU Uᵀ)``, never through
the inversion).

Preparation and state pass each have two implementations of one arithmetic
(``gdn_prepare.py``: ``prepare_chunk``, ``prepare_chunk_bwd``;
``gdn_state.py``: ``fwd_step``, ``read_out``, ``bwd_step``), and
:func:`state_pass` says which a call gets, from what it can see: Pallas
kernels (``gdn_prepare_fwd``, ``gdn_prepare_bwd``: a chunk of a block of
heads visits VMEM once; ``gdn_state_fwd``, ``gdn_state_bwd``: the state
stays in VMEM for the whole pass) where the head widths are multiples of
128, the chunk is 64 and the platform is a TPU; otherwise the same functions
mapped over chunks, batch and heads in XLA, the state pass as a ``lax.scan``
(test widths, CPU runs: the Pallas interpreter walks a 128-step grid
slowly).

The triangular system, the decays and the state are float32 (``γ`` summed
in two parts, so that ``exp(γ_i − γ_j)`` stays float32-exact where the decay
vanishes and ``γ`` runs to the hundreds); the matmuls take their operands in
the inputs' dtype (bf16 on the MXU) and accumulate in float32.  A length that is no multiple of the chunk is padded with steps
that leave the state alone (``β = 0, g = 0``).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from distributed_machine_learning_tpu.ops.pallas import gdn_prepare, gdn_state
from distributed_machine_learning_tpu.ops.pallas.common import interpret

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


def state_pass(dk: int, dv: int, chunk: int, platform: str) -> str:
    """Which implementation of the preparation and of the state pass a call
    gets: ``"kernel"`` (the ``pallas_call``s of ``ops/pallas/gdn_prepare.py``
    and ``gdn_state.py``) or ``"scan"`` (the same chunk and step functions
    mapped in XLA, the state pass under ``lax.scan``).  The kernels tile in
    (8, 128) registers and 128-wide MXU passes and were written for the
    chunk of 64; anywhere but on a TPU Pallas interprets, a grid step at a
    time."""
    fits = dk % 128 == 0 and dv % 128 == 0 and chunk == 64
    return "kernel" if fits and platform == "tpu" else "scan"


def gated_delta_rule(q, k, v, g, beta, chunk: int = CHUNK):
    """The chunked form (module docstring).  Shapes as
    :func:`gated_delta_rule_recurrent`; returns [B, T, H, dv] in
    ``v.dtype``.

    The backward pass keeps the five inputs and computes the preparation
    and the forward states again, as a flash-attention kernel recomputes
    its probabilities: the chunk-local systems, their solutions and the
    per-chunk states are float32 arrays of ``[T/C, B, H, C, dk + dv]`` and
    ``[T/C, B, H, dk, dv]`` — some 2 GB a layer at 8192 tokens and 32 heads
    of 128, against 0.2 GB of inputs — and cost a few small matmuls a chunk
    to make again."""
    return _chunked(q, k, v, g, beta, chunk)


@partial(jax.custom_vjp, nondiff_argnums=(5,))
def _chunked(q, k, v, g, beta, chunk):
    kind = _kind(q, v, chunk)
    tiles = _tiles(q, k, v, g, beta, chunk)
    W, U, attn, q_in, k_out, d = _prepared(kind, tiles)
    o = _pass(kind, W, U, k_out, d, q_in, attn)          # [nc, B, H, C, dv]
    B, H, T = q.shape[0], q.shape[2], q.shape[1]
    o = jnp.moveaxis(o, (0, 2), (1, 3))                  # [B, nc, C, H, dv]
    return o.reshape(B, -1, H, o.shape[-1])[:, :T]


def _chunked_fwd(q, k, v, g, beta, chunk):
    return _chunked(q, k, v, g, beta, chunk), (q, k, v, g, beta)


def _chunked_bwd(chunk, inputs, do):
    # What is made again here is, to the compiler, what the forward pass
    # made: without the barrier it keeps that (0.7 GB a layer) instead.  The
    # barrier also ties the inputs to ``do``: nothing starts before it.
    inputs, do = lax.optimization_barrier((inputs, do))
    kind = _kind(inputs[0], inputs[2], chunk)
    # ``from_tiles`` undoes the chunking, the padding and the casts.
    tiles, from_tiles = jax.vjp(partial(_tiles, chunk=chunk), *inputs)
    W, U, attn, q_in, k_out, d = _prepared(kind, tiles)
    S, u = _pass(kind, W, U, k_out, d)
    dU, dW, dk_out, dq_in, dattn, dd = _reverse_pass(
        kind, S, u, W, k_out, q_in, attn, _chunks(do, chunk), d)
    return from_tiles(_prepared(
        kind, tiles, (dW, dU, dattn, dq_in, dk_out, dd)))


_chunked.defvjp(_chunked_fwd, _chunked_bwd)


def _chunks(a, chunk):
    """[B, T, H, ...] -> [nc, B, H, chunk, ...], ``T`` padded with zeros to
    a multiple of the chunk."""
    B, T, H = a.shape[:3]
    pad = -T % chunk
    if pad:
        a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
    a = a.reshape(B, (T + pad) // chunk, chunk, H, *a.shape[3:])
    return jnp.moveaxis(a, (1, 3), (0, 2))


def _tiles(q, k, v, g, beta, chunk):
    """The five inputs as the preparation kernels take them: ``q, k, v``
    [nc, B, H, C, d], ``g`` and ``beta`` float32 rows, [nc, B, H, 1, C]."""
    row = lambda a: _chunks(a.astype(jnp.float32), chunk)[..., None, :]
    return (*(_chunks(a, chunk) for a in (q, k, v)), row(g), row(beta))


def _prepare(q, k, v, g, beta, chunk):
    """The preparation with ``solve_triangular``, batched over the chunks —
    no call takes it since ``gdn_prepare.prepare_chunk``; it is what the
    tests hold that to.  ``W`` [nc, B, H, C,
    dk], ``U`` float32 [nc, B, H, C, dv], ``attn`` [nc, B, H, C, C],
    ``q_in``, ``k_out`` [nc, B, H, C, dk] of the module docstring, and
    ``d`` float32 [nc, B, H, 1, dv]: a head's number as a row of equal
    lanes, the form in which it meets the state's rows."""
    f32 = jnp.float32
    dt = v.dtype
    dk, dv = q.shape[-1], v.shape[-1]
    q, k, v = (_chunks(a, chunk) for a in (q, k, v))
    beta = _chunks(beta.astype(f32), chunk)[..., None]   # [nc, B, H, C, 1]
    gamma = jnp.cumsum(_chunks(g.astype(f32), chunk), axis=-1)
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
    d = jnp.broadcast_to(jnp.exp(gamma_end)[..., None], (*W.shape[:3], 1, dv))
    return W, U, attn, q_in, k_out, d


def _kind(q, v, chunk):
    return state_pass(q.shape[-1], v.shape[-1], chunk,
                      "cpu" if interpret() else "tpu")


def _prepared(kind, tiles, cotangents=None):
    """The chunk-local preparation of ``_tiles``' arrays: ``W, U, attn,
    q_in, k_out, d`` over [nc, B, H, ...].  With the ``cotangents`` of those
    six: the cotangents of the tiles."""
    reverse = cotangents is not None
    if kind == "kernel":
        kernels = (gdn_prepare.prepare_bwd if reverse
                   else gdn_prepare.prepare_fwd)
        return _through_kernels(kernels, tiles + (cotangents or ()))
    chunk_fn = (gdn_prepare.prepare_chunk_bwd if reverse
                else gdn_prepare.prepare_chunk)
    return jax.vmap(_heads(chunk_fn))(*tiles, *(cotangents or ()))


def _pass(kind, W, U, k_out, d, q_in=None, attn=None):
    """The state pass over [nc, B, H, ...] arrays.  With ``q_in`` and
    ``attn``: ``o`` [nc, B, H, C, dv] in the operands' dtype.  Without: the
    state entering each chunk (float32 [nc, B, H, dk, dv]) and ``u`` [nc, B,
    H, C, dv]."""
    reads = q_in is not None
    xs = (W, U, k_out, d) + ((q_in, attn) if reads else ())
    if kind == "kernel":
        return _through_kernels(gdn_state.state_fwd, xs)

    def step(S, x):
        S_new, u = _heads(gdn_state.fwd_step)(S, *x[:4])
        if reads:
            return S_new, _heads(gdn_state.read_out)(S, u, *x[4:]).astype(
                u.dtype)
        return S_new, (S, u)

    S0 = jnp.zeros((*W.shape[1:3], W.shape[-1], U.shape[-1]), jnp.float32)
    return lax.scan(step, S0, xs)[1]


def _reverse_pass(kind, S, u, W, k_out, q_in, attn, do, d):
    """The reverse state pass over [nc, B, H, ...] arrays: the cotangents
    of ``U, W, k_out, q_in, attn, d``."""
    xs = (S, u, W, k_out, q_in, attn, do, d)
    if kind == "kernel":
        return _through_kernels(gdn_state.state_bwd, xs)
    return lax.scan(lambda dS, x: _heads(gdn_state.bwd_step)(dS, *x),
                    jnp.zeros(S.shape[1:], jnp.float32), xs, reverse=True)[1]


def _heads(f):
    return jax.vmap(jax.vmap(f))


def _through_kernels(kernels, arrays):
    """``kernels(*arrays)`` with batch and heads folded for the call ([nc, B,
    H, ...] -> [nc, BH, ...]) and unfolded in what comes back."""
    lead = arrays[0].shape[:3]
    out = kernels(*(a.reshape(lead[0], -1, *a.shape[3:]) for a in arrays))
    return jax.tree_util.tree_map(
        lambda a: a.reshape(*lead, *a.shape[2:]), out)
