"""Plain reference for ``qwen3_next_80b_a3b``: the Qwen3-Next decoder
(huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct, ``config.json`` and the
published modelling code's equations) — Gated DeltaNet and gated softmax
attention layers 3:1, each followed by a sparse mixture of experts with a
shared expert — with a token embedding, a final norm, an untied head and
mean next-token cross-entropy.

``d`` the hidden size, no bias anywhere.  ``N(x; w) = x · rsqrt(mean(x²) + ε)
· (1 + w)``.  Layer ``i`` (0-based) is an attention layer when ``(i + 1) %
full_attention_interval == 0``, else a DeltaNet layer::

    h = x + Mixer_i(N(x; w1));   y = h + MoE(N(h; w2));   final N, then the head

- *Gated DeltaNet*: ``[q, k, v, z] = x·W_qkvz``, ``[b, a] = x·W_ba``; ``q, k,
  v`` concatenated pass a depthwise causal convolution (no bias) and SiLU;
  ``q, k`` are repeated from the key heads to the value heads.  Per value
  head: ``β_t = σ(b_t)``, ``g_t = −exp(A_log)·softplus(a_t + dt_bias)``,
  ``q̂ = q/‖q‖₂ · dk^-½``, ``k̂ = k/‖k‖₂``; state ``S`` of ``[dk, dv]``, zero
  at the start: ``S ← S·exp(g_t)``; ``Δ = β_t (v_t − Sᵀk̂_t)``; ``S ← S + k̂_t
  Δᵀ``; ``o_t = Sᵀ q̂_t``.  Then ``o ← rmsnorm(o)·w·SiLU(z)`` per head (a
  plain weight), heads concatenated, ``·W_out``.
- *Gated attention*: ``[q, gate] = x·W_q``, ``k = x·W_k``, ``v = x·W_v``;
  ``q ← N(q; w_q)``, ``k ← N(k; w_k)`` per head; the first
  ``partial_rotary_factor`` of each head's dimensions rotated (half-split
  pairs, base ``rope_theta``); causal softmax attention scaled ``dh^-½``,
  each key/value head serving ``H/Hkv`` query heads; ``(attn ⊙ σ(gate))·W_o``.
- *MoE*: ``p = softmax(x·W_r)`` over ``router_width``; the
  ``num_experts_per_tok`` largest, renormalised to sum to one; expert ``e``
  is ``W_down(SiLU(W_gate x) ⊙ W_up x)``; ``y = Σ_{e ∈ top-k ∩ held} p̃_e
  E_e(x) + σ(x·w_s)·E_shared(x)``.  No token is dropped.  ``held_experts =
  [first, count]`` are the experts whose weights are given; what the others
  would add is left out (the chip's share of an expert-parallel layer).

Straightforward ``jax.numpy`` in float32 under "highest" matmul precision; no
kernel, no chunked form, no sorting.  The recurrence runs one time step at a
time (blocks of steps rematerialized in the backward pass), attention one
head at a time over the whole sequence, every held expert on every token,
masked by the renormalised top-k weights; each layer is rematerialized —
only so that an 8192-token sequence fits beside the resident training state.
Imports nothing from the system; takes the system's parameter tree by name.

Departures from the published model, all stated in the configuration file:
the columns of ``W_qkvz``, ``W_ba`` and ``W_q`` are laid out ``[q | k | v |
z]``, ``[b | a]``, ``[q | gate]`` (the published code interleaves them per
head group: a permutation of columns); no multi-token-prediction module; no
router auxiliary loss.

``operand_dtype``: every matmul operand rounded through that dtype first —
how the check reads "the same mathematics in the next precision down".
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference.transformer_lm import get_leaf, with_leaves

SCAN_BLOCK = 64  # time steps rematerialized together in the recurrence
L2_EPS = 1e-6


def rounder(operand_dtype):
    """Rounds a matmul operand to ``operand_dtype``'s exponent and mantissa
    widths on the way forward (``lax.reduce_precision``: a cast there and
    back is a pair the TPU compiler may fold away as excess precision) and
    passes its cotangent through unrounded: the gradients are those of the
    rounded forward pass.  (Cotangents here are of order 1e-6 and would
    flush to zero in float8.)"""
    if operand_dtype is None:
        return lambda a: a
    info = jnp.finfo(operand_dtype)
    return lambda a: a + lax.stop_gradient(
        lax.reduce_precision(a, info.nexp, info.nmant) - a)


def norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + w)


def rope(x, rotary_dim, base):
    """Rotate the first ``rotary_dim`` of ``[T, H, D]`` by position: halves
    ``(x1, x2)`` of that width go to ``(x1·cos − x2·sin, x1·sin + x2·cos)``."""
    t = x.shape[0]
    half = rotary_dim // 2
    freqs = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2, rest = (x[..., :half], x[..., half:rotary_dim],
                    x[..., rotary_dim:])
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos, rest], -1)


def delta_rule(q, k, v, g, beta):
    """The recurrence, one step at a time.  ``q, k``: [T, H, dk]; ``v``:
    [T, H, dv]; ``g, beta``: [T, H].  Returns [T, H, dv]."""
    t, h, dk = q.shape
    pad = -t % SCAN_BLOCK
    q, k, v, g, beta = (jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
                        for a in (q, k, v, g, beta))  # β = 0, g = 0: inert

    def step(S, x):
        q_t, k_t, v_t, g_t, b_t = x
        S = S * jnp.exp(g_t)[:, None, None]
        delta = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", S, k_t))
        S = S + k_t[:, :, None] * delta[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    @jax.checkpoint
    def block(S, xs):
        return lax.scan(step, S, xs)

    blocks = lambda a: a.reshape(-1, SCAN_BLOCK, *a.shape[1:])
    S0 = jnp.zeros((h, dk, v.shape[-1]), jnp.float32)
    _, out = lax.scan(block, S0, tuple(map(blocks, (q, k, v, g, beta))))
    return out.reshape(-1, h, v.shape[-1])[:t]


def delta_net(x, p, c, r):
    t = x.shape[0]
    hk, hv = c["linear_num_key_heads"], c["linear_num_value_heads"]
    dk, dv = c["linear_key_head_dim"], c["linear_value_head_dim"]
    qk, vz = hk * dk, hv * dv
    qkvz = r(x) @ r(p["in_proj_qkvz"]["kernel"])
    ba = r(x) @ r(p["in_proj_ba"]["kernel"])
    w = p["conv_weight"]                                    # [K, channels]
    taps = w.shape[0]
    padded = jnp.pad(qkvz[:, :2 * qk + vz], ((taps - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(w[i] * padded[i:i + t] for i in range(taps)))
    unit = lambda a: a * lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + L2_EPS)
    rep = hv // hk
    q = jnp.repeat(unit(qkv[:, :qk].reshape(t, hk, dk)), rep, 1) * dk ** -0.5
    k = jnp.repeat(unit(qkv[:, qk:2 * qk].reshape(t, hk, dk)), rep, 1)
    v = qkv[:, 2 * qk:].reshape(t, hv, dv)
    z = qkvz[:, 2 * qk + vz:].reshape(t, hv, dv)
    beta = jax.nn.sigmoid(ba[:, :hv])
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[:, hv:] + p["dt_bias"])
    o = delta_rule(r(q), r(k), r(v), g, beta)
    o = o * lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + c["rms_norm_eps"])
    o = o * p["norm_weight"] * jax.nn.silu(z)
    return r(o.reshape(t, vz)) @ r(p["out_proj"]["kernel"])


def _one_head(qkv):
    q, k, v = qkv  # [T, D] each
    t, d = q.shape
    scores = (q @ k.T) / math.sqrt(d)
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    return probs @ v


def attention(x, p, c, r):
    t = x.shape[0]
    h, hkv, dh = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    q_gate = r(x) @ r(p["q_proj"]["kernel"])
    q, gate = q_gate[:, :h * dh].reshape(t, h, dh), q_gate[:, h * dh:]
    k = (r(x) @ r(p["k_proj"]["kernel"])).reshape(t, hkv, dh)
    v = (r(x) @ r(p["v_proj"]["kernel"])).reshape(t, hkv, dh)
    eps = c["rms_norm_eps"]
    rotary = int(dh * c["partial_rotary_factor"])
    q = rope(norm(q, p["q_norm"]["weight"], eps), rotary, c["rope_theta"])
    k = rope(norm(k, p["k_norm"]["weight"], eps), rotary, c["rope_theta"])
    heads = lambda a: r(a).transpose(1, 0, 2)  # [H, T, D]
    expand = lambda a: jnp.repeat(a, h // hkv, axis=0)
    out = lax.map(jax.checkpoint(_one_head),
                  (heads(q), expand(heads(k)), expand(heads(v))))
    out = out.transpose(1, 0, 2).reshape(t, h * dh) * jax.nn.sigmoid(gate)
    return r(out) @ r(p["o_proj"]["kernel"])


def route(x, p, c):
    """``(expert ids [T, k], weights [T, k])``: the k largest of the router's
    softmax over its whole width, renormalised when ``norm_topk_prob``."""
    probs = jax.nn.softmax(x @ p["router"]["kernel"], axis=-1)
    weights, idx = lax.top_k(probs, c["num_experts_per_tok"])
    if c["norm_topk_prob"]:
        weights = weights / weights.sum(-1, keepdims=True)
    return idx, weights


def gated_mlp(x, w_gate, w_up, w_down, r):
    return r(jax.nn.silu(r(x) @ r(w_gate)) * (r(x) @ r(w_up))) @ r(w_down)


def routed_experts(x, p, c, r):
    """The held experts' part: every held expert applied to every token,
    weighted by the token's renormalised weight for it (zero where the
    expert is not among the token's k)."""
    idx, weights = route(x, p, c)
    first = c.get("held_experts", (0, c["num_experts"]))[0]

    @jax.checkpoint
    def one(y, expert):
        e, w_gate, w_up, w_down = expert
        weight = jnp.sum(jnp.where(idx == first + e, weights, 0.0), -1)
        return y + weight[:, None] * gated_mlp(x, w_gate, w_up, w_down, r), None

    held = p["w_up"].shape[0]
    y, _ = lax.scan(one, jnp.zeros_like(x),
                    (jnp.arange(held), p["w_gate"], p["w_up"], p["w_down"]))
    return y


def shared_expert(x, p, r):
    gate = jax.nn.sigmoid(r(x) @ r(p["shared_expert_gate"]["kernel"]))
    return gate * gated_mlp(
        x, p["shared_gate_proj"]["kernel"], p["shared_up_proj"]["kernel"],
        p["shared_down_proj"]["kernel"], r)


def moe(x, p, c, r=rounder(None)):
    return routed_experts(x, p, c, r) + shared_expert(x, p, r)


def mix(x, p, c, r):
    """``h = x + Mixer(N(x; w1))``: the first half of a block."""
    h = norm(x, p["norm1"]["weight"], c["rms_norm_eps"])
    return x + (attention(h, p["attn"], c, r) if "attn" in p
                else delta_net(h, p["gdn"], c, r))


def block(x, p, c, r):
    x = mix(x, p, c, r)
    return x + moe(norm(x, p["norm2"]["weight"], c["rms_norm_eps"]),
                   p["moe"], c, r)


def n_layers(params) -> int:
    return sum(1 for name in params if name.startswith("block_"))


def hidden(params, c, tokens, r):
    """Final-norm hidden states of one sequence ``[T]``."""
    x = params["embed"]["embedding"][tokens]
    for i in range(n_layers(params)):
        p = params[f"block_{i}"]
        if ("attn" in p) != ((i + 1) % c["full_attention_interval"] == 0):
            raise ValueError(f"block_{i} is not the layer kind the "
                             "configuration's period puts there")
        x = jax.checkpoint(lambda x, p: block(x, p, c, r))(x, p)
    return norm(x, params["norm_f"]["weight"], c["rms_norm_eps"])


def sequence_logits(params, c, tokens, r=rounder(None)):
    return r(hidden(params, c, tokens, r)) @ r(params["lm_head"]["kernel"])


def sequence_loss(params, c, tokens, targets, r):
    logp = jax.nn.log_softmax(sequence_logits(params, c, tokens, r), axis=-1)
    return -jnp.take_along_axis(logp, targets[:, None], axis=-1).mean()


def _f32(params):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)


def logits(params, c, tokens):
    """``[B, T, vocab]`` logits."""
    with jax.default_matmul_precision("highest"):
        params = _f32(params)
        return jnp.stack([sequence_logits(params, c, row) for row in tokens])


def loss(params, c, tokens, targets, operand_dtype=None):
    """Mean over the sequences of ``tokens`` ``[B, T]``."""
    with jax.default_matmul_precision("highest"):
        params, r = _f32(params), rounder(operand_dtype)
        per_seq = [sequence_loss(params, c, tokens[i], targets[i], r)
                   for i in range(tokens.shape[0])]
        return sum(per_seq) / len(per_seq)


def layer0_routing(params, c, tokens):
    """The first layer's routed expert ids ``[B, T, k]``: its router reads
    the embedding through one DeltaNet mixer, so two computations of one
    model differ there only by their own rounding."""
    with jax.default_matmul_precision("highest"):
        params = _f32(params)
        p = params["block_0"]

        def one(row):
            x = mix(params["embed"]["embedding"][row], p, c, rounder(None))
            return route(norm(x, p["norm2"]["weight"], c["rms_norm_eps"]),
                         p["moe"], c)[0]

        return jnp.stack([one(row) for row in tokens])


def loss_and_grads(params, c, tokens, targets, sample, operand_dtype=None):
    """``(loss, {path: grad})`` for the tensors named in ``sample``."""
    picked = {path: get_leaf(params, path) for path in sample}
    return jax.value_and_grad(lambda s: loss(
        with_leaves(params, s), c, tokens, targets, operand_dtype))(picked)
