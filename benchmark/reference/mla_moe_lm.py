"""Plain reference for ``kanana2_30b_a3b``: the ``deepseek_v3`` decoder as
kakaocorp/kanana-2-30b-a3b-instruct-2601 configures it
(huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601, ``config.json``) —
multi-head latent attention in every layer, one leading dense gated-SiLU
layer, then sparse layers routed by ``sigmoid score + bias`` with ungated
shared experts — with a token embedding, a final norm, an untied head and
mean next-token cross-entropy.

``x`` a token's hidden state, no bias anywhere, ``N(x; w) = x · rsqrt(mean(x²)
+ ε) · w``.  Block ``i``::

    h = x + MLA(N(x; w1));   y = h + F_i(N(h; w2));   final N, then the head

``F_i`` the dense MLP for ``i < first_k_dense_replace``, else the sparse layer.

- *MLA*, ``H`` heads, no query latent: ``q = W_q x`` → per head ``[q_nope |
  q_rope]``; ``[c | k_r] = W_kva x``; ``c̃ = N(c; w_c)``; ``W_kvb c̃`` → per
  head ``[k_nope | v]``.  ``q_rope`` and the ONE ``k_r`` all heads share are
  rotated by position on adjacent pairs ``(2j, 2j+1)`` by ``t · θ^(−2j/d_r)``.
  ``k_h = [k_nope_h | k_r]``, ``q_h = [q_nope_h | q_rope_h]``; ``a =
  softmax_causal(q_h·k_hᵀ / √(d_nope + d_r))``; ``o_h = a·v_h``; ``out = W_o
  [o_1 … o_H]``.  ``W_kvb`` is applied to every position (the training form).
- *Dense MLP / expert / shared expert*: ``W_down(SiLU(W_gate x) ⊙ W_up x)``.
- *Router*, float32: ``s = σ(W_r x)``; ``S = top-k(s + b)``; ``w_e =
  routed_scaling_factor · s_e / (Σ_{j∈S} s_j + 1e-20)`` for ``e ∈ S`` — the
  bias ``b`` picks, it never weighs.  ``F(x) = Σ_{e ∈ S ∩ held} w_e E_e(x) +
  E_shared(x)``.  No token is dropped.  ``held_experts = [first, count]`` are
  the experts whose weights are given; what the others would add is left out
  (the chip's share of an expert-parallel layer).

Straightforward ``jax.numpy`` in float32 under "highest" matmul precision; no
kernel, no sorting.  Attention runs one head at a time in blocks of queries
against all keys, every held expert runs on every token masked by its weight,
and each layer is rematerialized — only so that an 8192-token sequence fits
beside the resident training state.  Imports nothing from the system; takes
the system's parameter tree by name, ``b`` among it.

Departures from the published model are stated in the configuration file.

``operand_dtype``: every matmul operand rounded through that dtype first
(``benchmark/reference/hybrid_moe_lm.py::rounder``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference.hybrid_moe_lm import _f32, gated_mlp, rounder
from benchmark.reference.transformer_lm import get_leaf, with_leaves

QUERY_BLOCK = 1024  # queries scored against all keys at once


def norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope_pairs(x, base):
    """Rotate the adjacent pairs ``(2j, 2j+1)`` of ``[T, H, D]`` by ``t ·
    base^(−2j/D)``: ``(a, b) → (a·cos − b·sin, a·sin + b·cos)``."""
    t, _, d = x.shape
    freqs = base ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], -1).reshape(
        x.shape)


def _one_head(qkv):
    """Causal softmax attention of one head: ``q, k`` [T, D], ``v`` [T, Dv],
    a block of queries at a time."""
    q, k, v = qkv
    t, d = q.shape
    block = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t

    @jax.checkpoint
    def rows(args):
        q_rows, first = args
        scores = (q_rows @ k.T) / math.sqrt(d)
        causal = (first + jnp.arange(block))[:, None] >= jnp.arange(t)[None, :]
        return jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1) @ v

    out = lax.map(rows, (q.reshape(-1, block, d), jnp.arange(0, t, block)))
    return out.reshape(t, v.shape[-1])


def attention(x, p, c, r):
    t = x.shape[0]
    h, dn, dr, dv, dl = (c["num_attention_heads"], c["qk_nope_head_dim"],
                         c["qk_rope_head_dim"], c["v_head_dim"],
                         c["kv_lora_rank"])
    theta = float(c["rope_theta"])
    q = (r(x) @ r(p["q_proj"]["kernel"])).reshape(t, h, dn + dr)
    kva = r(x) @ r(p["kv_a_proj_with_mqa"]["kernel"])
    latent = norm(kva[:, :dl], p["kv_a_layernorm"]["weight"],
                  c["rms_norm_eps"])
    kv = (r(latent) @ r(p["kv_b_proj"]["kernel"])).reshape(t, h, dn + dv)
    q = jnp.concatenate([q[..., :dn], rope_pairs(q[..., dn:], theta)], -1)
    k_r = rope_pairs(kva[:, None, dl:], theta)              # [T, 1, dr]
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_r, (t, h, dr))], -1)
    heads = lambda a: r(a).transpose(1, 0, 2)               # [H, T, D]
    out = lax.map(jax.checkpoint(_one_head),
                  (heads(q), heads(k), heads(kv[..., dn:])))
    return r(out.transpose(1, 0, 2).reshape(t, h * dv)) @ r(
        p["o_proj"]["kernel"])


def dense_mlp(x, p, r):
    return gated_mlp(x, p["gate_proj"]["kernel"], p["up_proj"]["kernel"],
                     p["down_proj"]["kernel"], r)


def route(x, p, c):
    """``(expert ids [T, k], weights [T, k])``: the k largest of ``σ(W_r x) +
    b`` over the router's whole width, weighted by the sigmoids alone,
    renormalised when ``norm_topk_prob`` and scaled."""
    scores = jax.nn.sigmoid(x @ p["router"]["kernel"])
    _, idx = lax.top_k(scores + p["e_score_correction_bias"],
                       c["num_experts_per_tok"])
    weights = jnp.take_along_axis(scores, idx, -1)
    if c["norm_topk_prob"]:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    return idx, weights * c["routed_scaling_factor"]


def routed_experts(x, p, c, r):
    """The held experts' part: every held expert applied to every token,
    weighted by the token's weight for it (zero where the expert is not
    among the token's k)."""
    idx, weights = route(x, p, c)
    first = c.get("held_experts", (0, c["n_routed_experts"]))[0]

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
    return gated_mlp(
        x, p["shared_gate_proj"]["kernel"], p["shared_up_proj"]["kernel"],
        p["shared_down_proj"]["kernel"], r)


def moe(x, p, c, r=rounder(None)):
    return routed_experts(x, p, c, r) + shared_expert(x, p, r)


def mix(x, p, c, r):
    """``h = x + MLA(N(x; w1))``: the first half of a block."""
    return x + attention(
        norm(x, p["norm1"]["weight"], c["rms_norm_eps"]), p["attn"], c, r)


def block(x, p, c, r):
    x = mix(x, p, c, r)
    h = norm(x, p["norm2"]["weight"], c["rms_norm_eps"])
    return x + (dense_mlp(h, p["mlp"], r) if "mlp" in p
                else moe(h, p["moe"], c, r))


def n_layers(params) -> int:
    return sum(1 for name in params if name.startswith("block_"))


def hidden(params, c, tokens, r, layers: int | None = None):
    """Final-norm hidden states of one sequence ``[T]`` (``layers``: the
    residual stream after that many blocks instead, no final norm)."""
    x = params["embed"]["embedding"][tokens]
    for i in range(n_layers(params) if layers is None else layers):
        p = params[f"block_{i}"]
        if ("mlp" in p) != (i < c["first_k_dense_replace"]):
            raise ValueError(f"block_{i} is not the layer kind the "
                             "configuration puts there")
        x = jax.checkpoint(lambda x, p: block(x, p, c, r))(x, p)
    if layers is not None:
        return x
    return norm(x, params["norm_f"]["weight"], c["rms_norm_eps"])


def sequence_logits(params, c, tokens, r=rounder(None)):
    return r(hidden(params, c, tokens, r)) @ r(params["lm_head"]["kernel"])


def sequence_loss(params, c, tokens, targets, r):
    logp = jax.nn.log_softmax(sequence_logits(params, c, tokens, r), axis=-1)
    return -jnp.take_along_axis(logp, targets[:, None], axis=-1).mean()


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


def first_sparse_routing(params, c, tokens):
    """The first sparse layer's routed expert ids ``[B, T, k]``: its router
    reads the embedding through the dense layers and one more attention, so
    two computations of one model differ there only by their own rounding."""
    with jax.default_matmul_precision("highest"):
        params = _f32(params)
        first = c["first_k_dense_replace"]
        p = params[f"block_{first}"]

        def one(row):
            x = mix(hidden(params, c, row, rounder(None), layers=first),
                    p, c, rounder(None))
            return route(norm(x, p["norm2"]["weight"], c["rms_norm_eps"]),
                         p["moe"], c)[0]

        return jnp.stack([one(row) for row in tokens])


def loss_and_grads(params, c, tokens, targets, sample, operand_dtype=None):
    """``(loss, {path: grad})`` for the tensors named in ``sample``."""
    picked = {path: get_leaf(params, path) for path in sample}
    return jax.value_and_grad(lambda s: loss(
        with_leaves(params, s), c, tokens, targets, operand_dtype))(picked)
