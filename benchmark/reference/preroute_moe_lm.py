"""Plain reference for ``smallthinker_21b_a3b``: the SmallThinker decoder as
PowerInfer/SmallThinker-21BA3B-Instruct configures it
(huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct, ``config.json``;
the SmallThinker report, arXiv:2507.20984) — softmax attention in every
layer, through a sliding window on the layers ``sliding_window_layout``
marks and rotary positions on those ``rope_layout`` marks (the others attend
over the whole prefix with no position encoding), then a sparse layer of
ReGLU experts whose router reads the block's normed input — with a token
embedding, a final norm, an untied head and mean next-token cross-entropy.

``x`` a token's hidden state, no bias anywhere, ``N(x; w) = x · rsqrt(mean(x²)
+ ε) · w``.  ``x₀ = E[token]``.  Block ``i``::

    r = N₁(x);   a = x + Attn_i(r);   y = a + MoE(N₂(a); route = r)

and after the last block a final ``N`` and the head.

- *Attention*, ``H`` query heads and ``H_kv`` key/value heads of ``head_dim``:
  ``q = W_q r``, ``k = W_k r``, ``v = W_v r``; on ``rope_layout`` layers ``q``
  and ``k`` are rotated by position on half-split pairs ``(j, j +
  head_dim/2)`` by ``t · θ^(−2j/head_dim)``; scores ``q·kᵀ / √head_dim``; key
  ``j`` is visible to query ``i`` iff ``j ≤ i`` and, on
  ``sliding_window_layout`` layers, ``j > i − sliding_window_size``; key head
  ``h // (H/H_kv)`` serves query head ``h``; ``out = W_o softmax(scores) v``.
- *Router*, float32: ``p = softmax(W_r r)`` over the router's whole width;
  ``S`` the ``moe_num_active_primary_experts`` largest; ``w_e = p_e /
  Σ_{j∈S} p_j`` for ``e ∈ S`` (no division unless ``norm_topk_prob``).
- *Experts* (ReGLU), on ``h = N₂(a)``: ``E_e(h) = W_down(ReLU(W_gate h) ⊙ W_up
  h)``; ``MoE = Σ_{e ∈ S ∩ held} w_e E_e(h)``.  No shared expert; no token is
  dropped.  ``held_experts = [first, count]`` are the experts whose weights
  are given; what the others would add is left out (the chip's share of an
  expert-parallel layer).

Straightforward ``jax.numpy`` in float32 under "highest" matmul precision; no
kernel, no sorting, no recomputation of anything the mathematics does not
ask for beyond ``jax.checkpoint`` (which changes no number).  Attention runs
one head at a time in blocks of queries against all keys, the window a mask
(``window_moe_lm``'s ``_one_head``); every held expert runs on every token
masked by its weight; the loss sums blocks of positions — only so that a
16 384-token sequence fits beside the resident training state.  Imports
nothing from the system; takes the system's parameter tree by name.

Departures from the published model are stated in the configuration file;
those that concern the equations: the router's input is ``N₁(x)`` (the
report says the router is placed before attention; the modelling code is
not on this machine), the experts' activation is ReLU (``described_as``:
"sparse ReGLU"; the config has no ``hidden_act``), no query/key norm and no
output gate (no config key), no secondary experts and no router auxiliary
loss.

``operand_dtype``: every matmul operand rounded through that dtype first
(``benchmark/reference/hybrid_moe_lm.py::rounder``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference.hybrid_moe_lm import _f32, rounder
from benchmark.reference.mla_moe_lm import norm
from benchmark.reference.transformer_lm import get_leaf, with_leaves
from benchmark.reference.window_moe_lm import LOSS_BLOCK, _one_head, rope

__all__ = ["get_leaf", "with_leaves", "rounder"]


def _layer(c, key: str, i: int) -> bool:
    flag = c[key][i]
    if flag not in (0, 1):
        raise ValueError(f"{key}[{i}] = {flag!r}")
    return bool(flag)


def attention(r_in, p, c, r, i: int):
    """``Attn_i(r_in)``: layer ``i``'s attention of the normed input."""
    t = r_in.shape[0]
    h, hkv, dh = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    q = (r(r_in) @ r(p["q_proj"]["kernel"])).reshape(t, h, dh)
    k = (r(r_in) @ r(p["k_proj"]["kernel"])).reshape(t, hkv, dh)
    v = (r(r_in) @ r(p["v_proj"]["kernel"])).reshape(t, hkv, dh)
    if _layer(c, "rope_layout", i):
        theta = float(c["rope_theta"])
        q, k = rope(q, theta), rope(k, theta)
    heads = lambda a: r(a).transpose(1, 0, 2)               # [heads, T, D]
    wide = lambda a: jnp.repeat(heads(a), h // hkv, axis=0)  # kv head h // rep
    window = (c["sliding_window_size"]
              if _layer(c, "sliding_window_layout", i) else None)
    out = lax.map(jax.checkpoint(lambda qkv: _one_head(qkv, window)),
                  (heads(q), wide(k), wide(v)))
    return r(out.transpose(1, 0, 2).reshape(t, h * dh)) @ r(
        p["o_proj"]["kernel"])


def route(r_in, p, c):
    """``(expert ids [T, k], weights [T, k])``: the k largest of the
    router's softmax over its whole width, renormalised when
    ``norm_topk_prob``."""
    probs = jax.nn.softmax(r_in @ p["router"]["kernel"], axis=-1)
    weights, idx = lax.top_k(probs, c["moe_num_active_primary_experts"])
    if c["norm_topk_prob"]:
        weights = weights / weights.sum(-1, keepdims=True)
    return idx, weights


def reglu(h, w_gate, w_up, w_down, r):
    return r(jax.nn.relu(r(h) @ r(w_gate)) * (r(h) @ r(w_up))) @ r(w_down)


def moe(h, r_in, p, c, r=rounder(None)):
    """The held experts' part, routed from ``r_in``: every held expert
    applied to every token of ``h``, weighted by the token's weight for it
    (zero where the expert is not among the token's k)."""
    idx, weights = route(r_in, p, c)
    first = c.get("held_experts", (0, c["moe_num_primary_experts"]))[0]

    @jax.checkpoint
    def one(y, expert):
        e, w_gate, w_up, w_down = expert
        weight = jnp.sum(jnp.where(idx == first + e, weights, 0.0), -1)
        return y + weight[:, None] * reglu(h, w_gate, w_up, w_down, r), None

    held = p["w_up"].shape[0]
    y, _ = lax.scan(one, jnp.zeros_like(h),
                    (jnp.arange(held), p["w_gate"], p["w_up"], p["w_down"]))
    return y


def block(x, p, c, r, i: int):
    eps = c["rms_norm_eps"]
    r_in = norm(x, p["input_layernorm"]["weight"], eps)
    a = x + attention(r_in, p["attn"], c, r, i)
    h = norm(a, p["post_attention_layernorm"]["weight"], eps)
    return a + moe(h, r_in, p["moe"], c, r)


def hidden(params, c, tokens, r):
    """Final-norm hidden states of one sequence ``[T]``."""
    x = params["embed"]["embedding"][tokens]
    n = c["num_hidden_layers"]
    if any(f"block_{i}" not in params for i in range(n)) or \
            f"block_{n}" in params:
        raise ValueError(f"the parameters do not hold {n} blocks")
    for i in range(n):
        x = jax.checkpoint(lambda x, p, i=i: block(x, p, c, r, i))(
            x, params[f"block_{i}"])
    return norm(x, params["norm_f"]["weight"], c["rms_norm_eps"])


def sequence_logits(params, c, tokens, r=rounder(None)):
    return r(hidden(params, c, tokens, r)) @ r(params["lm_head"]["kernel"])


def sequence_loss(params, c, tokens, targets, r):
    """Mean next-token cross-entropy of one sequence, a block of positions'
    logits at a time (16 384 × 37 984 float32 logits, their log-softmax and
    their cotangent would be 7 GiB beside the resident state)."""
    h, w = r(hidden(params, c, tokens, r)), r(params["lm_head"]["kernel"])
    t, d = h.shape
    block_rows = LOSS_BLOCK if t % LOSS_BLOCK == 0 else t

    @jax.checkpoint
    def rows(args):
        h_rows, target_rows = args
        logp = jax.nn.log_softmax(h_rows @ w, axis=-1)
        return -jnp.take_along_axis(logp, target_rows[:, None], axis=-1).sum()

    return lax.map(rows, (h.reshape(-1, block_rows, d),
                          targets.reshape(-1, block_rows))).sum() / t


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


def _layer_inputs(params, c, row):
    """Each layer's ``(r, h)`` for one sequence: the router's input and the
    experts'."""
    eps, r = c["rms_norm_eps"], rounder(None)
    x, out = params["embed"]["embedding"][row], []
    for i in range(c["num_hidden_layers"]):
        p = params[f"block_{i}"]
        r_in = norm(x, p["input_layernorm"]["weight"], eps)
        a = x + attention(r_in, p["attn"], c, r, i)
        out.append((r_in, norm(a, p["post_attention_layernorm"]["weight"],
                               eps)))
        x = a + moe(out[-1][1], r_in, p["moe"], c, r)
    return out


def routing(params, c, tokens):
    """Every layer's routed expert ids: ``{block name: [B, T, k]}``.  Layer
    0's router reads the embedding through one norm, so two computations of
    one model differ there only by their own rounding; the later ones' also
    by every choice that fell differently before them."""
    with jax.default_matmul_precision("highest"):
        params = _f32(params)
        rows = [[route(r_in, params[f"block_{i}"]["moe"], c)[0]
                 for i, (r_in, _) in enumerate(_layer_inputs(params, c, row))]
                for row in tokens]
        return {f"block_{i}": jnp.stack([ids[i] for ids in rows])
                for i in range(c["num_hidden_layers"])}


def reglu_active_share(params, c, tokens):
    """The share of the held experts' hidden units that ReLU passes
    (``W_gate h > 0``), over the rows routed to a held expert, a layer;
    the mean over the layers."""
    with jax.default_matmul_precision("highest"):
        params = _f32(params)
        first = c.get("held_experts", (0, c["moe_num_primary_experts"]))[0]
        n = c["num_hidden_layers"]
        active, rows = [0] * n, [0] * n
        for row in tokens:
            for i, (r_in, h) in enumerate(_layer_inputs(params, c, row)):
                p = params[f"block_{i}"]["moe"]
                idx, _ = route(r_in, p, c)
                held = first + jnp.arange(p["w_gate"].shape[0])
                routed = (idx[:, :, None] == held).any(1)     # [T, held]
                gate = jnp.einsum("td,edf->tef", h, p["w_gate"])
                active[i] += jnp.sum((gate > 0) & routed[..., None])
                rows[i] += jnp.sum(routed)
        return jnp.mean(jnp.stack([
            a / (m * c["moe_ffn_hidden_size"]) for a, m in zip(active, rows)]))


def loss_and_grads(params, c, tokens, targets, sample, operand_dtype=None):
    """``(loss, {path: grad})`` for the tensors named in ``sample``."""
    picked = {path: get_leaf(params, path) for path in sample}
    return jax.value_and_grad(lambda s: loss(
        with_leaves(params, s), c, tokens, targets, operand_dtype))(picked)
