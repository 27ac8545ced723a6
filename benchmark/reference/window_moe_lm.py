"""Plain reference for ``trinity_mini_26b_a3b``: the ``afmoe`` decoder as
arcee-ai/Trinity-Mini configures it (huggingface.co/arcee-ai/Trinity-Mini,
``config.json``) — gated softmax attention in every layer, through a sliding
window with rotary positions on ``sliding_attention`` layers and over the
whole prefix with no position encoding on ``full_attention`` layers, four
norms a block, leading dense gated-SiLU layers, then sparse layers routed by
``sigmoid score + bias`` with an ungated shared expert — with a token
embedding scaled by ``√d``, a final norm, an untied head, mean next-token
cross-entropy, and the rule that moves the bias after a step.

``x`` a token's hidden state, no bias anywhere, ``N(x; w) = x · rsqrt(mean(x²)
+ ε) · w``.  ``x₀ = E[token] · √d`` (``mup_enabled``).  Block ``i``::

    a = x + N₂(Attn_i(N₁(x)));   y = a + N₄(F_i(N₃(a)));   final N, the head

``F_i`` the dense MLP for ``i < num_dense_layers``, else the sparse layer.

- *Attention*, ``H`` query heads and ``H_kv`` key/value heads of ``head_dim``:
  ``[q | g] = W_q x`` (``H·head_dim`` columns each), ``k = W_k x``, ``v = W_v
  x``; ``q ← N(q; w_q)``, ``k ← N(k; w_k)`` over each head's dimensions; on
  ``sliding_attention`` layers ONLY, ``q`` and ``k`` are rotated by position
  on half-split pairs ``(j, j + head_dim/2)`` by ``t · θ^(−2j/head_dim)``;
  scores ``q·kᵀ / √head_dim``; key ``j`` is visible to query ``i`` iff ``j ≤
  i`` and, on ``sliding_attention`` layers, ``j > i − sliding_window``; key
  head ``h // (H/H_kv)`` serves query head ``h``; ``out = W_o (softmax(scores)
  v ⊙ σ(g))``.
- *Dense MLP / expert / shared expert*: ``W_down(SiLU(W_gate x) ⊙ W_up x)``.
- *Router*, float32: ``s = σ(W_r x)``; ``S = top-k(s + b)``; ``w_e =
  route_scale · s_e / (Σ_{j∈S} s_j + 1e-20)`` for ``e ∈ S`` (no division
  unless ``route_norm``) — the bias ``b`` picks, it never weighs.  ``F(x) =
  Σ_{e ∈ S ∩ held} w_e E_e(x) + E_shared(x)``.  No token is dropped.
  ``held_experts = [first, count]`` are the experts whose weights are given;
  what the others would add is left out (the chip's share of an
  expert-parallel layer).
- *Balancing* (``load_balance_coeff`` ``u``), once a step, a sparse layer:
  ``c_e`` the step's assignments to expert ``e`` over the router's whole
  width; ``b_e ← b_e + u · sign(mean(c) − c_e)``.  No gradient reaches ``b``.

Straightforward ``jax.numpy`` in float32 under "highest" matmul precision; no
kernel, no sorting, no recomputation of anything the mathematics does not
ask for beyond ``jax.checkpoint`` (which changes no number).  Attention runs
one head at a time in blocks of queries against all keys, the window a mask;
every held expert runs on every token masked by its weight; the loss sums
blocks of positions — only so that a 16 384-token sequence fits beside the
resident training state.  Imports
nothing from the system; takes the system's parameter tree by name, ``b``
among it.

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
from benchmark.reference.mla_moe_lm import dense_mlp, norm, shared_expert
from benchmark.reference.transformer_lm import get_leaf, with_leaves

__all__ = ["get_leaf", "with_leaves", "rounder", "shared_expert"]

QUERY_BLOCK = 1024  # queries scored against all keys at once
LOSS_BLOCK = 2048  # positions whose logits exist at once




def rope(x, base):
    """Rotate ``[T, H, D]`` by position: halves ``(x1, x2)`` of each head go
    to ``(x1·cos − x2·sin, x1·sin + x2·cos)``, angle ``t · base^(−j/(D/2))``
    for pair ``j``."""
    t, _, d = x.shape
    freqs = base ** (-jnp.arange(d // 2, dtype=jnp.float32) / (d // 2))
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _one_head(qkv, window):
    """Softmax attention of one head: ``q, k, v`` [T, D], key ``j`` visible
    to query ``i`` iff ``i − window < j ≤ i`` (``window`` None: ``j ≤ i``), a
    block of queries at a time."""
    q, k, v = qkv
    t, d = q.shape
    block = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t

    @jax.checkpoint
    def rows(args):
        q_rows, first = args
        scores = (q_rows @ k.T) / math.sqrt(d)
        i = (first + jnp.arange(block))[:, None]
        j = jnp.arange(t)[None, :]
        visible = j <= i
        if window is not None:
            visible &= j > i - window
        return jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), -1) @ v

    out = lax.map(rows, (q.reshape(-1, block, d), jnp.arange(0, t, block)))
    return out.reshape(t, d)


def attention(x, p, c, r, sliding: bool):
    t = x.shape[0]
    h, hkv, dh = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    eps = c["rms_norm_eps"]
    q_gate = r(x) @ r(p["q_proj"]["kernel"])
    q = q_gate[:, :h * dh].reshape(t, h, dh)
    gate = q_gate[:, h * dh:]
    k = (r(x) @ r(p["k_proj"]["kernel"])).reshape(t, hkv, dh)
    v = (r(x) @ r(p["v_proj"]["kernel"])).reshape(t, hkv, dh)
    q = norm(q, p["q_norm"]["weight"], eps)
    k = norm(k, p["k_norm"]["weight"], eps)
    if sliding:
        theta = float(c["rope_theta"])
        q, k = rope(q, theta), rope(k, theta)
    heads = lambda a: r(a).transpose(1, 0, 2)               # [heads, T, D]
    wide = lambda a: jnp.repeat(heads(a), h // hkv, axis=0)  # kv head h // rep
    window = c["sliding_window"] if sliding else None
    out = lax.map(jax.checkpoint(lambda qkv: _one_head(qkv, window)),
                  (heads(q), wide(k), wide(v)))
    out = out.transpose(1, 0, 2).reshape(t, h * dh) * jax.nn.sigmoid(gate)
    return r(out) @ r(p["o_proj"]["kernel"])




def route(x, p, c):
    """``(expert ids [T, k], weights [T, k])``: the k largest of ``σ(W_r x) +
    b`` over the router's whole width, weighted by the sigmoids alone,
    renormalised when ``route_norm`` and scaled."""
    scores = jax.nn.sigmoid(x @ p["router"]["kernel"])
    _, idx = lax.top_k(scores + p["e_score_correction_bias"],
                       c["num_experts_per_tok"])
    weights = jnp.take_along_axis(scores, idx, -1)
    if c["route_norm"]:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    return idx, weights * c["route_scale"]


def assignment_counts(idx, width: int):
    """``c`` [width]: how many of the assignments ``idx`` went to each of the
    router's experts."""
    return jnp.sum(idx[..., None] == jnp.arange(width),
                   axis=tuple(range(idx.ndim)), dtype=jnp.int32)


def balanced_bias(b, counts, u: float):
    """``b_e + u · sign(mean(c) − c_e)``."""
    counts = counts.astype(jnp.float32)
    return b + u * jnp.sign(counts.mean() - counts)


def routed_experts(x, p, c, r):
    """The held experts' part: every held expert applied to every token,
    weighted by the token's weight for it (zero where the expert is not
    among the token's k)."""
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




def moe(x, p, c, r=rounder(None)):
    return routed_experts(x, p, c, r) + shared_expert(x, p, r)


def mix(x, p, c, r, sliding: bool):
    """``a = x + N₂(Attn(N₁(x)))``: the first half of a block."""
    eps = c["rms_norm_eps"]
    h = attention(norm(x, p["input_layernorm"]["weight"], eps), p["attn"],
                  c, r, sliding)
    return x + norm(h, p["post_attention_layernorm"]["weight"], eps)


def feed_forward(a, p, c, r):
    """``y = a + N₄(F(N₃(a)))``: the second half of a block."""
    eps = c["rms_norm_eps"]
    h = norm(a, p["pre_mlp_layernorm"]["weight"], eps)
    h = dense_mlp(h, p["mlp"], r) if "mlp" in p else moe(h, p["moe"], c, r)
    return a + norm(h, p["post_mlp_layernorm"]["weight"], eps)


def _sliding(c, i: int) -> bool:
    kind = c["layer_types"][i]
    if kind not in ("sliding_attention", "full_attention"):
        raise ValueError(f"layer_types[{i}] = {kind!r}")
    return kind == "sliding_attention"


def embed(params, c, tokens):
    scale = math.sqrt(c["hidden_size"]) if c.get("mup_enabled") else 1.0
    return params["embed"]["embedding"][tokens] * scale


def hidden(params, c, tokens, r, layers: int | None = None):
    """Final-norm hidden states of one sequence ``[T]`` (``layers``: the
    residual stream after that many blocks instead, no final norm)."""
    x = embed(params, c, tokens)
    n = len(c["layer_types"])
    if any(f"block_{i}" not in params for i in range(n)) or \
            f"block_{n}" in params:
        raise ValueError(f"the parameters do not hold {n} blocks")
    for i in range(n if layers is None else layers):
        p = params[f"block_{i}"]
        if ("mlp" in p) != (i < c["num_dense_layers"]):
            raise ValueError(f"block_{i} is not the layer kind the "
                             "configuration puts there")
        a = jax.checkpoint(
            lambda x, p, i=i: mix(x, p, c, r, _sliding(c, i)))(x, p)
        x = jax.checkpoint(lambda a, p: feed_forward(a, p, c, r))(a, p)
    if layers is not None:
        return x
    return norm(x, params["norm_f"]["weight"], c["rms_norm_eps"])


def sequence_logits(params, c, tokens, r=rounder(None)):
    return r(hidden(params, c, tokens, r)) @ r(params["lm_head"]["kernel"])


def sequence_loss(params, c, tokens, targets, r):
    """Mean next-token cross-entropy of one sequence, a block of positions'
    logits at a time (16 384 × 25 024 float32 logits, their log-softmax and
    their cotangent would be 4.6 GiB beside the resident state)."""
    h, w = r(hidden(params, c, tokens, r)), r(params["lm_head"]["kernel"])
    t, d = h.shape
    block = LOSS_BLOCK if t % LOSS_BLOCK == 0 else t

    @jax.checkpoint
    def rows(args):
        h_rows, target_rows = args
        logp = jax.nn.log_softmax(h_rows @ w, axis=-1)
        return -jnp.take_along_axis(logp, target_rows[:, None], axis=-1).sum()

    return lax.map(rows, (h.reshape(-1, block, d),
                          targets.reshape(-1, block))).sum() / t


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


def sparse_routing(params, c, tokens):
    """Every sparse layer's routed expert ids: ``{block name: [B, T, k]}``.
    The first sparse layer's router reads the embedding through the dense
    layers and one more attention, so two computations of one model differ
    there only by their own rounding; the later ones' also by every choice
    that fell differently before them."""
    with jax.default_matmul_precision("highest"):
        params, r = _f32(params), rounder(None)
        eps = c["rms_norm_eps"]

        def one(row):
            x, ids = embed(params, c, row), {}
            for i in range(len(c["layer_types"])):
                p = params[f"block_{i}"]
                a = mix(x, p, c, r, _sliding(c, i))
                if "moe" in p:
                    ids[f"block_{i}"] = route(
                        norm(a, p["pre_mlp_layernorm"]["weight"], eps),
                        p["moe"], c)[0]
                x = feed_forward(a, p, c, r)
            return ids

        rows = [one(row) for row in tokens]
        return {name: jnp.stack([ids[name] for ids in rows])
                for name in rows[0]}


def first_sparse_routing(params, c, tokens):
    """The first sparse layer's routed expert ids ``[B, T, k]``."""
    return sparse_routing(params, c, tokens)[f"block_{c['num_dense_layers']}"]


def sparse_counts(params, c, tokens):
    """``{block name: c [router width]}``: each sparse layer's assignments
    to every expert over all of ``tokens`` ``[B, T]``."""
    width = c.get("router_width", c["num_experts"])
    return {name: assignment_counts(ids, width)
            for name, ids in sparse_routing(params, c, tokens).items()}


def loss_and_grads(params, c, tokens, targets, sample, operand_dtype=None):
    """``(loss, {path: grad})`` for the tensors named in ``sample``."""
    picked = {path: get_leaf(params, path) for path in sample}
    return jax.value_and_grad(lambda s: loss(
        with_leaves(params, s), c, tokens, targets, operand_dtype))(picked)
