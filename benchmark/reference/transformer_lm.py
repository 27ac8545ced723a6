"""Plain reference for ``starcoder2_3b``: the StarCoder2 decoder block
(arXiv:2402.19173) — pre-LayerNorm with bias, rotary positions on the full
head, grouped-query causal attention, biased projections, a tanh-GELU MLP —
with a token embedding, a final LayerNorm, an output head and mean next-token
cross-entropy.

Straightforward ``jax.numpy`` in float32 under "highest" matmul precision; no
kernel, no fusion, no cache.  Imports nothing from the system; takes the
system's parameter tree by name (``embed``, ``block_i/{ln1,attn/{q,kv,out},
ln2,fc_in,fc_out}``, ``ln_f``, ``lm_head``).  Attention runs one head at a
time and each block is rematerialized in the backward pass, only so that a
4096-token sequence fits beside the resident training state.

Departures from the published model, all the system's: LayerNorm epsilon 1e-6
(published 1e-5), RoPE base 10000 (published about 1e6), an output head of its
own with a bias (published: tied to the embedding), no sliding window (inert
up to 4096 tokens).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

LN_EPS = 1e-6
ROPE_BASE = 10000.0


def layer_norm(x, p):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + LN_EPS) * p["scale"] + p["bias"]


def rope(x):
    """Rotate ``[T, H, D]`` by position: halves ``(x1, x2)`` of the head go to
    ``(x1·cos − x2·sin, x1·sin + x2·cos)`` (the rotate-half convention)."""
    t, _, d = x.shape
    freqs = ROPE_BASE ** (-jnp.arange(d // 2, dtype=jnp.float32) / (d // 2))
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _one_head(qkv):
    q, k, v = qkv  # [T, D] each
    t, d = q.shape
    scores = (q @ k.T) / math.sqrt(d)
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    return probs @ v


def expand_kv(a, rep):
    """``[Hkv, T, D]`` → ``[H, T, D]``: each key/value head serves ``rep``
    neighbouring query heads."""
    return jnp.repeat(a, rep, axis=0)


def attention(h, p):
    """Grouped-query causal self-attention on one sequence ``[T, E]``: query
    head ``i`` reads key/value head ``i // (H / Hkv)``."""
    if "qkv" in p:
        raise ValueError("the reference covers the grouped-query layout "
                         "(separate q and kv projections) only")
    q = jnp.einsum("te,ehd->thd", h, p["q"]["kernel"]) + p["q"]["bias"]
    kv = jnp.einsum("te,eshd->tshd", h, p["kv"]["kernel"]) + p["kv"]["bias"]
    k, v = kv[:, 0], kv[:, 1]
    rep = q.shape[1] // k.shape[1]
    q, k = rope(q), rope(k)
    heads = lambda a: a.transpose(1, 0, 2)  # [H, T, D]
    out = jax.lax.map(
        jax.checkpoint(_one_head),
        (heads(q), expand_kv(heads(k), rep), expand_kv(heads(v), rep)),
    )
    return (jnp.einsum("htd,hde->te", out, p["out"]["kernel"])
            + p["out"]["bias"])


def mlp(h, p):
    h = h @ p["fc_in"]["kernel"] + p["fc_in"]["bias"]
    h = jax.nn.gelu(h, approximate=True)
    return h @ p["fc_out"]["kernel"] + p["fc_out"]["bias"]


def block(x, p):
    x = x + attention(layer_norm(x, p["ln1"]), p["attn"])
    return x + mlp(layer_norm(x, p["ln2"]), p)


def sequence_loss(params, tokens, targets):
    """Mean next-token cross-entropy of one sequence ``[T]``."""
    x = params["embed"]["embedding"][tokens]
    n_layers = sum(1 for name in params if name.startswith("block_"))
    for i in range(n_layers):
        x = jax.checkpoint(block)(x, params[f"block_{i}"])
    x = layer_norm(x, params["ln_f"])
    logits = x @ params["lm_head"]["kernel"] + params["lm_head"]["bias"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[:, None], axis=-1).mean()


def loss(params, tokens, targets):
    """Mean over the sequences of ``tokens`` ``[B, T]``."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float32), params)
        per_seq = [sequence_loss(params, tokens[i], targets[i])
                   for i in range(tokens.shape[0])]
        return sum(per_seq) / len(per_seq)


def with_leaves(params, leaves):
    """A copy of the nested ``params`` dict with ``{"a/b/c": value}`` leaves
    replaced — how a gradient is taken for a sample of tensors only."""
    out = dict(params)
    for path, value in leaves.items():
        head, _, rest = path.partition("/")
        out[head] = (with_leaves(out[head], {rest: value}) if rest else value)
    return out


def get_leaf(params, path):
    for key in path.split("/"):
        params = params[key]
    return params


def loss_and_grads(params, tokens, targets, sample):
    """``(loss, {path: grad})`` for the tensors named in ``sample``."""
    picked = {path: get_leaf(params, path) for path in sample}
    return jax.value_and_grad(
        lambda s: loss(with_leaves(params, s), tokens, targets))(picked)
