"""Routed-ahead mixture-of-experts LM (``model_type`` ``smallthinker``):
softmax attention in every layer — through a sliding window on the layers
``sliding_window_layout`` marks, over the whole prefix on the others; rotary
positions on the layers ``rope_layout`` marks, none on the others —, then a
sparse layer of ReGLU experts whose router reads the block's normed INPUT,
the tensor the attention reads, not the residual after it.

Block ``i``::

    r = N₁(x)        a = x + Attn_i(r)        y = a + MoE(N₂(a); route = r)

with ``N(x; w) = x · rsqrt(mean(x²) + ε) · w`` in float32 (a plain RMSNorm
weight), ``MoE(h; route = r)`` the experts' sum over ``h`` weighted by the
top-k of ``softmax(r·W_r)`` renormalised over the chosen, an expert
``W_down(ReLU(W_gate h) ⊙ W_up h)``, no shared expert; a final ``N`` and an
untied, bias-free head.  No projection has a bias, the embedding is not
scaled.  The plain float32 restatement the tests and the benchmark compare
against is ``benchmark/reference/preroute_moe_lm.py``.

This module holds only what differs from its siblings: the mixer is
``models/hybrid_moe.py::GatedAttention`` by settings (no output gate, no
query/key norm, a window or none, a rotary width or none), the expert layer
that file's ``SparseMoE`` (routed from ``r``, ReLU-gated experts that count
the hidden units their gate passes, no shared expert).  Because the routing
depends on ``r`` alone, it does not wait for the attention: an
expert-parallel deployment can send the tokens to their experts while the
attention runs; on one chip nothing is sent.

A chip's share of the experts (``router_width``, ``held_experts``) works as
``HybridMoELM``'s does.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributed_machine_learning_tpu.models.hybrid_moe import (
    _INIT,
    STATS_COLLECTION,
    GatedAttention,
    RMSNorm,
    SparseMoE,
    _dense,
    routing_counts,
)
from distributed_machine_learning_tpu.models.transformer import (
    remat_whole_block,
)


@dataclasses.dataclass(frozen=True)
class PrerouteMoESizes:
    """The sizes an HF-style ``smallthinker`` configuration states."""

    vocab_size: int
    d_model: int
    sliding: tuple  # a layer attends through the window
    rotary: tuple  # a layer rotates q and k
    window: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    rope_base: float
    router_width: int
    held_experts: tuple  # (first, count)
    experts_per_token: int
    expert_d_ff: int
    norm_topk_prob: bool
    rms_eps: float

    @property
    def n_layers(self) -> int:
        return len(self.sliding)

    @classmethod
    def from_config(cls, config: dict) -> "PrerouteMoESizes":
        """``moe_num_primary_experts`` counts the experts HELD here and
        ``router_width`` (default: the same) the layer's; ``held_experts`` is
        ``[first, count]`` (default ``[0, moe_num_primary_experts]``).  The
        experts are ReGLU: a file may state ``hidden_act`` ``relu`` only."""
        only = {"moe_primary_router_apply_softmax": True, "hidden_act": "relu",
                "rope_scaling": None, "tie_word_embeddings": False}
        for key, value in only.items():
            if config.get(key, value) != value:
                raise ValueError(
                    f"model_type smallthinker supports {key} = {value!r} "
                    f"only (got {config[key]!r})")
        experts = config["moe_num_primary_experts"]
        held = tuple(config.get("held_experts", (0, experts)))
        if len(held) != 2 or held[1] != experts:
            raise ValueError(
                f"held_experts {held} must be [first, count] with count = "
                f"moe_num_primary_experts = {experts}")
        layers = config["num_hidden_layers"]
        layouts = {key: tuple(config[key]) for key in (
            "sliding_window_layout", "rope_layout")}
        for key, layout in layouts.items():
            if len(layout) != layers or not set(layout) <= {0, 1}:
                raise ValueError(
                    f"{key} must give 0 or 1 for each of the {layers} "
                    f"layers, got {list(layout)}")
        return cls(
            vocab_size=config["vocab_size"],
            d_model=config["hidden_size"],
            sliding=tuple(map(bool, layouts["sliding_window_layout"])),
            rotary=tuple(map(bool, layouts["rope_layout"])),
            window=config["sliding_window_size"],
            n_heads=config["num_attention_heads"],
            n_kv_heads=config["num_key_value_heads"],
            head_dim=config["head_dim"],
            rope_base=float(config["rope_theta"]),
            router_width=config.get("router_width", experts),
            held_experts=held,
            experts_per_token=config["moe_num_active_primary_experts"],
            expert_d_ff=config["moe_ffn_hidden_size"],
            norm_topk_prob=config["norm_topk_prob"],
            rms_eps=config["rms_norm_eps"],
        )


def _norm(mdl, name: str) -> RMSNorm:
    return RMSNorm(mdl.sizes.rms_eps, mdl.compute_dtype, zero_centred=False,
                   name=name)


def _experts(mdl: "PrerouteMoEBlock", a, r):
    """Norm 2 and the sparse layer routed from ``r`` (residual added by the
    caller): a function of the block, so that ``nn.remat`` can lift it
    without moving a parameter (``models/window_moe.py::_feed_forward``'s
    arrangement)."""
    m = mdl.sizes
    return SparseMoE(
        router_width=m.router_width, held_experts=m.held_experts,
        experts_per_token=m.experts_per_token, d_ff=m.expert_d_ff,
        shared_d_ff=0, norm_topk_prob=m.norm_topk_prob,
        compute_dtype=mdl.compute_dtype, activation="relu",
        name="moe")(_norm(mdl, "post_attention_layernorm")(a), route_from=r)


class PrerouteMoEBlock(nn.Module):
    sizes: PrerouteMoESizes
    sliding: bool
    rotary: bool
    attn_impl: str
    compute_dtype: Any
    remat_ffn: bool = False

    @nn.compact
    def __call__(self, x, positions):
        from distributed_machine_learning_tpu.ops.pallas.flash_attention import (  # noqa: E501
            active_tiles,
        )

        m = self.sizes
        window = m.window if self.sliding and m.window < x.shape[1] else None
        r = _norm(self, "input_layernorm")(x)
        mixed = GatedAttention(
            n_heads=m.n_heads, n_kv_heads=m.n_kv_heads, head_dim=m.head_dim,
            rotary_dim=m.head_dim if self.rotary else 0,
            rope_base=m.rope_base, eps=m.rms_eps, attn_impl=self.attn_impl,
            compute_dtype=self.compute_dtype, window=window, gated=False,
            qk_norm=False, name="attn")(r, positions)
        if self.attn_impl == "flash":
            # From shapes alone: the tiles this layer's kernels compute of
            # the causal triangle's, and whether they are windowed calls.
            self.sow(STATS_COLLECTION, "attn_tiles", jnp.asarray(
                [*active_tiles(x.shape[1], window), window is not None],
                jnp.float32))
        a = x + mixed
        sublayer = nn.remat(_experts) if self.remat_ffn else _experts
        return a + sublayer(self, a, r)


class PrerouteMoELM(nn.Module):
    """Causal LM: tokens [B, L] → logits [B, L, vocab] (module docstring).
    Sequence-local attention only (``attn_impl`` ``"dense"`` or ``"flash"``);
    ``remat`` / ``remat_policy`` as ``HybridMoELM``'s (``"mlp"``: norm 2 and
    the sparse layer recomputed in the backward pass, ``r`` kept; ``"block"``:
    the whole block, routing among it, but for what
    ``models/transformer.py::whole_block_policy`` keeps — the flash kernel's
    inputs and outputs, the attention's output, the router's logits, the
    sort's integers, the dispatched rows and the two pre-activations)."""

    sizes: PrerouteMoESizes
    attn_impl: str = "dense"
    compute_dtype: Any = jnp.float32
    remat: bool = False
    remat_policy: str = "mlp"

    #: What ``train/lm_step.py`` asks a model that counts (as
    #: ``HybridMoELM``).
    stats_collection = STATS_COLLECTION
    stats_counters = ("moe_held_rows", "moe_dropped_rows",
                      "attn_window_calls")
    step_stats = staticmethod(routing_counts)

    @nn.compact
    def __call__(self, tokens, *, train: bool = False,
                 return_hidden: bool = False):
        del train  # no dropout; kept for the shared train-step interface
        if self.attn_impl not in ("dense", "flash"):
            raise ValueError(
                "PrerouteMoELM runs sequence-local attention only (attn_impl "
                f"'dense' or 'flash', got {self.attn_impl!r})")
        if self.remat_policy not in ("mlp", "block"):
            raise ValueError(
                f"remat_policy must be 'mlp' or 'block', got "
                f"{self.remat_policy!r}")
        m, dt = self.sizes, self.compute_dtype
        positions = jnp.arange(tokens.shape[1])
        x = nn.Embed(m.vocab_size, m.d_model, dtype=dt,
                     embedding_init=_INIT, name="embed")(tokens)
        whole_block = self.remat and self.remat_policy == "block"
        block_cls = (remat_whole_block(PrerouteMoEBlock) if whole_block
                     else PrerouteMoEBlock)
        for i in range(m.n_layers):
            x = block_cls(
                sizes=m, sliding=m.sliding[i], rotary=m.rotary[i],
                attn_impl=self.attn_impl, compute_dtype=dt,
                remat_ffn=self.remat and self.remat_policy == "mlp",
                name=f"block_{i}")(x, positions)
        x = RMSNorm(m.rms_eps, dt, zero_centred=False, name="norm_f")(x)
        if return_hidden:
            return x
        with jax.named_scope("head"):
            logits = _dense(m.vocab_size, dt, "lm_head")(x)
        return logits.astype(jnp.float32)
