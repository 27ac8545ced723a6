"""Sliding-window / full-attention mixture-of-experts LM (``model_type``
``afmoe``): gated softmax attention in every layer — through a sliding
window with rotary positions on ``sliding_attention`` layers, over the whole
prefix with NO position encoding on ``full_attention`` layers —, four norms a
block, ``num_dense_layers`` leading layers with a dense gated-SiLU MLP, then
sparse layers whose router selects by ``sigmoid score + bias`` and whose bias
the balancing rule moves after every step.

Block ``i`` (sandwich norms)::

    a = x + N₂(Attn_i(N₁(x)))        y = a + N₄(F_i(N₃(a)))

with ``N(x; w) = x · rsqrt(mean(x²) + ε) · w`` in float32 (a plain RMSNorm
weight), ``F_i`` the dense MLP for ``i < num_dense_layers`` and the sparse
layer after; the embedding is scaled by ``√d`` (``mup_enabled``); a final
``N`` and an untied, bias-free head.  No projection has a bias.  The plain
float32 restatement the tests and the benchmark compare against is
``benchmark/reference/window_moe_lm.py``.

This module holds only what differs from its siblings: the mixer is
``models/hybrid_moe.py::GatedAttention`` by settings (a window or none, a
rotary width or none, plain norms), the expert layer that file's
``SparseMoE`` (sigmoid scores, a selection bias, a scale, one ungated shared
expert, its assignments counted), the dense MLP and the norm
``models/mla_moe.py``'s.

To the train step this module is what its siblings are, plus one thing: the
selection bias ``b`` is in ``frozen_params`` (no gradient, no AdamW step, no
decay) AND in ``param_rules``, by which ``train/lm_step.py::_update`` sets
``b ← b + u · sign(mean(c) − c)`` from the step's assignment counts ``c``
(``load_balance_coeff`` ``u``; ``hybrid_moe.balanced_bias``).  A chip's share
of the experts (``router_width``, ``held_experts``) works as ``HybridMoELM``'s
does: ``c`` counts the assignments to all ``router_width`` experts, held here
or not.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from distributed_machine_learning_tpu.models.hybrid_moe import (
    FFN_OUT,
    _INIT,
    ASSIGNMENTS,
    SELECTION_BIAS,
    STATS_COLLECTION,
    GatedAttention,
    RMSNorm,
    SparseMoE,
    _dense,
    balanced_bias,
    routing_counts,
)
from distributed_machine_learning_tpu.models.mla_moe import DenseMLP
from distributed_machine_learning_tpu.models.transformer import (
    remat_whole_block,
)

LAYER_TYPES = ("sliding_attention", "full_attention")


@dataclasses.dataclass(frozen=True)
class WindowMoESizes:
    """The sizes an HF-style ``afmoe`` configuration states."""

    vocab_size: int
    d_model: int
    layer_types: tuple  # one of LAYER_TYPES a layer
    window: int
    n_dense: int
    dense_d_ff: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    rope_base: float
    router_width: int
    held_experts: tuple  # (first, count)
    experts_per_token: int
    expert_d_ff: int
    shared_d_ff: int
    routed_scale: float
    norm_topk_prob: bool
    balance_rate: float | None
    embed_scale: float
    rms_eps: float

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @classmethod
    def from_config(cls, config: dict) -> "WindowMoESizes":
        """``num_experts`` counts the experts HELD here and ``router_width``
        (default: the same) the layer's; ``held_experts`` is ``[first,
        count]`` (default ``[0, num_experts]``).  Without a
        ``load_balance_coeff`` the selection bias stays as it was drawn."""
        only = {
            "score_func": "sigmoid", "n_group": 1, "topk_group": 1,
            "num_expert_groups": 1, "num_limited_groups": 1,
            "hidden_act": "silu", "rope_scaling": None,
            "tie_word_embeddings": False,
        }
        for key, value in only.items():
            if config.get(key, value) != value:
                raise ValueError(
                    f"model_type afmoe supports {key} = {value!r} only "
                    f"(got {config[key]!r})")
        held = tuple(config.get("held_experts", (0, config["num_experts"])))
        if len(held) != 2 or held[1] != config["num_experts"]:
            raise ValueError(
                f"held_experts {held} must be [first, count] with count = "
                f"num_experts = {config['num_experts']}")
        layer_types = tuple(config["layer_types"])
        if len(layer_types) != config["num_hidden_layers"] or any(
                t not in LAYER_TYPES for t in layer_types):
            raise ValueError(
                f"layer_types must name one of {LAYER_TYPES} for each of the "
                f"{config['num_hidden_layers']} layers, got {layer_types}")
        if not 0 <= config["num_dense_layers"] < len(layer_types):
            raise ValueError(
                "num_dense_layers must lie in [0, num_hidden_layers): a "
                "sparse layer has to follow the dense ones")
        return cls(
            vocab_size=config["vocab_size"],
            d_model=config["hidden_size"],
            layer_types=layer_types,
            window=config["sliding_window"],
            n_dense=config["num_dense_layers"],
            dense_d_ff=config["intermediate_size"],
            n_heads=config["num_attention_heads"],
            n_kv_heads=config["num_key_value_heads"],
            head_dim=config["head_dim"],
            rope_base=float(config["rope_theta"]),
            router_width=config.get("router_width", config["num_experts"]),
            held_experts=held,
            experts_per_token=config["num_experts_per_tok"],
            expert_d_ff=config["moe_intermediate_size"],
            shared_d_ff=(config["num_shared_experts"]
                         * config["moe_intermediate_size"]),
            routed_scale=float(config["route_scale"]),
            norm_topk_prob=config["route_norm"],
            balance_rate=config.get("load_balance_coeff"),
            embed_scale=(math.sqrt(config["hidden_size"])
                         if config.get("mup_enabled") else 1.0),
            rms_eps=config["rms_norm_eps"],
        )


def _norm(mdl, name: str) -> RMSNorm:
    return RMSNorm(mdl.sizes.rms_eps, mdl.compute_dtype, zero_centred=False,
                   name=name)


def _feed_forward(mdl: "WindowMoEBlock", a):
    """Norm 3, the layer's feed-forward, norm 4 (residual added by the
    caller): a function of the block, so that ``nn.remat`` can lift it
    without moving a parameter (``models/mla_moe.py::_feed_forward``'s
    arrangement)."""
    m, dt = mdl.sizes, mdl.compute_dtype
    h = _norm(mdl, "pre_mlp_layernorm")(a)
    if mdl.dense:
        h = DenseMLP(m.dense_d_ff, dt, name="mlp")(h)
    else:
        h = SparseMoE(
            router_width=m.router_width, held_experts=m.held_experts,
            experts_per_token=m.experts_per_token, d_ff=m.expert_d_ff,
            shared_d_ff=m.shared_d_ff, norm_topk_prob=m.norm_topk_prob,
            compute_dtype=dt, score_func="sigmoid", selection_bias=True,
            routed_scale=m.routed_scale, shared_gate=False,
            balance_bias=m.balance_rate is not None,
            bias_init=nn.initializers.zeros, name="moe")(h)
    return _norm(mdl, "post_mlp_layernorm")(checkpoint_name(h, FFN_OUT))


class WindowMoEBlock(nn.Module):
    sizes: WindowMoESizes
    layer_type: str
    dense: bool
    attn_impl: str
    compute_dtype: Any
    remat_ffn: bool = False

    @nn.compact
    def __call__(self, x, positions):
        from distributed_machine_learning_tpu.ops.pallas.flash_attention import (  # noqa: E501
            active_tiles,
        )

        m, dt = self.sizes, self.compute_dtype
        sliding = self.layer_type == "sliding_attention"
        window = m.window if sliding and m.window < x.shape[1] else None
        mixed = GatedAttention(
            n_heads=m.n_heads, n_kv_heads=m.n_kv_heads, head_dim=m.head_dim,
            rotary_dim=m.head_dim if sliding else 0, rope_base=m.rope_base,
            eps=m.rms_eps, attn_impl=self.attn_impl, compute_dtype=dt,
            window=window, zero_centred_norm=False,
            name="attn")(_norm(self, "input_layernorm")(x), positions)
        if self.attn_impl == "flash":
            # From shapes alone: the tiles this layer's kernels compute of
            # the causal triangle's, and whether they are windowed calls.
            self.sow(STATS_COLLECTION, "attn_tiles", jnp.asarray(
                [*active_tiles(x.shape[1], window), window is not None],
                jnp.float32))
        x = x + _norm(self, "post_attention_layernorm")(mixed)
        sublayer = nn.remat(_feed_forward) if self.remat_ffn else _feed_forward
        return x + sublayer(self, x)


class WindowMoELM(nn.Module):
    """Causal LM: tokens [B, L] → logits [B, L, vocab] (module docstring).
    Sequence-local attention only (``attn_impl`` ``"dense"`` or ``"flash"``);
    ``remat`` / ``remat_policy`` as ``HybridMoELM``'s (``"mlp"``: norm 3,
    the feed-forward and norm 4 recomputed in the backward pass; ``"block"``:
    the whole block but for what
    ``models/transformer.py::whole_block_policy`` keeps — the flash kernel's
    inputs and outputs, both sub-layers' outputs, which the sandwich norms
    read, the expert layer's routing, rows and pre-activations, the shared
    expert's and the dense layer's pre-activations)."""

    sizes: WindowMoESizes
    attn_impl: str = "dense"
    compute_dtype: Any = jnp.float32
    remat: bool = False
    remat_policy: str = "mlp"

    #: What ``train/lm_step.py`` asks a model that counts (as
    #: ``HybridMoELM``), and the buffers it keeps in the parameter tree.
    stats_collection = STATS_COLLECTION
    stats_counters = ("moe_held_rows", "moe_dropped_rows",
                      "moe_bias_updates", "attn_window_calls")
    step_stats = staticmethod(routing_counts)
    frozen_params = (SELECTION_BIAS,)

    @property
    def param_rules(self) -> dict:
        """``{leaf name: (what its layer sowed, rule)}`` for
        ``train/lm_step.py::_update``: the balancing rule on every sparse
        layer's selection bias, where the configuration gives it a rate."""
        if self.sizes.balance_rate is None:
            return {}
        return {SELECTION_BIAS: (ASSIGNMENTS, partial(
            balanced_bias, rate=self.sizes.balance_rate))}

    @nn.compact
    def __call__(self, tokens, *, train: bool = False,
                 return_hidden: bool = False):
        del train  # no dropout; kept for the shared train-step interface
        if self.attn_impl not in ("dense", "flash"):
            raise ValueError(
                "WindowMoELM runs sequence-local attention only (attn_impl "
                f"'dense' or 'flash', got {self.attn_impl!r})")
        if self.remat_policy not in ("mlp", "block"):
            raise ValueError(
                f"remat_policy must be 'mlp' or 'block', got "
                f"{self.remat_policy!r}")
        m, dt = self.sizes, self.compute_dtype
        positions = jnp.arange(tokens.shape[1])
        x = nn.Embed(m.vocab_size, m.d_model, dtype=dt,
                     embedding_init=_INIT, name="embed")(tokens)
        if m.embed_scale != 1.0:  # in float32: √2048 is no bf16 number
            x = (x.astype(jnp.float32) * m.embed_scale).astype(dt)
        whole_block = self.remat and self.remat_policy == "block"
        block_cls = (remat_whole_block(WindowMoEBlock) if whole_block
                     else WindowMoEBlock)
        for i, layer_type in enumerate(m.layer_types):
            x = block_cls(
                sizes=m, layer_type=layer_type, dense=i < m.n_dense,
                attn_impl=self.attn_impl, compute_dtype=dt,
                remat_ffn=self.remat and self.remat_policy == "mlp",
                name=f"block_{i}")(x, positions)
        x = RMSNorm(m.rms_eps, dt, zero_centred=False, name="norm_f")(x)
        if return_hidden:
            return x
        with jax.named_scope("head"):
            logits = _dense(m.vocab_size, dt, "lm_head")(x)
        return logits.astype(jnp.float32)
