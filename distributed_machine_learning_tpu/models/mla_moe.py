"""Latent-attention mixture-of-experts LM (``model_type`` ``deepseek_v3``):
multi-head latent attention in every layer, ``first_k_dense`` leading layers
with a dense gated-SiLU MLP, then sparse layers whose router selects by
``sigmoid score + bias`` and weighs by the score alone, plus ungated shared
experts.

Block ``i``::

    h = x + MLA(N(x; w1))        y = h + F_i(N(h; w2))

with ``N(x; w) = x · rsqrt(mean(x²) + ε) · w`` in float32 (a plain RMSNorm
weight), ``F_i`` the dense MLP for ``i < first_k_dense`` and the sparse layer
(``models/hybrid_moe.py::SparseMoE``, the one expert layer of every
configuration) after; a final ``N`` and an untied, bias-free head.  No
projection has a bias.  The plain float32 restatement the tests and the
benchmark compare against is ``benchmark/reference/mla_moe_lm.py``.

This is the TRAINING form of latent attention: the key/value up-projection
is applied to every position and the kernels see per-head keys and values
(``ops/pallas/flash_attention.py`` at a query/key head of ``qk_nope + qk_rope``
against a value head of ``v_head_dim``).  The absorbed form that decodes
from a cache of latents is not here (``inference/`` has no latent cache).

To the train step this module is what ``TransformerLM`` and ``HybridMoELM``
are: ``apply(params, tokens, train=, return_hidden=)``, an ``lm_head/kernel``
and the routing counts of ``stats_collection``; the router's selection bias
lives in the parameter tree and is named in ``frozen_params``, which the
step leaves as it is.  A chip's share of the experts (``router_width``,
``held_experts``) works as ``HybridMoELM``'s does.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributed_machine_learning_tpu.models.hybrid_moe import (
    _INIT,
    SELECTION_BIAS,
    STATS_COLLECTION,
    RMSNorm,
    SparseMoE,
    _dense,
    routing_counts,
)
from distributed_machine_learning_tpu.models.transformer import (
    apply_rope,
    remat_whole_block,
)
from distributed_machine_learning_tpu.ops.ring_attention import (
    dense_self_attention,
)


@dataclasses.dataclass(frozen=True)
class MLAMoESizes:
    """The sizes an HF-style ``deepseek_v3`` configuration states."""

    vocab_size: int
    d_model: int
    n_layers: int
    first_k_dense: int
    dense_d_ff: int
    n_heads: int
    qk_nope_dim: int
    qk_rope_dim: int
    v_dim: int
    kv_latent_dim: int
    rope_base: float
    router_width: int
    held_experts: tuple  # (first, count)
    experts_per_token: int
    expert_d_ff: int
    shared_d_ff: int
    routed_scale: float
    norm_topk_prob: bool = True
    rms_eps: float = 1e-6

    @classmethod
    def from_config(cls, config: dict) -> "MLAMoESizes":
        """``n_routed_experts`` counts the experts HELD here and
        ``router_width`` (default: the same) the layer's; ``held_experts``
        is ``[first, count]`` (default ``[0, n_routed_experts]``)."""
        only = {
            "q_lora_rank": None, "rope_scaling": None, "rope_interleave": True,
            "scoring_func": "sigmoid", "topk_method": "noaux_tc",
            "n_group": 1, "topk_group": 1, "moe_layer_freq": 1,
            "hidden_act": "silu", "attention_bias": False,
            "tie_word_embeddings": False,
            "num_key_value_heads": config["num_attention_heads"],
        }
        for key, value in only.items():
            if config.get(key, value) != value:
                raise ValueError(
                    f"model_type deepseek_v3 supports {key} = {value!r} "
                    f"only (got {config[key]!r})")
        held = tuple(config.get("held_experts",
                                (0, config["n_routed_experts"])))
        if len(held) != 2 or held[1] != config["n_routed_experts"]:
            raise ValueError(
                f"held_experts {held} must be [first, count] with count = "
                f"n_routed_experts = {config['n_routed_experts']}")
        if not 0 <= config["first_k_dense_replace"] < \
                config["num_hidden_layers"]:
            raise ValueError(
                "first_k_dense_replace must lie in [0, num_hidden_layers): "
                "a sparse layer has to follow the dense ones")
        return cls(
            vocab_size=config["vocab_size"],
            d_model=config["hidden_size"],
            n_layers=config["num_hidden_layers"],
            first_k_dense=config["first_k_dense_replace"],
            dense_d_ff=config["intermediate_size"],
            n_heads=config["num_attention_heads"],
            qk_nope_dim=config["qk_nope_head_dim"],
            qk_rope_dim=config["qk_rope_head_dim"],
            v_dim=config["v_head_dim"],
            kv_latent_dim=config["kv_lora_rank"],
            rope_base=float(config["rope_theta"]),
            router_width=config.get("router_width",
                                    config["n_routed_experts"]),
            held_experts=held,
            experts_per_token=config["num_experts_per_tok"],
            expert_d_ff=config["moe_intermediate_size"],
            shared_d_ff=(config["n_shared_experts"]
                         * config["moe_intermediate_size"]),
            routed_scale=float(config["routed_scaling_factor"]),
            norm_topk_prob=config["norm_topk_prob"],
            rms_eps=config["rms_norm_eps"],
        )


def rope_adjacent_pairs(x, positions, base: float):
    """Rotate the adjacent pairs ``(2j, 2j+1)`` of ``x`` [B, T, H, D] by
    ``position · base^(−2j/D)``, as the published code does: permute to
    ``[evens | odds]``, then rotate half-split pairs.  The permutation is the
    same for queries and keys, so ``q·k`` is that of rotating in place."""
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    return apply_rope(x, positions, base)


class MLAttention(nn.Module):
    """Multi-head latent attention, training form, no query latent.  ``q =
    x·W_q`` → per head ``[q_nope | q_rope]``; ``[c | k_r] = x·W_kva``; ``c̃ =
    N(c; w_c)``; ``c̃·W_kvb`` → per head ``[k_nope | v]``; ``q_rope`` and the
    one ``k_r`` all heads share are rotated by position; ``k_h = [k_nope_h |
    k_r]``; causal softmax attention scaled ``(qk_nope + qk_rope)^-½``;
    heads concatenated, ``·W_o``."""

    n_heads: int
    qk_nope_dim: int
    qk_rope_dim: int
    v_dim: int
    kv_latent_dim: int
    rope_base: float
    eps: float
    attn_impl: str
    compute_dtype: Any

    @nn.compact
    def __call__(self, x, positions):
        B, T, D = x.shape
        dt = self.compute_dtype
        H, dn, dr, dv = (self.n_heads, self.qk_nope_dim, self.qk_rope_dim,
                         self.v_dim)
        with jax.named_scope("mla.q"):
            q = _dense(H * (dn + dr), dt, "q_proj")(x).reshape(
                B, T, H, dn + dr)
        with jax.named_scope("mla.kv"):
            kva = _dense(self.kv_latent_dim + dr, dt, "kv_a_proj_with_mqa")(x)
            latent = RMSNorm(self.eps, dt, zero_centred=False,
                             name="kv_a_layernorm")(
                kva[..., :self.kv_latent_dim])
            kv = _dense(H * (dn + dv), dt, "kv_b_proj")(latent).reshape(
                B, T, H, dn + dv)
        with jax.named_scope("mla.rope"):
            q = jnp.concatenate([
                q[..., :dn],
                rope_adjacent_pairs(q[..., dn:], positions, self.rope_base),
            ], axis=-1)
            k_rope = rope_adjacent_pairs(
                kva[..., None, self.kv_latent_dim:], positions,
                self.rope_base)
            # The per-head keys in HBM: every head's copy of the one
            # rotated key beside its own k_nope (PERF.md §7 on what a
            # kernel that reads k_r once would save).
            k = jnp.concatenate([
                kv[..., :dn], jnp.broadcast_to(k_rope, (B, T, H, dr)),
            ], axis=-1)
            v = kv[..., dn:]
        with jax.named_scope("mla.core"):
            if self.attn_impl == "flash":
                from distributed_machine_learning_tpu.ops.pallas.flash_attention import (  # noqa: E501
                    flash_self_attention,
                )

                out = flash_self_attention(q, k, v)
            else:
                out = dense_self_attention(q, k, v, positions)
        with jax.named_scope("mla.out"):
            return _dense(D, dt, "o_proj")(out.reshape(B, T, H * dv))


class DenseMLP(nn.Module):
    """``W_down(SiLU(W_gate x) ⊙ W_up x)``."""

    d_ff: int
    compute_dtype: Any

    @nn.compact
    def __call__(self, x):
        dt = self.compute_dtype
        with jax.named_scope("mlp.dense"):
            h = (jax.nn.silu(_dense(self.d_ff, dt, "gate_proj")(x))
                 * _dense(self.d_ff, dt, "up_proj")(x))
            return _dense(x.shape[-1], dt, "down_proj")(h)


def _feed_forward(mdl: "MLABlock", h):
    """Norm 2 + the layer's feed-forward (residual added by the caller): a
    function of the block, so that ``nn.remat`` can lift it without moving
    a parameter (``models/hybrid_moe.py::_moe_sublayer``'s arrangement)."""
    m, dt = mdl.sizes, mdl.compute_dtype
    h = RMSNorm(m.rms_eps, dt, zero_centred=False, name="norm2")(h)
    if mdl.dense:
        return DenseMLP(m.dense_d_ff, dt, name="mlp")(h)
    return SparseMoE(
        router_width=m.router_width, held_experts=m.held_experts,
        experts_per_token=m.experts_per_token, d_ff=m.expert_d_ff,
        shared_d_ff=m.shared_d_ff, norm_topk_prob=m.norm_topk_prob,
        compute_dtype=dt, score_func="sigmoid", selection_bias=True,
        routed_scale=m.routed_scale, shared_gate=False, name="moe")(h)


class MLABlock(nn.Module):
    sizes: MLAMoESizes
    dense: bool
    attn_impl: str
    compute_dtype: Any
    remat_ffn: bool = False

    @nn.compact
    def __call__(self, x, positions):
        m, dt = self.sizes, self.compute_dtype
        h = RMSNorm(m.rms_eps, dt, zero_centred=False, name="norm1")(x)
        x = x + MLAttention(
            n_heads=m.n_heads, qk_nope_dim=m.qk_nope_dim,
            qk_rope_dim=m.qk_rope_dim, v_dim=m.v_dim,
            kv_latent_dim=m.kv_latent_dim, rope_base=m.rope_base,
            eps=m.rms_eps, attn_impl=self.attn_impl, compute_dtype=dt,
            name="attn")(h, positions)
        sublayer = nn.remat(_feed_forward) if self.remat_ffn else _feed_forward
        return x + sublayer(self, x)


class MLAMoELM(nn.Module):
    """Causal LM: tokens [B, L] → logits [B, L, vocab] (module docstring).
    Sequence-local attention only (``attn_impl`` ``"dense"`` or ``"flash"``);
    ``remat`` / ``remat_policy`` as ``HybridMoELM``'s (``"mlp"``: norm 2 +
    feed-forward recomputed in the backward pass; ``"block"``: the whole
    block but for the flash kernel's ``(out, lse)``, kept where the kernel
    runs — ``models/transformer.py::whole_block_policy``)."""

    sizes: MLAMoESizes
    attn_impl: str = "dense"
    compute_dtype: Any = jnp.float32
    remat: bool = False
    remat_policy: str = "mlp"

    #: What ``train/lm_step.py`` asks a model that counts (as
    #: ``HybridMoELM``), and the buffers it keeps in the parameter tree.
    stats_collection = STATS_COLLECTION
    stats_counters = ("moe_held_rows", "moe_dropped_rows")
    step_stats = staticmethod(routing_counts)
    frozen_params = (SELECTION_BIAS,)

    @nn.compact
    def __call__(self, tokens, *, train: bool = False,
                 return_hidden: bool = False):
        del train  # no dropout; kept for the shared train-step interface
        if self.attn_impl not in ("dense", "flash"):
            raise ValueError(
                "MLAMoELM runs sequence-local attention only (attn_impl "
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
        block_cls = (remat_whole_block(MLABlock) if whole_block
                     else MLABlock)
        for i in range(m.n_layers):
            x = block_cls(
                sizes=m, dense=i < m.first_k_dense,
                attn_impl=self.attn_impl, compute_dtype=dt,
                remat_ffn=self.remat and self.remat_policy == "mlp",
                name=f"block_{i}")(x, positions)
        x = RMSNorm(m.rms_eps, dt, zero_centred=False, name="norm_f")(x)
        if return_hidden:
            return x
        with jax.named_scope("head"):
            logits = _dense(m.vocab_size, dt, "lm_head")(x)
        return logits.astype(jnp.float32)
