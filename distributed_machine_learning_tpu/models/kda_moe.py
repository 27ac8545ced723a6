"""Kimi Delta Attention / latent-attention mixture-of-experts LM
(``model_type`` ``kimi_linear``): Kimi Delta Attention (KDA) layers — a
gated delta rule whose decay is one number a KEY CHANNEL — and multi-head
latent attention layers with no position encoding, in the order
``linear_attn_config``'s 1-based lists give; ``first_k_dense_replace``
leading layers with a dense gated-SiLU MLP, then sparse layers routed by
``sigmoid score + bias`` with an ungated shared expert.

Block ``i``::

    h = x + Mixer_i(N(x; w1))        y = h + F_i(N(h; w2))

with ``N(x; w) = x · rsqrt(mean(x²) + ε) · w`` in float32 (a plain RMSNorm
weight), ``F_i`` the dense MLP for ``i < first_k_dense`` and the sparse
layer after; a final ``N`` and an untied, bias-free head.  The KDA mixer, per
head of ``dk = dv``::

    q = SiLU(conv(x W_q)),  k = SiLU(conv(x W_k)),  v = SiLU(conv(x W_v))
    q̂ = q/‖q‖ · dk^-½,  k̂ = k/‖k‖,  β = σ(x W_β)
    g = −exp(A_log) · softplus(x W_f↓ W_f↑ + dt_bias)          # [H, dk]
    S ← Diag(exp(g)) S;  S ← S + β k̂ (v − Sᵀ k̂)ᵀ;  o = Sᵀ q̂
    o ← rmsnorm(o) · w ⊙ σ(x W_g↓ W_g↑ + b_g);  out = [o_1 … o_H] W_o

(``conv`` depthwise and causal, a kernel per channel; the decay's and the
gate's projections of rank ``dv``).  The rule is ``ops/delta_rule.py`` with a
``g`` of ``[B, T, H, dk]``: the per-channel preparation and state kernels.
The latent-attention layer is ``models/mla_moe.py::MLAttention`` with no
rotation (``mla_use_nope``); the expert layer ``models/hybrid_moe.py::
SparseMoE`` by settings (sigmoid scores, a selection bias that picks and
never weighs — a buffer in ``frozen_params`` —, renormalised, scaled, one
ungated shared expert), the dense MLP ``mla_moe.DenseMLP``.  The plain
float32 restatement the tests and the benchmark compare against is
``benchmark/reference/kda_moe_lm.py``.

To the train step this module is what its siblings are: ``apply(params,
tokens, train=, return_hidden=)``, an ``lm_head/kernel``, the routing counts
of ``stats_collection`` — and one count of its own, ``kda_decay_span``, the
largest log-decay a channel runs through inside one chunk of the rule
(``delta_rule.chunk_decay_span``, the largest over the KDA layers): how far
the cell runs where the factored form would overflow.  A chip's share of the
experts (``router_width``, ``held_experts``) works as ``HybridMoELM``'s
does.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from distributed_machine_learning_tpu.models.hybrid_moe import (
    _INIT,
    DECAY_SPAN,
    SELECTION_BIAS,
    STATS_COLLECTION,
    RMSNorm,
    SparseMoE,
    _conv_init,
    _dense,
    causal_depthwise_conv,
    rms_norm,
    routing_counts,
)
from distributed_machine_learning_tpu.models.mla_moe import (
    DenseMLP,
    MLAttention,
)
from distributed_machine_learning_tpu.models.transformer import (
    remat_whole_block,
)
from distributed_machine_learning_tpu.ops.delta_rule import (
    chunk_decay_span,
    gated_delta_rule,
)


@dataclasses.dataclass(frozen=True)
class KDAMoESizes:
    """The sizes an HF-style ``kimi_linear`` configuration states."""

    vocab_size: int
    d_model: int
    layer_kinds: tuple  # "kda" or "mla" a layer
    first_k_dense: int
    dense_d_ff: int
    kda_heads: int
    kda_head_dim: int
    conv_kernel: int
    n_heads: int
    qk_nope_dim: int
    qk_rope_dim: int
    v_dim: int
    kv_latent_dim: int
    mla_rope: bool
    rope_base: float
    router_width: int
    held_experts: tuple  # (first, count)
    experts_per_token: int
    expert_d_ff: int
    shared_d_ff: int
    routed_scale: float
    norm_topk_prob: bool
    rms_eps: float

    @property
    def n_layers(self) -> int:
        return len(self.layer_kinds)

    @classmethod
    def from_config(cls, config: dict) -> "KDAMoESizes":
        """``num_experts`` counts the experts HELD here and ``router_width``
        (default: the same) the layer's; ``held_experts`` is ``[first,
        count]`` (default ``[0, num_experts]``).  Layer ``i`` (1-based) is a
        KDA layer when ``linear_attn_config["kda_layers"]`` lists it and a
        latent-attention layer when ``full_attn_layers`` does."""
        only = {
            "q_lora_rank": None, "rope_scaling": None, "hidden_act": "silu",
            "moe_router_activation_func": "sigmoid", "num_expert_group": 1,
            "topk_group": 1, "moe_layer_freq": 1,
            "tie_word_embeddings": False, "num_nextn_predict_layers": 0,
            "num_key_value_heads": config["num_attention_heads"],
        }
        for key, value in only.items():
            if config.get(key, value) != value:
                raise ValueError(
                    f"model_type kimi_linear supports {key} = {value!r} only "
                    f"(got {config[key]!r})")
        held = tuple(config.get("held_experts", (0, config["num_experts"])))
        if len(held) != 2 or held[1] != config["num_experts"]:
            raise ValueError(
                f"held_experts {held} must be [first, count] with count = "
                f"num_experts = {config['num_experts']}")
        linear = config["linear_attn_config"]
        n = config["num_hidden_layers"]
        kda, full = set(linear["kda_layers"]), set(linear["full_attn_layers"])
        if kda & full or kda | full != set(range(1, n + 1)):
            raise ValueError(
                "linear_attn_config's kda_layers and full_attn_layers must "
                f"name each of the layers 1 … {n} once (got {sorted(kda)} "
                f"and {sorted(full)})")
        if not 0 <= config["first_k_dense_replace"] < n:
            raise ValueError(
                "first_k_dense_replace must lie in [0, num_hidden_layers): "
                "a sparse layer has to follow the dense ones")
        return cls(
            vocab_size=config["vocab_size"],
            d_model=config["hidden_size"],
            layer_kinds=tuple("kda" if i in kda else "mla"
                              for i in range(1, n + 1)),
            first_k_dense=config["first_k_dense_replace"],
            dense_d_ff=config["intermediate_size"],
            kda_heads=linear["num_heads"],
            kda_head_dim=linear["head_dim"],
            conv_kernel=linear["short_conv_kernel_size"],
            n_heads=config["num_attention_heads"],
            qk_nope_dim=config["qk_nope_head_dim"],
            qk_rope_dim=config["qk_rope_head_dim"],
            v_dim=config["v_head_dim"],
            kv_latent_dim=config["kv_lora_rank"],
            mla_rope=not config.get("mla_use_nope", False),
            rope_base=float(config["rope_theta"]),
            router_width=config.get("router_width", config["num_experts"]),
            held_experts=held,
            experts_per_token=config["num_experts_per_token"],
            expert_d_ff=config["moe_intermediate_size"],
            shared_d_ff=(config["num_shared_experts"]
                         * config["moe_intermediate_size"]),
            routed_scale=float(config["routed_scaling_factor"]),
            norm_topk_prob=config["moe_renormalize"],
            rms_eps=config["rms_norm_eps"],
        )


def _a_log_init(key, shape):
    """``log A`` with ``A`` uniform on [1, 16] (fla's KDA layer)."""
    return jnp.log(jax.random.uniform(key, shape, minval=1.0, maxval=16.0))


def _dt_bias_init(key, shape):
    """The inverse softplus of a step ``dt`` log-uniform on [1e-3, 0.1]
    (fla's KDA layer, Mamba's rule)."""
    dt = jnp.exp(jax.random.uniform(key, shape, minval=math.log(1e-3),
                                    maxval=math.log(0.1)))
    return dt + jnp.log(-jnp.expm1(-dt))


class KimiDeltaAttention(nn.Module):
    """The KDA mixer (module docstring)."""

    heads: int
    head_dim: int
    kernel_size: int
    eps: float
    compute_dtype: Any

    @nn.compact
    def __call__(self, x):
        B, T, D = x.shape
        dt = self.compute_dtype
        H, d = self.heads, self.head_dim
        width = H * d
        q, k, v = (_dense(width, dt, f"{n}_proj")(x) for n in "qkv")
        convs = tuple(self.param(f"{n}_conv1d", _conv_init,
                                 (self.kernel_size, width)) for n in "qkv")
        f = _dense(width, dt, "f_b_proj")(_dense(d, dt, "f_a_proj")(x))
        b = _dense(H, dt, "b_proj")(x)
        gate = nn.Dense(width, use_bias=True, dtype=dt, kernel_init=_INIT,
                        name="g_b_proj")(_dense(d, dt, "g_a_proj")(x))
        a_log = self.param("A_log", _a_log_init, (H,))
        dt_bias = self.param("dt_bias", _dt_bias_init, (width,))
        norm_w = self.param("o_norm_weight", nn.initializers.ones, (d,))
        with jax.named_scope("kda.conv"):
            q, k, v = _kda_qkv(q, k, v, convs, H)
        with jax.named_scope("kda.gate"):
            g, beta = _kda_gate(f, b, a_log, dt_bias, H)
            self.sow(STATS_COLLECTION, DECAY_SPAN,
                     lax.stop_gradient(chunk_decay_span(g)))
        with jax.named_scope("kda.scan"):
            o = gated_delta_rule(q, k, v, g, beta)
        with jax.named_scope("kda.out"):
            o = _sigmoid_gated_norm(o, gate.reshape(B, T, H, d), norm_w,
                                    self.eps)
            return _dense(D, dt, "o_proj")(o.reshape(B, T, width))


# The elementwise stretches are made again in the backward pass from their
# bf16 inputs (``jax.checkpoint``), as ``GatedDeltaNet``'s are: their float32
# intermediates are arrays of [T, 4096] that autodiff would keep.

@partial(jax.checkpoint, static_argnums=(4,))
def _kda_qkv(q, k, v, convs, heads):
    """``q̂, k̂, v`` of the rule: depthwise causal convolution and SiLU each,
    then L2-normalised (``q`` also scaled by ``dk^-½``) heads."""
    B, T, width = q.shape
    dt, d = q.dtype, width // heads
    q, k, v = (jax.nn.silu(causal_depthwise_conv(a, w)).reshape(
        B, T, heads, d) for a, w in zip((q, k, v), convs))
    l2 = lambda a: a * lax.rsqrt(
        jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)
    return ((l2(q) * d ** -0.5).astype(dt), l2(k).astype(dt), v.astype(dt))


@partial(jax.checkpoint, static_argnums=(4,))
def _kda_gate(f, b, a_log, dt_bias, heads):
    """The log-decay a channel, ``g = −exp(A_log)·softplus(f + dt_bias)``
    [B, T, H, dk], and ``β = σ(b)`` [B, T, H] (float32)."""
    B, T, _ = f.shape
    pre = (f.astype(jnp.float32) + dt_bias).reshape(B, T, heads, -1)
    g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(pre)
    return g, jax.nn.sigmoid(b.astype(jnp.float32))


@partial(jax.checkpoint, static_argnums=(3,))
def _sigmoid_gated_norm(o, gate, weight, eps):
    """``rmsnorm(o) · w ⊙ σ(gate)`` per head, float32 inside."""
    o = rms_norm(o, weight, eps, zero_centred=False)
    return (o * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(gate.dtype)


def _feed_forward(mdl: "KDAMoEBlock", h):
    """Norm 2 + the layer's feed-forward (residual added by the caller): a
    function of the block, so that ``nn.remat`` can lift it without moving
    a parameter (``models/mla_moe.py::_feed_forward``'s arrangement)."""
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


class KDAMoEBlock(nn.Module):
    sizes: KDAMoESizes
    kind: str  # "kda" or "mla"
    dense: bool
    attn_impl: str
    compute_dtype: Any
    remat_ffn: bool = False

    @nn.compact
    def __call__(self, x, positions):
        m, dt = self.sizes, self.compute_dtype
        h = RMSNorm(m.rms_eps, dt, zero_centred=False, name="norm1")(x)
        if self.kind == "kda":
            mixed = KimiDeltaAttention(
                heads=m.kda_heads, head_dim=m.kda_head_dim,
                kernel_size=m.conv_kernel, eps=m.rms_eps, compute_dtype=dt,
                name="kda")(h)
        else:
            mixed = MLAttention(
                n_heads=m.n_heads, qk_nope_dim=m.qk_nope_dim,
                qk_rope_dim=m.qk_rope_dim, v_dim=m.v_dim,
                kv_latent_dim=m.kv_latent_dim, rope_base=m.rope_base,
                eps=m.rms_eps, attn_impl=self.attn_impl, compute_dtype=dt,
                rope=m.mla_rope, name="attn")(h, positions)
        x = x + mixed
        sublayer = nn.remat(_feed_forward) if self.remat_ffn else _feed_forward
        return x + sublayer(self, x)


class KDAMoELM(nn.Module):
    """Causal LM: tokens [B, L] → logits [B, L, vocab] (module docstring).
    Sequence-local attention only (``attn_impl`` ``"dense"`` or ``"flash"``:
    the delta rule's state does not cross a sequence shard); ``remat`` /
    ``remat_policy`` as ``HybridMoELM``'s (``"mlp"``: norm 2 + feed-forward
    recomputed in the backward pass; ``"block"``: the whole block but for
    what ``models/transformer.py::whole_block_policy`` keeps of what runs
    here — the flash kernel's inputs and outputs, the expert layer's
    routing, rows and pre-activations, the gated MLPs' pre-activations)."""

    sizes: KDAMoESizes
    attn_impl: str = "dense"
    compute_dtype: Any = jnp.float32
    remat: bool = False
    remat_policy: str = "mlp"

    #: What ``train/lm_step.py`` asks a model that counts (as
    #: ``MLAMoELM``), and the buffers it keeps in the parameter tree.
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
                "KDAMoELM runs sequence-local attention only (attn_impl "
                f"'dense' or 'flash', got {self.attn_impl!r}): the delta "
                "rule's state does not cross a sequence shard")
        if self.remat_policy not in ("mlp", "block"):
            raise ValueError(
                f"remat_policy must be 'mlp' or 'block', got "
                f"{self.remat_policy!r}")
        m, dt = self.sizes, self.compute_dtype
        positions = jnp.arange(tokens.shape[1])
        x = nn.Embed(m.vocab_size, m.d_model, dtype=dt,
                     embedding_init=_INIT, name="embed")(tokens)
        whole_block = self.remat and self.remat_policy == "block"
        block_cls = (remat_whole_block(KDAMoEBlock) if whole_block
                     else KDAMoEBlock)
        for i, kind in enumerate(m.layer_kinds):
            x = block_cls(
                sizes=m, kind=kind, dense=i < m.first_k_dense,
                attn_impl=self.attn_impl, compute_dtype=dt,
                remat_ffn=self.remat and self.remat_policy == "mlp",
                name=f"block_{i}")(x, positions)
        x = RMSNorm(m.rms_eps, dt, zero_centred=False, name="norm_f")(x)
        if return_hidden:
            return x
        with jax.named_scope("head"):
            logits = _dense(m.vocab_size, dt, "lm_head")(x)
        return logits.astype(jnp.float32)
