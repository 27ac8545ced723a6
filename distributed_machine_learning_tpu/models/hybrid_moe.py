"""Hybrid linear/full-attention mixture-of-experts LM (``model_type``
``qwen3_next``): Gated DeltaNet and gated softmax attention layers in a
fixed period, each followed by a sparse MoE feed-forward with a shared
expert.

Layer ``i`` (0-based) is a gated-attention layer when ``(i + 1) %
full_attention_interval == 0`` and a Gated DeltaNet layer otherwise::

    h = x + Mixer_i(N(x; w1))        y = h + MoE(N(h; w2))

with ``N(x; w) = x · rsqrt(mean(x²) + ε) · (1 + w)`` in float32 (a
zero-centred RMSNorm weight), a final ``N`` and an untied, bias-free head.
No projection has a bias.  The equations of each sublayer stand in its
module's docstring; the plain float32 restatement the tests and the
benchmark compare against is ``benchmark/reference/hybrid_moe_lm.py``.

To the train step this module is what ``TransformerLM`` is:
``apply(params, tokens, train=, return_hidden=)`` and an ``lm_head/kernel``,
so ``train/lm_step.py`` (``lm_loss`` with fused cross-entropy,
``make_lm_train_step``), ``train_epoch``, AdamW and checkpointing are
shared.  bf16 compute; parameters, router, norms, decay and the delta
rule's state are float32.

**A chip's share of the experts.**  ``router_width`` is the layer's
published expert count and ``held_experts = (first, count)`` the experts
whose weights live here: the router scores all ``router_width`` experts
and picks ``experts_per_token`` of them, the expert parameters have the
local shape ``[count, ...]``, and the layer returns the part of the sum
that the held experts give (plus the shared expert, which every chip
computes).  What the absent experts would add is left out — on an
expert-parallel group it is the other members' part.  The row buffer of
the held experts' grouped matmuls is bounded at ``HELD_ROWS_FACTOR`` times
the share it expects; rows past it are dropped and counted.

Routing counts are sown into the ``moe_stats`` collection (one entry a
layer: rows computed here, fullest held expert over the mean, rows
dropped); a step that makes the collection mutable returns them beside
the loss (``train/lm_step.py``).
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
from jax.ad_checkpoint import checkpoint_name

from distributed_machine_learning_tpu.models.transformer import (
    _repeat_kv,
    apply_rope,
    remat_whole_block,
)
from distributed_machine_learning_tpu.ops.delta_rule import gated_delta_rule
from distributed_machine_learning_tpu.ops.grouped import (
    ROUTE_NAMES,
    grouped_expert_mlp,
    route_topk,
    selection_moved_share,
)
from distributed_machine_learning_tpu.ops.ring_attention import (
    dense_self_attention,
)

#: ``jax.ad_checkpoint.checkpoint_name`` tags of what a recomputed block
#: keeps (``models/transformer.py::whole_block_policy``) beside the flash
#: kernel's and the grouped experts': the attention layer's and the
#: feed-forward's outputs ``[B, L, D]``, which a norm after them reads (kept,
#: they spare the output projections and the experts' combine), and a gated
#: MLP's two pre-activations ``[N, d_ff]`` (the shared expert's here, the
#: dense layer's in ``models/mla_moe.py::DenseMLP``).
SUBLAYER_OUT_NAMES = ATTN_OUT, FFN_OUT = ("attn_out", "ffn_out")
MLP_NAMES = ("mlp_gate", "mlp_up")
#: The held experts' row buffer, as a multiple of the rows a balanced
#: router sends here (tokens × experts a token × held ÷ router width).
HELD_ROWS_FACTOR = 2.0
#: Where a model sows its per-layer routing counts; a step that finds this
#: attribute on a model returns the collection beside the loss.
STATS_COLLECTION = "moe_stats"

#: The selection bias in the parameter tree, and what a layer that has its
#: bias balanced sows for the rule: its assignments a router output.
SELECTION_BIAS = "e_score_correction_bias"
ASSIGNMENTS = "assignments"
#: What a delta-rule layer whose decay is a channel's sows: the largest
#: log-decay a channel runs through inside one chunk of the rule.
DECAY_SPAN = "kda_span"
#: What a sparse layer whose experts are ReGLU sows: the share of its
#: computed rows' hidden units that the ReLU gate passes.
REGLU_ACTIVE = "reglu_active"
#: An expert's gate activation, by the name a configuration gives it.
ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu}

_INIT = nn.initializers.normal(stddev=0.02)  # the family's initializer_range


def _dense(features: int, dtype, name: str) -> nn.Dense:
    return nn.Dense(features, use_bias=False, dtype=dtype, kernel_init=_INIT,
                    name=name)


def rms_norm(x, weight, eps: float, zero_centred: bool = True):
    """``x · rsqrt(mean(x²) + ε) · (1 + w)`` over the last axis, float32;
    ``zero_centred=False`` multiplies by ``w`` itself."""
    x = x.astype(jnp.float32)
    x = x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * ((1.0 + weight) if zero_centred else weight)


class RMSNorm(nn.Module):
    """RMSNorm, float32 inside, ``dtype`` out: zero-centred (``1 + w``, the
    weight starts at zero) or, with ``zero_centred=False``, plain (``w``,
    starting at one)."""

    eps: float
    dtype: Any
    zero_centred: bool = True

    @nn.compact
    def __call__(self, x):
        init = (nn.initializers.zeros if self.zero_centred
                else nn.initializers.ones)
        weight = self.param("weight", init, (x.shape[-1],))
        return rms_norm(x, weight, self.eps,
                        self.zero_centred).astype(self.dtype)


def _a_log_init(key, shape):
    """``log A`` with ``A`` uniform on (0, 16): the family's decay init."""
    return jnp.log(jax.random.uniform(key, shape, minval=1e-3, maxval=16.0))


def _conv_init(key, shape):
    """Uniform on ±1/sqrt(K): a depthwise convolution's fan-in is its K
    taps."""
    bound = 1.0 / math.sqrt(shape[0])
    return jax.random.uniform(key, shape, minval=-bound, maxval=bound)


def causal_depthwise_conv(x, kernel):
    """``out[t] = Σ_i kernel[i] · x[t − (K−1) + i]`` per channel, zeros
    before the sequence's start.  ``x``: [B, T, C]; ``kernel``: [K, C]."""
    K, T = kernel.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return sum(kernel[i] * padded[:, i:i + T].astype(jnp.float32)
               for i in range(K))


class GatedDeltaNet(nn.Module):
    """The linear-attention mixer.  ``[q, k, v, z] = x·W_qkvz``, ``[b, a] =
    x·W_ba``; ``q, k, v`` together pass a depthwise causal convolution and
    SiLU; ``q, k`` are repeated from ``key_heads`` to ``value_heads``.  Per
    value head, float32: ``β = σ(b)``, ``g = −exp(A_log)·softplus(a +
    dt_bias)``, ``q̂ = q/‖q‖ · dk^-½``, ``k̂ = k/‖k‖``, then the gated delta
    rule (``ops/delta_rule.py``); ``o ← rmsnorm(o)·w·SiLU(z)`` per head,
    heads concatenated, ``·W_out``."""

    key_heads: int
    value_heads: int
    key_dim: int
    value_dim: int
    kernel_size: int
    eps: float
    compute_dtype: Any

    @nn.compact
    def __call__(self, x):
        B, T, D = x.shape
        dt = self.compute_dtype
        Hk, Hv, dk, dv = (self.key_heads, self.value_heads, self.key_dim,
                          self.value_dim)
        qk, vz = Hk * dk, Hv * dv
        qkvz = _dense(2 * qk + 2 * vz, dt, "in_proj_qkvz")(x)
        ba = _dense(2 * Hv, dt, "in_proj_ba")(x).astype(jnp.float32)
        conv_w = self.param("conv_weight", _conv_init,
                            (self.kernel_size, 2 * qk + vz))
        a_log = self.param("A_log", _a_log_init, (Hv,))
        dt_bias = self.param("dt_bias", nn.initializers.ones, (Hv,))
        norm_w = self.param("norm_weight", nn.initializers.ones, (dv,))
        with jax.named_scope("gdn.conv"):
            q, k, v, g, beta = _delta_rule_inputs(
                qkvz[..., :2 * qk + vz], ba, conv_w, a_log, dt_bias,
                (Hk, Hv, dk, dv))
        with jax.named_scope("gdn.scan"):
            o = gated_delta_rule(q, k, v, g, beta)
        with jax.named_scope("gdn.out"):
            z = qkvz[..., 2 * qk + vz:].reshape(B, T, Hv, dv)
            o = _gated_norm(o, z, norm_w, self.eps)
            return _dense(D, dt, "out_proj")(o.reshape(B, T, vz))


# The two elementwise stretches of the mixer are made again in the backward
# pass from their bf16 inputs (``jax.checkpoint``): their float32
# intermediates — the convolution's output, its SiLU, the normalised heads,
# the gate — are arrays of [T, 8192] and [T, 4096] that autodiff would keep,
# 0.8 GB a layer at 8192 tokens, and cost a pass over memory to make again.

@partial(jax.checkpoint, static_argnums=(5,))
def _delta_rule_inputs(qkv, ba, conv_w, a_log, dt_bias, heads):
    """``q̂, k̂, v, g, β`` of the delta rule from the projections: depthwise
    causal convolution and SiLU, L2-normalised and scaled heads repeated to
    the value heads, decay and step size (float32)."""
    Hk, Hv, dk, dv = heads
    B, T, _ = qkv.shape
    dt, qk = qkv.dtype, Hk * dk
    qkv = jax.nn.silu(causal_depthwise_conv(qkv, conv_w))
    q = qkv[..., :qk].reshape(B, T, Hk, dk)
    k = qkv[..., qk:2 * qk].reshape(B, T, Hk, dk)
    v = qkv[..., 2 * qk:].reshape(B, T, Hv, dv).astype(dt)
    l2 = lambda a: a * lax.rsqrt(
        jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)
    q = _repeat_kv((l2(q) * dk ** -0.5).astype(dt), Hv // Hk)
    k = _repeat_kv(l2(k).astype(dt), Hv // Hk)
    beta = jax.nn.sigmoid(ba[..., :Hv])
    g = -jnp.exp(a_log) * jax.nn.softplus(ba[..., Hv:] + dt_bias)
    return q, k, v, g, beta


@partial(jax.checkpoint, static_argnums=(3,))
def _gated_norm(o, z, weight, eps):
    """``rmsnorm(o) · w · SiLU(z)`` per head, float32 inside."""
    o = rms_norm(o, weight, eps, zero_centred=False)
    return (o * jax.nn.silu(z.astype(jnp.float32))).astype(z.dtype)


class GatedAttention(nn.Module):
    """Softmax attention with an output gate.  ``[q, gate] = x·W_q``,
    ``k = x·W_k``, ``v = x·W_v``; ``q`` and ``k`` take an RMSNorm per head
    (zero-centred, or plain with ``zero_centred_norm=False``); the first
    ``rotary_dim`` dimensions of each head are rotated (half-split pairs,
    base ``rope_base``; ``rotary_dim = 0``: no position encoding at all);
    causal attention — through a sliding ``window`` of that many keys, the
    query's own among them, where one is given — with each key/value head
    serving ``n_heads / n_kv_heads`` query heads; ``out = (attn ⊙
    σ(gate))·W_o``.  ``gated=False``: ``q = x·W_q`` and ``out = attn·W_o``;
    ``qk_norm=False``: ``q`` and ``k`` are not normed."""

    n_heads: int
    n_kv_heads: int
    head_dim: int
    rotary_dim: int
    rope_base: float
    eps: float
    attn_impl: str
    compute_dtype: Any
    window: int | None = None
    zero_centred_norm: bool = True
    gated: bool = True
    qk_norm: bool = True

    @nn.compact
    def __call__(self, x, positions):
        B, T, D = x.shape
        dt = self.compute_dtype
        H, Hkv, dh = self.n_heads, self.n_kv_heads, self.head_dim
        if self.gated:
            q_gate = _dense(2 * H * dh, dt, "q_proj")(x)
            q = q_gate[..., :H * dh].reshape(B, T, H, dh)
            gate = q_gate[..., H * dh:]
        else:
            q = _dense(H * dh, dt, "q_proj")(x).reshape(B, T, H, dh)
        k = _dense(Hkv * dh, dt, "k_proj")(x).reshape(B, T, Hkv, dh)
        v = _dense(Hkv * dh, dt, "v_proj")(x).reshape(B, T, Hkv, dh)
        with jax.named_scope("attn.qk"):
            if self.qk_norm:
                q = RMSNorm(self.eps, dt, self.zero_centred_norm,
                            name="q_norm")(q)
                k = RMSNorm(self.eps, dt, self.zero_centred_norm,
                            name="k_norm")(k)
            if self.rotary_dim:
                q = apply_rope(q, positions, self.rope_base, self.rotary_dim)
                k = apply_rope(k, positions, self.rope_base, self.rotary_dim)
        with jax.named_scope(
                "attn.core.full" if self.window is None else "attn.core.window"):
            if self.attn_impl == "flash":
                from distributed_machine_learning_tpu.ops.pallas.flash_attention import (  # noqa: E501
                    flash_self_attention,
                )

                out = flash_self_attention(q, k, v, window=self.window)
            else:
                rep = H // Hkv
                out = dense_self_attention(
                    q, _repeat_kv(k, rep), _repeat_kv(v, rep), positions,
                    window=self.window)
        out = out.reshape(B, T, H * dh)
        if self.gated:
            with jax.named_scope("attn.gate"):
                out = _sigmoid_gated(out, gate)
        with jax.named_scope("attn.out"):
            return checkpoint_name(_dense(D, dt, "o_proj")(out), ATTN_OUT)


@jax.checkpoint
def _sigmoid_gated(out, gate):
    """``out ⊙ σ(gate)`` in float32 (made again in the backward pass, as
    the DeltaNet mixer's elementwise stretches are)."""
    return (out.astype(jnp.float32)
            * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(out.dtype)


def _selection_bias_init(key, shape):
    """Uniform on ±0.05: wide enough beside sigmoid scores near one half
    that the bias changes the chosen set for a measurable share of tokens."""
    return jax.random.uniform(key, shape, minval=-0.05, maxval=0.05)


class SparseMoE(nn.Module):
    """``p = score(x·W_r)`` over ``router_width`` experts in float32,
    ``score`` the softmax or (``score_func="sigmoid"``) the element-wise
    sigmoid; the chosen set ``S`` is the ``experts_per_token`` largest of
    ``p`` — of ``p + b`` with ``selection_bias``, ``b`` a buffer
    (``e_score_correction_bias``: in the parameter tree, no gradient, named
    in the model's ``frozen_params``) that picks and never weighs; ``p̃_e =
    routed_scale · p_e / Σ_{j∈S} p_j`` (no division unless
    ``norm_topk_prob``); expert ``e`` is ``W_down(act(W_gate x) ⊙ W_up x)``,
    ``act`` the ``activation`` named (SiLU, or ReLU: ReGLU experts, which
    also count the hidden units their gate passes);
    ``y = Σ_{e ∈ S ∩ held} p̃_e·E_e(x) + g·E_shared(x)``, ``g = σ(x·w_s)``
    with ``shared_gate``, else one; ``shared_d_ff = 0``: no shared expert.
    ``route_from``: the router reads that tensor (of ``x``'s shape) in place
    of ``x``, and the experts still transform ``x``.  With ``balance_bias``
    the layer also hands out ``c``, this step's assignments to each of the
    ``router_width`` experts, for the rule that moves ``b``
    (:func:`balanced_bias`)."""

    router_width: int
    held_experts: tuple  # (first, count)
    experts_per_token: int
    d_ff: int
    shared_d_ff: int
    norm_topk_prob: bool
    compute_dtype: Any
    score_func: str = "softmax"
    selection_bias: bool = False
    routed_scale: float = 1.0
    shared_gate: bool = True
    balance_bias: bool = False
    bias_init: Any = _selection_bias_init
    activation: str = "silu"

    @nn.compact
    def __call__(self, x, route_from=None):
        B, T, D = x.shape
        dt = self.compute_dtype
        N, k = B * T, self.experts_per_token
        first, held = self.held_experts
        if not 0 <= first <= first + held <= self.router_width or held < 1:
            raise ValueError(
                f"held_experts {self.held_experts} must be a non-empty "
                f"range inside the router's {self.router_width} experts")
        tokens = x.reshape(N, D)
        router_in = tokens if route_from is None else route_from.reshape(N, D)
        w_gate = self.param("w_gate", _INIT, (held, D, self.d_ff))
        w_up = self.param("w_up", _INIT, (held, D, self.d_ff))
        w_down = self.param("w_down", _INIT, (held, self.d_ff, D))
        with jax.named_scope("moe.route"):
            # A float32 router for real: a TPU's default matmul precision
            # would round the operands to bf16 and move near-tied choices.
            logits = checkpoint_name(nn.Dense(
                self.router_width, use_bias=False, dtype=jnp.float32,
                kernel_init=_INIT, precision=lax.Precision.HIGHEST,
                name="router")(router_in.astype(jnp.float32)), ROUTE_NAMES[0])
            if self.score_func not in ("softmax", "sigmoid"):
                raise ValueError(
                    f"score_func must be 'softmax' or 'sigmoid', got "
                    f"{self.score_func!r}")
            probs = (jax.nn.sigmoid(logits) if self.score_func == "sigmoid"
                     else jax.nn.softmax(logits, axis=-1))
            bias = self.param(
                SELECTION_BIAS, self.bias_init,
                (self.router_width,)) if self.selection_bias else None
            expert_idx, weights = route_topk(
                probs, k, self.norm_topk_prob, bias=bias,
                scale=self.routed_scale)
            if bias is not None:
                self.sow(STATS_COLLECTION, "bias_moved",
                         selection_moved_share(probs, expert_idx))
            if self.balance_bias:
                self.sow(STATS_COLLECTION, ASSIGNMENTS, jnp.sum(
                    expert_idx[..., None] == jnp.arange(self.router_width),
                    axis=(0, 1), dtype=jnp.int32))
                self.sow(STATS_COLLECTION, "bias_abs",
                         jnp.mean(jnp.abs(bias)))
        self.sow("moe_routing", "expert_idx", expert_idx)
        # Every assignment there is when all experts are held; else the
        # balanced share times HELD_ROWS_FACTOR, in whole 128-row tiles.
        share = N * k * held / self.router_width
        capacity = min(N * k, 128 * math.ceil(HELD_ROWS_FACTOR * share / 128))
        reglu = self.activation == "relu"
        with jax.named_scope("moe.experts"):
            y, (sizes, dropped, *active) = grouped_expert_mlp(
                tokens.astype(dt), expert_idx, weights, w_up, w_down,
                w_gate=w_gate, activation=ACTIVATIONS[self.activation],
                first_held=first, capacity=capacity, return_counts=True,
                return_active=reglu)
        sizes = sizes.astype(jnp.float32)
        self.sow(STATS_COLLECTION, "layer", jnp.stack([
            jnp.sum(sizes),
            jnp.max(sizes) / jnp.maximum(jnp.mean(sizes), 1.0),
            dropped.astype(jnp.float32)]))
        if reglu:
            self.sow(STATS_COLLECTION, REGLU_ACTIVE, active[0].astype(
                jnp.float32) / jnp.maximum(jnp.sum(sizes) * self.d_ff, 1.0))
        if not self.shared_d_ff:
            return y.reshape(B, T, D)
        with jax.named_scope("moe.shared"):
            gate, up = MLP_NAMES
            h = (jax.nn.silu(checkpoint_name(_dense(
                self.shared_d_ff, dt, "shared_gate_proj")(tokens), gate))
                 * checkpoint_name(_dense(
                     self.shared_d_ff, dt, "shared_up_proj")(tokens), up))
            shared = _dense(D, dt, "shared_down_proj")(h)
            if self.shared_gate:
                gate = jax.nn.sigmoid(_dense(1, dt, "shared_expert_gate")(
                    tokens).astype(jnp.float32))
                shared = (gate * shared.astype(jnp.float32)).astype(dt)
            y = y + shared
        return y.reshape(B, T, D)


def balanced_bias(bias, assignments, rate: float):
    """The auxiliary-loss-free balancing rule (Wang et al.,
    arXiv:2408.15664), once a step: ``b_e ← b_e + rate · sign(mean(c) −
    c_e)`` with ``c`` the step's assignments to each expert — an expert
    under the mean load becomes likelier to be picked, one over it less.
    Not a gradient step: ``b`` picks and never weighs."""
    c = assignments.astype(jnp.float32)
    return bias + rate * jnp.sign(jnp.mean(c) - c)


def routing_counts(sown) -> dict:
    """One step's routing counts from what the sparse layers sowed: from
    each layer's ``[rows, max/mean, dropped]`` the assignments computed here
    a layer (the mean over the layers), the fullest held expert over the
    mean (the worst layer) and the rows dropped (all layers); where the
    layers select with a bias, the share of tokens whose chosen set the
    bias changed (the mean over the layers); where a rule moves that bias,
    its mean size (over the layers) and how many layers' it moved; and,
    from a model that sows its attention layers' ``[active, causal,
    windowed]`` tile counts, active over causal tiles and the windowed
    calls; from a model that sows its KDA layers' chunk decay spans, the
    largest; from ReGLU expert layers, the share of their computed hidden
    units that the gate passes (the mean over the layers)."""
    by_name: dict = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(sown):
        name = [k.key for k in path if hasattr(k, "key")][-1]
        by_name.setdefault(name, []).append(leaf)
    layers = jnp.stack(by_name["layer"])
    counts = {"moe_held_rows": layers[:, 0].mean(),
              "moe_load_max_over_mean": layers[:, 1].max(),
              "moe_dropped_rows": layers[:, 2].sum()}
    if "bias_moved" in by_name:
        counts["moe_bias_moved_share"] = jnp.stack(
            by_name["bias_moved"]).mean()
    if "bias_abs" in by_name:
        counts["moe_bias_abs_mean"] = jnp.stack(by_name["bias_abs"]).mean()
        counts["moe_bias_updates"] = jnp.float32(len(by_name["bias_abs"]))
    if "attn_tiles" in by_name:
        active, causal, windowed = jnp.stack(by_name["attn_tiles"]).sum(0)
        counts["attn_active_tile_share"] = active / causal
        counts["attn_window_calls"] = windowed
    if DECAY_SPAN in by_name:
        counts["kda_decay_span"] = jnp.stack(by_name[DECAY_SPAN]).max()
    if REGLU_ACTIVE in by_name:
        counts["moe_reglu_active_share"] = jnp.stack(
            by_name[REGLU_ACTIVE]).mean()
    return counts


@dataclasses.dataclass(frozen=True)
class HybridMoESizes:
    """The sizes an HF-style ``qwen3_next`` configuration states."""

    vocab_size: int
    d_model: int
    n_layers: int
    full_attention_interval: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    partial_rotary_factor: float
    rope_base: float
    linear_key_heads: int
    linear_value_heads: int
    linear_key_dim: int
    linear_value_dim: int
    conv_kernel: int
    router_width: int
    held_experts: tuple  # (first, count)
    experts_per_token: int
    expert_d_ff: int
    shared_d_ff: int
    norm_topk_prob: bool = True
    rms_eps: float = 1e-6

    @classmethod
    def from_config(cls, config: dict) -> "HybridMoESizes":
        """``num_experts`` counts the experts HELD here and ``router_width``
        (default: the same) the layer's; ``held_experts`` is ``[first,
        count]`` (default ``[0, num_experts]``)."""
        only = {
            "decoder_sparse_step": 1, "mlp_only_layers": [],
            "hidden_act": "silu", "tie_word_embeddings": False,
            "rope_scaling": None, "use_sliding_window": False,
        }
        for key, value in only.items():
            if config.get(key, value) != value:
                raise ValueError(
                    f"model_type qwen3_next supports {key} = {value!r} only "
                    f"(got {config[key]!r})")
        held = tuple(config.get("held_experts", (0, config["num_experts"])))
        if len(held) != 2 or held[1] != config["num_experts"]:
            raise ValueError(
                f"held_experts {held} must be [first, count] with count = "
                f"num_experts = {config['num_experts']}")
        return cls(
            vocab_size=config["vocab_size"],
            d_model=config["hidden_size"],
            n_layers=config["num_hidden_layers"],
            full_attention_interval=config["full_attention_interval"],
            n_heads=config["num_attention_heads"],
            n_kv_heads=config["num_key_value_heads"],
            head_dim=config["head_dim"],
            partial_rotary_factor=config["partial_rotary_factor"],
            rope_base=float(config["rope_theta"]),
            linear_key_heads=config["linear_num_key_heads"],
            linear_value_heads=config["linear_num_value_heads"],
            linear_key_dim=config["linear_key_head_dim"],
            linear_value_dim=config["linear_value_head_dim"],
            conv_kernel=config["linear_conv_kernel_dim"],
            router_width=config.get("router_width", config["num_experts"]),
            held_experts=held,
            experts_per_token=config["num_experts_per_tok"],
            expert_d_ff=config["moe_intermediate_size"],
            shared_d_ff=config["shared_expert_intermediate_size"],
            norm_topk_prob=config["norm_topk_prob"],
            rms_eps=config["rms_norm_eps"],
        )


def _moe_sublayer(mdl: "HybridBlock", h):
    """Norm 2 + the sparse MoE (residual added by the caller): a function of
    the block, so that ``nn.remat`` can lift it without moving a parameter
    (the ``models/transformer.py::_mlp_sublayer`` arrangement)."""
    m = mdl.sizes
    h = RMSNorm(m.rms_eps, mdl.compute_dtype, name="norm2")(h)
    return SparseMoE(
        router_width=m.router_width, held_experts=m.held_experts,
        experts_per_token=m.experts_per_token, d_ff=m.expert_d_ff,
        shared_d_ff=m.shared_d_ff, norm_topk_prob=m.norm_topk_prob,
        compute_dtype=mdl.compute_dtype, name="moe")(h)


class HybridBlock(nn.Module):
    sizes: HybridMoESizes
    full_attention: bool
    attn_impl: str
    compute_dtype: Any
    remat_moe: bool = False

    @nn.compact
    def __call__(self, x, positions):
        m, dt = self.sizes, self.compute_dtype
        h = RMSNorm(m.rms_eps, dt, name="norm1")(x)
        if self.full_attention:
            mixed = GatedAttention(
                n_heads=m.n_heads, n_kv_heads=m.n_kv_heads,
                head_dim=m.head_dim,
                rotary_dim=int(m.head_dim * m.partial_rotary_factor),
                rope_base=m.rope_base, eps=m.rms_eps,
                attn_impl=self.attn_impl, compute_dtype=dt,
                name="attn")(h, positions)
        else:
            mixed = GatedDeltaNet(
                key_heads=m.linear_key_heads, value_heads=m.linear_value_heads,
                key_dim=m.linear_key_dim, value_dim=m.linear_value_dim,
                kernel_size=m.conv_kernel, eps=m.rms_eps, compute_dtype=dt,
                name="gdn")(h)
        x = x + mixed
        sublayer = nn.remat(_moe_sublayer) if self.remat_moe else _moe_sublayer
        return x + sublayer(self, x)


class HybridMoELM(nn.Module):
    """Causal LM: tokens [B, L] → logits [B, L, vocab] (module docstring).
    Sequence-local attention only (``attn_impl`` ``"dense"`` or ``"flash"``);
    ``remat`` / ``remat_policy`` as ``TransformerLM``'s (``"mlp"``: norm 2 +
    MoE recomputed in the backward pass; ``"block"``: the whole block, but
    for what ``models/transformer.py::whole_block_policy`` keeps — the flash
    kernel's inputs and outputs where a full-attention layer runs it, the
    attention's output, the expert layer's routing, rows and
    pre-activations, the shared expert's pre-activations)."""

    sizes: HybridMoESizes
    attn_impl: str = "dense"
    compute_dtype: Any = jnp.float32
    remat: bool = False
    remat_policy: str = "mlp"

    #: What ``train/lm_step.py`` asks a model that counts: the collection
    #: it sows into, the per-step reduction of what was sown, and which of
    #: the reduced counts are also running totals.
    stats_collection = STATS_COLLECTION
    stats_counters = ("moe_held_rows", "moe_dropped_rows")

    step_stats = staticmethod(routing_counts)

    @nn.compact
    def __call__(self, tokens, *, train: bool = False,
                 return_hidden: bool = False):
        del train  # no dropout; kept for the shared train-step interface
        if self.attn_impl not in ("dense", "flash"):
            raise ValueError(
                "HybridMoELM runs sequence-local attention only "
                f"(attn_impl 'dense' or 'flash', got {self.attn_impl!r}): "
                "the delta rule's state does not cross a sequence shard")
        if self.remat_policy not in ("mlp", "block"):
            raise ValueError(
                f"remat_policy must be 'mlp' or 'block', got "
                f"{self.remat_policy!r}")
        m, dt = self.sizes, self.compute_dtype
        positions = jnp.arange(tokens.shape[1])
        x = nn.Embed(m.vocab_size, m.d_model, dtype=dt,
                     embedding_init=_INIT, name="embed")(tokens)
        whole_block = self.remat and self.remat_policy == "block"
        block_cls = (remat_whole_block(HybridBlock) if whole_block
                     else HybridBlock)
        for i in range(m.n_layers):
            x = block_cls(
                sizes=m,
                full_attention=(i + 1) % m.full_attention_interval == 0,
                attn_impl=self.attn_impl, compute_dtype=dt,
                remat_moe=self.remat and self.remat_policy == "mlp",
                name=f"block_{i}")(x, positions)
        x = RMSNorm(m.rms_eps, dt, name="norm_f")(x)
        if return_hidden:
            return x
        with jax.named_scope("head"):
            logits = _dense(m.vocab_size, dt, "lm_head")(x)
        return logits.astype(jnp.float32)
