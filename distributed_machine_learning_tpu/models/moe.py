"""Mixture-of-Experts transformer (Switch-style top-1 routing).

Routing and the dropless grouped compute are ``ops/grouped.py``'s one
routed front end (``route_topk`` + ``grouped_expert_mlp``: k experts a
token, GELU or gated SiLU experts, with or without biases, all experts or
a held range of them); this module is its ``k = 1``, GELU, biased,
all-experts-held case, and ``models/hybrid_moe.py`` its top-k, gated,
bias-free, held-range case.

Model family beyond the reference (EP/MoE absent — SURVEY.md §2.3), built
for expert parallelism the GSPMD way: every expert-owned parameter carries
a leading ``[n_experts, ...]`` axis, routing is expressed as static-shape
einsums against a dispatch one-hot (no gather/scatter, no dynamic shapes),
and when ``parallel/expert_parallel.py`` shards that leading axis over the
mesh's ``expert`` axis, XLA's partitioner turns the dispatch/combine
einsums into the token all-to-all — Switch Transformer's comm pattern,
inserted by the compiler.

Capacity semantics (Switch): each expert processes at most
``capacity = ceil(tokens/n_experts · capacity_factor)`` tokens per batch;
overflow tokens are dropped (their MLP output is zero and they pass
through the residual unchanged — exactly Switch's overflow behavior).
The router's load-balancing auxiliary loss (Switch eq. 4:
``E · Σ_e f_e·P_e``) is sown into the ``losses`` collection; the MoE train
step adds it with weight ``aux_loss_weight``.
"""

from __future__ import annotations

import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

class MoEMLP(nn.Module):
    """Switch (top-1) routed expert MLP over [B, T, D] activations: the
    ``k = 1`` case of ``ops/grouped.py::route_topk``.

    Two compute paths behind that one routing front end (``moe_impl``):

    - ``"einsum"`` (default): Switch-style capacity + overflow drops via
      static one-hot dispatch/combine einsums — the GSPMD-shardable form
      whose E axis ``parallel/expert_parallel.py`` shards to get the
      token all-to-all.
    - ``"grouped"``: dropless sort + ``lax.ragged_dot`` grouped matmuls
      (``ops/grouped.py``) — no capacity, no O(N²·D) dispatch FLOPs;
      the fast path on a single device or under shard_map DP, where no
      expert-axis partitioning is in play.
    """

    n_experts: int
    d_ff: int
    capacity_factor: float = 1.25
    compute_dtype: Any = jnp.float32
    moe_impl: str = "einsum"
    # Manual expert parallelism (shard_map context): when ``expert_axis``
    # is set, this module's expert params are declared at their LOCAL
    # shard shape [E/ep, ...] and the grouped compute path dispatches
    # token rows to their owner device with an explicit all_to_all
    # (ops/grouped.py::grouped_expert_mlp_ep).  ``token_axes`` names
    # every mesh axis the token rows are sharded over, so the Switch aux
    # loss is computed from GLOBAL routing statistics (pmean'd fractions)
    # — numerically the same aux the unsharded model computes.
    expert_axis: str | None = None
    token_axes: tuple = ()
    # Manual-EP send-slot bound (ADVICE r4; ops/grouped.py): None =
    # N_local slots per owner (provably dropless, ~ep× the useful
    # all-to-all rows on a balanced router); an int bounds the wire
    # bytes at Switch-style per-owner overflow drops.
    ep_slots_per_owner: int | None = None
    # Dropless routing regardless of capacity_factor.  Serving sets
    # this: Switch's capacity drop is a TRAINING-time load-balancing
    # mechanism whose drop pattern depends on the batch shape — a
    # decode step's N is B·1, so per-expert capacity collapses and two
    # batch rows routing to one expert would silently drop a token,
    # diverging the served stream from the trained model.  Dropless
    # compute runs the GROUPED path (sort + ragged_dot) regardless of
    # ``moe_impl``: it is dropless with no one-hot, so a served prompt
    # prefill costs O(N·D) dispatch instead of the einsum's O(N²·E)
    # one-hot tensors (a multi-thousand-token prompt under the einsum
    # dispatch would OOM on the [N, E, N] slot one-hot — ADVICE r4).
    dropless: bool = False
    # "int8" = weight-only quantized expert serving (dropless/decode
    # only): expert weights are int8 with per-expert per-output-channel
    # scales, read through the scale-folded ragged_dot
    # (ops/grouped.py::grouped_expert_mlp).  The router stays f32 —
    # routing decisions are argmax ties waiting to happen, and its
    # [D, E] matmul has no bandwidth to win.
    weight_quant: str | None = None
    # Manual Megatron TP for DECODE (make_tp_generate_fn's shard_map):
    # this module is then configured at its LOCAL expert width
    # (d_ff = F/tp — the column/row split applied per expert), the
    # router runs replicated (identical routing on every device), and
    # the psum below completes the per-expert row-parallel w_out
    # (b_out pre-divided by tp — tp_decode_params).  Serving-only.
    tp_axis: str | None = None

    @nn.compact
    def __call__(self, x):
        if self.moe_impl not in ("einsum", "grouped"):
            raise ValueError(
                f"moe_impl must be 'einsum' or 'grouped', got {self.moe_impl!r}"
            )
        if self.expert_axis is not None and self.moe_impl != "grouped":
            raise ValueError(
                "expert_axis (the manual shard_map EP path) requires "
                "moe_impl='grouped'; einsum EP is the GSPMD step "
                "(parallel/expert_parallel.py::make_ep_train_step)"
            )
        if self.weight_quant not in (None, "int8"):
            raise ValueError(
                f"weight_quant must be None or 'int8', got "
                f"{self.weight_quant!r}"
            )
        if self.weight_quant is not None and not self.dropless:
            raise ValueError(
                "weight_quant is a serving feature (int8 experts are not "
                "trainable); it requires the dropless serving path "
                "(decode=True — inference/generate.py clones it on)"
            )
        if self.ep_slots_per_owner is not None and self.expert_axis is None:
            raise ValueError(
                "ep_slots_per_owner bounds the manual-EP dispatch "
                "all-to-all; it requires expert_axis (the shard_map EP "
                "path) — without it the grouped path is dropless and "
                "the bound would be silently ignored"
            )
        if self.weight_quant is not None and self.expert_axis is not None:
            raise NotImplementedError(
                "int8 expert serving is single-host (no manual-EP "
                "shard_map decode path exists to quantize)"
            )
        if self.tp_axis is not None and not self.dropless:
            raise ValueError(
                "tp_axis is the manual TP-decode wiring (serving only); "
                "training-time expert parallelism is the EP step "
                "(parallel/expert_parallel.py)"
            )
        if self.tp_axis is not None and self.expert_axis is not None:
            raise NotImplementedError(
                "TP decode and manual-EP shard_map do not compose (one "
                "shard_map program each); shard experts' d_ff via tp"
            )
        B, T, D = x.shape
        N = B * T
        E = self.n_experts
        tokens = x.reshape(N, D)

        # Router in fp32: small matmul, precision matters for argmax ties.
        gate = nn.Dense(E, dtype=jnp.float32, name="router")(
            tokens.astype(jnp.float32)
        )
        probs = jax.nn.softmax(gate, axis=-1)  # [N, E]
        from distributed_machine_learning_tpu.ops.grouped import route_topk

        # Switch routing is the k = 1 case of the one routed front end.
        routed_idx, routed_weights = route_topk(probs, 1)  # [N, 1] each
        expert_idx = routed_idx[:, 0]  # [N]
        expert_prob = routed_weights[:, 0]  # [N]
        onehot = jax.nn.one_hot(expert_idx, E, dtype=jnp.float32)  # [N, E]

        # Switch aux loss: E · Σ_e (token fraction)·(mean router prob).
        # Under manual sharding the fractions pmean over every token-
        # sharded axis first, so the sown scalar equals the global-batch
        # aux on every device (and the einsum-EP / single-device value).
        frac = onehot.mean(axis=0)
        mean_prob = probs.mean(axis=0)
        if self.token_axes:
            from jax import lax

            frac = lax.pmean(frac, self.token_axes)
            mean_prob = lax.pmean(mean_prob, self.token_axes)
        self.sow("losses", "load_balancing", E * jnp.sum(frac * mean_prob))

        dt = self.compute_dtype
        if self.expert_axis is not None:
            from jax import lax

            ep = lax.axis_size(self.expert_axis)
            if E % ep:
                raise ValueError(
                    f"n_experts={E} must divide over expert axis size {ep}"
                )
            e_param = E // ep  # params declared at the LOCAL shard shape
        else:
            e_param = E
        if self.weight_quant == "int8":
            # Serving layout (quantize_lm_params writes it): int8 expert
            # kernels + per-(expert, out-channel) f32 scales; biases keep
            # the unquantized shape.  Zeros/ones inits — real values come
            # from the converted checkpoint.
            w_in = self.param(
                "w_in_q", nn.initializers.zeros, (e_param, D, self.d_ff),
                jnp.int8,
            )
            w_in_scale = self.param(
                "w_in_scale", nn.initializers.ones, (e_param, self.d_ff),
                jnp.float32,
            )
            w_out = self.param(
                "w_out_q", nn.initializers.zeros, (e_param, self.d_ff, D),
                jnp.int8,
            )
            w_out_scale = self.param(
                "w_out_scale", nn.initializers.ones, (e_param, D),
                jnp.float32,
            )
        else:
            w_in = self.param(
                "w_in", nn.initializers.lecun_normal(), (e_param, D, self.d_ff)
            )
            w_out = self.param(
                "w_out", nn.initializers.lecun_normal(), (e_param, self.d_ff, D)
            )
            w_in_scale = w_out_scale = None
        b_in = self.param("b_in", nn.initializers.zeros, (e_param, self.d_ff))
        b_out = self.param("b_out", nn.initializers.zeros, (e_param, D))

        if self.expert_axis is not None:
            from distributed_machine_learning_tpu.ops.grouped import (
                grouped_expert_mlp_ep,
            )

            y = grouped_expert_mlp_ep(
                tokens.astype(dt), expert_idx, w_in, b_in, w_out, b_out,
                expert_axis=self.expert_axis, n_experts_global=E,
                slots_per_owner=self.ep_slots_per_owner,
            )
            y = y * expert_prob[:, None].astype(dt)
            return y.reshape(B, T, D)

        # Serving (dropless) always computes through the grouped path —
        # see the ``dropless`` field note: same dropless math as
        # "einsum with capacity=N" minus the O(N²·E) one-hots, and the
        # only expert path the int8 serving scales are wired through.
        if self.moe_impl == "grouped" or self.dropless:
            from distributed_machine_learning_tpu.ops.grouped import (
                grouped_expert_mlp,
            )

            y = grouped_expert_mlp(
                tokens.astype(dt), routed_idx, routed_weights, w_in, w_out,
                b_in=b_in, b_out=b_out,
                w_in_scale=w_in_scale, w_out_scale=w_out_scale,
            )
            if self.tp_axis is not None:
                # Megatron's second g-collective, per expert: w_out is
                # row-parallel over the local d_ff slice (b_out and the
                # router-prob scale commute with the sum — both are
                # identical across devices).
                from jax import lax

                y = lax.psum(y, self.tp_axis)
            return y.reshape(B, T, D)

        # Position of each token within its expert's queue; drop overflow.
        capacity = max(1, math.ceil(N / E * self.capacity_factor))
        pos = jnp.cumsum(onehot, axis=0) * onehot  # 1-based where routed
        within = (pos > 0) & (pos <= capacity)
        slot = jax.nn.one_hot(
            (pos - 1).clip(0).astype(jnp.int32), capacity, dtype=jnp.float32
        )  # [N, E, C]
        dmask = slot * within.astype(jnp.float32)[..., None]  # [N, E, C]

        # Dispatch → expert FFN → combine: three static einsums whose E axis
        # shards over the mesh (the all_to_all lives inside the first/last).
        xe = jnp.einsum("nd,nec->ecd", tokens.astype(dt), dmask.astype(dt))
        h = nn.gelu(
            jnp.einsum("ecd,edf->ecf", xe, w_in.astype(dt))
            + b_in.astype(dt)[:, None, :]
        )
        ye = jnp.einsum("ecf,efd->ecd", h, w_out.astype(dt)) + b_out.astype(dt)[
            :, None, :
        ]
        y = jnp.einsum("ecd,nec->nd", ye, dmask.astype(dt))
        y = y * expert_prob[:, None].astype(dt)  # router-scaled (Switch)
        return y.reshape(B, T, D)


# Attention impls that need no sequence mesh axis — the set both the
# model's guard and make_ep_train_step's guard accept.
SEQ_LOCAL_ATTN_IMPLS = ("dense", "flash", "auto")
# The sequence-SHARDED impls (MoE × context parallelism): one constant so
# the model's RoPE-offset branch and the step builders can never disagree
# about which impls shard the sequence.
SEQ_SHARDED_ATTN_IMPLS = ("ring", "ring_flash", "ulysses")


def _moe_block(model: "MoETransformerLM", name: str) -> "nn.Module":
    """A transformer Block whose MLP is the routed expert mixture — the
    shared ``models.transformer.Block`` wiring, not a copy."""
    from distributed_machine_learning_tpu.models.transformer import Block

    return Block(
        n_heads=model.n_heads,
        n_kv_heads=model.n_kv_heads,
        d_ff=model.d_ff or 4 * model.d_model,
        attn_impl=model.attn_impl,
        seq_axis=model.seq_axis,
        compute_dtype=model.compute_dtype,
        flash_mesh=model.flash_mesh,
        flash_batch_axis=model.flash_batch_axis,
        # Selective remat (models/transformer.py::_mlp_sublayer wraps
        # the mlp_factory too): LN2 + the routed expert MLP recompute
        # in backward; attention residuals stay saved.
        remat_mlp=model.remat,
        decode=model.decode,
        kv_cache_dtype=model.kv_cache_dtype,
        decode_continuation=model.decode_continuation,
        # Attention projections follow the same int8 serving story as
        # the dense LM (ops/quant.py::QuantDenseGeneral).
        weight_quant=model.weight_quant,
        # Manual TP decode: attention psums ride the shared Block
        # wiring; head_dim pins the GLOBAL per-head width.
        tp_axis=model.tp_axis,
        head_dim=model.head_dim,
        mlp_factory=lambda: MoEMLP(
            n_experts=model.n_experts,
            d_ff=model.d_ff or 4 * model.d_model,
            capacity_factor=model.capacity_factor,
            compute_dtype=model.compute_dtype,
            moe_impl=model.moe_impl,
            expert_axis=model.expert_axis,
            token_axes=model.token_axes,
            ep_slots_per_owner=model.ep_slots_per_owner,
            # Serving routes dropless (see MoEMLP.dropless), through the
            # grouped sort+ragged_dot compute path.
            dropless=model.decode,
            weight_quant=model.weight_quant,
            tp_axis=model.tp_axis,
            name="moe",
        ),
        name=name,
    )


class MoETransformerLM(nn.Module):
    """Decoder-only LM with a routed expert MLP in every block."""

    vocab_size: int
    d_model: int = 256
    n_layers: int = 4
    n_heads: int = 8
    n_experts: int = 8
    d_ff: int | None = None
    capacity_factor: float = 1.25
    aux_loss_weight: float = 0.01
    compute_dtype: Any = jnp.float32
    # "einsum" (capacity + drops, EP-shardable) or "grouped" (dropless
    # ragged_dot; composes with real EP via the manual shard_map step).
    moe_impl: str = "einsum"
    # dense / flash / auto (sequence-local kernels) anywhere; the
    # sequence-SHARDED impls (ring/ring_flash/ulysses) additionally
    # require the manual MoE × context-parallel step
    # (parallel/expert_parallel.py::make_ep_grouped_train_step with
    # seq_axis) — a mesh whose ``seq_axis`` appears in ``token_axes``.
    attn_impl: str = "dense"
    seq_axis: str = "seq"
    # Flash-under-GSPMD composition; see ``transformer.Attention``.
    flash_mesh: Any = None
    flash_batch_axis: str = "batch"
    # Manual shard_map EP (see ``MoEMLP.expert_axis``): the step builder
    # (parallel/expert_parallel.py::make_ep_grouped_train_step) clones
    # the model with these set; user code leaves them None/().
    expert_axis: str | None = None
    token_axes: tuple = ()
    # Manual-EP send-slot bound (see ``MoEMLP.ep_slots_per_owner``).
    ep_slots_per_owner: int | None = None
    # Grouped-query attention (see ``transformer.Attention``); None =
    # classic MHA with the fused qkv layout.
    n_kv_heads: int | None = None
    # Selective rematerialization: checkpoint LN2 + the expert MLP of
    # every block (the "mlp" policy — attention residuals stay saved,
    # backward never re-runs attention; models/transformer.py).  The
    # long-context enabler for MoE exactly as for the dense LM.
    remat: bool = False
    # KV-cached autoregressive serving, exactly as TransformerLM: the
    # attention caches live in the shared Block; the router runs
    # per-token, so routed expert compute needs no cache at all.
    # ``inference/generate.py`` clones these on.
    decode: bool = False
    kv_cache_dtype: Any = None
    decode_continuation: bool = False
    # Per-row cache frontiers (batched speculative decoding) — same
    # contract as ``TransformerLM.decode_batched_frontier``.
    decode_batched_frontier: bool = False
    # Manual Megatron TP for DECODE (``tp_local_decode_clone`` sets
    # these): attention heads/KV cache and every expert's d_ff shard
    # over the model axis; embed/router/lm_head/LayerNorms replicate.
    # Same contract as ``TransformerLM.tp_axis``/``head_dim``.
    tp_axis: str | None = None
    head_dim: int | None = None
    # "int8" = weight-only quantized serving (decode only): attention
    # projections and the lm_head through QuantDenseGeneral, expert
    # weights through the scale-folded ragged_dot (``MoEMLP``); params
    # from ``ops.quant.quantize_lm_params`` (it recognizes the expert
    # leaves).  The router stays f32.
    weight_quant: str | None = None

    @nn.compact
    def __call__(self, tokens, *, train: bool = False):
        del train
        if self.weight_quant is not None and not self.decode:
            raise ValueError(
                "weight_quant is a serving-decode feature (int8 weights "
                "are not trainable); clone with decode=True — "
                "inference/generate.py does this"
            )
        if self.tp_axis is not None and not self.decode:
            raise ValueError(
                "tp_axis is the manual TP-decode wiring "
                "(make_tp_generate_fn); training-time parallelism for "
                "MoE is the EP step (parallel/expert_parallel.py)"
            )
        seq_sharded = self.seq_axis in self.token_axes
        if self.attn_impl not in SEQ_LOCAL_ATTN_IMPLS and not seq_sharded:
            raise NotImplementedError(
                "MoETransformerLM runs the sequence-local attention "
                "kernels (dense/flash/auto) under plain apply; the "
                "sequence-sharded impls (ring/ring_flash/ulysses) need "
                "the MoE x context-parallel step, which clones the model "
                "with the seq axis in token_axes "
                "(parallel/expert_parallel.py::make_ep_grouped_train_step)"
            )
        B, L = tokens.shape
        if self.decode:
            if self.attn_impl != "dense":
                raise ValueError(
                    "decode mode runs dense cached attention; clone the "
                    'model with attn_impl="dense" (generate.py does this)'
                )
            # Autoregressive position tracking — one counter for the
            # stack (or one per ROW under decode_batched_frontier),
            # same contract as TransformerLM.
            if self.decode_batched_frontier:
                idx = self.variable(
                    "cache", "idx", lambda: jnp.zeros((B,), jnp.int32)
                )
                start = idx.value  # [B]
                positions = start[:, None] + jnp.arange(L)[None, :]
            else:
                idx = self.variable(
                    "cache", "idx", lambda: jnp.zeros((), jnp.int32)
                )
                start = idx.value
                positions = start + jnp.arange(L)
            if not self.is_initializing():
                idx.value = start + L
        elif self.attn_impl in SEQ_SHARDED_ATTN_IMPLS:
            # Sequence-sharded: this device holds chunk axis_index(seq)
            # of the global sequence — same RoPE offset rule as
            # TransformerLM, so sharded and unsharded logits match.
            from jax import lax

            offset = lax.axis_index(self.seq_axis) * L
            positions = offset + jnp.arange(L)
        else:
            positions = jnp.arange(L)
        x = nn.Embed(
            self.vocab_size, self.d_model, dtype=self.compute_dtype, name="embed"
        )(tokens)
        for i in range(self.n_layers):
            x = _moe_block(self, name=f"block_{i}")(x, positions)
        x = nn.LayerNorm(dtype=self.compute_dtype, name="ln_f")(x)
        if self.weight_quant == "int8":
            from distributed_machine_learning_tpu.ops.quant import (
                QuantDenseGeneral,
            )

            logits = QuantDenseGeneral(
                out_features=(self.vocab_size,),
                compute_dtype=self.compute_dtype, name="lm_head",
            )(x)
        else:
            logits = nn.Dense(
                self.vocab_size, dtype=self.compute_dtype, name="lm_head"
            )(x)
        return logits.astype(jnp.float32)
