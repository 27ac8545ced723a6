"""Pipeline parallelism: GPipe-style SPMD pipeline over a ``pipe`` mesh axis.

Capability beyond the reference (PP absent — SURVEY.md §2.3), built the
TPU way: transformer blocks are *stacked* along a leading layer axis and
that axis is sharded over the mesh, so every device holds a contiguous
span of layers.  The schedule is a single SPMD loop: each tick, every
device applies its span to its current microbatch activation, then the
activations rotate one hop along the ring via ``lax.ppermute``.  Stage 0
injects a fresh embedded microbatch per tick; the last stage peels off
finished microbatches into the loss.  After ``M + P − 1`` ticks all ``M``
microbatches have flowed through all ``P`` stages.

The backward pass needs no hand-written schedule: the transpose of
``ppermute`` is the reverse ``ppermute``, so ``jax.grad`` of this loop IS
the reverse pipeline, with XLA free to overlap the per-tick collective
with the neighboring stage compute.

What grad-of-scan FIXES, though, is the schedule: all forwards complete
before any backward starts (GPipe), so a stage holds (or remats) every
microbatch's activations at once — O(M) memory that caps how many
microbatches can amortize the (P−1)/(M+P−1) bubble.  Two sibling
schedules attack the two costs separately: 1F1B
(``parallel/pipeline_1f1b.py``, the CLI default) hand-writes the
one-backward-per-forward tick order to cut activation memory to O(P),
and the interleaved schedule (``parallel/pipeline_interleaved.py``,
``--pp-schedule interleaved``) gives each device v virtual stages to
cut the bubble itself to (P−1)/(v·M+P−1).  This module remains the
jax.grad-schedule reference both are property-tested against.

Parameter layout inside ``shard_map``:
  - ``blocks``: every Block param stacked to ``[n_layers, ...]``, sharded
    ``P("pipe", ...)`` → local ``[n_layers/P, ...]``, consumed by
    ``lax.scan`` (static shapes, one compiled block body per device);
  - ``embed`` / ``ln_f`` / ``lm_head``: replicated; only one stage's
    contribution is non-zero, so their gradients are ``psum``-ed over the
    pipe axis (the zero shares from other stages are free).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributed_machine_learning_tpu.models.transformer import (
    Block,
    TransformerLM,
    whole_block_policy,
)
from distributed_machine_learning_tpu.train.losses import lm_cross_entropy
from distributed_machine_learning_tpu.train.optimizers import (
    moment_layout as _moment_layout,
    update_fn_for_config,
)
from distributed_machine_learning_tpu.train.state import TrainState
from distributed_machine_learning_tpu.runtime.mesh import (
    shard_map_no_check as _shard_map,
)

PIPE_AXIS = "pipe"


def _block_module(model: TransformerLM) -> Block:
    # Flash passes through for the pipeline steps.  Pure pipeline: the
    # shard_map is FULLY manual over the pipe axis, so the Pallas call
    # sees local [mb, L] shapes natively (flash_mesh stays None).  The
    # 3-D step (partial-manual: batch/model automatic) sets flash_mesh +
    # flash_manual_axes on its model clone, and the wrap manualizes the
    # remaining axes from inside the pipe-manual region (parallel3d.py).
    return Block(
        n_heads=model.n_heads,
        d_ff=model.d_ff or 4 * model.d_model,
        attn_impl="flash" if model.attn_impl == "flash" else "dense",
        seq_axis=model.seq_axis,
        compute_dtype=model.compute_dtype,
        n_kv_heads=model.n_kv_heads,
        flash_mesh=model.flash_mesh,
        flash_batch_axis=model.flash_batch_axis,
        flash_head_axis=model.flash_head_axis,
        flash_manual_axes=model.flash_manual_axes,
        # The selective remat policy lives INSIDE the block (LN2+MLP
        # checkpointed, attention residuals saved), so the pipeline
        # honors it here; the "block" policy is applied by
        # _apply_local_span's whole-layer jax.checkpoint instead — see
        # _whole_layer_remat.
        remat_mlp=model.remat and model.remat_policy == "mlp",
    )


def _whole_layer_remat(model: TransformerLM) -> bool:
    """True when the pipeline span scan should wrap each layer in
    ``jax.checkpoint`` — i.e. ``remat=True`` under the whole-block
    policy.  The selective "mlp" policy checkpoints inside the Block
    (``_block_module``) and must NOT also be wrapped here, or the outer
    checkpoint would re-run attention anyway, silently downgrading the
    policy the user asked for."""
    return model.remat and model.remat_policy == "block"


def stack_lm_params(params: dict, n_layers: int) -> dict:
    """TransformerLM params (block_0..block_{n-1} dicts) → pipeline layout
    (one ``blocks`` tree with leading layer axis)."""
    blocks = [params[f"block_{i}"] for i in range(n_layers)]
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *blocks)
    return {
        "embed": params["embed"],
        "blocks": stacked,
        "ln_f": params["ln_f"],
        "lm_head": params["lm_head"],
    }


def unstack_lm_params(pipeline_params: dict, n_layers: int) -> dict:
    """Inverse of ``stack_lm_params`` (for checkpoint interop/tests)."""
    out = {
        "embed": pipeline_params["embed"],
        "ln_f": pipeline_params["ln_f"],
        "lm_head": pipeline_params["lm_head"],
    }
    for i in range(n_layers):
        out[f"block_{i}"] = jax.tree_util.tree_map(
            lambda x, i=i: x[i], pipeline_params["blocks"]
        )
    return out


def init_pipeline_state(model: TransformerLM, seed: int = 69143,
                        config=None) -> TrainState:
    """Initialize TransformerLM params (dense path) and restack them.
    ``config``: optional optimizer config (as in ``init_lm_state``)."""
    from distributed_machine_learning_tpu.train.lm_step import init_lm_state

    state = init_lm_state(model, seed=seed, config=config)
    return TrainState.create(
        params=stack_lm_params(state.params, model.n_layers),
        rng=state.rng,
        config=state.config,
    )


def _apply_local_span(block: Block, stacked_local, x, positions,
                      remat: bool = False):
    """Run this device's span of layers over x via lax.scan.

    ``remat=True`` wraps each layer application in ``jax.checkpoint``
    under the LMs' whole-block policy (``whole_block_policy``: the flash
    kernel's folded q, k, v and ``(out, lse)`` are kept, and on
    ``grouped_expert_mlp``'s path the routing, the dispatched rows and the
    pre-activations; everything else is made again — so each (tick ×
    layer) holds those bytes beside its input: ``Block``'s docstring):
    the backward pipeline then recomputes block activations instead of
    holding every (tick × layer) activation live — the memory term that
    otherwise scales with microbatch count under grad-of-scan."""

    def apply_layer(layer_params, h):
        return block.apply({"params": layer_params}, h, positions)

    if remat:
        apply_layer = jax.checkpoint(apply_layer, policy=whole_block_policy())

    def body(h, layer_params):
        return apply_layer(layer_params, h), None

    h, _ = lax.scan(body, x, stacked_local)
    return h


def _pipeline_forward_loss(
    model: TransformerLM,
    params: dict,
    tokens_mb,  # [M, mb, L] int32 (replicated)
    targets_mb,  # [M, mb, L] int32
    *,
    pipe_axis: str,
    num_stages: int,
):
    """Forward + loss for all microbatches through the SPMD pipeline."""
    import flax.linen as nn

    block = _block_module(model)
    M, mb, L = tokens_mb.shape
    E = model.d_model
    rank = lax.axis_index(pipe_axis)
    positions = jnp.arange(L)
    is_first = rank == 0
    is_last = (rank == num_stages - 1).astype(jnp.float32)
    perm = [(i, (i + 1) % num_stages) for i in range(num_stages)]

    # The exact stage-boundary modules TransformerLM uses, applied with the
    # pipeline's param slices — bit-identical numerics to the dense model.
    embed_mod = nn.Embed(model.vocab_size, E, dtype=model.compute_dtype)
    ln_f_mod = nn.LayerNorm(dtype=model.compute_dtype)
    head_mod = nn.Dense(model.vocab_size, dtype=model.compute_dtype)

    def embed(tok):
        return embed_mod.apply({"params": params["embed"]}, tok)

    def head_loss(x, tgt):
        h = ln_f_mod.apply({"params": params["ln_f"]}, x)
        logits = head_mod.apply({"params": params["lm_head"]}, h)
        return lm_cross_entropy(logits.astype(jnp.float32), tgt)

    # One lax.scan over the M+P−1 ticks: the body is traced once, so
    # program size (and compile time) is independent of the microbatch
    # count — tick-dependent behavior (injection window, peel-off window)
    # is expressed as masks on the traced tick index.
    def tick_core(act, loss_acc, t):
        # Stage 0 ingests microbatch t (clamped index; masked elsewhere).
        inject = embed(
            lax.dynamic_index_in_dim(tokens_mb, jnp.clip(t, 0, M - 1), keepdims=False)
        )
        x = jnp.where(is_first & (t < M), inject, act)
        y = _apply_local_span(block, params["blocks"], x, positions,
                              remat=_whole_layer_remat(model))
        # Last stage peels off microbatch m = t − (P−1).
        m = t - (num_stages - 1)
        tgt = lax.dynamic_index_in_dim(
            targets_mb, jnp.clip(m, 0, M - 1), keepdims=False
        )
        valid = ((m >= 0) & (m < M)).astype(jnp.float32)
        return y, loss_acc + is_last * valid * head_loss(y, tgt)

    def tick(carry, t):
        act, loss_acc = carry
        y, loss_acc = tick_core(act, loss_acc, t)
        return (lax.ppermute(y, pipe_axis, perm), loss_acc), None

    T = M + num_stages - 1
    act = jnp.zeros((mb, L, E), model.compute_dtype)
    loss_acc = jnp.zeros((), jnp.float32)
    # Scan the first T−1 ticks; the final tick runs outside the scan so its
    # output needs no (wasted) ppermute hop.
    (act, loss_acc), _ = lax.scan(tick, (act, loss_acc), jnp.arange(T - 1))
    _, loss_acc = tick_core(act, loss_acc, jnp.asarray(T - 1))
    # Local loss: non-zero on the last stage only.  The psum that shares it
    # with every stage happens OUTSIDE value_and_grad — a psum inside the
    # differentiated region would inflate cotangents by the axis size under
    # shard_map with replication-checking off (its transpose conservatively
    # psums the cotangent).
    return loss_acc / M


def _reject_lars(config) -> None:
    """Shared guard for every pipeline schedule: inside the shard_map
    each device's "blocks" leaves are only its stage's slice, so LARS's
    per-leaf norms would be stage-local and the trust ratios would
    change with the stage count — the same flat-slice inexactness
    ZeRO-1/FSDP refuse (zero1.py / fsdp.py)."""
    from distributed_machine_learning_tpu.train.lars import LARSConfig

    if type(config) is LARSConfig:
        raise ValueError(
            "LARS is not supported under pipeline/3-D parallelism: "
            "per-leaf weight/grad norms would be computed on per-stage "
            "slices; use sgd or adamw (elementwise updates are exact on "
            "any slice)"
        )


_BOUNDARY_MODULES = ("embed", "ln_f", "lm_head")


def _boundary_mom(momentum, take):
    """Apply ``take`` (a subtree selector/merger) across the momentum
    slot's two possible layouts: params-shaped (SGD) or a dict of
    params-shaped moment trees (AdamW's ``{"mu","nu"}``)."""
    if isinstance(momentum, dict) and "blocks" not in momentum:
        return {k: take(v) for k, v in momentum.items()}
    return take(momentum)


def _sharded_boundary_update(state: TrainState, grads, pipe_axis: str,
                             num_stages: int):
    """ZeRO-1-over-pipe for the replicated boundary modules: each stage
    updates only its 1/P slice of the flattened (embed, ln_f, lm_head)
    parameter+moment vectors, then ring-gathers the updated slices back
    to replicated — so the boundary update compute shards P-fold and
    the gathers' ppermute hops get async windows the scheduler fills
    with the (much larger) stacked-blocks update math: the pipeline
    flavor of the overlap-aware sharded weight update (arxiv
    2004.13336), with the gather hidden under the tail of the step
    instead of feeding ROOT as one sync collective.

    Bit-identical to the replicated update: the boundary grads arrive
    psum'd (same reduction as before), elementwise updates are exact on
    any slice of the flat vector, and the ring gather is pure data
    movement.  The moments stay REPLICATED in the state (the public
    TrainState layout is unchanged — this shards the update's compute
    and schedule, not its storage), so the updated moment slices ride
    the same ring home as the params.
    """
    from jax.flatten_util import ravel_pytree

    from distributed_machine_learning_tpu.ops.ring import (
        ring_all_gather_flat,
    )

    update_fn = update_fn_for_config(state.config)
    take = lambda t: {k: t[k] for k in _BOUNDARY_MODULES}

    flat_p, unravel_p = ravel_pytree(take(state.params))
    flat_g, _ = ravel_pytree(take(grads))
    mom_sub = _boundary_mom(state.momentum, take)
    if isinstance(mom_sub, dict) and "embed" not in mom_sub:
        # AdamW layout: one flat vector per moment tree.
        pairs = {k: ravel_pytree(v) for k, v in mom_sub.items()}
        flat_m = {k: p[0] for k, p in pairs.items()}
        unravel_m = {k: p[1] for k, p in pairs.items()}
    else:
        flat_m, unravel_m = ravel_pytree(mom_sub)

    n_elems = flat_p.shape[0]
    padded = -(-n_elems // num_stages) * num_stages
    shard_len = padded // num_stages
    rank = lax.axis_index(pipe_axis)
    pad = lambda v: jnp.pad(v, (0, padded - v.shape[0]))
    slice_of = lambda v: lax.dynamic_slice(
        pad(v), (rank * shard_len,), (shard_len,)
    )

    p_slice = slice_of(flat_p)
    g_slice = slice_of(flat_g)
    m_slice = jax.tree_util.tree_map(slice_of, flat_m)
    new_p_slice, new_m_slice = update_fn(
        p_slice, m_slice, g_slice, state.config, step=state.step
    )

    gather = lambda s: ring_all_gather_flat(
        s, pipe_axis, num_stages, n_buckets=2
    )[:n_elems]
    new_boundary_p = unravel_p(gather(new_p_slice))
    if isinstance(flat_m, dict):
        new_boundary_m = {
            k: unravel_m[k](gather(new_m_slice[k])) for k in flat_m
        }
    else:
        new_boundary_m = unravel_m(gather(new_m_slice))
    return new_boundary_p, new_boundary_m


def pp_grads_and_update(state: TrainState, loss_fn, pipe_axis,
                        grad_constraint=None, overlap_update=False,
                        num_stages=None):
    """Shared back half of every jax.grad-scheduled pipeline step (GPipe
    and interleaved): differentiate the forward-loss, share the
    last-stage loss, psum the boundary-module grads, update.

    Invariants that must hold for ANY schedule using this: the psums
    stay OUTSIDE value_and_grad (a psum inside the differentiated region
    would inflate cotangents by the axis size under shard_map with
    replication-checking off), and every replicated (non-"blocks") param
    — each stage holds a share that is zero unless it used the param —
    is summed here; stage-sharded blocks grads are already exact
    locally.

    ``grad_constraint``: optional ``grads -> grads`` hook applied
    between the backward and the update — the ZeRO-1 × 3-D step
    annotates the grads with their dp-sharded MOMENT layout here, so
    GSPMD reshards once at the update instead of propagating the moment
    sharding up into the stacked-layer backward scatter (see
    ``parallel3d.py``).

    ``overlap_update``: shard the boundary-module update over the pipe
    axis and ring-gather the updated slices (see
    :func:`_sharded_boundary_update`) — bit-identical math, with the
    boundary gather off the step's sync tail.  Requires ``num_stages``.
    """
    _reject_lars(state.config)
    loss, grads = jax.value_and_grad(loss_fn)(state.params)
    loss = lax.psum(loss, pipe_axis)
    for name in _BOUNDARY_MODULES:
        grads[name] = jax.tree_util.tree_map(
            lambda g: lax.psum(g, pipe_axis), grads[name]
        )
    if grad_constraint is not None:
        grads = grad_constraint(grads)
    if overlap_update:
        if num_stages is None:
            raise ValueError("overlap_update requires num_stages")
        take_blocks = lambda t: {"blocks": t["blocks"]}
        blk_params, blk_mom = update_fn_for_config(state.config)(
            take_blocks(state.params),
            _boundary_mom(state.momentum, take_blocks),
            take_blocks(grads),
            state.config,
            step=state.step,
        )
        bnd_params, bnd_mom = _sharded_boundary_update(
            state, grads, pipe_axis, num_stages
        )
        new_params = {**bnd_params, **blk_params}

        def merge(blk, bnd):
            return {**bnd, **blk}

        if isinstance(state.momentum, dict) and "blocks" not in state.momentum:
            new_momentum = {
                k: merge(blk_mom[k], bnd_mom[k]) for k in state.momentum
            }
        else:
            new_momentum = merge(blk_mom, bnd_mom)
    else:
        new_params, new_momentum = update_fn_for_config(state.config)(
            state.params, state.momentum, grads, state.config,
            step=state.step
        )
    new_state = state.replace(
        params=new_params, momentum=new_momentum, step=state.step + 1
    )
    return new_state, loss


def _pp_step_impl(
    model, state: TrainState, tokens_mb, targets_mb, *, pipe_axis,
    num_stages, grad_constraint=None, overlap_update=False,
):
    loss_fn = partial(
        _pipeline_forward_loss,
        model,
        tokens_mb=tokens_mb,
        targets_mb=targets_mb,
        pipe_axis=pipe_axis,
        num_stages=num_stages,
    )
    return pp_grads_and_update(state, loss_fn, pipe_axis,
                               grad_constraint=grad_constraint,
                               overlap_update=overlap_update,
                               num_stages=num_stages)


def _state_specs(
    pipe_axis: str, params_example: dict, momentum_example=None
) -> TrainState:
    """shard_map PartitionSpec pytree for a pipeline TrainState."""

    def param_spec(tree, stacked: bool):
        return jax.tree_util.tree_map(
            lambda x: P(pipe_axis, *(None,) * (x.ndim - 1)) if stacked else P(),
            tree,
        )

    def specs(params):
        return {
            "embed": param_spec(params["embed"], False),
            "blocks": param_spec(params["blocks"], True),
            "ln_f": param_spec(params["ln_f"], False),
            "lm_head": param_spec(params["lm_head"], False),
        }

    p_specs = specs(params_example)
    return TrainState(
        params=p_specs,
        momentum=_moment_layout(p_specs, params_example, momentum_example),
        batch_stats={},
        step=P(),
        rng=P(),
        config=None,
    )


def make_pipeline_step(
    step_impl,
    model: TransformerLM,
    mesh: Mesh,
    num_microbatches: int,
    pipe_axis: str = PIPE_AXIS,
):
    """Shared pipeline step builder (GPipe and 1F1B): validation, the
    tree-structure-keyed jit cache, and the shard_map/donate wrapper
    around ``step_impl(model, state, tokens_mb, targets_mb, *,
    pipe_axis, num_stages)`` — one copy so the schedules cannot drift
    on anything but the schedule itself."""
    if model.attn_impl not in ("dense", "flash"):
        raise ValueError(
            "pipeline step supports attn_impl='dense' or 'flash' (the "
            "pipe-axis shard_map is fully manual, so the flash kernel "
            "runs on local shapes); sequence-sharded impls need a "
            "second mesh axis"
        )
    if pipe_axis not in mesh.axis_names:
        raise ValueError(f"mesh is missing axis {pipe_axis!r}: {mesh.axis_names}")
    num_stages = mesh.shape[pipe_axis]
    if model.n_layers % num_stages:
        raise ValueError(
            f"n_layers={model.n_layers} must divide evenly into "
            f"{num_stages} pipeline stages"
        )
    if num_microbatches < 1:
        raise ValueError("num_microbatches must be >= 1")

    impl = partial(
        step_impl, model, pipe_axis=pipe_axis, num_stages=num_stages
    )

    jitted: dict = {}

    def step(state: TrainState, tokens_mb, targets_mb):
        if tokens_mb.shape[0] != num_microbatches:
            raise ValueError(
                f"expected {num_microbatches} microbatches, got input shaped "
                f"{tokens_mb.shape} (use microbatch(tokens, targets, "
                f"{num_microbatches}))"
            )
        key = jax.tree_util.tree_structure(state)
        fn = jitted.get(key)
        if fn is None:
            state_spec = _state_specs(pipe_axis, state.params,
                                      state.momentum)
            state_spec = state_spec.replace(config=state.config)
            fn = jitted[key] = jax.jit(
                _shard_map(
                    impl,
                    mesh=mesh,
                    in_specs=(state_spec, P(), P()),
                    out_specs=(state_spec, P()),
                ),
                donate_argnums=(0,),
            )
        return fn(state, tokens_mb, targets_mb)

    return step


def make_pp_lm_train_step(
    model: TransformerLM,
    mesh: Mesh,
    num_microbatches: int,
    pipe_axis: str = PIPE_AXIS,
    overlap_update: bool = False,
):
    """Build the GPipe ``step(state, tokens_mb, targets_mb) ->
    (state, loss)``.

    ``tokens_mb``/``targets_mb``: [num_microbatches, mb, L] (see
    ``microbatch``).  ``state`` from ``init_pipeline_state`` +
    ``shard_pp_state``.  Requires ``n_layers % P == 0``.

    ``overlap_update=True``: shard the boundary-module (embed / ln_f /
    lm_head) optimizer update over the pipe axis and ring-gather the
    updated slices back (bit-identical math; the gather's ppermute hops
    overlap the stacked-blocks update — see
    :func:`_sharded_boundary_update`).
    """
    impl = (partial(_pp_step_impl, overlap_update=True)
            if overlap_update else _pp_step_impl)
    return make_pipeline_step(
        impl, model, mesh, num_microbatches, pipe_axis
    )


def shard_pp_state(
    state: TrainState, mesh: Mesh, pipe_axis: str = PIPE_AXIS
) -> TrainState:
    """Place a pipeline TrainState: blocks sharded over stages, rest
    replicated."""
    from distributed_machine_learning_tpu.telemetry import startup

    spec_state = _state_specs(pipe_axis, state.params, state.momentum)
    spec_state = spec_state.replace(config=state.config)
    with startup.place_state(state, mesh):
        return jax.tree_util.tree_map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
            state, spec_state,
        )


def microbatch(tokens, targets, num_microbatches: int):
    """[B, L] → [M, B/M, L] microbatch stacks (GPipe input layout)."""
    B = tokens.shape[0]
    if B % num_microbatches:
        raise ValueError(
            f"batch {B} not divisible by num_microbatches={num_microbatches}"
        )
    shape = (num_microbatches, B // num_microbatches) + tokens.shape[1:]
    return (
        jnp.asarray(tokens).reshape(shape),
        jnp.asarray(targets).reshape(shape),
    )
