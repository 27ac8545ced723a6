"""Composed 3-D parallelism: data × pipeline × tensor on one mesh.

The reference composes nothing — its one strategy axis is data-parallel
gradient sync (SURVEY.md §2.3).  This module runs all three major
parallelism dimensions simultaneously over a ``("batch", "pipe",
"model")`` mesh, the way a real TPU pod is carved up:

- **pipe** (pipeline): *manual* — the GPipe-style ppermute tick loop of
  ``parallel/pipeline.py``, reused verbatim: transformer blocks stacked
  on a leading layer axis and sharded over the pipe axis, activations
  rotating one hop per tick.
- **model** (tensor): *automatic* — block params additionally carry the
  Megatron column/row splits of ``parallel/tensor_parallel.py`` on their
  trailing dims; XLA's SPMD partitioner derives every activation sharding
  and inserts the per-block all-reduces.
- **batch** (data): *automatic* — each microbatch's batch dim is sharded
  over the data axis; the partitioner emits the gradient all-reduce.

The composition mechanism is partial-manual ``shard_map`` (jax's
``axis_names``): only ``pipe`` is manual inside the body — giving us
``lax.axis_index``/``ppermute`` for the schedule — while ``batch`` and
``model`` stay under GSPMD propagation, seeded by the state's
``NamedSharding``s at the jit boundary.  One compiled program carries the
pipeline collectives, the Megatron all-reduces, and the data-parallel
gradient reduction, and XLA is free to overlap all three.
"""

from __future__ import annotations

from functools import partial

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributed_machine_learning_tpu.models.transformer import TransformerLM
from distributed_machine_learning_tpu.parallel.pipeline import (
    _pp_step_impl,
    _state_specs,
    init_pipeline_state,
    microbatch,
)
from distributed_machine_learning_tpu.parallel.tensor_parallel import tp_spec_for
from distributed_machine_learning_tpu.runtime.mesh import (
    make_mesh,
    shard_map_no_check as _shard_map,
)
from distributed_machine_learning_tpu.train.state import TrainState

DATA_AXIS = "batch"
PIPE_AXIS = "pipe"
MODEL_AXIS = "model"
MESH_AXES = (DATA_AXIS, PIPE_AXIS, MODEL_AXIS)

__all__ = [
    "MESH_AXES",
    "make_3d_mesh",
    "p3_param_spec",
    "p3_zero1_moment_spec",
    "p3_zero1_grad_spec",
    "shard_3d_state",
    "make_3d_lm_train_step",
    "shard_3d_batch",
    "init_pipeline_state",
    "microbatch",
]


def make_3d_mesh(dp: int, pp: int, tp: int, devices=None) -> Mesh:
    """(dp, pp, tp)-shaped mesh over dp·pp·tp devices.

    Axis order puts ``model`` innermost (fastest-varying chips): on real
    hardware the Megatron all-reduces are the latency-critical
    collectives, so they get the shortest ICI hops; the per-tick pipe
    hop is next; the once-per-step data-parallel reduce rides whatever
    is left (DCN across hosts).
    """
    return make_mesh(
        dp * pp * tp, axis_names=MESH_AXES, axis_shape=(dp, pp, tp),
        devices=devices,
    )


def p3_param_spec(
    path: tuple[str, ...],
    ndim: int,
    pipe_axis: str = PIPE_AXIS,
    model_axis: str = MODEL_AXIS,
) -> P:
    """PartitionSpec for one pipeline-layout parameter under 3-D layout.

    ``blocks/...`` leaves have a leading stacked-layer dim sharded over
    the pipe axis and Megatron splits (``tp_spec_for``) on the rest;
    stage-boundary params (embed / ln_f / lm_head) replicate over pipe
    and keep their plain TP spec — except the embedding table, which
    replicates over model too: partitioning the token-gather's operand
    dim trips an XLA SPMD-partitioner CHECK under partial-manual
    shard_map (observed in XLA's PartitionGatherTrivialSlicedOperand-
    Dimensions), and an O(V·E) table is small next to the block stack.
    """
    if path and path[0] == "blocks":
        inner = tuple(tp_spec_for(path[1:], ndim - 1, model_axis))
        return P(pipe_axis, *inner)
    if path and path[0] == "embed":
        return P(*(None,) * ndim)
    return tp_spec_for(path, ndim, model_axis)


def _path_keys(path) -> tuple:
    """KeyPath → plain string keys — the ONE normalization the param,
    moment, and grad spec builders all share."""
    return tuple(k.key if hasattr(k, "key") else str(k) for k in path)


def p3_zero1_moment_spec(
    path: tuple[str, ...],
    shape: tuple[int, ...],
    dp: int,
    data_axis: str = DATA_AXIS,
) -> P:
    """Optimizer-moment PartitionSpec under ZeRO-1 × 3-D: the param's
    3-D spec (``p3_param_spec``) PLUS the data axis on the largest
    dp-divisible still-unsharded dim — the moments are the state the dp
    axis otherwise replicates dp-fold for nothing (a real pod LM run
    wants ZeRO-1 on its data axis; VERDICT r4 item 8).  Leaves with no
    divisible free dim replicate over dp, the O(d) minority (same
    degrade rule as ``fsdp_perlayer.fsdp_pl_spec_for``).  Params are
    NOT touched: every dp rank needs them whole each forward, and the
    update's shard→replicated transition is exactly the all-gather
    GSPMD inserts."""
    if path and path[0] == "embed":
        # Same exclusion (and reason) as p3_param_spec's embed rule: a
        # dp-sharded embedding MOMENT forces the partitioner to push the
        # vocab sharding up through the scatter-add gradient into the
        # token gather, tripping the same SPMD-partitioner CHECK under
        # partial-manual shard_map (observed from the cli.lm 3d
        # --zero1-dp program).  O(V·E) — noise next to the block stack.
        return P(*(None,) * len(shape))
    base = tuple(p3_param_spec(path, len(shape)))
    axes = list(base) + [None] * (len(shape) - len(base))
    best = None
    for i, d in enumerate(shape):
        if axes[i] is None and d % dp == 0 and d >= dp and (
            best is None or d > shape[best]
        ):
            best = i
    if best is not None:
        axes[best] = data_axis
    return P(*axes)


def p3_zero1_grad_spec(
    path: tuple[str, ...],
    shape: tuple[int, ...],
    dp: int,
    data_axis: str = DATA_AXIS,
    pipe_axis: str = PIPE_AXIS,
) -> P:
    """Gradient PartitionSpec at the zero1_dp backward→update boundary:
    the MOMENT's dp-sharded layout (``p3_zero1_moment_spec``) with the
    pipe axis dropped (pipe is manual inside the step's shard_map region
    — stacked-layer grads are already per-stage slices).  This is the
    annotation that lets GSPMD propagate the dp-sharded update end to
    end: the grads arrive at the update already in their consumer's
    layout, one planned reshard per leaf, instead of the old PARAM-spec
    barrier's dp-replicated pin (which forced a full-grad
    materialization and left the dp transition implicit)."""
    full = tuple(p3_zero1_moment_spec(path, shape, dp, data_axis))
    axes = [None if a == pipe_axis else a for a in full]
    return P(*(axes + [None] * (len(shape) - len(axes))))


def _state_shardings_3d(
    state: TrainState, mesh: Mesh, zero1_dp: bool = False
) -> TrainState:
    """NamedSharding pytree: params per ``p3_param_spec``; momentum the
    same, or additionally dp-sharded (``p3_zero1_moment_spec``) when
    ``zero1_dp``; scalar fields replicated."""

    def spec(path, leaf):
        return NamedSharding(mesh, p3_param_spec(_path_keys(path), leaf.ndim))

    def z1_spec(path, leaf):
        keys = _path_keys(path)
        return NamedSharding(
            mesh,
            p3_zero1_moment_spec(keys, leaf.shape, mesh.shape[DATA_AXIS]),
        )

    from distributed_machine_learning_tpu.train.optimizers import (
        moment_layout as _moment_layout,
    )

    param_shardings = jax.tree_util.tree_map_with_path(spec, state.params)
    moment_base = (
        jax.tree_util.tree_map_with_path(z1_spec, state.params)
        if zero1_dp else param_shardings
    )
    replicated = NamedSharding(mesh, P())
    return TrainState(
        params=param_shardings,
        momentum=_moment_layout(moment_base, state.params, state.momentum),
        batch_stats=jax.tree_util.tree_map(lambda _: replicated, state.batch_stats),
        step=replicated,
        rng=replicated,
        config=state.config,
    )


def shard_3d_state(
    state: TrainState, mesh: Mesh, zero1_dp: bool = False
) -> TrainState:
    """Place a pipeline-layout TrainState (``init_pipeline_state``) into
    the 3-D layout.  ``zero1_dp=True`` additionally shards the optimizer
    moments 1/dp over the data axis (pass the same flag to
    ``make_3d_lm_train_step``)."""
    from distributed_machine_learning_tpu.telemetry import startup

    with startup.place_state(state, mesh):
        return jax.tree_util.tree_map(
            jax.device_put, state, _state_shardings_3d(state, mesh, zero1_dp)
        )


def shard_3d_batch(mesh: Mesh, tokens_mb, targets_mb):
    """[M, mb, L] microbatch stacks with the batch dim sharded over the
    data axis (microbatch and sequence dims stay whole)."""
    import jax.numpy as jnp

    dp = mesh.shape[DATA_AXIS]
    mb = np.shape(tokens_mb)[1]
    if mb % dp:
        raise ValueError(
            f"microbatch size {mb} must be divisible by the {dp}-device "
            f"data axis (global batch = microbatches × mb; pick a batch "
            "divisible by microbatches × dp)"
        )
    sharding = NamedSharding(mesh, P(None, DATA_AXIS, None))
    return (
        jax.device_put(jnp.asarray(tokens_mb), sharding),
        jax.device_put(jnp.asarray(targets_mb), sharding),
    )


def make_3d_lm_train_step(
    model: TransformerLM, mesh: Mesh, num_microbatches: int,
    zero1_dp: bool = False,
):
    """Build ``step(state, tokens_mb, targets_mb) -> (state, loss)``.

    ``state`` from ``init_pipeline_state`` + ``shard_3d_state``; inputs
    from ``microbatch`` + ``shard_3d_batch``.  Requires ``n_layers``
    divisible by the pipe-axis size and ``n_heads`` by the model-axis
    size.  Reuses the pipeline step implementation unchanged — only the
    shard_map becomes partial-manual and the jit shardings add the
    batch/model dimensions.

    ``zero1_dp=True`` (ZeRO-1 × 3-D, the 4th axis): the optimizer
    moments live dp-sharded (``p3_zero1_moment_spec``; state placed
    with the same flag).  The MANUAL pipe region is untouched — the
    extra sharding enters purely through the jit in/out_shardings, so
    GSPMD partitions the elementwise update to the moment shards and
    inserts the dp all-gather where the updated params go back to
    replicated; the update stays elementwise-exact, so the trajectory
    equals plain 3-D (tested)."""
    if model.attn_impl in ("flash", "auto"):
        if model.flash_mesh is not None:
            raise ValueError(
                "make_3d_lm_train_step configures the model's flash "
                "shard_map wrap itself (it must match this step's mesh "
                "and axes); pass a model with flash_mesh unset"
            )
        # Flash inside the 3-D step: the outer shard_map is manual over
        # PIPE only, so the model's wrap manualizes the REMAINING
        # (batch, model) axes — a nested partial-manual shard_map whose
        # union covers the whole mesh, leaving the Mosaic custom call
        # fully local (batch sharded over dp, heads over tp, and the
        # pipe axis already manual in the enclosing region).
        model = model.clone(
            flash_mesh=mesh,
            flash_batch_axis=DATA_AXIS,
            flash_head_axis=MODEL_AXIS,
            flash_manual_axes=(DATA_AXIS, MODEL_AXIS),
        )
    elif model.attn_impl != "dense":
        raise ValueError(
            "3-D step supports attn_impl dense/flash/auto (sequence-"
            "sharded impls have no axis here)"
        )
    missing = [a for a in MESH_AXES if a not in mesh.axis_names]
    if missing:
        raise ValueError(f"3-D mesh is missing axes {missing}: {mesh.axis_names}")
    pp = mesh.shape[PIPE_AXIS]
    tp = mesh.shape[MODEL_AXIS]
    if model.n_layers % pp:
        raise ValueError(
            f"n_layers={model.n_layers} must divide into {pp} pipeline stages"
        )
    if model.n_heads % tp:
        raise ValueError(
            f"n_heads={model.n_heads} must be divisible by the model-axis "
            f"size {tp}"
        )
    if num_microbatches < 1:
        raise ValueError("num_microbatches must be >= 1")

    grad_constraint = None
    if zero1_dp:
        dp = mesh.shape[DATA_AXIS]

        def grad_constraint(grads):
            # Two sharding-annotated dependencies between backward and
            # update (replacing the old single PARAM-spec barrier whose
            # dp-replicated pin was the END of layout propagation — the
            # update's dp-sharded reshard was left implicit, wherever
            # GSPMD happened to put it):
            #
            # 1. pin the backward's output to the param sharding (pipe
            #    is manual inside the region — dropped from the spec),
            #    so the dp-sharded moment layout cannot walk up into
            #    the stacked-layer backward scatter (the historical XLA
            #    SPMD-partitioner CHECK; regression-covered at the
            #    microbatch-rows > 1 shape);
            # 2. immediately annotate the grads with their MOMENT's
            #    dp-sharded layout (``p3_zero1_moment_spec``), making
            #    the shard transition ONE explicit planned reshard per
            #    leaf through which GSPMD propagates into the update —
            #    the elementwise update then runs on dp shards end to
            #    end and the partitioner inserts the dp all-gather
            #    exactly where updated params return to replicated
            #    (arxiv 2004.13336's shard-the-update placement).
            def param_spec(path, leaf):
                full = tuple(p3_param_spec(_path_keys(path), leaf.ndim))
                axes = [None if a == PIPE_AXIS else a for a in full]
                return P(*(axes + [None] * (leaf.ndim - len(axes))))

            def moment_spec(path, leaf):
                return p3_zero1_grad_spec(
                    _path_keys(path), leaf.shape, dp
                )

            grads = jax.lax.with_sharding_constraint(
                grads, jax.tree_util.tree_map_with_path(param_spec, grads)
            )
            return jax.lax.with_sharding_constraint(
                grads, jax.tree_util.tree_map_with_path(moment_spec, grads)
            )

    impl = partial(_pp_step_impl, model, pipe_axis=PIPE_AXIS, num_stages=pp,
                   grad_constraint=grad_constraint)
    batch_sharding = NamedSharding(mesh, P(None, DATA_AXIS, None))
    jitted: dict = {}

    def step(state: TrainState, tokens_mb, targets_mb):
        if tokens_mb.shape[0] != num_microbatches:
            raise ValueError(
                f"expected {num_microbatches} microbatches, got input shaped "
                f"{tokens_mb.shape}"
            )
        key = jax.tree_util.tree_structure(state)
        fn = jitted.get(key)
        if fn is None:
            # in_specs constrain the MANUAL axis only (blocks stacked dim
            # over pipe — pipeline.py's specs, reused); batch/model
            # shardings enter through in_shardings and propagate via GSPMD.
            pipe_spec = _state_specs(PIPE_AXIS, state.params,
                                     state.momentum)
            pipe_spec = pipe_spec.replace(config=state.config)
            shardings = _state_shardings_3d(state, mesh, zero1_dp)
            fn = jitted[key] = jax.jit(
                _shard_map(
                    impl,
                    mesh=mesh,
                    in_specs=(pipe_spec, P(), P()),
                    out_specs=(pipe_spec, P()),
                    manual_axes={PIPE_AXIS},
                ),
                in_shardings=(shardings, batch_sharding, batch_sharding),
                out_shardings=(shardings, NamedSharding(mesh, P())),
                donate_argnums=(0,),
            )
        return fn(state, tokens_mb, targets_mb)

    return step
