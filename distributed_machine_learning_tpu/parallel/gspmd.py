"""Shared GSPMD machinery for the sharded-parameter strategies
(TP / EP / per-layer FSDP).

All three follow the same recipe — a ``spec_for(path, shape)`` rule
table mapped over the param tree (TP/EP rules key on the path, the
per-layer FSDP rule on the shape), a TrainState-shaped sharding pytree,
and a jit cache keyed by the state's tree structure (SGDConfig is
*static* pytree metadata, so differently configured states need
distinct jitted signatures).  This module is that recipe, written once.
"""

from __future__ import annotations

from typing import Callable

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributed_machine_learning_tpu.train.state import TrainState

SpecFor = Callable[[tuple[str, ...], tuple[int, ...]], P]


def param_specs(params, spec_for: SpecFor):
    """Map a (path, shape)→PartitionSpec rule over a param tree.
    TP/EP rules key on the path; the per-layer FSDP rule keys on the
    shape (which dim is divisible) — both get both."""

    def spec(path, leaf):
        keys = tuple(k.key if hasattr(k, "key") else str(k) for k in path)
        return spec_for(keys, tuple(leaf.shape))

    return jax.tree_util.tree_map_with_path(spec, params)


def state_shardings(state: TrainState, mesh: Mesh, spec_for: SpecFor) -> TrainState:
    """NamedSharding pytree for a TrainState: params and momentum follow
    the rule table, everything else replicates.

    The momentum slot is either params-shaped (SGD/LARS) or a dict of
    params-shaped trees (AdamW's ``{"mu","nu"}`` — train/adamw.py);
    each moment tree inherits its parameter's spec."""
    from distributed_machine_learning_tpu.train.optimizers import moment_layout

    specs = param_specs(state.params, spec_for)
    to_sharding = lambda s: NamedSharding(mesh, s)
    spec_shardings = jax.tree_util.tree_map(to_sharding, specs)
    mom_shardings = moment_layout(spec_shardings, state.params, state.momentum)
    return TrainState(
        params=spec_shardings,
        momentum=mom_shardings,
        batch_stats=jax.tree_util.tree_map(
            lambda _: to_sharding(P()), state.batch_stats
        ),
        step=to_sharding(P()),
        rng=to_sharding(P()),
        config=state.config,
    )


def shard_state(state: TrainState, mesh: Mesh, spec_for: SpecFor) -> TrainState:
    """Place a host/replicated TrainState into the rule table's layout."""
    from distributed_machine_learning_tpu.telemetry import startup

    with startup.place_state(state, mesh):
        return jax.tree_util.tree_map(
            jax.device_put, state, state_shardings(state, mesh, spec_for)
        )


def make_cached_sharded_step(impl, mesh: Mesh, spec_for: SpecFor, batch_sharding):
    """jit ``impl(state, tokens, targets)`` with shardings derived from the
    first call's actual state, cached per state tree structure."""
    jitted: dict = {}

    def build(state):
        shardings = state_shardings(state, mesh, spec_for)
        return jax.jit(
            impl,
            in_shardings=(shardings, batch_sharding, batch_sharding),
            out_shardings=(shardings, NamedSharding(mesh, P())),
            donate_argnums=(0,),
        )

    def step(state: TrainState, tokens, targets):
        key = jax.tree_util.tree_structure(state)
        fn = jitted.get(key)
        if fn is None:
            fn = jitted[key] = build(state)
        return fn(state, tokens, targets)

    # AOT access for the Layer-2 HLO audits and benches: lower without
    # executing (abstract ShapeDtypeStruct states work — the sharding
    # derivation only reads shapes).
    step.lower = lambda state, tokens, targets: build(state).lower(
        state, tokens, targets)
    return step
