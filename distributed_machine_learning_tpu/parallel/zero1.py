"""ZeRO-1: optimizer-state sharding under replicated parameters.

The middle point of the ZeRO family this framework offers (SURVEY.md
§2.3 records all of it as absent in the reference):

- replicated DP (``parallel/strategies.py``) — params + momentum on
  every device;
- **ZeRO-1 (this module)** — params replicated, momentum sharded 1/N;
- ZeRO-3/FSDP (``parallel/fsdp.py``) — params *and* momentum sharded.

The step:

  1. forward/backward on the replicated params (local gradients);
  2. ``lax.psum_scatter`` the flattened gradient — each device receives
     only the mean-reduced slice it owns (half the ring);
  3. SGD/momentum update on that slice against its momentum shard;
  4. ``lax.all_gather`` the updated parameter slices back to the full
     replicated vector (the other half of the ring).

Per-step traffic is exactly one all-reduce's worth (reduce-scatter +
all-gather), the same bytes replicated DP pays — ZeRO-1 costs no extra
bandwidth and saves (N−1)/N of the momentum memory, the reason it is
the default first rung of optimizer sharding.  Flat-vector layout and
padding follow ``parallel/fsdp.py``.

Step (4) has two builds (see :func:`make_zero1_train_step`): the sync
baseline keeps the gather inside the program (on the critical path,
feeding ROOT — the arxiv 2004.13336 anti-pattern, dmlcheck DML102),
and ``overlap=True`` moves it to a separately-dispatched bucketed
ppermute ring (``parallel/overlap.py``) that runs behind the next
step's data wait — bit-identical trajectory, gather off the critical
path.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from flax import struct
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributed_machine_learning_tpu.data.augment import augment_batch, normalize
from distributed_machine_learning_tpu.parallel.fsdp import (
    _padded_len,
    flat_mean_grad_shard,
    flatten_padded,
    fsdp_memory_footprint,
)
from distributed_machine_learning_tpu.runtime.mesh import (
    BATCH_AXIS,
    shard_map_no_check as _shard_map,
)
from distributed_machine_learning_tpu.train.common import step_rng
from distributed_machine_learning_tpu.train.lars import LARSConfig
from distributed_machine_learning_tpu.train.optimizers import update_fn_for_config
from distributed_machine_learning_tpu.train.sgd import SGDConfig
from distributed_machine_learning_tpu.train.state import TrainState


@struct.dataclass
class Zero1State:
    """Replicated flat params + 1/N momentum shards per device."""

    param_flat: jax.Array  # [padded_len], replicated
    # [padded_len] global, sharded over the batch axis; a {"mu","nu"}
    # dict of such vectors for AdamW.
    momentum_shards: jax.Array | dict
    batch_stats: dict
    step: jax.Array
    rng: jax.Array
    config: SGDConfig = struct.field(pytree_node=False)


def shard_zero1_state(state: TrainState, mesh: Mesh, axis_name: str = BATCH_AXIS):
    """Flatten a replicated TrainState into the ZeRO-1 layout.

    Returns ``(zero1_state, unravel, n_elems)`` — ``unravel`` maps the
    unpadded flat vector back to the params pytree.
    """
    if isinstance(state.config, LARSConfig):
        # Elementwise updates (SGD, AdamW) are exact on any slice of the
        # flat vector; LARS's per-leaf norms are not.
        raise ValueError(
            "ZeRO-1 cannot shard LARS (per-layer norms are not "
            "sliceable); use sgd or adamw"
        )
    flat, mom_flat, unravel, n_elems = flatten_padded(
        state, mesh.shape[axis_name]
    )
    z1 = Zero1State(
        param_flat=jax.device_put(flat, NamedSharding(mesh, P())),
        momentum_shards=jax.tree_util.tree_map(
            lambda a: jax.device_put(a, NamedSharding(mesh, P(axis_name))),
            mom_flat,
        ),
        batch_stats=jax.device_put(
            state.batch_stats, NamedSharding(mesh, P())
        ),
        step=jax.device_put(state.step, NamedSharding(mesh, P())),
        rng=jax.device_put(state.rng, NamedSharding(mesh, P())),
        config=state.config,
    )
    return z1, unravel, n_elems


def zero1_params(state: Zero1State, unravel, n_elems: int):
    """The params pytree (for eval/checkpoint) — params are replicated,
    so this is just an unravel, no collective."""
    return unravel(jnp.asarray(state.param_flat)[:n_elems])


def make_zero1_train_step(
    model,
    mesh: Mesh,
    unravel,
    n_elems: int,
    axis_name: str = BATCH_AXIS,
    augment: bool = True,
    overlap: bool = False,
):
    """Build the jitted ZeRO-1 train step (MEAN gradient semantics).

    Returns ``step(zero1_state, images_u8, labels) -> (state, loss)``
    with the batch sharded along the data axis.

    ``overlap=False`` (the sync baseline): one program whose final op
    is the parameter all-gather — the gather feeds ROOT and nothing can
    be scheduled under it, exactly the critical-path anti-pattern
    "Automatic Cross-Replica Sharding of Weight Update in Data-Parallel
    Training" (arxiv 2004.13336) eliminates (dmlcheck DML102 flags this
    build as an error).

    ``overlap=True`` (the 2004.13336 recipe): the step is split into an
    **update phase** — forward/backward, gradient reduce-scatter, and
    the shard-local optimizer step, whose program ends at the updated
    SHARD (no gather anywhere; the host's loss block returns as soon as
    the update lands) — and a **consume phase**: the gather of the
    updated shards is dispatched as a separate, immediately-issued
    program (a chunked :func:`~distributed_machine_learning_tpu.ops.ring.ring_all_gather_flat`
    ppermute chain, each hop an async window the scheduler fills with
    the per-chunk assembly), so it executes behind the host's
    bookkeeping and next dispatch (``train_epoch`` holds one batch ahead:
    the next batch was fetched and placed under the update program) and
    is consumed by the next step's forward.  Dispatch is async, so the returned state's ``param_flat``
    is simply the in-flight gather result — checkpoint/eval callers
    block on it transparently and see the identical replicated vector.
    The two builds are BIT-IDENTICAL in trajectory (the gather is pure
    data movement; the update math is shared) — tested.

    When telemetry is installed the wrapper records a ``param_gather``
    span from gather dispatch to observed readiness (closed at the next
    step's consume), and exposes ``step.pop_gather_seconds()`` so the
    train loop can add a ``param_gather_s`` column — the span that
    should outlast ``device_block`` on the trace timeline (the loss is
    back before the gather is).  ``step.update_for(cfg)`` /
    ``step.gather_inner`` expose the two jitted programs for AOT
    lowering and the HLO overlap audit (``analysis/overlap_audit.py``).
    """
    n = mesh.shape[axis_name]

    def sharded_for(cfg: SGDConfig, gather: bool):
        def impl(param_flat, momentum_shard, batch_stats, step_ctr, rng,
                 images_u8, labels):
            shard_len = param_flat.shape[0] // n
            rank = lax.axis_index(axis_name)
            params = unravel(param_flat[:n_elems])

            r = step_rng(rng, step_ctr, axis_name)
            x = augment_batch(r, images_u8) if augment else normalize(images_u8)

            # (2) forward/backward + reduce-scatter of the MEAN gradient —
            # shared with ZeRO-3 (parallel/fsdp.py) so the schemes cannot
            # drift apart.
            loss, new_stats, grad_shard = flat_mean_grad_shard(
                model, params, batch_stats, x, labels, axis_name, n,
                param_flat.shape[0],
            )

            # (3) Update the owned param slice against the momentum shard.
            p_shard = lax.dynamic_slice(
                param_flat, (rank * shard_len,), (shard_len,)
            )
            new_p_shard, new_m_shard = update_fn_for_config(cfg)(
                p_shard, momentum_shard, grad_shard, cfg, step=step_ctr
            )

            if gather:
                # (4, sync build) All-gather the updated slices into the
                # full vector — ON the critical path, feeding ROOT.
                new_flat = lax.all_gather(new_p_shard, axis_name, tiled=True)
                return new_flat, new_m_shard, new_stats, loss
            # (4, overlap build) stop at the shard; the consume-phase
            # program gathers it behind the next step's data wait.
            return new_p_shard, new_m_shard, new_stats, loss

        shard = P(axis_name)
        return _shard_map(
            impl,
            mesh=mesh,
            in_specs=(P(), shard, P(), P(), P(), shard, shard),
            out_specs=((P() if gather else shard), shard, P(), P()),
        )

    if not overlap:
        def step(state: Zero1State, images_u8, labels):
            new_flat, new_mom, new_stats, loss = sharded_for(
                state.config, gather=True
            )(
                state.param_flat,
                state.momentum_shards,
                state.batch_stats,
                state.step,
                state.rng,
                images_u8,
                labels,
            )
            new_state = state.replace(
                param_flat=new_flat,
                momentum_shards=new_mom,
                batch_stats=new_stats,
                step=state.step + 1,
            )
            return new_state, loss

        return jax.jit(step, donate_argnums=(0,))

    from distributed_machine_learning_tpu.parallel.overlap import (
        GatherSpanClock,
        make_ring_gather,
    )

    # The consume-phase program: the freshly updated shards are donated
    # into the gather (nothing else reads them); the replicated full
    # vector is the survivor the next step reads.
    gather_inner = make_ring_gather(mesh, axis_name, n, donate=True)

    jitted: dict = {}

    def update_for(cfg):
        # Donate param_flat (arg 0 — it cannot alias the SHARDED
        # shard-output, but freeing it mid-program caps peak HBM at
        # the sync build's level, same reasoning as the fsdp prefetch
        # wrapper's full vector) plus the momentum and BN-stats
        # buffers (1, 2), which alias their updated twins.  NOT
        # donated: step (3) is read again by the wrapper's
        # ``state.step + 1`` and rng (4) is carried unchanged into the
        # next step — donating either would hand the wrapper a dead
        # buffer on backends that take donation.
        fn = jitted.get(cfg)
        if fn is None:
            fn = jitted[cfg] = jax.jit(
                sharded_for(cfg, gather=False), donate_argnums=(0, 1, 2)
            )
        return fn

    clock = GatherSpanClock()

    def step(state: Zero1State, images_u8, labels):
        clock.close()
        new_shard, new_mom, new_stats, loss = update_for(state.config)(
            state.param_flat,
            state.momentum_shards,
            state.batch_stats,
            state.step,
            state.rng,
            images_u8,
            labels,
        )
        new_flat = gather_inner(new_shard)
        clock.open(new_flat)
        new_state = state.replace(
            param_flat=new_flat,
            momentum_shards=new_mom,
            batch_stats=new_stats,
            step=state.step + 1,
        )
        return new_state, loss

    step.overlap = True
    step.update_for = update_for
    step.gather_inner = gather_inner
    step.pop_gather_seconds = clock.pop
    return step


def zero1_memory_footprint(n_params: int, n_dev: int, bytes_per_elem: int = 4):
    """Per-device param+momentum bytes: replicated vs ZeRO-1 vs ZeRO-3.

    ZeRO-1 counts the *padded* replicated vector — what
    :func:`shard_zero1_state` actually materializes per device — plus the
    1/N momentum shard (also padded, matching the momentum term of
    ``fsdp_memory_footprint``).
    """
    fp = fsdp_memory_footprint(n_params, n_dev, bytes_per_elem)
    padded = _padded_len(n_params, n_dev)
    fp["zero1"] = (padded + padded // n_dev) * bytes_per_elem
    return fp
