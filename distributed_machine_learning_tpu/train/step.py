"""Jitted train/eval steps over a device mesh.

Replaces the reference's training driver + torch autograd + gloo stack
(``train_model`` at ``part1/main.py:19-58`` and clones): one pure function
per step — forward, loss, ``jax.grad``, the pluggable gradient-sync
strategy, and the SGD update — compiled by XLA as a single program.
Distribution is SPMD: the step is ``shard_map``-ed over the mesh's
``"batch"`` axis with the batch sharded and the state replicated, so the
sync strategy's collectives (psum / all-gather / ppermute ring) lower to
ICI ops scheduled and overlapped by the compiler — the work DDP's C++
reducer and autograd hooks do by hand in the reference (part3).

Augmentation runs inside the step (see ``data/augment.py``), keyed per
step and per mesh position, so each shard draws independent crops/flips
the way each reference node draws from its own torch RNG.
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributed_machine_learning_tpu.data.augment import augment_batch, normalize
from distributed_machine_learning_tpu.parallel.strategies import NoSync, SyncStrategy
from distributed_machine_learning_tpu.runtime.mesh import (
    BATCH_AXIS,
    shard_map_no_check as _shard_map,
)
from distributed_machine_learning_tpu.train.common import make_loss_fn, step_rng
from distributed_machine_learning_tpu.train.losses import cross_entropy_loss, count_correct
from distributed_machine_learning_tpu.train.state import TrainState


def _train_step_impl(
    model,
    strategy: SyncStrategy,
    state: TrainState,
    images_u8,
    labels,
    sync_state=None,
    *,
    axis_name: str | None,
    axis_size: int,
    augment: bool,
    sync_bn: bool,
    schedule=None,
    clip_norm: float | None = None,
    accum_steps: int = 1,
    update_fn=None,
    local_loss: bool = False,
    guard: bool = False,
):
    # Unsynced-BN quirk mode (reference part3: per-node running stats,
    # part3/model.py:24 + group25.pdf p.3-4): the replicated state holds
    # a [world, *S]-stacked stats tree; each device reads/writes its own
    # row, and an all_gather of the (tiny) stats restores replication.
    unsync_bn = axis_name is not None and not sync_bn
    stats_in = state.batch_stats
    if unsync_bn and stats_in:
        dev_idx = lax.axis_index(axis_name)
        stats_in = jax.tree_util.tree_map(lambda s: s[dev_idx], stats_in)
    if update_fn is None:
        # Dispatch on the state's (static) optimizer config at trace time.
        from distributed_machine_learning_tpu.train.optimizers import (
            update_fn_for_config,
        )

        update_fn = update_fn_for_config(state.config)
    rng = step_rng(state.rng, state.step, axis_name)
    if accum_steps == 1:
        x = augment_batch(rng, images_u8) if augment else normalize(images_u8)
        loss_fn = make_loss_fn(model, stats_in, x, labels, train=True)
        (loss, (_, new_stats)), grads = jax.value_and_grad(
            loss_fn, has_aux=True
        )(state.params)
    else:
        # Gradient accumulation: split the (local) batch into microbatches
        # and scan, accumulating gradients — the program stays one
        # microbatch big, peak activation memory drops accum_steps-fold,
        # and with equal microbatches mean-of-means == the full-batch mean
        # so the update is identical (BN-free; BN running stats update
        # per microbatch, sequentially, like small-batch torch training).
        B = images_u8.shape[0]
        if B % accum_steps:
            raise ValueError(
                f"per-device batch {B} not divisible by accum_steps="
                f"{accum_steps}"
            )
        micro_imgs = images_u8.reshape(
            accum_steps, B // accum_steps, *images_u8.shape[1:]
        )
        micro_labels = labels.reshape(accum_steps, B // accum_steps)
        micro_rngs = jax.random.split(rng, accum_steps)

        def body(carry, xs):
            stats, grads_acc, loss_acc = carry
            mi, ml, r = xs
            x = augment_batch(r, mi) if augment else normalize(mi)
            loss_fn = make_loss_fn(model, stats, x, ml, train=True)
            (loss, (_, new_stats)), g = jax.value_and_grad(
                loss_fn, has_aux=True
            )(state.params)
            grads_acc = jax.tree_util.tree_map(jnp.add, grads_acc, g)
            return (new_stats if new_stats else stats, grads_acc,
                    loss_acc + loss), None

        zeros = jax.tree_util.tree_map(jnp.zeros_like, state.params)
        (new_stats, grads, loss), _ = lax.scan(
            body,
            (stats_in, zeros, jnp.zeros((), jnp.float32)),
            (micro_imgs, micro_labels, micro_rngs),
        )
        grads = jax.tree_util.tree_map(lambda g: g / accum_steps, grads)
        loss = loss / accum_steps

    new_sync_state = None
    if axis_name is not None:
        if sync_state is not None:
            # Stateful strategy (error-feedback compressed ring): the
            # state rides OUTSIDE TrainState, sharded P(batch) on a
            # leading [world, ...] axis so each device carries its OWN
            # residual — error feedback is rank-local; replicating it
            # would both waste world× memory and be semantically wrong.
            local = jax.tree_util.tree_map(lambda r: r[0], sync_state)
            grads, new_local = strategy.apply(
                grads, local, axis_name, axis_size
            )
            new_sync_state = jax.tree_util.tree_map(
                lambda r: r[None], new_local
            )
        else:
            grads = strategy(grads, axis_name, axis_size)
        if new_stats and sync_bn:
            # part3's reference leaves BN running stats unsynced per node (a
            # documented quirk — SURVEY.md §7.3); the TPU-idiomatic default
            # axis-means them so replicated state stays bit-identical across
            # devices (the framework's cross-replica invariant).
            new_stats = jax.tree_util.tree_map(
                lambda s: lax.pmean(s, axis_name), new_stats
            )
        elif new_stats and unsync_bn:
            # Re-stack every device's locally-updated stats so the
            # replicated out_spec stays truthful: all devices hold the
            # identical [world, *S] array whose row d is device d's stats.
            new_stats = jax.tree_util.tree_map(
                lambda s: lax.all_gather(s, axis_name), new_stats
            )

    if clip_norm is not None:
        # After sync: clip the global gradient (DDP-semantics order).
        from distributed_machine_learning_tpu.train.schedule import (
            clip_by_global_norm,
        )

        grads = clip_by_global_norm(grads, clip_norm)
    new_params, new_momentum = update_fn(
        state.params,
        state.momentum,
        grads,
        state.config,
        lr=None if schedule is None else schedule(state.step),
        step=state.step,
    )
    new_state = state.replace(
        params=new_params,
        momentum=new_momentum,
        batch_stats=new_stats,
        step=state.step + 1,
    )
    if guard:
        # Non-finite-gradient guard: a NaN/Inf anywhere in the (synced)
        # gradients skips the whole update — params, momentum, BN stats,
        # and the step counter all stay exactly as they were, so one bad
        # batch costs one step, not the run.  Checked on the post-sync
        # gradients (identical on every device), so the skip decision is
        # replicated and cross-device state stays bit-identical.
        from distributed_machine_learning_tpu.train.common import (
            guard_update,
            tree_all_finite,
        )

        ok = tree_all_finite(grads)
        new_state = guard_update(ok, new_state, state)
        if new_sync_state is not None:
            # A skipped update must also freeze the residual: feeding a
            # non-finite error back into the next step would poison it.
            new_sync_state = jax.tree_util.tree_map(
                lambda a, b: jnp.where(ok, a, b), new_sync_state, sync_state
            )
    if axis_name is not None:
        if local_loss:
            # Reference print-surface parity mode: each rank prints its
            # OWN shard's loss (part2/2a/main.py:58-61).  Out spec is
            # P(axis), so the step returns the [world] per-device vector.
            loss = loss[None]
        else:
            # Default: the global mean loss (SPMD has one print stream,
            # so surface the mean).
            loss = lax.pmean(loss, axis_name)
    if sync_state is not None:
        return new_state, loss, new_sync_state
    return new_state, loss


def make_train_step(
    model,
    strategy: SyncStrategy | None = None,
    mesh: Mesh | None = None,
    axis_name: str = BATCH_AXIS,
    augment: bool = True,
    sync_bn: bool = True,
    schedule=None,
    clip_norm: float | None = None,
    accum_steps: int = 1,
    jit: bool = True,
    optimizer: str | None = None,
    local_loss: bool = False,
    guard_nonfinite: bool = False,
):
    """Build the jitted train step.

    Without a mesh: the part1 path — plain ``jit``, no collectives.
    With a mesh: ``shard_map`` over ``axis_name``; batch sharded on axis 0,
    state replicated; `strategy` decides how gradients synchronize.

    ``schedule``: optional ``step -> lr`` fn (``train/schedule.py``)
    overriding the static config rate; ``clip_norm``: optional global-norm
    gradient clip, applied after sync; ``accum_steps``: split each batch
    into this many sequential microbatches, accumulating gradients
    (identical update for BN-free models, accum-fold lower activation
    memory).

    ``sync_bn``: True (default) axis-means BN running stats so replicated
    state stays bit-identical; False reproduces the reference part3's
    per-node unsynced stats (part3/model.py:24) — pass state through
    ``broadcast_bn_stats(state, mesh.shape[axis_name])`` first, and eval
    with ``make_eval_step(..., sync_bn=False)``.

    ``local_loss`` (mesh only): return the [world] vector of per-device
    losses instead of the pmean — each reference rank prints its own
    local loss (part2/2a/main.py:58-61); this is that print surface.

    ``optimizer``: None (default) dispatches on the TrainState's config
    type — SGDConfig → sgd (reference parity), LARSConfig → lars,
    AdamWConfig → adamw; an explicit registry name pins the update fn.

    ``jit=False`` returns the un-jitted step function (no donation) — for
    callers that embed the step in a larger compiled program, e.g. a
    ``lax.scan``-ed epoch.

    Stateful strategies (``strategy.stateful``, e.g. the error-feedback
    compressed ring — ``RingAllReduce(compress="int8")``): the compiled
    step threads the strategy's per-device state (the EF residual)
    through the program — state in, state out, donated, sharded
    P(batch).  With ``jit=True`` the returned callable keeps the
    ``step(state, x, y) -> (state, loss)`` signature and manages the
    residual buffers itself (``step.sync_state()`` /
    ``step.set_sync_state(res)`` / ``step.reset_sync_state()`` /
    ``step.fresh_sync_state(params)``; ``step.inner`` is the raw 4-ary
    jitted fn for AOT lowering).  The wrapper is world-change-safe: a
    residual stacked for a different world (an elastic shrink/grow
    carried it across a gang reshape) is rebuilt as zeros at this
    mesh's world — logged/counted as ``ring_residual_reset`` — never a
    shape crash inside the compiled program.  With
    ``jit=False`` the raw 4-ary fn is returned and the caller threads
    the state.  Stateless strategies compile the exact program they
    always did — zero overhead.

    ``guard_nonfinite``: compile the non-finite-gradient guard into the
    step — an all-leaves ``isfinite`` reduction over the (synced)
    gradients; when any gradient blew up, the update is skipped wholesale
    (state unchanged, step NOT incremented) and the returned loss is the
    non-finite value so the host can count the event
    (``runtime/faults.FaultEvents.skipped_steps``).  Off by default:
    reference-parity runs must not mask numeric bugs.

    Returns ``step(state, images_u8, labels) -> (state, loss)``.
    """
    if accum_steps < 1:
        raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
    if local_loss and mesh is None:
        raise ValueError("local_loss requires a mesh (it is the per-device "
                         "loss vector; the part1 path has one device)")
    from distributed_machine_learning_tpu.train.optimizers import get_optimizer

    # optimizer=None → dispatch from the TrainState's config at trace time
    # (the natural path); an explicit name pins the update fn regardless.
    update_fn = None if optimizer is None else get_optimizer(optimizer)[2]
    strategy = strategy or NoSync()
    if mesh is not None and isinstance(strategy, NoSync):
        # Unsynced gradients under a replicated-state shard_map would let
        # params silently diverge per device (out_specs claims replication).
        # part1 semantics on a mesh is simply mesh=None.
        raise ValueError(
            "strategy 'none' (part1) cannot run on a mesh: gradients would "
            "not be synchronized and replicated state would diverge; use "
            "mesh=None, or pick all_reduce/gather_scatter/ring"
        )

    if mesh is None:
        impl = partial(
            _train_step_impl,
            model,
            strategy,
            axis_name=None,
            axis_size=1,
            augment=augment,
            sync_bn=sync_bn,
            schedule=schedule,
            clip_norm=clip_norm,
            accum_steps=accum_steps,
            update_fn=update_fn,
            guard=guard_nonfinite,
        )
        return jax.jit(impl, donate_argnums=(0,)) if jit else impl

    axis_size = mesh.shape[axis_name]
    # sync_bn=False is the reference part3 quirk mode: per-device BN
    # running stats (part3/model.py:24, <1% cross-node accuracy drift —
    # group25.pdf p.3-4).  State must carry [world, *S]-stacked stats —
    # build it with ``broadcast_bn_stats(state, world)``; each device
    # reads/writes its own row (see _train_step_impl).
    impl = partial(
        _train_step_impl,
        model,
        strategy,
        axis_name=axis_name,
        axis_size=axis_size,
        augment=augment,
        sync_bn=sync_bn,
        schedule=schedule,
        clip_norm=clip_norm,
        accum_steps=accum_steps,
        update_fn=update_fn,
        local_loss=local_loss,
        guard=guard_nonfinite,
    )
    state_spec = P()  # replicated
    batch_spec = P(axis_name)  # sharded along the data axis
    loss_spec = P(axis_name) if local_loss else P()
    if not getattr(strategy, "stateful", False):
        sharded = _shard_map(
            impl,
            mesh=mesh,
            in_specs=(state_spec, batch_spec, batch_spec),
            out_specs=(state_spec, loss_spec),
        )
        return jax.jit(sharded, donate_argnums=(0,)) if jit else sharded

    # Stateful strategy (error-feedback compressed ring): the compiled
    # step threads the strategy's per-device state through the program —
    # state in, state out, DONATED, sharded P(batch) on a leading
    # [world, ...] axis (each device owns its residual row).  The
    # stateless path above compiles the exact program it always did:
    # the uncompressed ring pays zero overhead for this feature.
    res_spec = P(axis_name)
    sharded = _shard_map(
        impl,
        mesh=mesh,
        in_specs=(state_spec, batch_spec, batch_spec, res_spec),
        out_specs=(state_spec, loss_spec, res_spec),
    )
    if not jit:
        # Un-jitted stateful form: the caller threads the state
        # explicitly — step(state, x, y, sync_state) →
        # (state, loss, sync_state) — e.g. a scanned epoch carrying it
        # alongside TrainState.
        return sharded
    inner = jax.jit(sharded, donate_argnums=(0, 3))

    def fresh_sync_state(params):
        """[world, *leaf] stacked zeros, sharded P(batch) over the mesh
        — each device's row is its own (initially empty) residual.
        Shapes come from an abstract eval of the strategy's init (no
        throwaway full-size zeros tree is ever materialized)."""
        res0 = jax.eval_shape(strategy.init_state, params)
        stacked = jax.tree_util.tree_map(
            lambda r: jnp.zeros((axis_size,) + r.shape, r.dtype), res0
        )
        return jax.device_put(
            stacked, NamedSharding(mesh, P(axis_name))
        )

    holder = {"res": None}

    def _residual_world(res) -> int | None:
        """The world size a stacked residual was built for — its leading
        axis (every leaf is ``[world, *leaf]``)."""
        leaves = jax.tree_util.tree_leaves(res)
        return int(leaves[0].shape[0]) if leaves else None

    def _check_world(res):
        """Accept ``res`` only if its stacked world matches THIS step's
        mesh; a mismatch (an elastic shrink/grow carried the residual
        across a world change) resets to fresh zeros instead of shape-
        crashing inside the compiled program, and says so: a silent
        reset would weaken the EF-exactness story, a crash would turn a
        planned reshape into a failure.  Returns the residual to use."""
        got = _residual_world(res)
        if got is None or got == axis_size:
            return res
        from distributed_machine_learning_tpu.telemetry import (
            get_telemetry,
        )

        tel = get_telemetry()
        if tel is not None:
            tel.registry.counter("ring_residual_reset").inc()
            tel.tracer.instant("ring_residual_reset", from_world=got,
                               to_world=axis_size)
        print(
            f"[ring] ring_residual_reset: error-feedback residual was "
            f"stacked for world {got}, mesh is world {axis_size} — "
            "rebuilding at the new world with zeros (one step of EF "
            "warmup)", flush=True,
        )
        return None

    def step(state, images_u8, labels):
        # Caller-facing signature unchanged (state, x, y) → (state,
        # loss): the wrapper owns the residual buffers, lazily zeroed
        # from the first state's param shapes and re-donated each call.
        if holder["res"] is not None:
            holder["res"] = _check_world(holder["res"])
        if holder["res"] is None:
            holder["res"] = fresh_sync_state(state.params)
        new_state, loss, holder["res"] = inner(
            state, images_u8, labels, holder["res"]
        )
        return new_state, loss

    def sync_state():
        """The CURRENT residual pytree — the live buffers the next
        ``step()`` call donates back into the program, so a kept
        reference dies with that call (Array deleted).  Copy before
        holding across steps: ``jax.tree_util.tree_map(jnp.copy, ...)``."""
        return holder["res"]

    def set_sync_state(res):
        """Install a carried residual — the elastic-rebind hook: a
        caller that preserved the residual across a step rebuild (same
        params, possibly a DIFFERENT world after a gang reshape) hands
        it to the new step here.  A world mismatch resets to fresh
        zeros at the new world (logged as ``ring_residual_reset``)
        rather than shape-crashing; a matching one is re-placed onto
        this step's mesh sharding."""
        res = _check_world(res)
        if res is not None:
            res = jax.device_put(res, NamedSharding(mesh, P(axis_name)))
        holder["res"] = res

    step.inner = inner  # AOT/lowering access: inner.lower(state, x, y, res)
    step.fresh_sync_state = fresh_sync_state
    step.sync_state = sync_state
    step.set_sync_state = set_sync_state
    step.reset_sync_state = lambda: holder.__setitem__("res", None)
    return step


def broadcast_bn_stats(state: TrainState, world: int) -> TrainState:
    """Stack ``world`` copies of the BN running stats ([world, *S] per
    leaf) — the state layout the unsynced-BN quirk mode
    (``make_train_step(..., sync_bn=False)``) reads and writes.  The
    stacked tree stays replicated across devices; row d is device d's
    private running stats, the TPU encoding of the reference's per-node
    BN state (part3/model.py:24)."""
    if not state.batch_stats:
        return state
    stacked = jax.tree_util.tree_map(
        lambda s: jnp.tile(s[None], (world,) + (1,) * s.ndim),
        state.batch_stats,
    )
    return state.replace(batch_stats=stacked)


def make_eval_step(model, mesh: Mesh | None = None, axis_name: str = BATCH_AXIS,
                   sync_bn: bool = True):
    """Jitted eval step: (params, batch_stats, images_u8, labels) →
    (batch mean loss, correct count) — ``test_model`` parity
    (``part1/main.py:62-77``): normalize only (no augmentation), BN in
    inference mode, loss averaged per batch, top-1 correct counts.

    With a mesh, evaluation is *sharded*: each device scores its slice of
    the batch and the per-batch mean loss / correct count come back via
    ``pmean``/``psum`` — an N-fold speedup over the reference's
    every-rank-evaluates-everything protocol (SURVEY.md §3.5) with
    identical results (equal shards ⇒ pmean of shard means == the global
    batch mean).

    ``sync_bn=False`` (quirk-mode eval, mesh only): ``batch_stats`` is
    the [world, *S]-stacked tree from the unsynced-BN train step; each
    device scores its shard with its own stats row, so the reported
    numbers mix per-device models exactly the way the reference's
    per-node evals do.
    """

    def eval_impl(params, batch_stats, images_u8, labels, *, axis=None):
        x = normalize(images_u8)
        if batch_stats and axis is not None and not sync_bn:
            batch_stats = jax.tree_util.tree_map(
                lambda s: s[lax.axis_index(axis)], batch_stats
            )
        variables: dict[str, Any] = {"params": params}
        if batch_stats:
            variables["batch_stats"] = batch_stats
        logits = model.apply(variables, x, train=False)
        loss = cross_entropy_loss(logits, labels)
        correct = count_correct(logits, labels)
        if axis is not None:
            loss = lax.pmean(loss, axis)
            correct = lax.psum(correct, axis)
        return loss, correct

    if mesh is None:
        return jax.jit(eval_impl)

    sharded = _shard_map(
        partial(eval_impl, axis=axis_name),
        mesh=mesh,
        in_specs=(P(), P(), P(axis_name), P(axis_name)),
        out_specs=(P(), P()),
    )
    return jax.jit(sharded)


def shard_batch(mesh: Mesh, images_u8, labels, axis_name: str = BATCH_AXIS):
    """Place a host batch onto the mesh, sharded along the batch axis —
    straight from host memory to each device's shard (no staging copy of
    the whole batch on the default device)."""
    sharding = NamedSharding(mesh, P(axis_name))
    return (
        jax.device_put(images_u8, sharding),
        jax.device_put(labels, sharding),
    )
