"""ZeRO-3 / FSDP-style *sharded* data parallelism.

The reference's capability surface stops at replicated data parallelism
(SURVEY.md §2.3 — "ZeRO/FSDP sharding: absent"), whose memory cost is a
full copy of params + momentum on every worker (~38 MB × 2 for VGG-11,
``group25.pdf`` p.2).  This module goes beyond parity with the sharded
scheme DDP cannot express: every device owns a 1/N slice of the flattened
parameter and momentum vectors, and the train step

  1. **all-gathers** the parameter shards into the full vector
     (``lax.all_gather(tiled=True)`` — one bandwidth-optimal ICI
     collective, not a per-tensor broadcast),
  2. runs forward/backward on the full params,
  3. **reduce-scatters** the gradient so each device receives only the
     reduced slice it owns (``lax.psum_scatter(tiled=True)`` — half the
     ring all-reduce, the same trick phase 1 of ``ops/ring.py`` plays),
  4. applies the SGD/momentum update **on the local shard only**.

Per-device optimizer memory drops from 2·P to 2·P/N (the ZeRO-3
partitioning), and per-step traffic is the same 2·(N−1)/N·P bytes as the
ring all-reduce — FSDP costs no extra bandwidth, it just moves the
all-gather before the forward instead of after the backward.

Flat-vector sharding (rather than per-tensor) keeps every collective a
single static-shape op on one contiguous buffer — the layout XLA/ICI
likes — and sidesteps uneven-tensor bookkeeping: one pad to a multiple of
N covers the whole model.

What the flat layout GIVES UP: the single up-front all-gather is a
serial ICI prelude the forward must wait out, and the full parameter
vector stays resident in HBM for the whole step — there is no
gather/compute overlap and no per-layer liveness.  Two ways back:
``overlap=True`` (round 9, ``parallel/overlap.py``) keeps the flat
layout but moves the gather off the critical path entirely — the
updated shards are gathered by a separately-dispatched bucketed ring
that runs behind the next step's data wait, at the cost of ZeRO-1-like
parameter residency between steps; the per-layer GSPMD scheme
(``parallel/fsdp_perlayer.py``) trades the flat layout's simplicity
for use-site gathers and per-layer liveness (layer i+1's gather
overlapped with layer i's compute by XLA's latency-hiding scheduler).
Prefer per-layer for deep models at scale and this one as the simplest
correct baseline and for the CNN path.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from flax import struct
from jax import lax
from jax.flatten_util import ravel_pytree
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributed_machine_learning_tpu.data.augment import augment_batch, normalize
from distributed_machine_learning_tpu.runtime.mesh import (
    BATCH_AXIS,
    padded_len,
    shard_map_no_check as _shard_map,
)
from distributed_machine_learning_tpu.train.common import make_loss_fn, step_rng
from distributed_machine_learning_tpu.train.lars import LARSConfig
from distributed_machine_learning_tpu.train.optimizers import update_fn_for_config
from distributed_machine_learning_tpu.train.sgd import SGDConfig
from distributed_machine_learning_tpu.train.state import TrainState


@struct.dataclass
class FSDPState:
    """Sharded training state: flat 1/N param + momentum slices per device.

    ``param_shards``/``momentum_shards`` are global arrays of shape
    ``(padded_len,)`` sharded along the mesh batch axis, so each device
    materializes only ``padded_len / N`` elements (ZeRO-3 partitioning).
    BatchNorm running stats stay replicated — they are O(channels), not
    O(params), and the cross-replica invariant keeps them bit-identical.
    """

    param_shards: jax.Array
    # Flat like param_shards for SGD; a {"mu","nu"} dict of flat vectors
    # for AdamW (both elementwise — exact on arbitrary slices).
    momentum_shards: jax.Array | dict
    batch_stats: dict
    step: jax.Array
    rng: jax.Array
    config: SGDConfig = struct.field(pytree_node=False)


def _padded_len(n_elems: int, n_dev: int) -> int:
    # Canonical definition lives in runtime/mesh.py so the checkpoint
    # resharder recomputes the same partition boundaries.
    return padded_len(n_elems, n_dev)


def flat_mean_grad_shard(
    model, params, batch_stats, x, labels, axis_name: str, n: int,
    padded_len: int,
):
    """Shared back half of the flat-shard schemes' forward/backward:
    loss + grads on full params, flatten/pad, reduce-scatter the MEAN
    gradient so each device holds only the slice it owns, axis-sync BN
    stats and the loss.  Returns ``(loss, new_stats, grad_shard)``.
    One copy so ZeRO-1 and ZeRO-3 cannot drift apart.
    """
    loss_fn = make_loss_fn(model, batch_stats, x, labels, train=True)
    (loss, (_, new_stats)), grads = jax.value_and_grad(loss_fn, has_aux=True)(
        params
    )
    flat_grads, _ = ravel_pytree(grads)
    flat_grads = jnp.pad(flat_grads, (0, padded_len - flat_grads.shape[0]))
    grad_shard = lax.psum_scatter(flat_grads, axis_name, tiled=True) / n
    if new_stats:
        new_stats = jax.tree_util.tree_map(
            lambda s: lax.pmean(s, axis_name), new_stats
        )
    return lax.pmean(loss, axis_name), new_stats, grad_shard


def flatten_padded(state: TrainState, n_dev: int):
    """Flatten params + momentum to N-divisible padded vectors — the
    shared front half of every flat-shard scheme (ZeRO-1 and ZeRO-3).

    Returns ``(param_flat, momentum_flat, unravel, n_elems)``.
    """
    flat, unravel = ravel_pytree(state.params)
    n_elems = int(flat.shape[0])
    padded = _padded_len(n_elems, n_dev)
    flat = jnp.pad(flat, (0, padded - n_elems))

    def flat_pad(tree):
        f, _ = ravel_pytree(tree)
        return jnp.pad(f, (0, padded - f.shape[0]))

    p_struct = jax.tree_util.tree_structure(state.params)
    if jax.tree_util.tree_structure(state.momentum) == p_struct:
        mom_flat = flat_pad(state.momentum)  # SGD: one buffer vector
    else:
        # AdamW: each param-shaped moment tree flattens in the same leaf
        # order as the params, so flat index i of mu/nu is the moment of
        # flat param i — slicing stays aligned.
        mom_flat = {k: flat_pad(v) for k, v in state.momentum.items()}
    return flat, mom_flat, unravel, n_elems


def shard_fsdp_state(
    state: TrainState, mesh: Mesh, axis_name: str = BATCH_AXIS
):
    """Flatten a replicated TrainState into FSDP shards on the mesh.

    Returns ``(fsdp_state, unravel, n_elems)``: ``unravel`` maps the
    unpadded flat vector back to the params pytree and ``n_elems`` is the
    unpadded parameter count — both needed by
    :func:`make_fsdp_train_step` and by checkpoint export.
    """
    if isinstance(state.config, LARSConfig):
        # The flat-shard layout slices the parameter vector arbitrarily:
        # elementwise updates (SGD, AdamW) are exact on any slice, but
        # LARS's per-leaf norms would become per-slice norms.
        raise ValueError(
            "ZeRO-3/FSDP cannot shard LARS (per-layer norms are not "
            "sliceable); use sgd or adamw"
        )
    from distributed_machine_learning_tpu.telemetry import startup

    with startup.place_state(state, mesh):
        flat, mom_flat, unravel, n_elems = flatten_padded(
            state, mesh.shape[axis_name]
        )
        sharding = NamedSharding(mesh, P(axis_name))
        replicated = NamedSharding(mesh, P())
        fsdp_state = FSDPState(
            param_shards=jax.device_put(flat, sharding),
            momentum_shards=jax.tree_util.tree_map(
                lambda a: jax.device_put(a, sharding), mom_flat
            ),
            batch_stats=jax.device_put(state.batch_stats, replicated),
            step=jax.device_put(state.step, replicated),
            rng=jax.device_put(state.rng, replicated),
            config=state.config,
        )
    return fsdp_state, unravel, n_elems


def gather_fsdp_params(fsdp_state: FSDPState, unravel, n_elems: int):
    """Reassemble the full params pytree from shards (for eval/checkpoint)."""
    flat = jnp.asarray(fsdp_state.param_shards)[:n_elems]
    return unravel(flat)


def make_fsdp_train_step(
    model,
    mesh: Mesh,
    unravel,
    n_elems: int,
    axis_name: str = BATCH_AXIS,
    augment: bool = True,
    jit: bool = True,
    overlap: bool = False,
):
    """Build the jitted ZeRO-3 train step.

    ``unravel``/``n_elems`` come from :func:`shard_fsdp_state`.  Gradient
    reduction is MEAN (DDP/part3 semantics — the natural pairing for a
    scheme whose comparison point is DDP-style replicated DP).

    Returns ``step(fsdp_state, images_u8, labels) -> (fsdp_state, loss)``
    with the batch sharded along the data axis.  ``jit=False`` returns
    the traceable step for callers that compile it inside a larger
    program (a scanned epoch — same convention as
    ``make_train_step``); the donate-argnums buffer reuse only applies
    to the jitted form.

    ``overlap=True`` (requires ``jit``): the prefetch protocol of the
    overlap-aware sharded update (arxiv 2004.13336; see
    ``parallel/overlap.py``).  The up-front all-gather leaves the step
    program: the wrapper gathers the UPDATED shards into a full vector
    as a separate, immediately-dispatched bucketed-ring program right
    after each update, so the gather runs behind the host's data wait
    and the next step's program consumes the pre-gathered vector
    directly.  Bit-identical trajectory to the sync build (the gather
    is pure data movement).  The cost is ZeRO-1-like parameter
    residency: the prefetched full vector stays live between steps —
    the flat scheme keeps it live across the whole step anyway, so the
    delta is the inter-step window only.  (``FSDPState`` is unchanged;
    after a restore or any state rebind the wrapper detects the
    prefetch miss and re-gathers.)
    """
    n = mesh.shape[axis_name]

    def sharded_for(cfg: SGDConfig, gather: bool = True):
        # cfg is static (FSDPState.config is not a pytree node), so the
        # enclosing jit keys its trace cache on it and this builder runs
        # once per config — no memoization needed here.
        def body(full_flat, param_shards, momentum_shards, batch_stats,
                 step_ctr, rng, images_u8, labels):
            params = unravel(full_flat[:n_elems])

            r = step_rng(rng, step_ctr, axis_name)
            x = augment_batch(r, images_u8) if augment else normalize(images_u8)

            # (2)+(3) forward/backward + reduce-scatter of the MEAN grad —
            # each device receives the slice it owns (half the ring, half
            # the bytes of a full all-reduce).
            loss, new_stats, grad_shard = flat_mean_grad_shard(
                model, params, batch_stats, x, labels, axis_name, n,
                full_flat.shape[0],
            )

            # (4) Optimizer update on the local shard only (the registry
            # update fns work on bare arrays / dicts of arrays): weight
            # decay reads the local *param* shard, so no second
            # all-gather is needed.
            new_params, new_mom = update_fn_for_config(cfg)(
                param_shards, momentum_shards, grad_shard, cfg,
                step=step_ctr,
            )
            return new_params, new_mom, new_stats, loss

        shard = P(axis_name)
        if gather:
            # Sync build: (1) the up-front all-gather INSIDE the program
            # — a serial ICI prelude the forward must wait out.
            def impl(param_shards, momentum_shards, batch_stats, step_ctr,
                     rng, images_u8, labels):
                full_flat = lax.all_gather(param_shards, axis_name,
                                           tiled=True)
                return body(full_flat, param_shards, momentum_shards,
                            batch_stats, step_ctr, rng, images_u8, labels)

            return _shard_map(
                impl,
                mesh=mesh,
                in_specs=(shard, shard, P(), P(), P(), shard, shard),
                out_specs=(shard, shard, P(), P()),
            )
        # Overlap build: the full vector arrives pre-gathered (the
        # consume phase of the previous step's prefetch dispatch).
        return _shard_map(
            body,
            mesh=mesh,
            in_specs=(P(), shard, shard, P(), P(), P(), shard, shard),
            out_specs=(shard, shard, P(), P()),
        )

    if not overlap:
        def step(state: FSDPState, images_u8, labels):
            new_params, new_mom, new_stats, loss = sharded_for(
                state.config
            )(
                state.param_shards,
                state.momentum_shards,
                state.batch_stats,
                state.step,
                state.rng,
                images_u8,
                labels,
            )
            new_state = state.replace(
                param_shards=new_params,
                momentum_shards=new_mom,
                batch_stats=new_stats,
                step=state.step + 1,
            )
            return new_state, loss

        return jax.jit(step, donate_argnums=(0,)) if jit else step

    if not jit:
        raise ValueError(
            "overlap=True manages its own two-program dispatch and "
            "cannot be embedded un-jitted; use overlap=False with "
            "jit=False for scanned-epoch callers"
        )
    return _make_fsdp_overlap_step(
        mesh, axis_name, n,
        update_sharded_for=lambda cfg: sharded_for(cfg, gather=False),
        make_state=lambda state, new_params, new_mom, new_stats: state.replace(
            param_shards=new_params,
            momentum_shards=new_mom,
            batch_stats=new_stats,
            step=state.step + 1,
        ),
        state_args=lambda state: (
            state.momentum_shards,
            state.batch_stats,
            state.step,
            state.rng,
        ),
        donate=(0, 2, 3),
    )


def _make_fsdp_overlap_step(mesh, axis_name, n, update_sharded_for,
                            make_state, state_args,
                            donate=(0, 2, 3)):
    """Prefetch-protocol wrapper shared by the CNN and LM ZeRO-3 steps:
    holds the in-flight full-parameter vector between steps, re-gathers
    on a prefetch miss (first call, restore, external rebind), and
    keeps the ``param_gather`` telemetry span.

    The update program takes ``(full_flat, param_shards, *state_args,
    x, y)`` and returns ``(new_shards, new_mom, *rest, loss)``; the
    wrapper dispatches the next gather right after it."""
    from distributed_machine_learning_tpu.parallel.overlap import (
        GatherSpanClock,
        make_ring_gather,
    )

    # donate=False: the gather input IS the state's param_shards — the
    # next update (and any checkpoint) still reads it.
    gather_inner = make_ring_gather(mesh, axis_name, n, donate=False)

    jitted: dict = {}

    def update_for(cfg):
        fn = jitted.get(cfg)
        if fn is None:
            # Donate the prefetched full vector (arg 0 — consumed by
            # the forward; freeing it mid-program caps peak HBM at the
            # sync build's level) plus the momentum/stats buffers,
            # which alias their updated twins.  NOT donated:
            # param_shards (arg 1 — the separately-dispatched gather
            # still reads it), step (re-read by the wrapper's
            # ``state.step + 1``) and rng (carried unchanged into the
            # next step).
            fn = jitted[cfg] = jax.jit(
                update_sharded_for(cfg), donate_argnums=donate
            )
        return fn

    clock = GatherSpanClock()
    holder: dict = {"shards": None, "full": None}

    def step(state: FSDPState, images_u8, labels):
        clock.close()
        if holder["shards"] is not state.param_shards:
            # Prefetch miss: first step, post-restore, or the caller
            # rebound the state — gather now (still an async dispatch;
            # the update program below queues behind it).
            holder["full"] = gather_inner(state.param_shards)
        full, holder["full"] = holder["full"], None  # donated below
        out = update_for(state.config)(
            full, state.param_shards, *state_args(state), images_u8,
            labels,
        )
        new_params, loss = out[0], out[-1]
        new_state = make_state(state, *out[:-1])
        holder["shards"] = new_params
        holder["full"] = gather_inner(new_params)
        clock.open(holder["full"])
        return new_state, loss

    step.overlap = True
    step.update_for = update_for
    step.gather_inner = gather_inner
    step.pop_gather_seconds = clock.pop
    return step


def make_fsdp_lm_train_step(
    model,
    mesh: Mesh,
    unravel,
    n_elems: int,
    axis_name: str = BATCH_AXIS,
    fused_ce_chunks: int | None = None,
    overlap: bool = False,
):
    """ZeRO-3 for the transformer LM: params + optimizer state sharded
    1/N over the data axis, batch sharded over the same axis.

    The flat-shard machinery is model-agnostic, so this is the same
    all-gather → fwd/bwd → psum_scatter → local-shard-update recipe as
    the CNN step, with the LM loss (``train/lm_step.py::lm_loss`` —
    optionally the fused head+loss) in the middle.  Pair with AdamW
    (``config=AdamWConfig()``): the two fp32 moment vectors are the
    memory ZeRO exists to shard.  Dense attention only (ring/ulysses
    need a 2-D mesh; composing FSDP×CP is future work).

    ``overlap=True``: the prefetch protocol (see
    :func:`make_fsdp_train_step` and ``parallel/overlap.py``) — the
    up-front gather leaves the program and runs behind the host's data
    wait as a bucketed-ring dispatch; bit-identical trajectory.

    Returns ``step(fsdp_state, tokens, targets) -> (fsdp_state, loss)``.
    """
    if model.attn_impl != "dense":
        raise ValueError(
            "FSDP LM step requires attn_impl='dense' (sequence-sharded "
            "attention needs a second mesh axis)"
        )
    n = mesh.shape[axis_name]

    def sharded_for(cfg, gather: bool = True):
        def body(full_flat, param_shards, momentum_shards, step_ctr, rng,
                 tokens, targets):
            del rng  # no augmentation on the LM path
            from distributed_machine_learning_tpu.train.lm_step import lm_loss

            params = unravel(full_flat[:n_elems])

            loss, grads = jax.value_and_grad(
                lambda p: lm_loss(model, p, tokens, targets, fused_ce_chunks)
            )(params)
            flat_grads, _ = ravel_pytree(grads)
            flat_grads = jnp.pad(
                flat_grads, (0, full_flat.shape[0] - flat_grads.shape[0])
            )
            grad_shard = lax.psum_scatter(flat_grads, axis_name, tiled=True) / n

            new_params, new_mom = update_fn_for_config(cfg)(
                param_shards, momentum_shards, grad_shard, cfg,
                step=step_ctr,
            )
            return new_params, new_mom, lax.pmean(loss, axis_name)

        shard = P(axis_name)
        if gather:
            def impl(param_shards, momentum_shards, step_ctr, rng, tokens,
                     targets):
                full_flat = lax.all_gather(param_shards, axis_name,
                                           tiled=True)
                return body(full_flat, param_shards, momentum_shards,
                            step_ctr, rng, tokens, targets)

            return _shard_map(
                impl,
                mesh=mesh,
                in_specs=(shard, shard, P(), P(), shard, shard),
                out_specs=(shard, shard, P()),
            )
        return _shard_map(
            body,
            mesh=mesh,
            in_specs=(P(), shard, shard, P(), P(), shard, shard),
            out_specs=(shard, shard, P()),
        )

    if not overlap:
        def step(state: FSDPState, tokens, targets):
            new_params, new_mom, loss = sharded_for(state.config)(
                state.param_shards,
                state.momentum_shards,
                state.step,
                state.rng,
                tokens,
                targets,
            )
            new_state = state.replace(
                param_shards=new_params,
                momentum_shards=new_mom,
                step=state.step + 1,
            )
            return new_state, loss

        return jax.jit(step, donate_argnums=(0,))

    return _make_fsdp_overlap_step(
        mesh, axis_name, n,
        update_sharded_for=lambda cfg: sharded_for(cfg, gather=False),
        make_state=lambda state, new_params, new_mom: state.replace(
            param_shards=new_params,
            momentum_shards=new_mom,
            step=state.step + 1,
        ),
        state_args=lambda state: (state.momentum_shards, state.step,
                                  state.rng),
        donate=(0, 2),
    )


def fsdp_memory_footprint(n_params: int, n_dev: int, bytes_per_elem: int = 4):
    """Per-device optimizer-state bytes: replicated DP vs ZeRO-3 shards."""
    replicated = 2 * n_params * bytes_per_elem
    sharded = 2 * _padded_len(n_params, n_dev) // n_dev * bytes_per_elem
    return {"replicated": replicated, "fsdp": sharded}
