"""Sort-based grouped expert MLP — the dropless MoE compute path.

The einsum dispatch in ``models/moe.py`` is the right shape for GSPMD
expert parallelism (the one-hot dispatch/combine einsums are what the
partitioner turns into the token all-to-all), but on a single device it
pays O(N·E·C·D) = O(1.25·N²·D) FLOPs of pure data movement per
dispatch/combine pair — quadratic in tokens and all of it off the MXU's
useful-work path.  The grouped path here is the TPU-idiomatic
alternative (the design MegaBlocks argues for on GPUs, mapped onto
XLA's native ragged matmul): sort token rows by their routed expert,
run one ``lax.ragged_dot`` per projection over the contiguous groups,
and unsort.  Dispatch cost falls to O(N·D) gather/scatter bandwidth,
and the expert matmuls run at dense-matmul MFU (measured on this
repo's chip: 134 TF/s ragged vs 94 TF/s effective for the einsum
fragment at N=8k, D=2k, F=8k — before counting the combine einsum).

It is **dropless**: every token reaches its expert, with no capacity
rounding — group sizes are data-dependent *values*, which ``ragged_dot``
consumes without shape dynamism (the row buffer holds every assignment
there is).  Capacity/overflow semantics (Switch's) remain available via
the einsum path; parity between the two holds whenever capacity is ample
enough that nothing drops (tested).

One routed front end serves every model: :func:`route_topk` picks ``k``
experts a token from the router's probabilities (``k = 1``: Switch; with
``renormalize`` the chosen weights sum to one) and
:func:`grouped_expert_mlp` computes, for GELU or gated-SiLU experts with
or without biases, the part of the weighted sum that the experts HELD
here give.  The router keeps its whole width whatever is held: a chip
that holds experts ``[first_held, first_held + E_local)`` of a wider
layer (one member of an expert-parallel group, run without the exchange)
sorts assignments to absent experts into a tail group that no matmul
touches, and may bound its row buffer near the share it expects, every
row past the bound counted.

Scope: single-device, shard_map-style data parallelism (each device
runs this on its local tokens), and — via
:func:`grouped_expert_mlp_ep` — real expert parallelism under a
fully-manual shard_map (top-1 only): token rows travel to their expert's
owner device through an explicit ``lax.all_to_all`` along the expert mesh
axis, ``ragged_dot`` runs over the received groups locally, and the
outputs ride the inverse all-to-all home.  ``ragged_dot`` has no GSPMD
partitioning rule, so the automatic-partitioner EP step keeps the
einsum path (guarded in ``parallel/expert_parallel.py``); the manual
path here is how the dropless kernel composes with EP.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

#: ``jax.ad_checkpoint.checkpoint_name`` tags of :func:`grouped_expert_mlp`'s
#: routing, which the reverses of the gathers and of ``ragged_dot`` read: the
#: sorted assignment of each buffer slot, its token, each assignment's slot,
#: the held groups' sizes, each row's expert and its weight.  With the chosen
#: experts (:func:`route_topk`) what a recomputed block keeps of the sort
#: (``models/transformer.py::whole_block_policy``).
EXPERT_ROUTING_NAMES = (
    "expert_slots", "expert_token_of_slot", "expert_slot_of_assignment",
    "expert_sizes", "expert_eids", "expert_weight_of_slot")
#: Tags of the dispatched rows ``[rows, D]`` and the two pre-activation
#: outputs ``[rows, F]`` (``x·w_in``; ``x·w_gate`` of a gated MLP): the
#: grouped matmuls' inputs and what the activation's reverse reads.
EXPERT_ROW_NAMES = ("expert_rows", "expert_up", "expert_gate")
#: Tags of the router's float32 logits ``[N, E]`` (``models/hybrid_moe.py::
#: SparseMoE``) and of the chosen experts ``[N, k]`` where a selection bias
#: chose them (:func:`route_topk`): what the scores' reverse reads, so that a
#: recomputed block runs neither the router's matmul nor its top-k again.
ROUTE_NAMES = ("route_logits", "route_choice")


def sort_by_expert(expert_idx: jax.Array, n_experts: int):
    """Permutation that groups token rows by expert, plus group sizes.

    Returns ``(order, inv_order, group_sizes)``: ``order`` sorts rows so
    expert 0's tokens come first, ``inv_order`` undoes it, and
    ``group_sizes[e]`` counts expert e's tokens (int32, as
    ``lax.ragged_dot`` requires).

    Counting sort, not ``argsort``: a bitonic sort of N int keys costs
    ~log²N full-array passes on the VPU (measured ~2 ms at N=8k on this
    chip — comparable to one of the expert matmuls it feeds).  With E
    experts the permutation is cheaper to *construct*: one [N, E] cumsum
    over the routing one-hot gives each token its rank within its
    expert's group, an exclusive-sum of group sizes gives each group's
    base offset, and rank + offset IS the token's destination slot —
    stable, total, and O(N·E) elementwise work.
    """
    n = expert_idx.shape[0]
    onehot = jax.nn.one_hot(expert_idx, n_experts, dtype=jnp.int32)  # [N, E]
    ranks = jnp.cumsum(onehot, axis=0)  # rank-within-expert, 1-based at own row
    group_sizes = ranks[-1]  # [E] — totals; int32 already
    offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(group_sizes)[:-1]]
    )  # exclusive prefix: group e starts at offsets[e]
    # Destination slot of each token = its group's base + its 0-based rank.
    dest = offsets[expert_idx] + (
        jnp.sum(ranks * onehot, axis=1, dtype=jnp.int32) - 1
    )
    inv_order = dest  # sorted[dest[i]] = tokens[i]  ⇒  dest inverts order
    order = jnp.zeros((n,), jnp.int32).at[dest].set(
        jnp.arange(n, dtype=jnp.int32)
    )
    return order, inv_order, group_sizes


@jax.custom_vjp
def _permute_rows(x: jax.Array, perm: jax.Array, inv_perm: jax.Array):
    """``x[perm]`` with a permutation-aware VJP.

    ``jnp.take``'s generic transpose is a scatter-add (indices could
    repeat), which TPUs execute row-at-a-time — profiled at ~22 GB/s on
    this chip, ~3 ms per [8k, 2k] un-permute in the MoE backward.  A
    permutation is bijective, so its cotangent is just the gather by the
    inverse permutation: both directions run at gather (HBM) speed.
    """
    return jnp.take(x, perm, axis=0)


def _permute_rows_fwd(x, perm, inv_perm):
    return jnp.take(x, perm, axis=0), (perm, inv_perm)


def _permute_rows_bwd(res, ct):
    perm, inv_perm = res
    return jnp.take(ct, inv_perm, axis=0), None, None


_permute_rows.defvjp(_permute_rows_fwd, _permute_rows_bwd)


def route_topk(probs: jax.Array, k: int, renormalize: bool = False, *,
               bias: jax.Array | None = None, scale: float = 1.0):
    """The ``k`` experts of every token and their weights.

    ``probs``: [N, E] router scores over ALL experts, held here or not (a
    softmax's probabilities or element-wise sigmoids).  Returns
    ``(expert_idx [N, k] int32, weights [N, k])`` in descending order of
    what was selected by; ``renormalize`` makes each token's ``k`` weights
    sum to one (over the chosen ``k``, wherever they live), and ``scale``
    multiplies them after that.  ``k = 1`` is Switch routing: the argmax
    and its probability.

    ``bias`` [E]: the set is the ``k`` largest of ``probs + bias`` and the
    weights are the chosen experts' ``probs`` — the bias picks, it never
    weighs (bias-corrected routing without an auxiliary loss).  It gets no
    gradient: the choice is an integer."""
    if bias is None:
        weights, expert_idx = lax.top_k(probs, k)
    else:
        _, expert_idx = lax.top_k(probs + bias, k)
        # Tagged where the weights' reverse (a gather's) reads it: a
        # recomputed block that keeps it selects nothing again.
        expert_idx = checkpoint_name(expert_idx, ROUTE_NAMES[1])
        weights = jnp.take_along_axis(probs, expert_idx, axis=-1)
    if renormalize:
        # + 1e-20: k sigmoids may sum to nothing; a softmax's top-k sum
        # (>= k/E) takes it without moving a bit.
        weights = weights / (
            jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    if scale != 1.0:
        weights = weights * scale
    return expert_idx, weights


def selection_moved_share(probs: jax.Array, expert_idx: jax.Array):
    """The share of tokens whose chosen set ``expert_idx`` [N, k] is not the
    ``k`` largest of ``probs`` [N, E]: some expert left out scores higher
    than some expert chosen (what a selection bias changed)."""
    chosen = jnp.any(
        expert_idx[:, :, None] == jnp.arange(probs.shape[-1]), axis=1)
    lowest_in = jnp.min(jnp.where(chosen, probs, jnp.inf), axis=-1)
    highest_out = jnp.max(jnp.where(chosen, -jnp.inf, probs), axis=-1)
    return jnp.mean((highest_out > lowest_in).astype(jnp.float32))


def _gather_sum(rows, slot_of_assignment, weights=None):
    """``out[n] = Σ_j weights[n, j] · rows[slot_of_assignment[n, j]]`` in
    float32, with a zero row behind the buffer for assignments that have no
    slot (``slot == len(rows)``).  One ``[N, D]`` gather an assignment
    column, summed as they come: the ``[N, k, D]`` stack is never built —
    at ``k = 10`` of which a sixteenth is held it would be 320 MB a layer
    at 8192 tokens, nearly all of it the zero row."""
    ext = jnp.concatenate([rows, jnp.zeros_like(rows[:1])])
    out = 0.0
    for j in range(slot_of_assignment.shape[1]):
        term = jnp.take(ext, slot_of_assignment[:, j], axis=0).astype(
            jnp.float32)
        if weights is not None:
            term = term * weights[:, j, None]
        out = out + term
    return out


@jax.custom_vjp
def _dispatch_rows(tokens, token_of_slot, slot_of_assignment):
    """``tokens[token_of_slot]``: one row a buffer slot.  A token routed to
    several held experts fills several slots, so the generic transpose is a
    scatter-add with repeated rows (row-at-a-time on a TPU, see
    :func:`_permute_rows`).  ``slot_of_assignment`` [N, k] is the inverse
    map — the slot of token n's j-th expert, or the buffer's length for an
    assignment that has none — so the cotangent is a gather: each token sums
    the rows of its own slots."""
    return jnp.take(tokens, token_of_slot, axis=0)


def _dispatch_rows_fwd(tokens, token_of_slot, slot_of_assignment):
    return jnp.take(tokens, token_of_slot, axis=0), slot_of_assignment


def _dispatch_rows_bwd(slot_of_assignment, ct):
    return (_gather_sum(ct, slot_of_assignment).astype(ct.dtype), None, None)


_dispatch_rows.defvjp(_dispatch_rows_fwd, _dispatch_rows_bwd)


@jax.custom_vjp
def _combine_rows(ys, weights, slot_of_assignment, token_of_slot,
                  weight_of_slot):
    """``y[n] = Σ_j weights[n, j] · ys[slot_of_assignment[n, j]]`` with a
    zero row behind the buffer for assignments that have no slot.  The
    cotangents are gathers over the buffer's slots (slot s reads its
    token's row: times its weight for ``ys``, dotted with its own row for
    the weight) instead of the scatter-add autodiff would emit.  The
    weights round to the rows' dtype first (for ``k = 1`` the product is
    then Switch's ``y · prob`` to the bit); the sum over ``k`` is
    float32."""
    rounded = weights.astype(ys.dtype).astype(jnp.float32)
    return _gather_sum(ys, slot_of_assignment, rounded).astype(ys.dtype)


def _combine_rows_fwd(ys, weights, slot_of_assignment, token_of_slot,
                      weight_of_slot):
    out = _combine_rows(ys, weights, slot_of_assignment, token_of_slot,
                        weight_of_slot)
    return out, (ys, weights, slot_of_assignment, token_of_slot,
                 weight_of_slot)


def _combine_rows_bwd(res, ct):
    ys, weights, slot_of_assignment, token_of_slot, weight_of_slot = res
    ct_of_slot = jnp.take(ct, token_of_slot, axis=0)         # [rows, D]
    d_ys = ct_of_slot * weight_of_slot[:, None].astype(ct.dtype)
    # <ys[s], ct[token of s]> a slot, handed to the slot's assignment; an
    # assignment without a slot reads the zero behind the buffer.
    per_slot = jnp.einsum("sd,sd->s", ys, ct_of_slot,
                          preferred_element_type=jnp.float32)
    d_weights = jnp.take(jnp.concatenate([per_slot, jnp.zeros((1,))]),
                         slot_of_assignment, axis=0)
    return d_ys, d_weights.astype(weights.dtype), None, None, None


_combine_rows.defvjp(_combine_rows_fwd, _combine_rows_bwd)


def grouped_expert_mlp(
    tokens: jax.Array,
    expert_idx: jax.Array,
    weights: jax.Array,
    w_in: jax.Array,
    w_out: jax.Array,
    *,
    b_in: jax.Array | None = None,
    b_out: jax.Array | None = None,
    w_gate: jax.Array | None = None,
    activation=jax.nn.gelu,
    first_held: int = 0,
    capacity: int | None = None,
    w_in_scale: jax.Array | None = None,
    w_out_scale: jax.Array | None = None,
    return_counts: bool = False,
    return_active: bool = False,
):
    """Routed expert MLP over ``[N, D]`` token rows: the part of
    ``Σ_j weights[n, j] · Expert_{expert_idx[n, j]}(tokens[n])`` that the
    experts HELD here give.

    ``tokens``: [N, D] (already cast to the compute dtype);
    ``expert_idx``, ``weights``: [N, k] from :func:`route_topk` — global
    expert ids over the router's whole width.  The expert weights carry a
    leading LOCAL expert axis ``[E_local, ...]`` and stand for the global
    experts ``[first_held, first_held + E_local)``; an assignment to any
    other expert sorts to a tail group that no matmul touches and adds
    nothing (what absent experts would add is another chip's).  An expert
    is ``act(x·w_in + b_in)·w_out + b_out``, or, with ``w_gate``,
    ``(act(x·w_gate) ⊙ x·w_in)·w_out`` (a gated MLP; biases optional in
    both).  Returns [N, D] in ``tokens.dtype``.  Gradients flow to
    tokens, weights and every expert leaf; the integer routing is
    non-differentiable.

    ``capacity``: rows of the dispatch buffer.  ``None`` = ``N·k``, every
    assignment there is — dropless as a static property, and exactly what
    is needed when all experts are held.  A chip that holds a share of
    the experts expects ``N·k·E_local/E`` rows and may bound the buffer
    near that; assignments past the bound are dropped from the last
    groups and COUNTED: ``return_counts=True`` also returns
    ``(group_sizes [E_local] int32 as computed, n_dropped int32)``, and
    with ``return_active=True`` (a gated MLP) a third count: the entries of
    ``x·w_gate`` above zero over the rows computed — the hidden units a
    ReLU gate passes.

    ``w_in_scale``/``w_out_scale`` ([E, F] / [E, D] f32): weight-only
    int8 expert serving — ``w_in``/``w_out`` are then int8 and the
    per-expert per-output-channel scales fold into the activations
    AFTER each ragged matmul (each row multiplies its own expert's
    scale row, gathered by ``eids``), the same
    quantize-stays-in-the-dot recipe as the int8 KV cache's einsum
    (``models/transformer.py::_cached_attention_quant``): the int8→
    compute-dtype convert fuses into ``ragged_dot``'s operand read, so
    HBM only ever reads the int8 expert bytes.
    """
    n, k = expert_idx.shape
    e_local = w_in.shape[0]
    rows = n * k if capacity is None else min(capacity, n * k)
    local = expert_idx.reshape(-1) - first_held
    local = jnp.where((local >= 0) & (local < e_local), local, e_local)
    order, dest, group_sizes = sort_by_expert(local, e_local + 1)
    # Held groups sort first, so the buffer is the head of the order; the
    # bound cuts whole rows off the last groups.
    ends = jnp.minimum(jnp.cumsum(group_sizes[:e_local]), rows)
    sizes = jnp.diff(ends, prepend=0)
    n_dropped = jnp.sum(group_sizes[:e_local]) - ends[-1]
    slots = order[:rows]
    token_of_slot = slots // k
    in_buffer = (local < e_local) & (dest < rows)
    slot_of_assignment = jnp.where(in_buffer, dest, rows).reshape(n, k)
    weight_of_slot = jnp.where(
        jnp.arange(rows) < ends[-1],
        jnp.take(weights.reshape(-1), slots, axis=0), 0)
    # Sorted expert of each row; a row past the held groups (no matmul
    # touches it, nothing reads it) borrows the last expert's bias and scale.
    eids = jnp.minimum(jnp.take(local, slots, axis=0), e_local - 1)
    # The identity outside a ``jax.checkpoint``; inside a recomputed block
    # what the backward pass reads instead of sorting and gathering again.
    (slots, token_of_slot, slot_of_assignment, sizes, eids,
     weight_of_slot) = map(checkpoint_name, (
         slots, token_of_slot, slot_of_assignment, sizes, eids,
         weight_of_slot), EXPERT_ROUTING_NAMES)

    dt = tokens.dtype
    rows_name, up_name, gate_name = EXPERT_ROW_NAMES
    xs = checkpoint_name(
        _dispatch_rows(tokens, token_of_slot, slot_of_assignment), rows_name)

    def project(x, w, scale, bias):
        y = lax.ragged_dot(x, w.astype(dt), sizes)
        if scale is not None:
            y = y * jnp.take(scale, eids, axis=0).astype(dt)
        if bias is not None:
            y = y + jnp.take(bias.astype(dt), eids, axis=0)
        return y

    h = checkpoint_name(project(xs, w_in, w_in_scale, b_in), up_name)
    if w_gate is None:
        h = activation(h)
    else:
        g = checkpoint_name(project(xs, w_gate, None, None), gate_name)
        h = activation(g) * h
    ys = project(h, w_out, w_out_scale, b_out)
    y = _combine_rows(ys, weights, slot_of_assignment, token_of_slot,
                      weight_of_slot)
    if not return_counts:
        return y
    if not return_active:
        return y, (sizes, n_dropped)
    if w_gate is None:
        raise ValueError("return_active counts a gated MLP's gate units")
    computed = (jnp.arange(rows) < ends[-1])[:, None]
    active = jnp.sum((g > 0) & computed, dtype=jnp.int32)
    return y, (sizes, n_dropped, active)


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def _scatter_rows(x: jax.Array, idx: jax.Array, n_out: int):
    """Rows of ``x`` scattered to UNIQUE slots ``idx`` of a zero
    [n_out, D] buffer.  Because the slots are unique (an injection —
    the EP slotting map below guarantees it), the exact cotangent is
    the gather back by ``idx`` — never the generic scatter-add
    transpose (row-at-a-time on TPU, ~22 GB/s measured; see
    ``_permute_rows``)."""
    return jnp.zeros((n_out, x.shape[1]), x.dtype).at[idx].set(x)


def _scatter_rows_fwd(x, idx, n_out):
    return _scatter_rows(x, idx, n_out), idx


def _scatter_rows_bwd(n_out, idx, ct):
    return jnp.take(ct, idx, axis=0), None


_scatter_rows.defvjp(_scatter_rows_fwd, _scatter_rows_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def _gather_rows(x: jax.Array, idx: jax.Array, n_in: int):
    """``x[idx]`` where ``idx`` addresses UNIQUE rows of an [n_in, D]
    buffer: the exact cotangent is the scatter-set back (unaddressed
    rows correctly get zero), avoiding ``jnp.take``'s scatter-add
    transpose."""
    return jnp.take(x, idx, axis=0)


def _gather_rows_fwd(x, idx, n_in):
    return jnp.take(x, idx, axis=0), idx


def _gather_rows_bwd(n_in, idx, ct):
    return jnp.zeros((n_in, ct.shape[1]), ct.dtype).at[idx].set(ct), None


_gather_rows.defvjp(_gather_rows_fwd, _gather_rows_bwd)


def grouped_expert_mlp_ep(
    tokens: jax.Array,
    expert_idx: jax.Array,
    w_in: jax.Array,
    b_in: jax.Array,
    w_out: jax.Array,
    b_out: jax.Array,
    *,
    expert_axis: str,
    n_experts_global: int,
    activation=jax.nn.gelu,
    slots_per_owner: int | None = None,
    return_dropped: bool = False,
) -> jax.Array | tuple[jax.Array, jax.Array]:
    """Dropless routed expert MLP under REAL expert parallelism.

    Must run inside a ``shard_map`` with ``expert_axis`` bound (fully
    manual over it).  Each device holds ``tokens`` [N_local, D] — its
    shard of the global batch — and the weights of its
    ``E_local = n_experts_global / ep`` experts (leading axis of
    ``w_in``/``b_in``/``w_out``/``b_out`` is the LOCAL expert count;
    device r owns global experts [r·E_local, (r+1)·E_local)).
    ``expert_idx`` routes each local token to a GLOBAL expert.

    The dance (all static shapes, exact inverses on the way back):

    1. **Slot**: token i goes to owner ``o = expert // E_local`` at
       slot ``o·S + rank_within_owner(i)`` with ``S = N_local`` send
       slots per owner — a device can send at most all its rows to one
       owner, so the bound can never overflow: **provably dropless**,
       unlike the einsum path's per-expert capacity.  The slot map is
       injective, so scatter/gather custom VJPs are exact inverses.
    2. **all_to_all** along ``expert_axis``: chunk o of the send
       buffer lands on device o — the token all-to-all the einsum path
       leaves to the GSPMD partitioner, written explicitly.
    3. **Group**: received rows counting-sort by LOCAL expert with a
       trailing dummy group for empty slots; ``lax.ragged_dot`` covers
       only the real groups (uncovered trailing rows produce zeros
       with zero gradients — verified semantics).
    4. **Return**: un-sort, all_to_all back, gather by the slot map.

    Returns [N_local, D] in ``tokens.dtype``; one expert a token, and
    the router-prob scaling is the caller's (:func:`grouped_expert_mlp`
    takes ``k`` experts a token and applies the weights itself).  The
    ICI cost is
    2 all_to_alls of ep·S rows; the matmul padding is bounded by the
    receive buffer (ep·S rows vs ~N_local useful on a balanced
    router).  Reference: the all-to-all pattern is Switch/GShard
    dispatch (SURVEY.md §2.3 marks EP absent in the reference — this
    is beyond-parity capability).

    ``slots_per_owner`` (ADVICE r4): by default S = N_local send slots
    per owner — provably dropless, but the all-to-all moves ep·N_local
    rows (~ep× the useful rows on a balanced router).  Setting S lower
    (e.g. ``2·N_local/ep``) bounds the wire bytes and matmul padding at
    the cost of Switch-style drops: a token whose within-owner rank
    exceeds S gets ZERO output (residual pass-through) and zero
    gradients — the same overflow semantics as einsum capacity, applied
    per OWNER at the transport instead of per expert.
    ``return_dropped=True`` additionally returns the local dropped-row
    count (int32 scalar) for monitoring.
    """
    ep = lax.axis_size(expert_axis)
    e_local = w_in.shape[0]
    if e_local * ep != n_experts_global:
        raise ValueError(
            f"local expert axis {e_local} x mesh axis {ep} != "
            f"n_experts_global {n_experts_global}"
        )
    n, d = tokens.shape
    if slots_per_owner is not None and not 1 <= slots_per_owner <= n:
        raise ValueError(
            f"slots_per_owner must be in [1, N_local={n}], got "
            f"{slots_per_owner} (None = dropless N_local slots)"
        )
    S = n if slots_per_owner is None else slots_per_owner
    e0 = lax.axis_index(expert_axis) * e_local

    owner = expert_idx // e_local  # [N] destination device on the axis
    oh = jax.nn.one_hot(owner, ep, dtype=jnp.int32)
    rank = jnp.sum(jnp.cumsum(oh, axis=0) * oh, axis=1) - 1  # within-owner
    # ONE dispatch form for both modes (tested bitwise-equal at ample
    # slots): overflowing rows — impossible when S = N_local, since
    # rank < n always — route to a TRASH slot past the buffer; the
    # [:ep*S] slice discards it, so (a) receivers never see them and
    # (b) the slice's transpose zeroes their cotangent.  _scatter_rows'
    # unique-slot contract is violated only at the trash slot, whose
    # value and cotangent are both dead.  Expert ids ride beside the
    # rows; -1 marks never-written slots.
    valid = rank < S
    slot = jnp.where(valid, owner * S + rank, ep * S)
    send = _scatter_rows(tokens, slot, ep * S + 1)[:ep * S]
    send_ids = jnp.full((ep * S + 1,), -1, jnp.int32).at[slot].set(
        expert_idx
    )[:ep * S]
    n_dropped = jnp.sum((~valid).astype(jnp.int32))
    recv = lax.all_to_all(
        send.reshape(ep, S, d), expert_axis, 0, 0, tiled=False
    ).reshape(ep * S, d)
    recv_ids = lax.all_to_all(
        send_ids.reshape(ep, S, 1), expert_axis, 0, 0, tiled=False
    ).reshape(ep * S)

    # Local grouping: dummy group (= e_local) LAST, so ragged_dot's
    # group_sizes[:e_local] cover exactly the real rows.
    le = jnp.where(recv_ids >= 0, recv_ids - e0, e_local)
    order, inv_order, group_sizes = sort_by_expert(le, e_local + 1)
    xs = _permute_rows(recv, order, inv_order)
    eids = jnp.take(le, order, axis=0)  # sorted; dummies trail
    gs = group_sizes[:e_local]
    dt = tokens.dtype
    # Biases extended with a zero row so dummy rows stay inert.
    b_in_x = jnp.concatenate([b_in, jnp.zeros_like(b_in[:1])]).astype(dt)
    b_out_x = jnp.concatenate([b_out, jnp.zeros_like(b_out[:1])]).astype(dt)
    h = lax.ragged_dot(xs, w_in.astype(dt), gs)
    h = activation(h + jnp.take(b_in_x, eids, axis=0))
    ys = lax.ragged_dot(h, w_out.astype(dt), gs)
    ys = ys + jnp.take(b_out_x, eids, axis=0)
    # Dummy rows stay exactly zero: ragged_dot leaves uncovered trailing
    # rows zero and their bias row (index e_local of the extended bias)
    # is zero.  They are also never gathered on the sender side — the
    # slot map only reads slots it wrote — so BOTH properties protect
    # the result independently.
    ys = _permute_rows(ys, inv_order, order)
    back = lax.all_to_all(
        ys.reshape(ep, S, d), expert_axis, 0, 0, tiled=False
    ).reshape(ep * S, d)
    # Dropped rows gather the appended zero row (their slot is the
    # trash index ep*S): zero output, and the concat transpose discards
    # the trash cotangent — zero gradients, matching the forward's
    # pass-through semantics.  (Unbounded: no row points at the trash
    # index, so the appended zero row is inert — the single code path
    # the ample-slots test pins bitwise against the r4 form.)
    back = jnp.concatenate([back, jnp.zeros((1, d), back.dtype)])
    y = _gather_rows(back, slot, ep * S + 1)
    return (y, n_dropped) if return_dropped else y
