"""Ring-bucket comm/compute overlap audit — schedule-level proof.

The north-star program (``ops/ring.py``) claims XLA's async collective
scheduler overlaps bucket k's ppermutes with bucket k+1's adds — the
property DDP's C++ reducer provides and the reason 25 MB buckets exist
(``/root/reference/part3/main.py:59``, group25.pdf p.6).  A single
attached chip cannot *run* an 8-device ring (a 1-device mesh has zero
ppermutes), so this audit produces the strongest evidence available
without a pod: it AOT-compiles the full part3 train step for a REAL
multi-chip TPU target (``jax.experimental.topologies`` — the same
XLA:TPU backend, latency-hiding scheduler included, that a pod would
use) and walks the optimized module's schedule:

- every ``collective-permute-start``/``-done`` pair is an async window
  in which the DMA is in flight;
- compute ops textually scheduled between start and done execute under
  that DMA — the overlap, read straight off the executable.

Run: ``python -m distributed_machine_learning_tpu.bench.overlap_audit``
(needs libtpu for the compile-only TPU client; prints one JSON line).

This is a static schedule, not a device timeline: it proves the
executable *orders* bucket math under bucket DMAs, while actual wall-
clock hiding additionally depends on DMA latency vs fusion runtime —
the part a pod xprof would add.

**Wire-byte audit** (round 7, ``--wire-bytes``): the compressed ring
(``ops/ring.py`` wire schemes) claims ~4x fewer bytes per hop for the
int8 codec.  :func:`wire_bytes_from_hlo` reads the claim off the
COMPILED program — it sums the operand bytes of every
``collective-permute``/``collective-permute-start`` the executable
actually issues — so the reduction is verified in the artifact that
runs, not assumed from the source.  Works against any backend's HLO
(the CPU test mesh and the TPU AOT target name the op identically);
``--wire-bytes`` compiles the part3 step exact and int8 and asserts
the compressed build moves ≤ 1/3 of the exact build's bytes.
"""

from __future__ import annotations

import collections
import json
import re


# Collective kinds the async-window walker tracks (round 8: the
# analysis/program_audit passes reuse this walker for the zero1
# weight-update all-gather, so it is no longer permute-only).
ASYNC_COLLECTIVE_KINDS = (
    "collective-permute", "all-gather", "all-reduce", "reduce-scatter",
)
_KIND_ALT = "|".join(ASYNC_COLLECTIVE_KINDS)
_ASYNC_START_RE = re.compile(
    rf"%?(\S+) = .* ({_KIND_ALT})-start\(")
# A -done op closes the window its operand (the -start op) opened.  The
# operand list may spell the start's full tuple type inline
# (``collective-permute-done((f32[1066]{0:T(1024)}, ...) %cps.1)`` — the
# TPU backend does), so a lazy scan-to-first-paren mis-captures; instead
# the walker tokenizes everything after ``-done(`` and closes the first
# token that names an open window.
_ASYNC_DONE_RE = re.compile(rf"(?:{_KIND_ALT})-done\((.*)")
_NAME_TOKEN_RE = re.compile(r"%?([\w\.\-]+)")


def audit_schedule(hlo_text: str) -> dict:
    """Walk an optimized, scheduled HLO module; report per-async-window
    compute.  Returns a JSON-able summary dict.

    Tracks every async collective kind in :data:`ASYNC_COLLECTIVE_KINDS`
    (the ``-start``/``-done`` pairs); the legacy permute-only keys keep
    their meaning (``async_ppermute_pairs`` counts permute windows), and
    ``async_pairs_by_kind`` breaks all windows down per collective."""
    m = re.search(r"ENTRY [^\{]+\{(.*?)\n\}", hlo_text, re.S)
    if not m:
        raise ValueError("no ENTRY computation found in HLO text")
    compute_re = re.compile(
        r"%?(\S+) = .*?(fusion|convolution|dot|all-reduce(?!-)|"
        r"reduce-scatter(?!-))\("
    )
    open_pairs: dict[str, list] = {}
    open_kinds: dict[str, str] = {}
    in_flight, max_in_flight = 0, 0
    windows = []
    for line in m.group(1).splitlines():
        s = _ASYNC_START_RE.search(line)
        if s:
            open_pairs[s.group(1)] = []
            open_kinds[s.group(1)] = s.group(2)
            in_flight += 1
            max_in_flight = max(max_in_flight, in_flight)
            continue
        d = _ASYNC_DONE_RE.search(line)
        if d:
            name = next(
                (t for t in _NAME_TOKEN_RE.findall(d.group(1))
                 if t in open_pairs),
                None,
            )
            if name is not None:
                windows.append((name, open_kinds.pop(name),
                                open_pairs.pop(name)))
                in_flight -= 1
                continue
        c = compute_re.search(line)
        if c:
            for ops in open_pairs.values():
                ops.append((c.group(1), c.group(2)))
    # An op inside two concurrently-open windows counts once: the
    # metric is "distinct compute ops that execute under some in-flight
    # DMA", not a per-window tally.
    unique_ops = {name: kind for _, _, ops in windows for name, kind in ops}
    kinds = collections.Counter(unique_ops.values())
    permute = [w for w in windows if w[1] == "collective-permute"]
    return {
        "async_ppermute_pairs": len(permute),
        "pairs_with_compute_in_window": sum(
            1 for _, _, o in windows if o),
        "async_pairs_by_kind": dict(
            collections.Counter(k for _, k, _ in windows)),
        "pairs_with_compute_by_kind": dict(
            collections.Counter(k for _, k, o in windows if o)),
        "distinct_compute_ops_in_windows": len(unique_ops),
        "op_kinds_in_windows": dict(kinds),
        "max_concurrent_in_flight": max_in_flight,
    }


_SYNC_DEF_RE = re.compile(
    rf"%?([\w\.\-]+) = \(?\s*([a-z]+\d*\[[\d,]*\])[^=]*?"
    rf"\b({_KIND_ALT})(?!-start|-done)\(")


_GTE_RE = re.compile(
    r"%?([\w\.\-]+) = [^=]*get-tuple-element\([^%]*%([\w\.\-]+)\)"
)


def sync_collectives_from_hlo(hlo_text: str, kinds=None) -> list[dict]:
    """Every SYNC collective definition in the module — a collective
    issued without a ``-start``/``-done`` split sits on the critical
    path by construction (nothing can be scheduled under it).  Returns
    ``[{"name", "kind", "shape", "feeds_root"}]``; ``feeds_root`` is
    True when the op's result is a direct operand of its computation's
    ROOT — for a train step, the signature of a weight-update gather
    serialized against the step output (arxiv 2004.13336's target).
    Tuple-fused collectives (the TPU backend folds the gather into a
    variadic all-reduce whose elements reach ROOT via
    ``get-tuple-element``) are attributed through one GTE hop."""
    kinds = set(kinds or ASYNC_COLLECTIVE_KINDS)
    out = []
    root_operands: set[str] = set()
    gte_operand: dict[str, str] = {}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if stripped.startswith("ROOT "):
            root_operands.update(re.findall(r"%([\w\.\-]+)", stripped))
        g = _GTE_RE.search(line)
        if g:
            gte_operand[g.group(1)] = g.group(2)
        m = _SYNC_DEF_RE.search(line)
        if m and m.group(3) in kinds:
            out.append({"name": m.group(1), "kind": m.group(3),
                        "shape": m.group(2), "feeds_root": False})
    rooted = set(root_operands)
    rooted.update(op for gte, op in gte_operand.items()
                  if gte in root_operands)
    for rec in out:
        rec["feeds_root"] = rec["name"] in rooted
    return out


# HLO primitive-type widths (bytes) — the types a ring payload can carry
# (plus the widths the parser may meet in other programs' permutes).
_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

# A defining collective-permute line: ``%name = <shape> collective-permute(``
# or the async ``collective-permute-start(`` whose result is a tuple —
# group(1) grabs the FIRST shape either way, which for the start op is
# the operand buffer (counting the paired result buffer too would double
# every byte).  ``-done`` lines are uses of the start's buffers, skipped.
_CP_DEF_RE = re.compile(
    r"=\s*\(?\s*([a-z]+\d*\[[\d,]*\])[^=]*?\bcollective-permute"
    r"(?:-start)?\("
)

# The permute's routing table: ``source_target_pairs={{0,1},{1,2},...}``
# — the ground truth for attributing a compiled hop to a topology axis.
_PAIRS_RE = re.compile(r"source_target_pairs=\{((?:\{\d+,\d+\},?)*)\}")
_PAIR_RE = re.compile(r"\{(\d+),(\d+)\}")


def permute_pairs_from_line(line: str) -> list | None:
    """The ``source_target_pairs`` of one HLO line, or None."""
    m = _PAIRS_RE.search(line)
    if not m:
        return None
    return [(int(a), int(b)) for a, b in _PAIR_RE.findall(m.group(1))]


def _shape_bytes(shape: str) -> int:
    """``'f32[2,4]'`` → 32.  ``'f32[]'`` (scalar) → 4."""
    dtype, dims = shape.rstrip("]").split("[")
    if dtype not in _DTYPE_BYTES:
        raise ValueError(f"unknown HLO primitive type in {shape!r}")
    n = 1
    for d in filter(None, dims.split(",")):
        n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def wire_bytes_from_hlo(hlo_text: str, inner: int | None = None) -> dict:
    """Sum every collective-permute's operand bytes across the module.

    Walks ALL computations (not just ENTRY — a while-body ring on some
    backends hides the permutes one call deep) and counts each
    *defining* occurrence once.  Returns ``{"total_bytes", "count",
    "by_dtype": {prim: bytes}}``.

    ``inner`` (round 11): also attribute each permute's bytes to a
    topology axis from its compiled ``source_target_pairs`` routing
    (``ops.topology.classify_permute_pairs`` over inner-major blocks of
    that size — imported at call time so this module stays importable
    without jax, while compiled and static attribution share ONE
    classifier), adding ``"by_axis": {"inner": bytes, "outer": bytes}``
    — the per-axis number DML103 pins against the static
    ``ring_wire_bytes_by_axis`` accounting.  A permute with no routing
    table (never seen from the jax lowerings audited here) is charged
    to the outer axis: over-counting the bottleneck link is the safe
    direction."""
    if inner is not None:
        from distributed_machine_learning_tpu.ops.topology import (
            classify_permute_pairs,
        )
    total = 0
    count = 0
    by_dtype: dict[str, int] = {}
    by_axis = {"inner": 0, "outer": 0}
    for line in hlo_text.splitlines():
        m = _CP_DEF_RE.search(line)
        if not m:
            continue
        b = _shape_bytes(m.group(1))
        total += b
        count += 1
        prim = m.group(1).split("[")[0]
        by_dtype[prim] = by_dtype.get(prim, 0) + b
        if inner is not None:
            pairs = permute_pairs_from_line(line)
            axis = ("outer" if pairs is None
                    else classify_permute_pairs(pairs, inner))
            by_axis[axis] += b
    out = {"total_bytes": total, "count": count, "by_dtype": by_dtype}
    if inner is not None:
        out["by_axis"] = by_axis
    return out


# A computation header (``%name (params) -> result {`` / ``ENTRY %name ...``)
# and an all-reduce definition in any of its three spellings.
_COMPUTATION_RE = re.compile(r"^(ENTRY )?%?([\w\.\-]+) \(.*\{\s*$")
_ALL_REDUCE_DEF_RE = re.compile(
    r"^\s*(?:ROOT )?%?([\w\.\-]+) = (.*?)\ball-reduce(-start|-done)?\(")
_HLO_SHAPE_RE = re.compile(r"[a-z]+\d*\[[\d,]*\]")
_CALLS_RE = re.compile(r"calls=%?([\w\.\-]+)")
_INSTR_NAME_RE = re.compile(r"^\s*(?:ROOT )?%?([\w\.\-]+) = ")
# XLA:TPU's async-collective fusion spells one all-reduce three times:
# in the fused computation of an ``AsyncCollectiveStart`` custom call,
# in the ``async_collective_fusion.N`` body that steps it beside a
# matmul or an elementwise loop, and in the ``AsyncCollectiveDone`` one.
_ASYNC_FUSION_START = 'custom_call_target="AsyncCollectiveStart"'


def all_reduces_from_hlo(hlo_text: str) -> list[dict]:
    """Every all-reduce a compiled, scheduled module issues, once each:
    ``[{"name", "bytes", "async", "position"}]`` in schedule order.

    ``bytes`` is the result's size — every element of a tuple-shaped
    (combined) all-reduce counted.  ``async`` is True for an
    ``all-reduce-start``/``-done`` pair and for an async-collective
    fusion (counted at its start; the copies of the instruction inside
    the fusion's later steps are the same collective and are skipped);
    a plain ``all-reduce`` holds the core for its whole duration and is
    False.  ``position`` is the index in the ENTRY schedule of the
    instruction (or of the fusion that starts it) among its
    ``schedule_length`` instructions, None for one inside a loop body.
    """
    computations: dict[str, list[str]] = {}
    entry = current = None
    for line in hlo_text.splitlines():
        header = _COMPUTATION_RE.match(line)
        if header:
            current = header.group(2)
            computations[current] = []
            if header.group(1):
                entry = current
        elif line.startswith("}"):
            current = None
        elif current is not None:
            computations[current].append(line)
    if entry is None:
        raise ValueError("no ENTRY computation found in HLO text")
    fused = {callee for lines in computations.values() for line in lines
             if " fusion(" in line for callee in _CALLS_RE.findall(line)}
    # Where the ENTRY schedule holds an instruction, and where it calls a
    # computation.
    instruction_at: dict[str, int] = {}
    called_at: dict[str, int] = {}
    for i, line in enumerate(computations[entry]):
        for callee in _CALLS_RE.findall(line):
            called_at[callee] = i
        name = _INSTR_NAME_RE.match(line)
        if name:
            instruction_at[name.group(1)] = i
    out = []
    for comp, lines in computations.items():
        starts = any(_ASYNC_FUSION_START in line for line in lines)
        for line in lines:
            m = _ALL_REDUCE_DEF_RE.match(line)
            if not m or m.group(3) == "-done":
                continue
            if comp in fused and not starts:
                continue  # a later step of an async-collective fusion
            out.append({
                "name": m.group(1),
                "bytes": sum(_shape_bytes(s)
                             for s in _HLO_SHAPE_RE.findall(m.group(2))),
                "async": starts or m.group(3) == "-start",
                "position": (instruction_at.get(m.group(1))
                             if comp == entry else called_at.get(comp)),
                "schedule_length": len(computations[entry]),
            })
    out.sort(key=lambda r: (r["position"] is None, r["position"] or 0))
    return out


def grad_sync_bytes(rows: list[dict]) -> dict:
    """``{"grad_sync_bytes", "grad_sync_async_bytes"}`` of a compiled
    train step from its :func:`all_reduces_from_hlo` rows: bytes a chip
    all-reduces a step in all, and through asynchronous collectives."""
    return {
        "grad_sync_bytes": sum(r["bytes"] for r in rows),
        "grad_sync_async_bytes": sum(r["bytes"] for r in rows if r["async"]),
    }


def compile_ring_hlo(mesh, length: int, *, compress: str = "none",
                     topk_frac: float = 0.125,
                     bucket_bytes: int | None = None,
                     mean: bool = True,
                     topology: str | None = None,
                     hd_max_bytes: int | None = None,
                     codec_impl: str = "xla") -> str:
    """jit-compile a bare bucketed ring all-reduce over ``mesh`` and
    return the optimized HLO text — backend-agnostic (the CPU test mesh
    compiles the same collective-permute program shape the TPU target
    does), so the wire-byte audit can run in CI without libtpu.

    ``topology`` ("INNERxOUTER", round 11): compile the hierarchical
    plan instead — ``compress`` becomes the OUTER axis's codec (the CLI
    mapping) and ``hd_max_bytes`` caps the selector's halving-doubling
    admissibility (``None`` lets the round-20 cost model decide, 0
    pins every bucket to the ring plans, a large value admits
    halving-doubling for every bucket it wins).

    ``codec_impl`` (round 13): compile the int8 codec as the fused
    Pallas kernels (``"pallas"``) instead of the XLA ops — the DML103
    audit runs both and asserts the kernel build moves the exact same
    collective-permute bytes (the fusion must never change the wire)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from distributed_machine_learning_tpu.ops.ring import (
        DEFAULT_BUCKET_BYTES,
        get_wire_scheme,
        ring_all_reduce,
    )
    from distributed_machine_learning_tpu.runtime.mesh import (
        shard_map_no_check,
    )

    axis = mesh.axis_names[0]
    n = mesh.shape[axis]
    scheme = get_wire_scheme(compress, topk_frac=topk_frac,
                             codec_impl=codec_impl)
    topo = None
    if topology is not None:
        from distributed_machine_learning_tpu.ops.topology import (
            Topology,
            parse_topology,
        )

        inner, outer = parse_topology(topology)
        if inner * outer != n:
            raise ValueError(
                f"topology {topology!r} does not factor the mesh's "
                f"{n}-device axis"
            )
        topo = Topology(
            inner, outer, outer_scheme=compress, topk_frac=topk_frac,
            codec_impl=codec_impl, hd_max_bytes=hd_max_bytes,
        )

    def per_device(x):
        out = ring_all_reduce(
            x.reshape(-1), axis, n, mean=mean,
            bucket_bytes=(bucket_bytes if bucket_bytes is not None
                          else DEFAULT_BUCKET_BYTES),
            scheme=None if compress == "none" else scheme,
            topology=topo,
        )
        return out[None]

    fn = jax.jit(shard_map_no_check(
        per_device, mesh=mesh, in_specs=P(axis), out_specs=P(axis)
    ))
    x = jax.ShapeDtypeStruct((n, length), jnp.float32)
    return fn.lower(x).compile().as_text()


def _tpu_topology_mesh(topology_name: str):
    """8-chip AOT mesh for a named TPU topology (compile-only client).
    Sets ``TPU_SKIP_MDS_QUERY`` so libtpu skips the GCE-metadata probe
    that otherwise stalls the compile-only client for minutes off-GCE."""
    import os

    os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh

    topo = topologies.get_topology_desc(
        platform="tpu", topology_name=topology_name
    )
    devs = np.array(topo.devices)
    return Mesh(devs.reshape(devs.size), ("batch",))


def compile_zero1_hlo(mesh, global_batch: int = 256,
                      overlap: bool = True) -> dict:
    """Compile the zero1 train step for ``mesh`` (a CPU test mesh or a
    TPU AOT topology mesh) and return the optimized HLO text(s):
    ``{"update": ..., "gather": ...}`` for the overlap build,
    ``{"step": ...}`` for the sync baseline.  State shapes are built
    host-side (``flatten_padded`` + ``eval_shape``) so no device_put
    onto AOT devices is needed."""
    import jax
    import jax.numpy as jnp

    from distributed_machine_learning_tpu.cli.common import (
        init_model_and_state,
    )
    from distributed_machine_learning_tpu.models.vgg import VGGTest
    from distributed_machine_learning_tpu.parallel.fsdp import (
        flatten_padded,
    )
    from distributed_machine_learning_tpu.parallel.zero1 import (
        Zero1State,
        make_zero1_train_step,
    )

    axis = mesh.axis_names[0]
    n = mesh.shape[axis]
    model = VGGTest()
    st = init_model_and_state(model)
    flat, mom_flat, unravel, n_elems = flatten_padded(st, n)
    z1 = Zero1State(param_flat=flat, momentum_shards=mom_flat,
                    batch_stats=st.batch_stats, step=st.step, rng=st.rng,
                    config=st.config)
    zshape = jax.eval_shape(lambda: z1)
    x = jax.ShapeDtypeStruct((global_batch, 32, 32, 3), jnp.float32)
    y = jax.ShapeDtypeStruct((global_batch,), jnp.int32)
    step = make_zero1_train_step(model, mesh, unravel, n_elems,
                                 axis_name=axis, augment=False,
                                 overlap=overlap)
    if not overlap:
        return {"step": step.lower(zshape, x, y).compile().as_text()}
    upd = step.update_for(z1.config).lower(
        zshape.param_flat, zshape.momentum_shards, zshape.batch_stats,
        zshape.step, zshape.rng, x, y,
    ).compile().as_text()
    gat = step.gather_inner.lower(zshape.param_flat).compile().as_text()
    return {"update": upd, "gather": gat}


def zero1_overlap_audit(mesh, global_batch: int = 256) -> dict:
    """The ISSUE-9 acceptance audit, read off compiled artifacts:

    - sync baseline: the weight-update all-gather IS on the critical
      path (sync, feeding ROOT) — the 2004.13336 anti-pattern the
      overlap build exists to kill (on backends that rewrite the gather
      into an equivalent collective, that collective is reported);
    - overlap build, update program: contains NO all-gather (and no
      root-feeding collective of any kind) — the critical path ends at
      the updated shard;
    - overlap build, consume program: the bucketed ppermute ring; on
      backends with async collectives (the TPU AOT target) the hops
      must form non-empty async windows — DMAs with the other buckets'
      assembly scheduled under them, several concurrently in flight.
    """
    sync_hlo = compile_zero1_hlo(mesh, global_batch, overlap=False)["step"]
    ov = compile_zero1_hlo(mesh, global_batch, overlap=True)
    sync_colls = sync_collectives_from_hlo(sync_hlo)
    upd_colls = sync_collectives_from_hlo(ov["update"])
    upd_sched = audit_schedule(ov["update"])
    gat_sched = audit_schedule(ov["gather"])
    # The consume program must stay PERMUTE-CHAINED: sync permutes are
    # fine (the CPU backend emits them), but any non-permute collective
    # there is the gather re-serializing under a different op name, and
    # zero permutes at all means it regressed to a monolithic gather.
    gat_nonpermute = [c for c in sync_collectives_from_hlo(ov["gather"])
                      if c["kind"] != "collective-permute"]
    # wire_bytes_from_hlo counts every defining collective-permute,
    # sync AND -start forms, so it covers both backends' spellings.
    gat_permutes = wire_bytes_from_hlo(ov["gather"])["count"]
    pairs = gat_sched["async_pairs_by_kind"].get("collective-permute", 0)
    windows_nonempty = gat_sched["pairs_with_compute_by_kind"].get(
        "collective-permute", 0)
    return {
        "sync_build": {
            "critical_path_collectives": sync_colls,
            "gather_on_critical_path": any(
                c["feeds_root"] for c in sync_colls),
        },
        "overlap_build": {
            "update_all_gathers": [
                c for c in upd_colls if c["kind"] == "all-gather"],
            "update_root_feeding_collectives": [
                c for c in upd_colls if c["feeds_root"]],
            "update_schedule": upd_sched,
            "gather_sync_nonpermute_collectives": gat_nonpermute,
            "gather_permutes": gat_permutes,
            "gather_async_permute_pairs": pairs,
            "gather_windows_with_compute": windows_nonempty,
            "gather_max_in_flight": gat_sched["max_concurrent_in_flight"],
        },
        "passes": (
            not any(c["kind"] == "all-gather" for c in upd_colls)
            and not any(c["feeds_root"] for c in upd_colls)
            and not gat_nonpermute
            and gat_permutes > 0
            # Async windows are a property of backends that emit
            # -start/-done (TPU); on a sync-collective backend (CPU)
            # the structural checks above carry the gate.
            and (pairs == 0 or windows_nonempty > 0)
        ),
    }


def compile_part3_for_topology(topology_name: str = "v5e:2x4",
                               global_batch: int = 256,
                               ring_kwargs: dict | None = None) -> str:
    """AOT-compile the part3 ring train step (VGG-11+BN, 25 MB buckets)
    for a multi-chip TPU topology; return the optimized HLO text."""
    import jax
    import jax.numpy as jnp

    from distributed_machine_learning_tpu.cli.common import (
        init_model_and_state,
    )
    from distributed_machine_learning_tpu.models.vgg import VGG11
    from distributed_machine_learning_tpu.parallel.strategies import (
        get_strategy,
    )
    from distributed_machine_learning_tpu.train.step import make_train_step

    mesh = _tpu_topology_mesh(topology_name)
    model = VGG11(use_bn=True, compute_dtype=jnp.bfloat16)
    state_shape = jax.eval_shape(lambda: init_model_and_state(model))
    x = jax.ShapeDtypeStruct((global_batch, 32, 32, 3), jnp.float32)
    y = jax.ShapeDtypeStruct((global_batch,), jnp.int32)
    strategy = get_strategy("ring", **(ring_kwargs or {}))
    step = make_train_step(model, strategy, mesh=mesh)
    if getattr(strategy, "stateful", False):
        # Error-feedback strategies thread a residual pytree; lower the
        # inner 4-ary program with a zero-state shape struct.
        res = jax.eval_shape(
            lambda: step.fresh_sync_state(state_shape.params)
        )
        return step.inner.lower(state_shape, x, y, res).compile().as_text()
    return step.lower(state_shape, x, y).compile().as_text()


def wire_bytes_main(topology_name: str = "v5e:2x4",
                    global_batch: int = 256) -> dict:
    """Compile the part3 step exact and int8 for the TPU topology, sum
    each build's collective-permute bytes, and assert the compressed
    build moves ≤ 1/3 of the exact build's bytes."""
    exact = wire_bytes_from_hlo(
        compile_part3_for_topology(topology_name, global_batch)
    )
    int8 = wire_bytes_from_hlo(
        compile_part3_for_topology(
            topology_name, global_batch, ring_kwargs={"compress": "int8"}
        )
    )
    ratio = (int8["total_bytes"] / exact["total_bytes"]
             if exact["total_bytes"] else float("nan"))
    return {
        "metric": f"ring_wire_bytes_{topology_name.replace(':', '_')}",
        "exact": exact,
        "int8": int8,
        "int8_over_exact": ratio,
        "passes_leq_one_third": ratio <= 1 / 3,
    }


def main(argv=None) -> None:
    from distributed_machine_learning_tpu.runtime.compile_cache import (
        configure_compile_cache,
    )

    configure_compile_cache()
    import argparse
    import sys

    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--topology", default="v5e:2x4")
    parser.add_argument("--global-batch", default=256, type=int)
    parser.add_argument("--wire-bytes", action="store_true",
                        help="audit collective-permute payload bytes "
                             "(exact vs int8 ring) instead of the "
                             "overlap schedule; exits non-zero unless "
                             "the int8 build moves <= 1/3 of the exact "
                             "build's bytes")
    parser.add_argument("--zero1", action="store_true",
                        help="audit the overlap-aware zero1 weight "
                             "update (ISSUE 9): sync baseline's gather "
                             "on the critical path vs the overlap "
                             "build's shard-terminated update program "
                             "+ bucketed-ring consume program; exits "
                             "non-zero unless the overlap build "
                             "passes")
    parser.add_argument("--cpu-mesh", action="store_true",
                        help="with --zero1: audit against the local "
                             "8-device CPU mesh (structural checks "
                             "only — XLA:CPU emits sync collectives) "
                             "instead of the TPU AOT topology")
    args = parser.parse_args(argv)
    if args.wire_bytes:
        summary = wire_bytes_main(args.topology, args.global_batch)
        print(json.dumps(summary))
        if not summary["passes_leq_one_third"]:
            sys.exit(1)
        return
    if args.zero1:
        if args.cpu_mesh:
            from distributed_machine_learning_tpu.runtime.mesh import (
                ensure_host_devices,
                make_mesh,
            )

            ensure_host_devices(8)
            mesh = make_mesh(8)
        else:
            mesh = _tpu_topology_mesh(args.topology)
        summary = zero1_overlap_audit(mesh, args.global_batch)
        summary["metric"] = (
            f"zero1_overlap_audit_"
            f"{'cpu8' if args.cpu_mesh else args.topology.replace(':', '_')}"
        )
        print(json.dumps(summary))
        if not summary["passes"]:
            sys.exit(1)
        return
    summary = audit_schedule(
        compile_part3_for_topology(args.topology, args.global_batch)
    )
    summary["metric"] = (
        f"ring_overlap_audit_{args.topology.replace(':', '_')}"
    )
    print(json.dumps(summary))


if __name__ == "__main__":
    main()
