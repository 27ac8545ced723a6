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
use) and walks the optimized module's schedule with ``ops/hlo.py``'s
text walkers (:func:`~distributed_machine_learning_tpu.ops.hlo.audit_schedule`).

Run: ``python -m distributed_machine_learning_tpu.analysis.overlap_audit``
(needs libtpu for the compile-only TPU client; prints one JSON line).

This is a static schedule, not a device timeline: it proves the
executable *orders* bucket math under bucket DMAs, while actual wall-
clock hiding additionally depends on DMA latency vs fusion runtime —
the part a pod xprof would add.

**Wire-byte audit** (round 7, ``--wire-bytes``): the compressed ring
(``ops/ring.py`` wire schemes) claims ~4x fewer bytes per hop for the
int8 codec.  ``ops.hlo.wire_bytes_from_hlo`` reads the claim off the
COMPILED program, so the reduction is verified in the artifact that
runs, not assumed from the source; ``--wire-bytes`` compiles the part3
step exact and int8 and asserts the compressed build moves ≤ 1/3 of the
exact build's bytes.

This module builds and compiles programs (it imports ``cli/``,
``parallel/``, ``models/`` and ``train/`` inside its ``compile_*``
functions), so it sits with the audits, above the library; the walkers
it and ``train/lm_step.py`` share sit below, in ``ops/hlo.py``.
"""

from __future__ import annotations

import json

from distributed_machine_learning_tpu.ops.hlo import (
    audit_schedule,
    sync_collectives_from_hlo,
    wire_bytes_from_hlo,
)


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
