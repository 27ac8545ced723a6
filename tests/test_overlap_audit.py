"""Schedule-walker unit tests for the ring overlap audit
(ops/hlo.py's walkers, analysis/overlap_audit.py's compiles); the TPU AOT compile itself is exercised by
the audit's __main__ on TPU-capable hosts.  The wire-byte audit
(--wire-bytes) additionally gets a REAL compile check here: the CPU
backend names collective-permute identically, so the int8-vs-exact
byte ratio is asserted against actual compiled executables in CI."""

import pytest

from distributed_machine_learning_tpu.analysis.overlap_audit import (
    compile_ring_hlo,
)
from distributed_machine_learning_tpu.ops.hlo import (
    audit_schedule,
    wire_bytes_from_hlo,
)

HLO = """\
HloModule m

ENTRY main {
  p0 = f32[8]{0} parameter(0)
  cps.1 = (f32[8]{0}, f32[8]{0}) collective-permute-start(p0), source_target_pairs={{0,1}}
  f.1 = f32[8]{0} fusion(p0), kind=kLoop, calls=fused_add
  cpd.1 = f32[8]{0} collective-permute-done(cps.1)
  cps.2 = (f32[8]{0}, f32[8]{0}) collective-permute-start(cpd.1), source_target_pairs={{0,1}}
  cpd.2 = f32[8]{0} collective-permute-done(cps.2)
  ROOT r = f32[8]{0} add(cpd.1, cpd.2)
}
"""


def test_audit_counts_windows_and_overlap():
    s = audit_schedule(HLO)
    assert s["async_ppermute_pairs"] == 2
    assert s["pairs_with_compute_in_window"] == 1  # f.1 inside window 1
    assert s["distinct_compute_ops_in_windows"] == 1
    assert s["op_kinds_in_windows"] == {"fusion": 1}
    assert s["max_concurrent_in_flight"] == 1


def test_audit_rejects_entryless_text():
    with pytest.raises(ValueError, match="ENTRY"):
        audit_schedule("HloModule empty")


WIRE_HLO = """\
HloModule m

ENTRY main {
  p0 = f32[64]{0} parameter(0)
  q = s8[64]{0} convert(p0)
  cp.1 = s8[64]{0} collective-permute(q), source_target_pairs={{0,1}}
  s = f32[1]{0} constant({1.0})
  cp.2 = f32[1]{0} collective-permute(s), source_target_pairs={{0,1}}
  cps.1 = (f32[2,8]{1,0}, f32[2,8]{1,0}) collective-permute-start(p0), source_target_pairs={{0,1}}
  cpd.1 = f32[2,8]{1,0} collective-permute-done(cps.1)
  ROOT r = f32[64]{0} convert(cp.1)
}
"""


def test_wire_bytes_parser_counts_defs_once():
    """Sync and async forms both count; a start's tuple result counts
    the operand buffer only (not the paired result buffer), and -done
    lines are uses, never double-counted."""
    got = wire_bytes_from_hlo(WIRE_HLO)
    assert got["count"] == 3
    # s8[64]=64B + f32[1]=4B + first tuple element f32[2,8]=64B
    assert got["total_bytes"] == 64 + 4 + 64
    assert got["by_dtype"] == {"s8": 64, "f32": 68}


AXIS_HLO = """\
HloModule m

ENTRY main {
  p0 = f32[8]{0} parameter(0)
  cp.in = f32[8]{0} collective-permute(p0), source_target_pairs={{0,1},{1,0},{2,3},{3,2}}
  cp.out = s8[8]{0} collective-permute(p0), source_target_pairs={{0,1},{1,2},{2,3},{3,0}}
  cp.bare = f32[2]{0} collective-permute(p0)
  ROOT r = f32[8]{0} add(cp.in, cp.in)
}
"""


def test_wire_bytes_by_axis_from_routing_tables():
    """Inner-major blocks of 2: a permute whose every pair stays inside a
    block rides the inner links; one with ANY cross-block pair, or with
    no routing table at all, is charged to the outer (bottleneck) axis."""
    from distributed_machine_learning_tpu.ops.hlo import (
        permute_pairs_from_line,
    )

    got = wire_bytes_from_hlo(AXIS_HLO, inner=2)
    assert got["by_axis"] == {"inner": 32, "outer": 8 + 8}
    assert got["total_bytes"] == 48 and got["count"] == 3
    assert "by_axis" not in wire_bytes_from_hlo(AXIS_HLO)
    assert permute_pairs_from_line(
        "x = f32[1] collective-permute(y), source_target_pairs={{0,1},{3,2}}"
    ) == [(0, 1), (3, 2)]
    assert permute_pairs_from_line("x = f32[1] add(y, y)") is None


@pytest.mark.parametrize("shape, nbytes", [
    ("f32[2,4]", 32), ("f32[]", 4), ("bf16[3]", 6), ("pred[5]", 5),
])
def test_shape_bytes(shape, nbytes):
    from distributed_machine_learning_tpu.ops.hlo import _shape_bytes

    assert _shape_bytes(shape) == nbytes


def test_shape_bytes_refuses_an_unknown_type():
    """A width the table lacks must not count as zero bytes."""
    with pytest.raises(ValueError, match="unknown HLO primitive type"):
        wire_bytes_from_hlo(
            "ENTRY e {\n  c = s4[8]{0} collective-permute(p)\n}")


def test_wire_bytes_parser_empty_module():
    got = wire_bytes_from_hlo("HloModule m\nENTRY main { ROOT r = f32[] constant(0) }")
    assert got == {"total_bytes": 0, "count": 0, "by_dtype": {}}


TPU_STYLE_ASYNC_HLO = """\
HloModule m

ENTRY main {
  p0 = f32[1066]{0} parameter(0)
  collective-permute-start = (f32[1066]{0:T(1024)}, f32[1066]{0:T(1024)}, u32[]{:S(2)}, u32[]{:S(2)}) collective-permute-start(p0), source_target_pairs={{0,1}}
  f.1 = f32[8,1066]{1,0} fusion(p0), kind=kLoop, calls=fused_dus
  collective-permute-done = f32[1066]{0:T(1024)} collective-permute-done((f32[1066]{0:T(1024)}, f32[1066]{0:T(1024)}, u32[]{:S(2)}, u32[]{:S(2)}) %collective-permute-start)
  ROOT r = f32[1066]{0} add(collective-permute-done, p0)
}
"""


def test_audit_closes_tuple_typed_done_windows():
    """The TPU backend spells the -done operand's full tuple type
    inline (``...-done((f32[...]{0:T(1024)}, ...) %start)``); the
    walker must still close the window — a lazy scan-to-first-paren
    used to mis-capture ``1024`` and leave every window open (so
    max_in_flight counted starts, never overlap)."""
    s = audit_schedule(TPU_STYLE_ASYNC_HLO)
    assert s["async_ppermute_pairs"] == 1
    assert s["pairs_with_compute_in_window"] == 1
    assert s["max_concurrent_in_flight"] == 1


GTE_ROOT_HLO = """\
HloModule m

ENTRY main {
  p0 = f32[1066]{0} parameter(0)
  ar = (f32[8528]{0}, f32[]) all-reduce(p0, p0), replica_groups={{0,1}}, to_apply=add
  gte0 = f32[8528]{0} get-tuple-element((f32[8528]{0}, f32[]) %ar), index=0
  ROOT r = (f32[8528]{0}) tuple(%gte0)
}
"""


def test_sync_collectives_feed_root_through_gte():
    """Tuple-fused collectives (the TPU backend folds the zero1 gather
    into a variadic all-reduce) reach ROOT via get-tuple-element; the
    feeds_root attribution must see through one GTE hop, or the sync
    baseline's critical-path collective reads as innocent."""
    from distributed_machine_learning_tpu.ops.hlo import (
        sync_collectives_from_hlo,
    )

    recs = sync_collectives_from_hlo(GTE_ROOT_HLO)
    assert len(recs) == 1
    assert recs[0]["kind"] == "all-reduce"
    assert recs[0]["feeds_root"] is True


def test_zero1_overlap_audit_ci_regression(mesh8):
    """The ISSUE-9 acceptance gate, on real compiled executables (CPU
    mesh — structural checks): the sync baseline's weight-update
    all-gather IS on the critical path feeding ROOT (the 2004.13336
    anti-pattern), and the overlap build kills it — the update program
    contains no all-gather and no root-feeding collective of any kind;
    the consume program is permute-only.  A future change that
    re-serializes the gather fails here."""
    from distributed_machine_learning_tpu.analysis.overlap_audit import (
        zero1_overlap_audit,
    )

    summary = zero1_overlap_audit(mesh8, global_batch=16)
    assert summary["sync_build"]["gather_on_critical_path"], (
        "the sync baseline must still exhibit the anti-pattern the "
        "overlap build is measured against"
    )
    ov = summary["overlap_build"]
    assert ov["update_all_gathers"] == []
    assert ov["update_root_feeding_collectives"] == []
    # The consume program is permute-chained: a regression back to one
    # monolithic all-gather shows up as zero permutes and/or a
    # non-permute collective, and must fail the gate.
    assert ov["gather_sync_nonpermute_collectives"] == []
    assert ov["gather_permutes"] > 0
    assert summary["passes"], summary


def test_ring_all_gather_bitwise_and_bucketed(mesh8):
    """The consume-phase primitive: the bucketed ppermute ring gather
    is bit-identical to ``lax.all_gather(tiled=True)`` for every bucket
    count (pure data movement — the overlap builds' parity rests on
    this), and compiles to (N−1)·buckets permutes."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from distributed_machine_learning_tpu.ops.ring import (
        ring_all_gather_flat,
    )
    from distributed_machine_learning_tpu.runtime.mesh import (
        shard_map_no_check,
    )

    x = np.random.default_rng(0).normal(size=(8, 97)).astype(np.float32)
    ref = jax.jit(shard_map_no_check(
        lambda s: lax.all_gather(s.reshape(-1), "batch", tiled=True)[None],
        mesh=mesh8, in_specs=P("batch"), out_specs=P("batch")))(x)
    for k in (1, 3, 4):
        fn = jax.jit(shard_map_no_check(
            lambda s, k=k: ring_all_gather_flat(
                s.reshape(-1), "batch", 8, n_buckets=k)[None],
            mesh=mesh8, in_specs=P("batch"), out_specs=P("batch")))
        np.testing.assert_array_equal(np.asarray(fn(x)), np.asarray(ref))
        hlo = fn.lower(
            jax.ShapeDtypeStruct((8, 97), jnp.float32)
        ).compile().as_text()
        permutes = wire_bytes_from_hlo(hlo)["count"]
        assert permutes == 7 * k, (k, permutes)


def test_hier_wire_bytes_per_axis_ci_regression(mesh8):
    """The round-11 acceptance gate, read off COMPILED executables:

    1. per-axis attribution: every permute's ``source_target_pairs``
       routing classifies to the inner/outer axis, and the compiled
       per-axis bytes equal the static ``ring_wire_bytes_by_axis``
       accounting for none/int8/topk — the labeled telemetry counters
       and the executable can never drift apart silently;
    2. the inter-node reduction: the exact hierarchical build's
       OUTER-axis bytes are ≤ (1/inner + 5%) of the exact FLAT ring's
       total, for both 2x4 and 4x2 factorizations of the 8-mesh.
    """
    from distributed_machine_learning_tpu.ops.ring import (
        ring_wire_bytes,
        ring_wire_bytes_by_axis,
    )
    from distributed_machine_learning_tpu.ops.topology import Topology

    length, bb = 4096, 8192
    flat_total = wire_bytes_from_hlo(
        compile_ring_hlo(mesh8, length, bucket_bytes=bb)
    )["total_bytes"]
    assert flat_total == ring_wire_bytes(length, 8, bucket_bytes=bb)
    for inner, outer in ((2, 4), (4, 2)):
        spec = f"{inner}x{outer}"
        for compress in ("none", "int8", "topk"):
            got = wire_bytes_from_hlo(
                compile_ring_hlo(mesh8, length, compress=compress,
                                 bucket_bytes=bb, topology=spec,
                                 hd_max_bytes=0),
                inner=inner,
            )
            topo = Topology(inner, outer, outer_scheme=compress,
                            hd_max_bytes=0)
            want = ring_wire_bytes_by_axis(
                length, 8, bucket_bytes=bb, topology=topo)
            assert got["by_axis"] == want, (spec, compress, got, want)
            if compress == "none":
                bound = (1.0 / inner + 0.05) * flat_total
                assert got["by_axis"]["outer"] <= bound, (
                    spec, got["by_axis"], flat_total)


def test_hd_wire_bytes_attribution(mesh8):
    """The halving-doubling path's compiled permutes attribute by
    exchange distance: distance-1 exchanges stay intra-node on a
    2-wide inner axis, distances 2 and 4 cross — and the compiled
    per-axis bytes equal the static accounting."""
    from distributed_machine_learning_tpu.ops.ring import (
        ring_wire_bytes_by_axis,
    )
    from distributed_machine_learning_tpu.ops.topology import Topology

    hlo = compile_ring_hlo(mesh8, 256, bucket_bytes=8192, topology="2x4",
                           hd_max_bytes=1 << 30)
    got = wire_bytes_from_hlo(hlo, inner=2)
    topo = Topology(2, 4, hd_max_bytes=1 << 30)
    want = ring_wire_bytes_by_axis(256, 8, bucket_bytes=8192,
                                   topology=topo)
    assert got["by_axis"] == want
    assert got["by_axis"]["inner"] > 0 and got["by_axis"]["outer"] > 0
    # 2·log2(8) = 6 exchange steps, each one ppermute.
    assert got["count"] == 6


def test_predicted_plan_bytes_match_hlo_audit(mesh8):
    """Round-20 acceptance: ``Topology.select`` is PREDICTION-driven
    (no ``hd_max_bytes`` override anywhere here), and the plan the cost
    model picks prices exactly the bytes the compiled executable moves:
    for 2x4/4x2 × {none,int8,topk}, the per-axis payloads of
    ``plan_hops`` under the selected plan equal the per-axis bytes the
    DML103 HLO walker reads off ``source_target_pairs`` — the link
    model can never cost a different program than the one that runs."""
    from distributed_machine_learning_tpu.ops.ring import (
        ring_wire_bytes_by_axis,
    )
    from distributed_machine_learning_tpu.ops.topology import Topology

    length, bb = 4096, 8192  # two 8 KiB buckets
    for inner, outer in ((2, 4), (4, 2)):
        for compress in ("none", "int8", "topk"):
            topo = Topology(inner, outer, outer_scheme=compress)
            plan = topo.select(bb)
            # The cost model's regime split at this bucket size: exact
            # 8 KiB buckets sit below both topologies' hd/hier
            # crossovers (latency path); a requested codec forbids hd
            # above the fidelity bound (hier keeps the codec).
            assert plan == ("hd" if compress == "none" else "hier"), (
                inner, outer, compress, plan)
            priced = {"inner": 0, "outer": 0}
            for axis, _dist, nbytes in topo.plan_hops(bb, plan):
                priced[axis] += nbytes
            priced = {k: 2 * v for k, v in priced.items()}  # two buckets
            got = wire_bytes_from_hlo(
                compile_ring_hlo(mesh8, length, compress=compress,
                                 bucket_bytes=bb,
                                 topology=f"{inner}x{outer}"),
                inner=inner,
            )
            assert got["by_axis"] == priced, (
                inner, outer, compress, plan, got["by_axis"], priced)
            # And the static telemetry accounting dispatches through
            # the SAME selector, so all three agree.
            assert priced == ring_wire_bytes_by_axis(
                length, 8, bucket_bytes=bb, topology=topo)


def test_wire_bytes_ci_regression_int8_vs_exact(mesh8):
    """The fast CI gate (ISSUE 7 satellite): compile a real bucketed
    ring for the 8-device mesh, exact and int8, and assert the
    compressed executable moves ≤ 1/3 of the exact one's
    collective-permute bytes — read from the compiled programs, so a
    regression that silently decompresses the wire fails here."""
    from distributed_machine_learning_tpu.ops.ring import ring_wire_bytes
    from distributed_machine_learning_tpu.ops.ring import get_wire_scheme

    length = 4096
    exact = wire_bytes_from_hlo(
        compile_ring_hlo(mesh8, length, bucket_bytes=8192)
    )
    int8 = wire_bytes_from_hlo(
        compile_ring_hlo(mesh8, length, compress="int8", bucket_bytes=8192)
    )
    assert exact["count"] > 0 and int8["count"] > 0
    assert int8["total_bytes"] * 3 <= exact["total_bytes"]
    # The compiled programs' byte totals match the static accounting the
    # telemetry counter uses — the two can never drift apart silently.
    assert exact["total_bytes"] == ring_wire_bytes(
        length, 8, bucket_bytes=8192
    )
    assert int8["total_bytes"] == ring_wire_bytes(
        length, 8, bucket_bytes=8192, scheme=get_wire_scheme("int8")
    )
