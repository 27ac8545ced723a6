"""Compressed-ring weak-scaling bench: wire bytes, step tails, parity.

Measures the round-7 tentpole (``ops/ring.py`` wire schemes +
``parallel/strategies.py::RingAllReduce`` error feedback) three ways,
per world size and codec:

- **wire bytes/step** — the static accounting
  (``ring_wire_bytes``; the HLO audit in ``overlap_audit.py
  --wire-bytes`` verifies the same number against the compiled
  program's collective-permute shapes);
- **step time p50/p95** — the mandatory-tail protocol (PERF.md round-6
  mandate).  NOTE on CPU hosts the ppermute "wire" is a memcpy, so
  compression costs compute and saves nothing — the honest reading of
  a CPU row is *overhead of the codec*, while the byte column is the
  bandwidth win an ICI-bound pod realizes;
- **loss parity** — final-loss relative delta vs the exact ring over
  the same fixed-seed synthetic batch stream (error feedback on).

Weak scaling: per-device batch is FIXED (default 16); the global batch
grows with the world, the reference's scaling protocol.

**Topology sweep** (round 11, ``--topology``): every entry beyond
``flat`` reruns the matrix through the topology-aware hierarchical
ring (``ops/topology.py``) — rows gain the per-axis wire split
(``wire_bytes_by_axis``: the inter-node reduction is the point) and
the auto-selector's chosen ``plan`` for the gradient's bucket (exact
small gradients ride the halving-doubling latency path; compressed
ones the hierarchical ring).  The flat rows are the selector's
baseline: the acceptance bar is auto-selected p50 ≤ flat p50.

Run:  python -m distributed_machine_learning_tpu.bench.ring_compress \
          [--worlds 2,4,8] [--iters 24] [--model vggtest] \
          [--topology flat,2x4,4x2] [--json out]
"""

from __future__ import annotations

import argparse
import json
import time


def bench_ring_compress(worlds=(2, 4, 8), iters: int = 24,
                        per_device_batch: int = 16,
                        model_name: str = "vggtest",
                        topk_frac: float = 0.125,
                        bucket_mb: int = 25,
                        topologies=("flat",),
                        modeled_network: bool = False) -> list[dict]:
    import jax
    import numpy as np

    from distributed_machine_learning_tpu.cli.common import (
        SEED,
        init_model_and_state,
    )
    from distributed_machine_learning_tpu.models.registry import get_model
    from distributed_machine_learning_tpu.ops.ring import WIRE_SCHEMES
    from distributed_machine_learning_tpu.parallel.strategies import (
        get_strategy,
    )
    from distributed_machine_learning_tpu.runtime.mesh import make_mesh
    from distributed_machine_learning_tpu.train.sgd import SGDConfig
    from distributed_machine_learning_tpu.train.step import (
        make_train_step,
        shard_batch,
    )
    from distributed_machine_learning_tpu.utils.timing import (
        percentile_stats,
    )

    model = get_model(model_name, use_bn=False)
    rows = []
    for world in worlds:
        if world > jax.device_count():
            continue
        mesh = make_mesh(world)
        B = per_device_batch * world
        rng = np.random.default_rng(SEED)
        batches = [
            (rng.integers(0, 256, (B, 32, 32, 3), dtype=np.uint8),
             rng.integers(0, 10, B).astype(np.int32))
            for _ in range(iters)
        ]
        final_exact = None
        for topology in topologies:
            if topology != "flat":
                from distributed_machine_learning_tpu.ops.topology import (
                    parse_topology,
                )

                ti, to = parse_topology(topology)
                if ti * to != world:
                    continue  # this spec does not factor this world
            for compress in WIRE_SCHEMES:  # "none" first: parity anchor
                kwargs = {"bucket_bytes": bucket_mb * 2**20}
                if compress != "none":
                    kwargs.update(compress=compress, topk_frac=topk_frac)
                if topology != "flat":
                    kwargs["topology"] = topology
                strategy = get_strategy("ring", **kwargs)
                state = init_model_and_state(
                    model,
                    config=SGDConfig(learning_rate=0.1, weight_decay=0.0),
                )
                n_elems = sum(
                    int(l.size)
                    for l in jax.tree_util.tree_leaves(state.params)
                )
                step = make_train_step(model, strategy, mesh=mesh,
                                       augment=False)
                times = []
                loss = None
                for i, (x, y) in enumerate(batches):
                    xs, ys = shard_batch(mesh, x, y)
                    t0 = time.perf_counter()
                    state, loss = step(state, xs, ys)
                    loss = jax.block_until_ready(loss)
                    if i > 0:  # iteration 0 holds the compile
                        times.append(time.perf_counter() - t0)
                final = float(loss)
                if compress == "none" and final_exact is None:
                    # Parity anchor: the flat exact ring when 'flat'
                    # leads the sweep (the default), else the first
                    # exact plan — exact plans differ only by
                    # association order, so the column stays meaningful
                    # when a rerun sweeps topologies alone.
                    final_exact = final
                stats = percentile_stats(times)
                topo = strategy.topology_for(world)
                if topo is None:
                    plan = "flat"
                else:
                    # Per-BUCKET, matching the dispatch that actually
                    # runs (a multi-bucket gradient can mix plans, e.g.
                    # a small tail bucket riding hd): unique plans in
                    # bucket order, joined.
                    from distributed_machine_learning_tpu.ops.ring import (
                        _bucket_bounds,
                    )

                    plans = []
                    for b0, b1 in _bucket_bounds(
                        n_elems, bucket_mb * 2**20, 4
                    ):
                        p = topo.select((b1 - b0) * 4)
                        if p not in plans:
                            plans.append(p)
                    plan = "+".join(plans)
                row = {
                    "world": world,
                    "global_batch": B,
                    "topology": topology,
                    "compress": compress,
                    "error_feedback": getattr(strategy, "stateful",
                                              False),
                    "wire_bytes_per_step": strategy.wire_bytes_per_step(
                        n_elems, world
                    ),
                    "wire_bytes_by_axis": strategy.wire_bytes_by_axis(
                        n_elems, world
                    ),
                    "plan": plan,
                    "compression_ratio": strategy.compression_ratio(
                        n_elems, world
                    ),
                    "iter_p50_s": stats["p50"],
                    "iter_p95_s": stats["p95"],
                    "final_loss": final,
                    "final_loss_rel_delta_vs_exact": (
                        None if final_exact is None
                        else abs(final - final_exact)
                        / max(abs(final_exact), 1e-30)
                    ),
                }
                if modeled_network:
                    # The pod claim, priced instead of measured: seconds
                    # one bucketed all-reduce costs under the calibrated
                    # LinkModel (round 20) — the number the CPU rows
                    # cannot show because their ppermute "wire" is a
                    # memcpy.  Topology rows ride the selector's own
                    # cost model; a flat ring on a multi-node pod is
                    # topology-unaware, so every hop is priced at the
                    # inter-node link (Topology._flat_axis).
                    from distributed_machine_learning_tpu.ops.ring import (
                        _bucket_bounds,
                    )
                    from distributed_machine_learning_tpu.ops.topology import (  # noqa: E501
                        DEFAULT_LINK_MODEL,
                        Topology,
                        predict_all_reduce_time,
                    )

                    if topo is not None:
                        modeled = predict_all_reduce_time(
                            n_elems, topo, bucket_mb * 2**20)
                    else:
                        pod = Topology(
                            inner=1, outer=world,
                            outer_scheme=compress, topk_frac=topk_frac)
                        modeled = sum(
                            pod.predict_bucket_time(
                                (b1 - b0) * 4, plan="flat",
                                link=DEFAULT_LINK_MODEL)
                            for b0, b1 in _bucket_bounds(
                                n_elems, bucket_mb * 2**20, 4))
                    row["modeled_pod_step_s"] = modeled
                rows.append(row)
                print(json.dumps(row))
    return rows


def bench_selector_ab(world: int = 8, topology: str = "2x4",
                      iters: int = 60, per_device_batch: int = 16,
                      model_name: str = "vggtest") -> list[dict]:
    """The selector acceptance instrument: INTERLEAVED A/B of the flat
    ring vs the selector's plans (hd for the small exact bucket, hier
    with the codec) on the SAME batch stream — the shared protocol of
    ``bench/harness.py::interleaved_ab`` (one iteration of each config
    per round, so the 1-core host's ±5% sequential drift cancels
    instead of masquerading as a plan cost; the PR-9 overlap bench's
    protocol).  The bar: neither selected plan slower than flat at
    p50."""
    import dataclasses

    import jax
    import numpy as np

    from distributed_machine_learning_tpu.bench.harness import (
        interleaved_ab,
    )
    from distributed_machine_learning_tpu.cli.common import (
        SEED,
        init_model_and_state,
    )
    from distributed_machine_learning_tpu.models.registry import get_model
    from distributed_machine_learning_tpu.parallel.strategies import (
        RingAllReduce,
        get_strategy,
    )
    from distributed_machine_learning_tpu.runtime.mesh import make_mesh
    from distributed_machine_learning_tpu.train.sgd import SGDConfig
    from distributed_machine_learning_tpu.train.step import (
        make_train_step,
        shard_batch,
    )
    from distributed_machine_learning_tpu.utils.timing import (
        percentile_stats,
    )

    class _HierOnly(RingAllReduce):
        """The topology strategy with the hd path pinned off — isolates
        the hierarchical plan in the A/B (the selector would route the
        small exact bucket to hd)."""

        def topology_for(self, axis_size):
            topo = super().topology_for(axis_size)
            return (None if topo is None
                    else dataclasses.replace(topo, hd_max_bytes=0))

    mesh = make_mesh(world)
    model = get_model(model_name, use_bn=False)
    rng = np.random.default_rng(SEED)
    B = per_device_batch * world
    batches = [
        (rng.integers(0, 256, (B, 32, 32, 3), dtype=np.uint8),
         rng.integers(0, 10, B).astype(np.int32))
        for _ in range(4)
    ]
    configs = {
        "flat": get_strategy("ring"),
        "auto_hd": get_strategy("ring", topology=topology),
        "auto_hier_int8": get_strategy("ring", compress="int8",
                                       topology=topology),
        "hier_exact": _HierOnly(topology=topology),
    }
    steps, states = {}, {}
    for k, strat in configs.items():
        states[k] = init_model_and_state(
            model, config=SGDConfig(learning_rate=0.1, weight_decay=0.0)
        )
        steps[k] = make_train_step(model, strat, mesh=mesh, augment=False)

    def one_iter(k):
        def run(rep):
            xs, ys = shard_batch(mesh, *batches[rep % len(batches)])
            states[k], loss = steps[k](states[k], xs, ys)
            jax.block_until_ready(loss)
        return run

    times = interleaved_ab({k: one_iter(k) for k in configs}, iters,
                           warmup=1)
    rows = []
    flat_p50 = percentile_stats(times["flat"])["p50"]
    for k, ts in times.items():
        stats = percentile_stats(ts)
        topo = configs[k].topology_for(world)
        n_elems = sum(
            int(l.size)
            for l in jax.tree_util.tree_leaves(states[k].params)
        )
        rows.append({
            "bench": "selector_ab",
            "world": world,
            "config": k,
            "plan": ("flat" if topo is None
                     else topo.select(n_elems * 4)),
            "iter_p50_s": stats["p50"],
            "iter_p95_s": stats["p95"],
            "p50_vs_flat": stats["p50"] / flat_p50 - 1.0,
        })
        print(json.dumps(rows[-1]))
    return rows


def main(argv=None) -> None:
    from distributed_machine_learning_tpu.runtime.compile_cache import (
        configure_compile_cache,
    )

    configure_compile_cache()
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--worlds", default="2,4,8")
    parser.add_argument("--iters", default=24, type=int)
    parser.add_argument("--batch-size", default=16, type=int,
                        help="PER-DEVICE batch (weak scaling)")
    parser.add_argument("--model", default="vggtest")
    parser.add_argument("--topk-frac", default=0.125, type=float)
    parser.add_argument("--bucket-mb", default=25, type=int)
    parser.add_argument("--topology", default="flat",
                        help="comma list of sweep entries: 'flat' "
                             "and/or INNERxOUTER specs (e.g. "
                             "'flat,2x4,4x2'); specs that do not "
                             "factor a world are skipped for it")
    parser.add_argument("--selector-ab", action="store_true",
                        help="run the interleaved selector A/B "
                             "(flat vs auto-selected hd/hier, one "
                             "iteration of each per round — drift "
                             "cancels) instead of the sweep; the "
                             "first --topology entry that is not "
                             "'flat' is the factorization under test")
    parser.add_argument("--modeled-network", action="store_true",
                        help="add a modeled_pod_step_s column: the "
                             "calibrated LinkModel's predicted pod "
                             "all-reduce seconds next to the measured "
                             "CPU time (the digital-twin pricing, "
                             "round 20)")
    parser.add_argument("--json", dest="json_out", default=None)
    args = parser.parse_args(argv)
    if args.selector_ab:
        specs = [t.strip() for t in args.topology.split(",")
                 if t.strip() != "flat"]
        rows = bench_selector_ab(
            world=int(args.worlds.split(",")[0]),
            topology=specs[0] if specs else "2x4",
            iters=args.iters,
            per_device_batch=args.batch_size,
            model_name=args.model,
        )
    else:
        rows = bench_ring_compress(
            worlds=tuple(int(w) for w in args.worlds.split(",")),
            iters=args.iters,
            per_device_batch=args.batch_size,
            model_name=args.model,
            topk_frac=args.topk_frac,
            bucket_mb=args.bucket_mb,
            topologies=tuple(t.strip() for t in args.topology.split(",")),
            modeled_network=args.modeled_network,
        )
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(rows, f, indent=2)
        print(f"wrote {args.json_out}")


if __name__ == "__main__":
    main()
