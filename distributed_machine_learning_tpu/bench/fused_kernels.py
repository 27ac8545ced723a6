"""Round-13 fused-kernel A/B benches: ring codec and AdamW update.

Two interleaved A/B instruments (``bench/harness.py::interleaved_ab``
— one iteration of each config per round on the same batch stream, so
the 1-core host's sequential drift cancels the same way it does for
the round-11 selector A/B):

- **codec**: the part3 ring train step, int8 + error feedback, XLA
  codec vs the fused Pallas codec (``--ring-codec-impl``).  The two
  builds are BITWISE-identical in trajectory (the exact-product parity
  contract of ``ops/pallas/ring_codec.py``), so the final-loss column
  is an identity check, not a tolerance.
- **update**: the ZeRO-1 overlap train step (the build whose update
  program the round-9 spans put on the critical path) with AdamW,
  reference XLA update vs the fused one-pass kernel
  (``--fused-update``).

Honest-reporting note (the PERF.md round-13 protocol): on the 1-core
CPU CI host the kernels run under the Pallas INTERPRETER — a scan
over grid steps with functionalized state — so "fused" rows measure
interpreter overhead, not the in-register dataflow; the pod claim is
the kernels' dataflow (no dequantized partial / one-pass update in
HBM), exactly as PR 9's pp_gpipe rows claimed the overlap, not the
CPU numbers.  A TPU-backed run of this same file produces the
on-chip rows.

Run:  python -m distributed_machine_learning_tpu.bench.fused_kernels \\
          [--world 8] [--iters 40] [--model vggtest] [--json out]
"""

from __future__ import annotations

import argparse
import json


def bench_codec_ab(world: int = 8, iters: int = 40,
                   per_device_batch: int = 16,
                   model_name: str = "vggtest") -> list[dict]:
    """Interleaved A/B: int8+EF ring step, XLA codec vs fused Pallas
    codec.  Returns one row per config with p50/p95 and the final-loss
    identity column."""
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
        "int8_xla": get_strategy("ring", compress="int8"),
        "int8_pallas": get_strategy("ring", compress="int8",
                                    codec_impl="pallas"),
    }
    steps, states, last_loss = {}, {}, {}
    for k, strat in configs.items():
        states[k] = init_model_and_state(
            model, config=SGDConfig(learning_rate=0.1, weight_decay=0.0)
        )
        steps[k] = make_train_step(model, strat, mesh=mesh, augment=False)

    def one_iter(k):
        def run(rep):
            xs, ys = shard_batch(mesh, *batches[rep % len(batches)])
            states[k], loss = steps[k](states[k], xs, ys)
            last_loss[k] = float(jax.block_until_ready(loss))
        return run

    times = interleaved_ab({k: one_iter(k) for k in configs}, iters,
                           warmup=1)
    rows = []
    base_p50 = percentile_stats(times["int8_xla"])["p50"]
    for k, ts in times.items():
        stats = percentile_stats(ts)
        rows.append({
            "bench": "fused_codec_ab",
            "world": world,
            "config": k,
            "codec_impl": k.split("_", 1)[1],
            "iter_p50_s": stats["p50"],
            "iter_p95_s": stats["p95"],
            "p50_vs_xla": stats["p50"] / base_p50 - 1.0,
            "final_loss": last_loss[k],
            # The parity contract: identical trajectories, bit for bit.
            "loss_bitwise_equal": last_loss[k] == last_loss["int8_xla"],
        })
        print(json.dumps(rows[-1]))
    return rows


def bench_update_ab(world: int = 4, iters: int = 40,
                    per_device_batch: int = 16,
                    model_name: str = "vggtest") -> list[dict]:
    """Interleaved A/B: ZeRO-1 OVERLAP step with AdamW, reference
    update vs the fused one-pass kernel."""
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
    from distributed_machine_learning_tpu.parallel.zero1 import (
        make_zero1_train_step,
        shard_zero1_state,
    )
    from distributed_machine_learning_tpu.runtime.mesh import make_mesh
    from distributed_machine_learning_tpu.train.adamw import AdamWConfig
    from distributed_machine_learning_tpu.train.step import shard_batch
    from distributed_machine_learning_tpu.utils.timing import (
        percentile_stats,
    )

    mesh = make_mesh(world)
    model = get_model(model_name, use_bn=False)
    rng = np.random.default_rng(SEED)
    B = per_device_batch * world
    batches = [
        (rng.integers(0, 256, (B, 32, 32, 3), dtype=np.uint8),
         rng.integers(0, 10, B).astype(np.int32))
        for _ in range(4)
    ]
    steps, states, last_loss = {}, {}, {}
    for k, fused in (("adamw_reference", False), ("adamw_fused", True)):
        st = init_model_and_state(model, config=AdamWConfig(fused=fused))
        z1, unravel, n_elems = shard_zero1_state(st, mesh)
        states[k] = z1
        steps[k] = make_zero1_train_step(model, mesh, unravel, n_elems,
                                         augment=False, overlap=True)

    def one_iter(k):
        def run(rep):
            xs, ys = shard_batch(mesh, *batches[rep % len(batches)])
            states[k], loss = steps[k](states[k], xs, ys)
            last_loss[k] = float(jax.block_until_ready(loss))
        return run

    times = interleaved_ab({k: one_iter(k) for k in steps}, iters,
                           warmup=1)
    rows = []
    base_p50 = percentile_stats(times["adamw_reference"])["p50"]
    for k, ts in times.items():
        stats = percentile_stats(ts)
        rows.append({
            "bench": "fused_update_ab",
            "world": world,
            "config": k,
            "fused": k == "adamw_fused",
            "iter_p50_s": stats["p50"],
            "iter_p95_s": stats["p95"],
            "p50_vs_reference": stats["p50"] / base_p50 - 1.0,
            "final_loss": last_loss[k],
            # Documented-ulp contract, NOT bitwise: report the delta.
            "final_loss_rel_delta_vs_reference": (
                abs(last_loss[k] - last_loss["adamw_reference"])
                / max(abs(last_loss["adamw_reference"]), 1e-30)
            ),
        })
        print(json.dumps(rows[-1]))
    return rows


def main(argv=None) -> None:
    from distributed_machine_learning_tpu.runtime.compile_cache import (
        configure_compile_cache,
    )

    configure_compile_cache()
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--world", default=8, type=int,
                        help="codec A/B world (the update A/B runs at "
                             "min(world, 4): zero1's compile cost on the "
                             "1-core host scales with world)")
    parser.add_argument("--iters", default=40, type=int)
    parser.add_argument("--batch-size", default=16, type=int,
                        help="PER-DEVICE batch")
    parser.add_argument("--model", default="vggtest")
    parser.add_argument("--json", dest="json_out", default=None)
    args = parser.parse_args(argv)
    rows = bench_codec_ab(world=args.world, iters=args.iters,
                          per_device_batch=args.batch_size,
                          model_name=args.model)
    rows += bench_update_ab(world=min(args.world, 4), iters=args.iters,
                            per_device_batch=args.batch_size,
                            model_name=args.model)
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(rows, f, indent=2)
        print(f"wrote {args.json_out}")


if __name__ == "__main__":
    main()
