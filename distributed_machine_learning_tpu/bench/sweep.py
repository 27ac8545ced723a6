"""Weak-scaling sweep harness (SURVEY.md §7.2 step 5).

The reference's scalability story is a hand-run sweep over 1→4 CPU nodes
whose result — "~2.8× speedup given 4× computational power", i.e. ~70%
weak-scaling efficiency — lives only in its report (group25.pdf p.10,
SURVEY.md §6).  Here the sweep is a first-class harness: fixed per-device
batch (weak scaling), growing device count, measuring imgs/sec/device and
efficiency relative to the single-device baseline.  Target ≥85%
(BASELINE.json north-star).

Runs anywhere a mesh runs: real TPU chips, or a virtual CPU mesh via
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (the test path —
efficiency numbers on virtual devices are not meaningful, but the harness
logic and the sharded programs are identical).
"""

from __future__ import annotations

import argparse
import json
from dataclasses import asdict, dataclass

import jax
import numpy as np

from distributed_machine_learning_tpu.parallel.strategies import get_strategy
from distributed_machine_learning_tpu.runtime.mesh import make_mesh
from distributed_machine_learning_tpu.train.step import make_train_step


@dataclass
class ScalePoint:
    """One measured point of the sweep."""

    num_devices: int
    strategy: str
    per_device_batch: int
    timed_iters: int
    imgs_per_sec: float
    imgs_per_sec_per_device: float
    efficiency: float | None = None  # filled in by the sweep vs its baseline


def _synthetic_batch(rng: np.random.Generator, global_batch: int):
    """CIFAR-shaped uint8 batch; data content is irrelevant to step timing."""
    images = rng.integers(0, 256, (global_batch, 32, 32, 3), dtype=np.uint8)
    labels = rng.integers(0, 10, global_batch).astype(np.int32)
    return images, labels


def run_point(
    model,
    strategy_name: str,
    num_devices: int,
    per_device_batch: int = 64,
    timed_iters: int = 10,
    seed: int = 0,
    init_state=None,
    devices=None,
) -> ScalePoint:
    """Measure one (strategy, device-count) point.

    ``num_devices == 1`` runs the part1 path (plain jit, no mesh) so the
    baseline carries zero collective overhead — the honest denominator for
    weak-scaling efficiency.  ``model`` is a flax module instance;
    ``init_state`` (optional) is a pre-built TrainState to reuse across
    points so each point times the step, not initialization.  ``devices``
    (optional) pins the point to an explicit device list (e.g. virtual
    CPU devices under a TPU-default backend, the dryrun path).
    """
    from distributed_machine_learning_tpu.cli.common import init_model_and_state

    if num_devices < 1:
        raise ValueError(f"num_devices must be >= 1, got {num_devices}")
    if timed_iters < 1:
        raise ValueError(f"timed_iters must be >= 1, got {timed_iters}")
    # Nothing in the scan-epoch path donates buffers (the step is built
    # with jit=False and the harness jit has no donate_argnums), so one
    # shared init can seed every point as-is.
    state = init_state if init_state is not None else init_model_and_state(model)
    rng = np.random.default_rng(seed)
    global_batch = per_device_batch * num_devices

    if num_devices == 1:
        mesh = None
        step = make_train_step(model, mesh=None, jit=False)
    elif strategy_name == "fsdp":
        # ZeRO-3 is a different step builder, not a grad-strategy: the
        # state becomes flat 1/N shards and the step gathers/scatters
        # around the forward/backward (parallel/fsdp.py).
        from distributed_machine_learning_tpu.parallel.fsdp import (
            make_fsdp_train_step,
            shard_fsdp_state,
        )

        mesh = make_mesh(num_devices, devices=devices)
        state, unravel, n_elems = shard_fsdp_state(state, mesh)
        step = make_fsdp_train_step(model, mesh, unravel, n_elems,
                                    jit=False)
    else:
        mesh = make_mesh(num_devices, devices=devices)
        step = make_train_step(
            model, get_strategy(strategy_name), mesh=mesh, jit=False
        )

    # Shared scan-epoch methodology (bench/harness.py): one compiled scan,
    # timing bracketed by a value fetch, compile run excluded.
    from distributed_machine_learning_tpu.bench.harness import timed_scan_epoch

    batches = [_synthetic_batch(rng, global_batch) for _ in range(timed_iters)]
    imgs = np.stack([b[0] for b in batches])
    lbls = np.stack([b[1] for b in batches])
    if mesh is None:
        dx, dy = jax.numpy.asarray(imgs), jax.numpy.asarray(lbls)
        if devices is not None:
            # Commit inputs to the pinned device so jit runs there, not on
            # the ambient default backend.
            dx = jax.device_put(dx, devices[0])
            dy = jax.device_put(dy, devices[0])
    else:
        from jax.sharding import NamedSharding, PartitionSpec as P

        sharding = NamedSharding(mesh, P(None, "batch"))
        dx = jax.device_put(jax.numpy.asarray(imgs), sharding)
        dy = jax.device_put(jax.numpy.asarray(lbls), sharding)

    elapsed, _, state = timed_scan_epoch(step, state, dx, dy, reps=1)

    imgs_per_sec = global_batch * timed_iters / elapsed
    return ScalePoint(
        num_devices=num_devices,
        strategy=strategy_name if num_devices > 1 else "none",
        per_device_batch=per_device_batch,
        timed_iters=timed_iters,
        imgs_per_sec=imgs_per_sec,
        imgs_per_sec_per_device=imgs_per_sec / num_devices,
    )


def weak_scaling_sweep(
    model,
    strategy_name: str = "ring",
    device_counts: list[int] | None = None,
    per_device_batch: int = 64,
    timed_iters: int = 10,
    devices=None,
) -> list[ScalePoint]:
    """Sweep device counts at fixed per-device batch; annotate efficiency
    relative to the smallest point's per-device throughput."""
    if device_counts is None:
        n = len(devices) if devices is not None else jax.device_count()
        device_counts = [d for d in (1, 2, 4, 8, 16, 32) if d <= n]
    device_counts = sorted(set(device_counts))
    if not device_counts:
        raise ValueError("device_counts is empty: nothing to sweep")
    from distributed_machine_learning_tpu.cli.common import init_model_and_state

    state = init_model_and_state(model)
    points = [
        run_point(
            model,
            strategy_name,
            d,
            per_device_batch=per_device_batch,
            timed_iters=timed_iters,
            init_state=state,
            devices=devices,
        )
        for d in device_counts
    ]
    base = points[0].imgs_per_sec_per_device
    for p in points:
        p.efficiency = round(p.imgs_per_sec_per_device / base, 4) if base else None
    return points


def main() -> None:
    from distributed_machine_learning_tpu.runtime.compile_cache import (
        configure_compile_cache,
    )

    configure_compile_cache()
    from distributed_machine_learning_tpu.models.registry import list_models

    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--model", default="vgg11", choices=list_models())
    parser.add_argument("--strategy", default="ring",
                        choices=["gather_scatter", "all_reduce", "ring",
                                 "fsdp"])
    parser.add_argument("--devices", default=None, type=str,
                        help="comma-separated device counts, e.g. 1,2,4,8 "
                             "(default: powers of two up to the device count)")
    parser.add_argument("--batch-per-device", default=64, type=int)
    parser.add_argument("--iters", default=10, type=int)
    parser.add_argument("--compute-dtype", default="bfloat16",
                        choices=["float32", "bfloat16"])
    args = parser.parse_args()

    import jax.numpy as jnp

    from distributed_machine_learning_tpu.models.registry import get_model

    model = get_model(args.model, compute_dtype=getattr(jnp, args.compute_dtype))
    counts = (
        [int(d) for d in args.devices.split(",")] if args.devices else None
    )
    points = weak_scaling_sweep(
        model,
        args.strategy,
        device_counts=counts,
        per_device_batch=args.batch_per_device,
        timed_iters=args.iters,
    )
    for p in points:
        row = asdict(p)
        row["imgs_per_sec"] = round(row["imgs_per_sec"], 2)
        row["imgs_per_sec_per_device"] = round(row["imgs_per_sec_per_device"], 2)
        print(json.dumps(row))
    if len(points) > 1:
        print(
            json.dumps(
                {
                    "metric": "weak_scaling_efficiency",
                    "value": points[-1].efficiency,
                    "unit": f"x{points[-1].num_devices}_vs_x{points[0].num_devices}",
                    # Reference figure: ~70% at 4 nodes, VGG-11 only
                    # (group25.pdf p.10) — any other model is not comparable.
                    "vs_baseline": (
                        round(points[-1].efficiency / 0.70, 2)
                        if points[-1].efficiency and args.model == "vgg11"
                        else None
                    ),
                }
            )
        )


if __name__ == "__main__":
    main()
