"""Benchmark harness — prints ONE JSON line for the driver.

Flagship workload: VGG-11/CIFAR-10 train steps (the reference's part1
measurement: 39 timed iterations at batch 256, iteration 0 excluded —
``part1/main.py:32-58``; 2.39 s/iter on its CPU node, group25.pdf p.2).

Metric: images/sec through the train step.  ``vs_baseline`` compares
against the reference's measured part1 rate (256 / 2.39 s ≈ 107.1
imgs/sec — BASELINE.md).

Measurement design: the 39 iterations run as ONE jitted ``lax.scan`` over
pre-staged device-resident batches, timed around a forced host fetch of
the final loss.  Per-step Python dispatch is excluded on purpose (JAX
dispatch is asynchronous, so a timing must end in a fetch or
``block_until_ready`` or it measures the enqueue).  The scan measures
what the hardware does: 39 full fwd+bwd+update steps, each on its own
batch, augmentation included.  The trunk runs in bfloat16 (MXU-native;
master weights and loss stay fp32).  Uses the synthetic CIFAR stand-in
when the real dataset is not on disk — identical shapes/dtypes, so the
throughput number is unaffected.

Device metrics are only measured on the chip: the script refuses any
backend but a TPU, prints the device with its JSON, and exits on a
device kind the peak table (``utils/flops.py``) does not list.
"""

from __future__ import annotations

import argparse
import json

import jax
import jax.numpy as jnp
import numpy as np

from distributed_machine_learning_tpu.bench.harness import (
    chip_mfu,
    require_tpu,
    timed_scan_epoch,
)
from distributed_machine_learning_tpu.cli.common import init_model_and_state
from distributed_machine_learning_tpu.data.cifar10 import load_cifar10
from distributed_machine_learning_tpu.models.registry import get_model, list_models
from distributed_machine_learning_tpu.runtime.compile_cache import (
    configure_compile_cache,
)
from distributed_machine_learning_tpu.train.step import make_train_step

BATCH = 256  # part1/main.py:18
TIMED_ITERS = 39  # part1 protocol: 40 iters, iteration 0 excluded
BASELINE_IMGS_PER_SEC = 256 / 2.39  # group25.pdf p.2 → 107.1


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--model", default="vgg11", choices=list_models())
    parser.add_argument("--reps", default=3, type=int,
                        help="timed repetitions; the best is reported")
    parser.add_argument("--chain", default=8, type=int,
                        help="chained scan dispatches per measurement; the "
                             "per-scan time is the (chain vs 1) slope, "
                             "cancelling the constant per-measurement "
                             "dispatch+fetch cost (bench/harness.py)")
    args = parser.parse_args()
    configure_compile_cache()
    device = require_tpu()
    model = get_model(args.model, compute_dtype=jnp.bfloat16)

    train = load_cifar10("./data", train=True)
    n = BATCH * TIMED_ITERS
    idx = np.arange(n) % len(train.labels)
    images = np.asarray(train.images)[idx].reshape(
        TIMED_ITERS, BATCH, *train.images.shape[1:]
    )
    labels = np.asarray(train.labels)[idx].reshape(TIMED_ITERS, BATCH)
    dx = jax.device_put(jnp.asarray(images))
    dy = jax.device_put(jnp.asarray(labels))

    step = make_train_step(model, augment=True, jit=False)
    state = init_model_and_state(model)
    tail: dict = {}
    best, _, _ = timed_scan_epoch(
        step, state, dx, dy, reps=args.reps, chain=args.chain, stats=tail
    )

    imgs_per_sec = BATCH * TIMED_ITERS / best
    # The reference measured only VGG-11 (group25.pdf p.2); comparing any
    # other model against that number would be apples-to-oranges.
    vs_baseline = (
        round(imgs_per_sec / BASELINE_IMGS_PER_SEC, 2)
        if args.model == "vgg11"
        else None
    )
    out = {
        "metric": f"{args.model}_cifar10_train_imgs_per_sec",
        "value": round(imgs_per_sec, 2),
        "unit": "imgs/sec",
        "vs_baseline": vs_baseline,
        # Tail latency per ITERATION over every raw scan sample (chain
        # points included): future BENCH_*.json rounds must report p95
        # next to the best/mean (docs/PERF.md) — a straggler-free best
        # hides exactly the steps a production run diagnoses by.
        "iter_p50_s": round(tail["p50_s"] / TIMED_ITERS, 6),
        "iter_p95_s": round(tail["p95_s"] / TIMED_ITERS, 6),
        "iter_p99_s": round(tail["p99_s"] / TIMED_ITERS, 6),
        "iter_max_s": round(tail["max_s"] / TIMED_ITERS, 6),
        "tail_samples": tail["samples"],
        "device": device,
    }
    if args.model.startswith("vgg"):
        from distributed_machine_learning_tpu.models.vgg import _cfg
        from distributed_machine_learning_tpu.utils.flops import (
            vgg_train_flops_per_image,
        )

        flops = vgg_train_flops_per_image(_cfg[args.model.upper()])
        out["tflops_per_sec"] = round(imgs_per_sec * flops / 1e12, 1)
        out["mfu"] = round(chip_mfu(imgs_per_sec * flops, device), 3)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
