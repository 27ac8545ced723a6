"""Real-data parity harness — all four reference parts, one command.

The reference's published end-state (``group25.pdf``) is a handful of
numbers: part1's 10% test accuracy / 2.3031 average test loss after 40
iterations, and per-part execution times (93.44 s / 47.23 s / 36.44 s /
32.68 s for parts 1 / 2a / 2b / 3).  This harness runs the EXACT
reference protocol for every part — by invoking the same four CLI
entrypoints a user would, with their reference-default batch sizes,
seed 69143, 40-iteration cap, and full-test-set eval — and prints a
side-by-side table against the published numbers
(``/root/reference/part1/main.py:62-77,120-123``; BASELINE.md).

Usage::

    python -m distributed_machine_learning_tpu.cli.parity \
        --data-root /path/with/cifar-10-batches-py

Without a real ``cifar-10-batches-py/`` under ``--data-root`` the parts
train on the deterministic synthetic stand-in (``data/cifar10.py``) and
every row is marked ``synthetic`` — the harness is then a smoke test of
itself (this environment has no egress, so the real-data column fills
in whenever a host with the dataset exists).  Accuracy/loss parity is
published for part1 only; parts 2a/2b/3 compare step times.

The reference timed a 4-node CPU cluster; this harness runs whatever
devices the host offers and reports the world size next to each ratio
— time ratios across different hardware are a speedup statement, not a
parity check (accuracy/loss are the parity check).
"""

from __future__ import annotations

import argparse
import io
import json
import os
import re
import sys
from contextlib import redirect_stdout

# Published numbers: group25.pdf via BASELINE.md (the report is the only
# source; parts 2a/2b/3 publish times but no end-state accuracy).
REFERENCE = {
    "part1": {
        "total_s": 93.44, "avg_iter_s": 2.39,
        "accuracy_pct": 10.0, "avg_test_loss": 2.3031,
        "config": "batch 256, 1 CPU node", "source": "group25.pdf p.2",
    },
    "part2a": {
        "total_s": 47.23, "avg_iter_s": 1.21,
        "config": "batch 64/node, 4 CPU nodes", "source": "group25.pdf p.3",
    },
    "part2b": {
        "total_s": 36.44, "avg_iter_s": 0.934,
        "config": "batch 64/node, 4 CPU nodes", "source": "group25.pdf p.5",
    },
    "part3": {
        "total_s": 32.68, "avg_iter_s": 0.838,
        "config": "batch 64/node, 4 CPU nodes", "source": "group25.pdf p.6",
    },
}

_PARTS = list(REFERENCE)


def _part_main(part: str):
    import importlib

    mod = importlib.import_module(
        f"distributed_machine_learning_tpu.cli.{part}"
    )
    return mod.main


def _parse_output(out: str) -> dict:
    """Pull the reference-protocol numbers out of a part's print surface."""
    res: dict = {}
    m = re.search(r"Total execution time is : ([\d.eE+-]+) seconds", out)
    if m:
        res["total_s"] = float(m.group(1))
    m = re.search(r"Average execution time is\s+: ([\d.eE+-]+) seconds", out)
    if m:
        res["avg_iter_s"] = float(m.group(1))
    m = re.search(
        r"Test set: Average loss: ([\d.]+), Accuracy: \d+/\d+ \((\d+)%\)",
        out,
    )
    if m:
        res["avg_test_loss"] = float(m.group(1))
        res["accuracy_pct"] = float(m.group(2))
    return res


def run_parity(args) -> list[dict]:
    """Run the selected parts; return one result row per part."""
    from distributed_machine_learning_tpu.data.cifar10 import _maybe_extract

    real_data = (
        os.path.isdir(args.data_root)
        and _maybe_extract(args.data_root) is not None
    )
    import jax

    # Validate the whole list before any (potentially long) training run
    # — a typo in the last part must not discard the first's 40 iters.
    parts = [p.strip() for p in args.parts.split(",")]
    unknown = [p for p in parts if p not in REFERENCE]
    if unknown:
        raise ValueError(f"unknown part(s) {unknown}; choose from {_PARTS}")

    rows = []
    for part in parts:
        argv = ["--data-root", args.data_root,
                "--max-iters", str(args.max_iters)]
        if args.batch_size is not None:
            argv += ["--batch-size", str(args.batch_size)]
        if args.eval_batches is not None:
            argv += ["--eval-batches", str(args.eval_batches)]
        if args.eval_batch_size is not None:
            argv += ["--eval-batch-size", str(args.eval_batch_size)]
        if args.model is not None:
            argv += ["--model", args.model]
        buf = io.StringIO()
        # The part prints its protocol surface; capture it but keep the
        # user informed on stderr.
        print(f"[parity] running {part} {' '.join(argv)}", file=sys.stderr)
        with redirect_stdout(buf):
            _part_main(part)(argv)
        out = buf.getvalue()
        got = _parse_output(out)
        if not got:
            raise RuntimeError(
                f"{part} produced no parseable protocol output:\n{out}"
            )
        rows.append({
            "part": part,
            "data": "cifar-10-batches-py" if real_data else "synthetic",
            "world": jax.device_count(),
            "max_iters": args.max_iters,
            "reference": REFERENCE[part],
            "measured": got,
        })
    return rows


def print_table(rows: list[dict]) -> None:
    hdr = (f"{'part':8} {'metric':15} {'reference':>12} {'measured':>12} "
           f"{'ref/ours':>9}  note")
    print(hdr)
    print("-" * len(hdr))
    for row in rows:
        ref, got = row["reference"], row["measured"]
        note = f"{row['data']}, world={row['world']} (ref: {ref['config']})"
        # The reference total is 39 timed iterations; a shortened smoke
        # run's total is not comparable, so its label says what was run
        # and its ratio is suppressed (sec/iter stays fair at any cap).
        full_protocol = row["max_iters"] == 40
        timed = max(row["max_iters"] - 1, 1)
        for key, label in (
            ("total_s", f"total_s({timed}it)"),
            ("avg_iter_s", "sec/iter"),
            ("accuracy_pct", "accuracy_%"),
            ("avg_test_loss", "avg_test_loss"),
        ):
            if key not in ref:
                continue
            r = ref[key]
            g = got.get(key)
            if g is None:
                cell, ratio = "—", "—"
            else:
                cell = f"{g:.4f}" if key != "accuracy_pct" else f"{g:.0f}"
                comparable = key == "avg_iter_s" or (
                    key == "total_s" and full_protocol
                )
                ratio = (f"{r / g:.1f}x"
                         if key.endswith("_s") and g > 0 and comparable
                         else "—")
            print(f"{row['part']:8} {label:15} {r:>12} {cell:>12} "
                  f"{ratio:>9}  {note}")
            note = ""
    if any(r["data"] == "synthetic" for r in rows):
        print(
            "\nNOTE: no cifar-10-batches-py found under --data-root — the "
            "parts trained on the deterministic synthetic stand-in, so "
            "accuracy/loss rows are NOT a real-data parity claim.  Place "
            "the dataset (or its .tar.gz) under --data-root and re-run."
        )


def run_equivalence(args, devices=None) -> dict:
    """Machine-check the report's mathematical-equivalence argument
    (group25.pdf p.5-6) as a loss-trajectory table over the full
    40-iteration protocol on deterministic synthetic data:

    - **part2a ≡ part2b**: gather→sum→scatter and all-reduce(SUM) are
      the same update through different collectives — trajectories must
      match to float-associativity noise.
    - **SUM parts ≡ part1 at world× LR**: with per-node batch b and
      mean-reduction loss, the summed gradient over w workers equals
      w × the global-batch mean gradient — so 2a/2b on global batch w·b
      must track part1 on the same batches with ``lr × w`` (the §2.4
      effective-LR fact the reference's report glossed over).
    - **part3 (mean) ≡ part1**: the bucketed ppermute ring with pmean
      semantics is DDP's averaged update — must track part1 at the
      same LR.

    Controlled variables: BN-free model (BN running stats are the one
    part3 divergence the reference documented away — group25.pdf
    p.3-4), augmentation off, weight decay off (the SUM ≡ hot-LR
    identity holds for the GRADIENT term only: decay is ``lr·wd·p`` on
    the SUM side but ``lr·w·wd·p`` at the hot LR — a real semantic
    footnote to §2.4, excluded so the collectives are what is
    checked), identical synthetic batches, identical seed-69143 init.
    The strategy is the ONLY thing that varies — the trajectory table
    is the reference report's argument, machine-checked instead of
    eyeballed.

    ``devices``: optional explicit device list (the dryrun passes its
    virtual CPU devices).  A world of 1 would make every check
    vacuously pass (five identical runs), so it is refused.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

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

    n = len(devices) if devices is not None else jax.device_count()
    world = min(4, n)  # the reference cluster was 4 nodes
    if world < 2:
        raise ValueError(
            "the equivalence check needs >= 2 devices (a world of 1 "
            "makes every check vacuously pass); run with "
            "XLA_FLAGS=--xla_force_host_platform_device_count=4 "
            "JAX_PLATFORMS=cpu, or on a multi-chip host"
        )
    iters = args.max_iters
    per_node = args.batch_size or 64
    global_batch = per_node * world
    model = get_model(args.model or "vgg11", use_bn=False)
    base_lr = 0.1  # part1/main.py:120

    rng = np.random.default_rng(SEED)
    batches = [
        (
            rng.integers(0, 256, (global_batch, 32, 32, 3), dtype=np.uint8),
            rng.integers(0, 10, global_batch).astype(np.int32),
        )
        for _ in range(iters)
    ]

    def trajectory(strategy_name, lr):
        state = init_model_and_state(
            model, config=SGDConfig(learning_rate=lr, weight_decay=0.0)
        )
        if strategy_name is None:
            step = make_train_step(model, mesh=None, augment=False)
            dev0 = devices[0] if devices is not None else None
            place = lambda x, y: (
                jax.device_put(jnp.asarray(x), dev0),
                jax.device_put(jnp.asarray(y), dev0),
            )
        else:
            mesh = make_mesh(
                world,
                devices=devices[:world] if devices is not None else None,
            )
            step = make_train_step(
                model, get_strategy(strategy_name), mesh=mesh, augment=False
            )
            place = lambda x, y: shard_batch(mesh, x, y)
        losses = []
        for x, y in batches:
            state, loss = step(state, *place(x, y))
            losses.append(float(loss))
        return np.asarray(losses)

    print(f"[equivalence] world={world}, per-node batch {per_node} "
          f"(global {global_batch}), {iters} iters, model "
          f"{args.model or 'vgg11'} (BN-free), augment off",
          file=sys.stderr)
    part1 = trajectory(None, base_lr)
    part1_hot = trajectory(None, base_lr * world)  # the SUM-equivalent LR
    p2a = trajectory("gather_scatter", base_lr)
    p2b = trajectory("all_reduce", base_lr)
    p3 = trajectory("ring", base_lr)

    checks = {
        # gather/scatter vs all-reduce: identical SUM through different
        # collectives — float-associativity noise only.
        "part2a==part2b": (p2a, p2b, 1e-5),
        # SUM semantics = world× effective LR on the global batch
        # (exact with weight decay off — see docstring; tolerance is
        # 40 iters of f32 reduction-order drift).
        f"part2b==part1@lr*{world}": (p2b, part1_hot, 2e-3),
        # ring pmean = part3/DDP's averaged update = part1's rule.
        "part3==part1": (p3, part1, 1e-4),
    }

    hdr = (f"{'iter':>4} {'part1':>9} {'p1@hotlr':>9} {'part2a':>9} "
           f"{'part2b':>9} {'part3':>9}")
    print(hdr)
    print("-" * len(hdr))
    for i in range(0, iters, max(1, iters // 8)):
        print(f"{i:>4} {part1[i]:9.5f} {part1_hot[i]:9.5f} {p2a[i]:9.5f} "
              f"{p2b[i]:9.5f} {p3[i]:9.5f}")
    results = {}
    ok = True
    for name, (a, b, tol) in checks.items():
        dev = float(np.max(np.abs(a - b)))
        passed = dev <= tol
        ok &= passed
        results[name] = {"max_abs_dev": dev, "tol": tol, "pass": passed}
        print(f"{'PASS' if passed else 'FAIL'}  {name:28} "
              f"max|Δloss| = {dev:.2e} (tol {tol:g})")
    return {
        "world": world, "global_batch": global_batch, "iters": iters,
        "checks": results, "ok": ok,
    }


def make_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--data-root", default="./data",
                   help="directory containing cifar-10-batches-py/ (or "
                        "its tar.gz); synthetic stand-in otherwise")
    p.add_argument("--parts", default=",".join(_PARTS),
                   help="comma-separated subset of " + ",".join(_PARTS))
    p.add_argument("--max-iters", default=40, type=int,
                   help="reference protocol: 40 (iteration 0 untimed)")
    p.add_argument("--batch-size", default=None, type=int,
                   help="override each part's reference batch size "
                        "(smoke-testing the harness itself)")
    p.add_argument("--eval-batches", default=None, type=int,
                   help="cap eval batches (reference: full test set)")
    p.add_argument("--eval-batch-size", default=None, type=int)
    p.add_argument("--model", default=None,
                   help="override the model (reference: vgg11)")
    p.add_argument("--json", dest="json_out", default=None,
                   help="also write the rows as JSON to this path")
    p.add_argument("--equivalence", action="store_true",
                   help="machine-check the report's equivalence argument "
                        "(group25.pdf p.5-6) as a loss-trajectory table: "
                        "part2a==part2b, SUM parts==part1 at world x LR, "
                        "part3 mean==part1 — over the 40-iter synthetic "
                        "protocol; exits non-zero on any FAIL")
    return p


def main(argv=None) -> None:
    from distributed_machine_learning_tpu.runtime.compile_cache import (
        configure_compile_cache,
    )

    configure_compile_cache()
    args = make_parser().parse_args(argv)
    if args.equivalence:
        result = run_equivalence(args)
        if args.json_out:
            with open(args.json_out, "w") as f:
                json.dump(result, f, indent=2)
            print(f"\nwrote {args.json_out}")
        if not result["ok"]:
            sys.exit(1)
        return
    rows = run_parity(args)
    print_table(rows)
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(rows, f, indent=2)
        print(f"\nwrote {args.json_out}")


if __name__ == "__main__":
    main()
