"""Overlap-aware sharded weight update bench: sync vs overlapped tails.

Measures the ISSUE-9 tentpole (``parallel/overlap.py`` two-phase
update; arxiv 2004.13336) the way the train loop experiences it: the
per-iteration wall clock brackets ``block_until_ready(loss)`` (the
measurement protocol), and a configurable host-side data wait (a
``time.sleep`` standing in for the input pipeline) separates steps.

- **sync build**: the parameter gather is inside the step program and
  feeds ROOT, so the loss block waits it out — the gather is ON the
  measured critical path and the data wait hides nothing.
- **overlap build**: the loss block returns at the end of the update
  program; the separately-dispatched bucketed-ring gather executes
  during the data wait (the sleep releases the GIL, so even this
  one-core CI host genuinely runs the gather under it — on a pod the
  DMAs ride ICI while the host feeds data).  ``param_gather_s`` (the
  span from gather dispatch to observed readiness) is reported
  alongside, showing where the gather went.

Schemes: zero1 and fsdp (CNN steps, fixed-seed synthetic batches,
loss parity asserted bit-identical), the GPipe pipeline with the
pipe-sharded boundary update, and zero1×3-D (the annotated-dependency
grad constraint, over a partial-manual shard_map).

Run:  python -m distributed_machine_learning_tpu.bench.overlap_bench \
          [--iters 24] [--data-wait-ms 10] [--json out.json]
"""

from __future__ import annotations

import argparse
import json
import time


def _row(name, build, iters, gathers, loss, extra=None):
    from distributed_machine_learning_tpu.utils.timing import (
        percentile_stats,
    )

    stats = percentile_stats(iters)
    row = {
        "scheme": name,
        "build": build,
        "iters_timed": len(iters),
        "iter_p50_s": stats["p50"],
        "iter_p95_s": stats["p95"],
        "final_loss": loss,
    }
    if gathers:
        g = percentile_stats(gathers)
        row["param_gather_p50_s"] = g["p50"]
        row["param_gather_p95_s"] = g["p95"]
    if extra:
        row.update(extra)
    return row


def _gather_spans(make_step, shard, model, batches, data_wait_s):
    """Short telemetry-on pass: collect the param_gather span durations
    (dispatch → observed ready) the main timed pass cannot see."""
    import tempfile

    from distributed_machine_learning_tpu.cli.common import (
        init_model_and_state,
    )
    from distributed_machine_learning_tpu.runtime.mesh import make_mesh
    from distributed_machine_learning_tpu.telemetry import (
        Telemetry,
        set_telemetry,
    )

    mesh = make_mesh(8)
    state, unravel, n_elems = shard(init_model_and_state(model), mesh)
    step = make_step(state, unravel, n_elems)
    gathers = []
    with tempfile.TemporaryDirectory() as td:
        tel = Telemetry(td, flush_every=10**6)
        prev = set_telemetry(tel)
        try:
            for i, (x, y) in enumerate(batches):
                if data_wait_s:
                    time.sleep(data_wait_s)
                state, loss = step(state, x, y)
                g = step.pop_gather_seconds()
                if g is not None and i > 1:
                    gathers.append(g)
        finally:
            set_telemetry(prev)
            tel.close()
    return gathers


def bench_overlap(iters: int = 24, data_wait_ms: float = 10.0,
                  per_device_batch: int = 16) -> list[dict]:
    import jax
    import numpy as np

    from distributed_machine_learning_tpu.cli.common import (
        init_model_and_state,
    )
    from distributed_machine_learning_tpu.models.vgg import VGGTest
    from distributed_machine_learning_tpu.parallel.fsdp import (
        make_fsdp_train_step,
        shard_fsdp_state,
    )
    from distributed_machine_learning_tpu.parallel.zero1 import (
        make_zero1_train_step,
        shard_zero1_state,
    )
    from distributed_machine_learning_tpu.runtime.mesh import make_mesh
    from distributed_machine_learning_tpu.train.step import shard_batch

    data_wait_s = data_wait_ms / 1e3
    mesh = make_mesh(8)
    n = 8
    model = VGGTest(use_bn=False)
    rng = np.random.default_rng(20250804)
    global_batch = per_device_batch * n
    host_batches = [
        (rng.integers(0, 256, (global_batch, 32, 32, 3), dtype=np.uint8),
         rng.integers(0, 10, global_batch).astype(np.int32))
        for _ in range(iters)
    ]
    batches = [shard_batch(mesh, x, y) for x, y in host_batches]

    rows = []

    for scheme, make in (
        ("zero1", lambda ov: (
            lambda s, u, ne: make_zero1_train_step(
                model, mesh, u, ne, augment=False, overlap=ov),
            shard_zero1_state,
        )),
        ("fsdp", lambda ov: (
            lambda s, u, ne: make_fsdp_train_step(
                model, mesh, u, ne, augment=False, overlap=ov),
            shard_fsdp_state,
        )),
    ):
        # A/B INTERLEAVED: both builds advance through the same batch
        # stream alternately, one iteration apiece, so slow host drift
        # (the dominant noise on a 1-core box whose conv timings wander
        # by several percent) hits both series equally instead of
        # whichever build ran second.
        runs = {}
        for build, overlap in (("sync", False), ("overlap", True)):
            make_step, shard = make(overlap)
            state, unravel, n_elems = shard(
                init_model_and_state(model), mesh)
            runs[build] = {
                "step": make_step(state, unravel, n_elems),
                "state": state, "iters": [], "loss": None,
            }
        for i, b in enumerate(batches):
            for build in ("sync", "overlap"):
                r = runs[build]
                if data_wait_s:
                    time.sleep(data_wait_s)
                t0 = time.perf_counter()
                r["state"], loss = r["step"](r["state"], b[0], b[1])
                r["loss"] = float(jax.block_until_ready(loss))
                if i > 0:
                    r["iters"].append(time.perf_counter() - t0)
        make_step, shard = make(True)
        gathers = _gather_spans(make_step, shard, model, batches[:8],
                                data_wait_s)
        for build in ("sync", "overlap"):
            r = runs[build]
            rows.append(_row(scheme, build, r["iters"],
                             gathers if build == "overlap" else [],
                             r["loss"]))
        assert runs["sync"]["loss"] == runs["overlap"]["loss"], (
            f"{scheme}: overlapped final loss != sync "
            "(the builds must be bit-identical)")

    rows += _bench_fsdp_lm(iters, data_wait_s)
    rows += _bench_pipeline(iters, data_wait_s)
    rows += _bench_3d_zero1(iters, data_wait_s)
    return rows


def _bench_fsdp_lm(iters: int, data_wait_s: float) -> list[dict]:
    """The params-heavy configuration (embedding+head dominate): the
    sync build's up-front all-gather is a real ~10% prelude on this
    host, so taking it off the critical path shows up directly in the
    loss-ready p50 — the one scheme whose gather latency the CPU host
    can render (the CNN rows' gathers are sub-noise memcpys).
    Interleaved A/B like the CNN rows."""
    import jax
    import numpy as np

    from distributed_machine_learning_tpu.models.transformer import (
        TransformerLM,
    )
    from distributed_machine_learning_tpu.parallel.fsdp import (
        make_fsdp_lm_train_step,
        shard_fsdp_state,
    )
    from distributed_machine_learning_tpu.runtime.mesh import make_mesh
    from distributed_machine_learning_tpu.train.adamw import AdamWConfig
    from distributed_machine_learning_tpu.train.lm_step import init_lm_state
    from distributed_machine_learning_tpu.train.step import shard_batch

    model = TransformerLM(vocab_size=1024, d_model=128, n_layers=2,
                          n_heads=4, attn_impl="dense")
    mesh = make_mesh(8)
    rng = np.random.default_rng(3)
    toks = rng.integers(0, 1024, (16, 33))
    mx, my = shard_batch(mesh, toks[:, :-1].astype(np.int32),
                         toks[:, 1:].astype(np.int32))

    runs = {}
    for build, ov in (("sync", False), ("overlap", True)):
        st, unravel, n = shard_fsdp_state(
            init_lm_state(model, seed=0, config=AdamWConfig()), mesh)
        runs[build] = {
            "step": make_fsdp_lm_train_step(model, mesh, unravel, n,
                                            overlap=ov),
            "state": st, "iters": [], "loss": None,
        }
    for i in range(iters):
        for build in ("sync", "overlap"):
            r = runs[build]
            if data_wait_s:
                time.sleep(data_wait_s)
            t0 = time.perf_counter()
            r["state"], loss = r["step"](r["state"], mx, my)
            r["loss"] = float(jax.block_until_ready(loss))
            if i > 1:
                r["iters"].append(time.perf_counter() - t0)
    assert runs["sync"]["loss"] == runs["overlap"]["loss"]
    return [
        _row("fsdp_lm", build, runs[build]["iters"], [],
             runs[build]["loss"])
        for build in ("sync", "overlap")
    ]


def _bench_pipeline(iters: int, data_wait_s: float) -> list[dict]:
    import numpy as np

    from distributed_machine_learning_tpu.models.transformer import (
        TransformerLM,
    )
    from distributed_machine_learning_tpu.parallel.pipeline import (
        init_pipeline_state,
        make_pp_lm_train_step,
        microbatch,
        shard_pp_state,
    )
    from distributed_machine_learning_tpu.runtime.mesh import make_mesh
    from distributed_machine_learning_tpu.train.adamw import AdamWConfig

    model = TransformerLM(vocab_size=256, d_model=64, n_layers=4,
                          n_heads=4)
    mesh = make_mesh(4, axis_names=("pipe",))
    rng = np.random.default_rng(7)
    toks = [rng.integers(0, 256, (8, 65)) for _ in range(iters)]
    batches = [
        microbatch(t[:, :-1].astype(np.int32), t[:, 1:].astype(np.int32),
                   2)
        for t in toks
    ]
    import jax

    # Interleaved A/B like the CNN rows: both builds alternate through
    # the same batch stream so host drift cancels.
    runs = {}
    for build, overlap in (("sync", False), ("overlap", True)):
        runs[build] = {
            "step": make_pp_lm_train_step(model, mesh, 2,
                                          overlap_update=overlap),
            "state": shard_pp_state(
                init_pipeline_state(model, config=AdamWConfig()), mesh),
            "iters": [], "loss": None,
        }
    for i, (x, y) in enumerate(batches):
        for build in ("sync", "overlap"):
            r = runs[build]
            if data_wait_s:
                time.sleep(data_wait_s)
            t0 = time.perf_counter()
            r["state"], loss = r["step"](r["state"], x, y)
            r["loss"] = float(jax.block_until_ready(loss))
            if i > 0:
                r["iters"].append(time.perf_counter() - t0)
    return [
        _row("pp_gpipe", build, runs[build]["iters"], [],
             runs[build]["loss"])
        for build in ("sync", "overlap")
    ]


def _bench_3d_zero1(iters: int, data_wait_s: float) -> list[dict]:
    """zero1×3-D with the annotated-dependency grad constraint (a
    partial-manual shard_map over the pipe axis)."""
    import jax
    import numpy as np

    from distributed_machine_learning_tpu.models.transformer import (
        TransformerLM,
    )
    from distributed_machine_learning_tpu.parallel.parallel3d import (
        init_pipeline_state,
        make_3d_lm_train_step,
        make_3d_mesh,
        microbatch,
        shard_3d_batch,
        shard_3d_state,
    )
    from distributed_machine_learning_tpu.train.adamw import AdamWConfig

    model = TransformerLM(vocab_size=64, d_model=32, n_layers=4,
                          n_heads=4)
    mesh = make_3d_mesh(2, 2, 2)
    rng = np.random.default_rng(7)
    rows = []
    for build, z1 in (("plain", False), ("zero1_dp", True)):
        state = shard_3d_state(
            init_pipeline_state(model, config=AdamWConfig()), mesh,
            zero1_dp=z1)
        step = make_3d_lm_train_step(model, mesh, 2, zero1_dp=z1)
        it = []
        loss = None
        for i in range(iters):
            t = rng.integers(0, 64, (8, 17))
            mx, my = shard_3d_batch(
                mesh, *microbatch(t[:, :-1].astype(np.int32),
                                  t[:, 1:].astype(np.int32), 2))
            if data_wait_s:
                time.sleep(data_wait_s)
            t0 = time.perf_counter()
            state, loss = step(state, mx, my)
            loss = jax.block_until_ready(loss)
            it.append(time.perf_counter() - t0)
        rows.append(_row("3d_zero1", build, it[1:], [], float(loss)))
    return rows


def main(argv=None) -> None:
    from distributed_machine_learning_tpu.runtime.compile_cache import (
        configure_compile_cache,
    )

    configure_compile_cache()
    from distributed_machine_learning_tpu.runtime.mesh import (
        ensure_host_devices,
    )

    ensure_host_devices(8)  # before the CPU client spins up
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--iters", default=24, type=int)
    parser.add_argument("--data-wait-ms", dest="data_wait_ms",
                        default=10.0, type=float)
    parser.add_argument("--per-device-batch", dest="per_device_batch",
                        default=16, type=int)
    parser.add_argument("--json", default=None,
                        help="write the rows to this path")
    args = parser.parse_args(argv)
    rows = bench_overlap(args.iters, args.data_wait_ms,
                         args.per_device_batch)
    out = {
        "metric": "overlap_weight_update",
        "iters": args.iters,
        "data_wait_ms": args.data_wait_ms,
        "rows": rows,
    }
    print(json.dumps(out, indent=2))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=2)


if __name__ == "__main__":
    main()
