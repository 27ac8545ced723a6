"""Shared scan-epoch timing harness for the benchmark entrypoints.

One copy of the measurement protocol (bench.py and bench/sweep.py both
use it): all timed iterations run as ONE jitted ``lax.scan`` over
pre-staged device-resident batches, and timing brackets a HOST VALUE
FETCH of the final loss.  Rationale — per-step Python dispatch is not
what the benchmark measures, and JAX dispatch is asynchronous, so a
timing must end in a fetch (or ``block_until_ready``) or it measures
the enqueue; the reference's excluded iteration 0
(``part1/main.py:53-58``) maps to the excluded compile run.
"""

from __future__ import annotations

import time

import jax
import numpy as np


def require_tpu() -> dict:
    """The device a measurement runs on, as JAX reports it — or exit.

    A time, a rate or a utilization is a statement about the chip; a
    measurement path that finds no TPU fails instead of continuing on
    another backend (JAX falls back to the CPU with only a warning when
    a TPU fails to initialise)."""
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"no TPU found: JAX's default backend is {dev.platform!r} "
            f"({dev.device_kind}); device metrics are only measured on "
            "the chip"
        )
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def chip_mfu(flops_per_sec: float, device: dict) -> float:
    """MFU of a :func:`require_tpu` device; exits on a kind the peak
    table (``utils/flops.py::DEVICE_PEAKS``) does not list."""
    from distributed_machine_learning_tpu.utils.flops import mfu

    util = mfu(flops_per_sec, device["kind"])
    if util is None:
        raise SystemExit(
            f"device kind {device['kind']!r} is not in the peak table "
            "(utils/flops.py::DEVICE_PEAKS); add it with its source"
        )
    return util


def two_point_fit(timed, chain: int) -> float:
    """Per-dispatch seconds from a two-point fit: ``timed(n)`` measures n
    back-to-back dispatches + one host fetch; the slope between the
    1-dispatch and chain-dispatch measurements cancels the constant
    per-measurement cost (first dispatch + the fetch).  Shared by
    bench.py and bench_lm.py so the methodology cannot diverge.

    Guards both sides: host jitter can make the slope exceed the chained
    average (impossible physically — take the min) or go non-positive
    (a slow t1, a fast tk — fall back to the overhead-inclusive
    chained average rather than report a negative time)."""
    t1 = timed(1)
    if chain <= 1:
        return t1
    tk = timed(chain)
    slope = (tk - t1) / (chain - 1)
    if slope <= 0:
        return tk / chain
    return min(slope, tk / chain)


def length_slope_fit(timed, n1: int, n2: int) -> float:
    """Per-unit seconds from measurements at two WORK SIZES ``n1 < n2``
    (scan lengths, generation lengths): slope ``(t2−t1)/(n2−n1)``
    cancels every size-independent cost (dispatch, fetch, prefill,
    compile-warm residue).  Jitter guard mirrors :func:`two_point_fit`:
    an impossible slope falls back to the overhead-inclusive average
    ``t2/n2``."""
    if not 0 < n1 < n2:
        raise ValueError(f"need 0 < n1 < n2, got ({n1}, {n2})")
    t1 = timed(n1)
    t2 = timed(n2)
    slope = (t2 - t1) / (n2 - n1)
    avg = t2 / n2
    return avg if slope <= 0 else min(slope, avg)


def cast_serving_params(params, dtype):
    """Serving cast (f32 leaves only → ``dtype``) — one definition for
    every bench's target and draft params."""
    return jax.tree_util.tree_map(
        lambda p: p.astype(dtype) if p.dtype == jax.numpy.float32 else p,
        params,
    )


def prepare_serving_params(master, quant: str | None, dtype=None):
    """The serving param pipeline every decode bench shares: int8
    quantization from the f32 master params (``quant="int8"``), or the
    compute-dtype cast.  One copy (bench_lm.py, bench/spec_trained.py)
    so the benches can never measure different pipelines."""
    if quant == "int8":
        from distributed_machine_learning_tpu.ops.quant import (
            quantize_lm_params,
        )

        return quantize_lm_params(master)
    return cast_serving_params(
        master, dtype if dtype is not None else jax.numpy.bfloat16
    )


def interleaved_ab(run_one: dict, iters: int, warmup: int = 1) -> dict:
    """The interleaved A/B measurement protocol (grown as round 11's
    ``--selector-ab``; one copy here so every A/B bench cancels drift
    the same way).

    ``run_one`` maps config name → ``fn(round_idx)`` running ONE
    complete iteration of that config INCLUDING the host sync (block
    on the value) — the function is the timed unit.  Each round runs
    one iteration of EVERY config back-to-back, so the 1-core host's
    ±5% sequential drift (thermal, scheduler, page cache) lands on all
    configs equally and cancels in the comparison instead of
    masquerading as a config cost — the failure mode of timing config
    A's block and then config B's block.  ``warmup`` rounds run
    untimed first (compile lands there, the reference's excluded
    iteration 0).

    Returns ``{name: [seconds, ...]}`` with ``iters`` timed samples
    per config, in round order.
    """
    times: dict = {k: [] for k in run_one}
    for r in range(warmup):
        for fn in run_one.values():
            fn(r)
    for r in range(iters):
        for k, fn in run_one.items():
            t0 = time.perf_counter()
            fn(r)
            times[k].append(time.perf_counter() - t0)
    return times


def two_point_dispatch(dispatch, fetch, reps: int, chain: int) -> float:
    """The decode benches' shared timing harness: best-of-``reps`` over
    n chained dispatches closed by one host fetch, per-dispatch seconds
    via :func:`two_point_fit` (cancels the per-measurement cost)."""

    def timed(n_dispatches):
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            out = None
            for _ in range(n_dispatches):
                out = dispatch()
            fetch(out)
            best = min(best, time.perf_counter() - t0)
        return best

    return two_point_fit(timed, chain)


def timed_scan_epoch(step, state, imgs, lbls, reps: int = 1, chain: int = 1,
                     stats: dict | None = None):
    """Time ``len(imgs)`` train steps as one compiled scan.

    ``step``: un-jitted ``(state, x, y) -> (state, loss)`` (build with
    ``make_train_step(..., jit=False)``).  ``imgs``/``lbls``: stacked
    [T, ...] device arrays, one leading slice per iteration.  Runs once
    untimed (compile, the reference's iteration 0), then ``reps`` timed
    runs; returns ``(best_seconds, final_loss, state)``.

    ``chain > 1`` measures by a two-point fit: each timed measurement
    still brackets dispatch + one host value fetch, but a second set of
    measurements enqueues ``chain`` back-to-back dispatches of the SAME
    epoch (every run starts from the untouched initial state, so the
    numerics of each are identical to the canonical single run — no
    1000-step divergence) before the single fetch, and the per-scan time
    is the slope ``(t_chain - t_1) / (chain - 1)``.  The constant
    per-measurement dispatch+fetch cost cancels in the subtraction,
    leaving device time per 39-step scan.  The reference's own protocol has no
    such overhead to exclude — its timer wraps on-node compute only
    (part1/main.py:53-58).

    ``stats``: optional dict, filled in place with the tail of the raw
    measurements — ``p50_s``/``p95_s``/``p99_s``/``max_s`` per-scan
    seconds plus ``samples`` — so bench result dicts report tail
    latency alongside the best (BENCH_*.json rounds must carry p95 with
    the mean; docs/PERF.md).  Computed over the LONGEST-chain regime
    only: the 1-dispatch measurements each carry the full fixed cost
    that the chained ones amortize chain-fold, so pooling the regimes
    would make "p95" measure the cost the two-point fit exists to
    cancel, not step stragglers.

    Raises ``RuntimeError`` on a non-finite final loss — a benchmark
    number from a diverged run must never be reported.
    """

    @jax.jit
    def run(state, imgs, lbls):
        def body(st, xy):
            st, loss = step(st, *xy)
            return st, loss

        return jax.lax.scan(body, state, (imgs, lbls))

    state0 = state
    out_state, losses = run(state0, imgs, lbls)
    final_loss = float(losses[-1])  # compile + completion
    if not np.isfinite(final_loss):
        raise RuntimeError(
            f"benchmark run diverged (final loss {final_loss}); refusing to "
            "report a throughput number"
        )

    samples: list[tuple[int, float]] = []  # (chain length, per-scan s)

    def timed(n_dispatches):
        """Best-of-reps seconds for n async same-epoch dispatches + 1 fetch."""
        best = float("inf")
        for _ in range(max(reps, 1)):
            start = time.perf_counter()
            for _ in range(n_dispatches):
                _, losses = run(state0, imgs, lbls)
            float(losses[-1])  # forces real device completion of the queue
            elapsed = time.perf_counter() - start
            samples.append((n_dispatches, elapsed / n_dispatches))
            best = min(best, elapsed)
        return best

    best = two_point_fit(timed, chain)
    if stats is not None:
        from distributed_machine_learning_tpu.utils.timing import (
            percentile_stats,
        )

        # Longest-chain regime only (see docstring): at chain=1 this is
        # the single regime, overhead-inclusive by necessity.
        longest = max(n for n, _ in samples)
        per_scan = [s for n, s in samples if n == longest]
        tail = percentile_stats(per_scan)
        stats.update(
            p50_s=tail["p50"], p95_s=tail["p95"], p99_s=tail["p99"],
            max_s=tail["max"], samples=len(per_scan),
        )
    return best, final_loss, out_state
