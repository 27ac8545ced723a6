"""Per-iteration timing harness.

Reproduces the reference's measurement protocol (``part1/main.py:36,53-58``):
wall-clock per iteration, iteration 0 excluded as warm-up, totals and the
average over the remaining iterations printed at the end.  On TPU the
warm-up iteration is where XLA compilation lands, so excluding iteration 0
is exactly the right protocol here too — but the caller must block on the
device result (``jax.block_until_ready``) before stopping the clock, since
JAX dispatch is asynchronous (unlike the reference's synchronous CPU torch).
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Sequence


def percentile(times: Sequence[float], q: float) -> float:
    """Exact ``q``-quantile (``q`` in [0, 1]) by linear interpolation
    between order statistics (numpy's default method, stdlib-only so the
    tools layer can share it without dependencies)."""
    if not 0.0 <= q <= 1.0:
        raise ValueError(f"q must be in [0, 1], got {q}")
    if not times:
        return 0.0
    xs = sorted(times)
    rank = q * (len(xs) - 1)
    lo = int(rank)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (rank - lo) * (xs[hi] - xs[lo])


def percentile_stats(times: Sequence[float]) -> dict:
    """{p50, p95, p99, max} of a sample — the tail-latency block every
    timing surface shares, because a
    mean hides exactly the straggler steps production debugging needs
    (ISSUE 2; arxiv 1811.05233's per-phase accounting)."""
    return {
        "p50": percentile(times, 0.50),
        "p95": percentile(times, 0.95),
        "p99": percentile(times, 0.99),
        "max": max(times) if times else 0.0,
    }


@dataclass
class IterationTimer:
    """Accumulates per-iteration wall-clock, excluding `skip_first` iters.

    The reference runs 40 iterations and divides total by 39
    (``part1/main.py:53-58``): iteration 0 is measured but not accumulated.
    """

    skip_first: int = 1
    times: list = field(default_factory=list)
    _start: float = 0.0
    _iter: int = 0

    def start(self) -> None:
        self._start = time.perf_counter()

    @property
    def started(self) -> float:
        """The ``perf_counter`` reading of the newest :meth:`start`: with
        what :meth:`stop` returns, the iteration as a span, at no further
        clock read (``telemetry/startup.py``'s ``startup.first_step``)."""
        return self._start

    def stop(self) -> float:
        """Stop the clock; returns this iteration's time (always), and
        accumulates it unless it is among the first `skip_first` iters."""
        elapsed = time.perf_counter() - self._start
        if self._iter >= self.skip_first:
            self.times.append(elapsed)
        self._iter += 1
        return elapsed

    @property
    def total(self) -> float:
        return sum(self.times)

    @property
    def average(self) -> float:
        return self.total / len(self.times) if self.times else 0.0

    @property
    def count(self) -> int:
        return len(self.times)

    def percentiles(self) -> dict:
        """{p50, p95, p99, max} over the accumulated iterations."""
        return percentile_stats(self.times)

    def summary(self) -> str:
        # Same first two lines as the reference (part1/main.py:57-58);
        # the tail line is ours — the reference's average hides the
        # straggler iterations a per-step timeline exists to expose.
        p = self.percentiles()
        return (
            f"Total execution time is : {self.total} seconds\n"
            f"Average execution time is  : {self.average} seconds\n"
            f"Iteration time p50/p95/p99/max : {p['p50']:.6f}/"
            f"{p['p95']:.6f}/{p['p99']:.6f}/{p['max']:.6f} seconds"
        )
