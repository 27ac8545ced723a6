"""Regime-aware dispatch scheduler for the serving tier (ISSUE 19).

The serving tier has two levers: speculative decoding
wins latency when the batch is THIN (per-request wall time is
decode-step count; extra draft FLOPs are free at low occupancy), and
int8 weight-only ``quant_matmul`` wins throughput when the batch is
WIDE (decode is weight-bandwidth-bound; halving weight bytes ~halves
step time at large width).  The boundary between those regimes is a
function of *load*, not of the request — so the serving fleet needs a
policy object that watches load and flips the dispatch lever.

This module is that policy, deliberately tiny and jax-free:
:class:`RegimeScheduler` observes ``(queue_depth, in_flight_width)``
each engine step — both read through the telemetry registry's gauges
so dashboards see exactly what the policy saw — and returns which
lever the next step should use.  **Hysteresis** comes from two
mechanisms, both required to not thrash at the boundary:

* a **dead band**: pressure must reach ``wide_width`` to enter the
  throughput regime but fall to ``thin_width`` (< wide) to leave it —
  oscillation inside (thin, wide) never flips;
* a **dwell**: the out-of-regime pressure must persist for
  ``dwell_steps`` consecutive observations before the flip commits —
  a one-step spike (one bursty arrival, one long retire) is ignored.

The scheduler is consulted by the continuous-batching engine
(``inference/continuous.py``) per step, and by the router
(``runtime/serving.py``) at dispatch, which stamps the chosen lever
onto each request so every replica's engine follows one fleet-wide
regime instead of N drifting local views.
"""

from __future__ import annotations

import dataclasses
import threading

LATENCY = "latency"
THROUGHPUT = "throughput"


@dataclasses.dataclass(frozen=True)
class RegimeConfig:
    """Thresholds are in units of *pressure* = queued + in-flight
    requests at observation time.  Defaults suit a W=8-lane engine:
    <= 2 outstanding means requests mostly ride alone (latency
    regime); >= 6 means the batch runs wide (throughput regime)."""

    thin_width: int = 2
    wide_width: int = 6
    dwell_steps: int = 8

    def __post_init__(self):
        if self.thin_width < 0:
            raise ValueError(f"thin_width must be >= 0: {self.thin_width}")
        if self.wide_width <= self.thin_width:
            raise ValueError(
                f"need thin_width < wide_width for a dead band, got "
                f"{self.thin_width} >= {self.wide_width}"
            )
        if self.dwell_steps < 1:
            raise ValueError(f"dwell_steps must be >= 1: {self.dwell_steps}")


class RegimeScheduler:
    """Hysteretic two-regime lever policy.

    ``observe(queue_depth, width) -> "latency" | "throughput"``.
    Thread-safe: the router thread and an engine thread may both
    observe (the lock is a leaf — held for arithmetic only).
    """

    def __init__(self, cfg: RegimeConfig | None = None, registry=None):
        self.cfg = cfg or RegimeConfig()
        self._lock = threading.Lock()
        self.lever = LATENCY
        self.flips = 0
        self._streak = 0
        self._g_regime = self._g_pressure = self._c_flips = None
        if registry is not None:
            self._g_regime = registry.gauge("serving_regime")
            self._g_pressure = registry.gauge("serving_pressure")
            self._c_flips = registry.counter("serving_regime_flips")
            self._g_regime.set(0.0)

    def observe(self, queue_depth: int, width: int) -> str:
        """Feed one load sample; returns the lever for the next step."""
        pressure = int(queue_depth) + int(width)
        with self._lock:
            cfg = self.cfg
            if self.lever == LATENCY:
                wants_flip = pressure >= cfg.wide_width
            else:
                wants_flip = pressure <= cfg.thin_width
            if wants_flip:
                self._streak += 1
                if self._streak >= cfg.dwell_steps:
                    self.lever = (
                        THROUGHPUT if self.lever == LATENCY else LATENCY
                    )
                    self.flips += 1
                    self._streak = 0
                    if self._c_flips is not None:
                        self._c_flips.inc()
            else:
                self._streak = 0
            lever = self.lever
        if self._g_pressure is not None:
            self._g_pressure.set(float(pressure))
        if self._g_regime is not None:
            self._g_regime.set(1.0 if lever == THROUGHPUT else 0.0)
        return lever

    def snapshot(self) -> dict:
        with self._lock:
            return {"lever": self.lever, "flips": self.flips,
                    "streak": self._streak,
                    "thin_width": self.cfg.thin_width,
                    "wide_width": self.cfg.wide_width,
                    "dwell_steps": self.cfg.dwell_steps}
