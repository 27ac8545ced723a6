#!/usr/bin/env python3
"""Summarize a telemetry directory — no third-party imports, jax-free.

Reads the artifacts a run's ``--telemetry-dir`` produced
(``distributed_machine_learning_tpu/telemetry/``) and prints:

- per-phase time shares from the Chrome trace's complete events
  (data_wait / place_batch / step_dispatch / device_block, the loop's
  own self time where the trace has ``train_step`` parents, the next
  batch's arrival as an overlay /
  checkpoint_save / eval / ...), the first diagnosis dimension for
  stragglers and sync overhead — trace *instants* (fault markers,
  gang_shrink, restarts) are counted in the same table: a fault that
  fired during a phase is the context that phase's duration needs;
- the top-5 slowest steps from the metrics JSONL (attempt-tagged), with
  their phase breakdown, and the share of steps that hid their next
  batch's arrival (``batch_lead_s`` > 0);
- attempt/restart structure when the run was supervised.

Tolerates the artifacts of a crash: a torn final JSONL line and an
unterminated trace array are both read to the last complete record —
this tool's main job is diagnosing runs that died.

Usage:  python tools/trace_summary.py <telemetry-dir> [--top N]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

# One source of truth for the tolerant readers: the modules that WRITE
# the artifacts also own the readers that decode them (so the formats
# cannot drift apart).  These imports are jax-free by construction (jax
# only loads lazily inside the sinks' write paths) — this tool stays
# runnable on a bare host; the path bootstrap makes it runnable from
# anywhere, not just the repo root.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
from distributed_machine_learning_tpu.telemetry.sink import (  # noqa: E402
    read_jsonl,
)
from distributed_machine_learning_tpu.telemetry.tracer import (  # noqa: E402
    read_trace,
)
from distributed_machine_learning_tpu.utils.timing import (  # noqa: E402
    percentile,
)

METRICS_FILE = "metrics.jsonl"
TRACE_FILE = "trace.json"
REGISTRY_FILE = "registry.json"

# The per-step driver phases, in pipeline order (other spans —
# checkpoint_save, eval, restart_attempt — are reported after these).
STEP_PHASES = ("data_wait", "place_batch", "step_dispatch", "device_block")
# The parent of one iteration's phases (fetch start to next fetch start).
# What of it the phases do not cover is the loop's self time: telemetry
# rows, tracer writes, watchdog, prints — the row field ``loop_self_s``.
STEP_PARENT = "train_step"
# Spans that run CONCURRENTLY with the pipeline phases, and under which:
# shown in the phase table for visibility, but excluded from the pipeline
# total — counting an overlapped span into the denominator would misstate
# every share.  ``param_gather``: the overlap-aware sharded update's
# consume-phase gather.  ``batch_ready``: placement call to the placed
# batch resident on every shard, waited for at the head of device_block.
# The loop holds one batch ahead, so the span tagged step k is batch
# k+1's arrival: it lies under step k's placement and block, the block of
# the step BEFORE the one that trains the batch.
OVERLAY_PHASES = {
    "param_gather": "the phases from one dispatch to the next",
    "batch_ready": "place_batch/device_block of the step before "
                   "(one batch ahead)",
}


def summarize(telemetry_dir: str, top: int = 5) -> str:
    lines: list[str] = []
    trace_path = os.path.join(telemetry_dir, TRACE_FILE)
    metrics_path = os.path.join(telemetry_dir, METRICS_FILE)

    # -- per-phase shares from the trace --------------------------------
    if os.path.isfile(trace_path):
        all_events = [e for e in read_trace(trace_path)
                      if isinstance(e, dict)]
        events = [e for e in all_events if e.get("ph") == "X"]
        # Instants (ph "i") are the zero-duration markers — injected
        # faults, gang aborts/shrinks, worker starts.  They were
        # silently dropped before this fix; a phase table that omits
        # the fault fired mid-phase misreads the run it summarizes.
        instants: dict[str, int] = {}
        for e in all_events:
            if e.get("ph") == "i":
                name = str(e.get("name", "?"))
                instants[name] = instants.get(name, 0) + 1
        by_name: dict[str, dict] = {}
        for e in events:
            name = e.get("name", "?")
            if (e.get("args") or {}).get("primed"):
                # Batch 0's fetch and placement, before the first
                # iteration: under no train_step parent, so not among
                # the shares of the loop's wall-clock.
                name += "[primed]"
            d = by_name.setdefault(name, {"dur": 0.0, "count": 0})
            d["dur"] += float(e.get("dur", 0.0))
            d["count"] += 1
        phase_total = sum(
            by_name.get(p, {"dur": 0.0})["dur"] for p in STEP_PHASES
        )
        # With the phases' parents in the trace the shares are of the
        # loop's whole wall-clock, self time included (clamped: a killed
        # run can leave one step's phases without their parent).
        parent = by_name.get(STEP_PARENT)
        loop_self = (max(parent["dur"] - phase_total, 0.0)
                     if parent is not None else None)
        if loop_self is not None:
            phase_total += loop_self
        lines.append(f"== Phase time shares ({trace_path}) ==")
        if phase_total > 0:
            for p in STEP_PHASES:
                d = by_name.get(p)
                if d is None:
                    continue
                share = 100.0 * d["dur"] / phase_total
                lines.append(
                    f"  {p:<14} {share:5.1f}%  "
                    f"({d['dur'] / 1e6:.3f}s over {d['count']} spans)"
                )
            if loop_self is not None:
                lines.append(
                    f"  {'loop_self':<14} "
                    f"{100.0 * loop_self / phase_total:5.1f}%  "
                    f"({loop_self / 1e6:.3f}s of {parent['count']} "
                    f"{STEP_PARENT} spans outside the phases above)"
                )
            for p, under in OVERLAY_PHASES.items():
                d = by_name.get(p)
                if d is None:
                    continue
                # Reported against the same pipeline total so "how much
                # of a step the gather spans" reads directly, but
                # flagged: this time runs UNDER the phases above
                # (overlap-aware update), not in addition to them.
                share = 100.0 * d["dur"] / phase_total
                lines.append(
                    f"  {p:<14} {share:5.1f}%  "
                    f"({d['dur'] / 1e6:.3f}s over {d['count']} spans, "
                    f"overlapped — runs under {under})"
                )
        other = sorted(
            (n for n in by_name
             if n not in STEP_PHASES and n not in OVERLAY_PHASES
             and n != STEP_PARENT),
            key=lambda n: -by_name[n]["dur"],
        )
        for n in other:
            d = by_name[n]
            lines.append(
                f"  {n:<14} ------  "
                f"({d['dur'] / 1e6:.3f}s over {d['count']} spans)"
            )
        for n in sorted(instants, key=lambda n: (-instants[n], n)):
            lines.append(f"  {n:<14} ------  ({instants[n]} instant(s))")
        if not by_name and not instants:
            lines.append("  (no complete events)")
    else:
        lines.append(f"== No trace at {trace_path} ==")

    # -- slowest steps from the metrics stream --------------------------
    if os.path.isfile(metrics_path):
        all_rows = [r for r in read_jsonl(metrics_path)
                    if isinstance(r, dict) and "iter_s" in r]
        # Warm-up iterations (XLA compile; timer-excluded, row-tagged)
        # would otherwise head every "slowest" list and own the tail.
        rows = [r for r in all_rows if not r.get("warmup")]
        n_warm = len(all_rows) - len(rows)
        lines.append(f"== Steps ({metrics_path}) ==")
        if rows:
            iters = [float(r["iter_s"]) for r in rows]
            attempts = sorted({int(r.get("attempt", 0)) for r in all_rows})
            lines.append(
                f"  {len(rows)} step rows over attempt(s) "
                f"{','.join(map(str, attempts))}"
                + (f" (+{n_warm} warm-up rows excluded)" if n_warm else "")
                + f"; iter_s "
                f"p50 {percentile(iters, 0.5):.6f}  "
                f"p95 {percentile(iters, 0.95):.6f}  "
                f"p99 {percentile(iters, 0.99):.6f}  "
                f"max {max(iters):.6f}"
            )
            selfs = [float(r["loop_self_s"]) for r in rows
                     if "loop_self_s" in r]
            if selfs:
                # Row k carries iteration k-1's: the period's remainder
                # after the four phases, telemetry's own cost included.
                lines.append(
                    f"  loop_self_s (the loop's own time a step) "
                    f"p50 {percentile(selfs, 0.5):.6f}  "
                    f"p95 {percentile(selfs, 0.95):.6f}  "
                    f"max {max(selfs):.6f}"
                )
            leads = [float(r["batch_lead_s"]) for r in rows
                     if "batch_lead_s" in r]
            if leads:
                # One batch ahead: positive = the next batch was resident
                # that long before this step's loss came back (its arrival
                # hidden under the step); negative = the block waited that
                # long for the batch with the device already empty.
                hidden = sum(1 for v in leads if v > 0)
                lines.append(
                    f"  batch_lead_s (next batch resident before the "
                    f"loss) hidden in {hidden} of {len(leads)} steps "
                    f"({100.0 * hidden / len(leads):.1f}%)  "
                    f"p50 {percentile(leads, 0.5):.6f}  "
                    f"min {min(leads):.6f}"
                )
            lines.append(f"  top-{top} slowest steps:")
            slowest = sorted(rows, key=lambda r: -float(r["iter_s"]))[:top]
            for r in slowest:
                phases = "  ".join(
                    f"{k}={float(r[k]):.6f}"
                    for k in ("data_wait_s", "place_s", "dispatch_s",
                              "block_s", "batch_ready_s", "batch_lead_s",
                              "loop_self_s", "param_gather_s")
                    if k in r
                )
                lines.append(
                    f"    step {r.get('step', '?'):>6}  attempt "
                    f"{r.get('attempt', 0)}  iter_s "
                    f"{float(r['iter_s']):.6f}  {phases}"
                )
        else:
            lines.append("  (no step rows)")
    else:
        lines.append(f"== No metrics at {metrics_path} ==")

    # -- fault counters, if the registry snapshot landed ----------------
    reg_path = os.path.join(telemetry_dir, REGISTRY_FILE)
    if os.path.isfile(reg_path):
        with open(reg_path) as f:
            snap = json.load(f)
        faults = [c for c in snap.get("counters", [])
                  if c.get("name") == "fault_events"]
        if faults:
            lines.append(f"== Fault events ({reg_path}) ==")
            for c in sorted(faults, key=lambda c: c["labels"].get("kind", "")):
                lines.append(
                    f"  {c['labels'].get('kind', '?'):<18} {c['value']}"
                )
        # -- ring wire compression, if the run synced through the ring --
        wire = [c for c in snap.get("counters", [])
                if c.get("name") == "ring_wire_bytes"]
        ratio = [g for g in snap.get("gauges", [])
                 if g.get("name") == "ring_compression_ratio"]
        if wire:
            total = sum(c.get("value", 0) for c in wire)
            r = ratio[0].get("value") if ratio else None
            lines.append("== Ring wire compression ==")
            lines.append(f"  wire bytes (whole run)   {total:,.0f}")
            # Per-axis split (round 11): a --ring-topology run labels
            # the counter {axis=inner|outer}; the outer (inter-node)
            # share is the link the hierarchy exists to relieve.  Flat
            # runs carry {axis=flat} and skip the breakdown.
            by_axis = {}
            for c in wire:
                ax = (c.get("labels") or {}).get("axis", "flat")
                by_axis[ax] = by_axis.get(ax, 0) + c.get("value", 0)
            if set(by_axis) - {"flat"} and total:
                for ax in ("inner", "outer", "flat"):
                    if ax in by_axis:
                        lines.append(
                            f"    axis={ax:<6} {by_axis[ax]:>14,.0f}  "
                            f"({100 * by_axis[ax] / total:.0f}%)"
                        )
            if r:
                lines.append(f"  compression ratio        {r:.2f}x "
                             f"(exact/compressed)")
                if r > 1:
                    saved = total * (r - 1)
                    lines.append(
                        f"  bytes saved vs exact     {saved:,.0f}"
                    )
    return "\n".join(lines)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("telemetry_dir", help="directory a run's "
                                              "--telemetry-dir pointed at")
    parser.add_argument("--top", default=5, type=int,
                        help="how many slowest steps to list (default 5)")
    args = parser.parse_args(argv)
    if not os.path.isdir(args.telemetry_dir):
        print(f"not a directory: {args.telemetry_dir}", file=sys.stderr)
        return 2
    try:
        print(summarize(args.telemetry_dir, top=args.top))
    except BrokenPipeError:  # `| head` closed the pipe — not an error
        os.dup2(os.open(os.devnull, os.O_WRONLY), sys.stdout.fileno())
    return 0


if __name__ == "__main__":
    sys.exit(main())
