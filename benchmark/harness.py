"""The general run path of the benchmark: one cell, one process, one JSON line.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file found by the name ``BENCHMARK.json`` gives it:

- ``<configs[].file>``: the configuration's sizes, with ``family`` naming the
  module under ``benchmark/families/`` that knows how to start such a model
  through its CLI and which plain reference checks it;
- ``<paths[0]>/traffic/<traffic>.json``: the job's shape (CLI, argv, batch a
  chip, sequence length) and the ``data`` parameters ``generate.py`` reads;
- ``<paths[0]>/metrics/<metric>.json``: ``{"reader": ..., "args": {...}}`` for
  a per-layer metric, read by ``benchmark/readers/<reader>.py``.

A later PR adds a cell, a configuration or a metric by adding such files and
an entry to ``BENCHMARK.json``.

The window runs the program's own ``train/loop.py::train_epoch`` with the
step, state and placement its CLI built.  The benchmark times at its own
iterator: the time between two ``next()`` calls is one step period, whatever
the loop does in between.
"""

from __future__ import annotations

import contextlib
import dataclasses
import glob
import importlib
import json
import math
import os
import shutil
import statistics
import sys
import tempfile
import time
from typing import Any, Callable, Iterator

from benchmark import flops, trace_reduce

#: Steps run and thrown away before the window opens (the first iterations
#: after set-up still fill the loader's queue and the allocator's pools).
DISCARD_STEPS = 3
#: Loss is printed at these window steps, so that two commits with one seed
#: can be compared by eye.
LOSS_AT = (0, 1, 2, 5, 10, 20, 50, 100, 200, 500, 1000, 2000, 5000)
COMPILE_EVENTS = (
    "/jax/core/compile/backend_compile_duration",
    "/jax/compilation_cache/cache_retrieval_time_sec",
)


@dataclasses.dataclass
class Cell:
    """What a family hands back after set-up."""

    result: Any                       # the CLI's RunResult
    batches: Callable[[], Iterator]   # endless host batches from the seed
    item: str                         # "images" | "tokens"
    items_per_step: int
    flops_per_item: float
    check: Callable[[], dict]         # plain-reference comparison
    loss_must_fall: bool              # mean loss, last tenth <= first tenth


class Refused(SystemExit):
    """The run cannot be a measurement (no TPU, wrong chip count, unknown
    cell): exit code 1 and no result line."""

    def __init__(self, message: str):
        print(f"benchmark: {message}", file=sys.stderr, flush=True)
        super().__init__(1)


def load_json(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def by_name(entries: list[dict], name: str, what: str) -> dict:
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise Refused(f"no {what} named {name!r} in BENCHMARK.json")


def metrics_of(manifest: dict, group: str, cell: str) -> list[dict]:
    """The ``group`` metrics this cell reports: those with no ``workloads``
    key and those that list it."""
    return [m for m in manifest[group]
            if "workloads" not in m or cell in m["workloads"]]


def load_cell(root: str, workload: str) -> dict:
    manifest = load_json(os.path.join(root, "BENCHMARK.json"))
    cell = by_name(manifest["workloads"], workload, "workload")
    config_entry = by_name(manifest["configs"], cell["config"], "config")
    data_dir = os.path.join(root, manifest["paths"][0])
    return {
        "manifest": manifest,
        "cell": cell,
        "config": load_json(os.path.join(root, config_entry["file"])),
        "traffic": load_json(
            os.path.join(data_dir, "traffic", cell["traffic"] + ".json")),
        "metrics_dir": os.path.join(data_dir, "metrics"),
    }


def require_device(chips: int, require_tpu: bool) -> dict:
    """The device as JAX reports it; refuses anything but ``chips`` chips of
    a TPU the peaks table lists.  ``require_tpu=False`` is the tests' seam
    (a Python argument only): any backend, any count."""
    import jax

    devices = jax.devices()
    first = devices[0]
    if require_tpu:
        if first.platform != "tpu":
            raise Refused(
                f"JAX found platform {first.platform!r}, not a TPU; this "
                "benchmark measures only on the chip")
        try:
            flops.peak_for(first.device_kind)
        except KeyError as e:
            raise Refused(str(e)) from e
        if len(devices) != chips:
            raise Refused(
                f"the cell asks for {chips} chip(s) and JAX holds "
                f"{len(devices)}")
    return {"platform": first.platform, "kind": first.device_kind,
            "count": len(devices)}


def memory_peak_bytes(probe: "StepProbe") -> int:
    """Peak device memory on the fullest chip.  This runtime's
    ``peak_bytes_in_use`` counts the buffers the client holds (state,
    batches, results); what a program allocates while it runs is reserved
    apart (``peak_bytes_reserved``) and not in it: my chip runs, PR 24, read
    0.25 GiB in use under a VGG step whose activations take 4.75 GiB, and
    5.090e9 bytes reserved where the compiler states 5.097e9 of temporaries.
    So the peak is the step program's footprint as the compiler reports it
    for the very program that ran — arguments + results − aliased (donated)
    + temporaries — or the allocator's own peak where that is higher."""
    import jax

    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    t = time.perf_counter()
    analysis = probe.compiled_memory()
    footprint = (analysis.argument_size_in_bytes
                 + analysis.output_size_in_bytes
                 - analysis.alias_size_in_bytes
                 + analysis.temp_size_in_bytes)
    print("bench.memory " + json.dumps({
        "allocator": stats[0],
        "step_program": {k: getattr(analysis, k) for k in (
            "argument_size_in_bytes", "output_size_in_bytes",
            "alias_size_in_bytes", "temp_size_in_bytes")},
        "analysis_s": time.perf_counter() - t,
    }), flush=True)
    if not any(stats):  # a backend without an allocator to ask (the CPU)
        return 0
    return int(max(footprint,
                   max(s.get("peak_bytes_in_use", 0) for s in stats)))


class CompileCounter:
    """Counts programs compiled or fetched from the persistent cache while
    ``active`` — inside the window there must be none."""

    def __init__(self):
        self.active = False
        self.count = 0

    def _on_event(self, name: str, *_args, **_kw) -> None:
        if self.active and name in COMPILE_EVENTS:
            self.count += 1

    def __enter__(self):
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self._on_event)


def annotate(name: str):
    import jax.profiler

    return jax.profiler.TraceAnnotation(name)


def _abstract(tree):
    import jax

    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=getattr(a, "sharding", None)),
        tree)


class StepProbe:
    """The compiled step, with each step's loss array kept (a reference, no
    host sync) and, when tracing, a host span around the dispatch.  Other
    attributes (``pop_gather_seconds``, ``lower``) are the step's own."""

    def __init__(self, step, traced: bool):
        self._step = step
        self._traced = traced
        self._signature = None
        self.losses: list = []

    def __call__(self, state, *batch):
        if self._signature is None:
            self._signature = _abstract((state, *batch))
        with annotate("bench.step_dispatch") if self._traced \
                else contextlib.nullcontext():
            out = self._step(state, *batch)
        self.losses.append(out[1])
        return out

    def compiled_memory(self):
        """The compiler's memory analysis of the step as the window called
        it (same shapes, dtypes, shardings: the persistent cache serves the
        executable again)."""
        return self._step.lower(*self._signature).compile().memory_analysis()

    def __getattr__(self, name):
        return getattr(self._step, name)

    def sync(self) -> None:
        import jax

        if self.losses:
            jax.block_until_ready(self.losses[-1])


class Window:
    """The benchmark's iterator around the host batches.  Timestamps every
    ``next()``; opens the window after ``DISCARD_STEPS`` steps; ends the epoch at
    the first ``next()`` at or past the deadline, after waiting for the
    newest step, so a loop that stops blocking every iteration is still
    measured whole.  When ``trace_steps`` is set, the profiler runs for that
    many steps from the window's opening, between two syncs."""

    def __init__(self, source: Iterator, seconds: float, sync: Callable,
                 trace_steps: int = 0, trace_dir: str | None = None,
                 host_events: bool = True):
        self._host_events = host_events
        self._source = source
        self._seconds = seconds
        self._sync = sync
        self._trace_steps = trace_steps
        self._trace_dir = trace_dir
        self._stretch = None
        self.entries: list[float] = []
        self.t_open: float | None = None
        self.t_close: float | None = None
        self.traced_range: tuple[int, int] | None = None

    def __iter__(self):
        return self

    def _start_trace(self, index: int) -> None:
        import jax.profiler

        # Host events at the critical level only (the benchmark's own
        # annotations), no Python tracer.  A traffic file turns host events
        # off altogether (``trace_host_events``) where the runtime's own
        # critical-level events flood the trace: placing a 25 MB uint8 image
        # batch writes 600 k ``TransposePlan::ExecuteTyped`` events a step and
        # stretches a 0.1 s step to 1.4 s (my chip runs, PR 24).  Idle gaps
        # then go unnamed, and the stretch is bounded by the device's first
        # and last operation.
        options = jax.profiler.ProfileOptions()
        options.host_tracer_level = 1 if self._host_events else 0
        options.python_tracer_level = 0
        jax.profiler.start_trace(self._trace_dir, profiler_options=options)
        self._stretch = annotate(trace_reduce.STRETCH)
        self._stretch.__enter__()
        self.traced_range = (index, index + self._trace_steps)

    def _stop_trace(self, index: int) -> None:
        import jax.profiler

        self._sync()
        self._stretch.__exit__(None, None, None)
        self._stretch = None
        jax.profiler.stop_trace()
        self.traced_range = (self.traced_range[0], index)  # steps traced

    def __next__(self):
        index = len(self.entries)
        if self._stretch is not None and index >= self.traced_range[1]:
            self._stop_trace(index)
        now = time.perf_counter()
        if self.t_open is not None and now - self.t_open >= self._seconds:
            if self._stretch is not None:
                self._stop_trace(index)
            self._sync()
            self.t_close = time.perf_counter()
            raise StopIteration
        if index == DISCARD_STEPS:
            self._sync()
            if self._trace_steps:
                self._start_trace(index)
            now = time.perf_counter()
            self.t_open = now
        self.entries.append(now)
        with annotate("bench.data_next") if self._stretch is not None \
                else contextlib.nullcontext():
            return next(self._source)

    @property
    def periods(self) -> list[float]:
        """Step periods inside the window, seconds."""
        marks = self.entries[DISCARD_STEPS:] + [self.t_close]
        return [b - a for a, b in zip(marks, marks[1:])]


def read_step_rows(telemetry_dir: str) -> list[dict]:
    rows = []
    for path in glob.glob(os.path.join(telemetry_dir, "metrics*.jsonl")):
        with open(path, encoding="utf-8") as f:
            rows.extend(json.loads(line) for line in f if line.strip())
    return [r for r in rows if "data_wait_s" in r]


def run_window(cell: Cell, seconds: float, trace: bool, trace_steps: int,
               host_events: bool, scratch: str) -> dict:
    """Runs ``train_epoch`` for ``seconds`` and returns what was seen."""
    import jax
    import numpy as np

    from distributed_machine_learning_tpu.telemetry import Telemetry
    from distributed_machine_learning_tpu.train.loop import train_epoch

    result = cell.result
    probe = StepProbe(result.train_step, traced=trace)
    trace_dir = os.path.join(scratch, "trace")
    window = Window(cell.batches(), seconds, probe.sync,
                    trace_steps=trace_steps if trace else 0,
                    trace_dir=trace_dir, host_events=host_events)
    place = result.place_batch
    if trace and place is not None:
        def place(*batch, _place=result.place_batch):
            with annotate("bench.place_batch"):
                return _place(*batch)
    telemetry = (Telemetry(os.path.join(scratch, "telemetry"), fsync=False)
                 if trace else None)
    step_before = int(jax.device_get(result.state.step))
    with CompileCounter() as compiles:
        compiles.active = True
        try:
            state, _ = train_epoch(
                probe, result.state, window, place_batch=place,
                max_iters=10**12, loss_print_every=10**12,
                telemetry=telemetry,
            )
        finally:
            compiles.active = False
            if telemetry is not None:
                telemetry.close()
    steps_run = len(window.entries)
    applied = int(jax.device_get(state.step)) - step_before
    losses = [float(np.mean(np.asarray(v)))
              for v in jax.device_get(probe.losses)]
    out = {
        "window": window,
        "probe": probe,
        "losses": losses[DISCARD_STEPS:],
        "skipped": steps_run - applied,
        "compiles": compiles.count,
        "step_rows": [],
        "trace": None,
        "inventory": None,
    }
    if trace:
        rows = read_step_rows(os.path.join(scratch, "telemetry"))
        lo, hi = window.traced_range or (0, 0)
        # The traced steps (and the one that pays for stopping the
        # profiler) carry the tracer's cost, the discarded ones the warm-up.
        out["step_rows"] = [r for r in rows
                            if r["batch"] >= DISCARD_STEPS
                            and not lo <= r["batch"] <= hi]
        files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if files:
            records = trace_reduce.load_xplane(files[0])
            out["inventory"] = trace_reduce.inventory(records)
            out["trace"] = trace_reduce.reduce(records, steps=hi - lo)
    return out


def per_layer_values(wanted: list[dict], metrics_dir: str,
                     context: dict) -> dict:
    """Each per-layer metric through the reader its file names; a reader
    that finds nothing to read returns None and the metric is left out."""
    values = {}
    for metric in wanted:
        how = load_json(os.path.join(metrics_dir, metric["name"] + ".json"))
        reader = importlib.import_module("benchmark.readers." + how["reader"])
        value = reader.read(context, **how.get("args", {}))
        if value is not None:
            values[metric["name"]] = value
    return values


def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace: bool, t0: float, require_tpu: bool = True) -> dict:
    """One run of one cell; returns the result object (and prints the
    earlier lines).  ``require_tpu=False`` is for the tests alone."""
    import numpy as np

    spec = load_cell(root, workload)
    cell_entry, traffic = spec["cell"], spec["traffic"]
    device = require_device(cell_entry["chips"], require_tpu)
    print("bench.device " + json.dumps(device), flush=True)
    family = importlib.import_module(
        "benchmark.families." + spec["config"]["family"])
    # Under the driver's TMPDIR, removed before the run ends.
    scratch = tempfile.mkdtemp(prefix="bench_run_")
    try:
        t_start = time.perf_counter()
        cell: Cell = family.setup(spec["config"], traffic, seed)
        t_ready = time.perf_counter()
        check = cell.check()
        print("bench.check " + json.dumps(check, default=float), flush=True)
        print("bench.setup " + json.dumps({
            "imports_and_device_s": t_start - t0,
            "cli_and_data_s": t_ready - t_start,
            "reference_check_s": time.perf_counter() - t_ready,
        }), flush=True)
        seen = run_window(cell, seconds, trace,
                          int(traffic.get("trace_steps", 5)),
                          bool(traffic.get("trace_host_events", True)),
                          scratch)
    finally:
        shutil.rmtree(scratch, ignore_errors=True)
    window: Window = seen["window"]
    periods, losses = window.periods, seen["losses"]
    steps = len(periods)
    duration = window.t_close - window.t_open
    chips = cell_entry["chips"]
    rate = steps * cell.items_per_step / duration / chips
    nonfinite = sum(1 for v in losses if not math.isfinite(v))
    tenth = max(1, steps // 10)
    first, last = (statistics.fmean(losses[:tenth]),
                   statistics.fmean(losses[-tenth:]))
    fell = last <= first
    print("bench.loss_at " + json.dumps(
        {str(i): losses[i] for i in LOSS_AT if i < steps}), flush=True)
    print("bench.window " + json.dumps({
        "steps": steps, "seconds": duration,
        "compilations_in_window": seen["compiles"],
        "step_ms_median": 1e3 * statistics.median(periods),
        "items_per_step": cell.items_per_step,
        "flops_per_item": cell.flops_per_item,
        "loss_first_tenth": first, "loss_last_tenth": last,
        "guard_skipped": seen["skipped"],
    }), flush=True)
    device["memory_peak_bytes"] = memory_peak_bytes(seen["probe"])
    wanted = metrics_of(spec["manifest"],
                        "per_layer" if trace else "end_to_end", workload)
    breakdown = None
    if trace:
        if seen["inventory"] is not None:
            print("bench.trace_inventory " + json.dumps(seen["inventory"]),
                  flush=True)
        values = per_layer_values(wanted, spec["metrics_dir"], {
            "step_rows": seen["step_rows"], "trace": seen["trace"],
            "memory_peak_bytes": device["memory_peak_bytes"]})
        reduced = seen["trace"]
        if reduced is not None:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            breakdown = {"device_ops": reduced["device_ops"],
                         "idle_gaps": reduced["idle_gaps"]}
    else:
        values = {
            f"{cell.item}_per_s_chip": rate,
            "step_ms_p90": 1e3 * float(np.percentile(periods, 90.0)),
            "setup_s": window.t_open - t0,
        }
        if require_tpu:  # no utilization of a device without listed peaks
            values["mfu_pct"] = flops.mfu_pct(
                cell.flops_per_item, rate, device["kind"])
    units = {m["name"]: m["unit"] for m in wanted}
    out = {
        "correct": bool(check["ok"] and seen["compiles"] == 0
                        and nonfinite == 0 and seen["skipped"] == 0
                        and (fell or not cell.loss_must_fall)),
        "attempted": steps,
        "failed": max(nonfinite, seen["skipped"]),
        "metrics": {name: {"value": values[name], "unit": units[name]}
                    for name in units if name in values},
        "device": device,
    }
    if breakdown is not None:
        out["breakdown"] = breakdown
    return out


def main(argv: list[str], t0: float) -> int:
    import argparse

    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = run_cell(root, args.workload, args.seed, args.seconds,
                   bool(args.trace), t0)
    print(json.dumps(out), flush=True)
    return 0
