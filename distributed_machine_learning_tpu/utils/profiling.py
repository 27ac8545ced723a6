"""Tracing / profiling + structured per-step metrics.

The reference's observability is a hand-rolled wall-clock harness
(``part1/main.py:36,53-58``) plus out-of-band dstat plots in its report
(group25.pdf p.4,7) — SURVEY.md §5.  TPU-native equivalents:

- :func:`trace` — context manager around ``jax.profiler`` producing an
  XPlane/Perfetto trace directory (the principled replacement for the
  report's external CPU/network plots: the trace shows MXU occupancy,
  HBM traffic, and ICI collective time per step).
- :class:`MetricsLogger` — per-step structured metrics (step, loss,
  wall-clock) accumulated in memory and flushed to CSV and/or JSONL,
  rank-0 gated; feeds the scaling-sweep harness.
- :func:`annotate` — ``jax.profiler.TraceAnnotation`` wrapper:
  ``train_epoch`` brackets each iteration (``train.step``) and its phases
  (``train.data_wait`` … ``train.bookkeeping``) with it, so they show up
  as named host spans beside the device's operations in the trace.
- :class:`Timed` — that annotation plus the two host-clock reads of a
  tracer span: the one bracket of the loop's phases and the start-up spans.
"""

from __future__ import annotations

import contextlib
import csv
import json
import os
import time
from dataclasses import dataclass, field

import jax


@contextlib.contextmanager
def trace(log_dir: str | os.PathLike | None):
    """Profile the enclosed block with ``jax.profiler`` into `log_dir`.

    No-op when `log_dir` is falsy, so call sites can thread a CLI flag
    straight through.  View the result with TensorBoard's profile plugin
    or Perfetto (the trace directory contains ``*.xplane.pb``).
    """
    if not log_dir:
        yield
        return
    log_dir = os.fspath(log_dir)
    os.makedirs(log_dir, exist_ok=True)
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def annotate(name: str, step_num: int | None = None):
    """Named host span on the profiler's clock — the device trace's own,
    so the span lies against the device's ``XLA Ops`` line.  With no
    profiler session the span costs a flag test.  ``step_num`` makes it a
    ``StepTraceAnnotation``: the span carries the step's number, which
    viewers that group by step (XProf) give to the spans and device
    operations inside it (``train/loop.py::train_epoch`` brackets each
    iteration so).  The device plane's own ``Steps`` line in the raw
    ``.xplane.pb`` keeps the runtime's count from 0 (PERF.md, PR 25)."""
    if step_num is not None:
        return jax.profiler.StepTraceAnnotation(name, step_num=step_num)
    return jax.profiler.TraceAnnotation(name)


class Timed:
    """The one span bracket, on both clocks: the profiler annotation
    ``name`` (:func:`annotate`) and, inside it, the two ``perf_counter``
    reads a ``SpanTracer`` span is made from, handed to ``done(name, t0,
    t1)`` as the block ends (also when it raises).  ``train_epoch``'s
    phases under a ``Telemetry`` and the start-up record's spans
    (``telemetry/startup.py``) are both this.  As the annotation starts
    at construction, make it in the ``with`` line itself."""

    __slots__ = ("_ann", "_name", "_done", "_t0")

    def __init__(self, name: str, done):
        self._ann, self._name, self._done = annotate(name), name, done

    def __enter__(self):
        self._ann.__enter__()
        self._t0 = time.perf_counter()

    def __exit__(self, *exc):
        self._done(self._name, self._t0, time.perf_counter())
        return self._ann.__exit__(*exc)


@dataclass
class MetricsLogger:
    """Per-step metric rows; flush to CSV / JSONL, rank-0 gated.

    Rows are plain dicts; the column set is the union over rows (missing
    keys serialize empty in CSV, absent in JSONL).

    Two modes.  **Buffered** (``path=None``, the historical default):
    rows accumulate in memory and ``save()`` writes the whole file —
    fine for benches that exit cleanly.  **Streaming** (``path=`` a
    non-CSV target): rows are ALSO appended to the file as they land,
    through a crash-safe sink (``telemetry/sink.py``: flush+fsync every
    ``flush_every`` rows, rank-0 gated) — a crash keeps every flushed
    row, and with ``append=True`` (the CLI sets it for ``--resume``
    runs) a restart into the same path APPENDS to the survivor rows
    instead of truncating them; fresh runs truncate, the historical
    semantics.  ``save()``
    to the streaming path is then just a final flush.  CSV cannot
    stream (the header is the union of columns, unknowable until the
    end), so ``.csv`` targets stay buffered.

    In streaming mode ``rows`` stays EMPTY — the disk is the buffer
    (duplicating a long run's history in host memory is the design the
    sink replaces); ``count`` tracks rows logged in both modes, and
    ``save()`` accepts only the streamed path.
    """

    rows: list[dict] = field(default_factory=list)
    path: str | os.PathLike | None = None
    flush_every: int = 20
    append: bool = False
    count: int = field(default=0, init=False)
    _sink: object = field(default=None, init=False, repr=False)

    def __post_init__(self):
        if self.path is not None and not os.fspath(self.path).endswith(
            ".csv"
        ):
            from distributed_machine_learning_tpu.telemetry.sink import (
                JsonlSink,
            )

            # append=False (default) keeps the historical fresh-file
            # semantics for unrelated reruns; the CLI passes append=True
            # for resumed runs, where truncating would destroy the
            # survivor rows the streaming mode exists to protect.
            self._sink = JsonlSink(self.path, flush_every=self.flush_every,
                                   append=self.append)

    def log(self, step: int, **metrics) -> None:
        row = {"step": step, "time": time.time(), **metrics}
        if self._sink is not None and "attempt" not in row:
            # Streamed files append across runs (by design — restarts
            # must not truncate history), so rows need a separator tag:
            # borrow the telemetry attempt when one is installed, the
            # same tag metrics.jsonl uses.
            from distributed_machine_learning_tpu.telemetry import (
                get_telemetry,
            )

            tel = get_telemetry()
            if tel is not None:
                row["attempt"] = tel.attempt
        self.count += 1
        if self._sink is not None:
            self._sink.write(row)
        else:
            self.rows.append(row)

    def save(self, path: str | os.PathLike) -> None:
        """Write rows to `path`, format chosen by extension: ``.csv`` for
        CSV, anything else JSONL.  The single dispatch point for every
        caller.  In streaming mode a save to the
        streamed path flushes (the rows are already on disk) instead of
        rewriting — rewriting would truncate prior attempts' appended
        history, the exact loss this logger was rebuilt to prevent."""
        if self._sink is not None:
            if os.path.abspath(os.fspath(path)) != os.path.abspath(
                os.fspath(self.path)
            ):
                raise ValueError(
                    f"streaming MetricsLogger bound to {self.path}; "
                    f"cannot save to {os.fspath(path)} (rows are on "
                    "disk, not buffered)"
                )
            self._sink.touch()  # zero rows still leaves the file
            self._sink.close()
            return
        if os.fspath(path).endswith(".csv"):
            self.to_csv(path)
        else:
            self.to_jsonl(path)

    def to_csv(self, path: str | os.PathLike) -> None:
        if jax.process_index() != 0:
            return
        columns: list[str] = []
        for row in self.rows:
            for key in row:
                if key not in columns:
                    columns.append(key)
        os.makedirs(os.path.dirname(os.path.abspath(os.fspath(path))),
                    exist_ok=True)
        # Zero rows still writes the (possibly header-only) file, so a
        # reported path always exists.
        with open(path, "w", newline="") as f:
            writer = csv.DictWriter(f, fieldnames=columns)
            if columns:
                writer.writeheader()
            writer.writerows(self.rows)

    def to_jsonl(self, path: str | os.PathLike) -> None:
        if jax.process_index() != 0:
            return
        os.makedirs(os.path.dirname(os.path.abspath(os.fspath(path))),
                    exist_ok=True)
        with open(path, "w") as f:
            for row in self.rows:
                f.write(json.dumps(row) + "\n")
