"""The process's start-up record: what happened between the package's
first import and the first loss, from the inside.

``setup_s`` — the time an operator waits before the first step, and the
one end-to-end metric of ``BENCHMARK.json`` that no layer metric moved —
was known only by stopwatch.  This is the record of it, kept in the
telemetry that is there:

- **spans** ``startup.*``, opened and closed where the work happens
  (``cli/lm.py::main``, ``cli/common.py::run_part``, ``init_lm_state``,
  ``runtime/mesh.py::replicate``, ``train_epoch``'s first iteration)
  through the one bracket the loop's phases use
  (``utils/profiling.Timed``: a profiler annotation and two
  ``perf_counter`` reads);
- **counters** ``jax_*_total`` from one ``jax.monitoring`` listener
  (:func:`listen_to_jax`, registered by ``configure_compile_cache``):
  seconds of tracing, lowering, backend compile, cache retrieval, the
  persistent cache's hits and misses, and the programs (one a
  backend-compile event), each labelled with the
  ``phase`` it fell in — the innermost open ``startup.*`` span, ``train``
  inside a ``train_epoch`` past the first step, else ``outside``.

The record exists before any ``Telemetry`` can (imports and the chip come
before argument parsing).  Its zero is the package's first import
(``distributed_machine_learning_tpu.IMPORT_STARTED``); the gauge
``process_age_at_import_s`` says what came before that where ``/proc``
tells.  An installed ``Telemetry`` (``set_telemetry``) is handed the closed
spans with their own timestamps, and the spans that close while it stays
installed; the counters stay HERE, their one home, and the ``Telemetry``'s
registry exports them with its own (``MetricsRegistry.adopt``).  Without
one, the operator still gets one line at the end of the first step
(rank 0)::

    startup 41.2 s: imports 3.1 | runtime 7.9 | data 0.4 | build 12.6
    (init_state 9.8, place_state 1.9) | first_step 16.9 (trace 6.0, lower
    2.2, compile 0.0, cache load 4.1; 14 programs, 14 hits, 0 misses) | ...

``misses`` counts the programs this process compiled AND wrote to the
persistent cache (those that took over 0.3 s): a dozen or more in a cold
run, none in a warm one — or one or two whose compile time straddles the
0.3 s (PERF.md §6, PR 35).  The step itself was warm where ``first_step``'s
own bracket reads ``1 hits, 0 misses``.  ``compile`` stays at a few seconds
in a warm run: the ~100 programs under 0.3 s (the un-jitted
initialization's) are compiled by every process.  JAX times
``backend_compile`` around compile-OR-fetch, so a hit's retrieval lies
inside it: compile time is backend compile less retrieval
(:func:`compile_seconds`), never both.

A start-up happens once: after the first step's end the record is closed,
``span`` hands back the bare profiler annotation, and only the counters
(and the snapshot each ``train_epoch`` takes of them as it begins — what
"set-up" means to the benchmark's reader) go on.  Importing this module
needs no JAX.
"""

from __future__ import annotations

import contextlib
import os
import time

from distributed_machine_learning_tpu.telemetry.registry import MetricsRegistry

ROOT = "startup"
IMPORTS = "startup.imports"
FIRST_STEP = "startup.first_step"

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: ``jax.monitoring`` duration events (JAX 0.9.0) -> seconds counters.
DURATION_COUNTERS = {
    "/jax/core/compile/jaxpr_trace_duration": "jax_trace_seconds_total",
    "/jax/core/compile/jaxpr_to_mlir_module_duration":
        "jax_lower_seconds_total",
    BACKEND_COMPILE_EVENT: "jax_backend_compile_seconds_total",
    "/jax/compilation_cache/cache_retrieval_time_sec":
        "jax_cache_retrieval_seconds_total",
}
#: Plain events -> counters.  A miss is recorded where an entry is WRITTEN:
#: a program that compiled in under 0.3 s is a program, and neither.
EVENT_COUNTERS = {
    "/jax/compilation_cache/cache_hits": "jax_cache_hits_total",
    "/jax/compilation_cache/cache_misses": "jax_cache_misses_total",
}
#: One per backend-compile event: a program compiled or fetched.  Programs
#: less hits less misses: compiled by every process, never cached.
PROGRAMS = "jax_programs_total"


def compile_seconds(totals: dict) -> float:
    """Seconds XLA compiled, of ``{counter name: value}``: backend compile
    less the cache retrieval that JAX times inside it on a hit."""
    return max(totals.get("jax_backend_compile_seconds_total", 0.0)
               - totals.get("jax_cache_retrieval_seconds_total", 0.0), 0.0)


def tree_size(tree, unit: str = "elements") -> int:
    """Elements (or ``"bytes"``) of a pytree's array leaves: the argument
    that makes an ``init_state`` / ``place_state`` span comparable."""
    import jax

    attr = "size" if unit == "elements" else "nbytes"
    return sum(int(getattr(leaf, attr, 0))
               for leaf in jax.tree_util.tree_leaves(tree))


def _process_age_s() -> float | None:
    """Seconds since the process started, where ``/proc`` tells."""
    try:
        with open("/proc/self/stat", encoding="ascii") as f:
            # field 22 (starttime, clock ticks since boot); the command
            # name in field 2 may hold spaces, so count from its ")".
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime", encoding="ascii") as f:
            uptime = float(f.read().split()[0])
        age = uptime - ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return None
    return age if age >= 0.0 else None


class StartupRecord:
    """See the module.  ``zero``: the ``perf_counter`` reading the spans
    are counted from (default: now)."""

    def __init__(self, zero: float | None = None):
        now = time.perf_counter()
        self.zero = now if zero is None else zero
        self.registry = MetricsRegistry()
        #: Closed spans, in closing order: name, start, end (``perf_counter``
        #: seconds), parent, args.
        self.spans: list[dict] = []
        #: True from the first step's end: the start-up is over.
        self.closed = False
        #: ``{(counter, phase): value}`` as the process's newest
        #: ``train_epoch`` began; None before any.
        self.at_epoch: dict | None = None
        self._open: list[tuple[str, dict]] = []  # innermost last
        self._base_phase = "outside"
        self._telemetry = None
        self._summary = None
        #: Seconds the process had lived at ``zero`` (also a gauge); None
        #: where ``/proc`` does not tell.
        self.process_age_at_import_s = None
        age = _process_age_s()
        if age is not None:
            self.process_age_at_import_s = max(age - (now - self.zero), 0.0)
            self.registry.gauge("process_age_at_import_s").set(
                self.process_age_at_import_s)

    # -- spans -----------------------------------------------------------
    def span(self, name: str, **args):
        """``with record.span("startup.build", parallel="dp"): ...`` — call
        it in the ``with`` line.  ``args`` make runs comparable (device
        count, parameter count, bytes); :meth:`note` adds what is known
        only inside the block."""
        from distributed_machine_learning_tpu.utils import profiling

        if self.closed:
            return profiling.annotate(name)
        self._open.append((name, args))
        return profiling.Timed(name, self._span_done)

    def note(self, **args) -> None:
        """Arguments for the innermost open span."""
        if self._open:
            self._open[-1][1].update(args)

    def _span_done(self, name: str, t0: float, t1: float) -> None:
        if self.closed:  # the first step ended inside this span
            return
        # by name, innermost first: a span left open by a block that never
        # ended (none does today) cannot pass for this one
        at = max((i for i, o in enumerate(self._open) if o[0] == name),
                 default=None)
        if at is None:  # taken off already (``epoch``'s end)
            return
        _, args = self._open.pop(at)
        self._complete(name, t0, t1,
                       self._open[at - 1][0] if at else ROOT, args)

    def _complete(self, name, t0, t1, parent, args) -> None:
        self.spans.append({"name": name, "start": t0, "end": t1,
                           "parent": parent, "args": args})
        if self._telemetry is not None:
            self._to_tracer(self._telemetry)

    def _to_tracer(self, telemetry) -> None:
        """The closed spans ``telemetry`` does not hold yet, with their own
        timestamps (``telemetry.startup_spans`` counts those it holds)."""
        for span in self.spans[telemetry.startup_spans:]:
            parent = {"parent": span["parent"]} if span["parent"] else {}
            telemetry.tracer.complete(
                span["name"], span["start"], span["end"],
                **parent, **span["args"])
        telemetry.startup_spans = len(self.spans)

    def imports_done(self) -> None:
        """An entry point was entered: the imports end here (once)."""
        if self.closed or any(s["name"] == IMPORTS for s in self.spans):
            return
        self._complete(IMPORTS, self.zero, time.perf_counter(), ROOT, {})

    def seconds(self, names) -> float | None:
        """The closed spans of these names, summed; None without one."""
        found = [s["end"] - s["start"] for s in self.spans
                 if s["name"] in names]
        return sum(found) if found else None

    # -- the first step --------------------------------------------------
    def first_step_stop(self, timer):
        """For ``train_epoch``, called inside :meth:`epoch` just before
        iteration 0: None once the process has made its first step.  Until
        then a stand-in for ``timer.stop`` in the FIRST iteration: the
        timer's own two clock reads (just outside ``train.step_dispatch``'s
        start and ``train.device_block``'s end; ``IterationTimer.started``)
        become ``startup.first_step``, which ends the start-up and prints
        the line — no further clock read, and nothing in a later
        iteration.  A caller's timer that does not say when it started
        costs the first iteration one clock read.  An epoch that ends or
        raises before its first step leaves the start-up open
        (:meth:`epoch` takes the span off again)."""
        if self.closed:
            return None
        self._open.append((FIRST_STEP, {}))

        def stop() -> float:
            elapsed = timer.stop()
            t0 = getattr(timer, "started", None)
            if t0 is None:
                t0 = time.perf_counter() - elapsed
            self._first_step_done(t0, t0 + elapsed)
            return elapsed

        return stop

    def _first_step_done(self, t0: float, t1: float) -> None:
        from distributed_machine_learning_tpu.utils.logging import rank0_print

        self._open.clear()  # the start-up is over, whatever was left open
        self._complete(FIRST_STEP, t0, t1, ROOT, {})
        self._complete(ROOT, self.zero, t1, None, {})
        self.closed = True
        self._summary = self.summary()
        rank0_print(format_line(self._summary))

    def pop_summary(self) -> dict | None:
        """The start-up line's object, once, after the first step: the
        first step row's ``startup`` field."""
        summary, self._summary = self._summary, None
        return summary

    # -- counters --------------------------------------------------------
    def phase(self) -> str:
        if self._open:
            return self._open[-1][0]
        return self._base_phase if self.closed else ROOT

    def count(self, name: str, value: float = 1) -> None:
        self.registry.counter(name, phase=self.phase()).inc(value)

    def totals(self, phase: str | None = None, at_epoch: bool = False) -> dict:
        """``{counter: value}`` over all phases or one: now, or as the
        newest ``train_epoch`` began."""
        if at_epoch:
            items = (self.at_epoch or {}).items()
        else:
            items = self._counters().items()
        out: dict = {}
        for (name, of), value in items:
            if phase is None or of == phase:
                out[name] = out.get(name, 0) + value
        return out

    def _counters(self) -> dict:
        return {(c["name"], c["labels"].get("phase")): c["value"]
                for c in self.registry.snapshot()["counters"]}

    @contextlib.contextmanager
    def epoch(self):
        """Around one ``train_epoch``: the counters are copied as it
        begins, and what counts inside it past the first step is
        ``train``.  A ``startup.first_step`` that iteration 0 did not end
        (an empty epoch, a step that raised) is taken off again."""
        self.at_epoch = self._counters()
        before, self._base_phase = self._base_phase, "train"
        try:
            yield
        finally:
            self._base_phase = before
            self._open = [o for o in self._open if o[0] != FIRST_STEP]

    # -- an installed Telemetry -----------------------------------------
    def follow(self, telemetry) -> None:
        """``telemetry`` (None: nobody) receives the spans that close from
        now on, and first the closed ones it does not hold.  Its registry
        exports this record's counters and gauge with its own: they are
        kept here alone."""
        self._telemetry = telemetry
        if telemetry is not None:
            telemetry.registry.adopt(self.registry)
            self._to_tracer(telemetry)

    # -- the operator's line --------------------------------------------
    def summary(self) -> dict:
        """The line as an object: seconds by span name (same names
        summed), the first step's JAX counters and the process's."""
        by_name: dict = {}
        parents: dict = {}
        for s in self.spans:
            by_name[s["name"]] = (by_name.get(s["name"], 0.0)
                                  + s["end"] - s["start"])
            parents[s["name"]] = s["parent"]
        out = {"spans": by_name, "parents": parents,
               "first_step": _jax_summary(self.totals(FIRST_STEP)),
               "all_phases": _jax_summary(self.totals())}
        if self.process_age_at_import_s is not None:
            out["process_age_at_import_s"] = self.process_age_at_import_s
        return out


def _jax_summary(totals: dict) -> dict:
    return {
        "trace_s": totals.get("jax_trace_seconds_total", 0.0),
        "lower_s": totals.get("jax_lower_seconds_total", 0.0),
        "compile_s": compile_seconds(totals),
        "cache_load_s": totals.get("jax_cache_retrieval_seconds_total", 0.0),
        "programs": int(totals.get(PROGRAMS, 0)),
        "hits": int(totals.get("jax_cache_hits_total", 0)),
        "misses": int(totals.get("jax_cache_misses_total", 0)),
    }


def format_line(summary: dict) -> str:
    """``startup 41.2 s: imports 3.1 | ... | first_step 16.9 (...)``: the
    root's children in the order they closed, each with its own children
    in brackets, the seconds no child covers as ``other``."""
    spans, parents = summary["spans"], summary["parents"]

    def short(name: str) -> str:
        return name.rsplit(".", 1)[-1]

    def jax_part(j: dict, programs: bool = True) -> str:
        text = (f"trace {j['trace_s']:.1f}, lower {j['lower_s']:.1f}, "
                f"compile {j['compile_s']:.1f}, "
                f"cache load {j['cache_load_s']:.1f}")
        if programs:
            text += (f"; {j['programs']} programs, {j['hits']} hits, "
                     f"{j['misses']} misses")
        return text

    parts, covered = [], 0.0
    for name, seconds in spans.items():
        if parents[name] != ROOT:
            continue
        covered += seconds
        inner = [f"{short(child)} {spans[child]:.1f}" for child in spans
                 if parents[child] == name]
        if name == FIRST_STEP:
            inner.append(jax_part(summary["first_step"]))
        parts.append(f"{short(name)} {seconds:.1f}"
                     + (f" ({', '.join(inner)})" if inner else ""))
    total = spans.get(ROOT, 0.0)
    parts.append(f"other {max(total - covered, 0.0):.1f}")
    j = summary["all_phases"]
    tail = (f"all phases: {j['programs']} programs, {j['hits']} hits, "
            f"{j['misses']} misses, compile {j['compile_s']:.1f}")
    if "process_age_at_import_s" in summary:
        tail += (f"; process {summary['process_age_at_import_s']:.1f} s old "
                 "at import")
    return f"startup {total:.1f} s: " + " | ".join(parts) + " | " + tail


# -- the process's record and its listener ---------------------------------
_record: StartupRecord | None = None
_listening = False


def record() -> StartupRecord:
    """The process's record (made at the first call, counted from the
    package's first import)."""
    global _record
    if _record is None:
        import distributed_machine_learning_tpu as package

        _record = StartupRecord(zero=package.IMPORT_STARTED)
    return _record


def span(name: str, **args):
    """``record().span``: for the functions that do start-up work."""
    return record().span(name, **args)


def place_state(tree, mesh):
    """The span of a function that puts a state onto ``mesh``
    (``startup.build.place_state``, with the bytes placed and the mesh's
    devices).  It times the placement CALL: the transfers it starts end
    under whatever waits for them next (the first step)."""
    rec = record()
    if rec.closed:
        return rec.span("startup.build.place_state")
    return rec.span("startup.build.place_state",
                    bytes=tree_size(tree, "bytes"), devices=mesh.size)


def _on_event(event: str, **_kw) -> None:
    name = EVENT_COUNTERS.get(event)
    if name is not None:
        record().count(name)


def _on_duration(event: str, seconds: float, **_kw) -> None:
    name = DURATION_COUNTERS.get(event)
    if name is None:
        return
    rec = record()
    rec.count(name, seconds)
    if event == BACKEND_COMPILE_EVENT:
        rec.count(PROGRAMS)


def listen_to_jax() -> None:
    """Register the process's one ``jax.monitoring`` listener (its two
    callbacks: plain events and durations); again is a no-op."""
    global _listening
    if _listening:
        return
    _listening = True
    import jax.monitoring

    record()  # made here, not at the first event: no clock read later

    jax.monitoring.register_event_listener(_on_event)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
