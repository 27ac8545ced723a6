"""The process's start-up record (``telemetry/startup.py``): spans from the
package's first import to the first loss through the loop's own bracket,
JAX's trace / lower / compile / cache events as counters by phase, the
replay into a ``Telemetry`` installed afterwards, the snapshot a
``train_epoch`` takes as it begins, and the operator's line of a tiny
``cli.lm`` and ``cli.part3`` run."""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_machine_learning_tpu.telemetry import (
    Telemetry,
    get_telemetry,
    set_telemetry,
    startup,
)
from distributed_machine_learning_tpu.telemetry.tracer import read_trace
from distributed_machine_learning_tpu.train import loop
from distributed_machine_learning_tpu.train.loop import train_epoch
from distributed_machine_learning_tpu.utils import profiling
from distributed_machine_learning_tpu.utils.timing import IterationTimer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: The table of ISSUE 35: every span a plain run of either CLI closes.
CLI_SPANS = ["startup", "startup.imports", "startup.runtime", "startup.data",
             "startup.build", "startup.build.init_state",
             "startup.build.place_state", "startup.first_step"]


@pytest.fixture
def record(monkeypatch):
    """A fresh record in the process's place (the listener finds it
    through ``startup.record()``)."""
    fresh = startup.StartupRecord()
    monkeypatch.setattr(startup, "_record", fresh)
    startup.listen_to_jax()
    return fresh


@pytest.fixture
def installed(tmp_path):
    """Installs the ``Telemetry`` a test makes; uninstalls and closes."""
    made = []

    def install():
        tel = Telemetry(tmp_path, flush_every=1)
        made.append((tel, set_telemetry(tel)))
        return tel

    yield install
    for tel, prev in reversed(made):
        set_telemetry(prev)
        tel.close()


def _counters(path, prefix="jax_"):
    snap = json.loads((path / "registry.json").read_text())
    return {(c["name"], c["labels"].get("phase")): c["value"]
            for c in snap["counters"] if c["name"].startswith(prefix)}


class _State:
    def __init__(self, step=0):
        self.step = step


def _step(state, x, y):
    return _State(state.step + 1), jnp.float32(0.0)


def _batches(n=3):
    return [(np.zeros((2, 4), np.float32), np.zeros((2,), np.int32))
            for _ in range(n)]


# ------------------------------------------------------------------ spans

def test_spans_nest_under_the_innermost_open_one_and_close_once(record):
    with record.span("startup.build", parallel="dp"):
        assert record.phase() == "startup.build"
        with record.span("startup.build.init_state"):
            assert record.phase() == "startup.build.init_state"
            record.note(params=12)
        with pytest.raises(RuntimeError):
            with record.span("startup.build.place_state", bytes=48):
                raise RuntimeError("a placement that failed still closes")
        assert record.phase() == "startup.build"
    assert record.phase() == "startup"  # the root is open until a first step
    by_name = {s["name"]: s for s in record.spans}
    assert list(by_name) == ["startup.build.init_state",
                             "startup.build.place_state", "startup.build"]
    assert len(record.spans) == 3  # each closed once
    assert by_name["startup.build"]["parent"] == "startup"
    assert by_name["startup.build"]["args"] == {"parallel": "dp"}
    assert by_name["startup.build.init_state"]["parent"] == "startup.build"
    assert by_name["startup.build.init_state"]["args"] == {"params": 12}
    assert by_name["startup.build.place_state"]["args"] == {"bytes": 48}
    outer = by_name["startup.build"]
    for inner in ("startup.build.init_state", "startup.build.place_state"):
        assert outer["start"] <= by_name[inner]["start"] \
            <= by_name[inner]["end"] <= outer["end"]
    assert record.seconds(["startup.build.init_state",
                           "startup.build.place_state"]) <= \
        record.seconds(["startup.build"])
    assert record.seconds(["startup.resume"]) is None


def test_imports_end_at_the_first_entry_point_only(record):
    record.imports_done()
    record.imports_done()
    spans = [s for s in record.spans if s["name"] == "startup.imports"]
    assert len(spans) == 1 and spans[0]["start"] == record.zero


def test_one_bracket_serves_the_loop_and_the_record(record, monkeypatch):
    """``profiling.Timed`` is the loop's phase bracket under a ``Telemetry``
    and the record's span: both put their name on the profiler's clock."""
    named = []
    annotate = profiling.annotate
    monkeypatch.setattr(
        profiling, "annotate",
        lambda name, step_num=None: named.append(name) or annotate(name))
    assert isinstance(record.span("startup.data"), profiling.Timed)
    record._open.clear()

    class Rec:
        def phase_done(self, *a):
            pass

    assert isinstance(loop._phase("train.data_wait", Rec()), profiling.Timed)
    assert not hasattr(loop, "_Timed")
    assert named == ["startup.data", "train.data_wait"]


def test_a_process_older_than_its_import_says_by_how_much():
    zero = time.perf_counter()
    age = startup.StartupRecord(zero=zero).process_age_at_import_s
    if age is None:
        pytest.skip("/proc does not tell here")
    # pytest started this process seconds before the record's zero
    assert 0.5 < age < 24 * 3600
    gauges = startup.StartupRecord(zero=zero).registry.snapshot()["gauges"]
    assert [g["name"] for g in gauges] == ["process_age_at_import_s"]


# ------------------------------------------- replay into a later Telemetry

def test_a_telemetry_installed_later_gets_spans_with_their_own_times(
        record, installed, tmp_path):
    record.imports_done()
    with record.span("startup.runtime", devices=8):
        record.count("jax_trace_seconds_total", 0.25)
    record.count("jax_cache_hits_total")
    closed_before = time.perf_counter()
    tel = installed()
    made = tel.tracer._us(closed_before)
    with record.span("startup.build", parallel="dp"):
        record.count("jax_trace_seconds_total", 0.5)
    set_telemetry(None)
    with record.span("startup.data"):  # nobody follows any more
        record.count("jax_cache_hits_total")
    tel.close()
    events = {e["name"]: e for e in read_trace(tmp_path / "trace.json")}
    assert set(events) == {"startup.imports", "startup.runtime",
                           "startup.build"}
    # before the Telemetry existed, at the record's own timestamps
    for name in ("startup.imports", "startup.runtime"):
        assert events[name]["ts"] + events[name]["dur"] <= made
    span = record.spans[1]
    assert events["startup.runtime"]["dur"] == pytest.approx(
        (span["end"] - span["start"]) * 1e6)
    assert events["startup.runtime"]["args"] == {"parent": "startup",
                                                 "devices": 8}
    assert events["startup.build"]["ts"] >= made
    # the counters have one home, the record's registry, which the
    # Telemetry's exports with its own: what counted later is there too
    assert _counters(tmp_path) == {
        ("jax_trace_seconds_total", "startup.runtime"): 0.25,
        ("jax_trace_seconds_total", "startup.build"): 0.5,
        ("jax_cache_hits_total", "startup"): 1,
        ("jax_cache_hits_total", "startup.data"): 1}
    assert not [c for c in tel.registry._instruments.values()
                if c.name.startswith("jax_")]
    prom = (tmp_path / "metrics.prom").read_text()
    assert 'jax_trace_seconds_total{phase="startup.build"} 0.5' in prom
    assert 'jax_cache_hits_total{phase="startup"} 1' in prom
    assert "process_age_at_import_s" in prom or \
        record.process_age_at_import_s is None


def test_a_reinstalled_telemetry_gets_what_it_missed_and_nothing_twice(
        record, installed, tmp_path):
    record.count("jax_programs_total")
    with record.span("startup.runtime"):
        pass
    tel = installed()
    set_telemetry(None)
    with record.span("startup.data"):
        record.count("jax_programs_total")
    set_telemetry(tel)
    with record.span("startup.build"):
        pass
    assert tel.startup_spans == len(record.spans) == 3
    tel.close()
    assert [e["name"] for e in read_trace(tmp_path / "trace.json")] == [
        "startup.runtime", "startup.data", "startup.build"]
    assert _counters(tmp_path) == {("jax_programs_total", "startup"): 1,
                                   ("jax_programs_total", "startup.data"): 1}


# ------------------------------------------------------------ the listener

def test_registering_twice_registers_once(record):
    from distributed_machine_learning_tpu.runtime.compile_cache import (
        configure_compile_cache,
    )

    configure_compile_cache()  # conftest's call was the first
    configure_compile_cache()
    startup.listen_to_jax()
    jax.jit(lambda x: jnp.exp(x) - 0.75)(np.arange(5.0, dtype=np.float32))
    assert record.totals()["jax_programs_total"] == 1  # heard once


def test_one_jit_counts_its_trace_lowering_and_backend_compile(record):
    with record.span("startup.build"):
        jax.jit(lambda x: jnp.tanh(x) * 3.0 + 1.5)(
            np.arange(7.0, dtype=np.float32))
    totals = record.totals("startup.build")
    assert totals["jax_programs_total"] == 1
    for name in ("jax_trace_seconds_total", "jax_lower_seconds_total",
                 "jax_backend_compile_seconds_total"):
        assert totals[name] > 0, name
    assert record.totals() == totals  # nothing counted in another phase


@pytest.fixture
def empty_cache(tmp_path):
    """A persistent compile cache of this test's own, every program
    written to it."""
    from jax.experimental.compilation_cache import compilation_cache

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    before = {key: getattr(jax.config, key) for key in keys}
    jax.config.update(keys[0], str(tmp_path / "cache"))
    jax.config.update(keys[1], 0.0)
    compilation_cache.reset_cache()
    yield
    for key, value in before.items():
        jax.config.update(key, value)
    compilation_cache.reset_cache()


def test_a_miss_then_a_hit_and_retrieval_is_not_counted_twice(record,
                                                              empty_cache):
    def run():
        return jax.jit(lambda x: jnp.sin(x) @ jnp.cos(x).T - 0.125)(
            np.ones((5, 5), np.float32))

    with record.span("startup.build"):
        run()
    cold = record.totals("startup.build")
    assert (cold["jax_programs_total"], cold["jax_cache_misses_total"],
            cold.get("jax_cache_hits_total", 0)) == (1, 1, 0)
    assert "jax_cache_retrieval_seconds_total" not in cold
    assert startup.compile_seconds(cold) == \
        cold["jax_backend_compile_seconds_total"] > 0
    jax.clear_caches()
    with record.span("startup.first_step"):
        run()
    warm = record.totals("startup.first_step")
    assert (warm["jax_programs_total"], warm["jax_cache_hits_total"],
            warm.get("jax_cache_misses_total", 0)) == (1, 1, 0)
    # JAX times backend_compile around compile-or-fetch: the retrieval is
    # inside it, and compile time is what is left
    retrieval = warm["jax_cache_retrieval_seconds_total"]
    assert 0 < retrieval <= warm["jax_backend_compile_seconds_total"]
    assert startup.compile_seconds(warm) == pytest.approx(
        warm["jax_backend_compile_seconds_total"] - retrieval)
    # every counter has a reader: the line, or a ``setup.*`` metric file
    assert set(cold) | set(warm) == {
        "jax_trace_seconds_total", "jax_lower_seconds_total",
        "jax_backend_compile_seconds_total",
        "jax_cache_retrieval_seconds_total", "jax_cache_hits_total",
        "jax_cache_misses_total", "jax_programs_total"}


# ------------------------------------------- the first step and the epochs

def test_the_first_step_ends_the_start_up_and_later_epochs_add_nothing(
        record, capsys):
    record.imports_done()
    timer = IterationTimer(skip_first=1)
    train_epoch(_step, _State(), _batches(3), max_iters=10,
                loss_print_every=10**9, timer=timer)
    assert record.closed and record.phase() == "outside"
    names = [s["name"] for s in record.spans]
    assert names == ["startup.imports", "startup.first_step", "startup"]
    first, root = record.spans[1], record.spans[2]
    assert (root["start"], root["end"]) == (record.zero, first["end"])
    assert first["parent"] == "startup" and root["parent"] is None
    out = capsys.readouterr().out
    assert out.count("startup ") == 1 and "first_step" in out
    # a span the first step ended inside closes without a trace
    open_before = startup.StartupRecord()
    with open_before.span("startup.build"):
        open_before.first_step_stop(IterationTimer())()
    assert [s["name"] for s in open_before.spans] == [
        "startup.first_step", "startup"]
    capsys.readouterr()
    # a closed record hands the loop nothing and records no span
    assert record.first_step_stop(timer) is None
    assert not isinstance(record.span("startup.build"), profiling.Timed)
    train_epoch(_step, _State(), _batches(2), max_iters=10,
                loss_print_every=10**9)
    assert [s["name"] for s in record.spans] == names
    assert "startup " not in capsys.readouterr().out


class _BareTimer:
    """A caller's timer: ``start`` and ``stop``, nothing else."""

    def start(self):
        self._t0 = time.perf_counter()

    def stop(self):
        return time.perf_counter() - self._t0

    def summary(self):
        return "bare"


def test_a_timer_that_only_starts_and_stops_still_ends_the_start_up(record):
    before = time.perf_counter()
    train_epoch(_step, _State(), _batches(2), max_iters=10,
                loss_print_every=10**9, timer=_BareTimer())
    first = next(s for s in record.spans if s["name"] == "startup.first_step")
    assert record.closed
    assert before <= first["start"] <= first["end"] <= time.perf_counter()


@pytest.mark.parametrize("how", ["empty", "raises"])
def test_an_epoch_without_a_first_step_leaves_the_start_up_open(record, how):
    """``startup.first_step`` is taken off the open spans again: the phase
    is the enclosing span's, which closes under its own name, arguments and
    parent, and a later epoch makes the first step."""
    with record.span("startup.build", parallel="dp"):
        if how == "empty":
            train_epoch(_step, _State(), [], max_iters=10)
        else:
            with pytest.raises(RuntimeError):
                train_epoch(
                    lambda *a: (_ for _ in ()).throw(RuntimeError("x")),
                    _State(), _batches(1), max_iters=10)
        assert not record.closed and record.phase() == "startup.build"
    assert record.phase() == "startup"
    assert record.spans == [{**record.spans[0], "name": "startup.build",
                             "parent": "startup",
                             "args": {"parallel": "dp"}}]
    train_epoch(_step, _State(), _batches(1), max_iters=10)
    assert [s["name"] for s in record.spans] == [
        "startup.build", "startup.first_step", "startup"]


def test_a_span_closes_under_its_own_name_whatever_is_open_inside(record):
    outer = record.span("startup.build", parallel="dp")
    outer.__enter__()
    record.span("startup.build.init_state", params=3)  # opened, never ended
    outer.__exit__(None, None, None)
    assert record.spans[0]["name"] == "startup.build"
    assert record.spans[0]["args"] == {"parallel": "dp"}
    assert record.spans[0]["parent"] == "startup"


def test_telemetry_off_the_first_step_reads_no_clock_of_its_own(
        record, monkeypatch):
    assert get_telemetry() is None
    reads = []
    perf_counter = time.perf_counter
    monkeypatch.setattr(time, "perf_counter",
                        lambda: reads.append(1) or perf_counter())
    train_epoch(_step, _State(), _batches(3), max_iters=10,
                loss_print_every=10**9)
    monkeypatch.undo()
    assert record.closed
    assert len(reads) == 2 * 3  # the timer's start and stop, as ever


def test_the_first_row_carries_the_line_as_an_object(record, tmp_path):
    with Telemetry(tmp_path, flush_every=1) as tel:
        for _ in range(2):
            train_epoch(_step, _State(), _batches(2), max_iters=10,
                        loss_print_every=10**9, telemetry=tel)
    rows = [json.loads(line) for line in
            (tmp_path / "metrics.jsonl").read_text().splitlines()]
    assert [("startup" in r) for r in rows] == [True, False, False, False]
    summary = rows[0]["startup"]
    assert summary["spans"]["startup"] >= summary["spans"][
        "startup.first_step"] > 0
    assert set(summary["first_step"]) == {
        "trace_s", "lower_s", "compile_s", "cache_load_s", "programs",
        "hits", "misses"}
    assert startup.format_line(summary).startswith("startup ")


def test_the_phase_is_train_inside_an_epoch_and_outside_after_it(record):
    seen = []

    def step(state, x, y):
        seen.append(record.phase())
        return _step(state, x, y)

    train_epoch(step, _State(), _batches(3), max_iters=10,
                loss_print_every=10**9)
    assert seen == ["startup.first_step", "train", "train"]
    assert record.phase() == "outside"
    with pytest.raises(RuntimeError):
        train_epoch(lambda *a: (_ for _ in ()).throw(RuntimeError("x")),
                    _State(), _batches(1), max_iters=10)
    assert record.phase() == "outside"  # also when the epoch raised


def test_the_snapshot_is_of_the_newest_epochs_beginning(record):
    assert record.at_epoch is None
    with record.span("startup.build"):
        jax.jit(lambda x: x * 2.0 - 7.0)(np.arange(3.0, dtype=np.float32))
    train_epoch(_step, _State(), _batches(2), max_iters=10,
                loss_print_every=10**9)
    before = record.totals(at_epoch=True)
    assert before["jax_programs_total"] == 1
    # compiled after the epoch began (here: after it ended, as the
    # harness's memory analysis is): in the live totals, not the snapshot
    jax.jit(lambda x: x * 3.0 - 11.0)(np.arange(3.0, dtype=np.float32))
    assert record.totals()["jax_programs_total"] == 2
    assert record.totals("outside")["jax_programs_total"] == 1
    assert record.totals(at_epoch=True) == before
    train_epoch(_step, _State(), _batches(1), max_iters=10,
                loss_print_every=10**9)
    assert record.totals(at_epoch=True)["jax_programs_total"] == 2


# ------------------------------------------------------ the operator's line

def test_the_line_names_children_in_brackets_and_what_is_left():
    summary = {
        "spans": {"startup.imports": 3.1, "startup.runtime": 7.9,
                  "startup.build.init_state": 9.8,
                  "startup.build.place_state": 1.9, "startup.build": 12.6,
                  "startup.first_step": 16.9, "startup": 41.2},
        "parents": {"startup.imports": "startup",
                    "startup.runtime": "startup",
                    "startup.build.init_state": "startup.build",
                    "startup.build.place_state": "startup.build",
                    "startup.build": "startup",
                    "startup.first_step": "startup", "startup": None},
        "first_step": {"trace_s": 6.0, "lower_s": 2.2, "compile_s": 0.0,
                       "cache_load_s": 4.1, "programs": 14, "hits": 14,
                       "misses": 0},
        "all_phases": {"trace_s": 9.0, "lower_s": 3.0, "compile_s": 0.4,
                       "cache_load_s": 5.0, "programs": 37, "hits": 20,
                       "misses": 0},
        "process_age_at_import_s": 11.3}
    assert startup.format_line(summary) == (
        "startup 41.2 s: imports 3.1 | runtime 7.9 | build 12.6 "
        "(init_state 9.8, place_state 1.9) | first_step 16.9 (trace 6.0, "
        "lower 2.2, compile 0.0, cache load 4.1; 14 programs, 14 hits, "
        "0 misses) | other 0.7 | all phases: 37 programs, 20 hits, "
        "0 misses, compile 0.4; process 11.3 s old at import")


LM = ["distributed_machine_learning_tpu.cli.lm", "--parallel", "dp",
      "--d-model", "32", "--n-layers", "2", "--n-heads", "4", "--seq-len",
      "16", "--batch-size", "8", "--vocab", "64", "--max-iters", "3"]
PART3 = ["distributed_machine_learning_tpu.cli.part3", "--batch-size", "4",
         "--max-iters", "3", "--eval-batches", "1", "--model", "vggtest",
         "--eval-batch-size", "16"]


@pytest.mark.parametrize("argv", [LM, PART3], ids=["cli.lm", "cli.part3"])
def test_a_tiny_cli_run_prints_the_line_and_its_telemetry_has_the_record(
        argv, tmp_path):
    """A process of its own: the imports are real, and the record is the
    process's.  Without ``--telemetry-dir`` the line is all there is; with
    it (the same run, to spare a second start) ``trace.json`` holds every
    span, the imports at timestamps before the ``Telemetry`` existed."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8"}
    plain = subprocess.run(
        [sys.executable, "-m", *argv], cwd=REPO, env=env, timeout=600,
        capture_output=True, text=True)
    assert plain.returncode == 0, plain.stderr[-2000:]
    lines = [ln for ln in plain.stdout.splitlines()
             if ln.startswith("startup ")]
    assert len(lines) == 1, plain.stdout
    line = lines[0]
    total = float(re.match(r"startup (\S+) s: ", line).group(1))
    top = {name: float(value) for name, value in re.findall(
        r"(?:: |\| )(imports|runtime|data|build|first_step|other) (\d+\.\d)",
        line)}
    assert set(top) == {"imports", "runtime", "data", "build", "first_step",
                        "other"}, line
    assert re.search(r"build \S+ \(init_state \S+, place_state \S+\)", line)
    assert re.search(r"\d+ programs, \d+ hits, \d+ misses\)", line)
    assert sum(top.values()) == pytest.approx(total, abs=0.35)  # 0.1 s each

    traced = subprocess.run(
        [sys.executable, "-m", *argv, "--telemetry-dir", str(tmp_path)],
        cwd=REPO, env=env, timeout=600, capture_output=True, text=True)
    assert traced.returncode == 0, traced.stderr[-2000:]
    assert sum(ln.startswith("startup ")
               for ln in traced.stdout.splitlines()) == 1
    spans = [e for e in read_trace(tmp_path / "trace.json")
             if e["name"].startswith("startup")]
    by_name: dict = {}
    for e in spans:
        by_name.setdefault(e["name"], []).append(e)
    assert set(CLI_SPANS) <= set(by_name)
    root, = by_name["startup"]
    children = [e for e in spans if e.get("args", {}).get("parent")
                == "startup"]
    assert root["dur"] >= sum(e["dur"] for e in children) - 1.0
    for e in spans:  # all inside the root
        assert root["ts"] - 1.0 <= e["ts"] \
            and e["ts"] + e["dur"] <= root["ts"] + root["dur"] + 1.0
    imports, = by_name["startup.imports"]
    runtime, = by_name["startup.runtime"]
    # the imports: from the record's zero, over before the runtime span —
    # inside which the Telemetry was made — began
    assert imports["ts"] == pytest.approx(root["ts"], abs=1.0)
    assert imports["ts"] + imports["dur"] <= runtime["ts"] + 1.0
    assert imports["dur"] > 0.1e6  # importing jax alone takes longer
    init, = by_name["startup.build.init_state"]
    assert init["args"]["parent"] == "startup.build"
    assert init["args"]["params"] > 0
    assert by_name["startup.build.place_state"][0]["args"]["bytes"] > 0
    counters = _counters(tmp_path)
    phases = {phase for _, phase in counters}
    assert "startup.build.init_state" in phases
    assert "startup.first_step" in phases
    assert sum(v for (name, _), v in counters.items()
               if name == "jax_programs_total") >= 2
    row = json.loads((tmp_path / "metrics.jsonl").read_text()
                     .splitlines()[0])
    assert row["startup"]["spans"]["startup"] == pytest.approx(
        root["dur"] / 1e6, abs=1e-3)
    prom = (tmp_path / "metrics.prom").read_text()
    # (cli.lm's multi-device step under a Telemetry compiles inside
    # startup.hlo_gauges, and its first call finds the program made)
    assert 'jax_programs_total{phase="startup.' in prom
    assert '_seconds_total{phase="startup.first_step"}' in prom
    assert "process_age_at_import_s" in prom
