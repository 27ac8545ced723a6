"""The six ``setup.*`` per-layer metrics that read the program's own
start-up record (``telemetry/startup.py``) through
``benchmark/readers/startup_record.py``: the committed metric files, what the
reader makes of an empty record and of one phase's counters, and a tiny
traced run on the CPU seam (``require_tpu=False``) through a scratch
manifest with the six entries appended — the way a ``benchmark`` PR will
enter them in ``BENCHMARK.json`` (PERF.md §7: a PR may only append, and
``test_benchmark_lead.py`` holds the last place)."""

from __future__ import annotations

import json
import os
import shutil
import time

import pytest

from benchmark import harness
from benchmark.readers import startup_record
from test_benchmark_manifest import (  # noqa: F401 — fixtures
    REPO,
    TINY_FILES,
    _assert_correct_but_for_the_trend,
    _load,
    manifest,
)

#: name -> (unit, source, the reader's arguments): ISSUE 35's table, but
#: for two things its review asked for — the span ``startup`` is read as
#: ``setup.startup_s``, and the misses are the first step's own (0 = the
#: process found its step compiled), since over all phases a warm run
#: writes one to three programs that straddle the cache's 0.3 s.
SETUP_METRICS = {
    "setup.startup_s": ("s", "program_span", {
        "kind": "span", "name": ["startup"]}),
    "setup.init_state_s": ("s", "program_span", {
        "kind": "span", "name": ["startup.build.init_state",
                                 "startup.build.place_state"]}),
    "setup.trace_lower_s": ("s", "program_counter", {
        "kind": "counter", "name": ["jax_trace_seconds_total",
                                    "jax_lower_seconds_total"]}),
    "setup.compile_s": ("s", "program_counter", {
        "kind": "counter", "name": ["jax_backend_compile_seconds_total"],
        "less": ["jax_cache_retrieval_seconds_total"]}),
    "setup.cache_load_s": ("s", "program_counter", {
        "kind": "counter", "name": ["jax_cache_retrieval_seconds_total"]}),
    "setup.cache_misses": ("programs", "program_counter", {
        "kind": "counter", "name": ["jax_cache_misses_total"],
        "phase": ["startup.first_step", "startup.hlo_gauges"]}),
}
ENTRIES = [{"name": name, "unit": unit, "better": "lower", "source": source,
            "layer": "set-up", "moves": "setup_s"}
           for name, (unit, source, _) in SETUP_METRICS.items()]


@pytest.mark.parametrize("name", list(SETUP_METRICS))
def test_metric_is_a_data_file_over_the_start_up_record(manifest, name):
    assert _load(f"benchmark/metrics/{name}.json") == {
        "reader": "startup_record", "args": SETUP_METRICS[name][2]}
    # every cell reports setup_s, so the entries need no workloads key
    assert "workloads" not in next(
        m for m in manifest["end_to_end"] if m["name"] == "setup_s")


@pytest.fixture
def startup(monkeypatch):
    """The program's record module with a fresh record in the process's
    place: this worker's own start-up ended tests ago."""
    from distributed_machine_learning_tpu.telemetry import startup

    monkeypatch.setattr(startup, "_record", startup.StartupRecord())
    return startup


def test_reader_returns_none_on_an_empty_record(startup):
    for _, _, args in SETUP_METRICS.values():
        assert startup_record.read({}, **args) is None
    # once an epoch has snapshotted the counters, one that never counted
    # reads zero: a warm run has 0 misses, not no reading
    with startup.record().epoch():
        pass
    assert startup_record.read(
        {}, **SETUP_METRICS["setup.cache_misses"][2]) == 0
    assert startup_record.read(
        {}, **SETUP_METRICS["setup.init_state_s"][2]) is None
    with pytest.raises(ValueError, match="kind"):
        startup_record.read({}, kind="gauge", name=["x"])


def test_reader_reads_spans_summed_and_counters_as_the_epoch_began(startup):
    record = startup.record()
    with record.span("startup.build"):
        with record.span("startup.build.init_state"):
            record.count("jax_backend_compile_seconds_total", 5.0)
            record.count("jax_cache_retrieval_seconds_total", 2.0)
        with record.span("startup.build.place_state"):
            pass
    with record.epoch():
        record.count("jax_backend_compile_seconds_total", 100.0)  # the window
    spans = {s["name"]: s["end"] - s["start"] for s in record.spans}
    assert startup_record.read(
        {}, **SETUP_METRICS["setup.init_state_s"][2]) == pytest.approx(
        spans["startup.build.init_state"]
        + spans["startup.build.place_state"])
    assert startup_record.read(
        {}, **SETUP_METRICS["setup.compile_s"][2]) == 3.0
    assert startup_record.read(
        {}, **SETUP_METRICS["setup.cache_load_s"][2]) == 2.0


def test_reader_counts_the_misses_of_the_first_step_alone(startup):
    """A warm run writes the one to three small programs that straddle the
    cache's 0.3 s (PERF.md §6, PR 35), in ``init_state`` or the check; the
    step's own miss is what says the process compiled it."""
    record = startup.record()
    with record.span("startup.build.init_state"):
        record.count("jax_cache_misses_total")
    with record.span("startup.first_step"):
        record.count("jax_cache_hits_total")
    record.count("jax_cache_misses_total")
    args = SETUP_METRICS["setup.cache_misses"][2]
    with record.epoch():
        pass
    assert startup_record.read({}, **args) == 0  # the step came back
    assert startup_record.read({}, **{**args, "phase": [None]}) == 2
    with record.span("startup.first_step"):
        with record.span("startup.hlo_gauges"):
            record.count("jax_cache_misses_total")
    assert startup_record.read({}, **args) == 0  # not snapshotted yet
    with record.epoch():
        pass
    assert startup_record.read({}, **args) == 1


@pytest.fixture(scope="module")
def setup_root(tmp_path_factory, manifest):
    """The scratch manifest: added files only (two tiny cells), the
    committed metric files, and the six entries appended."""
    root = tmp_path_factory.mktemp("tiny_setup_benchmark")
    shutil.copytree(os.path.join(REPO, "benchmark", "metrics"),
                    root / "benchmark" / "metrics")
    for rel, body in TINY_FILES.items():
        path = root / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(body))
    cells = ["t_part3", "t_lm"]
    tiny = json.loads(json.dumps(manifest))
    tiny["configs"] = [
        {"name": n, "source": "test", "file": f"benchmark/configs/{n}.json",
         "reduced": [], "why": "test"} for n in ("tiny_vgg", "tiny_lm")]
    tiny["workloads"] = [
        {"name": c, "config": "tiny_lm" if c == "t_lm" else "tiny_vgg",
         "traffic": c, "chips": 1, "why": "test"} for c in cells]
    for group in ("end_to_end", "per_layer"):
        for metric in tiny[group]:
            if "workloads" in metric:
                metric["workloads"] = cells
    tiny["per_layer"] += ENTRIES
    (root / "BENCHMARK.json").write_text(json.dumps(tiny))
    return str(root)


@pytest.mark.parametrize("cell", ["t_part3", "t_lm"])
def test_traced_run_reports_all_six(setup_root, startup, cell, capsys):
    out = harness.run_cell(setup_root, cell, seed=2**31 + 35, seconds=3.0,
                           trace=True, t0=time.perf_counter(),
                           require_tpu=False)
    _assert_correct_but_for_the_trend(out, capsys.readouterr().out)
    got = {name: m for name, m in out["metrics"].items()
           if name.startswith("setup.")}
    assert set(got) == set(SETUP_METRICS)
    for name, (unit, _, _) in SETUP_METRICS.items():
        assert got[name]["unit"] == unit
    values = {name: m["value"] for name, m in got.items()}
    # package import (here: the fresh record's making) to the first loss
    # holds the state's creation and placement
    assert values["setup.startup_s"] > values["setup.init_state_s"] > 0
    misses = values["setup.cache_misses"]
    assert isinstance(misses, int) and misses >= 0
    assert values["setup.trace_lower_s"] > 0  # paid warm and cold
    assert values["setup.compile_s"] >= 0
    assert values["setup.cache_load_s"] >= 0
    # the snapshot is the window's epoch's: the record went on counting
    record = startup.record()
    assert record.closed and record.at_epoch is not None
    assert record.totals().get("jax_programs_total", 0) \
        >= record.totals(at_epoch=True)["jax_programs_total"] > 0
