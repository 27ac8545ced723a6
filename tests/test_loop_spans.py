"""``train_epoch``'s iteration as a closed set of spans: every phase
bracketed once and delivered on both clocks (profiler annotations always,
tracer spans and step-row fields with a ``Telemetry``), the batch's
arrival and the loop's self time measured, and no host sync, device read
or clock read on telemetry's account when it is off."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_machine_learning_tpu.telemetry import Telemetry, get_telemetry
from distributed_machine_learning_tpu.telemetry.sink import read_jsonl
from distributed_machine_learning_tpu.telemetry.tracer import read_trace
from distributed_machine_learning_tpu.train import loop
from distributed_machine_learning_tpu.train.loop import train_epoch
from distributed_machine_learning_tpu.utils import profiling

CHILDREN = ("data_wait_s", "place_s", "dispatch_s", "block_s")
PROFILER_PHASES = ["train.data_wait", "train.place_batch",
                   "train.step_dispatch", "train.device_block",
                   "train.bookkeeping"]


class _State:
    def __init__(self, step=0):
        self.step = step


def _step(state, x, y):
    return _State(state.step + 1), jnp.float32(0.0)


def _place(x, y):
    return jnp.asarray(x), jnp.asarray(y)


def _batches(n=4, b=4):
    r = np.random.default_rng(0)
    return [(r.integers(0, 256, (b, 8, 8, 3)).astype(np.uint8),
             r.integers(0, 10, b).astype(np.int32)) for _ in range(n)]


def _run(tmp_path, n=4, **kw):
    with Telemetry(tmp_path, flush_every=1) as tel:
        state, _ = train_epoch(_step, _State(kw.pop("step0", 0)),
                               _batches(n), max_iters=10,
                               loss_print_every=10**9, telemetry=tel, **kw)
    rows = read_jsonl(tmp_path / "metrics.jsonl")
    spans = [e for e in read_trace(tmp_path / "trace.json")
             if e.get("ph") == "X"]
    return state, rows, spans


class _Calls:
    """Counts calls of ``jax.device_get`` and ``jax.block_until_ready``,
    keeping what each wait was given."""

    def __init__(self, monkeypatch):
        self.device_gets = 0
        self.waited_for = []
        get, block = jax.device_get, jax.block_until_ready

        def counting_get(x):
            self.device_gets += 1
            return get(x)

        def counting_block(x):
            self.waited_for.append(x)
            return block(x)

        monkeypatch.setattr(jax, "device_get", counting_get)
        monkeypatch.setattr(jax, "block_until_ready", counting_block)


# ------------------------------------------------- (a) rows with a placement

def test_rows_carry_arrival_bytes_block_and_self_time(tmp_path):
    _, rows, _ = _run(tmp_path, place_batch=_place)
    assert [r["batch"] for r in rows] == [0, 1, 2, 3]
    images, labels = _batches(1)[0]
    for r in rows:
        for k in CHILDREN + ("batch_ready_s", "h2d_bytes"):
            assert k in r, f"missing {k}"
        # placement call -> resident ends after the call returned
        assert r["batch_ready_s"] >= r["place_s"] > 0
        assert r["h2d_bytes"] == images.nbytes + labels.nbytes
    # A row is written before its own iteration ends: the first has no
    # self time yet, each later one carries its predecessor's.
    assert "loop_self_s" not in rows[0]
    assert all(r["loop_self_s"] >= 0 for r in rows[1:])


def test_children_and_self_time_add_up_to_the_period(tmp_path):
    _, rows, spans = _run(tmp_path, place_batch=_place)
    steps = sorted((e for e in spans if e["name"] == "train_step"),
                   key=lambda e: e["args"]["step"])
    assert [e["args"]["step"] for e in steps] == [0, 1, 2, 3]
    for k in range(3):
        period_s = steps[k]["dur"] / 1e6  # fetch start -> next fetch start
        children = sum(rows[k][f] for f in CHILDREN)
        assert children + rows[k + 1]["loop_self_s"] == \
            pytest.approx(period_s, abs=2e-6)  # the trace keeps microseconds
    # the parent's next sibling starts where it ends
    for a, b in zip(steps, steps[1:]):
        assert a["ts"] + a["dur"] == pytest.approx(b["ts"], abs=1.0)


def test_tracer_spans_nest_under_the_step_span(tmp_path):
    _, _, spans = _run(tmp_path, place_batch=_place)
    by_step = {}
    for e in spans:
        by_step.setdefault(e["args"]["step"], {})[e["name"]] = e
    for step, named in by_step.items():
        assert set(named) == {"train_step", "data_wait", "place_batch",
                              "step_dispatch", "device_block",
                              "batch_ready"}, step
        parent = named["train_step"]
        for name, e in named.items():
            assert e["ts"] >= parent["ts"] - 1.0, name
            assert e["ts"] + e["dur"] <= parent["ts"] + parent["dur"] + 1.0
        # arrival is timed from the placement call into the block
        ready, block = named["batch_ready"], named["device_block"]
        assert ready["ts"] == pytest.approx(named["place_batch"]["ts"])
        assert block["ts"] <= ready["ts"] + ready["dur"] \
            <= block["ts"] + block["dur"] + 1.0


def test_h2d_bytes_total_counts_every_step(tmp_path):
    import json

    _run(tmp_path, place_batch=_place)
    snap = json.loads((tmp_path / "registry.json").read_text())
    counters = {c["name"]: c["value"] for c in snap["counters"]}
    images, labels = _batches(1)[0]
    assert counters["h2d_bytes_total"] == 4 * (images.nbytes + labels.nbytes)


def test_device_resident_batches_count_no_host_bytes(tmp_path):
    batches = [_place(x, y) for x, y in _batches(2)]
    with Telemetry(tmp_path, flush_every=1) as tel:
        train_epoch(_step, _State(), batches, max_iters=10,
                    loss_print_every=10**9, telemetry=tel)
    assert [r["h2d_bytes"] for r in read_jsonl(tmp_path / "metrics.jsonl")] \
        == [0, 0]


# ---------------------------------------------- (b) rows without a placement

def test_without_place_batch_no_arrival_and_no_wait_on_host_arrays(
        tmp_path, monkeypatch):
    calls = _Calls(monkeypatch)
    _, rows, spans = _run(tmp_path, n=3)
    assert len(rows) == 3
    for r in rows:
        assert "batch_ready_s" not in r
        assert r["place_s"] == 0.0  # place.ms reads it in every cell
        assert r["h2d_bytes"] > 0  # jit moves the same bytes
    assert not {"batch_ready", "place_batch"} & {e["name"] for e in spans}
    # one wait a step, and it is the loss
    assert len(calls.waited_for) == 3
    assert all(isinstance(x, jax.Array) and x.shape == ()
               for x in calls.waited_for)


# ------------------------------------------------ (c) nothing when it is off

def test_telemetry_off_reads_and_waits_for_nothing_more(monkeypatch):
    assert get_telemetry() is None
    calls = _Calls(monkeypatch)
    clock_reads = []
    perf_counter = time.perf_counter
    monkeypatch.setattr(time, "perf_counter",
                        lambda: clock_reads.append(1) or perf_counter())
    state, _ = train_epoch(_step, _State(), _batches(3), place_batch=_place,
                           max_iters=10, loss_print_every=10**9)
    monkeypatch.undo()
    assert state.step == 3
    assert calls.device_gets == 0
    assert len(calls.waited_for) == 3  # the loss, never the batch
    assert all(not isinstance(x, tuple) for x in calls.waited_for)
    assert len(clock_reads) == 2 * 3  # the timer's start and stop, as before


def test_telemetry_on_reads_the_step_counter_once_an_epoch(tmp_path,
                                                           monkeypatch):
    calls = _Calls(monkeypatch)
    _run(tmp_path, place_batch=_place)
    assert calls.device_gets == 1
    # with a placement: the batch, then the loss, every step
    assert [isinstance(x, tuple) for x in calls.waited_for] \
        == [True, False] * 4


def test_until_step_pays_for_its_own_read_and_the_row_reuses_it(
        tmp_path, monkeypatch):
    calls = _Calls(monkeypatch)
    state, rows, _ = _run(tmp_path, place_batch=_place, until_step=3)
    assert state.step == 3 and [r["step"] for r in rows] == [1, 2, 3]
    assert calls.device_gets == 1 + 3


# ------------------------------------------------ (d) the profiler's clock

class _Recorded:
    def __init__(self, log, name, step_num):
        self.log, self.name, self.step_num = log, name, step_num

    def __enter__(self):
        self.log.append(("enter", self.name, self.step_num))

    def __exit__(self, *exc):
        self.log.append(("exit", self.name, self.step_num))


@pytest.mark.parametrize("with_telemetry", [False, True])
def test_profiler_names_once_a_step_nested_under_the_step(
        tmp_path, monkeypatch, with_telemetry):
    log = []
    monkeypatch.setattr(
        profiling, "annotate",
        lambda name, step_num=None: _Recorded(log, name, step_num))
    kw = dict(place_batch=_place, max_iters=10, loss_print_every=10**9)
    if with_telemetry:
        with Telemetry(tmp_path, flush_every=1) as tel:
            train_epoch(_step, _State(), _batches(2), telemetry=tel, **kw)
    else:
        train_epoch(_step, _State(), _batches(2), **kw)
    one_step = [("enter", "train.step")]
    for name in PROFILER_PHASES:
        one_step += [("enter", name), ("exit", name)]
    one_step.append(("exit", "train.step"))
    # two whole steps, then the fetch that ends the epoch
    assert [(what, name) for what, name, _ in log] == one_step * 2 + [
        ("enter", "train.step"), ("enter", "train.data_wait"),
        ("exit", "train.data_wait"), ("exit", "train.step")]
    assert [n for what, name, n in log
            if what == "enter" and name == "train.step"] == [0, 1, 2]


def test_annotate_is_a_step_annotation_when_numbered():
    assert isinstance(profiling.annotate("x"), jax.profiler.TraceAnnotation)
    assert isinstance(profiling.annotate("x", step_num=0),
                      jax.profiler.StepTraceAnnotation)
    with profiling.annotate("train.step", step_num=3):
        with profiling.annotate("train.data_wait"):
            pass


def test_every_phase_is_declared_once():
    assert list(loop._PHASES) == PROFILER_PHASES[:-1]
    spans = [span for span, _ in loop._PHASES.values()]
    fields = [field for _, field in loop._PHASES.values()]
    assert spans == ["data_wait", "place_batch", "step_dispatch",
                     "device_block"]
    assert fields == list(CHILDREN)


# ------------------------------------------------ (e) the step in the rows

def test_row_step_equals_the_optimizer_count_when_nothing_is_skipped(
        tmp_path):
    state, rows, _ = _run(tmp_path, place_batch=_place, step0=17)
    assert state.step == 21
    assert [r["step"] for r in rows] == [18, 19, 20, 21]


def test_row_step_with_a_real_jitted_step(tmp_path):
    @jax.jit
    def step(state, x, y):
        return ({"step": state["step"] + 1},
                jnp.mean(x.astype(jnp.float32)))

    class State(dict):
        step = property(lambda self: self["step"])

    def train_step(state, x, y):
        new, loss = step(dict(state), x, y)
        return State(new), loss

    with Telemetry(tmp_path, flush_every=1) as tel:
        state, _ = train_epoch(
            train_step, State(step=jnp.int32(5)), _batches(3),
            place_batch=_place, max_iters=10, loss_print_every=10**9,
            telemetry=tel)
    rows = read_jsonl(tmp_path / "metrics.jsonl")
    assert [r["step"] for r in rows] == [6, 7, 8] and int(state.step) == 8


# ------------------------------------------------ tools/trace_summary.py

def test_trace_summary_shows_arrival_and_self_time(tmp_path):
    import os
    import subprocess
    import sys

    _run(tmp_path, n=6, place_batch=_place)
    tool = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "trace_summary.py")
    out = subprocess.run([sys.executable, tool, str(tmp_path)],
                         capture_output=True, text=True, check=True,
                         timeout=60).stdout
    phase_lines = {line.split()[0]: line for line in out.splitlines()
                   if line.startswith("  ") and "%" in line}
    # the four phases and the loop's own time share the step's wall-clock
    shares = [float(phase_lines[p].split()[1].rstrip("%"))
              for p in ("data_wait", "place_batch", "step_dispatch",
                        "device_block", "loop_self")]
    assert sum(shares) == pytest.approx(100.0, abs=0.3)
    assert "6 train_step spans" in phase_lines["loop_self"]
    assert "overlapped — runs under place_batch/dispatch/device_block" \
        in phase_lines["batch_ready"]
    assert "train_step" not in "".join(
        line for line in out.splitlines() if "------" in line)
    assert "loop_self_s (the loop's own time a step) p50" in out
    assert "batch_ready_s=" in out and "loop_self_s=" in out


# ------------------------------------------------ the tracer's fsync option

@pytest.mark.parametrize("fsync", [True, False])
def test_tracer_fsyncs_only_when_asked(tmp_path, monkeypatch, fsync):
    """``Telemetry(fsync=False)`` (the benchmark's traced run) used to stop
    the rows' fsync and leave the trace's: one every 20 spans, inside the
    loop's self time."""
    import os

    from distributed_machine_learning_tpu.telemetry.tracer import SpanTracer

    synced = []
    monkeypatch.setattr(os, "fsync", lambda fd: synced.append(fd))
    tracer = SpanTracer(tmp_path / "trace.json", flush_every=2,
                        enabled=True, fsync=fsync)
    for i in range(4):
        tracer.complete("x", 0.0, 1.0, step=i)
    assert len(synced) == (2 if fsync else 0)
    tracer.close()
    assert len(read_trace(tmp_path / "trace.json")) == 4
    with Telemetry(tmp_path / "tel", fsync=fsync) as tel:
        assert tel.tracer.fsync is fsync and tel.metrics.fsync is fsync
