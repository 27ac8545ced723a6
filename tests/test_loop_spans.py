"""``train_epoch``'s iteration as a closed set of spans: every phase
bracketed once and delivered on both clocks (profiler annotations always,
tracer spans and step-row fields with a ``Telemetry``), the batch's
arrival and the loop's self time measured, and no host sync, device read
or clock read on telemetry's account when it is off.  And the order of an
iteration: the loop holds one placed batch ahead — batch k+1 is fetched
and placed after step k is dispatched and before its loss is waited for
— except under ``until_step``."""

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
        for k in CHILDREN + ("h2d_bytes",):
            assert k in r, f"missing {k}"
        assert r["h2d_bytes"] == images.nbytes + labels.nbytes
    # Row k holds the placement of batch k+1 and its arrival, waited for
    # in step k's block: the last iteration has no batch ahead.
    for r in rows[:-1]:
        # placement call -> resident ends after the call returned
        assert r["batch_ready_s"] >= r["place_s"] > 0
        assert "batch_lead_s" in r
    assert rows[-1]["place_s"] == 0.0
    assert not {"batch_ready_s", "batch_lead_s"} & set(rows[-1])
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
        period_s = steps[k]["dur"] / 1e6  # dispatch -> the next dispatch
        children = sum(rows[k][f] for f in CHILDREN)
        assert children + rows[k + 1]["loop_self_s"] == \
            pytest.approx(period_s, abs=2e-6)  # the trace keeps microseconds
    # the parent's next sibling starts where it ends
    for a, b in zip(steps, steps[1:]):
        assert a["ts"] + a["dur"] == pytest.approx(b["ts"], abs=1.0)


def test_tracer_spans_nest_under_the_step_span(tmp_path):
    _, _, spans = _run(tmp_path, place_batch=_place)
    # batch 0 is fetched and placed before the first iteration
    primed = [e for e in spans if e["args"].get("primed")]
    assert [e["name"] for e in primed] == ["data_wait", "place_batch"]
    by_step = {}
    for e in spans:
        if e not in primed:
            by_step.setdefault(e["args"]["step"], {})[e["name"]] = e
    assert sorted(by_step) == [0, 1, 2, 3]
    assert primed[1]["ts"] + primed[1]["dur"] \
        <= by_step[0]["train_step"]["ts"] + 1.0
    for step, named in by_step.items():
        ahead = {"place_batch", "batch_ready"} if step < 3 else set()
        assert set(named) == {"train_step", "data_wait", "step_dispatch",
                              "device_block"} | ahead, step
        parent = named["train_step"]
        for name, e in named.items():
            assert e["ts"] >= parent["ts"] - 1.0, name
            assert e["ts"] + e["dur"] <= parent["ts"] + parent["dur"] + 1.0
        # dispatch k, then the fetch (and placement) of k+1, then the block
        order = [named[n] for n in ("step_dispatch", "data_wait",
                                    "place_batch", "device_block")
                 if n in named]
        for a, b in zip(order, order[1:]):
            assert a["ts"] + a["dur"] <= b["ts"] + 1.0
        if not ahead:
            continue
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
    # with a placement: the batch ahead, then the loss, every step but
    # the last, which has no batch ahead
    assert [isinstance(x, tuple) for x in calls.waited_for] \
        == [True, False] * 3 + [False]


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
    def bracketed(*names):
        return [(what, name) for name in names for what in ("enter", "exit")]

    def one_step(*phases):
        return [("enter", "train.step"), *bracketed(*phases),
                ("exit", "train.step")]

    # batch 0 before the first step; step 0 with batch 1 fetched and
    # placed between its dispatch and its block; step 1 with the fetch
    # that ends the epoch, and nothing to place
    assert [(what, name) for what, name, _ in log] == (
        bracketed("train.data_wait", "train.place_batch")
        + one_step("train.step_dispatch", "train.data_wait",
                   "train.place_batch", "train.device_block",
                   "train.bookkeeping")
        + one_step("train.step_dispatch", "train.data_wait",
                   "train.device_block", "train.bookkeeping"))
    assert [n for what, name, n in log
            if what == "enter" and name == "train.step"] == [0, 1]


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


# ------------------------------------------------ (f) one batch ahead

class _Fake:
    """Stands for a device array: ``block_until_ready`` and ``is_ready``
    are written to ``log`` as ``(what, tag)``, and take ``wait_s``."""

    def __init__(self, log, tag, wait_s=0.0, ready=False):
        self.log, self.tag, self.wait_s, self.ready = log, tag, wait_s, ready

    def is_ready(self):
        self.log.append(("is_ready", self.tag))
        return self.ready

    def block_until_ready(self):
        self.log.append(("block", self.tag))
        time.sleep(self.wait_s)
        self.ready = True
        return self


def _ordered(log, n, place=True, loss_wait_s=0.0, loss_ready=False,
             batch_wait_s=0.0, **kw):
    """``train_epoch`` over ``n`` numbered batches with every fetch,
    placement, dispatch and wait written to ``log``."""

    def batches():
        for k in range(n):
            log.append(("fetch", k))
            yield (np.full((2, 3), k, np.uint8), np.full((2,), k, np.int32))

    def place_batch(x, y):
        k = int(x[0, 0])
        log.append(("place", k))
        return (_Fake(log, ("batch", k), batch_wait_s),
                _Fake(log, ("labels", k)))

    def step(state, x, y):
        k = x.tag[1] if place else int(x[0, 0])
        log.append(("dispatch", k))
        return (_State(state.step + 1),
                _Fake(log, ("loss", k), loss_wait_s, loss_ready))

    return train_epoch(step, _State(), batches(),
                       place_batch=place_batch if place else None,
                       loss_print_every=10**9, **kw)


def _loop_order(log):
    """The log without the labels' waits (they follow the images')."""
    return [e for e in log
            if not (isinstance(e[1], tuple) and e[1][0] == "labels")]


def test_batch_k_plus_1_is_fetched_and_placed_under_step_k():
    log = []
    state, _ = _ordered(log, 3, max_iters=10)
    assert state.step == 3
    assert _loop_order(log) == [
        ("fetch", 0), ("place", 0),
        ("dispatch", 0), ("fetch", 1), ("place", 1), ("block", ("loss", 0)),
        ("dispatch", 1), ("fetch", 2), ("place", 2), ("block", ("loss", 1)),
        # the fetch that ends the epoch; step 2 is still finished
        ("dispatch", 2), ("block", ("loss", 2)),
    ]


def test_without_place_batch_the_fetch_still_moves_under_the_step():
    log = []
    state, _ = _ordered(log, 3, place=False, max_iters=10)
    assert state.step == 3
    assert log == [
        ("fetch", 0),
        ("dispatch", 0), ("fetch", 1), ("block", ("loss", 0)),
        ("dispatch", 1), ("fetch", 2), ("block", ("loss", 1)),
        ("dispatch", 2), ("block", ("loss", 2)),
    ]  # and nothing is placed ahead: the loop invents no device_put


@pytest.mark.parametrize("ends_by, fetched, trained", [
    ("iterator", 4, 4),
    # the reference's cap: batch max_iters is fetched, tested, discarded
    ("max_iters", 3, 2),
    ("stop", 3, 2),  # true once two steps are dispatched
])
def test_every_placed_batch_is_trained(ends_by, fetched, trained):
    log = []
    kw = {"iterator": dict(max_iters=10), "max_iters": dict(max_iters=2),
          "stop": dict(max_iters=10, stop=lambda: sum(
              1 for what, _ in log if what == "dispatch") >= 2)}[ends_by]
    state, _ = _ordered(log, 4, **kw)
    assert state.step == trained
    of = {what: [k for w, k in log if w == what]
          for what in ("fetch", "place", "dispatch")}
    assert of["fetch"] == list(range(fetched))
    # none is dropped after placement, and none placed that today's loop
    # would have discarded before placing it
    assert of["place"] == of["dispatch"] == list(range(trained))
    assert [tag for what, tag in log if what == "block"
            and tag[0] == "loss"] == [("loss", k) for k in range(trained)]


def test_until_step_fetches_nothing_ahead(tmp_path):
    """Whether batch k+1 is wanted depends on step k's result: today's
    order, with or without telemetry."""
    expected = [
        ("fetch", 0), ("place", 0), ("dispatch", 0), ("block", ("loss", 0)),
        ("fetch", 1), ("place", 1), ("dispatch", 1), ("block", ("loss", 1)),
    ]
    log = []
    state, _ = _ordered(log, 5, max_iters=10, until_step=2)
    assert state.step == 2 and _loop_order(log) == expected
    log = []
    with Telemetry(tmp_path, flush_every=1) as tel:
        _ordered(log, 5, max_iters=10, until_step=2, telemetry=tel)
    # telemetry waits for the step's own batch inside its block, as
    # before, and asks no loss whether it is ready
    assert [e for e in _loop_order(log) if e[0] != "block"
            or e[1][0] == "loss"] == expected
    assert not [e for e in log if e[0] == "is_ready"]
    rows = read_jsonl(tmp_path / "metrics.jsonl")
    assert all("batch_ready_s" in r and "batch_lead_s" not in r
               for r in rows)
    for r in rows:  # the row's phases are this step's own, fetch first
        assert r["batch_ready_s"] >= r["place_s"] > 0


def test_telemetry_off_asks_no_array_anything(monkeypatch):
    assert get_telemetry() is None
    calls = _Calls(monkeypatch)
    log = []
    _ordered(log, 3, max_iters=10)
    assert calls.device_gets == 0
    assert not [e for e in log if e[0] == "is_ready"]
    assert [tag[0] for what, tag in log if what == "block"] == ["loss"] * 3


def _lead_rows(tmp_path, **kw):
    log = []
    with Telemetry(tmp_path, flush_every=1) as tel:
        _ordered(log, 4, max_iters=10, telemetry=tel, **kw)
    return log, read_jsonl(tmp_path / "metrics.jsonl")


def test_lead_is_positive_when_the_batch_beats_the_loss(tmp_path):
    log, rows = _lead_rows(tmp_path, loss_wait_s=0.03)
    # asked once a batch ahead, at the instant that batch is resident,
    # before the loss is waited for
    for k in range(3):
        at = log.index(("is_ready", ("loss", k)))
        assert log[at - 1] == ("block", ("labels", k + 1))
        assert log[at + 1] == ("block", ("loss", k))
    assert len([e for e in log if e[0] == "is_ready"]) == 3
    for r in rows[:-1]:
        # loss ready - batch ready: the loss's wait, and inside the block
        assert 0.03 <= r["batch_lead_s"] <= r["block_s"]
    assert "batch_lead_s" not in rows[-1]


def test_lead_is_negative_when_a_slow_placement_loses_to_the_loss(tmp_path):
    _, rows = _lead_rows(tmp_path, loss_ready=True, batch_wait_s=0.03)
    for r in rows[:-1]:
        # minus the time the block waited for the batch
        assert -r["block_s"] <= r["batch_lead_s"] <= -0.03
        assert r["batch_ready_s"] >= 0.03


def test_batches_ahead_total_counts_the_positive_leads(tmp_path):
    import json

    log = []
    slow = iter([0.0, 0.03, 0.0, 0.0])  # batch 2 arrives after loss 1

    def place_batch(x, y):
        return _Fake(log, "images", next(slow)), _Fake(log, "labels")

    def step(state, x, y):
        # done already, unless the loop is still to wait 10 ms for it
        ready = state.step == 1
        return (_State(state.step + 1),
                _Fake(log, "loss", 0.0 if ready else 0.01, ready))

    with Telemetry(tmp_path, flush_every=1) as tel:
        train_epoch(step, _State(), _batches(4), place_batch=place_batch,
                    max_iters=10, loss_print_every=10**9, telemetry=tel)
    rows = read_jsonl(tmp_path / "metrics.jsonl")
    leads = [r.get("batch_lead_s") for r in rows]
    assert leads[0] > 0 and leads[1] < 0 and leads[2] > 0
    assert leads[3] is None
    snap = json.loads((tmp_path / "registry.json").read_text())
    counters = {c["name"]: c["value"] for c in snap["counters"]}
    assert counters["batches_ahead_total"] == 2
    assert counters["steps_total"] == 4


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
    # batch k+1's arrival, under the block of the step before its own
    assert "5 spans, overlapped — runs under place_batch/device_block of " \
        "the step before (one batch ahead)" in phase_lines["batch_ready"]
    assert "train_step" not in "".join(
        line for line in out.splitlines() if "------" in line)
    assert "loop_self_s (the loop's own time a step) p50" in out
    assert "batch_ready_s=" in out and "loop_self_s=" in out
    # the share of steps whose next batch was resident before their loss
    lead = next(line for line in out.splitlines()
                if line.startswith("  batch_lead_s"))
    assert " of 4 steps (" in lead and "p50" in lead and "min" in lead
    assert "batch_lead_s=" in out


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
