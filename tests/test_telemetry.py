"""Streaming telemetry subsystem (telemetry/): registry semantics,
crash-safe JSONL sinks, Chrome-trace spans, loop integration, and the
chaos-run acceptance — one attempt-tagged stream spanning a supervised
restart, with registry counters matching the run's FaultEvents exactly.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from distributed_machine_learning_tpu.telemetry import (
    JsonlSink,
    MetricsRegistry,
    SpanTracer,
    Telemetry,
    get_telemetry,
    read_jsonl,
    read_trace,
    set_telemetry,
)

TOOLS = os.path.join(os.path.dirname(os.path.dirname(__file__)), "tools")


# ---------------------------------------------------------------------------
# Registry (telemetry/registry.py)
# ---------------------------------------------------------------------------


def test_counter_gauge_semantics():
    reg = MetricsRegistry()
    c = reg.counter("steps_total")
    c.inc()
    c.inc(4)
    assert c.value == 5
    assert reg.counter("steps_total") is c  # get-or-create
    with pytest.raises(ValueError):
        c.inc(-1)  # counters are monotonic
    g = reg.gauge("queue_depth")
    g.set(3)
    g.set(1)
    assert g.value == 1


def test_labels_key_distinct_instruments():
    reg = MetricsRegistry()
    a = reg.counter("fault_events", kind="stalls")
    b = reg.counter("fault_events", kind="restarts")
    a.inc(2)
    b.inc(7)
    assert a is not b
    assert reg.counter("fault_events", kind="stalls").value == 2
    assert reg.counter("fault_events", kind="restarts").value == 7


def test_histogram_quantiles():
    reg = MetricsRegistry()
    h = reg.histogram("step_seconds", buckets=[0.1 * i for i in range(1, 11)])
    for v in np.linspace(0.05, 0.95, 100):
        h.observe(float(v))
    q = h.quantiles()
    assert h.count == 100
    assert abs(h.mean - 0.5) < 0.01
    # Fixed-bucket interpolation: right bucket, not exact rank.
    assert 0.4 <= q["p50"] <= 0.6
    assert 0.85 <= q["p95"] <= 1.0
    assert q["max"] == pytest.approx(0.95)
    # Observations past the last bound land in +inf; its quantile
    # reports the exact max rather than interpolating to infinity.
    h.observe(5.0)
    assert h.percentile(1.0) == 5.0


def test_histogram_empty_and_validation():
    reg = MetricsRegistry()
    h = reg.histogram("empty")
    assert h.quantiles() == {"p50": 0.0, "p95": 0.0, "p99": 0.0, "max": 0.0}
    with pytest.raises(ValueError):
        h.percentile(1.5)


def test_prometheus_export_format():
    reg = MetricsRegistry()
    reg.counter("fault_events", kind="stalls").inc(3)
    reg.gauge("examples_per_s").set(123.0)
    h = reg.histogram("step_seconds", buckets=[0.1, 1.0])
    h.observe(0.05)
    h.observe(0.5)
    text = reg.to_prometheus()
    assert '# TYPE fault_events counter' in text
    assert 'fault_events{kind="stalls"} 3' in text
    assert "examples_per_s 123.0" in text
    assert 'step_seconds_bucket{le="+Inf"} 2' in text
    assert "step_seconds_count 2" in text


def _parse_prometheus(text):
    """A minimal exposition-format parser for round-trip assertions:
    {(name, ((label, value), ...)): float}, plus {family: type}.

    Label values are matched with the escape-aware pattern
    ``(?:[^"\\\\]|\\\\.)*`` (a quote inside a value is always written
    escaped, so an unescaped quote really ends the value) and unescaped
    in a SINGLE pass — sequential str.replace would corrupt values like
    a literal backslash-n, and splitting on '",' would cut any value
    containing a quote-then-comma.
    """
    import re

    label_re = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')

    def unescape(v):
        return re.sub(r"\\(.)",
                      lambda m: "\n" if m.group(1) == "n" else m.group(1),
                      v)

    series, types = {}, {}
    for line in text.splitlines():
        if not line.strip():
            continue
        if line.startswith("# TYPE "):
            _, _, fam, kind = line.split(" ")
            assert fam not in types, f"duplicate TYPE for {fam}"
            types[fam] = kind
            continue
        head, _, value = line.rpartition(" ")
        labels = ()
        if "{" in head:
            name, _, rest = head.partition("{")
            labels = tuple((k, unescape(v))
                           for k, v in label_re.findall(rest.rstrip("}")))
        else:
            name = head
        key = (name, labels)
        assert key not in series, f"duplicate series {key}"
        series[key] = float(value)
    return series, types


def test_prometheus_labeled_round_trip_with_escaping(tmp_path):
    """The textfile export must survive hostile label values (quotes,
    backslashes, newlines — an abort reason or fault spec carried as a
    label) and parse back to the exact instrument values."""
    from distributed_machine_learning_tpu.telemetry import (
        write_prometheus,
    )

    reg = MetricsRegistry()
    hostile = 'rank "1"\\fault\nspec'
    tricky = 'a",b\\n'  # quote-then-comma + literal backslash-n
    reg.counter("gang_straggler", rank="1").inc(2)
    reg.counter("fault_events", kind=hostile).inc(5)
    reg.counter("fault_events", kind=tricky).inc(1)
    reg.gauge("gang_skew_ratio", why='a "quoted" reason').set(7.5)
    text = reg.to_prometheus()
    for line in text.splitlines():
        assert "\n" not in line  # the raw newline must be escaped away
    assert r"\n" in text and r"\"" in text
    series, types = _parse_prometheus(text)
    assert types["gang_straggler"] == "counter"
    assert types["gang_skew_ratio"] == "gauge"
    assert series[("gang_straggler", (("rank", "1"),))] == 2
    assert series[("fault_events", (("kind", hostile),))] == 5
    assert series[("fault_events", (("kind", tricky),))] == 1
    assert series[("gang_skew_ratio",
                   (("why", 'a "quoted" reason'),))] == 7.5
    # And the atomic file writer emits the same parseable payload.
    write_prometheus(tmp_path / "m.prom", reg)
    assert (tmp_path / "m.prom").read_text() == text


@pytest.mark.parametrize("fsync", [True, False])
def test_registry_export_fsyncs_only_when_asked(tmp_path, monkeypatch,
                                                fsync):
    """``Telemetry(fsync=False)`` (the benchmark's traced window) stopped
    the rows' and the trace's fsync while every registry export still
    fsynced ``metrics.prom``: the flag now reaches ``write_prometheus``
    (whose own default stays durable), and the file is whole either way."""
    synced = []
    monkeypatch.setattr(os, "fsync", lambda fd: synced.append(fd))
    tel = Telemetry(tmp_path, fsync=fsync, enabled=True)
    tel.registry.counter("steps_total").inc(3)
    tel.flush()
    tel.close()
    assert bool(synced) is fsync
    assert "steps_total 3" in (tmp_path / "metrics.prom").read_text()
    assert not (tmp_path / "metrics.prom.tmp").exists()


def test_an_adopted_registry_is_exported_live_and_wins_a_shared_series():
    """``MetricsRegistry.adopt``: the other registry's instruments are
    exported with this one's as they stand at each export (no copy, no
    second increment), once however often adopted; a series both hold —
    a resume rehydrated the prior attempt's — is exported once, the
    adopted registry's."""
    own, other = MetricsRegistry(), MetricsRegistry()
    own.counter("steps_total").inc(3)
    own.counter("jax_programs_total", phase="startup").inc(40)  # rehydrated
    other.counter("jax_programs_total", phase="startup").inc(2)
    own.adopt(other)
    own.adopt(other)
    own.adopt(own)
    other.counter("jax_programs_total", phase="train").inc()  # made later
    other.gauge("process_age_at_import_s").set(1.5)
    snap = own.snapshot()
    assert {(c["name"], c["labels"].get("phase")): c["value"]
            for c in snap["counters"]} == {
        ("steps_total", None): 3, ("jax_programs_total", "startup"): 2,
        ("jax_programs_total", "train"): 1}
    assert len(snap["counters"]) == 3
    assert [g["value"] for g in snap["gauges"]] == [1.5]
    text = own.to_prometheus()
    assert text.count("# TYPE jax_programs_total counter") == 1
    assert text.count('jax_programs_total{phase="startup"}') == 1
    assert 'jax_programs_total{phase="startup"} 2' in text
    assert other.snapshot()["counters"] == [
        c for c in snap["counters"] if c["name"] != "steps_total"]


def test_prometheus_histogram_bucket_round_trip():
    """Labeled histograms: bucket bounds strictly ascending with +Inf
    last, cumulative counts non-decreasing and ending at _count, _sum
    matching the observations — per label series, under one TYPE."""
    reg = MetricsRegistry()
    # Creation order descends on purpose: export must still ascend.
    for shard in ("a", "b"):
        h = reg.histogram("step_seconds", buckets=[1.0, 0.1, 0.5],
                          shard=shard)
        obs = [0.05, 0.3, 0.3, 0.7, 2.0] if shard == "a" else [0.2]
        for v in obs:
            h.observe(v)
    text = reg.to_prometheus()
    assert text.count("# TYPE step_seconds histogram") == 1
    series, _ = _parse_prometheus(text)
    for shard, total, summed in (("a", 5, 3.35), ("b", 1, 0.2)):
        sel = {
            dict(labels)["le"]: v
            for (name, labels), v in series.items()
            if name == "step_seconds_bucket"
            and dict(labels)["shard"] == shard
        }
        bounds = [b for b in sel if b != "+Inf"]
        assert [float(b) for b in bounds] == sorted(float(b)
                                                    for b in bounds)
        assert list(sel)[-1] == "+Inf"  # +Inf closes the series
        cum = [sel[b] for b in sel]
        assert cum == sorted(cum)  # cumulative counts never decrease
        assert cum[-1] == total
        assert series[("step_seconds_count",
                       (("shard", shard),))] == total
        assert series[("step_seconds_sum",
                       (("shard", shard),))] == pytest.approx(summed)


def test_registry_snapshot_shape():
    reg = MetricsRegistry()
    reg.counter("c").inc()
    reg.gauge("g").set(2.0)
    reg.histogram("h").observe(0.01)
    snap = reg.snapshot()
    assert snap["counters"][0] == {"name": "c", "labels": {}, "value": 1}
    assert snap["gauges"][0]["value"] == 2.0
    hist = snap["histograms"][0]
    assert hist["count"] == 1 and "p95" in hist and "max" in hist


# ---------------------------------------------------------------------------
# JSONL sink (telemetry/sink.py)
# ---------------------------------------------------------------------------


def test_sink_appends_and_flushes(tmp_path):
    p = tmp_path / "m.jsonl"
    with JsonlSink(p, flush_every=2, enabled=True) as sink:
        sink.write({"step": 0})
        sink.write({"step": 1})  # hits the flush threshold
        # Rows up to the flush boundary are durable BEFORE close.
        assert len(read_jsonl(p)) == 2
        sink.write({"step": 2})
    assert [r["step"] for r in read_jsonl(p)] == [0, 1, 2]


def test_sink_append_mode_survives_restart(tmp_path):
    # A second sink on the same path (the supervisor-restart case) must
    # APPEND to the survivor rows, never truncate them.
    p = tmp_path / "m.jsonl"
    with JsonlSink(p, flush_every=1, enabled=True) as s:
        s.write({"attempt": 0, "step": 0})
    with JsonlSink(p, flush_every=1, enabled=True) as s:
        s.write({"attempt": 1, "step": 0})
    assert [r["attempt"] for r in read_jsonl(p)] == [0, 1]


def test_sink_disabled_writes_nothing(tmp_path):
    p = tmp_path / "m.jsonl"
    with JsonlSink(p, enabled=False) as sink:
        sink.write({"step": 0})
    assert not p.exists()


def test_read_jsonl_tolerates_torn_final_line(tmp_path):
    # A kill mid-write leaves one partial trailing line — the reader
    # must return every complete row and drop the torn one.
    p = tmp_path / "m.jsonl"
    with open(p, "w") as f:
        f.write(json.dumps({"step": 0}) + "\n")
        f.write(json.dumps({"step": 1}) + "\n")
        f.write('{"step": 2, "loss"')  # torn by the simulated kill
    rows = read_jsonl(p)
    assert [r["step"] for r in rows] == [0, 1]


def test_read_jsonl_raises_on_mid_file_corruption(tmp_path):
    p = tmp_path / "m.jsonl"
    with open(p, "w") as f:
        f.write('{"step": 0}\n')
        f.write("NOT JSON\n")
        f.write('{"step": 2}\n')
    with pytest.raises(json.JSONDecodeError):
        read_jsonl(p)


def test_sink_validates_flush_every(tmp_path):
    with pytest.raises(ValueError):
        JsonlSink(tmp_path / "x.jsonl", flush_every=0)


def test_sink_reopen_truncates_torn_final_line(tmp_path):
    # A restart must not weld its first row onto the dead run's torn
    # final line (that would corrupt BOTH and move the damage mid-file,
    # where read_jsonl rightly raises).
    p = tmp_path / "m.jsonl"
    with open(p, "w") as f:
        f.write(json.dumps({"attempt": 0, "step": 0}) + "\n")
        f.write('{"attempt": 0, "step"')  # killed mid-write
    with JsonlSink(p, flush_every=1, enabled=True) as s:
        s.write({"attempt": 1, "step": 0})
    rows = read_jsonl(p, tolerate_truncation=False)  # strictly clean now
    assert [(r["attempt"], r["step"]) for r in rows] == [(0, 0), (1, 0)]


def test_prometheus_one_type_line_per_family():
    # The exposition format allows ONE `# TYPE` per metric family;
    # promtool rejects duplicates, so multi-kind fault counters (every
    # chaos run) must group under a single header.
    reg = MetricsRegistry()
    reg.counter("fault_events", kind="stalls").inc()
    reg.counter("fault_events", kind="restarts").inc(2)
    text = reg.to_prometheus()
    assert text.count("# TYPE fault_events counter") == 1
    assert 'fault_events{kind="stalls"} 1' in text
    assert 'fault_events{kind="restarts"} 2' in text


# ---------------------------------------------------------------------------
# Chrome-trace span tracer (telemetry/tracer.py)
# ---------------------------------------------------------------------------


def test_tracer_closed_file_is_valid_json_with_nested_spans(tmp_path):
    p = tmp_path / "trace.json"
    tr = SpanTracer(p, flush_every=1, enabled=True)
    with tr.span("outer", step=0):
        with tr.span("inner", step=0):
            pass
    tr.instant("fault_stalls")
    tr.close()
    events = json.loads(p.read_text())  # strict JSON after a clean close
    assert isinstance(events, list) and len(events) == 3
    by_name = {e["name"]: e for e in events}
    outer, inner = by_name["outer"], by_name["inner"]
    assert outer["ph"] == "X" and inner["ph"] == "X"
    # Proper nesting: the inner span's [ts, ts+dur] lies within the
    # outer's — that containment is what the viewer renders as a stack.
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-6
    assert by_name["fault_stalls"]["ph"] == "i"


def test_tracer_unterminated_trace_still_loads(tmp_path):
    # No close() — the crash case.  The JSON Array Format's trailing ]
    # is optional for viewers; read_trace applies the same tolerance.
    p = tmp_path / "trace.json"
    tr = SpanTracer(p, flush_every=1, enabled=True)
    with tr.span("step_dispatch", step=0):
        pass
    with tr.span("device_block", step=0):
        pass
    tr.flush()
    with pytest.raises(json.JSONDecodeError):
        json.loads(p.read_text())  # not yet strict JSON...
    names = [e["name"] for e in read_trace(p)]  # ...but fully readable
    assert names == ["step_dispatch", "device_block"]


def test_tracer_reopen_after_clean_close_stays_one_valid_array(tmp_path):
    # Run 1 closes the array; run 2 (same dir, the append/resume
    # contract) must strip the terminator before appending — events
    # after a ']' are rejected by viewers (unlike a missing ']').
    p = tmp_path / "trace.json"
    tr1 = SpanTracer(p, flush_every=1, enabled=True)
    with tr1.span("run1"):
        pass
    tr1.close()
    tr2 = SpanTracer(p, flush_every=1, enabled=True)
    with tr2.span("run2"):
        pass
    tr2.close()
    events = json.loads(p.read_text())  # strictly valid, ONE array
    assert [e["name"] for e in events] == ["run1", "run2"]
    # And chronological: run2's anchor is later wall-clock.
    assert events[0]["ts"] <= events[1]["ts"]


def test_tracer_reopen_after_torn_event_truncates_it(tmp_path):
    p = tmp_path / "trace.json"
    tr1 = SpanTracer(p, flush_every=1, enabled=True)
    with tr1.span("survivor"):
        pass
    tr1.flush()
    with open(p, "a") as f:
        f.write(',\n{"name": "torn_by_kil')  # killed mid-event
    tr2 = SpanTracer(p, flush_every=1, enabled=True)
    with tr2.span("after_restart"):
        pass
    tr2.close()
    events = json.loads(p.read_text())
    assert [e["name"] for e in events] == ["survivor", "after_restart"]


def test_tracer_span_records_error_and_max_events(tmp_path):
    p = tmp_path / "trace.json"
    tr = SpanTracer(p, flush_every=1, enabled=True, max_events=2)
    with pytest.raises(RuntimeError):
        with tr.span("restart_attempt", attempt=0):
            raise RuntimeError("injected")
    tr.instant("second")
    tr.instant("dropped-by-cap")
    tr.close()
    events = json.loads(p.read_text())
    assert len(events) == 2  # the cap held
    assert events[0]["args"]["error"] == "RuntimeError"


# ---------------------------------------------------------------------------
# Telemetry facade (attempt tagging, registry export)
# ---------------------------------------------------------------------------


def test_telemetry_log_step_tags_attempt_and_exports(tmp_path):
    with Telemetry(tmp_path, flush_every=1) as tel:
        tel.registry.counter("fault_events", kind="stalls").inc()
        tel.log_step(0, iter_s=0.1)
        tel.set_attempt(1)
        tel.log_step(0, iter_s=0.2)
    rows = read_jsonl(tmp_path / "metrics.jsonl")
    assert [r["attempt"] for r in rows] == [0, 1]
    snap = json.loads((tmp_path / "registry.json").read_text())
    assert snap["counters"][0]["value"] == 1
    assert 'fault_events{kind="stalls"} 1' in (
        (tmp_path / "metrics.prom").read_text()
    )


def test_telemetry_resumes_attempt_numbering_from_disk(tmp_path):
    # A re-executed process (external supervisor, os._exit restart) must
    # continue the attempt sequence already on disk, not restart at 0.
    with Telemetry(tmp_path, flush_every=1) as tel:
        tel.set_attempt(2)
        tel.log_step(5, iter_s=0.1)
    tel2 = Telemetry(tmp_path, flush_every=1)
    assert tel2.attempt == 3
    # set_attempt never moves backwards: the in-process supervisor's
    # attempt 0 keeps the resumed offset.
    tel2.set_attempt(0)
    assert tel2.attempt == 3
    tel2.close()


def test_telemetry_off_by_default():
    assert get_telemetry() is None


def test_telemetry_resume_rehydrates_counter_totals(tmp_path):
    # A re-exec'd process resuming into the same dir must extend the
    # exported counter totals, not clobber registry.json back to zero —
    # same append-not-truncate contract as the stream artifacts.
    with Telemetry(tmp_path, flush_every=1) as tel:
        tel.registry.counter("fault_events", kind="ckpt_kills").inc()
        tel.log_step(0, iter_s=0.1)
    with Telemetry(tmp_path, flush_every=1) as tel2:
        assert tel2.attempt == 1
        tel2.registry.counter("fault_events", kind="ckpt_kills").inc()
        tel2.log_step(0, iter_s=0.1)
    snap = json.loads((tmp_path / "registry.json").read_text())
    kills = [c["value"] for c in snap["counters"]
             if c["labels"].get("kind") == "ckpt_kills"]
    assert kills == [2]  # both processes' kills, one counter


# ---------------------------------------------------------------------------
# train_epoch integration (phase spans, throughput, zero-cost off)
# ---------------------------------------------------------------------------


class _S:
    def __init__(self, step=0):
        self.step = step


def _fake_step(s, x, y):
    return _S(s.step + 1), 0.0


def _img_batches(n=4, b=4):
    r = np.random.default_rng(0)
    return [(r.integers(0, 256, (b, 8, 8, 3)).astype(np.uint8),
             r.integers(0, 10, b).astype(np.int32)) for _ in range(n)]


def test_train_epoch_emits_phase_spans_and_rows(tmp_path):
    from distributed_machine_learning_tpu.train.loop import train_epoch

    with Telemetry(tmp_path, flush_every=1) as tel:
        tel.flops_per_example = 1e6
        state, _ = train_epoch(
            _fake_step, _S(), _img_batches(3),
            place_batch=lambda x, y: (x, y), max_iters=10,
            loss_print_every=10**9, telemetry=tel,
        )
    assert state.step == 3
    rows = read_jsonl(tmp_path / "metrics.jsonl")
    assert len(rows) == 3
    for r in rows:
        assert r["attempt"] == 0
        for k in ("iter_s", "data_wait_s", "place_s", "dispatch_s",
                  "block_s", "examples_per_s", "mfu"):
            assert k in r, f"missing {k}"
        assert "tokens_per_s" not in r  # image batches have no tokens
    # The first (timer-excluded, compile-bearing) iteration is tagged so
    # quantile consumers can keep it out of the tail.
    assert rows[0].get("warmup") is True
    assert all("warmup" not in r for r in rows[1:])
    names = {e["name"] for e in read_trace(tmp_path / "trace.json")}
    assert {"data_wait", "place_batch", "step_dispatch",
            "device_block"} <= names
    snap = json.loads((tmp_path / "registry.json").read_text())
    counters = {c["name"]: c["value"] for c in snap["counters"]}
    assert counters["steps_total"] == 3
    hists = {h["name"]: h for h in snap["histograms"]}
    # Histogram mirrors the timer's warm-up protocol: 3 steps, first
    # excluded — registry quantiles and summary() describe one sample.
    assert hists["step_seconds"]["count"] == 2


def test_train_epoch_applies_static_step_counters(tmp_path):
    """``Telemetry.step_counters``: static per-step increments the CLI
    registers (ring_wire_bytes) accumulate once per completed step and
    land in the registry snapshot next to the compression-ratio gauge —
    the surface trace_summary and gang benches read bytes-saved from."""
    from distributed_machine_learning_tpu.train.loop import train_epoch

    with Telemetry(tmp_path, flush_every=1) as tel:
        tel.step_counters["ring_wire_bytes"] = 1000
        tel.registry.gauge("ring_compression_ratio").set(4.0)
        train_epoch(
            _fake_step, _S(), _img_batches(3),
            place_batch=lambda x, y: (x, y), max_iters=10,
            loss_print_every=10**9, telemetry=tel,
        )
    snap = json.loads((tmp_path / "registry.json").read_text())
    counters = {c["name"]: c["value"] for c in snap["counters"]}
    assert counters["ring_wire_bytes"] == 3000
    gauges = {g["name"]: g["value"] for g in snap["gauges"]}
    assert gauges["ring_compression_ratio"] == 4.0
    # trace_summary's ring section renders from exactly this snapshot.
    out = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "trace_summary.py"),
         str(tmp_path)],
        capture_output=True, text=True, check=True, timeout=60,
    ).stdout
    assert "Ring wire compression" in out
    assert "3,000" in out and "compression ratio        4.00x" in out


def test_train_epoch_applies_labeled_step_counters(tmp_path):
    """Round-11 satellite: a ``step_counters`` entry may be a list of
    ``(labels, value)`` sub-counters — the per-AXIS ring_wire_bytes
    split a ``--ring-topology`` run registers — and trace_summary
    renders the inner/outer breakdown under the ring section."""
    from distributed_machine_learning_tpu.train.loop import train_epoch

    with Telemetry(tmp_path, flush_every=1) as tel:
        tel.step_counters["ring_wire_bytes"] = [
            ({"axis": "inner"}, 800), ({"axis": "outer"}, 200),
        ]
        train_epoch(
            _fake_step, _S(), _img_batches(3),
            place_batch=lambda x, y: (x, y), max_iters=10,
            loss_print_every=10**9, telemetry=tel,
        )
    snap = json.loads((tmp_path / "registry.json").read_text())
    wire = {c["labels"]["axis"]: c["value"] for c in snap["counters"]
            if c["name"] == "ring_wire_bytes"}
    assert wire == {"inner": 2400, "outer": 600}
    out = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "trace_summary.py"),
         str(tmp_path)],
        capture_output=True, text=True, check=True, timeout=60,
    ).stdout
    assert "Ring wire compression" in out
    assert "axis=inner" in out and "axis=outer" in out
    assert "(80%)" in out and "(20%)" in out


def test_train_epoch_token_batches_report_tokens_per_s(tmp_path):
    from distributed_machine_learning_tpu.train.loop import train_epoch

    r = np.random.default_rng(1)
    batches = [(r.integers(0, 32, (2, 16)).astype(np.int32),
                r.integers(0, 32, (2, 16)).astype(np.int32))
               for _ in range(2)]
    with Telemetry(tmp_path, flush_every=1) as tel:
        train_epoch(_fake_step, _S(), batches, max_iters=10,
                    loss_print_every=10**9, telemetry=tel)
    rows = read_jsonl(tmp_path / "metrics.jsonl")
    assert all(r["tokens_per_s"] > 0 for r in rows)


def test_train_epoch_telemetry_off_is_inert(tmp_path, monkeypatch):
    # Off (the default): no telemetry object is consulted at all — the
    # loop must never touch a Telemetry method, so patching every
    # instrument to a tripwire proves the no-op guard is a guard.
    from distributed_machine_learning_tpu.train.loop import train_epoch

    assert get_telemetry() is None
    monkeypatch.chdir(tmp_path)

    def boom(*a, **k):
        raise AssertionError("telemetry touched while off")

    monkeypatch.setattr(Telemetry, "log_step", boom)
    monkeypatch.setattr(Telemetry, "span", boom)
    state, _ = train_epoch(_fake_step, _S(), _img_batches(2),
                           max_iters=10, loss_print_every=10**9)
    assert state.step == 2
    assert os.listdir(tmp_path) == []  # and no files appeared


def test_async_checkpoint_save_records_telemetry(tmp_path):
    # --async-ckpt is the path built BECAUSE saves are slow; it must not
    # be the one path whose saves are invisible to the telemetry.
    from distributed_machine_learning_tpu.cli.common import (
        init_model_and_state,
    )
    from distributed_machine_learning_tpu.models.vgg import VGGTest
    from distributed_machine_learning_tpu.train.checkpoint import (
        AsyncCheckpointWriter,
    )

    state = init_model_and_state(VGGTest(use_bn=False))
    tel = Telemetry(tmp_path / "tel", flush_every=1)
    prev = set_telemetry(tel)
    try:
        with AsyncCheckpointWriter() as w:
            w.save(tmp_path / "ck", state)
    finally:
        set_telemetry(prev)
        tel.close()
    names = [e["name"] for e in read_trace(tmp_path / "tel" / "trace.json")]
    assert "checkpoint_save" in names
    snap = json.loads((tmp_path / "tel" / "registry.json").read_text())
    counters = {c["name"]: c["value"] for c in snap["counters"]}
    assert counters["checkpoint_saves_total"] == 1
    assert counters["checkpoint_save_bytes_total"] > 0


# ---------------------------------------------------------------------------
# MetricsLogger streaming shim (utils/profiling.py satellite)
# ---------------------------------------------------------------------------


def test_metrics_logger_streams_rows_as_they_land(tmp_path):
    from distributed_machine_learning_tpu.utils.profiling import (
        MetricsLogger,
    )

    p = tmp_path / "m.jsonl"
    m = MetricsLogger(path=p, flush_every=1)
    m.log(step=1, loss=2.5)
    # On disk BEFORE save() — the crash-loss fix.
    assert len(read_jsonl(p)) == 1
    m.log(step=2, loss=2.4)
    m.save(p)  # flush, not rewrite
    assert [r["step"] for r in read_jsonl(p)] == [1, 2]
    # Streaming mode: the DISK is the buffer — no unbounded in-memory
    # duplicate of a long run's history; `count` carries the tally.
    assert m.count == 2 and m.rows == []
    # And a save to some OTHER path has nothing buffered to write with:
    # it must refuse loudly, not silently produce an empty file.
    with pytest.raises(ValueError):
        m.save(tmp_path / "elsewhere.jsonl")


def test_metrics_logger_streaming_save_appends_not_truncates(tmp_path):
    from distributed_machine_learning_tpu.utils.profiling import (
        MetricsLogger,
    )

    p = tmp_path / "m.jsonl"
    m0 = MetricsLogger(path=p, flush_every=1)
    m0.log(step=1, attempt=0)
    m0.save(p)
    # The restarted (resumed) process's logger appends to the survivor
    # rows; append=True is what the CLI passes under --resume.
    m1 = MetricsLogger(path=p, flush_every=1, append=True)
    m1.log(step=1, attempt=1)
    m1.save(p)
    assert [r["attempt"] for r in read_jsonl(p)] == [0, 1]
    # A FRESH run (append=False, the default) truncates — two unrelated
    # runs must not silently interleave in one file.
    m2 = MetricsLogger(path=p, flush_every=1)
    m2.log(step=1, attempt=0)
    m2.save(p)
    assert len(read_jsonl(p)) == 1


def test_metrics_logger_csv_stays_buffered(tmp_path):
    from distributed_machine_learning_tpu.utils.profiling import (
        MetricsLogger,
    )

    p = tmp_path / "m.csv"
    m = MetricsLogger(path=p, flush_every=1)
    m.log(step=1, loss=1.0)
    assert not p.exists()  # CSV cannot stream (union-of-columns header)
    m.save(p)
    assert p.read_text().startswith("step,")


# ---------------------------------------------------------------------------
# get_logger satellite (utils/logging.py)
# ---------------------------------------------------------------------------


def test_get_logger_does_not_propagate_to_root(capsys):
    import logging

    from distributed_machine_learning_tpu.utils.logging import get_logger

    root_records = []
    handler = logging.Handler()
    handler.emit = lambda record: root_records.append(record)
    logging.getLogger().addHandler(handler)
    try:
        logger = get_logger("dml_tpu_prop_test")
        assert logger.propagate is False
        logger.info("hello once")
        assert root_records == []  # a configured root would double-print
    finally:
        logging.getLogger().removeHandler(handler)


def test_get_logger_is_idempotent():
    from distributed_machine_learning_tpu.utils.logging import get_logger

    a = get_logger("dml_tpu_idem")
    b = get_logger("dml_tpu_idem")
    assert a is b and len(a.handlers) == 1


# ---------------------------------------------------------------------------
# IterationTimer percentiles satellite (utils/timing.py)
# ---------------------------------------------------------------------------


def test_percentile_stats_exact():
    from distributed_machine_learning_tpu.utils.timing import (
        percentile,
        percentile_stats,
    )

    xs = [float(i) for i in range(1, 101)]
    assert percentile(xs, 0.5) == pytest.approx(50.5)
    s = percentile_stats(xs)
    assert s["p95"] == pytest.approx(95.05)
    assert s["max"] == 100.0
    assert percentile_stats([]) == {"p50": 0.0, "p95": 0.0, "p99": 0.0,
                                    "max": 0.0}
    with pytest.raises(ValueError):
        percentile(xs, 2.0)


def test_iteration_timer_summary_includes_tail():
    from distributed_machine_learning_tpu.utils.timing import IterationTimer

    t = IterationTimer(skip_first=0)
    t.times = [0.1, 0.2, 0.3, 1.0]
    p = t.percentiles()
    assert p["max"] == 1.0 and 0.1 <= p["p50"] <= 0.3
    text = t.summary()
    assert "Total execution time is" in text  # reference lines intact
    assert "p50/p95/p99/max" in text


# ---------------------------------------------------------------------------
# tools/trace_summary.py smoke (tier-1: the artifact format cannot drift)
# ---------------------------------------------------------------------------


def _make_telemetry_dir(tmp_path):
    with Telemetry(tmp_path, flush_every=1) as tel:
        tel.registry.counter("fault_events", kind="restarts").inc()
        for i in range(6):
            with tel.span("data_wait", step=i):
                pass
            with tel.span("step_dispatch", step=i):
                pass
            tel.log_step(
                i, batch=i, iter_s=0.01 * (i + 1), data_wait_s=0.001,
                place_s=0.0, dispatch_s=0.005, block_s=0.004,
                examples_per_s=100.0,
            )
    return tmp_path


def test_trace_summary_smoke(tmp_path):
    d = _make_telemetry_dir(tmp_path)
    out = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "trace_summary.py"), str(d),
         "--top", "3"],
        capture_output=True, text=True, timeout=60,
    )
    assert out.returncode == 0, out.stderr
    assert "Phase time shares" in out.stdout
    assert "data_wait" in out.stdout and "step_dispatch" in out.stdout
    assert "slowest steps" in out.stdout
    assert "6 step rows" in out.stdout
    assert "restarts" in out.stdout  # fault counter section
    # The slowest step is the last one (iter_s grows with i).
    assert "step      5" in out.stdout


def test_trace_summary_tolerates_crashed_artifacts(tmp_path):
    d = _make_telemetry_dir(tmp_path)
    # Simulate a kill mid-write on BOTH artifacts.
    with open(d / "metrics.jsonl", "a") as f:
        f.write('{"step": 99, "iter_s"')
    trace = (d / "trace.json").read_text()
    (d / "trace.json").write_text(trace.rstrip().rstrip("]").rstrip()
                                  + ',\n{"name": "torn')
    out = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "trace_summary.py"), str(d)],
        capture_output=True, text=True, timeout=60,
    )
    assert out.returncode == 0, out.stderr
    assert "6 step rows" in out.stdout  # torn row dropped, not fatal


# ---------------------------------------------------------------------------
# CLI flags + the chaos acceptance run
# ---------------------------------------------------------------------------


def test_cli_telemetry_flags_parse_and_validate():
    from distributed_machine_learning_tpu.cli.common import (
        make_flag_parser,
        parse_flags,
    )

    parser = make_flag_parser("test")
    args = parse_flags(parser, [])
    assert args.telemetry_dir is None  # off by default
    assert args.telemetry_flush_every == 20
    args = parse_flags(parser, ["--telemetry-dir", "/tmp/t",
                                "--telemetry-flush-every", "5"])
    assert args.telemetry_dir == "/tmp/t"
    assert args.telemetry_flush_every == 5
    with pytest.raises(SystemExit):
        parse_flags(parser, ["--telemetry-flush-every", "0"])


def test_lm_cli_has_telemetry_flags():
    from distributed_machine_learning_tpu.cli.lm import make_parser

    args = make_parser().parse_args([])
    assert args.telemetry_dir is None


@pytest.mark.faultinject
def test_part_cli_chaos_run_yields_one_attempt_tagged_timeline(tmp_path,
                                                               capsys):
    """The PR-2 acceptance keystone: a PR-1 chaos run with
    --telemetry-dir yields ONE metrics stream whose rows span both
    attempts (attempt-0 rows intact after the restart), a Chrome trace
    containing restart_attempt and per-step phase spans, and registry
    counters matching the run's FaultEvents totals exactly."""
    from distributed_machine_learning_tpu.cli import part1

    tel_dir = tmp_path / "tel"
    ck = tmp_path / "ck"
    part1.main([
        "--batch-size", "4", "--max-iters", "3", "--epochs", "2",
        "--model", "vggtest", "--eval-batches", "0",
        "--data-root", str(tmp_path), "--ckpt-dir", str(ck),
        "--resume", "auto", "--max-restarts", "2",
        "--guard-nonfinite", "--loader-retries", "2",
        "--faults", "kill_ckpt@1,nan@2,raise@4",
        "--telemetry-dir", str(tel_dir), "--telemetry-flush-every", "1",
    ])
    out = capsys.readouterr().out
    assert "Telemetry written to" in out
    assert get_telemetry() is None  # uninstalled after the run

    # One metrics stream spanning both attempts; the pre-restart
    # (attempt-0) rows were appended to, never truncated.
    rows = read_jsonl(tel_dir / "metrics.jsonl")
    by_attempt = {}
    for r in rows:
        by_attempt.setdefault(r["attempt"], []).append(r)
    assert set(by_attempt) == {0, 1}
    # Attempt 0: the 3 pre-kill batches; attempt 1: the replayed epoch 0
    # plus epoch 1 (the raise@4 retry consumes no extra row).
    assert len(by_attempt[0]) == 3
    assert len(by_attempt[1]) == 6

    # The trace shows the restart and the per-step phase structure.
    names = [e["name"] for e in read_trace(tel_dir / "trace.json")]
    assert names.count("restart_attempt") == 2  # failed + successful
    # No place_batch span: part1 is the single-device path (place=None);
    # the distributed parts add it (unit-covered in the loop test above).
    for phase in ("data_wait", "step_dispatch", "device_block",
                  "checkpoint_save", "eval"):
        assert phase in names, f"missing {phase} span"
    assert "fault_ckpt_kills" in names  # the fault instant marker

    # Registry counters match the run's FaultEvents totals exactly:
    # kill_ckpt@1 → 1 kill + 1 restart; nan@2 → 1 guard skip; raise@4 →
    # 1 loader retry; nothing else fired.
    snap = json.loads((tel_dir / "registry.json").read_text())
    faults = {
        c["labels"]["kind"]: c["value"]
        for c in snap["counters"] if c["name"] == "fault_events"
    }
    assert faults.get("ckpt_kills") == 1
    assert faults.get("skipped_steps") == 1
    assert faults.get("loader_retries") == 1
    assert faults.get("restarts") == 1
    assert faults.get("stalls") is None and faults.get("preemptions") is None
    counters = {
        (c["name"], c["labels"].get("kind")): c["value"]
        for c in snap["counters"]
    }
    # 3 applied + 1 skipped on attempt 0's view... the steps_total
    # counter counts loop iterations that completed: 3 + 6.
    assert counters[("steps_total", None)] == 9

    # And the stdlib summarizer digests the whole directory.
    out = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "trace_summary.py"),
         str(tel_dir)],
        capture_output=True, text=True, timeout=60,
    )
    assert out.returncode == 0, out.stderr
    assert "attempt(s) 0,1" in out.stdout
