"""From a profiler trace to device metrics — the benchmark's own reduction.

Works on plain ``Record(plane, line, name, start_ns, dur_ns)`` tuples, so it
is tested on hand-made lists; :func:`load_xplane` is the thin adapter from
``jax.profiler.ProfileData``.  Device time comes only from the lines in
``OP_LINES`` of planes named ``/device:TPU:<n>``; host spans are the
``bench.*`` ``TraceAnnotation`` events the harness writes around its calls.
"""

from __future__ import annotations

import re
from collections import defaultdict
from typing import Iterable, NamedTuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
#: Lines of a device plane whose events are operations running on the core.
OP_LINES = ("XLA Ops",)
HOST_PREFIX = "bench."
#: The harness's marker around the traced steps: the device is synced at
#: both ends, so the marker's span is the window idle time is a share of.
STRETCH = "bench.traced_stretch"
COLLECTIVE = re.compile(
    r"^%?(all-reduce|all-gather|reduce-scatter|collective-permute|"
    r"all-to-all|collective-broadcast|send|recv)(-start|-done)?\b"
)
#: A Mosaic (Pallas) kernel is a custom call to this target; the event's text
#: carries it, whatever the kernel's own name is.
PALLAS = 'custom_call_target="tpu_custom_call"'
_HLO = re.compile(r"^%?(\S+) = (.*?) ([\w\-]+)\(")


def is_collective(name: str) -> bool:
    """By the instruction's opcode where the event carries the whole
    instruction (a ``lax.pmean`` is ``%psum.492 = f32[...] all-reduce(...)``
    on the chip), else by the name itself."""
    m = _HLO.match(name)
    return bool(COLLECTIVE.match(m.group(3) if m else name))


def label(name: str) -> str:
    """A device event's text is its whole HLO instruction; keep the
    instruction's name, its opcode (``pallas`` for a Mosaic kernel) and the
    head of its result shape."""
    m = _HLO.match(name)
    if not m:
        return name[:120]
    opcode = "pallas" if PALLAS in name else m.group(3)
    return f"{m.group(1)} {opcode} {m.group(2)[:60]}"


class Record(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float


def load_xplane(path: str) -> list[Record]:
    """Every event of an ``.xplane.pb`` as a :class:`Record`."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    return [
        Record(plane.name, line.name, event.name,
               float(event.start_ns), float(event.duration_ns))
        for plane in data.planes
        for line in plane.lines
        for event in line.events
    ]


def inventory(records: Iterable[Record], names: int = 3) -> dict:
    """``{plane: {line: [count, first few distinct names]}}`` — what a
    trace holds, for reading one by hand."""
    out: dict = defaultdict(dict)
    for r in records:
        entry = out[r.plane].setdefault(r.line, [0, []])
        entry[0] += 1
        if len(entry[1]) < names and r.name not in entry[1]:
            entry[1].append(r.name[:80])
    return {plane: dict(lines) for plane, lines in out.items()}


def union(intervals: Iterable[tuple[float, float]]) -> list[tuple[float, float]]:
    """Merged, sorted ``(start, end)`` intervals; empty ones are dropped."""
    merged: list[tuple[float, float]] = []
    for start, end in sorted(i for i in intervals if i[1] > i[0]):
        if merged and start <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], end))
        else:
            merged.append((start, end))
    return merged


def total(intervals: Iterable[tuple[float, float]]) -> float:
    return sum(end - start for start, end in intervals)


def subtract(a: list[tuple[float, float]],
             b: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """The parts of merged intervals ``a`` that no interval of merged ``b``
    covers."""
    out = []
    j = 0
    for start, end in a:
        cur = start
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < end:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < end:
            out.append((cur, end))
    return out


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def device_ops(records: Iterable[Record],
               op_lines: tuple[str, ...] = OP_LINES) -> dict[int, list[Record]]:
    """``{device id: its operation events}``."""
    out: dict[int, list[Record]] = defaultdict(list)
    for r in records:
        m = DEVICE_PLANE.match(r.plane)
        if m and r.line in op_lines:
            out[int(m.group(1))].append(r)
    return dict(out)


def host_spans(records: Iterable[Record]) -> list[Record]:
    return [r for r in records
            if r.name.startswith(HOST_PREFIX)
            and not DEVICE_PLANE.match(r.plane)]


def attribute_gaps(gaps: list[tuple[float, float]],
                   spans: list[Record]) -> dict[str, float]:
    """Seconds of idle ``gaps`` by the host span that covered them; where
    several do, the one that started last (the innermost); where none does,
    ``unannotated`` (in this program: the loop's own block, its telemetry and
    bookkeeping)."""
    out: dict[str, float] = defaultdict(float)
    spans = sorted((s for s in spans if s.name != STRETCH),
                   key=lambda s: s.start_ns)
    for start, end in gaps:
        covered: list[tuple[float, float]] = []
        # innermost first: later-starting spans claim their part first
        for s in reversed(spans):
            part = clip([(s.start_ns, s.start_ns + s.dur_ns)], start, end)
            fresh = subtract(union(part), union(covered))
            if fresh:
                out[s.name] += total(fresh) / 1e9
                covered.extend(fresh)
        rest = subtract([(start, end)], union(covered))
        if rest:
            out["unannotated"] += total(rest) / 1e9
    return dict(out)


def reduce(records: list[Record], steps: int,
           op_lines: tuple[str, ...] = OP_LINES) -> dict | None:
    """The traced stretch's device metrics, or None where no operation ran
    on a device.  Times in seconds unless the key says ``_ms`` (per step) or
    ``_pct``.  Over several devices: busy and idle are means; ``device_ms``
    and ``pallas_ms`` are the busiest device's, ``exposed_collective_ms`` that
    of the device that waited longest.  ``device_ops`` sums each operation
    over the whole stretch, not a step."""
    by_device = device_ops(records, op_lines)
    if not by_device or steps <= 0:
        return None
    spans = host_spans(records)
    lo = min(r.start_ns for ops in by_device.values() for r in ops)
    hi = max(r.start_ns + r.dur_ns for ops in by_device.values() for r in ops)
    for s in spans:
        if s.name == STRETCH:
            lo, hi = min(lo, s.start_ns), max(hi, s.start_ns + s.dur_ns)
    window = hi - lo
    busy, exposed, gaps_of, pallas = {}, {}, {}, {}
    op_seconds: dict[str, float] = defaultdict(float)
    for dev, ops in by_device.items():
        all_iv = union((r.start_ns, r.start_ns + r.dur_ns) for r in ops)
        coll = union((r.start_ns, r.start_ns + r.dur_ns) for r in ops
                     if is_collective(r.name))
        other = union((r.start_ns, r.start_ns + r.dur_ns) for r in ops
                      if not is_collective(r.name))
        busy[dev] = total(all_iv)
        exposed[dev] = total(subtract(coll, other))
        gaps_of[dev] = subtract([(lo, hi)], all_iv)
        pallas[dev] = sum(r.dur_ns for r in ops if PALLAS in r.name)
        for r in ops:
            op_seconds[label(r.name)] += r.dur_ns / 1e9 / len(by_device)
    slowest = max(busy, key=busy.get)
    mean_busy = sum(busy.values()) / len(busy)
    gap_names = attribute_gaps(gaps_of[slowest], spans)
    top = lambda d: [[k, v] for k, v in
                     sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return {
        "devices": len(by_device),
        "steps": steps,
        "window_s": window / 1e9,
        "busy_s": mean_busy / 1e9,
        "idle_pct": 100.0 * (1.0 - mean_busy / window),
        "device_ms": busy[slowest] / 1e6 / steps,
        "exposed_collective_ms": max(exposed.values()) / 1e6 / steps,
        "has_collectives": any(
            is_collective(r.name) for ops in by_device.values() for r in ops),
        "pallas_ms": pallas[slowest] / 1e6 / steps,
        "has_pallas": any(pallas.values()),
        "device_ops": top(op_seconds),
        "idle_gaps": top(gap_names),
    }
