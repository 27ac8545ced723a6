"""dmlcheck layer 3 — deterministic interleaving exploration for the
gang control plane.

Layers 1 and 2 look at *programs* (AST idioms, jaxpr/HLO structure);
the properties PR 12's transport actually promises — exactly-once
ledger appends, first-writer-wins abort, admit-once joins, epoch
fencing — are *interleaving* properties, invisible to both.  This
module makes them testable deterministically:

- :class:`Scheduler` — a cooperative scheduler driven through the
  ``_sched_point`` / ``_sched_block`` seam in ``runtime/coordinator.py``
  (aliased by ``runtime/transport.py``).  Exactly one scenario thread
  runs between schedule points; every context switch is an explicit
  *choice*, so a run is fully described by its choice list.
- :func:`explore` — stateless DFS over choice prefixes: exhaustive for
  the quick configs (≤3 threads / ≤8 ops), with label-based
  partial-order pruning and a bounded-preemption filter for the larger
  ``full`` configs.
- :data:`SCENARIOS` — nine bounded gang protocols (abort race, join
  duplicate delivery, ledger append storm, dedup-cache hit racing a
  slow in-flight apply, beat publish vs batched reads, epoch fence vs
  zombie thread, serving drain/promote handoff vs a retiring
  replica's late result, weight hot-swap commit vs an old-version
  compute's late post, paged-KV admission racing decode appends and
  retirement frees), each with invariants checked after every
  terminal schedule.
- :data:`MUTATIONS` — the known-bug seeds (the pre-fix dedup eviction,
  the pre-fix epoch check outside the lock, the pre-fix serving
  result fence, the pre-fix weight-swap version fence, the pre-fix
  block-allocator capacity check outside the lock).  The
  mutation-test gate: with a seed applied, the explorer must
  rediscover the bug deterministically; on the fixed tree it must
  exit clean.
- Reproducers — a failing schedule serializes to JSON
  (:func:`save_reproducer`); ``dmlcheck --replay FILE`` re-runs that
  exact interleaving (:func:`replay_file`), so a CI failure is a
  deterministic test case, not a flake.

Determinism contract: no randomness, and no wall-clock reads in
control flow (``perf_counter`` is used only for reported durations and
the full-mode deadline; quick mode is capped by schedule COUNT only,
so two quick runs explore the identical schedule set).

Stdlib-only by construction, like the rest of layer 1's import chain.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import threading
import time

from ..runtime import coordinator as _coord
from ..runtime import transport as _transport
from ..runtime.transport import (
    InProcHub,
    InProcTransport,
    TcpGangServer,
    TransportError,
    _InFlight,
    _read_jsonl_dicts,
)
from .findings import Finding


def _load_kv_blocks():
    """The block allocator under test WITHOUT importing the
    ``inference`` package: its ``__init__`` pulls in jax, and this
    module must stay importable under ``python -S`` (the dmlcheck
    CLI).  ``kv_blocks.py`` itself is stdlib-only by construction, so
    when the canonical module is already loaded (pytest runs) the
    scenario — and the ``admit-unlocked`` seed — target the REAL
    class; otherwise the file is loaded directly, bypassing the
    package ``__init__``."""
    mod = sys.modules.get(
        "distributed_machine_learning_tpu.inference.kv_blocks")
    if mod is not None:
        return mod
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "inference", "kv_blocks.py")
    spec = importlib.util.spec_from_file_location(
        "dml_layer3_kv_blocks", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_kvb = _load_kv_blocks()

LAYER3_RULES = {"DML301", "DML302"}

_WATCHDOG_S = 20.0


class ScheduleAbort(BaseException):
    """Raised inside a scenario thread during teardown so it unwinds
    instead of running free once exploration is done with this
    schedule.  Deliberately a BaseException: scenario code that
    catches ``Exception`` (e.g. retry loops) must not swallow it."""


class DeadlockError(RuntimeError):
    """No runnable thread, at least one blocked thread: the schedule
    wedged.  Reported as DML302."""

    def __init__(self, message: str, trace):
        super().__init__(message)
        self.trace = list(trace)


class SchedulerStuckError(RuntimeError):
    """A scheduled thread failed to reach its next schedule point
    within the watchdog — a real (seam-invisible) lock cycle or an
    unbounded wait inside the scenario."""


class _ThreadState:
    __slots__ = ("name", "thread", "gate", "state", "label",
                 "predicate", "error")

    def __init__(self, name: str):
        self.name = name
        self.thread: threading.Thread | None = None
        self.gate = threading.Semaphore(0)
        self.state = "runnable"     # runnable | blocked | running | done
        self.label = "spawn"
        self.predicate = None
        self.error: BaseException | None = None


class Scheduler:
    """Cooperative scheduler: scenario threads hand control back at
    every ``_sched_point``/``_sched_block`` via a semaphore handshake;
    the scheduler picks the next thread to run by asking its chooser.

    Threads not registered via :meth:`spawn` (e.g. leftover daemon
    monitors from other tests — the seam is a process-global) pass
    through every point as a no-op and fall back to real waits in
    ``block``, so installing a scheduler never perturbs bystanders.
    """

    def __init__(self, chooser, watchdog_s: float = _WATCHDOG_S):
        self._chooser = chooser
        self._threads: list[_ThreadState] = []
        self._by_ident: dict[int, _ThreadState] = {}
        self._control = threading.Semaphore(0)
        self._ready = threading.Semaphore(0)
        self._abort = False
        self.watchdog_s = watchdog_s
        self.trace: list[tuple[str, str]] = []

    # -- called from scenario threads (via the runtime seam) -------------
    def point(self, label: str) -> None:
        ts = self._by_ident.get(threading.get_ident())
        if ts is None:
            return
        if self._abort:
            raise ScheduleAbort()
        ts.label = label
        ts.state = "runnable"
        self._control.release()
        ts.gate.acquire()
        if self._abort:
            raise ScheduleAbort()

    def block(self, label: str, predicate) -> bool:
        """Deschedule the calling thread until ``predicate()`` is true
        (evaluated by the scheduler between steps).  Returns False for
        unregistered threads — the caller then falls back to its real
        blocking wait."""
        ts = self._by_ident.get(threading.get_ident())
        if ts is None:
            return False
        if self._abort:
            raise ScheduleAbort()
        ts.label = label
        ts.predicate = predicate
        ts.state = "blocked"
        self._control.release()
        ts.gate.acquire()
        ts.predicate = None
        if self._abort:
            raise ScheduleAbort()
        return True

    # -- driver ----------------------------------------------------------
    def spawn(self, name: str, fn) -> None:
        ts = _ThreadState(name)
        self._threads.append(ts)

        def body():
            self._by_ident[threading.get_ident()] = ts
            self._ready.release()
            ts.gate.acquire()
            try:
                if not self._abort:
                    fn()
            except ScheduleAbort:
                pass
            except BaseException as exc:
                ts.error = exc
            ts.state = "done"
            self._control.release()

        ts.thread = threading.Thread(
            target=body, name=f"l3-{name}", daemon=True)
        ts.thread.start()
        if not self._ready.acquire(timeout=self.watchdog_s):
            raise SchedulerStuckError(
                f"thread {name} never registered")

    def run(self) -> None:
        while True:
            for ts in self._threads:
                if (ts.state == "blocked" and ts.predicate is not None
                        and ts.predicate()):
                    ts.state = "runnable"
            runnable = [t for t in self._threads
                        if t.state == "runnable"]
            if not runnable:
                blocked = [t for t in self._threads
                           if t.state == "blocked"]
                if blocked:
                    raise DeadlockError(
                        "deadlock: no runnable thread; blocked: "
                        + ", ".join(f"{t.name}@{t.label}"
                                    for t in blocked),
                        self.trace)
                return
            options = [(t.name, t.label) for t in runnable]
            idx = self._chooser.choose(options)
            ts = runnable[idx]
            self.trace.append((ts.name, ts.label))
            ts.state = "running"
            ts.gate.release()
            if not self._control.acquire(timeout=self.watchdog_s):
                self._abort = True
                raise SchedulerStuckError(
                    f"watchdog: thread {ts.name} did not reach its "
                    f"next schedule point within {self.watchdog_s}s")

    def teardown(self) -> None:
        self._abort = True
        for ts in self._threads:
            if ts.state != "done":
                ts.gate.release()
        for ts in self._threads:
            if ts.thread is not None:
                ts.thread.join(timeout=5.0)


class _Chooser:
    """Replays a choice prefix, then always picks index 0 (the first
    runnable in registration order).  Records every decision and the
    options it saw, so the explorer can branch on the alternatives."""

    def __init__(self, prefix=()):
        self.prefix = list(prefix)
        self.choices: list[int] = []
        self.options: list[list[tuple[str, str]]] = []

    def choose(self, options) -> int:
        i = len(self.choices)
        pick = self.prefix[i] if i < len(self.prefix) else 0
        if pick >= len(options):
            # A stale prefix (e.g. a reproducer replayed against an
            # edited scenario) must not crash the scheduler: fall back
            # to the default and let the invariants speak.
            pick = 0
        self.choices.append(pick)
        self.options.append(list(options))
        return pick


class _ScheduleResult:
    __slots__ = ("choices", "options", "trace", "violations", "deadlock")

    def __init__(self, choices, options, trace, violations, deadlock):
        self.choices = list(choices)
        self.options = list(options)
        self.trace = list(trace)
        self.violations = list(violations)
        self.deadlock = deadlock


class _Scenario:
    """One bounded protocol instance: named thread bodies, an
    invariant check over the terminal state, and a cleanup hook."""

    def __init__(self, threads, check, cleanup=None):
        self.threads = list(threads)   # [(name, fn), ...]
        self._check = check
        self._cleanup = cleanup

    def check(self) -> list[str]:
        return list(self._check())

    def cleanup(self) -> None:
        if self._cleanup is not None:
            self._cleanup()


def _run_schedule(build, prefix=(),
                  watchdog_s: float = _WATCHDOG_S) -> _ScheduleResult:
    """Run ONE schedule of ``build()`` under the controllable
    scheduler, replaying ``prefix`` then defaulting.  Always uninstalls
    the scheduler and tears the threads down, even on invariant
    failure."""
    inst = build()
    chooser = _Chooser(prefix)
    sched = Scheduler(chooser, watchdog_s)
    violations: list[str] = []
    deadlock = False
    _coord.install_scheduler(sched)
    try:
        try:
            for name, fn in inst.threads:
                sched.spawn(name, fn)
            sched.run()
        except DeadlockError as e:
            deadlock = True
            violations.append(str(e))
        except SchedulerStuckError as e:
            violations.append(f"scheduler stuck: {e}")
        for ts in sched._threads:
            if ts.error is not None:
                violations.append(
                    f"thread {ts.name} raised "
                    f"{type(ts.error).__name__}: {ts.error}")
        if not violations:
            violations.extend(inst.check())
    finally:
        try:
            sched.teardown()
        finally:
            _coord.uninstall_scheduler()
            inst.cleanup()
    return _ScheduleResult(chooser.choices, chooser.options,
                           sched.trace, violations, deadlock)


# ---------------------------------------------------------------------------
# Exploration — stateless DFS over choice prefixes
# ---------------------------------------------------------------------------


def _independent(label_a: str, label_b: str) -> bool:
    """Label-level independence for the POR pruning (full mode only;
    quick mode is exhaustive and never consults this).  Labels are
    structured ``family:channel:mode`` — different channels commute,
    two reads commute, everything touching ``clear`` (the epoch fence)
    or with an unstructured/blocking mode conflicts conservatively."""
    pa, pb = label_a.split(":"), label_b.split(":")
    if len(pa) < 3 or len(pb) < 3:
        return False
    if "clear" in (pa[1], pb[1]):
        return False
    if pa[2] not in ("r", "w") or pb[2] not in ("r", "w"):
        return False
    if pa[0] != pb[0] or pa[1] != pb[1]:
        return True
    return pa[2] == "r" and pb[2] == "r"


def _count_preemptions(options, choices) -> int:
    """A preemption = switching away from a thread that could have
    kept running (its name still among the options)."""
    count = 0
    prev = None
    for opts, ch in zip(options, choices):
        name = opts[ch][0]
        if (prev is not None and name != prev
                and any(n == prev for n, _ in opts)):
            count += 1
        prev = name
    return count


class ExploreStats:
    __slots__ = ("schedules", "capped", "violation", "seconds")

    def __init__(self):
        self.schedules = 0
        self.capped = False
        self.violation: _ScheduleResult | None = None
        self.seconds = 0.0


@contextlib.contextmanager
def _on_one_cpu():
    """Hold the calling thread — and the scenario threads it spawns,
    which inherit its mask — to ONE of its CPUs (as a decorator: for
    the call).

    The cooperative scheduler runs exactly one thread at a time, so
    more cores buy nothing, while every hand-off between threads the
    OS has spread over cores pays a cross-core wake-up (an IPI and an
    idle core's exit latency; 40% of an unpinned sweep is system
    time).  Which CPU is irrelevant to the schedule set; taking it
    from the pid spreads concurrent sweeps over the allowed ones."""
    if not hasattr(os, "sched_setaffinity"):   # not Linux: leave it
        yield
        return
    allowed = os.sched_getaffinity(0)
    os.sched_setaffinity(0, {sorted(allowed)[os.getpid() % len(allowed)]})
    try:
        yield
    finally:
        os.sched_setaffinity(0, allowed)


@_on_one_cpu()
def explore(build, max_schedules: int = 2000,
            stop_on_violation: bool = True,
            preemption_bound: int | None = None,
            por: bool = False,
            deadline_s: float | None = None) -> ExploreStats:
    """Systematically explore the schedule space of ``build()``.

    Stateless DFS: each stack entry is a choice prefix; running it
    replays the prefix then takes defaults, and every not-taken
    alternative at a position past the prefix becomes a new entry.
    With no ``preemption_bound``/``por``/``deadline_s`` (quick mode)
    the search is EXHAUSTIVE up to ``max_schedules`` and fully
    deterministic — same build, same schedule sequence, every run.
    """
    stats = ExploreStats()
    t0 = time.perf_counter()
    stack: list[tuple[int, ...]] = [()]
    while stack:
        if stats.schedules >= max_schedules:
            stats.capped = True
            break
        if (deadline_s is not None
                and time.perf_counter() - t0 > deadline_s):
            stats.capped = True
            break
        prefix = stack.pop()
        res = _run_schedule(build, prefix)
        stats.schedules += 1
        if res.violations:
            stats.violation = res
            if stop_on_violation:
                break
        for i in range(len(prefix), len(res.choices)):
            opts = res.options[i]
            for alt in range(1, len(opts)):
                if por and _independent(opts[0][1], opts[alt][1]):
                    continue
                cand = tuple(res.choices[:i]) + (alt,)
                if (preemption_bound is not None
                        and _count_preemptions(
                            res.options[:i + 1], list(cand))
                        > preemption_bound):
                    continue
                stack.append(cand)
    stats.seconds = time.perf_counter() - t0
    return stats


# ---------------------------------------------------------------------------
# Eviction spy — separates the BUG from capped-dedup physics
# ---------------------------------------------------------------------------


def _spy_evictions(srv: TcpGangServer) -> dict:
    """Wrap ``srv._evict_seen_locked`` (instance attribute shadowing
    the class method — so a MUTATIONS patch of the class still takes
    effect underneath) and record which op_ids each eviction dropped,
    split by whether the entry was still ``_InFlight``.

    This is what keeps the invariants honest at tiny ``_DEDUP_CAP``:
    evicting a SETTLED result early is legitimate capped-dedup
    behavior (the retry then re-applies — with the production cap of
    65536 that window is unreachable), while evicting an IN-FLIGHT
    reservation is exactly the PR-12 bug.  Scenarios assert
    ``spy['inflight'] == []`` unconditionally and excuse
    exactly-once row counts only for ops in ``spy['settled']``."""
    log = {"inflight": [], "settled": []}

    def spy():
        before = dict(srv._seen)
        type(srv)._evict_seen_locked(srv)
        for op_id, entry in before.items():
            if op_id not in srv._seen:
                kind = ("inflight" if isinstance(entry, _InFlight)
                        else "settled")
                log[kind].append(op_id)

    srv._evict_seen_locked = spy
    return log


# ---------------------------------------------------------------------------
# Scenarios
# ---------------------------------------------------------------------------


def _server(cap: int) -> TcpGangServer:
    srv = TcpGangServer(listen=False)
    srv._DEDUP_CAP = cap   # instance attr shadows the class's 65536
    return srv


def _build_abort_race() -> _Scenario:
    """Two ranks declare abort concurrently, each delivery duplicated
    (retry with the same op_id).  Invariants: every declarer sees ONE
    stable verdict across its deliveries, exactly one wins, and the
    latched abort matches the winner."""
    srv = _server(cap=8)
    results: dict[int, list] = {}

    def declarer(i: int):
        def run():
            req = {"op": "declare_abort", "op_id": f"ab{i}",
                   "reason": f"r{i}", "by_rank": i}
            out = []
            for _ in range(2):
                out.append(srv.dispatch(dict(req)))
            results[i] = out
        return run

    def check():
        v = []
        winners = []
        for i in sorted(results):
            out = results[i]
            if len({bool(x) for x in out}) > 1:
                v.append(f"declarer {i} saw an unstable verdict "
                         f"across duplicate deliveries: {out}")
            if out and out[0]:
                winners.append(i)
        if len(winners) != 1:
            v.append(f"abort latched by {winners or 'nobody'} "
                     "(want exactly one winner)")
        ab = srv.hub.abort
        if ab is None:
            v.append("no abort recorded after two declares")
        elif len(winners) == 1 and ab.get("by_rank") != winners[0]:
            v.append(f"latched abort credits rank {ab.get('by_rank')} "
                     f"but the stable winner is {winners[0]}")
        return v

    return _Scenario([("declare0", declarer(0)),
                      ("declare1", declarer(1))], check)


def _build_join_dup() -> _Scenario:
    """A join announce races its admit (consume+consumed-append),
    with the admit delivered twice — at ``_DEDUP_CAP=1`` so the store
    churns.  Invariant: the admit is applied exactly once (one
    consumed row) unless its settled result was legitimately evicted;
    an in-flight reservation is NEVER evicted."""
    srv = _server(cap=1)
    spy = _spy_evictions(srv)

    def announcer():
        srv.dispatch({"op": "announce_join", "op_id": "an1",
                      "rank": 7, "payload": {"host": "h7"}})

    def admit():
        srv.dispatch({"op": "append_consumed", "op_id": "ac1",
                      "rank": 7, "payload": {"admit": 1}})

    def check():
        v = []
        if spy["inflight"]:
            v.append("dedup eviction dropped in-flight reservation(s) "
                     f"{spy['inflight']} — their retries will "
                     "re-apply")
        rows = len(srv.hub.consumed.get(7, ()))
        if rows != 1 and "ac1" not in spy["settled"]:
            v.append(f"join admitted {rows} times (want exactly once; "
                     "no settled-result eviction to excuse it)")
        if srv.hub.joins.get(7) is None:
            v.append("join announcement lost")
        return v

    return _Scenario([("announce", announcer), ("admit", admit),
                      ("admit-dup", admit)], check)


def _build_ledger_storm(appends_per_writer: int = 2) -> _Scenario:
    """Two writers appending to the health ledger (mirrored to disk),
    the first append of writer 0 duplicated.  ``_DEDUP_CAP=8`` exceeds
    the distinct op count, so NO eviction can occur and the strict
    checks are sound: every append applied exactly once, per-writer
    order preserved, and the on-disk mirror byte-for-byte
    order-consistent with the hub ledger."""
    tmp = tempfile.mkdtemp(prefix="l3-ledger-")
    srv = TcpGangServer(listen=False, mirror_dir=tmp)
    srv._DEDUP_CAP = 8

    def writer(i: int):
        def run():
            for j in range(appends_per_writer):
                req = {"op": "append_health",
                       "op_id": f"w{i}n{j}",
                       "payload": {"w": i, "n": j}}
                srv.dispatch(dict(req))
                if i == 0 and j == 0:
                    srv.dispatch(dict(req))   # duplicated delivery
        return run

    def check():
        v = []
        rows = [(e["w"], e["n"]) for e in srv.hub.health]
        want = {(i, j) for i in range(2)
                for j in range(appends_per_writer)}
        for key in sorted(want):
            n = rows.count(key)
            if n != 1:
                v.append(f"append {key} applied {n} times "
                         "(want exactly once)")
        for i in range(2):
            mine = [n for (w, n) in rows if w == i]
            if mine != sorted(mine):
                v.append(f"writer {i}'s appends reordered: {mine}")
        mirror = [(e["w"], e["n"]) for e in _read_jsonl_dicts(
            os.path.join(tmp, _coord.GANG_HEALTH_FILE))]
        if mirror != rows:
            v.append(f"mirror order diverged from hub ledger: "
                     f"mirror={mirror} hub={rows}")
        return v

    return _Scenario(
        [("writer0", writer(0)), ("writer1", writer(1))], check,
        cleanup=lambda: shutil.rmtree(tmp, ignore_errors=True))


def _build_dedup_inflight() -> _Scenario:
    """THE dedup-eviction gate: an append's retry races the original's
    slow apply while a third op churns the dedup store at
    ``_DEDUP_CAP=1``.  Fixed tree: eviction skips the in-flight
    reservation, the retry waits on it, exactly-once holds (modulo a
    legitimately evicted SETTLED result, which the spy excuses).
    With ``MUTATIONS['dedup-evict']`` the naive popitem loop evicts
    the reservation and the retry re-applies."""
    srv = _server(cap=1)
    spy = _spy_evictions(srv)
    append_v1 = {"op": "append_health", "op_id": "v1",
                 "payload": {"k": "v1"}}

    def orig():
        srv.dispatch(dict(append_v1))

    def retry():
        srv.dispatch(dict(append_v1))

    def evictor():
        srv.dispatch({"op": "append_health", "op_id": "e1",
                      "payload": {"k": "e1"}})

    def check():
        v = []
        if spy["inflight"]:
            v.append("dedup eviction dropped in-flight reservation(s) "
                     f"{spy['inflight']} — exactly-once broken for "
                     "their retries")
        rows = [e["k"] for e in srv.hub.health].count("v1")
        if rows != 1 and "v1" not in spy["settled"]:
            v.append(f"append v1 applied {rows} times (want exactly "
                     "once; no settled-result eviction to excuse it)")
        return v

    return _Scenario([("orig", orig), ("retry", retry),
                      ("evictor", evictor)], check)


def _build_beat_read_race() -> _Scenario:
    """Beat publishes and health appends race a batched reader.
    Invariants: the reader's snapshot health is a prefix of the final
    ledger (prefix-closed reads), beat versions it observes never
    regress, and the terminal beat is the last publish."""
    hub = InProcHub()
    pub_t = InProcTransport(hub)
    app_t = InProcTransport(hub)
    read_t = InProcTransport(hub)
    seen: dict = {}

    def publisher():
        for k in (1, 2):
            pub_t.publish_beat(0, {"step": k})

    def appender():
        for j in (1, 2):
            app_t.append_health_event("mark", n=j)

    def reader():
        first = read_t.read_beats()
        snap = read_t.snapshot()
        second = read_t.read_beats()
        seen["first"] = first
        seen["snap"] = snap
        seen["second"] = second

    def check():
        v = []
        final_health = [e.get("n") for e in hub.health]
        snap_health = [e.get("n")
                       for e in seen["snap"]["health"]]
        if final_health[:len(snap_health)] != snap_health:
            v.append(f"snapshot health {snap_health} is not a prefix "
                     f"of the final ledger {final_health}")
        v0 = seen["first"].get(0, (0, None))[0]
        v1 = seen["second"].get(0, (0, None))[0]
        if v1 < v0:
            v.append(f"beat version regressed across reads: "
                     f"{v0} -> {v1}")
        final = hub.beats.get(0)
        if final is None or final[1] != {"step": 2}:
            v.append(f"terminal beat is not the last publish: {final}")
        return v

    return _Scenario([("publisher", publisher),
                      ("appender", appender),
                      ("reader", reader)], check)


def _build_epoch_fence() -> _Scenario:
    """A zombie thread from a drained attempt (epoch-bound transport)
    races the supervisor's clear + first write of the next attempt.
    Invariant: the zombie NEVER lands a row in the post-clear ledger —
    it either wrote before the clear (wiped) or got the
    TransportError fence.  ``MUTATIONS['epoch-unlocked']`` reopens
    the check-then-act window layer 3 must catch."""
    hub = InProcHub()
    zombie_t = InProcTransport(hub, bind_epoch=True)
    super_t = InProcTransport(hub)
    outcome: dict = {}

    def zombie():
        try:
            zombie_t.append_health_event("beat", zombie=True)
            outcome["zombie"] = "wrote"
        except TransportError:
            outcome["zombie"] = "fenced"

    def supervisor():
        hub.clear(restore_records=True, fault_ledger=True)
        super_t.append_health_event("init", post=True)

    def check():
        v = []
        # Strip the wall timestamps the coordinator stamps into health
        # rows: violation MESSAGES must be replay-stable byte for byte.
        rows = [{k: x for k, x in e.items() if k != "time"}
                for e in hub.health]
        if any(e.get("zombie") for e in rows):
            v.append("drained epoch's thread mutated hub state after "
                     f"the clear: post-clear ledger {rows}")
        if not any(e.get("post") for e in rows):
            v.append(f"next attempt's init write lost: {rows}")
        return v

    return _Scenario([("zombie", zombie),
                      ("supervisor", supervisor)], check)


def _build_drain_promote() -> _Scenario:
    """The serving drain/promote handoff (ISSUE 16): replica 7 holds
    request "x" in flight while the router retires it (the epoch-fence
    bump) and promotes spare 9 in its place, re-dispatching "x" to the
    survivor if 7's result never arrived.  Invariants: "x" is
    delivered exactly once through the router's first-result-wins
    collection, and a post from the RETIRED epoch never lands in the
    results channel after the handoff — the atomic check-and-append
    that ``MUTATIONS['result-unfenced']`` breaks open.
    """
    hub = InProcHub()
    router_t = InProcTransport(hub)
    zombie_t = InProcTransport(hub)
    spare_t = InProcTransport(hub)
    # Pre-schedule setup: 7 is live, "x" dispatched and taken (in
    # flight on the soon-to-be-drained replica).
    router_t.set_serving_role(7, "live")
    e0 = router_t.read_serving(7)["epoch"]
    router_t.push_request(7, {"rid": "x", "epoch": e0})
    assert zombie_t.take_requests(7, 1), "setup: take must claim x"
    delivered: list = []
    seen_rids: set = set()
    outcome: dict = {}

    def collect():
        for res in router_t.take_results(8):
            if res.get("rid") in seen_rids:
                outcome["duplicates"] = outcome.get("duplicates", 0) + 1
                continue
            seen_rids.add(res.get("rid"))
            delivered.append(res)

    def zombie():
        # The draining replica's late post, racing its own demotion.
        ok = zombie_t.post_result(7, e0, {"rid": "x", "who": "zombie"})
        outcome["zombie"] = "delivered" if ok else "fenced"

    def router():
        collect()
        router_t.retire_replica(7)     # the epoch-fenced handoff
        router_t.set_serving_role(9, "live")
        if not any(r.get("rid") == "x" for r in delivered):
            # 7 never answered: re-dispatch to the promoted spare.
            e9 = router_t.read_serving(9)["epoch"]
            router_t.push_request(9, {"rid": "x", "epoch": e9})
            for req in spare_t.take_requests(9, 1):
                spare_t.post_result(9, e9, {"rid": req.get("rid"),
                                            "who": "spare"})
        collect()

    def check():
        v = []
        xs = [r.get("who") for r in delivered if r.get("rid") == "x"]
        if len(xs) != 1:
            v.append(f"request x delivered {len(xs)} time(s) by {xs} "
                     "(want exactly once)")
        leftover = [{k: x for k, x in r.items() if k != "time"}
                    for r in hub.serving_results
                    if r.get("rid") == "x"]
        if leftover:
            v.append("retired replica's late result landed in the "
                     "results channel AFTER the drain/promote handoff "
                     f"(epoch fence broken): {leftover}")
        return v

    return _Scenario([("zombie", zombie), ("router", router)], check)


def _build_weight_swap() -> _Scenario:
    """The continuous-deployment hot-swap (ISSUE 18): replica 7 serves
    weights v1 with request "x" in flight while the deploy controller
    stages v2 and the swap commits (the worker's drain-then-commit
    edge).  Invariants: "x" completes exactly once — either the
    old-version compute's post landed BEFORE the commit (the graceful
    drain) or it is fenced and the post-swap compute answers — and a
    post from the OLD weights version never lands in the results
    channel after the swap committed.  The atomic
    version-check-and-append that ``MUTATIONS['swap-unfenced']``
    breaks open.
    """
    hub = InProcHub()
    deploy_t = InProcTransport(hub)
    zombie_t = InProcTransport(hub)
    fresh_t = InProcTransport(hub)
    # Pre-schedule setup: 7 is live on committed weights v1, "x"
    # dispatched and taken (in flight on the old-version compute).
    deploy_t.set_serving_role(7, "live")
    deploy_t.set_weights(7, 1, {"step": 100})
    deploy_t.commit_weights(7, 1)
    e0 = deploy_t.read_serving(7)["epoch"]
    deploy_t.push_request(7, {"rid": "x", "epoch": e0})
    assert zombie_t.take_requests(7, 1), "setup: take must claim x"
    delivered: list = []
    seen_rids: set = set()
    outcome: dict = {}

    def collect():
        for res in deploy_t.take_results(8):
            if res.get("rid") in seen_rids:
                outcome["duplicates"] = outcome.get("duplicates", 0) + 1
                continue
            seen_rids.add(res.get("rid"))
            delivered.append(res)

    def zombie():
        # The old-version compute's post, racing the swap commit.
        ok = zombie_t.post_result(7, e0, {"rid": "x", "who": "v1"},
                                  version=1)
        outcome["zombie"] = "delivered" if ok else "fenced"

    def deployer():
        # Stage v2, commit the swap, then redispatch "x" to the
        # post-swap compute if the old-version result never arrived —
        # the controller's zero-dropped-requests obligation.
        deploy_t.set_weights(7, 2, {"step": 200})
        deploy_t.commit_weights(7, 2)
        collect()
        if not any(r.get("rid") == "x" for r in delivered):
            deploy_t.push_request(7, {"rid": "x", "epoch": e0})
            for req in fresh_t.take_requests(7, 1):
                fresh_t.post_result(7, e0, {"rid": req.get("rid"),
                                            "who": "v2"}, version=2)
        collect()

    def check():
        v = []
        leftover = [{k: x for k, x in r.items() if k != "time"}
                    for r in hub.serving_results
                    if r.get("rid") == "x"]
        whos = [r.get("who") for r in delivered if r.get("rid") == "x"]
        n = len(whos) + len(leftover) + outcome.get("duplicates", 0)
        if n != 1:
            v.append(
                f"request x completed {n} time(s) (delivered by "
                f"{whos}, {outcome.get('duplicates', 0)} duplicate(s),"
                f" leftover {leftover}) — an old-version post landed "
                "after the swap committed (want exactly once)")
        return v

    return _Scenario([("zombie", zombie), ("deployer", deployer)],
                     check)


def _build_continuous_batching() -> _Scenario:
    """The paged-KV admission race (ISSUE 19): the router thread
    admits sequences into the block pool while the engine thread
    appends decode tokens and retires finished lanes.  Pool of 3
    blocks (block_size 2); lane "c" is live holding one block;
    admitters "a" and "b" each pledge 2 blocks — either alone fits
    the 2-block headroom, both together overcommit it.  Invariants:
    the allocator's accounting identities hold at every admit edge
    and terminally (pledged never exceeds free — the reserve-on-admit
    guarantee), every admitted sequence decodes its full budget at
    contiguous slots, and every block returns to the pool.
    ``MUTATIONS['admit-unlocked']`` hoists the capacity check out of
    the critical section: two admitters park in the TOCTOU window,
    both pass against the same headroom, and the pool overcommits.
    """
    alloc = _kvb.BlockAllocator(num_blocks=3, block_size=2)
    alloc.admit("c", prompt_len=2, max_new=0)   # a live decode lane
    outcome: dict = {}

    def admitter(seq):
        def run():
            try:
                alloc.admit(seq, prompt_len=2, max_new=2)
            except _kvb.CacheExhausted:
                outcome[seq] = "exhausted"
                return
            alloc.check_invariants()   # the admit edge must be sane
            slots = [alloc.append(seq) for _ in range(2)]
            alloc.free(seq)
            outcome[seq] = slots
        return run

    def retire_c():
        # Free-on-finish returning "c"'s block while admissions race.
        alloc.free("c")

    def check():
        v = []
        try:
            alloc.check_invariants()
        except AssertionError as e:
            v.append(f"allocator invariant broken: {e}")
        st = alloc.stats()
        if st["sequences"] or st["free"] != alloc.num_blocks:
            v.append("blocks leaked past retirement: "
                     f"{st['free']}/{alloc.num_blocks} free, "
                     f"{st['sequences']} live sequence(s)")
        admitted = [s for s in ("a", "b")
                    if isinstance(outcome.get(s), list)]
        if not admitted:
            v.append("admission control starved both admitters of a "
                     f"2-block headroom: {outcome}")
        for s in admitted:
            if outcome[s] != [2, 3]:
                v.append(f"sequence {s} decoded slots {outcome[s]} "
                         "(want contiguous [2, 3] — the "
                         "reserve-on-admit guarantee)")
        return v

    return _Scenario([("admit-a", admitter("a")),
                      ("admit-b", admitter("b")),
                      ("retire-c", retire_c)], check)


# name -> {"quick": build, "full": build, "quick_max": int,
#          "full_max": int, "invariant": str}
SCENARIOS = {
    "abort_race": {
        "quick": _build_abort_race,
        "full": _build_abort_race,
        "quick_max": 2000, "full_max": 20000,
        "invariant": "abort latched exactly once with a stable "
                     "verdict under duplicate delivery",
    },
    "join_dup": {
        "quick": _build_join_dup,
        "full": _build_join_dup,
        "quick_max": 12000, "full_max": 60000,
        "invariant": "a join is never admitted twice (duplicate "
                     "admit delivery, dedup store at cap)",
    },
    "ledger_storm": {
        "quick": _build_ledger_storm,
        "full": lambda: _build_ledger_storm(appends_per_writer=3),
        "quick_max": 400, "full_max": 20000,
        "invariant": "every ledger append applied exactly once and "
                     "order-consistent with the on-disk mirror",
    },
    "dedup_inflight": {
        "quick": _build_dedup_inflight,
        "full": _build_dedup_inflight,
        "quick_max": 12000, "full_max": 60000,
        "invariant": "dedup eviction never drops an in-flight "
                     "reservation (retry must wait, not re-apply)",
    },
    "beat_read_race": {
        "quick": _build_beat_read_race,
        "full": _build_beat_read_race,
        "quick_max": 6000, "full_max": 30000,
        "invariant": "snapshot() sees a prefix-closed ledger and "
                     "non-regressing beat versions",
    },
    "epoch_fence": {
        "quick": _build_epoch_fence,
        "full": _build_epoch_fence,
        "quick_max": 500, "full_max": 5000,
        "invariant": "a drained epoch's thread never mutates hub "
                     "state past the clear",
    },
    "drain_promote": {
        "quick": _build_drain_promote,
        "full": _build_drain_promote,
        "quick_max": 3000, "full_max": 20000,
        "invariant": "a retired replica's late result is fenced and "
                     "every request delivers exactly once across the "
                     "drain/promote handoff",
    },
    "weight_swap": {
        "quick": _build_weight_swap,
        "full": _build_weight_swap,
        "quick_max": 4000, "full_max": 20000,
        "invariant": "an old-version compute's late post is fenced "
                     "at the swap commit and every request delivers "
                     "exactly once across the weight hot-swap",
    },
    "continuous_batching": {
        "quick": _build_continuous_batching,
        "full": _build_continuous_batching,
        "quick_max": 6000, "full_max": 30000,
        "invariant": "paged-KV admission check-and-bind is one "
                     "critical section: the pool never overcommits "
                     "and every admitted sequence decodes within its "
                     "reservation",
    },
}


# ---------------------------------------------------------------------------
# Mutation seeds — the known bugs the explorer must rediscover
# ---------------------------------------------------------------------------


def _evict_seen_naive(self) -> None:
    # The pre-fix TcpGangServer eviction: blind to _InFlight.
    while len(self._seen) > self._DEDUP_CAP:
        self._seen.popitem(last=False)


@contextlib.contextmanager
def _locked_epoch_unlocked(self, label: str):
    # The pre-fix InProcTransport fence: epoch checked BEFORE the
    # lock, with an explicit schedule point in the TOCTOU window so
    # the explorer can park the zombie inside it.
    _transport._sched_point(label)
    hub = self.hub
    if self._epoch is not None and self._epoch != hub.epoch:
        raise TransportError(
            f"stale transport handle (epoch {self._epoch}, hub at "
            f"{hub.epoch})")
    _transport._sched_point("hub:epoch:gap")
    with hub.lock:
        yield hub


def _post_result_unfenced(self, replica, epoch, payload, version=None):
    # The pre-fix serving fence: the poster's epoch checked BEFORE
    # the lock that appends the result, with an explicit schedule
    # point in the TOCTOU window — a retiring replica can pass the
    # stale check, park in the gap through retire_replica's epoch
    # bump, and land its zombie result after the handoff.  (The
    # weights-version fence stays correct — inside the lock — so this
    # seed breaks exactly the epoch invariant, nothing else.)
    _transport._sched_point("hub:sresults:w")
    hub = self.hub
    if int(epoch) != hub.serving_epoch.get(int(replica), 0):
        return False
    _transport._sched_point("hub:sepoch:gap")
    with hub.lock:
        if version is not None:
            wrec = hub.serving_weights.get(int(replica)) or {}
            if int(version) != int(wrec.get("version", 0)):
                return False
            payload = dict(payload, version=int(version))
        hub.serving_results.append(
            dict(payload, replica=int(replica), epoch=int(epoch)))
    return True


def _post_result_swap_unfenced(self, replica, epoch, payload,
                               version=None):
    # The pre-fix weight-swap fence: the poster's weights VERSION
    # checked BEFORE the lock that appends the result, with an
    # explicit schedule point in the TOCTOU window — an old-version
    # compute can pass the stale check, park in the gap through
    # commit_weights' version flip, and land its result after the
    # swap committed.  (The epoch fence stays correct — inside the
    # lock — so this seed breaks exactly the swap invariant.)
    _transport._sched_point("hub:sresults:w")
    hub = self.hub
    if version is not None:
        wrec = hub.serving_weights.get(int(replica)) or {}
        if int(version) != int(wrec.get("version", 0)):
            return False
    _transport._sched_point("hub:swv:gap")
    with hub.lock:
        if int(epoch) != hub.serving_epoch.get(int(replica), 0):
            return False
        if version is not None:
            payload = dict(payload, version=int(version))
        hub.serving_results.append(
            dict(payload, replica=int(replica), epoch=int(epoch)))
    return True


def _admit_unlocked(self, seq, prompt_len: int, max_new: int):
    # The pre-fix BlockAllocator.admit: the capacity check reads the
    # headroom OUTSIDE the critical section that binds the blocks,
    # with an explicit schedule point in the TOCTOU window — two
    # admitters park in the gap, both pass against the same headroom,
    # and the pool overcommits (pledged > free), breaking the
    # reserve-on-admit guarantee as an empty-pool pop mid-decode.
    if prompt_len < 1:
        raise ValueError(f"prompt_len must be >= 1, got {prompt_len}")
    if max_new < 0:
        raise ValueError(f"max_new must be >= 0, got {max_new}")
    _coord._sched_point("kvb:admit")
    with self._lock:
        if seq in self._tables:
            raise ValueError(f"sequence {seq!r} already admitted")
        avail = len(self._free) - self._pledged
    need = _kvb.blocks_needed(prompt_len + max_new, self.block_size)
    if need > avail:
        raise _kvb.CacheExhausted(
            f"need {need} blocks, {avail} available")
    _coord._sched_point("kvb:admit:gap")
    with self._lock:
        now = _kvb.blocks_needed(prompt_len, self.block_size)
        table = [self._free.pop() for _ in range(now)]
        self._tables[seq] = table
        self._lengths[seq] = prompt_len
        self._reserved[seq] = need
        self._pledged += need - now
        return list(table)


# name -> (class, attr, broken replacement)
MUTATIONS = {
    "dedup-evict": (TcpGangServer, "_evict_seen_locked",
                    _evict_seen_naive),
    "epoch-unlocked": (InProcTransport, "_locked",
                       _locked_epoch_unlocked),
    "result-unfenced": (InProcTransport, "_do_post_result",
                        _post_result_unfenced),
    "swap-unfenced": (InProcTransport, "_do_post_result",
                      _post_result_swap_unfenced),
    "admit-unlocked": (_kvb.BlockAllocator, "admit", _admit_unlocked),
}


@contextlib.contextmanager
def apply_mutations(names):
    """Temporarily re-introduce known bugs (class-level monkeypatch),
    restoring the fixed methods on exit — the mutation-test gate's
    switch."""
    saved = []
    try:
        for name in names:
            if name not in MUTATIONS:
                raise ValueError(
                    f"unknown mutation {name!r} (have: "
                    f"{sorted(MUTATIONS)})")
            cls, attr, repl = MUTATIONS[name]
            saved.append((cls, attr, cls.__dict__[attr]))
            setattr(cls, attr, repl)
        yield
    finally:
        for cls, attr, orig in reversed(saved):
            setattr(cls, attr, orig)


# ---------------------------------------------------------------------------
# Minimization + reproducers
# ---------------------------------------------------------------------------


def _minimize(build, choices, budget: int = 60) -> list[int]:
    """Greedy schedule shrink: find the shortest failing choice
    prefix, then zero out individual non-default choices.  Every
    candidate is re-run; only still-failing candidates are kept, and
    the result is re-confirmed (falls back to the original if the
    search was non-monotonic)."""
    remaining = [budget]

    def fails(cand) -> bool:
        if remaining[0] <= 0:
            return False
        remaining[0] -= 1
        return bool(_run_schedule(build, cand).violations)

    best = list(choices)
    lo, hi = 0, len(best)
    while lo < hi:
        mid = (lo + hi) // 2
        if fails(best[:mid]):
            hi = mid
        else:
            lo = mid + 1
    cand = best[:hi]
    if fails(cand):
        best = cand
    for i in range(len(best)):
        if best[i] != 0:
            cand = best[:i] + [0] + best[i + 1:]
            if fails(cand):
                best = cand
    while best and best[-1] == 0 and fails(best[:-1]):
        best = best[:-1]
    if not fails(best):
        return list(choices)
    return best


def format_trace(trace) -> str:
    """Annotated schedule trace: step x thread x schedule point."""
    lines = [f"  {'step':>4}  {'thread':<12} schedule point"]
    for i, (name, label) in enumerate(trace):
        lines.append(f"  {i:>4}  {name:<12} {label}")
    return "\n".join(lines)


def save_reproducer(path: str, scenario: str, size: str, mutate,
                    result: _ScheduleResult) -> str:
    payload = {
        "version": 1,
        "tool": "dmlcheck-layer3",
        "scenario": scenario,
        "size": size,
        "mutate": list(mutate),
        "choices": list(result.choices),
        "violations": list(result.violations),
        "trace": [list(step) for step in result.trace],
    }
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
        f.write("\n")
    return path


def replay_file(path: str) -> dict:
    """Re-run the exact interleaving a reproducer recorded.  Returns
    the replay verdict dict (violations, trace, plus what the
    reproducer expected) — deterministic, so two replays of one file
    fail identically."""
    with open(path) as f:
        payload = json.load(f)
    name = payload["scenario"]
    if name not in SCENARIOS:
        raise ValueError(f"reproducer names unknown scenario {name!r}")
    size = payload.get("size", "quick")
    build = SCENARIOS[name][size]
    with apply_mutations(payload.get("mutate", ())):
        res = _run_schedule(build, payload.get("choices", ()))
    return {
        "scenario": name,
        "size": size,
        "mutate": payload.get("mutate", []),
        "violations": res.violations,
        "expected_violations": payload.get("violations", []),
        "reproduced": bool(res.violations),
        "trace": [list(step) for step in res.trace],
    }


# ---------------------------------------------------------------------------
# The layer entry point
# ---------------------------------------------------------------------------


def run_layer3(quick: bool = True, scenarios=None, mutate=(),
               repro_dir: str | None = None,
               stop_on_violation: bool = True):
    """Run the interleaving exploration; returns ``(findings, stats)``.

    ``quick``: exhaustive small configs under per-scenario schedule
    caps — deterministic, CI-sized.  Full mode scales the configs up
    and leans on POR pruning + a preemption bound + a wall-clock
    deadline per scenario.  ``mutate`` re-introduces known bugs for
    the mutation-test gate.  A violated invariant becomes one DML301
    finding (DML302 for deadlocks) carrying the minimized schedule and
    the reproducer path."""
    size = "quick" if quick else "full"
    names = list(scenarios) if scenarios else sorted(SCENARIOS)
    for name in names:
        if name not in SCENARIOS:
            raise ValueError(f"unknown scenario {name!r} (have: "
                             f"{sorted(SCENARIOS)})")
    findings: list[Finding] = []
    stats = {"size": size, "mutate": list(mutate), "scenarios": {}}
    t0 = time.perf_counter()
    with apply_mutations(mutate):
        for name in names:
            spec = SCENARIOS[name]
            build = spec[size]
            if quick:
                st = explore(build, max_schedules=spec["quick_max"],
                             stop_on_violation=stop_on_violation)
            else:
                st = explore(build, max_schedules=spec["full_max"],
                             stop_on_violation=stop_on_violation,
                             preemption_bound=3, por=True,
                             deadline_s=60.0)
            entry = {"schedules": st.schedules,
                     "seconds": round(st.seconds, 3),
                     "capped": st.capped,
                     "violations": 0}
            if st.violation is not None:
                minimized = _minimize(build, st.violation.choices)
                res = _run_schedule(build, minimized)
                if not res.violations:
                    res = st.violation   # shrink lost the bug: keep it
                entry["violations"] = len(res.violations)
                repro_path = None
                if repro_dir is not None:
                    repro_path = save_reproducer(
                        os.path.join(repro_dir, f"{name}.repro.json"),
                        name, size, mutate, res)
                    entry["reproducer"] = repro_path
                rule = "DML302" if res.deadlock else "DML301"
                head = res.violations[0]
                tail = (f"; +{len(res.violations) - 1} more"
                        if len(res.violations) > 1 else "")
                findings.append(Finding(
                    rule=rule,
                    file=f"layer3:{name}",
                    line=0,
                    message=(
                        f"invariant '{spec['invariant']}' violated: "
                        f"{head}{tail} [{st.schedules} schedule(s) "
                        f"explored; minimized to {len(res.choices)} "
                        "choice(s); reproducer: "
                        f"{repro_path or 'pass --repro-dir to emit'}"
                        "]"),
                    snippet=" -> ".join(
                        f"{t}@{l}" for t, l in res.trace[:6]),
                    layer=3,
                ))
            stats["scenarios"][name] = entry
    stats["seconds"] = round(time.perf_counter() - t0, 3)
    return findings, stats
