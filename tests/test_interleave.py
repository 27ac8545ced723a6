"""dmlcheck layer 3 (ISSUE 15): the deterministic interleaving
explorer over the gang control plane.

Tier-1 keystones: ``test_quick_sweep_is_clean_and_bounded`` (the
fixed tree survives exhaustive-small-config exploration — the layer-3
analogue of ``test_package_is_clean``) and the mutation gates
(with a known bug re-introduced the explorer MUST rediscover it
deterministically, and its reproducer must replay to the same failure
twice).  The scaled-up full sweep rides behind ``slow``.
"""

import json
import os
import subprocess
import sys

import pytest

from distributed_machine_learning_tpu.analysis.interleave import (
    MUTATIONS,
    SCENARIOS,
    _run_schedule,
    _Scenario,
    apply_mutations,
    explore,
    format_trace,
    replay_file,
    run_layer3,
)
from distributed_machine_learning_tpu.runtime import coordinator as _coord
from distributed_machine_learning_tpu.runtime.transport import (
    InProcTransport,
    TcpGangServer,
)

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
DMLCHECK = os.path.join(REPO, "tools", "dmlcheck.py")


# ---------------------------------------------------------------------------
# Scheduler mechanics
# ---------------------------------------------------------------------------

def test_seam_is_noop_without_scheduler():
    # The runtime must be oblivious to layer 3 when nothing is
    # installed: points vanish, blocking waits fall back to real ones.
    _coord._sched_point("hub:beats:w")
    assert _coord._sched_block("tcp:inflight:wait", lambda: True) is False


def test_identical_choices_give_identical_traces():
    build = SCENARIOS["abort_race"]["quick"]
    first = _run_schedule(build, ())
    again = _run_schedule(build, ())
    assert first.choices == again.choices
    assert first.trace == again.trace
    assert first.violations == again.violations == []
    replayed = _run_schedule(build, first.choices)
    assert replayed.trace == first.trace


def test_explore_is_deterministic():
    build = SCENARIOS["epoch_fence"]["quick"]
    a = explore(build, max_schedules=500)
    b = explore(build, max_schedules=500)
    assert a.schedules == b.schedules > 1
    assert not a.capped and a.violation is None


def test_scheduler_detects_deadlock():
    # Two threads each blocked on a predicate only the other could
    # satisfy — but neither ever does: the scheduler must call it a
    # deadlock, not hang.
    flags = {"a": False, "b": False}

    def build():
        def left():
            _coord._sched_block("test:left:wait", lambda: flags["a"])

        def right():
            _coord._sched_block("test:right:wait", lambda: flags["b"])

        return _Scenario([("left", left), ("right", right)],
                         check=lambda: [])

    res = _run_schedule(build, ())
    assert res.deadlock
    assert any("deadlock" in v for v in res.violations)


def test_blocked_thread_resumes_when_predicate_turns_true():
    state = {"ready": False, "resumed": False}

    def build():
        def waiter():
            _coord._sched_block("test:chan:wait",
                                lambda: state["ready"])
            state["resumed"] = True

        def setter():
            _coord._sched_point("test:chan:w")
            state["ready"] = True

        return _Scenario([("waiter", waiter), ("setter", setter)],
                         check=lambda: [])

    res = _run_schedule(build, ())
    assert not res.violations and not res.deadlock
    assert state["resumed"]


def test_scenario_thread_errors_become_violations():
    def build():
        def boom():
            raise RuntimeError("seeded failure")

        return _Scenario([("boom", boom)], check=lambda: [])

    res = _run_schedule(build, ())
    assert any("seeded failure" in v for v in res.violations)


def test_chooser_survives_stale_prefix():
    # A reproducer replayed against an edited scenario must degrade to
    # defaults, not crash the scheduler.
    build = SCENARIOS["epoch_fence"]["quick"]
    res = _run_schedule(build, (99, 99, 99))
    assert res.violations == []


# ---------------------------------------------------------------------------
# The tier-1 gate: the fixed tree is clean, quickly
# ---------------------------------------------------------------------------

# Scenarios whose quick schedule space is larger than their cap: the
# rest are explored exhaustively, and must stay so.
QUICK_CAPPED = {"beat_read_race", "dedup_inflight", "join_dup",
                "ledger_storm"}
# The sweep's size is its schedule count (33 090 today), not its
# duration: quick mode reads no clock, so the count is the same on
# every machine and under any load.
QUICK_BUDGET = 40_000


def test_quick_sweep_is_clean_and_bounded(tmp_path):
    findings, stats = run_layer3(quick=True,
                                 repro_dir=str(tmp_path / "repros"))
    assert findings == [], [f.message for f in findings]
    assert set(stats["scenarios"]) == set(SCENARIOS)
    for name, entry in stats["scenarios"].items():
        assert entry["violations"] == 0, (name, entry)
        cap = SCENARIOS[name]["quick_max"]
        assert 1 <= entry["schedules"] <= cap, (name, entry)
        assert entry["capped"] == (name in QUICK_CAPPED), (name, entry)
        if entry["capped"]:
            assert entry["schedules"] == cap, (name, entry)
    total = sum(e["schedules"] for e in stats["scenarios"].values())
    assert total <= QUICK_BUDGET, (total, stats)


# ---------------------------------------------------------------------------
# Mutation gates: re-introduced bugs MUST be rediscovered
# ---------------------------------------------------------------------------

def _gate(tmp_path, scenario, mutation):
    findings, stats = run_layer3(
        quick=True, scenarios=[scenario], mutate=(mutation,),
        repro_dir=str(tmp_path))
    assert len(findings) == 1, (
        f"{mutation} not rediscovered: {stats}")
    f = findings[0]
    assert f.rule == "DML301" and f.layer == 3
    assert f.file == f"layer3:{scenario}"
    repro = stats["scenarios"][scenario]["reproducer"]
    assert os.path.exists(repro)
    assert repro in f.message  # the finding carries its reproducer
    return f, repro


def test_dedup_eviction_bug_is_rediscovered(tmp_path):
    f, repro = _gate(tmp_path, "dedup_inflight", "dedup-evict")
    assert "in-flight" in f.message
    # The reproducer replays to the SAME failure twice — a CI failure
    # is a deterministic test case, not a flake.
    r1 = replay_file(repro)
    r2 = replay_file(repro)
    assert r1 == r2
    assert r1["reproduced"] and r1["violations"]
    assert r1["violations"] == json.load(open(repro))["violations"]


def test_epoch_fence_bug_is_rediscovered(tmp_path):
    f, repro = _gate(tmp_path, "epoch_fence", "epoch-unlocked")
    assert "drained" in f.message
    r1 = replay_file(repro)
    r2 = replay_file(repro)
    assert r1 == r2 and r1["reproduced"]
    # The minimized trace names the actual TOCTOU window.
    trace = format_trace(r1["trace"])
    assert "zombie" in trace and "hub:epoch:gap" in trace


def test_drain_promote_bug_is_rediscovered(tmp_path):
    # ISSUE 16: the serving drain/promote handoff.  With the result
    # fence's epoch check hoisted outside the lock, a retiring
    # replica's late post parks in the TOCTOU window through the
    # epoch bump and lands AFTER the handoff.
    f, repro = _gate(tmp_path, "drain_promote", "result-unfenced")
    assert "late result" in f.message
    r1 = replay_file(repro)
    r2 = replay_file(repro)
    assert r1 == r2 and r1["reproduced"]
    trace = format_trace(r1["trace"])
    assert "zombie" in trace and "hub:sepoch:gap" in trace


def test_weight_swap_bug_is_rediscovered(tmp_path):
    # ISSUE 18: the continuous-deployment hot-swap.  With the post
    # fence's weights-version check hoisted outside the lock, an
    # old-version compute's post parks in the TOCTOU window through
    # commit_weights' version flip and lands AFTER the swap committed
    # — a duplicate completion for a request the post-swap compute
    # already answered.
    f, repro = _gate(tmp_path, "weight_swap", "swap-unfenced")
    assert "old-version post" in f.message
    r1 = replay_file(repro)
    r2 = replay_file(repro)
    assert r1 == r2 and r1["reproduced"]
    trace = format_trace(r1["trace"])
    assert "zombie" in trace and "hub:swv:gap" in trace


def test_continuous_batching_bug_is_rediscovered(tmp_path):
    # ISSUE 19: the paged-KV admission race.  With the allocator's
    # capacity check hoisted outside the lock that binds the blocks,
    # two admitters park in the TOCTOU window, both pass against the
    # same headroom, and the pool overcommits — the reserve-on-admit
    # guarantee breaks while decodes are in flight.
    f, repro = _gate(tmp_path, "continuous_batching", "admit-unlocked")
    r1 = replay_file(repro)
    r2 = replay_file(repro)
    assert r1 == r2 and r1["reproduced"]
    assert any("overcommitted" in v or "pop from empty" in v
               for v in r1["violations"]), r1["violations"]
    # The minimized trace names the actual TOCTOU window.
    trace = format_trace(r1["trace"])
    assert "admit-" in trace and "kvb:admit:gap" in trace


def test_mutations_restore_the_fixed_methods(tmp_path):
    orig_evict = TcpGangServer.__dict__["_evict_seen_locked"]
    orig_locked = InProcTransport.__dict__["_locked"]
    with apply_mutations(("dedup-evict", "epoch-unlocked")):
        assert TcpGangServer.__dict__["_evict_seen_locked"] \
            is not orig_evict
        assert InProcTransport.__dict__["_locked"] is not orig_locked
    assert TcpGangServer.__dict__["_evict_seen_locked"] is orig_evict
    assert InProcTransport.__dict__["_locked"] is orig_locked
    # And the fixed tree stays clean on the gate scenarios afterwards.
    findings, _ = run_layer3(
        quick=True, scenarios=["dedup_inflight", "epoch_fence"],
        repro_dir=str(tmp_path))
    assert findings == []


def test_unknown_mutation_and_scenario_are_loud():
    with pytest.raises(ValueError, match="unknown mutation"):
        with apply_mutations(("no-such-bug",)):
            pass
    with pytest.raises(ValueError, match="unknown scenario"):
        run_layer3(quick=True, scenarios=["no_such_protocol"])
    assert set(MUTATIONS) == {"dedup-evict", "epoch-unlocked",
                              "result-unfenced", "swap-unfenced",
                              "admit-unlocked"}


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def _run_tool(*args):
    return subprocess.run(
        [sys.executable, "-S", "-E", DMLCHECK, *args],
        capture_output=True, text=True, timeout=180,
    )


def test_cli_layer3_quick_json_is_clean():
    res = _run_tool("--layer3", "--quick", "--json")
    assert res.returncode == 0, res.stdout + res.stderr
    verdict = json.loads(res.stdout)
    assert verdict["clean"] is True
    # Per-layer / per-rule timing for CI budget regressions.
    timing = verdict["timing"]
    assert {"layer1_s", "layer2_s", "layer3_s", "rules"} <= set(timing)
    assert timing["layer3_s"] > 0 and timing["layer2_s"] == 0
    assert any(k.startswith("layer3:") for k in timing["rules"])
    assert "DML013" in timing["rules"] and "DML014" in timing["rules"]
    assert verdict["layer3"]["size"] == "quick"


def test_cli_replay_fails_the_same_way_twice(tmp_path):
    _, stats = run_layer3(
        quick=True, scenarios=["epoch_fence"],
        mutate=("epoch-unlocked",), repro_dir=str(tmp_path))
    repro = stats["scenarios"]["epoch_fence"]["reproducer"]
    r1 = _run_tool("--replay", repro)
    r2 = _run_tool("--replay", repro)
    assert r1.returncode == r2.returncode == 1
    assert r1.stdout == r2.stdout
    assert "VIOLATION" in r1.stdout
    assert "schedule point" in r1.stdout  # the annotated trace header
    bad = _run_tool("--replay", str(tmp_path / "missing.json"))
    assert bad.returncode == 2


def test_cli_layer3_rules_require_the_flag():
    res = _run_tool("--rules", "DML301")
    assert res.returncode == 2
    assert "layer-3" in res.stderr.lower()


# ---------------------------------------------------------------------------
# The full sweep (slow)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_full_sweep_is_clean(tmp_path):
    findings, stats = run_layer3(quick=False,
                                 repro_dir=str(tmp_path / "repros"))
    assert findings == [], [f.message for f in findings]
    # Full mode explores at least as much as quick everywhere.
    _, qstats = run_layer3(quick=True,
                           repro_dir=str(tmp_path / "qrepros"))
    for name in SCENARIOS:
        assert (stats["scenarios"][name]["schedules"]
                >= min(qstats["scenarios"][name]["schedules"], 100))


@pytest.mark.slow
def test_full_sweep_rediscovers_dedup_bug(tmp_path):
    findings, _ = run_layer3(
        quick=False, scenarios=["dedup_inflight"],
        mutate=("dedup-evict",), repro_dir=str(tmp_path))
    assert len(findings) == 1 and findings[0].rule == "DML301"
