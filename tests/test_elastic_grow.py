"""Elastic GROW (ISSUE 10): rejoin-on-recovery, warm spares, backup-
worker straggler replacement, and the supervision plumbing behind them.

Fast half (stub processes, no jax in the workers): the coordinator's
join/announcement channel, the ``recover_rank`` fault kind and its
gang-wide ledger latch, ``checkpoint_extra`` round-trips, the
``_seed_checkpoint`` admission copy, ``gang_supervise`` grow/spare
validation, and stub-process supervision proofs — grow-on-announced-
join at a planned boundary, spare promotion filling the grown world,
failure shrinks NOT silently backfilled by spares, and readmission
after a shrink (the 3→2→3 trajectory with the lose_rank marker cleared
by recover_rank).

Slow half (``slow`` + ``faultinject``): the ROADMAP's named chaos
proofs — a 4-worker gang goes 4→3→5 in one supervised run (lose a
rank, recover it, promote a spare) with exactly-once consumption
across both transitions and a final checkpoint restoring onto worlds
1/3/4/5; the linear scaling rule keeps the loss curve continuous
across the world changes while the pinned control shifts the floor
(the rule is load-bearing); and ``--straggler-policy=replace`` turns a
``stall_rank`` fault into a demotion + spare promotion the status tool
can narrate.
"""

from __future__ import annotations

import hashlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distributed_machine_learning_tpu.runtime.coordinator import (
    announce_join,
    clear_gang_state,
    consume_join,
    read_joins,
)
from distributed_machine_learning_tpu.runtime.faults import (
    FAULT_LEDGER_FILE,
    FaultEvents,
    FaultInjector,
    corrupt_checkpoint_data,
    ledger_entries,
    ledger_recovered_ranks,
    ledger_unrecovered_lost_ranks,
)
from distributed_machine_learning_tpu.runtime.supervisor import (
    _seed_checkpoint,
    gang_supervise,
)
from distributed_machine_learning_tpu.telemetry.aggregator import (
    read_health_events,
)
from distributed_machine_learning_tpu.train.checkpoint import (
    checkpoint_config,
    checkpoint_extra,
    latest_checkpoint,
    quarantine_checkpoint,
    reshard_restore,
    save_checkpoint,
    validate_checkpoint,
)
from distributed_machine_learning_tpu.train.state import TrainState

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


# ---------------------------------------------------------------------------
# Coordinator join/announcement channel
# ---------------------------------------------------------------------------


def test_join_channel_roundtrip(tmp_path):
    announce_join(tmp_path, 2, kind="recover", at_step=5)
    announce_join(tmp_path, 4, spare=True, prefetched_step=10)
    joins = read_joins(tmp_path)
    assert set(joins) == {2, 4}
    assert joins[2]["spare"] is False and joins[2]["at_step"] == 5
    assert joins[4]["spare"] is True and joins[4]["prefetched_step"] == 10
    # Re-announcing is an idempotent atomic overwrite (the spare's
    # heartbeat refreshes its prefetch progress this way).
    announce_join(tmp_path, 4, spare=True, prefetched_step=12)
    assert read_joins(tmp_path)[4]["prefetched_step"] == 12
    consume_join(tmp_path, 2)
    assert set(read_joins(tmp_path)) == {4}
    consume_join(tmp_path, 2)  # consuming twice is a no-op
    with pytest.raises(ValueError):
        announce_join(tmp_path, -1)
    # A torn payload is skipped, not fatal — the next poll sees it whole.
    (tmp_path / "join_rank7.json").write_text("{not json")
    assert set(read_joins(tmp_path)) == {4}


def test_clear_gang_state_join_survival(tmp_path):
    """A pending join must survive the very boundary that will admit it
    (between-attempt and shrink clears), dying only at fresh-run init —
    the same rule as the fault ledger."""
    announce_join(tmp_path, 3)
    clear_gang_state(tmp_path)  # between same-size attempts
    assert 3 in read_joins(tmp_path)
    clear_gang_state(tmp_path, restore_records=True, fault_ledger=False)
    assert 3 in read_joins(tmp_path)  # a shrink boundary keeps it too
    clear_gang_state(tmp_path, restore_records=True)  # fresh run
    assert read_joins(tmp_path) == {}


def test_ledger_loss_recovery_masking_is_order_aware(tmp_path):
    """A recover_rank clears only EARLIER lose_rank entries: a rank
    that dies again after recovering is lost again.  Plain set
    subtraction would mask the second loss forever."""
    ledger = tmp_path / FAULT_LEDGER_FILE

    def append(entry):
        with open(ledger, "a") as f:
            f.write(json.dumps(entry) + "\n")

    append({"kind": "lose_rank", "rank": 1, "at": 3})
    assert ledger_unrecovered_lost_ranks(ledger) == {1}
    append({"kind": "recover_rank", "rank": 0, "target": 1, "at": 6})
    assert ledger_unrecovered_lost_ranks(ledger) == set()
    append({"kind": "lose_rank", "rank": 1, "at": 9})
    assert ledger_unrecovered_lost_ranks(ledger) == {1}
    # ... while the all-time sets stay order-blind (the budget-reset
    # marker keeps using them).
    assert ledger_recovered_ranks(ledger) == {1}


# ---------------------------------------------------------------------------
# recover_rank fault kind
# ---------------------------------------------------------------------------


def test_recover_rank_grammar():
    inj = FaultInjector.parse("recover_rank@1:5", rank=0)
    assert inj.pending() == ["recover_rank@1:5"]
    with pytest.raises(ValueError):
        FaultInjector.parse("recover_rank@5")  # missing target rank
    with pytest.raises(ValueError):
        FaultInjector.parse("recover_rank@1:5:2.0")  # too many fields


def test_recover_rank_acts_via_current_rank0(tmp_path):
    ledger = tmp_path / FAULT_LEDGER_FILE
    ev = FaultEvents()
    # A process NOT currently holding rank 0 latches without acting:
    # no ledger entry, no join announcement.
    inj = FaultInjector.parse("recover_rank@1:5", rank=3)
    inj.current_rank = 2
    inj.attach_ledger(ledger)
    assert list(inj.wrap_batches(range(8), ev)) == list(range(8))
    assert ev.rank_recoveries == 0
    assert read_joins(tmp_path) == {}
    assert ledger_recovered_ranks(ledger) == set()
    # The current rank 0 (here: original rank 2 after a renumbering)
    # acts on the dead host's behalf: ledger entry with the TARGET rank
    # distinct from the acting rank, plus the join announcement.
    inj = FaultInjector.parse("recover_rank@1:5", rank=2)
    inj.current_rank = 0
    inj.attach_ledger(ledger)
    list(inj.wrap_batches(range(8), ev))
    assert ev.rank_recoveries == 1
    joins = read_joins(tmp_path)
    assert joins[1]["spare"] is False and joins[1]["at_step"] == 5
    assert ledger_recovered_ranks(ledger) == {1}
    entry = ledger_entries(ledger)[-1]
    assert entry["kind"] == "recover_rank"
    assert entry["target"] == 1 and entry["rank"] == 2
    # The latch is GANG-WIDE: any fresh process re-attaching (including
    # a different future holder of rank 0) sees it fired and never
    # re-fires the recovery.
    inj2 = FaultInjector.parse("recover_rank@1:5", rank=0)
    inj2.current_rank = 0
    inj2.attach_ledger(ledger)
    assert inj2.pending() == []


# ---------------------------------------------------------------------------
# checkpoint_extra + _seed_checkpoint (the admission copy)
# ---------------------------------------------------------------------------


def test_checkpoint_extra_roundtrip(tmp_path):
    state = TrainState.create(params={"w": jnp.zeros((4,), jnp.float32)})
    p = save_checkpoint(tmp_path, state,
                        extra_payload={"example_cursor": 96, "world": 4})
    assert checkpoint_extra(p) == {"example_cursor": 96, "world": 4}
    # The extra payload rides the config file without polluting the
    # config read-back.
    checkpoint_config(p)
    p2 = save_checkpoint(tmp_path, state.replace(step=state.step + 1))
    assert checkpoint_extra(p2) == {}  # absent: empty, not an error
    quarantine_checkpoint(p, "gang election verdict")
    assert checkpoint_extra(p) == {}  # known-bad data is never served


def test_seed_checkpoint_copies_and_validates(tmp_path):
    state = TrainState.create(
        params={"w": jnp.arange(4, dtype=jnp.float32)}
    )
    src = tmp_path / "src"
    save_checkpoint(src, state)  # step_0
    dst = tmp_path / "dst"
    os.makedirs(dst)
    assert _seed_checkpoint(dst, 0, [str(src)]) is True
    assert validate_checkpoint(os.path.join(dst, "step_0")) == []
    # Already holding a valid copy: True without touching any source.
    assert _seed_checkpoint(dst, 0, [str(tmp_path / "nowhere")]) is True
    assert _seed_checkpoint(dst, None, [str(src)]) is False
    # A corrupt source is skipped (the COPY is validated, so a torn
    # copy can never masquerade as a checkpoint); a later valid source
    # still lands.
    src_bad = tmp_path / "src_bad"
    corrupt_checkpoint_data(save_checkpoint(src_bad, state))
    dst2 = tmp_path / "dst2"
    assert _seed_checkpoint(dst2, 0, [str(src_bad)]) is False
    assert _seed_checkpoint(dst2, 0, [str(src_bad), str(src)]) is True
    assert validate_checkpoint(os.path.join(dst2, "step_0")) == []


# ---------------------------------------------------------------------------
# gang_supervise validation
# ---------------------------------------------------------------------------


def test_gang_supervise_grow_validation(tmp_path):
    def cmd4(rank, attempt, world, orig):
        return ["true"]

    def cmd3(rank, attempt, world):
        return ["true"]

    def spare(orig, attempt):
        return ["true"]

    g = str(tmp_path / "g")
    with pytest.raises(ValueError):  # max_world below the launch world
        gang_supervise(cmd4, 4, g, max_world=3)
    with pytest.raises(ValueError):
        gang_supervise(cmd4, 2, g, spares=-1)
    with pytest.raises(ValueError):  # spares need a spare_cmd
        gang_supervise(cmd4, 2, g, spares=1)
    with pytest.raises(ValueError):
        gang_supervise(cmd4, 2, g, straggler_policy="evict")
    with pytest.raises(ValueError):  # replace needs a spare to promote
        gang_supervise(cmd4, 2, g, straggler_policy="replace")
    with pytest.raises(ValueError):
        gang_supervise(cmd4, 2, g, spares=1, spare_cmd=spare,
                       straggler_policy="replace", replace_after=0)
    with pytest.raises(ValueError):  # growing needs the 4-arg signature
        gang_supervise(cmd3, 2, g, max_world=3)
    with pytest.raises(ValueError):  # per-rank dirs must cover spares
        gang_supervise(cmd4, 2, g, spares=1, spare_cmd=spare,
                       ckpt_dirs=[str(tmp_path / "a"), str(tmp_path / "b")])


# ---------------------------------------------------------------------------
# Stub-process supervision: grow, spare promotion, no silent backfill
# ---------------------------------------------------------------------------


def _stub_worker_cmd(tmp_path, body: str):
    """Worker argv factory: the subprocess runs ``body`` with {rank}/
    {attempt}/{world}/{orig}/{root} substitutions — cheap processes, no
    jax import.  Same idiom as tests/test_elastic.py."""

    def worker_cmd(rank, attempt, world, orig_rank):
        code = body.format(rank=rank, attempt=attempt, world=world,
                           orig=orig_rank, root=str(tmp_path))
        return [sys.executable, "-c", code]

    return worker_cmd


def _spare_stub_cmd(tmp_path, prefetched_step=0):
    """Spare argv factory: announce on the join channel, then stand by
    until the drain terminates us."""

    def spare_cmd(orig, attempt):
        code = (
            "import json, os, time\n"
            f"orig = {orig}\n"
            f"gang = os.path.join({str(tmp_path)!r}, 'gang')\n"
            "os.makedirs(gang, exist_ok=True)\n"
            "tmp = os.path.join(gang, '.spare%d' % orig)\n"
            "with open(tmp, 'w') as f:\n"
            "    json.dump(dict(rank=orig, spare=True, time=time.time(),\n"
            f"                   prefetched_step={prefetched_step}), f)\n"
            "os.replace(tmp, os.path.join(gang, 'join_rank%d.json' % orig))\n"
            "time.sleep(60)\n"
        )
        return [sys.executable, "-c", code]

    return spare_cmd


# Attempt-0 workers: rank 0 announces a (non-spare) join for JOINRANK
# — once SPAREFILE, the spare's own announcement, is there, where a
# test has a spare: the supervisor takes its snapshot of promotable
# spares at the boundary the join triggers, and a spare process still
# starting up is not in it — then everyone waits on the abort latch and
# takes the coordinated abort exit (43); attempt >= 1 workers record
# themselves and finish.
_GROW_BODY = (
    "import json, os, sys, time\n"
    "rank, attempt, world, orig = {rank}, {attempt}, {world}, {orig}\n"
    "root = {root!r}\n"
    "gang = os.path.join(root, 'gang')\n"
    "with open(os.path.join(root, 'seen.jsonl'), 'a') as f:\n"
    "    f.write(json.dumps(dict(rank=rank, attempt=attempt,\n"
    "                            world=world, orig=orig)) + '\\n')\n"
    "if attempt == 0:\n"
    "    deadline = time.time() + 20\n"
    "    if rank == 0:\n"
    "        spare = 'SPAREFILE'\n"
    "        while (spare and time.time() < deadline and not\n"
    "               os.path.exists(os.path.join(gang, spare))):\n"
    "            time.sleep(0.01)\n"
    "        tmp = os.path.join(gang, '.join_tmp')\n"
    "        with open(tmp, 'w') as f:\n"
    "            json.dump(dict(rank=JOINRANK, spare=False,\n"
    "                           time=time.time()), f)\n"
    "        os.replace(tmp, os.path.join(gang, 'join_rankJOINRANK.json'))\n"
    "    while time.time() < deadline:\n"
    "        if os.path.exists(os.path.join(gang, 'abort.json')):\n"
    "            os._exit(43)\n"
    "        time.sleep(0.05)\n"
    "sys.exit(0)\n"
)


def _seen(tmp_path):
    return [json.loads(line) for line in
            (tmp_path / "seen.jsonl").read_text().splitlines()]


def test_gang_supervise_grows_on_announced_join(tmp_path):
    """A pending (non-spare) join triggers a PLANNED boundary: the
    supervisor latches the abort itself, admits the joiner, renumbers
    2→3, charges nobody's budget and consumes no max_restarts — with
    the grow visible in events and the health ledger."""
    gang = tmp_path / "gang"
    events = FaultEvents()
    codes = gang_supervise(
        _stub_worker_cmd(tmp_path, _GROW_BODY.replace("JOINRANK", "2")
                         .replace("SPAREFILE", "")),
        2, gang, max_world=3, events=events, poll_s=0.05,
        max_restarts=1, grace_s=5.0,
    )
    assert codes == [0, 0, 0]
    assert events.gang_grows == 1
    assert events.gang_restarts == 0  # planned boundaries are free
    assert events.gang_shrinks == 0
    final = [s for s in _seen(tmp_path) if s["attempt"] == 1]
    assert sorted((s["rank"], s["orig"]) for s in final) == [
        (0, 0), (1, 1), (2, 2)]
    assert all(s["world"] == 3 for s in final)
    # The admission consumed the announcement: it can't drive a second
    # grow.
    assert read_joins(gang) == {}
    kinds = [e.get("kind") for e in read_health_events(gang)]
    assert "boundary" in kinds and "grow" in kinds


def test_gang_supervise_promotes_spare_to_fill_grown_world(tmp_path):
    """With room left after the announced join (max_world 4, 2 workers,
    1 joiner), the live announced spare is promoted to fill the world —
    counted as a spare_promotion and narrated in the health ledger."""
    gang = tmp_path / "gang"
    events = FaultEvents()
    codes = gang_supervise(
        _stub_worker_cmd(tmp_path, _GROW_BODY.replace("JOINRANK", "3")
                         .replace("SPAREFILE", "join_rank2.json")),
        2, gang, max_world=4, spares=1,
        spare_cmd=_spare_stub_cmd(tmp_path, prefetched_step=7),
        events=events, poll_s=0.05, max_restarts=1, grace_s=5.0,
    )
    assert codes == [0, 0, 0, 0]
    assert events.gang_grows == 1
    assert events.spare_promotions == 1
    assert events.spare_demotions == 0
    final = [s for s in _seen(tmp_path) if s["attempt"] == 1]
    # Joined rank 3 AND promoted spare (orig 2) fill the world of 4,
    # renumbered in original order.
    assert sorted((s["rank"], s["orig"]) for s in final) == [
        (0, 0), (1, 1), (2, 2), (3, 3)]
    assert all(s["world"] == 4 for s in final)
    health = read_health_events(gang)
    promo = [e for e in health if e.get("kind") == "promote"]
    assert len(promo) == 1 and promo[0]["rank"] == 2
    grow = [e for e in health if e.get("kind") == "grow"]
    assert grow and grow[0]["joined"] == [3] and grow[0]["promoted"] == [2]


# Attempt-0: rank 1 writes a lose_rank ledger entry and dies hard;
# later attempts just finish.  Used to prove failure shrinks never
# silently backfill from the spare pool.
_LOSE_BODY = (
    "import json, os, sys\n"
    "rank, attempt, world, orig = {rank}, {attempt}, {world}, {orig}\n"
    "root = {root!r}\n"
    "with open(os.path.join(root, 'seen.jsonl'), 'a') as f:\n"
    "    f.write(json.dumps(dict(rank=rank, attempt=attempt,\n"
    "                            world=world, orig=orig)) + '\\n')\n"
    "if attempt == 0 and orig == 1:\n"
    "    with open(os.path.join(root, 'gang',\n"
    "                           'faults_fired.jsonl'), 'a') as f:\n"
    "        f.write(json.dumps(dict(index=0, kind='lose_rank', at=7,\n"
    "                                rank=1)) + '\\n')\n"
    "    os._exit(23)\n"
    "sys.exit(0)\n"
)


def test_failure_shrink_never_backfills_from_spares(tmp_path):
    """Spares promote ONLY at planned boundaries: a lose_rank failure
    shrink proceeds to the smaller world even with a live announced
    spare standing by — the reduced world stays observable."""
    gang = tmp_path / "gang"
    events = FaultEvents()
    codes = gang_supervise(
        _stub_worker_cmd(tmp_path, _LOSE_BODY), 3, gang,
        min_world=1, max_world=3, spares=1,
        spare_cmd=_spare_stub_cmd(tmp_path),
        events=events, poll_s=0.05, max_restarts=2, grace_s=5.0,
    )
    assert codes == [0, 0]
    assert events.gang_shrinks == 1
    assert events.gang_grows == 0 and events.spare_promotions == 0
    final = [s for s in _seen(tmp_path) if s["attempt"] == 1]
    assert sorted((s["rank"], s["orig"]) for s in final) == [(0, 0), (1, 2)]
    assert all(s["world"] == 2 for s in final)


# The readmission trajectory 3→2→3: attempt 0 loses rank 1 (shrink to
# 2); attempt 1's CURRENT rank 0 announces rank 1 recovered (the
# recover_rank acting rule) and the gang waits at the latch; attempt 2
# runs the re-grown world of 3.
_RECOVER_BODY = (
    "import json, os, sys, time\n"
    "rank, attempt, world, orig = {rank}, {attempt}, {world}, {orig}\n"
    "root = {root!r}\n"
    "gang = os.path.join(root, 'gang')\n"
    "with open(os.path.join(root, 'seen.jsonl'), 'a') as f:\n"
    "    f.write(json.dumps(dict(rank=rank, attempt=attempt,\n"
    "                            world=world, orig=orig)) + '\\n')\n"
    "if attempt == 0 and orig == 1:\n"
    "    with open(os.path.join(gang, 'faults_fired.jsonl'), 'a') as f:\n"
    "        f.write(json.dumps(dict(index=0, kind='lose_rank', at=7,\n"
    "                                rank=1)) + '\\n')\n"
    "    os._exit(23)\n"
    "if attempt == 1:\n"
    "    if rank == 0:\n"
    "        with open(os.path.join(gang, 'faults_fired.jsonl'), 'a') as f:\n"
    "            f.write(json.dumps(dict(index=1, kind='recover_rank',\n"
    "                                    at=9, rank=orig,\n"
    "                                    target=1)) + '\\n')\n"
    "        tmp = os.path.join(gang, '.join_tmp')\n"
    "        with open(tmp, 'w') as f:\n"
    "            json.dump(dict(rank=1, spare=False, kind='recover',\n"
    "                           time=time.time()), f)\n"
    "        os.replace(tmp, os.path.join(gang, 'join_rank1.json'))\n"
    "    deadline = time.time() + 20\n"
    "    while time.time() < deadline:\n"
    "        if os.path.exists(os.path.join(gang, 'abort.json')):\n"
    "            os._exit(43)\n"
    "        time.sleep(0.05)\n"
    "sys.exit(0)\n"
)


def test_recovered_rank_rejoins_after_shrink(tmp_path):
    """The full rejoin-on-recovery trajectory with stubs: 3→2 on
    lose_rank, then the recover_rank ledger entry clears the lost
    marker and the announced join re-admits original rank 1 → 2→3,
    with its failure budget reset."""
    gang = tmp_path / "gang"
    events = FaultEvents()
    codes = gang_supervise(
        _stub_worker_cmd(tmp_path, _RECOVER_BODY), 3, gang,
        min_world=1, max_world=3, events=events, poll_s=0.05,
        max_restarts=2, grace_s=5.0,
    )
    assert codes == [0, 0, 0]
    assert events.gang_shrinks == 1 and events.gang_grows == 1
    assert events.gang_restarts == 1  # only the failure charged
    by_attempt: dict[int, list] = {}
    for s in _seen(tmp_path):
        by_attempt.setdefault(s["attempt"], []).append(s)
    assert sorted(s["orig"] for s in by_attempt[1]) == [0, 2]
    assert all(s["world"] == 2 for s in by_attempt[1])
    assert sorted(s["orig"] for s in by_attempt[2]) == [0, 1, 2]
    assert all(s["world"] == 3 for s in by_attempt[2])
    # The world trajectory reads 3 -> 2 -> 3 in the status tool's
    # derivation of the health ledger.
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "gang_status", os.path.join(REPO, "tools", "gang_status.py")
    )
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    status = tool.collect(str(gang), str(tmp_path / "no-telemetry"))
    assert status["world_trajectory"] == [3, 2, 3]


# ---------------------------------------------------------------------------
# Chaos proofs (slow + faultinject): 4→3→5, scaling-rule continuity,
# straggler replacement
# ---------------------------------------------------------------------------


def _run_gang(root, *, faults=None, workers=4, steps=30, save_every=5,
              timeout=280, extra=()):
    from distributed_machine_learning_tpu.cli.gang import (
        scrubbed_worker_env,
    )

    cmd = [
        sys.executable, "-m", "distributed_machine_learning_tpu.cli.gang",
        "--workers", str(workers), "--steps", str(steps),
        "--save-every", str(save_every),
        "--ckpt-dir", os.path.join(root, "ckpt"),
        "--gang-dir", os.path.join(root, "gang"),
        "--telemetry-dir", os.path.join(root, "telemetry"),
        *extra,
    ]
    if faults:
        cmd += ["--faults", faults]
    return subprocess.run(
        cmd, capture_output=True, text=True, timeout=timeout,
        env=scrubbed_worker_env(REPO), cwd=REPO,
    )


def _consumed_records(root):
    gang = os.path.join(root, "gang")
    recs = []
    for name in os.listdir(gang):
        if name.startswith("consumed_rank"):
            with open(os.path.join(gang, name)) as f:
                for line in f:
                    recs.append(json.loads(line))
    return recs


def _assert_exactly_once_chained(root, n_steps) -> dict[int, int]:
    """Judged in the attempt that finally completed each step, the
    consumed example ids chain CONTIGUOUSLY across the whole run — any
    world/batch history partitions the example stream into
    non-overlapping global batches (the elastic exactly-once
    invariant).  Returns step -> world."""
    by_step: dict[int, list] = {}
    for r in _consumed_records(root):
        by_step.setdefault(r["step"], []).append(r)
    assert sorted(by_step) == list(range(n_steps))
    cursor = 0
    worlds: dict[int, int] = {}
    for step in range(n_steps):
        rows = by_step[step]
        final_attempt = max(r["attempt"] for r in rows)
        final = [r for r in rows if r["attempt"] == final_attempt]
        ids = sorted(i for r in final for i in r["ids"])
        assert ids == list(range(cursor, cursor + len(ids))), (
            f"step {step}: consumed ids {ids[:3]}..{ids[-3:]} do not "
            f"chain at cursor {cursor} — examples lost or duplicated"
        )
        ws = {r["world"] for r in final}
        assert len(ws) == 1, f"step {step} consumed at mixed worlds {ws}"
        worlds[step] = ws.pop()
        assert len(final) == worlds[step]  # every rank logged its shard
        cursor += len(ids)
    return worlds


def _step_losses(root) -> dict[int, float]:
    """step -> quadratic loss from current-rank-0's per-attempt logs,
    later attempts overriding replayed steps (original rank 0 survives
    every transition in these scenarios, so it holds current rank 0
    throughout)."""
    logs = os.path.join(root, "gang", "logs")
    by_attempt = sorted(
        (name for name in os.listdir(logs)
         if name.startswith("rank0.attempt")),
        key=lambda n: int(n.split("attempt")[1].split(".")[0]),
    )
    losses: dict[int, float] = {}
    for name in by_attempt:
        with open(os.path.join(logs, name)) as f:
            for line in f:
                parts = line.split()
                if len(parts) == 4 and parts[0] == "step" \
                        and parts[2] == "loss":
                    losses[int(parts[1])] = float(parts[3])
    return losses


def _registry_counters(root):
    with open(os.path.join(root, "telemetry", "registry.json")) as f:
        snap = json.load(f)
    counters = {c["name"]: c["value"] for c in snap["counters"]
                if not c.get("labels")}
    gauges = {g["name"]: g["value"] for g in snap.get("gauges", [])}
    return counters, gauges, snap


# The 4→3→5 schedule: lose rank 1 at step 7 (shrink to 3), recover it
# at step 14 (planned grow boundary; the warm spare rides along to 5).
_CHAOS_FAULTS = "lose_rank@1:7,recover_rank@1:14"
_CHAOS_EXTRA = ("--max-world", "5", "--spares", "1",
                "--feature-dim", "64", "--min-world", "1")


@pytest.mark.slow
@pytest.mark.faultinject
def test_chaos_world_4_3_5_with_linear_rule(tmp_path):
    """The ROADMAP's named chaos proof: one supervised run goes 4→3→5 —
    lose_rank@1:7 shrinks to the 3 survivors, recover_rank@1:14
    triggers a planned grow boundary readmitting rank 1 AND promoting
    the warm spare to reach 5 — finishing with a verified checkpoint
    that restores onto worlds 1/3/4/5, exactly-once consumption
    chained across both transitions, and (under the linear scaling
    rule) a loss curve continuous across both world changes."""
    root = str(tmp_path / "chaos")
    res = _run_gang(root, faults=_CHAOS_FAULTS,
                    extra=(*_CHAOS_EXTRA, "--scaling-rule", "linear"))
    assert res.returncode == 0, res.stdout + res.stderr
    assert "shrinking to 3 survivor(s)" in res.stdout
    assert "world 3 -> 5" in res.stdout
    assert "world size 5" in res.stdout

    counters, gauges, _ = _registry_counters(root)
    assert counters["gang_shrinks"] == 1
    assert counters["gang_grows"] == 1
    assert counters["spare_promotions"] == 1
    assert counters["gang_restarts"] == 1  # only the failure charged
    assert gauges.get("gang_world_size") == 5

    # Both transitions are trace instants (tools/trace_merge.py renders
    # them on the merged timeline).
    with open(os.path.join(root, "telemetry", "trace.json")) as f:
        trace = f.read()
    assert '"gang_shrink"' in trace and '"gang_grow"' in trace

    # Exactly-once consumption, chained across 4→3→5 (batch 24→18→30
    # under the linear rule).
    worlds = _assert_exactly_once_chained(root, 30)
    assert set(worlds.values()) == {3, 4, 5}
    assert worlds[0] == 4 and worlds[29] == 5

    # The health ledger narrates the story and the status tool derives
    # the 4→3→5 trajectory from it.
    res_status = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "gang_status.py"),
         os.path.join(root, "gang"), "--json"],
        capture_output=True, text=True, timeout=60,
    )
    assert res_status.returncode == 0, res_status.stderr
    status = json.loads(res_status.stdout)
    assert status["world_trajectory"] == [4, 3, 5]
    kinds = [e.get("kind") for e in status["health"]]
    assert "shrink" in kinds and "grow" in kinds and "promote" in kinds
    grow = next(e for e in status["health"] if e.get("kind") == "grow")
    assert grow["joined"] == [1] and grow["promoted"] == [4]

    # The final checkpoint restores onto worlds 1/3/4/5 bit-identically
    # from every member's directory, and the whole chain verifies.
    digests = {}
    for orig_rank in (0, 2, 3, 4):
        latest = latest_checkpoint(
            os.path.join(root, "ckpt", f"rank{orig_rank}")
        )
        assert latest is not None and latest.endswith("step_30")
        for w in (1, 3, 4, 5):
            state, spec = reshard_restore(latest, world=w)
            assert spec.world == w
            digests[(orig_rank, w)] = hashlib.sha256(
                np.ascontiguousarray(
                    np.asarray(state.params["w"])
                ).tobytes()
            ).hexdigest()
    assert len(set(digests.values())) == 1, digests
    res_verify = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "ckpt_verify.py"),
         os.path.join(root, "ckpt"), "--json"],
        capture_output=True, text=True, timeout=120,
    )
    assert res_verify.returncode == 0, res_verify.stdout + res_verify.stderr
    assert json.loads(res_verify.stdout)["invalid"] == 0

    # Loss-curve continuity (the scaling-rule proof, linear half):
    # no step-discontinuity beyond the fixed tolerance at either
    # transition, and the stationary floor is world-invariant within
    # band — the quadratic loss is chi-square-noisy (dim 64: ~18%/step),
    # so windows average a few steps and the tolerances are generous
    # multiples of the expected shifts.
    losses = _step_losses(root)
    assert sorted(losses) == list(range(30))
    for boundary in (7, 14):
        pre = np.mean([losses[s] for s in range(boundary - 3, boundary)])
        post = np.mean([losses[s] for s in range(boundary, boundary + 3)])
        assert 1 / 3 < post / pre < 3, (
            f"loss discontinuity at the world change near step "
            f"{boundary}: {pre:.4f} -> {post:.4f}"
        )
    floor3 = np.mean([losses[s] for s in range(9, 14)])
    floor5 = np.mean([losses[s] for s in range(25, 30)])
    assert 0.6 < floor5 / floor3 < 2.0, (
        f"linear rule failed to hold the stationary floor: world-3 "
        f"window {floor3:.4f} vs world-5 window {floor5:.4f}"
    )


@pytest.mark.slow
@pytest.mark.faultinject
def test_chaos_control_unscaled_rule_breaks_the_floor(tmp_path):
    """The load-bearing control: the same 4→3→5 run under ``unscaled``
    (batch tracks the world, LR never compensates) shifts the
    stationary loss floor with 1/world — the discontinuity the linear
    rule exists to prevent (expected ratio ≈ 0.6 here, well outside
    the linear run's band)."""
    root = str(tmp_path / "control")
    res = _run_gang(root, faults=_CHAOS_FAULTS,
                    extra=(*_CHAOS_EXTRA, "--scaling-rule", "unscaled"))
    assert res.returncode == 0, res.stdout + res.stderr
    assert "world size 5" in res.stdout
    worlds = _assert_exactly_once_chained(root, 30)
    assert worlds[29] == 5  # same trajectory, same exactly-once story
    losses = _step_losses(root)
    floor3 = np.mean([losses[s] for s in range(9, 14)])
    floor5 = np.mean([losses[s] for s in range(25, 30)])
    # lr/(B(2-lr)) per coordinate: unchanged lr over a 18→30 batch
    # change moves the floor by ~0.6x — the control demonstrates the
    # compensation is load-bearing, not decorative.
    assert floor5 / floor3 < 0.75, (
        f"expected the unscaled control to shift the floor: "
        f"{floor3:.4f} -> {floor5:.4f}"
    )


@pytest.mark.slow
@pytest.mark.faultinject
def test_chaos_straggler_replacement_policy(tmp_path):
    """stall_rank@1:6:30 under ``--straggler-policy=replace``: the
    stalled rank is demoted to the spare pool at a planned replacement
    boundary and the warm spare is promoted in its place — world size
    unchanged, nobody's restart budget charged, and the counters +
    health ledger tell the story through ``gang_status``."""
    root = str(tmp_path / "straggle")
    res = _run_gang(
        root, faults="stall_rank@1:6:30", steps=16,
        extra=("--spares", "1", "--straggler-policy", "replace",
               "--replace-after", "2", "--peer-timeout", "60",
               "--max-world", "4"),
    )
    assert res.returncode == 0, res.stdout + res.stderr
    assert "straggler policy: demoting rank 1" in res.stdout
    assert "world size 4" in res.stdout

    counters, gauges, snap = _registry_counters(root)
    assert counters["spare_promotions"] == 1
    assert counters["spare_demotions"] == 1
    assert counters.get("gang_restarts", 0) == 0  # planned, not charged
    assert counters.get("gang_shrinks", 0) == 0
    assert gauges.get("gang_world_size") == 4
    straggler = [c for c in snap["counters"]
                 if c["name"] == "gang_straggler"
                 and c.get("labels", {}).get("rank") == "1"]
    assert straggler and straggler[0]["value"] >= 1

    worlds = _assert_exactly_once_chained(root, 16)
    assert set(worlds.values()) == {4}  # replacement kept the world

    res_status = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "gang_status.py"),
         os.path.join(root, "gang"), "--json"],
        capture_output=True, text=True, timeout=60,
    )
    assert res_status.returncode == 0, res_status.stderr
    status = json.loads(res_status.stdout)
    demotes = [e for e in status["health"] if e.get("kind") == "demote"]
    promotes = [e for e in status["health"] if e.get("kind") == "promote"]
    assert len(demotes) == 1 and demotes[0]["rank"] == 1
    assert len(promotes) == 1 and promotes[0]["rank"] == 4
    # The demoted rank stands by as a spare in the final attempt.
    spare_ranks = {r["rank"] for r in status.get("spares", ())}
    assert 1 in spare_ranks
    # And the human rendering narrates the same story.
    res_render = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "gang_status.py"),
         os.path.join(root, "gang")],
        capture_output=True, text=True, timeout=60,
    )
    assert "demote" in res_render.stdout
    assert "promote" in res_render.stdout
