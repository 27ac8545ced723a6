"""Elastic serving fleet (ISSUE 16): router, replica workers, and the
chaos-proven SLO campaigns.

Tier-1 keystones: ``test_chaos_kill_two_replicas_mid_load`` (the
flagship — 8 in-proc replicas + 2 warm spares, two killed under load;
the fleet must heal by promotion, keep p99 bounded, and deliver every
admitted request exactly once) and the graceful-drain campaign (a
drained replica finishes its in-flight work and demotes with zero
drops).  The subprocess-replica tcp variant with an injected partition
rides behind ``slow``.
"""

import importlib.util
import json
import os
import subprocess
import sys
import threading
import time

import pytest

from distributed_machine_learning_tpu.runtime.faults import FaultEvents
from distributed_machine_learning_tpu.runtime.serving import (
    Overloaded,
    ServingConfig,
    ServingRouter,
)
from distributed_machine_learning_tpu.runtime.serving_worker import (
    ServingWorkerConfig,
    run_serving_worker,
    start_worker_thread,
)
from distributed_machine_learning_tpu.runtime.transport import (
    FileTransport,
    InProcHub,
    InProcTransport,
    TcpGangServer,
    TcpTransport,
)
from distributed_machine_learning_tpu.telemetry import Telemetry
from distributed_machine_learning_tpu.telemetry.registry import (
    Histogram,
    default_latency_buckets,
    default_time_buckets,
)
from distributed_machine_learning_tpu.telemetry.tracer import read_trace

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


def _step(prompts):
    return [list(p) + [sum(p) % 97] for p in prompts]


def _slow_step(delay_s):
    def step(prompts):
        time.sleep(delay_s)
        return _step(prompts)

    return step


# ---------------------------------------------------------------------------
# Router policy units (no fleet spawned)
# ---------------------------------------------------------------------------


def test_admission_control_rejects_loudly_past_the_bound():
    events = FaultEvents()
    router = ServingRouter(InProcTransport(InProcHub()),
                           ServingConfig(max_queue=2), events=events)
    router.submit([1])
    router.submit([2])
    with pytest.raises(Overloaded, match="queue full"):
        router.submit([3])
    # The rejection is counted, mirrored into FaultEvents — never a
    # silent drop.
    assert router.rejected == 1
    assert events.request_rejects == 1
    audit = router.audit()
    assert audit["admitted"] == 2 and audit["rejected"] == 1


def test_duplicate_rid_and_closed_router_are_refused():
    router = ServingRouter(InProcTransport(InProcHub()),
                           ServingConfig(max_queue=8))
    router.submit([1], rid="a")
    with pytest.raises(ValueError, match="duplicate rid"):
        router.submit([2], rid="a")
    router.close()
    with pytest.raises(Overloaded, match="closed"):
        router.submit([3])


def test_latency_buckets_resolve_millisecond_tails():
    """The ISSUE 16 bugfix: the train-step doubling grid
    (``default_time_buckets``) puts a whole millisecond-scale serving
    distribution inside one bucket, flattening p50 into p99; the √2
    latency preset resolves the tail."""
    old = Histogram("lat_old", (), buckets=default_time_buckets())
    new = Histogram("lat_new", (), buckets=default_latency_buckets())
    for _ in range(90):          # the body: 1.7 ms
        old.observe(1.7e-3)
        new.observe(1.7e-3)
    for _ in range(10):          # the tail: 3.0 ms
        old.observe(3.0e-3)
        new.observe(3.0e-3)
    qo, qn = old.quantiles(), new.quantiles()
    # Old grid: body and tail share the [1.6ms, 3.2ms] bucket — the
    # interpolated p50 drifts >30% off the true 1.7 ms and the p99/p50
    # separation collapses.
    assert qo["p50"] > 1.3 * 1.7e-3
    assert qo["p99"] < 1.5 * qo["p50"]
    # New grid: the body lands within 10% and the tail stays visible.
    assert abs(qn["p50"] - 1.7e-3) < 0.1 * 1.7e-3
    assert qn["p99"] > 1.5 * qn["p50"]
    # The router's histogram is built on the fixed preset.
    router = ServingRouter(InProcTransport(InProcHub()))
    assert router.latency.bounds == tuple(default_latency_buckets())


def test_straggler_replica_is_replaced_by_a_spare():
    """PR 6 replace semantics re-aimed at serving: a replica whose
    compute intervals stay >4x the fleet median for 3 consecutive
    judgments is demoted and a warm spare promoted in its place.

    ISSUE 17 moved the detector feed off the beat channel and onto the
    request event stream (the ``computed`` stage deltas — the shared
    ``serving_stage_samples`` code path), so this test fabricates
    completions with deterministic compute intervals instead of beats
    with service times."""
    hub = InProcHub()
    tx = InProcTransport(hub)
    events = FaultEvents()
    router = ServingRouter(
        InProcTransport(hub),
        ServingConfig(replicas=3, replica_timeout_s=60.0),
        events=events)
    for rank in range(4):
        tx.announce_join(rank, {"rank": rank, "spare": True,
                                "kind": "serving", "time": time.time()})
    router.pump()  # heal: promote 3 of the 4 spares
    assert sorted(router._replicas) == [0, 1, 2]
    for _ in range(9):
        router.submit([1, 2])
    router.pump()  # dispatch across the three replicas
    for rank in range(3):
        for req in tx.take_requests(rank, 8):
            # A deterministic compute interval in the stage record:
            # rank 2's is 10x the others' — the straggler signal.
            req["events"].append({
                "stage": "computed", "by": f"replica{rank}",
                "dt": 0.5 if rank == 2 else 0.05})
            assert tx.post_result(rank, req["epoch"], {
                "rid": req["rid"], "output": req["prompt"],
                "events": req["events"]})
    for _ in range(4):  # collect, then 3 consecutive judgments
        router.pump()
    assert router.evictions == 1
    assert events.replica_evictions == 1
    assert 2 not in router._replicas and 3 in router._replicas
    assert tx.read_serving(2)["role"] == "spare"
    kinds = [e.get("kind") for e in tx.read_health_events()]
    assert kinds.count("serve_promote") == 4  # 3 initial + the heal
    evict = [e for e in tx.read_health_events()
             if e.get("kind") == "serve_evict"]
    assert evict[0]["rank"] == 2 and "straggler" in evict[0]["why"]


def test_worker_promotion_restores_and_demotion_respares():
    """The replica state machine seen from the worker: spare announces
    ride the join channel with the prefetched step, promotion triggers
    exactly one O(restore) callback, retirement falls back to spare."""
    hub = InProcHub()
    router_tx, worker_tx = InProcTransport(hub), InProcTransport(hub)
    stop = threading.Event()
    restored = []
    t, out = start_worker_thread(
        worker_tx, 5, _step, stop,
        ServingWorkerConfig(heartbeat_interval=0.01),
        prefetch_fn=lambda: 42, on_restore=restored.append)
    deadline = time.monotonic() + 5.0
    while 5 not in router_tx.read_joins():
        assert time.monotonic() < deadline, "spare never announced"
        time.sleep(0.005)
    assert router_tx.read_joins()[5]["prefetched_step"] == 42
    router_tx.set_serving_role(5, "live")
    router_tx.push_request(5, {"rid": "q1", "prompt": [2, 3],
                               "epoch": 0})
    while not router_tx.take_results(8):
        assert time.monotonic() < deadline, "no result served"
        time.sleep(0.005)
    assert restored == [42]
    # Retire: the worker observes the role flip and re-announces.
    router_tx.retire_replica(5)
    router_tx.consume_join(5)
    while 5 not in router_tx.read_joins():
        assert time.monotonic() < deadline, "never re-spared"
        time.sleep(0.005)
    stop.set()
    t.join(5.0)
    assert out["restores"] == 1 and out["served"] == 1


def test_make_serving_step_seam_matches_generate():
    """The inference seam: ``make_serving_step`` wraps the batch-static
    decode program as ``step(prompts) -> outputs`` over ragged python
    token lists, grouping by length so each group is one batched call —
    and greedy outputs must match ``generate`` exactly."""
    import jax.numpy as jnp
    import numpy as np

    from distributed_machine_learning_tpu.inference.generate import (
        generate,
        make_serving_step,
    )
    from distributed_machine_learning_tpu.models.transformer import (
        TransformerLM,
    )
    from distributed_machine_learning_tpu.train.lm_step import (
        init_lm_state,
    )

    model = TransformerLM(vocab_size=32, d_model=16, n_layers=2,
                          n_heads=2)
    params = init_lm_state(model).params
    step = make_serving_step(model, params, max_new_tokens=4)
    prompts = [[1, 2, 3], [4, 5], [6, 7, 8]]
    outs = step(prompts)
    assert [len(o) for o in outs] == [7, 6, 7]
    for p, o in zip(prompts, outs):
        assert o[:len(p)] == p
        assert all(isinstance(t, int) for t in o)
    # The length-3 group ran as ONE batched call and must agree with
    # the batch-static entry point row for row.
    want = generate(model, params,
                    jnp.asarray([prompts[0], prompts[2]], jnp.int32),
                    max_new_tokens=4)
    np.testing.assert_array_equal(np.asarray([outs[0], outs[2]]),
                                  np.asarray(want))
    assert step(prompts) == outs  # greedy: deterministic
    with pytest.raises(ValueError, match="empty prompt"):
        step([[1], []])


def test_late_result_after_requeue_is_not_redispatched():
    """REVIEW fix: a rid requeued by an eviction and then completed by
    the dead replica's late-collected result must NOT be dispatched
    again off the queue — re-dispatching a done rid reset it to
    "dispatched", drove the open count negative when the survivor
    answered too, failed the exactly-once audit, and hung wait_idle."""
    hub = InProcHub()
    tx = InProcTransport(hub)
    router = ServingRouter(
        InProcTransport(hub),
        ServingConfig(replicas=1, replica_timeout_s=60.0))
    tx.announce_join(0, {"rank": 0, "spare": True, "kind": "serving",
                         "time": time.time()})
    router.pump()
    assert sorted(router._replicas) == [0]
    rid = router.submit([1, 2])
    router.pump()  # dispatched to replica 0
    # Replica 0 serves the request, but BEFORE the router collects the
    # result it judges 0 dead and evicts it — requeueing the rid.
    reqs = tx.take_requests(0, 8)
    assert [r["rid"] for r in reqs] == [rid]
    assert tx.post_result(0, reqs[0]["epoch"],
                          {"rid": rid, "output": [9]}) is True
    with router._lock:
        router._evict_locked(0, "test: presumed dead", time.monotonic())
    assert router.result(rid)["state"] == "queued"
    # A survivor joins; the next pump collects the late result FIRST,
    # then must skip the stale queue entry instead of re-dispatching.
    tx.announce_join(1, {"rank": 1, "spare": True, "kind": "serving",
                         "time": time.time()})
    router.pump()
    assert router.result(rid)["state"] == "done"
    with router._lock:
        assert router._replicas[1].in_flight == set()
    assert tx.take_requests(1, 8) == []
    verdict = router.audit()
    assert verdict["exactly_once"], verdict
    assert verdict["completed"] == 1 and verdict["open"] == 0
    assert verdict["duplicates_discarded"] == 0
    assert router.wait_idle(1.0)


class _StaleReadTx(InProcTransport):
    """Forces the retired-and-re-promoted race deterministically: the
    first time the worker observes its rank live, retire + re-promote
    the rank and push a request stamped with the NEW epoch — then hand
    the worker the pre-retire (stale) view it just read."""

    def __init__(self, hub, admin, rank):
        super().__init__(hub)
        self._admin = admin
        self._rank = rank
        self._raced = False

    def read_serving(self, replica=None):
        state = super().read_serving(replica)
        if (not self._raced and replica == self._rank
                and state.get("role") == "live"):
            self._raced = True
            self._admin.retire_replica(self._rank)
            self._admin.set_serving_role(self._rank, "live")
            e = self._admin.read_serving(self._rank)["epoch"]
            self._admin.push_request(self._rank, {
                "rid": "z", "prompt": [1, 2], "epoch": e})
        return state


def test_worker_repushes_requests_stamped_with_a_newer_epoch():
    """REVIEW fix: rank retired and re-promoted between the worker's
    serving read and its take — the taken requests carry the NEW
    epoch.  The worker must push them back and rebind instead of
    running them under the stale bound (where every post is fenced and
    the requests strand in the new replica's in-flight set forever,
    since the rank keeps beating and is never evicted)."""
    hub = InProcHub()
    admin = InProcTransport(hub)
    worker_tx = _StaleReadTx(hub, admin, rank=4)
    stop = threading.Event()
    t, out = start_worker_thread(
        worker_tx, 4, _step, stop,
        ServingWorkerConfig(heartbeat_interval=0.01))
    deadline = time.monotonic() + 10.0
    while 4 not in admin.read_joins():
        assert time.monotonic() < deadline, "spare never announced"
        time.sleep(0.002)
    admin.set_serving_role(4, "live")  # epoch 0; the racer moves the
    # rank to epoch 1 on the worker's next serving read.
    results = []
    while not results:
        assert time.monotonic() < deadline, "request z never served"
        results = admin.take_results(8)
        time.sleep(0.002)
    stop.set()
    t.join(5.0)
    assert [r["rid"] for r in results] == ["z"]
    assert results[0]["epoch"] == 1  # served under the REBOUND epoch
    assert out["repushed"] == 1 and out["served"] == 1
    assert out["fenced"] == 0 and out["restores"] == 2


def test_completed_entries_compact_and_late_duplicates_classify():
    """REVIEW fix: the ledger retains at most ``retain_done`` completed
    entries (prompt/result payloads are dropped; counters keep the
    audit exact), and a very late duplicate for a compacted rid still
    counts as a duplicate, never an unknown result."""
    hub = InProcHub()
    tx = InProcTransport(hub)
    router = ServingRouter(
        InProcTransport(hub),
        ServingConfig(replicas=1, replica_timeout_s=60.0,
                      retain_done=3))
    tx.announce_join(0, {"rank": 0, "spare": True, "kind": "serving",
                         "time": time.time()})
    router.pump()
    rids = [router.submit([i]) for i in range(8)]
    deadline = time.monotonic() + 10.0
    while router.completed < 8:
        assert time.monotonic() < deadline, router.audit()
        router.pump()
        for req in tx.take_requests(0, 8):
            tx.post_result(0, req["epoch"],
                           {"rid": req["rid"], "output": [0]})
    with router._lock:
        assert len(router._ledger) == 3
    assert router.result(rids[0]) is None  # compacted away
    assert router.result(rids[-1])["state"] == "done"
    # A dead replica's very late duplicate for a compacted rid.
    tx.post_result(0, 0, {"rid": rids[0], "output": [0]})
    router.pump()
    verdict = router.audit()
    assert verdict["admitted"] == verdict["completed"] == 8
    assert verdict["compacted"] == 5
    assert verdict["exactly_once"], verdict
    assert verdict["duplicates_discarded"] == 1
    assert verdict["unknown_results"] == 0


# ---------------------------------------------------------------------------
# Tier-1 campaigns
# ---------------------------------------------------------------------------

CHAOS_BUDGET_S = 150.0


def _spawn_fleet(hub, world, step_fn, wcfg=None):
    """One worker thread per rank, each with its OWN kill switch."""
    wcfg = wcfg or ServingWorkerConfig(heartbeat_interval=0.02)
    fleet = []
    for rank in range(world):
        stop = threading.Event()
        t, out = start_worker_thread(InProcTransport(hub), rank,
                                     step_fn, stop, wcfg)
        fleet.append((rank, stop, t, out))
    return fleet


def _submit_with_backpressure(router, n, deadline_s=120.0):
    deadline = time.monotonic() + deadline_s
    rng = 12345
    for _ in range(n):
        rng = (1103515245 * rng + 12345) % (1 << 31)
        prompt = [1 + (rng >> s) % 13 for s in (3, 7)]
        while True:
            try:
                router.submit(prompt)
                break
            except Overloaded:
                assert time.monotonic() < deadline, (
                    "fleet stopped absorbing load under backpressure")
                time.sleep(0.002)


@pytest.mark.faultinject
def test_chaos_kill_two_replicas_mid_load(tmp_path):
    """The flagship SLO campaign: 8 live replicas + 2 warm spares under
    a 200-request load; two replicas are killed mid-load.  The fleet
    must evict them on beat staleness, promote both spares, re-dispatch
    the orphaned requests, and still deliver every admitted request
    exactly once with a bounded p99."""
    t_start = time.monotonic()
    hub = InProcHub(mirror_dir=str(tmp_path / "gang"))
    events = FaultEvents()
    router = ServingRouter(
        InProcTransport(hub),
        ServingConfig(replicas=8, max_queue=64, micro_batch=4,
                      replica_timeout_s=0.4, poll_s=0.002),
        events=events)
    fleet = _spawn_fleet(hub, world=10,
                         step_fn=_slow_step(0.002))
    stop_router = threading.Event()
    rt = threading.Thread(target=router.run, args=(stop_router,),
                          name="router", daemon=True)
    rt.start()
    try:
        # Phase 1: quarter of the load against the healthy fleet.
        _submit_with_backpressure(router, 50)
        deadline = time.monotonic() + 30.0
        while router.completed < 25 or len(router._replicas) < 8:
            assert time.monotonic() < deadline, "fleet never warmed up"
            time.sleep(0.01)
        with router._lock:
            victims = sorted(router._replicas)[:2]
        # Phase 2: kill two LIVE replicas, keep the load coming.
        for rank, stop, _, _ in fleet:
            if rank in victims:
                stop.set()
        _submit_with_backpressure(router, 150)
        assert router.wait_idle(60.0), router.audit()
    finally:
        verdict = router.close()
        stop_router.set()
        for _, stop, t, _ in fleet:
            stop.set()
            t.join(5.0)
        rt.join(5.0)
    elapsed = time.monotonic() - t_start
    # Exactly-once: 200 admitted, 200 completed, zero lost; a request
    # finished by a dying replica AND a survivor is one delivery plus
    # one counted duplicate.
    assert verdict["exactly_once"], verdict
    assert verdict["admitted"] == verdict["completed"] == 200
    assert verdict["unknown_results"] == 0
    # The two kills were healed by the two warm spares.
    assert verdict["evictions"] == 2
    assert events.replica_evictions == 2
    assert verdict["promotions"] == 10  # 8 initial + 2 heals
    with router._lock:
        live = sorted(router._replicas)
    assert len(live) == 8 and not set(victims) & set(live)
    # SLO: the p99 absorbs the ~0.4s eviction window but stays bounded.
    assert verdict["latency"]["p99"] < 5.0, verdict["latency"]
    assert elapsed < CHAOS_BUDGET_S, (
        f"serving chaos campaign took {elapsed:.1f}s (cap "
        f"{CHAOS_BUDGET_S}s, target <20s)")
    # The post-mortem serving view renders from the mirrored ledger.
    spec = importlib.util.spec_from_file_location(
        "gang_status", os.path.join(REPO, "tools", "gang_status.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    status = tool.collect(str(tmp_path / "gang"),
                          str(tmp_path / "no-telemetry"))
    rendered = tool.render(status)
    assert "Serving fleet" in rendered
    assert "exactly-once: PASS" in rendered


@pytest.mark.faultinject
def test_graceful_drain_finishes_inflight_with_zero_drops():
    """Redeploy protocol: drain one replica mid-load — it stops getting
    new work, finishes what it owns, and demotes to spare.  Nothing is
    dropped, nothing is duplicated, and the eviction counter stays at
    zero (a drain is not a failure)."""
    hub = InProcHub()
    events = FaultEvents()
    router = ServingRouter(
        InProcTransport(hub),
        ServingConfig(replicas=2, max_queue=32, micro_batch=2,
                      replica_timeout_s=5.0, poll_s=0.002),
        events=events)
    fleet = _spawn_fleet(hub, world=3, step_fn=_slow_step(0.002))
    stop_router = threading.Event()
    rt = threading.Thread(target=router.run, args=(stop_router,),
                          daemon=True)
    rt.start()
    try:
        _submit_with_backpressure(router, 20)
        deadline = time.monotonic() + 30.0
        while router.completed < 5:
            assert time.monotonic() < deadline, "fleet never served"
            time.sleep(0.01)
        with router._lock:
            target = sorted(router._replicas)[0]
        assert router.drain(target)
        assert not router.drain(target)  # idempotent: already draining
        _submit_with_backpressure(router, 20)
        assert router.wait_idle(30.0), router.audit()
        drain_deadline = time.monotonic() + 10.0
        while router.drains_done < 1:
            assert time.monotonic() < drain_deadline, "drain never done"
            time.sleep(0.01)
    finally:
        verdict = router.close()
        stop_router.set()
        for _, stop, t, _ in fleet:
            stop.set()
            t.join(5.0)
        rt.join(5.0)
    assert verdict["exactly_once"], verdict
    assert verdict["admitted"] == verdict["completed"] == 40
    assert verdict["drains"] == 1 and events.drains == 1
    assert verdict["evictions"] == 0
    tx = InProcTransport(hub)
    assert tx.read_serving(target)["role"] == "spare"
    demote = [e for e in tx.read_health_events()
              if e.get("kind") == "serve_demote"]
    assert demote and demote[0]["why"] == "drained"


@pytest.mark.faultinject
def test_cli_serve_inproc_smoke():
    """The launcher end-to-end: in-proc fleet, a mid-load drain, exit
    status = the exactly-once audit."""
    res = subprocess.run(
        [sys.executable, "-m",
         "distributed_machine_learning_tpu.cli.serve",
         "--replicas", "2", "--spares", "1", "--requests", "40",
         "--drain-after", "10", "--gang-transport", "inproc",
         "--timeout", "60"],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert res.returncode == 0, res.stdout + res.stderr
    assert "exactly-once audit: PASS" in res.stdout
    assert "2 replicas + 1 spares over inproc" in res.stdout
    assert "1 drains" in res.stdout


# ---------------------------------------------------------------------------
# Slow campaign: subprocess replicas over tcp, with a partition
# ---------------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.faultinject
def test_tcp_subprocess_replica_partition_is_healed(tmp_path):
    """The cross-process shape: replica workers are real subprocesses
    joined over tcp; one gets its channel severed by injected chaos.
    The router must evict it on beat staleness, promote the spare
    subprocess, and keep the load exactly-once."""
    server = TcpGangServer().start()
    addr = server.address
    cmd = [sys.executable, "-m",
           "distributed_machine_learning_tpu.cli.serve",
           "--role", "worker", "--address", addr,
           "--service-time", "0.005"]
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    procs = [
        subprocess.Popen([*cmd, "--rank", "0"], env=env),
        # Rank 1's channel is severed after ~300 of its own transport
        # ops — comfortably after its promotion, while it serves.
        subprocess.Popen([*cmd, "--rank", "1", "--tx-chaos",
                          "partition@300"], env=env),
    ]
    events = FaultEvents()
    router = ServingRouter(
        TcpTransport(addr, backoff_s=0.01),
        ServingConfig(replicas=2, max_queue=32, micro_batch=2,
                      replica_timeout_s=1.0, poll_s=0.01),
        events=events)
    stop_router = threading.Event()
    rt = threading.Thread(target=router.run, args=(stop_router,),
                          daemon=True)
    rt.start()
    try:
        # Gate the load on BOTH subprocess replicas being live, so the
        # partition is guaranteed to hit a serving replica.
        _wait_replicas(router, 2)
        _submit_with_backpressure(router, 60)
        # The warm spare joins mid-load, ready for the heal.
        procs.append(subprocess.Popen([*cmd, "--rank", "2"], env=env))
        _submit_with_backpressure(router, 60)
        assert router.wait_idle(90.0), router.audit()
        # The severed rank stops beating whenever its chaos fires; the
        # router must notice, evict, and heal back to 2 live.
        deadline = time.monotonic() + 30.0
        while True:
            with router._lock:
                live = sorted(router._replicas)
            if router.evictions >= 1 and live == [0, 2]:
                break
            assert time.monotonic() < deadline, (
                router.evictions, live)
            time.sleep(0.05)
    finally:
        verdict = router.close()
        stop_router.set()
        rt.join(5.0)
        for p in procs:
            p.terminate()
        for p in procs:
            p.wait(timeout=10)
        server.stop()
    assert verdict["exactly_once"], verdict
    assert verdict["admitted"] == verdict["completed"] == 120
    assert verdict["evictions"] >= 1  # the partitioned rank
    assert events.replica_evictions >= 1


# ---------------------------------------------------------------------------
# Request-scoped tracing + SLO observability (ISSUE 17)
# ---------------------------------------------------------------------------

# The documented happy-path journey (runtime/transport.py::SERVING_STAGES
# minus the failure stamps): what every completed record must show.
EXPECTED_JOURNEY = ["admitted", "queued", "dispatched", "taken",
                    "bound", "computed", "posted", "completed"]


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _observed_fleet(tmp_path, backend, world, step_fn, *,
                    replicas=2, replica_timeout_s=5.0):
    """Router + workers with instance-tagged telemetry, over the file
    or inproc (dir-mirrored) backend — both leave a readable gang dir
    for the offline tools."""
    gang = str(tmp_path / "gang")
    teldir = str(tmp_path / "telemetry")
    if backend == "inproc":
        hub = InProcHub(mirror_dir=gang)
        make_tx = lambda: InProcTransport(hub)  # noqa: E731
    else:
        os.makedirs(gang, exist_ok=True)
        make_tx = lambda: FileTransport(gang)  # noqa: E731
    router_tel = Telemetry(teldir, instance="router", enabled=True)
    worker_tels = [Telemetry(teldir, instance=f"replica{r}", enabled=True)
                   for r in range(world)]
    router = ServingRouter(
        make_tx(),
        ServingConfig(replicas=replicas, max_queue=64, micro_batch=2,
                      replica_timeout_s=replica_timeout_s, poll_s=0.002),
        telemetry=router_tel)
    fleet = []
    for rank in range(world):
        stop = threading.Event()
        t, out = start_worker_thread(
            make_tx(), rank, step_fn, stop,
            ServingWorkerConfig(heartbeat_interval=0.02),
            telemetry=worker_tels[rank])
        fleet.append((rank, stop, t, out))
    return gang, teldir, router, router_tel, worker_tels, fleet


def _wait_replicas(router, n: int) -> None:
    """Block until the running router has promoted ``n`` replicas."""
    deadline = time.monotonic() + 30.0
    while True:
        with router._lock:
            if len(router._replicas) == n:
                return
        assert time.monotonic() < deadline, f"never saw {n} live replicas"
        time.sleep(0.005)


def _teardown_fleet(router, rt, stop_router, fleet, router_tel,
                    worker_tels):
    verdict = router.close()
    stop_router.set()
    for _, stop, t, _ in fleet:
        stop.set()
        t.join(5.0)
    rt.join(5.0)
    router_tel.close()
    for tel in worker_tels:
        tel.close()
    return verdict


@pytest.mark.parametrize("backend", ["inproc", "file"])
def test_request_journey_lands_in_every_artifact_plane(tmp_path,
                                                       backend):
    """The ISSUE 17 acceptance path on both single-host backends: a
    served request's journey shows up (a) as the documented stage-event
    sequence in the ledger record, (b) as per-stage histograms in the
    router's registry snapshot, (c) in the offline serve_status
    renderings including --postmortem, and (d) as a merged Perfetto
    timeline with router + replica tracks and the request span on both
    sides of a flow link."""
    gang, teldir, router, router_tel, worker_tels, fleet = \
        _observed_fleet(tmp_path, backend, world=2, step_fn=_step)
    stop_router = threading.Event()
    rt = threading.Thread(target=router.run, args=(stop_router,),
                          daemon=True)
    rt.start()
    rids = []
    try:
        # (c) below wants BOTH replicas in the served records: the router
        # dispatches to the least loaded of those it has promoted, so a
        # replica that joins after the load has gone out serves none.
        _wait_replicas(router, 2)
        for i in range(8):
            rids.append(router.submit([1 + i, 2]))
        assert router.wait_idle(60.0), router.audit()
        records = [router.result(rid) for rid in rids]
    finally:
        verdict = _teardown_fleet(router, rt, stop_router, fleet,
                                  router_tel, worker_tels)
    assert verdict["exactly_once"], verdict
    assert verdict["admitted"] == verdict["completed"] == 8

    # (a) The ledger record carries the full documented journey, with
    # rank-local deltas only: dt is None exactly where the previous
    # stamp crossed a process boundary (DML001 — no cross-host deltas).
    for rec in records:
        stages = [e["stage"] for e in rec["events"]]
        assert stages == EXPECTED_JOURNEY, stages
        by_stage = {e["stage"]: e for e in rec["events"]}
        assert by_stage["admitted"]["dt"] is None   # first stamp ever
        assert by_stage["taken"]["dt"] is None      # crossed the wire
        for stage in ("queued", "dispatched", "bound", "computed",
                      "posted", "completed"):
            assert by_stage[stage]["dt"] >= 0.0, by_stage[stage]
        for stage in ("admitted", "queued", "dispatched", "completed"):
            assert by_stage[stage]["by"] == "router"
        worker_by = by_stage["taken"]["by"]
        assert worker_by in ("replica0", "replica1")
        for stage in ("bound", "computed", "posted"):
            assert by_stage[stage]["by"] == worker_by
        assert by_stage["dispatched"]["disp"] == 1
        assert by_stage["taken"]["disp"] == 1   # rides the payload tag
        for ev in rec["events"]:
            assert "_mono_last" not in ev and "_mono_by" not in ev

    # Router-clock stage intervals partition the end-to-end latency:
    # queued + dispatched + completed ≈ total (worker stages nest
    # INSIDE completed's dispatch round trip — summing all eight would
    # double-count).  Means are exact sums, so the tolerance is only
    # clock-read placement, not histogram interpolation.
    means = {s: h.sum / h.count
             for s, h in router._stage_hist.items() if h.count}
    router_clock = (means["queued"] + means["dispatched"]
                    + means["completed"])
    e2e = router.latency.sum / router.latency.count
    assert abs(router_clock - e2e) < 0.25 * e2e + 0.05, (means, e2e)
    sl = verdict["stage_latency"]
    p50_sum = sum(sl[s]["p50"]
                  for s in ("queued", "dispatched", "completed"))
    assert p50_sum < 4.0 * verdict["latency"]["p50"] + 0.05

    # (b) The registry snapshot streams the per-stage histograms.
    with open(os.path.join(teldir, "registry.router.json")) as f:
        reg = json.load(f)
    stage_rows = {h["labels"]["stage"]: h for h in reg["histograms"]
                  if h["name"] == "serving_stage_latency_s"}
    assert {"queued", "dispatched", "bound", "computed", "posted",
            "completed"} <= set(stage_rows)
    assert all(row["count"] == 8 for row in stage_rows.values())
    gauge_names = {g["name"] for g in reg["gauges"]}
    assert {"serving_queue_depth", "serving_inflight",
            "serving_replicas"} <= gauge_names

    # (c) serve_status renders the same story offline, from the dirs.
    serve_status = _load_tool("serve_status")
    status = serve_status.collect(gang, teldir)
    assert len(status["requests"]) == 8
    assert set(status["stages"]) >= {"computed", "completed"}
    assert [r["rank"] for r in status["replicas"]] == [0, 1]
    rendered = serve_status.render(status)
    assert "Per-stage latency" in rendered
    assert "Per-replica compute" in rendered
    pm = serve_status.render_postmortem(status, rids[0])
    assert pm is not None and f"Postmortem {rids[0]}" in pm
    for stage in EXPECTED_JOURNEY:
        assert stage in pm
    assert serve_status.render_postmortem(status, "no-such-rid") is None
    slo = serve_status.slo_replay(status["requests"], ["p99<=30s"],
                                  short_window_s=5.0, long_window_s=60.0,
                                  burn_threshold=2.0)
    assert slo["ok"] is True and slo["replayed"] == 8

    # (d) trace_merge fuses router + replica streams into named tracks
    # in their own pid block, with the request flow-linked by rid.
    trace_merge = _load_tool("trace_merge")
    merged, counts = trace_merge.merge_traces(teldir)
    assert set(counts) == {"router", "replica0", "replica1"}
    assert counts["router"] == 8
    events = merged["traceEvents"]
    base = trace_merge.SERVING_PID_BASE
    spans = [e for e in events
             if e.get("ph") == "X" and e.get("name") == "request"]
    for rid in rids:
        pids = {e["pid"] for e in spans if e["args"].get("rid") == rid}
        assert base in pids, f"{rid} missing its router span"
        assert pids & {base + 1, base + 2}, (
            f"{rid} missing its replica span")
    flows = [e for e in events if e.get("name") == "request_flow"]
    assert len(flows) == 2 * 8   # one s + one f per request
    meta = {e["pid"]: e["args"]["name"] for e in events
            if e.get("ph") == "M" and e["name"] == "process_name"}
    assert meta[base] == "serve router"
    assert meta[base + 1] == "serve replica 0"
    assert meta[base + 2] == "serve replica 1"


@pytest.mark.faultinject
def test_chaos_kill_replica_mid_compute_terminates_the_record(tmp_path):
    """ISSUE 17 chaos proof: a replica wedges mid-compute holding a
    dispatched request.  The router evicts it on beat staleness and the
    record shows the victim's leg TERMINATED — ``requeued`` after
    ``dispatched`` — then a single ``completed`` on the promoted
    survivor; the victim's own late post is fenced, and every replica
    trace span is closed with a terminal outcome."""
    t_start = time.monotonic()
    release = threading.Event()
    poison = [13, 13, 13]

    def step(prompts):
        if poison in [list(p) for p in prompts]:
            release.wait(30.0)
        return _step(prompts)

    gang, teldir, router, router_tel, worker_tels, fleet = \
        _observed_fleet(tmp_path, "inproc", world=3, step_fn=step,
                        replicas=2, replica_timeout_s=0.4)
    stop_router = threading.Event()
    rt = threading.Thread(target=router.run, args=(stop_router,),
                          daemon=True)
    rt.start()
    try:
        _wait_replicas(router, 2)
        rid = router.submit(poison)
        for i in range(10):
            router.submit([1 + i])
        deadline = time.monotonic() + 30.0
        while router.evictions < 1:
            assert time.monotonic() < deadline, (
                "stalled replica never evicted")
            time.sleep(0.005)
        release.set()   # un-wedge: survivors serve the requeued work
        assert router.wait_idle(60.0), router.audit()
        rec = router.result(rid)
    finally:
        release.set()
        verdict = _teardown_fleet(router, rt, stop_router, fleet,
                                  router_tel, worker_tels)
    assert verdict["exactly_once"], verdict
    assert verdict["admitted"] == verdict["completed"] == 11
    assert verdict["evictions"] == 1

    # The poisoned request's record: dispatched -> requeued (victim's
    # leg terminated by the router) -> dispatched again -> completed
    # ONCE, with the second leg's worker stamps from a different rank.
    stages = [e["stage"] for e in rec["events"]]
    first_disp = stages.index("dispatched")
    requeue_at = stages.index("requeued")
    assert first_disp < requeue_at, stages
    assert stages.count("dispatched") >= 2
    assert stages.count("completed") == 1
    assert stages.index("completed") > requeue_at
    requeue_ev = rec["events"][requeue_at]
    assert requeue_ev["by"] == "router"
    victim = requeue_ev["replica"]
    assert victim is not None
    serving_leg = [e for e in rec["events"] if e["stage"] == "computed"]
    assert serving_leg and all(
        e["by"] != f"replica{victim}" for e in serving_leg)
    # The requeue interval reached the stage histograms.
    assert verdict["stage_latency"].get("requeued", {}).get("count", 0) \
        or "requeued" in verdict["stage_latency"]

    # No unclosed spans: every request span in every replica trace is a
    # complete event with a terminal outcome — including the victim's
    # fenced late post.
    outcomes = []
    for r in range(3):
        path = os.path.join(teldir, f"trace.replica{r}.json")
        if not os.path.exists(path):
            continue
        for e in read_trace(path):
            if isinstance(e, dict) and e.get("name") == "request":
                assert e.get("ph") == "X" and e.get("dur", -1) >= 0
                stage = (e.get("args") or {}).get("stage")
                assert stage in ("posted", "fenced", "requeued"), e
                outcomes.append((e["args"].get("rank"), stage))
    assert (victim, "fenced") in outcomes, outcomes

    # The postmortem renders the full story from the mirrored ledger.
    serve_status = _load_tool("serve_status")
    pm = serve_status.render_postmortem(
        serve_status.collect(gang, teldir), rid)
    assert pm is not None and "requeued" in pm and "completed" in pm
    elapsed = time.monotonic() - t_start
    assert elapsed < CHAOS_BUDGET_S, (
        f"mid-compute chaos took {elapsed:.1f}s (cap {CHAOS_BUDGET_S}s)")


@pytest.mark.faultinject
def test_cli_serve_slo_verdict_gates_exit_status(tmp_path):
    """--slo end to end: a generous objective passes (rc 0) and leaves
    the telemetry artifacts; an impossible objective over deliberately
    slow service prints a failing verdict and exits 1."""
    base = [sys.executable, "-m",
            "distributed_machine_learning_tpu.cli.serve",
            "--replicas", "2", "--spares", "0", "--requests", "30",
            "--gang-transport", "inproc", "--timeout", "60"]
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    teldir = str(tmp_path / "tel")
    ok = subprocess.run(
        [*base, "--telemetry-dir", teldir, "--slo", "p99<=30s",
         "--slo", "reject_ratio<=50%"],
        capture_output=True, text=True, timeout=120, env=env)
    assert ok.returncode == 0, ok.stdout + ok.stderr
    assert "exactly-once audit: PASS" in ok.stdout
    assert "slo p99<=30s: PASS" in ok.stdout
    assert "slo verdict: PASS" in ok.stdout
    assert os.path.exists(os.path.join(teldir, "registry.router.json"))
    assert os.path.exists(os.path.join(teldir, "trace.router.json"))
    assert os.path.exists(os.path.join(teldir, "trace.replica0.json"))

    bad = subprocess.run(
        [*base, "--service-time", "0.02", "--slo", "p99<=1ms"],
        capture_output=True, text=True, timeout=120, env=env)
    assert bad.returncode == 1, bad.stdout + bad.stderr
    assert "exactly-once audit: PASS" in bad.stdout  # delivery still ok
    assert "slo p99<=1ms: FAIL" in bad.stdout
    assert "slo verdict: FAIL" in bad.stdout
    assert "SLO objectives violated" in bad.stderr
