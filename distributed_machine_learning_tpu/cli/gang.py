"""Local gang launcher — N coordinated workers, one restart domain.

The smallest end-to-end surface for the gang fault-tolerance stack::

    python -m distributed_machine_learning_tpu.cli.gang \
        --workers 4 --steps 12 --save-every 5 \
        --ckpt-dir /tmp/run/ckpt --gang-dir /tmp/run/gang \
        --faults kill_rank@1:7 --telemetry-dir /tmp/run/telemetry

launches ``runtime/gang_worker.py`` once per rank (each its own OS
process, lock-stepped through the beat-directory barrier, checkpointing
into its own ``<ckpt-dir>/rank<r>`` — the per-host shard layout),
supervises them with ``runtime/supervisor.py::gang_supervise``, and
prints the resilience summary.  Worker logs land under
``<gang-dir>/logs/``.  The workers are forced onto the CPU backend by
design (``scrubbed_worker_env``, ``gang_worker.py``): a ``cli.gang`` run
exercises the control plane and is never a chip run.

Elastic by default: a rank that is gone for good (``lose_rank@r:k``
fired, or ``--rank-restart-budget`` spent) shrinks the gang to the
survivors instead of stranding the job — down to ``--min-world``
workers (default 1; 0 disables shrinking), with the per-host batch
rescaled so the ``--global-batch`` (and the LR schedule) is preserved
and every example still consumed exactly once per step.

Elastic GROW (ISSUE 10): ``--max-world N`` lets the gang grow back —
a recovered host (``recover_rank@r:k``, or any out-of-band
``announce_join``) is readmitted at the next coordinated boundary and
the world renumbers M→N through the same ``reshard_restore`` path a
shrink uses.  ``--spares K`` runs K warm-spare workers beside the gang
(heartbeating and prefetching the newest verified checkpoint, never
training); spares are promoted at planned boundaries — filling the
world after a grow admission, or, under
``--straggler-policy replace``, replacing a persistently slow rank
(demoted to spare, with ``--replace-after`` consecutive flagged health
feeds of hysteresis).  ``--scaling-rule`` picks how (global batch, LR)
respond to a world change (``train/scaling.py``): ``pinned`` keeps
PR 5's world-invariant batch, ``linear``/``lars`` grow the batch with
the world and compensate the LR so the loss trajectory stays
continuous; ``unscaled`` is the deliberately-wrong control.

Observable by default (ISSUE 6): the gang telemetry plane lands under
``<gang-dir>/telemetry`` — supervisor counters/spans at canonical
names, each worker's stream rank-suffixed beside them — with live
straggler detection (``--straggler-multiple``/
``--straggler-consecutive``) feeding ``gang_straggler{rank}`` counters,
the ``gang_skew_ratio`` gauge, and the ``gang_health.jsonl`` advisory
ledger; the run ends with a cross-rank skew summary.  Post-mortem:
``tools/gang_status.py <gang-dir>`` and ``tools/trace_merge.py
<gang-dir>/telemetry``.  ``--no-telemetry`` turns it all off.
"""

from __future__ import annotations

import argparse
import os
import sys


def scrubbed_worker_env(repo_root: str | None = None) -> dict:
    """A worker environment safe for a fresh multi-process rendezvous:
    force the CPU platform (a chip belongs to one process; N workers
    cannot share it) and drop any 8-way virtual-device split (each
    worker must own exactly one device for the mesh to really span the
    process boundary).  ``repo_root`` goes first on PYTHONPATH so the
    workers import this package wherever they are launched from."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    keep = [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]
    if repo_root:
        keep.insert(0, repo_root)
    env["PYTHONPATH"] = os.pathsep.join(keep)
    return env


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workers", type=int, default=4,
                    help="gang size (one process, one CPU device each)")
    ap.add_argument("--steps", type=int, default=12,
                    help="training steps each worker must complete")
    ap.add_argument("--save-every", type=int, default=5,
                    help="checkpoint every N steps (plus a final save)")
    ap.add_argument("--ckpt-dir", required=True,
                    help="shared checkpoint directory (verified saves)")
    ap.add_argument("--gang-dir", required=True,
                    help="shared coordination directory (heartbeats, "
                         "abort latch, restore-point records)")
    ap.add_argument("--global-batch", dest="global_batch", type=int,
                    default=24,
                    help="examples per global step batch; each rank "
                         "consumes its exact shard, so a shrink "
                         "rescales the per-host batch while the global "
                         "batch (and LR schedule) is preserved")
    ap.add_argument("--faults", default=None,
                    help="fault spec forwarded to every worker, e.g. "
                         "'kill_rank@1:7' or 'lose_rank@1:7' "
                         "(runtime/faults.py)")
    ap.add_argument("--max-restarts", dest="max_restarts", type=int,
                    default=3,
                    help="coordinated gang relaunches before giving up")
    ap.add_argument("--min-world", dest="min_world", type=int, default=1,
                    help="smallest gang the supervisor may shrink to "
                         "when a rank is unrecoverable (lose_rank fired "
                         "or per-rank budget spent); 0 disables "
                         "shrinking — an unrecoverable rank then fails "
                         "the job")
    ap.add_argument("--max-world", dest="max_world", type=int, default=0,
                    help="largest gang the supervisor may GROW to when "
                         "a recovered/new host announces a join "
                         "(recover_rank fault or announce_join); 0 "
                         "(default) disables growing")
    ap.add_argument("--spares", type=int, default=0,
                    help="warm-spare workers run beside the gang: they "
                         "heartbeat on the join channel and prefetch "
                         "the newest verified checkpoint but never "
                         "train; promoted at planned boundaries")
    ap.add_argument("--straggler-policy", dest="straggler_policy",
                    default="advise", choices=("advise", "replace"),
                    help="what a straggler verdict does: 'advise' "
                         "(default) only flags; 'replace' demotes the "
                         "slow rank to spare and promotes a warm spare "
                         "in its place (requires --spares >= 1)")
    ap.add_argument("--replace-after", dest="replace_after", type=int,
                    default=2,
                    help="consecutive flagged health feeds before the "
                         "replace policy acts (hysteresis: one flag "
                         "never flips the gang)")
    ap.add_argument("--scaling-rule", dest="scaling_rule",
                    default="pinned",
                    choices=("pinned", "linear", "lars", "unscaled"),
                    help="how (global batch, LR) respond to a world "
                         "change (train/scaling.py); anchored at the "
                         "launch world")
    ap.add_argument("--base-lr", dest="base_lr", type=float, default=0.5,
                    help="learning rate at the launch world (the "
                         "scaling rule's anchor)")
    ap.add_argument("--feature-dim", dest="feature_dim", type=int,
                    default=8,
                    help="toy example dimensionality (the chaos "
                         "continuity proof uses a wider dim so the "
                         "per-step loss noise is small against the "
                         "floor shifts it measures)")
    ap.add_argument("--rank-restart-budget", dest="rank_restart_budget",
                    type=int, default=None,
                    help="failures attributable to one rank before it "
                         "is declared unrecoverable (default: "
                         "unlimited; lose_rank marks a rank "
                         "unrecoverable regardless)")
    ap.add_argument("--heartbeat-interval", dest="heartbeat_interval",
                    type=float, default=0.25,
                    help="seconds between heartbeat-file writes")
    ap.add_argument("--peer-timeout", dest="peer_timeout", type=float,
                    default=15.0,
                    help="seconds without peer progress before the gang "
                         "aborts and restarts together")
    ap.add_argument("--telemetry-dir", dest="telemetry_dir", default=None,
                    help="the gang telemetry plane (default: "
                         "<gang-dir>/telemetry): supervisor metrics "
                         "under canonical names, each worker under "
                         "rank-suffixed ones (metrics.rank<r>.jsonl) — "
                         "read back by telemetry/aggregator.py, "
                         "tools/gang_status.py, tools/trace_merge.py")
    ap.add_argument("--no-telemetry", dest="no_telemetry",
                    action="store_true",
                    help="disable the default-on gang telemetry")
    ap.add_argument("--straggler-multiple", dest="straggler_multiple",
                    type=float, default=4.0,
                    help="flag a rank whose effective step time exceeds "
                         "this multiple of the gang median (advisory "
                         "detection only)")
    ap.add_argument("--straggler-consecutive",
                    dest="straggler_consecutive", type=int, default=3,
                    help="consecutive over-threshold observations "
                         "before a straggler verdict")
    ap.add_argument("--gang-transport", dest="gang_transport",
                    default="file", choices=("file", "inproc", "tcp"),
                    help="control-plane backend (runtime/transport.py): "
                         "'file' = shared-directory channels in "
                         "--gang-dir (default, on-disk format "
                         "unchanged); 'inproc' = THREAD workers over "
                         "in-memory channels — no subprocess spawn, so "
                         "64-128-rank chaos campaigns run in seconds "
                         "(durable ledgers still mirror into "
                         "--gang-dir for gang_status; workers share "
                         "ONE checkpoint dir, rank 0 saves); 'tcp' = "
                         "this launcher hosts the gang server and "
                         "workers connect with per-op timeouts, "
                         "retry+backoff, and idempotent delivery")
    ap.add_argument("--net-model", dest="net_model", default=None,
                    help="attach the digital-twin network model "
                         "(runtime/netmodel.py) to the in-proc hub "
                         "(inproc only): 'INNER[:COMPUTE_US"
                         "[:STEP_MB]]' — inner-major nodes of INNER "
                         "ranks, intra-node fast / inter-node slow; "
                         "ranks report MODELED step times (virtual "
                         "seconds, no real sleeps) while liveness "
                         "stays on the real heartbeat clock, and the "
                         "gray fault kinds (--faults "
                         "'degrade_link@SRC-DST:STEP:K,"
                         "flaky_link@SRC-DST:STEP:P,"
                         "bw_collapse@NODE:STEP:K,"
                         "restore_link@SRC-DST:STEP') mutate the "
                         "model's links")
    ap.add_argument("--tx-chaos", dest="tx_chaos", default=None,
                    help="transport-level fault injection forwarded to "
                         "tcp workers (runtime/gang_worker.py): "
                         "'partition@RANK:AFTER_OPS' severs that "
                         "original rank's channel on attempt 0 — the "
                         "connection-loss-is-peer-death chaos proof")
    args = ap.parse_args(argv)
    if args.workers < 1:
        ap.error(f"--workers must be >= 1, got {args.workers}")
    if args.peer_timeout <= 2 * args.heartbeat_interval:
        ap.error("--peer-timeout must exceed two heartbeat intervals")
    if not 0 <= args.min_world <= args.workers:
        ap.error(f"--min-world must be in [0, {args.workers}], got "
                 f"{args.min_world}")
    if args.global_batch < 1:
        ap.error(f"--global-batch must be >= 1, got {args.global_batch}")
    if args.straggler_multiple <= 1.0:
        ap.error("--straggler-multiple must be > 1 (a rank at the "
                 "median is not a straggler)")
    if args.straggler_consecutive < 1:
        ap.error("--straggler-consecutive must be >= 1")
    if args.max_world and args.max_world < args.workers:
        ap.error(f"--max-world must be >= --workers ({args.workers}) "
                 f"or 0 to disable, got {args.max_world}")
    if args.spares < 0:
        ap.error(f"--spares must be >= 0, got {args.spares}")
    if args.straggler_policy == "replace" and args.spares < 1:
        ap.error("--straggler-policy replace needs at least one warm "
                 "spare to promote (--spares >= 1)")
    if args.spares and not args.max_world \
            and args.straggler_policy != "replace":
        ap.error("--spares without a promotion path: spares can only "
                 "be promoted at a grow (--max-world) or replacement "
                 "(--straggler-policy replace) boundary")
    if args.net_model and args.gang_transport != "inproc":
        ap.error("--net-model is the in-proc hub's digital-twin seam; "
                 "use --gang-transport inproc")
    if args.replace_after < 1:
        ap.error(f"--replace-after must be >= 1, got {args.replace_after}")
    if args.tx_chaos and args.gang_transport != "tcp":
        ap.error("--tx-chaos injects at the transport send boundary, "
                 "which only the lossy tcp backend has — it would "
                 "silently never fire under "
                 f"--gang-transport {args.gang_transport}")

    from distributed_machine_learning_tpu.runtime.faults import (
        FaultEvents,
        FaultInjector,
    )
    from distributed_machine_learning_tpu.runtime.supervisor import (
        GangFailure,
        gang_supervise,
    )
    from distributed_machine_learning_tpu.utils.summary import (
        resilience_summary,
    )

    if args.faults:
        try:  # validate before spawning anything
            probe = FaultInjector.parse(args.faults,
                                        horizon=max(args.steps, 2))
        except ValueError as e:
            ap.error(f"--faults: {e}")
        bad_targets = {r for r in probe.targeted_ranks()
                       if r >= args.workers}
        if bad_targets:
            ap.error(
                f"--faults targets rank(s) {sorted(bad_targets)} but the "
                f"gang only has ranks 0..{args.workers - 1} — the fault "
                "would silently never fire"
            )

    # The gang telemetry plane is ON by default: the supervisor writes
    # canonical filenames at the root, each worker rank-suffixed ones
    # beside them — one directory, no append collisions, readable as a
    # cross-rank whole by telemetry/aggregator.py and the tools.
    telemetry = None
    tel_dir = args.telemetry_dir or os.path.join(args.gang_dir,
                                                 "telemetry")
    if not args.no_telemetry:
        from distributed_machine_learning_tpu.telemetry import (
            Telemetry,
            set_telemetry,
        )

        telemetry = Telemetry(tel_dir)
        set_telemetry(telemetry)

    def worker_cmd(rank: int, attempt: int, world: int,
                   orig_rank: int) -> list[str]:
        # Elastic signature: the supervisor passes the CURRENT world
        # size (a shrink reduces it) and the rank's original identity
        # (its checkpoint dir and consumption ledger follow it across
        # renumberings).  No fresh ports needed: the beat-directory
        # protocol is portless.
        cmd = [
            sys.executable, "-m",
            "distributed_machine_learning_tpu.runtime.gang_worker",
            "--rank", str(rank), "--world", str(world),
            "--orig-rank", str(orig_rank), "--attempt", str(attempt),
            "--gang-dir", args.gang_dir, "--ckpt-dir", args.ckpt_dir,
            "--steps", str(args.steps),
            "--save-every", str(args.save_every),
            "--global-batch", str(args.global_batch),
            "--heartbeat-interval", str(args.heartbeat_interval),
            "--peer-timeout", str(args.peer_timeout),
            # The scaling rule anchors at the LAUNCH world: relaunches
            # at other worlds re-derive (batch, lr) from this fixed
            # base point, not from whatever world they wake up in.
            "--scaling-rule", args.scaling_rule,
            "--base-world", str(args.workers),
            "--base-lr", str(args.base_lr),
            "--feature-dim", str(args.feature_dim),
        ]
        if args.faults:
            cmd += ["--faults", args.faults]
        if args.no_telemetry:
            cmd += ["--no-telemetry"]
        else:
            # Workers share ONE telemetry dir; their default instance
            # tag (rank<orig>) keeps the streams collision-safe and
            # stable across shrink renumberings.
            cmd += ["--telemetry-dir", tel_dir]
        return cmd

    def spare_cmd(orig_rank: int, attempt: int) -> list[str]:
        # A warm spare never trains: it only needs its identity, the
        # join channel, and the checkpoint root it prefetches from/into.
        return [
            sys.executable, "-m",
            "distributed_machine_learning_tpu.runtime.gang_worker",
            "--spare", "--rank", str(orig_rank),
            "--world", str(args.workers),  # unused in spare mode
            "--orig-rank", str(orig_rank), "--attempt", str(attempt),
            "--gang-dir", args.gang_dir, "--ckpt-dir", args.ckpt_dir,
            "--heartbeat-interval", str(args.heartbeat_interval),
        ]

    events = FaultEvents()
    # The package's own root goes onto the workers' PYTHONPATH so they
    # import it whatever directory the launcher was started from.
    import distributed_machine_learning_tpu as _pkg

    pkg_root = os.path.dirname(os.path.dirname(os.path.abspath(
        _pkg.__file__
    )))

    # -- control-plane backend (ISSUE 12) -------------------------------
    server = None
    transport = None
    ckpt_dirs = [os.path.join(args.ckpt_dir, f"rank{r}")
                 for r in range(args.workers + args.spares)]
    if args.gang_transport == "tcp":
        # The launcher hosts the gang server (on a pod: rank 0 / the
        # controller); workers get its address on their argv.  The
        # supervisor talks to its OWN server hub directly — it must
        # never compete with the workers for its socket.  Durable
        # ledgers mirror into --gang-dir for post-mortem tooling.
        from distributed_machine_learning_tpu.runtime.transport import (
            TcpGangServer,
        )

        server = TcpGangServer(mirror_dir=args.gang_dir).start()
        transport = server.local_transport(events=events)
        base_worker_cmd = worker_cmd

        def worker_cmd(rank, attempt, world, orig_rank):  # noqa: F811
            cmd = base_worker_cmd(rank, attempt, world, orig_rank) + [
                "--gang-transport", "tcp", "--gang-addr", server.address,
            ]
            if args.tx_chaos:
                cmd += ["--tx-chaos", args.tx_chaos]
            return cmd

        base_spare_cmd = spare_cmd

        def spare_cmd(orig_rank, attempt):  # noqa: F811
            return base_spare_cmd(orig_rank, attempt) + [
                "--gang-transport", "tcp", "--gang-addr", server.address,
            ]
    elif args.gang_transport == "inproc":
        # Thread ranks over in-memory channels: the 64-128-rank
        # campaign mode.  One SHARED checkpoint directory (replicated
        # dp state; rank 0 saves, the commit broadcasts over the hub),
        # durable ledgers mirrored into --gang-dir so gang_status and
        # the consumption audit read the run like any file gang.
        from distributed_machine_learning_tpu.runtime.inproc_worker import (
            InprocGangConfig,
            inproc_worker_cmds,
        )
        from distributed_machine_learning_tpu.runtime.transport import (
            InProcHub,
            InProcTransport,
        )

        hub = InProcHub(mirror_dir=args.gang_dir)
        if args.net_model:
            # The digital-twin seam (round 20): workers report modeled
            # step times, rank 0 advances the virtual clock, and gray
            # faults mutate these links.
            from distributed_machine_learning_tpu.runtime.netmodel import (  # noqa: E501
                NetModel,
            )

            parts = args.net_model.split(":")
            try:
                nm_inner = int(parts[0])
                nm_compute_us = (float(parts[1]) if len(parts) > 1
                                 else 2000.0)
                nm_step_mb = float(parts[2]) if len(parts) > 2 else 4.0
                hub.netmodel = NetModel(
                    args.workers, inner=nm_inner,
                    compute_s=nm_compute_us / 1e6,
                    step_bytes=int(nm_step_mb * 2**20))
            except ValueError as e:
                ap.error(f"bad --net-model spec {args.net_model!r} "
                         f"(expected INNER[:COMPUTE_US[:STEP_MB]]): {e}")
        transport = InProcTransport(hub, events=events)
        cfg = InprocGangConfig(
            ckpt_dir=args.ckpt_dir, steps=args.steps,
            save_every=args.save_every, global_batch=args.global_batch,
            scaling_rule=args.scaling_rule, base_world=args.workers,
            base_lr=args.base_lr, feature_dim=args.feature_dim,
            heartbeat_interval=min(args.heartbeat_interval, 0.1),
            # Modeled pod gangs run hundreds of thread ranks on a few
            # cores: startup alone can exceed the thread-campaign
            # clamp, and their death detection is exit-code/model
            # driven — honor the user's timeout there.
            peer_timeout=(args.peer_timeout if args.net_model
                          else min(args.peer_timeout, 5.0)),
            faults=args.faults,
        )
        worker_cmd, spare_cmd = inproc_worker_cmds(cfg, hub)
        ckpt_dirs = args.ckpt_dir  # shared: one dir for the whole gang
        os.makedirs(args.ckpt_dir, exist_ok=True)

    try:
        final_codes = gang_supervise(
            worker_cmd, args.workers, args.gang_dir,
            # Per-rank layout: spares hold original ids just past the
            # launch world and prefetch into their own rank<orig> dirs,
            # so the dir list covers workers AND spares.  The in-proc
            # campaign mode passes ONE shared directory instead.
            ckpt_dirs=ckpt_dirs,
            max_restarts=args.max_restarts,
            rank_restart_budget=args.rank_restart_budget,
            min_world=args.min_world if args.min_world > 0 else None,
            max_world=args.max_world if args.max_world > 0 else None,
            spares=args.spares, spare_cmd=spare_cmd,
            straggler_policy=args.straggler_policy,
            replace_after=args.replace_after,
            events=events, env=scrubbed_worker_env(pkg_root),
            log_dir=os.path.join(args.gang_dir, "logs"),
            straggler_multiple=args.straggler_multiple,
            straggler_consecutive=args.straggler_consecutive,
            transport=transport,
        )
    except GangFailure as e:
        print(f"gang failed: {e}", file=sys.stderr, flush=True)
        print(resilience_summary(events), flush=True)
        return 1
    finally:
        if server is not None:
            server.stop()
        if telemetry is not None:
            telemetry.close()
    final_world = len(final_codes)
    print(resilience_summary(events), flush=True)
    print(f"gang of {args.workers} finished {args.steps} steps at "
          f"world size {final_world} ({events.gang_restarts} coordinated "
          f"restart(s), {events.gang_shrinks} shrink(s), "
          f"{events.gang_grows} grow(s), {events.spare_promotions} "
          f"spare promotion(s))", flush=True)
    if not args.no_telemetry:
        _print_gang_rollup(tel_dir, args)
    return 0


def _print_gang_rollup(tel_dir: str, args) -> None:
    """Post-run cross-rank summary from the per-rank streams — the
    one-line answer to "was anyone slow?" plus pointers to the deeper
    tools.  Best-effort: a rollup failure must never fail the run it
    summarizes."""
    try:
        from distributed_machine_learning_tpu.telemetry.aggregator import (
            aggregate_gang_metrics,
        )

        rollup = aggregate_gang_metrics(
            tel_dir, multiple=args.straggler_multiple,
            consecutive=args.straggler_consecutive,
        )
    except Exception as e:  # diagnostics-only path
        print(f"[gang] cross-rank rollup unavailable: {e}", flush=True)
        return
    if not rollup.ranks:
        return
    print(f"cross-rank step-time skew (slowest/median): "
          f"p95 {rollup.skew['p95']:.2f}x  max {rollup.skew['max']:.2f}x"
          f" over {len(rollup.steps)} step(s), "
          f"{len(rollup.ranks)} rank stream(s)", flush=True)
    for v in rollup.stragglers:
        print(f"  straggler (offline): rank {v['rank']} at step "
              f"{v['step']} ({v['ratio']:.1f}x median)", flush=True)
    print(f"inspect: python tools/gang_status.py {args.gang_dir}  |  "
          f"python tools/trace_merge.py {tel_dir}", flush=True)


if __name__ == "__main__":
    raise SystemExit(main())
