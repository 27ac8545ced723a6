"""Shared runner behind the four entrypoints.

The reference's four parts are copy-pasted clones varying only in the
gradient-sync layer (SURVEY.md §1); here one runner takes the strategy
(and each part's constants) as parameters.  The reference CLI flags are
kept verbatim (north-star): ``--master-ip`` (default ``127.0.1.1:8000``),
``--rank`` (0), ``--num-nodes`` (1) — ``part2/2a/main.py:210-218``.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import os
from typing import Any, Callable

import jax

from distributed_machine_learning_tpu.data.cifar10 import load_cifar10
from distributed_machine_learning_tpu.data.distributed_loader import (
    DistributedBatchLoader,
)
from distributed_machine_learning_tpu.data.loader import BatchLoader
from distributed_machine_learning_tpu.models.registry import get_model, list_models
from distributed_machine_learning_tpu.parallel.strategies import get_strategy
from distributed_machine_learning_tpu.runtime.distributed import (
    DEFAULT_MASTER_IP,
    initialize_from_flags,
)
from distributed_machine_learning_tpu.runtime.mesh import make_mesh, replicate
from distributed_machine_learning_tpu.telemetry import startup
from distributed_machine_learning_tpu.train.loop import evaluate, train_epoch
from distributed_machine_learning_tpu.train.sgd import SGDConfig
from distributed_machine_learning_tpu.train.state import TrainState
from distributed_machine_learning_tpu.train.step import (
    make_eval_step,
    make_train_step,
    shard_batch,
)
from distributed_machine_learning_tpu.utils.logging import rank0_print
from distributed_machine_learning_tpu.utils.profiling import MetricsLogger, trace

SEED = 69143  # part1/main.py:17
EVAL_BATCH = 256


@dataclasses.dataclass(frozen=True)
class RunResult:
    """What a finished run hands back to a caller driving ``main(argv)``
    in-process (``chip_smoke.py``): the final state, the compiled step
    that produced it and the batch placement, so the caller can check
    where things live and lower the very step that ran."""

    state: Any
    train_step: Callable
    place_batch: Callable | None


def device_banner(pallas: bool) -> str:
    """``platform=… device_kind=…`` for the run banners, plus
    ``pallas=compiled|interpreted`` when the run may dispatch a Pallas
    kernel — so a TPU that failed to initialise (JAX then picks the CPU
    with a warning, and every kernel silently interprets) is visible in
    the first line of the log."""
    dev = jax.devices()[0]
    out = f"platform={dev.platform} device_kind={dev.device_kind!r}"
    if pallas:
        from distributed_machine_learning_tpu.ops.pallas.common import (
            interpret,
        )

        out += f" pallas={'interpreted' if interpret() else 'compiled'}"
    return out


def add_node_flags(parser: argparse.ArgumentParser) -> None:
    """The reference's exact connectivity flags (part2/2a/main.py:210-218)
    — one definition shared by every entrypoint parser."""
    parser.add_argument("--master-ip", dest="master_ip", default=DEFAULT_MASTER_IP,
                        type=str, help="coordinator address host:port")
    parser.add_argument("--rank", default=0, type=int, help="process rank")
    parser.add_argument("--num-nodes", dest="num_nodes", default=1, type=int,
                        help="number of processes")


def add_telemetry_flags(parser: argparse.ArgumentParser) -> None:
    """The streaming-telemetry flags, shared by the CNN parts and the LM
    entrypoint (one definition, like ``add_node_flags``)."""
    parser.add_argument("--telemetry-dir", dest="telemetry_dir",
                        default=None, type=str,
                        help="stream run telemetry here: metrics.jsonl "
                             "(per-step rows, attempt-tagged, fsynced "
                             "every --telemetry-flush-every rows — "
                             "crash-safe, restarts append), trace.json "
                             "(Chrome trace of driver phases: data_wait/"
                             "place_batch/step_dispatch/device_block/"
                             "checkpoint_save/eval/restart_attempt; open "
                             "in ui.perfetto.dev), registry.json + "
                             "metrics.prom (final counters/quantiles). "
                             "Off by default: zero per-step cost")
    parser.add_argument("--telemetry-flush-every",
                        dest="telemetry_flush_every", default=20, type=int,
                        help="flush+fsync the telemetry sinks every N "
                             "rows/events (default 20); lower = smaller "
                             "crash-loss window, more write syscalls")


def add_gang_flags(parser: argparse.ArgumentParser) -> None:
    """Gang-coordination flags (``runtime/coordinator.py``): multi-host
    runs that share a filesystem get heartbeat-based peer-failure
    detection and coordinated abort, so one dead rank restarts the gang
    instead of hanging it forever."""
    parser.add_argument("--gang-dir", dest="gang_dir", default=None,
                        type=str,
                        help="shared directory for gang coordination "
                             "(heartbeat files, abort latch, restore-"
                             "point records — runtime/coordinator.py); "
                             "enables peer-failure detection: a rank "
                             "dead/stalled past --peer-timeout aborts "
                             "the whole gang (exit 43) so an external "
                             "gang supervisor (cli/gang.py, "
                             "gang_supervise) can relaunch all ranks "
                             "together from the agreed restore point. "
                             "Off by default")
    parser.add_argument("--heartbeat-interval", dest="heartbeat_interval",
                        default=1.0, type=float,
                        help="seconds between heartbeat-file writes "
                             "(with --gang-dir; default 1.0)")
    parser.add_argument("--peer-timeout", dest="peer_timeout",
                        default=60.0, type=float,
                        help="seconds without peer progress before this "
                             "rank declares the gang dead and aborts "
                             "(with --gang-dir; default 60; set it above "
                             "the first step's XLA compile time)")


def make_flag_parser(description: str) -> argparse.ArgumentParser:
    """The reference's exact flag surface (part2/2a/main.py:210-218)."""
    parser = argparse.ArgumentParser(description=description)
    add_node_flags(parser)
    add_gang_flags(parser)
    parser.add_argument("--data-root", default="./data", type=str)
    parser.add_argument("--epochs", default=1, type=int)  # range(1): part1/main.py:123
    parser.add_argument("--compute-dtype", default="float32",
                        choices=["float32", "bfloat16"],
                        help="trunk compute dtype (bfloat16 targets the MXU)")
    # Extensions beyond the reference surface (defaults reproduce it).
    parser.add_argument("--model", default="vgg11", type=str,
                        choices=list_models(),
                        help="model to train; default reproduces the "
                             "reference's VGG11")
    parser.add_argument("--max-iters", default=40, type=int,
                        help="training iteration cap (reference: 40)")
    parser.add_argument("--batch-size", default=None, type=int,
                        help="override the part's per-worker batch size")
    parser.add_argument("--eval-batches", default=None, type=int,
                        help="cap eval batches (default: full test set)")
    parser.add_argument("--eval-batch-size", dest="eval_batch_size",
                        default=EVAL_BATCH, type=int,
                        help="eval batch size (default 256; the compile "
                             "cost of the eval program scales with it on "
                             "CPU hosts, so short smoke runs want it small)")
    parser.add_argument("--ckpt-dir", default=None, type=str,
                        help="checkpoint directory; saves TrainState after "
                             "each epoch (off by default — reference parity)")
    parser.add_argument("--async-ckpt", dest="async_ckpt",
                        action="store_true",
                        help="write checkpoints asynchronously (orbax "
                             "background thread; train/checkpoint.py::"
                             "AsyncCheckpointWriter) — training continues "
                             "while the save serializes; the run waits for "
                             "the last save before exiting")
    parser.add_argument("--resume", nargs="?", const="latest", default=None,
                        choices=["latest", "auto"],
                        help="resume weights/optimizer/step from the latest "
                             "complete checkpoint in --ckpt-dir; the run then "
                             "trains --epochs further epochs (the epoch count "
                             "is not offset by prior progress).  '--resume "
                             "auto' additionally supervises the run: on a "
                             "stall, crash, or preemption it restores the "
                             "newest complete checkpoint and continues, up "
                             "to --max-restarts times (runtime/supervisor.py)")
    parser.add_argument("--max-restarts", dest="max_restarts", default=3,
                        type=int,
                        help="with --resume auto: restore-and-continue this "
                             "many times before giving up (default 3)")
    parser.add_argument("--keep-last-n", dest="keep_last_n", default=None,
                        type=int,
                        help="garbage-collect all but the newest N complete "
                             "checkpoints after each save (supervised long "
                             "runs checkpoint often; default keeps "
                             "everything).  The newest complete checkpoint "
                             "is never deleted")
    parser.add_argument("--guard-nonfinite", dest="guard_nonfinite",
                        action="store_true",
                        help="compile a non-finite-gradient guard into the "
                             "train step: a NaN/Inf gradient skips that "
                             "update (state unchanged, step not counted) "
                             "instead of poisoning the params; skips are "
                             "counted in the resilience summary")
    parser.add_argument("--loader-retries", dest="loader_retries", default=0,
                        type=int,
                        help="retry the training data iterator this many "
                             "times on exceptions (exponential backoff; a "
                             "batch failing twice is skipped — "
                             "data/retry.py); 0 disables")
    parser.add_argument("--faults", default=None, type=str,
                        help="deterministic fault injection spec, e.g. "
                             "'nan@2,raise@4,stall@7:2.5,kill_ckpt@1' "
                             "(runtime/faults.py; also read from the "
                             "DML_FAULTS env var); chaos-testing only, "
                             "off by default")
    parser.add_argument("--trace-dir", default=None, type=str,
                        help="write a jax.profiler trace of the training "
                             "loop here (view with TensorBoard/Perfetto)")
    parser.add_argument("--metrics-file", default=None, type=str,
                        help="write per-step metrics (step, loss, iteration "
                             "seconds) here; .csv for CSV, else JSONL "
                             "(JSONL streams to disk as rows land — a "
                             "crash keeps everything already flushed)")
    add_telemetry_flags(parser)
    parser.add_argument("--loader", default="auto",
                        choices=["auto", "python", "native"],
                        help="batch loader backend: 'native' is the C++ "
                             "prefetching worker (native/dataloader.cc), "
                             "'python' the pure-Python loader, 'auto' "
                             "native-if-buildable (identical batch streams "
                             "either way)")
    parser.add_argument("--lr-schedule", dest="lr_schedule", default="constant",
                        choices=["constant", "cosine", "step"],
                        help="learning-rate schedule (train/schedule.py); "
                             "'constant' reproduces the reference's fixed "
                             "lr=0.1, 'cosine' adds linear warmup + cosine "
                             "decay over the run, 'step' decays 10x at 50%% "
                             "and 75%% of the run")
    parser.add_argument("--warmup-steps", dest="warmup_steps", default=0,
                        type=int, help="warmup steps for --lr-schedule=cosine")
    parser.add_argument("--clip-norm", dest="clip_norm", default=None,
                        type=float,
                        help="clip the (synced) gradient to this global L2 "
                             "norm before the update (off by default). "
                             "Clips whatever the sync strategy produced: "
                             "part2a/2b SUM gradients over the world "
                             "(reference semantics, SURVEY.md §2.4), so "
                             "their clip engages world-size-times earlier "
                             "than part3's mean gradient — and once it "
                             "engages, a clipped SUM equals a clipped "
                             "mean, cancelling the SUM strategies' "
                             "effective-LR scaling")
    from distributed_machine_learning_tpu.train.optimizers import (
        optimizer_names,
    )

    parser.add_argument("--optimizer", default="sgd", choices=optimizer_names(),
                        help="'sgd' reproduces the reference "
                             "(lr=0.1/momentum/wd — part1/main.py:120-121); "
                             "'lars' adds layer-wise adaptive rate scaling "
                             "for large global batches (train/lars.py); "
                             "'adamw' is the decoupled-decay Adam "
                             "(train/adamw.py)")
    parser.add_argument("--fused-update", dest="fused_update",
                        action="store_true",
                        help="run the AdamW update as the fused one-pass "
                             "Pallas kernel (ops/pallas/fused_adamw.py): "
                             "moment update, bias correction, weight "
                             "decay, parameter update and the dtype cast "
                             "in-register per tile — the round-13 "
                             "update-phase lever; --optimizer adamw only "
                             "(documented-ulp parity with the reference "
                             "update)")
    parser.add_argument("--wire-dtype", dest="wire_dtype", default=None,
                        choices=["bfloat16"],
                        help="DEPRECATED: use --ring-compress bf16 (this "
                             "is the cast-only wire compression, kept for "
                             "compatibility)")
    parser.add_argument("--ring-compress", dest="ring_compress",
                        default="none",
                        choices=["none", "bf16", "int8", "topk"],
                        help="ring all-reduce wire compression (part3 "
                             "ring only; ops/ring.py): 'bf16' casts each "
                             "hop's payload (2x fewer bytes, no residual "
                             "correction), 'int8' is per-chunk symmetric "
                             "int8 + fp32 scale fused into each hop (~4x "
                             "fewer bytes), 'topk' sends only the "
                             "largest --ring-topk-frac of each chunk "
                             "(values+indices).  int8/topk carry an "
                             "error-feedback residual across steps "
                             "(EF-SGD) unless --ring-no-error-feedback")
    parser.add_argument("--ring-codec-impl", dest="ring_codec_impl",
                        default="xla", choices=["xla", "pallas"],
                        help="implementation of the int8 ring codec "
                             "(round 13): 'pallas' runs each hop's "
                             "dequantize-add-requantize and the EF "
                             "residual as fused in-register kernels "
                             "(ops/pallas/ring_codec.py) — bitwise-"
                             "identical to 'xla', no dequantized "
                             "partial in HBM; only --ring-compress "
                             "int8 has kernels (bf16/topk keep the "
                             "XLA path)")
    parser.add_argument("--ring-topk-frac", dest="ring_topk_frac",
                        default=0.125, type=float,
                        help="fraction of each ring chunk kept by "
                             "--ring-compress topk (default 0.125 = 4x "
                             "fewer wire bytes at fp32 values + int32 "
                             "indices)")
    parser.add_argument("--ring-no-error-feedback",
                        dest="ring_error_feedback", action="store_false",
                        help="disable the error-feedback residual for "
                             "--ring-compress int8/topk (ablation only: "
                             "the dropped compression error is then lost "
                             "instead of re-injected next step)")
    parser.add_argument("--ring-topology", dest="ring_topology",
                        default=None, metavar="INNERxOUTER",
                        help="topology-aware hierarchical ring (part3 "
                             "ring only; ops/topology.py): factor the "
                             "data axis as INNERxOUTER (e.g. 2x4 = "
                             "2-chip nodes × 4 nodes; the product must "
                             "equal the world size) and all-reduce as "
                             "reduce-scatter on the fast inner axis, a "
                             "--ring-compress'd ring on the slow outer "
                             "axis over 1/INNER of the data (inter-node "
                             "traffic drops ~INNER-fold), all-gather "
                             "back down; small buckets take a recursive "
                             "halving-doubling latency path.  A 1-sized "
                             "axis degenerates to the flat ring")
    parser.add_argument("--dist-eval", dest="dist_eval", action="store_true",
                        help="shard evaluation batches over the mesh "
                             "(pmean/psum reductions) instead of the "
                             "reference's every-rank-evaluates-everything "
                             "protocol; identical results, N-fold faster")
    parser.add_argument("--watchdog-timeout", dest="watchdog_timeout",
                        default=0, type=float,
                        help="seconds without a completed step before the "
                             "watchdog (runtime/resilience.py) declares a "
                             "stall and dumps thread stacks — detects hung "
                             "collectives (a dead peer leaves the reference "
                             "blocked forever, SURVEY.md §5); 0 disables. "
                             "Set it above the first step's XLA compile "
                             "time (~20-40s cold)")
    parser.add_argument("--local-loss", dest="local_loss", action="store_true",
                        help="print each device's own shard loss instead of "
                             "the global mean — the reference's per-rank "
                             "print surface (part2/2a/main.py:58-61); "
                             "distributed parts only")
    parser.add_argument("--unsync-bn", dest="unsync_bn", action="store_true",
                        help="per-device BatchNorm running stats (the "
                             "reference part3's documented quirk: per-node "
                             "stats, <1%% cross-node accuracy drift — "
                             "part3/model.py:24, group25.pdf p.3-4); "
                             "default axis-syncs the stats")
    parser.add_argument("--grad-accum", dest="grad_accum", default=1, type=int,
                        help="split each per-device batch into this many "
                             "sequential microbatches, accumulating "
                             "gradients for one update (accum-fold lower "
                             "activation memory; identical update when "
                             "augmentation is off — with augmentation each "
                             "microbatch draws its own crops/flips, and BN "
                             "stats update per microbatch)")
    return parser


def make_schedule(args, learning_rate: float, start_step: int = 0):
    """Build the ``step -> lr`` schedule the flags describe (None for the
    reference's fixed rate).

    ``start_step``: the state's step counter at run start (non-zero after
    ``--resume``).  The horizon covers *this run's* ``max_iters × epochs``
    from there — otherwise a resumed cosine run would start past its own
    total_steps and train at end_lr (zero) throughout.
    """
    from distributed_machine_learning_tpu.train.schedule import (
        step_decay,
        warmup_cosine,
    )

    total = max(args.max_iters * args.epochs, 1)
    if args.lr_schedule == "cosine":
        # parse_flags guarantees 0 <= warmup_steps < total.
        base = warmup_cosine(learning_rate, args.warmup_steps, total)
    elif args.lr_schedule == "step":
        base = step_decay(
            learning_rate, boundaries=(total // 2, (3 * total) // 4)
        )
    else:
        return None
    if start_step:
        return lambda step: base(step - start_step)
    return base


def parse_flags(parser: argparse.ArgumentParser, argv=None) -> argparse.Namespace:
    """parse_args + cross-flag validation (fail at parse time, before any
    distributed runtime spin-up)."""
    args = parser.parse_args(argv)
    if args.resume and not args.ckpt_dir:
        parser.error("--resume requires --ckpt-dir")
    if args.max_restarts < 0:
        parser.error(f"--max-restarts must be >= 0, got {args.max_restarts}")
    if args.keep_last_n is not None and args.keep_last_n < 1:
        parser.error(f"--keep-last-n must be >= 1, got {args.keep_last_n}")
    if args.loader_retries < 0:
        parser.error(
            f"--loader-retries must be >= 0, got {args.loader_retries}"
        )
    if args.faults:
        from distributed_machine_learning_tpu.runtime.faults import (
            FaultInjector,
        )

        try:  # validate the spec at parse time, before any runtime spin-up
            FaultInjector.parse(args.faults)
        except ValueError as e:
            parser.error(f"--faults: {e}")
    if args.clip_norm is not None and args.clip_norm <= 0:
        parser.error(f"--clip-norm must be positive, got {args.clip_norm}")
    frac = getattr(args, "ring_topk_frac", 0.125)
    if not 0.0 < frac <= 1.0:
        parser.error(f"--ring-topk-frac must be in (0, 1], got {frac}")
    if getattr(args, "ring_topology", None):
        from distributed_machine_learning_tpu.ops.topology import (
            parse_topology,
        )

        try:  # malformed/zero-axis specs die at parse time; the
            # world-equality half runs once the mesh exists (run_part)
            parse_topology(args.ring_topology)
        except ValueError as e:
            parser.error(f"--ring-topology: {e}")
    if args.grad_accum < 1:
        parser.error(f"--grad-accum must be >= 1, got {args.grad_accum}")
    if args.warmup_steps < 0:
        parser.error(f"--warmup-steps must be >= 0, got {args.warmup_steps}")
    if getattr(args, "telemetry_flush_every", 20) < 1:
        parser.error(
            f"--telemetry-flush-every must be >= 1, got "
            f"{args.telemetry_flush_every}"
        )
    if getattr(args, "gang_dir", None):
        hb = getattr(args, "heartbeat_interval", 1.0)
        if hb <= 0:
            parser.error(
                f"--heartbeat-interval must be > 0, got {hb}"
            )
        if getattr(args, "peer_timeout", 60.0) <= 2 * hb:
            parser.error(
                "--peer-timeout must exceed two heartbeat intervals "
                "(a single delayed write would read as a death)"
            )
        if getattr(args, "async_ckpt", False):
            # Restore-point records are written when a save RETURNS
            # complete; the async writer commits later, so no rank
            # would ever record a step and the election would silently
            # never elect — ranks could then resume from different
            # steps after a gang restart.
            parser.error(
                "--gang-dir requires the synchronous checkpoint path "
                "(drop --async-ckpt): the restore-point election needs "
                "saves recorded at commit time"
            )
    if args.lr_schedule == "cosine":
        total = args.max_iters * args.epochs
        if args.warmup_steps >= total:
            parser.error(
                f"--warmup-steps {args.warmup_steps} must be shorter than "
                f"the run (max_iters × epochs = {total} steps): the rate "
                "would never reach its peak"
            )
    return args


def init_model_and_state(model, seed: int = SEED, config: SGDConfig | None = None):
    """Initialize once from the shared seed → identical weights everywhere,
    the property the reference gets by seeding every rank before building
    the model (``part2/2a/main.py:199``, SURVEY.md §2.5)."""
    with startup.span("startup.build.init_state"):
        rng = jax.random.PRNGKey(seed)
        init_rng, state_rng = jax.random.split(rng)
        variables = model.init(init_rng, jax.numpy.zeros((1, 32, 32, 3)),
                               train=False)
        state = TrainState.create(
            params=variables["params"],
            batch_stats=variables.get("batch_stats"),
            rng=state_rng,
            config=config,
        )
        startup.record().note(params=startup.tree_size(state.params))
    return state


def run_part(
    strategy_name: str,
    per_rank_batch: int,
    use_bn: bool,
    args,
    strategy_kwargs: dict | None = None,
) -> RunResult:
    """Train `args.model` (default VGG-11) on CIFAR-10 for `args.epochs`
    under one sync strategy."""
    import jax.numpy as jnp

    from distributed_machine_learning_tpu.runtime.compile_cache import (
        configure_compile_cache,
    )
    from distributed_machine_learning_tpu.runtime.faults import FaultEvents
    from distributed_machine_learning_tpu.telemetry import (
        set_telemetry,
        telemetry_from_flags,
    )

    record = startup.record()
    record.imports_done()
    with record.span("startup.runtime"):
        configure_compile_cache()
        # Streaming mode: rows hit the disk as they land (rank-0 gated,
        # periodic fsync) instead of only at exit — a crash keeps history.
        # Append only when this run CONTINUES prior work (--resume): a
        # restart then extends the survivor rows.  A fresh run truncates,
        # the historical semantics — appending would silently mix
        # unrelated runs in one file.
        metrics = (
            MetricsLogger(
                path=args.metrics_file,
                flush_every=getattr(args, "telemetry_flush_every", 20),
                append=bool(args.resume))
            if args.metrics_file else None
        )
        telemetry = telemetry_from_flags(args)
        prev_telemetry = None
        if telemetry is not None:
            # Installed process-wide so the deep layers (loader queue gauge,
            # retry counters, checkpoint spans, FaultEvents mirror,
            # supervisor restart spans) see it without signature threading.
            prev_telemetry = set_telemetry(telemetry)
            from distributed_machine_learning_tpu.models.vgg import _cfg
            from distributed_machine_learning_tpu.utils.flops import (
                vgg_train_flops_per_image,
            )

            if args.model.upper() in _cfg:
                # MFU cost model (utils/flops.py); non-VGG models log
                # throughput without MFU rather than against a wrong model.
                telemetry.flops_per_example = vgg_train_flops_per_image(
                    _cfg[args.model.upper()]
                )
        ctx = initialize_from_flags(args.master_ip, args.rank, args.num_nodes)
        n_devices = jax.device_count()  # the first touch takes the chip
        record.note(devices=n_devices)
    preemption = None
    watchdog = None
    ckpt_writer = None
    coordinator = None
    run_completed = False
    events = FaultEvents()
    show_resilience = False
    try:
        with record.span("startup.build", parallel=strategy_name,
                         devices=n_devices):
            distributed = strategy_name != "none"
            mesh = make_mesh() if distributed else None
            world = mesh.shape["batch"] if mesh is not None else 1
            # The two Pallas paths a part can select (int8 is the only
            # codec with kernels, AdamW the only fused update).
            fused_codec = (
                strategy_name == "ring"
                and getattr(args, "ring_codec_impl", "xla") == "pallas"
                and getattr(args, "ring_compress", "none") == "int8"
            )
            fused_update = (getattr(args, "fused_update", False)
                            and args.optimizer == "adamw")
            # Reference banner (part2/2a/main.py:200-203) + the device.
            rank0_print(
                f"strategy={strategy_name} world_size={world} "
                f"devices={n_devices} processes={jax.process_count()} "
                + device_banner(fused_codec or fused_update)
            )

            compute_dtype = (jnp.bfloat16 if args.compute_dtype == "bfloat16"
                             else jnp.float32)
            model = get_model(args.model, use_bn=use_bn,
                              compute_dtype=compute_dtype)
            from distributed_machine_learning_tpu.train.optimizers import (
                get_optimizer,
            )

            opt_config = get_optimizer(args.optimizer)[0]()
            if getattr(args, "fused_update", False):
                from distributed_machine_learning_tpu.train.adamw import (
                    AdamWConfig,
                )

                if isinstance(opt_config, AdamWConfig):
                    import dataclasses

                    opt_config = dataclasses.replace(opt_config, fused=True)
                else:
                    rank0_print(
                        "WARNING: --fused-update applies to --optimizer adamw "
                        f"only; {args.optimizer!r} runs its reference update."
                    )
            state = init_model_and_state(model, config=opt_config)

            # Unsynced-BN quirk mode (reference part3 parity: per-node running
            # stats — part3/model.py:24, group25.pdf p.3-4).  Decided BEFORE
            # --resume so the checkpoint-restore template carries the stacked
            # [world, C] stats layout a quirk-mode checkpoint was saved with.
            unsync_bn = bool(getattr(args, "unsync_bn", False))
            if unsync_bn and mesh is None:
                rank0_print("WARNING: --unsync-bn has no effect on the "
                            "single-device part1 path (one device, one set of "
                            "stats).")
                unsync_bn = False
            if unsync_bn and not state.batch_stats:
                unsync_bn = False  # BN-free model: nothing to (un)sync
            from distributed_machine_learning_tpu.train.step import (
                broadcast_bn_stats,
            )

            def _maybe_stack(st):
                return broadcast_bn_stats(st, world) if unsync_bn else st

            def _replicate(st):
                return replicate(st, mesh) if mesh is not None else st

            state = _replicate(_maybe_stack(state))

        def restore_latest(fresh_state):
            """State from the newest complete checkpoint in --ckpt-dir
            (or ``fresh_state`` when none exists).  Factored so the
            supervised mode (--resume auto) can re-run it after every
            restart — the auto-resume leg of the skip/retry/restart
            ladder."""
            state = fresh_state
            from distributed_machine_learning_tpu.train.checkpoint import (
                NoRestorableCheckpointError,
                checkpoint_chain_report,
                checkpoint_config,
                latest_checkpoint,
                restore_checkpoint,
            )

            if not args.ckpt_dir:
                raise ValueError("--resume requires --ckpt-dir")
            latest = latest_checkpoint(args.ckpt_dir, events=events)
            if latest is None:
                report = checkpoint_chain_report(args.ckpt_dir)
                if any(v.startswith("quarantined") for _, v in report):
                    # Real checkpoints existed and every one was
                    # CONDEMNED (quarantined — bad digests, or a gang
                    # election verdict): silently training from scratch
                    # over a dir full of condemned checkpoints is how
                    # runs lose weeks — fail loudly with the
                    # per-candidate verdicts.  Incomplete-only leftovers
                    # (a crash during the first save) still start from
                    # scratch silently: that IS the resume guarantee.
                    lines = "\n".join(f"  {p}: {v}" for p, v in report)
                    raise NoRestorableCheckpointError(
                        f"--resume: no restorable checkpoint under "
                        f"{args.ckpt_dir} — every candidate in the "
                        f"fallback chain is unusable:\n{lines}\n"
                        "(remove --resume, or point --ckpt-dir at a "
                        "clean directory, to start from scratch)"
                    )
                rank0_print(f"No checkpoint under {args.ckpt_dir}; "
                            "starting from scratch.")
            else:
                # The restore template must use the *saved* momentum
                # layout (AdamW's {"mu","nu"} dict vs SGD's buffer tree);
                # a cross-optimizer resume rebuilds it below.
                saved_cfg = checkpoint_config(latest)
                abstract = (
                    state
                    if type(saved_cfg) is type(opt_config)
                    else _maybe_stack(
                        init_model_and_state(model, config=saved_cfg)
                    )
                )
                # In quirk mode, pick the restore template by the SAVED
                # stats layout — a metadata read (no array IO) — rather
                # than retrying on a blanket except, which would also
                # mask unrelated restore failures (corrupt checkpoint,
                # dtype/optimizer mismatch) behind a second confusing
                # error.
                restore_against = abstract
                stack_after = False
                if unsync_bn:
                    from distributed_machine_learning_tpu.train.checkpoint import (  # noqa: E501
                        checkpoint_array_shapes,
                    )

                    saved_stats = checkpoint_array_shapes(latest).get(
                        "batch_stats"
                    ) or {}
                    saved_leaves = jax.tree_util.tree_leaves(
                        saved_stats, is_leaf=lambda x: isinstance(x, tuple)
                    )
                    want_leaves = jax.tree_util.tree_leaves(
                        abstract.batch_stats
                    )
                    if (saved_leaves and want_leaves
                            and len(saved_leaves[0])
                            < want_leaves[0].ndim):
                        # The checkpoint predates --unsync-bn (plain [C]
                        # stats): restore against the plain template,
                        # then enter quirk mode by stacking the restored
                        # stats.
                        restore_against = init_model_and_state(
                            model,
                            config=saved_cfg
                            if type(saved_cfg) is not type(opt_config)
                            else opt_config,
                        )
                        stack_after = True
                state = restore_checkpoint(
                    latest, abstract_state=restore_against,
                    files_verified=True,  # latest_checkpoint just swept
                )
                if stack_after:
                    state = _maybe_stack(state)
                rank0_print(f"Resumed from {latest} (step "
                            f"{int(jax.device_get(state.step))})")
                want = opt_config
                if type(state.config) is not type(want):
                    # The checkpoint records its optimizer config class;
                    # SGD's (raw-gradient-scale) and LARS's
                    # (lr·trust·ratio-scaled) momentum buffers are not
                    # interchangeable, so switching optimizers at resume
                    # resets them rather than misapplying them.
                    rank0_print(
                        f"WARNING: checkpoint was trained with "
                        f"{type(state.config).__name__} but this run uses "
                        f"--optimizer {args.optimizer}; resetting momentum "
                        "buffers (params/step/stats are kept)."
                    )
                    from distributed_machine_learning_tpu.train.optimizers import (
                        init_for_config,
                    )

                    state = state.replace(
                        config=want,
                        # Fresh buffers in the NEW optimizer's layout —
                        # zeroing the old tree would hand e.g. an SGD
                        # buffer tree to AdamW's {"mu","nu"} update.
                        momentum=init_for_config(want)(state.params),
                    )
                state = _replicate(state)
            return state

        if args.resume:
            with record.span("startup.resume"):
                state = restore_latest(state)
        strategy_kwargs = dict(strategy_kwargs or {})
        ring_compress = getattr(args, "ring_compress", "none")
        if args.wire_dtype:
            # --wire-dtype is subsumed by --ring-compress bf16 (same
            # cast-only wire path); keep it working, steer users over.
            rank0_print(
                "WARNING: --wire-dtype is deprecated; use --ring-compress "
                "bf16 (cast-only) or --ring-compress int8/topk for the "
                "error-feedback compressed ring."
            )
            if ring_compress == "none":
                ring_compress = "bf16"
        ring_topology = getattr(args, "ring_topology", None)
        ring_codec_impl = getattr(args, "ring_codec_impl", "xla")
        if strategy_name == "ring":
            if ring_compress != "none":
                strategy_kwargs["compress"] = ring_compress
                strategy_kwargs["topk_frac"] = getattr(
                    args, "ring_topk_frac", 0.125
                )
                strategy_kwargs["error_feedback"] = getattr(
                    args, "ring_error_feedback", True
                )
            if ring_codec_impl != "xla":
                if ring_compress != "int8":
                    rank0_print(
                        "WARNING: --ring-codec-impl pallas has kernels for "
                        "--ring-compress int8 only; "
                        f"{ring_compress!r} runs the XLA path."
                    )
                strategy_kwargs["codec_impl"] = ring_codec_impl
            if ring_topology:
                strategy_kwargs["topology"] = ring_topology
        elif ring_compress != "none":
            rank0_print(
                "WARNING: --ring-compress/--wire-dtype only apply to the "
                f"ring strategy (part3); strategy {strategy_name!r} runs "
                "uncompressed."
            )
        if strategy_name != "ring" and ring_topology:
            rank0_print(
                "WARNING: --ring-topology only applies to the ring "
                f"strategy (part3); strategy {strategy_name!r} runs the "
                "flat collective."
            )
        # Reference part1 prints a torchsummary table before training
        # (part1/main.py:118; the ~9.2M-param total the report leans on).
        from distributed_machine_learning_tpu.utils.summary import model_summary

        rank0_print(model_summary(state.params, title=args.model))

        strategy = get_strategy(strategy_name, **strategy_kwargs)
        if hasattr(strategy, "topology_for"):
            # Fail the factorization mismatch HERE — before any data
            # loading or compilation — with the flag-level message
            # (inner×outer must equal the mesh world; topology_for is
            # also what the train step resolves per call, so a passing
            # check here is the same check the program will use).
            strategy.topology_for(world)
        if args.resume and getattr(strategy, "stateful", False):
            # The EF residual is per-device step-wrapper state, not part
            # of TrainState: a resumed run starts it at zero (one step
            # of EF warmup), so its trajectory can differ slightly from
            # an uninterrupted run's — say so rather than silently
            # weakening the resume-exactness story.
            rank0_print(
                "NOTE: error-feedback residuals (--ring-compress "
                f"{strategy.compress}) are not checkpointed; resuming "
                "with a zero residual (one step of EF warmup)."
            )
        if (telemetry is not None and mesh is not None
                and hasattr(strategy, "wire_bytes_per_step")):
            # Static per-step wire accounting: the ring's bytes-on-the-
            # wire are a compile-time property of (param count, world,
            # bucket size, codec), so the counter increment is computed
            # once here and applied per step by the train loop —
            # gang benches and tools/trace_summary.py read the totals
            # back out of registry.json.
            n_elems = sum(
                int(l.size) for l in jax.tree_util.tree_leaves(state.params)
            )
            # Split by mesh axis (round 11): the flat ring counts under
            # {axis="flat"}; a --ring-topology run counts inner
            # (intra-node) and outer (inter-node) bytes separately so
            # tools/trace_summary.py can show the bottleneck-link
            # reduction, not just the total.
            telemetry.step_counters["ring_wire_bytes"] = [
                ({"axis": ax}, b)
                for ax, b in strategy.wire_bytes_by_axis(
                    n_elems, world
                ).items()
                if b
            ]
            telemetry.registry.gauge("ring_compression_ratio").set(
                strategy.compression_ratio(n_elems, world)
            )
        if telemetry is not None:
            # Which implementation was REQUESTED, per step in the
            # registry/trace (round 13).  The counters follow the flags,
            # not the lowered program: whether the requested kernels
            # were compiled or interpreted is the banner's ``pallas=``
            # word (``device_banner``).
            if fused_codec:
                telemetry.step_counters["fused_codec_steps"] = 1
            if fused_update:
                telemetry.step_counters["fused_update_steps"] = 1
        with record.span("startup.build", parallel=strategy_name,
                         devices=n_devices):
            train_step = make_train_step(
                model, strategy, mesh=mesh,
                schedule=make_schedule(
                    args, state.config.learning_rate,
                    start_step=int(jax.device_get(state.step)),
                ),
                clip_norm=args.clip_norm,
                accum_steps=args.grad_accum,
                optimizer=args.optimizer,
                sync_bn=not unsync_bn,
                local_loss=bool(getattr(args, "local_loss", False))
                and mesh is not None,
                guard_nonfinite=bool(getattr(args, "guard_nonfinite", False)),
            )
            eval_step = make_eval_step(model)
            if unsync_bn and state.batch_stats:
                # Quirk-mode stats are [world, *S]-stacked; the single-device
                # eval step can't consume them — evaluate with device 0's row
                # (each reference node evaluates with its own stats; rank 0's
                # is the one whose prints we surface).
                base_eval = eval_step

                def eval_step(params, stats, images, labels):
                    stats0 = jax.tree_util.tree_map(lambda s: s[0], stats)
                    return base_eval(params, stats0, images, labels)
            if args.dist_eval and mesh is None:
                rank0_print(
                    "WARNING: --dist-eval has no effect for the single-device "
                    "part1 path (no mesh to shard over); evaluating on one "
                    "device."
                )
            if args.dist_eval and mesh is not None:
                # Sharded eval for world-size-divisible batches; the single
                # device step covers the test set's short final batch (the
                # reference instead evaluates everything on every rank —
                # SURVEY.md §3.5).
                # sync_bn=False makes the sharded eval read each device's own
                # row of quirk-mode stacked stats (make_eval_step docstring).
                dist_eval, single_eval = (
                    make_eval_step(model, mesh=mesh, sync_bn=not unsync_bn),
                    eval_step,
                )

                def eval_step(params, stats, images, labels):
                    fn = dist_eval if len(labels) % world == 0 else single_eval
                    return fn(params, stats, images, labels)

        with record.span("startup.data"):
            # Never the network: the dataset is on disk under --data-root or
            # it is the seeded stand-in, so a run reads nothing from outside.
            train_set = load_cifar10(args.data_root, train=True,
                                     download=False)
            test_set = load_cifar10(args.data_root, train=False,
                                    download=False)
            if train_set.synthetic:
                rank0_print("WARNING: CIFAR-10 not found on disk — using "
                            "the deterministic synthetic stand-in dataset.")

            if args.batch_size is not None:
                per_rank_batch = args.batch_size

            loader_cls, dist_loader_cls = BatchLoader, DistributedBatchLoader
            loader_choice = getattr(args, "loader", "auto")
            if loader_choice in ("auto", "native"):
                from distributed_machine_learning_tpu.data.native_loader import (  # noqa: E501
                    NativeBatchLoader,
                    NativeDistributedBatchLoader,
                    native_available,
                    native_unavailable_reason,
                )

                if native_available():
                    loader_cls, dist_loader_cls = (
                        NativeBatchLoader,
                        NativeDistributedBatchLoader,
                    )
                elif loader_choice == "native":
                    raise RuntimeError(native_unavailable_reason())
                else:
                    rank0_print(
                        f"native loader unavailable, using python loader "
                        f"({native_unavailable_reason()})"
                    )

        place = (lambda i, l: shard_batch(mesh, i, l)) if mesh is not None else None
        from distributed_machine_learning_tpu.runtime.faults import (
            FaultInjector,
        )
        from distributed_machine_learning_tpu.runtime.resilience import (
            PreemptionHandler,
            Watchdog,
            agree_stop,
            periodic_agree_stop,
        )

        supervised = args.resume == "auto"
        injector = FaultInjector.from_flags(
            getattr(args, "faults", None), seed=SEED,
            horizon=max(args.max_iters, 2),
        )
        if injector is not None and getattr(args, "gang_dir", None):
            # Gang mode: the exactly-once latch must survive the
            # coordinated relaunch a fault causes — without the ledger
            # every relaunched process re-parses the spec and re-fires
            # the same fault until the restart budget is gone.
            from distributed_machine_learning_tpu.runtime.faults import (
                FAULT_LEDGER_FILE,
            )

            os.makedirs(args.gang_dir, exist_ok=True)
            injector.attach_ledger(
                os.path.join(args.gang_dir, FAULT_LEDGER_FILE)
            )
        mid_save = (
            injector.mid_save_hook(events) if injector is not None else None
        )
        post_save = (
            injector.post_save_hook(events) if injector is not None else None
        )
        if (injector is not None and args.async_ckpt
                and (injector.has_kind("kill_ckpt")
                     or injector.has_kind("corrupt_ckpt"))):
            # The async writer defers the config file past the orbax
            # commit, so there is no synchronous "between state and
            # config" window to kill in, and it takes no post-save hook
            # to corrupt through — either fault would silently never
            # fire, which is worse than refusing.
            raise ValueError(
                "kill_ckpt/corrupt_ckpt faults require the synchronous "
                "checkpoint path (drop --async-ckpt)"
            )
        retry_policy = None
        if getattr(args, "loader_retries", 0):
            from distributed_machine_learning_tpu.data.retry import (
                RetryPolicy,
            )

            retry_policy = RetryPolicy(max_retries=args.loader_retries)
        show_resilience = (
            supervised or injector is not None
            or bool(getattr(args, "guard_nonfinite", False))
            or bool(getattr(args, "loader_retries", 0))
        )
        # Per-step fault accounting costs a host sync per step; only pay
        # it when some robustness feature can actually produce events.
        loop_events = events if show_resilience else None

        preemption = PreemptionHandler().install()
        # Multi-host: every host must leave the step loop at the SAME
        # boundary or the stragglers hang in a collective.  The in-loop
        # predicate agrees cross-host every few steps (per-step agreement
        # would tax every step with an allgather); the epoch tail agrees
        # unconditionally.
        in_loop_stop = periodic_agree_stop(lambda: preemption.requested)
        if getattr(args, "gang_dir", None):
            # Gang mode: heartbeat + peer-failure detection around the
            # whole run (runtime/coordinator.py).  A dead/stalled peer
            # aborts this process (exit 43) so an external gang
            # supervisor relaunches every rank together — the agreement
            # the in-process ladder above cannot provide once a rank is
            # stuck inside a collective.
            from distributed_machine_learning_tpu.runtime.coordinator import (  # noqa: E501
                GangCoordinator,
            )

            coordinator = GangCoordinator(
                args.gang_dir,
                rank=jax.process_index(),
                world=jax.process_count(),
                heartbeat_interval_s=args.heartbeat_interval,
                peer_timeout_s=args.peer_timeout,
                events=events,
            ).start()
            show_resilience = True
            if args.resume:
                # A successful restore is this rank's proof that the
                # restored checkpoint is whole — its half of the
                # restore-point election, recorded even if no further
                # save ever lands (gang_worker.py does the same).
                coordinator.record_valid_step(
                    int(jax.device_get(state.step))
                )
            base_in_loop_stop = in_loop_stop
            # Warm-up suspension: the first step's XLA compile can
            # outlast any sane peer timeout, and the stop predicate is
            # polled BEFORE each step — so stay suspended (liveness
            # still monitored, progress not judged) until the second
            # poll, which can only happen after the first step (and its
            # compile) completed.
            warmup_cm = coordinator.suspend()
            warmup_cm.__enter__()
            warmup = {"polls": 0, "cm": warmup_cm, "last": None,
                      "suspends": coordinator.suspensions}

            def in_loop_stop(_base=base_in_loop_stop):
                import time as _time

                # The stop predicate is polled once per step on every
                # rank — the natural place to record gang progress
                # without threading the coordinator into the loop.  The
                # inter-poll delta is one completed step, so past
                # warm-up each poll also feeds the heartbeat metric
                # snapshot (rolling step time) the gang straggler
                # detector compares across ranks.  A delta only counts
                # when NO suspension happened inside it: compile, eval
                # and checkpoint saves all run under coordinator
                # .suspend(), and an interval that swallowed one is not
                # a step time — feeding it would poison the rolling
                # mean for a whole window and fire false straggler
                # verdicts (`suspensions` is the entry counter the
                # coordinator keeps for exactly this comparison).
                now = _time.perf_counter()
                spans = coordinator.suspensions
                if (warmup["cm"] is None and warmup["last"] is not None
                        and spans == warmup["suspends"]):
                    coordinator.observe_step(warmup["polls"],
                                             now - warmup["last"])
                else:
                    coordinator.beat()
                warmup["last"] = now
                warmup["suspends"] = spans
                warmup["polls"] += 1
                if warmup["cm"] is not None and warmup["polls"] >= 2:
                    warmup["cm"].__exit__(None, None, None)
                    warmup["cm"] = None
                return _base()
        if args.watchdog_timeout and not supervised:
            watchdog = Watchdog(timeout_s=args.watchdog_timeout).start()
        # Epochs completed across supervised restarts: a restart resumes
        # from the per-epoch checkpoint, so finished epochs stay done.
        progress = {"epochs": 0}

        def make_epoch_batches():
            import itertools

            if distributed:
                base = dist_loader_cls(train_set, per_rank_batch, world)
            else:
                base = loader_cls(train_set, per_rank_batch)
            # Fault steps index the run's global batch ordinal; epochs
            # are --max-iters batches under the reference protocol.
            epoch_base = progress["epochs"] * args.max_iters

            def source(pos):
                # Seekable by re-slicing: every loader here is
                # deterministic, so skipping `pos - epoch_base` batches
                # replays the exact stream (data/retry.py's contract).
                it = itertools.islice(iter(base), pos - epoch_base, None)
                if injector is not None:
                    it = injector.wrap_batches(it, events, start=pos)
                return it

            if retry_policy is not None:
                from distributed_machine_learning_tpu.data.retry import (
                    retry_batches,
                )

                return retry_batches(
                    source, retry_policy, events, start=epoch_base
                )
            return source(epoch_base)

        def run_epochs(state, wd):
            """The per-epoch train/eval/checkpoint cycle; returns
            (state, stopped_early)."""
            nonlocal ckpt_writer
            while progress["epochs"] < args.epochs:
                batches = make_epoch_batches()
                if wd is not None:
                    # Reset the timer at the epoch boundary so the first
                    # step's XLA compile gets the full timeout window
                    # instead of whatever is left from the setup phase.
                    wd.beat()
                with trace(args.trace_dir):
                    state, _ = train_epoch(
                        train_step, state, batches, place_batch=place,
                        max_iters=args.max_iters, metrics=metrics,
                        stop=in_loop_stop, watchdog=wd,
                        events=loop_events, telemetry=telemetry,
                    )
                # One agreed decision governs the whole epoch tail —
                # eval, checkpoint, and loop exit must diverge on NO host.
                stopping = agree_stop(preemption.requested)
                if not stopping:
                    eval_batches = BatchLoader(
                        test_set, getattr(args, "eval_batch_size", EVAL_BATCH)
                    )
                    if args.eval_batches is not None:
                        import itertools

                        eval_batches = itertools.islice(
                            iter(eval_batches), args.eval_batches
                        )
                    # Eval time is not step time: suspend the stall
                    # clock so a long eval (including its own compile)
                    # can't be declared a stall — under --resume auto a
                    # declared stall costs a restart.
                    with (wd.suspend() if wd is not None
                          else contextlib.nullcontext()), \
                         (coordinator.suspend() if coordinator is not None
                          else contextlib.nullcontext()), \
                         (telemetry.span("eval", epoch=progress["epochs"])
                          if telemetry is not None
                          else contextlib.nullcontext()):
                        evaluate(eval_step, state, eval_batches)
                if args.ckpt_dir:
                    from distributed_machine_learning_tpu.train.checkpoint import (  # noqa: E501
                        AsyncCheckpointWriter,
                        save_checkpoint,
                    )

                    # Same for the (possibly long, blocking) checkpoint
                    # write: not step time — stop the stall clock.
                    with (wd.suspend() if wd is not None
                          else contextlib.nullcontext()), \
                         (coordinator.suspend() if coordinator is not None
                          else contextlib.nullcontext()):
                        if args.async_ckpt:
                            if ckpt_writer is None:
                                ckpt_writer = AsyncCheckpointWriter()
                            path = ckpt_writer.save(
                                args.ckpt_dir, state,
                                keep_last_n=getattr(args, "keep_last_n",
                                                    None),
                            )
                            rank0_print(
                                f"Saving checkpoint to {path} (async)"
                            )
                        else:
                            path = save_checkpoint(
                                args.ckpt_dir, state, mid_save_hook=mid_save,
                                keep_last_n=getattr(args, "keep_last_n",
                                                    None),
                                post_save_hook=post_save,
                            )
                            rank0_print(f"Saved checkpoint to {path}")
                            if coordinator is not None:
                                # This rank's half of the restore-point
                                # election: the save returned, so the
                                # checkpoint is locally verified.  (Async
                                # saves commit later; they are recorded
                                # only after the writer's flush, which
                                # the gang path doesn't use yet.)
                                coordinator.record_valid_step(
                                    int(jax.device_get(state.step))
                                )
                if stopping:
                    events.preemptions += 1
                    rank0_print(
                        "preemption checkpoint complete; exiting cleanly "
                        "(resume with --resume)"
                        if args.ckpt_dir
                        else "stop requested; exiting (no --ckpt-dir, so no "
                             "checkpoint was written)"
                    )
                    return state, True
                progress["epochs"] += 1
            return state, False

        if supervised:
            # --resume auto: the supervised ladder — on a stall, crash,
            # or injected death, restore the newest complete checkpoint
            # and continue where the per-epoch progress left off, up to
            # --max-restarts times (runtime/supervisor.py).
            from distributed_machine_learning_tpu.runtime.supervisor import (
                RaisingWatchdog,
                run_attempts,
            )

            def attempt(restart_idx):
                s = state
                if restart_idx > 0:
                    if ckpt_writer is not None:
                        # Flush the async writer's pending config before
                        # looking for the newest complete checkpoint:
                        # without this, the last scheduled save is still
                        # invisible to latest_checkpoint and the restart
                        # would silently drop an epoch of finished work.
                        try:
                            ckpt_writer.wait()
                        except Exception as e:
                            # Torn save stays incomplete; restore falls
                            # back to the previous complete one — but
                            # say so (dmlcheck DML005): a silently
                            # dropped save reads as lost work.
                            rank0_print(
                                "async checkpoint save failed before "
                                f"restart ({type(e).__name__}: {e}); "
                                "resuming from the previous complete "
                                "checkpoint"
                            )
                    s = restore_latest(_maybe_stack(
                        init_model_and_state(model, config=opt_config)
                    ))
                    if coordinator is not None:
                        coordinator.record_valid_step(
                            int(jax.device_get(s.step))
                        )
                    # Re-derive finished-epoch progress from what was
                    # actually RESTORED, never from the in-memory
                    # counter: if the newest complete checkpoint is
                    # older than the counter says (torn async save,
                    # kill mid-write), trusting the counter would
                    # silently drop the un-checkpointed epochs.
                    # Rounds down under guard-skipped steps — an epoch
                    # is re-run rather than skipped, which only costs
                    # time, not correctness.
                    progress["epochs"] = min(
                        args.epochs,
                        int(jax.device_get(s.step))
                        // max(args.max_iters, 1),
                    )
                wd = (
                    RaisingWatchdog(args.watchdog_timeout, events).start()
                    if args.watchdog_timeout
                    else None
                )
                try:
                    out, _ = run_epochs(s, wd)
                    return out
                finally:
                    if wd is not None:
                        wd.stop()

            state = run_attempts(
                attempt, max_restarts=args.max_restarts, events=events
            )
        else:
            state, _ = run_epochs(state, watchdog)
        run_completed = True
    finally:
        # Flush in finally so a crash/interrupt mid-run keeps the rows
        # already logged — the feature's main use is diagnosing bad runs.
        if watchdog is not None:
            # Disarm before the (potentially long) final async-save
            # flush — a blocking close() with no beats is not a stall.
            watchdog.stop()
        if coordinator is not None:
            # Clean completion must publish done=True (finish): a
            # frozen-but-not-done beat file reads as a death to peers
            # still in their run tail.  A failed run deliberately does
            # NOT publish done — the frozen file going stale is exactly
            # how the gang learns this rank died.
            if run_completed:
                coordinator.finish()
            else:
                coordinator.stop()
        if ckpt_writer is not None:
            # Don't exit with a half-written async save in flight.
            ckpt_writer.close()
        if preemption is not None:
            preemption.uninstall()
        if show_resilience:
            # Printed even on a crashed run (in finally): the counters
            # are the diagnosis — silent robustness is no robustness.
            from distributed_machine_learning_tpu.utils.summary import (
                resilience_summary,
            )

            rank0_print(resilience_summary(events))
        if metrics is not None:
            metrics.save(args.metrics_file)
            rank0_print(
                f"Wrote {metrics.count} metric rows to "
                f"{args.metrics_file}"
                + (" (streamed; append mode: prior runs' rows in the "
                   "same file are preserved above this run's)"
                   if metrics._sink is not None and metrics.append else
                   " (streamed)" if metrics._sink is not None else "")
            )
        if telemetry is not None:
            # Uninstall BEFORE close so late events (shutdown paths) hit
            # a closed sink never; then flush + terminate the trace.
            set_telemetry(prev_telemetry)
            telemetry.close()
            rank0_print(f"Telemetry written to {args.telemetry_dir}")
        ctx.shutdown()  # dist.destroy_process_group parity (part2/2a/main.py:207)
    return RunResult(state=state, train_step=train_step, place_batch=place)
