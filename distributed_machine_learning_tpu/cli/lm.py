"""Language-model training entrypoint — every parallelism scheme behind
one flag.

The reference's CLI surface only trains its CNN (SURVEY.md §1); this
entrypoint gives the transformer stack the same driveable surface, with
``--parallel`` selecting how the step distributes over the mesh:

  dp       data parallelism (replicated params, pmean grads)
  fsdp     ZeRO-3 sharded data parallelism, flat-vector layout — params
           + optimizer state 1/N per device, one whole-model all-gather
           up front (parallel/fsdp.py); pair with adamw, whose fp32
           moments are the memory ZeRO shards
  fsdp_pl  ZeRO-3, per-layer GSPMD layout — each leaf sharded over the
           data axis; XLA gathers weights at their use site and
           overlaps layer i+1's gather with layer i's compute
           (parallel/fsdp_perlayer.py)
  ring     context parallelism — ppermute ring attention over the
           sequence axis (ops/ring_attention.py)
  ulysses  context parallelism — all-to-all head re-sharding
           (ops/ulysses.py)
  tp       tensor parallelism — Megatron layout via GSPMD
           (parallel/tensor_parallel.py)
  pp       pipeline parallelism — ppermute pipeline; --pp-schedule
           picks 1f1b (default: one backward per forward, O(P)
           activation memory, parallel/pipeline_1f1b.py), interleaved
           (--pp-chunks virtual stages per device, bubble
           (P-1)/(v*M+P-1), parallel/pipeline_interleaved.py), or
           gpipe (all-forward-then-all-backward, parallel/pipeline.py)
  3d       data × pipeline × tensor composed
           (parallel/parallel3d.py)
  ep       expert parallelism — Switch-routed MoE transformer, experts
           sharded over an expert axis, batch over the rest
           (parallel/expert_parallel.py, models/moe.py)

Data is a deterministic synthetic byte stream (seeded from the shared
69143) — the reference's CIFAR runs are likewise about the training
machinery, not the dataset.  The measurement protocol is the reference's:
``--max-iters`` capped, iteration 0 excluded from timing, loss printed
every 20 iterations, total/average summary at the end
(``part1/main.py:32-58``).
"""

from __future__ import annotations

import numpy as np
import jax

from distributed_machine_learning_tpu.cli.common import (
    SEED,
    RunResult,
    device_banner,
)
from distributed_machine_learning_tpu.models.transformer import TransformerLM
from distributed_machine_learning_tpu.runtime.distributed import (
    initialize_from_flags,
)
from distributed_machine_learning_tpu.runtime.mesh import make_mesh, replicate
from distributed_machine_learning_tpu.train.loop import train_epoch
from distributed_machine_learning_tpu.utils.logging import rank0_print


def make_parser():
    import argparse

    from distributed_machine_learning_tpu.cli.common import (
        add_node_flags,
        add_telemetry_flags,
    )

    p = argparse.ArgumentParser(description=__doc__)
    add_node_flags(p)
    add_telemetry_flags(p)
    p.add_argument("--parallel", default="dp",
                   choices=["dp", "ring", "ulysses", "fsdp", "fsdp_pl",
                            "tp", "pp", "3d", "ep"])
    p.add_argument("--n-experts", dest="n_experts", default=8, type=int,
                   help="MoE experts (--parallel ep only)")
    p.add_argument("--capacity-factor", dest="capacity_factor", default=1.25,
                   type=float, help="MoE expert capacity factor (ep only)")
    p.add_argument("--ep", default=None, type=int,
                   help="expert-axis size for --parallel ep (default: the "
                        "greatest common divisor of the device count and "
                        "--n-experts); the remaining devices/ep factor "
                        "becomes the data axis")
    p.add_argument("--moe-impl", dest="moe_impl", default="einsum",
                   choices=["einsum", "grouped"],
                   help="MoE expert compute (--parallel ep only): 'einsum' "
                        "= Switch capacity + drops, GSPMD-sharded over the "
                        "expert axis; 'grouped' = dropless ragged-matmul "
                        "path (ops/grouped.py) — single-device fast path "
                        "(no cell measures it: ROADMAP X3) AND, "
                        "multi-device, the manual shard_map EP step with "
                        "an explicit token all_to_all to expert owners "
                        "(batch shards over data x expert; no attention "
                        "duplication)")
    p.add_argument("--ep-slots", dest="ep_slots", default=None, type=int,
                   help="grouped-EP send slots per owner device (default "
                        "N_local = provably dropless; lower bounds the "
                        "dispatch all-to-all bytes at Switch-style "
                        "per-owner overflow drops -- ops/grouped.py)")
    p.add_argument("--ep-seq", dest="ep_seq", default=1, type=int,
                   help="sequence-axis size for MoE x context parallelism "
                        "(--parallel ep --moe-impl grouped only): shards "
                        "the sequence over a third mesh axis and runs "
                        "ring attention over it while the MoE dispatch "
                        "all_to_alls over the expert axis")
    p.add_argument("--model-config", dest="model_config", default=None,
                   help="an HF-style config.json: its sizes replace the "
                        "size flags (hidden_size, num_hidden_layers, "
                        "num_attention_heads, num_key_value_heads, "
                        "vocab_size) and it carries what no flag does "
                        "(intermediate_size, rope_theta, norm_epsilon); "
                        "its model_type picks the module — 'qwen3_next' is "
                        "the hybrid Gated-DeltaNet / gated-attention MoE "
                        "(models/hybrid_moe.py), 'deepseek_v3' the latent-"
                        "attention MoE with bias-corrected sigmoid routing "
                        "behind leading dense layers (models/mla_moe.py), "
                        "'afmoe' the sliding-window / full gated-attention "
                        "MoE with sandwich norms whose selection bias the "
                        "balancing rule moves (models/window_moe.py; the "
                        "benchmark's cell: --parallel dp --compute-dtype "
                        "bfloat16 --attn flash --optimizer adamw "
                        "--fused-ce-chunks 8 --remat --remat-policy block "
                        "--lr 5e-6 --seq-len 16384 --batch-size 1; its plain "
                        "reference: benchmark/reference/window_moe_lm.py), "
                        "all under --parallel dp only; anything else the "
                        "dense TransformerLM")
    p.add_argument("--d-model", dest="d_model", default=256, type=int)
    p.add_argument("--n-layers", dest="n_layers", default=4, type=int)
    p.add_argument("--n-heads", dest="n_heads", default=8, type=int)
    p.add_argument("--n-kv-heads", dest="n_kv_heads", default=None, type=int,
                   help="grouped-query attention: K/V heads shared by "
                        "query-head groups (1 = MQA; shrinks the decode "
                        "KV cache by n_heads/n_kv_heads); default = MHA")
    p.add_argument("--vocab", default=256, type=int,
                   help="byte-level vocabulary by default")
    p.add_argument("--seq-len", dest="seq_len", default=256, type=int)
    p.add_argument("--batch-size", dest="batch_size", default=8, type=int,
                   help="global batch (sequences per step)")
    p.add_argument("--max-iters", dest="max_iters", default=40, type=int)
    p.add_argument("--microbatches", default=2, type=int,
                   help="pipeline microbatches (pp/3d)")
    p.add_argument("--ckpt-dir", default=None,
                   help="save the trained state here (orbax, sharded "
                        "global arrays as-is); restores with --resume. "
                        "All schemes except the flat-vector fsdp (whose "
                        "FSDPState is not a TrainState; use fsdp_pl for "
                        "checkpointable ZeRO-3)")
    p.add_argument("--resume", nargs="?", const="latest", default=None,
                   choices=["latest", "auto"],
                   help="restore the latest checkpoint in --ckpt-dir "
                        "before training (same scheme + optimizer as "
                        "the save).  '--resume auto' supervises the run: "
                        "a crash restores the newest complete checkpoint "
                        "and retrains, up to --max-restarts times "
                        "(runtime/supervisor.py; coarse-grained here — "
                        "the LM path checkpoints once, at the end)")
    p.add_argument("--max-restarts", dest="max_restarts", default=3, type=int,
                   help="with --resume auto: restore-and-retry this many "
                        "times before giving up")
    p.add_argument("--guard-nonfinite", dest="guard_nonfinite",
                   action="store_true",
                   help="compile a non-finite-gradient guard into the "
                        "train step: a NaN/Inf gradient skips that update "
                        "(state unchanged, step not counted); "
                        "dp/ring/ulysses schemes")
    p.add_argument("--loss-scale", dest="loss_scale", default="none",
                   choices=["none", "dynamic"],
                   help="'dynamic' enables dynamic loss scaling for the "
                        "bf16 path (train/lm_step.py): loss multiplied by "
                        "an adaptive scale before backward, gradients "
                        "unscaled after; overflow skips the update and "
                        "halves the scale, 200 consecutive good steps "
                        "double it; dp/ring/ulysses schemes")
    p.add_argument("--pp-schedule", dest="pp_schedule", default="1f1b",
                   choices=["1f1b", "gpipe", "interleaved"],
                   help="pipeline schedule (pp only): 1f1b interleaves "
                        "one backward with one forward per tick — O(P) "
                        "activation memory instead of GPipe's O(M) "
                        "(parallel/pipeline_1f1b.py); gpipe is "
                        "all-forward-then-all-backward; interleaved "
                        "gives each device --pp-chunks virtual stages, "
                        "cutting the bubble to (P-1)/(v*M+P-1) "
                        "(parallel/pipeline_interleaved.py)")
    p.add_argument("--pp-chunks", dest="pp_chunks", default=None, type=int,
                   help="virtual stages per device for "
                        "--pp-schedule interleaved (v, default 2); "
                        "n_layers must divide by devices x v")
    p.add_argument("--dp", default=None, type=int,
                   help="data-axis size for --parallel 3d "
                        "(default: devices // (pp*tp))")
    p.add_argument("--pp", default=2, type=int,
                   help="pipe-axis size for --parallel 3d")
    p.add_argument("--tp", default=2, type=int,
                   help="model-axis size for --parallel 3d")
    p.add_argument("--zero1-dp", dest="zero1_dp", action="store_true",
                   help="with --parallel 3d: shard the optimizer moments "
                        "1/dp over the data axis (ZeRO-1 x 3-D, the 4th "
                        "composed axis — parallel/parallel3d.py::"
                        "p3_zero1_moment_spec); update-equivalent to "
                        "plain 3d")
    p.add_argument("--overlap-update", dest="overlap_update",
                   action="store_true",
                   help="overlap-aware sharded weight update (arxiv "
                        "2004.13336): with --parallel fsdp, take the "
                        "parameter gather off the critical path (the "
                        "prefetch protocol of parallel/overlap.py — "
                        "bit-identical trajectory); with --parallel pp "
                        "--pp-schedule gpipe, shard the boundary-module "
                        "update over the pipe axis and ring-gather the "
                        "slices back")
    p.add_argument("--compute-dtype", default="float32",
                   choices=["float32", "bfloat16"])
    from distributed_machine_learning_tpu.train.optimizers import (
        optimizer_names,
    )

    p.add_argument("--optimizer", default="adamw", choices=optimizer_names(),
                   help="LM default is adamw (train/adamw.py); sgd gives "
                        "the reference's torch-semantics update")
    p.add_argument("--lr", default=None, type=float,
                   help="override the optimizer config's learning rate")
    p.add_argument("--fused-update", dest="fused_update",
                   action="store_true",
                   help="run the AdamW update as the fused one-pass Pallas "
                        "kernel (ops/pallas/fused_adamw.py) — moment "
                        "update, bias correction, decay, parameter update "
                        "and the bf16 cast in-register; adamw only")
    p.add_argument("--momentum-dtype", dest="momentum_dtype", default=None,
                   help="SGD momentum-buffer storage dtype (e.g. "
                        "bfloat16): halves optimizer-state memory, the "
                        "term that bounds model depth on one chip; "
                        "update math stays f32 (train/sgd.py; sgd only)")
    p.add_argument("--data-dir", dest="data_dir", default=None, type=str,
                   help="train on real text: every text file under this "
                        "directory becomes a byte-level corpus "
                        "(data/text.py; vocab auto-set to 257 = bytes+BOS); "
                        "default trains on the deterministic synthetic "
                        "stream")
    p.add_argument("--eval-batches", dest="eval_batches", default=0, type=int,
                   help="after training, evaluate perplexity on this many "
                        "windows from a held-out corpus slice (the final "
                        "10%% of tokens is reserved from training when "
                        "--data-dir is set); dp/ring/ulysses/fsdp, "
                        "single-process only; 0 skips)")
    p.add_argument("--fused-ce-chunks", dest="fused_ce_chunks", default=None,
                   type=int,
                   help="compute the loss fused with the lm_head in this "
                        "many vocab chunks (ops/fused_ce.py) — the "
                        "[B,L,vocab] logits are never materialized; "
                        "dp/ring/ulysses modes only")
    p.add_argument("--attn", default="auto",
                   choices=["auto", "dense", "flash"],
                   help="attention kernel: for dp/fsdp, 'auto' picks the "
                        "Pallas flash kernel from 512 context up "
                        "(flash_wins; ROADMAP D5) and 'dense' "
                        "the XLA fused path; for --parallel ring, "
                        "'auto'/'flash' upgrade the per-chunk math to "
                        "the flash-kernel ring when the per-device chunk "
                        "is big enough, 'dense' pins the einsum ring; "
                        "tp/fsdp_pl/ep honor 'auto'/'flash' via the "
                        "shard_map-wrapped kernel, pp takes explicit "
                        "'flash', 3d and flat fsdp resolve 'auto' to "
                        "dense, ulysses owns its attention")
    p.add_argument("--remat", action="store_true",
                   help="recompute activations in the backward pass, as "
                        "--remat-policy says: activation memory drops up "
                        "to ~n_layers-fold for up to ~33%% more FLOPs — "
                        "the long-context enabler (models/transformer.py)")
    p.add_argument("--remat-policy", dest="remat_policy", default="mlp",
                   choices=["mlp", "block"],
                   help="with --remat: 'mlp' checkpoints only the LN2+MLP "
                        "sub-layer (attention residuals incl. flash "
                        "out+lse stay saved — backward never re-runs the "
                        "O(L^2) attention forward); 'block' is whole-block "
                        "remat, the maximal-memory-savings fallback: "
                        "everything of the block is made again in backward "
                        "except the flash kernel's out+lse, kept beside the "
                        "block's input (nothing more on the dense path)")
    return p


def synthetic_tokens(rng: np.random.Generator, batch: int, seq_len: int,
                     vocab: int):
    """[B, L+1] int32 token block; [:, :-1] feeds, [:, 1:] targets."""
    return rng.integers(0, vocab, (batch, seq_len + 1)).astype(np.int32)


#: HF-style configuration key -> the size flag's destination it replaces.
_CONFIG_SIZES = {
    "hidden_size": "d_model",
    "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "vocab_size": "vocab",
}


def read_model_config(args) -> dict:
    """``--model-config``'s file ({} without the flag), its sizes written
    over the size flags' values so that everything downstream of the
    parser (banner, data, FLOPs model) sees the model that is built."""
    path = getattr(args, "model_config", None)
    if path is None:
        return {}
    import json

    with open(path, encoding="utf-8") as f:
        config = json.load(f)
    for key, dest in _CONFIG_SIZES.items():
        if key in config:
            setattr(args, dest, config[key])
    return config


#: ``model_type``s of ``--model-config`` that bring a module of their own:
#: (module under ``models/``, model class, sizes class).  Each trains under
#: ``--parallel dp`` only.
CONFIG_MODELS = {
    "qwen3_next": ("hybrid_moe", "HybridMoELM", "HybridMoESizes"),
    "deepseek_v3": ("mla_moe", "MLAMoELM", "MLAMoESizes"),
    "afmoe": ("window_moe", "WindowMoELM", "WindowMoESizes"),
}


def dp_model(args, config: dict, **common):
    """The model ``--parallel dp`` trains: the module ``config``'s
    ``model_type`` names, or ``TransformerLM(**common)``."""
    if config.get("model_type") not in CONFIG_MODELS:
        return TransformerLM(**common)
    import importlib

    module, model_cls, sizes_cls = CONFIG_MODELS[config["model_type"]]
    module = importlib.import_module(
        "distributed_machine_learning_tpu.models." + module)
    from distributed_machine_learning_tpu.models.transformer import (
        _flash_wins,
    )

    attn = common["attn_impl"]
    if attn == "auto":
        attn = "flash" if _flash_wins(args.seq_len) else "dense"
    return getattr(module, model_cls)(
        getattr(module, sizes_cls).from_config(config), attn_impl=attn,
        compute_dtype=common["compute_dtype"], remat=common["remat"],
        remat_policy=common["remat_policy"])


def build(args):
    """(step, state, place, model, params_fn) for the chosen parallelism
    scheme; ``params_fn(state)`` yields the replicated params pytree for
    eval (a gather for fsdp)."""
    import jax.numpy as jnp

    n = jax.device_count()
    dtype = jnp.bfloat16 if args.compute_dtype == "bfloat16" else jnp.float32
    attn = getattr(args, "attn", "auto")
    config = read_model_config(args)
    if config.get("model_type") in CONFIG_MODELS and args.parallel != "dp":
        raise ValueError(
            f"--model-config with model_type {config['model_type']!r} (as "
            f"each of {sorted(CONFIG_MODELS)}) trains under --parallel dp "
            f"only (got --parallel {args.parallel}): these models have no "
            "sequence-, tensor- or pipeline-sharded step yet")
    carried = sorted({"intermediate_size", "rope_theta", "norm_epsilon"}
                     & set(config))
    if carried and args.parallel in ("pp", "3d", "ep"):
        # These steps build their blocks themselves (parallel/pipeline.py,
        # models/moe.py) and would run the defaults in silence.
        raise ValueError(
            f"--model-config states {carried}, which --parallel "
            f"{args.parallel} does not take from a file yet")
    if args.parallel in ("pp", "fsdp") and attn == "auto":
        # These steps resolve "auto" to the dense path they default to
        # (pp accepts an EXPLICIT --attn flash — its pipe-axis shard_map
        # is fully manual; flat-fsdp's step is dense-only and keeps a
        # loud guard for explicit flash).  tp/fsdp_pl/ep/3d honor auto
        # themselves via the model's flash_mesh shard_map wrap.
        attn = "dense"
    common = dict(
        vocab_size=args.vocab, d_model=args.d_model, n_layers=args.n_layers,
        n_heads=args.n_heads, compute_dtype=dtype, remat=args.remat,
        remat_policy=getattr(args, "remat_policy", "mlp"),
        n_kv_heads=args.n_kv_heads,
        # ring/ulysses overwrite this below; all other modes honor it.
        attn_impl=attn,
        # What only a configuration file states (None/absent: the
        # defaults the flags-only path has always run).
        d_ff=config.get("intermediate_size"),
        rope_base=float(config.get("rope_theta", 10000.0)),
        ln_eps=config.get("norm_epsilon", 1e-6),
    )
    from distributed_machine_learning_tpu.train.optimizers import get_optimizer

    cfg_cls = get_optimizer(args.optimizer)[0]
    if args.pp_chunks is not None and not (
        args.parallel == "pp" and args.pp_schedule == "interleaved"
    ):
        # Checked before the scheme dispatch so the flag cannot be
        # silently ignored under any --parallel value.
        raise ValueError(
            "--pp-chunks applies to --parallel pp with --pp-schedule "
            f"interleaved only (got --parallel {args.parallel}, "
            f"--pp-schedule {args.pp_schedule})"
        )
    if getattr(args, "ep_seq", 1) != 1 and args.parallel != "ep":
        # Same pre-dispatch discipline as --pp-chunks: a flag that only
        # one scheme reads must not be silently ignored by the others.
        raise ValueError(
            "--ep-seq (MoE x context parallelism) applies to --parallel "
            f"ep only (got --parallel {args.parallel})"
        )
    if getattr(args, "ep_slots", None) is not None and not (
        args.parallel == "ep" and args.moe_impl == "grouped"
    ):
        raise ValueError(
            "--ep-slots applies to --parallel ep --moe-impl grouped only "
            f"(got --parallel {args.parallel}, --moe-impl {args.moe_impl})"
        )
    if getattr(args, "zero1_dp", False) and args.parallel != "3d":
        raise ValueError(
            "--zero1-dp (ZeRO-1 x 3-D moment sharding) applies to "
            f"--parallel 3d only (got --parallel {args.parallel}); the "
            "standalone ZeRO-1 scheme is parallel/zero1.py"
        )
    if getattr(args, "overlap_update", False):
        if args.parallel not in ("fsdp", "pp") or (
            args.parallel == "pp" and args.pp_schedule != "gpipe"
        ):
            raise ValueError(
                "--overlap-update applies to --parallel fsdp (prefetch "
                "protocol) or --parallel pp --pp-schedule gpipe "
                "(pipe-sharded boundary update); got --parallel "
                f"{args.parallel}"
                + (f" --pp-schedule {args.pp_schedule}"
                   if args.parallel == "pp" else "")
            )
    cfg_kwargs = {}
    if args.lr is not None:
        cfg_kwargs["learning_rate"] = args.lr
    if args.momentum_dtype is not None:
        if args.optimizer != "sgd":
            raise ValueError(
                "--momentum-dtype applies to --optimizer sgd only "
                "(AdamW keeps fp32 moments; LARS accumulates in the "
                "buffer dtype and refuses narrowing)"
            )
        cfg_kwargs["momentum_dtype"] = args.momentum_dtype
    if getattr(args, "fused_update", False):
        if args.optimizer != "adamw":
            raise ValueError(
                "--fused-update applies to --optimizer adamw only (the "
                "fused kernel is the AdamW rule; got "
                f"--optimizer {args.optimizer})"
            )
        cfg_kwargs["fused"] = True
    opt_config = cfg_cls(**cfg_kwargs)
    if args.fused_ce_chunks and args.parallel not in (
        "dp", "ring", "ulysses", "fsdp", "fsdp_pl"
    ):
        raise ValueError(
            "--fused-ce-chunks applies to the dp/ring/ulysses/fsdp/"
            "fsdp_pl steps only (tp shards the lm_head, pp computes the "
            "loss on the last stage)"
        )
    guard = bool(getattr(args, "guard_nonfinite", False))
    dynamic_scale = getattr(args, "loss_scale", "none") == "dynamic"
    if (guard or dynamic_scale) and args.parallel not in (
        "dp", "ring", "ulysses"
    ):
        # Same pre-dispatch discipline as --pp-chunks: a robustness flag
        # the chosen step doesn't implement must fail loudly, not
        # silently train unguarded.
        raise ValueError(
            "--guard-nonfinite/--loss-scale apply to the replicated "
            f"dp/ring/ulysses steps only (got --parallel {args.parallel})"
        )

    if args.parallel in ("dp", "ring", "ulysses"):
        from distributed_machine_learning_tpu.train.lm_step import (
            init_lm_state,
            make_lm_train_step,
            shard_lm_batch,
        )

        if args.parallel == "dp":
            if args.batch_size % n:
                raise ValueError(
                    f"--batch-size {args.batch_size} must be divisible by "
                    f"the {n}-device data axis"
                )
            mesh = make_mesh(n, ("batch", "seq"), (n, 1))
            model = dp_model(args, config, **common)
        else:
            if args.seq_len % n:
                raise ValueError(
                    f"--seq-len {args.seq_len} must be divisible by the "
                    f"{n}-device sequence axis ({args.parallel} shards the "
                    "sequence)"
                )
            mesh = make_mesh(n, ("batch", "seq"), (1, n))
            impl = args.parallel
            if args.parallel == "ring" and args.attn in ("auto", "flash"):
                from distributed_machine_learning_tpu.models.transformer import (
                    _ring_flash_wins,
                )
                from distributed_machine_learning_tpu.ops.pallas.flash_attention import (  # noqa: E501
                    _needs_pad,
                )

                # Explicit --attn flash still requires a natively
                # tileable chunk: the ring kernels have no pad/slice
                # wrapper, so an untileable chunk (largest power-of-two
                # divisor < 128) stays on the einsum ring rather than
                # handing Mosaic a block it must reject.
                chunk = args.seq_len // n
                if (args.attn == "flash" and not _needs_pad(chunk)) or (
                    args.attn == "auto" and _ring_flash_wins(chunk)
                ):
                    impl = "ring_flash"
                elif args.attn == "flash":
                    rank0_print(
                        f"WARNING: --attn flash with --parallel ring: "
                        f"per-device chunk {chunk} is not natively "
                        "tileable (largest power-of-two divisor < 128) "
                        "and the ring kernels have no pad path — "
                        "falling back to the einsum ring"
                    )
            model = TransformerLM(**{**common, "attn_impl": impl})
        # Placed up front: left on the default device, the whole step
        # compiles a second time at step 1 (38 s at d2048/8L on a v5e).
        state = replicate(
            init_lm_state(model, seed=SEED, config=opt_config), mesh
        )
        step = make_lm_train_step(model, mesh=mesh,
                                  fused_ce_chunks=args.fused_ce_chunks,
                                  guard_nonfinite=guard,
                                  dynamic_scale=dynamic_scale)
        place = lambda x, y: shard_lm_batch(mesh, x, y)
        return step, state, place, model, lambda st: st.params

    if args.parallel == "fsdp":
        from jax.sharding import NamedSharding, PartitionSpec as P

        from distributed_machine_learning_tpu.parallel.fsdp import (
            gather_fsdp_params,
            make_fsdp_lm_train_step,
            shard_fsdp_state,
        )
        from distributed_machine_learning_tpu.train.lm_step import init_lm_state

        if args.batch_size % n:
            raise ValueError(
                f"--batch-size {args.batch_size} must be divisible by "
                f"the {n}-device data axis"
            )
        mesh = make_mesh(n)
        model = TransformerLM(**common)
        fstate, unravel, n_elems = shard_fsdp_state(
            init_lm_state(model, seed=SEED, config=opt_config), mesh
        )
        step = make_fsdp_lm_train_step(
            model, mesh, unravel, n_elems,
            fused_ce_chunks=args.fused_ce_chunks,
            overlap=getattr(args, "overlap_update", False),
        )
        sharding = NamedSharding(mesh, P("batch"))
        place = lambda x, y: (
            jax.device_put(x, sharding), jax.device_put(y, sharding)
        )
        params_fn = lambda st: gather_fsdp_params(st, unravel, n_elems)
        return step, fstate, place, model, params_fn

    if args.parallel == "ep":
        from distributed_machine_learning_tpu.models.moe import (
            MoETransformerLM,
        )
        from distributed_machine_learning_tpu.parallel.expert_parallel import (
            init_moe_state,
            make_ep_train_step,
            shard_ep_state,
        )
        from distributed_machine_learning_tpu.parallel.tensor_parallel import (
            shard_tp_batch,
        )

        if args.n_kv_heads is not None and (
            args.n_kv_heads < 1 or args.n_heads % args.n_kv_heads
        ):
            raise ValueError(
                f"--n-kv-heads {args.n_kv_heads} must be a positive "
                f"divisor of --n-heads {args.n_heads}"
            )
        if args.remat and getattr(args, "remat_policy", "mlp") != "mlp":
            # MoETransformerLM implements the selective policy only (the
            # Block-level remat_mlp wrap); dropping 'block' silently
            # would surprise anyone counting on its memory profile.
            raise ValueError(
                "--parallel ep supports --remat-policy mlp only (the "
                "selective LN2+expert-MLP checkpoint); whole-block "
                "remat is not wired through the MoE blocks"
            )
        if args.n_experts < 1:
            raise ValueError(f"--n-experts must be >= 1, got "
                             f"{args.n_experts}")
        if args.ep is None:
            # Largest axis size dividing BOTH the device count and the
            # expert count — the biggest valid default on any host.
            import math

            ep = math.gcd(n, args.n_experts)
        else:
            ep = args.ep
        if ep < 1 or n % ep:
            raise ValueError(
                f"--ep {ep} must be a positive divisor of the device "
                f"count {n}"
            )
        if args.n_experts % ep:
            raise ValueError(
                f"--n-experts {args.n_experts} must be divisible by "
                f"--ep {ep}"
            )
        sp = args.ep_seq
        if sp < 1:
            raise ValueError(f"--ep-seq must be >= 1, got {sp}")
        if sp > 1 and args.moe_impl != "grouped":
            raise ValueError(
                "--ep-seq (MoE x context parallelism) requires "
                "--moe-impl grouped (the manual shard_map step; the "
                "GSPMD einsum step has no sequence axis)"
            )
        if n % (ep * sp):
            raise ValueError(
                f"--ep {ep} x --ep-seq {sp} must divide the device "
                f"count {n}"
            )
        dp = n // (ep * sp)
        if args.batch_size % dp:
            raise ValueError(
                f"--batch-size {args.batch_size} must be divisible by "
                f"the {dp}-device data axis (devices/(ep*ep_seq))"
            )
        model = MoETransformerLM(
            vocab_size=args.vocab, d_model=args.d_model,
            n_layers=args.n_layers, n_heads=args.n_heads,
            n_kv_heads=args.n_kv_heads, remat=args.remat,
            n_experts=args.n_experts, capacity_factor=args.capacity_factor,
            compute_dtype=dtype, attn_impl=attn, moe_impl=args.moe_impl,
        )
        if args.moe_impl == "grouped":
            if n == 1 and sp == 1:
                # Single device: the plain-jit dropless path.
                step = make_ep_train_step(model, mesh=None)
                state = init_moe_state(model, seed=SEED, config=opt_config)
                place = lambda x, y: (jnp.asarray(x), jnp.asarray(y))
                return step, state, place, model, lambda st: st.params
            # Multi-device: the manual shard_map EP step — explicit token
            # all_to_all to expert owners + local ragged_dot (dropless).
            # The batch shards over data × expert (the einsum step
            # replicates activations over the expert axis; this one does
            # not).  With --ep-seq > 1 the sequence shards over a third
            # mesh axis (MoE × context parallelism): attention becomes
            # the ppermute ring — upgraded to the flash-kernel ring
            # exactly like --parallel ring when the per-device chunk
            # tiles natively and the user asked for flash/auto.
            from jax.sharding import NamedSharding, PartitionSpec as P

            from distributed_machine_learning_tpu.parallel.expert_parallel import (  # noqa: E501
                make_ep_grouped_train_step,
            )

            if args.batch_size % (dp * ep):
                raise ValueError(
                    f"--batch-size {args.batch_size} must be divisible "
                    f"by data x expert = {dp * ep} (the EP-grouped step "
                    "shards the batch over both)"
                )
            if sp > 1:
                from distributed_machine_learning_tpu.models.transformer import (  # noqa: E501
                    _ring_flash_wins,
                )

                if args.seq_len % sp:
                    raise ValueError(
                        f"--seq-len {args.seq_len} must be divisible by "
                        f"--ep-seq {sp}"
                    )
                chunk = args.seq_len // sp
                if attn in ("auto", "flash") and _ring_flash_wins(chunk):
                    ring_impl = "ring_flash"
                else:
                    if attn == "flash":
                        rank0_print(
                            f"WARNING: per-device chunk {chunk} does not "
                            "qualify for the flash ring kernels — "
                            "falling back to the einsum ring"
                        )
                    ring_impl = "ring"
                model = model.clone(attn_impl=ring_impl)
                mesh = make_mesh(
                    n, ("batch", "expert", "seq"), (dp, ep, sp)
                )
                step = make_ep_grouped_train_step(
                    model, mesh, seq_axis="seq",
                    slots_per_owner=args.ep_slots,
                )
                batch_spec = P(("batch", "expert"), "seq")
            else:
                mesh = make_mesh(n, ("batch", "expert"), (dp, ep))
                step = make_ep_grouped_train_step(
                    model, mesh, slots_per_owner=args.ep_slots
                )
                batch_spec = P(("batch", "expert"), None)
            state = shard_ep_state(
                init_moe_state(model, seed=SEED, config=opt_config), mesh
            )
            batch_sharding = NamedSharding(mesh, batch_spec)
            place = lambda x, y: (
                jax.device_put(jnp.asarray(x), batch_sharding),
                jax.device_put(jnp.asarray(y), batch_sharding),
            )
            return step, state, place, model, lambda st: st.params
        mesh = make_mesh(n, ("batch", "expert"), (dp, ep))
        step = make_ep_train_step(model, mesh)
        state = shard_ep_state(
            init_moe_state(model, seed=SEED, config=opt_config), mesh
        )
        place = lambda x, y: shard_tp_batch(mesh, x, y)
        return step, state, place, model, lambda st: st.params

    if args.parallel == "fsdp_pl":
        from distributed_machine_learning_tpu.parallel.fsdp_perlayer import (
            make_fsdp_pl_lm_train_step,
            shard_fsdp_pl_state,
        )
        from distributed_machine_learning_tpu.parallel.tensor_parallel import (
            shard_tp_batch,
        )
        from distributed_machine_learning_tpu.train.lm_step import init_lm_state

        if args.batch_size % n:
            raise ValueError(
                f"--batch-size {args.batch_size} must be divisible by "
                f"the {n}-device data axis"
            )
        mesh = make_mesh(n)
        model = TransformerLM(**common)
        step = make_fsdp_pl_lm_train_step(
            model, mesh, fused_ce_chunks=args.fused_ce_chunks
        )
        state = shard_fsdp_pl_state(
            init_lm_state(model, seed=SEED, config=opt_config), mesh
        )
        place = lambda x, y: shard_tp_batch(mesh, x, y)
        return step, state, place, model, lambda st: st.params

    if args.parallel == "tp":
        from distributed_machine_learning_tpu.parallel.tensor_parallel import (
            make_tp_lm_train_step,
            shard_tp_batch,
            shard_tp_state,
        )
        from distributed_machine_learning_tpu.train.lm_step import init_lm_state

        mesh = make_mesh(n, ("batch", "model"), (1, n))
        model = TransformerLM(**common)
        # Build the step first: its validation (n_heads % model-axis size)
        # gives a clear error before any state is placed.
        step = make_tp_lm_train_step(model, mesh)
        state = shard_tp_state(init_lm_state(model, seed=SEED, config=opt_config), mesh)
        place = lambda x, y: shard_tp_batch(mesh, x, y)
        return step, state, place, model, lambda st: st.params

    if args.parallel == "pp":
        from distributed_machine_learning_tpu.parallel.pipeline import (
            init_pipeline_state,
            make_pp_lm_train_step,
            microbatch,
            shard_pp_state,
        )

        mesh = make_mesh(n, ("pipe",))
        model = TransformerLM(**common)
        # Each schedule picks its step builder and (for interleaved, whose
        # block stacking is permuted) its state init; the placement and
        # return tail are shared.
        if args.pp_schedule == "1f1b":
            from distributed_machine_learning_tpu.parallel.pipeline_1f1b import (  # noqa: E501
                make_pp_1f1b_lm_train_step,
            )

            step = make_pp_1f1b_lm_train_step(model, mesh, args.microbatches)
            raw_state = init_pipeline_state(model, seed=SEED,
                                            config=opt_config)
        elif args.pp_schedule == "interleaved":
            from distributed_machine_learning_tpu.parallel.pipeline_interleaved import (  # noqa: E501
                init_interleaved_state,
                make_pp_interleaved_lm_train_step,
            )

            v = args.pp_chunks or 2
            step = make_pp_interleaved_lm_train_step(
                model, mesh, args.microbatches, v
            )
            raw_state = init_interleaved_state(model, n, v, seed=SEED,
                                               config=opt_config)
        else:
            step = make_pp_lm_train_step(
                model, mesh, args.microbatches,
                overlap_update=getattr(args, "overlap_update", False),
            )
            raw_state = init_pipeline_state(model, seed=SEED,
                                            config=opt_config)
        state = shard_pp_state(raw_state, mesh)
        place = lambda x, y: microbatch(x, y, args.microbatches)
        return step, state, place, model, lambda st: st.params

    # 3d
    from distributed_machine_learning_tpu.parallel.parallel3d import (
        init_pipeline_state,
        make_3d_lm_train_step,
        make_3d_mesh,
        microbatch,
        shard_3d_batch,
        shard_3d_state,
    )

    if args.pp < 1 or args.tp < 1:
        raise ValueError(
            f"--pp and --tp must be >= 1, got pp={args.pp} tp={args.tp}"
        )
    if args.dp is not None and args.dp < 1:
        raise ValueError(f"--dp must be >= 1, got {args.dp}")
    dp = args.dp if args.dp is not None else max(n // (args.pp * args.tp), 1)
    if dp * args.pp * args.tp != n:
        raise ValueError(
            f"3-D mesh dp×pp×tp = {dp}×{args.pp}×{args.tp} = "
            f"{dp * args.pp * args.tp} must equal the device count {n} "
            "(a prefix-subset mesh would silently idle the rest)"
        )
    mesh = make_3d_mesh(dp, args.pp, args.tp)
    model = TransformerLM(**common)
    step = make_3d_lm_train_step(model, mesh, args.microbatches,
                                 zero1_dp=args.zero1_dp)
    state = shard_3d_state(
        init_pipeline_state(model, seed=SEED, config=opt_config), mesh,
        zero1_dp=args.zero1_dp,
    )
    place = lambda x, y: shard_3d_batch(mesh, *microbatch(x, y, args.microbatches))
    return step, state, place, model, lambda st: st.params


def main(argv=None) -> RunResult:
    from distributed_machine_learning_tpu.runtime.compile_cache import (
        configure_compile_cache,
    )
    from distributed_machine_learning_tpu.telemetry import (
        set_telemetry,
        startup,
        telemetry_from_flags,
    )

    record = startup.record()
    record.imports_done()
    with record.span("startup.runtime"):
        configure_compile_cache()
        parser = make_parser()
        args = parser.parse_args(argv)
        if args.telemetry_flush_every < 1:
            # Same parse-time validation the CNN parts get from parse_flags.
            parser.error(
                f"--telemetry-flush-every must be >= 1, got "
                f"{args.telemetry_flush_every}"
            )
        telemetry = telemetry_from_flags(args)
        prev_telemetry = None
        if telemetry is not None:
            prev_telemetry = set_telemetry(telemetry)
        ctx = initialize_from_flags(args.master_ip, args.rank, args.num_nodes)
        n_devices = jax.device_count()  # the first touch takes the chip
        record.note(devices=n_devices)
    try:
        read_model_config(args)  # the sizes the banner and the data use
        rank0_print(
            f"lm parallel={args.parallel} devices={n_devices} "
            f"d_model={args.d_model} layers={args.n_layers} "
            f"seq_len={args.seq_len} batch={args.batch_size} "
            # --attn auto/flash may dispatch the Pallas flash kernels.
            + device_banner(args.fused_update or args.attn != "dense")
        )
        # Eval runs for EVERY scheme and process count: params are
        # materialized to host numpy first (a cross-process all-gather
        # on multi-host runs), then every process runs the plain-jit
        # eval step over the identical held-out stream independently —
        # the reference's every-rank eval semantics
        # (``part1/main.py:62-77``).
        will_eval = bool(args.eval_batches)
        corpus = None
        eval_corpus = None
        with record.span("startup.data"):
            if args.data_dir is not None:
                from distributed_machine_learning_tpu.data.text import (
                    VOCAB_SIZE,
                    load_corpus,
                )

                corpus = load_corpus(args.data_dir)
                if args.vocab < VOCAB_SIZE:
                    rank0_print(
                        f"--data-dir is byte-level: vocab {args.vocab} -> "
                        f"{VOCAB_SIZE} (256 bytes + BOS)"
                    )
                    args.vocab = VOCAB_SIZE
                if will_eval:
                    from distributed_machine_learning_tpu.data.text import (
                        split_corpus,
                    )

                    corpus, eval_corpus = split_corpus(
                        corpus, eval_frac=0.1,
                        min_eval_tokens=args.seq_len + 1,
                    )
                    if len(eval_corpus) == len(corpus):
                        # split_corpus's documented degrade path: don't let
                        # training-set perplexity masquerade as held-out.
                        rank0_print(
                            "WARNING: corpus too small to hold out an eval "
                            "slice — eval will run on in-distribution "
                            "training windows"
                        )
                        rank0_print(f"corpus: {len(corpus)} tokens from "
                                    f"{args.data_dir}")
                    else:
                        rank0_print(
                            f"corpus: {len(corpus)} train tokens from "
                            f"{args.data_dir}, {len(eval_corpus)} held-out "
                            "eval tokens"
                        )
                else:
                    rank0_print(
                        f"corpus: {len(corpus)} tokens from {args.data_dir}"
                    )
        with record.span("startup.build", parallel=args.parallel,
                         devices=n_devices):
            step, state, place, model, params_fn = build(args)
        if telemetry is not None:
            # MFU cost model: ~6·P/token + attention term
            # (utils/flops.py).  Parameter count from the state when it
            # exposes a params tree (every scheme but flat-fsdp, whose
            # state is one sharded vector — throughput-only there).
            params_tree = getattr(state, "params", None)
            if params_tree is not None:
                from distributed_machine_learning_tpu.utils.flops import (
                    transformer_train_flops_per_token,
                )

                n_params = sum(
                    int(np.prod(leaf.shape))
                    for leaf in jax.tree_util.tree_leaves(params_tree)
                    if hasattr(leaf, "shape")
                )
                telemetry.flops_per_token = (
                    transformer_train_flops_per_token(
                        n_params, args.n_layers, args.d_model,
                        args.seq_len,
                    )
                )
        rng = np.random.default_rng(SEED)

        if corpus is not None:
            from distributed_machine_learning_tpu.data.text import (
                TextWindowLoader,
            )

            # Same convention as the synthetic path: every process
            # draws the identical FULL global batch (seeded), and
            # place() shards it over the mesh — so the global data
            # stream is process-count-invariant.  (TextWindowLoader's
            # rank/world striding is the per-host-slice alternative for
            # pipelines that assemble global arrays from local shards.)
            batches = lambda: iter(TextWindowLoader(
                corpus, args.batch_size, args.seq_len, seed=SEED,
            ))
        else:
            def batches():
                for _ in range(args.max_iters):
                    block = synthetic_tokens(
                        rng, args.batch_size, args.seq_len, args.vocab
                    )
                    yield block[:, :-1], block[:, 1:]

        if args.ckpt_dir and args.parallel == "fsdp":
            raise ValueError(
                "--ckpt-dir does not support the flat-vector fsdp state "
                "(FSDPState is not a TrainState); use --parallel fsdp_pl "
                "for checkpointable ZeRO-3"
            )
        # The pipeline schedules permute the stacked block layout but
        # share one tree structure — a resume under the wrong layout
        # would silently load permuted layers, so the layout is tagged
        # into the checkpoint and checked here.
        if args.parallel == "pp" and args.pp_schedule == "interleaved":
            from distributed_machine_learning_tpu.parallel.pipeline_interleaved import (  # noqa: E501
                interleaved_layout_tag,
            )

            run_layout = interleaved_layout_tag(jax.device_count(),
                                                args.pp_chunks or 2)
        elif args.parallel in ("pp", "3d"):
            run_layout = "pp-contiguous"
        else:
            run_layout = None
        def _resume(state):
            """State from the newest complete checkpoint (or unchanged
            when none exists) — re-runnable, so --resume auto can
            restore after every supervised restart."""
            from distributed_machine_learning_tpu.train.checkpoint import (
                checkpoint_config,
                checkpoint_layout,
                latest_checkpoint,
                restore_checkpoint,
            )

            if not args.ckpt_dir:
                raise ValueError("--resume requires --ckpt-dir")
            latest = latest_checkpoint(args.ckpt_dir)
            if latest is None:
                rank0_print(f"No checkpoint under {args.ckpt_dir}; "
                            "starting from scratch.")
            else:
                saved_layout = checkpoint_layout(latest)
                # Pre-tag checkpoints (saved before the layout field
                # existed) are all contiguous stackings — interleaved
                # postdates the tag — so None is compatible with the
                # contiguous layouts (including plain, non-pipeline
                # ones, whose run_layout is None too).
                compatible = saved_layout == run_layout or (
                    saved_layout is None and run_layout in
                    (None, "pp-contiguous")
                )
                if not compatible:
                    raise ValueError(
                        f"checkpoint parameter layout {saved_layout!r} "
                        f"does not match this run's {run_layout!r} "
                        "(same tree structure, permuted layers — "
                        "resume with the schedule/chunks/device-count "
                        "it was saved under)"
                    )
                saved_cfg = checkpoint_config(latest)
                if type(saved_cfg) is not type(state.config):
                    raise ValueError(
                        f"checkpoint was trained with "
                        f"{type(saved_cfg).__name__} but this run uses "
                        f"--optimizer {args.optimizer}; the LM resume "
                        "path requires a matching optimizer (the CNN "
                        "parts' cross-optimizer reset lives in "
                        "cli/common.py)"
                    )
                # The placed state doubles as the abstract template, so
                # placed leaves (dp's replicated state, fsdp_pl/tp/pp's
                # sharded ones) restore straight into their shardings.
                # Leaves a scheme keeps UNCOMMITTED must stay
                # uncommitted — a restore pins them to one device, which
                # then conflicts with the mesh-sharded batch at dispatch
                # — so those take a host round-trip back to plain
                # relocatable arrays.
                import jax.numpy as _jnp

                restored = restore_checkpoint(latest, abstract_state=state,
                                              files_verified=True)
                # This run's hyperparameters win (same semantics as the
                # CNN path): carrying the current config also keeps the
                # static config leaves identical for the tree_map below,
                # which would otherwise reject two TrainStates whose
                # configs differ in any field (e.g. a routine --lr
                # adjustment on resume).
                restored = restored.replace(config=state.config)

                from distributed_machine_learning_tpu.train.checkpoint import (  # noqa: E501
                    fresh_buffers,
                )

                def _match_commitment(orig, new):
                    if getattr(orig, "committed", True):
                        return new
                    # fresh_buffers is load-bearing: donating the bare
                    # asarray corrupts the heap when the host buffer
                    # happens to be 64-byte aligned (zero-copied, then
                    # freed with XLA's allocator) — see its docstring.
                    return fresh_buffers(_jnp.asarray(jax.device_get(new)))

                state = jax.tree_util.tree_map(
                    _match_commitment, state, restored
                )
                rank0_print(
                    f"Resumed from {latest} (step "
                    f"{int(jax.device_get(state.step))})"
                )
            return state

        if args.resume:
            with record.span("startup.resume"):
                state = _resume(state)

        def run_once(s):
            """Train + final save; the unit a supervised restart retries.
            The shared driver owns the measurement protocol (iter-0-
            excluded timing, loss cadence, summary) — one copy for CNN
            and LM."""
            if getattr(args, "loss_scale", "none") == "dynamic":
                from distributed_machine_learning_tpu.train.lm_step import (
                    with_dynamic_scale,
                )

                s = with_dynamic_scale(s)
            s, _ = train_epoch(
                step, s, batches(), place_batch=place,
                max_iters=args.max_iters,
            )
            from distributed_machine_learning_tpu.train.lm_step import (
                unwrap_dynamic_scale,
            )

            s = unwrap_dynamic_scale(s)
            if args.ckpt_dir:
                from distributed_machine_learning_tpu.train.checkpoint import (
                    save_checkpoint,
                )

                path = save_checkpoint(args.ckpt_dir, s, layout=run_layout)
                rank0_print(f"Saved checkpoint to {path}")
            return s

        if args.resume == "auto":
            # Coarse-grained supervision: on any crash, restore the
            # newest complete checkpoint (possibly none — fresh start)
            # and retrain, up to --max-restarts times.  The fine-grained
            # cursor-exact machinery is runtime/supervisor.py::
            # supervised_train; the CNN parts wire it per-epoch.
            from distributed_machine_learning_tpu.runtime.supervisor import (
                run_attempts,
            )

            def attempt(restart_idx):
                s = state
                if restart_idx > 0:
                    _, fresh, *_ = build(args)
                    s = _resume(fresh)
                return run_once(s)

            state = run_attempts(attempt, max_restarts=args.max_restarts)
        else:
            state = run_once(state)
        if args.eval_batches:
            from distributed_machine_learning_tpu.data.text import (
                eval_windows,
            )
            from distributed_machine_learning_tpu.train.lm_step import (
                make_lm_eval_step,
            )
            from distributed_machine_learning_tpu.train.loop import (
                evaluate_lm,
            )

            if corpus is not None:
                ev = eval_windows(eval_corpus, args.batch_size,
                                  args.seq_len, args.eval_batches)
            else:
                ev_rng = np.random.default_rng(SEED + 1)
                ev = (
                    (b[:, :-1], b[:, 1:])
                    for b in (
                        synthetic_tokens(ev_rng, args.batch_size,
                                         args.seq_len, args.vocab)
                        for _ in range(args.eval_batches)
                    )
                )
            params = params_fn(state)
            if args.parallel in ("pp", "3d"):
                # Pipeline layouts stack the blocks along a leading
                # layer dim; restore the per-layer tree the plain model
                # apply expects.  The interleaved schedule stacks in its
                # chunk-major device order, so it has its own inverse.
                if (args.parallel == "pp"
                        and args.pp_schedule == "interleaved"):
                    from distributed_machine_learning_tpu.parallel.pipeline_interleaved import (  # noqa: E501
                        unstack_interleaved,
                    )

                    params = unstack_interleaved(
                        params, args.n_layers, jax.device_count(),
                        args.pp_chunks or 2,
                    )
                else:
                    from distributed_machine_learning_tpu.parallel.pipeline import (  # noqa: E501
                        unstack_lm_params,
                    )

                    params = unstack_lm_params(params, args.n_layers)
            # Materialize params on the host so the eval jit owns its
            # own placement: sharded leaves (fsdp_pl/tp) assemble, and
            # on multi-host runs the cross-process all-gather replaces
            # the old single-process gate — every process then runs the
            # identical eval stream independently, per the reference's
            # every-rank eval loop (``part1/main.py:62-77``).
            if jax.process_count() > 1:
                from jax.experimental import multihost_utils

                # tiled=True is the required mode for global (non-fully-
                # addressable) arrays: it re-jits each leaf to a fully
                # replicated sharding and returns the whole value as
                # host numpy on every process.
                params = multihost_utils.process_allgather(params,
                                                           tiled=True)
            else:
                params = jax.device_get(params)
            import contextlib

            with (telemetry.span("eval") if telemetry is not None
                  else contextlib.nullcontext()):
                evaluate_lm(make_lm_eval_step(model), params, ev)
    finally:
        if telemetry is not None:
            set_telemetry(prev_telemetry)
            telemetry.close()
            rank0_print(f"Telemetry written to {args.telemetry_dir}")
        ctx.shutdown()
    return RunResult(state=state, train_step=step, place_batch=place)


if __name__ == "__main__":
    main()
