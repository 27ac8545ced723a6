"""LM throughput benchmark — tokens/sec through the transformer train
step on the TPU, per attention implementation.

Secondary to ``bench.py`` (the driver's reference-protocol CNN bench):
this one characterizes the framework's beyond-parity surface — the
decoder-only LM with dense vs Pallas-flash attention — so kernel wins
are measured, not assumed.  Same honest-measurement design as bench.py:
the timed iterations run as ONE jitted ``lax.scan`` over device-resident
batches, timed around a host fetch (dispatch is asynchronous: a timing
without one measures the enqueue).  Refuses any backend but a TPU and
prints the device with every JSON line.

Usage::

    python bench_lm.py                         # default config
    python bench_lm.py --seq-len 2048 --attn flash
    python bench_lm.py --attn dense,flash      # comparison table
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from distributed_machine_learning_tpu.models.transformer import TransformerLM
from distributed_machine_learning_tpu.train.lm_step import (
    _lm_step_impl,
    init_lm_state,
)

TIMED_ITERS = 20


# Shared with bench/spec_trained.py via the package harness (one copy
# of the serving cast + chained-dispatch fit).
from distributed_machine_learning_tpu.bench.harness import (  # noqa: E402
    cast_serving_params as _cast_params,
    two_point_dispatch as _two_point_dispatch,
)


def bench_one(attn: str, args) -> tuple[float, int]:
    """(tokens/sec, n_params) for one attention implementation."""
    model = TransformerLM(
        vocab_size=args.vocab,
        d_model=args.d_model,
        n_layers=args.n_layers,
        n_heads=args.n_heads,
        n_kv_heads=args.n_kv_heads,
        attn_impl=attn,
        compute_dtype=jnp.bfloat16 if args.bf16 else jnp.float32,
        remat=args.remat,
        remat_policy=args.remat_policy,
    )
    from distributed_machine_learning_tpu.train.sgd import SGDConfig

    state = init_lm_state(
        model, config=SGDConfig(momentum_dtype=args.momentum_dtype)
    )
    rng = np.random.default_rng(0)
    toks = rng.integers(
        0, args.vocab, (TIMED_ITERS, args.batch, args.seq_len + 1)
    ).astype(np.int32)
    dx = jax.device_put(jnp.asarray(toks[:, :, :-1]))
    dy = jax.device_put(jnp.asarray(toks[:, :, 1:]))

    from functools import partial

    from jax import lax

    step = partial(
        _lm_step_impl, model, axis_names=(),
        fused_ce_chunks=args.fused_ce_chunks,
    )

    @jax.jit
    def epoch(state, xs, ys):
        def body(s, xy):
            s, loss = step(s, xy[0], xy[1])
            return s, loss

        state, losses = lax.scan(body, state, (xs, ys))
        return state, losses[-1]

    # Compile + warm-up (excluded, like the reference's iteration 0).
    _, loss = epoch(state, dx, dy)
    if not np.isfinite(float(loss)):
        raise RuntimeError("bench_lm diverged; refusing to report")

    def timed(n_dispatches):
        """Best-of-reps seconds: n async same-epoch dispatches + 1 fetch.
        Every dispatch starts from the same initial state, so numerics
        match the canonical epoch regardless of n."""
        best = float("inf")
        for _ in range(args.reps):
            t0 = time.perf_counter()
            for _ in range(n_dispatches):
                _, loss = epoch(state, dx, dy)
            float(loss)  # host fetch forces completion of the queue
            best = min(best, time.perf_counter() - t0)
        return best

    # Two-point fit cancels the constant per-measurement dispatch+fetch
    # cost (bench.py's methodology).
    from distributed_machine_learning_tpu.bench.harness import two_point_fit

    best = two_point_fit(timed, args.chain)
    tokens = TIMED_ITERS * args.batch * args.seq_len
    n_params = sum(
        int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(state.params)
    )
    # The input embedding is a gather, not a matmul — drop it from the
    # 6P matmul-FLOPs term (at 32k vocab × d2048 it would otherwise
    # inflate MFU ~12%).  The lm_head IS a matmul and stays counted.
    # Assumes the UNTIED embed + lm_head layout TransformerLM uses; if
    # weight tying is ever added, this subtraction must become
    # conditional or it would remove the (real) lm_head matmul instead.
    n_params -= args.vocab * args.d_model
    return tokens / best, n_params


def bench_decode(args) -> None:
    """Decode-path benchmark: prefill vs steady-state tokens/sec.

    Methodology: build two generate fns differing only in
    ``max_new_tokens`` (N_small, N_big); each is timed with the same
    two-point dispatch fit as the train benches (cancelling the fixed
    per-measurement cost), and the per-token steady-state time is the slope
    ``(T_big − T_small) / (N_big − N_small)`` — prefill, sampling setup,
    and any constant overhead cancel in the subtraction.  Prefill time
    is then ``T_small − N_small·t_tok``.  Weights are cast to the
    compute dtype first (serving configuration: decode is bound by HBM
    reads of weights + KV cache, so fp32 master params would halve
    throughput).
    """
    from distributed_machine_learning_tpu.inference.generate import (
        make_generate_fn,
    )

    kv_dtype = (
        jnp.dtype(args.kv_cache_dtype) if args.kv_cache_dtype else None
    )
    if args.moe:
        from distributed_machine_learning_tpu.models.moe import (
            MoETransformerLM,
        )

        model = MoETransformerLM(
            vocab_size=args.vocab, d_model=args.d_model,
            n_layers=args.n_layers, n_heads=args.n_heads,
            n_kv_heads=args.n_kv_heads, n_experts=args.n_experts,
            compute_dtype=jnp.bfloat16 if args.bf16 else jnp.float32,
            kv_cache_dtype=kv_dtype,
        )
    else:
        model = TransformerLM(
            vocab_size=args.vocab,
            d_model=args.d_model,
            n_layers=args.n_layers,
            n_heads=args.n_heads,
            n_kv_heads=args.n_kv_heads,
            compute_dtype=jnp.bfloat16 if args.bf16 else jnp.float32,
            kv_cache_dtype=kv_dtype,
        )
    state = init_lm_state(model)
    dtype = jnp.bfloat16 if args.bf16 else jnp.float32
    master = state.params
    # A serving bench must not hold training state: the f32 momentum
    # buffer alone is ~2 GB at this width, and keeping it (plus the f32
    # master params after the cast) resident is the difference between
    # the f32-cache 32k config fitting the 16 GB chip or OOMing.
    del state
    # Shared serving pipeline (bench/harness.py): int8 quantization from
    # the f32 master params, or the compute-dtype cast.
    from distributed_machine_learning_tpu.bench.harness import (
        prepare_serving_params,
    )

    params = prepare_serving_params(
        master, "int8" if args.quant else None, dtype
    )
    del master
    params = jax.block_until_ready(params)
    rng = np.random.default_rng(0)
    prompt = jax.device_put(jnp.asarray(
        rng.integers(0, args.vocab, (args.batch, args.prompt_len)),
        jnp.int32,
    ))
    key = jax.random.PRNGKey(0)

    n_small, n_big = 32, args.gen_tokens
    if n_big <= n_small:
        raise ValueError(f"--gen-tokens must exceed {n_small}")

    def timed_for(n_tokens):
        fn = make_generate_fn(model, n_tokens, temperature=0.0,
                              quantize="int8" if args.quant else None)
        jax.block_until_ready(fn(params, prompt, key))
        return _two_point_dispatch(
            lambda: fn(params, prompt, key),
            lambda out: np.asarray(out[0, -1]),  # fetch drains the queue
            args.reps, args.chain,
        )

    t_small = timed_for(n_small)
    t_big = timed_for(n_big)
    t_tok = (t_big - t_small) / (n_big - n_small)
    # An n-token generate runs n−1 scanned decode steps (token 0 comes
    # from the prefill logits), so prefill = T − (n−1)·t_tok.
    t_prefill = max(t_small - (n_small - 1) * t_tok, 0.0)
    print(json.dumps({
        "metric": "lm_decode_tokens_per_sec",
        "value": round(args.batch / t_tok, 1),
        "unit": "tokens/sec",
        "per_sequence_tokens_per_sec": round(1.0 / t_tok, 1),
        "prefill_tokens_per_sec": round(
            args.batch * args.prompt_len / t_prefill, 1
        ) if t_prefill > 0 else None,
        "prefill_ms": round(t_prefill * 1e3, 2),
        "ms_per_decode_step": round(t_tok * 1e3, 3),
        "config": {
            "d_model": args.d_model, "n_layers": args.n_layers,
            "n_heads": args.n_heads, "n_kv_heads": args.n_kv_heads,
            "vocab": args.vocab, "batch": args.batch,
            "prompt_len": args.prompt_len, "gen_tokens": args.gen_tokens,
            "bf16": args.bf16, "kv_cache_dtype": args.kv_cache_dtype,
            "quant": "int8" if args.quant else None,
            "moe": args.n_experts if args.moe else None,
        },
        "device": args.device,
    }))

    if args.spec_gamma > 0:
        # Speculative-decoding FLOOR (random draft, acceptance ~ 0): the
        # reproducible command behind docs/PERF.md's envelope — a real
        # draft only raises tokens/round, never the per-round cost.
        # Any --batch: rows ride per-row frontiers (batched speculation);
        # the floor is per ROW, so total tok/s scales with the batch.
        from distributed_machine_learning_tpu.inference.speculative import (
            make_speculative_generate_fn,
        )

        draft = TransformerLM(
            vocab_size=args.vocab, d_model=args.spec_draft_d_model,
            n_layers=args.spec_draft_n_layers, n_heads=args.n_heads,
            n_kv_heads=args.n_kv_heads, compute_dtype=dtype,
            kv_cache_dtype=kv_dtype,
        )
        dparams = _cast_params(init_lm_state(draft, seed=11).params, dtype)

        def spec_timed_for(n_tokens):
            fn = make_speculative_generate_fn(
                model, draft, n_tokens, gamma=args.spec_gamma,
                quantize="int8" if args.quant else None,
            )
            jax.block_until_ready(fn(params, dparams, prompt, key))
            return _two_point_dispatch(
                lambda: fn(params, dparams, prompt, key),
                lambda out: np.asarray(out[0, -1]),
                args.reps, args.chain,
            )

        st_small = spec_timed_for(n_small)
        st_big = spec_timed_for(n_big)
        st_tok = (st_big - st_small) / (n_big - n_small)
        if st_tok <= 0:
            # Cross-fit jitter (two_point_fit guards within one fit,
            # not across the two): fail loudly like harness.py's own
            # slope guard rather than print a negative rate.
            raise RuntimeError(
                f"speculative slope non-positive ({st_tok:.2e}s): "
                "host jitter swamped the measurement; raise "
                "--gen-tokens and/or --reps"
            )
        print(json.dumps({
            "metric": "lm_speculative_decode_floor_tokens_per_sec",
            "value": round(args.batch / st_tok, 1),
            "unit": "tokens/sec",
            "per_sequence_tokens_per_sec": round(1.0 / st_tok, 1),
            "ms_per_token": round(st_tok * 1e3, 3),
            "vs_vanilla": round(t_tok / st_tok, 3),
            "note": "random draft: acceptance~0 floor of the envelope",
            "config": {"gamma": args.spec_gamma, "batch": args.batch,
                       "draft_d_model": args.spec_draft_d_model,
                       "draft_n_layers": args.spec_draft_n_layers,
                       "kv_cache_dtype": args.kv_cache_dtype,
                       "quant": "int8" if args.quant else None},
            "device": args.device,
        }))


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--attn", default="dense",
                   help="comma-separated: dense, flash")
    p.add_argument("--d-model", dest="d_model", default=512, type=int)
    p.add_argument("--n-layers", dest="n_layers", default=8, type=int)
    p.add_argument("--n-heads", dest="n_heads", default=8, type=int)
    p.add_argument("--n-kv-heads", dest="n_kv_heads", default=None, type=int)
    p.add_argument("--vocab", default=32000, type=int)
    p.add_argument("--seq-len", dest="seq_len", default=1024, type=int)
    p.add_argument("--batch", default=8, type=int)
    p.add_argument("--reps", default=3, type=int)
    p.add_argument("--chain", default=4, type=int,
                   help="chained epoch dispatches per measurement; per-"
                        "epoch time is the (chain vs 1) slope, cancelling "
                        "the constant per-measurement dispatch+fetch cost")
    p.add_argument("--fused-ce-chunks", dest="fused_ce_chunks",
                   default=None, type=int)
    p.add_argument("--momentum-dtype", dest="momentum_dtype", default=None,
                   help="SGD momentum-buffer storage dtype (e.g. bfloat16) "
                        "— optimizer-state memory is what bounds depth at "
                        "realistic width on one chip (train/sgd.py)")
    p.add_argument("--remat", action="store_true",
                   help="rematerialization (selective 'mlp' policy by "
                        "default — attention residuals stay saved) — lets "
                        "realistic-width long-context configs fit the "
                        "chip; reported MFU counts model FLOPs only")
    p.add_argument("--remat-policy", dest="remat_policy", default="mlp",
                   choices=("mlp", "block"),
                   help="'mlp' (selective: save attention residuals, remat "
                        "only LN2+MLP — backward never re-runs the O(L^2) "
                        "attention forward) or 'block' (whole-block, "
                        "maximal memory savings)")
    p.add_argument("--fp32", dest="bf16", action="store_false",
                   help="run the trunk in fp32 (default bfloat16)")
    p.add_argument("--quant", action="store_true",
                   help="with --decode: weight-only int8 serving (the "
                        "Pallas int8 matmul kernel, ops/quant.py)")
    p.add_argument("--decode", action="store_true",
                   help="benchmark the KV-cached decode path instead of "
                        "the train step (prefill vs steady-state tok/s)")
    p.add_argument("--moe", action="store_true",
                   help="with --decode: serve a Switch-MoE model "
                        "(dropless grouped expert path; composes with "
                        "--quant int8 expert weights and --spec-gamma)")
    p.add_argument("--n-experts", dest="n_experts", default=8, type=int)
    p.add_argument("--spec-gamma", dest="spec_gamma", default=0, type=int,
                   help="with --decode: ALSO measure speculative decoding "
                        "at this gamma with a random draft (the "
                        "acceptance~0 FLOOR of the envelope -- "
                        "docs/PERF.md; any --batch via per-row frontiers)")
    p.add_argument("--spec-draft-d-model", dest="spec_draft_d_model",
                   default=512, type=int)
    p.add_argument("--spec-draft-n-layers", dest="spec_draft_n_layers",
                   default=2, type=int)
    p.add_argument("--prompt-len", dest="prompt_len", default=2048, type=int)
    p.add_argument("--gen-tokens", dest="gen_tokens", default=160, type=int)
    p.add_argument("--kv-cache-dtype", dest="kv_cache_dtype", default=None,
                   help="decode KV-cache storage dtype ablation "
                        "(e.g. float32; default = compute dtype)")
    args = p.parse_args()
    from distributed_machine_learning_tpu.bench.harness import (
        chip_mfu,
        require_tpu,
    )
    from distributed_machine_learning_tpu.runtime.compile_cache import (
        configure_compile_cache,
    )

    configure_compile_cache()
    args.device = require_tpu()

    if args.spec_gamma > 0 and not args.decode:
        raise ValueError(
            "--spec-gamma is a decode-path option; pass --decode with it "
            "(any --batch: per-row frontiers, inference/speculative.py)"
        )
    if args.quant and not args.decode:
        raise ValueError(
            "--quant is a decode-path option (weight-only int8 serving); "
            "pass --decode with it — the train benches run full precision"
        )
    if args.moe and not args.decode:
        raise ValueError(
            "--moe here is a decode-path option (the MoE TRAIN benches "
            "are cli.lm --parallel ep and bench/lm_sweep --scheme ep)"
        )
    if args.decode:
        bench_decode(args)
        return

    from distributed_machine_learning_tpu.utils.flops import (
        transformer_train_flops_per_token,
    )

    for attn in args.attn.split(","):
        tps, n_params = bench_one(attn.strip(), args)
        # Two FLOPs conventions (utils/flops.py): "causal" counts the
        # attention term at the work a causal kernel performs (T/2);
        # "full" is the PaLM-style full-score-matrix convention most
        # published MFU tables use.  Report both — they differ by up to
        # 2× on the attention term at long context.
        fpt = transformer_train_flops_per_token(
            n_params, args.n_layers, args.d_model, args.seq_len, causal=True
        )
        fpt_full = transformer_train_flops_per_token(
            n_params, args.n_layers, args.d_model, args.seq_len, causal=False
        )
        print(json.dumps({
            "metric": f"lm_{attn.strip()}_train_tokens_per_sec",
            "value": round(tps, 1),
            "unit": "tokens/sec",
            # Keyed by convention (like mfu_*) — the r02 artifacts'
            # "tflops_per_sec" used the full convention WITH embedding
            # params, so neither new key is silently comparable to it.
            "tflops_causal": round(tps * fpt / 1e12, 1),
            "tflops_full": round(tps * fpt_full / 1e12, 1),
            "mfu_causal": round(chip_mfu(tps * fpt, args.device), 3),
            "mfu_full": round(chip_mfu(tps * fpt_full, args.device), 3),
            "config": {
                "d_model": args.d_model, "n_layers": args.n_layers,
                "seq_len": args.seq_len, "batch": args.batch,
                "vocab": args.vocab, "bf16": args.bf16,
                "n_kv_heads": args.n_kv_heads,
                "fused_ce_chunks": args.fused_ce_chunks,
            },
            "device": args.device,
        }))


if __name__ == "__main__":
    main()
