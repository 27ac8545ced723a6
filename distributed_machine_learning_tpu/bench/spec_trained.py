"""Trained-draft speculative serving bench — the acceptance-real numbers.

`bench_lm.py --decode --spec-gamma` measures the random-draft FLOOR
(acceptance ≈ 0); this bench completes the envelope with a REAL target
+ draft pair (train via ``cli.lm --ckpt-dir``, distill the draft via
``cli.distill`` — one command each), serving prompts drawn from the
same corpus:

- vanilla greedy vs speculative γ ∈ {4, 6}, batch 1 AND batch 8
  (eight DIFFERENT corpus prompts riding per-row frontiers — the
  batched-speculation headline row, VERDICT r4 item 1);
- one sampled-acceptance point (temperature/top-p warps active in the
  Leviathan rule) vs plain sampled decoding — VERDICT r4 item 3's
  measured companion to the distributional tests.

Timing: the decode bench's two-point method — per-token time is the
slope between two generation lengths (32 vs --gen-tokens), each timed
with chained dispatches + one fetch (cancels the fixed per-measurement
cost).

Reproduce end-to-end::

    python -m distributed_machine_learning_tpu.cli.lm --parallel dp \
        --data-dir <corpus> --d-model 2048 --n-layers 8 --n-heads 16 \
        --n-kv-heads 4 --seq-len 512 --batch-size 8 --max-iters 500 \
        --compute-dtype bfloat16 --ckpt-dir <target>
    python -m distributed_machine_learning_tpu.cli.distill \
        --target-ckpt-dir <target> --d-model 2048 --n-layers 8 \
        --n-heads 16 --n-kv-heads 4 --draft-d-model 128 \
        --draft-n-layers 2 --data-dir <corpus> --seq-len 512 \
        --batch-size 8 --max-iters 1500 --ckpt-dir <draft>
    python -m distributed_machine_learning_tpu.bench.spec_trained \
        --target-ckpt-dir <target> --draft-ckpt-dir <draft> \
        --data-dir <corpus>
"""

from __future__ import annotations

import argparse
import json

import jax
import jax.numpy as jnp
import numpy as np


def _prompts(data_dir: str, batch: int, prompt_len: int):
    """[batch, prompt_len] byte windows from the corpus, BOS-led, at
    deterministic spread-out offsets — real text, distinct rows."""
    from distributed_machine_learning_tpu.data.text import BOS, load_corpus

    corpus = load_corpus(data_dir)
    span = len(corpus) - prompt_len - 1
    if span < batch:
        # Distinct rows are the CONTRACT: identical prompts would make
        # the per-row frontiers move in lockstep and overstate batched
        # acceptance.
        raise ValueError(
            f"corpus ({len(corpus)} tokens) too small for {batch} "
            f"distinct {prompt_len}-token prompts"
        )
    rows = []
    for b in range(batch):
        off = (b * 7919) % span
        rows.append(
            np.concatenate([[BOS], corpus[off:off + prompt_len - 1]])
        )
    return jnp.asarray(np.stack(rows), jnp.int32)


def main() -> None:
    from distributed_machine_learning_tpu.runtime.compile_cache import (
        configure_compile_cache,
    )

    configure_compile_cache()
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--target-ckpt-dir", dest="target_ckpt_dir",
                   required=True)
    p.add_argument("--draft-ckpt-dir", dest="draft_ckpt_dir", required=True)
    p.add_argument("--data-dir", dest="data_dir", required=True)
    p.add_argument("--d-model", dest="d_model", default=2048, type=int)
    p.add_argument("--n-layers", dest="n_layers", default=8, type=int)
    p.add_argument("--n-heads", dest="n_heads", default=16, type=int)
    p.add_argument("--n-kv-heads", dest="n_kv_heads", default=4, type=int)
    p.add_argument("--draft-d-model", dest="draft_d_model", default=128,
                   type=int)
    p.add_argument("--draft-n-layers", dest="draft_n_layers", default=2,
                   type=int)
    p.add_argument("--draft-n-heads", dest="draft_n_heads", default=8,
                   type=int)
    p.add_argument("--prompt-len", dest="prompt_len", default=512, type=int)
    p.add_argument("--gen-tokens", dest="gen_tokens", default=160, type=int)
    p.add_argument("--gammas", default="4,6")
    p.add_argument("--batches", default="1,8")
    p.add_argument("--reps", default=3, type=int)
    p.add_argument("--chain", default=4, type=int)
    p.add_argument("--quant", action="store_true",
                   help="serve the TARGET weight-only int8 (the draft "
                        "stays bf16 — it is small and runs the most "
                        "steps per round, latency-bound not weight-"
                        "bound); composes with batched speculation")
    p.add_argument("--kv-cache-dtype", dest="kv_cache_dtype", default=None,
                   help="decode cache storage dtype for BOTH models")
    args = p.parse_args()

    from distributed_machine_learning_tpu.bench.harness import (
        cast_serving_params,
        length_slope_fit,
        prepare_serving_params,
        two_point_dispatch,
    )

    from distributed_machine_learning_tpu.cli.generate import (
        _restore_lm_params,
    )
    from distributed_machine_learning_tpu.data.text import VOCAB_SIZE
    from distributed_machine_learning_tpu.inference.generate import (
        make_generate_fn,
    )
    from distributed_machine_learning_tpu.inference.speculative import (
        make_speculative_generate_fn,
    )
    from distributed_machine_learning_tpu.models.transformer import (
        TransformerLM,
    )

    kv_dtype = (
        jnp.dtype(args.kv_cache_dtype) if args.kv_cache_dtype else None
    )
    target = TransformerLM(
        vocab_size=VOCAB_SIZE, d_model=args.d_model,
        n_layers=args.n_layers, n_heads=args.n_heads,
        n_kv_heads=args.n_kv_heads, compute_dtype=jnp.bfloat16,
        kv_cache_dtype=kv_dtype,
    )
    draft = TransformerLM(
        vocab_size=VOCAB_SIZE, d_model=args.draft_d_model,
        n_layers=args.draft_n_layers, n_heads=args.draft_n_heads,
        compute_dtype=jnp.bfloat16, kv_cache_dtype=kv_dtype,
    )
    quant = "int8" if args.quant else None
    tparams = prepare_serving_params(
        _restore_lm_params(args.target_ckpt_dir, args.n_layers), quant
    )
    dparams = cast_serving_params(
        _restore_lm_params(args.draft_ckpt_dir, args.draft_n_layers),
        jnp.bfloat16,
    )
    key = jax.random.PRNGKey(0)
    n_small = 32

    def slope(make_fn, prompt):
        def timed_for(n_tokens):
            fn = make_fn(n_tokens)
            jax.block_until_ready(fn(prompt, key))
            return two_point_dispatch(
                lambda: fn(prompt, key),
                lambda out: np.asarray(out[0, -1]),
                args.reps, args.chain,
            )

        # length_slope_fit validates n_small < gen_tokens and guards
        # the jitter cases (bench/harness.py — one fit, every bench).
        return length_slope_fit(timed_for, n_small, args.gen_tokens)

    # Each factory call builds ONE jitted program per length; the inner
    # lambda only binds params (a fresh make_* per dispatch would
    # retrace every call — the first cut of this bench did exactly
    # that and read compile-cache jitter as negative slopes).
    def vanilla_fn(n, **warp):
        g = make_generate_fn(target, n, quantize=quant, **warp)
        return lambda pr, k: g(tparams, pr, k)

    def spec_fn(n, gamma, **warp):
        g = make_speculative_generate_fn(target, draft, n, gamma=gamma,
                                         quantize=quant, **warp)
        return lambda pr, k: g(tparams, dparams, pr, k)

    for batch in (int(b) for b in args.batches.split(",")):
        prompt = _prompts(args.data_dir, batch, args.prompt_len)
        t_van = slope(vanilla_fn, prompt)
        print(json.dumps({
            "metric": "spec_trained_vanilla_tokens_per_sec",
            "value": round(batch / t_van, 1), "batch": batch,
            "quant": quant, "kv_cache_dtype": args.kv_cache_dtype,
            "per_sequence_tokens_per_sec": round(1 / t_van, 1),
            "ms_per_step": round(t_van * 1e3, 3),
        }), flush=True)
        for gamma in (int(g) for g in args.gammas.split(",")):
            t_spec = slope(
                lambda n, g=gamma: spec_fn(n, g), prompt
            )
            print(json.dumps({
                "metric": "spec_trained_tokens_per_sec",
                "value": round(batch / t_spec, 1), "batch": batch,
                "gamma": gamma, "quant": quant,
                "kv_cache_dtype": args.kv_cache_dtype,
                "per_sequence_tokens_per_sec": round(1 / t_spec, 1),
                "vs_vanilla": round(t_van / t_spec, 3),
            }), flush=True)

    # Sampled-acceptance point: the Leviathan accept/resample rule under
    # real warps, vs plain sampled decoding (batch 1).
    prompt = _prompts(args.data_dir, 1, args.prompt_len)
    warp = dict(temperature=0.8, top_p=0.9)
    t_plain = slope(lambda n: vanilla_fn(n, **warp), prompt)
    t_spec = slope(lambda n: spec_fn(n, 4, **warp), prompt)
    print(json.dumps({
        "metric": "spec_trained_sampled_tokens_per_sec",
        "value": round(1 / t_spec, 1), "gamma": 4, "quant": quant,
        "kv_cache_dtype": args.kv_cache_dtype, **warp,
        "plain_sampled_tokens_per_sec": round(1 / t_plain, 1),
        "vs_plain_sampled": round(t_plain / t_spec, 3),
    }), flush=True)


if __name__ == "__main__":
    main()
