"""Measure the tiered int8-KV-cache dispatch question (VERDICT r4 #7).

The int8 decode dispatch (``models/transformer.py``) always takes the
scale-folding einsum, which reads ALL S allocated cache slots; the
Pallas kernel's frontier clamp reads O(pos).  The einsum is ~2.8×
cheaper per byte (measured r4), so it loses only while pos/S < ~0.36 —
a transient early phase — and r4 dismissed a two-tier ``lax.switch``
as "not worth its compile cost" WITHOUT a number.  This bench produces
the numbers for both sides of that call:

1. per-step attention time, einsum vs int8-kernel, at a ladder of
   pos/S fill fractions (the kernel's O(pos) advantage vs the einsum's
   cheaper bytes — locates the real crossover);
2. the compile cost of a two-tier ``lax.cond`` decode program (the
   dispatch _INT8_TIERED_DISPATCH enables) vs the single-path program,
   at a realistic layer count (the cond is traced per layer).

Timing: the attention ops are µs-scale, below the host's per-dispatch
cost and its jitter, so each measurement runs N data-dependent
iterations inside ONE jitted ``lax.scan`` (the step's output feeds the
next step's query — nothing can be hoisted or elided) and the per-op
time is the two-point slope over scan lengths (N vs 2N), which cancels
the single dispatch+fetch.  The first cut of this bench used chained
dispatches per op and read dispatch jitter, not op time.

Run on the TPU::

    python -m distributed_machine_learning_tpu.bench.int8_tier \
        --s-alloc 32768 --fracs 0.05,0.2,0.36,0.7,0.95
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np


def _time_op(op, q, *rest, reps: int = 3, iters: int = 200):
    """Per-op seconds for ``op(q, *rest) -> array shaped like q``: N
    data-dependent iterations inside one jitted scan (q threads
    through), per-op time from the (N vs 2N)-scan slope — see the
    module docstring for why chained dispatches cannot measure this."""
    from jax import lax

    def make(n):
        @jax.jit
        def run(q0, *r):
            def body(qc, _):
                return op(qc, *r).astype(q0.dtype), ()

            qn, _ = lax.scan(body, q0, None, length=n)
            return qn

        return run

    from distributed_machine_learning_tpu.bench.harness import (
        length_slope_fit,
    )

    def timed(n):
        run = make(n)
        jax.block_until_ready(run(q, *rest))  # compile + warm
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            np.asarray(run(q, *rest)[..., 0])  # fetch closes the timing
            best = min(best, time.perf_counter() - t0)
        return best

    # One slope fit for every bench (bench/harness.py): per-op seconds
    # from the N-vs-2N scan lengths, jitter-guarded.
    return length_slope_fit(timed, iters, 2 * iters)


def bench_attention_ladder(s_alloc: int, fracs, hkv: int, rep: int,
                           d: int, reps: int, chain: int):
    """Single-token int8 cached attention: einsum (full-S reads) vs the
    Pallas kernel (frontier-clamped O(pos) reads) at each fill
    fraction."""
    from distributed_machine_learning_tpu.models.transformer import (
        _cached_attention_quant,
    )
    from distributed_machine_learning_tpu.ops.pallas.decode_attention import (
        cached_flash_attention,
    )

    rng = np.random.default_rng(0)
    B, H = 1, hkv * rep
    q = jnp.asarray(rng.standard_normal((B, 1, H, d)), jnp.bfloat16)
    k_int = jnp.asarray(
        rng.integers(-127, 127, (B, hkv, s_alloc, d)), jnp.int8
    )
    v_int = jnp.asarray(
        rng.integers(-127, 127, (B, hkv, s_alloc, d)), jnp.int8
    )
    ks = jnp.asarray(rng.random((B, hkv, s_alloc)) * 0.01, jnp.float32)
    vs = jnp.asarray(rng.random((B, hkv, s_alloc)) * 0.01, jnp.float32)

    def einsum_op(q_, ki, ks_, vi, vs_, pos):
        return _cached_attention_quant(q_, ki, ks_, vi, vs_, pos)

    def kernel_op(q_, ki, ks_, vi, vs_, p0):
        return cached_flash_attention(q_, ki, vi, p0, k_scale=ks_,
                                      v_scale=vs_)

    rows = []
    for frac in fracs:
        pos = max(1, int(s_alloc * frac) - 1)
        positions = jnp.asarray([pos], jnp.int32)
        p0 = jnp.asarray(pos, jnp.int32)
        t_e = _time_op(einsum_op, q, k_int, ks, v_int, vs, positions,
                       reps=reps, iters=chain)
        t_k = _time_op(kernel_op, q, k_int, ks, v_int, vs, p0,
                       reps=reps, iters=chain)
        rows.append({
            "pos_over_S": round(frac, 3), "pos": pos,
            "einsum_us": round(t_e * 1e6, 1),
            "kernel_us": round(t_k * 1e6, 1),
            "kernel_wins": bool(t_k < t_e),
        })
        print(json.dumps({"metric": "int8_cache_attention_us", **rows[-1],
                          "s_alloc": s_alloc}), flush=True)
    return rows


def bench_switch_compile(s_alloc: int, n_layers: int, d_model: int,
                         n_heads: int, n_kv_heads: int):
    """Compile-time cost of the two-tier dispatch: a generate-shaped
    decode step whose attention is the per-layer ``lax.cond(kernel,
    einsum)`` that ``_INT8_TIERED_DISPATCH`` enables, vs the plain
    einsum-only program.  The cond's runtime price (both branches'
    code, one executed) rides along in the compiled-program
    comparison; what this measures is the COMPILE delta a server would
    pay per (batch, prompt-length) shape."""
    from distributed_machine_learning_tpu.models.transformer import (
        TransformerLM,
    )
    from distributed_machine_learning_tpu.train.lm_step import init_lm_state

    model = TransformerLM(
        vocab_size=32000, d_model=d_model, n_layers=n_layers,
        n_heads=n_heads, n_kv_heads=n_kv_heads,
        compute_dtype=jnp.bfloat16, kv_cache_dtype=jnp.int8,
    )
    params = jax.tree_util.tree_map(
        lambda p: p.astype(jnp.bfloat16) if p.dtype == jnp.float32 else p,
        init_lm_state(model).params,
    )
    from distributed_machine_learning_tpu.inference.generate import (
        make_generate_fn,
    )

    prompt = jnp.zeros((1, 128), jnp.int32)
    results = {}
    for tiered in (False, True):
        import distributed_machine_learning_tpu.models.transformer as tmod

        tmod._INT8_TIERED_DISPATCH = tiered
        fn = make_generate_fn(model, s_alloc - 256)
        t0 = time.perf_counter()
        lowered = jax.jit(
            lambda p, pr, k: fn(p, pr, k)
        ).lower(params, prompt, jax.random.PRNGKey(0))
        compiled = lowered.compile()
        dt = time.perf_counter() - t0
        results["tiered" if tiered else "plain"] = round(dt, 2)
        del compiled
        print(json.dumps({
            "metric": "int8_generate_compile_seconds",
            "tiered": tiered, "seconds": round(dt, 2),
            "n_layers": n_layers, "gen_tokens": s_alloc - 256,
        }), flush=True)
    tmod._INT8_TIERED_DISPATCH = False
    return results


def main() -> None:
    from distributed_machine_learning_tpu.runtime.compile_cache import (
        configure_compile_cache,
    )

    configure_compile_cache()
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--s-alloc", dest="s_alloc", default=32768, type=int)
    p.add_argument("--fracs", default="0.05,0.2,0.36,0.7,0.95")
    p.add_argument("--hkv", default=8, type=int)
    p.add_argument("--rep", default=1, type=int,
                   help="query heads per KV head (GQA group)")
    p.add_argument("--head-dim", dest="head_dim", default=64, type=int)
    p.add_argument("--reps", default=3, type=int)
    p.add_argument("--chain", default=200, type=int,
               help="scan iterations per timed dispatch (per-op\n                    time is the N-vs-2N slope)")
    p.add_argument("--compile-layers", dest="compile_layers", default=8,
                   type=int)
    p.add_argument("--compile-d-model", dest="compile_d_model",
                   default=512, type=int)
    p.add_argument("--skip-compile", dest="skip_compile",
                   action="store_true")
    args = p.parse_args()
    fracs = [float(f) for f in args.fracs.split(",")]
    bench_attention_ladder(args.s_alloc, fracs, args.hkv, args.rep,
                           args.head_dim, args.reps, args.chain)
    if not args.skip_compile:
        # Same GQA shape as the ladder: H = hkv * rep query heads.
        bench_switch_compile(args.s_alloc, args.compile_layers,
                             args.compile_d_model, args.hkv * args.rep,
                             args.hkv)


if __name__ == "__main__":
    main()
