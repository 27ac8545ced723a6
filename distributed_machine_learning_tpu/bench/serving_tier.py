"""Serving-tier A/B: continuous batching vs the batch-static path
(ISSUE 19).

The question this bench answers with numbers: what do the paged
KV-cache allocator + iteration-level scheduler
(``inference/continuous.py``) buy over the batch-static
``make_serving_step`` dispatch loop, per offered load?  The
batch-static path loses on two axes the engine was built to remove:

* **padding**: every request in a dispatch decodes the GLOBAL
  ``max_new`` cap even when its own budget is a quarter of it — the
  compute for the padded tail is pure waste;
* **head-of-line**: a micro-batch is grouped by prompt length and each
  group runs as one full-length program, serially; a ragged 4-batch
  can cost four whole scans, and nothing new starts until the whole
  dispatch returns.  The engine retires per sequence, backfills the
  freed lane the same step, and advances mixed lengths in ONE
  dispatch.

Method: a **virtual-clock discrete-event simulation** — no sleeps.
Seeded Poisson arrivals land on a virtual clock T; T advances by the
*measured wall time of each real compute call* (an engine ``step()``
or a batch-static dispatch) and jumps to the next arrival when idle.
A request's e2e is completion-T minus arrival-T, so queueing physics
(waits, HOL, backfill) are exact while the compute costs are real
measured numbers.  Both systems serve the identical seeded workload:
one replica, greedy decoding, the same micro width (``max_lanes`` ==
``micro_batch``), no EOS (raggedness comes from per-request
``max_new`` budgets, which the engine honors natively and the
batch-static path must pad to the cap).  Compile costs are paid
before the timed pass for both sides (every (batch, length) shape the
sweep can hit is pre-warmed).

Throughput counts USEFUL tokens only — the tokens a request asked
for — so the baseline's padded tail is counted as the waste it is.

Run::

    python -m distributed_machine_learning_tpu.bench.serving_tier \
        --rates 6,16,48 --requests 80 --out BENCH_r19_serving.json
"""

from __future__ import annotations

import argparse
import json
import random
import time

PROMPT_LENS = (4, 8, 12, 16)
BUDGETS = (4, 8, 16, 48)


def make_model(d_model: int = 320, n_layers: int = 4, n_heads: int = 8,
               n_kv_heads: int = 2, vocab: int = 128):
    from distributed_machine_learning_tpu.models.transformer import (
        TransformerLM,
    )
    from distributed_machine_learning_tpu.train.lm_step import (
        init_lm_state,
    )

    model = TransformerLM(
        vocab_size=vocab, d_model=d_model, n_layers=n_layers,
        n_heads=n_heads, n_kv_heads=n_kv_heads,
    )
    params = init_lm_state(model).params
    return model, params


def make_workload(n_requests: int, rate_rps: float, seed: int,
                  prompt_lens=PROMPT_LENS, budgets=BUDGETS,
                  vocab: int = 128):
    """Seeded Poisson arrivals with ragged prompts AND ragged decode
    budgets.  Returns arrival-time-sorted request dicts."""
    rng = random.Random(seed)
    t = 0.0
    out = []
    for i in range(n_requests):
        t += rng.expovariate(rate_rps)
        lp = rng.choice(prompt_lens)
        out.append({
            "rid": f"q{i:03d}",
            "t_arr": t,
            "prompt": [rng.randrange(1, vocab) for _ in range(lp)],
            "max_new": rng.choice(budgets),
        })
    return out


def _quantiles(values):
    xs = sorted(values)

    def q(p):
        if not xs:
            return 0.0
        idx = min(len(xs) - 1, max(0, int(round(p * (len(xs) - 1)))))
        return xs[idx]

    return {"p50_e2e_s": q(0.50), "p95_e2e_s": q(0.95),
            "p99_e2e_s": q(0.99), "max_e2e_s": xs[-1] if xs else 0.0}


def build_engine(model, params, *, max_lanes: int,
                 prompt_lens=PROMPT_LENS, budgets=BUDGETS,
                 block_size: int = 8, num_blocks: int = 64):
    """One warmed engine, reused across the whole rate sweep so XLA
    compiles (per-lever decode, per-prompt-length prefill) are paid
    exactly once, outside every timed pass."""
    from distributed_machine_learning_tpu.inference.continuous import (
        ContinuousEngine,
        EngineConfig,
    )
    from distributed_machine_learning_tpu.runtime.scheduler import (
        LATENCY,
    )

    cfg = EngineConfig(
        max_lanes=max_lanes, block_size=block_size,
        num_blocks=num_blocks,
        max_len=max(prompt_lens) + max(budgets),
        max_new=max(budgets), levers=(LATENCY,),
    )
    engine = ContinuousEngine(model, params, cfg)
    engine.warmup(prompt_lens=sorted(set(prompt_lens)))
    return engine


def simulate_engine(engine, workload):
    """Continuous-batching side: arrivals with ``t_arr <= T`` submit,
    each real ``engine.step()`` advances T by its measured wall time,
    retirements complete at the post-step T."""
    arrivals = {r["rid"]: r["t_arr"] for r in workload}
    clock = 0.0
    nxt = 0
    e2e: dict = {}
    steps = 0
    while len(e2e) < len(workload):
        while nxt < len(workload) and workload[nxt]["t_arr"] <= clock:
            r = workload[nxt]
            engine.submit(r["rid"], r["prompt"], max_new=r["max_new"])
            nxt += 1
        if not engine.has_work():
            clock = workload[nxt]["t_arr"]
            continue
        t0 = time.perf_counter()
        done = engine.step()
        clock += time.perf_counter() - t0
        steps += 1
        for d in done:
            e2e[d["rid"]] = clock - arrivals[d["rid"]]
    engine.allocator.check_invariants()
    useful = sum(r["max_new"] for r in workload)
    return {"e2e": e2e, "makespan_s": clock, "useful_tokens": useful,
            "dispatches": steps}


def build_baseline(model, params, *, micro_batch: int,
                   prompt_lens=PROMPT_LENS, budgets=BUDGETS):
    """The batch-static step callable, with every (group size, prompt
    length) program the sweep can hit pre-warmed so timed dispatches
    measure decode, not XLA."""
    from distributed_machine_learning_tpu.inference.generate import (
        make_serving_step,
    )

    cap = max(budgets)
    step = make_serving_step(model, params, cap)
    for lp in sorted(set(prompt_lens)):
        for g in range(1, micro_batch + 1):
            step([[1] * lp] * g)
    return step, cap


def simulate_baseline(step, cap, workload, *, micro_batch: int):
    """Batch-static side: the router loop ``serving_worker`` drives —
    pull up to ``micro_batch`` queued arrivals, run ONE
    ``make_serving_step`` dispatch (grouped by prompt length, every
    row decoding the global cap), the whole batch completes when the
    dispatch returns."""
    clock = 0.0
    nxt = 0
    queue: list = []
    e2e: dict = {}
    dispatches = 0
    while len(e2e) < len(workload):
        while nxt < len(workload) and workload[nxt]["t_arr"] <= clock:
            queue.append(workload[nxt])
            nxt += 1
        if not queue:
            clock = workload[nxt]["t_arr"]
            continue
        batch = queue[:micro_batch]
        del queue[:micro_batch]
        t0 = time.perf_counter()
        outs = step([r["prompt"] for r in batch])
        clock += time.perf_counter() - t0
        dispatches += 1
        for r, tokens in zip(batch, outs):
            # Delivery truncates the padded tail to the request's own
            # budget — the compute for it was still paid above.
            assert len(tokens) == len(r["prompt"]) + cap
            e2e[r["rid"]] = clock - r["t_arr"]
    useful = sum(r["max_new"] for r in workload)
    return {"e2e": e2e, "makespan_s": clock, "useful_tokens": useful,
            "dispatches": dispatches}


def run_sweep(rates, n_requests: int, seed: int = 0, *, width: int = 4,
              model=None, params=None, prompt_lens=PROMPT_LENS,
              budgets=BUDGETS, num_blocks: int = 64,
              modeled_network: bool = False):
    """One row per (rate, system), rates ascending.  The engine rows
    carry the head-to-head verdicts the acceptance gate reads.  The
    same seed drives every rate, so the request mix (prompts, budgets)
    is identical across the sweep and only the arrival spacing moves."""
    if model is None:
        model, params = make_model()
    engine = build_engine(model, params, max_lanes=width,
                          prompt_lens=prompt_lens, budgets=budgets,
                          num_blocks=num_blocks)
    step, cap = build_baseline(model, params, micro_batch=width,
                               prompt_lens=prompt_lens, budgets=budgets)
    rows = []
    for rate in sorted(rates):
        wl = make_workload(n_requests, rate, seed,
                           prompt_lens=prompt_lens, budgets=budgets,
                           vocab=model.vocab_size)
        base = simulate_baseline(step, cap, wl, micro_batch=width)
        eng = simulate_engine(engine, wl)
        for system, res in (("batch_static", base), ("engine", eng)):
            row = {
                "bench": "serving_tier",
                "system": system,
                "rate_rps": rate,
                "n_requests": n_requests,
                "width": width,
                "seed": seed,
                "useful_tokens": res["useful_tokens"],
                "tokens_per_sec": round(
                    res["useful_tokens"] / res["makespan_s"], 1),
                "makespan_s": round(res["makespan_s"], 4),
                "dispatches": res["dispatches"],
            }
            row.update({k: round(v, 4) for k, v in
                        _quantiles(list(res["e2e"].values())).items()})
            if modeled_network:
                # Router<->replica transit over the modeled inter-node
                # link (round 20): one round trip per dispatch (the
                # per-hop overhead both directions) plus the token
                # payload — prompts out, completions back — priced at
                # the calibrated outer bandwidth.  Reported NEXT TO the
                # measured numbers, never folded into the simulation:
                # the column is what a pod adds on top of the CPU
                # compute the rows measured.
                from distributed_machine_learning_tpu.ops.topology import (  # noqa: E501
                    DEFAULT_LINK_MODEL,
                )

                link = DEFAULT_LINK_MODEL
                payload = sum(
                    (len(r["prompt"]) + r["max_new"]) * 4 for r in wl)
                net_s = (res["dispatches"] * 2 * link.outer_overhead_s
                         + 2 * payload / link.outer_bytes_per_s)
                row["modeled_net_s"] = round(net_s, 6)
                row["tokens_per_sec_modeled_pod"] = round(
                    res["useful_tokens"]
                    / (res["makespan_s"] + net_s), 1)
            rows.append(row)
            print(json.dumps(row), flush=True)
        erow, brow = rows[-1], rows[-2]
        erow["engine_wins_tokens_per_sec"] = bool(
            erow["tokens_per_sec"] > brow["tokens_per_sec"])
        erow["engine_wins_p95_e2e"] = bool(
            erow["p95_e2e_s"] < brow["p95_e2e_s"])
    return rows


def acceptance(rows) -> dict:
    """The r19 gate: the engine must beat batch-static on useful
    tokens/sec at the HIGHEST offered load and on p95 e2e at the
    LOWEST."""
    engine = [r for r in rows if r["system"] == "engine"]
    lo = min(engine, key=lambda r: r["rate_rps"])
    hi = max(engine, key=lambda r: r["rate_rps"])
    return {
        "bench": "serving_tier_acceptance",
        "highest_rate_rps": hi["rate_rps"],
        "engine_beats_tokens_per_sec_at_highest_load":
            hi["engine_wins_tokens_per_sec"],
        "lowest_rate_rps": lo["rate_rps"],
        "engine_beats_p95_e2e_at_lowest_load":
            lo["engine_wins_p95_e2e"],
    }


def main() -> None:
    from distributed_machine_learning_tpu.runtime.compile_cache import (
        configure_compile_cache,
    )

    configure_compile_cache()
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--rates", default="6,16,48",
                   help="offered loads, requests/sec (ascending)")
    p.add_argument("--requests", default=80, type=int)
    p.add_argument("--seed", default=0, type=int)
    p.add_argument("--width", default=4, type=int,
                   help="micro_batch == max_lanes")
    p.add_argument("--d-model", dest="d_model", default=320, type=int)
    p.add_argument("--n-layers", dest="n_layers", default=4, type=int)
    p.add_argument("--modeled-network", action="store_true",
                   help="add modeled_net_s / tokens_per_sec_modeled_pod "
                        "columns: router<->replica transit priced on "
                        "the calibrated inter-node LinkModel next to "
                        "the measured CPU numbers (round 20)")
    p.add_argument("--out", default=None,
                   help="write the row list as JSON (BENCH idiom)")
    args = p.parse_args()
    rates = [float(r) for r in args.rates.split(",")]
    model, params = make_model(d_model=args.d_model,
                               n_layers=args.n_layers)
    rows = run_sweep(rates, args.requests, args.seed, width=args.width,
                     model=model, params=params,
                     modeled_network=args.modeled_network)
    verdict = acceptance(rows)
    rows.append(verdict)
    print(json.dumps(verdict), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=2)
            f.write("\n")


if __name__ == "__main__":
    main()
