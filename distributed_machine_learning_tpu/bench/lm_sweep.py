"""LM scaling sweep harness — tokens/sec/device for the LM schemes.

Round 3's sweep machinery (`bench/sweep.py`) covered only the CNN
strategies; the schemes a real pod will actually run — per-layer FSDP,
tensor parallelism, pipeline parallelism — had no harness, so a
multi-chip session would have started by writing one (VERDICT r03
item 6).  This module makes each of them a one-command sweep:

- ``fsdp_pl`` — **weak scaling over the batch**: fixed per-device
  batch, device count grows the global batch (the classic data-parallel
  weak-scaling protocol, matching the CNN sweep and the reference's
  1→4-node experiment, group25.pdf p.10).
- ``tp`` — **strong scaling at fixed problem size**: the global batch
  and model are pinned while the model axis grows; efficiency is
  tokens/sec(d) / (d · tokens/sec(1)).  (Growing the model with the
  mesh would change the program per point — the fixed-model curve is
  the one that answers "how many chips should serve this model".)
- ``pp`` — **weak scaling over depth**: ``n_layers = layers_per_stage
  × stages``, so per-device compute is fixed while the MODEL grows with
  the pipeline — pipeline parallelism's reason to exist.  Microbatches
  scale with the stage count to hold the bubble fraction
  (P−1)/(M+P−1) comparable across points.
- ``ep`` — **weak scaling over the expert axis** (VERDICT r04 item 5):
  ``n_experts = experts_per_device × devices`` and the global batch
  grows with the mesh, through the dropless grouped-EP step (explicit
  token all_to_all + ragged_dot).  Top-1 routing keeps per-TOKEN
  compute constant as experts grow, so tokens/sec/device is flat on
  ideal hardware — the efficiency norm is 1, and the shortfall is the
  genuine all_to_all + padding cost.
- ``ring`` — **weak scaling over sequence** (the long-context pod
  scheme): global ``seq = seq_len × devices`` at a fixed per-device
  chunk, ring-attention context parallelism.  Causal attention work
  per token GROWS with the global sequence, so the efficiency norm is
  FLOPs/sec/device (tokens/sec/device × modeled FLOPs/token at that
  point's length — ``utils/flops.py``), not raw token rate.

Timing: chained donated steps, per-step time from the two-point slope
(N vs 2N chained steps — fixed dispatch overhead cancels; same
methodology as bench.py / bench_lm.py).

Runs anywhere a mesh runs: real chips, or the virtual CPU mesh
(``XLA_FLAGS=--xla_force_host_platform_device_count=N``) where the
harness logic and the compiled sharded programs are what is being
exercised — per-device throughput on virtual devices falls with the
count by construction and is labeled as such in the dryrun.
"""

from __future__ import annotations

import argparse
import json
import time
from dataclasses import asdict, dataclass

import jax
import jax.numpy as jnp
import numpy as np

LM_SWEEP_SCHEMES = ("fsdp_pl", "tp", "pp", "ep", "ring")
# One default, shared by lm_run_point's signature and the tp auto-count
# filter, so they cannot drift.
DEFAULT_N_HEADS = 8


@dataclass
class LMScalePoint:
    """One measured point of an LM scaling sweep."""

    num_devices: int
    scheme: str
    mode: str  # "weak-batch" | "strong" | "weak-depth"
    d_model: int
    n_layers: int
    seq_len: int
    global_batch: int
    tokens_per_sec: float
    tokens_per_sec_per_device: float
    efficiency: float | None = None
    # Modeled train FLOPs per token at this point's shape (set for the
    # weak-seq ring mode, whose per-token work grows with the global
    # sequence — the efficiency norm multiplies by it).
    flops_per_token: float | None = None


def _time_chained(step, state, x, y, n: int):
    """Wall time of ``n`` chained step dispatches closed by a loss fetch.
    The state threads through (steps donate their input state), so the
    chain is the real training execution pattern."""
    t0 = time.perf_counter()
    loss = None
    for _ in range(n):
        state, loss = step(state, x, y)
    jax.block_until_ready(loss)
    float(loss)
    return time.perf_counter() - t0, state


def _per_step_time(step, state, x, y, iters: int):
    """Two-point slope: (t(2N) − t(N)) / N cancels fixed overhead."""
    state, _ = step(state, x, y)  # compile (excluded)
    # A full throwaway chain: the first post-compile chain still carries
    # one-time costs (executable load, donation buffer setup — measured
    # ~1.5× steady state on the CPU mesh) that would corrupt the slope.
    _, state = _time_chained(step, state, x, y, iters)
    t1, state = _time_chained(step, state, x, y, iters)
    t2, state = _time_chained(step, state, x, y, 2 * iters)
    slope = (t2 - t1) / iters
    avg = t2 / (2 * iters)
    # Same jitter guard as bench/harness.py::two_point_fit: a noisy t1
    # can push the slope negative (absurd throughput) or above the
    # chained average (impossible) — fall back to the average, which
    # over-counts only the fixed overhead instead of fabricating rates.
    if slope <= 0 or slope > avg:
        slope = avg
    return slope


def lm_run_point(
    scheme: str,
    num_devices: int,
    *,
    d_model: int = 256,
    n_heads: int = DEFAULT_N_HEADS,
    vocab: int = 256,
    seq_len: int = 128,
    per_device_batch: int = 4,
    global_batch: int | None = None,
    n_layers: int = 4,
    layers_per_stage: int = 2,
    experts_per_device: int = 2,
    timed_iters: int = 4,
    devices=None,
) -> LMScalePoint:
    """Measure one (scheme, device-count) point; see module docstring
    for each scheme's scaling mode."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_machine_learning_tpu.models.transformer import (
        TransformerLM,
    )
    from distributed_machine_learning_tpu.runtime.mesh import make_mesh
    from distributed_machine_learning_tpu.train.lm_step import init_lm_state

    if scheme not in LM_SWEEP_SCHEMES:
        raise ValueError(
            f"scheme must be one of {LM_SWEEP_SCHEMES}, got {scheme!r}"
        )
    if num_devices < 1:
        raise ValueError(f"num_devices must be >= 1, got {num_devices}")
    if timed_iters < 1:
        raise ValueError(f"timed_iters must be >= 1, got {timed_iters}")
    rng = np.random.default_rng(0)

    if scheme == "fsdp_pl":
        from distributed_machine_learning_tpu.parallel.fsdp_perlayer import (
            make_fsdp_pl_lm_train_step,
            shard_fsdp_pl_state,
        )
        from distributed_machine_learning_tpu.train.adamw import AdamWConfig

        mode = "weak-batch"
        batch = per_device_batch * num_devices
        model = TransformerLM(
            vocab_size=vocab, d_model=d_model, n_layers=n_layers,
            n_heads=n_heads, compute_dtype=jnp.bfloat16,
        )
        mesh = make_mesh(num_devices, ("batch",), devices=devices)
        state = shard_fsdp_pl_state(
            init_lm_state(model, config=AdamWConfig()), mesh
        )
        step = make_fsdp_pl_lm_train_step(model, mesh)
        sharding = NamedSharding(mesh, P("batch", None))
        layers = n_layers
    elif scheme == "tp":
        from distributed_machine_learning_tpu.parallel.tensor_parallel import (
            make_tp_lm_train_step,
            shard_tp_state,
        )

        mode = "strong"
        if n_heads % num_devices:
            raise ValueError(
                f"tp sweep needs n_heads ({n_heads}) divisible by every "
                f"device count (got {num_devices})"
            )
        batch = global_batch or per_device_batch
        model = TransformerLM(
            vocab_size=vocab, d_model=d_model, n_layers=n_layers,
            n_heads=n_heads, compute_dtype=jnp.bfloat16,
        )
        mesh = make_mesh(
            num_devices, ("batch", "model"), (1, num_devices),
            devices=devices,
        )
        state = shard_tp_state(init_lm_state(model), mesh)
        step = make_tp_lm_train_step(model, mesh)
        sharding = NamedSharding(mesh, P("batch", None))
        layers = n_layers
    elif scheme == "ep":
        from distributed_machine_learning_tpu.models.moe import (
            MoETransformerLM,
        )
        from distributed_machine_learning_tpu.parallel.expert_parallel import (
            init_moe_state,
            make_ep_grouped_train_step,
            shard_ep_state,
        )

        mode = "weak-expert"
        batch = per_device_batch * num_devices
        model = MoETransformerLM(
            vocab_size=vocab, d_model=d_model, n_layers=n_layers,
            n_heads=n_heads, n_experts=experts_per_device * num_devices,
            moe_impl="grouped", compute_dtype=jnp.bfloat16,
        )
        mesh = make_mesh(
            num_devices, ("batch", "expert"), (1, num_devices),
            devices=devices,
        )
        state = shard_ep_state(init_moe_state(model), mesh)
        step = make_ep_grouped_train_step(model, mesh)
        # The grouped-EP step's contract: token rows shard over the
        # combined (data, expert) axes.
        sharding = NamedSharding(mesh, P(("batch", "expert"), None))
        layers = n_layers
    elif scheme == "ring":
        from distributed_machine_learning_tpu.train.lm_step import (
            make_lm_train_step,
        )

        mode = "weak-seq"
        batch = per_device_batch  # fixed global batch; the SEQUENCE grows
        seq_len = seq_len * num_devices  # seq_len acts as per-device chunk
        model = TransformerLM(
            vocab_size=vocab, d_model=d_model, n_layers=n_layers,
            n_heads=n_heads, attn_impl="ring", compute_dtype=jnp.bfloat16,
        )
        mesh = make_mesh(
            num_devices, ("batch", "seq"), (1, num_devices),
            devices=devices,
        )
        state = init_lm_state(model)
        step = make_lm_train_step(model, mesh=mesh)
        sharding = NamedSharding(mesh, P("batch", "seq"))
        layers = n_layers
    else:  # pp — weak over depth
        from distributed_machine_learning_tpu.parallel.pipeline import (
            init_pipeline_state,
            microbatch,
            shard_pp_state,
        )
        from distributed_machine_learning_tpu.parallel.pipeline_1f1b import (
            make_pp_1f1b_lm_train_step,
        )

        mode = "weak-depth"
        layers = layers_per_stage * num_devices
        microbatches = max(2, num_devices)
        batch = per_device_batch * microbatches
        model = TransformerLM(
            vocab_size=vocab, d_model=d_model, n_layers=layers,
            n_heads=n_heads, compute_dtype=jnp.bfloat16,
        )
        mesh = make_mesh(num_devices, ("pipe",), devices=devices)
        state = shard_pp_state(init_pipeline_state(model), mesh)
        step = make_pp_1f1b_lm_train_step(
            model, mesh, num_microbatches=microbatches
        )

    toks = rng.integers(0, vocab, (batch, seq_len + 1)).astype(np.int32)
    if scheme == "pp":
        # Microbatched and replicated over the pipe mesh (the step's
        # contract: every stage sees all microbatches, masked by tick).
        x, y = microbatch(toks[:, :-1], toks[:, 1:], microbatches)
        rep = NamedSharding(mesh, P())
        x, y = jax.device_put(x, rep), jax.device_put(y, rep)
    else:
        x = jax.device_put(jnp.asarray(toks[:, :-1]), sharding)
        y = jax.device_put(jnp.asarray(toks[:, 1:]), sharding)

    per_step = _per_step_time(step, state, x, y, timed_iters)
    tps = batch * seq_len / per_step
    fpt = None
    if mode == "weak-seq":
        # Per-token work grows with the global sequence (causal
        # attention); the sweep's efficiency norm needs the modeled
        # FLOPs/token at THIS length.  Embedding is a gather, not a
        # matmul — excluded, as in bench_lm.py.
        from distributed_machine_learning_tpu.utils.flops import (
            transformer_train_flops_per_token,
        )

        n_params = sum(
            int(np.prod(p.shape))
            for p in jax.tree_util.tree_leaves(state.params)
        ) - vocab * d_model
        fpt = transformer_train_flops_per_token(
            n_params, layers, d_model, seq_len, causal=True
        )
    return LMScalePoint(
        num_devices=num_devices,
        scheme=scheme,
        mode=mode,
        d_model=d_model,
        n_layers=layers,
        seq_len=seq_len,
        global_batch=batch,
        tokens_per_sec=tps,
        tokens_per_sec_per_device=tps / num_devices,
        flops_per_token=fpt,
    )


def lm_scaling_sweep(
    scheme: str,
    device_counts: list[int] | None = None,
    devices=None,
    **point_kwargs,
) -> list[LMScalePoint]:
    """Sweep device counts for one LM scheme; annotate efficiency
    against the smallest point.

    Efficiency = per-device WORK rate relative to the smallest point:
    tokens/sec/device for the fixed-model modes (fsdp_pl weak-batch, tp
    strong), tokens·layers/sec/device for pp's weak-depth mode (the
    model grows with the pipeline, so raw token rate falls ~1/d even on
    ideal hardware — see ``norm`` below)."""
    if device_counts is None:
        n = len(devices) if devices is not None else jax.device_count()
        device_counts = [d for d in (1, 2, 4, 8, 16, 32) if d <= n]
        if scheme == "tp":
            # Auto-selection must not crash the sweep mid-run at a count
            # n_heads cannot shard over (explicit counts still raise).
            heads = point_kwargs.get("n_heads", DEFAULT_N_HEADS)
            device_counts = [d for d in device_counts if heads % d == 0]
    device_counts = sorted(set(device_counts))
    if not device_counts:
        raise ValueError("device_counts is empty: nothing to sweep")
    points = [
        lm_run_point(scheme, d, devices=devices, **point_kwargs)
        for d in device_counts
    ]

    def norm(p: LMScalePoint) -> float:
        # Per-device WORK rate, not raw token rate: pp's weak-depth mode
        # grows per-token FLOPs with the model (n_layers ∝ stages), so
        # tokens/sec/device falls ~1/d on IDEAL hardware — the honest
        # per-device quantity is tokens·layers/sec/device (∝ model
        # FLOPs/sec/device).  ring's weak-seq mode grows the causal
        # attention term with the global sequence — its norm is the
        # modeled FLOPs/sec/device.  The flat modes (fsdp_pl, tp, and
        # ep — top-1 routing holds per-token compute constant as
        # experts grow) normalize by 1.
        if p.mode == "weak-seq":
            return p.tokens_per_sec_per_device * p.flops_per_token
        return p.tokens_per_sec_per_device * (
            p.n_layers if p.mode == "weak-depth" else 1
        )

    base = norm(points[0])
    for p in points:
        p.efficiency = round(norm(p) / base, 4) if base else None
    return points


def format_row(p: LMScalePoint) -> dict:
    """JSON-able row for one sweep point — the ONE formatter the CLI and
    the dryrun share, so their rows cannot drift."""
    row = asdict(p)
    row["tokens_per_sec"] = round(row["tokens_per_sec"], 1)
    row["tokens_per_sec_per_device"] = round(
        row["tokens_per_sec_per_device"], 1
    )
    return row


def main() -> None:
    from distributed_machine_learning_tpu.runtime.compile_cache import (
        configure_compile_cache,
    )

    configure_compile_cache()
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--scheme", default="fsdp_pl",
                        choices=list(LM_SWEEP_SCHEMES))
    parser.add_argument("--devices", default=None, type=str,
                        help="comma-separated device counts, e.g. 1,2,4,8")
    parser.add_argument("--d-model", dest="d_model", default=256, type=int)
    parser.add_argument("--n-heads", dest="n_heads", default=8, type=int)
    parser.add_argument("--n-layers", dest="n_layers", default=4, type=int,
                        help="fsdp_pl/tp model depth (pp grows depth as "
                             "layers-per-stage x stages)")
    parser.add_argument("--layers-per-stage", dest="layers_per_stage",
                        default=2, type=int)
    parser.add_argument("--experts-per-device", dest="experts_per_device",
                        default=2, type=int,
                        help="ep mode: n_experts = this x device count")
    parser.add_argument("--seq-len", dest="seq_len", default=128, type=int,
                        help="ring mode: the PER-DEVICE chunk (global "
                             "sequence = seq-len x device count)")
    parser.add_argument("--batch-per-device", dest="per_device_batch",
                        default=4, type=int)
    parser.add_argument("--global-batch", dest="global_batch", default=None,
                        type=int, help="tp mode: the fixed global batch")
    parser.add_argument("--iters", default=4, type=int)
    args = parser.parse_args()

    counts = (
        [int(d) for d in args.devices.split(",")] if args.devices else None
    )
    points = lm_scaling_sweep(
        args.scheme,
        device_counts=counts,
        d_model=args.d_model,
        n_heads=args.n_heads,
        n_layers=args.n_layers,
        layers_per_stage=args.layers_per_stage,
        experts_per_device=args.experts_per_device,
        seq_len=args.seq_len,
        per_device_batch=args.per_device_batch,
        global_batch=args.global_batch,
        timed_iters=args.iters,
    )
    for p in points:
        print(json.dumps(format_row(p)))
    if len(points) > 1:
        summary = {
            "metric": f"lm_{args.scheme}_scaling_efficiency",
            "value": points[-1].efficiency,
            "unit": (
                f"x{points[-1].num_devices}_vs_x{points[0].num_devices}"
            ),
            "mode": points[-1].mode,
        }
        if points[-1].mode != "strong":
            # BASELINE.md north-star (>=85%) is a WEAK-scaling target;
            # attaching it to tp's fixed-problem strong-scaling curve
            # would flag healthy runs as regressions.
            summary["target"] = 0.85
        print(json.dumps(summary))


if __name__ == "__main__":
    main()
