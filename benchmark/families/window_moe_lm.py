"""Family ``window_moe_lm``: a sliding-window / full-attention MoE LM
(``model_type`` ``afmoe``) trained through ``cli.lm --parallel dp
--model-config <file>``.

Set-up and the check are the ``hybrid_moe_lm`` family's arrangement (the
configuration written to a file, ``cli.lm.main(argv)`` in-process, the
weightless model rebuilt through ``cli.lm.dp_model`` from the same argv, the
system's own ``train/lm_step.py::lm_loss`` on the resident parameters): its
``argv_for``, ``model_from_argv`` and ``params_outside`` are used as they
are.  This file holds what differs: the model FLOPs a token, the attention
kernels' FLOPs with and without a window, the sampled leaves, the reference,
the limits, and the check of the selection bias that the balancing rule moved
during the warm iterations.
"""

from __future__ import annotations

import json
import os
import tempfile

from benchmark import generate
from benchmark.families import hybrid_moe_lm as hybrid_family
from benchmark.families import lm as dense_family
from benchmark.harness import Cell
from benchmark.reference import window_moe_lm as reference

#: bf16 compute against the float32 reference at 16 384 tokens and published
#: widths, random weights two AdamW steps (at 5e-6) from initialization.  Each
#: limit lies between two chip readings (my chip runs, PR 33; PERF.md §6):
#: what the program read over its seeds, and what the reference itself read
#: with every matmul operand rounded, on the way forward, to float8's exponent
#: and mantissa widths (``reference.rounder``: ``lax.reduce_precision`` 4/3,
#: the next precision below bf16), which fails.  The readings stand beside
#: each limit in PERF.md §6.
LOSS_RTOL = 0.001
GRAD_COSINE = 0.95
GRAD_NORM_RTOL = 0.05
#: The selection bias, in units of the rule's rate ``u``: after ``n`` steps
#: every ``b_e / u`` is a whole number inside ``[−n, n]`` (a step at which
#: ``c_e`` IS the mean — 1024 of 16 384 · 8 / 128 here, one expert in a few
#: hundred — moves nothing), and within ``n − 1`` of ``sign(mean(c⁰) − c⁰_e)``,
#: step 0's move, which the reference makes again from the seeded
#: initialization and the first warm batch.  Float32 sums of ``n`` terms of
#: ``u`` are exact to ~1e-7 of ``u``; a rule with the wrong sign is 2 off, one
#: with another rate is no whole number, a bias that nothing moved has mean
#: size zero.  (What AdamW's decay would take from ``b`` at this cell's rate,
#: 5e-6 · 0.01 · |b| a step, is under float32's resolution of ``b``: that
#: ``b`` gets no optimizer step is ``tests/test_window_moe.py``'s to show, to
#: the bit, at a rate where it would.)
BIAS_UNITS_ATOL = 1e-3


def attention_core_flops_per_token(config: dict, seq_len: int,
                                   layer: int) -> float:
    """Model FLOPs a trained token of ONE layer's attention kernels (forward
    and backward together): ``12 · H · head_dim`` a visible key — the forward
    pass's ``q·kᵀ`` and ``p·v`` (``4 · head_dim`` a score and head), times
    three for forward and backward — times the mean number of keys a query
    sees: ``T/2`` on a full layer; on a window layer ``k̄ = w − w(w − 1)/(2T)``
    (the first ``w`` queries see 1 … w keys, the others ``w``; ``T ≥ w``).
    Nothing recomputed is counted (the backward kernel makes the scores
    again, block recomputation the whole forward: performed, not the
    model's).  These are the kernels' roofline counts: ``flash_fwd`` /
    ``flash_bwd_fused`` share the full layer's 1 : 2, ``…_w2048`` a window
    layer's."""
    w = config["sliding_window"]
    if config["layer_types"][layer] == "full_attention" or w >= seq_len:
        keys = seq_len / 2.0
    else:
        keys = w - w * (w - 1) / (2.0 * seq_len)
    return 12.0 * config["num_attention_heads"] * config["head_dim"] * keys


def train_flops_per_token(config: dict, n_outside: int, seq_len: int) -> float:
    """Model FLOPs of one trained token (a matmul counts multiply and add,
    a step is 3 × its forward pass, recomputation is never counted):

    - ``6 · n_outside`` for every parameter outside the embedding table (a
      gather), the routed experts and the selection bias (no matmul):
      attention projections, the dense MLP, routers, shared experts, norms,
      head;
    - ``6 · L_sparse · (k · held / router_width) · expert`` for the routed
      experts' expected share;
    - :func:`attention_core_flops_per_token` of each layer.
    """
    layers = config["num_hidden_layers"]
    sparse = layers - config["num_dense_layers"]
    d, f = config["hidden_size"], config["moe_intermediate_size"]
    routed_share = (config["num_experts_per_tok"] * config["num_experts"]
                    / config.get("router_width", config["num_experts"]))
    return (6.0 * n_outside
            + 6.0 * sparse * routed_share * 3 * d * f
            + sum(attention_core_flops_per_token(config, seq_len, i)
                  for i in range(layers)))


def params_outside(params, config: dict) -> int:
    """Parameters outside the embedding table, the routed experts and the
    routers' selection bias."""
    sparse = config["num_hidden_layers"] - config["num_dense_layers"]
    return (hybrid_family.params_outside(params)
            - sparse * config.get("router_width", config["num_experts"]))


def setup(config: dict, traffic: dict, seed: int) -> Cell:
    # A program without this module would build its dense model from the
    # file's sizes: it fails here, before JAX is touched or anything compiled.
    from distributed_machine_learning_tpu.models.window_moe import WindowMoELM

    import jax

    from distributed_machine_learning_tpu.cli import lm as cli

    world = jax.device_count()
    # The program reads its sizes from a file: the very object the harness
    # read, written where the driver's TMPDIR says, gone after set-up.
    with tempfile.TemporaryDirectory(prefix="bench_config_") as scratch:
        path = os.path.join(scratch, "model_config.json")
        with open(path, "w", encoding="utf-8") as f:
            json.dump(config, f)
        argv = hybrid_family.argv_for(path, traffic, world)
        model, chunks = hybrid_family.model_from_argv(argv)
        if not isinstance(model, WindowMoELM):
            raise ValueError(
                f"cli.lm built {type(model).__name__} from model_type "
                f"{config.get('model_type')!r}, not the window/full MoE")
        result = cli.main(argv)
    batch = traffic["seqs_per_chip"] * world
    return Cell(
        result=result,
        batches=lambda: generate.token_blocks(
            seed, batch=batch, seq_len=traffic["seq_len"],
            vocab=config["vocab_size"]),
        item="tokens",
        items_per_step=batch * traffic["seq_len"],
        flops_per_item=train_flops_per_token(
            config, params_outside(result.state.params, config),
            traffic["seq_len"]),
        check=lambda: check(result, model, chunks, config, traffic, seed),
        loss_must_fall=False,
    )


def sample_paths(config: dict) -> list[str]:
    """The embedding (through the ``√d`` scale and every layer); the first
    layer's fused ``[W_q | W_g]`` (a window layer: its gradient crosses the
    head norms, the rotation, the windowed kernels and the gate) and dense
    MLP; the first router (through the renormalised, scaled weights only);
    the last window layer's key norm; the full layer's ``[W_q | W_g]`` (no
    rotation, the windowless kernels), held experts, shared expert and last
    sandwich norm; the head, the final norm."""
    types = config["layer_types"]
    first = config["num_dense_layers"]
    full = max(i for i, t in enumerate(types) if t == "full_attention")
    window = max(i for i, t in enumerate(types) if t == "sliding_attention")
    paths = ["embed/embedding", "block_0/attn/q_proj/kernel",
             f"block_{first}/moe/router/kernel",
             f"block_{window}/attn/k_norm/weight",
             f"block_{full}/attn/q_proj/kernel",
             f"block_{full}/moe/w_gate",
             f"block_{full}/moe/shared_up_proj/kernel",
             f"block_{full}/post_mlp_layernorm/weight",
             "lm_head/kernel", "norm_f/weight"]
    if first > 0:
        paths.insert(2, "block_0/mlp/down_proj/kernel")
    return paths


def system_loss_and_grads(result, model, chunks, tokens, targets, paths):
    """The program's loss and sampled gradients on the resident parameters,
    and every sparse layer's routed expert ids ``{block: [B, T, k]}``."""
    import jax
    from jax.sharding import PartitionSpec as P

    from distributed_machine_learning_tpu.train.lm_step import lm_loss

    params = result.state.params

    def system(params, tokens, targets):
        picked = {p: reference.get_leaf(params, p) for p in paths}
        out = jax.value_and_grad(lambda s: lm_loss(
            model, reference.with_leaves(params, s), tokens, targets,
            chunks))(picked)
        _, sown = model.apply({"params": params}, tokens, train=True,
                              return_hidden=True, mutable=["moe_routing"])
        return out, {block: layer["moe"]["expert_idx"][0].reshape(
            *tokens.shape, -1) for block, layer in sown["moe_routing"].items()}

    # Every chip computes the same check on its replica of the parameters:
    # the kernels then see local shapes, as they do inside the step.
    mesh = jax.tree_util.tree_leaves(params)[0].sharding.mesh
    system = jax.jit(jax.shard_map(
        system, mesh=mesh, in_specs=(P(), P(), P()), out_specs=P(),
        check_vma=False))
    return jax.device_get(system(params, tokens, targets))


def first_step_counts(model, config: dict, traffic: dict, world: int) -> dict:
    """``{block: c⁰}``: the reference's assignment counts of step 0 — on the
    parameters ``cli.lm`` drew at initialization, drawn again, and the first
    batch of its warm iterations, drawn again (the global batch: the counts
    are summed over the mesh before the rule)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_machine_learning_tpu.cli import lm as cli
    from distributed_machine_learning_tpu.train.lm_step import init_lm_state

    block = cli.synthetic_tokens(
        np.random.default_rng(cli.SEED), traffic["seqs_per_chip"] * world,
        traffic["seq_len"], config["vocab_size"])
    return jax.device_get(jax.jit(lambda tokens: reference.sparse_counts(
        init_lm_state(model, seed=cli.SEED).params, config, tokens))(
        jnp.asarray(block[:, :-1])))


def selection_bias_check(biases: dict, steps: int, rate: float,
                         first_counts: dict, count_gap: dict) -> dict:
    """The resident ``b`` of every sparse layer (``{block: b}``) against the
    rule (see ``BIAS_UNITS_ATOL``).  ``first_counts``: the reference's ``c⁰`` a layer;
    ``count_gap``: a layer's largest program-against-reference difference in
    one expert's count (from the check's batch) — an expert whose ``c⁰`` lies
    within it of the mean could have moved either way in the program's step 0
    and is only held to the bounds every expert is held to."""
    import numpy as np

    undecided = violations = followed = decided = 0
    sizes = []
    for block, counts in first_counts.items():
        units = np.asarray(biases[block], np.float64) / rate
        counts = np.asarray(counts, np.float64)
        off_mean = counts.mean() - counts
        sure = np.abs(off_mean) > count_gap[block]
        whole = np.abs(units - np.round(units)) <= BIAS_UNITS_ATOL
        inside = np.abs(units) <= steps + BIAS_UNITS_ATOL
        first_move = np.abs(units - np.sign(off_mean)) \
            <= steps - 1 + BIAS_UNITS_ATOL
        violations += int((~(whole & inside)).sum()
                          + (sure & ~first_move).sum())
        undecided += int((~sure).sum())
        decided += int(sure.sum())
        followed += int((sure & (np.abs(units - steps * np.sign(off_mean))
                                 <= BIAS_UNITS_ATOL)).sum())
        sizes.append(float(np.abs(units).mean() * rate))
    return {"bias_steps": steps, "bias_rule_violations": violations,
            "bias_undecided_experts": undecided,
            "bias_same_way_every_step_share": followed / max(decided, 1),
            "bias_abs_mean": float(np.mean(sizes))}


def check(result, model, chunks, config: dict, traffic: dict, seed: int,
          operand_dtype=None) -> dict:
    """``operand_dtype``: grade the REFERENCE computed with its matmul
    operands rounded through that dtype in the program's place (how the
    limits' lower reading is taken; never in a run)."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    tokens, targets = next(generate.token_blocks(
        seed, batch=traffic["check_seqs"], seq_len=traffic["seq_len"],
        vocab=config["vocab_size"], stream=1))
    paths = tuple(sample_paths(config))
    params = result.state.params
    seconds, t = {}, time.perf_counter()

    def lap(name):  # where set-up's time goes: each program's compile + run
        nonlocal t
        seconds[name], t = time.perf_counter() - t, time.perf_counter()

    def ref(operand_dtype):  # the configuration is static: closed over
        return jax.device_get(jax.jit(
            lambda params, tokens, targets: reference.loss_and_grads(
                params, config, tokens, targets, paths, operand_dtype))(
            params, jnp.asarray(tokens), jnp.asarray(targets)))

    ref_loss, ref_grads = ref(None)
    lap("reference")
    if operand_dtype is not None:
        loss, grads = ref(operand_dtype)
        out = grade(float(loss), float(ref_loss), grads, ref_grads)
        return {"sequences": int(tokens.shape[0]),
                "seq_len": int(tokens.shape[1]), **out,
                "operand_dtype": str(jnp.dtype(operand_dtype))}
    (loss, grads), routing = system_loss_and_grads(
        result, model, chunks, tokens, targets, paths)
    lap("program")
    out = grade(float(loss), float(ref_loss), grads, ref_grads)
    # Top-k sets of the sparse layers, program against reference: near ties
    # fall differently in bf16.  Stated, not limited, and the reference is
    # never handed the program's choices.
    ref_routing = jax.device_get(jax.jit(
        lambda params, tokens: reference.sparse_routing(
            params, config, tokens))(params, jnp.asarray(tokens)))
    lap("reference_routing")
    first = f"block_{config['num_dense_layers']}"
    same = (routing[first][..., :, None]
            == ref_routing[first][..., None, :]).any(-1).mean()
    width = config.get("router_width", config["num_experts"])
    count_gap = {block: int(np.abs(
        np.bincount(routing[block].ravel(), minlength=width)
        - np.bincount(ref_routing[block].ravel(), minlength=width)).max())
        for block in ref_routing}
    extra = {"top_k_differing_share": float(1.0 - same),
             "count_gap": max(count_gap.values())}
    if config.get("load_balance_coeff") is not None:
        extra.update(selection_bias_check(
            jax.device_get({block: params[block]["moe"][
                "e_score_correction_bias"] for block in ref_routing}),
            int(jax.device_get(result.state.step)),
            config["load_balance_coeff"],
            first_step_counts(model, config, traffic, jax.device_count()),
            count_gap))
        lap("reference_first_step")
        out["ok"] = bool(out["ok"] and extra["bias_rule_violations"] == 0
                         and extra["bias_abs_mean"] > 0.0)
    return {"sequences": int(tokens.shape[0]),
            "seq_len": int(tokens.shape[1]), **out, **extra,
            "seconds": seconds}


def grade(loss: float, ref_loss: float, grads: dict, ref_grads: dict) -> dict:
    """The ``lm`` family's comparison (loss, cosine and norm ratio of each
    sampled gradient), decided by this family's limits."""
    out = dense_family.grade(loss, ref_loss, grads, ref_grads)
    out["ok"] = bool(
        abs(loss - ref_loss) <= LOSS_RTOL * abs(ref_loss)
        and min(out["grad_cosine"].values()) >= GRAD_COSINE
        and max(abs(n - 1.0) for n in out["grad_norm_ratio"].values())
        <= GRAD_NORM_RTOL)
    return out


def attention_core_bytes_per_token(config: dict) -> float:
    """Bytes a trained token of ONE layer's attention kernels must move
    between HBM and the chip, whatever the window (the other half of a
    roofline; bf16 tensors, float32 rows): the forward reads q, k, v and
    writes o and the logsumexp; the backward reads q, k, v, dO, the
    logsumexp and Δ and writes dq and, a QUERY head each, dk and dv.  61 824
    bytes a layer at the published widths, 1.0 GB a step at 16 384 tokens, 1.2 ms
    at 819 GB/s against 10–40 ms of kernel time: the kernels are bound by
    compute (PERF.md §5)."""
    heads = config["num_attention_heads"] * config["head_dim"]
    kv = 2 * config["num_key_value_heads"] * config["head_dim"]
    rows = 4 * config["num_attention_heads"]
    forward = 2 * (heads + kv + heads) + rows
    backward = 2 * (heads + kv + heads) + 2 * rows + 2 * (heads + 2 * heads)
    return float(forward + backward)
