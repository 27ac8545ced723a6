"""Family ``mla_moe_lm``: a latent-attention MoE LM (``model_type``
``deepseek_v3``) trained through ``cli.lm --parallel dp --model-config
<file>``.

Set-up and the check are the ``hybrid_moe_lm`` family's arrangement (the
configuration written to a file, ``cli.lm.main(argv)`` in-process, the
weightless model rebuilt through ``cli.lm.dp_model`` from the same argv, the
system's own ``train/lm_step.py::lm_loss`` on the resident parameters): its
``argv_for``, ``model_from_argv`` and ``params_outside`` are used as they
are.  This file holds what differs: the model FLOPs a token, the latent
attention kernels' FLOPs, the sampled leaves, the reference and the limits.
"""

from __future__ import annotations

import json
import os
import tempfile

from benchmark import generate
from benchmark.families import hybrid_moe_lm as hybrid_family
from benchmark.families import lm as dense_family
from benchmark.harness import Cell
from benchmark.reference import mla_moe_lm as reference

#: bf16 compute against the float32 reference at 8192 tokens and published
#: widths, random weights two AdamW steps from initialization.  Each limit
#: lies between two chip readings (my chip runs, PR 31; PERF.md §6): what the
#: program read over ten seeds, and what the reference itself read with every
#: matmul operand rounded, on the way forward, to float8's exponent and
#: mantissa widths (``reference.rounder``: ``lax.reduce_precision`` 4/3, the
#: next precision below bf16; seed 2147485001), which fails all three.
#: - Loss: the program within 5.5e-5; float8 5.2e-3.  It sits near ln(vocab)
#:   whatever the model does; the limit is 18 times the first reading and a
#:   fifth of the second.
#: - Cosine of each sampled gradient: the program's lowest is always the
#:   first router (0.9776–0.9837) or the last layer's held experts
#:   (0.9800–0.9857): 0.9% of the first sparse layer's top-6 sets fall
#:   differently in bf16 and those tokens' gradients land on other experts
#:   (the reference with bf16 operands alone reads 0.9820 / 0.9875 there, so
#:   it is the precision); every other leaf ≥ 0.9989.  float8 reads 0.008 on
#:   the router, 0.04 on the experts, at most 0.49 on any matrix and 0.84 on
#:   the final norm.
#: - Norm ratio of each sampled gradient: the program within 0.90% (the
#:   router, once; 0.64% at most otherwise); float8 21% on the experts, 11%
#:   on the router, 13% on the final norm, 2–4% elsewhere.
#: - Selection bias ``b`` of every sparse layer, resident (after the warm
#:   iterations of the timed path's own ``train/lm_step.py::_update``) against
#:   the seeded draw made again here: the program reads 0.0 (chip and CPU);
#:   a ``b`` that AdamW only decays (``frozen_params`` missed; no gradient
#:   reaches it) moves by lr · decay · |b| a step, 2 · 3e-4 · 0.01 · 0.05 =
#:   3e-7 over the two warm iterations at ``cli.lm``'s defaults, which
#:   ``tests/test_mla_moe_cell.py`` reads and which fails; any gradient step
#:   moves it by ~lr = 3e-4.
LOSS_RTOL = 0.001
GRAD_COSINE = 0.95
GRAD_NORM_RTOL = 0.05
BIAS_DRIFT_ATOL = 1e-8


def attention_core_flops_per_token(config: dict, seq_len: int) -> float:
    """Model FLOPs a trained token of ONE layer's latent-attention kernels
    (``flash_fwd_qk192v128`` and the two backward kernels together):
    ``3 · H · (d_qk + d_v) · T`` — the forward pass's ``q·kᵀ`` (``2·d_qk`` a
    score) and ``p·v`` (``2·d_v``) over half the square, causal, times three
    for forward and backward.  Nothing recomputed is counted (the backward
    kernels make the scores again: performed work, not the model's)."""
    d_qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    return (3.0 * config["num_attention_heads"]
            * (d_qk + config["v_head_dim"]) * seq_len)


def train_flops_per_token(config: dict, n_outside: int, seq_len: int) -> float:
    """Model FLOPs of one trained token (a matmul counts multiply and add,
    a step is 3 × its forward pass, recomputation is never counted):

    - ``6 · n_outside`` for every parameter outside the embedding table (a
      gather), the routed experts and the selection bias (no matmul):
      attention projections, the dense MLP, routers, shared experts, norms,
      head;
    - ``6 · L_sparse · (k · held / router_width) · expert`` for the routed
      experts' expected share;
    - :func:`attention_core_flops_per_token` a layer.
    """
    layers = config["num_hidden_layers"]
    sparse = layers - config["first_k_dense_replace"]
    d, f = config["hidden_size"], config["moe_intermediate_size"]
    routed_share = (config["num_experts_per_tok"] * config["n_routed_experts"]
                    / config.get("router_width", config["n_routed_experts"]))
    return (6.0 * n_outside
            + 6.0 * sparse * routed_share * 3 * d * f
            + layers * attention_core_flops_per_token(config, seq_len))


def params_outside(params, config: dict) -> int:
    """Parameters outside the embedding table, the routed experts and the
    routers' selection bias."""
    sparse = config["num_hidden_layers"] - config["first_k_dense_replace"]
    return (hybrid_family.params_outside(params)
            - sparse * config.get("router_width", config["n_routed_experts"]))


def setup(config: dict, traffic: dict, seed: int) -> Cell:
    import jax

    from distributed_machine_learning_tpu.cli import lm as cli
    # A program without this module would build its dense model from the
    # file's sizes: it fails here, before anything is compiled.
    from distributed_machine_learning_tpu.models.mla_moe import MLAMoELM

    world = jax.device_count()
    # The program reads its sizes from a file: the very object the harness
    # read, written where the driver's TMPDIR says, gone after set-up.
    with tempfile.TemporaryDirectory(prefix="bench_config_") as scratch:
        path = os.path.join(scratch, "model_config.json")
        with open(path, "w", encoding="utf-8") as f:
            json.dump(config, f)
        argv = hybrid_family.argv_for(path, traffic, world)
        model, chunks = hybrid_family.model_from_argv(argv)
        if not isinstance(model, MLAMoELM):
            raise ValueError(
                f"cli.lm built {type(model).__name__} from model_type "
                f"{config.get('model_type')!r}, not the latent-attention MoE")
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
    """The first layer's key/value down-projection (its gradient crosses the
    latent norm, the up-projection, the one rotated key all heads share, the
    kernels and every later layer) and query projection, the dense MLP, the
    first router (through the renormalised, scaled weights only), the last
    layer's latent norm, held experts and shared expert, the head, the final
    norm."""
    first = config["first_k_dense_replace"]
    last = config["num_hidden_layers"] - 1
    paths = ["block_0/attn/kv_a_proj_with_mqa/kernel",
             "block_0/attn/q_proj/kernel",
             f"block_{first}/moe/router/kernel",
             f"block_{last}/attn/kv_a_layernorm/weight",
             f"block_{last}/moe/w_gate",
             f"block_{last}/moe/shared_up_proj/kernel",
             "lm_head/kernel", "norm_f/weight"]
    if first > 0:
        paths.insert(2, "block_0/mlp/down_proj/kernel")
    return paths


def system_loss_and_grads(result, model, chunks, tokens, targets, paths,
                          routing_block: str):
    """The program's loss and sampled gradients on the resident parameters,
    and the routed expert ids of ``routing_block``'s sparse layer."""
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
        return out, sown["moe_routing"][routing_block]["moe"]["expert_idx"][0]

    # Every chip computes the same check on its replica of the parameters:
    # the kernels then see local shapes, as they do inside the step.
    mesh = jax.tree_util.tree_leaves(params)[0].sharding.mesh
    system = jax.jit(jax.shard_map(
        system, mesh=mesh, in_specs=(P(), P(), P()), out_specs=P(),
        check_vma=False))
    return jax.device_get(system(params, tokens, targets))


def selection_bias_drift(model, params) -> float:
    """Largest ``|b − b₀|`` over the sparse layers: ``b`` the resident
    ``e_score_correction_bias``, ``b₀`` the draw ``cli.lm`` made at
    initialization, made again (under ``jit`` only those leaves are
    computed).  ``lm_loss`` in :func:`check` and the reference are both
    handed the resident ``b``, so a ``b`` that the timed path's optimizer
    moved or decayed would pass them: this is what sees it."""
    import jax
    import numpy as np

    from distributed_machine_learning_tpu.cli.lm import SEED
    from distributed_machine_learning_tpu.train.lm_step import init_lm_state

    def biases(params):
        return {jax.tree_util.keystr(path): leaf for path, leaf
                in jax.tree_util.tree_leaves_with_path(params)
                if path[-1].key == "e_score_correction_bias"}

    drawn = jax.device_get(jax.jit(
        lambda: biases(init_lm_state(model, seed=SEED).params))())
    resident = jax.device_get(biases(params))
    if not drawn or drawn.keys() != resident.keys():
        raise ValueError(f"selection biases drawn {sorted(drawn)}, "
                         f"resident {sorted(resident)}")
    return max(float(np.abs(resident[k] - drawn[k]).max()) for k in drawn)


def check(result, model, chunks, config: dict, traffic: dict, seed: int,
          operand_dtype=None) -> dict:
    """``operand_dtype``: grade the REFERENCE computed with its matmul
    operands rounded through that dtype in the program's place (how the
    limits' lower reading is taken; never in a run)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    tokens, targets = next(generate.token_blocks(
        seed, batch=traffic["check_seqs"], seq_len=traffic["seq_len"],
        vocab=config["vocab_size"], stream=1))
    paths = tuple(sample_paths(config))
    params = result.state.params

    def ref(operand_dtype):  # the configuration is static: closed over
        return jax.device_get(jax.jit(
            lambda params, tokens, targets: reference.loss_and_grads(
                params, config, tokens, targets, paths, operand_dtype))(
            params, jnp.asarray(tokens), jnp.asarray(targets)))

    ref_loss, ref_grads = ref(None)
    if operand_dtype is None:
        (loss, grads), routing = system_loss_and_grads(
            result, model, chunks, tokens, targets, paths,
            f"block_{config['first_k_dense_replace']}")
        # Top-k sets of the first sparse layer, program against reference:
        # near ties fall differently in bf16.  Stated, not limited, and the
        # reference is never handed the program's choices.
        ref_routing = np.asarray(jax.jit(
            lambda params, tokens: reference.first_sparse_routing(
                params, config, tokens))(params, jnp.asarray(tokens)))
        k = ref_routing.shape[-1]
        same = (np.asarray(routing).reshape(-1, k)[:, :, None]
                == ref_routing.reshape(-1, k)[:, None, :]).any(-1).mean()
        extra = {"top_k_differing_share": float(1.0 - same),
                 "selection_bias_drift": selection_bias_drift(model, params)}
    else:
        loss, grads = ref(operand_dtype)
        extra = {"operand_dtype": str(jnp.dtype(operand_dtype))}
    out = grade(float(loss), float(ref_loss), grads, ref_grads)
    out["ok"] = bool(out["ok"] and extra.get("selection_bias_drift", 0.0)
                     <= BIAS_DRIFT_ATOL)
    return {"sequences": int(tokens.shape[0]),
            "seq_len": int(tokens.shape[1]), **out, **extra}


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
