"""Family ``preroute_moe_lm``: a window / full attention MoE LM whose router
reads the block's input ahead of the attention and whose experts are ReGLU
(``model_type`` ``smallthinker``), trained through ``cli.lm --parallel dp
--model-config <file>``.

Set-up and the check are the ``kda_moe_lm`` family's arrangement (the
configuration written to a file, ``cli.lm.main(argv)`` in-process, the
weightless model rebuilt through ``cli.lm.dp_model`` from the same argv, the
system's own ``train/lm_step.py::lm_loss`` on the resident parameters, then
one call of the timed step held to AdamW): its ``update_error``, the
``mla_moe_lm`` family's ``system_loss_and_grads`` and the ``hybrid_moe_lm``
family's ``argv_for``, ``model_from_argv`` and ``params_outside`` are used as
they are.  This file holds what differs: the model FLOPs a token, the
sampled leaves, the reference and the limits.
"""

from __future__ import annotations

import json
import os
import tempfile

from benchmark import generate
from benchmark.families import hybrid_moe_lm as hybrid_family
from benchmark.families import kda_moe_lm as kda_family
from benchmark.families import lm as dense_family
from benchmark.families import mla_moe_lm as mla_family
from benchmark.harness import Cell
from benchmark.reference import preroute_moe_lm as reference

#: bf16 compute against the float32 reference at 16 384 tokens and published
#: widths, random weights two AdamW steps (5e-6) from initialization.  Each
#: limit lies between two readings on a TPU v5e (PERF.md §6): what the
#: program read in the cell's runs (nine seeds), and what the reference read
#: with every matmul operand rounded, on the way forward, to float8's exponent
#: and mantissa widths (``reference.rounder``: ``lax.reduce_precision`` 4/3,
#: the next precision below bf16; seed 2200000007), which fails all three.
#: - Loss: the program within 8.6e-8 … 1.7e-5; float8 5.1e-3.  The limit is
#:   60 times the first and a fifth of the second.
#: - Cosine of each sampled gradient: the program's lowest is the last
#:   layer's held experts or the first router (0.9935–0.9959: 0.23–0.26% of
#:   the first layer's top-6 sets fall differently in bf16, and those tokens'
#:   gradients land on other experts); every other leaf ≥ 0.9964.  float8
#:   reads 0.059 on the experts and at most 0.73 on any leaf.  0.95: seven
#:   times the program's worst distance from one, above every float8 leaf.
#: - Norm ratio of each sampled gradient: the program within 0.85%; float8
#:   52% on the experts, 44% on the last norm.  0.05: six times the first, a
#:   tenth of the second.
LOSS_RTOL = 0.001
GRAD_COSINE = 0.95
GRAD_NORM_RTOL = 0.05
#: What the timed step does to the state (``kda_moe_lm.update_error``): each
#: sampled leaf's change against plain AdamW of the gradient the step fed its
#: optimizer and the moments before; a state left unchanged reads 1.  The
#: program reads at most 2.4e-3 (the norm weights: float32 near one holds a
#: change of 5e-6 to a few parts in a thousand) and 2.6e-4 on every matrix;
#: the step's gradient, graded by the three limits above, reads as the
#: check's own.  0.1: forty times the first, a tenth of an unchanged state.
UPDATE_RTOL = 0.1


def attention_core_flops_per_token(config: dict, seq_len: int,
                                   layer: int) -> float:
    """Model FLOPs a trained token of ONE layer's attention kernels (forward
    and backward together): ``12 · H · head_dim`` a visible key times the
    mean number of keys a query sees — ``T/2`` on a full layer, ``k̄ = w −
    w(w − 1)/(2T)`` on a window layer (``window_moe_lm``'s count; ``T ≥
    w``).  Nothing recomputed is counted."""
    w = config["sliding_window_size"]
    if not config["sliding_window_layout"][layer] or w >= seq_len:
        keys = seq_len / 2.0
    else:
        keys = w - w * (w - 1) / (2.0 * seq_len)
    return 12.0 * config["num_attention_heads"] * config["head_dim"] * keys


def train_flops_per_token(config: dict, n_outside: int, seq_len: int) -> float:
    """Model FLOPs of one trained token (a matmul counts multiply and add,
    a step is 3 × its forward pass, recomputation is never counted):

    - ``6 · n_outside`` for every parameter outside the embedding table (a
      gather) and the routed experts: attention projections, routers, norms,
      head;
    - ``6 · L · (k · held / router_width) · expert`` for the routed experts'
      expected share (an expert is ``3 · d · f``: gate, up, down);
    - :func:`attention_core_flops_per_token` of each layer.
    """
    layers = config["num_hidden_layers"]
    d, f = config["hidden_size"], config["moe_ffn_hidden_size"]
    held = config["moe_num_primary_experts"]
    routed_share = (config["moe_num_active_primary_experts"] * held
                    / config.get("router_width", held))
    return (6.0 * n_outside
            + 6.0 * layers * routed_share * 3 * d * f
            + sum(attention_core_flops_per_token(config, seq_len, i)
                  for i in range(layers)))


def setup(config: dict, traffic: dict, seed: int) -> Cell:
    import jax

    from distributed_machine_learning_tpu.cli import lm as cli
    # A program without this module would build its dense model from the
    # file's sizes: it fails here, before anything is compiled.
    from distributed_machine_learning_tpu.models.preroute_moe import (
        PrerouteMoELM,
    )

    world = jax.device_count()
    # The program reads its sizes from a file: the very object the harness
    # read, written where TMPDIR says, gone after set-up.
    with tempfile.TemporaryDirectory(prefix="bench_config_") as scratch:
        path = os.path.join(scratch, "model_config.json")
        with open(path, "w", encoding="utf-8") as f:
            json.dump(config, f)
        argv = hybrid_family.argv_for(path, traffic, world)
        model, chunks = hybrid_family.model_from_argv(argv)
        if not isinstance(model, PrerouteMoELM):
            raise ValueError(
                f"cli.lm built {type(model).__name__} from model_type "
                f"{config.get('model_type')!r}, not the routed-ahead MoE")
        result = cli.main(argv)
    batch = traffic["seqs_per_chip"] * world
    cell = Cell(
        result=result,
        batches=lambda: generate.token_blocks(
            seed, batch=batch, seq_len=traffic["seq_len"],
            vocab=config["vocab_size"]),
        item="tokens",
        items_per_step=batch * traffic["seq_len"],
        flops_per_item=train_flops_per_token(
            config, hybrid_family.params_outside(result.state.params),
            traffic["seq_len"]),
        check=None,
        loss_must_fall=False,
    )
    # The check steps the state once (update_error): the window starts from
    # the state it hands back.
    cell.check = lambda: check(cell, model, chunks, config, traffic, seed)
    return cell


def sample_paths(config: dict) -> list[str]:
    """The embedding; the first layer's query projection (a full layer: no
    rotation, the windowless kernels), its input norm (its gradient reaches
    it through the attention AND the router) and its router (through the
    renormalised weights only); a window layer's key projection (rotated,
    the windowed kernels at 7 query heads a key head); the last layer's held
    experts (through ReLU) and its second norm; the head, the final norm."""
    last = config["num_hidden_layers"] - 1
    window = next(i for i, w in enumerate(config["sliding_window_layout"])
                  if w)
    return ["embed/embedding", "block_0/attn/q_proj/kernel",
            "block_0/input_layernorm/weight", "block_0/moe/router/kernel",
            f"block_{window}/attn/k_proj/kernel", f"block_{last}/moe/w_gate",
            f"block_{last}/post_attention_layernorm/weight",
            "lm_head/kernel", "norm_f/weight"]


def check(cell: Cell, model, chunks, config: dict, traffic: dict, seed: int,
          operand_dtype=None) -> dict:
    """``operand_dtype``: grade the REFERENCE computed with its matmul
    operands rounded through that dtype in the program's place (how the
    limits' lower reading is taken; never in a run; no step is taken)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    tokens, targets = next(generate.token_blocks(
        seed, batch=traffic["check_seqs"], seq_len=traffic["seq_len"],
        vocab=config["vocab_size"], stream=1))
    paths = tuple(sample_paths(config))
    result = cell.result
    params = result.state.params

    def ref(operand_dtype):  # the configuration is static: closed over
        return jax.device_get(jax.jit(
            lambda params, tokens, targets: reference.loss_and_grads(
                params, config, tokens, targets, paths, operand_dtype))(
            params, jnp.asarray(tokens), jnp.asarray(targets)))

    ref_loss, ref_grads = ref(None)
    if operand_dtype is None:
        (loss, grads), routing = mla_family.system_loss_and_grads(
            result, model, chunks, tokens, targets, paths, "block_0")
        # Top-k sets of the first layer, program against reference: near
        # ties fall differently in bf16.  Stated, not limited, and the
        # reference is never handed the program's choices.
        ref_routing = np.asarray(jax.jit(
            lambda params, tokens: reference.routing(
                params, config, tokens)["block_0"])(
            params, jnp.asarray(tokens)))
        k = ref_routing.shape[-1]
        same = (np.asarray(routing).reshape(-1, k)[:, :, None]
                == ref_routing.reshape(-1, k)[:, None, :]).any(-1).mean()
        extra = {"top_k_differing_share": float(1.0 - same)}
        # Last: the step donates ``params``.
        extra["update_error"], step_grads = kda_family.update_error(
            cell, tokens, targets, paths, traffic)
        stepped = grade(float(loss), float(ref_loss), step_grads, ref_grads)
        extra["step_grads_ok"] = stepped["ok"]
        extra["step_grad_cosine"] = stepped["grad_cosine"]
        extra["step_grad_norm_ratio"] = stepped["grad_norm_ratio"]
    else:
        loss, grads = ref(operand_dtype)
        extra = {"operand_dtype": str(jnp.dtype(operand_dtype))}
    out = grade(float(loss), float(ref_loss), grads, ref_grads)
    out["ok"] = bool(out["ok"]
                     and max(extra.get("update_error", {0: 0.0}).values())
                     <= UPDATE_RTOL and extra.get("step_grads_ok", True))
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
