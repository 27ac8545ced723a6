"""Family ``lm``: a decoder-only LM trained through ``cli.lm``.

Set-up calls ``cli.lm.main(argv)`` in-process for two iterations; the window
reuses the step, state and placement it returned, fed token blocks made from
the seed.  ``RunResult`` does not carry the model, so for the reference check
the family rebuilds the (weightless) ``TransformerLM`` description from the
same argv, as ``cli.lm.build`` does for ``--parallel dp``, and calls the
system's own loss function (``train/lm_step.py::lm_loss``: flash attention,
fused cross-entropy, bf16) on the resident parameters.
"""

from __future__ import annotations

from benchmark import flops, generate
from benchmark.harness import Cell
from benchmark.reference import transformer_lm as reference

#: bf16 compute against a float32 reference, random weights two AdamW steps
#: from initialization.  The loss sits near ln(vocab) whatever the model
#: does, so it is held to 0.2%; the weight of the check is on the gradients
#: of the sampled tensors: measured cosine 0.9999 or better, norms within
#: 0.07%, loss within 1e-5 (my chip runs, PR 24).  A missing causal mask,
#: rotation, GELU or scale, or arithmetic coarser than bf16, lands far
#: outside.  AdamW's first updates are about
#: lr·sign(g) and would hide a wrong magnitude, hence gradients and not
#: "parameters after one step".
LOSS_RTOL = 0.002
GRAD_COSINE = 0.995
GRAD_NORM_RTOL = 0.02

#: config key -> cli.lm flag: the sizes a configuration file states.
SIZE_FLAGS = {
    "hidden_size": "--d-model",
    "num_hidden_layers": "--n-layers",
    "num_attention_heads": "--n-heads",
    "num_key_value_heads": "--n-kv-heads",
    "vocab_size": "--vocab",
}


def argv_for(config: dict, traffic: dict, world: int) -> list[str]:
    if config["intermediate_size"] != 4 * config["hidden_size"]:
        raise ValueError("cli.lm has no --d-ff: the MLP is 4 x d_model")
    sizes = [x for key, flag in SIZE_FLAGS.items()
             for x in (flag, str(config[key]))]
    return [*sizes, *traffic["argv"],
            "--seq-len", str(traffic["seq_len"]),
            "--batch-size", str(traffic["seqs_per_chip"] * world),
            "--max-iters", str(traffic["warm_iters"])]


def setup(config: dict, traffic: dict, seed: int) -> Cell:
    import jax
    import numpy as np

    from distributed_machine_learning_tpu.cli import lm as cli

    world = jax.device_count()
    argv = argv_for(config, traffic, world)
    result = cli.main(argv)
    batch = traffic["seqs_per_chip"] * world
    n_params = sum(int(np.prod(leaf.shape)) for leaf in
                   jax.tree_util.tree_leaves(result.state.params))
    return Cell(
        result=result,
        batches=lambda: generate.token_blocks(
            seed, batch=batch, seq_len=traffic["seq_len"],
            vocab=config["vocab_size"]),
        item="tokens",
        items_per_step=batch * traffic["seq_len"],
        flops_per_item=flops.lm_train_flops_per_token(
            n_params, config["vocab_size"] * config["hidden_size"],
            config["num_hidden_layers"], config["hidden_size"],
            traffic["seq_len"]),
        check=lambda: check(result, argv, config, traffic, seed),
        loss_must_fall=False,
    )


def model_from_argv(argv: list[str]):
    """The model description ``cli.lm.build`` makes for ``--parallel dp``."""
    import jax.numpy as jnp

    from distributed_machine_learning_tpu.cli import lm as cli
    from distributed_machine_learning_tpu.models.transformer import (
        TransformerLM,
    )

    args = cli.make_parser().parse_args(argv)
    if args.parallel != "dp":
        raise ValueError("the lm family checks --parallel dp cells only; "
                         "another scheme needs its own family file")
    dtype = jnp.bfloat16 if args.compute_dtype == "bfloat16" else jnp.float32
    model = TransformerLM(
        vocab_size=args.vocab, d_model=args.d_model, n_layers=args.n_layers,
        n_heads=args.n_heads, n_kv_heads=args.n_kv_heads,
        compute_dtype=dtype, remat=args.remat,
        remat_policy=args.remat_policy, attn_impl=args.attn)
    return model, args.fused_ce_chunks


def sample_paths(n_layers: int) -> list[str]:
    """One attention kernel (the grouped K/V projection of the first block,
    whose gradient crosses every later layer, the rotation and the kernel's
    sum over a group's query heads), one MLP kernel, the head, the final
    LayerNorm."""
    return ["block_0/attn/kv/kernel", f"block_{n_layers - 1}/fc_in/kernel",
            "lm_head/kernel", "ln_f/scale"]


def check(result, argv, config: dict, traffic: dict, seed: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from distributed_machine_learning_tpu.train.lm_step import lm_loss

    model, chunks = model_from_argv(argv)
    params = result.state.params
    tokens, targets = next(generate.token_blocks(
        seed, batch=traffic["check_seqs"], seq_len=traffic["seq_len"],
        vocab=config["vocab_size"], stream=1))
    paths = sample_paths(config["num_hidden_layers"])

    def system(params, tokens, targets):
        picked = {p: reference.get_leaf(params, p) for p in paths}
        return jax.value_and_grad(lambda s: lm_loss(
            model, reference.with_leaves(params, s), tokens, targets,
            chunks))(picked)

    # Every chip computes the same check on its replica of the parameters:
    # the kernel then sees local shapes, as it does inside the step.
    mesh = jax.tree_util.tree_leaves(params)[0].sharding.mesh
    system = jax.jit(jax.shard_map(
        system, mesh=mesh, in_specs=(P(), P(), P()), out_specs=P(),
        check_vma=False))
    loss, grads = jax.device_get(system(params, tokens, targets))
    ref_loss, ref_grads = jax.device_get(jax.jit(
        reference.loss_and_grads, static_argnames="sample")(
        params, jnp.asarray(tokens), jnp.asarray(targets),
        sample=tuple(paths)))
    out = grade(float(loss), float(ref_loss), grads, ref_grads)
    return {"sequences": int(tokens.shape[0]),
            "seq_len": int(tokens.shape[1]), **out}


def grade(loss: float, ref_loss: float, grads: dict, ref_grads: dict) -> dict:
    """The system's loss and sampled gradients against the reference's, by
    the tolerances above."""
    import numpy as np

    cosines, norms = {}, {}
    for path, ref in ref_grads.items():
        g = np.asarray(grads[path], np.float32).ravel()
        r = np.asarray(ref, np.float32).ravel()
        cosines[path] = float(np.vdot(g, r) /
                              (np.linalg.norm(g) * np.linalg.norm(r)))
        norms[path] = float(np.linalg.norm(g) / np.linalg.norm(r))
    ok = (abs(loss - ref_loss) <= LOSS_RTOL * abs(ref_loss)
          and min(cosines.values()) >= GRAD_COSINE
          and max(abs(n - 1.0) for n in norms.values()) <= GRAD_NORM_RTOL)
    return {"ok": bool(ok), "loss": loss, "reference_loss": ref_loss,
            "grad_cosine": cosines, "grad_norm_ratio": norms}
