"""Family ``cnn_part``: a CNN trained through ``cli.part1`` … ``cli.part3``.

Set-up calls the CLI's ``main(argv)`` in-process, as a user would, for a few
iterations; the window then reuses the step, state and placement it returned,
and the loader class ``run_part`` picks for ``--loader native``, fed images
made from the seed.  The reference check runs one more step of that same
compiled function on ``check_batch`` images a rank (the reference protocol's
own batch: the whole cell batch in float32 would outgrow the step's own
memory and set the process's peak) against ``reference/vgg.py``.
"""

from __future__ import annotations

import importlib

from benchmark import flops, generate
from benchmark.harness import Cell
from benchmark.reference import vgg as reference

#: bf16 compute against a float32 reference.  Loss: bf16 has 8 bits of
#: mantissa, so logits carry about 0.4% error and the mean loss well under
#: 2%.  Gradients pass through 8 bf16 convolutions: measured cosine 0.989 or
#: better a tensor, norms within 2% (my chip runs, PR 24).  Arithmetic coarser
#: than bf16 (an 8-bit float), a dropped BatchNorm, bias or weight-decay term,
#: or an unsynced ring lands far below 0.97 or outside 10% in norm.  The
#: update rule is float32 on both sides: its error is held to 1e-5 of the
#: step plus 4 ulp of the parameter, and reported as a share of that
#: allowance (at most 1).
LOSS_RTOL = 0.02
GRAD_COSINE = 0.97
GRAD_NORM_RTOL = 0.10
UPDATE_RTOL = 1e-5
STATS_RTOL = 0.02
#: A tensor whose reference gradient is under this share of the largest
#: tensor's norm has no direction to compare (a conv bias before BatchNorm).
NEGLIGIBLE = 1e-4


def argv_for(config: dict, traffic: dict) -> list[str]:
    return ["--model", config["model"], *traffic["argv"],
            "--batch-size", str(traffic["per_rank_batch"]),
            "--max-iters", str(traffic["warm_iters"]), "--eval-batches", "0"]


def setup(config: dict, traffic: dict, seed: int) -> Cell:
    from distributed_machine_learning_tpu.data.cifar10 import Dataset
    from distributed_machine_learning_tpu.data.native_loader import (
        NativeBatchLoader,
        NativeDistributedBatchLoader,
    )

    cli = importlib.import_module(
        "distributed_machine_learning_tpu.cli." + traffic["cli"])
    result = cli.main(argv_for(config, traffic))
    world = _world(result)
    images, labels = generate.images(seed, **traffic["data"])
    train_set = Dataset(images=images, labels=labels, synthetic=True)
    batch = traffic["per_rank_batch"]
    # cli/common.py::run_part: the distributed loader behind a mesh, the
    # plain one for part1.
    loader = (NativeDistributedBatchLoader(train_set, batch, world)
              if result.place_batch is not None
              else NativeBatchLoader(train_set, batch))
    if len(train_set) % (batch * world):
        raise ValueError(
            f"{len(train_set)} images do not divide into batches of "
            f"{batch * world}: a short last batch would compile a second "
            "shape inside the window")

    def batches():
        while True:
            yield from loader

    return Cell(
        result=result,
        batches=batches,
        item="images",
        items_per_step=batch * world,
        flops_per_item=flops.vgg_train_flops_per_image(
            config["cfg"], config["image_size"], config["num_channels"],
            config["num_classes"]),
        check=lambda: check(result, config, traffic, train_set, world),
        loss_must_fall=True,
    )


def _world(result) -> int:
    if result.place_batch is None:
        return 1
    import jax

    leaf = jax.tree_util.tree_leaves(result.state.params)[0]
    return leaf.sharding.mesh.size


def _augmented(state, images, world: int, on_mesh: bool):
    """The step's own input pipeline (``data/augment.py``, keyed as
    ``train/common.py::step_rng`` keys it), rank by rank — the reference
    checks the model, the loss, the sync and the update, given the same
    pixels."""
    import jax
    import jax.numpy as jnp

    from distributed_machine_learning_tpu.data.augment import augment_batch

    key = jax.random.fold_in(state.rng, state.step)
    per_rank = jnp.asarray(images).reshape(world, -1, *images.shape[1:])
    return jnp.concatenate([
        augment_batch(jax.random.fold_in(key, r) if on_mesh else key,
                      per_rank[r])
        for r in range(world)
    ])


def check(result, config: dict, traffic: dict, train_set, world: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    n = traffic["check_batch"] * world
    images, labels = train_set.images[:n], train_set.labels[:n]
    state0 = jax.device_get(result.state)
    on_mesh = result.place_batch is not None
    x = _augmented(state0, images, world, on_mesh)
    ref_loss, ref_grads, ref_stats = jax.jit(
        reference.loss_and_grads, static_argnames=("cfg", "bn_groups"))(
        state0.params, state0.batch_stats, x, jnp.asarray(labels),
        cfg=tuple(config["cfg"]), bn_groups=world)
    cfg = state0.config
    # The compiled step donates its state where the CLI built it so: give
    # it a copy, the window needs the original.
    scratch_state = jax.tree_util.tree_map(jnp.copy, result.state)
    batch = (images, labels)
    if on_mesh:
        batch = result.place_batch(*batch)
    new_state, loss = result.train_step(scratch_state, *batch)
    new_state, loss = jax.device_get((new_state, loss))
    loss = float(np.mean(loss))
    p0, m0 = flat(state0.params), flat(state0.momentum)
    p1, m1 = flat(new_state.params), flat(new_state.momentum)
    # the gradient the system applied, read back out of its momentum buffer
    g_sys = {name: m1[name] - cfg.momentum * m0[name]
             - cfg.weight_decay * p0[name] for name in p0}
    out = grade(loss, float(ref_loss), g_sys, flat(ref_grads))
    # ... and the reference's SGD rule on that gradient must land on the
    # system's updated parameters (float32 on both sides)
    ref_p1, _ = reference.sgd_step(p0, m0, g_sys, cfg.learning_rate,
                                   cfg.momentum, cfg.weight_decay)
    eps = float(np.finfo(np.float32).eps)
    out["max_update_rule_error"] = max(
        float(np.abs(p1[name] - ref_p1[name]).max()
              / (UPDATE_RTOL * cfg.learning_rate * np.abs(m1[name]).max()
                 + 4 * eps * np.abs(p0[name]).max() + 1e-30))
        for name in p0)
    s1 = flat(new_state.batch_stats)
    out["max_bn_stats_error"] = max(
        (float(np.abs(s1[name] - ref).max() / (np.abs(ref).max() + 1e-6))
         for name, ref in flat(ref_stats).items()), default=0.0)
    out["ok"] = bool(out["ok"]
                     and out["max_update_rule_error"] <= 1.0
                     and out["max_bn_stats_error"] <= STATS_RTOL)
    return {"images": n, "bn_groups": world, **out}


def flat(tree) -> dict:
    """``{"a/b": numpy leaf}`` of a nested parameter tree."""
    import jax
    import numpy as np

    return {"/".join(str(getattr(k, "key", k)) for k in path): np.asarray(v)
            for path, v in jax.tree_util.tree_leaves_with_path(tree)}


def grade(loss: float, ref_loss: float, grads: dict, ref_grads: dict) -> dict:
    """The system's loss and gradients against the reference's, tensor by
    tensor, by the tolerances above."""
    import numpy as np

    largest = max(np.linalg.norm(g) for g in ref_grads.values())
    worst_cos, worst_norm = 1.0, 0.0
    for name, ref in ref_grads.items():
        norm_ref, norm_sys = np.linalg.norm(ref), np.linalg.norm(grads[name])
        if norm_ref < NEGLIGIBLE * largest:
            if norm_sys > 100 * NEGLIGIBLE * largest:
                worst_norm = float("inf")
            continue
        worst_cos = min(worst_cos, float(
            np.vdot(ref, grads[name]) / (norm_ref * norm_sys)))
        worst_norm = max(worst_norm, float(abs(norm_sys / norm_ref - 1.0)))
    ok = (abs(loss - ref_loss) <= LOSS_RTOL * abs(ref_loss)
          and worst_cos >= GRAD_COSINE and worst_norm <= GRAD_NORM_RTOL)
    return {"ok": bool(ok), "loss": loss, "reference_loss": ref_loss,
            "min_grad_cosine": worst_cos, "max_grad_norm_error": worst_norm}
