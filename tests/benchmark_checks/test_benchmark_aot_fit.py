"""AOT compiles at the real sizes for a described ``v5e:2x2`` device — no chip.

Guards what a chip run would otherwise find out the expensive way: that the
``sc2_3b_dp_s4096`` step fits 16 GB at the depth its configuration file
states, that the reference-check programs fit beside the resident state and
stay under the step's own peak (so the process's ``memory_peak_bytes`` is the
step's), and that the VGG cells' steps pass the quarter-of-a-chip floor that
chose their batches.

Written as the ``on-chip-measurement`` guide's section 2 requires: the
topology is described inside a module-scoped fixture (never at import), the
file skips from there, nothing starts a child process, and all such tests
live in this one file.
"""

from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
GIB = 2**30
#: What a v5e chip offers a program (``memory_stats()["bytes_limit"]``, 15.75 GiB).
USABLE = 15.75 * GIB


def _load(rel):
    with open(os.path.join(REPO, rel), encoding="utf-8") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe: skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def compiled_for_chip(topo, monkeypatch_module):
    """Compile as the chip would: Mosaic kernels compiled (not interpreted),
    and the persistent cache off (a described-device entry cannot be read
    back without a chip, and warns)."""
    from jax.experimental.compilation_cache import compilation_cache

    from distributed_machine_learning_tpu.ops.pallas import (
        common,
        flash_attention,
    )

    # Each kernel module binds the shared decision by name at import.
    for module in (common, flash_attention):
        for name in ("interpret", "_interpret"):
            if hasattr(module, name):
                monkeypatch_module.setattr(module, name, lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def monkeypatch_module():
    mp = pytest.MonkeyPatch()
    yield mp
    mp.undo()


def _shaped(tree, sharding):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _lm_parts(topo):
    """Model, abstract state and one-chip mesh of ``sc2_3b_dp_s4096``, built
    from the committed files through the family's own argv."""
    from benchmark.families import lm as family
    from distributed_machine_learning_tpu.train.adamw import AdamWConfig
    from distributed_machine_learning_tpu.train.lm_step import init_lm_state

    config = _load("benchmark/configs/starcoder2_3b.json")
    traffic = _load("benchmark/traffic/dp_2x4096.json")
    model, chunks = family.model_from_argv(family.argv_for(config, traffic, world=1))
    state = jax.eval_shape(
        lambda: init_lm_state(model, config=AdamWConfig()))
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("batch", "seq"))
    return config, traffic, model, chunks, state, mesh


def test_sc2_3b_step_fits_the_chip_at_the_stated_depth(compiled_for_chip):
    from distributed_machine_learning_tpu.train.lm_step import (
        make_lm_train_step,
    )

    config, traffic, model, chunks, state, mesh = _lm_parts(compiled_for_chip)
    assert (model.d_model, model.n_heads, model.n_kv_heads, model.vocab_size,
            model.n_layers) == (3072, 24, 2, 49152, 4)
    n_params = sum(int(np.prod(a.shape))
                   for a in jax.tree_util.tree_leaves(state.params))
    assert abs(n_params - 686.0e6) < 0.5e6, n_params
    step = make_lm_train_step(model, mesh=mesh, fused_ce_chunks=chunks)
    tokens = jax.ShapeDtypeStruct(
        (traffic["seqs_per_chip"], traffic["seq_len"]), jnp.int32,
        sharding=NamedSharding(mesh, P("batch", "seq")))
    compiled = step.lower(
        _shaped(state, NamedSharding(mesh, P())), tokens, tokens).compile()
    assert "tpu_custom_call" in compiled.as_text()  # the flash kernels
    mem = compiled.memory_analysis()
    # The state is donated: arguments and outputs share their bytes.
    peak = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert peak <= USABLE, f"{peak / GIB:.2f} GiB does not fit"
    assert peak >= 0.25 * 16e9  # the cell fills the chip, not a corner of it
    # What the configuration file's memory reckoning states.
    assert 7.5 * GIB < mem.argument_size_in_bytes < 7.9 * GIB
    assert 3.5 * GIB < mem.temp_size_in_bytes < 4.8 * GIB


def test_sc2_3b_reference_check_fits_beside_the_state(compiled_for_chip):
    """The plain reference and the system's loss-and-gradient program run
    while the training state (7.67 GiB) is resident, and must not set the
    process's memory peak: each stays under the step's temporaries."""
    from benchmark.families import lm as family
    from benchmark.reference import transformer_lm as reference
    from distributed_machine_learning_tpu.train.lm_step import lm_loss

    config, traffic, model, chunks, state, mesh = _lm_parts(compiled_for_chip)
    rep = NamedSharding(mesh, P())
    params = _shaped(state.params, rep)
    tokens = jax.ShapeDtypeStruct(
        (traffic["check_seqs"], traffic["seq_len"]), jnp.int32, sharding=rep)
    paths = tuple(family.sample_paths(config["num_hidden_layers"]))
    ref = jax.jit(reference.loss_and_grads, static_argnames="sample").lower(
        params, tokens, tokens, sample=paths).compile().memory_analysis()

    def system(params, tokens, targets):
        picked = {p: reference.get_leaf(params, p) for p in paths}
        return jax.value_and_grad(lambda s: lm_loss(
            model, reference.with_leaves(params, s), tokens, targets,
            chunks))(picked)

    sys_mem = jax.jit(jax.shard_map(
        system, mesh=mesh, in_specs=(P(), P(), P()), out_specs=P(),
        check_vma=False)).lower(params, tokens, tokens).compile(
    ).memory_analysis()
    for name, mem in (("reference", ref), ("system", sys_mem)):
        extra = mem.temp_size_in_bytes + mem.output_size_in_bytes
        assert extra < 4.0 * GIB, f"{name} check needs {extra / GIB:.2f} GiB"


@pytest.mark.parametrize("traffic_file, use_bn", [
    ("part3_b8192", True), ("part1_b16384", False)])
def test_vgg_cells_pass_the_memory_floor(compiled_for_chip, traffic_file,
                                         use_bn):
    """The batch of each VGG cell is the smallest power of two whose compiled
    step holds a quarter of the chip's 16 GB; half of it does not."""
    from jax.sharding import SingleDeviceSharding

    from distributed_machine_learning_tpu.cli.common import (
        init_model_and_state,
    )
    from distributed_machine_learning_tpu.models.registry import get_model
    from distributed_machine_learning_tpu.parallel.strategies import (
        get_strategy,
    )
    from distributed_machine_learning_tpu.train.step import make_train_step

    topo = compiled_for_chip
    traffic = _load(f"benchmark/traffic/{traffic_file}.json")
    model = get_model("vgg11", use_bn=use_bn, compute_dtype=jnp.bfloat16)
    state = jax.eval_shape(lambda: init_model_and_state(model))
    if use_bn:
        mesh = Mesh(np.array(topo.devices[:1]), ("batch",))
        step = make_train_step(
            model, get_strategy("ring", bucket_bytes=25 * 2**20), mesh=mesh,
            optimizer="sgd")
        rep, bat = NamedSharding(mesh, P()), NamedSharding(mesh, P("batch"))
    else:
        step = make_train_step(model, get_strategy("none"), optimizer="sgd")
        rep = bat = SingleDeviceSharding(topo.devices[0])

    def peak(batch):
        mem = step.lower(
            _shaped(state, rep),
            jax.ShapeDtypeStruct((batch, 32, 32, 3), jnp.uint8, sharding=bat),
            jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=bat),
        ).compile().memory_analysis()
        return mem.argument_size_in_bytes + mem.temp_size_in_bytes

    batch = traffic["per_rank_batch"]
    floor = 0.25 * 16e9
    assert floor < peak(batch) < USABLE
    assert peak(batch // 2) < floor
