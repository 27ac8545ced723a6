"""What whole-block recomputation keeps (``models/transformer.py::
whole_block_policy``): the flash kernel's ``(out, lse)`` beside the block's
input, in all four LMs — each layer's forward kernel once in the gradient's
program, the numbers those of the model that recomputes nothing, and no
other program touched but for the ``name`` tags."""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_machine_learning_tpu.models import hybrid_moe as hm
from distributed_machine_learning_tpu.models import mla_moe as mm
from distributed_machine_learning_tpu.models import transformer as tr
from distributed_machine_learning_tpu.models import window_moe as wm
from distributed_machine_learning_tpu.ops.pallas import flash_attention
from distributed_machine_learning_tpu.train.losses import lm_cross_entropy
from tests.test_hybrid_moe import TINY as HYBRID
from tests.test_mla_moe import TINY as MLA
from tests.test_window_moe import TINY as WINDOW

VOCAB, L = 97, 128
#: name → (the model on the flash kernels, interpreted here; the forward
#: kernel's name a layer that runs it).
MODELS = {
    "TransformerLM": (lambda: tr.TransformerLM(
        vocab_size=VOCAB, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        attn_impl="flash"), ["flash_fwd"] * 2),
    # three DeltaNet layers, then the one gated-attention layer
    "HybridMoELM": (lambda: hm.HybridMoELM(
        hm.HybridMoESizes.from_config(HYBRID), attn_impl="flash"),
        ["flash_fwd"]),
    "MLAMoELM": (lambda: mm.MLAMoELM(
        mm.MLAMoESizes.from_config(MLA), attn_impl="flash"),
        ["flash_fwd_qk24v16"] * 3),
    "WindowMoELM": (lambda: wm.WindowMoELM(
        wm.WindowMoESizes.from_config(WINDOW), attn_impl="flash"),
        ["flash_fwd"] + ["flash_fwd_w16"] * 4),
}


@pytest.fixture(scope="module")
def batch():
    keys = jax.random.split(jax.random.PRNGKey(0))
    return tuple(jax.random.randint(k, (1, L), 0, VOCAB) for k in keys)


@pytest.fixture(scope="module", params=MODELS)
def lm(request, batch):
    make, forward = MODELS[request.param]
    model = make()
    params = model.init(jax.random.PRNGKey(1), batch[0])["params"]
    return model, params, sorted(forward)


def _loss_and_grads(model, batch):
    tokens, targets = batch
    return jax.value_and_grad(lambda p: lm_cross_entropy(
        model.apply({"params": p}, tokens), targets))


def _kernels(model, params, batch):
    """The flash kernels of the gradient's program, by name."""
    text = str(jax.make_jaxpr(_loss_and_grads(model, batch))(params))
    names = sorted(re.findall(r"name=(flash_(?:fwd|bwd)\w*)", text))
    return ([n for n in names if n.startswith("flash_fwd")],
            [n for n in names if n.startswith("flash_bwd")])


def test_a_recomputed_block_runs_each_forward_kernel_once(lm, batch,
                                                          monkeypatch):
    model, params, forward = lm
    backward = [n.replace("fwd", "bwd_fused") for n in forward]
    assert _kernels(model, params, batch) == (forward, backward)
    recomputed = model.clone(remat=True, remat_policy="block")
    assert _kernels(recomputed, params, batch) == (forward, backward)
    # The numbers are the un-recomputed model's to the bit — every
    # primitive run alone: XLA:CPU rounds a fused program by its context,
    # and a recomputed block splits the model's own jitted pieces.
    with jax.disable_jit():
        loss, grads = _loss_and_grads(model, batch)(params)
        got_loss, got = _loss_and_grads(recomputed, batch)(params)
    assert float(got_loss) == float(loss)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(grads), strict=True):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    # What the policy is for: a block that keeps its input alone makes
    # every forward call a second time.
    monkeypatch.setattr(tr, "whole_block_policy", lambda: None)
    assert _kernels(recomputed, params, batch) == (sorted(forward * 2),
                                                   backward)


def _residuals(capsys, f, *args):
    """``jax.ad_checkpoint.print_saved_residuals`` as (aval, source) rows,
    less the function's own arguments and constants."""
    capsys.readouterr()
    jax.ad_checkpoint.print_saved_residuals(f, *args)
    rows = [line.split(" ", 1) for line in
            capsys.readouterr().out.strip().splitlines()]
    return [(aval, src) for aval, src in rows
            if not src.startswith(("from the argument", "from a constant"))]


@pytest.mark.parametrize("attn_impl, kept", [
    # the kernel's folded out [B·H, L, d_v] and float32 lse [B·H, 1, L]
    ("flash", ["f32[4,1,128]", "f32[4,128,16]"]),
    ("dense", []),
])
def test_a_recomputed_block_keeps_its_inputs_and_the_tagged_pair(
        capsys, attn_impl, kept):
    block = tr.remat_whole_block(wm.WindowMoEBlock)(
        sizes=wm.WindowMoESizes.from_config(WINDOW),
        layer_type="sliding_attention", dense=False, attn_impl=attn_impl,
        compute_dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, L, 32))
    positions = jnp.arange(L)
    params = block.init(jax.random.PRNGKey(1), x, positions)["params"]
    rows = _residuals(
        capsys, lambda p, x: block.apply({"params": p}, x, positions).sum(),
        params, x)
    assert sorted(aval for aval, _ in rows) == kept
    assert sum("flash_lse" in src for _, src in rows) == (attn_impl == "flash")


def _program(jaxpr):
    """Every equation of a jaxpr and of the jaxprs in its parameters:
    (primitive, output types)."""
    out = []
    for eqn in jaxpr.eqns:
        out.append((eqn.primitive.name,
                    tuple(str(v.aval) for v in eqn.outvars)))
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    out.extend(_program(sub))
    return out


@pytest.mark.parametrize("settings", [
    {"remat": False}, {"remat": True, "remat_policy": "mlp"}],
    ids=["no_remat", "mlp"])
def test_without_block_recomputation_the_tags_are_all_that_changed(
        lm, batch, settings, monkeypatch):
    model, params, _ = lm
    model = model.clone(**settings)

    def program():
        return _program(jax.make_jaxpr(
            _loss_and_grads(model, batch))(params).jaxpr)

    tagged = program()
    monkeypatch.setattr(flash_attention, "checkpoint_name", lambda x, _: x)
    assert [e for e in tagged if e[0] != "name"] == program()
    assert sum(e[0] == "name" for e in tagged) >= 2
