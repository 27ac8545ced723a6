"""What whole-block recomputation keeps (``models/transformer.py::
whole_block_policy``): beside the block's input, the flash kernel's inputs
and ``(out, lse)``, and the expert layer's routing, dispatched rows and
pre-activations, in all five LMs — each layer's forward kernel once in the
gradient's program, the numbers those of the model that recomputes nothing,
and no other program touched but for the ``name`` tags."""

from __future__ import annotations

import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.ad_checkpoint import checkpoint_name

from distributed_machine_learning_tpu.models import hybrid_moe as hm
from distributed_machine_learning_tpu.models import mla_moe as mm
from distributed_machine_learning_tpu.models import preroute_moe as pm
from distributed_machine_learning_tpu.models import transformer as tr
from distributed_machine_learning_tpu.models import window_moe as wm
from distributed_machine_learning_tpu.train.losses import lm_cross_entropy
from tests.test_hybrid_moe import TINY as HYBRID
from tests.test_mla_moe import TINY as MLA
from tests.test_preroute_moe import TINY as PREROUTE
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
    # one global layer without rotation, then three windowed ones
    "PrerouteMoELM": (lambda: pm.PrerouteMoELM(
        pm.PrerouteMoESizes.from_config(PREROUTE), attn_impl="flash"),
        ["flash_fwd"] + ["flash_fwd_w16"] * 3),
}


def untagged(monkeypatch, keep=()):
    """Every ``checkpoint_name`` call of the program's modules made the
    identity but for the tags in ``keep``."""
    def tag(x, name):
        return checkpoint_name(x, name) if name in keep else x

    for module in list(sys.modules.values()):
        if (getattr(module, "__name__", "").startswith(
                "distributed_machine_learning_tpu.")
                and getattr(module, "checkpoint_name", None)
                is checkpoint_name):
            monkeypatch.setattr(module, "checkpoint_name", tag)


@pytest.fixture(scope="module")
def batch():
    keys = jax.random.split(jax.random.PRNGKey(0))
    return tuple(jax.random.randint(k, (1, L), 0, VOCAB) for k in keys)


@pytest.fixture(scope="module", params=MODELS)
def lm(request, batch):
    make, forward = MODELS[request.param]
    model = make()
    params = jax.jit(model.init)(jax.random.PRNGKey(1), batch[0])["params"]
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


#: What the flash kernel's call keeps, folded to [B·H, L, ·]: q, k, v
#: (K/V unrepeated), out and the float32 lse — at 4 query heads over 2 key
#: heads of 16 (WINDOW), 7 over 1 (PREROUTE).
FLASH_4_2 = ["f32[2,128,16]"] * 2 + ["f32[4,1,128]"] + ["f32[4,128,16]"] * 2
FLASH_7_1 = ["f32[1,128,16]"] * 2 + ["f32[7,1,128]"] + ["f32[7,128,16]"] * 2
#: What the expert layer keeps over 128 tokens, 3 experts a token (384
#: buffer rows): the router's logits over 8, each row's token and weight,
#: each assignment's slot, the groups' sizes; then the dispatched rows and
#: the two pre-activations [384, F] (of SiLU's, what its reverse reads: σ(g),
#: not g).
ROUTED = ["f32[128,8]", "f32[384]", "i32[128,3]", "i32[384]", "i32[8]"]
#: ... and the chosen experts where a selection bias chose them (the
#: gather's clamped copy beside them).
CHOSEN = ["i32[128,3]"] * 2
#: The sandwich-normed block's attention and feed-forward outputs, and a
#: gated MLP's two pre-activations: the shared expert's [128, 16], the dense
#: layer's [1, 128, 48].
SUBLAYERS = ["f32[1,128,32]"] * 2
SHARED, DENSE = ["f32[128,16]"] * 2, ["f32[1,128,48]"] * 2


def _window_block(attn_impl, dense):
    return tr.remat_whole_block(wm.WindowMoEBlock)(
        sizes=wm.WindowMoESizes.from_config(WINDOW),
        layer_type="sliding_attention", dense=dense, attn_impl=attn_impl,
        compute_dtype=jnp.float32), 32


def _preroute_block(attn_impl, dense):
    assert not dense  # every SmallThinker layer is sparse
    sizes = pm.PrerouteMoESizes.from_config(PREROUTE)
    return tr.remat_whole_block(pm.PrerouteMoEBlock)(
        sizes=sizes, sliding=True, rotary=True, attn_impl=attn_impl,
        compute_dtype=jnp.float32), sizes.d_model


EXPERTS_32_16 = ROUTED + CHOSEN + ["f32[384,32]"] + ["f32[384,16]"] * 2


@pytest.mark.parametrize("make, attn_impl, dense, kept", [
    (_window_block, "flash", False,
     FLASH_4_2 + SUBLAYERS + EXPERTS_32_16 + SHARED),
    (_window_block, "dense", False, SUBLAYERS + EXPERTS_32_16 + SHARED),
    # a dense MLP: nothing on grouped_expert_mlp's path to keep
    (_window_block, "flash", True, FLASH_4_2 + SUBLAYERS + DENSE),
    (_window_block, "dense", True, SUBLAYERS + DENSE),
    # routed from the block's input by a softmax, no bias; no shared expert;
    # a norm reads the attention's output, none the experts'; ReLU's reverse
    # reads the gate's pre-activation itself
    (_preroute_block, "flash", False, FLASH_7_1 + ["f32[1,128,64]"] + ROUTED
     + ["f32[384,64]"] + ["f32[384,32]"] * 2),
], ids=["window-flash-experts", "window-dense-experts", "window-flash-mlp",
        "window-dense-mlp", "preroute-flash-experts"])
def test_a_recomputed_block_keeps_its_inputs_and_the_tagged_pair(
        capsys, make, attn_impl, dense, kept):
    block, d_model = make(attn_impl, dense)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, L, d_model))
    positions = jnp.arange(L)
    params = jax.jit(block.init)(jax.random.PRNGKey(1), x, positions)["params"]
    rows = _residuals(
        capsys, lambda p, x: block.apply({"params": p}, x, positions).sum(),
        params, x)
    assert sorted(aval for aval, _ in rows) == sorted(kept)
    assert sum("flash_lse" in src for _, src in rows) == (attn_impl == "flash")
    assert sum("expert_sizes" in src for _, src in rows) == (not dense)


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
    untagged(monkeypatch)
    assert [e for e in tagged if e[0] != "name"] == program()
    assert sum(e[0] == "name" for e in tagged) >= 5
