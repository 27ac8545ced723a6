"""The plain references against the system's models at tiny sizes on the CPU,
on seeded random weights (biases and BatchNorm parameters included, so that
a dropped term shows)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import transformer_lm as ref_lm
from benchmark.reference import vgg as ref_vgg

CFG = (8, "M", 16, "M", 16, "M", 16, "M", 16, "M")
TOL = 2e-5  # float32 on both sides; only the order of sums differs


def _randomized(tree, seed):
    """Every leaf redrawn: flax zero-initializes biases, which would hide a
    dropped bias.  Kernels keep a fan-in scale, norm scales sit around 1,
    biases are of order 1."""
    leaves = jax.tree_util.tree_leaves_with_path(tree)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))

    def draw(key, path, a):
        noise = jax.random.normal(key, a.shape, a.dtype)
        if a.ndim > 1 and "bias" not in str(path[-1]):
            return noise / np.sqrt(np.prod(a.shape[:-1]) / (
                a.shape[0] if "embed" in str(path) else 1))
        return 1.0 + 0.3 * noise if "scale" in str(path[-1]) else noise

    return jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(tree),
        [draw(k, path, a) for k, (path, a) in zip(keys, leaves)])


def _vgg_system(use_bn, seed=0, batch=8):
    from distributed_machine_learning_tpu.models.vgg import VGG
    from distributed_machine_learning_tpu.train.common import make_loss_fn

    model = VGG(name_cfg="VGGTEST", use_bn=use_bn)
    x = jax.random.normal(jax.random.PRNGKey(seed), (batch, 32, 32, 3))
    labels = jax.random.randint(jax.random.PRNGKey(seed + 1), (batch,), 0, 10)
    variables = model.init(jax.random.PRNGKey(2), x, train=False)
    params = _randomized(variables["params"], 3)
    stats = variables.get("batch_stats", {})
    (loss, (_, new_stats)), grads = jax.value_and_grad(
        make_loss_fn(model, stats, x, labels, train=True), has_aux=True)(params)
    return params, stats, x, labels, loss, grads, new_stats


def _max_rel(a, b):
    """Largest error of a leaf against that leaf's size; a leaf far smaller
    than the largest (a conv bias in front of BatchNorm, whose gradient is
    rounding noise) is held to the largest leaf's size instead."""
    a, b = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    floor = 1e-3 * max(float(jnp.abs(y).max()) for y in b)
    return max(float(jnp.abs(x - y).max() / max(float(jnp.abs(y).max()), floor))
               for x, y in zip(a, b))


@pytest.mark.parametrize("use_bn", [False, True])
def test_vgg_reference_matches_models_vgg(use_bn):
    params, stats, x, labels, loss, grads, new_stats = _vgg_system(use_bn)
    r_loss, r_grads, r_stats = ref_vgg.loss_and_grads(
        params, stats, x, labels, CFG)
    assert float(r_loss) == pytest.approx(float(loss), rel=TOL)
    assert _max_rel(r_grads, grads) < 1e-3
    if use_bn:
        assert _max_rel(r_stats, new_stats) < 1e-4


@pytest.mark.parametrize("dropped", ["conv bias", "bn", "bn groups"])
def test_vgg_dropped_term_breaks_the_tolerance(dropped):
    params, stats, x, labels, loss, grads, _ = _vgg_system(use_bn=True)
    broken, groups = params, 1
    if dropped == "conv bias":
        broken = {**params, "fc1": {**params["fc1"],
                                    "bias": jnp.zeros_like(params["fc1"]["bias"])}}
    elif dropped == "bn":
        broken = {k: v for k, v in params.items()
                  if not k.startswith("BatchNorm")}
    else:
        groups = 2  # statistics per half batch: the unsynced-BN fault
    from benchmark.families import cnn_part

    r_loss, r_grads, _ = ref_vgg.loss_and_grads(
        broken, stats, x, labels, CFG, bn_groups=groups)
    shared = cnn_part.flat(r_grads)  # the broken tree may lack tensors
    verdict = cnn_part.grade(float(loss), float(r_loss),
                             {k: cnn_part.flat(grads)[k] for k in shared},
                             shared)
    assert not verdict["ok"], verdict
    whole = ref_vgg.loss_and_grads(params, stats, x, labels, CFG)
    assert cnn_part.grade(float(loss), float(whole[0]), cnn_part.flat(grads),
                          cnn_part.flat(whole[1]))["ok"]


def test_vgg_bn_groups_are_data_parallel_workers():
    """Two groups = two workers that each normalize their own half: the loss
    is the mean of the halves' losses."""
    params, stats, x, labels, *_ = _vgg_system(use_bn=True)
    whole, _, _ = ref_vgg.loss_and_grads(params, stats, x, labels, CFG, 2)
    halves = [ref_vgg.loss_and_grads(params, stats, x[i:i + 4],
                                     labels[i:i + 4], CFG)[0]
              for i in (0, 4)]
    assert float(whole) == pytest.approx(float(sum(halves) / 2), rel=TOL)


def test_sgd_step_is_the_system_update():
    from distributed_machine_learning_tpu.train.sgd import SGDConfig, sgd_update

    params, _, _, _, _, grads, _ = _vgg_system(use_bn=False)
    momentum = _randomized(params, 9)
    cfg = SGDConfig()
    want_p, want_m = sgd_update(params, momentum, grads, cfg)
    got_p, got_m = ref_vgg.sgd_step(params, momentum, grads, cfg.learning_rate,
                                    cfg.momentum, cfg.weight_decay)
    assert _max_rel(got_p, want_p) < 1e-6 and _max_rel(got_m, want_m) < 1e-6


def _lm_system(seed=0):
    from distributed_machine_learning_tpu.models.transformer import (
        TransformerLM,
    )
    from distributed_machine_learning_tpu.train.lm_step import lm_loss

    model = TransformerLM(vocab_size=96, d_model=32, n_layers=2, n_heads=4,
                          n_kv_heads=2, attn_impl="dense")
    block = jax.random.randint(jax.random.PRNGKey(seed), (2, 25), 0, 96)
    tokens, targets = block[:, :-1], block[:, 1:]
    params = _randomized(
        model.init(jax.random.PRNGKey(1), tokens, train=False)["params"], 4)
    loss, grads = jax.value_and_grad(
        lambda p: lm_loss(model, p, tokens, targets))(params)
    return params, tokens, targets, loss, grads


def test_lm_reference_matches_models_transformer_gqa():
    params, tokens, targets, loss, grads = _lm_system()
    paths = ("block_0/attn/kv/kernel", "block_0/attn/q/bias",
             "block_1/fc_in/kernel", "block_1/fc_out/bias", "lm_head/kernel",
             "ln_f/scale", "embed/embedding")
    r_loss, r_grads = ref_lm.loss_and_grads(params, tokens, targets, paths)
    assert float(r_loss) == pytest.approx(float(loss), rel=TOL)
    for path in paths:
        want = ref_lm.get_leaf(grads, path)
        assert float(jnp.abs(r_grads[path] - want).max()) < \
            1e-3 * float(jnp.abs(want).max()), path


def _unmasked_head(qkv):
    q, k, v = qkv
    return jax.nn.softmax((q @ k.T) / np.sqrt(q.shape[-1]), axis=-1) @ v


@pytest.mark.parametrize("dropped", ["causal mask", "out bias", "ln bias",
                                     "kv grouping"])
def test_lm_dropped_term_breaks_the_tolerance(dropped, monkeypatch):
    from benchmark.families import lm as family

    jax.clear_caches()  # jax.checkpoint keeps a trace of block() by shape,
    # so a variant patched in by another case would be served again here
    params, tokens, targets, loss, grads = _lm_system()
    paths = tuple(family.sample_paths(2))
    picked = {p: ref_lm.get_leaf(grads, p) for p in paths}
    r_loss, r_grads = ref_lm.loss_and_grads(params, tokens, targets, paths)
    assert family.grade(float(loss), float(r_loss), picked, r_grads)["ok"]
    broken = params
    if dropped == "causal mask":
        monkeypatch.setattr(ref_lm, "_one_head", _unmasked_head)
    elif dropped in ("out bias", "ln bias"):
        path = {"out bias": "block_0/attn/out/bias",
                "ln bias": "block_1/ln2/bias"}[dropped]
        broken = ref_lm.with_leaves(params, {
            path: jnp.zeros_like(ref_lm.get_leaf(params, path))})
    else:  # query head i must read KV head i // 2, not i % 2
        monkeypatch.setattr(ref_lm, "expand_kv",
                            lambda a, rep: jnp.tile(a, (rep, 1, 1)))
    jax.clear_caches()
    r_loss, r_grads = ref_lm.loss_and_grads(broken, tokens, targets, paths)
    assert not family.grade(float(loss), float(r_loss), picked, r_grads)["ok"]


def test_with_leaves_replaces_only_the_named_leaf():
    params = {"a": {"b": 1, "c": 2}, "d": 3}
    out = ref_lm.with_leaves(params, {"a/b": 10})
    assert out == {"a": {"b": 10, "c": 2}, "d": 3}
    assert params["a"]["b"] == 1
