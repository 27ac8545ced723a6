"""Plain reference for ``vgg11_cifar10``: VGG on 32×32 images as the reference
repository's ``model.py`` describes it (3×3 convolutions with bias at stride 1
and padding 1, optional BatchNorm2d, ReLU, 2×2 max-pools, one Linear head),
mean cross-entropy, and torch's SGD with momentum and L2 weight decay.

Straightforward ``jax.numpy`` in float32 under "highest" matmul precision.
Imports nothing from the system; takes the system's parameter tree by name
(``Conv_i``, ``BatchNorm_i``, ``fc1``).

Departures, each on purpose: BatchNorm keeps the *biased* batch variance in
its running average (what the system's flax layer does; torch keeps the
unbiased one), and with ``bn_groups`` > 1 each group of the batch is
normalized by its own statistics and the running averages take the groups'
mean — data parallelism over ``bn_groups`` workers, as DDP does it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

BN_EPS = 1e-5
BN_MOMENTUM = 0.9  # share of the old running value that is kept


def _batch_norm(x, scale, bias, running, groups):
    n = x.shape[0]
    g = x.reshape(groups, n // groups, *x.shape[1:])
    mean = g.mean(axis=(1, 2, 3), keepdims=True)
    var = (g * g).mean(axis=(1, 2, 3), keepdims=True) - mean * mean
    y = ((g - mean) / jnp.sqrt(var + BN_EPS)).reshape(x.shape) * scale + bias
    new_running = {
        "mean": BN_MOMENTUM * running["mean"]
        + (1 - BN_MOMENTUM) * mean.reshape(groups, -1).mean(0),
        "var": BN_MOMENTUM * running["var"]
        + (1 - BN_MOMENTUM) * var.reshape(groups, -1).mean(0),
    }
    return y, new_running


def forward(params, batch_stats, x, cfg, bn_groups=1):
    """Training-mode logits and new BN running statistics.  ``x``:
    normalized float32 NHWC."""
    new_stats = {}
    conv = 0
    for item in cfg:
        if item == "M":
            n, h, w, c = x.shape
            x = x.reshape(n, h // 2, 2, w // 2, 2, c).max(axis=(2, 4))
            continue
        p = params[f"Conv_{conv}"]
        x = jax.lax.conv_general_dilated(
            x, p["kernel"], (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        ) + p["bias"]
        bn = params.get(f"BatchNorm_{conv}")
        if bn is not None:
            x, new_stats[f"BatchNorm_{conv}"] = _batch_norm(
                x, bn["scale"], bn["bias"],
                batch_stats[f"BatchNorm_{conv}"], bn_groups,
            )
        x = jnp.maximum(x, 0.0)
        conv += 1
    x = x.reshape(x.shape[0], -1)
    return x @ params["fc1"]["kernel"] + params["fc1"]["bias"], new_stats


def cross_entropy(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], axis=-1).mean()


def loss_and_grads(params, batch_stats, x, labels, cfg, bn_groups=1):
    """``(loss, grads, new_batch_stats)`` on the whole batch."""

    def loss_fn(p):
        logits, new_stats = forward(p, batch_stats, x, cfg, bn_groups)
        return cross_entropy(logits, labels), new_stats

    with jax.default_matmul_precision("highest"):
        (loss, new_stats), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
    return loss, grads, new_stats


def sgd_step(params, momentum, grads, lr, mu, weight_decay):
    """torch.optim.SGD: ``g += wd·p; buf = mu·buf + g; p -= lr·buf``."""
    new_m = jax.tree_util.tree_map(
        lambda p, m, g: mu * m + g + weight_decay * p, params, momentum, grads)
    new_p = jax.tree_util.tree_map(lambda p, m: p - lr * m, params, new_m)
    return new_p, new_m
