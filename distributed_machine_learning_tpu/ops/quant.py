"""Weight-only int8 serving quantization: module + checkpoint converter.

Two pieces on top of the Pallas kernel (``ops/pallas/quant_matmul.py``):

- :class:`QuantDenseGeneral` — the drop-in projection module the decode
  model uses when ``weight_quant="int8"``: params are ``w_q`` (int8,
  [D_in_flat, K_out_flat]), ``scale`` (f32, [K]), ``bias`` (original
  shape), and the matmul is the int8-reading kernel.  Input/output axis
  grouping mirrors ``nn.DenseGeneral`` so activations are bit-shaped
  identically to the unquantized model.
- :func:`quantize_lm_params` — walks a trained ``TransformerLM`` params
  tree and rewrites every ``kernel``-bearing projection to that layout
  (per-output-channel symmetric int8, ``quantize_int8``).  Embeddings
  and LayerNorms pass through untouched (a gather and O(D) vectors —
  no bandwidth to win), as does anything else without a ``kernel``.

Why serving-only: quantized weights are constants of the decode
program; training keeps full-precision master weights (the usual
weight-only recipe).  The reference has no inference path at all
(part1/main.py:62-77 is classification eval) — this is beyond-parity
capability.
"""

from __future__ import annotations

import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributed_machine_learning_tpu.ops.pallas.quant_matmul import (
    int8_matmul,
    quantize_int8,
)


class QuantDenseGeneral(nn.Module):
    """``nn.DenseGeneral``-shaped projection over int8 weights.

    ``out_features``: the output axis shape appended to the input's
    leading axes (e.g. ``(3, H, Dh)`` for the fused qkv, ``(V,)`` for
    the head); ``n_in_axes``: trailing input axes contracted (2 for the
    attention out-projection's [H, Dh]).  The flattened kernel lives as
    ``w_q``/``scale``; ``bias`` keeps the unquantized module's shape so
    :func:`quantize_lm_params` can pass it through unchanged.
    """

    out_features: tuple[int, ...]
    n_in_axes: int = 1
    compute_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        in_shape = x.shape[-self.n_in_axes:]
        d_in = math.prod(in_shape)
        k_out = math.prod(self.out_features)
        w_q = self.param(
            "w_q", nn.initializers.zeros, (d_in, k_out), jnp.int8
        )
        scale = self.param(
            "scale", nn.initializers.ones, (k_out,), jnp.float32
        )
        bias = self.param(
            "bias", nn.initializers.zeros, self.out_features, jnp.float32
        )
        lead = x.shape[: x.ndim - self.n_in_axes]
        rows = math.prod(lead) if lead else 1
        y = int8_matmul(x.reshape(rows, d_in), w_q, scale)
        y = y.reshape(*lead, *self.out_features).astype(self.compute_dtype)
        return y + bias.astype(self.compute_dtype)


# Module names whose kernels contract TWO trailing input axes (the
# attention out-projection's [H, Dh] — nn.DenseGeneral(axis=(-2, -1))).
_TWO_AXIS_MODULES = frozenset({"out"})


def _quantize_module(name: str, leaves: dict) -> dict:
    kernel = leaves["kernel"]
    n_in = 2 if name in _TWO_AXIS_MODULES else 1
    if name in _TWO_AXIS_MODULES and kernel.ndim != 3:
        # The two-input-axis flatten is keyed on the module NAME alone,
        # so validate the structure it assumes: the attention
        # out-projection's kernel is [H, Dh, E].  Any other module that
        # happens to be named 'out' would otherwise be silently
        # mis-flattened into wrong serving weights.
        raise ValueError(
            f"module {name!r} is flattened over two input axes "
            f"(attention out-projection, kernel rank 3) but its kernel "
            f"has rank {kernel.ndim} {kernel.shape}; rename the module "
            "or extend _TWO_AXIS_MODULES' rule"
        )
    if kernel.ndim < n_in + 1:
        raise ValueError(
            f"module {name!r}: kernel rank {kernel.ndim} leaves no "
            f"output axis after {n_in} input axes"
        )
    d_in = math.prod(kernel.shape[:n_in])
    q, scale = quantize_int8(jnp.reshape(kernel, (d_in, -1)))
    out = {"w_q": q, "scale": scale}
    if "bias" in leaves:
        out["bias"] = leaves["bias"]
    return out


def _quantize_expert_module(leaves: dict) -> dict:
    """A ``MoEMLP`` module's params → its ``weight_quant="int8"``
    layout: the [E, D_in, D_out] expert kernels quantize per-expert
    per-output-channel (a vmapped :func:`quantize_int8` over the expert
    axis), biases pass through, and the ROUTER stays f32 — its [D, E]
    matmul has no bandwidth to win and its argmax decides the routing
    (``models/moe.py::MoEMLP``)."""
    qi, si = jax.vmap(quantize_int8)(leaves["w_in"])
    qo, so = jax.vmap(quantize_int8)(leaves["w_out"])
    return {
        "router": leaves["router"],
        "w_in_q": qi, "w_in_scale": si, "b_in": leaves["b_in"],
        "w_out_q": qo, "w_out_scale": so, "b_out": leaves["b_out"],
    }


def quantize_lm_params(params) -> dict:
    """Trained ``TransformerLM`` / ``MoETransformerLM`` params → the
    ``weight_quant="int8"`` decode model's structure.  Dense projections
    (any module with a ``kernel``) go per-output-channel int8; MoE
    expert modules (the ``w_in``/``w_out`` leaves) go per-expert
    per-output-channel with the router left f32.  Pure function of
    arrays — jit-safe, and cheap enough to run once at serving setup."""

    def walk(name: str, node):
        if isinstance(node, dict) or hasattr(node, "items"):
            node = dict(node)
            if "kernel" in node:
                return _quantize_module(name, node)
            if "w_in" in node and "w_out" in node:
                return _quantize_expert_module(node)
            return {k: walk(k, v) for k, v in node.items()}
        return node

    return walk("", params)
