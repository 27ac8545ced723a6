"""Weight-only int8 serving: kernel parity, converter structure, and
token-exact generation vs the dequantized reference (ops/quant.py,
ops/pallas/quant_matmul.py — interpret mode on the CPU harness)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_machine_learning_tpu.inference.generate import (
    generate,
    make_generate_fn,
)
from distributed_machine_learning_tpu.models.transformer import TransformerLM
from distributed_machine_learning_tpu.ops.pallas.quant_matmul import (
    int8_matmul,
    quantize_int8,
)
from distributed_machine_learning_tpu.ops.quant import quantize_lm_params


def test_quantize_int8_roundtrip_error_bound():
    w = jnp.asarray(
        np.random.default_rng(0).standard_normal((64, 96)), jnp.float32
    ) * 0.02
    q, s = quantize_int8(w)
    assert q.dtype == jnp.int8 and s.shape == (96,)
    back = q.astype(jnp.float32) * s[None, :]
    # Symmetric 8-bit: error <= scale/2 per element, elementwise.
    assert float(jnp.abs(back - w).max()) <= float(s.max()) / 2 + 1e-8
    # All-zero columns quantize cleanly (scale 1, values 0).
    q0, s0 = quantize_int8(jnp.zeros((8, 4)))
    assert float(jnp.abs(q0).max()) == 0 and float(s0.min()) == 1.0


def test_int8_matmul_matches_dequant_reference():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((24, 64)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((64, 256)), jnp.float32) * 0.05
    q, s = quantize_int8(w)
    ref = x.astype(jnp.bfloat16) @ (
        q.astype(jnp.bfloat16) * s[None, :].astype(jnp.bfloat16)
    )
    out = int8_matmul(x, q, s)
    assert out.dtype == x.dtype
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=2e-2, atol=2e-2,
    )


def test_int8_matmul_pads_awkward_row_counts():
    """An odd prefill row count (> 8, no multiple-of-8 divisor) is
    zero-padded to tile rather than falling back to one whole-array
    tile (the VMEM blowup the caps exist to prevent)."""
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.standard_normal((13, 64)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((64, 128)), jnp.float32) * 0.05
    q, s = quantize_int8(w)
    out = int8_matmul(x, q, s)
    assert out.shape == (13, 128)
    ref = x.astype(jnp.bfloat16) @ (
        q.astype(jnp.bfloat16) * s[None, :].astype(jnp.bfloat16)
    )
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=2e-2, atol=2e-2,
    )


def test_int8_matmul_shape_guards():
    with pytest.raises(ValueError, match="shape mismatch"):
        int8_matmul(jnp.ones((8, 32)), *quantize_int8(jnp.ones((64, 128))))
    # An explicit block_k that does not tile still refuses loudly (the
    # auto path pads instead — test_int8_matmul_pads_awkward_widths).
    q, s = quantize_int8(jnp.ones((64, 1000)))
    with pytest.raises(ValueError, match="tile"):
        int8_matmul(jnp.ones((8, 64)), q, s, block_k=384)


def _dequant_tree(params, qparams):
    """Quantized tree → kernel-shaped full-precision tree (the reference
    a correct int8 path must reproduce through the kernel)."""

    def walk(ref, node):
        if isinstance(ref, dict):
            if "w_q" in node:
                w = node["w_q"].astype(jnp.float32) * node["scale"][None, :]
                out = {"kernel": w.reshape(ref["kernel"].shape)}
                if "bias" in node:
                    out["bias"] = node["bias"]
                return out
            return {k: walk(ref[k], node[k]) for k in ref}
        return node

    return walk(params, qparams)


@pytest.mark.parametrize("kv", [None, 2], ids=["mha", "gqa"])
def test_quantized_generate_token_exact_vs_dequant(kv):
    model = TransformerLM(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=kv
    )
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))[
        "params"
    ]
    qparams = quantize_lm_params(params)
    # Converter structure: every projection quantized, embed untouched.
    blk = qparams["block_0"]["attn"]
    assert ("qkv" if kv is None else "q") in blk
    for leaf in jax.tree_util.tree_leaves(
        blk[("qkv" if kv is None else "q")]["w_q"]
    ):
        assert leaf.dtype == jnp.int8
    assert "embedding" in qparams["embed"]

    prompt = np.asarray([[1, 2, 3, 4, 5, 6, 7, 8]], np.int32)
    ref = generate(model, _dequant_tree(params, qparams), prompt, 12)
    fn = make_generate_fn(model, 12, quantize="int8")
    out = fn(qparams, jnp.asarray(prompt), jax.random.PRNGKey(0))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_weight_quant_requires_decode():
    model = TransformerLM(
        vocab_size=64, d_model=32, n_layers=1, n_heads=4, weight_quant="int8"
    )
    with pytest.raises(ValueError, match="decode"):
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    with pytest.raises(ValueError, match="int8"):
        make_generate_fn(
            TransformerLM(vocab_size=64, d_model=32, n_layers=1, n_heads=4),
            4,
            quantize="int4",
        )


def test_tp_int8_decode_token_exact(rng):
    """--quant int8 composes with TP (VERDICT r03 item 5): the tp=2
    head-sharded int8 decode (permuted fused w_q column blocks, sharded
    scales, pre-divided row-parallel biases) generates the same greedy
    tokens as single-device int8 decode — both read the SAME quantized
    values, so any layout slip would show immediately."""
    from distributed_machine_learning_tpu.inference.generate import (
        generate,
        make_tp_generate_fn,
    )
    from distributed_machine_learning_tpu.models.transformer import (
        TransformerLM,
    )
    from distributed_machine_learning_tpu.ops.quant import quantize_lm_params
    from distributed_machine_learning_tpu.parallel.tensor_parallel import (
        tp_decode_params,
    )
    from distributed_machine_learning_tpu.runtime.mesh import make_mesh
    from distributed_machine_learning_tpu.train.lm_step import init_lm_state

    mesh = make_mesh(2, axis_names=("model",))
    prompt = jnp.asarray(rng.integers(0, 32, (2, 4)), jnp.int32)
    for n_kv in (None, 2):  # fused-qkv MHA and GQA layouts
        model = TransformerLM(
            vocab_size=32, d_model=32, n_layers=2, n_heads=4,
            n_kv_heads=n_kv,
        )
        params = init_lm_state(model).params
        qparams = quantize_lm_params(params)
        ref = generate(model, params, prompt, max_new_tokens=6,
                       quantize="int8")
        fn = make_tp_generate_fn(model, 6, mesh, quantize="int8")
        out = fn(tp_decode_params(qparams, 2), prompt,
                 jax.random.PRNGKey(0))
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_int8_matmul_pads_awkward_widths():
    """K with no 128-multiple divisor under the cap (e.g. 960 from a
    d_model=320 fused qkv) zero-pads to the next 128 multiple and
    slices back instead of raising (ADVICE r03)."""
    from distributed_machine_learning_tpu.ops.pallas.quant_matmul import (
        int8_matmul,
        quantize_int8,
    )

    rng = np.random.default_rng(11)
    w = jnp.asarray(rng.standard_normal((64, 960)), jnp.float32) * 0.05
    x = jnp.asarray(rng.standard_normal((8, 64)), jnp.float32)
    q, scale = quantize_int8(w)
    out = int8_matmul(x, q, scale)
    assert out.shape == (8, 960)
    ref = x.astype(jnp.bfloat16) @ (
        q.astype(jnp.bfloat16) * scale[None, :].astype(jnp.bfloat16)
    )
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=2e-2, atol=2e-2,
    )


def test_quantize_lm_params_rejects_misshaped_out_module():
    """The name-keyed two-axis flatten validates the kernel rank it
    assumes (ADVICE r03): a rank-2 kernel under a module named 'out'
    raises instead of silently mis-flattening."""
    from distributed_machine_learning_tpu.ops.quant import quantize_lm_params

    bad = {"blk": {"out": {"kernel": jnp.zeros((8, 4)),
                           "bias": jnp.zeros((4,))}}}
    with pytest.raises(ValueError, match="rank"):
        quantize_lm_params(bad)


def test_tp_decode_with_int8_kv_cache_token_exact(rng):
    """TP decode composes with the int8 KV cache: per-(head, slot)
    quantization is local to each device's cache shard, so the tp=2
    run matches single-device int8-KV decode token-for-token."""
    from distributed_machine_learning_tpu.inference.generate import (
        generate,
        make_tp_generate_fn,
    )
    from distributed_machine_learning_tpu.parallel.tensor_parallel import (
        tp_decode_params,
    )
    from distributed_machine_learning_tpu.runtime.mesh import make_mesh
    from distributed_machine_learning_tpu.train.lm_step import init_lm_state

    mesh = make_mesh(2, axis_names=("model",))
    model = TransformerLM(
        vocab_size=32, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        kv_cache_dtype=jnp.int8,
    )
    params = init_lm_state(model).params
    prompt = jnp.asarray(rng.integers(0, 32, (2, 4)), jnp.int32)
    ref = generate(model, params, prompt, max_new_tokens=6)
    fn = make_tp_generate_fn(model, 6, mesh)
    out = fn(tp_decode_params(params, 2), prompt, jax.random.PRNGKey(0))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_int8_tiered_dispatch_token_exact(rng):
    """The gated two-tier int8-cache dispatch
    (models/transformer.py::_INT8_TIERED_DISPATCH) must be semantics-
    neutral: same greedy stream as the default einsum-only dispatch,
    with the generation crossing the break-even so BOTH branches run.

    Exact token equality is a property of THIS suite's platform (CPU,
    interpret-mode kernel, f32 softmax in both paths); the kernel-vs-
    einsum ulp differences that could flip a near-tied argmax on other
    backends are the same shape-dependent ties the speculative
    docstring documents — if this ever flakes off-CPU, compare
    prefix-agreement rates instead of pinning bitwise."""
    import distributed_machine_learning_tpu.models.transformer as tmod
    from distributed_machine_learning_tpu.train.lm_step import init_lm_state

    model = TransformerLM(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        kv_cache_dtype=jnp.int8,
    )
    params = init_lm_state(model).params
    prompt = jnp.asarray(rng.integers(0, 64, (1, 6)), jnp.int32)
    # 320 new tokens in a 512-slot cache: pos/S runs 0..0.64, crossing
    # the 0.36 break-even — the lax.cond takes the kernel branch early
    # and the einsum branch late.
    ref = make_generate_fn(model, 320)(params, prompt, jax.random.PRNGKey(0))
    tmod._INT8_TIERED_DISPATCH = True
    try:
        out = make_generate_fn(model, 320)(
            params, prompt, jax.random.PRNGKey(0)
        )
    finally:
        tmod._INT8_TIERED_DISPATCH = False
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
