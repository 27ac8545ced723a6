"""Autoregressive generation with a KV cache.

The reference has no inference path at all (its ``test_model`` is
classification eval — ``part1/main.py:62-77``); this module is the LM
serving half this framework adds: prefill the prompt once, then decode
one token per step against per-layer K/V caches
(``models/transformer.py`` ``decode=True``), the whole loop a single
jitted program (`lax.scan`) — no per-token Python dispatch, which
would cost more than a µs-scale decode step.

TPU notes: the decode step is memory-bound (matvec against the cache),
so the cache stays in the model's compute dtype (bf16 halves HBM
traffic); sampling math is fp32.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax


def warp_logits(logits, temperature: float, top_k: int | None,
                top_p: float | None):
    """The sampling warper chain, HF-warper order: temperature FIRST,
    then ``top_k``, then ``top_p`` (nucleus sampling, Holtzman et al.:
    the smallest token set whose TEMPERED probability mass ≥ p) over
    the survivors.  Returns f32 logits with masked entries at -inf.
    The ONE warper shared by ``_sample`` and the speculative decoder
    (``inference/speculative.py``) — guards and semantics cannot drift.
    ``temperature`` must be > 0 (greedy has its own exact path)."""
    logits = logits.astype(jnp.float32) / temperature
    if top_k is not None:
        if top_k > logits.shape[-1]:
            raise ValueError(
                f"top_k={top_k} exceeds the vocabulary size "
                f"{logits.shape[-1]}"
            )
        # lax.top_k is O(V·k) vs a full O(V log V) sort — this runs once
        # per decoded token inside the scan, so it matters at real vocabs.
        kth = lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p is not None:
        if not 0.0 < top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")
        # Nucleus: sort descending, keep the prefix whose cumulative
        # probability is < p PLUS the first token crossing it (so the
        # kept mass is >= p and at least one token always survives).
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        keep_sorted = cum - probs < top_p  # prefix + the crossing token
        # Threshold logit = smallest kept logit per row; mask below it.
        thresh = jnp.min(
            jnp.where(keep_sorted, sorted_logits, jnp.inf), axis=-1,
            keepdims=True,
        )
        logits = jnp.where(logits < thresh, -jnp.inf, logits)
    return logits


def _sample(logits, rng, temperature: float, top_k: int | None,
            top_p: float | None = None):
    """One sampling decision per batch row.  [B, V] fp32 → [B] int32.
    Greedy (``temperature=0``) returns before any masking — argmax is
    invariant to it, and the nucleus sort is O(V log V) per decoded
    token inside the scan."""
    if temperature == 0.0:  # greedy (static: part of the compiled program)
        return jnp.argmax(
            logits.astype(jnp.float32), axis=-1
        ).astype(jnp.int32)
    return jax.random.categorical(
        rng, warp_logits(logits, temperature, top_k, top_p), axis=-1
    ).astype(jnp.int32)


def make_generate_fn(
    model,
    max_new_tokens: int,
    temperature: float = 0.0,
    top_k: int | None = None,
    quantize: str | None = None,
    top_p: float | None = None,
    eos_id: int | None = None,
):
    """Build a jitted ``fn(params, prompt, rng) -> tokens``.

    ``prompt``: [B, Lp] int32; returns [B, Lp + max_new_tokens] with the
    prompt preserved as a prefix.  ``temperature=0`` is greedy decoding
    (``rng`` unused); ``top_k`` restricts sampling to the k highest
    logits.  The model is cloned to dense cached attention — parameters
    from any training-time ``attn_impl`` (ring/ulysses/flash share the
    exact same parameter structure) drop in unchanged.

    ``quantize="int8"`` serves weight-only int8: pass params already
    converted by ``ops.quant.quantize_lm_params`` (the ``generate``
    wrapper converts for you) — decode is weight-bandwidth-bound, so
    halving the weight bytes is ~the step-time divisor.

    ``eos_id`` (ISSUE 19): with an EOS token set, decode runs as a
    ``lax.while_loop`` that exits as soon as EVERY row has emitted
    ``eos_id`` — a short batch stops paying ``max_new_tokens`` steps.
    Rows that finish early emit ``eos_id`` for their remaining slots
    (the output shape stays static), and their pre-EOS tokens are
    token-for-token identical to the ``eos_id=None`` run — asserted in
    ``tests/test_serving.py``.  ``eos_id=None`` keeps the original
    fixed-length ``lax.scan`` program bit-for-bit.
    """
    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
    if quantize not in (None, "int8"):
        raise ValueError(f"quantize must be None or 'int8', got {quantize!r}")
    dm = model.clone(attn_impl="dense", decode=True, weight_quant=quantize)
    sample = partial(_sample, temperature=temperature, top_k=top_k,
                     top_p=top_p)
    return jax.jit(partial(_generate_body, dm, sample, max_new_tokens,
                           eos_id))


def _generate_body(dm, sample, max_new_tokens, eos_id, params, prompt, rng):
    """The traced generate program (prefill + decode scan) — shared by
    the single-device jit (:func:`make_generate_fn`) and the manual-TP
    shard_map wrap (:func:`make_tp_generate_fn`), so the two paths can
    never drift."""
    B, Lp = prompt.shape
    max_len = Lp + max_new_tokens
    # Cache layout via eval_shape (no FLOPs): init in decode mode
    # with a [B, cache_len] input sizing every layer's K/V cache.
    # The allocation rounds up to a 512 multiple so the cache tiles
    # into the flash-decode kernel's S blocks
    # (ops/pallas/decode_attention.py) — the frontier-clamped DMA
    # never reads the pad slots, so the only cost is their
    # allocation.
    cache_len = -(-max_len // 512) * 512
    shapes = jax.eval_shape(
        lambda: dm.init(
            jax.random.PRNGKey(0),
            jnp.zeros((B, cache_len), jnp.int32),
            train=False,
        )
    )["cache"]
    cache = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), shapes
    )

    # Prefill: one pass over the whole prompt fills slots [0, Lp).
    logits, vars_ = dm.apply(
        {"params": params, "cache": cache}, prompt, train=False,
        mutable=["cache"],
    )
    rng, r = jax.random.split(rng)
    tok = sample(logits[:, -1], r)  # first generated token

    if eos_id is None:
        def body(carry, _):
            cache, tok, rng = carry
            logits, vars_ = dm.apply(
                {"params": params, "cache": cache}, tok[:, None],
                train=False, mutable=["cache"],
            )
            rng, r = jax.random.split(rng)
            nxt = sample(logits[:, -1], r)
            return (vars_["cache"], nxt, rng), tok

        (_, last, _), toks = lax.scan(
            body, (vars_["cache"], tok, rng), None,
            length=max_new_tokens - 1,
        )
        # toks: [max_new-1, B] tokens 1..max_new-1; `last` is the final.
        gen = jnp.concatenate([toks, last[None]], axis=0).swapaxes(0, 1)
        return jnp.concatenate([prompt, gen], axis=1)

    # EOS early-exit (ISSUE 19): a while_loop that stops the moment
    # every row has finished.  Finished rows keep riding the batch
    # (the program stays batch-static; their cache writes are masked
    # into irrelevance by forcing their tokens to eos), but once ALL
    # rows are done the remaining decode steps are never issued —
    # that is the "finished sequences stop consuming decode steps"
    # fix for the batch-static serving path.
    eos = jnp.int32(eos_id)
    done = tok == eos
    buf = jnp.full((B, max_new_tokens), eos, jnp.int32)
    buf = buf.at[:, 0].set(tok)

    def cond(carry):
        _, _, _, _, done, i = carry
        return jnp.logical_and(i < max_new_tokens,
                               jnp.logical_not(jnp.all(done)))

    def body(carry):
        cache, tok, rng, buf, done, i = carry
        logits, vars_ = dm.apply(
            {"params": params, "cache": cache}, tok[:, None],
            train=False, mutable=["cache"],
        )
        rng, r = jax.random.split(rng)
        nxt = sample(logits[:, -1], r)
        nxt = jnp.where(done, eos, nxt)
        done = jnp.logical_or(done, nxt == eos)
        buf = buf.at[:, i].set(nxt)
        return (vars_["cache"], nxt, rng, buf, done, i + 1)

    _, _, _, buf, _, _ = lax.while_loop(
        cond, body,
        (vars_["cache"], tok, rng, buf, done, jnp.int32(1)),
    )
    return jnp.concatenate([prompt, buf], axis=1)


def tp_local_decode_clone(model, mesh, model_axis: str,
                          quantize: str | None):
    """Validate the Megatron decode layout's divisibility rules and
    clone ``model`` at its LOCAL width (heads, KV heads, d_ff ÷ tp;
    head_dim pinned global; ``tp_axis`` set so the model's psums
    complete each row-parallel projection).  The ONE place those rules
    live — shared by :func:`make_tp_generate_fn` and the speculative TP
    wrapper (``inference/speculative.py``), so the two cannot drift."""
    if quantize not in (None, "int8"):
        raise ValueError(f"quantize must be None or 'int8', got {quantize!r}")
    if model_axis not in mesh.axis_names:
        raise ValueError(
            f"mesh is missing axis {model_axis!r}: {mesh.axis_names}"
        )
    tp = mesh.shape[model_axis]
    if model.n_heads % tp:
        raise ValueError(
            f"n_heads={model.n_heads} must be divisible by tp={tp}"
        )
    n_kv = model.n_kv_heads
    if n_kv is not None and n_kv % tp:
        raise ValueError(
            f"n_kv_heads={n_kv} must be divisible by tp={tp}"
        )
    d_ff = model.d_ff or 4 * model.d_model
    if d_ff % tp:
        raise ValueError(f"d_ff={d_ff} must be divisible by tp={tp}")
    return model.clone(
        n_heads=model.n_heads // tp,
        n_kv_heads=None if n_kv is None else n_kv // tp,
        d_ff=d_ff // tp,
        # Global per-head width (honoring an explicit override).
        head_dim=model.head_dim or model.d_model // model.n_heads,
        attn_impl="dense", decode=True, weight_quant=quantize,
        tp_axis=model_axis,
    )


def tp_param_specs(params, model_axis: str):
    """The TP decode in_specs tree for params arranged by
    ``tp_decode_params`` — one leaf-path → PartitionSpec mapping for
    every TP decode factory."""
    from distributed_machine_learning_tpu.parallel.tensor_parallel import (
        tp_decode_spec_for,
    )

    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: tp_decode_spec_for(
            tuple(k.key if hasattr(k, "key") else str(k) for k in path),
            leaf.ndim if hasattr(leaf, "ndim") else 0,
            model_axis,
        ),
        params,
    )


def make_tp_generate_fn(
    model,
    max_new_tokens: int,
    mesh,
    temperature: float = 0.0,
    top_k: int | None = None,
    quantize: str | None = None,
    model_axis: str = "model",
    top_p: float | None = None,
    eos_id: int | None = None,
):
    """Tensor-parallel generation: ``fn(params, prompt, rng) -> tokens``.

    The Megatron decode layout, written as a fully-manual shard_map over
    ``model_axis``: each device runs the SAME generate program as the
    single-device path (:func:`_generate_body`) on a model clone at its
    LOCAL width (``n_heads=H/tp``, ``n_kv_heads=Hkv/tp``,
    ``d_ff=F/tp``), with the model's ``tp_axis`` psums completing the
    row-parallel out-projection and fc_out (``models/transformer.py``).
    Every Pallas kernel on the path — flash prefill, the decode cache
    kernel, the int8 weight-reading matmul — sees purely local shapes
    and never meets the GSPMD partitioner: this is how ``--quant int8``
    composes with TP.  The KV cache is born head-sharded (each device's
    cache holds its Hkv/tp heads — the cache memory ÷ tp).

    ``params`` must be pre-arranged by
    ``parallel.tensor_parallel.tp_decode_params`` (row-parallel biases
    ÷ tp; fused quantized column blocks re-ordered head-contiguous);
    pass them global — the shard_map in_specs slice each device's
    shard.  Sampling runs replicated (same rng, same logits on every
    device), so the returned tokens are identical across devices.
    """
    from jax.sharding import PartitionSpec as P

    from distributed_machine_learning_tpu.runtime.mesh import (
        shard_map_no_check,
    )

    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
    local = tp_local_decode_clone(model, mesh, model_axis, quantize)
    sample = partial(_sample, temperature=temperature, top_k=top_k,
                     top_p=top_p)
    body = partial(_generate_body, local, sample, max_new_tokens, eos_id)

    jitted: dict = {}

    def run(params, prompt, rng):
        key = jax.tree_util.tree_structure(params)
        fn = jitted.get(key)
        if fn is None:
            fn = jitted[key] = jax.jit(shard_map_no_check(
                body,
                mesh=mesh,
                in_specs=(tp_param_specs(params, model_axis), P(), P()),
                out_specs=P(),
            ))
        return fn(params, prompt, rng)

    return run


def generate(
    model,
    params,
    prompt,
    max_new_tokens: int,
    temperature: float = 0.0,
    top_k: int | None = None,
    rng=None,
    quantize: str | None = None,
    top_p: float | None = None,
    eos_id: int | None = None,
):
    """One-shot convenience wrapper around :func:`make_generate_fn`.

    For repeated generation at fixed shapes, build the fn once instead —
    this wrapper retraces on every call.  ``quantize="int8"`` converts
    the (full-precision) params with ``quantize_lm_params`` here.
    """
    fn = make_generate_fn(model, max_new_tokens, temperature, top_k,
                          quantize=quantize, top_p=top_p, eos_id=eos_id)
    if quantize == "int8":
        from distributed_machine_learning_tpu.ops.quant import (
            quantize_lm_params,
        )

        params = quantize_lm_params(params)
    if rng is None:
        rng = jax.random.PRNGKey(0)
    return fn(params, jnp.asarray(prompt, jnp.int32), rng)


def make_serving_step(
    model,
    params,
    max_new_tokens: int,
    temperature: float = 0.0,
    top_k: int | None = None,
    quantize: str | None = None,
    top_p: float | None = None,
    rng=None,
    eos_id: int | None = None,
):
    """The step-callable seam for the serving fleet (ISSUE 16): wrap
    the batch-static decode program as ``step(prompts) -> outputs``
    over plain python token lists — the signature
    ``runtime/serving_worker.py`` drives, so the worker serves requests
    without forking this module.

    Ragged micro-batches are grouped by prompt length and each group
    runs as one batched call (the program stays batch-static; expect
    one jit cache entry per distinct ``(batch, length)`` shape — a
    router with a fixed ``micro_batch`` converges on a handful).  The
    RNG threads through calls so repeated sampling steps never reuse a
    key.

    ``eos_id`` fixes the semantics drift this path had vs
    ``generate``: without it every group decodes ``max_new_tokens``
    unconditionally; with it a group's while_loop exits once all its
    rows emit EOS and finished rows pad with ``eos_id`` (see
    :func:`make_generate_fn`).  The group-level exit is the
    batch-static ceiling — per-sequence retirement is what the
    continuous engine (``inference/continuous.py``) adds.
    """
    fn = make_generate_fn(model, max_new_tokens, temperature, top_k,
                          quantize=quantize, top_p=top_p, eos_id=eos_id)
    if quantize == "int8":
        from distributed_machine_learning_tpu.ops.quant import (
            quantize_lm_params,
        )

        params = quantize_lm_params(params)
    state = {"rng": rng if rng is not None else jax.random.PRNGKey(0)}

    def step(prompts):
        if any(len(p) == 0 for p in prompts):
            raise ValueError("serving step got an empty prompt")
        outs: list = [None] * len(prompts)
        by_len: dict[int, list[int]] = {}
        for i, p in enumerate(prompts):
            by_len.setdefault(len(p), []).append(i)
        for length in sorted(by_len):
            idxs = by_len[length]
            batch = jnp.asarray([list(map(int, prompts[i]))
                                 for i in idxs], jnp.int32)
            state["rng"], call_rng = jax.random.split(state["rng"])
            tokens = jax.device_get(fn(params, batch, call_rng))
            for row, i in zip(tokens.tolist(), idxs):
                outs[i] = [int(t) for t in row]
        return outs

    return step
