"""Speculative decoding — draft-and-verify autoregressive generation.

Decode is bound by HBM reads of the target model's weights per token;
speculative decoding (Leviathan et al.) buys tokens per
weight-read: a cheap DRAFT model proposes ``gamma`` tokens
autoregressively, the TARGET verifies all of them in ONE forward pass
(γ+1 positions against its cache — compute-parallel, the same weight
bytes as a single decode step), and a rejection rule keeps the output
distribution EXACTLY the target's:

- greedy (``temperature=0``): accept the longest prefix where the
  draft's token equals the target argmax, then emit the target argmax
  at the first mismatch (or the bonus token when all γ survive) — the
  output is bitwise the target-only greedy stream under matched
  numerics (f32 compute, as the tests pin it).  bf16-serving caveat,
  measured not hypothesized: where the top-2 logits tie within one
  bf16 ulp, DIFFERENTLY-SHAPED programs break the tie differently —
  the Lq=γ+1 verify pass vs the Lq=1 decode step, but equally the
  Lq=1 decode step vs the teacher-forced full forward (at the first
  observed flip on a trained bf16 model, the teacher-forced argmax
  matched NEITHER stream; top-2 gap exactly one bf16 ulp).  Ties are
  equal-probability choices, so the served distribution is unchanged;
  this is a property of shape-dependent XLA numerics, not of
  speculation;
- sampled: accept ``d_i`` with probability ``min(1, p_i(d_i)/q_i(d_i))``
  (p = target, q = draft, both WARPED — temperature/top-k/top-p — so
  the preserved distribution is the one the plain sampler uses); on
  rejection sample from ``norm(max(p_i − q_i, 0))``; on full acceptance
  sample the bonus from ``p_γ``.  The tests pin this branch against a
  NumPy oracle of the rule and check the served empirical distribution
  against plain sampling (tests/test_speculative.py).

TPU-shaped implementation notes:

- **Cache rollback is free.**  The KV caches index slots by absolute
  position with an ``idx`` frontier counter; slots past the frontier
  are causally masked (``slot <= pos``) and overwritten by the next
  write.  Rejecting draft tokens is therefore just rewinding the
  counter in the carried cache pytree — no K/V copy, no re-prefill.
- The draft phase runs γ+1 steps (it processes its own last proposal),
  keeping its cache exactly one token behind the committed stream at
  every round — the invariant that makes the loop shape-static.
- One ``lax.while_loop`` emits a variable 1..γ+1 tokens per round into
  a fixed output buffer at a moving pointer; every slot below the final
  pointer is committed before it can be read.
- **Batched** (B > 1): acceptance length is data-dependent PER ROW, so
  the models are cloned with ``decode_batched_frontier=True`` — the
  cache frontier becomes a [B] counter, positions/RoPE/masks go
  per-row (``models/transformer.py``), and every round each row
  rewinds by its own rejection count.  Rows that reach
  ``max_new_tokens`` freeze (their frontier, pointer, and last token
  stop advancing) and keep verifying dead tokens until the slowest
  row finishes — the standard batched-speculation shape; per-row
  output is token-exact vs the row served alone (tested at batch 8).
  Batch 1 keeps the scalar frontier (and its measured perf numbers).

The reference has no inference path at all (SURVEY.md §2); this extends
the serving surface of ``inference/generate.py``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from distributed_machine_learning_tpu.inference.generate import warp_logits


def sampled_acceptance(d, q, p, u):
    """The Leviathan accept/reject-residual rule, vectorized per row —
    the exact math the sampled branch runs, factored out so the tests
    can pin it against a NumPy oracle (tests/test_speculative.py).

    ``d``: [B, γ] draft proposals; ``q``: [B, γ, V] draft probabilities
    and ``p``: [B, γ+1, V] target probabilities (both already WARPED —
    the preserved distribution is the warped one); ``u``: [B, γ]
    uniforms.  Returns ``(n_acc, resid)``: ``n_acc[b]`` = length of row
    b's accepted prefix (accept d_i iff u_i·q_i(d_i) < p_i(d_i), i.e.
    u_i < p/q), and ``resid[b]`` = the [V] distribution the correction
    token samples from — ``norm(max(p_i − q_i, 0))`` at the first
    rejection i, or the bonus row ``p_γ`` on full acceptance (q_row is
    zeroed there, so the residual IS p_γ).  Emitting ``d_{<n_acc}``
    then one draw from ``resid`` makes each committed token exactly
    target-distributed (Leviathan et al., Theorem 1)."""
    gamma = d.shape[1]
    p_d = jnp.take_along_axis(p[:, :gamma], d[..., None], axis=2)[..., 0]
    q_d = jnp.take_along_axis(q, d[..., None], axis=2)[..., 0]
    acc = u * q_d < p_d  # accept iff u < p/q (q>0 where sampled)
    n_acc = jnp.sum(jnp.cumprod(acc.astype(jnp.int32), axis=1), axis=1)
    # Residual at the first rejection; bonus row at γ.
    p_row = jnp.take_along_axis(p, n_acc[:, None, None], axis=1)[:, 0]
    q_row = jnp.where(
        (n_acc < gamma)[:, None],
        jnp.take_along_axis(
            q, jnp.minimum(n_acc, gamma - 1)[:, None, None], axis=1
        )[:, 0],
        jnp.zeros_like(p_row),
    )
    resid = jnp.maximum(p_row - q_row, 0.0)
    resid = resid / jnp.maximum(resid.sum(axis=-1, keepdims=True), 1e-30)
    return n_acc, resid


def _validate_speculative_args(target_model, draft_model,
                               max_new_tokens: int, gamma: int,
                               quantize, draft_quantize) -> None:
    """The speculative factories' shared contract — one copy, so the
    single-device and TP entry points cannot drift."""
    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
    if gamma < 1:
        raise ValueError(f"gamma must be >= 1, got {gamma}")
    if target_model.vocab_size != draft_model.vocab_size:
        raise ValueError(
            f"target and draft must share a vocabulary (got "
            f"{target_model.vocab_size} vs {draft_model.vocab_size})"
        )
    for name, q in (("quantize", quantize),
                    ("draft_quantize", draft_quantize)):
        if q not in (None, "int8"):
            raise ValueError(f"{name} must be None or 'int8', got {q!r}")


def make_speculative_generate_fn(
    target_model,
    draft_model,
    max_new_tokens: int,
    gamma: int = 4,
    temperature: float = 0.0,
    top_k: int | None = None,
    top_p: float | None = None,
    quantize: str | None = None,
    draft_quantize: str | None = None,
):
    """Build ``fn(target_params, draft_params, prompt, rng) -> tokens``.

    ``prompt``: [B, Lp] int32 (any batch; rows share the prompt length
    but not content — each decodes its own stream); returns
    [B, Lp + max_new_tokens].  ``gamma``: draft tokens per verify round.
    ``quantize``/``draft_quantize``: "int8" serves that model through
    the weight-only kernel (``ops/quant.py``) — pass params converted by
    ``quantize_lm_params``.

    Correctness contract: each row's emitted stream follows the TARGET's
    sampling distribution exactly (greedy: bitwise-identical to
    ``make_generate_fn`` with the same flags — tested, per row at batch
    8); the draft only changes HOW FAST tokens appear, never WHICH
    distribution they come from.
    """
    _validate_speculative_args(target_model, draft_model, max_new_tokens,
                               gamma, quantize, draft_quantize)
    tm = target_model.clone(attn_impl="dense", decode=True,
                            weight_quant=quantize)
    dm = draft_model.clone(attn_impl="dense", decode=True,
                           weight_quant=draft_quantize)
    from functools import partial

    return jax.jit(partial(
        _speculative_body, tm, dm, max_new_tokens, gamma, temperature,
        top_k, top_p,
    ))


def _speculative_body(tm, dm, max_new_tokens, gamma, temperature, top_k,
                      top_p, tparams, dparams, prompt, rng):
    """The traced speculative program (prefill + draft/verify rounds) —
    shared by the single-device jit (:func:`make_speculative_generate_fn`)
    and the manual-TP shard_map wrap (:func:`make_tp_speculative_generate_fn`),
    so the two paths can never drift.  ``tm``/``dm`` are decode-mode
    clones (the TP path passes a LOCAL-width target whose ``tp_axis``
    psums complete each projection)."""
    greedy = temperature == 0.0
    V = tm.vocab_size

    def warp(logits):
        return warp_logits(logits, temperature, top_k, top_p)

    B, Lp = prompt.shape
    # Batch 1 keeps the scalar cache frontier (the measured-perf
    # latency path); B > 1 switches the models to per-row frontiers.
    batched = B > 1
    tm_b = tm.clone(decode_batched_frontier=batched)
    dm_b = dm.clone(decode_batched_frontier=batched)
    # The verify pass applies γ+1 tokens MID-STREAM: it must attend
    # the full cache, not take the start-0 prefill fast path — the
    # continuation clone routes multi-token decode through
    # _cached_attention (same params, same cache layout).
    tm_verify = tm_b.clone(decode_continuation=True)
    # Output slack: an ACTIVE row's pointer tops out at
    # max_new−1 + (γ+1); a FROZEN row's window writes span γ+1 more
    # slots — 2(γ+1) covers both without DUS clamping ever shifting
    # a write into committed slots.  Batch 1 never freezes, so it
    # keeps the tighter γ+1 slack (the extra slots could bump
    # cache_len across a 512 tile and tax every einsum read).
    budget = max_new_tokens + (gamma + 1) * (2 if batched else 1)
    cache_len = -(-(Lp + budget + 1) // 512) * 512

    def init_cache(model):
        shapes = jax.eval_shape(
            lambda: model.init(
                jax.random.PRNGKey(0),
                jnp.zeros((B, cache_len), jnp.int32),
                train=False,
            )
        )["cache"]
        return jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), shapes
        )

    tcache, dcache = init_cache(tm_b), init_cache(dm_b)

    # Prefill both models on the prompt; the target's last logits
    # sample the first committed token.
    tlogits, tvars = tm_b.apply(
        {"params": tparams, "cache": tcache}, prompt, train=False,
        mutable=["cache"],
    )
    _, dvars = dm_b.apply(
        {"params": dparams, "cache": dcache}, prompt, train=False,
        mutable=["cache"],
    )
    tcache, dcache = tvars["cache"], dvars["cache"]
    rng, r0 = jax.random.split(rng)
    if greedy:
        cur = jnp.argmax(tlogits[:, -1], axis=-1).astype(jnp.int32)
    else:
        cur = jax.random.categorical(
            r0, warp(tlogits[:, -1]), axis=-1
        ).astype(jnp.int32)

    out = jnp.zeros((B, budget), jnp.int32)
    out = lax.dynamic_update_slice(out, cur[:, None], (0, 0))
    # ptr[b]: tokens EMITTED by row b so far (cur at slot 0 counts).
    ptr = jnp.ones((B,), jnp.int32)
    state = (tcache, dcache, cur, out, ptr, rng)

    def round_body(state):
        tcache, dcache, cur, out, ptr, rng = state
        # Frozen rows (only possible when batched): done decoding,
        # still riding the loop until the slowest row finishes.
        done = ptr >= max_new_tokens  # [B]

        # ---- draft phase: γ+1 steps (the last processes its own
        # final proposal, keeping the draft cache one token behind
        # the committed stream after any acceptance count).
        def dstep(carry, r):
            dcache, tok = carry
            logits, vars_ = dm_b.apply(
                {"params": dparams, "cache": dcache}, tok[:, None],
                train=False, mutable=["cache"],
            )
            lg = logits[:, -1]
            if greedy:
                nxt = jnp.argmax(lg, axis=-1).astype(jnp.int32)
                q = jnp.zeros((B, V), jnp.float32)  # unused
            else:
                w = warp(lg)  # one warp per step: probs AND sample
                q = jax.nn.softmax(w, axis=-1)
                nxt = jax.random.categorical(r, w, axis=-1).astype(
                    jnp.int32
                )
            return (vars_["cache"], nxt), (nxt, q)

        rng, *draft_keys = jax.random.split(rng, gamma + 2)
        (dcache2, _), (draft_toks, draft_q) = lax.scan(
            dstep, (dcache, cur), jnp.stack(draft_keys)
        )
        # draft_toks: [γ+1, B]; proposals are the first γ.
        d = draft_toks[:gamma].swapaxes(0, 1)  # [B, γ] int32
        q = draft_q[:gamma].swapaxes(0, 1)  # [B, γ, V]

        # ---- verify: one target pass over [cur, d_0..d_{γ-1}].
        verify_in = jnp.concatenate([cur[:, None], d], axis=1)
        vlogits, tvars = tm_verify.apply(
            {"params": tparams, "cache": tcache}, verify_in,
            train=False, mutable=["cache"],
        )  # [B, γ+1, V]; row (b, i) predicts the slot of d_i.

        rng, r_acc, r_fix = jax.random.split(rng, 3)
        if greedy:
            tbest = jnp.argmax(vlogits, axis=-1).astype(jnp.int32)
            acc = d == tbest[:, :gamma]  # [B, γ]
            # n_acc[b] = length of row b's all-accepted prefix.
            n_acc = jnp.sum(
                jnp.cumprod(acc.astype(jnp.int32), axis=1), axis=1
            )
            # Correction/bonus token: target argmax at slot n_acc.
            t_new = jnp.take_along_axis(
                tbest, n_acc[:, None], axis=1
            )[:, 0]
        else:
            p = jax.nn.softmax(warp(vlogits), axis=-1)  # [B, γ+1, V]
            u = jax.random.uniform(r_acc, (B, gamma))
            n_acc, resid = sampled_acceptance(d, q, p, u)
            t_new = jax.random.categorical(
                r_fix, jnp.log(jnp.maximum(resid, 1e-30)), axis=-1
            ).astype(jnp.int32)

        # Tokens row b commits this round (frozen rows commit none).
        adv = jnp.where(done, 0, n_acc + 1)  # [B]

        # ---- commit: window = [d_0..d_{n_acc-1}, t_new, junk...];
        # the junk beyond n_acc is overwritten by the next round's
        # window (or never read past the final pointer); frozen
        # rows' windows land entirely past max_new_tokens.
        window = jnp.where(
            jnp.arange(gamma + 1)[None] == n_acc[:, None],
            t_new[:, None],
            jnp.concatenate([d, jnp.zeros((B, 1), jnp.int32)], axis=1),
        )
        out = jax.vmap(
            lambda o, w, p0: lax.dynamic_update_slice(o, w, (p0,))
        )(out, window, ptr)

        # ---- cache rewinds (the free rollback): target holds the
        # committed stream MINUS t_new; draft holds one token less.
        # Frozen rows rewind the full γ+1 — their frontier is pinned.
        delta = adv - (gamma + 1)  # [B], <= 0
        back = delta if batched else delta[0]
        tcache = dict(tvars["cache"])
        tcache["idx"] = tcache["idx"] + back
        dcache2 = dict(dcache2)
        dcache2["idx"] = dcache2["idx"] + back
        cur = jnp.where(done, cur, t_new)
        return (tcache, dcache2, cur, out, ptr + adv, rng)

    def cond(state):
        return jnp.any(state[4] < max_new_tokens)

    _, _, _, out, _, _ = lax.while_loop(cond, round_body, state)
    return jnp.concatenate([prompt, out[:, :max_new_tokens]], axis=1)



def make_tp_speculative_generate_fn(
    target_model,
    draft_model,
    max_new_tokens: int,
    mesh,
    gamma: int = 4,
    temperature: float = 0.0,
    top_k: int | None = None,
    top_p: float | None = None,
    quantize: str | None = None,
    draft_quantize: str | None = None,
    model_axis: str = "model",
):
    """Speculative decoding with a TENSOR-PARALLEL target: the whole
    draft/verify/accept program runs inside one shard_map over
    ``model_axis`` (the Megatron decode layout of
    ``inference/generate.py::make_tp_generate_fn``).

    The TARGET runs at its LOCAL width (heads, KV cache, and d_ff ÷ tp;
    ``tp_axis`` psums complete each row-parallel projection), so the
    expensive verify pass — the reason TP serves the model at all —
    is sharded exactly like plain TP decode.  The DRAFT is replicated:
    it exists to be small, so sharding it would trade its whole matmul
    for ICI latency γ times per round.  Acceptance, sampling, and the
    round loop run replicated on every device (same rng ⇒ same
    control flow ⇒ the emitted tokens are identical across devices).

    ``target_params`` must be pre-arranged by
    ``parallel.tensor_parallel.tp_decode_params``; draft params pass
    through whole.  Output is token-exact vs single-device speculative
    decoding (tested on the virtual mesh).
    """
    from jax.sharding import PartitionSpec as P

    from distributed_machine_learning_tpu.inference.generate import (
        tp_local_decode_clone,
        tp_param_specs,
    )
    from distributed_machine_learning_tpu.runtime.mesh import (
        shard_map_no_check,
    )

    _validate_speculative_args(target_model, draft_model, max_new_tokens,
                               gamma, quantize, draft_quantize)
    # Layout rules + local-width clone shared with make_tp_generate_fn
    # (inference/generate.py::tp_local_decode_clone).
    local_target = tp_local_decode_clone(
        target_model, mesh, model_axis, quantize
    )
    dm = draft_model.clone(attn_impl="dense", decode=True,
                           weight_quant=draft_quantize)
    from functools import partial

    body = partial(_speculative_body, local_target, dm, max_new_tokens,
                   gamma, temperature, top_k, top_p)

    jitted: dict = {}

    def run(tparams, dparams, prompt, rng):
        key = (jax.tree_util.tree_structure(tparams),
               jax.tree_util.tree_structure(dparams))
        fn = jitted.get(key)
        if fn is None:
            dspecs = jax.tree_util.tree_map(lambda _: P(), dparams)
            fn = jitted[key] = jax.jit(shard_map_no_check(
                body,
                mesh=mesh,
                in_specs=(tp_param_specs(tparams, model_axis), dspecs,
                          P(), P()),
                out_specs=P(),
            ))
        return fn(tparams, dparams, prompt, rng)

    return run
