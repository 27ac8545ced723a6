"""Text generation entrypoint — serve a checkpoint trained by ``cli.lm``.

The reference has no inference surface at all (SURVEY.md §2 — its
``test_model`` is classification eval); this CLI completes the LM
serving loop the framework adds: restore a ``cli.lm --ckpt-dir``
checkpoint, encode the prompt with the same byte-level scheme the
trainer's ``--data-dir`` corpora use (``data/text.py``: vocab 256 bytes
+ BOS), and run the KV-cached jitted generate loop
(``inference/generate.py`` — flash prefill, GQA-native narrow-cache
decode).

Usage::

    python -m distributed_machine_learning_tpu.cli.generate \
        --ckpt-dir runs/lm --prompt "The " --max-new-tokens 128 \
        --d-model 256 --n-layers 4 --n-heads 8   # match the training run

Model flags must match the training run (the checkpoint stores arrays,
not architecture).  Pipeline-layout checkpoints (``--parallel pp/3d``)
are detected by their stacked ``blocks`` tree and unstacked
automatically.  ``--random-init`` serves an untrained model (demo /
smoke path — no checkpoint needed).
"""

from __future__ import annotations

import argparse

import numpy as np


def make_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--ckpt-dir", default=None,
                   help="directory written by cli.lm --ckpt-dir")
    p.add_argument("--random-init", action="store_true",
                   help="serve freshly initialized weights (no checkpoint)")
    p.add_argument("--prompt", default="The ")
    p.add_argument("--max-new-tokens", dest="max_new_tokens", default=128,
                   type=int)
    p.add_argument("--temperature", default=1.0, type=float,
                   help="0 = greedy decoding")
    p.add_argument("--top-k", dest="top_k", default=None, type=int)
    p.add_argument("--top-p", dest="top_p", default=None, type=float,
                   help="nucleus sampling: keep the smallest token set "
                        "whose TEMPERED cumulative probability >= p "
                        "(HF warper order: temperature, then top-k, "
                        "then top-p)")
    p.add_argument("--seed", default=0, type=int)
    # Architecture flags — must match the training run.
    p.add_argument("--d-model", dest="d_model", default=256, type=int)
    p.add_argument("--n-layers", dest="n_layers", default=4, type=int)
    p.add_argument("--n-heads", dest="n_heads", default=8, type=int)
    p.add_argument("--n-kv-heads", dest="n_kv_heads", default=None, type=int)
    p.add_argument("--moe", action="store_true",
                   help="serve a Switch-MoE checkpoint (cli.lm --parallel "
                        "ep): per-token routing runs inside the cached "
                        "decode loop; pair with --n-experts etc.")
    p.add_argument("--n-experts", dest="n_experts", default=8, type=int)
    p.add_argument("--capacity-factor", dest="capacity_factor",
                   default=1.25, type=float)
    p.add_argument("--moe-impl", dest="moe_impl", default="einsum",
                   choices=["einsum", "grouped"])
    p.add_argument("--vocab", default=None, type=int,
                   help="default: byte-level 257 (data/text.py)")
    p.add_argument("--compute-dtype", default="bfloat16",
                   choices=["float32", "bfloat16"])
    p.add_argument("--kv-cache-dtype", dest="kv_cache_dtype", default=None,
                   help="decode cache storage dtype (default: compute "
                        "dtype)")
    p.add_argument("--quant", default=None, choices=["int8"],
                   help="weight-only quantized serving: projections read "
                        "int8 weights through the Pallas kernel "
                        "(ops/quant.py) — decode is weight-bandwidth-"
                        "bound")
    p.add_argument("--tp", default=1, type=int,
                   help="tensor-parallel decode over this many devices "
                        "(manual Megatron shard_map — heads, d_ff, and "
                        "the KV cache sharded; composes with --quant "
                        "int8: inference/generate.py::make_tp_generate_fn)")
    # Speculative decoding (inference/speculative.py): a cheap draft
    # model proposes --spec-gamma tokens per target verify pass; output
    # distribution is EXACTLY the target's (greedy: bitwise-identical).
    p.add_argument("--spec-gamma", dest="spec_gamma", default=0, type=int,
                   help="enable speculative decoding with this many draft "
                        "tokens per verify round (0 = off); the draft "
                        "defaults to the target architecture at random "
                        "init unless --draft-* flags say otherwise; "
                        "composes with --quant, --moe, and --tp (the "
                        "target verifies sharded, the draft replicates)")
    p.add_argument("--draft-ckpt-dir", dest="draft_ckpt_dir", default=None,
                   help="cli.lm checkpoint for the draft model; absent "
                        "= random-init draft (output stays exact, "
                        "acceptance is just poor)")
    p.add_argument("--draft-d-model", dest="draft_d_model", default=None,
                   type=int, help="draft architecture (defaults mirror "
                                  "the target's flags)")
    p.add_argument("--draft-n-layers", dest="draft_n_layers", default=None,
                   type=int)
    p.add_argument("--draft-n-heads", dest="draft_n_heads", default=None,
                   type=int)
    p.add_argument("--draft-n-kv-heads", dest="draft_n_kv_heads",
                   default=None, type=int)
    return p


def _restore_lm_params(ckpt_dir: str, n_layers: int):
    """Restore a cli.lm checkpoint's params, unstacking pipeline-layout
    trees (contiguous or interleaved) into the per-layer form plain
    apply expects — the ONE restore path for target AND draft models."""
    from distributed_machine_learning_tpu.train.checkpoint import (
        checkpoint_layout,
        latest_checkpoint,
        restore_checkpoint,
    )

    latest = latest_checkpoint(ckpt_dir)
    if latest is None:
        raise FileNotFoundError(f"no complete checkpoint under {ckpt_dir}")
    params = restore_checkpoint(latest, files_verified=True).params
    if "blocks" in params:
        from distributed_machine_learning_tpu.parallel.pipeline_interleaved import (  # noqa: E501
            parse_interleaved_layout,
        )

        interleaved = parse_interleaved_layout(checkpoint_layout(latest))
        if interleaved is not None:
            from distributed_machine_learning_tpu.parallel.pipeline_interleaved import (  # noqa: E501
                unstack_interleaved,
            )

            p_saved, v_saved = interleaved
            params = unstack_interleaved(params, n_layers, p_saved, v_saved)
        else:
            from distributed_machine_learning_tpu.parallel.pipeline import (
                unstack_lm_params,
            )

            params = unstack_lm_params(params, n_layers)
    print(f"restored {latest}")
    return params


def main(argv=None) -> None:
    from distributed_machine_learning_tpu.runtime.compile_cache import (
        configure_compile_cache,
    )

    configure_compile_cache()
    args = make_parser().parse_args(argv)
    if not args.ckpt_dir and not args.random_init:
        raise ValueError("pass --ckpt-dir (a cli.lm checkpoint) or "
                         "--random-init")

    import jax
    import jax.numpy as jnp

    from distributed_machine_learning_tpu.data.text import BOS, VOCAB_SIZE
    from distributed_machine_learning_tpu.inference.generate import (
        make_generate_fn,
    )
    from distributed_machine_learning_tpu.models.transformer import (
        TransformerLM,
    )

    vocab = args.vocab or VOCAB_SIZE
    dtype = (jnp.bfloat16 if args.compute_dtype == "bfloat16"
             else jnp.float32)
    kv_dtype = (
        jnp.dtype(args.kv_cache_dtype) if args.kv_cache_dtype else None
    )
    if args.moe:
        from distributed_machine_learning_tpu.models.moe import (
            MoETransformerLM,
        )

        model = MoETransformerLM(
            vocab_size=vocab, d_model=args.d_model,
            n_layers=args.n_layers, n_heads=args.n_heads,
            n_kv_heads=args.n_kv_heads, n_experts=args.n_experts,
            capacity_factor=args.capacity_factor, moe_impl=args.moe_impl,
            compute_dtype=dtype, kv_cache_dtype=kv_dtype,
        )
    else:
        model = TransformerLM(
            vocab_size=vocab,
            d_model=args.d_model,
            n_layers=args.n_layers,
            n_heads=args.n_heads,
            n_kv_heads=args.n_kv_heads,
            compute_dtype=dtype,
            kv_cache_dtype=kv_dtype,
        )

    if args.ckpt_dir:
        params = _restore_lm_params(args.ckpt_dir, args.n_layers)
    else:
        from distributed_machine_learning_tpu.train.lm_step import (
            init_lm_state,
        )

        params = init_lm_state(model).params
        print("WARNING: --random-init weights (untrained output)")
    # Serving configuration: quantize (from the fp32 master params) or
    # cast to the compute dtype (decode is bound by HBM weight reads).
    if args.quant == "int8":
        from distributed_machine_learning_tpu.ops.quant import (
            quantize_lm_params,
        )

        params = quantize_lm_params(params)
    else:
        params = jax.tree_util.tree_map(
            lambda p: p.astype(dtype) if p.dtype == jnp.float32 else p,
            params,
        )

    # Byte-level prompt encoding, BOS-prefixed like every corpus
    # document (data/text.py::load_corpus).
    prompt_bytes = args.prompt.encode("utf-8")
    if vocab == VOCAB_SIZE:
        toks = [BOS] + list(prompt_bytes)
    else:
        toks = [b % vocab for b in prompt_bytes] or [0]
    prompt = jnp.asarray(np.asarray(toks, np.int32)[None, :])

    # Shared TP setup (one copy for the speculative and plain branches):
    # device-count guard + the model-axis mesh.  The Megatron param
    # arrangement (tp_decode_params) runs AFTER the factory below — the
    # factories' divisibility validation (tp_local_decode_clone) must
    # fire before any reshape touches the arrays.
    mesh = None
    if args.tp > 1:
        from distributed_machine_learning_tpu.runtime.mesh import make_mesh

        if args.tp > jax.device_count():
            raise ValueError(
                f"--tp {args.tp} exceeds the device count "
                f"{jax.device_count()} (the mesh uses the first tp "
                "devices)"
            )
        mesh = make_mesh(args.tp, axis_names=("model",))

    if args.spec_gamma > 0:
        from distributed_machine_learning_tpu.inference.speculative import (
            make_speculative_generate_fn,
            make_tp_speculative_generate_fn,
        )

        # The draft is a plain dense LM even for an MoE target — it only
        # proposes; the target's verify pass owns the distribution.  It
        # shares --kv-cache-dtype: the draft runs the most decode steps,
        # so the int8 cache pays off there first (ADVICE r4).
        draft = TransformerLM(
            vocab_size=vocab,
            d_model=args.draft_d_model or args.d_model,
            n_layers=args.draft_n_layers or args.n_layers,
            n_heads=args.draft_n_heads or args.n_heads,
            n_kv_heads=(args.draft_n_kv_heads
                        if args.draft_n_kv_heads is not None
                        else args.n_kv_heads),
            compute_dtype=dtype,
            kv_cache_dtype=kv_dtype,
        )
        from distributed_machine_learning_tpu.train.lm_step import (
            init_lm_state,
        )

        if args.draft_ckpt_dir:
            draft_params = _restore_lm_params(
                args.draft_ckpt_dir, draft.n_layers
            )
        else:
            draft_params = init_lm_state(draft, seed=11).params
            print("WARNING: random-init draft (exact output, poor "
                  "acceptance)")
        draft_params = jax.tree_util.tree_map(
            lambda p: p.astype(dtype) if p.dtype == jnp.float32 else p,
            draft_params,
        )
        if mesh is not None:
            spec_fn = make_tp_speculative_generate_fn(
                model, draft, args.max_new_tokens, mesh,
                gamma=args.spec_gamma, temperature=args.temperature,
                top_k=args.top_k, top_p=args.top_p, quantize=args.quant,
            )
        else:
            spec_fn = make_speculative_generate_fn(
                model, draft, args.max_new_tokens, gamma=args.spec_gamma,
                temperature=args.temperature, top_k=args.top_k,
                top_p=args.top_p, quantize=args.quant,
            )
        # Same (params, prompt, key) signature as the other paths, so
        # the shared detokenize/print epilogue below serves all three.
        fn = lambda p, pr, k: spec_fn(p, draft_params, pr, k)
    elif mesh is not None:
        from distributed_machine_learning_tpu.inference.generate import (
            make_tp_generate_fn,
        )

        fn = make_tp_generate_fn(
            model, args.max_new_tokens, mesh,
            temperature=args.temperature, top_k=args.top_k,
            top_p=args.top_p, quantize=args.quant,
        )
    else:
        fn = make_generate_fn(model, args.max_new_tokens,
                              temperature=args.temperature,
                              top_k=args.top_k, top_p=args.top_p,
                              quantize=args.quant)
    if mesh is not None:
        from distributed_machine_learning_tpu.parallel.tensor_parallel import (  # noqa: E501
            tp_decode_params,
        )

        params = tp_decode_params(params, args.tp)
    out = np.asarray(
        fn(params, prompt, jax.random.PRNGKey(args.seed))
    )[0, prompt.shape[1]:]
    if vocab == VOCAB_SIZE:
        text = bytes(t for t in out.tolist() if t < 256).decode(
            "utf-8", errors="replace"
        )
    else:
        text = " ".join(str(t) for t in out.tolist())
    print(args.prompt + text)


if __name__ == "__main__":
    main()
