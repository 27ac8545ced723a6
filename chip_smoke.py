"""chip_smoke.py — does the system still start on the chip?

One process, no flags, no small mode, no CPU mode: ``python chip_smoke.py``
drives the trainers end to end on the local TPU through the entry points a
user calls (``cli.part1`` / ``cli.part3`` / ``cli.lm`` ``main(argv)``) at
full width, then calls every remaining Pallas kernel once at a production
shape against the XLA reference it sits next to.  It exits non-zero at the
first thing that is not right (no TPU, a missing print line, a non-finite
loss, an interpreted kernel, a lowered step without a Mosaic custom call, a
parity miss), and on success prints as its last line::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

One process holds the chip for the whole run; nothing here starts a child
that needs it.  The compile cache follows ``runtime/compile_cache.py``:
``JAX_COMPILATION_CACHE_DIR`` if set, else ``<checkout>/.jax_cache``.

The phase functions take their CLI flags / shapes as arguments so
``tests/test_chip_smoke.py`` can call the same code at a tiny size on the
CPU; ``main()`` only ever passes the production values below.
"""

from __future__ import annotations

import contextlib
import gc
import io
import json
import math
import re
import sys
import time

# The reference protocol is each CLI's default (VGG-11, 40 iterations,
# loss print at 20, full test-set eval; part1 batch 256, part3 64 per
# rank + BatchNorm + 25 MB buckets), so the flags only pick the MXU
# dtype and make a missing C++ toolchain a failure instead of a fallback.
PART1_ARGV = ["--compute-dtype", "bfloat16", "--loader", "native"]
PART3_ARGV = ["--compute-dtype", "bfloat16", "--loader", "native"]
# The fused build: int8 ring codec + fused AdamW kernels inside the real
# shard_map step, and the sharded eval path.
PART3_FUSED_ARGV = PART3_ARGV + [
    "--ring-compress", "int8", "--ring-codec-impl", "pallas",
    "--optimizer", "adamw", "--fused-update",
    "--max-iters", "6", "--eval-batches", "2", "--dist-eval",
]
# A d2048 GQA LM that fills the MXU's tiles; --batch-size (the GLOBAL batch,
# 4 per chip) is appended once the chip count is known.
LM_ARGV = [
    "--parallel", "dp", "--d-model", "2048", "--n-heads", "16",
    "--n-kv-heads", "4", "--n-layers", "8", "--seq-len", "1024",
    "--compute-dtype", "bfloat16", "--attn", "flash",
    "--fused-ce-chunks", "2", "--max-iters", "40",
]
LM_BATCH_PER_CHIP = 4
KERNEL_SHAPES = dict(
    heads=16, kv_heads=4, head_dim=128,   # the LM's attention geometry
    cache_len=4096, cache_batch=4, cache_pos=3000,
    page_block=128, pages=64, page_positions=(5, 700, 127, 1500),
    matmul=(8, 2048, 8192),               # decode rows × d_model × d_ff
    codec_len=1_600_000,                  # a VGG-11 ring chunk at world 4
    delta_len=1000, delta_heads=4,        # 16 chunks, the last one padded
    prepare_len=8192, prepare_heads=32,   # the hybrid cell's DeltaNet layer
    ring_seq_per_chip=1024, ring_batch=1,
)
# On the MXU both kernel and reference multiply in bf16, so the f32
# tolerances of the CPU parity tests do not apply; these are the bf16 /
# int8 ones the repo states (tests/test_quant.py, test_decode_attention).
BF16_TOL = 2e-2
# Float32 against float32 where both sides ask for full precision: a single
# bf16 pass in place of a float32 product reads 4e-3.
F32_TOL = 1e-5
INT8_KV_TOL = 5e-2


class SmokeFailure(Exception):
    """A phase found something that is not right."""


def _require(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


class _Tee(io.TextIOBase):
    def __init__(self, stream):
        self.stream, self.buf = stream, io.StringIO()

    def write(self, s):
        self.buf.write(s)
        return self.stream.write(s)

    def flush(self):
        self.stream.flush()


def _run_cli(main, argv):
    """``main(argv)`` with its stdout shown AND kept: (text, RunResult)."""
    tee = _Tee(sys.stdout)
    print(f"[chip_smoke] $ {main.__module__} {' '.join(argv)}", flush=True)
    with contextlib.redirect_stdout(tee):
        result = main(argv)
    return tee.buf.getvalue(), result


def _losses(out: str) -> dict[int, float]:
    found = {int(n): float(v) for n, v in
             re.findall(r"Loss at (\d+)th batch is (\S+)", out)}
    _require(bool(found), "no 'Loss at Nth batch' line was printed")
    for n, v in found.items():
        _require(math.isfinite(v), f"loss at batch {n} is {v}")
    return found


def _require_surface(out: str, needles) -> None:
    for needle in needles:
        _require(needle in out, f"missing from the output: {needle!r}")


def _require_kernel_mode(out: str, kernels: str) -> None:
    _require(f"pallas={kernels}" in out,
             f"the run banner does not say pallas={kernels}")


def _require_mosaic(lowered_text: str, kernels: str, what: str) -> None:
    """Mosaic kernels lower to a ``tpu_custom_call``; the interpreter
    lowers to plain HLO, so its absence means nothing was compiled."""
    has = "tpu_custom_call" in lowered_text
    _require(has == (kernels == "compiled"),
             f"{what}: lowered program "
             f"{'contains' if has else 'contains no'} Mosaic custom call, "
             f"expected kernels {kernels}")


def _lower_step(result, batch):
    """StableHLO text of the very step ``result`` ran, at ``batch``."""
    step = result.train_step
    if result.place_batch is not None:
        batch = result.place_batch(*batch)
    if hasattr(step, "sync_state"):  # stateful (error-feedback) wrapper
        return step.inner.lower(
            result.state, *batch, step.sync_state()).as_text()
    return step.lower(result.state, *batch).as_text()


def _require_spread(result, batch) -> None:
    """More than one chip: state and batch live on EVERY device, and the
    replicated params hold the same bits everywhere."""
    import jax
    import jax.numpy as jnp

    devices = jax.devices()
    if len(devices) == 1:
        return
    everywhere = set(devices)
    for leaf in jax.tree_util.tree_leaves(result.state):
        if isinstance(leaf, jax.Array):
            _require({s.device for s in leaf.addressable_shards}
                     == everywhere, "a state leaf is not on every device")
    for arr in result.place_batch(*batch):
        _require({s.device for s in arr.addressable_shards} == everywhere,
                 "a placed batch does not have a shard on every device")
    same = jax.jit(lambda a, b: jnp.array_equal(a, b, equal_nan=True))
    for leaf in jax.tree_util.tree_leaves(result.state.params):
        ref, *others = leaf.addressable_shards
        for other in others:
            moved = jax.device_put(other.data, ref.device)
            _require(bool(same(ref.data, moved)),
                     f"replicated params differ between {ref.device} and "
                     f"{other.device}")


def _cnn_batch(per_rank: int):
    import jax
    import numpy as np

    n = per_rank * jax.device_count()
    return np.zeros((n, 32, 32, 3), np.uint8), np.zeros((n,), np.int32)


# -- phases -------------------------------------------------------------


def require_tpu() -> dict:
    """The device this smoke run is about, as JAX reports it — or exit.

    JAX falls back to the CPU with only a warning when a TPU fails to
    initialise; a run that finds no TPU fails instead of continuing on
    another backend."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"no TPU found: JAX's default backend is {dev.platform!r} "
            f"({dev.device_kind}); chip_smoke.py only runs on the chip"
        )
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def phase_device() -> dict:
    import importlib.metadata as md

    import jax

    from distributed_machine_learning_tpu.runtime.compile_cache import (
        configure_compile_cache,
    )

    cache_dir = configure_compile_cache()
    device = require_tpu()  # exits non-zero naming the missing TPU
    print(f"[chip_smoke] platform={device['platform']} "
          f"device_kind={device['kind']!r} count={device['count']} "
          f"jax={jax.__version__} jaxlib={md.version('jaxlib')} "
          f"libtpu={md.version('libtpu')} cache_dir={cache_dir}")
    # The mesh helpers take jax.devices() in this order (runtime/mesh.py).
    print("[chip_smoke] device order: " + " ".join(
        f"id{d.id}@{tuple(d.coords)}" for d in jax.devices()))
    return device


def phase_part1(argv=PART1_ARGV) -> None:
    from distributed_machine_learning_tpu.cli import part1

    out, _ = _run_cli(part1.main, argv)
    _require_surface(out, ["strategy=none world_size=1",
                           "Loss at 20th batch", "Test set: Average loss"])
    _losses(out)
    test_loss = float(re.search(r"Average loss: (\S+),", out).group(1))
    _require(math.isfinite(test_loss), f"test loss is {test_loss}")


def phase_part3(argv=PART3_ARGV, fused_argv=PART3_FUSED_ARGV,
                kernels: str = "compiled", per_rank: int = 64) -> None:
    import jax

    from distributed_machine_learning_tpu.cli import part3

    world = jax.device_count()
    batch = _cnn_batch(per_rank)
    out, result = _run_cli(part3.main, argv)
    _require_surface(out, [f"strategy=ring world_size={world}",
                           "Loss at 20th batch", "Test set: Average loss"])
    _losses(out)
    _require_spread(result, batch)
    del result

    out, result = _run_cli(part3.main, fused_argv)
    _require_surface(out, [f"strategy=ring world_size={world}",
                           "Test set: Average loss"])
    _require_kernel_mode(out, kernels)
    _require_mosaic(_lower_step(result, batch), kernels, "fused part3 step")
    _require_spread(result, batch)
    for leaf in jax.tree_util.tree_leaves(result.state.params):
        _require(bool(jax.numpy.isfinite(leaf).all()),
                 "non-finite params after the fused part3 run")


def phase_lm(argv=None, kernels: str = "compiled") -> None:
    import jax
    import numpy as np

    from distributed_machine_learning_tpu.cli import lm

    if argv is None:
        argv = LM_ARGV + [
            "--batch-size", str(LM_BATCH_PER_CHIP * jax.device_count())]
    out, result = _run_cli(lm.main, argv)
    _require_kernel_mode(out, kernels)
    losses = _losses(out)
    first, last = losses[min(losses)], losses[max(losses)]
    _require(last <= first * 1.02,
             f"LM loss rose from {first} to {last}")
    args = lm.make_parser().parse_args(argv)
    batch = (np.zeros((args.batch_size, args.seq_len), np.int32),) * 2
    _require_mosaic(_lower_step(result, batch), kernels, "LM step")
    _require_spread(result, batch)


def _exact(fn, *args):
    """``fn(*args)`` with f32 matmuls at full precision — the reference
    side of a parity check (the kernels run as they ship)."""
    import jax

    with jax.default_matmul_precision("highest"):
        return fn(*args)


def _run_kernel(name: str, fn, args, kernels: str):
    """Lower (assert Mosaic compiled it), then run: the kernel's output."""
    import jax

    jitted = jax.jit(fn)
    _require_mosaic(jitted.lower(*args).as_text(), kernels, name)
    return jax.block_until_ready(jitted(*args))


def _close(name: str, got, want, tol: float) -> None:
    import numpy as np

    got, want = (np.asarray(a, np.float32) for a in (got, want))
    _require(bool(np.isfinite(got).all()), f"{name}: non-finite output")
    err = float(np.abs(got - want).max())
    print(f"[chip_smoke]   {name}: max|err| {err:.3g} (tol {tol:g})")
    _require(bool(np.allclose(got, want, rtol=tol, atol=tol)),
             f"{name}: max|err| {err:.3g} exceeds rtol=atol={tol:g}")


def phase_kernels(shapes=KERNEL_SHAPES, kernels: str = "compiled") -> None:
    """Every shipped Pallas kernel phases 3–4 did not already compile
    (they cover fused AdamW and the flash forward/backward)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from distributed_machine_learning_tpu.models.transformer import (
        _cached_attention,
    )
    from distributed_machine_learning_tpu.ops.pallas import (
        decode_attention as da,
    )
    from distributed_machine_learning_tpu.ops.pallas.quant_matmul import (
        int8_matmul,
        quantize_int8,
    )

    rng = np.random.default_rng(69143)
    H, Hkv, D = shapes["heads"], shapes["kv_heads"], shapes["head_dim"]
    bf16 = jnp.bfloat16

    def normal(shape, dtype=bf16):
        return jnp.asarray(rng.standard_normal(shape), dtype)

    def f32(*arrays):
        return [a.astype(jnp.float32) for a in arrays]

    # cached_flash_attention: bf16 cache and the int8+scale cache.
    B, S, pos = (shapes[k] for k in ("cache_batch", "cache_len", "cache_pos"))
    _require(da.decode_flash_qualifies(S),
             f"cache length {S} is on the einsum side of the dispatch")
    q, k, v = (normal((B, 1, H, D)), normal((B, Hkv, S, D)),
               normal((B, Hkv, S, D)))
    want = _exact(_cached_attention, *f32(q, k, v),
                  jnp.asarray([pos], jnp.int32))
    got = _run_kernel("cached_flash_attention[bf16]",
                      da.cached_flash_attention,
                      (q, k, v, jnp.int32(pos)), kernels)
    _close("cached_flash_attention[bf16]", got, want, BF16_TOL)

    def quantize_rows(t):
        t = t.astype(jnp.float32)
        amax = jnp.abs(t).max(axis=-1)
        scale = jnp.where(amax > 0, amax / 127.0, 1.0)
        q8 = jnp.clip(jnp.round(t / scale[..., None]), -127, 127)
        return q8.astype(jnp.int8), scale

    (k8, ks), (v8, vs) = quantize_rows(k), quantize_rows(v)
    got = _run_kernel("cached_flash_attention[int8]",
                      da.cached_flash_attention,
                      (q, k8, v8, jnp.int32(pos), ks, vs), kernels)
    _close("cached_flash_attention[int8]", got, want, INT8_KV_TOL)

    # paged_flash_attention vs the gather reference, ragged lanes.
    bs, nb = shapes["page_block"], shapes["pages"]
    positions = shapes["page_positions"]
    _require(da.paged_flash_qualifies(bs),
             f"pool block {bs} is on the reference side of the dispatch")
    tables = np.zeros(
        (len(positions), max(p // bs + 1 for p in positions)), np.int32)
    free = iter(rng.permutation(nb))
    for lane, p in enumerate(positions):
        for j in range(p // bs + 1):
            tables[lane, j] = next(free)
    q = normal((len(positions), 1, H, D))
    kp, vp = normal((nb, Hkv, bs, D)), normal((nb, Hkv, bs, D))
    tbl, lane_pos = jnp.asarray(tables), jnp.asarray(positions, jnp.int32)
    want = _exact(da.paged_attention_reference, *f32(q, kp, vp), tbl,
                  lane_pos)
    got = _run_kernel("paged_flash_attention", da.paged_flash_attention,
                      (q, kp, vp, tbl, lane_pos), kernels)
    _close("paged_flash_attention", got, want, BF16_TOL)

    # quant_matmul against the dequantize-then-matmul reference.
    R, Dm, K = shapes["matmul"]
    x = normal((R, Dm))
    wq, ws = quantize_int8(normal((Dm, K), jnp.float32) * 0.02)
    want = x @ (wq.astype(bf16) * ws[None, :].astype(bf16))
    got = _run_kernel("quant_matmul", int8_matmul, (x, wq, ws), kernels)
    _close("quant_matmul", got, want, BF16_TOL)

    # Ring codec (compiled inside the part3 step only when world > 1):
    # every seam against the XLA build of the same recipe.
    from distributed_machine_learning_tpu.ops.ring import Int8Scheme

    L = shapes["codec_len"]
    vec, acc = normal((L,), jnp.float32), normal((L,), jnp.float32)

    def seams(scheme):
        def f(vec, acc):
            enc, err = scheme.encode_with_residual(vec)
            return (*enc, err, scheme.decode(enc, L),
                    scheme.decode_add(enc, acc, L))
        return f

    want = jax.jit(seams(Int8Scheme("xla")))(vec, acc)
    got = _run_kernel("ring_codec", seams(Int8Scheme("pallas")),
                      (vec, acc), kernels)
    for seam, g, w in zip(("q", "scale", "residual", "decode", "decode_add"),
                          got, want):
        _require(bool(np.array_equal(np.asarray(g), np.asarray(w))),
                 f"ring_codec: seam {seam!r} is not bit-equal to the XLA "
                 "build")
    print("[chip_smoke]   ring_codec: 5 seams bit-equal to the XLA build")

    # gdn_prepare_* / gdn_state_*: at head widths of 128 the delta rule's
    # dispatch takes the kernels on a TPU (and the mapped functions and the
    # scan anywhere else).
    from distributed_machine_learning_tpu.ops import delta_rule

    Td, Hd = shapes["delta_len"], shapes["delta_heads"]
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    qd, kd = (unit(normal((1, Td, Hd, 128), jnp.float32)) for _ in range(2))
    rule_args = ((qd * 128 ** -0.5).astype(bf16), kd.astype(bf16),
                 normal((1, Td, Hd, 128)),
                 -0.1 * jax.nn.softplus(normal((1, Td, Hd), jnp.float32)),
                 jax.nn.sigmoid(normal((1, Td, Hd), jnp.float32)))

    def out_and_grads(rule):
        def f(*a):
            out = rule(*a).astype(jnp.float32)
            return out, jax.grad(lambda *b: jnp.sin(
                rule(*b).astype(jnp.float32)).sum(), argnums=(0, 1, 2))(*a)
        return f

    want = _exact(out_and_grads(delta_rule.gated_delta_rule_recurrent),
                  *f32(*rule_args))
    got = _run_kernel("gated_delta_rule",
                      out_and_grads(delta_rule.gated_delta_rule), rule_args,
                      kernels)
    for name, g, w in zip(("out", "dq", "dk", "dv"),
                          jax.tree_util.tree_leaves(got),
                          jax.tree_util.tree_leaves(want)):
        scale = float(jnp.abs(w).max())
        _close(f"gated_delta_rule[{name}]", g / scale, w / scale, BF16_TOL)

    # gdn_prepare_fwd / gdn_prepare_bwd alone, against the batched
    # solve_triangular and jax.vjp of it: W and the cotangents of q, k, v
    # round to bf16 on both sides; U and the cotangents of g and β are
    # float32, so they show the precision of the inverse and of the products
    # with it.  Interpret mode computes float32 products exactly and cannot
    # show a bf16 pass Mosaic put in their place.
    from distributed_machine_learning_tpu.ops.pallas import gdn_prepare

    Tp, Hp = shapes["prepare_len"], shapes["prepare_heads"]
    qp, kp = (unit(normal((1, Tp, Hp, 128), jnp.float32)) for _ in range(2))
    prepare_args = ((qp * 128 ** -0.5).astype(bf16), kp.astype(bf16),
                    normal((1, Tp, Hp, 128)),
                    -0.1 * jax.nn.softplus(normal((1, Tp, Hp), jnp.float32)),
                    jax.nn.sigmoid(normal((1, Tp, Hp), jnp.float32)))
    fold = lambda a: a.reshape(a.shape[0], -1, *a.shape[3:])
    solve = lambda *a: delta_rule._prepare(*a, delta_rule.CHUNK)
    tiles = lambda *a: map(fold, delta_rule._tiles(*a, delta_rule.CHUNK))
    want = _exact(jax.jit(lambda *a: solve(*a)[:2]), *prepare_args)
    got = _run_kernel(
        "gdn_prepare_fwd",
        lambda *a: gdn_prepare.prepare_fwd(*tiles(*a))[:2],
        prepare_args, kernels)
    for name, g, w, tol in zip(("W", "U"), got, want, (BF16_TOL, F32_TOL)):
        scale = float(jnp.abs(w).max())
        _close(f"gdn_prepare_fwd[{name}]", g.astype(jnp.float32) / scale,
               fold(w).astype(jnp.float32) / scale, tol)

    cotangents = tuple(normal(a.shape, a.dtype)
                       for a in jax.eval_shape(solve, *prepare_args))

    def from_tiles(args, grads):
        made, undo = jax.vjp(
            lambda *a: delta_rule._tiles(*a, delta_rule.CHUNK), *args)
        return undo(tuple(g.reshape(t.shape) for g, t in zip(grads, made)))

    want = _exact(jax.jit(lambda args, cts: jax.vjp(solve, *args)[1](cts)),
                  prepare_args, cotangents)
    got = _run_kernel(
        "gdn_prepare_bwd",
        lambda args, cts: from_tiles(args, gdn_prepare.prepare_bwd(
            *tiles(*args), *map(fold, cts))),
        (prepare_args, cotangents), kernels)
    for name, g, w in zip(("dq", "dk", "dv", "dg", "dbeta"), got, want):
        scale = float(jnp.abs(w.astype(jnp.float32)).max())
        _close(f"gdn_prepare_bwd[{name}]", g.astype(jnp.float32) / scale,
               w.astype(jnp.float32) / scale,
               F32_TOL if w.dtype == jnp.float32 else BF16_TOL)

    # ring_flash_attention: a ring needs more than one chip.
    n = jax.device_count()
    if n == 1:
        print("[chip_smoke]   ring_flash_attention: SKIPPED — one chip, "
              "a sequence ring needs at least two")
        return
    from distributed_machine_learning_tpu.ops.pallas.ring_flash_attention import (  # noqa: E501
        ring_flash_self_attention,
    )
    from distributed_machine_learning_tpu.ops.ring_attention import (
        dense_self_attention,
    )
    from distributed_machine_learning_tpu.runtime.mesh import (
        make_mesh,
        shard_map_no_check,
    )

    Lr, Br = shapes["ring_seq_per_chip"] * n, shapes["ring_batch"]
    q, k, v = (normal((Br, Lr, H, D)) for _ in range(3))
    spec = P(None, "seq")
    ring = shard_map_no_check(
        lambda q, k, v: ring_flash_self_attention(q, k, v, "seq", n),
        mesh=make_mesh(n, ("seq",)), in_specs=(spec,) * 3, out_specs=spec)
    want = _exact(dense_self_attention, *f32(q, k, v))
    got = _run_kernel("ring_flash_attention", ring, (q, k, v), kernels)
    _close("ring_flash_attention", got, want, BF16_TOL)


def main() -> int:
    import jax

    cache = {"hits": 0, "writes": 0}

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache["writes"] += 1

    jax.monitoring.register_event_listener(on_event)
    t_start = time.perf_counter()
    returned = {}
    for name, phase in (("device", phase_device), ("part1", phase_part1),
                        ("part3", phase_part3), ("lm", phase_lm),
                        ("kernels", phase_kernels)):
        before, t0 = dict(cache), time.perf_counter()
        try:
            returned[name] = phase()
        except BaseException as e:
            print(f"[chip_smoke] phase {name}: FAIL "
                  f"{time.perf_counter() - t0:.1f}s — "
                  f"{type(e).__name__}: {e}", flush=True)
            if isinstance(e, SmokeFailure):
                return 1
            raise  # with its traceback; the exit code is non-zero
        gc.collect()  # drop the phase's device buffers before the next
        print(f"[chip_smoke] phase {name}: PASS "
              f"{time.perf_counter() - t0:.1f}s (compile cache: "
              f"{cache['hits'] - before['hits']} hit(s), "
              f"{cache['writes'] - before['writes']} write(s))", flush=True)
    print(f"[chip_smoke] all phases PASS in "
          f"{time.perf_counter() - t_start:.1f}s (compile cache: "
          f"{cache['hits']} hit(s), {cache['writes']} write(s))")
    print(json.dumps({"ok": True, "device": returned["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
