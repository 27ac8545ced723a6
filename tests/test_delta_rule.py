"""The chunked gated delta rule (``ops/delta_rule.py``) against the
recurrence it restates, forward and gradient."""

from __future__ import annotations

import collections
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from distributed_machine_learning_tpu.ops import delta_rule
from distributed_machine_learning_tpu.ops.delta_rule import (
    CHUNK,
    gated_delta_rule,
    gated_delta_rule_recurrent,
    state_pass,
)
from distributed_machine_learning_tpu.ops.pallas import gdn_prepare, kda_prepare

#: float32 on both sides; the two differ by the order of their sums and by
#: the triangular solve's own rounding (64 steps of forward substitution).
TOL = 2e-5


def _inputs(seed, T, decay_shift=0.0, B=2, H=3, dk=16, dv=24):
    """Unit keys, scaled unit queries, ``g = −exp(shift)·softplus(·)``:
    ``shift`` −8 holds the decay at 1 − 3e-4 a step, +3 drives it to
    1e-27."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (B, T, H, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (B, T, H, dk)))
    v = jax.random.normal(ks[2], (B, T, H, dv))
    g = -jnp.exp(decay_shift) * jax.nn.softplus(
        jax.random.normal(ks[3], (B, T, H)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H)))
    return q, k, v, g, beta


def _rel(a, b):
    return float(jnp.abs(a - b).max() / jnp.abs(b).max())


@pytest.mark.parametrize("T", [CHUNK, 2 * CHUNK, 150, 7])
def test_chunked_rule_matches_the_recurrence(T):
    args = _inputs(0, T)
    assert _rel(gated_delta_rule(*args),
                gated_delta_rule_recurrent(*args)) < TOL


@pytest.mark.parametrize("decay_shift", [-8.0, 0.0, 3.0],
                         ids=["decay_near_1", "decay_mid", "decay_near_0"])
def test_chunked_rule_gradients_match_the_recurrence(decay_shift):
    args = _inputs(1, 150, decay_shift)  # 150: not a multiple of the chunk
    decay = jnp.exp(args[3])
    if decay_shift < 0:
        assert float(decay.min()) > 0.99
    if decay_shift > 0:
        assert float(decay.min()) < 1e-20
    w = jax.random.normal(jax.random.PRNGKey(9), (*args[0].shape[:3], 24))
    grads = [jax.grad(lambda *a: (f(*a) * w).sum(), argnums=range(5))(*args)
             for f in (gated_delta_rule, gated_delta_rule_recurrent)]
    # The decay's own gradient at 1e-27 is a sum of vanishing terms: held
    # to 1e-4 there, everything else to TOL.
    for name, a, b in zip("q k v g beta".split(), *grads):
        assert jnp.isfinite(a).all(), name
        assert _rel(a, b) < (1e-4 if name == "g" else TOL), name


def test_padding_steps_leave_the_state_alone():
    """A length that is no multiple of the chunk gives the prefix of the
    longer run: the padded steps neither write nor decay."""
    args = _inputs(2, 2 * CHUNK)
    short = tuple(a[:, :CHUNK + 5] for a in args)
    assert _rel(gated_delta_rule(*short),
                gated_delta_rule(*args)[:, :CHUNK + 5]) < TOL


def test_bf16_operands_accumulate_in_float32():
    args = _inputs(3, 2 * CHUNK)
    low = tuple(a.astype(jnp.bfloat16) for a in args[:3]) + args[3:]
    out = gated_delta_rule(*low)
    assert out.dtype == jnp.bfloat16
    ref = gated_delta_rule_recurrent(*low)
    assert _rel(out.astype(jnp.float32), ref) < 0.03


# --- The state pass: the Pallas kernels (interpreted here) against the
# --- ``lax.scan`` of the same step functions, and the hand-written backward
# --- pass against autodiff.

#: The widths at which ``state_pass`` picks the kernels on a TPU.
WIDE = dict(B=1, H=2, dk=128, dv=128)


@pytest.fixture
def through_kernels(monkeypatch):
    """Takes the kernel path whatever the platform: the steering a CPU test
    needs, since the dispatch itself leaves the interpreter alone."""
    monkeypatch.setattr(delta_rule, "state_pass", lambda *a: "kernel")


def _value_and_grads(rule, args, seed=9):
    w = jax.random.normal(jax.random.PRNGKey(seed), args[2].shape)
    loss = lambda *a: (rule(*a).astype(jnp.float32) * w).sum()
    return rule(*args), jax.grad(loss, argnums=range(5))(*args)


def _through(kind, monkeypatch, args):
    with monkeypatch.context() as m:
        m.setattr(delta_rule, "state_pass", lambda *a: kind)
        return _value_and_grads(gated_delta_rule, args)


def _autodiff_oracle(q, k, v, g, beta, chunk=CHUNK):
    """The chunked rule as it was before the state pass had a backward pass
    of its own: the preparation, then one ``lax.scan`` with four matmuls a
    step, differentiated by autodiff."""
    f32, dt = jnp.float32, v.dtype
    W, U, attn, q_in, k_out, d = delta_rule._prepare(q, k, v, g, beta, chunk)
    matmul = lambda a, b: jnp.matmul(a, b, preferred_element_type=f32)

    def step(S, x):
        W_c, U_c, attn_c, q_c, k_c, d_c = x
        S_in = S.astype(dt)
        u = U_c - matmul(W_c, S_in)
        u_in = u.astype(dt)
        out = matmul(q_c, S_in) + matmul(attn_c, u_in)
        return S * d_c + matmul(jnp.swapaxes(k_c, -1, -2), u_in), out

    B, T, H, dk = q.shape
    S0 = jnp.zeros((B, H, dk, v.shape[-1]), f32)
    _, out = lax.scan(step, S0, (W, U, attn, q_in, k_out, d))
    out = jnp.moveaxis(out, (0, 2), (1, 3))
    return out.reshape(B, -1, H, v.shape[-1])[:, :T].astype(dt)


DISPATCH = pytest.mark.parametrize("dk, dv, chunk, platform, kind", [
    (128, 128, 64, "tpu", "kernel"),
    (256, 128, 64, "tpu", "kernel"),
    (128, 128, 64, "cpu", "scan"),
    (128, 128, 64, "gpu", "scan"),
    (16, 24, 64, "tpu", "scan"),
    (128, 192, 64, "tpu", "scan"),
    (64, 128, 64, "tpu", "scan"),
    (128, 128, 32, "tpu", "scan"),
])


@DISPATCH
def test_state_pass_is_chosen_from_shapes_and_platform(
        dk, dv, chunk, platform, kind):
    assert state_pass(dk, dv, chunk, platform) == kind


@DISPATCH
def test_the_preparation_follows_the_state_pass(
        monkeypatch, dk, dv, chunk, platform, kind):
    """One rule for both: where ``state_pass`` says ``"kernel"`` the traced
    gradient holds the preparation kernel once (the backward pass takes the
    forward pass's), its reverse once, the state kernels twice (forward,
    made again) and once; elsewhere no kernel at all.  Traced only, on the
    platform the row names (anything but a TPU interprets)."""
    monkeypatch.setattr(delta_rule, "interpret", lambda: platform != "tpu")
    args = _inputs(0, 2 * chunk, B=1, H=1, dk=dk, dv=dv)
    loss = lambda *a: gated_delta_rule(*a, chunk=chunk).sum()
    traced = str(jax.make_jaxpr(jax.grad(loss, argnums=range(5)))(*args))
    calls = {name: traced.count(f"name={name}\n") for name in (
        "gdn_prepare_fwd", "gdn_prepare_bwd", "gdn_state_fwd",
        "gdn_state_bwd")}
    assert list(calls.values()) == (
        [1, 1, 2, 1] if kind == "kernel" else [0, 0, 0, 0])


def test_the_dispatch_asks_state_pass(monkeypatch):
    """What ``state_pass`` says is what runs: the call asks it with its own
    widths, and here — no TPU — with ``"cpu"``; the backward pass takes
    what the forward pass was told."""
    asked = []

    def record(*a):
        asked.append(a)
        return state_pass(*a)

    monkeypatch.setattr(delta_rule, "state_pass", record)
    _value_and_grads(gated_delta_rule, _inputs(0, 70))
    assert asked and set(asked) == {(16, 24, CHUNK, "cpu")}
    assert state_pass(*asked[0]) == "scan"


@pytest.mark.parametrize("per_channel", [False, True],
                         ids=["scalar", "per_channel"])
def test_the_scan_prepares_once_a_gradient(monkeypatch, per_channel):
    """The XLA path keeps the preparation as the kernels do: under
    ``jax.grad`` the chunk function is traced once forward and its reverse
    once, a decay a head or a channel, and the gradient is the
    recurrence's at a length no multiple of the chunk."""
    module = kda_prepare if per_channel else gdn_prepare
    traced = collections.Counter()

    def counting(name):
        chunk_fn = getattr(module, name)

        def counted(*a, **kw):
            traced[name] += 1
            return chunk_fn(*a, **kw)
        return counted

    for name in ("prepare_chunk", "prepare_chunk_bwd"):
        monkeypatch.setattr(module, name, counting(name))
    q, k, v, g, beta = _inputs(15, 100)  # padded to 128
    if per_channel:
        g = -jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(16),
                                               q.shape))
    args = q, k, v, g, beta
    w = jax.random.normal(jax.random.PRNGKey(9), v.shape)
    grads = [jax.grad(lambda *a: (f(*a) * w).sum(), argnums=range(5))(*args)
             for f in (gated_delta_rule, gated_delta_rule_recurrent)]
    assert traced == {"prepare_chunk": 1, "prepare_chunk_bwd": 1}
    for name, a, b in zip("q k v g beta".split(), *grads):
        assert a.shape == b.shape and jnp.isfinite(a).all(), name
        assert _rel(a, b) < TOL, name


@pytest.mark.parametrize("T", [2 * CHUNK, 150])
def test_kernels_match_the_recurrence(through_kernels, T):
    args = _inputs(4, T, **WIDE)
    out, grads = _value_and_grads(gated_delta_rule, args)
    ref, ref_grads = _value_and_grads(gated_delta_rule_recurrent, args)
    assert _rel(out, ref) < TOL
    for name, a, b in zip("q k v g beta".split(), grads, ref_grads):
        assert _rel(a, b) < TOL, name


@pytest.mark.parametrize("T", [2 * CHUNK, 150])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bf16_operands"])
def test_kernels_match_the_scan(monkeypatch, T, dtype):
    """One arithmetic, two ways to walk the chunks: float32 within ``TOL``,
    bf16 operands to the bit or within one bf16 ulp of a tensor's largest
    entry (both round at the same places)."""
    args = _inputs(5, T, **WIDE)
    args = tuple(a.astype(dtype) for a in args[:3]) + args[3:]
    out, grads = _through("kernel", monkeypatch, args)
    ref, ref_grads = _through("scan", monkeypatch, args)
    limit = TOL if dtype == jnp.float32 else 2.0 ** -8
    assert out.dtype == dtype
    assert _rel(out.astype(jnp.float32), ref.astype(jnp.float32)) < limit
    for name, a, b in zip("q k v g beta".split(), grads, ref_grads):
        assert a.dtype == b.dtype and jnp.isfinite(a).all(), name
        assert _rel(a.astype(jnp.float32), b.astype(jnp.float32)) < limit, name


@pytest.mark.parametrize("kind, sizes, T, decay_shift", [
    ("scan", {}, 150, -8.0), ("scan", {}, 150, 0.0), ("scan", {}, 150, 3.0),
    ("kernel", WIDE, 2 * CHUNK, 0.0), ("kernel", WIDE, 150, -8.0),
], ids=["scan_decay_near_1", "scan_decay_mid", "scan_decay_near_0",
        "kernel_decay_mid", "kernel_decay_near_1"])
def test_backward_pass_by_hand_matches_autodiff(
        monkeypatch, kind, sizes, T, decay_shift):
    args = _inputs(6, T, decay_shift, **sizes)
    with monkeypatch.context() as m:
        m.setattr(delta_rule, "state_pass", lambda *a: kind)
        out, grads = _value_and_grads(gated_delta_rule, args)
    ref, ref_grads = _value_and_grads(_autodiff_oracle, args)
    assert _rel(out, ref) < TOL
    for name, a, b in zip("q k v g beta".split(), grads, ref_grads):
        assert jnp.isfinite(a).all(), name
        assert _rel(a, b) < TOL, name


def test_padding_steps_are_inert_through_the_kernels(through_kernels):
    args = _inputs(7, 2 * CHUNK, **WIDE)
    short = tuple(a[:, :CHUNK + 5] for a in args)
    assert _rel(gated_delta_rule(*short),
                gated_delta_rule(*args)[:, :CHUNK + 5]) < TOL


def test_backward_pass_keeps_the_five_inputs_only(through_kernels):
    """Of the five inputs the forward pass keeps their tiles, and beside
    them the preparation's six arrays — nothing the state pass wrote: the
    per-chunk states and ``u`` are what the backward pass makes again."""
    args = _inputs(8, 2 * CHUNK, **WIDE)
    _, vjp = jax.vjp(gated_delta_rule, *args)
    kept = sorted(a.shape for a in jax.tree_util.tree_leaves(vjp)
                  if hasattr(a, "shape"))
    tiles = delta_rule._tiles(*args, CHUNK)
    prepared = delta_rule._prepare(*args, CHUNK)
    assert kept == sorted(a.shape for a in tiles + prepared)


def _recurrence_in_float64(q, k, v, g, beta, w):
    """The five gradients of ``Σ w ∘ o`` through the recurrence, in float64
    on the host's CPU: what both float32 forms are an approximation of."""
    def loss(q, k, v, g, beta):
        def step(S, x):
            q_t, k_t, v_t, g_t, b_t = x
            S = S * jnp.exp(g_t)[..., None, None]
            delta = b_t[..., None] * (
                v_t - jnp.einsum("bhkv,bhk->bhv", S, k_t))
            S = S + k_t[..., :, None] * delta[..., None, :]
            return S, jnp.einsum("bhkv,bhk->bhv", S, q_t)

        S0 = jnp.zeros((q.shape[0], q.shape[2], q.shape[3], v.shape[3]),
                       jnp.float64)
        _, out = lax.scan(step, S0, tuple(
            jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
        return (jnp.moveaxis(out, 0, 1) * w).sum()

    with jax.enable_x64(True):
        q, k, v, g, beta, w = (jnp.asarray(np.asarray(a), jnp.float64)
                               for a in (q, k, v, g, beta, w))
        return [np.asarray(a) for a in jax.grad(loss, argnums=range(5))(
            q, k, v, g, beta)]


@pytest.mark.parametrize("kind, sizes", [("scan", {}), ("kernel", WIDE)],
                         ids=["scan", "kernel"])
def test_gradients_where_the_decay_vanishes_are_float32_exact(
        monkeypatch, kind, sizes):
    """At a decay of 1e-27 a step ``γ`` runs to the hundreds inside a chunk
    and ``exp(γ_i − γ_j)`` of neighbouring steps, all that is left, is only
    as good as the difference: from one float32 cumulative sum the decay's
    gradient is 1e-5 … 6e-5 from a float64 run of the recurrence (the
    ``solve_triangular`` form's, whatever the seed), from the preparation's
    two-part sum all five are at float32's own precision."""
    args = _inputs(6, 150, 3.0, **sizes)
    w = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)
    with monkeypatch.context() as m:
        m.setattr(delta_rule, "state_pass", lambda *a: kind)
        grads = jax.grad(lambda *a: (gated_delta_rule(*a) * w).sum(),
                         argnums=range(5))(*args)
    for name, a, b in zip("q k v g beta".split(), grads,
                          _recurrence_in_float64(*args, w)):
        assert np.abs(a - b).max() / np.abs(b).max() < 2e-6, name


@pytest.mark.parametrize("chunk", [16, 24, 48])
def test_any_chunk_matches_the_recurrence(chunk):
    """The blocks of the inverse are 16 rows and what is left: a chunk need
    be no 16 · 2ⁿ (one block, one block and a half, three)."""
    args = _inputs(14, 100)
    rule = partial(gated_delta_rule, chunk=chunk)
    out, grads = _value_and_grads(rule, args)
    ref, ref_grads = _value_and_grads(gated_delta_rule_recurrent, args)
    assert _rel(out, ref) < TOL
    for name, a, b in zip("q k v g beta".split(), grads, ref_grads):
        assert _rel(a, b) < TOL, name


# --- The preparation: the Pallas kernels (interpreted here) against
# --- ``_prepare``, the ``solve_triangular`` form batched over all chunks,
# --- and their reverse against ``jax.vjp`` of it.

PREPARED = "W U attn q_in k_out d".split()


def _cast(args, dtype):
    return tuple(a.astype(dtype) for a in args[:3]) + args[3:]


def _folded(arrays):
    """[nc, B, H, ...] -> [nc, BH, ...], as the kernels take them."""
    return tuple(a.reshape(a.shape[0], -1, *a.shape[3:]) for a in arrays)


def _from_tiles(grads, args):
    """The kernels' five cotangents ([nc, BH, ...]) as cotangents of
    ``args``: what ``delta_rule._chunked_bwd`` does with them."""
    tiles, undo = jax.vjp(partial(delta_rule._tiles, chunk=CHUNK), *args)
    return undo(tuple(g.reshape(t.shape) for g, t in zip(grads, tiles)))


@pytest.mark.parametrize("T", [2 * CHUNK, 150])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bf16_operands"])
def test_preparation_kernel_matches_the_batched_solve(T, dtype):
    """The six outputs.  float32 within ``TOL``; with bf16 operands the two
    round the same float32 numbers (to 1e-6) to bf16, so an entry differs by
    an ulp at most, and ``U``, ``d`` stay float32."""
    args = _cast(_inputs(10, T, **WIDE), dtype)
    want = delta_rule._prepare(*args, CHUNK)
    got = gdn_prepare.prepare_fwd(
        *_folded(delta_rule._tiles(*args, CHUNK)))
    for name, a, b in zip(PREPARED, got, _folded(want)):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        exact = a.dtype == jnp.float32
        assert _rel(a.astype(jnp.float32), b.astype(jnp.float32)) < (
            TOL if exact else 2.0 ** -8), name


@pytest.mark.parametrize("T, decay_shift", [
    (2 * CHUNK, 0.0), (150, 0.0), (150, -8.0), (150, 3.0)],
    ids=["even", "padded", "decay_near_1", "decay_near_0"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bf16_operands"])
def test_preparation_reverse_kernel_matches_autodiff(T, decay_shift, dtype):
    """The five cotangents against ``jax.vjp(_prepare)`` fed the same six.
    At a decay of 1e-27 ``γ_i − γ_j`` above the diagonal would overflow:
    the mask before the exponential holds in the reverse too (finite), and
    its gradient keeps ``TOL`` (``jax.vjp(_prepare)`` is 6e-6 from float64
    there, the kernel 2e-7).
    With bf16 operands autodiff rounds each of a cotangent's terms to bf16
    and adds them in bf16, the kernel adds in float32 and rounds once: two
    ulps apart at most."""
    args = _cast(_inputs(11, T, decay_shift, **WIDE), dtype)
    want, vjp = jax.vjp(partial(delta_rule._prepare, chunk=CHUNK), *args)
    keys = jax.random.split(jax.random.PRNGKey(12), 6)
    cts = tuple(jax.random.normal(key, a.shape).astype(a.dtype)
                for key, a in zip(keys, want))
    grads = gdn_prepare.prepare_bwd(
        *_folded(delta_rule._tiles(*args, CHUNK)), *_folded(cts))
    for name, a, b in zip("q k v g beta".split(), _from_tiles(grads, args),
                          vjp(cts)):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        assert jnp.isfinite(a).all(), name
        limit = 2.0 ** -7 if a.dtype == jnp.bfloat16 else TOL
        assert _rel(a.astype(jnp.float32), b.astype(jnp.float32)) < limit, name


def _doubling_inverse(A):
    """``(I − A)(I + A²)(I + A⁴)…``: exact for a nilpotent ``A``, and what
    the conditioning case is there to keep out."""
    T, power = jnp.eye(A.shape[0]) - A, A
    for _ in range(5):
        power = power @ power
        T = T @ (jnp.eye(A.shape[0]) + power)
    return T


def test_the_inverse_is_as_stable_as_substitution():
    """Equal keys, ``β = 1 − 1e-3``, ``g = 0``: the system is all ones (less
    a thousandth) below its diagonal.  ``U = T (β v)`` from the kernel is
    held to four times the error float32 ``solve_triangular`` itself shows
    against a float64 solve on the host; the nilpotent doubling, in float32
    like the kernel, misses that by orders of magnitude."""
    import scipy.linalg

    q, k, v, g, beta = _inputs(13, CHUNK, **WIDE)
    k = jnp.broadcast_to(k[:, :1], k.shape)
    g, beta = jnp.zeros_like(g), jnp.full_like(beta, 1 - 1e-3)
    tiles = _folded(delta_rule._tiles(q, k, v, g, beta, CHUNK))
    got = gdn_prepare.prepare_fwd(*tiles)[1]
    solved = delta_rule._prepare(q, k, v, g, beta, CHUNK)[1]
    kf, vf = (np.asarray(a, np.float64) for a in tiles[1:3])
    err = {"kernel": 0.0, "solve": 0.0, "doubling": 0.0}
    for h in range(kf.shape[1]):
        system = np.tril((1 - 1e-3) * kf[0, h] @ kf[0, h].T, -1)
        rhs = (1 - 1e-3) * vf[0, h]
        exact = scipy.linalg.solve_triangular(
            system + np.eye(CHUNK), rhs, lower=True, unit_diagonal=True)
        doubled = _doubling_inverse(jnp.asarray(system, jnp.float32)) @ (
            jnp.asarray(rhs, jnp.float32))
        for name, a in (("kernel", got[0, h]), ("solve", solved[0, 0, h]),
                        ("doubling", doubled)):
            err[name] = max(err[name], float(
                np.abs(np.asarray(a, np.float64) - exact).max()
                / np.abs(exact).max()))
    assert err["kernel"] <= 4 * err["solve"], err
    assert err["doubling"] > 100 * err["solve"], err
