"""Round-13 fused-kernel parity gates — interpret mode, fast tier.

Two contracts, two strengths (see the kernel module docstrings):

- **ring codec** (``ops/pallas/ring_codec.py``): BITWISE.  The
  exact-product construction (mantissa-truncated scale) removes the
  FMA-contraction freedom, so the fused build must equal the XLA
  ``WireScheme`` build bit for bit — wire payload, decoded values, EF
  residual, and whole-ring outputs with rank identity — across worlds
  and both topology axes.
- **fused AdamW** (``ops/pallas/fused_adamw.py``): documented ulp
  bound.  Single update from identical state ≤ 8 ulp; fixed-seed
  3-step trajectories compound the last-bit freedom through state (and
  through re-evaluated gradients in the ZeRO-1 keystone), gated at the
  documented relative bound.

Everything here runs the Pallas interpreter on the CPU CI mesh — the
identical kernel code path the TPU compiles — so tier-1 exercises the
fused kernels on every run.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from distributed_machine_learning_tpu.ops.ring import (
    Int8Scheme,
    get_wire_scheme,
    ring_all_reduce_flat,
)
from distributed_machine_learning_tpu.runtime.mesh import (
    shard_map_no_check,
)
from distributed_machine_learning_tpu.train.adamw import (
    AdamWConfig,
    adamw_init,
    adamw_update,
)

BATCH_AXIS = "batch"


def _ulps(a, b):
    a = np.asarray(jnp.asarray(a, jnp.float32))
    b = np.asarray(jnp.asarray(b, jnp.float32))
    return int(np.abs(
        a.view(np.int32).astype(np.int64) - b.view(np.int32).astype(np.int64)
    ).max()) if a.size else 0


# ---------------------------------------------------------------------------
# Ring codec: bitwise.
# ---------------------------------------------------------------------------


def _codec_outputs(scheme, v, acc):
    """Every codec seam in one jitted program (the fusion context the
    ring compiles): payload, residual, relay decode, decode-add."""
    L = v.shape[0]

    def f(v, acc):
        enc, err = scheme.encode_with_residual(v)
        return (*enc, err, scheme.decode(enc, L),
                scheme.decode_add(enc, acc, L))

    return jax.jit(f)(v, acc)


@pytest.mark.parametrize("length", [5, 1000, 70000])
def test_codec_seams_bitwise(rng, length):
    v = jnp.asarray(rng.normal(size=length).astype(np.float32))
    acc = jnp.asarray(rng.normal(size=length).astype(np.float32))
    ox = _codec_outputs(Int8Scheme("xla"), v, acc)
    op = _codec_outputs(Int8Scheme("pallas"), v, acc)
    names = ("q", "scale", "residual", "decode", "decode_add")
    for name, a, b in zip(names, ox, op):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b), err_msg=f"codec seam {name!r}"
        )


def test_codec_zero_chunk_bitwise():
    v = jnp.zeros(257, jnp.float32)
    for a, b in zip(_codec_outputs(Int8Scheme("xla"), v, v),
                    _codec_outputs(Int8Scheme("pallas"), v, v)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _ring_both(mesh, world, length, scheme, rng):
    g = jnp.asarray(rng.normal(size=(world, length)).astype(np.float32))

    def per_dev(row):
        out, res = ring_all_reduce_flat(
            row[0], BATCH_AXIS, world, mean=True, scheme=scheme,
            return_residual=True,
        )
        return out[None], res[None]

    fn = jax.jit(shard_map_no_check(
        per_dev, mesh=mesh, in_specs=P(BATCH_AXIS),
        out_specs=(P(BATCH_AXIS), P(BATCH_AXIS)),
    ))
    return fn(g)


@pytest.mark.parametrize("world", [2, 4, 8])
def test_ring_codec_bitwise_with_residual(mesh8, world):
    """Whole-ring parity per world: fused == XLA bitwise on the synced
    gradient AND the EF residual, with rank identity preserved (every
    rank ends with identical bits — the replication invariant)."""
    from jax.sharding import Mesh

    mesh = Mesh(np.array(mesh8.devices).reshape(-1)[:world], (BATCH_AXIS,))
    length = 1237
    # One seed, regenerated per run, so both impls see identical bits.
    seed_rng = np.random.default_rng(7)
    ox, rx = _ring_both(mesh, world, length, Int8Scheme("xla"), seed_rng)
    seed_rng = np.random.default_rng(7)
    op, rp = _ring_both(mesh, world, length, Int8Scheme("pallas"), seed_rng)
    np.testing.assert_array_equal(np.asarray(ox), np.asarray(op))
    np.testing.assert_array_equal(np.asarray(rx), np.asarray(rp))
    out = np.asarray(op)
    assert all((out[i] == out[0]).all() for i in range(world)), \
        "rank identity broken: ranks ended with different bits"


@pytest.mark.parametrize("axis", ["inner", "outer"])
def test_hierarchical_codec_bitwise_both_axes(mesh8, axis, rng):
    """The 2x4 hierarchical plan with the int8 codec on EITHER axis:
    fused == XLA bitwise (values + residual), so the knob covers the
    inner reduce-scatter/all-gather hops and the outer sub-ring hops
    alike."""
    from distributed_machine_learning_tpu.ops.topology import (
        Topology,
        topology_all_reduce_flat,
    )

    length = 613
    outs = {}
    for impl in ("xla", "pallas"):
        topo = Topology(2, 4, codec_impl=impl,
                        **{f"{axis}_scheme": "int8"})
        seed_rng = np.random.default_rng(11)
        g = jnp.asarray(
            seed_rng.normal(size=(8, length)).astype(np.float32))

        def per_dev(row, topo=topo):
            out, res = topology_all_reduce_flat(
                row[0], BATCH_AXIS, topo, mean=True, return_residual=True,
                plan="hier",
            )
            return out[None], res[None]

        fn = jax.jit(shard_map_no_check(
            per_dev, mesh=mesh8, in_specs=P(BATCH_AXIS),
            out_specs=(P(BATCH_AXIS), P(BATCH_AXIS)),
        ))
        outs[impl] = fn(g)
    np.testing.assert_array_equal(
        np.asarray(outs["xla"][0]), np.asarray(outs["pallas"][0]))
    np.testing.assert_array_equal(
        np.asarray(outs["xla"][1]), np.asarray(outs["pallas"][1]))


def test_codec_wire_payload_shape_and_accounting():
    """The fused codec must not change the wire: payload leaves keep
    int8[L] + f32[1], and payload_bytes (what the DML103 audit and the
    telemetry counter charge) is impl-independent."""
    for impl in ("xla", "pallas"):
        s = get_wire_scheme("int8", codec_impl=impl)
        q, scale = jax.jit(s.encode)(jnp.ones(300, jnp.float32))
        assert q.dtype == jnp.int8 and q.shape == (300,)
        assert scale.dtype == jnp.float32 and scale.shape == (1,)
        assert s.payload_bytes(300) == 304


def test_codec_non_f32_chunk_falls_back_bitwise(rng):
    """The kernels engage on f32 chunks only (the dtype every ring path
    carries): a bf16 chunk routes the fused seams through the XLA
    arithmetic, so parity holds trivially — the kernel's
    f32-accumulate-round-once would differ in the last bf16 bit."""
    v = jnp.asarray(rng.normal(size=300).astype(np.float32)).astype(
        jnp.bfloat16)
    acc = jnp.asarray(rng.normal(size=300).astype(np.float32)).astype(
        jnp.bfloat16)
    ox = _codec_outputs(Int8Scheme("xla"), v, acc)
    op = _codec_outputs(Int8Scheme("pallas"), v, acc)
    for name, a, b in zip(("q", "scale", "residual", "decode",
                           "decode_add"), ox, op):
        assert a.dtype == b.dtype, name
        np.testing.assert_array_equal(
            np.asarray(jnp.asarray(a, jnp.float32)),
            np.asarray(jnp.asarray(b, jnp.float32)),
            err_msg=f"bf16 codec seam {name!r}",
        )


def test_codec_impl_validation():
    with pytest.raises(ValueError, match="codec impl"):
        get_wire_scheme("int8", codec_impl="triton")
    with pytest.raises(ValueError, match="codec impl"):
        Int8Scheme("triton")
    from distributed_machine_learning_tpu.parallel.strategies import (
        get_strategy,
    )

    with pytest.raises(ValueError, match="codec impl"):
        get_strategy("ring", compress="int8", codec_impl="triton")


# ---------------------------------------------------------------------------
# Fused AdamW: documented ulp bound.
# ---------------------------------------------------------------------------

#: The documented parity bound of ops/pallas/fused_adamw.py: a single
#: update from identical state stays within this many ulp on params
#: and moments (measured worst case 5; zero-moment first steps exact).
SINGLE_UPDATE_ULP = 8
#: 3-step fixed-seed trajectory gate (last-bit freedom compounding
#: through state and re-evaluated gradients; measured 6e-8 on the
#: ZeRO-1 keystone).
TRAJECTORY_REL = 5e-6


def _tree(rng, dtypes=("f32", "f32", "bf16")):
    mk = lambda shape, dt: jnp.asarray(
        rng.normal(size=shape).astype(np.float32)
    ).astype(jnp.bfloat16 if dt == "bf16" else jnp.float32)
    return {"w": mk((37, 19), dtypes[0]), "b": mk((5,), dtypes[1]),
            "e": mk((2000,), dtypes[2])}


def test_fused_adamw_three_fixed_seed_steps(rng):
    """3 fixed-seed updates, fused vs reference trajectories: within
    the documented bound, with the bf16 leaf cast in-kernel."""
    params = _tree(rng)
    cfgs = {False: AdamWConfig(), True: AdamWConfig(fused=True)}
    states = {k: (params, adamw_init(params)) for k in cfgs}
    grads_seq = [
        jax.tree_util.tree_map(
            lambda p: jnp.asarray(
                rng.normal(size=p.shape).astype(np.float32)),
            params,
        )
        for _ in range(3)
    ]
    for step, g in enumerate(grads_seq):
        for fused, cfg in cfgs.items():
            p, m = states[fused]
            states[fused] = jax.jit(
                adamw_update, static_argnames=("config",)
            )(p, m, g, cfg, step=step)
    pr, mr = states[False]
    pf, mf = states[True]
    for k in params:
        assert pf[k].dtype == pr[k].dtype  # bf16 stays bf16
        assert _ulps(pr[k], pf[k]) <= SINGLE_UPDATE_ULP * 3, k
        assert _ulps(mr["mu"][k], mf["mu"][k]) <= SINGLE_UPDATE_ULP * 3, k
        assert _ulps(mr["nu"][k], mf["nu"][k]) <= SINGLE_UPDATE_ULP * 3, k


def test_fused_adamw_single_update_ulp_bound(rng):
    """One update from a WARM (nonzero-moment) shared state — the
    context where FMA contraction has something to perturb — within
    the documented single-update bound."""
    params = _tree(rng, dtypes=("f32", "f32", "f32"))
    moments = adamw_init(params)
    # Warm the moments with one reference step so they are nonzero.
    g0 = jax.tree_util.tree_map(
        lambda p: jnp.asarray(rng.normal(size=p.shape).astype(np.float32)),
        params,
    )
    params, moments = adamw_update(params, moments, g0, AdamWConfig(),
                                   step=0)
    g1 = jax.tree_util.tree_map(
        lambda p: jnp.asarray(rng.normal(size=p.shape).astype(np.float32)),
        params,
    )
    pr, mr = jax.jit(adamw_update, static_argnames=("config",))(
        params, moments, g1, AdamWConfig(), step=1)
    pf, mf = jax.jit(adamw_update, static_argnames=("config",))(
        params, moments, g1, AdamWConfig(fused=True), step=1)
    for k in params:
        assert _ulps(pr[k], pf[k]) <= SINGLE_UPDATE_ULP, k
        assert _ulps(mr["mu"][k], mf["mu"][k]) <= SINGLE_UPDATE_ULP, k
        assert _ulps(mr["nu"][k], mf["nu"][k]) <= SINGLE_UPDATE_ULP, k


def test_fused_adamw_zero1_keystone(mesh4):
    """The marquee consumer: ZeRO-1 (flat padded vector, one kernel
    launch) over 3 real train steps — fused trajectory within the
    documented relative bound of the reference, and the loss finite."""
    from distributed_machine_learning_tpu.cli.common import (
        init_model_and_state,
    )
    from distributed_machine_learning_tpu.models.vgg import VGGTest
    from distributed_machine_learning_tpu.parallel.zero1 import (
        make_zero1_train_step,
        shard_zero1_state,
    )
    from distributed_machine_learning_tpu.train.step import shard_batch

    model = VGGTest(use_bn=False)
    data_rng = np.random.default_rng(0)
    x = data_rng.integers(0, 256, (16, 32, 32, 3), dtype=np.uint8)
    y = data_rng.integers(0, 10, 16).astype(np.int32)
    flats = {}
    for fused in (False, True):
        st = init_model_and_state(model, config=AdamWConfig(fused=fused))
        z1, unravel, n_elems = shard_zero1_state(st, mesh4)
        step = make_zero1_train_step(model, mesh4, unravel, n_elems,
                                     augment=False, overlap=True)
        xs, ys = shard_batch(mesh4, jnp.asarray(x), jnp.asarray(y))
        for _ in range(3):
            z1, loss = step(z1, xs, ys)
        assert np.isfinite(float(loss))
        flats[fused] = np.asarray(jnp.asarray(z1.param_flat))
    denom = max(float(np.abs(flats[False]).max()), 1e-30)
    rel = float(np.abs(flats[True] - flats[False]).max()) / denom
    assert rel <= TRAJECTORY_REL, rel


# ---------------------------------------------------------------------------
# dmlcheck keeps its teeth through the kernel boundary.
# ---------------------------------------------------------------------------


def test_layer2_sees_through_fused_builds(mesh8):
    """The round-13 acceptance: donation (DML101), critical-path
    (DML102) and wire accounting (DML103) hold THROUGH the pallas_call
    boundary — fused ring step permute-only and fully donated (EF
    residual included), fused zero1 update gather-free with aliased
    moments, kernel build moving the exact same wire bytes — with zero
    new baseline entries."""
    from distributed_machine_learning_tpu.analysis.program_audit import (
        audit_ring_step,
        audit_ring_wire_accounting,
        audit_zero1_step,
    )

    ring = audit_ring_step(mesh8, codec_impl="pallas")
    assert [f.message for f in ring] == []
    zero1 = audit_zero1_step(mesh8, fused_update=True)
    assert [f.message for f in zero1] == []
    findings, table = audit_ring_wire_accounting(
        mesh8, 4096, schemes=("int8",), codec_impl="pallas",
        label="ring_all_reduce_pallas")
    assert [f.message for f in findings] == []
    assert table["int8"]["hlo_bytes"] == table["int8"]["static_bytes"]


def test_callback_walker_descends_pallas_kernels():
    """The jaxpr walker must see INSIDE a pallas_call: a debug_callback
    hidden in a kernel body is the same per-step host round-trip DML104
    exists for."""
    from jax.experimental import pallas as pl

    from distributed_machine_learning_tpu.analysis.program_audit import (
        audit_step_host_callbacks,
    )

    def chatty_kernel(x_ref, o_ref):
        pl.debug_print("x0 = {}", x_ref[0, 0])
        o_ref[...] = x_ref[...] * 2.0

    def step(x):
        return pl.pallas_call(
            chatty_kernel,
            out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
            interpret=True,
        )(x)

    x = jax.ShapeDtypeStruct((8, 128), jnp.float32)
    findings = audit_step_host_callbacks(step, x, label="seeded")
    assert findings, "debug print inside a pallas kernel must be flagged"

    def quiet(x):
        from distributed_machine_learning_tpu.ops.pallas.ring_codec import (
            encode_int8,
        )

        return encode_int8(x)

    assert audit_step_host_callbacks(
        quiet, jax.ShapeDtypeStruct((300,), jnp.float32), label="seeded"
    ) == []


# ---------------------------------------------------------------------------
# Deep variants: the kernel benches and the cross-length sweep, slow
# tier with in-test wall-clock caps (the 870s tier-1 budget stays
# protected; `pytest -m ""` runs them).
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_codec_bitwise_deep_sweep(mesh8):
    """Cross-length × cross-world sweep of the bitwise contract,
    capped: the sweep must not eat the slow tier either."""
    t0 = time.monotonic()
    for world in (2, 4, 8):
        from jax.sharding import Mesh

        mesh = Mesh(np.array(mesh8.devices).reshape(-1)[:world],
                    (BATCH_AXIS,))
        for length in (3, 129, 4096, 20011):
            seed_rng = np.random.default_rng(length)
            ox, rx = _ring_both(mesh, world, length, Int8Scheme("xla"),
                                seed_rng)
            seed_rng = np.random.default_rng(length)
            op, rp = _ring_both(mesh, world, length,
                                Int8Scheme("pallas"), seed_rng)
            np.testing.assert_array_equal(np.asarray(ox), np.asarray(op))
            np.testing.assert_array_equal(np.asarray(rx), np.asarray(rp))
    assert time.monotonic() - t0 < 420, "deep sweep blew its wall-clock cap"
