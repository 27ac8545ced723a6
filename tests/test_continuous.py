"""Continuous-batching engine (inference/continuous.py): token-for-token
parity with ``inference/generate.py``, EOS retirement + same-step
backfill, admission control against the paged pool, the swap fence,
and the regime lever (ISSUE 19).  All CPU; the tiny model keeps every
jitted program sub-second."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_machine_learning_tpu.inference.continuous import (
    ContinuousEngine,
    EngineConfig,
)
from distributed_machine_learning_tpu.inference.generate import (
    generate,
    make_serving_step,
)
from distributed_machine_learning_tpu.models.transformer import TransformerLM
from distributed_machine_learning_tpu.runtime.scheduler import (
    RegimeConfig,
    RegimeScheduler,
)
from distributed_machine_learning_tpu.telemetry.registry import (
    MetricsRegistry,
)

EOS_PROMPT = [9, 10, 11, 12]


@pytest.fixture(scope="module")
def lm():
    model = TransformerLM(
        vocab_size=32, d_model=16, n_layers=2, n_heads=4, n_kv_heads=2
    )
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return model, params


@pytest.fixture(scope="module")
def eos(lm):
    """An EOS id the fixture model really emits: the second new token
    of ``EOS_PROMPT``'s greedy stream.  Read from the model, because a
    constant goes stale whenever the tiny model's initialisation moves
    (13 was its attractor once; its streams no longer hold a 13)."""
    model, params = lm
    return _ref(model, params, EOS_PROMPT, 2)[-1]


def _ref(model, params, prompt, n, **kw):
    return np.asarray(
        generate(model, params, np.asarray([prompt], np.int32), n, **kw)
    )[0].tolist()


def test_engine_greedy_parity_ragged_batch(lm):
    """Every ragged request decoded by one shared-pool engine matches
    the dedicated-cache generate() token for token."""
    model, params = lm
    eng = ContinuousEngine(model, params, EngineConfig(
        max_lanes=3, block_size=4, num_blocks=32, max_len=32,
        levers=("latency",),
    ))
    prompts = [[1, 2, 3, 4], [5, 6, 7], [9, 10, 11, 12, 13],
               [2, 4, 6, 8], [3, 3, 3]]
    for i, p in enumerate(prompts):
        eng.submit(f"r{i}", list(p), max_new=6)
    done = {d["rid"]: d for d in eng.drain()}
    assert len(done) == len(prompts)
    for i, p in enumerate(prompts):
        assert done[f"r{i}"]["tokens"] == _ref(model, params, p, 6)
        assert done[f"r{i}"]["finish"] == "length"


def test_engine_mid_flight_admission_parity(lm):
    """Requests submitted while others are mid-decode join without
    disturbing anyone's stream — the whole point of iteration-level
    scheduling."""
    model, params = lm
    eng = ContinuousEngine(model, params, EngineConfig(
        max_lanes=2, block_size=4, num_blocks=32, max_len=32,
        levers=("latency",),
    ))
    eng.submit("a", [1, 2, 3, 4], max_new=8)
    for _ in range(3):
        eng.step()
    assert eng.in_flight() == 1
    eng.submit("b", [5, 6, 7], max_new=8)     # joins mid-flight
    done = {d["rid"]: d for d in eng.drain()}
    assert done["a"]["tokens"] == _ref(model, params, [1, 2, 3, 4], 8)
    assert done["b"]["tokens"] == _ref(model, params, [5, 6, 7], 8)


def test_engine_eos_retires_and_backfills_same_step(lm, eos):
    """EOS retirement frees the lane and the pool blocks, and a queued
    request backfills inside the same step() call."""
    model, params = lm
    eng = ContinuousEngine(model, params, EngineConfig(
        max_lanes=1, block_size=4, num_blocks=8, max_len=32,
        eos_id=eos, levers=("latency",),
    ))
    # EOS_PROMPT reaches EOS at its second new token; b's three never do.
    assert eos not in _ref(model, params, [1, 2, 3], 3)[3:]
    eng.submit("a", EOS_PROMPT, max_new=10)
    eng.submit("b", [1, 2, 3], max_new=3)
    # Step until a retires; b must be admitted in that same call.
    for _ in range(50):
        out = eng.step()
        if out:
            break
    assert out and out[0]["rid"] == "a"
    assert out[0]["finish"] == "eos"
    assert out[0]["tokens"][-1] == eos
    assert eng.in_flight() == 1            # b backfilled immediately
    assert eng.queued() == 0
    ref = _ref(model, params, EOS_PROMPT, 10, eos_id=eos)
    cut = ref.index(eos, len(EOS_PROMPT)) + 1
    assert out[0]["tokens"] == ref[:cut]
    done = eng.drain()
    assert done[0]["rid"] == "b"
    assert done[0]["tokens"] == _ref(model, params, [1, 2, 3], 3)


def test_serving_step_eos_parity_token_for_token(lm, eos):
    """The ISSUE 19 semantics-drift fix: make_serving_step(eos_id=...)
    matches generate() token for token — identical prefix through the
    first EOS, eos-padding after — while the eos-free path is
    unchanged."""
    model, params = lm
    prompts = [[1, 2, 3, 4], EOS_PROMPT, [5, 6, 7, 8]]
    step = make_serving_step(model, params, 10, eos_id=eos)
    outs = step([list(p) for p in prompts])
    for p, out in zip(prompts, outs):
        ref = _ref(model, params, p, 10)          # no-eos reference
        gen_ref = ref[len(p):]
        gen_out = out[len(p):]
        if eos in gen_ref:
            cut = gen_ref.index(eos) + 1
            assert gen_out[:cut] == gen_ref[:cut]
            assert all(t == eos for t in gen_out[cut:])
        else:
            assert gen_out == gen_ref
    # eos_id=None keeps the original scan program's output exactly.
    plain = make_serving_step(model, params, 10)
    outs0 = plain([list(p) for p in prompts])
    for p, out in zip(prompts, outs0):
        assert out == _ref(model, params, p, 10)


def test_engine_admission_control_queues_then_serves(lm):
    """A pool too small for all requests at once admits what fits,
    holds the rest queued, and serves everything as retirements free
    blocks — nothing dropped, everything exact."""
    model, params = lm
    # 6 blocks x 4 slots = 24 slots; each request needs 4+4=8 slots
    # (2 blocks), so at most 3 of the 5 fit concurrently.
    eng = ContinuousEngine(model, params, EngineConfig(
        max_lanes=4, block_size=4, num_blocks=6, max_len=8,
        levers=("latency",),
    ))
    prompts = {f"r{i}": [1 + i, 2 + i, 3, 4] for i in range(5)}
    for rid, p in prompts.items():
        eng.submit(rid, list(p), max_new=4)
    eng.step()
    assert eng.in_flight() == 3 and eng.queued() == 2
    done = {d["rid"]: d for d in eng.drain()}
    assert len(done) == 5
    for rid, p in prompts.items():
        assert done[rid]["tokens"] == _ref(model, params, p, 4)


def test_engine_shared_pool_beats_padded_footprint(lm):
    """Engine-level statement of the paged-memory win: lanes x max_len
    padding would need 4 x 32 = 128 slots; this pool has 48 — yet the
    same 4-wide ragged batch runs, because residency is per-token."""
    model, params = lm
    eng = ContinuousEngine(model, params, EngineConfig(
        max_lanes=4, block_size=4, num_blocks=12, max_len=32,
        levers=("latency",),
    ))
    pool_slots = 12 * 4
    padded_slots = 4 * 32
    assert pool_slots < padded_slots
    prompts = [[1, 2, 3], [4, 5, 6], [7, 8, 9], [2, 4, 6]]
    for i, p in enumerate(prompts):
        eng.submit(f"r{i}", list(p), max_new=5)
    eng.step()
    assert eng.in_flight() == 4            # all admitted concurrently
    done = {d["rid"]: d for d in eng.drain()}
    for i, p in enumerate(prompts):
        assert done[f"r{i}"]["tokens"] == _ref(model, params, p, 5)


def test_engine_swap_fence_refuses_in_flight(lm):
    """swap_params is the weight hot-swap fence: it refuses while any
    sequence is in flight, and after a drain the new weights serve
    with the new version stamped on completions."""
    model, params = lm
    params2 = model.init(
        jax.random.PRNGKey(7), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    eng = ContinuousEngine(model, params, EngineConfig(
        max_lanes=2, block_size=4, num_blocks=16, max_len=32,
        levers=("latency",),
    ), version=1)
    eng.submit("a", [1, 2, 3, 4], max_new=6)
    eng.step()
    assert eng.in_flight() == 1
    with pytest.raises(RuntimeError, match="in flight"):
        eng.swap_params(params2, version=2)
    eng.pause_admission()
    done = eng.drain()
    assert done and done[0]["version"] == 1
    assert done[0]["tokens"] == _ref(model, params, [1, 2, 3, 4], 6)
    eng.swap_params(params2, version=2)
    eng.resume_admission()
    eng.submit("b", [1, 2, 3, 4], max_new=6)
    done2 = eng.drain()
    assert done2[0]["version"] == 2
    assert done2[0]["tokens"] == _ref(model, params2, [1, 2, 3, 4], 6)
    # The two versions genuinely decode differently (the mixing test
    # in tests/test_deploy.py leans on this).
    assert done2[0]["tokens"] != done[0]["tokens"]


def test_engine_regime_lever_int8_parity(lm):
    """The throughput lever serves int8 weight-only decode; outputs
    match generate(quantize="int8") and the lever is recorded."""
    model, params = lm
    sched = RegimeScheduler(RegimeConfig(
        thin_width=0, wide_width=1, dwell_steps=1,
    ))
    eng = ContinuousEngine(model, params, EngineConfig(
        max_lanes=2, block_size=4, num_blocks=16, max_len=16,
        levers=("latency", "throughput"),
    ), scheduler=sched)
    eng.submit("q", [1, 2, 3, 4], max_new=4)
    done = eng.drain()
    assert done[0]["lever"] == "throughput"
    assert sched.flips >= 1
    assert done[0]["tokens"] == _ref(
        model, params, [1, 2, 3, 4], 4, quantize="int8"
    )


def test_engine_router_hint_overrides_local_scheduler(lm):
    model, params = lm
    sched = RegimeScheduler(RegimeConfig(
        thin_width=0, wide_width=1, dwell_steps=1,
    ))
    eng = ContinuousEngine(model, params, EngineConfig(
        max_lanes=2, block_size=4, num_blocks=16, max_len=16,
        levers=("latency", "throughput"),
    ), scheduler=sched)
    eng.note_lever("latency")
    eng.submit("q", [1, 2, 3, 4], max_new=3)
    done = eng.drain()
    assert done[0]["lever"] == "latency"
    with pytest.raises(ValueError):
        eng.note_lever("warp")


def test_engine_telemetry_and_invariants(lm):
    """Histograms/gauges land in the registry and the allocator's
    invariants hold after a full serve cycle."""
    model, params = lm
    reg = MetricsRegistry()
    eng = ContinuousEngine(model, params, EngineConfig(
        max_lanes=2, block_size=4, num_blocks=16, max_len=16,
        levers=("latency",),
    ), registry=reg)
    for i in range(3):
        eng.submit(f"r{i}", [1 + i, 2, 3], max_new=4)
    eng.drain()
    eng.allocator.check_invariants()
    assert eng.allocator.free_blocks() == 16
    snap = reg.snapshot()
    hists = {m["name"]: m for m in snap["histograms"]}
    for name in ("engine_prefill_s", "engine_decode_s", "engine_e2e_s"):
        assert hists[name]["count"] == 3, name
    counters = {m["name"]: m["value"] for m in snap["counters"]}
    assert counters["engine_requests_total"] == 3
    assert counters["engine_tokens_total"] == 12


def test_engine_submit_validation(lm):
    model, params = lm
    eng = ContinuousEngine(model, params, EngineConfig(
        max_lanes=1, block_size=4, num_blocks=8, max_len=16,
        levers=("latency",),
    ))
    with pytest.raises(ValueError, match="empty"):
        eng.submit("a", [])
    with pytest.raises(ValueError, match="max_len"):
        eng.submit("a", list(range(1, 14)), max_new=8)
    with pytest.raises(ValueError, match="max_new"):
        eng.submit("a", [1, 2], max_new=0)
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        ContinuousEngine(
            model.clone(kv_cache_dtype=jnp.int8), params,
            EngineConfig(levers=("latency",)),
        )
