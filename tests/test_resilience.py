"""The self-healing runtime, end to end.

Detection (runtime/resilience.py): the watchdog must catch a stalled
step, the preemption handler must turn SIGTERM into a clean
stop-at-step-boundary, and the training loop must honor both.

Recovery (the skip/retry/restart ladder): the non-finite-gradient guard
skips an update without touching state, the retrying data path survives
iterator deaths, and the supervisor (runtime/supervisor.py) restores the
newest complete checkpoint after stalls/crashes/kills.  The keystone is
the chaos test: one supervised run with a NaN gradient, a loader raise,
a stall, and a kill-mid-checkpoint injected must finish at the same step
count with BIT-IDENTICAL params to a fault-free run of the same seed
(minus the guard-skipped batch) — and every injected fault class must
show up in the resilience counters, because a recovery nobody can see is
indistinguishable from a fault that never fired."""

import os
import signal
import time

import numpy as np
import pytest

from distributed_machine_learning_tpu.data.retry import (
    RetryPolicy,
    retry_batches,
)
from distributed_machine_learning_tpu.runtime.faults import (
    FaultEvents,
    FaultInjector,
    InjectedFault,
    InjectedKill,
)
from distributed_machine_learning_tpu.runtime.resilience import (
    PreemptionHandler,
    Watchdog,
)
from distributed_machine_learning_tpu.runtime.supervisor import (
    RaisingWatchdog,
    StallError,
    run_attempts,
    supervised_train,
)


def test_watchdog_fires_on_stall():
    fired = []
    with Watchdog(timeout_s=0.2, on_stall=fired.append, poll_s=0.05) as wd:
        time.sleep(0.6)
    assert wd.stalled
    assert fired and fired[0] >= 0.2


def test_watchdog_beats_prevent_stall():
    fired = []
    with Watchdog(timeout_s=0.4, on_stall=fired.append, poll_s=0.05) as wd:
        for _ in range(6):
            time.sleep(0.1)
            wd.beat()
    assert not wd.stalled
    assert not fired


def test_watchdog_rejects_bad_timeout():
    with pytest.raises(ValueError):
        Watchdog(timeout_s=0)


def test_preemption_handler_catches_sigterm():
    with PreemptionHandler() as handler:
        assert not handler()
        os.kill(os.getpid(), signal.SIGTERM)
        # Signal delivery is synchronous-enough on the main thread: the
        # handler runs before the next bytecode boundary completes.
        time.sleep(0.05)
        assert handler()
    # Outside the context, the previous disposition is restored.
    assert signal.getsignal(signal.SIGTERM) not in (handler._handle,)


def test_preemption_restores_previous_handler():
    prev = signal.getsignal(signal.SIGTERM)
    h = PreemptionHandler().install()
    h.uninstall()
    assert signal.getsignal(signal.SIGTERM) is prev


def test_train_epoch_stops_at_boundary_and_beats_watchdog(rng):
    # A tiny real train loop: stop requested after the 3rd step must end
    # the epoch with exactly 3 updates applied and consistent state.
    from distributed_machine_learning_tpu.cli.common import init_model_and_state
    from distributed_machine_learning_tpu.models.vgg import VGGTest
    from distributed_machine_learning_tpu.train.loop import train_epoch
    from distributed_machine_learning_tpu.train.step import make_train_step

    model = VGGTest(use_bn=False)
    state = init_model_and_state(model)
    step = make_train_step(model, augment=False)

    def batches():
        while True:
            yield (rng.integers(0, 256, (2, 32, 32, 3)).astype(np.uint8),
                   rng.integers(0, 10, 2).astype(np.int32))

    calls = {"n": 0}

    def stop():
        return calls["n"] >= 3

    real_step = step

    def counting_step(s, x, y):
        calls["n"] += 1
        return real_step(s, x, y)

    wd = Watchdog(timeout_s=60).start()
    state, _ = train_epoch(
        counting_step, state, batches(), max_iters=10, stop=stop,
        watchdog=wd,
    )
    wd.stop()
    assert calls["n"] == 3
    assert int(state.step) == 3
    assert not wd.stalled


def test_agree_stop_single_process():
    from distributed_machine_learning_tpu.runtime.resilience import agree_stop

    assert agree_stop(True) is True
    assert agree_stop(False) is False


def test_periodic_agree_stop_single_process_is_immediate():
    from distributed_machine_learning_tpu.runtime.resilience import (
        periodic_agree_stop,
    )

    flag = {"v": False}
    stop = periodic_agree_stop(lambda: flag["v"], every=10)
    assert not stop()
    flag["v"] = True
    # Single-process forces every=1: honored on the very next poll,
    # and sticky afterwards.
    assert stop()
    flag["v"] = False
    assert stop()


def test_periodic_agree_stop_validates_every():
    import pytest

    from distributed_machine_learning_tpu.runtime.resilience import (
        periodic_agree_stop,
    )

    with pytest.raises(ValueError):
        periodic_agree_stop(lambda: False, every=0)


# ---------------------------------------------------------------------------
# Watchdog suspension + stall escalation (runtime/supervisor.py)
# ---------------------------------------------------------------------------


def test_watchdog_suspend_stops_the_clock():
    # A checkpoint save / eval longer than the timeout must NOT be
    # declared a stall — under --resume auto that would burn a restart
    # per save on a perfectly healthy run.
    fired = []
    with Watchdog(timeout_s=0.3, on_stall=fired.append, poll_s=0.05) as wd:
        with wd.suspend():
            time.sleep(0.6)
        time.sleep(0.1)  # post-suspend: the exit beat granted a window
    assert not wd.stalled
    assert not fired


def test_watchdog_suspend_is_reentrant():
    with Watchdog(timeout_s=0.2, poll_s=0.05) as wd:
        with wd.suspend(), wd.suspend():
            time.sleep(0.45)
    assert not wd.stalled


def test_raising_watchdog_escalates_at_the_next_beat():
    events = FaultEvents()
    wd = RaisingWatchdog(0.2, events, poll_s=0.05).start()
    try:
        wd.beat()  # healthy beat passes
        time.sleep(0.5)
        with pytest.raises(StallError):
            wd.beat()  # first beat after the declared stall raises
    finally:
        wd.stop()
    assert events.stalls == 1


def test_train_epoch_entry_beat_refreshes_a_stale_clock():
    # The loop beats once BEFORE pulling batch 0, so a slow setup phase
    # (compile, restore) can't eat the first batch's timeout window.
    from distributed_machine_learning_tpu.train.loop import train_epoch

    fired = []
    wd = Watchdog(timeout_s=0.3, on_stall=fired.append, poll_s=0.05).start()
    wd._last_beat -= 10.0  # pretend setup burned far more than the window

    def slow_first_batch():
        time.sleep(0.15)  # < timeout: fine IF the window was refreshed
        yield from ()

    class S:
        step = 0

    out, _ = train_epoch(
        lambda s, x, y: (s, 0.0), S(), slow_first_batch(), max_iters=1,
        watchdog=wd,
    )
    wd.stop()
    assert not fired and not wd.stalled


def test_loader_hanging_on_first_batch_is_caught_as_a_stall():
    from distributed_machine_learning_tpu.train.loop import train_epoch

    fired = []
    wd = Watchdog(timeout_s=0.2, on_stall=fired.append, poll_s=0.05).start()

    def hanging():
        time.sleep(0.6)  # past the timeout: a batch-0 hang, not setup
        yield from ()

    class S:
        step = 0

    train_epoch(lambda s, x, y: (s, 0.0), S(), hanging(), max_iters=1,
                watchdog=wd)
    wd.stop()
    assert wd.stalled and fired


@pytest.mark.parametrize("placed", [False, True])
def test_train_epoch_until_step_counts_applied_updates(placed):
    # until_step is an APPLIED-updates target: a step that leaves the
    # counter unchanged (the guard's skip) consumes a batch but does not
    # count, so the epoch pulls further data to reach the target.
    from distributed_machine_learning_tpu.train.loop import train_epoch

    class S:
        def __init__(self, step):
            self.step = step

    consumed, order = [], []

    def batches():
        for i in range(100):
            consumed.append(i)
            order.append(("fetch", i))
            yield (i, i)

    def place(x, y):
        order.append(("place", x))
        return x, y

    def step_skipping_batch_1(s, x, y):
        order.append(("step", x))
        return (S(s.step) if x == 1 else S(s.step + 1)), 0.0

    events = FaultEvents()
    out, _ = train_epoch(
        step_skipping_batch_1, S(0), batches(), max_iters=10**9,
        until_step=3, events=events, place_batch=place if placed else None,
    )
    assert out.step == 3
    assert consumed == [0, 1, 2, 3]  # four batches for three updates
    assert events.skipped_steps == 1
    # Whether a further batch is wanted depends on the step's result, so
    # under until_step nothing is fetched (or placed) ahead of it.
    per_batch = ["fetch", "place", "step"] if placed else ["fetch", "step"]
    assert order == [(what, i) for i in range(4) for what in per_batch]


def test_train_epoch_events_carry_the_counter_from_step_to_step(monkeypatch):
    # Without until_step the loop runs one batch ahead; the skip
    # accounting then reads the counter once before the loop and once a
    # step: step k's "after" is step k+1's "before".
    import jax

    from distributed_machine_learning_tpu.train.loop import train_epoch

    class S:
        def __init__(self, step, loss_scale):
            self.step, self.loss_scale = step, loss_scale

    def step(s, x, y):  # skips batches 1 and 2, halving the scale
        if x in (1, 2):
            return S(s.step, s.loss_scale / 2), 0.0
        return S(s.step + 1, s.loss_scale * (2 if x == 4 else 1)), 0.0

    reads = []
    get = jax.device_get
    monkeypatch.setattr(jax, "device_get",
                        lambda x: reads.append(x) or get(x))
    events = FaultEvents()
    out, _ = train_epoch(step, S(7, 8.0), [(i, i) for i in range(6)],
                         max_iters=10, events=events,
                         loss_print_every=10**9)
    assert out.step == 7 + 4
    assert events.skipped_steps == 2
    assert events.scaler_backoffs == 2 and events.scaler_growths == 1
    assert reads == [7, 8, 8, 8, 9, 10, 11]  # one before, one a step


def test_train_epoch_stop_while_a_batch_is_ahead_trains_it():
    # The stop predicate is polled once a fetched batch, as before; the
    # fetch now runs under the step in flight, so a stop seen at the fetch
    # of batch k+1 still finishes step k — the batch already placed — and
    # discards only the batch just fetched, never a placed one.
    from distributed_machine_learning_tpu.train.loop import train_epoch

    class S:
        step = 0

    seen = {"fetched": [], "placed": [], "trained": [], "polls": 0}

    def batches():
        for i in range(10):
            seen["fetched"].append(i)
            yield (i, i)

    def place(x, y):
        seen["placed"].append(x)
        return x, y

    def step(s, x, y):
        seen["trained"].append(x)
        return s, 0.0

    def stop():
        seen["polls"] += 1
        return seen["polls"] > 3  # true at the fourth fetched batch

    wd = Watchdog(timeout_s=60).start()
    train_epoch(step, S(), batches(), place_batch=place, max_iters=10,
                stop=stop, watchdog=wd)
    wd.stop()
    assert seen["fetched"] == [0, 1, 2, 3]
    assert seen["placed"] == seen["trained"] == [0, 1, 2]
    assert seen["polls"] == 4 and not wd.stalled


# ---------------------------------------------------------------------------
# Fault injector (runtime/faults.py)
# ---------------------------------------------------------------------------


def test_fault_spec_parses_all_classes():
    inj = FaultInjector.parse("nan@2,raise@4,stall@7:2.5,kill_ckpt@1")
    assert inj.pending() == ["nan@2", "raise@4", "stall@7:2.5",
                             "kill_ckpt@1"]


@pytest.mark.parametrize("spec", [
    "boom@2",          # unknown kind
    "nan",             # no @step
    "nan@x",           # non-integer step
    "nan@-1",          # negative step
    "kill_ckpt@0",     # save ordinals are 1-based
    "kill_ckpt@1:now",  # only :exit is a valid kill arg
    "stall@2:soon",    # stall arg must be float seconds
])
def test_fault_spec_rejects_bad_entries(spec):
    with pytest.raises(ValueError):
        FaultInjector.parse(spec)


def test_fault_spec_random_steps_are_seed_deterministic():
    a = FaultInjector.parse("nan@?,raise@?", seed=5, horizon=20)
    b = FaultInjector.parse("nan@?,raise@?", seed=5, horizon=20)
    c = FaultInjector.parse("nan@?,raise@?", seed=6, horizon=20)
    assert a.pending() == b.pending()
    assert a.pending() != c.pending()  # (astronomically unlikely to tie)


def test_env_var_spec_and_off_by_default(monkeypatch):
    monkeypatch.delenv("DML_FAULTS", raising=False)
    assert FaultInjector.from_flags(None) is None  # OFF is the default
    monkeypatch.setenv("DML_FAULTS", "nan@3")
    inj = FaultInjector.from_flags(None)
    assert inj is not None and inj.pending() == ["nan@3"]
    # An explicit spec wins over the env var.
    assert FaultInjector.from_flags("raise@1").pending() == ["raise@1"]


def _uint8_batches(n, start=0):
    r = np.random.default_rng(0)
    return [(r.integers(0, 256, (2, 8, 8, 3)).astype(np.uint8),
             r.integers(0, 10, 2).astype(np.int32)) for _ in range(start, n)]


def test_injector_nan_poisons_once_and_latches():
    inj = FaultInjector.parse("nan@1")
    out = list(inj.wrap_batches(_uint8_batches(3)))
    assert np.isnan(out[1][0]).all() and not np.isnan(
        out[0][0].astype(np.float32)).any()
    # A replay crossing the same index must NOT re-poison: the fault
    # fired and recovery is supposed to make progress past it.
    replay = list(inj.wrap_batches(_uint8_batches(3)))
    assert replay[1][0].dtype == np.uint8


def test_injector_raise_fires_at_absolute_index():
    inj = FaultInjector.parse("raise@5")
    events = FaultEvents()
    # start=4: the wrapper sees local index 1 == absolute index 5.
    it = inj.wrap_batches(iter(_uint8_batches(3)), events, start=4)
    next(it)
    with pytest.raises(InjectedFault):
        next(it)


def test_injector_refuses_to_poison_token_batches():
    inj = FaultInjector.parse("nan@0")
    tokens = (np.zeros((2, 8), np.int32), np.zeros((2, 8), np.int32))
    with pytest.raises(TypeError):
        next(inj.wrap_batches(iter([tokens])))


def test_mid_save_hook_kills_on_its_ordinal():
    inj = FaultInjector.parse("kill_ckpt@2")
    events = FaultEvents()
    hook = inj.mid_save_hook(events)
    hook()  # save #1: survives
    with pytest.raises(InjectedKill):
        hook()  # save #2: dies
    hook()  # fired-once: save #3 survives
    assert events.ckpt_kills == 1


# ---------------------------------------------------------------------------
# Retrying data path (data/retry.py)
# ---------------------------------------------------------------------------


def _flaky_factory(fail_at, times):
    """A seekable stream 0..5 whose batch ``fail_at`` raises its first
    ``times`` deliveries."""
    fails = {"left": times}

    def make(start):
        def gen():
            for i in range(start, 6):
                if i == fail_at and fails["left"] > 0:
                    fails["left"] -= 1
                    raise OSError(f"transient failure at {i}")
                yield i
        return gen()

    return make


def test_retry_recreates_the_source_at_the_failing_index():
    events = FaultEvents()
    got = list(retry_batches(
        _flaky_factory(3, times=1), RetryPolicy(backoff_s=0.0), events))
    assert got == [0, 1, 2, 3, 4, 5]  # nothing lost, nothing duplicated
    assert events.loader_retries == 1 and events.skipped_batches == 0


def test_retry_skips_a_persistently_bad_batch():
    events = FaultEvents()
    got = list(retry_batches(
        _flaky_factory(2, times=10),
        RetryPolicy(max_retries=5, max_attempts_per_batch=2, backoff_s=0.0),
        events,
    ))
    assert got == [0, 1, 3, 4, 5]  # batch 2 skipped, stream continues
    assert events.skipped_batches == 1 and events.loader_retries == 2


def test_retry_exhaustion_reraises():
    def always_dead(start):
        raise OSError("storage is gone")
        yield  # pragma: no cover

    with pytest.raises(OSError):
        list(retry_batches(always_dead, RetryPolicy(max_retries=2,
                                                    backoff_s=0.0)))


def test_retry_never_swallows_keyboard_interrupt():
    def interrupted(start):
        def gen():
            raise KeyboardInterrupt
            yield  # pragma: no cover
        return gen()

    with pytest.raises(KeyboardInterrupt):
        list(retry_batches(interrupted, RetryPolicy(max_retries=5,
                                                    backoff_s=0.0)))


def test_retry_policy_validates():
    with pytest.raises(ValueError):
        RetryPolicy(max_retries=-1)
    with pytest.raises(ValueError):
        RetryPolicy(max_attempts_per_batch=0)
    with pytest.raises(ValueError):
        RetryPolicy(backoff_mult=0.5)


class _FlakyDataset:
    """images/labels-style dataset whose array access fails N times."""

    def __init__(self, n=8, fail_times=0):
        r = np.random.default_rng(3)
        self._images = r.integers(0, 256, (n, 8, 8, 3)).astype(np.uint8)
        self.labels = r.integers(0, 10, n).astype(np.int32)
        self._fails = fail_times

    def __len__(self):
        return len(self.labels)

    @property
    def images(self):
        if self._fails > 0:
            self._fails -= 1
            raise OSError("transient dataset read")
        return self._images


def test_batch_loader_retry_recovers_a_transient_fault():
    from distributed_machine_learning_tpu.data.loader import BatchLoader

    loader = BatchLoader(_FlakyDataset(fail_times=1), batch_size=4,
                         retry=RetryPolicy(backoff_s=0.0))
    batches = list(loader)
    assert len(batches) == 2 and batches[0][0].shape == (4, 8, 8, 3)


@pytest.mark.parametrize("prefetch", [0, 2])
def test_batch_loader_surfaces_unrecovered_faults(prefetch):
    # Without the retry layer a producer death must RAISE in the
    # consumer, never leave the training loop blocked on an empty queue.
    from distributed_machine_learning_tpu.data.loader import BatchLoader

    loader = BatchLoader(_FlakyDataset(fail_times=99), batch_size=4,
                         prefetch=prefetch)
    with pytest.raises(OSError):
        list(loader)


# ---------------------------------------------------------------------------
# run_attempts (the supervisor's restart policy)
# ---------------------------------------------------------------------------


def test_run_attempts_retries_then_succeeds():
    events = FaultEvents()

    def attempt(i):
        if i < 2:
            raise RuntimeError(f"attempt {i} died")
        return "done"

    assert run_attempts(attempt, max_restarts=3, events=events) == "done"
    assert events.restarts == 2


def test_run_attempts_gives_up_loudly():
    def attempt(i):
        raise RuntimeError("always dead")

    with pytest.raises(RuntimeError):
        run_attempts(attempt, max_restarts=2)


def test_run_attempts_never_retries_keyboard_interrupt():
    calls = []

    def attempt(i):
        calls.append(i)
        raise KeyboardInterrupt

    with pytest.raises(KeyboardInterrupt):
        run_attempts(attempt, max_restarts=5)
    assert calls == [0]


# ---------------------------------------------------------------------------
# Non-finite-gradient guard (train/step.py) + resilience summary
# ---------------------------------------------------------------------------


def _cnn_batch(i, n=2):
    """Deterministic batch ``i`` of the chaos stream — cursor-keyed, so
    replays after a restart regenerate the identical arrays."""
    r = np.random.default_rng(1000 + i)
    return (r.integers(0, 256, (n, 32, 32, 3)).astype(np.uint8),
            r.integers(0, 10, n).astype(np.int32))


def _nan_batch(n=2):
    return (np.full((n, 32, 32, 3), np.nan, np.float32),
            np.zeros(n, np.int32))


@pytest.fixture(scope="module")
def guarded_cnn(tmp_path_factory):
    """A guarded VGGTest step with every signature the chaos run hits
    pre-compiled (uint8 fresh state, poisoned float32, restored state) —
    the tests use second-scale watchdog timeouts, and an XLA compile
    landing mid-run would read as a stall.  Real runs size the timeout
    in minutes, far above any compile."""
    import shutil

    from distributed_machine_learning_tpu.cli.common import (
        init_model_and_state,
    )
    from distributed_machine_learning_tpu.models.vgg import VGGTest
    from distributed_machine_learning_tpu.train.checkpoint import (
        restore_checkpoint,
        save_checkpoint,
    )
    from distributed_machine_learning_tpu.train.step import make_train_step

    model = VGGTest(use_bn=False)
    step = make_train_step(model, augment=False, guard_nonfinite=True)
    step(init_model_and_state(model), *_cnn_batch(0))
    step(init_model_and_state(model), *_nan_batch())
    warm_dir = tmp_path_factory.mktemp("warm_ckpt")
    path = save_checkpoint(warm_dir, init_model_and_state(model))
    restored = restore_checkpoint(
        path, abstract_state=init_model_and_state(model)
    )
    step(restored, *_cnn_batch(0))
    shutil.rmtree(warm_dir, ignore_errors=True)
    return model, step


def test_guard_skips_the_update_and_preserves_state(guarded_cnn):
    from distributed_machine_learning_tpu.cli.common import (
        init_model_and_state,
    )

    model, step = guarded_cnn
    state = init_model_and_state(model)
    import jax
    params_before = jax.device_get(state.params)
    new_state, loss = step(state, *_nan_batch())
    assert int(jax.device_get(new_state.step)) == 0  # step NOT counted
    assert not np.isfinite(float(loss))  # the blowup is still observable
    for a, b in zip(jax.tree_util.tree_leaves(params_before),
                    jax.tree_util.tree_leaves(
                        jax.device_get(new_state.params))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # The next good batch trains normally from the preserved state.
    new_state, loss = step(new_state, *_cnn_batch(0))
    assert int(jax.device_get(new_state.step)) == 1
    assert np.isfinite(float(loss))


def test_unguarded_step_is_poisoned_by_the_same_batch():
    # The contrast case: guard off (the default — reference parity must
    # not mask numeric bugs) lets one NaN batch destroy the params.
    import jax

    from distributed_machine_learning_tpu.cli.common import (
        init_model_and_state,
    )
    from distributed_machine_learning_tpu.models.vgg import VGGTest
    from distributed_machine_learning_tpu.train.step import make_train_step

    model = VGGTest(use_bn=False)
    step = make_train_step(model, augment=False)
    state, _ = step(init_model_and_state(model), *_nan_batch())
    assert int(jax.device_get(state.step)) == 1  # counted as if fine
    leaves = jax.tree_util.tree_leaves(jax.device_get(state.params))
    assert any(np.isnan(np.asarray(l)).any() for l in leaves)


def test_resilience_summary_renders_counters():
    from distributed_machine_learning_tpu.utils.summary import (
        resilience_summary,
    )

    events = FaultEvents()
    assert "clean run" in resilience_summary(events)
    events.skipped_steps = 2
    events.restarts = 1
    text = resilience_summary(events)
    assert "non-finite" in text and "restarts" in text
    assert "Total events" in text and "3" in text


# ---------------------------------------------------------------------------
# Dynamic loss scaling (train/lm_step.py)
# ---------------------------------------------------------------------------


def _tiny_lm():
    from distributed_machine_learning_tpu.models.transformer import (
        TransformerLM,
    )

    return TransformerLM(vocab_size=32, d_model=16, n_layers=1, n_heads=2)


def _lm_batch(rng=None):
    r = rng or np.random.default_rng(11)
    return (r.integers(0, 32, (2, 8)).astype(np.int32),
            r.integers(0, 32, (2, 8)).astype(np.int32))


@pytest.fixture(scope="module")
def scaled_lm_step():
    from distributed_machine_learning_tpu.train.lm_step import (
        make_lm_train_step,
    )

    model = _tiny_lm()
    return model, make_lm_train_step(model, dynamic_scale=True)


def test_dynamic_scale_doubles_after_growth_interval(scaled_lm_step):
    import jax

    from distributed_machine_learning_tpu.train.lm_step import (
        init_lm_state,
        with_dynamic_scale,
    )

    model, step = scaled_lm_step
    s = with_dynamic_scale(init_lm_state(model), init_scale=2.0**10,
                           growth_interval=2)
    toks, tgts = _lm_batch()
    s, loss = step(s, toks, tgts)
    assert float(s.loss_scale) == 2.0**10 and int(s.good_steps) == 1
    assert np.isfinite(float(loss))  # reported loss is UNSCALED
    s, _ = step(s, toks, tgts)
    assert float(s.loss_scale) == 2.0**11  # doubled after 2 good steps
    assert int(s.good_steps) == 0  # growth resets the streak
    assert int(jax.device_get(s.step)) == 2


def test_dynamic_scale_halves_and_skips_on_overflow(scaled_lm_step):
    import jax
    import jax.numpy as jnp

    from distributed_machine_learning_tpu.train.lm_step import (
        init_lm_state,
        with_dynamic_scale,
    )

    model, step = scaled_lm_step
    inner = init_lm_state(model)
    # Poison one parameter leaf: the gradients are then non-finite, the
    # overflow path every bf16 run eventually hits.
    leaves, treedef = jax.tree_util.tree_flatten(inner.params)
    leaves[0] = jnp.full_like(leaves[0], jnp.nan)
    inner = inner.replace(params=jax.tree_util.tree_unflatten(treedef,
                                                              leaves))
    s = with_dynamic_scale(inner, init_scale=2.0**10, growth_interval=2)
    s2, loss = step(s, *_lm_batch())
    assert int(jax.device_get(s2.step)) == 0  # update skipped
    assert float(s2.loss_scale) == 2.0**9  # halved
    assert int(s2.good_steps) == 0
    assert not np.isfinite(float(loss))


def test_dynamic_scale_clamps_at_one(scaled_lm_step):
    import jax
    import jax.numpy as jnp

    from distributed_machine_learning_tpu.train.lm_step import (
        init_lm_state,
        with_dynamic_scale,
    )

    model, step = scaled_lm_step
    inner = init_lm_state(model)
    leaves, treedef = jax.tree_util.tree_flatten(inner.params)
    leaves[0] = jnp.full_like(leaves[0], jnp.inf)
    inner = inner.replace(params=jax.tree_util.tree_unflatten(treedef,
                                                              leaves))
    s = with_dynamic_scale(inner, init_scale=1.0, growth_interval=2)
    s2, _ = step(s, *_lm_batch())
    assert float(s2.loss_scale) == 1.0  # never collapses below 1


def test_with_dynamic_scale_validates():
    from distributed_machine_learning_tpu.train.lm_step import (
        init_lm_state,
        with_dynamic_scale,
    )

    inner = init_lm_state(_tiny_lm())
    with pytest.raises(ValueError):
        with_dynamic_scale(inner, init_scale=0.5)
    with pytest.raises(ValueError):
        with_dynamic_scale(inner, growth_interval=0)


def test_scaler_events_are_counted_by_the_loop(scaled_lm_step):
    import jax
    import jax.numpy as jnp

    from distributed_machine_learning_tpu.train.lm_step import (
        init_lm_state,
        with_dynamic_scale,
    )
    from distributed_machine_learning_tpu.train.loop import train_epoch

    model, step = scaled_lm_step
    inner = init_lm_state(model)
    leaves, treedef = jax.tree_util.tree_flatten(inner.params)
    leaves[0] = jnp.full_like(leaves[0], jnp.nan)
    inner = inner.replace(params=jax.tree_util.tree_unflatten(treedef,
                                                              leaves))
    s = with_dynamic_scale(inner, init_scale=2.0**10, growth_interval=2)
    events = FaultEvents()
    s, _ = train_epoch(step, s, [_lm_batch()], max_iters=1, events=events,
                       loss_print_every=10**9)
    assert events.skipped_steps == 1 and events.scaler_backoffs == 1


# ---------------------------------------------------------------------------
# The supervised run (runtime/supervisor.py::supervised_train)
# ---------------------------------------------------------------------------


def _make_batches(cursor):
    """Cursor-keyed batch factory over the deterministic chaos stream."""
    def gen():
        i = cursor
        while i < 64:
            yield _cnn_batch(i)
            i += 1
    return gen()


def _params_equal(a, b):
    import jax

    return all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(jax.tree_util.tree_leaves(jax.device_get(a)),
                        jax.tree_util.tree_leaves(jax.device_get(b)))
    )


def test_supervised_fault_free_run_is_exact(guarded_cnn, tmp_path):
    import jax

    from distributed_machine_learning_tpu.cli.common import (
        init_model_and_state,
    )
    from distributed_machine_learning_tpu.train.loop import train_epoch

    model, step = guarded_cnn
    events = FaultEvents()
    final = supervised_train(
        step, init_model_and_state(model), _make_batches,
        target_steps=5, ckpt_dir=tmp_path, save_every=2, events=events,
    )
    assert int(jax.device_get(final.step)) == 5
    assert events.total() == 0  # a clean run reports a clean bill
    plain = init_model_and_state(model)
    plain, _ = train_epoch(step, plain, [_cnn_batch(i) for i in range(5)],
                           max_iters=10**9, loss_print_every=10**9)
    assert _params_equal(final.params, plain.params)


@pytest.mark.faultinject
def test_chaos_run_matches_fault_free_run(guarded_cnn, tmp_path):
    """The acceptance keystone: all four fault classes in ONE supervised
    run — kill during the first save, NaN gradient at batch 4, loader
    raise at batch 6, stall past the watchdog at batch 8 — and the run
    still finishes at the target step count with bit-identical params to
    the fault-free trajectory over the same stream minus the one
    guard-skipped batch, with every fault class visible in the
    counters."""
    import jax

    from distributed_machine_learning_tpu.cli.common import (
        init_model_and_state,
    )
    from distributed_machine_learning_tpu.train.checkpoint import (
        latest_checkpoint,
    )
    from distributed_machine_learning_tpu.train.loop import train_epoch

    model, step = guarded_cnn
    events = FaultEvents()
    injector = FaultInjector.parse("kill_ckpt@1,nan@4,raise@6,stall@8:4.0")
    final = supervised_train(
        step, init_model_and_state(model), _make_batches,
        target_steps=10, ckpt_dir=tmp_path, save_every=3, max_restarts=4,
        events=events, watchdog_timeout=1.5, injector=injector,
        retry=RetryPolicy(max_retries=3), keep_last_n=2,
    )
    assert int(jax.device_get(final.step)) == 10

    # Every injected fault class is observable in the counters.
    assert events.ckpt_kills == 1     # kill_ckpt@1
    assert events.skipped_steps == 1  # nan@4
    assert events.loader_retries >= 1  # raise@6
    assert events.stalls >= 1         # stall@8
    assert events.restarts >= 2       # the kill and the stall both restart

    # Bit-identical to the fault-free run of the same seed, minus the
    # guard-skipped batch (index 4 was consumed but its update skipped).
    clean = init_model_and_state(model)
    applied = [_cnn_batch(i) for i in range(11) if i != 4]
    clean, _ = train_epoch(step, clean, applied, max_iters=10**9,
                           loss_print_every=10**9)
    assert _params_equal(final.params, clean.params)

    # keep_last_n GC ran and the newest complete checkpoint survived.
    latest = latest_checkpoint(tmp_path)
    assert latest is not None and latest.endswith("step_10")
    complete = [d for d in os.listdir(tmp_path)
                if os.path.exists(os.path.join(tmp_path, d,
                                               "sgd_config.json"))]
    assert len(complete) <= 2


@pytest.mark.faultinject
def test_supervised_preemption_checkpoints_and_resumes(guarded_cnn,
                                                       tmp_path):
    import jax

    from distributed_machine_learning_tpu.cli.common import (
        init_model_and_state,
    )
    from distributed_machine_learning_tpu.train.loop import train_epoch

    model, step = guarded_cnn
    events = FaultEvents()
    polls = {"n": 0}

    def stop():  # "preemption" arrives after the first save boundary
        polls["n"] += 1
        return polls["n"] > 3

    partial = supervised_train(
        step, init_model_and_state(model), _make_batches,
        target_steps=8, ckpt_dir=tmp_path, save_every=3, events=events,
        stop=stop,
    )
    stopped_at = int(jax.device_get(partial.step))
    assert 0 < stopped_at < 8
    assert events.preemptions == 1

    # A fresh supervised run auto-resumes from the preemption checkpoint
    # and lands exactly where an uninterrupted run would have.
    final = supervised_train(
        step, init_model_and_state(model), _make_batches,
        target_steps=8, ckpt_dir=tmp_path, save_every=3,
    )
    assert int(jax.device_get(final.step)) == 8
    clean = init_model_and_state(model)
    clean, _ = train_epoch(step, clean, [_cnn_batch(i) for i in range(8)],
                           max_iters=10**9, loss_print_every=10**9)
    assert _params_equal(final.params, clean.params)


def test_supervised_train_validates():
    with pytest.raises(ValueError):
        supervised_train(None, None, _make_batches, target_steps=0,
                         ckpt_dir="/tmp/x")
    with pytest.raises(ValueError):
        supervised_train(None, None, _make_batches, target_steps=1,
                         ckpt_dir="/tmp/x", save_every=0)
    with pytest.raises(ValueError):
        run_attempts(lambda i: None, max_restarts=-1)


# ---------------------------------------------------------------------------
# CLI wiring (--resume auto, --faults, --guard-nonfinite, ...)
# ---------------------------------------------------------------------------


def test_cli_flags_validate():
    from distributed_machine_learning_tpu.cli.common import (
        make_flag_parser,
        parse_flags,
    )

    parser = make_flag_parser("test")
    assert parse_flags(parser, []).resume is None
    base = ["--ckpt-dir", "/tmp/x"]
    assert parse_flags(parser, base + ["--resume"]).resume == "latest"
    assert parse_flags(parser, base + ["--resume", "auto"]).resume == "auto"
    for bad in (
        ["--resume"],          # any resume mode requires --ckpt-dir
        ["--resume", "auto"],  # auto requires --ckpt-dir
        base + ["--resume", "auto", "--max-restarts", "-1"],
        ["--keep-last-n", "0"],
        ["--loader-retries", "-2"],
        ["--faults", "boom@3"],  # spec validated at parse time
    ):
        with pytest.raises(SystemExit):
            parse_flags(parser, bad)


@pytest.mark.faultinject
def test_part_cli_supervised_chaos_run(tmp_path, capsys):
    """The CNN CLI end to end under --resume auto with injected faults:
    a NaN batch (skipped by the guard), a loader raise (retried), and a
    kill during the first checkpoint save (restarted) — the run must
    finish, leave a complete checkpoint, and print every recovery in the
    resilience summary."""
    from distributed_machine_learning_tpu.cli import part1
    from distributed_machine_learning_tpu.train.checkpoint import (
        checkpoint_cursor,
        latest_checkpoint,
    )

    ck = tmp_path / "ck"
    part1.main([
        "--batch-size", "4", "--max-iters", "3", "--epochs", "2",
        "--model", "vggtest", "--eval-batches", "0",
        "--data-root", str(tmp_path), "--ckpt-dir", str(ck),
        "--resume", "auto", "--max-restarts", "2", "--keep-last-n", "1",
        "--guard-nonfinite", "--loader-retries", "2",
        "--faults", "kill_ckpt@1,nan@2,raise@4",
    ])
    out = capsys.readouterr().out
    assert "Resilience summary" in out
    assert "updates skipped (non-finite grads)" in out
    assert "injected mid-checkpoint kills" in out
    assert "supervisor restarts" in out
    assert "data-loader retries" in out
    latest = latest_checkpoint(ck)
    # 2 epochs x 3 batches, one skipped on the first (pre-kill) attempt
    # whose epoch was replayed clean after the restart: 6 applied steps.
    assert latest is not None and latest.endswith("step_6")
    assert checkpoint_cursor(latest) is None  # epoch-cycle saves: no cursor
    # keep_last_n=1: only the newest complete checkpoint remains.
    steps = [d for d in os.listdir(ck) if d.startswith("step_")]
    assert steps == ["step_6"]
