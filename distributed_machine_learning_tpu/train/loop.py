"""Training/eval drivers with the reference's measurement protocol.

Mirrors ``train_model``/``test_model`` (``part1/main.py:19-77`` and the
clones in 2a/2b/part3): hard cap at 40 iterations, per-iteration wall
clock with iteration 0 excluded (where XLA compilation lands, replacing
the reference's warm-up), loss printed every 20 iterations, and the same
total/average summary lines.  Timing brackets ``block_until_ready`` —
JAX dispatch is async, so without the block the clock would measure
enqueue latency, not the step.
"""

from __future__ import annotations

import collections
import functools
import time
from typing import Iterable

import jax
import numpy as np

from distributed_machine_learning_tpu.telemetry import get_telemetry, startup
from distributed_machine_learning_tpu.train.state import TrainState
from distributed_machine_learning_tpu.utils import profiling
from distributed_machine_learning_tpu.utils.flops import mfu
from distributed_machine_learning_tpu.utils.logging import rank0_print
from distributed_machine_learning_tpu.utils.timing import IterationTimer

# Reference constants (part1/main.py:32-33, 49-50).
MAX_ITERS = 40
LOSS_PRINT_EVERY = 20


def _host_local_losses(loss) -> list[tuple[int, float]]:
    """(global device index, loss) pairs addressable on this host.

    The local-loss vector (``make_train_step(local_loss=True)``) is
    sharded P(batch): on a multi-host run ``np.asarray`` on the global
    array would raise (not fully addressable), and this host should
    print only its own devices' losses anyway — reference semantics.
    Scalars (the pmean path, fully replicated) report as device 0.
    """
    if not getattr(loss, "ndim", 0):
        return [(0, float(loss))]
    shards = getattr(loss, "addressable_shards", None)
    if shards is None:
        return [(d, float(v)) for d, v in enumerate(np.asarray(loss))]
    out = []
    for sh in shards:
        start = sh.index[0].start or 0
        for j, v in enumerate(np.asarray(sh.data).ravel()):
            out.append((start + j, float(v)))
    return sorted(out)


def _print_loss(batch_no: int, loss) -> None:
    if getattr(loss, "ndim", 0):
        # local-loss mode (make_train_step(local_loss=True)): one line
        # per THIS-HOST device — the reference's every-rank-prints-its-
        # own-loss surface (part2/2a/main.py:58-61); printed
        # unconditionally (not rank-0-gated) for the same reason.
        for d, lv in _host_local_losses(loss):
            print(f"Loss at {batch_no}th batch is {lv} (device {d})")
    else:
        rank0_print(f"Loss at {batch_no}th batch is {float(loss)}")


#: One iteration's phases, each declared once: the name on the profiler's
#: clock (``utils/profiling.annotate``) -> the ``SpanTracer`` span and the
#: step-row field.  The last two exist only with a ``Telemetry`` and are
#: made from the two ``perf_counter`` reads of the bracket.
_PHASES = {
    "train.data_wait": ("data_wait", "data_wait_s"),
    "train.place_batch": ("place_batch", "place_s"),
    "train.step_dispatch": ("step_dispatch", "dispatch_s"),
    "train.device_block": ("device_block", "block_s"),
}


def _phase(name: str, rec: "_LoopTelemetry | None"):
    """The one bracket of a phase.  Always a profiler annotation (a flag
    test without a profiler session); with telemetry the bracket that also
    reads the host clock (``profiling.Timed``, the start-up spans' too),
    from which the phase's tracer span and row field come."""
    if rec is None:
        return profiling.annotate(name)
    return profiling.Timed(name, rec.phase_done)


def _as_an_epoch(fn):
    """``train_epoch`` inside the start-up record's ``epoch()``: the JAX
    counters are copied as it begins, and what they count inside it, past
    the process's first step, is labelled ``train``."""

    @functools.wraps(fn)
    def train_epoch(*args, **kwargs):
        with startup.record().epoch():
            return fn(*args, **kwargs)

    return train_epoch


class _LoopTelemetry:
    """``train_epoch``'s telemetry side, one per epoch: from the host
    clock of each iteration's phases to tracer spans, registry series and
    the step row."""

    def __init__(self, tel, state, train_step, timer):
        self.tel = tel
        #: The phases bracketed since the last row: one iteration's.
        self.times: dict[str, tuple[float, float]] = {}
        self._train_step = train_step
        self._timer = timer
        self._device_kind = jax.devices()[0].device_kind
        # The optimizer's count, read once: rows count on from it on the
        # host (a read-back every step is a host sync on telemetry's
        # account).  Only a guard-skipped step makes the two differ, and
        # whoever cares about those passes ``events`` or ``until_step``,
        # whose own read the row then uses.
        self._step_base = int(jax.device_get(state.step))
        # (examples, tokens, host bytes) of each batch fetched and not
        # yet in a row: the loop runs one batch ahead of its rows.
        self._geometry: collections.deque = collections.deque()
        # (resident at, was the loss still pending then) of the batch
        # this iteration waited for inside its block.
        self._arrival = None
        # (start, phases' seconds, batch) of the iteration whose period
        # is still open: it ends where the next one starts.
        self._open = None

    def phase_done(self, name: str, t0: float, t1: float) -> None:
        self.times[name] = (t0, t1)

    def host_batch(self, images, labels) -> None:
        """Batch geometry BEFORE placement (sharding may hide it), and
        the bytes about to cross to the device: no sync, host arrays
        only."""
        shape = getattr(images, "shape", None)
        self._geometry.append((
            int(shape[0]) if shape else 0,
            int(shape[0]) * int(shape[1])
            if shape is not None and len(shape) == 2 else None,
            sum(a.nbytes for a in (images, labels)
                if isinstance(a, np.ndarray)),
        ))

    def primed(self) -> None:
        """Batch 0 was fetched and placed before the first iteration and
        belongs to no row: its two spans go to the trace as they are."""
        for name, (t0, t1) in self.times.items():
            self.tel.tracer.complete(_PHASES[name][0], t0, t1, primed=True)
        self.times.clear()

    def batch_ready(self, loss=None) -> None:
        """The newest placed batch is resident on every shard: now.
        ``loss``: the running step's, when that batch is the NEXT step's
        — asked, without a wait, whether it is still pending."""
        t_ready, pending = time.perf_counter(), None
        if loss is not None:
            is_ready = getattr(loss, "is_ready", None)  # a host scalar has none
            pending = is_ready is not None and not is_ready()
        self._arrival = (t_ready, pending)

    def log_step(self, batch_idx: int, iter_time: float,
                 step_after: int | None) -> None:
        tel, times = self.tel, self.times
        tr, reg = tel.tracer, tel.registry
        # Mirror the timer's warm-up protocol: an iteration the
        # timer excluded (XLA compile lands there) must not skew
        # the histogram quantiles either — registry p99 and the
        # printed summary percentiles describe the same population.
        # The span and the (warmup-tagged) row still record it: the
        # compile step belongs on the timeline, not in the tail.
        warmup = self._timer._iter <= self._timer.skip_first
        # ``place_s`` is 0 where the iteration placed nothing: the loop
        # was given no placement call (``jit`` moves the batch inside
        # ``dispatch_s``/``block_s``), or its fetch ended the epoch.
        row = {"batch": batch_idx, "iter_s": iter_time,
               **({"warmup": True} if warmup else {}), "place_s": 0.0}
        children = 0.0
        for name, (span, field) in _PHASES.items():
            if name in times:
                t0, t1 = times[name]
                tr.complete(span, t0, t1, step=batch_idx)
                row[field] = t1 - t0
                children += t1 - t0
        if self._arrival is not None:
            # Placement call -> batch resident, waited for at the head
            # of device_block: it overlaps place_batch and, one batch
            # ahead, the running step.
            t_ready, loss_pending = self._arrival
            t_place = times["train.place_batch"][0]
            tr.complete("batch_ready", t_place, t_ready, step=batch_idx)
            row["batch_ready_s"] = t_ready - t_place
            if loss_pending is not None:
                # One batch ahead: by how much the input beat the step
                # (the loss came back that long after the batch was
                # resident), or, negative, how long the block waited for
                # the batch with the step already done and the device
                # empty.
                t_block, t_loss = times["train.device_block"]
                lead = t_loss - t_ready if loss_pending \
                    else t_block - t_ready
                row["batch_lead_s"] = lead
                if lead > 0:
                    reg.counter("batches_ahead_total").inc()
            self._arrival = None
        t_start = min(t0 for t0, _ in times.values())
        times.clear()
        if self._open is not None:
            # The period of an iteration ends where the next one starts,
            # after its own row was written: row k carries iteration
            # k-1's self time (as ``param_gather_s`` below does its
            # gather).
            t_prev, children_prev, batch_prev = self._open
            tr.complete("train_step", t_prev, t_start, step=batch_prev)
            row["loop_self_s"] = (t_start - t_prev) - children_prev
        else:
            # An epoch's first row; the process's first carries the
            # start-up line as an object.
            summary = startup.record().pop_summary()
            if summary is not None:
                row["startup"] = summary
        self._open = (t_start, children, batch_idx)
        n_examples, n_tokens, h2d_bytes = self._geometry.popleft()
        data_wait_s = row["data_wait_s"]
        reg.counter("steps_total").inc()
        reg.counter("h2d_bytes_total").inc(h2d_bytes)
        row["h2d_bytes"] = h2d_bytes
        for _cname, _cval in (getattr(tel, "step_counters", None)
                              or {}).items():
            # Static per-step increments the CLI registered (e.g.
            # ring_wire_bytes — the compressed ring's per-step wire
            # bytes, a compile-time constant of the program).  A
            # list value is labeled sub-counters:
            # [({"axis": "outer"}, bytes), ...] increments one
            # counter per label set under the shared name.
            if isinstance(_cval, (list, tuple)):
                for _clabels, _v in _cval:
                    reg.counter(_cname, **_clabels).inc(_v)
            else:
                reg.counter(_cname).inc(_cval)
        if not warmup:
            reg.histogram("step_seconds").observe(iter_time)
            reg.histogram("data_wait_seconds").observe(data_wait_s)
        # The four phases are the iteration's wall-clock in either order
        # (``iter_time`` holds the fetch only when it ran under the step).
        wall = children
        examples_per_s = n_examples / wall if wall > 0 else 0.0
        row["examples_per_s"] = examples_per_s
        reg.gauge("examples_per_s").set(examples_per_s)
        # Overlap-aware sharded updates (zero1/fsdp overlap=True)
        # expose the consume-phase gather span: dispatch → observed
        # ready, closed at the NEXT step's consume, so row k
        # reports step k−1's gather.  On the trace timeline the
        # param_gather span outlasts device_block — the 2004.13336
        # proof that the weight-update gather left the critical
        # path (device_block shrinks by what param_gather hides).
        pop_gather = getattr(self._train_step, "pop_gather_seconds", None)
        if pop_gather is not None:
            gather_s = pop_gather()
            if gather_s is not None:
                row["param_gather_s"] = gather_s
        # A step whose model counts (routing counts of a sparse MoE,
        # ``train/lm_step.py::_StepWithStats``) hands over the counts of
        # the step before this one, already on the host: row fields, and
        # running totals for those the step names.
        pop_stats = getattr(self._train_step, "pop_step_stats", None)
        stats = pop_stats() if pop_stats is not None else None
        if stats:
            row.update(stats)
            for name in self._train_step.step_stats_counters:
                reg.counter(name + "_total").inc(stats[name])
        if n_tokens is not None:
            tokens_per_s = n_tokens / wall if wall > 0 else 0.0
            row["tokens_per_s"] = tokens_per_s
        else:
            tokens_per_s = None
        flops_per_s = tel.model_flops_per_s(examples_per_s, tokens_per_s)
        if flops_per_s is not None:
            # None on a device kind the peak table does not list
            # (every CPU run): the row says "no MFU", never a number
            # against another device's peak.
            row["mfu"] = mfu(flops_per_s, self._device_kind)
        tel.log_step(
            step_after if step_after is not None
            else self._step_base + batch_idx + 1,
            **row,
        )

    def close(self) -> None:
        """The last iteration's step span ends where the loop did: at
        the fetch that ended the epoch, if that came after its row."""
        if self._open is not None:
            last_fetch = self.times.get("train.data_wait")
            self.tel.tracer.complete(
                "train_step", self._open[0],
                last_fetch[0] if last_fetch else time.perf_counter(),
                step=self._open[2])


@_as_an_epoch
def train_epoch(
    train_step,
    state: TrainState,
    batches: Iterable,
    place_batch=None,
    max_iters: int = MAX_ITERS,
    loss_print_every: int = LOSS_PRINT_EVERY,
    timer: IterationTimer | None = None,
    metrics=None,
    stop=None,
    watchdog=None,
    events=None,
    until_step: int | None = None,
    telemetry=None,
) -> tuple[TrainState, IterationTimer]:
    """One epoch, reference-style: returns (state, timer).

    `place_batch(images, labels)` moves a host batch onto device(s)
    (e.g. `shard_batch(mesh, ...)`); defaults to identity (jit handles
    transfer for the single-device path).

    **The loop holds one placed batch ahead of the step.**  It primes
    itself with batch 0 (fetch, place); iteration k then (1) dispatches
    step k on the batch already placed, (2) fetches batch k+1, applies
    the ``max_iters`` / ``stop`` tests to it and places it — the loader's
    wait, the host's gather and the transfer ``device_put`` started all
    run while the device runs step k — and (3) blocks on loss k and does
    step k's bookkeeping.  One *step* is in flight at a time (step k+1 is
    dispatched only after loss k is back, so a skipped or non-finite step
    is seen before the next starts); what runs ahead is one *batch*.
    When the fetch in (2) ends the epoch the loop finishes (3) for step k
    and returns: every placed batch is trained, and a fetched batch is
    discarded only by the ``max_iters`` and ``stop`` tests, before
    placement.  Without a ``place_batch`` the fetch still moves under
    the step; nothing is placed ahead.  The exception is ``until_step``:
    whether batch k+1 is wanted then depends on step k's result, so the
    loop fetches it only after step k's bookkeeping (fetch, place,
    dispatch, block — nothing overlaps, and the batches consumed are
    exactly those the target needs).

    ``stop``: optional zero-arg predicate polled once a fetched batch
    (e.g. a ``runtime/resilience.PreemptionHandler``) — True ends the
    epoch cleanly with state consistent, so the caller can checkpoint.
    ``watchdog``: optional ``runtime/resilience.Watchdog``; beaten once
    per completed step, and once BEFORE the first batch is pulled — a
    loader that hangs on batch 0 is then caught as a stall with a full
    timeout window instead of hanging forever against a window already
    spent on setup/compile.
    ``events``: optional ``runtime/faults.FaultEvents``; counts steps the
    non-finite-gradient guard skipped (step counter unchanged after a
    consumed batch) and dynamic loss-scale adjustments.  The counter is
    read once before the loop and once after each step: step k's reading
    is step k+1's "before".
    ``until_step``: optional absolute step-counter target — the epoch
    ends once ``state.step`` reaches it.  Unlike ``max_iters`` (a batch
    cap) this counts *applied* updates, so guard-skipped steps are
    retried with further batches — the supervisor's contract that a
    faulted run still lands on the same final step count.
    ``timer``: an iteration's time runs from the dispatch of step k to
    its loss (the reference's printed "average time per iteration");
    one batch ahead, the fetch and placement of batch k+1 run inside it.

    One iteration is a closed set of spans, each bracketed once
    (``_PHASES``): ``train.step`` (a ``StepTraceAnnotation`` numbered by
    the batch) holds ``train.step_dispatch`` / ``train.data_wait`` /
    ``train.place_batch`` / ``train.device_block`` /
    ``train.bookkeeping`` on the profiler's clock, always — without a
    profiler session each is a flag test, and with one (``--trace-dir``)
    they lie against the device's operations in the trace.  (With
    ``until_step`` the fetch and placement come last, after the
    bookkeeping; batch 0's lie before ``train.step`` 0 in either order.)

    ``telemetry``: optional ``telemetry.Telemetry``; defaults to the
    process-wide install (``get_telemetry()``, None unless a CLI set
    ``--telemetry-dir``).  When active, the same brackets also read the
    host clock: spans ``step_dispatch`` / ``data_wait`` / ``place_batch``
    / ``device_block`` under a ``train_step`` parent go to the Chrome
    trace, and each step logs an attempt-tagged metrics row (the phases'
    seconds, examples/s, tokens/s, MFU when the CLI installed a FLOPs
    model).  Row k holds the phases of iteration k — so, one batch
    ahead, the fetch and placement of batch k+1 — and four fields only
    this loop can know: ``batch_ready_s`` (placement call -> the placed
    batch resident on every shard; the loop waits for it at the head of
    ``device_block``, where it blocks anyway, so nothing is delayed;
    span ``batch_ready``; absent where nothing was placed),
    ``batch_lead_s`` (at the instant the batch is resident the loop asks
    ``loss.is_ready()``, no wait: not yet — the arrival was hidden, and
    the field is loss ready − batch ready, positive, the margin by which
    the input beat the step; already — the device sat empty for the
    arrival, and the field is minus the time the block waited for the
    batch; counter ``batches_ahead_total`` counts the positive ones
    beside ``steps_total``; absent in an epoch's last iteration and with
    ``until_step``), ``h2d_bytes`` (the bytes of the host batch step k
    trained; counter ``h2d_bytes_total``) and ``loop_self_s`` (the
    iteration's period, its first phase to the next iteration's, minus
    the four phases: this loop's own bookkeeping, telemetry included —
    what the instrumentation costs when it is on.  A row is written
    before its iteration ends, so row k carries iteration k-1's).  The
    row's ``step`` is counted on the host from one read of
    ``state.step`` before the loop.  When None (the default) every
    telemetry branch is a single pointer test: no clock reads, no
    device reads or waits, no syscalls beyond today's loop.

    The process's FIRST step ends its start-up record
    (``telemetry/startup.py``): the timer's two clock reads around
    iteration 0 become the span ``startup.first_step`` (the step's trace,
    lowering, compile or cache load, and first execution), the record
    closes, rank 0 prints the start-up line, and under a ``Telemetry`` the
    first step row carries it as the field ``startup``.  Every epoch runs
    inside the record's ``epoch()`` (``_as_an_epoch``): the ``jax_*_total``
    counters are copied as it begins and count under ``phase="train"``
    past the first step.  No iteration after the first reads a clock or
    tests anything for it.
    """
    timer = timer or IterationTimer(skip_first=1)
    tel = telemetry if telemetry is not None else get_telemetry()
    rec = (_LoopTelemetry(tel, state, train_step, timer)
           if tel is not None else None)
    if watchdog is not None:
        watchdog.beat()
    batches = iter(batches)

    def next_batch(idx: int):
        """Batch ``idx`` fetched, tested and placed; None where the
        fetch ends the epoch."""
        with _phase("train.data_wait", rec):
            try:
                images, labels = next(batches)
            except StopIteration:
                return None
        if idx == max_iters:  # part1/main.py:32-33
            return None
        if stop is not None and stop():
            rank0_print(
                f"stop requested; ending epoch after {idx} iterations"
            )
            return None
        if rec is not None:
            rec.host_batch(images, labels)
        if place_batch is None:
            return images, labels
        with _phase("train.place_batch", rec):
            return place_batch(images, labels)

    ahead = until_step is None
    batch = next_batch(0)
    if rec is not None and ahead:
        rec.primed()
    if events is not None:
        step_before = int(jax.device_get(state.step))
        scale_before = getattr(state, "loss_scale", None)
        if scale_before is not None:
            scale_before = float(scale_before)
    # The process's first step ends its start-up record
    # (``startup.first_step``) through the timer's own two clock reads; from
    # the second iteration on ``lap`` is the timer's ``stop`` again.
    timer_stop = timer.stop
    lap = startup.record().first_step_stop(timer) or timer_stop
    batch_idx = 0
    while batch is not None:
        with profiling.annotate("train.step", step_num=batch_idx):
            timer.start()
            with _phase("train.step_dispatch", rec):
                state, loss = train_step(state, *batch)
            if ahead:
                # Under the running step: nothing here needs its result.
                batch = next_batch(batch_idx + 1)
            with _phase("train.device_block", rec):
                if (tel is not None and place_batch is not None
                        and batch is not None):
                    # When did the newest placed batch arrive?  The loop
                    # blocks on the loss next anyway, so this wait delays
                    # nothing.  (No step donates its batch.)
                    jax.block_until_ready(batch)
                    rec.batch_ready(loss if ahead else None)
                loss = jax.block_until_ready(loss)
            iter_time = lap()
            with profiling.annotate("train.bookkeeping"):
                # One host sync serves both the skip accounting and the
                # until_step check below — these reads serialize
                # dispatch, so pay for them only when a consumer asked.
                step_after = (
                    int(jax.device_get(state.step))
                    if events is not None or until_step is not None
                    else None
                )
                if events is not None:
                    # Account BEFORE the watchdog beat: a RaisingWatchdog
                    # beat escalates a declared stall into an exception,
                    # and a skip that landed on the same step must
                    # already be counted.
                    if step_after == step_before:
                        events.skipped_steps += 1
                    step_before = step_after
                    if scale_before is not None:
                        scale_after = float(state.loss_scale)
                        if scale_after < scale_before:
                            events.scaler_backoffs += 1
                        elif scale_after > scale_before:
                            events.scaler_growths += 1
                        scale_before = scale_after
                if watchdog is not None:
                    watchdog.beat()
                if tel is not None:
                    rec.log_step(batch_idx, iter_time, step_after)
                if metrics is not None:
                    metrics.log(
                        step=int(state.step),
                        loss=float(np.mean(
                            [lv for _, lv in _host_local_losses(loss)]
                        )),
                        iter_seconds=iter_time,
                    )
                if (batch_idx + 1) % loss_print_every == 0:
                    _print_loss(batch_idx + 1, loss)  # part1/main.py:49-50
            if not ahead:
                if step_after >= until_step:
                    break
                batch = next_batch(batch_idx + 1)
        batch_idx += 1
        lap = timer_stop
    if tel is not None:
        rec.close()
    rank0_print(timer.summary())  # part1/main.py:57-58
    return state, timer


def evaluate_lm(eval_step, params, batches: Iterable) -> tuple[float, float]:
    """Corpus-level LM eval: pooled mean NLL/token and perplexity.

    ``eval_step`` from ``train/lm_step.py::make_lm_eval_step``; batches
    yield host ``(tokens, targets)`` pairs.  Pools nll *sums* and token
    counts so unequal batch sizes still give the exact corpus mean
    (unlike the reference's mean-of-batch-means — ``part1/main.py:74``,
    which this deliberately improves on for the LM path).
    """
    import math

    total_nll = 0.0
    total_tokens = 0
    for tokens, targets in batches:
        nll, count = eval_step(params, tokens, targets)
        total_nll += float(nll)
        total_tokens += int(count)
    mean_nll = total_nll / max(total_tokens, 1)
    ppl = math.exp(min(mean_nll, 700.0))  # overflow guard for garbage models
    rank0_print(
        f"Eval: nll/token {mean_nll:.4f}, perplexity {ppl:.2f} "
        f"({total_tokens} tokens)"
    )
    return mean_nll, ppl


def evaluate(
    eval_step,
    state: TrainState,
    batches: Iterable,
    num_test_samples: int | None = None,
) -> tuple[float, float]:
    """Full-test-set eval, ``test_model`` parity (``part1/main.py:62-77``):
    test_loss = mean of per-batch mean losses; top-1 accuracy over the set.
    Every reference rank evaluates the full test set independently; here a
    single device does (params are replicated — same result by construction).
    """
    total_loss = 0.0
    correct = 0
    total = 0
    num_batches = 0
    for images, labels in batches:
        loss, c = eval_step(state.params, state.batch_stats, images, labels)
        total_loss += float(loss)
        correct += int(c)
        total += len(labels)
        num_batches += 1
    avg_loss = total_loss / max(num_batches, 1)
    if num_test_samples is not None:
        total = num_test_samples
    accuracy = 100.0 * correct / max(total, 1)
    rank0_print(
        "Test set: Average loss: {:.4f}, Accuracy: {}/{} ({:.0f}%)\n".format(
            avg_loss, correct, total, accuracy
        )
    )
    return avg_loss, accuracy
