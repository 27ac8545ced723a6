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

import time
from typing import Iterable

import jax
import numpy as np

from distributed_machine_learning_tpu.telemetry import get_telemetry
from distributed_machine_learning_tpu.train.state import TrainState
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

    ``stop``: optional zero-arg predicate polled at every step boundary
    (e.g. a ``runtime/resilience.PreemptionHandler``) — True ends the
    epoch cleanly with state consistent, so the caller can checkpoint.
    ``watchdog``: optional ``runtime/resilience.Watchdog``; beaten once
    per completed step, and once BEFORE the first batch is pulled — a
    loader that hangs on batch 0 is then caught as a stall with a full
    timeout window instead of hanging forever against a window already
    spent on setup/compile.
    ``events``: optional ``runtime/faults.FaultEvents``; counts steps the
    non-finite-gradient guard skipped (step counter unchanged after a
    consumed batch) and dynamic loss-scale adjustments.
    ``until_step``: optional absolute step-counter target — the epoch
    ends once ``state.step`` reaches it.  Unlike ``max_iters`` (a batch
    cap) this counts *applied* updates, so guard-skipped steps are
    retried with further batches — the supervisor's contract that a
    faulted run still lands on the same final step count.
    ``telemetry``: optional ``telemetry.Telemetry``; defaults to the
    process-wide install (``get_telemetry()``, None unless a CLI set
    ``--telemetry-dir``).  When active, the old single timing bracket is
    split into per-phase spans — ``data_wait`` / ``place_batch`` /
    ``step_dispatch`` / ``device_block`` — streamed to the Chrome trace,
    and each step logs an attempt-tagged metrics row (examples/s,
    tokens/s, MFU when the CLI installed a FLOPs model).  When None
    (the default) every telemetry branch is a single pointer test: no
    allocations, no clock reads, no syscalls beyond today's loop.
    """
    timer = timer or IterationTimer(skip_first=1)
    tel = telemetry if telemetry is not None else get_telemetry()
    device_kind = jax.devices()[0].device_kind if tel is not None else None
    if watchdog is not None:
        watchdog.beat()
    batches = iter(batches)
    batch_idx = 0
    while True:
        t_fetch = time.perf_counter() if tel is not None else 0.0
        try:
            images, labels = next(batches)
        except StopIteration:
            break
        t_got = time.perf_counter() if tel is not None else 0.0
        if batch_idx == max_iters:  # part1/main.py:32-33
            break
        if stop is not None and stop():
            rank0_print(
                f"stop requested; ending epoch after {batch_idx} iterations"
            )
            break
        if events is not None:
            step_before = int(jax.device_get(state.step))
            # Read the value NOW: the jitted step donates its input
            # state, so this buffer is dead after the call.
            scale_before = getattr(state, "loss_scale", None)
            if scale_before is not None:
                scale_before = float(scale_before)
        if tel is not None:
            # Batch geometry BEFORE placement (sharding may hide it).
            shape = getattr(images, "shape", None)
            n_examples = int(shape[0]) if shape else 0
            n_tokens = (
                int(shape[0]) * int(shape[1])
                if shape is not None and len(shape) == 2
                else None
            )
        timer.start()
        t_place = time.perf_counter() if tel is not None else 0.0
        if place_batch is not None:
            images, labels = place_batch(images, labels)
        t_dispatch = time.perf_counter() if tel is not None else 0.0
        state, loss = train_step(state, images, labels)
        t_block = time.perf_counter() if tel is not None else 0.0
        loss = jax.block_until_ready(loss)
        t_end = time.perf_counter() if tel is not None else 0.0
        iter_time = timer.stop()
        # One host sync serves both the skip accounting and the
        # until_step check below — these reads serialize dispatch, so
        # pay for them only when a consumer asked.
        step_after = (
            int(jax.device_get(state.step))
            if events is not None or until_step is not None
            else None
        )
        if events is not None:
            # Account BEFORE the watchdog beat: a RaisingWatchdog beat
            # escalates a declared stall into an exception, and a skip
            # that landed on the same step must already be counted.
            if step_after == step_before:
                events.skipped_steps += 1
            if scale_before is not None:
                before, after = scale_before, float(state.loss_scale)
                if after < before:
                    events.scaler_backoffs += 1
                elif after > before:
                    events.scaler_growths += 1
        if watchdog is not None:
            watchdog.beat()
        if tel is not None:
            step_no = (
                step_after if step_after is not None
                else int(jax.device_get(state.step))
            )
            tr = tel.tracer
            tr.complete("data_wait", t_fetch, t_got, step=batch_idx)
            if place_batch is not None:
                tr.complete("place_batch", t_place, t_dispatch,
                            step=batch_idx)
            tr.complete("step_dispatch", t_dispatch, t_block,
                        step=batch_idx)
            tr.complete("device_block", t_block, t_end, step=batch_idx)
            data_wait_s = t_got - t_fetch
            # Mirror the timer's warm-up protocol: an iteration the
            # timer excluded (XLA compile lands there) must not skew
            # the histogram quantiles either — registry p99 and the
            # printed summary percentiles describe the same population.
            # The span and the (warmup-tagged) row still record it: the
            # compile step belongs on the timeline, not in the tail.
            warmup = timer._iter <= timer.skip_first
            reg = tel.registry
            reg.counter("steps_total").inc()
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
            wall = iter_time + data_wait_s
            examples_per_s = n_examples / wall if wall > 0 else 0.0
            row = {
                "batch": batch_idx,
                "iter_s": iter_time,
                "data_wait_s": data_wait_s,
                **({"warmup": True} if warmup else {}),
                "place_s": t_dispatch - t_place,
                "dispatch_s": t_block - t_dispatch,
                "block_s": t_end - t_block,
                "examples_per_s": examples_per_s,
            }
            # Overlap-aware sharded updates (zero1/fsdp overlap=True)
            # expose the consume-phase gather span: dispatch → observed
            # ready, closed at the NEXT step's consume, so row k
            # reports step k−1's gather.  On the trace timeline the
            # param_gather span overlaps data_wait — the 2004.13336
            # proof that the weight-update gather left the critical
            # path (device_block shrinks by what param_gather hides).
            pop_gather = getattr(train_step, "pop_gather_seconds", None)
            if pop_gather is not None:
                gather_s = pop_gather()
                if gather_s is not None:
                    row["param_gather_s"] = gather_s
                    if not warmup:
                        reg.histogram("param_gather_seconds").observe(
                            gather_s)
            if n_tokens is not None:
                tokens_per_s = n_tokens / wall if wall > 0 else 0.0
                row["tokens_per_s"] = tokens_per_s
                reg.gauge("tokens_per_s").set(tokens_per_s)
            else:
                tokens_per_s = None
            reg.gauge("examples_per_s").set(examples_per_s)
            flops_per_s = tel.model_flops_per_s(examples_per_s, tokens_per_s)
            if flops_per_s is not None:
                # None on a device kind the peak table does not list
                # (every CPU run): the row says "no MFU", never a number
                # against another device's peak.
                row["mfu"] = mfu(flops_per_s, device_kind)
                if row["mfu"] is not None:
                    reg.gauge("mfu").set(row["mfu"])
            tel.log_step(step_no, **row)
        if metrics is not None:
            metrics.log(
                step=int(state.step),
                loss=float(np.mean(
                    [lv for _, lv in _host_local_losses(loss)]
                )),
                iter_seconds=iter_time,
            )
        if (batch_idx + 1) % loss_print_every == 0:  # part1/main.py:49-50
            if getattr(loss, "ndim", 0):
                # local-loss mode (make_train_step(local_loss=True)): one
                # line per THIS-HOST device — the reference's every-rank-
                # prints-its-own-loss surface (part2/2a/main.py:58-61);
                # printed unconditionally (not rank-0-gated) for the same
                # reason.
                for d, lv in _host_local_losses(loss):
                    print(
                        f"Loss at {batch_idx + 1}th batch is {lv} "
                        f"(device {d})"
                    )
            else:
                rank0_print(
                    f"Loss at {batch_idx + 1}th batch is {float(loss)}"
                )
        if until_step is not None and step_after >= until_step:
            break
        batch_idx += 1
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
