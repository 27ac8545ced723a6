"""Continuous-batching decode engine over a paged KV pool (ISSUE 19).

The batch-static serving path (``make_serving_step``) holds a whole
micro-batch hostage to its slowest member: requests are grouped by
prompt length, every group decodes to its own worst case, and nothing
new starts until the whole dispatch returns.  This engine replaces
that with **iteration-level scheduling** (the Orca/vLLM discipline):

* one *step* = one jitted decode dispatch advancing EVERY in-flight
  sequence by one token, each at its own cache frontier;
* newly admitted prompts prefill and join the very next step;
* a sequence that finishes (EOS or its own ``max_new``) retires
  mid-flight, its KV blocks free immediately, and the freed lane
  backfills from the waiting queue in the same ``step()`` call.

KV residency is a shared **paged pool** — per layer, a
``[num_blocks + 1, Hkv, block_size, D]`` array whose rows are handed
out by ``inference/kv_blocks.py``'s :class:`BlockAllocator` (the +1
row is a scratch block that idle lanes point at).  The decode step
gathers each lane's pages through its block table, runs the model's
batched-frontier cached attention (``models/transformer.py``
``decode_batched_frontier=True`` — per-row ``idx``, per-row masks),
and scatters the one newly written (Hkv, D) row per lane back into
the pool.  The gather formulation is numerically identical to
``ops/pallas/decode_attention.paged_attention_reference`` (asserted
in tests) and is what the engine runs on EVERY backend, TPU included.
``paged_flash_attention`` — the Pallas kernel whose scalar-prefetched
table walk makes each lane's reads O(position) without materializing
the gather — takes the same pool + tables but is not dispatched from
here yet.

The **regime lever** (``runtime/scheduler.py``): per step the engine
asks its :class:`~..runtime.scheduler.RegimeScheduler` (or honors the
router's stamped hint) which dispatch variant to run — ``"latency"``
(full-precision weights; the thin-batch regime where speculative
decoding's economics apply) or ``"throughput"`` (int8 weight-only via
``quantize_lm_params``, the measured wide-batch lever).  Lever
variants share the KV pool — they are the same weights at different
precision — so flipping between steps is free; *weight versions* (hot
swap) are different weights, and :meth:`swap_params` refuses to land
while any sequence is in flight (the engine-step-boundary fence the
deploy pipeline drains to).
"""

from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
from functools import partial

from distributed_machine_learning_tpu.inference.generate import _sample
from distributed_machine_learning_tpu.inference.kv_blocks import (
    BlockAllocator,
    CacheExhausted,
    blocks_needed,
)
from distributed_machine_learning_tpu.runtime.scheduler import (
    LATENCY,
    THROUGHPUT,
)
from distributed_machine_learning_tpu.telemetry.registry import (
    default_latency_buckets,
)


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """``max_lanes`` is the decode batch width W (one jitted program,
    idle lanes ride as masked work); ``num_blocks * block_size`` is
    the shared cache budget in token slots; ``max_len`` caps
    ``prompt_len + max_new`` per request and fixes the per-lane block
    table width (the jit-static gather shape)."""

    max_lanes: int = 4
    block_size: int = 16
    num_blocks: int = 64
    max_len: int = 128
    max_new: int = 32              # default per-request cap
    eos_id: int | None = None
    temperature: float = 0.0
    top_k: int | None = None
    top_p: float | None = None
    levers: tuple = (LATENCY, THROUGHPUT)

    def __post_init__(self):
        if self.max_lanes < 1:
            raise ValueError(f"max_lanes must be >= 1: {self.max_lanes}")
        if self.max_len > self.num_blocks * self.block_size:
            raise ValueError(
                f"max_len={self.max_len} exceeds the pool "
                f"({self.num_blocks} x {self.block_size} slots)"
            )
        if not self.levers or any(
            l not in (LATENCY, THROUGHPUT) for l in self.levers
        ):
            raise ValueError(f"unknown levers: {self.levers}")


@dataclasses.dataclass
class _Lane:
    rid: object
    prompt_len: int
    max_new: int
    tokens: list
    request: dict | None
    version: object
    lever: str
    t_submit: float
    t_ready: float        # prefill completed
    prefill_s: float


def _gather_cache(mb, bs, pools, tables, positions):
    """Pool pages -> one dense batched-frontier cache tree."""
    def leaf(pool):
        g = pool[tables]  # [W, MB, Hkv, bs, D]
        W, _, hkv, _, d = g.shape
        return g.transpose(0, 2, 1, 3, 4).reshape(W, hkv, mb * bs, d)

    cache = jax.tree_util.tree_map(leaf, pools)
    cache["idx"] = positions
    return cache


def _decode_step(dm, sample, mb, bs, params, pools, tables, positions,
                 toks, rng):
    """One iteration: gather pages -> model decode (every lane writes
    its slot ``positions[w]`` and attends slots <= it) -> scatter the
    fresh K/V row of each lane back to its page -> sample."""
    cache = _gather_cache(mb, bs, pools, tables, positions)
    logits, vars_ = dm.apply(
        {"params": params, "cache": cache}, toks[:, None],
        train=False, mutable=["cache"],
    )
    newc = vars_["cache"]
    newc.pop("idx", None)
    bidx = positions // bs
    phys = jnp.take_along_axis(tables, bidx[:, None], axis=1)[:, 0]
    off = positions % bs

    def scatter(pool, cache_leaf):
        # cache_leaf [W, Hkv, S, D]: pull each lane's just-written row.
        new = jnp.take_along_axis(
            cache_leaf, positions[:, None, None, None], axis=2
        )[:, :, 0, :]
        return pool.at[phys, :, off, :].set(new)

    pools = jax.tree_util.tree_map(scatter, pools, newc)
    rng, r = jax.random.split(rng)
    nxt = sample(logits[:, -1], r)
    return pools, nxt


def _prefill(dm, sample, nb, bs, params, pools, table_row, prompt, rng):
    """Prefill one prompt ([1, Lp]) into its ``nb`` allocated pool
    blocks and sample the first generated token."""
    sp = nb * bs
    shapes = jax.eval_shape(
        lambda: dm.init(
            jax.random.PRNGKey(0), jnp.zeros((1, sp), jnp.int32),
            train=False,
        )
    )["cache"]
    cache = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), shapes
    )
    logits, vars_ = dm.apply(
        {"params": params, "cache": cache}, prompt, train=False,
        mutable=["cache"],
    )
    newc = vars_["cache"]
    newc.pop("idx", None)

    def scatter(pool, cache_leaf):
        # [1, Hkv, Sp, D] -> [nb, Hkv, bs, D] page rows.
        hkv, d = cache_leaf.shape[1], cache_leaf.shape[3]
        pages = cache_leaf[0].reshape(hkv, nb, bs, d).transpose(1, 0, 2, 3)
        return pool.at[table_row].set(pages)

    pools = jax.tree_util.tree_map(scatter, pools, newc)
    rng, r = jax.random.split(rng)
    tok = sample(logits[:, -1], r)
    return pools, tok[0]


class ContinuousEngine:
    """One replica's iteration-level serving loop.

    ``submit()`` queues requests (any thread); ``step()`` (the owning
    worker thread) advances the world by one decode iteration and
    returns the requests that finished.  Construction compiles
    nothing — prefill programs trace per distinct prompt length, the
    decode program once per (lever) — so a replica is serving-warm
    after its first few requests.
    """

    def __init__(self, model, params, cfg: EngineConfig | None = None, *,
                 registry=None, scheduler=None, name: str = "engine",
                 version=None, rng=None):
        self.cfg = cfg = cfg or EngineConfig()
        if model.kv_cache_dtype is not None:
            raise ValueError(
                "paged pools hold compute-dtype KV; int8 caches are the "
                "batch-static path's lever (kv_cache_dtype must be None)"
            )
        self._by = name
        self._scheduler = scheduler
        self._hint: str | None = None
        self.version = version
        self._rng = rng if rng is not None else jax.random.PRNGKey(0)
        self._mb = blocks_needed(cfg.max_len, cfg.block_size)
        self._trash = cfg.num_blocks  # scratch page for idle lanes
        self.allocator = BlockAllocator(cfg.num_blocks, cfg.block_size)
        self._dm = {}
        self._params = {}
        self._model = model
        self._base_params = params
        for lever in cfg.levers:
            quant = "int8" if lever == THROUGHPUT else None
            self._dm[lever] = model.clone(
                attn_impl="dense", decode=True, weight_quant=quant,
                decode_batched_frontier=True,
            )
        self._set_params(params)
        sample = partial(_sample, temperature=cfg.temperature,
                         top_k=cfg.top_k, top_p=cfg.top_p)
        self._decode_jit = {
            lever: jax.jit(partial(_decode_step, self._dm[lever], sample,
                                   self._mb, cfg.block_size))
            for lever in cfg.levers
        }
        self._prefill_jit = {}   # (lever, nb, Lp) -> jitted fn
        self._sample = sample
        # The pool tree: the decode cache structure minus "idx", one
        # leading page axis replacing the batch axis.  Built from a
        # one-block eval_shape so layout/dtype can never drift from
        # the model's own cache variables.
        shapes = jax.eval_shape(
            lambda: self._dm[cfg.levers[0]].init(
                jax.random.PRNGKey(0),
                jnp.zeros((1, cfg.block_size), jnp.int32), train=False,
            )
        )["cache"]
        shapes.pop("idx")
        self.pools = jax.tree_util.tree_map(
            lambda s: jnp.zeros((cfg.num_blocks + 1,) + s.shape[1:],
                                s.dtype),
            shapes,
        )
        self._lanes: list[_Lane | None] = [None] * cfg.max_lanes
        self._waiting: list[_Lane] = []
        self._paused = False
        self.steps = 0
        self.completed_total = 0
        self._metrics = None
        if registry is not None:
            lat = default_latency_buckets()
            self._metrics = {
                "lanes": registry.gauge("engine_active_lanes"),
                "queue": registry.gauge("engine_queue_depth"),
                "free": registry.gauge("kv_free_blocks"),
                "avail": registry.gauge("kv_available_blocks"),
                "tokens": registry.counter("engine_tokens_total"),
                "done": registry.counter("engine_requests_total"),
                "prefill": registry.histogram(
                    "engine_prefill_s", buckets=lat),
                "decode": registry.histogram(
                    "engine_decode_s", buckets=lat),
                "e2e": registry.histogram("engine_e2e_s", buckets=lat),
            }

    # -- params / levers ------------------------------------------------

    def _set_params(self, params):
        self._base_params = params
        self._params = {}
        for lever in self.cfg.levers:
            if lever == THROUGHPUT:
                from distributed_machine_learning_tpu.ops.quant import (
                    quantize_lm_params,
                )

                self._params[lever] = quantize_lm_params(params)
            else:
                self._params[lever] = params

    def swap_params(self, params, version=None) -> None:
        """Install new weights — the hot-swap fence.  Refuses while any
        sequence is in flight: the worker drains (keeps stepping with
        admission paused until ``in_flight() == 0``) first, so no
        sequence ever mixes weight versions mid-stream."""
        if self.in_flight():
            raise RuntimeError(
                f"swap_params with {self.in_flight()} sequences in "
                "flight — drain the engine first (pause_admission + "
                "step until empty)"
            )
        self._set_params(params)
        if version is not None:
            self.version = version

    def warmup(self, prompt_lens=(4,)) -> None:
        """Compile ahead of serving: run one dummy request per distinct
        prompt length through every lever's prefill + decode program
        and drain it.  A fleet replica warms up BEFORE it starts
        heartbeating — XLA compilation inside the first live ``step()``
        would otherwise starve the beat channel long enough for the
        router's staleness eviction to fire on a healthy replica."""
        hint = self._hint
        eos = self.cfg.eos_id
        # EOS off for the dummies (frozen-dataclass override, restored
        # below): an instant EOS out of prefill would retire the lane
        # before the decode program ever traced.
        object.__setattr__(self.cfg, "eos_id", None)
        try:
            for lever in self.cfg.levers:
                self._hint = lever
                for lp in prompt_lens:
                    lp = int(lp)
                    if lp + 2 > self.cfg.max_len:
                        raise ValueError(
                            f"warmup prompt_len {lp} + 2 exceeds "
                            f"max_len={self.cfg.max_len}")
                    # max_new=2: the first token retires at max_new=1
                    # straight out of prefill and the decode program
                    # would never trace.
                    self.submit(("__warmup__", lever, lp),
                                [1] * lp, max_new=2)
                self.drain()
        finally:
            self._hint = hint
            object.__setattr__(self.cfg, "eos_id", eos)

    def note_lever(self, lever: str | None) -> None:
        """Router-stamped fleet-wide regime hint; overrides the local
        scheduler until cleared with ``None``."""
        if lever is not None and lever not in (LATENCY, THROUGHPUT):
            raise ValueError(f"unknown lever {lever!r}")
        self._hint = lever

    def _pick_lever(self) -> str:
        lever = self._hint
        if lever is None and self._scheduler is not None:
            lever = self._scheduler.observe(len(self._waiting),
                                            self.in_flight())
        if lever is None:
            lever = LATENCY
        if lever not in self._dm:   # single-lever engines ignore regime
            lever = self.cfg.levers[0]
        return lever

    # -- admission ------------------------------------------------------

    def submit(self, rid, prompt, *, max_new: int | None = None,
               request: dict | None = None) -> None:
        """Queue one request.  ``prompt`` is a python token list;
        ``request`` is the fleet's request record (stage events are
        stamped onto it).  Raises ``ValueError`` if the request can
        never fit (admission control handles the *transient* full-pool
        case by leaving it queued)."""
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise ValueError("empty prompt")
        mn = self.cfg.max_new if max_new is None else int(max_new)
        if mn < 1:
            raise ValueError(f"max_new must be >= 1: {mn}")
        if len(prompt) + mn > self.cfg.max_len:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new ({mn}) exceeds "
                f"max_len={self.cfg.max_len}"
            )
        self._waiting.append(_Lane(
            rid=rid, prompt_len=len(prompt), max_new=mn, tokens=prompt,
            request=request, version=None, lever=LATENCY,
            t_submit=time.perf_counter(), t_ready=0.0, prefill_s=0.0,
        ))

    def pause_admission(self) -> None:
        self._paused = True

    def resume_admission(self) -> None:
        self._paused = False

    def abort_all(self) -> list:
        """Drop every queued and in-flight request WITHOUT completing
        it — the retired-replica path.  When the router retires this
        replica it atomically requeues everything the replica owned
        for survivors, so emitting results here would race the epoch
        fence (they would post as fenced no-ops anyway).  Frees all
        pool blocks; returns the dropped rids for the worker's audit
        trail."""
        dropped = [l.rid for l in self._lanes if l is not None]
        dropped += [l.rid for l in self._waiting]
        for lane in self._lanes:
            if lane is not None:
                self.allocator.free(lane.rid)
        self._lanes = [None] * self.cfg.max_lanes
        self._waiting.clear()
        return dropped

    # -- introspection --------------------------------------------------

    def in_flight(self) -> int:
        return sum(1 for l in self._lanes if l is not None)

    def queued(self) -> int:
        return len(self._waiting)

    def has_work(self) -> bool:
        return self.in_flight() > 0 or (
            not self._paused and bool(self._waiting)
        )

    # -- the iteration loop ---------------------------------------------

    def _stamp(self, lane: _Lane, stage: str, **extra) -> None:
        if lane.request is not None and isinstance(
            lane.request.get("events"), list
        ):
            from distributed_machine_learning_tpu.runtime.transport import (
                stamp_stage,
            )

            stamp_stage(lane.request, stage, self._by, **extra)

    def _admit(self, lever: str, completed: list) -> None:
        """Move waiting requests into free lanes while the allocator
        admits them (prefill runs here — the admitted prompt joins the
        next decode dispatch)."""
        while self._waiting and not self._paused:
            free = [i for i, l in enumerate(self._lanes) if l is None]
            if not free:
                return
            lane = self._waiting[0]
            try:
                table = self.allocator.admit(
                    lane.rid, lane.prompt_len, lane.max_new
                )
            except CacheExhausted:
                return  # head-of-line waits for a retirement
            except ValueError:
                self._waiting.pop(0)
                raise
            self._waiting.pop(0)
            nb = len(table)
            key = (lever, nb, lane.prompt_len)
            fn = self._prefill_jit.get(key)
            if fn is None:
                fn = self._prefill_jit[key] = jax.jit(partial(
                    _prefill, self._dm[lever], self._sample, nb,
                    self.cfg.block_size,
                ))
            t0 = time.perf_counter()
            self._rng, r = jax.random.split(self._rng)
            prompt = jnp.asarray([lane.tokens], jnp.int32)
            row = jnp.asarray(table, jnp.int32)
            self.pools, tok = fn(self._params[lever], self.pools, row,
                                 prompt, r)
            tok = int(jax.device_get(tok))
            lane.t_ready = time.perf_counter()
            lane.prefill_s = lane.t_ready - t0
            lane.version = self.version
            lane.lever = lever
            lane.tokens.append(tok)
            self._stamp(lane, "prefill", lever=lever)
            if self._metrics is not None:
                self._metrics["prefill"].observe(lane.prefill_s)
                self._metrics["tokens"].inc()
            if self._finished(lane, tok):
                self._retire(lane, completed)
            else:
                self._lanes[free[0]] = lane

    def _finished(self, lane: _Lane, tok: int) -> bool:
        if self.cfg.eos_id is not None and tok == self.cfg.eos_id:
            return True
        return len(lane.tokens) - lane.prompt_len >= lane.max_new

    def _retire(self, lane: _Lane, completed: list) -> None:
        self.allocator.free(lane.rid)
        now = time.perf_counter()
        decode_s = now - lane.t_ready
        e2e_s = now - lane.t_submit
        gen = len(lane.tokens) - lane.prompt_len
        eos = (self.cfg.eos_id is not None
               and lane.tokens[-1] == self.cfg.eos_id)
        self._stamp(lane, "decode", tokens=gen, lever=lane.lever)
        if self._metrics is not None:
            self._metrics["decode"].observe(decode_s)
            self._metrics["e2e"].observe(e2e_s)
            self._metrics["done"].inc()
        self.completed_total += 1
        completed.append({
            "rid": lane.rid,
            "tokens": list(lane.tokens),
            "prompt_len": lane.prompt_len,
            "generated": gen,
            "finish": "eos" if eos else "length",
            "lever": lane.lever,
            "version": lane.version,
            "prefill_s": lane.prefill_s,
            "decode_s": decode_s,
            "e2e_s": e2e_s,
            "request": lane.request,
        })

    def step(self) -> list[dict]:
        """One engine iteration; returns the requests that completed
        during it.  Safe to call with nothing in flight (admission
        still runs); a no-work step returns []."""
        completed: list[dict] = []
        lever = self._pick_lever()
        self._admit(lever, completed)
        active = [(i, l) for i, l in enumerate(self._lanes)
                  if l is not None]
        if active:
            W, mb = self.cfg.max_lanes, self._mb
            tables = np.full((W, mb), self._trash, np.int32)
            positions = np.zeros((W,), np.int32)
            toks = np.zeros((W,), np.int32)
            for i, lane in active:
                pos = self.allocator.append(lane.rid)
                tbl = self.allocator.table(lane.rid)
                tables[i, :len(tbl)] = tbl
                positions[i] = pos
                toks[i] = lane.tokens[-1]
            self._rng, r = jax.random.split(self._rng)
            self.pools, nxt = self._decode_jit[lever](
                self._params[lever], self.pools,
                jnp.asarray(tables), jnp.asarray(positions),
                jnp.asarray(toks), r,
            )
            nxt = np.asarray(jax.device_get(nxt))
            for i, lane in active:
                tok = int(nxt[i])
                lane.tokens.append(tok)
                if self._metrics is not None:
                    self._metrics["tokens"].inc()
                if self._finished(lane, tok):
                    self._lanes[i] = None
                    self._retire(lane, completed)
            # Backfill freed lanes the same step: the next admitted
            # prompt prefills NOW and decodes from the next iteration.
            if completed:
                self._admit(lever, completed)
        self.steps += 1
        if self._metrics is not None:
            st = self.allocator.stats()
            self._metrics["lanes"].set(float(self.in_flight()))
            self._metrics["queue"].set(float(len(self._waiting)))
            self._metrics["free"].set(float(st["free"]))
            self._metrics["avail"].set(float(st["available"]))
        return completed

    def drain(self, max_steps: int = 100000) -> list[dict]:
        """Step until nothing is queued or in flight (admission stays
        as-is; pause first for a swap-style drain of in-flight only)."""
        out: list[dict] = []
        for _ in range(max_steps):
            if not (self.in_flight()
                    or (not self._paused and self._waiting)):
                break
            out.extend(self.step())
        return out
