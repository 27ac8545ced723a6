"""Hand-rolled bucketed ring all-reduce on ``lax.ppermute``.

The north-star (BASELINE.json): reimplement part3's bucketed ring
all-reduce — which the reference delegates to PyTorch DDP's C++ reducer
with ``bucket_cap_mb=25`` (``part3/main.py:137``) — as an *explicit*
``lax.ppermute`` ring over the device axis.

Algorithm (classic two-phase ring, 2·(N−1) steps total):

  1. The flattened gradient vector is padded and viewed as N chunks.
  2. **reduce-scatter** (N−1 steps): at step s, device r sends its running
     partial sum of chunk ``(r − s) mod N`` to its right neighbor
     ``(r+1) mod N`` and adds the chunk it receives from the left into its
     local copy.  After N−1 steps device r holds the *complete* sum of
     chunk ``(r+1) mod N``.
  3. **all-gather** (N−1 steps): the completed chunks circulate around the
     same ring until every device holds the full reduced vector.

Each device moves 2·(N−1)/N of the gradient bytes — the bandwidth-optimal
schedule DDP's ring uses, here riding ICI links via ``ppermute``.

Bucketing: gradients are flattened once (``ravel_pytree``) and split into
``bucket_bytes`` buckets (default 25 MB — the reference's
``bucket_cap_mb=25``).  Buckets are independent rings, so XLA's async
collective scheduler overlaps bucket k's ppermutes with bucket k+1's
adds — the same comm/compute overlap DDP's autograd hooks implement in
C++ (``part3/main.py:59``, group25.pdf p.6), obtained from the compiler
instead of hand-written callbacks.  **Verified, not assumed** (round 4,
``analysis/overlap_audit.py``): AOT-compiling the full part3 step for a
real v5e 2×4 target shows 28 async ``collective-permute-start/done``
pairs (= 2 buckets × 2·(N−1) steps), 21 of which have the *other*
bucket's ``slice_add``/``slice_reduce`` fusions scheduled inside their
in-flight window, with up to 2 ppermutes concurrently in flight and the
two buckets' rings interleaved step-for-step — a schedule, not a
timeline: what the ring hides on the chip no cell measures yet.

The ring steps use *static* chunk indices (the loop over steps is unrolled;
N is a compile-time mesh constant), so every slice is a static-shape
``lax.slice`` the TPU backend can lay out without dynamic-update overhead.

**Wire compression** (round 7): every hop's payload can be compressed
through a :class:`WireScheme` — the quantized/sparsified multi-hop
all-reduce of the retrieved literature (DynamiQ, arxiv 2602.08923;
"Efficient Training of Convolutional Neural Nets on Large Distributed
Systems", arxiv 1711.00705).  Three codecs behind one interface:

- ``bf16`` — plain dtype cast on the wire (2 bytes/elem, no metadata);
  this is CAST-ONLY lossy compression, not the error-compensated scheme
  of the literature — residual correction lives a layer up, in
  ``parallel/strategies.py::RingAllReduce(error_feedback=True)``.
- ``int8`` — per-chunk symmetric int8 with one fp32 scale per chunk
  (~4x fewer wire bytes).  Each reduce-scatter hop dequantizes, adds
  in fp32, and requantizes — the dequantize–add–requantize fusion of
  the compressed multi-hop all-reduce.  Two implementations behind
  ``codec_impl`` (round 13): ``"xla"`` spells the codec as separate
  XLA ops (quantization arithmetic shared with the serving weight
  quantizer's recipe — ``quantize_int8`` in
  ``ops/pallas/quant_matmul.py`` — applied per chunk), ``"pallas"``
  runs the fused in-register kernels of the shared codec module
  ``ops/pallas/ring_codec.py`` (bitwise-identical payload, residual,
  and output; no dequantized partial ever reaches HBM).
- ``topk`` — magnitude top-k sparsification: (values, indices) on the
  wire, ``k = topk_frac × chunk``; the receiver scatter-adds.

The all-gather phase relays each completed chunk's *encoded payload*
bit-exactly around the ring and decodes it on every rank (including the
owner), so all ranks end the all-reduce with IDENTICAL synced gradients
and replicated params cannot drift — the same invariant the bf16 path
establishes by quantizing the owner's copy once.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.flatten_util import ravel_pytree

DEFAULT_BUCKET_BYTES = 25 * 2**20  # part3/main.py:137 (bucket_cap_mb=25)


def _right_shift_perm(n: int) -> list[tuple[int, int]]:
    """Ring permutation: every device sends to its right neighbor."""
    return [(i, (i + 1) % n) for i in range(n)]


# ---------------------------------------------------------------------------
# Wire schemes — per-chunk codecs for the ring hops.
# ---------------------------------------------------------------------------


class WireScheme:
    """Codec for one ring hop's payload over a flat fp32 chunk.

    ``encode(v) -> tuple[jax.Array, ...]`` produces the arrays that go
    over the wire (each leaf is ppermuted independently);
    ``decode(payload, length) -> jax.Array`` reconstructs a dense fp32
    chunk of ``length`` elements; ``payload_bytes(length)`` is the
    static byte accounting the telemetry counters and the HLO wire-byte
    audit (``analysis/overlap_audit.py --wire-bytes``) check against.

    The base class is the exact (identity) scheme.
    """

    name = "none"

    def encode(self, v: jax.Array) -> tuple[jax.Array, ...]:
        return (v,)

    def decode(self, payload: tuple[jax.Array, ...], length: int) -> jax.Array:
        return payload[0]

    def payload_bytes(self, length: int, itemsize: int = 4) -> int:
        return length * itemsize

    # -- fusion seams (round 13) ---------------------------------------
    # The ring loops route every hop through these two methods instead
    # of spelling encode/decode/add/residual inline, so a codec that
    # owns fused kernels (Int8Scheme(impl="pallas")) can collapse each
    # piece to one in-register pass.  The defaults reproduce the
    # historical op-for-op XLA arithmetic exactly.

    def encode_with_residual(self, v: jax.Array):
        """``(payload, err)`` where ``err = v − decode(encode(v))`` is
        the error-feedback send error this encode drops."""
        enc = self.encode(v)
        return enc, v - self.decode(enc, v.shape[0]).astype(v.dtype)

    def decode_add(
        self, payload: tuple[jax.Array, ...], acc: jax.Array, length: int
    ) -> jax.Array:
        """One arrival: decode ``payload`` and accumulate into ``acc``
        (the reduce-scatter hop's dequantize–add)."""
        return acc + self.decode(payload, length).astype(acc.dtype)


class CastScheme(WireScheme):
    """Dtype cast on the wire (``bf16``): halves fp32 bytes, no metadata.

    Cast-only — per-hop rounding error is NOT tracked here; pairing it
    with the strategy layer's error-feedback residual is possible but
    historically this ran bare (the deprecated ``wire_dtype`` knob).
    """

    name = "bf16"

    def __init__(self, dtype=jnp.bfloat16):
        self.dtype = jnp.dtype(dtype)

    def encode(self, v):
        return (v.astype(self.dtype),)

    def decode(self, payload, length):
        return payload[0].astype(jnp.float32)

    def payload_bytes(self, length, itemsize=4):
        return length * self.dtype.itemsize


class Int8Scheme(WireScheme):
    """Per-chunk symmetric int8 + one fp32 scale (~itemsize/1 ≈ 4x fewer
    bytes for fp32 gradients).  Both implementations share ONE recipe,
    defined in the codec module ``ops/pallas/ring_codec.py``
    (:func:`~distributed_machine_learning_tpu.ops.pallas.ring_codec.quantize_chunk_int8`):
    the serving weight quantizer's symmetric ``scale = max|v|/127``
    applied per chunk, with the scale's mantissa truncated to 16 bits
    so every decode product ``q·scale`` is EXACT in f32 — the property
    that makes the fused/XLA parity bitwise by construction (FMA
    contraction cannot perturb an exact product) instead of at the
    mercy of backend fusion decisions.

    ``impl`` (round 13, the ``--ring-codec-impl`` knob): ``"xla"``
    spells encode/decode/residual as separate XLA ops (the historical
    build); ``"pallas"`` dispatches to the fused in-register kernels of
    the same codec module — identical wire payload (bitwise),
    identical residual, no dequantized partial in HBM.  The kernels
    engage on f32 chunks (the dtype every ring path carries — flat
    gradients ravel to f32); a non-f32 chunk falls back to the XLA
    seams, because the kernels accumulate/subtract in f32 and round
    once where the XLA seams compute in the chunk dtype — on f32 the
    two coincide bit for bit, on narrower dtypes they would not, and
    the bitwise contract must hold wherever the kernels run."""

    name = "int8"

    def __init__(self, impl: str = "xla"):
        if impl not in CODEC_IMPLS:
            raise ValueError(
                f"unknown int8 codec impl {impl!r}; choose from "
                f"{CODEC_IMPLS} (the fused kernels live in "
                "ops/pallas/ring_codec.py)"
            )
        self.impl = impl

    def encode(self, v):
        if self.impl == "pallas":
            from distributed_machine_learning_tpu.ops.pallas.ring_codec import (
                encode_int8,
            )

            return encode_int8(v)
        from distributed_machine_learning_tpu.ops.pallas.ring_codec import (
            quantize_chunk_int8,
        )

        return quantize_chunk_int8(v)

    def encode_with_residual(self, v):
        # f32-only kernel engagement (see class docstring): the kernel
        # subtracts in f32 and rounds the residual once, the XLA seam
        # subtracts in the chunk dtype — identical bits on f32 only.
        if self.impl != "pallas" or v.dtype != jnp.float32:
            return super().encode_with_residual(v)
        from distributed_machine_learning_tpu.ops.pallas.ring_codec import (
            encode_int8_residual,
        )

        q, scale, err = encode_int8_residual(v)
        return (q, scale), err

    def decode(self, payload, length):
        q, scale = payload
        if self.impl == "pallas":
            from distributed_machine_learning_tpu.ops.pallas.ring_codec import (
                decode_int8,
            )

            return decode_int8(q, scale, length)
        # Exact product (the truncated scale of ring_codec.chunk_scale
        # bounds q·scale to 24 significand bits), so downstream
        # adds/subtracts cannot be perturbed by FMA contraction —
        # bitwise-identical to the fused kernel in any fusion context.
        return q.astype(jnp.float32) * scale  # scale is [1]; broadcasts

    def decode_add(self, payload, acc, length):
        # f32-only kernel engagement (see class docstring): the kernel
        # accumulates in f32 and rounds the sum once, the XLA seam
        # casts the decode then adds in the accumulator dtype.
        if self.impl != "pallas" or acc.dtype != jnp.float32:
            return super().decode_add(payload, acc, length)
        from distributed_machine_learning_tpu.ops.pallas.ring_codec import (
            decode_add_int8,
        )

        q, scale = payload
        return decode_add_int8(q, scale, acc)

    def payload_bytes(self, length, itemsize=4):
        return length + 4  # int8 chunk + one fp32 scale


class TopKScheme(WireScheme):
    """Magnitude top-k sparsification: ``k = max(1, round(frac·L))``
    (values fp32, indices int32 — 8 bytes per kept element, so the wire
    ratio vs fp32 is ``2·frac``; the default frac 1/8 is 4x fewer
    bytes).  Indices from ``lax.top_k`` are unique, so decode is a
    scatter-``set`` into zeros."""

    name = "topk"

    def __init__(self, frac: float = 0.125):
        if not 0.0 < frac <= 1.0:
            raise ValueError(f"topk frac must be in (0, 1], got {frac}")
        self.frac = float(frac)

    def k_for(self, length: int) -> int:
        return min(length, max(1, int(round(self.frac * length))))

    def encode(self, v):
        k = self.k_for(v.shape[0])
        _, idx = lax.top_k(jnp.abs(v), k)
        return (jnp.take(v, idx), idx.astype(jnp.int32))

    def decode(self, payload, length):
        vals, idx = payload
        return jnp.zeros((length,), jnp.float32).at[idx].set(
            vals.astype(jnp.float32)
        )

    def payload_bytes(self, length, itemsize=4):
        return self.k_for(length) * (itemsize + 4)


WIRE_SCHEMES = ("none", "bf16", "int8", "topk")
CODEC_IMPLS = ("xla", "pallas")


def get_wire_scheme(
    name: str, topk_frac: float = 0.125, codec_impl: str = "xla"
) -> WireScheme:
    """Resolve a ``--ring-compress`` name to a codec instance.

    ``codec_impl`` (``--ring-codec-impl``): ``"pallas"`` routes the
    int8 codec through the fused in-register kernels of
    ``ops/pallas/ring_codec.py`` (bitwise-identical to the XLA build).
    Only int8 has a kernel: ``none``/``bf16`` have nothing to fuse and
    ``topk``'s top-k/scatter stays on the XLA path by design, so the
    knob is a no-op for them.
    """
    if codec_impl not in CODEC_IMPLS:
        raise ValueError(
            f"unknown codec impl {codec_impl!r}; choose from "
            f"{CODEC_IMPLS} (the fused int8 codec kernels live in "
            "ops/pallas/ring_codec.py)"
        )
    if name == "none":
        return WireScheme()
    if name == "bf16":
        return CastScheme(jnp.bfloat16)
    if name == "int8":
        return Int8Scheme(impl=codec_impl)
    if name == "topk":
        return TopKScheme(topk_frac)
    raise ValueError(
        f"unknown wire scheme {name!r}; choose from {WIRE_SCHEMES} "
        "(codecs live in ops/ring.py, the fused int8 kernels in "
        "ops/pallas/ring_codec.py)"
    )


def _resolve_scheme(scheme, wire_dtype) -> WireScheme | None:
    """Back-compat shim: the legacy ``wire_dtype`` kwarg maps onto the
    cast scheme; an explicit ``scheme`` wins.  None = exact (identity
    fast path: the uncompressed program is bit-identical to the
    pre-compression implementation)."""
    if scheme is not None:
        return None if scheme.name == "none" else scheme
    if wire_dtype is not None:
        return CastScheme(wire_dtype)
    return None


def ring_all_reduce_flat(
    x: jax.Array,
    axis_name: str,
    axis_size: int,
    mean: bool = False,
    wire_dtype=None,
    scheme: WireScheme | None = None,
    return_residual: bool = False,
    perm: list[tuple[int, int]] | None = None,
    ring_rank=None,
):
    """All-reduce a flat vector via an explicit ppermute ring.

    Must be called inside ``shard_map`` (or any context where ``axis_name``
    is bound).  ``axis_size`` is the static ring size (mesh axis length).

    ``perm``/``ring_rank`` (round 11): run the ring over a LOGICAL
    sub-axis of the bound mesh axis — ``perm`` is the full permutation
    table (one entry per physical rank; disjoint sub-rings run
    concurrently in each ppermute) and ``ring_rank`` this rank's traced
    position within its sub-ring of size ``axis_size``.  Defaults
    reproduce the flat whole-axis ring.  This is how the hierarchical
    all-reduce (``ops/topology.py``) reuses the codec + error-feedback
    machinery verbatim on the slow outer axis.

    ``scheme`` (a :class:`WireScheme`): compress every hop's payload —
    reduce-scatter hops dequantize–add–requantize, all-gather hops relay
    the encoded payload bit-exactly so every rank decodes the identical
    chunk.  ``wire_dtype`` (e.g. ``jnp.bfloat16``) is the legacy
    cast-only spelling of ``scheme=CastScheme(...)``; None/None = exact.

    ``return_residual``: also return this rank's error-feedback residual
    — COMPLETE local error accounting, zero extra collectives.  Every
    lossy encode in the ring is observed by exactly one rank:

    - *send error*: each reduce-scatter hop's sender sees
      ``partial − decode(encode(partial))`` — the mass that hop drops
      from the downstream accumulation.  Upstream ranks' errors were
      already theirs (the received value is the decode), so summing
      per-send errors over ranks counts every phase-1 drop exactly once;
    - *owner correction*: the rank that completed a chunk is the only
      one that sees both the true reduced chunk and its lossy broadcast
      encode; it re-injects that gap (× N under mean semantics, so the
      next step's mean moves by exactly the gap) — without this term
      the all-gather's loss is invisible to EF.

    Summed over ranks, the residuals equal the all-reduce's total
    compression error — the next step's reduction of ``grad + residual``
    recovers everything the wire dropped this step (EF-SGD with exact
    bookkeeping; arxiv 1711.00705's error compensation, DynamiQ's
    residual accumulation).
    """
    n = axis_size
    if n == 1:
        if return_residual:
            return x, jnp.zeros_like(x)
        return x
    scheme = _resolve_scheme(scheme, wire_dtype)

    orig_len = x.shape[0]
    chunk = -(-orig_len // n)  # ceil division
    padded = jnp.pad(x, (0, n * chunk - orig_len))
    chunks = padded.reshape(n, chunk)
    if perm is None:
        perm = _right_shift_perm(n)
    rank = lax.axis_index(axis_name) if ring_rank is None else ring_rank

    def hop(payload):
        return tuple(lax.ppermute(p, axis_name, perm) for p in payload)

    # Phase 1 — reduce-scatter.  The chunk index each rank touches at step s
    # is rank-dependent (r−s mod n), but ppermute needs every rank to execute
    # the same program; we roll the chunk axis by the (traced) rank once so
    # that the per-step indices become static: after rolling by −r, rank r's
    # "send chunk (r−s)" is row (−s mod n) for every rank.
    chunks = jnp.roll(chunks, -rank, axis=0)  # row i ≡ global chunk (i + r) mod n
    account = scheme is not None and return_residual
    res_rows = jnp.zeros_like(chunks) if account else None
    for s in range(n - 1):
        send_row = (-s) % n
        recv_row = (-s - 1) % n
        v = chunks[send_row]
        if scheme is None:
            recvd = lax.ppermute(v, axis_name, perm)
            chunks = chunks.at[recv_row].add(recvd)
        else:
            # One hop of dequantize–add–requantize: encode the partial,
            # permute the payload, decode-accumulate on arrival; the
            # requantize is the next hop's encode of the updated
            # partial.  Both pieces go through the scheme's fusion
            # seams, so the fused codec (Int8Scheme(impl="pallas"))
            # runs each as one in-register kernel.
            if account:
                # Send error: the mass THIS encode drops from the
                # downstream accumulation (decode(enc) is what the
                # receiver actually adds) — observed by the sender,
                # once per hop across the whole ring.
                enc, err = scheme.encode_with_residual(v)
                res_rows = res_rows.at[send_row].add(err)
            else:
                enc = scheme.encode(v)
            chunks = chunks.at[recv_row].set(
                scheme.decode_add(hop(enc), chunks[recv_row], chunk)
            )
    # Rank r now owns the full sum of global chunk (r+1) mod n == row 1.
    own = chunks[1 % n]
    if mean:
        own = own / n

    # Phase 2 — all-gather the completed chunks around the same ring.
    out = jnp.zeros_like(chunks)
    own_dec = own
    if scheme is None:
        out = out.at[1 % n].set(own)
        cur = own
        for s in range(n - 1):
            cur = lax.ppermute(cur, axis_name, perm)
            # After s+1 hops, the chunk arriving at rank r was completed by
            # rank (r − s − 1), i.e. global chunk (r − s) mod n == local row
            # (−s) mod n.
            out = out.at[(-s) % n].set(cur)
    else:
        # Encode the completed chunk ONCE, store its DECODE (the owner
        # must see exactly what receivers will see, or ranks end the
        # all-reduce with slightly different "synced" gradients and
        # replicated params silently drift apart), then relay the
        # encoded payload bit-exactly — every rank decodes identical
        # bits, so the replication invariant holds for lossy codecs too.
        payload = scheme.encode(own)
        own_dec = scheme.decode(payload, chunk).astype(x.dtype)
        out = out.at[1 % n].set(own_dec)
        for s in range(n - 1):
            payload = hop(payload)
            out = out.at[(-s) % n].set(
                scheme.decode(payload, chunk).astype(x.dtype)
            )
    # Undo the roll to restore global chunk order.
    out = jnp.roll(out, rank, axis=0)
    result = out.reshape(-1)[:orig_len]
    if not return_residual:
        return result
    if scheme is None:
        return result, jnp.zeros_like(x)
    # Owner correction on the owned row (row 1, the only row this rank
    # never sent): phase-1 send errors accumulated above are in SUM
    # units; the broadcast gap is in output units, so × N under mean
    # semantics makes the next step's mean move by exactly the gap.
    factor = float(n) if mean else 1.0
    res_rows = res_rows.at[1 % n].add(factor * (own - own_dec))
    res = jnp.roll(res_rows, rank, axis=0).reshape(-1)[:orig_len]
    return result, res


def _ring_gather_one(shard: jax.Array, axis_name: str, n: int) -> jax.Array:
    """One ring all-gather: local chunk → ``[n, L]`` in global rank
    order, via N−1 ppermute hops.

    Unlike the reduce ring (whose per-step SLICES need static indices,
    hence its roll-by-rank trick), the gather only WRITES — one
    dynamic-update-slice per hop at a traced row index is a single
    static-shape store, so the chunks land directly in global rank
    order and no roll/unroll repacking pass is ever materialized (a
    pair of whole-array permutes that measurably dominated the gather
    on the memcpy-bound CPU host)."""
    L = shard.shape[0]
    perm = _right_shift_perm(n)
    rank = lax.axis_index(axis_name)
    out = jnp.zeros((n, L), shard.dtype)
    # Own chunk is global row ``rank``; the chunk arriving after hop
    # s+1 was sent by rank (r − s − 1), whose chunk is that global row.
    out = lax.dynamic_update_slice(out, shard[None], (rank, 0))
    cur = shard
    for s in range(n - 1):
        cur = lax.ppermute(cur, axis_name, perm)
        out = lax.dynamic_update_slice(
            out, cur[None], ((rank - s - 1) % n, 0)
        )
    return out


def ring_all_gather_flat(
    shard: jax.Array,
    axis_name: str,
    axis_size: int,
    n_buckets: int = 1,
):
    """All-gather a flat shard via the ring's phase-2 structure.

    Rank r holds global chunk r (``shard``); after N−1 ppermute hops
    every rank holds the full ``[N·L]`` vector.  Pure data movement —
    bit-identical to ``lax.all_gather(shard, axis, tiled=True)`` — but
    spelled as a chunked ppermute chain so each hop's DMA gets its own
    async window, reused for the overlap-aware sharded weight update
    (arxiv 2004.13336), where the updated-parameter gather must stop
    feeding ROOT as one monolithic sync collective.

    ``n_buckets > 1`` splits the shard into that many independent rings
    whose hops interleave — the same bucket-pipelining that earns the
    reduce ring its comm/compute overlap (bucket k's DMA in flight
    while bucket k±1's assembly runs; schedule-verified on the v5e AOT
    target: 4 buckets → 4 DMAs concurrently in flight with assembly
    fusions inside the windows).  A single bucket is one serial hop
    chain: async, but with nothing of its own to hide under the DMAs.
    """
    n = axis_size
    if n == 1:
        return shard
    L = shard.shape[0]
    k = max(1, min(n_buckets, L))
    if k == 1:
        return _ring_gather_one(shard, axis_name, n).reshape(-1)
    bounds = [(i * L // k, (i + 1) * L // k) for i in range(k)]
    parts = [
        _ring_gather_one(shard[a:b], axis_name, n)
        for a, b in bounds
    ]
    # Reassemble [n, L] from the per-bucket [n, Lb] blocks, then
    # flatten: global layout is rank-major, bucket-minor.
    return jnp.concatenate(parts, axis=1).reshape(-1)


def _bucket_bounds(n_elems: int, bucket_bytes: int, itemsize: int):
    """(start, stop) element ranges of the ring buckets — ONE definition
    shared by the all-reduce/residual accounting and the static byte
    accounting, so the two can never chunk differently."""
    bucket_elems = max(1, int(bucket_bytes) // itemsize)
    return [
        (i, min(i + bucket_elems, n_elems))
        for i in range(0, n_elems, bucket_elems)
    ]


def ring_all_reduce(
    grads,
    axis_name: str,
    axis_size: int,
    mean: bool = True,
    bucket_bytes: int = DEFAULT_BUCKET_BYTES,
    wire_dtype=None,
    scheme: WireScheme | None = None,
    return_residual: bool = False,
    topology=None,
) -> object:
    """Bucketed ring all-reduce over a gradient pytree.

    ``mean=True`` reproduces DDP's averaging (part3 semantics — SURVEY.md
    §2.4); ``mean=False`` gives the SUM semantics of parts 2a/2b.
    ``scheme``/``wire_dtype``: optional on-the-wire compression;
    ``return_residual``: also return the per-rank error-feedback
    residual pytree (see :func:`ring_all_reduce_flat`).

    ``topology`` (round 11): an ``ops.topology.Topology`` descriptor —
    every bucket is dispatched through ``topology.select(bucket_bytes)``
    to the flat ring, the hierarchical (inner reduce-scatter →
    compressed outer ring → inner all-gather) path, or the
    recursive-halving-doubling latency path.  The descriptor carries the
    per-axis wire schemes, so ``scheme`` is ignored when it is given.
    ``topology=None`` compiles the exact historical flat-ring program.
    """
    flat, unravel = ravel_pytree(grads)
    if axis_size == 1 or flat.shape[0] == 0:
        if return_residual:
            return grads, jax.tree_util.tree_map(jnp.zeros_like, grads)
        return grads
    if topology is not None:
        from distributed_machine_learning_tpu.ops.topology import (
            topology_all_reduce_flat,
        )

        outs = [
            topology_all_reduce_flat(
                flat[start:stop],
                axis_name,
                topology,
                mean=mean,
                return_residual=return_residual,
            )
            for start, stop in _bucket_bounds(
                flat.shape[0], bucket_bytes, flat.dtype.itemsize
            )
        ]
    else:
        outs = [
            ring_all_reduce_flat(
                flat[start:stop],
                axis_name,
                axis_size,
                mean=mean,
                wire_dtype=wire_dtype,
                scheme=scheme,
                return_residual=return_residual,
            )
            for start, stop in _bucket_bounds(
                flat.shape[0], bucket_bytes, flat.dtype.itemsize
            )
        ]
    if return_residual:
        reduced = [o for o, _ in outs]
        residuals = [r for _, r in outs]
        return (
            unravel(reduced[0] if len(reduced) == 1
                    else jnp.concatenate(reduced)),
            unravel(residuals[0] if len(residuals) == 1
                    else jnp.concatenate(residuals)),
        )
    reduced = outs
    return unravel(reduced[0] if len(reduced) == 1 else jnp.concatenate(reduced))


def ring_wire_bytes(
    n_elems: int,
    axis_size: int,
    bucket_bytes: int = DEFAULT_BUCKET_BYTES,
    scheme: WireScheme | None = None,
    itemsize: int = 4,
    topology=None,
) -> int:
    """Static per-device wire bytes of ONE bucketed ring all-reduce:
    ``sum over buckets of 2·(N−1) hops × payload_bytes(chunk)``.

    Pure host arithmetic — the number the ``ring_wire_bytes`` telemetry
    counter accumulates per step, and the number the HLO audit
    (``analysis/overlap_audit.py --wire-bytes``) verifies against the
    compiled program's actual collective-permute operand shapes.

    ``topology``: total over both axes of the hierarchical plan (see
    :func:`ring_wire_bytes_by_axis` for the per-axis split).
    """
    if topology is not None:
        return sum(
            ring_wire_bytes_by_axis(
                n_elems, axis_size, bucket_bytes=bucket_bytes,
                scheme=scheme, itemsize=itemsize, topology=topology,
            ).values()
        )
    if axis_size <= 1 or n_elems <= 0:
        return 0
    scheme = scheme or WireScheme()
    total = 0
    for start, stop in _bucket_bounds(n_elems, bucket_bytes, itemsize):
        chunk = -(-(stop - start) // axis_size)
        total += 2 * (axis_size - 1) * scheme.payload_bytes(chunk, itemsize)
    return total


def ring_wire_bytes_by_axis(
    n_elems: int,
    axis_size: int,
    bucket_bytes: int = DEFAULT_BUCKET_BYTES,
    scheme: WireScheme | None = None,
    itemsize: int = 4,
    topology=None,
) -> dict[str, int]:
    """Per-AXIS static wire bytes — the split the round-11 telemetry
    counter labels (``ring_wire_bytes{axis=inner|outer|flat}``) carry
    and the per-axis HLO audit checks against the compiled program.

    Without a topology the flat ring's bytes all ride one undeclared
    link class: ``{"flat": total}``.  With one, each bucket's plan
    (``topology.select``) is accounted hop-by-hop and every hop's bytes
    are attributed by the SAME pair classifier the HLO walker uses
    (``ops.topology.classify_permute_pairs``): a hop whose
    permutation crosses an inner block is inter-node (outer-axis)
    traffic — which for the flat ring on a 2-D topology means ALL of
    its bytes, exactly the bottleneck the hierarchical plan divides by
    ``inner``.
    """
    if topology is None:
        return {"flat": ring_wire_bytes(
            n_elems, axis_size, bucket_bytes=bucket_bytes, scheme=scheme,
            itemsize=itemsize,
        )}
    from distributed_machine_learning_tpu.ops.topology import (
        topology_wire_bytes,
    )

    return topology_wire_bytes(
        n_elems, topology, bucket_bytes=bucket_bytes, itemsize=itemsize,
    )
