"""2-D topology layer over the ppermute ring — compressed multi-hop
all-reduce with per-axis wire accounting (round 11).

The flat ring (``ops/ring.py``) treats every hop as equally expensive;
on a real pod the links are NOT uniform — intra-node (ICI/NVLink-class)
hops are cheap and inter-node (DCN-class) hops are the bottleneck.
DynamiQ (PAPERS.md, arxiv 2602.08923) frames the win as *compressed
multi-hop* all-reduce over the hierarchy; this module is that layer:

- :class:`Topology` — the descriptor: ``inner`` (fast-axis / intra-node
  world) × ``outer`` (slow-axis / inter-node world) with a per-axis
  :class:`~distributed_machine_learning_tpu.ops.ring.WireScheme`.  Ranks
  are inner-major: node ``o`` owns the contiguous block
  ``[o·inner, (o+1)·inner)``, so an inner hop stays inside a block and
  an outer hop jumps between blocks at stride ``inner``.
- :func:`hierarchical_all_reduce_flat` — the three-phase plan:
  (1) reduce-scatter on the fast inner axis (``inner−1`` hops), leaving
  each rank the NODE-sum of one 1/inner chunk; (2) a compressed ring
  all-reduce (reusing the round-7 codec + error-feedback machinery of
  ``ring_all_reduce_flat`` verbatim, via its ``perm``/``ring_rank``
  sub-ring form) on the slow outer axis over that 1/inner of the data —
  the inter-node traffic drops to ~1/inner of the flat ring's; (3)
  all-gather back down the inner axis.  Lossy codecs keep every rank's
  output BIT-IDENTICAL (encoded payloads are relayed verbatim, the
  flat ring's replication invariant), and the per-axis residuals still
  sum to the all-reduce's total compression error (see the residual
  contract below).
- :func:`halving_doubling_all_reduce_flat` — recursive halving +
  doubling for latency-bound small buckets: the same 2·(N−1)/N bytes
  as the ring but only ``2·log2(N)`` serial hops (the ring's
  ``2·(N−1)``), the classic latency-optimal exchange.
- ``Topology.select(bucket_bytes)`` — the per-bucket auto-selector the
  bucketed ``ring_all_reduce(topology=...)`` dispatches through.

**Residual contract (per-axis error feedback).**  The flat ring's EF
invariant is: summed over ranks, the residuals equal N × (exact mean −
output) — so reducing ``grad + residual`` next step recovers everything
the wire dropped.  The hierarchical plan preserves it per axis:

- an inner reduce-scatter hop's sender keeps ``v − decode(encode(v))``
  (the mass that encode drops from its node-sum, hence from the total
  sum — sum units, counted once);
- the outer sub-ring runs with SUM semantics and its own EF bookkeeping
  (``ring_all_reduce_flat(return_residual=True)``), so the residuals it
  hands back already sum to the outer phase's total drop in sum units;
- the inner all-gather encodes the finished (meaned) chunk ONCE per
  node; the chunk's owner in each node keeps ``inner × (own −
  decode(encode(own)))`` — there are ``outer`` such owners holding the
  identical gap (the encode is deterministic over bit-identical
  inputs), so the gaps total ``N × gap``, exactly the broadcast loss in
  the sum-unit convention.

Summing every rank's residual therefore still equals N × (exact mean −
output) — asserted to 1e-4 in ``tests/test_topology.py`` for codecs on
either axis or both.
"""

from __future__ import annotations

import dataclasses
import re

import jax
import jax.numpy as jnp
from jax import lax

from distributed_machine_learning_tpu.ops.ring import (
    CODEC_IMPLS,
    WIRE_SCHEMES,
    WireScheme,
    _bucket_bounds,
    get_wire_scheme,
    ring_all_reduce_flat,
)

#: When a lossy codec was requested, halving-doubling (which is exact
#: and would silently discard the codec) only takes buckets at or
#: under this size — the regime where per-chunk codec metadata and
#: encode compute rival the payload itself.  This is a FIDELITY bound,
#: not a performance threshold: the cost model below decides perf, but
#: silently rerouting a requested codec onto an exact plan is only
#: defensible where the codec could not have paid for itself anyway.
HD_LOSSY_MAX_BYTES = 4 * 1024


@dataclasses.dataclass(frozen=True)
class LinkModel:
    """Per-axis link cost model (round 20): the digital twin's notion
    of what one ``ppermute`` costs on a pod.

    Wormhole/cut-through routing semantics: a permute at ring distance
    ``d`` on an axis pays the axis's per-message **overhead once** (the
    header cuts through intermediate switches without store-and-forward
    buffering) but its **payload occupies d links** of that axis's ring
    — the congestion/bandwidth term scales with distance while the
    latency term does not.  That asymmetry is what gives every
    topology×scheme cell a genuine flat/hier/hd crossover: hd spends
    fewer serial overheads than hier but its long-distance exchanges
    multiply bytes across links, so hd wins small buckets and hier wins
    large ones (2x4 exact: the crossover sits at
    ``8·outer_overhead_s·outer_bytes_per_s`` = 1 MiB-ish under the
    defaults; 4x2 exact: ``4·inner_overhead_s·inner_bytes_per_s``).

    Defaults are ICI-class intra-node links (~1 µs, 100 GB/s) and
    DCN-class inter-node links (~5 µs, 25 GB/s) — the fast/slow axis
    split the :class:`Topology` descriptor declares.  Calibration:
    ``tests/test_netmodel.py`` pins the model's per-axis bytes to the
    static ``topology_wire_bytes`` accounting (itself pinned to the
    compiled HLO by DML103) and its plan ordering to the CPU-mesh rows
    of ``BENCH_r11_hier.json``: not calibrated on v5e (ROADMAP D5).
    """

    inner_overhead_s: float = 1.0e-6
    inner_bytes_per_s: float = 100.0e9
    outer_overhead_s: float = 5.0e-6
    outer_bytes_per_s: float = 25.0e9

    def permute_time(self, axis: str, distance: int, nbytes: int) -> float:
        """Modeled seconds for one permute: overhead once, bytes across
        ``distance`` links of the axis ring."""
        if axis == "inner":
            return (self.inner_overhead_s
                    + distance * nbytes / self.inner_bytes_per_s)
        return (self.outer_overhead_s
                + distance * nbytes / self.outer_bytes_per_s)


DEFAULT_LINK_MODEL = LinkModel()

_TOPOLOGY_RE = re.compile(r"^\s*(\d+)\s*[x×X]\s*(\d+)\s*$")


def parse_topology(spec: str) -> tuple[int, int]:
    """``"2x4"`` (also ``2×4``) → ``(inner, outer)``; raises ValueError
    on anything else — the parse-time half of ``--ring-topology``
    validation (the world-equality half needs the mesh and lives in
    ``RingAllReduce.topology_for``)."""
    m = _TOPOLOGY_RE.match(spec or "")
    if not m:
        raise ValueError(
            f"topology spec {spec!r} is not of the form INNERxOUTER "
            "(e.g. '2x4': inner=intra-node world, outer=inter-node world)"
        )
    inner, outer = int(m.group(1)), int(m.group(2))
    if inner < 1 or outer < 1:
        raise ValueError(
            f"topology axes must be >= 1, got {inner}x{outer}"
        )
    return inner, outer


def _is_pow2(n: int) -> bool:
    return n >= 1 and (n & (n - 1)) == 0


@dataclasses.dataclass(frozen=True)
class Topology:
    """inner×outer factorization of the mesh's data axis, with a wire
    scheme per axis.

    ``inner``: the fast-axis world (chips sharing a node's cheap
    links); ``outer``: the slow-axis world (nodes).  ``inner_scheme`` /
    ``outer_scheme`` name the per-axis codecs (``ops.ring.WIRE_SCHEMES``)
    — the CLI maps ``--ring-compress`` onto the OUTER axis (compress
    where the wire is expensive) and leaves the inner axis exact, but
    the descriptor supports compressing either or both.
    ``hd_max_bytes`` (round 20): an OPTIONAL admissibility cap on the
    halving-doubling plan — ``None`` (default) lets the cost model
    decide, ``0`` disables hd entirely, and a positive value admits hd
    only at or under that many bytes; the lossy fidelity bound
    :data:`HD_LOSSY_MAX_BYTES` is applied on top in every case.
    ``codec_impl`` (round 13): the int8 codec implementation both axes
    resolve — ``"pallas"`` runs the fused in-register kernels
    (``ops/pallas/ring_codec.py``), bitwise-identical to ``"xla"``.
    """

    inner: int
    outer: int
    inner_scheme: str = "none"
    outer_scheme: str = "none"
    topk_frac: float = 0.125
    hd_max_bytes: int | None = None
    codec_impl: str = "xla"

    def __post_init__(self):
        if self.inner < 1 or self.outer < 1:
            raise ValueError(
                f"topology axes must be >= 1, got "
                f"{self.inner}x{self.outer}"
            )
        for name in (self.inner_scheme, self.outer_scheme):
            if name not in WIRE_SCHEMES:
                raise ValueError(
                    f"unknown wire scheme {name!r}; choose from "
                    f"{WIRE_SCHEMES}"
                )
        if self.codec_impl not in CODEC_IMPLS:
            raise ValueError(
                f"unknown codec impl {self.codec_impl!r}; choose from "
                f"{CODEC_IMPLS}"
            )

    @property
    def world(self) -> int:
        return self.inner * self.outer

    # -- per-axis codecs ------------------------------------------------

    def axis_scheme(self, axis: str) -> WireScheme:
        name = self.inner_scheme if axis == "inner" else self.outer_scheme
        return get_wire_scheme(name, topk_frac=self.topk_frac,
                               codec_impl=self.codec_impl)

    def _scheme_or_none(self, axis: str) -> WireScheme | None:
        s = self.axis_scheme(axis)
        return None if s.name == "none" else s

    def _flat_axis(self) -> str:
        """Which axis a FLAT whole-world ring's traffic rides: with one
        node (outer==1) every hop is intra-node; otherwise the ring
        crosses node boundaries and its bytes are charged to the
        bottleneck inter-node links (see ``classify_permute_pairs``)."""
        return "inner" if self.outer == 1 else "outer"

    # -- selector (round 20: prediction-driven, no byte threshold) ------

    def _hd_admissible(self, bucket_bytes: int) -> bool:
        """Whether halving-doubling may even be CONSIDERED for this
        bucket — correctness/fidelity gates, not performance (the cost
        model owns performance): pairwise exchange needs a power-of-two
        world; when a lossy codec was requested, hd (which is exact and
        would silently discard it) is only admissible at or under
        :data:`HD_LOSSY_MAX_BYTES`; an explicit ``hd_max_bytes`` caps
        it further (``0`` disables hd outright)."""
        if not (_is_pow2(self.world) and self.world >= 4):
            return False
        cap = self.hd_max_bytes
        if self.inner_scheme != "none" or self.outer_scheme != "none":
            cap = (HD_LOSSY_MAX_BYTES if cap is None
                   else min(cap, HD_LOSSY_MAX_BYTES))
        return cap is None or bucket_bytes <= cap

    def plan_hops(
        self, bucket_bytes: int, plan: str, itemsize: int = 4,
    ) -> list[tuple[str, int, int]]:
        """The serial hop schedule of one bucket under ``plan``: a list
        of ``(axis, distance, payload_bytes)``, one entry per
        ``ppermute`` on the program's critical path.

        The per-axis payload accounting is EXACTLY
        :func:`topology_wire_bytes` re-expressed hop-by-hop (asserted
        in ``tests/test_netmodel.py``), so the cost model prices the
        same bytes the HLO audit counts; ``distance`` is the axis-ring
        distance the payload travels (1 for ring hops, ``2**s`` scaled
        into node units for the hd exchanges — the congestion input of
        :meth:`LinkModel.permute_time`).
        """
        n = self.world
        blen = -(-bucket_bytes // itemsize)
        hops: list[tuple[str, int, int]] = []
        if n <= 1 or blen <= 0:
            return hops
        if plan == "flat":
            chunk = -(-blen // n)
            axis = self._flat_axis()
            pb = self.axis_scheme(axis).payload_bytes(chunk, itemsize)
            hops.extend([(axis, 1, pb)] * (2 * (n - 1)))
        elif plan == "hd":
            chunk = -(-blen // n)
            for s in range(n.bit_length() - 1):
                d = 1 << s
                # An exchange at rank distance d stays inside a block
                # when d < inner (power-of-two factors nest), else it
                # jumps d/inner nodes — the same block arithmetic as
                # classify_permute_pairs, with the distance kept.
                axis, dist = (("inner", d) if d < self.inner
                              else ("outer", d // self.inner))
                pb = (n >> (s + 1)) * chunk * itemsize
                hops.extend([(axis, dist, pb)] * 2)
        elif plan == "hier":
            chunk_i = -(-blen // self.inner)
            chunk_o = -(-chunk_i // self.outer)
            pb_i = self.axis_scheme("inner").payload_bytes(
                chunk_i, itemsize)
            pb_o = self.axis_scheme("outer").payload_bytes(
                chunk_o, itemsize)
            hops.extend([("inner", 1, pb_i)] * (2 * (self.inner - 1)))
            hops.extend([("outer", 1, pb_o)] * (2 * (self.outer - 1)))
        else:
            raise ValueError(f"unknown plan {plan!r}")
        return hops

    def predict_bucket_time(
        self,
        bucket_bytes: int,
        plan: str | None = None,
        link: LinkModel | None = None,
        itemsize: int = 4,
    ) -> float:
        """Modeled seconds for one bucket's all-reduce under ``plan``
        (default: whatever :meth:`select` picks under the same link
        model) — the sum of the hop schedule through the link model."""
        link = link or DEFAULT_LINK_MODEL
        if plan is None:
            plan = self.select(bucket_bytes, link=link)
        return sum(
            link.permute_time(axis, dist, pb)
            for axis, dist, pb in self.plan_hops(bucket_bytes, plan,
                                                 itemsize)
        )

    def select(self, bucket_bytes: int,
               link: LinkModel | None = None) -> str:
        """Pick the plan for one bucket: ``"flat"`` / ``"hier"`` /
        ``"hd"`` — by PREDICTED hop time under the link model (round
        20), not a hard-coded byte threshold.

        - a degenerate axis (inner==1 or outer==1) means there is no
          hierarchy to exploit: the flat ring, with the live axis's
          scheme, for EVERY bucket size — bit-for-bit the round-7
          program, never a crash and never a silent reroute (the
          ``--ring-topology 1xN`` contract);
        - otherwise every admissible plan is priced through
          :meth:`plan_hops` × :class:`LinkModel` and the cheapest wins.
          Under the default pod parameters that reproduces the old
          policy's *shape* from first principles: hd (fewest serial
          overheads) takes small buckets, hier (1/inner the inter-node
          bytes) takes large ones, and the crossover now moves with
          the topology and link speeds instead of sitting at a frozen
          64 KiB.  hd admissibility (:meth:`_hd_admissible`) stays a
          correctness/fidelity gate: power-of-two worlds only, lossy
          codecs never silently discarded above
          :data:`HD_LOSSY_MAX_BYTES`, ``hd_max_bytes=0`` still
          disables the plan.  Ties go to ``hier`` (keeps the codec).
        """
        if self.world == 1 or self.inner == 1 or self.outer == 1:
            # Degenerate axis FIRST: the documented contract is that a
            # 1-sized axis IS the flat ring, bit-for-bit the round-7
            # program — routing its small buckets to hd would change
            # the association order (and could discard a codec) behind
            # the user's declared no-hierarchy topology.
            return "flat"
        link = link or DEFAULT_LINK_MODEL
        candidates = ["hier"]
        if self._hd_admissible(bucket_bytes):
            candidates.append("hd")
        candidates.append("flat")
        best, best_t = None, None
        for plan in candidates:
            t = self.predict_bucket_time(bucket_bytes, plan, link=link)
            if best_t is None or t < best_t:
                best, best_t = plan, t
        return best

    # -- static permutation tables (one entry per physical rank; the
    #    disjoint sub-rings all move in a single ppermute) --------------

    def inner_perm(self) -> list[tuple[int, int]]:
        """Right-shift ring inside every inner block."""
        return [
            (o * self.inner + i, o * self.inner + (i + 1) % self.inner)
            for o in range(self.outer)
            for i in range(self.inner)
        ]

    def outer_perm(self) -> list[tuple[int, int]]:
        """Right-shift ring across blocks at stride ``inner``, one ring
        per inner position."""
        return [
            (o * self.inner + i,
             ((o + 1) % self.outer) * self.inner + i)
            for o in range(self.outer)
            for i in range(self.inner)
        ]

    def hd_perm(self, step: int) -> list[tuple[int, int]]:
        """Pairwise exchange at rank distance ``2**step``."""
        return [(r, r ^ (1 << step)) for r in range(self.world)]


def hierarchical_all_reduce_flat(
    x: jax.Array,
    axis_name: str,
    topo: Topology,
    mean: bool = True,
    return_residual: bool = False,
):
    """Hierarchical all-reduce of a flat vector inside ``shard_map``.

    Reduce-scatter on the inner axis → compressed ring on the outer
    axis over 1/inner of the data → all-gather down the inner axis.
    Requires ``inner > 1`` and ``outer > 1`` (degenerate axes are
    dispatched to the flat ring by ``topology_all_reduce_flat``).

    Every rank ends with IDENTICAL bits (lossy encodes are relayed
    verbatim and decoded everywhere, including by their producer), and
    with ``return_residual`` the per-axis EF residuals sum — over all
    N ranks — to N × (exact mean − output): the module docstring's
    residual contract.
    """
    inner, outer = topo.inner, topo.outer
    n = topo.world
    assert inner > 1 and outer > 1, "degenerate topology must go flat"
    inner_scheme = topo._scheme_or_none("inner")
    outer_scheme = topo._scheme_or_none("outer")
    perm_inner = topo.inner_perm()

    rank = lax.axis_index(axis_name)
    inner_idx = rank % inner
    outer_idx = rank // inner

    orig_len = x.shape[0]
    chunk = -(-orig_len // inner)
    chunks = jnp.pad(x, (0, inner * chunk - orig_len)).reshape(inner, chunk)

    def hop(payload):
        return tuple(
            lax.ppermute(p, axis_name, perm_inner) for p in payload
        )

    # Phase 1 — inner reduce-scatter (same roll-by-rank trick as the
    # flat ring, over the inner sub-ring): after inner−1 hops this rank
    # holds the NODE-sum of global inner-chunk (inner_idx+1) mod inner,
    # at local row 1.
    chunks = jnp.roll(chunks, -inner_idx, axis=0)
    account = return_residual and (
        inner_scheme is not None or outer_scheme is not None
    )
    res_rows = jnp.zeros_like(chunks) if account else None
    for s in range(inner - 1):
        send_row = (-s) % inner
        recv_row = (-s - 1) % inner
        v = chunks[send_row]
        if inner_scheme is None:
            recvd = lax.ppermute(v, axis_name, perm_inner)
            chunks = chunks.at[recv_row].add(recvd)
        else:
            # Routed through the scheme's fusion seams (round 13) like
            # the flat ring, so the fused int8 codec collapses each
            # piece to one in-register kernel on this axis too.
            if account:
                # Send error: mass this encode drops from the node-sum,
                # hence from the total sum — sum units, sender-observed,
                # once per hop (the flat ring's phase-1 bookkeeping).
                enc, err = inner_scheme.encode_with_residual(v)
                res_rows = res_rows.at[send_row].add(err)
            else:
                enc = inner_scheme.encode(v)
            chunks = chunks.at[recv_row].set(
                inner_scheme.decode_add(hop(enc), chunks[recv_row], chunk)
            )
    own = chunks[1 % inner]

    # Phase 2 — compressed ring all-reduce on the outer axis, SUM
    # semantics (one global mean division below keeps the accounting in
    # sum units throughout).  The round-7 codec + EF machinery runs
    # unchanged on the sub-ring via perm/ring_rank.
    outer_out = ring_all_reduce_flat(
        own,
        axis_name,
        outer,
        mean=False,
        scheme=outer_scheme,
        return_residual=account,
        perm=topo.outer_perm(),
        ring_rank=outer_idx,
    )
    if account:
        outer_out, outer_res = outer_out
    own_final = outer_out / n if mean else outer_out

    # Phase 3 — all-gather back down the inner axis: encode the
    # finished chunk ONCE, relay the payload bit-exactly, decode it on
    # every rank (owner included) — the replication invariant.
    out_rows = jnp.zeros_like(chunks)
    own_dec = own_final
    if inner_scheme is None:
        out_rows = out_rows.at[1 % inner].set(own_final)
        cur = own_final
        for s in range(inner - 1):
            cur = lax.ppermute(cur, axis_name, perm_inner)
            out_rows = out_rows.at[(-s) % inner].set(cur)
    else:
        payload = inner_scheme.encode(own_final)
        own_dec = inner_scheme.decode(payload, chunk).astype(x.dtype)
        out_rows = out_rows.at[1 % inner].set(own_dec)
        for s in range(inner - 1):
            payload = hop(payload)
            out_rows = out_rows.at[(-s) % inner].set(
                inner_scheme.decode(payload, chunk).astype(x.dtype)
            )
    result = jnp.roll(out_rows, inner_idx, axis=0).reshape(-1)[:orig_len]
    if not return_residual:
        return result
    if not account:
        return result, jnp.zeros_like(x)
    # Owner corrections on the owned row: the outer sub-ring's residual
    # (already sum units), plus the inner broadcast gap.  Each node's
    # owner holds the identical gap (deterministic encode of identical
    # bits), so the `outer` copies need a per-owner factor of
    # N/outer = inner under mean semantics (total = N × gap) and
    # 1/outer under sum semantics (total = gap).
    res_rows = res_rows.at[1 % inner].add(outer_res)
    gfactor = float(inner) if mean else 1.0 / outer
    res_rows = res_rows.at[1 % inner].add(gfactor * (own_final - own_dec))
    res = jnp.roll(res_rows, inner_idx, axis=0).reshape(-1)[:orig_len]
    return result, res


def halving_doubling_all_reduce_flat(
    x: jax.Array,
    axis_name: str,
    axis_size: int,
    mean: bool = True,
):
    """Recursive halving-doubling all-reduce (exact, power-of-two
    worlds): ``log2 N`` pairwise-exchange reduce-scatter steps at rank
    distances 1, 2, 4, …, then the mirror ``log2 N`` all-gather steps —
    the same 2·(N−1)/N per-device bytes as the ring in 2·log2 N serial
    hops instead of 2·(N−1), the latency-optimal exchange for small
    buckets.

    Every chunk's total is computed at its owning rank through one
    fixed reduction tree and broadcast verbatim, so all ranks end with
    IDENTICAL bits (and, the sum being a single association order, the
    result is deterministic across plans only up to float rounding —
    the selector never mixes plans within one bucket).
    """
    n = axis_size
    if n == 1:
        return x
    if not _is_pow2(n):
        raise ValueError(
            f"halving-doubling needs a power-of-two world, got {n}"
        )
    k = n.bit_length() - 1
    orig_len = x.shape[0]
    chunk = -(-orig_len // n)
    a = jnp.pad(x, (0, n * chunk - orig_len)).reshape(n, chunk)
    rank = lax.axis_index(axis_name)

    # Recursive halving (reduce-scatter).  Invariant entering step s:
    # `a` holds the partial sums of the chunks whose low s index bits
    # equal this rank's, row-indexed by the remaining high bits — so
    # row parity IS chunk bit s, and the rank-dependent "send the half
    # whose bit s differs from mine" is a traced select of two static
    # strided slices (the payload halves each step: the halving).
    for s in range(k):
        bit = ((rank >> s) & 1) == 1
        evens, odds = a[0::2], a[1::2]
        send = jnp.where(bit, evens, odds)
        keep = jnp.where(bit, odds, evens)
        recvd = lax.ppermute(
            send, axis_name, [(r, r ^ (1 << s)) for r in range(n)]
        )
        a = keep + recvd
    own = a[0]  # the chunk whose index == this rank, fully summed
    if mean:
        own = own / n

    # Recursive doubling (all-gather): unfix the bits in reverse order;
    # after the step at distance 2**s the array holds the chunks whose
    # low s bits match, row-indexed by chunk >> s — interleaving the
    # kept and received halves lands the final array in GLOBAL chunk
    # order with no repacking pass.
    b = own[None]
    for s in reversed(range(k)):
        recvd = lax.ppermute(
            b, axis_name, [(r, r ^ (1 << s)) for r in range(n)]
        )
        bit = ((rank >> s) & 1) == 1
        first = jnp.where(bit, recvd, b)   # chunks with bit s == 0
        second = jnp.where(bit, b, recvd)  # chunks with bit s == 1
        b = jnp.stack([first, second], axis=1).reshape(-1, chunk)
    return b.reshape(-1)[:orig_len]


def topology_all_reduce_flat(
    x: jax.Array,
    axis_name: str,
    topo: Topology,
    mean: bool = True,
    return_residual: bool = False,
    plan: str | None = None,
):
    """One bucket's all-reduce under a topology: dispatch through
    ``topo.select`` (or an explicit ``plan``) to flat / hier / hd.

    The flat fallback carries the live axis's wire scheme (a 1-sized
    axis degenerates to exactly the round-7 compressed ring); the hd
    path is exact, so its residual is identically zero.
    """
    plan = plan or topo.select(x.shape[0] * x.dtype.itemsize)
    if plan == "hier":
        return hierarchical_all_reduce_flat(
            x, axis_name, topo, mean=mean,
            return_residual=return_residual,
        )
    if plan == "hd":
        out = halving_doubling_all_reduce_flat(
            x, axis_name, topo.world, mean=mean
        )
        if return_residual:
            return out, jnp.zeros_like(x)
        return out
    return ring_all_reduce_flat(
        x,
        axis_name,
        topo.world,
        mean=mean,
        scheme=topo._scheme_or_none(topo._flat_axis()),
        return_residual=return_residual,
    )


# ---------------------------------------------------------------------------
# Static per-axis wire accounting.
# ---------------------------------------------------------------------------


def classify_permute_pairs(pairs, inner: int) -> str:
    """Attribute one permute's routing to a topology axis (round 11).

    Ranks are inner-major (see :class:`Topology`): node ``o`` is the
    contiguous block ``[o·inner, (o+1)·inner)``.  A permute whose every
    pair stays inside a block is intra-node (``"inner"``); one with ANY
    cross-block pair is charged to the inter-node links (``"outer"``) —
    bottleneck-rank accounting: the block-edge ranks of a flat ring
    push every hop's payload inter-node, so a mixed permute's bytes ARE
    outer-axis exposure.  The HLO walker
    (``ops.hlo.wire_bytes_from_hlo``) classifies compiled
    ``source_target_pairs`` through this same function, so compiled and
    static attribution can never drift."""
    if any(s // inner != t // inner for s, t in pairs):
        return "outer"
    return "inner"


def topology_wire_bytes(
    n_elems: int,
    topo: Topology,
    bucket_bytes: int,
    itemsize: int = 4,
) -> dict[str, int]:
    """Per-device wire bytes of one bucketed topology all-reduce, split
    ``{"inner": ..., "outer": ...}`` by the link class each hop rides.

    Every hop is attributed through the SAME permutation-pair
    classifier the HLO audit applies to the compiled program's
    ``source_target_pairs`` (:func:`classify_permute_pairs`, which
    ``ops.hlo.wire_bytes_from_hlo`` imports) — the static
    accounting and the executable attribution cannot chunk or classify
    differently.  Note
    the flat plan's bytes land on the OUTER axis whenever the ring
    crosses nodes: the bottleneck-link exposure is the honest number
    (the block-edge ranks push every hop inter-node), and it is exactly
    what the hierarchical plan divides by ``inner``.
    """
    out = {"inner": 0, "outer": 0}
    if n_elems <= 0 or topo.world <= 1:
        return out
    n = topo.world
    for start, stop in _bucket_bounds(n_elems, bucket_bytes, itemsize):
        blen = stop - start
        plan = topo.select(blen * itemsize)
        if plan == "flat":
            chunk = -(-blen // n)
            axis = classify_permute_pairs(
                [(r, (r + 1) % n) for r in range(n)], topo.inner
            )
            scheme = topo.axis_scheme(topo._flat_axis())
            out[axis] += 2 * (n - 1) * scheme.payload_bytes(chunk, itemsize)
        elif plan == "hd":
            chunk = -(-blen // n)
            k = n.bit_length() - 1
            for s in range(k):
                axis = classify_permute_pairs(topo.hd_perm(s), topo.inner)
                # The halving step at distance 2**s and its mirror
                # doubling step each move (n >> (s+1)) chunks.
                out[axis] += 2 * (n >> (s + 1)) * chunk * itemsize
        else:  # hier
            chunk_i = -(-blen // topo.inner)
            chunk_o = -(-chunk_i // topo.outer)
            si = topo.axis_scheme("inner")
            so = topo.axis_scheme("outer")
            # inner reduce-scatter + inner all-gather: (inner−1) hops
            # each, payload one inner chunk through the inner codec.
            axis = classify_permute_pairs(topo.inner_perm(), topo.inner)
            out[axis] += (
                2 * (topo.inner - 1) * si.payload_bytes(chunk_i, itemsize)
            )
            # outer compressed ring: 2·(outer−1) hops over 1/inner of
            # the data — the 1/inner_world inter-node reduction.
            axis = classify_permute_pairs(topo.outer_perm(), topo.inner)
            out[axis] += (
                2 * (topo.outer - 1) * so.payload_bytes(chunk_o, itemsize)
            )
    return out
