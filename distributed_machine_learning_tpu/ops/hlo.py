"""Walkers over the text of a compiled, scheduled HLO module.

What a compiled program does with its collectives, read off the artifact
that runs — ``compiled.as_text()`` of any backend (the CPU test mesh and
the TPU AOT target name the ops identically):

- :func:`audit_schedule` — every ``-start``/``-done`` pair is an async
  window in which the DMA is in flight; compute ops textually scheduled
  between start and done execute under that DMA.
- :func:`sync_collectives_from_hlo` — collectives issued without the
  split: on the critical path by construction.
- :func:`wire_bytes_from_hlo` — the operand bytes of every
  ``collective-permute`` the executable issues, by dtype and (with
  ``inner``) by ``ops/topology.py``'s rank blocks: the compiled side of
  the ring's static wire-byte accounting.
- :func:`all_reduces_from_hlo`, :func:`grad_sync_bytes` — every
  all-reduce of a train step, its bytes and whether it is asynchronous
  (``train/lm_step.py``'s two ``grad_sync_*`` gauges).
- :func:`flash_calls_from_hlo` — the flash-attention kernel calls the
  compiler kept, forward and backward (its ``flash_*_calls`` gauges).
- :func:`prepare_calls_from_hlo` — the delta rule's preparation kernels
  likewise, a decay a head and a decay a channel (its
  ``gdn_prepare_*_calls`` and ``kda_prepare_*_calls`` gauges);
  :func:`kernel_calls_from_hlo` is both in one reading.
- :func:`remat_instructions_from_hlo` — what XLA's own rematerialization
  pass made again to fit the device (its ``xla_remat_instructions`` gauge).

A schedule is not a device timeline: these prove what the executable
*orders* under a collective, while what is really hidden is a chip's
trace to say.  Pure text: ``re`` and ``collections`` only; the
programs are built and compiled by the callers
(``analysis/overlap_audit.py``, ``analysis/program_audit.py``).
"""

from __future__ import annotations

import collections
import re


# Collective kinds the async-window walker tracks (round 8: the
# analysis/program_audit passes reuse this walker for the zero1
# weight-update all-gather, so it is no longer permute-only).
ASYNC_COLLECTIVE_KINDS = (
    "collective-permute", "all-gather", "all-reduce", "reduce-scatter",
)
_KIND_ALT = "|".join(ASYNC_COLLECTIVE_KINDS)
_ASYNC_START_RE = re.compile(
    rf"%?(\S+) = .* ({_KIND_ALT})-start\(")
# A -done op closes the window its operand (the -start op) opened.  The
# operand list may spell the start's full tuple type inline
# (``collective-permute-done((f32[1066]{0:T(1024)}, ...) %cps.1)`` — the
# TPU backend does), so a lazy scan-to-first-paren mis-captures; instead
# the walker tokenizes everything after ``-done(`` and closes the first
# token that names an open window.
_ASYNC_DONE_RE = re.compile(rf"(?:{_KIND_ALT})-done\((.*)")
_NAME_TOKEN_RE = re.compile(r"%?([\w\.\-]+)")


def audit_schedule(hlo_text: str) -> dict:
    """Walk an optimized, scheduled HLO module; report per-async-window
    compute.  Returns a JSON-able summary dict.

    Tracks every async collective kind in :data:`ASYNC_COLLECTIVE_KINDS`
    (the ``-start``/``-done`` pairs); the legacy permute-only keys keep
    their meaning (``async_ppermute_pairs`` counts permute windows), and
    ``async_pairs_by_kind`` breaks all windows down per collective."""
    m = re.search(r"ENTRY [^\{]+\{(.*?)\n\}", hlo_text, re.S)
    if not m:
        raise ValueError("no ENTRY computation found in HLO text")
    compute_re = re.compile(
        r"%?(\S+) = .*?(fusion|convolution|dot|all-reduce(?!-)|"
        r"reduce-scatter(?!-))\("
    )
    open_pairs: dict[str, list] = {}
    open_kinds: dict[str, str] = {}
    in_flight, max_in_flight = 0, 0
    windows = []
    for line in m.group(1).splitlines():
        s = _ASYNC_START_RE.search(line)
        if s:
            open_pairs[s.group(1)] = []
            open_kinds[s.group(1)] = s.group(2)
            in_flight += 1
            max_in_flight = max(max_in_flight, in_flight)
            continue
        d = _ASYNC_DONE_RE.search(line)
        if d:
            name = next(
                (t for t in _NAME_TOKEN_RE.findall(d.group(1))
                 if t in open_pairs),
                None,
            )
            if name is not None:
                windows.append((name, open_kinds.pop(name),
                                open_pairs.pop(name)))
                in_flight -= 1
                continue
        c = compute_re.search(line)
        if c:
            for ops in open_pairs.values():
                ops.append((c.group(1), c.group(2)))
    # An op inside two concurrently-open windows counts once: the
    # metric is "distinct compute ops that execute under some in-flight
    # DMA", not a per-window tally.
    unique_ops = {name: kind for _, _, ops in windows for name, kind in ops}
    kinds = collections.Counter(unique_ops.values())
    permute = [w for w in windows if w[1] == "collective-permute"]
    return {
        "async_ppermute_pairs": len(permute),
        "pairs_with_compute_in_window": sum(
            1 for _, _, o in windows if o),
        "async_pairs_by_kind": dict(
            collections.Counter(k for _, k, _ in windows)),
        "pairs_with_compute_by_kind": dict(
            collections.Counter(k for _, k, o in windows if o)),
        "distinct_compute_ops_in_windows": len(unique_ops),
        "op_kinds_in_windows": dict(kinds),
        "max_concurrent_in_flight": max_in_flight,
    }


_SYNC_DEF_RE = re.compile(
    rf"%?([\w\.\-]+) = \(?\s*([a-z]+\d*\[[\d,]*\])[^=]*?"
    rf"\b({_KIND_ALT})(?!-start|-done)\(")


_GTE_RE = re.compile(
    r"%?([\w\.\-]+) = [^=]*get-tuple-element\([^%]*%([\w\.\-]+)\)"
)


def sync_collectives_from_hlo(hlo_text: str, kinds=None) -> list[dict]:
    """Every SYNC collective definition in the module — a collective
    issued without a ``-start``/``-done`` split sits on the critical
    path by construction (nothing can be scheduled under it).  Returns
    ``[{"name", "kind", "shape", "feeds_root"}]``; ``feeds_root`` is
    True when the op's result is a direct operand of its computation's
    ROOT — for a train step, the signature of a weight-update gather
    serialized against the step output (arxiv 2004.13336's target).
    Tuple-fused collectives (the TPU backend folds the gather into a
    variadic all-reduce whose elements reach ROOT via
    ``get-tuple-element``) are attributed through one GTE hop."""
    kinds = set(kinds or ASYNC_COLLECTIVE_KINDS)
    out = []
    root_operands: set[str] = set()
    gte_operand: dict[str, str] = {}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if stripped.startswith("ROOT "):
            root_operands.update(re.findall(r"%([\w\.\-]+)", stripped))
        g = _GTE_RE.search(line)
        if g:
            gte_operand[g.group(1)] = g.group(2)
        m = _SYNC_DEF_RE.search(line)
        if m and m.group(3) in kinds:
            out.append({"name": m.group(1), "kind": m.group(3),
                        "shape": m.group(2), "feeds_root": False})
    rooted = set(root_operands)
    rooted.update(op for gte, op in gte_operand.items()
                  if gte in root_operands)
    for rec in out:
        rec["feeds_root"] = rec["name"] in rooted
    return out


# HLO primitive-type widths (bytes) — the types a ring payload can carry
# (plus the widths the parser may meet in other programs' permutes).
_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

# A defining collective-permute line: ``%name = <shape> collective-permute(``
# or the async ``collective-permute-start(`` whose result is a tuple —
# group(1) grabs the FIRST shape either way, which for the start op is
# the operand buffer (counting the paired result buffer too would double
# every byte).  ``-done`` lines are uses of the start's buffers, skipped.
_CP_DEF_RE = re.compile(
    r"=\s*\(?\s*([a-z]+\d*\[[\d,]*\])[^=]*?\bcollective-permute"
    r"(?:-start)?\("
)

# The permute's routing table: ``source_target_pairs={{0,1},{1,2},...}``
# — the ground truth for attributing a compiled hop to a topology axis.
_PAIRS_RE = re.compile(r"source_target_pairs=\{((?:\{\d+,\d+\},?)*)\}")
_PAIR_RE = re.compile(r"\{(\d+),(\d+)\}")


def permute_pairs_from_line(line: str) -> list | None:
    """The ``source_target_pairs`` of one HLO line, or None."""
    m = _PAIRS_RE.search(line)
    if not m:
        return None
    return [(int(a), int(b)) for a, b in _PAIR_RE.findall(m.group(1))]


def _shape_bytes(shape: str) -> int:
    """``'f32[2,4]'`` → 32.  ``'f32[]'`` (scalar) → 4."""
    dtype, dims = shape.rstrip("]").split("[")
    if dtype not in _DTYPE_BYTES:
        raise ValueError(f"unknown HLO primitive type in {shape!r}")
    n = 1
    for d in filter(None, dims.split(",")):
        n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def wire_bytes_from_hlo(hlo_text: str, inner: int | None = None) -> dict:
    """Sum every collective-permute's operand bytes across the module.

    Walks ALL computations (not just ENTRY — a while-body ring on some
    backends hides the permutes one call deep) and counts each
    *defining* occurrence once.  Returns ``{"total_bytes", "count",
    "by_dtype": {prim: bytes}}``.

    ``inner`` (round 11): also attribute each permute's bytes to a
    topology axis from its compiled ``source_target_pairs`` routing
    (``ops.topology.classify_permute_pairs`` over inner-major blocks of
    that size — imported at call time so this module stays importable
    without jax, while compiled and static attribution share ONE
    classifier), adding ``"by_axis": {"inner": bytes, "outer": bytes}``
    — the per-axis number DML103 pins against the static
    ``ring_wire_bytes_by_axis`` accounting.  A permute with no routing
    table (never seen from the jax lowerings audited here) is charged
    to the outer axis: over-counting the bottleneck link is the safe
    direction."""
    if inner is not None:
        from distributed_machine_learning_tpu.ops.topology import (
            classify_permute_pairs,
        )
    total = 0
    count = 0
    by_dtype: dict[str, int] = {}
    by_axis = {"inner": 0, "outer": 0}
    for line in hlo_text.splitlines():
        m = _CP_DEF_RE.search(line)
        if not m:
            continue
        b = _shape_bytes(m.group(1))
        total += b
        count += 1
        prim = m.group(1).split("[")[0]
        by_dtype[prim] = by_dtype.get(prim, 0) + b
        if inner is not None:
            pairs = permute_pairs_from_line(line)
            axis = ("outer" if pairs is None
                    else classify_permute_pairs(pairs, inner))
            by_axis[axis] += b
    out = {"total_bytes": total, "count": count, "by_dtype": by_dtype}
    if inner is not None:
        out["by_axis"] = by_axis
    return out


# A computation header (``%name (params) -> result {`` / ``ENTRY %name ...``)
# and an all-reduce definition in any of its three spellings.
_COMPUTATION_RE = re.compile(r"^(ENTRY )?%?([\w\.\-]+) \(.*\{\s*$")
_ALL_REDUCE_DEF_RE = re.compile(
    r"^\s*(?:ROOT )?%?([\w\.\-]+) = (.*?)\ball-reduce(-start|-done)?\(")
_HLO_SHAPE_RE = re.compile(r"[a-z]+\d*\[[\d,]*\]")
_CALLS_RE = re.compile(r"calls=%?([\w\.\-]+)")
_INSTR_NAME_RE = re.compile(r"^\s*(?:ROOT )?%?([\w\.\-]+) = ")
# XLA:TPU's async-collective fusion spells one all-reduce three times:
# in the fused computation of an ``AsyncCollectiveStart`` custom call,
# in the ``async_collective_fusion.N`` body that steps it beside a
# matmul or an elementwise loop, and in the ``AsyncCollectiveDone`` one.
_ASYNC_FUSION_START = 'custom_call_target="AsyncCollectiveStart"'


def all_reduces_from_hlo(hlo_text: str) -> list[dict]:
    """Every all-reduce a compiled, scheduled module issues, once each:
    ``[{"name", "bytes", "async", "position"}]`` in schedule order.

    ``bytes`` is the result's size — every element of a tuple-shaped
    (combined) all-reduce counted.  ``async`` is True for an
    ``all-reduce-start``/``-done`` pair and for an async-collective
    fusion (counted at its start; the copies of the instruction inside
    the fusion's later steps are the same collective and are skipped);
    a plain ``all-reduce`` holds the core for its whole duration and is
    False.  ``position`` is the index in the ENTRY schedule of the
    instruction (or of the fusion that starts it) among its
    ``schedule_length`` instructions, None for one inside a loop body.
    """
    computations: dict[str, list[str]] = {}
    entry = current = None
    for line in hlo_text.splitlines():
        header = _COMPUTATION_RE.match(line)
        if header:
            current = header.group(2)
            computations[current] = []
            if header.group(1):
                entry = current
        elif line.startswith("}"):
            current = None
        elif current is not None:
            computations[current].append(line)
    if entry is None:
        raise ValueError("no ENTRY computation found in HLO text")
    fused = {callee for lines in computations.values() for line in lines
             if " fusion(" in line for callee in _CALLS_RE.findall(line)}
    # Where the ENTRY schedule holds an instruction, and where it calls a
    # computation.
    instruction_at: dict[str, int] = {}
    called_at: dict[str, int] = {}
    for i, line in enumerate(computations[entry]):
        for callee in _CALLS_RE.findall(line):
            called_at[callee] = i
        name = _INSTR_NAME_RE.match(line)
        if name:
            instruction_at[name.group(1)] = i
    out = []
    for comp, lines in computations.items():
        starts = any(_ASYNC_FUSION_START in line for line in lines)
        for line in lines:
            m = _ALL_REDUCE_DEF_RE.match(line)
            if not m or m.group(3) == "-done":
                continue
            if comp in fused and not starts:
                continue  # a later step of an async-collective fusion
            out.append({
                "name": m.group(1),
                "bytes": sum(_shape_bytes(s)
                             for s in _HLO_SHAPE_RE.findall(m.group(2))),
                "async": starts or m.group(3) == "-start",
                "position": (instruction_at.get(m.group(1))
                             if comp == entry else called_at.get(comp)),
                "schedule_length": len(computations[entry]),
            })
    out.sort(key=lambda r: (r["position"] is None, r["position"] or 0))
    return out


def grad_sync_bytes(rows: list[dict]) -> dict:
    """``{"grad_sync_bytes", "grad_sync_async_bytes"}`` of a compiled
    train step from its :func:`all_reduces_from_hlo` rows: bytes a chip
    all-reduces a step in all, and through asynchronous collectives."""
    return {
        "grad_sync_bytes": sum(r["bytes"] for r in rows),
        "grad_sync_async_bytes": sum(r["bytes"] for r in rows if r["async"]),
    }


_MOSAIC_CALL = 'custom_call_target="tpu_custom_call"'
# ``op_name="jit(step)/…/transpose(jvp(flash_bwd_fused_w2048))/pallas_call"``:
# a Pallas kernel's ``name=`` is the scope its call sits in, inside whatever
# transformations traced it.
_PALLAS_SCOPE_RE = re.compile(r'op_name="(?:[^"]*/)?([^"/]+)/pallas_call"')


def _mosaic_kernels(hlo_text: str):
    """The kernel name (``name=``) of every Mosaic custom call in the text,
    in order, less the transformations that traced it."""
    for line in hlo_text.splitlines():
        scope = _MOSAIC_CALL in line and _PALLAS_SCOPE_RE.search(line)
        if scope:
            yield re.sub(r"\w+\(|\)", "", scope.group(1))


_FLASH = ("flash_fwd", "flash_bwd")
_PREPARE = ("gdn_prepare_fwd", "gdn_prepare_bwd",
            "kda_prepare_fwd", "kda_prepare_bwd")


def _calls_by_prefix(hlo_text: str, prefixes) -> dict:
    kernels = list(_mosaic_kernels(hlo_text))
    return {f"{prefix}_calls": sum(k.startswith(prefix) for k in kernels)
            for prefix in prefixes}


def kernel_calls_from_hlo(hlo_text: str) -> dict:
    """:func:`flash_calls_from_hlo` and :func:`prepare_calls_from_hlo` in
    one reading of the text: what a step publishes."""
    return _calls_by_prefix(hlo_text, _FLASH + _PREPARE)


def flash_calls_from_hlo(hlo_text: str) -> dict:
    """``{"flash_fwd_calls", "flash_bwd_calls"}``: the module's Mosaic
    custom calls whose kernel name starts with ``flash_fwd`` / ``flash_bwd``
    (``ops/pallas/flash_attention.py``; a split backward counts its two
    kernels).  What the compiler kept, not what was asked for: a block
    recomputed without the kernel's ``(out, lse)`` shows each layer's
    forward call twice.  Counted in the text — a call in a loop's body is
    one; off the TPU the kernels are interpreted and both read 0."""
    return _calls_by_prefix(hlo_text, _FLASH)


def prepare_calls_from_hlo(hlo_text: str) -> dict:
    """``{"gdn_prepare_fwd_calls", "gdn_prepare_bwd_calls",
    "kda_prepare_fwd_calls", "kda_prepare_bwd_calls"}``: the delta rule's
    preparation kernels (``ops/pallas/gdn_prepare.py``, a decay a head;
    ``ops/pallas/kda_prepare.py``, a decay a key channel) among the module's
    Mosaic calls — one and one a delta-rule layer of a train step (the
    forward pass's preparation, kept for the backward pass; the reverse),
    two and one where a block is recomputed as a whole.  0 in a step with
    such layers: ``ops/delta_rule.py::state_pass`` said ``"scan"``."""
    return _calls_by_prefix(hlo_text, _PREPARE)


# ``%fusion.986.remat = …``, ``%gte.remat.16 = …``, ``%fusion.2026.remat3 =
# …``: the instructions the rematerialization pass cloned.
_REMAT_INSTRUCTION_RE = re.compile(
    r"^\s*(?:ROOT )?%?[\w.\-]*\.remat[\w.\-]* = ", re.MULTILINE)


def remat_instructions_from_hlo(hlo_text: str) -> int:
    """The instructions XLA's rematerialization pass added to the module: a
    scheduled program that would not fit the device's memory as written
    computes them a second time, each named after the one it copies with a
    ``.remat`` suffix.  0 where the program fits as it is; JAX's own
    recomputation (``jax.checkpoint``) is not counted."""
    return len(_REMAT_INSTRUCTION_RE.findall(hlo_text))
