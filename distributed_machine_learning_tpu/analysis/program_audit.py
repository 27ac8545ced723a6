"""Layer 2: jaxpr/HLO audit passes over COMPILED train steps.

Layer 1 lints what the source says; these passes check what the
executable actually does — the invariants live in the compiled
artifact, and source-level truth can be compiled away (an unaliasable
donation silently becomes a copy; a "sharded" update can still gather
on the critical path).  Builds on ``ops/hlo.py``'s HLO-text
walkers (the ppermute overlap audit and the wire-byte parser grew
there; this module generalizes them into reusable passes):

- :func:`audit_donation` — every donated operand must appear in the
  module's ``input_output_alias`` map; a donated-but-copied buffer
  doubles peak memory exactly where donation was supposed to save it
  (the ISSUE 1 restore-then-donate class, seen from the program side).
- :func:`audit_critical_path_collectives` — SYNC collectives (no
  ``-start``/``-done`` split) sit on the critical path by construction;
  for the zero1 weight update this is the all-gather that "Automatic
  Cross-Replica Sharding of Weight Update in Data-Parallel Training"
  (arxiv 2004.13336) eliminates.  The overlap-aware update (ISSUE 9:
  ``make_zero1_train_step(overlap=True)`` splits the step into an
  update program and a bucketed-ring consume program) landed, so this
  is now an ERROR for the zero1 step — the historical advisory phase
  is over and a re-serialized gather fails the run.
- :func:`audit_ring_wire_accounting` — the compiled program's
  collective-permute payload bytes must equal the static
  ``ops.ring.ring_wire_bytes`` accounting for every wire scheme (the
  generalization of ISSUE 7's single CI assertion): the telemetry
  counter and the executable can never drift apart silently.
- :func:`audit_step_host_callbacks` — a jaxpr pass: no host callback
  primitives (``pure_callback``/``io_callback``/debug prints) inside a
  compiled train step — the program-level twin of Layer 1's DML004.

jax is imported lazily INSIDE the passes that need it; importing this
module stays stdlib-cheap (the parsers are pure text).
"""

from __future__ import annotations

import re
from typing import Iterable, Sequence

from distributed_machine_learning_tpu.analysis.findings import Finding
from distributed_machine_learning_tpu.analysis.overlap_audit import (
    compile_ring_hlo,
)
from distributed_machine_learning_tpu.ops.hlo import (
    audit_schedule,
    sync_collectives_from_hlo,
    wire_bytes_from_hlo,
)

# Layer-2 rule ids (DML1xx so a --rules filter can select layers).
RULE_DONATION = "DML101"
RULE_CRITICAL_PATH = "DML102"
RULE_WIRE_ACCOUNTING = "DML103"
RULE_HOST_CALLBACK = "DML104"

_ALIAS_ENTRY_RE = re.compile(
    r"\{([\d,\s]*)\}:\s*\((\d+),\s*\{([\d,\s]*)\}"
)
_ENTRY_LAYOUT_RE = re.compile(r"entry_computation_layout=\{\((.*?)\)->")
_SHAPE_RE = re.compile(r"[a-z]+\d*\[[^\]]*\](?:\{[^}]*\})?")


def parse_input_output_alias(hlo_text: str) -> list[dict]:
    """The module header's donation/alias map as
    ``[{"output_index", "param_number", "param_index"}]`` — empty when
    XLA took no donation at all."""
    # Brace-balanced extraction: the map nests braces per entry
    # (``{ {0}: (0, {}, may-alias), ... }``), so a lazy regex would
    # stop at the first inner ``}``.
    start = hlo_text.find("input_output_alias={")
    if start < 0:
        return []
    i = hlo_text.index("{", start)
    depth, j = 0, i
    for j in range(i, min(len(hlo_text), i + 1_000_000)):
        if hlo_text[j] == "{":
            depth += 1
        elif hlo_text[j] == "}":
            depth -= 1
            if depth == 0:
                break
    blob = hlo_text[i + 1:j]
    out = []
    for om, pnum, pidx in _ALIAS_ENTRY_RE.findall(blob):
        out.append({
            "output_index": [int(x) for x in om.split(",") if x.strip()],
            "param_number": int(pnum),
            "param_index": [int(x) for x in pidx.split(",")
                            if x.strip()],
        })
    return out


def entry_param_shapes(hlo_text: str) -> list[str]:
    """The entry computation's parameter shapes, in order."""
    m = _ENTRY_LAYOUT_RE.search(hlo_text)
    if not m:
        return []
    return _SHAPE_RE.findall(m.group(1))


def audit_donation(hlo_text: str, donated_params: Iterable[int],
                   label: str = "train_step") -> list[Finding]:
    """Donation actually taken: every parameter index in
    ``donated_params`` must appear in the compiled module's
    ``input_output_alias`` map.  A missing entry means XLA inserted a
    copy of the donated operand — the buffer is NOT reused, peak memory
    holds two copies of the state, and on real checkpoint-sized params
    that is the difference between fitting and OOM."""
    aliased = {e["param_number"] for e in parse_input_output_alias(hlo_text)}
    shapes = entry_param_shapes(hlo_text)
    findings = []
    for p in donated_params:
        if p in aliased:
            continue
        shape = shapes[p] if p < len(shapes) else "?"
        findings.append(Finding(
            rule=RULE_DONATION, file=label, line=0,
            message=(
                f"donated operand {p} ({shape}) is not aliased to any "
                "output in the compiled module — XLA copied it instead "
                "of reusing the buffer (dtype/shape mismatch or a live "
                "second use); donation is silently not taken"
            ),
            snippet=f"param {p}: {shape}", severity="error", layer=2,
        ))
    return findings


def audit_critical_path_collectives(
    hlo_text: str, kinds: Sequence[str] = ("all-gather",),
    label: str = "train_step", severity: str = "error",
) -> list[Finding]:
    """No sync collective of the given kinds on the critical path.

    A collective compiled WITHOUT the ``-start``/``-done`` split cannot
    overlap anything — it serializes the step at exactly the point the
    sharded weight update was supposed to be free (2004.13336).  An
    async pair whose window contains no compute is flagged the same
    way: in-flight but hiding nothing.  Severity defaults to ``error``
    since the overlap-aware weight update landed (ISSUE 9); pass
    ``severity="advisory"`` for programs still carrying documented
    debt."""
    findings = []
    for rec in sync_collectives_from_hlo(hlo_text, kinds=kinds):
        where = ("feeds the step output directly"
                 if rec["feeds_root"] else "mid-step")
        findings.append(Finding(
            rule=RULE_CRITICAL_PATH, file=label, line=0,
            message=(
                f"sync {rec['kind']} ({rec['shape']}) on the critical "
                f"path ({where}) — compiled without -start/-done, so "
                "nothing overlaps it; the weight-update gather belongs "
                "under the next step's backward (arxiv 2004.13336)"
            ),
            snippet=f"{rec['name']} = {rec['shape']} {rec['kind']}(...)",
            severity=severity, layer=2,
        ))
    try:
        sched = audit_schedule(hlo_text)
    except ValueError:
        sched = None
    if sched is not None:
        # Per-KIND emptiness: permute windows full of compute must not
        # mask an all-gather window that hides nothing.
        empty = any(
            sched["async_pairs_by_kind"].get(k, 0) > 0
            and sched["pairs_with_compute_by_kind"].get(k, 0) == 0
            for k in kinds)
        if empty:
            findings.append(Finding(
                rule=RULE_CRITICAL_PATH, file=label, line=0,
                message=(
                    "async collective windows contain no compute — the "
                    "DMA is in flight but hides nothing; effectively "
                    "still on the critical path"
                ),
                severity=severity, layer=2,
            ))
    return findings


def audit_ring_wire_accounting(
    mesh, length: int, schemes: Sequence[str] = ("none", "int8"),
    bucket_bytes: int = 8192, topk_frac: float = 0.125,
    label: str = "ring_all_reduce", topology: str | None = None,
    codec_impl: str = "xla",
) -> tuple[list[Finding], dict]:
    """Compiled collective-permute bytes == static ``ring_wire_bytes``
    accounting, per wire scheme — the telemetry counter's number and
    the executable's number must be the same number (ISSUE 7's CI
    assertion, generalized to every scheme).  Returns
    ``(findings, {scheme: {"hlo_bytes", "static_bytes", "permutes"}})``.

    ``topology`` ("INNERxOUTER", round 11): audit the hierarchical
    build instead, PER AXIS — each permute's compiled
    ``source_target_pairs`` routing is attributed to the inner or
    outer axis and must equal the static per-axis accounting
    (``ring_wire_bytes_by_axis``); the known XLA:CPU bf16-widening
    signature stays an advisory, carried per axis.  Additionally the
    exact hierarchical build's OUTER-axis (inter-node) bytes must be
    ≤ (1/inner + 5%) of the exact FLAT ring's total — the DynamiQ
    multi-hop reduction, proven on the compiled artifact."""
    from distributed_machine_learning_tpu.ops.ring import (
        get_wire_scheme,
        ring_wire_bytes,
        ring_wire_bytes_by_axis,
    )

    n = mesh.shape[mesh.axis_names[0]]
    findings = []
    table: dict = {}
    topo = None
    if topology is not None:
        from distributed_machine_learning_tpu.ops.topology import (
            Topology,
            parse_topology,
        )

        t_inner, t_outer = parse_topology(topology)
        flat_exact = ring_wire_bytes(length, n, bucket_bytes=bucket_bytes)
    for scheme_name in schemes:
        if topology is not None:
            topo = Topology(t_inner, t_outer, outer_scheme=scheme_name,
                            topk_frac=topk_frac, hd_max_bytes=0,
                            codec_impl=codec_impl)
            hlo = compile_ring_hlo(mesh, length, compress=scheme_name,
                                   topk_frac=topk_frac,
                                   bucket_bytes=bucket_bytes,
                                   topology=topology, hd_max_bytes=0,
                                   codec_impl=codec_impl)
            got = wire_bytes_from_hlo(hlo, inner=t_inner)
            want_axes = ring_wire_bytes_by_axis(
                length, n, bucket_bytes=bucket_bytes, topology=topo)
            full_width = ring_wire_bytes_by_axis(
                length, n, bucket_bytes=bucket_bytes,
                topology=Topology(t_inner, t_outer, hd_max_bytes=0))
            table[scheme_name] = {"hlo_bytes": got["total_bytes"],
                                  "hlo_by_axis": got["by_axis"],
                                  "static_by_axis": want_axes,
                                  "permutes": got["count"]}
            for axis in ("inner", "outer"):
                got_ax = got["by_axis"][axis]
                want_ax = want_axes[axis]
                if got_ax == want_ax:
                    continue
                widened = got_ax == full_width[axis]
                findings.append(Finding(
                    rule=RULE_WIRE_ACCOUNTING, file=label, line=0,
                    message=(
                        f"wire scheme {scheme_name!r} ({topology}): "
                        f"compiled program moves {got_ax} "
                        f"collective-permute bytes on the {axis} axis "
                        f"but the static per-axis accounting says "
                        f"{want_ax}"
                        + (" — the backend widened the sub-32-bit "
                           "payload to full 32-bit words (known "
                           "XLA:CPU behavior); validate the reduction "
                           "on the TPU target" if widened else
                           " — the per-axis ring_wire_bytes telemetry "
                           "counter is lying about the executable")
                    ),
                    snippet=f"{scheme_name}@{axis}: hlo={got_ax} "
                            f"static={want_ax}",
                    severity="advisory" if widened else "error", layer=2,
                ))
            if scheme_name == "none":
                bound = (1.0 / t_inner + 0.05) * flat_exact
                if t_inner > 1 and got["by_axis"]["outer"] > bound:
                    findings.append(Finding(
                        rule=RULE_WIRE_ACCOUNTING, file=label, line=0,
                        message=(
                            f"hierarchical {topology} exact build moves "
                            f"{got['by_axis']['outer']} outer-axis "
                            f"(inter-node) bytes — more than "
                            f"(1/{t_inner} + 5%) of the flat ring's "
                            f"{flat_exact}-byte total; the multi-hop "
                            "inter-node reduction has regressed"
                        ),
                        snippet=(f"outer={got['by_axis']['outer']} "
                                 f"flat_total={flat_exact}"),
                        severity="error", layer=2,
                    ))
            continue
        hlo = compile_ring_hlo(mesh, length, compress=scheme_name,
                               topk_frac=topk_frac,
                               bucket_bytes=bucket_bytes,
                               codec_impl=codec_impl)
        got = wire_bytes_from_hlo(hlo)
        scheme = (None if scheme_name == "none"
                  else get_wire_scheme(scheme_name, topk_frac=topk_frac,
                                       codec_impl=codec_impl))
        want = ring_wire_bytes(length, n, bucket_bytes=bucket_bytes,
                               scheme=scheme)
        full_width = ring_wire_bytes(length, n, bucket_bytes=bucket_bytes)
        table[scheme_name] = {"hlo_bytes": got["total_bytes"],
                              "static_bytes": want,
                              "permutes": got["count"]}
        if got["total_bytes"] != want:
            # The one known benign shape: XLA:CPU widens sub-32-bit
            # collective payloads back to 32-bit words (bf16 wire
            # compiles to f32 permutes; s8 stays narrow), so on the CI
            # backend a 16-bit scheme's savings do not materialize.
            # That is a true statement about THIS executable — reported
            # — but it is a backend property, not a codec bug, so it is
            # advisory here and an error on targets that can carry the
            # narrow dtype (the TPU AOT audit).
            widened = got["total_bytes"] == full_width
            findings.append(Finding(
                rule=RULE_WIRE_ACCOUNTING, file=label, line=0,
                message=(
                    f"wire scheme {scheme_name!r}: compiled program "
                    f"moves {got['total_bytes']} collective-permute "
                    f"bytes but the static ring_wire_bytes accounting "
                    f"says {want}"
                    + (" — the backend widened the sub-32-bit payload "
                       "to full 32-bit words (known XLA:CPU behavior); "
                       "validate the reduction on the TPU target"
                       if widened else
                       " — the ring_wire_bytes telemetry counter is "
                       "lying about the executable")
                ),
                snippet=f"{scheme_name}: hlo={got['total_bytes']} "
                        f"static={want}",
                severity="advisory" if widened else "error", layer=2,
            ))
    return findings, table


# ``debug_print`` is the Pallas-kernel spelling (``pl.debug_print``):
# under the interpreter it is a host round-trip per grid step, and on
# TPU a trace-slowing scalar dump — same class of leak as the XLA
# callbacks, visible now that the walker descends kernel jaxprs.
_CALLBACK_PRIMITIVES = ("pure_callback", "io_callback", "debug_callback",
                        "debug_print")


def audit_step_host_callbacks(fn, *args, label: str = "train_step",
                              allowed: Sequence[str] = ()) -> list[Finding]:
    """Jaxpr pass: no host-callback primitives inside a compiled step.

    ``jax.debug.print`` / ``pure_callback`` inside a train step round-
    trips device→host EVERY step — the program-level version of Layer
    1's DML004 (which can only see syncs the loop spells out).  ``fn``
    is traced (not compiled) with ``jax.make_jaxpr`` over ``args``
    (shape structs are fine); nested jaxprs (pjit/scan/cond bodies,
    shard_map, AND ``pallas_call`` kernel bodies — a Pallas kernel's
    params carry an *open* Jaxpr, not a ClosedJaxpr, so the walker
    descends both spellings and the audit sees through the round-13
    fused-kernel boundary) are walked recursively."""
    import jax

    jaxpr = jax.make_jaxpr(fn)(*args)
    hits: list[str] = []

    def _sub(v):
        # ClosedJaxpr carries .jaxpr; an open Jaxpr (pallas_call's
        # kernel param) IS the walkable object itself.
        inner = getattr(v, "jaxpr", None)
        if inner is not None:
            return inner
        return v if hasattr(v, "eqns") else None

    def walk(jx):
        for eqn in jx.eqns:
            name = eqn.primitive.name
            if name in _CALLBACK_PRIMITIVES and name not in allowed:
                hits.append(name)
            for v in eqn.params.values():
                sub = _sub(v)
                if sub is not None:
                    walk(sub)
                elif isinstance(v, (list, tuple)):
                    for item in v:
                        s = _sub(item)
                        if s is not None:
                            walk(s)

    walk(jaxpr.jaxpr)
    return [Finding(
        rule=RULE_HOST_CALLBACK, file=label, line=0,
        message=(
            f"host callback primitive {name!r} inside the compiled "
            "step — a device→host round-trip on every step; move it "
            "behind a profiling guard in the driver loop"
        ),
        snippet=name, severity="error", layer=2,
    ) for name in hits]


# ---------------------------------------------------------------------------
# Whole-program entry points (what tools/dmlcheck.py --layer2 runs)
# ---------------------------------------------------------------------------

def _vggtest_setup():
    """(model, init_fn, state_shape) for the audits' canonical tiny
    model — VGGTest keeps the compiles tier-affordable while every
    structural property under audit is model-size-independent."""
    import jax
    import jax.numpy as jnp

    from distributed_machine_learning_tpu.models.vgg import VGGTest
    from distributed_machine_learning_tpu.train.state import TrainState

    model = VGGTest()

    def init():
        variables = model.init(jax.random.PRNGKey(0),
                               jnp.zeros((1, 32, 32, 3)))
        return TrainState.create(params=variables["params"],
                                 rng=jax.random.PRNGKey(1))

    return model, init, jax.eval_shape(init)


def _audit_ring_strategy(mesh, strategy, label: str,
                         global_batch: int = 16) -> list[Finding]:
    """Shared body of the ring-step audits: compile the part3 train
    step under ``strategy`` and run the donation, critical-path
    (permute-only) and host-callback passes.  Stateful strategies
    (error-feedback codecs) lower the inner 4-ary program so donation
    covers the threaded residual too."""
    import jax
    import jax.numpy as jnp

    from distributed_machine_learning_tpu.train.step import make_train_step

    model, _, state_shape = _vggtest_setup()
    step = make_train_step(model, strategy, mesh=mesh, augment=False)
    x = jax.ShapeDtypeStruct((global_batch, 32, 32, 3), jnp.float32)
    y = jax.ShapeDtypeStruct((global_batch,), jnp.int32)
    n_leaves = len(jax.tree_util.tree_leaves(state_shape))
    if getattr(strategy, "stateful", False):
        res = jax.eval_shape(
            lambda: step.fresh_sync_state(state_shape.params))
        hlo = step.inner.lower(state_shape, x, y, res).compile().as_text()
        n_res = len(jax.tree_util.tree_leaves(res))
        # Flat entry params: state leaves, then x, y, then the residual
        # (a copied residual would double the EF memory exactly where
        # it is per-device by design).
        donated = list(range(n_leaves)) + list(
            range(n_leaves + 2, n_leaves + 2 + n_res))
        cb_args = (step.inner, state_shape, x, y, res)
    else:
        hlo = step.lower(state_shape, x, y).compile().as_text()
        donated = list(range(n_leaves))
        cb_args = (step, state_shape, x, y)
    findings = audit_donation(hlo, donated, label=label)
    findings += audit_critical_path_collectives(
        hlo, kinds=("all-gather",), label=label, severity="error")
    findings += audit_step_host_callbacks(*cb_args, label=label)
    return findings


def audit_ring_step(mesh, global_batch: int = 16,
                    codec_impl: str | None = None) -> list[Finding]:
    """Compile the part3 ring train step for ``mesh``; run the donation
    audit (every state leaf is donated via donate_argnums=(0,)), the
    critical-path all-gather pass (the ring must have NONE — it is
    permute-only), and the jaxpr host-callback pass.

    ``codec_impl`` (round 13): audit the COMPRESSED ring instead —
    int8 + error feedback with the given codec implementation.  With
    ``"pallas"`` this is the fused-kernel build: the audits must see
    through the ``pallas_call`` boundary and prove the fused step is
    still permute-only and fully donated (EF residual included), with
    zero new baseline entries."""
    from distributed_machine_learning_tpu.parallel.strategies import (
        get_strategy,
    )

    if codec_impl is None:
        return _audit_ring_strategy(
            mesh, get_strategy("ring"), "ring_step",
            global_batch=global_batch)
    return _audit_ring_strategy(
        mesh,
        get_strategy("ring", compress="int8", codec_impl=codec_impl),
        f"ring_step_int8_{codec_impl}", global_batch=global_batch)


def audit_hier_ring_step(mesh, global_batch: int = 16,
                         topology: str | None = None,
                         codec_impl: str = "xla") -> list[Finding]:
    """Round 11: compile the part3 train step under the TOPOLOGY-aware
    hierarchical ring (int8 outer codec + error feedback — the
    stateful build, so donation covers the threaded residual too) and
    hold it to the flat ring's program invariants:

    - donation taken on every state leaf AND the EF residual pytree
      (the residual is donated argnum 3 — a copied residual would
      double the EF memory exactly where it is per-device by design);
    - permute-only: the hierarchical phases (inner reduce-scatter,
      outer compressed ring, inner all-gather, halving-doubling) must
      all lower to collective-permutes — an ``all-gather`` appearing on
      the critical path means phase 3 re-serialized into the monolithic
      collective the explicit ring exists to replace;
    - no host callbacks in the jaxpr.

    ``codec_impl="pallas"`` (round 13) audits the fused-kernel build of
    the same program — the knob must not change any invariant.
    """
    from distributed_machine_learning_tpu.parallel.strategies import (
        get_strategy,
    )

    n = mesh.shape[mesh.axis_names[0]]
    if topology is None:
        topology = f"2x{n // 2}" if n % 2 == 0 else f"1x{n}"
    label = ("hier_ring_step" if codec_impl == "xla"
             else f"hier_ring_step_{codec_impl}")
    return _audit_ring_strategy(
        mesh,
        get_strategy("ring", compress="int8", topology=topology,
                     codec_impl=codec_impl),
        label, global_batch=global_batch)


def audit_zero1_step(mesh, global_batch: int = 16,
                     fused_update: bool = False) -> list[Finding]:
    """Compile the OVERLAP-AWARE zero1 train step (the default build
    this audit gates since ISSUE 9) — both phases:

    - the **update program** must contain no all-gather at all (the
      2004.13336 anti-pattern is structurally impossible: the program
      ends at the updated shard) — checked at ERROR severity, so a
      change that re-serializes the gather into the step fails CI;
    - the **consume program** (bucketed ring gather) must be
      permute-only — an all-gather reappearing there is the same
      regression wearing the other program's clothes;
    - donation on the update program: the momentum buffers (the only
      donated operands — param_flat cannot alias the sharded output,
      and step/rng are wrapper-carried) must actually alias.

    ``fused_update`` (round 13): audit the AdamW build with the fused
    one-pass update kernel (``AdamWConfig(fused=True)``) — the update
    program the overlap work can least afford to bloat.  The same
    invariants must hold THROUGH the ``pallas_call`` boundary: the
    fused moments still alias (the kernel's ``input_output_aliases``
    must not break the jit-level donation), and the update program
    stays gather-free.

    The legacy sync build (``overlap=False``) still exists for parity
    testing; it is not audited here because its
    critical-path gather is now a *documented baseline*, not the
    shipped default."""
    import jax
    import jax.numpy as jnp

    from distributed_machine_learning_tpu.parallel.zero1 import (
        make_zero1_train_step,
        shard_zero1_state,
    )

    model, init_state, _ = _vggtest_setup()
    state = init_state()
    if fused_update:
        from distributed_machine_learning_tpu.train.adamw import (
            AdamWConfig,
            adamw_init,
        )

        cfg = AdamWConfig(fused=True)
        state = state.replace(config=cfg,
                              momentum=adamw_init(state.params))
    z1, unravel, n_elems = shard_zero1_state(state, mesh)
    step = make_zero1_train_step(model, mesh, unravel, n_elems,
                                 augment=False, overlap=True)
    zshape = jax.eval_shape(lambda: z1)
    x = jax.ShapeDtypeStruct((global_batch, 32, 32, 3), jnp.float32)
    y = jax.ShapeDtypeStruct((global_batch,), jnp.int32)
    upd_hlo = step.update_for(z1.config).lower(
        zshape.param_flat, zshape.momentum_shards, zshape.batch_stats,
        zshape.step, zshape.rng, x, y,
    ).compile().as_text()
    gather_hlo = step.gather_inner.lower(
        zshape.param_flat
    ).compile().as_text()

    # Donated operands of the update program: momentum (+ BN stats when
    # present) — flat entry params 1..1+len(mom)+len(stats).
    n_donated = len(jax.tree_util.tree_leaves(
        (zshape.momentum_shards, zshape.batch_stats)
    ))
    suffix = "_fused" if fused_update else ""
    findings = audit_donation(
        upd_hlo, range(1, 1 + n_donated), label=f"zero1_update{suffix}")
    findings += audit_critical_path_collectives(
        upd_hlo, kinds=("all-gather",), label=f"zero1_update{suffix}",
        severity="error")
    findings += audit_critical_path_collectives(
        gather_hlo, kinds=("all-gather",), label=f"zero1_gather{suffix}",
        severity="error")
    return findings


def audit_fsdp_perlayer_step(mesh, batch: int = 8, seq: int = 16
                             ) -> list[Finding]:
    """Compile the per-layer (GSPMD) FSDP LM step and verify the
    overlap-aware structure it claims: one all-gather per parameter AT
    ITS USE SITE — so there must be SEVERAL gathers (per-leaf, not one
    monolithic prelude) and NONE of them may feed ROOT (the updated
    params leave the program in their SHARDED layout; a gather feeding
    ROOT would mean the update's output was re-gathered onto the
    critical path — the 2004.13336 anti-pattern in GSPMD clothing)."""
    import jax
    import jax.numpy as jnp

    from distributed_machine_learning_tpu.models.transformer import (
        TransformerLM,
    )
    from distributed_machine_learning_tpu.parallel.fsdp_perlayer import (
        make_fsdp_pl_lm_train_step,
        shard_fsdp_pl_state,
    )
    from distributed_machine_learning_tpu.train.adamw import AdamWConfig
    from distributed_machine_learning_tpu.train.lm_step import init_lm_state

    model = TransformerLM(vocab_size=64, d_model=32, n_layers=2,
                          n_heads=4, attn_impl="dense")
    state = shard_fsdp_pl_state(
        init_lm_state(model, seed=0, config=AdamWConfig()), mesh
    )
    step = make_fsdp_pl_lm_train_step(model, mesh)
    sshape = jax.eval_shape(lambda: state)
    x = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    y = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    hlo = step.lower(sshape, x, y).compile().as_text()

    findings = []
    gathers = sync_collectives_from_hlo(hlo, kinds=("all-gather",))
    rooted = [g for g in gathers if g["feeds_root"]]
    for g in rooted:
        findings.append(Finding(
            rule=RULE_CRITICAL_PATH, file="fsdp_perlayer_step", line=0,
            message=(
                f"per-layer FSDP all-gather {g['name']} ({g['shape']}) "
                "feeds ROOT — the updated params must leave the program "
                "sharded (gathers belong at the NEXT use site, where "
                "the scheduler overlaps them with the previous layer's "
                "compute); a root-feeding gather puts the weight update "
                "back on the critical path (arxiv 2004.13336)"
            ),
            snippet=f"{g['name']} = {g['shape']} all-gather(...)",
            severity="error", layer=2,
        ))
    # Structural sanity: per-layer means SEVERAL gathers (use-site, one
    # per sharded leaf neighborhood), not one monolithic prelude.
    if len(gathers) < 2:
        findings.append(Finding(
            rule=RULE_CRITICAL_PATH, file="fsdp_perlayer_step", line=0,
            message=(
                f"per-layer FSDP step compiled with {len(gathers)} "
                "all-gather(s) — the per-leaf use-site gathers the "
                "scheme is named for have collapsed into a monolithic "
                "(or absent) gather; overlap with the consuming forward "
                "is no longer possible"
            ),
            severity="error", layer=2,
        ))
    return findings


def audit_dp_lm_step(
    topology_name: str = "v5e:2x2", *, d_model: int = 3072,
    n_layers: int = 4, n_heads: int = 24, n_kv_heads: int = 2,
    vocab_size: int = 49152, seq_len: int = 4096, seqs_per_chip: int = 2,
    fused_ce_chunks: int = 8, sync_limit_bytes: int = 64 * 2**20,
) -> tuple[list[Finding], dict]:
    """DML102's twin for the replicated LM step: AOT-compile
    ``cli.lm --parallel dp`` for a DESCRIBED multi-chip TPU topology (no
    chip; needs libtpu) and report every all-reduce of the schedule —
    bytes, synchronous or asynchronous, position
    (``ops.hlo.all_reduces_from_hlo``).  The defaults are the
    benchmark cell ``sc2_3b_dp_w4``'s sizes (``benchmark/configs/
    starcoder2_3b.json``, ``benchmark/traffic/dp_2x4096_w4.json``; bf16,
    flash attention, AdamW).  A synchronous all-reduce of more than
    ``sync_limit_bytes`` is an ERROR: it holds the core for its whole
    duration where ``train/lm_step.py``'s compiler options were to run it
    beside the backward pass or the optimizer.

    By hand (``tools/dmlcheck.py --dp-lm-step``, about a minute), not part
    of ``--layer2``: a process that describes a TPU topology holds
    libtpu, which the test suite allows one file only (``tests/
    benchmark_checks/test_benchmark_aot_fit.py``).  A schedule is not a
    timeline: what an asynchronous reduction really hides is a chip's
    trace to say."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from distributed_machine_learning_tpu.analysis.overlap_audit import (
        _tpu_topology_mesh,
    )
    from distributed_machine_learning_tpu.ops.hlo import (
        all_reduces_from_hlo,
        grad_sync_bytes,
    )
    from distributed_machine_learning_tpu.models.transformer import (
        TransformerLM,
    )
    from distributed_machine_learning_tpu.train.adamw import AdamWConfig
    from distributed_machine_learning_tpu.train.lm_step import (
        init_lm_state,
        make_lm_train_step,
    )

    devices = _tpu_topology_mesh(topology_name).devices
    mesh = Mesh(devices.reshape(devices.size, 1), ("batch", "seq"))
    model = TransformerLM(
        vocab_size=vocab_size, d_model=d_model, n_layers=n_layers,
        n_heads=n_heads, n_kv_heads=n_kv_heads, compute_dtype=jnp.bfloat16,
        attn_impl="flash")
    replicated = NamedSharding(mesh, P())
    state = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=replicated),
        jax.eval_shape(lambda: init_lm_state(model, config=AdamWConfig())))
    tokens = jax.ShapeDtypeStruct(
        (seqs_per_chip * devices.size, seq_len), jnp.int32,
        sharding=NamedSharding(mesh, P("batch", "seq")))
    step = make_lm_train_step(model, mesh=mesh,
                              fused_ce_chunks=fused_ce_chunks)
    # A described device as the default makes ``ops/pallas`` compile its
    # kernels with Mosaic, as on the chip, instead of interpreting them.
    with jax.default_device(devices.flat[0]):
        compiled = step.lower(state, tokens, tokens).compile()
    rows = all_reduces_from_hlo(compiled.as_text())
    memory = compiled.memory_analysis()
    label = f"dp_lm_step_{topology_name.replace(':', '_')}"
    findings = [
        Finding(
            rule=RULE_CRITICAL_PATH, file=label, line=0,
            message=(
                f"synchronous all-reduce {row['name']} of "
                f"{row['bytes'] / 2**20:.0f} MiB at position "
                f"{row['position']} of {row['schedule_length']}: it holds "
                "the core for its whole duration; the replicated LM step "
                "is compiled so that its gradient reductions run beside "
                "the backward pass and the optimizer (train/lm_step.py)"
            ),
            snippet=f"{row['name']} = all-reduce(...)",
            severity="error", layer=2,
        )
        for row in rows
        if not row["async"] and row["bytes"] > sync_limit_bytes
    ]
    report = {
        "metric": label,
        "all_reduces": rows,
        **grad_sync_bytes(rows),
        "argument_gib": memory.argument_size_in_bytes / 2**30,
        "temp_gib": memory.temp_size_in_bytes / 2**30,
    }
    return findings, report


def run_layer2(mesh=None) -> list[Finding]:
    """The full Layer-2 sweep ``tools/dmlcheck.py --layer2`` runs:
    ring-step donation/collective/jaxpr audits (flat, the round-11
    topology-aware hierarchical build, AND the round-13 fused-codec
    build), the overlap-aware zero1 two-program audit (DML102 at ERROR
    severity since ISSUE 9; reference and fused-AdamW builds), the
    per-layer-FSDP use-site-gather audit, and the wire-byte accounting
    for every wire scheme — whole-ring, per-axis, and through the
    fused int8 kernels (the fusion must never change the wire)."""
    from distributed_machine_learning_tpu.runtime.mesh import make_mesh

    if mesh is None:
        mesh = make_mesh(8)
    findings = audit_ring_step(mesh)
    findings += audit_ring_step(mesh, codec_impl="pallas")
    findings += audit_hier_ring_step(mesh)
    findings += audit_zero1_step(mesh)
    findings += audit_zero1_step(mesh, fused_update=True)
    findings += audit_fsdp_perlayer_step(mesh)
    wire_findings, _ = audit_ring_wire_accounting(
        mesh, 4096, schemes=("none", "bf16", "int8", "topk"))
    findings += wire_findings
    pallas_findings, _ = audit_ring_wire_accounting(
        mesh, 4096, schemes=("int8",), codec_impl="pallas",
        label="ring_all_reduce_pallas")
    findings += pallas_findings
    n = mesh.shape[mesh.axis_names[0]]
    hier_findings, _ = audit_ring_wire_accounting(
        mesh, 4096, schemes=("none", "bf16", "int8", "topk"),
        topology=f"2x{n // 2}" if n % 2 == 0 else f"1x{n}",
        label="hier_ring_all_reduce")
    findings += hier_findings
    return findings
