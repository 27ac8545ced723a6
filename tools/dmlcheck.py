#!/usr/bin/env python3
"""dmlcheck — static analysis for this repo's distributed-correctness
invariants.

Usage::

    python tools/dmlcheck.py [ROOT] [--json] [--rules DML001,DML004]
                             [--baseline FILE | --no-baseline]
                             [--layer2] [--layer3 [--quick]]
                             [--mutate NAME,NAME] [--repro-dir DIR]
                             [--replay FILE] [--list-rules]
                             [--write-baseline] [--dp-lm-step]

Layer 1 (default, stdlib-only, no jax import, <10 s): the AST rules in
``distributed_machine_learning_tpu/analysis/ast_rules.py`` over the
package + tools + tests sources.  ``--layer2`` additionally compiles
the ring and zero1 train steps on an 8-virtual-device CPU mesh and runs
the jaxpr/HLO audit passes (donation taken, no critical-path
all-gather, wire-byte accounting) — slower, imports jax.  ``--layer3``
runs the deterministic interleaving explorer over the gang-transport
scenarios (``analysis/interleave.py``): ``--quick`` keeps it to the
exhaustive small configs (CI-sized, <30 s); a violated invariant
(DML301, DML302 for deadlocks) carries a minimized schedule trace and
a reproducer file ``--replay`` re-runs bit-for-bit.  ``--mutate``
re-introduces a known-bug seed (the mutation-test gate).
``--dp-lm-step`` is a mode of its own, by hand (needs libtpu): the
four-chip dp LM step AOT-compiled for a described ``v5e:2x2``, every
all-reduce of its schedule listed, exit 1 where a large one is synchronous.

Exit codes: 0 clean (every finding baselined, no stale baseline
entries), 1 non-baselined ERROR findings or stale entries, 2 usage /
malformed-baseline errors.  Advisory findings are always reported but
never fail the run.  ``--json`` prints one machine-readable verdict
dict (same philosophy as ``ckpt_verify --json``).

Baseline workflow: fix the finding if you can; when the flagged idiom
is deliberate, add an entry to ``dmlcheck_baseline.json`` with a
written justification (entries without one fail with exit 2), matched
on (rule, file, substring-of-the-flagged-line).  Stale entries —
suppressing nothing — fail the run so the baseline only shrinks.
``--write-baseline`` prints a skeleton for the current NEW findings to
paste in (justifications left for you to write; an empty one will not
pass).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# Layer 1 must stay importable without jax: only analysis.ast_rules /
# analysis.findings (stdlib-only by construction) are imported here;
# program_audit is imported inside --layer2.
from distributed_machine_learning_tpu.analysis.ast_rules import (  # noqa: E402,E501
    RULES,
    run_layer1,
)
from distributed_machine_learning_tpu.analysis.findings import (  # noqa: E402,E501
    BaselineError,
    apply_baseline,
    findings_to_json,
    load_baseline,
)

BASELINE_NAME = "dmlcheck_baseline.json"


def _run_layer2():
    # The CPU mesh needs the 8-way host-platform split BEFORE jax
    # initializes a backend (shared helper; Layer 1 must stay jax-free,
    # so this import lives inside the layer-2 branch only).
    from distributed_machine_learning_tpu.runtime.mesh import (
        ensure_host_devices,
    )

    ensure_host_devices(8)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from distributed_machine_learning_tpu.analysis.program_audit import (
        run_layer2,
    )

    return run_layer2()


def _run_dp_lm_step(as_json: bool) -> int:
    """The four-chip dp LM step compiled for a described ``v5e:2x2``:
    every all-reduce of its schedule, and exit 1 where a large one is
    synchronous (``analysis.program_audit.audit_dp_lm_step``)."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from distributed_machine_learning_tpu.analysis.program_audit import (
        audit_dp_lm_step,
    )

    findings, report = audit_dp_lm_step()
    if as_json:
        report["findings"] = [f.as_dict() for f in findings]
        print(json.dumps(report, indent=1))
    else:
        for row in report["all_reduces"]:
            print(f"  {row['position']:>5} / {row['schedule_length']}  "
                  f"{'async' if row['async'] else 'SYNC '}  "
                  f"{row['bytes'] / 2**20:9.2f} MiB  {row['name']}")
        print(f"{report['metric']}: {report['grad_sync_async_bytes']} of "
              f"{report['grad_sync_bytes']} bytes asynchronous; arguments "
              f"{report['argument_gib']:.2f} GiB, temporaries "
              f"{report['temp_gib']:.2f} GiB")
        for f in findings:
            print(f"  {f.rule}: {f.message}")
    return 1 if findings else 0


def _run_replay(path: str, as_json: bool) -> int:
    """Re-run the exact interleaving a layer-3 reproducer recorded.
    Exit 1 when the failure reproduces (the deterministic-CI-failure
    contract: two replays of one file fail identically), 0 when the
    schedule now passes (the bug is fixed — delete the file), 2 on a
    malformed/unknown reproducer."""
    from distributed_machine_learning_tpu.analysis.interleave import (
        format_trace,
        replay_file,
    )

    try:
        verdict = replay_file(path)
    except (OSError, ValueError, KeyError, json.JSONDecodeError) as e:
        print(f"dmlcheck: bad reproducer {path}: {e}", file=sys.stderr)
        return 2
    if as_json:
        print(json.dumps(verdict, indent=1))
    else:
        print(f"replay {verdict['scenario']} ({verdict['size']}"
              + (f", mutate={verdict['mutate']}" if verdict["mutate"]
                 else "") + "):")
        print(format_trace(verdict["trace"]))
        for v in verdict["violations"]:
            print(f"  VIOLATION: {v}")
        if not verdict["reproduced"]:
            print("  schedule passes now — fixed; delete the "
                  "reproducer")
    return 1 if verdict["reproduced"] else 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    parser.add_argument("root", nargs="?", default=REPO,
                        help="repo root to scan (default: this repo)")
    parser.add_argument("--json", action="store_true",
                        help="machine-readable verdict on stdout")
    parser.add_argument("--rules", default=None,
                        help="comma-separated rule ids to run "
                             "(default: all Layer-1 rules)")
    parser.add_argument("--baseline", default=None,
                        help=f"suppression file (default: "
                             f"ROOT/{BASELINE_NAME})")
    parser.add_argument("--no-baseline", action="store_true",
                        help="ignore the baseline (report everything)")
    parser.add_argument("--layer2", action="store_true",
                        help="also compile train steps and run the "
                             "jaxpr/HLO audit passes (imports jax)")
    parser.add_argument("--layer3", action="store_true",
                        help="also run the deterministic interleaving "
                             "explorer over the gang-transport "
                             "scenarios (DML301/DML302)")
    parser.add_argument("--quick", action="store_true",
                        help="layer 3: exhaustive small configs only "
                             "(CI-sized, <30s)")
    parser.add_argument("--mutate", default=None,
                        help="layer 3: comma-separated known-bug "
                             "seeds to re-introduce (mutation-test "
                             "gate); see analysis/interleave.py "
                             "MUTATIONS")
    parser.add_argument("--repro-dir", default=None,
                        help="layer 3: directory for reproducer files "
                             "(default: <tmp>/dmlcheck-repros)")
    parser.add_argument("--replay", default=None, metavar="FILE",
                        help="re-run the exact interleaving a "
                             "reproducer recorded, print the "
                             "annotated trace, exit 1 if it still "
                             "fails (deterministic)")
    parser.add_argument("--dp-lm-step", action="store_true",
                        help="by hand (needs libtpu, ~1 min): AOT-compile "
                             "the four-chip dp LM step for a described "
                             "v5e:2x2 at the benchmark cell's sizes and "
                             "list every all-reduce of its schedule — "
                             "bytes, sync or async, position; exit 1 "
                             "where one over 64 MiB is synchronous "
                             "(DML102's twin for train/lm_step.py)")
    parser.add_argument("--list-rules", action="store_true")
    parser.add_argument("--write-baseline", action="store_true",
                        help="print a baseline skeleton for the "
                             "current NEW findings and exit")
    args = parser.parse_args(argv)

    if args.list_rules:
        for r in RULES.values():
            print(f"{r.id}  {r.title}")
            print(f"        incident: {r.incident}")
        return 0

    if args.replay:
        return _run_replay(args.replay, as_json=args.json)
    if args.dp_lm_step:
        return _run_dp_lm_step(as_json=args.json)

    LAYER2_RULES = {"DML101", "DML102", "DML103", "DML104"}
    LAYER3_RULES = {"DML301", "DML302"}
    rules = None
    if args.rules:
        rules = {r.strip() for r in args.rules.split(",") if r.strip()}
        unknown = rules - set(RULES) - LAYER2_RULES - LAYER3_RULES
        if unknown:
            print(f"dmlcheck: unknown rule id(s): {sorted(unknown)}",
                  file=sys.stderr)
            return 2
        if rules & LAYER2_RULES and not args.layer2:
            # Without the pass actually running, a Layer-2-only filter
            # would report a false green verdict.
            print("dmlcheck: rule(s) "
                  f"{sorted(rules & LAYER2_RULES)} are Layer-2 program "
                  "audits — add --layer2 to run them", file=sys.stderr)
            return 2
        if rules & LAYER3_RULES and not args.layer3:
            print("dmlcheck: rule(s) "
                  f"{sorted(rules & LAYER3_RULES)} are Layer-3 "
                  "interleaving checks — add --layer3 to run them",
                  file=sys.stderr)
            return 2
    if args.mutate and not args.layer3:
        print("dmlcheck: --mutate only applies to --layer3",
              file=sys.stderr)
        return 2

    root = os.path.abspath(args.root)
    rule_timings: dict = {}
    timing = {"layer1_s": 0.0, "layer2_s": 0.0, "layer3_s": 0.0,
              "rules": rule_timings}
    t0 = time.perf_counter()
    findings = run_layer1(
        root, rules=None if rules is None
        else {r for r in rules if r in RULES},
        timings=rule_timings)
    timing["layer1_s"] = round(time.perf_counter() - t0, 3)
    if args.layer2:
        t0 = time.perf_counter()
        l2 = _run_layer2()
        timing["layer2_s"] = round(time.perf_counter() - t0, 3)
        if rules is not None:
            l2 = [f for f in l2 if f.rule in rules]
        findings += l2
    layer3_stats = None
    if args.layer3:
        from distributed_machine_learning_tpu.analysis.interleave import (
            run_layer3,
        )

        mutate = tuple(m.strip() for m in (args.mutate or "").split(",")
                       if m.strip())
        repro_dir = args.repro_dir or os.path.join(
            tempfile.gettempdir(), "dmlcheck-repros")
        t0 = time.perf_counter()
        try:
            l3, layer3_stats = run_layer3(
                quick=args.quick, mutate=mutate, repro_dir=repro_dir)
        except ValueError as e:
            print(f"dmlcheck: {e}", file=sys.stderr)
            return 2
        timing["layer3_s"] = round(time.perf_counter() - t0, 3)
        for name, entry in layer3_stats["scenarios"].items():
            rule_timings[f"layer3:{name}"] = entry["seconds"]
        if rules is not None:
            l3 = [f for f in l3 if f.rule in rules]
        findings += l3

    baseline = []
    if not args.no_baseline:
        try:
            baseline = load_baseline(
                args.baseline or os.path.join(root, BASELINE_NAME))
        except BaselineError as e:
            print(f"dmlcheck: {e}", file=sys.stderr)
            return 2
    if rules is not None:
        # A --rules subset must not report the OTHER rules' baseline
        # entries as stale: only entries whose rule actually ran can be
        # judged used/unused.
        baseline = [e for e in baseline if e["rule"] in rules]
    new, suppressed, unused = apply_baseline(findings, baseline)
    advisories = [f for f in new if f.severity == "advisory"]
    errors = [f for f in new if f.severity != "advisory"]

    if args.write_baseline:
        skeleton = [{"rule": f.rule, "file": f.file,
                     "match": f.snippet or f.message,
                     "justification": ""} for f in errors]
        print(json.dumps({"suppressions": skeleton}, indent=2))
        return 0

    if args.json:
        payload = findings_to_json(
            new, suppressed, unused,
            rules_run=sorted(rules) if rules else sorted(RULES))
        payload["errors"] = len(errors)
        payload["advisories"] = len(advisories)
        payload["clean"] = not errors and not unused
        timing["rules"] = {k: round(v, 4)
                           for k, v in sorted(rule_timings.items())}
        payload["timing"] = timing
        if layer3_stats is not None:
            payload["layer3"] = layer3_stats
        print(json.dumps(payload, indent=1))
    else:
        for f in errors:
            print(f"{f.rule} {f.location()}: {f.message}")
            if f.snippet:
                print(f"    > {f.snippet}")
        for f in advisories:
            print(f"{f.rule} {f.location()} (advisory): {f.message}")
        for e in unused:
            print(f"STALE baseline entry (fixed? drop it): "
                  f"{e['rule']} {e['file']} ~ {e['match']!r}")
        print(f"dmlcheck: {len(errors)} error(s), "
              f"{len(advisories)} advisory, "
              f"{len(suppressed)} baselined, "
              f"{len(unused)} stale baseline entr(ies)")
    return 1 if (errors or unused) else 0


if __name__ == "__main__":
    raise SystemExit(main())
