"""dmlcheck (ISSUE 8): Layer-1 AST rules, the baseline workflow, the
CLI, and the Layer-2 program audits.

The tier-1 keystones here are ``test_package_is_clean`` (the whole repo
passes Layer 1 with zero non-baselined findings — the checker IS the
regression gate for every invariant it encodes) and
``test_layer1_is_fast_and_jax_free`` (the gate stays cheap enough to
run on every change: < 10 s, no jax import).  Compile-heavy Layer-2
sweeps over the real train steps live behind ``slow``; the SEEDED
violation programs (a donation XLA cannot alias, a forced sync
all-gather feeding the step output, a host callback in a step body) are
tiny compiles and stay in the default run — they are the acceptance
proof that each pass actually catches its bug class.
"""

import json
import os
import subprocess
import sys

import pytest

from distributed_machine_learning_tpu.analysis.ast_rules import (
    RULES,
    iter_source_files,
    run_layer1,
    run_source,
)
from distributed_machine_learning_tpu.analysis.findings import (
    BaselineError,
    Finding,
    apply_baseline,
    load_baseline,
)

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
FIXTURES = os.path.join(HERE, "fixtures", "dmlcheck")
DMLCHECK = os.path.join(REPO, "tools", "dmlcheck.py")


# ---------------------------------------------------------------------------
# Per-rule fixtures: every rule has one firing and one clean case
# ---------------------------------------------------------------------------

def _fixture(name):
    with open(os.path.join(FIXTURES, name), encoding="utf-8") as f:
        return f.read()


@pytest.mark.parametrize("rule_id", sorted(RULES))
def test_rule_fires_on_its_fixture(rule_id):
    src = _fixture(f"{rule_id.lower()}_fires.py")
    hits = [f for f in run_source(src, "unused.py") if f.rule == rule_id]
    assert hits, f"{rule_id} did not fire on its firing fixture"
    assert all(f.line > 0 and f.snippet for f in hits)


@pytest.mark.parametrize("rule_id", sorted(RULES))
def test_rule_stays_quiet_on_clean_fixture(rule_id):
    src = _fixture(f"{rule_id.lower()}_clean.py")
    hits = [f for f in run_source(src, "unused.py") if f.rule == rule_id]
    assert not hits, (
        f"{rule_id} false-positived on its clean fixture: "
        + "; ".join(f"{f.line}: {f.snippet}" for f in hits)
    )


def test_fixture_set_is_complete():
    names = set(os.listdir(FIXTURES))
    for rule_id in RULES:
        assert f"{rule_id.lower()}_fires.py" in names
        assert f"{rule_id.lower()}_clean.py" in names


# ---------------------------------------------------------------------------
# The package itself is clean (the tier-1 gate)
# ---------------------------------------------------------------------------

def test_package_is_clean():
    """Zero non-baselined Layer-1 findings over the whole repo, zero
    stale baseline entries, every entry justified (load_baseline
    enforces the justification contract)."""
    findings = run_layer1(REPO)
    baseline = load_baseline(os.path.join(REPO, "dmlcheck_baseline.json"))
    assert baseline, "expected checked-in justified suppressions"
    new, suppressed, unused = apply_baseline(findings, baseline)
    assert not new, "non-baselined findings:\n" + "\n".join(
        f"  {f.rule} {f.location()}: {f.snippet or f.message}"
        for f in new)
    assert not unused, f"stale baseline entries (fixed? drop them): {unused}"
    assert suppressed, "baseline matched nothing — matching is broken"


def test_lock_discipline_rules_find_nothing_in_runtime():
    """ISSUE 15: DML013/DML014 (lock-ownership + check-then-act over
    the gang control plane) must find ZERO issues in the real
    ``runtime/transport.py`` / ``runtime/coordinator.py`` — genuine
    findings get fixed in-PR (the epoch fence moved inside the lock),
    never baselined."""
    findings = run_layer1(REPO, rules={"DML013", "DML014"})
    assert findings == [], [
        f"{f.rule} {f.location()}: {f.snippet or f.message}"
        for f in findings]


def test_scan_covers_the_tree_but_not_fixtures():
    files = list(iter_source_files(REPO))
    assert any(f.startswith("distributed_machine_learning_tpu/runtime/")
               for f in files)
    assert any(f.startswith("tools/") for f in files)
    assert any(f.startswith("tests/") for f in files)
    assert not any("fixtures" in f for f in files), (
        "fixtures are deliberate violations and must not be scanned")
    assert len(files) > 100


def test_layer1_is_fast_and_jax_free():
    """The whole Layer-1 scan completes in < 10 s in a fresh
    interpreter with NO jax import — ``-S`` skips the site config, so
    the assertion checks the analyzer itself."""
    code = (
        "import sys, time; sys.path.insert(0, %r)\n"
        "t0 = time.monotonic()\n"
        "from distributed_machine_learning_tpu.analysis.ast_rules "
        "import run_layer1\n"
        "n = len(run_layer1(%r))\n"
        "print('%%.2f %%d %%s' %% (time.monotonic() - t0, n, "
        "'jax' in sys.modules))\n" % (REPO, REPO)
    )
    res = subprocess.run(
        [sys.executable, "-S", "-E", "-c", code],
        capture_output=True, text=True, timeout=120,
    )
    assert res.returncode == 0, res.stderr
    elapsed, n_findings, jax_loaded = res.stdout.split()
    assert jax_loaded == "False", "Layer 1 imported jax"
    assert float(elapsed) < 10.0, f"Layer 1 took {elapsed}s (budget 10s)"
    assert int(n_findings) >= 3  # the baselined deliberate sites


# ---------------------------------------------------------------------------
# Baseline machinery
# ---------------------------------------------------------------------------

def test_baseline_requires_justification(tmp_path):
    p = tmp_path / "baseline.json"
    p.write_text(json.dumps({"suppressions": [
        {"rule": "DML001", "file": "x.py", "match": "y"},
    ]}))
    with pytest.raises(BaselineError, match="justification"):
        load_baseline(p)
    p.write_text(json.dumps({"suppressions": [
        {"rule": "DML001", "file": "x.py", "match": "y",
         "justification": "short"},
    ]}))
    with pytest.raises(BaselineError, match="justification"):
        load_baseline(p)
    p.write_text("{not json")
    with pytest.raises(BaselineError, match="JSON"):
        load_baseline(p)
    assert load_baseline(tmp_path / "absent.json") == []


def test_baseline_matching_is_line_number_free():
    f1 = Finding(rule="DML001", file="a.py", line=10,
                 message="m", snippet="time.time() - t0")
    f2 = Finding(rule="DML001", file="a.py", line=99,
                 message="m", snippet="time.time() - t0  # moved")
    entry = {"rule": "DML001", "file": "a.py",
             "match": "time.time() - t0",
             "justification": "x" * 20}
    new, suppressed, unused = apply_baseline([f1, f2], [entry])
    assert not new and len(suppressed) == 2 and not unused
    stale = {"rule": "DML002", "file": "b.py", "match": "nothing",
             "justification": "x" * 20}
    new, _, unused = apply_baseline([f1], [entry, stale])
    assert not new and unused == [stale]


# ---------------------------------------------------------------------------
# tools/dmlcheck.py CLI
# ---------------------------------------------------------------------------

def _run_tool(*args):
    return subprocess.run(
        [sys.executable, "-S", "-E", DMLCHECK, *args],
        capture_output=True, text=True, timeout=120,
    )


def test_tool_clean_run_and_json():
    res = _run_tool("--json")
    assert res.returncode == 0, res.stdout + res.stderr
    verdict = json.loads(res.stdout)
    assert verdict["clean"] is True
    assert verdict["errors"] == 0
    assert verdict["new"] == 0
    assert len(verdict["suppressed"]) >= 3
    assert verdict["baseline_unused"] == []
    assert "DML001" in verdict["rules_run"]
    # Per-layer / per-rule timing (ISSUE 15): budget regressions must
    # be visible in CI output.  Layers 2/3 did not run here → 0.
    timing = verdict["timing"]
    assert {"layer1_s", "layer2_s", "layer3_s", "rules"} <= set(timing)
    assert 0 < timing["layer1_s"] < 10.0
    assert timing["layer2_s"] == 0 and timing["layer3_s"] == 0
    for rule_id in ("DML001", "DML012", "DML013", "DML014"):
        assert rule_id in timing["rules"]
    res = _run_tool("--list-rules")
    assert res.returncode == 0
    for rule_id in RULES:
        assert rule_id in res.stdout


def _mini_repo(tmp_path, src):
    pkg = tmp_path / "distributed_machine_learning_tpu" / "runtime"
    pkg.mkdir(parents=True)
    (pkg / "bad.py").write_text(src)
    return tmp_path


def test_tool_baseline_workflow(tmp_path):
    """finding → rc 1; unjustified suppression → rc 2; justified →
    rc 0; stale entry after the fix → rc 1 (baseline only shrinks)."""
    root = _mini_repo(tmp_path, _fixture("dml002_fires.py"))
    res = _run_tool(str(root))
    assert res.returncode == 1 and "DML002" in res.stdout

    baseline = root / "dmlcheck_baseline.json"
    entry = {"rule": "DML002",
             "file": "distributed_machine_learning_tpu/runtime/bad.py",
             "match": 'with open(ledger_path, "a") as f:',
             "justification": ""}
    entry2 = dict(entry, match='with open(gang_dir + "/gang_health.jsonl'
                               '", "a") as f:')
    baseline.write_text(json.dumps({"suppressions": [entry, entry2]}))
    res = _run_tool(str(root))
    assert res.returncode == 2 and "justification" in res.stderr

    for e in (entry, entry2):
        e["justification"] = ("fixture: deliberately unsynced ledger "
                              "writes for the workflow test")
    baseline.write_text(json.dumps({"suppressions": [entry, entry2]}))
    res = _run_tool(str(root), "--json")
    assert res.returncode == 0, res.stdout + res.stderr
    assert json.loads(res.stdout)["clean"] is True

    # "Fix" the violations: the now-stale suppressions must fail loud.
    (root / "distributed_machine_learning_tpu" / "runtime"
     / "bad.py").write_text(_fixture("dml002_clean.py"))
    res = _run_tool(str(root))
    assert res.returncode == 1 and "STALE" in res.stdout


def test_tool_write_baseline_skeleton(tmp_path):
    root = _mini_repo(tmp_path, _fixture("dml011_fires.py"))
    # dml011's virtual-path header does not apply to real files: the
    # file sits under runtime/, where DML011 is out of scope — use a
    # rule that applies everywhere in the package instead.
    (root / "distributed_machine_learning_tpu" / "runtime"
     / "bad.py").write_text(_fixture("dml009_fires.py"))
    res = _run_tool(str(root), "--write-baseline")
    assert res.returncode == 0
    skeleton = json.loads(res.stdout)["suppressions"]
    assert skeleton and all(e["justification"] == "" for e in skeleton)
    assert {e["rule"] for e in skeleton} == {"DML009"}


# ---------------------------------------------------------------------------
# Layer 2: seeded violations (the acceptance proof per pass)
# ---------------------------------------------------------------------------

def test_audit_donation_catches_unaliasable_donation():
    """Donate an f32 input to a program whose only output is bf16:
    XLA cannot alias (dtype width differs), the alias map stays empty,
    and the pass must flag the silent copy.  The well-formed twin
    (same-shape update) must alias and pass."""
    import warnings

    import jax
    import jax.numpy as jnp

    from distributed_machine_learning_tpu.analysis.program_audit import (
        audit_donation,
    )

    bad = jax.jit(lambda x: x.astype(jnp.bfloat16) * 2, donate_argnums=(0,))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # jax warns on unused donation
        hlo_bad = bad.lower(
            jax.ShapeDtypeStruct((128,), jnp.float32)).compile().as_text()
    findings = audit_donation(hlo_bad, [0], label="seeded")
    assert len(findings) == 1
    assert findings[0].rule == "DML101"
    assert "not aliased" in findings[0].message

    good = jax.jit(lambda x: x * 2 + 1, donate_argnums=(0,))
    hlo_good = good.lower(
        jax.ShapeDtypeStruct((128,), jnp.float32)).compile().as_text()
    assert audit_donation(hlo_good, [0], label="seeded") == []


def test_audit_flags_forced_critical_path_allgather(mesh8):
    """A sync all-gather whose result IS the step output — the exact
    2004.13336 anti-pattern — must be flagged, with the feeds-root
    attribution; a permute-only ring program must stay clean."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from distributed_machine_learning_tpu.analysis.overlap_audit import (
        compile_ring_hlo,
    )
    from distributed_machine_learning_tpu.analysis.program_audit import (
        audit_critical_path_collectives,
    )
    from distributed_machine_learning_tpu.runtime.mesh import (
        shard_map_no_check,
    )

    def update(w_shard):
        new_shard = w_shard * 0.9
        return jax.lax.all_gather(new_shard, "batch", tiled=True)

    fn = jax.jit(shard_map_no_check(
        update, mesh=mesh8, in_specs=P("batch"), out_specs=P(None)))
    hlo = fn.lower(
        jax.ShapeDtypeStruct((8, 64), jnp.float32)).compile().as_text()
    findings = audit_critical_path_collectives(
        hlo, kinds=("all-gather",), label="seeded", severity="error")
    assert findings, "forced sync all-gather not flagged"
    assert any("feeds the step output directly" in f.message
               for f in findings)
    assert all(f.rule == "DML102" for f in findings)

    ring_hlo = compile_ring_hlo(mesh8, 512, bucket_bytes=8192)
    assert audit_critical_path_collectives(
        ring_hlo, kinds=("all-gather",), label="ring") == []


def test_audit_jaxpr_flags_host_callback():
    """jax.debug.print inside a step body is a per-step device→host
    round-trip; the jaxpr pass must see it through the jit wrapper."""
    import jax
    import jax.numpy as jnp

    from distributed_machine_learning_tpu.analysis.program_audit import (
        audit_step_host_callbacks,
    )

    @jax.jit
    def chatty_step(x):
        jax.debug.print("loss {}", x.sum())
        return x * 2

    x = jax.ShapeDtypeStruct((8,), jnp.float32)
    findings = audit_step_host_callbacks(chatty_step, x, label="seeded")
    assert findings and all(f.rule == "DML104" for f in findings)

    quiet = jax.jit(lambda x: x * 2)
    assert audit_step_host_callbacks(quiet, x, label="seeded") == []


# ---------------------------------------------------------------------------
# Layer 2: the real train steps (compile-heavy → slow)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_layer2_real_steps_have_no_errors(mesh8):
    """The full --layer2 sweep over the real programs: the ring step's
    donation is fully taken (every state leaf aliased) with no
    all-gather anywhere; the zero1 audit now gates the OVERLAP-AWARE
    build at ERROR severity (ISSUE 9 landed the 2004.13336 overlap
    item: the update program contains no all-gather at all and the
    consume program is a permute-only bucketed ring) and must be
    entirely clean — the pre-overlap advisory phase is over.  The
    per-layer FSDP audit (use-site gathers, none feeding ROOT) must be
    clean too."""
    from distributed_machine_learning_tpu.analysis.program_audit import (
        audit_fsdp_perlayer_step,
        audit_ring_step,
        audit_zero1_step,
    )

    ring = audit_ring_step(mesh8)
    assert ring == [], [f.message for f in ring]
    zero1 = audit_zero1_step(mesh8)
    assert zero1 == [], [f.message for f in zero1]
    pl = audit_fsdp_perlayer_step(mesh8)
    assert pl == [], [f.message for f in pl]
    # Round 11: the topology-aware hierarchical build holds the same
    # invariants — donation taken on state AND the EF residual,
    # permute-only, no host callbacks.
    from distributed_machine_learning_tpu.analysis.program_audit import (
        audit_hier_ring_step,
    )

    hier = audit_hier_ring_step(mesh8)
    assert hier == [], [f.message for f in hier]


def test_zero1_sync_baseline_still_flagged(mesh8):
    """The legacy sync zero1 build (overlap=False — kept for parity
    tests) must STILL trip DML102 at error
    severity: the gate's teeth are demonstrated against the known-bad
    program, so a future change can't silently neuter the pass while
    the overlap build stays green."""
    import jax
    import jax.numpy as jnp

    from distributed_machine_learning_tpu.analysis.program_audit import (
        _vggtest_setup,
        audit_critical_path_collectives,
    )
    from distributed_machine_learning_tpu.parallel.zero1 import (
        make_zero1_train_step,
        shard_zero1_state,
    )

    model, init_state, _ = _vggtest_setup()
    z1, unravel, n_elems = shard_zero1_state(init_state(), mesh8)
    step = make_zero1_train_step(model, mesh8, unravel, n_elems,
                                 augment=False, overlap=False)
    zshape = jax.eval_shape(lambda: z1)
    x = jax.ShapeDtypeStruct((16, 32, 32, 3), jnp.float32)
    y = jax.ShapeDtypeStruct((16,), jnp.int32)
    hlo = step.lower(zshape, x, y).compile().as_text()
    findings = audit_critical_path_collectives(
        hlo, kinds=("all-gather",), label="zero1_sync")
    assert findings, "sync zero1 build no longer trips DML102"
    assert all(f.severity == "error" for f in findings), (
        "DML102 must default to error severity now that the overlap "
        "item landed")
    assert any("feeds the step output directly" in f.message
               for f in findings)


@pytest.mark.slow
def test_layer2_wire_accounting_all_schemes(mesh8):
    """Compiled collective-permute bytes == static ring_wire_bytes for
    every scheme the backend can carry; the bf16 widening on XLA:CPU is
    reported as an advisory, never an error (backend property)."""
    from distributed_machine_learning_tpu.analysis.program_audit import (
        audit_ring_wire_accounting,
    )

    findings, table = audit_ring_wire_accounting(
        mesh8, 4096, schemes=("none", "bf16", "int8", "topk"),
        bucket_bytes=8192)
    assert not [f for f in findings if f.severity == "error"], (
        [f.message for f in findings])
    for scheme in ("none", "int8", "topk"):
        assert table[scheme]["hlo_bytes"] == table[scheme]["static_bytes"]
    # int8 actually compresses in the artifact that runs.
    assert table["int8"]["hlo_bytes"] * 3 <= table["none"]["hlo_bytes"]
    # Round 11: the PER-AXIS accounting over the hierarchical build —
    # compiled inner/outer bytes equal the static split for every
    # scheme the backend carries, the bf16 widening stays a per-axis
    # advisory, and the exact build's inter-node bytes clear the
    # (1/inner + 5%) DynamiQ bound (asserted inside the audit).
    hfindings, htable = audit_ring_wire_accounting(
        mesh8, 4096, schemes=("none", "bf16", "int8", "topk"),
        bucket_bytes=8192, topology="2x4")
    assert not [f for f in hfindings if f.severity == "error"], (
        [f.message for f in hfindings])
    for scheme in ("none", "int8", "topk"):
        assert htable[scheme]["hlo_by_axis"] \
            == htable[scheme]["static_by_axis"]
    assert htable["int8"]["hlo_by_axis"]["outer"] * 3 \
        <= htable["none"]["hlo_by_axis"]["outer"]
