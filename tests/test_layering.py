"""No library package reaches up into an entry point or an audit.

``cli/`` holds the entry points and ``analysis/`` the audits that build
and compile whole programs; both import the library, never the other
way round.  Read from the source text alone (``ast``; nothing here
imports the package or JAX), function-level imports included.  No order
is asserted among the library packages themselves: their cycles
(``runtime`` ↔ ``train``, ``train`` ↔ ``parallel``, ``utils`` ↔
``telemetry``) are ROADMAP D11's.

Second, the documents' side of the same rule: nothing that describes the
system points at the deleted bench layer or its result files.
"""

import ast
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = "distributed_machine_learning_tpu"
LIBRARY = ("utils", "telemetry", "data", "ops", "models", "train",
           "parallel", "inference", "runtime")
ABOVE = ("cli", "analysis", "bench")


def _imports(path: str, package: list[str]):
    """``(line, absolute dotted name)`` of every name ``path`` imports,
    anywhere in the file; ``package`` (the dotted parts of the package
    the file sits in) resolves relative imports."""
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read(), filename=path)
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                yield node.lineno, alias.name
        elif isinstance(node, ast.ImportFrom):
            base = [node.module] if node.module else []
            if node.level:
                base = package[:len(package) - node.level + 1] + base
            for alias in node.names:  # ``from package import cli`` too
                yield node.lineno, ".".join(base + [alias.name])


def _reaches_up(name: str) -> bool:
    parts = name.split(".")
    if parts[0] == PACKAGE:
        return len(parts) > 1 and parts[1] in ABOVE
    return parts[0] == "bench" or parts[0].startswith("bench_")


@pytest.mark.parametrize("layer", LIBRARY)
def test_library_package_imports_no_entry_point_or_audit(layer):
    root = os.path.join(REPO, PACKAGE, layer)
    found = []
    if os.path.exists(os.path.join(REPO, PACKAGE, "bench")):
        found.append(f"{PACKAGE}/bench exists: the benchmark is benchmark/")
    for dirpath, _, filenames in os.walk(root):
        for filename in sorted(filenames):
            if not filename.endswith(".py"):
                continue
            path = os.path.join(dirpath, filename)
            rel = os.path.relpath(path, REPO)
            up = {}  # one finding a statement
            for lineno, name in _imports(path,
                                         os.path.dirname(rel).split(os.sep)):
                if _reaches_up(name):
                    up.setdefault(lineno, name)
            found += [f"{rel}:{lineno} imports {name}"
                      for lineno, name in sorted(up.items())]
    assert os.path.isdir(root) and not found, "\n".join(found)


# One account of speed (ISSUE 29): the pre-chip bench layer, its result
# files and the documents that quoted them are gone, and nothing that
# describes the system may point at them again.  ``BENCH_r11_hier.json``
# stays, as ``tests/test_netmodel.py``'s pin (ROADMAP D5).
# (Spelt in pieces, so that a grep for the names does not find this file.)
DELETED_LAYER = re.compile("|".join([
    r"machine_learning_tpu\.bench", "bench" + "_lm",
    r"(?<![_\w])bench" + r"\.py", "docs/" + r"PERF\.md",
    "VERDICT" + r"\.md", "ADVICE" + r"\.md", r"BENCH_r(?!11_hier)"]))


@pytest.mark.parametrize("where", [
    "README.md", "docs", ".claude/skills/verify/SKILL.md", PACKAGE,
    "tools", "chip_smoke.py", "__graft_entry__.py",
])
def test_nothing_points_at_the_deleted_bench_layer(where):
    top = os.path.join(REPO, where)
    paths = [top] if os.path.isfile(top) else [
        os.path.join(dirpath, filename)
        for dirpath, _, filenames in os.walk(top)
        for filename in filenames if filename.endswith((".py", ".md"))]
    assert paths, where
    found = []
    for path in sorted(paths):
        with open(path, encoding="utf-8") as f:
            found += [f"{os.path.relpath(path, REPO)}:{i}: {line.strip()}"
                      for i, line in enumerate(f, 1)
                      if DELETED_LAYER.search(line)]
    assert not found, "\n".join(found)
