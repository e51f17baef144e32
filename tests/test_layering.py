"""Imports inside ``synapseml_tpu`` point down.

One case a package.  The base (``native``, ``telemetry``, ``resilience``,
``core``) imports nothing above itself; ``parallel``, ``io`` and ``ops``
nothing from ``models`` or ``serving``; ``models`` nothing from
``serving``; and none of the nine imports a feature package
(``automl``, ``causal``, ``explainers``, ...), which sit on top of them.
Imports inside functions count: a lazy import is still a dependency.
Parses source, imports nothing.
"""

import ast
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = "synapseml_tpu"

#: a module may import from packages of its own rank or a lower one; a
#: subpackage that is not listed is a feature package, above all of these
RANK = {"native": 0, "telemetry": 0, "resilience": 0, "core": 0,
        "parallel": 1, "io": 1, "ops": 1,
        "models": 2,
        "serving": 3}
TOP = max(RANK.values()) + 1

#: the one upward import, by file, with the debt it stands for.  The file
#: must still break the rule (a repaid debt is taken off this list), and
#: no other file may.
EXCEPTIONS = {
    "synapseml_tpu/telemetry/autotune.py":
        "ROADMAP D12: the autotune plane's spaces build their candidates "
        "from models.llm, models.gbdt and parallel, so the lowest layer "
        "imports the highest",
}


def _imports(path, module_package):
    """→ {(subpackage of synapseml_tpu, line)} that ``path`` imports."""
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read(), path)
    found = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            targets = [alias.name.split(".") for alias in node.names]
        elif isinstance(node, ast.ImportFrom):
            base = (module_package[:len(module_package) - (node.level - 1)]
                    if node.level else [])
            stem = base + (node.module.split(".") if node.module else [])
            # ``from . import x`` / ``from synapseml_tpu import x``: the
            # names are the modules
            targets = ([stem + [alias.name] for alias in node.names]
                       if len(stem) < 2 else [stem])
        else:
            continue
        for parts in targets:
            if parts[0] == PACKAGE and len(parts) > 1:
                found.add((parts[1], node.lineno))
    return found


def _upward(package):
    """→ {file: [(imported subpackage, line)]} breaking the rule."""
    subpackages = {
        name for name in os.listdir(os.path.join(REPO, PACKAGE))
        if os.path.isdir(os.path.join(REPO, PACKAGE, name))
        and name != "__pycache__"}
    broken = {}
    for root, dirs, names in os.walk(os.path.join(REPO, PACKAGE, package)):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for name in names:
            if not name.endswith(".py"):
                continue
            path = os.path.join(root, name)
            rel = os.path.relpath(path, REPO).replace(os.sep, "/")
            parts = rel[:-3].split("/")
            module_package = parts[:-1]       # of a module and of __init__
            bad = sorted(
                (target, line)
                for target, line in _imports(path, module_package)
                if target in subpackages
                and RANK.get(target, TOP) > RANK[package])
            if bad:
                broken[rel] = bad
    return broken


@pytest.mark.parametrize("package", sorted(RANK, key=lambda p: (RANK[p], p)))
def test_imports_point_down(package):
    broken = _upward(package)
    listed = {f for f in EXCEPTIONS if f.startswith(f"{PACKAGE}/{package}/")}
    assert set(broken) - listed == set(), (
        f"{package} imports from a layer above it: "
        f"{ {f: v for f, v in broken.items() if f not in listed} }")
    assert listed - set(broken) == set(), (
        f"{sorted(listed - set(broken))} no longer import upward: take "
        f"them off EXCEPTIONS (and strike the debt in ROADMAP.md)")
