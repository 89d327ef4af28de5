#!/usr/bin/env python
"""Repo lint: raw collectives live ONLY in the parallel primitives layer.

PR 16's communication observatory accounts every collective dispatch
(bytes, participants, host-blocked wall) by instrumenting ONE choke
point: ``stark_tpu/parallel/primitives.py``.  That accounting is only
trustworthy while the choke point is actually unique — a raw
``lax.psum`` / ``lax.all_gather`` / ``process_allgather`` /
``shard_map`` call anywhere else moves bytes the observatory never
sees, silently re-opening the blind spot the layer exists to close.
This lint pins the invariant statically (mirroring
``tools/lint_failpoints.py``):

1. AST-collect every call to a raw-collective name under ``stark_tpu/``.
2. Fail on any call outside the allowed home,
   ``stark_tpu/parallel/primitives.py`` (the accounting layer itself).

``lax.pmean`` / ``lax.pmax`` stay un-linted by design: they are
in-kernel reductions over the chains axis whose traffic rides the same
fused program as the accounted ``psum`` — adding them to the wall would
double-count without adding information.  AST-based, so collective
names in comments/docstrings can't trip it; imports nothing from the
package, so it runs anywhere.  Run directly or via
``tests/test_lint_collectives.py`` (tier-1).
"""

from __future__ import annotations

import ast
import os
import sys
from typing import Dict, List, Tuple

#: raw-collective call names the primitives layer must monopolize
_COLLECTIVE_FUNCS = frozenset({
    "psum", "all_gather", "process_allgather", "shard_map",
})

#: repo-relative files allowed to touch raw collectives: the accounting
#: layer itself
_ALLOWED = frozenset({
    os.path.join("stark_tpu", "parallel", "primitives.py"),
})


def _call_name(node: ast.Call) -> str:
    if isinstance(node.func, ast.Attribute):
        return node.func.attr
    if isinstance(node.func, ast.Name):
        return node.func.id
    return ""


def find_collective_calls(
    source: str, filename: str
) -> List[Tuple[int, str]]:
    """(lineno, name) for every raw-collective call in a module."""
    tree = ast.parse(source, filename=filename)
    hits = []
    for node in ast.walk(tree):
        if (
            isinstance(node, ast.Call)
            and _call_name(node) in _COLLECTIVE_FUNCS
        ):
            hits.append((node.lineno, _call_name(node)))
    return hits


def collect_calls(repo: str) -> Dict[str, List[Tuple[int, str]]]:
    """repo-relative path -> [(line, collective), ...] under stark_tpu/."""
    calls: Dict[str, List[Tuple[int, str]]] = {}
    pkg_dir = os.path.join(repo, "stark_tpu")
    for root, _dirs, files in os.walk(pkg_dir):
        if "__pycache__" in root:
            continue
        for name in sorted(files):
            if not name.endswith(".py"):
                continue
            path = os.path.join(root, name)
            with open(path) as f:
                source = f.read()
            hits = find_collective_calls(source, path)
            if hits:
                calls[os.path.relpath(path, repo)] = hits
    return calls


def lint_repo(repo: str) -> List[str]:
    """Violation strings for the whole repo; empty = clean."""
    calls = collect_calls(repo)
    if not any(rel in _ALLOWED for rel in calls):
        return ["no raw collective calls found in the allowed homes "
                "(stark_tpu/parallel/primitives.py) — the collector "
                "itself is broken"]
    violations = []
    for rel in sorted(calls):
        if rel in _ALLOWED:
            continue
        for lineno, name in calls[rel]:
            violations.append(
                f"{os.path.join(repo, rel)}:{lineno}: raw collective "
                f"{name!r} outside the parallel primitives layer — "
                "route it through stark_tpu.parallel.primitives "
                "(reduce_tree/gather_axis/broadcast/shard_put/"
                "gather_tree) so the comms observatory accounts it"
            )
    return violations


def main(argv=None) -> int:
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    violations = lint_repo(repo)
    for v in violations:
        print(v, file=sys.stderr)
    if violations:
        print(
            f"{len(violations)} raw-collective violation(s) — see "
            "tools/lint_collectives.py docstring",
            file=sys.stderr,
        )
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
