"""tools/lint_collectives.py: raw collectives (psum / all_gather /
process_allgather / shard_map) live ONLY in the parallel primitives
layer — a raw call anywhere else moves bytes the PR 16 communication
observatory never accounts."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

import lint_collectives  # noqa: E402


def test_repo_is_clean():
    violations = lint_collectives.lint_repo(REPO)
    assert violations == [], "\n".join(violations)


def test_collector_finds_primitives_layer():
    """The AST collector must see the accounting layer's own raw calls —
    an empty collection means the collector (not the repo) is broken."""
    calls = lint_collectives.collect_calls(REPO)
    prim = os.path.join("stark_tpu", "parallel", "primitives.py")
    assert prim in calls
    names = {name for _ln, name in calls[prim]}
    assert {"psum", "all_gather"} <= names


@pytest.mark.parametrize(
    "source,expect",
    [
        ("import jax.lax as lax\nlax.psum(x, 'i')\n", ["psum"]),
        ("from jax import lax\ny = lax.all_gather(x, 'i')\n",
         ["all_gather"]),
        ("from jax.experimental.multihost_utils import process_allgather\n"
         "process_allgather(x)\n", ["process_allgather"]),
        ("from jax.experimental.shard_map import shard_map\n"
         "f = shard_map(g, mesh=m, in_specs=s, out_specs=s)\n",
         ["shard_map"]),
        # comments/docstrings must not trip the collector
        ("# lax.psum(x, 'i')\n\"\"\"lax.all_gather(x, 'i')\"\"\"\n", []),
        # a bare import (no call) is not a dispatch
        ("from jax.experimental.multihost_utils import process_allgather\n",
         []),
        # pmean/pmax are un-linted by design (in-kernel chain reductions)
        ("from jax import lax\nlax.pmean(x, 'i')\nlax.pmax(x, 'i')\n", []),
    ],
)
def test_find_collective_calls(source, expect):
    hits = lint_collectives.find_collective_calls(source, "<test>")
    assert [name for _ln, name in hits] == expect


def test_raw_call_outside_layer_fails(tmp_path):
    """A raw psum outside primitives.py is a violation; the same call
    inside the allowed home is clean."""
    repo = tmp_path
    pkg = repo / "stark_tpu"
    (pkg / "parallel").mkdir(parents=True)
    (pkg / "parallel" / "primitives.py").write_text(
        "from jax import lax\n"
        "def reduce_tree(x, axis):\n    return lax.psum(x, axis)\n"
    )
    (pkg / "rogue.py").write_text(
        "from jax import lax\n"
        "def f(x):\n    return lax.psum(x, 'chains')\n"
    )
    violations = lint_collectives.lint_repo(str(repo))
    assert len(violations) == 1
    assert "rogue.py" in violations[0] and "psum" in violations[0]
    # moving the call behind the primitives layer clears it
    (pkg / "rogue.py").write_text(
        "from .parallel.primitives import reduce_tree\n"
        "def f(x):\n    return reduce_tree(x, 'chains')\n"
    )
    assert lint_collectives.lint_repo(str(repo)) == []


def test_empty_package_reports_broken_collector(tmp_path):
    (tmp_path / "stark_tpu").mkdir()
    violations = lint_collectives.lint_repo(str(tmp_path))
    assert violations and "collector itself is broken" in violations[0]


def test_cli_exit_zero():
    out = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "tools", "lint_collectives.py")],
        capture_output=True, text=True,
    )
    assert out.returncode == 0, out.stderr
