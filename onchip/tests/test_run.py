"""The harness end to end at toy size on the CPU (`--dry-run`): the last line,
the control (the program's own lower-precision stream) coming out not correct,
and the timed path broken underneath coming out not correct, once for each
fault a cell can have.  The chip runs of the same are in PERF.md."""

import json
import os
import subprocess
import sys

import pytest

ONCHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(ONCHIP)
for _p in (ROOT, ONCHIP, os.path.dirname(os.path.abspath(__file__))):
    if _p not in sys.path:
        sys.path.insert(0, _p)

CELLS = ["logistic_n20m.sample", "hier_n16m.sample"]
KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run_cli(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ONCHIP, "run.py"), *args], cwd=ROOT,
        env=env, capture_output=True, text=True, timeout=900)
    return p, (json.loads(p.stdout.strip().splitlines()[-1])
               if p.returncode == 0 else None)


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_dry_run_prints_the_contracts_last_line(cell, trace):
    p, line = run_cli("--workload", cell, "--seed", str(2**31 + 77),
                      "--seconds", "2", "--trace", str(trace), "--dry-run")
    assert p.returncode == 0, p.stderr[-2000:]
    assert KEYS <= set(line) and list(line)[-1] == "compared"
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    if trace:
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
        assert line["metrics"]["compiles_in_window"]["value"] == 0
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"}
    # a CPU run reports no rate, share of a peak or roofline
    assert not {"grad_evals_per_s_chip", "step_mfu", "collect_s",
                "time_to_first_draw_s", "fused_ll_roofline",
                "device_idle_share"} & set(line["metrics"])
    assert not any(v is not None for v in line.get("readings", {}).values())
    with open(os.path.join(ONCHIP, "workloads", cell + ".json")) as f:
        assert [c[0] for c in line["compared"]] == list(json.load(f)["checks"])
    tail = [l for l in p.stderr.splitlines() if l.startswith("[onchip] compared")]
    assert len(tail) == len(line["compared"])


def test_without_the_chip_it_fails_and_prints_no_result():
    p, _ = run_cli("--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                   "--trace", "0")
    assert p.returncode != 0 and p.stdout.strip() == ""


@pytest.mark.parametrize("cell", CELLS)
def test_the_control_comes_out_not_correct(cell):
    """The rows streamed in bfloat16 (the program's own STARK_FUSED_X_DTYPE
    path): the step a later PR would be tempted by."""
    p, line = run_cli("--workload", cell, "--seed", "5", "--seconds", "2",
                      "--trace", "0", "--dry-run", "--control", "x_bf16")
    assert p.returncode == 0, p.stderr[-2000:]
    assert line["correct"] is False
    over = {n for n, v, lim in line["compared"] if v is None or v > lim}
    assert "grad_gap" in over


import faults  # noqa: E402  (beside this file)

FAULTS = [
    ("logistic_n20m.sample", faults.state_unchanged,
     {"frozen_chains", "sd_gap", "accept_gap"}),
    ("logistic_n20m.sample", faults.half_the_rows, {"grad_gap", "mean_z"}),
    ("logistic_n20m.sample", faults.draw_altered, {"grad_gap"}),
    ("logistic_n20m.sample", faults.rejected_tries_again, {"accept_gap"}),
    ("hier_n16m.sample", faults.state_unchanged,
     {"frozen_chains", "accept_gap"}),
    ("hier_n16m.sample", faults.half_the_rows_grouped, {"grad_gap"}),
    ("hier_n16m.sample", faults.draw_altered, {"grad_gap"}),
    ("hier_n16m.sample", faults.rejected_tries_again, {"accept_gap"}),
]


@pytest.mark.parametrize("cell, fault, caught_by", FAULTS,
                         ids=[f"{c}-{f.__name__}" for c, f, _ in FAULTS])
def test_a_broken_timed_path_comes_out_not_correct(
        monkeypatch, capsys, cell, fault, caught_by):
    """The harness's look for a chip is skipped (`--dry-run`); the rest of a
    run is driven with the program broken underneath."""
    import importlib.util

    fault(monkeypatch.setattr)
    spec = importlib.util.spec_from_file_location(
        "onchip_run_under_test", os.path.join(ONCHIP, "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    line = run.main(["--workload", cell, "--seed", "11", "--seconds", "2",
                     "--trace", "0", "--dry-run"])
    capsys.readouterr()
    assert line["correct"] is False
    over = {n for n, v, lim in line["compared"] if v is None or v > lim}
    assert caught_by <= over, line["compared"]
