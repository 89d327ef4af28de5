"""The random-slopes deployment's cell `lmm_n49m.sample` at toy size on the
CPU (`--dry-run`): the last line, the manifest's entries for it, its counts
against hand arithmetic, and the faults only its check can see.  The chip
readings of the same are in PERF.md."""

import importlib.util
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

ONCHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(ONCHIP)
for _p in (ROOT, ONCHIP, os.path.dirname(os.path.abspath(__file__))):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import faults  # noqa: E402  (beside this file)
import faults_lmm  # noqa: E402

CELL, CONFIG = "lmm_n49m.sample", "lmm_d8_q2_g10k_n49m"


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _load(folder, name):
    spec = importlib.util.spec_from_file_location(
        f"onchip_{folder}_{name}_t", os.path.join(ONCHIP, folder, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_lmm_dry_run_is_correct_and_the_gate_fetches_the_ess_row():
    """(Until PR 37 `test_lmm_dry_run_comes_out_correct`, which pinned the
    gate's fetch at the accumulator's size; `tests/test_onchip.py` still
    lists that name as stale, and holds a twin of this run: PERF.md
    section 7.)"""
    p = subprocess.run(
        [sys.executable, os.path.join(ONCHIP, "run.py"), "--workload", CELL,
         "--seed", str(2**31 + 404), "--seconds", "2", "--trace", "1",
         "--dry-run"], cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    checks = _json(ONCHIP, "workloads", CELL + ".json")["checks"]
    assert [c[0] for c in line["compared"]] == list(checks)
    assert "pe_diff_nats" in checks
    assert line["metrics"]["compiles_in_window"]["value"] == 0
    # since PR 35 the gate fetches the ESS row, 44 coordinates in float32, and
    # the draw counts of 8 chains, a block; not the accumulator
    assert line["metrics"]["diag_mb_per_block"]["value"] == pytest.approx(
        (44 * 4 + 8 * 4) / 1e6)
    # the check says, chain by chain, what it compared and who moved
    said = [ln for ln in p.stderr.splitlines() if "pe_diff_nats by chain" in ln]
    assert len(said) == 1 and "share of moved transitions" in said[0]


def test_lmm_manifest_entries_and_the_configuration_file():
    manifest = _json(ROOT, "BENCHMARK.json")
    cell = {w["name"]: w for w in manifest["workloads"]}[CELL]
    assert cell["config"] == CONFIG and cell["chips"] == 1
    cfg = _json(ONCHIP, "configs", CONFIG + ".json")
    assert cfg["model"] == {"class": "FusedLinearMixedModelGrouped",
                            "args": [8, 10000, 2]}
    assert (cfg["sizes"]["d"], cfg["sizes"]["q"], cfg["sizes"]["groups"]) == (
        8, 2, 10000)
    # upstream's sampler, but for the three reduced keys, and no key more
    assert cfg["sampler"]["chains"] == cfg["published"]["chains"] == 16
    assert cfg["sampler"]["block_size"] == cfg["published"]["dispatch_steps"]
    assert cfg["sampler"]["init_step_size"] == 0.1
    assert cfg["reduced"] == ["num_warmup", "map_init_steps", "max_leapfrog"]
    assert set(cfg["sampler"]) == set(cfg["reduced"]) | {
        "chains", "block_size", "init_step_size"}
    assert all(cfg["sampler"][k] < cfg["published"][k] for k in cfg["reduced"])
    assert cfg["sampler"]["num_warmup"] % cfg["sampler"]["block_size"] == 0
    # whole lane tiles, and y's (1, N) operand stays a bitcast
    n = cfg["sizes"]["n"]
    assert n % 8192 == 0 and n % 1024 == 0
    assert n % _load("references", "lmm").BLOCK == 0
    # every per-layer metric the flagship's cell reports, and the new one
    for m in manifest["per_layer"]:
        if "hier_n16m.sample" in m["workloads"]:
            assert CELL in m["workloads"], m["name"]
    new = {m["name"]: m for m in manifest["per_layer"]}[
        "checkpoint_bytes_per_block"]
    assert new["workloads"] == [w["name"] for w in manifest["workloads"]]
    spec = _json(ONCHIP, "metrics", "checkpoint_bytes_per_block.json")
    assert spec["reader"] == "span_field"
    assert spec["params"]["name"] == "block.checkpoint"


def test_lmm_counts_against_hand_arithmetic():
    counts = _load("counts", "lmm_rows")
    sizes = {"n": 49_152_000, "d": 8, "q": 2, "groups": 10000}
    assert counts.flops_per_chain_gradient(sizes) == 4 * 49_152_000 * 10
    # 40 B of x and z, 4 of y, 4 of the group id a row
    assert counts.bytes_per_ensemble_gradient(sizes) == 49_152_000 * 48
    peak = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
    least, bound = counts.least_seconds(sizes, 16, peak)
    assert bound == "bytes" and least == pytest.approx(2.8807e-3, rel=1e-4)
    # enough chains and the matmuls bound it
    assert counts.least_seconds(sizes, 512, peak)[1] == "flops"


# (what is planted, the numbers that have to read over their limits (None: the
# run has to come out `correct`), least readings).  The second proposal moves
# a chain with probability p (2 - p) where p is reported: `accept_gap` reads
# p (1 - p), 0.136 on the chip at the cell's size (p 0.84), which the cell's
# limit of 0.07 was set from, and 0.06-0.07 at toy size (p 0.93), where it is
# held to five times what a sound dry run reads.  A quarter of the chains
# handed back (2 of the dry run's 8) pass `frozen_chains` (limit 4) and read
# a quarter of the reported acceptance in `accept_gap`
LMM_PLANTED = [
    (faults_lmm.plain_float32_sum, {"pe_diff_nats"}, {}),
    (faults_lmm.half_the_outcomes, {"grad_gap", "pe_gap"}, {}),
    (faults.rejected_tries_again, set(), {"accept_gap": 0.05}),
    (faults.state_unchanged, {"frozen_chains", "accept_gap"}, {}),
    (faults.a_quarter_handed_back, {"accept_gap"}, {"accept_gap": 0.1}),
    (faults.one_chain_rejects_all, None, {"frozen_chains": 1.0}),
]


@pytest.mark.parametrize("fault, caught_by, least", LMM_PLANTED,
                         ids=[f.__name__ for f, _, _ in LMM_PLANTED])
def test_lmm_broken_underneath_comes_out_not_correct(
        monkeypatch, capsys, fault, caught_by, least):
    fault(monkeypatch.setattr)
    spec = importlib.util.spec_from_file_location(
        "onchip_run_under_lmm_test", os.path.join(ONCHIP, "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    line = run.main(["--workload", CELL, "--seed", "11", "--seconds", "2",
                     "--trace", "0", "--dry-run"])
    capsys.readouterr()
    over = {n for n, v, lim in line["compared"] if v is None or v > lim}
    if caught_by is None:
        assert line["correct"] is True and not over, line["compared"]
    else:
        if caught_by:
            assert line["correct"] is False
        assert caught_by <= over, line["compared"]
    read = {n: v for n, v, _ in line["compared"]}
    for name, floor in least.items():
        assert read[name] >= floor, line["compared"]


# `pe_diff_nats` on states made by hand, against a reference that is a bowl
# (potential k |z|^2 / 2, the mode at the origin): chains as the cell's size
# shows them on the chip (PERF.md section 6, PR 37).  (case, height of the
# chain above the mode, fall before the window, descent in the window, the
# program's offset in nats, is call A's block handed over, `correct`)
BOWL = [
    ("high_and_creeping_sensitivity_sized_offset",
     2e7, 0.0, 400.0, 1.8, True, True),
    ("high_and_creeping_8_nat_step", 2e7, 0.0, 400.0, 8.0, True, False),
    ("near_the_mode_hundredths", 3e4, 0.0, 2.0, 0.15, True, True),
    ("near_the_mode_4_nat_step", 3e4, 0.0, 2.0, 4.0, True, False),
    ("descending_a_millionth_of_it", 2e7, 1e6, 1.5e7, 150.0, True, True),
    # seed 2147497012, chain 12, as the chip printed it
    ("fell_before_the_window_far_from_its_centre",
     4.94e7, 6.86e5, 2.37e5, 41.0, True, True),
    ("the_same_measured_against_the_window_alone",
     4.94e7, 6.86e5, 2.37e5, 41.0, False, False),
]


@pytest.mark.parametrize("case, height, before, descent, offset, handed, ok",
                         BOWL, ids=[b[0] for b in BOWL])
def test_lmm_pe_diff_nats_on_a_bowl(case, height, before, descent, offset,
                                    handed, ok):
    k, ndim = 1e4, 8

    def at(pe):
        """A position whose potential in the bowl is `pe`."""
        z = np.zeros(ndim, np.float32)
        z[0] = np.sqrt(2.0 * pe / k)
        return z

    def potential_and_grad(rows, z):
        z = np.asarray(z, np.float64)
        return 0.5 * k * np.sum(z * z, axis=1), k * z

    # the chain under test, and one that rests at the mode's foot
    tops = [height + descent, 10.0]
    first = np.stack([at(t + before) for t in tops])
    z_a = np.stack([at(t) for t in tops])
    z_b = np.stack([at(height), at(9.0)])
    pe_a, grad_a = potential_and_grad(None, z_a)
    pe_b, grad_b = potential_and_grad(None, z_b)
    measured = {
        # a constant a chain, and the offset planted along the first
        "state_start": {"z": z_a, "pe": pe_a + np.array([77.0, -5.0]),
                        "grad": grad_a.astype(np.float32)},
        "state_end": {"z": z_b, "grad": grad_b.astype(np.float32),
                      "pe": pe_b + np.array([77.0 + offset, -5.0])},
        "draws_flat": z_b[:, None], "blocks": [{"mean_accept": 1.0}],
    }
    if handed:
        measured["draws_before"] = first[:, None]
    mods = {
        ("checks", "sampler_state"): _load("checks", "sampler_state"),
        ("references", "bowl"): types.SimpleNamespace(
            potential_and_grad=potential_and_grad),
        ("rows", "none"): types.SimpleNamespace(make=lambda *a: None),
    }
    env = {"config": {"reference": "bowl",
                      "rows": {"generator": "none", "params": {}}},
           "sizes": {}, "seed": 0, "load": lambda f, n: mods[f, n]}
    got = _load("checks", "sampler_state_nats").compare(
        measured, env, ["pe_diff_nats", "frozen_chains", "accept_gap"])
    limit = _json(ONCHIP, "workloads", CELL + ".json")["checks"]["pe_diff_nats"]
    assert (got["pe_diff_nats"] <= limit) is ok, got
    assert got["frozen_chains"] == 0 and got["accept_gap"] == 0
