"""The flagship under upstream's own sampler: the cell `hier_n16m.nuts` at toy
size on the CPU (`--dry-run`): the last line, the manifest's entries for it
and its configuration against the north-star yaml, the faults only its check
can see, the check's own arithmetic, and the readers of the tree counters on a
recorded slice.  The chip readings of the same are in PERF.md."""

import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

ONCHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(ONCHIP)
for _p in (ROOT, ONCHIP, os.path.dirname(os.path.abspath(__file__))):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import faults_nuts  # noqa: E402  (beside this file)

CELL, CONFIG = "hier_n16m.nuts", "hier_logistic_d32_g1000_n16m_nuts"


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _load(folder, name):
    spec = importlib.util.spec_from_file_location(
        f"onchip_{folder}_{name}_nuts_t",
        os.path.join(ONCHIP, folder, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _cli(*more):
    p = subprocess.run(
        [sys.executable, os.path.join(ONCHIP, "run.py"), "--workload", CELL,
         "--seed", str(2**31 + 38), "--seconds", "2", "--dry-run", *more],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    return p, json.loads(p.stdout.strip().splitlines()[-1])


def test_nuts_dry_run_is_correct_and_reports_its_trees():
    p, line = _cli("--trace", "1")
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    checks = _json(ONCHIP, "workloads", CELL + ".json")["checks"]
    assert [c[0] for c in line["compared"]] == list(checks)
    assert {"pe_diff_nats", "leaf_dh_nats", "leaves_out_of_range"} <= set(
        checks) and "accept_gap" not in checks
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert got["compiles_in_window"] == 0
    # the dry run's trees stop at depth 3: 1 to 7 leaves a draw
    assert 1.0 <= got["leaves_per_draw"] <= 7.0
    assert 0.0 <= got["depth_cap_share"] <= 100.0
    assert 100.0 / 8 <= got["nuts_lane_occupancy"] <= 100.0
    # warm-up ran whole, and says what it cost: 16 transitions of 8 chains,
    # each at least one leaf, and the centre's gradient a segment
    assert got["warmup_grad_evals"] >= 16 * 8 + 4 * 8
    # a CPU run reports no rate, share of a peak or roofline
    assert not {"grad_evals_per_s_chip", "step_mfu", "fused_ll_roofline",
                "device_idle_share"} & set(got)
    said = "\n".join(p.stderr.splitlines())
    for what in ("pe_diff_nats by chain", "leaf_dh_nats by chain",
                 "tree depths of the window"):
        assert said.count(what) == 1, what


def test_nuts_control_comes_out_not_correct():
    """The rows streamed in bfloat16: a lower precision than the file
    states."""
    _, line = _cli("--trace", "0", "--control", "x_bf16")
    assert line["correct"] is False
    over = {n for n, v, lim in line["compared"] if v is None or v > lim}
    assert "grad_gap" in over and "pe_gap" in over


def test_nuts_manifest_entries_and_the_yaml_to_the_letter():
    import yaml

    manifest = _json(ROOT, "BENCHMARK.json")
    assert len(manifest["workloads"]) == 5 and len(manifest["configs"]) == 5
    assert manifest["workloads"][-1]["name"] == CELL
    cell = manifest["workloads"][-1]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "sample_nuts", 1)
    entry = manifest["configs"][-1]
    cfg = _json(ONCHIP, "configs", CONFIG + ".json")
    assert entry["name"] == cfg["name"] == CONFIG
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == cfg["reduced"] == ["block_size"]
    with open(os.path.join(ROOT, "configs",
                           "hier_logistic_northstar.yaml")) as f:
        north = yaml.safe_load(f)
    assert CONFIG in open(os.path.join(
        ROOT, "configs", "hier_logistic_northstar.yaml")).read()
    s, pub = cfg["sampler"], cfg["published"]
    for k in ("kernel", "max_tree_depth", "num_warmup", "block_size"):
        assert pub[k] == north["sampler"][k], k
        if k != "block_size":
            assert s[k] == north["sampler"][k], k
    assert s["chains"] == pub["chains"] == north["execution"]["chains"] == 8
    assert s["block_size"] < pub["block_size"]
    assert s["num_warmup"] % s["block_size"] == 0 and cfg["full_warmup"]
    assert "init_step_size" not in s  # the sampler's default
    assert s["map_init_steps"] in (0, 100)
    assert set(s) == {"kernel", "chains", "block_size", "max_tree_depth",
                      "num_warmup", "map_init_steps"}
    flagship = _json(ONCHIP, "configs", "hier_logistic_d32_g1000_n16m.json")
    for k in ("model", "sizes", "rows", "reference", "counts"):
        assert cfg[k] == flagship[k], k
    assert (north["model"]["num_features"], north["model"]["num_groups"]) == (
        cfg["sizes"]["d"], cfg["sizes"]["groups"])
    traffic = _json(ONCHIP, "traffic", "sample_nuts.json")
    assert os.path.isfile(os.path.join(
        ONCHIP, "drivers", traffic["driver"] + ".py"))
    # the three metrics of the tree counters, read by one reader; and every
    # per-layer metric the flagship's cell reports but the two that cannot
    metrics = {m["name"]: m for m in manifest["per_layer"]}
    for name in ("leaves_per_draw", "depth_cap_share", "nuts_lane_occupancy"):
        m = metrics[name]
        assert m["workloads"] == [CELL] and m["layer"] == "sampler_kernels"
        assert m["moves"] == "grad_evals_per_s_chip"
        spec = _json(ONCHIP, "metrics", name + ".json")
        assert spec == {"reader": "tree_counters", "params": {"number": name}}
    left_out = {"block_gap_us"} | ({"map_s"} if not s["map_init_steps"]
                                   else set())
    for m in manifest["per_layer"]:
        if "hier_n16m.sample" in m["workloads"]:
            assert (CELL in m["workloads"]) is (m["name"] not in left_out), \
                m["name"]
        if m["layer"] == "collectives":
            assert CELL not in m["workloads"], m["name"]
    for name in ("time_to_first_draw_s", "warmup_grad_evals"):
        assert CELL in metrics[name]["workloads"]


# (what is planted, the numbers that have to read over their limits, least
# readings).  The stale gradient is planted in the sampling blocks' trees; planted
# in warm-up too, dual averaging shrinks the step until the wrong energies are
# small (4e-4 to 3e-3 at toy size, 2e-4 to 2e-3 on the chip), and the leaf reads
# 0.03 at toy size, sixty times a sound dry run's 5e-4, and 0.024 on the chip,
# as a sound run does: what `leaf_dh_nats` cannot see (PERF.md section 6, PR 38)
NUTS_PLANTED = [
    (faults_nuts.plain_float32_sum, {"pe_diff_nats"}, {}),
    (faults_nuts.stale_gradient, {"leaf_dh_nats"}, {}),
    (faults_nuts.stale_gradient_everywhere, set(), {"leaf_dh_nats": 0.01}),
    (faults_nuts.state_handed_back, {"frozen_chains"}, {}),
    (faults_nuts.tree_of_64_leaves, {"leaves_out_of_range"}, {}),
    (faults_nuts.uncentred, None, {}),
]


@pytest.mark.parametrize("fault, caught_by, least", NUTS_PLANTED,
                         ids=[f.__name__ for f, _, _ in NUTS_PLANTED])
def test_nuts_broken_underneath_comes_out_not_correct(
        monkeypatch, capsys, fault, caught_by, least):
    fault(monkeypatch.setattr)
    spec = importlib.util.spec_from_file_location(
        "onchip_run_under_nuts_test", os.path.join(ONCHIP, "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    line = run.main(["--workload", CELL, "--seed", "11", "--seconds", "2",
                     "--trace", "0", "--dry-run"])
    capsys.readouterr()
    over = {n for n, v, lim in line["compared"] if v is None or v > lim}
    if caught_by is None:
        # the plain potential at toy size: float32 holds it, nothing to see
        assert line["correct"] is True and not over, line["compared"]
    else:
        if caught_by:
            assert line["correct"] is False
        assert caught_by <= over, line["compared"]
    read = {n: v for n, v, _ in line["compared"]}
    for name, floor in least.items():
        assert read[name] >= floor, line["compared"]


# a block of 4 draws x 8 chains at depth cap 6: (case, the gate span's fields
# changed from a sound block's, the record's block_grad_evals, out of range?)
SOUND = {"tree_leaves": 2016, "tree_depths": [0, 0, 0, 0, 0, 0, 32],
         "lane_iterations": 252, "divergent": 0}
BLOCKS = [
    ("every_tree_at_the_cap", {}, 2016, False),
    ("mixed_depths", {"tree_leaves": 31 * 63 + 5,
                      "tree_depths": [0, 0, 0, 1, 0, 0, 31],
                      "lane_iterations": 252}, 31 * 63 + 5, False),
    ("a_tree_of_64_leaves", {"tree_leaves": 2048,
                             "tree_depths": [0, 0, 0, 0, 0, 0, 0, 32],
                             "lane_iterations": 256}, 2048, True),
    ("more_leaves_than_the_depths_hold", {"tree_leaves": 2017}, 2017, True),
    ("leaves_that_the_record_does_not_count", {}, 2000, True),
    ("a_transition_without_a_leaf",
     {"tree_depths": [1, 0, 0, 0, 0, 0, 31], "tree_leaves": 1953,
      "lane_iterations": 252}, 1953, True),
    ("lanes_that_cannot_cover_the_leaves", {"lane_iterations": 200}, 2016,
     True),
    ("no_counters", None, 2016, True),
]


@pytest.mark.parametrize("case, changed, grads, bad", BLOCKS,
                         ids=[b[0] for b in BLOCKS])
def test_nuts_block_out_of_range(case, changed, grads, bad):
    check = _load("checks", "sampler_trees")
    fields = {} if changed is None else dict(SOUND, **changed)
    why = check.block_out_of_range(
        fields, {"block_grad_evals": grads}, chains=8, max_depth=6)
    assert (why is not None) is bad, why


def test_nuts_reference_leaf_on_a_bowl():
    """`lib/leaf.reference_leaf` against the closed form of one
    velocity-Verlet step in a quadratic bowl."""
    leaf = _load("lib", "leaf")
    k = np.array([4.0, 9.0])

    def bowl(z):
        z = np.asarray(z, np.float64)
        return 0.5 * np.sum(k * z * z, axis=1), k * z

    z0 = np.array([[1.0, -0.5]], np.float32)
    r0 = np.array([[0.25, 2.0]])
    eps, inv_mass = 0.125, np.array([[0.5, 2.0]])
    dh, z1 = leaf.reference_leaf(bowl, {
        "z0": z0, "r0": r0, "step_size": np.array([eps]),
        "inv_mass": inv_mass})
    r_half = r0 - 0.5 * eps * k * z0
    want_z1 = z0 + eps * inv_mass * r_half
    r1 = r_half - 0.5 * eps * k * want_z1
    want = (0.5 * np.sum(k * want_z1 ** 2) + 0.5 * np.sum(inv_mass * r1 ** 2)
            - 0.5 * np.sum(k * z0 ** 2) - 0.5 * np.sum(inv_mass * r0 ** 2))
    np.testing.assert_allclose(z1, want_z1, rtol=1e-7)
    np.testing.assert_allclose(dh, [want], rtol=1e-5)


def test_nuts_tree_counter_readers_on_a_recorded_slice():
    """The three metrics from the window's `block.gate` spans of a run on the
    chip (`testdata/spans_nuts_v5e.json`, which says what run), against the
    numbers written beside them."""
    reader = _load("readers", "tree_counters")
    rec = _json(ONCHIP, "testdata", "spans_nuts_v5e.json")
    spans = [{"id": i + 2, "parent": 1, "run": 3, "name": "block.gate",
              "start_ns": 10 * i, "end_ns": 10 * i + 5, "fields": f}
             for i, f in enumerate(rec["gate_fields"])]
    ctx = {"chains": rec["chains"], "dry_run": False,
           "program_spans": {"window": spans, "setup": [], "collect": []}}
    for name, want in rec["expect"].items():
        got = reader.read(ctx, {"number": name})
        assert got == pytest.approx(want, rel=1e-12), name
    # a program without the counters: nothing, and no error
    bare = [dict(s, fields={"block": 1, "block_grad_evals": 2016})
            for s in spans]
    ctx["program_spans"] = {"window": bare, "setup": [], "collect": []}
    assert all(reader.read(ctx, {"number": n}) is None for n in rec["expect"])
    ctx["program_spans"] = None
    assert reader.read(ctx, {"number": "leaves_per_draw"}) is None
