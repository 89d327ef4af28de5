"""The data-sharded deployment (`logistic_d32_n80m_x4`, cell
`logistic_n80m.sample.x4`) at toy size on the CPU's host devices: the rows
generator, the counts, the plain reference against the one-chip reference and
against the program on a `data=4` mesh, the cell end to end (`--dry-run`), and
the deployment's own planted fault.  The chip runs of the same are in PERF.md.

Tolerances, each with its reason, are beside the assertion that uses them.
"""

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

import faults_sharded  # noqa: E402  (beside this file)

CELL = "logistic_n80m.sample.x4"
CONFIG = "logistic_d32_n80m_x4"
SIZES = {"n": 4096, "d": 8, "groups": 0, "data_shards": 4}
PARAMS = {"posterior_seed": 7}
SEED = 2**31 + 5


def load(folder, name):
    spec = importlib.util.spec_from_file_location(
        f"sharded_test_{folder}_{name}",
        os.path.join(ONCHIP, folder, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def rows():
    return load("rows", "glm_rows_sharded").make(PARAMS, SIZES, SEED)


def test_sharded_rows_are_deterministic_and_born_on_their_devices(rows):
    import jax

    gen = load("rows", "glm_rows_sharded")
    again = gen.make(PARAMS, SIZES, SEED)
    other_order = gen.make(PARAMS, SIZES, SEED + 1)
    other_rows = gen.make({"posterior_seed": 8}, SIZES, SEED)
    assert rows["x"].shape == (4096, 8) and rows["y"].shape == (4096,)
    for k in rows:
        np.testing.assert_array_equal(np.asarray(rows[k]), np.asarray(again[k]))
        shards = sorted(rows[k].addressable_shards,
                        key=lambda s: s.index[0].start)
        assert [s.device for s in shards] == jax.devices()[:4]
        assert {s.data.shape[0] for s in shards} == {1024}
    # --seed reorders the rows inside each shard and moves none to another
    for a, b in zip(rows["x"].addressable_shards,
                    other_order["x"].addressable_shards):
        a, b = np.asarray(a.data), np.asarray(b.data)
        assert not np.array_equal(a, b)
        np.testing.assert_array_equal(a[np.lexsort(a.T)], b[np.lexsort(b.T)])
    assert not np.array_equal(np.asarray(rows["x"]), np.asarray(other_rows["x"]))
    # shards draw from different keys
    first, second = (np.asarray(s.data) for s in shards[:2])
    assert not np.array_equal(first, second)
    assert 0.2 < float(np.asarray(rows["y"]).mean()) < 0.8


def test_per_shard_counts_are_the_one_chip_counts_at_a_quarter():
    sharded, one = load("counts", "glm_rows_sharded"), load("counts", "glm_rows")
    sizes = {"n": 80_000_000, "d": 32, "groups": 0, "data_shards": 4}
    quarter = {"n": 20_000_000, "d": 32, "groups": 0}
    peak = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
    assert sharded.least_seconds(sizes, 8, peak) == one.least_seconds(
        quarter, 8, peak)
    assert sharded.least_seconds(sizes, 8, peak)[1] == "bytes"
    # the whole job's FLOPs: step_mfu divides by the chips itself
    assert sharded.flops_per_chain_gradient(sizes) == (
        4 * one.flops_per_chain_gradient(quarter))


def test_shard_sums_add_up_to_the_one_chip_reference(rows):
    """Float32 block sums added in float64: the two references differ only in
    where the blocks are cut (one block of 4096 rows against four of 1024),
    which moves a float32 sum of 4096 terms by a few ulp: 1e-5 relative is a
    hundred times that, and far below the 25 % a lost shard makes."""
    import jax.numpy as jnp

    sharded, one = load("references", "logistic_sharded"), load(
        "references", "logistic")
    whole = {k: jnp.asarray(np.asarray(v)) for k, v in rows.items()}
    z = np.random.default_rng(0).normal(size=(3, 8)) * 0.3
    pe_s, g_s = sharded.potential_and_grad(rows, z)
    pe_1, g_1 = one.potential_and_grad(whole, z)
    np.testing.assert_allclose(pe_s, pe_1, rtol=1e-5)
    np.testing.assert_allclose(g_s, g_1, rtol=1e-5, atol=1e-5 * np.abs(g_1).max())
    mode_s, sd_s = sharded.laplace(rows)
    mode_1, sd_1 = one.laplace(whole)
    np.testing.assert_allclose(mode_s, mode_1, atol=1e-4 * sd_1.max())
    np.testing.assert_allclose(sd_s, sd_1, rtol=1e-5)


@pytest.fixture(scope="module")
def mesh_and_one_chip(rows, tmp_path_factory):
    """Call A (cold chains, MAP, warm-up, one block, checkpoint) and the
    resumed call B through `sample_until_converged` on a `data=4` mesh, and
    the same two calls on one device over the same rows."""
    import jax

    import stark_tpu
    from stark_tpu import telemetry
    from stark_tpu.backends import JaxBackend, ShardedBackend
    from stark_tpu.models import FusedLogistic
    from stark_tpu.parallel.mesh import make_mesh

    tmp = tmp_path_factory.mktemp("sharded")
    model = FusedLogistic(8)
    mesh = make_mesh({"data": 4, "chains": 1}, devices=jax.devices()[:4])
    kw = dict(chains=8, kernel="chees", rhat_target=0.0, adaptive_blocks=False,
              block_size=25, min_blocks=1, seed=17, init_step_size=0.1,
              map_init_steps=20, num_warmup=150, max_leapfrog=64)
    out = {}
    for name, backend, data in (
            ("mesh", ShardedBackend(mesh), rows),
            ("one", JaxBackend(), {k: jax.device_put(np.asarray(v))
                                   for k, v in rows.items()})):
        since = len(telemetry.span_log())
        data = stark_tpu.prepare_model_data(model, data)
        ck_a, ck_b = str(tmp / f"{name}_a.npz"), str(tmp / f"{name}_b.npz")
        stark_tpu.sample_until_converged(
            model, data, backend=backend, max_blocks=1, checkpoint_path=ck_a,
            **kw)
        result = stark_tpu.sample_until_converged(
            model, data, backend=backend, max_blocks=5, resume_from=ck_a,
            checkpoint_path=ck_b, **kw)
        with np.load(ck_b) as f:
            state = {k: np.asarray(f[k]) for k in ("z", "pe", "grad")}
        out[name] = {"result": result, "state": state, "data": data,
                     "spans": telemetry.span_log()[since:]}
    return out


def test_mesh_run_matches_the_sharded_reference(rows, mesh_and_one_chip):
    """The state call B checkpointed on the mesh against the reference at the
    same positions.  `pe_gap` 1e-6 and `grad_gap` 1e-4 are the cell's own
    limits: float32 partial sums over 1024 rows a shard, psum'd, against
    float64 sums of float32 blocks read 1e-7 and 6e-7 here, and a lost shard
    reads 0.24 and 0.34."""
    ref = load("references", "logistic_sharded")
    end = mesh_and_one_chip["mesh"]["state"]
    pe, grad = ref.potential_and_grad(rows, end["z"])
    pe_gap = np.max(np.abs(end["pe"] - pe) / np.abs(pe))
    grad_gap = np.max(np.linalg.norm(end["grad"] - grad, axis=1)
                      / np.linalg.norm(grad, axis=1))
    assert pe_gap <= 1e-6 and grad_gap <= 1e-4, (pe_gap, grad_gap)
    draws = np.asarray(mesh_and_one_chip["mesh"]["result"].draws_flat)
    assert draws.shape == (8, 125, 8)
    np.testing.assert_array_equal(draws[:, -1], end["z"])


def test_mesh_run_matches_the_one_chip_run(rows, mesh_and_one_chip):
    """Same rows, same seeds, another order of summation (a psum of four
    partial sums against one sum): the chains part ways within a few
    transitions, so it is the posteriors that are compared: pooled means of
    8 x 125 draws within half a posterior standard deviation, where Monte
    Carlo error of either run is about a tenth."""
    ref = load("references", "logistic_sharded")
    _, sd = ref.laplace(rows)
    mean = {k: np.asarray(v["result"].draws_flat).reshape(-1, 8).mean(axis=0)
            for k, v in mesh_and_one_chip.items()}
    assert np.max(np.abs(mean["mesh"] - mean["one"]) / sd) < 0.5
    # and the one-chip run's own checkpointed potential agrees with the
    # reference as closely as the mesh run's
    end = mesh_and_one_chip["one"]["state"]
    pe, _ = ref.potential_and_grad(rows, end["z"])
    assert np.max(np.abs(end["pe"] - pe) / np.abs(pe)) <= 1e-6


def test_mesh_run_moves_no_row_and_says_what_it_sends(rows, mesh_and_one_chip):
    spans = mesh_and_one_chip["mesh"]["spans"]
    shard = [s for s in spans if s.name == "shard_data"]
    assert len(shard) == 2  # call A and call B
    assert all(s.fields["moved_bytes"] == 0 and s.fields["shards"] == 4
               for s in shard)
    runs = [s for s in spans if s.name == "run"]
    assert [(s.fields["mesh_data"], s.fields["mesh_chains"], s.fields["resumed"])
            for s in runs] == [(4, 1, False), (4, 1, True)]
    gates = [s for s in spans if s.name == "block.gate"]
    assert len(gates) == 5
    for g in gates:
        assert g.fields["psums_per_gradient"] == 1
        # 8 chains x (1 + d) float32: the packed [ll, ll_grad] on one chip
        assert g.fields["psum_bytes_per_gradient"] == 8 * 9 * 4
    # the prepared rows lie over the four devices, y untouched
    data = mesh_and_one_chip["mesh"]["data"]
    assert data["y"] is rows["y"]
    assert len(data["xT"].sharding.device_set) == 4
    # one device: a mesh of one, and no psum counter
    one = mesh_and_one_chip["one"]["spans"]
    assert all(s.fields["mesh_data"] == 1 for s in one if s.name == "run")
    assert not any("psums_per_gradient" in s.fields for s in one)
    assert not any(s.name == "shard_data" for s in one)


def run_cli(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ONCHIP, "run.py"), *args], cwd=ROOT,
        env=env, capture_output=True, text=True, timeout=900)
    return p, (json.loads(p.stdout.strip().splitlines()[-1])
               if p.returncode == 0 else None)


def test_sharded_cell_dry_run_comes_out_correct():
    p, line = run_cli("--workload", CELL, "--seed", str(2**31 + 78),
                      "--seconds", "2", "--trace", "1", "--dry-run")
    assert p.returncode == 0, p.stderr[-2000:]
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["count"] >= 4
    assert line["metrics"]["compiles_in_window"]["value"] == 0
    # a CPU run reports no device number of the new layer either
    assert not {"collective_exposed_share", "collective_us_per_gradient",
                "grad_evals_per_s_chip", "fused_ll_roofline"} & set(
                    line["metrics"])
    with open(os.path.join(ONCHIP, "workloads", CELL + ".json")) as f:
        assert [c[0] for c in line["compared"]] == list(json.load(f)["checks"])


def test_sharded_cell_control_comes_out_not_correct():
    p, line = run_cli("--workload", CELL, "--seed", "5", "--seconds", "2",
                      "--trace", "0", "--dry-run", "--control", "x_bf16")
    assert p.returncode == 0, p.stderr[-2000:]
    assert line["correct"] is False
    assert "grad_gap" in {n for n, v, lim in line["compared"]
                          if v is None or v > lim}


def test_a_shard_left_out_comes_out_not_correct(monkeypatch, capsys):
    faults_sharded.shard_left_out(monkeypatch.setattr)
    run = load(".", "run")
    line = run.main(["--workload", CELL, "--seed", "11", "--seconds", "2",
                     "--trace", "0", "--dry-run"])
    capsys.readouterr()
    assert line["correct"] is False
    over = {n for n, v, lim in line["compared"] if v is None or v > lim}
    assert {"pe_gap", "grad_gap"} <= over, line["compared"]


def test_the_sharded_cell_is_the_manifests_one_four_chip_cell():
    from lib import manifest

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    assert manifest.problems(m, ROOT) == []
    four = [w for w in m["workloads"] if w["chips"] == 4]
    assert [w["name"] for w in four] == [CELL]
    assert four[0]["config"] == CONFIG and four[0]["traffic"] == "sample_sharded"
    with open(os.path.join(ONCHIP, "configs", CONFIG + ".json")) as f:
        cfg = json.load(f)
    with open(os.path.join(ONCHIP, "configs", "logistic_d32_n20m.json")) as f:
        one = json.load(f)
    entry = [c for c in m["configs"] if c["name"] == CONFIG][0]
    assert entry["source"] == cfg["source"] and entry["reduced"] == cfg["reduced"]
    # widths and sampler as the one-chip configuration, a quarter of the rows
    # a chip
    assert cfg["sampler"] == one["sampler"] and cfg["model"] == one["model"]
    assert cfg["sizes"]["d"] == one["sizes"]["d"]
    assert cfg["sizes"]["n"] // cfg["mesh"]["data"] == one["sizes"]["n"]
    assert cfg["mesh"] == {"data": 4, "chains": 1}
    collectives = [p for p in m["per_layer"] if p["layer"] == "collectives"]
    assert {p["name"] for p in collectives} == {
        "collective_exposed_share", "collective_us_per_gradient",
        "psum_bytes_per_gradient", "shard_data_s"}
    for p in collectives:
        assert p["workloads"] == [CELL]
        with open(os.path.join(ONCHIP, "metrics", p["name"] + ".json")) as f:
            spec = json.load(f)
        assert os.path.isfile(os.path.join(
            ONCHIP, "readers", spec["reader"] + ".py"))
