"""A later PR adds a cell by adding files only.  In a copy of the benchmark a
cell of another kind is added: its own configuration, rows generator, plain
reference, check, driver, traffic, metric and reader, plus manifest entries.
No file that was there is touched, and `run.py` runs the new cell."""

import json
import os
import shutil
import subprocess
import sys

ONCHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(ONCHIP)

NEW = {
    "configs/toy_sum.json": json.dumps({
        "rows": {"generator": "toy_rows", "params": {"scale": 3.0}},
        "reference": "toy_sum", "counts": "none",
        "sizes": {"n": 1000}, "dry_run": {"n": 1000}}),
    "workloads/toy_sum.once.json": json.dumps({
        "config": "toy_sum", "traffic": "once", "check": "toy_sum_check",
        "checks": {"sum_gap": 1e-6}}),
    "traffic/once.json": json.dumps({"driver": "toy_once",
                                    "step_event": "answer"}),
    "metrics/toy_answers.json": json.dumps({"reader": "toy_answers"}),
    "rows/toy_rows.py": (
        "import numpy as np\n"
        "def make(params, sizes, seed):\n"
        "    rng = np.random.default_rng(seed)\n"
        "    return params['scale'] * rng.standard_normal(sizes['n'])\n"),
    "references/toy_sum.py": (
        "import math\n"
        "def total(rows):\n"
        "    return math.fsum(rows.tolist())\n"),
    "checks/toy_sum_check.py": (
        "def compare(measured, env, wanted):\n"
        "    cfg, load = env['config'], env['load']\n"
        "    rows = load('rows', cfg['rows']['generator']).make(\n"
        "        cfg['rows']['params'], env['sizes'], env['seed'])\n"
        "    ref = load('references', cfg['reference']).total(rows)\n"
        "    return {'sum_gap': abs(measured['answer'] - ref) / abs(ref)}\n"),
    "drivers/toy_once.py": (
        "import jax.numpy as jnp\n"
        "def run(env):\n"
        "    cfg = env['config']\n"
        "    rows = env['load']('rows', cfg['rows']['generator']).make(\n"
        "        cfg['rows']['params'], env['sizes'], env['seed'])\n"
        "    env['hooks']['window_opens']()\n"
        "    answer = float(jnp.sum(jnp.asarray(rows, jnp.float64)))\n"
        "    env['hooks']['on_record']({'event': 'answer'})\n"
        "    env['hooks']['window_closes']()\n"
        "    return {'answer': answer, 'attempted': 1, 'failed': 0}\n"),
    "readers/toy_answers.py": (
        "def read(ctx, params):\n"
        "    return ctx['attempted']\n"),
}


def _files(top):
    out = {}
    for folder, _, names in os.walk(top):
        if "__pycache__" in folder or os.sep + "out" in folder:
            continue
        for n in names:
            p = os.path.join(folder, n)
            with open(p, "rb") as f:
                out[os.path.relpath(p, top)] = f.read()
    return out


def test_a_cell_of_another_kind_is_added_by_files_alone(tmp_path):
    shutil.copytree(ONCHIP, tmp_path / "onchip", ignore=shutil.ignore_patterns(
        "out", "__pycache__"))
    before = _files(tmp_path / "onchip")
    for rel, text in NEW.items():
        assert rel not in before
        (tmp_path / "onchip" / rel).write_text(text)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"].append({
        "name": "toy_sum", "source": "a test", "reduced": [], "why": "a test",
        "file": "onchip/configs/toy_sum.json"})
    manifest["workloads"].append({
        "name": "toy_sum.once", "config": "toy_sum", "traffic": "once",
        "chips": 1, "why": "a test"})
    manifest["per_layer"].append({
        "name": "toy_answers", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "entry", "moves": "setup_s",
        "workloads": ["toy_sum.once"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))

    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_ENABLE_X64="1",
               PYTHONPATH=ROOT, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "c"))
    p = subprocess.run(
        [sys.executable, "onchip/run.py", "--workload", "toy_sum.once",
         "--seed", str(2**31 + 5), "--seconds", "1", "--trace", "1",
         "--dry-run"], cwd=tmp_path, env=env, capture_output=True, text=True,
        timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["attempted"] == 1
    assert line["metrics"] == {"toy_answers": {"value": 1, "unit": "count"}}
    assert [c[0] for c in line["compared"]] == ["sum_gap"]
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    after = _files(tmp_path / "onchip")
    assert {k: v for k, v in after.items() if k in before} == before
