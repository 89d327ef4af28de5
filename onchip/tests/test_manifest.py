"""`BENCHMARK.json` and the files it names, on the CPU, before anything is
sent to the chip.  PR 22 was refused for a layer written as plain words."""

import json
import os
import sys

import pytest

ONCHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(ONCHIP)
for _p in (ROOT, ONCHIP):
    if _p not in sys.path:
        sys.path.insert(0, _p)
from lib import manifest as rules


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_manifest_keeps_every_rule(manifest):
    assert rules.problems(manifest, ROOT) == []


@pytest.mark.parametrize("breach, said", [
    (lambda m: m["per_layer"][0].__setitem__("layer", "fused likelihoods"),
     "layer"),
    (lambda m: m["end_to_end"][0].__setitem__("unit", "tokens per second"),
     "unit"),
    (lambda m: m["per_layer"][0].__setitem__("moves", "nothing"), "moves"),
    (lambda m: m["workloads"][0].__setitem__("name", "a cell"), "name rule"),
    (lambda m: m["per_layer"][0].__setitem__("why", "x"), "keys"),
])
def test_a_breach_is_found(manifest, breach, said):
    m = json.loads(json.dumps(manifest))
    breach(m)
    assert any(said in p for p in rules.problems(m, ROOT))


def test_every_cell_has_its_files(manifest):
    metrics = manifest["end_to_end"] + manifest["per_layer"]
    for cell in manifest["workloads"]:
        with open(os.path.join(ONCHIP, "workloads", cell["name"] + ".json")) as f:
            w = json.load(f)
        assert w["config"] == cell["config"] and w["traffic"] == cell["traffic"]
        assert w["checks"], "a cell is held to at least one compared number"
        assert os.path.isfile(os.path.join(ONCHIP, "checks", w["check"] + ".py"))
        for name in w.get("readings", []):
            assert os.path.isfile(os.path.join(ONCHIP, "metrics", name + ".json"))
        with open(os.path.join(ONCHIP, "traffic", cell["traffic"] + ".json")) as f:
            driver = json.load(f)["driver"]
        assert os.path.isfile(os.path.join(ONCHIP, "drivers", driver + ".py"))
        with open(os.path.join(ONCHIP, "configs", cell["config"] + ".json")) as f:
            cfg = json.load(f)
        for key in ("source", "reduced", "assumed", "sizes", "sampler",
                    "model", "rows", "reference", "counts", "precision",
                    "dry_run"):
            assert key in cfg, (cell["config"], key)
        for folder, name in (("rows", cfg["rows"]["generator"]),
                             ("references", cfg["reference"]),
                             ("counts", cfg["counts"])):
            assert os.path.isfile(os.path.join(ONCHIP, folder, name + ".py"))
    for m in metrics:
        with open(os.path.join(ONCHIP, "metrics", m["name"] + ".json")) as f:
            spec = json.load(f)
        assert os.path.isfile(os.path.join(
            ONCHIP, "readers", spec.get("reader", m["name"]) + ".py"))


def test_configs_say_what_the_manifest_says(manifest):
    for c in manifest["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert cfg["sampler"][key] != cfg["published"][key]
