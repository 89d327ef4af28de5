"""The rules `BENCHMARK.json` is held to before anything runs, as this
repository's tests check them (the driver's own check is the authority)."""

import json
import os
import re

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_./%-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}


def _line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def problems(manifest, root):
    """Every breach found, as a list of sentences (empty: valid)."""
    bad = []
    if set(manifest) != KEYS:
        bad.append(f"keys {sorted(manifest)} are not exactly {sorted(KEYS)}")
        return bad
    paths = manifest["paths"]
    if not 1 <= len(paths) <= 16 or not all(PATH.match(p) for p in paths):
        bad.append("paths: 1 to 16 relative directories")

    def under_paths(f):
        return any(f.startswith(p.rstrip("/") + "/") for p in paths)

    if not all(_line(w) for w in manifest["command"]) or len(
            manifest["command"]) > 32:
        bad.append("command: at most 32 words of 1 to 200 characters")
    if not isinstance(manifest["run_seconds"], int) or not 1 <= manifest[
            "run_seconds"] <= 51:
        bad.append("run_seconds: a whole number from 1 to 51")
    cells = len(manifest["workloads"])
    full = 2 + 14 * 24
    if full * (manifest["run_seconds"] + 60) + 24 * 180 + 1200 > 43200:
        bad.append("run_seconds: a full check of 24 cells does not fit")

    names = set()

    def name(kind, n):
        if not isinstance(n, str) or not NAME.match(n):
            bad.append(f"{kind} name {n!r} breaks the name rule")
        if (kind, n) in names:
            bad.append(f"{kind} name {n!r} twice")
        names.add((kind, n))

    files = set()
    for c in manifest["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            bad.append(f"config {c.get('name')}: keys {sorted(c)}")
            continue
        name("config", c["name"])
        if not under_paths(c["file"]) or c["file"] in files:
            bad.append(f"config {c['name']}: file outside paths or shared")
        files.add(c["file"])
        if not os.path.isfile(os.path.join(root, c["file"])):
            bad.append(f"config {c['name']}: {c['file']} is not there")
        if len(c["reduced"]) > 16 or not all(
                NAME.match(k) for k in c["reduced"]):
            bad.append(f"config {c['name']}: reduced")
        if any(k.endswith(("_dim", "_rank")) for k in c["reduced"]):
            bad.append(f"config {c['name']}: reduced names a width")
        if not _line(c["source"]) or not _line(c["why"]):
            bad.append(f"config {c['name']}: source / why")
    config_names = {c["name"] for c in manifest["configs"]}
    pairs, used = set(), set()
    for w in manifest["workloads"]:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            bad.append(f"cell {w.get('name')}: keys {sorted(w)}")
            continue
        name("cell", w["name"])
        if not NAME.match(w["traffic"]):
            bad.append(f"cell {w['name']}: traffic name")
        if w["config"] not in config_names:
            bad.append(f"cell {w['name']}: unknown config")
        used.add(w["config"])
        if (w["config"], w["traffic"]) in pairs:
            bad.append(f"cell {w['name']}: pair appears twice")
        pairs.add((w["config"], w["traffic"]))
        if w["chips"] not in (1, 4) or not _line(w["why"]):
            bad.append(f"cell {w['name']}: chips / why")
    if used != config_names:
        bad.append("a configuration no cell uses")
    four = sum(w.get("chips") == 4 for w in manifest["workloads"])
    if four > max(1, cells // 4):
        bad.append("too many four-chip cells")
    cell_names = {w["name"] for w in manifest["workloads"]}

    def reports(metric):
        return set(metric.get("workloads", cell_names))

    e2e = {}
    for m in manifest["end_to_end"]:
        if not {"name", "unit", "better", "bound", "source"} <= set(m) or set(
                m) - {"name", "unit", "better", "bound", "source",
                      "workloads"}:
            bad.append(f"end_to_end {m.get('name')}: keys {sorted(m)}")
            continue
        name("metric", m["name"])
        e2e[m["name"]] = m
        if not UNIT.match(m["unit"]) or m["better"] not in ("lower", "higher"):
            bad.append(f"{m['name']}: unit / better")
        if m["source"] not in ("host_clock", "device_trace"):
            bad.append(f"{m['name']}: source")
        if not 0.01 <= m["bound"] <= 0.1:
            bad.append(f"{m['name']}: bound")
        if not reports(m) <= cell_names:
            bad.append(f"{m['name']}: unknown cell")
    if "setup_s" not in e2e or "workloads" in e2e.get("setup_s", {}):
        bad.append("setup_s has to be reported by every cell")
    layers = {}
    for m in manifest["per_layer"]:
        want = {"name", "unit", "better", "source", "layer", "moves"}
        if not want <= set(m) or set(m) - want - {"workloads"}:
            bad.append(f"per_layer {m.get('name')}: keys {sorted(m)}")
            continue
        name("metric", m["name"])
        if not UNIT.match(m["unit"]) or m["better"] not in ("lower", "higher"):
            bad.append(f"{m['name']}: unit / better")
        if m["source"] not in SOURCES:
            bad.append(f"{m['name']}: source")
        if not NAME.match(m["layer"]):
            bad.append(f"{m['name']}: layer {m['layer']!r} breaks the name rule")
        layers.setdefault(m["layer"], []).append(m["name"])
        if m["moves"] not in e2e:
            bad.append(f"{m['name']}: moves no end-to-end metric")
        elif not reports(m) <= reports(e2e[m["moves"]]):
            bad.append(f"{m['name']}: a cell that does not report "
                       f"{m['moves']}")
        if m["name"].endswith("_roofline") and m["unit"] != "%":
            bad.append(f"{m['name']}: a roofline share has the unit %")
    for w in cell_names:
        mine = [m for m in manifest["end_to_end"] if w in reports(m)]
        if len(mine) < 2 or not any(
                w in reports(m) for m in manifest["per_layer"]):
            bad.append(f"cell {w}: needs setup_s, another end-to-end metric "
                       "and a per-layer metric")
    if len(json.dumps(manifest)) > 64 * 1024:
        bad.append("the manifest is over 64 KiB")
    return bad
