"""The program's device timeline as the benchmark reads it (`lib/timeline.py`
and `readers/device_timeline.py`, PR 40): the window's account on a
hand-made log, worked out by hand beside it; set-up's device waits and
warm-up's lanes; nothing from a program without the fields; and the
manifest's cells for each new metric."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

ONCHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(ONCHIP)
for _p in (ROOT, ONCHIP):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from lib import spans, timeline  # noqa: E402

TIMELINE_METRICS = {
    "setup_device_share": None, "warmup_device_share": None,
    "warmup_lane_occupancy": ["hier_n16m.nuts"],
    "window_device_idle_share": None, "window_tail_s": None,
}


def _reader(name):
    with open(os.path.join(ONCHIP, "metrics", name + ".json")) as f:
        spec = json.load(f)
    path = os.path.join(ONCHIP, "readers", spec["reader"] + ".py")
    s = importlib.util.spec_from_file_location("reader_" + spec["reader"],
                                               path)
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return lambda ctx: mod.read(ctx, spec["params"])


def sp(id, parent, run, name, start, end, **fields):
    return {"id": id, "parent": parent, "run": run, "name": name,
            "start_ns": start, "end_ns": end, "fields": fields}


#: set-up (prepare, call A with MAP, a two-segment warm-up and its block,
#: the rehearsal), then the window run: blocks 2 to 5 counted, the host
#: late after block 2's checkpoint and in block 3's record (a traced run's
#: profiler), the budget record, collect.  Device seconds are nanoseconds
#: here, so that the shares come out plain.
TIMELINE = [
    sp(1, None, 0, "prepare_data", 0, 100, device_wait_s=50e-9),
    sp(1, None, 1, "run", 200, 1000),
    sp(2, 1, 1, "compile", 210, 300, stage="init+map"),
    sp(3, 2, 1, "map_init", 220, 300, device_wait_s=60e-9),
    sp(4, 1, 1, "warmup", 300, 900),
    sp(5, 4, 1, "compile", 300, 400, stage="warmup_init",
       device_wait_s=40e-9),
    sp(6, 4, 1, "warmup_block", 400, 700, device_wait_s=280e-9,
       tree_leaves=18, lane_iterations=6),
    sp(7, 4, 1, "warmup_block", 700, 880, device_wait_s=130e-9,
       tree_leaves=14, lane_iterations=4),
    sp(8, 1, 1, "block.wait", 910, 990, block=1, device_wait_s=70e-9,
       device_done_ns=980),
    sp(1, None, 2, "run", 1100, 1900, resumed=True),
    sp(2, 1, 2, "block.wait", 1200, 1300, block=2, device_wait_s=90e-9,
       device_done_ns=1290),
    sp(1, None, 3, "run", 2000, 9000, resumed=True),
    sp(2, 1, 3, "resume_load", 2010, 2100),
    sp(3, 1, 3, "block.dispatch", 2100, 2200, block=2),
    sp(4, 1, 3, "block.dispatch", 2200, 2300, block=3),
    sp(5, 1, 3, "block.wait", 2300, 3100, block=2, device_done_ns=3000),
    sp(6, 1, 3, "block.gate", 3100, 3200, block=2),
    sp(7, 1, 3, "block.record", 3200, 3220, block=2),
    sp(8, 1, 3, "block.checkpoint", 3220, 3900, block=2),
    sp(9, 1, 3, "block.dispatch", 3900, 4000, block=4),
    sp(10, 1, 3, "block.wait", 4000, 4010, block=3, device_done_ns=3500),
    sp(11, 1, 3, "block.gate", 4010, 4100, block=3),
    sp(12, 1, 3, "block.record", 4100, 4600, block=3),
    sp(13, 1, 3, "block.dispatch", 4600, 4700, block=5),
    sp(14, 1, 3, "block.wait", 4700, 4710, block=4, device_done_ns=4300),
    sp(15, 1, 3, "block.gate", 4710, 4800, block=4),
    sp(16, 1, 3, "block.record", 4800, 4810, block=4),
    sp(17, 1, 3, "block.dispatch", 4810, 4820, block=6),
    sp(18, 1, 3, "block.wait", 4820, 5500, block=5, device_done_ns=5400),
    sp(19, 1, 3, "block.gate", 5500, 5600, block=5),
    sp(20, 1, 3, "block.record", 5600, 5610, block=5),
    sp(21, 1, 3, "block.record", 5700, 5710, block=5,
       event="budget_exhausted", tail_s=300e-9),
    sp(22, 1, 3, "collect", 5800, 8900),
    sp(23, 22, 3, "collect.drain", 5800, 6000, block=6,
       device_wait_s=150e-9),
]


def timeline_ctx(log=TIMELINE, **over):
    ctx = {"dry_run": False, "program_spans": spans.split(log),
           "program_span_list": log, "window_s": 3700e-9,
           "setup_s": 1000e-9, "chains": 4, "blocks": [{}] * 4}
    ctx.update(over)
    return ctx


def test_window_account_adds_up_to_the_window():
    wnd = timeline.window(TIMELINE)
    # the run's start to the budget record's
    assert wnd["window_s"] == pytest.approx(3700e-9)
    assert wnd["resume_s"] == pytest.approx(100e-9)
    assert wnd["first_enqueue_s"] == pytest.approx(100e-9)
    # block 2 from its dispatch's end, 3 from 2's completion, 4 from its
    # own dispatch's end (after 3's completion), 5 likewise
    assert wnd["intervals"] == [[2, 2200, 3000], [3, 3000, 3500],
                                [4, 4000, 4300], [5, 4700, 5400]]
    assert wnd["busy_s"] == pytest.approx(2300e-9)
    assert wnd["idle_s"] == pytest.approx(900e-9)
    assert wnd["tail_s"] == pytest.approx(300e-9)
    total = sum(wnd[k] for k in ("resume_s", "first_enqueue_s", "busy_s",
                                 "idle_s", "tail_s"))
    assert total == pytest.approx(wnd["window_s"])
    assert wnd["blocks"] == 4


def test_window_idle_by_the_innermost_host_span():
    idle = timeline.window(TIMELINE)["idle_by_span"]
    # 3500-3900 in block 2's checkpoint, 3900-4000 in block 4's dispatch;
    # 4300-4600 in block 3's record, 4600-4700 in block 5's dispatch
    assert idle == pytest.approx({"block.checkpoint": 400e-9,
                                  "block.dispatch": 200e-9,
                                  "block.record": 300e-9})
    assert timeline.idle_by_span([(0, 10), (20, 30)], [
        sp(1, None, 1, "a", 0, 25), sp(2, 1, 1, "b", 5, 8)]) == \
        pytest.approx({"a": 12e-9, "b": 3e-9, "other": 5e-9})


def test_timeline_readers_on_the_hand_made_log(capsys):
    ctx = timeline_ctx()
    # prepare 50 + MAP 60 + warm-up 40 + 280 + 130 + A's block 70 + the
    # rehearsal's 90 of 1000
    assert _reader("setup_device_share")(ctx) == pytest.approx(72.0)
    # (40 + 280 + 130) of warm-up's 600
    assert _reader("warmup_device_share")(ctx) == pytest.approx(75.0)
    # 32 leaves over 4 chains x 10 lane iterations
    assert _reader("warmup_lane_occupancy")(ctx) == pytest.approx(80.0)
    # 900 idle less 300 in `block.record`, of 3700
    assert _reader("window_device_idle_share")(ctx) == pytest.approx(
        100 * 600 / 3700)
    assert _reader("window_tail_s")(ctx) == pytest.approx(300e-9)
    assert "device timeline" in capsys.readouterr().err
    # a dry run keeps the counter and no time
    dry = timeline_ctx(dry_run=True)
    assert _reader("warmup_lane_occupancy")(dry) == pytest.approx(80.0)
    assert _reader("setup_device_share")(dry) is None
    assert _reader("window_tail_s")(dry) is None


def _without_fields(log):
    drop = {"device_wait_s", "device_done_ns", "tail_s", "tree_leaves",
            "lane_iterations"}
    return [dict(s, fields={k: v for k, v in s["fields"].items()
                            if k not in drop}) for s in log]


@pytest.mark.parametrize("name", sorted(TIMELINE_METRICS))
def test_timeline_readers_return_nothing_where_there_is_nothing(
        name, monkeypatch):
    """The parent's log (no such fields), a log without an entry call, and a
    program without a span log: None, and no raise."""
    read = _reader(name)
    parent = _without_fields(TIMELINE)
    assert read(timeline_ctx(parent)) is None
    assert read(timeline_ctx(program_spans=None)) is None
    from stark_tpu import telemetry

    monkeypatch.delattr(telemetry, "span_log")
    assert read({"dry_run": False, "setup_s": 1.0, "window_s": 1.0,
                 "chains": 4}) is None


def test_each_timeline_metric_is_listed_on_its_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = [w["name"] for w in manifest["workloads"]]
    spec = importlib.util.spec_from_file_location(
        "onchip_run_manifest", os.path.join(ONCHIP, "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    entries = {m["name"]: m for m in manifest["per_layer"]}
    for name, only in TIMELINE_METRICS.items():
        m = entries[name]
        assert m["source"] == "program_counter"
        assert m["workloads"] == (only or cells)
        for cell in manifest["workloads"]:
            listed = name in run.metrics_of(manifest, cell, "per_layer")
            assert listed == (cell["name"] in (only or cells))


def test_dry_run_reports_warmup_lanes_of_the_nuts_cell():
    env = dict(os.environ, JAX_PLATFORMS="cpu", STARK_PROFILE="0")
    p = subprocess.run(
        [sys.executable, os.path.join(ONCHIP, "run.py"), "--workload",
         "hier_n16m.nuts", "--seed", str(2**31 + 40), "--seconds", "2",
         "--trace", "1", "--dry-run"], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])["metrics"]
    assert 0.0 < got["warmup_lane_occupancy"]["value"] <= 100.0
    assert got["warmup_lane_occupancy"]["unit"] == "%"
    # times are the chip's: none from the CPU
    assert not set(TIMELINE_METRICS) - {"warmup_lane_occupancy"} & set(got)
