"""The program's spans as the benchmark reads them (`lib/spans.py` and the six
readers built on it): the split of the log, the alignment to the profiler's
clock and idle time by span, on a slice recorded on the chip with the numbers
worked out by hand beside it (`testdata/trace_named_v5e.json`), on a few
hand-made cases, and on a program or a trace that has nothing to read."""

import importlib.util
import json
import os
import sys

import pytest

ONCHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(ONCHIP)
for _p in (ROOT, ONCHIP):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from lib import spans, tracered  # noqa: E402

NEW_METRICS = [
    "prepare_data_s", "map_s", "warmup_s", "setup_compile_s", "resume_s",
    "host_cycle_share", "collect_drain_s", "collect_compile_s",
    "block_gap_us", "ll_kernel_ms", "outside_kernel_share",
    "idle_in_gate_share", "idle_in_checkpoint_share",
    "idle_in_dispatch_share",
]


def read_metric(name, ctx):
    with open(os.path.join(ONCHIP, "metrics", name + ".json")) as f:
        spec = json.load(f)
    path = os.path.join(ONCHIP, "readers", spec["reader"] + ".py")
    s = importlib.util.spec_from_file_location("reader_" + spec["reader"],
                                               path)
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod.read(ctx, spec["params"])


def sp(id, parent, run, name, start, end, **fields):
    return {"id": id, "parent": parent, "run": run, "name": name,
            "start_ns": start, "end_ns": end, "fields": fields}


def op(start, dur, name="%fusion.1 = f32[] fusion()", line="XLA Ops"):
    return {"plane": "/device:TPU:0", "line": line, "name": name,
            "start_ns": float(start), "dur_ns": float(dur)}


@pytest.fixture(scope="module")
def named_slice():
    with open(os.path.join(ONCHIP, "testdata", "trace_named_v5e.json")) as f:
        return json.load(f)


#: a hand-made process: prepare outside any run, call A (run 1), the window
#: run (run 2) with two blocks, the budget record and collect
HAND = [
    sp(1, None, 0, "prepare_data", 0, 100, bytes_in=8),
    sp(1, None, 1, "run", 200, 1000, resumed=False),
    sp(2, 1, 1, "compile", 210, 300, stage="init+map", compile_s=0.5),
    sp(3, 2, 1, "map_init", 220, 300, compile_s=0.25),
    sp(4, 1, 1, "warmup", 300, 900),
    sp(1, None, 2, "run", 2000, 9000, resumed=True),
    sp(2, 1, 2, "resume_load", 2010, 2100),
    sp(3, 1, 2, "block.dispatch", 2100, 2200, block=2),
    sp(4, 1, 2, "block.dispatch", 2200, 2300, block=3),
    sp(5, 1, 2, "block.wait", 2300, 4000, block=2),
    sp(6, 1, 2, "block.gate", 4000, 4400, block=2),
    sp(7, 1, 2, "block.record", 4400, 4420, block=2),
    sp(8, 1, 2, "block.checkpoint", 4420, 4500, block=2),
    sp(9, 1, 2, "block.wait", 4500, 6000, block=3),
    sp(10, 1, 2, "block.gate", 6000, 6300, block=3),
    sp(11, 1, 2, "block.record", 6300, 6310, block=3),
    sp(12, 1, 2, "block.record", 6400, 6500, block=3,
       event="budget_exhausted"),
    sp(13, 1, 2, "collect", 6500, 8900),
    sp(14, 13, 2, "collect.drain", 6600, 8000),
    sp(15, 13, 2, "collect.constrain", 8000, 8900, compile_s=0.125),
]


def test_split_into_setup_window_collect():
    parts = spans.split(HAND)
    assert [s["name"] for s in parts["setup"]] == [
        "prepare_data", "run", "compile", "map_init", "warmup"]
    assert parts["run"]["run"] == 2 and parts["window_end_ns"] == 6400
    assert [s["id"] for s in parts["window"]] == list(range(1, 12))
    assert [s["name"] for s in parts["collect"]] == [
        "collect", "collect.drain", "collect.constrain"]
    assert [s["id"] for s in spans.block_records(parts)] == [7, 11]
    # the window by its top-level spans, the uncovered rest as `other`
    cyc = spans.cycle(parts)
    assert cyc["block.wait"] == pytest.approx(3200e-9)
    assert cyc["other"] == pytest.approx((4400 - sum(
        s["end_ns"] - s["start_ns"] for s in parts["window"][1:])) * 1e-9)
    assert spans.split([s for s in HAND if s["name"] != "run"]) is None
    # the tree prints what the children leave uncovered, never drops it
    rows = spans.tree(parts["setup"])
    assert [(d, n) for d, n, *_ in rows] == [
        (0, "prepare_data"), (0, "run"), (1, "compile"), (2, "map_init"),
        (2, "other"), (1, "warmup"), (1, "other")]
    assert rows[4][2] == pytest.approx(10e-9)       # compile less map_init
    assert rows[6][2] == pytest.approx(110e-9)      # run less its children


def test_offset_from_markers_inside_record_spans():
    parts = spans.split(HAND)
    # profiler clock = program clock + 1000; each marker inside its span
    off, half = spans.offset_ns(parts, [("onchip.block.0", 5405.0),
                                        ("onchip.block.1", 7308.0)])
    # block 0 allows [5405-4420, 5405-4400] = [985, 1005]; block 1 allows
    # [7308-6310, 7308-6300] = [998, 1008]; together [998, 1005]
    assert (off, half) == (1001.5, 3.5)
    # markers that no common offset explains: the median of marker less
    # span middle (995 and 1095) with the largest residual
    off, half = spans.offset_ns(parts, [("onchip.block.0", 5405.0),
                                        ("onchip.block.1", 7400.0)])
    assert (off, half) == (1045.0, 50.0)
    assert spans.offset_ns(parts, []) == (None, None)
    assert spans.offset_ns(parts, [("onchip.block.7", 1.0)]) == (None, None)


def test_idle_by_span_names_each_gap_by_the_innermost_span():
    parts = spans.split(HAND)
    events = [
        op(3000, 1000),            # program 2000-3000
        op(4000, 1050),            # gap 5050-5100: in gate of block 2
        op(5100, 305),             # gap 5405-5425: the record (innermost)
        op(5425, 1875),            # gap 7300-7350: gap after block 3's
        op(7350, 50),              # record, no span: other
        op(3000, 4400, "%while.1 = () while()"),  # a container: not counted
        op(3000, 2405, "jit_stark_chees_sample(1)", "XLA Modules"),
        op(5425, 1975, "jit_stark_chees_sample(1)", "XLA Modules"),
    ]
    assert spans.device_gaps(events) == [(5050.0, 5100.0), (5405.0, 5425.0),
                                         (7300.0, 7350.0)]
    idle = spans.idle_by_span(events, parts, 1000.0)
    assert idle["by_span"] == pytest.approx({
        "block.gate": 50e-9, "block.record": 20e-9, "other": 50e-9})
    assert idle["longest"][0][1] == pytest.approx(50e-9)
    assert [g[0] for g in idle["longest"]].count("block.record") == 1
    assert spans.module_gaps_ns(events, "stark_chees_sample") == [20.0]
    assert spans.module_gaps_ns(events, "stark_chees_warm") == []


def hand_ctx(**over):
    ctx = {"dry_run": False, "program_spans": spans.split(HAND),
           "window_s": 4400e-9, "blocks": [{}, {}], "setup_s": 1.0,
           "device": {"window_s": 4400e-9}, "config": {"name": "hand"},
           "trace_events": []}
    ctx.update(over)
    return ctx


def test_span_readers_on_the_hand_made_log(tmp_path, monkeypatch, capsys):
    ctx = hand_ctx()
    assert read_metric("prepare_data_s", ctx) == pytest.approx(100e-9)
    assert read_metric("map_s", ctx) == pytest.approx(80e-9)
    assert read_metric("warmup_s", ctx) == pytest.approx(600e-9)
    assert read_metric("setup_compile_s", ctx) == 0.75
    assert read_metric("resume_s", ctx) == pytest.approx(100e-9)
    # gate 700 + checkpoint 80 + record 30 + dispatch 200 of 4400
    assert read_metric("host_cycle_share", ctx) == pytest.approx(
        100 * 1010 / 4400)
    assert read_metric("collect_drain_s", ctx) == pytest.approx(1400e-9)
    assert read_metric("collect_compile_s", ctx) == 0.125
    capsys.readouterr()


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_readers_return_nothing_where_there_is_nothing(
        name, monkeypatch, capsys):
    """An empty trace, a dry run, and a program without a span log (the
    parent commit under this PR's benchmark files): None, and no raise."""
    from stark_tpu import telemetry

    empty = {"dry_run": False, "trace_events": [], "device": {},
             "blocks": [], "window_s": 1.0, "config": {"name": "none"}}
    monkeypatch.delattr(telemetry, "span_log")
    assert read_metric(name, dict(empty)) is None
    monkeypatch.undo()
    assert read_metric(name, dict(empty, dry_run=True)) is None
    # a log with no entry call in it
    assert read_metric(name, dict(empty, program_spans=None)) is None
    capsys.readouterr()


# ---------------------------------------------------------------------------
# the slice recorded on the chip after the renaming, numbers by hand beside it
# ---------------------------------------------------------------------------


def test_the_recorded_slice_carries_the_stable_names(named_slice):
    names = {(e["line"], tracered.short_name(e["name"]))
             for e in named_slice["events"]}
    assert ("XLA Ops", "jvp_stark_logistic_ll_ (tpu_custom_call)") in names
    assert any(line == spans.MODULES_LINE
               and name.startswith("jit_stark_chees_sample(")
               for line, name in names)
    assert {s["name"] for s in named_slice["spans"]} == {
        "run", "block.wait", "block.gate", "block.record",
        "block.checkpoint"}


def test_alignment_and_idle_by_span_on_the_recorded_slice(named_slice):
    hand = named_slice["by_hand"]
    events = named_slice["events"]
    parts = spans.split(named_slice["spans"])
    assert parts["run"]["fields"] == {"resumed": True}
    off, half = spans.offset_ns(parts, tracered.markers(events))
    assert off == hand["offset_ns"] and half == hand["offset_half_width_ns"]
    assert half < 100e3  # the acceptance bound on the chip: under 100 us
    gaps = spans.device_gaps(events)
    assert len(gaps) == hand["idle_gaps"]
    assert sum(b - a for a, b in gaps) == hand["idle_ns"]
    idle = spans.idle_by_span(events, parts, off)
    assert idle["by_span"] == pytest.approx(
        {k: v / 1e9 for k, v in hand["idle_by_span_ns"].items()})
    assert "other" not in idle["by_span"]
    assert idle["longest"][0] == [hand["longest_gap_in"],
                                  pytest.approx(hand["longest_gap_ns"] / 1e9)]
    assert spans.module_gaps_ns(events, "stark_chees_sample") == [
        hand["module_gap_ns"]]
    # the trace reduction PR 25 wrote agrees on the same events
    busy = tracered.busy(events)
    assert busy["busy_s"] == pytest.approx(hand["busy_ns"] / 1e9)
    assert busy["window_s"] == pytest.approx(hand["slice_ns"] / 1e9)
    assert tracered.kernel_time(events, hand["kernel_pattern"]) == (
        hand["kernel_calls"], pytest.approx(hand["kernel_ns"] / 1e9))
    # the old key finds the same calls: a kernel PR that adds a second
    # custom call breaks that one, not the name
    assert tracered.kernel_time(events, tracered.KERNEL_TARGET)[0] == \
        hand["kernel_calls"]


def test_every_new_reader_on_the_recorded_slice(named_slice, capsys):
    hand = named_slice["by_hand"]
    parts = spans.split(named_slice["spans"])
    window_s = (parts["window_end_ns"] - parts["run"]["start_ns"]) / 1e9
    ctx = {"dry_run": False, "program_spans": parts, "window_s": window_s,
           "blocks": [{}], "setup_s": 1.0, "config": {"name": "recorded"},
           "trace_events": named_slice["events"],
           "device": {"window_s": hand["slice_ns"] / 1e9}}
    got = {name: read_metric(name, ctx) for name in NEW_METRICS}
    capsys.readouterr()
    assert got["block_gap_us"] == hand["module_gap_ns"] / 1e3
    assert got["ll_kernel_ms"] == pytest.approx(
        hand["kernel_ns"] / hand["kernel_calls"] / 1e6)
    assert got["outside_kernel_share"] == pytest.approx(
        100 * (1 - hand["kernel_ns"] / hand["busy_ns"]))
    assert got["idle_in_gate_share"] == pytest.approx(
        100 * hand["idle_by_span_ns"]["block.gate"] / hand["slice_ns"])
    assert got["idle_in_checkpoint_share"] == 0.0
    assert got["idle_in_dispatch_share"] == 0.0
    by_name = {}
    for s in parts["window"]:
        by_name[s["name"]] = by_name.get(s["name"], 0.0) + spans.seconds(s)
    assert got["host_cycle_share"] == pytest.approx(100 * (
        by_name["block.gate"] + by_name["block.record"]
        + by_name["block.checkpoint"]) / window_s)
    # the slice holds no set-up, no collect and no dispatch span
    for name in ("prepare_data_s", "map_s", "warmup_s", "setup_compile_s",
                 "resume_s", "collect_drain_s", "collect_compile_s"):
        assert got[name] is None, name
