"""Layer `collectives`' readers and the name-keyed readers on a slice recorded
on four chips (`testdata/trace_mesh_v5e.json`: four device planes around a
block boundary of `logistic_n80m.sample.x4`, numbers worked out by hand beside
it), on hand-made cases, and on a trace or a program that has nothing to
read."""

import importlib.util
import json
import os
import re
import sys

import pytest

ONCHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(ONCHIP)
for _p in (ROOT, ONCHIP):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from lib import tracered  # noqa: E402

PLANES = [f"/device:TPU:{i}" for i in range(4)]
PSUM = "%psum.7 = f32[8,33]{1,0:T(8,128)S(1)} all-reduce(f32[8,33] %x), channel_id=1"


def reader(name):
    with open(os.path.join(ONCHIP, "metrics", name + ".json")) as f:
        spec = json.load(f)
    path = os.path.join(ONCHIP, "readers", spec["reader"] + ".py")
    s = importlib.util.spec_from_file_location("reader_" + spec["reader"], path)
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return lambda ctx: mod.read(ctx, spec["params"])


def op(plane, start, dur, name="%fusion.1 = f32[] fusion()", line="XLA Ops"):
    return {"plane": plane, "line": line, "name": name,
            "start_ns": float(start), "dur_ns": float(dur)}


@pytest.fixture(scope="module")
def mesh_slice():
    with open(os.path.join(ONCHIP, "testdata", "trace_mesh_v5e.json")) as f:
        return json.load(f)


def test_the_recorded_mesh_slice_is_what_it_says(mesh_slice):
    events, hand = mesh_slice["events"], mesh_slice["by_hand"]
    assert tracered.device_planes(events) == PLANES
    with open(os.path.join(ONCHIP, "metrics", "ll_kernel_ms.json")) as f:
        rx = json.load(f)["params"]["pattern"]
    for plane in PLANES:
        ops = tracered.ops(events, plane)
        kernels = [int(e["dur_ns"]) for e in ops if re.search(rx, e["name"])]
        psums = [int(e["dur_ns"]) for e in ops if " all-reduce(" in e["name"]]
        assert kernels == hand["kernel_ns"][plane]
        assert psums == hand["psum_ns"][plane]
    b = tracered.busy(events)
    assert round(1e9 * b["window_s"]) == hand["slice_ns"]
    assert len(b["per_plane"]) == 4


def test_collective_readers_on_the_recorded_mesh_slice(mesh_slice):
    ctx = {"trace_events": mesh_slice["events"]}
    hand = mesh_slice["by_hand"]
    assert reader("collective_us_per_gradient")(ctx) == pytest.approx(
        hand["collective_us_per_gradient"], rel=1e-9)
    assert reader("collective_exposed_share")(ctx) == pytest.approx(
        hand["collective_exposed_share"], rel=1e-4)


def test_name_keyed_readers_find_the_mesh_runs_program_and_kernel(mesh_slice):
    """`block_gap_us` finds `jit_stark_chees_sample` on the modules line of a
    mesh run (first plane), `ll_kernel_ms` the shared kernel on all four."""
    ctx = {"trace_events": mesh_slice["events"]}
    hand = mesh_slice["by_hand"]
    assert reader("block_gap_us")(ctx) == pytest.approx(
        hand["block_gap_us"], rel=1e-9)
    assert reader("ll_kernel_ms")(ctx) == pytest.approx(
        hand["ll_kernel_ms"], rel=1e-9)
    calls, seconds = tracered.kernel_time(
        mesh_slice["events"], "custom_call_target=\"tpu_custom_call\"")
    assert calls == 0 and seconds == 0  # names cut short: see `what`


def test_collective_time_by_hand():
    """One plane, a program from 0 to 1000: a synchronous all-reduce alone
    (100-140), one half under another operation (300-340 under 320-400), an
    asynchronous pair (start 500-505, done 560-570: 70 start to done) with
    an operation inside it (510-550), and one outside the program."""
    p = PLANES[0]
    events = [
        op(p, 0, 1000, "jit_stark_chees_sample(1)", "XLA Modules"),
        op(p, 0, 100), op(p, 100, 40, PSUM),
        op(p, 300, 40, PSUM), op(p, 320, 80),
        op(p, 500, 5, "%all-reduce-start.3 = (f32[8], f32[8]) "
           "all-reduce-start(f32[8] %y)"),
        op(p, 510, 40),
        op(p, 560, 10, "%all-reduce-done.3 = f32[8] all-reduce-done("
           "(f32[8], f32[8]) %all-reduce-start.3)"),
        op(p, 1200, 30, PSUM), op(p, 1230, 70),
    ]
    ctx = {"trace_events": events}
    # inside the program: 40, 40 and 70 long
    assert reader("collective_us_per_gradient")(ctx) == pytest.approx(0.040)
    # exposed: 40 + 20 + (70 - 40) + 30 = 120 of a slice of 1300
    assert reader("collective_exposed_share")(ctx) == pytest.approx(
        100.0 * 120 / 1300)
    # an operand that is a collective's result is no collective
    events.append(op(p, 1300, 10, "%fusion.9 = f32[8] fusion(f32[8,33] "
                     "%psum.7, f32[8] %all-reduce-done.3), kind=kLoop"))
    assert reader("collective_exposed_share")(
        {"trace_events": events}) == pytest.approx(100.0 * 120 / 1310)


def test_collective_readers_average_planes_and_find_nothing_quietly():
    events = [op(PLANES[0], 0, 100), op(PLANES[0], 100, 20, PSUM),
              op(PLANES[1], 0, 100), op(PLANES[1], 100, 60, PSUM)]
    ctx = {"trace_events": events}
    # (20 + 60) / 2 of a slice of 160
    assert reader("collective_exposed_share")(ctx) == pytest.approx(25.0)
    # no program of that name (a parent whose mesh programs are not named)
    assert reader("collective_us_per_gradient")(ctx) is None
    one_chip = {"trace_events": [op(PLANES[0], 0, 100)]}
    assert reader("collective_exposed_share")(one_chip) is None
    assert reader("collective_us_per_gradient")(one_chip) is None
    for empty in ({}, {"trace_events": []}):
        assert reader("collective_exposed_share")(empty) is None


def sp(id, parent, run, name, start, end, **fields):
    return {"id": id, "parent": parent, "run": run, "name": name,
            "start_ns": start, "end_ns": end, "fields": fields}


def test_the_programs_counters_are_read_from_its_spans():
    parts = {
        "setup": [sp(2, 1, 1, "shard_data", 10, 4010, bytes=8, shards=4,
                     moved_bytes=0),
                  sp(2, 1, 2, "shard_data", 9000, 11000, bytes=8, shards=4,
                     moved_bytes=0)],
        "window": [sp(2, 1, 3, "shard_data", 20000, 21000, moved_bytes=0),
                   sp(5, 1, 3, "block.gate", 30000, 31000, block=2,
                      block_grad_evals=10, psums_per_gradient=1,
                      psum_bytes_per_gradient=1056),
                   sp(9, 1, 3, "block.gate", 40000, 41000, block=3,
                      block_grad_evals=10, psums_per_gradient=1,
                      psum_bytes_per_gradient=1056)],
        "collect": [],
    }
    ctx = {"dry_run": False, "program_spans": parts}
    assert reader("psum_bytes_per_gradient")(ctx) == 1056.0
    # set-up's two calls, summed; the window's own is not set-up
    assert reader("shard_data_s")(ctx) == pytest.approx(6e-6)
    # one chip, or a program from before the counters: nothing, no error
    bare = {"dry_run": False, "program_spans": {
        "setup": [], "collect": [],
        "window": [sp(5, 1, 3, "block.gate", 0, 1, block_grad_evals=10)]}}
    assert reader("psum_bytes_per_gradient")(bare) is None
    assert reader("shard_data_s")(bare) is None
    assert reader("psum_bytes_per_gradient")(
        {"dry_run": False, "program_spans": None}) is None
    assert reader("psum_bytes_per_gradient")(
        dict(ctx, dry_run=True)) is None
