"""Run timeline profiler (stark_tpu/profiling.py): span attribution,
the ``span`` event family, and the promoted dispatch-count probe.

The acceptance contract under test: a fresh eight-schools trace must
decompose into non-overlapping spans covering >=95% of the run wall
(``tools/timeline_report.py``), ``span`` is a registered event type
(schema lint green), pre-PR-11 traces degrade to ``n/a`` — never an
error — and `profiling.DispatchProbe` is the PR 8 `_GradEvalProbe`
promoted (same counting semantics, re-exported under the old name for
the nutssched microbench).
"""

import json
import os
import sys

import pytest

from stark_tpu import profiling, telemetry
from stark_tpu.profiling import (
    DispatchProbe,
    deregister_probe,
    probe_counts,
    register_probe,
    spans_from_events,
    timeline_summary,
)

sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools")
)


def _ev(event, wall_s, run=1, **fields):
    return {"schema": 1, "event": event, "ts": 0.0, "wall_s": wall_s,
            "run": run, **fields}


def _synthetic_trace():
    """A hand-built run: compile 1s, warmup 2s, two draw blocks (one
    with overlap fields), one checkpoint, collect — tiling 10s."""
    return [
        _ev("run_start", 0.0, model="M", kernel="nuts", chains=2),
        _ev("compile", 1.0, dur_s=1.0, stage="build"),
        _ev("warmup_block", 3.0, dur_s=2.0),
        # block 1: 2s, 0.5s host hidden + 0.25s device idle
        _ev("sample_block", 5.0, dur_s=2.0, block=1,
            t_host_hidden_s=0.5, device_idle_s=0.25),
        _ev("checkpoint", 5.5, dur_s=0.5, block=1),
        # block 2: no overlap fields (pre-PR-3 shape) -> one dispatch span
        _ev("sample_block", 8.5, dur_s=3.0, block=2),
        _ev("collect", 10.0, dur_s=1.5),
        _ev("run_end", 10.0, dur_s=10.0, converged=True),
    ]


# ---------------------------------------------------------------------------
# span synthesis
# ---------------------------------------------------------------------------


def test_spans_tile_and_never_overlap():
    tl = spans_from_events(_synthetic_trace())
    assert tl["synthesized"] is True
    assert tl["wall_s"] == pytest.approx(10.0)
    spans = tl["spans"]
    # strictly non-overlapping, sorted
    for a, b in zip(spans, spans[1:]):
        assert a["end"] <= b["start"] + 1e-9
    covered = sum(sp["dur"] for sp in spans)
    assert covered == pytest.approx(10.0, abs=1e-6)
    kinds = {sp["kind"] for sp in spans}
    assert {"compile", "warmup", "dispatch", "host_hidden",
            "device_idle", "checkpoint", "host"} == kinds


def test_block_overlap_decomposition_sums_to_block_wall():
    spans = [
        sp for sp in spans_from_events(_synthetic_trace())["spans"]
        if sp.get("block") == 1 and sp["src"] == "sample_block"
    ]
    by_kind = {sp["kind"]: sp["dur"] for sp in spans}
    assert by_kind["host_hidden"] == pytest.approx(0.5)
    assert by_kind["device_idle"] == pytest.approx(0.25)
    assert by_kind["dispatch"] == pytest.approx(1.25)
    assert sum(by_kind.values()) == pytest.approx(2.0)


def test_nested_phase_keeps_inner_attribution():
    """The fleet nests warmup_block phases inside a compile setup phase:
    the inner (earlier-emitted) spans keep their interval, the outer
    keeps only the unclaimed remainder — no double counting."""
    events = [
        _ev("run_start", 0.0),
        _ev("warmup_block", 2.0, dur_s=1.0),   # inner [1, 2]
        _ev("compile", 3.0, dur_s=3.0),        # outer [0, 3]
        _ev("run_end", 3.0, dur_s=3.0),
    ]
    tl = spans_from_events(events)
    by_kind = {}
    for sp in tl["spans"]:
        by_kind[sp["kind"]] = by_kind.get(sp["kind"], 0.0) + sp["dur"]
    assert by_kind["warmup"] == pytest.approx(1.0)
    assert by_kind["compile"] == pytest.approx(2.0)  # [0,1] + [2,3]
    assert sum(by_kind.values()) == pytest.approx(3.0)


def test_overlap_estimates_clipped_to_block_wall():
    """An overshooting device-idle estimate can never attribute more
    time than the block's own measured wall."""
    events = [
        _ev("run_start", 0.0),
        _ev("sample_block", 1.0, dur_s=1.0, block=1,
            t_host_hidden_s=2.0, device_idle_s=2.0),
        _ev("run_end", 1.0, dur_s=1.0),
    ]
    spans = spans_from_events(events)["spans"]
    assert sum(sp["dur"] for sp in spans) == pytest.approx(1.0)


def test_summary_fields_and_null_conventions():
    s = timeline_summary(_synthetic_trace())
    assert s["compile_s"] == pytest.approx(1.0)
    assert s["dispatch_count"] == 3  # warmup + 2 draw blocks
    assert s["span_coverage_frac"] == pytest.approx(1.0)
    # a trace with no phase events: every field null, never 0.0
    bare = timeline_summary([_ev("run_start", 0.0), _ev("run_end", 1.0)])
    assert bare["compile_s"] is None
    assert bare["dispatch_count"] is None
    assert bare["span_coverage_frac"] is None
    empty = timeline_summary([])
    assert empty["span_coverage_frac"] is None


def test_summary_picks_last_run_by_default():
    events = _synthetic_trace() + [
        _ev("run_start", 11.0, run=2),
        _ev("compile", 13.0, run=2, dur_s=2.0),
        _ev("run_end", 13.0, run=2, dur_s=2.0),
    ]
    s = timeline_summary(events)
    assert s["run"] == 2
    assert s["compile_s"] == pytest.approx(2.0)
    assert timeline_summary(events, run=1)["dispatch_count"] == 3


# ---------------------------------------------------------------------------
# span event family (written from the span log)
# ---------------------------------------------------------------------------


def test_span_event_registered_in_schema():
    assert "span" in telemetry.ALL_EVENT_TYPES
    assert "span" in telemetry.PROFILING_EVENT_TYPES


def test_span_events_written_from_the_span_log(tmp_path, monkeypatch):
    """``span`` events are the program's own spans: real start, end and
    parent on the trace's wall clock, nothing derived from ``dur_s``."""
    import time as _time

    monkeypatch.setenv("STARK_PROFILE_SPANS", "1")
    path = str(tmp_path / "t.jsonl")
    with telemetry.RunTrace(path) as tr:
        with telemetry.span("before"):  # closed before the block: not written
            pass
        with profiling.span_events(tr), telemetry.run_span():
            tr.emit("run_start")
            with tr.phase("compile", stage="init+map"):
                with telemetry.span("map_init", steps=3):
                    _time.sleep(0.02)
            with telemetry.span("block.wait", block=1):
                _time.sleep(0.01)
            with telemetry.span("block.gate", block=1):
                pass
            tr.emit("run_end", dur_s=0.05)
        with telemetry.span("block.gate", block=9):  # after the block
            pass
    events = telemetry.read_trace(path)
    spans = [e for e in events if e["event"] == "span"]
    assert [e["src"] for e in spans] == [
        "map_init", "compile", "block.wait", "block.gate"]
    assert [e["kind"] for e in spans] == [
        "warmup", "compile", "dispatch", "host"]
    by_src = {e["src"]: e for e in spans}
    assert by_src["map_init"]["parent"] == by_src["compile"]["id"]
    assert by_src["compile"]["stage"] == "init+map"
    assert by_src["block.wait"]["block"] == 1
    # written at run_end: inside the run's envelope, with its ordinal
    order = [e["event"] for e in events]
    assert order.index("run_end") < order.index("span") or all(
        e["run"] == 1 for e in spans)
    assert all(e["run"] == 1 for e in spans)
    compile_ev = next(e for e in events if e["event"] == "compile")
    for e in spans:
        assert e["end_s"] - e["start_s"] == pytest.approx(e["dur_s"],
                                                          abs=1e-3)
        telemetry.validate_event(e)
    # one clock: the phase event's emission time is the span's end
    assert by_src["compile"]["end_s"] == pytest.approx(
        compile_ev["wall_s"], abs=2e-3)
    assert by_src["compile"]["dur_s"] == pytest.approx(
        compile_ev["dur_s"], abs=1e-3)
    assert by_src["map_init"]["dur_s"] >= 0.02
    # the read path prefers literal spans over synthesis; the inner span
    # claims its interval, the outer keeps the remainder
    tl = spans_from_events(events)
    assert tl["synthesized"] is False
    assert {sp["kind"] for sp in tl["spans"]} >= {"warmup", "dispatch"}
    total = sum(sp["dur"] for sp in tl["spans"])
    assert total <= tl["wall_s"] + 1e-6


def test_span_events_need_no_subtraction(tmp_path, monkeypatch):
    """The pipelined loop's enqueue ran while the previous block
    computed: the subtraction path has to GUESS that wall (its ``gap``
    spans); the span log measured it, so a file with ``span`` events
    carries no gap span and covers the same wall."""
    import time as _time

    monkeypatch.setenv("STARK_PROFILE_SPANS", "1")
    path = str(tmp_path / "t.jsonl")
    with telemetry.RunTrace(path) as tr, profiling.span_events(tr):
        for blk in (1, 2):
            with telemetry.span("block.dispatch", block=blk):
                _time.sleep(0.05)  # the enqueue, out of line
            with telemetry.span("block.wait", block=blk):
                _time.sleep(0.02)
            tr.emit("sample_block", dur_s=0.02, block=blk)
    events = telemetry.read_trace(path)
    literal = spans_from_events(events)
    assert literal["synthesized"] is False
    assert not any(sp.get("gap") for sp in literal["spans"])
    synth = spans_from_events([e for e in events if e["event"] != "span"])
    assert synth["synthesized"] is True
    assert any(sp.get("gap") for sp in synth["spans"])
    lit_cov = sum(sp["dur"] for sp in literal["spans"])
    syn_cov = sum(sp["dur"] for sp in synth["spans"])
    assert lit_cov == pytest.approx(0.14, abs=0.03)
    assert lit_cov >= syn_cov - 1e-3


def test_span_events_env_gate(tmp_path, monkeypatch):
    def spans_written(trace_path):
        return [e for e in telemetry.read_trace(trace_path)
                if e["event"] == "span"]

    monkeypatch.delenv("STARK_PROFILE_SPANS", raising=False)
    a = str(tmp_path / "a.jsonl")
    with telemetry.RunTrace(a) as tr, profiling.span_events(tr):
        with tr.phase("compile"):
            pass
        assert not telemetry._EVENT_LISTENERS
    assert spans_written(a) == []  # default traces carry no span event
    monkeypatch.setenv("STARK_PROFILE_SPANS", "1")
    with profiling.span_events(telemetry.NULL_TRACE):
        assert not telemetry._EVENT_LISTENERS
    b = str(tmp_path / "b.jsonl")
    with telemetry.RunTrace(b) as tr, profiling.span_events(tr):
        assert telemetry._EVENT_LISTENERS
        with tr.phase("compile"):
            pass
    assert [e["src"] for e in spans_written(b)] == ["compile"]
    assert not telemetry._EVENT_LISTENERS


# ---------------------------------------------------------------------------
# dispatch probe (promoted _GradEvalProbe)
# ---------------------------------------------------------------------------


def test_dispatch_probe_counts_executed_calls():
    import jax
    import jax.numpy as jnp

    probe = DispatchProbe(label="unit")
    f = jax.jit(probe.wrap(lambda x: x * 2.0))
    for _ in range(3):
        jax.block_until_ready(f(jnp.ones(4)))
    assert probe.snapshot() == 3
    probe.reset()
    assert probe.snapshot() == 0


def test_dispatch_probe_counts_masked_lane_evals_too():
    """The probe's reason to exist: a while_loop iteration evaluates
    every lane, finished or not — executed counts exceed 'useful'."""
    import jax
    import jax.numpy as jnp

    probe = DispatchProbe(label="loop")
    g = probe.wrap(lambda x: x + 1.0)

    @jax.jit
    def run(x):
        return jax.lax.fori_loop(0, 5, lambda i, v: g(v), x)

    jax.block_until_ready(run(jnp.zeros(2)))
    assert probe.snapshot() == 5


def test_probe_registry_roundtrip():
    probe = register_probe(DispatchProbe(label="reg_demo"))
    try:
        assert probe_counts(drain=False)["reg_demo"] == 0
        probe.calls = 7
        assert probe_counts()["reg_demo"] == 7
    finally:
        deregister_probe("reg_demo")
    assert "reg_demo" not in probe_counts(drain=False)


def test_benchmarks_reexports_probe_under_historical_name():
    from stark_tpu.benchmarks import _GradEvalProbe

    assert _GradEvalProbe is DispatchProbe


def test_probe_bind_matches_model_potential():
    """The FlatModel-compatible bind: same values/grads as the unprobed
    potential, calls counted per executed evaluation."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from stark_tpu.model import flatten_model, prepare_model_data
    from stark_tpu.models import Logistic, synth_logistic_data

    model = Logistic(num_features=3)
    data, _ = synth_logistic_data(jax.random.PRNGKey(0), 64, 3)
    fm = flatten_model(model)
    pdata = prepare_model_data(model, data)
    probe = DispatchProbe(fm)
    z = 0.1 * jnp.ones(fm.ndim)
    v_ref, g_ref = fm.bind(pdata).value_and_grad(z)
    pot = probe.bind(pdata)
    v, g = jax.jit(pot.value_and_grad)(z)
    jax.block_until_ready((v, g))
    np.testing.assert_allclose(np.asarray(v), np.asarray(v_ref), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), rtol=1e-6)
    assert probe.snapshot() >= 1


# ---------------------------------------------------------------------------
# timeline_report tool + the eight-schools coverage acceptance
# ---------------------------------------------------------------------------


def _timeline_report_main():
    import timeline_report

    return timeline_report.main


def test_timeline_report_json_on_synthetic(tmp_path, capsys):
    path = tmp_path / "t.jsonl"
    with open(path, "w") as f:
        for e in _synthetic_trace():
            f.write(json.dumps(e) + "\n")
    assert _timeline_report_main()([str(path), "--json"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["span_coverage_frac"] == pytest.approx(1.0)
    assert out["dispatch_count"] == 3
    assert _timeline_report_main()([str(path), "--spans"]) == 0
    assert "dispatch" in capsys.readouterr().out


def test_timeline_report_na_safe_on_pre_pr11_trace(tmp_path, capsys):
    """A PR-1-era trace shape (no overlap fields, no collect, no
    run_end dur): renders n/a where it can't attribute, never raises."""
    path = tmp_path / "old.jsonl"
    events = [
        _ev("run_start", 0.0, model="M"),
        _ev("sample_block", 1.0, dur_s=1.0, block=1),
        _ev("chain_health", 1.1, max_rhat=1.01),
    ]
    with open(path, "w") as f:
        for e in events:
            f.write(json.dumps(e) + "\n")
    assert _timeline_report_main()([str(path)]) == 0
    out = capsys.readouterr().out
    assert "dispatch" in out
    # and an event-free run renders the no-spans note
    path2 = tmp_path / "bare.jsonl"
    with open(path2, "w") as f:
        f.write(json.dumps(_ev("run_start", 0.0)) + "\n")
    assert _timeline_report_main()([str(path2)]) == 0
    assert "n/a" in capsys.readouterr().out


def test_timeline_report_missing_or_empty_file_fails_cleanly(tmp_path):
    # missing file: exit 1 with a message, not a traceback
    assert _timeline_report_main()([str(tmp_path / "absent.jsonl")]) == 1
    (tmp_path / "empty.jsonl").write_text("")
    assert _timeline_report_main()([str(tmp_path / "empty.jsonl")]) == 1


def test_eight_schools_trace_coverage_at_least_95pct(tmp_path, capsys):
    """The acceptance criterion: a fresh eight-schools trace attributes
    >=95% of the run wall to non-overlapping spans."""
    from stark_tpu.models.eight_schools import EightSchools, eight_schools_data
    from stark_tpu.runner import sample_until_converged

    path = str(tmp_path / "es.jsonl")
    with telemetry.use_trace(telemetry.RunTrace(path)) as tr:
        sample_until_converged(
            EightSchools(), eight_schools_data(),
            chains=2, block_size=50, max_blocks=4, min_blocks=2,
            rhat_target=10.0, ess_target=1.0, num_warmup=100,
            kernel="hmc", num_leapfrog=8, seed=0,
        )
        tr.close()
    events = telemetry.read_trace(path)
    s = timeline_summary(events)
    assert s["span_coverage_frac"] is not None
    assert s["span_coverage_frac"] >= 0.95, s
    assert s["compile_s"] is not None and s["compile_s"] > 0
    assert s["dispatch_count"] is not None and s["dispatch_count"] >= 3
    # spans are non-overlapping by construction — verify on real data
    spans = spans_from_events(events)["spans"]
    for a, b in zip(spans, spans[1:]):
        assert a["end"] <= b["start"] + 1e-9
    # and the report renders it
    assert _timeline_report_main()([path]) == 0
    out = capsys.readouterr().out
    assert "attributed" in out and "compile" in out
