"""Telemetry layer: schema round-trip, NullTrace no-op, traced runs.

The trace is a durable artifact other tooling parses (trace_report,
bench.py), so the contract under test is the SCHEMA: envelope fields on
every event, version rejection on mismatch, run ordinals, phase durations
that tile the run wall, and the canonical run_start -> sample_block ->
run_end ordering on a real eight_schools run.
"""

import io
import json
import os
import time
from contextlib import redirect_stdout

import jax.numpy as jnp
import numpy as np
import pytest

import stark_tpu
from stark_tpu import telemetry
from stark_tpu.model import Model, ParamSpec
from stark_tpu.telemetry import (
    EVENT_TYPES,
    NULL_TRACE,
    SCHEMA_VERSION,
    NullTrace,
    RunTrace,
    TraceError,
    read_trace,
    summarize_trace,
    use_trace,
    validate_event,
)


class StdNormal2(Model):
    def param_spec(self):
        return {"x": ParamSpec((2,))}

    def log_prior(self, p):
        return -0.5 * jnp.sum(p["x"] ** 2)

    def log_lik(self, p, data):
        return jnp.zeros(())


# ---------------------------------------------------------------------------
# schema round-trip
# ---------------------------------------------------------------------------


def test_emit_jsonl_roundtrip(tmp_path):
    p = tmp_path / "t.jsonl"
    with RunTrace(str(p)) as tr:
        tr.emit("run_start", model="M", kernel="nuts", chains=4)
        tr.emit("chain_health", mean_accept=0.8, num_divergent=3)
        tr.emit("run_end", dur_s=1.25)
    events = read_trace(str(p))
    assert [e["event"] for e in events] == [
        "run_start", "chain_health", "run_end"
    ]
    for e in events:
        assert e["schema"] == SCHEMA_VERSION
        assert e["run"] == 1
        assert isinstance(e["ts"], float) and isinstance(e["wall_s"], float)
    assert events[0]["model"] == "M" and events[0]["chains"] == 4
    assert events[1]["mean_accept"] == 0.8
    assert events[2]["dur_s"] == 1.25
    # every canonical event type is representable and survives round-trip
    assert {"run_start", "chain_health", "run_end"} <= EVENT_TYPES


def test_run_ordinals_and_tags(tmp_path):
    p = tmp_path / "t.jsonl"
    with RunTrace(str(p)) as tr:
        tr.emit("run_start")
        tr.emit("run_end", dur_s=0.1)
        shard = tr.tagged(shard=3, component="consensus")
        shard.emit("run_start")
        shard.emit("chain_health", step_size=0.5)
    events = read_trace(str(p))
    assert [e["run"] for e in events] == [1, 1, 2, 2]
    assert events[3]["shard"] == 3 and events[3]["component"] == "consensus"
    # tagged views share the file and run counter; tags never leak back
    assert "shard" not in events[0]


def test_validate_event_rejects_bad_envelope():
    good = {"schema": SCHEMA_VERSION, "event": "run_start", "ts": 1.0,
            "wall_s": 0.0, "run": 1}
    assert validate_event(dict(good)) == good
    with pytest.raises(TraceError):
        validate_event({k: v for k, v in good.items() if k != "ts"})
    with pytest.raises(TraceError):
        validate_event({**good, "schema": SCHEMA_VERSION + 1})
    # unknown event TYPES are forward-compatible, never an error
    validate_event({**good, "event": "a_future_event"})


def test_read_trace_strict_and_lenient(tmp_path):
    p = tmp_path / "t.jsonl"
    with RunTrace(str(p)) as tr:
        tr.emit("run_start")
    with open(p, "a") as f:
        f.write('{"torn line...')  # live file killed mid-write
    with pytest.raises(TraceError):
        read_trace(str(p))
    events = read_trace(str(p), strict=False)
    assert len(events) == 1 and events[0]["event"] == "run_start"


def test_phase_emits_duration_and_error_class(tmp_path):
    p = tmp_path / "t.jsonl"
    tr = RunTrace(str(p))
    with tr.phase("sample_block", block=1) as ph:
        time.sleep(0.01)
        ph.note(mean_accept=0.9)
    with pytest.raises(RuntimeError):
        with tr.phase("warmup_block"):
            raise RuntimeError("fault mid-phase")
    tr.close()
    blk, warm = read_trace(str(p))
    assert blk["event"] == "sample_block" and blk["dur_s"] >= 0.01
    assert blk["block"] == 1 and blk["mean_accept"] == 0.9
    # the failed phase still records its timing + the fault class: that is
    # the stalled-run evidence the layer exists for
    assert warm["event"] == "warmup_block" and warm["error"] == "RuntimeError"
    assert warm["dur_s"] >= 0.0


def test_heartbeat_is_rate_limited(tmp_path):
    p = tmp_path / "t.jsonl"
    with RunTrace(str(p)) as tr:
        for i in range(50):
            tr.heartbeat(min_interval_s=10.0, label="sample", step=i)
    events = read_trace(str(p))
    assert len(events) == 1  # 49 of 50 dropped by the limiter
    assert events[0]["event"] == "progress" and events[0]["step"] == 0


def test_emit_survives_closed_file(tmp_path):
    tr = RunTrace(str(tmp_path / "t.jsonl"))
    tr.emit("run_start")
    tr.close()
    # observability must never kill the run: emits after close are dropped
    assert tr.emit("run_end") is None
    with tr.phase("sample_block"):
        pass


# ---------------------------------------------------------------------------
# NullTrace: the no-op default
# ---------------------------------------------------------------------------


def test_nulltrace_is_default_and_noop(tmp_path):
    assert isinstance(telemetry.get_trace(), NullTrace)
    assert not NULL_TRACE.enabled
    assert NULL_TRACE.emit("run_start", anything=1) is None
    assert NULL_TRACE.tagged(shard=0) is NULL_TRACE
    ph = NULL_TRACE.phase("sample_block")
    with ph as inner:
        assert inner.note(x=1) is inner
    NULL_TRACE.heartbeat(label="x", step=0)
    NULL_TRACE.close()
    # it emits nothing, but the phase is still a span (always on)
    last = telemetry.span_log()[-1]
    assert last.name == "sample_block" and last.fields == {"x": 1}


# ---------------------------------------------------------------------------
# spans: one measurement at the site, always on, in memory
# ---------------------------------------------------------------------------


def _closed_since(mark):
    log = telemetry.span_log()
    return log[[r is mark for r in log].index(True) + 1:]


def _mark():
    with telemetry.span("mark"):
        pass
    return telemetry.span_log()[-1]


def test_span_records_start_end_parent_run():
    mark = _mark()
    with telemetry.span("outer", x=1) as outer:
        with telemetry.span("inner"):
            time.sleep(0.002)
        outer.note(y=2)
    inner, out = _closed_since(mark)
    assert (inner.name, out.name) == ("inner", "outer")  # oldest close first
    assert inner.run == out.run == 0  # outside any entry call
    assert out.parent is None and inner.parent == out.id
    assert out.start_ns <= inner.start_ns <= inner.end_ns <= out.end_ns
    assert inner.end_ns - inner.start_ns >= 2_000_000
    assert out.fields == {"x": 1, "y": 2}
    assert outer.seconds == (out.end_ns - out.start_ns) / 1e9


def test_span_ids_are_ordinals_within_their_run():
    """Two identical entry calls in one process leave identical logs but
    for the run ordinal and the times."""
    mark = _mark()

    def entry():
        with telemetry.run_span(resumed=False):
            with telemetry.span("a"):
                with telemetry.span("b"):
                    pass
            with telemetry.span("c"):
                pass

    entry()
    entry()
    recs = _closed_since(mark)
    first, second = recs[:4], recs[4:]
    shape = [(r.id, r.parent, r.name) for r in first]
    assert shape == [(3, 2, "b"), (2, 1, "a"), (4, 1, "c"), (1, None, "run")]
    assert shape == [(r.id, r.parent, r.name) for r in second]
    assert len({r.run for r in first}) == 1 and first[0].run >= 1
    assert second[0].run == first[0].run + 1
    # a span opened after the entry call returned is outside any run again
    with telemetry.span("after"):
        pass
    assert telemetry.span_log()[-1].run == 0


def test_span_exception_path_and_unclosed_children():
    mark = _mark()
    with pytest.raises(KeyError):
        with telemetry.span("dies"):
            telemetry.span("left_open").open()
            raise KeyError("x")
    left, dies = _closed_since(mark)
    assert dies.fields["error"] == "KeyError"
    # an ancestor that closes first closes what was left open inside it,
    # with the same error and end
    assert left.name == "left_open" and left.parent == dies.id
    assert left.fields["error"] == "KeyError" and left.end_ns == dies.end_ns
    # and the thread's innermost open span is restored
    with telemetry.span("next"):
        pass
    assert telemetry.span_log()[-1].parent is None


def test_span_log_is_bounded():
    n = telemetry.SPAN_LOG_SIZE
    for i in range(n + 10):
        with telemetry.span("fill", i=i):
            pass
    log = telemetry.span_log()
    assert len(log) == n
    assert log[-1].fields["i"] == n + 9 and log[0].fields["i"] == 10


def test_phase_is_a_span_and_the_event_is_unchanged(tmp_path):
    """`RunTrace.phase` = span + today's event (no new field); under
    `NullTrace` the span alone."""
    p = tmp_path / "t.jsonl"
    mark = _mark()
    with RunTrace(str(p)) as tr:
        with tr.phase("compile", stage="build") as ph:
            ph.note(k=1)
        with NULL_TRACE.phase("warmup_block", start=0, end=5):
            pass
    (ev,) = read_trace(str(p))
    assert set(ev) == set(telemetry.ENVELOPE_KEYS) | {"dur_s", "stage", "k"}
    real, null = _closed_since(mark)
    assert (real.name, real.fields) == ("compile", {"stage": "build", "k": 1})
    assert (null.name, null.fields) == ("warmup_block", {"start": 0, "end": 5})
    assert ev["dur_s"] == round((real.end_ns - real.start_ns) / 1e9, 4)


def test_compile_counters_land_on_the_span_that_compiled():
    import jax
    import jax.numpy as jnp

    mark = _mark()
    f = jax.jit(lambda x: jnp.sin(x) * 3.0 + 0.125)
    with telemetry.span("outer"):
        with telemetry.span("compiles"):
            jax.block_until_ready(f(jnp.ones(7)))
        with telemetry.span("reuses"):
            jax.block_until_ready(f(jnp.ones(7)))
    compiles, reuses, outer = _closed_since(mark)
    assert compiles.fields["compile_s"] > 0 and compiles.fields["lower_s"] > 0
    assert not {"compile_s", "lower_s"} & set(reuses.fields)
    assert "compile_s" not in outer.fields  # innermost open span only


def test_span_opens_a_profiler_annotation(monkeypatch):
    import jax  # noqa: F401 — the span layer hooks jax once it is imported

    seen = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            seen.append(("enter", self.name))

        def __exit__(self, *exc):
            seen.append(("exit", self.name))

    telemetry._hook_jax()
    monkeypatch.setattr(telemetry, "_ANNOTATE", Annotation)
    with telemetry.span("block.gate"):
        seen.append("body")
    assert seen == [("enter", "stark.block.gate"), "body",
                    ("exit", "stark.block.gate")]


def test_use_trace_scopes_and_restores(tmp_path):
    tr = RunTrace(str(tmp_path / "t.jsonl"))
    assert telemetry.get_trace() is NULL_TRACE
    with use_trace(tr) as got:
        assert got is tr and telemetry.get_trace() is tr
        with use_trace(None):
            assert telemetry.get_trace() is NULL_TRACE
        assert telemetry.get_trace() is tr
    assert telemetry.get_trace() is NULL_TRACE
    tr.close()


def test_nulltrace_runs_pay_nothing(tmp_path):
    """An untraced run must not write anywhere or change results: same
    seeds with and without an (enabled) trace give identical draws."""
    post_plain = stark_tpu.sample(
        StdNormal2(), chains=2, kernel="hmc", num_leapfrog=4,
        num_warmup=20, num_samples=20, seed=0,
    )
    p = tmp_path / "t.jsonl"
    with use_trace(RunTrace(str(p))) as tr:
        post_traced = stark_tpu.sample(
            StdNormal2(), chains=2, kernel="hmc", num_leapfrog=4,
            num_warmup=20, num_samples=20, seed=0,
        )
        tr.close()
    np.testing.assert_array_equal(post_plain.draws_flat, post_traced.draws_flat)
    assert len(read_trace(str(p))) >= 3  # and the traced run DID record


# ---------------------------------------------------------------------------
# traced runs: the canonical event stream
# ---------------------------------------------------------------------------


def _run_eight_schools(trace):
    from stark_tpu.backends import JaxBackend
    from stark_tpu.models import EightSchools, eight_schools_data

    backend = JaxBackend()  # shared so the traced pass hits the jit cache
    kwargs = dict(
        chains=2, kernel="nuts", max_tree_depth=5, num_warmup=50,
        num_samples=50, seed=0, backend=backend,
    )
    with use_trace(NULL_TRACE):
        stark_tpu.sample(EightSchools(), eight_schools_data(), **kwargs)
    with use_trace(trace):
        stark_tpu.sample(EightSchools(), eight_schools_data(), **kwargs)


@pytest.mark.slow  # >=8s on the 1-core host (pytest.ini policy, re-profiled 2026-08-03)
def test_eight_schools_trace_smoke(tmp_path):
    """The acceptance-shaped smoke: an eight_schools run under a trace
    produces run_start -> sample_block -> run_end IN ORDER, carries
    acceptance + divergence counts, and its phase durations tile the
    run wall (compile-cached pass, same contract as --trace on the CLI
    bench path)."""
    p = tmp_path / "t.jsonl"
    tr = RunTrace(str(p))
    _run_eight_schools(tr)
    tr.close()
    events = read_trace(str(p))
    names = [e["event"] for e in events]
    # ordered core: run_start before sample_block before run_end
    assert names.index("run_start") < names.index("sample_block") < names.index("run_end")
    health = [e for e in events if e["event"] == "chain_health"]
    assert health and "mean_accept" in health[-1]
    assert "num_divergent" in health[-1]

    s = summarize_trace(events)
    assert s["meta"]["model"] == "EightSchools"
    phase_sum = sum(v["total_s"] for v in s["phases"].values())
    assert s["wall_s"] > 0
    # summed phase durations within 10% of the run wall (the compile-
    # cached pass — cold passes hide XLA compile outside any dispatch)
    assert abs(phase_sum - s["wall_s"]) / s["wall_s"] < 0.10


def test_adaptive_runner_trace_events(tmp_path):
    """sample_until_converged emits the full vocabulary: compile,
    warmup_block(s), per-block sample_block + chain_health (R-hat/ESS/
    step size), checkpoint timings, run_end."""
    p = tmp_path / "t.jsonl"
    ckpt = tmp_path / "c.npz"
    tr = RunTrace(str(p))
    post = stark_tpu.sample_until_converged(
        StdNormal2(), chains=2, block_size=20, max_blocks=3, min_blocks=1,
        rhat_target=1.5, ess_target=5.0, num_warmup=60, kernel="nuts",
        max_tree_depth=4, seed=0, checkpoint_path=str(ckpt), trace=tr,
    )
    tr.close()
    events = read_trace(str(p))
    names = [e["event"] for e in events]
    assert names[0] == "run_start" and names[-1] == "run_end"
    for required in ("compile", "warmup_block", "sample_block",
                     "chain_health", "checkpoint"):
        assert required in names, f"missing {required}: {names}"
    # block-level health carries the live convergence signal
    block_health = [e for e in events
                    if e["event"] == "chain_health" and "max_rhat" in e]
    assert block_health
    h = block_health[-1]
    assert h["min_ess"] > 0 and h["step_size"] > 0
    assert h["num_divergent"] >= 0 and "mean_accept" in h
    end = events[-1]
    assert end["converged"] == post.converged
    assert end["blocks"] == len(post.history)


@pytest.mark.slow  # >=8s on the 1-core host (pytest.ini policy, re-profiled 2026-08-03)
def test_trace_report_renders_phase_and_health_table(tmp_path):
    """tools/trace_report.py renders a per-phase table including
    acceptance rate and divergence counts from a real trace."""
    import importlib.util

    p = tmp_path / "t.jsonl"
    tr = RunTrace(str(p))
    _run_eight_schools(tr)
    tr.close()

    spec = importlib.util.spec_from_file_location(
        "trace_report",
        os.path.join(os.path.dirname(__file__), "..", "tools",
                     "trace_report.py"),
    )
    trace_report = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(trace_report)

    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = trace_report.main([str(p)])
    out = buf.getvalue()
    assert rc == 0
    assert "phase" in out and "sample_block" in out
    assert "acceptance rate" in out and "divergences" in out

    # --json mode emits the machine-readable summary
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = trace_report.main([str(p), "--json"])
    assert rc == 0
    summary = json.loads(buf.getvalue())
    assert summary["phases"] and "mean_accept" in summary["health"]


def test_in_loop_heartbeat_progress_events(tmp_path):
    """progress_every wires a jit-safe jax.debug.callback heartbeat into
    the compiled sampling scan; events land in the trace from the
    callback thread."""
    p = tmp_path / "t.jsonl"
    with use_trace(RunTrace(str(p))) as tr:
        stark_tpu.sample(
            StdNormal2(), chains=2, kernel="hmc", num_leapfrog=4,
            num_warmup=10, num_samples=60, seed=0, progress_every=25,
        )
        import jax

        jax.effects_barrier()
        tr.close()
    events = read_trace(str(p), strict=False)
    progress = [e for e in events if e["event"] == "progress"]
    assert progress, "no progress heartbeat reached the trace"
    assert progress[0]["label"] == "sample"
    assert 0.0 <= progress[0]["accept"] <= 1.0


def test_summarize_trace_counts_restarts(tmp_path):
    p = tmp_path / "t.jsonl"
    with RunTrace(str(p)) as tr:
        tr.emit("run_start")
        tr.emit("chain_health", status="restart", attempt=1,
                error="ChainHealthError: boom")
        tr.emit("chain_health", status="restart", attempt=2,
                error="XlaRuntimeError: device")
        tr.emit("run_end", dur_s=2.0)
    s = summarize_trace(read_trace(str(p)))
    assert s["restarts"] == 2


def test_restarts_counted_across_runs(tmp_path):
    """The supervisor stamps a restart with the FAILED attempt's run
    ordinal; the summary of the (later, successful) run must still count
    it — restart totals are a whole-trace property."""
    p = tmp_path / "t.jsonl"
    with RunTrace(str(p)) as tr:
        tr.emit("run_start")  # attempt 1 (faults)
        tr.emit("chain_health", status="restart", attempt=1,
                error="ChainHealthError: boom")
        tr.emit("run_start")  # attempt 2 (succeeds)
        tr.emit("run_end", dur_s=1.0)
    s = summarize_trace(read_trace(str(p)))
    assert s["run"] == 2 and s["restarts"] == 1


def test_restarts_not_absorbed_from_earlier_sessions(tmp_path):
    """A clean run appended after an earlier session's restarts must not
    inherit them: the chain-walk stops at a predecessor run with no
    restart event (the earlier session's successful final run)."""
    p = tmp_path / "t.jsonl"
    with RunTrace(str(p)) as tr:  # session 1: one restart, then success
        tr.emit("run_start")
        tr.emit("chain_health", status="restart", attempt=1, error="boom")
        tr.emit("run_start")
        tr.emit("run_end", dur_s=1.0)
    with RunTrace(str(p)) as tr:  # session 2: clean
        tr.emit("run_start")
        tr.emit("run_end", dur_s=2.0)
    events = read_trace(str(p))
    assert summarize_trace(events)["restarts"] == 0  # run 3, clean story
    assert summarize_trace(events, run=2)["restarts"] == 1


def test_chees_progress_heartbeat(tmp_path):
    """progress_every reaches the ChEES ensemble sampling scan too (the
    flagship path)."""
    from stark_tpu.models import Logistic, synth_logistic_data
    import jax

    data, _ = synth_logistic_data(jax.random.PRNGKey(0), 200, 3)
    p = tmp_path / "t.jsonl"
    with use_trace(RunTrace(str(p))) as tr:
        stark_tpu.sample(
            Logistic(num_features=3), data, chains=4, kernel="chees",
            num_warmup=20, num_samples=60, init_step_size=0.1,
            progress_every=25, seed=0,
        )
        jax.effects_barrier()
        tr.close()
    progress = [e for e in read_trace(str(p), strict=False)
                if e["event"] == "progress"]
    assert progress and progress[0]["label"] == "chees_sample"


def test_reopened_trace_continues_run_ordinals(tmp_path):
    """Appending a second session to the same --trace PATH must continue
    the run numbering, never collide with the first session's runs."""
    p = tmp_path / "t.jsonl"
    with RunTrace(str(p)) as tr:
        tr.emit("run_start")
        tr.emit("run_end", dur_s=0.5)
    with RunTrace(str(p)) as tr:  # new process/session, same file
        tr.emit("run_start")
        tr.emit("run_end", dur_s=0.7)
    events = read_trace(str(p))
    assert [e["run"] for e in events] == [1, 1, 2, 2]
    assert summarize_trace(events)["wall_s"] == 0.7  # last run, unmerged


# ---------------------------------------------------------------------------
# event listeners + in-memory bus (the live-exporter fan-out)
# ---------------------------------------------------------------------------


def test_event_listeners_receive_every_record(tmp_path):
    p = tmp_path / "t.jsonl"
    seen = []
    telemetry.add_event_listener(seen.append)
    try:
        with RunTrace(str(p)) as tr:
            tr.emit("run_start", model="M")
            with tr.phase("sample_block", block=1):
                pass
    finally:
        telemetry.remove_event_listener(seen.append)
    assert [e["event"] for e in seen] == ["run_start", "sample_block"]
    # listeners see the SAME record that lands in the file
    events = read_trace(str(p))
    assert seen[0] == events[0] and seen[1] == events[1]
    # removed: no further delivery
    with RunTrace(str(p)) as tr:
        tr.emit("run_end", dur_s=0.1)
    assert len(seen) == 2


def test_in_memory_trace_feeds_listeners_writes_nothing(tmp_path):
    seen = []
    telemetry.add_event_listener(seen.append)
    try:
        tr = RunTrace(None)  # the status daemon's untraced mode
        assert tr.path is None and tr.enabled
        tr.emit("run_start", model="M")
        tr.emit("run_end", dur_s=0.2)
    finally:
        telemetry.remove_event_listener(seen.append)
    assert [e["event"] for e in seen] == ["run_start", "run_end"]
    assert seen[0]["run"] == 1 and seen[0]["schema"] == SCHEMA_VERSION
    assert list(tmp_path.iterdir()) == []  # nothing hit the filesystem


def test_in_memory_trace_without_listeners_is_noop():
    tr = RunTrace(None)
    assert tr.emit("run_start") is None  # nothing to deliver to


def test_listener_exception_never_reaches_the_run(tmp_path):
    p = tmp_path / "t.jsonl"

    def bad(rec):
        raise RuntimeError("listener bug")

    telemetry.add_event_listener(bad)
    try:
        with RunTrace(str(p)) as tr:
            assert tr.emit("run_start") is not None
    finally:
        telemetry.remove_event_listener(bad)
    assert read_trace(str(p))[0]["event"] == "run_start"


def test_no_listener_no_record_overhead(tmp_path):
    """The zero-cost contract: without listeners, an emit on a file-less
    trace builds nothing, and NullTrace still does nothing at all."""
    assert not telemetry._EVENT_LISTENERS
    assert RunTrace(None).emit("sample_block") is None
    assert NULL_TRACE.emit("sample_block") is None


# ---------------------------------------------------------------------------
# provenance stamping (satellite: attributable ledger rows / run_starts)
# ---------------------------------------------------------------------------


def test_provenance_fields_and_caching():
    prov = telemetry.provenance()
    assert set(prov) == {"git_sha", "jax_version", "jaxlib_version"}
    # best-effort: values may be None, but in this repo git + jax exist
    assert prov["jax_version"]
    assert prov["git_sha"]
    # cached: the second call is the same content, not a new subprocess
    assert telemetry.provenance() == prov
    # callers mutate their copy safely
    prov["git_sha"] = "clobbered"
    assert telemetry.provenance()["git_sha"] != "clobbered"


def test_run_start_carries_provenance_and_device_kind(tmp_path):
    p = tmp_path / "t.jsonl"
    with use_trace(RunTrace(str(p))):
        stark_tpu.sample(
            StdNormal2(), chains=2, kernel="hmc", num_leapfrog=4,
            num_warmup=5, num_samples=5, seed=0,
        )
    start = read_trace(str(p), strict=False)[0]
    assert start["event"] == "run_start"
    for k in ("git_sha", "jax_version", "jaxlib_version", "device_kind"):
        assert k in start, k
    # summarize_trace surfaces them through meta (the ledger reads this)
    meta = summarize_trace(read_trace(str(p), strict=False))["meta"]
    assert "git_sha" in meta and "jax_version" in meta


# ---------------------------------------------------------------------------
# PR-1-era traces degrade gracefully in the report tool (satellite)
# ---------------------------------------------------------------------------


def _pr1_era_trace(path):
    """A trace as PR 1 wrote them: no overlap/diag/block_len/provenance
    fields anywhere."""
    events = [
        {"event": "run_start", "entry": "sample", "model": "M",
         "kernel": "nuts", "chains": 4, "platform": "cpu",
         "device_count": 1},
        {"event": "compile", "dur_s": 0.5, "stage": "setup"},
        {"event": "warmup_block", "dur_s": 0.3, "start": 0, "end": 50},
        {"event": "sample_block", "dur_s": 0.4, "t_dispatch_s": 0.3,
         "t_diag_s": 0.1},
        {"event": "chain_health", "max_rhat": 1.01, "min_ess": 200.0,
         "mean_accept": 0.8, "num_divergent": 0},
        {"event": "checkpoint", "dur_s": 0.05},
        {"event": "run_end", "dur_s": 1.0, "num_divergent": 0},
    ]
    with open(path, "w") as f:
        for i, e in enumerate(events):
            f.write(json.dumps({
                "schema": SCHEMA_VERSION, "ts": 1.0 + i,
                "wall_s": float(i), "run": 1, **e,
            }) + "\n")


def test_trace_report_degrades_on_pr1_era_traces(tmp_path):
    """Traces that predate the overlap/diag fields must render (no
    KeyError), simply omitting the newer tables; --json emits the
    summarize_trace dict with empty overlap/diag sections."""
    import importlib.util

    p = tmp_path / "old.jsonl"
    _pr1_era_trace(p)

    spec = importlib.util.spec_from_file_location(
        "trace_report",
        os.path.join(os.path.dirname(__file__), "..", "tools",
                     "trace_report.py"),
    )
    trace_report = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(trace_report)

    buf = io.StringIO()
    with redirect_stdout(buf):
        assert trace_report.main([str(p)]) == 0
    out = buf.getvalue()
    assert "sample_block" in out and "max R-hat" in out
    assert "block overlap" not in out  # absent, not crashed

    buf = io.StringIO()
    with redirect_stdout(buf):
        assert trace_report.main([str(p), "--json"]) == 0
    summary = json.loads(buf.getvalue())
    assert summary["overlap"] == {} and summary["diag"] == {}
    assert summary["health"]["max_rhat"] == 1.01
    # the ledger ingests the same dict without choking on the gaps
    from stark_tpu import ledger

    row = ledger.make_row(source="test", config="old", trace_summary=summary)
    assert row["device_idle_frac"] is None
    assert row["ess_per_sec"] == pytest.approx(200.0)


def test_trace_report_renders_na_for_missing_values():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "trace_report",
        os.path.join(os.path.dirname(__file__), "..", "tools",
                     "trace_report.py"),
    )
    trace_report = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(trace_report)
    assert trace_report._fmt(None) == "n/a"


# ---------------------------------------------------------------------------
# PR 15 satellite bugfix: health.num_divergent is cumulative-with-reset
# across supervised attempts, not the latest event's value
# ---------------------------------------------------------------------------


def _attempt_events(run, divs, restart_after=False, resumed=False):
    """One supervised attempt's skeleton: run_start (stamped
    ``resuming`` exactly as the runner does — bool(resume_from)), a
    per-block chain_health divergence trail, optionally the failed
    attempt's restart record (stamped with THIS run's ordinal, as
    supervise does)."""
    evs = [{"event": "run_start", "model": "M", "kernel": "nuts",
            "resuming": bool(resumed)}]
    if resumed:
        # a checkpoint-resumed attempt re-emits warmup_done without a
        # fresh warmup; its block counters CONTINUE the restored total
        evs.append({"event": "chain_health", "status": "warmup_done",
                    "num_divergent": 7})
    for d in divs:
        evs.append({"event": "chain_health", "mean_accept": 0.8,
                    "num_divergent": d})
    if restart_after:
        evs.append({"event": "chain_health", "status": "restart",
                    "fault": "transient", "attempt": run})
    else:
        evs.append({"event": "run_end", "dur_s": 1.0})
    return [
        {"schema": SCHEMA_VERSION, "ts": 0.0, "wall_s": 0.0, "run": run,
         **e}
        for e in evs
    ]


def test_summarize_num_divergent_accumulates_across_cold_restarts():
    """A cold retry restarts its cumulative counter from zero: the
    failed attempt's final count must be banked, not discarded (the
    old latest-event semantics reported 2 here) — including when the
    retry happens to reach a HIGHER count than the failed attempt (no
    value decrease is ever observed; the run_start boundary is the
    reset signal, not the values)."""
    events = (
        _attempt_events(1, [2, 3], restart_after=True)
        + _attempt_events(2, [1, 2])
    )
    s = summarize_trace(events)
    assert s["run"] == 2 and s["restarts"] == 1
    assert s["health"]["num_divergent"] == 5  # 3 banked + 2 current
    # monotone-looking cold retry: attempt 1 ends at 5, attempt 2
    # reaches 7 with no observed decrease — still 5 + 7
    events = (
        _attempt_events(1, [5], restart_after=True)
        + _attempt_events(2, [6, 7])
    )
    assert summarize_trace(events)["health"]["num_divergent"] == 12


def test_summarize_num_divergent_resumed_attempt_not_double_counted():
    """A checkpoint-resumed retry CONTINUES the restored counter (no
    decrease) — cumulative-with-reset must not double count it, and the
    warmup_done record's warmup divergences stay out of the number."""
    events = (
        _attempt_events(1, [2, 3], restart_after=True)
        + _attempt_events(2, [3, 4], resumed=True)
    )
    s = summarize_trace(events)
    assert s["restarts"] == 1
    assert s["health"]["num_divergent"] == 4  # monotone across resume


def test_summarize_num_divergent_shard_partials_excluded():
    """Consensus-style per-shard chain_health records carry per-SHARD
    partial counts: they must not be folded as if they were run totals
    — run_end's total is the authoritative value."""
    evs = [
        {"event": "run_start", "model": "M", "kernel": "nuts"},
        {"event": "chain_health", "shard": 0, "num_divergent": 5},
        {"event": "chain_health", "shard": 1, "num_divergent": 2},
        {"event": "chain_health", "shard": 2, "num_divergent": 7},
        {"event": "chain_health", "shard": 3, "num_divergent": 1},
        {"event": "run_end", "dur_s": 1.0, "num_divergent": 15},
    ]
    events = [
        {"schema": SCHEMA_VERSION, "ts": 0.0, "wall_s": 0.0, "run": 1, **e}
        for e in evs
    ]
    assert summarize_trace(events)["health"]["num_divergent"] == 15


def test_summarize_num_divergent_ignores_unrelated_earlier_runs():
    """Two independent runs appended to one file (bench legs): the
    selected run's count never absorbs the other's."""
    events = _attempt_events(1, [9]) + _attempt_events(2, [1])
    s = summarize_trace(events)
    assert s["health"]["num_divergent"] == 1
    assert summarize_trace(events, run=1)["health"]["num_divergent"] == 9


# ---------------------------------------------------------------------------
# summarize_trace over heterogeneous inputs: rotated sequences, mixed
# schema versions, torn final lines (PR 20 satellite)
# ---------------------------------------------------------------------------


def test_summarize_trace_over_rotated_sequence(tmp_path, monkeypatch):
    """A rotated trace read back through `rotated_paths` + `iter_traces`
    summarizes as ONE story: every block lands in the phase totals, the
    `trace_rotated` markers count as ordinary auxiliary events, and the
    run_end wall survives in whichever part it rotated into."""
    monkeypatch.setenv("STARK_TRACE_MAX_MB", "0.001")
    p = str(tmp_path / "t.jsonl")
    with RunTrace(p) as tr:
        tr.emit("run_start")
        for b in range(40):
            tr.emit("sample_block", block=b, dur_s=0.01, note="x" * 64)
        tr.emit("run_end", dur_s=1.5)
    parts = telemetry.rotated_paths(p)
    assert len(parts) > 1, "rotation never triggered"
    events = list(telemetry.iter_traces(parts))
    s = summarize_trace(events)
    assert s["phases"]["sample_block"]["count"] == 40
    assert s["wall_s"] == 1.5
    assert s["events"] == len(events)
    # each fresh part opens with its rotation marker; the summary treats
    # them as known auxiliaries (not "other"/unknown)
    rotated = [e for e in events if e["event"] == "trace_rotated"]
    assert len(rotated) == len(parts) - 1
    assert s["other"] == {}


def test_summarize_trace_mixed_schema_versions():
    """One file holding records from different writer generations — a
    PR-1-era record with no envelope at all, a current-schema record,
    and a future-schema record with unknown fields — summarizes without
    raising; unknown event families degrade into ``other``, never
    silently vanish."""
    events = [
        # current writer
        {"schema": SCHEMA_VERSION, "ts": 1.0, "wall_s": 0.0, "run": 0,
         "event": "run_start", "entry": "sample"},
        {"schema": SCHEMA_VERSION, "ts": 2.0, "wall_s": 0.1, "run": 0,
         "event": "sample_block", "block": 0, "dur_s": 0.1},
        # pre-schema (PR-1-era): no schema/run/ts envelope
        {"event": "sample_block", "block": 1, "dur_s": 0.2},
        # future writer: higher schema, unknown event + fields
        {"schema": SCHEMA_VERSION + 1, "ts": 3.0, "wall_s": 0.2, "run": 0,
         "event": "quantum_block", "qubits": 8},
        {"schema": SCHEMA_VERSION, "ts": 4.0, "wall_s": 0.3, "run": 0,
         "event": "run_end", "dur_s": 0.9},
    ]
    s = summarize_trace(events)
    assert s["phases"]["sample_block"]["count"] == 2
    assert s["phases"]["sample_block"]["total_s"] == pytest.approx(0.3)
    assert s["wall_s"] == 0.9
    assert s["other"] == {"quantum_block": 1}


def test_summarize_trace_torn_final_line(tmp_path):
    """A crash mid-append leaves a torn last line; the tolerant reader
    (strict=False) skips it and the summary still covers everything
    before the tear — the strict reader refuses, loudly."""
    p = str(tmp_path / "t.jsonl")
    with RunTrace(p) as tr:
        tr.emit("run_start")
        tr.emit("sample_block", block=0, dur_s=0.4)
    with open(p, "a") as f:
        f.write('{"schema": 1, "event": "run_end", "dur_s"')  # torn
    with pytest.raises(TraceError):
        read_trace(p)
    events = read_trace(p, strict=False)
    s = summarize_trace(events)
    assert s["phases"]["sample_block"]["count"] == 1
    # the run_end never landed: the summary falls back to the event span
    assert s["wall_s"] == pytest.approx(
        events[-1]["wall_s"] - events[0]["wall_s"])
    assert s["events"] == 2


def test_summarize_trace_torn_line_inside_rotated_part(tmp_path,
                                                       monkeypatch):
    """The tear can sit in a ROTATED part (the file that was live at
    crash time is not always the live file now): `iter_traces` with
    strict=False chains past it and later parts still contribute."""
    monkeypatch.setenv("STARK_TRACE_MAX_MB", "0.001")
    p = str(tmp_path / "t.jsonl")
    with RunTrace(p) as tr:
        tr.emit("run_start")
        for b in range(40):
            tr.emit("sample_block", block=b, dur_s=0.01, note="x" * 64)
        tr.emit("run_end", dur_s=1.5)
    parts = telemetry.rotated_paths(p)
    assert len(parts) > 2
    with open(parts[1], "a") as f:
        f.write('{"event": "sample_bl')  # tear the middle part
    events = list(telemetry.iter_traces(parts, strict=False))
    s = summarize_trace(events)
    assert s["phases"]["sample_block"]["count"] == 40
    assert s["wall_s"] == 1.5
