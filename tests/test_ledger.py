"""Perf regression ledger: row schema, provenance, and the median gate.

The acceptance behavior under test: a synthetic 2x ess_per_sec drop
appended to a healthy ledger makes ``check`` fail (non-zero from the
CLI), a noisy-but-honest row inside the tolerance band passes, and a
fresh ledger (insufficient history) never fails CI.
"""

import io
import json
import os
from contextlib import redirect_stdout

import pytest

from stark_tpu import ledger, telemetry


def _bench(eps, wall=100.0, **extra):
    return {"value": eps, "wall_s": wall, "max_rhat": 1.005,
            "converged": True, **extra}


def _fill(path, rates, config="c1"):
    for eps in rates:
        ledger.append_row(
            ledger.make_row(source="test", config=config, bench=_bench(eps)),
            str(path),
        )


# ---------------------------------------------------------------------------
# rows
# ---------------------------------------------------------------------------


def test_row_carries_schema_provenance_and_metrics(tmp_path):
    p = tmp_path / "ledger.jsonl"
    row = ledger.make_row(
        source="test", config="c1",
        bench=_bench(10.0, device_idle_frac=0.05, overshoot_draws=46,
                     diag_bytes_to_host=4900, platform="cpu"),
        note="hello",
    )
    ledger.append_row(row, str(p))
    (read,) = ledger.read_rows(str(p))
    assert read["schema"] == ledger.LEDGER_SCHEMA
    assert read["source"] == "test" and read["config"] == "c1"
    assert read["note"] == "hello"
    # provenance: keys always present (values best-effort None)
    for k in ("git_sha", "jax_version", "jaxlib_version", "platform"):
        assert k in read
    assert read["ess_per_sec"] == 10.0 and read["wall_s"] == 100.0
    assert read["device_idle_frac"] == 0.05
    assert read["overshoot_draws"] == 46
    assert read["diag_bytes_to_host"] == 4900
    assert read["converged"] is True


def test_non_finite_bench_values_become_null():
    row = ledger.make_row(
        source="test", config="c1",
        bench={"value": float("nan"), "wall_s": float("inf"),
               "max_rhat": None, "converged": False},
    )
    assert row["ess_per_sec"] is None
    assert row["wall_s"] is None
    assert row["converged"] is False


def test_row_from_trace_summary_reuses_summarize_trace(tmp_path):
    """The trace ingest path consumes the summarize_trace dict — the same
    machine contract trace_report --json emits."""
    p = tmp_path / "t.jsonl"
    with telemetry.RunTrace(str(p)) as tr:
        tr.emit("run_start", model="M", chains=2)
        tr.emit("sample_block", block=1, dur_s=2.0, t_wait_s=1.0,
                t_host_hidden_s=0.5, device_idle_s=0.2,
                diag_bytes_to_host=4900)
        tr.emit("chain_health", block=1, max_rhat=1.01, min_ess=100.0)
        tr.emit("run_end", dur_s=10.0, converged=True, overshoot_draws=12)
    summary = telemetry.summarize_trace(telemetry.read_trace(str(p)))
    row = ledger.make_row(source="test", config="t", trace_summary=summary)
    assert row["wall_s"] == 10.0
    assert row["ess_per_sec"] == pytest.approx(10.0)  # min_ess / wall
    assert row["max_rhat"] == 1.01
    assert row["overshoot_draws"] == 12
    assert row["diag_bytes_to_host"] == 4900
    assert row["device_idle_frac"] is not None


def test_bench_wins_over_trace_summary():
    summary = {"wall_s": 50.0, "health": {"min_ess": 100.0},
               "overlap": {}, "diag": {}}
    row = ledger.make_row(source="test", config="c",
                          bench=_bench(7.0, wall=42.0),
                          trace_summary=summary)
    assert row["ess_per_sec"] == 7.0 and row["wall_s"] == 42.0


def test_read_rows_skips_torn_and_foreign_lines(tmp_path):
    p = tmp_path / "ledger.jsonl"
    _fill(p, [10.0])
    with open(p, "a") as f:
        f.write("{torn...\n")
        f.write(json.dumps({"schema": 99, "other": "writer"}) + "\n")
    assert len(ledger.read_rows(str(p))) == 1


def test_default_path_env_override_and_disable(monkeypatch):
    monkeypatch.setenv(ledger.LEDGER_ENV, "/tmp/elsewhere.jsonl")
    assert ledger.default_ledger_path() == "/tmp/elsewhere.jsonl"
    monkeypatch.setenv(ledger.LEDGER_ENV, "0")
    assert ledger.default_ledger_path() is None
    with pytest.raises(ValueError):
        ledger.append_row({}, None)
    monkeypatch.delenv(ledger.LEDGER_ENV)
    p = ledger.default_ledger_path()
    assert p is not None and p.endswith(
        os.path.join("bench_artifacts", "ledger.jsonl")
    )


# ---------------------------------------------------------------------------
# the gate
# ---------------------------------------------------------------------------


def test_check_passes_within_tolerance(tmp_path):
    p = tmp_path / "ledger.jsonl"
    _fill(p, [10.0, 11.0, 10.5, 9.8])  # ±25% band around median
    ok, report = ledger.check_rows(ledger.read_rows(str(p)))
    assert ok, report


def test_check_fails_on_2x_ess_drop(tmp_path):
    p = tmp_path / "ledger.jsonl"
    _fill(p, [10.0, 11.0, 10.5, 5.2])  # 2x drop on the newest row
    ok, report = ledger.check_rows(ledger.read_rows(str(p)))
    assert not ok
    assert any("REGRESSION" in line and "ess_per_sec" in line
               for line in report)


def test_check_insufficient_history_is_ok(tmp_path):
    p = tmp_path / "ledger.jsonl"
    _fill(p, [10.0, 1.0])  # terrible newest row, but only 1 predecessor
    ok, report = ledger.check_rows(ledger.read_rows(str(p)))
    assert ok and "insufficient history" in report[0]
    assert ledger.check_rows([])[0]


def test_check_isolates_configs(tmp_path):
    """A row gates only against its own config peers — the fallback CPU
    capture must never be compared to an on-chip run."""
    p = tmp_path / "ledger.jsonl"
    _fill(p, [100.0, 101.0, 99.0], config="tpu")
    _fill(p, [10.0, 10.2, 9.9], config="cpu-fallback")
    ok, report = ledger.check_rows(ledger.read_rows(str(p)))
    assert ok, report  # newest (cpu 9.9) vs cpu median, not tpu's 100


def test_check_window_bounds_history(tmp_path):
    p = tmp_path / "ledger.jsonl"
    # ancient glory (100), recent steady-state (10): window=3 must gate
    # against the recent median only
    _fill(p, [100.0, 100.0, 100.0, 10.0, 10.0, 10.0, 9.5])
    ok, report = ledger.check_rows(ledger.read_rows(str(p)), window=3)
    assert ok, report


def test_check_strict_gates_efficiency_metrics(tmp_path):
    p = tmp_path / "ledger.jsonl"
    for wall in (100.0, 100.0, 100.0):
        ledger.append_row(
            ledger.make_row(source="t", config="c",
                            bench=_bench(10.0, wall=wall)),
            str(p),
        )
    ledger.append_row(
        ledger.make_row(source="t", config="c",
                        bench=_bench(10.0, wall=200.0)),  # 2x wall
        str(p),
    )
    rows = ledger.read_rows(str(p))
    ok, _ = ledger.check_rows(rows)  # wall_s not gated by default
    assert ok
    ok, report = ledger.check_rows(rows, strict=True)
    assert not ok
    assert any("wall_s" in line and "REGRESSION" in line for line in report)


def test_check_missing_metric_is_na_not_failure(tmp_path):
    p = tmp_path / "ledger.jsonl"
    for _ in range(3):
        ledger.append_row(
            ledger.make_row(source="t", config="c",
                            bench={"converged": True}),  # no rate at all
            str(p),
        )
    ok, report = ledger.check_rows(ledger.read_rows(str(p)))
    assert ok
    assert any("ess_per_sec: n/a" in line for line in report)


# ---------------------------------------------------------------------------
# CLI (tools/perf_ledger.py)
# ---------------------------------------------------------------------------


@pytest.fixture
def perf_ledger_cli():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "perf_ledger",
        os.path.join(os.path.dirname(__file__), "..", "tools",
                     "perf_ledger.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_cli_ingest_then_check_gate(tmp_path, perf_ledger_cli):
    led = str(tmp_path / "ledger.jsonl")
    art = tmp_path / "bench.json"
    for eps in (10.0, 10.4, 9.9):
        art.write_text(json.dumps(_bench(eps)))
        rc = perf_ledger_cli.main([
            "--ledger", led, "ingest", "--bench-json", str(art),
            "--config", "c1",
        ])
        assert rc == 0
    with redirect_stdout(io.StringIO()):
        assert perf_ledger_cli.main(["--ledger", led, "check"]) == 0
    # the synthetic 2x drop: check must exit non-zero
    art.write_text(json.dumps(_bench(5.0)))
    perf_ledger_cli.main([
        "--ledger", led, "ingest", "--bench-json", str(art),
        "--config", "c1",
    ])
    buf = io.StringIO()
    with redirect_stdout(buf):
        assert perf_ledger_cli.main(["--ledger", led, "check"]) == 1
    assert "REGRESSION" in buf.getvalue()


def test_cli_ingest_accepts_bench_stdout_tail(tmp_path, perf_ledger_cli):
    """bench.py's whole stdout works as --bench-json input: the LAST
    parseable JSON line (the authoritative artifact) wins."""
    led = str(tmp_path / "ledger.jsonl")
    art = tmp_path / "stdout.txt"
    art.write_text(
        json.dumps({"value": 1.0, "partial": True}) + "\n"
        + "[bench] noise line\n"
        + json.dumps(_bench(12.5)) + "\n"
    )
    rc = perf_ledger_cli.main([
        "--ledger", led, "ingest", "--bench-json", str(art),
        "--config", "c1",
    ])
    assert rc == 0
    (row,) = ledger.read_rows(led)
    assert row["ess_per_sec"] == 12.5


def test_cli_ingest_from_trace(tmp_path, perf_ledger_cli):
    led = str(tmp_path / "ledger.jsonl")
    tp = tmp_path / "t.jsonl"
    with telemetry.RunTrace(str(tp)) as tr:
        tr.emit("run_start", model="M", chains=2)
        tr.emit("chain_health", min_ess=50.0, max_rhat=1.0)
        tr.emit("run_end", dur_s=5.0)
    rc = perf_ledger_cli.main([
        "--ledger", led, "ingest", "--trace", str(tp), "--config", "smoke",
    ])
    assert rc == 0
    (row,) = ledger.read_rows(led)
    assert row["ess_per_sec"] == pytest.approx(10.0)


def test_zero_ess_becomes_zero_rate_not_na(tmp_path):
    """A measured-zero ESS (stuck chains) is the exact collapse the gate
    exists to catch: it must land as rate 0.0, never a skipped n/a."""
    summary = {"wall_s": 10.0, "health": {"min_ess": 0.0},
               "overlap": {}, "diag": {}}
    row = ledger.make_row(source="t", config="c1", trace_summary=summary)
    assert row["ess_per_sec"] == 0.0
    p = tmp_path / "ledger.jsonl"
    _fill(p, [10.0, 10.0, 10.0])
    ledger.append_row(row, str(p))
    ok, report = ledger.check_rows(ledger.read_rows(str(p)))
    assert not ok, report


def test_interleaved_config_cannot_mask_a_regression(tmp_path):
    """An append for an unrelated config after a regressed run must not
    unmask it: --config pins the gate, --all-configs sweeps them."""
    p = tmp_path / "ledger.jsonl"
    _fill(p, [10.0, 10.0, 10.0, 5.0], config="flagship")  # 2x drop
    _fill(p, [1.0], config="smoke")  # interleaved writer, newest overall
    rows = ledger.read_rows(str(p))
    # default (global newest) sees the smoke row: insufficient history
    ok, _ = ledger.check_rows(rows)
    assert ok
    ok, report = ledger.check_rows(rows, config="flagship")
    assert not ok
    assert any("REGRESSION" in line for line in report)
    ok, report = ledger.check_rows(rows, all_configs=True)
    assert not ok
    assert any("flagship" in line for line in report)
    assert any("smoke" in line for line in report)


def test_row_shape_is_uniform_across_sources():
    """Bench- and trace-sourced rows carry the same metric keys (the
    documented LEDGER_SCHEMA), just with None where a source lacks the
    measurement."""
    summary = {"wall_s": 10.0, "health": {"min_ess": 50.0},
               "overlap": {}, "diag": {}, "restarts": 2}
    from_trace = ledger.make_row(source="t", config="c",
                                 trace_summary=summary)
    from_bench = ledger.make_row(source="t", config="c", bench=_bench(5.0))
    metric_keys = {"ess_per_sec", "wall_s", "max_rhat", "converged",
                   "restarts", "device_idle_frac", "overshoot_draws",
                   "diag_bytes_to_host"}
    assert metric_keys <= set(from_trace) and metric_keys <= set(from_bench)
    assert from_trace["restarts"] == 2
    assert from_bench["restarts"] is None


# ---------------------------------------------------------------------------
# (config, profile) series — autotuned-profile provenance (PR 19)
# ---------------------------------------------------------------------------


def test_row_carries_profile_provenance():
    """Every row carries the hardware fingerprint and a ``profile``
    column — honest-null when no profile steers the process, and the
    bench dict's explicit value (the autotuner's own row) wins over the
    ambient active profile."""
    row = ledger.make_row(source="t", config="c", bench=_bench(1.0))
    assert row["profile"] is None
    assert isinstance(row["fingerprint"], str) and row["fingerprint"]
    row = ledger.make_row(
        source="t", config="c", bench={**_bench(1.0), "profile": "hw#beef"}
    )
    assert row["profile"] == "hw#beef"


def test_check_isolates_profile_series(tmp_path):
    """Switching the autotuned profile starts a FRESH series: a knob
    flip must not masquerade as (or mask) a perf regression.  Same
    config + same profile still gates."""
    p = tmp_path / "ledger.jsonl"
    for eps in (100.0,) * 5:
        ledger.append_row(
            ledger.make_row(source="t", config="c",
                            bench={**_bench(eps), "profile": "hw#aaaa"}),
            str(p),
        )
    # different profile, half the rate: a new series, not a regression
    ledger.append_row(
        ledger.make_row(source="t", config="c",
                        bench={**_bench(50.0), "profile": "hw#bbbb"}),
        str(p),
    )
    ok, report = ledger.check_rows(ledger.read_rows(str(p)))
    assert ok, report
    assert any("hw#bbbb" in line for line in report)
    # same profile, half the rate: the gate still fires
    ledger.append_row(
        ledger.make_row(source="t", config="c",
                        bench={**_bench(50.0), "profile": "hw#aaaa"}),
        str(p),
    )
    ok, report = ledger.check_rows(ledger.read_rows(str(p)))
    assert not ok, report
    assert any("hw#aaaa" in line for line in report)


def test_check_legacy_rows_are_the_null_profile_series(tmp_path):
    """Rows predating the ``profile`` column group with profile=None
    rows (legacy ≡ default-knob series), so history written before this
    schema addition keeps gating."""
    p = tmp_path / "ledger.jsonl"
    for eps in (100.0,) * 5:
        row = ledger.make_row(source="t", config="c", bench=_bench(eps))
        row.pop("profile", None)
        row.pop("fingerprint", None)  # pre-PR-19 row shape
        ledger.append_row(row, str(p))
    ledger.append_row(
        ledger.make_row(source="t", config="c", bench=_bench(50.0)),
        str(p),
    )
    ok, report = ledger.check_rows(ledger.read_rows(str(p)))
    assert not ok, report
