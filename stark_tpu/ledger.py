"""Cross-run performance ledger: append-only perf rows + a regression gate.

The first five driver captures of the flagship were never compared with
one another — a perf regression would ship silently
as long as the run still converged.  This module turns that trajectory
into a *gate*: every bench (or any traced run) appends one schema'd JSONL
row of its headline numbers to ``bench_artifacts/ledger.jsonl``, and
``check`` compares the newest row against the **trailing median** of its
predecessors with a tolerance band, exiting non-zero on regression — the
CI hook the ROADMAP's production-traffic story needs.

Row schema (``LEDGER_SCHEMA`` = 1)::

    schema       int    — writer version
    ts           float  — unix time the row was appended
    source       str    — who appended ("bench.py", "perf_ledger ingest")
    config       str    — comparability key: rows are only gated against
                          earlier rows with the SAME config string
    note         str?   — freeform operator annotation
    git_sha / jax_version / jaxlib_version   — telemetry.provenance()
    platform / device_kind / device_count    — telemetry.device_info()
    fingerprint  str    — platform.hardware_fingerprint() (the autotuner's
                          hardware comparability key)
    profile      str?   — the active autotuned profile id (stark_tpu.profile),
                          or None when the run used default/explicit-env
                          knobs.  Rows with DIFFERENT profiles are distinct
                          gating series: an autotuned config must never be
                          judged against the default-knob median (or vice
                          versa), so `check_rows` filters history on
                          (config, profile), with legacy pre-profile rows
                          (no column) ≡ None.
    metrics: ess_per_sec, wall_s, max_rhat, converged, restarts,
             device_idle_frac, overshoot_draws, diag_bytes_to_host
             (absent → None; the gate skips missing values)

Direction matters: ``ess_per_sec`` regresses DOWN, everything else
regresses UP — `METRIC_SPECS` records which.  Only ``ess_per_sec`` gates
by default (throughput is the judged metric); ``--strict`` gates the
efficiency metrics too.  The median (not the mean, not the max) is the
baseline so one lucky/unlucky round can't move the bar, and the tolerance
band (default ±25%) absorbs run-to-run noise: a genuine 2x throughput
drop is ~3x past the band, a 5% wobble is inside it.

CLI: ``tools/perf_ledger.py ingest|check`` (stdlib-only read path);
``bench.py`` auto-appends its final artifact line (STARK_PERF_LEDGER=0
opts out, a path overrides the destination).
"""

from __future__ import annotations

import json
import math
import os
import time
from typing import Any, Dict, List, Optional, Tuple

from . import telemetry

__all__ = [
    "LEDGER_ENV",
    "LEDGER_SCHEMA",
    "METRIC_SPECS",
    "append_row",
    "check_rows",
    "default_ledger_path",
    "make_row",
    "read_rows",
    "row_from_trace_summary",
]

LEDGER_SCHEMA = 1

#: env knob: a path overrides the default ledger location; "0"/"" disables
#: the bench auto-append entirely
LEDGER_ENV = "STARK_PERF_LEDGER"

#: metric name -> (higher_is_better, gated_by_default).  Gated metrics
#: fail `check_rows`; the rest report only under ``strict``.
METRIC_SPECS: Dict[str, Tuple[bool, bool]] = {
    "ess_per_sec": (True, True),
    "wall_s": (False, False),
    "device_idle_frac": (False, False),
    "overshoot_draws": (False, False),
    "diag_bytes_to_host": (False, False),
}


def default_ledger_path() -> Optional[str]:
    """The effective ledger path (None = auto-append disabled)."""
    raw = os.environ.get(LEDGER_ENV)
    if raw is not None:
        raw = raw.strip()
        if raw in ("", "0"):
            return None
        return raw
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(repo, "bench_artifacts", "ledger.jsonl")


def _finite(v: Any) -> Optional[float]:
    try:
        f = float(v)
    except (TypeError, ValueError):
        return None
    return f if math.isfinite(f) else None


def row_from_trace_summary(summary: Dict[str, Any]) -> Dict[str, Any]:
    """Ledger metric fields from a `telemetry.summarize_trace` dict (the
    same dict ``tools/trace_report.py --json`` emits — machine consumers
    share one schema).  ess_per_sec is derived from the summarized health
    (min_ess over the run wall) when both are present."""
    health = summary.get("health") or {}
    overlap = summary.get("overlap") or {}
    diag = summary.get("diag") or {}
    wall = _finite(summary.get("wall_s"))
    min_ess = _finite(health.get("min_ess"))
    return {
        # `is not None`, not truthiness: a measured-zero ESS (stuck
        # chains) must become rate 0.0 — the exact collapse the gate
        # exists to catch — never a skipped n/a
        "ess_per_sec": (
            round(min_ess / wall, 4)
            if min_ess is not None and wall
            else None
        ),
        "wall_s": wall,
        "max_rhat": _finite(health.get("max_rhat")),
        "converged": None,
        "device_idle_frac": _finite(overlap.get("device_idle_frac")),
        "overshoot_draws": _finite(diag.get("overshoot_draws")),
        "diag_bytes_to_host": _finite(diag.get("bytes_last")),
        "restarts": summary.get("restarts"),
    }


def make_row(
    *,
    source: str,
    config: str,
    bench: Optional[Dict[str, Any]] = None,
    trace_summary: Optional[Dict[str, Any]] = None,
    note: Optional[str] = None,
) -> Dict[str, Any]:
    """One schema'd ledger row from a bench artifact line and/or a trace
    summary; the bench line wins where both carry a metric (it is the
    judged artifact, the trace is the supporting evidence)."""
    row: Dict[str, Any] = {
        "schema": LEDGER_SCHEMA,
        "ts": time.time(),
        "source": source,
        "config": config,
    }
    if note:
        row["note"] = note
    row.update(telemetry.provenance())
    info = telemetry.device_info()
    for k in ("platform", "device_kind", "device_count"):
        row[k] = info[k]
    from . import platform as _platform

    row["fingerprint"] = _platform.hardware_fingerprint()
    # profile provenance is ALWAYS written (null-not-absent for new rows:
    # the column is part of the series key); a bench artifact that stamped
    # its own "profile" wins over the ambient application state, because
    # the artifact records what was active WHEN IT RAN
    if bench is not None and "profile" in bench:
        row["profile"] = bench["profile"]
    else:
        try:
            from . import profile as _profile

            row["profile"] = _profile.active_profile_id()
        except Exception:  # noqa: BLE001 — provenance must never fault a run
            row["profile"] = None
    metrics: Dict[str, Any] = {
        k: None
        for k in ("ess_per_sec", "wall_s", "max_rhat", "converged",
                  "restarts", "device_idle_frac", "overshoot_draws",
                  "diag_bytes_to_host")
    }
    if trace_summary is not None:
        for k, v in row_from_trace_summary(trace_summary).items():
            if v is not None:
                metrics[k] = v
    if bench is not None:
        # bench.py final-line vocabulary: "value" IS ess/sec/chip
        mapping = {
            "ess_per_sec": bench.get("value"),
            "wall_s": bench.get("wall_s"),
            "max_rhat": bench.get("max_rhat"),
            "device_idle_frac": bench.get("device_idle_frac"),
            "overshoot_draws": bench.get("overshoot_draws"),
            "diag_bytes_to_host": bench.get("diag_bytes_to_host"),
        }
        for k, v in mapping.items():
            v = _finite(v)
            if v is not None:
                metrics[k] = v
        if bench.get("converged") is not None:
            metrics["converged"] = bool(bench["converged"])
        if bench.get("platform") is not None:
            row["platform"] = bench["platform"]
    row.update(metrics)
    return row


def append_row(row: Dict[str, Any], path: Optional[str] = None) -> str:
    """Append one row (flushed+fsynced, same durability contract as the
    supervisor's restart records); returns the path written."""
    if path is None:
        path = default_ledger_path()
        if path is None:
            raise ValueError(f"ledger disabled ({LEDGER_ENV})")
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    with open(path, "a") as f:
        f.write(json.dumps(row) + "\n")
        f.flush()
        os.fsync(f.fileno())
    return path


def read_rows(path: str) -> List[Dict[str, Any]]:
    """All parseable rows, oldest first; torn/foreign lines are skipped
    (the ledger is append-only and a crash may tear the last line)."""
    rows = []
    try:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if isinstance(rec, dict) and rec.get("schema") == LEDGER_SCHEMA:
                    rows.append(rec)
    except OSError:
        return []
    return rows


def _median(values: List[float]) -> float:
    s = sorted(values)
    n = len(s)
    mid = n // 2
    return s[mid] if n % 2 else 0.5 * (s[mid - 1] + s[mid])


def check_rows(
    rows: List[Dict[str, Any]],
    *,
    window: int = 5,
    tolerance: float = 0.25,
    min_history: int = 2,
    strict: bool = False,
    config: Optional[str] = None,
    all_configs: bool = False,
) -> Tuple[bool, List[str]]:
    """Gate the NEWEST row against the trailing median of its config peers.

    Which "newest"?  Default: the last row in the file — right for the
    append-then-check CI sequence.  But an interleaved append for an
    UNRELATED config would then mask a just-regressed run (the check
    would examine the wrong row and pass on "insufficient history"), so
    a pinned ``config=`` gates the newest row OF THAT config, and
    ``all_configs=True`` gates the newest row of every config present —
    use one of them whenever the ledger has concurrent writers.

    History is additionally filtered to the newest row's ``profile``
    (None for legacy/default-knob rows): switching an autotuned profile
    on or off starts a fresh series rather than comparing apples to
    oranges.

    Returns ``(ok, report_lines)``.  ``ok`` is False when a gated metric
    (all metrics under ``strict``) regressed past the tolerance band:
    higher-is-better metrics must reach ``median * (1 - tolerance)``,
    lower-is-better ones must stay under ``median * (1 + tolerance)``.
    Fewer than ``min_history`` comparable predecessors → ok with a note
    (a fresh ledger must not fail CI), as must a metric missing on either
    side (null stays distinguishable from measured-zero).
    """
    if not rows:
        return True, ["ledger empty: nothing to check"]
    if all_configs:
        seen: List[str] = []
        for r in rows:
            c = r.get("config")
            if c not in seen:
                seen.append(c)
        ok_all, report_all = True, []
        for c in seen:
            ok, report = check_rows(
                rows, window=window, tolerance=tolerance,
                min_history=min_history, strict=strict, config=c,
            )
            ok_all &= ok
            report_all.extend(report)
        return ok_all, report_all
    if config is not None:
        rows = [r for r in rows if r.get("config") == config]
        if not rows:
            return True, [f"no rows for config {config!r}: nothing to check"]
    newest = rows[-1]
    config = newest.get("config")
    # (config, profile) is the series key: a row produced under an
    # autotuned profile is only comparable to rows under the SAME profile
    # (legacy rows without the column ≡ None, the default-knob series)
    profile = newest.get("profile")
    history = [
        r for r in rows[:-1]
        if r.get("config") == config and r.get("profile") == profile
    ]
    series = f"config {config!r}" + (
        f" profile {profile!r}" if profile else ""
    )
    if len(history) < min_history:
        return True, [
            f"insufficient history for {series}: "
            f"{len(history)} prior row(s) < min_history={min_history}"
        ]
    history = history[-window:]
    ok = True
    report = [
        f"{series}: newest row "
        f"(git {newest.get('git_sha') or 'unknown'}) vs trailing median "
        f"of {len(history)} row(s), tolerance {tolerance:.0%}"
    ]
    for metric, (higher_better, gated) in METRIC_SPECS.items():
        new_v = _finite(newest.get(metric))
        hist_v = [
            v for v in (_finite(r.get(metric)) for r in history)
            if v is not None
        ]
        if new_v is None or not hist_v:
            report.append(f"  {metric}: n/a (missing values)")
            continue
        med = _median(hist_v)
        if higher_better:
            bound = med * (1.0 - tolerance)
            regressed = new_v < bound
            direction = ">="
        else:
            bound = med * (1.0 + tolerance)
            regressed = new_v > bound
            direction = "<="
        tag = "OK"
        if regressed:
            if gated or strict:
                ok = False
                tag = "REGRESSION"
            else:
                tag = "regressed (not gated)"
        report.append(
            f"  {metric}: {new_v:.6g} vs median {med:.6g} "
            f"(must be {direction} {bound:.6g}) — {tag}"
        )
    return ok, report
