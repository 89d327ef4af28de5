#!/usr/bin/env python
"""Render a phase-timing + chain-health summary from a telemetry trace.

    python tools/trace_report.py /tmp/t.jsonl            # last run in file
    python tools/trace_report.py /tmp/t.jsonl --run 1    # a specific run
    python tools/trace_report.py /tmp/t.jsonl --all      # every run
    python tools/trace_report.py /tmp/t.jsonl --json     # machine-readable

Traces are written by ``--trace PATH`` on the ``python -m stark_tpu``
subcommands, by ``bench.py`` (under the supervised workdir), or by any code
that installs a `stark_tpu.telemetry.RunTrace`.  Stdlib-only on the read
path apart from the schema helpers it shares with the writer
(`stark_tpu.telemetry`) — no jax import, so it runs anywhere the trace
file lands, including hosts without an accelerator.

Forward/backward compat: fields a trace predates (PR-1-era files carry no
overlap/diag accounting) render as ``n/a`` — never an error — and
``--json`` emits the raw `summarize_trace` dict, the machine contract
``tools/perf_ledger.py ingest --trace`` consumes for ledger rows.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

# repo-root invocation without installation
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from stark_tpu.telemetry import (  # noqa: E402
    PHASE_EVENTS,
    read_trace,
    summarize_trace,
)


def _fmt(v) -> str:
    # "n/a", never a crash: traces written before a field existed (e.g.
    # PR-1-era files predate the overlap/diag fields) must still render
    if v is None:
        return "n/a"
    if isinstance(v, bool):
        return "yes" if v else "no"
    if isinstance(v, float):
        return f"{v:.4g}"
    return str(v)


def _table(rows, header) -> str:
    """Plain aligned text table (no deps)."""
    cols = [header] + [[_fmt(c) for c in r] for r in rows]
    widths = [max(len(r[i]) for r in cols) for i in range(len(header))]
    lines = []
    for j, r in enumerate(cols):
        lines.append("  ".join(c.ljust(w) for c, w in zip(r, widths)).rstrip())
        if j == 0:
            lines.append("  ".join("-" * w for w in widths))
    return "\n".join(lines)


def render_run(events, run) -> str:
    s = summarize_trace(events, run=run)
    out = []
    meta = s["meta"]
    desc = " ".join(
        f"{k}={meta[k]}"
        for k in ("entry", "model", "kernel", "chains", "num_shards",
                  "num_temps", "platform", "device_count")
        if k in meta
    )
    out.append(f"run {s['run']}: {desc or '(no run_start event)'}")
    wall = s["wall_s"] or 0.0
    phase_sum = sum(p["total_s"] for p in s["phases"].values())
    out.append(
        f"wall {wall:.2f}s, {s['events']} events, "
        f"phases cover {phase_sum:.2f}s"
        + (f" ({100.0 * phase_sum / wall:.0f}%)" if wall else "")
        + (f", {s['restarts']} restart(s)" if s["restarts"] else "")
    )
    out.append("")

    # phase table in canonical order, then any others the writer added
    order = {name: i for i, name in enumerate(PHASE_EVENTS)}
    rows = [
        (
            name,
            p["count"],
            round(p["total_s"], 3),
            f"{100.0 * p['total_s'] / wall:.1f}%" if wall else "—",
        )
        for name, p in sorted(
            s["phases"].items(), key=lambda kv: order.get(kv[0], 99)
        )
    ]
    out.append(_table(rows, ("phase", "events", "total_s", "share")))
    out.append("")

    # block-pipeline overlap accounting (runner's async sample loop):
    # host work hidden behind in-flight device blocks, and the estimated
    # device idle fraction — the number the pipeline exists to drive to 0
    ov = s.get("overlap") or {}
    if ov:
        rows = [
            ("host work hidden (s)", ov.get("t_host_hidden_s")),
            ("host wait on device (s)", ov.get("t_wait_s")),
            ("device idle (s)", ov.get("device_idle_s")),
            (
                "device idle fraction",
                f"{100.0 * ov['device_idle_frac']:.1f}%"
                if ov.get("device_idle_frac") is not None
                else None,
            ),
        ]
        out.append(_table(
            [r for r in rows if r[1] is not None], ("block overlap", "value")
        ))
        out.append("")

    # streaming-diagnostics / adaptive-scheduler accounting: what the
    # convergence gate transferred per block (constant O(chains*d*L) with
    # streaming on, growing with the history under the legacy gate), the
    # last ESS forecast, and the end-of-run overshoot estimate
    dg = s.get("diag") or {}
    if dg:
        def _bytes(v):
            return None if v is None else f"{v / 1024.0:.1f} KiB"

        rows = [
            ("streaming gate", dg.get("stream_diag")),
            ("adaptive blocks", dg.get("adaptive_blocks")),
            ("gate transfer / block (last)", _bytes(dg.get("bytes_last"))),
            ("gate transfer / block (max)", _bytes(dg.get("bytes_max"))),
            ("gate transfer total", _bytes(dg.get("bytes_total"))),
            ("ESS forecast (draws/chain)", dg.get("ess_forecast_last")),
            ("overshoot (draws/chain)", dg.get("overshoot_draws")),
        ]
        out.append(_table(
            [r for r in rows if r[1] is not None],
            ("diagnostics transfer", "value"),
        ))
        out.append("")

    # ragged-NUTS scheduling (STARK_RAGGED_NUTS): lane occupancy — the
    # useful fraction of the gradient evaluations the batched block loop
    # executed (1.0 = no lane-sync waste); present only on knob-on runs
    ns = s.get("nutssched") or {}
    if ns:
        def _pct(v):
            return None if v is None else f"{100.0 * v:.1f}%"

        rows = [
            ("step-synchronized (ragged)", ns.get("ragged")),
            ("lane occupancy (last)", _pct(ns.get("occupancy_last"))),
            ("lane occupancy (min)", _pct(ns.get("occupancy_min"))),
            ("lane occupancy (mean)", _pct(ns.get("occupancy_mean"))),
            ("scheduler iterations", ns.get("sched_iters_total")),
            ("blocks accounted", ns.get("blocks")),
        ]
        out.append(_table(
            [r for r in rows if r[1] is not None],
            ("NUTS scheduling", "value"),
        ))
        out.append("")

    # fleet-sampling accounting (stark_tpu.fleet): batch occupancy /
    # convergence rollup plus a per-problem table from the
    # problem_converged events — which posterior finished when, at what
    # gradient cost, and who straggled
    fl = s.get("fleet") or {}
    if fl:
        rows = [
            ("problems", fl.get("problems")),
            ("converged", fl.get("problems_converged")),
            ("budget exhausted", fl.get("problems_budget_exhausted")),
            # per-problem fault domains: contained lane reseeds and
            # terminal quarantines (the fleet completed DEGRADED around
            # the lost problems — per-tenant loss, not process unhealth)
            ("quarantined", fl.get("problems_quarantined")),
            ("lane reseeds", fl.get("lane_reseeds")),
            ("degraded", fl.get("degraded")),
            ("lost problems",
             ", ".join(str(p) for p in fl["lost_problems"])
             if fl.get("lost_problems") else None),
            ("fleet blocks", fl.get("blocks")),
            ("compactions", fl.get("compactions")),
            # in-place admission accounting (slot scheduler / streaming
            # feed, PR 13) — n/a on traces that predate it
            ("admissions", fl.get("admissions")),
            ("slot recycles", fl.get("slot_recycles")),
            ("queue depth (last)", fl.get("queue_depth_last")),
            ("warm-started admissions", fl.get("warmstarted")),
            ("warmup draws saved", fl.get("warmup_draws_saved")),
            ("last occupancy", fl.get("occupancy_last")),
            ("last active/batch",
             f"{fl['active_last']}/{fl['batch_last']}"
             if fl.get("active_last") is not None
             and fl.get("batch_last") is not None else None),
            ("active grad evals", fl.get("grad_evals")),
            # mesh-parallel fleet (PR 14): shard count + per-shard
            # occupancy — n/a-filtered on single-device and pre-PR-14
            # traces like every other late-addition field
            ("mesh shards", fl.get("shards")),
            ("per-shard occupancy (last)",
             ", ".join(f"{float(o):.2f}" for o in fl["shard_occupancy_last"])
             if fl.get("shard_occupancy_last") else None),
            # elastic fault domains (PR 17): shards the deadman declared
            # lost (the fleet re-packed onto the survivors) and
            # backpressure-bounced feed submissions — n/a-filtered on
            # traces that predate them
            ("lost shards",
             ", ".join(str(k) for k in fl["lost_shards"])
             if fl.get("lost_shards") else None),
            ("feed rejects", fl.get("feed_rejects")),
        ]
        out.append(_table(
            [r for r in rows if r[1] is not None], ("fleet", "value")
        ))
        out.append("")
        # admission timeline (slot scheduler / streaming feed): which
        # problem entered which slot at which block, what the queue
        # looked like, and whether warm-start transfer seeded it —
        # absent (not an error) on traces that predate the events
        admitted = [
            e for e in events
            if e.get("run") == s["run"] and e["event"] == "problem_admitted"
        ]
        if admitted:
            rows = [
                (
                    e.get("block"),
                    e.get("problem_id"),
                    e.get("slot"),
                    e.get("source"),
                    e.get("queue_depth"),
                    e.get("warmstart"),
                    e.get("warmup_draws_saved"),
                )
                for e in admitted
            ]
            out.append(_table(
                rows,
                ("block", "admitted", "slot", "source", "queue",
                 "warm-start", "warmup saved"),
            ))
            out.append("")
        done = [
            e for e in events
            if e.get("run") == s["run"]
            and e["event"] in ("problem_converged", "problem_quarantined")
        ]
        if done:
            # quarantine forensics (PR 9 fields): WHY a problem was lost
            # and where its store's forensic copy went — n/a on older
            # traces and on rows that were never quarantined
            rows = [
                (
                    e.get("problem_id"),
                    e.get("status"),
                    e.get("blocks"),
                    e.get("draws_per_chain"),
                    e.get("grad_evals"),
                    e.get("min_ess"),
                    e.get("max_rhat"),
                    e.get("reason"),
                    e.get("quarantined_store"),
                )
                for e in done
            ]
            out.append(_table(
                rows,
                ("problem", "status", "blocks", "draws/chain",
                 "grad evals", "min ESS", "max R-hat", "reason",
                 "quarantined store"),
            ))
            out.append("")

    # mesh communication observatory (parallel.primitives, PR 16):
    # accounted collective calls / predicted wire bytes / host-blocked
    # wall plus the latest straggler attribution — absent (not an
    # error) on pre-PR-16 and STARK_COMM_TELEMETRY=0 traces
    cm = s.get("comms") or {}
    if cm:
        def _bytes(v):
            return None if v is None else f"{v / 1024.0:.1f} KiB"

        rows = [
            ("accounted calls", cm.get("calls")),
            ("payload bytes", _bytes(cm.get("payload_bytes"))),
            ("wire bytes", _bytes(cm.get("wire_bytes"))),
            ("host blocked (s)", cm.get("host_blocked_s")),
            ("by primitive",
             ", ".join(
                 f"{k}x{v['calls']}"
                 for k, v in sorted(cm["by_primitive"].items())
             ) if cm.get("by_primitive") else None),
            ("shards timed", cm.get("shards")),
            ("straggler shard (last)", cm.get("straggler_shard_last")),
            ("straggler ratio (last)", cm.get("straggler_ratio_last")),
        ]
        out.append(_table(
            [r for r in rows if r[1] is not None], ("comms", "value")
        ))
        out.append("")

    # unknown event types the summarizer could not classify (newer
    # writers): counted, never dropped
    other = s.get("other") or {}
    if other:
        out.append(_table(
            sorted(other.items()), ("unrecognized event", "count")
        ))
        out.append("")

    h = s["health"]
    if h:
        keys = (
            ("mean_accept", "acceptance rate"),
            ("num_divergent", "divergences"),
            ("max_rhat", "max R-hat"),
            ("min_ess", "min ESS"),
            ("num_stuck_components", "stuck components"),
            ("step_size", "step size"),
            ("draws_per_chain", "draws/chain"),
            # statistical-health observatory (stark_tpu.health) rollup —
            # n/a-filtered on pre-PR-15 / STARK_HEALTH=0 traces; the full
            # warning + divergence-localization table is
            # tools/health_report.py
            ("warnings", "health warnings"),
        )
        rows = [(label, h[k]) for k, label in keys if k in h]
        if h.get("warning_counts"):
            rows.append((
                "warning types",
                ", ".join(
                    f"{k}x{v}" for k, v in h["warning_counts"].items()
                ),
            ))
        out.append(_table(rows, ("chain health", "value")))
    else:
        out.append("(no chain_health events)")

    # per-shard / per-replica tagged health, when the parallel paths ran
    for tag in ("shard", "replica"):
        tagged = [
            e for e in events
            if e.get("run") == s["run"] and e["event"] == "chain_health"
            and tag in e
        ]
        if not tagged:
            continue
        cols = [
            k for k in ("step_size", "traj_length", "beta",
                        "swap_accept_pair", "num_divergent")
            if any(k in e for e in tagged)
        ]
        rows = [
            tuple([e[tag]] + [e.get(k) for k in cols]) for e in tagged
        ]
        out.append("")
        out.append(_table(rows, tuple([tag] + cols)))
    return "\n".join(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace", help="JSONL trace file")
    ap.add_argument("--run", type=int, default=None,
                    help="run ordinal to report (default: last)")
    ap.add_argument("--all", action="store_true", help="report every run")
    ap.add_argument("--json", action="store_true",
                    help="print the summary dict(s) as JSON instead")
    args = ap.parse_args(argv)

    # tolerate a torn final line: the trace may still be live
    events = read_trace(args.trace, strict=False)
    if not events:
        print(f"{args.trace}: no parseable events", file=sys.stderr)
        return 1
    runs = sorted({e.get("run", 0) for e in events})
    picked = runs if args.all else [args.run if args.run is not None else runs[-1]]
    if args.json:
        out = [summarize_trace(events, run=r) for r in picked]
        print(json.dumps(out[0] if len(out) == 1 else out, indent=1))
        return 0
    chunks = [render_run(events, r) for r in picked]
    print(("\n\n" + "=" * 60 + "\n\n").join(chunks))
    return 0


if __name__ == "__main__":
    sys.exit(main())
