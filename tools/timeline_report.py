#!/usr/bin/env python
"""Render the attributed run timeline from a telemetry trace.

    python tools/timeline_report.py /tmp/t.jsonl            # last run
    python tools/timeline_report.py /tmp/t.jsonl --run 1    # specific run
    python tools/timeline_report.py /tmp/t.jsonl --all      # every run
    python tools/timeline_report.py /tmp/t.jsonl --json     # machine-readable
    python tools/timeline_report.py /tmp/t.jsonl --spans    # raw span list

Where ``tools/trace_report.py`` answers "what happened", this answers
"where did every wall-second go": the run decomposes into non-
overlapping, kind-tagged spans — compile / warmup / dispatch /
host_hidden / device_idle / checkpoint / comm / host.  A file written
under STARK_PROFILE_SPANS=1 carries the program's own spans as ``span``
events (`telemetry.span`: measured at the site, with start, end and
parent) and those are what is shown; any other file gets spans derived
by `stark_tpu.profiling` from the phase events' durations and emission
times.  The coverage line states how much of the run
wall the attribution accounts for; healthy post-PR-3 traces tile >=95%,
and the remainder is host-driver slack between phases.

Forward/backward compat: traces that predate a field (PR-1-era files
carry no overlap split; any pre-PR-11 trace carries no ``span``
events) render coarser attribution or ``n/a`` — never an error.
``--json`` emits the `profiling.timeline_summary` dict, the machine
contract ``bench.py`` stamps into perf-ledger rows (``compile_s`` /
``dispatch_count`` / ``span_coverage_frac``).  Stdlib-only read path
(no jax import), so it runs anywhere the trace file lands.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

# repo-root invocation without installation
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from stark_tpu.profiling import (  # noqa: E402
    SPAN_KINDS,
    spans_from_events,
    timeline_summary,
)
from stark_tpu.telemetry import read_trace  # noqa: E402


def _fmt(v) -> str:
    if v is None:
        return "n/a"
    if isinstance(v, bool):
        return "yes" if v else "no"
    if isinstance(v, float):
        return f"{v:.4g}"
    return str(v)


def _table(rows, header) -> str:
    cols = [header] + [[_fmt(c) for c in r] for r in rows]
    widths = [max(len(r[i]) for r in cols) for i in range(len(header))]
    lines = []
    for j, r in enumerate(cols):
        lines.append("  ".join(c.ljust(w) for c, w in zip(r, widths)).rstrip())
        if j == 0:
            lines.append("  ".join("-" * w for w in widths))
    return "\n".join(lines)


def render_run(events, run, show_spans=False) -> str:
    s = timeline_summary(events, run=run)
    out = []
    wall = s["wall_s"]
    cov = s["span_coverage_frac"]
    out.append(
        f"run {s['run']}: wall {_fmt(wall)}s, "
        f"attributed {_fmt(cov if cov is None else 100.0 * cov)}"
        + ("%" if cov is not None else "")
        + (" (spans synthesized from phase events)"
           if s["synthesized"] else " (the program's own spans)")
    )
    out.append(
        f"compile {_fmt(s['compile_s'])}s, "
        f"device dispatches {_fmt(s['dispatch_count'])}"
    )
    if s.get("x_dtype") is not None:
        # quantized/bf16 X streaming (ops/quantize.py); n/a-safe on
        # pre-quant traces (the key is simply absent there)
        out.append(
            f"x stream {s['x_dtype']}, "
            f"{_fmt(s.get('x_bytes_per_grad'))} bytes per gradient eval"
        )
    out.append("")
    by_kind = s["by_kind"]
    if not by_kind:
        out.append("(no attributable phase events in this run)")
        return "\n".join(out)
    order = {k: i for i, k in enumerate(SPAN_KINDS)}
    rows = [
        (
            kind,
            int(k["count"]),
            round(k["total_s"], 3),
            f"{100.0 * k['frac']:.1f}%" if k.get("frac") is not None else None,
        )
        for kind, k in sorted(
            by_kind.items(), key=lambda kv: order.get(kv[0], 99)
        )
    ]
    if wall is not None and cov is not None:
        un = max(wall - sum(k["total_s"] for k in by_kind.values()), 0.0)
        rows.append(("(unattributed)", None, round(un, 3),
                     f"{100.0 * un / wall:.1f}%" if wall else None))
    out.append(_table(rows, ("span kind", "spans", "total_s", "share")))
    if show_spans:
        tl = spans_from_events(events, run=run)
        out.append("")
        out.append(_table(
            [
                (
                    sp["kind"],
                    round(sp["start"], 3),
                    round(sp["end"], 3),
                    round(sp["dur"], 4),
                    sp.get("src"),
                    sp.get("block"),
                    sp.get("id"),
                    sp.get("parent"),
                )
                for sp in tl["spans"]
            ],
            ("kind", "start_s", "end_s", "dur_s", "src", "block", "id",
             "parent"),
        ))
    return "\n".join(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace", help="JSONL trace file")
    ap.add_argument("--run", type=int, default=None,
                    help="run ordinal to report (default: last)")
    ap.add_argument("--all", action="store_true", help="report every run")
    ap.add_argument("--json", action="store_true",
                    help="print the timeline_summary dict(s) as JSON")
    ap.add_argument("--spans", action="store_true",
                    help="also list every attributed span")
    args = ap.parse_args(argv)

    # tolerate a torn final line: the trace may still be live
    try:
        events = read_trace(args.trace, strict=False)
    except OSError as e:
        print(f"{args.trace}: {e}", file=sys.stderr)
        return 1
    if not events:
        print(f"{args.trace}: no parseable events", file=sys.stderr)
        return 1
    runs = sorted({e.get("run", 0) for e in events})
    picked = (
        runs if args.all
        else [args.run if args.run is not None else runs[-1]]
    )
    if args.json:
        out = [timeline_summary(events, run=r) for r in picked]
        print(json.dumps(out[0] if len(out) == 1 else out, indent=1))
        return 0
    chunks = [render_run(events, r, show_spans=args.spans) for r in picked]
    print(("\n\n" + "=" * 60 + "\n\n").join(chunks))
    return 0


if __name__ == "__main__":
    sys.exit(main())
