"""The program's own spans (`stark_tpu.telemetry.span_log`) for the readers:
the log split into set-up, window and collect, its alignment to the profiler's
clock, and device idle time by the span the host was in.

A span here is a plain dict {"id", "parent", "run", "name", "start_ns",
"end_ns", "fields"} (times on the program's `time.perf_counter_ns`), so that a
small recorded excerpt (`onchip/testdata`) checks every function.  A program
without a span log (a commit before the spans) gives None, and every reader
built on this returns nothing there.

The alignment is needed because `tracered.load_xplane` keeps, of the host
planes, only the harness's own `onchip.`-prefixed markers.  The program wraps
the harness's `on_record` in its `block.record` span, so marker
`onchip.block.<i>` lies inside the i-th `block.record` span of the process's
last run: that pins the offset between the two clocks.  (The program also
writes every span as a `stark.<name>` annotation on the profiler's clock; a
reader of those needs no alignment.)
"""

import json
import os
import re
import statistics
import sys

from . import tracered

#: the device line with one event per executed program
MODULES_LINE = "XLA Modules"


def as_dicts(records):
    return [{"id": r.id, "parent": r.parent, "run": r.run, "name": r.name,
             "start_ns": r.start_ns, "end_ns": r.end_ns,
             "fields": dict(r.fields)} for r in records]


def seconds(span):
    return (span["end_ns"] - span["start_ns"]) / 1e9


def split(spans):
    """{"setup", "window", "collect", "run", "window_end_ns"}: set-up = the
    spans that ended before the last `run` span started; window = the last
    run's spans (its root among them) that started before its
    `budget_exhausted` record (before `collect`, or the run's end, without
    one); collect = its `collect` span and what is inside.  None without a
    `run` span."""
    roots = sorted((s for s in spans
                    if s["name"] == "run" and s["parent"] is None),
                   key=lambda s: s["start_ns"])
    if not roots:
        return None
    run = roots[-1]
    mine = sorted((s for s in spans if s["run"] == run["run"]),
                  key=lambda s: s["start_ns"])
    collect = [s for s in mine if s["name"].split(".")[0] == "collect"]
    end = min([s["start_ns"] for s in mine
               if s["fields"].get("event") == "budget_exhausted"]
              + [s["start_ns"] for s in collect] + [run["end_ns"]])
    return {
        "setup": sorted((s for s in spans if s["end_ns"] <= run["start_ns"]),
                        key=lambda s: s["start_ns"]),
        "window": [s for s in mine if s["start_ns"] < end],
        "collect": collect, "run": run, "window_end_ns": end,
    }


def program_spans(ctx=None):
    """`split` of the running program's span log, worked out once a run
    (kept in `ctx`).  None where the program has none."""
    if ctx is not None and "program_spans" in ctx:
        return ctx["program_spans"]
    from stark_tpu import telemetry

    log = getattr(telemetry, "span_log", None)
    parts = split(as_dicts(log())) if log is not None else None
    if ctx is not None:
        ctx["program_spans"] = parts
    return parts


def block_records(parts):
    """The last run's `block.record` spans of block records, in order (the
    `warmup_done` and `budget_exhausted` records carry an `event` field)."""
    return [s for s in parts["window"] + parts["collect"]
            if s["name"] == "block.record" and "event" not in s["fields"]]


def offset_ns(parts, markers):
    """(offset, half_width): what to add to a program time to get the
    profiler's, and how far off that can be.  Marker `onchip.block.<i>`
    starts inside the i-th `block.record` span, so each block bounds the
    offset from both sides (marker less span end, marker less span start);
    the answer is the middle of what all blocks allow.  Where they allow
    nothing in common, the median over blocks of marker start less span
    middle, with the largest residual.  (None, None) without a pair."""
    records = block_records(parts)
    pairs = []
    for name, start in markers:
        m = re.match(r"onchip\.block\.(\d+)$", name)
        if m and int(m.group(1)) < len(records):
            pairs.append((start, records[int(m.group(1))]))
    if not pairs:
        return None, None
    lo = max(m - s["end_ns"] for m, s in pairs)
    hi = min(m - s["start_ns"] for m, s in pairs)
    if lo <= hi:
        return (lo + hi) / 2.0, (hi - lo) / 2.0
    mids = [m - (s["start_ns"] + s["end_ns"]) / 2.0 for m, s in pairs]
    med = statistics.median(mids)
    return med, max(abs(d - med) for d in mids)


def device_gaps(events):
    """[(start_ns, end_ns), ...]: where no operation ran on the first device
    plane, between its first operation and its last."""
    planes = tracered.device_planes(events)
    if not planes:
        return []
    gaps, end = [], None
    for s, e in sorted((e["start_ns"], e["start_ns"] + e["dur_ns"])
                       for e in tracered.ops(events, planes[0])):
        if end is not None and s > end:
            gaps.append((end, s))
        end = e if end is None else max(end, e)
    return gaps


def idle_by_span(events, parts, offset, k=10):
    """{"by_span": {name: seconds}, "longest": [[name, seconds], ...]}:
    device idle time of the traced slice by the innermost program span of
    the last run that covers the gap's middle, `other` where none does (the
    root `run` span counts as none), and the k longest gaps with their
    names."""
    spans = [s for s in parts["window"] + parts["collect"]
             if s is not parts["run"]]
    by_span, named = {}, []
    for a, b in device_gaps(events):
        mid = (a + b) / 2.0 - offset
        over = [s for s in spans if s["start_ns"] <= mid <= s["end_ns"]]
        name = max(over, key=lambda s: s["start_ns"])["name"] if over \
            else "other"
        by_span[name] = by_span.get(name, 0.0) + (b - a) / 1e9
        named.append([name, (b - a) / 1e9])
    return {"by_span": by_span,
            "longest": sorted(named, key=lambda g: -g[1])[:k]}


def module_gaps_ns(events, pattern):
    """Device time between consecutive executions of the programs whose
    modules-line event matches `pattern`, on the first device plane."""
    planes = tracered.device_planes(events)
    rx = re.compile(pattern)
    runs = sorted((e["start_ns"], e["start_ns"] + e["dur_ns"])
                  for e in events
                  if planes and e["plane"] == planes[0]
                  and e["line"] == MODULES_LINE and rx.search(e["name"]))
    return [b[0] - a[1] for a, b in zip(runs, runs[1:])]


def tree(spans, root=None):
    """[[depth, name, seconds, compile_s, fields], ...] under `root` (all
    top-level spans of the list when None), in time order, with an `other`
    row for what a span's children leave uncovered."""
    ids = {(s["run"], s["id"]) for s in spans}
    rows = []

    def walk(span, depth):
        kids = sorted((s for s in spans if s["run"] == span["run"]
                       and s["parent"] == span["id"]),
                      key=lambda s: s["start_ns"])
        rows.append([depth, span["name"], seconds(span),
                     span["fields"].get("compile_s", 0.0), {
                         k: v for k, v in span["fields"].items()
                         if k in ("stage", "block", "event")}])
        for kid in kids:
            walk(kid, depth + 1)
        if kids:
            rows.append([depth + 1, "other",
                         seconds(span) - sum(seconds(k) for k in kids),
                         0.0, {}])

    tops = [root] if root is not None else [
        s for s in spans
        if s["parent"] is None or (s["run"], s["parent"]) not in ids]
    for top in sorted(tops, key=lambda s: s["start_ns"]):
        walk(top, 0)
    return rows


def cycle(parts):
    """{name: seconds}: the window by the top-level spans of its run, summed
    by name, with `other`."""
    run = parts["run"]
    out, covered = {}, 0.0
    for s in parts["window"]:
        if s["parent"] == run["id"] and s is not run:
            end = min(s["end_ns"], parts["window_end_ns"])
            out[s["name"]] = out.get(s["name"], 0.0) + (
                end - s["start_ns"]) / 1e9
            covered += (end - s["start_ns"]) / 1e9
    out["other"] = (parts["window_end_ns"] - run["start_ns"]) / 1e9 - covered
    return out


def report(ctx):
    """What a person reads beside the metrics, once a traced run: the set-up
    tree, the window's cycle, collect's split, idle by span and the
    alignment; on standard error and in `onchip/out/span_report.<config>
    .json` (with the raw spans and an excerpt of the trace around the first
    block boundary, the form `onchip/testdata` keeps)."""
    if "span_report" in ctx:
        return ctx["span_report"]
    ctx["span_report"] = rep = {}
    parts = program_spans(ctx)
    if parts is None:
        return rep
    events = ctx.get("trace_events") or []
    offset, half = offset_ns(parts, tracered.markers(events))
    rep.update(
        setup_s=ctx.get("setup_s"), window_s=ctx.get("window_s"),
        setup_tree=tree(parts["setup"]),
        call_runs=[[r["fields"].get("resumed"), seconds(r),
                    sum(seconds(s) for s in parts["setup"]
                        if s["run"] == r["run"] and s["parent"] == r["id"])]
                   for r in parts["setup"] if r["name"] == "run"],
        window_cycle=cycle(parts),
        collect_tree=tree(parts["collect"]),
        offset_ns=offset, offset_half_width_ns=half,
        spans_per_block=len(parts["window"]) / max(
            1, len(block_records(parts))),
    )
    if offset is not None:
        rep["idle"] = idle_by_span(events, parts, offset)
        ends = [e["start_ns"] + e["dur_ns"] for e in events
                if e["line"] == MODULES_LINE
                and "stark_chees_sample" in e["name"]]
        if ends:
            # around the first block boundary of the slice: the last
            # gradient before it, and after it the host's gate and record
            lo, hi = min(ends) - 8e6, min(ends) + 16e6
            rep["excerpt"] = {
                "offset_ns": offset,
                "events": [
                    e for e in events
                    if e["start_ns"] < hi and e["start_ns"] + e["dur_ns"] > lo
                    and (e["line"] in (tracered.OPS_LINE, MODULES_LINE)
                         or e["name"].startswith(tracered.MARKER_PREFIX))],
                "spans": [s for s in parts["window"]
                          if s["start_ns"] + offset < hi
                          and s["end_ns"] + offset > lo]}
    for depth, name, secs, comp, fields in rep["setup_tree"]:
        if depth <= 2 and secs >= 0.05 and name not in (
                "warmup_block", "block.checkpoint"):
            print(f"[onchip] setup {'  ' * depth}{name} {fields or ''} "
                  f"{secs:.3f} s (compile {comp:.3f})", file=sys.stderr)
    print(f"[onchip] window cycle {rep['window_cycle']}; offset half-width "
          f"{half} ns; idle {rep.get('idle', {}).get('by_span')}",
          file=sys.stderr, flush=True)
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "out",
        f"span_report.{ctx['config'].get('name', 'config')}.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(dict(rep, spans=parts["setup"] + parts["window"]
                       + parts["collect"]), f)
    return rep
