"""The device timeline the program keeps on its own spans (PR 40), for the
readers: when the device finished each block of the window, what it waited
for in set-up, and what warm-up's trees cost.

The fields, all on spans that existed before them:

  device_wait_s   seconds the host waited for the device in a synchronous
                  wait (`telemetry.wait`), on the innermost open span: a
                  lower bound on the device's busy time there
  device_done_ns  on a block's `block.wait`: when the device finished the
                  block and its ESS row (the runner's waiter thread, or the
                  block loop's own fetch where earlier)
  tail_s          on the `budget_exhausted` record: its start less the last
                  counted block's `device_done_ns`
  tree_leaves, lane_iterations
                  on a per-chain warm-up's `warmup_block` spans, as the
                  window's `block.gate` spans carry them

Block i of the window ran on the device over [start_i, done_i], start_i the
later of its `block.dispatch`'s end and done_(i-1) (never past done_i); the
device idled over [done_(i-1), start_i].  So the window, from the run's start
to the budget record's, is the resume, the first enqueue, the blocks' busy
time, the idle between them and the record's `tail_s`, exactly.  Spans are
plain dicts as `lib/spans.py` has them; a program without these fields gives
None.
"""

import sys

from . import spans as libspans

#: the span inside which the profiler starts and stops in a traced run
#: (ROADMAP D11 (b)): the device's idle there is the harness's
PROFILER_SPAN = "block.record"


def span_list(ctx):
    """Every span of the running program's log, as dicts, once a run (kept
    in `ctx`); None where the program keeps none."""
    if "program_span_list" not in ctx:
        from stark_tpu import telemetry

        log = getattr(telemetry, "span_log", None)
        ctx["program_span_list"] = (
            libspans.as_dicts(log()) if log is not None else None)
    return ctx["program_span_list"]


def last_run(spans):
    """The spans of the process's last entry call, by start (its root `run`
    first); [] without one."""
    roots = [s for s in spans if s["name"] == "run" and s["parent"] is None]
    if not roots:
        return []
    run = max(roots, key=lambda s: s["start_ns"])["run"]
    return sorted((s for s in spans if s["run"] == run),
                  key=lambda s: s["start_ns"])


def descendants(spans, root):
    """`root` and every span of its run below it."""
    kids = {}
    for s in spans:
        if s["run"] == root["run"]:
            kids.setdefault(s["parent"], []).append(s)
    out, todo = [], [root]
    while todo:
        s = todo.pop()
        out.append(s)
        todo.extend(kids.get(s["id"], []))
    return out


def call_a_warmup(parts):
    """Call A's `warmup` span and everything inside it (the first `warmup`
    of set-up), or None."""
    warm = [s for s in parts["setup"] if s["name"] == "warmup"]
    if not warm:
        return None
    return descendants(parts["setup"], min(warm, key=lambda s: s["start_ns"]))


def device_wait_s(spans):
    """Sum of `device_wait_s` over `spans`; None where none carries it."""
    waits = [s["fields"]["device_wait_s"] for s in spans
             if "device_wait_s" in s["fields"]]
    return float(sum(waits)) if waits else None


def _innermost(spans, t):
    over = [s for s in spans if s["start_ns"] <= t < s["end_ns"]]
    return max(over, key=lambda s: s["start_ns"])["name"] if over \
        else "other"


def idle_by_span(gaps, spans):
    """{name: seconds}: the gaps' time by the innermost span covering each
    piece of them (`other` where none does)."""
    out = {}
    for a, b in gaps:
        cuts = sorted({a, b} | {t for s in spans
                                for t in (s["start_ns"], s["end_ns"])
                                if a < t < b})
        for lo, hi in zip(cuts, cuts[1:]):
            name = _innermost(spans, (lo + hi) / 2.0)
            out[name] = out.get(name, 0.0) + (hi - lo) / 1e9
    return out


def window(spans):
    """The last run's window on the device, from its spans: {"window_s"
    (the run's start to the budget record's, program clock), "resume_s",
    "first_enqueue_s", "busy_s", "idle_s", "tail_s", "blocks",
    "idle_by_span", "intervals" [[block, start_ns, done_ns], ...]}; the
    tail is the budget record's `tail_s`.  None where the run has no such
    record or no block of its window carries `device_done_ns`."""
    mine = last_run(spans)
    budget = [s for s in mine if s["name"] == "block.record"
              and "tail_s" in s["fields"]]
    if not budget:
        return None
    root, end = mine[0], budget[0]["start_ns"]
    dispatch = {s["fields"].get("block"): s for s in mine
                if s["name"] == "block.dispatch"}
    waits = sorted((s for s in mine if s["name"] == "block.wait"
                    and s["start_ns"] < end
                    and "device_done_ns" in s["fields"]
                    and s["fields"].get("block") in dispatch),
                   key=lambda s: s["fields"]["block"])
    if not waits:
        return None
    first = dispatch[waits[0]["fields"]["block"]]
    intervals, gaps, prev = [], [], None
    for w in waits:
        done = w["fields"]["device_done_ns"]
        start = dispatch[w["fields"]["block"]]["end_ns"]
        if prev is not None:
            start = max(start, prev)
        start = min(start, done)
        if prev is not None and start > prev:
            gaps.append((prev, start))
        intervals.append([w["fields"]["block"], start, done])
        prev = done
    host = [s for s in mine[1:] if s["start_ns"] < end]
    busy = sum(d - s for _, s, d in intervals) / 1e9
    return {
        "window_s": (end - root["start_ns"]) / 1e9,
        "resume_s": (first["start_ns"] - root["start_ns"]) / 1e9,
        "first_enqueue_s": (intervals[0][1] - first["start_ns"]) / 1e9,
        "busy_s": busy,
        "idle_s": sum(b - a for a, b in gaps) / 1e9,
        "tail_s": budget[0]["fields"]["tail_s"],
        "blocks": len(intervals),
        "idle_by_span": idle_by_span(gaps, host),
        "intervals": intervals,
    }


def report(ctx, wnd):
    """The window's account on standard error, once a run."""
    if ctx.get("timeline_reported"):
        return
    ctx["timeline_reported"] = True
    parts = {k: wnd[k] for k in ("resume_s", "first_enqueue_s", "busy_s",
                                 "idle_s", "tail_s")}
    print(f"[onchip] device timeline: window {wnd['window_s']:.6f} s "
          f"(harness {ctx.get('window_s')}), {wnd['blocks']} blocks: "
          f"{parts}; sum {sum(parts.values()):.6f} s; idle by host span "
          f"{wnd['idle_by_span']}", file=sys.stderr, flush=True)
