"""What the program's own device timeline says (`lib/timeline.py`: the
`device_wait_s`, `device_done_ns`, `tail_s` and warm-up tree counters it
writes on its spans); `params["number"]` says which number:

  setup_device_share        % of `setup_s` the host spent in synchronous
                            waits for the device (all of set-up's spans):
                            a lower bound on the device's share of set-up
  warmup_device_share       the same over call A's `warmup` span and what is
                            inside it, of that span's seconds
  warmup_lane_occupancy     % of the vmapped lanes of call A's warm-up trees
                            that did work: leaves over chains x lane
                            iterations, summed over its segments (a counter:
                            a dry run reports it too)
  window_device_idle_share  % of `window_s` the device idled between the
                            window's blocks, from the blocks' dispatch ends
                            and completion stamps; idle while the host was
                            in `block.record` (where a traced run starts and
                            stops the profiler) is left out and printed
                            apart, with idle by the innermost host span
  window_tail_s             the budget record's `tail_s`: the window's last
                            seconds, after the device finished the last
                            block it counts

Nothing from a program without the fields (the commit before them)."""

from lib import spans, timeline


def read(ctx, params):
    number = params["number"]
    if ctx["dry_run"] and number != "warmup_lane_occupancy":
        return None
    parts = spans.program_spans(ctx)
    if parts is None:
        return None
    if number == "setup_device_share":
        waited = timeline.device_wait_s(parts["setup"])
        return None if waited is None else 100.0 * waited / ctx["setup_s"]
    if number in ("warmup_device_share", "warmup_lane_occupancy"):
        warm = timeline.call_a_warmup(parts)
        if warm is None:
            return None
        if number == "warmup_device_share":
            waited = timeline.device_wait_s(warm)
            return None if waited is None else 100.0 * waited / spans.seconds(
                warm[0])
        segs = [s["fields"] for s in warm if s["name"] == "warmup_block"
                and "tree_leaves" in s["fields"]]
        lanes = ctx["chains"] * sum(g["lane_iterations"] for g in segs)
        return 100.0 * sum(g["tree_leaves"] for g in segs) / lanes \
            if lanes else None
    full = timeline.span_list(ctx)
    wnd = timeline.window(full) if full else None
    if wnd is None:
        return None
    timeline.report(ctx, wnd)
    if number == "window_device_idle_share":
        idle = wnd["idle_s"] - wnd["idle_by_span"].get(
            timeline.PROFILER_SPAN, 0.0)
        return 100.0 * idle / ctx["window_s"]
    if number == "window_tail_s":
        return wnd["tail_s"]
    raise ValueError(f"device_timeline: unknown number {number!r}")
