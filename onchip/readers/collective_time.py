"""Collective operations in the profiler trace, plane by plane.

A collective is a leaf event of the operations line whose instruction is an
all-reduce, all-gather, reduce-scatter, collective-permute or all-to-all
(`params["pattern"]`).  Where the compiler made one asynchronous, its time is
start-to-done: from the `-start` event's start to the end of the next `-done`
event on that plane.

`params["what"]`:
  exposed_share   device time of the traced slice, averaged over the planes,
                  inside a collective while no other leaf operation runs on
                  that plane, as a share of the slice: what the collective
                  adds to the step, the wait for the slowest chip included
  us_per_call     median length of a collective that lies inside an execution
                  of the program whose modules-line event matches
                  `params["program"]` (the sampling program: one packed psum a
                  gradient), over all planes, in microseconds
"""

import re
import statistics

from lib import spans, tracered


def intervals(events, plane, pattern):
    """([(start_ns, end_ns), ...] of the plane's collectives, the plane's
    other leaf operations as the same)."""
    rx = re.compile(pattern)
    mine, others, open_starts = [], [], []
    for e in sorted(tracered.ops(events, plane), key=lambda e: e["start_ns"]):
        span = (e["start_ns"], e["start_ns"] + e["dur_ns"])
        m = rx.search(e["name"])
        if m is None:
            others.append(span)
        elif m.group("half") == "-start":
            open_starts.append(span[0])
        elif m.group("half") == "-done" and open_starts:
            mine.append((open_starts.pop(0), span[1]))
        else:
            mine.append(span)
    return mine, others


def _exposed_ns(mine, others):
    """Length of `mine` not covered by `others`."""
    both = tracered.union_ns(mine + others)
    return both - tracered.union_ns(others)


def read(ctx, params):
    events = ctx.get("trace_events")
    if not events:
        return None
    planes = tracered.device_planes(events)
    per_plane = [intervals(events, p, params["pattern"]) for p in planes]
    if not any(mine for mine, _ in per_plane):
        return None
    if params["what"] == "exposed_share":
        slice_ns = 1e9 * tracered.busy(events)["window_s"]
        exposed = [_exposed_ns(mine, others) for mine, others in per_plane]
        return 100.0 * sum(exposed) / len(exposed) / slice_ns
    rx = re.compile(params["program"])
    lengths = []
    for plane, (mine, _) in zip(planes, per_plane):
        runs = [(e["start_ns"], e["start_ns"] + e["dur_ns"]) for e in events
                if e["plane"] == plane and e["line"] == spans.MODULES_LINE
                and rx.search(e["name"])]
        lengths += [b - a for a, b in mine
                    if any(s <= a and b <= t for s, t in runs)]
    return statistics.median(lengths) / 1e3 if lengths else None
