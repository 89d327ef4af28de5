"""Reduction from a profiler trace to numbers: device busy time as the union
of operation intervals, idle share, kernel time by name pattern, the
operations that took most time, and the longest idle gaps with what the host
was doing.  Works on a plain list of events, so that a small recorded trace
(`onchip/testdata`) checks it; `load_xplane` is the thin reader of what
`jax.profiler` writes.

An event is {"plane", "line", "name", "start_ns", "dur_ns"}.
"""

import glob
import os
import re

#: device planes and the line that holds one event per executed operation
DEVICE_PLANE = r"^/device:TPU:\d+$"
OPS_LINE = "XLA Ops"
#: operations that only contain others (their interval covers their body's):
#: counting them would make every loop read as busy from end to end
CONTAINERS = r"^(while|conditional|call)$"
#: a Pallas / Mosaic kernel, as XLA names its custom call in an event's text
KERNEL_TARGET = "tpu_custom_call"
#: host markers the harness writes with `jax.profiler.TraceAnnotation`
MARKER_PREFIX = "onchip."


def load_xplane(trace_dir):
    """Events of the newest `*.xplane.pb` under `trace_dir`: every device
    plane's lines, and the harness's own markers from the host planes."""
    import jax

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    events = []
    for plane in jax.profiler.ProfileData.from_file(paths[-1]).planes:
        device = re.match(DEVICE_PLANE, plane.name) is not None
        for line in plane.lines:
            for ev in line.events:
                if device or ev.name.startswith(MARKER_PREFIX):
                    events.append({
                        "plane": plane.name, "line": line.name,
                        "name": ev.name, "start_ns": float(ev.start_ns),
                        "dur_ns": float(ev.duration_ns)})
    return events


def short_name(name):
    """An event of the operations line carries the operation's whole HLO text
    ("%while.61 = (s32[], ...) while(...)"): the result's name without the
    leading % and the trailing instance number; a kernel says so."""
    short = re.sub(r"[.:]\d+$", "", name.split(" = ")[0].lstrip("%"))
    return short + " (" + KERNEL_TARGET + ")" if KERNEL_TARGET in name else short


def device_planes(events):
    return sorted({e["plane"] for e in events
                   if re.match(DEVICE_PLANE, e["plane"])})


def ops(events, plane=None):
    """Leaf operations on the devices (one plane if named)."""
    return [e for e in events
            if e["line"] == OPS_LINE and re.match(DEVICE_PLANE, e["plane"])
            and (plane is None or e["plane"] == plane)
            and not re.match(CONTAINERS, short_name(e["name"]))]


def union_ns(intervals):
    """Total length of the union of (start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def busy(events):
    """{"busy_s", "window_s", "per_plane"}: seconds in which an operation ran,
    averaged over the device planes, and the traced window: from the first
    operation's start to the last one's end over all planes.  None when no
    operation ran on a device."""
    planes = device_planes(events)
    per, lo, hi = {}, None, None
    for p in planes:
        iv = [(e["start_ns"], e["start_ns"] + e["dur_ns"])
              for e in ops(events, p)]
        if not iv:
            continue
        per[p] = union_ns(iv) / 1e9
        lo = min(iv)[0] if lo is None else min(lo, min(iv)[0])
        hi = max(b for _, b in iv) if hi is None else max(
            hi, max(b for _, b in iv))
    if not per:
        return None
    return {"busy_s": sum(per.values()) / len(per),
            "window_s": (hi - lo) / 1e9, "per_plane": per}


def kernel_time(events, pattern):
    """(calls, seconds) of the operations whose text matches `pattern`,
    summed over the device planes."""
    rx = re.compile(pattern)
    hit = [e for e in ops(events) if rx.search(e["name"])]
    return len(hit), sum(e["dur_ns"] for e in hit) / 1e9


def top_ops(events, k=10):
    """[[name, seconds], ...]: the operations that took most time, by
    `short_name` (fusion.12 -> fusion)."""
    acc = {}
    for e in ops(events):
        name = short_name(e["name"])
        acc[name] = acc.get(name, 0.0) + e["dur_ns"] / 1e9
    return [[n, s] for n, s in
            sorted(acc.items(), key=lambda kv: -kv[1])[:k]]


def idle_gaps(events, host_spans=(), k=10):
    """[[what, seconds], ...]: the longest gaps between operations on the
    first device plane, each named by the host span (name, start_ns, end_ns,
    on the profiler's clock) that covers the gap's middle, else "host:other".
    """
    planes = device_planes(events)
    if not planes:
        return []
    iv = sorted((e["start_ns"], e["start_ns"] + e["dur_ns"])
                for e in ops(events, planes[0]))
    gaps, end = [], None
    for s, e in iv:
        if end is not None and s > end:
            gaps.append((s - end, (s + end) / 2.0))
        end = e if end is None else max(end, e)
    out = []
    for length, mid in sorted(gaps, reverse=True)[:k]:
        what = "host:other"
        for name, s, e in host_spans:
            if s <= mid <= e:
                what = name
                break
        out.append([what, length / 1e9])
    return out


def markers(events):
    """The harness's own host markers, by time: [(name, start_ns), ...]."""
    return sorted(((e["name"], e["start_ns"]) for e in events
                   if e["name"].startswith(MARKER_PREFIX)),
                  key=lambda m: m[1])


def write_summary(events, path, extract=600):
    """What a person reads before trusting the patterns above: every plane and
    line with its event count and its heaviest names, and the first events of
    the operations line with the markers (the form `onchip/testdata` keeps)."""
    import json

    lines = {}
    for e in events:
        rec = lines.setdefault(f"{e['plane']} | {e['line']}",
                               {"events": 0, "names": {}})
        rec["events"] += 1
        name = short_name(e["name"])
        rec["names"][name] = rec["names"].get(name, 0.0) + e["dur_ns"] / 1e9
    for rec in lines.values():
        rec["names"] = sorted(rec["names"].items(), key=lambda kv: -kv[1])[:15]
    planes = device_planes(events)
    first = sorted((e for e in events
                    if e["line"] == OPS_LINE and planes
                    and e["plane"] == planes[0]),
                   key=lambda e: e["start_ns"])[:extract]
    hi = max((e["start_ns"] + e["dur_ns"] for e in first), default=0.0)
    marks = [e for e in events if e["name"].startswith(MARKER_PREFIX)
             and e["start_ns"] <= hi]
    with open(path, "w") as f:
        json.dump({"lines": lines, "extract": first + marks}, f)
