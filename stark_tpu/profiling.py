"""Run timeline profiling: span attribution + dispatch-count probes.

The trace bus (telemetry.py) records *phase events* — compile / warmup /
draw-block / checkpoint records stamped with ``dur_s`` and an emission
time — but nothing turns them into the question an accelerator budget
actually asks: **where did every wall-second go?**  "Running MCMC on
Modern Hardware" (PAPERS.md) argues dispatch accounting is exactly what
decides NUTS-on-accelerator viability, and the repo's own bench rounds
report one opaque wall number per leg.  This module is the attribution
layer:

  * **Span timeline** — `spans_from_events` decomposes one run's trace
    into non-overlapping, kind-tagged spans (``compile`` / ``warmup`` /
    ``dispatch`` / ``host_hidden`` / ``device_idle`` / ``checkpoint`` /
    ``host``), reusing the PR 3 block-overlap fields to split each draw
    block's wall into device-dispatch vs host-work-hidden vs
    device-idle.  `timeline_summary` rolls the spans up (coverage
    fraction, per-kind totals, ``compile_s``, ``dispatch_count``) —
    the numbers ``tools/timeline_report.py`` renders and ``bench.py``
    stamps into perf-ledger rows.  Works on ANY trace, including
    pre-PR-11 files (missing fields degrade to coarser attribution,
    never an error).
  * **``span`` event family** — the program measures real spans at the
    site (`telemetry.span`: start, end, parent, run; always on, in
    memory).  `span_events` writes them onto a trace as first-class
    ``span`` events (registered in `telemetry.ALL_EVENT_TYPES`) where
    ``STARK_PROFILE_SPANS=1`` asks for them; with the knob off, traces
    are byte-identical to historical behavior and `spans_from_events`
    falls back to subtracting durations from emission times.
  * **`DispatchProbe`** — the PR 8 ``benchmarks._GradEvalProbe``
    promoted to a first-class, installable dispatch-count probe: wraps
    a FlatModel's bound potential (``bind``) or any callable
    (``wrap``) so every EXECUTED evaluation — including the ones
    batched ``while_loop``s run for already-finished lanes, which
    never show up in ``num_grad_evals`` — bumps a host counter via
    ``jax.debug.callback``.  A process-level registry
    (`register_probe` / `probe_counts`) makes executed-vs-useful
    evaluation counts a per-run metric any harness can read.

No jax at module import: the timeline read path (like
``tools/trace_report.py``) must run anywhere the trace file lands,
including hosts without an accelerator.  Probe methods import
jax lazily at call time.
"""

from __future__ import annotations

import contextlib
import os
import threading
from typing import Any, Dict, Iterator, List, Optional, Tuple

from . import telemetry

__all__ = [
    "DispatchProbe",
    "SPAN_KINDS",
    "deregister_probe",
    "get_probe",
    "probe_counts",
    "register_probe",
    "span_events",
    "spans_from_events",
    "timeline_summary",
    "timeline_summary_from_file",
]

#: opt-in knob for ``span`` event emission (`span_events`)
PROFILE_SPANS_ENV = "STARK_PROFILE_SPANS"

#: span kinds, in the order the per-block decomposition emits them.
#: ``dispatch`` is host wall spent driving/awaiting device compute;
#: ``host_hidden`` is host work overlapped with an in-flight device
#: block (the PR 3 pipeline's win); ``device_idle`` is host work the
#: device starved behind; ``host`` is un-overlapped host phases
#: (the ``collect`` post-processing pass)
#: ``comm`` is host wall blocked inside a parallel-primitives collective
#: (PR 16's communication observatory) — carved OUT of the enclosing
#: block span by the emission-order claiming below (comm events emit
#: before their enclosing phase event closes)
SPAN_KINDS = (
    "compile",
    "warmup",
    "dispatch",
    "host_hidden",
    "device_idle",
    "checkpoint",
    "comm",
    "host",
)

#: phase event -> span kind for the single-kind phases
_SIMPLE_KINDS = {
    "compile": "compile",
    "warmup_block": "warmup",
    "checkpoint": "checkpoint",
    "collect": "host",
}

#: phase events that decompose via the block-overlap fields
_BLOCK_EVENTS = ("sample_block", "fleet_block")

#: phase events that represent device dispatch segments — the
#: ``dispatch_count`` numerator (one entry per retired dispatch cycle)
_DISPATCH_EVENTS = ("sample_block", "fleet_block", "warmup_block")


def _spans_from_phase_event(e: Dict[str, Any]) -> List[Dict[str, Any]]:
    """Kind-tagged (start, end) spans for ONE phase event.

    The event's ``wall_s`` is its emission time (= phase end) and
    ``dur_s`` the measured phase wall, so the span is
    ``[wall_s - dur_s, wall_s]``.  Draw-block events additionally split
    into dispatch / host-hidden / device-idle sub-spans by the PR 3
    overlap fields; events that predate those fields stay one
    ``dispatch`` span (coarser, never wrong-by-construction).
    """
    ev = e.get("event")
    end = e.get("wall_s")
    if ev == "comm":
        # comm events carry host_blocked_s, NOT dur_s (they overlap the
        # enclosing phase event and must not join the PHASE_EVENTS
        # tiling); the span is the host wall blocked inside the call
        hb = e.get("host_blocked_s")
        if (
            not isinstance(hb, (int, float))
            or not isinstance(end, (int, float))
            or float(hb) <= 0.0
        ):
            return []
        base = {"src": "comm"}
        if e.get("primitive") is not None:
            base["stage"] = e["primitive"]
        return [{"kind": "comm", "start": float(end) - float(hb),
                 "end": float(end), **base}]
    dur = e.get("dur_s")
    if not isinstance(dur, (int, float)) or not isinstance(end, (int, float)):
        return []
    dur = max(float(dur), 0.0)
    start = float(end) - dur
    base = {"src": ev}
    if e.get("block") is not None:
        base["block"] = e["block"]
    if e.get("stage") is not None:
        base["stage"] = e["stage"]
    if ev in _SIMPLE_KINDS:
        return [{"kind": _SIMPLE_KINDS[ev], "start": start, "end": float(end),
                 **base}]
    if ev not in _BLOCK_EVENTS:
        return []
    hh = e.get("t_host_hidden_s")
    di = e.get("device_idle_s")
    hh = max(float(hh), 0.0) if isinstance(hh, (int, float)) else 0.0
    di = max(float(di), 0.0) if isinstance(di, (int, float)) else 0.0
    # the sub-attributions cannot exceed the block's own wall: scale
    # down proportionally where they overshoot it (device_idle is measured
    # on the device's timeline, which the host's record of the block need
    # not cover; traces before PR 40 estimated it)
    if hh + di > dur and hh + di > 0:
        scale = dur / (hh + di)
        hh *= scale
        di *= scale
    dispatch = max(dur - hh - di, 0.0)
    spans = []
    t = start
    for kind, d in (("dispatch", dispatch), ("host_hidden", hh),
                    ("device_idle", di)):
        if d > 0.0:
            spans.append({"kind": kind, "start": t, "end": t + d, **base})
            t += d
    return spans


def _subtract_claimed(
    start: float, end: float, claimed: List[Tuple[float, float]]
) -> List[Tuple[float, float]]:
    """``[start, end)`` minus the (sorted, merged) claimed intervals."""
    out = []
    cur = start
    for cs, ce in claimed:
        if ce <= cur:
            continue
        if cs >= end:
            break
        if cs > cur:
            out.append((cur, min(cs, end)))
        cur = max(cur, ce)
        if cur >= end:
            break
    if cur < end:
        out.append((cur, end))
    return out


def _claim(start: float, end: float,
           claimed: List[Tuple[float, float]]) -> None:
    """Insert ``[start, end)`` into the merged claimed-interval list."""
    claimed.append((start, end))
    claimed.sort()
    merged: List[Tuple[float, float]] = []
    for s, e in claimed:
        if merged and s <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], e))
        else:
            merged.append((s, e))
    claimed[:] = merged


def spans_from_events(
    events: List[Dict[str, Any]], run: Optional[int] = None
) -> Dict[str, Any]:
    """Build the non-overlapping span timeline for one run.

    Uses literal ``span`` events when the writer emitted them
    (`span_events`: the program's own spans), otherwise synthesizes
    spans from the phase events.  Overlapping phases (the fleet's warmup blocks nest inside
    its ``compile`` setup phase) are resolved in emission order —
    inner phases end (and are emitted) first, so they claim their
    interval and the outer phase keeps only its unclaimed remainder.
    Returns::

        {"run": int,
         "t0": float | None, "t1": float | None,   # run window (wall_s)
         "wall_s": float | None,
         "spans": [{"kind", "start", "end", "dur", ...}, ...],
         "synthesized": bool}   # False when literal span events existed
    """
    runs = sorted({e.get("run", 0) for e in events})
    if not runs:
        return {"run": 0, "t0": None, "t1": None, "wall_s": None,
                "spans": [], "synthesized": True}
    run = runs[-1] if run is None else run
    evs = [e for e in events if e.get("run", 0) == run]

    t0 = t1 = None
    for e in evs:
        if e.get("event") == "run_start":
            t0 = e.get("wall_s")
        elif e.get("event") == "run_end":
            t1 = e.get("wall_s")

    literal = [e for e in evs if e.get("event") == "span"]
    raw: List[Dict[str, Any]] = []
    if literal:
        for e in literal:
            s, en = e.get("start_s"), e.get("end_s")
            if (
                isinstance(s, (int, float)) and isinstance(en, (int, float))
                and en > s and isinstance(e.get("kind"), str)
            ):
                sp = {"kind": e["kind"], "start": float(s), "end": float(en)}
                for k in ("src", "block", "stage", "gap", "id", "parent"):
                    if e.get(k) is not None:
                        sp[k] = e[k]
                raw.append(sp)
        # inner spans claim first: by end, the later start first
        raw.sort(key=lambda sp: (sp["end"], -sp["start"]))
    else:
        # prev_end: wall clock of the latest phase-event completion seen
        # so far — the cursor the block-loop gap attribution (below)
        # measures against
        prev_end: Optional[float] = None
        for e in evs:
            spans = _spans_from_phase_event(e)
            if not spans:
                continue
            s0 = min(sp["start"] for sp in spans)
            if (
                e.get("event") in _BLOCK_EVENTS
                and prev_end is not None
                and s0 > prev_end
            ):
                # pipelined block loop: a draw block's ``dur_s`` counts
                # its enqueue (jit trace/compile + dispatch) but that
                # enqueue ran EARLIER on the wall clock, while the
                # previous block computed — the host wall between two
                # block-loop completions is, by the loop's construction,
                # exactly that in-flight enqueue/dispatch work, so the
                # gap is attributed as dispatch rather than reported as
                # unaccounted slack
                raw.append({"kind": "dispatch", "start": prev_end,
                            "end": s0, "src": e.get("event"),
                            "gap": True})
            raw.extend(spans)
            end = e.get("wall_s")
            if isinstance(end, (int, float)):
                prev_end = (
                    float(end) if prev_end is None
                    else max(prev_end, float(end))
                )

    if t0 is None and raw:
        t0 = min(sp["start"] for sp in raw)
    if t1 is None:
        ends = [sp["end"] for sp in raw]
        if ends:
            t1 = max(ends)
        elif evs:
            t1 = evs[-1].get("wall_s")

    claimed: List[Tuple[float, float]] = []
    spans: List[Dict[str, Any]] = []
    for sp in raw:
        start, end = sp["start"], sp["end"]
        if t0 is not None:
            start = max(start, t0)
        if t1 is not None:
            end = min(end, t1)
        if end <= start:
            continue
        for fs, fe in _subtract_claimed(start, end, claimed):
            if fe - fs <= 0:
                continue
            frag = dict(sp)
            frag["start"], frag["end"] = fs, fe
            frag["dur"] = fe - fs
            spans.append(frag)
        _claim(start, end, claimed)
    spans.sort(key=lambda sp: sp["start"])
    wall = (t1 - t0) if (t0 is not None and t1 is not None) else None
    return {"run": run, "t0": t0, "t1": t1, "wall_s": wall,
            "spans": spans, "synthesized": not literal}


def timeline_summary(
    events: List[Dict[str, Any]], run: Optional[int] = None
) -> Dict[str, Any]:
    """Roll one run's span timeline up into the profiling headline
    numbers.  Every field degrades to ``None`` (never 0.0) when the
    trace predates the data it needs — the bench ledger's
    null-when-unavailable convention.  Returns::

        {"run": int,
         "wall_s": float | None,
         "by_kind": {kind: {"count", "total_s", "frac"}},
         "compile_s": float | None,      # compile-phase wall
         "dispatch_count": int | None,   # retired device dispatch
                                         # cycles (draw/warmup/fleet
                                         # block events)
         "span_coverage_frac": float | None,  # attributed fraction of
                                              # the run wall
         "x_dtype": str | None,          # resolved X-stream dtype when
                                         # the run streamed a non-f32
                                         # design slab (run_start tag)
         "x_bytes_per_grad": int | None, # that slab's bytes per
                                         # gradient evaluation
         "synthesized": bool}
    """
    tl = spans_from_events(events, run=run)
    evs = [e for e in events if e.get("run", 0) == tl["run"]]
    by_kind: Dict[str, Dict[str, float]] = {}
    covered = 0.0
    for sp in tl["spans"]:
        k = by_kind.setdefault(sp["kind"], {"count": 0, "total_s": 0.0})
        k["count"] += 1
        k["total_s"] += sp["dur"]
        covered += sp["dur"]
    wall = tl["wall_s"]
    for k in by_kind.values():
        k["total_s"] = round(k["total_s"], 4)
        k["frac"] = round(k["total_s"] / wall, 4) if wall else None
    compile_s = None
    dispatch_count = None
    n_dispatch = 0
    saw_dispatch = False
    comp = 0.0
    saw_comp = False
    x_dtype = None
    x_bytes = None
    for e in evs:
        ev = e.get("event")
        if ev == "compile" and isinstance(e.get("dur_s"), (int, float)):
            comp += float(e["dur_s"])
            saw_comp = True
        elif ev in _DISPATCH_EVENTS:
            n_dispatch += 1
            saw_dispatch = True
        elif ev == "run_start":
            # quantized/bf16 X streaming tags (ops/quantize.py): carried
            # into the summary so dispatch_count x x_bytes_per_grad
            # turns the bandwidth claim into measured arithmetic; None
            # (never 0) on f32 runs and pre-quant traces
            x_dtype = e.get("x_dtype", x_dtype)
            x_bytes = e.get("x_bytes_per_grad", x_bytes)
    if saw_comp:
        compile_s = round(comp, 4)
    if saw_dispatch:
        dispatch_count = n_dispatch
    coverage = (
        round(min(covered / wall, 1.0), 4) if wall and tl["spans"] else None
    )
    return {
        "run": tl["run"],
        "wall_s": wall,
        "by_kind": by_kind,
        "compile_s": compile_s,
        "dispatch_count": dispatch_count,
        "span_coverage_frac": coverage,
        "x_dtype": x_dtype,
        "x_bytes_per_grad": x_bytes,
        "synthesized": tl["synthesized"],
    }


def timeline_summary_from_file(
    path: str, run: Optional[int] = None
) -> Optional[Dict[str, Any]]:
    """`timeline_summary` over a trace file; None when the file is
    missing/empty/unreadable (the bench stamping path must never fail
    a measured run over its own evidence)."""
    try:
        events = telemetry.read_trace(path, strict=False)
    except OSError:
        return None
    if not events:
        return None
    return timeline_summary(events, run=run)


#: span name (`telemetry.span`) -> kind of the ``span`` event written for
#: it; a name that is not here is ``host``.  The root ``run`` span is not
#: written: it would claim the whole run and hide what no span covers.
_KIND_OF_SPAN = {
    "compile": "compile",
    "map_init": "warmup",
    "warmup": "warmup",
    "warmup_block": "warmup",
    "block.dispatch": "dispatch",
    "block.wait": "dispatch",
    "sample_block": "dispatch",
    "fleet_block": "dispatch",
    "block.checkpoint": "checkpoint",
    "checkpoint": "checkpoint",
}


@contextlib.contextmanager
def span_events(trace) -> Iterator[None]:
    """``STARK_PROFILE_SPANS=1`` (and a real trace): write the program's
    spans onto ``trace`` as ``span`` events — those that closed inside
    this block, at each ``run_end`` (so that they carry their run's
    ordinal) and at the block's exit.  Start, end and parent are the
    span's own; nothing is derived.  The CLI/bench wiring point."""
    if os.environ.get(PROFILE_SPANS_ENV, "") != "1" or not getattr(
            trace, "enabled", False):
        yield
        return
    written = (telemetry.span_log() or [None])[-1]  # the newest one out
    zero_ns = int(trace.clock_zero() * 1e9)

    def flush(rec=None):
        nonlocal written
        if rec is not None and rec.get("event") != "run_end":
            return
        log = telemetry.span_log()
        first = next((i + 1 for i in range(len(log) - 1, -1, -1)
                      if log[i] is written), 0)
        written = (log or [written])[-1]
        for sp in log[first:]:
            if sp.name == "run" and sp.parent is None:
                continue
            fields = {
                "kind": _KIND_OF_SPAN.get(sp.name, "host"),
                "start_s": round((sp.start_ns - zero_ns) / 1e9, 4),
                "end_s": round((sp.end_ns - zero_ns) / 1e9, 4),
                "dur_s": round((sp.end_ns - sp.start_ns) / 1e9, 4),
                "src": sp.name,
                "id": sp.id,
                "parent": sp.parent,
            }
            for k in ("block", "stage", "compile_s", "error"):
                if sp.fields.get(k) is not None:
                    fields[k] = sp.fields[k]
            trace.emit("span", **fields)

    telemetry.add_event_listener(flush)
    try:
        yield
    finally:
        telemetry.remove_event_listener(flush)
        flush()


# ---------------------------------------------------------------------------
# dispatch-count probes (promoted from benchmarks._GradEvalProbe, PR 8)
# ---------------------------------------------------------------------------


class DispatchProbe:
    """Dispatch-count probe for jitted entry points (jit-trace
    instrumentation — ROADMAP item 3's "profile the NUTS tree-building
    scan for dispatch-bound segments").  Wraps a FlatModel's bound
    potential (``bind``) — or any callable (``wrap``) — so every
    EXECUTED evaluation, including the ones vmap's batched
    ``while_loop``s run for already-finished (masked) lanes, which
    never show up in ``num_grad_evals``, bumps a host counter via
    ``jax.debug.callback``.  ``calls`` / the calibration in
    `benchmarks.bench_nuts_sched` turn that into executed-batched-
    evaluation counts, the denominator of the lane-occupancy numbers
    the trace events only estimate from the carry.

    Installable on any jitted entry — runner, fleet, fused ops: pass a
    probe-wrapped model (``DispatchProbe(fm)`` quacks like the
    FlatModel for ``bind``-consuming drivers) or wrap the callable
    directly.  `register_probe` makes the live count readable by name
    (`probe_counts`) from any harness in the process.
    """

    def __init__(self, fm=None, label: str = "grad_eval"):
        self._fm = fm
        self.label = label
        self.calls = 0

    def bind(self, data=None):
        """FlatModel-compatible bind: the returned Potential's
        value-and-grad counts every executed evaluation."""
        from .kernels.base import value_and_grad_of
        from .model import Potential

        inner = self._fm.bind(data)
        vag = value_and_grad_of(inner)
        counted = self.wrap(vag)
        return Potential(lambda z: inner(z), counted)

    def wrap(self, fn):
        """Wrap ANY callable so each executed (traced-in) call bumps the
        counter — the generalized form for jitted entries that are not
        model potentials (fused ops, block runners)."""
        import jax
        import jax.numpy as jnp

        def counting(*args, **kwargs):
            out = fn(*args, **kwargs)
            jax.debug.callback(self._bump, jnp.zeros((), jnp.int32))
            return out

        return counting

    def _bump(self, _x):
        self.calls += 1

    def reset(self) -> None:
        self.calls = 0

    def snapshot(self) -> int:
        """Drain pending callback effects, then read the counter —
        ``jax.block_until_ready`` waits only for OUTPUT buffers, not for
        debug-callback side effects, so every probe read must cross this
        barrier or risk undercounting."""
        import jax

        jax.effects_barrier()
        return self.calls


#: process probe registry: name -> live probe.  A harness (bench leg,
#: test, operator tooling) registers its probe so executed-dispatch
#: counts are readable as a per-run metric without plumbing the probe
#: object through every layer.
_PROBES: Dict[str, DispatchProbe] = {}
_PROBES_LOCK = threading.Lock()


def register_probe(probe: DispatchProbe,
                   name: Optional[str] = None) -> DispatchProbe:
    """Register ``probe`` under ``name`` (default: its label); returns
    the probe.  Re-registering a name replaces the previous probe."""
    with _PROBES_LOCK:
        _PROBES[name if name is not None else probe.label] = probe
    return probe


def deregister_probe(name: str) -> None:
    with _PROBES_LOCK:
        _PROBES.pop(name, None)


def get_probe(name: str) -> Optional[DispatchProbe]:
    """The live probe registered under ``name`` (None when absent) — how
    an instrumentable entry point (the fleet's batched block scan wraps
    its dispatch when ``"fleet_block_scan"`` is registered) discovers a
    harness's probe without plumbing the object through every layer."""
    with _PROBES_LOCK:
        return _PROBES.get(name)


def probe_counts(drain: bool = True) -> Dict[str, int]:
    """Live counts of every registered probe.  ``drain`` crosses the
    effects barrier first (the accurate read); pass False for a cheap
    peek from contexts that must not touch jax."""
    with _PROBES_LOCK:
        probes = dict(_PROBES)
    out = {}
    for name, p in probes.items():
        out[name] = p.snapshot() if drain else p.calls
    return out


# ---------------------------------------------------------------------------
# collective-dispatch probe (the communication observatory, PR 16)
# ---------------------------------------------------------------------------


class CommProbe:
    """Collective-dispatch counter for the parallel-primitives layer —
    the `DispatchProbe` pattern WITHOUT the device callback: primitives
    dispatch from host Python (or emit at jit-trace time), so a plain
    locked counter is exact and `snapshot` needs no effects barrier (and
    no jax import — the probe is readable from no-jax tooling).

    ``bump(site, primitive, wire_bytes)`` returns the new monotone
    per-(site, primitive) sequence number that rides each ``comm`` trace
    event, so executed-vs-emitted collective counts are testable: both
    sides of the acceptance check read the same counter."""

    label = "comm"

    def __init__(self):
        self._lock = threading.Lock()
        self._counts: Dict[Tuple[str, str], int] = {}
        self._bytes: Dict[Tuple[str, str], int] = {}

    def bump(self, site: str, primitive: str, wire_bytes: int = 0) -> int:
        """Count one executed collective; returns its per-(site,
        primitive) sequence number (1-based, monotone)."""
        key = (str(site), str(primitive))
        with self._lock:
            seq = self._counts.get(key, 0) + 1
            self._counts[key] = seq
            self._bytes[key] = self._bytes.get(key, 0) + int(wire_bytes)
            return seq

    def counts(self) -> Dict[Tuple[str, str], int]:
        """(site, primitive) -> executed-dispatch count."""
        with self._lock:
            return dict(self._counts)

    def bytes_by_site(self) -> Dict[Tuple[str, str], int]:
        """(site, primitive) -> cumulative predicted wire bytes."""
        with self._lock:
            return dict(self._bytes)

    def total_calls(self) -> int:
        with self._lock:
            return sum(self._counts.values())

    def total_bytes(self) -> int:
        with self._lock:
            return sum(self._bytes.values())

    @property
    def calls(self) -> int:
        # DispatchProbe-registry protocol (probe_counts drain=False)
        return self.total_calls()

    def snapshot(self) -> int:
        # registry protocol: host-side counter, no effects barrier needed
        return self.total_calls()

    def reset(self) -> None:
        with self._lock:
            self._counts.clear()
            self._bytes.clear()


_COMM_PROBE: Optional[CommProbe] = None


def comm_probe() -> CommProbe:
    """The process CommProbe singleton, registered under ``"comm"`` in
    the probe registry on first use (readable via `probe_counts`)."""
    global _COMM_PROBE
    with _PROBES_LOCK:
        if _COMM_PROBE is None:
            _COMM_PROBE = CommProbe()
            _PROBES["comm"] = _COMM_PROBE
    return _COMM_PROBE
