"""Structured run telemetry: a schema-versioned JSONL event bus (RunTrace).

Today's only window into a run is stdout — ad-hoc ``[bench]`` lines and the
JSON tail bench.py scrapes.  This module makes the run itself the artifact:
a `RunTrace` appends one JSON object per line to a trace file, each event
stamped with the schema version, wall-clock offsets, and the emitting
component's tags (shard/replica ids on the parallel paths), so a stalled
700 s run or an R-hat-2 chain decomposes into *phases* after the fact —
compile vs warmup vs draw blocks vs host diagnostics — with the chain-health
trail (acceptance, step size, divergences) alongside.  `tools/trace_report.py`
renders the summary table; `bench.py` consumes the same file for its phase
breakdown instead of re-deriving it from stdout.

Design rules:

  * **Events cost nothing when off; spans are always on.**  The default
    trace is the `NullTrace` singleton: every emit is a constant-time
    no-op and nothing here imports jax at module load.  `phase()` always
    records a `span` (below) — a few microseconds, in memory — and only a
    real trace adds the phase event.
  * **One span primitive.**  `span(name, **fields)` measures at the site:
    start, end, parent and run on ``time.perf_counter_ns()``, kept in one
    bounded in-memory log (`span_log`).  Every ``trace.phase(...)`` is a
    span; the runner adds spans where it has no phase event.  Each span
    also opens a ``jax.profiler.TraceAnnotation("stark.<name>")``, so a
    profile shows the host spans on the device's timeline, and collects
    what jax reports about compilation while it is the innermost open
    span (`_SPAN_COUNTERS`).
  * **Host-side only, block-bounded.**  Events are emitted from the host
    driver after `jax.block_until_ready` readbacks — never from inside a
    device program.  The one exception is the opt-in in-loop heartbeat
    (`heartbeat`, fed by ``jax.debug.callback`` — see `kernels.base.
    scan_progress`), which is rate-limited on the host so an unrolled
    vmap of callbacks cannot flood the file.
  * **Durable, append-only, crash-tolerant.**  Every line is flushed as
    written (same contract as the runner's metrics JSONL): a SIGKILL at any
    point leaves a parseable prefix.

Canonical event types (``EVENT_TYPES``): ``run_start``, ``compile``,
``warmup_block``, ``sample_block``, ``chain_health``, ``checkpoint``,
``run_end``.  Auxiliary types (``AUX_EVENT_TYPES``: ``progress``, ``adapt``,
``budget``, ``collect``, ``fault``) ride the same envelope; readers must
ignore event types they don't know (that is the forward-compat rule that
lets the schema grow without a version bump).  WRITERS are stricter: every
``emit("<name>", ...)``/``phase("<name>", ...)`` site in ``stark_tpu/``
must use a name from ``ALL_EVENT_TYPES`` — ``tools/lint_trace_schema.py``
enforces it, so schema drift (an event the readers and the metrics
exporter have never heard of) cannot land silently.

Live consumers: besides the JSONL file, every emitted record is fanned out
to registered **event listeners** (`add_event_listener`) — the in-process
metrics registry (`stark_tpu.metrics`) subscribes one to populate the
``/metrics``/``/status`` endpoints (`stark_tpu.statusd`) without touching
any emit site.  A `RunTrace` built with ``path=None`` is a pure in-memory
bus: events reach listeners but no file is written (how the status daemon
observes an otherwise-untraced run).  With no listeners registered the
fan-out is one truth test per emit; the `NullTrace` default path is
unchanged (no record is built at all).

Envelope fields present on EVERY event::

    schema   int   — SCHEMA_VERSION of the writer
    event    str   — event type
    ts       float — absolute unix time of emission
    wall_s   float — seconds since the trace (not the run) was opened
    run      int   — 1-based run ordinal within this trace file (0 = before
                     any run_start; a trace may hold several runs, e.g. a
                     compile pass + a timed pass)

Phase events (``compile``/``warmup_block``/``sample_block``/``checkpoint``)
additionally carry ``dur_s`` — the measured wall-clock of that phase — and
the per-run phase durations tile the run's wall (run_end.dur_s) to within
the host-driver slack, which is what makes the trace a *timing* artifact
and not just a log.
"""

from __future__ import annotations

import collections
import contextlib
import itertools
import json
import os
import sys
import threading
import time
from contextvars import ContextVar
from typing import Any, Dict, Iterator, List, NamedTuple, Optional

SCHEMA_VERSION = 1

#: canonical event types — the documented core of the schema.  Readers must
#: tolerate (skip or pass through) any OTHER event name: auxiliary events
#: (progress/adapt/budget) and future additions share the envelope.
EVENT_TYPES = frozenset(
    {
        "run_start",
        "compile",
        "warmup_block",
        "sample_block",
        "chain_health",
        "checkpoint",
        "run_end",
    }
)

#: auxiliary event types: legal for writers, optional for readers —
#: in-scan heartbeats, adaptation/budget markers, the host post-processing
#: phase, and injected-fault records (faults.py)
AUX_EVENT_TYPES = frozenset({"progress", "adapt", "budget", "collect",
                             "fault"})

#: fleet-sampling event types (stark_tpu.fleet): ``fleet_block`` — one
#: vmapped dispatch advanced the whole batch (occupancy/active/grad-eval
#: accounting); ``problem_converged`` — one problem finished (status
#: "converged" or "budget_exhausted", with its per-problem totals);
#: ``fleet_compact`` — converged lanes were compacted out of the batch
#: (and the batch refilled from the pending queue); ``problem_reseeded``
#: — one problem's lane went non-finite and was cold-restarted in place
#: with an attempt-folded key (the neighbors never notice);
#: ``problem_quarantined`` — a problem exhausted its per-problem restart
#: budget (or its persisted draws were corrupt on resume) and was masked
#: out terminally, its artifacts quarantined with the reason — the fleet
#: completes DEGRADED around it; ``slot_recycled`` — a terminal problem's
#: batch lane was handed to a queued problem IN PLACE (the slot-scheduler
#: or legacy top-up admission path — the compiled batch shape never
#: changes); ``problem_admitted`` — a queued problem entered the batch
#: through an in-place admission (slot/queue-depth/warm-start accounting);
#: ``shard_lost`` — the mesh fleet's shard deadman (STARK_SHARD_DEADLINE)
#: declared one mesh shard a unit of failure: every active lane on it
#: returned non-finite, or its block wall blew the deadline ratio over
#: the surviving-shard median — with ``shard`` (the lost ordinal),
#: ``cause`` ("nonfinite" or "wall"), ``lanes`` (the tenant lanes it
#: carried), ``shards_before``/``shards_after`` (the degraded re-shard),
#: and the affected ``problem_ids``; the survivors re-pack onto the
#: shrunk mesh and the victims cold-restart against their existing
#: budgets; ``feed_reject`` — a `FleetFeed.submit` was refused by the
#: bounded-depth backpressure gate (STARK_FEED_MAXDEPTH), with ``depth``
#: / ``maxdepth`` / ``retry_after_s`` (the structured reject the
#: producer got)
FLEET_EVENT_TYPES = frozenset({"fleet_block", "problem_converged",
                               "fleet_compact", "problem_reseeded",
                               "problem_quarantined", "slot_recycled",
                               "problem_admitted", "shard_lost",
                               "feed_reject"})

#: profiling event types (stark_tpu.profiling): ``span`` — one
#: attributed slice of the run timeline (``kind`` in
#: `profiling.SPAN_KINDS`, ``start_s``/``end_s``/``dur_s`` on the
#: trace's wall clock, ``id`` / ``parent`` / ``src`` of the program's own
#: span) written from the span log by the opt-in `profiling.span_events`
#: (STARK_PROFILE_SPANS=1; default traces carry none and stay
#: byte-identical)
PROFILING_EVENT_TYPES = frozenset({"span"})

#: statistical-health event types (stark_tpu.health): ``health_warning``
#: — one Stan-style sampler-health warning (``warning`` in
#: `health.WARNINGS`: divergences / low_ebfmi / max_treedepth_saturation
#: / low_accept / stuck_chain / high_rhat / low_ess_per_param), with
#: ``severity``, the measured ``value`` vs its ``threshold`` knob,
#: affected ``chains`` (and ``problem_id`` on fleet lanes), a
#: ``hint`` remediation string, and — on ``divergences`` — the bounded
#: per-block ``snapshots`` ring of divergent-transition positions
#: (divergence localization).  Emitted OUTSIDE the kernels' op/key
#: sequence, from the host block loop; STARK_HEALTH=0 suppresses the
#: family entirely (byte-identical traces).
HEALTH_EVENT_TYPES = frozenset({"health_warning"})

#: communication-observatory event types (stark_tpu.parallel.primitives):
#: ``comm`` — one collective dispatch through the MapReduce primitives
#: layer, with ``primitive`` (map_shards / reduce_tree / gather_axis /
#: broadcast / shard_put / gather_tree / scan_shards — the ordered
#: cross-shard scan's allgather; its replicated-slice mode moves nothing
#: and emits nothing), the named mesh ``axis`` (when
#: one is in scope), ``participants`` (collective fan-in/fan-out),
#: ``payload_bytes`` (one participant's pytree-leaf bytes, the
#: `quantize.predict_x_bytes` idiom), ``wire_bytes`` (payload x fan),
#: ``host_blocked_s`` (host wall inside the call — NOT ``dur_s``: comm
#: walls overlap the enclosing phase events, so they must not join the
#: PHASE_EVENTS tiling), ``site`` (caller file:function) and ``seq``
#: (monotone per-(site, primitive) count from `profiling.comm_probe`).
#: Host-side collectives (gather_tree/shard_put/broadcast/map_shards
#: dispatch) emit once per call; in-program collectives (reduce_tree /
#: gather_axis) emit once per TRACE of the enclosing jit — both outside
#: the compiled program's op/key sequence.  STARK_COMM_TELEMETRY=0
#: suppresses the family entirely (byte-identical traces).
COMM_EVENT_TYPES = frozenset({"comm"})

#: serving event types (stark_tpu.serving): ``serve_request`` — one
#: posterior read-plane request (``endpoint`` in summary / predict /
#: draws, ``problem_id``, ``dur_s`` host wall, ``cache`` hit/miss,
#: ``ok``; predict requests add ``batch``/``groups`` — requests and
#: compiled dispatches in the batched evaluation).  Emitted host-side
#: by `serving.PosteriorStore`, entirely outside the samplers' op/key
#: sequence; STARK_SERVE_TELEMETRY=0 suppresses the family (a fleet run
#: queried by a live read plane then stays byte-identical — the
#: ``serving_clean_identity`` drill).
SERVING_EVENT_TYPES = frozenset({"serve_request"})

#: config-plane event types (stark_tpu.profile): ``profile_load`` — one
#: autotuned-profile resolution FAILURE at an entry point (``action`` in
#: refused / missing, with ``path``, ``reason``, and the ``profile`` id
#: when the file parsed far enough to carry one).  The loud half of the
#: profile contract: a parity-failing / schema-mismatched / wrong-
#: fingerprint profile is REFUSED (the run proceeds on defaults) and
#: this event + a log warning say so.  The quiet half emits nothing: a
#: successfully applied profile is stamped into ``run_start``
#: (``profile`` field) instead, and no-profile / STARK_PROFILE=0 runs
#: emit neither — trace files stay byte-identical to the pre-profile
#: era by construction.
PROFILE_EVENT_TYPES = frozenset({"profile_load"})

#: lineage event types (stark_tpu.lineage): ``feed_submit`` — one
#: accepted `FleetFeed.submit`, the moment a tenant's ``job_id`` is
#: minted (``problem_id``, ``job_id``, queue ``depth``); ``slo_burn`` —
#: block-cadence SLO burn-rate accounting over a tenant's
#: `ProblemBudget` grants (``deadline_burn`` / ``restart_burn`` /
#: ``ess_burn`` fractions consumed; absent budgets ride as null, never
#: 0.0); ``trace_rotated`` — the trace file crossed
#: ``STARK_TRACE_MAX_MB`` and was atomically rotated (``rotated_to``,
#: ``size_bytes``; first line of the fresh file).  ``feed_submit`` and
#: ``slo_burn`` are emitted only with lineage enabled
#: (STARK_LINEAGE=0 → byte-identical traces); ``trace_rotated`` only
#: when the rotation knob is set (unset → unbounded file, the
#: pre-rotation contract).
LINEAGE_EVENT_TYPES = frozenset({"feed_submit", "slo_burn",
                                 "trace_rotated"})

#: the complete WRITER registry: every emit()/phase() call in stark_tpu/
#: must use one of these names (tools/lint_trace_schema.py enforces it)
ALL_EVENT_TYPES = (EVENT_TYPES | AUX_EVENT_TYPES | FLEET_EVENT_TYPES
                   | PROFILING_EVENT_TYPES | HEALTH_EVENT_TYPES
                   | COMM_EVENT_TYPES | SERVING_EVENT_TYPES
                   | PROFILE_EVENT_TYPES | LINEAGE_EVENT_TYPES)

#: envelope keys every event must carry (validate_event)
ENVELOPE_KEYS = ("schema", "event", "ts", "wall_s", "run")

#: phase event types whose dur_s values tile the run wall.  ``collect`` is
#: the auxiliary host post-processing phase (draw constraining, stat
#: assembly) — not in the canonical set but timed like the others so phase
#: sums account for the whole run.  ``fleet_block`` is the fleet runner's
#: per-dispatch sampling phase (stark_tpu.fleet) — a fleet run's wall is
#: tiled by fleet_block + warmup_block + checkpoint, not sample_block
PHASE_EVENTS = ("compile", "warmup_block", "sample_block", "fleet_block",
                "checkpoint", "collect")


def _trace_max_bytes() -> Optional[int]:
    """Resolved ``STARK_TRACE_MAX_MB`` rotation threshold in bytes, or
    None (unset / unparseable / non-positive → unbounded, the historical
    contract).  Read once per trace open: a long-lived serving loop's
    always-on recorder must not grow one file without bound, but a knob
    flip mid-run only takes effect on the next trace."""
    raw = os.environ.get("STARK_TRACE_MAX_MB", "").strip()
    if not raw:
        return None
    try:
        mb = float(raw)
    except ValueError:
        return None
    if mb <= 0:
        return None
    return int(mb * 1024 * 1024)


def rotated_paths(path: str) -> List[str]:
    """The on-disk rotation sequence for a trace, OLDEST FIRST, live
    file last: ``path.1``, ``path.2``, …, ``path``.  Readers
    (`summarize_trace` callers, lineage folding, the report tool) chain
    these to see the whole history; flight-recorder bundles are exempt
    from rotation and unaffected."""
    parts = []
    n = 1
    while os.path.exists(f"{path}.{n}"):
        parts.append(f"{path}.{n}")
        n += 1
    parts.append(path)
    return parts


def iter_traces(paths, strict: bool = False):
    """Chain `iter_trace` over many files (a rotated sequence, a fleet's
    mixed trace set); a missing file is skipped, not fatal."""
    for path in paths:
        try:
            yield from iter_trace(path, strict=strict)
        except OSError:
            continue


def _last_run_ordinal(path: str) -> int:
    """Highest run ordinal already in ``path`` (0 for a new/empty file).

    Run ordinals are monotone within a file, so only the tail needs
    reading; torn or foreign trailing lines are skipped."""
    try:
        size = os.path.getsize(path)
    except OSError:
        return 0
    if not size:
        return 0
    try:
        with open(path, "rb") as f:
            f.seek(max(0, size - 65536))
            tail = f.read().decode("utf-8", errors="replace")
    except OSError:
        return 0
    for line in reversed(tail.splitlines()):
        line = line.strip()
        if not line:
            continue
        try:
            rec = json.loads(line)
            return int(rec.get("run", 0))
        except (json.JSONDecodeError, TypeError, ValueError):
            continue
    return 0


class _TraceState:
    """Shared mutable core of a trace: file handle, clock zero, run counter.

    One instance is shared by a `RunTrace` and every `tagged()` child view,
    so tags are cheap (a new dict, same file/lock) and the run ordinal is
    global to the file.
    """

    __slots__ = ("f", "t0", "run", "lock", "path", "last_progress_ts",
                 "max_bytes")

    def __init__(self, path: Optional[str]):
        self.path = path
        self.max_bytes = _trace_max_bytes() if path is not None else None
        if path is None:
            # in-memory bus: no file — events exist only for the
            # registered listeners (the status daemon's untraced mode)
            self.f = None
            self.run = 0
        else:
            d = os.path.dirname(os.path.abspath(path))
            os.makedirs(d, exist_ok=True)
            self.f = open(path, "a")
            # append semantics: continue the file's run numbering, never
            # collide with a previous session's ordinals (run is monotone,
            # so the last parseable line carries the current maximum)
            self.run = _last_run_ordinal(path)
        self.t0 = time.perf_counter()
        # emits can arrive from jax.debug.callback threads: one lock
        # serializes line writes so events never interleave mid-line
        self.lock = threading.Lock()
        self.last_progress_ts = 0.0


def _rotate_locked(st: "_TraceState") -> Optional[Dict[str, Any]]:
    """Rotate the live trace file (st.lock HELD): close, shift the full
    file to the next free ``path.N`` slot via os.replace (atomic — a
    concurrent reader sees the old complete file or the new one, never
    a truncation), reopen fresh, and write one ``trace_rotated`` record
    as the new file's first line.  The run ordinal continues across the
    rotation.  Returns the rotated record for listener fan-out, or None
    when rotation failed (the trace keeps appending to the original
    file — retention is best-effort, the run is not)."""
    path = st.path
    size = st.f.tell()
    n = 1
    while os.path.exists(f"{path}.{n}"):
        n += 1
    try:
        st.f.close()
        os.replace(path, f"{path}.{n}")
        st.f = open(path, "a")
    except OSError:
        try:  # rotation failed: best effort to keep tracing at all
            st.f = open(path, "a")
        except OSError:
            st.f = None
        return None
    rec = {
        "schema": SCHEMA_VERSION,
        "event": "trace_rotated",
        "ts": time.time(),
        "wall_s": round(time.perf_counter() - st.t0, 4),
        "run": st.run,
        "rotated_to": f"{path}.{n}",
        "size_bytes": size,
    }
    st.f.write(json.dumps(rec) + "\n")
    st.f.flush()
    return rec



# ---------------------------------------------------------------------------
# spans: one measurement at the site, always on, in memory
# ---------------------------------------------------------------------------


class SpanRecord(NamedTuple):
    """One closed span.  ``(run, id)`` names it: ``run`` is the ordinal of
    the entry call it belongs to (1, 2, ... in this process; 0 outside any
    entry call, as `model.prepare_model_data`), ``id`` the ordinal of its
    opening within that run (the run's root span is 1), ``parent`` the id
    of the innermost span open in the thread when it opened (None for a
    root and at top level).  Times are ``time.perf_counter_ns()``.
    ``fields`` holds what the site gave, the jax compile counters that
    fired while this was the innermost open span (`_SPAN_COUNTERS`) and,
    for a span that died, ``error`` (the exception class)."""

    id: int
    parent: Optional[int]
    run: int
    name: str
    start_ns: int
    end_ns: int
    fields: Dict[str, Any]


#: set-up plus a 30 s window of the benchmark is under 500 spans; a test
#: process that drives a dozen runs and slices the log by its length
#: (`onchip/tests`) must not fill it
SPAN_LOG_SIZE = 16384

_SPAN_LOG: "collections.deque[SpanRecord]" = collections.deque(
    maxlen=SPAN_LOG_SIZE)
_OPEN_SPAN: ContextVar[Optional["Span"]] = ContextVar(
    "stark_tpu_span", default=None)
_RUN_IDS = itertools.count(1)
_TOP_LEVEL_IDS = itertools.count(1)

#: jax.monitoring events -> the field of the innermost open span they add
#: to.  Durations (seconds): backend compilation (on a persistent-cache hit
#: jax times the retrieval under the same event), the retrieval alone, and
#: tracing + lowering.  Counts: programs that asked the persistent cache,
#: and hits.
_SPAN_COUNTERS = {
    "/jax/core/compile/backend_compile_duration": "compile_s",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_s",
    "/jax/core/compile/jaxpr_trace_duration": "lower_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/compilation_cache/compile_requests_use_cache": "cache_requests",
    "/jax/compilation_cache/cache_hits": "cache_hits",
}

#: `jax.profiler.TraceAnnotation` once jax is in the process (the span
#: layer never imports it first: a process without jax has no profiler
#: session to annotate and no compilation to count)
_ANNOTATE: Any = None


def _on_jax_event(event: str, amount: Any = 1, **_: Any) -> None:
    """The one listener behind both jax.monitoring registrations (events
    come without an amount and count 1, durations come in seconds)."""
    key = _SPAN_COUNTERS.get(event)
    sp = _OPEN_SPAN.get() if key is not None else None
    if sp is not None:
        sp.fields[key] = sp.fields.get(key, 0) + amount


def _hook_jax() -> Any:
    """Register the compile counters and find TraceAnnotation, once, when
    jax has been imported by someone else."""
    global _ANNOTATE
    if _ANNOTATE is None and "jax" in sys.modules:
        import jax.profiler
        from jax import monitoring

        monitoring.register_event_listener(_on_jax_event)
        monitoring.register_event_duration_secs_listener(_on_jax_event)
        _ANNOTATE = jax.profiler.TraceAnnotation
    return _ANNOTATE


class Span:
    """An open span; use `span` / `run_span`.  A context manager, or
    ``open()`` ... ``close()`` where the interval does not fit one block
    of code (an ancestor that closes first closes it too, with its
    error)."""

    __slots__ = ("name", "fields", "id", "parent", "run", "start_ns",
                 "end_ns", "_root", "_outer", "_token", "_ids", "_mark")

    def __init__(self, name: str, fields: Dict[str, Any], root: bool = False):
        self.name = name
        self.fields = fields
        self._root = root
        self.end_ns = None

    def open(self) -> "Span":
        outer = self._outer = _OPEN_SPAN.get()
        if self._root:
            self.run, self.id, self.parent = next(_RUN_IDS), 1, None
            self._ids = itertools.count(2)
        elif outer is None:
            self.run, self.id, self.parent = 0, next(_TOP_LEVEL_IDS), None
            self._ids = _TOP_LEVEL_IDS
        else:
            self.run, self.parent = outer.run, outer.id
            self.id = next(outer._ids)
            self._ids = outer._ids
        annotate = _ANNOTATE or _hook_jax()
        self._mark = annotate("stark." + self.name) if annotate else None
        if self._mark is not None:
            self._mark.__enter__()
        self._token = _OPEN_SPAN.set(self)
        self.start_ns = time.perf_counter_ns()
        return self

    def close(self, error: Optional[str] = None, **fields: Any) -> None:
        end = time.perf_counter_ns()
        if self.end_ns is not None:
            return
        # spans opened inside and left open (an exception passed their
        # close) end here, innermost first, with the same error
        inner = _OPEN_SPAN.get()
        while inner is not None and inner is not self:
            inner._finish(end, error or "unclosed")
            inner = inner._outer
        self.fields.update(fields)
        self._finish(end, error)
        _OPEN_SPAN.reset(self._token)

    def _finish(self, end_ns: int, error: Optional[str]) -> None:
        self.end_ns = end_ns
        if error is not None:
            self.fields.setdefault("error", error)
        if self._mark is not None:
            self._mark.__exit__(None, None, None)
        _SPAN_LOG.append(SpanRecord(
            self.id, self.parent, self.run, self.name, self.start_ns,
            end_ns, self.fields))

    def note(self, **fields: Any) -> "Span":
        """Attach fields discovered while the span runs."""
        self.fields.update(fields)
        return self

    @property
    def seconds(self) -> float:
        """The span's length once closed, else the time since it opened."""
        end = self.end_ns if self.end_ns is not None else time.perf_counter_ns()
        return (end - self.start_ns) / 1e9

    def __enter__(self) -> "Span":
        return self.open()

    def __exit__(self, exc_type, exc, tb) -> None:
        self.close(exc_type.__name__ if exc_type is not None else None)


def span(name: str, **fields: Any) -> Span:
    """``with span("block.gate", block=3): ...`` — see `SpanRecord`."""
    return Span(name, fields)


def run_span(**fields: Any) -> Span:
    """The root span ``run`` of one entry call: spans opened inside it
    carry its run ordinal."""
    return Span("run", fields, root=True)


def note(root: bool = False, **fields: Any) -> None:
    """Attach fields to the innermost open span, or with ``root`` to the
    outermost (an entry call's ``run``), from code that did not open it;
    nothing where no span is open."""
    sp = _OPEN_SPAN.get()
    while root and sp is not None and sp._outer is not None:
        sp = sp._outer
    if sp is not None:
        sp.fields.update(fields)


def wait(x: Any) -> Any:
    """``jax.block_until_ready(x)``, timed on the innermost open span: the
    seconds waited are added to its ``device_wait_s`` (the path
    `_on_jax_event` takes for ``compile_s``).  In a synchronous loop the
    wait is the device's time for what was just dispatched, less whatever
    ran while the host was still enqueuing: a lower bound on device-busy
    time, tight when programs are cached.  With no span open it only
    waits."""
    import jax

    sp = _OPEN_SPAN.get()
    if sp is None:
        return jax.block_until_ready(x)
    t0 = time.perf_counter_ns()
    out = jax.block_until_ready(x)
    sp.fields["device_wait_s"] = (
        sp.fields.get("device_wait_s", 0.0)
        + (time.perf_counter_ns() - t0) / 1e9)
    return out


def span_log() -> List[SpanRecord]:
    """The closed spans the bounded log still holds, oldest close first."""
    return list(_SPAN_LOG)


class _Phase:
    """Context manager for a timed phase: a `span` named after the event,
    and ONE event at exit with the span's ``dur_s`` (plus any fields
    captured at enter or added via ``note()`` while the phase runs).  Under
    `NullTrace` (``trace`` None) the span is all there is."""

    __slots__ = ("_trace", "_event", "_fields", "_span")

    def __init__(self, trace: Optional["RunTrace"], event: str,
                 fields: Dict[str, Any]):
        self._trace = trace
        self._event = event
        self._fields = fields

    def note(self, **fields) -> "_Phase":
        """Attach fields discovered mid-phase (e.g. divergence counts read
        back after the dispatch)."""
        self._fields.update(fields)
        return self

    def __enter__(self) -> "_Phase":
        # the span keeps its own dict: the compile counters it gathers
        # are no field of the event
        self._span = Span(self._event, dict(self._fields)).open()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if exc_type is not None:
            # a phase that died still leaves its timing + the error class
            # in the trace — that is exactly the stall/fault evidence the
            # layer exists for
            self._fields.setdefault("error", exc_type.__name__)
        self._span.fields.update(self._fields)
        self._span.close(self._fields.get("error"))
        if self._trace is not None:
            self._trace.emit(self._event,
                             dur_s=round(self._span.seconds, 4),
                             **self._fields)


class RunTrace:
    """Append-only JSONL event bus for one trace file.

    ``emit`` never raises into the run: observability must not kill the
    sampler (the same rule as the runner's ``progress_cb``) — write errors
    disable the trace and the run continues.

    ``path=None`` builds a pure in-memory bus: no file is opened and no
    bytes are written, but every record still reaches the registered event
    listeners (`add_event_listener`) — how the status daemon observes a
    run nobody asked to trace to disk.
    """

    enabled = True

    def __init__(self, path: Optional[str], *,
                 tags: Optional[Dict[str, Any]] = None,
                 _state: Optional[_TraceState] = None):
        self._state = _state if _state is not None else _TraceState(path)
        self._tags = dict(tags) if tags else {}

    @property
    def path(self) -> Optional[str]:
        return self._state.path

    def emit(self, event: str, **fields) -> Optional[Dict[str, Any]]:
        """Write one event line; returns the record (None if disabled).

        Listeners see the record even when no file is attached (in-memory
        bus) or the file died (full disk) — the live exporters must not
        share the trace file's fate.
        """
        st = self._state
        listening = bool(_EVENT_LISTENERS)
        if st.f is None and not listening:
            return None
        rec = {
            "schema": SCHEMA_VERSION,
            "event": event,
            "ts": time.time(),
            "wall_s": round(time.perf_counter() - st.t0, 4),
            "run": st.run + (1 if event == "run_start" else 0),
        }
        rec.update(self._tags)
        rec.update(fields)
        if _RECORD_ANNOTATORS:
            # lineage (stark_tpu.lineage) stamps job_id here — one hook
            # covers every emit site; annotators must be cheap and a
            # failing one must never fault the run
            for fn in list(_RECORD_ANNOTATORS):
                try:
                    fn(rec)
                except Exception:  # noqa: BLE001
                    pass
        rotated_rec = None
        try:
            with st.lock:
                if event == "run_start":
                    st.run += 1
                    rec["run"] = st.run
                if st.f is not None:
                    st.f.write(json.dumps(rec) + "\n")
                    st.f.flush()
                    if (st.max_bytes is not None
                            and st.f.tell() >= st.max_bytes):
                        rotated_rec = _rotate_locked(st)
        except (OSError, ValueError):  # closed/full disk: drop tracing,
            st.f = None  # never the run
            if not listening:
                return None
        if listening:
            notify_event(rec)
            if rotated_rec is not None:
                notify_event(rotated_rec)
        return rec

    def phase(self, event: str, **fields) -> _Phase:
        """Timed phase: ``with trace.phase("sample_block", block=3): ...``
        emits one event at exit carrying the measured ``dur_s``."""
        return _Phase(self, event, dict(fields))

    def clock_zero(self) -> float:
        """``time.perf_counter()`` at ``wall_s`` 0: what puts a span
        (``perf_counter_ns``, the same clock) on this trace's wall."""
        return self._state.t0

    def tagged(self, **tags) -> "RunTrace":
        """A view writing to the same file with extra constant tags — how
        the parallel paths stamp shard/replica ids on their events."""
        merged = {**self._tags, **tags}
        return RunTrace(self._state.path, tags=merged, _state=self._state)

    def heartbeat(self, min_interval_s: float = 0.5, **fields) -> None:
        """Rate-limited auxiliary ``progress`` event for in-loop device
        callbacks: at most one line per ``min_interval_s`` regardless of
        how many chain-unrolled callbacks fire."""
        st = self._state
        now = time.perf_counter()
        if now - st.last_progress_ts < min_interval_s:
            return
        st.last_progress_ts = now
        self.emit("progress", **fields)

    def close(self) -> None:
        st = self._state
        with st.lock:
            if st.f is not None:
                st.f.close()
                st.f = None

    def __enter__(self) -> "RunTrace":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class NullTrace:
    """The default trace everywhere: it emits nothing; a phase still
    records its span."""

    enabled = False
    path = None

    def emit(self, event: str, **fields) -> None:
        return None

    def phase(self, event: str, **fields) -> _Phase:
        return _Phase(None, event, fields)

    def tagged(self, **tags) -> "NullTrace":
        return self

    def heartbeat(self, min_interval_s: float = 0.5, **fields) -> None:
        return None

    def close(self) -> None:
        return None

    def __enter__(self) -> "NullTrace":
        return self

    def __exit__(self, *exc) -> None:
        return None


NULL_TRACE = NullTrace()

# ambient trace: entry points (CLI --trace, bench.py) install a trace once;
# the drivers below them pick it up without threading a parameter through
# every backend signature.  ContextVar keeps nested/threaded runs isolated.
# A module-level mirror (_CALLBACK_TRACE) carries the same trace to
# jax.debug.callback host threads, which run OUTSIDE the installing
# context — the heartbeat path reads the mirror, everything else the
# ContextVar.
_CURRENT: ContextVar[Any] = ContextVar("stark_tpu_trace", default=NULL_TRACE)
_CALLBACK_TRACE: Any = NULL_TRACE

# progress listeners: the liveness side-channel the watchdog subscribes to.
# Distinct from the trace (beats flow even with tracing off) and zero-cost
# when nobody listens — one empty-list truth test per beat site.
_PROGRESS_LISTENERS: List[Any] = []

# event listeners: the live fan-out of every emitted trace record — the
# metrics registry (stark_tpu.metrics) subscribes one so /metrics and /status
# populate without any emit site changing.  Zero-cost when empty (one
# truth test per emit); listeners must be cheap and never raise (the
# exporter must not fault the run it observes).
_EVENT_LISTENERS: List[Any] = []

# record annotators: in-place enrichment of every record BEFORE it is
# serialized — the lineage layer (stark_tpu.lineage) registers one to
# stamp job_id at the single point all ~50 emit sites funnel through.
# Zero-cost when empty; an annotator must be cheap, must only ADD
# fields, and must never raise (exceptions are swallowed in emit).
_RECORD_ANNOTATORS: List[Any] = []


def add_record_annotator(fn) -> None:
    """Register ``fn(record)`` to mutate every record in place before it
    is written/fanned out (see `_RECORD_ANNOTATORS`)."""
    if fn not in _RECORD_ANNOTATORS:
        _RECORD_ANNOTATORS.append(fn)


def remove_record_annotator(fn) -> None:
    try:
        _RECORD_ANNOTATORS.remove(fn)
    except ValueError:
        pass


def add_event_listener(fn) -> None:
    """Register ``fn(record)`` to receive every emitted trace record (the
    full dict, envelope included).  Used by `stark_tpu.metrics`; listeners
    must be cheap and must not raise (exceptions are swallowed)."""
    if fn not in _EVENT_LISTENERS:
        _EVENT_LISTENERS.append(fn)


def remove_event_listener(fn) -> None:
    try:
        _EVENT_LISTENERS.remove(fn)
    except ValueError:
        pass


def notify_event(rec: Dict[str, Any]) -> None:
    """Fan one emitted record out to the event listeners; free when none
    are registered, and a listener exception never reaches the run."""
    if not _EVENT_LISTENERS:
        return
    for fn in list(_EVENT_LISTENERS):
        try:
            fn(rec)
        except Exception:  # noqa: BLE001 — observability must not fault the run
            pass


def add_progress_listener(fn) -> None:
    """Register ``fn()`` to be called on every progress beat (see
    `notify_progress`).  Used by `watchdog.Watchdog`; listeners must be
    cheap and must not raise (exceptions are swallowed)."""
    if fn not in _PROGRESS_LISTENERS:
        _PROGRESS_LISTENERS.append(fn)


def remove_progress_listener(fn) -> None:
    try:
        _PROGRESS_LISTENERS.remove(fn)
    except ValueError:
        pass


def notify_progress() -> None:
    """One progress beat: the run advanced by an observable unit (a draw
    block, a warmup segment, a checkpoint write, an in-scan heartbeat).
    Called from the host drivers; free when no listener is registered."""
    if not _PROGRESS_LISTENERS:
        return
    for fn in list(_PROGRESS_LISTENERS):
        try:
            fn()
        except Exception:  # noqa: BLE001 — liveness must not fault the run
            pass


#: WHAT the run is waiting on right now — context the watchdog stamps on
#: its stall event (a stall that names the hung shard is actionable; one
#: that doesn't is a shrug).  A plain dict swapped atomically: the host
#: driver writes, the watchdog thread reads a snapshot.
_PROGRESS_CONTEXT: Dict[str, Any] = {}


def set_progress_context(**fields: Any) -> None:
    """Annotate the current wait (e.g. ``waiting_on_shards=[2]``) so a
    stall fired DURING it carries the culprit.  Overwrites per key; the
    driver clears with `clear_progress_context` once the wait returns."""
    global _PROGRESS_CONTEXT
    ctx = dict(_PROGRESS_CONTEXT)
    ctx.update(fields)
    _PROGRESS_CONTEXT = ctx


def clear_progress_context(*keys: str) -> None:
    """Drop the named context keys (no args: drop everything)."""
    global _PROGRESS_CONTEXT
    if not keys:
        _PROGRESS_CONTEXT = {}
        return
    _PROGRESS_CONTEXT = {
        k: v for k, v in _PROGRESS_CONTEXT.items() if k not in keys
    }


def progress_context() -> Dict[str, Any]:
    """Snapshot of the current wait annotations (watchdog-thread safe:
    the dict is replaced, never mutated in place)."""
    return dict(_PROGRESS_CONTEXT)


def get_trace():
    """The ambient trace (NULL_TRACE unless one was installed)."""
    return _CURRENT.get()


def set_trace(trace) -> None:
    """Install ``trace`` as the ambient trace (None -> NULL_TRACE)."""
    global _CALLBACK_TRACE
    trace = trace if trace is not None else NULL_TRACE
    _CURRENT.set(trace)
    _CALLBACK_TRACE = trace


@contextlib.contextmanager
def use_trace(trace):
    """Scoped ambient-trace install: ``with use_trace(RunTrace(p)): ...``"""
    global _CALLBACK_TRACE
    trace = trace if trace is not None else NULL_TRACE
    token = _CURRENT.set(trace)
    prev_cb = _CALLBACK_TRACE
    _CALLBACK_TRACE = trace
    try:
        yield trace
    finally:
        _CURRENT.reset(token)
        _CALLBACK_TRACE = prev_cb


def resolve_trace(trace=None):
    """Parameter-or-ambient resolution used by traced entry points."""
    return trace if trace is not None else get_trace()


def device_info() -> Dict[str, Any]:
    """Platform/device fields for run_start events and ledger rows, as
    jax reports them.  A backend that cannot be reached raises here like
    it would at the first dispatch: a run stamped ``platform: unknown``
    says nothing about where it ran."""
    import jax

    devs = jax.local_devices()
    return {
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "device_count": jax.device_count(),
        "local_device_count": jax.local_device_count(),
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
    }


#: provenance cache: the git subprocess and version lookups run once per
#: process — run_start events fire per supervised attempt and must not
#: pay a fork each time
_PROVENANCE: Optional[Dict[str, Any]] = None


def provenance() -> Dict[str, Any]:
    """Best-effort run provenance for ``run_start`` events and perf-ledger
    rows: the repo git SHA (with a ``-dirty`` suffix when the worktree has
    modifications) and the jax/jaxlib versions.  Without these a cross-run
    regression is unattributable — the ledger can say WHAT got slower but
    not WHICH commit or toolchain did it.  Every field degrades to
    ``None`` rather than failing (no git binary, not a checkout, jax
    unimportable): provenance must never be the thing that kills a run.
    """
    global _PROVENANCE
    if _PROVENANCE is not None:
        return dict(_PROVENANCE)
    out: Dict[str, Any] = {"git_sha": None, "jax_version": None,
                           "jaxlib_version": None}
    try:
        import subprocess

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        sha = subprocess.run(
            ["git", "rev-parse", "--short=12", "HEAD"],
            cwd=repo, capture_output=True, text=True, timeout=10,
        )
        if sha.returncode == 0 and sha.stdout.strip():
            # -uno: tracked files only — run artifacts this very layer
            # appends (the perf ledger, traces under the repo) must not
            # stamp every later run -dirty on a pristine source tree
            dirty = subprocess.run(
                ["git", "status", "--porcelain", "-uno"],
                cwd=repo, capture_output=True, text=True, timeout=10,
            )
            suffix = (
                "-dirty"
                if dirty.returncode == 0 and dirty.stdout.strip()
                else ""
            )
            out["git_sha"] = sha.stdout.strip() + suffix
    except Exception:  # noqa: BLE001 — best-effort by contract
        pass
    try:
        import jax

        out["jax_version"] = jax.__version__
    except Exception:  # noqa: BLE001
        pass
    try:
        import jaxlib

        out["jaxlib_version"] = jaxlib.__version__
    except Exception:  # noqa: BLE001
        pass
    _PROVENANCE = out
    return dict(out)


def heartbeat(label, step, accept) -> None:
    """Host target for in-loop ``jax.debug.callback`` progress (see
    `kernels.base.scan_progress`): forwards to the installed trace's
    rate-limited heartbeat.  Reads the callback mirror, not the
    ContextVar — the runtime invokes debug callbacks from its own
    threads, outside the installing context.  Must accept whatever the
    callback thread hands it without raising."""
    notify_progress()  # in-scan liveness beats flow even with tracing off
    try:
        _CALLBACK_TRACE.heartbeat(
            label=str(label), step=int(step), accept=round(float(accept), 4)
        )
    except Exception:  # noqa: BLE001 — a progress tick must never fault a run
        pass


# ---------------------------------------------------------------------------
# reading side: parse + validate + summarize (trace_report / bench.py)
# ---------------------------------------------------------------------------


class TraceError(ValueError):
    """A trace line violates the envelope schema."""


def validate_event(rec: Dict[str, Any]) -> Dict[str, Any]:
    """Check the envelope; returns ``rec``.  Unknown event *types* are legal
    (forward compat); unknown schema *versions* are not — a reader must
    never silently misinterpret a future writer."""
    if not isinstance(rec, dict):
        raise TraceError(f"event must be an object, got {type(rec).__name__}")
    missing = [k for k in ENVELOPE_KEYS if k not in rec]
    if missing:
        raise TraceError(f"event missing envelope keys {missing}: {rec}")
    if rec["schema"] != SCHEMA_VERSION:
        raise TraceError(
            f"trace schema {rec['schema']} != reader schema {SCHEMA_VERSION}"
        )
    if not isinstance(rec["event"], str):
        raise TraceError(f"event type must be a string: {rec['event']!r}")
    return rec


def iter_trace(path: str, *, strict: bool = True) -> Iterator[Dict[str, Any]]:
    """Yield validated events.  ``strict=False`` skips undecodable lines
    (a live file's torn final line) instead of raising."""
    with open(path) as f:
        for lineno, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                rec = validate_event(json.loads(line))
            except (json.JSONDecodeError, TraceError):
                if strict:
                    raise TraceError(f"{path}:{lineno}: bad trace line {line!r}")
                continue
            yield rec


def read_trace(path: str, *, strict: bool = True) -> List[Dict[str, Any]]:
    return list(iter_trace(path, strict=strict))


# ---------------------------------------------------------------------------
# postmortem flight recorder
# ---------------------------------------------------------------------------

#: ring capacity (events) — STARK_FLIGHT_RING overrides
FLIGHT_RING_ENV = "STARK_FLIGHT_RING"
#: STARK_FLIGHT_RECORDER=0 disables capture AND dumps (the repo-wide
#: ``=0 opts out`` env convention); checked at use time so a drill can
#: toggle it without rebuilding the process singleton
FLIGHT_RECORDER_ENV = "STARK_FLIGHT_RECORDER"
#: how many postmortem bundles to keep per workdir (oldest pruned) —
#: a crash-looping run must not fill the disk with forensics
POSTMORTEM_KEEP_ENV = "STARK_POSTMORTEM_KEEP"

_POSTMORTEM_SCHEMA = 1


class FlightRecorder:
    """Always-on, zero-dependency postmortem capture.

    A bounded in-memory ring of the most recent trace events plus
    derived aggregates (per-type counts), installed as an event
    listener for the duration of any supervised / fleet / watchdog-
    armed run (refcounted — the zero-listener contract holds outside
    runs), and a ``dump_postmortem`` that writes a forensic bundle to
    the workdir the moment an anomaly fires: supervised restart,
    watchdog stall, fleet lane quarantine, per-problem deadline blow.
    The recorder only ever READS the trace stream — with it enabled
    and no anomaly, trace files are byte-identical to historical
    behavior and nothing lands on disk.

    Bundle layout (``<workdir>/postmortem/pmNNN-<trigger>/``)::

        events.jsonl   — ring contents (the last ~256 events, oldest
                         first; the triggering event is the final line)
        meta.json      — schema, trigger, unix ts, the triggering
                         event, `provenance()`, active config (the
                         STARK_*/JAX_*/BENCH_* environment), per-type
                         event counts
        status.json    — the live /status snapshot (only when a status
                         daemon is running in-process)
        metrics.prom   — the metrics exposition (same condition)

    Dumps never raise into the run (forensics must not kill the thing
    they document) and old bundles are pruned past
    ``STARK_POSTMORTEM_KEEP`` (default 16).
    """

    def __init__(self, capacity: Optional[int] = None):
        if capacity is None:
            try:
                capacity = int(os.environ.get(FLIGHT_RING_ENV, "") or 256)
            except ValueError:
                capacity = 256
        from collections import deque

        self._ring: Any = deque(maxlen=max(int(capacity), 16))
        self._lock = threading.Lock()
        self._counts: Dict[str, int] = {}
        self._workdir: Optional[str] = None
        self._refs = 0
        self._listening = False
        self._last: Optional[Dict[str, Any]] = None
        self._seq = 0

    @property
    def enabled(self) -> bool:
        return os.environ.get(FLIGHT_RECORDER_ENV, "1") != "0"

    def set_workdir(self, workdir: Optional[str]) -> None:
        """Where bundles land; the supervising entry point sets it."""
        with self._lock:
            self._workdir = workdir

    # -- capture -----------------------------------------------------------

    def install(self) -> "FlightRecorder":
        """Refcounted listener subscribe: nested supervision layers
        (supervisor + watchdog + fleet) each install/uninstall and the
        listener is registered exactly once, removed at zero.  The ref
        is taken even when disabled (install/uninstall stay paired);
        only the listener registration is gated on ``enabled`` — and
        re-checked on EVERY install, so a recorder re-enabled between
        nested installs starts capturing at the next one instead of
        staying deaf until the refcount drains."""
        with self._lock:
            self._refs += 1
            subscribe = self.enabled and not self._listening
            if subscribe:
                self._listening = True
        if subscribe:
            add_event_listener(self._on_event)
        return self

    def uninstall(self) -> None:
        with self._lock:
            if self._refs == 0:
                return
            self._refs -= 1
            last = self._refs == 0
            if last:
                self._listening = False
        if last:
            # no-op when the listener was never registered (disabled)
            remove_event_listener(self._on_event)

    def _on_event(self, rec: Dict[str, Any]) -> None:
        ev = rec.get("event")
        if ev == "span":
            # the span log's copy of what the phase events in the ring
            # already say (profiling.span_events): ringing them would
            # shrink the forensic window under STARK_PROFILE_SPANS=1
            return
        with self._lock:
            self._ring.append(rec)
            if isinstance(ev, str):
                self._counts[ev] = self._counts.get(ev, 0) + 1

    def aggregates(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "events_by_type": dict(self._counts),
                "ring_len": len(self._ring),
                "ring_capacity": self._ring.maxlen,
                "workdir": self._workdir,
            }

    def last_postmortem(self) -> Optional[Dict[str, Any]]:
        with self._lock:
            return dict(self._last) if self._last else None

    # -- dumps -------------------------------------------------------------

    def record_anomaly(self, trigger: str, trace, event: str,
                       **fields) -> Optional[str]:
        """The one anomaly idiom every wiring site uses: emit the event
        on ``trace`` when tracing is on (the listener rings the emitted
        record), fall back to a synthetic record when it isn't, and
        dump the postmortem bundle either way.  Returns the bundle
        path (None when disabled or no workdir is known)."""
        emitted = trace.emit(event, **fields) if trace.enabled else None
        return self.note_anomaly(
            trigger, emitted or {"event": event, **fields}
        )

    def note_anomaly(
        self,
        trigger: str,
        rec: Optional[Dict[str, Any]] = None,
        workdir: Optional[str] = None,
    ) -> Optional[str]:
        """One anomaly happened: make sure its record is in the ring,
        then dump a bundle.  ``rec`` is the already-emitted trace
        record when tracing was on (the listener has it — compared by
        content, never duplicated) or a synthetic record the caller
        built when it wasn't.  Returns the bundle path (None when
        disabled or no workdir is known)."""
        if not self.enabled:
            return None
        if rec is not None:
            rec = dict(rec) if "ts" in rec else {"ts": time.time(), **rec}
            with self._lock:
                # when tracing is on the listener already ringed the
                # emitted record; the copy above breaks identity, so
                # dedup by content against the ring tail
                if not self._ring or self._ring[-1] != rec:
                    self._ring.append(rec)
                    ev = rec.get("event")
                    if isinstance(ev, str):
                        self._counts[ev] = self._counts.get(ev, 0) + 1
        return self.dump_postmortem(trigger, trigger_event=rec,
                                    workdir=workdir)

    def dump_postmortem(
        self,
        trigger: str,
        trigger_event: Optional[Dict[str, Any]] = None,
        workdir: Optional[str] = None,
    ) -> Optional[str]:
        """Write one bundle; returns its path (None when disabled, no
        workdir, or the write failed — never raises)."""
        if not self.enabled:
            return None
        with self._lock:
            wd = workdir or self._workdir
        if not wd:
            return None
        import logging
        import re

        log = logging.getLogger("stark_tpu.telemetry")
        slug = re.sub(r"[^A-Za-z0-9_.-]+", "_", trigger)[:60] or "anomaly"
        try:
            root = os.path.join(wd, "postmortem")
            os.makedirs(root, exist_ok=True)
            with self._lock:
                self._seq += 1
                seq = self._seq
                ring = list(self._ring)
                counts = dict(self._counts)
            d = os.path.join(root, f"pm{seq:03d}-{slug}")
            while os.path.exists(d):
                seq += 1
                d = os.path.join(root, f"pm{seq:03d}-{slug}")
            os.makedirs(d)
            with open(os.path.join(d, "events.jsonl"), "w") as f:
                for rec in ring:
                    f.write(json.dumps(rec, default=str) + "\n")
            config = {
                k: v for k, v in sorted(os.environ.items())
                if k.startswith(("STARK_", "JAX_", "BENCH_"))
            }
            meta = {
                "schema": _POSTMORTEM_SCHEMA,
                "trigger": trigger,
                "ts": time.time(),
                "trigger_event": trigger_event,
                "provenance": provenance(),
                "config": config,
                "events_by_type": counts,
                "ring_len": len(ring),
            }
            with open(os.path.join(d, "meta.json"), "w") as f:
                json.dump(meta, f, indent=1, default=str)
                f.write("\n")
            # live /status + /metrics snapshots ride along when a status
            # daemon is up in-process (lazy import: statusd -> metrics ->
            # telemetry is safe at call time, and absent otherwise)
            try:
                from . import statusd

                srv = statusd.get_server()
                if srv is not None:
                    with open(os.path.join(d, "status.json"), "w") as f:
                        json.dump(srv.collector.status(), f, indent=1,
                                  default=str)
                        f.write("\n")
                    with open(os.path.join(d, "metrics.prom"), "w") as f:
                        f.write(srv.registry.render())
            except Exception:  # noqa: BLE001 — snapshots are best-effort
                pass
            self._prune(root)
            info = {"path": d, "trigger": trigger, "ts": meta["ts"]}
            with self._lock:
                self._last = info
            log.warning("postmortem bundle written: %s (%s)", d, trigger)
            return d
        except Exception as e:  # noqa: BLE001 — forensics must not kill the run
            log.warning("postmortem dump failed (%s): %s",
                        type(e).__name__, e)
            return None

    def _prune(self, root: str) -> None:
        try:
            keep = int(os.environ.get(POSTMORTEM_KEEP_ENV, "") or 16)
        except ValueError:
            keep = 16
        try:
            bundles = sorted(
                e for e in os.listdir(root)
                if e.startswith("pm")
                and os.path.isdir(os.path.join(root, e))
            )
            import shutil

            for stale in bundles[:-keep] if keep > 0 else []:
                shutil.rmtree(os.path.join(root, stale),
                              ignore_errors=True)
        except OSError:
            pass


#: process flight-recorder singleton (built on first supervised /
#: fleet / watchdog-armed run; never from a pure read like /status)
_FLIGHT: Optional[FlightRecorder] = None
_FLIGHT_LOCK = threading.Lock()


def flight_recorder(workdir: Optional[str] = None) -> FlightRecorder:
    """The process flight recorder (created on first call).  ``workdir``
    (when given) becomes the bundle destination for subsequent dumps."""
    global _FLIGHT
    with _FLIGHT_LOCK:
        if _FLIGHT is None:
            _FLIGHT = FlightRecorder()
    if workdir is not None:
        _FLIGHT.set_workdir(workdir)
    return _FLIGHT


def last_postmortem() -> Optional[Dict[str, Any]]:
    """{path, trigger, ts} of the most recent bundle this process wrote
    (None if none) — surfaced as ``/status.last_postmortem``.  A pure
    peek: never creates the recorder."""
    rec = _FLIGHT
    return rec.last_postmortem() if rec is not None else None


def peek_flight_recorder() -> Optional[FlightRecorder]:
    """The process flight recorder IF one exists (None otherwise) — for
    layers that should dump forensics when a supervised/fleet run armed
    the recorder but must never create it from an unsupervised read
    (the health warning engine's severity>=error dumps)."""
    return _FLIGHT


def summarize_trace(events: List[Dict[str, Any]], run: Optional[int] = None
                    ) -> Dict[str, Any]:
    """Aggregate one run's events into the phase/health summary that
    `tools/trace_report.py` renders and `bench.py` logs.

    ``run=None`` picks the LAST run in the trace (the timed pass when a
    compile pass precedes it).  ``restarts`` counts the supervised-restart
    chain LEADING TO the selected run: the supervisor stamps each restart
    with the FAILED attempt's run ordinal, so the successful final run
    never contains one — the count walks back through contiguous
    predecessor runs that carry restart events (run N-1 restarted into
    run N), which reconstructs the selected run's supervision story
    without absorbing restarts from unrelated earlier sessions appended
    to the same file.  Returns::

        {"run": int, "meta": {...run_start fields...},
         "wall_s": float | None,          # run_end dur, else event span
         "phases": {name: {"count": n, "total_s": s}},
         "health": {"mean_accept", "num_divergent", "max_rhat", "min_ess",
                    "step_size", ...last-seen values...;
                    "num_divergent" is cumulative-with-reset across the
                    selected run's supervised restart chain (matching the
                    metrics counters), and "warnings"/"warning_counts"
                    aggregate health_warning events (stark_tpu.health) —
                    absent on pre-PR-15 / STARK_HEALTH=0 traces},
         "overlap": {"t_host_hidden_s", "device_idle_s", "t_wait_s",
                     "device_idle_frac"} | {},   # block-pipeline totals,
                                                 # when the writer emitted
                                                 # the overlap fields
         "diag": {"stream_diag", "bytes_last", "bytes_max", "bytes_total",
                  "ess_forecast_last", "adaptive_blocks",
                  "overshoot_draws"} | {},       # streaming-diagnostics /
                                                 # adaptive-scheduler
                                                 # accounting, when emitted
         "fleet": {"problems", "blocks", "occupancy_last", "active_last",
                   "batch_last", "grad_evals", "problems_converged",
                   "problems_budget_exhausted", "problems_quarantined",
                   "lane_reseeds", "degraded",
                   "lost_problems",
                   "lost_shards", "feed_rejects",
                   "compactions",
                   "admissions", "slot_recycles", "queue_depth_last",
                   "warmstarted",
                   "warmup_draws_saved",
                   "shards",
                   "shard_occupancy_last"} | {}, # fleet-sampling events
                                                 # (stark_tpu.fleet), when
                                                 # the run emitted them —
                                                 # the admission keys only
                                                 # on streaming/slot runs
         "nutssched": {"ragged", "occupancy_last", "occupancy_min",
                       "occupancy_mean", "blocks",
                       "sched_iters_total"} | {},  # ragged-NUTS lane
                                                 # occupancy (STARK_RAGGED_
                                                 # NUTS), when emitted
         "comms": {"calls", "payload_bytes", "wire_bytes",
                   "host_blocked_s", "by_primitive",
                   "straggler_ratio_last", "straggler_shard_last",
                   "shards"} | {},               # communication
                                                 # observatory (``comm``
                                                 # events + fleet_block
                                                 # shard walls) — absent
                                                 # on pre-PR-16 /
                                                 # STARK_COMM_TELEMETRY=0
                                                 # traces
         "other": {event: count},               # events outside
                                                 # ALL_EVENT_TYPES —
                                                 # future families degrade
                                                 # visibly, never silently
         "restarts": int, "events": int}

    ``overlap`` aggregates the runner's pipelined ``sample_block``
    accounting: total host work hidden behind device compute, total
    device idle (both from the blocks' completion stamps), total host
    wait, and the idle fraction
    (device_idle_s / total sample_block time — 0.0 when the device never
    starved).

    ``diag`` aggregates the convergence-gate transfer accounting
    (``diag_bytes_to_host`` per ``sample_block``: the ESS row and the
    draw counts with streaming diagnostics on, constant; growing
    O(draws*k) under the legacy full-history gate), the last ESS
    forecast (predicted draws-per-chain
    to reach the ESS target), and ``run_end``'s ``overshoot_draws``.

    ``nutssched`` aggregates the step-synchronized NUTS scheduler's
    lane-occupancy fields (``lane_occupancy`` / ``sched_iters`` on
    ``sample_block`` and ``fleet_block`` events — useful gradient
    evaluations over the max-lane iterations x lanes the batched loop
    executed); present only on STARK_RAGGED_NUTS runs.
    """
    restarts_by_run: Dict[int, int] = {}
    for e in events:
        if e.get("event") == "chain_health" and e.get("status") == "restart":
            r = e.get("run", 0)
            restarts_by_run[r] = restarts_by_run.get(r, 0) + 1
    runs = sorted({e.get("run", 0) for e in events})
    if not runs:
        return {"run": 0, "meta": {}, "wall_s": None, "phases": {},
                "health": {}, "overlap": {}, "diag": {}, "fleet": {},
                "nutssched": {}, "comms": {}, "other": {},
                "restarts": 0, "events": 0}
    run = runs[-1] if run is None else run
    evs = [e for e in events if e.get("run", 0) == run]
    # restart chain: the selected run's own restarts (it may itself be a
    # failed attempt) plus those of contiguous failed predecessors
    restarts_total = restarts_by_run.get(run, 0)
    r = run - 1
    while r in restarts_by_run:
        restarts_total += restarts_by_run[r]
        r -= 1
    chain_runs = set(range(r + 1, run + 1))
    # health.num_divergent: CUMULATIVE-WITH-RESET over the supervised
    # restart chain, matching the monotone metrics counters.  Each
    # attempt's per-block records carry a within-attempt cumulative
    # count (the run's LAST qualifying value is its final count;
    # run_end's num_divergent, when present, is authoritative — it also
    # covers paths like consensus whose per-block events are per-SHARD
    # partial counts, which are excluded below).  Attempt boundaries
    # come from run_start's ``resuming`` flag: a checkpoint-RESUMED
    # attempt restored its counter and continues the chain's number (no
    # double count — its own final value already spans the whole run),
    # while a cold retry restarts from zero, so the failed attempt's
    # final count is banked first.  The old code took the LATEST
    # event's value, silently dropping every cold attempt's
    # divergences.  Warmup counts (chain_health status="warmup_done")
    # and shard/replica-tagged partials stay out, as before.
    per_run_last: Dict[int, Any] = {}
    per_run_resuming: Dict[int, bool] = {}
    for e in events:
        e_run = e.get("run", 0)
        if e_run not in chain_runs:
            continue
        ev_name = e.get("event")
        if "shard" in e or "replica" in e:
            continue  # per-shard/rung partial counts, not run totals
        if ev_name == "run_start":
            per_run_resuming[e_run] = bool(e.get("resuming"))
        elif (
            ev_name in ("sample_block", "run_end")
            or (ev_name == "chain_health" and e.get("status") is None)
        ):
            v = e.get("num_divergent")
            if v is not None:
                per_run_last[e_run] = v
    div_total = None
    if per_run_last:
        banked, last = 0, None
        for rr in sorted(chain_runs):
            if rr not in per_run_last:
                continue
            if last is not None and not per_run_resuming.get(rr, False):
                banked += last  # cold retry: bank the failed attempt
            last = per_run_last[rr]
        div_total = banked + last

    meta: Dict[str, Any] = {}
    phases: Dict[str, Dict[str, float]] = {}
    health: Dict[str, Any] = {}
    overlap: Dict[str, float] = {}
    diag: Dict[str, Any] = {}
    fleet: Dict[str, Any] = {}
    nutssched: Dict[str, Any] = {}
    comms: Dict[str, Any] = {}
    other: Dict[str, int] = {}
    occ_sum = 0.0
    saw_overlap = False
    wall = None
    accepts: List[float] = []
    warn_counts: Dict[str, int] = {}
    for e in evs:
        ev = e["event"]
        if (
            ev in ("sample_block", "fleet_block")
            and e.get("lane_occupancy") is not None
        ):
            occ = float(e["lane_occupancy"])
            nutssched["ragged"] = bool(e.get("ragged_nuts", True))
            nutssched["occupancy_last"] = occ
            nutssched["occupancy_min"] = min(
                nutssched.get("occupancy_min", occ), occ
            )
            nutssched["blocks"] = nutssched.get("blocks", 0) + 1
            occ_sum += occ
            if e.get("sched_iters") is not None:
                nutssched["sched_iters_total"] = (
                    nutssched.get("sched_iters_total", 0)
                    + int(e["sched_iters"])
                )
        if ev == "fleet_block":
            fleet["blocks"] = fleet.get("blocks", 0) + 1
            if e.get("occupancy") is not None:
                fleet["occupancy_last"] = e["occupancy"]
            # mesh-parallel fleet (STARK_FLEET_MESH): shard count and the
            # latest per-shard occupancy — absent (not 0) off-mesh and on
            # pre-PR-14 traces
            if e.get("shards") is not None:
                fleet["shards"] = int(e["shards"])
            if e.get("shard_occupancy") is not None:
                fleet["shard_occupancy_last"] = e["shard_occupancy"]
            if e.get("active") is not None:
                fleet["active_last"] = e["active"]
            if e.get("batch") is not None:
                fleet["batch_last"] = e["batch"]
            if e.get("block_grad_evals") is not None:
                fleet["grad_evals"] = (
                    fleet.get("grad_evals", 0) + int(e["block_grad_evals"])
                )
            if e.get("queue_depth") is not None:
                fleet["queue_depth_last"] = int(e["queue_depth"])
            # shard-imbalance trail (PR 16): per-shard host walls ride
            # mesh + STARK_COMM_TELEMETRY runs only — absent (not 0) on
            # everything else, the null-not-0.0 rule
            if e.get("straggler_ratio") is not None:
                comms["straggler_ratio_last"] = float(e["straggler_ratio"])
            if e.get("straggler_shard") is not None:
                comms["straggler_shard_last"] = int(e["straggler_shard"])
            if e.get("shard_walls") is not None:
                comms["shards"] = len(e["shard_walls"])
        elif ev == "problem_converged":
            key = (
                "problems_converged"
                if e.get("status", "converged") == "converged"
                else "problems_budget_exhausted"
            )
            fleet[key] = fleet.get(key, 0) + 1
        elif ev == "problem_reseeded":
            fleet["lane_reseeds"] = fleet.get("lane_reseeds", 0) + 1
        elif ev == "problem_quarantined":
            fleet["problems_quarantined"] = (
                fleet.get("problems_quarantined", 0) + 1
            )
            fleet.setdefault("lost_problems", []).append(
                e.get("problem_id")
            )
        elif ev == "shard_lost":
            # the mesh fleet's shard deadman fired (PR 17): absent (not
            # []) on traces that never lost a shard
            fleet.setdefault("lost_shards", []).append(e.get("shard"))
        elif ev == "feed_reject":
            fleet["feed_rejects"] = fleet.get("feed_rejects", 0) + 1
        elif ev == "fleet_compact":
            fleet["compactions"] = fleet.get("compactions", 0) + 1
            if e.get("pending") is not None:
                fleet["queue_depth_last"] = int(e["pending"])
        elif ev == "slot_recycled":
            fleet["slot_recycles"] = fleet.get("slot_recycles", 0) + 1
        elif ev == "problem_admitted":
            fleet["admissions"] = fleet.get("admissions", 0) + 1
            if e.get("queue_depth") is not None:
                fleet["queue_depth_last"] = int(e["queue_depth"])
            if e.get("warmstart"):
                fleet["warmstarted"] = fleet.get("warmstarted", 0) + 1
            if e.get("warmup_draws_saved"):
                fleet["warmup_draws_saved"] = (
                    fleet.get("warmup_draws_saved", 0)
                    + int(e["warmup_draws_saved"])
                )
        elif ev == "run_start" and e.get("problems") is not None:
            fleet["problems"] = e["problems"]
        elif ev == "run_end" and e.get("degraded") is not None and (
            fleet or e.get("problems") is not None
        ):
            fleet["degraded"] = bool(e["degraded"])
            if e.get("lost_shards"):
                fleet["lost_shards"] = list(e["lost_shards"])
            if e.get("problems") is not None:
                # the FINAL problem count: a streamed (FleetFeed) run
                # ends with more problems than run_start announced
                fleet["problems"] = e["problems"]
        if ev == "sample_block":
            for k in ("t_host_hidden_s", "device_idle_s", "t_wait_s"):
                if e.get(k) is not None:
                    saw_overlap = True
                    overlap[k] = overlap.get(k, 0.0) + float(e[k])
            if e.get("diag_bytes_to_host") is not None:
                b = int(e["diag_bytes_to_host"])
                diag["bytes_last"] = b
                diag["bytes_max"] = max(diag.get("bytes_max", 0), b)
                diag["bytes_total"] = diag.get("bytes_total", 0) + b
            if e.get("stream_diag") is not None:
                diag["stream_diag"] = bool(e["stream_diag"])
            if e.get("ess_forecast") is not None:
                diag["ess_forecast_last"] = e["ess_forecast"]
        elif ev == "run_end":
            for k in ("overshoot_draws", "adaptive_blocks"):
                if e.get(k) is not None:
                    diag[k] = e[k]
        if ev == "run_start":
            meta = {
                k: v for k, v in e.items()
                if k not in ENVELOPE_KEYS
            }
        elif ev == "run_end":
            wall = e.get("dur_s", wall)
        if "dur_s" in e and ev in PHASE_EVENTS:
            p = phases.setdefault(ev, {"count": 0, "total_s": 0.0})
            p["count"] += 1
            p["total_s"] += float(e["dur_s"])
        if ev == "chain_health":
            for k in ("max_rhat", "min_ess", "step_size", "min_ess_per_grad",
                      "num_stuck_components", "draws_per_chain"):
                if e.get(k) is not None:
                    health[k] = e[k]
            if e.get("mean_accept") is not None:
                accepts.append(float(e["mean_accept"]))
        # blocks may carry accept/divergence inline (monolithic runs)
        elif ev in ("sample_block", "warmup_block"):
            if e.get("mean_accept") is not None:
                accepts.append(float(e["mean_accept"]))
        elif ev == "health_warning":
            # statistical-health observatory (stark_tpu.health): count
            # warning emissions by taxonomy name — absent (not 0) on
            # pre-PR-15 / STARK_HEALTH=0 traces, the null-not-0.0 rule
            name = str(e.get("warning", "unknown"))
            warn_counts[name] = warn_counts.get(name, 0) + 1
        elif ev == "comm":
            # communication observatory (parallel.primitives): roll the
            # per-collective accounting up by primitive kind
            comms["calls"] = comms.get("calls", 0) + 1
            comms["payload_bytes"] = (
                comms.get("payload_bytes", 0) + int(e.get("payload_bytes", 0))
            )
            comms["wire_bytes"] = (
                comms.get("wire_bytes", 0) + int(e.get("wire_bytes", 0))
            )
            comms["host_blocked_s"] = round(
                comms.get("host_blocked_s", 0.0)
                + float(e.get("host_blocked_s", 0.0)),
                6,
            )
            prim = str(e.get("primitive", "unknown"))
            by = comms.setdefault("by_primitive", {}).setdefault(
                prim, {"calls": 0, "wire_bytes": 0}
            )
            by["calls"] += 1
            by["wire_bytes"] += int(e.get("wire_bytes", 0))
        if ev not in ALL_EVENT_TYPES:
            # forward-compat: an event family this build predates still
            # shows up in the rollup instead of silently vanishing
            other[ev] = other.get(ev, 0) + 1
    if accepts:
        health["mean_accept"] = sum(accepts) / len(accepts)
    if div_total is not None:
        health["num_divergent"] = div_total
    if warn_counts:
        health["warnings"] = int(sum(warn_counts.values()))
        health["warning_counts"] = dict(sorted(warn_counts.items()))
    if wall is None and evs:
        wall = evs[-1]["wall_s"] - evs[0]["wall_s"]
    if saw_overlap:
        # idle fraction over the whole BLOCK-LOOP time: sample_block durs
        # exclude checkpoint time (each checkpoint has its own phase
        # event, so phase durations tile the wall without double
        # counting), but the per-block idle attribution covers the full
        # host cycle INCLUDING checkpoints — the denominator must too, or
        # checkpoint-heavy serial runs would report fractions above 1
        loop_total = (
            phases.get("sample_block", {}).get("total_s", 0.0)
            + phases.get("checkpoint", {}).get("total_s", 0.0)
        )
        overlap = {k: round(v, 4) for k, v in overlap.items()}
        overlap["device_idle_frac"] = round(
            min(overlap.get("device_idle_s", 0.0) / loop_total, 1.0)
            if loop_total > 0
            else 0.0,
            4,
        )
    if nutssched.get("blocks"):
        nutssched["occupancy_mean"] = round(
            occ_sum / nutssched["blocks"], 4
        )
    return {
        "run": run,
        "meta": meta,
        "wall_s": wall,
        "phases": {
            k: {"count": int(v["count"]), "total_s": round(v["total_s"], 4)}
            for k, v in phases.items()
        },
        "health": health,
        "overlap": overlap if saw_overlap else {},
        "diag": diag,
        "fleet": fleet,
        "nutssched": nutssched,
        "comms": comms,
        "other": other,
        "restarts": restarts_total,
        "events": len(evs),
    }
