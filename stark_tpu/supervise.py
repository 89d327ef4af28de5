"""Failure detection + supervised auto-restart (SURVEY.md §6).

The reference's failure story is Spark task retry (SURVEY.md §6, INFERRED);
the TPU-native equivalent is checkpoint-based restart: the adaptive runner
checkpoints the full chain state every draw block (one atomic .npz), and
this module supervises a run — detecting failures and restarting from the
last *healthy* checkpoint, or from scratch when no healthy checkpoint
exists.

Fault taxonomy (`classify_fault` — every restart record and trace event
carries the class):

  * ``transient``          — process/device faults: any exception out of
    the run (XLA error, TPU runtime fault, preemption surfacing as a crash)
    → restart from the latest valid checkpoint, with exponential backoff.
  * ``poisoned_state``     — non-finite sampler state detected by the
    runner's per-block health check BEFORE checkpointing (a poisoned state
    never lands on disk) → `ChainHealthError` → immediate restart with a
    fresh seed (no backoff: the fault is numerical, not environmental).
  * ``corrupt_checkpoint`` — a checkpoint that fails to load or contains
    non-finite state is quarantined (with the REASON logged and traced)
    and the run cold-starts.
  * ``stall``              — no progress beat within ``stall_timeout_s``:
    the `watchdog.Watchdog` aborts the attempt (`StallError`) and the
    supervisor restarts from the last checkpoint.
  * ``restart_budget_exhausted`` — the restart-rate window overflowed; the
    final fault is re-raised to the caller.
  * ``shard_lost``          — fleet-only (stark_tpu.fleet): the mesh shard
    a problem's lane lived on was declared dead by the shard deadman
    (``STARK_SHARD_DEADLINE``); the victim cold-restarts against its
    EXISTING per-problem budget on the shrunk mesh, and past the budget
    quarantines terminally as ``failed:shard_lost``.

Restart discipline: failures are recorded in a sliding `RestartBudget`
(``max_restarts`` within ``restart_window_s``; an infinite window — the
default — reproduces the old lifetime counter), and each restart waits
``backoff_base_s * 2^(attempt-1)`` seconds with deterministic jitter,
capped at ``backoff_cap_s`` (base 0 — the default — keeps restarts
immediate, matching historical behavior; production configs set a base).

Every fault shape above is injectable on demand via `faults` (see the
``chaos-drill`` CLI subcommand / `chaos.run_drill` for the scripted
scenario matrix).

Elastic re-sharding (changing the device mesh mid-run) is a documented
non-goal for v1 — restart-from-checkpoint onto the new topology covers the
preemption story without it (DESIGN.md §6).
"""

from __future__ import annotations

import json
import logging
import os
import random
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from . import lineage, telemetry
from .checkpoint import load_checkpoint
from .faults import fail_point
from .model import Model
from .watchdog import StallError, Watchdog

log = logging.getLogger("stark_tpu.supervise")

__all__ = [
    "ChainHealthError",
    "RestartBudget",
    "agree_resume",
    "backoff_delay",
    "check_finite_state",
    "checkpoint_health",
    "checkpoint_is_healthy",
    "classify_fault",
    "quarantine_path",
    "supervised_sample",
]

#: fault-class names (the taxonomy every restart record/trace event uses)
FAULT_TRANSIENT = "transient"
FAULT_POISONED = "poisoned_state"
FAULT_CORRUPT = "corrupt_checkpoint"
FAULT_STALL = "stall"


class ChainHealthError(RuntimeError):
    """Sampler state went non-finite (detected before checkpointing)."""


_HEALTH_KEYS = (
    "z", "pe", "grad", "step_size", "inv_mass",
    # chees warmup-phase checkpoints carry adaptation state whose
    # poisoning would otherwise survive the position/grad check and be
    # resumed on every restart (keys absent from other checkpoints are
    # simply skipped)
    "log_T", "da_log_step", "da_h_avg", "adam_m", "adam_v",
    "wf_mean", "wf_m2",
)


def check_finite_state(arrays: Dict[str, Any]) -> None:
    """Raise ChainHealthError if any monitored state array is non-finite.

    ``grad`` here is the CARRIED gradient of the accepted state — it seeds
    the next transition's first leapfrog half-step, so a non-finite value
    poisons every resume from this state (unlike a transient inf at a
    rejected proposal, which is legal and never carried).
    """
    for name in _HEALTH_KEYS:
        if name not in arrays:
            continue
        a = np.asarray(arrays[name])
        if not np.all(np.isfinite(a)):
            bad = int(a.size - np.sum(np.isfinite(a)))
            raise ChainHealthError(
                f"non-finite sampler state: {bad}/{a.size} entries of {name!r}"
            )


def checkpoint_health(path: str) -> Tuple[bool, Optional[str]]:
    """(healthy, reason) for a checkpoint file.

    ``reason`` (None when healthy) is "<fault class>: <detail>" — the
    WHY a checkpoint is about to be quarantined, so discards are never
    silent (they are logged and traced by the supervisor).
    """
    try:
        arrays, _ = load_checkpoint(path)
    except Exception as e:  # noqa: BLE001 — unreadable file = corrupt
        return False, f"{FAULT_CORRUPT}: {type(e).__name__}: {e}"
    try:
        check_finite_state(arrays)
    except ChainHealthError as e:
        return False, f"{FAULT_POISONED}: {e}"
    return True, None


def checkpoint_is_healthy(path: str) -> bool:
    """True iff the checkpoint loads and its state arrays are finite."""
    return checkpoint_health(path)[0]


def classify_fault(exc: BaseException) -> str:
    """Map an exception out of an attempt to its fault class."""
    if isinstance(exc, ChainHealthError):
        return FAULT_POISONED
    if isinstance(exc, StallError):
        return FAULT_STALL
    return FAULT_TRANSIENT


def backoff_delay(
    fault: str,
    attempt: int,
    *,
    base_s: float,
    cap_s: float = 60.0,
    seed: int = 0,
) -> float:
    """Exponential backoff with deterministic jitter for restart ``attempt``.

    ``base_s * 2^(attempt-1)`` scaled by a jitter in [0.5, 1.5) derived
    from (seed, attempt) — deterministic per run so drills reproduce,
    decorrelated across seeds so a fleet of supervised runs restarting
    off the same shared-filesystem hiccup doesn't thundering-herd — and
    the RESULT capped at ``cap_s`` (the cap is the contract an operator
    sizes budgets around, so jitter stays inside it).  Poisoned state
    skips backoff entirely: the fault is numerical, the fix is the
    reseed, and waiting buys nothing.
    """
    if base_s <= 0 or fault == FAULT_POISONED:
        return 0.0
    jitter = 0.5 + random.Random(f"{seed}:{attempt}").random()
    return min(cap_s, base_s * 2.0 ** max(attempt - 1, 0) * jitter)


class RestartBudget:
    """Sliding-window restart-rate limit (replaces the bare counter).

    Allows at most ``max_restarts`` failures inside any ``window_s``-second
    window; ``window_s=None`` (default) never forgets — exactly the old
    lifetime ``max_restarts`` semantics.  A finite window is the crash-loop
    detector for long runs: three preemptions across a day is routine,
    three faults in two minutes is a broken build.
    """

    def __init__(self, max_restarts: int, window_s: Optional[float] = None):
        self.max_restarts = int(max_restarts)
        self.window_s = window_s
        self._times: List[float] = []

    def record_failure(self, now: Optional[float] = None) -> None:
        self._times.append(time.monotonic() if now is None else now)

    def in_window(self, now: Optional[float] = None) -> int:
        now = time.monotonic() if now is None else now
        if self.window_s is not None:
            self._times = [t for t in self._times if now - t <= self.window_s]
        return len(self._times)

    def exhausted(self, now: Optional[float] = None) -> bool:
        """True when the CURRENT window holds more failures than allowed
        restarts (the n-th failure is terminal once n > max_restarts)."""
        return self.in_window(now) > self.max_restarts


def quarantine_path(path: str, reason: Optional[str] = None) -> str:
    """Move a bad artifact aside as ``path.bad`` / ``path.badN``:
    numbered suffixes so a second quarantine in the same workdir never
    overwrites the forensic copy of an earlier failure.

    ``reason`` (optional) is persisted next to the forensic copy as
    ``<dst>.reason.json`` — the fleet's per-problem quarantines use it so
    WHY an artifact was discarded survives the process that discarded it
    (the log and trace carry it too, but those are per-run).  Returns the
    destination path."""
    dst = path + ".bad"
    n = 1
    while os.path.exists(dst):
        n += 1
        dst = f"{path}.bad{n}"
    os.replace(path, dst)
    if reason is not None:
        try:
            with open(dst + ".reason.json", "w") as f:
                json.dump(
                    {"path": path, "quarantined_as": dst,
                     "reason": reason, "ts": time.time()},
                    f,
                )
                f.write("\n")
        except OSError as e:  # noqa: PERF203 — forensics are best-effort
            log.warning("could not persist quarantine reason for %s: %s",
                        dst, e)
    return dst


def _ranks_agree(all_done) -> bool:
    """True iff every rank reported a healthy checkpoint at the SAME
    (phase, progress) — the resume-consistency rule for multi-process
    supervision (see `agree_resume`)."""
    a = np.asarray(all_done).reshape(-1, 2)
    return bool((a[:, 0] >= 0).all() and (a == a[0]).all())


def agree_resume(
    resume: Optional[str],
    *,
    quarantine: Callable[[str], None],
    trace=None,
) -> Optional[str]:
    """Cross-rank agreement on resume-vs-cold-start (multi-process).

    Each rank reads only ITS per-rank checkpoint; a kill between two
    ranks' checkpoint renames (atomic per file, not across ranks)
    leaves blocks_done skewed by one, and skewed resumes would issue
    different numbers of collective-bearing blocks — the pod then
    hangs on an unmatched allgather.  Rule: resume ONLY when every
    rank holds a healthy checkpoint with the SAME blocks_done;
    otherwise all ranks cold-start in lockstep.  The skew window is
    one checkpoint rename per block, so losing it costs (rarely) one
    attempt's progress, never correctness.
    """
    import jax

    if jax.process_count() == 1:
        return resume

    trace = telemetry.resolve_trace(trace)
    # (phase, progress): warmup checkpoints count warm_done segments,
    # sample-phase ones count blocks_done — compare both so a
    # warmup-2 file never falsely agrees with a blocks-2 one
    done = (-1, -1)
    if resume is not None:
        try:
            _, meta = load_checkpoint(resume)
            warm = meta.get("phase") == "warmup"
            done = (
                0 if warm else 1,
                int(meta["warm_done"] if warm
                    else meta.get("blocks_done", 0)),
            )
        except Exception:  # noqa: BLE001 — unreadable: treat as cold
            done = (-1, -1)
    from .parallel.primitives import gather_tree

    all_done = gather_tree(np.array(done), tiled=False)
    if _ranks_agree(all_done):
        return resume
    if resume is not None:
        # healthy but unusable (a peer is cold or skewed): quarantine
        # so the stale state can't mix into the cold restart
        log.warning(
            "quarantining %s: ranks disagree on resume point %s "
            "(cold-starting in lockstep)", resume, np.asarray(all_done).tolist(),
        )
        if trace.enabled:
            trace.emit(
                "chain_health", status="quarantine", path=resume,
                reason="rank resume-point skew",
            )
        quarantine(resume)
    return None


def _append_record(path: str, rec: Dict[str, Any]) -> None:
    """Append one JSONL record, flushed AND fsynced — a restart record
    documents a crash, so it must survive the crash (and the host dying
    right after) that it documents."""
    with open(path, "a") as f:
        f.write(json.dumps(rec) + "\n")
        f.flush()
        os.fsync(f.fileno())


def supervised_sample(
    model: Model,
    data: Any = None,
    *,
    workdir: str,
    max_restarts: int = 3,
    restart_window_s: Optional[float] = None,
    backoff_base_s: float = 0.0,
    backoff_cap_s: float = 60.0,
    stall_timeout_s: Optional[float] = None,
    seed: int = 0,
    reseed_on_restart: bool = True,
    trace=None,
    _runner=None,
    **kwargs,
):
    """Run ``sample_until_converged`` under supervision.

    Checkpoints, draw store, and metrics all live under ``workdir``; on any
    failure the run restarts from the last healthy checkpoint (or from
    scratch if none).  Each restart is logged as a ``{"event": "restart",
    "fault": <class>, ...}`` line in the metrics JSONL — the observable
    failure-detection record — and restarts are bounded by a
    `RestartBudget` (``max_restarts`` failures within ``restart_window_s``;
    the default infinite window is the historical lifetime counter) with
    `backoff_delay` pauses between attempts.

    ``stall_timeout_s`` arms a `watchdog.Watchdog` around every attempt: an
    attempt that stops emitting progress beats (draw blocks, warmup
    segments, in-scan heartbeats) for that long is aborted (`StallError`)
    and restarted like any other fault.  Pick it LARGER than the worst
    single dispatch including compile — beats only flow between
    dispatches.  A genuine Ctrl-C is never converted: only an interrupt
    the watchdog itself fired counts as a stall.

    ``trace`` (default: the ambient `telemetry` trace): ONE RunTrace spans
    every attempt — each attempt emits its own run envelope, and restarts
    appear between them as ``chain_health`` events with
    ``status="restart"`` plus the fault class, so a trace file reads as
    the complete supervision story.

    The runner's asynchronous block pipeline composes with supervision
    unchanged: a fault with block k+1 in flight discards that block (its
    draws never reached the host), the restart resumes block k's
    checkpoint, and the runner's resume reconciliation truncates any draw
    store rows the checkpoint doesn't account for — so the replayed block
    k+1 is bit-identical to what the serial loop would have produced.
    Restart attempts also reuse the persistent compilation cache enabled
    here (`platform.enable_compilation_cache`), so they skip the re-jit
    of every segment.

    Returns the AdaptiveResult of the first successful attempt.

    ``_runner`` (internal): the attempt callable — defaults to
    `runner.sample_until_converged`; `fleet.supervised_sample_fleet`
    plugs in the fleet runner so the SAME restart budget / fault
    taxonomy / watchdog / checkpoint-health machinery supervises a
    many-problem fleet (its checkpoints carry the surviving active set).
    """
    from .runner import sample_until_converged

    if _runner is None:
        _runner = sample_until_converged
    trace = telemetry.resolve_trace(trace)

    # a wall-clock budget is an absolute deadline across ALL attempts — a
    # crash at 80% of the budget leaves the retry only the remaining 20%,
    # never a fresh full budget (the caller's capture window doesn't reset)
    time_budget_s = kwargs.pop("time_budget_s", None)
    deadline = (
        time.monotonic() + time_budget_s if time_budget_s is not None else None
    )

    os.makedirs(workdir, exist_ok=True)
    # persistent XLA compilation cache: every restart attempt builds a
    # fresh backend and would otherwise re-pay the full jit of warmup
    # segments + draw blocks; later runs of the same programs hit it too
    from .platform import enable_compilation_cache

    enable_compilation_cache()
    # per-process file names on multi-process meshes (idempotent — the
    # runner applies the same mapping to whatever paths it receives, so
    # supervisor-side health checks and runner-side writes agree)
    from .checkpoint import rank_path

    ckpt_path = rank_path(os.path.join(workdir, "chain.ckpt.npz"))
    metrics_path = rank_path(
        kwargs.pop("metrics_path", os.path.join(workdir, "metrics.jsonl"))
    )
    kwargs.setdefault("draw_store_path", os.path.join(workdir, "draws.stkr"))
    kwargs["draw_store_path"] = rank_path(kwargs["draw_store_path"])
    kwargs.setdefault("health_check", True)

    store_path = kwargs.get("draw_store_path")
    budget = RestartBudget(max_restarts, restart_window_s)

    # postmortem flight recorder: capture the run's recent events for
    # the duration of supervision and dump a forensic bundle into the
    # workdir on every restart (on_failure) / stall (watchdog) — scoped
    # install so the zero-listener contract holds outside runs
    recorder = telemetry.flight_recorder(workdir)
    recorder.install()

    # lineage: ONE ambient job for the whole supervision (every restart
    # attempt, every supervisor-side quarantine/restart event correlates
    # to the same id — minted deterministically from model/seed, so the
    # runner's own minting agrees and a process-crash resume re-mints
    # the same id).  Entered manually so the existing try/finally
    # structure stays put; no-op with STARK_LINEAGE=0.
    _job_cm = None
    if lineage.enabled():
        _job_cm = lineage.use_job(
            lineage.current_job() or lineage.mint_job_id(
                getattr(model, "tag", type(model).__name__), int(seed)
            )
        )
        _job_cm.__enter__()

    attempt = 0

    def on_failure(e: BaseException, fault: str, resumed: bool) -> None:
        """Record one failed attempt; re-raise when the budget is gone,
        otherwise back off and let the loop retry."""
        nonlocal attempt
        attempt += 1
        budget.record_failure()
        exhausted = budget.exhausted()
        delay = (
            0.0 if exhausted
            else backoff_delay(
                fault, attempt,
                base_s=backoff_base_s, cap_s=backoff_cap_s, seed=seed,
            )
        )
        rec = {
            "event": "restart",
            "attempt": attempt,
            "fault": fault,
            "error": f"{type(e).__name__}: {e}",
            "resumed_from_checkpoint": resumed,
            "backoff_s": round(delay, 3),
            "ts": time.time(),
        }
        log.warning(
            "attempt %d failed (%s): %s — %s", attempt, fault, e,
            "restart budget exhausted" if exhausted
            else f"restarting in {delay:.2f}s",
        )
        if metrics_path:  # caller may disable metrics with None
            _append_record(metrics_path, rec)
        # the failure-detection record, in the trace's vocabulary:
        # a chain-health transition, not a new run.  Budget state
        # rides along so live observers (/status, /metrics) can show
        # how much supervision headroom remains without re-deriving
        # the sliding window from the restart history.
        # the restart documents a crash: the flight recorder dumps the
        # postmortem bundle (recent events + snapshots) into workdir
        # whether or not tracing was on
        recorder.record_anomaly(
            f"restart:{fault}",
            trace,
            "chain_health",
            status="restart",
            attempt=attempt,
            fault=fault,
            error=f"{type(e).__name__}: {e}",
            resumed_from_checkpoint=resumed,
            backoff_s=round(delay, 3),
            restarts_in_window=budget.in_window(),
            max_restarts=budget.max_restarts,
        )
        if exhausted:
            recorder.record_anomaly(
                "restart_budget_exhausted",
                trace,
                "chain_health",
                status="restart_budget_exhausted",
                restarts_in_window=budget.in_window(),
                window_s=restart_window_s,
            )
            raise e
        if delay > 0:
            time.sleep(delay)

    try:
        while True:
            fail_point("supervise.attempt")
            resume: Optional[str] = None
            if os.path.exists(ckpt_path):
                healthy, reason = checkpoint_health(ckpt_path)
                if healthy:
                    resume = ckpt_path
                else:
                    # corrupt/poisoned checkpoint: quarantine it (keeping the
                    # forensic copy) and cold-start — NEVER silently: the
                    # reason lands in the log and the trace
                    log.warning("quarantining %s: %s", ckpt_path, reason)
                    if trace.enabled:
                        trace.emit(
                            "chain_health", status="quarantine",
                            path=ckpt_path, reason=reason,
                        )
                    quarantine_path(ckpt_path)
            resume = agree_resume(resume, quarantine=quarantine_path, trace=trace)
            if resume is None and store_path and os.path.exists(store_path):
                # cold start: draws persisted by a discarded run must not mix
                # into this run's store (a later resume reads the whole store)
                quarantine_path(store_path)
            wd: Optional[Watchdog] = None
            try:
                remaining = (
                    # floor at 1s: with the deadline already blown the attempt
                    # still runs (resuming its checkpoint) and the runner stops
                    # it at the first completed block — partial > nothing
                    max(deadline - time.monotonic(), 1.0)
                    if deadline is not None
                    else None
                )
                # ambient install: the runner and the drivers below it pick up
                # this supervisor's trace even though only ``trace=`` was given
                with telemetry.use_trace(trace):
                    if stall_timeout_s is not None:
                        wd = Watchdog(
                            stall_timeout_s, trace=trace, label="supervise"
                        ).start()
                    try:
                        return _runner(
                            model,
                            data,
                            seed=seed + attempt if reseed_on_restart else seed,
                            checkpoint_path=ckpt_path,
                            resume_from=resume,
                            metrics_path=metrics_path,
                            reseed=attempt if (attempt and reseed_on_restart) else None,
                            time_budget_s=remaining,
                            trace=trace,
                            **kwargs,
                        )
                    finally:
                        if wd is not None:
                            wd.stop()
            except KeyboardInterrupt:
                # ONLY a watchdog-fired interrupt is a stall; a user Ctrl-C
                # (no stall flag) propagates untouched — supervision must
                # never eat a genuine interrupt
                if wd is not None and wd.consume_stall():
                    e = StallError(
                        f"no progress beat within {stall_timeout_s}s "
                        "(watchdog aborted the attempt)"
                    )
                    on_failure(e, FAULT_STALL, resume is not None)
                else:
                    raise
            except Exception as e:  # noqa: BLE001 — supervision boundary
                on_failure(e, classify_fault(e), resume is not None)
    finally:
        recorder.uninstall()
        if _job_cm is not None:
            _job_cm.__exit__(None, None, None)
