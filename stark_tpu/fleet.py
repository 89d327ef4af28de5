"""Fleet sampling: one compiled, vmapped scan advances B independent
posteriors per device dispatch (ROADMAP item 2).

The tfp.mcmc paper (PAPERS.md) argues modern hardware wants thousands of
chains per dispatch; production traffic wants thousands of *posteriors* —
per-user / per-segment models with shared structure but different data.
The single-problem runner amortizes the host round-trip over one problem's
chains; at eight-schools scale (0.3 s wall) serving N small posteriors
sequentially pays the dispatch + host-loop overhead N times.  This module
vmaps the existing per-chain block scan (`sampler.make_block_runner`) and
warmup parts over a leading PROBLEM axis, so ONE dispatch advances the
whole fleet:

  * **Model contract** — a `FleetSpec` wraps one shared `Model` (same
    ``param_spec``/``log_prior``/``log_lik``) with a per-problem dataset
    list; data leaves are stacked along a new axis 0 AFTER the model's
    ``prepare_data`` layout hook runs per problem, so fused-layout models
    batch correctly.
  * **Kernel plumbing** — the NUTS/HMC block scan and the windowed warmup
    gain the problem axis via an outer ``jax.vmap``; step-size /
    mass-matrix adaptation state and the PR 4 `StreamDiagState` streaming
    diagnostics carry are per problem per chain (one more leading axis on
    the same layout).
  * **Ragged convergence** — the streaming ESS gate is evaluated PER
    PROBLEM; a problem that passes its full split-R-hat/ESS validation is
    masked out (its persisted draws are frozen, its gradient evaluations
    stop counting toward any budget) and lanes are COMPACTED out of the
    batch at a block boundary once occupancy drops below
    ``refill_occupancy`` — stragglers keep sampling in a smaller batch,
    and queued problems (``max_batch``) are warmed up and swapped in.
  * **Fleet-aware persistence/telemetry** — per-problem draw stores
    (`FleetDrawStore`), one fleet checkpoint carrying the active set,
    ``fleet_block`` / ``problem_converged`` / ``fleet_compact`` trace
    events, and per-problem fields in ``/status`` (stark_tpu.metrics).
  * **Per-problem fault domains** — the PROBLEM, not the fleet, is the
    unit of failure: the post-block finite scan runs per lane, a
    poisoned lane is reseeded in place (attempt-folded key) up to its
    `ProblemBudget.max_restarts`, then QUARANTINED (masked, artifacts
    quarantined with the reason, terminal ``failed:poisoned_state``)
    while the surviving B-1 lanes continue bit-identically; per-problem
    ``ess_target`` / ``deadline_s`` budgets close their own gates
    (``budget_exhausted``) without touching neighbors; and the fleet
    completes DEGRADED (`FleetResult.degraded` + ``lost_problems``)
    instead of dying with one tenant.  Whole-fleet restart — the PR 2
    supervisor — is reserved for process-level faults (crash, stall,
    corrupt fleet checkpoint).

Determinism contract: every problem owns an independent host-side PRNG
stream (``PRNGKey(seed + index)``) advanced with exactly the single-problem
runner's key discipline, and lanes of a vmapped batch are bit-identical to
the unbatched computation on the same backend — so a problem's draws do
not depend on which other problems share its batch, survive compaction /
refill / crash-resume unchanged, and a straggler reaches the SAME draws
as ``sample_until_converged(seed=seed+index, adaptive_blocks=False)``
(tests/test_fleet.py drills all three).

**Zero-recompile streaming (PR 13).**  Three additions on top:

  * **Fixed-capacity lane slots** (``STARK_FLEET_SLOTS=1``, default
    off): the compiled batch shape is pinned for the whole run — no
    compaction; a terminal lane's slot is handed to a queued problem IN
    PLACE (state/diag/data scattered, warmup padded to full batch
    width so the compiled warmup is reused too), so steady-state churn
    triggers zero batched-scan re-specializations after the first
    compile.  Knob-off preserves the compaction path bit-identically —
    except the PR 13 top-up bugfix: the legacy path now admits queued
    problems into masked slots in place when riding at/above
    ``refill_occupancy`` instead of stranding the queue.
  * **Streaming admission** (`FleetFeed`): ``feed.submit`` hands
    problems to a RUNNING fleet (thread-safe, consumed at block
    boundaries, ``seed + arrival-index`` streams, queue persisted in
    the fleet checkpoint so crash-resume replays admissions
    bit-identically) — `sample_fleet` becomes a long-lived serving
    loop, the ROADMAP item 2 refill API under the item-1 control plane.
  * **Warm-start adaptation transfer** (``STARK_FLEET_WARMSTART=1``,
    default off): admitted problems seed step size + mass diagonal
    from a finite-validated `DonorPool` of completed problems and run
    a short adapt-confirm warmup; the full split-R-hat/ESS validation
    still gates every stop.

**Device-parallel fleet (PR 14).**  ``STARK_FLEET_MESH=1`` (or
``sample_fleet(mesh=...)`` with a Mesh carrying a "problems" axis, env
default off and knob-off bit-identical) shards the PROBLEM axis over the
mesh via `parallel.primitives.map_shards`: every batched dispatch (warmup
init, warmup segments, the block scan) runs the same vmapped program on
each device's contiguous slice of the batch, so B problems span D devices
instead of one.  Problems are independent — the mapped program contains
no collective — and per-lane draws are bit-identical to the single-device
fleet (batch-composition independence is the drilled contract that makes
the device split free).  All host-side bookkeeping (per-lane finite scan,
quarantine, budgets, slot admission, checkpoints) runs on the
`gather_tree`'d global view, so PR 9 fault domains and PR 13 slots work
unchanged per shard: an admission scatters into the owning shard's slot
(slot j belongs to shard ``j // (width / D)`` for the life of the batch),
so steady-state churn still costs zero re-specializations.  Batch widths
that do not divide D are padded with discarded replicas of lane 0; the
compile accounting (`FleetResult.block_scan_compiles`) tracks padded
widths — the shapes XLA actually specializes on.  ``fleet_block`` events
gain ``shards`` + per-shard occupancy on mesh runs only.

Escape hatches: ``STARK_FLEET=0`` (or ``fleet=False``) runs the problems
SEQUENTIALLY through the unmodified single-problem runner (honoring the
same `FleetFeed` API) — and a one-problem feed-less fleet always takes
that path, so B=1 is bit-identical to
`runner.sample_until_converged` by construction (draws, metrics trail,
checkpoint arrays), the same flags-off discipline as PRs 3–4.

``STARK_RAGGED_NUTS=1`` routes the fleet's NUTS block dispatches through
the step-synchronized scheduler (`kernels.nuts_ragged`): the B x chains
lanes — where max-tree lane sync is worst — each advance their own tree
per batched gradient evaluation, draws stay bit-identical, and
``fleet_block`` events gain lane-occupancy accounting.

Out of scope (documented, not silently wrong): the chees ensemble kernel
(its warmup adapts cross-chain with its own host loop) and multi-process
meshes raise; per-problem ``init_params``/adaptation import are not
plumbed.  Supervision composes: `supervised_sample_fleet` runs the fleet
under the PR 2 restart machinery, and a crash resumes the SURVIVING
active set from the fleet checkpoint.
"""

from __future__ import annotations

import dataclasses
import json
import logging
import os
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as _PSPEC

from . import diagnostics, faults, health as _health, lineage, telemetry
from . import profile as _profile
from .adaptation import DualAveragingState, build_warmup_schedule
from .kernels.base import STREAM_DIAG_LAGS, HMCState, StreamDiagState
from .model import Model, flatten_model, prepare_model_data
from .sampler import SamplerConfig, make_block_runner, make_warmup_parts

Array = jax.Array
PyTree = Any

log = logging.getLogger("stark_tpu.fleet")

#: env escape hatch: "0" forces the sequential single-problem path
FLEET_ENV = "STARK_FLEET"

#: seed spacing between problems on RESEEDED sequential restarts — wide
#: enough that the supervisor's per-attempt seed bump never walks one
#: problem's cold stream onto a neighbor's (see `_cold_key`)
_RESEED_STRIDE = 1 << 20

#: fold_in salt applied BEFORE the lane-restart ordinal when a poisoned
#: lane is reseeded in place: lane-reseed streams must never alias the
#: supervisor's attempt folds (`_cold_key` folds the bare attempt number)
_LANE_RESEED_SALT = 0x51AB

#: sequential-hatch twin of the lane-reseed fold: the single runner takes
#: an int seed, so a lane retry shifts the problem's seed by a stride far
#: outside any neighbor's ``seed + i`` lattice.  NOT a multiple of
#: `_RESEED_STRIDE`: ``r * 2^34`` would alias problem ``i + r*2^14``'s
#: reseeded base seed on fleets past 16384 problems — the +1 keeps every
#: retry off both lattices
_LANE_SEED_STRIDE = (1 << 34) + 1

#: fault class a quarantined lane carries (matches supervise's taxonomy)
_FAULT_POISONED = "poisoned_state"
_FAULT_CORRUPT = "corrupt_checkpoint"

#: fault class of a tenant whose MESH SHARD was declared lost (the shard
#: deadman, STARK_SHARD_DEADLINE): the lane cold-restarts against its
#: EXISTING budget on the shrunk mesh, then quarantines as
#: ``failed:shard_lost``
_FAULT_SHARD_LOST = "shard_lost"

#: shard-deadman knob: a positive float ARMS shard-loss detection on
#: mesh fleets — a shard whose active lanes all return non-finite, or
#: whose block wall exceeds this multiple of the surviving-shard median
#: wall, is declared lost and the fleet degrades onto a shrunk mesh.
#: Unset / "" / "0" (the default) disables the subsystem entirely:
#: traces stay byte-identical to a build without it.
SHARD_DEADLINE_ENV = "STARK_SHARD_DEADLINE"

#: wall-deadman absolute floor: the ratio test only applies once a
#: shard's wall is past this, so sub-millisecond scheduler jitter on
#: tiny blocks can never fake a dead shard (a real hung collective is
#: seconds, not microseconds)
_SHARD_WALL_FLOOR_S = 0.25

#: `FleetFeed` backpressure knob: maximum queued (undrained) submissions
#: before `submit` rejects with `FeedRejected`.  Unset / "" / "0" (the
#: default) keeps the queue unbounded — the pre-PR-17 behavior.
FEED_MAXDEPTH_ENV = "STARK_FEED_MAXDEPTH"


class CapabilityError(NotImplementedError):
    """A requested configuration is outside what this build supports,
    with the KNOB that asked for it and the supported fallback named —
    the structured twin of the sequential-hatch warning, so callers (and
    the multi-process smoke test) can assert the capability boundary
    instead of pattern-matching a bare exception."""

    def __init__(self, message: str, *, knob: str, fallback: str):
        super().__init__(f"{message} (knob: {knob}; supported fallback: "
                         f"{fallback})")
        self.knob = knob
        self.fallback = fallback


class FeedRejected(RuntimeError):
    """`FleetFeed.submit` refused a submission: the queue is at its
    bounded depth (``STARK_FEED_MAXDEPTH``).  Carries the observed
    ``depth``, the ``maxdepth`` bound, and ``retry_after_s`` — the
    producer's structured backoff hint (the feed's recent drain cadence,
    1s when it has never drained)."""

    def __init__(self, *, depth: int, maxdepth: int, retry_after_s: float):
        super().__init__(
            f"FleetFeed queue at depth {depth} >= maxdepth {maxdepth} "
            f"({FEED_MAXDEPTH_ENV}); retry after ~{retry_after_s:.1f}s"
        )
        self.depth = int(depth)
        self.maxdepth = int(maxdepth)
        self.retry_after_s = float(retry_after_s)


def _resolve_shard_deadline() -> Optional[float]:
    """The armed shard-deadman ratio, or None (disabled — the default).
    Literal env read so the knob lint ties it to its README row."""
    raw = os.environ.get("STARK_SHARD_DEADLINE", "").strip()
    if not raw or raw == "0":
        return None
    try:
        v = float(raw)
    except ValueError:
        log.warning("ignoring non-numeric %s=%r", SHARD_DEADLINE_ENV, raw)
        return None
    if v <= 0:
        return None
    if v < 1.0:
        log.warning(
            "%s=%g < 1 would declare the MEDIAN shard dead; clamping to 1",
            SHARD_DEADLINE_ENV, v,
        )
        v = 1.0
    return v


def _resolve_feed_maxdepth() -> Optional[int]:
    """The feed's bounded depth, or None (unbounded — the default).
    Literal env read so the knob lint ties it to its README row."""
    raw = os.environ.get("STARK_FEED_MAXDEPTH", "").strip()
    if not raw or raw == "0":
        return None
    try:
        v = int(raw)
    except ValueError:
        log.warning("ignoring non-integer %s=%r", FEED_MAXDEPTH_ENV, raw)
        return None
    return v if v > 0 else None


def _status_string(failed, converged, budget_exhausted, *,
                   default: str) -> str:
    """The ONE terminal-status fold every reporter shares (results,
    metrics JSONL, trace events): ``failed:<fault>`` wins, then
    ``converged``, then ``budget_exhausted``, else ``default``."""
    if failed:
        return f"failed:{failed}"
    if converged:
        return "converged"
    if budget_exhausted:
        return "budget_exhausted"
    return default


# --------------------------------------------------------------------------
# model contract: one shared Model, B stacked datasets
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ProblemBudget:
    """Per-problem gate targets and fault budget — the per-tenant
    contract ROADMAP item 2 lists and the item-1 control plane admits
    jobs against.  ``None`` fields inherit the fleet-wide defaults
    (`sample_fleet`'s ``ess_target`` / ``problem_max_restarts``; there is
    no fleet-wide deadline default — a deadline is always a per-problem
    decision).

    * ``ess_target``   — this problem's convergence target.
    * ``deadline_s``   — deadline on the run's CUMULATIVE sampling wall
      (the fleet checkpoint persists elapsed wall, so supervised
      restarts do not re-grant the window); a problem still active past
      it exits ``budget_exhausted`` (masked like a converged one — it
      never poisons neighbors), and on the sequential hatch the same
      clamp bounds every attempt including `ChainHealthError` retries.
    * ``max_restarts`` — in-place lane reseeds allowed before the
      problem is QUARANTINED (terminal ``failed:<fault>`` —
      ``poisoned_state``, or ``shard_lost`` when the lane's mesh shard
      died; a shard-loss re-placement burns THIS budget, never a fresh
      one).
    """

    ess_target: Optional[float] = None
    deadline_s: Optional[float] = None
    max_restarts: Optional[int] = None

    def __post_init__(self):
        if self.deadline_s is not None and self.deadline_s < 0:
            raise ValueError("deadline_s must be >= 0")
        if self.max_restarts is not None and self.max_restarts < 0:
            raise ValueError("max_restarts must be >= 0")

    def resolve(self, ess_target: float, max_restarts: int):
        """The ONE None-means-inherit fold both execution paths share:
        -> (ess_target, deadline_s, max_restarts) with fleet defaults
        filled in (there is no fleet-wide deadline default)."""
        return (
            float(self.ess_target) if self.ess_target is not None
            else float(ess_target),
            self.deadline_s,
            self.max_restarts if self.max_restarts is not None
            else int(max_restarts),
        )


_DEFAULT_BUDGET = ProblemBudget()


@dataclasses.dataclass(frozen=True)
class FleetSpec:
    """One shared `Model` + per-problem datasets with identical pytree
    structure and leaf shapes (the "shared structure, different data"
    contract).  ``problem_ids`` name the problems in every persisted
    artifact (draw stores, checkpoints, trace events, /status).
    ``budgets`` (optional, aligned with ``datasets``; entries may be
    None) carry per-problem `ProblemBudget` gate targets."""

    model: Model
    datasets: Tuple[PyTree, ...]
    problem_ids: Tuple[str, ...]
    budgets: Optional[Tuple[Optional[ProblemBudget], ...]] = None

    def __post_init__(self):
        if not self.datasets:
            raise ValueError("FleetSpec needs at least one problem")
        if len(self.problem_ids) != len(self.datasets):
            raise ValueError(
                f"{len(self.problem_ids)} problem_ids for "
                f"{len(self.datasets)} datasets"
            )
        if len(set(self.problem_ids)) != len(self.problem_ids):
            raise ValueError("problem_ids must be unique")
        if self.budgets is not None:
            if len(self.budgets) != len(self.datasets):
                raise ValueError(
                    f"{len(self.budgets)} budgets for "
                    f"{len(self.datasets)} datasets"
                )
            for i, b in enumerate(self.budgets):
                if b is not None and not isinstance(b, ProblemBudget):
                    raise ValueError(
                        f"budgets[{i}] is {type(b).__name__}, expected "
                        "ProblemBudget or None"
                    )
        for i, d in enumerate(self.datasets[1:], start=1):
            check_problem_data(self.datasets[0], d, self.problem_ids[i])

    @classmethod
    def from_problems(
        cls,
        model: Model,
        datasets: Sequence[PyTree],
        problem_ids: Optional[Sequence[str]] = None,
        budgets: Optional[Sequence[Optional[ProblemBudget]]] = None,
    ) -> "FleetSpec":
        if problem_ids is None:
            problem_ids = [f"p{i:04d}" for i in range(len(datasets))]
        return cls(
            model, tuple(datasets), tuple(str(p) for p in problem_ids),
            tuple(budgets) if budgets is not None else None,
        )

    def budget_for(self, i: int) -> ProblemBudget:
        """Problem ``i``'s budget (an all-defaults one when unset)."""
        if self.budgets is None or self.budgets[i] is None:
            return _DEFAULT_BUDGET
        return self.budgets[i]

    @classmethod
    def from_stacked(
        cls,
        model: Model,
        stacked: PyTree,
        problem_ids: Optional[Sequence[str]] = None,
    ) -> "FleetSpec":
        """Split a pre-stacked pytree (leading axis = problems) back into
        the per-problem dataset list (views, no copies)."""
        sizes = {int(np.shape(leaf)[0]) for leaf in jax.tree.leaves(stacked)}
        if len(sizes) != 1:
            raise ValueError(
                f"stacked leaves disagree on the problem axis: {sizes}"
            )
        b = sizes.pop()
        datasets = [jax.tree.map(lambda a, i=i: a[i], stacked) for i in range(b)]
        return cls.from_problems(model, datasets, problem_ids)

    @property
    def num_problems(self) -> int:
        return len(self.datasets)

    def prepared_stacked(self) -> PyTree:
        """Apply the model's host-side ``prepare_data`` layout hook PER
        PROBLEM, then stack along a new leading problem axis — the device
        layout every fleet dispatch closes over."""
        prepared = [prepare_model_data(self.model, d) for d in self.datasets]
        if prepared[0] is None:
            raise ValueError("fleet sampling requires per-problem data")
        return jax.tree.map(lambda *leaves: jnp.stack(leaves), *prepared)


def _check_finite_submission(data: PyTree, pid: str) -> None:
    """Streamed submissions must carry FINITE data: a NaN/Inf leaf
    passes the shape check but poisons its lane's warmup inside an
    already-compiled (and health-checked) batch — one hostile tenant
    must be rejected at the admission boundary, never escalated into a
    whole-fleet ChainHealthError.  Scoped to FleetFeed submissions: the
    spec path keeps its historical behavior (operator data is not
    tenant data)."""
    for leaf in jax.tree.leaves(data):
        arr = np.asarray(leaf)
        if (
            np.issubdtype(arr.dtype, np.floating)
            and not np.all(np.isfinite(arr))
        ):
            raise ValueError(f"problem {pid!r}: non-finite data leaf")


def check_problem_data(ref: PyTree, d: PyTree, pid: str) -> None:
    """The ONE batched-data admission check (`FleetSpec` construction and
    `FleetFeed` streaming submissions share it): ``d`` must match the
    reference dataset's pytree structure and leaf shapes exactly, or it
    cannot share the fleet's stacked device layout."""
    if jax.tree.structure(d) != jax.tree.structure(ref):
        raise ValueError(
            f"problem {pid!r}: data pytree structure differs from "
            "problem 0 (fleet batching needs identical structure and "
            "leaf shapes)"
        )
    ref_shapes = [np.shape(a) for a in jax.tree.leaves(ref)]
    shapes = [np.shape(a) for a in jax.tree.leaves(d)]
    if shapes != ref_shapes:
        raise ValueError(
            f"problem {pid!r}: data leaf shapes {shapes} differ from "
            f"problem 0's {ref_shapes} (fleet batching stacks along a "
            "new leading axis)"
        )


# --------------------------------------------------------------------------
# streaming admission (the ROADMAP item 2 "refill API": problems arriving
# WHILE the fleet runs — sample_fleet becomes a long-lived serving loop)
# --------------------------------------------------------------------------


class FleetFeed:
    """Thread-safe streaming admission queue for a live ``sample_fleet``.

    ``submit(data, problem_id=..., budget=...)`` may be called from ANY
    thread while the fleet runs; submissions are handed off to the fleet
    at block boundaries (the same unit every other fleet decision is made
    in), validated against the spec's batched-data contract, seeded with
    the next global problem index (the existing ``seed + i`` discipline —
    a submitted problem's draws are bit-identical to its unbatched run
    and independent of WHEN it was submitted relative to the batch), and
    queued for in-place admission.  ``close()`` marks the feed complete:
    the fleet drains the queue and returns once every problem (spec +
    submitted) is terminal.  An open feed keeps ``sample_fleet`` alive as
    a serving loop even when every current problem has finished.

    Durability: consumed submissions are persisted in the fleet
    checkpoint (data leaves + budget + arrival order), so a supervised
    crash-resume replays the admission order bit-identically without the
    caller re-submitting.  The sequential ``STARK_FLEET=0`` hatch honors
    the same API (submissions run through the single-problem runner after
    the spec sweep, same seed discipline).

    Backpressure: ``maxdepth`` (default ``STARK_FEED_MAXDEPTH``, unset =
    unbounded) bounds the UNDRAINED queue — an admission storm gets a
    structured `FeedRejected` carrying ``retry_after_s`` (the feed's
    recent drain cadence) instead of unbounded host-memory growth.  A
    reject emits one ``feed_reject`` trace event (the
    ``stark_fleet_feed_rejects_total`` counter) and consumes nothing:
    the producer retries with the SAME problem_id or drops.  `requeue`
    is exempt — crash-recovery reinsertion of already-admitted items
    must never bounce.
    """

    def __init__(self, maxdepth: Optional[int] = None):
        self._cond = threading.Condition()
        self._items: List[Tuple[Optional[str], PyTree,
                                Optional[ProblemBudget]]] = []
        self._closed = False
        self._seq = 0
        self.maxdepth = (
            int(maxdepth) if maxdepth is not None
            else _resolve_feed_maxdepth()
        )
        self._rejects = 0
        # drain cadence for the retry-after hint: the consumer's block
        # boundary sets the natural retry horizon
        self._last_drain_t: Optional[float] = None
        self._drain_gap_s: Optional[float] = None
        # the fleet binds its trace here so producer-thread rejects emit
        # on the run's bus (the ambient ContextVar does not cross threads)
        self._trace = None

    @property
    def rejects(self) -> int:
        """Submissions refused by the depth bound since construction."""
        with self._cond:
            return self._rejects

    def _retry_after_s(self) -> float:
        """Backoff hint: the feed's observed drain cadence (how often the
        fleet's block boundary empties the queue), default 1s."""
        gap = self._drain_gap_s
        if gap is None and self._last_drain_t is not None:
            gap = time.monotonic() - self._last_drain_t
        return round(min(max(gap if gap is not None else 1.0, 0.1), 60.0), 3)

    def submit(self, data: PyTree, problem_id: Optional[str] = None,
               budget: Optional[ProblemBudget] = None) -> str:
        """Queue one problem; returns its problem_id (``s####`` when not
        given).  Raises once the feed is closed, or `FeedRejected` when
        the bounded queue is full (nothing is consumed — retry with the
        same arguments after ``retry_after_s``)."""
        if budget is not None and not isinstance(budget, ProblemBudget):
            raise ValueError(
                f"budget is {type(budget).__name__}, expected "
                "ProblemBudget or None"
            )
        with self._cond:
            if self._closed:
                raise RuntimeError("FleetFeed is closed")
            if (self.maxdepth is not None
                    and len(self._items) >= self.maxdepth):
                self._rejects += 1
                depth, retry = len(self._items), self._retry_after_s()
                tr = self._trace
                if tr is None:
                    tr = telemetry.get_trace()
                if tr is not None and tr.enabled:
                    tr.emit(
                        "feed_reject", depth=depth,
                        maxdepth=self.maxdepth, retry_after_s=retry,
                        rejects=self._rejects,
                        # lineage: a retrying tenant's rejects correlate
                        # to its job once the pid is known; field rides
                        # only with lineage on (byte-identity contract)
                        **({"problem_id": str(problem_id)}
                           if problem_id is not None and lineage.enabled()
                           else {}),
                    )
                raise FeedRejected(
                    depth=depth, maxdepth=self.maxdepth,
                    retry_after_s=retry,
                )
            if problem_id is None:
                problem_id = f"s{self._seq:04d}"
            self._seq += 1
            pid = str(problem_id)
            self._items.append((pid, data, budget))
            if lineage.enabled():
                # mint the tenant's job_id at the FRONT DOOR: the same
                # arrival-ordinal discipline as the key seeding, so a
                # resubmit-after-crash re-mints the same id.  The
                # feed_submit event is the lineage anchor every report
                # starts from.
                jid = lineage.job_for(pid)
                if jid is None:
                    jid = lineage.mint_job_id(pid, self._seq - 1)
                    lineage.register(pid, jid)
                tr = self._trace
                if tr is None:
                    tr = telemetry.get_trace()
                if tr is not None and getattr(tr, "enabled", False):
                    tr.emit(
                        "feed_submit", problem_id=pid,
                        depth=len(self._items),
                        budgeted=budget is not None,
                    )
            self._cond.notify_all()
        return pid

    def close(self) -> None:
        """No more submissions: the fleet finishes once the queue drains."""
        with self._cond:
            self._closed = True
            self._cond.notify_all()

    @property
    def closed(self) -> bool:
        with self._cond:
            return self._closed

    def drain(self) -> List[Tuple[str, PyTree, Optional[ProblemBudget]]]:
        """Pop every queued submission (the fleet's block-boundary
        consumption point)."""
        with self._cond:
            now = time.monotonic()
            if self._last_drain_t is not None:
                self._drain_gap_s = now - self._last_drain_t
            self._last_drain_t = now
            items, self._items = self._items, []
            return items

    def requeue(
        self, items: List[Tuple[str, PyTree, Optional[ProblemBudget]]]
    ) -> None:
        """Return consumed submissions to the FRONT of the queue — the
        fleet's crash-recovery path for items drained but not yet
        persisted in a checkpoint (the drain->checkpoint window).
        Allowed on a closed feed: the items were legitimately submitted
        before close, and the supervised retry must see them again."""
        with self._cond:
            self._items[:0] = list(items)
            self._cond.notify_all()

    def wait(self, timeout_s: float) -> bool:
        """Block until a submission or close arrives (or the timeout);
        True when there is anything to act on.  The fleet's idle-serving
        wait — callers must keep feeding progress beats around it."""
        with self._cond:
            if self._items or self._closed:
                return True
            self._cond.wait(timeout_s)
            return bool(self._items) or self._closed


# --------------------------------------------------------------------------
# warm-start adaptation transfer (STARK_FLEET_WARMSTART=1)
# --------------------------------------------------------------------------


class DonorPool:
    """Running moment pool of completed problems' adaptation state, keyed
    by model tag — the donor side of warm-start admission transfer.

    A CONVERGED problem donates ``mean(log step_size)`` and its
    mass-matrix diagonal (both averaged over chains); an admitted problem
    seeds from the pool mean.  Every donation AND every summary read is
    validated finite — a NaN'd completed problem (the
    ``fleet.warmstart_poison`` drill) is rejected at the pool boundary
    and can never propagate into an admitted lane's warmup.  The pool
    state rides the fleet checkpoint so crash-resume replays warm-started
    admissions deterministically.

    Since the serving layer landed the pool also carries full POSITION
    ENSEMBLES per tag (`add_ensemble` / `ensemble`): the latest finite
    (chains, d) snapshot of a completed problem's final draws.  An
    admitted problem whose tag has an ensemble starts its chains AT the
    donor posterior instead of at ``init_flat`` — the substrate for
    incremental posterior updating (resubmit a grown-data tenant with
    yesterday's posterior as the donor; `serving.donor_pool_from_store`
    builds such a pool from a served store + sidecar).  Ensembles obey
    the same discipline as the moments: finite-validated on write AND
    read, and they ride ``state_dict``/``load_state``."""

    def __init__(self):
        # tag -> {"count": int, "log_step_sum": float,
        #         "inv_mass_sum": np.ndarray (d,)}
        self._by_tag: Dict[str, Dict[str, Any]] = {}
        # tag -> np.ndarray (chains, d): latest finite position ensemble
        self._ens_by_tag: Dict[str, np.ndarray] = {}

    def add(self, tag: str, step_size: np.ndarray,
            inv_mass: np.ndarray) -> bool:
        """Fold one completed problem's (chains,) step sizes and
        (chains, d) mass diagonal into the pool; False (rejected) when
        any summary stat is non-finite."""
        step_size = np.asarray(step_size, np.float64)
        inv_mass = np.asarray(inv_mass, np.float64)
        log_step = float(np.mean(np.log(step_size))) if step_size.size \
            else float("nan")
        im = np.mean(inv_mass.reshape(-1, inv_mass.shape[-1]), axis=0)
        if not (np.isfinite(log_step) and np.all(np.isfinite(im))):
            return False
        ent = self._by_tag.setdefault(
            tag, {"count": 0, "log_step_sum": 0.0,
                  "inv_mass_sum": np.zeros_like(im)},
        )
        ent["count"] += 1
        ent["log_step_sum"] += log_step
        ent["inv_mass_sum"] = ent["inv_mass_sum"] + im
        return True

    def summary(self, tag: str) -> Optional[Tuple[float, np.ndarray, int]]:
        """(step_size, inv_mass_diag (d,), donor_count) pool mean, or
        None when the pool is empty or the mean is non-finite (a reader-
        side guard on top of the add-side one)."""
        ent = self._by_tag.get(tag)
        if not ent or ent["count"] <= 0:
            return None
        n = ent["count"]
        step = float(np.exp(ent["log_step_sum"] / n))
        im = np.asarray(ent["inv_mass_sum"]) / n
        if not (np.isfinite(step) and step > 0 and np.all(np.isfinite(im))):
            return None
        return step, im, n

    def add_ensemble(self, tag: str, positions: np.ndarray) -> bool:
        """Bank one completed problem's (chains, d) final positions as the
        tag's position donor (latest finite wins); False = rejected
        (non-finite anywhere, or not a 2-D ensemble)."""
        positions = np.asarray(positions, np.float32)
        if positions.ndim != 2 or positions.size == 0 \
                or not np.all(np.isfinite(positions)):
            return False
        self._ens_by_tag[tag] = np.array(positions, np.float32, copy=True)
        return True

    def ensemble(self, tag: str) -> Optional[np.ndarray]:
        """The tag's (chains, d) position ensemble, or None — with the
        same reader-side finite guard as `summary` (checkpoint state is
        operator-editable JSON; trust nothing)."""
        ens = self._ens_by_tag.get(tag)
        if ens is None or ens.ndim != 2 or ens.size == 0 \
                or not np.all(np.isfinite(ens)):
            return None
        return ens

    def state_dict(self) -> Dict[str, Any]:
        state = {
            tag: {"count": e["count"], "log_step_sum": e["log_step_sum"],
                  "inv_mass_sum": np.asarray(e["inv_mass_sum"]).tolist()}
            for tag, e in self._by_tag.items()
        }
        for tag, ens in self._ens_by_tag.items():
            state.setdefault(tag, {})["ensemble"] = \
                np.asarray(ens, np.float32).tolist()
        return state

    def load_state(self, state: Dict[str, Any]) -> None:
        self._by_tag = {
            tag: {"count": int(e["count"]),
                  "log_step_sum": float(e["log_step_sum"]),
                  "inv_mass_sum": np.asarray(e["inv_mass_sum"],
                                             np.float64)}
            for tag, e in (state or {}).items()
            if "count" in e  # ensemble-only entries carry no moments
        }
        self._ens_by_tag = {}
        for tag, e in (state or {}).items():
            if "ensemble" in e:
                # add-side validation re-runs on load: a hand-edited or
                # torn checkpoint cannot smuggle NaNs past the boundary
                self.add_ensemble(
                    tag, np.asarray(e["ensemble"], np.float32)
                )


# --------------------------------------------------------------------------
# results
# --------------------------------------------------------------------------


class FleetProblemResult:
    """One problem's slice of a fleet run.  ``draws`` (constrained, named)
    is computed lazily through a fm-shared jit cache so a 256-problem
    fleet does not pay 256 recompiles of the constrain map.

    ``failed`` (None when the problem was never quarantined) is the fault
    class of a terminal quarantine — ``status`` folds the three terminal
    outcomes into the one string the service layer reports per tenant:
    ``converged`` / ``budget_exhausted`` / ``failed:<fault>``."""

    def __init__(self, problem_id, draws_flat, fm, *, converged,
                 budget_exhausted, blocks, grad_evals, num_divergent,
                 min_ess, max_rhat, history, _constrain_cache,
                 failed=None, failed_reason=None, lane_restarts=0,
                 warmstarted=False, warmup_draws_saved=0, health=None):
        self.problem_id = problem_id
        self.draws_flat = draws_flat  # (chains, n, d) unconstrained
        self.flat_model = fm
        self.converged = converged
        self.budget_exhausted = budget_exhausted
        self.blocks = blocks
        self.grad_evals = grad_evals
        self.num_divergent = num_divergent
        self.min_ess = min_ess
        self.max_rhat = max_rhat
        self.history = history
        self.failed = failed
        self.failed_reason = failed_reason
        self.lane_restarts = lane_restarts
        # warm-start admission transfer (STARK_FLEET_WARMSTART): whether
        # this problem's warmup was donor-seeded, and how many warmup
        # draws per chain the shortened schedule skipped
        self.warmstarted = warmstarted
        self.warmup_draws_saved = warmup_draws_saved
        # per-problem statistical-health verdict (stark_tpu.health):
        # sorted warning names the observatory raised for this tenant
        # ([] = clean trail); None when STARK_HEALTH=0 or the problem
        # predates the observatory — null, never a claim of health
        self.health = health
        self._cache = _constrain_cache
        self._draws = None

    @property
    def status(self) -> str:
        return _status_string(
            self.failed, self.converged, self.budget_exhausted,
            default="incomplete",
        )

    @property
    def draws(self) -> Dict[str, np.ndarray]:
        if self._draws is None:
            key = self.draws_flat.shape
            fn = self._cache.get(key)
            if fn is None:
                fn = self._cache[key] = jax.jit(
                    jax.vmap(jax.vmap(self.flat_model.constrain))
                )
            out = fn(np.asarray(self.draws_flat))
            self._draws = {k: np.asarray(v) for k, v in out.items()}
        return self._draws

    @property
    def draws_per_chain(self) -> int:
        return int(self.draws_flat.shape[1])


class FleetResult:
    """All problems' results + fleet-level accounting."""

    def __init__(self, problems: List[FleetProblemResult], *, wall_s,
                 blocks_dispatched, compactions, occupancy_trail,
                 total_grad_evals, budget_exhausted=False,
                 block_scan_compiles=0, admissions=0, slot_recycles=0,
                 dispatch_occupancy_trail=None, shards=None,
                 lost_shards=None):
        self.problems = problems
        self.wall_s = wall_s
        self.blocks_dispatched = blocks_dispatched
        self.compactions = compactions
        self.occupancy_trail = occupancy_trail
        self.total_grad_evals = total_grad_evals
        self.budget_exhausted = budget_exhausted
        # batched-scan specializations this run dispatched (distinct
        # batch widths the compiled block scan saw): the zero-recompile
        # evidence — 1 on a slot-scheduler run, >= 1 + compaction sizes
        # on the legacy path.  0 on the sequential hatch (no batched
        # scan at all).
        self.block_scan_compiles = block_scan_compiles
        # in-place admissions (slot scheduler or legacy top-up) and the
        # slots they recycled
        self.admissions = admissions
        self.slot_recycles = slot_recycles
        # (occupancy_at_dispatch, queue_depth_at_dispatch) per fleet
        # block: occupancy as the DEVICE saw it — measured after the
        # boundary's admissions, unlike occupancy_trail's post-block
        # pre-admission reading
        self.dispatch_occupancy_trail = dispatch_occupancy_trail or []
        # mesh-parallel fleet (STARK_FLEET_MESH): the "problems" mesh
        # axis size the batched dispatches sharded over; None on
        # single-device (and sequential-hatch) runs.  On a run that
        # degraded onto a shrunk mesh this is the FINAL shard count.
        self.shards = shards
        # shard ordinals the deadman (STARK_SHARD_DEADLINE) declared
        # lost, in loss order — the fleet twin of degraded consensus's
        # lost_shards (empty on healthy / off-mesh runs)
        self.lost_shards: List[int] = list(lost_shards or [])
        self._by_id = {p.problem_id: p for p in problems}

    def __getitem__(self, problem_id: str) -> FleetProblemResult:
        return self._by_id[problem_id]

    @property
    def num_problems(self) -> int:
        return len(self.problems)

    @property
    def converged_fraction(self) -> float:
        """Converged over ALL problems: a quarantined or exhausted lane
        counts as NOT converged — the denominator never shrinks."""
        if not self.problems:
            return 0.0
        return sum(p.converged for p in self.problems) / len(self.problems)

    @property
    def lost_problems(self) -> List[str]:
        """problem_ids of terminally quarantined (``failed:*``) problems
        — the fleet twin of degraded consensus's ``lost_shards``."""
        return [p.problem_id for p in self.problems if p.failed]

    @property
    def degraded(self) -> bool:
        """True when the fleet completed AROUND a loss: any quarantined
        problem, or any mesh shard the deadman declared lost (even when
        every displaced tenant reconverged within budget — the run did
        not execute on the mesh it was asked for).  Budget-exhausted
        problems are a policy outcome, not a fault — they do not degrade
        the fleet."""
        return bool(self.lost_problems) or bool(self.lost_shards)

    def aggregate_min_ess(self) -> float:
        """Sum of per-problem min-ESS — the fleet throughput numerator
        (aggregate min-ESS/s = this over the fleet wall).  Quarantined
        problems carry ``min_ess=None`` and contribute nothing."""
        vals = [p.min_ess for p in self.problems if p.min_ess is not None]
        return float(np.nansum(vals)) if vals else float("nan")

    @property
    def warmup_draws_saved(self) -> int:
        """Total warmup draws per chain skipped by warm-start admission
        transfer across the fleet (0 on cold runs)."""
        return sum(p.warmup_draws_saved for p in self.problems)


# --------------------------------------------------------------------------
# per-problem draw persistence
# --------------------------------------------------------------------------


class FleetDrawStore:
    """Per-problem `DrawStore` files under one directory, so every
    persisted draw row is keyed by problem_id (``p_<id>.stkr``) — the
    fleet flavor of the single-problem store path."""

    def __init__(self, root: str, chains: int, dim: int):
        self.root = root
        self.chains = chains
        self.dim = dim
        self._stores: Dict[str, Any] = {}
        os.makedirs(root, exist_ok=True)

    def path(self, problem_id: str) -> str:
        return os.path.join(self.root, f"p_{problem_id}.stkr")

    def _store(self, problem_id: str):
        s = self._stores.get(problem_id)
        if s is None:
            from .drawstore import DrawStore

            s = self._stores[problem_id] = DrawStore(
                self.path(problem_id), self.chains, self.dim
            )
        return s

    def append(self, problem_id: str, block: np.ndarray) -> None:
        self._store(problem_id).append(block)

    def flush(self) -> None:
        for s in self._stores.values():
            s.flush()

    def truncate(self, problem_id: str, n_draws: int) -> None:
        from .drawstore import truncate_draws

        p = self.path(problem_id)
        if os.path.exists(p):
            truncate_draws(p, n_draws)

    def read(self, problem_id: str) -> Optional[np.ndarray]:
        """(chains, n, d) history for one problem, or None."""
        from .drawstore import read_draws

        p = self.path(problem_id)
        if not os.path.exists(p):
            return None
        stored, _, _ = read_draws(p, mmap=False)
        return np.ascontiguousarray(stored.transpose(1, 0, 2))

    def close_problem(self, problem_id: str) -> None:
        """Close one problem's store once its file is final — open
        handles stay bounded by the ACTIVE batch, not the whole fleet
        (a thousands-of-posteriors sweep would otherwise exhaust the
        process fd limit)."""
        s = self._stores.pop(problem_id, None)
        if s is not None:
            s.close()

    def close(self) -> None:
        for s in self._stores.values():
            s.close()
        self._stores.clear()


# --------------------------------------------------------------------------
# vmapped kernel plumbing (problem axis on top of the chain axis)
# --------------------------------------------------------------------------


class _FleetParts:
    """Compiled fleet callables, cached per (fm, cfg, mesh) instance: the
    single-problem warmup parts and block runner with one extra leading
    problem axis from an outer ``jax.vmap`` (data mapped over problems,
    broadcast over chains — exactly the JaxBackend layout plus one axis).
    XLA re-specializes per batch size; compaction sizes are bounded by
    the refill threshold (at most O(log B) distinct sizes per run).

    With a ``mesh`` (STARK_FLEET_MESH / ``sample_fleet(mesh=...)``) every
    callable is additionally shard_mapped over the mesh "problems" axis
    via `parallel.primitives.map_shards`: each device runs the SAME
    vmapped program on its contiguous slice of the problem axis, so B
    problems span D devices instead of one.  Problems are independent —
    there is no collective inside the mapped program at all — and the
    repo's drilled batch-composition-independence contract is exactly
    what makes the sharded dispatch bit-identical per lane to the
    single-device one.  Batch widths that do not divide the shard count
    are padded with replicas of lane 0 (finite, discarded — the same
    dummy-lane trick as `_warm_slots_padded`) and outputs sliced back,
    so ALL host-side bookkeeping sees exactly the unpadded batch."""

    def __init__(self, fm, cfg: SamplerConfig, mesh=None):
        from .parallel.primitives import axis_size

        self.fm = fm
        self.cfg = cfg
        self.mesh = mesh
        self.shards = axis_size(mesh, "problems") if mesh is not None else 1
        init_carry, segment, _finalize = make_warmup_parts(fm, cfg)
        self.finalize = _finalize
        PP, R = _PSPEC("problems"), _PSPEC()
        self.v_init = self._compile(
            jax.vmap(jax.vmap(init_carry, in_axes=(0, 0, None)),
                     in_axes=(0, 0, 0)),
            in_specs=(PP, PP, PP),
        )
        self.v_seg = self._compile(
            jax.vmap(
                jax.vmap(segment, in_axes=(1, None, None, 0, 0, 0, 0, None)),
                in_axes=(0, None, None, 0, 0, 0, 0, 0),
            ),
            in_specs=(PP, R, R, PP, PP, PP, PP, PP),
        )
        self._blocks: Dict[Tuple[Any, ...], Any] = {}

    def padded_width(self, width: int) -> int:
        """The problem-axis width a dispatch of ``width`` lanes actually
        runs at: the next multiple of the shard count (identity with no
        mesh) — what the compiled program specializes on."""
        d = self.shards
        return -(-width // d) * d

    def _compile(self, fn, in_specs):
        """`map_shards` + the pad/slice wrapper.  No mesh: exactly
        ``jax.jit(fn)`` (the primitive's identity fast path) — the
        historical single-device fleet, bit- and trace-identical."""
        from .parallel.primitives import map_shards

        if self.mesh is None:
            return map_shards(fn)
        rep = _PSPEC()
        jitted = map_shards(
            fn, mesh=self.mesh, in_specs=in_specs,
            out_specs=_PSPEC("problems"),
        )
        mapped = [i for i, s in enumerate(in_specs) if s != rep]

        def call(*args):
            width = jax.tree.leaves(args[mapped[0]])[0].shape[0]
            padded = self.padded_width(width)
            # pad per-TREE (each arg from its own leading dim): the
            # stacked dataset arrives pre-padded + pre-sharded from
            # `place_batch` at batch-rebuild time and passes through
            # untouched, while host-rebuilt carries/keys pad here
            args = tuple(
                self.place_batch(a, padded) if i in mapped else a
                for i, a in enumerate(args)
            )
            out = jitted(*args)
            if padded != width:
                out = jax.tree.map(lambda a: a[:width], out)
            return out

        return call

    def place_batch(self, tree, padded: Optional[int] = None):
        """Pad a problem-leading pytree up to ``padded`` lanes (default:
        its own padded width) with discarded replicas of lane 0, and
        commit it to the "problems" sharding.  Identity off-mesh.
        Idempotent — an already padded-and-placed tree costs only the
        sharding equality check, which is what lets `_sample_fleet`
        place the stacked dataset ONCE per batch rebuild instead of
        paying an O(dataset-bytes) reshard per block dispatch."""
        if self.mesh is None:
            return tree
        from jax.sharding import NamedSharding

        width = jax.tree.leaves(tree)[0].shape[0]
        if padded is None:
            padded = self.padded_width(width)
        if width < padded:
            tree = jax.tree.map(
                lambda a: jnp.concatenate(
                    [a, jnp.broadcast_to(
                        a[:1], (padded - width,) + a.shape[1:]
                    )], axis=0,
                ),
                tree,
            )
        return jax.device_put(
            tree, NamedSharding(self.mesh, _PSPEC("problems"))
        )

    def get_block(self, length: int, diag_lags: Optional[int] = None,
                  ragged: bool = False):
        key = (length, diag_lags, ragged)
        fn = self._blocks.get(key)
        if fn is None:
            inner_axes = (
                (0, 0, 0, 0, None) if diag_lags is None
                else (0, 0, 0, 0, 0, None)
            )
            # every input (incl. the data pytree) maps over the problem axis
            outer_axes = (0,) * len(inner_axes)
            # ragged (STARK_RAGGED_NUTS): the step-synchronized NUTS
            # scheduler — the B x chains lanes of the doubly-vmapped loop
            # slip independently (the fleet is where max-tree lane sync
            # is worst), and the runners return one extra trailing
            # (problems, chains) lane-iteration output
            fn = self._blocks[key] = self._compile(
                jax.vmap(
                    jax.vmap(
                        make_block_runner(self.fm, self.cfg, length,
                                          diag_lags=diag_lags,
                                          ragged=ragged),
                        in_axes=inner_axes,
                    ),
                    in_axes=outer_axes,
                ),
                in_specs=tuple(
                    _PSPEC("problems") for _ in range(len(inner_axes))
                ),
            )
        return fn


#: compiled fleet parts per (model, cfg, mesh) — keyed on the model
#: OBJECT (kept alive by the key, like JaxBackend's runner cache), so
#: repeated fleet calls over the same model reuse every jitted warmup
#: segment and block variant instead of re-tracing per call
_PARTS_CACHE: Dict[Tuple[Any, ...], Tuple[Any, _FleetParts]] = {}


def _lane_model(model: Model):
    """The flat model of a fleet's lanes.  A lane carries the plain
    `HMCState` (the pool's slots, donors and checkpoints read its fields),
    so the per-chain centre of `sampler.chain_potential` stays off here: a
    fleet's problems are small by design."""
    return dataclasses.replace(flatten_model(model), chain_centering=None)


def _fleet_parts_for(model: Model, cfg: SamplerConfig, mesh=None):
    key = (model, cfg, mesh)
    hit = _PARTS_CACHE.get(key)
    if hit is None:
        fm = _lane_model(model)
        hit = _PARTS_CACHE[key] = (fm, _FleetParts(fm, cfg, mesh))
    return hit


def _fleet_warmup(parts: _FleetParts, cfg, warm_keys, z0, data, seg, trace,
                  num_warmup: Optional[int] = None, seed_hook=None):
    """The fleet twin of `sampler.drive_segmented_warmup`: identical key
    layout and schedule slicing per problem (so each lane's warmup is
    bit-identical to the single-problem driver's), with the problem axis
    leading every carried array.  Any schedule or key-discipline change
    in `drive_segmented_warmup` must be mirrored here — the bit-identity
    tests in tests/test_fleet.py are the drift alarm.

    ``num_warmup`` overrides ``cfg.num_warmup`` (the warm-start
    adapt-confirm window); ``seed_hook(state, da, welford, inv_mass) ->
    same tuple`` runs right after the carry init — the donor-transfer
    injection point.  Both default to the cold-path behavior exactly."""
    nw = cfg.num_warmup if num_warmup is None else int(num_warmup)
    with trace.phase("compile", stage="fleet_warmup_init"):
        kinit = jax.vmap(jax.vmap(lambda k: jax.random.split(k, 2)))(warm_keys)
        state, da, welford, inv_mass = jax.block_until_ready(
            parts.v_init(kinit[:, :, 0], z0, data)
        )
        if seed_hook is not None:
            state, da, welford, inv_mass = seed_hook(
                state, da, welford, inv_mass
            )
        schedule = build_warmup_schedule(nw)
        aflags = np.asarray(schedule.adapt_mass)
        wflags = np.asarray(schedule.window_end)
        # (problems, num_warmup, chains, 2) step keys — the per-problem
        # transpose of the single-problem driver's (num_warmup, chains, 2)
        wkeys = jnp.transpose(
            jax.vmap(
                jax.vmap(lambda k: jax.random.split(k, max(nw, 1)))
            )(kinit[:, :, 1]),
            (0, 2, 1, 3),
        )
    warm_div = None
    for s in range(0, nw, seg):
        e = min(s + seg, nw)
        with trace.phase("warmup_block", start=s, end=e,
                         fleet=int(z0.shape[0])):
            state, da, welford, inv_mass, ndiv, *_ = jax.block_until_ready(
                parts.v_seg(
                    wkeys[:, s:e], jnp.asarray(aflags[s:e]),
                    jnp.asarray(wflags[s:e]), state, da, welford, inv_mass,
                    data,
                )
            )
        telemetry.notify_progress()
        warm_div = ndiv if warm_div is None else warm_div + ndiv
    if warm_div is None:
        warm_div = jnp.zeros(z0.shape[:2], jnp.int32)
    return state, parts.finalize(da), inv_mass, warm_div


# --------------------------------------------------------------------------
# the fleet runner
# --------------------------------------------------------------------------


def _resolve_fleet_flag(fleet: Optional[bool]) -> bool:
    if fleet is not None:
        return bool(fleet)
    return os.environ.get(FLEET_ENV, "1") != "0"


def _resolve_slots_flag(slots: Optional[bool]) -> bool:
    """Default-off knob: "1" pins the compiled batch shape for the whole
    run (fixed-capacity lane slots with in-place admission); off
    preserves the legacy compaction path bit-identically.  The literal
    knob name keeps it collectable by tools/lint_fused_knobs.py."""
    if slots is not None:
        return bool(slots)
    return os.environ.get("STARK_FLEET_SLOTS", "0") == "1"


def _resolve_warmstart_flag(warmstart: Optional[bool]) -> bool:
    """Default-off knob: "1" donor-seeds admitted problems' adaptation
    state and shrinks their warmup to an adapt-confirm window (slots
    path only); the full stop validation is unchanged either way."""
    if warmstart is not None:
        return bool(warmstart)
    return os.environ.get("STARK_FLEET_WARMSTART", "0") == "1"


def _resolve_fleet_mesh(mesh):
    """None (single-device fleet) or a Mesh with a "problems" axis.

    An explicit ``mesh`` argument wins (it must carry a "problems" axis
    — the fleet shards problems, nothing else).  Otherwise the
    STARK_FLEET_MESH env knob decides: "0"/unset — off, bit-identical to
    the historical single-device fleet; "1" — every local device on one
    "problems" axis; an integer N>1 — the first N devices.  Multi-process
    is rejected at the `sample_fleet` boundary already (problems shard
    over local devices; cross-host problem placement is the item-1
    control plane's job).  The literal knob name keeps it collectable
    by tools/lint_fused_knobs.py."""
    if mesh is not None:
        if "problems" not in mesh.axis_names:
            raise ValueError(
                f'fleet mesh must have a "problems" axis; got axes '
                f"{mesh.axis_names}"
            )
        extra = [
            (ax, sz) for ax, sz in mesh.shape.items()
            if ax != "problems" and sz > 1
        ]
        if extra:
            raise ValueError(
                "the fleet shards only the problem axis; mesh axes "
                f"{extra} would duplicate work — use a mesh with all "
                'non-"problems" axes of size 1'
            )
        return mesh
    val = os.environ.get("STARK_FLEET_MESH", "0")
    if val in ("", "0"):
        return None
    devices = jax.devices()
    n = len(devices) if val == "1" else int(val)
    if n < 1 or n > len(devices):
        raise ValueError(
            f"STARK_FLEET_MESH={val!r}: need 1..{len(devices)} devices"
        )
    from .parallel.mesh import make_mesh

    return make_mesh({"problems": n}, devices=devices[:n])


def _shard_ready_walls(tree, t0: float) -> Optional[List[float]]:
    """Host wall (since ``t0``, the dispatch enqueue) at which each mesh
    shard's output buffer became ready, ordered by shard ordinal along
    the leading (problems) axis — the per-shard timing trail behind
    shard-imbalance attribution.

    Polls ``is_ready`` across all shards when the runtime exposes it
    (true per-shard completion order); otherwise falls back to
    sequential ``block_until_ready`` in ordinal order, where each wall
    is the time the shard was OBSERVED ready by — an upper bound that
    keeps the slowest shard exact.  None when the output carries no
    addressable shards (off-mesh paths)."""
    leaves = jax.tree.leaves(tree)
    if not leaves:
        return None
    shards = getattr(leaves[0], "addressable_shards", None)
    if not shards or len(shards) < 2:
        return None

    def ordinal(sh):
        idx = getattr(sh, "index", None)
        if idx and isinstance(idx[0], slice) and idx[0].start is not None:
            return int(idx[0].start)
        return 0

    datas = [sh.data for sh in sorted(shards, key=ordinal)]
    walls: List[Optional[float]] = [None] * len(datas)
    # per-shard watchdog beats: every shard that completes IS progress,
    # so a single hung shard cannot silence the deadman — and the wait
    # context names the shards still outstanding, so a stall fired here
    # carries the culprit in the stall event and postmortem bundle
    if all(hasattr(d, "is_ready") for d in datas):
        remaining = set(range(len(datas)))
        telemetry.set_progress_context(
            waiting_on_shards=sorted(remaining))
        try:
            while remaining:
                progressed = False
                for k in list(remaining):
                    if datas[k].is_ready():
                        walls[k] = time.perf_counter() - t0
                        remaining.discard(k)
                        progressed = True
                if progressed:
                    telemetry.set_progress_context(
                        waiting_on_shards=sorted(remaining))
                    telemetry.notify_progress()
                if remaining:
                    time.sleep(0.0002)
        finally:
            telemetry.clear_progress_context("waiting_on_shards")
    else:
        try:
            for k, d in enumerate(datas):
                telemetry.set_progress_context(
                    waiting_on_shards=list(range(k, len(datas))))
                jax.block_until_ready(d)
                walls[k] = time.perf_counter() - t0
                telemetry.notify_progress()
        finally:
            telemetry.clear_progress_context("waiting_on_shards")
    return [round(float(w), 6) for w in walls]


def _classify_lost_shards(
    *,
    n_shards: int,
    lanes_per: int,
    active_js: List[int],
    poisoned_js: Any,
    shard_walls: Optional[List[float]],
    deadline_ratio: float,
    wall_floor_s: float = _SHARD_WALL_FLOOR_S,
) -> Dict[int, str]:
    """The shard deadman's pure classifier: which mesh shards are LOST
    this block, and why — ``{shard: "nonfinite" | "wall"}``.

    Two independent signals (either alone declares the shard):

    * ``nonfinite`` — every ACTIVE lane the shard carries failed the
      per-lane finite scan (``poisoned_js``).  One poisoned lane is a
      lane fault (PR 9 containment); ALL of a shard's lanes poisoned at
      once is the shard-death signature — independent tenants do not
      fail together by coincidence.
    * ``wall`` — the shard's block wall (the PR 16 ``shard_walls``
      trail) exceeds ``deadline_ratio`` x the median wall of the OTHER
      live shards, AND the absolute floor ``wall_floor_s`` (so
      microsecond scheduler jitter on tiny blocks can never fake a
      death; a real hung collective is seconds).

    A shard with no active lanes has no evidence and no victims: it is
    never classified.  Callers must treat "every shard lost" as a BATCH
    fault, not a shard fault (there is no surviving mesh to re-pack
    onto) — this function just reports what it sees.
    """
    per_shard_active: Dict[int, List[int]] = {}
    for j in active_js:
        k = j // max(lanes_per, 1)
        if 0 <= k < n_shards:
            per_shard_active.setdefault(k, []).append(j)
    lost: Dict[int, str] = {}
    for k, js in per_shard_active.items():
        if js and all(j in poisoned_js for j in js):
            lost[k] = "nonfinite"
    if shard_walls:
        walls = [float(w) for w in shard_walls]
        for k, w in enumerate(walls):
            if k in lost or k not in per_shard_active:
                continue
            others = [
                x for k2, x in enumerate(walls)
                if k2 != k and k2 not in lost
            ]
            if not others:
                continue
            med = float(np.median(others))
            if w > max(wall_floor_s, deadline_ratio * med):
                lost[k] = "wall"
    return lost


def _fleet_workdir(*paths: Optional[str]) -> Optional[str]:
    """Directory the flight recorder drops postmortem bundles into: the
    parent of the first persisted fleet artifact (None for a fully
    in-memory run — no artifacts, no forensics destination)."""
    for p in paths:
        if p:
            return os.path.dirname(os.path.abspath(p))
    return None


class _ProblemState:
    """Host-side bookkeeping for one problem (device state lives stacked
    in the batch arrays; this is everything per-problem the gate,
    persistence, resume — and now the per-problem FAULT DOMAIN — need).

    ``ess_target`` / ``deadline_s`` / ``max_restarts`` are the resolved
    per-problem budget (spec budget, fleet default where unset);
    ``lane_restarts`` counts in-place reseeds of this problem's lane,
    and ``failed`` (a fault-class string) marks a terminal quarantine.
    """

    __slots__ = (
        "idx", "pid", "key", "hist", "suff", "blocks_done",
        "next_full_check", "grad_evals", "total_div", "converged",
        "budget_exhausted", "history", "min_ess", "max_rhat",
        "ess_target", "deadline_s", "max_restarts", "lane_restarts",
        "failed", "failed_reason", "submitted", "warmstarted",
        "warmup_draws_saved", "job_id",
    )

    def __init__(self, idx: int, pid: str, key, chains: int, ndim: int, *,
                 ess_target: float, deadline_s: Optional[float],
                 max_restarts: int, submitted: bool = False):
        self.idx = idx
        self.pid = pid
        self.key = key
        self.ess_target = ess_target
        self.deadline_s = deadline_s
        self.max_restarts = max_restarts
        self.lane_restarts = 0
        self.failed: Optional[str] = None
        self.failed_reason: Optional[str] = None
        self.history: List[Dict[str, Any]] = []
        # streaming/warm-start accounting: whether the problem arrived
        # through a FleetFeed, whether its warmup was donor-seeded, and
        # the warmup draws/chain the shortened schedule skipped
        self.submitted = submitted
        self.warmstarted = False
        self.warmup_draws_saved = 0
        # lineage correlation id (stark_tpu.lineage); None with
        # STARK_LINEAGE=0 so knob-off checkpoints stay byte-identical
        self.job_id: Optional[str] = None
        self._reset(chains, ndim)

    def _reset(self, chains: int, ndim: int) -> None:
        """Cold-lane bookkeeping: everything a reseed discards."""
        self.hist = diagnostics.DrawHistory(chains, ndim)
        self.suff = diagnostics.ChainSuffStats(chains, ndim)
        self.blocks_done = 0
        self.next_full_check = 0
        self.grad_evals = 0
        self.total_div = 0
        self.converged = False
        self.budget_exhausted = False
        self.min_ess: Optional[float] = None
        self.max_rhat: Optional[float] = None

    def reseed(self, key, chains: int, ndim: int) -> None:
        """Cold-restart this problem's lane in place: discard its draws
        and diagnostics, take the attempt-folded key.  ``lane_restarts``
        is the one counter a reseed must NOT reset — it is the budget."""
        self.key = key
        self._reset(chains, ndim)
        # a reseeded lane re-warms COLD (full schedule, fresh stream):
        # any donor transfer it got at admission is gone with the lane
        self.warmstarted = False
        self.warmup_draws_saved = 0

    @property
    def active(self) -> bool:
        return not (
            self.converged or self.budget_exhausted or self.failed
        )

    @property
    def status(self) -> str:
        return _status_string(
            self.failed, self.converged, self.budget_exhausted,
            default="active",
        )

    def meta(self) -> Dict[str, Any]:
        # only the LAST block record rides in the checkpoint: the full
        # per-problem trail is already durable in the metrics JSONL, and
        # serializing O(blocks) history per problem per checkpoint would
        # make fleet checkpoints O(B*blocks^2) over a run.  The
        # streaming/warm-start keys ride ONLY when set (a knob-off run's
        # checkpoint stays byte-identical to pre-slot-scheduler files).
        extra = {}
        if self.submitted:
            extra["submitted"] = True
        if self.warmstarted:
            extra["warmstarted"] = True
            extra["warmup_draws_saved"] = self.warmup_draws_saved
        if self.job_id is not None:
            # lineage rides only when minted: a STARK_LINEAGE=0 run's
            # checkpoint stays byte-identical to pre-lineage files
            extra["job_id"] = self.job_id
        return {
            **extra,
            "blocks_done": self.blocks_done,
            "draws": self.hist.rows,
            "next_full_check": self.next_full_check,
            "grad_evals": self.grad_evals,
            "num_divergent": self.total_div,
            "converged": self.converged,
            "budget_exhausted": self.budget_exhausted,
            "history_tail": self.history[-1:],
            "min_ess": self.min_ess,
            "max_rhat": self.max_rhat,
            # fault-domain state: a quarantined lane STAYS quarantined
            # across supervised restarts, and a resumed lane's reseed
            # budget picks up where the crashed attempt left it
            "lane_restarts": self.lane_restarts,
            "failed": self.failed,
            "failed_reason": self.failed_reason,
        }

    def load_meta(self, m: Dict[str, Any]) -> None:
        self.blocks_done = int(m.get("blocks_done", 0))
        self.next_full_check = int(m.get("next_full_check", 0))
        self.grad_evals = int(m.get("grad_evals", 0))
        self.total_div = int(m.get("num_divergent", 0))
        self.converged = bool(m.get("converged", False))
        self.budget_exhausted = bool(m.get("budget_exhausted", False))
        self.history = list(m.get("history_tail", m.get("history", [])))
        self.min_ess = m.get("min_ess")
        self.max_rhat = m.get("max_rhat")
        self.lane_restarts = int(m.get("lane_restarts", 0))
        self.failed = m.get("failed")
        self.failed_reason = m.get("failed_reason")
        self.submitted = bool(m.get("submitted", self.submitted))
        self.warmstarted = bool(m.get("warmstarted", False))
        self.warmup_draws_saved = int(m.get("warmup_draws_saved", 0))
        jid = m.get("job_id")
        if jid is not None:
            # a resumed tenant keeps its minted id (and re-arms the
            # annotator's registry in the resuming process)
            self.job_id = jid
            lineage.register(self.pid, jid)


@_profile.entrypoint
def sample_fleet(spec: FleetSpec, data: Any = None, **kwargs) -> FleetResult:
    """Advance a fleet of independent posteriors — one vmapped dispatch
    per block — until every problem converges or exhausts its budget.
    See the module docstring for the contract; `_sample_fleet` for the
    parameter reference.  The thin wrapper pins the telemetry trace as
    ambient for the whole run (same discipline as the single runner) and
    applies the autotuned profile's knob defaults — including the
    STARK_FLEET_* trio read below in `_sample_fleet` — before any knob
    read (stark_tpu.profile; explicit env wins, STARK_PROFILE=0 off)."""
    if data is not None:
        raise TypeError(
            "sample_fleet takes per-problem data via FleetSpec, not a "
            "shared data argument"
        )
    trace = telemetry.resolve_trace(kwargs.pop("trace", None))
    with telemetry.use_trace(trace):
        return _sample_fleet(spec, trace=trace, **kwargs)


def _sample_fleet(
    spec: FleetSpec,
    *,
    chains: int = 4,
    block_size: int = 100,
    max_blocks: int = 50,
    min_blocks: int = 2,
    rhat_target: float = 1.01,
    ess_target: float = 400.0,
    seed: int = 0,
    fleet: Optional[bool] = None,
    max_batch: Optional[int] = None,
    refill_occupancy: float = 0.5,
    checkpoint_path: Optional[str] = None,
    resume_from: Optional[str] = None,
    metrics_path: Optional[str] = None,
    draw_store_path: Optional[str] = None,
    health_check: bool = False,
    reseed: Optional[int] = None,
    time_budget_s: Optional[float] = None,
    problem_max_restarts: int = 1,
    stream_diag: Optional[bool] = None,
    diag_lags: Optional[int] = None,
    diag_components: int = 64,
    feed: Optional[FleetFeed] = None,
    slots: Optional[bool] = None,
    warmstart: Optional[bool] = None,
    warmstart_warmup: Optional[int] = None,
    donor_pool: Optional[DonorPool] = None,
    mesh: Optional[Any] = None,
    trace: Optional[Any] = None,
    **cfg_kwargs,
) -> FleetResult:
    """The fleet block loop.

    Each problem ``i`` owns the PRNG stream ``PRNGKey(seed + i)`` and the
    single-problem runner's exact key discipline (init/warmup split, one
    ``split`` per dispatched block), so its draws are independent of the
    batch composition and bit-identical to
    ``sample_until_converged(seed=seed+i, adaptive_blocks=False,
    block_size=block_size)`` run unbatched.

    ``max_batch``: device-batch capacity.  Problems beyond it queue;
    compaction events refill the batch from the queue (new cohorts are
    warmed up in one vmapped dispatch before joining).  Default: the
    whole fleet in one batch.

    ``refill_occupancy``: when the ACTIVE fraction of the current batch
    drops strictly below this, converged lanes are compacted out at the
    next block boundary (and the batch refilled from the queue).  1.0
    compacts immediately on any convergence; 0.0 never compacts (masked
    lanes ride along — their gradient evaluations still stop counting).

    ``time_budget_s`` bounds the SAMPLING wall like the single runner:
    the run stops after the first block past the budget, marking the
    still-active problems ``budget_exhausted`` (a problem that already
    converged is NEVER re-marked — its result is final).

    **Per-problem fault domains.**  With ``health_check`` on, the
    post-block finite scan runs PER LANE: a problem whose carried state
    goes non-finite is reseeded in place (cold lane restart under an
    attempt-folded key — `_LANE_RESEED_SALT` keeps the stream off every
    neighbor's and off the supervisor's attempt folds) up to its
    ``max_restarts`` budget (`ProblemBudget.max_restarts`, default
    ``problem_max_restarts``), then QUARANTINED: masked like a converged
    problem, its draw store quarantined with the reason persisted
    (`supervise.quarantine_path`), terminal status
    ``failed:poisoned_state`` — while the other B-1 lanes continue with
    bit-identical draws to an uninjected fleet.  Whole-fleet restart is
    reserved for process-level faults (crash / stall / corrupt FLEET
    checkpoint); a single problem's corrupt draw store detected on
    resume is likewise contained (quarantine + lane reseed).  Per-problem
    ``deadline_s`` (wall since this run's start) trips a problem into
    ``budget_exhausted`` without touching its neighbors.  The fleet then
    completes DEGRADED: `FleetResult.degraded` / ``lost_problems`` name
    what was lost (mirroring degraded consensus).

    Escape hatch: ``fleet=False`` (or ``STARK_FLEET=0``) and every B=1
    fleet run the problems sequentially through the unmodified
    `runner.sample_until_converged` — bit-identical artifacts to the
    single-problem path (per-problem budgets and `ChainHealthError`
    containment are honored there too, but a reseeded lane's retry
    stream differs from the vmapped path's fold — reseeds are a recovery
    path, not part of the identity contract).

    **Fixed-capacity lane slots** (``slots=True`` / ``STARK_FLEET_SLOTS=1``,
    default OFF — off preserves the compaction path bit-identically).
    The compiled batch shape is pinned for the whole run: the batch is
    never compacted, and when a lane goes terminal (converged /
    quarantined / budget-exhausted) a queued problem is admitted IN
    PLACE — its stacked data, fresh PRNG lane (the same ``seed + i``
    discipline, so draws stay batch-composition-independent), warmup
    carry, and `StreamDiagState` are scattered into the freed slot
    inside the already-compiled dispatch.  Steady-state churn therefore
    triggers ZERO batched-scan re-specializations after the first
    compile (`FleetResult.block_scan_compiles`; ``compile`` trace
    phases with ``stage="fleet_block_scan"`` are the span evidence).
    Admission waves re-run the SAME full-width compiled warmup (freed
    slots padded with discarded dummy lanes), so the warmup program is
    not re-specialized either; only the rare lane-fault rewarm path
    still compiles at cohort width.

    **Streaming admission** (``feed=FleetFeed()``): problems submitted
    while the fleet runs are drained at block boundaries, validated
    against the spec's batched-data contract, seeded ``seed + i`` with
    ``i`` their global arrival index, and queued for admission.  An open
    feed keeps the loop alive (a long-lived serving loop); consumed
    submissions are persisted in the fleet checkpoint so crash-resume
    replays the admission order bit-identically.  PR 9 fault domains
    (budgets, quarantine, deadlines) apply to admitted problems
    unchanged.

    **Device-parallel fleet** (``mesh=`` / ``STARK_FLEET_MESH``, default
    OFF — off is bit-identical to the single-device fleet).  The problem
    axis shards over the mesh "problems" axis inside `_FleetParts`
    (`parallel.primitives.map_shards`); draws are bit-identical per
    problem to the unsharded run, the host loop is unchanged (it reads
    the gathered global view), and every fault-domain/slot/streaming
    feature composes per shard.  Widths pad up to the shard count with
    discarded lane-0 replicas; per-shard occupancy rides ``fleet_block``
    events and the ``stark_fleet_shard_occupancy`` gauge.  The
    sequential hatch has no problem axis and ignores a requested mesh
    (with a warning).

    **Warm-start adaptation transfer** (``warmstart=True`` /
    ``STARK_FLEET_WARMSTART=1``, default OFF; slot-scheduler path only).
    An admitted problem seeds its step size and mass-matrix diagonal
    from the `DonorPool` mean of COMPLETED problems (keyed by model
    tag; donor summaries validated finite on write and read) and runs a
    short adapt-confirm warmup (``warmstart_warmup``, default
    ``max(50, num_warmup // 4)``) instead of the full schedule.  The
    full split-R-hat/ESS validation pass still gates every stop, so
    warm-start can only change WHEN a problem converges.
    """
    cfg = SamplerConfig(**cfg_kwargs)
    if cfg.kernel == "chees":
        raise ValueError(
            "fleet sampling supports the per-chain kernels (nuts/hmc); "
            "the chees ensemble warmup has its own host loop"
        )
    if jax.process_count() > 1:
        # the structured twin of the sequential-hatch warning: name the
        # capability boundary, the knob that crossed it, and the
        # supported way down — so a control plane (and the two-process
        # smoke) can branch on the message instead of a bare exception
        raise CapabilityError(
            f"fleet sampling is single-process for now (this run has "
            f"{jax.process_count()} processes; multi-process meshes "
            "shard chains, not problems)",
            knob="mesh=/STARK_FLEET_MESH",
            fallback="run one fleet per process, or STARK_FLEET=0 for "
                     "the sequential per-problem sweep",
        )
    if stream_diag is None:
        stream_diag = os.environ.get("STARK_STREAM_DIAG", "1") != "0"
    if diag_lags is None:
        diag_lags = STREAM_DIAG_LAGS
    # step-synchronized NUTS scheduling (STARK_RAGGED_NUTS): the fleet is
    # where the B x chains lane product makes max-tree sync worst — the
    # ragged block runners let every lane advance its own tree and add a
    # (problems, chains) lane-iteration output for occupancy accounting
    from .kernels.nuts_ragged import ragged_nuts_enabled

    ragged = ragged_nuts_enabled(cfg)

    # a feed implies fleet semantics even at B=1: the batch grows as
    # submissions arrive, so the vmapped path owns the run whenever the
    # fleet flag is on and a feed is attached
    use_fleet = _resolve_fleet_flag(fleet) and (
        spec.num_problems > 1 or feed is not None
    )
    if not use_fleet:
        if mesh is not None:
            # the escape hatch ALWAYS wins: a sequential sweep has no
            # problem axis to shard, so a requested mesh is dropped
            # loudly, never silently half-honored
            log.warning(
                "sequential fleet hatch (STARK_FLEET=0 / B=1): the "
                "requested problems mesh is ignored"
            )
        return _sample_fleet_sequential(
            spec, chains=chains, block_size=block_size,
            max_blocks=max_blocks, min_blocks=min_blocks,
            rhat_target=rhat_target, ess_target=ess_target, seed=seed,
            checkpoint_path=checkpoint_path, resume_from=resume_from,
            metrics_path=metrics_path, draw_store_path=draw_store_path,
            health_check=health_check, reseed=reseed,
            time_budget_s=time_budget_s, stream_diag=stream_diag,
            diag_lags=diag_lags, diag_components=diag_components,
            problem_max_restarts=problem_max_restarts,
            feed=feed, trace=trace, **cfg_kwargs,
        )
    slots_on = _resolve_slots_flag(slots)
    warmstart_on = slots_on and _resolve_warmstart_flag(warmstart)
    # device-parallel fleet (STARK_FLEET_MESH / mesh=): the problem axis
    # shards over the mesh "problems" axis inside _FleetParts — every
    # host-side decision below runs on the gather_tree'd global view
    # (np.asarray on sharded outputs), so fault domains, budgets, slot
    # admission, and checkpoints are untouched by the device layout
    fleet_mesh = _resolve_fleet_mesh(mesh)
    n_shards = 1

    trace = telemetry.resolve_trace(trace)
    t_start = time.perf_counter()
    model = spec.model
    fm, _parts_cached = _fleet_parts_for(model, cfg, fleet_mesh)
    n_shards = _parts_cached.shards
    B = spec.num_problems
    # postmortem flight recorder: per-problem quarantines and deadline
    # blows dump a forensic bundle next to the fleet's own artifacts
    # (under a supervisor the workdir is already set to the same
    # directory — and the supervisor's scoped install is what feeds the
    # ring; an unsupervised fleet still dumps its triggering records)
    recorder = telemetry.flight_recorder()
    recorder.set_workdir(
        _fleet_workdir(checkpoint_path, metrics_path, draw_store_path)
    )
    # statistical-health observatory (stark_tpu.health): one host-side
    # monitor per PROBLEM, fed from the gathered block readbacks below —
    # warnings are per-tenant trace events (problem_id-tagged) and the
    # terminal verdict rides the per-problem result.  Entirely outside
    # the compiled dispatches: draws/metrics/checkpoints are
    # bit-identical with it on, and STARK_HEALTH=0 removes the extra
    # device->host energy/accept gathers too.
    health_on = _health.health_enabled()
    monitors: Dict[str, _health.HealthMonitor] = {}
    health_verdicts: Dict[str, List[str]] = {}
    # shard-imbalance straggler trail (PR 16): on mesh runs the host
    # times each shard's output readiness after dispatch (the per-shard
    # comm trail that feeds fleet_block shard_walls fields and the
    # windowed ``mesh_imbalance`` health warning).  Rides ONLY mesh +
    # STARK_COMM_TELEMETRY runs — knob-off traces stay byte-identical.
    from .parallel.primitives import comm_telemetry_enabled

    comm_on = comm_telemetry_enabled()
    shard_trail = (
        _health.ShardBalanceTrail(trace=trace)
        if fleet_mesh is not None and comm_on and health_on
        else None
    )
    # SLO burn-rate trail (lineage observatory): block-cadence slo_burn
    # events per budgeted tenant + the once-per-(tenant, budget)
    # ``budget_burn`` health warning.  Rides ONLY lineage-on runs —
    # STARK_LINEAGE=0 traces stay byte-identical to the pre-lineage repo.
    lineage_on = lineage.enabled()
    burn_trail = (
        _health.BudgetBurnTrail(trace=trace)
        if lineage_on and health_on else None
    )
    # elastic fault domains (PR 17): STARK_SHARD_DEADLINE arms the
    # per-shard deadman on mesh runs — None (the default) disables the
    # whole subsystem and keeps traces byte-identical
    shard_deadline = (
        _resolve_shard_deadline() if fleet_mesh is not None else None
    )
    lost_shard_ids: List[int] = []
    # producer-thread feed rejects must emit on THIS run's trace bus
    # (the ambient ContextVar does not cross threads)
    if feed is not None:
        feed._trace = trace

    def monitor_for(p):
        m = monitors.get(p.pid)
        if m is None:
            m = monitors[p.pid] = _health.HealthMonitor(
                kernel=cfg.kernel, max_depth=cfg.max_tree_depth,
                trace=trace, problem_id=p.pid,
            )
        return m

    def finalize_monitor(p):
        """Terminal per-problem verdict: finalize the monitor (end-of-run
        R-hat/ESS warnings) and bank the sorted warning names."""
        m = monitors.pop(p.pid, None)
        if m is not None:
            health_verdicts[p.pid] = m.finalize(
                converged=p.converged, max_rhat=p.max_rhat,
                min_ess=p.min_ess,
            )
        else:
            health_verdicts.setdefault(p.pid, [])
    if trace.enabled:
        trace.emit(
            "run_start",
            entry="sample_fleet",
            fleet=True,
            model=type(model).__name__,
            kernel=cfg.kernel,
            problems=B,
            chains=chains,
            block_size=block_size,
            max_blocks=max_blocks,
            rhat_target=rhat_target,
            ess_target=ess_target,
            resuming=bool(resume_from),
            # mesh-parallel fleet accounting rides ONLY mesh runs, so
            # knob-off trace files stay byte-identical to PR 13
            **({"fleet_shards": n_shards} if fleet_mesh is not None else {}),
            # {"profile": id} when an autotuned profile steers this run;
            # ABSENT otherwise (byte-identical traces)
            **_profile.run_start_tags(),
            **telemetry.device_info(),
            **telemetry.provenance(),
        )
    with trace.phase("compile", stage="fleet_setup"):
        fdata_all = spec.prepared_stacked()
        parts = _parts_cached

    # the store holds no file handles until the first append (per-problem
    # files open lazily), so creating it BEFORE the metrics handle means
    # neither constructor failing can strand the other's open fd
    store = (
        FleetDrawStore(draw_store_path, chains, fm.ndim)
        if draw_store_path else None
    )
    metrics_f = open(metrics_path, "a") if metrics_path else None
    metrics_buf: List[str] = []

    def emit(rec):
        # records buffer within one fleet-block cycle and hit disk as ONE
        # write+flush+fsync at the block boundary (`flush_metrics`): a
        # 256-problem block emits O(B) records, and per-record fsyncs
        # would serialize exactly the per-problem host overhead the fleet
        # exists to amortize.  The crash-relevant boundaries (the
        # fleet.block.* failpoints, the checkpoint) all sit AFTER the
        # flush, so the durability story is unchanged at block
        # granularity — the same unit the checkpoint accounts in.
        telemetry.notify_progress()
        if metrics_f:
            metrics_buf.append(json.dumps(rec) + "\n")

    def flush_metrics():
        if metrics_f and metrics_buf:
            metrics_f.write("".join(metrics_buf))
            metrics_buf.clear()
            metrics_f.flush()
            os.fsync(metrics_f.fileno())

    def _cold_key(i: int):
        k = jax.random.PRNGKey(seed + i)
        if reseed is not None:
            # the supervisor bumps seed by the attempt number on reseeded
            # restarts; over a fleet that bump ALIASES neighbor lattices
            # (seed+attempt+i == seed+(i+attempt)), so a cold-started
            # problem would replay a stream a neighbor consumed in the
            # crashed attempt — folding the attempt in decorrelates them
            # (resumed problems get the same fold on their saved keys)
            k = jax.random.fold_in(k, reseed)
        return k

    def _lane_key(i: int, restarts: int):
        """Key for lane-reseed attempt ``restarts`` of problem ``i`` —
        salted so it can never alias the problem's own cold stream, a
        neighbor's, or any supervisor attempt fold."""
        k = jax.random.fold_in(_cold_key(i), _LANE_RESEED_SALT)
        return jax.random.fold_in(k, restarts)

    def _budget_for(i: int):
        if i < B:
            b = spec.budget_for(i)
        else:
            b = submitted_budgets.get(all_ids[i]) or _DEFAULT_BUDGET
        ess, deadline, mr = b.resolve(ess_target, problem_max_restarts)
        return dict(ess_target=ess, deadline_s=deadline, max_restarts=mr)

    probs = [
        _ProblemState(
            i, spec.problem_ids[i], _cold_key(i), chains, fm.ndim,
            **_budget_for(i),
        )
        for i in range(B)
    ]
    if lineage.enabled():
        # direct-entry parity: spec problems (no FleetFeed front door)
        # mint at registration, same (pid, global ordinal) discipline —
        # a feed-submitted pid resuming through the spec keeps its id
        for p in probs:
            p.job_id = lineage.job_for(p.pid) or lineage.mint_job_id(
                p.pid, p.idx
            )
            lineage.register(p.pid, p.job_id)

    # dynamic problem registry: streamed submissions (FleetFeed) extend
    # the spec's problem list at block boundaries.  ``all_ids[i]`` is
    # problem i's id for EVERY global index; submitted problems keep
    # their raw datasets around so the fleet checkpoint can persist the
    # queue (crash-resume replays the admission order bit-identically).
    all_ids: List[str] = list(spec.problem_ids)
    submitted_raw: Dict[str, PyTree] = {}
    submitted_order: List[str] = []
    submitted_budgets: Dict[str, Optional[ProblemBudget]] = {}
    submitted_leaves: Dict[str, int] = {}
    # submitted pids the LAST persisted checkpoint covers: anything
    # outside this set is requeued to the feed on an abnormal exit, so
    # the drain->checkpoint window can never lose a submission
    last_ckpt_pids: set = set()

    # warm-start adaptation transfer: donor summaries of completed
    # problems, keyed by model tag; the adapt-confirm window replaces
    # the full warmup schedule for donor-seeded admissions.  A caller-
    # provided ``donor_pool`` (e.g. `serving.donor_pool_from_store` — an
    # earlier run's posterior as the donor) seeds the pool for
    # INCREMENTAL reconvergence; without warm-start it is ignored.
    if warmstart_on:
        donor_pool = donor_pool if donor_pool is not None else DonorPool()
    else:
        donor_pool = None
    donor_tag = getattr(model, "tag", type(model).__name__)
    # adapt-confirm window: long enough that the schedule's slow window
    # re-estimates the mass matrix from a usable sample count (a too-
    # short window hands the lane a 20-sample metric and the gate then
    # rightly refuses to converge it — measured, not hypothetical)
    ws_window = (
        min(cfg.num_warmup, max(50, cfg.num_warmup // 4))
        if warmstart_warmup is None
        else min(cfg.num_warmup, max(int(warmstart_warmup), 1))
    )

    # cumulative sampling wall carried ACROSS supervised attempts (the
    # fleet checkpoint persists it): per-problem deadline_s budgets are a
    # tenant contract on total wall, so a crash-looping fleet must not
    # re-grant every tenant a fresh deadline window per attempt
    wall_offset = 0.0

    # device batch: lane j holds problem order[j]; converged lanes stay
    # (masked) until the next compaction
    order: List[int] = []
    state = step_size = inv_mass = diag = None
    bdata = None  # device data for the CURRENT batch; refreshed only
    pending: List[int] = []  # when the batch composition changes
    compactions = 0
    occupancy_trail: List[float] = []
    blocks_dispatched = 0
    fleet_budget_exhausted = False
    # zero-recompile accounting: every DISTINCT batch width the compiled
    # block scan dispatches is one XLA specialization — the slot
    # scheduler's whole point is to hold this at 1
    seen_widths: set = set()
    block_scan_compiles = 0
    n_admissions = 0
    n_slot_recycles = 0
    dispatch_occupancy_trail: List[Tuple[float, int]] = []

    def batch_data(indices: List[int]):
        ix = jnp.asarray(indices)
        picked = jax.tree.map(lambda a: a[ix], fdata_all)
        # mesh runs: pad + commit the slab to the "problems" sharding
        # HERE, once per batch rebuild — the dispatch wrapper's per-call
        # placement then no-ops on it (identity off-mesh)
        return parts.place_batch(picked)

    def warm_cohort(indices: List[int]):
        """Warm up a cohort of problems in one vmapped dispatch; returns
        stacked (state, step_size, inv_mass) with a problem axis.  Key
        layout per lane mirrors the single-problem runner exactly."""
        z0s, wkeys = [], []
        for i in indices:
            p = probs[i]
            p.key, key_init, key_warm = jax.random.split(p.key, 3)
            z0s.append(
                jax.vmap(fm.init_flat)(jax.random.split(key_init, chains))
            )
            wkeys.append(jax.random.split(key_warm, chains))
        z0 = jnp.stack(z0s)
        warm_keys = jnp.stack(wkeys)
        st, ss, im, wdiv = _fleet_warmup(
            parts, cfg, warm_keys, z0, batch_data(indices), block_size, trace
        )
        wdiv = np.asarray(wdiv)
        for j, i in enumerate(indices):
            rec = {
                "event": "warmup_done",
                "problem_id": probs[i].pid,
                "num_divergent": int(wdiv[j].sum()),
                "wall_s": time.perf_counter() - t_start,
            }
            emit(rec)
        return st, ss, im

    def init_diag_for(indices: List[int], histories, dtype):
        """Stacked StreamDiagState for a cohort, rebuilt from each
        problem's (possibly empty) draw history — the same host reference
        accumulator the single runner uses on resume.  ``dtype`` is the
        sampling state's dtype (f64 under x64), matching the carry the
        compiled scan produces — the single runner threads state.z.dtype
        the same way."""
        dtype = np.dtype(dtype)
        stacked = None
        for i, hist in zip(indices, histories):
            draws = (
                hist.view() if hist.rows
                else np.zeros((chains, 0, fm.ndim), np.float32)
            )
            host = diagnostics.stream_diag_from_draws(
                draws, diag_lags, chains=chains, ndim=fm.ndim, dtype=dtype
            )
            if stacked is None:
                stacked = {k: [v] for k, v in host.items()}
            else:
                for k, v in host.items():
                    stacked[k].append(v)
        return StreamDiagState(
            **{k: jnp.asarray(np.stack(v)) for k, v in stacked.items()}
        )

    def concat_batches(a, b):
        return jax.tree.map(
            lambda x, y: jnp.concatenate([x, y], axis=0), a, b
        )

    def take_lanes(tree, lane_idx: List[int]):
        ix = jnp.asarray(lane_idx, dtype=jnp.int32)
        return jax.tree.map(lambda a: a[ix], tree)

    def admit(indices: List[int]):
        """Warm up ``indices`` and append them to the batch."""
        nonlocal state, step_size, inv_mass, diag, order, bdata
        st, ss, im = warm_cohort(indices)
        dg = (
            init_diag_for(indices, [probs[i].hist for i in indices],
                          st.z.dtype)
            if stream_diag else None
        )
        if state is None:
            state, step_size, inv_mass, diag = st, ss, im, dg
        else:
            state = concat_batches(state, st)
            step_size = jnp.concatenate([step_size, ss], axis=0)
            inv_mass = jnp.concatenate([inv_mass, im], axis=0)
            if stream_diag:
                diag = concat_batches(diag, dg)
        order = order + list(indices)
        bdata = batch_data(order)
        flush_metrics()

    def _add_problem(pid: str, data: PyTree,
                     budget: Optional[ProblemBudget]) -> int:
        """Register one streamed submission as a full fleet problem:
        validate against the batched-data contract, append its prepared
        data to the stacked slab, and mint its `_ProblemState` under the
        ``seed + i`` discipline (i = global arrival index)."""
        nonlocal fdata_all
        if pid in set(all_ids):
            raise ValueError(f"problem id {pid!r} already exists")
        check_problem_data(spec.datasets[0], data, pid)
        _check_finite_submission(data, pid)
        # EVERY fallible step runs before the first registry mutation
        # (prepare_data runs arbitrary model code, and a grouped/fused
        # layout's prepared shapes can be value-dependent): a rejected
        # tenant must leave the registry exactly as it found it
        prepared = prepare_model_data(model, data)
        new_slab = jax.tree.map(
            lambda a, b: jnp.concatenate([a, jnp.asarray(b)[None]]),
            fdata_all, prepared,
        )
        i = len(probs)
        all_ids.append(pid)
        submitted_raw[pid] = data
        submitted_order.append(pid)
        submitted_budgets[pid] = budget
        submitted_leaves[pid] = len(jax.tree.leaves(data))
        fdata_all = new_slab
        probs.append(_ProblemState(
            i, pid, _cold_key(i), chains, fm.ndim, submitted=True,
            **_budget_for(i),
        ))
        if lineage.enabled():
            p = probs[i]
            # the feed minted at submit time (registry hit); a direct
            # _add_problem (resume replay) mints at the arrival ordinal
            p.job_id = lineage.job_for(pid) or lineage.mint_job_id(pid, i)
            lineage.register(pid, p.job_id)
        return i

    def _drain_feed() -> int:
        """Consume queued FleetFeed submissions (block-boundary handoff).
        A malformed submission is rejected with a logged reason — one bad
        tenant must not kill the serving loop."""
        if feed is None:
            return 0
        n = 0
        for pid, data, budget in feed.drain():
            try:
                pending.append(_add_problem(pid, data, budget))
                n += 1
            except Exception as e:  # noqa: BLE001 — a bad tenant must
                # not kill the serving loop: the shape check catches
                # structural mistakes (ValueError), but the model's own
                # prepare_data hook runs arbitrary code over the
                # submitted leaves and may raise anything
                log.warning("fleet feed submission %r rejected: %s", pid, e)
                emit({
                    "event": "problem_rejected",
                    "problem_id": pid,
                    "reason": str(e),
                    "wall_s": time.perf_counter() - t_start,
                })
        return n

    def _scatter_lanes(ix, sub, st, ss, im, idxs: List[int]) -> None:
        """Scatter warmed lanes ``sub`` of (st, ss, im) into batch slots
        ``ix`` — the in-place admission write (same ``.at[ix].set``
        pattern as the lane-fault rewarm, so every other lane's arrays
        are untouched)."""
        nonlocal state, step_size, inv_mass, diag
        state = jax.tree.map(lambda a, b: a.at[ix].set(b[sub]), state, st)
        step_size = step_size.at[ix].set(ss[sub])
        inv_mass = inv_mass.at[ix].set(im[sub])
        if stream_diag:
            dg = init_diag_for(
                idxs, [probs[i].hist for i in idxs], st.z.dtype
            )
            diag = jax.tree.map(lambda a, b: a.at[ix].set(b), diag, dg)

    def _warm_slots_padded(pairs: List[Tuple[int, int]], donor,
                           donor_ens=None) -> None:
        """Full-batch-width warmup for an admitted cohort (slot
        scheduler): admitted problems ride their TARGET slots, every
        other lane is a dummy (zero key, zero z0 — vmap lanes are
        independent, outputs discarded), so the shapes match the initial
        cohort warmup exactly and the compiled warmup parts are reused
        with zero re-specialization.  ``donor`` (step, inv_mass_diag,
        count or None) seeds the dual-averaging state and mass diagonal
        and shrinks the schedule to the adapt-confirm window.
        ``donor_ens`` ((chains, d) or None — `DonorPool.ensemble`)
        additionally starts the admitted chains AT the donor posterior's
        final positions (incremental reconvergence): z0 is traced DATA,
        so the override costs zero re-specialization, and the key-split
        discipline below is unchanged (init keys are still split and
        burned) so every neighbor's stream is untouched."""
        js = [j for j, _ in pairs]
        for j, i in pairs:
            p = probs[i]
            p.key, key_init, key_warm = jax.random.split(p.key, 3)
            # placed first so the fill lanes can zeros_like a real lane
            p_z0 = jax.vmap(fm.init_flat)(jax.random.split(key_init, chains))
            if donor_ens is not None and donor_ens.shape[1] == p_z0.shape[1]:
                # donor chains tile/truncate onto the lane's chain count
                p_z0 = jnp.asarray(
                    donor_ens[np.arange(chains) % donor_ens.shape[0]],
                    p_z0.dtype,
                )
            p_wk = jax.random.split(key_warm, chains)
            if j == js[0]:
                z0_l = [jnp.zeros_like(p_z0)] * len(order)
                wk_l = [jnp.zeros_like(p_wk)] * len(order)
            z0_l[j] = p_z0
            wk_l[j] = p_wk
        z0 = jnp.stack(z0_l)
        warm_keys = jnp.stack(wk_l)
        nw = None
        hook = None
        if donor is not None:
            d_step, d_im, _n_donors = donor
            nw = ws_window
            ix_w = jnp.asarray(js, dtype=jnp.int32)

            def hook(h_st, h_da, h_wf, h_im):
                # anchor the dual-averaging stream AT the donor step
                # (mu=log(step), the adaptation.da_init re-tuning form)
                # and hand the lane the donor mass diagonal; the confirm
                # window re-tunes both from there
                ls = jnp.log(jnp.asarray(d_step, h_da.log_step.dtype))
                h_da = DualAveragingState(
                    log_step=h_da.log_step.at[ix_w].set(ls),
                    log_avg_step=h_da.log_avg_step.at[ix_w].set(ls),
                    h_avg=h_da.h_avg.at[ix_w].set(0.0),
                    mu=h_da.mu.at[ix_w].set(ls),
                    count=h_da.count,
                )
                h_im = h_im.at[ix_w].set(jnp.asarray(d_im, h_im.dtype))
                return h_st, h_da, h_wf, h_im

        st, ss, im, wdiv = _fleet_warmup(
            parts, cfg, warm_keys, z0, bdata, block_size, trace,
            num_warmup=nw, seed_hook=hook,
        )
        wdiv = np.asarray(wdiv)
        for j, i in pairs:
            p = probs[i]
            if donor is not None or donor_ens is not None:
                p.warmstarted = True
            if donor is not None:
                p.warmup_draws_saved = max(cfg.num_warmup - ws_window, 0)
            emit({
                "event": "warmup_done",
                "problem_id": p.pid,
                "num_divergent": int(wdiv[j].sum()),
                "warmstart": donor is not None,
                "warmstart_positions": donor_ens is not None,
                "wall_s": time.perf_counter() - t_start,
            })
        ix = jnp.asarray(js, dtype=jnp.int32)
        _scatter_lanes(ix, ix, st, ss, im, [i for _, i in pairs])

    def admit_into_slots(slot_js: List[int], indices: List[int]) -> None:
        """In-place admission: hand freed (masked) batch slots to queued
        problems WITHOUT reshaping the batch.  On the slot-scheduler
        path the cohort warms at full batch width (padded — compiled
        warmup reused); on the legacy top-up path it warms at cohort
        width (legacy never promised pinned shapes) and scatters the
        same way."""
        nonlocal bdata, n_admissions, n_slot_recycles
        for j, i in zip(slot_js, indices):
            old = probs[order[j]]
            n_slot_recycles += 1
            fields = dict(
                slot=j, from_problem=old.pid, from_status=old.status,
                to_problem=probs[i].pid,
            )
            if trace.enabled:
                trace.emit("slot_recycled", **fields)
            emit({
                "event": "slot_recycled", **fields,
                "wall_s": time.perf_counter() - t_start,
            })
            order[j] = i
        bdata = batch_data(order)
        if slots_on:
            # one padded full-width warmup wave; the donor summary is
            # read ONCE per wave (one tag per fleet) — checkpoint-replay
            # determinism rides on the pool state, and the pool is
            # persisted
            donor = (
                donor_pool.summary(donor_tag)
                if donor_pool is not None else None
            )
            donor_ens = (
                donor_pool.ensemble(donor_tag)
                if donor_pool is not None else None
            )
            _warm_slots_padded(
                list(zip(slot_js, indices)), donor, donor_ens
            )
        else:
            st, ss, im = warm_cohort(indices)
            ix = jnp.asarray(slot_js, dtype=jnp.int32)
            sub = jnp.arange(len(indices), dtype=jnp.int32)
            _scatter_lanes(ix, sub, st, ss, im, list(indices))
        for j, i in zip(slot_js, indices):
            p = probs[i]
            n_admissions += 1
            fields = dict(
                problem_id=p.pid,
                slot=j,
                block=blocks_dispatched,
                queue_depth=len(pending),
                warmstart=p.warmstarted,
                warmup_draws_saved=p.warmup_draws_saved,
                source="feed" if p.submitted else "spec",
            )
            if trace.enabled:
                trace.emit("problem_admitted", **fields)
            emit({
                "event": "problem_admitted", **fields,
                "wall_s": time.perf_counter() - t_start,
            })
        flush_metrics()

    def quarantine_problem(p: _ProblemState, fault: str, reason: str,
                           quarantined_as: Optional[str] = None):
        """Terminal per-problem quarantine: mask the lane like a
        converged problem (the surviving B-1 continue untouched), move
        its draw store aside with the REASON persisted
        (`supervise.quarantine_path` + its ``.reason.json`` sidecar),
        and record the loss everywhere a tenant's fate must be visible
        — metrics JSONL, trace (``problem_quarantined``), and through
        the collector /metrics + /status.  ``quarantined_as``: the
        forensic copy's path when the caller already moved the store
        (the resume corrupt-store path) — events must name it either
        way."""
        from .supervise import quarantine_path

        p.failed = fault
        p.failed_reason = reason
        # a poisoned problem's diagnostics are not evidence: they must
        # never leak into aggregate-ESS numerators or bench gates
        p.min_ess = None
        p.max_rhat = None
        if health_on:
            # terminal verdict BEFORE the diagnostics are voided above
            # took effect on the monitor (it holds the stuck_chain
            # warning the containment path just raised)
            finalize_monitor(p)
        if store is not None and quarantined_as is None:
            store.close_problem(p.pid)
            path = store.path(p.pid)
            if os.path.exists(path):
                quarantined_as = quarantine_path(
                    path, reason=f"{p.pid}: {fault}: {reason}"
                )
        log.warning(
            "fleet problem %s quarantined (%s) after %d lane restart(s): "
            "%s", p.pid, fault, p.lane_restarts, reason,
        )
        emit({
            "event": "problem_done",
            "problem_id": p.pid,
            "status": p.status,
            "fault": fault,
            "reason": reason,
            "lane_restarts": p.lane_restarts,
            "max_restarts": p.max_restarts,
            "blocks": p.blocks_done,
            "quarantined_store": quarantined_as,
            "wall_s": time.perf_counter() - t_start,
        })
        # a lost tenant is exactly what the postmortem bundle exists
        # for: emit the quarantine and dump the flight recorder with it
        # as the trigger
        recorder.record_anomaly(
            f"quarantine:{p.pid}",
            trace,
            "problem_quarantined",
            problem_id=p.pid,
            status=p.status,
            fault=fault,
            reason=reason,
            lane_restarts=p.lane_restarts,
            max_restarts=p.max_restarts,
            quarantined_store=quarantined_as,
        )

    def reseed_problem(p: _ProblemState, fault: str, reason: str,
                       quarantined_as: Optional[str] = None) -> bool:
        """One lane fault: cold-restart the lane in place under an
        attempt-folded key when restart budget remains (True), else
        quarantine the problem (False).  The single-run analogue is the
        supervisor's reseeded restart — scoped to ONE lane.
        ``quarantined_as``: forensic copy of an already-quarantined
        store (the resume corrupt-store path), named in the events."""
        p.lane_restarts += 1
        if p.lane_restarts > p.max_restarts:
            quarantine_problem(p, fault, reason,
                               quarantined_as=quarantined_as)
            return False
        if store is not None:
            # the lane's persisted draws are discarded with the lane
            # (close first: truncating under the open async writer races
            # its write offset)
            store.close_problem(p.pid)
            store.truncate(p.pid, 0)
        p.reseed(_lane_key(p.idx, p.lane_restarts), chains, fm.ndim)
        # the reseeded lane is a fresh chain: its health accumulators
        # restart with it (the emitted stuck_chain warning and the
        # lane_restarts count remain the durable evidence)
        monitors.pop(p.pid, None)
        log.warning(
            "fleet problem %s lane reseeded (%s, restart %d/%d): %s",
            p.pid, fault, p.lane_restarts, p.max_restarts, reason,
        )
        extra = (
            {"quarantined_store": quarantined_as}
            if quarantined_as else {}
        )
        emit({
            "event": "problem_reseeded",
            "problem_id": p.pid,
            "fault": fault,
            "reason": reason,
            "lane_restarts": p.lane_restarts,
            "max_restarts": p.max_restarts,
            **extra,
            "wall_s": time.perf_counter() - t_start,
        })
        if trace.enabled:
            trace.emit(
                "problem_reseeded",
                problem_id=p.pid,
                fault=fault,
                reason=reason,
                lane_restarts=p.lane_restarts,
                max_restarts=p.max_restarts,
                **extra,
            )
        return True

    def finish_problem(p: _ProblemState, **extra):
        """A problem reached a NON-FAULT terminal status (converged /
        budget_exhausted): close its store file (no masked lane ever
        appends again) and announce it — including the per-tenant SLO
        accounting (ESS rate over the cumulative wall, deadline
        headroom, restart burn) the control-plane gauges scrape.
        Returns the announced record (the trace record when tracing is
        on) so callers can hand it to the flight recorder."""
        if store is not None:
            store.close_problem(p.pid)
        status = p.status
        verdict = None
        if health_on:
            # end-of-problem health sweep (may emit high_rhat /
            # low_ess_per_param) BEFORE the terminal announcement below
            finalize_monitor(p)
            verdict = health_verdicts.get(p.pid)
        # SLO rollup on the CUMULATIVE wall (the same clock deadlines
        # charge): what the tenant got, per second, and how much of its
        # deadline / restart budget the run consumed
        elapsed = time.perf_counter() - t_start + wall_offset
        fields = {
            "problem_id": p.pid,
            "status": status,
            "blocks": p.blocks_done,
            "draws_per_chain": int(p.suff.count[0]),
            "grad_evals": p.grad_evals,
            "min_ess": p.min_ess,
            "max_rhat": p.max_rhat,
            "elapsed_s": round(elapsed, 4),
            "ess_rate": (
                round(p.min_ess / elapsed, 4)
                if p.min_ess is not None and elapsed > 0 else None
            ),
            "deadline_s": p.deadline_s,
            "deadline_headroom_s": (
                round(p.deadline_s - elapsed, 4)
                if p.deadline_s is not None else None
            ),
            "lane_restarts": p.lane_restarts,
            "max_restarts": p.max_restarts,
        }
        if p.warmstarted:
            # warm-start accounting rides only donor-seeded problems, so
            # cold runs' terminal records stay byte-identical
            fields["warmstart"] = True
            fields["warmup_draws_saved"] = p.warmup_draws_saved
        if store is not None:
            # posterior-as-a-service summary sidecar
            # (``<store>.summary.json``): moments + quantile sketch +
            # the gate/health verdicts + adaptation state, written ONCE
            # here so a serving summary read never touches draws (and
            # `serving.donor_pool_from_store` can fully re-seed a donor).
            # The fleet is the ONLY writer — the read plane never writes
            # into the store root.  No new trace/metrics events, and a
            # failed write degrades serving, never the run.
            try:
                from . import serving as _serving

                adapt = None
                if step_size is not None and p.idx in order:
                    j_lane = order.index(p.idx)
                    ss_j = np.asarray(step_size)[j_lane]
                    im_j = np.asarray(inv_mass)[j_lane]
                    adapt = {
                        "step_size": float(np.exp(np.mean(np.log(ss_j)))),
                        "inv_mass_diag": np.mean(
                            im_j.reshape(-1, im_j.shape[-1]), axis=0
                        ),
                    }
                    if not (np.isfinite(adapt["step_size"]) and
                            np.all(np.isfinite(adapt["inv_mass_diag"]))):
                        adapt = None
                _serving.write_summary(
                    store.path(p.pid),
                    problem_id=p.pid,
                    model_tag=donor_tag,
                    status=status,
                    min_ess=p.min_ess,
                    max_rhat=p.max_rhat,
                    health=verdict,
                    adaptation=adapt,
                    # lineage: the sidecar carries job_id across the
                    # process boundary to the read plane, so a serving
                    # daemon's serve_request events correlate back to
                    # this run; rides only when minted (STARK_LINEAGE=0
                    # sidecars stay byte-identical)
                    **({"extra": {"job_id": p.job_id}}
                       if p.job_id is not None else {}),
                )
            except Exception as e:  # noqa: BLE001 — serving is best-effort
                log.warning(
                    "summary sidecar for %s failed (%s: %s)",
                    p.pid, type(e).__name__, e,
                )
        fields.update(extra)
        emit({"event": "problem_done", **fields})
        # the health verdict rides ONLY the trace event (and only when
        # non-empty): the metrics JSONL record above stays byte-identical
        # to the pre-observatory fleet
        trace_fields = (
            dict(fields, health=verdict) if verdict else fields
        )
        emitted = (
            trace.emit("problem_converged", **trace_fields)
            if trace.enabled else None
        )
        return emitted or {"event": "problem_converged", **trace_fields}

    def poison_lane_site(st):
        """``fleet.lane_nan`` (action ``nan``, arg = problem ordinal,
        default 0): NaN-fill ONE problem's lanes of the carried state —
        the injection the B-1 bit-identity invariant is drilled
        against.  An inactive/absent target fizzles (the shot is still
        consumed, matching `kill_shards`)."""
        act = faults.fail_point("fleet.lane_nan")
        if act is None or act.kind != "nan":
            return st
        target = act.arg_int(0)
        for j, i in enumerate(order):
            if i == target and probs[i].active:
                lane = jnp.asarray(j)

                def bad(x, lane=lane):
                    x = jnp.asarray(x)
                    if jnp.issubdtype(x.dtype, jnp.floating):
                        return x.at[lane].set(jnp.nan)
                    return x

                return jax.tree.map(bad, st)
        return st

    def kill_shard_site(st):
        """``fleet.shard_dead`` (action ``kill``, arg = shard ordinal):
        NaN-fill EVERY lane of one mesh shard of the carried state — the
        deterministic whole-shard death the deadman + degraded re-shard
        are drilled against (the mesh twin of ``fleet.lane_nan``; the
        `faults.kill_shards` idiom applied to the fleet's problem axis).
        Fizzles off-mesh or on a shard past the current width (the shot
        is still consumed)."""
        act = faults.fail_point("fleet.shard_dead")
        if act is None or act.kind != "kill":
            return st
        if fleet_mesh is None or n_shards < 2:
            log.warning(
                "failpoint fleet.shard_dead fired off-mesh: fizzled"
            )
            return st
        k = act.arg_int(0)
        width = parts.padded_width(len(order))
        lanes_per = width // n_shards
        lo, hi = k * lanes_per, (k + 1) * lanes_per
        if not 0 <= k < n_shards:
            log.warning(
                "failpoint fleet.shard_dead: shard %d outside mesh of "
                "%d: fizzled", k, n_shards,
            )
            return st

        def bad(x):
            x = jnp.asarray(x)
            if jnp.issubdtype(x.dtype, jnp.floating):
                return x.at[lo:hi].set(jnp.nan)
            return x

        return jax.tree.map(bad, st)

    def corrupt_one_store_site():
        """``fleet.ckpt_corrupt_one`` (action ``corrupt``): tear the
        header of the FIRST ACTIVE problem's draw store right after the
        checkpoint-boundary flush — per-problem-artifact bitrot, which
        the per-problem resume path must detect and CONTAIN (quarantine
        + lane reseed) instead of failing the fleet resume."""
        act = faults.fail_point("fleet.ckpt_corrupt_one")
        if act is None or act.kind != "corrupt" or store is None:
            return
        for i in order:
            path = store.path(probs[i].pid)
            if probs[i].active and os.path.exists(path):
                with open(path, "r+b") as f:
                    f.write(b"\xde\xad\xbe\xef" * 6)
                log.warning(
                    "failpoint fleet.ckpt_corrupt_one: tore the header "
                    "of %s", path,
                )
                return

    # ---- resume or cold start --------------------------------------------
    # the handles above (metrics file, per-problem draw stores) are
    # closed by the block loop's finally; anything that raises BEFORE
    # that try is entered — resume validation, the first cohort's
    # warmup — must not leak them across supervised restart attempts
    try:
        if resume_from:
            from .checkpoint import load_checkpoint

            arrays, meta = load_checkpoint(resume_from)
            if not meta.get("fleet"):
                raise ValueError(
                    f"{resume_from!r} is not a fleet checkpoint"
                )
            if meta.get("kernel") != cfg.kernel:
                raise ValueError(
                    f"checkpoint was written by kernel={meta.get('kernel')!r}, "
                    f"resuming run uses kernel={cfg.kernel!r}"
                )
            # chains shapes every per-problem array; block_size sets the
            # key split cadence — a mismatch would not fail loudly on its
            # own (chains dies in a deep shape error, block_size silently
            # breaks the bit-identical replay the chaos drills rely on)
            for field, current in (("chains", chains),
                                   ("block_size", block_size)):
                if meta.get(field) != current:
                    raise ValueError(
                        f"checkpoint was written with "
                        f"{field}={meta.get(field)!r}, resuming run uses "
                        f"{field}={current!r}"
                    )
            saved_ids = list(meta["problem_ids"])
            nspec = len(spec.problem_ids)
            if saved_ids[:nspec] != list(spec.problem_ids):
                raise ValueError(
                    "checkpointed problem_ids differ from this FleetSpec"
                )
            # streamed submissions consumed before the crash: rebuild
            # them (data leaves + budget, in arrival order) so the
            # resumed run replays the admission order bit-identically —
            # the caller does not re-submit what the checkpoint owns
            saved_submitted = list(meta.get("submitted", []))
            if saved_ids[nspec:] != [s["pid"] for s in saved_submitted]:
                raise ValueError(
                    "checkpointed submitted problems are inconsistent "
                    "with its problem_ids"
                )
            ref_struct = jax.tree.structure(spec.datasets[0])
            for s in saved_submitted:
                pid = s["pid"]
                if s.get("data", True):
                    leaves = [
                        arrays[f"feed_{pid}_{k}"]
                        for k in range(int(s["leaves"]))
                    ]
                    data = jax.tree.unflatten(ref_struct, leaves)
                else:
                    # terminal before the crash: its draws are durable
                    # and a terminal problem is never re-sampled (a
                    # corrupt store quarantines it, never re-serves it),
                    # so a zero placeholder keeps the index space dense
                    # without carrying dead data
                    data = jax.tree.map(np.zeros_like, spec.datasets[0])
                budget = (
                    ProblemBudget(**s["budget"])
                    if s.get("budget") is not None else None
                )
                _add_problem(pid, data, budget)
            # checkpoint-born submissions are by definition covered by a
            # durable checkpoint: never requeued to the feed on a crash
            last_ckpt_pids.update(s["pid"] for s in saved_submitted)
            if donor_pool is not None and meta.get("donor_pool"):
                donor_pool.load_state(meta["donor_pool"])
            from .supervise import quarantine_path

            wall_offset = float(meta.get("elapsed_wall_s", 0.0))
            per_problem = meta["problems"]
            for p in probs:
                p.load_meta(per_problem[p.pid])
            # draw histories: store wins (truncated to the accounted rows);
            # otherwise the checkpoint carries them inline
            corrupt_cold: List[int] = []
            for p in probs:
                accounted = int(per_problem[p.pid].get("draws", 0))
                blk = None
                if store is not None:
                    try:
                        store.truncate(p.pid, accounted)
                        blk = store.read(p.pid)
                    except Exception as e:  # noqa: BLE001 — contained below
                        # ONE problem's persisted draws are unreadable: a
                        # per-problem artifact fault, not a fleet fault —
                        # the store is quarantined with the reason and the
                        # problem cold-restarts against its lane budget
                        # (fleet.ckpt_corrupt_one drills this); the other
                        # B-1 problems resume untouched
                        reason = f"{type(e).__name__}: {e}"
                        store.close_problem(p.pid)
                        quarantined_as = None
                        if os.path.exists(store.path(p.pid)):
                            quarantined_as = quarantine_path(
                                store.path(p.pid),
                                reason=f"{p.pid}: {_FAULT_CORRUPT}: "
                                       f"{reason}",
                            )
                        if p.active:
                            if reseed_problem(
                                p, _FAULT_CORRUPT, reason,
                                quarantined_as=quarantined_as,
                            ):
                                corrupt_cold.append(p.idx)
                        elif not p.failed:
                            # a finished problem's draws are gone for
                            # good: the fleet completes degraded around
                            # it rather than re-serving proven work off
                            # garbage bytes
                            p.converged = False
                            p.budget_exhausted = False
                            quarantine_problem(
                                p, _FAULT_CORRUPT, reason,
                                quarantined_as=quarantined_as,
                            )
                        blk = None
                elif f"draws_{p.pid}" in arrays:
                    blk = arrays[f"draws_{p.pid}"]
                if blk is not None and blk.shape[1]:
                    p.hist.append(np.asarray(blk))
                    p.suff.update(np.asarray(blk))
            active_ids = list(meta["active_ids"])
            by_id = {p.pid: p for p in probs}
            keys = np.asarray(arrays["keys"])
            # lanes to RESUME from the saved arrays: still-active
            # problems whose stores survived (quarantined problems stay
            # quarantined; corrupt-store ones cold-start via pending)
            cold = set(corrupt_cold)
            keep = [
                j for j, a in enumerate(active_ids)
                if by_id[a].active and by_id[a].idx not in cold
            ]
            order = [by_id[active_ids[j]].idx for j in keep]
            for j in keep:
                k = jnp.asarray(keys[j])
                if reseed is not None:
                    k = jax.random.fold_in(k, reseed)
                by_id[active_ids[j]].key = k
            if order:
                ix = np.asarray(keep, dtype=np.int64)
                state = HMCState(
                    z=jnp.asarray(arrays["z"][ix]),
                    potential_energy=jnp.asarray(arrays["pe"][ix]),
                    grad=jnp.asarray(arrays["grad"][ix]),
                )
                step_size = jnp.asarray(arrays["step_size"][ix])
                inv_mass = jnp.asarray(arrays["inv_mass"][ix])
                if stream_diag:
                    diag = init_diag_for(
                        order, [probs[i].hist for i in order],
                        state.z.dtype,
                    )
                bdata = batch_data(order)
            # else: every saved lane had already converged (a crash landed
            # between full convergence and the next cohort's admission) —
            # leave state None so the pending top-up below takes the
            # cold-batch path instead of concatenating onto 0-lane arrays
            in_batch = set(order)
            pending = [
                p.idx for p in probs
                if p.active and p.idx not in in_batch
            ]
            if pending:
                # top the resumed batch back up to capacity (a crash may have
                # landed with the batch partially drained; resuming only the
                # survivors would run the device under-occupied until the
                # next compaction)
                room = (
                    (max_batch - len(order))
                    if max_batch is not None else len(pending)
                )
                if room > 0:
                    nxt, pending = pending[:room], pending[room:]
                    admit(nxt)
        else:
            first = list(range(B if max_batch is None else min(max_batch, B)))
            pending = list(range(len(first), B))
            admit(first)

        v_block = parts.get_block(
            block_size, diag_lags=diag_lags if stream_diag else None,
            ragged=ragged,
        )
        # registered DispatchProbe (profiling): a harness that registers
        # "fleet_block_scan" counts every EXECUTED batched-scan dispatch
        # — paired with the fleet_block_scan compile spans it separates
        # "dispatched N times" from "specialized K times"
        from . import profiling as _profiling

        _probe = _profiling.get_probe("fleet_block_scan")
        v_dispatch = _probe.wrap(v_block) if _probe is not None else v_block
    except BaseException:
        flush_metrics()
        if metrics_f:
            metrics_f.close()
        if store is not None:
            store.close()
        raise

    def gate_and_record(p: _ProblemState, zs, divergent, blk_grads,
                        diag_lane, accept=None, energy=None, ngrad=None):
        """One problem's share of a finished block: diagnostics, gate,
        metrics record — the per-problem twin of the single runner's
        `process_block` (same streaming gate, same full-pass validation,
        same backoff).  ``accept``/``energy``/``ngrad`` are this lane's
        health-observatory readbacks (None when STARK_HEALTH=0)."""
        p.blocks_done += 1
        p.hist.append(zs)
        if store is not None:
            store.append(p.pid, zs)
        p.total_div += int(np.sum(np.asarray(divergent)))
        p.grad_evals += blk_grads
        p.suff.update(zs)
        srhat = p.suff.rhat()
        n_stuck = int(np.count_nonzero(np.isnan(srhat)))
        finite_rhat = srhat[~np.isnan(srhat)]
        max_rhat = (
            float(np.max(finite_rhat)) if finite_rhat.size else float("inf")
        )
        if diag_lane is not None:
            diag_bytes = int(sum(np.asarray(a).nbytes for a in diag_lane))
            ess_vals = diagnostics.ess_from_suffstats(*diag_lane)
        else:
            k = min(diag_components, fm.ndim)
            worst = np.argsort(
                np.where(np.isnan(srhat), -np.inf, -srhat)
            )[:k]
            subset = p.hist.take(worst)
            diag_bytes = int(subset.nbytes)
            ess_vals = diagnostics.ess(subset)
        finite_ess = ess_vals[np.isfinite(ess_vals)]
        min_ess = (
            float(np.min(finite_ess)) if finite_ess.size else float("nan")
        )
        p.min_ess = min_ess if np.isfinite(min_ess) else None
        p.max_rhat = max_rhat if np.isfinite(max_rhat) else None
        rec = {
            "event": "block",
            "problem_id": p.pid,
            "block": p.blocks_done,
            "draws_per_chain": int(p.suff.count[0]),
            "max_rhat": p.max_rhat,
            "min_ess": p.min_ess,
            "num_stuck_components": n_stuck,
            "num_divergent": p.total_div,
            "block_grad_evals": blk_grads,
            "diag_bytes_to_host": diag_bytes,
            "wall_s": time.perf_counter() - t_start,
        }
        min_gate = p.blocks_done >= min_blocks
        gate_pass = (
            n_stuck == 0
            and max_rhat < rhat_target
            and min_ess > p.ess_target
        )
        # same failpoint as the single runner's gate: a forced-optimistic
        # streaming signal sends the candidate stop to the full
        # validation pass early, which must reject it — the PR 4
        # never-stop-past-failed-validation guard drills the fleet gate
        # through the identical site
        forced_opt = (
            faults.fail_point("runner.gate.optimistic") is not None
        )
        if (
            min_gate
            and (gate_pass or forced_opt)
            and p.blocks_done >= p.next_full_check
        ):
            full_draws = p.hist.view()
            full_rhat = float(np.max(diagnostics.split_rhat(full_draws)))
            full_ess = float(np.min(diagnostics.ess(full_draws)))
            rec["full_max_rhat"] = full_rhat
            rec["full_min_ess"] = full_ess
            rec["full_max_rank_rhat"] = float(
                np.max(diagnostics.rank_rhat(full_draws))
            )
            if full_rhat < rhat_target and full_ess > p.ess_target:
                p.converged = True
                p.min_ess = full_ess
                p.max_rhat = full_rhat
            else:
                p.next_full_check = p.blocks_done + max(
                    1, p.blocks_done // 4
                )
        if not p.converged and p.blocks_done >= max_blocks:
            p.budget_exhausted = True
        p.history.append(rec)
        emit(rec)
        if health_on:
            # per-tenant warning sweep AFTER the block record, so the
            # metrics trail stays byte-identical to the pre-observatory
            # fleet (warnings are trace events only)
            monitor_for(p).observe_block(
                block=p.blocks_done,
                zs=zs,
                accept=accept,
                divergent=divergent,
                energy=energy,
                ngrad=ngrad if cfg.kernel == "nuts" else None,
                max_rhat=p.max_rhat,
                min_ess=p.min_ess,
                n_stuck=n_stuck,
                draws_per_chain=int(p.suff.count[0]),
            )
        if not p.active:
            # this problem's final block was appended above; no masked
            # lane ever appends again, so its store file is final
            finish_problem(p)

    def save_fleet_checkpoint(path: str):
        from .checkpoint import save_checkpoint

        t_ckpt = time.perf_counter()
        active_lanes = [j for j, i in enumerate(order) if probs[i].active]
        active_ids = [probs[order[j]].pid for j in active_lanes]
        st = take_lanes(state, active_lanes)
        arrays = {
            "z": np.asarray(st.z),
            "pe": np.asarray(st.potential_energy),
            "grad": np.asarray(st.grad),
            "step_size": np.asarray(take_lanes(step_size, active_lanes)),
            "inv_mass": np.asarray(take_lanes(inv_mass, active_lanes)),
            "keys": np.stack(
                [np.asarray(probs[order[j]].key) for j in active_lanes]
            ) if active_lanes else np.zeros((0, 2), np.uint32),
        }
        if store is None:
            for p in probs:
                if p.hist.rows:
                    arrays[f"draws_{p.pid}"] = p.hist.view()
        else:
            store.flush()
            corrupt_one_store_site()
        if health_check:
            from .supervise import check_finite_state

            check_finite_state(
                {k: arrays[k] for k in
                 ("z", "pe", "grad", "step_size", "inv_mass")}
            )
        # streaming/slot/warm-start state rides ONLY when in play — a
        # knob-off, feed-less run's checkpoint stays byte-identical to
        # the pre-slot-scheduler schema
        stream_meta: Dict[str, Any] = {}
        if submitted_order:
            stream_meta["submitted"] = []
            by_pid = {p.pid: p for p in probs}
            for pid in submitted_order:
                # data leaves ride the checkpoint only while the problem
                # could still need them (queued or sampling): a TERMINAL
                # submission's draws are already durable and it is never
                # re-sampled, so a long-lived serving loop's checkpoint
                # stays O(live problems), not O(total submissions) —
                # and the host-side raw copy is dropped with it (the
                # stacked device slab still grows with submissions; a
                # documented bound for very-long-lived loops)
                has_data = bool(by_pid[pid].active)
                if has_data:
                    for k, leaf in enumerate(
                        jax.tree.leaves(submitted_raw[pid])
                    ):
                        arrays[f"feed_{pid}_{k}"] = np.asarray(leaf)
                b = submitted_budgets.get(pid)
                stream_meta["submitted"].append({
                    "pid": pid,
                    "leaves": submitted_leaves[pid],
                    "data": has_data,
                    "budget": dataclasses.asdict(b) if b else None,
                })
        if slots_on:
            stream_meta["slots"] = True
        if donor_pool is not None:
            stream_meta["donor_pool"] = donor_pool.state_dict()
        save_checkpoint(
            path,
            arrays,
            {
                "fleet": True,
                "kernel": cfg.kernel,
                "model": type(model).__name__,
                "chains": chains,
                "block_size": block_size,
                "problem_ids": list(all_ids),
                "active_ids": active_ids,
                "problems": {p.pid: p.meta() for p in probs},
                # cumulative wall including prior attempts: what resumed
                # runs charge per-problem deadline_s budgets against
                "elapsed_wall_s": (
                    time.perf_counter() - t_start + wall_offset
                ),
                **stream_meta,
            },
        )
        # the checkpoint is durable: every consumed submission is now
        # replayable from it (nothing to requeue on a crash), and
        # terminal submissions' host-side raw data can be dropped
        last_ckpt_pids.update(submitted_order)
        for s in stream_meta.get("submitted", ()):
            if not s["data"]:
                submitted_raw.pop(s["pid"], None)
        if trace.enabled:
            trace.emit(
                "checkpoint",
                stage="fleet",
                path=path,
                active=len(active_ids),
                dur_s=round(time.perf_counter() - t_ckpt, 4),
            )

    from .parallel.primitives import gather_tree

    # key advancement is batched: vmap maps the same deterministic
    # threefry split over the stacked keys, so each lane's stream stays
    # bit-identical to per-problem `jax.random.split` while the host
    # pays O(1) dispatches per block instead of ~2B
    v_split2 = jax.vmap(lambda k: jax.random.split(k))
    v_split_chains = jax.vmap(lambda k: jax.random.split(k, chains))

    try:
        while True:
            # --- next cohort / serve the feed / done ----------------------
            if not any(probs[i].active for i in order):
                if feed is not None:
                    _drain_feed()
                pending = [i for i in pending if probs[i].active]
                if pending:
                    if slots_on and order:
                        # pinned batch shape: the next cohort enters IN
                        # PLACE (every slot is free here) — the compiled
                        # scan keeps its width
                        free_js = [
                            j for j, i in enumerate(order)
                            if not probs[i].active
                        ]
                        k = min(len(free_js), len(pending))
                        nxt, pending = pending[:k], pending[k:]
                        admit_into_slots(free_js[:k], nxt)
                        if (
                            pending and max_batch is not None
                            and len(order) < max_batch
                        ):
                            # same under-capacity growth as the in-loop
                            # boundary: append toward max_batch
                            room = max_batch - len(order)
                            nxt, pending = pending[:room], pending[room:]
                            admit(nxt)
                    else:
                        # legacy: start the next cohort fresh (e.g. the
                        # whole batch finished without triggering a
                        # refill under refill_occupancy=0)
                        state = step_size = inv_mass = diag = bdata = None
                        order = []
                        room = (
                            max_batch if max_batch is not None
                            else len(pending)
                        )
                        nxt, pending = pending[:room], pending[room:]
                        admit(nxt)
                elif feed is not None and not feed.closed:
                    # long-lived serving loop: every problem is terminal
                    # but the feed is open — wait for the next
                    # submission, feeding the watchdog while idle.  The
                    # fleet time budget still bounds the wait: an idle
                    # serving loop must not outlive it.
                    if (
                        time_budget_s is not None
                        and time.perf_counter() - t_start > time_budget_s
                    ):
                        fleet_budget_exhausted = True
                        # same observables as the block-path expiry: the
                        # telemetry trail must say WHY the serving loop
                        # closed, idle or not
                        emit({
                            "event": "budget_exhausted",
                            "time_budget_s": float(time_budget_s),
                            "wall_s": time.perf_counter() - t_start,
                        })
                        if trace.enabled:
                            trace.emit(
                                "budget",
                                time_budget_s=float(time_budget_s),
                                blocks=blocks_dispatched,
                            )
                        break
                    telemetry.notify_progress()
                    feed.wait(0.2)
                    continue
                else:
                    break
            # --- dispatch one fleet block over the CURRENT batch ---------
            act_lanes = [i for i in order if probs[i].active]
            blk_key: Dict[int, Any] = {}
            if act_lanes:
                pair = np.asarray(
                    v_split2(jnp.stack([probs[i].key for i in act_lanes]))
                )
                for j, i in enumerate(act_lanes):
                    probs[i].key = pair[j, 0]
                    blk_key[i] = pair[j, 1]
            # frozen lanes feed their STALE key — their stream must not
            # advance (a resumed or compacted run never replays them);
            # outputs are discarded
            bkeys = v_split_chains(
                jnp.stack([blk_key.get(i, probs[i].key) for i in order])
            )
            t_enq = time.perf_counter()
            lane_iters = None
            # the compiled program specializes on the PADDED width (the
            # next multiple of the shard count; identity off-mesh), so
            # the zero-recompile accounting tracks that, not len(order)
            width = parts.padded_width(len(order))
            new_width = width not in seen_widths
            if new_width:
                seen_widths.add(width)
                block_scan_compiles += 1
            # occupancy AS DISPATCHED (post-admission): the number the
            # device actually runs at, vs occupancy_trail's post-block
            # pre-admission reading
            dispatch_occupancy_trail.append(
                (len(act_lanes) / max(width, 1), len(pending))
            )
            args = (
                (bkeys, state, diag, step_size, inv_mass, bdata)
                if stream_diag
                else (bkeys, state, step_size, inv_mass, bdata)
            )
            if new_width:
                # first dispatch at this batch width: the batched scan
                # re-specializes.  A compile phase claims the wall so
                # the timeline bills it as compile (not dispatch) — and
                # the span count IS the zero-recompile evidence the
                # slot scheduler is gated on (exactly one per run)
                with trace.phase("compile", stage="fleet_block_scan",
                                 batch=width):
                    out = jax.block_until_ready(v_dispatch(*args))
            else:
                out = v_dispatch(*args)
            if stream_diag:
                if ragged:
                    (state, diag, zs, accept, divergent, energy, ngrad,
                     lane_iters) = out
                else:
                    state, diag, zs, accept, divergent, energy, ngrad = out
            else:
                if ragged:
                    (state, zs, accept, divergent, energy, ngrad,
                     lane_iters) = out
                else:
                    state, zs, accept, divergent, energy, ngrad = out
            state = faults.poison("runner.carried_nan", state)
            state = poison_lane_site(state)
            state = kill_shard_site(state)
            blocks_dispatched += 1

            # --- host side ------------------------------------------------
            faults.fail_point("fleet.block.pre")
            # a pathologically slow lane (``sleep`` action): the
            # per-problem ``deadline_s`` budget is what turns the delay
            # into a per-tenant outcome instead of a fleet-wide fate
            faults.fail_point("fleet.lane_stall")
            # per-shard timing trail (PR 16): observe each shard's output
            # readiness since enqueue BEFORE the global gather collapses
            # the layout — host-side observation only, the draws are
            # untouched.  Rides mesh + STARK_COMM_TELEMETRY runs — and
            # mesh + STARK_SHARD_DEADLINE runs, where the walls feed the
            # shard deadman's ``wall`` signal (comm-off deadman runs
            # keep the walls OUT of the trace: timing-field emission
            # stays the comm observatory's contract).
            shard_walls = None
            if fleet_mesh is not None and (
                comm_on or shard_deadline is not None
            ):
                shard_walls = _shard_ready_walls(zs, t_enq)
            t_blk = time.perf_counter()
            # the GLOBAL host view (parallel.primitives.gather_tree):
            # everything below — gates, fault domains, budgets, slots,
            # checkpoints — reads this, so the mesh layout is invisible
            # to the whole host loop
            zs = gather_tree(zs)
            divergent_h = gather_tree(divergent)
            ngrad_h = gather_tree(ngrad)
            diag_h = gather_tree(diag) if stream_diag else None
            # acceptance + per-block Hamiltonian series cross to host
            # ONLY for the health observatory (STARK_HEALTH=0 restores
            # the historical drop-on-device behavior)
            accept_h = (
                np.asarray(gather_tree(accept)) if health_on else None
            )
            energy_h = (
                np.asarray(gather_tree(energy)) if health_on else None
            )
            t_wait = time.perf_counter() - t_blk
            # per-LANE finite scan: a poisoned lane is a PROBLEM fault,
            # contained below (reseed-or-quarantine) — never a fleet
            # fault.  Whole-fleet restart stays reserved for process-
            # level faults (crash / stall / corrupt fleet checkpoint).
            poisoned: List[Tuple[int, int, str]] = []
            if health_check:
                from .supervise import ChainHealthError, check_finite_state

                # one device→host transfer per array for the WHOLE batch;
                # the per-lane loop below only slices host memory
                z_h = np.asarray(state.z)
                pe_h = np.asarray(state.potential_energy)
                grad_h = np.asarray(state.grad)
                ss_h = np.asarray(step_size)
                im_h = np.asarray(inv_mass)
                for j, i in enumerate(order):
                    if not probs[i].active:
                        continue  # masked lanes are not health-gated
                    try:
                        check_finite_state({
                            "z": z_h[j],
                            "pe": pe_h[j],
                            "grad": grad_h[j],
                            "step_size": ss_h[j],
                            "inv_mass": im_h[j],
                        })
                    except ChainHealthError as e:
                        poisoned.append((j, i, str(e)))

            # --- shard deadman + degraded re-shard (elastic mesh) ---------
            # geometry AS DISPATCHED: the fleet_block accounting below
            # must describe the mesh this block actually ran on, even
            # when the deadman re-packs the fleet mid-cycle
            mesh_ran, shards_ran, width_ran = fleet_mesh, n_shards, width
            lane_fault: Dict[int, str] = {}
            if (
                shard_deadline is not None
                and fleet_mesh is not None
                and n_shards > 1
            ):
                # the SHARD as a unit of failure: all of a shard's active
                # lanes non-finite (device loss surfaces as NaN'd
                # transfers), or its ready wall blown past
                # STARK_SHARD_DEADLINE x the surviving-shard median —
                # either declares the shard LOST.  Victim lanes join the
                # per-problem containment below under the shard_lost
                # fault class (burn restarts, then quarantine
                # failed:shard_lost); the survivors re-pack onto a
                # shrunk mesh and the block loop carries on.
                lost_now = _classify_lost_shards(
                    n_shards=n_shards,
                    lanes_per=width // n_shards,
                    active_js=[
                        j for j, i in enumerate(order) if probs[i].active
                    ],
                    poisoned_js={j for j, _i, _r in poisoned},
                    shard_walls=shard_walls,
                    deadline_ratio=shard_deadline,
                )
                if lost_now and len(lost_now) >= n_shards:
                    # every shard "lost" is not shard loss — it is a
                    # batch-wide fault (e.g. poisoned carried state
                    # reaching every lane at once): there is no
                    # surviving mesh to re-pack onto, so leave it to the
                    # per-problem taxonomy instead of tearing the fleet
                    # down to nothing
                    log.error(
                        "fleet shard deadman: all %d shards classified "
                        "lost (%s) — treating as a batch fault, not "
                        "shard loss", n_shards, lost_now,
                    )
                    lost_now = {}
                if lost_now:
                    lanes_per = width // n_shards
                    already = {j for j, _i, _r in poisoned}
                    shards_after = n_shards - len(lost_now)
                    for k in sorted(lost_now):
                        cause = lost_now[k]
                        lo = k * lanes_per
                        victims = [
                            j
                            for j in range(lo, min(lo + lanes_per,
                                                   len(order)))
                            if probs[order[j]].active
                        ]
                        for j in victims:
                            lane_fault[j] = _FAULT_SHARD_LOST
                            if j not in already:
                                # a wall-lost shard's draws came back
                                # finite but untrusted — discarded with
                                # the shard, exactly like a poisoned
                                # lane's block
                                poisoned.append((
                                    j, order[j],
                                    f"shard {k} lost ({cause})",
                                ))
                        ev = dict(
                            shard=k,
                            cause=cause,
                            lanes=len(victims),
                            problem_ids=[
                                probs[order[j]].pid for j in victims
                            ],
                            shards_before=n_shards,
                            shards_after=shards_after,
                            block=blocks_dispatched,
                        )
                        emit({"event": "shard_lost", **ev})
                        # the loss IS the forensic moment: one idiom
                        # emits the trace event AND dumps a postmortem
                        # bundle per lost shard (trigger slug names the
                        # shard)
                        recorder.record_anomaly(
                            f"shard_lost:{k}", trace, "shard_lost", **ev
                        )
                        lost_shard_ids.append(k)
                        log.error(
                            "fleet shard %d LOST (%s): %d lane(s) "
                            "re-homed, mesh %d -> %d shard(s)",
                            k, cause, len(victims), n_shards,
                            shards_after,
                        )
                    # degraded re-shard: the survivors' carried state is
                    # host-recoverable (the finite scan above already
                    # read it back), so snapshot it and re-pack onto the
                    # surviving devices.  ONE accounted
                    # re-specialization: clearing seen_widths makes the
                    # next dispatch take the existing new-width path
                    # (compile phase + block_scan_compiles), and the
                    # batch-composition-independence contract is what
                    # makes the survivors' draws bit-identical to an
                    # uninjected fleet on the shrunk mesh.
                    old_devices = list(
                        np.asarray(fleet_mesh.devices).reshape(-1)
                    )
                    survivors_d = [
                        d for k2, d in enumerate(old_devices)
                        if k2 not in lost_now
                    ]
                    if len(survivors_d) > 1:
                        from .parallel.mesh import make_mesh

                        fleet_mesh = make_mesh(
                            {"problems": len(survivors_d)},
                            devices=survivors_d,
                        )
                    else:
                        # one survivor: the mesh degrades all the way to
                        # the historical single-device fleet
                        fleet_mesh = None
                    fm, parts = _fleet_parts_for(model, cfg, fleet_mesh)
                    n_shards = parts.shards
                    # host round-trip the carried trees; the dispatch
                    # wrapper re-pads + re-places them onto the new mesh
                    state, step_size, inv_mass = (
                        jax.tree.map(
                            lambda a: jnp.asarray(np.asarray(a)), t
                        )
                        for t in (state, step_size, inv_mass)
                    )
                    if stream_diag:
                        diag = jax.tree.map(
                            lambda a: jnp.asarray(np.asarray(a)), diag
                        )
                    bdata = batch_data(order)
                    v_block = parts.get_block(
                        block_size,
                        diag_lags=diag_lags if stream_diag else None,
                        ragged=ragged,
                    )
                    v_dispatch = (
                        _probe.wrap(v_block)
                        if _probe is not None else v_block
                    )
                    seen_widths.clear()
            poisoned_idx = {i for _j, i, _r in poisoned}
            block_grads_active = 0
            new_donors: List[Tuple[int, _ProblemState]] = []
            for j, i in enumerate(order):
                p = probs[i]
                if not p.active or i in poisoned_idx:
                    # masked or poisoned: draws discarded, grads not
                    # counted (a poisoned lane's block is not evidence)
                    continue
                blk_grads = int(ngrad_h[j].sum())
                block_grads_active += blk_grads
                diag_lane = (
                    jax.tree.map(lambda a, j=j: a[j], diag_h)
                    if stream_diag else None
                )
                gate_and_record(
                    p, zs[j], divergent_h[j], blk_grads, diag_lane,
                    accept=accept_h[j] if accept_h is not None else None,
                    energy=energy_h[j] if energy_h is not None else None,
                    ngrad=ngrad_h[j],
                )
                if donor_pool is not None and p.converged:
                    new_donors.append((j, p))
            if new_donors:
                # warm-start donors: a CONVERGED problem's final step
                # size + mass diagonal joins the pool — validated finite
                # at the boundary (``fleet.warmstart_poison`` drills a
                # NaN'd donor; it must be rejected here, never seeded)
                ss_h2 = np.asarray(step_size)
                im_h2 = np.asarray(inv_mass)
                for j, p in new_donors:
                    d_ss, d_im = ss_h2[j], im_h2[j]
                    d_ens = np.asarray(zs[j][:, -1, :], np.float32)
                    act = faults.fail_point("fleet.warmstart_poison")
                    if act is not None and act.kind == "nan":
                        d_ss = np.full_like(d_ss, np.nan)
                        d_ens = np.full_like(d_ens, np.nan)
                    if not donor_pool.add(donor_tag, d_ss, d_im):
                        log.warning(
                            "fleet warm-start donor %s rejected "
                            "(non-finite adaptation summary)", p.pid,
                        )
                    # position donor: the lane's final draw across chains
                    # — the latest finite ensemble wins; a poisoned one is
                    # rejected at the same boundary as the moments
                    if not donor_pool.add_ensemble(donor_tag, d_ens):
                        log.warning(
                            "fleet warm-start position ensemble from %s "
                            "rejected (non-finite)", p.pid,
                        )

            # --- lane containment -----------------------------------------
            if poisoned:
                rewarm_js: List[int] = []
                rewarm_idx: List[int] = []
                rewarm_fault: List[str] = []
                for j, i, reason in poisoned:
                    if health_on:
                        # the statistical trail records the stuck lane
                        # BEFORE the fault taxonomy acts on it (the
                        # reseed/quarantine below) — the same
                        # warning-first ordering as the single runner
                        monitor_for(probs[i]).warn_nonfinite(
                            reason, block=blocks_dispatched
                        )
                    # the fault CLASS travels with the lane: a shard-loss
                    # victim burns the same per-problem RestartBudget as
                    # a poisoned lane (no fresh budget on re-placement)
                    # but its reseed/quarantine events — and a terminal
                    # verdict — say shard_lost, not poisoned
                    if reseed_problem(
                        probs[i], lane_fault.get(j, _FAULT_POISONED),
                        reason,
                    ):
                        rewarm_js.append(j)
                        rewarm_idx.append(i)
                        rewarm_fault.append(
                            lane_fault.get(j, _FAULT_POISONED)
                        )
                # cold-restart the reseeded lanes IN PLACE: one vmapped
                # warmup dispatch per round, scattered back into their
                # batch slots — every other lane's arrays (and key
                # stream) are untouched, which is what keeps the B-1
                # survivors bit-identical.  A lane whose REWARM itself
                # comes back non-finite (a genuinely broken tenant
                # posterior) burns its own restart budget right here, so
                # poisoned state cannot reach the fleet checkpoint
                # through the rewarm path either.
                while rewarm_js:
                    st, ss, im = warm_cohort(rewarm_idx)
                    z_w = np.asarray(st.z)
                    pe_w = np.asarray(st.potential_energy)
                    g_w = np.asarray(st.grad)
                    ss_w = np.asarray(ss)
                    im_w = np.asarray(im)
                    ok = [
                        k for k in range(len(rewarm_idx))
                        if all(
                            np.all(np.isfinite(a[k]))
                            for a in (z_w, pe_w, g_w, ss_w, im_w)
                        )
                    ]
                    if ok:
                        ix = jnp.asarray(
                            [rewarm_js[k] for k in ok], dtype=jnp.int32
                        )
                        sub = jnp.asarray(ok, dtype=jnp.int32)
                        state = jax.tree.map(
                            lambda a, b: a.at[ix].set(b[sub]), state, st
                        )
                        step_size = step_size.at[ix].set(ss[sub])
                        inv_mass = inv_mass.at[ix].set(im[sub])
                        if stream_diag:
                            ok_idx = [rewarm_idx[k] for k in ok]
                            dg = init_diag_for(
                                ok_idx,
                                [probs[i].hist for i in ok_idx],
                                st.z.dtype,
                            )
                            diag = jax.tree.map(
                                lambda a, b: a.at[ix].set(b), diag, dg
                            )
                    retry_js: List[int] = []
                    retry_idx: List[int] = []
                    retry_fault: List[str] = []
                    for k in range(len(rewarm_idx)):
                        if k in ok:
                            continue
                        # retries keep the lane's original fault class: a
                        # shard-loss victim whose cold restart itself
                        # comes back non-finite still quarantines as
                        # failed:shard_lost
                        if reseed_problem(
                            probs[rewarm_idx[k]], rewarm_fault[k],
                            "non-finite warmup state after lane reseed",
                        ):
                            retry_js.append(rewarm_js[k])
                            retry_idx.append(rewarm_idx[k])
                            retry_fault.append(rewarm_fault[k])
                    rewarm_js, rewarm_idx = retry_js, retry_idx
                    rewarm_fault = retry_fault

            # --- per-problem deadlines ------------------------------------
            # charged against the CUMULATIVE wall (wall_offset restores
            # prior attempts' elapsed time on resume)
            now_wall = time.perf_counter() - t_start + wall_offset
            for p in probs:
                if (
                    p.active and p.deadline_s is not None
                    and now_wall > p.deadline_s
                ):
                    # the tenant's own gate target tripped: it exits
                    # budget_exhausted, masked like a converged problem
                    # — it never poisons (or restarts) its neighbors.
                    # A blown deadline is a per-tenant SLO failure: the
                    # flight recorder captures the moment
                    p.budget_exhausted = True
                    rec_done = finish_problem(p, deadline_s=p.deadline_s)
                    recorder.note_anomaly(
                        f"deadline:{p.pid}", rec_done
                    )
            # --- SLO burn-rate accounting (lineage observatory) -----------
            # block-cadence fraction of each active tenant's ProblemBudget
            # grants consumed: deadline wall, restart count, and ESS
            # progress toward the gate target.  Absent budgets ride as
            # null, never 0.0 (the null-not-0.0 rule); the whole family
            # rides ONLY lineage-on runs (STARK_LINEAGE=0 byte-identity).
            if lineage_on and trace.enabled:
                for p in probs:
                    if not p.active:
                        continue
                    deadline_burn = (
                        round(now_wall / p.deadline_s, 4)
                        if p.deadline_s else None
                    )
                    restart_burn = (
                        round(p.lane_restarts / p.max_restarts, 4)
                        if p.max_restarts else None
                    )
                    ess_burn = (
                        round(p.min_ess / p.ess_target, 4)
                        if p.min_ess is not None and p.ess_target
                        else None
                    )
                    if (deadline_burn is None and restart_burn is None
                            and ess_burn is None):
                        continue
                    trace.emit(
                        "slo_burn",
                        problem_id=p.pid,
                        block=blocks_dispatched,
                        **{k: v for k, v in (
                            ("deadline_burn", deadline_burn),
                            ("restart_burn", restart_burn),
                            ("ess_burn", ess_burn),
                        ) if v is not None},
                    )
                    if burn_trail is not None:
                        burn_trail.observe(
                            p.pid,
                            {"deadline": deadline_burn,
                             "restart": restart_burn},
                            block=blocks_dispatched,
                        )
            n_active = sum(probs[i].active for i in order)
            occupancy = n_active / max(len(order), 1)
            occupancy_trail.append(occupancy)
            # ragged-NUTS lane occupancy: useful (active-lane) gradients
            # over the max(lane_iters) x all-lanes gradients the batched
            # loop actually executed — distinct from the problem-level
            # ``occupancy`` above (active problems per batch slot).
            # Fields ride ONLY knob-on runs (knob-off trails byte-equal).
            sched_fields = {}
            if ragged and lane_iters is not None:
                from .kernels.nuts_ragged import lane_occupancy_fields

                sched_fields = lane_occupancy_fields(
                    lane_iters, useful=block_grads_active
                )
            # queue-depth accounting rides ONLY slot-scheduler / streaming
            # runs (knob-off, feed-less fleet_block events stay byte-
            # identical to pre-PR traces)
            if slots_on or feed is not None:
                sched_fields = dict(sched_fields, queue_depth=len(pending))
            # mesh-parallel fleet: per-shard occupancy — shard k runs the
            # k-th contiguous slice of the PADDED batch (shard_map's
            # leading-axis layout); pad lanes count as idle.  Fields ride
            # ONLY mesh runs (knob-off events stay byte-identical).
            if mesh_ran is not None:
                lanes_per = width_ran // shards_ran
                shard_occ = []
                for k in range(shards_ran):
                    lo = k * lanes_per
                    hi = min(lo + lanes_per, len(order))
                    act = sum(
                        1 for j in range(lo, max(hi, lo))
                        if probs[order[j]].active
                    )
                    shard_occ.append(round(act / max(lanes_per, 1), 4))
                sched_fields = dict(
                    sched_fields, shards=shards_ran,
                    shard_occupancy=shard_occ,
                )
                # shard-imbalance attribution (PR 16): per-shard ready
                # walls + slowest/median straggler ratio ride ONLY
                # mesh + comm-telemetry runs (knob-off events stay
                # byte-identical — a deadman-only run computes the walls
                # but keeps them out of the trace); the windowed health
                # warning fires through the ShardBalanceTrail
                if shard_walls is not None and comm_on:
                    med = float(np.median(shard_walls))
                    worst = int(np.argmax(shard_walls))
                    sched_fields = dict(
                        sched_fields,
                        shard_walls=shard_walls,
                        straggler_shard=worst,
                        straggler_ratio=(
                            round(float(shard_walls[worst]) / med, 4)
                            if med > 0 else None
                        ),
                    )
                    if shard_trail is not None:
                        shard_trail.observe(
                            shard_walls, block=blocks_dispatched
                        )
            if trace.enabled:
                trace.emit(
                    "fleet_block",
                    block=blocks_dispatched,
                    batch=len(order),
                    active=n_active,
                    occupancy=round(occupancy, 4),
                    block_len=block_size,
                    chains=chains,
                    block_grad_evals=block_grads_active,
                    t_wait_s=round(t_wait, 4),
                    dur_s=round(
                        time.perf_counter() - t_enq, 4
                    ),
                    **sched_fields,
                )
            emit({
                "event": "fleet_block",
                "block": blocks_dispatched,
                "batch": len(order),
                "active": n_active,
                "occupancy": round(occupancy, 4),
                "block_grad_evals": block_grads_active,
                **sched_fields,
                "wall_s": time.perf_counter() - t_start,
            })

            # --- scheduling at the block boundary -------------------------
            # feed submissions land here (the same unit every other fleet
            # decision is made in), then one of three paths runs:
            #   slots on    — recycle freed slots in place, never reshape
            #   legacy      — threshold-gated compaction + refill
            #   legacy top-up (PR 13 bugfix, documented behavior change) —
            #     a batch riding AT/ABOVE refill_occupancy used to strand
            #     its queue even with masked lanes free; now queued
            #     problems are admitted into the masked slots in place
            #     (no reshape, so no batched-scan re-specialization)
            if feed is not None:
                _drain_feed()
            pending = [i for i in pending if probs[i].active]
            free_js = [
                j for j, i in enumerate(order) if not probs[i].active
            ]
            if slots_on:
                if pending and free_js:
                    k = min(len(free_js), len(pending))
                    nxt, pending = pending[:k], pending[k:]
                    admit_into_slots(free_js[:k], nxt)
                if (
                    pending and max_batch is not None
                    and len(order) < max_batch
                ):
                    # under configured capacity (a feed grew a small
                    # spec): APPEND toward max_batch — one batched-scan
                    # specialization per growth wave, pinned again once
                    # at capacity.  Growth is the legacy cohort-append
                    # admission (no slot to recycle), so it carries the
                    # fleet_compact-free warmup path, not
                    # problem_admitted events.
                    room = max_batch - len(order)
                    nxt, pending = pending[:room], pending[room:]
                    admit(nxt)
            elif (
                n_active < len(order)
                and occupancy < refill_occupancy
                and refill_occupancy > 0.0
            ):
                keep = [j for j, i in enumerate(order) if probs[i].active]
                from_size = len(order)
                state = take_lanes(state, keep)
                step_size = take_lanes(step_size, keep)
                inv_mass = take_lanes(inv_mass, keep)
                if stream_diag:
                    diag = take_lanes(diag, keep)
                order = [order[j] for j in keep]
                bdata = batch_data(order) if order else None
                refill = []
                # a queued problem whose deadline already passed exits
                # budget_exhausted at the gate above — never admit it
                pending = [i for i in pending if probs[i].active]
                if pending:
                    room = (
                        (max_batch - len(order))
                        if max_batch is not None else len(pending)
                    )
                    refill, pending = pending[:room], pending[room:]
                    if refill:
                        admit(refill)
                compactions += 1
                if trace.enabled:
                    trace.emit(
                        "fleet_compact",
                        from_batch=from_size,
                        to_batch=len(order),
                        refilled=len(refill),
                        pending=len(pending),
                    )
                emit({
                    "event": "fleet_compact",
                    "from_batch": from_size,
                    "to_batch": len(order),
                    "refilled": len(refill),
                    "pending": len(pending),
                    "wall_s": time.perf_counter() - t_start,
                })
            elif pending and free_js and refill_occupancy > 0.0:
                # legacy top-up: queued work + free masked slots, but the
                # batch rides at/above the compaction threshold — drain
                # the queue into the masked slots without compacting.
                # refill_occupancy=0.0 keeps its documented meaning (the
                # batch is NEVER touched mid-run; the queue starts fresh
                # cohorts only once the whole batch drains)
                k = min(len(free_js), len(pending))
                nxt, pending = pending[:k], pending[k:]
                admit_into_slots(free_js[:k], nxt)

            flush_metrics()  # one write+fsync per fleet block (see emit)
            if checkpoint_path:
                save_fleet_checkpoint(checkpoint_path)
                if lineage_on:
                    # the /jobs index sidecar snapshots on the same
                    # durability cadence as the checkpoint (atomic
                    # tmp+rename; best-effort — never faults the run)
                    lineage.save_index(trace.path)
            if pending:
                # crash-with-queued-work drill point: the checkpoint just
                # persisted the queue (spec indices and streamed
                # submissions alike), so a crash HERE must replay the
                # admission order bit-identically on resume
                # (chaos ``fleet_admit_crash``)
                faults.fail_point("fleet.admit_pending")
            faults.fail_point("fleet.block.post")

            if (
                time_budget_s is not None
                and time.perf_counter() - t_start > time_budget_s
            ):
                fleet_budget_exhausted = True
                emit({
                    "event": "budget_exhausted",
                    "time_budget_s": float(time_budget_s),
                    "wall_s": time.perf_counter() - t_start,
                })
                if trace.enabled:
                    trace.emit(
                        "budget", time_budget_s=float(time_budget_s),
                        blocks=blocks_dispatched,
                    )
                break

            # (next-cohort admission moved to the loop head: the same
            # boundary also serves streamed submissions and the slots
            # path's in-place cohort swap)
    except BaseException:
        # the drain->checkpoint window must not LOSE submissions: any
        # consumed submission the last durable checkpoint does not cover
        # goes back to the front of the feed, so the supervised retry
        # (same process, same feed object) re-drains it in order
        if feed is not None:
            lost = [
                (pid, submitted_raw[pid], submitted_budgets.get(pid))
                for pid in submitted_order
                if pid not in last_ckpt_pids and pid in submitted_raw
            ]
            if lost:
                log.warning(
                    "requeueing %d un-checkpointed feed submission(s) "
                    "after abnormal fleet exit", len(lost),
                )
                feed.requeue(lost)
        raise
    finally:
        flush_metrics()
        if metrics_f:
            metrics_f.close()
        if store is not None:
            store.close()

    wall = time.perf_counter() - t_start
    if health_on:
        # problems still live at fleet exit (a fleet-level budget trip)
        # get their terminal health sweep here
        for p in probs:
            if p.pid not in health_verdicts:
                finalize_monitor(p)
    constrain_cache: Dict[Any, Any] = {}
    results = [
        FleetProblemResult(
            p.pid,
            np.ascontiguousarray(p.hist.view()),
            fm,
            converged=p.converged,
            # a converged (or quarantined) problem is never re-marked by
            # a fleet-level time-budget trip — its terminal status is
            # already decided
            budget_exhausted=p.budget_exhausted
            or (fleet_budget_exhausted and not p.converged
                and not p.failed),
            blocks=p.blocks_done,
            grad_evals=p.grad_evals,
            num_divergent=p.total_div,
            min_ess=p.min_ess,
            max_rhat=p.max_rhat,
            history=p.history,
            _constrain_cache=constrain_cache,
            failed=p.failed,
            failed_reason=p.failed_reason,
            lane_restarts=p.lane_restarts,
            warmstarted=p.warmstarted,
            warmup_draws_saved=p.warmup_draws_saved,
            health=health_verdicts.get(p.pid) if health_on else None,
        )
        for p in probs
    ]
    total_grads = sum(p.grad_evals for p in probs)
    lost = [p.pid for p in probs if p.failed]
    if trace.enabled:
        # streaming/slot accounting rides run_end only on knob-on /
        # fed runs, keeping knob-off trace files byte-identical
        stream_end = (
            dict(admissions=n_admissions, slot_recycles=n_slot_recycles,
                 block_scan_compiles=block_scan_compiles)
            if (slots_on or feed is not None or n_admissions) else {}
        )
        if fleet_mesh is not None:
            stream_end = dict(stream_end, fleet_shards=n_shards)
        trace.emit(
            "run_end",
            dur_s=round(wall, 4),
            converged=all(p.converged for p in probs),
            problems=len(probs),
            converged_problems=sum(p.converged for p in probs),
            blocks=blocks_dispatched,
            compactions=compactions,
            fleet_grad_evals=total_grads,
            budget_exhausted=fleet_budget_exhausted,
            degraded=bool(lost) or bool(lost_shard_ids),
            lost_problems=lost,
            # shard-loss accounting rides run_end ONLY on runs that
            # actually lost shards (knob-off — and knob-on-but-clean —
            # trace files stay byte-identical)
            **({"lost_shards": lost_shard_ids} if lost_shard_ids else {}),
            **stream_end,
        )
    if lineage_on:
        # final index snapshot: every terminal state (and the run_end
        # fold) is durable next to the trace for /jobs + the report tool
        lineage.save_index(trace.path)
    return FleetResult(
        results,
        wall_s=wall,
        blocks_dispatched=blocks_dispatched,
        compactions=compactions,
        occupancy_trail=occupancy_trail,
        total_grad_evals=total_grads,
        budget_exhausted=fleet_budget_exhausted,
        block_scan_compiles=block_scan_compiles,
        admissions=n_admissions,
        slot_recycles=n_slot_recycles,
        dispatch_occupancy_trail=dispatch_occupancy_trail,
        shards=n_shards if fleet_mesh is not None else None,
        lost_shards=lost_shard_ids,
    )


def _problem_path(path: Optional[str], pid: str, b: int) -> Optional[str]:
    """Per-problem variant of a state-file path on sequential runs.  A
    ONE-problem fleet keeps the caller's path untouched so its artifacts
    land exactly where a plain single-problem run would (the B=1
    bit-identity contract covers file layout too)."""
    if path is None or b == 1:
        return path
    root, ext = os.path.splitext(path)
    return f"{root}.{pid}{ext}"


def _sample_fleet_sequential(
    spec: FleetSpec,
    *,
    chains, block_size, max_blocks, min_blocks, rhat_target, ess_target,
    seed, checkpoint_path, resume_from, metrics_path, draw_store_path,
    health_check, reseed, time_budget_s, stream_diag, diag_lags,
    diag_components, trace, problem_max_restarts=1, feed=None,
    **cfg_kwargs,
) -> FleetResult:
    """The escape hatch: problems run one at a time through the
    UNMODIFIED single-problem runner (fixed block march — the fleet path
    has no per-problem block sizing either), seeded ``seed + index`` like
    their fleet lanes, so the two paths produce identical draws.

    Crash-resume (B > 1): the supervisor's single-checkpoint contract
    cannot see the per-problem files this path writes, so each problem
    resumes ITSELF from its own checkpoint when one exists and is
    healthy (unhealthy ones are quarantined, and a cold start
    quarantines the problem's orphaned draw store) — a supervised
    restart therefore continues the sweep from where the crash landed
    instead of re-running every problem from scratch.  B=1 passes the
    caller's paths through untouched (the supervisor drives resume).

    Per-problem fault domains hold here too (B > 1): a
    `ChainHealthError` out of one problem retries it under a far-shifted
    seed (``_LANE_SEED_STRIDE`` — outside every neighbor's lattice) up
    to its restart budget, then quarantines its artifacts and records it
    ``failed:poisoned_state`` — the sweep continues either way.
    Per-problem ``ess_target`` / ``deadline_s`` budgets are honored by
    clamping each problem's gate target and time budget — re-derived per
    attempt (retries included), with the sweep clock persisted across
    supervised restarts in a ``<checkpoint_path>.sweep.json`` sidecar so
    deadlines charge CUMULATIVE wall here too.

    The streaming `FleetFeed` API is honored on the hatch: submissions
    drain at problem boundaries (after the spec sweep, and whenever the
    work queue runs dry while the feed is open), run through the same
    single-problem runner with seed ``seed + i`` (``i`` their global
    arrival index — identical streams to their vmapped-fleet lanes),
    and the loop stays alive until the feed closes.  Queue durability
    is the vmapped path's checkpointed-queue feature; here a completed
    submission's artifacts are durable per problem, and unconsumed
    submissions stay in the caller's feed across a supervised restart
    (same process, same feed object)."""
    from .backends.jax_backend import JaxBackend
    from .runner import sample_until_converged
    from .supervise import (
        ChainHealthError,
        checkpoint_health,
        quarantine_path,
    )

    t0 = time.perf_counter()
    b = spec.num_problems
    # "multi-problem" layout decision: a feed can grow a B=1 sweep past
    # one problem, so per-problem artifact paths + fault containment
    # engage whenever a feed is attached, not just when B > 1
    multi = b if feed is None else max(b, 2)
    # same forensics destination rule as the vmapped path: bundles land
    # next to the sweep's own artifacts
    recorder = telemetry.flight_recorder()
    recorder.set_workdir(
        _fleet_workdir(checkpoint_path, metrics_path, draw_store_path)
    )
    # cumulative sweep wall across supervised attempts: the vmapped path
    # persists elapsed_wall_s in the fleet checkpoint; the hatch has no
    # single checkpoint, so a sidecar next to checkpoint_path carries
    # the sweep clock — per-problem deadline_s stays a contract on TOTAL
    # wall under crash loops here too (the sweep-level time_budget_s
    # needs no equivalent: the supervisor already hands each attempt the
    # reduced remainder)
    sweep_sidecar = (
        checkpoint_path + ".sweep.json"
        if (checkpoint_path and multi > 1) else None
    )
    sweep_offset = 0.0
    if sweep_sidecar and os.path.exists(sweep_sidecar):
        # the clock only carries over into a sweep that actually RESUMES
        # prior work (some per-problem checkpoint survives the crash) —
        # otherwise the sidecar is stale state from an earlier sweep in
        # this workdir and must not pre-charge fresh tenants' deadlines
        resuming = any(
            os.path.exists(_problem_path(checkpoint_path, pid, multi))
            for pid in spec.problem_ids
        )
        if resuming:
            try:
                with open(sweep_sidecar) as f:
                    sweep_offset = float(
                        json.load(f).get("elapsed_wall_s", 0.0)
                    )
            except (OSError, ValueError):
                sweep_offset = 0.0
        else:
            try:
                os.unlink(sweep_sidecar)
            except OSError:
                pass

    def sweep_wall() -> float:
        return time.perf_counter() - t0 + sweep_offset

    def persist_sweep_wall() -> None:
        if sweep_sidecar:
            try:
                with open(sweep_sidecar, "w") as f:
                    json.dump({"elapsed_wall_s": sweep_wall()}, f)
            except OSError as e:  # the clock is advisory, never fatal
                log.warning("could not persist sweep clock: %s", e)

    # one backend across the whole sweep: the runner caches compiled
    # segments per (model, cfg) on the instance, so problems 2..B skip
    # the re-jit (the steady-state serving loop, and what keeps the
    # sequential escape hatch usable at fleet sizes)
    backend = JaxBackend()
    results = []
    constrain_cache: Dict[Any, Any] = {}
    budget_hit = False
    total_grads = 0
    fm = flatten_model(spec.model)

    def empty_result(pid, *, budget_exhausted=False, failed=None,
                     failed_reason=None, lane_restarts=0):
        return FleetProblemResult(
            pid,
            np.zeros((chains, 0, fm.ndim), np.float32),
            fm,
            converged=False,
            budget_exhausted=budget_exhausted,
            blocks=0,
            grad_evals=0,
            num_divergent=0,
            min_ess=None,
            max_rhat=None,
            history=[],
            _constrain_cache=constrain_cache,
            failed=failed,
            failed_reason=failed_reason,
            lane_restarts=lane_restarts,
        )

    # FIFO work queue: the spec's problems up front, streamed submissions
    # appended as they drain — every problem's global index i (and so its
    # seed + i stream) is its arrival position, exactly like the vmapped
    # path's dynamic registry
    work: List[Tuple[int, str, Any, ProblemBudget]] = [
        (i, pid, d, spec.budget_for(i))
        for i, (pid, d) in enumerate(zip(spec.problem_ids, spec.datasets))
    ]
    seen_ids = set(spec.problem_ids)
    next_idx = b
    # every ACCEPTED feed submission in arrival order: on an abnormal
    # exit the WHOLE list is requeued, so the supervised retry re-drains
    # them in the same order and reassigns the same global indices (and
    # therefore the same seed + i streams); already-completed ones
    # resume their per-problem checkpoints and re-report cheaply
    drained_feed: List[Tuple[str, Any, Optional[ProblemBudget]]] = []

    try:
        while True:
            if not work:
                if feed is not None:
                    for f_pid, f_data, f_budget in feed.drain():
                        try:
                            if f_pid in seen_ids:
                                raise ValueError(
                                    f"problem id {f_pid!r} already exists"
                                )
                            check_problem_data(spec.datasets[0], f_data, f_pid)
                            _check_finite_submission(f_data, f_pid)
                        except Exception as e:  # noqa: BLE001 — same
                            # reject-don't-die contract as the vmapped path
                            log.warning(
                                "fleet feed submission %r rejected: %s",
                                f_pid, e,
                            )
                            continue
                        seen_ids.add(f_pid)
                        drained_feed.append((f_pid, f_data, f_budget))
                        work.append((
                            next_idx, f_pid, f_data,
                            f_budget if f_budget is not None else _DEFAULT_BUDGET,
                        ))
                        next_idx += 1
                if not work:
                    if feed is None or feed.closed:
                        break
                    if time_budget_s is not None and (
                        time.perf_counter() - t0 >= time_budget_s
                    ):
                        # the sweep budget bounds the idle serving wait too
                        budget_hit = True
                        break
                    # serving loop: stay alive for the next submission
                    telemetry.notify_progress()
                    feed.wait(0.2)
                    continue
            i, pid, data_p, p_budget = work.pop(0)
            # checkpoint the sweep clock at problem granularity (the same
            # unit the hatch's crash-resume accounts in)
            persist_sweep_wall()
            ess_i, deadline_i, mr_i = p_budget.resolve(
                ess_target, problem_max_restarts
            )
            if time_budget_s is not None and (
                time.perf_counter() - t0 >= time_budget_s
            ):
                # never attempted: back on the queue so the tail below
                # reports it budget_exhausted with the rest
                work.insert(0, (i, pid, data_p, p_budget))
                budget_hit = True
                break
            ckpt_p = _problem_path(checkpoint_path, pid, multi)
            resume_p = _problem_path(resume_from, pid, multi)
            store_p = _problem_path(draw_store_path, pid, multi)
            if multi > 1:
                if not (resume_p and os.path.exists(resume_p)):
                    resume_p = None
                if resume_p is None and ckpt_p and os.path.exists(ckpt_p):
                    healthy, _reason = checkpoint_health(ckpt_p)
                    if healthy:
                        resume_p = ckpt_p
                    else:
                        quarantine_path(ckpt_p, reason=_reason)
                if (
                    resume_p is None
                    and store_p
                    and os.path.exists(store_p)
                ):
                    # cold start: a discarded attempt's draws must not mix
                    # into this run's store (supervisor discipline, applied
                    # per problem)
                    quarantine_path(store_p)
            seed_i = seed + i
            if reseed is not None and multi > 1:
                # reseeded restart: the single runner folds `reseed` only
                # into RESUMED keys, so a cold-started problem would replay
                # a neighbor's attempt-0 stream (seed+attempt+i aliases
                # seed+(i+attempt) — the same lattice collision `_cold_key`
                # fixes on the vmapped path); spreading the problems keeps
                # every attempt bump inside a problem's private seed range
                seed_i = seed + i * _RESEED_STRIDE
            res = None
            fault_reason = None
            faults_seen = 0
            lane_restarts = 0
            stopped = None  # "sweep" | "deadline" budget stop mid-retries
            for r in range(mr_i + 1):
                # the budget clamp is re-derived per ATTEMPT, retries
                # included: a ChainHealthError retry must never re-grant a
                # tenant its original deadline window (or outrun the sweep
                # budget) — the clocks keep running across recovery
                now = time.perf_counter() - t0
                remaining = None
                if time_budget_s is not None:
                    if time_budget_s - now <= 0:
                        stopped = "sweep"
                        break
                    remaining = time_budget_s - now
                if deadline_i is not None:
                    # deadlines charge the CUMULATIVE sweep wall (restored
                    # from the sidecar), not this attempt's
                    dl_left = deadline_i - sweep_wall()
                    if dl_left <= 0:
                        stopped = "deadline"
                        break
                    remaining = dl_left if remaining is None else min(
                        remaining, dl_left
                    )
                try:
                    res = sample_until_converged(
                        spec.model,
                        data_p,
                        backend=backend,
                        chains=chains,
                        block_size=block_size,
                        max_blocks=max_blocks,
                        min_blocks=min_blocks,
                        rhat_target=rhat_target,
                        ess_target=ess_i,
                        seed=seed_i + r * _LANE_SEED_STRIDE,
                        checkpoint_path=ckpt_p,
                        resume_from=resume_p,
                        metrics_path=_problem_path(metrics_path, pid, multi),
                        draw_store_path=store_p,
                        health_check=health_check,
                        reseed=reseed,
                        time_budget_s=remaining,
                        stream_diag=stream_diag,
                        diag_lags=diag_lags,
                        diag_components=diag_components,
                        adaptive_blocks=False,
                        trace=trace,
                        **cfg_kwargs,
                    )
                    lane_restarts = r
                    break
                except ChainHealthError as e:
                    if multi == 1:
                        # the supervisor owns the single-problem fault story
                        raise
                    # per-problem fault domain on the sequential path too:
                    # quarantine the poisoned attempt's artifacts (the reason
                    # rides the forensic copy) and retry under a seed shifted
                    # far outside every neighbor's lattice
                    faults_seen = r + 1
                    fault_reason = str(e)
                    log.warning(
                        "sequential fleet problem %s poisoned "
                        "(restart %d/%d): %s", pid, r + 1, mr_i, e,
                    )
                    for path in (ckpt_p, store_p):
                        if path and os.path.exists(path):
                            quarantine_path(
                                path,
                                reason=f"{pid}: {_FAULT_POISONED}: {e}",
                            )
                    resume_p = None
                    # same observable as the vmapped path's lane reseed:
                    # the collector's fleet_lane_reseeds_total / /status
                    # last_reseeded must move on the hatch too
                    if faults_seen <= mr_i and trace.enabled:
                        trace.emit(
                            "problem_reseeded",
                            problem_id=pid,
                            fault=_FAULT_POISONED,
                            reason=fault_reason,
                            lane_restarts=faults_seen,
                            max_restarts=mr_i,
                        )
            if res is None:
                if stopped == "deadline":
                    # the tenant's own clock ran out (possibly mid-retries):
                    # a budget outcome, NOT a quarantine — faults_seen keeps
                    # the honest count of restarts actually consumed.  Same
                    # forensic parity as the vmapped path: a blown per-
                    # tenant deadline dumps a postmortem bundle
                    results.append(empty_result(
                        pid, budget_exhausted=True,
                        lane_restarts=faults_seen,
                    ))
                    recorder.record_anomaly(
                        f"deadline:{pid}",
                        trace,
                        "problem_converged",
                        problem_id=pid,
                        status="budget_exhausted",
                        deadline_s=deadline_i,
                        deadline_headroom_s=round(
                            deadline_i - sweep_wall(), 4
                        ),
                        lane_restarts=faults_seen,
                        max_restarts=mr_i,
                    )
                    continue
                if stopped == "sweep":
                    # the FLEET budget cut this problem off before its retry
                    # budget was spent: the tail marks it (and every problem
                    # after it) budget_exhausted — never failed
                    work.insert(0, (i, pid, data_p, p_budget))
                    budget_hit = True
                    break
                # retries exhausted on faults: terminal quarantine, with the
                # true fault count (every attempt faulted: mr_i + 1)
                results.append(empty_result(
                    pid, failed=_FAULT_POISONED,
                    failed_reason=fault_reason, lane_restarts=faults_seen,
                ))
                recorder.record_anomaly(
                    f"quarantine:{pid}",
                    trace,
                    "problem_quarantined",
                    problem_id=pid,
                    status=f"failed:{_FAULT_POISONED}",
                    fault=_FAULT_POISONED,
                    reason=fault_reason,
                    lane_restarts=faults_seen,
                    max_restarts=mr_i,
                )
                continue
            grad_evals = int(sum(
                r.get("block_grad_evals", 0)
                for r in res.history
                if r.get("event") == "block"
            ))
            total_grads += grad_evals
            last = res.history[-1] if res.history else {}
            n_blocks = len(
                [r for r in res.history if r.get("event") == "block"]
            )
            results.append(
                FleetProblemResult(
                    pid,
                    res.draws_flat,
                    res.flat_model,
                    converged=res.converged,
                    # max_blocks exhaustion IS a budget outcome (the vmapped
                    # path's taxonomy) — the single runner only flags TIME
                    # budget trips itself
                    budget_exhausted=res.budget_exhausted or (
                        not res.converged and n_blocks >= max_blocks
                    ),
                    blocks=n_blocks,
                    grad_evals=grad_evals,
                    num_divergent=int(np.sum(
                        res.sample_stats.get("num_divergent", 0)
                    )),
                    min_ess=last.get("full_min_ess", last.get("min_ess")),
                    max_rhat=last.get("full_max_rhat", last.get("max_rhat")),
                    history=res.history,
                    _constrain_cache=constrain_cache,
                    lane_restarts=lane_restarts,
                    # the sequential hatch inherits the single runner's
                    # health verdict (None when STARK_HEALTH=0)
                    health=getattr(res, "health_warnings", None),
                )
            )
    except BaseException:
        # hatch twin of the vmapped requeue-on-crash: EVERY drained feed
        # submission (completed, in flight, or queued) goes back to the
        # feed in arrival order, so the supervised retry re-drains them
        # with the SAME global indices (same seed + i streams — no
        # cross-problem collision) and re-reports completed ones off
        # their per-problem checkpoints; spec problems need no requeue
        # (the spec is re-supplied on every attempt)
        if feed is not None and drained_feed:
            log.warning(
                "requeueing %d feed submission(s) after abnormal "
                "sequential-fleet exit", len(drained_feed),
            )
            feed.requeue(drained_feed)
        raise
    # the sweep RETURNED (converged, exhausted, or budget-stopped — all
    # terminal): the clock has served its purpose, and leaving it would
    # pre-charge the next logical sweep in this workdir
    if sweep_sidecar and os.path.exists(sweep_sidecar):
        try:
            os.unlink(sweep_sidecar)
        except OSError:
            pass
    # budget stop mid-sweep: problems never attempted (spec tail and any
    # already-drained submissions) still appear in the result (empty
    # draws, budget_exhausted) — the fleet path reports every problem,
    # and converged_fraction must count the unserved ones, not silently
    # shrink its denominator
    for _i, pid, _d, _bud in work:
        results.append(empty_result(pid, budget_exhausted=True))
    return FleetResult(
        results,
        wall_s=time.perf_counter() - t0,
        blocks_dispatched=sum(r.blocks for r in results),
        compactions=0,
        occupancy_trail=[],
        total_grad_evals=total_grads,
        budget_exhausted=budget_hit,
    )


def supervised_sample_fleet(
    spec: FleetSpec,
    *,
    workdir: str,
    stall_timeout_s: Optional[float] = None,
    **kwargs,
) -> FleetResult:
    """Run `sample_fleet` under the PR 2 supervision machinery
    (`supervise.supervised_sample` with the fleet runner plugged in):
    restart budget, fault taxonomy, backoff, watchdog, checkpoint health
    gating.  A crash mid-fleet resumes the SURVIVING ACTIVE SET from the
    fleet checkpoint — finished problems' draws are already durable and
    are never re-sampled, and QUARANTINED problems stay quarantined
    (their terminal status rides the checkpoint meta).

    ``stall_timeout_s`` arms the PR 2 watchdog around every fleet
    attempt: the fleet's block loop feeds `telemetry.notify_progress`
    beats from every warmup segment and every per-problem block record,
    so a hung fleet dispatch is aborted (`StallError`) and restarted
    like any other process-level fault — pick it larger than one
    vmapped dispatch including compile.  Supervision restarts stay
    WHOLE-FLEET by design (process-level faults); per-problem faults
    are contained below, inside `sample_fleet`, and never reach the
    supervisor."""
    from .supervise import supervised_sample

    def _runner(spec_, data_, **kw):
        assert data_ is None
        return sample_fleet(spec_, **kw)

    return supervised_sample(
        spec, None, workdir=workdir, stall_timeout_s=stall_timeout_s,
        _runner=_runner, **kwargs
    )
