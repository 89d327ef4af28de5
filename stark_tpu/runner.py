"""Adaptive runner: sample in blocks until R-hat < target (SURVEY.md §4).

The primary judged metric is *wall-clock to R-hat < 1.01* (BASELINE.json:2),
so this is the measurement driver: warmup once (compiled), then draw blocks
of ``block_size`` transitions per host round-trip; after each block the host
checks split-R-hat/ESS on the accumulated draws, appends a JSONL metrics
record, and optionally checkpoints the full chain state.  Stop when
converged (or budget exhausted) — the convergence-based stopping the
reference exposes via its R-hat/ESS diagnostics (SURVEY.md §2 layer C).

The block loop is a SOFTWARE PIPELINE by default: block k+1 is enqueued on
the device (jax dispatch is asynchronous) before the host materializes
block k's outputs, so device→host transfer, streaming diagnostics, draw
persistence, and checkpointing for block k all run while the device
computes block k+1 — the serial loop left the device idle for every
block's ``t_diag_s``.  PRNG keys are split on the host in dispatch order,
so the pipelined and serial (``STARK_SYNC_BLOCKS=1`` / ``sync_blocks=``)
loops produce bit-identical draws, metrics, and checkpoints; block k's
health check still gates block k's checkpoint, and a crash with block k+1
in flight discards it — resume reconciliation (`drawstore.truncate_draws`)
already accounts for the at-most-one-block skew between the draw store and
the checkpoint.  The trace's ``sample_block`` events carry the overlap
accounting (``t_wait_s`` / ``t_host_hidden_s`` / ``device_idle_s``) that
`tools/trace_report.py` and bench.py surface as a device-idle fraction,
measured from the device timeline: a waiter thread stamps when the device
finished each block (``device_done_ns`` on its ``block.wait`` span).

Auxiliary subsystems wired here (SURVEY.md §6):
  * metrics JSONL   — one line per block (max_rhat, min_ess, wall, divs)
  * checkpoint      — `checkpoint.save_checkpoint` every block; resume via
                      ``resume_from=`` (restarts mid-run after preemption)
  * profiler hooks  — ``profile_dir=`` wraps the first post-warmup block in
                      a `jax.profiler.trace` for TPU timeline inspection
  * failure detect  — ``health_check=True`` raises ChainHealthError on
                      non-finite state BEFORE it is checkpointed; see
                      `supervise.supervised_sample` for auto-restart
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import queue
import threading
import time
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np

from . import diagnostics, faults, health as _health, lineage, telemetry
from . import profile as _profile
from .backends.base import KernelEnv
from .chees import CheesBlockKernel, load_adapt_state  # noqa: F401 (re-export)
from .ops import quantize as _quantize
from .model import Model
from .platform import named_jit
from .sampler import ChainBlockKernel, Posterior, SamplerConfig
from .sampler import _constrain_draws, tree_counters

#: the streaming gate's device half: a block's accumulator
#: (`kernels.base.StreamDiagState`, chains-batched) reduced to its ESS row
#: where it lies, beside the draw counts the host checks.  One program for
#: every draw count (the count is data); the sampler's business in no way,
#: so it lives on this side of the `BlockKernel` seam.
_stream_ess = named_jit(
    lambda diag: (diagnostics.ess_from_suffstats(*diag), diag.n),
    "stark_stream_ess")


class AdaptiveResult(Posterior):
    """Posterior + convergence trajectory."""

    def __init__(self, *args, history=None, converged=False, wall_s=0.0, **kw):
        super().__init__(*args, **kw)
        self.history = history or []
        self.converged = converged
        self.wall_s = wall_s
        self.budget_exhausted = False
        # estimated draws beyond the ESS target at the measured ESS rate
        # (None when unconverged or no rate estimate) — see run_end trace
        self.overshoot_draws = None
        # statistical-health verdict (stark_tpu.health): sorted warning
        # names the observatory raised; None when STARK_HEALTH=0
        self.health_warnings = None


def data_fingerprint(data) -> str:
    """Order-stable fingerprint of a data pytree: tree structure, every
    array leaf's shape/dtype, and a strided content sample (<=64 KiB
    hashed per leaf, so N=1M stays cheap).  Guards the adaptation import
    against the silent case ADVICE r4 flagged: same model class, same
    ndim, DIFFERENT dataset — where every chain would start at the old
    posterior's typical-set points with mass/trajectory frozen at stale
    estimates and split R-hat could pass inside one basin."""
    import hashlib

    if data is None:
        return "none"
    h = hashlib.sha1()
    leaves, treedef = jax.tree.flatten(data)
    h.update(repr(treedef).encode())
    for leaf in leaves:
        try:
            a = np.ascontiguousarray(np.asarray(leaf))
            h.update(f"{a.shape}|{a.dtype}|".encode())
            b = a.view(np.uint8).ravel()
            if b.size > 65536:
                b = b[np.linspace(0, b.size - 1, 65536).astype(np.int64)]
            h.update(b.tobytes())
        except (TypeError, ValueError):  # non-buffer leaf (object, scalar)
            h.update(repr(leaf).encode())
    return h.hexdigest()[:16]


def _psum_counters(fm, chains: int, backend) -> Dict[str, int]:
    """What a gradient of a data-sharded run sends over the mesh, as the
    potential's trace wrote it down (`model.FlatModel.comm`): how many
    ``psum``s, and the bytes of their operand on one chip (the chains one
    chip holds, each with its packed ``[ll, ll_grad]``).  Empty off the
    mesh."""
    if not fm.comm:
        return {}
    local_chains = chains // _mesh_shape(backend)[1]
    return {
        "psums_per_gradient": fm.comm["psums_per_gradient"],
        "psum_bytes_per_gradient":
            fm.comm["psum_bytes_per_chain"] * local_chains,
    }


def _mesh_shape(backend) -> Tuple[int, int]:
    """Sizes of the backend's mesh axes ("data", "chains"); (1, 1) for a
    backend without a mesh (one device)."""
    shape = getattr(getattr(backend, "mesh", None), "shape", {})
    return int(shape.get("data", 1)), int(shape.get("chains", 1))


@_profile.entrypoint
def sample_until_converged(model: Model, data: Any = None, **kwargs):
    """Run chains until converged — see `_sample_until_converged` for the
    full parameter reference (this thin wrapper only pins the telemetry
    trace as ambient for the WHOLE run, so in-loop ``progress_every``
    heartbeats and backend-driver phase events reach a parameter-passed
    trace, not just an ambiently installed one, and applies the
    autotuned profile's knob defaults for the run — stark_tpu.profile;
    explicit env always wins, STARK_PROFILE=0 disables)."""
    trace = telemetry.resolve_trace(kwargs.pop("trace", None))
    mesh_data, mesh_chains = _mesh_shape(kwargs.get("backend"))
    with telemetry.use_trace(trace), telemetry.run_span(
        resumed=bool(kwargs.get("resume_from")),
        mesh_data=mesh_data, mesh_chains=mesh_chains,
    ):
        job = contextlib.nullcontext()
        if lineage.enabled():
            # single-run lineage parity: one ambient job for the whole run
            # (the supervisor's outer job wins, so every restart attempt
            # correlates to ONE id; otherwise mint deterministically from
            # the model/seed — a resumed run re-mints the same id)
            job = lineage.use_job(
                lineage.current_job() or lineage.mint_job_id(
                    getattr(model, "tag", type(model).__name__),
                    int(kwargs.get("seed", 0)),
                ))
        with job:
            return _sample_until_converged(model, data, trace=trace, **kwargs)


def _sample_until_converged(
    model: Model,
    data: Any = None,
    *,
    backend: Optional[Any] = None,
    chains: int = 4,
    block_size: int = 100,
    max_blocks: int = 50,
    min_blocks: int = 2,
    rhat_target: float = 1.01,
    ess_target: float = 400.0,
    diag_components: int = 64,
    seed: int = 0,
    checkpoint_path: Optional[str] = None,
    resume_from: Optional[str] = None,
    metrics_path: Optional[str] = None,
    profile_dir: Optional[str] = None,
    draw_store_path: Optional[str] = None,
    init_params: Optional[Dict[str, Any]] = None,
    health_check: bool = False,
    reseed: Optional[int] = None,
    progress_cb: Optional[Any] = None,
    time_budget_s: Optional[float] = None,
    adapt_path: Optional[str] = None,
    adapt_export_path: Optional[str] = None,
    adapt_touchup_frac: float = 0.2,
    trace: Optional[Any] = None,
    sync_blocks: Optional[bool] = None,
    stream_diag: Optional[bool] = None,
    adaptive_blocks: Optional[bool] = None,
    diag_lags: Optional[int] = None,
    **cfg_kwargs,
) -> AdaptiveResult:
    """Run chains until R-hat < rhat_target AND min-ESS > ess_target.

    Draw blocks are compiled once and reused.  The per-block convergence
    signal is STREAMING: per-chain Welford sufficient statistics updated in
    O(chains*d) (`diagnostics.ChainSuffStats` -> `rhat_from_suffstats`), plus
    Geyer ESS on only the ``diag_components`` worst-mixing components — so
    the per-block full-history work is O(draws * diag_components),
    independent of d (the old path rescanned all d components every
    block).  When the streaming criteria pass, one full split-R-hat/ESS
    pass over all draws VALIDATES the stop (recorded as ``full_max_rhat`` /
    ``full_min_ess`` in the block's metrics line); failed validations back
    off geometrically, so the O(draws*d) full diagnostics run O(log blocks)
    times per run instead of every block.

    ``progress_cb`` (if given) is invoked with every metrics record
    (warmup_done and block events) as it is emitted — callers use it to
    surface best-so-far results while the run is still in flight, so an
    external kill/timeout never erases all evidence of progress.
    ``time_budget_s`` bounds the SAMPLING wall-clock: after any block that
    ends past the budget (measured from this call's start) the run stops
    and returns what it has, with ``budget_exhausted=True`` on the result.
    Warmup is not interrupted — a run whose warmup alone exceeds the
    budget is misconfigured, and an aborted warmup would leave nothing
    usable to return.

    ``backend`` (default: a fresh `JaxBackend`) supplies the compiled
    execution layer via `SamplerBackend.adaptive_parts` — pass a
    `ShardedBackend` to run the SAME convergence/checkpoint/supervision
    protocol with chains and data sharded over a device mesh (checkpoints
    round-trip through host numpy; resume re-places state on the mesh).

    ``adapt_path`` (chees only): adaptation REUSE across runs — the
    Stan-style "metric import" that attacks the warmup share of wall
    (measured 37% on the r3 flagship).  After a fresh warmup the tuned
    (step size, trajectory length, inverse mass, end-of-warmup
    positions) are saved there; a later run whose (kernel, model, ndim,
    dataset fingerprint) match loads them, starts the ensemble NEAR the
    saved typical-set positions (re-jittered by half the cross-chain
    spread so starts stay overdispersed — ADVICE r4), and replaces the
    full warmup with a short touch-up
    (``adapt_touchup_frac`` of ``num_warmup``; ONLY the step size
    re-tunes, anchored at the imported value — trajectory length and
    mass stay frozen at the imported estimates).  Convergence
    is still validated by the same R-hat/ESS gate on fresh draws, so a
    stale import costs extra blocks, never a false convergence claim.
    Set ``map_init_steps=0`` on reuse runs — MAP descent from imported
    typical-set positions is wasted work.

    ``trace`` (default: the ambient `telemetry` trace, `NullTrace` when
    none is installed): schema-versioned JSONL run telemetry — run
    envelope, compile/warmup_block/sample_block phase timings, per-block
    chain_health (acceptance, step size, divergences, R-hat/ESS), and
    checkpoint durations.  Distinct from ``metrics_path`` (the runner's
    convergence trail): the trace is the cross-run artifact
    `tools/trace_report.py` and `bench.py` consume.

    ``sync_blocks`` (default: the ``STARK_SYNC_BLOCKS=1`` env escape
    hatch, else False on single-process runs; multi-process meshes
    always run serial — their collect is an allgather whose dispatch
    would be stream-ordered behind the prefetched block): True disables
    the asynchronous block pipeline and runs the historical
    strictly-serial loop — one block dispatched, awaited, and
    host-processed at a time.  Draws, metrics history, and
    checkpoints are bit-identical in both modes (only timing fields and
    the overlap trace fields differ); the serial mode exists for
    debugging and as the equivalence oracle in tests.

    ``stream_diag`` (default: on; ``STARK_STREAM_DIAG=0`` escape hatch):
    the compiled draw blocks additionally carry an ON-DEVICE streaming-
    diagnostics accumulator (`kernels.base.StreamDiagState` — Welford
    moments + lag-1..``diag_lags`` autocovariance sums, per chain per
    coordinate), and the per-block ESS signal comes from
    `diagnostics.ess_from_suffstats` on that O(chains*d*L) summary
    instead of the full-history FFT pass over the worst-k components.
    The summary never leaves the device: a small program
    (``stark_stream_ess``) enqueued behind each block reduces it to the
    ESS row, and the gate fetches that row and the draw counts, ``d``
    floats and ``chains`` integers whatever the draw count and the lags
    (the ``diag_bytes_to_host`` trace field documents it).  The
    streaming estimate is an ESS LOWER BOUND (truncation errs
    conservative), and it only decides *when to look*: every candidate
    stop is still validated by the same full split-R-hat/ESS pass over
    all draws before the run may stop.  Draws/checkpoints are unaffected
    (the accumulator only consumes the draw stream); with the flag off
    the runner is bit-identical to the pre-streaming behavior.

    ``adaptive_blocks`` (default: on; ``STARK_ADAPTIVE_BLOCKS=0`` escape
    hatch): replaces the fixed ``block_size`` march with an ESS-rate
    forecaster.  Blocks grow geometrically (block_size/2 -> block_size ->
    2x -> 4x, capped) while far from the target, and once an ESS rate is
    measurable the next block is sized to the forecast deficit
    ``(ess_target - min_ess)/rate`` (quantized to the geometric ladder to
    bound compile variants), so a converging run stops within about one
    small block of the target instead of overshooting by a full fixed
    block.  The TOTAL draw budget is unchanged — ``max_blocks *
    block_size`` draws per chain, so a budget-bounded run
    (``rhat_target=0``) draws exactly the same total as the fixed march,
    only the block boundaries (and checkpoint cadence) differ;
    ``min_blocks`` still counts blocks, so the earliest stop comes after
    ``min_blocks`` (now smaller) blocks, always full-pass
    validated.  With the flag off the historical
    fixed-size loop runs bit-exactly.  ``diag_lags`` (default
    `kernels.base.STREAM_DIAG_LAGS` = 50) sets the autocovariance
    truncation L.
    """
    cfg = SamplerConfig(**cfg_kwargs)
    if backend is None:
        from .backends.jax_backend import JaxBackend

        backend = JaxBackend()
    if not hasattr(backend, "adaptive_parts"):
        raise TypeError(
            f"{type(backend).__name__} does not support the adaptive "
            "runner (no adaptive_parts); use JaxBackend or ShardedBackend"
        )
    # streaming diagnostics + adaptive block scheduling (see docstring).
    # Env escape hatches restore the historical behavior bit-exactly.
    from .kernels.base import STREAM_DIAG_LAGS

    if stream_diag is None:
        stream_diag = os.environ.get("STARK_STREAM_DIAG", "1") != "0"
    if adaptive_blocks is None:
        adaptive_blocks = os.environ.get("STARK_ADAPTIVE_BLOCKS", "1") != "0"
    if diag_lags is None:
        diag_lags = STREAM_DIAG_LAGS
    # multi-process meshes: every process drives identical blocks on its
    # shard of the chains and (after the collect allgather) holds
    # identical host state, so each writes its own state files — shared
    # filesystems must not race on one path (real pods write per-host
    # anyway).  rank_path is identity in single-process runs.
    from .checkpoint import rank_path

    checkpoint_path = rank_path(checkpoint_path)
    resume_from = rank_path(resume_from)
    metrics_path = rank_path(metrics_path)
    draw_store_path = rank_path(draw_store_path)
    adapt_path = rank_path(adapt_path)
    # export target may differ from the import candidate so a caller can
    # import a pinned (committed) artifact while cold-start exports land
    # in an untracked cache — the runner then structurally CANNOT dirty
    # the pinned file, even if its own validation rejects what the
    # caller's pre-check accepted (file changed between the two loads)
    adapt_export_path = rank_path(adapt_export_path) or adapt_path

    # fingerprint the CALLER's data before `data` is rebound to the
    # prepared/sharded form below: the adaptation-artifact contract is
    # keyed on what the caller passed, so bench.py (which holds the same
    # raw pytree) computes the identical fingerprint when deciding
    # whether the import will be accepted
    adapt_fp = (
        data_fingerprint(data)
        if (adapt_path or adapt_export_path)
        else None
    )
    # telemetry (telemetry.py): the runner is the primary trace emitter —
    # run envelope, compile/warmup/sample phase boundaries, per-block
    # chain health, checkpoint timings.  Default is the ambient trace
    # (NullTrace unless a --trace flag / bench driver installed one).
    trace = telemetry.resolve_trace(trace)
    # which fused likelihood family (if any) will evaluate every gradient
    # of this run — knob state resolved HERE, once, so the tag matches
    # the execution path the compiled potential actually takes.  Stamped
    # into run_start and every per-block grad-eval record below: a trace
    # or ledger row then says which path produced its numbers.
    fused_tag = model.fused_tag() if hasattr(model, "fused_tag") else None
    # statistical-health observatory (stark_tpu.health): a host-side
    # streaming monitor fed from the block readbacks below — entirely
    # outside the kernels' op/key sequence, so draws/metrics/checkpoints
    # are bit-identical with it on; STARK_HEALTH=0 removes the trace
    # events too (byte-identical traces)
    monitor = (
        _health.HealthMonitor(
            kernel=cfg.kernel, max_depth=cfg.max_tree_depth, trace=trace
        )
        if _health.health_enabled() else None
    )
    t_run0 = time.perf_counter()  # run_end dur covers setup/compile too
    if trace.enabled:
        trace.emit(
            "run_start",
            entry="sample_until_converged",
            model=type(model).__name__,
            **({"fused": fused_tag} if fused_tag else {}),
            # quantized/bf16 X streaming (ops/quantize.py): resolved
            # stream dtype + slab bytes per gradient evaluation, so the
            # timeline/ledger can turn dispatch counts into measured
            # bandwidth; absent on f32 runs (trace byte-identity)
            **_quantize.x_stream_tags(fused_tag, data),
            kernel=cfg.kernel,
            chains=chains,
            block_size=block_size,
            max_blocks=max_blocks,
            rhat_target=rhat_target,
            ess_target=ess_target,
            resuming=bool(resume_from),
            # {"profile": id} when an autotuned profile steers this run;
            # ABSENT otherwise (byte-identical pre-profile traces)
            **_profile.run_start_tags(),
            **telemetry.device_info(),
            **telemetry.provenance(),
        )
    with trace.phase("compile", stage="build"):
        ap = backend.adaptive_parts(model, cfg, data)
        # the posterior's width, the sampler, and whether its programs
        # carry the potential's centre (`model.Centering`: the ensemble's,
        # or a row a chain under the per-chain kernels), on the call's
        # `run` span
        centering = (ap.fm.centering if ap.chees is not None
                     else ap.fm.chain_centering)
        telemetry.note(root=True, ndim=ap.fm.ndim, kernel=cfg.kernel,
                       centred=centering is not None and data is not None)

    if sync_blocks is None:
        # multi-process meshes run serial: collect is a process_allgather
        # (distributed.gather_draws) — a dispatched computation that is
        # stream-ordered AFTER an already-enqueued block k+1, so a
        # prefetch there wouldn't overlap anything; it would delay block
        # k's health check and checkpoint durability by a whole block
        sync_blocks = (
            os.environ.get("STARK_SYNC_BLOCKS", "") == "1"
            or jax.process_count() > 1
        )

    run = _Run(
        cfg=cfg, ap=ap, backend=backend, trace=trace, monitor=monitor,
        model_name=type(model).__name__, fused_tag=fused_tag,
        block_size=block_size, max_blocks=max_blocks, min_blocks=min_blocks,
        rhat_target=rhat_target, ess_target=ess_target,
        diag_components=diag_components, diag_lags=diag_lags,
        checkpoint_path=checkpoint_path, health_check=health_check,
        progress_cb=progress_cb, time_budget_s=time_budget_s,
        profile_dir=profile_dir, sync_blocks=sync_blocks,
        adaptive_blocks=adaptive_blocks,
    )
    # the kernel seam (`backends.base.BlockKernel`): the ensemble sampler
    # or the per-chain kernels, as the backend's parts say
    kernel_cls = CheesBlockKernel if ap.chees is not None else ChainBlockKernel
    run.kernel = kernel_cls(ap, cfg, chains, KernelEnv(
        block_size=block_size, stream_diag=stream_diag,
        sync_blocks=sync_blocks, diag_lags=diag_lags, seed=seed,
        init_params=init_params, trace=trace, emit=run.emit,
        model_name=run.model_name, checkpoint_path=checkpoint_path,
        health_check=health_check, adapt_path=adapt_path,
        adapt_export_path=adapt_export_path,
        adapt_touchup_frac=adapt_touchup_frac, adapt_fp=adapt_fp,
    ))
    run.t_start = time.perf_counter()
    run.metrics_f = open(metrics_path, "a") if metrics_path else None
    run.waiter = _DoneWaiter()
    try:
        try:
            _setup(run, resume_from, reseed, draw_store_path)
            _block_loop(run)
        finally:
            if run.metrics_f:
                run.metrics_f.close()
            if run.draw_store is not None:
                run.draw_store.close()
        result = _collect(run, t_run0)
    finally:
        run.waiter.close()
    # the kernel holds `run.emit`: cut the cycle, nothing waits for a gc
    run.kernel = run.pending = None
    return result


@dataclasses.dataclass
class _Run:
    """One call of the runner: its configuration, its collaborators and the
    block loop's state, in one place for `_setup`, `_block_loop` and
    `_collect`.  The sampler's own carried state is the kernel's."""

    cfg: SamplerConfig
    ap: Any  # the backend's `AdaptiveParts`
    backend: Any
    trace: Any
    monitor: Any  # health observatory, or None
    model_name: str
    fused_tag: Optional[str]
    block_size: int
    max_blocks: int
    min_blocks: int
    rhat_target: float
    ess_target: float
    diag_components: int
    diag_lags: int
    checkpoint_path: Optional[str]
    health_check: bool
    progress_cb: Any
    time_budget_s: Optional[float]
    profile_dir: Optional[str]
    sync_blocks: bool
    adaptive_blocks: bool
    kernel: Any = None  # `backends.base.BlockKernel`
    t_start: float = 0.0
    metrics_f: Any = None
    draw_store: Any = None
    suff: Any = None  # diagnostics.ChainSuffStats
    draws_hist: Any = None  # diagnostics.DrawHistory
    # -- the loop's state --------------------------------------------------
    key: Any = None  # host PRNG key, split once a dispatch
    diag: Any = None  # device-resident StreamDiagState, streaming mode
    pending: Any = None  # the block dispatched ahead
    blocks_done: int = 0
    blocks_dispatched: int = 0
    # the kernels' stream position: advanced at DISPATCH (the pipeline runs
    # ahead of the host's count), anchored at the resumed draw count, so
    # every mode walks the same sequence
    draws_dispatched: int = 0
    total_div: int = 0
    converged: bool = False
    budget_exhausted: bool = False
    next_full_check: int = 0  # earliest block allowed to run full validation
    profile_next: bool = False
    history: list = dataclasses.field(default_factory=list)
    # adaptive block scheduler: the (draws, min_ess) trail of processed
    # blocks that the ESS-rate forecaster reads, and its reporting forecast
    points: list = dataclasses.field(default_factory=list)
    forecast_draws: Optional[int] = None
    rate: Optional[float] = None
    # the device timeline: the waiter that stamps each block's completion
    # (`_DoneWaiter`), and the last processed block's stamp
    waiter: Any = None
    done_ns: Optional[int] = None

    @property
    def chains(self) -> int:
        return self.kernel.chains

    @property
    def stream_diag(self) -> bool:
        return self.kernel.stream_diag

    @property
    def max_draws(self) -> int:  # the fixed march as a DRAW budget
        return self.max_blocks * self.block_size

    def emit(self, rec):
        # every record is a progress beat (watchdog liveness), flushed AND
        # fsynced: the metrics trail must survive the crash it documents
        telemetry.notify_progress()
        if self.metrics_f:
            self.metrics_f.write(json.dumps(rec) + "\n")
            self.metrics_f.flush()
            os.fsync(self.metrics_f.fileno())
        if self.progress_cb is not None:
            try:
                self.progress_cb(rec)
            except Exception:  # noqa: BLE001 — observability must not kill
                # the run: a BrokenPipeError from a closed capture pipe would
                # burn the supervisor's restart budget on healthy state
                pass
        if self.trace.enabled and rec.get("event", "").startswith("adapt_"):
            # adaptation decisions mirror into the trace
            fields = {k: v for k, v in rec.items() if k != "event"}
            self.trace.emit("adapt", kind=rec["event"], **fields)

    def wall_s(self) -> float:
        return time.perf_counter() - self.t_start


# ---------------------------------------------------------------------------
# set-up: a fresh start or a resume, through the kernel seam
# ---------------------------------------------------------------------------


def _emit_warmup_done(run: _Run, num_divergent, fields):
    """The ``warmup_done`` record: the loop's part and the kernel's
    ``fields`` (``warmup_grad_evals``, ``resumed_from_step``,
    ``adapt_imported``, where they apply)."""
    rec = {
        "event": "warmup_done",
        "wall_s": run.wall_s(),
        "num_divergent": int(np.sum(np.asarray(num_divergent))),
        # per-chain kernels carry chain-sharded step sizes: collect
        # (allgather on a multi-process mesh) before reading
        "step_size": np.asarray(
            run.ap.collect(run.kernel.step_size)).tolist(),
        **fields,
    }
    with telemetry.span("block.record", event="warmup_done"):
        run.emit(rec)
    if run.trace.enabled:
        run.trace.emit(
            "chain_health", status="warmup_done",
            num_divergent=rec["num_divergent"],
            step_size=round(float(np.mean(rec["step_size"])), 6),
        )


def _setup(run: _Run, resume_from, reseed, draw_store_path):
    """Bring the kernel to its first sampling dispatch (fresh: keys,
    positions, MAP, warm-up; resumed: the checkpoint, and the rest of a
    warm-up it was cut in), rebuild the streaming statistics from the
    draws a resume finds stored, open the draw store."""
    cfg, kernel = run.cfg, run.kernel
    draw_blocks = []
    # a resumed run's way to its first dispatch, as one span: checkpoint
    # load, state restore, the rebuild of the streaming statistics from the
    # stored draws (closed where the block loop starts)
    resume_span = None
    if resume_from:
        from .checkpoint import load_checkpoint

        resume_span = telemetry.span(
            "resume_load", bytes_read=os.path.getsize(resume_from)).open()
        arrays, meta = load_checkpoint(resume_from)
        ckpt_kernel = meta.get("kernel")
        if ckpt_kernel is not None and ckpt_kernel != cfg.kernel:
            raise ValueError(
                f"checkpoint was written by kernel={ckpt_kernel!r}, "
                f"resuming run uses kernel={cfg.kernel!r}"
            )
        run.key, n_div, fields = kernel.restore(arrays, meta, reseed)
        if fields is not None:  # the file was cut in the warm-up
            _emit_warmup_done(run, n_div, fields)
        run.blocks_done = int(meta.get("blocks_done", 0))
        run.total_div = int(meta.get("num_divergent", 0))
        run.history = list(meta.get("history", []))
        if "draws" in arrays:
            draw_blocks = [arrays["draws"]]
        elif draw_store_path and os.path.exists(draw_store_path):
            from .drawstore import read_draws, truncate_draws

            # the async writer can land a block after the last completed
            # checkpoint: drop rows the checkpoint doesn't account for, or
            # the re-run block double-counts.  The accounted count rides in
            # the meta (the original run's block size, not this call's —
            # they may differ legally, so the fallback must use the
            # checkpointed block_size, never the resuming call's).
            accounted = meta.get(
                "draw_rows",
                run.blocks_done * int(meta.get("block_size", run.block_size)),
            )
            truncate_draws(draw_store_path, accounted)
            stored, _, _ = read_draws(draw_store_path, mmap=False)
            if stored.shape[0]:
                # (n, chains, d) on disk -> (chains, n, d) in memory
                draw_blocks = [np.ascontiguousarray(stored.transpose(1, 0, 2))]
    else:
        run.key, n_div, fields = kernel.start()
        _emit_warmup_done(run, n_div, fields)

    # what stands between here and the first dispatch is `resume_load`'s on
    # a resumed run and this span's on a fresh one (no phase event): the
    # streaming statistics and the diagnostics carry are built and placed
    loop_span = resume_span or telemetry.span(
        "compile", stage="loop_init").open()
    chains, ndim = run.chains, run.ap.fm.ndim
    run.suff = diagnostics.ChainSuffStats(chains, ndim)
    # the draw history in ONE growing preallocated host buffer: a block is
    # written once, and full-history passes read a zero-copy view
    run.draws_hist = diagnostics.DrawHistory(chains, ndim)
    for blk in draw_blocks:
        run.suff.update(blk)  # a resume's streaming stats, from stored draws
        run.draws_hist.append(blk)
    del draw_blocks
    run.draws_dispatched = int(run.suff.count[0])

    if run.stream_diag:
        # device-resident streaming-diagnostics carry, (chains,)-batched.
        # A resume rebuilds it from the stored draws (host reference
        # implementation of the same accumulator), so the gate's summary
        # covers the WHOLE history, not just post-resume blocks.
        from .kernels.base import StreamDiagState

        host_diag = diagnostics.stream_diag_from_draws(
            run.draws_hist.view()
            if run.draws_hist.rows
            else np.zeros((chains, 0, ndim), np.float32),
            run.diag_lags, chains=chains, ndim=ndim, dtype=kernel.dtype,
        )
        run.diag = StreamDiagState(
            **{k: run.ap.put_chains(v) for k, v in host_diag.items()})

    # the scheduler's trail is seeded from the resumed metrics history so a
    # resumed run reconstructs the SAME schedule decisions the original made
    for r in run.history:
        e = r.get("min_ess")
        run.points.append((int(r.get("draws_per_chain", 0)),
                           float(e) if e is not None else None))
    if draw_store_path:
        from .drawstore import DrawStore

        run.draw_store = DrawStore(draw_store_path, chains, ndim)
    run.blocks_dispatched = run.blocks_done
    run.profile_next = bool(run.profile_dir) and run.blocks_done == 0
    loop_span.close(draws_rebuilt=run.draws_hist.rows * chains)


# ---------------------------------------------------------------------------
# the block loop: dispatch / wait / gate / record / checkpoint
# ---------------------------------------------------------------------------


def _rate_and_deficit(points, ess_target):
    """(rate, deficit) from a (draws, min_ess) trail — window rate over the
    last two finite points when it is positive, else the cumulative rate;
    deficit is vs the LAST finite point."""
    usable = [p for p in points if p[1] is not None]
    if not usable:
        return None, None
    draws_u, ess_u = usable[-1]
    rate = None
    if len(usable) >= 2:
        dd = draws_u - usable[-2][0]
        de = ess_u - usable[-2][1]
        if dd > 0 and de > 0:
            rate = de / dd
    if rate is None and draws_u > 0 and ess_u > 0:
        rate = ess_u / draws_u
    return rate, ess_target - ess_u


def _next_block_len(run: _Run) -> int:
    """Length of the next dispatch.  Fixed mode: always block_size (the
    historical loop).  Adaptive mode: geometric growth from block_size/2 capped
    at 4x (ramp ordinal = GLOBAL block ordinal, so a resumed run continues the
    ramp), shrunk to the ESS-forecast deficit (quantized to multiples of the
    base quantum so at most cap/quantum compiled block variants exist), and
    truncated to the remaining draw budget.

    REPLAY DETERMINISM: the forecast reads the stats trail only up to block
    ``m-2`` when sizing block ``m`` — exactly what the pipelined loop (which
    dispatches m before processing m-1) can know.  The serial loop deliberately
    ignores its one-block-fresher stats, and a resumed run re-reads the same
    window from the checkpointed history, so serial, pipelined, and
    crash-resumed runs all size every block identically — which is what keeps
    the supervised replay bit-identical (chaos: inflight_block_replay).
    """
    if not run.adaptive_blocks:
        return run.block_size
    remaining = run.max_draws - run.draws_dispatched
    if remaining <= 0:
        return 0
    quantum = max(1, run.block_size // 2)
    cap = max(run.block_size, 4 * run.block_size)
    m = run.blocks_dispatched  # 0-based ordinal of the next dispatch
    n = min(cap, quantum * (2 ** min(m, 8)))
    rate, deficit = _rate_and_deficit(
        run.points[: max(0, m - 1)], run.ess_target)
    if rate and deficit is not None and deficit > 0:
        # 1.1 safety: the rate estimate is noisy, and undershooting
        # repeatedly costs a host round-trip per correction
        need = int(np.ceil(1.1 * deficit / rate))
        need = -(-max(need, 1) // quantum) * quantum
        n = min(n, max(need, quantum))
    return min(n, remaining)


def _note_block_ess(run: _Run, min_ess, draws_now):
    """Record one processed block's ESS; refresh the REPORTING forecast
    (trace/metrics fields) from the full trail — the scheduler itself reads the
    delayed window above."""
    run.points.append(
        (int(draws_now), float(min_ess) if np.isfinite(min_ess) else None)
    )
    rate, deficit = _rate_and_deficit(run.points, run.ess_target)
    run.rate = rate
    run.forecast_draws = (
        int(draws_now + max(0.0, deficit) / rate) if rate else None)


def _dispatch_next(run: _Run):
    """Split the next block's key on the HOST (identical stream in serial and
    pipelined order), size the block (fixed or ESS-forecast adaptive), and
    ENQUEUE it without waiting.  -> the pending record `_process_block`
    materializes later, or None when the draw budget is spent.  Its ``carried``
    refs are what block k's health check gates and block k's checkpoint
    persists, and ``key`` is the host key as of THIS split — stored in the
    checkpoint regardless of how far ahead the pipeline has already split for
    later blocks."""
    length = _next_block_len(run)
    if length <= 0:
        return None
    enq_span = telemetry.span(
        "block.dispatch", block=run.blocks_dispatched + 1, length=length
    ).open()
    run.key, key_block = jax.random.split(run.key)
    # the profiler wants one block's device timeline by itself: run the first
    # block synchronously under the trace, then pipeline from the next block on
    profiled, run.profile_next = run.profile_next, False
    with (jax.profiler.trace(run.profile_dir) if profiled
          else contextlib.nullcontext()):
        pend = run.kernel.dispatch(
            key_block, length, run.diag, run.draws_dispatched)
        if profiled:
            telemetry.wait(pend.outs)
    run.diag, pend.key = pend.diag, run.key
    if run.stream_diag:
        # enqueued HERE, behind block k and ahead of block k+1: dispatched
        # from the gate it would wait on the device behind the block in
        # flight, and the gate would serialise with the device
        pend.ess = _stream_ess(pend.diag)
    enq_span.close()
    pend.t_enq, pend.dispatched_ns = enq_span.seconds, enq_span.end_ns
    run.waiter.put(pend)
    run.blocks_dispatched += 1
    run.draws_dispatched += length
    return pend


class _DoneWaiter:
    """One thread beside the block loop that blocks on every dispatched
    block's outputs and ESS row, in dispatch order, and stamps
    ``pend.t_done_ns`` when the device has finished them: the block
    loop's own wait returns only when the HOST gets there, which says
    nothing of the device when the host was late.  A wait that raises
    leaves no stamp (the loop's own wait raises the same error); the
    thread ends at `close`, after the blocks handed to it, and holds a
    block only until the device has finished it.  `sample_until_converged`
    owns it, from `_setup` to the end of `_collect`."""

    #: how long `close` waits for the thread: past it (a device wedged on a
    #: queued block) the daemon thread ends once the device lets it go
    JOIN_S = 10.0

    def __init__(self):
        self._queue = queue.SimpleQueue()
        self._thread = threading.Thread(
            target=self._run, name="stark-block-done", daemon=True)
        self._thread.start()

    def put(self, pend):
        pend.done = threading.Event()
        self._queue.put(pend)

    def _run(self):
        while (pend := self._queue.get()) is not None:
            try:
                jax.block_until_ready((pend.outs, pend.ess))
                pend.t_done_ns = time.perf_counter_ns()
            except Exception:  # noqa: BLE001 — the loop's wait raises it
                pass
            finally:
                pend.done.set()
                del pend

    def close(self):
        if self._thread.is_alive():
            self._queue.put(None)
            self._thread.join(self.JOIN_S)


def _device_done_ns(pend, ready_ns: int) -> int:
    """When the device finished ``pend`` and its ESS row: the earlier of
    two moments by which it had, the waiter's stamp and ``ready_ns``, when
    the block loop had both (`_Block.ready_ns`).  The loop's is exact when the host waited; the
    waiter's when the host came late, unless the loop held the
    interpreter lock then (a lag of at most its switch interval)."""
    if pend.done.wait(timeout=60.0) and pend.t_done_ns is not None:
        return min(pend.t_done_ns, ready_ns)
    return ready_ns


@dataclasses.dataclass
class _Block:
    """One finished block's way through the host cycle."""

    pend: Any  # `backends.base.PendingBlock`
    blk: int
    host: Any = None  # `backends.base.HostBlock`
    wait_span: Any = None
    # when the loop had the block's outputs and ESS row (`block.wait`'s
    # wait, or the gate's fetch of the row), and when the device had
    # finished them (`_device_done_ns`)
    ready_ns: int = 0
    done_ns: int = 0
    rec: Any = None
    n_stuck: int = 0
    max_rhat: float = float("inf")
    min_ess: float = float("nan")
    draws_per_chain: int = 0
    diag_bytes: int = 0
    t_ckpt: float = 0.0


def _tree_counters(cfg, hb) -> dict:
    """What a per-chain kernel's block cost, for the `block.gate` span
    (nothing for the ensemble sampler): `sampler.tree_counters`,
    ``divergent`` and, for NUTS, ``tree_depths``, the transitions by
    trajectory depth 0..max_tree_depth
    (`kernels.nuts.tree_depth_from_leaves`)."""
    if hb.ngrad is None:
        return {}
    out = dict(tree_counters(hb.ngrad), divergent=int(np.sum(hb.divergent)))
    if cfg.kernel == "nuts":
        from .kernels.nuts import tree_depth_from_leaves

        out["tree_depths"] = np.bincount(
            tree_depth_from_leaves(hb.ngrad).ravel(),
            minlength=cfg.max_tree_depth + 1).tolist()
    return out


def _gate_block(run: _Run, b: _Block):
    """`block.gate`: the host's work on the block up to its record —
    health gate, draw persistence, streaming R-hat / ESS, stop
    validation."""
    pend, hb = b.pend, b.host
    gate_span = telemetry.span(
        "block.gate", block=b.blk, block_grad_evals=hb.grad_evals,
        **_psum_counters(run.ap.fm, run.chains, run.backend),
        **_tree_counters(run.cfg, hb),
    ).open()
    if run.health_check:
        # poisoned state must never reach the checkpoint (the supervisor
        # restarts from the last healthy one); ``pend`` holds block k's
        # carried state, so k's health gates k's checkpoint with k+1 in flight
        from .supervise import check_finite_state

        carried = run.ap.collect(dict(pend.carried))
        if run.monitor is not None:
            # the statistical trail records the stuck chain BEFORE the
            # finite check below raises into the supervisor
            run.monitor.observe_state(carried, block=b.blk)
        check_finite_state(carried)
    run.blocks_done += 1
    run.draws_hist.append(hb.zs)
    if run.draw_store is not None:
        # async writer; a draw-major block goes in as it is, without a copy
        if hb.zs_dm is not None:
            run.draw_store.append(hb.zs_dm, draw_major=True)
        else:
            run.draw_store.append(hb.zs)
    run.total_div += int(np.sum(hb.divergent))

    run.suff.update(hb.zs)
    srhat = run.suff.rhat()
    # NaN streaming R-hat = frozen component: counted, never hidden by a
    # nanmax, and it blocks the stop gate below
    b.n_stuck = int(np.count_nonzero(np.isnan(srhat)))
    finite_rhat = srhat[~np.isnan(srhat)]
    b.max_rhat = (
        float(np.max(finite_rhat)) if finite_rhat.size else float("inf"))
    if run.stream_diag:
        # streaming gate: the convergence signal's ONLY device->host traffic
        # is the ESS row the device reduced its accumulator to behind the
        # block (`_dispatch_next`) and the chains' draw counts
        ess_vals, n_host = run.ap.collect(pend.ess)
        b.ready_ns = time.perf_counter_ns()
        b.diag_bytes = int(ess_vals.nbytes + n_host.nbytes)
        diagnostics.uniform_count(n_host)  # ragged counts raise
    else:
        # host gate: ESS on the worst-mixing components alone (by streaming
        # R-hat, NaN counting as worst): O(draws * k) host work per block
        k = min(run.diag_components, run.ap.fm.ndim)
        worst = np.argsort(np.where(np.isnan(srhat), -np.inf, -srhat))[:k]
        subset = run.draws_hist.take(worst)
        b.diag_bytes = int(subset.nbytes)
        ess_vals = diagnostics.ess(subset)
    finite_ess = ess_vals[np.isfinite(ess_vals)]
    # NaN ESS (stuck components) stays out of the reported minimum, which
    # num_stuck_components covers; all-NaN gives NaN and fails the gate
    b.min_ess = float(np.min(finite_ess)) if finite_ess.size else float("nan")
    b.draws_per_chain = int(run.suff.count[0])
    _note_block_ess(run, b.min_ess, b.draws_per_chain)
    b.rec = rec = {
        "event": "block",
        "block": run.blocks_done,
        "draws_per_chain": b.draws_per_chain,
        # metrics must stay strict JSON: non-finite values -> null
        "max_rhat": b.max_rhat if np.isfinite(b.max_rhat) else None,
        "min_ess": b.min_ess if np.isfinite(b.min_ess) else None,
        "num_stuck_components": b.n_stuck,
        "num_divergent": run.total_div,
        "mean_accept": hb.mean_accept,
        # device-attributed time (enqueue + the host's wait for the device,
        # near zero when the pipeline hides host work)
        "t_dispatch_s": round(pend.t_enq + b.wait_span.seconds, 3),
        # the length of the `block.gate` span, stamped where it closes (below)
        "t_diag_s": None,
        # GRADIENT EVALUATIONS on all paths: leapfrog steps (ChEES / HMC) or
        # tree leaves (NUTS), one each; the basis is named beside the count
        "block_grad_evals": hb.grad_evals,
        "grad_eval_basis":
            "tree_leaves" if run.cfg.kernel == "nuts" else "leapfrog",
        # only on fused-model runs / ragged-NUTS runs: the plain trail stays
        # byte-identical
        **({"fused": run.fused_tag} if run.fused_tag else {}),
        **hb.sched_fields,
        "wall_s": run.wall_s(),
    }
    if run.stream_diag:  # these fields ride the streaming mode alone
        rec["diag_bytes_to_host"] = b.diag_bytes
        if run.forecast_draws is not None:
            rec["ess_forecast"] = run.forecast_draws
    # failpoint: force the streaming gate optimistic (arm with the ``nan``
    # data directive) — the candidate stop then reaches the full validation
    # pass early, which must reject it (the tier-1 guard test's invariant)
    forced_opt = faults.fail_point("runner.gate.optimistic") is not None
    # min_blocks counts BLOCKS in both modes (the adaptive scheduler's early
    # blocks are smaller); the full pass gates every stop on all draws
    gate_pass = (b.n_stuck == 0 and b.max_rhat < run.rhat_target
                 and b.min_ess > run.ess_target)
    if (
        run.blocks_done >= run.min_blocks
        and (gate_pass or forced_opt)
        and run.blocks_done >= run.next_full_check
    ):
        # candidate stop: validate with the full split-form pass
        full_draws = run.draws_hist.view()
        full_rhat = float(np.max(diagnostics.split_rhat(full_draws)))
        full_ess = float(np.min(diagnostics.ess(full_draws)))
        rec["full_max_rhat"] = full_rhat
        rec["full_min_ess"] = full_ess
        # recorded, not gated: the rank form flags heavy-tail / scale
        # disagreement the classic gate can miss
        rec["full_max_rank_rhat"] = float(
            np.max(diagnostics.rank_rhat(full_draws)))
        # re-stamp the wall: it covers the expensive validation too
        rec["wall_s"] = run.wall_s()
        if full_rhat < run.rhat_target and full_ess > run.ess_target:
            run.converged = True
        else:
            run.next_full_check = run.blocks_done + max(
                1, run.blocks_done // 4)
    gate_span.close(diag_bytes_to_host=b.diag_bytes)
    rec["t_diag_s"] = round(gate_span.seconds, 3)
    run.history.append(rec)


def _record_block(run: _Run, b: _Block):
    """`block.record`: the block's metrics record, then the health
    observatory's sweep."""
    with telemetry.span("block.record", block=b.blk):
        run.emit(b.rec)
    if run.monitor is not None:
        # host-side only and AFTER the block record: the metrics trail is
        # the same with the observatory on
        hb = b.host
        run.monitor.observe_block(
            block=run.blocks_done, zs=hb.zs, accept=hb.accept,
            divergent=hb.divergent, energy=hb.energy, ngrad=hb.ngrad,
            max_rhat=b.max_rhat, min_ess=b.min_ess, n_stuck=b.n_stuck,
            draws_per_chain=b.draws_per_chain,
        )


def _checkpoint_block(run: _Run, b: _Block):
    """`block.checkpoint`: the kernel's arrays, the draws where no store
    holds them, the loop's counters."""
    if not run.checkpoint_path:
        return
    ckpt_span = telemetry.span("block.checkpoint", block=b.blk).open()
    from .checkpoint import save_checkpoint

    arrays = run.kernel.checkpoint_arrays(b.pend)
    if run.draw_store is None:
        # no draw store -> draws ride in the checkpoint; a store has them
        # already (avoids O(blocks^2) checkpoint I/O)
        arrays["draws"] = run.draws_hist.view()
    else:
        run.draw_store.flush()  # store on disk before state advances
    save_checkpoint(run.checkpoint_path, arrays, {
        "blocks_done": run.blocks_done,
        "block_size": run.block_size,
        "draw_rows": b.draws_per_chain,
        "num_divergent": run.total_div,
        "history": run.history,
        "model": run.model_name,
        "kernel": run.cfg.kernel,
    })
    ckpt_span.close(
        bytes_written=sum(int(np.asarray(a).nbytes) for a in arrays.values()))
    b.t_ckpt = ckpt_span.seconds
    if run.trace.enabled:
        run.trace.emit(
            "checkpoint", block=run.blocks_done, path=run.checkpoint_path,
            dur_s=round(b.t_ckpt, 4),
        )


def _trace_block(run: _Run, b: _Block):
    """One phase event (timing) + one health event (diagnostics) per block,
    emitted once the block's ENTIRE host cycle (diagnostics + persistence +
    checkpoint) is done. ``dur_s`` excludes the checkpoint time — the
    checkpoint phase has its own event and the per-run phase durations must
    still tile the wall without double counting.  Overlap, from the device
    timeline (`_DoneWaiter`): a block runs on the device from the later of
    its dispatch's end and the previous block's completion to its own
    completion; ``device_idle_s`` is the gap before this block's start
    (after the previous block's completion: 0 for a run's first block),
    ``t_host_hidden_s`` the part of this block's host cycle during which
    the next block ran on the device (0 in sync mode).  Both are bounded by
    the host-cycle totals, so the summarized idle fraction (idle over
    sample_block + checkpoint phase time) stays in [0, 1]."""
    pend, rec, t_wait = b.pend, b.rec, b.wait_span.seconds
    now = time.perf_counter_ns()
    host_cycle = (now - b.wait_span.end_ns) / 1e9
    idle = hidden = 0.0
    if run.done_ns is not None:  # the previous block's completion
        idle = max(0, min(pend.dispatched_ns, b.done_ns) - run.done_ns) / 1e9
    nxt = run.pending
    if nxt is not None:
        start = max(nxt.dispatched_ns, b.done_ns, b.wait_span.end_ns)
        end = min(now, nxt.t_done_ns or now)
        hidden = max(0, end - start) / 1e9
    forecast = {} if run.forecast_draws is None else {
        "ess_forecast": run.forecast_draws}
    run.trace.emit(
        "sample_block",
        block=run.blocks_done,
        # this block's own host timeline: enqueue (a first call's compile
        # lands there) + wait + host diagnostics, less the checkpoint
        dur_s=round(pend.t_enq + t_wait + host_cycle - b.t_ckpt, 4),
        t_dispatch_s=rec["t_dispatch_s"],
        t_diag_s=rec["t_diag_s"],
        t_wait_s=round(t_wait, 4),
        t_host_hidden_s=round(hidden, 4),
        device_idle_s=round(idle, 4),
        pipelined=not run.sync_blocks,
        draws_per_chain=b.draws_per_chain,
        block_len=pend.length,
        block_grad_evals=b.host.grad_evals,
        **({"fused": run.fused_tag} if run.fused_tag else {}),
        # the ESS row and the draw counts with streaming diagnostics (d
        # floats, whatever the draws and lags), O(draws*k) without
        stream_diag=run.stream_diag,
        diag_bytes_to_host=b.diag_bytes,
        **b.host.sched_fields,
        **forecast,
    )
    run.trace.emit(
        "chain_health",
        block=run.blocks_done,
        max_rhat=rec["max_rhat"],
        min_ess=rec["min_ess"],
        num_stuck_components=b.n_stuck,
        num_divergent=run.total_div,
        mean_accept=rec["mean_accept"],
        step_size=round(float(np.mean(np.asarray(
            run.ap.collect(pend.carried["step_size"])))), 6),
        draws_per_chain=b.draws_per_chain,
    )


def _over_budget(run: _Run) -> bool:
    """Whether the run is past its ``time_budget_s``.  The stop must be agreed
    ACROSS RANKS on a multi-process mesh: convergence decisions derive from
    identical collected draws, but wall clocks skew per host — an unilateral
    break would leave the other ranks hanging on the next block's unmatched
    collectives.  Rule: stop when ANY rank is over budget (one tiny allgather
    per block, only when a budget is actually set)."""
    if run.time_budget_s is None:
        return False
    over = run.wall_s() > run.time_budget_s
    if jax.process_count() > 1:
        from .parallel.primitives import gather_tree

        over = bool(np.any(
            gather_tree(np.array([over], np.bool_), tiled=False)))
    return over


def _process_block(run: _Run, pend) -> bool:
    """Host side of ONE finished block, along its spans: wait, gate, record,
    checkpoint.  Returns True when the run stops (converged or over budget); an
    in-flight speculative block is then discarded by the caller."""
    # failpoint: crash/preempt/sleep/stall before the host consumes a
    # completed block (@skip counts hits: ``stall(600)*1@1`` stalls once, at
    # block 2 of the first attempt).  A crash discards a block k+1 in flight
    # and the supervisor replays from block k-1's checkpoint.
    faults.fail_point("runner.block.pre")
    b = _Block(pend, run.blocks_done + 1)
    # waits only until the DEVICE finishes block k: k+1 may be running
    with telemetry.span("block.wait", block=b.blk) as b.wait_span:
        telemetry.wait(pend.outs)
        b.ready_ns = time.perf_counter_ns()
        b.host = run.kernel.host_block(pend, energy=run.monitor is not None)
    _gate_block(run, b)
    # the block's completion, ESS row included, once the gate has the row:
    # on `block.wait`, whose own wait leaves the row to the gate
    b.done_ns = _device_done_ns(pend, b.ready_ns)
    b.wait_span.note(device_done_ns=b.done_ns)
    _record_block(run, b)
    _checkpoint_block(run, b)
    if run.trace.enabled:
        _trace_block(run, b)
    run.done_ns = b.done_ns
    # failpoint: crash/preempt after the block is fully accounted (metrics +
    # checkpoint durable), the next block in flight: the orphaned-block drill
    faults.fail_point("runner.block.post")

    if run.converged:
        return True
    if _over_budget(run):
        # stop AFTER the block is emitted and checkpointed, so the
        # returned (and persisted) result accounts for every draw
        run.budget_exhausted = True
        with telemetry.span("block.record", block=b.blk,
                            event="budget_exhausted") as rec_span:
            # the window's seconds since the device finished the last block
            # it counts: the host cycle that ends here
            rec_span.note(tail_s=(rec_span.start_ns - b.done_ns) / 1e9)
            run.emit({
                "event": "budget_exhausted",
                "time_budget_s": float(run.time_budget_s),
                "wall_s": run.wall_s(),
            })
        if run.trace.enabled:
            run.trace.emit(
                "budget", time_budget_s=float(run.time_budget_s),
                blocks=run.blocks_done,
            )
        return True
    return False


def _within_budget(run: _Run, draws: int, blocks: int) -> bool:
    # the fixed march counts BLOCKS (bit-exact legacy loop); the
    # adaptive scheduler budgets DRAWS — same total either way
    if run.adaptive_blocks:
        return draws < run.max_draws
    return blocks < run.max_blocks


def _block_loop(run: _Run):
    """Draw blocks until converged, out of budget or out of blocks: a
    software pipeline unless ``sync_blocks`` (module docstring)."""
    while _within_budget(run, run.draws_hist.rows, run.blocks_done):
        if run.pending is None:
            run.pending = _dispatch_next(run)
            if run.pending is None:
                break
        current, run.pending = run.pending, None
        if not run.sync_blocks and _within_budget(
            run, run.draws_dispatched, run.blocks_dispatched
        ):
            # the overlap: block k+1 starts on the device while the
            # host processes block k below
            run.pending = _dispatch_next(run)
        if _process_block(run, current):
            # the block in flight is discarded: the serial path never ran
            # it, and no persisted artifact shows its draws or key split
            break


# ---------------------------------------------------------------------------
# collect: drain, lay out, constrain, the result
# ---------------------------------------------------------------------------


def _collect(run: _Run, t_run0: float) -> AdaptiveResult:
    fm, trace = run.ap.fm, run.trace
    with trace.phase("collect"):
        with telemetry.span("collect.layout") as sp:
            # one final contiguous copy out of the history buffer (the buffer
            # over-allocates by up to 2x; the result should not pin that)
            all_draws = np.ascontiguousarray(run.draws_hist.view())
            sp.note(draws=run.draws_hist.rows * run.chains,
                    bytes=all_draws.nbytes)
        if run.pending is not None:
            # the block dispatched ahead of the stop is dropped, but the
            # device runs it to its end before anything queued behind it:
            # wait here, so that `collect.constrain` is the layout alone
            with telemetry.span("collect.drain", block=run.blocks_dispatched):
                telemetry.wait(run.pending.outs)
        with telemetry.span("collect.constrain", bytes=all_draws.nbytes):
            draws = _constrain_draws(fm, all_draws)
    result = AdaptiveResult(
        draws,
        {"num_divergent": np.asarray(run.total_div)},
        flat_model=fm,
        draws_flat=all_draws,
        history=run.history,
        converged=run.converged,
        wall_s=run.wall_s(),
    )
    result.budget_exhausted = run.budget_exhausted
    # statistical-health verdict: every warning the observatory raised
    # (None when STARK_HEALTH=0 — null, never an empty claim of health)
    result.health_warnings = (
        run.monitor.finalize(converged=run.converged)
        if run.monitor is not None else None
    )
    # overshoot: estimated draws spent beyond what the ESS target needed at
    # the measured rate, which the adaptive scheduler drives toward one
    # small block
    overshoot = None
    final_pts = [p for p in run.points if p[1] is not None]
    if run.converged and run.rate and final_pts:
        overshoot = int(
            max(0.0, (final_pts[-1][1] - run.ess_target) / run.rate))
    result.overshoot_draws = overshoot
    if trace.enabled:
        trace.emit(
            "run_end",
            dur_s=round(time.perf_counter() - t_run0, 4),
            converged=run.converged,
            blocks=run.blocks_done,
            num_divergent=run.total_div,
            budget_exhausted=run.budget_exhausted,
            stream_diag=run.stream_diag,
            adaptive_blocks=run.adaptive_blocks,
            **({"overshoot_draws": overshoot} if overshoot is not None
               else {}),
        )
    return result
