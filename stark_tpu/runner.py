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
`tools/trace_report.py` and bench.py surface as a device-idle fraction.

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

import json
import os
import time
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import diagnostics, faults, health as _health, lineage, telemetry
from . import profile as _profile
from .kernels.base import HMCState
from .ops import quantize as _quantize
from .model import Model
from .sampler import Posterior, SamplerConfig, _constrain_draws


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


_ADAPT_KEYS = ("z", "log_eps", "log_T", "inv_mass")


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


def load_adapt_state(path, *, kernel, model_name, ndim, data_fp=None):
    """Load + validate an adaptation-import artifact (``adapt_path``).

    Returns ``(arrays, None)`` on success, ``(None, reason)`` on any
    missing/corrupt/mismatched file — the ONE validation used both by
    the runner's import and by callers deciding whether to skip MAP
    descent (a skip decided on mere file existence would combine
    "no MAP" with "no import" when the load is later rejected).
    ``reason`` is None only when the file simply does not exist.
    """
    if not path or not os.path.exists(path):
        return None, None
    from .checkpoint import load_checkpoint

    try:
        arrays, meta = load_checkpoint(path)
        missing = [k for k in _ADAPT_KEYS if k not in arrays]
        if missing:
            return None, f"missing arrays: {missing}"
        if (
            meta.get("kernel") != kernel
            or meta.get("model") != model_name
            or int(arrays["inv_mass"].shape[-1]) != ndim
        ):
            return None, (
                f"mismatch: kernel={meta.get('kernel')} "
                f"model={meta.get('model')} "
                f"ndim={arrays['inv_mass'].shape[-1]} "
                f"(want {kernel}/{model_name}/{ndim})"
            )
        if data_fp is not None and meta.get("data_fp") != data_fp:
            # an artifact tuned on a DIFFERENT dataset (or one predating
            # fingerprints) must not seed this run's positions/mass
            return None, (
                f"mismatch: data_fp={meta.get('data_fp')} (want {data_fp}; "
                "artifact was adapted on a different dataset)"
            )
        return arrays, None
    except Exception as e:  # noqa: BLE001 — corrupt import file
        return None, repr(e)


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


def _with_potential(arrays: Dict[str, Any]) -> Dict[str, Any]:
    """Checkpoint arrays whose ``pe`` is the potential itself.  An ensemble
    that carries its energies relative to a centre
    (`chees.CheesRunCarry.pe_center`, collected beside them) has the two
    added in float64, which holds both to the last bit of the float32 that
    was carried; ``pe_center`` stays in the file for the resume."""
    center = arrays.pop("pe_center", None)
    if center is not None:
        arrays["pe"] = np.asarray(arrays["pe"], np.float64) + np.float64(
            center
        )
        arrays["pe_center"] = center
    return arrays


def _carried_potential(arrays, centred: bool):
    """-> (pe, pe_center) as an ensemble's carry holds them, from a
    checkpoint's arrays: `_with_potential` undone.  ``centred``: whether
    the programs that resume carry a centre; a file without one (written
    off the mesh) then resumes relative to 0."""
    if not centred:
        return arrays["pe"], None
    center = np.float32(arrays.get("pe_center", 0.0))
    pe = np.asarray(arrays["pe"], np.float64) - np.float64(center)
    return pe.astype(np.float32), center


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
        if lineage.enabled():
            # single-run lineage parity: one ambient job for the whole
            # run (the supervisor's outer job wins, so every restart
            # attempt correlates to ONE id; otherwise mint
            # deterministically from the model/seed — a resumed run
            # re-mints the same id)
            jid = lineage.current_job() or lineage.mint_job_id(
                getattr(model, "tag", type(model).__name__),
                int(kwargs.get("seed", 0)),
            )
            with lineage.use_job(jid):
                return _sample_until_converged(
                    model, data, trace=trace, **kwargs
                )
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
    instead of the full-history FFT pass over the worst-k components —
    the convergence gate's host transfer stops scaling with the draw
    count (the ``diag_bytes_to_host`` trace field documents it).  The
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
    fm, data, extra = ap.fm, ap.data, ap.extra

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

    is_chees = cfg.kernel == "chees"
    ragged = False  # resolved on the per-chain branch below
    if is_chees:
        # ensemble kernel: blocks advance the whole ensemble through
        # chees sample segments (frozen adaptation), checkpointed as a
        # CheesRunCarry — same block/checkpoint/metrics protocol as the
        # per-chain kernels below
        from .chees import chees_init_positions
        from .kernels.chees import halton

        parts = ap.chees
        chees_init_j, chees_warm_j, chees_samp_j = (
            ap.init_j, ap.warm_j, ap.samp_j,
        )
        if stream_diag and ap.samp_diag is None:
            stream_diag = False  # backend without the streaming segment
        # donation of the diag carry is safe only when a block's
        # accumulators are read back BEFORE the next block is dispatched
        # — i.e. the serial loop; the pipeline reads block k's diag while
        # block k+1 (which consumed it) is already in flight
        chees_samp_diag_j = (
            ap.samp_diag(donate=sync_blocks) if stream_diag else None
        )

        def save_warmup_checkpoint(path, carry, key, key_warm, done, nd, nl):
            """Warmup-phase checkpoint: the full CheesWarmCarry, so a
            fault mid-warmup resumes at the last finished segment instead
            of burning the whole (dominant) warmup budget again."""
            ckpt_span = telemetry.span("block.checkpoint", stage="warmup").open()
            from .checkpoint import save_checkpoint

            # ap.collect (gather_draws on a mesh) materializes the
            # chain-sharded leaves on every host — np.asarray alone
            # cannot read non-addressable shards on multi-process meshes
            arrays = ap.collect({
                # standard names so checkpoint_is_healthy's finite check
                # covers position/grad/step/mass exactly like sample-phase
                "z": carry.states.z,
                "pe": carry.states.potential_energy,
                "grad": carry.states.grad,
                "inv_mass": carry.inv_mass,
                "da_log_step": carry.da.log_step,
                "da_log_avg_step": carry.da.log_avg_step,
                "da_h_avg": carry.da.h_avg,
                "da_mu": carry.da.mu,
                "da_count": carry.da.count,
                "adam_m": carry.adam.m,
                "adam_v": carry.adam.v,
                "adam_t": carry.adam.t,
                "log_T": carry.log_T,
                "wf_count": carry.wf.count,
                "wf_mean": carry.wf.mean,
                "wf_m2": carry.wf.m2,
                "pe_center": carry.pe_center,
            })
            arrays = _with_potential(arrays)
            arrays["step_size"] = np.exp(arrays["da_log_step"])
            # PRNG keys are host-side driver state, never mesh-sharded
            arrays["key"] = np.asarray(key)
            arrays["key_warm"] = np.asarray(key_warm)
            if health_check:
                # a poisoned adaptation carry must never land on disk
                # (the load-side check in supervise covers old files)
                from .supervise import check_finite_state

                check_finite_state(arrays)
            save_checkpoint(
                path,
                arrays,
                {
                    "kernel": cfg.kernel,
                    "phase": "warmup",
                    "warm_done": done,
                    "warm_div": nd,
                    "warm_leap": nl,
                    "model": type(model).__name__,
                },
            )
            ckpt_span.close()
            if trace.enabled:
                trace.emit(
                    "checkpoint",
                    stage="warmup",
                    warm_done=done,
                    path=path,
                    dur_s=round(ckpt_span.seconds, 4),
                )

        def run_chees_touchup(carry, key_warm):
            """Short re-equilibration warmup for an imported adaptation
            state (``adapt_path``): ONLY the step size re-tunes (DA,
            anchored at the imported value).  Mass windows are OFF (zero
            flags) and the trajectory-length Adam is OFF (indices below
            its t_start gate): both estimates come from a full previous
            warmup, and a short window would only degrade them —
            measured: a fresh Adam re-adapting the imported log_T walked
            trajectories from ~100 to ~288 leapfrogs in 80 touch-up
            transitions (N=20k fallback replica), tripling every later
            block's cost."""
            sched = parts.schedule
            n = max(20, int(cfg.num_warmup * adapt_touchup_frac))
            u = jnp.asarray(2.0 * halton(n), jnp.float32)
            wkeys = jax.random.split(key_warm, n)
            aoff = jnp.zeros((n,), np.asarray(sched.adapt_mass).dtype)
            woff = jnp.zeros((n,), np.asarray(sched.window_end).dtype)
            idxs = jnp.full((n,), -1, jnp.int32)  # < t_start: log_T frozen
            n_div, n_leap = 0, 0
            for s in range(0, n, block_size):
                e = min(s + block_size, n)
                with trace.phase(
                    "warmup_block", start=s, end=e, stage="touchup"
                ) as ph:
                    carry, (nd, nl) = jax.block_until_ready(
                        chees_warm_j(
                            carry, wkeys[s:e], u[s:e], idxs[s:e],
                            aoff[s:e], woff[s:e], *extra,
                        )
                    )
                    if trace.enabled:
                        ph.note(num_divergent=int(nd), leapfrogs=int(nl))
                telemetry.notify_progress()  # watchdog liveness beat
                n_div += int(nd)
                n_leap += int(nl)
            return carry, n_div, n_leap

        def load_adapt_import():
            """Validated adaptation import, or None (missing/mismatched
            file — a mismatch is logged, never fatal: the run falls back
            to a full warmup)."""
            arrays, reason = load_adapt_state(
                adapt_path, kernel="chees",
                model_name=type(model).__name__, ndim=fm.ndim,
                data_fp=adapt_fp,
            )
            if arrays is None:
                if reason is not None:
                    emit({"event": "adapt_import_rejected", "reason": reason})
                return None
            z = np.asarray(arrays["z"])
            if z.shape[0] >= chains:
                z = z[:chains]
            else:
                # more chains than saved: tile the typical-set points
                reps = -(-chains // z.shape[0])
                z = np.tile(z, (reps, 1))[:chains]
            # overdispersed warm starts: the saved z are one posterior
            # point per chain; jitter by half the cross-chain spread so
            # imported starts stay overdispersed relative to the target
            # (and tiled duplicates separate — zero cross-chain variance
            # would zero the ChEES criterion) instead of replaying the
            # exporting run's exact typical-set points.  Zero-spread dims
            # fall back to a 0.05 absolute scale.
            sd = z.std(axis=0)
            sd = np.where(sd > 0, sd, 0.05).astype(z.dtype)
            z = z + 0.5 * sd * np.random.default_rng(
                seed
            ).standard_normal(z.shape).astype(z.dtype)
            return {
                "z": z,
                "log_eps": np.asarray(arrays["log_eps"]),
                "log_T": np.asarray(arrays["log_T"]),
                "inv_mass": np.asarray(arrays["inv_mass"]),
            }

        def save_adapt(run_carry):
            """Persist the tuned adaptation + end-of-warmup positions for
            reuse by later runs (atomic, same npz machinery as
            checkpoints).  A poisoned state is never exported — a NaN
            import artifact would sabotage every later run."""
            from .checkpoint import save_checkpoint

            leaves = [
                np.asarray(ap.collect(run_carry.states.z)),
                np.asarray(run_carry.log_eps),
                np.asarray(run_carry.log_T),
                np.asarray(run_carry.inv_mass),
            ]
            if not all(np.all(np.isfinite(a)) for a in leaves):
                emit({"event": "adapt_export_skipped",
                      "reason": "non-finite warmup state"})
                return
            save_checkpoint(
                adapt_export_path,
                {
                    "z": leaves[0],
                    "log_eps": leaves[1],
                    "log_T": leaves[2],
                    "inv_mass": leaves[3],
                },
                {
                    "kernel": cfg.kernel,
                    "model": type(model).__name__,
                    "num_warmup": cfg.num_warmup,
                    "data_fp": adapt_fp,
                },
            )

        def run_chees_warmup(carry, start, key, key_warm, nd0, nl0):
            """Drive warmup segments from ``start``; checkpoint each."""
            sched = parts.schedule
            aflags = jnp.asarray(np.asarray(sched.adapt_mass))
            wflags = jnp.asarray(np.asarray(sched.window_end))
            u_warm = jnp.asarray(2.0 * halton(cfg.num_warmup), jnp.float32)
            wkeys = jax.random.split(key_warm, max(cfg.num_warmup, 1))
            idxs = jnp.arange(cfg.num_warmup)
            n_div, n_leap = nd0, nl0
            for s in range(start, cfg.num_warmup, block_size):
                e = min(s + block_size, cfg.num_warmup)
                with trace.phase("warmup_block", start=s, end=e) as ph:
                    carry, (nd, nl) = jax.block_until_ready(
                        chees_warm_j(
                            carry, wkeys[s:e], u_warm[s:e], idxs[s:e],
                            aflags[s:e], wflags[s:e], *extra,
                        )
                    )
                    if trace.enabled:
                        ph.note(num_divergent=int(nd), leapfrogs=int(nl))
                telemetry.notify_progress()  # watchdog liveness beat
                n_div += int(nd)
                n_leap += int(nl)
                if checkpoint_path and e < cfg.num_warmup:
                    # the final segment's state is captured by the first
                    # sample-phase checkpoint; persisting it here too
                    # would only duplicate I/O
                    save_warmup_checkpoint(
                        checkpoint_path, carry, key, key_warm, e, n_div,
                        n_leap,
                    )
            return carry, n_div, n_leap
    else:
        if stream_diag:
            try:  # probe: older/third-party backends lack the diag carry
                ap.get_block(
                    block_size, diag_lags=diag_lags, donate_diag=sync_blocks
                )
            except TypeError:
                stream_diag = False
        # step-synchronized NUTS scheduling (STARK_RAGGED_NUTS): the block
        # runners gain one trailing lane-iteration output (occupancy
        # accounting).  Knob-gated per config, and probed like the diag
        # carry — a backend without the ragged path (sharded meshes,
        # whose data-sharded potentials carry collectives that must run
        # in lockstep) falls back to the legacy scan.
        from .kernels.nuts_ragged import ragged_nuts_enabled

        ragged = ragged_nuts_enabled(cfg)
        if ragged:
            try:
                ap.get_block(block_size, ragged=True)
            except TypeError:
                ragged = False

        def get_v_block(length):
            """Compiled block runner for ``length`` transitions — the
            streaming-diagnostics variant when the feature is on (the
            backend caches per (length, diag, donate, ragged))."""
            kw = {"ragged": True} if ragged else {}
            if stream_diag:
                return ap.get_block(
                    length, diag_lags=diag_lags, donate_diag=sync_blocks,
                    **kw,
                )
            return ap.get_block(length, **kw)

        # warmup runs as block_size-bounded dispatches too (same
        # device-program length as the draw blocks, so warmup is
        # checkpointable and beats the watchdog) — shared driver with
        # the segmented backend paths
        seg_warmup = ap.seg_warmup

    t_start = time.perf_counter()
    metrics_f = open(metrics_path, "a") if metrics_path else None

    def emit(rec):
        # every record is a progress beat (watchdog liveness) and is
        # flushed AND fsynced line-by-line: the metrics trail documents
        # crashes, so it must survive the crash it documents
        telemetry.notify_progress()
        if metrics_f:
            metrics_f.write(json.dumps(rec) + "\n")
            metrics_f.flush()
            os.fsync(metrics_f.fileno())
        if progress_cb is not None:
            try:
                progress_cb(rec)
            except Exception:  # noqa: BLE001 — observability must not kill
                # the run: e.g. a BrokenPipeError from a closed capture
                # pipe would otherwise surface as a sampler fault and burn
                # the supervisor's restart budget on healthy state
                pass
        if trace.enabled and rec.get("event", "").startswith("adapt_"):
            # adaptation decisions (import rejected / export skipped)
            # mirror into the trace as auxiliary events
            trace.emit(
                "adapt",
                kind=rec["event"],
                **{k: v for k, v in rec.items() if k != "event"},
            )

    def emit_warmup_done(n_div_total, step_size, warmup_grads=None,
                         resumed_from=None, adapt_imported=None):
        """One builder for the warmup_done record — fresh and
        warmup-resumed paths must emit identical shapes."""
        rec = {
            "event": "warmup_done",
            "wall_s": time.perf_counter() - t_start,
            "num_divergent": int(n_div_total),
            # per-chain kernels carry chain-sharded step sizes: collect
            # (allgather on a multi-process mesh) before reading
            "step_size": np.asarray(ap.collect(step_size)).tolist(),
        }
        if warmup_grads is not None:
            rec["warmup_grad_evals"] = int(warmup_grads)
        if resumed_from is not None:
            rec["resumed_from_step"] = int(resumed_from)
        if adapt_imported:
            rec["adapt_imported"] = True
        with telemetry.span("block.record", event="warmup_done"):
            emit(rec)
        if trace.enabled:
            trace.emit(
                "chain_health",
                status="warmup_done",
                num_divergent=rec["num_divergent"],
                step_size=round(float(np.mean(rec["step_size"])), 6),
            )

    blocks_done = 0
    total_div = 0
    budget_exhausted = False
    history = []
    draw_blocks = []
    # a resumed run's way to its first dispatch, as one span: checkpoint
    # load, state restore, the rebuild of the streaming statistics from the
    # stored draws (closed where the block loop starts)
    resume_span = None
    if resume_from:
        from .checkpoint import load_checkpoint

        resume_span = telemetry.span(
            "resume_load", bytes_read=os.path.getsize(resume_from)
        ).open()
        arrays, meta = load_checkpoint(resume_from)
        ckpt_kernel = meta.get("kernel")
        if ckpt_kernel is None and is_chees:
            # legacy checkpoints (pre-kernel field) were only ever written
            # by the per-chain kernels; they lack the chees carry arrays
            raise ValueError(
                "checkpoint has no kernel record (pre-chees format); "
                "cannot resume it with kernel='chees'"
            )
        if ckpt_kernel is not None and ckpt_kernel != cfg.kernel:
            raise ValueError(
                f"checkpoint was written by kernel={ckpt_kernel!r}, "
                f"resuming run uses kernel={cfg.kernel!r}"
            )
        # checkpoints are host numpy; re-place on the backend's layout
        # (chains-sharded state, replicated ensemble adaptation on a mesh;
        # identity/device_put on a single device)
        pc, pr = ap.put_chains, ap.put_rep
        pe, pe_center = _carried_potential(
            arrays,
            is_chees and ap.fm.centering is not None and ap.data is not None,
        )
        if pe_center is not None:
            pe_center = pr(jnp.asarray(pe_center))
        state = HMCState(
            z=pc(jnp.asarray(arrays["z"])),
            potential_energy=pc(jnp.asarray(pe)),
            grad=pc(jnp.asarray(arrays["grad"])),
        )
        # chees adaptation is ensemble-shared; per-chain kernels carry
        # per-chain step/mass
        put_sm = pr if is_chees else pc
        step_size = put_sm(jnp.asarray(arrays["step_size"]))
        inv_mass = put_sm(jnp.asarray(arrays["inv_mass"]))
        key = jnp.asarray(arrays["key"])
        if reseed is not None:
            # a deterministic numerical failure would otherwise replay
            # identically from the checkpointed key on every retry — the
            # supervisor passes the attempt number to branch the stream
            key = jax.random.fold_in(key, reseed)
        chains = state.z.shape[0]
        if is_chees and meta.get("phase") == "warmup":
            # mid-warmup checkpoint: rebuild the full adaptation carry and
            # finish the remaining warmup segments before sampling
            from .adaptation import DualAveragingState, WelfordState
            from .chees import AdamState, CheesWarmCarry

            rep = lambda name: pr(jnp.asarray(arrays[name]))  # noqa: E731
            carry = CheesWarmCarry(
                states=state,
                da=DualAveragingState(
                    log_step=rep("da_log_step"),
                    log_avg_step=rep("da_log_avg_step"),
                    h_avg=rep("da_h_avg"),
                    mu=rep("da_mu"),
                    count=rep("da_count"),
                ),
                adam=AdamState(
                    m=rep("adam_m"),
                    v=rep("adam_v"),
                    t=rep("adam_t"),
                ),
                log_T=rep("log_T"),
                wf=WelfordState(
                    count=rep("wf_count"),
                    mean=rep("wf_mean"),
                    m2=rep("wf_m2"),
                ),
                inv_mass=inv_mass,
                pe_center=pe_center,
            )
            key_warm = jnp.asarray(arrays["key_warm"])
            if reseed is not None:
                key_warm = jax.random.fold_in(key_warm, reseed)
            with telemetry.span(
                "warmup", steps=cfg.num_warmup - int(meta["warm_done"])
            ) as warm_span:
                carry, n_div, n_warm_leap = run_chees_warmup(
                    carry,
                    int(meta["warm_done"]),
                    key,
                    key_warm,
                    int(meta.get("warm_div", 0)),
                    int(meta.get("warm_leap", 0)),
                )
                warm_span.note(grad_evals=int(n_warm_leap) * chains)
            run_carry = parts.finalize(carry)
            state = run_carry.states
            step_size = jnp.exp(run_carry.log_eps)
            inv_mass = run_carry.inv_mass
            emit_warmup_done(
                n_div, step_size,
                warmup_grads=(n_warm_leap + cfg.map_init_steps) * chains,
                resumed_from=int(meta["warm_done"]),
            )
        elif is_chees:
            from .chees import CheesRunCarry

            run_carry = CheesRunCarry(
                states=state,
                log_eps=pr(jnp.asarray(arrays["log_eps"])),
                log_T=pr(jnp.asarray(arrays["log_T"])),
                inv_mass=inv_mass,
                pe_center=pe_center,
            )
        blocks_done = int(meta.get("blocks_done", 0))
        total_div = int(meta.get("num_divergent", 0))
        history = list(meta.get("history", []))
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
                blocks_done * int(meta.get("block_size", block_size)),
            )
            truncate_draws(draw_store_path, accounted)
            stored, _, _ = read_draws(draw_store_path, mmap=False)
            if stored.shape[0]:
                # (n, chains, d) on disk -> (chains, n, d) in memory
                draw_blocks = [np.ascontiguousarray(stored.transpose(1, 0, 2))]
    else:
        # a span, no phase event: the run's keys and the ensemble's start
        # positions (the per-chain path has its `chain_init` phase below)
        with telemetry.span("compile", stage="chain_init"):
            key = jax.random.PRNGKey(seed)
            key, key_init, key_warm = jax.random.split(key, 3)
            warm_import = None
            if is_chees:
                warm_import = load_adapt_import()
                if warm_import is not None:
                    # imported adaptation: start AT the saved typical-set
                    # positions; the short touch-up below replaces the
                    # full warmup (docstring: adapt_path)
                    z0 = ap.put_chains(jnp.asarray(warm_import["z"]))
                else:
                    z0 = ap.put_chains(
                        chees_init_positions(
                            fm, key_init, chains, init_params
                        )
                    )
        if is_chees:
            # init dispatch = first compile + MAP descent (map_init_steps)
            with trace.phase("compile", stage="init+map",
                             map_init_steps=cfg.map_init_steps):
                # the MAP descent itself; its compile counters say how
                # much of it was compilation
                with telemetry.span(
                    "map_init", steps=cfg.map_init_steps,
                    grad_evals=cfg.map_init_steps * chains,
                ):
                    carry = jax.block_until_ready(
                        chees_init_j(key_init, z0, *extra)
                    )
            warm_span = telemetry.span("warmup", steps=cfg.num_warmup).open()
            if warm_import is not None:
                from .adaptation import da_init

                pr = ap.put_rep
                ls = jnp.asarray(warm_import["log_eps"])
                # DA anchored AT the imported step (mu = log_eps, not
                # Stan's log(10*eps) exploration prior — that prior is
                # for cold starts and measurably pulled a tuned eps 2.7x
                # up during an 80-transition touch-up)
                carry = carry._replace(
                    da=jax.tree.map(pr, da_init(jnp.exp(ls), mu=ls)),
                    log_T=pr(jnp.asarray(warm_import["log_T"])),
                    inv_mass=pr(jnp.asarray(warm_import["inv_mass"])),
                )
                carry, n_div, n_warm_leap = run_chees_touchup(carry, key_warm)
            else:
                # warmup dispatches bounded by block_size, like the draw
                # blocks, each segment checkpointed for mid-warmup resume
                carry, n_div, n_warm_leap = run_chees_warmup(
                    carry, 0, key, key_warm, 0, 0
                )
            run_carry = parts.finalize(carry)
            state = run_carry.states
            step_size = jnp.exp(run_carry.log_eps)
            inv_mass = run_carry.inv_mass
            warm_span.close(grad_evals=int(n_warm_leap) * chains)
            if adapt_export_path and warm_import is None:
                # populate the reuse cache from a FULL warmup only.  A
                # successful import leaves the artifact byte-identical: a
                # judged capture must not dirty committed artifacts
                # (VERDICT r4 weak #2), and overwriting a full-warmup
                # state with the touch-up's slightly re-tuned eps would
                # trade provenance for noise.
                save_adapt(run_carry)
            elif adapt_export_path:
                emit({"event": "adapt_export_skipped", "reason": "imported"})
        else:
            # chain-position init is the first real dispatch of the
            # per-chain path (vmapped init_flat compiles here): a
            # compile-stage phase covers it so the span timeline
            # (profiling.spans_from_events) attributes it instead of
            # reporting pre-warmup slack
            with trace.phase("compile", stage="chain_init"):
                if init_params is not None:
                    z0 = jnp.broadcast_to(
                        fm.unconstrain(init_params), (chains, fm.ndim)
                    )
                else:
                    z0 = jax.vmap(fm.init_flat)(
                        jax.random.split(key_init, chains)
                    )
                z0 = ap.put_chains(z0)
                warm_keys = ap.put_chains(jax.random.split(key_warm, chains))
                jax.block_until_ready(z0)
            # the segmented warmup driver reads the ambient trace, which
            # the public wrapper pinned to THIS run's trace
            with telemetry.span("warmup", steps=cfg.num_warmup):
                state, step_size, inv_mass, n_div = seg_warmup(
                    warm_keys, z0, data, block_size
                )
                # per-chain counts are chain-sharded
                n_div = ap.collect(n_div)
        # chees: ensemble gradient evals spent before sampling — MAP
        # descent (one fused gradient per Adam step per chain) + warm
        # leapfrogs; per-chain kernels have no shared-budget equivalent
        emit_warmup_done(
            np.sum(np.asarray(n_div)),
            step_size,
            warmup_grads=(
                (n_warm_leap + cfg.map_init_steps) * chains
                if is_chees
                else None
            ),
            adapt_imported=(is_chees and warm_import is not None) or None,
        )

    # what stands between here and the first dispatch is `resume_load`'s on
    # a resumed run and this span's on a fresh one (no phase event): the
    # streaming statistics and the diagnostics carry are built and placed
    loop_span = resume_span or telemetry.span(
        "compile", stage="loop_init"
    ).open()
    suff = diagnostics.ChainSuffStats(chains, fm.ndim)
    # full draw history in ONE growing preallocated host buffer: each block
    # is written exactly once, the per-block worst-k ESS subset is a single
    # fancy index, and full-history passes (stop validation, no-store
    # checkpoints, final collection) read a zero-copy view — the old
    # per-block ``np.concatenate`` over the block list was O(blocks²)
    # copy traffic in the hot loop
    draws_hist = diagnostics.DrawHistory(chains, fm.ndim)
    for blk in draw_blocks:
        suff.update(blk)  # resume: rebuild streaming stats from stored draws
        draws_hist.append(blk)
    del draw_blocks
    next_full_check = 0  # earliest block allowed to run full validation
    # chees Halton stream position: advanced at DISPATCH time (the
    # pipeline enqueues ahead of the host-side suff.count), anchored at
    # the resumed draw count so every mode walks the same sequence
    halton_start = int(suff.count[0])

    diag = None
    if stream_diag:
        # device-resident streaming-diagnostics carry, (chains,)-batched.
        # A resume rebuilds it from the stored draws (host reference
        # implementation of the same accumulator), so the gate's summary
        # covers the WHOLE history, not just post-resume blocks.
        from .kernels.base import StreamDiagState

        host_diag = diagnostics.stream_diag_from_draws(
            draws_hist.view()
            if draws_hist.rows
            else np.zeros((chains, 0, fm.ndim), np.float32),
            diag_lags,
            chains=chains,
            ndim=fm.ndim,
            dtype=np.dtype(state.z.dtype),
        )
        diag = StreamDiagState(
            **{k: ap.put_chains(v) for k, v in host_diag.items()}
        )

    # adaptive block scheduler (STARK_ADAPTIVE_BLOCKS): the fixed march is
    # re-expressed as a DRAW budget so both modes draw the same total —
    # only the block boundaries differ.  ``sched["points"]`` is the
    # per-processed-block (draws, min_ess) trail the ESS-rate forecaster
    # reads; it is seeded from the resumed metrics history so a resumed
    # run reconstructs the SAME schedule decisions the original made.
    max_draws = max_blocks * block_size
    blk_quantum = max(1, block_size // 2)
    blk_cap = max(block_size, 4 * block_size)
    sched = {"points": [], "forecast_draws": None, "rate": None}
    for _r in history:
        _e = _r.get("min_ess")
        sched["points"].append(
            (int(_r.get("draws_per_chain", 0)),
             float(_e) if _e is not None else None)
        )
    draws_dispatched = halton_start

    def _rate_and_deficit(points):
        """(rate, deficit) from a (draws, min_ess) trail — window rate over
        the last two finite points when it is positive, else the
        cumulative rate; deficit is vs the LAST finite point."""
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

    def next_block_len():
        """Length of the next dispatch.  Fixed mode: always block_size
        (the historical loop).  Adaptive mode: geometric growth from
        block_size/2 capped at 4x (ramp ordinal = GLOBAL block ordinal,
        so a resumed run continues the ramp), shrunk to the ESS-forecast
        deficit (quantized to multiples of the base quantum so at most
        cap/quantum compiled block variants exist), and truncated to the
        remaining draw budget.

        REPLAY DETERMINISM: the forecast reads the stats trail only up to
        block ``m-2`` when sizing block ``m`` — exactly what the
        pipelined loop (which dispatches m before processing m-1) can
        know.  The serial loop deliberately ignores its one-block-fresher
        stats, and a resumed run re-reads the same window from the
        checkpointed history, so serial, pipelined, and crash-resumed
        runs all size every block identically — which is what keeps the
        supervised replay bit-identical (chaos: inflight_block_replay).
        """
        if not adaptive_blocks:
            return block_size
        remaining = max_draws - draws_dispatched
        if remaining <= 0:
            return 0
        m = blocks_dispatched  # 0-based ordinal of the next dispatch
        n = min(blk_cap, blk_quantum * (2 ** min(m, 8)))
        rate, deficit = _rate_and_deficit(sched["points"][: max(0, m - 1)])
        if rate and deficit is not None and deficit > 0:
            # 1.1 safety: the rate estimate is noisy, and undershooting
            # repeatedly costs a host round-trip per correction
            need = int(np.ceil(1.1 * deficit / rate))
            need = -(-max(need, 1) // blk_quantum) * blk_quantum
            n = min(n, max(need, blk_quantum))
        return min(n, remaining)

    def note_block_ess(min_ess, draws_now):
        """Record one processed block's ESS; refresh the REPORTING
        forecast (trace/metrics fields) from the full trail — the
        scheduler itself reads the delayed window above."""
        sched["points"].append(
            (int(draws_now),
             float(min_ess) if np.isfinite(min_ess) else None)
        )
        rate, deficit = _rate_and_deficit(sched["points"])
        sched["rate"] = rate
        sched["forecast_draws"] = (
            int(draws_now + max(0.0, deficit) / rate) if rate else None
        )

    # overlap accounting across blocks: host-side seconds of the previous
    # cycle (diagnostics + persistence + checkpoint) and the running
    # device-seconds-per-block estimate (exact whenever the host waited)
    pipe = {"t_host_prev": 0.0, "dev_est": None}

    draw_store = None
    converged = False
    try:
        if draw_store_path:
            from .drawstore import DrawStore

            draw_store = DrawStore(draw_store_path, chains, fm.ndim)

        def dispatch_block(key_block, key_snap, length):
            """ENQUEUE one draw block of ``length`` transitions on the
            device without waiting, and refresh the carried device state
            so the next dispatch chains off it.  Returns the
            pending-block record `process_block` materializes later: the
            ``state``/``step_size``/``inv_mass`` (and chees adaptation)
            refs inside it are what block k's health check gates and
            block k's checkpoint persists, and ``key`` is the host RNG
            key as of THIS split — stored in the checkpoint regardless of
            how far ahead the pipeline has already split for later
            blocks.  With streaming diagnostics on, the block also
            carries the StreamDiagState accumulators; ``pend["diag"]`` is
            the post-block summary the convergence gate collects."""
            nonlocal state, step_size, inv_mass, halton_start, diag
            if is_chees:
                nonlocal run_carry
                # Halton jitter continues the global sampling sequence
                # (draws already dispatched = halton_start), so a resumed,
                # blocked, or pipelined run walks the SAME stream
                us = jnp.asarray(
                    2.0 * halton(length, start=halton_start), jnp.float32
                )
                halton_start += length
                bkeys = jax.random.split(key_block, length)
                if stream_diag:
                    run_carry, diag, (zs, accept, divergent, n_leap) = (
                        chees_samp_diag_j(run_carry, diag, bkeys, us, *extra)
                    )
                else:
                    run_carry, (zs, accept, divergent, n_leap) = chees_samp_j(
                        run_carry, bkeys, us, *extra
                    )
                # failpoint: NaN-poison the carried state — injected where
                # a real numerical fault would surface (health_check=True
                # catches it before block k's checkpoint; with the check
                # off it lands on disk and exercises the quarantine path)
                st = faults.poison("runner.carried_nan", run_carry.states)
                state = st
                step_size = jnp.exp(run_carry.log_eps)
                inv_mass = run_carry.inv_mass
                return {
                    "key": key_snap,
                    "state": st,
                    "step_size": step_size,
                    "inv_mass": inv_mass,
                    "log_eps": run_carry.log_eps,
                    "log_T": run_carry.log_T,
                    "pe_center": run_carry.pe_center,
                    "diag": diag,
                    "len": length,
                    "outs": {"zs": zs, "accept": accept,
                             "divergent": divergent, "n_leap": n_leap},
                }
            block_keys = ap.put_chains(jax.random.split(key_block, chains))
            lane_iters = None
            if stream_diag:
                out = get_v_block(length)(
                    block_keys, state, diag, step_size, inv_mass, data
                )
                if ragged:
                    (new_state, diag, zs, accept, divergent, energy,
                     ngrad, lane_iters) = out
                else:
                    new_state, diag, zs, accept, divergent, energy, ngrad = out
            else:
                out = get_v_block(length)(
                    block_keys, state, step_size, inv_mass, data
                )
                if ragged:
                    (new_state, zs, accept, divergent, energy, ngrad,
                     lane_iters) = out
                else:
                    new_state, zs, accept, divergent, energy, ngrad = out
            # per-chain kernels CARRY the (possibly poisoned) state into
            # the next dispatch — same rebinding as the serial loop
            new_state = faults.poison("runner.carried_nan", new_state)
            state = new_state
            return {
                "key": key_snap,
                "state": new_state,
                "step_size": step_size,
                "inv_mass": inv_mass,
                "diag": diag,
                "len": length,
                "outs": {"zs": zs, "accept": accept,
                         "divergent": divergent, "ngrad": ngrad,
                         # per-block Hamiltonian series: kernels always
                         # computed it; the health observatory is its
                         # first host-side consumer (E-BFMI)
                         "energy": energy,
                         **({"lane_iters": lane_iters} if ragged else {})},
            }

        def process_block(pend, next_in_flight):
            """Host side of ONE finished block: materialize its outputs
            (blocks only until the DEVICE finishes block k — block k+1 may
            already be running), health-gate, update diagnostics, emit
            metrics/trace, checkpoint.  Returns True when the run stops
            (converged or over budget); an in-flight speculative block is
            then discarded by the caller."""
            nonlocal blocks_done, total_div, converged, next_full_check
            nonlocal budget_exhausted
            # failpoint: crash/preempt/sleep/stall before the host consumes
            # a completed block — @skip counts hits, so ``stall(600)*1@1``
            # stalls exactly once, at block 2 of the first attempt.  With
            # the pipeline on, block k+1 may already be in flight here; a
            # crash discards it and the supervisor replays from block
            # k-1's checkpoint.
            faults.fail_point("runner.block.pre")
            blk = blocks_done + 1
            wait_span = telemetry.span("block.wait", block=blk).open()
            outs = pend["outs"]
            if is_chees:
                # chain-sharded outputs cross to host via collect (an
                # allgather on multi-process meshes); n_leap is the SHARED
                # per-transition trajectory length (replicated), and the
                # ensemble total is chains x that (chees.py convention)
                zs_dm, accept, divergent = ap.collect(
                    (outs["zs"], outs["accept"], outs["divergent"])
                )
                # the device block is draw-major (block, chains, d): keep
                # it for the draw store and give host diagnostics a free
                # transposed VIEW — no transpose copies on this path
                zs_dm = np.asarray(zs_dm)
                zs = zs_dm.transpose(1, 0, 2)
                blk_grads = int(np.sum(np.asarray(outs["n_leap"]))) * chains
            else:
                zs, accept, divergent, ngrad = ap.collect(
                    (outs["zs"], outs["accept"], outs["divergent"],
                     outs["ngrad"])
                )
                zs, zs_dm = np.asarray(zs), None
                blk_grads = int(np.sum(np.asarray(ngrad)))
            # the per-block energy series crosses to host ONLY for the
            # health observatory (STARK_HEALTH=0 restores the historical
            # drop-on-device behavior); chees blocks carry no energies
            blk_energy = (
                np.asarray(ap.collect(outs["energy"]))
                if monitor is not None and "energy" in outs
                else None
            )
            # ragged-NUTS occupancy accounting: the batch executed
            # max(lane_iters) iterations x chains lane-gradients; the
            # useful fraction is what the step-synchronized scheduler
            # exists to raise (fields ride ONLY ragged runs, so the
            # knob-off metrics/trace trails stay byte-identical)
            sched_fields = {}
            if ragged and outs.get("lane_iters") is not None:
                from .kernels.nuts_ragged import lane_occupancy_fields

                sched_fields = lane_occupancy_fields(
                    ap.collect(outs["lane_iters"])
                )
            wait_span.close()
            t_wait = wait_span.seconds

            def host_since_wait():
                # the host cycle so far: everything since the device's
                # outputs arrived
                return (time.perf_counter_ns() - wait_span.end_ns) / 1e9

            # the host's work on the block up to its record: health gate,
            # draw persistence, streaming R-hat / ESS, stop validation
            gate_span = telemetry.span(
                "block.gate", block=blk, block_grad_evals=blk_grads,
                **_psum_counters(fm, chains, backend),
            ).open()
            if health_check:
                # poisoned state must never reach the checkpoint; the
                # supervisor (supervise.supervised_sample) restarts from
                # the last healthy one.  The refs in ``pend`` are block
                # k's carried state, so block k's health still gates
                # block k's checkpoint even with k+1 in flight.
                from .supervise import check_finite_state

                carried = ap.collect({
                    "z": pend["state"].z,
                    "pe": pend["state"].potential_energy,
                    "grad": pend["state"].grad,
                    "step_size": pend["step_size"],
                    "inv_mass": pend["inv_mass"],
                })
                if monitor is not None:
                    # the statistical trail records the stuck chain
                    # BEFORE the fault taxonomy fires (the finite check
                    # below raises into the supervisor)
                    monitor.observe_state(carried, block=blocks_done + 1)
                check_finite_state(carried)
            blocks_done += 1
            draws_hist.append(zs)
            if draw_store is not None:
                # async writer; doesn't stall the loop.  The chees block
                # is already draw-major — append it without the
                # transpose-back + ascontiguousarray copy
                if zs_dm is not None:
                    draw_store.append(zs_dm, draw_major=True)
                else:
                    draw_store.append(zs)
            total_div += int(np.sum(np.asarray(divergent)))

            suff.update(zs)
            srhat = suff.rhat()
            # NaN streaming R-hat = frozen component; surface it explicitly
            # (nanmax would report a healthy-looking max while never
            # converging) and hard-block the stop gate below
            n_stuck = int(np.count_nonzero(np.isnan(srhat)))
            finite_rhat = srhat[~np.isnan(srhat)]
            max_rhat = (
                float(np.max(finite_rhat)) if finite_rhat.size else float("inf")
            )
            if stream_diag:
                # streaming gate: the ONLY device->host traffic the
                # convergence signal needs is the O(chains*d*L)
                # accumulator summary — constant per block, independent
                # of the accumulated draw count (the draws themselves
                # still stream to the DrawStore/history for persistence
                # and the stop-time validation pass)
                diag_host = ap.collect(pend["diag"])
                diag_bytes = int(
                    sum(np.asarray(a).nbytes for a in diag_host)
                )
                ess_vals = diagnostics.ess_from_suffstats(*diag_host)
            else:
                # legacy gate: ESS only on the worst-mixing components (by
                # streaming R-hat); NaN R-hat counts as worst — it flags a
                # suspicious component.  One fancy index off the
                # preallocated history buffer — still O(draws * k) host
                # work and memory traffic per block
                k = min(diag_components, fm.ndim)
                worst = np.argsort(
                    np.where(np.isnan(srhat), -np.inf, -srhat)
                )[:k]
                subset = draws_hist.take(worst)
                diag_bytes = int(subset.nbytes)
                ess_vals = diagnostics.ess(subset)
            finite_ess = ess_vals[np.isfinite(ess_vals)]
            # NaN ESS values (stuck components) are excluded from the
            # reported minimum — num_stuck_components carries that signal;
            # the all-NaN edge gives NaN, which fails the stop gate below
            min_ess = (
                float(np.min(finite_ess)) if finite_ess.size else float("nan")
            )
            draws_per_chain = int(suff.count[0])
            note_block_ess(min_ess, draws_per_chain)
            rec = {
                "event": "block",
                "block": blocks_done,
                "draws_per_chain": draws_per_chain,
                # metrics must stay strict JSON: non-finite values -> null
                "max_rhat": max_rhat if np.isfinite(max_rhat) else None,
                "min_ess": min_ess if np.isfinite(min_ess) else None,
                "num_stuck_components": n_stuck,
                "num_divergent": total_div,
                "mean_accept": float(np.mean(np.asarray(accept))),
                # wall attribution (VERDICT r2 weak #6): device-attributed
                # time (enqueue + host wait for the device — near-zero wait
                # when the pipeline hides host work) vs host diagnostics;
                # grad_evals divides out to device cost per gradient
                "t_dispatch_s": round(pend["t_enq"] + t_wait, 3),
                # the length of the `block.gate` span, stamped where it
                # closes (below)
                "t_diag_s": None,
                # Normalized to GRADIENT EVALUATIONS on all paths: the
                # ChEES/HMC count is leapfrog steps (1 grad eval each),
                # the NUTS count is tree leaves (1 grad eval each).
                # grad_eval_basis names the counting basis so the paths
                # are never silently conflated (ADVICE r3).
                "block_grad_evals": blk_grads,
                "grad_eval_basis": (
                    "tree_leaves" if cfg.kernel == "nuts" else "leapfrog"
                ),
                # fused-path tag rides ONLY fused-model runs, so the
                # plain-model metrics trail stays byte-identical
                **({"fused": fused_tag} if fused_tag else {}),
                # ragged-NUTS scheduling fields ride ONLY knob-on runs
                **sched_fields,
                "wall_s": time.perf_counter() - t_start,
            }
            if stream_diag:
                # new fields ride ONLY the streaming mode, so the
                # flags-off metrics trail stays byte-identical to the
                # historical runner
                rec["diag_bytes_to_host"] = diag_bytes
                if sched["forecast_draws"] is not None:
                    rec["ess_forecast"] = sched["forecast_draws"]
            # failpoint: force the streaming gate optimistic (arm with the
            # ``nan`` data directive) — the candidate stop then reaches
            # the full validation pass early, which must reject it; the
            # tier-1 guard test drills exactly this never-stop-on-a-
            # rejected-validation invariant
            forced_opt = (
                faults.fail_point("runner.gate.optimistic") is not None
            )
            # min_blocks counts BLOCKS in both modes: under the adaptive
            # scheduler the early blocks are smaller, so the earliest
            # possible stop moves from min_blocks*block_size draws to
            # min_blocks small blocks — the full validation pass still
            # gates every stop on the complete history
            min_gate = blocks_done >= min_blocks
            gate_pass = (
                n_stuck == 0
                and max_rhat < rhat_target
                and min_ess > ess_target
            )
            if (
                min_gate
                and (gate_pass or forced_opt)
                and blocks_done >= next_full_check
            ):
                # candidate stop: validate with the full split-form pass
                # (zero-copy view of the history buffer)
                full_draws = draws_hist.view()
                full_rhat = float(np.max(diagnostics.split_rhat(full_draws)))
                full_ess = float(np.min(diagnostics.ess(full_draws)))
                rec["full_max_rhat"] = full_rhat
                rec["full_min_ess"] = full_ess
                # recorded for the metrics trail, not gated: the robust
                # rank form flags heavy-tail/scale disagreement the
                # classic gate can miss
                rec["full_max_rank_rhat"] = float(
                    np.max(diagnostics.rank_rhat(full_draws))
                )
                # the full pass is host diagnostics too (inside the gate
                # span) — re-stamp the wall so it covers the expensive
                # validation blocks
                rec["wall_s"] = time.perf_counter() - t_start
                if full_rhat < rhat_target and full_ess > ess_target:
                    converged = True
                else:
                    next_full_check = blocks_done + max(1, blocks_done // 4)
            gate_span.close(diag_bytes_to_host=diag_bytes)
            rec["t_diag_s"] = round(gate_span.seconds, 3)
            history.append(rec)
            with telemetry.span("block.record", block=blk):
                emit(rec)
            if monitor is not None:
                # per-block warning sweep — host-side only, AFTER the
                # block record so the metrics trail stays byte-identical
                # to the pre-observatory runner.  The chees block is
                # draw-major (block, chains): transpose to the monitor's
                # (chains, block) layout (``zs`` is already transposed)
                acc_cm = np.asarray(accept)
                div_cm = np.asarray(divergent)
                if is_chees:
                    acc_cm, div_cm = acc_cm.T, div_cm.T
                monitor.observe_block(
                    block=blocks_done,
                    zs=zs,
                    accept=acc_cm,
                    divergent=div_cm,
                    energy=blk_energy,
                    ngrad=(
                        np.asarray(ngrad) if not is_chees else None
                    ),
                    max_rhat=max_rhat,
                    min_ess=min_ess,
                    n_stuck=n_stuck,
                    draws_per_chain=draws_per_chain,
                )

            t_ckpt_dur = 0.0
            if checkpoint_path:
                ckpt_span = telemetry.span("block.checkpoint", block=blk).open()
                from .checkpoint import save_checkpoint

                arrays = ap.collect({
                    "z": pend["state"].z,
                    "pe": pend["state"].potential_energy,
                    "grad": pend["state"].grad,
                    "step_size": pend["step_size"],
                    "inv_mass": pend["inv_mass"],
                    "pe_center": pend.get("pe_center"),
                })
                arrays = _with_potential(arrays)
                # host driver state AS OF this block's dispatch: the
                # pipeline may have split further keys for in-flight
                # blocks, but a resume from THIS checkpoint must replay
                # block k+1 from the serial stream position
                arrays["key"] = np.asarray(pend["key"])
                if is_chees:
                    arrays["log_eps"] = np.asarray(pend["log_eps"])
                    arrays["log_T"] = np.asarray(pend["log_T"])
                if draw_store is None:
                    # no draw store -> draws ride in the checkpoint; with a
                    # store the draws are already persisted incrementally
                    # (avoids O(blocks^2) checkpoint I/O)
                    arrays["draws"] = draws_hist.view()
                else:
                    draw_store.flush()  # store on disk before state advances
                save_checkpoint(
                    checkpoint_path,
                    arrays,
                    {
                        "blocks_done": blocks_done,
                        "block_size": block_size,
                        "draw_rows": draws_per_chain,
                        "num_divergent": total_div,
                        "history": history,
                        "model": type(model).__name__,
                        "kernel": cfg.kernel,
                    },
                )
                ckpt_span.close()
                t_ckpt_dur = ckpt_span.seconds
                if trace.enabled:
                    trace.emit(
                        "checkpoint",
                        block=blocks_done,
                        path=checkpoint_path,
                        dur_s=round(t_ckpt_dur, 4),
                    )
            if trace.enabled:
                # one phase event (timing) + one health event (diagnostics)
                # per block, emitted once the block's ENTIRE host cycle
                # (diagnostics + persistence + checkpoint) is done.
                # ``dur_s`` excludes the checkpoint time — the checkpoint
                # phase has its own event and the per-run phase durations
                # must still tile the wall without double counting.
                # Overlap accounting: ``t_host_hidden_s`` is this block's
                # host-cycle time that ran while the next block computed
                # on device; ``device_idle_s`` is the device idle the host
                # caused before this block ran — exact in sync mode (the
                # whole previous host cycle), estimated in pipelined mode
                # from the latest device-seconds-per-block observation
                # (0 whenever the host had to wait, i.e. the device never
                # starved).  Both are bounded by the host-cycle totals, so
                # the summarized idle fraction (idle over sample_block +
                # checkpoint phase time) stays in [0, 1].
                host_cycle = host_since_wait()
                if sync_blocks:
                    hidden, idle = 0.0, pipe["t_host_prev"]
                else:
                    hidden = host_cycle if next_in_flight else 0.0
                    idle = (
                        0.0
                        if t_wait > 1e-4 or pipe["dev_est"] is None
                        else max(0.0, pipe["t_host_prev"] - pipe["dev_est"])
                    )
                trace.emit(
                    "sample_block",
                    block=blocks_done,
                    # dur covers this block's own host timeline: enqueue
                    # (jit tracing/compile on the first call lands there)
                    # + wait + host diagnostics — checkpoint excluded
                    # (own phase event), so per-run phases still tile the
                    # wall
                    dur_s=round(
                        pend["t_enq"] + t_wait + host_cycle - t_ckpt_dur,
                        4,
                    ),
                    t_dispatch_s=rec["t_dispatch_s"],
                    t_diag_s=rec["t_diag_s"],
                    t_wait_s=round(t_wait, 4),
                    t_host_hidden_s=round(hidden, 4),
                    device_idle_s=round(idle, 4),
                    pipelined=not sync_blocks,
                    draws_per_chain=draws_per_chain,
                    block_len=pend["len"],
                    block_grad_evals=blk_grads,
                    **({"fused": fused_tag} if fused_tag else {}),
                    # convergence-gate transfer accounting: constant
                    # O(chains*d*L) with streaming diagnostics, O(draws*k)
                    # under the legacy full-history gate — the contrast
                    # trace_report's diagnostics table renders
                    stream_diag=stream_diag,
                    diag_bytes_to_host=diag_bytes,
                    **sched_fields,
                    **(
                        {"ess_forecast": sched["forecast_draws"]}
                        if sched["forecast_draws"] is not None
                        else {}
                    ),
                )
                trace.emit(
                    "chain_health",
                    block=blocks_done,
                    max_rhat=rec["max_rhat"],
                    min_ess=rec["min_ess"],
                    num_stuck_components=n_stuck,
                    num_divergent=total_div,
                    mean_accept=rec["mean_accept"],
                    step_size=round(
                        float(
                            np.mean(np.asarray(ap.collect(pend["step_size"])))
                        ),
                        6,
                    ),
                    draws_per_chain=draws_per_chain,
                )
            # failpoint: crash/preempt after the block is fully accounted
            # (metrics + checkpoint durable) — with the pipeline on, the
            # next block is in flight HERE, so this site drills the
            # orphaned-in-flight-block recovery story
            faults.fail_point("runner.block.post")

            # overlap bookkeeping: device-seconds estimate is exact when
            # the host waited (device busy for the whole previous host
            # cycle plus the wait); host cycle time feeds the next
            # block's idle attribution
            if t_wait > 1e-4 or pipe["dev_est"] is None:
                pipe["dev_est"] = (
                    t_wait if sync_blocks else pipe["t_host_prev"] + t_wait
                )
            pipe["t_host_prev"] = host_since_wait()

            if converged:
                return True
            # budget stop must be agreed ACROSS RANKS on a multi-process
            # mesh: convergence decisions derive from identical collected
            # draws, but wall clocks skew per host — an unilateral break
            # would leave the other ranks hanging on the next block's
            # unmatched collectives.  Rule: stop when ANY rank is over
            # budget (one tiny allgather per block, only when a budget is
            # actually set).
            over_budget = (
                time_budget_s is not None
                and time.perf_counter() - t_start > time_budget_s
            )
            if time_budget_s is not None and jax.process_count() > 1:
                from .parallel.primitives import gather_tree

                over_budget = bool(
                    np.any(
                        gather_tree(
                            np.array([over_budget], np.bool_), tiled=False
                        )
                    )
                )
            if over_budget:
                # stop AFTER the block is emitted and checkpointed, so the
                # returned (and persisted) result accounts for every draw
                budget_exhausted = True
                with telemetry.span(
                    "block.record", block=blk, event="budget_exhausted"
                ):
                    emit(
                        {
                            "event": "budget_exhausted",
                            "time_budget_s": float(time_budget_s),
                            "wall_s": time.perf_counter() - t_start,
                        }
                    )
                if trace.enabled:
                    trace.emit(
                        "budget", time_budget_s=float(time_budget_s),
                        blocks=blocks_done,
                    )
                return True
            return False

        pending = None
        blocks_dispatched = blocks_done
        profile_next = bool(profile_dir) and blocks_done == 0
        loop_span.close(draws_rebuilt=draws_hist.rows * chains)

        def dispatch_next():
            """Split the next block's key on the HOST (identical stream in
            serial and pipelined order), size the block (fixed or
            ESS-forecast adaptive), and enqueue it."""
            nonlocal key, blocks_dispatched, profile_next, draws_dispatched
            length = next_block_len()
            if length <= 0:
                return None
            enq_span = telemetry.span(
                "block.dispatch", block=blocks_dispatched + 1, length=length
            ).open()
            key, key_block = jax.random.split(key)
            if profile_next:
                # the profiler wants one block's device timeline by
                # itself: run the first block synchronously under the
                # trace, then pipeline from the next block on
                profile_next = False
                with jax.profiler.trace(profile_dir):
                    pend = dispatch_block(key_block, key, length)
                    jax.block_until_ready(pend["outs"])
            else:
                pend = dispatch_block(key_block, key, length)
            enq_span.close()
            pend["t_enq"] = enq_span.seconds
            blocks_dispatched += 1
            draws_dispatched += length
            return pend

        def can_dispatch():
            # the fixed march counts BLOCKS (bit-exact legacy loop); the
            # adaptive scheduler budgets DRAWS — same total either way
            if adaptive_blocks:
                return draws_dispatched < max_draws
            return blocks_dispatched < max_blocks

        def keep_running():
            if adaptive_blocks:
                return draws_hist.rows < max_draws
            return blocks_done < max_blocks

        while keep_running():
            if pending is None:
                pending = dispatch_next()
                if pending is None:
                    break
            current, pending = pending, None
            if not sync_blocks and can_dispatch():
                # the overlap: block k+1 starts on the device while the
                # host processes block k below
                pending = dispatch_next()
            if process_block(current, next_in_flight=pending is not None):
                # converged or budget stop: a speculative in-flight block
                # is simply discarded — the serial path never ran it, and
                # neither its draws nor its key split are observable in
                # any persisted artifact
                break
    finally:
        if metrics_f:
            metrics_f.close()
        if draw_store is not None:
            draw_store.close()

    with trace.phase("collect"):
        with telemetry.span("collect.layout") as sp:
            # one final contiguous copy out of the history buffer (the
            # buffer over-allocates by up to 2x; the result should not pin
            # that)
            all_draws = np.ascontiguousarray(draws_hist.view())
            sp.note(draws=draws_hist.rows * chains, bytes=all_draws.nbytes)
        if pending is not None:
            # the block dispatched ahead of the stop is dropped, but the
            # device runs it to its end before anything queued behind it:
            # wait here, so that `collect.constrain` is the layout alone
            with telemetry.span("collect.drain", block=blocks_dispatched):
                jax.block_until_ready(pending["outs"])
        with telemetry.span("collect.constrain", bytes=all_draws.nbytes):
            draws = _constrain_draws(fm, all_draws)
    stats = {"num_divergent": np.asarray(total_div)}
    result = AdaptiveResult(
        draws,
        stats,
        flat_model=fm,
        draws_flat=all_draws,
        history=history,
        converged=converged,
        wall_s=time.perf_counter() - t_start,
    )
    result.budget_exhausted = budget_exhausted
    # statistical-health verdict: every warning the observatory raised
    # (None when STARK_HEALTH=0 — null, never an empty claim of health)
    result.health_warnings = (
        monitor.finalize(converged=converged) if monitor is not None
        else None
    )
    # overshoot accounting: estimated draws spent beyond what the ESS
    # target needed (at the measured rate) — the number the adaptive
    # scheduler exists to drive toward ~one small block; surfaced in the
    # trace so BENCH artifacts can show the win
    overshoot = None
    final_pts = [p for p in sched["points"] if p[1] is not None]
    if converged and sched["rate"] and final_pts:
        overshoot = int(
            max(0.0, (final_pts[-1][1] - ess_target) / sched["rate"])
        )
    result.overshoot_draws = overshoot
    if trace.enabled:
        trace.emit(
            "run_end",
            dur_s=round(time.perf_counter() - t_run0, 4),
            converged=converged,
            blocks=blocks_done,
            num_divergent=total_div,
            budget_exhausted=budget_exhausted,
            stream_diag=stream_diag,
            adaptive_blocks=adaptive_blocks,
            **({"overshoot_draws": overshoot} if overshoot is not None
               else {}),
        )
    return result
