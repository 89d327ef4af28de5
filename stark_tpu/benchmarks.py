"""The five judged benchmark configs (BASELINE.json:6-12) as runnable entries.

Each ``bench_*`` function builds the workload at an adjustable scale, runs it
twice with the same backend instance (first run pays XLA compile; the timed
second run hits the runner cache), and reports ESS and wall-clock — the
primary metric being effective samples/sec/chip (BASELINE.json:2).

Scales default to smoke-test sizes; ``bench.py`` at the repo root runs the
flagship at full benchmark size on the real chip.

Telemetry: under an ambient `telemetry` trace (the CLI's ``--trace PATH``),
each benchmark's TIMED run emits the full event stream (run envelope, phase
timings, chain health) — the compile pass is suppressed by ``_timed`` so
the trace holds exactly one run per benchmark.
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

import stark_tpu
from . import diagnostics
from .backends import JaxBackend
from .models import (
    BayesianMLP,
    EightSchools,
    GaussianMixture,
    HierLogistic,
    LinearMixedModel,
    eight_schools_data,
    synth_bnn_data,
    synth_gmm_data,
    synth_lmm_data,
    synth_logistic_data,
)
from .parallel import consensus_sample, tempered_sample
from .sghmc import sghmc_sample


@dataclasses.dataclass
class BenchResult:
    name: str
    wall_s: float
    min_ess: float
    ess_per_sec: float
    max_rhat: float
    extra: Dict[str, Any] = dataclasses.field(default_factory=dict)
    #: what ess_per_sec measures — benchmarks whose defensible metric is
    #: not weight-space ESS (the BNN diagnoses in predictive space) name
    #: it here so the judged table's headline column says so itself
    metric_name: str = "ESS/s"
    #: pass/fail judgment + its basis.  None -> the default R-hat<1.01
    #: gate; a benchmark whose R-hat is structurally uninformative (BNN
    #: mode structure) supplies its own measured gate instead, and
    #: max_rhat stays in the table as a diagnostic column
    converged: Optional[bool] = None
    gate: str = "R-hat<1.01"

    def passed(self) -> bool:
        return (
            self.converged
            if self.converged is not None
            else bool(self.max_rhat < 1.01)
        )

    def row(self) -> str:
        return (
            f"{self.name}: {self.ess_per_sec:.1f} {self.metric_name} "
            f"(min_ess={self.min_ess:.0f}, wall={self.wall_s:.1f}s, "
            f"max_rhat={self.max_rhat:.3f})"
        )


def _timed(fn: Callable[[], Any]):
    from .telemetry import NULL_TRACE, use_trace

    # compile pass — populates the backend's runner cache.  It runs with
    # telemetry suppressed so a --trace file carries exactly ONE run (the
    # timed one, whose phase durations tile the reported wall) instead of
    # a compile-skewed duplicate.
    with use_trace(NULL_TRACE):
        fn()
    t0 = time.perf_counter()
    post = fn()
    wall = time.perf_counter() - t0
    return post, wall


def _result(name, post, wall, **extra) -> BenchResult:
    min_ess = post.min_ess()
    return BenchResult(
        name=name,
        wall_s=wall,
        min_ess=min_ess,
        ess_per_sec=min_ess / wall,
        max_rhat=post.max_rhat(),
        extra=extra,
    )


def bench_eight_schools(*, chains=4, num_warmup=500, num_samples=1000, seed=0):
    """Config 1: 8-schools hierarchical normal, NUTS."""
    model = EightSchools()
    data = eight_schools_data()
    backend = JaxBackend()
    post, wall = _timed(
        lambda: stark_tpu.sample(
            model, data, backend=backend, chains=chains, kernel="nuts",
            max_tree_depth=10, num_warmup=num_warmup, num_samples=num_samples,
            seed=seed,
        )
    )
    return _result("eight_schools_nuts", post, wall)


def fleet_eight_schools_spec(problems: int, *, seed: int = 0):
    """An eight-schools fleet: the classic dataset re-observed ``problems``
    times with fresh measurement noise — same hierarchical structure,
    different data per problem (the per-user/per-segment shape of ROADMAP
    item 2)."""
    from .fleet import FleetSpec
    from .models.eight_schools import SIGMA, Y

    rng = np.random.default_rng(seed)
    y, sig = np.asarray(Y), np.asarray(SIGMA)
    datasets = [
        {
            "y": (y + rng.normal(0.0, 0.25 * sig, y.shape)).astype(
                np.float32
            ),
            "sigma": sig,
        }
        for _ in range(problems)
    ]
    return FleetSpec.from_problems(EightSchools(), datasets)


def bench_fleet_eight_schools(
    *, problems=256, chains=4, num_warmup=200, block_size=50, max_blocks=24,
    ess_target=100.0, rhat_target=1.01, max_tree_depth=None, seq_probe=2,
    seed=0,
):
    """Fleet leg: eight-schools x ``problems`` through ONE vmapped block
    loop (stark_tpu.fleet), vs the same problems served sequentially.

    Headline: AGGREGATE min-ESS/s — the sum of per-problem min-ESS over
    the fleet wall (the throughput a per-user service actually delivers),
    measured on the steady-state pass (`_timed` convention: the compile
    pass is untimed, like every other leg).  ``max_tree_depth`` defaults
    to 5 on the legacy scheduler — a vmapped NUTS batch steps every lane
    until the DEEPEST tree finishes, so bounding the depth bounds the
    lane-sync waste — and lifts to the single-problem default of 10 when
    the step-synchronized scheduler is on (``STARK_RAGGED_NUTS=1``):
    ragged lanes advance their own trees, so a deep straggler costs only
    itself.  The sequential baseline always runs the same depth as the
    fleet, so the comparison stays apples-to-apples, and the ledger row
    records the scheduler + depth in its config key (distinct series).

    TWO sequential baselines ride in ``extra``, both extrapolated from
    ``seq_probe`` measured runs of the unmodified single-problem runner:

    * ``seq_per_job_ess_per_sec_est`` — a FRESH backend per problem: the
      one-job-per-process serving mode, the only way this repo served N
      posteriors before the fleet runner (ROADMAP item 1), with each job
      re-paying trace/compile (process startup excluded, so it is an
      UNDERestimate of the real per-job cost).  ``speedup_vs_sequential``
      is measured against this baseline.
    * ``seq_warm_ess_per_sec_est`` — one shared backend across the sweep
      (compiled segments reused): the in-process steady-state floor.  On
      a CPU host batching cannot beat it (no parallel lane width — the
      honest number rides in ``speedup_vs_warm_sequential``); on
      dispatch-bound accelerators this is the gap the tfp.mcmc argument
      says the fleet opens (PAPERS.md).
    """
    from .fleet import sample_fleet
    from .kernels.nuts_ragged import ragged_nuts_enabled
    from .runner import sample_until_converged

    ragged = ragged_nuts_enabled()
    if max_tree_depth is None:
        # the PR 6 depth cap exists ONLY to bound legacy lane-sync waste;
        # the ragged scheduler removes that coupling, so the cap lifts
        max_tree_depth = 10 if ragged else 5
    spec = fleet_eight_schools_spec(problems, seed=seed)
    gate_kw = dict(
        chains=chains, num_warmup=num_warmup, block_size=block_size,
        max_blocks=max_blocks, min_blocks=2, ess_target=ess_target,
        rhat_target=rhat_target, kernel="nuts",
        max_tree_depth=max_tree_depth,
    )
    res, wall = _timed(lambda: sample_fleet(spec, seed=seed, **gate_kw))

    per_ess = [p.min_ess for p in res.problems if p.min_ess is not None]
    agg_ess = float(np.sum(per_ess)) if per_ess else float("nan")
    max_rhat = float(np.max([
        p.max_rhat for p in res.problems if p.max_rhat is not None
    ] or [float("nan")]))
    conv_frac = res.converged_fraction
    fleet_rate = agg_ess / wall if wall else 0.0

    def _run_one(i, backend):
        r = sample_until_converged(
            spec.model, spec.datasets[i], backend=backend,
            seed=seed + i, adaptive_blocks=False, **gate_kw,
        )
        last = [h for h in r.history if h.get("event") == "block"][-1]
        e = last.get("full_min_ess", last.get("min_ess"))
        return float(e) if e is not None else 0.0

    n_probe = max(1, min(seq_probe, problems))
    # per-job baseline: fresh backend per problem (each probe re-traces)
    pj_ess, backend = 0.0, None
    t0 = time.perf_counter()
    for i in range(n_probe):
        backend = JaxBackend()
        pj_ess += _run_one(i, backend)
    pj_wall = time.perf_counter() - t0
    pj_rate = (pj_ess / pj_wall) if pj_wall else 0.0
    # warm baseline: the last probe's backend is compiled — re-run the
    # same probe problems through it, steady-state
    t0 = time.perf_counter()
    warm_ess = sum(_run_one(i, backend) for i in range(n_probe))
    warm_wall = time.perf_counter() - t0
    warm_rate = (warm_ess / warm_wall) if warm_wall else 0.0

    return BenchResult(
        name=f"fleet_eight_schools_x{problems}",
        wall_s=wall,
        min_ess=agg_ess,
        ess_per_sec=fleet_rate,
        max_rhat=max_rhat,
        metric_name="aggregate min-ESS/s",
        # the fleet's own gate: a high-convergence fleet, not one lucky
        # problem (max_rhat stays in the table as a diagnostic).
        # converged_fraction counts quarantined/budget-exhausted
        # problems as NOT converged over the FULL denominator, and a
        # quarantined problem's min_ess is None (never 0.0/NaN), so a
        # degraded fleet fails this gate instead of silently shipping a
        # shrunken aggregate — bench.py then records a null (not 0.0)
        # value, keeping the trailing-median regression gate clean (the
        # PR 7 null-not-0.0 convention).
        converged=conv_frac >= 0.95,
        gate=">=95% problems converged",
        extra={
            "problems": problems,
            "chains": chains,
            "sched": "ragged" if ragged else "legacy",
            "max_tree_depth": max_tree_depth,
            "converged_fraction": round(conv_frac, 4),
            # degraded completion (per-problem fault domains): recorded
            # on every row so a lossy fleet is visible in the ledger
            "degraded": res.degraded,
            "lost_problems": len(res.lost_problems),
            "blocks_dispatched": res.blocks_dispatched,
            "compactions": res.compactions,
            "fleet_grad_evals": res.total_grad_evals,
            "seq_probe": n_probe,
            "seq_per_job_ess_per_sec_est": round(pj_rate, 3),
            "seq_warm_ess_per_sec_est": round(warm_rate, 3),
            "speedup_vs_sequential": round(
                fleet_rate / pj_rate, 2
            ) if pj_rate else None,
            "speedup_vs_warm_sequential": round(
                fleet_rate / warm_rate, 2
            ) if warm_rate else None,
        },
    )


def bench_fleet_mesh_eight_schools(
    *, problems=32, shards=None, chains=4, num_warmup=200, block_size=50,
    max_blocks=24, ess_target=100.0, rhat_target=1.01, max_tree_depth=None,
    seed=0,
):
    """Device-parallel fleet leg (PR 14): eight-schools x ``problems``
    with the problem axis sharded over a ``shards``-wide "problems" mesh
    (`parallel.primitives.map_shards` under ``sample_fleet(mesh=...)``)
    vs the SINGLE-DEVICE fleet at equal B — the ROADMAP item 2 "no
    problem axis on meshes yet" gap, measured.

    Both variants run the same spec through `_timed` (compile pass
    untimed; the parts cache is keyed per (model, cfg, mesh) so each
    variant warms its own executable), and every problem's draws are
    compared BIT-EXACTLY across the two layouts — the mesh split must be
    free, not approximately free.

    Gate: >=95% converged, draws bit-identical, and the mesh fleet at
    >=2x the single-device aggregate min-ESS/s.  The 2x leg is the
    accelerator's number: D virtual CPU devices on a 1-core container
    share the same core, so the CPU row records an honest null for the
    gate (never a fabricated speedup) while the bit-identity and
    convergence evidence still ride the row.

    ``shards`` defaults to every local device — the committed ledger
    rows run under ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
    (the MULTICHIP dry-run environment).
    """
    from .fleet import sample_fleet
    from .kernels.nuts_ragged import ragged_nuts_enabled
    from .parallel.mesh import make_mesh

    ragged = ragged_nuts_enabled()
    if max_tree_depth is None:
        max_tree_depth = 10 if ragged else 5
    if shards is None:
        shards = len(jax.devices())
    if shards < 2:
        raise RuntimeError(
            f"bench_fleet_mesh needs >=2 devices to shard over (have "
            f"{shards}); force a CPU mesh via "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8"
        )
    spec = fleet_eight_schools_spec(problems, seed=seed)
    gate_kw = dict(
        chains=chains, num_warmup=num_warmup, block_size=block_size,
        max_blocks=max_blocks, min_blocks=2, ess_target=ess_target,
        rhat_target=rhat_target, kernel="nuts",
        max_tree_depth=max_tree_depth, seed=seed,
    )

    def rollup(res, wall):
        per = [p.min_ess for p in res.problems if p.min_ess is not None]
        agg = float(np.sum(per)) if per else float("nan")
        return agg, (agg / wall if wall else 0.0)

    single, s_wall = _timed(lambda: sample_fleet(spec, **gate_kw))
    _s_agg, s_rate = rollup(single, s_wall)
    mesh = make_mesh({"problems": shards}, devices=jax.devices()[:shards])
    # comms observatory (PR 16): predicted wire bytes the mesh leg's
    # accounted collectives moved, read off the primitives-layer probe
    from . import profiling
    from .parallel.primitives import comm_telemetry_enabled

    comm_bytes_before = profiling.comm_probe().total_bytes()
    res, wall = _timed(lambda: sample_fleet(spec, mesh=mesh, **gate_kw))
    agg, rate = rollup(res, wall)
    comm_bytes = profiling.comm_probe().total_bytes() - comm_bytes_before

    bit_identical = True
    for a, b in zip(single.problems, res.problems):
        da, db = np.asarray(a.draws_flat), np.asarray(b.draws_flat)
        if da.shape != db.shape or not np.array_equal(da, db):
            bit_identical = False
            break
    conv_frac = res.converged_fraction
    max_rhat = float(np.max([
        p.max_rhat for p in res.problems if p.max_rhat is not None
    ] or [float("nan")]))
    speedup = rate / s_rate if s_rate else None
    # per-shard occupancy rollup: mean over blocks of the mean shard
    # occupancy — how evenly the problem axis kept the mesh busy
    occ = [o for o, _q in res.dispatch_occupancy_trail]
    return BenchResult(
        name=f"fleet_mesh_eight_schools_x{problems}_s{shards}",
        wall_s=wall,
        min_ess=agg,
        ess_per_sec=rate,
        max_rhat=max_rhat,
        metric_name="aggregate min-ESS/s (mesh)",
        converged=(
            conv_frac >= 0.95 and bit_identical
            and speedup is not None and speedup >= 2.0
        ),
        gate=">=95% converged, draws bit-identical, >=2x single-device",
        extra={
            "problems": problems,
            "shards": shards,
            "chains": chains,
            "sched": "ragged" if ragged else "legacy",
            "max_tree_depth": max_tree_depth,
            "converged_fraction": round(conv_frac, 4),
            "bit_identical": bit_identical,
            # the measured rates survive an honest-null value column
            "mesh_ess_per_sec": round(rate, 3),
            "single_device_ess_per_sec": round(s_rate, 3),
            "speedup_vs_single_device": (
                round(speedup, 2) if speedup is not None else None
            ),
            "degraded": res.degraded,
            "lost_problems": len(res.lost_problems),
            "blocks_dispatched": res.blocks_dispatched,
            "dispatch_occupancy_mean": (
                round(float(np.mean(occ)), 4) if occ else None
            ),
            # comms observatory columns (honest nulls, never fabricated
            # 0.0): measured wire bytes when the telemetry is on, and a
            # null straggler ratio — D virtual CPU devices on one core
            # make shard-wall ratios scheduling noise, not imbalance
            "comm_bytes_total": (
                int(comm_bytes)
                if comm_telemetry_enabled() and comm_bytes > 0 else None
            ),
            "straggler_ratio": None,
        },
    )


def bench_fleet_stream(
    *, problems=16, chains=2, num_warmup=300, block_size=25, max_blocks=40,
    ess_target=60.0, rhat_target=1.1, max_batch=4, seed=0, warmstart=True,
):
    """Churn-heavy streaming-fleet leg: slot scheduler vs legacy
    compaction at EQUAL problem sets (PR 13's zero-recompile evidence).

    ``problems`` eight-schools variants share a ``max_batch``-wide batch,
    so the queue stays deep and every convergence churns the batch: the
    legacy path pays a fresh XLA specialization per compaction width,
    the slot scheduler admits in place and keeps the ONE compiled scan.
    Unlike every `_timed` leg, each variant runs ONCE with a FRESH model
    instance and the wall INCLUDES compiles — in-run re-specialization
    cost is the thing being measured, so warming it away would erase the
    evidence.  Evidence per variant: aggregate min-ESS/s, batched-scan
    specializations (`FleetResult.block_scan_compiles` — the compile
    spans carry the same count), compactions, in-place admissions, and
    ``occupancy_streaming`` (mean at-dispatch occupancy over blocks with
    a non-empty queue — the "slots stay hot while work waits" number).

    The gate: the slotted variant converges >=95% of problems, records
    EXACTLY ONE batched-scan compile vs >=2 for the legacy path, and its
    aggregate min-ESS/s is at or above the legacy-compaction baseline.

    ``warmstart=True`` adds a third variant (slots + donor transfer):
    its ``warmup_draws_saved`` and rate are recorded, with
    ``warmstart_speedup`` an honest null when transfer doesn't pay
    (never a fabricated 0.0)."""
    from .fleet import sample_fleet
    from .kernels.nuts_ragged import ragged_nuts_enabled

    ragged = ragged_nuts_enabled()
    max_tree_depth = 10 if ragged else 5
    gate_kw = dict(
        chains=chains, num_warmup=num_warmup, block_size=block_size,
        max_blocks=max_blocks, min_blocks=2, ess_target=ess_target,
        rhat_target=rhat_target, kernel="nuts",
        max_tree_depth=max_tree_depth, seed=seed, max_batch=max_batch,
    )

    def run(slots, ws=False, refill=0.5):
        # fresh spec => fresh model instance => this variant pays its
        # OWN compiles (the parts cache is keyed on the model object)
        spec = fleet_eight_schools_spec(problems, seed=seed)
        t0 = time.perf_counter()
        res = sample_fleet(
            spec, slots=slots, warmstart=ws, refill_occupancy=refill,
            **gate_kw,
        )
        wall = time.perf_counter() - t0
        per_ess = [p.min_ess for p in res.problems if p.min_ess is not None]
        agg = float(np.sum(per_ess)) if per_ess else float("nan")
        occ_q = [o for o, q in res.dispatch_occupancy_trail if q > 0]
        rhats = [p.max_rhat for p in res.problems if p.max_rhat is not None]
        return res, {
            "wall_s": round(wall, 2),
            "agg_min_ess": round(agg, 1),
            "max_rhat": round(float(np.max(rhats)), 4) if rhats else None,
            "ess_per_sec": round(agg / wall, 3) if wall else 0.0,
            "converged_fraction": round(res.converged_fraction, 4),
            "block_scan_compiles": res.block_scan_compiles,
            "compactions": res.compactions,
            "admissions": res.admissions,
            "occupancy_streaming": (
                round(float(np.mean(occ_q)), 4) if occ_q else None
            ),
        }

    slot_res, slot = run(slots=True)
    # legacy baseline at refill_occupancy=1.0: compact on every
    # convergence — the maximum-occupancy legacy configuration, i.e. the
    # STRONGEST compaction baseline to hold "at or above" against
    legacy_res, legacy = run(slots=False, refill=1.0)

    ws_row = None
    if warmstart:
        _ws_res, ws_row = run(slots=True, ws=True)
        ws_row["warmup_draws_saved"] = _ws_res.warmup_draws_saved
        ws_rate = ws_row["ess_per_sec"]
        # honest null: transfer that doesn't pay records no speedup,
        # never a measured-looking 0.0 (the PR 7 null-not-0.0 rule).
        # Guard on the ROUNDED value: a 1.004x "win" that rounds to
        # 1.0 is noise, not a claimable payoff
        sp = (
            round(ws_rate / slot["ess_per_sec"], 2)
            if slot["ess_per_sec"] else None
        )
        ws_row["warmstart_speedup"] = sp if sp is not None and sp > 1.0 \
            else None

    max_rhat = float(np.max([
        p.max_rhat for p in slot_res.problems if p.max_rhat is not None
    ] or [float("nan")]))
    gate_ok = (
        slot["converged_fraction"] >= 0.95
        and slot["block_scan_compiles"] == 1
        and legacy["block_scan_compiles"] >= 2
        and slot["ess_per_sec"] >= legacy["ess_per_sec"]
    )
    return BenchResult(
        name=f"fleet_stream_eight_schools_x{problems}",
        wall_s=slot["wall_s"],
        min_ess=slot["agg_min_ess"],
        ess_per_sec=slot["ess_per_sec"],
        max_rhat=max_rhat,
        metric_name="aggregate min-ESS/s (slotted, compile-inclusive)",
        converged=gate_ok,
        gate=(">=95% converged, exactly 1 batched-scan compile "
              "(legacy >=2), rate >= compaction baseline"),
        extra={
            "problems": problems,
            "chains": chains,
            "max_batch": max_batch,
            "sched": "slots",
            "max_tree_depth": max_tree_depth,
            "block_scan_compiles": slot["block_scan_compiles"],
            "compactions": slot_res.compactions,
            "admissions": slot["admissions"],
            "occupancy_streaming": slot["occupancy_streaming"],
            "converged_fraction": slot["converged_fraction"],
            "degraded": slot_res.degraded,
            "lost_problems": len(slot_res.lost_problems),
            "speedup_vs_compaction": (
                round(slot["ess_per_sec"] / legacy["ess_per_sec"], 2)
                if legacy["ess_per_sec"] else None
            ),
            "legacy": legacy,
            "warmstart": ws_row,
        },
    )


def bench_hier_logistic(
    *, n=200_000, d=32, groups=1000, chains=16, num_warmup=450,
    num_samples=300, max_tree_depth=6, seed=0, backend=None,
):
    """Config 2 / north-star numerator: hierarchical logistic, NUTS.

    16 vmapped chains measured 13.0 ESS/s vs 7.6 at 8 (2026-07-31);
    R-hat ~1.013 at this smoke budget is the depth-6 tree's honest
    limit on the 1034-dim posterior (depth 7 was ruled out by a
    device-program limit of the runtime this was tuned on, which the
    v5e machine does not have) — the judged flagship path is
    the converged ChEES run in bench.py, this leg is the NUTS
    comparison.
    """
    model = HierLogistic(num_features=d, num_groups=groups)
    data, _ = synth_logistic_data(
        jax.random.PRNGKey(seed), n, d, num_groups=groups
    )
    if backend is None:
        # bounded device programs on accelerators (progress granularity;
        # the bound predates PR 21, whose chip run found no
        # device-program time limit on the v5e machine)
        on_accel = jax.devices()[0].platform != "cpu"
        backend = JaxBackend(dispatch_steps=100 if on_accel else None)
    post, wall = _timed(
        lambda: stark_tpu.sample(
            model, data, backend=backend, chains=chains, kernel="nuts",
            max_tree_depth=max_tree_depth, num_warmup=num_warmup,
            num_samples=num_samples, seed=seed,
        )
    )
    grad_evals = float(np.sum(post.sample_stats.get("num_grad_evals", 0)))
    return _result(
        "hier_logistic_nuts", post, wall, n=n, d=d,
        grad_evals_per_sec=grad_evals / wall,
    )


def bench_consensus_logistic(
    *, n=100_000, d=16, num_shards=8, chains=8, num_warmup=300,
    num_samples=300, sampler="chees", seed=0, combine_check=True,
):
    """Config 2 (consensus variant): data-sharded sub-posteriors, zero
    per-step communication.

    Default sub-posterior sampler is ensemble ChEES (the judged config
    pins "consensus Monte Carlo", not the within-shard kernel): measured
    on the CPU replica (n=100k, 8 shards), chees 6.2 ESS/s vs NUTS 2.3
    at equal posterior accuracy.  On accelerators the fused Pallas
    likelihood serves each shard's ensemble with one X pass per
    evaluation (posterior parity with the plain model verified on CPU;
    interpret mode there is slower, so CPU keeps the XLA autodiff path).

    combine_check: quantify the consensus combine's accuracy against a
    full-data run at the same scale (VERDICT r3 missing #3) — reported
    as ``combine_rel_err``: the max over coefficients of
    |mean_consensus - mean_full| / sd_full, i.e. posterior-mean error in
    posterior-sd units.  Computed OUTSIDE the timed section (it is
    evidence about correctness, not part of the consensus cost).
    """
    from .models import FusedLogistic, Logistic

    on_accel = jax.devices()[0].platform != "cpu"
    model = FusedLogistic(num_features=d) if on_accel else Logistic(num_features=d)
    data, _ = synth_logistic_data(jax.random.PRNGKey(seed), n, d)

    if sampler == "chees":
        # bounded device programs on accelerators (6 transitions x the
        # 512-leapfrog warmup cap ~ 3k gradients a program; the bound
        # predates PR 21, whose chip run found no device-program time
        # limit); on CPU the monolithic dispatch avoids per-segment
        # overhead
        dispatch = 6 if on_accel else None

        def run():
            return consensus_sample(
                model, data, num_shards=num_shards, chains=chains,
                kernel="chees", num_warmup=num_warmup,
                num_samples=num_samples, init_step_size=0.1,
                map_init_steps=200, dispatch_steps=dispatch, seed=seed,
            )
    elif sampler == "nuts":
        def run():
            return consensus_sample(
                model, data, num_shards=num_shards, chains=chains,
                kernel="nuts", max_tree_depth=6, num_warmup=num_warmup,
                num_samples=num_samples, seed=seed,
            )
    else:
        raise ValueError(f"unknown sampler {sampler!r}; use 'chees' or 'nuts'")

    from . import profiling
    from .parallel.primitives import comm_telemetry_enabled

    comm_bytes_before = profiling.comm_probe().total_bytes()
    post, wall = _timed(run)
    comm_bytes = profiling.comm_probe().total_bytes() - comm_bytes_before
    extra = {
        "num_shards": num_shards,
        "sampler": sampler,
        # comms observatory columns (honest nulls, never fabricated 0.0):
        # consensus moves zero per-step traffic by design, so the bytes
        # column is the claim's receipt; no mesh shard walls exist here,
        # so the straggler column is null, not 0.0
        "comm_bytes_total": (
            int(comm_bytes)
            if comm_telemetry_enabled() and comm_bytes > 0 else None
        ),
        "straggler_ratio": None,
    }
    if combine_check:
        from .telemetry import NULL_TRACE, use_trace

        # correctness cross-check, not part of the consensus run: keep it
        # out of the trace so the traced consensus run stays the last one
        with use_trace(NULL_TRACE):
            full = stark_tpu.sample(
                model, data, chains=chains, kernel="chees",
                num_warmup=num_warmup, num_samples=num_samples,
                init_step_size=0.1, map_init_steps=200, seed=seed + 1,
            )
        mc = np.asarray(post.draws["beta"]).mean(axis=(0, 1))
        mf = np.asarray(full.draws["beta"]).mean(axis=(0, 1))
        sf = np.asarray(full.draws["beta"]).std(axis=(0, 1))
        extra["combine_rel_err"] = float(np.max(np.abs(mc - mf) / sf))
    return _result("consensus_logistic", post, wall, **extra)


def bench_lmm(
    *, n=100_000, d=8, groups=10_000, chains=16, num_warmup=700,
    num_samples=500, sampler="chees", max_tree_depth=9, seed=0,
):
    """Config 3: hierarchical LMM, random slopes, 10k groups.

    Default sampler is ensemble ChEES: on the ~2k-dim CPU-scale replica
    (n=20k, 1k groups) ChEES reached R-hat 1.010 / min-ESS 1896 / 6.7
    ESS/s where depth-8 NUTS at a comparable budget sat unconverged at
    R-hat 1.10 / 0.63 ESS/s — the cross-chain learned trajectory handles
    the group-effect block that NUTS needs depth 9+ trees for.
    sampler="nuts" keeps the Stan-class tree path for comparison (depth
    6 / warmup 300 measured R-hat > 100; depth 9 / warmup 600+
    converges — hence the depth-9 default).
    """
    from .models import FusedLinearMixedModelGrouped

    # grouped fused kernel on accelerators: group offsets + u-gradient
    # inside the one X pass (measured 7.2 -> 1.5 ms/ensemble grad at
    # C=16, N=100k, G=10k); falls back to the offset layout internally
    # if the grouping defeats the dense-window trick.  CPU keeps
    # autodiff (interpret-mode Pallas is slower there).
    on_accel = jax.devices()[0].platform != "cpu"
    mk = FusedLinearMixedModelGrouped if on_accel else LinearMixedModel
    model = mk(num_features=d, num_groups=groups, num_random=2)
    data, _ = synth_lmm_data(jax.random.PRNGKey(seed), n, d, groups)
    # bounded device programs of ~3k gradients: chees transitions can
    # reach the 512-leapfrog warmup cap, so 6 transitions bound the worst
    # case; NUTS depth-9 trees are 2^9 grads, so 6 transitions ~ 3k there
    # too (the bound predates PR 21, whose chip run found no
    # device-program time limit on the v5e machine)
    backend = JaxBackend(dispatch_steps=6)
    if sampler == "chees":
        post, wall = _timed(
            lambda: stark_tpu.sample(
                model, data, backend=backend, chains=chains, kernel="chees",
                num_warmup=num_warmup, num_samples=num_samples,
                init_step_size=0.1, map_init_steps=300, seed=seed,
            )
        )
    elif sampler == "nuts":
        post, wall = _timed(
            lambda: stark_tpu.sample(
                model, data, backend=backend, chains=chains, kernel="nuts",
                max_tree_depth=max_tree_depth, num_warmup=num_warmup,
                num_samples=num_samples, seed=seed,
            )
        )
    else:
        raise ValueError(f"unknown sampler {sampler!r}; use 'chees' or 'nuts'")
    return _result(
        "lmm_random_slopes", post, wall, groups=groups, sampler=sampler
    )


def bench_gmm_tempered(
    *, n=50_000, k=16, chains=2, num_temps=8, num_warmup=600,
    num_samples=500, max_tree_depth=7, seed=0,
):
    """Config 4: GMM K=16, reparameterized HMC + parallel tempering."""
    from .models.gmm import gmm_init_1d

    model = GaussianMixture(num_components=k)
    data, _ = synth_gmm_data(jax.random.PRNGKey(seed), n, k, spread=4.0)
    # with N=50k rows the posterior is too peaked for a prior-draw init to
    # find the mode reliably: k-means init (see gmm_init_1d) fixes the
    # component allocation; tempering then has to hold the chains
    # together, not find the basin from scratch
    init = gmm_init_1d(np.asarray(data["x"]), k)

    def run():
        # NUTS replicas: adaptive trajectories mix the 3K-1-dim mixture
        # posterior far better than fixed-length leapfrog (measured ~5x
        # min-ESS at equal draws); adapt_ladder gives the rungs ΔE-matched
        # spacing so swaps actually fire at this N (DESIGN.md §4b)
        return tempered_sample(
            model, data, chains=chains, num_temps=num_temps, kernel="nuts",
            max_tree_depth=max_tree_depth, num_warmup=num_warmup,
            num_samples=num_samples, swap_every=5, seed=seed,
            init_params=init, adapt_ladder=True,
        )

    post, wall = _timed(run)
    stats = post.sample_stats
    return _result(
        "gmm16_tempered", post, wall, num_temps=num_temps,
        swap_accept_rate=round(float(np.mean(stats["swap_accept_rate"])), 4),
        swap_accept_min_pair=round(
            float(np.min(stats["swap_accept_per_pair"])), 4
        ),
        beta_hot=round(float(np.min(stats["betas_adapted"])), 5),
    )


def bench_bnn_sghmc(
    *, n=100_000, d=64, hidden=64, batch_size=1024, chains=4,
    num_warmup=2000, num_samples=4000, cycles=8, step_size=3e-3, seed=0,
):
    """Config 5: Bayesian 2-layer MLP, SG-HMC minibatch gradients.

    Preconditioned cyclical SG-HMC: the grad**2-EMA mass equilibrates the
    fan-in prior scales and the warm-restart cycles hop posterior modes.
    """
    model = BayesianMLP(num_features=d, hidden=hidden)
    data, _ = synth_bnn_data(jax.random.PRNGKey(seed), n, d)

    def run():
        return sghmc_sample(
            model, data, batch_size=batch_size, chains=chains,
            num_warmup=num_warmup, num_samples=num_samples,
            step_size=step_size, friction=5.0, cycles=cycles, seed=seed,
        )

    post, wall = _timed(run)
    # BNN weights are non-identifiable (hidden-unit permutation/sign
    # symmetry), so weight-space R-hat/ESS is meaningless by construction.
    # Diagnose in predictive space: logits at fixed probe inputs — and
    # report the numbers the multimodality story actually turns on
    # (VERDICT r3 missing #5 / weak #1): held-out predictive accuracy,
    # bulk/tail ESS of the predictive means, and per-cycle evidence that
    # the warm-restart schedule is visiting distinct modes (which is
    # precisely what inflates predictive R-hat without being a failure).
    x_probe = np.asarray(data["x"][:256])
    y_probe = np.asarray(data["y"][:256])
    logits = post.functional(lambda p: model.forward(p, x_probe))
    min_ess = float(np.min(diagnostics.ess(logits)))
    probs = 1.0 / (1.0 + np.exp(-np.asarray(logits, np.float64)))
    acc = float(np.mean((probs.mean(axis=(0, 1)) > 0.5) == (y_probe > 0.5)))
    extra = {
        "batch_size": batch_size,
        "diag_space": "predictive_logits",
        "predictive_accuracy": acc,
        "pred_ess_bulk": float(np.min(diagnostics.ess_bulk(logits))),
        "pred_ess_tail": float(np.min(diagnostics.ess_tail(logits))),
    }
    cyc = post.sample_stats.get("cycle_id")
    if cyc is not None and len(np.unique(cyc)) > 1:
        # mode evidence: per-cycle predictive means vs within-cycle
        # noise.  cycle_mode_ratio >> 1 = successive warm restarts land
        # in DISTINCT basins (the schedule is exploring modes — which is
        # what inflates predictive R-hat without being a failure);
        # ~<= 1 = cycles revisit the same basin
        pc = np.stack([
            logits[:, cyc == c, :].mean(axis=1)  # (chains, probes)
            for c in np.unique(cyc)
        ])  # (cycles, chains, probes)
        across = float(pc.std(axis=0).mean())
        within = float(np.mean([
            logits[:, cyc == c, :].std(axis=1).mean()
            for c in np.unique(cyc)
        ]))
        extra["cycle_mode_ratio"] = across / max(within, 1e-12)
        extra["n_cycles_collected"] = int(len(np.unique(cyc)))
    # headline metrics are the DEFENSIBLE ones (VERDICT r4 #4): held-out
    # predictive accuracy and predictive-space ESS/s.  Predictive R-hat
    # stays as a diagnostic column: its elevation measures mode structure
    # (cycle_mode_ratio ~7 = each warm restart lands in a distinct basin;
    # R-hat<1.01 would need every chain to visit and weight the same mode
    # set — an O(100s-of-cycles) budget), not
    # non-convergence.  The gate is therefore measured accuracy against
    # the 0.5 chance floor: 0.75 sits below the 0.80-0.82 band measured
    # stable across a 4x chain-budget escalation.
    mode_note = (
        f"; R-hat={float(np.max(diagnostics.split_rhat(logits))):.2f}"
        f"=mode structure (cycle_mode_ratio"
        f"={extra.get('cycle_mode_ratio', float('nan')):.1f})"
    )
    return BenchResult(
        name="bnn_sghmc",
        wall_s=wall,
        min_ess=min_ess,
        ess_per_sec=min_ess / wall,
        max_rhat=float(np.max(diagnostics.split_rhat(logits))),
        extra=extra,
        metric_name="pred-ESS/s",
        converged=bool(acc >= 0.75),
        gate=f"pred accuracy {acc:.2f}>=0.75{mode_note}",
    )


#: per-fused-op microbench workloads: family -> (plain model, fused
#: model, dataset, STARK_FUSED_* knob).  Sizes are the judged-scale
#: shapes shrunk to a few-second CPU leg; BENCH_FUSEDVG_SCALE rescales
#: the row count.
def _fused_vg_case(family: str, scale: float = 1.0):
    import os

    from .models import (
        FusedIRT2PL,
        FusedLMM,
        FusedOrderedLogistic,
        FusedStudentTRegression,
        IRT2PL,
        LinearMixedModel,
        OrderedLogistic,
        StudentTRegression,
        synth_irt_data,
        synth_lmm_data,
        synth_ordinal_data,
        synth_studentt_data,
    )

    scale = float(os.environ.get("BENCH_FUSEDVG_SCALE", scale))
    key = jax.random.PRNGKey(7)
    if family == "logistic":
        from .models import FusedLogistic, Logistic, synth_logistic_data

        n, d = max(int(200_000 * scale), 1000), 32
        data, _ = synth_logistic_data(key, n, d)
        return (
            Logistic(d), FusedLogistic(d), data,
            None, {"n": n, "d": d},
        )
    if family == "lmm":
        n, d, g = max(int(200_000 * scale), 1000), 32, 2000
        data, _ = synth_lmm_data(key, n, d, g)
        return (
            LinearMixedModel(d, g), FusedLMM(d, g), data,
            "STARK_FUSED_LMM", {"n": n, "d": d, "groups": g},
        )
    if family == "irt":
        p, i = max(int(2000 * scale), 50), 200
        data, _ = synth_irt_data(key, p, i)
        return (
            IRT2PL(p, i), FusedIRT2PL(p, i), data,
            "STARK_FUSED_IRT", {"persons": p, "items": i},
        )
    if family == "ordinal":
        n, d, k = max(int(200_000 * scale), 1000), 32, 5
        data, _ = synth_ordinal_data(key, n, d, num_categories=k)
        return (
            OrderedLogistic(d, k), FusedOrderedLogistic(d, k), data,
            "STARK_FUSED_ORDINAL", {"n": n, "d": d, "categories": k},
        )
    if family == "robust":
        n, d = max(int(200_000 * scale), 1000), 32
        data, _ = synth_studentt_data(key, n, d)
        return (
            StudentTRegression(d), FusedStudentTRegression(d), data,
            "STARK_FUSED_ROBUST", {"n": n, "d": d},
        )
    raise ValueError(f"unknown fused-vg family {family!r}")


def bench_fused_value_and_grad(
    family: str = "lmm", *, x_dtype: str = None, reps: int = 30,
    rounds: int = 3, seed: int = 0,
) -> BenchResult:
    """Per-fused-op microbench: fused vs autodiff value-and-grad
    throughput through the full potential (ROADMAP item 3 evidence legs).

    Times the jitted ``potential_and_grad`` — the exact call every
    leapfrog step pays — for the plain (autodiff) model and its
    ``Fused*`` variant with the family knob forced on, over ``rounds``
    interleaved rounds (the max rate per path is reported, which
    de-noises a shared CPU container).  The headline ``ess_per_sec``
    column carries FUSED evals/s; the autodiff rate, the speedup, and a
    fused-vs-autodiff gradient-parity delta ride ``extra``.  Gate:
    speedup >= 1.3x.

    ``x_dtype`` is the X-dtype axis (ROADMAP item 3's "fp8/int8 X"):
    it forces STARK_FUSED_X_DTYPE for the fused side's prepare + run,
    so one leg measures the fused op on a bf16 or quantized
    (ops/quantize.py) design-matrix stream.  The autodiff baseline
    stays on raw f32 data (the path a user runs today); the
    gradient-parity delta is instead taken against autodiff on the SAME
    dequantized X (the rounded-X reference convention), so it measures
    the kernel, not the calibration.  Every row carries the
    bytes-accounting evidence: ``x_bytes_per_grad`` (bytes of the
    packed slab + scales one fused evaluation streams),
    ``x_bytes_per_grad_f32`` (the same slab at f32), and their ratio
    ``x_traffic_reduction``.  Quantized legs additionally time the
    fused op on f32 X in the same interleaved rounds
    (``fused_f32x_evals_per_sec`` / ``speedup_vs_f32x``) — the
    does-quantization-pay number, reported honestly either way.

    Any internal failure of the fused path yields ``ess_per_sec = NaN``
    (-> ``null`` in bench artifacts and ledger rows, NEVER 0.0): a
    broken fused kernel must gate as missing data, not poison the
    trailing-median gate with a measured-zero (ADVICE r5 / PR 4
    convention).
    """
    import os

    from .model import flatten_model, prepare_model_data
    from .ops.precision import x_stream_config
    from .ops.quantize import (
        PACKED_DTYPES,
        fake_quant,
        x_bytes_per_grad as slab_bytes,
    )

    plain, fused, data, knob, shape = _fused_vg_case(family)
    t0 = time.perf_counter()
    prior = {
        k: os.environ.get(k)
        for k in ((knob,) if knob else ()) + (
            ("STARK_FUSED_X_DTYPE",) if x_dtype else ()
        )
    }
    if knob:
        os.environ[knob] = "1"
    try:
        if x_dtype:
            os.environ["STARK_FUSED_X_DTYPE"] = x_dtype
        xcfg = x_stream_config()
        fm_f = flatten_model(fused)
        df = prepare_model_data(fused, data)
        f32_env = dict(os.environ)
        os.environ["STARK_FUSED_X_DTYPE"] = "f32"
        try:
            # baseline sides always run on f32: raw X for the autodiff
            # timing baseline, dequantized X for the parity reference,
            # and (quantized legs only) the fused op itself on f32 X
            fm_p = flatten_model(plain)
            dp = prepare_model_data(plain, data)
            xname = xcfg.split("@")[0]
            dp_ref, df_f32 = dp, None
            if xname != "f32" and "x" in data:
                # the rounded-X reference convention: bf16 rounds, the
                # packed dtypes quantize-dequantize through the real
                # calibration path — either way the parity delta
                # measures the kernel, never the data rounding
                rounded = (
                    fake_quant(data["x"], xname)
                    if xname in PACKED_DTYPES
                    else jnp.asarray(data["x"])
                    .astype(jnp.bfloat16).astype(jnp.float32)
                )
                dp_ref = prepare_model_data(plain, {**data, "x": rounded})
            if xcfg != "f32":
                df_f32 = prepare_model_data(fused, data)
        finally:
            os.environ.clear()
            os.environ.update(f32_env)
        z = 0.1 * jax.random.normal(jax.random.PRNGKey(seed), (fm_p.ndim,))
        f_auto = jax.jit(lambda z: fm_p.potential_and_grad(z, dp))
        f_fused = jax.jit(lambda z: fm_f.potential_and_grad(z, df))

        def rate(f):
            jax.block_until_ready(f(z))  # compile outside the clock
            t = time.perf_counter()
            out = None
            for _ in range(reps):
                out = f(z)
            jax.block_until_ready(out)
            return reps / (time.perf_counter() - t)

        auto_rate, fused_rate = 0.0, float("nan")
        f32x_rate = None
        vp, gp = f_auto(z)
        if dp_ref is not dp:
            _, gp = jax.jit(
                lambda z: fm_p.potential_and_grad(z, dp_ref)
            )(z)
        try:
            vf, gf = f_fused(z)
            grad_delta = float(
                jnp.max(jnp.abs(gp - gf))
                / (1e-6 + jnp.max(jnp.abs(gp)))
            )
        except Exception:  # noqa: BLE001 — a broken fused path is the
            # exact condition the NaN/null contract exists for
            grad_delta = float("nan")
        else:
            f_f32x = (
                jax.jit(lambda z: fm_f.potential_and_grad(z, df_f32))
                if df_f32 is not None
                else None
            )
            for _ in range(rounds):
                # autodiff-side failures propagate as a LEG error — only
                # fused-side calls may trip the broken-fused NaN/null
                # contract, else a transient baseline failure records
                # the fused kernel as broken in the ledger
                auto_rate = max(auto_rate, rate(f_auto))
                try:
                    fused_rate = max(
                        0.0 if np.isnan(fused_rate) else fused_rate,
                        rate(f_fused),
                    )
                    if f_f32x is not None:
                        f32x_rate = max(f32x_rate or 0.0, rate(f_f32x))
                except Exception:  # noqa: BLE001 — broken fused path
                    fused_rate = float("nan")
                    break
        if np.isnan(fused_rate) and auto_rate == 0.0:
            # fused broke before any round: still record the autodiff
            # baseline as evidence alongside the null fused rate
            auto_rate = rate(f_auto)
        xbytes = slab_bytes(df)
        xbytes_f32 = slab_bytes(df_f32) if df_f32 is not None else (
            xbytes if xcfg == "f32" else None
        )
    finally:
        for k, v in prior.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    wall = time.perf_counter() - t0
    speedup = fused_rate / auto_rate if auto_rate > 0 else float("nan")
    # family-specific gate: the scatter/X-stream-dominated families must
    # beat autodiff >=1.3x on CPU; the ordinal likelihood is
    # transcendental-bound there (both paths pay ~the same per-row link
    # chain) so its CPU gate is parity — the one-pass contract's win for
    # it is the halved accelerator HBM traffic, which the on-chip
    # roofline measures, not this leg.  The flagship logistic kernel is
    # Pallas: on the CPU container it runs under the Pallas INTERPRETER,
    # so its CPU gate is also parity — its rows exist to carry the
    # quantized-stream bytes evidence, and an interpreter-bound leg that
    # loses to XLA autodiff reports an honest null, never a fake win
    min_speedup = 1.0 if family in ("ordinal", "logistic") else 1.3
    ok = bool(np.isfinite(speedup) and speedup >= min_speedup)
    return BenchResult(
        name=f"fused_vg_{family}",
        wall_s=wall,
        min_ess=float("nan"),  # not a sampling leg: no ESS to report
        ess_per_sec=fused_rate,
        max_rhat=float("nan"),
        metric_name="fused vg evals/s",
        converged=ok,
        gate=f"fused >= {min_speedup}x autodiff value-and-grad",
        extra={
            "family": family,
            **shape,
            "knob": knob,
            "x_dtype": xcfg,
            "autodiff_evals_per_sec": round(auto_rate, 3),
            "speedup_vs_autodiff": (
                round(speedup, 3) if np.isfinite(speedup) else None
            ),
            "grad_parity_rel": grad_delta,
            # bytes-accounting evidence for the quantized data-plane:
            # the bandwidth claim is carried as measured slab bytes per
            # evaluation, not asserted (null when no slab exists)
            "x_bytes_per_grad": xbytes,
            "x_bytes_per_grad_f32": xbytes_f32,
            "x_traffic_reduction": (
                round(xbytes_f32 / xbytes, 3)
                if xbytes and xbytes_f32
                else None
            ),
            "fused_f32x_evals_per_sec": (
                round(f32x_rate, 3) if f32x_rate else None
            ),
            "speedup_vs_f32x": (
                round(fused_rate / f32x_rate, 3)
                if f32x_rate and np.isfinite(fused_rate)
                else None
            ),
        },
    )


# dispatch-count probe: promoted to `profiling.DispatchProbe` (PR 11 —
# installable on any jitted entry, with a process registry); re-exported
# under the historical name for the nutssched microbench and its tests
from .profiling import DispatchProbe as _GradEvalProbe  # noqa: E402


def bench_nuts_sched(
    *, n=8192, d=16, chains=24, block_size=64, max_tree_depth=8,
    rounds=3, seed=0,
) -> BenchResult:
    """``bench.py microbench nutssched``: step-synchronized (ragged) vs
    legacy NUTS block scheduling on a mixed-curvature synthetic.

    The workload is a logistic posterior (N x d likelihood, so the
    gradient evaluation — not the scheduler bookkeeping — dominates each
    iteration) sampled by ``chains`` lanes whose step sizes are spread
    over octaves: lanes deliberately build trees of different depths, and
    NUTS's per-transition direction/depth randomness de-synchronizes them
    further — exactly the raggedness that makes the legacy vmapped loops
    pay max-lane-tree at every level.

    Measured, per scheduler:

    * **bit identity** — ragged draws/stats must equal legacy's exactly
      (the determinism contract, asserted before anything is timed);
    * **executed vs useful gradient evaluations** — executed counts come
      from the `_GradEvalProbe` dispatch-count instrumentation (a
      separate probed pass, so timing stays clean), useful from the
      kernels' ``num_grad_evals``; their ratio is the lane occupancy;
    * **occupancy-adjusted throughput** — useful gradient evaluations
      per second over ``rounds`` interleaved timed rounds (max rate per
      path, the `_fused_vg_case` de-noising convention).

    Headline ``ess_per_sec`` carries the RAGGED useful-grads/s; the
    legacy rate, speedup, both occupancies and both executed counts ride
    ``extra`` under the ``nutssched:*`` ledger config key.  Gate:
    bit-identical AND occupancy strictly improves AND >= 1.3x
    occupancy-adjusted throughput.
    """
    import os

    from .kernels.base import init_state
    from .model import flatten_model, prepare_model_data
    from .models import Logistic, synth_logistic_data
    from .sampler import SamplerConfig, make_block_runner

    scale = float(os.environ.get("BENCH_NUTSSCHED_SCALE", 1.0))
    n = max(int(n * scale), 512)
    t0 = time.perf_counter()
    model = Logistic(num_features=d)
    data, _ = synth_logistic_data(jax.random.PRNGKey(seed), n, d)
    fm = flatten_model(model)
    pdata = prepare_model_data(model, data)
    cfg = SamplerConfig(kernel="nuts", max_tree_depth=max_tree_depth)
    pot = fm.bind(pdata)
    key = jax.random.PRNGKey(seed + 1)
    kz, kb = jax.random.split(key)
    z0 = 0.05 * jax.vmap(fm.init_flat)(jax.random.split(kz, chains))
    state = jax.vmap(lambda z: init_state(pot, z))(z0)
    # mixed curvature: two interleaved step-size groups around the
    # posterior scale (~2/sqrt(n) for a logistic GLM) — the small-step
    # lanes build trees ~1 doubling deeper on average, and NUTS's
    # per-transition randomness spreads each lane's depth further.  The
    # groups stay within a factor 1.5 so no single lane dominates every
    # round (a lane that is ALWAYS deepest is the one case where the
    # legacy max-lane sync is already tight)
    base = 2.7 / np.sqrt(n)
    step_size = jnp.asarray(
        base * np.where(np.arange(chains) % 2 == 0, 1.0, 2.0 / 3.0),
        jnp.float32,
    )
    inv_mass = jnp.ones((chains, d), jnp.float32)
    bkeys = jax.random.split(kb, chains)
    args = (bkeys, state, step_size, inv_mass, pdata)

    def build(source_fm, ragged):
        return jax.jit(jax.vmap(
            make_block_runner(source_fm, cfg, block_size, ragged=ragged),
            in_axes=(0, 0, 0, 0, None),
        ))

    legacy_fn, ragged_fn = build(fm, False), build(fm, True)
    out_l = jax.block_until_ready(legacy_fn(*args))
    out_r = jax.block_until_ready(ragged_fn(*args))
    identical = all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(out_l[1:6], out_r[1:6])
    )
    ngrad = np.asarray(out_l[5])
    useful = int(ngrad.sum())
    lane_iters = np.asarray(out_r[6])

    # --- dispatch-count probe (separate pass: callbacks poison timing) --
    probe = _GradEvalProbe(fm)
    # calibrate callback multiplicity for one vmapped batched evaluation
    # (jax may invoke the callback once per batch or once per lane)
    probe.calls = 0
    jax.block_until_ready(
        jax.jit(jax.vmap(probe.bind(pdata).value_and_grad))(z0)
    )
    per_eval = max(probe.snapshot(), 1)
    counts = {}
    for name, ragged in (("legacy", False), ("ragged", True)):
        probe.calls = 0
        jax.block_until_ready(build(probe, ragged)(*args))
        counts[name] = probe.snapshot() // per_eval
    occ_legacy = useful / max(counts["legacy"] * chains, 1)
    occ_ragged = useful / max(counts["ragged"] * chains, 1)

    # --- occupancy-adjusted throughput (clean, interleaved rounds) ------
    def one_round(fn):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        return useful / (time.perf_counter() - t)

    rate_l, rate_r = 0.0, 0.0
    for _ in range(rounds):
        rate_l = max(rate_l, one_round(legacy_fn))
        rate_r = max(rate_r, one_round(ragged_fn))
    speedup = rate_r / rate_l if rate_l > 0 else float("nan")
    ok = bool(
        identical
        and np.isfinite(speedup)
        and speedup >= 1.3
        and occ_ragged > occ_legacy
    )
    draws = chains * block_size
    return BenchResult(
        name="nuts_sched_mixed_depth",
        wall_s=time.perf_counter() - t0,
        min_ess=float("nan"),  # not a sampling leg: no ESS to report
        ess_per_sec=rate_r if identical else float("nan"),
        max_rhat=float("nan"),
        metric_name="useful grad evals/s",
        converged=ok,
        gate="bit-identical + occupancy up + >=1.3x vs legacy NUTS",
        extra={
            "family": "nutssched",
            "n": n,
            "d": d,
            "chains": chains,
            "block_size": block_size,
            "max_tree_depth": max_tree_depth,
            "bit_identical": identical,
            "legacy_evals_per_sec": round(rate_l, 3),
            "speedup_vs_legacy": (
                round(speedup, 3) if np.isfinite(speedup) else None
            ),
            "useful_grad_evals": useful,
            "executed_batched_evals_legacy": counts["legacy"],
            "executed_batched_evals_ragged": counts["ragged"],
            "lane_occupancy_legacy": round(occ_legacy, 4),
            "lane_occupancy_ragged": round(occ_ragged, 4),
            # grad evals the batch EXECUTED per effective draw, by path —
            # the per-draw cost the lane sync inflates
            "executed_per_draw_legacy": round(
                counts["legacy"] * chains / draws, 2
            ),
            "executed_per_draw_ragged": round(
                counts["ragged"] * chains / draws, 2
            ),
            "useful_per_draw": round(useful / draws, 2),
            # carry-accounting cross-check: the ragged loop's iteration
            # count must equal the probe's executed-batched-evals
            "sched_iters_max": int(lane_iters.max()),
        },
    )


def _serving_summary_leg(tenants, chains, draws, dim, seed):
    """``read:summary:*``: warm-LRU vs cold-mmap summary QPS over a
    synthetic multi-tenant root.  Cold reads evict first (fresh mmap
    open + sidecar parse per query); warm reads hit the LRU.  Gate:
    warm >= 10x cold — the cache either pays for itself or the row says
    it did not."""
    import shutil
    import tempfile

    from . import serving
    from .drawstore import DrawStore

    t0 = time.perf_counter()
    root = tempfile.mkdtemp(prefix="stark_bench_serve_")
    try:
        rng = np.random.default_rng(seed)
        for t in range(tenants):
            path = os.path.join(root, f"p_t{t:03d}.stkr")
            with DrawStore(path, chains, dim) as ds:
                ds.append(
                    rng.standard_normal((chains, draws, dim)).astype(
                        np.float32
                    )
                )
                ds.flush()
            serving.write_summary(
                path, problem_id=f"t{t:03d}", model_tag="bench",
                status="converged",
            )
        store = serving.PosteriorStore(root, capacity=tenants)
        ids = store.ids()
        queries = 400

        def qps(cold: bool) -> float:
            t = time.perf_counter()
            for k in range(queries):
                pid = ids[k % len(ids)]
                if cold:
                    store.evict(pid)
                store.summary(pid)
            return queries / (time.perf_counter() - t)

        qps(cold=True)  # touch every sidecar once (page cache parity)
        cold_qps = qps(cold=True)
        warm_qps = qps(cold=False)
        stats = store.cache_stats()
        store.close()
    finally:
        shutil.rmtree(root, ignore_errors=True)
    speedup = warm_qps / cold_qps if cold_qps > 0 else float("nan")
    ok = bool(np.isfinite(speedup) and speedup >= 10.0)
    hit_ratio = stats["hits"] / max(stats["requests"], 1)
    return BenchResult(
        name="serving_summary_qps",
        wall_s=time.perf_counter() - t0,
        min_ess=float("nan"),  # not a sampling leg: no ESS to report
        ess_per_sec=warm_qps if ok else float("nan"),
        max_rhat=float("nan"),
        metric_name="summaries/s (warm)",
        converged=ok,
        gate=">=10x warm-LRU vs cold-mmap summary QPS",
        extra={
            "tenants": tenants,
            "summary_qps_warm": round(warm_qps, 1),
            "summary_qps_cold": round(cold_qps, 1),
            "warm_cold_speedup": round(speedup, 2),
            "cache_hit_ratio": round(hit_ratio, 4),
        },
    )


def _serving_predict_leg(tenants, chains, draws, dim, m, seed):
    """``read:predict:*``: ONE batched vmapped dispatch across tenants vs
    the per-draw Python-loop reference, at parity.  One tenant serves a
    packed int8 design (the `dequant_dot` scale-fold identity) — its
    parity is checked against the DEQUANTIZED design, so the gate proves
    the fold, not just the speed.  Gate: >=5x AND max |err| <= 1e-5."""
    import shutil
    import tempfile

    from . import serving
    from .drawstore import DrawStore

    t0 = time.perf_counter()
    root = tempfile.mkdtemp(prefix="stark_bench_predict_")
    try:
        rng = np.random.default_rng(seed + 1)
        designs = {}
        for t in range(tenants):
            pid = f"t{t:03d}"
            path = os.path.join(root, f"p_{pid}.stkr")
            with DrawStore(path, chains, dim) as ds:
                ds.append(
                    (0.3 * rng.standard_normal((chains, draws, dim))).astype(
                        np.float32
                    )
                )
                ds.flush()
            designs[pid] = rng.standard_normal((m, dim)).astype(np.float32)
        store = serving.PosteriorStore(root, capacity=tenants)
        quant_pid = "t000"  # one tenant serves off the packed int8 slab
        for pid, x in designs.items():
            store.register_design(
                pid, x, dtype="int8" if pid == quant_pid else None
            )
        reqs = [
            serving.PredictRequest(pid, link="identity")
            for pid in sorted(designs)
        ]
        out = store.predict(reqs)  # compile pass + the parity artifact

        # parity vs the per-draw loop on each tenant's EFFECTIVE design
        # (xq * scale — for the quantized tenant that is the dequantized
        # slab, so agreement proves the scale-fold identity end to end)
        max_err, s_used = 0.0, 0
        for req, row in zip(reqs, out):
            beta, xq, scale, _cache = store._predict_operands(req)
            s_used = beta.shape[0]
            x_eff = np.asarray(xq, np.float32) * scale[None, :]
            ref_mean, ref_q = serving.predict_reference(beta, x_eff)
            max_err = max(
                max_err,
                float(np.max(np.abs(np.asarray(row["mean"]) - ref_mean))),
                float(np.max(np.abs(np.asarray(row["quantiles"]) - ref_q))),
            )

        rounds, lat = 8, []
        for _ in range(rounds):
            t = time.perf_counter()
            store.predict(reqs)
            lat.append(time.perf_counter() - t)
        evals = s_used * m * len(reqs)  # draw-row predictions per call
        batched_eps = evals / min(lat)

        t = time.perf_counter()
        for req in reqs:
            beta, xq, scale, _cache = store._predict_operands(req)
            serving.predict_reference(
                beta, np.asarray(xq, np.float32) * scale[None, :]
            )
        loop_eps = evals / (time.perf_counter() - t)
        store.close()
    finally:
        shutil.rmtree(root, ignore_errors=True)
    speedup = batched_eps / loop_eps if loop_eps > 0 else float("nan")
    ok = bool(np.isfinite(speedup) and speedup >= 5.0 and max_err <= 1e-5)
    lat_ms = sorted(1e3 * v for v in lat)
    return BenchResult(
        name="serving_predict_batched",
        wall_s=time.perf_counter() - t0,
        min_ess=float("nan"),
        ess_per_sec=batched_eps if ok else float("nan"),
        max_rhat=float("nan"),
        metric_name="predictive evals/s",
        converged=ok,
        gate=">=5x vs per-draw loop at |err|<=1e-5 (incl. int8 tenant)",
        extra={
            "batch": len(reqs),
            "draws_used": s_used,
            "design_rows": m,
            "batched_evals_per_sec": round(batched_eps, 1),
            "loop_evals_per_sec": round(loop_eps, 1),
            "speedup_vs_loop": round(speedup, 2),
            "predict_parity_abs_err": float(max_err),
            "quantized_tenant": quant_pid,
            "predict_p50_ms": round(lat_ms[len(lat_ms) // 2], 3),
            "predict_p99_ms": round(lat_ms[-1], 3),
        },
    )


def _serving_reconverge_leg(chains, seed):
    """``read:reconverge:*``: incremental posterior updating end to end.

    Day 1: a fleet run persists one eight-schools tenant's store +
    summary sidecar.  Day 2: the tenant's data grows (a fresh
    re-observation) and it is RESUBMITTED through `fleet.FleetFeed` into
    a live slotted fleet — once cold, once with
    `serving.donor_pool_from_store` (yesterday's sidecar adaptation +
    store-tail position ensemble) as the donor under
    ``warmstart=True``.  The anchor problem that holds the slot open
    carries ``deadline_s=0`` so it exits ``budget_exhausted`` after one
    block WITHOUT donating (only converged problems donate), leaving the
    pool exactly as the serving layer seeded it.  Gate: both resubmitted
    runs converge AND the warm one needs strictly fewer total draws per
    chain (warmup + sampling) — ``reconverge_draws_saved > 0``."""
    import shutil
    import tempfile

    from . import serving
    from .fleet import FleetFeed, FleetSpec, ProblemBudget, sample_fleet
    from .models.eight_schools import SIGMA, Y

    t0 = time.perf_counter()
    rng = np.random.default_rng(seed + 2)
    y, sig = np.asarray(Y, np.float32), np.asarray(SIGMA, np.float32)

    def reobs():
        return {
            "y": (y + rng.normal(0.0, 0.25 * sig, y.shape)).astype(
                np.float32
            ),
            "sigma": sig,
        }

    kw = dict(
        chains=chains, block_size=25, max_blocks=8, min_blocks=2,
        num_warmup=100, ess_target=40.0, rhat_target=1.3, kernel="hmc",
        num_leapfrog=12, slots=True,
    )
    day1_data, day2_data = reobs(), reobs()
    root = tempfile.mkdtemp(prefix="stark_bench_reconv_")
    try:
        # --- day 1: cold run persists the tenant's store + sidecar ----
        spec1 = FleetSpec.from_problems(
            EightSchools(), [day1_data], problem_ids=["tenant"]
        )
        # an (empty, closed) feed pins the vmapped fleet path at B=1 —
        # the sequential hatch writes no summary sidecar, and the
        # sidecar's adaptation state is half the donor
        feed1 = FleetFeed()
        feed1.close()
        res1 = sample_fleet(spec1, draw_store_path=root, feed=feed1, **kw)
        if not res1["tenant"].converged:
            raise RuntimeError("day-1 tenant did not converge")
        store_path = serving.PosteriorStore(root).path("tenant")

        def day2(donor_pool):
            spec = FleetSpec.from_problems(
                EightSchools(), [reobs()], problem_ids=["anchor"],
                budgets=[ProblemBudget(deadline_s=0.0)],
            )
            feed = FleetFeed()
            feed.submit(day2_data, problem_id="tenant_day2")
            feed.close()
            res = sample_fleet(
                spec, feed=feed, max_batch=1, warmstart=True,
                donor_pool=donor_pool, **kw,
            )
            p = res["tenant_day2"]
            total = (
                kw["num_warmup"] - p.warmup_draws_saved + p.draws_per_chain
            )
            return p, total

        p_cold, cold_total = day2(None)
        pool = serving.donor_pool_from_store(store_path, "EightSchools")
        p_warm, warm_total = day2(pool)
    finally:
        shutil.rmtree(root, ignore_errors=True)
    saved = cold_total - warm_total
    ok = bool(p_cold.converged and p_warm.converged and saved > 0)
    return BenchResult(
        name="serving_incremental_reconverge",
        wall_s=time.perf_counter() - t0,
        min_ess=float(p_warm.min_ess or float("nan")),
        ess_per_sec=float(saved) if ok else float("nan"),
        max_rhat=float(p_warm.max_rhat or float("nan")),
        metric_name="draws saved/chain",
        converged=ok,
        gate="warm + cold resubmits converge AND reconverge_draws_saved>0",
        extra={
            "reconverge_draws_saved": int(saved),
            "cold_total_draws_per_chain": int(cold_total),
            "warm_total_draws_per_chain": int(warm_total),
            "warmup_draws_saved": int(p_warm.warmup_draws_saved),
            "warmstarted": bool(p_warm.warmstarted),
            "cold_sampling_draws": int(p_cold.draws_per_chain),
            "warm_sampling_draws": int(p_warm.draws_per_chain),
        },
    )


def bench_serving(
    *, tenants=16, chains=4, draws=512, dim=8, m=8, seed=0,
) -> List[BenchResult]:
    """``bench.py microbench serving``: the posterior-as-a-service read
    plane's three ledgered legs — summary-cache QPS, batched predictive
    throughput at parity, and the eight-schools incremental-reconvergence
    drill.  Returns one `BenchResult` per leg (``read:summary`` /
    ``read:predict`` / ``read:reconverge`` ledger series).  Timed reads
    run with serve telemetry OFF so the measurement is the data plane,
    not the event emission."""
    from .serving import SERVE_TELEMETRY_ENV

    prev = os.environ.get(SERVE_TELEMETRY_ENV)
    os.environ[SERVE_TELEMETRY_ENV] = "0"
    try:
        return [
            _serving_summary_leg(tenants, chains, draws, dim, seed),
            _serving_predict_leg(min(tenants, 8), chains, draws, dim, m,
                                 seed),
            _serving_reconverge_leg(chains, seed),
        ]
    finally:
        if prev is None:
            os.environ.pop(SERVE_TELEMETRY_ENV, None)
        else:
            os.environ[SERVE_TELEMETRY_ENV] = prev


ALL_BENCHMARKS = {
    "eight_schools": bench_eight_schools,
    "hier_logistic": bench_hier_logistic,
    "consensus_logistic": bench_consensus_logistic,
    "lmm": bench_lmm,
    "gmm_tempered": bench_gmm_tempered,
    "bnn_sghmc": bench_bnn_sghmc,
    "fused_vg_lmm": lambda: bench_fused_value_and_grad("lmm"),
    "fused_vg_irt": lambda: bench_fused_value_and_grad("irt"),
    "fused_vg_ordinal": lambda: bench_fused_value_and_grad("ordinal"),
    "fused_vg_robust": lambda: bench_fused_value_and_grad("robust"),
    "nuts_sched": bench_nuts_sched,
}
