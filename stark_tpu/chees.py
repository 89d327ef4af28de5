"""ChEES-HMC — cross-chain adaptive HMC without NUTS trees.

Why this exists (the TPU argument): vmapped iterative NUTS executes the
full 2^max_depth gradient budget for every chain at every transition —
masked lanes still run — so the per-draw cost is the worst case, always.
ChEES-HMC learns ONE trajectory length for the whole chain ensemble by
gradient ascent on the ChEES criterion (kernels/chees.py), runs plain
jittered fixed-length trajectories (static per-step cost, no tree control
flow), and uses the vectorized chains themselves as the adaptation signal
— the more chains the device runs, the better the adaptation, which is
exactly the axis TPUs scale.  Pattern: Hoffman, Radul & Sountsov 2021
(AISTATS), as deployed in tfp.mcmc — see PAPERS.md ("tfp.mcmc: Modern
MCMC Tools Built for Modern Hardware", "Running MCMC on Modern Hardware
and Software"); patterns only, no code reused.

Warmup (compiled `lax.scan` segments):
  * step size: dual averaging on the cross-chain mean accept (target 0.8)
  * trajectory length T: Adam ascent on log T with the per-step ChEES
    gradient (normalized by a second-moment EMA), jittered by a Halton
    sequence: L_t = ceil(u_t * T / eps), u_t in (0, 2)
  * diagonal mass: pooled cross-(chain x step) Welford over the second
    half of warmup, applied at window boundaries

Sampling runs with everything frozen except the Halton jitter (required
for ergodicity: any fixed L has nonergodic orbits on some targets).

Structure (the backend-plugin refactor): `make_chees_parts` builds the
ensemble-level pieces — init_carry / warm_segment / finalize /
sample_segment — with explicit carries, so every host driver composes
with them: `JaxBackend` serves `kernel="chees"` through the same
`SamplerBackend` boundary as NUTS/HMC, the adaptive runner checkpoints
the run carry between draw blocks (supervised restart included), and the
sharded mesh path wraps the same segments in `shard_map` with
``chains_axis`` turning cross-chain reductions into collectives.
`chees_sample` remains the one-call convenience driver.
"""

from __future__ import annotations

import os
from typing import Any, Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .adaptation import (
    DualAveragingState,
    WelfordState,
    build_warmup_schedule,
    da_init,
    da_update,
    welford_init,
    welford_variance,
)
from .backends.base import carried_potential, checkpoint_potential
from .kernels.base import HMCState, value_and_grad_of
from .kernels.chees import (
    _cmean,
    chees_transition,
    halton,
    init_ensemble,
)
from .model import Model, flatten_model, prepare_model_data
from .platform import named_jit
from .sampler import Posterior, SamplerConfig, _constrain_draws

#: the fixed names of the ensemble sampler's compiled programs (`named_jit`),
#: by the tag the backends build them under: what a trace reduction keys on.
#: The sampling program has one name with and without the streaming
#: diagnostics carry; a run compiles one of the two.
CHEES_PROGRAMS = {
    "init": "stark_chees_init",
    "warm": "stark_chees_warm",
    "samp": "stark_chees_sample",
    "samp_diag": "stark_chees_sample",
}


class AdamState(NamedTuple):
    m: jax.Array
    v: jax.Array
    t: jax.Array


def _adam_ascent(s: AdamState, grad, lr=0.025, b1=0.9, b2=0.95):
    t = s.t + 1
    m = b1 * s.m + (1.0 - b1) * grad
    v = b2 * s.v + (1.0 - b2) * grad * grad
    tf = t.astype(grad.dtype)
    mhat = m / (1.0 - b1**tf)
    vhat = v / (1.0 - b2**tf)
    step = lr * mhat / (jnp.sqrt(vhat) + 1e-8)
    return AdamState(m, v, t), step


def _welford_batch(w: WelfordState, xs: jax.Array, chains_axis=None) -> WelfordState:
    """Merge a (C, d) batch into the accumulator (Chan parallel combine).

    With ``chains_axis`` the batch spans the whole sharded ensemble: the
    batch mean is pmean'd and the within-batch M2 psum'd, so every device
    accumulates identical (global) statistics.
    """
    bc = xs.shape[0]
    bmean = jnp.mean(xs, axis=0)
    if chains_axis is not None:
        from .parallel.primitives import mapped_axis_size

        bc = bc * mapped_axis_size(chains_axis)
        bmean = jax.lax.pmean(bmean, chains_axis)
    bm2 = jnp.sum((xs - bmean[None, :]) ** 2, axis=0)
    if chains_axis is not None:
        from .parallel.primitives import reduce_tree

        bm2 = reduce_tree(bm2, chains_axis)
    na = w.count.astype(xs.dtype)
    nb = jnp.asarray(bc, xs.dtype)
    delta = bmean - w.mean
    tot = na + nb
    mean = w.mean + delta * nb / tot
    m2 = w.m2 + bm2 + delta * delta * na * nb / tot
    return WelfordState(w.count + bc, mean, m2)


def map_descent(potential_fn, z0, steps: int):
    """Descend each chain of ``z0`` (C, d) toward the mode with ``steps``
    of Adam on the potential, before warm-up (``map_init_steps``; the
    ensemble sampler's start and the per-chain kernels' alike): on peaked
    big-N posteriors a random unconstrained init is thousands of posterior
    sds from the mode and warm-up burns its whole budget descending; a few
    hundred fused-gradient Adam steps cost seconds and let warm-up adapt in
    the typical set.  Chains stay distinct (each descends its own init,
    stopping well short of collapse)."""
    vg_pot = jax.vmap(value_and_grad_of(potential_fn))

    def adam_body(carry, _):
        z, adam = carry
        _, g = vg_pot(z)
        g = jnp.where(jnp.isfinite(g), g, 0.0)
        adam, step = _adam_ascent(adam, -g, lr=0.05, b2=0.999)
        return (z + step, adam), None

    (z0, _), _ = jax.lax.scan(
        adam_body,
        (
            z0,
            AdamState(
                jnp.zeros_like(z0),
                jnp.zeros_like(z0),
                jnp.zeros((), jnp.int32),
            ),
        ),
        None,
        length=steps,
    )
    return z0


class CheesWarmCarry(NamedTuple):
    """Full warmup adaptation state — checkpointable between segments."""

    states: HMCState  # ensemble (C, d) (local shard when chains_axis set)
    da: DualAveragingState
    adam: AdamState
    log_T: jax.Array
    wf: WelfordState
    inv_mass: jax.Array
    # what ``states.potential_energy`` is summed relative to
    # (`model.Centering`: a replicated scalar, or a row a chain, laid out
    # like the states; potential = carried + its constant), None where the
    # potential is the plain sum: see ``recentre`` in `make_chees_parts`
    pe_center: Optional[jax.Array] = None


class CheesRunCarry(NamedTuple):
    """Frozen-adaptation sampling state — the per-block checkpoint unit."""

    states: HMCState
    log_eps: jax.Array
    log_T: jax.Array
    inv_mass: jax.Array
    pe_center: Optional[jax.Array] = None  # as `CheesWarmCarry.pe_center`


class CheesParts(NamedTuple):
    init_carry: Callable  # (key, z0, data) -> CheesWarmCarry
    warm_segment: Callable  # (carry, keys, us, idxs, aflags, wflags, data)
    finalize: Callable  # (CheesWarmCarry) -> CheesRunCarry
    sample_segment: Callable  # (carry, keys, us, data) -> (carry, outs)
    warm_cap: int
    schedule: Any  # WarmupSchedule for cfg.num_warmup
    # streaming-diagnostics variant (STARK_STREAM_DIAG): threads a
    # per-chain StreamDiagState batch through the scan —
    # (carry, diag, keys, us, data) -> (carry, diag, outs)
    sample_segment_diag: Optional[Callable] = None


def make_chees_parts(
    fm, cfg: SamplerConfig, *, chains_axis: Optional[str] = None
) -> CheesParts:
    """Ensemble-level ChEES building blocks with explicit carries.

    The host drives the warmup/sampling schedules in bounded slices
    (dispatch_steps) and may checkpoint any carry between slices; all
    functions take the data pytree as a runtime argument so jitted
    wrappers are reusable across same-shape datasets.  ``chains_axis``
    names the mesh axis the ensemble is sharded over (shard_map caller);
    cross-chain adaptation statistics then reduce with XLA collectives.
    """
    d = fm.ndim
    T0 = (
        cfg.init_traj_length
        if cfg.init_traj_length is not None
        else cfg.init_step_size
    )
    # Stan-style doubling windows (shared with the NUTS warmup): the metric
    # refreshes at EVERY window end, so eps recovers quickly as conditioning
    # improves and L = T/eps stays bounded.  T ascent starts after the
    # first metric refresh — adapting T against the un-whitened geometry
    # chases the condition number and blows trajectories to hundreds of
    # leapfrogs (measured 5x the whole run's wall-clock).
    sched = build_warmup_schedule(cfg.num_warmup)
    ends = np.flatnonzero(sched.window_end)
    t_start = int(ends[0]) + 1 if len(ends) else cfg.num_warmup // 4
    # cap warmup trajectories: pre-convergence T estimates are unreliable
    # and a single bad window must not cost max_leapfrog grads per draw.
    # 512 leaves headroom for stiff posteriors (the 1M-row flagship needs
    # L ~ 270; a 128 cap measured R-hat 8.8 where uncapped converged)
    warm_cap = min(cfg.max_leapfrog, 512)

    def num_steps(u, log_T, log_eps, cap):
        L = jnp.ceil(u * jnp.exp(log_T - log_eps)).astype(jnp.int32)
        return jnp.clip(L, 1, cap)

    def centred(data, pe_center):
        """The ensemble kernels' ``(potential_fn, chain_args)`` relative to
        a carry's ``pe_center``: one potential for all chains, or (a centre
        a chain) what makes a chain's potential of its row."""
        if pe_center is None or not fm.centering.per_chain:
            return fm.bind(data, pe_center), ()
        return (lambda row: fm.bind(data, row)), (pe_center,)

    def recentre(carry: CheesWarmCarry, data):
        """(potential, chain_args, carry) of a warm-up program.  A flat
        model that can centre (`model.Centering`) sums its potential
        relative to a constant held in the carry, so that the carried
        energies are small numbers and their differences keep float32's
        resolution at any number of rows.  Warm-up moves far (from where
        MAP stopped to the typical set), so each of its programs first
        moves the constant to the potential where the first chain now
        stands (where each chain stands, for a centre a chain) and
        evaluates the ensemble again relative to it: one gradient a
        program.  Sampling stays within tens of nats and keeps the constant
        warm-up's last program left (`_sample_scan`)."""
        if carry.pe_center is None:
            return fm.bind(data), (), carry
        st, cen = carry.states, fm.centering
        if cen.per_chain:
            pe_center = cen.at(st.z, st.potential_energy, carry.pe_center)
        else:
            # one constant for the whole ensemble: the mean of the chain
            # shards' first chains
            pe_center = carry.pe_center + _cmean(
                cen.at(st.z[:1], st.potential_energy[:1]), chains_axis
            )
        potential_fn, chain_args = centred(data, pe_center)
        return potential_fn, chain_args, carry._replace(
            states=init_ensemble(potential_fn, st.z, chain_args),
            pe_center=pe_center,
        )

    def init_carry(key, z0, data=None) -> CheesWarmCarry:
        potential_fn = fm.bind(data)
        if cfg.map_init_steps > 0:
            z0 = map_descent(potential_fn, z0, cfg.map_init_steps)
        centres = fm.centering is not None and data is not None
        carry = CheesWarmCarry(
            states=init_ensemble(potential_fn, z0),
            da=da_init(jnp.asarray(cfg.init_step_size)),
            adam=AdamState(
                jnp.zeros(()), jnp.zeros(()), jnp.zeros((), jnp.int32)
            ),
            log_T=jnp.log(jnp.asarray(T0)),
            wf=welford_init(d),
            inv_mass=jnp.ones((d,)),
            pe_center=fm.centering.zero(z0.shape[0]) if centres else None,
        )
        return recentre(carry, data)[2]

    def warm_body(potential_fn, chain_args):
        def body(carry: CheesWarmCarry, x):
            states, da, adam, log_T, wf, inv_mass, pe_center = carry
            key, u, idx, accum, at_window = x
            log_eps = da.log_step
            states, info = chees_transition(
                key, states, potential_fn, jnp.exp(log_eps), inv_mass,
                num_steps(u, log_T, log_eps, warm_cap),
                chains_axis=chains_axis, chain_args=chain_args,
            )
            da = da_update(
                da, _cmean(info.accept_prob, chains_axis), cfg.target_accept
            )
            # chain rule d/dlogT = T * d/dT on the criterion-relative grad
            adam, step = _adam_ascent(
                adam, info.grad_rel_T * jnp.exp(log_T), lr=0.05
            )
            new_log_T = jnp.where(idx >= t_start, log_T + step, log_T)
            # one non-finite step must not poison T for the rest of warmup
            log_T = jnp.where(jnp.isfinite(new_log_T), new_log_T, log_T)
            # keep T inside the regime warmup actually executes (warm_cap):
            # letting it ratchet past the executed length would let
            # sampling run lengths no warmup step ever validated.  idx < 0
            # marks an adaptation-import touch-up (runner.py): log_T is
            # fully frozen there — the clip's moving log_eps ceiling would
            # otherwise let a transient DA dip permanently shrink the
            # imported trajectory length with Adam frozen and unable to
            # restore it
            log_T = jnp.where(
                idx >= 0,
                jnp.clip(log_T, log_eps, log_eps + jnp.log(float(warm_cap))),
                log_T,
            )
            wf = jax.tree.map(
                lambda new, old: jnp.where(accum, new, old),
                _welford_batch(wf, states.z, chains_axis),
                wf,
            )
            # window end: apply pooled variance as the metric, restart the
            # accumulator and step-size averaging
            inv_mass = jnp.where(at_window, welford_variance(wf), inv_mass)
            wf = jax.tree.map(
                lambda w0, w: jnp.where(at_window, w0, w), welford_init(d), wf
            )
            da = jax.tree.map(
                lambda a, b: jnp.where(at_window, a, b),
                da_init(jnp.exp(da.log_step)),
                da,
            )
            return CheesWarmCarry(
                states, da, adam, log_T, wf, inv_mass, pe_center
            ), (info.is_divergent, info.num_leapfrog)

        return body

    def warm_segment(carry, keys, us, idxs, aflags, wflags, data=None):
        potential_fn, chain_args, carry = recentre(carry, data)
        carry, (div, nleap) = jax.lax.scan(
            warm_body(potential_fn, chain_args), carry,
            (keys, us, idxs, aflags, wflags),
        )
        n_div = jnp.sum(div.astype(jnp.int32))
        if chains_axis is not None:
            from .parallel.primitives import reduce_tree

            # global count: the host reads one replicated scalar
            n_div = reduce_tree(n_div, chains_axis)
        # nleap is the SHARED per-transition length (replicated across the
        # chains axis) — summed so the host can see where the warmup
        # gradient budget goes (the flagship wall is warmup-dominated)
        n_grad = jnp.sum(nleap)
        if carry.pe_center is not None:
            n_grad = n_grad + 1  # `recentre`'s evaluation of the ensemble
        return carry, (n_div, n_grad)

    def finalize(carry: CheesWarmCarry) -> CheesRunCarry:
        return CheesRunCarry(
            states=carry.states,
            log_eps=carry.da.log_avg_step,
            log_T=carry.log_T,
            inv_mass=carry.inv_mass,
            pe_center=carry.pe_center,
        )

    # telemetry opt-in (cfg.progress_every): jit-safe in-loop heartbeat
    # inside the compiled sampling scan; None (default) leaves the
    # compiled program identical to the untraced build
    from .kernels.base import scan_progress

    def _sample_scan(carry: CheesRunCarry, diag, keys, us, data):
        """The ONE sampling scan body serving both segment variants —
        ``diag=None`` (resolved at trace time) compiles the historical
        plain segment; a `kernels.base.StreamDiagState` batch (leading
        chains axis — the local shard under ``chains_axis``) is updated
        from every accepted ensemble position otherwise.  One body so the
        transitions cannot drift between the variants: the accumulator
        only CONSUMES states.z, so draws match bit-for-bit either way."""
        from .kernels.base import stream_diag_update

        potential_fn, chain_args = centred(data, carry.pe_center)
        # built at trace time so the interval clamps to THIS segment's
        # length (keys.shape is static per compiled variant): an interval
        # longer than one dispatch still heartbeats once per segment
        tick = scan_progress(
            "chees_sample",
            min(cfg.progress_every, keys.shape[0])
            if cfg.progress_every and keys.shape[0]
            else None,
        )

        def body(cd, x):
            c, dg = cd
            # x gains a leading segment-local index under the heartbeat
            (i, key, u) = x if tick is not None else (None,) + x
            # cap at warm_cap, not max_leapfrog: with the u in (0,2)
            # jitter a larger cap would let sampling run trajectory
            # lengths warmup never executed
            states, info = chees_transition(
                key, c.states, potential_fn, jnp.exp(c.log_eps), c.inv_mass,
                num_steps(u, c.log_T, c.log_eps, warm_cap),
                chains_axis=chains_axis, chain_args=chain_args,
            )
            if tick is not None:
                tick(i, jnp.mean(info.accept_prob))
            if dg is not None:
                dg = jax.vmap(stream_diag_update)(dg, states.z)
            out = (
                states.z,
                info.accept_prob,
                info.is_divergent,
                info.num_leapfrog,
            )
            return (c._replace(states=states), dg), out

        xs = (
            (jnp.arange(keys.shape[0]), keys, us)
            if tick is not None
            else (keys, us)
        )
        return jax.lax.scan(body, (carry, diag), xs)

    def sample_segment(carry: CheesRunCarry, keys, us, data=None):
        (carry, _), outs = _sample_scan(carry, None, keys, us, data)
        return carry, outs

    def sample_segment_diag(carry: CheesRunCarry, diag, keys, us, data=None):
        """`sample_segment` + the on-device streaming-diagnostics carry
        (see `_sample_scan`)."""
        (carry, diag), outs = _sample_scan(carry, diag, keys, us, data)
        return carry, diag, outs

    return CheesParts(
        init_carry=init_carry,
        warm_segment=warm_segment,
        finalize=finalize,
        sample_segment=sample_segment,
        warm_cap=warm_cap,
        schedule=sched,
        sample_segment_diag=sample_segment_diag,
    )


def chees_schedule_arrays(parts: CheesParts, cfg: SamplerConfig):
    """Host-side per-step scan inputs shared by every chees driver:
    (aflags, wflags, u_warm, u_run, idxs).  One builder so the schedule
    slicing/Halton conventions cannot drift between drivers."""
    sched = parts.schedule
    total = cfg.num_samples * cfg.thin
    return (
        jnp.asarray(np.asarray(sched.adapt_mass)),
        jnp.asarray(np.asarray(sched.window_end)),
        jnp.asarray(2.0 * halton(cfg.num_warmup), jnp.float32),
        jnp.asarray(2.0 * halton(total), jnp.float32),
        jnp.arange(cfg.num_warmup),
    )


def chees_segments(dispatch_steps: Optional[int], n: int):
    """[(lo, hi)) dispatch slices covering n steps; validates the bound."""
    if dispatch_steps is not None and dispatch_steps < 0:
        raise ValueError(
            f"dispatch_steps must be >= 0, got {dispatch_steps}"
        )
    seg = dispatch_steps if dispatch_steps else max(n, 1)
    return [(s, min(s + seg, n)) for s in range(0, n, seg)]


def chees_init_positions(fm, key, chains, init_params=None):
    """Shared ensemble init: random typical-set draws, or a jittered
    user-provided point (identical chains have zero cross-chain variance,
    which zeroes the ChEES criterion until momentum noise spreads them)."""
    if init_params is not None:
        z0 = jnp.broadcast_to(fm.unconstrain(init_params), (chains, fm.ndim))
        return z0 + 0.1 * jax.random.normal(key, (chains, fm.ndim))
    return jax.vmap(fm.init_flat)(jax.random.split(key, chains))


def drive_chees_segments(
    parts: CheesParts,
    fm,
    cfg: SamplerConfig,
    *,
    chains: int,
    seed: int,
    init_params,
    dispatch_steps: Optional[int],
    init_j,
    warm_j,
    samp_j,
    extra: tuple,
    put_z0=lambda x: x,
    put_aux=lambda x: x,
    collect=lambda out: jax.tree.map(np.asarray, out),
) -> Posterior:
    """The ONE host-side schedule driver over chees parts.

    Both the single-device path (`run_chees`) and the mesh path
    (`ShardedBackend._run_chees`) drive the same warmup/sampling schedule
    through this function — only placement (`put_z0`/`put_aux`), the
    jitted/shard_mapped segment callables, the trailing data args
    (``extra``), and draw collection (``collect``; allgather on pods)
    differ — so the two paths cannot drift.
    """
    key = jax.random.PRNGKey(seed)
    key, key_init, key_warm, key_run = jax.random.split(key, 4)
    z0 = put_z0(chees_init_positions(fm, key_init, chains, init_params))

    total = cfg.num_samples * cfg.thin
    aflags, wflags, u_warm, u_run, idxs = (
        put_aux(a) for a in chees_schedule_arrays(parts, cfg)
    )
    warm_keys = put_aux(jax.random.split(key_warm, max(cfg.num_warmup, 1)))
    run_keys = put_aux(jax.random.split(key_run, max(total, 1)))

    segments = lambda n: chees_segments(dispatch_steps, n)

    carry = jax.block_until_ready(init_j(key_init, z0, *extra))
    wdiv_total = 0
    wleap_total = 0
    for lo, hi in segments(cfg.num_warmup):
        carry, (wdiv, wleap) = jax.block_until_ready(
            warm_j(
                carry,
                warm_keys[lo:hi],
                u_warm[lo:hi],
                idxs[lo:hi],
                aflags[lo:hi],
                wflags[lo:hi],
                *extra,
            )
        )
        wdiv_total += int(np.asarray(wdiv))
        wleap_total += int(np.asarray(wleap))
    run_carry = parts.finalize(carry)

    outs = []
    for lo, hi in segments(total):
        run_carry, out = jax.block_until_ready(
            samp_j(run_carry, run_keys[lo:hi], u_run[lo:hi], *extra)
        )
        outs.append(collect(out))
    return assemble_chees_posterior(
        fm, cfg, chains, outs, run_carry, wdiv_total, wleap_total
    )


def run_chees(
    fm,
    cfg: SamplerConfig,
    data=None,
    *,
    chains: int,
    seed: int = 0,
    init_params: Optional[Dict[str, Any]] = None,
    dispatch_steps: Optional[int] = None,
    jit_cache: Optional[Dict[Any, Any]] = None,
    device: Optional[Any] = None,
) -> Posterior:
    """Single-device chees path (JaxBackend): jitted parts + shared driver.

    dispatch_steps: when set, warmup and sampling scans are issued as
    bounded device programs of at most this many transitions (runtimes
    that kill long executions — same mechanism as JaxBackend's segmented
    NUTS/HMC path).  jit_cache: backend-owned dict so repeated runs reuse
    compiled segments.  device: pins the run (committed inputs steer jit
    placement), honoring JaxBackend(device=...).
    """
    parts = make_chees_parts(fm, cfg)
    cache = jit_cache if jit_cache is not None else {}

    def put(x):
        return jax.device_put(x, device) if device is not None else x

    def cached(tag, builder):
        if tag not in cache:
            cache[tag] = builder()
        return cache[tag]

    return drive_chees_segments(
        parts,
        fm,
        cfg,
        chains=chains,
        seed=seed,
        init_params=init_params,
        dispatch_steps=dispatch_steps,
        init_j=cached("chees_init", lambda: named_jit(
            parts.init_carry, CHEES_PROGRAMS["init"])),
        warm_j=cached("chees_warm", lambda: named_jit(
            parts.warm_segment, CHEES_PROGRAMS["warm"])),
        samp_j=cached("chees_sample", lambda: named_jit(
            parts.sample_segment, CHEES_PROGRAMS["samp"])),
        extra=(data,),
        put_z0=put,
        put_aux=put,
    )


def assemble_chees_posterior(
    fm,
    cfg: SamplerConfig,
    chains: int,
    outs,
    run_carry,
    wdiv_total: int,
    wleap_total: int,
) -> Posterior:
    """Build the Posterior from collected segment outputs (numpy tuples of
    (zs, accept, divergent, nleap) stacked step-major) — shared by the
    single-device and sharded drivers."""
    if outs:
        zs, acc, div, nleap = (
            np.concatenate([o[i] for o in outs], axis=0) for i in range(4)
        )
    else:  # warmup-only run (num_samples=0), like the segmented NUTS path
        zs = np.zeros((0, chains, fm.ndim), np.float32)
        acc = np.zeros((0, chains), np.float32)
        div = np.zeros((0, chains), bool)
        nleap = np.zeros((0,), np.int32)
    # divergence count covers ALL transitions (repo convention), thinned-out
    # included; the kept-draw arrays are thinned below
    num_divergent = int(div.sum())
    total_leapfrog = int(nleap.sum())  # over ALL transitions, pre-thinning
    if cfg.thin > 1:
        zs = zs[cfg.thin - 1 :: cfg.thin]
        acc = acc[cfg.thin - 1 :: cfg.thin]
        div = div[cfg.thin - 1 :: cfg.thin]
    zs = np.swapaxes(zs, 0, 1)  # (chains, draws, d)
    draws = _constrain_draws(fm, zs)
    log_eps = float(np.asarray(run_carry.log_eps))
    stats = {
        "accept_prob": acc.T,
        "is_divergent": div.T,
        # post-warmup only (repo-wide convention); warmup count separate —
        # warmup divergences are routine while eps is still adapting
        "num_divergent": np.asarray(num_divergent),
        "num_warmup_divergent": np.asarray(wdiv_total),
        # the leapfrog count is the SHARED per-transition length; the
        # ensemble total is chains x that, matching the per-chain arrays
        # HMC/NUTS report (cross-sampler grad budgets apples-to-apples)
        "num_grad_evals": np.asarray(total_leapfrog * chains),
        # warmup budget accounting: where the (dominant) warmup wall goes —
        # warm-transition leapfrogs plus the MAP warm-start descent
        # (map_init_steps Adam steps, one fused gradient each, per chain)
        "num_warmup_grad_evals": np.asarray(
            (wleap_total + cfg.map_init_steps) * chains
        ),
        "step_size": np.full((chains,), float(np.exp(log_eps))),
        "traj_length": np.asarray(np.exp(np.asarray(run_carry.log_T))),
        "inv_mass": np.asarray(run_carry.inv_mass),
    }
    return Posterior(draws, stats, flat_model=fm, draws_flat=zs)


def chees_sample(
    model: Model,
    data: Any = None,
    *,
    chains: int = 16,
    num_warmup: int = 500,
    num_samples: int = 1000,
    init_step_size: float = 0.1,
    init_traj_length: Optional[float] = None,
    max_leapfrog: int = 1000,
    target_accept: float = 0.8,
    dispatch_steps: Optional[int] = None,
    map_init_steps: int = 0,
    seed: int = 0,
    init_params: Optional[Dict[str, Any]] = None,
) -> Posterior:
    """One-call ChEES-HMC; returns a Posterior (same surface as `sample`).

    chains: ChEES adapts from the ensemble — 16+ chains recommended (the
    chains are vmapped on one device; they are cheap on a TPU).
    Equivalent to ``sample(model, data, kernel="chees", ...)`` through the
    default JaxBackend; kept as the direct driver for scripts/benchmarks.
    """
    cfg = SamplerConfig(
        kernel="chees",
        num_warmup=num_warmup,
        num_samples=num_samples,
        init_step_size=init_step_size,
        init_traj_length=init_traj_length,
        max_leapfrog=max_leapfrog,
        target_accept=target_accept,
        map_init_steps=map_init_steps,
    )
    data = prepare_model_data(model, data)
    fm = flatten_model(model)
    return run_chees(
        fm,
        cfg,
        data,
        chains=chains,
        seed=seed,
        init_params=init_params,
        dispatch_steps=dispatch_steps,
    )


# ---------------------------------------------------------------------------
# the adaptive runner's kernel seam (`backends.base.BlockKernel`)
# ---------------------------------------------------------------------------

_ADAPT_KEYS = ("z", "log_eps", "log_T", "inv_mass")

#: a mid-warm-up checkpoint's replicated arrays ``<part>_<field>``, by the
#: `CheesWarmCarry` part they hold
_WARM_PARTS = {
    "da": (DualAveragingState,
           ("log_step", "log_avg_step", "h_avg", "mu", "count")),
    "adam": (AdamState, ("m", "v", "t")),
    "wf": (WelfordState, ("count", "mean", "m2")),
}


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


class CheesBlockKernel:
    """`backends.base.BlockKernel` for the ensemble sampler: blocks advance
    the whole ensemble through sample segments (frozen adaptation) from a
    `CheesRunCarry`; warm-up runs in ``block_size`` segments, each
    checkpointed as the full `CheesWarmCarry`.  The carries, their files
    and the potential's centre are known here and not by the block loop."""

    def __init__(self, ap, cfg: SamplerConfig, chains: int, env):
        self.ap, self.cfg, self.chains, self.env = ap, cfg, chains, env
        # a backend without the streaming segment runs the host gate
        self.stream_diag = env.stream_diag and ap.samp_diag is not None
        # the diag carry may be donated only where a block's accumulators are
        # read back BEFORE the next block is dispatched: the serial loop
        self._samp_diag_j = (
            ap.samp_diag(donate=env.sync_blocks) if self.stream_diag else None)
        # the carries' `model.Centering`, None where they hold no centre
        self._centering = ap.fm.centering if ap.data is not None else None
        self.carry: Optional[CheesRunCarry] = None
        self.step_size = None

    @property
    def dtype(self):
        return np.dtype(self.carry.states.z.dtype)


    def start(self):
        from . import telemetry

        ap, cfg, env, chains = self.ap, self.cfg, self.env, self.chains
        # a span, no phase event: the run's keys and the start positions
        with telemetry.span("compile", stage="chain_init"):
            key = jax.random.PRNGKey(env.seed)
            key, key_init, key_warm = jax.random.split(key, 3)
            imported = self._load_adapt_import()
            # an imported adaptation starts AT the saved typical-set positions
            # and a short touch-up replaces the warm-up (runner: adapt_path)
            z0 = ap.put_chains(
                jnp.asarray(imported["z"]) if imported is not None
                else chees_init_positions(
                    ap.fm, key_init, chains, env.init_params)
            )
        # init dispatch = first compile + MAP descent; the inner span is the
        # descent, its compile counters say how much of it was compilation
        with env.trace.phase("compile", stage="init+map",
                             map_init_steps=cfg.map_init_steps):
            with telemetry.span("map_init", steps=cfg.map_init_steps,
                                grad_evals=cfg.map_init_steps * chains):
                carry = telemetry.wait(ap.init_j(key_init, z0, *ap.extra))
        warm_span = telemetry.span("warmup", steps=cfg.num_warmup).open()
        if imported is not None:
            pr = ap.put_rep
            ls = jnp.asarray(imported["log_eps"])
            # DA anchored AT the imported step (mu = log_eps): Stan's
            # log(10*eps) exploration prior is for cold starts and pulled a
            # tuned eps 2.7x up during an 80-transition touch-up
            carry = carry._replace(
                da=jax.tree.map(pr, da_init(jnp.exp(ls), mu=ls)),
                log_T=pr(jnp.asarray(imported["log_T"])),
                inv_mass=pr(jnp.asarray(imported["inv_mass"])),
            )
        n_div, n_leap = self._warmup(
            carry, 0, key, key_warm, 0, 0, touchup=imported is not None)
        warm_span.close(grad_evals=int(n_leap) * chains)
        if env.adapt_export_path and imported is None:
            # the reuse cache is filled from a FULL warmup only: an import
            # leaves the artifact byte-identical (a judged capture must not
            # dirty committed artifacts, and the touch-up's slightly re-tuned
            # eps would trade provenance for noise)
            self._save_adapt()
        elif env.adapt_export_path:
            env.emit({"event": "adapt_export_skipped", "reason": "imported"})
        fields = self._warm_fields(n_leap)
        if imported is not None:
            fields["adapt_imported"] = True
        return key, n_div, fields

    def _warm_fields(self, n_leap):
        # gradient evaluations spent before sampling: the MAP descent (one
        # fused gradient per Adam step per chain) + the warm leapfrogs
        return {"warmup_grad_evals":
                int((n_leap + self.cfg.map_init_steps) * self.chains)}

    def _warmup(self, carry, start, key, key_warm, n_div, n_leap,
                touchup=False):
        """Drive warm-up segments of ``block_size`` from ``start`` and
        leave the finalized run carry in place; -> (divergences,
        leapfrogs).  The full warm-up checkpoints after every segment but
        the last, so a fault resumes at the last finished segment instead
        of burning the whole (dominant) warm-up budget again.

        ``touchup`` is the short re-equilibration of an imported
        adaptation state (``adapt_path``): ONLY the step size re-tunes (DA,
        anchored at the imported value).  Mass windows are OFF (zero
        flags) and the trajectory-length Adam is OFF (indices below its
        t_start gate): both estimates come from a full previous warmup,
        and a short window would only degrade them — measured: a fresh
        Adam re-adapting the imported log_T walked trajectories from ~100
        to ~288 leapfrogs in 80 touch-up transitions (N=20k fallback
        replica), tripling every later block's cost."""
        from . import telemetry

        ap, cfg, env = self.ap, self.cfg, self.env
        sched = ap.chees.schedule
        aflags = np.asarray(sched.adapt_mass)
        wflags = np.asarray(sched.window_end)
        if touchup:
            n = max(20, int(cfg.num_warmup * env.adapt_touchup_frac))
            us = jnp.asarray(2.0 * halton(n), jnp.float32)
            wkeys = jax.random.split(key_warm, n)
            aflags = jnp.zeros((n,), aflags.dtype)
            wflags = jnp.zeros((n,), wflags.dtype)
            idxs = jnp.full((n,), -1, jnp.int32)  # < t_start: log_T frozen
            stage = {"stage": "touchup"}
        else:
            n = cfg.num_warmup
            aflags, wflags = jnp.asarray(aflags), jnp.asarray(wflags)
            us = jnp.asarray(2.0 * halton(n), jnp.float32)
            wkeys = jax.random.split(key_warm, max(n, 1))
            idxs = jnp.arange(n)
            stage = {}
        for s in range(start, n, env.block_size):
            e = min(s + env.block_size, n)
            with env.trace.phase(
                    "warmup_block", start=s, end=e, **stage) as ph:
                carry, (nd, nl) = telemetry.wait(ap.warm_j(
                    carry, wkeys[s:e], us[s:e], idxs[s:e],
                    aflags[s:e], wflags[s:e], *ap.extra,
                ))
                if env.trace.enabled:
                    ph.note(num_divergent=int(nd), leapfrogs=int(nl))
            telemetry.notify_progress()  # watchdog liveness beat
            n_div += int(nd)
            n_leap += int(nl)
            if env.checkpoint_path and not touchup and e < n:
                # the final segment's state is captured by the first
                # sample-phase checkpoint
                self._save_warmup_checkpoint(
                    carry, key, key_warm, e, n_div, n_leap)
        self.carry = ap.chees.finalize(carry)
        self.step_size = jnp.exp(self.carry.log_eps)
        return n_div, n_leap

    def warm_checkpoint_arrays(self, carry: CheesWarmCarry, key, key_warm):
        """A mid-warm-up checkpoint's arrays: the full `CheesWarmCarry`,
        position / grad / step / mass under the sampling phase's names so
        `checkpoint_is_healthy`'s finite check covers them alike."""
        named = {
            "z": carry.states.z,
            "pe": carry.states.potential_energy,
            "grad": carry.states.grad,
            "inv_mass": carry.inv_mass,
            "log_T": carry.log_T,
            "pe_center": carry.pe_center,
        }
        for part, (_, fields) in _WARM_PARTS.items():
            for f in fields:
                named[f"{part}_{f}"] = getattr(getattr(carry, part), f)
        # ap.collect (gather_draws on a mesh): np.asarray alone cannot read
        # non-addressable shards on multi-process meshes
        arrays = checkpoint_potential(self.ap.collect(named), self._centering)
        arrays["step_size"] = np.exp(arrays["da_log_step"])
        # PRNG keys are host-side driver state, never mesh-sharded
        arrays["key"] = np.asarray(key)
        arrays["key_warm"] = np.asarray(key_warm)
        return arrays

    def _save_warmup_checkpoint(self, carry, key, key_warm, done, nd, nl):
        from . import telemetry
        from .checkpoint import save_checkpoint

        env = self.env
        ckpt_span = telemetry.span("block.checkpoint", stage="warmup").open()
        arrays = self.warm_checkpoint_arrays(carry, key, key_warm)
        if env.health_check:
            # a poisoned adaptation carry must never land on disk
            # (the load-side check in supervise covers old files)
            from .supervise import check_finite_state

            check_finite_state(arrays)
        save_checkpoint(env.checkpoint_path, arrays, {
            "kernel": self.cfg.kernel, "phase": "warmup", "warm_done": done,
            "warm_div": nd, "warm_leap": nl, "model": env.model_name,
        })
        ckpt_span.close()
        if env.trace.enabled:
            env.trace.emit(
                "checkpoint", stage="warmup", warm_done=done,
                path=env.checkpoint_path, dur_s=round(ckpt_span.seconds, 4),
            )


    def _load_adapt_import(self):
        """Validated adaptation import, or None (missing/mismatched file —
        a mismatch is logged, never fatal: the run falls back to a full
        warmup)."""
        env, chains = self.env, self.chains
        arrays, reason = load_adapt_state(
            env.adapt_path, kernel="chees", model_name=env.model_name,
            ndim=self.ap.fm.ndim, data_fp=env.adapt_fp,
        )
        if arrays is None:
            if reason is not None:
                env.emit({"event": "adapt_import_rejected", "reason": reason})
            return None
        z = np.asarray(arrays["z"])
        if z.shape[0] >= chains:
            z = z[:chains]
        else:
            # more chains than saved: tile the typical-set points
            reps = -(-chains // z.shape[0])
            z = np.tile(z, (reps, 1))[:chains]
        # overdispersed warm starts: jitter the saved points (one a chain) by
        # half the cross-chain spread, so imported starts stay overdispersed
        # and tiled duplicates separate (zero cross-chain variance would zero
        # the ChEES criterion); zero-spread dims fall back to 0.05 absolute
        sd = z.std(axis=0)
        sd = np.where(sd > 0, sd, 0.05).astype(z.dtype)
        z = z + 0.5 * sd * np.random.default_rng(
            env.seed
        ).standard_normal(z.shape).astype(z.dtype)
        return {"z": z, **{k: np.asarray(arrays[k]) for k in _ADAPT_KEYS[1:]}}

    def _save_adapt(self):
        """Persist the tuned adaptation + end-of-warmup positions for
        reuse by later runs (atomic, same npz machinery as checkpoints).
        A poisoned state is never exported — a NaN import artifact would
        sabotage every later run."""
        from .checkpoint import save_checkpoint

        env, run_carry = self.env, self.carry
        leaves = {
            "z": np.asarray(self.ap.collect(run_carry.states.z)),
            "log_eps": np.asarray(run_carry.log_eps),
            "log_T": np.asarray(run_carry.log_T),
            "inv_mass": np.asarray(run_carry.inv_mass),
        }
        if not all(np.all(np.isfinite(a)) for a in leaves.values()):
            env.emit({"event": "adapt_export_skipped",
                      "reason": "non-finite warmup state"})
            return
        save_checkpoint(env.adapt_export_path, leaves, {
            "kernel": self.cfg.kernel, "model": env.model_name,
            "num_warmup": self.cfg.num_warmup, "data_fp": env.adapt_fp,
        })


    def _placed(self, arrays):
        """-> (states, rep, pe_center) of a checkpoint's host arrays,
        re-placed on the backend's layout: the ensemble's state over the
        chains, ``rep(name)`` for its shared adaptation, replicated."""
        pc, pr = self.ap.put_chains, self.ap.put_rep
        pe, pe_center = carried_potential(arrays, self._centering)
        if pe_center is not None:
            # a row a chain lies where the chains' states lie
            put = pc if self._centering.per_chain else pr
            pe_center = put(jnp.asarray(pe_center))
        states = HMCState(*(
            pc(jnp.asarray(a)) for a in (arrays["z"], pe, arrays["grad"])
        ))
        return states, lambda name: pr(jnp.asarray(arrays[name])), pe_center

    def warm_carry_from(self, arrays) -> CheesWarmCarry:
        """A mid-warm-up checkpoint's arrays as the carry they were."""
        states, rep, pe_center = self._placed(arrays)
        return CheesWarmCarry(
            states=states, log_T=rep("log_T"), inv_mass=rep("inv_mass"),
            pe_center=pe_center,
            **{part: cls(*(rep(f"{part}_{f}") for f in fields))
               for part, (cls, fields) in _WARM_PARTS.items()},
        )

    def restore(self, arrays, meta, reseed):
        from . import telemetry
        from .backends.base import restored_key

        if meta.get("kernel") is None:
            # legacy checkpoints (pre-kernel field) were only ever written
            # by the per-chain kernels; they lack the chees carry arrays
            raise ValueError(
                "checkpoint has no kernel record (pre-chees format); "
                "cannot resume it with kernel='chees'"
            )
        self.chains = chains = arrays["z"].shape[0]
        key = restored_key(arrays, "key", reseed)
        if meta.get("phase") != "warmup":
            states, rep, pe_center = self._placed(arrays)
            self.step_size = rep("step_size")
            self.carry = CheesRunCarry(
                states, rep("log_eps"), rep("log_T"), rep("inv_mass"),
                pe_center,
            )
            return key, None, None
        # mid-warmup checkpoint: rebuild the full adaptation carry and
        # finish the remaining warmup segments before sampling
        done = int(meta["warm_done"])
        with telemetry.span(
            "warmup", steps=self.cfg.num_warmup - done
        ) as warm_span:
            n_div, n_leap = self._warmup(
                self.warm_carry_from(arrays), done, key,
                restored_key(arrays, "key_warm", reseed),
                int(meta.get("warm_div", 0)), int(meta.get("warm_leap", 0)),
            )
            warm_span.note(grad_evals=int(n_leap) * chains)
        return key, n_div, {
            **self._warm_fields(n_leap), "resumed_from_step": done}


    def dispatch(self, key_block, length, diag, first_draw):
        from . import faults
        from .backends.base import PendingBlock, carried_state

        ap, carry = self.ap, self.carry
        # the Halton jitter continues the global sequence (draws dispatched
        # so far): a resumed, blocked or pipelined run walks the SAME stream
        us = jnp.asarray(2.0 * halton(length, start=first_draw), jnp.float32)
        bkeys = jax.random.split(key_block, length)
        if self.stream_diag:
            carry, diag, outs = self._samp_diag_j(
                carry, diag, bkeys, us, *ap.extra)
        else:
            carry, outs = ap.samp_j(carry, bkeys, us, *ap.extra)
        self.carry = carry
        # failpoint: NaN-poison the carried state where a real numerical fault
        # would surface (health_check catches it before block k's checkpoint;
        # without the check it lands on disk: the quarantine path)
        st = faults.poison("runner.carried_nan", carry.states)
        self.step_size = jnp.exp(carry.log_eps)
        return PendingBlock(
            length, outs, diag,
            carried_state(st, self.step_size, carry.inv_mass),
            extras=carry._replace(states=None, inv_mass=None),
        )

    def host_block(self, pending, energy=False):
        from .backends.base import HostBlock

        zs_dm, accept, divergent, n_leap = pending.outs
        # n_leap is the SHARED per-transition trajectory length (replicated):
        # the ensemble's gradient count is chains x that
        zs_dm, accept, divergent = self.ap.collect((zs_dm, accept, divergent))
        # the device block is draw-major (block, chains, d): keep it for
        # the draw store and give host diagnostics a free transposed VIEW
        zs_dm, accept = np.asarray(zs_dm), np.asarray(accept)
        return HostBlock(
            zs=zs_dm.transpose(1, 0, 2), zs_dm=zs_dm,
            accept=accept.T, divergent=np.asarray(divergent).T,
            mean_accept=float(np.mean(accept)),
            grad_evals=int(np.sum(np.asarray(n_leap))) * self.chains,
        )

    def checkpoint_arrays(self, pending):
        extras = pending.extras  # the run carry's replicated scalars
        arrays = checkpoint_potential(self.ap.collect(
            {**pending.carried, "pe_center": extras.pe_center}
        ), self._centering)
        # the host key AS OF this block's dispatch: the pipeline may have split
        # further, but a resume from THIS file replays block k+1 from here
        arrays["key"] = np.asarray(pending.key)
        arrays["log_eps"] = np.asarray(extras.log_eps)
        arrays["log_T"] = np.asarray(extras.log_T)
        return arrays
