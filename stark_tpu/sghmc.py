"""SG-HMC sampler frontend (benchmark config 5, BASELINE.json:11).

Runs vectorized parallel chains of the friction SG-HMC kernel
(`kernels.sghmc`) with a static-shape minibatch gradient estimator.  The
whole warmup+sample run is one compiled program per chain (`lax.scan`),
chains vectorized with `vmap` and optionally spread over a mesh "chains"
axis with `shard_map` — no host round-trips inside the loop, matching the
target stack in SURVEY.md §4.

SG-HMC has no accept statistic, so there is no dual-averaging warmup; the
"warmup" here is a discarded burn-in run at the same step size.  During
burn-in a diagonal RMSprop-style preconditioner is adapted from the
stochastic gradients (grad**2 EMA — the scale-adapted SG-HMC pattern,
Springenberg et al. 2016; PAPERS.md — pattern only) and then FROZEN for
the sampling phase, so the sampled dynamics leave the target invariant
with a fixed mass matrix.  Neural-net posteriors mix orders of magnitude
faster under this equilibration (per-parameter curvature in a BNN spans
the 1/sqrt(fan_in) prior scales).
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from .kernels.sghmc import SGHMCState, make_minibatch_grad, sghmc_init, sghmc_step
from .model import Model, flatten_model, prepare_model_data
from .sampler import Posterior, _constrain_draws


def sghmc_sample(
    model: Model,
    data,
    *,
    batch_size: int,
    chains: int = 4,
    num_warmup: int = 500,
    num_samples: int = 1000,
    thin: int = 1,
    step_size: float = 1e-3,
    friction: float = 1.0,
    resample_every: int = 50,
    precondition: bool = True,
    precond_beta: float = 0.99,
    precond_damping: float = 1e-8,
    precond_clip: float = 100.0,
    cycles: int = 0,
    cycle_collect_frac: float = 0.3,
    seed: int = 0,
    mesh: Optional[Mesh] = None,
    init_params: Optional[Dict[str, Any]] = None,
) -> Posterior:
    """Run parallel-chain SG-HMC and return a Posterior.

    Rows may live on any per-leaf axis declared by ``model.data_row_axes``
    (axis 0 by default); the likelihood term is scaled by N/batch_size so
    the stochastic gradient is unbiased for the full-data potential.

    precondition: adapt a diagonal mass matrix from the grad**2 EMA ``v``
    during burn-in, frozen for sampling (per-chain).  Both the curvature
    signal and the minibatch-noise variance of the stochastic gradient
    scale per-coordinate as 1/posterior_sd**2, so the *ratios* of ``v``
    track inverse posterior variances; the absolute scale of ``v`` is in
    gradient units and is discarded by median-normalizing:
    ``M^{-1} = median(v)/v`` — the median coordinate keeps exactly the
    unit-mass dynamics (so ``step_size`` keeps its meaning, and d=1
    models are untouched) while badly-scaled coordinates equilibrate.

    cycles: when > 0, run cyclical SG-MCMC over the sampling phase (Zhang
    et al. 2020 pattern — PAPERS.md, pattern only): the step size follows
    ``step_size * (cos(pi * t_cyc / T) + 1)`` warm-restart cycles with a
    fresh momentum draw at each cycle start; high-step phases hop between
    posterior modes (the multimodality of e.g. BNN posteriors that a
    constant-step chain cannot cross), and draws are collected only in the
    final ``cycle_collect_frac`` of each cycle where the step is small.
    The returned Posterior holds the collected draws (num_samples*thin
    steps are run; roughly cycle_collect_frac of them are kept).
    """
    data = prepare_model_data(model, data)
    row_axes = model.data_row_axes(data)
    # first leaf with a real row axis (negative = row-less sentinel leaf)
    n = next(
        x.shape[ax]
        for x, ax in zip(jax.tree.leaves(data), jax.tree.leaves(row_axes))
        if ax >= 0
    )
    if batch_size > n:
        raise ValueError(f"batch_size={batch_size} > rows={n}")
    fm = flatten_model(model, lik_scale=n / batch_size)
    grad_fn = make_minibatch_grad(fm.potential, data, batch_size, row_axes=row_axes)

    total_sample = num_samples * thin
    # host-precomputed momentum-refresh schedule, fed to the scans as xs
    steps = np.arange(num_warmup + total_sample)
    flags = (
        (steps % max(resample_every, 1) == 0)
        if resample_every
        else np.zeros(num_warmup + total_sample, bool)
    )
    warm_flags = jnp.asarray(flags[:num_warmup])
    sample_flags = np.asarray(flags[num_warmup:])
    if cycles > 0:
        # cosine warm-restart schedule over the sampling phase; fresh
        # momentum at each cycle start; collect in the low-step tail
        t_period = max(total_sample // cycles, 1)
        phase = (np.arange(total_sample) % t_period) / t_period
        eps_mult = np.cos(np.pi * phase) + 1.0
        collect_mask = phase >= 1.0 - cycle_collect_frac
        if not collect_mask.any():
            raise ValueError(
                f"cycles={cycles} over {total_sample} sampling steps gives "
                f"{t_period}-step cycles whose last {cycle_collect_frac:.0%} "
                "contains no step — nothing would be collected; use fewer "
                "cycles or more samples"
            )
        sample_flags = sample_flags | (phase == 0.0)
    else:
        eps_mult = np.ones(total_sample)
        collect_mask = np.ones(total_sample, bool)
    eps_mult = jnp.asarray(eps_mult, jnp.float32)
    sample_flags = jnp.asarray(sample_flags)
    keep = jnp.asarray(np.flatnonzero(collect_mask)[thin - 1 :: thin])

    def inv_mass_from(v):
        # ratios of v ~ inverse posterior variances; median-normalize so
        # the typical coordinate keeps unit-mass dynamics.  The clip bounds
        # how far any coordinate's dynamics may be rescaled: an extreme
        # inv_mass inflates the per-step gradient-noise injection by the
        # same factor and outruns the friction (the SG-HMC stability
        # condition), so equilibration is deliberately conservative.
        v_hat = v / jnp.maximum(jnp.median(v), precond_damping)
        return jnp.clip(
            1.0 / jnp.maximum(v_hat, precond_damping),
            1.0 / precond_clip,
            precond_clip,
        )

    def run_chain(key, z0):
        key_init, key_warm, key_mom, key_scan = jax.random.split(key, 4)
        eps = jnp.asarray(step_size, z0.dtype)
        fric = jnp.asarray(friction, z0.dtype)
        unit_mass = jnp.ones_like(z0)
        state = sghmc_init(key_init, z0, unit_mass)

        # --- burn-in: adapt the preconditioner from the gradient stream ---
        def warm_body(carry, x):
            state, v = carry
            key, refresh = x
            inv_mass = inv_mass_from(v) if precondition else unit_mass
            state, info, grad = sghmc_step(
                key, state, grad_fn, eps, fric, inv_mass,
                resample_momentum=refresh,
            )
            v = jnp.where(
                jnp.isfinite(grad).all(),
                precond_beta * v + (1.0 - precond_beta) * grad * grad,
                v,
            )
            return (state, v), info.is_divergent

        v0 = jnp.ones_like(z0)
        (state, v), warm_div = jax.lax.scan(
            warm_body,
            (state, v0),
            (jax.random.split(key_warm, num_warmup), warm_flags),
        )
        inv_mass = inv_mass_from(v) if precondition else unit_mass
        # momentum was carried under the moving mass; re-draw it under the
        # frozen one so the sampling dynamics start in equilibrium
        state = sghmc_init(key_mom, state.z, inv_mass)

        # --- sampling: fixed preconditioner, target left invariant ---
        def body(state, x):
            key, refresh, mult = x
            state, info, _ = sghmc_step(
                key, state, grad_fn, eps * mult, fric, inv_mass,
                resample_momentum=refresh,
            )
            return state, (state.z, info.kinetic_energy, info.is_divergent)

        keys = jax.random.split(key_scan, total_sample)
        state, (zs, ke, div) = jax.lax.scan(
            body, state, (keys, sample_flags, eps_mult)
        )
        # keep is host-static: select collect-phase (cyclic), thinned draws
        # inside the jit so only kept draws cross device->host
        zs = jnp.take(zs, keep, axis=0)
        ke = jnp.take(ke, keep, axis=0)
        # sampling-phase divergences separately from the combined total:
        # the stats dict keeps the historical combined count, while the
        # health trail (like NUTS/HMC's) judges POST-WARMUP transitions
        # only — warmup divergences while the preconditioner tunes are
        # expected, not a warning
        n_div_sample = jnp.sum(div.astype(jnp.int32))
        n_div = n_div_sample + jnp.sum(warm_div.astype(jnp.int32))
        return zs, ke, n_div, n_div_sample

    key = jax.random.PRNGKey(seed)
    key_init, key_run = jax.random.split(key)
    if init_params is not None:
        z0 = jnp.broadcast_to(fm.unconstrain(init_params), (chains, fm.ndim))
    else:
        z0 = jax.vmap(fm.init_flat)(jax.random.split(key_init, chains))
    chain_keys = jax.random.split(key_run, chains)

    vrun = jax.vmap(run_chain)
    if mesh is None:
        zs, ke, n_div, n_div_sample = jax.block_until_ready(
            jax.jit(vrun)(chain_keys, z0)
        )
    else:
        from .parallel.primitives import run_over_chains

        zs, ke, n_div, n_div_sample = run_over_chains(
            mesh, vrun, chain_keys, z0
        )

    zs = np.asarray(zs)
    ke = np.asarray(ke)
    draws = _constrain_draws(fm, zs)
    stats = {
        "kinetic_energy": np.asarray(ke),
        "num_divergent": np.asarray(n_div),
        "step_size": np.full((chains,), step_size),
    }
    # statistical-health trail (stark_tpu.health): the kernel always
    # computed these arrays — wire them into the trace bus so the SG-HMC
    # BNN leg carries the same chain-health evidence as NUTS/HMC.
    # Gated on STARK_HEALTH so =0 keeps traces byte-identical.
    from . import health as _health, telemetry

    if _health.health_enabled():
        # POST-WARMUP divergences only, like the NUTS/HMC trail (the
        # stats dict above keeps the historical combined count)
        _health.sghmc_health_trail(
            telemetry.get_trace(),
            kinetic_energy=ke,
            num_divergent=n_div_sample,
            transitions=chains * total_sample,
        )
    if cycles > 0:
        # which warm-restart cycle each kept draw came from — the
        # per-cycle mode-coverage evidence for multimodal posteriors
        # (BNN config 5): draws from different cycles landing in
        # different modes is the cyclical schedule doing its job, and is
        # exactly what weight-space R-hat misreads as non-convergence
        stats["cycle_id"] = np.asarray(keep) // max(total_sample // cycles, 1)
    return Posterior(draws, stats, flat_model=fm, draws_flat=np.asarray(zs))
