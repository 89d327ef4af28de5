"""Parallel tempering (replica exchange) — benchmark config 4 capability.

K likelihood-tempered replicas per chain, target_k(z) ∝ prior(z)·lik(z)^β_k
with β_0 = 1 > β_1 > ... > β_{K-1}; each replica advances with HMC/NUTS and
adjacent replicas propose state swaps every ``swap_every`` steps with the
standard exchange acceptance  log A = (β_k − β_j)(ll_j − ll_k).

TPU-native layout (SURVEY.md §3 "Temperature parallelism"): the K replicas
of a chain are a vmapped axis *within* the device program — a swap is a
K-length gather, not communication — and chains shard over the mesh "chains"
axis like every other sampler here.  This is the mesh-axis folding the
survey prescribes; there is no per-swap host round-trip and no cross-device
traffic for swaps at all.

Replica state caches (ll, ll_grad, prior_pe, prior_grad) at the current
position so both the swap acceptance and the post-swap kernel state
(pe = prior_pe − β·ll, grad likewise) are recomputation-free; caches are
refreshed once per transition (≪ the leapfrog cost of the transition).

Reference parity: capability from BASELINE.json:10 ("Gaussian mixture K=16
with reparameterized HMC + parallel tempering"); reference tree absent
(SURVEY.md §0), design original.
"""

from __future__ import annotations

import time
from functools import partial
from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from .. import telemetry
from .primitives import map_shards, run_over_chains
from ..adaptation import da_init, da_update
from ..kernels.base import HMCState
from ..kernels.hmc import hmc_step
from ..kernels.nuts import nuts_step
from ..model import Model, flatten_model, prepare_model_data
from ..sampler import Posterior, _constrain_draws

Array = jax.Array


class ReplicaState(NamedTuple):
    """Stacked over the K-temperature axis (leading dim K)."""

    z: Array  # (K, d)
    prior_pe: Array  # (K,)  -(log_prior + fldj)
    prior_grad: Array  # (K, d)
    ll: Array  # (K,) log-likelihood at z
    ll_grad: Array  # (K, d)


def geometric_ladder(num_temps: int, beta_min: float = 0.05) -> jnp.ndarray:
    """β_0=1 ... β_{K-1}=beta_min, geometrically spaced."""
    if num_temps == 1:
        return jnp.ones((1,))
    return jnp.asarray(
        np.geomspace(1.0, beta_min, num_temps), jnp.float32
    )


def _betas_from_rho(rho: Array, t0: float = 1.0) -> Array:
    """Ladder from log-gap parameters: T_k = T_0 + Σ_{j≤k} e^{ρ_j}, β = 1/T.

    β_0 is pinned at 1/T_0 (the caller's cold temperature — the target
    posterior — no matter what adaptation does); every gap stays strictly
    positive, so the ladder is always monotone decreasing.
    """
    temps = t0 + jnp.concatenate([jnp.zeros((1,)), jnp.cumsum(jnp.exp(rho))])
    return 1.0 / temps


def tempered_sample(
    model: Model,
    data,
    *,
    chains: int = 2,
    num_temps: int = 8,
    betas: Optional[jnp.ndarray] = None,
    kernel: str = "hmc",
    num_leapfrog: int = 16,
    max_tree_depth: int = 6,
    num_warmup: int = 500,
    num_samples: int = 1000,
    swap_every: int = 5,
    target_accept: float = 0.8,
    init_step_size: float = 0.1,
    seed: int = 0,
    mesh: Optional[Mesh] = None,
    init_params: Optional[Dict[str, Any]] = None,
    adapt_ladder: bool = False,
    target_swap: float = 0.35,
    ladder_adapt_rate: float = 0.4,
) -> Posterior:
    """Run parallel-tempered MCMC; returns the β=1 replica's Posterior.

    Step sizes adapt per temperature with dual averaging during warmup
    (hot replicas want larger steps).  ``sample_stats["swap_accept_rate"]``
    reports the realized adjacent-swap acceptance per chain, and
    ``sample_stats["swap_accept_per_pair"]`` the per-rung rates — the
    evidence that the ladder is doing statistical work, not decoration.

    ``adapt_ladder=True`` turns on ΔE-matched spacing: during warmup each
    chain runs Robbins–Monro on its log-temperature-gaps ρ (β from
    ``_betas_from_rho``), nudging every adjacent pair's expected swap
    acceptance toward ``target_swap`` — pairs that never swap pull closer,
    pairs that always swap push apart, so the ladder spends its K replicas
    exactly where the energy gaps are (the fix for the measured
    Δβ·ΔE ≫ 1 dead ladder at N=50k, DESIGN.md §4b).  The ladder freezes at
    the end of warmup; the cold rung stays pinned at β=1 throughout, so
    adaptation never biases the returned posterior.
    """
    if data is None:
        raise ValueError("tempering requires a data likelihood to temper")
    data = prepare_model_data(model, data)
    fm = flatten_model(model)
    betas = geometric_ladder(num_temps) if betas is None else jnp.asarray(betas)
    num_temps = betas.shape[0]
    if num_temps > 1 and not bool(jnp.all(jnp.diff(betas) < 0)):
        # a non-monotone ladder would NaN-poison the adaptive
        # parameterization (log of a negative gap) and is wrong for the
        # fixed ladder too — fail loudly, not with NaN draws
        raise ValueError(
            f"betas must be strictly decreasing from the cold chain; got "
            f"{np.asarray(betas)}"
        )

    def prior_pot(z):
        return fm.potential(z, None)

    def loglik(z):
        return model.log_lik(fm.constrain(z), data)

    vag_prior = jax.value_and_grad(prior_pot)
    vag_ll = jax.value_and_grad(loglik)

    def refresh(z):
        ppe, pgr = vag_prior(z)
        ll, llg = vag_ll(z)
        return ppe, pgr, ll, llg

    if kernel == "nuts":
        kstep = partial(nuts_step, max_depth=max_tree_depth)
    elif kernel == "hmc":
        kstep = partial(hmc_step, num_leapfrog=num_leapfrog)
    else:
        raise ValueError(f"unknown kernel {kernel!r}")

    def one_replica_step(key, z, ppe, pgr, ll, llg, beta, step_size):
        pot = lambda zz: prior_pot(zz) - beta * loglik(zz)
        st = HMCState(z=z, potential_energy=ppe - beta * ll, grad=pgr - beta * llg)
        st, info = kstep(key, st, potential_fn=pot, step_size=step_size,
                         inv_mass_diag=jnp.ones_like(z))
        ppe, pgr, ll, llg = refresh(st.z)
        return (st.z, ppe, pgr, ll, llg), info

    v_step = jax.vmap(one_replica_step, in_axes=(0, 0, 0, 0, 0, 0, 0, 0))

    num_gaps = num_temps - 1
    gaps_idx = jnp.arange(num_gaps)  # empty when num_temps == 1: no swaps

    def swap(key, rs: ReplicaState, bs, parity):
        """Even-odd adjacent exchange, gap-centric.

        Gap g joins replicas g (colder) and g+1 (hotter); gaps of one parity
        are active per round, so accepted swaps never overlap.  Returns the
        permuted state plus per-gap (accepted, active, accept_prob) — the
        accept_prob drives ladder adaptation, the booleans the swap-rate
        accounting.
        """
        active = (gaps_idx % 2) == (parity % 2)
        delta = (bs[:-1] - bs[1:]) * (rs.ll[1:] - rs.ll[:-1])
        u = jax.random.uniform(key, (num_gaps,))
        accept = active & (jnp.log(u) < delta)
        # accepted gaps are non-adjacent by parity, so the swaps commute
        swap_up = jnp.concatenate([accept, jnp.zeros((1,), bool)])
        swap_dn = jnp.concatenate([jnp.zeros((1,), bool), accept])
        k = jnp.arange(num_temps)
        perm = jnp.where(swap_up, k + 1, jnp.where(swap_dn, k - 1, k))
        new = ReplicaState(*[x[perm] for x in rs])
        acc_prob = jnp.where(active, jnp.minimum(1.0, jnp.exp(delta)), 0.0)
        return new, accept, active, acc_prob

    swap_flags = np.zeros(num_warmup + num_samples, bool)
    if swap_every > 0:
        swap_flags[swap_every - 1 :: swap_every] = True
    parities = np.cumsum(swap_flags) % 2  # alternate parity across swap rounds
    swap_rounds = np.cumsum(swap_flags)  # 1-based round number, for RM decay
    is_warm = np.arange(num_warmup + num_samples) < num_warmup
    cold_t0 = float(1.0 / betas[0])  # adaptation pins β_0 at the caller's value
    rho0 = (
        jnp.log(jnp.diff(1.0 / betas)) if num_gaps > 0 else jnp.zeros((0,))
    )

    def run_chain(key, z0):
        ppe, pgr, ll, llg = jax.vmap(refresh)(z0)
        rs = ReplicaState(z0, ppe, pgr, ll, llg)
        da = jax.vmap(da_init)(jnp.full((num_temps,), init_step_size))

        def body(carry, x):
            rs, da, rho = carry
            key, do_swap, parity, rnd, warm = x
            bs = _betas_from_rho(rho, cold_t0) if adapt_ladder else betas
            key_step, key_swap = jax.random.split(key)
            step_size = jnp.where(warm, jnp.exp(da.log_step), jnp.exp(da.log_avg_step))
            keys = jax.random.split(key_step, num_temps)
            (z, ppe, pgr, ll, llg), info = v_step(
                keys, rs.z, rs.prior_pe, rs.prior_grad, rs.ll, rs.ll_grad,
                bs, step_size,
            )
            rs = ReplicaState(z, ppe, pgr, ll, llg)
            da_new = jax.vmap(lambda d, a: da_update(d, a, target_accept))(
                da, info.accept_prob
            )
            da = jax.tree.map(lambda a, b: jnp.where(warm, a, b), da_new, da)
            swapped, accept, active, acc_prob = swap(key_swap, rs, bs, parity)
            rs = jax.tree.map(
                lambda a, b: jnp.where(do_swap, a, b), swapped, rs
            )
            if adapt_ladder and num_gaps > 0:
                # Robbins–Monro toward target_swap on active gaps: a pair
                # accepting too rarely pulls its temperatures together, too
                # eagerly pushes them apart (ΔE-matched spacing)
                gamma = ladder_adapt_rate / (1.0 + rnd) ** 0.6
                # a non-finite acc_prob (e.g. inf-inf lls out of support)
                # must reject one swap, not poison the ladder forever
                rho_new = rho + gamma * jnp.where(
                    active & jnp.isfinite(acc_prob), acc_prob - target_swap, 0.0
                )
                rho = jnp.where(warm & do_swap, rho_new, rho)
            acc_i = (accept & do_swap).astype(jnp.int32)
            pairs_i = (active & do_swap).astype(jnp.int32)
            out = (rs.z[0], info.is_divergent[0], acc_i, pairs_i)
            return (rs, da, rho), out

        total = num_warmup + num_samples
        keys = jax.random.split(key, total)
        xs = (
            keys,
            jnp.asarray(swap_flags),
            jnp.asarray(parities, jnp.int32),
            jnp.asarray(swap_rounds, jnp.float32),
            jnp.asarray(is_warm),
        )
        (rs, da, rho), (z_cold, div, acc_g, pairs_g) = jax.lax.scan(
            body, (rs, da, rho0), xs
        )
        zs = z_cold[num_warmup:]
        n_div = jnp.sum(div[num_warmup:].astype(jnp.int32))
        # swap-rate accounting over the SAMPLING phase only — the warmup
        # ladder is still moving, its rates aren't evidence of anything
        acc_sum = jnp.sum(acc_g[num_warmup:], axis=0)
        pairs_sum = jnp.sum(pairs_g[num_warmup:], axis=0)
        rate_per_pair = acc_sum / jnp.maximum(pairs_sum, 1)
        swap_rate = jnp.sum(acc_sum) / jnp.maximum(jnp.sum(pairs_sum), 1)
        betas_final = _betas_from_rho(rho, cold_t0) if adapt_ladder else betas
        return (
            zs, n_div, swap_rate, rate_per_pair, betas_final,
            jnp.exp(da.log_avg_step),
        )

    trace = telemetry.get_trace().tagged(component="tempering")
    t_run0 = time.perf_counter()
    if trace.enabled:
        trace.emit(
            "run_start",
            entry="tempered",
            model=type(model).__name__,
            kernel=kernel,
            chains=chains,
            num_temps=num_temps,
            swap_every=swap_every,
            adapt_ladder=adapt_ladder,
            **telemetry.device_info(),
            **telemetry.provenance(),
        )
    key = jax.random.PRNGKey(seed)
    key_init, key_run = jax.random.split(key)
    if init_params is not None:
        z0 = jnp.broadcast_to(
            fm.unconstrain(init_params), (chains, num_temps, fm.ndim)
        )
    else:
        z0 = jax.vmap(jax.vmap(fm.init_flat))(
            jax.random.split(key_init, chains * num_temps).reshape(
                chains, num_temps, 2
            )
        )
    chain_keys = jax.random.split(key_run, chains)

    vrun = jax.vmap(run_chain)
    # the whole K-replica ladder runs as ONE device program (a swap is a
    # gather, not communication) — one sample_block phase covers it
    # failpoint: fault the ladder dispatch (crash/preempt/sleep) — the
    # whole-run program has no retry below the caller, so this is the
    # site that drills caller-level supervision of tempered runs
    from ..faults import fail_point

    fail_point("tempering.dispatch")
    with trace.phase(
        "sample_block", includes_warmup=True, includes_compile=True,
        transitions=num_warmup + num_samples, replicas=chains * num_temps,
    ):
        if mesh is None:
            out = jax.block_until_ready(
                map_shards(vrun)(chain_keys, z0)
            )
        else:
            out = run_over_chains(mesh, vrun, chain_keys, z0)

    zs, n_div, swap_rate, rate_per_pair, betas_final, step_sizes = out
    if trace.enabled:
        # per-replica health (replica = temperature rung), tagged with the
        # rung index: a frozen hot rung or a dead swap pair is visible per
        # rung, not averaged away
        bf = np.asarray(betas_final)
        bf = bf if bf.ndim == 2 else np.broadcast_to(bf, (chains, num_temps))
        ss_np = np.asarray(step_sizes)
        rp = np.asarray(rate_per_pair)
        for k in range(num_temps):
            fields = {
                "step_size": round(float(np.mean(ss_np[:, k])), 6),
                "beta": round(float(np.mean(bf[:, k])), 5),
            }
            if k < num_temps - 1 and rp.size:
                # swap rate of the (k, k+1) gap this rung COLDER-ends
                fields["swap_accept_pair"] = round(float(np.mean(rp[:, k])), 4)
            trace.tagged(replica=k).emit("chain_health", **fields)
        trace.emit(
            "chain_health",
            num_divergent=int(np.sum(np.asarray(n_div))),
            swap_accept_rate=round(float(np.mean(np.asarray(swap_rate))), 4),
        )
    with trace.phase("collect"):
        draws = _constrain_draws(fm, zs)
    stats = {
        "num_divergent": np.asarray(n_div),
        "swap_accept_rate": np.asarray(swap_rate),
        "swap_accept_per_pair": np.asarray(rate_per_pair),
        "step_size_per_temp": np.asarray(step_sizes),
        # 'betas' keeps the r2 semantics — the INPUT ladder, shape (K,) —
        # so external consumers keying on it are unaffected by ladder
        # adaptation (ADVICE r3).  The adapted, possibly per-chain final
        # ladder is exposed separately as 'betas_adapted' (chains, K).
        "betas": np.asarray(betas),
        "betas_init": np.asarray(betas),
        "betas_adapted": np.asarray(betas_final),
    }
    if trace.enabled:
        trace.emit(
            "run_end",
            dur_s=round(time.perf_counter() - t_run0, 4),
            num_divergent=int(np.sum(np.asarray(n_div))),
        )
    return Posterior(draws, stats, flat_model=fm, draws_flat=np.asarray(zs))
