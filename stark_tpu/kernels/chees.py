"""ChEES-HMC — accelerator-first adaptive HMC (no trajectory trees).

Vmapped iterative NUTS pays the full 2^max_depth gradient budget for EVERY
chain at EVERY step (masked lanes still execute under vmap; the
step-synchronized scheduler in `kernels/nuts_ragged.py` —
STARK_RAGGED_NUTS — shrinks that to end-of-block straggler imbalance,
but a per-lane tree budget remains), and its tree-building control flow
is exactly what XLA dislikes.  ChEES-HMC
(Hoffman, Radul & Sountsov 2021 — PAPERS.md, pattern only) replaces the
tree with plain fixed-length trajectories whose length is ADAPTED
cross-chain by gradient ascent on the ChEES criterion

    ChEES = E[ ((||z' - mu||^2 - ||z - mu||^2) / 2)^2 ]

(the squared change in squared distance from the cross-chain mean — a
proxy for maximizing the decay of the slowest second-moment
autocorrelation), with per-step trajectory-length jitter for ergodicity.
The result: every chain runs the SAME number of leapfrog steps per
transition (static cost, perfect for vmap/MXU pipelining), and that
number is *learned* instead of being a worst-case tree budget.

This module is the per-ensemble transition; cross-chain reductions are
means over the leading chains axis — free inside one device, which is
where the ensemble usually lives (the chain-batched fused kernel makes
the marginal chain ~0.25 ms at C=64).  When the ensemble IS sharded over
a mesh axis (``chains_axis=``), every cross-chain reduction becomes the
matching XLA collective (pmean/psum/pmax over the axis) so the adapted
step size, trajectory length, and mass matrix stay bit-identical on
every device — the shard_map path in `backends/sharded.py`
(`ShardedBackend._run_chees`).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from .base import (
    HMCState,
    PotentialFn,
    kinetic_energy,
    leapfrog_step,
    sample_momentum,
    value_and_grad_of,
)

Array = jax.Array

_DIVERGENCE_THRESHOLD = 1000.0


class CheesInfo(NamedTuple):
    accept_prob: Array  # (C,)
    is_accepted: Array  # (C,)
    is_divergent: Array  # (C,)
    grad_rel_T: Array  # scalar — d(log ChEES)/dT (criterion-normalized)
    num_leapfrog: Array  # scalar int


def dynamic_leapfrog(
    potential_fn: PotentialFn,
    z: Array,
    r: Array,
    grad: Array,
    step_size: Array,
    inv_mass_diag: Array,
    num_steps: Array,
):
    """Velocity-Verlet with a TRACED step count (lax.fori_loop).

    The dynamic bound is the point: the learned trajectory length changes
    during warmup without recompiling, and every chain shares it (the
    ensemble transition is one fori_loop over vmapped chains).
    """

    def body(_, carry):
        z, r, grad, _ = carry
        return leapfrog_step(potential_fn, z, r, grad, step_size, inv_mass_diag)

    pe0 = jnp.zeros(z.shape[:-1], z.dtype)
    return jax.lax.fori_loop(0, num_steps, body, (z, r, grad, pe0))


def _cmean(x: Array, chains_axis):
    """Mean over the chain ensemble: local mean, pmean'd across the mesh
    axis when the ensemble is sharded (equal local counts per device)."""
    m = jnp.mean(x, axis=0)
    return jax.lax.pmean(m, chains_axis) if chains_axis else m


def _csum(x, chains_axis):
    from ..parallel.primitives import reduce_tree

    s = jnp.sum(x)
    return reduce_tree(s, chains_axis) if chains_axis else s


def _cmax(x, chains_axis):
    m = jnp.max(x)
    return jax.lax.pmax(m, chains_axis) if chains_axis else m


def chees_transition(
    key: Array,
    states: HMCState,  # leading axis (C,): the chain ensemble (local shard)
    potential_fn: PotentialFn,  # single-chain potential (vmapped here)
    step_size: Array,
    inv_mass_diag: Array,  # (d,)
    num_leapfrog: Array,  # traced scalar int — shared by all chains
    chains_axis=None,  # mesh axis name when the ensemble is sharded
    chain_args=(),  # per-chain operands: ``potential_fn(*one chain's)`` is
    # then that chain's potential (a centre a chain, `model.Centering`)
):
    """One ensemble transition; returns (states, CheesInfo).

    The ChEES gradient w.r.t. log T is estimated from the proposals'
    end-velocities (Hoffman et al. eq. 6), weighted by accept prob.
    With ``chains_axis`` set, cross-chain statistics are reduced with XLA
    collectives so every device derives identical adaptation signals.
    """
    C = states.z.shape[0]
    key_mom, key_acc = jax.random.split(key)
    # per-chain randomness is derived by folding the GLOBAL chain id, so a
    # chains-sharded ensemble draws exactly the momenta/uniforms the
    # unsharded ensemble would (sharded == unsharded transitions, up to
    # psum reassociation) — and distinct shards never clone each other
    if chains_axis is not None:
        offset = jax.lax.axis_index(chains_axis) * C
    else:
        offset = 0
    chain_ids = offset + jnp.arange(C)
    mom_keys = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
        key_mom, chain_ids
    )
    r0 = jax.vmap(sample_momentum, in_axes=(0, None))(mom_keys, inv_mass_diag)
    ke0 = jax.vmap(kinetic_energy, in_axes=(0, None))(r0, inv_mass_diag)
    energy0 = states.potential_energy + ke0

    def integrate(z, r, grad, *own):
        return dynamic_leapfrog(
            potential_fn(*own) if own else potential_fn,
            z, r, grad, step_size, inv_mass_diag, num_leapfrog,
        )

    z1, r1, grad1, pe1 = jax.vmap(integrate)(
        states.z, r0, states.grad, *chain_args
    )
    ke1 = jax.vmap(kinetic_energy, in_axes=(0, None))(r1, inv_mass_diag)
    energy1 = pe1 + ke1

    delta = energy1 - energy0
    delta = jnp.where(jnp.isnan(delta), jnp.inf, delta)
    is_divergent = delta > _DIVERGENCE_THRESHOLD
    accept_prob = jnp.minimum(1.0, jnp.exp(-delta))
    acc_u = jax.vmap(
        lambda i: jax.random.uniform(jax.random.fold_in(key_acc, i))
    )(chain_ids)
    accept = acc_u < accept_prob

    proposal = HMCState(z=z1, potential_energy=pe1, grad=grad1)
    new_states = jax.tree.map(
        lambda a, b: jnp.where(accept.reshape((C,) + (1,) * (a.ndim - 1)), a, b),
        proposal,
        states,
    )

    # --- ChEES gradient for T, criterion-normalized (cross-chain) ---
    # d ChEES/dT = E_w[half_gain * <z'-mu', v'>]; dividing by the criterion
    # value E_w[half_gain^2] gives d log(ChEES)/dT — a scale-free signal
    # (raw gradients span orders of magnitude across targets and warmup
    # phases, which starves Adam's normalizer; measured on hier-logistic:
    # raw gradient left T frozen, the relative form adapts in ~100 steps).
    mu0 = _cmean(states.z, chains_axis)
    mu1 = _cmean(z1, chains_axis)
    d0 = jnp.sum((states.z - mu0) ** 2, axis=-1)
    d1 = jnp.sum((z1 - mu1) ** 2, axis=-1)
    half_gain = 0.5 * (d1 - d0)  # (C,)
    v1 = r1 * inv_mass_diag[None, :]  # end velocity dz/dt
    dir_term = jnp.sum((z1 - mu1) * v1, axis=-1)  # (C,)
    w = jnp.where(jnp.isfinite(half_gain), accept_prob, 0.0)
    # the ratio below is invariant to rescaling half_gain and dir_term, so
    # normalize each by its ensemble max BEFORE squaring/summing: during
    # early warmup on peaked posteriors the raw squares overflow float32
    # (measured on the 1M-row flagship: crit -> inf, grad -> NaN, T
    # poisoned for the rest of the run)
    ch = jnp.maximum(
        _cmax(jnp.where(w > 0, jnp.abs(half_gain), 0.0), chains_axis), 1e-20
    )
    ct = jnp.maximum(
        _cmax(jnp.where(w > 0, jnp.abs(dir_term), 0.0), chains_axis), 1e-20
    )
    h = jnp.where(jnp.isfinite(half_gain), half_gain / ch, 0.0)
    t = jnp.where(jnp.isfinite(dir_term), dir_term / ct, 0.0)
    num = _csum(w * h * t, chains_axis)
    crit = _csum(w * h * h, chains_axis)
    grad_rel_T = jnp.where(
        crit > 1e-10, (num / jnp.maximum(crit, 1e-10)) * (ct / ch), 0.0
    )
    grad_rel_T = jnp.where(jnp.isfinite(grad_rel_T), grad_rel_T, 0.0)

    info = CheesInfo(
        accept_prob=jnp.where(jnp.isfinite(accept_prob), accept_prob, 0.0),
        is_accepted=accept,
        is_divergent=is_divergent,
        grad_rel_T=grad_rel_T,
        num_leapfrog=num_leapfrog,
    )
    return new_states, info


def init_ensemble(
    potential_fn: PotentialFn, z: Array, chain_args=()
) -> HMCState:
    """Init the (C, d) ensemble state with one vmapped potential+grad
    (``chain_args``: as `chees_transition`'s)."""
    def one(zc, *own):
        return value_and_grad_of(potential_fn(*own) if own else potential_fn)(zc)

    pe, grad = jax.vmap(one)(z, *chain_args)
    return HMCState(z=z, potential_energy=pe, grad=grad)


def halton(n: int, base: int = 2, start: int = 0):
    """Halton-sequence points ``start..start+n-1`` in (0,1) — the
    low-discrepancy trajectory jitter (host-side, feeds the scan).  The
    ``start`` offset lets a resumed/segmented run continue the SAME
    sequence instead of replaying it from the beginning."""
    import numpy as np

    out = np.zeros(n)
    for i in range(n):
        f, r, idx = 1.0, 0.0, start + i + 1
        while idx > 0:
            f /= base
            r += f * (idx % base)
            idx //= base
        out[i] = r
    return out
