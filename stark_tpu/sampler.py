"""Sampler frontend: chain orchestration, warmup, draw collection.

The `Sampler`-equivalent layer (SURVEY.md §2 layer B / §3 "Sampler frontend").
The whole warmup-and-sample loop for a chain is ONE compiled function
(``lax.scan`` over steps); chains are vectorized with ``vmap``.  Control
crosses host<->device once per run (or once per draw block in the adaptive
runner), never per gradient evaluation — the structural fix for the
reference's per-step driver round-trip (SURVEY.md §4).
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import time
from functools import partial
from typing import Any, Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import diagnostics, telemetry
from . import profile as _profile
from .adaptation import (
    build_warmup_schedule,
    da_init,
    da_update,
    find_reasonable_step_size,
    welford_init,
    welford_update,
    welford_variance,
)
from .kernels.base import (
    CentredState,
    HMCState,
    chain_potential,
    chain_recentred,
    init_state,
)
from .kernels.hmc import hmc_step
from .kernels.nuts import nuts_step
from .model import FlatModel, Model, Potential, flatten_model
from .platform import named_jit

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class SamplerConfig:
    kernel: str = "nuts"  # "nuts" | "hmc" | "chees"
    num_warmup: int = 1000
    num_samples: int = 1000
    thin: int = 1
    target_accept: float = 0.8
    max_tree_depth: int = 10
    num_leapfrog: int = 32  # hmc only
    init_step_size: float = 1.0
    adapt_step_size: bool = True
    adapt_mass: bool = True
    # chees only (ensemble sampler — served by the backends via
    # `chees.make_chees_parts`, not by the per-chain vmapped runner):
    init_traj_length: Optional[float] = None
    max_leapfrog: int = 1000
    # Adam steps toward the mode from every chain's start, before warm-up
    # (`chees.map_descent`): the ensemble sampler's start, and the
    # per-chain kernels' under the adaptive runner (`ChainBlockKernel`)
    map_init_steps: int = 0
    # telemetry opt-in: emit a jit-safe in-loop heartbeat (device -> host
    # via jax.debug.callback) every N transitions inside the compiled
    # sampling scans.  None (default) leaves the compiled programs
    # bit-identical to the untraced build — the hot path pays nothing.
    progress_every: Optional[int] = None


def _tree_select(flag, a, b):
    return jax.tree.map(lambda x, y: jnp.where(flag, x, y), a, b)


def make_kernel(cfg: SamplerConfig) -> Callable:
    """Returns step(key, state, potential_fn=, step_size=, inv_mass_diag=)."""
    if cfg.kernel == "nuts":
        return partial(nuts_step, max_depth=cfg.max_tree_depth)
    if cfg.kernel == "hmc":
        return partial(hmc_step, num_leapfrog=cfg.num_leapfrog)
    if cfg.kernel == "chees":
        raise ValueError(
            "chees is an ensemble kernel with its own warmup; backends route "
            "it through chees.make_chees_parts, not the per-chain runner"
        )
    raise ValueError(f"unknown kernel {cfg.kernel!r}")


class ChainResult(NamedTuple):
    draws: Array  # (num_samples, d) flat unconstrained
    accept_prob: Array
    is_divergent: Array
    energy: Array
    num_grad_evals: Array
    step_size: Array
    inv_mass_diag: Array
    num_warmup_divergent: Array
    num_divergent: Array  # over ALL post-warmup transitions (pre-thinning)
    final_state: HMCState
    suff_count: Array  # streaming Welford over sample draws
    suff_mean: Array
    suff_m2: Array


def _make_warmup_body(cfg: SamplerConfig, kernel):
    """The per-transition warmup update shared by the one-dispatch warmup
    and the dispatch-bounded segment runner — one implementation so the two
    paths cannot drift."""

    def body(carry, x):
        state, da, welford, inv_mass = carry
        d = state.z.shape[0]
        dtype = state.z.dtype
        key, adapt_mass_f, window_end_f = x
        step_size = (
            jnp.exp(da.log_step)
            if cfg.adapt_step_size
            else jnp.asarray(cfg.init_step_size, dtype)
        )
        state, info = kernel(key, state, step_size=step_size, inv_mass_diag=inv_mass)
        if cfg.adapt_step_size:
            da = da_update(da, info.accept_prob, cfg.target_accept)
        if cfg.adapt_mass:
            welford = _tree_select(
                adapt_mass_f, welford_update(welford, state.z), welford
            )
            new_mass = welford_variance(welford)
            refresh = window_end_f & (welford.count > 1)
            inv_mass = jnp.where(refresh, new_mass, inv_mass)
            welford = _tree_select(window_end_f, welford_init(d, dtype), welford)
            if cfg.adapt_step_size:
                da = _tree_select(
                    window_end_f, da_init(jnp.exp(da.log_step)), da
                )
        return (state, da, welford, inv_mass), (
            info.is_divergent, info.num_grad_evals)

    return body


def _warmup_carry_init(cfg: SamplerConfig, potential_fn, key, state: HMCState):
    d = state.z.shape[0]
    dtype = state.z.dtype
    inv_mass = jnp.ones((d,), dtype)
    if cfg.adapt_step_size:
        step0 = find_reasonable_step_size(
            potential_fn,
            state.z,
            state.potential_energy,
            state.grad,
            inv_mass,
            key,
            cfg.init_step_size,
        )
    else:
        step0 = jnp.asarray(cfg.init_step_size, dtype)
    return state, da_init(step0), welford_init(d, dtype), inv_mass


def make_warmup_fn(fm: FlatModel, cfg: SamplerConfig):
    """Build warmup(key, state, potential_fn, kernel) ->
    (state, step_size, inv_mass, n_divergent) — the windowed Stan-style
    adaptation loop as one `lax.scan`."""
    schedule = build_warmup_schedule(cfg.num_warmup)
    adapt_mass_flags = jnp.asarray(schedule.adapt_mass)
    window_end_flags = jnp.asarray(schedule.window_end)

    def warmup(key, state: HMCState, potential_fn, kernel):
        dtype = state.z.dtype
        key_find, key_scan = jax.random.split(key)
        carry = _warmup_carry_init(cfg, potential_fn, key_find, state)
        if cfg.num_warmup > 0:
            keys = jax.random.split(key_scan, cfg.num_warmup)
            carry, (divergent, _) = jax.lax.scan(
                _make_warmup_body(cfg, kernel),
                carry,
                (keys, adapt_mass_flags, window_end_flags),
            )
            n_div = jnp.sum(divergent.astype(jnp.int32))
        else:
            n_div = jnp.zeros((), jnp.int32)
        state, da, _, inv_mass = carry
        step_size = (
            jnp.exp(da.log_avg_step)
            if cfg.adapt_step_size
            else jnp.asarray(cfg.init_step_size, dtype)
        )
        return state, step_size, inv_mass, n_div

    return warmup


def make_warmup_parts(fm: FlatModel, cfg: SamplerConfig):
    """Dispatch-bounded warmup: (init_carry, segment, finalize).

    Identical math to ``make_warmup_fn`` (same shared body), but the host
    drives the schedule in bounded slices, carrying the full adaptation
    state (chain state, dual-averaging, Welford, mass) between dispatches.
    Needed for checkpointable warmup and progress beats between
    dispatches.

      init_carry(key, z0, data) -> (state, da, welford, inv_mass)
      segment(keys, adapt_flags, wend_flags, state, da, welford, inv_mass,
              data) -> (state, da, welford, inv_mass, n_div, n_grad,
                        ngrad)
      finalize(da) -> step_size            (host-side, cheap)

    ``ngrad`` is the gradient count of each transition of the segment
    (its trees' leaves under NUTS), int32 (transitions,) a chain: what
    `tree_counters` reads.

    ``state`` is what the chain carries (`chain_potential`): an `HMCState`,
    or for a model that can centre a `CentredState`, whose centre every
    segment retakes where the chain stands (`chain_recentred`; the gradient
    that costs is in ``n_grad``).  Slice
    ``build_warmup_schedule(cfg.num_warmup)`` flags to feed segments.
    """
    step_kernel = make_kernel(cfg)

    def init_carry(key, z0, data=None):
        state = init_state(fm.bind(data), z0)
        cen = getattr(fm, "chain_centering", None)
        if cen is not None and data is not None:
            # the plain evaluation places the centre; `chain_recentred`
            # evaluates again relative to it
            state = chain_recentred(
                fm, data, CentredState(state, cen.zero(1)[0]))
        potential_fn, state, rewrap = chain_potential(fm, data, state)
        state, da, welford, inv_mass = _warmup_carry_init(
            cfg, potential_fn, key, state)
        return rewrap(state), da, welford, inv_mass

    def segment(keys, adapt_flags, wend_flags, state, da, welford, inv_mass,
                data=None):
        carried = chain_recentred(fm, data, state)
        potential_fn, state, rewrap = chain_potential(fm, data, carried)
        kernel = partial(step_kernel, potential_fn=potential_fn)
        (state, da, welford, inv_mass), (divergent, ngrad) = jax.lax.scan(
            _make_warmup_body(cfg, kernel),
            (state, da, welford, inv_mass),
            (keys, adapt_flags, wend_flags),
        )
        # `chain_recentred`'s evaluation, where there is a centre to move
        n_grad = jnp.sum(ngrad) + int(isinstance(carried, CentredState))
        return (rewrap(state), da, welford, inv_mass,
                jnp.sum(divergent.astype(jnp.int32)), n_grad,
                ngrad.astype(jnp.int32))

    def finalize(da):
        if cfg.adapt_step_size:
            return jnp.exp(da.log_avg_step)
        return jnp.full_like(jnp.asarray(da.log_avg_step), cfg.init_step_size)

    return init_carry, segment, finalize


def drive_segmented_warmup(cfg, v_init, v_seg, finalize, warm_keys, z0, data,
                           seg):
    """The ONE host-side schedule driver over compiled warmup segments.

    ``v_init(keys, z0, data)`` and ``v_seg(keys, aflags, wflags, state, da,
    welford, inv_mass, data)`` are the chain-vmapped warmup parts (-> the
    state, the step size, the mass, and the chains' divergences and
    gradient evaluations) — plain
    jitted on one device (``make_segmented_warmup``) or shard_mapped over a
    mesh (``ShardedBackend``); the schedule slicing and key layout live
    here so the two execution paths cannot drift.

    `fleet._fleet_warmup` mirrors this loop with a leading problem axis
    and a bit-identity contract against it — any schedule/key change here
    must be made there too (tests/test_fleet.py pins the identity).
    """
    trace = telemetry.get_trace()
    # warmup-carry init (find_reasonable_step_size) + the per-chain key
    # streams are the first compiles/dispatches of the run: one
    # compile-stage phase covers them so phase sums tile the wall
    with trace.phase("compile", stage="warmup_init"):
        kinit = jax.vmap(lambda k: jax.random.split(k, 2))(warm_keys)
        state, da, welford, inv_mass = telemetry.wait(
            v_init(kinit[:, 0], z0, data)
        )
        schedule = build_warmup_schedule(cfg.num_warmup)
        aflags = np.asarray(schedule.adapt_mass)
        wflags = np.asarray(schedule.window_end)
        # (num_warmup, chains, 2) step keys, computed and sliced ON DEVICE:
        # chains-sharded keys must never materialize on one host (on a
        # multi-process mesh they are not fully addressable), and slicing
        # rides the replicated time axis so it is shard-local everywhere
        wkeys = jnp.transpose(
            jax.vmap(lambda k: jax.random.split(k, max(cfg.num_warmup, 1)))(
                kinit[:, 1]
            ),
            (1, 0, 2),
        )
    counts = None  # accumulated on device (chains-sharded under a mesh)
    for s in range(0, cfg.num_warmup, seg):
        e = min(s + seg, cfg.num_warmup)
        with trace.phase("warmup_block", start=s, end=e) as ph:
            state, da, welford, inv_mass, ndiv, ngrad, leaves = (
                telemetry.wait(v_seg(
                    wkeys[s:e], jnp.asarray(aflags[s:e]),
                    jnp.asarray(wflags[s:e]), state, da, welford, inv_mass,
                    data)))
            if ngrad.is_fully_addressable:  # one process holds every chain
                ph.note(steps=e - s, grad_evals=int(np.sum(ngrad)))
                # the segment's trees, on its span alone (not the event)
                telemetry.note(**tree_counters(leaves))
        telemetry.notify_progress()  # watchdog liveness beat per segment
        counts = (ndiv, ngrad) if counts is None else (
            counts[0] + ndiv, counts[1] + ngrad)
    if counts is None:
        counts = (jnp.zeros((warm_keys.shape[0],), jnp.int32),) * 2
    return state, finalize(da), inv_mass, counts


def tree_counters(ngrad) -> Dict[str, int]:
    """What a per-chain kernel's transitions cost, from their gradient
    counts (``ngrad``, chains x transitions: a sampling block's
    ``HostBlock.ngrad``, a warm-up segment's per-transition counts):
    ``tree_leaves``, their sum; ``lane_iterations``, the longest tree of
    every vmapped transition added up: the chains run a transition's
    loops in lockstep until the last has finished (every round before a
    chain's last builds its whole subtree, so the deepest chain is also
    the longest in every round), which makes ``tree_leaves / (chains x
    lane_iterations)`` the share of lanes that did work."""
    ngrad = np.asarray(ngrad)
    return {"tree_leaves": int(np.sum(ngrad)),
            "lane_iterations": int(np.sum(np.max(ngrad, axis=0)))}


def make_map_init(fm: FlatModel, cfg: SamplerConfig):
    """``map_init(z0 (chains, d), data) -> z0``: the configured MAP descent of
    every chain's start (`chees.map_descent` on the plain potential: only its
    gradient is used), for a backend to compile; None where
    ``map_init_steps`` is 0, so that a run without one compiles nothing."""
    if cfg.map_init_steps <= 0:
        return None
    from .chees import map_descent

    return lambda z0, data=None: map_descent(
        fm.bind(data), z0, cfg.map_init_steps)


def make_segmented_warmup(fm: FlatModel, cfg: SamplerConfig):
    """Single-device segmented warmup: jit+vmap the warmup parts, return
    ``run(warm_keys, z0, data, seg) -> (state, step_size, inv_mass,
    (warm_div, warm_grad) device (chains,))`` driven by
    ``drive_segmented_warmup``.

    Used by JaxBackend._run_segmented and the adaptive runner; the sharded
    backend builds shard_mapped parts and shares the same driver.
    """
    init_carry, segment, finalize = make_warmup_parts(fm, cfg)
    v_init = jax.jit(jax.vmap(init_carry, in_axes=(0, 0, None)))
    v_seg = jax.jit(
        jax.vmap(segment, in_axes=(1, None, None, 0, 0, 0, 0, None))
    )

    def run(warm_keys, z0, data, seg):
        return drive_segmented_warmup(
            cfg, v_init, v_seg, finalize, warm_keys, z0, data, seg
        )

    return run


def make_chain_runner(fm: FlatModel, cfg: SamplerConfig):
    """Build (key, z0, data) -> ChainResult; one chain, fully compiled.

    The data pytree is a runtime argument so the jitted runner is reusable
    across datasets of the same shape (no recompile per ``sample()`` call).
    vmap over (key, z0) for chains with data broadcast.  Kernels receive a
    ``model.Potential`` so sharded models get the fused single-psum
    value-and-grad path.
    """
    step_kernel = make_kernel(cfg)
    warmup = make_warmup_fn(fm, cfg)
    from .kernels.base import scan_progress

    # clamp to the scan length so an interval longer than the run still
    # heartbeats at least once (step values are scan-local)
    total_steps = cfg.num_samples * cfg.thin
    tick = scan_progress(
        "sample",
        min(cfg.progress_every, total_steps)
        if cfg.progress_every and total_steps
        else None,
    )

    def run(key, z0, data=None):
        potential_fn = fm.bind(data)
        kernel = partial(step_kernel, potential_fn=potential_fn)
        state = init_state(potential_fn, z0)
        key_warm, key_sample = jax.random.split(key)
        state, step_size, inv_mass, warm_div = warmup(
            key_warm, state, potential_fn, kernel
        )

        def body(carry, x):
            # x is (index, key) only when the in-loop heartbeat is on, so
            # the untraced compiled program is bit-identical to the
            # pre-telemetry build (hot path pays nothing by construction)
            i, key = x if tick is not None else (None, x)
            state, wf = carry
            state, info = kernel(key, state, step_size=step_size, inv_mass_diag=inv_mass)
            if tick is not None:
                tick(i, info.accept_prob)
            wf = welford_update(wf, state.z)
            out = (
                state.z,
                info.accept_prob,
                info.is_divergent,
                info.energy,
                info.num_grad_evals,
            )
            return (state, wf), out

        total = cfg.num_samples * cfg.thin
        keys = jax.random.split(key_sample, total)
        xs = (jnp.arange(total), keys) if tick is not None else keys
        wf0 = welford_init(z0.shape[0], z0.dtype)
        (state, wf), (zs, accept, divergent, energy, ngrad) = jax.lax.scan(
            body, (state, wf0), xs
        )
        # divergence count must cover ALL transitions, including thinned-out ones
        num_divergent = jnp.sum(divergent.astype(jnp.int32))
        if cfg.thin > 1:
            zs = zs[cfg.thin - 1 :: cfg.thin]
            accept = accept[cfg.thin - 1 :: cfg.thin]
            divergent = divergent[cfg.thin - 1 :: cfg.thin]
            energy = energy[cfg.thin - 1 :: cfg.thin]
            ngrad = ngrad[cfg.thin - 1 :: cfg.thin]
        return ChainResult(
            draws=zs,
            accept_prob=accept,
            is_divergent=divergent,
            energy=energy,
            num_grad_evals=ngrad,
            step_size=step_size,
            inv_mass_diag=inv_mass,
            num_warmup_divergent=warm_div,
            num_divergent=num_divergent,
            final_state=state,
            suff_count=wf.count,
            suff_mean=wf.mean,
            suff_m2=wf.m2,
        )

    return run


def block_program(cfg: SamplerConfig) -> str:
    """The fixed name of the per-chain kernels' block program
    (`platform.named_jit`): ``jit_stark_nuts_block`` / ``jit_stark_hmc_block``
    on the profiler's modules line, as ``jit_stark_chees_sample`` is the
    ensemble sampler's."""
    return f"stark_{cfg.kernel}_block"


def make_block_runner(fm: FlatModel, cfg: SamplerConfig, block_size: int,
                      diag_lags: Optional[int] = None,
                      ragged: bool = False):
    """One draw block for the segmented/adaptive drivers, jit/vmap-able
    per chain:
      block_run(key, state, step_size, inv_mass, data)
        -> (state, zs, accept, divergent, energy, ngrad)

    ``state`` is an `HMCState`, or for a model that can centre a
    `CentredState` (`chain_potential`), in and out.

    Control crosses host<->device once per BLOCK (SURVEY.md §4: "periodic
    async draw fetch + convergence check"), which is how wall-clock-to-
    R-hat<1.01 — the primary metric — is measured without paying a host
    round-trip per transition.  Warmup has its own dispatch-bounded API
    (``make_segmented_warmup``).

    ``diag_lags`` (streaming diagnostics, STARK_STREAM_DIAG): when set,
    the block additionally carries a `kernels.base.StreamDiagState`
    through the scan — Welford moments + lag-1..L autocovariance sums
    updated per transition ON DEVICE — and the signature becomes
      block_run(key, state, diag, step_size, inv_mass, data)
        -> (HMCState, StreamDiagState, zs, accept, divergent, energy,
            ngrad)
    so the adaptive runner's convergence gate reads O(d*L) sufficient
    statistics per chain per block instead of re-reading the draw history
    (`diagnostics.ess_from_suffstats`, on the device: the host fetches
    the ESS row).

    ``ragged`` (STARK_RAGGED_NUTS, NUTS only): route the block through the
    step-synchronized scheduler (`kernels.nuts_ragged`) — one batched
    gradient evaluation per lane per loop iteration, with each vmapped
    lane advancing its own tree/transition independently.  Draws and all
    per-transition stats are EQUAL TO ROUNDING to this scan's (shared per-leaf
    code and key discipline); both signatures gain ONE trailing output,
    the per-lane live-iteration count (lane-occupancy accounting).
    """
    if ragged:
        from .kernels.nuts_ragged import make_ragged_block_runner

        # raises on non-NUTS / progress_every configs — drivers gate on
        # `ragged_nuts_enabled(cfg)` so a knob-on incompatible run falls
        # back to the legacy scan instead of reaching this error
        return make_ragged_block_runner(fm, cfg, block_size,
                                        diag_lags=diag_lags)
    step_kernel = make_kernel(cfg)
    from .kernels.base import scan_progress, stream_diag_update

    # clamp to the block length: an interval longer than one dispatch
    # block would otherwise never fire (scan indices restart per block;
    # heartbeat steps are block-local by design)
    tick = scan_progress(
        "sample_block",
        min(cfg.progress_every, block_size) if cfg.progress_every else None,
    )

    def _block_scan(key, state, diag, step_size, inv_mass, data):
        """The ONE per-chain block scan serving both variants —
        ``diag=None`` (resolved at trace time) compiles the historical
        plain block; the streaming accumulator is threaded through the
        carry otherwise.  One body so the transitions cannot drift
        between the stream-on and stream-off compiled programs."""
        # ``state`` is what the chain carries: an `HMCState` with pe and
        # grad, or a `CentredState`, whose centre the block keeps
        potential_fn, state, rewrap = chain_potential(fm, data, state)
        kernel = partial(step_kernel, potential_fn=potential_fn)

        def body(carry, x):
            state, diag = carry
            # (index, key) only under the heartbeat — see make_chain_runner
            i, key = x if tick is not None else (None, x)
            state, info = kernel(
                key, state, step_size=step_size, inv_mass_diag=inv_mass
            )
            if tick is not None:
                tick(i, info.accept_prob)
            if diag is not None:
                diag = stream_diag_update(diag, state.z)
            out = (
                state.z,
                info.accept_prob,
                info.is_divergent,
                info.energy,
                info.num_grad_evals,
            )
            return (state, diag), out

        keys = jax.random.split(key, block_size)
        xs = (jnp.arange(block_size), keys) if tick is not None else keys
        (state, diag), outs = jax.lax.scan(body, (state, diag), xs)
        return (rewrap(state), diag), outs

    def block_run(key, state, step_size, inv_mass, data=None):
        (state, _), (zs, accept, divergent, energy, ngrad) = _block_scan(
            key, state, None, step_size, inv_mass, data
        )
        return state, zs, accept, divergent, energy, ngrad

    if diag_lags is None:
        return block_run

    def block_run_diag(key, state, diag, step_size, inv_mass, data=None):
        (state, diag), (zs, accept, divergent, energy, ngrad) = _block_scan(
            key, state, diag, step_size, inv_mass, data
        )
        return state, diag, zs, accept, divergent, energy, ngrad

    return block_run_diag


class ChainBlockKernel:
    """`backends.base.BlockKernel` for the per-chain kernels (NUTS, HMC;
    both NUTS schedulers; with and without the streaming-diagnostics
    carry): every chain carries its own state, step size and mass through
    the backend's compiled blocks (`make_block_runner`), and warm-up is the
    segmented driver's (`drive_segmented_warmup`).  For a model that can
    centre, ``state`` is a `CentredState`: each chain's energies relative
    to the centre beside them (`kernels.base.chain_potential`), which
    warm-up's segments move and sampling keeps; a checkpoint's ``pe`` is
    the potential itself, in float64
    (`backends.base.checkpoint_potential`)."""

    def __init__(self, ap, cfg: SamplerConfig, chains: int, env):
        self.ap, self.cfg, self.chains, self.env = ap, cfg, chains, env
        self.stream_diag = env.stream_diag
        if self.stream_diag:
            try:  # probe: older/third-party backends lack the diag carry
                ap.get_block(env.block_size, diag_lags=env.diag_lags,
                             donate_diag=env.sync_blocks)
            except TypeError:
                self.stream_diag = False
        # step-synchronized NUTS scheduling (STARK_RAGGED_NUTS): blocks gain
        # one trailing lane-iteration output.  Knob-gated per config and
        # probed like the diag carry: a backend without it (sharded meshes,
        # whose collectives must run in lockstep) keeps the legacy scan
        from .kernels.nuts_ragged import ragged_nuts_enabled

        self._ragged = ragged_nuts_enabled(cfg)
        if self._ragged:
            try:
                ap.get_block(env.block_size, ragged=True)
            except TypeError:
                self._ragged = False
        # the carries' `model.Centering`, None where they hold no centre
        self._centering = (
            ap.fm.chain_centering if ap.data is not None else None)
        self.state = self.step_size = self.inv_mass = None

    @property
    def _hmc(self) -> HMCState:
        """The chains' states, whatever carries them."""
        return self.state if self._centering is None else self.state.state

    @property
    def dtype(self):
        return np.dtype(self._hmc.z.dtype)

    def _block(self, length):
        """Compiled block runner for ``length`` transitions (the backend
        caches per (length, diag, donate, ragged))."""
        kw = {"ragged": True} if self._ragged else {}
        if self.stream_diag:
            kw.update(diag_lags=self.env.diag_lags,
                      donate_diag=self.env.sync_blocks)
        return self.ap.get_block(length, **kw)

    def start(self):
        ap, env, chains, fm = self.ap, self.env, self.chains, self.ap.fm
        with telemetry.span("compile", stage="chain_init"):
            key = jax.random.PRNGKey(env.seed)
            key, key_init, key_warm = jax.random.split(key, 3)
        # chain-position init is the per-chain path's first real dispatch
        # (vmapped init_flat compiles here): a compile-stage phase, so the
        # span timeline attributes it instead of reporting pre-warmup slack
        with env.trace.phase("compile", stage="chain_init"):
            if env.init_params is not None:
                z0 = jnp.broadcast_to(
                    fm.unconstrain(env.init_params), (chains, fm.ndim))
            else:
                z0 = jax.vmap(fm.init_flat)(jax.random.split(key_init, chains))
            z0 = ap.put_chains(z0)
            warm_keys = ap.put_chains(jax.random.split(key_warm, chains))
            telemetry.wait(z0)
        map_steps = self.cfg.map_init_steps if ap.map_init is not None else 0
        if map_steps:
            # the descent toward the mode, as the ensemble sampler's start
            # has it: its compile counters say how much was compilation
            with env.trace.phase("compile", stage="init+map",
                                 map_init_steps=map_steps):
                with telemetry.span("map_init", steps=map_steps,
                                    grad_evals=map_steps * chains):
                    z0 = telemetry.wait(ap.map_init(z0, ap.data))
        # warm-up runs as block_size-bounded dispatches too (checkpointable,
        # beats the watchdog); the segmented driver reads the ambient trace,
        # which the public wrapper pinned to THIS run's
        with telemetry.span("warmup", steps=self.cfg.num_warmup) as warm_span:
            self.state, self.step_size, self.inv_mass, counts = ap.seg_warmup(
                warm_keys, z0, ap.data, env.block_size)
            # per-chain counts are chain-sharded
            n_div, n_grad = ap.collect(counts)
            n_grad = int(np.sum(n_grad))
            warm_span.note(grad_evals=n_grad)
        # gradient evaluations spent before sampling: the MAP descent (one
        # a step a chain), warm-up's leaves (or leapfrogs) and the centre's
        # evaluations, all chains
        return key, n_div, {
            "warmup_grad_evals": n_grad + map_steps * chains}

    def restore(self, arrays, meta, reseed):
        from .backends.base import carried_potential, restored_key

        # checkpoints are host numpy; per-chain kernels carry per-chain
        # step/mass: everything goes on the chains layout
        put = lambda a: self.ap.put_chains(jnp.asarray(a))  # noqa: E731
        pe, centre = carried_potential(arrays, self._centering)
        self.state = HMCState(put(arrays["z"]), put(pe), put(arrays["grad"]))
        if centre is not None:
            self.state = CentredState(self.state, put(centre))
        self.step_size = put(arrays["step_size"])
        self.inv_mass = put(arrays["inv_mass"])
        self.chains = arrays["z"].shape[0]
        return restored_key(arrays, "key", reseed), None, None

    def dispatch(self, key_block, length, diag, first_draw):
        from . import faults
        from .backends.base import PendingBlock, carried_state

        block_keys = self.ap.put_chains(
            jax.random.split(key_block, self.chains))
        carried = (self.state, diag) if self.stream_diag else (self.state,)
        out = list(self._block(length)(
            block_keys, *carried, self.step_size, self.inv_mass, self.ap.data
        ))
        # per-chain kernels CARRY the (possibly poisoned) state into the
        # next dispatch — same rebinding as the serial loop
        self.state = faults.poison("runner.carried_nan", out.pop(0))
        if self.stream_diag:
            diag = out.pop(0)
        # what is left: zs, accept, divergent, energy, ngrad[, lane_iters]
        return PendingBlock(
            length, tuple(out), diag,
            carried_state(self._hmc, self.step_size, self.inv_mass),
            extras=None if self._centering is None else self.state.center,
        )

    def host_block(self, pending, energy=False):
        from .backends.base import HostBlock

        collect = self.ap.collect
        zs, accept, divergent, energy_d, ngrad, *lane = pending.outs
        zs, accept, divergent, ngrad = collect((zs, accept, divergent, ngrad))
        # the per-block Hamiltonian series crosses to host only for the
        # health observatory, its first host-side consumer (E-BFMI)
        energy_h = np.asarray(collect(energy_d)) if energy else None
        sched_fields = {}
        if lane:
            # ragged-NUTS occupancy: the batch executed max(lane_iters)
            # iterations x chains lane-gradients; the useful fraction is
            # what the scheduler exists to raise (knob-on runs only)
            from .kernels.nuts_ragged import lane_occupancy_fields

            sched_fields = lane_occupancy_fields(collect(lane[0]))
        accept, ngrad = np.asarray(accept), np.asarray(ngrad)
        return HostBlock(
            zs=np.asarray(zs), zs_dm=None, accept=accept,
            divergent=np.asarray(divergent),
            mean_accept=float(np.mean(accept)),
            grad_evals=int(np.sum(ngrad)), energy=energy_h, ngrad=ngrad,
            sched_fields=sched_fields,
        )

    def checkpoint_arrays(self, pending):
        from .backends.base import checkpoint_potential

        named = dict(pending.carried)
        if pending.extras is not None:
            named["pe_center"] = pending.extras
        arrays = checkpoint_potential(
            self.ap.collect(named), self._centering)
        arrays["key"] = np.asarray(pending.key)  # as of this block's split
        return arrays


def drive_segmented_sampling(fm: FlatModel, cfg: SamplerConfig, seg_warmup,
                             get_block, chain_keys, z0, data, seg,
                             collect=None):
    """Warmup + sampling as bounded-length dispatches, one host driver for
    every backend (see JaxBackend docstring for why dispatches are
    bounded).  ``seg_warmup(warm_keys, z0, data, seg)`` and
    ``get_block(length) -> v_block(keys, state, step_size, inv_mass,
    data)`` are backend-compiled (jit or shard_map + jit); ``collect``
    materializes a device pytree on the host (allgather on pods).

    Draw blocks run as a two-deep software pipeline (the same discipline
    as the adaptive runner): segment i+1 is ENQUEUED before segment i's
    outputs are materialized, so the host-side transfer/thinning/append
    work overlaps device compute.  Per-segment keys are pre-split, so the
    pipelined and serial (``STARK_SYNC_BLOCKS=1``) orders are
    bit-identical.

    At most two compiled block variants run per call (the full segment and
    one remainder length).
    """
    if collect is None:
        collect = lambda t: jax.tree.map(np.asarray, t)  # noqa: E731
    chains = z0.shape[0]
    keys = jax.vmap(lambda k: jax.random.split(k, 2))(chain_keys)
    warm_keys, sample_keys = keys[:, 0], keys[:, 1]
    state, step_size, inv_mass, (warm_div, _) = seg_warmup(
        warm_keys, z0, data, seg)
    warm_div = np.asarray(collect(warm_div))

    total = cfg.num_samples * cfg.thin
    # per-chain step keys stay ON DEVICE (chains-sharded on a mesh; not
    # fully addressable on a multi-process mesh); sliced per block along
    # the replicated sample axis
    skeys = jax.vmap(lambda k: jax.random.split(k, max(total, 1)))(
        sample_keys
    )  # (chains, >=1, 2)
    # empty seeds keep the num_samples=0 (warmup-only) case concatenable;
    # thinning happens PER BLOCK so host memory holds only kept draws
    d = z0.shape[1]
    zs_blocks = [np.zeros((chains, 0, d), np.dtype(z0.dtype))]
    acc_blocks = [np.zeros((chains, 0), np.float32)]
    div_blocks = [np.zeros((chains, 0), bool)]
    en_blocks = [np.zeros((chains, 0), np.float32)]
    ng_blocks = [np.zeros((chains, 0), np.int32)]
    num_divergent = np.zeros((chains,), np.int64)
    trace = telemetry.get_trace()
    # statistical-health observatory (stark_tpu.health): host-side only,
    # fed from the readbacks this driver already materializes — the
    # compiled programs and draws are untouched; STARK_HEALTH=0 removes
    # the trace events too
    from . import health as _health

    monitor = (
        _health.HealthMonitor(
            kernel=cfg.kernel, max_depth=cfg.max_tree_depth, trace=trace
        )
        if _health.health_enabled() else None
    )
    # multi-process meshes stay serial: their collect is an allgather —
    # a dispatched computation stream-ordered after the prefetched block,
    # so prefetching only delays this block's materialization (see the
    # adaptive runner's identical gate)
    sync_blocks = (
        os.environ.get("STARK_SYNC_BLOCKS", "") == "1"
        or jax.process_count() > 1
    )
    spans = [(s, min(s + seg, total)) for s in range(0, total, seg)]

    # step-synchronized NUTS scheduling (STARK_RAGGED_NUTS): blocks gain a
    # per-chain lane-iteration output; probed like the runner does — a
    # get_block without the kwarg (sharded meshes) keeps the legacy scan
    from .kernels.nuts_ragged import ragged_nuts_enabled

    ragged = ragged_nuts_enabled(cfg)
    if ragged and spans:
        try:
            get_block(spans[0][1] - spans[0][0], ragged=True)
        except TypeError:
            ragged = False

    def dispatch(span):
        """Enqueue one segment (async) and chain the carried state."""
        nonlocal state
        s, e = span
        # block_run splits its own per-step keys from one key per chain
        fn = (
            get_block(e - s, ragged=True) if ragged else get_block(e - s)
        )
        out = fn(skeys[:, s, :], state, step_size, inv_mass, data)
        state = out[0]
        return out[1:]

    pend = None
    for i, (s, e) in enumerate(spans):
        if pend is None:
            pend = dispatch((s, e))
        outs, pend = pend, None
        if not sync_blocks and i + 1 < len(spans):
            # overlap: the next segment computes while the host thins and
            # appends this one
            pend = dispatch(spans[i + 1])
        with trace.phase("sample_block", start=s, end=e,
                         pipelined=not sync_blocks) as ph:
            if ragged:
                zs, accept, divergent, energy, ngrad, lane_iters = collect(
                    outs
                )
            else:
                zs, accept, divergent, energy, ngrad = collect(outs)
            if trace.enabled:
                ph.note(mean_accept=round(float(np.mean(accept)), 4))
                if ragged:
                    # lane-occupancy accounting (shared field definition)
                    from .kernels.nuts_ragged import lane_occupancy_fields

                    ph.note(**lane_occupancy_fields(lane_iters))
        num_divergent += divergent.astype(np.int64).sum(axis=1)
        if trace.enabled:
            trace.emit(
                "chain_health",
                transitions=int(e),
                mean_accept=round(float(np.mean(accept)), 4),
                num_divergent=int(num_divergent.sum()),
            )
        if monitor is not None:
            monitor.observe_block(
                block=i + 1,
                zs=np.asarray(zs),
                accept=np.asarray(accept),
                divergent=np.asarray(divergent),
                energy=np.asarray(energy),
                ngrad=np.asarray(ngrad),
            )
        # global transition i is kept when (i+1) % thin == 0
        keep = np.arange(s, e)
        keep = (
            (keep[(keep + 1) % cfg.thin == 0] - s)
            if cfg.thin > 1
            else slice(None)
        )
        zs_blocks.append(zs[:, keep])
        acc_blocks.append(accept[:, keep])
        div_blocks.append(divergent[:, keep])
        en_blocks.append(energy[:, keep])
        ng_blocks.append(ngrad[:, keep])

    if monitor is not None:
        # no convergence gate on this driver: the end-of-run R-hat/ESS
        # warnings stay silent (no values), the block-level trail stands
        monitor.finalize()
    with trace.phase("collect"):
        zs = np.concatenate(zs_blocks, axis=1)  # (chains, num_samples, d)
        step_size, inv_mass = collect((step_size, inv_mass))
        draws = _constrain_draws(fm, zs)
        stats = {
            "accept_prob": np.concatenate(acc_blocks, axis=1),
            "is_divergent": np.concatenate(div_blocks, axis=1),
            "energy": np.concatenate(en_blocks, axis=1),
            "num_grad_evals": np.concatenate(ng_blocks, axis=1),
            "step_size": step_size,
            "inv_mass_diag": inv_mass,
            "num_warmup_divergent": warm_div,
            "num_divergent": num_divergent,
        }
    return Posterior(draws, stats, flat_model=fm, draws_flat=zs)


class Posterior:
    """Posterior draws + sample stats for a finished run."""

    def __init__(
        self,
        draws: Dict[str, np.ndarray],
        sample_stats: Dict[str, np.ndarray],
        flat_model: Optional[FlatModel] = None,
        draws_flat: Optional[np.ndarray] = None,
    ):
        self.draws = draws
        self.sample_stats = sample_stats
        self.flat_model = flat_model
        self.draws_flat = draws_flat

    @property
    def num_chains(self) -> int:
        return next(iter(self.draws.values())).shape[0]

    @property
    def num_samples(self) -> int:
        return next(iter(self.draws.values())).shape[1]

    @property
    def num_divergent(self) -> int:
        # pre-thinning count when available (covers dropped transitions)
        if "num_divergent" in self.sample_stats:
            return int(np.sum(self.sample_stats["num_divergent"]))
        return int(np.sum(self.sample_stats.get("is_divergent", 0)))

    def rhat(self) -> Dict[str, np.ndarray]:
        return {k: diagnostics.split_rhat(v) for k, v in self.draws.items()}

    def rank_rhat(self) -> Dict[str, np.ndarray]:
        """Rank-normalized split-R-hat (bulk ∨ folded) — robust to heavy
        tails and monotone transforms; Stan's modern default."""
        return {k: diagnostics.rank_rhat(v) for k, v in self.draws.items()}

    def ess(self) -> Dict[str, np.ndarray]:
        return {k: diagnostics.ess(v) for k, v in self.draws.items()}

    def ess_tail(self) -> Dict[str, np.ndarray]:
        """Tail ESS (reliability of reported tail quantiles)."""
        return {k: diagnostics.ess_tail(v) for k, v in self.draws.items()}

    def summary(self):
        return diagnostics.summarize(self.draws)

    def max_rhat(self) -> float:
        return float(max(np.max(v) for v in self.rhat().values()))

    def min_ess(self) -> float:
        return float(min(np.min(v) for v in self.ess().values()))

    def functional(self, fn: Callable[[Dict[str, Any]], Any]) -> np.ndarray:
        """Apply ``fn(params) -> array`` to every draw; (chains, draws, ...).

        The honest diagnostic space for models whose raw parameters are
        non-identifiable (neural nets under permutation/sign symmetry,
        mixtures under label switching): compute R-hat/ESS on a posterior
        *functional* — e.g. predictions at probe inputs — instead of on
        weights.
        """
        out = jax.vmap(jax.vmap(fn))(
            {k: jnp.asarray(v) for k, v in self.draws.items()}
        )
        return np.asarray(out)


def _constrain_draws(fm: FlatModel, zs) -> Dict[str, np.ndarray]:
    # elementwise over the full draw history, on the default device: the
    # host copy goes up once and the constrained draws come back once
    constrained = named_jit(
        jax.vmap(jax.vmap(fm.constrain)), "stark_constrain"
    )(np.asarray(zs))
    return {k: np.asarray(v) for k, v in constrained.items()}


@_profile.entrypoint
def sample(
    model: Model,
    data: Any = None,
    *,
    chains: int = 4,
    seed: int = 0,
    backend: Any = None,
    init_params: Optional[Dict[str, Array]] = None,
    debug_nans: bool = False,
    trace: Optional[Any] = None,
    **cfg_kwargs,
) -> Posterior:
    """Run MCMC and return a Posterior.

    The default backend is the single-process JAX backend (jit + vmap over
    chains on the default device — TPU when present).  Pass a
    ``backends.SamplerBackend`` instance for sharded / CPU-reference
    execution.

    debug_nans: run under ``jax_debug_nans`` so the FIRST non-finite value
    in the potential/gradient raises with a traceback into the model code,
    instead of surfacing later as a silently frozen chain — the sanitizer
    mode of SURVEY.md §6 (pure-functional JAX has no data races to detect;
    numerics are the failure class that remains).

    trace: a `telemetry.RunTrace` (default: the ambient trace installed by
    ``telemetry.use_trace`` / the CLI ``--trace`` flag; `NullTrace` when
    none is installed — zero cost).  The run emits ``run_start`` /
    ``run_end`` envelope events here; backends emit the phase events
    (``warmup_block``/``sample_block``/``chain_health``) between them.
    """
    cfg = SamplerConfig(**cfg_kwargs)
    if backend is None:
        from .backends.jax_backend import JaxBackend

        backend = JaxBackend()
    trace = telemetry.resolve_trace(trace)
    ctx = jax.debug_nans(True) if debug_nans else contextlib.nullcontext()
    with ctx, telemetry.use_trace(trace):
        if trace.enabled:
            fused_tag = (
                model.fused_tag() if hasattr(model, "fused_tag") else None
            )
            from .ops.quantize import x_stream_tags

            trace.emit(
                "run_start",
                entry="sample",
                model=type(model).__name__,
                **({"fused": fused_tag} if fused_tag else {}),
                # resolved X-stream dtype + slab bytes (absent on f32
                # runs — trace byte-identity; see ops/quantize.py)
                **x_stream_tags(fused_tag, data),
                kernel=cfg.kernel,
                chains=chains,
                num_warmup=cfg.num_warmup,
                num_samples=cfg.num_samples,
                seed=seed,
                backend=type(backend).__name__,
                # {"profile": id} when an autotuned profile steers this
                # run; ABSENT otherwise (byte-identical traces)
                **_profile.run_start_tags(),
                **telemetry.device_info(),
                **telemetry.provenance(),
            )
        t0 = time.perf_counter()
        post = backend.run(
            model, data, cfg, chains=chains, seed=seed, init_params=init_params
        )
        if trace.enabled:
            trace.emit(
                "run_end",
                dur_s=round(time.perf_counter() - t0, 4),
                num_divergent=int(post.num_divergent),
            )
        return post
