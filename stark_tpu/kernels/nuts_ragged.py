"""Step-synchronized ("ragged") NUTS block scheduler — STARK_RAGGED_NUTS.

Vmapped iterative NUTS synchronizes lanes at every nested loop level: the
batched tree-building ``while_loop`` runs until the SLOWEST lane's subtree
closes, and the doubling loop until the slowest lane's trajectory ends, so
every chain (and, on the fleet path, every problem x chain lane) pays the
deepest lane's gradient budget at every transition — `kernels/chees.py`
documents the cost as "the full 2^max_depth gradient budget for EVERY
chain at EVERY step", and PR 6 capped fleet NUTS depth at 5 just to bound
it.  "Running MCMC on Modern Hardware" and the tfp.mcmc paper (PAPERS.md)
identify exactly this tree-raggedness lane-sync waste as the dominant
inefficiency of batched dynamic HMC on SIMD hardware.

This module flattens a whole draw BLOCK into ONE ``lax.while_loop`` whose
body performs exactly one leapfrog (one batched gradient evaluation) per
lane per iteration.  Each lane carries its own transition / trajectory /
subtree state plus a tiny phase machine:

  fresh_draw   -> consume the lane's next transition key, refresh momentum,
                  open a fresh single-point trajectory        (same iter)
  fresh_round  -> split the trajectory key 4-ways, sample a direction,
                  open a fresh subtree at the chosen edge     (same iter)
  (always)     -> ONE leaf: one leapfrog via `nuts._leaf_step`
  subtree done -> close the doubling round via `nuts._merge_traj`
  traj done    -> write the draw into the lane's output slot, advance the
                  lane to transition k+1 — NEXT iteration starts it

A lane that finishes draw k therefore starts draw k+1 on the very next
batched gradient evaluation instead of idling until the batch's slowest
tree closes: per-block lane-sync waste shrinks from
sum-over-steps-of-max-tree to end-of-block straggler imbalance.

Determinism contract: the per-lane op and key-split sequence is EXACTLY
the legacy kernel's — the transition keys come from the same
``jax.random.split(key, block_size)``, each transition does the same
(key_mom, key_loop) split, each doubling round the same 4-way split, each
leaf the same `nuts._leaf_step` (shared code, not a copy) — so the draws,
accept statistics, divergence flags, energies and grad-eval counts are
EQUAL TO ROUNDING to `sampler.make_block_runner`'s nested scan (integers
exactly, floats to 1e-5: two XLA programs), per lane, independent of batch
composition (tests/test_ragged_nuts.py).  Only the execution interleaving
across lanes changes.

Occupancy accounting rides in the carry: ``iters`` counts the iterations
a lane was still working (== its useful gradient evaluations — one leaf
per live iteration by construction).  The batch executes
``max(iters) * lanes`` lane-gradients, so
``occupancy = sum(iters) / (max(iters) * lanes)`` — the number the
``sample_block`` / ``fleet_block`` trace events, `metrics.TraceCollector`
and ``bench.py microbench nutssched`` surface.

Scope: the env knob applies to the per-chain NUTS *block* runners
(`sampler.make_block_runner` behind the adaptive runner, the segmented
driver, and `fleet._FleetParts`).  Warmup, the monolithic
`make_chain_runner` path, HMC/ChEES, in-scan ``progress_every``
heartbeats, and sharded meshes (whose data-sharded potentials contain
collectives that must execute in lockstep across processes) keep the
legacy scan — `ragged_nuts_enabled` gates all of that, and callers that
pass ``ragged=True`` to an execution layer that cannot serve it fall back
via TypeError probing.
"""

from __future__ import annotations

import os
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .base import (
    HMCState,
    chain_potential,
    kinetic_energy,
    sample_momentum,
    stream_diag_update,
)
from .nuts import (
    _Subtree,
    _Traj,
    _leaf_step,
    _merge_traj,
    _subtree_init,
    _traj_init,
)

Array = jax.Array

#: env knob: "1" routes NUTS block runners through the step-synchronized
#: scheduler; default off — the legacy nested scan runs as it always did
RAGGED_NUTS_ENV = "STARK_RAGGED_NUTS"


def ragged_nuts_enabled(cfg=None) -> bool:
    """Resolve the STARK_RAGGED_NUTS knob (default OFF).

    With a `SamplerConfig`, additionally require the NUTS kernel and no
    in-scan heartbeat (``progress_every`` indexes transitions inside the
    legacy scan; the ragged loop has no per-transition scan index) — so a
    knob-on run with an incompatible config silently keeps the legacy
    path instead of erroring.
    """
    # literal knob name: tools/lint_fused_knobs.py AST-collects env-read
    # string literals, so the read must not hide behind the constant
    if os.environ.get("STARK_RAGGED_NUTS", "0") != "1":
        return False
    if cfg is None:
        return True
    return cfg.kernel == "nuts" and not cfg.progress_every


def lane_occupancy_fields(lane_iters, useful=None):
    """The occupancy trace/metrics fields for ONE finished block — the
    single definition every driver (runner, fleet, segmented sampler)
    stamps into its ``sample_block`` / ``fleet_block`` events, so the
    schemas cannot drift.

    ``lane_iters``: the block runners' per-lane live-iteration output
    (host array-like, any batch shape).  The batched loop executed
    ``max(lane_iters)`` iterations x all lanes; ``useful`` defaults to
    ``lane_iters.sum()`` (single-runner: every live iteration performs
    one real leapfrog) — the fleet passes its ACTIVE-lane gradient total
    instead, since frozen lanes' work is discarded.
    """
    li = np.asarray(lane_iters)
    it_max = int(li.max()) if li.size else 0
    executed = it_max * li.size
    if useful is None:
        useful = float(li.sum())
    return {
        "ragged_nuts": True,
        "sched_iters": it_max,
        "lane_occupancy": (
            round(float(useful) / executed, 4) if executed else 1.0
        ),
    }


def _tree_sel(flag, a, b):
    return jax.tree.map(lambda x, y: jnp.where(flag, x, y), a, b)


class _RaggedCarry(NamedTuple):
    """One lane's full scheduler state (vmap adds the chain — and on the
    fleet path the problem — axes).

    Layout: ``k`` draws finished / ``iters`` live iterations; the chain
    state the NEXT transition starts from; the current transition
    (``loop_key``/``energy0``/``traj``), doubling round
    (``going_right``/``key_take``) and subtree (``sub`` + checkpoint
    stacks + leaf index ``i`` + ``sub_key``); the two phase flags; the
    per-draw output buffers the finished transitions scatter into; and
    the optional streaming-diagnostics accumulator."""

    k: Array
    iters: Array
    state: HMCState
    # transition-level
    loop_key: Array
    energy0: Array
    traj: _Traj
    # round-level
    going_right: Array
    key_take: Array
    # subtree-level
    sub: _Subtree
    r_ckpts: Array
    s_ckpts: Array
    vr_ckpts: Array
    i: Array
    sub_key: Array
    # phase machine
    fresh_draw: Array
    fresh_round: Array
    # outputs
    out_z: Array
    out_accept: Array
    out_div: Array
    out_energy: Array
    out_ngrad: Array
    diag: object  # StreamDiagState or None (empty pytree)


def make_ragged_block_runner(fm, cfg, block_size: int,
                             diag_lags: Optional[int] = None):
    """Build the ragged twin of `sampler.make_block_runner` for the NUTS
    kernel.  Same per-chain signature plus ONE extra trailing output —
    the lane's live-iteration count (its useful gradient evaluations):

      block_run(key, state, step_size, inv_mass, data)
        -> (HMCState, zs, accept, divergent, energy, ngrad, lane_iters)

    and with ``diag_lags`` the streaming-diagnostics variant mirrors
    the legacy one with the same extra output.  vmap over chains (and
    problems) exactly like the legacy runner — the batched while_loop
    masks finished lanes' carries while the live ones keep stepping.
    """
    if cfg.kernel != "nuts":
        raise ValueError(
            f"ragged scheduling serves the NUTS kernel only, got "
            f"{cfg.kernel!r}"
        )
    if cfg.progress_every:
        raise ValueError(
            "ragged NUTS has no per-transition scan index for the "
            "progress_every heartbeat; unset progress_every or the knob"
        )
    if block_size < 1:
        raise ValueError(f"block_size must be >= 1, got {block_size}")
    max_depth = cfg.max_tree_depth

    def _block(key, state, diag, step_size, inv_mass_diag, data):
        potential_fn, state, rewrap = chain_potential(fm, data, state)
        d = state.z.shape[0]
        dtype = state.z.dtype
        slots = jnp.arange(max_depth, dtype=jnp.int32)
        # the SAME per-transition key layout as the legacy block scan:
        # transition t consumes tkeys[t] regardless of scheduling order
        tkeys = jax.random.split(key, block_size)

        # dummies for the not-yet-started transition: any well-shaped
        # values — the first iteration's fresh_draw/fresh_round overwrite
        # every one of them before use
        r0_d = jnp.zeros((d,), dtype)
        e0_d = state.potential_energy + kinetic_energy(r0_d, inv_mass_diag)
        traj_d = _traj_init(state, r0_d, e0_d)
        sub_d, rc_d, sc_d, vc_d = _subtree_init(
            state.z, r0_d, state.grad, e0_d, max_depth
        )
        init = _RaggedCarry(
            k=jnp.zeros((), jnp.int32),
            iters=jnp.zeros((), jnp.int32),
            state=state,
            loop_key=tkeys[0],
            energy0=e0_d,
            traj=traj_d,
            going_right=jnp.asarray(False),
            key_take=tkeys[0],
            sub=sub_d,
            r_ckpts=rc_d,
            s_ckpts=sc_d,
            vr_ckpts=vc_d,
            i=jnp.zeros((), jnp.int32),
            sub_key=tkeys[0],
            fresh_draw=jnp.asarray(True),
            fresh_round=jnp.asarray(True),
            out_z=jnp.zeros((block_size, d), dtype),
            out_accept=jnp.zeros((block_size,), dtype),
            out_div=jnp.zeros((block_size,), bool),
            out_energy=jnp.zeros((block_size,), dtype),
            out_ngrad=jnp.zeros((block_size,), jnp.int32),
            diag=diag,
        )

        def cond(c):
            return c.k < block_size

        def body(c):
            # --- start a new transition (masked by fresh_draw) --------
            # every branch below is computed unconditionally and
            # select-merged: under vmap that is exactly the masked-lane
            # execution the legacy batched loops already pay, but here
            # the discarded work is O(d) bookkeeping, never a gradient
            tkey = tkeys[jnp.minimum(c.k, block_size - 1)]
            key_mom, key_loop0 = jax.random.split(tkey)
            r0 = sample_momentum(key_mom, inv_mass_diag)
            e0_new = (
                c.state.potential_energy + kinetic_energy(r0, inv_mass_diag)
            )
            fresh_draw = c.fresh_draw
            loop_key = jnp.where(fresh_draw, key_loop0, c.loop_key)
            energy0 = jnp.where(fresh_draw, e0_new, c.energy0)
            traj = _tree_sel(fresh_draw, _traj_init(c.state, r0, e0_new),
                             c.traj)
            fresh_round = c.fresh_round | fresh_draw

            # --- start a new doubling round (masked by fresh_round) ---
            # the 4-way split / direction draw replicate the legacy
            # doubling body's key order exactly; they advance the lane's
            # stream only when adopted (selects below)
            lk, key_dir, key_sub, key_take_n = jax.random.split(loop_key, 4)
            going_right_n = jax.random.bernoulli(key_dir)
            z_edge = jnp.where(going_right_n, traj.z_right, traj.z_left)
            r_edge = jnp.where(going_right_n, traj.r_right, traj.r_left)
            g_edge = jnp.where(going_right_n, traj.grad_right,
                               traj.grad_left)
            sub_n, rc_n, sc_n, vc_n = _subtree_init(
                z_edge, r_edge, g_edge, energy0, max_depth
            )
            loop_key = jnp.where(fresh_round, lk, loop_key)
            going_right = jnp.where(fresh_round, going_right_n,
                                    c.going_right)
            key_take = jnp.where(fresh_round, key_take_n, c.key_take)
            sub = _tree_sel(fresh_round, sub_n, c.sub)
            r_ckpts = jnp.where(fresh_round, rc_n, c.r_ckpts)
            s_ckpts = jnp.where(fresh_round, sc_n, c.s_ckpts)
            vr_ckpts = jnp.where(fresh_round, vc_n, c.vr_ckpts)
            i = jnp.where(fresh_round, jnp.zeros((), jnp.int32), c.i)
            sub_key = jnp.where(fresh_round, key_sub, c.sub_key)
            directed_step = jnp.where(going_right, step_size, -step_size)

            # --- ONE leaf: the iteration's single gradient eval -------
            sub, r_ckpts, s_ckpts, vr_ckpts, i, sub_key = _leaf_step(
                sub, r_ckpts, s_ckpts, vr_ckpts, i, sub_key,
                potential_fn=potential_fn,
                directed_step=directed_step,
                inv_mass_diag=inv_mass_diag,
                energy0=energy0,
                slots=slots,
            )

            # --- close the round (masked by sub_done) -----------------
            num_target = jnp.left_shift(
                jnp.int32(1), traj.depth.astype(jnp.int32)
            )
            sub_done = sub.turning | sub.diverging | (i >= num_target)
            traj_m = _merge_traj(traj, sub, going_right, key_take,
                                 inv_mass_diag)
            traj = _tree_sel(sub_done, traj_m, traj)
            traj_done = sub_done & (
                (traj_m.depth >= max_depth) | traj_m.turning
                | traj_m.diverging
            )

            # --- finalize the draw (masked by traj_done) --------------
            new_state = HMCState(
                z=traj.z_prop,
                potential_energy=traj.pe_prop,
                grad=traj.grad_prop,
            )
            state = _tree_sel(traj_done, new_state, c.state)
            num = jnp.maximum(traj.num_leaves, 1)
            accept = traj.sum_accept / num.astype(traj.sum_accept.dtype)
            idx = jnp.minimum(c.k, block_size - 1)

            def put(buf, v):
                return buf.at[idx].set(jnp.where(traj_done, v, buf[idx]))

            out_z = put(c.out_z, traj.z_prop)
            out_accept = put(c.out_accept, accept)
            out_div = put(c.out_div, traj.diverging)
            out_energy = put(c.out_energy, traj.energy_prop)
            out_ngrad = put(c.out_ngrad, traj.num_leaves)
            diag_c = c.diag
            if diag_c is not None:
                diag_c = _tree_sel(
                    traj_done, stream_diag_update(diag_c, new_state.z),
                    diag_c,
                )
            return _RaggedCarry(
                k=c.k + traj_done.astype(jnp.int32),
                iters=c.iters + 1,
                state=state,
                loop_key=loop_key,
                energy0=energy0,
                traj=traj,
                going_right=going_right,
                key_take=key_take,
                sub=sub,
                r_ckpts=r_ckpts,
                s_ckpts=s_ckpts,
                vr_ckpts=vr_ckpts,
                i=i,
                sub_key=sub_key,
                fresh_draw=traj_done,
                fresh_round=sub_done,
                out_z=out_z,
                out_accept=out_accept,
                out_div=out_div,
                out_energy=out_energy,
                out_ngrad=out_ngrad,
                diag=diag_c,
            )

        c = jax.lax.while_loop(cond, body, init)
        outs = (c.out_z, c.out_accept, c.out_div, c.out_energy, c.out_ngrad)
        return rewrap(c.state), c.diag, outs, c.iters

    def block_run(key, state, step_size, inv_mass, data=None):
        state, _, (zs, accept, divergent, energy, ngrad), iters = _block(
            key, state, None, step_size, inv_mass, data
        )
        return state, zs, accept, divergent, energy, ngrad, iters

    if diag_lags is None:
        return block_run

    def block_run_diag(key, state, diag, step_size, inv_mass, data=None):
        state, diag, (zs, accept, divergent, energy, ngrad), iters = _block(
            key, state, diag, step_size, inv_mass, data
        )
        return state, diag, zs, accept, divergent, energy, ngrad, iters

    return block_run_diag
