"""Kernel state/info containers shared by HMC-family kernels.

Every kernel is a pure function ``(key, state, params...) -> (state, info)``
composable under ``jax.lax.scan`` (SURVEY.md §8 step 2).  State lives on a
flat unconstrained vector; kinetic energy uses a diagonal inverse mass matrix
(vector) throughout — dense mass is a documented non-goal for v1.

Also home to the ON-DEVICE streaming-diagnostics accumulator
(`StreamDiagState` / `stream_diag_update`): Welford moments plus fixed-lag
autocovariance sums carried through the sampling scans, so the adaptive
runner's convergence gate reads O(chains*d*L) sufficient statistics per
block instead of depending on the accumulated O(draws) history
(`diagnostics.ess_from_suffstats` is the consumer: on the device behind
each block for the runner, whose gate fetches the ESS row alone; on the
host for the fleet's lanes).
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

Array = jax.Array
PotentialFn = Callable[[Array], Array]

#: default autocovariance truncation for the streaming ESS accumulator —
#: lags 1..L are tracked per chain per coordinate (issue: L ~ 50 resolves
#: integrated autocorrelation times up to tau ~ 25 exactly; slower-mixing
#: components fall back to the conservative geometric tail bound in
#: diagnostics.ess_from_suffstats, which under- rather than over-reports)
STREAM_DIAG_LAGS = 50


class StreamDiagState(NamedTuple):
    """Streaming-diagnostics sufficient statistics for ONE chain.

    Carried through the compiled sampling scans (vmap over chains /
    shard_map over a chain mesh axis adds the leading chains axis).  All
    moment sums are anchored at the chain's FIRST accumulated draw
    (``anchor``) — autocovariances are shift-invariant, so centering on a
    typical-set point keeps the float32 sums catastrophic-cancellation
    free without knowing the mean in advance; the true chain mean is
    recovered as ``anchor + s1/n``.

    n       ()      draws accumulated
    anchor  (d,)    first draw (centering anchor)
    s1      (d,)    sum of centered draws            y_t = x_t - anchor
    s2      (d,)    sum of squared centered draws
    cross   (L, d)  lagged cross-products: row l-1 holds sum_t y_t*y_{t-l}
    ring    (L, d)  last L centered draws, most recent first
    head    (L, d)  first L centered draws (head[i] = y_{i+1})
    """

    n: Array
    anchor: Array
    s1: Array
    s2: Array
    cross: Array
    ring: Array
    head: Array


def stream_diag_init(ndim: int, lags: int = STREAM_DIAG_LAGS,
                     dtype=jnp.float32) -> StreamDiagState:
    """Zero-initialized accumulator for one chain (vmap for an ensemble)."""
    return StreamDiagState(
        n=jnp.zeros((), jnp.int32),
        anchor=jnp.zeros((ndim,), dtype),
        s1=jnp.zeros((ndim,), dtype),
        s2=jnp.zeros((ndim,), dtype),
        cross=jnp.zeros((lags, ndim), dtype),
        ring=jnp.zeros((lags, ndim), dtype),
        head=jnp.zeros((lags, ndim), dtype),
    )


def stream_diag_update(s: StreamDiagState, x: Array) -> StreamDiagState:
    """Merge one draw into the accumulator — O(L*d), jit/scan-safe.

    The ring rows for not-yet-seen lags are zero, so their cross-product
    contributions vanish without masking; ``head`` captures the first L
    draws once (rows past L never match the write index).
    """
    lags = s.ring.shape[0]
    anchor = jnp.where(s.n == 0, x, s.anchor)
    y = (x - anchor).astype(s.s1.dtype)
    cross = s.cross + s.ring * y[None, :]
    head = jnp.where(
        (jnp.arange(lags) == s.n)[:, None], y[None, :], s.head
    )
    ring = jnp.concatenate([y[None, :], s.ring[:-1]], axis=0)
    return StreamDiagState(
        n=s.n + 1,
        anchor=anchor,
        s1=s.s1 + y,
        s2=s.s2 + y * y,
        cross=cross,
        ring=ring,
        head=head,
    )


class HMCState(NamedTuple):
    z: Array  # flat unconstrained position, shape (d,)
    potential_energy: Array  # scalar
    grad: Array  # shape (d,)


class CentredState(NamedTuple):
    """What a per-chain program carries for a model that can centre
    (`model.Centering`, `FlatModel.chain_centering`): the chain's state, its
    ``potential_energy`` RELATIVE to the constant of the row ``center``
    beside it, so that leaf energies, multinomial weights and the accept
    statistic are differences of small numbers and keep float32's
    resolution at any number of rows.  A model without `Model.center_data`
    carries the plain `HMCState`."""

    state: HMCState
    center: Array  # (width,): the constant, then what the model keeps


class HMCInfo(NamedTuple):
    accept_prob: Array  # mean MH accept prob (dual-averaging signal)
    is_accepted: Array
    is_divergent: Array
    energy: Array  # H at the accepted state
    num_grad_evals: Array


def scan_progress(label: str, every):
    """jit-safe in-loop progress for transition scans (telemetry opt-in).

    Returns ``tick(i, accept_prob)`` — callable INSIDE a jitted
    ``lax.scan`` body — that fires a ``jax.debug.callback`` into the
    ambient `telemetry` trace every ``every`` transitions, or None when
    disabled (``every`` falsy), in which case callers must skip the call
    so the compiled program is bit-identical to the untraced one.

    The callback is unordered (no sequencing constraint on the device
    program) and the host side is rate-limited by the trace's heartbeat,
    so a vmap-unrolled batch of callbacks cannot flood the trace file.
    """
    if not every:
        return None
    from .. import telemetry

    def _host(step, accept):
        telemetry.heartbeat(label, step, accept)

    def tick(i, accept_prob):
        jax.lax.cond(
            (i + 1) % every == 0,
            lambda a: jax.debug.callback(_host, i, a, ordered=False),
            lambda a: None,
            accept_prob,
        )

    return tick


def value_and_grad_of(potential_fn: PotentialFn):
    """Use the potential's fused value_and_grad when it provides one
    (sharded models pack value+grad into a single psum — see model.Potential);
    fall back to autodiff otherwise."""
    vag = getattr(potential_fn, "value_and_grad", None)
    return vag if vag is not None else jax.value_and_grad(potential_fn)


def init_state(potential_fn: PotentialFn, z: Array) -> HMCState:
    pe, grad = value_and_grad_of(potential_fn)(z)
    return HMCState(z=z, potential_energy=pe, grad=grad)


def chain_potential(fm, data, carried):
    """A per-chain program's view of what it carries: ``(potential_fn,
    HMCState, rewrap)``.  ``fm``: the `model.FlatModel`, or a stand-in that
    binds (`profiling.DispatchProbe`).  A model that can centre (`model.Centering`,
    `FlatModel.chain_centering`) carries a `CentredState`: the potential is
    bound relative to the chain's centre, and ``rewrap`` puts the centre
    back beside the state the program leaves.  Every other model carries
    the plain state and compiles the plain program."""
    if getattr(fm, "chain_centering", None) is None or data is None:
        return fm.bind(data), carried, lambda state: state
    centre = carried.center
    return (fm.bind_chain(data, centre), carried.state,
            lambda state: CentredState(state, centre))


def chain_recentred(fm, data, carried):
    """``carried`` with the chain's centre moved to where the chain stands,
    and its state evaluated again relative to it: one gradient.  Warm-up
    moves far (from the start positions to the typical set), so each of its
    programs starts with this, as ChEES's do (`chees.make_chees_parts`,
    ``recentre``); sampling keeps the centre warm-up's last program left."""
    cen = getattr(fm, "chain_centering", None)
    if cen is None or data is None:
        return carried
    st = carried.state
    centre = cen.at(st.z[None], st.potential_energy[None],
                    carried.center[None])[0]
    return CentredState(init_state(fm.bind_chain(data, centre), st.z), centre)


def kinetic_energy(r: Array, inv_mass_diag: Array) -> Array:
    return 0.5 * jnp.sum(inv_mass_diag * r * r)


def sample_momentum(key: Array, inv_mass_diag: Array) -> Array:
    # r ~ N(0, M) with M = diag(1/inv_mass_diag)
    eps = jax.random.normal(key, inv_mass_diag.shape, inv_mass_diag.dtype)
    return eps * jax.lax.rsqrt(inv_mass_diag)


def leapfrog_step(
    potential_fn: PotentialFn,
    z: Array,
    r: Array,
    grad: Array,
    step_size: Array,
    inv_mass_diag: Array,
):
    """One velocity-Verlet step — THE integrator, shared by every kernel."""
    r = r - 0.5 * step_size * grad
    z = z + step_size * (inv_mass_diag * r)
    pe, grad = value_and_grad_of(potential_fn)(z)
    r = r - 0.5 * step_size * grad
    return z, r, grad, pe


def leapfrog(
    potential_fn: PotentialFn,
    z: Array,
    r: Array,
    grad: Array,
    step_size: Array,
    inv_mass_diag: Array,
    num_steps: int,
):
    """Velocity-Verlet integrator, ``num_steps`` full steps under lax.scan."""

    def one_step(carry, _):
        z, r, grad, _ = carry
        z, r, grad, pe = leapfrog_step(potential_fn, z, r, grad, step_size, inv_mass_diag)
        return (z, r, grad, pe), None

    pe0 = jnp.zeros(())  # overwritten on first step
    (z, r, grad, pe), _ = jax.lax.scan(one_step, (z, r, grad, pe0), None, length=num_steps)
    return z, r, grad, pe
