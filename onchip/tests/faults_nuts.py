#!/usr/bin/env python3
"""Faults of the flagship under NUTS, planted in the program underneath the
harness: what `correct` has to catch in `hier_n16m.nuts`.  Each takes
`patch(obj, name, value)` as the faults of `faults.py` do
(`monkeypatch.setattr` in the tests, plain `setattr` here).

Run as a script it reads a fault at the cell's own size on the chip (PERF.md
gives the readings): `python3 onchip/tests/faults_nuts.py <fault> <run.py's
arguments>` plants the fault and drives a whole run of the harness over it.
Not part of the benchmark's own runs.
"""

import importlib.util
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def uncentred(patch):
    """The program as it stood before PR 38: the per-chain kernels sum the
    plain potential (the grouped model without `center_data`).  Nothing to
    see at toy size, where float32 holds the potential; read at the cell's
    size on the chip."""
    from stark_tpu.model import Model
    from stark_tpu.models import logistic as lg

    patch(lg.FusedHierLogisticGrouped, "center_data", Model.center_data)


def plain_float32_sum(patch):
    """The same at toy size: the log-likelihood in steps of 1 nat (the last
    bit of a float32 between 8.4e6 and 1.7e7, where the cell's chains stand),
    planted, because the tiles' sums lose nothing over a few thousand rows."""
    import jax.numpy as jnp

    from stark_tpu.ops import hier_fused

    def stepped(partials, center):
        total = jnp.round(jnp.sum(partials, axis=0))
        return total if center is None else total - center

    patch(hier_fused, "_sum_tiles", stepped)


def _stale(real):
    """`leapfrog_step` with the second half-kick's gradient the start's."""

    def stale(potential_fn, z, r, grad, step_size, inv_mass_diag):
        z1, _, grad1, pe1 = real(potential_fn, z, r, grad, step_size,
                                 inv_mass_diag)
        return z1, r - step_size * grad, grad1, pe1

    return stale


def stale_gradient(patch):
    """A leaf whose second half-kick takes the gradient of the leaf's START,
    in the sampling blocks' trees (and so in the leaf the check takes), not in
    warm-up's: position, potential and the carried gradient stay consistent,
    the momentum and so every leaf's energy are wrong by some eps^2 v.H.v / 2.
    Planted in warm-up too (`stale_gradient_everywhere`), dual averaging
    shrinks the step until the wrong energies are small, and a leaf reads as a
    sound one does: PERF.md section 6, PR 38."""
    from stark_tpu import sampler
    from stark_tpu.kernels import nuts

    real, drive = nuts.leapfrog_step, sampler.drive_segmented_warmup
    warm = {"done": False}

    def stale(*args):  # which leaf: read when a program is traced
        return (_stale(real) if warm["done"] else real)(*args)

    def driven(*a, **kw):
        try:
            return drive(*a, **kw)
        finally:
            warm["done"] = True

    patch(nuts, "leapfrog_step", stale)
    patch(sampler, "drive_segmented_warmup", driven)


def stale_gradient_everywhere(patch):
    """The same in every program, warm-up's too: what adaptation hides."""
    from stark_tpu.kernels import nuts

    patch(nuts, "leapfrog_step", _stale(nuts.leapfrog_step))


def state_handed_back(patch):
    """A transition that returns the state it was given."""
    from stark_tpu import sampler
    from stark_tpu.kernels import nuts

    real = nuts.nuts_step

    def frozen(key, state, **kw):
        _, info = real(key, state, **kw)
        return state, info

    patch(sampler, "nuts_step", frozen)


def tree_of_64_leaves(patch):
    """A tree that counts one leaf more than its depth can hold: the last
    doubling of a full tree reports 2**depth leaves, 64 at the cell's depth of
    6."""
    from stark_tpu.kernels import nuts

    real = nuts._merge_traj

    def one_more(traj, sub, *a, **kw):
        new = real(traj, sub, *a, **kw)
        full = new.num_leaves == (1 << new.depth) - 1
        return new._replace(
            num_leaves=new.num_leaves + (full & (new.depth > 1)))

    patch(nuts, "_merge_traj", one_more)


FAULTS = {f.__name__: f for f in (
    uncentred, plain_float32_sum, stale_gradient, stale_gradient_everywhere,
    state_handed_back, tree_of_64_leaves)}

if __name__ == "__main__":
    # the fault imports the program before run.py has put the checkout on
    # the path
    sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
    FAULTS[sys.argv[1]](setattr)
    spec = importlib.util.spec_from_file_location(
        "onchip_run", os.path.join(os.path.dirname(HERE), "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    run.main(sys.argv[2:])
