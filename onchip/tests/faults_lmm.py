#!/usr/bin/env python3
"""Faults of the random-slopes deployment, planted in the program underneath
the harness: what `correct` has to catch in `lmm_n49m.sample`.  Each takes
`patch(obj, name, value)` as the faults of `faults.py` do
(`monkeypatch.setattr` in the tests, plain `setattr` here); the faults there
that any sampling cell can have (a state handed back, a draw altered, a second
proposal) hold for this cell too.

Run as a script it reads a fault at the cell's own size on the chip (PERF.md
gives the readings): `python3 onchip/tests/faults_lmm.py <fault> <run.py's
arguments>` plants the fault and drives a whole run of the harness over it.
Not part of the benchmark's own runs.
"""

import importlib.util
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def plain_float32_sum(patch):
    """The potential as a float32 holds it over tens of millions of rows: the
    log-likelihood in steps of 8 nats (the last bit of a float32 between 6.7e7
    and 1.3e8, where the cell's chains stand).  The tiles' sums lose nothing
    at toy size, so the steps are planted; the cell's size is read with
    `uncentred` (PERF.md, section 6)."""
    import jax.numpy as jnp

    from stark_tpu.ops import hier_fused

    def stepped(partials, center):
        total = 8.0 * jnp.round(jnp.sum(partials, axis=0) / 8.0)
        return total if center is None else total - center

    patch(hier_fused, "_sum_tiles", stepped)


def half_the_outcomes(patch):
    """The grouped kernel's tiles cannot be cut: the same fault in kind as
    half the rows left out is every row's outcome pulled half way to 0."""
    from stark_tpu.models import lmm

    real = lmm.FusedLinearMixedModelGrouped.log_lik

    def half(self, p, data):
        return real(self, p, dict(data, y=0.5 * data["y"]))

    patch(lmm.FusedLinearMixedModelGrouped, "log_lik", half)


def uncentred(patch):
    """The program as it stood before PR 32: one chip sums the plain
    potential (`Model.center_per_chain` off).  Nothing to see at toy size;
    read at the cell's size on the chip."""
    from stark_tpu.models import lmm

    patch(lmm.FusedLinearMixedModelGrouped, "center_per_chain", False)


FAULTS = {f.__name__: f for f in (plain_float32_sum, half_the_outcomes,
                                  uncentred)}

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
