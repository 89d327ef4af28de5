#!/usr/bin/env python3
"""The fault only a data-sharded deployment can have, planted in the program
underneath the harness: what `correct` has to catch in a cell whose rows lie
over a mesh.  Takes `patch(obj, name, value)` as the faults of `faults.py` do
(`monkeypatch.setattr` in the tests, plain `setattr` here).

Run as a script it reads the fault at the cell's own size on the chips
(PERF.md gives the readings): `python3 onchip/tests/faults_sharded.py <fault>
<run.py's arguments>` plants the fault and drives a whole run of the harness
over it.  Not part of the benchmark's own runs.
"""

import importlib.util
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def shard_left_out(patch):
    """One shard left out of the sum: the first chip of the `data` axis
    streams its rows like the others and adds nothing, so the psum carries
    three quarters of the log-likelihood and of its gradient."""
    import jax
    import jax.numpy as jnp

    from stark_tpu.models import logistic as lg

    real = lg.FusedLogistic.log_lik

    def all_but_the_first(self, p, data):
        return jnp.where(jax.lax.axis_index("data") == 0, 0.0,
                         real(self, p, data))

    patch(lg.FusedLogistic, "log_lik", all_but_the_first)


FAULTS = {f.__name__: f for f in (shard_left_out,)}

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
