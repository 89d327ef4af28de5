"""Plain reference `logistic_sharded`: flat Bayesian logistic regression over
rows that lie in shards, one a device (`rows/glm_rows_sharded.py`).  The same
posterior as `references/logistic.py`: unconstrained position z = beta[d],
prior beta ~ N(0, 2.5), float32 at `highest`, block partials summed in
float64.  Each shard goes through `lib/glm.ll_parts` where it lies; the
shards' float64 sums are added on the host.  Nothing of the program is
imported, and nothing here stands in for a collective: a sum over shards is a
Python loop.
"""

import numpy as np

from lib import glm


def _shards(rows):
    """[(x_i, y_i), ...]: every shard's rows as arrays on the shard's own
    device, in shard order."""
    def parts(a):
        return [s.data for s in sorted(a.addressable_shards,
                                       key=lambda s: s.index[0].start or 0)]

    return list(zip(parts(rows["x"]), parts(rows["y"])))


def _sum_over_shards(rows, beta, hessian):
    """{name: float64 sum over all rows} of `glm.ll_parts`' outputs at the
    chains' `beta` (C, d).  Every shard's program is started before the first
    result is fetched, so the devices work side by side."""
    import jax.numpy as jnp

    chains = beta.shape[0]
    pending = []
    for x, y in _shards(rows):
        n, d = x.shape
        pending.append(glm.ll_parts(n, d, 0, chains, hessian)(
            x, y, jnp.zeros((n,), jnp.int32), jnp.asarray(beta, jnp.float32),
            jnp.zeros((chains, 1), jnp.float32)))
    sums = [glm.sum64(p) for p in pending]
    return {k: sum(s[k] for s in sums) for k in sums[0]}


def potential_and_grad(rows, z):
    """Potential energy (minus log posterior density) and its gradient at
    every chain's position `z` (C, d): ((C,), (C, d)) float64."""
    beta = np.asarray(z, np.float64)
    parts = _sum_over_shards(rows, beta, False)
    lp = parts["ll"] + glm.log_norm(beta, 2.5).sum(axis=1)
    return -lp, -(parts["dbeta"].T - beta / 2.5 ** 2)


def laplace(rows, iters=25, tol=1e-3):
    """Mode and marginal standard deviations of the posterior by Newton's
    method from beta = 0, as `references/logistic.py` (same damping, same
    stop).  Returns (mode (d,), sd (d,)); raises if Newton does not settle."""
    d = rows["x"].shape[1]
    beta = np.zeros(d)
    for _ in range(iters):
        parts = _sum_over_shards(rows, beta[None], True)
        grad = parts["dbeta"][:, 0] - beta / 2.5 ** 2
        hess = parts["hess"] + np.eye(d) / 2.5 ** 2
        cov = np.linalg.inv(hess)
        step = cov @ grad
        sd = np.sqrt(np.diag(cov))
        # damp the first, far steps: at most a unit in each coordinate
        scale = min(1.0, 1.0 / max(np.max(np.abs(step)), 1e-30))
        beta = beta + scale * step
        if np.max(np.abs(step) / sd) < tol:
            return beta, sd
    raise RuntimeError("Newton did not settle: the reference has no mode")
