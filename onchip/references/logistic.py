"""Plain reference `logistic`: flat Bayesian logistic regression.
Unconstrained position z = beta[d]; prior beta ~ N(0, 2.5) (the model's
documented one).  Rows come from the seed through the configuration's rows
generator; nothing of the program is imported."""

import numpy as np

from lib import glm


def potential_and_grad(rows, z):
    """Potential energy (minus log posterior density) and its gradient at
    every chain's position `z` (C, d): ((C,), (C, d)) float64."""
    import jax.numpy as jnp

    x, y = rows["x"], rows["y"]
    n, d = x.shape
    beta = np.asarray(z, np.float64)
    chains = beta.shape[0]
    parts = glm.sum64(glm.ll_parts(n, d, 0, chains, False)(
        x, y, jnp.zeros((n,), jnp.int32), jnp.asarray(beta, jnp.float32),
        jnp.zeros((chains, 1), jnp.float32)))
    lp = parts["ll"] + glm.log_norm(beta, 2.5).sum(axis=1)
    return -lp, -(parts["dbeta"].T - beta / 2.5 ** 2)


def laplace(rows, iters=25, tol=1e-3):
    """Mode and marginal standard deviations of the posterior by Newton's
    method from beta = 0 (at N rows in the millions and d in the tens the
    posterior is normal to O(1/sqrt(N)) of a standard deviation).  Returns
    (mode (d,), sd (d,)); raises if Newton does not settle."""
    import jax.numpy as jnp

    x, y = rows["x"], rows["y"]
    n, d = x.shape
    fn = glm.ll_parts(n, d, 0, 1, True)
    g0 = jnp.zeros((n,), jnp.int32)
    beta = np.zeros(d)
    for _ in range(iters):
        parts = glm.sum64(fn(x, y, g0, jnp.asarray(beta[None], jnp.float32),
                             jnp.zeros((1, 1), jnp.float32)))
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
