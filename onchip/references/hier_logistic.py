"""Plain reference `hier_logistic`: hierarchical logistic regression with
group intercepts.  Unconstrained position z = beta[d], alpha0,
log sigma_alpha, alpha_raw[G]; priors (the model's documented ones)
beta ~ N(0, 2.5), alpha0 ~ N(0, 5), sigma_alpha ~ half-N(0, 1),
alpha_raw ~ N(0, 1); alpha_g = alpha0 + sigma_alpha * alpha_raw_g.  Rows come
from the seed through the configuration's rows generator; nothing of the
program is imported."""

import numpy as np

from lib import glm


def potential_and_grad(rows, z):
    """Potential energy (minus log posterior density, unconstrained, with the
    log-Jacobian) and its gradient at every chain's position `z` (C, ndim):
    ((C,), (C, ndim)) float64."""
    import jax.numpy as jnp

    x, y, g = rows["x"], rows["y"], rows["g"]
    n, d = x.shape
    z = np.asarray(z, np.float64)
    chains, groups = z.shape[0], z.shape[1] - d - 2
    beta, alpha0, s, raw = z[:, :d], z[:, d], z[:, d + 1], z[:, d + 2:]
    sigma = np.exp(s)
    alpha = alpha0[:, None] + sigma[:, None] * raw
    parts = glm.sum64(glm.ll_parts(n, d, groups, chains, False)(
        x, y, g, jnp.asarray(beta, jnp.float32),
        jnp.asarray(alpha, jnp.float32)))
    da = parts["dalpha"].T  # (C, G): d ll / d alpha_g
    lp = (parts["ll"] + glm.log_norm(beta, 2.5).sum(axis=1)
          + glm.log_norm(alpha0, 5.0) + glm.log_norm(sigma, 1.0) + np.log(2.0)
          + glm.log_norm(raw, 1.0).sum(axis=1) + s)
    dbeta = parts["dbeta"].T - beta / 2.5 ** 2
    dalpha0 = da.sum(axis=1) - alpha0 / 25.0
    ds = sigma * (da * raw).sum(axis=1) - sigma ** 2 + 1.0
    draw = sigma[:, None] * da - raw
    grad = np.concatenate(
        [dbeta, dalpha0[:, None], ds[:, None], draw], axis=1)
    return -lp, -grad
