"""Plain reference `lmm`: the linear mixed model with random intercepts and
slopes.  Unconstrained position z = intercept, beta[d], u_raw[G x Q] (group by
group), log tau[Q], log sigma; mean mu = intercept + x.beta +
sum_q z_q u[g, q] with u = tau * u_raw (non-centred), y ~ Normal(mu, sigma);
priors (the model's documented ones, `LinearMixedModel.log_prior`)
intercept ~ N(0, 5), beta ~ N(0, 2.5), u_raw ~ N(0, 1), tau ~ half-N(0, 1),
sigma ~ half-N(0, 1); the log-Jacobians of tau and sigma are log tau and
log sigma.  Written out in straightforward `jax.numpy`, float32 at `highest`
matmul precision, rows in blocks, block partials summed in float64 on the
host; the u-gradient is a segment sum a block.  Rows come from the seed
through the configuration's rows generator (host arrays; the device holds
them lane-major, `rows/lmm_rows.py` says why); nothing of the program is
imported and nothing it made is used."""

import functools

import numpy as np

#: rows in a block of the reference (81 920 000 = 640 blocks)
BLOCK = 128_000


def _blocks(n):
    if n <= BLOCK:
        return 1, n
    if n % BLOCK:
        raise ValueError(f"the reference wants N a multiple of {BLOCK}")
    return n // BLOCK, BLOCK


def log_norm(x, scale):
    return -0.5 * (x / scale) ** 2 - np.log(scale) - 0.5 * np.log(2 * np.pi)


@functools.lru_cache(maxsize=None)
def ll_parts(n, d, q, groups, chains):
    """Jitted: rows (lane-major), parameters of all chains -> per-block
    partial sums of the squared residuals and of the residual's weighted
    sums."""
    import jax
    import jax.numpy as jnp

    nblk, blk = _blocks(n)

    def fn(xt, zt, g, y, ic, beta, u):
        # u: (G, C * Q), so that a row's gather and scatter move one slice

        def one(i):
            def rows(a):
                return jax.lax.dynamic_slice_in_dim(a, i * blk, blk, a.ndim - 1)

            xs, zs, gs, ys = rows(xt).T, rows(zt).T, rows(g), rows(y)
            ug = u[gs].reshape(blk, chains, q)
            mu = ic[None, :] + xs @ beta.T \
                + jnp.sum(zs[:, None, :] * ug, axis=-1)  # (blk, C)
            r = ys[:, None] - mu
            du = jax.ops.segment_sum(
                (r[:, :, None] * zs[:, None, :]).reshape(blk, chains * q),
                gs, num_segments=groups)
            return {"ssr": jnp.sum(r * r, axis=0), "dic": jnp.sum(r, axis=0),
                    "dbeta": xs.T @ r, "du": du.T}  # (C,) (C,) (d, C) (C*Q, G)

        return jax.lax.map(one, jnp.arange(nblk))

    def run(*args):
        with jax.default_matmul_precision("highest"):
            return fn(*args)

    return jax.jit(run)


def potential_and_grad(rows, z):
    """Potential energy (minus log posterior density, unconstrained, with the
    log-Jacobians) and its gradient at every chain's position `z` (C, ndim):
    ((C,), (C, ndim)) float64."""
    import jax.numpy as jnp

    x, zz, g, y = rows["x"], rows["z"], rows["g"], rows["y"]
    (n, d), q = x.shape, zz.shape[1]
    z = np.asarray(z, np.float64)
    chains, groups = z.shape[0], (z.shape[1] - d - q - 2) // q
    ic, beta = z[:, 0], z[:, 1:1 + d]
    raw = z[:, 1 + d:1 + d + groups * q].reshape(chains, groups, q)
    lt, ls = z[:, -q - 1:-1], z[:, -1]
    tau, sigma = np.exp(lt), np.exp(ls)
    u = tau[:, None, :] * raw  # (C, G, Q)
    parts = ll_parts(n, d, q, groups, chains)(
        np.ascontiguousarray(x.T), np.ascontiguousarray(zz.T), g, y,
        jnp.asarray(ic, jnp.float32), jnp.asarray(beta, jnp.float32),
        jnp.asarray(u.transpose(1, 0, 2).reshape(groups, -1), jnp.float32))
    parts = {k: np.asarray(v, np.float64).sum(axis=0)
             for k, v in parts.items()}
    du = parts["du"].reshape(chains, q, groups).transpose(0, 2, 1) \
        / sigma[:, None, None] ** 2  # (C, G, Q): d ll / d u
    ll = (-0.5 * parts["ssr"] / sigma ** 2 - n * ls
          - 0.5 * n * np.log(2 * np.pi))
    lp = (ll + log_norm(ic, 5.0) + log_norm(beta, 2.5).sum(axis=1)
          + log_norm(raw, 1.0).sum(axis=(1, 2))
          + (log_norm(tau, 1.0) + np.log(2.0)).sum(axis=1) + lt.sum(axis=1)
          + log_norm(sigma, 1.0) + np.log(2.0) + ls)
    dic = parts["dic"] / sigma ** 2 - ic / 25.0
    dbeta = parts["dbeta"].T / sigma[:, None] ** 2 - beta / 2.5 ** 2
    draw = tau[:, None, :] * du - raw
    dlt = tau * (du * raw).sum(axis=1) - tau ** 2 + 1.0
    dls = parts["ssr"] / sigma ** 2 - n - sigma ** 2 + 1.0
    grad = np.concatenate(
        [dic[:, None], dbeta, draw.reshape(chains, -1), dlt, dls[:, None]],
        axis=1)
    return -lp, -grad
