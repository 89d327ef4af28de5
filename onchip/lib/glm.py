"""What the plain references of the logistic family share: the
log-likelihood of (grouped) logistic regression written out in
straightforward `jax.numpy`, float32 at `highest` matmul precision, rows in
blocks, block partials summed in float64 on the host.  Nothing of the program
is imported and nothing it made is used.  `references/logistic.py` and
`references/hier_logistic.py` put their priors and layouts on top.
"""

import functools

import numpy as np

#: rows in a block of the reference; the one-hot of a grouped block is
#: BLOCK x G float32 (500 MB at G = 1000)
BLOCK = 125_000


def _blocks(n):
    if n <= BLOCK:
        return 1, n
    if n % BLOCK:
        raise ValueError(f"the reference wants N a multiple of {BLOCK}")
    return n // BLOCK, BLOCK


def log_norm(x, scale):
    return -0.5 * (x / scale) ** 2 - np.log(scale) - 0.5 * np.log(2 * np.pi)


@functools.lru_cache(maxsize=None)
def ll_parts(n, d, groups, chains, hessian):
    """Jitted: rows, parameters of all chains -> per-block partial sums of the
    log-likelihood, its gradients, and (logistic, one chain) the Hessian."""
    import jax
    import jax.numpy as jnp

    nblk, blk = _blocks(n)

    def fn(x, y, g, beta, alpha):
        xb = x.reshape(nblk, blk, d)
        yb = y.reshape(nblk, blk)
        gb = g.reshape(nblk, blk) if groups else jnp.zeros((nblk, 1), jnp.int32)

        def one(args):
            xs, ys, gs = args
            logits = xs @ beta.T  # (blk, C)
            if groups:
                hot = (gs[:, None] == jnp.arange(groups)[None, :]).astype(
                    jnp.float32)
                logits = logits + hot @ alpha.T
            yy = ys[:, None]
            ll = jnp.sum(yy * jax.nn.log_sigmoid(logits)
                         + (1.0 - yy) * jax.nn.log_sigmoid(-logits), axis=0)
            p = jax.nn.sigmoid(logits)
            r = yy - p
            out = {"ll": ll, "dbeta": xs.T @ r}  # (C,), (d, C)
            if groups:
                out["dalpha"] = hot.T @ r  # (G, C)
            if hessian:
                w = (p * (1.0 - p))[:, 0]
                out["hess"] = xs.T @ (w[:, None] * xs)
            return out

        return jax.lax.map(one, (xb, yb, gb))

    def run(x, y, g, beta, alpha):
        with jax.default_matmul_precision("highest"):
            return fn(x, y, g, beta, alpha)

    return jax.jit(run)


def sum64(parts):
    return {k: np.asarray(v, np.float64).sum(axis=0) for k, v in parts.items()}
