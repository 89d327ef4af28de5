"""Rows generator `lmm_rows`: rows of a linear mixed model with random
intercepts and slopes from the seeds.  A copy of
`stark_tpu.models.lmm.synth_lmm_data`, made on the device in one jitted call:
x ~ N(0, 1) (n, d); z = [1, N(0, 1) ...] (n, q); g uniform over the groups;
beta ~ N(0, 1); u = tau * N(0, 1) (groups, q) with tau = (0.8, 0.4, ...);
y = 1 + x.beta + sum_q z_q u[g, q] + noise * N(0, 1).

Which rows there are is the configuration's (`params["posterior_seed"]`,
with `noise`); the run's `--seed` draws the order in which the rows lie (and,
in the driver, the chains' seed), as `glm_rows` does.

The rows come back on the host, as the model's `prepare_data` takes them for
its sort by group.  A TPU cannot hold them row by row: an (n, 8) float32 array
is laid out in tiles of 128 lanes, sixteen times its bytes (25 GB at 49M
rows; the compiler refuses it).  So they are made lane-major on the device,
(d, n) and (q, n), and `x` and `z` are the host copies' transposed views.
"""

import functools

from lib.seeds import seed_words


@functools.lru_cache(maxsize=None)
def _make(n, d, q, groups, noise):
    import jax
    import jax.numpy as jnp

    def make(key, order_key):
        ks = jax.random.split(key, 6)
        xt = jax.random.normal(ks[0], (d, n), jnp.float32)
        zt = jnp.concatenate(
            [jnp.ones((1, n), jnp.float32),
             jax.random.normal(ks[1], (q - 1, n), jnp.float32)], axis=0)
        g = jax.random.randint(ks[2], (n,), 0, groups)
        beta = jax.random.normal(ks[3], (d,), jnp.float32)
        tau = jnp.asarray([0.8] + [0.4] * (q - 1), jnp.float32)
        ut = tau[:, None] * jax.random.normal(ks[4], (q, groups), jnp.float32)
        mu = 1.0 + jnp.einsum("dn,d->n", xt, beta,
                              precision=jax.lax.Precision.HIGHEST) \
            + sum(zt[j] * ut[j][g] for j in range(q))
        y = mu + noise * jax.random.normal(ks[5], (n,), jnp.float32)
        order = jax.random.permutation(order_key, n)

        def ordered(a):  # lane by lane: a gather of whole columns is laid
            # out row by row, (n, d)
            return jnp.stack([row[order] for row in a])

        return {"xT": ordered(xt), "zT": ordered(zt), "g": g[order],
                "y": y[order]}

    return jax.jit(make)


def make(params, sizes, seed):
    """{"x": (n, d) f32, "z": (n, q) f32, "g": (n,) int32, "y": (n,) f32},
    numpy arrays on the host: the rows of `params["posterior_seed"]` in the
    order of `seed`.  The same seeds give the same rows in the same order."""
    import jax
    import numpy as np

    data_word, _ = seed_words(params["posterior_seed"])
    order_word, _ = seed_words(seed)
    made = _make(int(sizes["n"]), int(sizes["d"]), int(sizes["q"]),
                 int(sizes["groups"]), float(params["noise"]))(
        jax.random.PRNGKey(data_word), jax.random.PRNGKey(order_word))
    host = {k: np.asarray(v) for k, v in made.items()}
    return {"x": host["xT"].T, "z": host["zT"].T, "g": host["g"],
            "y": host["y"]}
