"""Rows generator `glm_rows`: rows of a (grouped) logistic regression from
the seeds.  A copy of `stark_tpu.models.logistic.synth_logistic_data` (sound
generator; PERF.md verdict table), made on the device in one jitted call:
x ~ N(0, 1), beta ~ N(0, 1), optional group intercepts 0.5 * N(0, 1),
y ~ Bernoulli(sigmoid(x.beta + alpha[g])).

Which rows there are is the configuration's (`params["posterior_seed"]`):
every run of a cell samples the same posterior, because what adaptation costs
differs several-fold from one posterior to the next (PERF.md, Open
questions).  The run's `--seed` draws the order in which the rows lie on the
chip (and, in the driver, the chains' seed).

A configuration names its generator (`"rows": {"generator": ..., "params":
...}`); one that needs other rows, or rows made shard by shard, brings a file
of its own beside this one.
"""

import functools

from lib.seeds import seed_words


@functools.lru_cache(maxsize=None)
def _make(n, d, groups):
    import jax
    import jax.numpy as jnp

    def make(key, order_key):
        k1, k2, k3, k4, k5 = jax.random.split(key, 5)
        x = jax.random.normal(k1, (n, d), jnp.float32)
        beta = jax.random.normal(k2, (d,), jnp.float32)
        logits = jnp.einsum("nd,d->n", x, beta,
                            precision=jax.lax.Precision.HIGHEST)
        out = {"x": x}
        if groups:
            g = jax.random.randint(k3, (n,), 0, groups)
            alpha = 0.5 * jax.random.normal(k4, (groups,), jnp.float32)
            logits = logits + alpha[g]
            out["g"] = g
        u = jax.random.uniform(k5, (n,))
        out["y"] = (u < jax.nn.sigmoid(logits)).astype(jnp.float32)
        order = jax.random.permutation(order_key, n)
        return {k: v[order] for k, v in out.items()}

    return jax.jit(make)


def make(params, sizes, seed):
    """{"x": (n, d) f32, "y": (n,) 0/1 f32[, "g": (n,) int32]} on the default
    device: the rows of `params["posterior_seed"]` in the order of `seed`.
    The same seeds give the same rows in the same order."""
    import jax

    data_word, _ = seed_words(params["posterior_seed"])
    order_word, _ = seed_words(seed)
    return _make(int(sizes["n"]), int(sizes["d"]), int(sizes.get("groups", 0)))(
        jax.random.PRNGKey(data_word), jax.random.PRNGKey(order_word))
