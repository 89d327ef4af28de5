"""Rows generator `glm_rows_sharded`: the rows of a flat logistic regression
made shard by shard, each shard on its own chip, for a row stream that no
single device and no host array can hold (80M x 32 float32 is 10.24 GB and
2.56e9 elements).  The same model as `glm_rows` (x ~ N(0, 1), beta ~ N(0, 1),
y ~ Bernoulli(sigmoid(x.beta))); another stream of numbers, because every
shard draws from its own key.

Which rows there are is the configuration's (`params["posterior_seed"]`):
`beta` is drawn once, shard i's `x` and `u` from the rows' key folded with i.
The run's `--seed` draws the order in which the rows lie inside each shard
(its key folded with i; rows never change shards) and, in the driver, the
chains' seed.  One program, mapped over the `data` axis of the mesh the
configuration names (`sizes["data_shards"]` devices, in `jax.devices()`
order): shard i is born on device i and never leaves it.
"""

import functools

from lib.seeds import seed_words


def mesh_of(shards):
    """The `{"data": shards, "chains": 1}` mesh over the first devices: what
    the driver hands the program's backend, built here the same way."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    devs = jax.devices()
    if len(devs) < shards:
        raise SystemExit(
            f"onchip: the rows lie over {shards} devices and jax shows "
            f"{len(devs)}")
    return Mesh(np.asarray(devs[:shards]).reshape(shards, 1),
                ("data", "chains"))


@functools.lru_cache(maxsize=None)
def _make(n, d, shards):
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    if n % shards:
        raise ValueError(f"{n} rows do not divide over {shards} shards")
    rows = n // shards

    def shard(key, order_key):
        i = jax.lax.axis_index("data")
        k_beta, k_rows = jax.random.split(key)
        beta = jax.random.normal(k_beta, (d,), jnp.float32)
        k_x, k_u = jax.random.split(jax.random.fold_in(k_rows, i))
        x = jax.random.normal(k_x, (rows, d), jnp.float32)
        logits = jnp.einsum("nd,d->n", x, beta,
                            precision=jax.lax.Precision.HIGHEST)
        u = jax.random.uniform(k_u, (rows,))
        y = (u < jax.nn.sigmoid(logits)).astype(jnp.float32)
        order = jax.random.permutation(
            jax.random.fold_in(order_key, i), rows)
        return x[order], y[order]

    return jax.jit(shard_map(
        shard, mesh=mesh_of(shards), in_specs=(P(), P()),
        out_specs=(P("data", None), P("data")), check_vma=False))


def make(params, sizes, seed):
    """{"x": (n, d) f32, "y": (n,) 0/1 f32}: global arrays sharded by row over
    the `data` axis, shard i on device i.  The same seeds give the same rows
    on the same shards in the same order."""
    import jax

    if sizes.get("groups"):
        raise ValueError("glm_rows_sharded makes a flat model's rows")
    data_word, _ = seed_words(params["posterior_seed"])
    order_word, _ = seed_words(seed)
    x, y = _make(int(sizes["n"]), int(sizes["d"]), int(sizes["data_shards"]))(
        jax.random.PRNGKey(data_word), jax.random.PRNGKey(order_word))
    return {"x": x, "y": y}
