"""One leaf of the sampler's tree, twice: as the program takes it from a
checkpointed state, and as the configuration's plain reference takes the same
leaf.  `checks/sampler_trees.py` compares the two energy differences
(`leaf_dh_nats`): what an accept step, a multinomial weight and dual averaging
live on.

The momentum is drawn under the chain's own checkpointed inverse mass
(`kernels.base.sample_momentum`), so the leaf is one the sampler could take:
with the adapted step its dH is of order one.  (Drawn without the mass, at
these step sizes, the same probe reads a dH of 1e3 to 4e4 nats: PERF.md
section 6, PR 38.)
"""

import numpy as np


def program_leaf(model, data, state, max_depth, seed):
    """The first leaf of a NUTS subtree from every chain's checkpointed state,
    through the program's own `kernels.nuts._leaf_step` and its potential as
    the sampler binds it (relative to the chain's centre where the checkpoint
    holds one).  `state`: a sampling checkpoint's arrays (z, pe, grad,
    step_size, inv_mass and, from a program that centres, pe_center).
    -> {"z0", "r0", "z1", "r1", "dh", "step_size", "inv_mass"}, chains
    first, numpy."""
    import jax
    import jax.numpy as jnp

    from stark_tpu.kernels import nuts
    from stark_tpu.kernels.base import kinetic_energy, sample_momentum
    from stark_tpu.model import flatten_model

    fm = flatten_model(model)
    centre = state.get("pe_center")
    if centre is None or getattr(fm, "chain_centering", None) is None:
        centre, pe = (), state["pe"]
    else:  # the carried energy: the potential less the centre's constant
        pe = state["pe"].astype(np.float64) - np.float64(centre[:, 0])
        centre = (jnp.asarray(centre, jnp.float32),)

    def one(key, z, pe, grad, step, inv_mass, data, *centre):
        potential_fn = (fm.bind_chain(data, centre[0]) if centre
                        else fm.bind(data))
        key_mom, key_leaf = jax.random.split(key)
        r0 = sample_momentum(key_mom, inv_mass)
        energy0 = pe + kinetic_energy(r0, inv_mass)
        init, *stacks = nuts._subtree_init(z, r0, grad, energy0, max_depth)
        st = nuts._leaf_step(
            init, *stacks, jnp.zeros((), jnp.int32), key_leaf,
            potential_fn=potential_fn, directed_step=step,
            inv_mass_diag=inv_mass, energy0=energy0,
            slots=jnp.arange(max_depth, dtype=jnp.int32))[0]
        # the first leaf's weight is its own: log_weight = -(H - H0)
        return r0, st.z_far, st.r_far, -st.log_weight

    f32 = lambda a: jnp.asarray(np.asarray(a), jnp.float32)  # noqa: E731
    chains = state["z"].shape[0]
    r0, z1, r1, dh = jax.jit(jax.vmap(
        one, in_axes=(0,) * 6 + (None,) + (0,) * len(centre)))(
        jax.random.split(jax.random.PRNGKey(seed), chains), f32(state["z"]),
        f32(pe), f32(state["grad"]), f32(state["step_size"]),
        f32(state["inv_mass"]), data, *centre)
    return {"z0": np.asarray(state["z"]), "r0": np.asarray(r0),
            "z1": np.asarray(z1), "r1": np.asarray(r1),
            "dh": np.asarray(dh, np.float64),
            "step_size": np.asarray(state["step_size"], np.float64),
            "inv_mass": np.asarray(state["inv_mass"], np.float64)}


def reference_leaf(potential_and_grad, leaf, start=None):
    """The same leaf by the reference: one velocity-Verlet step in float64
    from (z0, r0) under the leaf's step and inverse mass, the gradients the
    reference's own.  `potential_and_grad(z)` -> ((C,), (C, ndim)); `start`:
    its value at z0 where the caller has it.  -> (dh (C,), z1 (C, ndim))."""
    z0 = np.asarray(leaf["z0"], np.float64)
    r0 = np.asarray(leaf["r0"], np.float64)
    eps = leaf["step_size"][:, None]
    inv_mass = leaf["inv_mass"]
    pe0, g0 = start if start is not None else potential_and_grad(z0)
    r = r0 - 0.5 * eps * g0
    # the position the reference evaluates is a float32, as the program's is
    z1 = (z0 + eps * inv_mass * r).astype(np.float32).astype(np.float64)
    pe1, g1 = potential_and_grad(z1)
    r1 = r - 0.5 * eps * g1

    def kinetic(r):
        return 0.5 * np.sum(inv_mass * r * r, axis=1)

    return (pe1 + kinetic(r1)) - (pe0 + kinetic(r0)), z1
