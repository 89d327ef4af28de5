import jax
import jax.numpy as jnp
import numpy as np

from stark_tpu.kernels.base import init_state, kinetic_energy, leapfrog, sample_momentum
from stark_tpu.kernels.hmc import hmc_step
import pytest


def std_normal_potential(z):
    return 0.5 * jnp.sum(z * z)


def test_leapfrog_energy_conservation():
    d = 4
    key = jax.random.PRNGKey(0)
    z = jax.random.normal(key, (d,))
    inv_mass = jnp.ones(d)
    r = sample_momentum(jax.random.PRNGKey(1), inv_mass)
    pe, grad = jax.value_and_grad(std_normal_potential)(z)
    e0 = pe + kinetic_energy(r, inv_mass)
    z1, r1, g1, pe1 = leapfrog(std_normal_potential, z, r, grad, 0.01, inv_mass, 100)
    e1 = pe1 + kinetic_energy(r1, inv_mass)
    assert abs(float(e1 - e0)) < 1e-3


def test_leapfrog_reversibility():
    d = 3
    z = jax.random.normal(jax.random.PRNGKey(2), (d,))
    inv_mass = jnp.ones(d)
    r = sample_momentum(jax.random.PRNGKey(3), inv_mass)
    _, grad = jax.value_and_grad(std_normal_potential)(z)
    z1, r1, g1, _ = leapfrog(std_normal_potential, z, r, grad, 0.1, inv_mass, 25)
    # integrate back with flipped momentum
    z2, r2, _, _ = leapfrog(std_normal_potential, z1, -r1, g1, 0.1, inv_mass, 25)
    np.testing.assert_allclose(np.asarray(z2), np.asarray(z), atol=1e-4)
    np.testing.assert_allclose(np.asarray(-r2), np.asarray(r), atol=1e-4)


def test_hmc_std_normal_moments():
    d = 5
    inv_mass = jnp.ones(d)
    state = init_state(std_normal_potential, jnp.zeros(d))

    def step(carry, key):
        st, = carry
        st, info = hmc_step(
            key, st, std_normal_potential, jnp.asarray(0.25), inv_mass, 8
        )
        return (st,), st.z

    keys = jax.random.split(jax.random.PRNGKey(4), 4000)
    _, zs = jax.lax.scan(jax.jit(step), (state,), keys)
    zs = np.asarray(zs)[500:]
    assert np.all(np.abs(zs.mean(0)) < 0.15)
    assert np.all(np.abs(zs.var(0) - 1.0) < 0.2)


@pytest.mark.slow
def test_segmented_backend_matches_posterior():
    """Dispatch-bounded execution (JaxBackend(dispatch_steps=...)) is
    statistically equivalent to the monolithic dispatch, including with a
    remainder segment (130 does not divide 500)."""
    import stark_tpu
    from stark_tpu.backends.jax_backend import JaxBackend
    from stark_tpu.models import EightSchools, eight_schools_data

    post = stark_tpu.sample(
        EightSchools(), eight_schools_data(),
        backend=JaxBackend(dispatch_steps=130),
        chains=4, num_warmup=500, num_samples=500, seed=1,
    )
    s = post.summary()
    assert abs(float(s["mu"]["mean"]) - 4.4) < 1.0
    assert abs(float(s["tau"]["mean"]) - 3.6) < 1.2
    assert post.max_rhat() < 1.02
    assert post.sample_stats["dispatch_steps"] == 130


def test_dispatch_bound_is_recorded_and_never_invented(monkeypatch):
    """The bound a run executed under rides in its sample stats (0 = one
    monolithic program), and an unset bound stays unset on every platform:
    nothing re-bounds a run behind the caller's back."""
    from stark_tpu.backends import JaxBackend, ShardedBackend
    from stark_tpu.backends.base import annotate_dispatch
    from stark_tpu.parallel.mesh import make_mesh

    stats = {}
    annotate_dispatch(stats, None)
    assert stats == {"dispatch_steps": 0}
    annotate_dispatch(stats, 50)
    assert stats == {"dispatch_steps": 50}
    monkeypatch.delenv("STARK_DISPATCH_STEPS", raising=False)
    assert JaxBackend().dispatch_steps is None
    assert ShardedBackend(make_mesh()).dispatch_steps is None
    monkeypatch.setenv("STARK_DISPATCH_STEPS", "7")
    assert JaxBackend().dispatch_steps == 7
