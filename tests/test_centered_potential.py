"""The potential of a data-sharded ChEES run is summed relative to a constant
carried beside the energies (PR 28): over tens of millions of rows a float32
log-likelihood's last bit is whole nats, coarser than the energy differences
the accept step lives on.  The op, the model's hook, the flat model's
`Centering`, the programs' carry and the checkpoint, at toy size on the host
devices."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from stark_tpu import Model, prepare_model_data
from stark_tpu.backends import ShardedBackend
from stark_tpu.chees import make_chees_parts
from stark_tpu.model import flatten_model
from stark_tpu.models import FusedLogistic, Logistic
from stark_tpu.models.logistic import synth_logistic_data
from stark_tpu.ops.logistic_fused import _sum_tiles, logistic_loglik
from stark_tpu.parallel.mesh import make_mesh, row_partition_specs
from stark_tpu.parallel.primitives import map_shards
from stark_tpu.sampler import SamplerConfig

N, D, C = 4096, 8, 8


@pytest.fixture(scope="module")
def rows():
    data, true = synth_logistic_data(jax.random.PRNGKey(5), N, D)
    return prepare_model_data(FusedLogistic(D), data), true["beta"]


def _row_specs(data):
    """The prepared leaves cut by row as the sharded backend cuts them."""
    return row_partition_specs(
        data, "data", FusedLogistic(D).data_shard_row_axes(data))


@pytest.fixture(scope="module")
def mesh():
    return make_mesh({"data": 4, "chains": 1}, devices=jax.devices()[:4])


def _ll64(beta, rows):
    x = np.asarray(rows["xT"], np.float64).T
    y = np.asarray(rows["y"], np.float64)
    logits = x @ np.asarray(beta, np.float64)
    return float(np.sum(y * -np.logaddexp(0, -logits)
                        + (1 - y) * -np.logaddexp(0, logits)))


def test_sum_tiles_keeps_what_the_plain_sum_rounds_away():
    # 2048 tiles near -3000 each (the cell's: 8192 rows a tile): the total
    # is near -6e6, where float32 steps by 0.5; two positions whose tiles
    # differ by 1e-3 each differ by 2.048 in all
    rng = np.random.default_rng(0)
    a = (-3000.0 + 30.0 * rng.standard_normal((2048, 1, 1))).astype(np.float32)
    b = a + np.float32(1e-3)
    want = float(np.sum(b.astype(np.float64) - a.astype(np.float64)))
    center = jnp.sum(jnp.asarray(a))
    plain = float(_sum_tiles(jnp.asarray(b), None)[0, 0]
                  - _sum_tiles(jnp.asarray(a), None)[0, 0])
    kept = float(_sum_tiles(jnp.asarray(b), center)[0, 0]
                 - _sum_tiles(jnp.asarray(a), center)[0, 0])
    assert abs(kept - want) < 2e-3
    assert abs(plain - want) > 0.02  # the last bit of 6e6 is 0.5


@pytest.mark.parametrize("batched", [False, True], ids=["one_chain", "chains"])
def test_centered_op_is_the_plain_op_less_a_constant(rows, batched):
    data, beta = rows
    center = jnp.float32(_ll64(beta, data))
    betas = beta + 0.01 * jax.random.normal(jax.random.PRNGKey(1), (C, D))

    def both(b):
        plain = jax.value_and_grad(logistic_loglik)(b, data["xT"], data["y"])
        cent = jax.value_and_grad(logistic_loglik)(
            b, data["xT"], data["y"], center)
        return plain, cent

    (v, g), (vc, gc) = jax.vmap(both)(betas) if batched else both(betas[0])
    np.testing.assert_array_equal(np.asarray(g), np.asarray(gc))
    # the value: the float64 log-lik less the centre, to a resolution the
    # plain float32 value (last bit 2.4e-4 at 2.7e3) does not have
    want = np.array([_ll64(b, data) for b in np.atleast_2d(
        np.asarray(betas if batched else betas[0]))]) - float(center)
    np.testing.assert_allclose(np.atleast_1d(vc), want, atol=2e-4)
    np.testing.assert_allclose(np.asarray(v) - float(center),
                               np.asarray(vc), atol=2e-3)


def test_only_a_sharded_potential_of_a_model_with_the_hook_centres():
    assert flatten_model(FusedLogistic(D)).centering is None
    assert flatten_model(Logistic(D), axis_name="data").centering is None
    assert flatten_model(FusedLogistic(D), axis_name="data").centering
    assert Model().center_data({}, 0.0) is None


def _potential64(z, data):
    return np.array([-_ll64(b, data) + 0.5 * np.sum(b ** 2) / 2.5 ** 2
                     + D * np.log(2.5 * np.sqrt(2 * np.pi))
                     for b in np.asarray(z, np.float64)])


def test_centred_potential_is_the_plain_one_less_the_centre(rows, mesh):
    data, beta = rows
    fm = flatten_model(FusedLogistic(D), axis_name="data")
    z = beta + 0.01 * jax.random.normal(jax.random.PRNGKey(2), (C, D))

    def body(z, data):
        pe, grad = jax.vmap(fm.bind(data).value_and_grad)(z)
        pe_center = fm.centering.at(z, pe)[0]
        pe_c, grad_c = jax.vmap(fm.bind(data, pe_center).value_and_grad)(z)
        return pe, grad, pe_c, grad_c, pe_center

    specs = _row_specs(data)
    pe, grad, pe_c, grad_c, pe_center = map_shards(
        body, mesh=mesh, in_specs=(P(), specs),
        out_specs=(P(), P(), P(), P(), P()))(z, data)
    np.testing.assert_array_equal(np.asarray(grad), np.asarray(grad_c))
    # the centre is the likelihood's part of the first position's potential
    np.testing.assert_allclose(float(pe_center), -_ll64(z[0], data), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(pe_c) + float(pe_center),
                               np.asarray(pe), rtol=1e-6)
    # and the centred potential is small: the prior's part and what the
    # positions differ by, not the thousands of the whole
    assert float(np.max(np.abs(pe_c))) < 0.05 * float(np.min(np.abs(pe)))


def test_mesh_programs_carry_the_centre_beside_small_energies(rows, mesh):
    data, beta = rows
    model = FusedLogistic(D)
    cfg = SamplerConfig(kernel="chees", num_warmup=10, map_init_steps=0)
    ap = ShardedBackend(mesh).adaptive_parts(model, cfg, data)
    assert ap.fm.centering is not None
    z0 = ap.put_chains(
        beta + 0.01 * jax.random.normal(jax.random.PRNGKey(3), (C, D)))
    key = jax.random.PRNGKey(0)
    warm = ap.init_j(key, z0, ap.data)
    # set where the first chain stands: the likelihood's part there
    np.testing.assert_allclose(float(warm.pe_center),
                               -_ll64(np.asarray(z0)[0], data), rtol=1e-6)
    carry = ap.chees.finalize(warm)
    # no warm-up here: a step and a trajectory length the posterior bears
    carry = carry._replace(log_eps=ap.put_rep(jnp.log(jnp.float32(0.01))),
                           log_T=ap.put_rep(jnp.log(jnp.float32(0.05))))
    keys, us = jax.random.split(key, 3), jnp.ones((3,), jnp.float32)
    carry, outs = ap.samp_j(carry, keys, us, ap.data)
    # sampling keeps the centre; the carried energies are relative to it
    assert float(carry.pe_center) == float(warm.pe_center)
    pe = np.asarray(carry.states.potential_energy, np.float64)
    np.testing.assert_allclose(pe + float(carry.pe_center),
                               _potential64(carry.states.z, data), rtol=2e-6)
    assert np.max(np.abs(pe)) < 0.05 * abs(float(carry.pe_center))
    assert np.any(np.asarray(outs[0])[0] != np.asarray(z0))  # chains moved


def test_only_warm_up_programs_move_the_centre(rows, mesh):
    data, beta = rows
    cfg = SamplerConfig(kernel="chees", num_warmup=10)
    z = jnp.tile(beta[None], (C, 1))
    keys = jax.random.split(jax.random.PRNGKey(1), 2)
    ones = jnp.ones((2,), jnp.float32)

    def kernel_calls(fn, *args):
        return str(jax.make_jaxpr(fn)(*args)).count("pallas_call")

    one = make_chees_parts(flatten_model(FusedLogistic(D)), cfg)
    warm = one.init_carry(jax.random.PRNGKey(0), z, data)
    assert warm.pe_center is None  # one device: the plain sum, as it was
    # one kernel call in the program, the loop body's: nothing at its start
    assert kernel_calls(one.sample_segment, one.finalize(warm), keys, ones,
                        data) == 1

    sharded = make_chees_parts(
        flatten_model(FusedLogistic(D), axis_name="data"), cfg)
    specs = (P(), _row_specs(data))

    def mapped(fn, n_args):
        return jax.shard_map(
            fn, mesh=mesh, in_specs=(P(),) * n_args + specs[1:],
            out_specs=P(), check_vma=False)

    warm = jax.jit(mapped(sharded.init_carry, 2))(
        jax.random.PRNGKey(0), z, data)
    assert warm.pe_center is not None
    # sampling on the mesh: the loop body's call alone, no entry gradient
    assert kernel_calls(mapped(sharded.sample_segment, 3),
                        sharded.finalize(warm), keys, ones, data) == 1
    # a warm-up program: the ensemble in the moved centre, the loop body
    flags = jnp.zeros((2,), bool)
    assert kernel_calls(mapped(sharded.warm_segment, 6), warm, keys, ones,
                        jnp.arange(2), flags, flags, data) == 2


def _run(backend, data, tmp_path=None, **kw):
    import stark_tpu

    records = []
    stark_tpu.sample_until_converged(
        FusedLogistic(D), data, backend=backend, chains=C, kernel="chees",
        rhat_target=0.0, adaptive_blocks=False, block_size=5, min_blocks=1,
        seed=3, init_step_size=0.01, map_init_steps=0, num_warmup=10,
        max_leapfrog=1, progress_cb=records.append, **kw)
    return records


def test_a_block_counts_its_leapfrog_gradients_on_and_off_the_mesh(rows, mesh):
    """With one leapfrog a draw a block of 5 draws holds 5 ensemble
    gradients, whatever the backend."""
    from stark_tpu.backends import JaxBackend

    data, _ = rows
    for backend in (ShardedBackend(mesh), JaxBackend()):
        records = _run(backend, data, max_blocks=2)
        assert [r["block_grad_evals"] for r in records
                if r.get("event") == "block"] == [5 * C, 5 * C]


def test_checkpoint_holds_the_potential_and_resumes_the_carry(
        rows, mesh, tmp_path):
    """`pe` in the file is the potential itself (float64: carried energy
    plus centre), `pe_center` rides beside it, and a resumed run goes on
    as the uninterrupted one does."""
    from stark_tpu.checkpoint import load_checkpoint

    data, _ = rows
    whole = str(tmp_path / "whole.npz")
    _run(ShardedBackend(mesh), data, max_blocks=2, checkpoint_path=whole)
    part = str(tmp_path / "part.npz")
    _run(ShardedBackend(mesh), data, max_blocks=1, checkpoint_path=part)
    first, _ = load_checkpoint(part)
    assert first["pe"].dtype == np.float64 and first["pe_center"].shape == ()
    np.testing.assert_allclose(first["pe"], _potential64(first["z"], data),
                               rtol=2e-6)
    _run(ShardedBackend(mesh), data, max_blocks=2, checkpoint_path=part,
         resume_from=part)
    a, _ = load_checkpoint(whole)
    b, _ = load_checkpoint(part)
    assert float(a["pe_center"]) == float(b["pe_center"])
    np.testing.assert_array_equal(a["z"], b["z"])
    np.testing.assert_allclose(a["pe"], b["pe"], rtol=0, atol=1e-7)


class _OwnCentres(FusedLogistic):
    """`FusedLogistic` centred chain by chain (`Model.center_per_chain`): a
    chain's row is its constant and, to have something behind it, its first
    coefficient where the constant was taken."""

    center_per_chain = True

    def center_keep(self, params):
        return params["beta"][:1]

    def center_data(self, data, center):
        return super().center_data(data, center[0])


def test_a_centre_a_chain_is_split_with_the_chains_over_the_mesh(rows):
    """Rows over two devices and chains over two more: every device holds
    the rows of its own chains' centres, each chain is centred where it
    stands itself, and the ensemble goes where the plain one goes."""
    data, beta = rows
    mesh = make_mesh({"data": 2, "chains": 2}, devices=jax.devices()[:4])
    cfg = SamplerConfig(kernel="chees", num_warmup=10, map_init_steps=0)
    # chains far apart: thousands of nats, where one constant cannot serve
    z0 = beta + jax.random.normal(jax.random.PRNGKey(3), (C, D))
    key = jax.random.PRNGKey(0)
    keys, us = jax.random.split(key, 3), jnp.ones((3,), jnp.float32)
    ends = {}
    for model in (_OwnCentres(D), Logistic(D)):
        ap = ShardedBackend(mesh).adaptive_parts(
            model, cfg, data if model.center_per_chain
            else prepare_model_data(model, {
                "x": np.asarray(data["xT"]).T, "y": np.asarray(data["y"])}))
        warm = ap.init_j(key, ap.put_chains(z0), ap.data)
        carry = ap.chees.finalize(warm)._replace(
            log_eps=ap.put_rep(jnp.log(jnp.float32(0.01))),
            log_T=ap.put_rep(jnp.log(jnp.float32(0.05))))
        ends[type(model)] = warm, ap.samp_j(carry, keys, us, ap.data)[0]
    warm, run = ends[_OwnCentres]
    centre = np.asarray(warm.pe_center)
    assert centre.shape == (C, 2)
    assert len(warm.pe_center.sharding.device_set) == 4
    assert not warm.pe_center.sharding.is_fully_replicated
    np.testing.assert_allclose(
        centre[:, 0], [-_ll64(b, data) for b in np.asarray(z0)], rtol=1e-6)
    np.testing.assert_array_equal(centre[:, 1], np.asarray(z0)[:, 0])
    assert np.ptp(centre[:, 0]) > 1000.0
    # carried: the prior's part alone, whatever the chain's potential
    assert np.max(np.abs(warm.states.potential_energy)) < 20.0
    np.testing.assert_array_equal(np.asarray(run.pe_center), centre)
    pe = np.asarray(run.states.potential_energy, np.float64)
    np.testing.assert_allclose(pe + centre[:, 0],
                               _potential64(run.states.z, data), rtol=2e-6)
    plain = ends[Logistic][1]
    assert plain.pe_center is None
    np.testing.assert_allclose(np.asarray(run.states.z),
                               np.asarray(plain.states.z), atol=1e-4)
