"""The random-slopes deployment (PR 32): the grouped Gaussian likelihood
against the benchmark's plain reference on the benchmark's rows, its potential
summed relative to a constant tile by tile (`ops.hier_fused._gauss_loglik`),
and every chain centred where it stands itself, on one chip, for the model
that asks (`Model.center_per_chain`; over a mesh that splits the chains:
`tests/test_centered_potential.py`).  Toy sizes, Pallas interpreted."""

import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from stark_tpu import prepare_model_data
from stark_tpu.backends.jax_backend import JaxBackend
from stark_tpu.model import flatten_model
from stark_tpu.models import (
    FusedLinearMixedModelGrouped, FusedLogistic, LinearMixedModel,
)
from stark_tpu.ops.hier_fused import _gauss_loglik, _tile_rows, ref_scale
from stark_tpu.sampler import SamplerConfig

ONCHIP = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "onchip")
N, D, Q, G, C = 2048, 8, 2, 16, 4


def _plugin(folder, name):
    """A file of the benchmark, loaded as `onchip/run.py` loads it."""
    if ONCHIP not in sys.path:
        sys.path.insert(0, ONCHIP)
    spec = importlib.util.spec_from_file_location(
        f"onchip_{folder}_{name}", os.path.join(ONCHIP, folder, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def job():
    rows = _plugin("rows", "lmm_rows").make(
        {"posterior_seed": 404, "noise": 0.5},
        {"n": N, "d": D, "q": Q, "groups": G}, 2**31 + 9)
    model = FusedLinearMixedModelGrouped(D, G, Q)
    data = prepare_model_data(model, rows)
    fm = flatten_model(model)
    z = 0.3 * jax.random.normal(jax.random.PRNGKey(1), (C, fm.ndim))
    return rows, data, fm, z


@pytest.mark.parametrize("centred", [False, True], ids=["plain", "centred"])
def test_the_program_agrees_with_the_benchmarks_plain_reference(job, centred):
    rows, data, fm, z = job
    assert fm.ndim == 1 + D + G * Q + Q + 1
    want_pe, want_grad = _plugin("references", "lmm").potential_and_grad(
        rows, np.asarray(z))
    pe, grad = jax.vmap(fm.bind(data).value_and_grad)(z)
    if centred:  # every chain relative to where it stands
        cen = fm.centering
        centre = cen.at(z, pe, cen.zero(C))
        assert centre.shape == (C, 4) and cen.per_chain
        pe, grad = jax.vmap(
            lambda zc, row: fm.bind(data, row).value_and_grad(zc))(z, centre)
        assert float(jnp.max(jnp.abs(pe))) < 0.02 * float(np.min(want_pe))
        pe = np.asarray(pe, np.float64) + np.asarray(cen.constant(centre))
    np.testing.assert_allclose(pe, want_pe, rtol=2e-6)
    gap = np.linalg.norm(np.asarray(grad) - want_grad, axis=1)
    assert float(np.max(gap / np.linalg.norm(want_grad, axis=1))) < 2e-5


def test_the_rows_are_the_seeds_and_lie_lane_major(job):
    rows = job[0]
    again = _plugin("rows", "lmm_rows").make(
        {"posterior_seed": 404, "noise": 0.5},
        {"n": N, "d": D, "q": Q, "groups": G}, 2**31 + 9)
    for k in ("x", "z", "g", "y"):
        np.testing.assert_array_equal(rows[k], again[k])
    # host arrays; x and z are views of what the device made, (d, n), (q, n)
    assert isinstance(rows["x"], np.ndarray) and rows["x"].shape == (N, D)
    assert rows["x"].T.flags["C_CONTIGUOUS"] and rows["z"].shape == (N, Q)
    np.testing.assert_array_equal(rows["z"][:, 0], 1.0)
    other = _plugin("rows", "lmm_rows").make(
        {"posterior_seed": 404, "noise": 0.5},
        {"n": N, "d": D, "q": Q, "groups": G}, 7)
    # another seed: the same rows in another order
    assert not np.array_equal(rows["y"], other["y"])
    np.testing.assert_array_equal(np.sort(rows["y"]), np.sort(other["y"]))


def test_the_normal_density_keeps_hundredths_of_a_nat_value_and_sigma_gradient():
    # the cell's shape: 10 000 tiles of 8192 rows at sigma 0.5: each tile's
    # log-density is near -5 950 and the total near -5.9e7, where float32
    # steps by 4 nats.  Positions a few posterior standard deviations of
    # log sigma apart (1 / sqrt(2 n) = 8e-5) differ by nats
    rng = np.random.default_rng(0)
    grid, tile = 10000, 8192
    n = grid * tile
    rows = _tile_rows(n, tile)
    assert rows.shape == (grid,) and rows.dtype == np.float32
    ssr = (0.25 * tile + 30.0 * rng.standard_normal(grid)).astype(np.float32)
    ls0 = np.float32(np.log(0.5) + 3e-4)
    ls = (ls0 + np.linspace(-4e-4, 4e-4, 201)).astype(np.float32)
    l64, total = ls.astype(np.float64), float(ssr.astype(np.float64).sum())
    want = -0.5 * total * np.exp(-2 * l64) - n * (
        l64 + 0.5 * np.log(2 * np.pi))
    want_d = total * np.exp(-3 * l64) - n * np.exp(-l64)
    centre = np.float32(want[100])
    tiles, sigma = jnp.asarray(ssr), jnp.exp(jnp.asarray(ls))

    def errors(sigma0):
        # the centre alone, at each position's own scale: the constant
        # comes off a plain evaluation's tiles
        row = None if sigma0 is None else jnp.concatenate(
            [centre[None], ref_scale(sigma0)])
        val, dsig = jax.vmap(lambda s: _gauss_loglik(
            tiles, s, rows,
            jnp.concatenate([centre[None], ref_scale(s)]) if row is None
            else row))(sigma)
        err = np.asarray(val, np.float64) + float(centre) - want
        return err - np.median(err), np.asarray(dsig, np.float64) - want_d

    kept, kept_d = errors(jnp.exp(ls0))
    at_sigma, _ = errors(None)
    # relative to the scale the centre was taken at: hundredths of a nat,
    # and smooth; at each position's own scale the scalars 1 / sigma^2 and
    # log sigma round anew: nats, that jump from one position to the next
    assert np.max(np.abs(kept)) < 0.02
    assert np.std(np.diff(kept)) < 0.005
    assert np.max(np.abs(at_sigma)) > 0.5
    assert np.std(np.diff(at_sigma)) > 0.1
    # the sigma-gradient, a difference of two numbers near 1.5e8: to 1e-3
    # of its size over the sweep (exp's own rounding moves the position by
    # 3e-8, which the curvature 6e8 turns into some 20)
    assert np.max(np.abs(kept_d)) < 1e-3 * np.max(np.abs(want_d))
    # the plain total, neither centred nor at a fixed scale
    plain = np.asarray(jax.vmap(
        lambda s: _gauss_loglik(tiles, s, rows)[0])(sigma), np.float64)
    assert np.all(plain % 4.0 == 0.0)  # the last bit of 5.9e7
    # a short last tile counts its own rows
    np.testing.assert_array_equal(_tile_rows(20, 8), [8.0, 8.0, 4.0])


@pytest.mark.parametrize("batched", [False, True], ids=["one_chain", "chains"])
def test_the_centred_op_is_the_plain_op_less_a_constant(job, batched):
    _, data, fm, z = job
    model = FusedLinearMixedModelGrouped(D, G, Q)
    centred = model.center_data(data, jnp.concatenate([
        jnp.float32(-1234.5)[None],
        ref_scale(1.003 * fm.constrain(z[1])["sigma"])]))

    def both(zc):
        p = fm.constrain(zc)
        return (jax.value_and_grad(lambda q: model.log_lik(q, data))(p),
                jax.value_and_grad(lambda q: model.log_lik(q, centred))(p))

    (v, g), (vc, gc) = jax.vmap(both)(z) if batched else both(z[0])
    for k in g:
        np.testing.assert_allclose(np.asarray(g[k]), np.asarray(gc[k]),
                                   rtol=2e-5, atol=1e-3)
    np.testing.assert_allclose(np.asarray(vc), np.asarray(v) + 1234.5,
                               rtol=2e-6)
    # and the model is the plain one's posterior
    plain = LinearMixedModel(D, G, Q)
    x64 = {k: jnp.asarray(v) for k, v in job[0].items()}
    want = jax.vmap(lambda zc: plain.log_lik(fm.constrain(zc), x64))(
        z if batched else z[:1])
    np.testing.assert_allclose(np.atleast_1d(v), want, rtol=1e-5)


def _warm_and_sample(ap, z0):
    """(warm carry, run carry after 3 transitions) of a backend's programs."""
    key = jax.random.PRNGKey(0)
    warm = ap.init_j(key, ap.put_chains(z0), *ap.extra)
    carry = ap.chees.finalize(warm)._replace(
        log_eps=ap.put_rep(jnp.log(jnp.float32(1e-3))),
        log_T=ap.put_rep(jnp.log(jnp.float32(4e-3))))
    keys, us = jax.random.split(key, 3), jnp.ones((3,), jnp.float32)
    return warm, ap.samp_j(carry, keys, us, *ap.extra)[0]


def test_one_chip_centres_for_the_model_that_asks_and_no_other(job):
    _, data, fm, _ = job
    assert FusedLinearMixedModelGrouped.center_per_chain is True
    assert fm.centering is not None and fm.centering.width == 4
    assert flatten_model(FusedLogistic(D)).centering is None
    assert flatten_model(LinearMixedModel(D, G, Q)).centering is None
    # the programs of one chip carry a centre a chain beside small energies,
    # from starts as far apart as the default's (within 2 of the origin)
    cfg = SamplerConfig(kernel="chees", num_warmup=6, map_init_steps=0)
    model = FusedLinearMixedModelGrouped(D, G, Q)
    ap = JaxBackend().adaptive_parts(model, cfg, data)
    z0 = jax.random.uniform(jax.random.PRNGKey(3), (8, ap.fm.ndim),
                            minval=-2.0, maxval=2.0)
    warm, run = _warm_and_sample(ap, z0)
    plain = np.asarray(jax.vmap(ap.fm.bind(data).value_and_grad)(z0)[0])
    assert np.ptp(plain) > 0.5 * np.min(plain)  # chains far apart
    # the constant: the likelihood's part where each chain stands; behind
    # it the noise scale there (`Model.center_keep`)
    centre = np.asarray(warm.pe_center)
    assert centre.shape == (8, 4)
    assert np.all(np.abs(centre[:, 0]) > 0.5 * np.abs(plain))
    sigma0 = np.asarray(jax.vmap(ap.fm.constrain)(z0)["sigma"], np.float64)
    np.testing.assert_allclose(
        centre[:, 1:], np.stack([sigma0, np.log(sigma0), sigma0 ** -2.0], 1),
        rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(warm.states.potential_energy) + centre[:, 0], plain,
        rtol=2e-6)
    assert np.max(np.abs(warm.states.potential_energy)) < 0.02 * np.min(plain)
    # sampling keeps the centres and moves the chains
    np.testing.assert_array_equal(np.asarray(run.pe_center), centre)
    assert np.all(np.any(np.asarray(run.states.z) != np.asarray(z0), axis=1))
