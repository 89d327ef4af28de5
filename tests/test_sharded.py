"""Sharded-data execution: logp parity and end-to-end posterior parity
(SURVEY.md §5 'multi-device without a cluster' on the 8-device CPU mesh)."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import stark_tpu
from jax import shard_map
from stark_tpu.backends.jax_backend import JaxBackend
from stark_tpu.backends.sharded import ShardedBackend
from stark_tpu.model import flatten_model
from stark_tpu.models.logistic import Logistic, synth_logistic_data
from stark_tpu.parallel.mesh import make_mesh, shard_data


@pytest.fixture(scope="module")
def logistic_setup():
    model = Logistic(num_features=4)
    data, _ = synth_logistic_data(jax.random.PRNGKey(0), 2048, 4)
    return model, data


def test_sharded_potential_matches_unsharded(logistic_setup):
    model, data = logistic_setup
    mesh = make_mesh({"data": 8, "chains": 1})
    fm_plain = flatten_model(model)
    fm_shard = flatten_model(model, axis_name="data")
    z = jax.random.normal(jax.random.PRNGKey(1), (fm_plain.ndim,))

    expected = float(fm_plain.potential(z, data))

    specs = jax.tree.map(lambda _: P("data"), data)
    fn = shard_map(
        lambda zz, dd: fm_shard.potential(zz, dd),
        mesh=mesh,
        in_specs=(P(), specs),
        out_specs=P(),
        check_vma=False,
    )
    got = float(jax.jit(fn)(z, shard_data(data, mesh)))
    np.testing.assert_allclose(got, expected, rtol=2e-5)


@pytest.mark.slow
def test_sharded_backend_matches_jax_backend(logistic_setup):
    model, data = logistic_setup
    mesh = make_mesh({"data": 2, "chains": 4})
    post_sharded = stark_tpu.sample(
        model, data, backend=ShardedBackend(mesh), chains=4,
        num_warmup=300, num_samples=300, seed=0,
    )
    post_plain = stark_tpu.sample(
        model, data, backend=JaxBackend(), chains=4,
        num_warmup=300, num_samples=300, seed=0,
    )
    assert post_sharded.max_rhat() < 1.05
    b_sh = post_sharded.summary()["beta"]
    b_pl = post_plain.summary()["beta"]
    # same posterior within MC error
    np.testing.assert_allclose(b_sh["mean"], b_pl["mean"], atol=0.05)
    np.testing.assert_allclose(b_sh["sd"], b_pl["sd"], rtol=0.35, atol=0.01)


@pytest.mark.slow
def test_sharded_backend_no_data_model():
    from stark_tpu.models.eight_schools import EightSchools, eight_schools_data

    # chains-only mesh; the model's data rows (8) don't divide 8 devices'
    # data axis, so run it replicated with data folded into chains axis
    mesh = make_mesh({"data": 1, "chains": 8})
    post = stark_tpu.sample(
        EightSchools(), eight_schools_data(), backend=ShardedBackend(mesh),
        chains=8, num_warmup=300, num_samples=200, seed=0,
    )
    mu = float(post.summary()["mu"]["mean"])
    assert 2.0 < mu < 7.0


def test_chains_not_divisible_raises():
    mesh = make_mesh({"data": 2, "chains": 4})
    with pytest.raises(ValueError, match="chains"):
        stark_tpu.sample(
            Logistic(2), {"x": jnp.zeros((16, 2)), "y": jnp.zeros(16)},
            backend=ShardedBackend(mesh), chains=3, num_warmup=10, num_samples=10,
        )


def test_rows_not_divisible_raises(logistic_setup):
    model, _ = logistic_setup
    mesh = make_mesh({"data": 8, "chains": 1})
    bad = {"x": jnp.zeros((2047, 4)), "y": jnp.zeros(2047)}
    with pytest.raises(ValueError, match="divisible"):
        stark_tpu.sample(
            model, bad, backend=ShardedBackend(mesh), chains=1,
            num_warmup=10, num_samples=10,
        )


def test_sharded_chees_transition_matches_unsharded(logistic_setup):
    """One ensemble transition with chains sharded over the mesh must equal
    the unsharded transition (per-chain-id RNG; cross-chain reductions as
    collectives), up to reduction-order float error."""
    from stark_tpu.kernels.chees import chees_transition, init_ensemble

    model, data = logistic_setup
    fm = flatten_model(model)
    C = 8
    potential_fn = fm.bind(data)
    z0 = jax.vmap(fm.init_flat)(jax.random.split(jax.random.PRNGKey(2), C))
    states = init_ensemble(potential_fn, z0)
    key = jax.random.PRNGKey(3)
    eps = jnp.asarray(0.05)
    inv_mass = jnp.ones((fm.ndim,))
    L = jnp.asarray(7, jnp.int32)

    ref_states, ref_info = jax.jit(
        lambda k, s: chees_transition(k, s, potential_fn, eps, inv_mass, L)
    )(key, states)

    from stark_tpu.kernels.chees import CheesInfo

    mesh = make_mesh({"data": 1, "chains": 8})
    info_spec = CheesInfo(
        accept_prob=P("chains"), is_accepted=P("chains"),
        is_divergent=P("chains"), grad_rel_T=P(), num_leapfrog=P(),
    )
    sharded = shard_map(
        lambda k, s: chees_transition(
            k, s, potential_fn, eps, inv_mass, L, chains_axis="chains"
        ),
        mesh=mesh,
        in_specs=(P(), P("chains")),
        out_specs=(P("chains"), info_spec),
        check_vma=False,
    )
    sh_states, sh_info = jax.jit(sharded)(key, states)

    np.testing.assert_allclose(
        np.asarray(sh_states.z), np.asarray(ref_states.z), rtol=1e-5, atol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(sh_info.accept_prob), np.asarray(ref_info.accept_prob),
        rtol=1e-4, atol=1e-5,
    )
    np.testing.assert_allclose(
        float(sh_info.grad_rel_T), float(ref_info.grad_rel_T),
        rtol=1e-3, atol=1e-5,
    )


@pytest.mark.slow
def test_sharded_chees_backend_matches_jax_backend(logistic_setup):
    """Full sharded ChEES run (data x chains mesh) reaches the same
    posterior as the single-device ensemble — distribution-level parity."""
    model, data = logistic_setup
    mesh = make_mesh({"data": 2, "chains": 4})
    post_sharded = stark_tpu.sample(
        model, data, backend=ShardedBackend(mesh), chains=8,
        kernel="chees", num_warmup=300, num_samples=300,
        init_step_size=0.1, seed=0,
    )
    post_plain = stark_tpu.sample(
        model, data, backend=JaxBackend(), chains=8,
        kernel="chees", num_warmup=300, num_samples=300,
        init_step_size=0.1, seed=0,
    )
    assert post_sharded.max_rhat() < 1.05
    assert post_plain.max_rhat() < 1.05
    for k in post_sharded.draws:
        m_s = np.mean(post_sharded.draws[k], axis=(0, 1))
        m_p = np.mean(post_plain.draws[k], axis=(0, 1))
        sd = np.std(post_plain.draws[k], axis=(0, 1))
        np.testing.assert_allclose(m_s, m_p, atol=4 * np.max(sd) / np.sqrt(300))


@pytest.mark.slow
def test_sharded_chees_dispatch_bounded(logistic_setup):
    """dispatch_steps segments the sharded chees run without changing the
    draw count or convergence."""
    model, data = logistic_setup
    mesh = make_mesh({"data": 4, "chains": 2})
    post = stark_tpu.sample(
        model, data, backend=ShardedBackend(mesh, dispatch_steps=50),
        chains=4, kernel="chees", num_warmup=120, num_samples=80,
        init_step_size=0.1, seed=1,
    )
    assert post.num_samples == 80
    assert np.isfinite(post.draws_flat).all()


def _coxph_tied_setup(n=2048, d=3, seed=0):
    """Survival data whose tie blocks SPAN shard boundaries: times drawn
    from a small value set (runs ~50 long at 256-row shards) plus one
    600-row mega-tie that swallows multiple whole shards — the worst
    case for the cross-shard tie stitching."""
    from stark_tpu.models import CoxPH

    rng = np.random.RandomState(seed)
    t = rng.randint(0, 37, size=n).astype(np.float32)
    t[100:700] = 50.0  # mega tie-run spanning shards
    data = {
        "x": rng.randn(n, d).astype(np.float32),
        "t": t,
        "event": (rng.rand(n) < 0.7).astype(np.float32),
    }
    model = CoxPH(num_features=d)
    return model, model.prepare_data(data)


@pytest.mark.slow  # >=8s on the 1-core host (pytest.ini policy, re-profiled 2026-08-03)
def test_coxph_sharded_potential_and_grad_match_unsharded():
    """Sequence-parallel CoxPH (r5): the cross-shard prefix-logsumexp +
    tie stitching in log_lik_sharded reproduces the unsharded Breslow
    potential AND gradient on the 8-device mesh to f32 roundoff —
    including tie blocks that span one or several shard boundaries."""
    from stark_tpu.parallel.mesh import row_partition_specs

    model, data = _coxph_tied_setup()
    mesh = make_mesh({"data": 8, "chains": 1})
    fm_plain = flatten_model(model)
    fm_shard = flatten_model(model, axis_name="data")
    z = 0.3 * jax.random.normal(jax.random.PRNGKey(1), (fm_plain.ndim,))

    v_exp, g_exp = jax.jit(fm_plain.potential_and_grad)(z, data)

    row_axes = model.data_shard_row_axes(data)
    specs = row_partition_specs(data, "data", row_axes)
    fn = shard_map(
        lambda zz, dd: fm_shard.potential_and_grad(zz, dd),
        mesh=mesh,
        in_specs=(P(), specs),
        out_specs=(P(), P()),
        check_vma=False,
    )
    v_got, g_got = jax.jit(fn)(
        z, shard_data(data, mesh, row_axes=row_axes)
    )
    np.testing.assert_allclose(float(v_got), float(v_exp), rtol=2e-5)
    np.testing.assert_allclose(
        np.asarray(g_got), np.asarray(g_exp), rtol=2e-4, atol=1e-4
    )


def test_coxph_minibatch_paths_still_fail_fast():
    """Mesh sharding is supported, but minibatching / sub-posterior
    splits consult data_row_axes and must STILL refuse CoxPH."""
    model, data = _coxph_tied_setup(n=256)
    with pytest.raises(NotImplementedError, match="minibatched"):
        model.data_row_axes(data)
    axes = model.data_shard_row_axes(data)  # the mesh path works
    assert all(a == 0 for a in jax.tree.leaves(axes))


@pytest.mark.slow
def test_coxph_sharded_backend_end_to_end():
    """ShardedBackend NUTS on CoxPH over the data axis converges and
    matches the single-device posterior (same seed)."""
    from stark_tpu.models import CoxPH, synth_survival_data

    data, true = synth_survival_data(jax.random.PRNGKey(0), 1024, 3)
    mesh = make_mesh({"data": 4, "chains": 2})
    post_s = stark_tpu.sample(
        CoxPH(num_features=3), data, backend=ShardedBackend(mesh),
        chains=2, kernel="nuts", max_tree_depth=6, num_warmup=200,
        num_samples=200, seed=0,
    )
    post_p = stark_tpu.sample(
        CoxPH(num_features=3), data, backend=JaxBackend(),
        chains=2, kernel="nuts", max_tree_depth=6, num_warmup=200,
        num_samples=200, seed=0,
    )
    assert post_s.max_rhat() < 1.05
    bs = np.asarray(post_s.draws["beta"]).mean(axis=(0, 1))
    bp = np.asarray(post_p.draws["beta"]).mean(axis=(0, 1))
    np.testing.assert_allclose(bs, bp, atol=0.15)
    np.testing.assert_allclose(bs, np.asarray(true["beta"]), atol=0.4)


def test_sv_sharded_potential_and_grad_match_unsharded():
    """Sequence-parallel StochasticVolatility (r5): each shard slices its
    time block from the replicated latent path; sharded potential and
    gradient match the unsharded model on the 8-device mesh."""
    from stark_tpu.models.timeseries import StochasticVolatility, synth_sv_data
    from stark_tpu.parallel.mesh import row_partition_specs

    model = StochasticVolatility(num_steps=512)
    data, _ = synth_sv_data(jax.random.PRNGKey(0), 512)
    mesh = make_mesh({"data": 8, "chains": 1})
    fm_plain = flatten_model(model)
    fm_shard = flatten_model(model, axis_name="data")
    z = 0.2 * jax.random.normal(jax.random.PRNGKey(1), (fm_plain.ndim,))

    v_exp, g_exp = jax.jit(fm_plain.potential_and_grad)(z, data)

    row_axes = model.data_shard_row_axes(data)
    specs = row_partition_specs(data, "data", row_axes)
    fn = shard_map(
        lambda zz, dd: fm_shard.potential_and_grad(zz, dd),
        mesh=mesh,
        in_specs=(P(), specs),
        out_specs=(P(), P()),
        check_vma=False,
    )
    v_got, g_got = jax.jit(fn)(
        z, shard_data(data, mesh, row_axes=row_axes)
    )
    np.testing.assert_allclose(float(v_got), float(v_exp), rtol=2e-5)
    np.testing.assert_allclose(
        np.asarray(g_got), np.asarray(g_exp), rtol=2e-4, atol=1e-4
    )
    # minibatch paths still refuse
    with pytest.raises(NotImplementedError, match="minibatched"):
        model.data_row_axes(data)


def test_sv_sharded_length_mismatch_fails_fast():
    """A num_steps/data-length mismatch must fail at trace time — the
    clamping semantics of dynamic_slice would otherwise evaluate several
    shards against the same tail slice of a too-short latent path."""
    from stark_tpu.models.timeseries import StochasticVolatility, synth_sv_data

    model = StochasticVolatility(num_steps=256)
    data, _ = synth_sv_data(jax.random.PRNGKey(0), 512)
    mesh = make_mesh({"data": 8, "chains": 1})
    with pytest.raises(ValueError, match="must[\\s\\S]*match exactly"):
        stark_tpu.sample(
            model, data, backend=ShardedBackend(mesh), chains=1,
            kernel="nuts", max_tree_depth=4, num_warmup=4, num_samples=4,
            seed=0,
        )


# ---------------------------------------------------------------------------
# scan_shards migration bit-identity (PR 19): the sequence-parallel
# stitching moved off hand-rolled gathers onto the ordered-scan
# primitive; each combine keeps the models' exact masked arithmetic, so
# the migration must be DRAW-bit-identical, pinned here against the
# pre-migration implementations copied verbatim below.
# ---------------------------------------------------------------------------


def _legacy_coxph_log_lik_sharded(model, p, data, axis_name):
    """The pre-scan_shards CoxPH stitching (hand-rolled gather_axis +
    shard-index masks), kept as the bit-identity reference."""
    from stark_tpu.models.survival import (
        _cumulative_logsumexp,
        _fill_from_right_valid,
    )
    from stark_tpu.parallel.primitives import gather_axis, mapped_axis_size

    eta = data["x"] @ p["beta"]
    t = data["t"]
    s = jax.lax.axis_index(axis_name)
    num_shards = mapped_axis_size(axis_name)
    prefix_l = _cumulative_logsumexp(eta)
    totals = gather_axis(prefix_l[-1], axis_name)
    firsts = gather_axis(t[0], axis_name)
    carry = jax.scipy.special.logsumexp(
        jnp.where(jnp.arange(num_shards) < s, totals, -jnp.inf)
    )
    prefix_g = jnp.logaddexp(prefix_l, carry)
    nxt = firsts[jnp.minimum(s + 1, num_shards - 1)]
    last_is_end = jnp.where(s + 1 < num_shards, t[-1] != nxt, True)
    is_end = jnp.concatenate([t[1:] != t[:-1], last_is_end[None]])
    fill, has_end = _fill_from_right_valid(prefix_g, is_end)
    g2 = gather_axis(
        jnp.stack([fill[0], has_end[0].astype(eta.dtype)]), axis_name
    )
    fs, hs = g2[:, 0], g2[:, 1] > 0.5
    later = jnp.arange(num_shards) > s
    rfill, _ = _fill_from_right_valid(
        jnp.where(later, fs, 0.0), later & hs
    )
    log_risk = jnp.where(has_end, fill, rfill[0])
    return jnp.sum(data["event"] * (eta - log_risk))


def _legacy_sv_log_lik_sharded(model, p, data, axis_name):
    """The pre-scan_shards SV slice (hand-rolled dynamic_slice by shard
    index), kept as the bit-identity reference."""
    from stark_tpu.parallel.primitives import mapped_axis_size

    h = model.latent_h(p)
    m = data["y"].shape[0]
    num_shards = mapped_axis_size(axis_name)
    assert m * num_shards == model.num_steps
    s = jax.lax.axis_index(axis_name)
    h_loc = jax.lax.dynamic_slice_in_dim(h, s * m, m)
    import jax.scipy.stats as jstats

    return jnp.sum(
        jstats.norm.logpdf(data["y"], 0.0, jnp.exp(h_loc / 2.0))
    )


def _bitwise_vs_legacy(model, data, legacy_log_lik, shards=4):
    """Potential AND gradient of the migrated sharded path, bitwise
    against the hand-rolled reference on the same mesh."""
    from stark_tpu.parallel.mesh import row_partition_specs

    mesh = make_mesh(
        {"data": shards, "chains": 1}, devices=jax.devices()[:shards]
    )
    fm = flatten_model(model, axis_name="data")
    z = 0.3 * jax.random.normal(jax.random.PRNGKey(1), (fm.ndim,))
    row_axes = model.data_shard_row_axes(data)
    specs = row_partition_specs(data, "data", row_axes)
    sharded = shard_data(data, mesh, row_axes=row_axes)

    def run(fmodel):
        fn = shard_map(
            lambda zz, dd: fmodel.potential_and_grad(zz, dd),
            mesh=mesh, in_specs=(P(), specs), out_specs=(P(), P()),
            check_vma=False,
        )
        v, g = jax.jit(fn)(z, sharded)
        return np.asarray(v), np.asarray(g)

    class _Legacy(type(model)):
        def log_lik_sharded(self, p, d, axis_name):
            return legacy_log_lik(self, p, d, axis_name)

    legacy = _Legacy.__new__(_Legacy)
    legacy.__dict__.update(model.__dict__)
    fm_legacy = flatten_model(legacy, axis_name="data")

    v_new, g_new = run(fm)
    v_old, g_old = run(fm_legacy)
    np.testing.assert_array_equal(v_new, v_old)
    np.testing.assert_array_equal(g_new, g_old)


def test_coxph_scan_shards_migration_bit_identical():
    """CoxPH's three-scan stitching on `scan_shards` reproduces the
    hand-rolled gathers to the BYTE (value and gradient), including tie
    blocks spanning shard boundaries."""
    model, data = _coxph_tied_setup(n=1024, d=3)
    _bitwise_vs_legacy(
        model, data, _legacy_coxph_log_lik_sharded, shards=4
    )


def test_sv_scan_shards_migration_bit_identical():
    """SV's replicated-path slice via scan_shards(replicated=True) is
    byte-identical to the hand-rolled dynamic_slice."""
    from stark_tpu.models import StochasticVolatility
    from stark_tpu.models.timeseries import synth_sv_data

    model = StochasticVolatility(num_steps=512)
    data, _ = synth_sv_data(jax.random.PRNGKey(2), 512)
    _bitwise_vs_legacy(model, data, _legacy_sv_log_lik_sharded, shards=4)
