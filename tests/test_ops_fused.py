"""Fused Pallas logistic kernel vs autodiff oracle (interpret mode on CPU)."""

import functools

import jax
import jax.extend.core
import jax.numpy as jnp
import numpy as np

import stark_tpu
from stark_tpu.model import flatten_model
from stark_tpu.models import Logistic, synth_logistic_data
from stark_tpu.ops import logistic_loglik_value_and_grad
import pytest


def _autodiff_oracle(beta, x, y):
    def ll(b):
        logits = x @ b
        return jnp.sum(
            y * jax.nn.log_sigmoid(logits) + (1 - y) * jax.nn.log_sigmoid(-logits)
        )

    return jax.value_and_grad(ll)(beta)


def test_fused_matches_autodiff():
    key = jax.random.PRNGKey(0)
    for n, d in [(100, 3), (1024, 8), (1500, 130)]:  # un/aligned rows+lanes
        data, _ = synth_logistic_data(jax.random.PRNGKey(n), n, d)
        beta = 0.5 * jax.random.normal(key, (d,))
        v1, g1 = logistic_loglik_value_and_grad(
            beta, data["x"].T, data["y"], lane_tile=256
        )
        v2, g2 = _autodiff_oracle(beta, data["x"], data["y"])
        np.testing.assert_allclose(float(v1), float(v2), rtol=2e-5)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), rtol=2e-4, atol=2e-4)


def test_offset_op_grads_match_autodiff():
    """custom_vjp fused op == plain autodiff through gather + non-centering."""
    from stark_tpu.models import FusedHierLogistic, HierLogistic

    data, _ = synth_logistic_data(jax.random.PRNGKey(4), 600, 5, num_groups=12)
    data = jax.tree.map(jnp.asarray, data)
    ref_model, fus_model = HierLogistic(5, 12), FusedHierLogistic(5, 12)
    ref_fm = flatten_model(ref_model)
    fus_fm = flatten_model(fus_model)
    z = 0.3 * jax.random.normal(jax.random.PRNGKey(5), (ref_fm.ndim,))
    va, ga = ref_fm.potential_and_grad(z, data)
    vf, gf = fus_fm.potential_and_grad(z, fus_model.prepare_data(data))
    np.testing.assert_allclose(float(va), float(vf), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(ga), np.asarray(gf), rtol=1e-3, atol=1e-4)


@pytest.mark.slow
def test_fused_hier_sampling_vmapped():
    """Fused hierarchical model samples under vmap'd NUTS (the real path)."""
    from stark_tpu.models import FusedHierLogistic

    model = FusedHierLogistic(num_features=3, num_groups=8)
    data, _ = synth_logistic_data(jax.random.PRNGKey(6), 512, 3, num_groups=8)
    post = stark_tpu.sample(
        model, data, chains=2, kernel="nuts", max_tree_depth=6,
        num_warmup=150, num_samples=150, seed=0,
    )
    assert np.all(np.isfinite(post.draws["beta"]))
    assert post.max_rhat() < 1.3


@pytest.mark.slow
def test_fused_flat_model_sampling():
    """NUTS through the fused potential reproduces the autodiff posterior."""
    from stark_tpu.models import FusedLogistic

    model = Logistic(num_features=4)
    fused_model = FusedLogistic(num_features=4)
    data, true = synth_logistic_data(jax.random.PRNGKey(1), 2048, 4)
    data = jax.tree.map(jnp.asarray, data)
    data_t = fused_model.prepare_data(data)
    fm = flatten_model(model)
    fm_fused = flatten_model(fused_model)

    pot_a = fm.bind(data)
    pot_f = fm_fused.bind(data_t)
    z = jnp.asarray([0.1, -0.2, 0.3, 0.0])
    va, ga = pot_a.value_and_grad(z)
    vf, gf = pot_f.value_and_grad(z)
    np.testing.assert_allclose(float(va), float(vf), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(ga), np.asarray(gf), rtol=1e-4, atol=1e-4)

    from stark_tpu.sampler import SamplerConfig, make_chain_runner

    cfg = SamplerConfig(kernel="nuts", max_tree_depth=6, num_warmup=200, num_samples=200)
    runner = jax.jit(jax.vmap(make_chain_runner(fm_fused, cfg), in_axes=(0, 0, None)))
    keys = jax.random.split(jax.random.PRNGKey(2), 2)
    z0 = 0.1 * jax.random.normal(jax.random.PRNGKey(3), (2, 4))
    res = runner(keys, z0, data_t)
    draws = np.asarray(res.draws)  # (2, 200, 4)
    assert np.all(np.isfinite(draws))
    np.testing.assert_allclose(
        draws.mean(axis=(0, 1)), np.asarray(true["beta"]), atol=0.3
    )


@pytest.mark.slow
def test_fused_model_all_entry_points():
    """Every row-splitting entry point honors prepare_data + data_row_axes.

    Regression: consensus/SG-HMC/sharded once bypassed Model.prepare_data
    (KeyError 'xT'), and a naive fix would have split the transposed xT
    along features instead of rows."""
    from stark_tpu.backends.sharded import ShardedBackend
    from stark_tpu.models import FusedLogistic
    from stark_tpu.parallel.consensus import consensus_sample
    from stark_tpu.parallel.mesh import make_mesh
    from stark_tpu.sghmc import sghmc_sample

    data, true = synth_logistic_data(jax.random.PRNGKey(0), 2048, 4)
    beta_true = np.asarray(true["beta"])

    post = consensus_sample(
        FusedLogistic(4), data, num_shards=2, chains=2, kernel="nuts",
        max_tree_depth=5, num_warmup=100, num_samples=100, seed=0,
    )
    np.testing.assert_allclose(
        np.asarray(post.draws["beta"]).mean((0, 1)), beta_true, atol=0.35
    )

    post = sghmc_sample(
        FusedLogistic(4), data, batch_size=256, chains=2, num_warmup=100,
        num_samples=200, step_size=5e-4, seed=0,
    )
    assert np.all(np.isfinite(np.asarray(post.draws["beta"])))

    mesh = make_mesh({"data": 4, "chains": 2})
    post = stark_tpu.sample(
        FusedLogistic(4), data, backend=ShardedBackend(mesh), chains=2,
        kernel="nuts", max_tree_depth=5, num_warmup=100, num_samples=100,
        seed=0,
    )
    np.testing.assert_allclose(
        np.asarray(post.draws["beta"]).mean((0, 1)), beta_true, atol=0.35
    )


@pytest.mark.slow
def test_chain_batched_vmap_matches_per_chain():
    """vmap over chains must hit the chain-batched kernel and agree with
    per-chain evaluation (both no-offset and offset variants, C not a
    multiple of the sublane pad)."""
    from stark_tpu.ops.logistic_fused import (
        logistic_loglik,
        logistic_offset_loglik,
    )

    key = jax.random.PRNGKey(1)
    n, d, C = 700, 5, 5  # ragged lanes AND ragged chain count
    data, _ = synth_logistic_data(jax.random.PRNGKey(2), n, d)
    xt, y = data["x"].T, data["y"]
    betas = 0.5 * jax.random.normal(key, (C, d))
    offs = 0.3 * jax.random.normal(jax.random.PRNGKey(3), (C, n))

    # values
    v_b = jax.vmap(lambda b: logistic_loglik(b, xt, y))(betas)
    v_s = jnp.stack([logistic_loglik(b, xt, y) for b in betas])
    np.testing.assert_allclose(np.asarray(v_b), np.asarray(v_s), rtol=2e-5)

    # gradients through the custom VJP under vmap
    g_b = jax.vmap(jax.grad(lambda b: logistic_loglik(b, xt, y)))(betas)
    g_s = jnp.stack([jax.grad(lambda b: logistic_loglik(b, xt, y))(b) for b in betas])
    np.testing.assert_allclose(np.asarray(g_b), np.asarray(g_s), rtol=2e-4, atol=2e-4)

    # offset variant: value + both grads
    f = lambda b, o: logistic_offset_loglik(b, o, xt, y)
    v_b = jax.vmap(f)(betas, offs)
    v_s = jnp.stack([f(b, o) for b, o in zip(betas, offs)])
    np.testing.assert_allclose(np.asarray(v_b), np.asarray(v_s), rtol=2e-5)
    gb_b, go_b = jax.vmap(jax.grad(f, argnums=(0, 1)))(betas, offs)
    gb_s = jnp.stack([jax.grad(f, argnums=0)(b, o) for b, o in zip(betas, offs)])
    go_s = jnp.stack([jax.grad(f, argnums=1)(b, o) for b, o in zip(betas, offs)])
    np.testing.assert_allclose(np.asarray(gb_b), np.asarray(gb_s), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(go_b), np.asarray(go_s), rtol=2e-4, atol=2e-4)


@pytest.mark.slow
def test_chain_batched_model_sampling_matches_unbatched_model():
    """FusedLogistic sampled with vmapped chains == plain Logistic."""
    from stark_tpu.models import FusedLogistic

    data, _ = synth_logistic_data(jax.random.PRNGKey(5), 800, 4)
    kw = dict(chains=5, kernel="nuts", max_tree_depth=5, num_warmup=200,
              num_samples=200, seed=0)
    post_f = stark_tpu.sample(FusedLogistic(num_features=4), dict(data), **kw)
    post_p = stark_tpu.sample(Logistic(num_features=4), dict(data), **kw)
    np.testing.assert_allclose(
        np.asarray(post_f.draws["beta"]).mean((0, 1)),
        np.asarray(post_p.draws["beta"]).mean((0, 1)),
        atol=0.05,
    )


def test_gaussian_offset_loglik_matches_autodiff():
    """Fused gaussian link (one-pass SSR + X-resid): value and all five
    gradients (beta, offsets, sigma via custom_vjp) match autodiff."""
    import jax
    import jax.numpy as jnp
    import jax.scipy.stats as jstats
    import numpy as np

    from stark_tpu.ops.logistic_fused import gaussian_offset_loglik

    n, d = 3333, 5  # ragged last lane tile on purpose
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (n, d))
    beta = 0.3 * jax.random.normal(jax.random.PRNGKey(1), (d,))
    off = 0.5 * jax.random.normal(jax.random.PRNGKey(2), (n,))
    y = x @ beta + off + 0.4 * jax.random.normal(jax.random.PRNGKey(3), (n,))
    sigma = jnp.asarray(0.7)

    def ref(beta, off, sigma):
        return jnp.sum(jstats.norm.logpdf(y, x @ beta + off, sigma))

    def fused(beta, off, sigma):
        return gaussian_offset_loglik(beta, off, x.T, y, sigma)

    v_r, g_r = jax.value_and_grad(ref, argnums=(0, 1, 2))(beta, off, sigma)
    v_f, g_f = jax.value_and_grad(fused, argnums=(0, 1, 2))(beta, off, sigma)
    np.testing.assert_allclose(float(v_f), float(v_r), rtol=2e-5)
    for a, b in zip(g_f, g_r):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4
        )

    # chain-batched: vmap over (beta, off, sigma) shares one X pass
    C = 6
    betas = 0.3 * jax.random.normal(jax.random.PRNGKey(4), (C, d))
    offs = 0.5 * jax.random.normal(jax.random.PRNGKey(5), (C, n))
    sigmas = jnp.linspace(0.5, 1.2, C)
    v_fb, g_fb = jax.vmap(
        jax.value_and_grad(fused, argnums=(0, 1, 2))
    )(betas, offs, sigmas)
    v_rb, g_rb = jax.vmap(
        jax.value_and_grad(ref, argnums=(0, 1, 2))
    )(betas, offs, sigmas)
    np.testing.assert_allclose(np.asarray(v_fb), np.asarray(v_rb), rtol=2e-5)
    for a, b in zip(g_fb, g_rb):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4
        )


# --- y in the kernel's own layout (PR 29) -------------------------------
# 4224 + 37 rows: not a multiple of 1024 (where XLA's re-layout of a rank-1
# y is a copy, not a bitcast) and a ragged last tile at 128 lanes


_N_RAGGED = 4224 + 37


def _y_rank_case(link, with_offsets, chains):
    d = 5
    k = jax.random.split(jax.random.PRNGKey(11), 4)
    xt = jax.random.normal(k[0], (d, _N_RAGGED))
    shape = (d,) if chains is None else (chains, d)
    beta = 0.4 * jax.random.normal(k[1], shape)
    offsets = None
    if with_offsets:
        offsets = 0.3 * jax.random.normal(k[2], shape[:-1] + (_N_RAGGED,))
    y = jax.random.uniform(k[3], (_N_RAGGED,))
    if link == "bernoulli_logit":
        y = (y < 0.4).astype(jnp.float32)
    return beta, xt, y, offsets


@pytest.mark.parametrize("centered", [False, True], ids=["plain", "centered"])
@pytest.mark.parametrize("with_offsets", [False, True], ids=["noff", "off"])
@pytest.mark.parametrize("link", ["bernoulli_logit", "gaussian"])
@pytest.mark.parametrize("call", ["_batched_call", "_fused_call"])
def test_rank2_y_is_bit_identical_to_rank1(call, link, with_offsets, centered):
    """A (1, N) float32 `y` goes to the kernel as it is and gives the bits
    the rank-1 path gives: value, gradient and per-row residual."""
    from stark_tpu.ops import logistic_fused as lf

    beta, xt, y, offsets = _y_rank_case(
        link, with_offsets, 3 if call == "_batched_call" else None
    )
    center = jnp.float32(-1234.5) if centered else None
    fn = jax.jit(functools.partial(
        getattr(lf, call), lane_tile=128, interpret=True, link=link
    ))
    flat = fn(beta, xt, y, offsets, center=center)
    lanes = fn(beta, xt, y[None, :], offsets, center=center)
    assert len(flat) == (3 if with_offsets else 2)
    for a, b in zip(flat, lanes):
        assert a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert np.all(np.isfinite(np.asarray(flat[0])))


def test_rank2_y_must_be_the_operand_itself():
    """No cast and no reshape on the rank-2 path: anything but (1, N)
    float32 is refused, not repaired inside the loop."""
    from stark_tpu.ops import logistic_fused as lf

    beta, xt, y, _ = _y_rank_case("bernoulli_logit", False, 3)
    for bad in (y[None, :].astype(jnp.bfloat16), y[None, :-1],
                jnp.stack([y, y])):
        with pytest.raises(ValueError, match="float32 of shape"):
            lf._batched_call(beta, xt, bad, None, lane_tile=128,
                             interpret=True)


def _sub_jaxprs(eqn):
    """The jaxprs an equation carries in its parameters (closed or open)."""
    return [
        getattr(p, "jaxpr", p) for p in eqn.params.values()
        if hasattr(getattr(p, "jaxpr", p), "eqns")
    ]


def _touches_rows(jaxpr, n, env):
    """(each pallas_call's operands traced back to the outermost jaxpr's
    inputs, None where an equation made them; every other equation, at any
    depth, that reads an array with an `n`-long axis).  ``env``: this
    jaxpr's variables that are outermost inputs."""
    calls, others = [], []
    for eqn in jaxpr.eqns:
        src = [
            env.get(v) if isinstance(v, jax.extend.core.Var) else None
            for v in eqn.invars
        ]
        if eqn.primitive.name == "pallas_call":
            calls.append(src)
            continue
        subs = _sub_jaxprs(eqn)
        if not subs and any(
            n in getattr(v.aval, "shape", ()) for v in eqn.invars
        ):
            others.append(eqn.primitive.name)
        for sub in subs:
            # a call's operands are its body's inputs one for one; any
            # other nesting resolves to None and fails the guard
            same = len(sub.invars) == len(src)
            c, o = _touches_rows(
                sub, n, dict(zip(sub.invars, src)) if same else {}
            )
            calls += c
            others += o
    return calls, others


@pytest.mark.parametrize("layout", ["y_lanes", "older_layout"])
def test_prepared_y_reaches_the_kernel_untouched(layout):
    """The guard: in `vmap(value_and_grad)` of FusedLogistic's potential
    over prepared data the kernel's `y` operand is an input of the jaxpr
    itself, and no other equation reads an N-long array, so no reshape,
    broadcast or cast of the outcomes can sit in a sampling loop again
    without this failing on the CPU.  Data laid out by an older tree (no
    leaf) takes the rank-1 path, whose re-layout the walk does see."""
    from stark_tpu.models import FusedLogistic
    from stark_tpu.models.logistic import Y_LANES

    model = FusedLogistic(5)
    raw, _ = synth_logistic_data(jax.random.PRNGKey(3), _N_RAGGED, 5)
    data = stark_tpu.prepare_model_data(model, raw)
    assert data["y"] is raw["y"]
    assert data[Y_LANES].shape == (1, _N_RAGGED)
    assert data[Y_LANES].dtype == jnp.float32
    if layout == "older_layout":
        data = {k: v for k, v in data.items() if k != Y_LANES}
    fm = flatten_model(model)
    closed = jax.make_jaxpr(
        lambda z, dd: jax.vmap(lambda zz: fm.potential_and_grad(zz, dd))(z)
    )(jnp.zeros((8, fm.ndim)), data)
    top = closed.jaxpr
    names = dict(zip(top.invars, ["z"] + sorted(data)))
    calls, others = _touches_rows(top, _N_RAGGED, {v: v for v in top.invars})
    assert len(calls) == 1
    xt_src, y_src = calls[0][:2]
    assert names.get(xt_src) == "xT"
    if layout == "y_lanes":
        assert names.get(y_src) == Y_LANES
        assert others == []
    else:
        assert y_src is None  # made inside: the re-layout
        assert others and set(others) <= {
            "reshape", "broadcast_in_dim", "convert_element_type"}


# --- the Bernoulli link: two exp, one log1p, one reciprocal (PR 31) ------
# The logits every case is held on: where the link is symmetric (0), where
# exp(-|z|) is 1 to the last bit (1e-6), ordinary (1), saturated (20),
# subnormal (88) and flushed to 0 (90), then a dense sweep between.

_LINK_POINTS = [0.0, 1e-6, -1e-6, 1.0, -1.0, 20.0, -20.0, 88.0, -88.0, 90.0, -90.0]


def _link_grid():
    return np.concatenate([
        np.asarray(_LINK_POINTS, np.float32),
        np.linspace(-95.0, 95.0, 4001).astype(np.float32),
    ])


def _link(y, logits, mask=None):
    """`_link_parts`' Bernoulli branch; every lane valid unless masked."""
    from stark_tpu.ops.logistic_fused import _link_parts

    y, logits = jnp.asarray(y), jnp.asarray(logits)
    if mask is None:
        mask = jnp.ones(logits.shape, bool)
    return _link_parts("bernoulli_logit", y, logits, mask)


def _old_link(y, logits):
    """The expression `_link_parts` held until PR 31 (two log-sigmoids and
    a sigmoid: three exp, two log, a reciprocal): kept here as what the
    lean form is compared with."""
    ll = y * jax.nn.log_sigmoid(logits) + (1.0 - y) * jax.nn.log_sigmoid(-logits)
    return ll, y - jax.nn.sigmoid(logits)


def _link_float64(y, z):
    y, z = y.astype(np.float64), z.astype(np.float64)
    ll = -y * np.logaddexp(0.0, -z) - (1.0 - y) * np.logaddexp(0.0, z)
    e = np.exp(-np.abs(z))
    return ll, y - np.where(z >= 0, 1.0, e) / (1.0 + e)


@pytest.mark.parametrize("y_value", [0.0, 1.0, 0.3], ids=["y0", "y1", "y_frac"])
def test_link_against_float64(y_value):
    """Value terms and residuals within 2 ulp of the term or 5e-7, and no
    further from float64 than the old expression was, for y in {0, 1} and
    for a fractional y (the identity holds for every real y)."""
    z = _link_grid()
    y = np.full_like(z, y_value)
    new, old = _link(y, z), _old_link(jnp.asarray(y), jnp.asarray(z))
    for name, got, was, want in zip(("value", "resid"), new, old, _link_float64(y, z)):
        ulp = np.spacing(np.abs(want).astype(np.float32)).astype(np.float64)
        tol = np.maximum(2.0 * ulp, 5e-7)
        err = np.abs(np.asarray(got, np.float64) - want) / tol
        err_old = np.abs(np.asarray(was, np.float64) - want) / tol
        assert err.max() <= 1.0, (name, z[err.argmax()], err.max())
        # in units of the tolerance, with a tenth of it for slack
        assert err.max() <= err_old.max() + 0.1, (name, err.max(), err_old.max())
        assert err.sum() <= 1.25 * err_old.sum(), (name, err.sum(), err_old.sum())


@pytest.mark.parametrize("y_value", [0.0, 1.0, 0.3], ids=["y0", "y1", "y_frac"])
def test_link_keeps_the_old_expression_s_bits(y_value):
    """Nothing was traded for the transcendentals saved: the residual is the
    old expression's bit for bit for every y, and so is the value term for
    an outcome in {0, 1} (for a fractional y the one rounding of y·z
    replaces two, and `test_link_against_float64` holds it).  On the CPU,
    eager and jitted; the chip's own units are PERF.md §6's to read."""
    z = jnp.asarray(np.concatenate([
        _link_grid(),
        np.random.default_rng(31).normal(0.0, 4.0, 50_000).astype(np.float32),
    ]))
    y = jnp.full(z.shape, y_value, jnp.float32)
    for wrap in (lambda f: f, jax.jit):
        (v, r), (v_old, r_old) = wrap(_link)(y, z), wrap(_old_link)(y, z)
        np.testing.assert_array_equal(np.asarray(r), np.asarray(r_old))
        if y_value in (0.0, 1.0):
            np.testing.assert_array_equal(np.asarray(v), np.asarray(v_old))


@pytest.mark.parametrize("y_value", [0.0, 1.0])
@pytest.mark.parametrize("bad", [np.inf, -np.inf, np.nan], ids=["inf", "-inf", "nan"])
def test_link_non_finite_logit_gives_non_finite_value(bad, y_value):
    """A divergent chain's logit: the sampler's reject depends on the value
    coming out non-finite, never a finite number that could be accepted."""
    val, _ = _link(
        np.full((3,), y_value, np.float32),
        np.asarray([0.5, bad, -0.5], np.float32),
    )
    val = np.asarray(val)
    assert np.isfinite(val[[0, 2]]).all()
    assert not np.isfinite(val[1])
    assert not np.isfinite(val.sum())


def test_link_masked_lanes_are_exact_zeros():
    """A ragged tile's overhang reads unspecified values: whatever they
    are, the lane contributes exactly 0 to value and residual (selects,
    not multiplies)."""
    z = np.asarray([np.nan, np.inf, -np.inf, 1e30, -1e30, 0.0, 3.0], np.float32)
    y = np.asarray([np.nan, 1.0, 0.0, np.inf, 1.0, 0.0, 1.0], np.float32)
    val, resid = _link(y, z, jnp.asarray([False] * 6 + [True]))
    for out in (np.asarray(val), np.asarray(resid)):
        np.testing.assert_array_equal(out[:6], np.zeros(6, np.float32))
        assert not np.signbit(out[:6]).any()
        assert np.isfinite(out[6]) and out[6] != 0.0


def _link_op_case(op):
    """(fused log-lik of per-chain parameters, the plain log-lik of the
    same, a (chains, ...) parameter pytree): 8192 + 37 rows (a ragged last
    tile at the default lane tile) with logits of scale 4, so both tails of
    the link carry weight."""
    from stark_tpu.models.logistic import _bernoulli_logit_loglik
    from stark_tpu.ops import hier_fused, logistic_fused

    n, d, groups, chains = 8192 + 37, 5, 12, 3
    k = jax.random.split(jax.random.PRNGKey(31), 5)
    x = jax.random.normal(k[0], (n, d))
    y = (jax.random.uniform(k[1], (n,)) < 0.4).astype(jnp.float32)
    params = {"beta": 1.8 * jax.random.normal(k[2], (chains, d))}
    if op == "logistic_loglik":
        fused = lambda p: logistic_fused.logistic_loglik(p["beta"], x.T, y)
        plain = lambda p: _bernoulli_logit_loglik(x @ p["beta"], y)
    elif op == "logistic_offset_loglik":
        params["off"] = jax.random.normal(k[3], (chains, n))
        fused = lambda p: logistic_fused.logistic_offset_loglik(
            p["beta"], p["off"], x.T, y
        )
        plain = lambda p: _bernoulli_logit_loglik(x @ p["beta"] + p["off"], y)
    elif op.startswith("gaussian"):
        sigma = 0.7

        def plain_at(mu):
            return jnp.sum(jax.scipy.stats.norm.logpdf(y, mu, sigma))

        if op == "gaussian_loglik":
            fused = lambda p: logistic_fused.gaussian_loglik(
                p["beta"], x.T, y, sigma)
            plain = lambda p: plain_at(x @ p["beta"])
        else:
            params["off"] = jax.random.normal(k[3], (chains, n))
            fused = lambda p: logistic_fused.gaussian_offset_loglik(
                p["beta"], p["off"], x.T, y, sigma)
            plain = lambda p: plain_at(x @ p["beta"] + p["off"])
    else:
        g = np.sort(np.random.RandomState(0).randint(0, groups, size=n))
        lane_tile, k_loc, first_gid, gl = hier_fused.grouped_layout(g, d)
        params["alpha"] = jax.random.normal(k[4], (chains, groups))
        layout = (
            jnp.asarray(gl), jnp.asarray(first_gid), jnp.zeros((k_loc,)),
            jnp.zeros((lane_tile // 128,)),
        )
        fused = lambda p: hier_fused.hier_logistic_loglik(
            p["beta"], p["alpha"], x.T, y, *layout
        )
        plain = lambda p: _bernoulli_logit_loglik(
            x @ p["beta"] + p["alpha"][g], y
        )
    return fused, plain, params


@pytest.mark.parametrize("batched", [False, True], ids=["one_chain", "vmapped"])
@pytest.mark.parametrize(
    "op", ["logistic_loglik", "logistic_offset_loglik", "hier_logistic_loglik",
           "gaussian_loglik", "gaussian_offset_loglik"]
)
def test_link_through_each_kernel_matches_plain_autodiff(op, batched):
    """All three kernels that share the link (`stark_logistic_ll_1chain`
    and `stark_logistic_ll` by the vmap rule, with and without offsets;
    `stark_hier_ll_grouped`), through their public ops under the
    interpreter: value and every gradient against autodiff of
    models/logistic.py's plain log-likelihood; and the Gaussian link's two
    ops through the same two kernels, against the normal log-density.  At
    the default precision the vmapped ones run the packed bf16 products
    (`batched_mxu_form`)."""
    fused, plain, params = _link_op_case(op)
    if batched:
        run = lambda f: jax.vmap(jax.value_and_grad(f))(params)
    else:
        one = jax.tree.map(lambda a: a[1], params)
        run = lambda f: jax.value_and_grad(f)(one)
    (v_f, g_f), (v_p, g_p) = run(fused), run(plain)
    np.testing.assert_allclose(np.asarray(v_f), np.asarray(v_p), rtol=2e-5)
    for name in params:
        np.testing.assert_allclose(
            np.asarray(g_f[name]), np.asarray(g_p[name]), rtol=2e-4,
            atol=2e-4, err_msg=name,
        )


@pytest.mark.parametrize(
    "op", ["logistic_loglik", "logistic_offset_loglik", "hier_logistic_loglik"]
)
def test_non_finite_position_gives_non_finite_value_through_each_kernel(op):
    """One chain of the ensemble diverged (an infinite coefficient): its
    value is non-finite, its neighbours' values are untouched."""
    fused, _, params = _link_op_case(op)
    sound = np.asarray(jax.vmap(fused)(params))
    params["beta"] = params["beta"].at[1, 0].set(jnp.inf)
    val = np.asarray(jax.vmap(fused)(params))
    assert not np.isfinite(val[1])
    np.testing.assert_array_equal(val[[0, 2]], sound[[0, 2]])


def test_link_spends_two_exp_one_log_one_reciprocal():
    """What the kernels' transcendental unit is asked for, an element: the
    jaxpr of the Bernoulli link holds two `exp` (one of -|z| for the value,
    one of -z for the sigmoid), one `log1p` and one `div`, and none of the
    library's `logistic` / `log` / `logaddexp` expansions."""
    def primitives(jaxpr):
        for eqn in jaxpr.eqns:
            subs = _sub_jaxprs(eqn)
            if not subs:
                yield eqn.primitive.name
            for sub in subs:
                yield from primitives(sub)

    z = jnp.zeros((8, 128), jnp.float32)
    names = list(primitives(
        jax.make_jaxpr(_link)(z[:1], z, jnp.ones((1, 128), bool)).jaxpr))
    costly = {"exp", "exp2", "log", "log1p", "logistic", "div", "tanh",
              "expm1", "pow", "rsqrt", "sqrt", "integer_pow"}
    assert sorted(n for n in names if n in costly) == [
        "div", "exp", "exp", "log1p"], names


# --- the chain-batched kernel's packed bf16 products ------------------------
# At `highest` `stark_logistic_ll` forms the six bf16 products of its two
# dots itself and packs them into one contraction each way
# (`batched_mxu_form`, `ops.precision.split6_*` with no exact rows).


def _packed_case(link, with_offsets, chains):
    """(beta (chains, 5), xt, y, offsets or None): logits of scale ~4 so
    both tails of the Bernoulli link carry weight; a ragged last tile at
    the default lane tile."""
    n, d = 8192 + 37, 5
    k = jax.random.split(jax.random.PRNGKey(41), 4)
    xt = jax.random.normal(k[0], (d, n))
    beta = 1.8 * jax.random.normal(k[1], (chains, d))
    offsets = (
        jax.random.normal(k[2], (chains, n)) if with_offsets else None
    )
    y = jax.random.uniform(k[3], (n,))
    if link == "bernoulli_logit":
        y = (y < 0.4).astype(jnp.float32)
    return beta, xt, y, offsets


def _float64_parts(link, beta, xt, y, offsets):
    """(value (C,), beta-gradient (C, D), residual (C, N)) of the kernel's
    outputs in float64 from the same float32 inputs."""
    x = np.asarray(xt, np.float64)
    z = np.asarray(beta, np.float64) @ x
    if offsets is not None:
        z = z + np.asarray(offsets, np.float64)
    y = np.asarray(y, np.float64)
    if link == "bernoulli_logit":
        val = (y * z - np.maximum(z, 0) - np.log1p(np.exp(-np.abs(z)))).sum(1)
        resid = y - 1.0 / (1.0 + np.exp(-z))
    else:
        resid = y - z
        val = (resid * resid).sum(1)
    return val, resid @ x.T, resid


def _batched(beta, xt, y, offsets, link):
    from stark_tpu.ops import logistic_fused as lf

    return jax.jit(functools.partial(
        lf._batched_call, lane_tile=None, interpret=True, link=link,
    ))(beta, xt, y, offsets)


def _rel_err(got, ref):
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    return float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))


@pytest.mark.parametrize("chains", [1, 3, 8], ids=["c1", "c3", "c8"])
@pytest.mark.parametrize("with_offsets", [False, True], ids=["noff", "off"])
@pytest.mark.parametrize("link", ["bernoulli_logit", "gaussian"])
def test_packed_batched_kernel_against_float64(link, with_offsets, chains,
                                               monkeypatch):
    """Every variant of `stark_logistic_ll` at `highest` (Bernoulli and
    Gaussian link, with and without offsets; one chain, three padded to
    eight, eight; a ragged last tile) against float64 of the same float32
    inputs.  The value and the beta-gradient, sums over the rows, are no
    further off than the parent's two f32 dots at `highest` put them on the
    same inputs (the kernel with `batched_mxu_form` held at its ``lax``
    form), beyond float32's rounding of the largest entry; the residual,
    row by row, is within four float32 roundings of its largest entry."""
    from stark_tpu.ops import logistic_fused as lf

    monkeypatch.delenv("STARK_FUSED_PRECISION", raising=False)
    assert lf.batched_mxu_form(5) == ("split6", 30)
    case = _packed_case(link, with_offsets, chains)
    ref = _float64_parts(link, *case)
    packed = _batched(*case, link)
    monkeypatch.setattr(lf, "batched_mxu_form", lambda d: ("lax", d))
    plain = _batched(*case, link)
    assert len(packed) == len(plain) == (3 if with_offsets else 2)
    for name, p, q, r in zip(("value", "grad", "resid"), packed, plain, ref):
        assert p.shape == q.shape == r.shape, name
        assert np.all(np.isfinite(np.asarray(p))), name
        if name == "resid":
            assert _rel_err(p, r) <= 4 * 2.0**-23, (name, _rel_err(p, r))
        else:
            assert _rel_err(p, r) <= 1.1 * _rel_err(q, r) + 2.0**-23, (
                name, _rel_err(p, r), _rel_err(q, r))


@pytest.mark.parametrize("bad", [np.inf, np.nan], ids=["inf", "nan"])
@pytest.mark.parametrize("with_offsets", [False, True], ids=["noff", "off"])
@pytest.mark.parametrize("link", ["bernoulli_logit", "gaussian"])
def test_packed_kernel_keeps_a_diverged_chain_to_itself(link, with_offsets,
                                                        bad, monkeypatch):
    """One chain of three diverged (a non-finite coefficient): its value is
    non-finite, and every output of its neighbours is the sound run's to
    the bit, though the packed dots carry all chains in one block each way."""
    monkeypatch.delenv("STARK_FUSED_PRECISION", raising=False)
    beta, xt, y, offsets = _packed_case(link, with_offsets, 3)
    sound = _batched(beta, xt, y, offsets, link)
    out = _batched(beta.at[1, 0].set(bad), xt, y, offsets, link)
    assert not np.isfinite(np.asarray(out[0])[1])
    for a, b in zip(out, sound):
        np.testing.assert_array_equal(np.asarray(a)[[0, 2]],
                                      np.asarray(b)[[0, 2]])


@pytest.mark.parametrize("precision, form, rows", [
    (None, "split6", 192),
    ("highest", "split6", 192),
    ("high", "lax", 32),
    ("default", "lax", 32),
])
def test_batched_mxu_form_follows_the_resolved_precision(
        precision, form, rows, monkeypatch):
    """`batched_mxu_form` at the cells' width: the packed six products, a
    6·D-row contraction, where STARK_FUSED_PRECISION resolves to `highest`
    (its default); one f32 dot over D otherwise."""
    from stark_tpu.ops.logistic_fused import batched_mxu_form
    from stark_tpu.ops.precision import split6_rows

    if precision is None:
        monkeypatch.delenv("STARK_FUSED_PRECISION", raising=False)
    else:
        monkeypatch.setenv("STARK_FUSED_PRECISION", precision)
    assert batched_mxu_form(32) == (form, rows)
    assert split6_rows(32, 0) == 6 * 32


def _kernel_dots(jaxpr):
    (call,) = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    out = []

    def walk(j):
        for eqn in j.eqns:
            if eqn.primitive.name == "dot_general":
                out.append(eqn)
            for sub in _sub_jaxprs(eqn):
                walk(sub)

    walk(call.params["jaxpr"])
    return call.params["name"], out


@pytest.mark.parametrize("precision", ["highest", "high", "default"])
@pytest.mark.parametrize("with_offsets", [False, True], ids=["noff", "off"])
def test_batched_kernel_holds_two_dots_at_every_precision(
        precision, with_offsets, monkeypatch):
    """Two dots a tile at every precision: at `highest` both take bfloat16
    operands at one pass and accumulate in float32 (the packed six
    products); at `high` and `default` both are the f32 dot at that
    precision the kernel has always run."""
    from _kernel_jaxpr_fixtures import _batched_jaxpr

    monkeypatch.setenv("STARK_FUSED_PRECISION", precision)
    name, dots = _kernel_dots(
        _batched_jaxpr("bernoulli_logit", "off" if with_offsets else "noff"
                       ).jaxpr)
    assert name == "stark_logistic_ll" and len(dots) == 2
    want = {"highest": (jnp.bfloat16, jax.lax.Precision.DEFAULT),
            "high": (jnp.float32, jax.lax.Precision.HIGH),
            "default": (jnp.float32, jax.lax.Precision.DEFAULT)}[precision]
    for eqn in dots:
        assert [v.aval.dtype for v in eqn.invars] == [want[0]] * 2
        assert eqn.params["precision"] in (want[1], (want[1], want[1]))
        assert eqn.params["preferred_element_type"] == jnp.float32
        assert eqn.outvars[0].aval.dtype == jnp.float32


def _recorded_cases():
    from _kernel_jaxpr_fixtures import CASES

    return [f"{case}@{precision}" for precision, case in CASES]


@pytest.mark.parametrize("case", _recorded_cases())
def test_kernel_jaxpr_is_the_recorded_one(case):
    """The kernels the packed batched form must leave alone trace to the
    jaxpr recorded before it existed (`tests/_kernel_jaxpr_fixtures.py`):
    the grouped Bernoulli kernel at `highest`, which runs the same helpers
    with exact rows, and the chain-batched kernel at `high` and `default`,
    every link and offset variant."""
    import json

    from _kernel_jaxpr_fixtures import FIXTURE, kernel_jaxpr

    with open(FIXTURE) as f:
        recorded = json.load(f)
    assert set(recorded) == set(_recorded_cases())
    name, precision = case.split("@")
    assert kernel_jaxpr(precision, name) == recorded[case]


@pytest.mark.parametrize("values", ["cancelling", "normal"])
def test_split6_without_exact_rows_is_highests_six_products(values):
    """The packed helpers with no exact rows (K = 0), as the chain-batched
    kernel runs them: the forward contraction over [x_hi; x_mid; x_lo;
    x_hi; x_mid; x_hi] (6·D rows) and the backward over [x_hi; x_mid; x_lo]
    (3·D) each give the float64 sum of `highest`'s six products of the
    rounded splits to float32 rounding, and 0 where those cancel while the
    three `highest` leaves out do not; no exact-row block comes back."""
    from _bf16_splits import (
        _NINE, _SIX, _cancelling_case, _np_products)
    from stark_tpu.ops.precision import (
        split6_backward, split6_forward, split6_operands, split6_rows)

    rng = np.random.default_rng(7)
    if values == "cancelling":
        c, d, t = 8, 4, 16
        w, _ = _cancelling_case(c, rng)
        _, x = _cancelling_case(t, rng)
        x = x.T  # (4, t): the second factor along the contraction
        r, _ = _cancelling_case(c, rng)
        _, xb = _cancelling_case(d, rng)  # (d, 4): streamed over 4 lanes
    else:
        c, d, t = 8, 32, 256
        w = rng.standard_normal((c, d)).astype(np.float32)
        x = rng.standard_normal((d, t)).astype(np.float32)
        r = rng.standard_normal((c, t)).astype(np.float32)
        xb = x
    fwd, bwd = split6_operands(jnp.asarray(x))
    assert fwd.dtype == bwd.dtype == jnp.bfloat16
    assert fwd.shape == (split6_rows(d, 0), t) and bwd.shape == (3 * d, t)
    got = np.asarray(split6_forward(jnp.asarray(w), None, fwd))
    assert got.dtype == np.float32 and got.shape == (c, t)
    _, bwd = split6_operands(jnp.asarray(xb))
    gx, ge = split6_backward(jnp.asarray(r), bwd, d)
    gx = np.asarray(gx)
    assert ge is None and gx.shape == (c, d)
    for out, a, b in ((got, w, x), (gx, r, xb.T)):
        six, nine = _np_products(a, b, _SIX), _np_products(a, b, _NINE)
        if values == "cancelling":
            np.testing.assert_array_equal(six, 0.0)
            np.testing.assert_array_equal(out, 0.0)
            np.testing.assert_allclose(np.abs(nine),
                                       4 * (2.0**-30 + 2.0**-40))
        else:
            size = np.abs(a).astype(np.float64) @ np.abs(b)
            assert np.max(np.abs(out - six) / size) < 2.0**-21


@pytest.mark.parametrize("precision, form, rows", [
    (None, "split6", 6 * 8),
    ("high", "lax", 8),
    ("default", "lax", 8),
])
def test_prepare_span_names_the_batched_mxu_form(precision, form, rows,
                                                 monkeypatch):
    """`FusedLogistic`'s `prepare_data` says how `stark_logistic_ll` will
    contract a tile, from the function the kernel builds its operands with,
    on the `prepare_data` span; the layout's own fields are as they were."""
    from stark_tpu import telemetry
    from stark_tpu.model import prepare_model_data
    from stark_tpu.models import FusedLogistic
    from stark_tpu.ops.logistic_fused import batched_mxu_form

    if precision is None:
        monkeypatch.delenv("STARK_FUSED_PRECISION", raising=False)
    else:
        monkeypatch.setenv("STARK_FUSED_PRECISION", precision)
    data, _ = synth_logistic_data(jax.random.PRNGKey(3), 300, 8)
    prepared = prepare_model_data(FusedLogistic(8), dict(data))
    (sp,) = [r for r in telemetry.span_log() if r.name == "prepare_data"][-1:]
    assert (sp.fields["mxu_form"], sp.fields["mxu_rows"]) == (form, rows)
    assert batched_mxu_form(8) == (form, rows)
    assert sp.fields["kernel_layout"] == "xT,y_lanes"
    assert prepared["xT"].shape == (8, 300)
