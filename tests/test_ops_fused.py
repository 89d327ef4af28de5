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
        subs = [
            getattr(p, "jaxpr", p) for p in eqn.params.values()
            if hasattr(getattr(p, "jaxpr", p), "eqns")
        ]
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
