"""Grouped hierarchical kernel tests (ops/hier_fused.py).

Oracle: the plain autodiff HierLogistic on the SAME (sorted) rows — the
grouped kernel must match its value and every parameter gradient to
float32 tolerance, single-chain and chain-batched, including ragged
last tiles and uneven group sizes.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _bf16_splits import (
    _NINE, _SIX, _cancelling_case, _np_products, _np_split3, _round_bf16)
from stark_tpu.model import flatten_model, prepare_model_data
from stark_tpu.models import (
    FusedHierLogistic,
    FusedHierLogisticGrouped,
    HierLogistic,
    synth_logistic_data,
)
from stark_tpu.ops.hier_fused import grouped_layout


def _models(n=4096 + 37, d=8, groups=50, seed=0):
    data, _ = synth_logistic_data(
        jax.random.PRNGKey(seed), n, d, num_groups=groups
    )
    ref = HierLogistic(num_features=d, num_groups=groups)
    grp = FusedHierLogisticGrouped(num_features=d, num_groups=groups)
    gdata = prepare_model_data(grp, data)
    # oracle uses the SAME row order as the grouped layout so float
    # accumulation differences stay at f32 roundoff
    order = np.argsort(np.asarray(data["g"]), kind="stable")
    rdata = {k: jnp.asarray(np.asarray(v)[order]) for k, v in data.items()}
    return ref, rdata, grp, gdata


def test_grouped_layout_invariants():
    g = np.sort(np.random.RandomState(0).randint(0, 50, size=10_000))
    lane_tile, k_loc, first_gid, gl = grouped_layout(g, d=8)
    assert k_loc % 8 == 0
    assert first_gid.shape[0] == -(-10_000 // lane_tile)
    assert gl.min() >= 0 and gl.max() < k_loc
    # reconstruction: first_gid[tile] + gl == g
    rec = first_gid[np.arange(10_000) // lane_tile] + gl
    np.testing.assert_array_equal(rec, g)
    with pytest.raises(ValueError):
        grouped_layout(g[::-1], d=8)  # unsorted


def test_grouped_layout_halving_stays_128_aligned():
    """d=63 starts at lane_tile 8064 (63*128); a dense grouping forces
    halving, and naive /2 would give 4032 -> non-128-multiple encodings
    that reconstruct the WRONG tile from lt128 (silent corruption)."""
    rows_per_group = 50
    n = 40_000
    g = np.sort(np.arange(n) // rows_per_group)
    out = grouped_layout(g, d=63)
    assert out is not None
    lane_tile, k_loc, first_gid, gl = out
    assert lane_tile % 128 == 0
    assert lane_tile * first_gid.shape[0] >= n
    # shape-encoding round trip is exact
    assert 128 * (lane_tile // 128) == lane_tile
    rec = first_gid[np.arange(n) // lane_tile] + gl
    np.testing.assert_array_equal(rec, g)
    assert gl.max() < k_loc


def test_grouped_matches_autodiff_value_and_grads():
    ref, rdata, grp, gdata = _models()
    params = {
        "beta": 0.1 * jnp.arange(8, dtype=jnp.float32),
        "alpha0": jnp.float32(0.3),
        "sigma_alpha": jnp.float32(0.7),
        "alpha_raw": 0.05 * jnp.arange(50, dtype=jnp.float32) - 1.0,
    }
    v_ref = ref.log_lik(params, rdata)
    v_grp = grp.log_lik(params, gdata)
    np.testing.assert_allclose(v_ref, v_grp, rtol=2e-5)

    g_ref = jax.grad(lambda p: ref.log_lik(p, rdata))(params)
    g_grp = jax.grad(lambda p: grp.log_lik(p, gdata))(params)
    for k in params:
        np.testing.assert_allclose(
            np.asarray(g_ref[k]), np.asarray(g_grp[k]), rtol=2e-4,
            atol=1e-4, err_msg=k,
        )


def _sorted_groups(n, groups):
    return np.sort(np.random.RandomState(0).randint(0, groups, size=n))


#: (n, d, chains, sorted group ids, STARK_GROUPED_LANE_TILE, lane tile, k_loc)
_FOLDED_CASES = {
    # the cell's shape at toy N: d + k_loc = 40, one MXU tile deep
    "d32_kloc8": (2 * 8192, 32, 8, _sorted_groups(2 * 8192, 12), None, 8192, 8),
    # the slab's one-hot rows start off a sublane boundary
    "d_not_multiple_of_8": (2048, 10, 8, _sorted_groups(2048, 40), "1024", 1024, 24),
    # the chain batch pads to 8 rows of parameters
    "chains_not_multiple_of_8": (2048, 8, 5, _sorted_groups(2048, 40), "1024", 1024, 24),
    # 37 rows of a fourth tile: the mask zeroes the slab's tail
    "ragged_last_tile": (3 * 1024 + 37, 8, 8, _sorted_groups(3 * 1024 + 37, 50), "1024", 1024, 24),
    # two rows a group: the window fills _K_LOC_MAX and d + k_loc = 136
    # spans two MXU tiles of contraction (and of output columns)
    "kloc128_two_mxu_tiles": (1024 + 9, 8, 8, np.arange(1024 + 9) // 2, None, 256, 128),
    # the cap sets a 512-lane tile: eight tiles share the resident beta
    "lane_tile_cap": (4096, 8, 8, _sorted_groups(4096, 50), "512", 512, 8),
}


@pytest.mark.parametrize("case", sorted(_FOLDED_CASES))
def test_folded_kernel_matches_autodiff(case, monkeypatch):
    """The kernel contracts [beta | alpha window] with [xT ; one-hot] in
    one dot and takes both gradients from one product with the slab's
    transpose: value, grad beta and grad alpha against jax.grad of
    HierLogistic.log_lik on the same sorted rows, chain-batched."""
    from stark_tpu.ops.hier_fused import hier_logistic_loglik

    n, d, chains, g, cap, lane_tile, k_loc = _FOLDED_CASES[case]
    if cap is None:
        monkeypatch.delenv("STARK_GROUPED_LANE_TILE", raising=False)
    else:
        monkeypatch.setenv("STARK_GROUPED_LANE_TILE", cap)
    groups = int(g.max()) + 1
    data, _ = synth_logistic_data(jax.random.PRNGKey(11), n, d, num_groups=groups)
    data["g"] = jnp.asarray(g, jnp.int32)  # already sorted: one row order
    gdata = prepare_model_data(
        FusedHierLogisticGrouped(num_features=d, num_groups=groups), data
    )
    assert 128 * gdata["lt128"].shape[0] == lane_tile
    assert gdata["k_loc"].shape[0] == k_loc
    kb, ka = jax.random.split(jax.random.PRNGKey(7))
    beta = 0.3 * jax.random.normal(kb, (chains, d), jnp.float32)
    alpha = 0.5 * jax.random.normal(ka, (chains, groups), jnp.float32)

    def fused_ll(b, a):
        return hier_logistic_loglik(
            b, a, gdata["xT"], gdata["y"], gdata["gl"], gdata["first_gid"],
            gdata["k_loc"], gdata["lt128"],
        )

    ref = HierLogistic(num_features=d, num_groups=groups)

    def ref_ll(b, a):
        p = {"beta": b, "alpha0": 0.0, "sigma_alpha": 1.0, "alpha_raw": a}
        return ref.log_lik(p, data)

    def batched(fn):
        return jax.vmap(jax.value_and_grad(fn, argnums=(0, 1)))

    v, (gb, ga) = batched(fused_ll)(beta, alpha)
    v0, (gb0, ga0) = batched(ref_ll)(beta, alpha)
    for name, a, b in (("value", v, v0), ("grad_beta", gb, gb0),
                       ("grad_alpha", ga, ga0)):
        a, b = np.asarray(a), np.asarray(b)
        assert a.shape == b.shape, name
        # float32 sums in two orders, relative to the largest entry
        assert np.max(np.abs(a - b)) <= 2e-5 * np.max(np.abs(b)), name


def _count_primitive(jaxpr, name):
    count = 0
    for eqn in jaxpr.eqns:
        count += eqn.primitive.name == name
        for sub in jax.core.jaxprs_in_params(eqn.params):
            count += _count_primitive(sub, name)
    return count


def _hier_kernel_jaxpr():
    from stark_tpu.ops.hier_fused import _grouped_call

    n, d, chains, groups, k_loc, lane_tile = 1024, 8, 8, 40, 24, 512
    return jax.make_jaxpr(
        lambda b, a, xt, y, gl, fg: _grouped_call(
            b, a, xt, y, gl, fg, k_loc=k_loc, lane_tile=lane_tile,
            interpret=True,
        )
    )(
        jnp.zeros((chains, d)), jnp.zeros((chains, groups)),
        jnp.zeros((d, n)), jnp.zeros((n,)), jnp.zeros((n,), jnp.int32),
        jnp.zeros((n // lane_tile,), jnp.int32),
    ).jaxpr


def _lmm_kernel_jaxpr(q):
    from stark_tpu.ops.hier_fused import _grouped_lmm_call

    n, d, chains, groups, k_loc, lane_tile = 1024, 8, 16, 40, 8, 512
    return jax.make_jaxpr(
        lambda b, u, ic, xt, zt, y, gl, fg: _grouped_lmm_call(
            b, u, ic, xt, zt, y, gl, fg, k_loc=k_loc, lane_tile=lane_tile,
            interpret=True,
        )
    )(
        jnp.zeros((chains, d)), jnp.zeros((chains, groups, q)),
        jnp.zeros((chains,)), jnp.zeros((d, n)), jnp.zeros((q, n)),
        jnp.zeros((n,)), jnp.zeros((n,), jnp.int32),
        jnp.zeros((n // lane_tile,), jnp.int32),
    ).jaxpr


def _dot_eqns(jaxpr):
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            out.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out += _dot_eqns(sub)
    return out


@pytest.mark.parametrize("kernel, q", [
    ("stark_hier_ll_grouped", None),
    ("stark_lmm_ll_grouped", 1),
    ("stark_lmm_ll_grouped", 2),
    ("stark_lmm_ll_grouped", 3),
])
def test_grouped_kernel_body_holds_two_dots(kernel, q):
    """One contraction forward and one backward a tile, at every number of
    random effects: at `highest` every further dot costs six MXU passes over
    the whole (C, TILE) block, however few of the array's rows it uses (the
    four-dot hier form ran at 7 % of the roofline: PERF.md, PR 27; the LMM's
    2·Q + 2 dots spilled half its schedule: PR 39).  The Bernoulli kernel at
    `highest` forms the six bf16 products itself (PR 41): both of its dots
    take bfloat16 operands and accumulate in float32, one MXU pass each."""
    jaxpr = _hier_kernel_jaxpr() if q is None else _lmm_kernel_jaxpr(q)
    (call,) = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert call.params["name"] == kernel
    assert _count_primitive(call.params["jaxpr"], "dot_general") == 2
    if q is None:
        for eqn in _dot_eqns(call.params["jaxpr"]):
            assert [v.aval.dtype for v in eqn.invars] == [jnp.bfloat16] * 2
            assert eqn.params["preferred_element_type"] == jnp.float32
            assert eqn.outvars[0].aval.dtype == jnp.float32


@pytest.mark.parametrize("sign", [1.0, -1.0])
@pytest.mark.parametrize("scale", [1e-20, 3e-8, 1e-3, 1.0, 7e2, 1e30])
def test_split_bf16x3_reconstructs_float32(sign, scale):
    """hi + mid + lo is x to the bit, each term a bfloat16 rounded to
    nearest, at the magnitudes a slab, a parameter or a residual holds
    (subnormals aside); each term is at most 2**-8 of the one before, and
    mid takes both signs whatever the sign of x."""
    from stark_tpu.ops.precision import split_bf16x3

    x = sign * scale * np.abs(np.random.default_rng(3).standard_normal(4096))
    x = np.concatenate([x, sign * scale * np.array([1, 1 + 2**-8 + 2**-16,
                                                    2 - 2**-23])])
    x = x.astype(np.float32)
    hi, mid, lo = (np.asarray(t) for t in split_bf16x3(jnp.asarray(x)))
    for t, t0 in zip((hi, mid, lo), _np_split3(x)):
        assert t.dtype == np.float32
        np.testing.assert_array_equal(t, t0)
        np.testing.assert_array_equal(_round_bf16(t), t)
    np.testing.assert_array_equal((hi + mid) + lo, x)
    np.testing.assert_array_equal(
        hi.astype(np.float64) + mid + lo, x.astype(np.float64))
    assert np.all(np.abs(mid) <= 2.0**-8 * np.abs(hi))
    assert np.all(np.abs(lo) <= 2.0**-8 * np.abs(mid))
    assert np.any(mid > 0) and np.any(mid < 0)


def _one_hot(k, t, rng):
    return np.eye(k, dtype=np.float32)[:, rng.integers(0, k, t)]


@pytest.mark.parametrize("values", ["cancelling", "normal"])
def test_split6_forward_is_highests_six_products(values):
    """[w | a] against [x ; e] through the packed contraction is the float64
    sum of `highest`'s six products of the rounded splits (a's three terms
    over the exact one-hot) to float32 rounding; where those cancel and the
    three `highest` leaves out do not, it is 0, not the nine-product sum."""
    from stark_tpu.ops.precision import (
        split6_forward, split6_operands, split6_rows)

    rng = np.random.default_rng(5)
    if values == "cancelling":
        c, d, k, t = 8, 4, 8, 16
        w, xt = _cancelling_case(c, rng)
        _, x = _cancelling_case(t, rng)
        x = x.T  # (4, t): the second factor along the contraction
        a = np.zeros((c, k), np.float32)
    else:
        c, d, k, t = 8, 32, 8, 256
        w, a = rng.standard_normal((c, d)), rng.standard_normal((c, k))
        x = rng.standard_normal((d, t))
        w, a, x = (v.astype(np.float32) for v in (w, a, x))
    e = _one_hot(k, t, rng)
    fwd, bwd = split6_operands(jnp.asarray(x), jnp.asarray(e))
    assert fwd.dtype == bwd.dtype == jnp.bfloat16
    assert fwd.shape == (split6_rows(d, k), t)
    assert bwd.shape == (3 * d + k, t)
    got = np.asarray(split6_forward(jnp.asarray(w), jnp.asarray(a), fwd))
    assert got.dtype == np.float32 and got.shape == (c, t)
    a_part = sum(_np_split3(a)) @ e.astype(np.float64)
    six = _np_products(w, x, _SIX) + a_part
    nine = _np_products(w, x, _NINE) + a_part
    if values == "cancelling":
        np.testing.assert_array_equal(six, 0.0)
        np.testing.assert_array_equal(got, 0.0)
        np.testing.assert_allclose(np.abs(nine), 4 * (2.0**-30 + 2.0**-40))
    else:
        size = np.abs(w).astype(np.float64) @ np.abs(x) + np.abs(a) @ e
        assert np.max(np.abs(got - six) / size) < 2.0**-21


@pytest.mark.parametrize("values", ["cancelling", "normal"])
def test_split6_backward_is_highests_six_products(values):
    """The residual's three splits streamed against [x splits ; e]: the
    six products of r·xᵀ that `highest` forms, and r·eᵀ, each to float32
    rounding of its float64 sum; the MXU's three further products are left
    out, so where the six cancel and those do not the result is 0."""
    from stark_tpu.ops.precision import split6_backward, split6_operands

    rng = np.random.default_rng(6)
    if values == "cancelling":
        c, d, k = 16, 32, 8
        r, _ = _cancelling_case(c, rng)
        _, x = _cancelling_case(d, rng)  # (d, 4): streamed over 4 lanes
    else:
        c, d, k = 16, 32, 8
        r = rng.standard_normal((c, 64)).astype(np.float32)
        x = rng.standard_normal((d, 64)).astype(np.float32)
    e = _one_hot(k, r.shape[1], rng)
    _, bwd = split6_operands(jnp.asarray(x), jnp.asarray(e))
    gx, ge = (np.asarray(v) for v in split6_backward(jnp.asarray(r), bwd, d))
    assert gx.shape == (c, d) and ge.shape == (c, k)
    six = _np_products(r, x.T, _SIX)
    nine = _np_products(r, x.T, _NINE)
    ge0 = sum(_np_split3(r)) @ e.T.astype(np.float64)
    if values == "cancelling":
        np.testing.assert_array_equal(six, 0.0)
        np.testing.assert_array_equal(gx, 0.0)
        np.testing.assert_allclose(np.abs(nine), 4 * (2.0**-30 + 2.0**-40))
        np.testing.assert_array_equal(ge, ge0.astype(np.float32))
    else:
        size = np.abs(r).astype(np.float64) @ np.abs(x.T)
        assert np.max(np.abs(gx - six) / size) < 2.0**-21
        np.testing.assert_allclose(ge, ge0, rtol=0, atol=2.0**-21 * np.max(
            np.abs(r).astype(np.float64) @ e.T))


@pytest.mark.parametrize("chains", [8, 64])
def test_grouped_kernel_matches_autodiff_at_chain_width(chains):
    """The packed kernel at the NUTS cell's width (8) and the ChEES cell's
    (64) against the plain autodiff oracle of
    `test_grouped_matches_autodiff_value_and_grads`, at its tolerances:
    value and every parameter gradient, chain by chain."""
    ref, rdata, grp, gdata = _models()
    ks = jax.random.split(jax.random.PRNGKey(chains), 4)
    params = {
        "beta": 0.3 * jax.random.normal(ks[0], (chains, 8)),
        "alpha0": 0.3 + 0.1 * jax.random.normal(ks[1], (chains,)),
        "sigma_alpha": jnp.full((chains,), 0.7),
        "alpha_raw": 0.5 * jax.random.normal(ks[3], (chains, 50)),
    }

    def vg(model, data):
        return jax.vmap(jax.value_and_grad(lambda p: model.log_lik(p, data)))(
            params)

    v_ref, g_ref = vg(ref, rdata)
    v_grp, g_grp = vg(grp, gdata)
    np.testing.assert_allclose(v_ref, v_grp, rtol=2e-5)
    for k in params:
        np.testing.assert_allclose(
            np.asarray(g_ref[k]), np.asarray(g_grp[k]), rtol=2e-4,
            atol=1e-4, err_msg=k,
        )


def test_grouped_width_8_batch_matches_per_chain_calls():
    """Eight chains through the op's batching rule (one packed call of
    width 8, as `hier_n16m.nuts` runs it) against eight one-chain calls."""
    from stark_tpu.ops.hier_fused import hier_logistic_loglik

    _, _, _, gdata = _models()
    kb, ka = jax.random.split(jax.random.PRNGKey(8))
    beta = 0.3 * jax.random.normal(kb, (8, 8))
    alpha = 0.5 * jax.random.normal(ka, (8, 50))

    def vg(b, a):
        return jax.value_and_grad(
            lambda b, a: hier_logistic_loglik(
                b, a, gdata["xT"], gdata["y"], gdata["gl"],
                gdata["first_gid"], gdata["k_loc"], gdata["lt128"]),
            argnums=(0, 1))(b, a)

    v_b, (gb_b, ga_b) = jax.vmap(vg)(beta, alpha)
    for i in range(8):
        v, (gb, ga) = vg(beta[i], alpha[i])
        np.testing.assert_allclose(np.asarray(v_b[i]), np.asarray(v), rtol=2e-5)
        np.testing.assert_allclose(
            np.asarray(gb_b[i]), np.asarray(gb), rtol=2e-4, atol=1e-4)
        np.testing.assert_allclose(
            np.asarray(ga_b[i]), np.asarray(ga), rtol=2e-4, atol=1e-4)


@pytest.mark.parametrize("precision, form, rows", [
    (None, "split6", 6 * 8 + 3 * 56),
    ("high", "lax", 8 + 56),
    ("default", "lax", 8 + 56),
])
def test_prepare_span_names_the_mxu_form(precision, form, rows, monkeypatch):
    """`prepare_data` says how the kernel will contract a tile, from the
    function the kernel builds its operands with: the packed six products
    at `highest` (6·D + 3·K_LOC rows deep), one dot at the precision
    otherwise; the layout's own fields are as they were."""
    from stark_tpu import telemetry
    from stark_tpu.ops.hier_fused import grouped_mxu_form

    if precision is None:
        monkeypatch.delenv("STARK_FUSED_PRECISION", raising=False)
    else:
        monkeypatch.setenv("STARK_FUSED_PRECISION", precision)
    _, _, _, gdata = _models()
    (sp,) = [r for r in telemetry.span_log() if r.name == "prepare_data"][-1:]
    assert gdata["k_loc"].shape[0] == 56  # 50 groups in one tile
    assert (sp.fields["mxu_form"], sp.fields["mxu_rows"]) == (form, rows)
    assert grouped_mxu_form(8, 56) == (form, rows)
    assert (sp.fields["lane_tile"], sp.fields["k_loc"], sp.fields["tiles"]) \
        == (8192, 56, 1)


@pytest.mark.parametrize("q", [1, 2, 3])
def test_lmm_kernel_matches_plain_sums(q, monkeypatch):
    """`_grouped_lmm_call` in interpret mode against plain jax.numpy at
    `highest` on the same group-sorted rows: the tiles' sums of squares,
    sum resid, X·resid and the u-gradient, at a ragged last tile, five chains
    (padded to eight) and groups that straddle tiles."""
    from stark_tpu.ops.hier_fused import _grouped_lmm_call

    monkeypatch.setenv("STARK_GROUPED_LANE_TILE", "512")
    n, d, chains, groups = 3 * 512 + 37, 5, 5, 40
    g = _sorted_groups(n, groups)
    lane_tile, k_loc, first_gid, gl = grouped_layout(g, d + q)
    assert lane_tile == 512 and n % lane_tile
    edges = np.arange(lane_tile, n, lane_tile)
    assert np.any(g[edges - 1] == g[edges])  # a group straddles a tile edge
    ks = jax.random.split(jax.random.PRNGKey(q), 6)
    xt = jax.random.normal(ks[0], (d, n))
    zt = jax.random.normal(ks[1], (q, n))
    y = 2.0 * jax.random.normal(ks[2], (n,))
    beta = 0.3 * jax.random.normal(ks[3], (chains, d))
    u = 0.5 * jax.random.normal(ks[4], (chains, groups, q))
    ic = jax.random.normal(ks[5], (chains,))

    ssr, sresid, gbeta, gu = _grouped_lmm_call(
        beta, u, ic, xt, zt, y, jnp.asarray(gl), jnp.asarray(first_gid),
        k_loc=k_loc, lane_tile=lane_tile, interpret=True,
    )

    hi = jax.lax.Precision.HIGHEST
    mu = (
        ic[:, None] + jnp.dot(beta, xt, precision=hi)
        + jnp.einsum("cnq,qn->cn", u[:, g, :], zt, precision=hi)
    )
    resid = y - mu  # (C, N)
    tiles = -(-n // lane_tile)
    padded = jnp.pad(resid, ((0, 0), (0, tiles * lane_tile - n)))
    ssr0 = jnp.sum(padded.reshape(chains, tiles, lane_tile) ** 2, axis=-1)
    gu0 = jnp.stack(
        [
            jax.ops.segment_sum((resid * zt[j]).T, g, num_segments=groups).T
            for j in range(q)
        ],
        axis=-1,
    )
    np.testing.assert_allclose(ssr, ssr0, rtol=2e-5)
    for name, a, b in (
        ("sresid", sresid, jnp.sum(resid, axis=1)),
        ("gbeta", gbeta, jnp.dot(resid, xt.T, precision=hi)),
        ("gu", gu, gu0),
    ):
        assert a.shape == b.shape, name
        np.testing.assert_allclose(a, b, rtol=3e-4, atol=3e-4, err_msg=name)


@pytest.mark.slow
def test_grouped_chain_batched_matches_per_chain():
    _, _, grp, gdata = _models()
    fm = flatten_model(grp)
    pot = fm.bind(gdata)
    zs = 0.1 * jax.random.normal(jax.random.PRNGKey(1), (5, fm.ndim))
    vg = jax.value_and_grad(pot)
    v_b, g_b = jax.vmap(vg)(zs)
    v_s = jnp.stack([vg(z)[0] for z in zs])
    g_s = jnp.stack([vg(z)[1] for z in zs])
    np.testing.assert_allclose(np.asarray(v_b), np.asarray(v_s), rtol=2e-5)
    np.testing.assert_allclose(
        np.asarray(g_b), np.asarray(g_s), rtol=2e-4, atol=1e-4
    )


@pytest.mark.slow
def test_grouped_same_posterior_as_offset_path():
    """End-to-end: short ChEES runs on grouped vs offset models land on
    the same posterior summaries (same data, different layouts)."""
    import stark_tpu

    n, d, groups = 20_000, 4, 20
    data, _ = synth_logistic_data(
        jax.random.PRNGKey(2), n, d, num_groups=groups
    )
    outs = {}
    for name, model in (
        ("offset", FusedHierLogistic(num_features=d, num_groups=groups)),
        ("grouped", FusedHierLogisticGrouped(num_features=d, num_groups=groups)),
    ):
        post = stark_tpu.sample(
            model, data, chains=8, kernel="chees", num_warmup=200,
            num_samples=200, init_step_size=0.1, map_init_steps=100, seed=3,
        )
        outs[name] = post.summary()["beta"]["mean"]
    np.testing.assert_allclose(
        np.asarray(outs["offset"]), np.asarray(outs["grouped"]), atol=0.05
    )


def test_chain_vmem_guard():
    """C=128 at TILE=8192 measured a 20 MB scoped-VMEM Mosaic OOM on
    chip; the guard must turn that into an actionable error (and stay
    quiet in interpret mode and at the measured-good C=64)."""
    from stark_tpu.ops.hier_fused import _check_chain_vmem

    _check_chain_vmem(64, 8192, False)  # the flagship config: fine
    _check_chain_vmem(128, 8192, True)  # interpreter: no VMEM, no guard
    with pytest.raises(ValueError, match="chains") as err:
        _check_chain_vmem(128, 8192, False)
    # every remedy the message names exists
    assert "STARK_GROUPED_LANE_TILE" in str(err.value)
    assert "offset-layout Fused" in str(err.value)
    # the stacked (D + K_LOC, TILE) slab counts: 96 chains fit the budget
    # without it (the LMM kernel's call) and not with it
    _check_chain_vmem(64, 8192, False, k_loc=8, slab_rows=40)  # the cell
    _check_chain_vmem(96, 8192, False, k_loc=8)
    with pytest.raises(ValueError, match="chains"):
        _check_chain_vmem(96, 8192, False, k_loc=8, slab_rows=40)


def test_interpret_mode_is_decided_in_one_place():
    """None means "compiled, unless the default backend is the CPU" (this
    suite); an explicit choice passes through.  Both kernel modules route
    through the same function."""
    from stark_tpu.ops import hier_fused, logistic_fused

    assert logistic_fused._resolve_interpret(None) is True  # CPU suite
    assert logistic_fused._resolve_interpret(False) is False
    assert logistic_fused._resolve_interpret(True) is True
    assert hier_fused._resolve_interpret is logistic_fused._resolve_interpret


@pytest.mark.slow
def test_lmm_grouped_matches_autodiff():
    """Grouped LMM kernel vs the plain autodiff LinearMixedModel on the
    same sorted rows — value and every parameter gradient, including the
    dense-grouping regime (few rows per group -> shrunken lane tile)."""
    from stark_tpu.models import (
        FusedLinearMixedModelGrouped,
        LinearMixedModel,
        synth_lmm_data,
    )

    n, d, groups, q = 12_288 + 55, 5, 1500, 2  # ~8 rows/group: dense
    data, _ = synth_lmm_data(jax.random.PRNGKey(3), n, d, groups)
    ref = LinearMixedModel(num_features=d, num_groups=groups)
    grp = FusedLinearMixedModelGrouped(num_features=d, num_groups=groups)
    gdata = prepare_model_data(grp, data)
    assert "gl" in gdata, "layout unexpectedly fell back"
    # dense grouping must have shrunk the tile below the default
    from stark_tpu.ops.hier_fused import grouped_lane_tile

    assert gdata["lt128"].shape[0] * 128 < grouped_lane_tile(d + q)
    order = np.argsort(np.asarray(data["g"]), kind="stable")
    rdata = {k: jnp.asarray(np.asarray(v)[order]) for k, v in data.items()}

    params = {
        "intercept": jnp.float32(0.8),
        "beta": 0.2 * jnp.arange(d, dtype=jnp.float32),
        "u_raw": 0.01 * jax.random.normal(jax.random.PRNGKey(5), (groups, q)),
        "tau": jnp.asarray([0.7, 0.4]),
        "sigma": jnp.float32(0.6),
    }
    v_ref = ref.log_lik(params, rdata)
    v_grp = grp.log_lik(params, gdata)
    np.testing.assert_allclose(v_ref, v_grp, rtol=2e-5)
    g_ref = jax.grad(lambda p: ref.log_lik(p, rdata))(params)
    g_grp = jax.grad(lambda p: grp.log_lik(p, gdata))(params)
    for k in params:
        np.testing.assert_allclose(
            np.asarray(g_ref[k]), np.asarray(g_grp[k]), rtol=3e-4,
            atol=3e-4, err_msg=k,
        )


@pytest.mark.slow
def test_lmm_grouped_chain_batched_matches_per_chain():
    from stark_tpu.models import FusedLinearMixedModelGrouped, synth_lmm_data

    n, d, groups = 8192, 4, 800
    data, _ = synth_lmm_data(jax.random.PRNGKey(6), n, d, groups)
    grp = FusedLinearMixedModelGrouped(num_features=d, num_groups=groups)
    gdata = prepare_model_data(grp, data)
    fm = flatten_model(grp)
    pot = fm.bind(gdata)
    zs = 0.05 * jax.random.normal(jax.random.PRNGKey(7), (4, fm.ndim))
    vg = jax.value_and_grad(pot)
    v_b, g_b = jax.vmap(vg)(zs)
    v_s = jnp.stack([vg(z)[0] for z in zs])
    g_s = jnp.stack([vg(z)[1] for z in zs])
    np.testing.assert_allclose(np.asarray(v_b), np.asarray(v_s), rtol=2e-5)
    np.testing.assert_allclose(
        np.asarray(g_b), np.asarray(g_s), rtol=3e-4, atol=3e-4
    )


def test_grouped_fallback_on_degenerate_grouping():
    """Every row its own group at N=20k: spans blow past _K_LOC_MAX, so
    prepare_data must fall back to the offset layout and still work."""
    d = 4
    n = 20_000
    data, _ = synth_logistic_data(jax.random.PRNGKey(4), n, d, num_groups=1)
    data["g"] = jnp.arange(n, dtype=jnp.int32)  # degenerate: n groups
    grp = FusedHierLogisticGrouped(num_features=d, num_groups=n)
    gdata = prepare_model_data(grp, data)
    assert "gl" not in gdata and "xT" in gdata
    params = {
        "beta": jnp.zeros((d,)),
        "alpha0": jnp.float32(0.0),
        "sigma_alpha": jnp.float32(1.0),
        "alpha_raw": jnp.zeros((n,)),
    }
    v = grp.log_lik(params, gdata)
    assert np.isfinite(np.asarray(v))


def test_fused_precision_knob(monkeypatch):
    """STARK_FUSED_PRECISION selects the MXU dot precision (the on-chip
    lever for the MXU-pass-bound grouped kernel, BASELINE.md r5); on CPU
    the three settings are numerically identical (f32 dots are exact
    there), and an invalid value fails loudly at kernel build."""
    import pytest

    from stark_tpu.ops.logistic_fused import _dot_precision
    import jax

    monkeypatch.delenv("STARK_FUSED_PRECISION", raising=False)
    assert _dot_precision() == jax.lax.Precision.HIGHEST  # default
    for name, want in (
        ("highest", jax.lax.Precision.HIGHEST),
        ("high", jax.lax.Precision.HIGH),
        ("default", jax.lax.Precision.DEFAULT),
        ("HIGH", jax.lax.Precision.HIGH),  # case-insensitive
    ):
        monkeypatch.setenv("STARK_FUSED_PRECISION", name)
        assert _dot_precision() == want
    monkeypatch.setenv("STARK_FUSED_PRECISION", "fast")
    with pytest.raises(ValueError, match="highest|high|default"):
        _dot_precision()


def test_grouped_x_bf16_stream_matches_rounded_oracle(monkeypatch):
    """STARK_FUSED_X_DTYPE=bf16 (the stream-side lever, BASELINE.md r5):
    prepare stores xT in bf16, the kernel casts back to f32 in-register,
    and the computed posterior is exactly that of the ROUNDED design
    matrix — value and gradients match the plain-autodiff oracle run on
    the same bf16-rounded X to f32 tolerance."""
    monkeypatch.setenv("STARK_FUSED_X_DTYPE", "bf16")
    ref, rdata, grp, gdata = _models()
    assert gdata["xT"].dtype == jnp.bfloat16
    rdata = dict(rdata)
    rdata["x"] = rdata["x"].astype(jnp.bfloat16).astype(jnp.float32)
    params = {
        "beta": 0.1 * jnp.arange(8, dtype=jnp.float32),
        "alpha0": jnp.float32(0.3),
        "sigma_alpha": jnp.float32(0.7),
        "alpha_raw": 0.05 * jnp.arange(50, dtype=jnp.float32) - 1.0,
    }
    v_ref = ref.log_lik(params, rdata)
    v_grp = grp.log_lik(params, gdata)
    np.testing.assert_allclose(v_ref, v_grp, rtol=2e-5)
    g_ref = jax.grad(lambda p: ref.log_lik(p, rdata))(params)
    g_grp = jax.grad(lambda p: grp.log_lik(p, gdata))(params)
    for k in params:
        np.testing.assert_allclose(
            np.asarray(g_ref[k]), np.asarray(g_grp[k]), rtol=2e-4,
            atol=1e-4, err_msg=k,
        )


def test_x_stream_dtype_knob(monkeypatch):
    from stark_tpu.ops.logistic_fused import _x_stream_dtype

    monkeypatch.delenv("STARK_FUSED_X_DTYPE", raising=False)
    assert _x_stream_dtype() == jnp.float32  # default
    monkeypatch.setenv("STARK_FUSED_X_DTYPE", "bf16")
    assert _x_stream_dtype() == jnp.bfloat16
    monkeypatch.setenv("STARK_FUSED_X_DTYPE", "fp8")
    with pytest.raises(ValueError, match="f32|bf16"):
        _x_stream_dtype()


def test_precision_knob_in_jit_cache_key(monkeypatch):
    """Toggling STARK_FUSED_PRECISION / STARK_FUSED_X_DTYPE mid-process
    must retrace the module-level-jitted public helper, never reuse the
    stale same-shape executable (ADVICE r5): the resolved knob values are
    threaded into the jit cache key as call-time statics."""
    from stark_tpu.ops.logistic_fused import (
        _loglik_vg_jit,
        logistic_loglik_value_and_grad,
    )

    monkeypatch.delenv("STARK_FUSED_PRECISION", raising=False)
    monkeypatch.delenv("STARK_FUSED_X_DTYPE", raising=False)
    rng = np.random.default_rng(0)
    xt = jnp.asarray(rng.standard_normal((4, 64)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 2, 64), jnp.float32)
    beta = jnp.asarray(rng.standard_normal(4), jnp.float32)
    v0, g0 = logistic_loglik_value_and_grad(beta, xt, y)
    n0 = _loglik_vg_jit._cache_size()
    # same shapes + same knobs: cache hit, no retrace
    logistic_loglik_value_and_grad(beta, xt, y)
    assert _loglik_vg_jit._cache_size() == n0
    # knob change: a FRESH executable must be traced for the same shapes
    monkeypatch.setenv("STARK_FUSED_PRECISION", "high")
    v1, g1 = logistic_loglik_value_and_grad(beta, xt, y)
    assert _loglik_vg_jit._cache_size() == n0 + 1
    # CPU f32 dots are exact, so the numerics agree on the test host
    np.testing.assert_allclose(np.asarray(v0), np.asarray(v1), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(g0), np.asarray(g1), rtol=1e-6)


def test_grouped_lane_tile_env_cap(monkeypatch):
    """STARK_GROUPED_LANE_TILE caps the starting tile so large chain
    batches (C=128) can trade tile size for VMEM instead of being refused
    by the guard; invalid values fail loudly."""
    g = np.sort(np.random.RandomState(0).randint(0, 50, size=20_000))
    lt_default, _, _, _ = grouped_layout(g, d=8)
    monkeypatch.setenv("STARK_GROUPED_LANE_TILE", "1024")
    lt_capped, k_loc, first_gid, gl = grouped_layout(g, d=8)
    assert lt_capped == 1024 < lt_default
    assert first_gid.shape[0] == -(-20_000 // 1024)
    rec = first_gid[np.arange(20_000) // 1024] + gl
    np.testing.assert_array_equal(rec, g)
    monkeypatch.setenv("STARK_GROUPED_LANE_TILE", "1000")  # not 128-aligned
    with pytest.raises(ValueError, match="128-multiple"):
        grouped_layout(g, d=8)
