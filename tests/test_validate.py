"""Geweke + SBC oracles (SURVEY.md §5): pass on a correct setup, and have
the power to flag a broken one."""

import jax
import jax.numpy as jnp
import jax.scipy.stats as jstats
import numpy as np

from stark_tpu.bijectors import Exp
from stark_tpu.model import Model, ParamSpec
from stark_tpu.validate import geweke_test, sbc
import pytest

_N = 20


class NormalModel(Model):
    """mu ~ N(0, 2), sigma ~ LogNormal(0, 0.5), y_i ~ N(mu, sigma)."""

    def param_spec(self):
        return {"mu": ParamSpec(()), "sigma": ParamSpec((), Exp())}

    def log_prior(self, p):
        lp = jstats.norm.logpdf(p["mu"], 0.0, 2.0)
        lp += jstats.norm.logpdf(jnp.log(p["sigma"]), 0.0, 0.5) - jnp.log(p["sigma"])
        return lp

    def log_lik(self, p, data):
        return jnp.sum(jstats.norm.logpdf(data["y"], p["mu"], p["sigma"]))


def _sample_prior(key):
    k1, k2 = jax.random.split(key)
    return {
        "mu": 2.0 * jax.random.normal(k1, ()),
        "sigma": jnp.exp(0.5 * jax.random.normal(k2, ())),
    }


def _simulate(key, params):
    return {"y": params["mu"] + params["sigma"] * jax.random.normal(key, (_N,))}


def test_geweke_passes_on_correct_kernel():
    res = geweke_test(
        NormalModel(), _sample_prior, _simulate, jax.random.PRNGKey(0),
        num_iters=1500, thin=5, step_size=0.2, num_leapfrog=8,
    )
    assert res.max_abs_z() < 4.5, res.zscores


def test_geweke_flags_mismatched_generative():
    """Power check: a prior/generative mismatch must blow up the z-scores."""

    def wrong_prior(key):  # draws mu ~ N(0, 4) while the model says N(0, 2)
        p = _sample_prior(key)
        return {**p, "mu": 2.0 * p["mu"]}

    res = geweke_test(
        NormalModel(), wrong_prior, _simulate, jax.random.PRNGKey(0),
        num_iters=1500, thin=5, step_size=0.2, num_leapfrog=8,
    )
    assert res.max_abs_z() > 6.0, res.zscores


@pytest.mark.slow  # >=8s on the 1-core host (pytest.ini policy, re-profiled 2026-08-03)
def test_sbc_ranks_uniform():
    res = sbc(
        NormalModel(), _sample_prior, _simulate, jax.random.PRNGKey(1),
        num_replicates=96, num_bins=8,
        kernel="nuts", max_tree_depth=6, num_warmup=300, num_samples=255,
        thin=4,
    )
    # chi2(7) 99.9% quantile ~= 24.3; a broken sampler lands far above
    stats = res.chi2()
    assert max(stats.values()) < 25.0, stats
    # sanity: ranks span the full [0, L] range rather than collapsing
    for r in res.ranks.values():
        assert int(np.min(r)) >= 0 and int(np.max(r)) <= 255
        assert np.ptp(r) > 100


# ---- distribution-level oracles on the PRODUCTION fused likelihood ----
# The flagship path runs FusedHierLogistic through the Pallas kernel with
# custom_vjp (gradients) and custom_vmap (chain batching).  Gradient parity
# is unit-tested in test_ops_fused; these tests cover the same code with
# the Geweke/SBC joint-distribution oracles so a subtly wrong VJP or
# batching rule shows up as a posterior-level miscalibration.

# small N: Geweke's successive chain explores theta ACROSS the prior via
# data redraws; a large informative dataset pins the per-redraw posterior
# (sd(alpha0|y) << prior sd 5) and the chain cannot traverse the prior in
# any reasonable budget — that shows up as z ~ 10+ on alpha0 for the
# autodiff and fused models IDENTICALLY, i.e. a test-setup artifact
_FN, _FD, _FG = 32, 3, 4
_fx = jax.random.normal(jax.random.PRNGKey(42), (_FN, _FD))
_fg = jax.random.randint(jax.random.PRNGKey(43), (_FN,), 0, _FG)


def _fused_prior(key):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "beta": 2.5 * jax.random.normal(k1, (_FD,)),
        "alpha0": 5.0 * jax.random.normal(k2, ()),
        "sigma_alpha": jnp.abs(jax.random.normal(k3, ())),  # half-normal(1)
        "alpha_raw": jax.random.normal(k4, (_FG,)),
    }


def _fused_simulate(key, p):
    alpha = p["alpha0"] + p["sigma_alpha"] * p["alpha_raw"]
    logits = _fx @ p["beta"] + alpha[_fg]
    y = (jax.random.uniform(key, (_FN,)) < jax.nn.sigmoid(logits)).astype(
        jnp.float32
    )
    return {"x": _fx, "g": _fg, "y": y}


@pytest.mark.slow
def test_geweke_fused_hier_logistic():
    from stark_tpu.models import FusedHierLogistic

    res = geweke_test(
        FusedHierLogistic(num_features=_FD, num_groups=_FG),
        _fused_prior, _fused_simulate, jax.random.PRNGKey(2),
        num_iters=800, thin=8, step_size=0.2, num_leapfrog=8,
    )
    assert res.max_abs_z() < 5.0, res.zscores


@pytest.mark.slow
def test_sbc_fused_hier_logistic():
    from stark_tpu.models import FusedHierLogistic

    res = sbc(
        FusedHierLogistic(num_features=_FD, num_groups=_FG),
        _fused_prior, _fused_simulate, jax.random.PRNGKey(3),
        num_replicates=64, num_bins=8,
        kernel="hmc", num_leapfrog=8, num_warmup=200, num_samples=127,
        thin=2,
    )
    stats = res.chi2()
    # chi2(7) 99.9% quantile ~= 24.3
    assert max(stats.values()) < 25.0, stats
    for r in res.ranks.values():
        # span check: a collapsed/stuck sampler bunches ranks; uniform
        # ranks over [0, 127] must cover most of the range
        assert np.ptp(r) > 90, (int(np.min(r)), int(np.max(r)))


@pytest.mark.slow
def test_sbc_cox_ph():
    """SBC on the Breslow partial likelihood with CONTINUOUS times.

    Continuous times only: with heavy ties Breslow's denominator is a
    known-biased approximation of the tied-event likelihood, and SBC
    correctly flags that statistical bias (measured chi2 ~ 125 with
    8-per-unit discretized times) — an estimator property, not an
    implementation bug.  The implementation's tie-block handling is
    pinned exactly by test_cox_breslow_ties_match_reference (O(N^2)
    reference); this test covers the sampler+likelihood calibration in
    the regime where the partial likelihood is the right estimator.
    """
    from stark_tpu.models import CoxPH

    _n, _d = 96, 2
    x_fix = jax.random.normal(jax.random.PRNGKey(44), (_n, _d))

    def prior(key):
        return {"beta": 2.5 * jax.random.normal(key, (_d,))}

    def simulate(key, p):
        k1, k2 = jax.random.split(key)
        rate = jnp.exp(x_fix @ p["beta"])
        t = jax.random.exponential(k1, (_n,)) / rate
        event = (jax.random.uniform(k2, (_n,)) > 0.3).astype(jnp.float32)
        return {"x": x_fix, "t": t, "event": event}

    res = sbc(
        CoxPH(num_features=_d), prior, simulate, jax.random.PRNGKey(5),
        num_replicates=64, num_bins=8,
        kernel="hmc", num_leapfrog=8, num_warmup=200, num_samples=127,
        thin=2,
    )
    stats = res.chi2()
    assert max(stats.values()) < 25.0, stats
