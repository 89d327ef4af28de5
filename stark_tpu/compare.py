"""Model comparison: WAIC and PSIS-LOO from pointwise log-likelihoods.

Predictive-accuracy estimates for fitted models (Vehtari, Gelman & Gabry
2017 patterns; implementations original):

* ``waic``: widely-applicable information criterion — elpd estimated as
  lppd minus the pointwise posterior variance penalty.
* ``psis_loo``: leave-one-out CV via Pareto-smoothed importance sampling
  — the raw importance ratios' tail is replaced by generalized-Pareto
  quantiles (Zhang–Stephens fit), and the per-observation shape k is the
  built-in reliability diagnostic (k > 0.7 = unreliable).

Both take a pointwise matrix ``ll`` of shape (chains, draws, N) — build
it with ``pointwise_log_lik`` for any model implementing
``log_lik_rows(params, data) -> (N,)``.  Pointwise matrices are
O(draws x N): this is a small-to-medium-N tool (model comparison), not a
flagship-scale one — compute it on the host CPU backend.

Capability beyond the reference inventory (SURVEY.md §3 lists no model
comparison); reference tree absent (SURVEY.md §0), design original.
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np


def _flatten(ll) -> np.ndarray:
    ll = np.asarray(ll, np.float64)
    if ll.ndim != 3:
        raise ValueError(f"ll must be (chains, draws, N); got {ll.shape}")
    return ll.reshape(-1, ll.shape[-1])  # (S, N)


def _logsumexp(a, axis=0):
    # scipy's handles all--inf columns (-inf, not NaN) — a real state when
    # an extreme draw saturates log_sigmoid
    from scipy.special import logsumexp

    return logsumexp(a, axis=axis)


def waic(ll) -> Dict[str, Any]:
    """-> {elpd_waic, p_waic, se, pointwise} from (chains, draws, N)."""
    s_ll = _flatten(ll)
    S = s_ll.shape[0]
    lppd_i = _logsumexp(s_ll, axis=0) - np.log(S)  # (N,)
    p_i = s_ll.var(axis=0, ddof=1)  # (N,) posterior variance penalty
    elpd_i = lppd_i - p_i
    n = elpd_i.shape[0]
    return {
        "elpd_waic": float(elpd_i.sum()),
        "p_waic": float(p_i.sum()),
        "se": float(np.sqrt(n * elpd_i.var(ddof=1))),
        "pointwise": elpd_i,
    }


def _gpd_fit(x: np.ndarray):
    """Zhang & Stephens (2009) profile-posterior-mean fit of the
    generalized Pareto to exceedances x > 0.

    Returns (xi, sigma) in the STANDARD shape convention (xi > 0 = heavy
    tail) that `_gpd_quantiles` and the k > 0.7 reliability threshold
    use — Zhang–Stephens' own k is -xi, and returning it unnegated made
    heavy tails report large-NEGATIVE k that could never trip the gate
    (caught by a sign-flipped fit on synthetic GPD(xi=0.5) samples).
    """
    x = np.sort(np.asarray(x, np.float64))
    n = x.shape[0]
    m = 30 + int(np.sqrt(n))
    prior_bs = 3.0
    q1 = x[int(n / 4 + 0.5) - 1] if n >= 4 else x[0]
    bs = 1.0 - np.sqrt(m / (np.arange(1, m + 1) - 0.5))
    bs = bs / (prior_bs * q1) + 1.0 / x[-1]
    ks = -np.mean(np.log1p(-bs[:, None] * x[None, :]), axis=1)
    L = n * (np.log(bs / ks) + ks - 1.0)
    with np.errstate(over="ignore"):  # inf -> weight 0, the right limit
        w = 1.0 / np.sum(np.exp(L[None, :] - L[:, None]), axis=1)
    b = np.sum(bs * w)
    xi = np.mean(np.log1p(-b * x))
    sigma = -xi / b
    return float(xi), float(sigma)


def _gpd_quantiles(p, k, sigma):
    if abs(k) < 1e-12:
        return -sigma * np.log1p(-p)
    return sigma * (np.power(1.0 - p, -k) - 1.0) / k


def psis_smooth(logw: np.ndarray):
    """Pareto-smooth ONE observation's S log-ratios.

    Returns (normalized log-weights, pareto k).  The top ~20% of raw
    ratios is replaced by generalized-Pareto order quantiles (in rank
    order) and capped at the raw maximum, per the PSIS recipe.
    """
    logw = np.asarray(logw, np.float64)
    logw = logw - logw.max()  # stabilize exp(); raw max becomes 0
    S = logw.shape[0]
    # tail size per the published recipe: min(0.2 S, 3 sqrt(S)) — the
    # sqrt cap keeps the GPD fit on the extreme tail instead of bulk
    # mass as S grows
    m = min(int(0.2 * S + 1), int(3.0 * np.sqrt(S)), S - 1)
    if m < 5:
        # cannot diagnose the tail: k is UNKNOWN, not zero — NaN forces
        # the caller to notice (ArviZ convention)
        return logw - _logsumexp(logw), float("nan")
    srt = np.argsort(logw)
    tail_idx = srt[-m:]  # ascending within the tail
    cutoff = logw[srt[-m - 1]]
    exceed = np.exp(logw[tail_idx]) - np.exp(cutoff)
    pos = exceed > 0
    n_fit = int(pos.sum())
    if n_fit < 5:
        return logw - _logsumexp(logw), float("nan")
    k, sigma = _gpd_fit(exceed[pos])
    # published-PSIS small-sample shape regularization: shrink khat toward
    # 0.5 with prior weight 10 so tiny tails don't produce noisy k near
    # the 0.7 reliability threshold (ADVICE r3: compare.py)
    k = (n_fit * k + 5.0) / (n_fit + 10.0)
    # smooth only the strictly-positive exceedances (the same set the GPD
    # was fitted on); ties at the cutoff keep their raw value, which IS
    # the cutoff — handing them GPD quantiles they never informed skewed
    # the smoothed tail (ADVICE r3)
    p = (np.arange(1, n_fit + 1) - 0.5) / n_fit
    smoothed = np.log(np.exp(cutoff) + _gpd_quantiles(p, k, sigma))
    out = logw.copy()
    out[tail_idx[pos]] = np.minimum(smoothed, 0.0)  # cap at the raw max
    return out - _logsumexp(out), float(k)


def psis_loo(ll) -> Dict[str, Any]:
    """-> {elpd_loo, p_loo, se, pareto_k, pointwise} from
    (chains, draws, N).  pareto_k > 0.7 marks observations whose LOO
    estimate is unreliable (refit without them to be sure); NaN k means
    the tail had too few distinct ratios to diagnose at all (tiny S)."""
    s_ll = _flatten(ll)
    S, n = s_ll.shape
    lppd_i = _logsumexp(s_ll, axis=0) - np.log(S)
    elpd_i = np.empty(n)
    ks = np.empty(n)
    for i in range(n):
        logw, k = psis_smooth(-s_ll[:, i])
        ks[i] = k
        elpd_i[i] = _logsumexp(logw + s_ll[:, i])
    return {
        "elpd_loo": float(elpd_i.sum()),
        "p_loo": float((lppd_i - elpd_i).sum()),
        "se": float(np.sqrt(n * elpd_i.var(ddof=1))),
        "pareto_k": ks,
        "pointwise": elpd_i,
    }


def compare(results: Dict[str, Dict[str, Any]]) -> Dict[str, Any]:
    """Rank models by elpd (waic or loo results); returns name -> row
    with elpd, the difference to the best, and the SE of the difference
    computed from the paired pointwise values (the honest SE — pointwise
    elpds are correlated across models on shared data)."""
    key = "elpd_loo" if "elpd_loo" in next(iter(results.values())) else "elpd_waic"
    best = max(results, key=lambda k: results[k][key])
    out = {}
    for name, r in results.items():
        diff_i = results[best]["pointwise"] - r["pointwise"]
        n = diff_i.shape[0]
        out[name] = {
            "elpd": r[key],
            "elpd_diff": float(diff_i.sum()),
            "diff_se": float(np.sqrt(n * diff_i.var(ddof=1))) if name != best else 0.0,
            "rank": None,  # filled below
        }
    for rank, name in enumerate(
        sorted(out, key=lambda k: -out[k]["elpd"]), start=1
    ):
        out[name]["rank"] = rank
    return out


def pointwise_log_lik(model, posterior, data, *, thin: int = 1) -> np.ndarray:
    """(chains, draws/thin, N) pointwise log-lik matrix via
    ``model.log_lik_rows`` applied to every (thinned) posterior draw, on
    the default device."""
    import jax

    # data is used RAW (log_lik_rows handles either layout): prepare_data
    # may permute rows (the Grouped models sort by group), which would
    # silently misalign pointwise elpds/pareto_k with the caller's rows
    # and break paired comparisons across models
    draws = {k: np.asarray(v)[:, ::thin] for k, v in posterior.draws.items()}
    fn = jax.jit(jax.vmap(jax.vmap(lambda p: model.log_lik_rows(p, data))))
    return np.asarray(fn(draws))
