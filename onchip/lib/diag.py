"""Convergence arithmetic of the benchmark: split R-hat, Geyer ESS on split
chains, rank normalisation, bulk ESS and rank R-hat.

A copy of the arithmetic of `stark_tpu.diagnostics` (sound; PERF.md verdict
table), kept here so that no later PR moves the yardstick that
`ess_per_s_chip` is measured with.  Host numpy, float64.  The original stays
in the program for its own gate; `onchip/tests` holds the two to agreement.
"""

import numpy as np

#: FFT / ranking workspace cap, bytes
_WORKSPACE_BYTES = 256e6


def _split_chains(x):
    """(chains, draws, ...) -> (2*chains, draws//2, ...)."""
    c, n = x.shape[0], x.shape[1]
    half = n // 2
    x = x[:, : 2 * half]
    return x.reshape(c * 2, half, *x.shape[2:])


def split_rhat(x):
    """Split-R-hat over (chains, draws, *event) -> (*event,)."""
    x = _split_chains(np.asarray(x, np.float64))
    n = x.shape[1]
    within = x.var(axis=1, ddof=1).mean(axis=0)
    between = n * x.mean(axis=1).var(axis=0, ddof=1)
    var_plus = (n - 1) / n * within + between / n
    with np.errstate(divide="ignore", invalid="ignore"):
        return np.sqrt(var_plus / within)


def _autocov_fft(x):
    n = x.shape[1]
    x = x - x.mean(axis=1, keepdims=True)
    size = 2 ** int(np.ceil(np.log2(2 * n)))
    f = np.fft.rfft(x, size, axis=1)
    return np.fft.irfft(f * np.conj(f), size, axis=1)[:, :n] / n


def _ess_chunk(x):
    """ESS of split chains (m, n, cols): Geyer's initial positive, monotone
    sequence over lag pairs."""
    m, n = x.shape[0], x.shape[1]
    acov = _autocov_fft(x)
    mean_var = (acov[:, 0] * n / (n - 1.0)).mean(axis=0)
    var_plus = mean_var * (n - 1.0) / n
    if m > 1:
        var_plus = var_plus + x.mean(axis=1).var(axis=0, ddof=1)
    with np.errstate(divide="ignore", invalid="ignore"):
        rho = 1.0 - (mean_var - acov.mean(axis=0)) / var_plus
    rho[0] = 1.0
    max_pairs = n // 2
    pair = rho[0: 2 * max_pairs: 2] + rho[1: 2 * max_pairs: 2]
    valid = np.cumprod(pair >= 0.0, axis=0).astype(bool)
    mono = np.minimum.accumulate(np.where(valid, pair, np.inf), axis=0)
    tau = -1.0 + 2.0 * np.sum(np.where(valid, mono, 0.0), axis=0)
    tau = np.maximum(tau, 1.0 / np.log10(m * n + 10.0))
    out = m * n / tau
    # a component that never moved has no ESS: NaN, so it fails a gate
    const = np.all(x.max(axis=1) == x.min(axis=1), axis=0)
    out[const | ~np.isfinite(var_plus) | (var_plus <= 0.0)] = np.nan
    return out


def ess(x):
    """Effective sample size of the mean over (chains, draws, *event)."""
    x = _split_chains(np.asarray(x, np.float64))
    m, n = x.shape[0], x.shape[1]
    event_shape = x.shape[2:]
    flat = x.reshape(m, n, -1)
    cols = flat.shape[2]
    size = 2 ** int(np.ceil(np.log2(2 * max(n, 1))))
    chunk = max(1, int(_WORKSPACE_BYTES / (m * size * 16)))
    out = np.empty(cols)
    for lo in range(0, cols, chunk):
        out[lo: lo + chunk] = _ess_chunk(flat[:, :, lo: lo + chunk])
    return out.reshape(event_shape) if event_shape else out[0]


def rank_normalize(x):
    """Pooled fractional ranks -> normal scores (Vehtari et al. 2021, eq. 14),
    average ties, (r - 3/8) / (S + 1/4)."""
    from scipy.special import ndtri
    from scipy.stats import rankdata

    x = np.asarray(x, np.float64)
    c, n = x.shape[0], x.shape[1]
    flat = x.reshape(c * n, -1)
    per = max(1, int(_WORKSPACE_BYTES) // (32 * max(flat.shape[0], 1)))
    z = np.empty_like(flat)
    for j0 in range(0, flat.shape[1], per):
        sl = slice(j0, j0 + per)
        r = rankdata(flat[:, sl], method="average", axis=0)
        z[:, sl] = ndtri((r - 0.375) / (c * n + 0.25))
    return z.reshape(x.shape)


def ess_bulk(x):
    """Bulk ESS: Geyer ESS of the rank-normalised draws."""
    return ess(rank_normalize(x))


def rank_rhat(x):
    """Rank-normalised split-R-hat: the larger of the bulk and folded forms."""
    x = np.asarray(x, np.float64)
    bulk = split_rhat(rank_normalize(x))
    med = np.median(x.reshape(-1, *x.shape[2:]), axis=0)
    return np.maximum(bulk, split_rhat(rank_normalize(np.abs(x - med))))


def min_bulk_ess(draws):
    """Smallest bulk ESS over every scalar of a dict of constrained draws,
    each (chains, draws, *event).  NaN when any scalar has none."""
    vals = [np.ravel(ess_bulk(np.asarray(v))) for v in draws.values()]
    return float(np.min(np.concatenate(vals)))
