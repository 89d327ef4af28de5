"""Counts `lmm_rows`: what the log-likelihood gradient of a linear mixed
model over N rows of d fixed and q random effects has to do, from its shapes
alone (`sizes`: n, d, q): the same numbers whatever implements the kernel.
One *ensemble gradient* streams the rows once for all `chains`; one
*chain-gradient* is one chain's share of it (`counts/glm_rows.py`).
"""


def flops_per_chain_gradient(sizes):
    """The mean x.beta + z.u[g] (2*N*(d + q)) and the gradient's x^T r and
    z-weighted group sums (2*N*(d + q)); the per-row residual and its square
    cost O(N) and are left out."""
    return 4 * sizes["n"] * (sizes["d"] + sizes["q"])


def bytes_per_ensemble_gradient(sizes, x_bytes=4):
    """x and z read once (N*(d + q) elements) plus y (4 B a row) and the int32
    group ids (4 B a row).  Parameters, the groups' effects and the outputs
    are a megabyte and left out."""
    return sizes["n"] * (x_bytes * (sizes["d"] + sizes["q"]) + 4 + 4)


def least_seconds(sizes, chains, peak):
    """The least time one ensemble gradient can take on a chip with these
    peaks, and which bound sets it."""
    t_flops = flops_per_chain_gradient(sizes) * chains / peak["flops_bf16"]
    t_bytes = bytes_per_ensemble_gradient(sizes) / peak["hbm_bytes_per_s"]
    return (t_flops, "flops") if t_flops > t_bytes else (t_bytes, "bytes")
