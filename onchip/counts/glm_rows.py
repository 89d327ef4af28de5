"""Counts `glm_rows`: what the log-likelihood gradient of a generalised linear
model over N rows of d features has to do, from its shapes alone (`sizes`:
n, d, groups): the same numbers whatever implements the kernel.  A
configuration names its counts (`"counts": ...`); a model whose gradient costs
otherwise brings a file of its own beside this one, with
`flops_per_chain_gradient(sizes)` and `least_seconds(sizes, chains, peak)`.

One *ensemble gradient* evaluates value and gradient for all `chains` at once
and streams the rows once; one *chain-gradient* is one chain's share of it.
"""


def flops_per_chain_gradient(sizes):
    """Forward matvec x.beta (2*N*d) and the gradient's x^T r (2*N*d); the
    per-row link costs O(N) and is left out."""
    return 4 * sizes["n"] * sizes["d"]


def bytes_per_ensemble_gradient(sizes, x_bytes=4):
    """x read once (N*d elements) plus y (4 B a row), plus int32 group ids for
    a grouped model.  Parameters and outputs are kilobytes and left out."""
    n = sizes["n"]
    return n * (x_bytes * sizes["d"] + 4) + (4 * n if sizes.get("groups") else 0)


def least_seconds(sizes, chains, peak):
    """The least time one ensemble gradient can take on a chip with these
    peaks, and which bound sets it."""
    t_flops = flops_per_chain_gradient(sizes) * chains / peak["flops_bf16"]
    t_bytes = bytes_per_ensemble_gradient(sizes) / peak["hbm_bytes_per_s"]
    return (t_flops, "flops") if t_flops > t_bytes else (t_bytes, "bytes")
