"""Counts `glm_rows_sharded`: the log-likelihood gradient of a generalised
linear model whose N rows lie in `sizes["data_shards"]` shards, one a chip,
from its shapes alone.  The formulas are `counts/glm_rows.py`'s, written out
again so that this file stands alone (a configuration names one counts file).

Two scopes, because two readers divide differently.  `step_mfu` takes the
FLOPs of the whole job's chain-gradient and divides by the chips itself, so
`flops_per_chain_gradient` counts all N rows.  `fused_ll_roofline` multiplies
`least_seconds` by the kernel's calls summed over every device's plane of the
trace, so `least_seconds` is what ONE chip's kernel call has to stream: N /
`data_shards` rows.  (With the whole job's bytes there the share would read
`data_shards` times too high.)
"""


def flops_per_chain_gradient(sizes):
    """Forward matvec x.beta (2*N*d) and the gradient's x^T r (2*N*d) over
    all N rows of the job; the per-row link costs O(N) and is left out."""
    return 4 * sizes["n"] * sizes["d"]


def shard_rows(sizes):
    """Rows one chip holds."""
    return sizes["n"] // sizes["data_shards"]


def bytes_per_kernel_call(sizes, x_bytes=4):
    """What one chip's kernel call reads: its shard's x once (rows*d
    elements) plus y (4 B a row).  Parameters, outputs and the all-reduce's
    operand are kilobytes and left out."""
    return shard_rows(sizes) * (x_bytes * sizes["d"] + 4)


def least_seconds(sizes, chains, peak):
    """The least time one chip's kernel call of one ensemble gradient can
    take on a chip with these peaks, and which bound sets it."""
    t_flops = 4 * shard_rows(sizes) * sizes["d"] * chains / peak["flops_bf16"]
    t_bytes = bytes_per_kernel_call(sizes) / peak["hbm_bytes_per_s"]
    return (t_flops, "flops") if t_flops > t_bytes else (t_bytes, "bytes")
