"""`SamplerBackend` — the pluggable execution-backend boundary.

Mirrors the reference's `StarkModel` / `SamplerBackend` plugin split
(BASELINE.json:5, SURVEY.md §2 layer D): models and sampler algorithms are
defined once; *where and how* the logp/grad + kernel loop executes is a
backend decision.  Provided backends:

* ``JaxBackend``      — jit + vmap chains on one device (TPU or CPU).
* ``ShardedBackend``  — shard_map over a ``jax.sharding.Mesh``; data sharded
                        over a "data" axis with psum'd likelihoods, chains
                        over a "chains" axis (SURVEY.md §4 target stack).
* ``CpuBackend``      — pure NumPy reference implementation; the measured
                        baseline denominator (SURVEY.md §8 step 5).
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Protocol, runtime_checkable


class AdaptiveParts(NamedTuple):
    """What a backend hands the adaptive runner (`sample_until_converged`)
    so convergence-driven blocks, checkpointing, and supervision compose
    with ANY execution layout (single device or sharded mesh).

    The runner owns the schedule/blocks/diagnostics/checkpoint protocol;
    the backend owns compilation and placement:

      fm / data    flat model + placed (possibly mesh-sharded) data pytree
      extra        () or (data,) — trailing args for every segment call
      chees        CheesParts (schedule/finalize) when kernel == "chees"
      init_j/warm_j/samp_j   compiled chees segment callables
      samp_diag    samp_diag(donate=False) -> compiled chees segment with
                   the streaming-diagnostics carry (carry, diag, keys, us,
                   data) -> (carry, diag, outs); ``donate=True`` donates
                   the diag buffers (safe only when the caller never reads
                   a block's diag after dispatching the next one — the
                   runner's serial mode)
      seg_warmup   run(warm_keys, z0, data, seg) for per-chain kernels
      get_block    get_block(block_size, diag_lags=None, donate_diag=False)
                   -> compiled v_block(keys, state, step_size, inv_mass,
                   data); with ``diag_lags`` the block threads a per-chain
                   StreamDiagState batch: v_block(keys, state, diag,
                   step_size, inv_mass, data)
      put_chains   place a host (chains, ...) array on the chains layout
      put_rep      place a host replicated array (adaptation state)
      collect      device pytree -> host numpy (allgather on pods)
    """

    fm: Any
    data: Any
    extra: tuple
    put_chains: Any
    put_rep: Any
    collect: Any
    chees: Any = None
    init_j: Any = None
    warm_j: Any = None
    samp_j: Any = None
    samp_diag: Any = None
    seg_warmup: Any = None
    get_block: Any = None


def annotate_dispatch(sample_stats: Dict[str, Any], dispatch_steps) -> None:
    """Record the dispatch bound a run executed under in its sample stats
    (0 = one monolithic device program).  Bounded and monolithic runs
    draw different RNG streams from the same seed, so the bound must be
    readable in the results themselves."""
    sample_stats["dispatch_steps"] = int(dispatch_steps or 0)


@runtime_checkable
class SamplerBackend(Protocol):
    def run(
        self,
        model,
        data,
        cfg,
        *,
        chains: int,
        seed: int,
        init_params: Optional[Dict[str, Any]] = None,
    ):
        """Run ``chains`` MCMC chains of ``model`` on ``data``; return Posterior."""
        ...
