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

import dataclasses
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
      map_init     map_init(z0, data) -> z0 for per-chain kernels: the
                   configured MAP descent (``map_init_steps``) of every
                   chain's start, compiled; None where none is configured
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
    map_init: Any = None
    get_block: Any = None


class KernelEnv(NamedTuple):
    """What a `BlockKernel` takes from the call it serves, beside the
    backend's parts, the sampler's configuration and the chain count."""

    block_size: int  # warm-up runs in segments of this length too
    stream_diag: bool  # asked for; the kernel's own attribute is what it can
    sync_blocks: bool  # serial loop: the diagnostics carry may be donated
    diag_lags: int
    seed: int
    init_params: Any
    trace: Any
    emit: Any  # the run's record sink (``adapt_*`` decisions)
    model_name: str
    checkpoint_path: Optional[str] = None  # mid-warm-up checkpoints
    health_check: bool = False
    adapt_path: Optional[str] = None
    adapt_export_path: Optional[str] = None
    adapt_touchup_frac: float = 0.2
    adapt_fp: Optional[str] = None


@dataclasses.dataclass
class PendingBlock:
    """One dispatched draw block until the host has processed it: every
    device reference of the block lives here, and is freed with it."""

    length: int
    outs: Any  # the block's device outputs, in the kernel's own layout
    diag: Any  # the streaming-diagnostics carry after the block, or None
    # the state carried out of the block under the checkpoint's names (z,
    # pe, grad, step_size, inv_mass): what the health check gates
    carried: Dict[str, Any]
    extras: Any = None  # what else the kernel's checkpoint needs
    key: Any = None  # the host key as of this block's split (the loop's)
    # the streaming ESS row and draw counts, reduced on the device behind
    # the block (the loop's: ``stark_stream_ess``), or None
    ess: Any = None
    t_enq: float = 0.0  # seconds the dispatch took (the loop's)
    # the device timeline (the loop's, ``time.perf_counter_ns``): when the
    # dispatch had enqueued everything, and when the device had finished
    # the block and its ESS row (the loop's waiter thread stamps it and
    # sets ``done``; None where the wait raised)
    dispatched_ns: int = 0
    t_done_ns: Optional[int] = None
    done: Any = None  # `threading.Event`


def carried_state(state, step_size, inv_mass) -> Dict[str, Any]:
    """`PendingBlock.carried` of an `HMCState` and its step and mass."""
    return {"z": state.z, "pe": state.potential_energy, "grad": state.grad,
            "step_size": step_size, "inv_mass": inv_mass}


def checkpoint_potential(arrays: Dict[str, Any], centering) -> Dict[str, Any]:
    """Checkpoint arrays whose ``pe`` is the potential itself.  A sampler
    that carries its energies relative to a centre (``pe_center``,
    collected beside them; ``centering``: its `model.Centering`) has the
    centre's constant added in float64, which holds both to the last bit of
    the float32 that was carried; ``pe_center`` stays in the file for the
    resume."""
    import numpy as np

    center = arrays.pop("pe_center", None)
    if center is not None:
        arrays["pe"] = (np.asarray(arrays["pe"], np.float64)
                        + np.float64(centering.constant(center)))
        arrays["pe_center"] = center
    return arrays


def carried_potential(arrays, centering):
    """-> (pe, pe_center) as a sampler's carry holds them, from a
    checkpoint's arrays: `checkpoint_potential` undone.  ``centering``: the
    `model.Centering` of the programs that resume, None where they carry no
    centre; a file without one (written by programs that summed the plain
    potential) then resumes relative to 0, which a centre that holds more
    than its constant cannot."""
    import numpy as np

    if centering is None:
        return arrays["pe"], None
    if "pe_center" in arrays:
        center = np.asarray(arrays["pe_center"], np.float32)
    elif centering.width > 1:
        raise ValueError(
            "this checkpoint holds no pe_center, and the model's centre "
            "keeps more than a constant: it cannot be made up on resume")
    else:
        center = np.asarray(
            centering.zero(np.asarray(arrays["z"]).shape[0]), np.float32)
    pe = (np.asarray(arrays["pe"], np.float64)
          - np.float64(centering.constant(center)))
    return pe.astype(np.float32), center


def restored_key(arrays, name, reseed):
    """A checkpoint's PRNG key.  A deterministic numerical failure would
    replay identically from it on every retry, so the supervisor passes
    the attempt number (``reseed``) to branch the stream."""
    import jax

    key = jax.numpy.asarray(arrays[name])
    return key if reseed is None else jax.random.fold_in(key, reseed)


class HostBlock(NamedTuple):
    """A finished block on the host, as the block loop reads it."""

    zs: Any  # (chains, block, d); may be a view of ``zs_dm``
    zs_dm: Any  # the block draw-major (block, chains, d), or None
    accept: Any  # (chains, block)
    divergent: Any  # (chains, block)
    mean_accept: float
    grad_evals: int  # gradient evaluations of the block, all chains
    energy: Any = None  # (chains, block), per-chain kernels, when asked for
    ngrad: Any = None  # (chains, block), per-chain kernels
    sched_fields: Any = {}  # lane occupancy of a ragged-NUTS block


class BlockKernel(Protocol):
    """The seam between the runner's block loop and its sampler.  The loop
    owns blocks, diagnostics, records and when a checkpoint is written; the
    kernel owns the sampler's carried state, its warm-up and what a
    checkpoint of it holds: `chees.CheesBlockKernel` (the ensemble
    sampler) and `sampler.ChainBlockKernel` (NUTS / HMC), each beside the
    state it hides.  ``start`` and ``restore`` return the run's key, the
    warm-up's divergences and the other fields of a ``warmup_done`` record
    (None from a sampling-phase checkpoint: no warm-up ran)."""

    chains: int  # the checkpoint's on a resume
    stream_diag: bool  # whether its blocks carry the diagnostics accumulators
    step_size: Any  # device value(s), for the ``warmup_done`` record
    dtype: Any  # of the positions

    def start(self): ...  # keys from the seed, positions, (MAP,) warm-up

    def restore(self, arrays, meta, reseed): ...  # + the rest of a warm-up

    def dispatch(self, key_block, length, diag, first_draw) -> PendingBlock:
        """Enqueue a block without waiting; ``first_draw`` counts the
        draws dispatched before it."""

    def host_block(self, pending, energy=False) -> HostBlock: ...

    def checkpoint_arrays(self, pending) -> Dict[str, Any]:
        """The kernel's part of a sampling-phase checkpoint: host arrays
        under the file's names, ``key`` among them."""


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
