"""Single-process JAX backend: jit + vmap over chains on one device.

Chain state stays resident in device memory (HBM on TPU) for the entire
warmup+sample loop; the host sees only the finished draw block — the
TPU-native replacement for the reference's per-step driver round-trip
(BASELINE.json:5).

The jitted runner is cached per (model, config) on the backend instance, and
takes the data pytree as a runtime argument, so repeated ``sample()`` calls
(multi-seed replications, benchmark sweeps) hit the XLA trace cache instead
of recompiling.
"""

from __future__ import annotations

import os
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..model import Model, flatten_model, prepare_model_data
from ..platform import named_jit
from ..telemetry import get_trace
from .base import annotate_dispatch
from ..sampler import (
    Posterior,
    SamplerConfig,
    _constrain_draws,
    block_program,
    drive_segmented_sampling,
    make_block_runner,
    make_chain_runner,
    make_map_init,
    make_segmented_warmup,
)


def _emit_chain_health(trace, stats: Dict[str, Any]) -> None:
    """One end-of-run chain_health event from a Posterior stats dict —
    the monolithic paths' health record (the block-bounded drivers emit
    per-block health instead).  Tolerant of missing keys: kernels differ
    in what they surface."""
    fields: Dict[str, Any] = {}
    acc = stats.get("accept_prob")
    if acc is not None and np.asarray(acc).size:
        fields["mean_accept"] = round(float(np.mean(np.asarray(acc))), 4)
    for key, out in (("num_divergent", "num_divergent"),
                     ("num_warmup_divergent", "num_warmup_divergent")):
        v = stats.get(key)
        if v is not None:
            fields[out] = int(np.sum(np.asarray(v)))
    ss = stats.get("step_size")
    if ss is not None and np.asarray(ss).size:
        fields["step_size"] = round(float(np.mean(np.asarray(ss))), 6)
    trace.emit("chain_health", **fields)


class JaxBackend:
    """Single-process backend.

    dispatch_steps: when set (or via the STARK_DISPATCH_STEPS env var), the
    run executes as a sequence of device programs of at most that many
    transitions each instead of one monolithic dispatch — the progress
    and checkpoint granularity, and what keeps any single fault
    re-startable.  Unset (or 0) means one monolithic program on every
    platform.  Results are statistically equivalent; the RNG stream
    differs from the monolithic path.
    """

    def __init__(self, device: Optional[Any] = None,
                 dispatch_steps: Optional[int] = None):
        self.device = device
        if dispatch_steps is None:
            env = os.environ.get("STARK_DISPATCH_STEPS")
            dispatch_steps = int(env) if env else None
        if dispatch_steps is not None and dispatch_steps < 0:
            raise ValueError(f"dispatch_steps must be >= 0, got {dispatch_steps}")
        self.dispatch_steps = dispatch_steps
        # keyed on the model OBJECT (kept alive by the key): an id() key can
        # be silently reused for a different model after garbage collection
        self._cache: Dict[Tuple[Any, ...], Any] = {}

    def _get_runner(self, model: Model, fm, cfg: SamplerConfig):
        key = (model, cfg)
        if key not in self._cache:
            runner = make_chain_runner(fm, cfg)
            self._cache[key] = jax.jit(jax.vmap(runner, in_axes=(0, 0, None)))
        return self._cache[key]

    def run(
        self,
        model: Model,
        data,
        cfg: SamplerConfig,
        *,
        chains: int,
        seed: int,
        init_params: Optional[Dict[str, Any]] = None,
    ) -> Posterior:
        trace = get_trace()
        # model flattening + data prep are the run's setup cost: traced as
        # a compile-stage phase so the per-run phase durations tile the
        # wall (run_start -> run_end); a setup fault records its error
        # class in the phase event like every other phase
        with trace.phase("compile", stage="setup"):
            fm = flatten_model(model)
            data = prepare_model_data(model, data)
        dispatch_steps = self.dispatch_steps
        if cfg.kernel == "chees":
            # ensemble kernel: served through the same backend boundary but
            # driven by the chees parts (its warmup adapts cross-chain, so
            # the per-chain vmapped runner does not apply)
            from ..chees import run_chees

            # one phase for the whole ensemble drive: the chees host loop
            # has its own internal segmentation, but its warmup/sample
            # split is not surfaced here — the adaptive runner
            # (sample_until_converged) is the finely-traced chees path
            with trace.phase("sample_block", kernel="chees",
                             includes_warmup=True, chains=chains):
                post = run_chees(
                    fm,
                    cfg,
                    data,
                    chains=chains,
                    seed=seed,
                    init_params=init_params,
                    dispatch_steps=dispatch_steps,
                    jit_cache=self._cache.setdefault(
                        (model, cfg, "chees"), {}
                    ),
                    device=self.device,
                )
            if trace.enabled:
                _emit_chain_health(trace, post.sample_stats)
            annotate_dispatch(post.sample_stats, dispatch_steps)
            return post

        # per-chain init keys/positions: first PRNG compiles of the run
        with trace.phase("compile", stage="chain_init"):
            key = jax.random.PRNGKey(seed)
            key_init, key_run = jax.random.split(key)
            if init_params is not None:
                z0 = jnp.broadcast_to(
                    fm.unconstrain(init_params), (chains, fm.ndim)
                )
            else:
                z0 = jax.vmap(fm.init_flat)(jax.random.split(key_init, chains))
            chain_keys = jax.random.split(key_run, chains)

            if self.device is not None:
                z0 = jax.device_put(z0, self.device)
                chain_keys = jax.device_put(chain_keys, self.device)

        if dispatch_steps:
            post = self._run_segmented(
                model, fm, cfg, data, chain_keys, z0, int(dispatch_steps)
            )
            annotate_dispatch(post.sample_stats, dispatch_steps)
            return post

        # monolithic dispatch: warmup+sampling fused in ONE device program,
        # so the trace gets a single sample_block covering it (the cache
        # miss flags where XLA compile time is hiding inside the phase)
        cache_hit = (model, cfg) in self._cache
        run = self._get_runner(model, fm, cfg)
        with trace.phase(
            "sample_block",
            includes_warmup=True,
            includes_compile=not cache_hit,
            transitions=cfg.num_warmup + cfg.num_samples * cfg.thin,
            chains=chains,
        ):
            res = run(chain_keys, z0, data)
            res = jax.block_until_ready(res)

        with trace.phase("collect"):
            draws = _constrain_draws(fm, res.draws)
            stats = {
                "accept_prob": np.asarray(res.accept_prob),
                "is_divergent": np.asarray(res.is_divergent),
                "energy": np.asarray(res.energy),
                "num_grad_evals": np.asarray(res.num_grad_evals),
                "step_size": np.asarray(res.step_size),
                "inv_mass_diag": np.asarray(res.inv_mass_diag),
                "num_warmup_divergent": np.asarray(res.num_warmup_divergent),
                "num_divergent": np.asarray(res.num_divergent),
            }
        if trace.enabled:
            _emit_chain_health(trace, stats)
        annotate_dispatch(stats, 0)
        return Posterior(
            draws, stats, flat_model=fm, draws_flat=np.asarray(res.draws)
        )

    def _cached(self, model, cfg, tag, builder):
        key = (model, cfg, tag)
        if key not in self._cache:
            self._cache[key] = builder()
        return self._cache[key]

    def _get_block(self, model, fm, cfg):
        """get_block(length, diag_lags=None, donate_diag=False,
        ragged=False) -> jitted vmapped block runner (cached).
        ``diag_lags`` threads the streaming-diagnostics carry (extra
        chains-batched StreamDiagState arg after ``state``);
        ``donate_diag`` donates those buffers so the serial loop updates
        the O(chains*d*L) accumulators in place.  ``ragged``
        (STARK_RAGGED_NUTS) selects the step-synchronized NUTS scheduler —
        same signatures plus one trailing per-chain lane-iteration output
        (drivers that request it unpack accordingly).  The program has
        one name a kernel, ``stark_nuts_block`` / ``stark_hmc_block``
        (`sampler.block_program`), whatever its length and carries."""
        name = block_program(cfg)

        def get(length, diag_lags=None, donate_diag=False, ragged=False):
            if diag_lags is None:
                return self._cached(
                    model, cfg, ("block", length, ragged),
                    lambda: named_jit(jax.vmap(
                        make_block_runner(fm, cfg, length, ragged=ragged),
                        in_axes=(0, 0, 0, 0, None),
                    ), name),
                )
            return self._cached(
                model, cfg, ("block", length, diag_lags, donate_diag,
                             ragged),
                lambda: named_jit(
                    jax.vmap(
                        make_block_runner(fm, cfg, length,
                                          diag_lags=diag_lags,
                                          ragged=ragged),
                        in_axes=(0, 0, 0, 0, 0, None),
                    ),
                    name,
                    donate_argnums=(2,) if donate_diag else (),
                ),
            )

        return get

    def _run_segmented(self, model, fm, cfg, data, chain_keys, z0,
                       dispatch_steps):
        """Warmup + sampling as bounded-length dispatches (see class doc),
        via the shared `sampler.drive_segmented_sampling` host driver."""
        seg_warmup = self._cached(
            model, cfg, "seg_warmup", lambda: make_segmented_warmup(fm, cfg)
        )
        return drive_segmented_sampling(
            fm, cfg, seg_warmup, self._get_block(model, fm, cfg),
            chain_keys, z0, data, dispatch_steps,
        )

    def adaptive_parts(self, model, cfg: SamplerConfig, data):
        """Compiled segment callables + placement hooks for the adaptive
        block runner (`runner.sample_until_converged`) — see
        `backends.base.AdaptiveParts`.  Single-device flavor: plain
        jit(+vmap), identity/device_put placement, host np collection.
        """
        from .base import AdaptiveParts

        fm = flatten_model(model)
        data = prepare_model_data(model, data)
        extra = () if data is None else (data,)

        def put(x):
            return (
                jax.device_put(x, self.device)
                if self.device is not None
                else x
            )

        bundle = AdaptiveParts(
            fm=fm,
            data=data,
            extra=extra,
            put_chains=put,
            put_rep=put,
            collect=lambda t: jax.tree.map(np.asarray, t),
        )
        if cfg.kernel == "chees":
            from ..chees import CHEES_PROGRAMS, make_chees_parts

            parts = self._cached(
                model, cfg, "chees_parts", lambda: make_chees_parts(fm, cfg)
            )

            def jit_part(tag, fn, donate=()):
                # bind data=None explicitly when absent so every backend's
                # segment callables share the (*args, *extra) convention
                wrapped = fn if data is not None else (
                    lambda *a, _fn=fn: _fn(*a, None)
                )
                # data-ness is part of the key: the wrapper's arity differs
                return self._cached(
                    model, cfg, ("chees_j", tag, data is None, donate),
                    lambda: named_jit(
                        wrapped, CHEES_PROGRAMS[tag], donate_argnums=donate
                    ),
                )

            def samp_diag(donate=False):
                # streaming-diagnostics segment; donate=True donates the
                # diag carry (arg 1) — jit wrappers are lazy, so building
                # a variant costs nothing until it is dispatched
                return jit_part(
                    "samp_diag", parts.sample_segment_diag,
                    donate=(1,) if donate else (),
                )

            return bundle._replace(
                chees=parts,
                init_j=jit_part("init", parts.init_carry),
                warm_j=jit_part("warm", parts.warm_segment),
                samp_j=jit_part("samp", parts.sample_segment),
                samp_diag=samp_diag,
            )
        seg_warmup = self._cached(
            model, cfg, "seg_warmup", lambda: make_segmented_warmup(fm, cfg)
        )
        map_init = make_map_init(fm, cfg)
        return bundle._replace(
            seg_warmup=seg_warmup,
            map_init=map_init and self._cached(
                model, cfg, "map_init",
                lambda: named_jit(map_init, f"stark_{cfg.kernel}_map"),
            ),
            get_block=self._get_block(model, fm, cfg),
        )
