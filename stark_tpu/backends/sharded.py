"""ShardedBackend: chains x data-shards over a 2-D device mesh via shard_map.

The target execution stack from SURVEY.md §4: every device holds one shard of
the dataset (resident in HBM) and a slice of the chains; inside the compiled
step the per-shard log-likelihood partial sums are combined with
``lax.psum(_, "data")`` over ICI.  Chain state/computation is replicated
across the data axis (all data-devices of a chain group advance the same
chains deterministically), which is what removes the reference's
driver-mediated reduce from the per-leapfrog-step path (BASELINE.json:5).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..model import Model, flatten_model, prepare_model_data
from ..parallel.mesh import (
    make_mesh,
    process_local_shard,
    row_partition_specs,
    shard_data,
)
from ..parallel.primitives import broadcast, map_shards, shard_put
from ..sampler import (
    Posterior,
    SamplerConfig,
    _constrain_draws,
    block_program,
    drive_segmented_sampling,
    drive_segmented_warmup,
    make_block_runner,
    make_chain_runner,
    make_map_init,
    make_warmup_parts,
)
from .base import annotate_dispatch


class ShardedBackend:
    """Run chains over a Mesh(("data", "chains")).

    mesh: a 2-axis mesh; default: all devices on "data".
    Chains must divide the "chains" axis size; data rows must divide the
    "data" axis size.
    """

    def __init__(self, mesh: Optional[Mesh] = None,
                 dispatch_steps: Optional[int] = None):
        self.mesh = mesh if mesh is not None else make_mesh()
        if "data" not in self.mesh.axis_names or "chains" not in self.mesh.axis_names:
            raise ValueError("mesh must have axes ('data', 'chains')")
        # bounded device programs (served for chees AND the per-chain
        # kernels via the segmented drivers); unset = one monolithic
        # program
        self.dispatch_steps = dispatch_steps
        self._cache: Dict[Tuple[int, SamplerConfig, Any], Any] = {}

    def _get_runner(self, model: Model, fm, cfg: SamplerConfig, data, row_axes):
        treedef = None if data is None else jax.tree.structure(data)
        # model OBJECT in the key (not id(): freed ids get reused after GC)
        key = (model, cfg, treedef)
        if key not in self._cache:
            runner = make_chain_runner(fm, cfg)
            vrunner = jax.vmap(runner, in_axes=(0, 0, None))
            if data is None:
                self._cache[key] = map_shards(
                    lambda keys, z0s: vrunner(keys, z0s, None),
                    mesh=self.mesh,
                    in_specs=(P("chains"), P("chains")),
                    out_specs=P("chains"),
                )
            else:
                data_specs = row_partition_specs(data, "data", row_axes)
                self._cache[key] = map_shards(
                    vrunner,
                    mesh=self.mesh,
                    in_specs=(P("chains"), P("chains"), data_specs),
                    out_specs=P("chains"),
                )
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
        n_chain_devs = self.mesh.shape["chains"]
        if chains % n_chain_devs:
            raise ValueError(
                f"chains={chains} must divide mesh 'chains' axis ({n_chain_devs})"
            )
        fm = flatten_model(model, axis_name="data" if data is not None else None)
        multiproc = jax.process_count() > 1

        row_axes = None
        if data is not None:
            data = prepare_model_data(model, data)
            row_axes = model.data_shard_row_axes(data)
            if multiproc:
                # sequence-parallel models must verify the cross-process
                # global order BEFORE the blocks are glued (per-host
                # prepare_data only sorts locally — a violation would
                # silently corrupt the stitched likelihood)
                validate = getattr(model, "validate_process_blocks", None)
                if validate is not None:
                    validate(data)
                # each process passed only ITS rows (distributed.local_row_range);
                # glue them into one global row-sharded array over ICI/DCN
                data = process_local_shard(data, self.mesh, "data", row_axes=row_axes)
            else:
                data = shard_data(data, self.mesh, "data", row_axes=row_axes)

        dispatch_steps = self.dispatch_steps
        if cfg.kernel == "chees":
            post = self._run_chees(
                model, fm, cfg, data, row_axes,
                chains=chains, seed=seed, init_params=init_params,
                multiproc=multiproc, dispatch_steps=dispatch_steps,
            )
            annotate_dispatch(post.sample_stats, dispatch_steps)
            return post

        key = jax.random.PRNGKey(seed)
        key_init, key_run = jax.random.split(key)
        if init_params is not None:
            z0 = jnp.broadcast_to(fm.unconstrain(init_params), (chains, fm.ndim))
        else:
            z0 = jax.vmap(fm.init_flat)(jax.random.split(key_init, chains))
        chain_keys = jax.random.split(key_run, chains)

        put_chains = self._chain_placer()
        z0 = put_chains(z0)
        chain_keys = put_chains(chain_keys)

        if dispatch_steps:
            # bounded device programs for the per-chain kernels too.
            # Works on multi-process meshes as well: the segmented
            # drivers keep chains-sharded keys/state on device and
            # collect via the draw allgather.
            seg_warmup, get_block, _ = self._segmented_parts(
                model, fm, cfg, data, row_axes
            )
            from ..distributed import gather_draws

            post = drive_segmented_sampling(
                fm, cfg, seg_warmup, get_block, chain_keys, z0, data,
                int(dispatch_steps), collect=gather_draws,
            )
            annotate_dispatch(post.sample_stats, dispatch_steps)
            return post

        run = self._get_runner(model, fm, cfg, data, row_axes)
        if data is None:
            res = jax.block_until_ready(run(chain_keys, z0))
        else:
            res = jax.block_until_ready(run(chain_keys, z0, data))

        if multiproc:
            # multi-host draw collection: allgather the chain-sharded results
            # so every host returns the same full Posterior (no driver funnel)
            from ..distributed import gather_draws

            res = gather_draws(res)

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
        annotate_dispatch(stats, 0)
        return Posterior(draws, stats, flat_model=fm, draws_flat=np.asarray(res.draws))

    def _chain_placer(self):
        """Place a host-computed (chains, ...) array over the "chains"
        axis via `primitives.shard_put(from_host_replica=True)` — on a
        multi-process mesh every process computed the full (identical,
        same-seed) array and contributes just its addressable shards;
        single-process is a plain device_put (the primitive branches)."""
        return lambda x: shard_put(
            x, self.mesh, P("chains"), from_host_replica=True
        )

    def _smap(self, fn, in_specs, out_specs, data, data_specs, donate=(),
              name=None):
        """`primitives.map_shards` over the backend mesh; a ``None``
        dataset is bound here so every compiled segment shares the
        (*args, *extra) calling convention with the single-device
        backend.  ``donate`` forwards to the outer jit's
        ``donate_argnums`` (buffer donation of carried state, e.g. the
        streaming-diagnostics accumulators), ``name`` to the program's
        fixed name."""
        if data is None:
            return map_shards(
                lambda *a: fn(*a, None), mesh=self.mesh, in_specs=in_specs,
                out_specs=out_specs, donate=donate, name=name,
            )
        return map_shards(
            fn, mesh=self.mesh, in_specs=in_specs + (data_specs,),
            out_specs=out_specs, donate=donate, name=name,
        )

    def _data_specs(self, data, row_axes):
        return (
            row_partition_specs(data, "data", row_axes)
            if data is not None
            else None
        )

    def _chees_smapped(self, model, fm, cfg, data, row_axes):
        """(parts, init_j, warm_j, samp_j, samp_diag): the chees segment
        callables shard_mapped over the mesh, cached per (model, cfg, data
        layout).  ``samp_diag(donate=False)`` is the streaming-diagnostics
        variant — the per-chain StreamDiagState batch is chain-sharded
        like the ensemble state (every accumulator leaf carries a leading
        chains axis), so no cross-device reduction runs per transition;
        ``collect`` (an allgather on pods) materializes the O(chains*d*L)
        summary on the hosts once per block."""
        from ..adaptation import DualAveragingState, WelfordState
        from ..chees import (
            CHEES_PROGRAMS,
            AdamState,
            CheesRunCarry,
            CheesWarmCarry,
            make_chees_parts,
        )
        from ..kernels.base import HMCState

        parts = make_chees_parts(fm, cfg, chains_axis="chains")

        S, R = P("chains"), P()
        state_spec = HMCState(z=S, potential_energy=S, grad=S)
        # the carries' centre, where the flat model has one (`chees.py`):
        # a row a chain goes with the chains
        center_spec = None
        if fm.centering is not None and data is not None:
            center_spec = S if fm.centering.per_chain else R
        warm_spec = CheesWarmCarry(
            states=state_spec,
            da=DualAveragingState(R, R, R, R, R),
            adam=AdamState(R, R, R),
            log_T=R,
            wf=WelfordState(R, R, R),
            inv_mass=R,
            pe_center=center_spec,
        )
        run_spec = CheesRunCarry(
            states=state_spec, log_eps=R, log_T=R, inv_mass=R,
            pe_center=center_spec,
        )
        out_spec = (P(None, "chains"), P(None, "chains"), P(None, "chains"), R)
        data_specs = self._data_specs(data, row_axes)

        cache_key = (
            model, cfg, "chees",
            None if data is None else jax.tree.structure(data),
        )
        if cache_key not in self._cache:

            def samp_diag(donate=False):
                # every StreamDiagState leaf is chain-sharded, so the one
                # prefix spec S covers the whole diag pytree; donation is
                # an outer-jit property, keyed separately
                dkey = cache_key + ("samp_diag", donate)
                if dkey not in self._cache:
                    self._cache[dkey] = self._smap(
                        parts.sample_segment_diag, (run_spec, S, R, R),
                        (run_spec, S, out_spec), data, data_specs,
                        donate=(1,) if donate else (),
                        name=CHEES_PROGRAMS["samp_diag"],
                    )
                return self._cache[dkey]

            self._cache[cache_key] = (
                self._smap(
                    parts.init_carry, (R, S), warm_spec, data, data_specs,
                    name=CHEES_PROGRAMS["init"],
                ),
                self._smap(
                    parts.warm_segment, (warm_spec, R, R, R, R, R),
                    (warm_spec, (R, R)), data, data_specs,
                    name=CHEES_PROGRAMS["warm"],
                ),
                self._smap(
                    parts.sample_segment, (run_spec, R, R),
                    (run_spec, out_spec), data, data_specs,
                    name=CHEES_PROGRAMS["samp"],
                ),
                samp_diag,
            )
        return (parts,) + self._cache[cache_key]

    def _segmented_parts(self, model, fm, cfg, data, row_axes):
        """(seg_warmup, get_block, map_init) for the per-chain kernels,
        shard_mapped:
        chains-sharded state/keys, data-sharded likelihood, driven by the
        same host drivers as the single-device backend."""
        S, R = P("chains"), P()
        data_specs = self._data_specs(data, row_axes)
        cache_key = (
            model, cfg, "segmented",
            None if data is None else jax.tree.structure(data),
        )
        if cache_key not in self._cache:

            def smap_seg(fn, in_specs, out_specs, donate=(), name=None):
                # the segmented drivers pass data as a trailing arg even
                # when it is None (the single-device vmapped parts need
                # it); tolerate-and-drop it in the dataless mesh case
                inner = self._smap(fn, in_specs, out_specs, data, data_specs,
                                   donate=donate, name=name)
                if data is None:
                    return lambda *a: inner(*a[:-1])
                return inner

            init_carry, segment, finalize = make_warmup_parts(fm, cfg)
            v_init = smap_seg(
                jax.vmap(init_carry, in_axes=(0, 0, None)), (S, S), S
            )
            v_seg = smap_seg(
                jax.vmap(segment, in_axes=(1, None, None, 0, 0, 0, 0, None)),
                (P(None, "chains"), R, R, S, S, S, S), S,
            )

            def seg_warmup(warm_keys, z0, data_arg, seg):
                return drive_segmented_warmup(
                    cfg, v_init, v_seg, finalize, warm_keys, z0, data_arg, seg
                )

            blocks: Dict[Any, Any] = {}

            def get_block(length, diag_lags=None, donate_diag=False):
                key = (length, diag_lags, donate_diag)
                if key not in blocks:
                    if diag_lags is None:
                        blocks[key] = smap_seg(
                            jax.vmap(
                                make_block_runner(fm, cfg, length),
                                in_axes=(0, 0, 0, 0, None),
                            ),
                            (S, S, S, S), S, name=block_program(cfg),
                        )
                    else:
                        # the chains-batched StreamDiagState rides the
                        # chains axis like the HMC state; one prefix spec
                        # covers every accumulator leaf
                        blocks[key] = smap_seg(
                            jax.vmap(
                                make_block_runner(
                                    fm, cfg, length, diag_lags=diag_lags
                                ),
                                in_axes=(0, 0, 0, 0, 0, None),
                            ),
                            (S, S, S, S, S), S,
                            donate=(2,) if donate_diag else (),
                            name=block_program(cfg),
                        )
                return blocks[key]

            map_init = make_map_init(fm, cfg)
            if map_init is not None:  # the chains' starts, where they lie
                map_init = smap_seg(map_init, (S,), S,
                                    name=f"stark_{cfg.kernel}_map")
            self._cache[cache_key] = (seg_warmup, get_block, map_init)
        return self._cache[cache_key]

    def adaptive_parts(self, model, cfg: SamplerConfig, data):
        """Mesh flavor of `backends.base.AdaptiveParts`: the adaptive
        runner's blocks/checkpoint/supervision protocol drives shard_mapped
        segments; chain state lives sharded over "chains", data over
        "data", adaptation state replicated.  Checkpoint arrays round-trip
        through host numpy, so resume re-places them via put_chains/put_rep.

        Multi-process meshes are first-class (VERDICT r4 missing #3): the
        runner collects chain-sharded state through ``gather_draws`` (an
        allgather, so every host checkpoints identical full state to its
        own ``rank_path`` file) and re-places resumed host arrays with the
        same make_array_from_callback placement ``run`` uses — each
        process contributes exactly its addressable shards.
        """
        from .base import AdaptiveParts
        from ..distributed import gather_draws

        multiproc = jax.process_count() > 1
        # one flat model a (model, data-ness): the cached programs below
        # closed over it, and its `comm` is what their traces wrote
        fm_key = (model, "fm", data is None)
        if fm_key not in self._cache:
            self._cache[fm_key] = flatten_model(
                model, axis_name="data" if data is not None else None
            )
        fm = self._cache[fm_key]
        row_axes = None
        if data is not None:
            data = prepare_model_data(model, data)
            row_axes = model.data_shard_row_axes(data)
            if multiproc:
                # same cross-process order check as `run` (sequence-
                # parallel models), then the same gluing contract
                validate = getattr(model, "validate_process_blocks", None)
                if validate is not None:
                    validate(data)
                data = process_local_shard(
                    data, self.mesh, "data", row_axes=row_axes
                )
            else:
                data = shard_data(data, self.mesh, "data", row_axes=row_axes)
        def put_rep(x):
            # replicated placement across processes: every process holds
            # the identical host value and contributes its local replicas
            # (`primitives.broadcast`)
            return broadcast(x, self.mesh)

        bundle = AdaptiveParts(
            fm=fm,
            data=data,
            extra=() if data is None else (data,),
            put_chains=self._chain_placer(),
            put_rep=put_rep,
            collect=gather_draws,
        )
        if cfg.kernel == "chees":
            parts, init_j, warm_j, samp_j, samp_diag = self._chees_smapped(
                model, fm, cfg, data, row_axes
            )
            return bundle._replace(
                chees=parts, init_j=init_j, warm_j=warm_j, samp_j=samp_j,
                samp_diag=samp_diag,
            )
        seg_warmup, get_block, map_init = self._segmented_parts(
            model, fm, cfg, data, row_axes
        )
        return bundle._replace(
            seg_warmup=seg_warmup, get_block=get_block, map_init=map_init)

    def _run_chees(
        self, model, fm, cfg, data, row_axes, *, chains, seed, init_params,
        multiproc, dispatch_steps=None,
    ):
        """kernel="chees" over the mesh: the ensemble is sharded over
        "chains", the dataset over "data" (per-shard likelihood psum'd
        inside the potential — model.py's packed single-psum path), and the
        cross-chain adaptation statistics reduce with collectives
        (chains_axis in kernels/chees.py), so every device advances its
        chain slice in lockstep with identical eps / T / mass.
        """
        from ..chees import drive_chees_segments
        from ..distributed import gather_draws

        parts, init_j, warm_j, samp_j, _ = self._chees_smapped(
            model, fm, cfg, data, row_axes
        )

        # shared schedule driver (chees.drive_chees_segments): only
        # placement (chains-sharded z0), the shard_mapped segments, and
        # draw collection (allgather on pods — the Posterior's replicated
        # carry leaves materialize on every host without one) differ from
        # the single-device path
        return drive_chees_segments(
            parts,
            fm,
            cfg,
            chains=chains,
            seed=seed,
            init_params=init_params,
            dispatch_steps=dispatch_steps,
            init_j=init_j,
            warm_j=warm_j,
            samp_j=samp_j,
            extra=() if data is None else (data,),
            put_z0=self._chain_placer(),
            collect=gather_draws,
        )
