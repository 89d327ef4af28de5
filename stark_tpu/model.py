"""Model abstraction — the `StarkModel`-equivalent plugin boundary.

A model declares its parameters (shapes + constraining bijectors), a log-prior
over the constrained parameters, and a per-row log-likelihood summed over a
batch of rows.  The framework turns this into a potential-energy function over
a single flat unconstrained vector, optionally allreducing data-sharded
log-likelihood terms over a mesh axis (the TPU-native replacement for the
reference's `Sampler.mapPartitions` driver round-trip — BASELINE.json:5,
SURVEY.md §4).

The reference tree was absent at build time (SURVEY.md §0); the API here
covers the capability surface of `StarkModel` as documented in SURVEY.md §2/§3
(layer A: log-prior + per-row log-likelihood + parameter (un)constraining).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from . import telemetry
from .bijectors import Bijector, Identity
from .tree import make_unflatten

Array = jax.Array
PyTree = Any


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    """Declared shape (constrained space) + constraining bijector."""

    shape: Tuple[int, ...] = ()
    bijector: Bijector = dataclasses.field(default_factory=Identity)


class Model:
    """Subclass and implement param_spec / log_prior / log_lik.

    ``log_lik(params, data)`` must return the *sum* of per-row log-likelihood
    terms over whatever batch of rows it is handed; the framework decides
    which rows those are (full data, a device shard, or a minibatch).
    Models with no data term (pure-prior / data baked into the model) may
    leave log_lik unimplemented and return everything from log_prior.
    """

    def param_spec(self) -> Dict[str, ParamSpec]:
        raise NotImplementedError

    def log_prior(self, params: Dict[str, Array]) -> Array:
        raise NotImplementedError

    def log_lik(self, params: Dict[str, Array], data: PyTree) -> Array:
        raise NotImplementedError

    def log_lik_rows(self, params: Dict[str, Array], data: PyTree) -> Array:
        """Optional: the (N,) per-row log-likelihood terms whose sum is
        ``log_lik``.  Enables pointwise model comparison (WAIC/PSIS-LOO,
        ``stark_tpu.compare``); not used by the samplers."""
        raise NotImplementedError(
            f"{type(self).__name__} does not define per-row log-lik terms"
        )

    def init_params(self, key: Array) -> Optional[Dict[str, Array]]:
        """Optional: return constrained init values; None -> U(-2,2) in
        unconstrained space (Stan-style random init)."""
        return None

    def fused_tag(self) -> Optional[str]:
        """Optional: short name of the fused likelihood family this model
        routes through RIGHT NOW — knob state included, so a knob-gated
        ``Fused*`` variant returns None when its ``STARK_FUSED_*`` knob
        is off.  Telemetry stamps the value into ``run_start`` and the
        per-block grad-eval records (``fused=``), so a trace/ledger row
        says which execution path produced its numbers.  None (default)
        -> plain autodiff likelihood.
        """
        return None

    def prepare_data(self, data: PyTree) -> PyTree:
        """Optional one-time, host-side data transform applied by backends
        BEFORE the compiled sample loop closes over the data.

        Use for layout changes the hot path should not pay per evaluation —
        e.g. the fused logistic models store the row matrix transposed
        ((D, N), features on the TPU sublane axis, rows on the 128-wide
        lane axis) so the Pallas kernel streams full-width tiles.

        Every entry point must route data through ``prepare_model_data``
        (below) so this hook is applied exactly once; models that move the
        row axis off axis 0 must override ``data_row_axes`` to match.
        """
        return data

    def data_row_axes(self, data: PyTree) -> PyTree:
        """Which axis of each ``prepare_data``-output leaf indexes data rows.

        Default: axis 0 everywhere.  Entry points that shard or minibatch
        rows (mesh sharding, SG-HMC minibatches, consensus shards) consult
        this so layout-transformed leaves (e.g. a transposed ``xT`` with
        rows on axis 1) are split along the correct axis.
        """
        return jax.tree.map(lambda _: 0, data)

    def center_data(self, data: PyTree, center: Array) -> Optional[PyTree]:
        """Optional: ``data`` such that ``log_lik`` returns its value less
        the scalar ``center``, with the subtraction done INSIDE the sum
        over rows (partial sums less their share of it), so that the
        difference keeps float32's resolution.  None (default): the model
        has no such sum, and the potential stays as it is.  A model with
        ``center_per_chain`` gets one chain's centre: a vector, the scalar
        first and what `center_keep` returned behind it.

        Why: over tens of millions of rows the log-likelihood is a
        float32 near 1e7-1e8 whose last bit is 1 to 4 nats, and the
        accept step of every sampler lives on energy differences of a
        tenth of a nat.  The ChEES programs ask for the potential relative
        to its value where the chains are (`Centering`,
        `chees.make_chees_parts`); the per-chain kernels (NUTS, HMC) for
        each chain's relative to where that chain stands, wherever they
        run (`FlatModel.chain_centering`, `kernels.base.CentredState`)."""
        return None

    #: how the ensemble sampler centres a model with `center_data` (the
    #: per-chain kernels always centre chain by chain).  False: one constant
    #: for the ensemble (where the first chain stands), and only over a data
    #: mesh: `FusedLogistic`'s, whose one-chip programs stay the plain
    #: ones.  True: every chain relative to where it stands itself, on one
    #: chip as on a mesh: an accept step compares a chain with itself
    #: alone, so chains may stand any distance apart
    center_per_chain = False

    def center_keep(self, params: Dict[str, Array]) -> Array:
        """With ``center_per_chain``: what of the position a chain's centre
        is taken at (``params``, constrained) its centred likelihood is
        evaluated relative to, as a vector; carried behind the constant
        and handed to `center_data` with it.  For a likelihood whose large
        sums are multiplied by scalars of the position (a noise scale):
        taking those to a fixed reference makes their rounding the same at
        every position.  Default: nothing."""
        return jnp.zeros((0,))

    def data_shard_row_axes(self, data: PyTree) -> PyTree:
        """Row axes for CONTIGUOUS, ORDER-PRESERVING data-axis sharding
        (the mesh "data" axis).  Defaults to ``data_row_axes``.

        Sequential-likelihood models (CoxPH) override THIS — their
        cross-shard ``log_lik_sharded`` stitches prefix state over the
        axis, which is only valid when shards are contiguous row blocks
        in the prepared global order — while leaving ``data_row_axes``
        fail-fast, because minibatching and independent sub-posterior
        splits (SG-HMC, consensus) remain statistically invalid for them.
        """
        return self.data_row_axes(data)


def prepare_model_data(model: Model, data: PyTree) -> PyTree:
    """The single data choke point for every entry point: apply the model's
    one-time host-side layout hook, then move leaves to device arrays.

    Entry points must NOT call ``jax.tree.map(jnp.asarray, data)`` directly —
    that skips ``Model.prepare_data`` and breaks models with custom layouts
    (the fused Pallas models crash on a missing ``xT``).

    Rows that arrive as global device arrays already sharded over a mesh
    (a row stream too large for one device or a host round trip) are
    prepared where they lie: nothing here converts a leaf to a host array
    or puts it on one device, and a ``prepare_data`` written in
    ``jax.numpy`` (the fused models' transpose) runs shard by shard,
    computation following the data.  The caller owns the raw rows and
    frees them; until then they sit beside the prepared copy.

    The span ``prepare_data`` carries ``bytes_in``, ``bytes_out`` and,
    where the hook added leaves to a dict of rows, ``kernel_layout``: their
    names (``"xT,y_lanes"`` for `FusedLogistic`), which is how a run says
    that its kernels got their operands laid out here and not in the
    sampling loop.  Data that arrives prepared adds none."""
    if data is None:
        return None
    with telemetry.span("prepare_data", model=type(model).__name__) as sp:
        out = telemetry.wait(
            jax.tree.map(jnp.asarray, model.prepare_data(data)))
        sp.note(bytes_in=_tree_nbytes(data), bytes_out=_tree_nbytes(out))
        if isinstance(data, dict) and isinstance(out, dict):
            laid_out = [k for k in out if k not in data]
            if laid_out:
                sp.note(kernel_layout=",".join(laid_out))
    return out


def _tree_nbytes(tree: PyTree) -> int:
    return sum(int(getattr(x, "nbytes", 0)) for x in jax.tree.leaves(tree))


class Potential:
    """Potential-energy callable with a fused value-and-grad path.

    Kernels call ``.value_and_grad(z)`` instead of
    ``jax.value_and_grad(pot)(z)`` so that sharded models can combine the
    log-likelihood value and its gradient into ONE ``psum`` of a packed
    (1+d)-vector per evaluation — one ICI allreduce per leapfrog step
    instead of two (and a total order over collectives, which the XLA:CPU
    test runtime needs to not starve its rendezvous thread pool).
    """

    def __init__(self, value_fn, value_and_grad_fn=None):
        self._value = value_fn
        self._vag = value_and_grad_fn or jax.value_and_grad(value_fn)

    def __call__(self, z):
        return self._value(z)

    def value_and_grad(self, z):
        return self._vag(z)


@dataclasses.dataclass(frozen=True)
class FlatModel:
    """A model compiled down to flat-unconstrained-vector functions."""

    ndim: int
    # potential(theta_flat, data) -> scalar (data may be None)
    potential: Callable[..., Array]
    # potential_and_grad(theta_flat, data) -> (scalar, (d,) grad); sharded
    # models use a single fused psum for both
    potential_and_grad: Callable[..., Tuple[Array, Array]]
    # constrain(theta_flat) -> params dict (constrained, named)
    constrain: Callable[[Array], Dict[str, Array]]
    # unconstrain(params dict) -> theta_flat
    unconstrain: Callable[[Dict[str, Array]], Array]
    init_flat: Callable[[Array], Array]
    # optional: data -> Potential, replacing the default autodiff assembly
    # (used by fused Pallas paths, e.g. ops.logistic_fused)
    potential_factory: Optional[Callable[..., Potential]] = None
    # what one gradient of the data-sharded potential sends over the mesh
    # axis, written when ``potential_and_grad`` is traced (empty off the
    # mesh and before any trace): ``psums_per_gradient`` and
    # ``psum_bytes_per_chain`` (the packed operand of one chain)
    comm: Dict[str, int] = dataclasses.field(
        default_factory=dict, compare=False
    )
    # optional (models with `center_data`): the potential summed relative
    # to a constant, see `Centering`: the ensemble sampler's, and the
    # per-chain kernels' (always a row a chain: `sampler.ChainBlockKernel`)
    centering: Optional["Centering"] = None
    chain_centering: Optional["Centering"] = None

    def bind(self, data=None, pe_center=None) -> Potential:
        """Close over a dataset -> a Potential for the kernels.  With
        ``pe_center`` (`Centering`: the ensemble's constant, or ONE chain's
        centre) the potential comes back less it."""
        if pe_center is not None:
            data = self.centering.data(data, pe_center)
        return self._bound(data)

    def bind_chain(self, data, centre) -> Potential:
        """`bind` for a per-chain kernel: the potential of ONE chain less
        the constant of its row ``centre`` (`chain_centering`)."""
        return self._bound(self.chain_centering.data(data, centre))

    def _bound(self, data) -> Potential:
        if self.potential_factory is not None:
            return self.potential_factory(data)
        return Potential(
            lambda z: self.potential(z, data),
            lambda z: self.potential_and_grad(z, data),
        )


class Centering(NamedTuple):
    """A potential summed relative to a constant, so that near the positions
    the constant was taken at it is a small number whose differences keep
    float32's resolution at any number of rows (`Model.center_data`; held
    as the carries' ``pe_center`` and moved by `chees.make_chees_parts`).
    What is carried is one array, and only this class and the model know
    what is in it: a scalar for the ensemble (``width`` 0), or for a model
    with `Model.center_per_chain` a row a chain, (C, width): the chain's
    constant, then what the model keeps of the position it was taken at.

      at(z (C, d), pe (C,)) -> (C,)   the constant at each position ``z``
                                      with plain potential ``pe``: the
                                      likelihood's part of it
      at(z, pe, centre) -> centre'    a row a chain: ``centre`` moved to
                                      ``z``, where the potential relative
                                      to it is ``pe``
      data(data, centre) -> data'     bound over data' the potential is the
                                      plain one less the constant; ``centre``
                                      the scalar, or ONE chain's row
    """

    at: Callable[..., Array]
    data: Callable[[PyTree, Array], PyTree]
    width: int = 0

    @property
    def per_chain(self) -> bool:
        return self.width > 0

    def zero(self, chains: int) -> Array:
        """The centre of an ensemble whose energies are the plain ones."""
        return jnp.zeros((chains, self.width) if self.per_chain else ())

    def constant(self, centre: Array) -> Array:
        """What the potential is the carried energy plus: a scalar, or a
        number a chain."""
        return centre[..., 0] if self.per_chain else centre


def flatten_model(
    model: Model,
    *,
    axis_name: Optional[str] = None,
    prior_scale: float = 1.0,
    lik_scale: float = 1.0,
) -> FlatModel:
    """Compile a Model into flat-vector potential / transforms.

    axis_name: if set, ``log_lik`` is treated as a per-shard partial sum and
      allreduced with ``lax.psum(_, axis_name)`` — the ICI collective that
      replaces the reference's driver-side reduce (SURVEY.md §4).
    prior_scale: prior tempering exponent (consensus Monte Carlo uses 1/S).
    lik_scale: likelihood scale (SG-HMC minibatching uses N/batch_size).
    """
    spec = model.param_spec()
    unc_shapes = {k: v.bijector.unconstrained_shape(tuple(v.shape)) for k, v in spec.items()}
    ndim, unflatten, flatten = make_unflatten(unc_shapes)

    def constrain_with_fldj(flat: Array) -> Tuple[Dict[str, Array], Array]:
        unc = unflatten(flat)
        params = {}
        fldj = jnp.zeros((), dtype=flat.dtype)
        for name, ps in spec.items():
            params[name] = ps.bijector.forward(unc[name])
            fldj = fldj + ps.bijector.fldj(unc[name])
        return params, fldj

    def constrain(flat: Array) -> Dict[str, Array]:
        return constrain_with_fldj(flat)[0]

    def unconstrain(params: Dict[str, Array]) -> Array:
        unc = {k: spec[k].bijector.inverse(jnp.asarray(params[k])) for k in spec}
        return flatten(unc)

    # cross-shard likelihood hook (sequence-parallel models): when the
    # model implements log_lik_sharded(params, data, axis_name), the
    # sharded path calls IT instead of log_lik — the model's own
    # collectives stitch the sequential structure (prefix scans,
    # boundary ties) across shards, and it returns this shard's PARTIAL
    # of the globally-stitched log-lik.  The same outer psum as the
    # ordinary per-shard path then reduces value and gradient — and
    # crucially the function's OUTPUT stays shard-local, so the
    # transposed in-likelihood collectives (which sum cotangent seeds
    # over shards) aggregate exactly one seed per shard output; a
    # replicated (internally psum'd) output would seed P cotangents and
    # inflate the gradient by the axis size (measured: exactly 8x on the
    # 8-shard mesh before this contract was fixed).
    sharded_ll_fn = getattr(model, "log_lik_sharded", None)
    comm: Dict[str, int] = {}

    def _local_ll(params, data):
        if axis_name is not None and sharded_ll_fn is not None:
            return sharded_ll_fn(params, data, axis_name)
        return model.log_lik(params, data)

    def potential(flat: Array, data: PyTree = None) -> Array:
        params, fldj = constrain_with_fldj(flat)
        lp = prior_scale * model.log_prior(params) + fldj
        if data is not None:
            ll = _local_ll(params, data)
            if axis_name is not None:
                from .parallel.primitives import reduce_tree

                ll = reduce_tree(ll, axis_name)
            lp = lp + lik_scale * ll
        return -lp

    def prior_part(z):
        params, fldj = constrain_with_fldj(z)
        return prior_scale * model.log_prior(params) + fldj

    def potential_and_grad(flat: Array, data: PyTree = None):
        if data is None or axis_name is None:
            return jax.value_and_grad(potential)(flat, data)

        # Sharded path: ONE fused psum carries [ll_value, ll_grad].
        def local_ll(z):
            params, _ = constrain_with_fldj(z)
            return _local_ll(params, data)

        from .parallel.primitives import predict_tree_bytes, reduce_tree

        ll, ll_grad = jax.value_and_grad(local_ll)(flat)
        packed = jnp.concatenate([ll[None], ll_grad])
        comm.update(
            psums_per_gradient=1,
            psum_bytes_per_chain=predict_tree_bytes(packed),
        )
        packed = reduce_tree(packed, axis_name)
        ll_tot, ll_grad_tot = packed[0], packed[1:]
        pp, pp_grad = jax.value_and_grad(prior_part)(flat)
        pe = -(pp + lik_scale * ll_tot)
        grad = -(pp_grad + lik_scale * ll_grad_tot)
        return pe, grad

    per_chain = bool(getattr(model, "center_per_chain", False))
    has_center = (
        getattr(type(model), "center_data", Model.center_data)
        is not Model.center_data
    )

    def center_keep(z: Array):
        return model.center_keep(constrain(z))

    def centering_of(rows: bool) -> "Centering":
        """The model's `Centering` with one constant for the ensemble, or
        (``rows``) a row a chain: the constant and what the model keeps
        beside it (nothing, unless it says `center_per_chain`)."""

        def center_at(z: Array, pe: Array, centre=None):
            # pe = -(prior + lik): what is left when the prior's part goes
            # is the whole mesh's log-likelihood term (0 where it is not
            # finite)
            c = pe + jax.vmap(prior_part)(z)
            c = jax.lax.stop_gradient(jnp.where(jnp.isfinite(c), c, 0.0))
            if not rows:
                return c
            return jax.lax.stop_gradient(jnp.concatenate(
                [(centre[:, 0] + c)[:, None], jax.vmap(center_keep)(z)],
                axis=1))

        def centered_data(data: PyTree, centre: Array):
            # each shard's sums take an even share of it off: rows dealt to
            # shards at random differ by a few thousand nats, still small
            # (one shard off the mesh)
            from .parallel.primitives import mapped_axis_size

            shards = lik_scale * mapped_axis_size(axis_name)
            if not rows:
                return model.center_data(data, -centre / shards)
            if not per_chain:  # the model's centre is the scalar alone
                return model.center_data(data, -centre[0] / shards)
            return model.center_data(
                data, jnp.concatenate([-centre[:1] / shards, centre[1:]]))

        return Centering(
            center_at, centered_data,
            # a row: the constant and what the model keeps beside it
            1 + jax.eval_shape(center_keep, jnp.zeros((ndim,))).shape[0]
            if rows else 0,
        )

    # the ensemble sampler centres over a data mesh, or where the model
    # asks for a centre a chain; the per-chain kernels wherever the model
    # can, each chain where it stands (an accept step compares a chain with
    # itself alone)
    centering = (
        centering_of(per_chain)
        if has_center and (axis_name is not None or per_chain) else None
    )
    chain_centering = (
        (centering if per_chain else centering_of(True))
        if has_center else None
    )

    def init_flat(key: Array) -> Array:
        init = model.init_params(key)
        if init is None:
            return jax.random.uniform(key, (ndim,), minval=-2.0, maxval=2.0)
        return unconstrain(init)

    return FlatModel(
        ndim=ndim,
        potential=potential,
        potential_and_grad=potential_and_grad,
        constrain=constrain,
        unconstrain=unconstrain,
        init_flat=init_flat,
        comm=comm,
        centering=centering,
        chain_centering=chain_centering,
    )
