"""Bayesian (hierarchical) logistic regression — the flagship/benchmark model.

Benchmark config 2 and the north-star workload (BASELINE.json:5,8): logistic
regression on N rows (1M in the benchmark), optionally with per-group random
intercepts ("hierarchical logistic").  The likelihood is one big
(rows x features) matvec + elementwise log-sigmoid — exactly the shape the
MXU wants: batched, dense, static.

Data pytree: {"x": (N, D) float, "y": (N,) 0/1 float, "g": (N,) int32 group
ids (only for the hierarchical variant)}.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import jax.scipy.stats as jstats
import numpy as np

from ..bijectors import Exp
from ..model import Model, ParamSpec


def _bernoulli_logit_rows(logits, y):
    return y * jax.nn.log_sigmoid(logits) + (1.0 - y) * jax.nn.log_sigmoid(-logits)


def _bernoulli_logit_loglik(logits, y):
    # sum_i [ y_i * log sigmoid(l_i) + (1-y_i) * log sigmoid(-l_i) ]
    return jnp.sum(_bernoulli_logit_rows(logits, y))


def _rows_x(data):
    """(N, D) design from either layout (prepare_data may have
    transposed — and, under a quantized STARK_FUSED_X_DTYPE, packed:
    the cold-path reconstruction dequantizes)."""
    if "x" in data:
        return data["x"]
    from ..ops.quantize import dequant_rows

    return dequant_rows(data)


class Logistic(Model):
    """Flat logistic regression: beta ~ N(0, prior_scale), y ~ Bern(sigmoid(x@beta))."""

    def __init__(self, num_features: int, prior_scale: float = 2.5):
        self.num_features = num_features
        self.prior_scale = prior_scale

    def param_spec(self):
        return {"beta": ParamSpec((self.num_features,))}

    def log_prior(self, p):
        return jnp.sum(jstats.norm.logpdf(p["beta"], 0.0, self.prior_scale))

    def log_lik(self, p, data):
        logits = data["x"] @ p["beta"]
        return _bernoulli_logit_loglik(logits, data["y"])

    def log_lik_rows(self, p, data):
        return _bernoulli_logit_rows(_rows_x(data) @ p["beta"], data["y"])


class HierLogistic(Model):
    """Hierarchical logistic: shared coefficients + per-group random intercepts.

    Non-centered: alpha_g = alpha0 + sigma_alpha * alpha_raw_g.
    The group-effect gather is a one-hot-free ``alpha[g]`` lookup that XLA
    lowers to a dynamic-gather — cheap next to the (N, D) matvec.
    """

    def __init__(self, num_features: int, num_groups: int, prior_scale: float = 2.5):
        self.num_features = num_features
        self.num_groups = num_groups
        self.prior_scale = prior_scale

    def param_spec(self):
        return {
            "beta": ParamSpec((self.num_features,)),
            "alpha0": ParamSpec(()),
            "sigma_alpha": ParamSpec((), Exp()),
            "alpha_raw": ParamSpec((self.num_groups,)),
        }

    def log_prior(self, p):
        lp = jnp.sum(jstats.norm.logpdf(p["beta"], 0.0, self.prior_scale))
        lp += jstats.norm.logpdf(p["alpha0"], 0.0, 5.0)
        # half-normal(0, 1) scale
        lp += jstats.norm.logpdf(p["sigma_alpha"], 0.0, 1.0) + jnp.log(2.0)
        lp += jnp.sum(jstats.norm.logpdf(p["alpha_raw"]))
        return lp

    def log_lik(self, p, data):
        alpha = p["alpha0"] + p["sigma_alpha"] * p["alpha_raw"]
        logits = data["x"] @ p["beta"] + alpha[data["g"]]
        return _bernoulli_logit_loglik(logits, data["y"])

    def log_lik_rows(self, p, data):
        alpha = p["alpha0"] + p["sigma_alpha"] * p["alpha_raw"]
        logits = _rows_x(data) @ p["beta"] + alpha[data["g"]]
        return _bernoulli_logit_rows(logits, data["y"])


def _transpose_x(data):
    """One-time host-side layout prep for the fused kernels: replace the
    (N, D) row matrix with its (D, N) transpose so the kernel streams the
    row axis on full-width TPU lanes (see ops/logistic_fused.py)."""
    if "xT" in data:
        return data
    from ..ops.logistic_fused import _x_stream_dtype
    from ..ops.quantize import is_packed_dtype, pack_slab

    out = {k: v for k, v in data.items() if k != "x"}
    # storage dtype per STARK_FUSED_X_DTYPE (bf16 halves the X stream,
    # int8/fp8 quarter it; kernels cast back to f32 in-register and the
    # quantized dtypes calibrate per-column scales at pack time — see
    # ops/quantize.py)
    xdt = _x_stream_dtype()
    if is_packed_dtype(xdt):
        out["xT"], out["xT_scale"] = pack_slab(
            jnp.asarray(data["x"]).T.astype(jnp.float32), xdt
        )
    else:
        out["xT"] = jnp.asarray(data["x"]).T.astype(xdt)
    return out


# `FusedLogistic`'s second kernel-layout leaf: ``y`` as the (1, N) float32
# operand `stark_logistic_ll` takes, rows on the lanes like ``xT``'s
Y_LANES = "y_lanes"


def _row_axes_xt(data):
    # rows ride axis 1 of the transposed matrix (and of the (1, N) outcome
    # row laid out beside it), axis 0 everywhere else.
    # Zero-length sentinel keys (e.g. the grouped model's 'offsets_path'
    # fallback marker) carry no rows: mark them None = replicated so the
    # data sharder never treats a (0,)-shaped marker as row-sharded data
    # (ADVICE r3).  Keys must stay aligned with ``data`` for tree.map,
    # and None is a zero-leaf pytree node, so -1 is the marker.  Shape
    # metadata only — np.asarray here would pull device arrays (the whole
    # (D, N) xT at flagship scale) back to the host on every backend setup.
    def ax(k, v):
        if np.ndim(v) == 0 or np.shape(v)[0] == 0:
            return -1
        if k.endswith("_scale"):
            # per-COLUMN quant scales (ops/quantize.py) carry no rows:
            # replicate them so every row shard dequantizes its slice of
            # the packed slab against the same global calibration
            return -1
        return 1 if k in ("xT", Y_LANES) else 0

    return {k: ax(k, v) for k, v in data.items()}


def _fold_scale(beta, data, key="xT_scale"):
    """Quant-scale epilogue fold for the Pallas fused kernels: with a
    packed slab, ``(s ⊙ q)·beta == q·(s ⊙ beta)`` — pre-scaling the
    (D,) parameter operand is algebraically the dequant epilogue, so
    the kernel streams the packed bytes untouched and autodiff chains
    the scale back through the custom_vjp beta-gradient (a second (D,)
    multiply).  No-op (same array) when the slab isn't quantized."""
    s = data.get(key)
    return beta if s is None else beta * s


class TransposedXMixin:
    """Shared layout hooks for every fused-kernel model: replace the
    (N, D) row matrix with its (D, N) transpose once, host-side, and
    declare the moved row axis for the data sharder.  ONE copy of the
    fused-layout convention — all Fused* models mix this in."""

    def prepare_data(self, data):
        return _transpose_x(data)

    def data_row_axes(self, data):
        return _row_axes_xt(data)


class KnobGatedFusedMixin:
    """Shared hooks for the default-OFF fused zoo variants (FusedLMM,
    FusedOrderedLogistic, FusedStudentTRegression): knob-gated transposed
    prepare, layout-aware row axes, knob-aware telemetry tag, and the
    fused/fallback ``log_lik`` shell.  ONE copy of the knob-off
    contract — knob off at prepare time is bit-identical to the parent
    model, and data already in the fused layout keeps working after the
    knob flips off (warm starts, resumes, fleet-stacked datasets port
    across knob states).

    Subclasses set ``_FUSED_FAMILY`` and implement ``_fused_enabled()``
    (lazy op import) and ``_fused_log_lik(p, data)``; a parent whose
    ``log_lik`` already reads both layouts overrides
    ``_fallback_log_lik`` to defer to it (FusedLMM).
    """

    _FUSED_FAMILY: str

    @staticmethod
    def _fused_enabled() -> bool:
        raise NotImplementedError

    def prepare_data(self, data):
        if self._fused_enabled():
            return _transpose_x(data)
        return data

    def data_row_axes(self, data):
        if "xT" in data:
            return _row_axes_xt(data)
        return super().data_row_axes(data)

    def fused_tag(self):
        return self._FUSED_FAMILY if self._fused_enabled() else None

    def log_lik(self, p, data):
        if "xT" not in data:
            return super().log_lik(p, data)
        if not self._fused_enabled():
            return self._fallback_log_lik(p, data)
        return self._fused_log_lik(p, data)

    def _fallback_log_lik(self, p, data):
        # knob flipped off after a fused-layout prepare: autodiff on the
        # de-transposed (and, for a packed slab, dequantized) matrix
        from ..ops.quantize import dequant_rows

        x = dequant_rows(data, dtype=jnp.float32)
        return super().log_lik(p, {**data, "x": x})

    def _fused_log_lik(self, p, data):
        raise NotImplementedError


class FusedLogistic(TransposedXMixin, Logistic):
    """Logistic with the one-pass Pallas likelihood kernel.

    Identical posterior; the per-evaluation HBM traffic over the row
    matrix is halved vs autodiff (see ops/logistic_fused.py).
    """

    def fused_tag(self):
        return "logistic"

    def prepare_data(self, data):
        """``xT`` (`_transpose_x`) and, beside it, ``y`` in the kernel's own
        operand layout: float32, shape (1, N), made here once a run, so the
        sampling loop hands its carry to the custom call with no operation
        in between (`ops.logistic_fused._y_operand` says what a rank-1
        ``y`` costs).  ``data["y"]`` stays the caller's array.  Written in
        ``jax.numpy``: rows sharded over a mesh are laid out shard by
        shard.  Data that already holds ``xT`` is returned as it is, with
        or without the leaf.  The caller's ``prepare_data`` span gets
        ``mxu_form`` and ``mxu_rows``: how the chain-batched kernel will
        contract a tile (`ops.logistic_fused.batched_mxu_form`), the same
        on every shard."""
        if "xT" in data:
            return data
        from .. import telemetry
        from ..ops.logistic_fused import batched_mxu_form

        out = _transpose_x(data)
        out[Y_LANES] = jnp.asarray(data["y"]).astype(jnp.float32)[None, :]
        form, rows = batched_mxu_form(int(np.shape(data["x"])[1]))
        telemetry.note(mxu_form=form, mxu_rows=rows)
        return out

    def log_lik(self, p, data):
        from ..ops.logistic_fused import logistic_loglik

        return logistic_loglik(
            _fold_scale(p["beta"], data), data["xT"],
            data.get(Y_LANES, data["y"]), data.get("ll_center"),
        )

    def center_data(self, data, center):
        """`Model.center_data`: the kernel's tile sums take ``center`` off
        before they are added (`ops.logistic_fused._sum_tiles`)."""
        return {**data, "ll_center": center}


class FusedHierLogistic(TransposedXMixin, HierLogistic):
    """HierLogistic with the fused kernel: the X-pass runs in Pallas; the
    group-intercept gather and its segment-sum VJP stay in XLA via the
    custom_vjp residual output."""

    def fused_tag(self):
        return "logistic"

    def log_lik(self, p, data):
        from ..ops.logistic_fused import logistic_offset_loglik

        alpha = p["alpha0"] + p["sigma_alpha"] * p["alpha_raw"]
        return logistic_offset_loglik(
            _fold_scale(p["beta"], data), alpha[data["g"]],
            data["xT"], data["y"],
        )


class FusedHierLogisticGrouped(HierLogistic):
    """HierLogistic with the fully-fused grouped kernel: rows pre-sorted
    by group so the group-intercept offsets AND the group gradient live
    inside the Pallas pass — no (C, N) gather/scatter/stream per
    evaluation (measured 16x the offset path's gradient cost on one v5e
    chip at N=1M, C=32; see ops/hier_fused.py).

    Same posterior as HierLogistic/FusedHierLogistic (the log-lik is a
    row sum — sorting is a permutation).  When the data defeats the
    dense-window layout (some lane tile spans > _K_LOC_MAX groups),
    prepare_data falls back to the offset-path layout and log_lik routes
    accordingly.  At the on-chip benchmark's shape (1000 groups over 16M
    rows) `grouped_layout` keeps the full lane tile of 8 192 and a window
    of ``k_loc`` 8.  Rows are NOT shardable across a data mesh axis: the
    tile layout is global (first_gid indexes absolute tiles) — use
    FusedHierLogistic for sharded runs.
    """

    def fused_tag(self):
        return "logistic"

    def prepare_data(self, data):
        if "gl" in data or "offsets_path" in data:
            return data  # already prepared (resume path)
        from .. import telemetry
        from ..ops.hier_fused import grouped_mxu_form, prepare_grouped

        d = int(np.asarray(data["x"]).shape[1])
        out = prepare_grouped(data, d)
        if out is None:
            # degenerate grouping (tiny groups scattered wide): keep the
            # offset-path layout, just transposed
            out = _transpose_x(data)
            out["offsets_path"] = jnp.zeros((0,))
            return out
        # on the caller's `prepare_data` span: how the kernel will contract
        form, rows = grouped_mxu_form(d, out["k_loc"].shape[0])
        telemetry.note(mxu_form=form, mxu_rows=rows)
        return out

    def data_row_axes(self, data):
        if "gl" not in data:  # fallback offset layout shards like the base
            return _row_axes_xt(data)
        raise NotImplementedError(
            "FusedHierLogisticGrouped's tile layout is global (first_gid "
            "indexes absolute lane tiles; 8192 lanes and a window of "
            "k_loc 8 at the benchmark's shape): rows cannot be re-sharded. "
            "Use FusedHierLogistic for data-sharded meshes; chain "
            "parallelism still applies."
        )

    def log_lik(self, p, data):
        alpha = p["alpha0"] + p["sigma_alpha"] * p["alpha_raw"]
        beta = _fold_scale(p["beta"], data)
        if "gl" not in data:  # fallback layout
            from ..ops.logistic_fused import logistic_offset_loglik

            ll = logistic_offset_loglik(
                beta, alpha[data["g"]], data["xT"], data["y"]
            )
            # no tiles to take a centre off: it comes off the total
            return ll - data["ll_center"] if "ll_center" in data else ll
        from ..ops.hier_fused import hier_logistic_loglik

        return hier_logistic_loglik(
            beta, alpha, data["xT"], data["y"], data["gl"],
            data["first_gid"], data["k_loc"], data["lt128"],
            data.get("ll_center"),
        )

    def center_data(self, data, center):
        """`Model.center_data`: the grouped kernel's tile sums take
        ``center`` off before they are added
        (`ops.logistic_fused._sum_tiles`).  The per-chain kernels ask for
        it, a constant a chain (`sampler.ChainBlockKernel`); the one-chip
        ensemble sampler sums the plain potential (``center_per_chain``
        stays off)."""
        return {**data, "ll_center": center}


def synth_logistic_data(key, n, d, *, num_groups=0, dtype=jnp.float32):
    """Synthetic benchmark dataset (+ the true parameters used)."""
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    x = jax.random.normal(k1, (n, d), dtype)
    beta = jax.random.normal(k2, (d,), dtype)
    logits = x @ beta
    out = {"x": x}
    true = {"beta": beta}
    if num_groups:
        g = jax.random.randint(k3, (n,), 0, num_groups)
        alpha = 0.5 * jax.random.normal(k4, (num_groups,), dtype)
        logits = logits + alpha[g]
        out["g"] = g
        true["alpha"] = alpha
    y = (jax.random.uniform(k5, (n,)) < jax.nn.sigmoid(logits)).astype(dtype)
    out["y"] = y
    return out, true
