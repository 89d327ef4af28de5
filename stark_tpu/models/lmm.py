"""Hierarchical linear mixed model — benchmark config 3 (BASELINE.json:9).

Random intercepts + random slopes over G groups (10k in the benchmark),
non-centered (u = tau * u_raw) so the funnel geometry is kernel-friendly.
The likelihood is a dense (N, D) matvec plus a gathered (N, Q) row-wise dot
with the per-group effects — gather + matmul, both XLA-native; the G×Q
random-effect block dominates the parameter vector exactly like the
benchmark intends (10k groups -> ~20k+ params).

data pytree:
  x: (N, D) fixed-effects design
  z: (N, Q) random-effects design (column 0 is typically ones = intercept)
  g: (N,) int32 group ids in [0, G)
  y: (N,) response
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import jax.scipy.stats as jstats

from ..bijectors import Exp
from ..model import Model, ParamSpec
from .logistic import (
    KnobGatedFusedMixin,
    TransposedXMixin as _TransposedXMixin,
    _fold_scale,
)


class LinearMixedModel(Model):
    def __init__(self, num_features: int, num_groups: int, num_random: int = 2):
        self.num_features = num_features
        self.num_groups = num_groups
        self.num_random = num_random  # Q: intercept + slopes

    def param_spec(self):
        return {
            "intercept": ParamSpec(()),
            "beta": ParamSpec((self.num_features,)),
            "u_raw": ParamSpec((self.num_groups, self.num_random)),
            "tau": ParamSpec((self.num_random,), Exp()),
            "sigma": ParamSpec((), Exp()),
        }

    def log_prior(self, p):
        lp = jstats.norm.logpdf(p["intercept"], 0.0, 5.0)
        lp += jnp.sum(jstats.norm.logpdf(p["beta"], 0.0, 2.5))
        lp += jnp.sum(jstats.norm.logpdf(p["u_raw"]))
        # half-normal(0,1) on random-effect scales and noise sd
        lp += jnp.sum(jstats.norm.logpdf(p["tau"], 0.0, 1.0) + jnp.log(2.0))
        lp += jstats.norm.logpdf(p["sigma"], 0.0, 1.0) + jnp.log(2.0)
        return lp

    def log_lik(self, p, data):
        return jnp.sum(self.log_lik_rows(p, data))

    def log_lik_rows(self, p, data):
        from ..ops.quantize import dequant_rows

        u = p["u_raw"] * p["tau"][None, :]  # (G, Q) non-centered
        x = data["x"] if "x" in data else dequant_rows(data)
        z = data["z"] if "z" in data else dequant_rows(data, key="zT")
        mu = (
            p["intercept"]
            + x @ p["beta"]
            + jnp.sum(z * u[data["g"]], axis=-1)
        )
        return jstats.norm.logpdf(data["y"], mu, p["sigma"])


class FusedLMM(KnobGatedFusedMixin, LinearMixedModel):
    """LMM with the shared one-pass fused value-and-grad
    (ops/lmm_fused.py), behind the default-OFF ``STARK_FUSED_LMM`` knob.

    Knob OFF (the default): ``prepare_data`` and ``log_lik`` are the
    parent's — bit-identical to `LinearMixedModel`.  Knob ON at prepare
    time: the row matrix is stored transposed (the shared fused layout,
    STARK_FUSED_X_DTYPE honored) and the potential gradient costs ONE
    pass instead of autodiff's forward+backward.  Data already prepared
    under the fused layout keeps working after the knob flips off
    (autodiff on the same transposed layout via the parent's
    ``log_lik_rows`` dual-layout read), so warm starts, resumes, and
    fleet-stacked datasets port across knob states.

    Distinct from `FusedLinearMixedModel` (always-on Pallas offset
    kernel) and `FusedLinearMixedModelGrouped` (fully-fused grouped
    Mosaic kernel): this variant is the XLA-level scaffold instance the
    rest of the zoo shares — and the knob-gated, parity-gated entry the
    accelerator rounds ratchet on.
    """

    _FUSED_FAMILY = "lmm"

    @staticmethod
    def _fused_enabled():
        from ..ops.lmm_fused import fused_lmm_enabled

        return fused_lmm_enabled()

    def _fallback_log_lik(self, p, data):
        # knob-off on fused-layout data: the parent reads either layout
        return super(KnobGatedFusedMixin, self).log_lik(p, data)

    def _fused_log_lik(self, p, data):
        from ..ops.lmm_fused import lmm_loglik
        from ..ops.quantize import stream_slab

        u = p["u_raw"] * p["tau"][None, :]  # (G, Q) non-centered
        return lmm_loglik(
            p["beta"], u, p["intercept"], p["sigma"],
            stream_slab(data), data["z"], data["g"], data["y"],
        )


class FusedLinearMixedModel(_TransposedXMixin, LinearMixedModel):
    """LMM with the fused gaussian Pallas kernel.

    Identical posterior; the (N, D) fixed-effects stream is read ONCE per
    value+gradient evaluation (vs twice under autodiff), and under vmap
    the whole chain ensemble shares that single pass — same treatment the
    flagship logistic gets from `ops/logistic_fused.py`.  The
    random-effects rowwise dot and its scatter-add VJP stay in XLA via
    the offsets input (∂/∂offsets = residual/sigma²).
    """

    def fused_tag(self):
        return "lmm"

    def log_lik(self, p, data):
        from ..ops.logistic_fused import gaussian_offset_loglik

        u = p["u_raw"] * p["tau"][None, :]  # (G, Q) non-centered
        offsets = p["intercept"] + jnp.sum(data["z"] * u[data["g"]], axis=-1)
        return gaussian_offset_loglik(
            _fold_scale(p["beta"], data), offsets,
            data["xT"], data["y"], p["sigma"],
        )


class FusedLinearMixedModelGrouped(LinearMixedModel):
    """LMM with the fully-fused grouped kernel (ops/hier_fused.py): rows
    pre-sorted by group; the random-effect offsets AND the (G, Q)
    u-gradient live inside the Pallas pass — no (C, N) gather/scatter
    per evaluation.  What `grouped_layout` does with it: at the on-chip
    benchmark's shape (10k groups over 81.9M rows, 8 192 rows a
    group: ``onchip/configs/lmm_d8_q2_g10k_n49m.json``) it keeps the full
    lane tile of 8 192 and a window of ``k_loc`` 8 (a tile spans at most
    three groups); at ``configs/lmm.yaml``'s 100k rows, ten a group, it
    halves the tile to 1 024, where a window of 104 groups fits.

    Same posterior as LinearMixedModel/FusedLinearMixedModel (row sums
    are permutation-invariant).  Falls back to the offset-path layout
    when no tile size keeps the window bounded.  Rows are NOT shardable
    (global tile layout) — use FusedLinearMixedModel on data meshes.
    Tens of millions of rows on one chip: the potential is centred chain
    by chain, off a mesh too (``center_per_chain``, ``center_data``).
    """

    def fused_tag(self):
        return "lmm"

    def prepare_data(self, data):
        if "gl" in data or "offsets_path" in data:
            return data  # already prepared (resume path)
        from ..ops.hier_fused import prepare_grouped

        d_eff = self.num_features + self.num_random  # x + z slabs share VMEM
        out = prepare_grouped(data, d_eff, transpose_keys=("x", "z"))
        if out is None:
            from .logistic import _transpose_x

            out = _transpose_x(data)
            out["offsets_path"] = jnp.zeros((0,))
        return out

    def data_row_axes(self, data):
        if "gl" not in data:
            from .logistic import _row_axes_xt

            return _row_axes_xt(data)
        raise NotImplementedError(
            "FusedLinearMixedModelGrouped's tile layout is global "
            "(first_gid indexes absolute lane tiles; 8192 lanes and a "
            "window of k_loc 8 at the benchmark's shape): rows cannot be "
            "re-sharded. Use FusedLinearMixedModel for data-sharded "
            "meshes; chain parallelism still applies."
        )

    def log_lik(self, p, data):
        u = p["u_raw"] * p["tau"][None, :]  # (G, Q) non-centered
        beta = _fold_scale(p["beta"], data)
        if "gl" not in data:  # fallback: offset path
            from ..ops.logistic_fused import gaussian_offset_loglik

            offsets = p["intercept"] + jnp.sum(
                data["z"] * u[data["g"]], axis=-1
            )
            ll = gaussian_offset_loglik(
                beta, offsets, data["xT"], data["y"], p["sigma"]
            )
            # no tile sums to centre on this path: the constant comes off
            # the total
            return ll - data["ll_center"][0] if "ll_center" in data else ll
        from ..ops.hier_fused import lmm_grouped_loglik

        # the z slab's quant scales fold into u the same way xT's fold
        # into beta: one rescale of the tiny (G, Q) array per evaluation
        if "zT_scale" in data:
            u = u * data["zT_scale"]
        return lmm_grouped_loglik(
            beta, u, p["intercept"], p["sigma"], data["xT"],
            data["zT"], data["y"], data["gl"], data["first_gid"],
            data["k_loc"], data["lt128"], data.get("ll_center"),
        )

    #: ten thousand groups of thousands of rows are tens of millions of
    #: rows on ONE chip, and chains that MAP leaves 1e7 nats and tens of
    #: percent of the noise scale apart (my chip runs, PR 32): every chain
    #: is centred where it stands itself, off a mesh too
    center_per_chain = True

    def center_keep(self, p):
        """`Model.center_keep`: the noise scale where the chain's centre is
        taken, with its logarithm and inverse square
        (`ops.hier_fused.ref_scale`)."""
        from ..ops.hier_fused import ref_scale

        return ref_scale(p["sigma"])

    def center_data(self, data, center):
        """`Model.center_data`: the tiles' log-densities take the chain's
        constant ``center[0]`` off before they are added, at the fixed
        scale ``center[1:]`` (`ops.hier_fused._gauss_loglik`)."""
        return {**data, "ll_center": center}


def synth_lmm_data(
    key, n, num_features, num_groups, *, num_random=2, noise=0.5,
    dtype=jnp.float32,
):
    """Synthetic LMM dataset + generating parameters."""
    ks = jax.random.split(key, 6)
    x = jax.random.normal(ks[0], (n, num_features), dtype)
    z = jnp.concatenate(
        [jnp.ones((n, 1), dtype), jax.random.normal(ks[1], (n, num_random - 1), dtype)],
        axis=1,
    )
    g = jax.random.randint(ks[2], (n,), 0, num_groups)
    beta = jax.random.normal(ks[3], (num_features,), dtype)
    tau = jnp.asarray([0.8] + [0.4] * (num_random - 1), dtype)
    u = tau[None, :] * jax.random.normal(ks[4], (num_groups, num_random), dtype)
    mu = 1.0 + x @ beta + jnp.sum(z * u[g], axis=-1)
    y = mu + noise * jax.random.normal(ks[5], (n,), dtype)
    data = {"x": x, "z": z, "g": g, "y": y}
    true = {"intercept": 1.0, "beta": beta, "tau": tau, "sigma": noise, "u": u}
    return data, true
