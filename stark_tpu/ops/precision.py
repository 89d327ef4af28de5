"""Shared mixed-precision + fused value-and-grad machinery for every
fused op in the zoo.

Before this module each fused-op file carried its own copy of the
precision plumbing (ops/logistic_fused.py defined it, ops/hier_fused.py
and ops/glm_fused.py re-imported the private names) and every new fused
likelihood re-implemented the same ~100 lines of custom_vjp + jit-cache
boilerplate.  This module is the single home for

* the two process-wide mixed-precision knobs every fused op honors:
  ``STARK_FUSED_PRECISION`` (`dot_precision`) for the MXU dot passes and
  ``STARK_FUSED_X_DTYPE`` (`x_stream_dtype`) for the HBM storage dtype of
  the streamed design matrix (bf16 slabs halve the dominant traffic;
  kernels/ops cast back to f32 in-register so accumulation stays f32);

* the call-time-static jit-key convention (`precision_statics`) that
  makes toggling either knob mid-process RETRACE instead of silently
  reusing a stale executable (the ADVICE-r5 fix, now shared);

* the boolean ``STARK_FUSED_<FAMILY>`` model knobs (`fused_knob`) behind
  which each fused model variant routes to its op or falls back to
  autodiff;

* `fused_value_and_grad` — the scaffold that turns a one-pass residual
  function into the full fused-op contract (a differentiable
  ``custom_vjp`` scalar whose VJP chains the precomputed gradients and
  never re-reads the data, plus a jitted direct value-and-grad entry
  keyed on the resolved precision knobs), so a new likelihood is ~a
  residual function, not 600 lines;

* `clip_band` — the shared clip-band gradient mask (saturated rows get
  zero sensitivity, exactly matching autodiff through ``jnp.clip``).

Data-layout contract (shared by every fused op): models store the row
matrix TRANSPOSED — ``xT`` of shape (D, N), rows on the 128-wide TPU
lane axis — produced once, host-side, by ``Model.prepare_data``
(`models.logistic.TransposedXMixin` / `_transpose_x`), so the hot path
never pays a layout change and fleet batching (`FleetSpec.prepare_data`
stacking) adds its problem axis on top of the already-fused layout.
"""

from __future__ import annotations

import functools
import inspect
import os
from typing import Callable, Tuple

import jax
import jax.numpy as jnp

__all__ = [
    "X_DTYPE_NAMES",
    "clip_band",
    "dot_precision",
    "fused_knob",
    "fused_value_and_grad",
    "precision_statics",
    "quant_percentile",
    "split6_backward",
    "split6_forward",
    "split6_operands",
    "split6_rows",
    "split_bf16x3",
    "stream_arg",
    "x_stream_config",
    "x_stream_dtype",
]


def split_bf16x3(x):
    """(hi, mid, lo): three float32 arrays, each exactly a bfloat16, whose
    sum is ``x``.

    ``hi = bf16(x)``, ``mid = bf16(x - hi)``, ``lo = x - hi - mid``, each
    conversion rounding to nearest and each difference exact in float32:
    hi holds the top 8 significant bits, mid the next 8 and lo the 8 that
    remain, so ``hi + mid + lo == x`` for every normal float32, and mid and
    lo are as often negative as positive.  The terms stay float32 so that a
    caller stacks them first and converts the stack once
    (`split6_operands`).

    Rounding, not truncation, matters in the products: the chip's compiler
    splits an f32 dot's operands at ``highest`` by clearing their low 16
    bits (PERF.md §6, PR 41), which leaves mid and lo with the sign of
    ``x``; through the three products `highest` leaves out, such a lo
    moves every logit along ``x`` the way a shifted ``beta`` would, and the
    flagship's beta-gradient, a sum over 16M rows of such rows, read 2.3
    times further from float64 than the kernel's own passes did (my chip
    run, PR 41)."""
    def round_bf16(v):
        return v.astype(jnp.bfloat16).astype(jnp.float32)

    x = x.astype(jnp.float32)
    hi = round_bf16(x)
    rest = x - hi
    mid = round_bf16(rest)
    return hi, mid, rest - mid


# `highest` on the MXU: an f32 dot is the six single-pass products of the
# operands' bf16 splits that are larger than float32's rounding, hi·hi,
# hi·mid, mid·hi, hi·lo, lo·hi and mid·mid (jax.lax.DotAlgorithmPreset.
# BF16_BF16_F32_X6), each over the whole contraction, added in float32.
# Where the contraction is short, a pass leaves most of the array's 128
# rows idle, so the functions below form the six products themselves and
# pack them into one contraction (forward) or one streamed block
# (backward): one bf16 dot for each, not six passes.  An operand ``e``
# that bfloat16 holds exactly (a one-hot) has no mid or lo term: only its
# three products with the other side's splits enter.  Without one (``e``
# and its parameters ``a`` None, K = 0: the chain-batched logistic kernel)
# only the split rows are packed.


def split6_rows(d: int, k: int) -> int:
    """The packed forward contraction's height for ``d`` split rows and
    ``k`` exact rows: six blocks of ``d`` and three of ``k``."""
    return 6 * d + 3 * k


def split6_operands(x, e=None):
    """A tile's packed operands, formed once for both dots.

    ``x`` (D, T) float32 and ``e`` (K, T), exact in bfloat16 ->
    ``fwd`` (6D + 3K, T) bfloat16, rows [x_hi; x_mid; x_lo; e; e; x_hi;
    x_mid; x_hi; e] (`split6_forward`), and ``bwd`` (3D + K, T) bfloat16,
    rows [x_hi; x_mid; x_lo; e] (`split6_backward`).  With ``e`` None
    (K = 0) ``fwd`` is [x_hi; x_mid; x_lo; x_hi; x_mid; x_hi] and ``bwd``
    [x_hi; x_mid; x_lo]."""
    hi, mid, lo = split_bf16x3(x)
    if e is None:
        fwd, bwd = [hi, mid, lo, hi, mid, hi], [hi, mid, lo]
    else:
        e = e.astype(jnp.float32)
        fwd, bwd = [hi, mid, lo, e, e, hi, mid, hi, e], [hi, mid, lo, e]
    fwd = jnp.concatenate(fwd, axis=0)
    bwd = jnp.concatenate(bwd, axis=0)
    return fwd.astype(jnp.bfloat16), bwd.astype(jnp.bfloat16)


def split6_forward(w, a, fwd):
    """``w @ x + a @ e`` (C, T) float32 at `highest`'s six products, as one
    bf16 dot whose contraction holds them all.

    ``w`` (C, D) and ``a`` (C, K) float32, ``fwd`` from `split6_operands`.
    The parameters' row [w_hi | w_hi | w_hi | a_hi | a_mid | w_mid | w_mid
    | w_lo | a_lo] meets the slab's blocks as hi·hi, hi·mid, hi·lo, mid·hi,
    mid·mid and lo·hi over ``x`` and the three terms of ``a`` over ``e``,
    all added in the MXU's float32 accumulator.  With ``a`` None (a slab
    made without ``e``) the row is [w_hi | w_hi | w_hi | w_mid | w_mid |
    w_lo] and the result ``w @ x``."""
    wh, wm, wl = split_bf16x3(w)
    if a is None:
        params = jnp.concatenate([wh, wh, wh, wm, wm, wl], axis=1)
    else:
        ah, am, al = split_bf16x3(a)
        params = jnp.concatenate([wh, wh, wh, ah, am, wm, wm, wl, al], axis=1)
    return jax.lax.dot(
        params.astype(jnp.bfloat16), fwd,
        precision=jax.lax.Precision.DEFAULT,
        preferred_element_type=jnp.float32,
    )


def split6_backward(r, bwd, d: int):
    """``(r @ xᵀ (C, D), r @ eᵀ (C, K))`` float32 at `highest`'s six
    products, as one bf16 dot over the streamed axis.

    ``r`` (C, T) float32, ``bwd`` from `split6_operands`, ``d`` its split
    rows.  [r_hi; r_mid; r_lo] (3C, T) against ``bwd``'s transpose gives
    nine (C, D) blocks and three (C, K); the six that `highest` forms and
    the three over ``e`` are added in float32, smallest first.  The MXU
    also forms r_mid·x_lo, r_lo·x_mid and r_lo·x_lo: they are left out, as
    `highest` leaves them out.  A slab made without ``e`` (3D rows) gives
    ``(r @ xᵀ, None)``."""
    c = r.shape[0]
    rows = jnp.concatenate(split_bf16x3(r), axis=0).astype(jnp.bfloat16)
    g = jax.lax.dot(
        rows, bwd.T, precision=jax.lax.Precision.DEFAULT,
        preferred_element_type=jnp.float32,
    )  # (3C, 3D + K): rows r_hi, r_mid, r_lo; columns x_hi, x_mid, x_lo, e

    def blk(i, j, width=d):
        return g[i * c:(i + 1) * c, j * d:j * d + width]

    gx = ((blk(2, 0) + blk(0, 2)) + blk(1, 1)) + (
        (blk(1, 0) + blk(0, 1)) + blk(0, 0)
    )
    k = bwd.shape[0] - 3 * d
    if k == 0:
        return gx, None
    ge = (blk(2, 3, k) + blk(1, 3, k)) + blk(0, 3, k)
    return gx, ge


def dot_precision():
    """MXU precision for the fused kernels' dots (STARK_FUSED_PRECISION).

    f32 matmuls on the TPU MXU are EMULATED in bf16 passes: DEFAULT is
    one pass (inputs truncated to bf16), HIGH three passes (~f32-accurate),
    HIGHEST six (the six largest cross products of three-way splits).
    At HIGHEST the grouped hierarchical kernel and the chain-batched
    logistic kernel form those six products themselves, packed into the
    MXU rows six short passes leave idle (`split6_forward`,
    `split6_backward`; `hier_fused.grouped_mxu_form`,
    `logistic_fused.batched_mxu_form`): the grouped kernel's static
    schedule at `hier_n16m.sample`'s shapes went from 12 036 bundles a
    tile, 97.9 % of them MXU, to 8 089, and the logistic kernel's at
    `logistic_n20m.sample`'s from 2 920 to 1 943 (compiles for a
    described v5e; PERF.md §5).  The knob exists
    so the on-chip roofline can measure the precision/throughput trade
    and the sampler can adopt the cheapest setting whose posterior matches
    (tools/precision_parity.py is that gate).  Default stays HIGHEST:
    numerics never change silently.
    """
    name = os.environ.get("STARK_FUSED_PRECISION", "highest").lower()
    try:
        return {
            "highest": jax.lax.Precision.HIGHEST,
            "high": jax.lax.Precision.HIGH,
            "default": jax.lax.Precision.DEFAULT,
        }[name]
    except KeyError:
        raise ValueError(
            f"STARK_FUSED_PRECISION={name!r}: use highest|high|default"
        ) from None


#: canonical STARK_FUSED_X_DTYPE values, ordered by bytes per element —
#: the single source the resolver's error message, the README coverage
#: table, and the parity sweep's dtype axis all derive from (so adding
#: a dtype here is the ONE place the accepted set changes; a test pins
#: the error message to exactly this tuple so they can't drift apart
#: again).
X_DTYPE_NAMES = ("f32", "bf16", "int8", "fp8e4m3", "fp8e5m2")

_X_DTYPES = {
    "f32": jnp.float32,
    "float32": jnp.float32,
    "bf16": jnp.bfloat16,
    "bfloat16": jnp.bfloat16,
    # quantized storage dtypes (ops/quantize.py): prepare_data packs X
    # with per-column calibrated scales; kernels fold the dequant into
    # the matvec epilogue, accumulation stays f32
    "int8": jnp.int8,
    "fp8e4m3": jnp.float8_e4m3fn,
    "float8_e4m3fn": jnp.float8_e4m3fn,
    "fp8e5m2": jnp.float8_e5m2,
    "float8_e5m2": jnp.float8_e5m2,
}


def x_stream_dtype():
    """HBM storage dtype for the streamed design matrix
    (STARK_FUSED_X_DTYPE: f32 default | bf16 | int8 | fp8e4m3 |
    fp8e5m2).

    The X stream is the dominant HBM traffic of every fused kernel
    (~94% of the grouped kernel's bytes at the flagship shape); bf16
    halves it and the quantized dtypes quarter it — the stream-side
    lever that compounds with the MXU-side `dot_precision` lever once
    the kernel stops being pass-bound.  Opt-in because it changes the
    DATA, not just the arithmetic: X is rounded (bf16) or packed with
    per-column calibrated scales (int8/fp8, ops/quantize.py) ONCE at
    prepare time, and the posterior is exactly that of the
    rounded/dequantized design matrix (kernels cast back to f32
    in-register and fold the scales into the matvec epilogue, so all
    accumulation stays f32).  Adopt via the same parity gate as the
    precision knob (tools/precision_parity.py, which sweeps the whole
    zoo over both knobs).  Adaptation-artifact fingerprints key on the
    CALLER's raw data, so warm starts port across X dtypes — the
    touch-up re-equilibrates and the convergence gate still validates.
    """
    name = os.environ.get("STARK_FUSED_X_DTYPE", "f32").lower()
    try:
        return _X_DTYPES[name]
    except KeyError:
        # enumerate EXACTLY the canonical accepted set: the README table
        # and this message once listed only f32|bf16 while drifting
        # independently — both now derive from X_DTYPE_NAMES
        raise ValueError(
            f"STARK_FUSED_X_DTYPE={name!r}: use {'|'.join(X_DTYPE_NAMES)}"
        ) from None


def quant_percentile():
    """Outlier-percentile calibration knob (STARK_QUANT_PCT): None
    (unset or 100) -> plain absmax calibration; a float in (0, 100) ->
    each design-matrix column's scale maps its p-th absolute percentile
    (not its max) onto the packed dtype's range, clipping the outlier
    tail symmetrically in exchange for bulk resolution.  Only consulted
    when STARK_FUSED_X_DTYPE resolves to a quantized dtype."""
    val = os.environ.get("STARK_QUANT_PCT")
    if val is None:
        return None
    try:
        pct = float(val)
    except ValueError:
        raise ValueError(
            f"STARK_QUANT_PCT={val!r}: need a percentile in (0, 100]"
        ) from None
    if not 0.0 < pct <= 100.0:
        raise ValueError(
            f"STARK_QUANT_PCT={val!r}: need a percentile in (0, 100]"
        )
    return None if pct == 100.0 else pct


def x_stream_config() -> str:
    """The RESOLVED X-stream config as one hashable jit cache-key
    token: the canonical dtype name, plus the calibration percentile
    when a quantized dtype is active (``"int8@p99.9"``) — so flipping
    EITHER the dtype knob or a STARK_QUANT_* calibration knob
    mid-process changes the key and retraces (ADVICE r5 extended to
    the quant config)."""
    dt = jnp.dtype(x_stream_dtype())
    name = {
        jnp.dtype(jnp.float32): "f32",
        jnp.dtype(jnp.bfloat16): "bf16",
        jnp.dtype(jnp.int8): "int8",
        jnp.dtype(jnp.float8_e4m3fn): "fp8e4m3",
        jnp.dtype(jnp.float8_e5m2): "fp8e5m2",
    }[dt]
    if name in ("int8", "fp8e4m3", "fp8e5m2"):
        pct = quant_percentile()
        if pct is not None:
            name += f"@p{pct:g}"
    return name


#: dtypes a kernel streams AS STORED (everything else normalizes to f32)
_STREAM_DTYPES = frozenset(
    jnp.dtype(d)
    for d in (jnp.bfloat16, jnp.int8, jnp.float8_e4m3fn, jnp.float8_e5m2)
)


def stream_arg(xt):
    """Pass a design-matrix slab to a kernel in its storage dtype (bf16
    streams halve HBM traffic, int8/fp8 quarter it; kernels cast back
    to f32 in-register); anything else is normalized to f32.  Accepts
    the packed ``(q, scale)`` pair (ops/quantize.py): the kernel sees
    the packed slab, while the scale rides the caller's pytree to the
    epilogue fold (Pallas kernels never see scales — the model folds
    them into the parameter operand, which is algebraically the same
    epilogue)."""
    if isinstance(xt, (tuple, list)):
        xt = xt[0]
    if xt.dtype in _STREAM_DTYPES:
        return xt
    return xt.astype(jnp.float32)


def precision_statics():
    """The resolved precision knobs as jit cache-key statics.

    Pass ``**precision_statics()`` into a jit whose ``static_argnames``
    include ``("_precision", "_x_dtype")`` and whose body re-reads the
    env knobs at trace time: keying the executable on the RESOLVED
    values is what forces a retrace when a knob changes mid-process —
    a module-level jit otherwise reuses the stale executable for
    same-shape calls, silently violating the "numerics never change
    silently" contract (ADVICE r5).  ``_x_dtype`` is the full
    `x_stream_config` token (dtype + quant calibration), so flipping a
    STARK_QUANT_* knob retraces too.
    """
    return {"_precision": dot_precision(), "_x_dtype": x_stream_config()}


def fused_knob(name: str, *, default: bool = False) -> bool:
    """Boolean ``STARK_FUSED_<FAMILY>`` model knob: unset -> ``default``,
    ``"0"`` -> off, anything else -> on.

    Family knobs gate which EXECUTION PATH a ``Fused*`` model variant
    takes (fused op vs autodiff fallback); they are read at
    prepare/trace time, so within one compiled run the path is fixed.
    The new zoo knobs default OFF — a knob-off run is bit-identical to
    the historical model — while ``STARK_FUSED_GLM`` keeps its
    historical default-on.
    """
    val = os.environ.get(name)
    if val is None:
        return default
    return val != "0"


def clip_band(eta_raw, clip: float):
    """(eta, inside): the clipped linear predictor and the f32 mask that
    zeroes gradient terms where the band saturates.

    ``inside`` is exactly the sensitivity autodiff assigns through
    ``jnp.clip`` (zero at a saturated link), so fused and autodiff
    gradients agree everywhere — including warmup excursions outside
    the band.
    """
    eta = jnp.clip(eta_raw, -clip, clip)
    inside = (jnp.abs(eta_raw) < clip).astype(eta_raw.dtype)
    return eta, inside


def fused_value_and_grad(
    vg: Callable, *, ndiff: int
) -> Tuple[Callable, Callable]:
    """Scaffold: one residual function -> the full fused-op contract.

    ``vg(*args) -> (value, grads)`` must compute the likelihood value
    AND the tuple of gradients w.r.t. its first ``ndiff`` arguments in
    ONE pass over the data arguments (positions ``ndiff`` onward —
    design matrices, index vectors, responses).  Returns

    * ``op`` — a ``jax.custom_vjp`` scalar function over the same
      arguments.  Differentiable: the VJP scales the precomputed
      gradients by the cotangent and never re-reads the data args
      (their cotangents are None), so ``jax.value_and_grad`` through a
      potential that calls ``op`` costs exactly one ``vg`` evaluation.
    * ``op_value_and_grad`` — the jitted direct entry returning
      ``(value, grads)``, with the resolved STARK_FUSED_PRECISION /
      STARK_FUSED_X_DTYPE knobs threaded in as call-time statics (a
      mid-process knob toggle retraces; the jit object is exposed as
      ``op_value_and_grad._jit`` for cache introspection in tests).

    The scaffold does not jit ``op`` itself: it runs inside the
    sampler's compiled potential, which owns that trace.
    """
    nargs = len(inspect.signature(vg).parameters)
    if not 0 < ndiff <= nargs:
        raise ValueError(f"ndiff={ndiff} out of range for {nargs}-arg vg")

    @functools.partial(jax.jit, static_argnames=("_precision", "_x_dtype"))
    def _vg_jit(*args, _precision, _x_dtype):
        # cache-key-only statics; vg re-reads the env knobs at trace time
        del _precision, _x_dtype
        return vg(*args)

    def op_value_and_grad(*args):
        return _vg_jit(*args, **precision_statics())

    op_value_and_grad._jit = _vg_jit
    op_value_and_grad.__doc__ = (
        f"One-pass (value, grads w.r.t. first {ndiff} args) of {vg.__name__},"
        " jitted with the precision knobs as call-time statics."
    )

    @jax.custom_vjp
    def op(*args):
        val, _ = vg(*args)
        return val

    def _fwd(*args):
        return vg(*args)

    def _bwd(grads, ct):
        cts = tuple(jax.tree.map(lambda g: ct * g, gr) for gr in grads)
        return cts + (None,) * (nargs - ndiff)

    op.defvjp(_fwd, _bwd)
    op.__wrapped__ = vg
    return op, op_value_and_grad
