"""Pallas TPU kernel: fused logistic log-likelihood value + gradient.

The hierarchical-logistic hot loop evaluates, per leapfrog step,
``ll = Σ_i [y_i·logσ(x_i·β) + (1−y_i)·logσ(−x_i·β)]`` and its gradient
``∇_β ll = Xᵀ(y − σ(Xβ))``.  Under autodiff that is a forward pass plus a
backward pass — the (N, D) row matrix is read from HBM twice.  At benchmark
scale (N=1M) the op is HBM-bandwidth-bound, so this kernel computes value
and gradient in ONE pass over X.

Layout: the kernel takes X TRANSPOSED — ``xT`` of shape (D, N) — so the
million-row axis rides the 128-wide TPU *lane* dimension in full native
(8, 128) tiles and features ride the sublane axis.  Row-major (N, D)
blocks at small D (the benchmark has D=32) fill only D of 128 lanes, which
measured ~4x slower than XLA's own matvec; transposing recovers full-width
streaming.  Models produce ``xT`` once per run via ``Model.prepare_data``
(a transpose outside the compiled loop), so the hot path never pays a
layout change.  ``y`` shares that promise where the model keeps it:
`FusedLogistic.prepare_data` also makes the kernel's (1, N) float32
outcome operand once, and `_y_operand` hands a rank-2 ``y`` through
untouched; a rank-1 ``y`` (every other caller) is cast and reshaped at the
call, which is free only where N divides by 1024 (`_y_operand`).

Each grid step handles one (D, LANE_TILE) slab and writes its OWN
partial-sum rows (no cross-step accumulation: Mosaic rejects
read-modify-write on revisited output blocks in kernels that also have a
per-tile output — "only constant accumulators supported" — and scalar
stores to VMEM).  The (grid,)-length partials are reduced outside, in XLA:
a (grid, D) sum is sub-microsecond next to the (D, N) stream.  The ragged
last tile is masked in-kernel from the static row count with
``jnp.where`` selects (NOT multiplies — 0·NaN = NaN; out-of-bounds lanes
read unspecified values).

Two kernels.  The chain-batched one (`_make_batched_kernel`,
``stark_logistic_ll``: what every vmapped ensemble, and so every benchmark
cell of this model, runs) holds the (C, D) block of all chains' beta and
does two MXU dots a tile: logits (C, D) x (D, TILE), then the gradient
(C, TILE) x (TILE, D); one X pass serves all chains.  At ``highest``
(`batched_mxu_form`) it forms that precision's six bf16 products itself,
rounding to nearest, and packs them into the rows six short passes leave
idle (`ops.precision.split6_operands`): the forward one bf16 dot over a
6·D-row contraction, the backward one of the residual's three splits
against the slab's three, the six products added in f32.  Compiled for a
described v5e at C = 8, D = 32, TILE 8192 a tile went from 2 920 bundles
(MXU 52.6 %, VALU 47.7 %, stores 46.9 %) to 1 943 (50.2 / 75.1 / 28.9 %),
under Mosaic's default scoped-VMEM limit (PERF.md §5).  At ``high`` and
``default`` the two dots stay f32 at that precision.
The one-chain kernel (`_make_kernel`, ``stark_logistic_ll_1chain``) does
its matvec on the VPU (multiply + sublane/lane reductions): with one
chain the work is bandwidth-bound, so the MXU buys nothing, and Mosaic
pattern-matches dot_general+add into a matmul-with-accumulator it cannot
compile for a non-constant accumulator (the per-row offset; adding the
offset AFTER a complete dot, as the batched kernel does, lowers fine).

The link (`_link_parts`, shared with `ops/hier_fused.py`'s grouped
kernel) is where the tile's vector and transcendental slots go once the
dots are issued, so it is written for the units, not from the library:
the value term is ``y·z − max(z, 0) − log1p(exp(−|z|))`` (the identity
``logσ(z) − logσ(−z) = z`` folds the two log-sigmoids into one) and the
residual ``y − 1/(1 + exp(−z))``: two ``exp``, one ``log1p`` and one
reciprocal an element, not three, two and one.

On the CPU backend, which has no Mosaic, the kernels run under the Pallas
interpreter (`_resolve_interpret`): that keeps the tests and the
virtual-device mesh runnable without a TPU; the numerics match autodiff to
float32 tolerance (see tests/test_ops_fused.py).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .precision import (
    dot_precision,
    precision_statics,
    split6_backward as _split6_backward,
    split6_forward as _split6_forward,
    split6_operands as _split6_operands,
    split6_rows as _split6_rows,
    stream_arg,
    x_stream_dtype,
)

# The precision/knob machinery lives in ops/precision.py (shared by every
# fused op); these aliases keep this module's historical private names —
# the jit cache keys are the RESOLVED values the functions return, so the
# move is bit-identical and the retrace-on-knob-toggle behavior (ADVICE
# r5) is unchanged.
_dot_precision = dot_precision
_x_stream_dtype = x_stream_dtype
_stream_arg = stream_arg

# Default lane-tile cap; the actual tile shrinks with D so the (D, LT) f32
# slab stays within a fixed VMEM budget (see _default_lane_tile).
_LANE_TILE = 8192
# ~2MB per input slab leaves room for double buffering + the small
# y/offset/resid streams in ~16MB of VMEM at any feature count.
_SLAB_BUDGET_ELEMS = (2 * 1024 * 1024) // 4


def _default_lane_tile(d: int) -> int:
    """Largest 128-multiple lane tile whose (d, tile) slab fits the budget."""
    return max(128, min(_LANE_TILE, (_SLAB_BUDGET_ELEMS // max(d, 1)) // 128 * 128))


def _resolve_interpret(interpret: Optional[bool]) -> bool:
    """The ONE place Pallas interpret mode is chosen, for every kernel in
    ops/.  ``None`` (every production call site) means compiled through
    Mosaic, except where the process's default backend is the CPU, which
    has no Mosaic: the tests, the virtual-device mesh and
    ``chip_smoke.py --dry-run``.  A process meant for the chip whose jax
    dropped to the CPU would land here too and interpret without a word,
    so an entry point that must run on the chip asserts the platform
    first and checks its compiled text for a ``tpu_custom_call``
    (chip_smoke.py does both)."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return interpret


def _link_parts(link, y, logits, mask):
    """Per-link elementwise math shared by both tile kernels.

    Returns (val_terms, resid): ``val_terms`` summed into the kernel's
    value output, ``resid`` the per-row quantity whose X-weighted sum is
    the beta-gradient direction.
      bernoulli_logit: val = log-lik terms,    resid = y - sigmoid(logits)
      gaussian:        val = (y - mu)^2 (SSR), resid = y - mu
    (the gaussian value/gradient are SCALE-FREE: the caller applies
    1/sigma^2 outside, so sigma never enters the kernel)

    The Bernoulli link spends two ``exp``, one ``log1p`` and one
    reciprocal an element where ``y·log_sigmoid(z) + (1−y)·log_sigmoid(−z)``
    and ``y − sigmoid(z)`` from the library spent three, two and one, with
    two ``logaddexp`` expansions' selects and NaN guards in the vector
    slots (PERF.md §5/§6, PR 31):
      - value: logsig(z) − logsig(−z) = z, so y·logsig(z) +
        (1−y)·logsig(−z) = y·z + logsig(−z) = y·z − max(z, 0) −
        log1p(exp(−|z|)) for every real y.  ``log1p(e)``, not
        ``log(1 + e)``: it keeps the tail (e < 6e-8 past |z| = 16.6).
      - residual: y − 1/(1 + exp(−z)), the sigmoid with an ``exp`` of
        its own.  Sharing the value's e = exp(−|z|) (σ(z) = e·σ(|z|) for
        z < 0) saves it and was measured (PERF.md §6, PR 31): the chip's
        ``exp`` is a microrelative off, one way; through exp(−z) that
        moves σ the same way on both sides of 0, as a shifted intercept
        would, but through exp(−|z|) it moves σ away from ½ on both
        sides, as stretched logits would, and that adds up along Xβ over
        the rows: the gradient's gap to the reference rose thirtyfold at
        20M rows, for no time gained.
    A non-finite logit gives a non-finite value term; exp(−z) overflowing
    to inf gives σ = 0, the right limit.
    """
    if link == "bernoulli_logit":
        e = jnp.exp(-jnp.abs(logits))
        ll = y * logits - jnp.maximum(logits, 0.0) - jnp.log1p(e)
        sig = 1.0 / (1.0 + jnp.exp(-logits))
        resid = jnp.where(mask, y - sig, 0.0)
        return jnp.where(mask, ll, 0.0), resid
    if link == "gaussian":
        resid = jnp.where(mask, y - logits, 0.0)
        return resid * resid, resid
    raise ValueError(f"unknown link {link!r}")


def _sum_tiles(partials, center):
    """The tiles' partial values (grid, ...) added up, less ``center`` when
    one is given: each tile's share of it comes off before the tiles are
    added.  A log-likelihood over tens of millions of rows is a float32
    near 1e7 whose last bit is a whole nat, coarser than the energy
    differences an accept step lives on; the tiles' partials (thousands,
    last bit 1e-4) still hold those differences, and taking a constant
    close to the total off them tile by tile keeps them: the sum comes
    out as a small number, the same constant away from the plain sum at
    every position."""
    if center is None:
        return jnp.sum(partials, axis=0)
    return jnp.sum(partials - center / partials.shape[0], axis=0)


def _y_operand(y, n):
    """The kernel's ``y`` operand, (1, n) float32.  A rank-2 ``y`` is that
    operand already (`FusedLogistic.prepare_data` lays it out once a run)
    and goes to ``pallas_call`` as it is: no operation stands between a
    sampling loop's carry and the custom call.  A rank-1 ``y`` is cast and
    given its leading axis here, inside whatever loop the call sits in:
    free where n divides by 1024 (a bitcast), a chunked copy of the whole
    vector before every call where it does not (the loop's ``f32[n]``
    carry is tiled T(1024), the operand T(1,128), and the two pad
    differently)."""
    if y.ndim == 2:
        if y.shape != (1, n) or y.dtype != jnp.float32:
            raise ValueError(
                f"a rank-2 y is the kernel's operand as it stands and must "
                f"be float32 of shape (1, {n}), got {y.dtype}{y.shape}"
            )
        return y
    return y.astype(jnp.float32)[None, :]


def _make_kernel(n, lane_tile, with_offset, link):
    """Tile kernel for a dataset of ``n`` rows (static)."""

    def kernel(*refs):
        if with_offset:
            xt_ref, y_ref, off_ref, beta_ref, val_ref, grad_ref, resid_ref = refs
        else:
            xt_ref, y_ref, beta_ref, val_ref, grad_ref = refs
            off_ref = resid_ref = None
        lane0 = pl.program_id(0) * lane_tile
        iota = jax.lax.broadcasted_iota(jnp.int32, (1, lane_tile), 1)
        mask = lane0 + iota < n  # (1, TILE) — False on ragged-tile overhang
        xt = jnp.where(mask, xt_ref[...].astype(jnp.float32), 0.0)  # (D, TILE)
        y = jnp.where(mask, y_ref[...], 0.0)  # (1, TILE)
        beta = beta_ref[...]  # (D, 1)
        logits = jnp.sum(xt * beta, axis=0, keepdims=True)  # (1, TILE)
        if off_ref is not None:
            logits = logits + jnp.where(mask, off_ref[...], 0.0)
        val_terms, resid = _link_parts(link, y, logits, mask)
        # partial-sum rows shaped (1, 1, ·)/(1, D, 1) to satisfy TPU tiling
        # (block last-two dims must equal the array's when not (8, 128)-aligned)
        val_ref[...] = jnp.sum(val_terms).reshape(1, 1, 1)
        if resid_ref is not None:
            resid_ref[...] = resid
        grad_ref[...] = jnp.sum(xt * resid, axis=1, keepdims=True)[None]  # (1, D, 1)

    return kernel


def batched_mxu_form(d: int):
    """(form, rows): how the chain-batched kernel contracts a tile at the
    resolved `dot_precision`.  At ``highest``, ``"split6"``: the six bf16
    products formed by the kernel and packed into one contraction of
    `split6_rows` (6·D, 192 at D = 32; `ops.precision`); otherwise
    ``"lax"``: one f32 dot over D at that precision.  The kernel builds
    its operands from this, and `FusedLogistic.prepare_data` notes it."""
    if _dot_precision() == jax.lax.Precision.HIGHEST:
        return "split6", _split6_rows(d, 0)
    return "lax", d


def _make_batched_kernel(n, lane_tile, with_offset, link):
    """Chain-batched tile kernel: one X slab read serves ALL chains.

    Per-chain evaluation under ``vmap`` re-streams the (D, N) row matrix
    from HBM once per chain — at 1M rows that stream IS the whole cost
    (measured ~11 ms/grad for 8 chains ≈ 8x the single-chain time).  Here
    the (C, D) beta block rides along and the logits become one
    (C, D) x (D, TILE) matmul on the MXU, so arithmetic intensity scales
    with C while the HBM traffic stays ~one X pass.
    """

    def kernel(*refs):
        if with_offset:
            xt_ref, y_ref, off_ref, beta_ref, val_ref, grad_ref, resid_ref = refs
        else:
            xt_ref, y_ref, beta_ref, val_ref, grad_ref = refs
            off_ref = resid_ref = None
        lane0 = pl.program_id(0) * lane_tile
        iota = jax.lax.broadcasted_iota(jnp.int32, (1, lane_tile), 1)
        mask = lane0 + iota < n  # (1, TILE)
        xt = jnp.where(mask, xt_ref[...].astype(jnp.float32), 0.0)  # (D, TILE)
        y = jnp.where(mask, y_ref[...], 0.0)  # (1, TILE)
        beta = beta_ref[...]  # (C, D)
        # explicit precision (HIGHEST unless STARK_FUSED_PRECISION says
        # otherwise): never depend on the global matmul-precision default
        # — bf16 input truncation here would silently give the batched
        # path different numerics than the single-chain VPU path.
        # (The add of a non-constant offset AFTER a complete dot lowers
        # fine on Mosaic — verified on-chip; the header's accumulator
        # caveat applies to accumulating INTO the dot.)
        d = xt.shape[0]
        form, rows = batched_mxu_form(d)
        if form == "split6":
            # `highest`'s six bf16 products, packed: the forward's
            # contraction holds all six (rows deep), the backward streams
            # the residual's three splits against the slab's three
            fwd, bwd = _split6_operands(xt)
            assert fwd.shape[0] == rows, (fwd.shape, rows)
            logits = _split6_forward(beta, None, fwd)  # (C, TILE)
        else:
            prec = _dot_precision()
            logits = jax.lax.dot(
                beta, xt, precision=prec,
                preferred_element_type=jnp.float32,
            )  # (C, TILE) — MXU
        if off_ref is not None:
            logits = logits + jnp.where(mask, off_ref[...], 0.0)  # (C, TILE)
        val_terms, resid = _link_parts(link, y, logits, mask)  # (C, TILE)
        val_ref[...] = jnp.sum(val_terms, axis=1)[None, :, None]
        if resid_ref is not None:
            resid_ref[...] = resid
        # (C, TILE) x (TILE, D) -> (C, D) — second MXU pass, in-VMEM
        if form == "split6":
            grad, _ = _split6_backward(resid, bwd, d)
        else:
            grad = jax.lax.dot(
                resid, xt.T, precision=prec,
                preferred_element_type=jnp.float32,
            )
        grad_ref[...] = grad[None]

    return kernel


def _batched_call(beta, xt, y, offsets, *, lane_tile, interpret,
                  link="bernoulli_logit", center=None):
    """Chain-batched fused pass.

    beta: (C, D); y: (N,), or (1, N) float32 (`_y_operand`); offsets:
    (C, N) or None -> (val (C,), grad (C, D) [, resid (C, N)]).  C is
    padded to a sublane multiple of 8 for Mosaic tiling; padded rows are
    discarded on return.  ``center`` (a scalar, or (C,): a constant a
    chain): val comes back less it (`_sum_tiles`).
    """
    interpret = _resolve_interpret(interpret)
    c, d = beta.shape
    n = xt.shape[1]
    cpad = -(-c // 8) * 8
    if cpad != c:
        beta = jnp.pad(beta, ((0, cpad - c), (0, 0)))
        if offsets is not None:
            offsets = jnp.pad(offsets, ((0, cpad - c), (0, 0)))
    if lane_tile is None:
        # (D + 2C + 1)-row slabs must fit the same VMEM budget
        lane_tile = _default_lane_tile(d + 2 * cpad + 1)
    grid = -(-n // lane_tile)

    def lane_spec(height=1):
        return pl.BlockSpec((height, lane_tile), lambda i: (0, i))

    args = [_stream_arg(xt), _y_operand(y, n)]
    in_specs = [lane_spec(d), lane_spec()]
    if offsets is not None:
        args.append(offsets.astype(jnp.float32))
        in_specs.append(lane_spec(cpad))
    args.append(beta.astype(jnp.float32))
    in_specs.append(pl.BlockSpec((cpad, d), lambda i: (0, 0)))

    out_specs = [
        pl.BlockSpec((1, cpad, 1), lambda i: (i, 0, 0)),
        pl.BlockSpec((1, cpad, d), lambda i: (i, 0, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((grid, cpad, 1), jnp.float32),
        jax.ShapeDtypeStruct((grid, cpad, d), jnp.float32),
    ]
    if offsets is not None:
        out_specs.append(lane_spec(cpad))
        out_shape.append(
            jax.ShapeDtypeStruct((cpad, grid * lane_tile), jnp.float32)
        )

    out = pl.pallas_call(
        _make_batched_kernel(n, lane_tile, offsets is not None, link),
        grid=(grid,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        name="stark_logistic_ll",
    )(*args)
    if center is not None and jnp.ndim(center):  # beside the tiles' (C, 1)
        center = jnp.pad(center.astype(jnp.float32), (0, cpad - c))[:, None]
    val = _sum_tiles(out[0], center)[:c, 0]
    grad = jnp.sum(out[1], axis=0)[:c]
    if offsets is not None:
        return val, grad, out[2][:c, :n]
    return val, grad


def _fused_call(beta, xt, y, offsets, *, lane_tile, interpret,
                link="bernoulli_logit", center=None):
    """Build specs and invoke the tile kernel.

    ``y``: (N,), or (1, N) float32 (`_y_operand`).
    -> (val scalar, X-weighted resid (D,)), plus the (N,) per-row
    residual when ``offsets`` is given.  Semantics are link-dependent
    (see _link_parts): for bernoulli_logit val IS the log-lik and the
    (D,) output its beta-gradient; for gaussian val is the SSR and the
    outputs are SCALE-FREE — the caller applies the 1/sigma^2 factors.
    """
    interpret = _resolve_interpret(interpret)
    d, n = xt.shape
    if lane_tile is None:
        lane_tile = _default_lane_tile(d)
    grid = -(-n // lane_tile)  # cdiv: ragged last tile masked in-kernel

    def lane_spec(height=1):
        return pl.BlockSpec((height, lane_tile), lambda i: (0, i))

    args = [_stream_arg(xt), _y_operand(y, n)]
    in_specs = [lane_spec(d), lane_spec()]
    if offsets is not None:
        args.append(offsets.astype(jnp.float32)[None, :])
        in_specs.append(lane_spec())
    args.append(beta.astype(jnp.float32)[:, None])
    in_specs.append(pl.BlockSpec((d, 1), lambda i: (0, 0)))

    # one partial-sum row per grid step; reduced in XLA below
    out_specs = [
        pl.BlockSpec((1, 1, 1), lambda i: (i, 0, 0)),
        pl.BlockSpec((1, d, 1), lambda i: (i, 0, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((grid, 1, 1), jnp.float32),
        jax.ShapeDtypeStruct((grid, d, 1), jnp.float32),
    ]
    if offsets is not None:
        # allocated at the padded lane count so the ragged tile's store stays
        # in-bounds; sliced back to n below (an output buffer, not a copy of
        # any input)
        out_specs.append(lane_spec())
        out_shape.append(jax.ShapeDtypeStruct((1, grid * lane_tile), jnp.float32))

    out = pl.pallas_call(
        _make_kernel(n, lane_tile, offsets is not None, link),
        grid=(grid,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        name="stark_logistic_ll_1chain",
    )(*args)
    grad = jnp.sum(out[1], axis=0)[:, 0]
    val = (
        jnp.sum(out[0]) if center is None
        else _sum_tiles(out[0], center)[0, 0]
    )
    if offsets is not None:
        return val, grad, out[2][0, :n]
    return val, grad


# --- custom_vmap entry points: chains batch INSIDE the kernel ----------
# The drivers evaluate the potential per chain under vmap; without a
# batching rule each chain re-streams X from HBM (pallas_call's default
# vmap adds a batch grid axis).  These rules reroute a chain-batched call
# to _batched_call: one X pass for the whole ensemble.


def _bcast(x, batched, axis_size):
    return x if batched else jnp.broadcast_to(x[None], (axis_size,) + x.shape)


def _make_vg_noff(link):
    """No-offset fused op with the chain-batching rule, per link.
    ``center``: None, or the scalar the value comes back less
    (`_sum_tiles`)."""

    @jax.custom_batching.custom_vmap
    def vg_noff(beta, xt, y, center):
        return _fused_call(
            beta, xt, y, None, lane_tile=None, interpret=None, link=link,
            center=center,
        )

    @vg_noff.def_vmap
    def _vmap_rule(axis_size, in_batched, beta, xt, y, center):
        beta_b, xt_b, y_b, _ = in_batched
        if xt_b or y_b:  # batched data: nothing to share
            out = jax.lax.map(
                lambda a: vg_noff(*a),
                tuple(
                    v if v is None else _bcast(v, b, axis_size)
                    for v, b in zip((beta, xt, y, center), in_batched)
                ),
            )
            return out, (True, True)
        beta = _bcast(beta, beta_b, axis_size)
        # a batched ``center`` is a constant a chain: still one X pass
        return (
            _batched_call(
                beta, xt, y, None, lane_tile=None, interpret=None, link=link,
                center=center,
            ),
            (True, True),
        )

    return vg_noff


_vg_noff = _make_vg_noff("bernoulli_logit")


def _make_vg_off(link):
    """Offset-taking fused op with the chain-batching rule, per link —
    one body so the batching logic cannot drift between links."""

    @jax.custom_batching.custom_vmap
    def vg_off(beta, offsets, xt, y):
        return _fused_call(
            beta, xt, y, offsets, lane_tile=None, interpret=None, link=link
        )

    @vg_off.def_vmap
    def _vmap_rule(axis_size, in_batched, beta, offsets, xt, y):
        beta_b, off_b, xt_b, y_b = in_batched
        if xt_b or y_b:
            out = jax.lax.map(
                lambda a: vg_off(*a),
                tuple(
                    _bcast(v, b, axis_size)
                    for v, b in zip((beta, offsets, xt, y), in_batched)
                ),
            )
            return out, (True, True, True)
        beta = _bcast(beta, beta_b, axis_size)
        offsets = _bcast(offsets, off_b, axis_size)
        return (
            _batched_call(
                beta, xt, y, offsets, lane_tile=None, interpret=None,
                link=link,
            ),
            (True, True, True),
        )

    return vg_off


_vg_off = _make_vg_off("bernoulli_logit")


@functools.partial(
    jax.jit,
    static_argnames=("lane_tile", "interpret", "_precision", "_x_dtype"),
)
def _loglik_vg_jit(beta, xt, y, *, lane_tile, interpret, _precision,
                   _x_dtype):
    # _precision/_x_dtype are cache-key-only statics: _fused_call re-reads
    # the STARK_FUSED_PRECISION / STARK_FUSED_X_DTYPE knobs at trace time,
    # so keying the executable on the resolved values is what forces a
    # retrace when a knob changes mid-process (ADVICE r5: a module-level
    # jit otherwise reuses the stale executable for same-shape calls,
    # silently violating the "numerics never change silently" contract)
    del _precision, _x_dtype
    return _fused_call(beta, xt, y, None, lane_tile=lane_tile,
                       interpret=interpret)


def logistic_loglik_value_and_grad(
    beta: jax.Array,
    xt: jax.Array,
    y: jax.Array,
    *,
    lane_tile: Optional[int] = None,
    interpret: Optional[bool] = None,
):
    """-> (ll scalar, dll/dbeta (D,)) in one pass over xt.

    beta: (D,), xt: (D, N) float32 — X TRANSPOSED — y: (N,) in {0, 1}.
    """
    return _loglik_vg_jit(
        beta, xt, y, lane_tile=lane_tile, interpret=interpret,
        **precision_statics(),
    )


@jax.custom_vjp
def logistic_offset_loglik(beta, offsets, xt, y):
    """Differentiable fused op: Bernoulli-logit log-lik of Xβ + offsets.

    ``xt`` is X transposed, (D, N).  One Pallas pass computes the value,
    ∂/∂β, and the per-row residual; the VJP is therefore free of any
    further pass over X.  ∂/∂offsets is the residual vector, which XLA
    chains through whatever produced the offsets (e.g. an ``alpha[g]``
    gather → segment-sum, handled by autodiff outside).  Under ``vmap``
    over chains the whole ensemble shares ONE X pass (`_vg_off`'s
    batching rule).
    """
    val, _, _ = _vg_off(beta, offsets, xt, y)
    return val


def _off_fwd(beta, offsets, xt, y):
    val, gbeta, resid = _vg_off(beta, offsets, xt, y)
    return val, (gbeta, resid)


def _off_bwd(res, ct):
    gbeta, resid = res
    return ct * gbeta, ct * resid, None, None


logistic_offset_loglik.defvjp(_off_fwd, _off_bwd)


@jax.custom_vjp
def logistic_loglik(beta, xt, y, center=None):
    """Differentiable fused op: Bernoulli-logit log-lik of Xβ (no offset).

    ``xt`` is X transposed, (D, N); ``y`` is (N,), or the (1, N) float32
    operand a model laid out in ``prepare_data`` (`_y_operand`).  One
    Pallas pass yields both the value and ∂/∂β, so the VJP never re-reads
    X and — unlike routing through ``logistic_offset_loglik`` with a zeros
    offset — no (N,) offset input is streamed in and no (N,) residual
    output is written back per evaluation.

    ``center``: a scalar close to the log-lik where the chains are; the
    value comes back less it, taken off tile by tile (`_sum_tiles`), so
    that it keeps float32's resolution however many rows there are.  A
    constant of the program: it gets no cotangent.
    """
    val, _ = _vg_noff(beta, xt, y, center)
    return val


def _noff_fwd(beta, xt, y, center):
    val, gbeta = _vg_noff(beta, xt, y, center)
    return val, gbeta


def _noff_bwd(gbeta, ct):
    return ct * gbeta, None, None, None


logistic_loglik.defvjp(_noff_fwd, _noff_bwd)


# --- gaussian link: fused SSR + gradient direction in one X pass --------
# The kernel is SCALE-FREE (sigma never enters): it returns the sum of
# squared residuals, X·resid, and the residual vector; the normal
# log-density and every gradient are assembled outside from those three,
# so the same one-pass kernel serves any noise scale (and its sigma
# gradient comes from the already-computed SSR).


_vg_gauss_off = _make_vg_off("gaussian")
_vg_gauss_noff = _make_vg_noff("gaussian")

_LOG_2PI = 1.8378770664093453


@jax.custom_vjp
def gaussian_offset_loglik(beta, offsets, xt, y, sigma):
    """Fused normal log-lik of y ~ N(Xβ + offsets, sigma) in one X pass.

    ``xt`` is X transposed, (D, N); offsets (N,) carries everything that
    is not Xβ (intercept, gathered random effects, ...), so ∂/∂offsets —
    the residual/sigma² — chains through whatever produced them in XLA.
    Under ``vmap`` over chains the whole ensemble shares ONE X pass
    (`_vg_gauss_off`'s batching rule).
    """
    ssr, _, _ = _vg_gauss_off(beta, offsets, xt, y)
    n = y.shape[-1]
    return -0.5 * ssr / sigma**2 - n * jnp.log(sigma) - 0.5 * n * _LOG_2PI


def _gauss_fwd(beta, offsets, xt, y, sigma):
    ssr, xresid, resid = _vg_gauss_off(beta, offsets, xt, y)
    n = y.shape[-1]
    val = -0.5 * ssr / sigma**2 - n * jnp.log(sigma) - 0.5 * n * _LOG_2PI
    return val, (xresid, resid, ssr, sigma)


def _gauss_bwd(res, ct):
    xresid, resid, ssr, sigma = res
    n = resid.shape[-1]
    inv2 = 1.0 / (sigma * sigma)
    return (
        ct * inv2 * xresid,
        ct * inv2 * resid,
        None,
        None,
        ct * (ssr * inv2 / sigma - n / sigma),
    )


gaussian_offset_loglik.defvjp(_gauss_fwd, _gauss_bwd)


@jax.custom_vjp
def gaussian_loglik(beta, xt, y, sigma):
    """Fused normal log-lik of y ~ N(Xβ, sigma), no offsets.

    Like `logistic_loglik` vs its offset variant: no (N,) offset stream
    in and no (N,) residual written back per evaluation — only the SSR
    and X·resid leave the kernel.
    """
    ssr, _ = _vg_gauss_noff(beta, xt, y, None)
    n = y.shape[-1]
    return -0.5 * ssr / sigma**2 - n * jnp.log(sigma) - 0.5 * n * _LOG_2PI


def _gauss_noff_fwd(beta, xt, y, sigma):
    ssr, xresid = _vg_gauss_noff(beta, xt, y, None)
    n = y.shape[-1]
    val = -0.5 * ssr / sigma**2 - n * jnp.log(sigma) - 0.5 * n * _LOG_2PI
    return val, (xresid, ssr, sigma, jnp.asarray(float(n), jnp.float32))


def _gauss_noff_bwd(res, ct):
    xresid, ssr, sigma, n = res
    inv2 = 1.0 / (sigma * sigma)
    return (
        ct * inv2 * xresid,
        None,
        None,
        ct * (ssr * inv2 / sigma - n / sigma),
    )


gaussian_loglik.defvjp(_gauss_noff_fwd, _gauss_noff_bwd)
