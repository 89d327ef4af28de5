"""Pallas TPU kernel: hierarchical logistic log-lik with IN-KERNEL groups.

The offset-path hierarchical likelihood (`logistic_offset_loglik`) leaves
the group-intercept machinery to XLA: per gradient evaluation it gathers
``alpha[g]`` into a (C, N) offsets array, streams it into the kernel,
streams a (C, N) residual back out, and segment-sums the residual into
(C, G).  Builder-measured before PR 1 on one v5e chip at the flagship
shape (N=1M, C=32), not in the driver's ledger: the Pallas kernel itself
ran 1.16 ms but the full potential gradient cost 19.3 ms — the XLA gather
(11.9 ms), segment-sum scatter (16.6 ms), and the (C, N) intermediate
streams all crawled at ~10 GB/s, an order of magnitude under the ~330 GB/s
the offset kernel streamed at.

This kernel removes every (C, N) intermediate.  Rows are PRE-SORTED by
group (a one-time host-side permutation in ``prepare_data`` — the
log-likelihood is a sum, so the posterior is row-order invariant), which
makes group membership *locally dense*: one (D, LANE_TILE) slab of X
spans only a handful of consecutive groups.  Per tile the kernel
  - builds a (K_LOC, TILE) one-hot of the LOCAL group ids (iota compare
    — K_LOC is the padded max groups-per-tile, static from the layout)
    and stacks it under the X slab: one (D + K_LOC, TILE) operand,
  - computes the logits X.beta + alpha[g] as ONE MXU dot of the tile's
    parameters [beta | alpha window] (C, D + K_LOC) with that operand (no
    (C, N) gather, no offsets stream),
  - reduces both gradients as ONE dot of the residual with the operand's
    transpose: the first D columns are the beta partial, the last K_LOC
    the group-gradient partials (no (C, N) residual write, no scatter
    over 1M indices).
Outside, the (grid, C, K_LOC) partials scatter-add into (C, G) over
grid*K_LOC ≈ 2k windowed indices — thousands of elements, not millions.
HBM traffic per evaluation drops from ~644 MB (C=32) to ~136 MB, nearly
all of it the unavoidable X stream.

Why two dots, and why the kernel forms the bf16 products itself: a matmul
instruction costs the same whether it uses 8 or 128 of the MXU's rows, and
at ``highest`` an f32 dot is six single-pass bf16 products of its
operands' three-way splits.  With the K_LOC window contracted on its own
(four dots a tile) the kernel ran 36.6 ms a call at N=16M, C=64, D=32,
K_LOC=8 on one v5e, 7.26 % of its HBM roofline (PERF_LEDGER.jsonl, PR 26,
`hier_n16m.sample`); with the window folded into the slab (two f32 dots,
six passes each over 40 of 128 contraction rows) 16.66 ms and 15.9 %
(ledger, PR 40), 12 036 bundles a tile of which the MXU filled 97.9 %.
At ``highest`` (`grouped_mxu_form`) the kernel now splits the operands
itself, rounding to nearest, and packs the six products into rows the
passes left idle (`ops.precision.split6_operands`): the forward is one
bf16 dot over a 216-row contraction, the backward one over the three
residual splits stacked on the streamed axis, the six products added in
f32.  8 089 bundles a tile at C = 64 (VALU-bound, 92.0 %) and 2 302 at
C = 8, against 3 587 (my compiles for a described v5e, PR 41, by the
README's recipe); on the chip one value-and-gradient of the op went from
17.13 to 11.25 ms at C = 64 and from 5.41 to 3.91 at C = 8, and the
beta-gradient of the cell's rows came seven times nearer float64 than
the compiler's truncating passes put it (my chip runs, PR 41: PERF.md
§6).  At ``high`` and ``default`` the two dots stay f32 at that
precision.

Capability parity: same posterior as `HierLogistic`/`FusedHierLogistic`
(BASELINE.json:8 flagship config); reference tree absent (SURVEY.md §0),
design original.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import telemetry
from .logistic_fused import (
    _LOG_2PI,
    _default_lane_tile,
    _link_parts,
    _resolve_interpret,
    _sum_tiles,
)
from .precision import (
    dot_precision as _dot_precision,
    split6_backward as _split6_backward,
    split6_forward as _split6_forward,
    split6_operands as _split6_operands,
    split6_rows as _split6_rows,
    stream_arg as _stream_arg,
    x_stream_dtype as _x_stream_dtype,
)

# Hard cap on the padded groups-per-tile: above this the one-hot slab and
# the MXU extra work stop being negligible next to the X stream, and the
# layout falls back to the offset path.
_K_LOC_MAX = 128

# What the Bernoulli kernel may ask of the core's scoped VMEM (128 MiB on a
# v5e; Mosaic's default limit is 16 MB).  Compiled for a described v5e at
# TILE 8192, D = 32, K_LOC = 8 it takes 6.7 MiB at C = 64 and 8.9 at
# C = 128 with the packed splits, 9.9 and 11.8 with six passes a dot (my
# compiles, PR 41): `_check_chain_vmem`'s estimate is the looser, and half
# this limit is its budget.
_HIER_VMEM_LIMIT = 48 * 1024 * 1024


def grouped_lane_tile(d: int) -> int:
    """Default (largest) lane tile for the grouped kernel."""
    return _default_lane_tile(d + 2)


def grouped_layout(g_sorted: np.ndarray, d: int):
    """Host-side layout from SORTED group ids.

    Returns (lane_tile, k_loc, first_gid (grid,) int32, gl (N,) int32)
    or None when no tile size keeps the group window within _K_LOC_MAX.
    Dense groupings (few rows per group, e.g. ``configs/lmm.yaml``'s 10k
    groups over 100k rows: tile 1024, window 104) get a SMALLER lane tile
    so each tile still spans few groups — the one-hot stays cheap and the
    window static; the on-chip benchmark's shapes (thousands of rows a
    group) keep the full tile of 8192 and a window of 8.  The chosen
    lane_tile rides back to the kernel call in the data layout (shape-
    encoded), so prepare and call cannot disagree.
    """
    import os

    g_sorted = np.asarray(g_sorted)
    if g_sorted.ndim != 1 or np.any(np.diff(g_sorted) < 0):
        raise ValueError("grouped_layout requires sorted 1-D group ids")
    n = g_sorted.shape[0]
    lane_tile = grouped_lane_tile(d)
    # STARK_GROUPED_LANE_TILE caps the starting tile (128-multiple).  The
    # default tile is chosen from D alone — it cannot see the CHAIN count,
    # and a C=128 batch at tile 8192 trips the VMEM guard (~12.6 MB of
    # (C, TILE) intermediates) where tile 4096 would fit.  The cap lets a
    # large-C on-chip experiment halve the tile instead of being refused;
    # the chosen tile still rides back shape-encoded, so prepare and call
    # cannot disagree.
    env_tile = os.environ.get("STARK_GROUPED_LANE_TILE")
    if env_tile:
        cap = int(env_tile)
        if cap % 128 or cap < 256:
            raise ValueError(
                f"STARK_GROUPED_LANE_TILE={cap}: need a 128-multiple >= 256"
            )
        lane_tile = min(lane_tile, cap)
    # Floor at 256 ON PURPOSE: at tile 128 the window can never exceed
    # _K_LOC_MAX (span <= rows-per-tile), so every grouping would
    # "succeed" — including one-row-per-group degenerates where the
    # per-tile fixed cost over N/128 tiles cancels the fused win.  Below
    # 256 the offset path is the better kernel, so fall back to it.
    while lane_tile >= 256:
        # the tile MUST stay a multiple of 128: it is shape-encoded as
        # lane_tile // 128 dummies, so any remainder would silently
        # reconstruct a different tile than the layout was built for
        assert lane_tile % 128 == 0, lane_tile
        first_gid = g_sorted[::lane_tile].astype(np.int32)  # (grid,)
        grid = first_gid.shape[0]
        last = g_sorted[
            np.minimum(np.arange(1, grid + 1) * lane_tile - 1, n - 1)
        ]
        span = int(np.max(last - first_gid)) + 1
        k_loc = -(-span // 8) * 8  # sublane-pad
        if k_loc <= _K_LOC_MAX:
            gl = (
                g_sorted - np.repeat(first_gid, lane_tile)[:n]
            ).astype(np.int32)
            return lane_tile, k_loc, first_gid, gl
        lane_tile = (lane_tile // 2) // 128 * 128
    return None


def prepare_grouped(data, d_eff, transpose_keys=("x",)):
    """Shared grouped-layout packing for the Grouped models.

    Sorts every leaf by data['g'] (stable), transposes the design
    matrices named in ``transpose_keys`` to lane-major ``<k>T`` layout,
    and packs the layout as gl/first_gid plus the SHAPE-encoded
    k_loc/lt128 dummies — one copy of the encoding convention.  Returns
    None when `grouped_layout` finds no workable tile (caller falls back
    to the offset-path layout).
    """
    # the three legs of the host round trip, each a span under the
    # caller's `prepare_data`: rows down, the stable sort by group and the
    # re-ordered (transposed) copies, rows up
    with telemetry.span("prepare_data.to_host") as sp:
        host = {k: np.asarray(v) for k, v in data.items()}
        sp.note(bytes=sum(v.nbytes for v in host.values()))
    with telemetry.span("prepare_data.sort", rows=int(host["g"].shape[0])):
        g = host["g"]
        order = np.argsort(g, kind="stable")
        layout = grouped_layout(g[order], d_eff)
        if layout is None:
            return None
        lane_tile, k_loc, first_gid, gl = layout
        host = {
            k: v[order].T if k in transpose_keys else v[order]
            for k, v in host.items()
        }
    # on the caller's `prepare_data` span: what `grouped_layout` chose
    telemetry.note(lane_tile=lane_tile, k_loc=k_loc, tiles=len(first_gid))
    xdt = _x_stream_dtype()
    from .quantize import is_packed_dtype, pack_slab

    with telemetry.span("prepare_data.to_device") as sp:
        out = {
            k: jnp.asarray(v) for k, v in host.items()
            if k not in transpose_keys
        }
        for k in transpose_keys:
            slab = jnp.asarray(host[k])
            if is_packed_dtype(xdt):
                # per-column calibrated scales ride next to each packed
                # slab (ops/quantize.py); the models fold them into the
                # parameter operands (beta for xT, the u windows for zT),
                # so the kernel streams packed bytes untouched
                out[k + "T"], out[k + "T_scale"] = pack_slab(
                    slab.astype(jnp.float32), xdt
                )
            else:
                out[k + "T"] = slab.astype(xdt)
        jax.block_until_ready(out)
        sp.note(bytes=sum(int(v.nbytes) for v in out.values()))
    out["gl"] = jnp.asarray(gl)
    out["first_gid"] = jnp.asarray(first_gid)
    # static window size and lane tile ride in SHAPES (never values)
    out["k_loc"] = jnp.zeros((k_loc,), jnp.float32)
    out["lt128"] = jnp.zeros((lane_tile // 128,), jnp.float32)
    return out


def _check_chain_vmem(cpad, lane_tile, interpret, k_loc=0, q=1, slab_rows=0,
                      transposed=0, bf16_rows=0, budget=10 * 1024 * 1024):
    """The kernel holds ~3 (C, TILE) f32 intermediates (logits, resid,
    value terms) in scoped VMEM; past ~16 MB Mosaic refuses to compile
    (measured: C=128 at TILE=8192 asked for 20 MB).  The grouped kernels
    additionally hold a (K_LOC, TILE) one-hot plus its iota slab and the
    per-tile (C, Q*K_LOC) group window (ADVICE r3: a small-C /
    large-K_LOC config could OOM past the C-only estimate), and the
    hierarchical kernel a stacked copy of the design slab and the one-hot
    (``slab_rows`` = D + K_LOC, and D + Q*K_LOC for the Gaussian kernel's
    weighted one-hots).  ``bf16_rows`` counts the rows of bfloat16
    (·, TILE) operands: the packed slabs and the residual's three splits of
    the hierarchical kernel at ``highest`` (`grouped_mxu_form`).
    ``transposed`` counts the (TILE, k)
    operands a kernel transposes for a dot over the lanes: each is laid
    out in tiles of 128 lanes whatever k is (4 MB at TILE 8192).  The
    default ``budget`` is conservative (the OOM had >3 live (C, TILE)s);
    a call that raises Mosaic's limit passes its own.  Fail with an
    actionable message instead of the compiler OOM."""
    if interpret:
        return
    need = (
        3 * cpad * lane_tile * 4        # (C, TILE) logits/resid/val terms
        + 2 * k_loc * lane_tile * 4     # (K_LOC, TILE) one-hot + iota
        + slab_rows * lane_tile * 4     # (D + K_LOC, TILE) stacked slab
        + bf16_rows * lane_tile * 2     # bfloat16 (., TILE) operands
        + cpad * q * k_loc * 4          # (C, Q*K_LOC) group window block
        + transposed * lane_tile * 128 * 4  # (TILE, k) in 128-lane tiles
    )
    if need > budget:
        raise ValueError(
            f"chain batch C={cpad} at lane_tile={lane_tile} "
            f"(k_loc={k_loc}, q={q}) needs ~{need / 2**20:.1f} MB scoped "
            f"VMEM, more than the {budget / 2**20:.0f} MB the kernel may ask "
            f"of the TPU core with headroom; "
            f"reduce chains per device program, halve the tile with "
            f"STARK_GROUPED_LANE_TILE, or use the offset-layout Fused "
            f"model, whose lane tile shrinks with the chain count"
        )


def grouped_mxu_form(d: int, k_loc: int):
    """(form, rows): how the grouped Bernoulli kernel contracts a tile at
    the resolved `dot_precision`.  At ``highest``, ``"split6"``: the six
    bf16 products formed by the kernel and packed into one contraction of
    `split6_rows` (216 at D = 32, K_LOC = 8; `ops.precision`); otherwise
    ``"lax"``: one f32 dot over D + K_LOC at that precision.  The kernel
    builds its operands from this, and ``prepare_data`` notes it."""
    if _dot_precision() == jax.lax.Precision.HIGHEST:
        return "split6", _split6_rows(d, k_loc)
    return "lax", d + k_loc


def _make_grouped_kernel(n, lane_tile, k_loc, link):
    def kernel(xt_ref, y_ref, gl_ref, beta_ref, alpha_ref,
               val_ref, gbeta_ref, galpha_ref):
        prec = _dot_precision()  # STARK_FUSED_PRECISION (see logistic_fused)
        lane0 = pl.program_id(0) * lane_tile
        iota = jax.lax.broadcasted_iota(jnp.int32, (1, lane_tile), 1)
        mask = lane0 + iota < n  # (1, TILE)
        xt = jnp.where(mask, xt_ref[...].astype(jnp.float32), 0.0)  # (D, TILE)
        y = jnp.where(mask, y_ref[...], 0.0)  # (1, TILE)
        # local one-hot: gl is in [0, K_LOC) for every valid lane (layout
        # guarantee); masked/ragged lanes contribute nothing because their
        # resid and val terms are zeroed below
        gl = jnp.where(mask, gl_ref[...], 0)  # (1, TILE) int32
        krows = jax.lax.broadcasted_iota(jnp.int32, (k_loc, lane_tile), 0)
        onehot = jnp.where(krows == gl, 1.0, 0.0)  # (K_LOC, TILE)
        d = xt.shape[0]
        form, rows = grouped_mxu_form(d, k_loc)
        # the group window rides in the design slab: [beta | alpha window]
        # against [X ; one-hot] is X.beta + alpha[g] in one contraction,
        # summed in the MXU's f32 accumulator
        if form == "split6":
            # `highest`'s six bf16 products, packed: the forward's
            # contraction holds all six (rows deep), the backward streams
            # the residual's three splits against [X splits ; one-hot]
            fwd, bwd = _split6_operands(xt, onehot)
            assert fwd.shape[0] == rows, (fwd.shape, rows)
            logits = _split6_forward(beta_ref[...], alpha_ref[0], fwd)
        else:
            slab = jnp.concatenate([xt, onehot], axis=0)  # (D + K_LOC, TILE)
            params = jnp.concatenate(
                [beta_ref[...], alpha_ref[0]], axis=1
            )  # (C, D + K_LOC) — beta resident, this tile's group window
            logits = jax.lax.dot(
                params, slab, precision=prec,
                preferred_element_type=jnp.float32,
            )  # (C, TILE) — offsets never touch HBM
        val_terms, resid = _link_parts(link, y, logits, mask)  # (C, TILE)
        val_ref[...] = jnp.sum(val_terms, axis=1)[None, :, None]
        # [beta partial | group-gradient partials]
        if form == "split6":
            gbeta, galpha = _split6_backward(resid, bwd, d)
        else:
            grads = jax.lax.dot(
                resid, slab.T, precision=prec,
                preferred_element_type=jnp.float32,
            )  # (C, D + K_LOC)
            gbeta, galpha = grads[:, :d], grads[:, d:]
        gbeta_ref[...] = gbeta[None]  # (1, C, D)
        galpha_ref[...] = galpha[None]  # (1, C, K_LOC)

    return kernel


def _grouped_call(beta, alpha, xt, y, gl, first_gid, *, k_loc, lane_tile,
                  interpret, link="bernoulli_logit", center=None):
    """Chain-batched fused hierarchical pass.

    beta: (C, D), alpha: (C, G) -> (val (C,), gbeta (C, D),
    galpha (C, G)).  C pads to a sublane multiple of 8.  ``center`` (C,):
    a constant a chain; val comes back less it, taken off tile by tile
    (`logistic_fused._sum_tiles`).  Nothing here multiplies the tiles'
    totals by a scale of the position, so a constant is all a centre needs
    (the Gaussian kernel's `_gauss_loglik` needs more).
    """
    interpret = _resolve_interpret(interpret)
    c, d = beta.shape
    g_total = alpha.shape[1]
    n = xt.shape[1]
    grid = -(-n // lane_tile)
    cpad = -(-c // 8) * 8
    form, rows = grouped_mxu_form(d, k_loc)
    operands = (  # the packed slabs and the residual's splits, or the slab
        dict(bf16_rows=rows + 3 * d + k_loc + 3 * cpad, transposed=1)
        if form == "split6" else dict(slab_rows=d + k_loc)
    )
    _check_chain_vmem(cpad, lane_tile, interpret, k_loc=k_loc,
                      budget=_HIER_VMEM_LIMIT // 2, **operands)
    if cpad != c:
        beta = jnp.pad(beta, ((0, cpad - c), (0, 0)))
        alpha = jnp.pad(alpha, ((0, cpad - c), (0, 0)))
    # pad the group axis so every (first_gid, K_LOC) window is in-bounds
    alpha_pad = jnp.pad(alpha.astype(jnp.float32), ((0, 0), (0, k_loc)))
    # per-tile alpha windows: (grid, C, K_LOC).  A windowed gather of
    # grid*K_LOC*C elements — thousands, vs the (C, N) gather (millions)
    # this kernel exists to avoid
    win = first_gid[:, None] + jnp.arange(k_loc)[None, :]  # (grid, K_LOC)
    alpha_tiles = jnp.moveaxis(alpha_pad[:, win], 0, 1)  # (grid, C, K_LOC)

    def lane_spec(height=1):
        return pl.BlockSpec((height, lane_tile), lambda i: (0, i))

    args = [
        _stream_arg(xt),
        y.astype(jnp.float32)[None, :],
        gl.astype(jnp.int32)[None, :],
        beta.astype(jnp.float32),
        alpha_tiles,
    ]
    in_specs = [
        lane_spec(d),
        lane_spec(),
        lane_spec(),
        pl.BlockSpec((cpad, d), lambda i: (0, 0)),
        pl.BlockSpec((1, cpad, k_loc), lambda i: (i, 0, 0)),
    ]
    out_specs = [
        pl.BlockSpec((1, cpad, 1), lambda i: (i, 0, 0)),
        pl.BlockSpec((1, cpad, d), lambda i: (i, 0, 0)),
        pl.BlockSpec((1, cpad, k_loc), lambda i: (i, 0, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((grid, cpad, 1), jnp.float32),
        jax.ShapeDtypeStruct((grid, cpad, d), jnp.float32),
        jax.ShapeDtypeStruct((grid, cpad, k_loc), jnp.float32),
    ]
    out = pl.pallas_call(
        _make_grouped_kernel(n, lane_tile, k_loc, link),
        grid=(grid,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        name="stark_hier_ll_grouped",
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_HIER_VMEM_LIMIT
        ),
    )(*args)
    if center is not None:  # a row a chain, beside the tiles' (C, 1)
        center = jnp.pad(center.astype(jnp.float32), (0, cpad - c))[:, None]
    val = _sum_tiles(out[0], center)[:c, 0]
    gbeta = jnp.sum(out[1], axis=0)[:c]
    # windowed scatter-add of the per-tile partials: grid*K_LOC indices
    galpha = (
        jnp.zeros((cpad, g_total + k_loc), jnp.float32)
        .at[:, win.reshape(-1)]
        .add(out[2].transpose(1, 0, 2).reshape(cpad, -1))[:c, :g_total]
    )
    return val, gbeta, galpha


def _bcast(x, batched, axis_size):
    return x if batched else jnp.broadcast_to(x[None], (axis_size,) + x.shape)


@functools.partial(jax.custom_batching.custom_vmap)
def _vg_grouped(beta, alpha, xt, y, gl, first_gid, k_loc_arr, lt_arr, center):
    # k_loc and lane_tile ride as shape-encoded dummies so they stay
    # static through jit/vmap (lane_tile = 128 * lt_arr.shape[0]);
    # ``center``: None, or the scalar the value comes back less
    val, gbeta, galpha = _grouped_call(
        beta[None], alpha[None], xt, y, gl, first_gid,
        k_loc=k_loc_arr.shape[0], lane_tile=128 * lt_arr.shape[0],
        interpret=None, center=None if center is None else center[None],
    )
    return val[0], gbeta[0], galpha[0]


@_vg_grouped.def_vmap
def _vg_grouped_vmap(axis_size, in_batched, beta, alpha, xt, y, gl,
                     first_gid, k_loc_arr, lt_arr, center):
    beta_b, alpha_b, xt_b, y_b, gl_b, fg_b, _, _, center_b = in_batched
    if xt_b or y_b or gl_b or fg_b:
        out = jax.lax.map(
            lambda a: _vg_grouped(*a[:-1], k_loc_arr, lt_arr, a[-1]),
            tuple(
                v if v is None else _bcast(v, b, axis_size)
                for v, b in zip(
                    (beta, alpha, xt, y, gl, first_gid, center),
                    (beta_b, alpha_b, xt_b, y_b, gl_b, fg_b, center_b),
                )
            ),
        )
        return out, (True, True, True)
    beta = _bcast(beta, beta_b, axis_size)
    alpha = _bcast(alpha, alpha_b, axis_size)
    if center is not None:  # one centre for all chains, or one a chain
        center = _bcast(center, center_b, axis_size)
    return (
        _grouped_call(
            beta, alpha, xt, y, gl, first_gid, k_loc=k_loc_arr.shape[0],
            lane_tile=128 * lt_arr.shape[0], interpret=None, center=center,
        ),
        (True, True, True),
    )


@jax.custom_vjp
def hier_logistic_loglik(beta, alpha, xt, y, gl, first_gid, k_loc_arr, lt_arr,
                         center=None):
    """Differentiable fused hierarchical Bernoulli-logit log-lik.

    One Pallas pass over group-sorted data yields the value, ∂/∂beta and
    ∂/∂alpha — no (C, N) intermediate ever exists.  ``gl`` are the
    per-row LOCAL group ids, ``first_gid`` the per-tile group bases, and
    ``k_loc_arr`` a dummy (K_LOC,) array carrying the static window size
    in its shape (all three produced by `grouped_layout`).  Under vmap
    over chains the ensemble shares ONE X pass.  ``center``: a scalar close
    to the log-lik where the chain is (one a chain under vmap, still one X
    pass); the value comes back less it, taken off tile by tile.  A
    constant of the program: it gets no cotangent.
    """
    val, _, _ = _vg_grouped(
        beta, alpha, xt, y, gl, first_gid, k_loc_arr, lt_arr, center
    )
    return val


def _hier_fwd(beta, alpha, xt, y, gl, first_gid, k_loc_arr, lt_arr, center):
    val, gbeta, galpha = _vg_grouped(
        beta, alpha, xt, y, gl, first_gid, k_loc_arr, lt_arr, center
    )
    return val, (gbeta, galpha)


def _hier_bwd(res, ct):
    gbeta, galpha = res
    return (ct * gbeta, ct * galpha) + (None,) * 7


hier_logistic_loglik.defvjp(_hier_fwd, _hier_bwd)


# --- grouped LMM: gaussian link, Q random effects per group -------------
# Same dense-window trick for benchmark config 3 (random intercept +
# slopes, 10k groups; over configs/lmm.yaml's 100k rows, ~10 rows/group,
# grouped_layout shrinks the lane tile to 1024 until each tile's window
# fits; over the on-chip cell's 81.9M rows it keeps 8192).  The kernel
# computes mu = intercept + X·beta + Σ_q z_q ⊙ u_q[g] entirely in-register
# and emits SSR, Σresid, X·resid and the per-tile windowed u-gradient
# partials; sigma stays outside (scale-free kernel, like
# ops/logistic_fused.py's gaussian link).  As in the hierarchical kernel the
# windows ride in the design slab: (u_q @ onehot) ⊙ z_q is u_q @ (onehot ⊙
# z_q), so [beta | u_1 window | ... | u_Q window] against [X ; onehot ⊙ z_1 ;
# ... ; onehot ⊙ z_Q] is one dot forward and its transpose one backward.  A
# dot a random effect each way (2·Q + 2 dots of contraction 8 at `highest`,
# each as many MXU passes over the (C, TILE) block) spilled half the
# schedule: at the on-chip cell's shapes 14 522 bundles a tile and 101 ms a
# call, folded 3 312 and 23.4 ms (a compile for a described v5e and my chip
# run, PR 39: PERF.md §5).

# What the kernel may ask of the core's VMEM (128 MiB on a v5e).  Mosaic's
# default scoped limit is 16 MB, and at the lane tile the layout picks for
# D + Q = 10 (8192) the chip's compiler asked 20.2 MB for C = 16, D = 8,
# Q = 2, K_LOC = 8 when each random effect had its own dots (a compile for a
# described v5e, PR 32).  The one dot over the lanes transposes the
# (D + Q*K_LOC, TILE) slab, 4 MB in tiles of 128 lanes (`_check_chain_vmem`).
_LMM_VMEM_LIMIT = 48 * 1024 * 1024


def _make_grouped_lmm_kernel(n, lane_tile, k_loc, q):
    def kernel(xt_ref, zt_ref, y_ref, gl_ref, beta_ref, ic_ref, u_ref,
               acc_ref, gbeta_ref, gu_ref):
        prec = _dot_precision()  # STARK_FUSED_PRECISION (see logistic_fused)
        lane0 = pl.program_id(0) * lane_tile
        iota = jax.lax.broadcasted_iota(jnp.int32, (1, lane_tile), 1)
        mask = lane0 + iota < n
        xt = jnp.where(mask, xt_ref[...].astype(jnp.float32), 0.0)  # (D, TILE)
        zt = jnp.where(mask, zt_ref[...].astype(jnp.float32), 0.0)  # (Q, TILE)
        y = jnp.where(mask, y_ref[...], 0.0)  # (1, TILE)
        gl = jnp.where(mask, gl_ref[...], 0)  # (1, TILE)
        krows = jax.lax.broadcasted_iota(jnp.int32, (k_loc, lane_tile), 0)
        onehot = jnp.where(krows == gl, 1.0, 0.0)  # (K_LOC, TILE)
        # the windows ride in the design slab, each one-hot weighted by its
        # random effect's covariate (exact: z times 1 or 0), in the order
        # u_ref lays the q-windows side by side
        slab = jnp.concatenate(
            [xt] + [onehot * zt[j : j + 1, :] for j in range(q)], axis=0
        )  # (D + Q*K_LOC, TILE)
        params = jnp.concatenate(
            [beta_ref[...], u_ref[0]], axis=1
        )  # (C, D + Q*K_LOC) — beta resident, this tile's windows
        mu = ic_ref[...] + jax.lax.dot(
            params, slab, precision=prec,
            preferred_element_type=jnp.float32,
        )  # (C, TILE)
        resid = jnp.where(mask, y - mu, 0.0)  # (C, TILE)
        ssr = jnp.sum(resid * resid, axis=1)  # (C,)
        sresid = jnp.sum(resid, axis=1)  # (C,) — the intercept gradient
        acc_ref[...] = jnp.stack([ssr, sresid], axis=-1)[None]  # (1, C, 2)
        grads = jax.lax.dot(
            resid, slab.T, precision=prec,
            preferred_element_type=jnp.float32,
        )  # (C, D + Q*K_LOC): [beta partial | windowed u partials]
        d = xt.shape[0]
        gbeta_ref[...] = grads[:, :d][None]  # (1, C, D)
        gu_ref[...] = grads[:, d:][None]  # (1, C, Q*K_LOC)

    return kernel


def _grouped_lmm_call(beta, u, intercept, xt, zt, y, gl, first_gid, *,
                      k_loc, lane_tile, interpret):
    """beta (C, D), u (C, G, Q), intercept (C,) ->
    (ssr (C, grid): the tiles' sums of squares, left apart for
    `_gauss_loglik`; sum_resid (C,), gbeta (C, D), gu (C, G, Q))."""
    interpret = _resolve_interpret(interpret)
    c, d = beta.shape
    g_total, q = u.shape[1], u.shape[2]
    n = xt.shape[1]
    grid = -(-n // lane_tile)
    cpad = -(-c // 8) * 8
    # (C, TILE)s: mu, resid and its square; transposed: the slab
    _check_chain_vmem(cpad, lane_tile, interpret, k_loc=k_loc, q=q,
                      slab_rows=d + q * k_loc, transposed=1,
                      budget=_LMM_VMEM_LIMIT // 2)
    if cpad != c:
        beta = jnp.pad(beta, ((0, cpad - c), (0, 0)))
        u = jnp.pad(u, ((0, cpad - c), (0, 0), (0, 0)))
        intercept = jnp.pad(intercept, (0, cpad - c))
    u_pad = jnp.pad(u.astype(jnp.float32), ((0, 0), (0, k_loc), (0, 0)))
    win = first_gid[:, None] + jnp.arange(k_loc)[None, :]  # (grid, K_LOC)
    # (C, grid, K_LOC, Q) -> (grid, C, Q*K_LOC): q-windows side by side
    u_tiles = jnp.moveaxis(u_pad[:, win, :], 0, 1)
    u_tiles = u_tiles.transpose(0, 1, 3, 2).reshape(grid, cpad, q * k_loc)

    def lane_spec(height=1):
        return pl.BlockSpec((height, lane_tile), lambda i: (0, i))

    args = [
        _stream_arg(xt),
        _stream_arg(zt),
        y.astype(jnp.float32)[None, :],
        gl.astype(jnp.int32)[None, :],
        beta.astype(jnp.float32),
        intercept.astype(jnp.float32)[:, None],
        u_tiles,
    ]
    in_specs = [
        lane_spec(d),
        lane_spec(q),
        lane_spec(),
        lane_spec(),
        pl.BlockSpec((cpad, d), lambda i: (0, 0)),
        pl.BlockSpec((cpad, 1), lambda i: (0, 0)),
        pl.BlockSpec((1, cpad, q * k_loc), lambda i: (i, 0, 0)),
    ]
    out_specs = [
        pl.BlockSpec((1, cpad, 2), lambda i: (i, 0, 0)),
        pl.BlockSpec((1, cpad, d), lambda i: (i, 0, 0)),
        pl.BlockSpec((1, cpad, q * k_loc), lambda i: (i, 0, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((grid, cpad, 2), jnp.float32),
        jax.ShapeDtypeStruct((grid, cpad, d), jnp.float32),
        jax.ShapeDtypeStruct((grid, cpad, q * k_loc), jnp.float32),
    ]
    out = pl.pallas_call(
        _make_grouped_lmm_kernel(n, lane_tile, k_loc, q),
        grid=(grid,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        name="stark_lmm_ll_grouped",
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_LMM_VMEM_LIMIT
        ),
    )(*args)
    ssr = out[0][:, :c, 0].T  # (C, grid)
    sresid = jnp.sum(out[0][:, :c, 1], axis=0)
    gbeta = jnp.sum(out[1], axis=0)[:c]
    parts = out[2].reshape(grid, cpad, q, k_loc)
    gu = jnp.stack(
        [
            jnp.zeros((cpad, g_total + k_loc), jnp.float32)
            .at[:, win.reshape(-1)]
            .add(parts[:, :, j, :].transpose(1, 0, 2).reshape(cpad, -1))[
                :c, :g_total
            ]
            for j in range(q)
        ],
        axis=-1,
    )  # (C, G, Q)
    return ssr, sresid, gbeta, gu


@functools.partial(jax.custom_batching.custom_vmap)
def _vg_lmm(beta, u, intercept, xt, zt, y, gl, first_gid, k_loc_arr, lt_arr):
    ssr, sresid, gbeta, gu = _grouped_lmm_call(
        beta[None], u[None], intercept[None], xt, zt, y, gl, first_gid,
        k_loc=k_loc_arr.shape[0], lane_tile=128 * lt_arr.shape[0],
        interpret=None,
    )
    return ssr[0], sresid[0], gbeta[0], gu[0]


@_vg_lmm.def_vmap
def _vg_lmm_vmap(axis_size, in_batched, beta, u, intercept, xt, zt, y, gl,
                 first_gid, k_loc_arr, lt_arr):
    beta_b, u_b, ic_b, xt_b, zt_b, y_b, gl_b, fg_b, _, _ = in_batched
    if xt_b or zt_b or y_b or gl_b or fg_b:
        out = jax.lax.map(
            lambda a: _vg_lmm(*a, k_loc_arr, lt_arr),
            tuple(
                _bcast(v, b, axis_size)
                for v, b in zip(
                    (beta, u, intercept, xt, zt, y, gl, first_gid),
                    (beta_b, u_b, ic_b, xt_b, zt_b, y_b, gl_b, fg_b),
                )
            ),
        )
        return out, (True, True, True, True)
    beta = _bcast(beta, beta_b, axis_size)
    u = _bcast(u, u_b, axis_size)
    intercept = _bcast(intercept, ic_b, axis_size)
    return (
        _grouped_lmm_call(
            beta, u, intercept, xt, zt, y, gl, first_gid,
            k_loc=k_loc_arr.shape[0], lane_tile=128 * lt_arr.shape[0],
            interpret=None,
        ),
        (True, True, True, True),
    )


def _tile_rows(n, lane_tile):
    """(grid,) float32: the rows each tile holds (the last may be short)."""
    first = lane_tile * np.arange(-(-n // lane_tile))
    return np.minimum(lane_tile, n - first).astype(np.float32)


def _log1p_near0(t):
    """log1p(t) = 2 atanh(t / (2 + t)) from its series where |t| < 0.4, in
    multiplications, additions and one quotient: it holds float32's 1e-7
    of the result, where the chip's own ``log1p`` is up to 1.6e-4 of it
    off for |t| in 1e-7..0.1 (and ``expm1`` 1.1e-4), which `_gauss_loglik`
    multiplies by 8e7 rows: with the library's two the centred density of
    the on-chip cell's shape read 0.07 to 175 nats off over sweeps of
    sigma 1e-3 to 1e-1 wide, with this series 0.014 to 1.6 (my chip run,
    PR 32: PERF.md section 6)."""
    s = t / (2.0 + t)
    s2, acc = s * s, 0.0
    for k in range(15, 0, -2):  # 1 + s2/3 + s2^2/5 + ...
        acc = 1.0 / k + s2 * acc
    return jnp.where(jnp.abs(t) < 0.4, 2.0 * s * acc, jnp.log1p(t))


def _gauss_loglik(ssr, sigma, rows, center=None):
    """The normal log-density of all rows and its derivative in sigma, from
    the tiles' sums of squares ``ssr`` (grid,) and row counts ``rows``.

    Over tens of millions of rows -ssr/(2 sigma^2) and -n log sigma are
    float32s near 1e7-1e8 whose last bit is whole nats, and the
    sigma-gradient ssr/sigma^3 - n/sigma is the difference of two such.  So
    every large sum is formed tile by tile, where the terms are thousands,
    and ``center[0]`` (a scalar close to the total) comes off tile by tile
    before they are added (`logistic_fused._sum_tiles`).

    That alone does not make the value one an accept step can live on: the
    two scalars 1/sigma^2 and log sigma multiply totals of 1e7-1e8, and a
    float32 holds them to 6e-8 at best (the chip's ``exp`` and ``log`` to
    1e-6: PERF.md section 6, PR 31 and PR 32), which is nats to a hundred
    nats that change with sigma's last bits.  ``center[1:]`` = (sigma0,
    log sigma0, 1 / sigma0^2) at the position the constant was taken at
    (`ref_scale`) takes the large terms to that fixed scale, where the
    scalars' errors are the same at every position and come off with the
    centre, and leaves ``delta = log(sigma / sigma0)``, formed as a
    ``log1p`` of the exact difference, to carry what moves:

        ll = sum_t [-ssr_t / (2 sigma0^2) - n_t log sigma0 - ...]
             - (S / 2) expm1(-2 delta) - n delta,     S = ssr / sigma0^2

    whose last two terms are some n * delta each, held to float32's 1e-7
    (`_log1p_near0`; ``expm1(-2 delta)`` is (sigma0 / sigma)^2 - 1, a
    quotient of the exact difference): within a thousandth of sigma0 their
    errors are hundredths of a nat, within a hundredth tenths.  The three numbers are DATA here, computed once
    where the centre is taken and carried beside it: two programs that
    each took ``log sigma0`` for themselves read it a microrelative
    apart on the chip (one folds ``log(exp(x))``), 140 nats over 7e7 rows
    between the energies one program left and the next one proposes
    against, and no proposal was ever accepted (my chip runs, PR 32).
    A chain has its own ``center`` (`Model.center_per_chain`): the three
    numbers of one chain agree with one another to a float32's rounding
    and no better, so the potentials of two chains may stand whole nats
    apart from the truth, each the same number of nats at every position
    of its chain.  Without ``center`` the scale is ``sigma`` itself, the
    sum is the plain one and ``delta`` is 0.
    """
    if center is None:
        s0, log_s0, inv0 = sigma, jnp.log(sigma), 1.0 / (sigma * sigma)
    else:
        s0, log_s0, inv0 = center[1], center[2], center[3]
    val = _sum_tiles(
        -0.5 * ssr * inv0 - rows * (log_s0 + 0.5 * _LOG_2PI),
        None if center is None else center[0],
    )
    excess = jnp.sum(ssr * inv0 - rows)  # S - n, tile by tile
    if center is None:
        return val, excess / sigma
    delta = _log1p_near0((sigma - s0) / s0)
    swing = jnp.sum(ssr) * inv0 * (
        (s0 - sigma) * (s0 + sigma) / (sigma * sigma)
    )
    val = val - 0.5 * swing - float(np.sum(rows, dtype=np.float64)) * delta
    return val, (excess + swing) / sigma


def ref_scale(sigma):
    """(3,): what `_gauss_loglik` keeps of the noise scale at the position
    its centre is taken at: sigma, log sigma, 1 / sigma^2."""
    return jnp.stack([sigma, jnp.log(sigma), 1.0 / (sigma * sigma)])


@jax.custom_vjp
def lmm_grouped_loglik(beta, u, intercept, sigma, xt, zt, y, gl, first_gid,
                       k_loc_arr, lt_arr, center=None):
    """Differentiable fused LMM normal log-lik over group-sorted rows.

    mu = intercept + X·beta + Σ_q z_q ⊙ u[g, q]; one Pallas pass yields
    the tiles' SSR, Σresid, ∂/∂beta and the windowed ∂/∂u — no (C, N)
    intermediate.  sigma applies outside (scale-free kernel), tile by
    tile (`_gauss_loglik`).  Layout args (gl, first_gid, k_loc_arr,
    lt_arr) come from `grouped_layout`.  ``center`` (4,): a scalar close
    to the log-lik where the chain is, then `ref_scale` of the sigma at the
    position it was taken at; the value comes back less ``center[0]``.  A
    constant of the program: it gets no cotangent.
    """
    return _lmm_fwd(beta, u, intercept, sigma, xt, zt, y, gl, first_gid,
                    k_loc_arr, lt_arr, center)[0]


def _lmm_fwd(beta, u, intercept, sigma, xt, zt, y, gl, first_gid,
             k_loc_arr, lt_arr, center):
    ssr, sresid, gbeta, gu = _vg_lmm(
        beta, u, intercept, xt, zt, y, gl, first_gid, k_loc_arr, lt_arr
    )
    val, dsigma = _gauss_loglik(
        ssr, sigma, _tile_rows(y.shape[-1], 128 * lt_arr.shape[0]), center
    )
    return val, (sresid, gbeta, gu, sigma, dsigma)


def _lmm_bwd(res, ct):
    sresid, gbeta, gu, sigma, dsigma = res
    inv2 = 1.0 / (sigma * sigma)
    return (
        ct * inv2 * gbeta,
        ct * inv2 * gu,
        ct * inv2 * sresid,
        ct * dsigma,
        None, None, None, None, None, None, None, None,
    )


lmm_grouped_loglik.defvjp(_lmm_fwd, _lmm_bwd)
