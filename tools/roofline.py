#!/usr/bin/env python
"""Roofline measurement for the fused logistic kernel (VERDICT r1 #2).

Separates DEVICE-EXECUTE time from dispatch overhead without trace
parsing: time the chain-batched fused gradient (a) dispatched individually
(block_until_ready per call — what a naive per-step driver pays) and
(b) amortized K iterations inside ONE compiled lax.fori_loop (what the
production scan-based samplers actually execute).  The difference is the
per-dispatch overhead; (b) gives kernel-only GB/s.

Also measures a plain-XLA reduction over the same X matrix inside one
program — the achievable HBM streaming rate for this shape on this chip —
so %-of-achievable is reported next to %-of-spec-sheet-peak.

Run on the chip:  python tools/roofline.py
Writes tools/roofline_results.json and prints a summary.  The %-of-peak
columns need the device's published HBM peak (`PEAK_GBS`); a device that
is not in that table is an error, not a default.
"""

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

N = int(os.environ.get("ROOF_N", 1_000_000))
D = int(os.environ.get("ROOF_D", 32))
K = int(os.environ.get("ROOF_K", 20))  # amortized iterations per program
REPS = int(os.environ.get("ROOF_REPS", 10))
# ROOF_INTERPRET=1: Pallas interpret mode at tiny shapes — a CPU smoke of
# the measurement harness itself (rates are meaningless there and no
# %-of-peak is computed; the on-chip run uses compiled kernels)
INTERPRET = os.environ.get("ROOF_INTERPRET", "") == "1"

#: published HBM bandwidth, GB/s, keyed by `device_kind` as jax reports
#: it (Google Cloud documentation, "TPU v5e": 819 GB/s per chip)
PEAK_GBS = {"TPU v5 lite": 819.0}


def peak_gbs(device_kind):
    """The device's published HBM peak; a kind not in `PEAK_GBS` raises
    (a %-of-peak against another chip's peak is not a measurement)."""
    try:
        return PEAK_GBS[device_kind]
    except KeyError:
        raise SystemExit(
            f"[roofline] no published HBM peak for device_kind="
            f"{device_kind!r}; known: {sorted(PEAK_GBS)} (add the chip to "
            f"PEAK_GBS with its source, or set ROOF_INTERPRET=1 for a CPU "
            f"smoke of the harness)"
        ) from None


def timeit(fn, warm_arg, arglist, *, sync_each=False):
    """Average seconds per call over ``arglist`` (the warm-up input is
    not in the timed list).  sync_each=True blocks per call
    (dispatch+sync latency, what a naive per-step driver pays);
    sync_each=False blocks once at the end (pipelined throughput).
    """
    jax.block_until_ready(fn(warm_arg))  # compile + warm
    t0 = time.perf_counter()
    if sync_each:
        for a in arglist:
            jax.block_until_ready(fn(a))
    else:
        jax.block_until_ready([fn(a) for a in arglist])
    return (time.perf_counter() - t0) / len(arglist)


def main():
    from stark_tpu.ops.logistic_fused import _batched_call

    dev = jax.devices()[0]
    platform = dev.platform
    # %-of-peak only against the peak of the chip that ran; None under the
    # interpreter, where no rate means anything
    peak = None if INTERPRET else peak_gbs(dev.device_kind)

    def pct_of_peak(gbs):
        return None if peak is None else 100.0 * gbs / peak

    def rate_str(entry, noun="GB/s"):
        pct = entry.get("pct_of_spec_peak")
        tail = "" if pct is None else f" = {pct:.0f}% of {dev.device_kind} peak"
        return f"({entry['amortized_gbs']:.0f} {noun}{tail})"

    print(f"[roofline] platform={platform} kind={dev.device_kind} "
          f"N={N} D={D} K={K}", file=sys.stderr)
    key = jax.random.PRNGKey(0)
    xt = jax.random.normal(key, (D, N), jnp.float32)
    y = (jax.random.uniform(jax.random.PRNGKey(1), (N,)) < 0.5).astype(jnp.float32)
    results = {"platform": platform, "device_kind": dev.device_kind,
               "n": N, "d": D, "k": K, "cases": []}

    # --- pure-XLA HBM stream baseline: sum(xt*s) amortized in one program ---
    @jax.jit
    def stream_once(s):
        return jnp.sum(xt * s)

    @jax.jit
    def stream_loop(s):
        def body(i, acc):
            # acc feeds back so iterations cannot be collapsed
            return acc + jnp.sum(xt * (s + 1e-9 * acc))

        return jax.lax.fori_loop(0, K, body, jnp.float32(0))

    xt_bytes = xt.size * 4

    scales = [jnp.float32(1.0 + i * 1e-6) for i in range(REPS)]
    t1 = timeit(stream_once, jnp.float32(0.5), scales, sync_each=True)
    tk = timeit(stream_loop, jnp.float32(0.5), scales) / K
    stream = results["stream"] = {
        "bytes": xt_bytes,
        "per_dispatch_s": t1,
        "amortized_s": tk,
        "per_dispatch_gbs": xt_bytes / t1 / 1e9,
        "amortized_gbs": xt_bytes / tk / 1e9,
    }
    print(
        f"[roofline] plain XLA sum over {xt_bytes/1e6:.0f} MB: "
        f"per-dispatch {stream['per_dispatch_s']*1e3:.2f} ms, "
        f"amortized {stream['amortized_s']*1e3:.2f} ms "
        f"({stream['amortized_gbs']:.0f} GB/s)",
        file=sys.stderr,
    )

    for C in (8, 32, 64):
        beta = 0.01 * jax.random.normal(jax.random.PRNGKey(2), (C, D), jnp.float32)
        offsets = jnp.zeros((C, N), jnp.float32)

        @jax.jit
        def one(beta):
            v, g, r = _batched_call(
                beta, xt, y, offsets, lane_tile=None, interpret=INTERPRET
            )
            return v, g

        @jax.jit
        def loop(beta):
            def body(i, b):
                v, g, r = _batched_call(
                    b, xt, y, offsets, lane_tile=None, interpret=INTERPRET
                )
                # feed the gradient back so no iteration can be elided
                return b + 1e-12 * g

            return jax.lax.fori_loop(0, K, body, beta)

        # bytes: read xt + y + offsets, write resid (+ tiny partials)
        nbytes = xt_bytes + 4 * N + 4 * N * C + 4 * N * C

        betas = [
            0.01 * jax.random.normal(
                jax.random.PRNGKey(10 + i), (C, D), jnp.float32
            )
            for i in range(REPS + 1)
        ]
        t1 = timeit(one, betas[0], betas[1:], sync_each=True)
        tk = timeit(loop, betas[0], betas[1:]) / K
        case = {
            "chains": C,
            "bytes": nbytes,
            "per_dispatch_s": t1,
            "amortized_s": tk,
            "per_dispatch_gbs": nbytes / t1 / 1e9,
            "amortized_gbs": nbytes / tk / 1e9,
            "dispatch_overhead_ms": (t1 - tk) * 1e3,
            "pct_of_spec_peak": pct_of_peak(nbytes / tk / 1e9),
        }
        results["cases"].append(case)
        print(
            f"[roofline] C={C}: {nbytes/1e6:.0f} MB/eval; per-dispatch "
            f"{case['per_dispatch_s']*1e3:.2f} ms, amortized "
            f"{case['amortized_s']*1e3:.2f} ms " + rate_str(case)
            + f"; dispatch overhead {case['dispatch_overhead_ms']:.2f} ms",
            file=sys.stderr,
        )

    # --- grouped hierarchical kernel (the kernel the FLAGSHIP runs on) ---
    # Builder-measured before PR 1 (not in the driver's ledger): the
    # grouped kernel moves ~137 MB/eval in 2.1 ms (~65 GB/s effective)
    # while the offset kernel above streams at ~326 GB/s.  Pass-count arithmetic says the grouped
    # kernel is MXU-pass-bound, not HBM-bound: it runs FOUR f32 dots per
    # tile (logits: beta + alpha-window one-hot; gradients: X-weighted +
    # one-hot-weighted) and HIGHEST f32 precision is emulated in 6 bf16
    # MXU passes at C/128 row utilization — ~12.3 GFLOP/eval x 6 passes
    # / (32/128 rows) ~ 1.5 ms at the v5e's ~200 bf16 TFLOPs, vs 0.42 ms
    # for the 137 MB stream at the measured 326 GB/s.  Three cases
    # attribute the non-stream time on-chip:
    #   grouped_full         production ensemble gradient (gather+kernel+
    #                        scatter+sums)
    #   grouped_gather_hoist alpha fixed across iterations, so XLA hoists
    #                        the alpha-window gather out of the loop —
    #                        full minus this = gather cost
    #   grouped_prec_high    STARK_FUSED_PRECISION=high (3-pass dots) —
    #                        full minus this = MXU-pass cost (the lever)
    import stark_tpu.ops.hier_fused as hf

    G = int(os.environ.get("ROOF_G", 1000))
    gsorted = np.sort(np.arange(N) % G).astype(np.int32)
    layout = hf.grouped_layout(gsorted, D)
    if layout is None:
        print("[roofline] grouped layout infeasible at this shape; skipped",
              file=sys.stderr)
    grouped_cases = []
    if layout is not None:
        lane_tile, k_loc, first_gid, gl = layout
        gl_j = jnp.asarray(gl)
        fg_j = jnp.asarray(first_gid)
        C = int(os.environ.get("ROOF_GROUPED_C", 32))
        grid = -(-N // lane_tile)
        # xt + y + gl + alpha windows + (val, gbeta, galpha) partials
        gbytes = (
            xt.size * 4 + N * 4 + N * 4
            + grid * C * k_loc * 4
            + grid * C * (1 + D + k_loc) * 4
        )

        def measure_case(tag, vary_alpha, precision, xt_case, case_bytes):
            def grouped_grad(beta, alpha):
                return hf._grouped_call(
                    beta, alpha, xt_case, y, gl_j, fg_j, k_loc=k_loc,
                    lane_tile=lane_tile, interpret=INTERPRET,
                )

            prior = os.environ.get("STARK_FUSED_PRECISION")
            os.environ["STARK_FUSED_PRECISION"] = precision
            try:
                @jax.jit
                def one(beta, alpha):
                    return grouped_grad(beta, alpha)

                @jax.jit
                def loop(beta, alpha):
                    def body(i, ba):
                        b, a = ba
                        v, gb, ga = grouped_grad(b, a)
                        # feed gradients back so no iteration elides;
                        # alpha fixed in the hoist case so the window
                        # gather is loop-invariant
                        b = b + 1e-12 * gb
                        if vary_alpha:
                            a = a + 1e-12 * ga
                        return (b, a)

                    return jax.lax.fori_loop(0, K, body, (beta, alpha))

                keys = [
                    jax.random.PRNGKey(77 + i)
                    for i in range(2 * (REPS + 1))
                ]
                betas = [
                    0.01 * jax.random.normal(k, (C, D), jnp.float32)
                    for k in keys[: REPS + 1]
                ]
                alphas = [
                    0.01 * jax.random.normal(k, (C, G), jnp.float32)
                    for k in keys[REPS + 1 :]
                ]
                t1 = timeit(
                    lambda ba: one(*ba), (betas[0], alphas[0]),
                    list(zip(betas[1:], alphas[1:])), sync_each=True,
                )
                tk = timeit(
                    lambda ba: loop(*ba), (betas[0], alphas[0]),
                    list(zip(betas[1:], alphas[1:])),
                ) / K
            finally:
                # restore, don't pop: a session-level setting must
                # survive this case (rows record their own precision)
                if prior is None:
                    os.environ.pop("STARK_FUSED_PRECISION", None)
                else:
                    os.environ["STARK_FUSED_PRECISION"] = prior
            return {
                "case": tag,
                "chains": C,
                "lane_tile": lane_tile,
                "k_loc": k_loc,
                "precision": precision,
                "x_dtype": str(xt_case.dtype),
                "bytes": case_bytes,
                "per_dispatch_s": t1,
                "amortized_s": tk,
                "per_dispatch_gbs": case_bytes / t1 / 1e9,
                "amortized_gbs": case_bytes / tk / 1e9,
                "pct_of_spec_peak": pct_of_peak(case_bytes / tk / 1e9),
            }

        # bf16 X stream: halves the dominant X bytes (the stream-side
        # lever that compounds with the precision lever once the kernel
        # stops being MXU-pass-bound)
        xt_b16 = xt.astype(jnp.bfloat16)
        gbytes_b16 = gbytes - xt.size * 2
        for tag, vary_alpha, precision, xt_case, case_bytes in (
            ("grouped_full", True, "highest", xt, gbytes),
            ("grouped_gather_hoist", False, "highest", xt, gbytes),
            ("grouped_prec_high", True, "high", xt, gbytes),
            ("grouped_prec_default", True, "default", xt, gbytes),
            ("grouped_x_bf16_prec_high", True, "high", xt_b16, gbytes_b16),
        ):
            case = measure_case(
                tag, vary_alpha, precision, xt_case, case_bytes
            )
            grouped_cases.append(case)
            print(
                f"[roofline] {tag}: {case_bytes/1e6:.0f} MB/eval; amortized "
                f"{case['amortized_s']*1e3:.2f} ms "
                + rate_str(case, "GB/s effective"),
                file=sys.stderr,
            )
        # non-stream time: measured amortized eval minus the time the
        # achievable stream rate needs for the same bytes
        full = grouped_cases[0]
        full["non_stream_ms"] = (
            full["amortized_s"] - gbytes / (stream["amortized_gbs"] * 1e9)
        ) * 1e3
    results["grouped"] = grouped_cases

    # --- grouped LMM kernel (judged config 3's kernel) -------------------
    # Same MXU-pass argument (4+Q HIGHEST dots per tile); these rows let
    # the one on-chip session quantify the precision lever for config 3
    # alongside the flagship kernel.  Dense grouping (~10 rows/group)
    # shrinks the lane tile, so per-tile fixed costs matter more here.
    LN = int(os.environ.get("ROOF_LMM_N", 100_000))
    LD = int(os.environ.get("ROOF_LMM_D", 8))
    LG = int(os.environ.get("ROOF_LMM_G", 10_000))
    LQ = 2
    LC = int(os.environ.get("ROOF_LMM_C", 16))
    lmm_cases = []
    g_l = np.sort(np.arange(LN) % LG).astype(np.int32)
    lmm_layout = hf.grouped_layout(g_l, LD + LQ + 2)
    if lmm_layout is None:
        print("[roofline] grouped-LMM layout infeasible; skipped",
              file=sys.stderr)
    else:
        lt_l, kloc_l, fg_l, gl_l = lmm_layout
        grid_l = -(-LN // lt_l)
        xt_l = jax.random.normal(jax.random.PRNGKey(5), (LD, LN), jnp.float32)
        zt_l = jax.random.normal(jax.random.PRNGKey(6), (LQ, LN), jnp.float32)
        y_l = jax.random.normal(jax.random.PRNGKey(7), (LN,), jnp.float32)
        gl_lj, fg_lj = jnp.asarray(gl_l), jnp.asarray(fg_l)
        lbytes = (
            (LD + LQ + 2) * LN * 4                      # xt + zt + y + gl
            + grid_l * LC * LQ * kloc_l * 4             # u windows in
            + grid_l * LC * (2 + LD + LQ * kloc_l) * 4  # partials out
        )

        def measure_lmm_case(tag, precision):
            def lmm_grad(beta, u, ic):
                return hf._grouped_lmm_call(
                    beta, u, ic, xt_l, zt_l, y_l, gl_lj, fg_lj,
                    k_loc=kloc_l, lane_tile=lt_l, interpret=INTERPRET,
                )

            prior = os.environ.get("STARK_FUSED_PRECISION")
            os.environ["STARK_FUSED_PRECISION"] = precision
            try:
                @jax.jit
                def loop(beta, u, ic):
                    def body(i, bui):
                        b, uu, i0 = bui
                        ssr, sresid, gb, gu = lmm_grad(b, uu, i0)
                        return (
                            b + 1e-12 * gb,
                            uu + 1e-12 * gu,
                            i0 + 1e-12 * sresid,
                        )

                    return jax.lax.fori_loop(0, K, body, (beta, u, ic))

                @jax.jit
                def one(beta, u, ic):
                    return lmm_grad(beta, u, ic)

                args = [
                    (
                        0.01 * jax.random.normal(
                            jax.random.PRNGKey(900 + i),
                            (LC, LD), jnp.float32,
                        ),
                        0.01 * jax.random.normal(
                            jax.random.PRNGKey(950 + i),
                            (LC, LG, LQ), jnp.float32,
                        ),
                        jnp.zeros((LC,), jnp.float32) + 0.01 * i,
                    )
                    for i in range(REPS + 1)
                ]
                t1 = timeit(
                    lambda a: one(*a), args[0], args[1:], sync_each=True
                )
                tk = timeit(lambda a: loop(*a), args[0], args[1:]) / K
            finally:
                if prior is None:
                    os.environ.pop("STARK_FUSED_PRECISION", None)
                else:
                    os.environ["STARK_FUSED_PRECISION"] = prior
            return {
                "case": tag,
                "chains": LC,
                "lane_tile": lt_l,
                "k_loc": kloc_l,
                "precision": precision,
                "bytes": lbytes,
                "per_dispatch_s": t1,
                "amortized_s": tk,
                "per_dispatch_gbs": lbytes / t1 / 1e9,
                "amortized_gbs": lbytes / tk / 1e9,
                "pct_of_spec_peak": pct_of_peak(lbytes / tk / 1e9),
            }

        for tag, precision in (
            ("lmm_grouped_full", "highest"),
            ("lmm_grouped_prec_high", "high"),
        ):
            case = measure_lmm_case(tag, precision)
            lmm_cases.append(case)
            print(
                f"[roofline] {tag}: {lbytes/1e6:.0f} MB/eval; amortized "
                f"{case['amortized_s']*1e3:.2f} ms "
                + rate_str(case, "GB/s effective"),
                file=sys.stderr,
            )
    results["grouped_lmm"] = lmm_cases

    # interpret/CPU smoke runs must never overwrite the committed on-chip
    # artifact (tests pin its sanity) — they validate the harness, not
    # the chip
    name = (
        "roofline_results.json"
        if not INTERPRET and platform != "cpu"
        else "roofline_smoke.json"
    )
    out_path = os.path.join(os.path.dirname(os.path.abspath(__file__)), name)
    with open(out_path, "w") as f:
        json.dump(results, f, indent=1)
    print(json.dumps({"wrote": out_path}))


if __name__ == "__main__":
    main()
