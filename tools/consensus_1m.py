#!/usr/bin/env python
"""Config 2 at its PINNED scale: logistic N=1M data-sharded consensus
(BASELINE.json:8; VERDICT r3 missing #3).

Runs consensus ChEES over 8 shards of 1M rows with the dispatch-bounded
accelerator settings, quantifies the combine accuracy against a
full-data run at the same scale, and prints one table row + the combine
error (``--out FILE`` appends it there).  Runs on whatever platform jax
gives it and labels the row with it (expect ~hours on a CPU — the 1M-row
run is an on-chip measurement).

Usage: python tools/consensus_1m.py [--n 1000000] [--out FILE]
"""

import argparse
import datetime
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=1_000_000)
    ap.add_argument("--out", default=None, metavar="FILE")
    ap.add_argument("--chains", type=int, default=8)
    ap.add_argument("--shards", type=int, default=8)
    args = ap.parse_args()

    import jax

    from stark_tpu.benchmarks import bench_consensus_logistic

    platform = jax.devices()[0].platform
    print(f"[consensus-1m] platform={platform} n={args.n}", file=sys.stderr)
    res = bench_consensus_logistic(
        n=args.n, num_shards=args.shards, chains=args.chains,
        combine_check=True,
    )
    err = res.extra.get("combine_rel_err")
    line = (
        f"| consensus_logistic N={args.n} | {res.ess_per_sec:.2f} | "
        f"{res.min_ess:.0f} | {res.wall_s:.1f} | {res.max_rhat:.3f} | "
        f"{'yes' if res.max_rhat < 1.01 else 'no'} | "
        f"combine_rel_err={err:.3f} | {platform} |"
    )
    print(res.row(), file=sys.stderr)
    print(line)
    if args.out:
        stamp = datetime.datetime.now().strftime("%Y-%m-%d %H:%M")
        with open(args.out, "a") as f:
            f.write(
                f"\n## Config 2 at pinned scale (N={args.n}, {stamp}, "
                f"platform={platform})\n\n"
                "combine_rel_err = max over coefficients of "
                "|mean_consensus - mean_full| / sd_full (posterior-sd "
                "units, full-data run at the same scale).\n\n"
                "| benchmark | ESS/s | min ESS | wall (s) | max R-hat | "
                "R-hat<1.01 | combine | platform |\n"
                "|---|---|---|---|---|---|---|---|\n"
                f"{line}\n"
            )
        print(f"appended to {args.out}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
