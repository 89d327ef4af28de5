"""Check `sampler_state_nats`: `sampler_state`'s comparison (that file, loaded
by name and asked for the names it knows) and one number more, for a posterior
over so many rows that its potential is a float32 with a last bit of whole
nats:

  pe_diff_nats   the potential's differences as an accept step sees them, free
                 of any constant both sides share: along each chain, the
                 potential the window's last block left less the one the
                 window began with, against the reference's difference at the
                 same two positions, in nats beyond what float32 arithmetic
                 that is sound leaves there (the two allowances below); the
                 worst chain's.  A potential summed plainly in float32 steps
                 by 4 to 16 nats at 3.6e7 to 1.7e8 and reads in nats here;
                 `pe_gap`, relative to the potential itself, reads 1e-6
                 either way.

Along a chain and not between the chains: an accept step compares a chain
with itself alone, and where MAP leaves the chains tens of millions of nats
apart (this cell: 5e7) the potentials of two chains agree with the reference
to a microrelative each, a hundred nats, whatever the program does (PERF.md
section 6, PR 32).  What comes off each chain's offset, and why (the readings
are in PERF.md section 6, PR 37):

  `SHARE_OF_FALL` of the chain's distance, in nats of the reference's
      potential, from where it stood at its first draw after warm-up, at the
      window's start and at its end, added.  The program sums a chain's
      potential relative to a centre taken where the chain stood when
      warm-up's last program began, and sampling never moves it
      (`chees.recentre`); the terms that carry what has changed since
      (`ops.hier_fused._gauss_loglik`: n log(sigma / sigma0) and its twin)
      are float32 products of that size, a smooth error of 4e-6 to 1e-5 of
      the distance from the centre that no accept step sees.  The window's
      own descent is the wrong yardstick for it: a chain that fell 6e6 nats
      before the window and 2e5 in it is 6e6 from its centre throughout
      (seed 2147497012, chain 12: 41 nats off).  Call A's block is the timed
      call's own output (`draws_before`) and its first draw the earliest
      position there is, up to seven transitions after the centre; that
      chain fell nine tenths of its way in those seven, which is why the
      share is 5e-5 and not 1e-5.  Without call A's block the window's start
      stands in.
  `SHARE_OF_HEIGHT` of the chain's height above the lowest potential any
      chain shows at either end of the window, start and end added.  What
      stands out of equilibrium there (sums of squares over 8e7 rows times a
      float32 1 / sigma^2, less n log sigma) is float32 products of that
      size, centred or not: a chain that stands 4e7 to 1e8 nats up and creeps
      (an ensemble whose one step size collapsed in the 48 warm-up
      transitions) reads 1 to 4 nats off however short its path, where the
      chains near the mode read hundredths.  The plain sum steps by whole
      last bits, 4 to 16 nats, at every height, and a chain near the mode
      shows them.

All of it is printed chain by chain beside the number, with each chain's share
of moved transitions: a chain that stands still all window is the
configuration's where it is among the highest (one step size for sixteen chains
that MAP left 5e7 nats apart), and `accept_gap` holds the program to having
said so.
"""

import sys
import types

import numpy as np

#: the share of a chain's distance from its first draw after warm-up (the
#: reference's potential there less at the window's start, and less at its
#: end, added) that the potential's difference may be off by
SHARE_OF_FALL = 5e-5
#: and the share of its height above the ensemble's lowest potential, at the
#: window's start and at its end, added
SHARE_OF_HEIGHT = 1e-7


def compare(measured, env, wanted):
    """{name: value} for the names in `wanted`."""
    load, kept = env["load"], {}

    def remembering(folder, name):
        """The harness's loader; the reference remembers the potential it was
        asked for and the generator the rows it made, so that the rows are
        made once and streamed once a state."""
        mod = load(folder, name)

        def potential_and_grad(rows, z):
            kept["pe"], grad = mod.potential_and_grad(rows, z)
            return kept["pe"], grad

        def make(*args):
            kept["rows"] = mod.make(*args)
            return kept["rows"]

        more = {"references": {"potential_and_grad": potential_and_grad},
                "rows": {"make": make}}.get(folder)
        return mod if more is None else types.SimpleNamespace(
            **dict(vars(mod), **more))

    mine = {"pe_diff_nats"} & set(wanted)
    asked = [k for k in wanted if k not in mine]
    if mine and "pe_gap" not in asked:
        asked.append("pe_gap")  # has the reference's potential computed
    out = load("checks", "sampler_state").compare(
        measured, dict(env, load=remembering), asked)
    if mine:
        end, start = measured["state_end"], measured["state_start"]
        reference = load("references", env["config"]["reference"])

        def ref_pe(z):
            return np.asarray(reference.potential_and_grad(
                kept["rows"], z)[0], np.float64)

        ref_end = np.asarray(kept["pe"], np.float64)
        ref_start = ref_pe(start["z"])
        before = measured.get("draws_before")
        ref_first = (ref_pe(before[:, 0])
                     if before is not None and before.shape[1] else ref_start)
        pe_end, pe_start = (s["pe"].astype(np.float64) for s in (end, start))
        off = (pe_end - pe_start) - (ref_end - ref_start)
        fall = np.abs(ref_first - ref_start) + np.abs(ref_first - ref_end)
        lowest = min(ref_start.min(), ref_end.min())
        height = (ref_start - lowest) + (ref_end - lowest)
        beyond = (np.abs(off) - SHARE_OF_FALL * fall
                  - SHARE_OF_HEIGHT * height)
        out["pe_diff_nats"] = float(max(0.0, np.max(beyond)))
        flat = measured["draws_flat"]  # compared in place: no second copy
        moved = np.concatenate(
            [np.any(flat[:, :1] != start["z"][:, None], axis=2),
             np.any(flat[:, 1:] != flat[:, :-1], axis=2)], axis=1).mean(axis=1)

        def row(fmt, values):
            return " ".join(format(v, fmt) for v in values)

        print("[onchip] pe_diff_nats by chain " + row("+.3f", off)
              + "; beyond the allowances " + row("+.3f", beyond)
              + "; the window's descent " + row(".3g", ref_start - ref_end)
              + "; fall since the first draw after warm-up, to the window's "
              "start and to its end " + row(".3g", ref_first - ref_start)
              + " / " + row(".3g", ref_first - ref_end)
              + "; potential above the lowest chain "
              + row(".3g", ref_end - lowest)
              + "; share of moved transitions " + row(".2f", moved),
              file=sys.stderr, flush=True)
    return {k: out[k] for k in wanted}
