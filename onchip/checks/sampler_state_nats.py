"""Check `sampler_state_nats`: `sampler_state`'s comparison (that file, loaded
by name and asked for the names it knows) and one number more, for a posterior
over so many rows that its potential is a float32 with a last bit of whole
nats:

  pe_diff_nats   the potential's differences as an accept step sees them, free
                 of any constant both sides share: along each chain, the
                 potential the window's last block left less the one the
                 window began with, against the reference's difference at the
                 same two positions, in nats beyond `SHARE_OF_DESCENT` of that
                 difference; the worst chain's.  A potential summed plainly in
                 float32 steps by 4 to 8 nats at 3.6e7 to 1.1e8 and reads in
                 nats here; `pe_gap`, relative to the potential itself, reads
                 1e-6 either way.

Along a chain and not between the chains: an accept step compares a chain
with itself alone, and where MAP leaves the chains tens of millions of nats
apart (this cell: 5e7) the potentials of two chains agree with the reference
to a microrelative each, a hundred nats, whatever the program does (the chip's
`exp` and `log`; PERF.md section 6, PR 32: 59 to 148 read between the chains
of the sound program, 153 of the plain float32 potential).  And less a share
of the descent: such a chain comes down millions of nats in a window, and a
position that the chip holds a microrelative off (its `exp` in sigma, the
MXU's passes) moves the potential by the gradient times that, a smooth error
that no accept step sees: 4e-6 to 9e-6 of the descent on the sound program's
chains, 3e-5 to 9e-5 with rows in bfloat16.  Both are printed beside it.
"""

import sys
import types

import numpy as np

#: the share of a chain's descent over the window that its potential's
#: difference may be off by before the nats count (the docstring says why)
SHARE_OF_DESCENT = 2e-5


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
        ref_end = np.asarray(kept["pe"], np.float64)
        ref_start = np.asarray(load(
            "references", env["config"]["reference"]).potential_and_grad(
                kept["rows"], start["z"])[0], np.float64)
        pe_end, pe_start = (s["pe"].astype(np.float64) for s in (end, start))
        off = (pe_end - pe_start) - (ref_end - ref_start)
        out["pe_diff_nats"] = float(max(0.0, np.max(
            np.abs(off) - SHARE_OF_DESCENT * np.abs(ref_start - ref_end))))
        between = (pe_end - pe_end.mean()) - (ref_end - ref_end.mean())
        print("[onchip] pe_diff_nats by chain "
              + " ".join(f"{o:+.3f}" for o in off)
              + "; the window's descent "
              + " ".join(f"{p:.3g}" for p in ref_start - ref_end)
              + "; between the chains (each less the mean) "
              + " ".join(f"{o:+.1f}" for o in between)
              + "; potential above the lowest chain "
              + " ".join(f"{p:.3g}" for p in ref_end - ref_end.min()),
              file=sys.stderr, flush=True)
    return {k: out[k] for k in wanted}
