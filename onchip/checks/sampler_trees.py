"""Check `sampler_trees`: `sampler_state`'s comparison (that file, loaded by
name and asked for the names it knows) and three numbers more, for a cell
whose sampler builds trees (NUTS through `sampler.ChainBlockKernel`) over so
many rows that the potential is a float32 with a last bit of a whole nat:

  pe_diff_nats   the potential's differences as a tree sees them, free of any
                 constant both sides share: along each chain, the potential
                 the window's last block left less the one the window began
                 with, against the reference's difference at the same two
                 positions; the worst chain's, in nats.  The chains of this
                 cell are adapted and near the mode (they stand hundreds of
                 nats apart and move by tens in a window), so nothing comes
                 off it.  A potential summed plainly in float32 steps by 1 nat
                 at 1e7 and reads half a nat here; `pe_gap`, relative to the
                 potential itself, reads 1e-7 either way.
  leaf_dh_nats   one leaf of the sampler's tree from the state the window's
                 last block left (`lib/leaf.py`: the program's own
                 `_leaf_step`, the momentum drawn under the chain's
                 checkpointed mass): the program's energy difference H(leaf) -
                 H(start) against the reference's for the same start, momentum,
                 step and mass; the worst chain's, in nats.  What leaf
                 weights, the accept statistic and dual averaging are made of.
  leaves_out_of_range   the window's blocks whose tree counters (`block.gate`:
                 `tree_leaves`, `tree_depths`, `lane_iterations`) are
                 impossible for the configuration's `max_tree_depth`, or
                 disagree with the record's `block_grad_evals` and the
                 block's size; a block without the counters counts.

`accept_gap` is not for this sampler: a NUTS transition reports the mean leaf
acceptance statistic, not the probability that the chain moves (it moves
nearly always), so the two do not compare.  What the trees were is printed
beside the numbers: depths, leaves, divergences, transitions that stayed.
"""

import sys

import numpy as np

from lib import leaf as libleaf
from lib import remember

MINE = ("pe_diff_nats", "leaf_dh_nats", "leaves_out_of_range")


def _row(fmt, values):
    return " ".join(format(v, fmt) for v in values)


def _say(msg):
    print("[onchip] " + msg, file=sys.stderr, flush=True)


def block_out_of_range(fields, record, chains, max_depth):
    """Why a block's tree counters cannot be (a string), or None."""
    need = ("tree_leaves", "tree_depths", "lane_iterations")
    if any(k not in fields for k in need):
        return "no tree counters"
    leaves, depths = int(fields["tree_leaves"]), list(fields["tree_depths"])
    lanes = int(fields["lane_iterations"])
    steps = sum(depths) // chains
    if leaves != int(record["block_grad_evals"]):
        return f"tree_leaves {leaves} != block_grad_evals"
    if len(depths) != max_depth + 1 or depths[0] or sum(depths) % chains:
        return f"depths {depths} beyond max_tree_depth {max_depth}"
    # a tree of depth k has 2**(k-1) to 2**k - 1 leaves
    low = sum(n << (k - 1) for k, n in enumerate(depths) if k)
    high = sum(n * ((1 << k) - 1) for k, n in enumerate(depths))
    if not low <= leaves <= high:
        return f"tree_leaves {leaves} outside {low}..{high}"
    # the longest tree of each transition: no shorter than the mean tree, no
    # longer than the cap, and the lanes cover the leaves
    if not (steps <= lanes <= steps * ((1 << max_depth) - 1)
            and lanes * chains >= leaves >= lanes):
        return f"lane_iterations {lanes} for {leaves} leaves"
    return None


def compare(measured, env, wanted):
    """{name: value} for the names in `wanted`."""
    load, kept = remember.remembering(env["load"])
    mine = set(MINE) & set(wanted)
    asked = [k for k in wanted if k not in mine]
    if {"pe_diff_nats", "leaf_dh_nats"} & mine and "pe_gap" not in asked:
        asked.append("pe_gap")  # has the reference's potential computed
    out = env["load"]("checks", "sampler_state").compare(
        measured, dict(env, load=load), asked)
    end, start = measured["state_end"], measured["state_start"]
    chains = end["z"].shape[0]

    reference = env["load"]("references", env["config"]["reference"])

    def ref(z):
        pe, grad = reference.potential_and_grad(kept["rows"], z)
        return np.asarray(pe, np.float64), np.asarray(grad, np.float64)

    if "pe_diff_nats" in mine or "leaf_dh_nats" in mine:
        ref_end = (np.asarray(kept["pe"], np.float64),
                   np.asarray(kept["grad"], np.float64))
    if "pe_diff_nats" in mine:
        ref_start = ref(start["z"])[0]
        off = ((end["pe"].astype(np.float64) - start["pe"].astype(np.float64))
               - (ref_end[0] - ref_start))
        out["pe_diff_nats"] = float(np.max(np.abs(off)))
        lowest = min(ref_start.min(), ref_end[0].min())
        _say("pe_diff_nats by chain " + _row("+.3f", off)
             + "; the window's descent " + _row(".3g", ref_start - ref_end[0])
             + "; potential above the lowest chain, at the window's start "
             + _row(".3g", ref_start - lowest) + " and at its end "
             + _row(".3g", ref_end[0] - lowest))
    if "leaf_dh_nats" in mine:
        leaf = measured.get("leaf")
        if leaf is None:
            out["leaf_dh_nats"] = float("inf")
        else:
            dh_ref, z1 = libleaf.reference_leaf(ref, leaf, start=ref_end)
            out["leaf_dh_nats"] = float(np.max(np.abs(leaf["dh"] - dh_ref)))
            _say("leaf_dh_nats by chain " + _row("+.4f", leaf["dh"] - dh_ref)
                 + "; the reference's dH " + _row("+.4f", dh_ref)
                 + "; the program's " + _row("+.4f", leaf["dh"])
                 + f"; the leaf's position off "
                 f"{float(np.max(np.abs(leaf['z1'] - z1))):.3g}"
                 + ", step sizes " + _row(".3g", leaf["step_size"])
                 + "; inverse mass, smallest and largest over the chains "
                 f"{leaf['inv_mass'].min():.3g} {leaf['inv_mass'].max():.3g}")
    if "leaves_out_of_range" in mine:
        depth, blocks = measured["max_tree_depth"], measured["blocks"]
        spans = {f.get("block"): f for f in measured.get("tree_spans", [])}
        why = [block_out_of_range(spans.get(b["block"], {}), b, chains, depth)
               for b in blocks]
        out["leaves_out_of_range"] = float(sum(w is not None for w in why))
        flat = measured["draws_flat"]
        stayed = int(np.sum(np.all(np.concatenate(
            [flat[:, :1] == start["z"][:, None], flat[:, 1:] == flat[:, :-1]],
            axis=1), axis=2)))
        hist = np.sum([s["tree_depths"] for s in spans.values()
                       if len(s.get("tree_depths", ())) == depth + 1]
                      or [np.zeros(depth + 1, int)], axis=0)
        _say("tree depths of the window "
             + " ".join(f"{k}:{n}" for k, n in enumerate(hist) if k)
             + f"; leaves {sum(s.get('tree_leaves', 0) for s in spans.values())}"
             + f"; lane iterations "
             f"{sum(s.get('lane_iterations', 0) for s in spans.values())}"
             + f"; divergent {sum(s.get('divergent', 0) for s in spans.values())}"
             + f"; stayed {stayed} of {flat.shape[0] * flat.shape[1]}"
             + "".join(f"; block {b['block']}: {w}"
                       for b, w in zip(blocks, why) if w is not None))
    return {k: out[k] for k in wanted}
