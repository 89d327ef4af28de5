"""What the window's trees were, from the counters the per-chain kernels'
block loop writes on its `block.gate` spans (`runner._tree_counters`:
`tree_leaves`, `tree_depths`, `lane_iterations`), summed over the window's
blocks; `params["number"]` says which number:

  leaves_per_draw      gradient evaluations (tree leaves) a transition a chain
  depth_cap_share      % of the window's transitions whose tree ran to the
                       deepest depth the histogram has (`max_tree_depth`)
  nuts_lane_occupancy  % of the vmapped loops' lanes that did work: leaves over
                       chains x lane iterations (the chains run a transition's
                       loops in lockstep until the longest tree has finished)

Counters, so a dry run reports them too.  Nothing from a program without them
(a commit before the counters, or the ensemble sampler)."""

from lib import spans


def read(ctx, params):
    parts = spans.program_spans(ctx)
    if parts is None:
        return None
    gates = [s["fields"] for s in parts["window"]
             if s["name"] == "block.gate" and "tree_leaves" in s["fields"]]
    leaves = sum(g["tree_leaves"] for g in gates)
    if not gates or not leaves:
        return None
    number = params["number"]
    if number == "nuts_lane_occupancy":
        return 100.0 * leaves / (ctx["chains"] * sum(
            g["lane_iterations"] for g in gates))
    depths = [g["tree_depths"] for g in gates if "tree_depths" in g]
    if not depths:
        return None
    transitions = sum(sum(d) for d in depths)
    if number == "leaves_per_draw":
        return leaves / transitions
    if number == "depth_cap_share":
        return 100.0 * sum(d[-1] for d in depths) / transitions
    raise ValueError(f"tree_counters: unknown number {number!r}")
