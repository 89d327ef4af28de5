"""Check `sampler_state`: how `correct` is decided for a cell that samples one
posterior in blocks.  Numbers compared with limits, each from what the timed
path produced at the timed sizes against the configuration's plain reference
(`references/<config["reference"]>.py`) on rows made again from the seed
(`rows/<config["rows"]["generator"]>.py`).  A cell's file names this check
and lists the numbers it is held to with the limit of each; PERF.md gives the
readings the limits were set from.

  pe_gap, grad_gap   the state the window's last block left on the device
                     (position, potential energy and gradient of every chain,
                     as checkpointed): the reference's potential and gradient
                     at the same positions.  Worst chain; pe as |a-b|/|b|,
                     gradient as |a-b|_2/|b|_2.
  state_draw_gap     the window's last draw of every chain against that state's
                     position: exact.
  frozen_chains      chains whose position at the window's end equals the one
                     at its start: a sampler that hands its state back.
  accept_gap         the accept step: the share of the window's transitions
                     (all chains) whose position moved, against the mean
                     acceptance probability the block records report for the
                     same transitions.  A chain moves with that probability,
                     so the two differ by binomial noise alone.
  mean_z, sd_gap     (flat model, warmed-up chains) the window's draws against
                     the reference's own Laplace posterior: worst coordinate's
                     |mean - mode| / sd, and |sd_draws / sd - 1|.

What the driver hands over (`measured`): `state_start`, `state_end` ({"z",
"pe", "grad"}, chains first), `draws_flat` (chains, draws, ndim: the window's
unconstrained draws), `blocks` (the window's block records).
"""

import numpy as np


def compare(measured, env, wanted):
    """{name: value} for the names in `wanted`.  `env`: config, sizes, seed,
    load (the harness's by-name loader)."""
    config, load = env["config"], env["load"]
    reference = load("references", config["reference"])
    out = {}
    end, start = measured["state_end"], measured["state_start"]
    flat = measured["draws_flat"]
    if "state_draw_gap" in wanted:
        out["state_draw_gap"] = (
            float(np.max(np.abs(flat[:, -1] - end["z"]))) if flat.shape[1]
            else float("inf"))
    if "frozen_chains" in wanted:
        out["frozen_chains"] = float(np.sum(
            np.all(end["z"] == start["z"], axis=1)))
    if "accept_gap" in wanted:
        path = np.concatenate([start["z"][:, None], flat], axis=1)
        moved = np.any(path[:, 1:] != path[:, :-1], axis=2)
        said = [b["mean_accept"] for b in measured["blocks"]]
        out["accept_gap"] = (
            abs(float(moved.mean()) - float(np.mean(said)))
            if moved.size and said else float("inf"))
    rows = load("rows", config["rows"]["generator"]).make(
        config["rows"]["params"], env["sizes"], env["seed"])
    if "pe_gap" in wanted or "grad_gap" in wanted:
        pe, grad = reference.potential_and_grad(rows, end["z"])
        out["pe_gap"] = float(np.max(
            np.abs(end["pe"].astype(np.float64) - pe) / np.abs(pe)))
        diff = np.linalg.norm(end["grad"].astype(np.float64) - grad, axis=1)
        out["grad_gap"] = float(np.max(diff / np.linalg.norm(grad, axis=1)))
    if "mean_z" in wanted or "sd_gap" in wanted:
        mode, sd = reference.laplace(rows)
        pooled = flat.reshape(-1, flat.shape[-1]).astype(np.float64)
        if pooled.shape[0] < 2:
            out["mean_z"] = out["sd_gap"] = float("inf")
        else:
            out["mean_z"] = float(np.max(
                np.abs(pooled.mean(axis=0) - mode) / sd))
            out["sd_gap"] = float(np.max(
                np.abs(pooled.std(axis=0, ddof=1) / sd - 1.0)))
    return {k: out[k] for k in wanted}
