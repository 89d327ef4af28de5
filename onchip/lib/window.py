"""Arithmetic over the window that more than one metric shares."""

from . import diag


def grads(ctx):
    """Chain-gradients of all blocks of the window (`block_grad_evals`)."""
    return sum(int(b["block_grad_evals"]) for b in ctx["blocks"])


def min_bulk_ess(ctx):
    """Smallest bulk ESS over every constrained scalar, of all the window's
    draws; None with fewer than 8 draws a chain or a scalar that never moved.
    Worked out once a run."""
    if "min_bulk_ess" not in ctx:
        draws = ctx["draws"]
        n = next(iter(draws.values())).shape[1]
        ess = diag.min_bulk_ess(draws) if n >= 8 else None
        ctx["min_bulk_ess"] = ess if ess is not None and ess == ess else None
    return ctx["min_bulk_ess"]
