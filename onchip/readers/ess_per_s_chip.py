"""Smallest bulk ESS over every constrained parameter, of all draws the window
produced, over the whole window's seconds and the chips."""

from lib import window


def read(ctx, params):
    if ctx["dry_run"] or not ctx["full_warmup"]:
        return None
    ess = window.min_bulk_ess(ctx)
    return None if ess is None else ess / ctx["window_s"] / ctx["chips"]
