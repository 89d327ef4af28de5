"""Seconds from the window's close (the `budget_exhausted` record) to the
entry's return: the device drains the block the loop had dispatched ahead and
now discards, and the entry lays all draws out once.  Every job pays it once;
it is outside the window of `grad_evals_per_s_chip`."""


def read(ctx, params):
    return None if ctx["dry_run"] else ctx["collect_s"]
