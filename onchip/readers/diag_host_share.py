"""Share of the window the host spent in the gate (`t_diag_s` of the block
records: host clock on host work)."""


def read(ctx, params):
    if ctx["dry_run"] or not ctx["blocks"]:
        return None
    return 100.0 * sum(float(b["t_diag_s"]) for b in ctx["blocks"]) / ctx[
        "window_s"]
