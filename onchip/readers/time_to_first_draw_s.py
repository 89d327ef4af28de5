"""Wall of MAP and the full warm-up from cold chains, programs compiled: call
A's start to its `warmup_done` record, on the harness's clock.  Only a
configuration that runs its full warm-up reports it."""


def read(ctx, params):
    if ctx["dry_run"] or not ctx["full_warmup"]:
        return None
    return ctx["time_to_first_draw_s"]
