"""Seconds of the program's own spans (`telemetry.span`): the spans named
`params["name"]` in one part of the run (`params["part"]`: set-up, the window
or collect, `lib/spans.py`), summed; with `params["until"]`, the time from the
first such span's start to the start of the first span of that name (the
window run's start to its first dispatch: a resume).  Host clock on host
work, measured at the site."""

from lib import spans


def read(ctx, params):
    parts = None if ctx["dry_run"] else spans.program_spans(ctx)
    if parts is None:
        return None
    mine = [s for s in parts[params["part"]] if s["name"] == params["name"]]
    if not mine:
        return None
    if "until" in params:
        then = [s for s in parts[params["part"]]
                if s["name"] == params["until"]]
        return (then[0]["start_ns"] - mine[0]["start_ns"]) / 1e9 \
            if then else None
    return sum(spans.seconds(s) for s in mine)
