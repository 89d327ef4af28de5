"""Share of the window the host spent in the spans named in
`params["names"]` (the program's own, `telemetry.span`): their seconds inside
the window over `window_s`."""

from lib import spans


def read(ctx, params):
    parts = None if ctx["dry_run"] else spans.program_spans(ctx)
    if parts is None or not ctx["blocks"]:
        return None
    total = sum(spans.seconds(s) for s in parts["window"]
                if s["name"] in params["names"])
    return 100.0 * total / ctx["window_s"]
