"""Device idle time of the traced slice while the host was in the program
span `params["span"]`, as a share of the slice: idle gaps of the profiler
trace named by the innermost span of the program's own log that covers them,
the two clocks aligned by the harness's block markers (`lib/spans.py`)."""

from lib import spans


def read(ctx, params):
    dev = ctx["device"]
    if ctx["dry_run"] or not dev.get("window_s"):
        return None
    idle = spans.report(ctx).get("idle")
    if idle is None:
        return None
    return 100.0 * idle["by_span"].get(params["span"], 0.0) / dev["window_s"]
