"""A kernel found by its name in the profiler trace (`params["pattern"]`
matches the operations-line event of the kernel's custom call: the Pallas
`name=` is the instruction's name).  `params["what"]`: `ms_per_call`, device
time per call; `outside_share`, the share of the slice's device busy time
spent outside the kernel."""

from lib import tracered


def read(ctx, params):
    events = ctx.get("trace_events")
    if not events:
        return None
    calls, seconds = tracered.kernel_time(events, params["pattern"])
    if not calls:
        return None
    if params["what"] == "ms_per_call":
        return 1e3 * seconds / calls
    busy = tracered.busy(events)
    planes = len(busy["per_plane"])
    return 100.0 * (1.0 - seconds / (busy["busy_s"] * planes))
