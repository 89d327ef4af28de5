"""The fused likelihood kernel's share of its roofline in the traced slice: the
least time its calls can take on this chip (the larger of FLOPs over the bf16
peak and bytes over the HBM peak, both from the shapes by the configuration's
counts, `counts/<config["counts"]>.py`) over the summed device durations of
the kernel's events.  `params["pattern"]` is the kernel's event name in the
trace as it is today."""

from lib import tracered


def read(ctx, params):
    events = ctx.get("trace_events")
    if not events or "peaks" not in ctx:
        return None
    calls, seconds = tracered.kernel_time(events, params["pattern"])
    if not calls or seconds <= 0:
        return None
    counts = ctx["load"]("counts", ctx["config"]["counts"])
    least, bound = counts.least_seconds(
        ctx["sizes"], ctx["chains"], ctx["peaks"])
    ctx["roofline"] = {"calls": calls, "kernel_s": seconds,
                       "least_s_per_call": least, "bound": bound}
    return 100.0 * calls * least / seconds
