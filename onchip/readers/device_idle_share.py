"""1 - (union of device-operation intervals) / (traced slice), from the
profiler trace."""


def read(ctx, params):
    dev = ctx["device"]
    if "busy_s" not in dev or dev["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - dev["busy_s"] / dev["window_s"])
