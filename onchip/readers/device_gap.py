"""Median device time between consecutive executions of the program whose
modules-line event matches `params["pattern"]` (the sampling program: the gap
between blocks), in microseconds, from the profiler trace."""

import statistics

from lib import spans


def read(ctx, params):
    gaps = spans.module_gaps_ns(ctx.get("trace_events") or [],
                                params["pattern"])
    return statistics.median(gaps) / 1e3 if gaps else None
