"""Seconds jax reports for backend compilation (on a persistent-cache hit:
for the retrieval) inside the spans of one part of the run
(`params["part"]`): the `compile_s` counter the program adds to the innermost
open span.  None where no span of the part carries the counter's name and the
program has no span log; 0.0 where nothing compiled."""

from lib import spans


def read(ctx, params):
    parts = None if ctx["dry_run"] else spans.program_spans(ctx)
    if parts is None or not parts[params["part"]]:
        return None
    return float(sum(s["fields"].get("compile_s", 0.0)
                     for s in parts[params["part"]]))
