"""A counter the program writes on its own spans (`telemetry.span` fields):
the largest value of field `params["field"]` over the spans named
`params["name"]` in one part of the run (`params["part"]`: set-up, the window
or collect, `lib/spans.py`).  Nothing where no such span carries the field (a
program from before the counter)."""

from lib import spans


def read(ctx, params):
    parts = None if ctx["dry_run"] else spans.program_spans(ctx)
    if parts is None:
        return None
    values = [s["fields"][params["field"]] for s in parts[params["part"]]
              if s["name"] == params["name"] and params["field"] in s["fields"]]
    return float(max(values)) if values else None
