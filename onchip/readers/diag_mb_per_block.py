"""Mean bytes of streaming diagnostics a block hands to the host."""


def read(ctx, params):
    vals = [b["diag_bytes_to_host"] for b in ctx["blocks"]
            if "diag_bytes_to_host" in b]
    return sum(vals) / len(vals) / 1e6 if vals else None
