"""Process start to the opening of the window: imports, rows, the model's
data layout, rehearsal (compilation on a first run), MAP, warm-up."""


def read(ctx, params):
    return ctx["setup_s"]
