"""Backend compilations inside the window that missed the persistent cache
(jax's monitoring events: requests less hits).  Expected 0."""


def read(ctx, params):
    return ctx["compile_requests"] - ctx["compile_hits"]
