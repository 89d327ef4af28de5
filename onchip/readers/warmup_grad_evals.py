"""Chain-gradients of MAP and the warm-up (the `warmup_done` record)."""


def read(ctx, params):
    rec = ctx["warmup_done"] or {}
    if not ctx["full_warmup"] or "warmup_grad_evals" not in rec:
        return None
    return rec["warmup_grad_evals"]
