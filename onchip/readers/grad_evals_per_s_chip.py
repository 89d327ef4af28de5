"""Chain-gradients (the program's `block_grad_evals`) of all blocks of the
window over the whole window's seconds and the chips."""

from lib import window


def read(ctx, params):
    if ctx["dry_run"] or not ctx["blocks"]:
        return None
    return window.grads(ctx) / ctx["window_s"] / ctx["chips"]
