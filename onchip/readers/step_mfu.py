"""The whole step's share of the chip's peak: the FLOPs of a chain-gradient
(from the shapes, by the configuration's counts) times the window's
chain-gradients, over the window's seconds, the chips and the bf16 peak.
Bounds a gain once a PR has replaced a kernel."""

from lib import window


def read(ctx, params):
    if "peaks" not in ctx or not ctx["blocks"]:
        return None
    counts = ctx["load"]("counts", ctx["config"]["counts"])
    flops = counts.flops_per_chain_gradient(ctx["sizes"]) * window.grads(ctx)
    return 100.0 * flops / ctx["window_s"] / (
        ctx["chips"] * ctx["peaks"]["flops_bf16"])
