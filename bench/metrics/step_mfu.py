"""Model FLOPs the traced window required, over the window times the
chips times the chip's peak. Counted: local SGD of every replica that
really trained (three forward passes per sample) and the eval forward
passes; padded or dead replicas are not model work."""


def read(ctx):
    if ctx.trace.window_s <= 0:
        return None
    flops = ctx.counts.window_flops(ctx.model, ctx.sim, ctx.work)
    return 100.0 * flops / (ctx.trace.window_s * ctx.chips
                            * ctx.peak["flops_per_s"])
