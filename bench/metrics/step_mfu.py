"""Whole-step model FLOP utilisation: the FLOPs one epoch's forward and
backward pass need (``bench/work``, no recompute) times the epochs of the
traced window, over the window's seconds times the chips times the peak."""


def read(ctx):
    flops = ctx.work["flops"] * ctx.epochs
    return 100.0 * flops / (ctx.red.window_s * ctx.chips
                            * ctx.peaks["peak_flops_per_s"])
