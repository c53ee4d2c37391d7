"""Device time per epoch of the aggregation (forward and backward):
non-matmul operations under the ``layer<i>`` scopes, mean over chips."""


def read(ctx):
    s = ctx.trace.tagged_s(ctx.red, "aggregation")
    return 1e3 * s / ctx.epochs if s > 0 else None
