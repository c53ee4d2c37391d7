"""Device time per epoch of the dense transforms (forward and backward):
matmul fusions under the ``layer<i>`` scopes, mean over chips."""


def read(ctx):
    s = ctx.trace.tagged_s(ctx.red, "dense")
    return 1e3 * s / ctx.epochs if s > 0 else None
