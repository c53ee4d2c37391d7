"""Device time per epoch of the halo exchange: operations under the
``tier_pull_*`` / ``refresh_ring_*`` scopes and the collective-permutes,
mean over the cell's chips."""


def read(ctx):
    s = ctx.trace.tagged_s(ctx.red, "exchange")
    return 1e3 * s / ctx.epochs if s > 0 else None
