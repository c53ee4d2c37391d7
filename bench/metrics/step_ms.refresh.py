"""Mean wall time of a step that refreshes the cache tiers, from the
program's ``refresh`` and ``pipelined`` step spans."""


def read(ctx):
    st = [ctx.phase_stats[k] for k in ("refresh", "pipelined")
          if k in ctx.phase_stats]
    n = sum(s["count"] for s in st)
    return 1e3 * sum(s["total_s"] for s in st) / n if n else None
