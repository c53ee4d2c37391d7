"""Mean wall time of a cached step, from the program's ``cached`` step
spans (``repro.obs.Tracer``, fenced on the loss)."""


def read(ctx):
    st = ctx.phase_stats.get("cached")
    return 1e3 * st["total_s"] / st["count"] if st else None
